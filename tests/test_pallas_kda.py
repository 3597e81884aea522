"""The KDA chunk kernel (``ops/pallas/kda.py``, interpret mode on CPU)
against its plain-XLA twin (``kda_prefill(impl="reference")``) AND against
the one-token recurrence ``kda_step`` run token by token — the cases of
``test_kda_lm.py::test_chunked_rule_equals_the_step_and_the_references_scan``
at the kernel's head size, to that test's tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import kda
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import kda as kernel

TOL = 1e-4
D = kernel.HEAD_DIM


def _inputs(rows, t, h, seed=0, decay=None, beta_shift=0.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (rows, t, h, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, t, h, D)))
    v = jax.random.normal(ks[2], (rows, t, h, D))
    g = -jnp.exp(jax.random.normal(ks[3], (rows, t, h, D)) * 1.5 - 2.0)
    if decay is not None:     # a stretch whose decay underflows a chunk
        g = g.at[:, 20:20 + decay[0]].set(decay[1])
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, t, h))
                              + beta_shift)
    state = jax.random.normal(ks[5], (rows, h, D, D))
    return (q, k, v, g, beta), state


@jax.jit
def _token_by_token(q, k, v, g, beta, lens, state):
    """``kda_step`` over the padded length: every position's output and
    the state right after each row's last valid token."""
    def step(carry, x):
        i, st, last = carry
        o, st = kda.kda_step(st, *x)
        last = jnp.where((i == lens - 1)[:, None, None, None], st, last)
        return (i + 1, st, last), o

    (_, _, last), o = lax.scan(
        step, (0, state, state),
        tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def _agree(got, want, lens, tol=TOL):
    (o, s), (o_want, s_want) = got, want
    assert o.dtype == s.dtype == jnp.float32 and bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want),
                               atol=tol, rtol=tol)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(o[b, :n]),
                                   np.asarray(o_want[b, :n]),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("t, lens, h, chunk, decay, beta_shift, start", [
    (64, (64, 64), 2, 64, None, 0.0, False),        # whole chunks, two rows
    # a full row, a row ending mid-chunk, a row shorter than a chunk
    (150, (150, 77, 1), 2, 64, None, 0.0, False),
    (100, (100, 33), 3, 32, None, 0.0, False),
    (70, (70, 17), 2, 16, None, 0.0, False),
    (100, (100, 50), 2, 48, None, 0.0, False),      # three sub-blocks a chunk
    (150, (150, 90), 2, 64, (70, -12.0), 0.0, False),   # exp(-840) a chunk
    (150, (150, 90), 2, 64, (9, -60.0), 0.0, False),    # and a sub-block
    (90, (90, 64), 2, 64, None, 3.0, False),        # beta near 2
    (100, (100, 40), 2, 64, None, 0.0, True),       # a state to start from
    (40, (0, 40), 4, 16, None, 0.0, True),          # a slack row keeps it
])
def test_kernel_equals_the_twin_and_the_step(t, lens, h, chunk, decay,
                                             beta_shift, start):
    x, state = _inputs(len(lens), t, h, decay=decay, beta_shift=beta_shift)
    if beta_shift:
        assert float(x[4].max()) > 1.9
    state = state if start else None
    lens_ = jnp.asarray(lens)
    got = kda.kda_prefill(*x, lens_, chunk=chunk, state=state, impl="kernel")
    _agree(got, kda.kda_prefill(*x, lens_, chunk=chunk, state=state,
                                impl="reference"), lens)
    zeros = jnp.zeros((len(lens), h, D, D))
    _agree(got, _token_by_token(*x, lens_, zeros if state is None else state),
           lens)


def test_a_head_count_the_group_does_not_divide():
    x, state = _inputs(2, 48, 3, seed=2)
    want = kernel.kda_chunk_prefill_reference(*x, 16, state)
    got = kernel.kda_chunk_prefill(*x, 16, state, interpret=True, group=2)
    _agree(got, want, (48, 48))


def test_the_kernel_in_bf16_keeps_a_float32_state():
    """bf16 q, k, v: the kernel rounds where the twin rounds (the operands
    of the three products against the float32 state), so the two differ by
    roundings that flipped, far inside what bf16 costs either of them."""
    x, state = _inputs(2, 150, 2, seed=4)
    lens = jnp.asarray((150, 77))
    full = kda.kda_prefill(*x, lens, chunk=64, state=state, impl="reference")
    low = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    o, s = kda.kda_prefill(*low, lens, chunk=64, state=state, impl="kernel")
    o_twin, s_twin = kda.kda_prefill(*low, lens, chunk=64, state=state,
                                     impl="reference")
    assert o.dtype == s.dtype == jnp.float32
    cost = float(jnp.abs(o_twin - full[0])[0].max())
    assert 1e-4 < cost < 0.3
    assert float(jnp.abs(o - o_twin)[0].max()) < 0.25 * cost
    assert float(jnp.abs(o - full[0])[0].max()) < 1.25 * cost
    assert float(jnp.abs(s - s_twin).max()) < 0.05


def test_the_kernel_differentiates_as_the_twin_does():
    """The kernel has no backward pass of its own: under ``jax.grad`` its
    route runs the kernel forward and the plain form's autodiff backward,
    so a ``K`` layer trains on a TPU as it did."""
    x, state = _inputs(1, 32, 2, seed=6)
    weights = jax.random.normal(jax.random.key(7), (1, 32, 2, D))

    def loss(impl, *a):
        o, s = kda.kda_prefill(*a, jnp.asarray((20,)), chunk=16, state=state,
                               impl=impl)
        return jnp.sum(o * weights) + jnp.sum(s * s)

    got = jax.grad(lambda *a: loss("kernel", *a), argnums=(0, 1, 2, 3, 4))(*x)
    want = jax.grad(lambda *a: loss("reference", *a),
                    argnums=(0, 1, 2, 3, 4))(*x)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=TOL, rtol=TOL)


def test_float32_products_are_six_pass_and_only_the_state_sees_bf16():
    """Interpret mode computes every product in float32 whatever it asks
    of the chip, so the precision is held on the kernel's text: every
    product with float32 operands asks for ``Precision.HIGHEST`` (Mosaic's
    ``fp32`` contract precision; its default is one bf16 pass), and with
    bf16 inputs exactly the four products against the state and ``u`` take
    bf16 operands."""
    (q, k, v, g, beta), _ = _inputs(1, 32, 2)
    low = lambda a: a.astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: kernel.kda_chunk_prefill(
        *a, 16, interpret=True))(low(q), low(k), low(v), g, beta)

    def dots(j):
        for e in j.eqns:
            if e.primitive.name == "dot_general":
                yield e
            for p in e.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    yield from dots(inner)

    found = list(dots(jaxpr.jaxpr))
    narrow = [e for e in found if e.invars[0].aval.dtype == jnp.bfloat16]
    assert len(narrow) == 4 and len(found) >= 10
    for e in found:
        if e not in narrow:
            assert all(v.aval.dtype == jnp.float32 for v in e.invars)
            assert e.params["precision"] == (lax.Precision.HIGHEST,) * 2


# -- routing ----------------------------------------------------------------


def test_routes_off_a_tpu_and_on_a_shape_the_kernel_does_not_take():
    (q, k, v, g, beta), _ = _inputs(1, 32, 2)
    with pallas.capture_routes() as routes:
        kda.kda_prefill(q, k, v, g, beta, chunk=16)
    assert routes == {("kda_prefill", "reference"): 1}
    # heads of 64: the kernel is asked for, the twin runs and says so
    narrow = tuple(a[..., :64] for a in (q, k, v, g))
    with pallas.capture_routes() as routes:
        o, _ = kda.kda_prefill(*narrow, beta, chunk=16, impl="kernel")
    assert routes == {("kda_prefill", "reference_shape"): 1}
    want, _ = kda.kda_prefill(*narrow, beta, chunk=16, impl="reference")
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
    with pallas.capture_routes() as routes:
        kda.kda_prefill(q, k, v, g, beta, chunk=16, impl="kernel")
    assert routes == {("kda_prefill", "kernel"): 1}
    with pytest.raises(ValueError, match="impl must be"):
        kda.kda_prefill(q, k, v, g, beta, chunk=16, impl="fused")
