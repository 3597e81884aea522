"""The gated delta rule (``ops/kda.py``) and its chunk kernel
(``ops/pallas/kda.py``, interpret mode on CPU).  The plain chunked rule at
a toy head size against the one-token recurrence ``kda_step`` and the scan
of ``benchmarks/references/solar_open2.py``; the kernel against its
plain-XLA twin (``kda_prefill(impl="reference")``) AND the recurrence, the
same cases at the kernel's head size (128, sub-blocks of 16: it takes no
other), to the same tolerance.  What a kernel case is for is its id.
``tests/test_kda_lm.py`` holds the whole mixer to its reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import kda
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import kda as kernel

import lm_toy

TOL = 1e-4
D = kernel.HEAD_DIM


@functools.cache
def _prefill(chunk, impl="reference"):
    """``kda_prefill`` at one chunk length, compiled once a shape."""
    return jax.jit(functools.partial(kda.kda_prefill, chunk=chunk, impl=impl))


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("seed", "decay", "beta_shift"))
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


# -- the plain chunked rule, at a toy head size -----------------------------------


@pytest.fixture(scope="module")
def ref():
    return lm_toy.load_reference("solar_open2")


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("h", "d", "seed", "decay", "beta_shift"))
def _rule_inputs(t, h=3, d=16, seed=0, decay=None, beta_shift=0.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (3, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (3, t, h, d)))
    v = jax.random.normal(ks[2], (3, t, h, d))
    g = -jnp.exp(jax.random.normal(ks[3], (3, t, h, d)) * 1.5 - 2.0)
    if decay is not None:     # a stretch whose decay underflows a chunk
        g = g.at[:, 20:20 + decay[0]].set(decay[1])
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (3, t, h))
                              + beta_shift)
    return q, k, v, g, beta


@pytest.mark.parametrize("t, lens, chunk, decay, beta_shift", [
    (64, (64, 64, 64), 64, None, 0.0),          # whole chunks
    (150, (150, 77, 1), 64, None, 0.0),         # ragged, not a multiple
    (150, (150, 77, 1), 16, None, 0.0),
    (100, (100, 33, 64), 32, None, 0.0),
    (150, (150, 90, 40), 64, (70, -12.0), 0.0),     # exp(-840) inside a chunk
    (150, (150, 90, 40), 64, (9, -60.0), 0.0),      # and inside a sub-block
    (90, (90, 17, 64), 64, None, 3.0),          # beta near 2
    (40, (0, 40, 3), 16, None, 0.0),            # a slack row
])
def test_chunked_rule_equals_the_step_and_the_references_scan(
        ref, t, lens, chunk, decay, beta_shift):
    """``kda_prefill`` over right-padded rows = ``kda_step`` token by
    token = the reference's scan, outputs at every valid position and the
    state at each row's LAST VALID token; padding neither decays nor
    writes."""
    q, k, v, g, beta = _rule_inputs(t, decay=decay, beta_shift=beta_shift)
    if beta_shift:
        assert float(beta.max()) > 1.9
    o, s = _prefill(chunk)(q, k, v, g, beta, jnp.asarray(lens))
    assert o.dtype == s.dtype == jnp.float32 and bool(jnp.isfinite(o).all())
    # the one-token step, all rows at once over the padded length
    outs, at_last = _token_by_token(q, k, v, g, beta, jnp.asarray(lens),
                                    jnp.zeros_like(s))
    for b, n in enumerate(lens):
        if not n:
            assert float(jnp.abs(s[b]).max()) == 0.0
            continue
        np.testing.assert_allclose(np.asarray(s[b]), np.asarray(at_last[b]),
                                   atol=TOL, rtol=TOL)
        np.testing.assert_allclose(np.asarray(o[b, :n]),
                                   np.asarray(outs[b, :n]),
                                   atol=TOL, rtol=TOL)
        with jax.default_matmul_precision("highest"):
            want, s_want = ref.delta_rule(q[b, :n], k[b, :n], v[b, :n],
                                          g[b, :n], beta[b, :n])
        np.testing.assert_allclose(np.asarray(o[b, :n]), np.asarray(want),
                                   atol=TOL, rtol=TOL)
        np.testing.assert_allclose(np.asarray(s[b]), np.asarray(s_want),
                                   atol=TOL, rtol=TOL)


def test_a_chunk_starts_from_the_state_it_is_given():
    q, k, v, g, beta = _rule_inputs(96, seed=3)
    whole, s_whole = _prefill(32)(q, k, v, g, beta)
    cut = lambda x, a, b: x[:, a:b]
    _, s0 = _prefill(16)(*(cut(x, 0, 40) for x in (q, k, v, g, beta)))
    tail, s1 = _prefill(16)(*(cut(x, 40, 96) for x in (q, k, v, g, beta)),
                            state=s0)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(whole[:, 40:]),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s_whole),
                               atol=TOL, rtol=TOL)
    with pytest.raises(ValueError, match="multiple of 16"):
        kda.kda_prefill(q, k, v, g, beta, chunk=24)


def test_the_rule_in_bf16_keeps_a_float32_state():
    q, k, v, g, beta = _rule_inputs(70, seed=4)
    want, s_want = _prefill(32)(q, k, v, g, beta)
    low = lambda x: x.astype(jnp.bfloat16)
    o, s = _prefill(32)(low(q), low(k), low(v), g, beta)
    assert o.dtype == s.dtype == jnp.float32
    assert 1e-4 < float(jnp.abs(o - want).max()) < 0.1
    assert float(jnp.abs(s - s_want).max()) < 0.1


# -- the kernel, at its head size --------------------------------------------------


# The lengths are what each property needs: a ragged last chunk a length
# that is no multiple of the chunk; a decay that underflows starts at
# position 20 and must END inside both rows (150 and 90).
@pytest.mark.parametrize("t, lens, h, chunk, decay, beta_shift, start", [
    (64, (64, 64), 2, 64, None, 0.0, False),
    # a full row, a row ending mid-chunk, a row shorter than a chunk
    (150, (150, 77, 1), 2, 64, None, 0.0, False),
    (100, (100, 33), 3, 32, None, 0.0, False),
    (70, (70, 17), 2, 16, None, 0.0, False),
    (100, (100, 50), 2, 48, None, 0.0, False),
    (150, (150, 90), 2, 64, (70, -12.0), 0.0, False),   # exp(-840)
    (150, (150, 90), 2, 64, (9, -60.0), 0.0, False),
    (90, (90, 64), 2, 64, None, 3.0, False),
    (100, (100, 40), 2, 64, None, 0.0, True),
    (40, (0, 40), 4, 16, None, 0.0, True),
], ids=["whole_chunks_two_rows", "ragged_last_chunk_and_a_row_under_a_chunk",
        "three_heads_chunk_32", "one_sub_block_a_chunk",
        "three_sub_blocks_a_chunk", "decay_underflows_a_chunk",
        "decay_underflows_a_sub_block", "beta_near_2",
        "a_state_to_start_from", "a_slack_row_keeps_its_state"])
def test_kernel_equals_the_twin_and_the_step(t, lens, h, chunk, decay,
                                             beta_shift, start):
    x, state = _inputs(len(lens), t, h, decay=decay, beta_shift=beta_shift)
    if beta_shift:
        assert float(x[4].max()) > 1.9
    state = state if start else None
    lens_ = jnp.asarray(lens)
    got = _prefill(chunk, "kernel")(*x, lens_, state=state)
    _agree(got, _prefill(chunk)(*x, lens_, state=state), lens)
    zeros = jnp.zeros((len(lens), h, D, D))
    _agree(got, _token_by_token(*x, lens_, zeros if state is None else state),
           lens)


def test_a_head_count_the_group_does_not_divide():
    x, state = _inputs(2, 48, 3, seed=2)
    want = jax.jit(lambda *a: kernel.kda_chunk_prefill_reference(
        *a, 16, state))(*x)
    got = jax.jit(lambda *a: kernel.kda_chunk_prefill(
        *a, 16, state, interpret=True, group=2))(*x)
    _agree(got, want, (48, 48))


def test_the_kernel_in_bf16_keeps_a_float32_state():
    """bf16 q, k, v: the kernel rounds where the twin rounds (the operands
    of the three products against the float32 state), so the two differ by
    roundings that flipped, far inside what bf16 costs either of them."""
    x, state = _inputs(2, 150, 2, seed=4)
    lens = jnp.asarray((150, 77))
    full = _prefill(64)(*x, lens, state=state)
    low = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    o, s = _prefill(64, "kernel")(*low, lens, state=state)
    o_twin, s_twin = _prefill(64)(*low, lens, state=state)
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

    # the gradient compiled: it is the route that is held, not the tape
    got = jax.jit(jax.grad(lambda *a: loss("kernel", *a),
                           argnums=(0, 1, 2, 3, 4)))(*x)
    want = jax.jit(jax.grad(lambda *a: loss("reference", *a),
                            argnums=(0, 1, 2, 3, 4)))(*x)
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
