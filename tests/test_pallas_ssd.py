"""The one-token Mamba-2 recurrence against the state pool as a kernel
(``ops/pallas/ssd.py``, interpret mode on CPU) against its plain-XLA twin
``mamba2.ssd_step``: float32 both, so they differ by a fused multiply-add
and the order of a 128-term sum — ``TOL`` = 1e-5 relative for ``y`` and
the new state.  What the pool must NOT show is held to the bit: every
other layer's row, an idle slot's state, a pool under ``dt = 0``.
``tests/test_ssm_lm.py`` and ``tests/test_hybrid_lm.py`` serve through it.

Summed seconds (the tier-1 command in this sandbox): 58; every case jits
its call, an interpreted kernel compiles in about a second.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import mamba2
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import ssd as kernel

TOL = 1e-5
L, B, H, P, N = 3, 4, 16, 8, 128


@functools.partial(jax.jit, static_argnames=("groups", "seed", "n"))
def _inputs(groups, seed=0, n=N):
    ks = jax.random.split(jax.random.key(seed), 7)
    pool = jax.random.normal(ks[0], (L, B, H, P, n))
    x = jax.random.normal(ks[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H)))
    a = -jnp.exp(jax.random.normal(ks[3], (H,)))
    b = jax.random.normal(ks[4], (B, groups, n))
    c = jax.random.normal(ks[5], (B, groups, n))
    d = jax.random.normal(ks[6], (H,))
    return pool, (x, dt, a, b, c, d)


@functools.cache
def _step(impl, head_block=None):
    """(pool, row, *args, live) -> (y, pool) by ``impl``, compiled once a
    shape: the routed entry, or the kernel at a block of heads."""
    if head_block is None:
        return jax.jit(functools.partial(mamba2.ssd_pool_step, impl=impl))
    return jax.jit(functools.partial(kernel.ssd_pool_step, interpret=True,
                                     head_block=head_block))


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL * np.abs(want).max(initial=0))


ALL = jnp.ones((B,), bool)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_a_static_row_equals_ssd_step(groups):
    pool, args = _inputs(groups)
    y, new = _step("kernel")(pool, 1, *args, ALL)
    y_want, s_want = mamba2.ssd_step(pool[1], *args)
    _close(y, y_want)
    _close(new[1], s_want)
    for other in (0, 2):            # only row 1 may change
        assert np.array_equal(new[other], pool[other])


@pytest.mark.parametrize("groups", [1, 8])
def test_a_traced_row_equals_ssd_step(groups):
    """Inside a ``lax.scan`` over two rows, the pool its carry — the
    rolled walk's arrangement."""
    pool, args = _inputs(groups, seed=1)

    def walk(impl):
        def layer(pool, row):
            y, pool = mamba2.ssd_pool_step(pool, row, *args, ALL, impl=impl)
            return pool, y
        return jax.jit(lambda pool: lax.scan(layer, pool,
                                             jnp.array([2, 0])))(pool)

    (new, y), (new_want, y_want) = walk("kernel"), walk("reference")
    _close(y, y_want)
    _close(new, new_want)
    assert np.array_equal(new[1], pool[1])
    assert not np.array_equal(new[0], pool[0])


@pytest.mark.parametrize("live", [[True, False, True, True],
                                  [False, False, False, True],
                                  [False] * 4])
def test_idle_rows_keep_their_state_to_the_bit(live):
    """Live and idle rows mixed: an idle slot's state is the bits it was,
    a negative zero among them; a live one's is ``ssd_step``'s."""
    pool, args = _inputs(1, seed=2)
    pool = pool.at[1, :, 0, 0, :4].set(-0.0)
    live = jnp.array(live)
    y, new = _step("kernel")(pool, 1, *args, live)
    y_want, s_want = mamba2.ssd_step(pool[1], *args)
    bits = lambda v: np.asarray(v).view(np.uint32)
    idle = ~np.asarray(live)
    assert np.array_equal(bits(new[1])[idle], bits(pool[1])[idle])
    _close(np.asarray(new[1])[~idle], np.asarray(s_want)[~idle])
    _close(np.asarray(y)[~idle], np.asarray(y_want)[~idle])


def test_dt_zero_leaves_the_pool_untouched():
    """``ssd_step``'s contract: ``dt = 0`` decays nothing and adds
    nothing, on live rows too."""
    pool, (x, dt, *rest) = _inputs(8, seed=3)
    y, new = _step("kernel")(pool, 2, x, jnp.zeros_like(dt), *rest, ALL)
    assert np.array_equal(new, pool)
    _close(y, mamba2.ssd_step(pool[2], x, jnp.zeros_like(dt), *rest)[0])


@pytest.mark.parametrize("head_block", [16, 8])
def test_every_block_of_heads_gives_the_same(head_block):
    pool, args = _inputs(2, seed=4)
    live = jnp.array([True, True, False, True])
    y, new = _step("kernel", head_block)(pool, 0, *args, live)
    y_want, new_want = jax.jit(kernel.ssd_pool_step_reference)(
        pool, 0, *args, live)
    _close(y, y_want)
    _close(new, new_want)


def test_no_block_of_three_heads():
    pool, args = _inputs(1)
    with pytest.raises(ValueError, match="no blocks of 3 of 16 heads"):
        kernel.ssd_pool_step(pool, 0, *args, ALL, interpret=True,
                             head_block=3)


@pytest.mark.parametrize("shape, route", [
    ((H, P, 128, 1), "kernel"), ((H, 16, 256, 8), "kernel"),
    ((12, P, 128, 1), "kernel"),    # heads that are no whole sublanes: one block
    ((H, P, 64, 1), "reference_shape"), ((H, P, 192, 1), "reference_shape"),
    ((H, 12, 128, 1), "reference_shape")])
def test_supports_and_the_census(shape, route):
    """A state that is no whole lanes (or rows that are no whole
    sublanes) runs ``ssd_step``, and the census says so."""
    heads, p, n, groups = shape
    assert kernel.supports(*shape) == (route == "kernel")
    ks = jax.random.split(jax.random.key(5), 2)
    pool = jax.random.normal(ks[0], (2, 2, heads, p, n))
    x, dt = jnp.ones((2, heads, p)), jnp.full((2, heads), 0.5)
    a, d = -jnp.ones((heads,)), jnp.ones((heads,))
    bc = jax.random.normal(ks[1], (2, groups, n))
    with pallas.capture_routes() as routes:
        y, new = jax.jit(functools.partial(
            mamba2.ssd_pool_step, impl="kernel"))(
            pool, 1, x, dt, a, bc, bc, d, jnp.ones((2,), bool))
    assert routes == {("ssd_step", route): 1}
    y_want, s_want = mamba2.ssd_step(pool[1], x, dt, a, bc, bc, d)
    _close(y, y_want)
    _close(new[1], s_want)


def test_groups_divide_the_heads_and_the_decays_fit_smem():
    assert not kernel.supports(H, P, 128, 3)
    assert not kernel.supports(H, P, 128, 0)
    assert kernel.supports(128, P, 128, 1, slots=512)
    assert not kernel.supports(128, P, 128, 1, slots=1024)


def test_auto_is_the_plain_form_off_a_tpu():
    pool, args = _inputs(1)
    with pallas.capture_routes() as routes:
        mamba2.ssd_pool_step(pool, 0, *args, ALL)
    assert routes == {("ssd_step", "reference"): 1}
