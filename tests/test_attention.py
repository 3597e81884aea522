"""Attention numerics: blockwise == exact, ring == exact (on the 8-device
virtual mesh), the flash kernel under a block-causal mask (interpret mode),
plus gradient agreement — the compare-two-implementations pattern of the
reference's test_matrixCompare/Compare2Function harnesses.  Gradients are
differentiated, then compiled: the eager tape dispatches op by op."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu.ops.pallas.flash_attention  # noqa: F401 (the module)
from paddle_tpu.ops import attention as A

# the package re-exports the function under the module's name
FA = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
BL = 4      # the block length of the block-causal cases


def _grads(loss):
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _qkv(b=2, t=32, h=4, d=8, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


def test_blockwise_matches_exact():
    q, k, v = _qkv()
    ref = A.dot_product_attention(q, k, v)
    out = A.blockwise_attention(q, k, v, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blockwise_causal_matches_exact():
    q, k, v = _qkv(t=33)  # non-divisible by block
    mask = A.causal_mask(33, 33)
    ref = A.dot_product_attention(q, k, v, mask=mask)
    out = A.blockwise_attention(q, k, v, block_size=8, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blockwise_grads_match():
    q, k, v = _qkv(t=16)

    def loss_exact(q, k, v):
        return jnp.sum(A.dot_product_attention(q, k, v) ** 2)

    def loss_block(q, k, v):
        return jnp.sum(A.blockwise_attention(q, k, v, block_size=4) ** 2)

    g_ref = _grads(loss_exact)(q, k, v)
    g_out = _grads(loss_block)(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_exact(causal):
    q, k, v = _qkv(b=2, t=32, h=2, d=4)
    mask = A.causal_mask(32, 32) if causal else None
    ref = A.dot_product_attention(q, k, v, mask=mask)

    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("seq",))
    out = A.attention_with_sequence_parallel(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_grads_match():
    q, k, v = _qkv(b=1, t=16, h=2, d=4)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("seq",))

    def loss_ring(q, k, v):
        return jnp.sum(
            A.attention_with_sequence_parallel(q, k, v, mesh, causal=True) ** 2
        )

    def loss_exact(q, k, v):
        m = A.causal_mask(16, 16)
        return jnp.sum(A.dot_product_attention(q, k, v, mask=m) ** 2)

    g_ring = _grads(loss_ring)(q, k, v)
    g_ref = _grads(loss_exact)(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_mha_shapes_and_causal():
    b, t, e, hds = 2, 10, 16, 16
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(b, t, e)).astype(np.float32))
    w = lambda m, n: jnp.asarray(r.normal(size=(m, n)).astype(np.float32) * 0.1)
    out = A.multi_head_attention(
        x, x, w(e, hds), w(e, hds), w(e, hds), w(hds, e), num_heads=4, causal=True
    )
    assert out.shape == (b, t, e)
    # causal: early positions unaffected by corrupting later positions
    wq, wk, wv, wo = w(e, hds), w(e, hds), w(e, hds), w(hds, e)
    o1 = A.multi_head_attention(x, x, wq, wk, wv, wo, num_heads=4, causal=True)
    o2 = A.multi_head_attention(
        x.at[:, 5:, :].set(123.0), x.at[:, 5:, :].set(123.0),
        wq, wk, wv, wo, num_heads=4, causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(o1[:, :5]), np.asarray(o2[:, :5]), atol=1e-5
    )


def test_collectives_surface():
    from paddle_tpu.parallel import collective as C

    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("data",))
    x = jnp.arange(8.0).reshape(4, 2)

    def body(x):
        s = C.all_reduce(x, "data")
        g = C.all_gather(x, "data")
        b = C.broadcast(x, "data", root=2)
        r = C.ring_shift(x, "data")
        return s, g, b, r

    fn = C.on_mesh(mesh, body, in_specs=(P("data"),),
                   out_specs=(P("data"), P("data"), P("data"), P("data")))
    s, g, b, r = fn(x)
    np.testing.assert_allclose(np.asarray(s)[0], x.sum(0))  # every shard = total
    assert np.asarray(g).shape == (16, 2)
    np.testing.assert_allclose(np.asarray(b)[0], np.asarray(x)[2])
    np.testing.assert_allclose(np.asarray(r)[1], np.asarray(x)[0])


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_exact(causal):
    """Ulysses (all_to_all seq<->head re-sharding) is exact: equals plain
    full-sequence attention, both maskings."""
    q, k, v = _qkv(b=2, t=32, h=4, d=4)
    mask = A.causal_mask(32, 32) if causal else None
    ref = A.dot_product_attention(q, k, v, mask=mask)

    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("seq",))
    out = A.attention_with_ulysses(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_attention_grads_match():
    q, k, v = _qkv(b=1, t=16, h=4, d=4)
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("seq",))

    def loss_u(q, k, v):
        return jnp.sum(
            A.attention_with_ulysses(q, k, v, mesh, causal=True) ** 2)

    def loss_exact(q, k, v):
        m = A.causal_mask(16, 16)
        return jnp.sum(A.dot_product_attention(q, k, v, mask=m) ** 2)

    g_u = _grads(loss_u)(q, k, v)
    g_ref = _grads(loss_exact)(q, k, v)
    for a, b in zip(g_u, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ulysses_rejects_indivisible_heads():
    import pytest as _pytest

    q, k, v = _qkv(b=1, t=16, h=2, d=4)  # 2 heads on a 4-way seq axis
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ("seq",))
    with _pytest.raises(ValueError, match="not divisible"):
        A.attention_with_ulysses(q, k, v, mesh, causal=True)


def test_ulysses_transformer_trains_on_dp_sp_mesh():
    """attn_impl='ulysses' through the LM train step on {data, seq}."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    devs = jax.devices()[:8]
    mesh = Mesh(np.asarray(devs).reshape(2, 4), ("data", "seq"))
    cfg = T.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, embed_dim=16, mlp_dim=32,
        max_seq_len=32, remat=False, attn_impl="ulysses")
    params = T.place_params(T.init_params(cfg, jax.random.key(0)), mesh, cfg)
    opt = Adam(learning_rate=1e-2)
    state = opt.init_tree(params)
    step = T.build_train_step(cfg, opt, mesh=mesh)
    ids = jax.device_put(
        jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 17))),
        NamedSharding(mesh, P("data", None)))
    txt = step.lower(params, state, ids).compile().as_text()
    assert "all-to-all" in txt
    losses = []
    for _ in range(6):
        params, state, loss = step(params, state, ids)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# -- the flash kernel's block-causal mask -------------------------------------------
# ``causal`` = a block length: a position sees its block and the blocks
# before it (generation by blocks, tests/test_block_lm.py).


def _flash(causal, bq):
    return jax.jit(lambda q, k, v: FA.flash_attention(
        q, k, v, causal, None, bq, bq, True))


@pytest.mark.parametrize("t,bq", [(24, 8), (40, 16), (12, 1024)])
def test_flash_forward_under_the_block_causal_mask(t, bq):
    """Interpret mode, several tiles and one: the mask at block
    granularity against the jnp mask, tiles above the block diagonal
    skipped; and ``causal=1`` is ``causal=True`` bit for bit."""
    k1, k2, k3 = jax.random.split(jax.random.key(t), 3)
    q, k, v = (jax.random.normal(kk, (2, t, 2, 8)) for kk in (k1, k2, k3))
    got = _flash(BL, bq)(q, k, v)
    blk = jnp.arange(t) // BL
    want = A.dot_product_attention(
        q, k, v, mask=(blk[:, None] >= blk[None, :])[None, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(FA.flash_attention_reference(q, k, v, BL)),
        np.asarray(want), atol=2e-5)
    one = _flash(1, bq)(q, k, v)
    true = _flash(True, bq)(q, k, v)
    assert np.array_equal(np.asarray(one), np.asarray(true))
    assert float(jnp.max(jnp.abs(true - got))) > 1e-3


def test_flash_backward_under_the_block_causal_mask():
    k1, k2, k3 = jax.random.split(jax.random.key(9), 3)
    q, k, v = (jax.random.normal(kk, (1, 24, 2, 8)) for kk in (k1, k2, k3))
    blk = jnp.arange(24) // BL
    mask = (blk[:, None] >= blk[None, :])[None, None]
    f = lambda q, k, v: jnp.sum(FA.flash_attention(
        q, k, v, BL, None, 8, 8, True) ** 2)
    g = lambda q, k, v: jnp.sum(A.dot_product_attention(
        q, k, v, mask=mask) ** 2)
    for a, b in zip(_grads(f)(q, k, v), _grads(g)(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
