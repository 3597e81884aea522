"""Mixture-of-Experts / expert parallelism (parallel/moe.py).

The repo's compare-two-implementations pattern (SURVEY §4): the
expert-parallel shard_map path must equal the dense-dispatch reference
run group-by-group, values AND gradients; the post-SPMD HLO must carry
real all-to-alls; routing must respect capacity; and the layer must
train."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.moe import (
    MoEConfig,
    capacity,
    init_moe_params,
    moe_ffn,
    moe_ffn_sharded,
    place_moe_params,
)

D, H, E = 16, 32, 8


def _mesh(n=4):
    devs = jax.devices()[:n]
    return Mesh(np.asarray(devs).reshape(n), ("expert",))


def _setup(top_k=2, T=64, seed=0):
    cfg = MoEConfig(num_experts=E, mlp_dim=H, top_k=top_k,
                    capacity_factor=1.5)
    params = init_moe_params(jax.random.key(seed), D, cfg)
    x = jax.random.normal(jax.random.key(seed + 1), (T, D), jnp.float32)
    return cfg, params, x


def _reference_groups(params, x, cfg, n, cap):
    """The sharded semantics, computed shard-by-shard with the dense path."""
    ys, auxes = [], []
    for xs in jnp.split(x, n, axis=0):
        y, aux = moe_ffn(params, xs, cfg, cap=cap)
        ys.append(y)
        auxes.append(aux)
    return jnp.concatenate(ys, axis=0), jnp.mean(jnp.asarray(auxes))


@pytest.mark.parametrize("top_k", [1, 2])
def test_sharded_equals_dense_groups(top_k):
    cfg, params, x = _setup(top_k)
    mesh = _mesh(4)
    cap = capacity(x.shape[0] // 4, cfg)
    want, want_aux = _reference_groups(params, x, cfg, 4, cap)

    placed = place_moe_params(params, mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P("expert")))
    got, aux = jax.jit(
        lambda p, v: moe_ffn_sharded(p, v, cfg, mesh))(placed, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(want_aux), rtol=1e-5)


def test_sharded_gradients_equal_dense():
    cfg, params, x = _setup(top_k=2)
    mesh = _mesh(4)
    cap = capacity(x.shape[0] // 4, cfg)

    def loss_sharded(p, v):
        y, aux = moe_ffn_sharded(p, v, cfg, mesh)
        return jnp.sum(y ** 2) + cfg.aux_loss_weight * aux

    def loss_ref(p, v):
        y, aux = _reference_groups(p, v, cfg, 4, cap)
        return jnp.sum(y ** 2) + cfg.aux_loss_weight * aux

    placed = place_moe_params(params, mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P("expert")))
    g_sh = jax.jit(jax.grad(loss_sharded))(placed, xs)
    g_ref = jax.jit(jax.grad(loss_ref))(params, x)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_sh[k]), np.asarray(g_ref[k]), rtol=2e-4, atol=2e-5,
            err_msg=k)


def test_all_to_all_in_hlo():
    cfg, params, x = _setup(top_k=2)
    mesh = _mesh(4)
    placed = place_moe_params(params, mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P("expert")))
    txt = (jax.jit(lambda p, v: moe_ffn_sharded(p, v, cfg, mesh))
           .lower(placed, xs).compile().as_text())
    assert txt.count("all-to-all") >= 2, "expected dispatch+return all2all"


def test_capacity_drops_overflow_tokens():
    # one expert, capacity 2 of 8 tokens: exactly the first 2 tokens in
    # group order survive, the rest emit zeros (dropped-token semantics)
    cfg = MoEConfig(num_experts=1, mlp_dim=H, top_k=1, capacity_factor=1.0)
    params = init_moe_params(jax.random.key(0), D, cfg)
    x = jax.random.normal(jax.random.key(1), (8, D), jnp.float32)
    y, _ = moe_ffn(params, x, cfg, cap=2)
    y = np.asarray(y)
    assert np.abs(y[:2]).sum() > 0
    np.testing.assert_allclose(y[2:], 0.0, atol=1e-7)


def test_top2_combine_weights_renormalize():
    cfg, params, x = _setup(top_k=2, T=32)
    from paddle_tpu.parallel.moe import route

    dispatch, combine, aux = route(x, params["wg"], cfg,
                                   capacity(32, cfg))
    s = np.asarray(combine.sum(axis=(1, 2)))
    # tokens with both choices kept sum to 1; dropped-one tokens < 1
    assert np.all(s <= 1.0 + 1e-5)
    assert (s > 0.99).mean() > 0.5
    assert float(aux) >= 1.0 - 1e-5  # Switch aux floor at uniform load


def test_moe_transformer_dp_ep_trains():
    """Flagship integration: MoE-LM train step on a {data, expert} mesh —
    loss finite and decreasing, all_to_alls present in the compiled HLO."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    devs = jax.devices()[:8]
    mesh = Mesh(np.asarray(devs).reshape(2, 4), ("data", "expert"))
    cfg = T.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=32,
        max_seq_len=32, remat=False, moe_experts=8, moe_top_k=2)
    params = T.place_params(T.init_params(cfg, jax.random.key(0)), mesh, cfg)
    opt = Adam(learning_rate=1e-2)
    state = opt.init_tree(params)
    step = T.build_train_step(cfg, opt, mesh=mesh)
    ids = jax.device_put(
        jnp.asarray(np.random.default_rng(0).integers(0, 64, (8, 17))),
        NamedSharding(mesh, P("data", None)))

    txt = step.lower(params, state, ids).compile().as_text()
    assert "all-to-all" in txt

    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, ids)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_transformer_dp_ep_tp_trains():
    """Three parallelism axes in ONE mesh: batch over data, experts over
    expert (all_to_all), attention/embedding weights Megatron-sharded
    over model — the composition story, not just pairwise."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    devs = jax.devices()[:8]
    mesh = Mesh(np.asarray(devs).reshape(2, 2, 2),
                ("data", "expert", "model"))
    cfg = T.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=32,
        max_seq_len=32, remat=False, moe_experts=4, moe_top_k=2)
    params = T.place_params(T.init_params(cfg, jax.random.key(0)), mesh, cfg)
    opt = Adam(learning_rate=1e-2)
    state = opt.init_tree(params)
    step = T.build_train_step(cfg, opt, mesh=mesh, zero1=True)
    ids = jax.device_put(
        jnp.asarray(np.random.default_rng(0).integers(0, 64, (8, 17))),
        NamedSharding(mesh, P("data", None)))
    txt = step.lower(params, state, ids).compile().as_text()
    assert "all-to-all" in txt
    losses = []
    for _ in range(6):
        params, state, loss = step(params, state, ids)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_moe_transformer_dense_path_trains():
    """moe_experts without a mesh: dense dispatch single-device path."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    cfg = T.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=32,
        max_seq_len=32, remat=False, moe_experts=4, moe_top_k=1)
    params = T.init_params(cfg, jax.random.key(0))
    opt = Adam(learning_rate=1e-2)
    state = opt.init_tree(params)
    step = T.build_train_step(cfg, opt)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 17)))
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, ids)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_layer_trains():
    cfg, params, x = _setup(top_k=2, T=64)
    tgt = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    @jax.jit
    def step(p):
        def loss_fn(p):
            y, aux = moe_ffn(p, x, cfg)
            return jnp.mean((y - tgt) ** 2) + cfg.aux_loss_weight * aux

        l, g = jax.jit(jax.value_and_grad(loss_fn))(p)
        return jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g), l

    losses = []
    # 60 steps: the init draw differs across jax PRNG streams, and at 30
    # steps the slowest observed stream sits right on the 0.7 threshold
    # (0.72 on jax 0.4.37 cpu); convergence, not speed, is the claim
    for _ in range(60):
        params, l = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


@pytest.mark.parametrize("top_k", [1, 2])
def test_sort_dispatch_equals_einsum(top_k):
    """dispatch='sort' (scatter/gather by slot id) reproduces the dense
    one-hot einsum path exactly: same routing, same outputs, same
    gradients."""
    import dataclasses

    cfg_e, params, x = _setup(top_k)
    cfg_s = dataclasses.replace(cfg_e, dispatch="sort")

    def loss(cfg):
        def f(p, v):
            y, aux = moe_ffn(p, v, cfg)
            return jnp.sum(y ** 2) + cfg.aux_loss_weight * aux
        return f

    y_e, aux_e = jax.jit(lambda p, v: moe_ffn(p, v, cfg_e))(params, x)
    y_s, aux_s = jax.jit(lambda p, v: moe_ffn(p, v, cfg_s))(params, x)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)

    g_e = jax.jit(jax.grad(loss(cfg_e)))(params, x)
    g_s = jax.jit(jax.grad(loss(cfg_s)))(params, x)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_s[k]), np.asarray(g_e[k]),
                                   rtol=2e-4, atol=2e-6, err_msg=k)


def test_sort_dispatch_sharded_equals_einsum_sharded():
    import dataclasses

    cfg_e, params, x = _setup(top_k=2)
    cfg_s = dataclasses.replace(cfg_e, dispatch="sort")
    mesh = _mesh(4)
    placed = place_moe_params(params, mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P("expert")))
    y_e, aux_e = jax.jit(
        lambda p, v: moe_ffn_sharded(p, v, cfg_e, mesh))(placed, xs)
    y_s, aux_s = jax.jit(
        lambda p, v: moe_ffn_sharded(p, v, cfg_s, mesh))(placed, xs)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)
