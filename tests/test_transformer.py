"""Transformer LM: single-device correctness, attn-impl equivalence, and the
full 3-axis (data x seq x model) sharded train step on the virtual mesh."""

import collections
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models import transformer as T
from paddle_tpu.optimizer import Adam
from paddle_tpu.parallel import zero
from paddle_tpu.parallel.pipeline import pipeline_apply


def _cfg(**kw):
    base = dict(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=32,
        max_seq_len=32, remat=False,
    )
    base.update(kw)
    return T.TransformerConfig(**base)


def _forward(cfg):
    """``T.forward`` under ``cfg``, compiled: eagerly the layer scan and
    every op around it dispatch (and compile) one by one."""
    return jax.jit(functools.partial(T.forward, cfg))


def test_forward_shapes_and_loss():
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)))
    logits = _forward(cfg)(params, ids)
    assert logits.shape == (2, 16, 64)
    loss = jax.jit(functools.partial(T.loss_fn, cfg))(params, ids)
    assert np.isfinite(float(loss))
    assert float(loss) < 2 * np.log(64)


def test_attn_impls_agree():
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)))
    ref = _forward(cfg)(params, ids)
    blk = _forward(
        dataclasses.replace(cfg, attn_impl="blockwise", attn_block_size=4))(
        params, ids)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref), atol=1e-4)
    flash = _forward(dataclasses.replace(cfg, attn_impl="flash"))(params, ids)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(ref), atol=1e-4)


def test_train_step_learns():
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.key(0))
    opt = Adam(learning_rate=1e-2)
    state = opt.init_tree(params)
    step = T.build_train_step(cfg, opt)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 16)))
    losses = []
    for _ in range(10):
        params, state, loss = step(params, state, ids)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8


def test_sharded_train_step_dp_tp_sp():
    """2x2x2 mesh: batch over data, sequence over seq (ring attention),
    weights over model — the full 3D parallel train step."""
    devs = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("data", "seq", "model"))
    cfg = _cfg(attn_impl="ring")
    params = T.init_params(cfg, jax.random.key(0))
    params = T.place_params(params, mesh, cfg)
    opt = Adam(learning_rate=1e-2)
    state = opt.init_tree(params)
    step = T.build_train_step(cfg, opt, mesh=mesh)

    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 17)))
    # tokens: ids[:, :-1] has T=16 -> sharded 2-way over seq
    ids = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    l0 = None
    for _ in range(5):
        params, state, loss = step(params, state, ids)
        if l0 is None:
            l0 = float(loss)
    assert np.isfinite(float(loss)) and float(loss) < l0

    # sharded result == single-device result (first step loss)
    cfg1 = _cfg()
    params1 = T.init_params(cfg1, jax.random.key(0))
    ids1 = jnp.asarray(np.asarray(ids))
    loss1 = float(jax.jit(functools.partial(T.loss_fn, cfg1))(params1, ids1))
    np.testing.assert_allclose(l0, loss1, atol=1e-3)


@functools.lru_cache(maxsize=None)
def _compiled_collectives(name):
    """Collective ops in the compiled train step of one mesh plan, counted
    from ``compiled.as_text()`` (async pairs once, by their -start)."""
    if name == "pp4":
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("pipe",))
        w = (jnp.full((4, 16, 16), .1), jnp.zeros((4, 16)))
        x = jnp.ones((32, 16))

        def loss_fn(params):
            return jnp.mean(pipeline_apply(
                lambda p, h: jnp.tanh(h @ p[0] + p[1]), params, x,
                n_microbatches=4, mesh=mesh) ** 2)

        text = jax.jit(jax.grad(loss_fn)).lower(w).compile().as_text()
    else:
        axes, zero_stage = {
            "dp8": ({"data": 8}, 0), "tp8": ({"model": 8}, 0),
            "dp2_sp2_tp2": ({"data": 2, "seq": 2, "model": 2}, 0),
            "dp8_zero2": ({"data": 8}, 2)}[name]
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(
            tuple(axes.values())), tuple(axes))
        cfg = T.TransformerConfig(
            vocab_size=256, num_layers=1, num_heads=4, embed_dim=16,
            mlp_dim=64, max_seq_len=16, remat=False,
            attn_impl="ring" if "seq" in axes else "exact")
        params = T.place_params(T.init_params(cfg, jax.random.key(0)),
                                mesh, cfg)
        opt = Adam(learning_rate=1e-4)
        state = opt.init_tree(params)
        if zero_stage:
            state = zero.shard_opt_state(
                state, params, mesh, param_specs=T.param_shardings(cfg))
        step = T.build_train_step(cfg, opt, mesh=mesh, zero=zero_stage)
        ids = np.random.default_rng(0).integers(
            0, 256, (axes.get("data", 1), 17))
        ids = jax.device_put(jnp.asarray(ids), NamedSharding(
            mesh, P("data" if "data" in axes else None, None)))
        text = step.lower(params, state, ids).compile().as_text()
    return collections.Counter(re.findall(
        r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all)(?:-start)?\(", text))


@pytest.mark.parametrize("name,ops,more_than", [
    ("dp8", ["all-reduce"], None),          # the gradient all-reduce
    ("tp8", ["all-reduce"], "dp8"),         # + per-layer activation sums
    ("dp2_sp2_tp2", ["collective-permute"], None),  # the attention ring
    ("pp4", ["collective-permute"], None),  # the stage hand-offs
    ("dp8_zero2", ["reduce-scatter", "all-gather"], None),  # ZeRO-2's swap
])
def test_compiled_train_step_collectives_per_mesh(name, ops, more_than):
    """The collective inventory each sharding must leave in the compiled
    train step (program-text counts: an op in a loop body counts once)."""
    census = _compiled_collectives(name)
    for one in ops:
        floor = _compiled_collectives(more_than)[one] if more_than else 0
        assert census[one] > floor, (name, dict(census))


def test_sharded_forward_flash_dp_tp():
    """flash kernel per-device under shard_map on a data x model mesh."""
    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("data", "model"))
    cfg = _cfg(attn_impl="flash")
    params = T.init_params(cfg, jax.random.key(0))
    params = T.place_params(params, mesh, cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 16)))
    ids = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    logits = jax.jit(lambda p, i: T.forward(cfg, p, i, mesh=mesh))(params, ids)
    ref = _forward(_cfg())(params, jnp.asarray(np.asarray(ids)))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=1e-4)
