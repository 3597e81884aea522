"""TPP fused microkernel layer (ops/pallas/tpp): interpret-mode parity of
every kernel against its in-module jnp reference (forward AND gradients),
flag-routing semantics, the fused conv+BN+ReLU layer node, and the
ZeRO-2 fused shard update's bit-identical trajectory."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import flags
from paddle_tpu.ops.pallas import tpp


@pytest.fixture
def flag_snapshot():
    snap = flags.snapshot_raw()
    yield
    flags.restore_raw(snap)


# -- brgemm -------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_brgemm_matches_reference(rng_np, dtype):
    a = jnp.asarray(rng_np.normal(size=(3, 17, 9)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng_np.normal(size=(3, 9, 21)).astype(np.float32)).astype(dtype)
    ref = tpp.brgemm_reference(a, b)
    ker = tpp.brgemm(a, b, impl="kernel", interpret=True)
    assert ker.dtype == ref.dtype == dtype
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(ker, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_brgemm_epilogue_and_stats(rng_np):
    a = jnp.asarray(rng_np.normal(size=(2, 30, 12)).astype(np.float32))
    b = jnp.asarray(rng_np.normal(size=(2, 12, 7)).astype(np.float32))
    sc = jnp.asarray(rng_np.normal(size=(7,)).astype(np.float32))
    sh = jnp.asarray(rng_np.normal(size=(7,)).astype(np.float32))
    ref, rs, rss = tpp.brgemm_reference(a, b, scale=sc, shift=sh,
                                        act="relu", stats=True)
    ker, ks, kss = tpp.brgemm(a, b, scale=sc, shift=sh, act="relu",
                              stats=True, impl="kernel", interpret=True)
    assert float(jnp.min(ker)) >= 0.0  # relu applied
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)
    # stats are of the PRE-epilogue accumulator (row/col padding excluded)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ks),
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(np.asarray(rss), np.asarray(kss),
                               rtol=2e-5, atol=2e-3)


# -- channel stats ------------------------------------------------------------


def test_channel_stats_matches_reference_fwd_and_grad(rng_np):
    x = jnp.asarray(rng_np.normal(size=(3, 5, 6, 7)).astype(np.float32))
    rs, rss = tpp.channel_stats_reference(x)
    ks, kss = tpp.channel_stats(x, "kernel", True)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ks), atol=1e-5)
    np.testing.assert_allclose(np.asarray(rss), np.asarray(kss), atol=1e-5)

    def loss_r(x):
        s, ss = tpp.channel_stats_reference(x)
        return jnp.sum(s * 0.5) + jnp.sum(ss * 0.25)

    def loss_k(x):
        s, ss = tpp.channel_stats(x, "kernel", True)
        return jnp.sum(s * 0.5) + jnp.sum(ss * 0.25)

    np.testing.assert_allclose(np.asarray(jax.jit(jax.grad(loss_r))(x)),
                               np.asarray(jax.jit(jax.grad(loss_k))(x)),
                               rtol=2e-5, atol=2e-5)


# -- direct conv --------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    (3, 1, 1),   # the ResNet 3x3
    (3, 2, 1),   # strided 3x3
    (1, 1, 0),   # 1x1 -> the brgemm fast path
    (1, 2, 0),   # strided 1x1 (downsample projection)
    (7, 2, 3),   # the stem conv
])
def test_conv2d_direct_matches_reference(rng_np, cfg):
    k, s, p = cfg
    x = jnp.asarray(rng_np.normal(size=(2, 13, 14, 5)).astype(np.float32))
    w = jnp.asarray(rng_np.normal(size=(k, k, 5, 9)).astype(np.float32) * .3)
    ref = tpp.conv2d_direct_reference(x, w, stride=s, padding=p)
    ker = tpp.conv2d_direct(x, w, stride=s, padding=p, impl="kernel",
                            interpret=True)
    assert ker.shape == ref.shape
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) ** 2)

    gr = jax.jit(jax.grad(loss(lambda x, w: tpp.conv2d_direct_reference(
        x, w, stride=s, padding=p)), argnums=(0, 1)))(x, w)
    gk = jax.jit(jax.grad(loss(lambda x, w: tpp.conv2d_direct(
        x, w, stride=s, padding=p, impl="kernel", interpret=True)),
        argnums=(0, 1)))(x, w)
    for a, b in zip(gr, gk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# -- fused conv + BN + act ----------------------------------------------------


@pytest.mark.parametrize("is_train", [True, False])
def test_conv2d_bn_act_matches_reference(rng_np, is_train):
    x = jnp.asarray(rng_np.normal(size=(2, 10, 11, 4)).astype(np.float32))
    w = jnp.asarray(rng_np.normal(size=(3, 3, 4, 8)).astype(np.float32) * .3)
    ga = jnp.asarray(rng_np.normal(size=(8,)).astype(np.float32) * .2 + 1)
    be = jnp.asarray(rng_np.normal(size=(8,)).astype(np.float32) * .2)
    rm = jnp.asarray(rng_np.normal(size=(8,)).astype(np.float32) * .1)
    rv = jnp.asarray(np.abs(rng_np.normal(size=(8,)).astype(np.float32)) + .5)

    def run(impl):
        return tpp.conv2d_bn_act(x, w, ga, be, rm, rv, is_train, stride=2,
                                 padding=1, act="relu", impl=impl,
                                 interpret=True)

    ref, ker = run("reference"), run("kernel")
    for a, b in zip(ref, ker):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    def loss(impl):
        def f(x, w, ga, be):
            y, nm, nv = tpp.conv2d_bn_act(
                x, w, ga, be, rm, rv, is_train, stride=2, padding=1,
                act="relu", impl=impl, interpret=True)
            return jnp.sum(y ** 2) + jnp.sum(nm) + 0.5 * jnp.sum(nv)
        return f

    grads = lambda impl: jax.jit(jax.grad(loss(impl), argnums=(0, 1, 2, 3)))
    gr = grads("reference")(x, w, ga, be)
    gk = grads("kernel")(x, w, ga, be)
    for a, b in zip(gr, gk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_conv2d_bn_act_reference_equals_unfused_composition(rng_np):
    """The reference IS the separate conv2d -> batch_norm -> relu chain —
    bit-identical, the bench ablation's CPU contract."""
    from paddle_tpu.ops import nn

    x = jnp.asarray(rng_np.normal(size=(2, 8, 9, 3)).astype(np.float32))
    w = jnp.asarray(rng_np.normal(size=(3, 3, 3, 6)).astype(np.float32))
    ga, be = jnp.ones((6,)), jnp.zeros((6,))
    rm, rv = jnp.zeros((6,)), jnp.ones((6,))
    y1, nm1, nv1 = tpp.conv2d_bn_act_reference(
        x, w, ga, be, rm, rv, True, stride=1, padding=1, act="relu")
    yc = nn.conv2d_xla(x, w, stride=1, padding=1)
    y2, nm2, nv2 = nn.batch_norm(yc, ga, be, rm, rv, is_train=True,
                                 use_fused_stats=False)
    y2 = jax.nn.relu(y2)
    assert bool(jnp.all(y1 == y2))
    assert bool(jnp.all(nm1 == nm2)) and bool(jnp.all(nv1 == nv2))


# -- flag routing -------------------------------------------------------------


def test_fused_enabled_flag_semantics(flag_snapshot):
    flags.set("fused_kernels", "on")
    assert tpp.fused_enabled() is True
    flags.set("fused_kernels", "off")
    assert tpp.fused_enabled() is False
    flags.set("fused_kernels", "auto")
    assert tpp.fused_enabled() is (jax.default_backend() == "tpu")


def test_nn_conv2d_routes_through_tpp_when_forced(rng_np, flag_snapshot,
                                                  monkeypatch):
    """Flag on -> ops/nn.conv2d dispatches eligible shapes to the tpp
    entry; on CPU that entry resolves to the reference, so values are
    bit-equal to the unfused lowering."""
    import paddle_tpu.ops.nn as nn
    import jax as jax_mod

    x = jnp.asarray(rng_np.normal(size=(2, 8, 8, 3)).astype(np.float32))
    w = jnp.asarray(rng_np.normal(size=(3, 3, 3, 4)).astype(np.float32))
    base = nn.conv2d_xla(x, w, stride=1, padding=1)

    calls = {"direct": 0}

    def counting(x, w, stride=1, padding=0, **k):
        calls["direct"] += 1
        # the faked-TPU backend can't run a compiled kernel on CPU; the
        # dispatch decision is what's under test, so answer via the oracle
        return tpp.conv2d_direct_reference(x, w, stride=stride,
                                           padding=padding)

    flags.set("fused_kernels", "on")
    # flag on over CPU: dispatch requires a TPU backend, stays on XLA
    y = nn.conv2d(x, w, stride=1, padding=1)
    assert bool(jnp.all(y == base))
    # pretend TPU: the dispatcher must route to the tpp entry (whose
    # reference path reproduces the XLA values exactly)
    monkeypatch.setattr(jax_mod, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tpp, "conv2d_direct", counting)
    try:
        y2 = nn.conv2d(x, w, stride=1, padding=1)
    finally:
        monkeypatch.undo()
    assert calls["direct"] == 1
    # groups/dilation stay on the XLA lowering regardless
    flags.set("fused_kernels", "on")
    yd = nn.depthwise_conv2d(x, jnp.ones((3, 3, 1, 3)), padding=1)
    assert yd.shape == (2, 8, 8, 3)


# -- fused optimizer update ---------------------------------------------------


@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_momentum_update_matches_reference(rng_np, nesterov):
    p = jnp.asarray(rng_np.normal(size=(37, 53)).astype(np.float32))
    g = jnp.asarray(rng_np.normal(size=(37, 53)).astype(np.float32))
    v = jnp.asarray(rng_np.normal(size=(37, 53)).astype(np.float32))
    ref = tpp.fused_momentum_update_reference(p, g, v, 0.1, 0.9,
                                              nesterov=nesterov,
                                              weight_decay=0.01)
    ker = tpp.fused_momentum_update(p, g, v, jnp.float32(0.1),
                                    jnp.float32(0.9), nesterov=nesterov,
                                    weight_decay=0.01, impl="kernel",
                                    interpret=True)
    for a, b in zip(ref, ker):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_fused_sgd_update_matches_reference(rng_np):
    p = jnp.asarray(rng_np.normal(size=(130,)).astype(np.float32))
    g = jnp.asarray(rng_np.normal(size=(130,)).astype(np.float32))
    ref = tpp.fused_sgd_update_reference(p, g, 0.05, weight_decay=0.02)
    ker = tpp.fused_sgd_update(p, g, jnp.float32(0.05), weight_decay=0.02,
                               impl="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker), atol=2e-6)


def test_fused_update_reference_bit_equals_optimizer_apply(rng_np):
    """The reference replicates Optimizer.apply op for op — bit-equal, so
    the fused ZeRO-2 path cannot drift from the unfused trainer."""
    from paddle_tpu.core.parameters import ParamSpec
    from paddle_tpu.optimizer import Momentum

    p = jnp.asarray(rng_np.normal(size=(24, 16)).astype(np.float32))
    g = jnp.asarray(rng_np.normal(size=(24, 16)).astype(np.float32))
    v = jnp.asarray(rng_np.normal(size=(24, 16)).astype(np.float32))
    opt = Momentum(momentum=0.9, learning_rate=0.1)
    specs = {"w": ParamSpec(name="w", shape=p.shape, initializer=None)}
    state = opt.init({"w": p}, specs)
    state["slots"]["w"]["velocity"] = v
    new_p, new_s = opt.apply({"w": g}, {"w": p}, state, specs)
    lr = opt.lr_fn(state["step"]) * specs["w"].learning_rate
    wd = (specs["w"].decay_rate
          if specs["w"].decay_rate is not None else opt.l2_rate) or 0.0
    fp, fv = tpp.fused_momentum_update_reference(p, g, v, lr, 0.9,
                                                 weight_decay=wd)
    assert bool(jnp.all(new_p["w"] == fp))
    assert bool(jnp.all(new_s["slots"]["w"]["velocity"] == fv))


def test_zero2_fused_shard_update_trajectory_bit_identical(flag_snapshot):
    """4 ZeRO-2 steps on the forced-8-device mesh: the fused shard update
    (flag on) must reproduce the unfused optimizer.apply trajectory
    bit for bit, and must actually be taken (fused_shard_apply used)."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core import rng as prng
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel import zero as Z
    from paddle_tpu.trainer.step import build_train_step

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU platform")

    in_dim, classes = 32, 8
    rngn = np.random.default_rng(3)
    feeds = [{"x": jnp.asarray(rngn.normal(size=(16, in_dim)).astype(np.float32)),
              "y": jnp.asarray(rngn.integers(0, classes, size=(16,)))}
             for _ in range(4)]

    def cost():
        x = layer.data(name="x", type=data_type.dense_vector(in_dim))
        h = layer.fc(input=x, size=64, act=act.ReluActivation())
        pred = layer.fc(input=h, size=classes, act=act.SoftmaxActivation())
        lab = layer.data(name="y", type=data_type.integer_value(classes))
        return layer.classification_cost(input=pred, label=lab)

    def train(fused):
        flags.set("fused_kernels", "on" if fused else "off")
        base.reset_name_counters()
        prng.seed(7)
        topo = Topology(cost())
        mesh = mesh_mod.MeshContext(mesh=mesh_mod.make_mesh({"data": 8}))
        params = {k: jnp.array(v) for k, v in
                  paddle.parameters.create(topo).as_dict().items()}
        opt = Momentum(momentum=0.9, learning_rate=1e-2)
        specs = {s.name: s for s in topo.param_specs()}
        opt_state = opt.init(params, specs)
        states = topo.init_states()
        params = mesh.place_params(params, specs)
        states = mesh.replicate(states)
        opt_state = Z.shard_opt_state(opt_state, params, mesh.mesh)
        step = build_train_step(topo, opt, mesh=mesh, zero=2)
        key = jax.random.key(0)
        for feed in feeds:
            params, opt_state, states, c, _ = step(
                params, opt_state, states, mesh.shard_batch(feed), key)
        return {k: np.asarray(v) for k, v in params.items()}, float(c)

    p_off, c_off = train(False)
    p_on, c_on = train(True)
    assert c_off == c_on
    for k in p_off:
        assert np.array_equal(p_off[k], p_on[k]), k


def test_fused_shard_apply_declines_ineligible_configs():
    """Adam / model-average / clipping configs must fall back (None)."""
    from paddle_tpu.optimizer import Adam, Momentum

    assert tpp.fused_shard_apply(
        Adam(), {}, {}, {"step": 0, "slots": {}}, {}, None, {}) is None
    clip = Momentum(momentum=0.9, gradient_clipping_threshold=1.0)
    assert tpp.fused_shard_apply(
        clip, {}, {}, {"step": 0, "slots": {}}, {}, None, {}) is None


# -- the fused layer node -----------------------------------------------------


def test_img_conv_bn_layer_matches_separate_layers(rng_np):
    """layer.img_conv_bn == img_conv(no bias, linear) -> batch_norm(relu)
    on identical weights: same forward, same running stats, same grads."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    h = w = 8
    x = rng_np.normal(size=(4, 3 * h * w)).astype(np.float32)

    def build(fused):
        base.reset_name_counters()
        img = layer.data(name="image",
                         type=data_type.dense_vector(3 * h * w, channels=3),
                         height=h, width=w)
        if fused:
            out = layer.img_conv_bn(name="blk", input=img, filter_size=3,
                                    num_filters=6, num_channels=3, padding=1,
                                    act=act.ReluActivation())
        else:
            tmp = layer.img_conv(name="blk_conv", input=img, filter_size=3,
                                 num_channels=3, num_filters=6, padding=1,
                                 act=act.LinearActivation(), bias_attr=False)
            out = layer.batch_norm(name="blk_bn", input=tmp,
                                   act=act.ReluActivation())
        topo = Topology(out)
        params = paddle.parameters.create(topo).as_dict()
        return topo, params, out.name

    topo_f, params_f, name_f = build(True)
    topo_u, params_u, name_u = build(False)
    # identical parameter census (the checkpoint-compat contract)
    assert sorted(params_f) == sorted(params_u)
    shared = {k: jnp.asarray(rng_np.normal(size=v.shape).astype(np.float32))
              for k, v in params_f.items()}
    states_f, states_u = topo_f.init_states(), topo_u.init_states()
    assert sorted(states_f) == sorted(states_u)

    vf, sf = topo_f.forward(shared, states_f, {"image": x}, True,
                            jax.random.key(0))
    vu, su = topo_u.forward(shared, states_u, {"image": x}, True,
                            jax.random.key(0))
    np.testing.assert_allclose(np.asarray(vf[name_f]),
                               np.asarray(vu[name_u]), atol=1e-6)
    for k in sf:
        np.testing.assert_allclose(np.asarray(sf[k]), np.asarray(su[k]),
                                   atol=1e-6)

    def loss(topo, name, states):
        def f(p):
            v, _ = topo.forward(p, states, {"image": x}, True,
                                jax.random.key(0))
            return jnp.sum(v[name] ** 2)
        return f

    gf = jax.jit(jax.grad(loss(topo_f, name_f, states_f)))(shared)
    gu = jax.jit(jax.grad(loss(topo_u, name_u, states_u)))(shared)
    for k in gf:
        np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gu[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)
