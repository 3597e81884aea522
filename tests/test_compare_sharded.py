"""Sharded-vs-local convergence-equality tests — the reference's strongest
correctness pattern, rebuilt for the mesh world:

- ``test_CompareTwoNets.cpp:50,107,170-177``: two setups training the same
  model must produce identical gradients/parameters.  Here: the SAME model
  trained with trainer_count=1 (no mesh) vs an 8-device data-parallel mesh
  at the same global batch must end with equal parameters.
- ``test_CompareSparse.cpp:48-67,140``: multi-trainer sparse-embedding
  training vs local must produce equal parameter tables.  Here: the CTR
  wide&deep sparse-gather path on the 8-device mesh vs local.
- ``test_NetworkCompare.cpp`` + ``concat_dotmul_a.conf``/``_b.conf``: two
  differently-written configs computing the same function must produce
  identical outputs and gradients.  Here: the literal reference config
  files are parsed and executed (skipped if the reference checkout is
  absent).

All runs use f32 compute so the only divergence source is cross-device
reduction order (tolerance 1e-5).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.config.topology import Topology
from paddle_tpu.core.lod import SequenceBatch
from paddle_tpu.layers import activation as act
from paddle_tpu.layers import api as layer
from paddle_tpu.layers import base, data_type
from paddle_tpu.optimizer import AdaGrad, Momentum
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.trainer.step import build_train_step

REF = "/root/reference"


def _train(topo, opt, params, feeds, mesh=None):
    """Run len(feeds) steps; returns final params dict (host numpy)."""
    # the jitted step donates params/opt_state/states; copy so the caller's
    # arrays survive for the second run
    params = {k: jnp.array(v) for k, v in params.items()}
    specs = {s.name: s for s in topo.param_specs()}
    opt_state = opt.init(params, specs)
    states = topo.init_states()
    if mesh is not None:
        params = mesh.place_params(params, specs)
        opt_state = mesh.replicate(opt_state)
        states = mesh.replicate(states)
    step = build_train_step(topo, opt, mesh=mesh)
    key = jax.random.key(0)
    for feed in feeds:
        if mesh is not None:
            feed = mesh.shard_batch(feed)
        params, opt_state, states, cost, _ = step(
            params, opt_state, states, feed, key)
    assert np.isfinite(float(cost))
    return {k: np.asarray(v) for k, v in params.items()}


def _mlp_cost(in_dim=24, classes=4):
    img = layer.data(name="x", type=data_type.dense_vector(in_dim))
    h = layer.fc(input=img, size=32, act=act.ReluActivation())
    h = layer.fc(input=h, size=16, act=act.TanhActivation())
    predict = layer.fc(input=h, size=classes, act=act.SoftmaxActivation())
    lab = layer.data(name="y", type=data_type.integer_value(classes))
    return layer.classification_cost(input=predict, label=lab)


def test_dp8_parameters_equal_local():
    """trainer_count=1 vs 8-way DP at the same global batch -> same params
    (test_CompareTwoNets analog on the virtual mesh)."""
    rng = np.random.default_rng(3)
    in_dim, classes, bs, steps = 24, 4, 32, 5
    feeds = [
        {"x": jnp.asarray(rng.normal(size=(bs, in_dim)).astype(np.float32)),
         "y": jnp.asarray(rng.integers(0, classes, size=(bs,)))}
        for _ in range(steps)
    ]

    base.reset_name_counters()
    topo = Topology(_mlp_cost(in_dim, classes))
    params0 = paddle.parameters.create(topo).as_dict()
    opt = Momentum(momentum=0.9, learning_rate=0.05)

    local = _train(topo, opt, dict(params0), feeds)

    ctx = mesh_mod.MeshContext(mesh=mesh_mod.make_mesh({"data": 8}))
    sharded = _train(topo, opt, dict(params0), feeds, mesh=ctx)

    assert local.keys() == sharded.keys()
    for name in local:
        np.testing.assert_allclose(
            local[name], sharded[name], rtol=2e-5, atol=2e-5,
            err_msg=f"parameter {name} diverged between local and 8-way DP")


def test_sparse_ctr_dp_equals_local():
    """Sparse-embedding CTR trained sharded vs local -> equal tables
    (test_CompareSparse.cpp:140 analog)."""
    from paddle_tpu.models.ctr import wide_and_deep_ctr

    rng = np.random.default_rng(5)
    vocabs, wide_dim, bs, steps = [64] * 3, 128, 32, 4

    def make_feed():
        feed = {"label": jnp.asarray(rng.integers(0, 2, size=(bs,)))}
        wide = np.zeros((bs, wide_dim), np.float32)
        for r in range(bs):
            wide[r, rng.integers(0, wide_dim, size=3)] = 1.0
        feed["wide_input"] = jnp.asarray(wide)
        for i, v in enumerate(vocabs):
            feed[f"cat_{i}"] = jnp.asarray(rng.integers(0, v, size=(bs,)))
        return feed

    feeds = [make_feed() for _ in range(steps)]

    base.reset_name_counters()
    cost, _, _ = wide_and_deep_ctr(
        wide_dim=wide_dim, categorical_vocab_sizes=vocabs,
        embedding_size=8, hidden_sizes=(16,))
    topo = Topology(cost)
    params0 = paddle.parameters.create(topo).as_dict()
    opt = AdaGrad(learning_rate=0.05)

    local = _train(topo, opt, dict(params0), feeds)
    ctx = mesh_mod.MeshContext(mesh=mesh_mod.make_mesh({"data": 8}))
    sharded = _train(topo, opt, dict(params0), feeds, mesh=ctx)

    emb_names = [n for n in local if "emb" in n.lower()] or list(local)
    for name in local:
        np.testing.assert_allclose(
            local[name], sharded[name], rtol=3e-5, atol=3e-5,
            err_msg=f"CTR parameter {name} diverged (sparse path)")
    assert emb_names, "expected embedding tables in the CTR model"


@pytest.mark.skipif(not os.path.isdir(REF), reason="reference checkout absent")
@pytest.mark.parametrize("pair", ["concat_dotmul", "concat_fullmatrix"])
def test_network_compare_reference_configs(pair):
    """Two equivalent reference configs -> identical outputs and input
    gradients (test_NetworkCompare.cpp analog, executing the reference's own
    concat_*_a.conf / concat_*_b.conf)."""
    from paddle_tpu.trainer.config_parser import parse_config

    confs = [
        os.path.join(REF, "paddle/gserver/tests", f"{pair}_{s}.conf")
        for s in ("a", "b")
    ]
    if not all(os.path.isfile(c) for c in confs):
        pytest.skip("reference confs missing")

    outs, grads, shapes = [], [], []
    rng = np.random.default_rng(11)
    x = None

    for conf in confs:
        base.reset_name_counters()
        parsed = parse_config(conf, "")
        topo = Topology(parsed.output_layers())
        if x is None:
            in_dim = topo.data_layers()["input"].attrs["dim"]
            x = rng.normal(size=(4, in_dim)).astype(np.float32) * 0.1
        specs = list(topo.param_specs())
        # deterministic identical init by creation order: the a/b configs
        # declare the same parameters in the same data-flow order
        params = {}
        for i, s in enumerate(specs):
            r = np.random.default_rng(100 + i)
            params[s.name] = jnp.asarray(
                r.normal(size=s.shape).astype(np.float32) * 0.05)
        shapes.append([tuple(s.shape) for s in specs])
        states = topo.init_states()
        out_name = topo.outputs[0].name

        def fwd(params, x):
            values, _ = topo.forward(
                params, states, {"input": jnp.asarray(x)}, False,
                jax.random.key(0))
            return values[out_name]

        out = np.asarray(fwd(params, x))
        g = jax.jit(jax.grad(
            lambda p: jnp.sum(jnp.cos(fwd(p, x)))))(params)
        outs.append(out)
        grads.append({i: np.asarray(g[s.name])
                      for i, s in enumerate(specs)})

    assert shapes[0] == shapes[1], (
        "a/b configs declare different parameter shapes")
    np.testing.assert_allclose(
        outs[0], outs[1], rtol=1e-6, atol=1e-6,
        err_msg=f"{pair}: outputs differ between equivalent configs")
    for i in grads[0]:
        np.testing.assert_allclose(
            grads[0][i], grads[1][i], rtol=1e-6, atol=1e-6,
            err_msg=f"{pair}: gradient {i} differs between equivalent configs")


def test_transformer_tp_dp_parameters_equal_local():
    """Flagship-model CompareTwoNets: the SAME transformer trained 3 steps
    on a 2x2 {data, model} mesh (Megatron TP + DP) vs unsharded must end
    with equal parameters — the full train-step (fwd+bwd+Adam) sharding
    invariance, not just a first-step loss check."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    cfg = T.TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                              embed_dim=16, mlp_dim=32, max_seq_len=32,
                              remat=False, attn_impl="exact")
    params0 = T.init_params(cfg, jax.random.key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 17)))

    def run(mesh):
        params = jax.tree.map(jnp.array, params0)
        if mesh is not None:
            params = T.place_params(params, mesh, cfg)
            ids_d = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
        else:
            ids_d = ids
        opt = Adam(learning_rate=1e-2)
        state = opt.init_tree(params)
        step = T.build_train_step(cfg, opt, mesh=mesh)
        for _ in range(3):
            params, state, loss = step(params, state, ids_d)
        assert np.isfinite(float(loss))
        return jax.tree.map(np.asarray, params)

    local = run(None)
    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    sharded = run(Mesh(devs, ("data", "model")))

    flat_l, _ = jax.tree.flatten(local)
    flat_s, _ = jax.tree.flatten(sharded)
    for i, (a, b) in enumerate(zip(flat_l, flat_s)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-4,
            err_msg=f"transformer param leaf {i} diverged under TP+DP")


def test_nmt_decoder_group_dp_equals_local():
    """The round-5 decoder path (recurrent_group with a SUNK softmax
    tail + fused logits-CE) under 8-way DP equals local training — the
    sink/fused-CE machinery must compose with mesh sharding."""
    from paddle_tpu.core import flags, rng as prng
    from paddle_tpu.models import seqtoseq as S

    prev_bf16 = flags.get("bf16")
    flags.set("bf16", False)
    try:
        vocab, bs, tlen, steps = 40, 16, 5, 3
        rng = np.random.default_rng(5)

        def seq():
            return SequenceBatch(
                data=jnp.asarray(rng.integers(0, vocab, size=(bs, tlen))),
                length=jnp.full((bs,), tlen, jnp.int32))

        feeds = [{"source_language_word": seq(),
                  "target_language_word": seq(),
                  "target_language_next_word": seq()}
                 for _ in range(steps)]

        def build():
            base.reset_name_counters()
            cost = S.seqtoseq_net(vocab, vocab, word_vector_dim=8,
                                  encoder_size=8, decoder_size=8)
            topo = Topology(cost)
            # the fused path must actually be engaged
            assert any(n.name.endswith("#logits") for n in topo.nodes)
            prng.seed(17)
            return topo, paddle.parameters.create(topo).as_dict()

        topo, params0 = build()
        opt = Momentum(momentum=0.9, learning_rate=0.05)
        local = _train(topo, opt, dict(params0), feeds)

        topo2, params2 = build()
        for k in params0:
            np.testing.assert_array_equal(np.asarray(params0[k]),
                                          np.asarray(params2[k]))
        ctx = mesh_mod.MeshContext(mesh=mesh_mod.make_mesh({"data": 8}))
        sharded = _train(topo2, opt, dict(params2), feeds, mesh=ctx)

        assert local.keys() == sharded.keys()
        for name in local:
            np.testing.assert_allclose(
                local[name], sharded[name], rtol=3e-5, atol=3e-5,
                err_msg=f"parameter {name} diverged (sunk decoder, DP8)")
    finally:
        flags.set("bf16", prev_bf16)
