"""recurrent_group / memory / beam_search / seq2seq tests.

Mirrors the reference's RecurrentGradientMachine tests
(``paddle/gserver/tests/test_RecurrentGradientMachine.cpp``,
``test_recurrent_machine_generation.cpp``) with numeric golden checks instead
of golden model dirs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.config.topology import Topology
from paddle_tpu.core.lod import SequenceBatch
from paddle_tpu.core.parameters import Parameters
from paddle_tpu.layers import api as layer
from paddle_tpu.layers import data_type
from paddle_tpu.layers.base import reset_name_counters
from paddle_tpu.layers.mixed import identity_projection, mixed
from paddle_tpu.layers.recurrent_group import (
    GeneratedSequence,
    StaticInput,
    memory,
    recurrent_group,
)


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_name_counters()
    yield


def _run(topology, feed, params=None):
    p = params or Parameters.from_specs(topology.param_specs(),
                                        key=jax.random.PRNGKey(0))
    vals, _ = topology.forward(p.as_dict(), topology.init_states(), feed,
                               is_train=False)
    return vals, p


def test_recurrent_group_cumsum_semantics():
    """step out = x_t + out_{t-1} -> masked cumulative sum (golden check of
    scan + memory wiring, no parameters involved)."""
    d = 4
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))

    def step(xt):
        mem = memory(name="acc", size=d)
        return mixed(size=d, name="acc",
                     input=[identity_projection(xt), identity_projection(mem)])

    out = recurrent_group(step=step, input=x)
    topo = Topology(out)

    data = np.random.RandomState(0).randn(2, 5, d).astype(np.float32)
    length = np.array([5, 3], np.int32)
    feed = {"x": SequenceBatch(jnp.asarray(data), jnp.asarray(length))}
    vals, _ = _run(topo, feed)
    got = np.asarray(vals[out.name].data)
    want = np.cumsum(data, axis=1)
    # valid region matches cumsum
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(got[1, :3], want[1, :3], rtol=1e-5)


def test_memory_boot_layer():
    d = 3
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))
    boot = layer.data(name="boot", type=data_type.dense_vector(d))

    def step(xt):
        mem = memory(name="acc", size=d, boot_layer=boot)
        return mixed(size=d, name="acc",
                     input=[identity_projection(xt), identity_projection(mem)])

    out = recurrent_group(step=step, input=x)
    topo = Topology(out)
    data = np.ones((1, 2, d), np.float32)
    feed = {
        "x": SequenceBatch(jnp.asarray(data), jnp.asarray([2])),
        "boot": jnp.full((1, d), 10.0),
    }
    vals, _ = _run(topo, feed)
    got = np.asarray(vals[out.name].data)
    np.testing.assert_allclose(got[0, 0], 11.0)  # 1 + boot
    np.testing.assert_allclose(got[0, 1], 12.0)


def test_recurrent_group_reverse():
    d = 2
    x = layer.data(name="x", type=data_type.dense_vector_sequence(d))

    def step(xt):
        mem = memory(name="acc", size=d)
        return mixed(size=d, name="acc",
                     input=[identity_projection(xt), identity_projection(mem)])

    out = recurrent_group(step=step, input=x, reverse=True)
    topo = Topology(out)
    data = np.random.RandomState(1).randn(1, 4, d).astype(np.float32)
    feed = {"x": SequenceBatch(jnp.asarray(data), jnp.asarray([4]))}
    vals, _ = _run(topo, feed)
    got = np.asarray(vals[out.name].data)
    want = np.cumsum(data[0][::-1], axis=0)[::-1]
    np.testing.assert_allclose(got[0], want, rtol=1e-5)


def test_seqtoseq_training_cost_and_grads():
    from paddle_tpu.models.seqtoseq import seqtoseq_net

    cost = seqtoseq_net(source_dict_dim=20, target_dict_dim=17,
                        word_vector_dim=8, encoder_size=8, decoder_size=8)
    topo = Topology(cost)
    params = Parameters.from_specs(topo.param_specs(),
                                   key=jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    feed = {
        "source_language_word": SequenceBatch(
            jnp.asarray(rs.randint(0, 20, (2, 6))), jnp.asarray([6, 4])),
        "target_language_word": SequenceBatch(
            jnp.asarray(rs.randint(0, 17, (2, 5))), jnp.asarray([5, 3])),
        "target_language_next_word": SequenceBatch(
            jnp.asarray(rs.randint(0, 17, (2, 5))), jnp.asarray([5, 3])),
    }

    def loss_fn(pvals):
        vals, _ = topo.forward(pvals, topo.init_states(), feed, is_train=False)
        return vals[cost.name]

    # compiled, as a trainer runs it: the eager tape walks both scans
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params.as_dict())
    assert np.isfinite(float(loss))
    # every trainable parameter gets a gradient signal somewhere
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
    nonzero = sum(float(jnp.sum(jnp.abs(g))) > 0 for g in flat)
    assert nonzero >= len(flat) - 2  # allow e.g. unused padding rows


def test_seqtoseq_beam_search_generation():
    from paddle_tpu.models.seqtoseq import seqtoseq_net

    gen = seqtoseq_net(source_dict_dim=20, target_dict_dim=17,
                       word_vector_dim=8, encoder_size=8, decoder_size=8,
                       is_generating=True, beam_size=3, max_length=7)
    topo = Topology(gen)
    params = Parameters.from_specs(topo.param_specs(),
                                   key=jax.random.PRNGKey(1))
    rs = np.random.RandomState(3)
    feed = {
        "source_language_word": SequenceBatch(
            jnp.asarray(rs.randint(0, 20, (2, 6))), jnp.asarray([6, 4])),
    }
    vals, _ = topo.forward(params.as_dict(), topo.init_states(), feed,
                           is_train=False)
    res = vals[gen.name]
    assert isinstance(res, GeneratedSequence)
    assert res.ids.shape == (2, 3, 7)
    scores = np.asarray(res.score)
    # beams sorted by score, best first
    assert np.all(np.diff(scores, axis=1) <= 1e-5)
    lens = np.asarray(res.length)
    assert np.all(lens >= 1) and np.all(lens <= 7)
    ids = np.asarray(res.ids)
    assert ids.min() >= 0 and ids.max() < 17
    # deterministic
    vals2, _ = topo.forward(params.as_dict(), topo.init_states(), feed,
                            is_train=False)
    np.testing.assert_array_equal(ids, np.asarray(vals2[gen.name].ids))
    # ragged python conversion works
    rows = res.to_list()
    assert len(rows) == 2 and len(rows[0]) == 3


def test_seqtoseq_train_generate_share_all_params_same_process():
    """Building the generation topology AFTER the training one (no counter
    reset, as a real user script does) must reference the same parameter
    names, or generation would silently run on fresh random weights."""
    from paddle_tpu.models.seqtoseq import seqtoseq_net

    cost = seqtoseq_net(20, 17, word_vector_dim=8, encoder_size=8,
                        decoder_size=8)
    train_names = {s.name for s in Topology(cost).param_specs()}
    gen = seqtoseq_net(20, 17, word_vector_dim=8, encoder_size=8,
                       decoder_size=8, is_generating=True, beam_size=2,
                       max_length=5)
    gen_names = {s.name for s in Topology(gen).param_specs()}
    # every generation parameter except the source-side-only data path must
    # exist in the trained set
    missing = gen_names - train_names
    assert not missing, f"generation params not trained: {missing}"


def test_scan_tail_sink_equivalence():
    """The sunk feed-forward tail (vocab fc outside the scan) is float-
    equal to the per-step application, for cost AND gradients, on the
    canonical NMT decoder step (simple_attention + gru_step -> fc)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core import flags
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.layers import base, recurrent_group as rg
    from paddle_tpu.models import seqtoseq as S

    rng = np.random.default_rng(0)
    bs, tlen, vocab = 4, 6, 50

    def build():
        base.reset_name_counters()
        cost = S.seqtoseq_net(vocab, vocab, word_vector_dim=8,
                              encoder_size=8, decoder_size=8)
        topo = Topology(cost)
        return cost, topo

    def run(topo, cost, params):
        from paddle_tpu.layers.base import Context, evaluate

        def f(params):
            ctx = Context(is_train=True, key=jax.random.key(0))
            ids = rng_feed
            vals, _ = evaluate([cost], ctx, params, topo.init_states(), ids)
            v = vals[cost.name]
            return v if v.ndim == 0 else v.mean()

        # both arrangements compiled (a fresh closure: the flag is read
        # when it is traced)
        loss, grads = jax.jit(jax.value_and_grad(f))(params)
        return loss, grads

    def seq(r):
        return SequenceBatch(data=r.integers(0, vocab, size=(bs, tlen)),
                             length=np.full((bs,), tlen, np.int32))

    r1 = np.random.default_rng(1)
    rng_feed = {"source_language_word": seq(r1),
                "target_language_word": seq(r1),
                "target_language_next_word": seq(r1)}

    prev_bf16 = flags.get("bf16")
    flags.set("bf16", False)
    try:
        from paddle_tpu.core import rng as prng

        assert rg.SINK_SCAN_TAIL
        cost, topo = build()
        prng.seed(11)
        params = paddle.parameters.create(topo).as_dict()
        loss_sink, grads_sink = run(topo, cost, params)

        rg.SINK_SCAN_TAIL = False
        cost2, topo2 = build()
        # identical init: same names + same seed path
        prng.seed(11)
        params2 = paddle.parameters.create(topo2).as_dict()
        for k in params:
            np.testing.assert_array_equal(np.asarray(params[k]),
                                          np.asarray(params2[k]))
        loss_ref, grads_ref = run(topo2, cost2, params2)
    finally:
        rg.SINK_SCAN_TAIL = True
        flags.set("bf16", prev_bf16)

    np.testing.assert_allclose(float(loss_sink), float(loss_ref),
                               rtol=1e-6)
    for k in grads_ref:
        np.testing.assert_allclose(
            np.asarray(grads_sink[k]), np.asarray(grads_ref[k]),
            rtol=1e-5, atol=1e-7, err_msg=k)


def test_fused_logits_ce_equivalence():
    """classification_cost's fused lse-based CE (via the #logits
    companion) equals the probs-path CE, for a DIRECT softmax fc and
    the NMT-style group with a sunk softmax tail — cost and grads."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core import flags, rng as prng
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer, base, data_type
    from paddle_tpu.layers.base import Context, evaluate

    flags.set("bf16", False)
    try:
        base.reset_name_counters()
        x = layer.data(name="fx", type=data_type.dense_vector(16))
        h = layer.fc(input=x, size=32, act=act.TanhActivation())
        out = layer.fc(input=h, size=7, act=act.SoftmaxActivation())
        assert "__fc_logits__" in out.attrs
        lbl = layer.data(name="fy", type=data_type.integer_value(7))
        cost = layer.classification_cost(input=out, label=lbl)
        # the fused path attached a hidden logits companion
        assert any(p.name.endswith("#logits") for p in cost.parents)
        topo = Topology(cost)
        prng.seed(3)
        params = paddle.parameters.create(topo).as_dict()
        r = np.random.default_rng(0)
        feed = {"fx": r.normal(size=(8, 16)).astype(np.float32),
                "fy": r.integers(0, 7, size=(8,))}

        def f(params):
            vals, _ = evaluate([cost], Context(is_train=True,
                                               key=jax.random.key(0)),
                               params, topo.init_states(), feed)
            return vals[cost.name].mean()

        loss, grads = jax.jit(jax.value_and_grad(f))(params)
        # reference: -log(softmax[y]) computed by hand
        w1 = params[[k for k in params if "fc_layer_0" in k and "w" in k
                     and "bias" not in k][0]]
        logits_h = np.tanh(feed["fx"] @ np.asarray(w1))
        wk = [k for k in params if "fc_layer_1" in k]
        w2 = np.asarray(params[[k for k in wk if k.endswith(".w0")][0]])
        b2 = np.asarray(params[[k for k in wk if "bias" in k][0]])
        lg = logits_h @ w2 + b2
        lse = np.log(np.exp(lg - lg.max(1, keepdims=True)).sum(1)) \
            + lg.max(1)
        ref = float(np.mean(lse - lg[np.arange(8), feed["fy"]]))
        np.testing.assert_allclose(float(loss), ref, rtol=1e-5)
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(grads))
    finally:
        flags.set("bf16", False)


def test_sink_rejects_static_input_tail():
    """A tail that reads a StaticInput must NOT sink, even when that
    static also feeds the recurrence (its per-step value is the whole
    sequence — stacking it would be wrong); the group falls back to the
    per-step path and still computes correctly."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer, base, data_type
    from paddle_tpu.layers.base import Context, evaluate
    from paddle_tpu.layers.recurrent_group import (
        StaticInput, memory, recurrent_group,
    )
    import jax

    base.reset_name_counters()
    seq = layer.data(name="stx", type=data_type.dense_vector_sequence(4))
    outer = layer.fc(input=layer.first_seq(input=seq), size=4,
                     act=act.TanhActivation(), name="outer_ctx")

    def step(s_t, ctx_static):
        mem = memory(name="st_step", size=4)
        h = layer.fc(input=[s_t, mem], size=4, act=act.TanhActivation(),
                     name="st_step")
        # tail reads BOTH the recurrence value and the static input
        out = layer.fc(input=[h, ctx_static], size=3,
                       act=act.SoftmaxActivation())
        return out

    g = recurrent_group(step=step,
                        input=[seq, StaticInput(input=outer)],
                        name="static_tail_group")
    topo = Topology(g)
    params = paddle.parameters.create(topo).as_dict()
    r = np.random.default_rng(0)
    sb = SequenceBatch(data=r.normal(size=(2, 5, 4)).astype(np.float32),
                       length=np.array([5, 3], np.int32))
    vals, _ = evaluate([g], Context(is_train=False, key=jax.random.key(0)),
                       params, topo.init_states(), {"stx": sb})
    out = vals[g.name]
    assert out.data.shape == (2, 5, 3)
    np.testing.assert_allclose(np.asarray(out.data).sum(-1)[0, 0], 1.0,
                               rtol=1e-5)  # softmax rows


def test_two_costs_share_one_logits_companion():
    """Two classification_cost calls on the same softmax fc reuse ONE
    #logits companion; both runtime metrics point at the node that
    actually exists."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer, base, data_type

    base.reset_name_counters()
    x = layer.data(name="tcx", type=data_type.dense_vector(8))
    out = layer.fc(input=x, size=4, act=act.SoftmaxActivation())
    y1 = layer.data(name="tcy1", type=data_type.integer_value(4))
    y2 = layer.data(name="tcy2", type=data_type.integer_value(4))
    c1 = layer.classification_cost(input=out, label=y1, name="costA")
    c2 = layer.classification_cost(input=out, label=y2, name="costB")
    companions = {p.name for c in (c1, c2) for p in c.parents
                  if p.name.endswith("#logits")}
    assert companions == {"costA#logits"}  # ONE shared companion
    topo = Topology([c1, c2])
    node_names = {n.name for n in topo.nodes}
    for kind, pred, lbl, tag in topo.metrics():
        assert pred in node_names, (pred, tag)
    # and the whole thing trains
    params = paddle.parameters.create(topo).as_dict()
    from paddle_tpu.trainer.step import build_train_step
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.parallel.mesh import get_mesh
    import jax
    import numpy as np

    step = build_train_step(topo, SGD(learning_rate=0.1))
    specs = {s.name: s for s in topo.param_specs()}
    opt_state = SGD(learning_rate=0.1).init(params, specs)
    r = np.random.default_rng(0)
    feed = {"tcx": r.normal(size=(8, 8)).astype(np.float32),
            "tcy1": r.integers(0, 4, size=(8,)),
            "tcy2": r.integers(0, 4, size=(8,))}
    params2, _, _, cost, metrics = step(params, opt_state, topo.init_states(),
                                        feed, jax.random.key(0))
    assert np.isfinite(float(cost))
