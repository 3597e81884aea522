"""Every device operation knows its sublayer (``telemetry/scopes.py``):
the named scopes through the transformer, the layer graph and the update,
what a held program says of itself under an armed tracer, and the
operator's table.  Tiny programs only (one prefill member and the decode
step a config, compiled twice: with the scopes and with
``jax.named_scope`` nulled); summed over the file about 55 s here."""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_toy
import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.layers import activation as act
from paddle_tpu.layers import api as layer
from paddle_tpu.layers import base as layer_base
from paddle_tpu.layers import data_type, pooling
from paddle_tpu.models import transformer as T
from paddle_tpu.serving import engine as E
from paddle_tpu.telemetry import scopes, tracing

# -- part_of on hand-written name stacks ------------------------------------------


@pytest.mark.parametrize("op_name, want", [
    ("jit(decode)/pt:attn.qkv/dot_general", ("attn.qkv", "fwd")),
    ("jit(f)/jvp(pt:embed)/mul", ("embed", "fwd")),
    ("jit(f)/transpose(jvp(pt:loss))/jit(<lambda>)/add_any",
     ("loss", "bwd")),
    ("jit(f)/jvp()/while/body/closed_call/pt:ffn/pt:norm/div",
     ("norm", "fwd")),
    ("jit(f)/transpose(jvp())/while/body/closed_call/pt:ffn/pt:ffn/"
     "checkpoint/rematted_computation/cos", ("ffn", "bwd")),
    ("jit(step)/transpose(jvp(pt:conv_bn/res2a_branch1))/pt:batch_norm/mul",
     ("batch_norm", "bwd")),
    ("jit(step)/jvp(pt:conv_bn/conv1)/pt:conv/conv_general_dilated",
     ("conv", "fwd")),
    ("jit(step)/jvp(pt:pool/pool1)/reduce_window_max", ("pool", "fwd")),
    ("jit(step)/shard_map/comm.all_reduce.data/psum", ("comm", "fwd")),
    ("jit(step)/shard_map/pt:update/comm.all_gather.data/all_gather",
     ("comm", "fwd")),
    ("jit(prefill)/jit(_prefill_layer)/pt:mamba2.proj/pt:mamba2.scan/"
     "while/body/mul", ("mamba2.scan", "fwd")),
    ("jit(decode)/while/body/dynamic_slice", (None, "fwd")),
    ("jit(step)/transpose(jvp())/while/cond/lt", (None, "bwd")),
    ("", (None, "fwd")),
    # a label opens a path element: this is no label
    ("jit(f)/script:pt:embed/mul", (None, "fwd")),
])
def test_part_of(op_name, want):
    assert scopes.part_of(op_name) == want


def test_part_of_reads_what_jax_writes():
    """The forms above are jax's own: a scope under grad, scan, checkpoint
    and an inner jit, read back from the compiled program."""
    def f(w, x):
        with scopes.part("embed"):
            x = x * 2

        def body(c, wl):
            with scopes.part("ffn"):
                h = jax.checkpoint(lambda a: jnp.sin(a @ wl))(c)
                with scopes.part("norm"):
                    h = h / (1.0 + jnp.sum(h * h))
            return h, None

        x, _ = jax.lax.scan(body, x, w)
        with scopes.part("loss"):
            return jnp.sum(jax.jit(lambda a: a * a)(x))

    compiled = jax.jit(jax.grad(f)).lower(
        jnp.ones((3, 8, 8)), jnp.ones((4, 8))).compile()
    got = scopes.op_scopes(compiled)
    assert {"embed", "ffn", "norm", "loss"} <= {
        k.split("|")[0] for k in got}
    assert any(k.endswith(scopes.BWD) for k in got)
    # nothing is listed twice, and no container is listed
    names = [n for v in got.values() for n in v]
    assert len(names) == len(set(names))
    assert not any(n.split(".")[0] in scopes.CONTAINERS for n in names)


_HLO = """HloModule jit_decode, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,16]) -> bf16[8,16] {
  %param_0.1 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %tanh.9 = bf16[8,16]{1,0:T(8,128)(2,1)} tanh(%param_0.1), metadata={op_name="jit(decode)/pt:norm/tanh"}
}

%body.2 (arg: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %arg = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[8,16]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %slice-start.3 = ((bf16[4,16,16]{2,1,0}), bf16[16,16]{1,0:T(8,128)(2,1)S(1)}, u32[]) slice-start(%w.5), slice={[0:1], [0:16], [0:16]}
  %slice-done.3 = bf16[16,16]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.3)
  %fusion.7 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%gte.1, %slice-done.3), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(decode)/pt:stack/while/body/closed_call/pt:ffn/dot_general" stack_frame_id=4}
  %copy.8 = bf16[8,16]{0,1:T(8,128)(2,1)} copy(%fusion.7)
  ROOT %tuple.9 = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) tuple(%c.1, %fusion.7)
}

ENTRY %main.10 (w.5: bf16[4,16,16], x.6: bf16[8,16]) -> bf16[8,16] {
  %w.5 = bf16[4,16,16]{2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params['w']"}
  %x.6 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(1)
  %copy.11 = bf16[8,16]{1,0:T(8,128)(2,1)S(1)} copy(%x.6), metadata={op_name="x"}
  %compare.12 = pred[8]{0:T(256)} fusion(%copy.11), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/gt"}
  %while.13 = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond.3, body=%body.2, metadata={op_name="jit(decode)/pt:stack/while"}
  ROOT %fusion.14 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%gte.20), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/pt:sample/select_n"}
}
"""


def test_what_the_compiler_made_belongs_to_its_reader():
    """A prefetch has no name stack: it is its reader's.  What the source
    issued outside every scope, or what nothing with a part reads, is
    ``unscoped``; a fusion's body and a container are not listed."""
    assert scopes.op_scopes(_HLO) == {
        # the weight's prefetch, through its two hands, is the product's
        "ffn": ["slice-start.3", "slice-done.3", "fusion.7"],
        # copy.11 (a parameter's re-laid copy) is read by the source's own
        # unscoped comparison; copy.8 by nothing
        "unscoped": ["copy.11", "compare.12", "copy.8"],
        "sample": ["fusion.14"]}
    assert [i for i, _, _ in scopes.instructions(_HLO)] == [
        "w.5", "x.6", "copy.11", "compare.12", "while.13", "fusion.14",
        "arg", "gte.1", "slice-start.3", "slice-done.3", "fusion.7",
        "copy.8", "tuple.9"]


# -- the serving programs of every kind of layer --------------------------------------

_YOCO = dict(vocab_size=64, num_heads=8, kv_heads=4, head_dim=64,
             embed_dim=64, mlp_dim=96, max_seq_len=64, norm="layer",
             positions="none", mlp="swiglu", attn_window=8, attn_diff=True,
             attn_bias=True, mamba1_inner=128, mamba1_state=4, mamba1_conv=4,
             mamba1_dt_rank=4, mamba1_chunk=4)
_MAMBA = dict(mamba_heads=4, mamba_head_dim=8, mamba_state=16, mamba_groups=2,
              mamba_conv=4, mamba_chunk=8)
_LM_CFGS = {
    # a homogeneous stack: one scan over the stacked blocks
    "dense": dict(),
    # generation by blocks: the decode step is the block pass
    "block": dict(block_len=4, mask_id=63, norm="rms", positions="rotary",
                  qk_norm=True),
    # M E * -: state by slot, routed experts with a shared one
    "hybrid": dict(
        vocab_size=97, num_layers=4, num_heads=4, kv_heads=2, head_dim=8,
        mlp_dim=24, norm="rms", positions="none", mlp="relu2",
        tie_embeddings=False, pattern="ME*-", moe_experts=8,
        moe_router="sigmoid", moe_top_k=2, moe_shared_dim=40,
        moe_held=[0, 8], **_MAMBA),
    # a pattern that rolls: one scan over two repeats of "M-*-"
    "rolled": dict(
        vocab_size=97, num_layers=8, num_heads=4, kv_heads=2, head_dim=8,
        mlp_dim=24, norm="rms", positions="none", mlp="swiglu",
        pattern="M-*-M-*-", **_MAMBA),
    # S W * G X -: Mamba-1, a window ring, the one cache, memory units
    "yoco": dict(num_layers=6, pattern="SW*GX-", **_YOCO),
    # K with E beside it: the delta rule, an output gate
    "kda": dict(
        vocab_size=97, num_layers=4, num_heads=4, kv_heads=2, head_dim=16,
        mlp_dim=24, norm="rms", positions="none", mlp="swiglu",
        tie_embeddings=False, pattern="*EKE", attn_gate=True,
        moe_experts=16, moe_router="sigmoid", moe_top_k=4,
        moe_shared_dim=24, moe_held=[0, 8], kda_heads=4, kda_conv=4,
        kda_chunk=16),
    # CCA's two convolutions, a top-1 MLP router with a depth carry
    "cca": dict(
        vocab_size=97, num_layers=4, num_heads=4, kv_heads=2, head_dim=8,
        mlp_dim=24, norm="rms", positions="rotary", rope_fraction=0.5,
        mlp="swiglu", pattern="*E*E", cca_taps=[2, 2], residual_scale=True,
        moe_experts=8, moe_router="softmax_topk", moe_top_k=1,
        moe_renorm=False, moe_router_hidden=12),
}
# parts a kind's decode step must name, beyond what every config names
_EVERY = {"embed", "norm", "attn.qkv", "attn.core", "attn.out", "head",
          "sample"}
_OWN = {
    "dense": {"ffn"}, "block": {"ffn", "kv.write"},
    "hybrid": {"ffn", "moe.route", "moe.product", "mamba2.proj",
               "mamba2.conv", "mamba2.scan"},
    "rolled": {"ffn", "mamba2.proj", "mamba2.conv", "mamba2.scan"},
    "yoco": {"ffn", "mamba1.proj", "mamba1.conv", "mamba1.scan", "gmu.proj"},
    "kda": {"ffn", "moe.route", "moe.product", "kda.proj", "kda.conv",
            "kda.rule"},
    "cca": {"moe.route", "moe.product", "cca.conv"},
}
# opcodes no test counts: they are no work of a sublayer's
_TRIVIAL = scopes.CONTAINERS | {"parameter", "constant", "tuple",
                                "get-tuple-element", "bitcast", "copy"}


def _stripped(compiled) -> str:
    """The optimized HLO with everything that is metadata taken out: the
    ``metadata={...}`` of each instruction, the tables of files and stack
    frames in front of the first computation, and the compiler's
    numbering of names (an instruction it carries over from the
    unoptimized module keeps that module's number, ``%reshape.69`` |
    ``%reshape.77``, which moves with the locations: every name becomes
    its rank in order of appearance)."""
    text = compiled.as_text()
    text = text[re.search(r"^(ENTRY )?%[\w.\-]+ \(", text, re.M).start():]
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    rank = {}
    for name in re.findall(r"%([\w.\-]+)", text):
        rank.setdefault(name, f"n{len(rank)}")
    text = re.sub(r"%([\w.\-]+)", lambda m: "%" + rank[m.group(1)], text)
    # a computation's parameters are named in its header without the sign
    return re.sub(r"(?<=[(\s])([\w.\-]+)(?=: )",
                  lambda m: rank.get(m.group(1), m.group(1)), text)


def _few_are_loose(compiled):
    """Under 5% of the program's non-trivial instructions were issued by
    the source outside every sublayer.  (An instruction the compiler made
    itself -- no name stack: a rewritten reduce-window, a weight's re-laid
    copy -- is not the source's; ``op_scopes`` says ``unscoped`` of it.)"""
    work = [(name, op_name) for name, opcode, op_name in scopes.instructions(
        compiled.as_text()) if opcode not in _TRIVIAL]
    assert len(work) > 40
    loose = [name for name, op_name in work if op_name.startswith("jit(")
             and scopes.part_of(op_name)[0] is None]
    assert len(loose) < 0.05 * len(work), loose


def _make_ready(kind, monkeypatch, scoped=True):
    """A fresh engine of the config (it shares no traced function with
    another) with its programs compiled: (engine, its ``program_ready``
    spans) under an armed tracer."""
    monkeypatch.setattr(E, "_FN_MEMO", {})
    if not scoped:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    cfg = lm_toy.small_cfg(**_LM_CFGS[kind])
    params = T.init_params(cfg, jax.random.key(7))
    serving = dict(max_slots=2, page_size=8 if kind == "yoco" else 4,
                   num_pages=24, max_prompt_len=8, max_new_tokens=4,
                   prefill_batch=1, seed=5)

    def ready():
        eng = lm_toy.engine(cfg, params, **serving)
        eng._make_ready()
        return eng

    eng, spans = lm_toy.traced(ready)
    return eng, spans["program_ready"]


@functools.cache
def _texts_without_scopes(kind):
    with pytest.MonkeyPatch.context() as mp:
        # (the one jitted function the memo does not renew)
        T._prefill_layer.clear_cache()
        eng, _ = _make_ready(kind, mp, scoped=False)
        return {k: _stripped(c) for k, c in eng._programs.items()}


@pytest.mark.parametrize("kind", list(_LM_CFGS))
def test_serving_programs_name_their_parts(kind, monkeypatch):
    eng, spans = _make_ready(kind, monkeypatch)
    assert [s.args["program"] for s in spans] == ["prefill", "decode"]
    programs = dict(zip(("prefill", "decode"),
                        (eng._programs[1, 8], eng._programs["decode"])))
    for span in spans:
        program = span.args["program"]
        said = span.args["op_scopes"]
        assert said == scopes.op_scopes(programs[program])
        assert isinstance(span.args["routes"], dict)
        _few_are_loose(programs[program])
        if program == "decode":
            assert _EVERY | _OWN[kind] <= set(said), (
                (_EVERY | _OWN[kind]) - set(said))
    # what the held programs were built with: the census of THEIR traces
    census = {}
    for span in spans:
        census.update(span.args["routes"])
    assert "ragged_paged_attention:reference" in census or kind == "block"
    if "M" in _LM_CFGS[kind].get("pattern", ""):
        decode = spans[1].args["routes"]
        # once a position of the period: a rolled walk traces one repeat
        assert decode["ssd_step:reference"] == \
            _LM_CFGS[kind]["pattern"][:4].count("M")
    # the scopes are metadata: the optimized programs are what they are
    # with ``jax.named_scope`` a null context
    bare = _texts_without_scopes(kind)
    for key, compiled in eng._programs.items():
        assert _stripped(compiled) == bare[key], key


def test_a_span_exports_its_scopes_as_counts(monkeypatch):
    _, spans = _make_ready("dense", monkeypatch)
    said = spans[1].args["op_scopes"]
    event = spans[1].to_event()["args"]
    assert event["op_scopes"] == {k: len(v) for k, v in said.items()}
    assert event["routes"] == spans[1].args["routes"]
    assert isinstance(said["attn.core"], list)      # the span keeps lists


class _Spy:
    """``as_text`` of every compiled program and ``capture_routes``,
    counted."""

    def __init__(self, monkeypatch):
        from jax._src import stages
        from paddle_tpu.ops import pallas

        self.calls = []
        as_text, capture = stages.Compiled.as_text, pallas.capture_routes

        def spied_text(this, *a, **kw):
            self.calls.append("as_text")
            return as_text(this, *a, **kw)

        def spied_capture():
            self.calls.append("capture_routes")
            return capture()

        monkeypatch.setattr(stages.Compiled, "as_text", spied_text)
        monkeypatch.setattr(pallas, "capture_routes", spied_capture)


def test_with_tracing_off_a_held_program_is_not_read(monkeypatch):
    spy = _Spy(monkeypatch)
    monkeypatch.setattr(E, "_FN_MEMO", {})
    cfg = lm_toy.small_cfg()
    eng = lm_toy.engine(cfg, T.init_params(cfg, jax.random.key(7)),
                        max_slots=2, page_size=4, num_pages=24,
                        max_prompt_len=8, max_new_tokens=4, prefill_batch=1)
    assert not tracing.get_tracer().enabled
    eng._make_ready()
    assert spy.calls == []
    _, spans = lm_toy.traced(eng._make_ready)
    assert spy.calls.count("as_text") == 2 == spy.calls.count(
        "capture_routes")
    # jax re-used the traces of the first round: the census is empty, and
    # says so rather than repeating another program's
    assert [s.args["routes"] for s in spans["program_ready"]] == [{}, {}]


def test_a_cache_entry_without_the_scopes_is_compiled_again(tmp_path,
                                                             monkeypatch):
    """jax keys its compilation cache without metadata: what a checkout
    without the scopes cached comes back for the same program with them,
    and says nothing.  The held program is then compiled again, past
    the cache."""
    from jax._src import compilation_cache

    def lower():
        def f(x):
            with scopes.part("embed"):
                return jnp.sin(x) * 2

        return jax.jit(f).lower(jnp.ones(8))

    def described():
        with tracing.Tracer(enabled=True).timed("program_ready") as one:
            scopes.compile_described(one, lower)
        return set(one.args["op_scopes"])

    kept = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        with monkeypatch.context() as bare:
            bare.setattr(jax, "named_scope",
                         lambda name: contextlib.nullcontext())
            assert described() == {"unscoped"}
        entries = sorted(os.listdir(tmp_path))
        assert described() == {"embed"}
        # past the cache: what an untraced run finds there is what it was
        assert sorted(os.listdir(tmp_path)) == entries
        assert not jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# -- the v2 surface: the layer graph, the cost and the update ------------------------


def _tiny_net():
    layer_base.reset_name_counters()
    img = layer.data(name="img", type=data_type.dense_vector(3 * 8 * 8),
                     height=8, width=8)
    lab = layer.data(name="lab", type=data_type.integer_value(4))
    c1 = layer.img_conv_bn(name="c1", input=img, filter_size=3,
                           num_filters=4, num_channels=3, padding=1)
    c2 = layer.img_conv_bn(name="c2", input=c1, filter_size=3, num_filters=4,
                           padding=1, act=act.LinearActivation())
    add = layer.addto(name="sum", input=[c1, c2], act=act.ReluActivation())
    pool = layer.img_pool(name="pool", input=add, pool_size=2, stride=2,
                          pool_type=pooling.AvgPooling())
    out = layer.fc(name="out", input=pool, size=4,
                   act=act.SoftmaxActivation())
    return layer.classification_cost(name="cost", input=out, label=lab)


def _feed(n=8):
    rng = np.random.default_rng(3)
    return [(rng.normal(size=3 * 8 * 8).astype(np.float32),
             int(rng.integers(4))) for _ in range(n)]


def _train(registry):
    cost = _tiny_net()
    params = paddle.parameters.create(paddle.topology.Topology(cost))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=0.01))
    rows = _feed()
    trainer.train(reader=paddle.reader.batch(lambda: iter(rows), 8),
                  num_passes=1, event_handler=lambda e: None,
                  metrics_registry=registry)
    return trainer


def test_the_train_step_names_its_parts(monkeypatch):
    from paddle_tpu import metrics

    reg = metrics.MetricsRegistry("scopes_train")
    reg.add_sink(metrics.MemorySink())
    spy = _Spy(monkeypatch)
    _train(reg)
    assert spy.calls == []          # tracing off: nothing is read
    _, spans = lm_toy.traced(lambda: _train(reg))
    (span,) = spans["program_ready"]
    assert span.args["program"] == "step" and span.cat == tracing.SETUP_CAT
    assert span.args["routes"] == {}
    said = span.args["op_scopes"]
    parts = {k.split("|")[0] for k in said}
    assert {"conv", "batch_norm", "addto", "pool", "fc", "loss",
            "update"} <= parts, parts
    both = {k.split("|")[0] for k in said if k.endswith(scopes.BWD)}
    assert {"conv", "batch_norm", "pool", "fc"} <= both, both
    assert spy.calls.count("as_text") == 1 == spy.calls.count(
        "capture_routes")


def test_the_train_step_is_what_it_was_without_scopes(monkeypatch):
    """The step's optimized HLO, metadata stripped, with the scopes and
    with ``jax.named_scope`` a null context."""
    from paddle_tpu.trainer.step import build_train_step

    def text():
        cost = _tiny_net()
        topo = paddle.topology.Topology(cost)
        specs = {s.name: s for s in topo.param_specs()}
        params = paddle.parameters.create(topo).as_dict()
        opt = paddle.optimizer.Momentum(momentum=0.9, learning_rate=0.01)
        img, lab = zip(*_feed())
        feed = {"img": np.stack(img), "lab": np.asarray(lab, np.int32)}
        step = build_train_step(topo, opt)
        return step.lower(params, opt.init(params, specs),
                          topo.init_states(), feed,
                          jax.random.key(0)).compile()

    compiled = text()
    _few_are_loose(compiled)
    with_scopes = _stripped(compiled)
    assert "pt:" not in with_scopes
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _stripped(text()) == with_scopes


# -- the operator's table ---------------------------------------------------------------


def test_device_ms_by_part_sums_a_profile_by_sublayer():
    events = [
        {"name": "%fusion.1", "dur_us": 300.0,
         "tf_op": "jit(step)/jvp(pt:conv_bn/c1)/pt:conv/conv_general_dilated"},
        {"name": "%fusion.2", "dur_us": 500.0,
         "tf_op": "jit(step)/transpose(jvp(pt:conv_bn/c1))/pt:conv/"
                  "conv_general_dilated"},
        {"name": "%fusion.3", "dur_us": 100.0,
         "tf_op": "jit(step)/jvp(pt:conv_bn/c1)/pt:batch_norm/mul"},
        {"name": "%copy.4", "dur_us": 50.0, "tf_op": ""},
        {"name": "%all-reduce.5", "dur_us": 50.0,
         "tf_op": "jit(step)/shard_map/comm.all_reduce.data/psum"},
        # a loop's own event: its body's operations are events themselves
        {"name": "%while.6", "dur_us": 9000.0, "tf_op": "jit(step)/while"},
    ]
    rows = profiler.device_ms_by_part(events, steps=2)
    assert [r["part"] for r in rows] == ["conv", "batch_norm", "comm",
                                         "unscoped"]
    conv = rows[0]
    assert conv["ms"] == pytest.approx(0.4)
    assert conv["fwd_ms"] == pytest.approx(0.15)
    assert conv["bwd_ms"] == pytest.approx(0.25)
    assert conv["share"] == pytest.approx(0.8)
    assert sum(r["share"] for r in rows) == pytest.approx(1.0)
    table = profiler.format_parts(rows)
    assert table.splitlines()[1].split()[:3] == ["conv", "0.400", "80.0%"]
    assert table.splitlines()[-1].split()[0] == "unscoped"
