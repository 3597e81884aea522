"""Set-up seen from inside (``telemetry/tracing.py``): XLA's build events
heard by the process's one listener, set-up phases as ``cat="setup"``
spans of the engine and the trainer, and both kept beside the ring.

What holds: each of jax's four build events is a retrospective child of
the span that was open on the thread that built, exact on a fake clock,
and the parent says how many programs it compiled or fetched; the
counters count with tracing off; a persistent-cache hit's pair of events
is ONE fetch, on hand-made events and on the CPU backend with a cache
directory, where the trainer's ``compute.compile`` is then false; kept
spans survive ``clear()`` and a ring wrap, come first, and are drained
once; ``engine_ready`` holds one ``program_ready`` a program and
``train_setup`` a build, a placement and the ``Parameters`` round trips;
nothing compiles, and so nothing is heard, once an engine serves.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry, tracing
from paddle_tpu.telemetry.tracing import Tracer, XlaBuildListener, get_tracer

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _listener(enabled=True):
    clk = _Clock()
    tr = Tracer(enabled=enabled, rank=0, clock=clk)
    reg = MetricsRegistry("xla_listener")
    return XlaBuildListener(lambda: tr, lambda: reg), tr, clk, reg


def _value(reg, name, **labels):
    m = reg.get(name)
    return m.value(**labels) if m is not None else 0.0


@pytest.fixture
def tracer():
    """The process tracer, enabled and empty; restored afterwards."""
    t = get_tracer()
    was = t.enabled
    t.configure(enabled=True)
    t.drain()
    yield t
    t.configure(enabled=was)
    t.drain()


# -- the listener on hand-made events -------------------------------------------


def test_four_events_are_four_children_of_the_live_span():
    lis, tr, clk, reg = _listener()
    with tr.span("program_ready", cat="setup") as tok:
        for event, dur, fun in ((TRACE, 0.5, "prefill"),
                                (LOWER, 0.25, "jit(prefill)"),
                                (COMPILE, 2.0, "jit(prefill)")):
            clk.t += dur
            lis(event, dur, fun_name=fun)
        clk.t += 0.125
        lis(FETCH, 0.125)
        clk.t += 1.0
    spans = tr.spans
    parent = spans[-1]
    assert parent.name == "program_ready" and parent.span_id == tok.span_id
    kids = spans[:-1]
    assert [s.name for s in kids] == ["xla_trace", "xla_lower",
                                     "xla_compile", "xla_cache_fetch"]
    assert [(s.t_start, s.t_end) for s in kids] == [
        (100.0, 100.5), (100.5, 100.75), (100.75, 102.75), (102.75, 102.875)]
    assert all(s.parent_id == parent.span_id and s.cat == "xla"
               and s.args["under"] == "program_ready" for s in kids)
    assert [s.args.get("fun") for s in kids] == [
        "prefill", "jit(prefill)", "jit(prefill)", None]
    assert parent.args == {"compiles": 1, "cache_fetches": 1}
    assert lis.events == {"trace": 1, "lower": 1, "compile": 1,
                          "cache_fetch": 1}


def test_counters_count_with_the_tracer_disabled():
    lis, tr, clk, reg = _listener(enabled=False)
    lis(TRACE, 0.5, fun_name="f")
    lis(LOWER, 0.25, fun_name="jit(f)")
    lis(COMPILE, 2.0, fun_name="jit(f)")
    lis("/jax/compilation_cache/compile_time_saved_sec", 9.0)   # not ours
    assert tr.spans == []
    assert _value(reg, "xla_programs_total", how="compiled") == 1
    assert _value(reg, "xla_programs_total", how="fetched") == 0
    for phase, want in (("trace", 0.5), ("lower", 0.25), ("compile", 2.0)):
        assert _value(reg, "xla_build_seconds_total", phase=phase) == want
    assert sum(lis.events.values()) == 3


@pytest.mark.parametrize("enabled", [True, False])
def test_a_cache_hits_pair_of_events_is_one_fetch(enabled):
    """jax times ``compile_or_get_cached`` as a backend compile: on a hit
    the retrieval event fires inside it, then the compile event."""
    lis, tr, clk, reg = _listener(enabled)
    tok = tr.begin("compute", cat="trainer")
    clk.t += 0.5
    lis(FETCH, 0.25)
    clk.t += 0.125      # the compile event's own tail
    lis(COMPILE, 0.625, fun_name="jit(step)")
    tr.end(tok)
    assert _value(reg, "xla_programs_total", how="fetched") == 1
    assert _value(reg, "xla_programs_total", how="compiled") == 0
    assert _value(reg, "xla_build_seconds_total", phase="cache_fetch") == 0.625
    assert _value(reg, "xla_build_seconds_total", phase="compile") == 0
    # raw, as jax fired them: what the benchmark's CompileWatch counts
    assert lis.events["cache_fetch"] == 1 and lis.events["compile"] == 1
    if enabled:
        fetch, compute = tr.spans
        assert fetch.name == "xla_cache_fetch"
        assert fetch.args == {"under": "compute", "fun": "jit(step)"}
        # widened to the compile event's interval: key, read, load
        assert (fetch.t_start, fetch.t_end) == (100.0, 100.625)
        assert compute.args == {"cache_fetches": 1}
    # the next compile on this thread is a compile again
    clk.t += 3.0
    lis(COMPILE, 3.0, fun_name="jit(other)")
    assert _value(reg, "xla_programs_total", how="compiled") == 1
    assert _value(reg, "xla_build_seconds_total", phase="compile") == 3.0


def test_a_short_trace_is_counted_not_spanned():
    lis, tr, clk, reg = _listener()
    lis(TRACE, tracing.XLA_SPAN_FLOOR_S / 2, fun_name="_mean")
    lis(COMPILE, tracing.XLA_SPAN_FLOOR_S / 2, fun_name="jit(_mean)")
    assert [s.name for s in tr.spans] == ["xla_compile"]    # a program
    assert lis.events["trace"] == 1
    assert _value(reg, "xla_build_seconds_total", phase="trace") > 0


def test_one_listener_a_process():
    """``import paddle_tpu`` installed it; asking again gives the same
    one, and an engine or a trainer registers none."""
    from jax._src import monitoring

    first = tracing.install_xla_listener()
    assert tracing.install_xla_listener() is first
    mine = [cb for cb in monitoring.get_event_duration_listeners()
            if isinstance(cb, XlaBuildListener)]
    assert mine == [first]
    _engine()
    _tiny_trainer()
    assert [cb for cb in monitoring.get_event_duration_listeners()
            if isinstance(cb, XlaBuildListener)] == [first]
    tracing.uninstall_xla_listener()
    try:
        assert not [cb for cb in monitoring.get_event_duration_listeners()
                    if isinstance(cb, XlaBuildListener)]
    finally:
        assert tracing.install_xla_listener() is not first


# -- kept beside the ring ---------------------------------------------------------


def test_kept_spans_survive_clear_and_a_wrap_and_are_drained_once(tmp_path):
    clk = _Clock()
    tr = Tracer(enabled=True, rank=0, clock=clk, capacity=4)
    tr.add_span("engine_ready", 1.0, 3.0, cat="setup", programs=3)
    tr.add_span("xla_compile", 1.5, 2.5, cat="xla")
    for i in range(10):         # the ring wraps
        tr.add_span("serve_decode", 10.0 + i, 10.5 + i, cat="serving")
    assert [s.name for s in tr.spans[:2]] == ["engine_ready", "xla_compile"]
    assert len(tr.spans) == 2 + 4 and tr.dropped == 6
    tr.clear()                  # a window opens
    assert [s.name for s in tr.spans] == ["engine_ready", "xla_compile"]
    assert tr.dropped == 0
    tr.add_span("serve_decode", 30.0, 30.5, cat="serving")
    assert set(tr.phase_summary()) == {"engine_ready", "xla_compile",
                                       "serve_decode"}
    names = [e["name"] for e in tr.chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["engine_ready", "xla_compile", "serve_decode"]
    with open(tr.dump(str(tmp_path / "t.json"))) as f:
        assert [e["name"] for e in json.load(f)["traceEvents"]
                if e["ph"] == "X"] == names
    # a polling /trace scraper gets them once
    assert [s.name for s in tr.drain()] == names
    assert tr.spans == [] and tr.drain() == []


def test_kept_spans_are_bounded_and_set_up_keeps_room():
    tr = Tracer(enabled=True, rank=0, clock=_Clock())
    for i in range(tracing.KEPT_MAX + 10):
        tr.add_span("xla_trace", float(i), i + 0.5, cat="xla")
    # the set-up span that ends after the builds under it still fits
    tr.add_span("engine_ready", 0.0, 999.0, cat="setup")
    kept = tr.spans
    assert len(kept) <= tracing.KEPT_MAX
    assert kept[-1].name == "engine_ready"
    assert tr.dropped == tracing.KEPT_MAX + 10 - (len(kept) - 1)
    for i in range(tracing.KEPT_MAX):
        tr.add_span("params_sync", float(i), i + 0.5, cat="setup")
    assert len(tr.spans) == tracing.KEPT_MAX


def test_import_is_a_span_once_the_process_tracer_is_armed(tracer):
    t0, t1 = paddle._IMPORT_WINDOW
    assert t0 < t1
    tracing._import_booked = False
    tracer.configure(enabled=True)
    tracer.configure(enabled=True)      # booked once
    (imp,) = [s for s in tracer.spans if s.name == "import_paddle_tpu"]
    assert (imp.t_start, imp.t_end, imp.cat) == (t0, t1, "setup")
    # a tracer on another clock (a test's) gets none
    other = Tracer(enabled=True, clock=_Clock())
    other.configure(enabled=True)
    assert other.spans == []


# -- the engine -------------------------------------------------------------------


def _engine(vocab_size=64, **kw):
    cfg = T.TransformerConfig(
        vocab_size=vocab_size, num_layers=1, num_heads=2, embed_dim=32,
        mlp_dim=64, max_seq_len=64, remat=False)
    params = T.init_params(cfg, jax.random.key(1))
    serving = dict(max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
                   max_new_tokens=4, prefill_batch=2, seed=0)
    serving.update(kw)
    return ServingEngine(cfg, params, ServingConfig(**serving),
                         registry=MetricsRegistry("engine_setup"))


def _inside(child, parent) -> bool:
    return (parent.t_start <= child.t_start
            and child.t_end <= parent.t_end)


@pytest.mark.serving
def test_engine_ready_holds_one_program_ready_a_program(tracer):
    # a model of this test's own: engines of one configuration share their
    # jitted functions (``_serving_fns``), so a process that served the
    # common toy before -- another test file on this xdist worker -- finds
    # every program lowered and compiled, and jax fires no event at all
    # ("engine ready in 0.01 s")
    eng = _engine(vocab_size=67)
    (init,) = [s for s in tracer.spans if s.name == "engine_init"]
    assert init.cat == "setup" and init.args["params_bytes"] > 0
    assert init.args["pool_bytes"] == eng.cache.k.nbytes + eng.cache.v.nbytes
    assert init.args["state_bytes"] == 0
    assert not [s for s in tracer.spans if s.name == "engine_ready"]
    res = eng.generate([[5, 17, 3], [9, 2]], max_new_tokens=3)
    assert [len(r.tokens) for r in res] == [3, 3]
    spans = tracer.spans
    (ready,) = [s for s in spans if s.name == "engine_ready"]
    programs = [s for s in spans if s.name == "program_ready"]
    ladder = eng.scheduler.prefill_rows
    assert len(programs) == len(ladder) + 1 == ready.args["programs"]
    assert [(s.args["program"], s.args["rows"]) for s in programs] == (
        [("prefill", n) for n in ladder] + [("decode", 2)])
    assert all(s.args["length"] == 8 for s in programs[:-1])
    for p in programs:
        assert p.parent_id == ready.span_id and _inside(p, ready)
        kids = [s for s in spans if s.parent_id == p.span_id]
        # every lower().compile() shows what XLA did for it: the build is
        # always a span (a trace or a lowering only from 5 ms up, which is
        # the host's speed: under that it is counted)
        assert len({k.name for k in kids}
                   & {"xla_compile", "xla_cache_fetch"}) == 1
        assert p.args.get("compiles", 0) + p.args.get("cache_fetches", 0) == 1
        assert all(k.cat == "xla" and _inside(k, p) for k in kids)
    # ready in the step that admitted the first request
    step = {s.span_id: s for s in spans if s.name == "serve_step"}
    assert ready.parent_id in step
    # a window opens: the ring goes, set-up stays, first
    tracer.clear()
    assert {"engine_init", "engine_ready", "program_ready"} <= {
        s.name for s in tracer.spans}


@pytest.mark.serving
def test_nothing_is_built_once_an_engine_serves(tracer):
    eng = _engine()
    eng.generate([[5, 17, 3], [9, 2, 4]], max_new_tokens=3)
    heard = dict(tracing.install_xla_listener().events)
    tracer.clear()
    res = eng.generate([[7, 7, 1], [3], [8, 2, 2, 2]], max_new_tokens=4)
    assert [len(r.tokens) for r in res] == [4, 4, 4]
    assert tracing.install_xla_listener().events == heard
    spans = tracer.spans
    assert {s.name for s in spans} >= {"serve_step", "serve_decode"}
    # the window's pass spans are leaves: no build under them
    window = [s for s in spans if s.t_start > max(
        k.t_end for k in spans if k.cat in ("setup", "xla"))]
    parents = {s.parent_id for s in window}
    assert all(s.span_id not in parents for s in window
               if s.name in ("serve_prefill", "serve_decode"))
    assert not [s for s in window if s.cat in ("setup", "xla")]


@pytest.mark.serving
def test_the_incremental_paths_first_pass_compiles_under_its_span(tracer):
    eng = _engine(prefill_chunk_tokens=4)
    eng.generate([[5, 17, 3]], max_new_tokens=2)
    spans = tracer.spans
    pre = [s for s in spans if s.name == "serve_prefill"]
    built = [s for s in spans if s.cat == "xla"
             and s.parent_id == pre[0].span_id]
    assert built and pre[0].args.get("compiles", 0) + pre[0].args.get(
        "cache_fetches", 0) >= 1
    assert all(s.args["under"] == "serve_prefill" for s in built)


# -- the trainer ------------------------------------------------------------------


def _tiny_trainer(width=4):
    from paddle_tpu.core import rng
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    base.reset_name_counters()
    rng.seed(7)
    x = layer.data(name="px", type=data_type.dense_vector(6))
    h = layer.fc(input=x, size=width, act=act.TanhActivation())
    h = layer.fc(input=h, size=4, act=act.SoftmaxActivation())
    lbl = layer.data(name="py", type=data_type.integer_value(4))
    cost = layer.classification_cost(input=h, label=lbl)
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    return paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.SGD(learning_rate=0.1))


def _train(trainer, n_samples=32):
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(6,)).astype(np.float32), int(i % 4))
            for i in range(n_samples)]
    trainer.train(reader=paddle.reader.batch(lambda: iter(data), 8),
                  num_passes=1, event_handler=lambda e: None,
                  metrics_registry=MetricsRegistry("trainer_setup"))


def test_train_setup_holds_build_placement_and_parameter_round_trips(tracer):
    trainer = _tiny_trainer()
    _train(trainer)
    spans = tracer.spans
    (setup,) = [s for s in spans if s.name == "train_setup"]
    first_step = min(s.t_start for s in spans if s.name == "step")
    assert setup.cat == "setup" and setup.t_end <= first_step
    (build,) = [s for s in spans if s.name == "build_step"]
    (place,) = [s for s in spans if s.name == "place_state"]
    assert build.parent_id == place.parent_id == setup.span_id
    assert _inside(build, setup) and _inside(place, setup)
    syncs = [s for s in spans if s.name == "params_sync"]
    to_device, back = syncs
    assert to_device.parent_id == place.span_id and _inside(to_device, place)
    assert "back" not in to_device.args and back.args["back"] is True
    assert back.parent_id is None and back.t_start >= setup.t_end
    n = len(trainer.parameters.names())
    nbytes = sum(trainer.parameters[k].nbytes
                 for k in trainer.parameters.names())
    assert to_device.args == {"arrays": n, "bytes": nbytes}
    assert (back.args["arrays"], back.args["bytes"]) == (n, nbytes)
    assert place.args["arrays"] > n and place.args["bytes"] > nbytes
    # the first compute of a signature holds XLA's work for it
    first = min((s for s in spans if s.name == "compute"),
                key=lambda s: s.t_start)
    assert {s.name for s in spans if s.parent_id == first.span_id} & {
        "xla_compile", "xla_cache_fetch"}
    # a second train(): no build, the round trips again
    tracer.clear()
    _train(trainer)
    spans = tracer.spans
    assert len([s for s in spans if s.name == "train_setup"]) == 2
    assert len([s for s in spans if s.name == "build_step"]) == 1
    assert len([s for s in spans if s.name == "place_state"]) == 2
    assert len([s for s in spans if s.name == "params_sync"]) == 4
    assert not any(s.args["compile"] for s in spans if s.name == "compute")


def test_train_setup_ends_where_no_step_ever_comes(tracer):
    trainer = _tiny_trainer()
    trainer.train(reader=paddle.reader.batch(lambda: iter([]), 8),
                  num_passes=1, event_handler=lambda e: None)
    (setup,) = [s for s in tracer.spans if s.name == "train_setup"]
    assert not [s for s in tracer.spans if s.name == "step"]
    with tracer.span("after"):
        pass
    assert [s for s in tracer.spans
            if s.name == "after"][0].parent_id is None


@pytest.fixture
def cache_dir(tmp_path):
    """jax's persistent cache in a directory of this test's, taking
    every program however small; the process's settings come back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield str(tmp_path / "cache")
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_compute_compile_is_a_backend_compile_not_a_cache_fetch(
        tracer, cache_dir):
    """The same step built twice in one process, a width no other test
    trains: compiled (and written) the first time, fetched the second."""
    lis = tracing.install_xla_listener()
    firsts = []
    for _ in range(2):
        before = dict(lis.events)
        tracer.clear()
        _train(_tiny_trainer(width=11))
        computes = sorted((s for s in tracer.spans if s.name == "compute"),
                          key=lambda s: s.t_start)
        firsts.append(computes[0])
        assert not any(s.args["compile"] for s in computes[1:])
        delta = {k: lis.events[k] - before[k] for k in before}
        kids = [s.name for s in tracer.spans
                if s.parent_id == computes[0].span_id]
        if len(firsts) == 1:
            assert "xla_compile" in kids and "xla_cache_fetch" not in kids
            assert delta["cache_fetch"] == 0
        else:
            # one program, fetched: jax fired both events, one is booked
            assert "xla_cache_fetch" in kids and "xla_compile" not in kids
            assert delta["cache_fetch"] >= 1
            assert delta["compile"] >= delta["cache_fetch"]
    compiled, fetched = firsts
    assert compiled.args["compile"] is True and compiled.args["compiles"] >= 1
    assert fetched.args["compile"] is False
    assert fetched.args["cache_fetches"] >= 1 and "compiles" not in fetched.args


def test_a_jitted_call_under_a_span_is_heard_end_to_end(tracer):
    with tracer.span("outer") as tok:
        jax.jit(lambda x: jnp.tanh(x) * 3 + x.sum())(jnp.ones((3, 5)))
    kids = [s for s in tracer.spans if s.parent_id == tok.span_id]
    assert {"xla_compile", "xla_cache_fetch"} & {s.name for s in kids}
    assert all(s.cat == "xla" and s.args["under"] == "outer" for s in kids)
    (outer,) = [s for s in tracer.spans if s.name == "outer"]
    assert outer.args.get("compiles", 0) + outer.args.get(
        "cache_fetches", 0) >= 1
