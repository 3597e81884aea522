"""tracewire — span tracing, the per-rank introspection server, and
windowed device profiling (telemetry/tracing.py + introspect.py +
tools/trace_merge.py).

The acceptance surface: a 4-step CPU train with --status_port serves
parseable /metrics /healthz /snapshot MID-RUN and /trace yields a valid
Chrome trace whose feed/compute/fence spans nest per step; a disabled
tracer is a no-op (bit-identical trajectory); trace_merge over a 2-rank
launch produces one timeline with both rank lanes; the introspection
server survives a concurrent scrape loop under train/serve load with
zero GL-THREAD findings.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import metrics as metrics_mod
from paddle_tpu.core import flags
from paddle_tpu.telemetry import MemorySink, MetricsRegistry, introspect
from paddle_tpu.telemetry.registry import SCHEMA
from paddle_tpu.telemetry.tracing import (
    ProfileWindow,
    Tracer,
    parse_profile_steps,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PY = sys.executable


@pytest.fixture(autouse=True)
def _restore_flags():
    snap = flags.snapshot_raw()
    yield
    flags.restore_raw(snap)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(port: int, path: str):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode()


def _fake_clock(start=100.0, tick=0.5):
    state = {"t": start}

    def clock():
        state["t"] += tick
        return state["t"]

    return clock


# -- Tracer core ---------------------------------------------------------------


class TestTracer:
    def test_deterministic_ids_and_fake_clock_durations(self):
        t = Tracer(enabled=True, rank=3, clock=_fake_clock(0.0, 1.0))
        with t.span("step", batch_id=0):
            with t.span("feed"):
                pass
        spans = {s.name: s for s in t.spans}
        # ids are rank*2**32 + seq, allocated in begin order
        assert spans["step"].span_id == 3 * (1 << 32)
        assert spans["feed"].span_id == 3 * (1 << 32) + 1
        assert spans["feed"].parent_id == spans["step"].span_id
        # fake clock: step spans ticks 1..4, feed 2..3 — exact durations
        assert spans["feed"].dur_ms == pytest.approx(1000.0)
        assert spans["step"].dur_ms == pytest.approx(3000.0)
        # a second identical run allocates identical ids
        t2 = Tracer(enabled=True, rank=3, clock=_fake_clock(0.0, 1.0))
        with t2.span("step", batch_id=0):
            with t2.span("feed"):
                pass
        assert [s.span_id for s in t2.spans] == \
            [s.span_id for s in t.spans]

    def test_disabled_tracer_is_a_shared_noop(self):
        t = Tracer(enabled=False)
        cm1 = t.span("a")
        cm2 = t.span("b", arg=1)
        assert cm1 is cm2  # one shared object: no allocation per call
        with cm1:
            pass
        assert t.begin("x") is None
        t.end(None)  # tolerated, so call sites skip the flag re-check
        assert t.add_span("y", 0.0, 1.0) is None
        assert t.spans == []

    def test_nesting_is_per_thread(self):
        t = Tracer(enabled=True, rank=0)
        tok = t.begin("main_parent")
        seen = {}

        def worker():
            with t.span("worker_span"):
                pass
            seen["spans"] = [s for s in t.spans
                             if s.name == "worker_span"]

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        t.end(tok)
        # the worker's span must NOT be parented under the main
        # thread's open span — stacks are thread-local
        assert seen["spans"][0].parent_id is None
        assert seen["spans"][0].thread != \
            [s for s in t.spans if s.name == "main_parent"][0].thread

    def test_end_truncates_abandoned_children(self):
        t = Tracer(enabled=True, rank=0)
        outer = t.begin("outer")
        t.begin("leaked")  # an exception path never closed this
        t.end(outer)
        with t.span("next_top"):
            pass
        nxt = [s for s in t.spans if s.name == "next_top"][0]
        assert nxt.parent_id is None  # not re-parented under "leaked"

    def test_retrospective_spans_and_drain(self):
        t = Tracer(enabled=True, rank=1)
        parent = t.add_span("request", 1.0, 5.0, cat="serving", request=7)
        t.add_span("queue", 1.0, 2.0, parent_id=parent, request=7)
        assert [s.name for s in t.spans] == ["request", "queue"]
        drained = t.drain()
        assert len(drained) == 2 and t.spans == []

    def test_chrome_trace_shape(self):
        t = Tracer(enabled=True, rank=2, clock=_fake_clock())
        with t.span("step", cat="trainer", batch_id=4):
            pass
        ct = t.chrome_trace()
        names = {e["name"] for e in ct["traceEvents"]}
        assert "process_name" in names and "step" in names
        x = [e for e in ct["traceEvents"] if e.get("ph") == "X"][0]
        assert x["pid"] == 2 and x["args"]["batch_id"] == 4
        assert x["dur"] > 0 and "ts" in x
        json.dumps(ct)  # serializable as-is

    def test_phase_summary_percentiles(self):
        t = Tracer(enabled=True, rank=0)
        for ms in (1.0, 2.0, 3.0, 4.0):
            t.add_span("feed", 0.0, ms / 1e3)
        s = t.phase_summary()["feed"]
        assert s["count"] == 4
        assert s["total_ms"] == pytest.approx(10.0)
        assert s["p50_ms"] == pytest.approx(2.5)
        assert s["max_ms"] == pytest.approx(4.0)

    def test_ring_capacity_drops_oldest(self):
        t = Tracer(enabled=True, rank=0, capacity=3)
        for i in range(5):
            t.add_span(f"s{i}", 0.0, 1.0)
        assert [s.name for s in t.spans] == ["s2", "s3", "s4"]
        assert t.dropped == 2


def test_parse_profile_steps():
    assert parse_profile_steps("") is None
    assert parse_profile_steps(None) is None
    assert parse_profile_steps("2:5") == (2, 5)
    assert parse_profile_steps("3") == (3, 4)
    with pytest.raises(ValueError):
        parse_profile_steps("5:2")


# -- histogram None-safety (the satellite fix) ---------------------------------


class TestEmptyHistograms:
    def test_summary_of_zero_count_is_json_safe(self):
        from paddle_tpu.telemetry.registry import Histogram, _Hist

        reg = MetricsRegistry("t")
        h = reg.histogram("h", "help")
        # force the pathological series a bug could leave behind
        with reg._lock:
            h._series[()] = _Hist(buckets=[0] * 13)
        s = h.summary()
        assert s["count"] == 0 and s["min"] == 0.0 and s["max"] == 0.0
        assert s["p99"] == 0.0
        json.dumps(s)  # no Infinity leaks into JSON
        assert h.percentile(99) is None
        assert isinstance(h, Histogram)

    def test_engine_summary_skips_empty_histograms(self, tmp_path):
        # emit_summary over a registry whose latency histograms exist
        # but have zero observations must not roll them up
        from paddle_tpu.serving.engine import _LAT_HISTS

        reg = MetricsRegistry("t")
        sink = MemorySink()
        reg.add_sink(sink)
        for name in _LAT_HISTS:
            reg.histogram(name, "empty")
        reg.histogram("serve_ttft_ms", "").observe(10.0)

        class _Eng:  # just the summary path, no engine build
            registry = reg
            scheduler = type("S", (), {"rejected_admissions": 0})()
            cache = type("C", (), {"prefix": None})()
            serving = type("V", (), {"incremental_prefill": False})()

        from paddle_tpu.serving.engine import ServingEngine

        ServingEngine.emit_summary(_Eng)
        rec = [r for r in sink.records
               if r.get("kind") == "serve_summary"][0]
        assert set(rec["summary"]) == {"serve_ttft_ms"}

    def test_exposition_skips_empty_histograms(self):
        reg = MetricsRegistry("t")
        reg.histogram("observed", "x").observe(2.0)
        reg.histogram("empty", "y")
        text = introspect.render_prometheus(reg)
        assert "observed_count 1" in text
        assert "empty" not in text
        assert "NaN" not in text and "inf" not in text


# -- prometheus render / parse -------------------------------------------------


def test_prometheus_roundtrip_with_labels():
    reg = MetricsRegistry("t")
    reg.counter("reqs", "c").inc(3, reason="ok")
    reg.counter("reqs", "c").inc(1, reason='we"ird')
    reg.gauge("depth", "g").set(7.5)
    reg.histogram("lat", "h").observe(12.0)
    text = introspect.render_prometheus(reg)
    parsed = introspect.parse_prometheus(text)
    assert parsed[("reqs", (("reason", "ok"),))] == 3.0
    assert parsed[("depth", ())] == 7.5
    assert parsed[("lat_count", ())] == 1.0
    assert parsed[("lat_sum", ())] == 12.0
    cum = [v for (n, labels), v in parsed.items() if n == "lat_bucket"]
    assert max(cum) == 1.0
    # aggregation sums across replicas
    agg = introspect.aggregate_prometheus([text, text])
    assert agg[("reqs", (("reason", "ok"),))] == 6.0


# -- the 4-step acceptance run -------------------------------------------------


def _tiny_trainer(lr=0.05):
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    base.reset_name_counters()
    x = layer.data(name="px", type=data_type.dense_vector(6))
    h = layer.fc(input=x, size=4, act=act.SoftmaxActivation())
    lbl = layer.data(name="py", type=data_type.integer_value(4))
    cost = layer.classification_cost(input=h, label=lbl)
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    return paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.SGD(learning_rate=lr))


def _batches(n_samples=32, batch=8):
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(6,)).astype(np.float32), int(i % 4))
            for i in range(n_samples)]
    return paddle.reader.batch(lambda: iter(data), batch)


def _run_train(trace_spans: bool, status_port=0, scrape_at=None,
               profile_steps="", n_samples=32, registry=None, prefetch=0):
    from paddle_tpu.core import rng
    from paddle_tpu.telemetry.tracing import get_tracer

    rng.seed(7)
    get_tracer().configure(enabled=trace_spans)
    get_tracer().drain()    # clear() leaves the kept set-up spans
    flags.set("trace_spans", trace_spans)
    flags.set("status_port", status_port)
    flags.set("profile_steps", profile_steps)
    flags.set("prefetch_depth", prefetch)
    trainer = _tiny_trainer()
    reg = registry or MetricsRegistry("test_introspect")
    sink = MemorySink()
    reg.add_sink(sink)
    scraped = {}

    def handler(e):
        if (scrape_at is not None
                and isinstance(e, paddle.event.EndIteration)
                and e.batch_id == scrape_at and not scraped):
            for path in ("/metrics", "/healthz", "/snapshot", "/trace"):
                scraped[path] = _get(status_port, path)

    trainer.train(reader=_batches(n_samples), num_passes=1,
                  event_handler=handler, metrics_registry=reg)
    steps = [r for r in sink.records if r.get("kind") == "step"]
    return trainer, steps, scraped, sink


def test_four_step_train_serves_all_endpoints_midrun():
    """The acceptance run: 4 steps on CPU with --status_port; /metrics,
    /healthz, /snapshot parse mid-run and /trace is a valid Chrome
    trace whose feed/compute/fence spans nest per step."""
    port = _free_port()
    trainer, steps, scraped, _ = _run_train(
        True, status_port=port, scrape_at=3)
    assert len(steps) == 4
    assert set(scraped) == {"/metrics", "/healthz", "/snapshot",
                            "/trace"}

    st, text = scraped["/metrics"]
    assert st == 200
    parsed = introspect.parse_prometheus(text)  # the tiny parser
    # by batch 3's EndIteration, 4 steps retired into the step counter
    assert parsed[("steps", (("run", "train"),))] == 4.0
    assert any(n == "step_ms_count" for n, _l in parsed)

    st, health = scraped["/healthz"]
    health = json.loads(health)
    assert st == 200 and health["ok"] is True
    assert health["heartbeat"]["age_s"] >= 0.0

    st, snap = scraped["/snapshot"]
    snap = json.loads(snap)
    # the flight ring is inspectable BEFORE any crash
    assert any(h.get("tag") == "begin_batch"
               for h in snap["flight"]["heartbeats"])
    assert "metrics" in snap and "census" in snap

    st, trace = scraped["/trace"]
    trace = json.loads(trace)
    events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    # sync_period=1: steps 0..2 fully retired (fence included) by the
    # time batch 3's EndIteration fires inside its own fence
    assert len(by_name["step"]) >= 3
    assert len(by_name["fence"]) >= 3
    step_ids = {e["args"]["id"]: e for e in by_name["step"]}
    for child in ("feed", "compute", "fence"):
        nested = [e for e in by_name[child]
                  if e["args"].get("parent") in step_ids]
        assert len(nested) >= 3, f"{child} spans not nested under steps"
        for e in nested:
            parent = step_ids[e["args"]["parent"]]
            # 5e-3 us slack: ts/dur are rounded to ns in the export
            assert parent["ts"] <= e["ts"] + 5e-3
            assert e["ts"] + e["dur"] <= \
                parent["ts"] + parent["dur"] + 5e-3

    # after train() the server is down: the port no longer accepts
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _get(port, "/healthz")


@pytest.mark.parametrize("prefetch", [0, 2])
def test_disabled_tracing_is_bitwise_noop(prefetch):
    """The no-op guard: tracing off vs on must not change the
    trajectory AT ALL, and tracing off must record nothing — through
    the inline feed path (read / convert / place under ``feed``) and
    through the prefetch worker's (under ``prefetch``)."""
    from paddle_tpu.telemetry.tracing import get_tracer

    tr_off, steps_off, _, _ = _run_train(False, prefetch=prefetch)
    assert get_tracer().spans == []  # nothing recorded, nothing leaked
    tr_on, steps_on, _, _ = _run_train(True, prefetch=prefetch)
    names = {s.name for s in get_tracer().spans}
    assert {"feed_read", "feed_convert", "feed_place"} <= names
    assert ("feed_stage" in names) == bool(prefetch)
    # set-up's spans and the listener's are on too, and change nothing
    assert {"train_setup", "build_step", "place_state",
            "params_sync"} <= names
    assert any(n.startswith("xla_") for n in names)
    np.testing.assert_array_equal(
        np.asarray([r["loss"] for r in steps_off]),
        np.asarray([r["loss"] for r in steps_on]),
        err_msg="span tracing changed the training trajectory")
    for name in tr_off.parameters.names():
        np.testing.assert_array_equal(
            np.asarray(tr_off.parameters[name]),
            np.asarray(tr_on.parameters[name]))


def test_profile_steps_window_emits_record(tmp_path):
    flags.set("profile_dir", str(tmp_path / "prof"))
    _, steps, _, sink = _run_train(True, profile_steps="1:3")
    prof = [r for r in sink.records if r.get("kind") == "profile"]
    assert len(prof) == 1
    rec = prof[0]
    assert rec["start_step"] == 1 and rec["end_step"] == 3
    assert rec["schema"] == SCHEMA
    assert rec["trace_dir"] == str(tmp_path / "prof")
    assert os.path.isdir(rec["trace_dir"])  # the device capture landed
    assert rec["spans"]["compute"]["count"] == 2  # the window's steps
    assert rec["wall_ms"] > 0


def test_profile_window_closes_when_run_is_shorter_than_B(tmp_path):
    flags.set("profile_dir", str(tmp_path / "prof2"))
    _, steps, _, sink = _run_train(True, profile_steps="2:100")
    prof = [r for r in sink.records if r.get("kind") == "profile"]
    assert len(prof) == 1  # close() at train() exit emitted it
    assert prof[0]["start_step"] == 2


def test_metrics_to_md_renders_trace_spans_table(tmp_path, capsys):
    flags.set("profile_dir", str(tmp_path / "prof3"))
    _, _, _, sink = _run_train(True, profile_steps="0:4")
    jsonl = tmp_path / "m.jsonl"
    from paddle_tpu.telemetry.sinks import json_default

    with open(jsonl, "w") as f:
        for r in sink.records:
            f.write(json.dumps(r, default=json_default) + "\n")
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import metrics_to_md
    finally:
        sys.path.pop(0)
    metrics_to_md.main([str(jsonl)])
    out = capsys.readouterr().out
    assert "## Trace spans" in out
    assert "| phase |" in out and "| compute |" in out
    # a fence phase >20% of step time gets flagged
    fake = {"kind": "profile", "start_step": 0, "end_step": 2,
            "wall_ms": 10.0, "trace_dir": "/tmp/x",
            "spans": {"step": {"count": 2, "total_ms": 100.0,
                               "p50_ms": 50.0, "p99_ms": 50.0,
                               "max_ms": 50.0},
                      "fence": {"count": 2, "total_ms": 40.0,
                                "p50_ms": 20.0, "p99_ms": 20.0,
                                "max_ms": 20.0}}}
    metrics_to_md.trace_table([fake])
    out = capsys.readouterr().out
    assert "⚠" in out and "fence" in out


# -- concurrent scrape under load (the satellite test) -------------------------


def test_concurrent_scrape_under_train_and_fleet_load():
    """A scrape loop hammers every endpoint while a 2-step train runs,
    then while a local fleet pumps; every /metrics snapshot parses and
    the new modules carry zero GL-THREAD findings."""
    port = _free_port()
    stop = threading.Event()
    results = {"scrapes": 0, "errors": []}

    def scrape_loop():
        while not stop.is_set():
            for path in ("/metrics", "/healthz", "/snapshot",
                         "/trace?keep=1"):
                try:
                    st, body = _get(port, path)
                    if path == "/metrics":
                        introspect.parse_prometheus(body)  # must parse
                    elif path != "/metrics":
                        json.loads(body)
                    results["scrapes"] += 1
                except urllib.error.HTTPError as e:
                    if e.code != 503:  # dead-loop verdicts are legal
                        results["errors"].append(f"{path}: {e}")
                except (urllib.error.URLError, ConnectionError,
                        OSError):
                    pass  # server not up yet / shut down between runs
                except Exception as e:  # noqa: BLE001 - the assertion
                    results["errors"].append(f"{path}: {e!r}")

    th = threading.Thread(target=scrape_loop, daemon=True)
    th.start()
    try:
        # phase 1: scrape during a 2-step train
        _run_train(True, status_port=port, n_samples=16)
        # phase 2: scrape during a fleet pump on the same port
        import jax

        from paddle_tpu.models import transformer as T
        from paddle_tpu.serving import ServingConfig
        from paddle_tpu.serving.fleet import build_local_fleet

        cfg = T.TransformerConfig(
            vocab_size=64, num_layers=1, num_heads=2, embed_dim=32,
            mlp_dim=64, max_seq_len=64, remat=False)
        params = T.init_params(cfg, jax.random.key(1))
        reg = MetricsRegistry("fleet_scrape")
        router = build_local_fleet(
            cfg, params,
            ServingConfig(max_slots=2, page_size=4, num_pages=32,
                          max_prompt_len=8, max_new_tokens=4, seed=0),
            n=2, registry=reg)
        srv = introspect.IntrospectionServer(registry=reg, port=port)
        srv.start()
        srv.add_health("fleet_pump",
                       lambda: router._loop_error_now() is None)
        rng = np.random.default_rng(0)
        for i in range(4):
            router.submit(list(rng.integers(1, 64, size=3)),
                          max_new_tokens=3)
        router.run_until_idle()
        assert router.stats()["requests_lost"] == 0
        srv.stop()
    finally:
        stop.set()
        th.join(timeout=10)
    assert results["errors"] == []
    assert results["scrapes"] > 0  # the loop really scraped mid-run

    # zero GL-THREAD/GL-LOCKORDER findings over the new modules
    from paddle_tpu.analysis.codebase import (
        THREADED_MODULES,
        iter_corpus,
        pass_lock_order,
        pass_thread_safety,
    )
    from paddle_tpu.analysis.core import repo_root

    mods = ("paddle_tpu/telemetry/tracing.py",
            "paddle_tpu/telemetry/introspect.py")
    assert all(m in THREADED_MODULES for m in mods)
    corpus = iter_corpus(repo_root(), files=list(mods))
    assert pass_thread_safety(corpus, repo_root(), modules=mods) == []
    assert pass_lock_order(corpus, repo_root(), modules=mods) == []


# -- serving lifecycle spans + fleet scrape aggregator -------------------------


@pytest.mark.serving
def test_serving_request_lifecycle_spans_and_scrape_rollup():
    import jax

    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving import ServingConfig
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.fleet import build_local_fleet
    from paddle_tpu.telemetry.tracing import get_tracer

    get_tracer().configure(enabled=True)
    get_tracer().clear()
    cfg = T.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, embed_dim=32,
        mlp_dim=64, max_seq_len=64, remat=False)
    params = T.init_params(cfg, jax.random.key(1))
    reg = MetricsRegistry("lifecycle")
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_slots=2, page_size=4, num_pages=32,
                      max_prompt_len=8, max_new_tokens=4, seed=0),
        registry=reg)
    res = eng.generate([[5, 17, 3], [9, 2]], max_new_tokens=3)
    assert all(len(r.tokens) >= 1 for r in res)
    spans = get_tracer().spans
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # live batch spans + per-request retrospective lifecycles
    assert by_name["serve_prefill"] and by_name["serve_decode"]
    assert len(by_name["request"]) == 2
    req_ids = {s.span_id for s in by_name["request"]}
    for phase in ("queue", "prefill", "decode"):
        assert len(by_name[phase]) == 2
        assert all(s.parent_id in req_ids for s in by_name[phase])
    # queue -> prefill -> decode tile the request interval in order
    for r in by_name["request"]:
        kids = sorted((s for s in spans
                       if s.parent_id == r.span_id),
                      key=lambda s: s.t_start)
        assert [k.name for k in kids] == ["queue", "prefill", "decode"]
        assert kids[0].t_start >= r.t_start - 1e-9
        assert kids[-1].t_end <= r.t_end + 1e-9
    get_tracer().configure(enabled=False)

    # the FleetRouter-side aggregator: two replica /metrics endpoints
    # folded into one fleet rollup record
    regs = [MetricsRegistry(f"replica{i}") for i in range(2)]
    for i, r in enumerate(regs):
        r.counter("serve_tokens", "t").inc(10 * (i + 1))
        r.gauge("serve_free_pages", "p").set(5)
    servers = [introspect.IntrospectionServer(registry=r, port=0)
               for r in regs]
    urls = [f"http://127.0.0.1:{s.start()}/metrics" for s in servers]
    fleet_reg = MetricsRegistry("fleet")
    sink = MemorySink()
    fleet_reg.add_sink(sink)
    router = build_local_fleet(
        cfg, params,
        ServingConfig(max_slots=2, page_size=4, num_pages=32,
                      max_prompt_len=8, max_new_tokens=4, seed=0),
        n=1, registry=fleet_reg)
    rollup = router.scrape_replicas(urls + ["http://127.0.0.1:9/metrics"])
    for s in servers:
        s.stop()
    assert rollup["replicas_scraped"] == 2
    assert rollup["serve_tokens"] == 30.0
    assert rollup["serve_free_pages"] == 10.0
    assert len(rollup["scrape_errors"]) == 1  # the dead endpoint, named
    recs = [r for r in sink.records
            if r.get("kind") == "fleet" and r.get("event") == "scrape"]
    assert recs and recs[0]["serve_tokens"] == 30.0


# -- 2-rank launch + trace_merge (the fleet timeline) --------------------------


_RANK_TRACE_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["REPO_ROOT"])
# create the backend FIRST: a local-fleet rank is its own single-process
# jax world where process_index() is 0 on EVERY rank — host_index must
# prefer the launcher's PADDLE_TPU_TRAINER_ID stamp or both ranks dump
# trace-host0.json and clobber each other (regression: the real-CLI
# 2-rank drive caught exactly this)
import jax
jax.config.update("jax_platforms", "cpu")
jax.devices()
from paddle_tpu.telemetry.tracing import Tracer
t = Tracer(enabled=True)  # rank from PADDLE_TPU_TRAINER_ID
assert t.rank == int(os.environ["PADDLE_TPU_TRAINER_ID"])
with t.span("step", cat="trainer", batch_id=0):
    with t.span("feed"):
        pass
    with t.span("compute"):
        pass
t.dump(os.path.join(os.environ["TRACE_OUT"],
                    "trace-host%d.json" % t.rank))
"""


def test_trace_merge_over_two_rank_launch(tmp_path):
    from paddle_tpu.distributed.launch import launch_local

    out = tmp_path / "traces"
    out.mkdir()
    env = dict(os.environ, TRACE_OUT=str(out), REPO_ROOT=REPO)
    rc = launch_local([_PY, "-c", _RANK_TRACE_CHILD], nproc=2, env=env,
                      log_dir=str(tmp_path / "logs"), timeout=120)
    assert rc == 0
    files = sorted(os.listdir(out))
    assert files == ["trace-host0.json", "trace-host1.json"]

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_merge
    finally:
        sys.path.pop(0)
    merged_path = tmp_path / "merged.json"
    rc = trace_merge.main([str(out), "-o", str(merged_path)])
    assert rc == 0
    merged = json.load(open(merged_path))
    counts = trace_merge.census(merged)
    # ONE timeline, BOTH rank lanes populated
    assert set(counts) == {0, 1}
    assert counts[0] == 3 and counts[1] == 3
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"rank 0", "rank 1"} <= names
    # span ids never collide across lanes (rank-strided allocation)
    ids = [e["args"]["id"] for e in merged["traceEvents"]
           if e.get("ph") == "X"]
    assert len(ids) == len(set(ids))


def test_launch_stamps_per_rank_status_port(tmp_path):
    from paddle_tpu.distributed.launch import launch_local

    child = ("import os, sys; "
             "assert os.environ['PADDLE_TPU_STATUS_PORT'] == "
             "str(19000 + int(os.environ['PADDLE_TPU_TRAINER_ID'])), "
             "os.environ.get('PADDLE_TPU_STATUS_PORT'); "
             "assert sys.argv[1] == os.environ['PADDLE_TPU_STATUS_PORT']")
    rc = launch_local([_PY, "-c", child, "{status_port}"], nproc=2,
                      log_dir=str(tmp_path), timeout=120,
                      status_port_base=19000)
    assert rc == 0
