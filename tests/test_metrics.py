"""Telemetry layer (ISSUE 1): registry semantics, sink round-trips, the
per-step records ``SGD.train`` emits, comm-bytes accounting from the
collective wrappers, and the flight recorder's dump-on-exception."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import metrics
from paddle_tpu.core import flags
from paddle_tpu.distributed import multihost as mh


@pytest.fixture
def registry():
    """A fresh, isolated registry (never the process-global one)."""
    return metrics.MetricsRegistry("test")


@pytest.fixture
def global_sink():
    """MemorySink attached to the process-global registry (what SGD.train
    uses), detached afterwards."""
    sink = metrics.MemorySink()
    reg = metrics.get_registry()
    reg.add_sink(sink)
    yield sink
    reg.remove_sink(sink)


# -- registry semantics -------------------------------------------------------

def test_counter_gauge_histogram_with_labels(registry):
    c = registry.counter("requests")
    c.inc(op="a")
    c.inc(2.5, op="a")
    c.inc(op="b")
    assert c.value(op="a") == 3.5 and c.value(op="b") == 1.0
    assert c.value(op="missing") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1.0, op="a")

    g = registry.gauge("loss")
    g.set(2.0, run="train")
    g.set(1.5, run="train")  # last write wins
    assert g.value(run="train") == 1.5
    assert g.value(run="test") is None

    h = registry.histogram("ms", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 500.0):
        h.observe(v, run="train")
    s = h.summary(run="train")
    assert s["count"] == 3 and s["min"] == 0.5 and s["max"] == 500.0
    assert s["buckets"] == {"1.0": 1, "10.0": 1, "+Inf": 1}

    # same name, same type -> same object; different type -> error
    assert registry.counter("requests") is c
    with pytest.raises(TypeError):
        registry.gauge("requests")

    snap = registry.snapshot()
    assert {"requests", "loss", "ms"} <= set(snap)
    assert {s["op"]: s["value"] for s in snap["requests"]} == \
        {"a": 3.5, "b": 1.0}


def test_emit_stamps_schema_and_fans_out(registry):
    m1, m2 = metrics.MemorySink(), metrics.MemorySink()
    registry.add_sink(m1)
    registry.add_sink(m2)
    rec = registry.emit({"value": 1}, kind="bench")
    for sink in (m1, m2):
        assert sink.records == [rec]
    assert rec["schema"] == metrics.SCHEMA
    assert rec["kind"] == "bench" and "ts" in rec and "host" in rec
    registry.remove_sink(m2)
    registry.emit({"value": 2})
    assert len(m1.records) == 2 and len(m2.records) == 1


def test_jsonl_sink_roundtrip(tmp_path, registry):
    path = str(tmp_path / "sub" / "metrics.jsonl")
    registry.add_sink(metrics.JsonlSink(path))
    registry.emit({"kind": "step", "loss": np.float32(1.5),
                   "n": np.int64(3), "arr": np.arange(2)})
    registry.emit({"kind": "step", "loss": 2.0})
    registry.clear_sinks()  # closes the file
    lines = [json.loads(line) for line in open(path)]
    assert len(lines) == 2
    assert lines[0]["loss"] == 1.5 and lines[0]["n"] == 3
    assert lines[0]["arr"] == [0, 1]  # numpy -> JSON-native
    assert all(r["schema"] == metrics.SCHEMA for r in lines)


# -- per-step records from SGD.train ------------------------------------------

def _tiny_trainer():
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import data_type

    x = layer.data(name="x", type=data_type.dense_vector(4))
    y = layer.data(name="y", type=data_type.dense_vector(1))
    fc = layer.fc(input=x, size=1,
                  act=paddle.activation.LinearActivation(), name="out")
    cost = layer.mse_cost(input=fc, label=y)
    params = paddle.parameters.create(paddle.topology.Topology(cost))
    return paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=0.05))


def _reader(n_batches=2, poison_batch=None):
    rs = np.random.RandomState(0)
    w = np.array([1.0, -2.0, 0.5, 3.0])

    def r():
        for i in range(8 * n_batches):
            x = rs.randn(4).astype(np.float32)
            if poison_batch is not None and i // 8 == poison_batch:
                x[0] = np.nan
            yield x, np.array([x @ w], np.float32)
    return paddle.reader.batch(r, batch_size=8)


def test_sgd_train_emits_one_record_per_step(global_sink, tmp_path):
    """Acceptance: a 2-step run with the JSONL sink produces one parseable
    record per step with {step, loss, step_ms, examples_per_sec, mfu_pct}."""
    path = str(tmp_path / "train.jsonl")
    jsonl = metrics.JsonlSink(path)
    reg = metrics.get_registry()
    reg.add_sink(jsonl)
    try:
        _tiny_trainer().train(reader=_reader(n_batches=2), num_passes=1)
    finally:
        reg.remove_sink(jsonl)
        jsonl.close()

    for records in ([json.loads(line) for line in open(path)],
                    global_sink.by_kind("step")):
        steps = [r for r in records if r.get("kind") == "step"]
        assert len(steps) == 2
        for i, r in enumerate(steps):
            assert r["step"] == i
            assert np.isfinite(r["loss"])
            assert r["step_ms"] > 0
            assert r["examples_per_sec"] > 0
            assert "mfu_pct" in r  # ~0 on CPU, but always present
            assert r["pass_id"] == 0 and r["batch_id"] == i
        # XLA cost analysis rode along (cached per compile signature)
        assert steps[0]["flops"] > 0


def test_sgd_train_uses_explicit_registry():
    reg = metrics.MetricsRegistry("isolated")
    sink = metrics.MemorySink()
    reg.add_sink(sink)
    _tiny_trainer().train(reader=_reader(n_batches=2), num_passes=1,
                          metrics_registry=reg)
    assert len(sink.by_kind("step")) == 2
    # pull-side aggregates accumulated on the same registry
    assert reg.counter("steps").value(run="train") == 2.0
    assert reg.counter("examples").value(run="train") == 16.0
    assert reg.histogram("step_ms").summary(run="train")["count"] == 2


def test_tokens_in_feed():
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.telemetry import tokens_in_feed

    assert tokens_in_feed({"x": np.zeros((4, 2))}) is None
    feed = {"s": SequenceBatch(data=np.zeros((2, 5)),
                               length=np.array([5, 3], np.int32)),
            "x": np.zeros((2, 2))}
    assert tokens_in_feed(feed) == 8


# -- comm accounting from the collective wrappers -----------------------------

def test_collective_wrappers_count_bytes():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import collective
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.telemetry import comm_snapshot, get_default_registry

    mesh = make_mesh({"data": 2})
    before = comm_snapshot().get("all_reduce/data", 0.0)
    fn = collective.on_mesh(
        mesh, lambda x: collective.all_reduce(x, "data"),
        in_specs=P("data"), out_specs=P())
    out = fn(jnp.ones((4, 8), jnp.float32))
    np.testing.assert_allclose(np.asarray(out)[0], 2.0)
    # per-shard payload of the traced program: [2, 8] f32 = 64 bytes
    # (>= : jax may trace the fresh shard_map more than once)
    after = comm_snapshot()["all_reduce/data"]
    delta = after - before
    assert delta >= 64.0 and delta % 64.0 == 0.0
    calls = get_default_registry().counter("comm_calls")
    assert calls.value(op="all_reduce", axis="data") >= 1


def test_capture_comm_scopes_trace_accounting():
    """record_comm feeds an active capture AND the global counters —
    jax's trace cache runs a program's Python body exactly once, so a
    single firing serves both consumers without double counting."""
    from paddle_tpu.telemetry import (capture_comm, comm_snapshot,
                                      record_comm)

    before = comm_snapshot().get("psum/data", 0.0)
    with capture_comm() as comm:
        record_comm("psum", "data", 256)
        record_comm("psum", "data", 256)
    assert comm == {"psum/data": 512.0}
    assert comm_snapshot()["psum/data"] == before + 512.0
    record_comm("psum", "data", 128)  # outside capture: counters only
    assert comm == {"psum/data": 512.0}
    assert comm_snapshot()["psum/data"] == before + 640.0


def test_cost_for_captures_program_comm():
    """cost_for returns (flops, bytes, comm) with the lowered program's
    collective payload — independent of which registry is in use."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import collective
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.telemetry import StepTelemetry

    mesh = make_mesh({"data": 2})
    fn = collective.on_mesh(
        mesh, lambda x: collective.all_reduce(x, "data"),
        in_specs=P("data"), out_specs=P())
    jitted = __import__("jax").jit(fn)
    x = jnp.ones((4, 8), jnp.float32)
    reg = metrics.MetricsRegistry("isolated-comm")
    st = StepTelemetry(registry=reg)
    flops, nbytes, comm = st.cost_for("sig0", lambda: jitted.lower(x))
    assert comm.get("all_reduce/data") == 64.0  # [2, 8] f32 per shard
    # cached: second call returns the same triple without re-lowering
    assert st.cost_for("sig0", lambda: 1 / 0) == (flops, nbytes, comm)
    rec = st.record_step(loss=1.0, step_ms=1.0, examples=4, comm=comm)
    assert rec["comm_bytes"] == {"all_reduce/data": 64.0}


def test_step_records_carry_comm_snapshot(registry):
    from paddle_tpu.telemetry import StepTelemetry, record_comm

    sink = metrics.MemorySink()
    registry.add_sink(sink)
    record_comm("all_gather", "model", 1024, registry=registry)
    st = StepTelemetry(registry=registry)
    rec = st.record_step(loss=1.0, step_ms=2.0, examples=4)
    assert rec["comm_bytes"] == {"all_gather/model": 1024.0}
    assert sink.records[-1]["comm_bytes"] == {"all_gather/model": 1024.0}


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    rec = mh.FlightRecorder(capacity=3)
    for i in range(5):
        rec.record({"step": i})
    rec.heartbeat("begin_batch", step=5)
    assert [r["step"] for r in rec.records] == [2, 3, 4]  # ring evicted 0,1
    path = rec.dump(reason="unit", dump_dir=str(tmp_path))
    payload = json.load(open(path))
    assert payload["reason"] == "unit"
    assert [r["step"] for r in payload["records"]] == [2, 3, 4]
    assert payload["heartbeats"][-1]["tag"] == "begin_batch"
    assert payload["schema"] == "paddle_tpu.flight/1"


def test_flight_recorder_dumps_when_train_step_raises(tmp_path):
    """Acceptance: the ring-buffer dump is written when the train step
    raises (here: debug_nans trapping a poisoned batch)."""
    import jax

    mh.flight_recorder().clear()
    prev_dir = flags.get("flight_recorder_dir")
    prev_nans = flags.get("debug_nans")
    flags.set("flight_recorder_dir", str(tmp_path))
    flags.set("debug_nans", True)
    prev_cfg = jax.config.jax_debug_nans
    try:
        with pytest.raises(FloatingPointError):
            _tiny_trainer().train(
                reader=_reader(n_batches=3, poison_batch=2), num_passes=1)
    finally:
        flags.set("flight_recorder_dir", prev_dir)
        flags.set("debug_nans", prev_nans)
        jax.config.update("jax_debug_nans", prev_cfg)
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-host")]
    assert len(dumps) == 1
    payload = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert "FloatingPointError" in payload["reason"]
    # the two good steps preceding the poisoned one are in the ring, and
    # the pre-step heartbeat pins where the failing batch began
    assert len(payload["records"]) >= 2
    assert all(r["kind"] == "step" for r in payload["records"])
    assert any(h["tag"] == "begin_batch" for h in payload["heartbeats"])


def test_geometric_buckets_and_observe_by_bisection():
    """The serve latency histograms' edges: each at most 5% above the
    one before, 0.1 ms to 60 s; an observation lands in the first bucket
    whose edge is not below it, one past the last edge in the overflow
    bucket, as the linear search did."""
    from paddle_tpu.serving.engine import _LATENCY_BUCKETS
    from paddle_tpu.telemetry.registry import geometric_buckets

    edges = geometric_buckets(0.1, 60_000.0, 1.05)
    assert edges == _LATENCY_BUCKETS
    assert edges[0] == pytest.approx(0.1) and edges[-1] == pytest.approx(6e4)
    assert all(b / a <= 1.05 + 1e-12 for a, b in zip(edges, edges[1:]))
    assert len(edges) < 300
    with pytest.raises(ValueError):
        geometric_buckets(1.0, 1.0, 1.05)
    reg = metrics.MetricsRegistry("buckets")
    h = reg.histogram("t_ms", "", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 3.9, 4.0, 4.1, 100.0):
        h.observe(v)
    assert list(h.summary()["buckets"].values()) == [2, 2, 2, 2]


@pytest.mark.parametrize("step_ms", [9.1, 14.6, 43.5, 238.0])
def test_a_steady_steps_quantile_reads_within_5pct_of_it(step_ms):
    """What DEFAULT_BUCKETS could not: a loop whose step takes 9.1 ms
    read 7.3 from the (5, 10] bucket's interpolation."""
    from paddle_tpu.serving.engine import _latency

    reg = metrics.MetricsRegistry("steady")
    rng = np.random.default_rng(0)
    # a steady step, with the odd short and long one a live loop has
    steps = np.concatenate([step_ms * (1 + 0.01 * rng.standard_normal(480)),
                            np.full(10, step_ms / 4), np.full(10, step_ms * 4)])
    coarse = reg.histogram("coarse_ms", "")
    for v in steps:
        _latency(reg, "serve_decode_step_ms").observe(float(v))
        coarse.observe(float(v))
    got = reg.get("serve_decode_step_ms").percentile(50)
    assert abs(got - step_ms) / step_ms < 0.05
    assert abs(coarse.percentile(50) - step_ms) / step_ms > 0.1
