"""Goodput ledger — wall-clock badput attribution (telemetry/goodput.py).

The acceptance properties of the ledger:

- a fake-clock chaos timeline (slow reader, NaN rescue with nested
  restore, elastic drain+reshard, recompile, supervisor restart) lands
  every injected second in its named bucket and the buckets sum to the
  wall-clock exactly;
- ``fold()`` is incremental over ring snapshots (each span classified
  once, new spans picked up on the next fold);
- the closing record is a schema/14 ``kind="ledger"`` emission, sets
  the ``goodput_fraction`` gauge, and appends to ledger.jsonl;
- a REAL 50-step CPU chaos run (nan-skip + one elastic 8→4 reshard +
  prefetch-starved reader) through the trainer produces a ledger whose
  buckets sum to wall-clock within 1% with each fault visible;
- arming the ledger never changes the training trajectory — final
  parameters are bit-identical to a ledger-off run.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags, rng as prng
from paddle_tpu.layers import api as layer, base, data_type
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.resilience.chaos import ChaosSchedule
from paddle_tpu.resilience.elastic import ElasticCoordinator
from paddle_tpu.telemetry import MemorySink, MetricsRegistry
from paddle_tpu.telemetry.goodput import (
    BADPUT_BUCKETS,
    BUCKETS,
    GoodputLedger,
    serving_costs,
)
from paddle_tpu.telemetry.tracing import (
    Tracer,
    configure_tracing,
    get_tracer,
)


@pytest.fixture(autouse=True)
def _restore_tracing_and_flags():
    """The trainer arms the global tracer when --goodput_ledger is on
    and never disarms it; undo that (and any flag edits) per test."""
    prev = flags.snapshot_raw()
    get_tracer().drain()    # clear() leaves the kept set-up spans
    yield
    flags.restore_raw(prev)
    configure_tracing(enabled=bool(flags.get("trace_spans")))


class _Clock:
    """Manually-advanced fake clock shared by tracer and ledger."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _ledger(reg=None):
    clk = _Clock()
    tracer = Tracer(enabled=True, rank=0, clock=clk)
    reg = reg or MetricsRegistry("goodput_test")
    return GoodputLedger(registry=reg, tracer=tracer).start(), tracer, clk, reg


# -- the fake-clock chaos timeline --------------------------------------------


def test_chaos_windows_land_in_their_buckets_and_sum_to_wall():
    """Every injected chaos window books its named bucket with exactly
    the injected seconds, idle absorbs the rest, and the closing
    account sums to the wall-clock."""
    led, tracer, clk, reg = _ledger()

    # slow reader: the consumer blocked 2.0s on the feed
    tracer.add_span("feed", 0.0, 2.0, cat="trainer")
    # first dispatch built a new executable: 3.0s of recompile
    tracer.add_span("compute", 2.0, 5.0, cat="trainer", compile=True)
    # steady-state step: 1.0s productive compute
    tracer.add_span("compute", 5.0, 6.0, cat="trainer", compile=False)
    tracer.add_span("fence", 6.0, 6.5, cat="trainer")
    # nan@k rescue (2.0s) that restored from checkpoint (nested 1.0s):
    # the restore second lands in checkpoint_restore, NOT twice
    tracer.add_span("restore", 7.5, 8.5, cat="trainer")
    tracer.add_span("guard_rescue", 7.0, 9.0, cat="trainer", policy="rollback")
    # host_loss@k:dp=4: drain checkpoint then the live mesh rebuild
    tracer.add_span("drain", 9.0, 10.0, cat="elastic")
    tracer.add_span("gather", 10.0, 10.5, cat="elastic")
    tracer.add_span("reshard", 10.5, 11.0, cat="elastic")
    tracer.add_span("rebuild", 11.0, 11.5, cat="elastic")
    tracer.add_span("checkpoint", 11.5, 12.0, cat="trainer")
    # parent/overlapping spans must NOT double-count
    tracer.add_span("step", 0.0, 12.0, cat="trainer")
    tracer.add_span("prefetch", 0.0, 12.0, cat="prefetch")
    # supervisor restart: the counter delta prices the recovery gauge in
    reg.counter("restarts", "").inc(run="train")
    reg.gauge("recovery_ms", "").set(500.0, run="train")
    reg.gauge("recovery_ms", "").set(9999.0, run="elastic")  # excluded

    clk.t = 20.0
    rec = led.finish()
    b = rec["buckets_s"]
    assert b["input_wait"] == pytest.approx(2.0)
    assert b["recompile"] == pytest.approx(3.0)
    assert b["compute"] == pytest.approx(1.0)
    assert b["fence"] == pytest.approx(0.5)
    assert b["guard_rescue"] == pytest.approx(1.0)      # 2.0 - nested 1.0
    assert b["checkpoint_restore"] == pytest.approx(1.0)
    assert b["elastic_drain"] == pytest.approx(1.0)
    assert b["elastic_reshard"] == pytest.approx(1.5)
    assert b["checkpoint_save"] == pytest.approx(0.5)
    assert b["restart"] == pytest.approx(0.5)           # 1 restart x 500ms
    assert b["idle"] == pytest.approx(20.0 - 12.0)
    assert rec["wall_s"] == pytest.approx(20.0)
    assert sum(b.values()) == pytest.approx(rec["wall_s"], rel=0.01)
    assert rec["goodput_fraction"] == pytest.approx(1.0 / 20.0)
    assert rec["badput_fraction"] == pytest.approx(19.0 / 20.0)
    assert set(b) == set(BUCKETS)
    assert set(BADPUT_BUCKETS) == set(BUCKETS) - {"compute"}


def test_wall_clock_identity_holds_with_the_layer_spans_in_the_ring():
    """The feed pipeline's and the engine step's spans are in the ring
    beside the trainer's: producer-thread spans (``prefetch`` and its
    four children), the inline path's children of ``feed``, and the
    serving engine's own loop are booked nowhere, so each second of the
    train loop is still counted once and the buckets sum to the wall."""
    led, tracer, clk, reg = _ledger()
    # two steps with the prefetch worker running ahead
    for i, t0 in enumerate((0.0, 5.0)):
        tracer.add_span("feed", t0, t0 + 1.0, cat="trainer")
        tracer.add_span("compute", t0 + 1.0, t0 + 4.0, cat="trainer")
        tracer.add_span("fence", t0 + 4.0, t0 + 5.0, cat="trainer")
        tracer.add_span("step", t0, t0 + 5.0, cat="trainer")
        p = tracer.add_span("prefetch", t0, t0 + 5.0, cat="reader")
        tracer.add_span("feed_read", t0, t0 + 1.0, "reader", p)
        tracer.add_span("feed_convert", t0 + 1.0, t0 + 3.0, "reader", p)
        tracer.add_span("feed_place", t0 + 3.0, t0 + 3.5, "reader", p)
        tracer.add_span("feed_stage", t0 + 3.5, t0 + 5.0, "reader", p)
    # a third step on the inline path: the three are children of feed
    f = tracer.add_span("feed", 10.0, 13.0, cat="trainer")
    tracer.add_span("feed_read", 10.0, 11.0, "reader", f)
    tracer.add_span("feed_convert", 11.0, 12.5, "reader", f)
    tracer.add_span("feed_place", 12.5, 13.0, "reader", f)
    tracer.add_span("compute", 13.0, 15.0, cat="trainer")
    # an engine serving in the same process
    s = tracer.add_span("serve_step", 0.0, 15.0, cat="serving")
    tracer.add_span("serve_schedule", 0.0, 1.0, "serving", s)
    tracer.add_span("serve_prefill", 1.0, 5.0, "serving", s)
    tracer.add_span("serve_decode", 5.0, 15.0, "serving", s)
    clk.t = 16.0
    rec = led.finish()
    b = rec["buckets_s"]
    assert b["input_wait"] == pytest.approx(1.0 + 1.0 + 3.0)
    assert b["compute"] == pytest.approx(3.0 + 3.0 + 2.0)
    assert b["fence"] == pytest.approx(2.0)
    assert b["idle"] == pytest.approx(1.0)
    assert sum(b.values()) == pytest.approx(rec["wall_s"]) == 16.0


def test_set_up_is_startup_each_second_once_and_the_buckets_sum_to_wall():
    """A resumed run's timeline: ``train_setup`` around a build, a
    placement that compiled (``xla_compile`` -> recompile), a restore
    (-> checkpoint_restore) and a ``Parameters`` round trip; then a first
    dispatch that fetched its program (the fetch is startup's line, the
    rest of the span compute), one that compiled, the copy back.  Each
    second lands once, out of ``idle``."""
    led, tracer, clk, reg = _ledger()
    # kept beside the ring, so handed to the fold BEFORE the ring's
    # restore: the fold sorts inner before outer itself
    setup = tracer.add_span("train_setup", 0.0, 10.0, cat="setup")
    tracer.add_span("build_step", 0.0, 1.0, cat="setup", parent_id=setup)
    place = tracer.add_span("place_state", 1.0, 5.0, cat="setup",
                            parent_id=setup)
    tracer.add_span("params_sync", 1.0, 2.0, cat="setup", parent_id=place)
    tracer.add_span("xla_trace", 2.0, 2.5, cat="xla", parent_id=place,
                    under="place_state")
    tracer.add_span("xla_compile", 2.5, 4.5, cat="xla", parent_id=place,
                    under="place_state")
    tracer.add_span("restore", 5.0, 8.0, cat="trainer", parent_id=setup)
    first = tracer.add_span("compute", 10.0, 14.0, cat="trainer",
                            compile=False, cache_fetches=1)
    tracer.add_span("xla_trace", 10.0, 12.0, cat="xla", parent_id=first,
                    under="compute")
    tracer.add_span("xla_cache_fetch", 12.5, 13.5, cat="xla",
                    parent_id=first, under="compute")
    second = tracer.add_span("compute", 14.0, 20.0, cat="trainer",
                             compile=True, compiles=1)
    tracer.add_span("xla_compile", 15.0, 19.0, cat="xla", parent_id=second,
                    under="compute")
    tracer.add_span("compute", 20.0, 21.0, cat="trainer", compile=False)
    tracer.add_span("params_sync", 21.0, 21.5, cat="setup", back=True)
    # another loop's set-up (a serving replica in the process): not ours
    other = Tracer(enabled=True, rank=0, clock=clk)
    t = threading.Thread(target=lambda: other.add_span(
        "engine_ready", 0.0, 20.0, cat="setup"), name="serving-engine")
    t.start()
    t.join()
    tracer._kept.extend(other.spans)
    clk.t = 22.0
    rec = led.finish()
    b = rec["buckets_s"]
    # train_setup's 10 s less the compile's 2 and the restore's 3, the
    # fetch's 1, the copy back's 0.5
    assert b["startup"] == pytest.approx(5.0 + 1.0 + 0.5)
    assert b["recompile"] == pytest.approx(2.0 + 6.0)
    assert b["checkpoint_restore"] == pytest.approx(3.0)
    assert b["compute"] == pytest.approx(3.0 + 1.0)
    assert b["idle"] == pytest.approx(0.5)
    assert sum(b.values()) == pytest.approx(rec["wall_s"]) == 22.0
    assert set(b) == set(BUCKETS) and "startup" in BADPUT_BUCKETS


@pytest.mark.parametrize("order", ["inner_first", "outer_first", "two_folds"])
def test_nested_set_up_spans_book_each_second_once_in_any_order(order):
    led, tracer, clk, reg = _ledger()
    inner = [("build_step", 1.0, 2.0), ("place_state", 2.0, 6.0),
             ("params_sync", 2.0, 3.0)]
    outer = [("train_setup", 0.0, 8.0)]
    seq = inner + outer if order != "outer_first" else outer + inner
    for i, (name, t0, t1) in enumerate(seq):
        tracer.add_span(name, t0, t1, cat="setup")
        if order == "two_folds" and i == 1:
            led.fold()
    # a span from before the run (the import) is not the run's
    tracer.add_span("import_paddle_tpu", -5.0, -1.0, cat="setup")
    clk.t = 10.0
    b = led.finish()["buckets_s"]
    assert b["startup"] == pytest.approx(8.0)
    assert b["idle"] == pytest.approx(2.0)
    assert sum(b.values()) == pytest.approx(10.0)


def test_fold_is_incremental_over_ring_snapshots():
    led, tracer, clk, _ = _ledger()
    tracer.add_span("feed", 0.0, 1.0, cat="trainer")
    tracer.add_span("compute", 1.0, 2.0, cat="trainer")
    assert led.fold() == 2
    assert led.fold() == 0          # nothing new -> nothing reclassified
    tracer.add_span("fence", 2.0, 2.5, cat="trainer")
    assert led.fold() == 1
    snap = led.snapshot()
    assert snap["input_wait"] == pytest.approx(1.0)
    assert snap["compute"] == pytest.approx(1.0)
    assert snap["fence"] == pytest.approx(0.5)
    clk.t = 3.0
    rec = led.finish()
    assert rec["spans_folded"] == 3
    assert rec["spans_dropped"] == 0


def test_finish_emits_ledger_record_gauge_and_jsonl(tmp_path):
    reg = MetricsRegistry("goodput_emit")
    sink = MemorySink()
    reg.add_sink(sink)
    led, tracer, clk, _ = _ledger(reg)
    tracer.add_span("compute", 0.0, 3.0, cat="trainer")
    clk.t = 4.0
    path = str(tmp_path / "ledger.jsonl")
    rec = led.finish(path=path)
    assert rec["kind"] == "ledger"
    assert rec["schema"].endswith("/16")
    assert reg.get("goodput_fraction").value() == pytest.approx(0.75)
    recs = [r for r in sink.records if r.get("kind") == "ledger"]
    assert len(recs) == 1
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == 1 and lines[0]["buckets_s"] == rec["buckets_s"]


def test_serving_costs_split_and_absence():
    reg = MetricsRegistry("goodput_serving")
    assert serving_costs(reg) == {}     # nothing served -> no section
    reg.counter("serve_prefill_compute_s", "").inc(3.0)
    reg.counter("serve_decode_compute_s", "").inc(7.0)
    reg.counter("serve_queue_s", "").inc(2.0)
    reg.counter("serve_kv_page_s", "").inc(40.0)
    reg.counter("serve_tokens", "").inc(1000)
    c = serving_costs(reg)
    assert c["cost_per_token_s"] == pytest.approx(0.01)
    assert c["cost_per_token_prefill_s"] == pytest.approx(0.003)
    assert c["cost_per_token_decode_s"] == pytest.approx(0.007)
    assert c["cost_per_token_queue_s"] == pytest.approx(0.002)
    assert c["kv_page_s"] == pytest.approx(40.0)
    assert c["tokens"] == 1000


# -- the real 50-step CPU chaos run -------------------------------------------

IN_DIM, HIDDEN, CLASSES = 8, 16, 4


def _trainer(mesh_ctx=None, zero=0):
    from paddle_tpu.layers import activation as act

    base.reset_name_counters()
    prng.seed(7)
    x = layer.data(name="x", type=data_type.dense_vector(IN_DIM))
    h = layer.fc(input=x, size=HIDDEN, act=act.ReluActivation())
    predict = layer.fc(input=h, size=CLASSES, act=act.SoftmaxActivation())
    lbl = layer.data(name="y", type=data_type.integer_value(CLASSES))
    cost = layer.classification_cost(input=predict, label=lbl)
    params = paddle.parameters.create(paddle.topology.Topology(cost))
    kw = {}
    if mesh_ctx is not None:
        kw = {"mesh": mesh_ctx, "zero": zero}
    return paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=0.05), **kw)


def _reader(batches=50, bs=8, delay_s=0.0):
    def r():
        rs = np.random.RandomState(0)
        for i in range(batches * bs):
            if delay_s and i % bs == 0:
                time.sleep(delay_s)  # prefetch-starved reader
            yield rs.randn(IN_DIM).astype(np.float32), int(i % CLASSES)

    return paddle.reader.batch(r, bs)


def _mesh(dp):
    import jax

    return mesh_mod.MeshContext(
        mesh=mesh_mod.make_mesh({"data": dp}, devices=jax.devices()[:dp]))


@pytest.mark.elastic
def test_fifty_step_chaos_run_ledger_sums_to_wall(tmp_path):
    """The ISSUE's acceptance run: 50 steps on CPU with a nan-skip at
    step 7, one elastic 8→4 reshard at step 25, and a prefetch-starved
    reader — the closing ledger must sum to wall-clock within 1% and
    show every injected fault in its bucket."""
    prev_mesh = mesh_mod._current
    flags.set("goodput_ledger", True)
    flags.set("ledger_dir", str(tmp_path))
    reg = MetricsRegistry("chaos_ledger")
    reg.add_sink(MemorySink())
    try:
        tr = _trainer(_mesh(8), zero=2)
        coord = ElasticCoordinator(registry=reg)
        sched = ChaosSchedule("nan@7,host_loss@25:dp=4",
                              registry=reg).bind_elastic(coord)
        tr.train(reader=sched.wrap_reader(_reader(delay_s=0.002)),
                 num_passes=1, nan_policy="skip",
                 checkpoint_dir=str(tmp_path / "ck"),
                 event_handler=sched.wrap_event_handler(None),
                 elastic=coord, metrics_registry=reg)
    finally:
        mesh_mod._current = prev_mesh

    with open(os.path.join(str(tmp_path), "ledger.jsonl")) as f:
        (rec,) = [json.loads(ln) for ln in f]
    b = rec["buckets_s"]
    assert sum(b.values()) == pytest.approx(rec["wall_s"], rel=0.01)
    assert rec["spans_dropped"] == 0
    # build and placement before step 0 are startup's, no longer idle's
    assert b["startup"] > 0
    assert b["compute"] > 0                      # steady-state steps
    assert b["recompile"] > 0                    # first-signature builds
    assert b["input_wait"] > 0                   # the starved reader
    assert b["guard_rescue"] > 0                 # nan@7 skip handling
    assert b["elastic_drain"] > 0                # drain ckpt before rebuild
    assert b["elastic_reshard"] > 0              # the 8→4 rebuild
    assert 0.0 < rec["goodput_fraction"] < 1.0
    assert reg.get("goodput_fraction").value() == pytest.approx(
        rec["goodput_fraction"], abs=1e-6)
    assert dict(tr.mesh.mesh.shape) == {"data": 4}


def test_trajectory_bit_identical_with_ledger_enabled():
    """Arming the ledger adds zero perturbation: the final parameters
    of a ledger-on run equal a ledger-off run bit-for-bit."""
    def run(enabled):
        flags.set("goodput_ledger", enabled)
        configure_tracing(enabled=False)
        tr = _trainer()
        tr.train(reader=_reader(batches=6), num_passes=1,
                 metrics_registry=MetricsRegistry("traj"))
        return {n: np.asarray(tr.parameters[n]) for n in
                tr.parameters.names()}

    off = run(False)
    on = run(True)
    assert off.keys() == on.keys()
    for n in off:
        np.testing.assert_array_equal(off[n], on[n], err_msg=n)
