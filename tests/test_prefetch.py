"""Input pipeline & overlapped step loop (ISSUE 3): DevicePrefetcher
ordering/exception/shutdown semantics, deferred-fence (sync_period)
trajectory equality against the synchronous loop, the reader decorator
exception fixes, shard_batch partial-batch policies, and the vectorized
DataFeeder densify paths."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.parallel.mesh import MeshContext, apply_remainder, make_mesh
from paddle_tpu.reader.decorator import buffered, xmap_readers
from paddle_tpu.reader.feeder import DataFeeder, _densify_ids, _densify_pairs
from paddle_tpu.reader.prefetch import DevicePrefetcher, SynchronousFeeds
from paddle_tpu.telemetry.registry import SCHEMA


# -- trainer helpers ----------------------------------------------------------

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


def _batches(n_samples=64, batch=8):
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(6,)).astype(np.float32), int(i % 4))
            for i in range(n_samples)]
    return paddle.reader.batch(lambda: iter(data), batch)


# -- DevicePrefetcher core contract -------------------------------------------

def test_prefetcher_matches_sync_order_and_content():
    def reader():
        for i in range(7):
            yield [(i, j) for j in range(3)]

    sync = list(SynchronousFeeds(reader))
    pre = list(DevicePrefetcher(reader, depth=2))
    assert [fb.feed for fb in pre] == [fb.feed for fb in sync]
    assert [fb.examples for fb in pre] == [3] * 7


def test_prefetcher_propagates_reader_exception():
    def reader():
        yield [1]
        yield [2]
        raise ValueError("disk ate the epoch")

    pf = DevicePrefetcher(reader, depth=2)
    assert next(pf).feed == [1]
    assert next(pf).feed == [2]
    with pytest.raises(ValueError, match="disk ate the epoch"):
        next(pf)
    # terminal: later pulls end the stream instead of hanging
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetcher_propagates_feeder_exception():
    def reader():
        yield [1, 2]

    def bad_feeder(batch):
        raise TypeError("sample shape mismatch")

    pf = DevicePrefetcher(reader, feeder=bad_feeder, depth=2)
    with pytest.raises(TypeError, match="sample shape mismatch"):
        next(pf)


def test_prefetcher_close_unblocks_producer_midstream():
    produced = []

    def reader():
        for i in range(10_000):
            produced.append(i)
            yield [i]

    pf = DevicePrefetcher(reader, depth=2)
    assert next(pf).feed == [0]
    # the bounded queue has the producer blocked in put by now
    pf.close()
    assert not pf._thread.is_alive()
    assert len(produced) < 100  # read-ahead stayed bounded
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetcher_as_context_manager_drains_on_early_exit():
    def reader():
        while True:
            yield [0]

    with DevicePrefetcher(reader, depth=2) as pf:
        next(pf)
    assert not pf._thread.is_alive()


# -- the pooled prefetcher: order, placement, errors, shutdown, bounds ----------

def _image_feeder():
    from paddle_tpu.layers import data_type

    return DataFeeder({"img": data_type.dense_vector(3 * 8 * 8),
                       "lbl": data_type.integer_value(1000)})


def _image_reader(batches=14, batch=8, fail_at=None):
    """``batches`` batches of ``batch`` samples (a [3, 8, 8] float32 row
    and a label), every value distinct across the whole stream; raises
    in place of batch ``fail_at``."""
    def reader():
        for b in range(batches):
            if b == fail_at:
                raise ValueError(f"reader failed at batch {b}")
            base = float(b * batch)
            yield [(np.arange(192, dtype=np.float32).reshape(3, 8, 8)
                    + 1000.0 * (base + i), int(base + i))
                   for i in range(batch)]
    return reader


def _mesh_on(devices):
    return MeshContext(make_mesh({"data": len(devices)}, devices=devices))


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("depth", [1, 3])
def test_pooled_feeds_bit_identical_in_order_on_their_devices(n_dev, depth):
    """Staging arrays are refilled while earlier feeds are still alive:
    fourteen distinct batches come out of the pool exactly as the inline
    path makes them, in reader order, every shard on its mesh device
    (none on device 0, which this mesh leaves out)."""
    import jax

    devices = jax.devices()[1:1 + n_dev]
    ctx = _mesh_on(devices)
    want = list(SynchronousFeeds(_image_reader(), _image_feeder(), ctx))
    with DevicePrefetcher(_image_reader(), _image_feeder(), ctx,
                          depth=depth) as pf:
        got = list(pf)      # all alive at once: no staging array recycled
    assert len(got) == len(want) == 14
    for g, w in zip(got, want):
        assert g.examples == w.examples == 8
        for name in ("img", "lbl"):
            assert g.feed[name].dtype == w.feed[name].dtype
            np.testing.assert_array_equal(np.asarray(g.feed[name]),
                                          np.asarray(w.feed[name]))
            shards = g.feed[name].addressable_shards
            assert [s.device for s in shards] == list(devices)
            rows = 8 // n_dev
            for k, sh in enumerate(shards):
                np.testing.assert_array_equal(
                    np.asarray(sh.data),
                    np.asarray(w.feed[name])[k * rows:(k + 1) * rows])
    assert [int(g.feed["lbl"][0]) for g in got] == list(range(0, 112, 8))


@pytest.mark.parametrize("who", ["reader", "feeder"])
@pytest.mark.parametrize("k", [0, 1, 7])
def test_an_error_at_batch_k_surfaces_after_exactly_k_good_batches(who, k):
    ctx = _mesh_on(__import__("jax").devices()[:2])
    inner = _image_feeder()

    def feeder(batch):
        if who == "feeder" and batch[0][1] == 8 * k:
            raise ValueError(f"feeder failed at batch {k}")
        return inner(batch)

    pf = DevicePrefetcher(
        _image_reader(fail_at=k if who == "reader" else None), feeder, ctx,
        depth=3)
    good = []
    with pytest.raises(ValueError, match=f"{who} failed at batch {k}"):
        for fb in pf:
            good.append(int(fb.feed["lbl"][0]))
    assert good == [8 * i for i in range(k)]
    with pytest.raises(StopIteration):      # terminal, and nothing hangs
        next(pf)
    assert not pf._thread.is_alive()


def _wait_until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


def test_close_with_units_in_flight_returns_within_its_deadline(monkeypatch):
    from paddle_tpu.reader import prefetch as prefetch_mod

    monkeypatch.setattr(prefetch_mod, "CLOSE_DEADLINE_S", 0.5)
    before = threading.active_count()
    entered, release = threading.Semaphore(0), threading.Event()
    inner = _image_feeder()

    def feeder(batch):
        entered.release()
        release.wait(30)
        return inner(batch)

    pf = DevicePrefetcher(_image_reader(batches=1000), feeder,
                          _mesh_on(__import__("jax").devices()[:2]), depth=2)
    assert entered.acquire(timeout=10) and entered.acquire(timeout=10)
    t0 = time.monotonic()
    pf.close()      # both workers are still inside the feeder
    assert time.monotonic() - t0 < 2.0
    with pytest.raises(StopIteration):
        next(pf)
    release.set()
    assert _wait_until(lambda: threading.active_count() <= before), \
        "the pool outlived close()"
    assert not pf._thread.is_alive()


def test_rebind_mesh_with_units_in_flight_replaces_every_feed():
    """Feeds staged or mid-conversion under the old mesh come out on the
    new one: in order, nothing lost, nothing left on the old devices."""
    import jax

    old, new = jax.devices()[:4], jax.devices()[4:6]
    rebound = threading.Event()
    inner = _image_feeder()

    def feeder(batch):
        if batch[0][1] == 8:    # batch 1 waits for the rebind: placed on
            rebound.wait(30)    # the old mesh all the same
        return inner(batch)

    with DevicePrefetcher(_image_reader(batches=6), feeder, _mesh_on(old),
                          depth=2) as pf:
        first = next(pf)
        assert [s.device for s in first.feed["img"].addressable_shards] \
            == list(old)
        pf.rebind_mesh(_mesh_on(new))
        rebound.set()
        rest = list(pf)
    assert [int(fb.feed["lbl"][0]) for fb in rest] == [8, 16, 24, 32, 40]
    want = list(SynchronousFeeds(_image_reader(batches=6), inner,
                                 _mesh_on(new)))[1:]
    for g, w in zip(rest, want):
        assert [s.device for s in g.feed["img"].addressable_shards] \
            == list(new)
        np.testing.assert_array_equal(np.asarray(g.feed["img"]),
                                      np.asarray(w.feed["img"]))


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_in_flight_plus_staged_never_exceeds_depth(depth):
    """The stated bound: units converting + units staged <= depth (with
    the one the consumer holds: depth + 1 feeds a device), whether the
    consumer is the slow side or the workers are."""
    inner = _image_feeder()
    lock = threading.Lock()
    seen = {"now": 0, "max_converting": 0, "max_total": 0, "read": 0}
    pf = None

    def reader():
        for batch in _image_reader(batches=24)():
            with lock:
                seen["read"] += 1
            yield batch

    def feeder(batch):
        with lock:
            seen["now"] += 1
            seen["max_converting"] = max(seen["max_converting"], seen["now"])
        time.sleep(0.002)
        try:
            return inner(batch)
        finally:
            with lock:
                seen["now"] -= 1

    taken = 0
    with DevicePrefetcher(reader, feeder,
                          _mesh_on(__import__("jax").devices()[:2]),
                          depth=depth) as pf:
        for fb in pf:
            taken += 1
            if taken % 6 == 0:
                time.sleep(0.05)    # the pipeline fills behind us
            with pf._count_lock:
                total = pf._in_flight + pf._staged
            with lock:
                seen["max_total"] = max(seen["max_total"], total)
                # read-ahead: what is converting or staged, and the one
                # batch the reader thread holds while it waits for a slot
                assert seen["read"] <= taken + depth + 1
    assert taken == 24
    assert seen["max_converting"] <= depth
    assert 1 <= seen["max_total"] <= depth
    if depth > 1:
        assert seen["max_converting"] > 1   # the overlap engages


def test_settle_keeps_only_staging_the_feed_does_not_live_in():
    """After ``_settle`` whatever is left in the staging dict can be
    overwritten without touching the placed feed — also where the CPU
    backend took an aligned host buffer as the device buffer."""
    import jax

    from paddle_tpu.reader.prefetch import _settle

    ctx = _mesh_on(jax.devices()[:2])
    raw = np.zeros(4 * 1024 + 64, np.float32)
    off = (-raw.ctypes.data % 64) // 4
    aligned = raw[off:off + 4096].reshape(8, 512)       # may be adopted
    skewed = raw[off + 1:off + 1 + 2048].reshape(8, 256)  # must be copied
    aligned[:] = np.arange(4096, dtype=np.float32).reshape(8, 512)
    skewed[:] = -np.arange(2048, dtype=np.float32).reshape(8, 256)
    host_only = np.ones((8, 2), np.float32)
    staging = {"a": aligned, "s": skewed, "h": host_only}
    feed = ctx.shard_batch({"a": aligned, "s": skewed})
    feed["h"] = host_only           # a leaf that never left the host
    want = {k: np.array(v) for k, v in feed.items()}
    _settle(staging, feed)
    assert "h" not in staging and "s" in staging
    for buf in staging.values():
        buf.fill(7.0)
    for k in feed:
        np.testing.assert_array_equal(np.asarray(feed[k]), want[k])


# -- deferred fence + overlap through SGD.train -------------------------------

def _run_train(sync_period, prefetch, n_samples=64, batch=8, passes=2):
    from paddle_tpu import metrics as metrics_mod
    from paddle_tpu.core import rng

    rng.seed(7)
    trainer = _tiny_trainer()
    sink = metrics_mod.MemorySink()
    reg = metrics_mod.MetricsRegistry("test_prefetch")
    reg.add_sink(sink)
    events = []

    def handler(e):
        events.append((type(e).__name__, getattr(e, "batch_id", None)))

    trainer.train(reader=_batches(n_samples, batch), num_passes=passes,
                  event_handler=handler, metrics_registry=reg,
                  sync_period=sync_period, prefetch=prefetch)
    steps = [r for r in sink.records if r.get("kind") == "step"]
    return trainer, steps, events


def test_trajectory_bit_identical_sync_vs_overlapped():
    """Same batches + same RNG key order => the overlapped loop must not
    change training AT ALL: per-step losses and the final parameters are
    bit-identical for (sync_period=1, prefetch=0) vs (4, 2) vs (3, 1)."""
    base_tr, base_steps, base_events = _run_train(1, 0)
    base_losses = [r["loss"] for r in base_steps]
    assert len(base_losses) == 16 and np.all(np.isfinite(base_losses))

    base_ends = [b for n, b in base_events if n == "EndIteration"]
    assert base_ends == list(range(8)) * 2

    for sp, pf in ((4, 2), (3, 1), (100, 2)):
        tr, steps, events = _run_train(sp, pf)
        np.testing.assert_array_equal(
            np.asarray([r["loss"] for r in steps]),
            np.asarray(base_losses),
            err_msg=f"trajectory diverged at sync_period={sp} prefetch={pf}")
        for name in tr.parameters.names():
            np.testing.assert_array_equal(
                np.asarray(tr.parameters[name]),
                np.asarray(base_tr.parameters[name]))
        # EndIteration still fires once per batch, ids in order
        assert [b for n, b in events if n == "EndIteration"] == base_ends


def test_sync_period_1_keeps_v2_event_cadence():
    _, _, events = _run_train(1, 2, n_samples=16, batch=8, passes=1)
    per_batch = [n for n, _ in events
                 if n in ("BeginIteration", "EndForwardBackward",
                          "EndIteration")]
    assert per_batch == ["BeginIteration", "EndForwardBackward",
                        "EndIteration"] * 2


def test_deferred_fence_bursts_and_schema2_fields():
    _, steps, events = _run_train(4, 2, n_samples=32, batch=8, passes=1)
    assert len(steps) == 4
    for r in steps:
        assert r["schema"] == SCHEMA
        assert "input_wait_ms" in r and "host_stall_ms" in r
        assert r["input_wait_ms"] >= 0.0 and r["host_stall_ms"] >= 0.0
    # with sync_period=4 the EndIterations arrive as one burst after the
    # last dispatch: every BeginIteration precedes every EndIteration
    order = [n for n, _ in events if n.endswith("Iteration")]
    assert order == ["BeginIteration"] * 4 + ["EndIteration"] * 4


def test_default_config_keeps_seed_feed_conversion_order(monkeypatch):
    """Unmodified v2 config (prefetch=0, remainder=error): the seed's
    order — reader pull, BeginIteration, THEN feed conversion — so a
    handler may still mutate feeder state for the CURRENT batch.  With
    prefetch, conversion runs ahead of the events (documented)."""
    from paddle_tpu.core import rng
    from paddle_tpu.reader.feeder import DataFeeder

    orig_feed = DataFeeder.feed

    def run(prefetch):
        rng.seed(7)
        trainer = _tiny_trainer()
        trace = []
        monkeypatch.setattr(
            DataFeeder, "feed",
            lambda self, batch, *staging: (
                trace.append("convert"),
                orig_feed(self, batch, *staging))[1])

        def handler(e):
            if type(e).__name__ == "BeginIteration":
                trace.append("begin")

        trainer.train(reader=_batches(16, 8), num_passes=1,
                      event_handler=handler, prefetch=prefetch)
        return trace

    assert run(0) == ["begin", "convert", "begin", "convert"]
    overlapped = run(2)
    assert sorted(overlapped) == sorted(["begin", "convert"] * 2)
    assert overlapped != ["begin", "convert", "begin", "convert"]


def test_sync_input_wait_includes_reader_time():
    """input_wait_ms in the default synchronous path must cover the
    reader pull (the dominant starvation cost), not just conversion."""
    from paddle_tpu import metrics as metrics_mod
    from paddle_tpu.core import rng

    rng.seed(7)
    trainer = _tiny_trainer()
    sink = metrics_mod.MemorySink()
    reg = metrics_mod.MetricsRegistry("wait_test")
    reg.add_sink(sink)
    rngnp = np.random.default_rng(0)

    def reader():
        for i in range(3):
            time.sleep(0.03)
            yield [(rngnp.normal(size=(6,)).astype(np.float32), int(j % 4))
                   for j in range(8)]

    trainer.train(reader=reader, num_passes=1, metrics_registry=reg,
                  sync_period=1, prefetch=0, event_handler=lambda e: None)
    waits = [r["input_wait_ms"] for r in sink.records
             if r.get("kind") == "step"]
    assert len(waits) == 3
    assert all(w >= 25.0 for w in waits), waits


def test_densify_pairs_rejects_fractional_index():
    with pytest.raises(IndexError, match="fractional"):
        _densify_pairs([[(1.5, 0.3)]], 8)


def test_preemption_drain_with_prefetch(tmp_path):
    """SIGTERM mid-pass with the prefetcher running: the loop flushes its
    fence backlog, checkpoints at a batch boundary and returns; the
    worker thread is drained, not leaked."""
    import os
    import signal

    rng = np.random.default_rng(0)

    def reader():
        for i in range(64):
            if i == 16:
                os.kill(os.getpid(), signal.SIGTERM)
            yield rng.normal(size=(6,)).astype(np.float32), int(i % 4)

    before = threading.active_count()
    trainer = _tiny_trainer()
    trainer.train(reader=paddle.reader.batch(reader, 8), num_passes=50,
                  checkpoint_dir=str(tmp_path / "ck"),
                  sync_period=3, prefetch=2)
    from paddle_tpu.trainer import checkpoint as ckpt

    found = ckpt.latest_checkpoint(str(tmp_path / "ck"))
    assert found is not None
    assert found[1]["pass_id"] < 49
    deadline = time.monotonic() + 5
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


# -- reader decorator fixes ---------------------------------------------------

def test_buffered_propagates_reader_exception():
    def failing():
        yield 1
        yield 2
        raise RuntimeError("mid-epoch IO error")

    got = []
    with pytest.raises(RuntimeError, match="mid-epoch IO error"):
        for e in buffered(failing, 2)():
            got.append(e)
    assert got == [1, 2]  # nothing silently truncated before the raise


def test_buffered_early_abandon_unblocks_producer():
    before = threading.active_count()

    def endless():
        i = 0
        while True:
            yield i
            i += 1

    gen = buffered(endless, 2)()
    assert next(gen) == 0
    gen.close()  # consumer walks away mid-stream
    deadline = time.monotonic() + 5
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before, \
        "buffered() leaked its producer thread blocked in Queue.put"


def _consume_with_timeout(reader, timeout=15.0):
    """Drive a reader on a worker thread so a regression to the infinite
    consumer loop fails the test instead of hanging the suite."""
    result: dict = {}

    def consume():
        try:
            result["items"] = list(reader())
        except BaseException as e:
            result["exc"] = e

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "consumer hung (the pre-fix deadlock)"
    return result


def test_xmap_mapper_exception_raises_instead_of_hanging():
    def mapper(x):
        if x == 5:
            raise ValueError("bad sample 5")
        return x * 2

    r = xmap_readers(mapper, lambda: iter(range(32)), process_num=2,
                     buffer_size=4)
    result = _consume_with_timeout(r)
    assert isinstance(result.get("exc"), ValueError)
    assert "bad sample 5" in str(result["exc"])


def test_xmap_source_exception_raises_instead_of_hanging():
    def bad_source():
        yield 1
        raise OSError("source died")

    r = xmap_readers(lambda x: x, bad_source, process_num=3, buffer_size=2)
    result = _consume_with_timeout(r)
    assert isinstance(result.get("exc"), OSError)


def test_xmap_early_abandon_releases_workers():
    before = threading.active_count()

    def endless():
        i = 0
        while True:
            yield i
            i += 1

    gen = xmap_readers(lambda x: x, endless, process_num=3, buffer_size=2)()
    assert next(gen) is not None or True
    gen.close()  # consumer walks away after one item
    deadline = time.monotonic() + 5
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before, \
        "xmap_readers leaked worker threads after early consumer exit"


def test_xmap_ordered_happy_path_unchanged():
    r = xmap_readers(lambda x: x * x, lambda: iter(range(20)),
                     process_num=4, buffer_size=4, order=True)
    result = _consume_with_timeout(r)
    assert result.get("items") == [x * x for x in range(20)]


# -- partial-batch policies ---------------------------------------------------

def _mesh2():
    return MeshContext(mesh=make_mesh({"data": 2}))


def test_apply_remainder_drop_and_pad():
    feed = {"x": np.arange(10, dtype=np.float32).reshape(5, 2),
            "y": np.arange(5)}
    dropped = apply_remainder(feed, 2, "drop")
    assert dropped["x"].shape == (4, 2) and dropped["y"].shape == (4,)
    padded = apply_remainder(feed, 2, "pad")
    assert padded["x"].shape == (6, 2) and padded["y"].shape == (6,)
    np.testing.assert_array_equal(padded["x"][5], feed["x"][4])
    assert padded["y"][5] == feed["y"][4]
    # divisible batches pass through untouched
    ok = {"x": np.zeros((4, 2))}
    assert apply_remainder(ok, 2, "drop") is ok
    # drop smaller-than-mesh -> None (callers skip the batch)
    assert apply_remainder({"x": np.zeros((1, 2))}, 2, "drop") is None
    with pytest.raises(EnforceError):
        apply_remainder(feed, 2, "bogus")


def test_shard_batch_remainder_opt_in():
    ctx = _mesh2()
    feed = {"x": np.zeros((5, 2), np.float32)}
    with pytest.raises(EnforceError):  # default stays strict
        ctx.shard_batch(feed)
    out = ctx.shard_batch(feed, remainder="drop")
    assert out["x"].shape == (4, 2)
    out = ctx.shard_batch(feed, remainder="pad")
    assert out["x"].shape == (6, 2)


def test_prefetcher_remainder_policies_with_mesh():
    ctx = _mesh2()

    def reader():
        yield [(np.zeros(2, np.float32),)] * 4
        yield [(np.ones(2, np.float32),)] * 3  # partial tail batch

    def feeder(batch):
        return {"x": np.stack([s[0] for s in batch])}

    fbs = list(DevicePrefetcher(reader, feeder, ctx, depth=2,
                                remainder="drop"))
    assert [fb.feed["x"].shape[0] for fb in fbs] == [4, 2]
    fbs = list(DevicePrefetcher(reader, feeder, ctx, depth=2,
                                remainder="pad"))
    assert [fb.feed["x"].shape[0] for fb in fbs] == [4, 4]
    # examples still counts REAL samples, not the padded/dropped size
    assert [fb.examples for fb in fbs] == [4, 3]
    # a batch that drops to nothing is skipped, not an error
    def tiny():
        yield [(np.zeros(2, np.float32),)]

    assert list(DevicePrefetcher(tiny, feeder, ctx, remainder="drop")) == []


def test_trainer_test_honors_batch_remainder():
    """trainer.test() on a multi-device mesh must apply the same
    partial-batch policy as training (a 5-sample tail batch on the
    8-device default mesh would otherwise hard-error)."""
    from paddle_tpu.core import flags

    trainer = _tiny_trainer()
    trainer.train(reader=_batches(16, 8), num_passes=1)
    rng = np.random.default_rng(1)
    ragged = [(rng.normal(size=(6,)).astype(np.float32), int(i % 4))
              for i in range(21)]  # 8 + 8 + 5-sample tail

    prev = flags.get("batch_remainder")
    try:
        flags.set("batch_remainder", "drop")
        res = trainer.test(reader=paddle.reader.batch(lambda: iter(ragged), 8))
        assert np.isfinite(res.cost)
        flags.set("batch_remainder", "pad")
        res = trainer.test(reader=paddle.reader.batch(lambda: iter(ragged), 8))
        assert np.isfinite(res.cost)
    finally:
        flags.set("batch_remainder", prev)


# -- vectorized DataFeeder hot path -------------------------------------------

def _densify_ids_ref(rows, dim):
    dense = np.zeros((len(rows), dim), np.float32)
    for i, ids in enumerate(rows):
        dense[i, np.asarray(list(ids), dtype=np.int64)] = 1.0
    return dense


def _densify_pairs_ref(rows, dim):
    dense = np.zeros((len(rows), dim), np.float32)
    for i, pairs in enumerate(rows):
        for j, v in pairs:
            dense[i, j] = v  # the seed's per-pair loop: last write wins
    return dense


def test_densify_ids_vectorized_matches_reference():
    rng = np.random.default_rng(3)
    rows = [list(rng.integers(0, 50, size=rng.integers(0, 8)))
            for _ in range(17)]
    rows[3] = []          # empty row
    rows[5] = [7, 7, 7]   # duplicates collapse to 1
    np.testing.assert_array_equal(
        _densify_ids(rows, 50), _densify_ids_ref(rows, 50))
    assert _densify_ids([[], []], 4).sum() == 0


def test_densify_pairs_vectorized_matches_reference():
    rng = np.random.default_rng(4)
    rows = [[(int(j), float(v)) for j, v in
             zip(rng.integers(0, 30, size=k), rng.normal(size=k))]
            for k in rng.integers(0, 6, size=13)]
    rows[2] = []
    np.testing.assert_allclose(
        _densify_pairs(rows, 30), _densify_pairs_ref(rows, 30), rtol=1e-6)
    # duplicate indices keep the seed's LAST-WRITE-WINS semantic, so
    # v2-era sparse_float datasets produce bit-identical feeds
    out = _densify_pairs([[(3, 1.0), (3, 2.0)]], 8)
    assert out[0, 3] == 2.0
    # malformed pairs still fail fast (the seed's unpack error) instead
    # of silently misaligning every later pair in the flat scan
    with pytest.raises(ValueError):
        _densify_pairs([[(1, 0.5, 9.9)], [(2, 1.0)]], 8)


def test_feeder_uniform_sequence_fast_path_matches_ragged():
    from paddle_tpu.layers.data_type import integer_value_sequence

    feeder = DataFeeder({"w": integer_value_sequence(100)})
    uniform = [([1, 2, 3],), ([4, 5, 6],), ([7, 8, 9],)]
    ragged = [([1, 2, 3],), ([4, 5, 6],), ([7, 8],)]
    fast = feeder.feed(uniform)["w"]
    slow = feeder.feed(ragged)["w"]
    assert fast.data.shape == (3, 16)  # bucket-padded like the slow path
    assert slow.data.shape == (3, 16)
    np.testing.assert_array_equal(np.asarray(fast.data)[:, :3],
                                  [[1, 2, 3], [4, 5, 6], [7, 8, 9]])
    np.testing.assert_array_equal(np.asarray(fast.length), [3, 3, 3])
    assert fast.data.dtype == slow.data.dtype


# -- the feeder's contract: host arrays out, placed once ------------------------

def _feeder_cases():
    from paddle_tpu.layers import data_type as dt

    rows = [np.arange(6, dtype=np.float64) + i for i in range(4)]
    return {
        "dense": (dt.dense_vector(6), [(r,) for r in rows],
                  np.stack(rows).astype(np.float32)),
        "dense_nested_lists": (
            dt.dense_vector(6), [([[1, 2, 3], [4, 5, 6]],)] * 4,
            np.tile(np.arange(1, 7, dtype=np.float32), (4, 1))),
        "integer": (dt.integer_value(10), [(3,), (np.int64(1),), (4,), (1,)],
                    np.asarray([3, 1, 4, 1], np.int32)),
        "sparse_binary": (dt.sparse_binary_vector(5),
                          [([0, 2],), ([],), ([4],), ([1, 1],)],
                          np.asarray([[1, 0, 1, 0, 0], [0, 0, 0, 0, 0],
                                      [0, 0, 0, 0, 1], [0, 1, 0, 0, 0]],
                                     np.float32)),
        "sparse_float": (dt.sparse_float_vector(4),
                         [([(0, .5)],), ([(3, 2.)],), ([],), ([(1, -1.)],)],
                         np.asarray([[.5, 0, 0, 0], [0, 0, 0, 2.],
                                     [0, 0, 0, 0], [0, -1., 0, 0]],
                                    np.float32)),
    }


@pytest.mark.parametrize("kind", sorted(_feeder_cases()))
def test_feeder_non_sequence_kinds_return_host_arrays(kind):
    itype, batch, want = _feeder_cases()[kind]
    got = DataFeeder({"s": itype}).feed(batch)["s"]
    assert type(got) is np.ndarray      # not a device array
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["integer", "dense"])
def test_feeder_uniform_sequence_path_returns_host_arrays(kind):
    from paddle_tpu.layers import data_type as dt

    if kind == "integer":
        itype, seqs, dtype = dt.integer_value_sequence(100), \
            [[1, 2, 3], [4, 5, 6]], np.int32
    else:
        itype, seqs, dtype = dt.dense_vector_sequence(2), \
            [[[1., 2.]] * 3, [[3., 4.]] * 3], np.float32
    got = DataFeeder({"w": itype}).feed([(q,) for q in seqs])["w"]
    assert type(got.data) is np.ndarray and type(got.length) is np.ndarray
    assert got.data.dtype == dtype and got.length.dtype == np.int32
    assert got.data.shape[:2] == (2, 16)    # bucket-padded as before
    np.testing.assert_array_equal(got.data[:, :3], np.asarray(seqs, dtype))
    assert not got.data[:, 3:].any()
    np.testing.assert_array_equal(got.length, [3, 3])


def test_feeder_staging_is_refilled_only_where_the_shape_still_fits():
    from paddle_tpu.layers import data_type as dt

    feeder = DataFeeder({"img": dt.dense_vector(12),
                         "lbl": dt.integer_value(10)})

    def batch(n, base):
        return [(np.full((3, 4), base + i, np.float64), i) for i in range(n)]

    staging: dict = {}
    first = feeder.feed(batch(4, 10.0), staging)
    assert set(staging) == {"img"}      # python ints are not staged
    buf = staging["img"]
    assert buf.shape == (4, 3, 4) and np.shares_memory(first["img"], buf)
    second = feeder.feed(batch(4, 50.0), staging)
    assert staging["img"] is buf and np.shares_memory(second["img"], buf)
    assert second["img"].dtype == np.float32
    np.testing.assert_array_equal(
        second["img"], feeder.feed(batch(4, 50.0))["img"])
    third = feeder.feed(batch(3, 70.0), staging)    # a partial last batch
    assert staging["img"] is not buf and third["img"].shape == (3, 12)
    np.testing.assert_array_equal(
        third["img"], feeder.feed(batch(3, 70.0))["img"])
    # without staging every call owns its arrays
    assert not np.shares_memory(feeder.feed(batch(4, 1.0))["img"],
                                feeder.feed(batch(4, 1.0))["img"])


def test_host_feed_on_a_mesh_without_device_0_leaves_nothing_there():
    import jax

    dev0 = jax.devices()[0]
    ctx = _mesh_on(jax.devices()[2:4])
    batch = next(iter(_image_reader(batches=1)()))
    before = {id(a) for a in jax.live_arrays()}
    out = ctx.shard_batch(_image_feeder()(batch))
    jax.block_until_ready(out)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert len(new) >= 2
    assert all(dev0 not in a.devices() for a in new)
    assert [s.device for s in out["img"].addressable_shards] \
        == jax.devices()[2:4]


def test_inference_and_sgd_test_run_from_host_feeds():
    """Callers without a prefetcher: ``paddle.infer`` hands the feeder's
    host arrays to ``jit``, ``SGD.test`` places them inline."""
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    base.reset_name_counters()
    x = layer.data(name="px", type=data_type.dense_vector(6))
    h = layer.fc(input=x, size=4, act=act.SoftmaxActivation())
    lbl = layer.data(name="py", type=data_type.integer_value(4))
    cost = layer.classification_cost(input=h, label=lbl)
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.SGD(learning_rate=0.05))
    trainer.train(reader=_batches(16, 8), num_passes=1)
    rng = np.random.default_rng(2)
    xs = [(rng.normal(size=(6,)).astype(np.float32),) for _ in range(8)]
    probs = paddle.infer(output_layer=h, parameters=trainer.parameters,
                         input=xs)
    assert probs.shape == (8, 4) and np.all(np.isfinite(probs))
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)
    res = trainer.test(reader=_batches(16, 8))
    assert np.isfinite(res.cost)


@pytest.mark.slow
def test_overlap_speeds_up_slow_reader():
    """The acceptance property (lenient CI threshold): reader sleep ≈
    step time must overlap."""

    def timed(sync_period, prefetch):
        from paddle_tpu.core import rng

        rng.seed(7)
        trainer = _tiny_trainer()
        rngnp = np.random.default_rng(0)
        data = [(rngnp.normal(size=(6,)).astype(np.float32), int(i % 4))
                for i in range(96)]

        def reader():
            for i in range(0, 96, 8):
                time.sleep(0.02)
                yield [data[j] for j in range(i, i + 8)]

        trainer.train(reader=lambda: iter([data[:8]]), num_passes=1,
                      sync_period=1, prefetch=0)  # pay the compile
        t0 = time.perf_counter()
        trainer.train(reader=reader, num_passes=1,
                      sync_period=sync_period, prefetch=prefetch)
        return time.perf_counter() - t0

    # wall-clock on a shared CI box is noisy: best of 2 per side
    t_sync = min(timed(1, 0) for _ in range(2))
    t_pre = min(timed(8, 2) for _ in range(2))
    assert t_pre < t_sync, (t_sync, t_pre)
