"""Layer boundaries as spans: inside the feed pipeline
(``reader/prefetch.py``), inside the serving engine's step
(``serving/engine.py``), and their mirrors on the profiler's clock
(``telemetry/tracing.py``).

What holds: a batch's production is ``feed_read`` and ``feed_stage`` on
the prefetcher's reader thread and one ``prefetch`` (``staged``,
``in_flight``) ⊃ ``feed_convert`` / ``feed_place`` on a pool worker, each
worker in a lane of its own (``feed_read`` / ``feed_convert`` /
``feed_place`` under ``feed`` with prefetch off), with ``examples`` /
``bytes`` / ``shards`` / ``from_host`` stated and the children summing to
the parent on a fake clock; an engine iteration that worked is
``serve_step`` ⊃ ``serve_schedule`` / ``serve_prefill`` /
``serve_decode`` with the two batch spans still leaves and
``context_tokens`` the live sequences' lengths, an idle one records
nothing; tracing off computes no argument and changes no result; every
live span shows up as a host event of its name in a ``jax.profiler``
trace taken meanwhile; a dump states its clock and ``trace_merge``
aligns lanes by it.
"""

import glob
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.core import flags
from paddle_tpu.models import transformer as T
from paddle_tpu.reader import prefetch as prefetch_mod
from paddle_tpu.reader.prefetch import DevicePrefetcher, SynchronousFeeds
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry
from paddle_tpu.telemetry.tracing import Tracer, get_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = "paddle-tpu-prefetch"


class _Clock:
    """A clock that only the instrumented work advances — each thread's
    own: a span opens and closes on one thread, so what another thread
    does meanwhile cannot stretch it."""

    def __init__(self):
        self._local = threading.local()

    @property
    def t(self):
        return getattr(self._local, "t", 100.0)

    @t.setter
    def t(self, value):
        self._local.t = value

    def __call__(self):
        return self.t


@pytest.fixture
def tracer():
    """The process tracer, enabled and empty; restored afterwards."""
    snap = flags.snapshot_raw()
    t = get_tracer()
    was_enabled, was_clock = t.enabled, t.clock
    t.configure(enabled=True)
    t.drain()       # clear() leaves the kept set-up and xla spans
    yield t
    t.configure(enabled=was_enabled, clock=was_clock)
    t.drain()
    flags.restore_raw(snap)


def _live(spans):
    """Without what is kept beside the ring: set-up phases, and XLA's
    builds (retrospective children of whatever span was open when jax
    compiled, which then carries ``compiles`` / ``cache_fetches``)."""
    return [s for s in spans if s.cat not in ("setup", "xla")]


def _own(args):
    return {k: v for k, v in args.items()
            if k not in ("compiles", "cache_fetches")}


def _by_name(spans):
    out: dict = {}
    for s in _live(spans):
        out.setdefault(s.name, []).append(s)
    return out


class _Mesh:
    """Stands in for a MeshContext: placing takes 5 s of the fake clock."""

    num_replicas = 4

    def __init__(self, clk):
        self.clk = clk

    def shard_batch(self, feed):
        self.clk.t += 5.0
        return feed


def _timed_pipeline(clk, batches=3, rows=6):
    """(reader, feeder): a pull takes 2 s of the fake clock, a
    conversion 3 s; a batch is ``rows`` samples of 4 float32."""
    def reader():
        for b in range(batches):
            clk.t += 2.0
            yield [(np.full((4,), b, np.float32), b) for _ in range(rows)]

    def feeder(batch):
        clk.t += 3.0
        return {"x": np.stack([r[0] for r in batch]),
                "y": np.asarray([r[1] for r in batch], np.int32)}

    return reader, feeder


# -- the feed pipeline ---------------------------------------------------------


def test_worker_spans_nest_under_prefetch_and_sum_to_it(tracer):
    clk = _Clock()
    tracer.configure(clock=clk)
    reader, feeder = _timed_pipeline(clk)
    with DevicePrefetcher(reader, feeder, _Mesh(clk), depth=2) as feeds:
        got = list(feeds)
    assert [fb.examples for fb in got] == [6, 6, 6]
    spans = _live(tracer.spans)
    by = _by_name(spans)
    # the reader thread's lane: the pulls and the waits for a free slot
    # (the end-of-stream pull is cancelled)
    assert len(by["feed_read"]) == len(by["feed_stage"]) == 3
    for s in by["feed_read"] + by["feed_stage"]:
        assert s.thread == WORKER and s.parent_id is None
    assert [s.dur_ms for s in by["feed_read"]] == [2e3] * 3
    assert all(s.args == {"examples": 6} for s in by["feed_read"])
    assert all(s.args == {} for s in by["feed_stage"])
    # a worker's lane: one prefetch a unit, its two phases inside
    assert len(by["prefetch"]) == 3
    nbytes = 6 * 4 * 4 + 6 * 4      # x float32 [6,4] + y int32 [6]
    for parent in by["prefetch"]:
        assert parent.thread.startswith(WORKER + "_")
        kids = sorted((s for s in spans if s.parent_id == parent.span_id),
                      key=lambda s: s.t_start)
        assert [k.name for k in kids] == ["feed_convert", "feed_place"]
        assert {k.thread for k in kids} == {parent.thread}
        assert [k.dur_ms for k in kids] == [3e3, 5e3]
        assert sum(k.dur_ms for k in kids) == parent.dur_ms == 8e3
        assert kids[0].t_start == parent.t_start
        assert kids[-1].t_end == parent.t_end
        convert, place = kids
        assert convert.args == {"bytes": nbytes}
        assert place.args == {"bytes": nbytes, "shards": 4,
                              "from_host": nbytes}
        assert set(parent.args) == {"staged", "in_flight"}
    assert all(s.cat == "reader" for s in spans)


def test_concurrent_units_land_in_their_own_lanes(tracer):
    """Two units in hand at once: each worker's spans nest in its own
    lane, and ``in_flight`` says how many others were in hand when a
    unit started (0 on every span would mean nothing ever overlapped)."""
    both = threading.Barrier(2, timeout=30)

    def reader():
        for b in range(4):
            yield [(np.full((4,), b, np.float32), b)] * 2

    def feeder(batch):
        if batch[0][1] < 2:
            both.wait()         # units 0 and 1 are in hand together
        return {"x": np.stack([r[0] for r in batch])}

    with DevicePrefetcher(reader, feeder, depth=2) as feeds:
        got = list(feeds)
    assert [int(fb.feed["x"][0, 0]) for fb in got] == [0, 1, 2, 3]
    spans = tracer.spans
    units = _by_name(spans)["prefetch"]
    assert len(units) == 4
    first_two = sorted(units, key=lambda s: s.t_start)[:2]
    assert sorted(s.args["in_flight"] for s in first_two) == [0, 1]
    assert len({s.thread for s in first_two}) == 2
    assert all(0 <= s.args["in_flight"] <= 1 and 0 <= s.args["staged"] <= 1
               for s in units)
    for unit in units:
        kids = [s for s in spans if s.parent_id == unit.span_id]
        assert [k.name for k in kids] == ["feed_convert"]   # no mesh
        assert kids[0].thread == unit.thread
        assert kids[0].args == {"bytes": 2 * 4 * 4}


def test_from_host_tells_a_host_feed_from_one_placed_again(tracer):
    """``feed_place.from_host`` is the bytes that were host arrays when
    they were placed; a feeder that hands over device arrays (the hop
    through the default device) reads 0."""
    import jax.numpy as jnp

    from paddle_tpu.layers import data_type
    from paddle_tpu.parallel.mesh import MeshContext, make_mesh
    from paddle_tpu.reader.feeder import DataFeeder

    mesh = MeshContext(make_mesh({"data": 4}))

    def reader():
        yield [(np.ones((6,), np.float32), 1)] * 8

    feeder = DataFeeder({"x": data_type.dense_vector(6),
                         "y": data_type.integer_value(4)})
    for feed_fn, want in ((feeder, 8 * 6 * 4 + 8 * 4),
                          (lambda b: {k: jnp.asarray(v)
                                      for k, v in feeder(b).items()}, 0)):
        tracer.clear()
        with DevicePrefetcher(reader, feed_fn, mesh, depth=2) as feeds:
            assert len(list(feeds)) == 1
        (place,) = _by_name(tracer.spans)["feed_place"]
        assert _own(place.args) == {"bytes": 8 * 6 * 4 + 8 * 4, "shards": 4,
                                    "from_host": want}


def test_synchronous_feeds_record_the_same_three_names(tracer):
    clk = _Clock()
    tracer.configure(clock=clk)
    reader, feeder = _timed_pipeline(clk, batches=2)
    got = list(SynchronousFeeds(reader, feeder, _Mesh(clk)))
    assert len(got) == 2
    by = _by_name(tracer.spans)
    assert set(by) == {"feed_read", "feed_convert", "feed_place"}
    assert [s.dur_ms for s in by["feed_read"]] == [2e3, 2e3]
    assert [s.dur_ms for s in by["feed_convert"]] == [3e3, 3e3]
    assert [s.dur_ms for s in by["feed_place"]] == [5e3, 5e3]
    assert by["feed_place"][0].args["shards"] == 4


def test_a_dropped_batch_has_no_place_span(tracer):
    from paddle_tpu.parallel.mesh import MeshContext, make_mesh

    mesh = MeshContext(make_mesh({"data": 4}))

    def reader():
        yield [(np.zeros((4,), np.float32), 0)] * 2     # < 4: dropped whole
        yield [(np.zeros((4,), np.float32), 0)] * 8

    def feeder(batch):
        return {"x": np.stack([r[0] for r in batch])}

    got = list(SynchronousFeeds(reader, feeder, mesh, remainder="drop"))
    assert [fb.examples for fb in got] == [8]
    by = _by_name(tracer.spans)
    assert len(by["feed_read"]) == 2 and len(by["feed_convert"]) == 2
    assert len(by["feed_place"]) == 1
    assert by["feed_place"][0].args == {"bytes": 8 * 4 * 4, "shards": 4,
                                        "from_host": 8 * 4 * 4}


def _tiny_trainer():
    from paddle_tpu.core import rng
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    base.reset_name_counters()
    rng.seed(7)
    x = layer.data(name="px", type=data_type.dense_vector(6))
    h = layer.fc(input=x, size=4, act=act.SoftmaxActivation())
    lbl = layer.data(name="py", type=data_type.integer_value(4))
    cost = layer.classification_cost(input=h, label=lbl)
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    return paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.SGD(learning_rate=0.1))


def _train(prefetch: int, n_samples=32):
    rng = np.random.default_rng(0)
    data = [(rng.normal(size=(6,)).astype(np.float32), int(i % 4))
            for i in range(n_samples)]
    trainer = _tiny_trainer()
    losses = []

    def handler(e):
        if isinstance(e, paddle.event.EndIteration):
            losses.append(float(e.cost))

    trainer.train(reader=paddle.reader.batch(lambda: iter(data), 8),
                  num_passes=1, event_handler=handler,
                  metrics_registry=MetricsRegistry("layer_spans"),
                  prefetch=prefetch)
    return trainer, losses


def test_trainer_inline_path_nests_the_three_under_feed(tracer):
    _train(prefetch=0)
    spans = tracer.spans
    by = _by_name(spans)
    feeds = {s.span_id for s in by["feed"]}
    assert len(feeds) == 4 and len(by["step"]) == 4
    for name in ("feed_read", "feed_convert", "feed_place"):
        assert len(by[name]) == 4
        assert all(s.parent_id in feeds for s in by[name])
        assert all(s.thread == "MainThread" for s in by[name])
    assert all(s.args["examples"] == 8 for s in by["feed_read"])
    # px float32 [8,6] + py int32 [8]
    assert all(s.args["bytes"] == 8 * 6 * 4 + 8 * 4
               for s in by["feed_convert"] + by["feed_place"])
    assert "prefetch" not in by and "feed_stage" not in by


def test_trainer_prefetch_path_keeps_feed_a_leaf(tracer):
    _train(prefetch=2)
    spans = tracer.spans
    by = _by_name(spans)
    parents = {s.parent_id for s in spans}
    assert len(by["feed"]) == 4
    # the ledger's idle gaps are named after this leaf on the main thread
    assert all(s.span_id not in parents and s.thread == "MainThread"
               for s in by["feed"])
    units = {s.span_id: s for s in by["prefetch"]}
    assert len(units) == 4
    for name in ("feed_read", "feed_stage"):    # the reader thread's lane
        assert len(by[name]) == 4
        assert all(s.parent_id is None and s.thread == WORKER
                   for s in by[name])
    for name in ("feed_convert", "feed_place"):     # a worker's lane
        assert len(by[name]) == 4
        assert all(s.parent_id in units
                   and s.thread == units[s.parent_id].thread
                   and s.thread.startswith(WORKER + "_") for s in by[name])
    # px float32 [8,6] + py int32 [8]: still the whole batch's host bytes
    nbytes = 8 * 6 * 4 + 8 * 4
    assert all(s.args == {"bytes": nbytes} for s in by["feed_convert"])
    assert all(s.args["from_host"] == s.args["bytes"] == nbytes
               for s in by["feed_place"])
    for p in by["prefetch"]:
        kids = [s for s in spans if s.parent_id == p.span_id]
        assert all(p.t_start <= k.t_start and k.t_end <= p.t_end
                   for k in kids)
        assert sum(k.dur_ms for k in kids) <= p.dur_ms + 1e-6


# -- the engine step -----------------------------------------------------------


def _engine(max_seq_len=64, **kw):
    cfg = T.TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, embed_dim=32,
        mlp_dim=64, max_seq_len=max_seq_len, remat=False)
    params = T.init_params(cfg, jax.random.key(1))
    serving = dict(max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
                   max_new_tokens=4, seed=0)
    serving.update(kw)
    return ServingEngine(cfg, params, ServingConfig(**serving),
                         registry=MetricsRegistry("engine_spans"))


def _inside(child, parent) -> bool:
    return (parent.t_start <= child.t_start
            and child.t_end <= parent.t_end)


@pytest.mark.serving
@pytest.mark.parametrize("incremental", [False, True])
def test_serve_step_holds_schedule_prefill_and_decode(tracer, incremental):
    eng = _engine(prefill_chunk_tokens=4 if incremental else 0)
    real = eng.scheduler.decode_batch
    expected = []

    def counted():
        batch = real()
        if batch is not None:
            # the tokens a pass in flight has sampled are context too
            expected.append(sum(a.prompt_len + a.sampled
                                for a in batch["live"]))
        return batch

    eng.scheduler.decode_batch = counted
    res = eng.generate([[5, 17, 3], [9, 2, 4, 4, 1, 7]], max_new_tokens=3)
    assert [len(r.tokens) for r in res] == [3, 3]
    spans = _live(tracer.spans)     # under incremental prefill the first
    by = _by_name(spans)            # pass compiles: xla_* children
    steps = {s.span_id: s for s in by["serve_step"]}
    parents = {s.parent_id for s in spans}
    assert steps and all({"waiting", "active"} <= set(s.args)
                         for s in steps.values())
    assert by["serve_step"][0].args["waiting"] == 2
    for name in ("serve_schedule", "serve_prefill", "serve_decode"):
        assert by[name], name
        for s in by[name]:
            assert s.parent_id in steps and _inside(s, steps[s.parent_id])
            assert s.cat == "serving"
    # the ledger's serve breakdown is handed LEAF spans of these names
    for s in by["serve_prefill"] + by["serve_decode"]:
        assert s.span_id not in parents
    assert all(s.span_id not in parents for s in by["serve_schedule"])
    assert [s.args["context_tokens"] for s in by["serve_decode"]] == expected
    if not incremental:     # both prompts resident after one pass
        assert expected[0] == (3 + 1) + (6 + 1)
    for s in by["serve_decode"]:
        # the dispatch call's own time; read at once (the incremental
        # path), a pass's span holds its dispatch
        assert 0.0 <= s.args["dispatch_ms"]
        if incremental:
            assert s.args["dispatch_ms"] <= s.dur_ms + 1e-3
        assert s.args["batch"] >= 1
    # a step's children never overlap: its self time is what is left
    for sid, step in steps.items():
        kids = sorted((s for s in spans if s.parent_id == sid),
                      key=lambda s: s.t_start)
        assert all(a.t_end <= b.t_start for a, b in zip(kids, kids[1:]))


@pytest.mark.serving
@pytest.mark.parametrize("incremental", [False, True])
def test_one_span_a_pass_and_none_overlap_one_pass_ahead(tracer, incremental):
    """Three requests through two slots.  The loop dispatches a decode
    step before it reads the pass before it (``ahead`` = 1) — the
    incremental prefill path reads every pass at once (0) — and either way
    there is one ``serve_decode`` leaf a decode step with the args the
    reducers read, closed at its read-back, and no two batch spans of the
    loop's thread overlap; the two counters say what the loop did."""
    reg = MetricsRegistry("ahead_spans")
    eng = _engine(prefill_batch=2, prefill_chunk_tokens=8 if incremental
                  else 0)
    eng.registry = reg
    for prompt, n in (([5, 17, 3], 3), ([9, 2, 4, 4, 1, 7], 2),
                      ([8, 8, 1], 2)):
        eng.submit(prompt, n)
    eng.run_until_idle()
    assert sorted(len(r.tokens) for r in eng.results()) == [2, 2, 3]
    by = _by_name(tracer.spans)
    dec, pre = by["serve_decode"], by["serve_prefill"]
    # a row for every token a decode step handed out: A and B together,
    # then A alone, then C — read at once, B's slot is refilled and C
    # decodes beside A, one step sooner
    assert [s.args["batch"] for s in dec] == ([2, 2] if incremental
                                              else [2, 1, 1])
    assert len(pre) == 2
    assert reg.get("serve_decode_step_ms").summary()["count"] == len(dec)
    assert reg.get("serve_prefill_ms").summary()["count"] == len(pre)
    for s in dec:
        assert {"batch", "context_tokens", "kv_block_tokens", "dispatch_ms",
                "loop_steps", "cache_layers", "ahead"} <= set(s.args)
    passes = sorted(dec + pre, key=lambda s: s.t_start)
    assert len({s.thread for s in passes}) == 1
    assert all(a.t_end <= b.t_start for a, b in zip(passes, passes[1:]))
    parents = {s.parent_id for s in _live(tracer.spans)}
    assert all(s.span_id not in parents for s in passes)      # leaves
    value = lambda name, **lab: (reg.get(name).value(**lab)
                                 if reg.get(name) else 0)
    if incremental:
        assert {s.args["ahead"] for s in passes} == {0}
        assert value("serve_passes_ahead_total", kind="decode") == 0
        assert value("serve_loop_drains_total",
                     why="incremental") == len(dec)
        assert value("serve_loop_drains_total", why="idle") == 0
    else:
        # every decode step went out behind an unread pass; the second
        # prefill pass behind an unread decode step, the first behind none
        assert [s.args["ahead"] for s in dec] == [1, 1, 1]
        assert [s.args["ahead"] for s in pre] == [0, 1]
        assert value("serve_passes_ahead_total", kind="decode") == 3
        assert value("serve_passes_ahead_total", kind="prefill") == 1
        assert value("serve_loop_drains_total", why="idle") == 1
        assert value("serve_loop_drains_total", why="incremental") == 0
    assert value("serve_tokens_dropped_total") == 0


@pytest.mark.serving
def test_serve_prefill_says_what_its_shape_carried(tracer):
    """A pass's span names the shape it ran (``rows``, ``length``,
    ``padded_tokens`` = rows x length) and the prompt tokens in it; two counters sum the same
    over the passes, so their ratio is the fill share; the gauge (set
    once, in the registry the engine was built with) is the number of
    shapes the engine compiles."""
    reg = MetricsRegistry("prefill_fill")
    eng = _engine(max_slots=4, max_prompt_len=24, prefill_batch=4)
    built_with, eng.registry = eng.registry, reg
    script = [((10,), 1), ((20, 3), 4), ((24,), 1), ((9, 9, 9), 4)]
    for lens, _ in script:
        eng.generate([[7] * n for n in lens], max_new_tokens=2)
    passes = _by_name(tracer.spans)["serve_prefill"]
    assert len(passes) == len(script)
    for span, (lens, rows) in zip(passes, script):
        assert span.args["batch"] == len(lens)
        assert span.args["rows"] == rows
        assert span.args["length"] == 24
        assert span.args["padded_tokens"] == rows * 24
        assert span.args["prompt_tokens"] == sum(lens)
    padded = reg.get("serve_prefill_padded_tokens_total").value()
    prompt = reg.get("serve_prefill_prompt_tokens_total").value()
    assert padded == (1 + 4 + 1 + 4) * 24
    assert prompt == 10 + 23 + 24 + 27
    assert prompt / padded == pytest.approx(84 / 240)   # the fill share
    # how often each length of the ladder ran: here it has one
    assert reg.get("serve_prefill_passes_total").value(length=24) == 4
    assert built_with.get("serve_prefill_programs").value() == len(
        eng.scheduler.prefill_rows) == 2


@pytest.mark.serving
def test_serve_decode_says_what_the_kernel_fetches(tracer):
    """``kv_block_tokens``: every live context rounded up to whole PAGES
    (the decode kernel copies a row's live pages, not whole blocks),
    beside ``context_tokens``, the part of it that is live."""
    eng = _engine(max_prompt_len=24, max_new_tokens=6)
    page = eng.serving.page_size
    assert page == 4
    eng.generate([[5, 17, 3], list(range(1, 21))], max_new_tokens=6)
    decodes = _by_name(tracer.spans)["serve_decode"]
    assert decodes
    for d in decodes:
        ctx, got = d.args["context_tokens"], d.args["kv_block_tokens"]
        assert got >= ctx and got % page == 0
        assert got < ctx + d.args["batch"] * page  # under a page a row
    # two rows of 4 and 21 tokens: one page and six
    assert decodes[0].args["context_tokens"] == 25
    assert decodes[0].args["kv_block_tokens"] == 28


@pytest.mark.serving
@pytest.mark.parametrize("pages", [8, 640])
def test_serve_decode_says_how_the_kernel_steps(tracer, pages):
    """``kv_block_len`` (the tokens a grid step of the decode kernel
    covers: ``decode_block_pages`` of the cache's shape) and ``kv_steps``
    (the kernel's grid a cache layer: a step for every block that holds a
    live token, an idle slot one) — from the HOST's lengths: the loop is
    still a pass ahead, so no device read crept in."""
    from paddle_tpu.ops.pallas.paged_attention import decode_block_pages

    reg = MetricsRegistry("kernel_steps")
    eng = _engine(max_slots=3, max_prompt_len=24, num_pages=3 * pages + 1,
                  max_new_tokens=4 * pages - 24, max_seq_len=4 * pages)
    assert eng.serving.max_pages_per_seq == pages
    eng.registry = reg
    block = 4 * decode_block_pages(2, 4, 16, 4, pages)
    # this toy's cache (2 heads of 16, float32, pages of 4) would carry a
    # megabyte in 4,096 tokens: the whole of the narrow table; under the
    # wide one the VMEM budget binds first (a page pads to the tile's 8 rows)
    assert block == {8: 32, 640: 1024}[pages]
    prompts = [[5, 17, 3], list(range(1, 21))]
    eng.generate(prompts, max_new_tokens=6)
    decodes = _by_name(tracer.spans)["serve_decode"]
    assert [d.args["batch"] for d in decodes] == [2] * 5
    for i, d in enumerate(decodes):
        # what the step reads: each prompt, its first token and i more;
        # the third slot idles and takes its one step
        lens = [len(p) + 1 + i for p in prompts] + [0]
        assert d.args["context_tokens"] == sum(lens)
        assert d.args["kv_block_len"] == block
        assert d.args["kv_steps"] == 3
    assert [d.args["ahead"] for d in decodes] == [1] * len(decodes)
    assert reg.get("serve_passes_ahead_total").value(
        kind="decode") == len(decodes)
    # lengths around a block's end, as the kernel's work list counts them
    said = eng._context_args(np.array([0, 1, block, block + 1, 3 * block]))
    assert said["kv_steps"] == 1 + 1 + 1 + 2 + 3
    assert said["kv_block_tokens"] == 4 + block + (block + 4) + 3 * block


@pytest.mark.serving
def test_an_idle_step_records_nothing(tracer):
    eng = _engine()
    eng.generate([[5, 17, 3]], max_new_tokens=2)
    tracer.drain()
    assert eng.step() is False and eng.step() is False
    assert tracer.spans == []
    # and leaves nothing open on this thread's stack
    with tracer.span("after"):
        pass
    assert tracer.spans[0].parent_id is None


@pytest.mark.serving
def test_a_failing_step_leaves_no_open_span(tracer):
    eng = _engine()
    eng.submit([5, 17, 3], max_new_tokens=2)

    def boom(*a, **kw):
        raise RuntimeError("device lost")

    boom.lower = boom   # getting ready lowers the program before any pass
    eng._prefill = boom
    with pytest.raises(RuntimeError, match="device lost"):
        eng.step()
    with tracer.span("after"):
        pass
    assert [s for s in tracer.spans if s.name == "after"][0].parent_id is None
    assert not [s for s in tracer.spans if s.name == "serve_step"]


# -- tracing off ---------------------------------------------------------------


@pytest.mark.serving
def test_disabled_tracing_computes_no_argument(monkeypatch):
    """Off, the new call sites read no clock and size no feed."""
    t = get_tracer()
    was_enabled, was_clock = t.enabled, t.clock
    t.configure(enabled=False)
    t.drain()

    def boom(*a, **kw):
        raise AssertionError("computed with tracing off")

    t.clock = boom
    monkeypatch.setattr(prefetch_mod, "_feed_bytes", boom)
    try:
        _, losses = _train(prefetch=2)
        _, losses0 = _train(prefetch=0)
        eng = _engine()
        eng.queued = boom
        res = eng.generate([[5, 17, 3], [9, 2]], max_new_tokens=3)
    finally:
        t.configure(enabled=was_enabled, clock=was_clock)
    assert len(losses) == 4 and losses == losses0
    assert [len(r.tokens) for r in res] == [3, 3]
    assert t.spans == []


@pytest.mark.serving
def test_traced_engine_serves_the_same_tokens(tracer):
    prompts = [[5, 17, 3], [9, 2, 4], [1, 1, 2, 3, 5]]
    traced = [r.tokens for r in _engine().generate(prompts, 4, 0.7)]
    assert tracer.spans
    tracer.configure(enabled=False)
    plain = [r.tokens for r in _engine().generate(prompts, 4, 0.7)]
    assert traced == plain


# -- one clock with the device trace -------------------------------------------


def _host_events(logdir) -> dict:
    """{event name: [(start_ns, duration_ns)]} over the host planes of
    the newest xplane under ``logdir``."""
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(files[-1])
    out: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns))
    return out


def _start_trace(logdir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)


def test_live_spans_are_host_events_of_a_profiler_trace(tmp_path):
    t = Tracer(enabled=True, rank=0)
    before = t.begin("opened_before_the_trace")
    _start_trace(tmp_path)
    try:
        def work():
            with t.span("prefetch", cat="reader"):
                with t.span("feed_convert", cat="reader"):
                    time.sleep(0.01)

        th = threading.Thread(target=work, name=WORKER)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
        with t.span("step", cat="trainer"):
            with t.span("compute", cat="trainer"):
                time.sleep(0.005)
            cancelled = t.begin("feed")
            t.cancel(cancelled)
        t.add_span("request", 0.0, 1.0)      # retrospective: no mirror
        t.end(before)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    for name in ("prefetch", "feed_convert", "step", "compute", "feed"):
        assert len(events.get(name, ())) == 1, name
    assert "request" not in events
    # mirrors are on the profiler's clock with the span's own extent
    spans = {s.name: s for s in t.spans}
    for name in ("feed_convert", "compute"):
        assert events[name][0][1] / 1e6 == pytest.approx(
            spans[name].dur_ms, abs=2.0)
    (s0, d0), (s1, d1) = events["step"][0], events["compute"][0]
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    # a disabled tracer mirrors nothing
    off = Tracer(enabled=False)
    assert off.begin("x") is None


def test_abandoned_children_close_their_mirrors_with_the_parent(tmp_path):
    """Closing a non-top token truncates the stack above it; the
    mirrors above close too, innermost first, so later spans nest
    right in the profile as in the ring."""
    t = Tracer(enabled=True, rank=0)
    _start_trace(tmp_path)
    try:
        outer = t.begin("outer_span")
        t.begin("abandoned_child")
        t.end(outer)
        with t.span("next_span"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in t.spans] == ["outer_span", "next_span"]
    assert t.spans[1].parent_id is None
    events = _host_events(str(tmp_path))
    (so, do), (sc, dc), (sn, dn) = (events["outer_span"][0],
                                    events["abandoned_child"][0],
                                    events["next_span"][0])
    assert so <= sc and sc + dc <= so + do      # closed inside its parent
    assert sn >= so + do                        # and the next one after it


def test_profile_window_arms_the_tracer_and_needs_no_step_marker(tmp_path):
    """``--profile_steps`` alone (no ``--trace_spans``): the window arms
    span tracing, the capture holds the window's spans by name and no
    ``train_step_<n>`` marker, and the record is what it was."""
    from paddle_tpu.telemetry import MemorySink

    snap = flags.snapshot_raw()
    t = get_tracer()
    was = t.enabled
    t.configure(enabled=False)
    t.clear()
    flags.set("trace_spans", False)
    flags.set("profile_steps", "1:4")
    flags.set("profile_dir", str(tmp_path / "prof"))
    reg = MetricsRegistry("profile_window")
    sink = MemorySink()
    reg.add_sink(sink)
    try:
        trainer = _tiny_trainer()
        rng = np.random.default_rng(0)
        data = [(rng.normal(size=(6,)).astype(np.float32), int(i % 4))
                for i in range(32)]
        trainer.train(reader=paddle.reader.batch(lambda: iter(data), 8),
                      num_passes=1, event_handler=lambda e: None,
                      metrics_registry=reg)
        assert t.enabled
    finally:
        t.configure(enabled=was)
        t.clear()
        flags.restore_raw(snap)
    (rec,) = [r for r in sink.records if r.get("kind") == "profile"]
    assert set(rec) >= {"start_step", "end_step", "steps", "trace_dir",
                        "wall_ms", "spans", "schema"}
    assert (rec["start_step"], rec["end_step"], rec["steps"]) == (1, 4, 3)
    assert rec["spans"]["compute"]["count"] == 3
    events = _host_events(rec["trace_dir"])
    assert len(events["compute"]) == 3      # the window's three dispatches
    # the first step's feed ran before the trace started; a step span
    # is whole only strictly inside the window (the trace stops right
    # after the last dispatch)
    assert len(events["feed"]) == len(events["feed_convert"]) == 2
    assert len(events["step"]) == 1
    assert not [n for n in events if n.startswith("train_step_")]
    assert not hasattr(paddle.telemetry.tracing.ProfileWindow, "annotation")


def test_stat_timer_keeps_its_aggregates(tmp_path):
    """``core/stat.timer`` lost its own TraceAnnotation (the tracer's
    mirrors carry the scopes); the reference's aggregates stay."""
    from paddle_tpu.core import stat

    snap = flags.snapshot_raw()
    flags.set("with_timer", True)
    ss = stat.StatSet("t")
    _start_trace(tmp_path)
    try:
        for _ in range(3):
            with stat.timer("forwardBackward", ss):
                pass
    finally:
        jax.profiler.stop_trace()
        flags.restore_raw(snap)
    assert ss.stats["forwardBackward"].count == 3
    assert "forwardBackward" not in _host_events(str(tmp_path))


# -- a dump states its clock ---------------------------------------------------


def test_chrome_trace_states_its_clock_and_merge_aligns_by_it(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_merge
    finally:
        sys.path.pop(0)
    clk = _Clock()
    t = Tracer(enabled=True, rank=0, clock=clk)
    with t.span("step"):
        clk.t += 1.0
    before = time.time_ns()
    clock = t.chrome_trace()["otherData"]["clock"]
    assert clock["tracer_s"] == clk.t
    assert before <= clock["unix_ns"] <= time.time_ns()

    # two ranks whose monotonic clocks started 40 s apart, dumped 2 s of
    # wall clock apart: the same instant reads 101 s on rank 0 and 61 s
    # on rank 1
    def dump(rank, tracer_s, unix_ns, ts_us):
        path = tmp_path / f"trace-host{rank}.json"
        path.write_text(json.dumps({
            "traceEvents": [{"name": "step", "ph": "X", "ts": ts_us,
                             "dur": 5.0, "pid": rank, "tid": "MainThread",
                             "args": {"id": rank}}],
            "otherData": {"rank": rank, "clock": {
                "tracer_s": tracer_s, "unix_ns": unix_ns}}}))
        return str(path)

    f0 = dump(0, 110.0, 1_000_000_000_000, 101e6)
    f1 = dump(1, 72.0, 1_002_000_000_000, 61e6)
    merged = trace_merge.merge([f0, f1])
    ts = {e["pid"]: e["ts"] for e in merged["traceEvents"]
          if e.get("ph") == "X"}
    assert ts[0] == 101e6 and ts[1] == pytest.approx(101e6, abs=1e-3)
    assert merged["otherData"]["unaligned"] == []
    # a dump without the pair keeps its times and is named
    old = tmp_path / "trace-host2.json"
    old.write_text(json.dumps({"traceEvents": [
        {"name": "step", "ph": "X", "ts": 7.0, "dur": 1.0, "pid": 2,
         "tid": "MainThread", "args": {"id": 9}}]}))
    merged = trace_merge.merge([f0, str(old)])
    assert merged["otherData"]["unaligned"] == [str(old)]
    assert [e["ts"] for e in merged["traceEvents"]
            if e.get("ph") == "X" and e["pid"] == 2] == [7.0]
