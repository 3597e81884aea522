"""Metric primitives + the registry that owns them.

One structured stream unifies what used to be scattered (``core/stat.py``
scope timers, ``profiler.py`` MFU accounting, bench JSONL): a
:class:`MetricsRegistry` holds named counters / gauges / histograms with
labeled series (pull side, cheap in-process aggregates) and a list of
pluggable sinks (push side: one dict per emitted record — JSONL file,
in-memory for tests, logging).  The per-step train records of
``SGD.train`` / ``trainer/cli.py`` and the ``--job=time`` result flow
through the same :meth:`MetricsRegistry.emit`, so operators and offline
tooling (``tools/metrics_to_md.py``) read one schema.

Comm accounting: the collective wrappers in ``parallel/collective.py``
call :func:`record_comm` while XLA traces the program, so the counters
hold bytes-moved-per-executed-step of each compiled program (shapes are
static; one trace per compile signature).  ``comm_snapshot()`` flattens
them into the per-step records.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import math
import threading
import time
from typing import Any

# /2 added the input-pipeline fields: per-step input_wait_ms (host time
# the step loop blocked waiting for a feed) and host_stall_ms (amortized
# device-fence wait per step under deferred fencing) — see
# reader/prefetch.py and SGD.train(sync_period=).
# /3 added the fault-tolerance stream (paddle_tpu/resilience/): counters
# faults_injected{kind} / faults_recovered / batches_skipped / rollbacks
# / restarts / retries{scope} / checkpoint_write_failures /
# heartbeat_stale, gauges recovery_ms / checkpoint_restore_ms, and two
# record kinds — "fault" (the numeric guard's nan_skip/nan_rollback
# events) and "recovery" (one per supervisor restart)
# /4 added the serving stream (paddle_tpu/serving/): record kinds
# "serve" (one per completed request: queue_wait_ms/ttft_ms/tpot_ms/
# total_ms) and "serve_summary" (latency histogram rollup), histograms
# serve_queue_wait_ms / serve_prefill_ms / serve_decode_step_ms /
# serve_ttft_ms / serve_tpot_ms / serve_dense_batch / serve_dense_ms,
# counters serve_requests{reason} / serve_tokens / serve_dense_requests,
# gauges serve_active_slots / serve_free_pages; histogram summaries grew
# interpolated percentile fields (p50/p90/p99)
# /5: step records carry a ``fused_kernels`` bool — whether the step's
# program routed the conv/BN/optimizer hot paths through the TPP fused
# Pallas kernels (ops/pallas/tpp), so bench streams and flight
# recordings identify which path produced a trajectory
# /6 added the elastic-fleet stream (resilience/elastic.py): record kind
# "elastic_event" — one per live mesh rebuild, carrying event
# (host_loss|scale_up), old_dp/new_dp, recovery_ms (drain→resume wall
# time), shard_source (live|checkpoint), the drain cursor and the ZeRO
# respec report — plus the elastic_events{kind} counter, the shared
# recovery_ms gauge labeled run="elastic", and the serving engine's
# serve_loop_crashes counter (background loop deaths that failed
# pending requests)
# /7 added the static-analysis stream (paddle_tpu/analysis): record
# kind "preflight" — one per `trainer --preflight` / analysis-CLI run,
# carrying the per-pass finding counts, the unsuppressed finding ids
# and whether the run was clean — plus the preflight_findings{rule}
# counter.  RECORD_KINDS (below) became the registered kind set the
# GL-SCHEMA drift pass checks every emitted record against.
# /8 added the serving-fleet stream (serving/router.py): record kind
# "fleet" — one per fleet event (replica_down with its failover
# requeue count, swap / swap_rollback for rolling weight swaps, and
# the summary availability rollup whose requests_lost must be 0) —
# plus the fleet_failovers / fleet_requeued / fleet_shed{reason} /
# fleet_swaps / fleet_swap_rollbacks / fleet_deadline_expired /
# fleet_redial_exhausted / fleet_duplicate_results /
# fleet_replica_down{reason} counters and the fleet_alive_replicas /
# fleet_queue_depth gauges.
# /10 added the per-step input padding signal (sequence bucketing):
# step records carry ``padding_ratio`` (padded/total timesteps across
# the feed's SequenceBatch slots, omitted for non-sequence feeds) plus
# the matching pull-side padding_ratio gauge — rendered by
# tools/metrics_to_md.py with a flag when >25% of fed timesteps are
# padding (the signal that the reader should bucket by length).  No new
# record kinds.
# /9 extended the "preflight" record with the GL-P-MEM static memory
# report (graftlint v2): a ``memory`` dict carrying the per-device byte
# accounting of the built step — params_bytes, opt_state_bytes (under
# the active zero mode's state_specs layout), states_bytes, feed_bytes,
# activation_bytes (+ activation_source: jaxpr-liveness or
# xla-memory-analysis), total_bytes, dp, zero and the per-pallas_call
# pallas_vmem footprints — rendered as a budget table by
# tools/metrics_to_md.py.  No new record kinds.
# /11 added the live-introspection stream (telemetry/tracing.py,
# telemetry/introspect.py): record kind "profile" — one per
# --profile_steps windowed jax.profiler capture, carrying
# start_step/end_step, trace_dir, wall_ms and (with --trace_spans) the
# tracer's per-phase duration summary {phase: {count, total_ms, p50_ms,
# p99_ms, max_ms}} rendered by tools/metrics_to_md.py's "Trace spans"
# table.  Histogram summaries became None-safe at zero observations
# (min/max clamp to 0 instead of leaking ±inf into JSON).
# /12 added the goodput ledger (telemetry/goodput.py): record kind
# "ledger" — one per run close, classifying every wall-clock second
# into productive compute vs. named badput buckets (input_wait, fence,
# recompile, checkpoint_save, checkpoint_restore, guard_rescue,
# restart, elastic_drain, elastic_reshard, idle) folded from existing
# tracewire spans and resilience counters, plus the serving cost
# split (prefill/decode compute-seconds, queue-seconds, KV-page
# occupancy-seconds, cost_per_token).  The "serve" record gained
# queue_s/prefill_s/decode_s/kv_page_s/cost_per_token fields and the
# fleet rollup gained cost-per-token components; rendered by
# tools/goodput_report.py and metrics_to_md.py's "Goodput" table.
# /13 extended the "preflight" record with the GL-P-COST static
# roofline (graftlint v3): a ``cost`` dict carrying the predicted
# step_ms / mfu_pct / compute_ms / comm_ms / overlap_headroom_ms, the
# per-op-class FLOPs+bytes breakdown (by_class), per-pallas_call
# compute, the collective wire model (collectives) and the named
# ``bottleneck`` under the selected --hw_profile — rendered by
# tools/metrics_to_md.py's "Static cost" table.  No new record kinds.
# /14 added prefix caching + chunked prefill to the serving path: the
# "serve" record gained cached_tokens (prompt tokens mapped from the
# prefix cache instead of recomputed) and prefill_chunks (incremental
# prefill passes this request took); "serve_summary" gained a "prefix"
# dict (hits/misses/hit_tokens/prompt_tokens/hit_rate/
# request_hit_rate/evictions/inserts/cached_pages/flops_saved) and a
# top-level prefill_chunks when either flag is on.  New counters
# serve_prefix_hit_tokens / serve_prefill_flops_saved /
# serve_prefill_chunks and gauge serve_cached_pages.  No new record
# kinds; flag-off runs emit the /13 field set plus the two zero-valued
# serve fields.
# /15 added the train→serve control plane (paddle_tpu/deploy): record
# kind "deploy" — one per DeploymentController rollout attempt
# (checkpoint, uuid, attempt, export_ms/swap_ms/total_ms, outcome
# deployed|rolled_back|export_failed) — and record kind "autoscale" —
# one per SloAutoscaler action (scale_up/scale_down with the
# triggering signals and scale_ms) and per PoolArbiter shift
# (pool_borrow/pool_return with the trainer/serving host split).  New
# counters deploys_succeeded / deploys_rolled_back /
# deploys_export_failed / autoscale_actions{action} /
# pool_shifts{event} / fleet_replicas_added / fleet_replicas_retired /
# fleet_scrape_errors / client_backoffs.
# /16 made set-up visible (telemetry/tracing.py, telemetry/goodput.py):
# the "ledger" record's buckets_s gained ``startup`` (cat="setup" spans
# and persistent-cache fetches, out of ``idle``) and ``recompile`` is
# backend-compile time only (a fetch used to read the same); the
# "profile" record's span summary may carry the set-up and ``xla_*``
# span names.  New counters xla_programs_total{how} /
# xla_build_seconds_total{phase} (tracing.XlaBuildListener, one a
# process).  The serve latency histograms (serve_decode_step_ms /
# serve_prefill_ms / serve_queue_wait_ms / serve_ttft_ms /
# serve_tpot_ms) take geometric bucket edges (ratio <= 1.05, 0.1 ms -
# 60 s) in place of DEFAULT_BUCKETS.  No new record kinds.
SCHEMA = "paddle_tpu.metrics/16"

# every record kind the schema knows.  The GL-SCHEMA codebase pass
# (paddle_tpu/analysis) cross-checks this against the tree: an emitted
# kind missing here — or an entry here nothing produces — is drift.
RECORD_KINDS = ("step", "bench", "fault", "recovery", "serve",
                "serve_summary", "elastic_event", "preflight", "fleet",
                "profile", "ledger", "deploy", "autoscale")

# histogram bucket upper bounds (ms-oriented default; values above the
# last edge land in the +Inf bucket)
DEFAULT_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
                   1000.0, 2500.0, 5000.0)


def geometric_buckets(lo: float, hi: float, ratio: float) -> tuple:
    """Upper bounds from ``lo`` to at least ``hi``, each ``ratio`` times
    the one before: a quantile read from them is off by at most
    ``ratio - 1`` of its value, at any magnitude."""
    if not (lo > 0 and hi > lo and ratio > 1):
        raise ValueError(f"geometric_buckets({lo}, {hi}, {ratio})")
    n = math.ceil(math.log(hi / lo) / math.log(ratio))
    return tuple(lo * (hi / lo) ** (i / n) for i in range(n + 1))


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class _Metric:
    def __init__(self, name: str, help: str, registry: "MetricsRegistry"):
        self.name = name
        self.help = help
        self._registry = registry
        self._series: dict[tuple, Any] = {}

    def _lock(self):
        return self._registry._lock

    def labels_of(self) -> list[dict]:
        return [dict(k) for k in self._series]


class Counter(_Metric):
    """Monotonically increasing value per label set."""

    def inc(self, value: float = 1.0, **labels) -> None:
        if value < 0:
            raise ValueError(f"counter {self.name}: negative inc {value}")
        key = _label_key(labels)
        with self._lock():
            self._series[key] = self._series.get(key, 0.0) + value

    def value(self, **labels) -> float:
        return self._series.get(_label_key(labels), 0.0)

    def snapshot(self) -> list[dict]:
        with self._lock():
            return [{**dict(k), "value": v} for k, v in self._series.items()]


class Gauge(_Metric):
    """Last-set value per label set."""

    def set(self, value: float, **labels) -> None:
        with self._lock():
            self._series[_label_key(labels)] = float(value)

    def value(self, **labels) -> float | None:
        return self._series.get(_label_key(labels))

    def snapshot(self) -> list[dict]:
        with self._lock():
            return [{**dict(k), "value": v} for k, v in self._series.items()]


@dataclasses.dataclass
class _Hist:
    count: int = 0
    total: float = 0.0
    min: float = float("inf")
    max: float = float("-inf")
    buckets: list[int] = dataclasses.field(default_factory=list)


class Histogram(_Metric):
    """Fixed-bucket distribution per label set (bucket edges are upper
    bounds; one overflow bucket beyond the last edge)."""

    def __init__(self, name, help, registry, buckets=DEFAULT_BUCKETS):
        super().__init__(name, help, registry)
        self.bucket_edges = tuple(sorted(buckets))

    def observe(self, value: float, **labels) -> None:
        key = _label_key(labels)
        with self._lock():
            h = self._series.get(key)
            if h is None:
                h = self._series[key] = _Hist(
                    buckets=[0] * (len(self.bucket_edges) + 1))
            h.count += 1
            h.total += value
            h.min = min(h.min, value)
            h.max = max(h.max, value)
            # the first edge >= value; past the last, the overflow bucket
            h.buckets[bisect.bisect_left(self.bucket_edges, value)] += 1

    def _percentile_of(self, h: _Hist, q: float) -> float:
        """Linear-interpolated q-th percentile from the bucket counts.

        Within the bucket containing the target rank, values are assumed
        uniform between the bucket's bounds (first bucket's lower bound =
        observed min; overflow bucket's upper bound = observed max), so
        the estimate is exact at bucket edges and clamped to [min, max]
        — good enough to assert SLOs against (tests) and render (the
        metrics_to_md "Serving latency" table)."""
        rank = (q / 100.0) * h.count
        cum = 0
        lower = h.min
        for i, cnt in enumerate(h.buckets):
            upper = (self.bucket_edges[i] if i < len(self.bucket_edges)
                     else h.max)
            if cnt:
                cum += cnt
                if cum >= rank:
                    lo = max(lower, h.min)
                    hi = min(upper, h.max)
                    frac = (rank - (cum - cnt)) / cnt
                    return float(min(max(lo + (hi - lo) * frac, h.min),
                                     h.max))
            lower = upper
        return float(h.max)

    def percentile(self, q: float, **labels) -> float | None:
        """Estimated q-th percentile (0..100) for a label set, or None
        with no observations — lets tests/SLO checks assert e.g.
        ``hist.percentile(99) < 250``."""
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"percentile q must be in [0, 100], got {q}")
        with self._lock():
            h = self._series.get(_label_key(labels))
            if h is None or not h.count:
                return None
            return self._percentile_of(h, q)

    def summary(self, **labels) -> dict | None:
        with self._lock():
            h = self._series.get(_label_key(labels))
            if h is None:
                return None
            pct = ({f"p{q}": self._percentile_of(h, q)
                    for q in (50, 90, 99)}
                   if h.count else {"p50": 0.0, "p90": 0.0, "p99": 0.0})
            # zero observations: min/max are the ±inf init sentinels —
            # clamp to 0 so an empty histogram's summary stays JSON-safe
            # (Infinity is not JSON) and SLO checks read 0, not -inf
            return {"count": h.count, "sum": h.total,
                    "avg": h.total / h.count if h.count else 0.0,
                    "min": h.min if h.count else 0.0,
                    "max": h.max if h.count else 0.0, **pct,
                    "buckets": dict(zip(
                        [str(e) for e in self.bucket_edges]
                        + ["+Inf"], h.buckets))}

    def snapshot(self) -> list[dict]:
        with self._lock():
            return [{**dict(k), **self.summary(**dict(k))}
                    for k in list(self._series)]


class MetricsRegistry:
    """Named metrics + sink fan-out.

    ``counter/gauge/histogram`` are get-or-create (re-registering the
    same name with a different type is an error).  ``emit`` stamps the
    record with schema/ts/host and writes it to every sink; with no
    sinks it is a no-op, so instrumented code paths can always call it
    (``active`` lets callers skip expensive record assembly entirely).
    """

    def __init__(self, name: str = "default"):
        self.name = name
        self._lock = threading.RLock()
        self._metrics: dict[str, _Metric] = {}
        self._sinks: list = []

    # -- metric construction --------------------------------------------------
    def _get_or_create(self, cls, name, help, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, self, **kw)
            elif type(m) is not cls:
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def get(self, name: str) -> _Metric | None:
        return self._metrics.get(name)

    # -- sinks ----------------------------------------------------------------
    def add_sink(self, sink) -> None:
        with self._lock:
            self._sinks.append(sink)

    def remove_sink(self, sink) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    def clear_sinks(self) -> None:
        with self._lock:
            for s in self._sinks:
                with swallow("sink_close", self):
                    s.close()
            self._sinks = []

    @property
    def sinks(self) -> list:
        return list(self._sinks)

    @property
    def active(self) -> bool:
        return bool(self._sinks)

    # -- the structured stream ------------------------------------------------
    def emit(self, record: dict, kind: str | None = None) -> dict:
        """Stamp + fan a record out to every sink; returns the stamped
        record (emitted or not, so callers can reuse it — e.g. the
        flight recorder keeps records the sinks never saw)."""
        rec = dict(record)
        rec.setdefault("schema", SCHEMA)
        if kind is not None:
            rec.setdefault("kind", kind)
        rec.setdefault("ts", time.time())
        if "host" not in rec:
            rec["host"] = host_index()
        for sink in self._sinks:
            try:
                sink.write(rec)
            except Exception as e:
                # telemetry must never abort training: a full disk or a
                # revoked path drops records, not the run (warn once per
                # sink so a long run doesn't drown in repeats)
                if not getattr(sink, "_write_failed", False):
                    try:
                        sink._write_failed = True
                        from paddle_tpu.core import logger

                        logger.get_logger("paddle_tpu.metrics").warning(
                            "metrics sink %s write failed (%s); further "
                            "records to it may be lost",
                            type(sink).__name__, e)
                    except Exception:
                        pass
        return rec

    def flush(self) -> None:
        for sink in self._sinks:
            with swallow("sink_flush", self):
                sink.flush()

    def snapshot(self) -> dict:
        """{metric name: list of labeled series dicts} — the pull-side
        view of every counter/gauge/histogram."""
        with self._lock:
            return {name: m.snapshot() for name, m in self._metrics.items()}


def host_index() -> int:
    """This process's host/worker index — ``jax.process_index`` whenever
    it can be read WITHOUT forcing backend init (telemetry must be
    importable before ``jax.distributed.initialize``); falls back to
    PADDLE_TPU_TRAINER_ID.  The single implementation step records AND
    flight dumps stamp with, so cross-host comparisons line up.

    Standard TPU pods auto-detect multihost without
    ``jax.distributed.is_initialized()`` ever flipping true, so the real
    gate is "has a backend already been created" — by emit/dump time in
    a train loop it always has, and ``process_index`` is then correct
    and free.  One exception: a LOCAL fleet (``distributed.launch`` on
    a CPU/dev box) runs each rank as its own single-process jax world,
    where ``process_index()`` is a constant 0 on every rank — there the
    launcher's ``PADDLE_TPU_TRAINER_ID`` stamp is the identity, or
    every rank's trace/flight dump would land on ``*-host0`` and
    clobber its peers'."""
    try:
        import jax

        if getattr(jax.distributed, "is_initialized", None) and \
                jax.distributed.is_initialized():
            return jax.process_index()
        from jax._src import xla_bridge

        if xla_bridge._backends:  # initialized already: reading is safe
            if jax.process_count() > 1:
                return jax.process_index()
            # single-process backend: a launcher-stamped fleet identity
            # (local ranks) outranks the backend's constant 0 — fall
            # through to the env read
    except (ImportError, AttributeError, RuntimeError):
        # jax absent/too old, or a backend probe that refuses before
        # init — the env-var fallback below is the answer either way
        pass
    import os

    return int(os.environ.get("PADDLE_TPU_TRAINER_ID", "0") or 0)


# -- the default (process-global) registry ------------------------------------

_default = MetricsRegistry()


def get_default_registry() -> MetricsRegistry:
    return _default


def safe_inc(name: str, help: str = "", amount: float = 1.0,
             registry: MetricsRegistry | None = None, **labels) -> None:
    """Best-effort counter increment for fault/recovery paths: accounting
    must never break the operation it observes (a retry, an injected
    fault, a failing checkpoint write), so every failure is swallowed."""
    try:
        (registry or _default).counter(name, help).inc(amount, **labels)
    except Exception:
        pass


@contextlib.contextmanager
def swallow(scope: str, registry: MetricsRegistry | None = None):
    """Accounting guard for telemetry/observability side work — the
    multi-statement sibling of :func:`safe_inc`: the operation being
    observed (a rebuild, a fault injection, a collective trace) must
    never die of its own bookkeeping.  A failure inside the block is
    logged at debug, counted (``telemetry_errors{scope}``) and
    swallowed.  Use this instead of ad-hoc ``except Exception: pass``
    blocks around accounting — the GL-EXCEPT static-analysis pass
    rejects those."""
    try:
        yield
    except Exception as e:
        try:
            from paddle_tpu.core import logger

            logger.get_logger("paddle_tpu.metrics").debug(
                "telemetry accounting failed in %s: %s: %s", scope,
                type(e).__name__, e)
            (registry or _default).counter(
                "telemetry_errors",
                "accounting failures swallowed by telemetry.swallow").inc(
                1.0, scope=scope)
        except Exception:
            pass  # the guard of last resort stays silent by design


# -- comm accounting (called by parallel/collective.py at trace time) ---------
#
# jax traces a program's Python body ONCE per signature (lower() and the
# jit call share the trace cache), so record_comm fires exactly once per
# compiled program.  Two consumers ride that single firing:
# - a scoped capture (capture_comm): StepTelemetry lowers a program under
#   it to get THAT program's per-execution payload, {"op/axis": bytes} —
#   what step records carry;
# - the global counters: every trace increments them (captured or not),
#   so they accumulate across compiles — a cumulative pull-side metric,
#   NOT a per-step number.
# Caveat for both: a collective inside a lax.scan/fori_loop body is
# traced once but executed once per iteration, so loop-carried comm is
# undercounted by the trip count.

_capture = threading.local()


@contextlib.contextmanager
def capture_comm():
    """Collect record_comm events into a {"op/axis": bytes} dict for the
    duration (typically one jit lowering).  The global counters still
    accumulate — the trace cache guarantees this is the program's only
    trace, so there is no double count.  NOTE: a capture over a program
    whose signature was already traced (e.g. a second lowering of the
    same jit object) stays empty — the cached trace skips the Python
    body entirely."""
    stack = getattr(_capture, "stack", None)
    if stack is None:
        stack = _capture.stack = []
    acc: dict[str, float] = {}
    stack.append(acc)
    try:
        yield acc
    finally:
        stack.pop()


def record_comm(op: str, axis: str, nbytes: int, registry=None) -> None:
    """One collective call site traced: bytes are the per-shard payload of
    one execution of the traced program body."""
    key = f"{op}/{axis}"
    for acc in getattr(_capture, "stack", None) or ():
        acc[key] = acc.get(key, 0.0) + float(nbytes)
    reg = registry or _default
    reg.counter("comm_bytes",
                "payload bytes of traced collectives (cumulative over "
                "traces)").inc(float(nbytes), op=op, axis=axis)
    reg.counter("comm_calls", "traced collective call sites").inc(
        1.0, op=op, axis=axis)


def comm_snapshot(registry=None) -> dict[str, float]:
    """Flatten the cumulative comm counters into {"op/axis": bytes}."""
    reg = registry or _default
    c = reg.get("comm_bytes")
    if c is None:
        return {}
    return {f"{s['op']}/{s['axis']}": s["value"] for s in c.snapshot()}


def census_by_kind(comm: dict[str, float]) -> dict[str, dict]:
    """Roll a {"op/axis": bytes} comm map (a step record's per-program
    payload, or :func:`comm_snapshot`'s cumulative counters) up to
    {kind: {"bytes", "sites", "axes"}} — the collective census.

    Under ZeRO-2 this is the table that PROVES the collective swap: the
    gradient flow's ``all_reduce`` bytes drop to (near) zero, replaced by
    ``reduce_scatter`` + ``all_gather`` whose per-device payloads are 1/n
    of the replicated run's all-reduce.  ``sites`` counts distinct
    op/axis call sites, not per-step executions (a collective in a scan
    body is traced once)."""
    out: dict[str, dict] = {}
    for key, nbytes in (comm or {}).items():
        kind, _, axis = key.partition("/")
        row = out.setdefault(kind, {"bytes": 0.0, "sites": 0, "axes": []})
        row["bytes"] += float(nbytes)
        row["sites"] += 1
        if axis and axis not in row["axes"]:
            row["axes"].append(axis)
    return out
