"""Goodput ledger — wall-clock badput attribution.

The reference's ``paddle/utils/Stat.h`` timer dumps answered "how long
did X take on average" but never "what fraction of the run was
productive, and where did the rest go" — the aggregates don't compose
into one wall-clock account.  This module does that composition: a
:class:`GoodputLedger` classifies **every wall-clock second** between
``start()`` and ``finish()`` into productive ``compute`` vs. named
badput buckets:

``input_wait``
    the trainer blocked on the feed (``feed`` spans — the consumer-side
    wait, NOT the prefetch threads' ``prefetch`` /
    ``feed_read`` / ``feed_convert`` / ``feed_place`` / ``feed_stage``,
    which overlap compute);
``fence``
    device sync at flush boundaries (``fence`` spans);
``recompile``
    ``compute`` spans stamped ``compile=True`` by the trainer — the
    dispatch under which XLA's backend compiled a program
    (``xla_compile`` events heard by ``tracing.XlaBuildListener``; a
    fetch from the persistent cache is not one) — and ``xla_compile``
    spans outside any ``compute`` (a set-up phase that compiled);
``startup``
    set-up on the train loop's thread: ``cat="setup"`` spans
    (``train_setup`` from the top of ``train()`` to the first step
    around ``build_step`` / ``place_state`` / ``params_sync``, the copy
    back at a pass's end) and every ``xla_cache_fetch``, each second
    once: what a nested ``restore`` or ``xla_compile`` booked is not
    booked again;
``checkpoint_save`` / ``checkpoint_restore``
    cursor/final checkpoint writes (``checkpoint`` spans) and state
    restores (the trainer's retrospective ``restore`` span, cut from
    the SAME ``perf_counter`` reading that already feeds the
    ``checkpoint_restore_ms`` gauge — no new timing source);
``guard_rescue``
    NaN-guard rollback handling (``guard_rescue`` spans, minus any
    nested restore time so the two buckets never double-count);
``restart``
    supervisor fault-to-retraining overhead (the ``restarts`` counter
    delta between folds prices the ``recovery_ms`` gauge in);
``elastic_drain`` / ``elastic_reshard``
    the drain checkpoint before a live mesh rebuild (``drain`` spans)
    and the rebuild itself (``gather``/``reshard``/``rebuild`` spans);
``idle``
    whatever remains: wall-clock not covered by any classified span
    (pass turnaround, ring overflow; set-up only with no span on it).

The ledger is a **fold over signals that already exist** — tracewire
spans and resilience counters.  It introduces no clocks of its own, so
a disabled run pays nothing and an enabled run's training trajectory is
bit-identical (asserted in ``tests/test_goodput.py``).  ``fold()`` is
incremental (the trainer calls it from its flush cadence): each call
classifies only spans that entered the ring since the previous call,
so the ring can wrap between run start and run end without losing the
account — only spans older than one whole ring per fold interval can
drop, and the closing record carries the tracer's drop counter so a
truncated account is visible, not silent.

``finish()`` emits one ``kind="ledger"`` telemetry record (schema /12;
``startup`` since /16)
with the bucket seconds, ``goodput_fraction`` (= compute / wall), the
serving cost split when serving counters are present (prefill/decode
compute-seconds, queue-seconds, KV-page occupancy-seconds,
cost-per-token — see ``serving/engine.py``), sets the
``goodput_fraction`` gauge (surfaced on ``/healthz`` and rolled up
fleet-wide by ``FleetRouter.scrape_replicas``), and appends the record
to ``<ledger_dir>/ledger.jsonl`` when a path is armed.  Render with
``tools/goodput_report.py`` or the "Goodput" table of
``tools/metrics_to_md.py``.
"""

from __future__ import annotations

import json
import os
import threading

# span name -> badput bucket.  The ledger counts each wall-clock second
# of the TRAIN LOOP's thread once, from the span that blocked it, so
# deliberately absent are: parent spans ("step", "elastic", "request");
# the prefetch threads' spans, which overlap compute ("prefetch",
# "feed_read" / "feed_convert" / "feed_place" / "feed_stage" — with
# prefetch off the three in the middle are children of "feed", which
# already books their time as input_wait); and the serving engine's
# ("serve_step", "serve_schedule", "serve_prefill", "serve_decode":
# another loop's wall-clock, accounted per request by serving_costs).
_LEAF_BUCKET = {
    "feed": "input_wait",
    "fence": "fence",
    "checkpoint": "checkpoint_save",
    "restore": "checkpoint_restore",
    "guard_rescue": "guard_rescue",
    "drain": "elastic_drain",
    "gather": "elastic_reshard",
    "reshard": "elastic_reshard",
    "rebuild": "elastic_reshard",
}

BADPUT_BUCKETS = ("input_wait", "fence", "recompile", "startup",
                  "checkpoint_save", "checkpoint_restore", "guard_rescue",
                  "restart", "elastic_drain", "elastic_reshard", "idle")
BUCKETS = ("compute",) + BADPUT_BUCKETS

# restore intervals remembered for the nested-in-guard_rescue
# subtraction; a run with more restores than this merely double-counts
# the excess into guard_rescue instead of growing without bound
_MAX_RESTORE_INTERVALS = 256
# ... and the disjoint intervals set-up has booked (the tracer keeps at
# most tracing.KEPT_MAX such spans; restores add theirs)
_MAX_BOOKED_INTERVALS = 1024


class GoodputLedger:
    """Incremental wall-clock classifier over the trace-span ring.

    :param registry: metrics registry the closing record lands in;
        default the process registry.
    :param tracer: span source; default the process tracer (which must
        be enabled for the ledger to see anything — the trainer arms
        tracing when ``--goodput_ledger`` is set).
    :param clock: seconds clock for the wall measurement; default the
        TRACER's clock, so a fake-clock test drives spans and wall from
        one timeline.
    """

    def __init__(self, registry=None, tracer=None, clock=None):
        if registry is None:
            from paddle_tpu.telemetry.registry import get_default_registry

            registry = get_default_registry()
        if tracer is None:
            from paddle_tpu.telemetry.tracing import get_tracer

            tracer = get_tracer()
        self.registry = registry
        self.tracer = tracer
        self.clock = clock or tracer.clock
        self._lock = threading.Lock()
        self._buckets = {b: 0.0 for b in BUCKETS}
        self._seen_ids: set[int] = set()   # span ids of the last fold
        self._restores: list[tuple[float, float]] = []
        # what set-up spans, restores and stand-alone compiles have
        # booked, as sorted disjoint intervals: spans nest there, and
        # each second goes to the innermost one that claims it
        self._booked: list[tuple[float, float]] = []
        self._thread: str | None = None    # the train loop's, at start()
        self._restarts_seen = 0.0
        self._spans_folded = 0
        self._t0: float | None = None
        self.record: dict | None = None

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> "GoodputLedger":
        with self._lock:
            self._t0 = self.clock()
            self._thread = threading.current_thread().name
        return self

    @property
    def started(self) -> bool:
        with self._lock:
            return self._t0 is not None

    # -- the fold --------------------------------------------------------------
    def _claim(self, t0: float, t1: float) -> float:
        """Seconds of ``[t0, t1]`` (cut to the run) that no earlier claim
        holds; the interval then holds them."""
        t0 = max(t0, self._t0)
        if t1 <= t0:
            return 0.0
        free, merged, lo, hi = t1 - t0, [], t0, t1
        for b0, b1 in self._booked:
            if b1 < t0 or b0 > t1:
                merged.append((b0, b1))
                continue
            free -= max(0.0, min(b1, t1) - max(b0, t0))
            lo, hi = min(lo, b0), max(hi, b1)
        if len(merged) < _MAX_BOOKED_INTERVALS:
            merged.append((lo, hi))
            merged.sort()
            self._booked = merged
        return max(free, 0.0)

    def _held(self, t0: float, t1: float) -> float:
        """Seconds of ``[t0, t1]`` that set-up's claims hold."""
        return sum(max(0.0, min(b1, t1) - max(b0, t0))
                   for b0, b1 in self._booked)

    def _classify(self, span) -> None:
        dur = max(0.0, span.t_end - span.t_start)
        name = span.name
        if name == "compute":
            if span.args.get("cache_fetches"):
                # the fetch under it is startup's line, booked already
                dur = max(0.0, dur - self._held(span.t_start, span.t_end))
            which = "recompile" if span.args.get("compile") else "compute"
            self._buckets[which] += dur
            return
        if span.cat in ("setup", "xla"):
            if span.thread != self._thread:
                return      # another loop's set-up (a serving replica's)
            if name == "xla_compile":
                if span.args.get("under") != "compute":
                    self._buckets["recompile"] += self._claim(
                        span.t_start, span.t_end)
            elif span.cat == "setup" or name == "xla_cache_fetch":
                self._buckets["startup"] += self._claim(
                    span.t_start, span.t_end)
            return
        bucket = _LEAF_BUCKET.get(name)
        if bucket is None:
            return
        if name == "restore":
            if len(self._restores) < _MAX_RESTORE_INTERVALS:
                self._restores.append((span.t_start, span.t_end))
            # under train_setup at a resume: claimed here, so the set-up
            # span around it books only what is left
            dur = self._claim(span.t_start, span.t_end)
        elif name == "guard_rescue":
            # a rollback that restored from checkpoint nests a restore
            # span inside this one; subtract it so the second lands in
            # checkpoint_restore, not twice
            for (r0, r1) in self._restores:
                if r0 >= span.t_start and r1 <= span.t_end:
                    dur -= (r1 - r0)
            dur = max(0.0, dur)
        self._buckets[bucket] += dur

    def _counter_total(self, name: str) -> float:
        m = self.registry.get(name)
        if m is None:
            return 0.0
        try:
            return float(sum(s["value"] for s in m.snapshot()))
        except (TypeError, KeyError):
            return 0.0

    def _fold_restarts(self) -> None:
        """Price supervisor restarts from the counters they already
        keep: each ``restarts`` increment observed since the last fold
        charges the last-set non-elastic ``recovery_ms`` gauge value
        (the supervisor sets it right before re-entering train)."""
        total = self._counter_total("restarts")
        delta = total - self._restarts_seen
        if delta <= 0:
            return
        self._restarts_seen = total
        g = self.registry.get("recovery_ms")
        if g is None:
            return
        vals = [s["value"] for s in g.snapshot()
                if s.get("run") != "elastic"]
        if vals:
            self._buckets["restart"] += delta * max(vals) / 1e3

    def fold(self) -> int:
        """Classify spans that entered the ring since the last fold;
        returns how many were classified this call.  Cheap enough for
        the trainer's flush cadence: one ring snapshot + a set diff,
        bounded by the ring capacity."""
        with self._lock:
            if self._t0 is None:
                return 0
            spans = self.tracer.spans
            cur = {s.span_id for s in spans}
            new = [s for s in spans if s.span_id not in self._seen_ids]
            self._seen_ids = cur
            # inner before outer: a child ends no later than its parent,
            # and what it claims its parent does not book again
            new.sort(key=lambda s: (s.t_end, -s.t_start))
            for s in new:
                self._classify(s)
            self._fold_restarts()
            self._spans_folded += len(new)
            return len(new)

    # -- reading / closing -----------------------------------------------------
    def snapshot(self) -> dict:
        """Current bucket seconds (idle excluded — it only exists
        relative to a wall measurement, which ``finish`` takes)."""
        with self._lock:
            return dict(self._buckets)

    def finish(self, wall_s: float | None = None,
               path: str | None = None) -> dict:
        """Close the account: one final fold, ``idle`` = wall minus
        everything classified (clamped at 0), emit the ``ledger``
        record, set the ``goodput_fraction`` gauge, and append to
        ``path`` (a ledger.jsonl) when given.  Idempotent-ish: callable
        once per run; returns the record."""
        self.fold()
        with self._lock:
            if self._t0 is None:
                raise RuntimeError("GoodputLedger.finish before start")
            wall = (self.clock() - self._t0 if wall_s is None
                    else float(wall_s))
            classified = sum(v for b, v in self._buckets.items()
                             if b != "idle")
            self._buckets["idle"] = max(0.0, wall - classified)
            buckets = {b: round(self._buckets[b], 6) for b in BUCKETS}
            goodput = (self._buckets["compute"] / wall) if wall > 0 else 0.0
            rec = {
                "wall_s": round(wall, 6),
                "buckets_s": buckets,
                "goodput_fraction": round(goodput, 6),
                "badput_fraction": round(max(0.0, 1.0 - goodput), 6),
                "spans_folded": self._spans_folded,
                "spans_dropped": self.tracer.dropped,
            }
        costs = serving_costs(self.registry)
        if costs:
            rec["serving"] = costs
        self.registry.gauge(
            "goodput_fraction",
            "productive compute / wall-clock of the closing "
            "goodput ledger").set(goodput)
        if self.registry.active:
            rec = self.registry.emit(dict(rec), kind="ledger")
        if path:
            append_jsonl(rec, path)
        self.record = rec
        return rec


def serving_costs(registry) -> dict:
    """Per-token cost split from the serving engine's accumulators
    (``serving/engine.py`` folds per-request queue/prefill/decode/KV
    seconds into these counters as requests retire).  Empty dict when
    the process served nothing — a pure training run's ledger record
    carries no serving section."""
    def total(name: str) -> float:
        m = registry.get(name)
        if m is None:
            return 0.0
        try:
            return float(sum(s["value"] for s in m.snapshot()))
        except (TypeError, KeyError):
            return 0.0

    prefill = total("serve_prefill_compute_s")
    decode = total("serve_decode_compute_s")
    queue = total("serve_queue_s")
    kv = total("serve_kv_page_s")
    tokens = total("serve_tokens")
    if not (prefill or decode or queue or kv):
        return {}
    out = {
        "prefill_compute_s": round(prefill, 6),
        "decode_compute_s": round(decode, 6),
        "queue_s": round(queue, 6),
        "kv_page_s": round(kv, 6),
        "tokens": tokens,
    }
    if tokens > 0:
        out["cost_per_token_s"] = round((prefill + decode) / tokens, 9)
        out["cost_per_token_prefill_s"] = round(prefill / tokens, 9)
        out["cost_per_token_decode_s"] = round(decode / tokens, 9)
        out["cost_per_token_queue_s"] = round(queue / tokens, 9)
    return out


def append_jsonl(rec: dict, path: str) -> str:
    """Append one record to a ledger.jsonl (parent dirs created) — the
    per-run file ``tools/goodput_report.py`` consumes."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return path
