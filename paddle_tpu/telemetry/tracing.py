"""Span tracing — the live timeline behind the introspection server.

The reference dumped ``paddle/utils/Stat.h`` timer aggregates to the log
at pass end; ``core/stat.py`` reproduces those aggregates but, like
them, throws the *timeline* away — by the time an operator asks "what
was the fleet doing at second 43" only averages remain.  This module
keeps the timeline: a :class:`Tracer` records :class:`Span`\\ s (named,
categorized, nested intervals) into a bounded ring, cheap enough to
stay on in production and exactly ``None`` overhead when disabled (the
``--trace_spans`` flag; ``span()`` returns a shared no-op context
manager without allocating, so a disabled run's trajectory and event
stream are bit-identical to an untraced one — asserted in
``tests/test_introspect.py``).

Instrumented phase boundaries (all behind the same flag):

- trainer step loop — ``step`` spans with nested ``feed`` / ``compute``
  / ``fence`` / ``checkpoint`` / ``guard_rescue`` children; ``step``'s
  self time is the loop's own Python (events, cost look-ups, heartbeats);
- the feed pipeline (``reader/prefetch.py``) — one batch's production
  is ``feed_read`` (the pull from the reader iterator; ``examples``),
  ``feed_convert`` (``DataFeeder`` + padding stats + remainder policy,
  host only; ``bytes``) and ``feed_place`` (``mesh.shard_batch``, the
  one transfer; ``bytes``, ``shards``, ``from_host`` = the bytes that
  were host arrays).  Under ``DevicePrefetcher`` ``feed_read`` and
  ``feed_stage`` (the wait for a free slot) sit in the reader thread's
  lane, and each pool worker's lane (spans carry the thread name) holds
  one ``prefetch`` (``staged``, ``in_flight`` = units other workers had
  in hand when it started) per batch around ``feed_convert`` and
  ``feed_place``, which there is fenced and so runs to the end of the
  transfer; the main thread's ``feed`` stays a leaf (its wait for the
  next unit).  With ``prefetch=0`` the first three are children of
  ``feed`` itself and nothing is fenced;
- ``ServingEngine`` — one ``serve_step`` (``waiting``, ``active``) per
  engine iteration that did work, with ``serve_schedule`` children
  around the calls that build or change scheduler / KV-cache state and
  the two leaf batch spans ``serve_prefill`` / ``serve_decode``, one a
  pass, closed at the pass's read-back (``batch``, ``dispatch_ms`` = the
  dispatch call's own time, ``ahead`` = 1 when the pass went out behind
  an unread one; decode also ``context_tokens`` = KV tokens the step's
  kernel reads).  The loop runs one pass ahead, so a pass is read by the
  iteration after the one that dispatched it: its span opens there,
  after the read-back before it, and spans of one thread never overlap;
  ``serve_step``'s self time is the loop's own Python.  Plus a
  per-request retrospective ``request`` span with ``queue`` /
  ``prefill`` / ``decode`` children reconstructed from the request's
  own timestamps at retire time;
- set-up, ``cat="setup"`` — ``import_paddle_tpu`` (the package's own
  import, booked when the global tracer is armed); a serving replica's
  ``engine_init`` (``params_bytes``, ``pool_bytes``, ``state_bytes``)
  and ``engine_ready`` around one ``program_ready`` (``program``,
  ``rows``, ``length``; ``routes`` and ``op_scopes``: what the held
  program was built with and which of its operations are whose,
  ``scopes.compile_described``) a compiled program — the trainer's
  step program too (``program="step"``), once a feed signature; the trainer's
  ``train_setup`` from the top of ``SGD.train`` to its first ``step``,
  around ``build_step``, ``place_state`` (``arrays``, ``bytes``), the
  ``restore`` span and ``params_sync`` (``arrays``, ``bytes``; also the
  copy back when a pass ends);
- XLA's own build events, ``cat="xla"`` (:class:`XlaBuildListener`, one
  a process) — ``xla_trace`` / ``xla_lower`` / ``xla_compile`` /
  ``xla_cache_fetch``: retrospective children of whatever span of that
  thread was open when jax built a program (``fun``, ``under`` = that
  span's name), which gets ``compiles`` / ``cache_fetches`` counts.
  They appear only when something is built: a steady loop has none;
- ``FleetRouter`` — ``failover`` (with nested ``requeue``), ``route``
  and per-replica ``swap`` spans;
- ``ElasticCoordinator`` — an ``elastic`` span with ``drain`` /
  ``gather`` / ``reshard`` / ``rebuild`` children around a live mesh
  rebuild.

One clock with the device trace: while enabled, every LIVE span
(``begin``/``end``/``span``) also opens and closes a
``jax.profiler.TraceAnnotation`` of its name, so any device trace taken
meanwhile (``--profile_steps``, ``jax.profiler.trace``) carries the
program's spans as host events on the profiler's own clock, in the
thread's lane — no marker, no alignment step.  Retrospective
``add_span``\\ s have no live interval to mirror and are not.

Set-up outlives the window: spans of the two categories above are kept
BESIDE the ring (at most ``KEPT_MAX``; overflow counts as dropped), so a
``clear()`` at a measurement window's opening or a ring wrap leaves them
where they were, first in ``spans``; ``drain()`` hands them out once.

Span identity is DETERMINISTIC: ``span_id = rank * 2**32 + seq`` where
``seq`` is the per-tracer allocation counter — two runs of the same
single-threaded program allocate the same ids, and a fleet's merged
timeline (``tools/trace_merge.py``) never collides across ranks.  The
clock is injectable (``Tracer(clock=...)``) so tests drive spans from a
fake clock and assert exact durations.

Export is Chrome-trace-event JSON (``chrome_trace()`` / ``dump()``),
loadable in Perfetto / ``chrome://tracing``: one complete ("ph": "X")
event per span, ``pid`` = rank (the lane), ``tid`` = thread.
``otherData.clock`` states the export's clock: one reading of the
tracer's clock beside ``time.time_ns()``, taken together, so a dump can
be laid beside an xplane or another host's dump
(``tools/trace_merge.py`` aligns lanes by it).  The introspection
server's ``/trace`` endpoint drains the ring through the same exporter.

:class:`ProfileWindow` brackets a ``--profile_steps A:B`` window of the
train loop with ``jax.profiler`` device tracing and arms the tracer, so
the capture holds the window's ``compute`` / ``feed`` / ``step`` spans
as host events beside the device timeline, and emits one
``kind="profile"`` telemetry record (schema /11) carrying the window,
the trace directory and the tracer's per-phase duration summary.
"""

from __future__ import annotations

import collections
import functools
import threading
import time

from paddle_tpu.telemetry.scopes import scope_counts

# spans the ring keeps by default; at ~120 bytes/span this is ~1 MB
DEFAULT_RING = 8192

# the categories kept beside the ring (module docstring), and how many
SETUP_CAT, XLA_CAT = "setup", "xla"
_KEPT_CATS = (SETUP_CAT, XLA_CAT)
KEPT_MAX = 256
# ... of which XLA's builds may take this many: a set-up span ends after
# the builds under it, and must not find the room gone
_KEPT_XLA_MAX = 192
# a trace or a lowering shorter than this is counted, not spanned: jax
# fires one for every jitted helper it meets inside an outer trace
XLA_SPAN_FLOOR_S = 5e-3

# rank multiplier for deterministic span ids: ids never collide across
# ranks in a merged timeline, and (rank, seq) is recoverable from the id
_RANK_STRIDE = 1 << 32


class Span:
    """One completed named interval."""

    __slots__ = ("name", "cat", "span_id", "parent_id", "rank", "thread",
                 "t_start", "t_end", "args")

    def __init__(self, name, cat, span_id, parent_id, rank, thread,
                 t_start, t_end, args):
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        self.rank = rank
        self.thread = thread
        self.t_start = t_start      # tracer-clock seconds
        self.t_end = t_end
        self.args = args

    @property
    def dur_ms(self) -> float:
        return (self.t_end - self.t_start) * 1e3

    def to_event(self) -> dict:
        """One Chrome-trace complete event (timestamps in microseconds,
        the trace-event unit)."""
        args = {"id": self.span_id}
        if self.parent_id is not None:
            args["parent"] = self.parent_id
        if self.args:
            # a held program's ``op_scopes`` as its counts, not its lists
            args.update(scope_counts(self.args))
        return {
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": round(self.t_start * 1e6, 3),
            "dur": round((self.t_end - self.t_start) * 1e6, 3),
            "pid": self.rank, "tid": self.thread, "args": args,
        }


class _OpenSpan:
    """Token handed out by :meth:`Tracer.begin`; closed by ``end`` /
    ``cancel`` (or used as a context manager via :meth:`Tracer.span`)."""

    __slots__ = ("tracer", "name", "cat", "span_id", "parent_id",
                 "t_start", "args", "mirror", "_done")

    def __init__(self, tracer, name, cat, span_id, parent_id, t_start,
                 args, mirror):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        self.t_start = t_start
        self.args = args
        self.mirror = mirror    # the open TraceAnnotation of this span
        self._done = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.tracer.end(self)
        return False


class _Timed:
    """Context manager of :meth:`Tracer.timed`: an interval that is
    always measured (``seconds``, for the log line that reports it) and
    is a span when tracing is on — one pair of readings for both.
    ``args`` may be filled inside the block."""

    __slots__ = ("tracer", "name", "cat", "args", "seconds", "_tok", "_t0")

    def __init__(self, tracer, name, cat, args):
        self.tracer, self.name, self.cat, self.args = tracer, name, cat, args
        self.seconds = 0.0

    def __enter__(self):
        # off, the tracer's clock is not read (nor anything else of it)
        self._tok = self.tracer.begin(self.name, self.cat)
        self._t0 = (self._tok.t_start if self._tok is not None
                    else time.perf_counter())
        return self

    def __exit__(self, *exc):
        span = self.tracer.end(self._tok, **self.args)
        self.seconds = (span.t_end if span is not None
                        else time.perf_counter()) - self._t0
        return False


def setup_span(name: str, args_of=None):
    """Decorator: the call is a ``cat="setup"`` span of the process's
    tracer (nothing but a flag read when tracing is off).
    ``args_of(self, result)`` gives the span its args."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, *a, **kw):
            tracer = get_tracer()
            if not tracer.enabled:
                return fn(self, *a, **kw)
            with tracer.timed(name) as t:
                out = fn(self, *a, **kw)
                if args_of is not None:
                    t.args.update(args_of(self, out))
            return out
        return wrapped
    return deco


class _NullSpan:
    """The disabled-tracer fast path: one shared, allocation-free
    context manager.  ``span()`` on a disabled tracer returns this very
    object, so tracing-off call sites cost a method call and an
    attribute read — nothing that could perturb a trajectory."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Thread-aware span recorder over a bounded ring.

    :param enabled: record spans (False = every entry point is a no-op).
    :param rank: the ``pid`` lane of exported events and the high bits
        of every span id; default: the telemetry host index.
    :param clock: seconds-returning monotonic clock (injectable so tests
        drive spans deterministically); default ``time.perf_counter``.
    :param capacity: completed spans kept (oldest dropped first).
    """

    def __init__(self, enabled: bool = False, rank: int | None = None,
                 clock=None, capacity: int = DEFAULT_RING):
        if rank is None:
            from paddle_tpu.telemetry.registry import host_index

            rank = host_index()
        self.rank = int(rank)
        self.clock = clock or time.perf_counter
        self._enabled = bool(enabled)
        self._lock = threading.RLock()
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=max(int(capacity), 1))
        self._kept: list[Span] = []     # set-up and xla spans, not ringed
        self._seq = 0
        self._stack = threading.local()  # per-thread open-span stack
        self._dropped = 0

    # -- configuration ---------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def configure(self, enabled: bool | None = None, clock=None,
                  rank: int | None = None) -> "Tracer":
        with self._lock:
            if enabled is not None:
                self._enabled = bool(enabled)
            if clock is not None:
                self.clock = clock
            if rank is not None:
                self.rank = int(rank)
        if enabled and self is _default:
            _book_import(self)
        return self

    # -- recording -------------------------------------------------------------
    def _tstack(self) -> list:
        s = getattr(self._stack, "open", None)
        if s is None:
            s = self._stack.open = []
        return s

    def _next_id(self) -> int:
        with self._lock:
            sid = self.rank * _RANK_STRIDE + self._seq
            self._seq += 1
        return sid

    def begin(self, name: str, cat: str = "phase", **args) -> _OpenSpan | None:
        """Open a span (returns None when disabled).  The span nests
        under this THREAD's innermost open span, and is mirrored as a
        ``jax.profiler.TraceAnnotation`` of the same name: a host event
        in whatever device trace is being taken, on that trace's clock."""
        if not self._enabled:
            return None
        from jax.profiler import TraceAnnotation

        stack = self._tstack()
        parent = stack[-1].span_id if stack else None
        mirror = TraceAnnotation(name)
        mirror.__enter__()
        tok = _OpenSpan(self, name, cat, self._next_id(), parent,
                        self.clock(), args, mirror)
        stack.append(tok)
        return tok

    def _unwind(self, tok: _OpenSpan) -> None:
        """Take ``tok`` off this thread's stack and close its mirror.
        Closing a non-top token truncates the stack above it: anything
        still open there was abandoned by an exception path, and leaving
        it would mis-parent the rest of the run (their mirrors close
        first: annotations nest strictly per thread)."""
        stack = self._tstack()
        gone = [tok]
        if tok in stack:
            i = stack.index(tok)
            gone = stack[i:]
            del stack[i:]
        for t in reversed(gone):
            mirror, t.mirror = t.mirror, None
            if mirror is not None:
                mirror.__exit__(None, None, None)

    def end(self, tok: _OpenSpan | None, **args) -> Span | None:
        """Close a span opened by :meth:`begin` (None token = no-op, so
        call sites don't re-check the enabled flag)."""
        if tok is None or tok._done:
            return None
        tok._done = True
        t_end = self.clock()
        self._unwind(tok)
        if args:
            tok.args.update(args)
        return self._record(Span(
            tok.name, tok.cat, tok.span_id, tok.parent_id, self.rank,
            threading.current_thread().name, tok.t_start, t_end, tok.args))

    def _record(self, span: Span) -> Span:
        with self._lock:
            if span.cat in _KEPT_CATS:
                if len(self._kept) < (KEPT_MAX if span.cat == SETUP_CAT
                                      else _KEPT_XLA_MAX):
                    self._kept.append(span)
                else:
                    self._dropped += 1
            else:
                if len(self._spans) == self._spans.maxlen:
                    self._dropped += 1
                self._spans.append(span)
        return span

    def cancel(self, tok: _OpenSpan | None) -> None:
        """Discard an open span without recording it (e.g. the feed pull
        that turned out to be the end-of-pass sentinel)."""
        if tok is None or tok._done:
            return
        tok._done = True
        self._unwind(tok)

    def span(self, name: str, cat: str = "phase", **args):
        """Context-manager form.  Disabled tracers return one shared
        no-op object — the hot-loop guard the bit-identical-trajectory
        test pins down."""
        if not self._enabled:
            return _NULL_SPAN
        return self.begin(name, cat, **args)

    def timed(self, name: str, cat: str = SETUP_CAT, **args) -> _Timed:
        """``with tracer.timed("engine_ready") as t: ...`` then
        ``t.seconds``: measured whether or not tracing is on, and a span
        (``cat="setup"`` by default) when it is."""
        return _Timed(self, name, cat, args)

    def add_span(self, name: str, t_start: float, t_end: float,
                 cat: str = "phase", parent_id: int | None = None,
                 **args) -> int | None:
        """Record a RETROSPECTIVE span from explicit clock readings (the
        serving engine reconstructs a request's queue/prefill/decode
        phases from its own timestamps at retire time).  Returns the
        span id (usable as ``parent_id`` for children), or None when
        disabled."""
        if not self._enabled:
            return None
        return self._record(Span(
            name, cat, self._next_id(), parent_id, self.rank,
            threading.current_thread().name, float(t_start), float(t_end),
            args)).span_id

    def _retro(self, name: str, duration: float, fun=None) -> Span:
        """One of XLA's builds, just over: ``[now - duration, now]`` under
        this thread's innermost open span (:class:`XlaBuildListener`)."""
        now = self.clock()
        stack = self._tstack()
        parent = stack[-1] if stack else None
        args = {"fun": fun} if fun else {}
        if parent is not None:
            args["under"] = parent.name
            key = _PARENT_COUNT.get(name)
            if key:
                parent.args[key] = parent.args.get(key, 0) + 1
        return self._record(Span(
            name, XLA_CAT, self._next_id(),
            parent.span_id if parent is not None else None, self.rank,
            threading.current_thread().name, now - duration, now, args))

    # -- reading ---------------------------------------------------------------
    @property
    def spans(self) -> list[Span]:
        with self._lock:
            return self._kept + list(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def seq_watermark(self) -> int:
        """The next seq this tracer will allocate — a stable "spans
        from here on" marker.  Positional ring indices are invalidated
        by a concurrent ``/trace`` drain or a ring wrap; the seq
        embedded in every span id is not."""
        with self._lock:
            return self._seq

    def clear(self) -> None:
        """Empty the ring (a measurement window opens).  What is kept
        beside it -- set-up, XLA's builds -- stays: ``drain`` takes it."""
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    def drain(self) -> list[Span]:
        """Pop every completed span, the kept ones first (the ``/trace``
        endpoint's read — each scrape gets every span once, so a polling
        scraper streams the timeline instead of re-downloading it)."""
        with self._lock:
            out = self._kept + list(self._spans)
            self._kept = []
            self._spans.clear()
        return out

    # -- export ----------------------------------------------------------------
    def chrome_trace(self, spans: list[Span] | None = None,
                     drain: bool = False) -> dict:
        """Chrome-trace-event JSON dict (Perfetto / chrome://tracing
        loadable): the spans as complete events plus process/thread
        metadata naming this rank's lane, and ``otherData.clock``."""
        if spans is None:
            spans = self.drain() if drain else self.spans
        events = [{
            "name": "process_name", "ph": "M", "pid": self.rank, "tid": 0,
            "args": {"name": f"rank {self.rank}"},
        }]
        threads = []
        for s in spans:
            if s.thread not in threads:
                threads.append(s.thread)
                events.append({
                    "name": "thread_name", "ph": "M", "pid": s.rank,
                    "tid": s.thread, "args": {"name": s.thread}})
            events.append(s.to_event())
        # the events' clock beside the wall clock, read together: what
        # lays this dump beside an xplane or another host's dump
        clock = {"tracer_s": self.clock(), "unix_ns": time.time_ns()}
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"rank": self.rank, "spans": len(spans),
                              "dropped": self.dropped, "clock": clock}}

    def dump(self, path: str, drain: bool = False) -> str:
        """Write :meth:`chrome_trace` to ``path`` (parent dirs created)
        — the per-rank file ``tools/trace_merge.py`` consumes."""
        import json
        import os

        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(drain=drain), f)
        return path

    def phase_summary(self, spans: list[Span] | None = None) -> dict:
        """{span name: {count, total_ms, p50_ms, p99_ms, max_ms}} over
        the current ring — the "Trace spans" table of
        ``tools/metrics_to_md.py`` and the ``profile`` record's span
        attachment.  Percentiles are exact (computed from the raw
        durations, not histogram buckets)."""
        by_name: dict[str, list[float]] = {}
        for s in (self.spans if spans is None else spans):
            by_name.setdefault(s.name, []).append(s.dur_ms)
        out = {}
        for name, durs in sorted(by_name.items()):
            durs.sort()
            out[name] = {
                "count": len(durs),
                "total_ms": round(sum(durs), 3),
                "p50_ms": round(_pctl(durs, 50.0), 3),
                "p99_ms": round(_pctl(durs, 99.0), 3),
                "max_ms": round(durs[-1], 3),
            }
        return out


def _pctl(sorted_vals: list[float], q: float) -> float:
    """Interpolated percentile over pre-sorted values."""
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    rank = (q / 100.0) * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (rank - lo)


# -- the process-global tracer -------------------------------------------------

_default: Tracer | None = None
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer every built-in instrumentation point
    uses; created on first use with ``--trace_spans`` /
    ``PADDLE_TPU_TRACE_SPANS`` deciding whether it records."""
    global _default
    with _default_lock:
        if _default is None:
            from paddle_tpu.core import flags

            _default = Tracer(enabled=bool(flags.get("trace_spans")),
                              capacity=int(flags.get("trace_ring_size")))
            if _default.enabled:
                _book_import(_default)
        return _default


def configure_tracing(enabled: bool | None = None, clock=None,
                      rank: int | None = None) -> Tracer:
    """Flip the global tracer's switches (tests, notebooks).  The
    trainer re-reads the ``trace_spans`` flag at ``train()`` entry via
    this, so a flag set after import still takes effect."""
    return get_tracer().configure(enabled=enabled, clock=clock, rank=rank)


def _book_import(tracer: Tracer) -> None:
    """``import paddle_tpu`` as a set-up span, from the two readings the
    package took at the top and the bottom of its ``__init__`` — once,
    when the process's tracer is armed (what ran before the package's
    first line is the gap before this span; nothing here guesses it)."""
    global _import_booked
    if _import_booked or tracer.clock is not time.perf_counter:
        return
    import paddle_tpu

    window = getattr(paddle_tpu, "_IMPORT_WINDOW", None)
    if window is not None:
        _import_booked = True
        tracer.add_span("import_paddle_tpu", *window, cat=SETUP_CAT)


_import_booked = False


# -- XLA's build events ---------------------------------------------------------

# jax.monitoring duration event -> (span name, phase label).  The four
# that jax 0.9 fires when it builds a program; none fires in a loop that
# only dispatches what is compiled.
_XLA_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("xla_trace", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("xla_lower", "lower"),
    "/jax/core/compile/backend_compile_duration": ("xla_compile", "compile"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("xla_cache_fetch", "cache_fetch"),
}
# a program is one of the last two: what the parent span counts it as,
# and ``xla_programs_total``'s ``how``
_PARENT_COUNT = {"xla_compile": "compiles", "xla_cache_fetch": "cache_fetches"}
_HOW = {"compile": "compiled", "cache_fetch": "fetched"}


class XlaBuildListener:
    """XLA's build events, heard inside the program.

    Always: ``xla_programs_total{how=compiled|fetched}`` and
    ``xla_build_seconds_total{phase=trace|lower|compile|cache_fetch}`` in
    the registry, and ``events``, the raw count of each event as jax
    fired it.  With the tracer enabled each event is also a retrospective
    span over ``[now - duration, now]`` under the innermost live span of
    the thread that built, which gets ``compiles`` / ``cache_fetches``.

    On a persistent-cache hit jax fires ``backend_compile_duration``
    AROUND ``cache_retrieval_time_sec`` (the compile event times
    ``compile_or_get_cached``): the pair is ONE program, fetched.  The
    fetch is booked when it fires; the compile event that follows on the
    same thread widens it to its own interval (key, read, load) and adds
    the function's name, and books no compile.

    ``tracer`` / ``registry``: zero-argument callables (default: the
    process's own), so a test drives one with a fake clock.
    """

    def __init__(self, tracer=None, registry=None):
        if registry is None:
            from paddle_tpu.telemetry.registry import get_default_registry

            registry = get_default_registry
        self._tracer = tracer or get_tracer
        self._registry = registry
        self._lock = threading.Lock()
        self.events = {phase: 0 for _, phase in _XLA_EVENTS.values()}
        self._pending = threading.local()  # this thread's unclaimed fetch

    def _count(self, how: str | None, phase: str, seconds: float) -> None:
        reg = self._registry()
        if how:
            reg.counter("xla_programs_total",
                        "programs XLA built for this process: compiled, or "
                        "fetched from the persistent cache").inc(how=how)
        reg.counter("xla_build_seconds_total",
                    "seconds jax spent building programs, by phase").inc(
                        max(seconds, 0.0), phase=phase)

    def __call__(self, event: str, duration: float, **kw) -> None:
        hit = _XLA_EVENTS.get(event)
        if hit is None:
            return
        try:
            self._heard(*hit, float(duration), kw.get("fun_name"))
        except Exception as e:     # jax calls this inside its compile path
            from paddle_tpu.core import logger as log

            log.debug("xla build listener: %s: %s", type(e).__name__, e)

    def _heard(self, name: str, phase: str, duration: float, fun) -> None:
        with self._lock:
            self.events[phase] += 1
        fetch = None
        if phase == "compile":
            fetch = getattr(self._pending, "fetch", None)
            self._pending.fetch = None
        tracer = self._tracer()
        if fetch is not None and duration >= fetch[0]:
            # the compile event around a fetch: one program, fetched
            self._count(None, "cache_fetch", duration - fetch[0])
            span = fetch[1]
            if span is not None:
                span.t_end = tracer.clock()
                span.t_start = span.t_end - duration
                if fun:
                    span.args["fun"] = fun
            return
        self._count(_HOW.get(phase), phase, duration)
        span = None
        if tracer.enabled and (duration >= XLA_SPAN_FLOOR_S
                               or phase in _HOW):
            span = tracer._retro(name, duration, fun)
        if phase == "cache_fetch":
            self._pending.fetch = (duration, span)


_listener: XlaBuildListener | None = None


def install_xla_listener() -> XlaBuildListener:
    """Register the process's one :class:`XlaBuildListener` with
    ``jax.monitoring`` (``import paddle_tpu`` does; again is a no-op)."""
    global _listener
    with _default_lock:
        if _listener is None:
            import jax.monitoring

            _listener = XlaBuildListener()
            jax.monitoring.register_event_duration_secs_listener(_listener)
        return _listener


def uninstall_xla_listener() -> None:
    """Take the process's listener off ``jax.monitoring`` (tests)."""
    global _listener
    with _default_lock:
        if _listener is not None:
            import jax.monitoring

            jax.monitoring.unregister_event_duration_listener(_listener)
            _listener = None


# -- windowed device profiling (--profile_steps A:B) ---------------------------


def parse_profile_steps(spec: str | None) -> tuple[int, int] | None:
    """``"A:B"`` -> (A, B), the half-open dispatch-step window
    [A, B) to capture; None/empty = no profiling.  A bare ``"N"`` means
    one step, [N, N+1)."""
    if not spec:
        return None
    s = str(spec).strip()
    if ":" in s:
        a, b = s.split(":", 1)
        lo, hi = int(a), int(b)
    else:
        lo, hi = int(s), int(s) + 1
    if lo < 0 or hi <= lo:
        raise ValueError(
            f"--profile_steps must be 'A:B' with 0 <= A < B, got {spec!r}")
    return lo, hi


class ProfileWindow:
    """Bracket dispatch steps [start, stop) of a train loop with a
    ``jax.profiler`` trace, so the capture holds exactly the steps the
    operator asked for instead of a whole run's worth of profile data.

    The trainer calls :meth:`maybe_start` before dispatching step ``n``
    and :meth:`maybe_stop` after.  A window arms its tracer, whose live
    spans are mirrored into the capture (``Tracer.begin``): the device
    timeline carries every span that opened and closed while the trace
    ran — the ``compute`` span of every step of the window, the ``feed``
    spans of all but the first, whole ``step`` spans strictly inside it
    (the trace starts and stops around a dispatch, inside a step).
    :meth:`close` stops a window left open by a run shorter than B.
    One ``kind="profile"`` record (schema /11) is emitted when the
    window closes: the step range, the trace directory and the tracer's
    per-phase duration summary.

    Profiling must never kill training: start/stop failures are logged
    and the window deactivates itself.
    """

    def __init__(self, spec: str | None, trace_dir: str | None = None,
                 registry=None, tracer: Tracer | None = None):
        self.window = parse_profile_steps(spec)
        self.trace_dir = trace_dir
        self.registry = registry
        self.tracer = tracer
        if self.window is not None and tracer is not None:
            tracer.configure(enabled=True)
        self.active = False
        self.emitted: dict | None = None
        self._t0 = 0.0
        self._span_floor = 0

    def _resolve_dir(self) -> str:
        if self.trace_dir:
            return self.trace_dir
        import os
        import tempfile

        from paddle_tpu.telemetry.registry import host_index

        return os.path.join(tempfile.gettempdir(),
                            f"paddle_tpu_profile_host{host_index()}")

    def maybe_start(self, step: int) -> bool:
        if self.window is None or self.active or step != self.window[0]:
            return False
        import jax

        from paddle_tpu.core import logger as log

        self.trace_dir = self._resolve_dir()
        try:
            jax.profiler.start_trace(self.trace_dir)
        except Exception as e:
            log.warning("--profile_steps: start_trace failed (%s: %s); "
                        "profiling disabled for this run",
                        type(e).__name__, e)
            self.window = None
            return False
        self.active = True
        self._t0 = time.perf_counter()
        if self.tracer is not None:
            # a SEQ watermark, not a ring index: a mid-window /trace
            # drain or ring wrap shifts positions but not span ids
            self._span_floor = self.tracer.seq_watermark()
        return True

    def maybe_stop(self, step: int, fence=None) -> dict | None:
        """Close the window once ``step`` (the NEXT step to dispatch)
        reaches B; returns the emitted profile record.  ``fence`` — an
        array from the window's last step — is blocked on before the
        trace stops, so the capture holds the device work it brackets
        (dispatch is async; values are untouched, only timing)."""
        if not self.active or step < self.window[1]:
            return None
        if fence is not None:
            import jax

            from paddle_tpu.core import logger as log

            try:
                jax.block_until_ready(fence)
            except Exception as e:
                log.debug("--profile_steps: fence before stop_trace "
                          "failed (%s); capture may truncate the last "
                          "step", e)
        return self.close()

    def close(self) -> dict | None:
        if not self.active:
            return None
        import jax

        from paddle_tpu.core import logger as log

        self.active = False
        wall_ms = (time.perf_counter() - self._t0) * 1e3
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("--profile_steps: stop_trace failed (%s: %s); the "
                        "device capture may be incomplete",
                        type(e).__name__, e)
        rec = {
            "start_step": self.window[0], "end_step": self.window[1],
            "steps": self.window[1] - self.window[0],
            "trace_dir": self.trace_dir,
            "wall_ms": round(wall_ms, 3),
        }
        if self.tracer is not None and self.tracer.enabled:
            # summarize only spans recorded DURING the window (seq at
            # or past the start watermark), so the profile record's
            # phase table matches the device capture even when a
            # /trace scrape drained the ring mid-window
            spans = [s for s in self.tracer.spans
                     if s.span_id % _RANK_STRIDE >= self._span_floor]
            rec["spans"] = self.tracer.phase_summary(spans)
        if self.registry is None:
            from paddle_tpu.telemetry.registry import get_default_registry

            self.registry = get_default_registry()
        if self.registry.active:
            rec = self.registry.emit(rec, kind="profile")
        log.info("--profile_steps: captured steps [%d, %d) to %s "
                 "(%.1f ms)", self.window[0], self.window[1],
                 self.trace_dir, wall_ms)
        self.emitted = rec
        return rec
