"""Structured telemetry for paddle_tpu — the user-facing facade.

One metrics layer unifies the scattered primitives (``core/stat.py``
scope timers, ``profiler.py`` MFU accounting, ``trainer/event.py``
callbacks): a :class:`MetricsRegistry` of counters /
gauges / histograms with labeled series and pluggable sinks, plus a
structured record stream — one record per train step from ``SGD.train``
and ``trainer/cli.py`` with {step, loss, step_ms, examples_per_sec,
tokens_per_sec, mfu_pct, hbm_gbps, comm_bytes} — read by one toolchain
(``tools/metrics_to_md.py``).

Typical operator setup::

    from paddle_tpu import metrics
    metrics.configure(jsonl="/var/log/train.metrics.jsonl")   # or:
    #   PADDLE_TPU_METRICS_JSONL=... / --metrics_jsonl=... (trainer CLI)
    trainer.train(...)          # one JSONL record per step, tail -f-able

Tests and notebooks::

    sink = metrics.MemorySink()
    metrics.get_registry().add_sink(sink)
    ...
    sink.records                # list of step dicts

Related: the multihost flight recorder
(:mod:`paddle_tpu.distributed.multihost`) keeps the last N step records
+ heartbeats in a ring buffer and dumps them on exception/SIGTERM.
"""

from __future__ import annotations

from paddle_tpu.telemetry import (  # noqa: F401
    SCHEMA,
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    LoggingSink,
    MemorySink,
    MetricsRegistry,
    StepTelemetry,
    capture_comm,
    comm_snapshot,
    get_default_registry,
    host_index,
    json_default,
    record_comm,
    tokens_in_feed,
)


def get_registry() -> MetricsRegistry:
    """The process-global registry every built-in instrument uses."""
    return get_default_registry()


def configure(jsonl: str | None = None, memory: bool = False,
              log: bool = False, registry: MetricsRegistry | None = None):
    """Attach sinks to the (default) registry; returns the sinks added.

    ``jsonl``: path for a JSONL file sink; ``memory``: add a MemorySink
    (returned for inspection); ``log``: mirror records through the
    glog-style logger.

    Idempotent for ``jsonl`` (same path) and ``log``: re-running the
    setup (notebook cell, a library configuring after user code) must
    not attach duplicate sinks that double every record.  ``memory``
    always adds a fresh sink — the caller wants that exact object."""
    reg = registry or get_default_registry()
    added = []
    if jsonl and not any(getattr(s, "path", None) == jsonl
                         for s in reg.sinks):
        added.append(JsonlSink(jsonl))
    if memory:
        added.append(MemorySink())
    if log and not any(isinstance(s, LoggingSink) for s in reg.sinks):
        added.append(LoggingSink())
    for s in added:
        reg.add_sink(s)
    return added


def configure_from_flags(registry: MetricsRegistry | None = None):
    """Honor the central flag registry (``--metrics_jsonl=PATH`` /
    ``PADDLE_TPU_METRICS_JSONL``): idempotently attach a JSONL sink.
    Called by ``SGD.train`` and the trainer CLI on entry."""
    from paddle_tpu.core import flags

    path = flags.get("metrics_jsonl")
    if not path:
        return None
    reg = registry or get_default_registry()
    for s in reg.sinks:
        if getattr(s, "path", None) == path:
            return s
    sink = JsonlSink(path)
    reg.add_sink(sink)
    return sink
