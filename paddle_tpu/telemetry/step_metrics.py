"""Per-step train telemetry: one structured record per optimizer step.

``SGD.train`` and ``trainer/cli.py`` hand this class the raw
observables of a step — loss, wall ms, batch size, token count — and it
derives the operator-facing rates (examples/sec, tokens/sec, achieved
MFU% against :func:`paddle_tpu.profiler.device_peak_flops`, HBM GB/s
from XLA cost-analysis byte counts), updates the pull-side aggregates
(step-latency histogram, loss gauge, throughput counters), attaches the
comm-bytes snapshot from the collective wrappers, emits through the
registry sinks, and appends to the flight recorder so the last N steps
survive a crash.

FLOP/byte counts come from ``jitted.lower(...).compile().cost_analysis()``
cached per compile signature (:meth:`cost_for`) — lowering re-traces but
hits the executable cache, so the analysis is paid once per feed-shape
bucket, exactly like compilation itself.
"""

from __future__ import annotations

import time

from paddle_tpu.core import logger as log


class StepTelemetry:
    """Builds/emits step records for one training run.

    :param registry: MetricsRegistry (default: the process-global one).
    :param run: label for this stream ("train", "time", ...).
    :param flight: optional FlightRecorder receiving every record.
    :param cost_cache: optional dict to hold per-signature cost results.
        Pass a dict owned by the jitted step's owner (SGD does) so a
        SECOND run over the same compiled program reuses the first run's
        analysis — the trace cache means re-lowering an already-traced
        program yields an empty comm capture.
    """

    def __init__(self, registry=None, run: str = "train", flight=None,
                 cost_cache: dict | None = None):
        from paddle_tpu.telemetry import registry as reg_mod

        self.registry = registry or reg_mod.get_default_registry()
        self.run = run
        self.flight = flight
        self._cost_cache = cost_cache if cost_cache is not None else {}
        self._peak_flops: float | None = None
        self.global_step = 0
        # schema/5: stamp which kernel path produced this run's records
        # (resolved once — routing is a build-time decision per step fn).
        # A failure here is an error, never a silent False: the stamp is
        # how a chip run shows it did not fall back to the references.
        from paddle_tpu.ops.pallas import tpp

        self.fused_kernels = bool(tpp.fused_enabled())

    # -- hardware / program constants -----------------------------------------
    def peak_flops(self) -> float:
        if self._peak_flops is None:
            from paddle_tpu import profiler

            # raises for a TPU kind without a published peak on file
            self._peak_flops = profiler.device_peak_flops()
        return self._peak_flops

    def cost_for(self, sig, lower_fn) -> tuple[float, float, dict]:
        """(flops, bytes_accessed, comm_bytes) of one step execution,
        cached by ``sig`` (the feed signature).  ``lower_fn`` must return
        a jax ``Lowered`` (e.g. ``lambda: jitted.lower(*args)``); any
        failure degrades to (0, 0, {}) — a record without MFU beats no
        record.

        The lowering runs under ``capture_comm``, so the collective
        wrappers traced in THIS program report its per-execution payload
        (and the global comm counters are left to the program's own jit
        trace).  Cost analysis is read from the ``Lowered`` (unoptimized
        HLO analysis — no second compilation); only when that comes back
        empty is ``.compile()`` forced."""
        if sig in self._cost_cache:
            return self._cost_cache[sig]
        from paddle_tpu.telemetry import registry as reg_mod

        flops, nbytes, comm = 0.0, 0.0, {}
        try:
            with reg_mod.capture_comm() as comm:
                lowered = lower_fn()
            cost = lowered.cost_analysis()
            if not cost:
                cost = lowered.compile().cost_analysis()
            if cost:
                flops = float(cost.get("flops", 0.0) or 0.0)
                nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
        except Exception as e:
            # documented degrade: a record without MFU beats no record
            log.debug("cost analysis failed for signature (%s); step "
                      "records carry no FLOPs/bytes", e)
        self._cost_cache[sig] = (flops, nbytes, dict(comm))
        return self._cost_cache[sig]

    # -- the per-step record ---------------------------------------------------
    def record_step(self, *, loss: float, step_ms: float,
                    examples: int | None = None, tokens: int | None = None,
                    flops: float = 0.0, bytes_accessed: float = 0.0,
                    pass_id: int | None = None, batch_id: int | None = None,
                    metrics: dict | None = None, step: int | None = None,
                    comm: dict | None = None,
                    input_wait_ms: float | None = None,
                    host_stall_ms: float | None = None,
                    padding_ratio: float | None = None,
                    extra: dict | None = None) -> dict:
        """Assemble, aggregate, emit and flight-record one step record.

        ``comm``: per-execution collective payload of this step's program
        ({"op/axis": bytes}, from :meth:`cost_for`); when None, the
        registry's CUMULATIVE comm counters stand in (clearly weaker —
        they sum over every traced program).

        ``input_wait_ms``: host time the step loop spent blocked waiting
        for this batch's feed (0 when the prefetcher kept up — the
        host-starvation signal).  ``host_stall_ms``: amortized per-step
        device-fence wait (the ``sync_period`` readback backlog divided
        across its window).  Both are schema/2 fields and also land as
        pull-side gauges.

        Returns the stamped record.  Emission is skipped when the
        registry has no sinks; the flight recorder gets the record
        either way (it is the crash dump, not the live stream)."""
        from paddle_tpu.telemetry import registry as reg_mod

        if step is None:
            step = self.global_step
        self.global_step = step + 1
        sec = max(step_ms, 1e-9) / 1e3
        rec: dict = {
            "kind": "step",
            "run": self.run,
            "step": step,
            "loss": float(loss),
            "step_ms": round(float(step_ms), 4),
            "fused_kernels": self.fused_kernels,
        }
        if pass_id is not None:
            rec["pass_id"] = pass_id
        if batch_id is not None:
            rec["batch_id"] = batch_id
        rec["examples_per_sec"] = (
            round(examples / sec, 2) if examples else 0.0)
        if tokens:
            rec["tokens_per_sec"] = round(tokens / sec, 1)
        peak = self.peak_flops()
        rec["mfu_pct"] = (
            round(flops / sec / peak * 100.0, 2) if flops and peak else 0.0)
        if flops:
            rec["flops"] = flops
        if bytes_accessed:
            rec["hbm_gbps"] = round(bytes_accessed / sec / 1e9, 2)
        if input_wait_ms is not None:
            rec["input_wait_ms"] = round(float(input_wait_ms), 4)
        if host_stall_ms is not None:
            rec["host_stall_ms"] = round(float(host_stall_ms), 4)
        if padding_ratio is not None:
            # padded/total timesteps of this step's sequence feeds — the
            # bucketing signal (schema/10; >25% means most-of-a-quarter
            # of the recurrent flops ran on padding)
            rec["padding_ratio"] = round(float(padding_ratio), 4)
        if comm is None:
            comm = reg_mod.comm_snapshot(self.registry)
        if comm:
            rec["comm_bytes"] = comm
        if metrics:
            rec["metrics"] = {k: float(v) for k, v in metrics.items()}
        if extra:
            rec.update(extra)

        # pull-side aggregates ride along for snapshot()/operator scrapes
        r = self.registry
        r.histogram("step_ms", "train step wall ms").observe(
            float(step_ms), run=self.run)
        r.gauge("loss", "last step loss").set(float(loss), run=self.run)
        if examples:
            r.counter("examples", "examples consumed").inc(
                float(examples), run=self.run)
        if tokens:
            r.counter("tokens", "tokens consumed").inc(
                float(tokens), run=self.run)
        r.counter("steps", "optimizer steps taken").inc(1.0, run=self.run)
        if input_wait_ms is not None:
            r.gauge("input_wait_ms",
                    "host ms the step loop waited for input").set(
                float(input_wait_ms), run=self.run)
        if host_stall_ms is not None:
            r.gauge("host_stall_ms",
                    "amortized device-fence ms per step").set(
                float(host_stall_ms), run=self.run)
        if padding_ratio is not None:
            r.gauge("padding_ratio",
                    "padded/total timesteps of the step's feeds").set(
                float(padding_ratio), run=self.run)

        if r.active:
            rec = r.emit(rec)
        else:
            rec.setdefault("ts", time.time())
        if self.flight is not None:
            self.flight.record(rec)
        return rec


def tokens_in_feed(feed: dict) -> int | None:
    """Sum of sequence lengths across SequenceBatch feed slots (None when
    the feed carries no sequences) — the tokens/sec numerator."""
    total, seen = 0, False
    for v in feed.values():
        length = getattr(v, "length", None)
        if length is not None:
            try:
                import numpy as np

                total += int(np.sum(np.asarray(length)))
                seen = True
            except (TypeError, ValueError):  # ragged/exotic length slot
                pass
    return total if seen else None
