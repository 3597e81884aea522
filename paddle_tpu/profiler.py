"""Profiling + MFU accounting.

Reference parity: ``paddle/utils/Stat.h`` RAII timers (see core/stat.py),
``hl_profiler_start/end`` cuda-profiler hooks, and the ``--job=time``
benchmark mode (``paddle/trainer/TrainerBenchmark.cpp``).  TPU-native:
``jax.profiler`` traces for xprof, XLA cost analysis for FLOP counts, and a
step-timing harness that reports model FLOPs utilisation against the
chip's peak — the number SURVEY's north star is phrased in."""

from __future__ import annotations

import contextlib
import time

import jax

from paddle_tpu.core.stat import global_stat

# bf16 peak FLOPs/s per chip (MXU), keyed by device_kind prefix
_PEAK_FLOPS = {
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,  # v5e (Google Cloud "TPU v5e")
    "tpu v5": 459e12,  # v5p
    "tpu v6 lite": 918e12,
}
_CPU_NOMINAL_FLOPS = 1e11  # CPU testbed: keeps MFU fields finite, not a peak


def device_peak_flops() -> float:
    """Peak bf16 FLOP/s of the first device.  A TPU whose ``device_kind``
    is not in the table is an error — an MFU against a guessed peak is
    worse than none."""
    d = jax.devices()[0]
    if d.platform != "tpu":
        return _CPU_NOMINAL_FLOPS
    kind = d.device_kind.lower()
    for k, v in _PEAK_FLOPS.items():
        if kind.startswith(k):
            return v
    raise ValueError(f"no peak FLOP/s on file for TPU device_kind "
                     f"{d.device_kind!r}; add it to profiler._PEAK_FLOPS")


@contextlib.contextmanager
def profile(log_dir: str):
    """Capture a jax.profiler trace viewable in xprof/tensorboard
    (hl_profiler_start/end analog)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_annotation(name: str):
    """Named region inside a profile (REGISTER_TIMER analog on-device)."""
    return jax.profiler.TraceAnnotation(name)


def flops_of(fn, *args, **kwargs) -> float:
    """Total FLOPs of one call of jitted ``fn`` via XLA cost analysis."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    cost = lowered.compile().cost_analysis()
    return float(cost.get("flops", 0.0))


class BenchmarkResult:
    def __init__(self, seconds_per_step: float, flops_per_step: float,
                 peak_flops: float):
        self.seconds_per_step = seconds_per_step
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops

    @property
    def tflops_per_sec(self) -> float:
        return self.flops_per_step / self.seconds_per_step / 1e12

    @property
    def mfu(self) -> float:
        return (self.flops_per_step / self.seconds_per_step) / self.peak_flops

    def __repr__(self):
        return (f"BenchmarkResult({self.seconds_per_step * 1e3:.2f} ms/step, "
                f"{self.tflops_per_sec:.1f} TFLOP/s, mfu={self.mfu:.1%})")


def benchmark(fn, args: tuple, iters: int = 50, warmup: int = 3,
              name: str = "benchmark") -> BenchmarkResult:
    """``--job=time`` analog: time jitted ``fn(*args)`` and report ms/step,
    TFLOP/s and MFU.  ``fn`` must be jax-jittable and return arrays.

    Timing is the two-point method: time n1 and n2 pipelined dispatches
    each fenced by ``block_until_ready``, and divide the difference by
    (n2 - n1) — the constant dispatch + fence cost cancels out.
    (``chip_smoke.py``'s device phase checks on the chip that
    ``block_until_ready`` really waits for the device.)
    """
    compiled = jax.jit(fn).lower(*args).compile()  # one compile: timing
    cost = compiled.cost_analysis()                # loop + FLOPs share it
    flops = float(cost.get("flops", 0.0))
    out = None
    for _ in range(warmup):
        out = compiled(*args)
    jax.block_until_ready(out)

    def run(n: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = compiled(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    n1 = max(1, iters // 10)
    n2 = max(iters, n1 + 1)
    t1 = min(run(n1) for _ in range(2))
    t2 = min(run(n2) for _ in range(2))
    dt = max(t2 - t1, 1e-9) / (n2 - n1)
    global_stat.add(name, dt)
    return BenchmarkResult(dt, flops, device_peak_flops())


# ---- trace-based device timing ----------------------------------------------

def read_device_trace(logdir: str):
    """Parse a jax.profiler chrome trace: returns (op_events, module_ms)
    where op_events are the per-HLO-op events of the device's "XLA Ops"
    thread (name, dur_us, tf_op = the operation's jax name stack) and
    module_ms sums the "XLA Modules" thread — the device-side wall time.
    ``device_ms_by_part`` reads the first, ``device_step_ms`` the
    second."""
    import glob
    import gzip
    import json
    import os

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"no trace under {logdir}")
    tr = json.load(gzip.open(files[-1]))
    pids, tids = {}, {}
    for e in tr["traceEvents"]:
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                pids[e["pid"]] = e["args"].get("name")
            elif e.get("name") == "thread_name":
                tids[(e["pid"], e["tid"])] = e["args"].get("name")
    events = []
    module_us = 0.0
    for e in tr["traceEvents"]:
        if e.get("ph") != "X" or "TPU" not in (pids.get(e["pid"]) or ""):
            continue
        tname = tids.get((e["pid"], e["tid"]))
        if tname == "XLA Modules":
            module_us += e.get("dur", 0.0)
        elif tname == "XLA Ops":
            a = e.get("args", {})
            events.append({
                "name": e["name"],
                "dur_us": e.get("dur", 0.0),
                "tf_op": a.get("tf_op", ""),
            })
    return events, module_us / 1000.0


def device_ms_by_part(events, steps: int = 1) -> list[dict]:
    """``read_device_trace``'s op events summed by the sublayer that
    issued them (``telemetry.scopes.part_of`` over ``tf_op``): rows
    ``{"part", "ms", "share", "fwd_ms", "bwd_ms"}`` — ms a step over
    ``steps``, ``share`` of the summed operation time — longest first,
    ``unscoped`` last.  Loops and branches are left out: their bodies'
    operations are events themselves."""
    from paddle_tpu.telemetry.scopes import CONTAINERS, UNSCOPED, part_of

    rows: dict[str, dict] = {}
    for e in events:
        if e["name"].split(".")[0].lstrip("%") in CONTAINERS:
            continue
        name, way = part_of(e["tf_op"])
        row = rows.setdefault(name or UNSCOPED, {"fwd": 0.0, "bwd": 0.0})
        row[way] += e["dur_us"]
    total = sum(r["fwd"] + r["bwd"] for r in rows.values()) or 1.0
    per = 1e3 * max(int(steps), 1)
    out = [{"part": name, "ms": (r["fwd"] + r["bwd"]) / per,
            "share": (r["fwd"] + r["bwd"]) / total,
            "fwd_ms": r["fwd"] / per, "bwd_ms": r["bwd"] / per}
           for name, r in rows.items()]
    return sorted(out, key=lambda r: (r["part"] == UNSCOPED, -r["ms"]))


def format_parts(rows: list[dict]) -> str:
    """``device_ms_by_part``'s rows as the table ``--job=time`` prints."""
    lines = [f"{'part':<22}{'ms/step':>10}{'share':>8}"
             f"{'fwd ms':>10}{'bwd ms':>10}"]
    lines += [f"{r['part']:<22}{r['ms']:>10.3f}{100 * r['share']:>7.1f}%"
              f"{r['fwd_ms']:>10.3f}{r['bwd_ms']:>10.3f}" for r in rows]
    return "\n".join(lines)


def device_step_ms(step_fn, steps: int = 10, warmup: int = 3,
                   parts: list | None = None) -> float:
    """ms/step measured on the DEVICE via a jax.profiler trace: the sum
    of the device's "XLA Modules" durations, so host dispatch gaps (which
    dominate wall-clock timing of sub-10 ms steps) are not counted.
    ``step_fn`` must keep its own state and return an array (the trace
    window is fenced on it).  ``parts`` (a list) gets the step's split
    by sublayer, ``device_ms_by_part``'s rows."""
    import shutil
    import tempfile

    for _ in range(warmup):
        out = step_fn()
    jax.block_until_ready(out)
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(logdir)
        for _ in range(steps):
            out = step_fn()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        events, module_ms = read_device_trace(logdir)
        if parts is not None:
            parts.extend(device_ms_by_part(events, steps))
        return module_ms / steps
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def step_ms_with_fallback(step_fn, wall_fn, steps: int = 10,
                          warmup: int = 3, parts: list | None = None
                          ) -> tuple[float, str, str]:
    """(ms, "device-side"|"wall-clock", reason): try device_step_ms, fall
    back to ``wall_fn()`` (a callable returning ms) when the trace is
    unavailable OR empty (non-TPU backends write traces whose module
    filter matches nothing — a 0.0 must never masquerade as a
    measurement).  The reason string records why the fallback fired.
    ``parts``: as ``device_step_ms`` (left empty by the fallback)."""
    try:
        ms = device_step_ms(step_fn, steps=steps, warmup=warmup, parts=parts)
        if ms > 0:
            return ms, "device-side", ""
        reason = "empty device trace (non-TPU backend?)"
    except Exception as e:
        reason = f"{type(e).__name__}: {e}"[:120]
    return wall_fn(), "wall-clock", reason
