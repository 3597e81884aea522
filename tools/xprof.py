"""Per-op TPU profiling via jax.profiler traces (no tensorboard needed).

``jax.profiler.start_trace`` emits a Chrome-trace ``*.trace.json.gz`` whose
``XLA Ops`` thread carries one complete event per executed HLO op with
``dur`` (device µs), ``model_flops`` and ``raw_bytes_accessed`` — enough to
attribute a step's wall time op-by-op and compute achieved FLOP/s and HBM
bandwidth per op class (the tensorboard_plugin_profile converter is
proto-incompatible with the installed protobuf; parsing the chrome trace
directly sidesteps it).

Usage:
    from tools.xprof import profile_step
    rows, totals = profile_step(lambda: step_fn(), steps=3)
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import tempfile

import numpy as np

import jax


def _read_trace(logdir: str):
    """(per-op events, module_ms) — thin wrapper over the library parser
    (paddle_tpu.profiler.read_device_trace, the single implementation)."""
    from paddle_tpu.profiler import read_device_trace

    events, module_ms = read_device_trace(logdir)
    return events, module_ms * 1000.0


def device_module_ms(run_once, steps: int = 10, logdir: str | None = None):
    """Device-side ms per call — delegates to
    paddle_tpu.profiler.device_step_ms (single implementation)."""
    from paddle_tpu.profiler import device_step_ms

    def scalarable():
        out = run_once()
        return jax.tree.leaves(out)[0]

    return device_step_ms(scalarable, steps=steps, warmup=1)


def profile_step(run_once, steps: int = 3, logdir: str | None = None,
                 top: int = 25, group: str = "op"):
    """Run ``run_once`` ``steps`` times under a device trace and print a
    per-op table (durations divided by the number of module executions).

    group: "op" (per HLO op) | "source" (per python source line).
    Returns (rows, totals) where rows are aggregated dicts.
    """
    logdir = logdir or tempfile.mkdtemp(prefix="xprof_")
    run_once()  # warm / compile outside the trace
    jax.profiler.start_trace(logdir)
    out = None
    for _ in range(steps):
        out = run_once()
    float(np.asarray(jax.tree.leaves(out)[0]).reshape(-1)[0])
    jax.profiler.stop_trace()
    events, _ = _read_trace(logdir)

    key = (lambda e: e["name"]) if group == "op" else (
        lambda e: e["source"] or e["name"])
    agg = collections.defaultdict(
        lambda: {"dur_us": 0.0, "flops": 0.0, "bytes": 0.0, "count": 0,
                 "tf_op": "", "source": ""})
    for e in events:
        r = agg[key(e)]
        r["dur_us"] += e["dur_us"]
        r["flops"] += e["flops"]
        r["bytes"] += e["bytes"]
        r["count"] += 1
        r["tf_op"] = e["tf_op"]
        r["source"] = e["source"]
    # one event per executed op: divide by executions of the module to get
    # per-step cost.  Module count is unreliable when several jits run, so
    # normalize by `steps` (callers run the same fn each time).
    rows = []
    for name, r in agg.items():
        d = dict(r)
        d["name"] = name
        d["ms"] = r["dur_us"] / 1000.0 / steps
        d["gbps"] = (r["bytes"] / steps) / max(d["ms"] * 1e-3, 1e-12) / 1e9
        d["tflops"] = (r["flops"] / steps) / max(d["ms"] * 1e-3, 1e-12) / 1e12
        rows.append(d)
    rows.sort(key=lambda d: -d["ms"])
    tot_ms = sum(d["ms"] for d in rows)
    tot_fl = sum(d["flops"] for d in rows) / steps
    tot_by = sum(d["bytes"] for d in rows) / steps
    totals = {"ms": tot_ms, "flops": tot_fl, "bytes": tot_by,
              "tflops": tot_fl / max(tot_ms * 1e-3, 1e-12) / 1e12,
              "gbps": tot_by / max(tot_ms * 1e-3, 1e-12) / 1e9}
    print(f"device total {tot_ms:8.2f} ms/step   "
          f"{totals['tflops']:6.1f} TF/s   {totals['gbps']:7.1f} GB/s   "
          f"({tot_by / 1e9:.2f} GB accessed)")
    print(f"{'ms':>8} {'%':>5} {'TF/s':>6} {'GB/s':>7} {'x':>4}  op  [origin]")
    for d in rows[:top]:
        frac = d["ms"] / tot_ms * 100
        label = d["name"]
        origin = d["tf_op"] or d["source"]
        print(f"{d['ms']:8.3f} {frac:5.1f} {d['tflops']:6.1f} {d['gbps']:7.1f} "
              f"{d['count'] // steps:4d}  {label[:48]:48s} {origin[:60]}")
    return rows, totals


def measure_utilization(run_once, steps: int = 8,
                        peak_flops: float = 197e12,
                        stream_gbps: float = 670.0):
    """Quiet per-step utilization: device ms, achieved TF/s and GB/s from
    the trace's per-op ``model_flops``/``raw_bytes_accessed`` sums, and the
    two ceiling ratios (MFU vs bf16 peak, HBM vs ``stream_gbps``, a
    STREAM-triad calibration of the chip, below the 819 GB/s datasheet).

    Returns a dict: {ms, tflops, gbps, mfu_pct, hbm_pct}.  The larger of
    mfu_pct/hbm_pct says which roof the workload is near; when both are
    low the step is latency/serialization-bound (small ops, scan chains).
    """
    import shutil

    logdir = tempfile.mkdtemp(prefix="xprof_util_")
    run_once()  # warm / compile outside the trace
    jax.profiler.start_trace(logdir)
    try:
        out = None
        for _ in range(steps):
            out = run_once()
        leaves = jax.tree.leaves(out)
        if leaves:
            float(np.asarray(leaves[0]).reshape(-1)[0])
    finally:
        # a dangling trace would poison every later measurement in the run
        jax.profiler.stop_trace()
    try:
        events, module_us = _read_trace(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    ms = module_us / 1000.0 / steps
    flops = sum(e["flops"] for e in events) / steps
    by = sum(e["bytes"] for e in events) / steps
    sec = max(ms * 1e-3, 1e-12)
    tflops = flops / sec / 1e12
    gbps = by / sec / 1e9
    return {
        "ms": ms,
        "tflops": round(tflops, 2),
        "gbps": round(gbps, 1),
        "mfu_pct": round(tflops * 1e12 / peak_flops * 100, 1),
        "hbm_pct": round(gbps / stream_gbps * 100, 1),
    }
