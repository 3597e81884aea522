"""The one last line the driver reads, and the lines above it."""

from __future__ import annotations

import json
import sys


def log(msg: str) -> None:
    print(msg, flush=True)


def device_stamp(devices, chips: int) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices[:chips]:
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # a backend without memory statistics
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": int(chips), "memory_peak_bytes": peak}


def final_line(correct: bool, attempted: int, failed: int, metrics: dict,
               units: dict, device: dict, breakdown: dict | None = None,
               extra: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items() if v is not None},
           "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    if extra:
        out.update(extra)
    return json.dumps(out)


def emit(line: str) -> None:
    sys.stdout.write(line + "\n")
    sys.stdout.flush()
