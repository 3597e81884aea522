"""1 - (union of the device's operation intervals) / traced window, in
percent, mean over the devices used."""

from benchmarks.harness import trace


def reduce(spec: dict, layer: dict, run):
    prof = layer.get("profile")
    return trace.idle_pct(prof, layer.get("profile_window")) if prof else None
