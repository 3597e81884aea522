"""Collective time during which no other operation runs on that device,
per execution of a program (``per_module``), mean over the devices."""

from benchmarks.harness import trace


def reduce(spec: dict, layer: dict, run):
    prof = layer.get("profile")
    if not prof or len(prof["devices"]) < 2:
        return None
    win = layer.get("profile_window")
    c = trace.exposed_collective_s(prof, win)
    if not c["collective_s"]:
        return None
    n = trace.module_ms(prof, spec["args"]["per_module"], win)["count"]
    return 1e3 * c["exposed_s"] / n if n else None
