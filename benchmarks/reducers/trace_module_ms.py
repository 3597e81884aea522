"""Device time of whole programs ("XLA Modules") whose name matches:
per call (``per: call``) or in total, mean over the devices."""

from benchmarks.harness import trace


def reduce(spec: dict, layer: dict, run):
    prof = layer.get("profile")
    if not prof:
        return None
    args = spec["args"]
    m = trace.module_ms(prof, args.get("pattern"),
                        layer.get("profile_window"))
    if not m["count"]:
        return None
    return (m["total_ms"] / m["count"] if args.get("per") == "call"
            else m["total_ms"])
