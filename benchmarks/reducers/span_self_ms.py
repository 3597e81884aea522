"""Self time of a span: its duration minus what its child spans cover;
the mean (or a quantile) over the window's spans of that name."""

from benchmarks.harness import stats


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    spans = layer.get("spans", ())
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    out = []
    for s in spans:
        if s["name"] != args["span"]:
            continue
        covered = sum(min(k["t1"], s["t1"]) - max(k["t0"], s["t0"])
                      for k in kids.get(s["id"], ()))
        out.append((s["t1"] - s["t0"] - max(covered, 0.0)) * 1e3)
    if not out:
        return None
    q = args.get("q")
    return stats.mean(out) if q is None else stats.quantile(out, float(q))
