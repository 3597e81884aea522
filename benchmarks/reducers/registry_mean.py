"""Mean of a series: a field of the program's step records, a registry
histogram (sum / count), or one of the benchmark's own sample lists."""

from benchmarks.harness import stats


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    xs = stats.series(args, layer)
    if xs is None:
        hist = layer["registry"].get(args["name"]) if layer.get(
            "registry") else None
        summ = hist.summary() if hist is not None else None
        return summ["avg"] if summ and summ["count"] else None
    return stats.mean(xs)
