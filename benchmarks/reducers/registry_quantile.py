"""A quantile of a series: a registry histogram of the program (bucket-
interpolated, as the program computes it), a field of its step records,
or one of the benchmark's own sample lists (exact; a tail only with ten
samples beyond it)."""

from benchmarks.harness import stats


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    q = float(args["q"])
    xs = stats.series(args, layer)
    if xs is None:
        hist = layer["registry"].get(args["name"]) if layer.get(
            "registry") else None
        return hist.percentile(100.0 * q) if hist is not None else None
    if not xs:
        return None
    return stats.tail(xs, q)[0] if q > 0.5 else stats.quantile(xs, q)
