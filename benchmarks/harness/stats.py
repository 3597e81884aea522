"""Order statistics for the benchmark's metrics (plain Python, exact)."""

from __future__ import annotations

import math
import statistics

# a tail is reported only with this many samples beyond it
TAIL_MIN_BEYOND = 10


def median(values) -> float | None:
    values = list(values)
    return float(statistics.median(values)) if values else None


def mean(values) -> float | None:
    values = list(values)
    return float(sum(values) / len(values)) if values else None


def quantile(values, q: float) -> float | None:
    """Linear-interpolated quantile (q in 0..1) of the samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tail(values, q: float = 0.95) -> tuple[float | None, int]:
    """(the q-quantile, sample count).  A tail with fewer than
    ``TAIL_MIN_BEYOND`` samples beyond it is not a number: None."""
    values = list(values)
    n = len(values)
    if n * (1.0 - q) < TAIL_MIN_BEYOND:
        return None, n
    return quantile(values, q), n


def iqr_share(values) -> float:
    """Interquartile distance as a share of the median (the contract's
    spread; ``statistics.quantiles(n=4)``, exclusive method)."""
    q1, _, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / statistics.median(values)


def series(args: dict, layer: dict):
    """The samples a per-layer reader asks for: a field of the program's
    step records (``from: records``) or one of the benchmark's own sample
    lists (``from: samples``).  None: it asks for a registry histogram."""
    src = args.get("from", "histogram")
    if src == "records":
        return [r[args["field"]] for r in layer.get("records", ())
                if r.get(args["field"]) is not None]
    if src == "samples":
        return list(layer.get("samples", {}).get(args["name"], ()))
    return None
