"""Seeded traffic: a pure function of (cell parameters, seed).

Every seed gets the SAME multiset of prompt lengths, output lengths and
inter-arrival gaps -- the stratified quantiles of the cell's
distributions -- in another order, plus its own token ids.  So the work
of a window does not swing with the seed (only its order does), and a
new traffic mix is a data file: lengths, rate, clients, sharing.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_pool(median: float, sigma: float, lo: int, hi: int,
                   n: int) -> list[int]:
    """n stratified quantiles of lognormal(median, sigma), clipped."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(median * math.exp(sigma * z)), lo), hi)))
    return out


def exponential_pool(rate: float, n: int) -> list[float]:
    """n stratified quantiles of the exponential gap at ``rate``/s,
    rescaled so their mean is exactly 1/rate."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Any non-negative whole seed (also beyond 32 bits)."""
    return np.random.default_rng([int(seed), int(stream)])


def make_requests(traffic: dict, seed: int, vocab: int) -> list[dict]:
    """The request pool of one run: ``pool`` entries of {prompt (list of
    token ids), max_new_tokens}, lengths permuted by the seed."""
    n = int(traffic["pool"])
    p, o = traffic["prompt_len"], traffic["output_len"]
    plens = lognormal_pool(p["median"], p["sigma"], p["min"], p["max"], n)
    olens = lognormal_pool(o["median"], o["sigma"], o["min"], o["max"], n)
    rng = rng_for(seed, 1)
    plens = [plens[i] for i in rng.permutation(n)]
    olens = [olens[i] for i in rng.permutation(n)]
    return [{"prompt": rng.integers(0, vocab, size=max(pl, 1)).tolist(),
             "max_new_tokens": int(ol)} for pl, ol in zip(plens, olens)]


def arrival_times(traffic: dict, seed: int, n: int) -> list[float]:
    """Due times (s from window start) of an open loop: exponential gaps
    at ``rate_rps``, the same multiset for every seed, permuted."""
    gaps = exponential_pool(float(traffic["rate_rps"]), n)
    order = rng_for(seed, 2).permutation(n)
    t, out = 0.0, []
    for i in order:
        t += gaps[i]
        out.append(t)
    return out
