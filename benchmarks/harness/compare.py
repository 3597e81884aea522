"""The comparison that decides ``correct`` for a trained model: per-leaf
norms of the program's and the plain reference's first gradient and
parameter change, and the numbers held against a cell's ``limits``.
Generic over the model: a reference hands over ``{layer: {part: x}}``
trees, the driver flat ``{leaf: x}`` dicts under the same leaf names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def diff_norms(a: dict, b: dict) -> dict:
    """{leaf: norm of (a - b)} of two flat {leaf: array} dicts."""
    @jax.jit
    def f(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
            for k in a}

    return {k: float(v) for k, v in jax.device_get(f(a, b)).items()}


def flat_tree(tree: dict) -> dict:
    """{layer: {part: x}} -> {"layer/part": x}."""
    return {f"{k}/{p}": x for k, d in tree.items() for p, x in d.items()}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` is decided on.  ``prog``/``ref``: losses,
    and flat {leaf: norm} dicts for the first gradient and the
    parameters' change.  A leaf's gap is the difference of the two norms
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    import statistics

    out = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(prog["losses"], ref["losses"]))}
    for what in ("grad_norms", "delta_norms"):
        rn, pn = ref[what], prog[what]
        med = max(statistics.median(rn.values()), 1e-30)
        gaps = {k: abs(pn[k] - rn[k]) / max(rn[k], med) for k in rn}
        worst = max(gaps, key=gaps.get)
        tag = what[:-6]
        out[tag + "_gap"] = gaps[worst]
        out[tag + "_worst_leaf"] = worst
        # the worst of 161 leaves swings with the seed; the leaf at the
        # 90th percentile is steadier
        out[tag + "_p90_gap"] = sorted(gaps.values())[int(0.9 * len(gaps))]
    if "grad_diff" in prog:
        # the norm of the difference itself, leaf by leaf: norms hide
        # rounding noise (it averages out inside a norm), this does not.
        # Sound only because the configuration's initialisation keeps the
        # network out of the chaotic regime (see the config's `assumed`)
        rn = ref["grad_norms"]
        med = max(statistics.median(rn.values()), 1e-30)
        d = sorted(prog["grad_diff"][k] / max(rn[k], med) for k in rn)
        out["grad_diff_p50"] = d[len(d) // 2]
        out["grad_diff_p90"] = d[int(0.9 * len(d))]
        out["grad_diff_max"] = d[-1]
    return out


def flat_norms(tree: dict) -> dict:
    """{layer: {part: x}} -> {"layer/part": float}."""
    return {k: float(x) for k, x in flat_tree(tree).items()}
