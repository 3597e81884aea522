"""Calls of one kernel family per execution of the program that holds
them, counted on the device trace: the median over the executions
("XLA Modules" events matching the family's ``inside``) that lie whole
inside the traced window, of the matching "XLA Ops" events inside each,
divided by the size ``per_model`` of the configuration's ``model`` (the
calls per layer of one execution: the passes a looped stack ran,
whatever its configuration says)."""

import re

from benchmarks.harness import stats, trace


def reduce(spec: dict, layer: dict, run):
    prof, win = layer.get("profile"), layer.get("profile_window")
    if not prof or not win:
        return None
    args = spec["args"]
    fam = run.json("kernels", args["family"])
    rx, inside = re.compile(fam["pattern"]), re.compile(fam["inside"])
    counts = []
    for lines in prof["devices"].values():
        calls = sorted(s for n, s, _ in lines.get(trace.OPS, ())
                       if rx.search(n))
        for n, s, d in lines.get(trace.MODULES, ()):
            if inside.search(n) and win[0] <= s and s + d <= win[1]:
                counts.append(sum(1 for c in calls if s <= c < s + d))
    if not any(counts):
        return None
    per = float(run.config["model"][args["per_model"]])
    run.log(f"{spec['name']}: {fam['pattern']} calls in {len(counts)} whole "
            f"{fam['inside']} executions: {sorted(set(counts))}")
    return stats.median(counts) / per
