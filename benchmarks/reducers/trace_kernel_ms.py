"""Device time in one kernel family (``kernels/<family>.json`` gives the
trace name pattern), per execution of a program (``per_module``) or in
total."""

from benchmarks.harness import trace


def reduce(spec: dict, layer: dict, run):
    prof = layer.get("profile")
    if not prof:
        return None
    args, win = spec["args"], layer.get("profile_window")
    fam = run.json("kernels", args["family"])
    k = trace.kernel_seconds(prof, fam["pattern"], win, fam.get("inside"))
    if not k["calls"]:
        return None
    if args.get("per_module"):
        n = trace.module_ms(prof, args["per_module"], win)["count"]
        return 1e3 * k["seconds"] / n if n else None
    return 1e3 * k["seconds"]
