"""A whole program's share of its roofline: the least time the chip
could take for the executions the trace holds ("XLA Modules" events whose
name matches the family's ``module``, cut to the traced window) over
their traced time, in percent.  ``kernels/<family>.json`` names the
module and the floor function, ``kernels/<floor.fn>.py``."""

from benchmarks.harness import trace


def reduce(spec: dict, layer: dict, run):
    prof = layer.get("profile")
    if not prof or not run.peak:
        return None
    fam = run.json("kernels", spec["args"]["family"])
    m = trace.module_ms(prof, fam["module"], layer.get("profile_window"))
    if not m["total_ms"]:
        return None
    got = run.py("kernels", fam["floor"]["fn"]).floor(fam, spec, layer, run)
    if got is None:
        return None
    least, note = got
    run.log(f"{spec['name']}: {m['count']:.0f} executions, floor "
            f"{1e3 * least:.3f} ms over {m['total_ms']:.3f} ms traced -- "
            f"{note}")
    return 100.0 * least * 1e3 / m["total_ms"]
