"""A kernel family's share of its roofline: the least time the chip
could take for the calls the trace holds over their traced time, in
percent.  ``kernels/<family>.json`` names the trace pattern and the floor
function, ``kernels/<floor.fn>.py``, which counts the operations and
bytes from the configuration's shapes (``harness/roofline.py``)."""

from benchmarks.harness import trace


def reduce(spec: dict, layer: dict, run):
    prof = layer.get("profile")
    if not prof or not run.peak:
        return None
    fam = run.json("kernels", spec["args"]["family"])
    k = trace.kernel_seconds(prof, fam["pattern"], layer.get("profile_window"),
                             fam.get("inside"))
    if not k["seconds"]:
        return None
    got = run.py("kernels", fam["floor"]["fn"]).floor(fam, spec, layer, run)
    if got is None:
        return None
    least, note = got
    run.log(f"{spec['name']}: {k['calls']} calls, floor {1e3 * least:.3f} ms "
            f"over {1e3 * k['seconds']:.3f} ms traced -- {note}")
    return 100.0 * least / k["seconds"]
