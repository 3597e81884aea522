"""Seconds (or the count) of the spans of the given names, wherever in
the run they lie: set-up's spans are kept beside the tracer's ring, so
the window's ``clear()`` leaves them in ``layer["spans"]``.  ``how``:
``sum`` of the durations, ``union`` of the intervals (a phase whose spans
nest or overlap: a trace inside a trace, a set-up span inside another),
or ``count``.  None where the run holds no span of these names -- or, with
``if_any``, none of those: a program that has the instrument reports a
phase that did not happen as 0, one that lacks it reports nothing."""


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    spans = layer.get("spans", ())
    present = set(args.get("if_any", args["spans"]))
    if not any(s["name"] in present for s in spans):
        return None
    names = set(args["spans"])
    mine = sorted((s["t0"], s["t1"]) for s in spans if s["name"] in names)
    how = args.get("how", "sum")
    if how == "count":
        return len(mine)
    if how == "sum":
        return sum(t1 - t0 for t0, t1 in mine)
    total, end = 0.0, float("-inf")
    for t0, t1 in mine:     # union: sorted by start
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total
