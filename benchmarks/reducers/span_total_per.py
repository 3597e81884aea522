"""Total time of the spans of one name, per span of another: what one
step of a loop spends in a phase that may run several times in it or not
at all (``serve_schedule`` per ``serve_step``), in ms."""


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    spans = layer.get("spans", ())
    per = sum(1 for s in spans if s["name"] == args["per"])
    if not per:
        return None
    total = sum(s["t1"] - s["t0"] for s in spans if s["name"] == args["span"])
    return total * 1e3 / per
