"""Mean of one argument of a span (e.g. the batch of ``serve_decode``),
optionally as a percentage of a size the configuration states."""


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    xs = [float(s["args"][args["arg"]]) for s in layer.get("spans", ())
          if s["name"] == args["span"] and args["arg"] in s["args"]]
    if not xs:
        return None
    v = sum(xs) / len(xs)
    if args.get("scale_by"):
        v = 100.0 * v / float(layer["sizes"][args["scale_by"]])
    return v
