"""Sum of one argument over the spans of one name, divided by the sum of
another argument over the same spans (tokens handed out per row of a
block pass; prompt tokens per padded token of a prefill pass), optionally
as a percentage.  Only spans that carry both arguments count; None where
none does, or where the divisor's sum is zero."""


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    num, den = args["arg"], args["per_arg"]
    pairs = [(float(s["args"][num]), float(s["args"][den]))
             for s in layer.get("spans", ())
             if s["name"] == args["span"] and num in s["args"]
             and den in s["args"]]
    total = sum(d for _, d in pairs)
    if not total:
        return None
    v = sum(n for n, _ in pairs) / total
    return 100.0 * v if args.get("percent") else v
