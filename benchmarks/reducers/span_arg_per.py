"""Mean of one argument of a span (``span_arg_mean``'s number) over a
size the configuration states (``per``: a path of keys into the
configuration), optionally as a percentage.  None where no span carries
the argument."""


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    mean = run.py("reducers", "span_arg_mean").reduce(
        {"args": {"span": args["span"], "arg": args["arg"]}}, layer, run)
    if mean is None:
        return None
    per = run.config
    for key in args["per"]:
        per = per[key]
    v = mean / float(per)
    return 100.0 * v if args.get("percent") else v
