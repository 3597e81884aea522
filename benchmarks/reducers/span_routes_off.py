"""How many kernel-or-reference decisions of the HELD programs went
another way than the kernel: the entries of the ``routes`` of the spans
named ``args["span"]`` (``program_ready``: one a compiled program the
engine holds, its census ``{"ssd_step:kernel": 9, "mamba1_step:xla":
9}`` taken while THAT program was traced) whose path is none of
``args["ok"]`` (``kernel``, and ``xla`` where no kernel is written) --
``reference``, ``reference_shape``, ``scan``, ``masked``.  Counted an
entry a program, not a layer.  None where no such span says ``routes``
(a program that lacks the instrument); 0 is a finding."""


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    said = [s["args"]["routes"] for s in layer.get("spans", ())
            if s["name"] == args["span"] and "routes" in s["args"]]
    if not said:
        return None
    off = [key for routes in said for key in routes
           if key.rsplit(":", 1)[-1] not in args["ok"]]
    if off and run is not None:
        run.log(f"{spec['name']}: {sorted(set(off))}")
    return len(off)
