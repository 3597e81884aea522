"""Device time of a program's operations by the SUBLAYER that issued them.

The profile names operations as XLA does (``%fusion.835 = ...``); which
sublayer a name belongs to the program says itself: under an armed tracer
every held program's ``program_ready`` span carries ``op_scopes`` --
``{part: [instruction names]}`` from the executable's own text
(``paddle_tpu/telemetry/scopes.py``; a backward operation under
``part|bwd``).  ``args``:

- ``program``: pattern of the "XLA Modules" events (``jit_decode``);
- ``span``: the span that describes it -- its ``name`` and the args it
  must carry (``{"name": "program_ready", "program": "decode"}``);
- ``parts``: patterns (``fnmatch``) of the parts to sum (``attn.core``,
  ``*.proj``, ``unscoped``), ``but``: patterns taken out of them again;
- ``per``: ``call`` -- ms a call of the program (the summed time over
  ``trace.module_ms``'s count) -- or ``pct`` -- a share of all the
  program's operation time, in percent.

An operation counts where it runs inside a call of the program, by its
leading ``%name``; loops and branches are left out (their bodies'
operations are events themselves, as ``trace.op_sums`` leaves them out);
an operation no list holds counts as ``unscoped``.  None where the span
has no ``op_scopes`` (a program that lacks the instrument) or the profile
no such program.

Several spans may describe one module name (the prefill ladder's members:
executables of one function whose instruction names may coincide).  A
module event's name carries the executable's own number
(``jit_prefill(1234)``), so the calls fall into executables by it; an
executable is the member whose lists hold every operation of its calls.
Where more than one member does and they disagree on the part of any of
those operations, the answer is None rather than a mixed number.

The first metric of a program that is reduced logs the program's FULL
table by part (``run.log``), forward and backward apart: ``norm``,
``embed``, ``comm``, ``loss`` and the rest that no metric file names.
"""

import bisect
import fnmatch
import json
import re

from benchmarks.harness import trace

# (spelled here as ``telemetry/scopes.py`` spells them: the benchmark's
# files are laid over a program that may lack that module)
UNSCOPED, BWD = "unscoped", "|bwd"
_NAME = re.compile(r"%?([\w.\-]+)")


def _members(layer: dict, want: dict) -> list[dict]:
    """name -> part(|bwd), of every span that describes the program."""
    out = []
    for s in layer.get("spans", ()):
        args = s["args"]
        if s["name"] != want["name"] or "op_scopes" not in args or any(
                args.get(k) != v for k, v in want.items() if k != "name"):
            continue
        out.append({name: part for part, names in args["op_scopes"].items()
                    for name in names})
    return out


def _calls(lines: dict, program: str, window):
    """{module event name: {operation name: ns}} over the program's calls
    on one device, containers left out."""
    rx = re.compile(program)
    mods = sorted((s, e, n) for n, s, e in trace._clip(
        lines.get(trace.MODULES, []), window) if rx.search(n))
    starts = [m[0] for m in mods]
    out: dict[str, dict[str, float]] = {}
    for n, s, e in trace._clip(lines.get(trace.OPS, []), window):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > mods[i][1] or trace.CONTAINER.search(n):
            continue
        ops = out.setdefault(mods[i][2], {})
        name = _NAME.match(n).group(1)
        ops[name] = ops.get(name, 0.0) + (e - s)
    return out


def _by_part(prof: dict, program: str, members: list, window):
    """({part(|bwd): ms, mean over devices}, the ms in no list) of the
    program's calls, or None where a call's member cannot be told."""
    sums, unlisted = {}, 0.0
    ndev = max(len(prof["devices"]), 1)
    for lines in prof["devices"].values():
        for ops in _calls(lines, program, window).values():
            # the members that hold every operation any member holds
            known = [n for n in ops if any(n in m for m in members)]
            fits = [m for m in members if all(n in m for n in known)]
            if not fits or any(m[n] != fits[0][n]
                               for m in fits[1:] for n in known):
                return None
            for n, ns in ops.items():
                part = fits[0].get(n)
                if part is None:
                    part, unlisted = UNSCOPED, unlisted + ns / 1e6 / ndev
                sums[part] = sums.get(part, 0.0) + ns / 1e6 / ndev
    return (sums, unlisted) if sums else None


def table(layer: dict, args: dict, run=None):
    """{part(|bwd): ms over the window, mean over devices} of the program
    ``args`` names, or None; kept in ``layer`` for the next metric of the
    same program, and logged whole the first time."""
    key = json.dumps([args["program"], args["span"]], sort_keys=True)
    kept = layer.setdefault("_scope_tables", {})
    if key in kept:
        return kept[key]
    members, prof = _members(layer, args["span"]), layer.get("profile")
    win = layer.get("profile_window")
    got = _by_part(prof, args["program"], members, win) \
        if members and prof else None
    sums = kept[key] = got[0] if got else None
    if sums and run is not None:
        total = sum(sums.values())
        calls = max(trace.module_ms(prof, args["program"], win)["count"], 1)
        run.log(f"{args['program']} by part, ms a call over {calls:.0f} "
                f"calls ({total / calls:.3f} in all; "
                f"{100 * got[1] / total:.2f}% in no list): " + ", ".join(
                    f"{p} {v / calls:.4f}" for p, v in sorted(
                        sums.items(), key=lambda kv: -kv[1])))
    return sums


def reduce(spec: dict, layer: dict, run):
    args = spec["args"]
    sums = table(layer, args, run)
    if not sums:
        return None

    def named(part):
        part = part[:-len(BWD)] if part.endswith(BWD) else part
        return any(fnmatch.fnmatchcase(part, p) for p in args["parts"]) \
            and not any(fnmatch.fnmatchcase(part, p)
                        for p in args.get("but", ()))

    mine = sum(v for part, v in sums.items() if named(part))
    if args.get("per") == "pct":
        return 100.0 * mine / sum(sums.values())
    calls = trace.module_ms(layer["profile"], args["program"],
                            layer.get("profile_window"))["count"]
    return mine / calls if calls else None
