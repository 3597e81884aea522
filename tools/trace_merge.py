#!/usr/bin/env python
"""Merge per-rank Chrome trace files into ONE Perfetto timeline.

Every rank of a ``distributed.launch`` fleet dumps its own span ring
(``--trace_dir`` -> ``trace-host<k>.json``, or a live scrape of
``/trace`` saved per rank); this tool folds them into a single
trace-event file where each rank is its own process lane (``pid`` =
rank, process_name ``rank <k>`` — replicas from ``launch --serving``
render as ``replica <k>``), so one Perfetto view shows the whole
fleet's feed/compute/fence (or queue/prefill/decode) phases side by
side.  Each process stamps its spans on its own monotonic clock; a
dump states that clock beside the wall clock (``otherData.clock``: one
``tracer_s`` / ``unix_ns`` pair read together at export), and the merge
moves every lane that has the pair onto the first such lane's clock, so
the lanes are aligned as well as the hosts' wall clocks agree.  A file
without the pair (an older dump, a hand-made trace) keeps its own
times, and a warning names it.

Usage::

    python tools/trace_merge.py LOGDIR [...]  -o merged.json
    python tools/trace_merge.py rank0.json rank1.json -o merged.json

Arguments are trace files or directories (directories are scanned for
``trace-host*.json`` / ``trace-replica*.json`` / ``*.trace.json``).
The rank of each file comes from its own metadata (``otherData.rank``,
the tracer's stamp) with the filename's ``host<k>`` as the fallback;
on a collision (two files claiming one rank — e.g. scrapes of the same
rank at two times) later files are offset to a free lane and a warning
names them.  Prints a per-rank span census.

A rank that produced a trace but recorded zero spans (tracing armed
late, ring drained by a /trace scrape) is TOLERATED: its lane merges
with a 0-span census row and the merge still succeeds.  Exit 2 only
when NO input yields any span event — the message then names which
files were empty (parsed, zero spans) vs. missing (named on the
command line but absent on disk), so "forgot --trace_spans" and
"wrong log dir" read differently.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys


def find_trace_files(args: list[str]) -> list[str]:
    files: list[str] = []
    for a in args:
        if os.path.isdir(a):
            for pat in ("trace-host*.json", "trace-replica*.json",
                        "*.trace.json"):
                files.extend(sorted(glob.glob(os.path.join(a, pat))))
        else:
            files.append(a)
    # de-dup, keep order
    seen: set[str] = set()
    out = []
    for f in files:
        if f not in seen:
            seen.add(f)
            out.append(f)
    return out


def rank_of(path: str, trace: dict) -> int | None:
    """The lane a file's events belong to: the tracer's own stamp, else
    the ``host<k>``/``replica<k>`` filename convention."""
    other = trace.get("otherData") or {}
    if isinstance(other.get("rank"), int):
        return other["rank"]
    m = re.search(r"(?:host|replica|rank)[-_]?(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else None


def clock_pair(trace: dict) -> tuple[float, int] | None:
    """A dump's ``otherData.clock``: (its tracer's clock in seconds, the
    wall clock in ns), read together at export; None without one."""
    clock = (trace.get("otherData") or {}).get("clock") or {}
    try:
        return float(clock["tracer_s"]), int(clock["unix_ns"])
    except (KeyError, TypeError, ValueError):
        return None


def merge(files: list[str], label: str = "rank") -> dict:
    """Fold trace files into one trace-event dict with one pid lane per
    rank, lanes aligned by their clock pairs.  Returns the merged trace;
    ``otherData.lanes`` maps pid -> source file, ``otherData.empty``
    lists inputs that parsed but held zero span events (their lanes
    still exist — a rank with an armed-late tracer shows as an empty
    lane, not a hole) and ``otherData.unaligned`` those without a clock
    pair."""
    events: list[dict] = []
    lanes: dict[int, str] = {}
    empty: list[str] = []
    unaligned: list[str] = []
    base = None  # the first lane with a clock pair keeps its own times
    next_free = 0
    for path in files:
        with open(path) as f:
            trace = json.load(f)
        src = (trace.get("traceEvents")
               if isinstance(trace, dict) else trace) or []
        meta = trace if isinstance(trace, dict) else {}
        rank = rank_of(path, meta)
        pair = clock_pair(meta)
        shift_us = 0.0
        if pair is None:
            unaligned.append(path)
        else:
            base = base or pair
            # this lane's clock read pair[0] when the base lane's read
            # base[0] + the wall time between the two exports
            shift_us = ((pair[1] - base[1]) / 1e3
                        - (pair[0] - base[0]) * 1e6)
        if rank is None or rank in lanes:
            while next_free in lanes:
                next_free += 1
            if rank is not None:
                print(f"trace_merge: {path} claims lane {rank} already "
                      f"taken by {lanes[rank]}; moving it to lane "
                      f"{next_free}", file=sys.stderr)
            rank = next_free
        lanes[rank] = path
        have_name = False
        n_spans = 0
        for e in src:
            e = dict(e)
            e["pid"] = rank
            if e.get("ph") == "X":
                n_spans += 1
                e["ts"] = round(e["ts"] + shift_us, 3)
            if e.get("ph") == "M" and e.get("name") == "process_name":
                e["args"] = {"name": f"{label} {rank}"}
                have_name = True
            events.append(e)
        if not n_spans:
            empty.append(path)
        if not have_name:
            events.append({"name": "process_name", "ph": "M",
                           "pid": rank, "tid": 0,
                           "args": {"name": f"{label} {rank}"}})
    if unaligned and len(files) > 1:
        print("trace_merge: no otherData.clock in "
              f"{', '.join(unaligned)}; those lanes keep their own "
              "clocks", file=sys.stderr)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"lanes": {str(k): v
                                    for k, v in sorted(lanes.items())},
                          "empty": empty, "unaligned": unaligned}}


def census(merged: dict) -> dict[int, int]:
    """{pid lane: complete-event count} — the per-rank span census the
    CLI prints (and tests assert both lanes are populated from)."""
    out: dict[int, int] = {}
    for e in merged.get("traceEvents", ()):
        if e.get("ph") == "X":
            out[e.get("pid", -1)] = out.get(e.get("pid", -1), 0) + 1
    return out


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 2
    out_path = "trace_merged.json"
    if "-o" in argv:
        i = argv.index("-o")
        out_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    label = "rank"
    if "--label" in argv:
        i = argv.index("--label")
        label = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    files = find_trace_files(argv)
    # explicit file arguments that don't exist are MISSING (wrong path,
    # rank never dumped), distinct from files that parse to zero spans
    # (tracing armed late / ring drained) — the exit-2 message names
    # each group so the two failure modes read differently
    missing = [f for f in files if not os.path.exists(f)]
    files = [f for f in files if os.path.exists(f)]
    if not files:
        if missing:
            print(f"trace_merge: no trace files — missing: "
                  f"{', '.join(missing)}", file=sys.stderr)
        else:
            print(f"trace_merge: no trace files under {argv}",
                  file=sys.stderr)
        return 2
    merged = merge(files, label=label)
    counts = census(merged)
    empty = merged["otherData"].get("empty", [])
    if not counts:
        parts = []
        if empty:
            parts.append(f"empty (parsed, zero spans): {', '.join(empty)}")
        if missing:
            parts.append(f"missing: {', '.join(missing)}")
        print("trace_merge: inputs contained no span events — "
              + "; ".join(parts or ["no inputs"]), file=sys.stderr)
        return 2
    with open(out_path, "w") as f:
        json.dump(merged, f)
    # zero-span lanes are tolerated: they merged, they just census 0
    for pid in merged["otherData"]["lanes"]:
        counts.setdefault(int(pid), 0)
    total = sum(counts.values())
    lanes = ", ".join(f"{label} {k}: {v}" for k, v in sorted(counts.items()))
    print(f"trace_merge: {total} spans across {len(counts)} lane(s) "
          f"({lanes}) -> {out_path}")
    if missing:
        print(f"trace_merge: warning — named but missing: "
              f"{', '.join(missing)}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
