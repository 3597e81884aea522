"""Reduction of a jax.profiler trace to device metrics.

``load_profile`` turns the newest ``*.xplane.pb`` under a directory into
a neutral form -- ``{"devices": {id: {line: [[name, start_ns, dur_ns],
...]}}, "host": [[name, start_ns, dur_ns], ...]}`` -- and every metric
is computed from that form, so the reductions are tested on a small
recorded trace (``benchmarks/tests/data/small_trace.json``) without a
chip.  The device-thread selection is copied from
``paddle_tpu/profiler.read_device_trace`` ("XLA Modules" = whole
programs, "XLA Ops" = single operations) and extended: busy union, idle
share, per-name sums, exposed collective time, idle-gap attribution.
"""

from __future__ import annotations

import glob
import os
import re
import threading
import time

MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_PREFIX = "bench:"            # host annotations the benchmark emits
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)


def start(logdir: str) -> int:
    """Start a device trace (Python call tracing off: a sample-by-sample
    reader makes millions of calls) and emit the start marker; returns
    the marker's time on the host's perf_counter clock (ns)."""
    import shutil

    import jax.profiler

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(HOST_PREFIX + "marker"):
        return time.perf_counter_ns()


def stop() -> None:
    import jax.profiler

    with jax.profiler.TraceAnnotation(HOST_PREFIX + "end"):
        pass
    jax.profiler.stop_trace()


def stop_off_thread(prof: dict) -> threading.Thread:
    """``stop()`` on a thread of its own, started here: ``stop_trace``
    collects and writes the trace for ~46 us a device event -- tens of
    seconds -- and a load generator that waits for it sends nothing
    meanwhile.  ``prof["stop_s"]`` gets the seconds it took.  The caller
    joins the thread once its sending loop is over, before ``attach``."""
    def stopper():
        t0 = time.perf_counter()
        try:
            stop()
        finally:
            prof["stop_s"] = time.perf_counter() - t0

    thread = threading.Thread(target=stopper, name="bench-trace-stop",
                              daemon=True)
    thread.start()
    return thread


def margin_notes(layer: dict, prof: dict) -> dict:
    """What a traced run's time limits erode with, for the line's
    ``notes``: the seconds ``stop_trace`` took and the device events
    ("XLA Ops") of the profile ``attach`` loaded."""
    devices = (layer.get("profile") or {}).get("devices", {})
    return {"stop_trace_s": prof.get("stop_s"),
            "profile_events": sum(len(lines.get(OPS, ()))
                                  for lines in devices.values())}


def span_dicts(spans) -> list[dict]:
    """The program's tracer spans as plain dicts (times in seconds on the
    host's perf_counter clock)."""
    return [{"name": s.name, "thread": s.thread, "t0": s.t_start,
             "t1": s.t_end, "id": s.span_id, "parent": s.parent_id,
             "args": dict(s.args or {})} for s in spans]


def attach(layer: dict, logdir: str, marker_ns: int | None,
           only: tuple = ()) -> None:
    """Load the trace under ``logdir`` into ``layer``: ``profile``, the
    ``profile_window`` between the two markers, and ``host_spans`` -- the
    leaf spans of ``layer["spans"]`` (of the names in ``only``, if given)
    moved onto the profile's clock by the start marker -- for the idle
    gaps.  Removes the directory."""
    import shutil

    prof = load_profile(logdir)
    layer["profile"] = prof if prof["devices"] else None
    start = host_marker(prof, HOST_PREFIX + "marker")
    end = host_marker(prof, HOST_PREFIX + "end")
    if start is not None and end is not None:
        layer["profile_window"] = (start, end)
    if start is not None and marker_ns is not None:
        off = start - marker_ns
        parents = {s["parent"] for s in layer["spans"]}
        layer["host_spans"] = [
            (f"{s['name']}@{s['thread']}", int(s["t0"] * 1e9) + off,
             int(s["t1"] * 1e9) + off)
            for s in layer["spans"] if s["id"] not in parents
            and (not only or s["name"] in only)]
    shutil.rmtree(logdir, ignore_errors=True)


def load_profile(logdir: str) -> dict:
    import jax.profiler

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no xplane trace under {logdir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            lines = out["devices"].setdefault(m.group(1), {})
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines.setdefault(line.name, []).extend(
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def _union(intervals) -> list[list[int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def _clip(events, window):
    """Events cut to the window (ns); None = keep all."""
    if window is None:
        return [(n, s, s + d) for n, s, d in events]
    lo, hi = window
    return [(n, max(s, lo), min(s + d, hi)) for n, s, d in events
            if s + d > lo and s < hi]


def span_of(profile: dict) -> tuple[int, int]:
    """First start and last end of any device operation."""
    starts, ends = [], []
    for lines in profile["devices"].values():
        for evs in lines.values():
            for _, s, d in evs:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise RuntimeError("the trace holds no device operation")
    return min(starts), max(ends)


def _busy_unions(profile: dict, window) -> dict:
    """Per device: the merged intervals in which an operation ran (the
    "XLA Ops" events, or the modules where a device has no ops line)."""
    return {dev: _union((s, e) for _, s, e in _clip(
        lines.get(OPS) or lines.get(MODULES) or [], window))
        for dev, lines in profile["devices"].items()}


def busy(profile: dict, window=None) -> dict:
    """Per device: seconds in which an operation ran (union of the
    "XLA Ops" intervals, or of the modules where a device has no ops
    line), and the mean over devices."""
    window = window or span_of(profile)
    per = {dev: _length(merged) / 1e9
           for dev, merged in _busy_unions(profile, window).items()}
    n = max(len(per), 1)
    return {"per_device_s": per, "busy_s": sum(per.values()) / n,
            "window_s": (window[1] - window[0]) / 1e9}


def idle_pct(profile: dict, window=None) -> float:
    b = busy(profile, window)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def module_ms(profile: dict, pattern: str | None = None, window=None) -> dict:
    """Time and count of the "XLA Modules" events whose name matches,
    averaged over devices: the device-side time of whole programs.  A
    call that an end of the window cuts gives the time it spends inside
    (the rooflines' floors count a cut step by that share too) and counts
    by that time in units of the mean call that lies whole inside -- not
    of its own length, which the profile's end may have cut as well -- so
    time over count is the mean time of the whole calls."""
    rx = re.compile(pattern) if pattern else None
    lo, hi = window or (float("-inf"), float("inf"))
    tot, cnt, ndev = 0.0, 0.0, 0
    for lines in profile["devices"].values():
        # (ns inside the window, the call's own ns)
        parts = [(min(s + d, hi) - max(s, lo), d)
                 for n, s, d in lines.get(MODULES, [])
                 if s + d > lo and s < hi and (rx is None or rx.search(n))]
        if parts:
            ndev += 1
            tot += sum(t for t, _ in parts) / 1e6
            whole = [d for t, d in parts if t == d]
            cut = sum(t for t, d in parts if t < d)
            mean = sum(whole) / len(whole) if whole else 0
            cnt += len(whole) + cut / mean if cut and mean else len(parts)
    ndev = max(ndev, 1)
    return {"total_ms": tot / ndev, "count": cnt / ndev}


CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def op_sums(profile: dict, window=None) -> dict[str, float]:
    """Seconds per operation name on the "XLA Ops" line, mean over
    devices.  Loops and branches are left out: their bodies' operations
    are on the line themselves."""
    sums: dict[str, float] = {}
    ndev = max(len(profile["devices"]), 1)
    for lines in profile["devices"].values():
        for n, s, e in _clip(lines.get(OPS, []), window):
            sums[n] = sums.get(n, 0.0) + (e - s) / 1e9 / ndev
    # a profile holds millions of events of some thousands of names
    return {n: v for n, v in sums.items() if not CONTAINER.search(n)}


def kernel_seconds(profile: dict, pattern: str, window=None,
                   inside: str | None = None) -> dict:
    """Total seconds and calls (mean over devices) of the operations
    whose name matches ``pattern``; with ``inside``, only those that run
    within a program ("XLA Modules" event) whose name matches it."""
    rx = re.compile(pattern)
    tot, cnt = 0.0, 0
    ndev = max(len(profile["devices"]), 1)
    for lines in profile["devices"].values():
        mods = None
        if inside:
            irx = re.compile(inside)
            mods = sorted((s, e) for n, s, e in _clip(
                lines.get(MODULES, []), window) if irx.search(n))
        for n, s, e in _clip(lines.get(OPS, []), window):
            if rx.search(n) and (mods is None or any(
                    ms <= s and e <= me for ms, me in mods)):
                tot += (e - s) / 1e9
                cnt += 1
    return {"seconds": tot / ndev, "calls": cnt / ndev}


def short_name(name: str, limit: int = 120) -> str:
    """An operation's name for the breakdown: "%x = shape op(...)" cut to
    its result and opcode."""
    m = re.match(r"(%[\w.\-]+) = (.*?) ([\w\-]+)\(", name)
    if m:
        tgt = re.search(r'custom_call_target="([^"]+)"', name)
        name = f"{m.group(1)} {m.group(3)}{'[' + tgt.group(1) + ']' if tgt else ''} -> {m.group(2)}"
    return name[:limit]


def exposed_collective_s(profile: dict, window=None) -> dict:
    """Collective time, and the part of it during which no other
    operation runs on that device (mean over devices)."""
    tot, exposed = 0.0, 0.0
    ndev = max(len(profile["devices"]), 1)
    for lines in profile["devices"].values():
        evs = _clip(lines.get(OPS, []), window)
        coll = _union((s, e) for n, s, e in evs if COLLECTIVE.search(n))
        comp = _union((s, e) for n, s, e in evs if not COLLECTIVE.search(n))
        both = _length(_union(coll + comp))
        tot += _length(coll) / 1e9
        exposed += (both - _length(comp)) / 1e9
    return {"collective_s": tot / ndev, "exposed_s": exposed / ndev}


def idle_gaps(profile: dict, host_spans=(), window=None, top: int = 10):
    """The longest gaps of the busiest-idle device, each named after the
    host span (name, start_ns, end_ns on the profile's clock) that covers
    most of it (of equal covers the first in ``host_spans``); gaps of one
    name are summed.  [[name, seconds], ...].  One sweep over the gaps
    and the spans, both by start: a gap looks only at the spans that
    overlap it, not at every span (657k gaps x 1,200 spans took 3 min)."""
    window = window or span_of(profile)
    unions = _busy_unions(profile, window)
    merged = unions[min(unions, key=lambda d: _length(unions[d]))]
    gaps, cur = [], window[0]
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    spans = sorted((ss, se, i, name)
                   for i, (name, ss, se) in enumerate(host_spans) if se > ss)
    live, nxt = [], 0       # the spans that start before this gap's end
    named: dict[str, float] = {}
    for gs, ge in gaps:     # ascending and disjoint
        while nxt < len(spans) and spans[nxt][0] < ge:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[1] > gs]    # ... and end after its start
        best, cover, first = "host:untraced", 0, 0
        for ss, se, i, name in live:
            c = min(ge, se) - max(gs, ss)
            if c > cover or (c == cover and i < first):
                best, cover, first = name, c, i
        named[best] = named.get(best, 0.0) + (ge - gs) / 1e9
    return [[k, v] for k, v in sorted(named.items(),
                                      key=lambda kv: -kv[1])[:top]]


def top_ops(profile: dict, window=None, top: int = 10):
    sums = op_sums(profile, window)
    return [[short_name(k), v] for k, v in sorted(
        sums.items(), key=lambda kv: -kv[1])[:top]]


def host_marker(profile: dict, name: str) -> int | None:
    """Start (ns, profile clock) of the first host annotation ``name``."""
    hits = [s for n, s, _ in profile["host"] if n == name]
    return min(hits) if hits else None
