"""Run one cell once: find its files by name, hand them to its driver,
reduce the per-layer metrics, print the contract's last line.

Everything that belongs to one configuration, cell, metric or kernel
family is a file of its own, found by the name in ``BENCHMARK.json``:

    configs/<config>.json        workloads/<cell>.json
    end_to_end/<metric>.json     layer_metrics/<metric>.json
    reducers/<reducer>.py        kernels/<family>.json
    drivers/<driver>.py          references/<reference>.py
    kernels/<floor function>.py

so a later PR adds files and ``BENCHMARK.json`` entries and edits none.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import importlib.util
import json
import os
import sys
import time

from . import result as result_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_CHIP = 3


def find(kind: str, name: str, ext: str, roots) -> str:
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {kind}/{name}{ext} under {[os.path.relpath(r) for r in roots]}")


def load_json(kind: str, name: str, roots) -> dict:
    with open(find(kind, name, ".json", roots)) as f:
        return json.load(f)


def load_py(kind: str, name: str, roots):
    path = find(kind, name, ".py", roots)
    mod_name = "bench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if mod_name in sys.modules and getattr(
            sys.modules[mod_name], "__file__", None) == path:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class CompileWatch:
    """Counts XLA compilations (fresh, or fetched from the persistent
    cache: either means a program was not warm)."""

    _EVENTS = ("backend_compile_duration", "cache_retrieval_time_sec")
    _installed = None

    def __init__(self):
        self.count = 0
        if CompileWatch._installed is None:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                CompileWatch._on_event)
        CompileWatch._installed = self

    @staticmethod
    def _on_event(event: str, duration: float, **kw) -> None:
        me = CompileWatch._installed
        if me is not None and event.endswith(CompileWatch._EVENTS):
            me.count += 1

    def mark(self) -> int:
        return self.count


@dataclasses.dataclass
class Run:
    """What a driver and the reducers are handed."""

    workload: str
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    roots: list
    on_chip: bool
    proc_t0: float
    chips: int
    devices: list = dataclasses.field(default_factory=list)
    peak: dict | None = None
    checks: list = dataclasses.field(default_factory=list)
    compiles: CompileWatch | None = None
    scratch: str = ""
    watchdog_ends: float | None = None

    def log(self, msg: str) -> None:
        result_mod.log(f"[{self.workload} +{time.perf_counter() - self.proc_t0:.1f}s] {msg}")

    def check(self, name: str, value, limit, ok: bool | None = None,
              note: str = "") -> bool:
        """One compared number beside its limit (printed in every run)."""
        if ok is None:
            ok = value is not None and value == value and value <= limit
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": bool(ok)})
        self.log(f"check {name}: {value!r} (limit {limit!r}) "
                 f"{'ok' if ok else 'FAILED'}{' -- ' + note if note else ''}")
        return bool(ok)

    def watchdog(self, seconds: float) -> None:
        """From here the run may take ``seconds`` more.  A run that hangs
        dumps every thread's stack and exits non-zero instead of holding
        the chip."""
        self.watchdog_ends = time.perf_counter() + seconds
        faulthandler.dump_traceback_later(seconds, exit=True,
                                          file=sys.__stderr__)

    def window_opens(self) -> None:
        """Set-up is over: what is left is the window, the grace and the
        reference."""
        self.watchdog(3 * self.seconds + 240)

    def json(self, kind: str, name: str) -> dict:
        return load_json(kind, name, self.roots)

    def py(self, kind: str, name: str):
        return load_py(kind, name, self.roots)


def _devices(chips: int, on_chip: bool):
    import jax

    devs = jax.devices()
    if on_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        sys.stderr.write(
            f"benchmark: need {chips} TPU chip(s), jax found "
            f"{len(devs)} x {devs[0].platform}; no result\n")
        raise SystemExit(EXIT_NO_CHIP)
    return devs


def _peak(devices, roots, on_chip: bool) -> dict | None:
    with open(os.path.join(roots[0], "peaks.json")) as f:
        table = json.load(f)
    kind = devices[0].device_kind
    if kind in table:
        return table[kind]
    if on_chip:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return None


def prepare(workload: str, seed: int, seconds: float, trace: bool,
            roots=None, on_chip: bool = True,
            proc_t0: float | None = None) -> Run:
    """Find the cell's files, place the compile cache, look for the
    chip(s).  ``on_chip=False`` (tests only) skips that look."""
    roots = [ROOT] + [r for r in (roots or []) if r != ROOT]
    proc_t0 = time.perf_counter() if proc_t0 is None else proc_t0
    cell = load_json("workloads", workload, roots)
    config = load_json("configs", cell["config"], roots)
    chips = int(cell["chips"])
    os.environ.pop("BENCH_RUN", None)  # the driver's own; not ours to read
    # a first run in a checkout compiles: it may take 1200 s, no more
    faulthandler.dump_traceback_later(1150, exit=True, file=sys.__stderr__)

    # the program's own cache placement: honours JAX_COMPILATION_CACHE_DIR,
    # else one fixed directory inside the checkout
    from paddle_tpu.core import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = _devices(chips, on_chip)
    run = Run(workload=workload, cell=cell, config=config, seed=int(seed),
              seconds=float(seconds), trace=bool(trace), roots=roots,
              on_chip=on_chip, proc_t0=proc_t0, chips=chips,
              devices=list(devices[:chips]),
              peak=_peak(devices, roots, on_chip),
              compiles=CompileWatch(),
              scratch=os.path.join(os.path.dirname(ROOT), ".bench_scratch"))
    run.log(f"seed {seed}, {seconds} s, trace {int(trace)}; platform "
            f"{devices[0].platform}, kind {devices[0].device_kind}, "
            f"{chips} of {len(devices)} device(s); compile cache {cache_dir}")
    return run


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             roots=None, on_chip: bool = True,
             proc_t0: float | None = None) -> dict:
    """Returns the parsed last line (also printed).  ``on_chip=False``
    (tests only) skips the look for a chip and the checks that only a
    chip can pass (kernel routes, compiles counted in the window)."""
    run = prepare(workload, seed, seconds, trace, roots, on_chip, proc_t0)
    cell, config, roots, chips = run.cell, run.config, run.roots, run.chips
    driver = load_py("drivers", config["driver"], roots)
    out = driver.run(run)

    units, values = {}, {}
    if trace:
        for name in cell["per_layer"]:
            spec = load_json("layer_metrics", name, roots)
            reducer = load_py("reducers", spec["reducer"], roots)
            try:
                v = reducer.reduce(spec, out["layer"], run)
            except Exception as e:  # a reader that cannot read says so
                run.log(f"per-layer metric {name}: reader failed: "
                        f"{type(e).__name__}: {e}")
                v = None
            if v is not None:
                values[name], units[name] = float(v), spec["unit"]
                run.log(f"per-layer {name} = {v!r} {spec['unit']} "
                        f"[{spec['layer']}] -> {spec['moves']}")
        missing = [n for n in cell["per_layer"] if n not in values]
        if missing:
            # the driver refuses a traced line that lacks a metric its
            # cell reports: take it out of the cell, or give it a reader
            # that finds something there
            run.log(f"NOT REPORTED, though the cell names them: {missing}")
    else:
        for name in cell["end_to_end"]:
            spec = load_json("end_to_end", name, roots)
            v = out["end_to_end"].get(name)
            if v is not None:
                values[name], units[name] = float(v), spec["unit"]

    if on_chip:
        run.check("compiles_in_window", out.get("compiles_in_window", 0), 0)
    correct = all(c["ok"] for c in run.checks) and bool(run.checks)
    device = result_mod.device_stamp(run.devices, chips)
    if out.get("memory_peak_bytes"):
        device["memory_peak_bytes"] = int(out["memory_peak_bytes"])
    breakdown = None
    if trace and out["layer"].get("profile"):
        from . import trace as trace_mod

        prof, win = out["layer"]["profile"], out["layer"].get("profile_window")
        b = trace_mod.busy(prof, win)
        device["busy_s"], device["window_s"] = b["busy_s"], b["window_s"]
        breakdown = {
            "device_ops": trace_mod.top_ops(prof, win),
            "idle_gaps": trace_mod.idle_gaps(
                prof, out["layer"].get("host_spans", ()), win)}
    faulthandler.cancel_dump_traceback_later()
    notes = out.get("notes", {})
    if trace and run.watchdog_ends is not None:
        # a traced run's margin, printed so that its erosion is seen
        # before it kills a run: the trace's size grows with the engine's
        # speed, and with it the time to stop, load and reduce it
        notes = dict(notes, watchdog_left_s=run.watchdog_ends
                     - time.perf_counter())
    line = result_mod.final_line(
        correct, out["attempted"], out["failed"], values, units, device,
        breakdown, extra={"workload": workload, "seed": int(seed),
                          "checks": run.checks, "notes": notes})
    result_mod.emit(line)
    return json.loads(line)


def main(argv=None, proc_t0: float | None = None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = a.seconds if a.seconds is not None else bench["run_seconds"]
    run_cell(a.workload, a.seed, seconds, bool(a.trace), proc_t0=proc_t0)
    return 0
