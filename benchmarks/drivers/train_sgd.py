"""Driver: a v2 topology through ``paddle.trainer.SGD.train``.

The whole trainer loop is under test: ``paddle.batch(reader, B)`` ->
``DataFeeder`` -> prefetch -> the compiled step -> the deferred fence.
Set-up builds ONE ``SGD`` object, drives it from the seed through its
first three steps (``SGD.train`` itself, on rows that all differ) and
hands that same object to the window.  After the window the plain
reference follows the same three steps and the two are compared.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import time

import numpy as np

from benchmarks.harness import compare, loadgen
from benchmarks.harness import trace as trace_mod

CHECK_STEPS = 3


class Feed:
    """The v2 reader: samples (input row, label) one by one, from a
    seeded host pool of one batch and seeded labels per step.  What a
    sample is (its shape, its label) is the reference's to say."""

    def __init__(self, run, batch: int, ref):
        self.cfg, self.ref = run.config, ref
        self.batch, self.seed = batch, run.seed
        self.pool = ref.feed_pool(self.cfg, loadgen.rng_for(run.seed, 0),
                                  batch)
        self.stop = False
        self.next_step = 0

    def labels(self, step: int) -> np.ndarray:
        return self.ref.feed_labels(
            self.cfg, loadgen.rng_for(self.seed, 100 + step), self.batch)

    def reader(self, steps: int | None = None):
        """A reader of ``steps`` batches' worth of samples (None: until
        ``stop``), continuing the step count."""
        def samples():
            n = 0
            while (steps is None and not self.stop) or (
                    steps is not None and n < steps):
                lab = self.labels(self.next_step).tolist()
                self.next_step += 1
                n += 1
                for i in range(self.batch):
                    yield self.pool[i], lab[i]
        return samples

    def reference_batches(self, steps: int) -> list:
        """The first ``steps`` batches as the reference takes them."""
        inputs = self.ref.reference_inputs(self.cfg, self.pool)
        return [(inputs, self.labels(k)) for k in range(steps)]


def _build_cost(cfg: dict):
    from paddle_tpu.layers import base as layer_base

    layer_base.reset_name_counters()
    model = cfg["model"]
    cost = getattr(importlib.import_module(model["module"]),
                   model["builder"])(**model.get("kwargs", {}))
    return cost[0] if isinstance(cost, (tuple, list)) else cost


def _set_flags(cfg: dict, cell: dict) -> dict:
    """Program flags the configuration (or the cell) states, e.g. which
    convolution path runs."""
    from paddle_tpu.core import flags as flags_mod

    stated = {**cfg.get("flags", {}), **cell.get("flags", {})}
    for name, value in stated.items():
        flags_mod.set(name, value)
    return stated


def _leaf_norms(arrays: dict, base: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - (0.0 if b is None else b[k]))))
            for k in a}

    return {k: float(v) for k, v in jax.device_get(
        norms(arrays, base)).items()}


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import metrics as metrics_mod
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import tpp
    from paddle_tpu.parallel.mesh import MeshContext, make_mesh
    from paddle_tpu.telemetry import tracing as tracing_mod

    cfg, traffic = run.config, run.cell["traffic"]
    batch = int(traffic["global_batch"])
    opts = cfg["trainer"]
    ref = run.py("references", cfg["reference"])

    stated = _set_flags(cfg, run.cell)

    # -- the model, its seeded weights, ONE trainer ---------------------------
    cost = _build_cost(cfg)
    weights0 = ref.init_weights(cfg, run.seed)          # on device, one jit
    flat0 = {ref.program_name(k): x
             for k, x in compare.flat_tree(weights0).items()}
    params = Parameters()
    for spec in paddle.topology.Topology(cost).param_specs():
        params.add(spec)
        params[spec.name] = flat0[spec.name]
    mesh = MeshContext(make_mesh({"data": run.chips},
                                 devices=run.devices[:run.chips]))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(
            momentum=opts["momentum"], learning_rate=opts["learning_rate"]),
        compute_dtype=getattr(jnp, cfg["compute_dtype"]), mesh=mesh,
        zero=int(traffic.get("zero", 0)))
    feed = Feed(run, batch, ref)

    if run.trace:
        tracing_mod.configure_tracing(enabled=True)
    sink = metrics_mod.MemorySink()
    registry = metrics_mod.MetricsRegistry(f"bench_{run.workload}")
    if run.trace:
        registry.add_sink(sink)

    def train(reader, handler):
        trainer.train(reader=paddle.reader.batch(reader, batch), num_passes=1,
                      event_handler=handler, metrics_registry=registry,
                      prefetch=int(opts["prefetch"]),
                      sync_period=int(opts["sync_period"]))

    # -- first three steps: through the window's own call and feed ------------
    losses: list[float] = []

    def on_check(e):
        if isinstance(e, paddle.event.EndIteration):
            losses.append(float(e.cost))

    with pallas.capture_routes() as routes:
        train(feed.reader(1), on_check)
    velocity = {n: s["velocity"]
                for n, s in trainer._opt_state["slots"].items()}
    prog = {"grad_norms": _leaf_norms(velocity)}
    # a copy: the step donates its optimizer state to the next call
    first_grad = {n: jnp.array(v, copy=True) for n, v in velocity.items()}
    train(feed.reader(CHECK_STEPS - 1), on_check)
    now = trainer.parameters.as_dict()
    prog["delta_norms"] = _leaf_norms({n: now[n] for n in flat0}, flat0)
    prog["losses"] = list(losses)
    del velocity, now, flat0
    routes = {f"{op}:{path}": n for (op, path), n in sorted(routes.items())}
    run.log(f"first {CHECK_STEPS} steps: losses {losses}; routes {routes}; "
            f"fused_kernels {tpp.fused_enabled()}")

    # -- the window -----------------------------------------------------------
    sync = max(int(opts["sync_period"]), 1)
    lead = int(traffic.get("lead_in_steps", 2 * sync))
    prof_steps = int(traffic.get("profile_steps", 3 * sync))
    st = {"phase": "lead", "n": 0, "t_open": None, "t_close": None,
          "examples": 0, "attempted": 0, "failed": 0, "profiling": False,
          "prof_from": None, "prof_n": 0, "marker_ns": None}
    profile_dir = os.path.join(run.scratch, "profile", run.workload)

    def burst_end(e) -> bool:
        return (e.batch_id + 1) % sync == 0

    def on_window(e):
        if not isinstance(e, paddle.event.EndIteration):
            return
        now = time.perf_counter()
        st["n"] += 1
        if st["phase"] == "lead":
            if st["n"] >= lead and burst_end(e):
                st["phase"], st["t_open"], st["lead_n"] = "open", now, st["n"]
                run.log(f"window open after {st['n']} lead-in steps")
                run.window_opens()
            return
        if st["phase"] != "open":
            return
        st["attempted"] += 1
        if math.isfinite(float(e.cost)):
            st["examples"] += batch
        else:
            st["failed"] += 1
        if not burst_end(e):
            return
        if run.trace and st["profiling"]:
            st["prof_n"] = st["attempted"] - st["prof_from"]
            if st["prof_n"] >= prof_steps:
                trace_mod.stop()
                st["profiling"] = False
                st["prof_done"] = True
                run.log(f"profiled {st['prof_n']} steps")
        elif (run.trace and not st.get("prof_done")
              and st["attempted"] >= sync):
            run.log("profile starts")
            st["marker_ns"] = trace_mod.start(profile_dir)
            st["profiling"], st["prof_from"] = True, st["attempted"]
        if now - st["t_open"] >= run.seconds and not st["profiling"]:
            st["phase"], st["t_close"] = "closed", now
            feed.stop = True

    sink.records.clear()
    tracing_mod.get_tracer().clear()
    run.watchdog(240)           # every program is warm: the lead-in is short
    setup_s = time.perf_counter() - run.proc_t0
    mark = run.compiles.mark()
    t_call = time.perf_counter()
    train(feed.reader(None), on_window)
    if st["profiling"]:
        trace_mod.stop()
    # set-up ends where the window opens: add the lead-in steps
    setup_s += (st["t_open"] or time.perf_counter()) - t_call
    compiles = run.compiles.mark() - mark
    if st["t_close"] is None:
        raise RuntimeError("the window never closed")
    window_s = st["t_close"] - st["t_open"]
    rate = st["examples"] / window_s
    # live buffers and the program's reserved scratch are separate pools
    peak_bytes = max(
        int(m.get("peak_bytes_in_use", 0)) + int(m.get(
            "peak_bytes_reserved", 0))
        for m in ((d.memory_stats() or {}) for d in run.devices))
    records = [r for r in sink.records if r.get("kind") == "step"][
        st.get("lead_n", 0):st.get("lead_n", 0) + st["attempted"]]
    spans = trace_mod.span_dicts(tracing_mod.get_tracer().spans)
    if run.peak:
        util = (rate * cfg["flops_per_example"]["train"] / run.chips
                / run.peak["flops_per_s"])
        run.log(f"window {window_s:.3f} s, {st['attempted']} steps, "
                f"{rate:.2f} examples/s = {100 * util:.2f}% of "
                f"{run.chips} x {run.peak['flops_per_s'] / 1e12:.0f} TFLOP/s "
                f"at {cfg['flops_per_example']['train'] / 1e9:.2f} "
                "GFLOP/example (end-to-end utilisation, not a roofline)")

    # -- free the program, then the plain reference ---------------------------
    stamp = bool(tpp.fused_enabled())
    del trainer, params
    gc.collect()
    t_ref = time.perf_counter()
    want = ref.train_reference(
        cfg, weights0, feed.reference_batches(CHECK_STEPS),
        lr=opts["learning_rate"], momentum=opts["momentum"],
        shards=run.chips)
    for what in ("grad_norms", "delta_norms"):
        want[what] = {ref.program_name(k): v
                      for k, v in compare.flat_norms(want[what]).items()}
    prog["grad_diff"] = compare.diff_norms(first_grad, {
        ref.program_name(k): v
        for k, v in compare.flat_tree(want.pop("first_grad")).items()})
    del first_grad
    got = compare.compare(prog, want)
    run.log(f"reference followed {CHECK_STEPS} steps in "
            f"{time.perf_counter() - t_ref:.1f} s: losses {want['losses']}")
    lim = run.cell["limits"]
    run.check("loss_gap", got["loss_gap"], lim["loss_gap"])
    run.check("grad_gap", got["grad_gap"], lim["grad_gap"],
              note=f"worst leaf {got['grad_worst_leaf']}")
    run.check("delta_gap", got["delta_gap"], lim["delta_gap"],
              note=f"worst leaf {got['delta_worst_leaf']}")
    run.check("grad_p90_gap", got["grad_p90_gap"], lim["grad_p90_gap"])
    run.check("delta_p90_gap", got["delta_p90_gap"], lim["delta_p90_gap"])
    run.check("grad_diff_p90", got["grad_diff_p90"], lim["grad_diff_p90"],
              note=f"median leaf {got['grad_diff_p50']}, worst "
              f"{got['grad_diff_max']}")
    run.check("nonfinite_steps", st["failed"], 0)
    if run.on_chip:
        run.check("reference_routes",
                  sum(n for k, n in routes.items()
                      if k.endswith(":reference")), 0)
        want_fused = str(stated.get("fused_kernels", "auto")) != "off"
        run.check("fused_kernels_not_as_stated", int(stamp != want_fused), 0,
                  note=f"fused_kernels {stamp}, the cell states "
                  f"{stated.get('fused_kernels', 'auto')!r}")

    layer = {"records": records, "spans": spans, "samples": {},
             "registry": registry, "steps": st["attempted"],
             "batch": batch}
    if run.trace and st.get("prof_done"):
        trace_mod.attach(layer, profile_dir, st["marker_ns"])
        layer["profile_steps"] = st["prof_n"]
    return {"end_to_end": {"train_examples_per_s": rate, "setup_s": setup_s},
            "attempted": st["attempted"], "failed": st["failed"],
            "layer": layer, "compiles_in_window": compiles,
            "memory_peak_bytes": peak_bytes,
            "notes": {"window_s": window_s, "steps": st["attempted"],
                      "routes": routes, "compare": got}}


def control(run) -> dict:
    """The control of ``correct`` (``benchmarks/control.py``): the
    reference in float32 and again in the configuration's
    ``control_precision``, compared exactly as the program is.  Training
    needs no window."""
    cfg, opts = run.config, run.config["trainer"]
    ref = run.py("references", cfg["reference"])
    feed = Feed(run, int(run.cell["traffic"]["global_batch"]), ref)
    batches = feed.reference_batches(CHECK_STEPS)
    weights = ref.init_weights(cfg, run.seed)
    out = {}
    for tag, quant in (("reference", None),
                       ("control", cfg["control_precision"])):
        r = ref.train_reference(cfg, weights, batches,
                                lr=opts["learning_rate"],
                                momentum=opts["momentum"], shards=run.chips,
                                quant=quant)
        for what in ("grad_norms", "delta_norms"):
            r[what] = compare.flat_norms(r[what])
        r["first_grad"] = compare.flat_tree(r["first_grad"])
        out[tag] = r
    out["control"]["grad_diff"] = compare.diff_norms(
        out["control"].pop("first_grad"), out["reference"].pop("first_grad"))
    return compare.compare(out["control"], out["reference"])


def aot_programs(cell: dict, cfg: dict, roots, topo, struct) -> list:
    """``benchmarks/aot_check.py``: the cell's step, lowered at full size
    for the described devices.  [(tag, lowered)]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.parallel.mesh import MeshContext
    from paddle_tpu.trainer.step import build_train_step

    from benchmarks.harness import runner

    _set_flags(cfg, cell)
    chips = int(cell["chips"])
    batch = int(cell["traffic"]["global_batch"])
    topology = paddle.topology.Topology(_build_cost(cfg))
    mesh = Mesh(topo.devices[:chips], ("data",))
    opt = paddle.optimizer.Momentum(
        momentum=cfg["trainer"]["momentum"],
        learning_rate=cfg["trainer"]["learning_rate"])
    specs = {s.name: s for s in topology.param_specs()}
    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = {n: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=rep)
              for n, s in specs.items()}
    opt_state = struct(jax.eval_shape(
        lambda p: opt.init(p, specs),
        {n: jax.ShapeDtypeStruct(s.shape, jnp.float32)
         for n, s in specs.items()}), rep)
    states = struct(jax.eval_shape(topology.init_states), rep)
    ref = runner.load_py("references", cfg["reference"], roots)
    feed = {name: jax.ShapeDtypeStruct(shape, getattr(jnp, dtype),
                                       sharding=dat)
            for name, (shape, dtype) in ref.feed_struct(cfg, batch).items()}
    key = struct(jax.eval_shape(lambda: jax.random.key(0)), rep)
    step = build_train_step(topology, opt, MeshContext(mesh),
                            compute_dtype=getattr(jnp, cfg["compute_dtype"]),
                            zero=int(cell["traffic"].get("zero", 0)))
    return [(f"{cell['name']}: train step, batch {batch} on {chips} chip(s)",
             step.lower(params, opt_state, states, feed, key))]
