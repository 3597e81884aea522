#!/usr/bin/env python
"""tools/bench_cost_calibration.py — ties the GL-P-COST roofline to a
tracewire-measured wall clock, so the static model stays honest.

For each checked-in bench family (transformer LM, resnet50, lstm) it
builds a **CPU-calibration shape** — the same architecture as the bench
config with reduced dims, because the full bench shapes take minutes
per step on the 1-core CI box — then:

- predicts the compute-phase time with ``cost_report(...)`` under the
  ``cpu-testbed`` profile (XLA's own ``cost_analysis()`` refinement
  engages, same as ``trainer --preflight``);
- measures it with a tracewire ``Tracer``: one warmup step (compile +
  first-touch excluded), then ``--steps`` executed steps each inside a
  ``span("compute")`` with ``block_until_ready``, taking the phase p50;
- fails (rc 1) when any family's prediction/measurement ratio leaves
  the documented band ``[1/BAND, BAND]`` with ``BAND = 2.0``.

The band is the contract: the ``cpu-testbed``
``HwProfile`` constants in ``paddle_tpu/analysis/cost.py`` are
*calibrated against this harness*, not datasheet numbers.  A run
outside the band means either those constants or the charging rules
drifted — fix the model, don't widen the band.

    python tools/bench_cost_calibration.py
    python tools/bench_cost_calibration.py --families lstm --steps 5
    python tools/bench_cost_calibration.py --json -
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# documented prediction band: predicted/measured must stay in
# [1/BAND, BAND].  2× is loose for a reason — XLA:CPU's achieved
# FLOP/s swings with shape, and the roofline carries no fusion model.
BAND = 2.0


def _measure(step, args_fn, steps: int) -> float:
    """Phase p50 over ``steps`` executed calls of ``step`` (donation-safe:
    ``args_fn`` threads the returned state back in), warmup excluded."""
    import jax

    from paddle_tpu.telemetry.tracing import Tracer

    tracer = Tracer(enabled=True)
    state = args_fn(None)
    state = jax.block_until_ready(step(*state))  # warmup: compile+run
    for _ in range(steps):
        state = args_fn(state)
        with tracer.span("compute"):
            state = jax.block_until_ready(step(*state))
    return tracer.phase_summary()["compute"]["p50_ms"]


# -- CPU-calibration shapes (the architectures plan_search.py ranks) -----------


def _calibrate_transformer(steps: int) -> dict:
    """GPT-2 architecture at calibration scale: 2 layers, embed 128,
    4 heads, seq 128, bs 4 (bench: 12×768×12, seq 1024, bs 16)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    cfg = T.TransformerConfig(
        vocab_size=2048, num_layers=2, num_heads=4, embed_dim=128,
        mlp_dim=512, max_seq_len=256, dtype=jnp.float32, remat=False)
    params = T.init_params(cfg, jax.random.key(0))
    opt = Adam(learning_rate=1e-4, moment_dtype=jnp.bfloat16)
    opt_state = opt.init_tree(params)
    ids = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(4, 129)))
    step = T.build_train_step(cfg, opt, compute_dtype=jnp.bfloat16)

    def args_fn(prev):
        if prev is None:
            return (params, opt_state, ids)
        p, o, _loss = prev
        return (p, o, ids)

    return {"step": step, "args_fn": args_fn,
            "args": (params, opt_state, ids), "steps": steps}


def _calibrate_topology(cost_fn, feed, optimizer, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import base
    from paddle_tpu.trainer.step import build_train_step

    base.reset_name_counters()
    topo = Topology(cost_fn())
    specs = {s.name: s for s in topo.param_specs()}
    params = paddle.parameters.create(topo).as_dict()
    opt_state = optimizer.init(params, specs)
    states = topo.init_states()
    step = build_train_step(topo, optimizer, compute_dtype=jnp.bfloat16)
    key = jax.random.key(0)

    def args_fn(prev):
        if prev is None:
            return (params, opt_state, states, feed, key)
        p, o, s, _cost, _metrics = prev
        return (p, o, s, feed, key)

    return {"step": step, "args_fn": args_fn,
            "args": (params, opt_state, states, feed, key),
            "steps": steps}


def _calibrate_resnet50(steps: int) -> dict:
    """The full resnet50 bottleneck stack at bs 1 (bench: bs 128).  The
    224×224 input cannot shrink — the trunk's stride-32 downsample ends
    in a hard-coded 7×7 global pool — so this family calibrates at full
    spatial resolution and caps its step count instead."""
    from paddle_tpu.models import image as M
    from paddle_tpu.optimizer import Momentum

    rng = np.random.default_rng(0)
    feed = {"image": rng.normal(size=(1, 224 * 224 * 3)).astype(
                np.float32),
            "label": rng.integers(0, 1000, size=(1,))}
    return _calibrate_topology(
        lambda: M.resnet_cost(depth=50)[0], feed,
        Momentum(momentum=0.9, learning_rate=0.01), min(steps, 3))


def _calibrate_lstm(steps: int) -> dict:
    """The bench lstm classifier at hidden 256, bs 16, T 50
    (bench: hidden 512, bs 256, T 100)."""
    import jax.numpy as jnp

    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.models.rnn import lstm_classify_cost
    from paddle_tpu.optimizer import Adam

    rng = np.random.default_rng(0)
    feed = {"data": SequenceBatch(
                data=rng.integers(0, 30000, size=(16, 50)),
                length=np.full((16,), 50, np.int32)),
            "label": rng.integers(0, 2, size=(16,))}
    return _calibrate_topology(
        lambda: lstm_classify_cost(256), feed,
        Adam(learning_rate=2e-3, moment_dtype=jnp.bfloat16), steps)


FAMILIES = {
    "transformer": _calibrate_transformer,
    "resnet50": _calibrate_resnet50,
    "lstm": _calibrate_lstm,
}


def calibrate_family(name: str, steps: int) -> dict:
    from paddle_tpu.analysis.cost import cost_report
    from paddle_tpu.analysis.program import jaxpr_of

    t0 = time.time()
    cal = FAMILIES[name](steps)
    jx = jaxpr_of(cal["step"], *cal["args"])
    lowered = None
    try:
        import jax

        lowered = jax.jit(cal["step"]).lower(*cal["args"])
    except Exception as e:
        # prediction falls back to the pure jaxpr walk
        print(f"bench_cost_calibration: {name}: lowering unavailable "
              f"({e}); using jaxpr-walk totals", file=sys.stderr)
    rep = cost_report(jx, profile="cpu-testbed", lowered=lowered)
    measured = _measure(cal["step"], cal["args_fn"], cal["steps"])
    ratio = rep["compute_ms"] / measured if measured > 0 else float("inf")
    return {
        "family": name,
        "predicted_compute_ms": round(rep["compute_ms"], 3),
        "measured_p50_ms": round(measured, 3),
        "ratio": round(ratio, 3),
        "in_band": (1.0 / BAND) <= ratio <= BAND,
        "flops_source": rep["flops_source"],
        "bottleneck": rep["bottleneck"],
        "wall_s": round(time.time() - t0, 1),
    }


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "-h" in argv or "--help" in argv:
        print(__doc__.strip())
        return 2

    def _opt(flag, default):
        if flag in argv:
            i = argv.index(flag)
            val = argv[i + 1]
            del argv[i:i + 2]
            return val
        return default

    steps = int(_opt("--steps", "5"))
    fams = _opt("--families", "")
    json_out = _opt("--json", "")
    families = [f for f in fams.split(",") if f] or list(FAMILIES)
    if argv:
        print(f"bench_cost_calibration: unknown arguments {argv}",
              file=sys.stderr)
        return 2
    unknown = [f for f in families if f not in FAMILIES]
    if unknown:
        print(f"bench_cost_calibration: unknown families {unknown} "
              f"(known: {', '.join(FAMILIES)})", file=sys.stderr)
        return 2

    rows = [calibrate_family(f, steps) for f in families]
    hdr = (f"{'family':<12} {'pred ms':>9} {'meas p50':>9} "
           f"{'ratio':>6}  band  source")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['family']:<12} {r['predicted_compute_ms']:>9.2f} "
              f"{r['measured_p50_ms']:>9.2f} {r['ratio']:>6.2f}  "
              f"{'ok  ' if r['in_band'] else 'FAIL'}  "
              f"{r['flops_source']}")
    ok = all(r["in_band"] for r in rows)
    verdict = (f"bench_cost_calibration: {'PASS' if ok else 'FAIL'} — "
               f"band [{1 / BAND:g}x, {BAND:g}x], {steps} steps/family")
    print(verdict)
    if json_out:
        payload = json.dumps({"band": BAND, "steps": steps,
                              "pass": ok, "rows": rows}, indent=1)
        if json_out == "-":
            print(payload)
        else:
            with open(json_out, "w") as f:
                f.write(payload + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
