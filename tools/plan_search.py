#!/usr/bin/env python
"""tools/plan_search.py — config-space feasibility pruner + static plan
ranking over the bench model families (the plan-cache seed for the
future autotuner; ROADMAP item 4).

``--enumerate`` sweeps the config grid (mesh data-axis size × zero mode
× lowering × fused_kernels × remat × seq_buckets × batch) per model
family, WITHOUT compiling or executing any step:

- each distinct (family, batch, remat) is traced ONCE to a jaxpr; every
  mesh/zero/lowering variant of it is scored analytically from that one
  trace (the same GSPMD global-shape scaling rule GL-P-MEM uses);
- infeasible points are pruned by the GL-P-MEM static byte model
  (params + zero-mode optimizer slots + activations/dp vs ``--hbm_gb``);
- survivors are ranked by the GL-P-COST roofline: primary key is
  normalized chip-time, ``step_ms × dp / batch`` (predicted step_ms
  alone would trivially crown the smallest config), with deterministic
  tie-breaks preferring the simpler plan (smaller dp, lower zero, the
  default lowering/bucketing, fused kernels on) — duplicate-cost
  variants the static model cannot distinguish must not rank randomly;
- the ranked plan is persisted as JSON (``--out``, default PLAN.json)
  with the per-family top choice and whether it matches the hand-picked
  checked-in bench config.

Trace-only: safe on a CPU dev box, no accelerator, no XLA compile.

    python tools/plan_search.py --enumerate
    python tools/plan_search.py --enumerate --families lstm --json -
    python tools/plan_search.py --enumerate --hw_profile v5p --hbm_gb 16
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the per-chip HBM budget the bench fleet's hand-picked configs were
# sized for (a v5e-class part); pass 0 to use the profile's capacity
DEFAULT_HBM_GB = 16.0

# the hand-picked plans recorded in PLAN.json — the plan search's
# correctness anchor: on that budget its top choice should rediscover
# at least one of these
HAND_PICKED = {
    "transformer": {"batch": 16, "remat": False, "dp": 1, "zero": 0},
    "resnet50": {"batch": 128, "dp": 1, "zero": 0},
    "lstm": {"batch": 256, "dp": 1, "zero": 0},
}


class _MeshShim:
    """Just enough mesh for the static models: ``shape`` (dict-like) and
    ``axis_names`` — no devices, so dp>1 plans can be scored on a 1-chip
    dev box without building a real jax Mesh."""

    def __init__(self, dp: int, axis: str = "data"):
        self.shape = {axis: int(dp)}
        self.axis_names = (axis,)


# -- one trace per (family, batch, remat) ---------------------------------------


def _trace_transformer(batch: int, remat: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.program import jaxpr_of
    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    seq = 1024
    cfg = T.TransformerConfig(
        vocab_size=50257, num_layers=12, num_heads=12, embed_dim=768,
        mlp_dim=3072, max_seq_len=2048, dtype=jnp.float32, remat=remat,
        attn_impl="flash", attn_block_size=1024)
    params = T.init_params(cfg, jax.random.key(0))
    opt = Adam(learning_rate=1e-4, moment_dtype=jnp.bfloat16)
    opt_state = opt.init_tree(params)
    ids = np.zeros((batch, seq + 1), np.int32)
    step = T.build_train_step(cfg, opt, compute_dtype=jnp.bfloat16)
    jx = jaxpr_of(step, params, opt_state, ids)
    return {"jx": jx, "params": params, "opt_state": opt_state,
            "states": {}, "feed": {"ids": ids}, "batch": batch,
            "seq": seq, "examples": batch}


def _trace_topology(cost_fn, feed, batch: int, optimizer=None) -> dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.analysis.program import jaxpr_of
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import base
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.trainer.step import build_train_step

    base.reset_name_counters()
    topo = Topology(cost_fn())
    opt = optimizer or Momentum(momentum=0.9, learning_rate=0.01)
    specs = {s.name: s for s in topo.param_specs()}
    params = paddle.parameters.create(topo).as_dict()
    opt_state = opt.init(params, specs)
    states = topo.init_states()
    step = build_train_step(topo, opt, compute_dtype=jnp.bfloat16)
    args = (params, opt_state, states, feed, jax.random.key(0))
    jx = jaxpr_of(step, *args)
    return {"jx": jx, "params": params, "opt_state": opt_state,
            "states": states, "feed": feed, "batch": batch,
            "examples": batch}


def _trace_resnet50(batch: int, remat: bool = False) -> dict:
    from paddle_tpu.models import image as M

    rng = np.random.default_rng(0)
    feed = {"image": rng.normal(size=(batch, 224 * 224 * 3)).astype(
                np.float32),
            "label": rng.integers(0, 1000, size=(batch,))}
    return _trace_topology(lambda: M.resnet_cost(depth=50)[0], feed,
                           batch)


def _trace_lstm(batch: int, remat: bool = False) -> dict:
    import jax.numpy as jnp

    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.models.rnn import lstm_classify_cost
    from paddle_tpu.optimizer import Adam

    rng = np.random.default_rng(0)
    feed = {"data": SequenceBatch(
                data=rng.integers(0, 30000, size=(batch, 100)),
                length=np.full((batch,), 100, np.int32)),
            "label": rng.integers(0, 2, size=(batch,))}
    return _trace_topology(
        lambda: lstm_classify_cost(512), feed,
        batch, optimizer=Adam(learning_rate=2e-3,
                              moment_dtype=jnp.bfloat16))


# -- the grid -------------------------------------------------------------------

# (dp, zero, lowering) mesh plans: dp=1 has one lowering; dp=8 scores
# both lowering families (identical static cost — the tie-break keeps
# the partitioner default first)
_MESH_PLANS = [(1, 0, "auto"),
               (8, 0, "gspmd"), (8, 0, "explicit"),
               (8, 1, "gspmd"), (8, 1, "explicit")]

FAMILIES = {
    "transformer": {
        "trace": _trace_transformer,
        "batches": (8, 16, 32),
        "remat": (False, True),
        "fused": (True,),
        "seq_buckets": ("",),
    },
    "resnet50": {
        "trace": _trace_resnet50,
        "batches": (64, 128, 256),
        "remat": (False,),
        "fused": (True,),
        "seq_buckets": ("",),
    },
    "lstm": {
        "trace": _trace_lstm,
        "batches": (128, 256),
        "remat": (False,),
        "fused": (True, False),
        "seq_buckets": ("", "32,64,100"),
    },
}


def _tie_key(pt: dict) -> tuple:
    """Deterministic ranking key: normalized chip-time first, then the
    simpler plan wins among statically indistinguishable variants."""
    return (pt["score_chip_ms_per_example"], pt["dp"], pt["zero"],
            0 if pt["lowering"] in ("auto", "gspmd") else 1,
            0 if pt["fused_kernels"] else 1,
            0 if not pt["seq_buckets"] else 1,
            0 if not pt["remat"] else 1,
            -pt["batch"])


def enumerate_family(name: str, spec: dict, profile, hbm_gb: float,
                     log=print) -> dict:
    """Trace, prune and rank one family's grid.  Returns the family
    section of the plan JSON."""
    from paddle_tpu.analysis.cost import cost_report
    from paddle_tpu.analysis.memory import (
        activation_peak_bytes,
        opt_state_bytes_per_device,
        pallas_vmem_estimates,
        tree_bytes,
    )

    feasible: list[dict] = []
    pruned: list[dict] = []
    n_traces = 0
    for batch in spec["batches"]:
        for remat in spec["remat"]:
            t0 = time.time()
            tr = spec["trace"](batch, remat)
            n_traces += 1
            log(f"  traced {name} batch={batch} remat={remat} "
                f"({time.time() - t0:.1f}s)")
            params_b = tree_bytes(tr["params"])
            states_b = tree_bytes(tr["states"])
            feed_b = tree_bytes(tr["feed"])
            act_b = activation_peak_bytes(tr["jx"])
            pallas = pallas_vmem_estimates(tr["jx"])
            cost_cache: dict = {}
            for dp, zero, lowering in _MESH_PLANS:
                shim = _MeshShim(dp) if dp > 1 else None
                opt_b = opt_state_bytes_per_device(
                    tr["opt_state"], tr["params"], shim, zero)
                total = (params_b + opt_b + states_b
                         + feed_b // dp + act_b // dp)
                if (dp, zero) not in cost_cache:
                    cost_cache[(dp, zero)] = cost_report(
                        tr["jx"], profile=profile, mesh=shim, zero=zero,
                        params_bytes=params_b)
                cost = cost_cache[(dp, zero)]
                for fused in spec["fused"]:
                    for buckets in spec["seq_buckets"]:
                        pt = {
                            "family": name, "batch": batch,
                            "remat": remat, "dp": dp, "zero": zero,
                            "lowering": lowering,
                            "fused_kernels": fused,
                            "seq_buckets": buckets,
                            "mem_total_bytes": total,
                            "step_ms": cost["step_ms"],
                            "mfu_pct": cost["mfu_pct"],
                            "comm_ms": cost["comm_ms"],
                            "bottleneck": cost["bottleneck"],
                            "score_chip_ms_per_example":
                                cost["step_ms"] * dp / tr["examples"],
                        }
                        budget = hbm_gb * 1e9
                        if budget > 0 and total > budget:
                            pt["pruned"] = (
                                f"GL-P-MEM: {total / 1e9:.2f} GB > "
                                f"{hbm_gb:g} GB")
                            pruned.append(pt)
                        else:
                            feasible.append(pt)
            del tr  # free the traced params before the next shape
    feasible.sort(key=_tie_key)
    top = feasible[0] if feasible else None
    want = HAND_PICKED.get(name, {})
    matches = bool(top) and all(top.get(k) == v for k, v in want.items())
    return {"points": len(feasible) + len(pruned), "traces": n_traces,
            "pruned": len(pruned), "ranked": feasible,
            "pruned_points": pruned, "top": top,
            "hand_picked": want, "top_matches_bench": matches}


def build_plan(families=None, hw_profile_name: str = "v5p",
               hbm_gb: float = DEFAULT_HBM_GB, log=print) -> dict:
    from paddle_tpu.analysis.cost import hw_profile

    profile = hw_profile(hw_profile_name)
    if hbm_gb <= 0:
        hbm_gb = profile.hbm_gb
    plan: dict = {
        "schema": "paddle_tpu.plan/1",
        "hw_profile": profile.name,
        "hbm_gb": hbm_gb,
        "families": {},
    }
    total = prunedn = 0
    for name, spec in FAMILIES.items():
        if families and name not in families:
            continue
        log(f"plan_search: enumerating {name} ...")
        fam = enumerate_family(name, spec, profile, hbm_gb, log=log)
        plan["families"][name] = fam
        total += fam["points"]
        prunedn += fam["pruned"]
    plan["grid_points"] = total
    plan["pruned"] = prunedn
    return plan


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or "-h" in argv or "--help" in argv:
        print(__doc__.strip())
        return 2
    if "--enumerate" not in argv:
        print("plan_search: nothing to do (pass --enumerate)",
              file=sys.stderr)
        return 2
    argv.remove("--enumerate")

    def _opt(flag, default):
        if flag in argv:
            i = argv.index(flag)
            val = argv[i + 1]
            del argv[i:i + 2]
            return val
        return default

    out_path = _opt("--out", os.path.join(REPO, "PLAN.json"))
    hw = _opt("--hw_profile", "v5p")
    hbm_gb = float(_opt("--hbm_gb", str(DEFAULT_HBM_GB)))
    fams = _opt("--families", "")
    families = [f for f in fams.split(",") if f] or None
    quiet = "--quiet" in argv
    if quiet:
        argv.remove("--quiet")
    if argv:
        print(f"plan_search: unknown arguments {argv}", file=sys.stderr)
        return 2
    log = (lambda *a, **k: None) if quiet else print

    t0 = time.time()
    try:
        plan = build_plan(families, hw_profile_name=hw, hbm_gb=hbm_gb,
                          log=log)
    except ValueError as e:  # unknown profile/family: a usage error
        print(f"plan_search: {e}", file=sys.stderr)
        return 2
    plan["wall_s"] = round(time.time() - t0, 1)

    text = json.dumps(plan, indent=1, default=float)
    if out_path == "-":
        print(text)
    else:
        with open(out_path, "w") as f:
            f.write(text + "\n")
    for name, fam in plan["families"].items():
        top = fam["top"] or {}
        log(f"plan_search: {name}: {fam['points']} points "
            f"({fam['traces']} traces), {fam['pruned']} pruned; top = "
            f"batch {top.get('batch')} remat {top.get('remat')} "
            f"dp {top.get('dp')} zero {top.get('zero')} "
            f"({top.get('score_chip_ms_per_example', 0):.4f} "
            f"chip-ms/example, MFU {top.get('mfu_pct', 0):.1f}%)"
            + ("  [= hand-picked bench config]"
               if fam["top_matches_bench"] else ""))
    log(f"plan_search: {plan['grid_points']} grid points, "
        f"{plan['pruned']} pruned, no step compiled, "
        f"{plan['wall_s']}s" + ("" if out_path == "-"
                                else f" -> {out_path}"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
