"""GL-P-COST — static roofline cost model for a built step.

GL-P-MEM answers "does it fit"; this pass answers "how fast should it
be", the objective function ROADMAP item 4's plan search needs (the GDP
framing, arxiv 1910.01578: a placement/config search is only as good as
its cheap static cost signal; arxiv 2104.05755 frames per-kernel
efficiency in exactly these roofline terms).

From nothing but the step's jaxpr and a hardware profile it produces:

- **per-op-class FLOPs and HBM bytes** — every equation is classified
  (matmul / conv / elementwise / reduce / gather / layout), charged
  ``2·M·N·K``-style FLOPs and its operand+result bytes, and rolled up
  per class with a per-class roofline time ``max(flops/peak,
  bytes/hbm_bw)``.  ``scan`` bodies multiply by trip count; control-flow
  wrappers are descended, not charged.
- **per-``pallas_call`` compute** — the kernel body's FLOPs × grid
  points, streamed bytes, and the VMEM-resident block footprint (from
  GL-P-MEM's block-shape walk), so a kernel that will spill VMEM is a
  named bottleneck, not a mystery slowdown.
- **a collective time model over the mesh** — payload bytes per
  reduce-scatter / all-gather / all-to-all from GL-P-COLL's extractor
  (or the analytic ZeRO schedule when the single-device trace carries
  no collectives), ring-scaled wire bytes / per-link ICI bandwidth.
- **predicted step_ms / MFU% / overlap headroom** — compute and
  collective time under the perfect-overlap model ``step =
  max(compute, comm)``; headroom is how much compute slack remains to
  hide the collectives.

When the step was lowered, XLA's own per-signature ``cost_analysis()``
FLOPs/bytes refine the walk's totals (the walk's class *proportions*
are kept — XLA reports totals only).  :func:`cost_report` returns the
dict attached to the ``preflight`` telemetry record (schema
``paddle_tpu.metrics/13``); :func:`cost_budget_pass` turns it into a
GL-P-COST finding when predicted MFU falls below ``--mfu_floor``,
naming the bottleneck: ``memory-bound:<class>``, ``collective-bound``,
or ``vmem-spill:<kernel>``.

Hardware profiles (``--hw_profile``) are a closed table —
:func:`hw_profile` raises a clean error listing the known names rather
than a KeyError, and ``auto`` resolves from the attached devices.
"""

from __future__ import annotations

from dataclasses import dataclass

from paddle_tpu.analysis.core import Finding, finalize


def _pname(name: str) -> str:
    return f"<program:{name}>"


# -- hardware profiles ----------------------------------------------------------


@dataclass(frozen=True)
class HwProfile:
    """Static machine model for the roofline: peak matmul FLOP/s (bf16
    for TPUs), HBM and per-ICI-link bandwidth, and the default memory
    budgets GL-P-MEM gates against when flags leave them unset."""

    name: str
    description: str
    peak_flops: float      # FLOP/s, dense matmul peak (bf16 on TPU)
    hbm_gbps: float        # GB/s, HBM (or host RAM) streaming bandwidth
    ici_gbps: float        # GB/s per ICI link direction (loopback on CPU)
    hbm_gb: float          # HBM capacity per chip
    vmem_mb: float         # VMEM per core (L2-ish working set on CPU)


HW_PROFILES: dict[str, HwProfile] = {
    # TPU v5e chip (Google Cloud "TPU v5e"): 197 TFLOP/s bf16, 16 GB HBM
    # @ 819 GB/s, 1,600 Gbit/s chip-to-chip over 4 ICI links
    "v5e": HwProfile(
        name="v5e",
        description="TPU v5e chip (bf16 MXU peak, HBM2e, 2D-torus ICI)",
        peak_flops=197e12, hbm_gbps=819.0, ici_gbps=50.0,
        hbm_gb=16.0, vmem_mb=128.0),
    # TPU v5p chip: 459 TFLOP/s bf16, 95 GB HBM2e @ 2765 GB/s, 6 ICI
    # links at ~100 GB/s per direction
    "v5p": HwProfile(
        name="v5p",
        description="TPU v5p chip (bf16 MXU peak, HBM2e, 3D-torus ICI)",
        peak_flops=459e12, hbm_gbps=2765.0, ici_gbps=100.0,
        hbm_gb=95.0, vmem_mb=128.0),
    # the CI box: one x86 core under XLA:CPU.  Peak/bandwidth are
    # CALIBRATED numbers (tools/bench_cost_calibration.py ties them to
    # tracewire-measured compute phases within the documented ≤2× band),
    # not datasheet numbers — XLA:CPU reaches nowhere near vector peak
    # on the small calibration shapes.
    "cpu-testbed": HwProfile(
        name="cpu-testbed",
        description="1-core x86 CI testbed under XLA:CPU (calibrated)",
        peak_flops=2.0e10, hbm_gbps=8.0, ici_gbps=4.0,
        hbm_gb=4.0, vmem_mb=1.0),
}


def hw_profile(name: str) -> HwProfile:
    """Profile lookup.  ``auto`` resolves from the first attached device
    ("TPU v5 lite" → ``v5e``, "TPU v5" → ``v5p``, a non-TPU platform →
    ``cpu-testbed``); a TPU kind without a profile and an unknown name
    are clean errors listing the table, never a KeyError or a default."""
    if name == "auto":
        try:
            import jax

            dev = jax.devices()[0]
        except (ImportError, IndexError, RuntimeError):
            # no backend attached: the CPU-testbed default stands
            return HW_PROFILES["cpu-testbed"]
        if dev.platform != "tpu":
            return HW_PROFILES["cpu-testbed"]
        kind = dev.device_kind.lower()
        for prefix, prof in (("tpu v5 lite", "v5e"), ("tpu v5", "v5p")):
            if kind.startswith(prefix):
                return HW_PROFILES[prof]
        raise ValueError(
            f"no hardware profile for TPU device_kind "
            f"{dev.device_kind!r}: known profiles are "
            f"{', '.join(sorted(HW_PROFILES))} — pass --hw_profile or add "
            "one to analysis/cost.HW_PROFILES")
    try:
        return HW_PROFILES[name]
    except KeyError:
        raise ValueError(
            f"unknown --hw_profile {name!r}: known profiles are "
            f"{', '.join(sorted(HW_PROFILES))} (or 'auto')") from None


# -- per-equation FLOP / byte charging ------------------------------------------

_LAYOUT_PRIMS = frozenset({
    "broadcast_in_dim", "transpose", "reshape", "squeeze", "slice",
    "rev", "expand_dims", "copy", "concatenate", "pad", "iota",
    "convert_element_type", "bitcast_convert_type", "stop_gradient",
})
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
    "cumsum", "cummax", "cummin", "cumprod", "cumlogsumexp", "sort",
})
_GATHER_PRIMS = frozenset({
    "gather", "scatter", "scatter_add", "scatter_mul", "scatter_min",
    "scatter_max", "dynamic_slice", "dynamic_update_slice", "take",
    "select_and_scatter_add", "select_and_gather_add",
})
# control flow / call wrappers: descend into the body, charge nothing
_WRAPPER_PRIMS = frozenset({
    "pjit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "checkpoint",
    "remat2", "cond", "while", "custom_lin",
})

OP_CLASSES = ("matmul", "conv", "elementwise", "reduce", "gather",
              "layout")


def _prod(xs) -> int:
    n = 1
    for x in xs:
        n *= int(x)
    return n


def _aval_elems(v) -> int:
    aval = getattr(v, "aval", None)
    return _prod(getattr(aval, "shape", ())) if aval is not None else 0


def _eqn_bytes(eqn) -> int:
    from paddle_tpu.analysis.memory import _aval_bytes

    return (sum(_aval_bytes(v) for v in eqn.invars)
            + sum(_aval_bytes(v) for v in eqn.outvars))


def _dot_flops(eqn) -> int:
    """2 · (batch · M · N) · K for a ``dot_general``: every output
    element is a length-K fused multiply-add chain."""
    (lhs_contract, _rhs_contract), _batch = eqn.params["dimension_numbers"]
    lhs_shape = getattr(eqn.invars[0].aval, "shape", ())
    k = _prod(lhs_shape[d] for d in lhs_contract)
    return 2 * _aval_elems(eqn.outvars[0]) * max(k, 1)


def _conv_flops(eqn) -> int:
    """2 · out_elems · (kernel window · in_channels): each output
    element contracts one kernel's worth of inputs."""
    rhs_shape = getattr(eqn.invars[1].aval, "shape", ())
    dn = eqn.params.get("dimension_numbers")
    out_ch_dim = dn.rhs_spec[0] if dn is not None else 0
    out_ch = rhs_shape[out_ch_dim] if rhs_shape else 1
    window = _prod(rhs_shape) // max(out_ch, 1)
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    return 2 * _aval_elems(eqn.outvars[0]) * max(window // max(groups, 1), 1)


def classify_eqn(eqn) -> tuple[str, int]:
    """(op class, FLOPs) for one equation — the charging rule of the
    whole model.  Layout ops are 0-FLOP (bytes still count); reduces
    charge one op per *input* element; everything else charges one op
    per output element."""
    p = eqn.primitive.name
    if p == "dot_general":
        return "matmul", _dot_flops(eqn)
    if p == "conv_general_dilated":
        return "conv", _conv_flops(eqn)
    if p in _LAYOUT_PRIMS:
        return "layout", 0
    if p in _GATHER_PRIMS:
        return "gather", 0
    if p in _REDUCE_PRIMS or p.startswith("reduce_"):
        return "reduce", sum(_aval_elems(v) for v in eqn.invars)
    return "elementwise", sum(_aval_elems(v) for v in eqn.outvars)


# -- the jaxpr walk -------------------------------------------------------------


def _blank_classes() -> dict:
    return {c: {"flops": 0, "bytes": 0} for c in OP_CLASSES}


def _accumulate(jx, mult: int, classes: dict, pallas: list,
                collectives: list) -> None:
    from paddle_tpu.analysis.program import (_JAXPR_COLLECTIVES,
                                             inner_jaxprs)

    for eqn in jx.eqns:
        p = eqn.primitive.name
        if p == "scan":
            trips = int(eqn.params.get("length", 1) or 1)
            for sub in inner_jaxprs(eqn):
                _accumulate(sub, mult * trips, classes, pallas,
                            collectives)
            continue
        if p == "pallas_call":
            pallas.append(_pallas_cost(eqn, mult))
            continue
        if p in _JAXPR_COLLECTIVES:
            from paddle_tpu.analysis.memory import _aval_bytes

            payload = sum(_aval_bytes(v) for v in eqn.invars)
            collectives.append({"kind": _JAXPR_COLLECTIVES[p],
                                "payload_bytes": payload * mult})
            continue
        subs = list(inner_jaxprs(eqn))
        if p in _WRAPPER_PRIMS or subs:
            # wrappers and anything else carrying a body: the body is
            # the cost, the wrapper eqn itself is bookkeeping
            for sub in subs:
                _accumulate(sub, mult, classes, pallas, collectives)
            continue
        cls, flops = classify_eqn(eqn)
        classes[cls]["flops"] += flops * mult
        classes[cls]["bytes"] += _eqn_bytes(eqn) * mult


def _pallas_cost(eqn, mult: int) -> dict:
    """FLOPs (kernel body × grid points), streamed bytes (operands and
    results cross HBM once) and the VMEM-resident block footprint of
    one ``pallas_call``."""
    from paddle_tpu.analysis.memory import (_aval_bytes,
                                            _shape_dtype_bytes)

    label = str(eqn.params.get("name_and_src_info", "pallas_call"))
    label = label.split(" ")[0].split("(")[0] or "pallas_call"
    gm = eqn.params.get("grid_mapping")
    grid = _prod(getattr(gm, "grid", ()) or (1,))
    body = eqn.params.get("jaxpr")
    inner_classes = _blank_classes()
    if body is not None and hasattr(body, "eqns"):
        _accumulate(body, 1, inner_classes, [], [])
    flops = sum(c["flops"] for c in inner_classes.values()) * grid * mult
    streamed = (sum(_aval_bytes(v) for v in eqn.invars)
                + sum(_aval_bytes(v) for v in eqn.outvars)) * mult
    vmem = 0
    for bm in getattr(gm, "block_mappings", ()) or ():
        shape = [d if isinstance(d, int) else 1
                 for d in getattr(bm, "block_shape", ())]
        sd = getattr(bm, "array_shape_dtype", None)
        vmem += _shape_dtype_bytes(shape, getattr(sd, "dtype", None))
    return {"kernel": label, "flops": flops, "bytes": streamed,
            "vmem_bytes": vmem, "grid": grid}


# -- collective wire model ------------------------------------------------------

# ring-algorithm wire bytes per device, as a multiple of the payload
_RING_FACTOR = {
    "all_reduce": lambda n: 2.0 * (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "collective_permute": lambda n: 1.0,
}


def collective_wire_bytes(kind: str, payload_bytes: int, n: int) -> float:
    """Wire bytes one device moves for one collective over ``n`` ranks
    under the ring algorithm (the bandwidth-optimal schedule both ICI
    tori and gloo rings implement)."""
    if n <= 1:
        return 0.0
    return float(payload_bytes) * _RING_FACTOR.get(
        kind, lambda _n: 1.0)(n)


def zero_collective_bytes(params_bytes: int, dp: int,
                          zero: int) -> list[dict]:
    """Analytic per-step collective schedule of the data-parallel
    gradient flow, for traces that carry no collective primitives (the
    GSPMD path only materializes them post-partitioning): zero=0
    all-reduces the full gradient; zero>=1 reduce-scatters the gradient
    and all-gathers the updated params."""
    if dp <= 1:
        return []
    if zero >= 1:
        return [{"kind": "reduce_scatter", "payload_bytes": params_bytes},
                {"kind": "all_gather", "payload_bytes": params_bytes}]
    return [{"kind": "all_reduce", "payload_bytes": params_bytes}]


# -- the report -----------------------------------------------------------------


def cost_report(fn_or_jaxpr=None, *args, profile: HwProfile | str = "auto",
                mesh=None, zero: int = 0, params_bytes: int = 0,
                lowered=None, compiled=None, axis: str = "data") -> dict:
    """Static roofline estimate of one step under ``profile``.

    ``fn_or_jaxpr``/``args`` drive the jaxpr walk (required);
    ``lowered`` (a ``jax.stages.Lowered``) refines the FLOP/byte totals
    with XLA's own per-signature ``cost_analysis()`` when the backend
    reports one (pass ``compiled`` too when the caller already compiled
    — the fallback then reuses it instead of compiling a second time);
    ``mesh``/``zero``/``params_bytes`` parameterize the collective
    model (``params_bytes`` feeds the analytic ZeRO schedule when the
    trace itself carries no collectives)."""
    from paddle_tpu.analysis.memory import _has_prim
    from paddle_tpu.analysis.program import jaxpr_of

    if isinstance(profile, str):
        profile = hw_profile(profile)
    mesh_obj = getattr(mesh, "mesh", mesh)
    dp = 1
    if mesh_obj is not None:
        dp = int(dict(mesh_obj.shape).get(axis, 1))

    jx = jaxpr_of(fn_or_jaxpr, *args)
    classes = _blank_classes()
    pallas: list[dict] = []
    collectives: list[dict] = []
    _accumulate(jx.jaxpr, 1, classes, pallas, collectives)

    # the GSPMD/jit lowering traces GLOBAL shapes; per-device work is
    # 1/dp of it.  The explicit shard_map lowering already traces
    # per-shard shapes (same rule as GL-P-MEM's activation walk).
    if dp > 1 and not _has_prim(jx.jaxpr, "shard_map"):
        for c in classes.values():
            c["flops"] //= dp
            c["bytes"] //= dp
        for p in pallas:
            p["flops"] //= dp
            p["bytes"] //= dp

    flops_total = (sum(c["flops"] for c in classes.values())
                   + sum(p["flops"] for p in pallas))
    bytes_total = (sum(c["bytes"] for c in classes.values())
                   + sum(p["bytes"] for p in pallas))
    flops_source = "jaxpr-walk"
    if lowered is not None or compiled is not None:
        xla = _xla_cost_totals(lowered, compiled)
        if xla and xla.get("flops", 0) > 0:
            scale_f = xla["flops"] / max(flops_total, 1)
            scale_b = (xla["bytes"] / max(bytes_total, 1)
                       if xla.get("bytes", 0) > 0 else 1.0)
            # keep the walk's class proportions, adopt XLA's totals
            # (XLA sees fusion the walk cannot; class split is ours)
            for c in classes.values():
                c["flops"] = int(c["flops"] * scale_f)
                c["bytes"] = int(c["bytes"] * scale_b)
            for p in pallas:
                p["flops"] = int(p["flops"] * scale_f)
                p["bytes"] = int(p["bytes"] * scale_b)
            flops_total = int(flops_total * scale_f)
            bytes_total = int(bytes_total * scale_b)
            flops_source = "xla-cost-analysis"

    peak = profile.peak_flops
    hbm_bw = profile.hbm_gbps * 1e9
    by_class = {}
    compute_s = 0.0
    for name in OP_CLASSES:
        c = classes[name]
        t_flops = c["flops"] / peak
        t_bytes = c["bytes"] / hbm_bw
        t = max(t_flops, t_bytes)
        compute_s += t
        by_class[name] = {
            "flops": c["flops"], "bytes": c["bytes"],
            "time_ms": t * 1e3,
            "bound": "memory" if t_bytes > t_flops else "compute"}
    for p in pallas:
        t = max(p["flops"] / peak, p["bytes"] / hbm_bw)
        p["time_ms"] = t * 1e3
        compute_s += t

    if not collectives:
        collectives = zero_collective_bytes(params_bytes, dp, zero)
    ici_bw = profile.ici_gbps * 1e9
    comm_s = 0.0
    for c in collectives:
        wire = collective_wire_bytes(c["kind"], c["payload_bytes"], dp)
        c["wire_bytes"] = wire
        c["time_ms"] = wire / ici_bw * 1e3
        comm_s += wire / ici_bw

    step_s = max(compute_s, comm_s)
    mfu_pct = (flops_total / (step_s * peak) * 100.0) if step_s > 0 else 0.0
    vmem_budget = profile.vmem_mb * 1e6
    spilled = [p for p in pallas if p["vmem_bytes"] > vmem_budget > 0]
    if spilled:
        worst = max(spilled, key=lambda p: p["vmem_bytes"])
        bottleneck = f"vmem-spill:{worst['kernel']}"
    elif comm_s > compute_s:
        bottleneck = "collective-bound"
    else:
        dominant = max(by_class.items(), key=lambda kv: kv[1]["time_ms"])
        bottleneck = (f"{dominant[1]['bound']}-bound:{dominant[0]}"
                      if dominant[1]["time_ms"] > 0 else "compute-bound")

    return {
        "profile": profile.name,
        "dp": dp, "zero": int(zero),
        "flops": flops_total, "hbm_bytes": bytes_total,
        "flops_source": flops_source,
        "by_class": by_class,
        "pallas": pallas,
        "collectives": collectives,
        "compute_ms": compute_s * 1e3,
        "comm_ms": comm_s * 1e3,
        "step_ms": step_s * 1e3,
        "overlap_headroom_ms": (compute_s - comm_s) * 1e3,
        "mfu_pct": mfu_pct,
        "bottleneck": bottleneck,
    }


def _xla_cost_totals(lowered, compiled=None) -> dict | None:
    """{"flops", "bytes"} from XLA's per-signature cost analysis, the
    same best-effort dance StepTelemetry.cost_for does: prefer the
    pre-compile estimate, fall back to the compiled one (reusing the
    caller's executable when given — never compile twice), normalize
    the older list-of-dict return shape."""
    from paddle_tpu.core import logger as log

    for getter in (lambda: lowered.cost_analysis(),
                   lambda: (compiled if compiled is not None
                            else lowered.compile()).cost_analysis()):
        try:
            ca = getter()
        except Exception as e:
            log.debug("xla cost_analysis unavailable (%s); "
                      "jaxpr-walk totals stand", e)
            continue
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            return {"flops": float(ca.get("flops", 0.0) or 0.0),
                    "bytes": float(ca.get("bytes accessed", 0.0) or 0.0)}
    return None


# -- the budget pass ------------------------------------------------------------


def cost_budget_pass(report: dict, name: str = "train_step", *,
                     mfu_floor: float = 0.0) -> list[Finding]:
    """GL-P-COST finding when the predicted MFU falls below
    ``--mfu_floor`` percent (0 = report only, no gate), naming the
    bottleneck the report identified so the failure is actionable."""
    findings: list[Finding] = []
    floor = float(mfu_floor)
    mfu = float(report.get("mfu_pct", 0.0))
    if floor > 0 and mfu < floor:
        bottleneck = report.get("bottleneck", "unknown")
        findings.append(Finding(
            "GL-P-COST", _pname(name), 0, "mfu-floor",
            f"predicted MFU {mfu:.1f}% under the {report.get('profile')} "
            f"profile falls below the --mfu_floor {floor:.1f}% "
            f"(predicted step {report.get('step_ms', 0.0):.2f} ms, "
            f"compute {report.get('compute_ms', 0.0):.2f} ms, comm "
            f"{report.get('comm_ms', 0.0):.2f} ms); bottleneck: "
            f"{bottleneck} — "
            + _remedy(bottleneck)))
    return finalize(findings)


def _remedy(bottleneck: str) -> str:
    if bottleneck.startswith("vmem-spill"):
        return ("shrink the kernel's block shapes or deepen its grid "
                "so the blocks fit VMEM")
    if bottleneck == "collective-bound":
        return ("grow per-device work (bigger batch/sequence), drop the "
                "zero mode, or shrink the data axis until compute "
                "covers the collectives")
    if bottleneck.startswith("memory-bound"):
        return ("fuse or widen the flagged op class (bigger matmul "
                "tiles, fused kernels) — it streams more HBM bytes "
                "than its FLOPs cover")
    return ("raise arithmetic intensity (bigger batch, fused kernels) "
            "or accept the floor does not fit this model")
