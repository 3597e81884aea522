"""GL-P-MEM — static per-device memory accounting for a built step.

The reference's ``config_parser.py`` rejected configs whose layer sizes
could not fit the configured capacity before a single kernel ran; the
weight-update-sharding analysis of arxiv 2004.13336 reasons about
exactly the same artifact — a per-device byte count of params, optimizer
state and activations under the active sharding.  This module computes
that artifact statically, from nothing but the model/optimizer pytrees,
the mesh, the active ``zero`` mode and the step's jaxpr:

- **params**: replicated per device by default; a parameter whose base
  spec names live mesh axes — the row-sharded embedding tables,
  ``sharding=("model", None)`` — costs ``bytes/degree``
  (:func:`params_bytes_per_device`; ZeRO-3 parameter sharding extends
  the same accounting);
- **optimizer slots**: at ``zero=0``, full bytes except same-shape slots
  of base-sharded params (``zeros_like`` slots inherit the table's
  placement, so sparse momentum shards with its table); at ``zero>=1``
  the :func:`paddle_tpu.parallel.zero.state_specs` layout — leaves cost
  ``bytes/placement-degree`` (the data axis composed with any preserved
  base TP axes), indivisible leaves stay full.  This mirrors device
  placement exactly, so the static number agrees with the runtime census
  (:func:`paddle_tpu.parallel.zero.state_bytes_per_device`) to dtype
  rounding;
- **activations**: a liveness walk over the jaxpr — intermediates are
  allocated at their defining equation and freed after their last use;
  the peak of the live set is the activation working set.  When the
  step was compiled, XLA's own ``memory_analysis()`` temp size is
  preferred (it sees donation/aliasing the walk cannot);
- **pallas VMEM**: per-``pallas_call`` footprint from the static block
  shapes of its ``GridMapping`` — a kernel whose blocks exceed the VMEM
  budget fails preflight instead of failing to fit at compile time.

:func:`memory_report` returns the accounting dict (attached to the
``preflight`` telemetry record, schema ``paddle_tpu.metrics/10``);
:func:`memory_budget_pass` turns it into GL-P-MEM findings against an
``--hbm_gb`` / ``--vmem_mb`` budget.
"""

from __future__ import annotations

from paddle_tpu.analysis.core import Finding, finalize


def _pname(name: str) -> str:
    return f"<program:{name}>"


# -- byte accounting primitives -------------------------------------------------


def _shape_dtype_bytes(shape, dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    try:
        itemsize = int(dtype.itemsize)
    except (AttributeError, TypeError):
        itemsize = 4  # extended dtypes (PRNG keys): negligible either way
    return n * itemsize


def _leaf_bytes(x) -> int:
    return _shape_dtype_bytes(getattr(x, "shape", ()),
                              getattr(x, "dtype", None))


def tree_bytes(tree) -> int:
    import jax

    return sum(_leaf_bytes(leaf) for leaf in jax.tree.leaves(tree))


def _spec_degree(spec, mesh_sizes: dict) -> int:
    """How many ways a leaf with base sharding ``spec`` splits across the
    mesh: the product of the named axes' sizes (axes absent from the mesh
    count 1).  Accepts a PartitionSpec or a raw tuple like
    ``("model", None)``; None/() means replicated."""
    if spec is None:
        return 1
    deg = 1
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        for a in names:
            if a is not None:
                deg *= int(mesh_sizes.get(a, 1))
    return max(deg, 1)


def params_bytes_per_device(params, mesh, param_specs=None) -> int:
    """Static per-device parameter residency: replicated by default, but a
    parameter whose base spec names live mesh axes — a row-sharded
    embedding table carrying ``sharding=("model", None)`` — costs
    ``bytes/degree``, matching what device placement does (the sharded-
    table extension of the GL-P-MEM byte model)."""
    import jax
    from jax.sharding import PartitionSpec as P

    if mesh is None or param_specs is None:
        return tree_bytes(params)
    sizes = dict(mesh.shape)
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(param_specs,
                                  is_leaf=lambda x: isinstance(x, P))
    if len(spec_leaves) != len(leaves):  # layout surprise: stay safe
        return tree_bytes(params)
    return sum(-(-_leaf_bytes(l) // _spec_degree(s, sizes))
               for l, s in zip(leaves, spec_leaves))


def opt_state_bytes_per_device(opt_state, params, mesh, zero: int,
                               param_specs=None, axis: str = "data") -> int:
    """Static per-device optimizer-state residency under ``zero``.

    At ``zero>=1`` with a live data axis every slot leaf costs
    ``bytes/dp`` when :func:`~paddle_tpu.parallel.zero.state_specs`
    shards it and full bytes when it stays replicated — the same
    decision device placement makes, so this agrees with the runtime
    census (``zero.state_bytes_per_device``) to dtype rounding."""
    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import zero as zero_mod

    dp = 1
    sizes = {}
    if mesh is not None:
        sizes = dict(mesh.shape)
        dp = int(sizes.get(axis, 1))
    if not (zero >= 1 and dp > 1):
        # zero off: the data axis doesn't shard slots, but base TP axes
        # still do — zeros_like slots inherit their parameter's placement,
        # so a row-sharded embedding table keeps its momentum on the shard
        if mesh is None or param_specs is None:
            return tree_bytes(opt_state)
        slots = (opt_state.get("slots")
                 if isinstance(opt_state, dict) else None)
        if not (isinstance(slots, dict) and isinstance(params, dict)
                and isinstance(param_specs, dict)):
            return tree_bytes(opt_state)
        total = tree_bytes(
            {k: v for k, v in opt_state.items() if k != "slots"})
        for nm, sl in slots.items():
            p_shape = tuple(getattr(params.get(nm), "shape", ()))
            base = param_specs.get(nm)
            for leaf in jax.tree.leaves(sl):
                b = _leaf_bytes(leaf)
                if tuple(getattr(leaf, "shape", ())) == p_shape:
                    b = -(-b // _spec_degree(base, sizes))
                total += b
        return total
    specs = zero_mod.state_specs(opt_state, params, mesh, axis=axis,
                                 param_specs=param_specs)
    leaves = jax.tree.leaves(opt_state)
    # P subclasses tuple, so an empty P() would vanish from a plain
    # pytree flatten and misalign the whole list — flatten with is_leaf
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    if len(spec_leaves) != len(leaves):  # layout surprise: stay safe
        return tree_bytes(opt_state)
    total = 0
    for leaf, spec in zip(leaves, spec_leaves):
        b = _leaf_bytes(leaf)
        if isinstance(spec, P):
            # the data axis (ZeRO) composes with any base TP axes the
            # state spec preserved — divide by the full placement degree
            total += b // max(_spec_degree(spec, sizes), 1)
        else:
            total += b
    return total


# -- activation liveness over the jaxpr -----------------------------------------


def _inner_jaxprs(eqn):
    from paddle_tpu.analysis.program import inner_jaxprs

    return inner_jaxprs(eqn)


def _is_var(v) -> bool:
    # Literals carry .val; Vars (incl. DropVar) don't
    return hasattr(v, "aval") and not hasattr(v, "val")


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None:
        return 0
    return _shape_dtype_bytes(getattr(aval, "shape", ()),
                              getattr(aval, "dtype", None))


def _peak_live_bytes(jx) -> int:
    """Peak bytes of equation-defined intermediates live at once: each
    outvar is allocated at its defining eqn and freed after its last
    use; nested jaxprs contribute their own peak while their caller's
    operands are still live."""
    last_use: dict = {}
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    for v in jx.outvars:
        if _is_var(v):
            last_use[v] = len(jx.eqns)
    # per-equation free list, so the walk stays O(total vars)
    free_at: dict[int, list] = {}
    live = 0
    peak = 0
    for i, eqn in enumerate(jx.eqns):
        inner = 0
        for sub in _inner_jaxprs(eqn):
            inner = max(inner, _peak_live_bytes(sub))
        out_b = 0
        for v in eqn.outvars:
            b = _aval_bytes(v)
            out_b += b
            free_at.setdefault(last_use.get(v, i), []).append(b)
        peak = max(peak, live + out_b + inner)
        live += out_b
        live -= sum(free_at.pop(i, ()))
    return peak


def activation_peak_bytes(fn_or_jaxpr, *args) -> int:
    """Liveness-walk peak of the program's intermediates.  A jitted fn
    traces to one ``pjit`` wrapper; the walk descends into it (the
    wrapper's outvars — the updated params/opt-state — are the update's
    double-buffer, which donation elides; they are accounted by the
    params/opt columns, not here)."""
    from paddle_tpu.analysis.program import jaxpr_of

    jx = jaxpr_of(fn_or_jaxpr, *args).jaxpr
    while len(jx.eqns) == 1 and jx.eqns[0].primitive.name in (
            "pjit", "closed_call", "core_call"):
        inner = next(_inner_jaxprs(jx.eqns[0]), None)
        if inner is None:
            break
        jx = inner
    return _peak_live_bytes(jx)


def _has_prim(jx, name: str) -> bool:
    from paddle_tpu.analysis.program import _walk_eqns

    return any(e.primitive.name == name for e in _walk_eqns(jx))


# -- pallas VMEM footprints -----------------------------------------------------


def pallas_vmem_estimates(fn_or_jaxpr, *args) -> list[tuple[str, int]]:
    """(kernel name, VMEM bytes) per ``pallas_call`` in the program —
    the sum of its static block shapes (one resident block per operand/
    result, the Pallas pipelining model's per-step footprint)."""
    from paddle_tpu.analysis.program import _walk_eqns, jaxpr_of

    jx = jaxpr_of(fn_or_jaxpr, *args).jaxpr
    out = []
    for eqn in _walk_eqns(jx):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        # the kernel function's name: pallas_call(name=...) when given,
        # else the kernel jaxpr's debug info (jax 0.9)
        debug = getattr(eqn.params.get("jaxpr"), "debug_info", None)
        label = (eqn.params.get("name")
                 or getattr(debug, "func_name", None) or "pallas_call")
        total = 0
        for bm in getattr(gm, "block_mappings", ()) or ():
            # jax 0.9 block dims are Blocked/Element objects carrying
            # block_size; squeezed dims carry none and count as 1
            shape = [d if isinstance(d, int) else getattr(d, "block_size", 1)
                     for d in getattr(bm, "block_shape", ())]
            sd = getattr(bm, "array_shape_dtype", None)
            total += _shape_dtype_bytes(shape, getattr(sd, "dtype", None))
        if total == 0:  # no grid mapping exposed: whole operands resident
            total = sum(_aval_bytes(v) for v in eqn.invars) + \
                sum(_aval_bytes(v) for v in eqn.outvars)
        out.append((label, total))
    return out


# -- the report and the budget pass ---------------------------------------------


def memory_report(params, opt_state, states, feed, mesh=None, *,
                  zero: int = 0, param_specs=None, step=None, args=None,
                  compiled=None, axis: str = "data") -> dict:
    """Static per-device memory accounting of the built step.

    ``step``/``args`` enable the activation walk and the pallas VMEM
    estimates (skipped when absent); ``compiled`` (a
    ``jax.stages.Compiled``) refines activations with XLA's own
    ``memory_analysis()`` temp size when the backend reports one."""
    mesh_obj = getattr(mesh, "mesh", mesh)  # MeshContext or jax Mesh
    dp = 1
    if mesh_obj is not None:
        dp = int(dict(mesh_obj.shape).get(axis, 1))
    report = {
        "dp": dp, "zero": int(zero),
        "params_bytes": params_bytes_per_device(params, mesh_obj,
                                                param_specs),
        "opt_state_bytes": opt_state_bytes_per_device(
            opt_state, params, mesh_obj, zero, param_specs=param_specs,
            axis=axis),
        "states_bytes": tree_bytes(states),
        "feed_bytes": tree_bytes(feed) // dp,
        "activation_bytes": 0,
        "activation_source": "none",
        "pallas_vmem": [],
    }
    if step is not None and args is not None:
        from paddle_tpu.analysis.program import jaxpr_of

        jx = jaxpr_of(step, *args)
        walk = activation_peak_bytes(jx)
        # the GSPMD/jit lowering traces GLOBAL shapes (activations are
        # batch-sharded onto the data axis at runtime); the explicit
        # shard_map lowering already traces per-shard shapes inside the
        # region, so only the former is scaled down
        if dp > 1 and not _has_prim(jx.jaxpr, "shard_map"):
            walk //= dp
        report["activation_bytes"] = walk
        report["activation_source"] = "jaxpr-liveness"
        report["pallas_vmem"] = [
            {"kernel": k, "bytes": b}
            for k, b in pallas_vmem_estimates(jx)]
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
        except Exception as e:  # backend without the API: walk stands
            from paddle_tpu.core import logger as log

            log.debug("memory_analysis unavailable (%s); jaxpr-liveness "
                      "estimate stands", e)
            ma = None
        temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        if temp > 0:
            report["activation_bytes"] = temp
            report["activation_source"] = "xla-memory-analysis"
    report["total_bytes"] = (report["params_bytes"]
                             + report["opt_state_bytes"]
                             + report["states_bytes"]
                             + report["feed_bytes"]
                             + report["activation_bytes"])
    return report


def serving_memory_report(cfg, serving, params=None, cache=None) -> dict:
    """Static per-device byte accounting of the SERVING path: the paged
    KV pool (k AND v, each ``paged_attention.kv_pool_shape`` at the model
    dtype: ``cache layers × kv_heads × pages × page_size × head_dim``, the
    heads rounded up to whole lane groups; cache layers = ``num_layers ×
    loop_steps``: a looped stack keeps one cache per pass; under a layer
    pattern its "*" layers — a cross layer reads one of those and holds
    nothing), the RINGS of a pattern's window layers (k AND v, the same
    layout: ``window layers × kv_heads × (1 + max_slots × window /
    page_size) pages``, bounded whatever the contexts:
    ``window_pool_bytes``) and the float32 state pools
    (``cfg.state_parts``: ``max_slots`` rows a layer that keeps the part,
    whatever kind of layer that is) next to the
    servable params — the same artifact :func:`memory_report` computes for training, so an
    oversized pool is a preflight failure, not an OOM at the first
    admission.  ``cfg`` is a TransformerConfig, ``serving`` a
    ``ServingConfig``; ``params`` (optional pytree) adds the weights.

    ``cache`` (optional, a live :class:`PagedKVCache`) adds the RUNTIME
    occupancy view the refcounted allocator makes non-trivial: with
    prefix caching on, mapped pages overcount residency (shared pages
    appear in many page tables), so the byte figures below are
    unique-resident — each physical page counted once regardless of how
    many sequences or cache entries reference it."""
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import kv_pool_shape

    per_pool = int(np.prod(kv_pool_shape(
        cfg.cache_layers, cfg.kv_heads, serving.num_pages,
        serving.page_size, cfg.head_dim))) * int(np.dtype(cfg.dtype).itemsize)
    kv = 2 * per_pool  # k and v pools
    window = 0
    if getattr(cfg, "window_layers", 0):
        from paddle_tpu.ops.pallas.paged_attention import window_pool_pages

        window = 2 * int(np.prod(kv_pool_shape(
            cfg.window_layers, cfg.kv_heads, window_pool_pages(
                cfg.attn_window, serving.page_size, serving.max_slots),
            serving.page_size, cfg.head_dim))) * int(
                np.dtype(cfg.dtype).itemsize)
    state = 4 * int(serving.max_slots) * sum(
        layers * int(np.prod(shape))
        for layers, shape in cfg.state_parts.values())
    p_bytes = tree_bytes(params) if params is not None else 0
    report = {
        "kv_pool_bytes": kv,
        "state_pool_bytes": state,
        "window_pool_bytes": window,
        "params_bytes": p_bytes,
        "num_pages": int(serving.num_pages),
        "page_size": int(serving.page_size),
        "dtype": np.dtype(cfg.dtype).name,
        "total_bytes": kv + state + window + p_bytes,
    }
    if cache is not None:
        page_bytes = kv // max(int(serving.num_pages), 1)
        res = cache.resident_report()
        report.update(res)
        report["page_bytes"] = page_bytes
        report["unique_resident_bytes"] = res["unique_pages"] * page_bytes
        report["shared_saved_bytes"] = (
            res["shared_saved_pages"] * page_bytes)
    return report


def serving_budget_pass(report: dict, name: str = "serving", *,
                        hbm_gb: float = 0.0) -> list[Finding]:
    """GL-P-MEM finding when the KV pool + params exceed ``--hbm_gb``
    (0 = report only) — sized per :func:`serving_memory_report`."""
    findings: list[Finding] = []
    budget = float(hbm_gb) * 1e9
    total = report.get("total_bytes", 0)
    if budget > 0 and total > budget:
        findings.append(Finding(
            "GL-P-MEM", _pname(name), 0, "kv-pool-budget",
            f"static serving footprint {total / 1e9:.3f} GB (KV pool "
            f"{report.get('kv_pool_bytes', 0) / 1e9:.3f} GB at "
            f"{report.get('num_pages', 0)} pages × "
            f"{report.get('page_size', 0)} tokens, params "
            f"{report.get('params_bytes', 0) / 1e9:.3f} GB) exceeds the "
            f"--hbm_gb budget {float(hbm_gb):.3f} GB — shrink num_pages/"
            f"page_size or the resident model before the pool OOMs at "
            f"first admission"))
    return finalize(findings)


def memory_budget_pass(report: dict, name: str = "train_step", *,
                       hbm_gb: float = 0.0,
                       vmem_mb: float = 128.0) -> list[Finding]:
    """GL-P-MEM findings from a :func:`memory_report`:

    - ``hbm-budget`` when the per-device total exceeds ``hbm_gb``
      (0 = report only, no HBM gate);
    - ``vmem:<kernel>`` per ``pallas_call`` whose static block
      footprint exceeds ``vmem_mb`` (0 disables the VMEM gate).
    """
    findings: list[Finding] = []
    budget = float(hbm_gb) * 1e9
    total = report.get("total_bytes", 0)
    if budget > 0 and total > budget:
        parts = ", ".join(
            f"{k.replace('_bytes', '')} {report.get(k, 0) / 1e6:.1f}"
            for k in ("params_bytes", "opt_state_bytes", "states_bytes",
                      "feed_bytes", "activation_bytes"))
        findings.append(Finding(
            "GL-P-MEM", _pname(name), 0, "hbm-budget",
            f"static per-device peak {total / 1e9:.3f} GB exceeds the "
            f"--hbm_gb budget {float(hbm_gb):.3f} GB at zero="
            f"{report.get('zero', 0)} dp={report.get('dp', 1)} "
            f"(MB: {parts}) — raise zero mode, shrink the batch, or "
            f"shard the model before this config OOMs on hardware"))
    vbudget = float(vmem_mb) * 1e6
    if vbudget > 0:
        for rec in report.get("pallas_vmem", ()):
            if rec["bytes"] > vbudget:
                findings.append(Finding(
                    "GL-P-MEM", _pname(name), 0, f"vmem:{rec['kernel']}",
                    f"pallas kernel `{rec['kernel']}` needs "
                    f"{rec['bytes'] / 1e6:.1f} MB of VMEM-resident "
                    f"blocks (> {float(vmem_mb):.0f} MB budget) — the "
                    f"kernel will not fit; shrink its block shapes or "
                    f"deepen the grid"))
    return finalize(findings)
