"""Jitted train/eval step builder — the compiled replacement of
``GradientMachine::forwardBackward`` + ``ParameterUpdater::update``.

One XLA program per (topology, optimizer, feed-shape bucket) does: forward,
backward (``jax.grad``), gradient all-reduce over the mesh ``data`` axis
(XLA inserts ICI collectives from the shardings — replacing
``MultiGradientMachine``'s software ring and the pserver round-trip of
``RemoteParameterUpdater``), optimizer update, and metric computation.  The
reference pipelines per-parameter updates with backward via UpdateCallback
(``TrainerInternal.cpp:99-111``); XLA's scheduler provides that overlap.

``zero`` lowers the weight update to the pserver's sharded-aggregation
form in-mesh (``parallel/zero.py``): 1 shards the optimizer state 1/n
over data-parallel ranks; 2 additionally replaces the gradient
all-reduce with reduce-scatter + sharded update + parameter all-gather
(ZeRO-2 / Xu et al.'s automatic weight-update sharding)."""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from paddle_tpu import compat
from paddle_tpu.config.topology import Topology
from paddle_tpu.layers.base import is_sequence, raw
from paddle_tpu.parallel.mesh import MeshContext
from paddle_tpu.telemetry.scopes import part, scoped


def _metric_parts(metric_specs, values) -> dict[str, tuple]:
    """Per-metric (numerator, denominator) pairs.  Splitting the ratio
    lets the ZeRO shard_map region psum both sides over the data axis —
    the sharded run's metrics are then EXACT, not a mean of per-shard
    means (which would mis-weight sequence masks)."""
    out = {}
    for kind, pred_name, label_name, tag in metric_specs:
        pred, label = values[pred_name], values[label_name]
        if kind == "classification_error":
            p, l = raw(pred), raw(label)
            if is_sequence(pred):
                mask = pred.mask()
                ids = jnp.argmax(p, axis=-1)
                err = (ids != raw(label)).astype(jnp.float32) * mask
                out["classification_error_evaluator"] = (
                    jnp.sum(err), jnp.sum(mask))
            else:
                ids = jnp.argmax(p, axis=-1)
                err = (ids != l.reshape(ids.shape)).astype(jnp.float32)
                out["classification_error_evaluator"] = (
                    jnp.sum(err), jnp.asarray(float(err.size), jnp.float32))
    return out


def _finalize_metrics(parts: dict[str, tuple]) -> dict[str, jax.Array]:
    return {k: num / jnp.maximum(den, 1.0)
            for k, (num, den) in parts.items()}


def _compute_metrics(metric_specs, values) -> dict[str, jax.Array]:
    return _finalize_metrics(_metric_parts(metric_specs, values))


def _cast_floats(tree, dtype):
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


def _cast_like(tree, ref):
    return jax.tree.map(
        lambda x, r: x.astype(r.dtype) if hasattr(r, "dtype") else x,
        tree, ref,
    )


def _batch_spec(x) -> P:
    """Batch-dim sharding spec of one feed leaf (mirrors
    ``MeshContext.data_sharding``)."""
    if hasattr(x, "ndim") and x.ndim >= 1:
        return P("data", *([None] * (x.ndim - 1)))
    return P()


def build_train_step(topology: Topology, optimizer,
                     mesh: MeshContext | None = None,
                     compute_dtype=None, fetch_layers=None,
                     zero: int | None = None, lowering: str = "auto"):
    """Returns jitted fn: (params, opt_state, states, feed, key)
    -> (params, opt_state, states, cost, metrics).

    ``compute_dtype=jnp.bfloat16`` enables mixed precision: forward/backward
    run in bf16 on the MXU while master parameters, optimizer state, and
    persistent states stay float32 (grads are upcast before the update).

    ``fetch_layers`` names layers whose batch values should ride along in
    the metrics dict (key ``"layer:<name>"``) — the declared-evaluator feed,
    computed by the SAME forward the update uses (same dropout draw, no
    extra pass).

    ``zero`` selects the weight-update sharding over the mesh ``data``
    axis (``parallel/zero.py``; None/0 = the replicated update):

    - ``1``: optimizer slots live 1/n-sharded (state memory /n); the
      gradient sync stays an all-reduce and updated parameters are
      all-gathered from the sharded deltas.
    - ``2``: the gradient all-reduce is REPLACED by reduce-scatter —
      each rank receives its 1/n gradient shard, applies the optimizer
      on its state shard, and updated parameters are all-gathered.

    On a pure-data mesh the zero>=2 gradient flow is lowered explicitly:
    forward/backward run per-shard inside ``shard_map`` and the sync goes
    through ``collective.reduce_scatter``/``all_gather``, so the
    telemetry census carries the real per-device payloads and the
    compiled program contains literal reduce-scatter ops on every
    backend.  On meshes with live TP/MoE axes the GSPMD lowering
    (sharding constraints, Xu et al.) is used instead — same math,
    partitioner-chosen collectives.

    On a TPU the per-shard forward/backward is used on a pure-data mesh
    at EVERY zero stage (zero 0/1 then all-reduce the gradients inside
    the region): the forward holds Mosaic kernels, and GSPMD refuses to
    partition those ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map").  Off-TPU the
    kernels run interpreted (plain jax ops), so zero 0/1 keep the GSPMD
    lowering there.  Dropout note: the explicit lowering
    folds the data-axis index into the step key (independent per-replica
    draws, like the reference's per-thread streams), so a stochastic
    model's trajectory differs from the replicated run's by the draw —
    deterministic models match to reduction-order tolerance.

    Mesh-mutability contract (elastic resharding): the returned step
    CAPTURES ``mesh`` and its data degree at build time — the shard_map
    region, the ZeRO specs, the 1/n gradient scale and the donated
    layouts are all frozen into the trace.  A runtime mesh change
    (``resilience/elastic.py``) must therefore discard the step and
    rebuild through this function (``SGD._ensure_built`` after nulling
    ``_train_step``), never re-invoke a stale one: jit would happily
    re-lower the old program onto arrays whose shardings name dead
    devices.  The per-signature cost analyses cached next to the step
    (``SGD._telemetry_costs``) freeze the same mesh and are invalidated
    together."""
    specs = {s.name: s for s in topology.param_specs()}
    trainable = {n for n, s in specs.items() if not s.is_static}
    metric_specs = topology.metrics()
    out_names = [o.name for o in topology.outputs]
    fetch_layers = list(fetch_layers or [])
    zero = int(zero or 0)
    # P() (not None) for unannotated params: a None entry is an empty
    # pytree to jax and would misalign spec lists in parallel/zero.py
    base_specs = {
        n: (P(*s.sharding) if getattr(s, "sharding", None) else P())
        for n, s in specs.items()}

    from paddle_tpu.parallel import zero as zero_mod

    dp = mesh.mesh.shape.get("data", 1) if mesh is not None else 1
    zero_on = zero >= 1 and mesh is not None and dp > 1
    # ``lowering`` pins how the data-parallel step is lowered: "auto"
    # (the production rule below), "explicit" (per-shard forward/backward
    # inside shard_map; with zero>=2 also literal reduce-scatter +
    # all-gather), or "gspmd" (sharding constraints, partitioner-chosen
    # collectives).  The preflight collective-sequence check
    # (paddle_tpu/analysis) builds BOTH and compares them — the
    # multi-host deadlock class is exactly a fleet whose hosts resolve
    # "auto" differently.
    if lowering not in ("auto", "explicit", "gspmd"):
        raise ValueError(f"lowering must be auto|explicit|gspmd, "
                         f"got {lowering!r}")
    pure_data = (mesh is not None
                 and zero_mod.explicit_lowering_ok(mesh.mesh))
    if lowering == "explicit" and dp > 1 and not pure_data:
        raise ValueError("explicit lowering requested but the mesh "
                         "has live non-data axes")
    from paddle_tpu.ops.pallas import on_tpu

    # auto: ZeRO-2 always takes the explicit flow on a pure-data mesh; on
    # a TPU so does every other stage, because GSPMD cannot partition the
    # Mosaic kernels in the forward (see the docstring)
    explicit_fwd = pure_data and (
        lowering == "explicit"
        or (lowering == "auto" and (zero >= 2 or on_tpu())))
    explicit = explicit_fwd and zero >= 2  # + the explicit ZeRO-2 flow
    # TPP fused shard update (ops/pallas/tpp/update): under the explicit
    # ZeRO-2 lowering with the fused_kernels flag on, the SGD/momentum
    # update runs as one read-modify-write pass inside a shard_map region
    # on exactly the 1/n gradient shard the reduce-scatter produced
    from paddle_tpu.ops.pallas import tpp as tpp_mod

    fused_update = explicit and tpp_mod.fused_enabled()

    def run_forward(tp, static_c, states, feed_c, key):
        """(cost, new_states, metric parts, fetch values, grads) on the
        batch visible to this trace (global under jit, the local shard
        under shard_map)."""
        def loss_fn(tp):
            if compute_dtype is not None:
                tp = _cast_floats(tp, compute_dtype)
            allp = {**static_c, **tp}
            values, new_states = topology.forward(
                allp, states, feed_c, True, key)
            with part("loss"):
                cost = functools.reduce(
                    lambda a, b: a + b,
                    [jnp.sum(values[n], dtype=jnp.float32)
                     for n in out_names])
                parts = _metric_parts(metric_specs, values)
            fetch = {f"layer:{n}": jax.lax.stop_gradient(values[n])
                     for n in fetch_layers if n in values}
            return cost, (new_states, parts, fetch)

        # grads arrive f32 already (cotangent of the bf16 cast upcasts)
        (cost, (new_states, parts, fetch)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(tp)
        return cost, new_states, parts, fetch, grads

    @scoped("update")
    def apply_update(grads, train_p, opt_state, gspecs):
        """Optimizer update (+ ZeRO constraints); returns
        (new_train, new_opt) with new_train back at its base layout."""
        fused = None
        if fused_update:
            fused = tpp_mod.fused_shard_apply(
                optimizer, grads, train_p, opt_state, specs, mesh.mesh,
                gspecs)
        if fused is not None:
            new_train, new_opt = fused
        else:
            new_train, new_opt = optimizer.apply(grads, train_p, opt_state,
                                                 specs)
        if zero_on:
            sspecs = zero_mod.state_specs(
                new_opt, {**train_p}, mesh.mesh,
                param_specs={n: base_specs[n] for n in train_p})
            new_opt = zero_mod.constrain_opt_state(new_opt, sspecs,
                                                   mesh.mesh)
            if explicit:
                new_train = zero_mod.gather_params(new_train, gspecs,
                                                   mesh.mesh)
            else:
                new_train = zero_mod.constrain_params(
                    new_train, mesh.mesh,
                    param_specs={n: base_specs[n] for n in train_p},
                    zero_specs=gspecs if zero >= 2 else None)
        return new_train, new_opt

    def step(params, opt_state, states, feed, key):
        train_p = {k: v for k, v in params.items() if k in trainable}
        static_p = {k: v for k, v in params.items() if k not in trainable}
        if compute_dtype is not None:
            feed_c = _cast_floats(feed, compute_dtype)
            static_c = _cast_floats(static_p, compute_dtype)
        else:
            feed_c, static_c = feed, static_p
        # persistent states (BN running stats) stay f32: batch_norm upcasts
        # internally, and a bf16 EMA accumulator would re-quantize each step

        gspecs = (zero_mod.grad_specs(
            train_p, mesh.mesh,
            param_specs={n: base_specs[n] for n in train_p})
            if zero_on else None)

        if explicit_fwd:
            from paddle_tpu.parallel import collective

            def local_step(tp, static_c, states, feed_c, key):
                # independent per-replica RNG stream (the reference's
                # per-thread dropout draws, MultiGradientMachine)
                key = jax.random.fold_in(key, lax.axis_index("data"))
                cost, new_states, parts, fetch, grads = run_forward(
                    tp, static_c, states, feed_c, key)
                # cost layers reduce to batch-MEAN scalars (layers/api
                # _mean_over_batch), so the global cost is the pmean of
                # equal-shard local means and the global gradient the
                # 1/n-scaled sum — exact for dense costs; a masked
                # sequence cost weights each replica equally instead of
                # each timestep (the reference's multi-trainer
                # averaging did the same).  Metric num/den parts are
                # psummed separately, so METRICS stay exact either way.
                # Scalar reductions use raw lax — accounting noise kept
                # out of the census; the census IS the gradient flow.
                cost = lax.pmean(cost, "data")
                parts = jax.tree.map(lambda x: lax.psum(x, "data"), parts)
                new_states = jax.tree.map(lambda x: lax.pmean(x, "data"),
                                          new_states)
                grads = jax.tree.map(lambda g: g / dp, grads)
                if explicit:
                    grads = zero_mod.sync_grads(grads, gspecs)
                else:  # zero 0/1: every rank keeps the whole gradient
                    grads = jax.tree.map(
                        lambda g: collective.all_reduce(g, "data"), grads)
                return cost, new_states, parts, fetch, grads

            # output STRUCTURE (metric keys, fetch leaves, state shapes)
            # comes from an abstract eval of the collective-free forward
            out_sh = jax.eval_shape(run_forward, train_p, static_c,
                                    states, feed_c, key)
            out_specs = (
                P(),                                        # cost
                jax.tree.map(lambda _: P(), out_sh[1]),     # new_states
                jax.tree.map(lambda _: P(), out_sh[2]),     # metric parts
                jax.tree.map(_batch_spec, out_sh[3]),       # fetch values
                (gspecs if explicit                         # synced grads
                 else jax.tree.map(lambda _: P(), out_sh[4])),
            )
            region = compat.shard_map(
                local_step, mesh=mesh.mesh,
                in_specs=(
                    jax.tree.map(lambda _: P(), train_p),
                    jax.tree.map(lambda _: P(), static_c),
                    jax.tree.map(lambda _: P(), states),
                    jax.tree.map(_batch_spec, feed_c),
                    P(),
                ),
                out_specs=out_specs,
                check_vma=False)
            cost, new_states, parts, fetch, grads = region(
                train_p, static_c, states, feed_c, key)
            metrics = _finalize_metrics(parts)
            metrics.update(fetch)
        else:
            cost, new_states, parts, fetch, grads = run_forward(
                train_p, static_c, states, feed_c, key)
            metrics = _finalize_metrics(parts)
            metrics.update(fetch)
            if zero_on and zero >= 2:
                grads = zero_mod.constrain_grads(grads, gspecs, mesh.mesh)

        if compute_dtype is not None:
            new_states = _cast_like(new_states, states)
        new_train, new_opt = apply_update(grads, train_p, opt_state, gspecs)
        new_params = {**static_p, **new_train}
        return new_params, new_opt, new_states, cost, metrics

    donate = (0, 1, 2)
    if mesh is not None:
        with mesh.mesh:
            return jax.jit(step, donate_argnums=donate)
    return jax.jit(step, donate_argnums=donate)


def build_eval_step(topology: Topology, mesh: MeshContext | None = None):
    """Jitted test/inference forward: (params, states, feed) -> (values of
    outputs, cost scalar, metrics) with is_train=False."""
    metric_specs = topology.metrics()
    out_names = [o.name for o in topology.outputs]

    def step(params, states, feed):
        values, _ = topology.forward(params, states, feed, False, jax.random.key(0))
        cost = functools.reduce(
            lambda a, b: a + b, [jnp.sum(values[n]) for n in out_names]
        )
        metrics = _compute_metrics(metric_specs, values)
        return {n: values[n] for n in values}, cost, metrics

    return jax.jit(step)


def build_tap_grads(topology: Topology, tap_names: list[str],
                    is_train: bool = True):
    """Jitted (params, states, feed, key) -> {layer: d(cost)/d(layer)} —
    the gradient_printer_evaluator's data source (≅ the reference printing
    ``input.grad`` during backward, Evaluator.cpp:1091) via zero-valued
    output taps (Topology.forward ``taps``).  ``is_train`` selects the
    train or eval forward (dropout on/off) to match the pass being
    printed."""
    out_names = [o.name for o in topology.outputs]

    def grads(params, states, feed, key):
        values, _ = topology.forward(params, states, feed, is_train, key)
        taps0 = {n: jnp.zeros_like(raw(values[n])) for n in tap_names}

        def cost_of(taps):
            vals, _ = topology.forward(params, states, feed, is_train, key,
                                       taps=taps)
            return functools.reduce(
                lambda a, b: a + b,
                [jnp.sum(vals[n], dtype=jnp.float32) for n in out_names])

        return jax.grad(cost_of)(taps0)

    return jax.jit(grads)


def build_forward(topology: Topology, output_names: list[str]):
    """Inference forward returning selected layer values."""

    def fwd(params, states, feed):
        values, _ = topology.forward(params, states, feed, False, jax.random.key(0))
        return [values[n] for n in output_names]

    return jax.jit(fwd)
