"""Fused SGD/momentum weight update — applied in place on the ZeRO-2
optimizer shard.

The XLA update for momentum-SGD is a chain of small elementwise ops
(decay-add, velocity scale-add, delta scale, subtract), each a separate
HBM round-trip over the parameter/velocity buffers.  This kernel does
the whole rule in ONE read-modify-write pass — read p/g/v once, write
p'/v' once, with ``input_output_aliases`` donating the p/v buffers so
the update is genuinely in place.

Under the explicit ZeRO-2 lowering (``trainer/step.py``), the update
runs INSIDE a ``shard_map`` region over the ``data`` axis on exactly the
1/n gradient shard the reduce-scatter produced and the 1/n state shard
ZeRO-1 placed — the weight-update-sharding design of Xu et al. (arxiv
2004.13336) with the update itself fused (:func:`fused_shard_apply`).

The ``*_reference`` twins replicate ``optimizer.Optimizer.apply``'s math
op for op (f32 gradient upcast, decay fold, velocity update, delta
subtract), so the CPU path is bit-identical to the unfused trainer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import resolve_impl, resolve_interpret, round_up

_LANES = 128


def fused_momentum_update_reference(p, g, v, lr, mu, nesterov=False,
                                    weight_decay=0.0):
    """jnp twin of ``Momentum.tensor_update`` (+ the apply()-level decay
    fold): v' = mu*v + g ; p' = p - lr*(g + mu*v') [nesterov] or
    p - lr*v'.  ``weight_decay`` is a python float (the spec-level L2
    coefficient), folded into the gradient exactly as ``apply`` does."""
    g32 = g.astype(jnp.float32)
    if weight_decay:
        g32 = g32 + weight_decay * p
    v_new = mu * v + g32
    delta = lr * (g32 + mu * v_new) if nesterov else lr * v_new
    return (p - delta).astype(p.dtype), v_new.astype(v.dtype)


def fused_sgd_update_reference(p, g, lr, weight_decay=0.0):
    """jnp twin of plain ``SGD.tensor_update``: p' = p - lr*g."""
    g32 = g.astype(jnp.float32)
    if weight_decay:
        g32 = g32 + weight_decay * p
    return (p - lr * g32).astype(p.dtype)


def _pad2d(x, block_rows):
    """Flatten to [rows, 128] lanes for the elementwise kernels, padded
    only to the lane width and the (size-clamped) row-block multiple —
    small leaves (BN scale/bias) pad to one 128-lane row, not a full
    block_rows*128 tile."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = max(-(-n // _LANES), 1)
    bm = min(rows, block_rows)
    npad = round_up(rows, bm) * _LANES
    if npad != n:
        flat = jnp.pad(flat, (0, npad - n))
    return flat.reshape(-1, _LANES), n


def _unpad(x2, n, shape, dtype):
    return x2.reshape(-1)[:n].reshape(shape).astype(dtype)


def _mom_kernel(lr_ref, mu_ref, p_ref, g_ref, v_ref, po_ref, vo_ref, *,
                nesterov, weight_decay):
    lr = lr_ref[0, 0]
    mu = mu_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    v = mu * v_ref[...].astype(jnp.float32) + g
    delta = lr * (g + mu * v) if nesterov else lr * v
    po_ref[...] = (p - delta).astype(po_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def _sgd_kernel(lr_ref, p_ref, g_ref, po_ref, *, weight_decay):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    po_ref[...] = (p - lr * g).astype(po_ref.dtype)


_BLOCK_ROWS = 512


def _scalar(x):
    return jnp.asarray(x, jnp.float32).reshape(1, 1)


def fused_momentum_update(p, g, v, lr, mu, nesterov=False, weight_decay=0.0,
                          impl="auto", interpret=None):
    """One-pass momentum update; returns (p', v') with p/v donated in
    place on the kernel path."""
    if resolve_impl(impl, "fused_momentum_update") == "reference":
        return fused_momentum_update_reference(
            p, g, v, lr, mu, nesterov=nesterov, weight_decay=weight_decay)
    interpret = resolve_interpret(interpret)
    p2, n = _pad2d(p, _BLOCK_ROWS)
    g2, _ = _pad2d(g, _BLOCK_ROWS)
    v2, _ = _pad2d(v, _BLOCK_ROWS)
    rows = p2.shape[0]
    bm = min(rows, _BLOCK_ROWS)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((bm, _LANES), lambda i: (i, 0))
    po, vo = pl.pallas_call(
        functools.partial(_mom_kernel, nesterov=nesterov,
                          weight_decay=float(weight_decay)),
        grid=(rows // bm,),
        in_specs=[scalar_spec, scalar_spec, blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        input_output_aliases={2: 0, 4: 1},  # p and v update in place
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(_scalar(lr), _scalar(mu), p2, g2, v2)
    return _unpad(po, n, p.shape, p.dtype), _unpad(vo, n, v.shape, v.dtype)


def fused_sgd_update(p, g, lr, weight_decay=0.0, impl="auto",
                     interpret=None):
    """One-pass plain-SGD update; returns p' with p donated in place on
    the kernel path."""
    if resolve_impl(impl, "fused_sgd_update") == "reference":
        return fused_sgd_update_reference(p, g, lr,
                                          weight_decay=weight_decay)
    interpret = resolve_interpret(interpret)
    p2, n = _pad2d(p, _BLOCK_ROWS)
    g2, _ = _pad2d(g, _BLOCK_ROWS)
    rows = p2.shape[0]
    bm = min(rows, _BLOCK_ROWS)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((bm, _LANES), lambda i: (i, 0))
    po = pl.pallas_call(
        functools.partial(_sgd_kernel, weight_decay=float(weight_decay)),
        grid=(rows // bm,),
        in_specs=[scalar_spec, blk, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(p2.shape, p.dtype),
        input_output_aliases={1: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(_scalar(lr), p2, g2)
    return _unpad(po, n, p.shape, p.dtype)


# -- the ZeRO-2 sharded fused apply -------------------------------------------


def fused_apply_eligible(optimizer, state, specs, names) -> bool:
    """True when ``fused_shard_apply`` reproduces ``optimizer.apply``
    exactly: plain SGD/Momentum, dict slot layout, no model average, no
    L1, no global/per-param clipping, no sparsity masks."""
    from paddle_tpu import optimizer as opt_mod

    if type(optimizer) not in (opt_mod.SGD, opt_mod.Momentum):
        return False
    if optimizer.l1_rate or optimizer.gradient_clipping_threshold:
        return False
    if "avg" in state or not isinstance(state.get("slots"), dict):
        return False
    for n in names:
        spec = specs.get(n)
        if spec is None:
            continue
        if spec.gradient_clipping_threshold or spec.sparsity_ratio:
            return False
    return True


def fused_shard_apply(optimizer, grads, params, state, specs, mesh, gspecs,
                      axis: str = "data"):
    """Explicit-lowering ZeRO-2 optimizer step: the fused update runs
    inside a ``shard_map`` region over ``axis`` — each rank reads exactly
    the 1/n gradient shard the reduce-scatter handed it and its 1/n
    velocity shard, and writes its updated parameter shard in place.

    Mirrors ``Optimizer.apply`` op for op for the eligible configs (see
    :func:`fused_apply_eligible`); returns (new_params, new_state), or
    None when not eligible — callers fall back to ``optimizer.apply``."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.compat import shard_map

    from paddle_tpu.ops.pallas.tpp.embedding import sparse_row_update
    from paddle_tpu.parallel import zero as zero_mod

    names = list(params)
    if not fused_apply_eligible(optimizer, state, specs, names):
        return None

    step = state["step"]
    lr = optimizer.lr_fn(step)
    is_momentum = type(optimizer) is opt_mod.Momentum

    plan = []  # (name, wd | "static", nesterov, has_velocity, spec, lazy)
    flat_in, flat_specs = [], []
    for n in names:
        spec = specs.get(n)
        if spec is not None and spec.is_static:
            plan.append((n, "static", None, False, False, False))
            continue
        slots = state["slots"][n]
        wd = (spec.decay_rate
              if spec is not None and spec.decay_rate is not None
              else optimizer.l2_rate) or 0.0
        plr = lr * (spec.learning_rate if spec is not None else 1.0)
        sp = gspecs[n]
        # row-lazy sparse tables (SparseRowMatrix semantics): the fused
        # rule needs whole rows on a shard to judge "touched", so a param
        # data-sharded on the feature dim disqualifies the whole step
        # (fall back to optimizer.apply, which sees full rows)
        lazy = (optimizer.lazy_sparse
                and opt_mod.lazy_sparse_rows(spec, params[n]))
        if lazy and zero_mod.data_dim(sp, axis) not in (None, 0):
            return None
        if is_momentum:
            mu = optimizer._coeff(spec)
            plan.append((n, wd, optimizer.use_nesterov, True, sp, lazy))
            flat_in += [params[n], grads[n], slots["velocity"],
                        _scalar(plr), _scalar(mu)]
            flat_specs += [sp, sp, sp, P(), P()]
        elif isinstance(slots, dict) and "velocity" in slots:
            # SGD with a per-param momentum slot (spec-level momentum)
            plan.append((n, wd, False, True, sp, lazy))
            flat_in += [params[n], grads[n], slots["velocity"],
                        _scalar(plr), _scalar(slots["mu"])]
            flat_specs += [sp, sp, sp, P(), P()]
        else:
            plan.append((n, wd, False, False, sp, lazy))
            flat_in += [params[n], grads[n], _scalar(plr)]
            flat_specs += [sp, sp, P()]

    def body(*args):
        it = iter(args)
        outs = []
        for n, wd, nesterov, has_v, _sp, lazy in plan:
            if wd == "static":
                continue
            if has_v:
                p, g, v, plr, mu = (next(it) for _ in range(5))
                if lazy:
                    p2, v2 = sparse_row_update(
                        p, g, v, lr=plr[0, 0], mu=mu[0, 0],
                        nesterov=nesterov, weight_decay=wd)
                else:
                    p2, v2 = fused_momentum_update(
                        p, g, v, plr[0, 0], mu[0, 0], nesterov=nesterov,
                        weight_decay=wd)
                outs += [p2, v2]
            else:
                p, g, plr = (next(it) for _ in range(3))
                if lazy:
                    p2, _ = sparse_row_update(p, g, None, lr=plr[0, 0],
                                              weight_decay=wd)
                    outs.append(p2)
                else:
                    outs.append(fused_sgd_update(p, g, plr[0, 0],
                                                 weight_decay=wd))
        return tuple(outs)

    out_specs = []
    for n, wd, nesterov, has_v, sp, lazy in plan:
        if wd == "static":
            continue
        out_specs += [sp, sp] if has_v else [sp]
    region = shard_map(body, mesh=mesh, in_specs=tuple(flat_specs),
                       out_specs=tuple(out_specs), check_vma=False)
    outs = list(region(*flat_in))

    new_params, new_slots = {}, {}
    i = 0
    for n, wd, nesterov, has_v, sp, lazy in plan:
        if wd == "static":
            new_params[n] = params[n]
            new_slots[n] = state["slots"][n]
            continue
        if has_v:
            new_params[n] = outs[i]
            new_slots[n] = dict(state["slots"][n], velocity=outs[i + 1])
            i += 2
        else:
            new_params[n] = outs[i]
            new_slots[n] = state["slots"][n]
            i += 1
    new_state = dict(state)
    new_state["step"] = step + 1
    new_state["slots"] = new_slots
    return new_params, new_state
