"""Fused embedding gather / unique-ids dedup / scatter-add — the sparse
pserver's row machinery as TPP microkernels.

The reference serves billion-row embedding tables through
``SparseRowMatrix``: each step prefetches exactly the rows the batch
touches, applies the update to exactly those rows, and never
materialises the dense table on a worker.  This module rebuilds that
row-level contract on the mesh-sharded tables of
``parallel/embedding.py``:

- :func:`dedup_ids` — sort-based unique-with-inverse over the batch's
  flat id list at a fixed capacity (the XLA sort IS the efficient TPU
  lowering for dedup; there is no profitable Pallas formulation, so the
  twin pair is jnp on both sides and exists for the pipeline's naming
  contract);
- :func:`embedding_gather` — one DMA per *unique* row, driven by a
  scalar-prefetched id list (``PrefetchScalarGridSpec``): the id array
  rides SMEM ahead of the grid so each step's BlockSpec index map picks
  the table row to fetch — the paged-attention page-table trick applied
  to embedding rows;
- :func:`embedding_scatter_add` — duplicate-exact scatter-add of
  per-unique-row updates expressed as a one-hot MXU contraction
  accumulated over id blocks (the XLA-on-TPU lowering for embedding
  scatter, done in one pass with an f32 VMEM accumulator);
- :func:`sparse_row_update` — the row-lazy SGD/momentum rule of
  ``SparseRowMatrix``: rows with an all-zero gradient keep their
  parameter AND their optimizer slot bit-for-bit (no decay, no momentum
  advance), in one read-modify-write pass over p/g/v;
- :func:`fused_embedding_lookup` — the ``custom_vjp`` composition:
  forward dedups then gathers each unique row once; backward
  segment-sums cotangents per unique row then scatter-adds once per
  row.

Every ``pallas_call`` entry ships a pure-jnp ``*_reference`` twin (the
CPU production path and the parity oracle, per the GL-KERNEL rule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import (pad_axis, resolve_impl,
                                   resolve_interpret, round_up)

_LANES = 128
_SCATTER_ROW_BLOCK = 256
_SCATTER_ID_BLOCK = 512
_UPDATE_ROW_BLOCK = 256


def _scalar(x):
    return jnp.asarray(x, jnp.float32).reshape(1, 1)


# ---------------------------------------------------------------------------
# dedup
# ---------------------------------------------------------------------------

def dedup_ids_reference(ids, capacity: int | None = None):
    """Unique-with-inverse over a flat id list at fixed ``capacity``.

    Returns ``(uids, inv)``: ``uids`` is int32 ``[capacity]`` holding the
    sorted unique ids padded with ``-1`` at the tail; ``inv`` is int32
    shaped like the flattened input with ``flat[i] == uids[inv[i]]``.
    ``capacity`` defaults to ``len(ids)`` (always sufficient)."""
    flat = jnp.asarray(ids).reshape(-1).astype(jnp.int32)
    cap = int(flat.shape[0]) if capacity is None else int(capacity)
    uids, inv = jnp.unique(flat, size=cap, fill_value=-1,
                           return_inverse=True)
    return uids.astype(jnp.int32), inv.reshape(flat.shape).astype(jnp.int32)


def dedup_ids(ids, capacity: int | None = None):
    """Twin of :func:`dedup_ids_reference`.

    Dedup is a sort — XLA's TPU sort is already the efficient lowering
    and a Pallas formulation would just re-derive it, so both sides of
    this pair are the same jnp program.  The name pair exists so the
    fused lookup's three stages (dedup / gather / scatter-add) share one
    dispatch and test vocabulary."""
    return dedup_ids_reference(ids, capacity)


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

def embedding_gather_reference(table, ids):
    """jnp twin: ``table[clip(ids, 0, V-1)]`` — rows for the scalar-
    prefetched id list.  Ids are clamped (``jnp.take``'s clip mode);
    callers mask invalid / padding ids outside."""
    v = table.shape[0]
    safe = jnp.clip(jnp.asarray(ids).astype(jnp.int32), 0, v - 1)
    return jnp.take(table, safe, axis=0)


_GATHER_ROWS = 8  # ids per grid step: that many row DMAs in flight


def _gather_kernel(ids_ref, *refs):
    del ids_ref  # consumed by the index maps
    *row_refs, out_ref = refs
    for r, row_ref in enumerate(row_refs):
        out_ref[r] = row_ref[0]


def embedding_gather(table, ids, *, impl: str = "auto", interpret=None):
    """One row-DMA per id: ``out[i] = table[ids[i]]`` with the id list
    scalar-prefetched into SMEM so each grid step's table BlockSpec
    index maps read ``ids[i]`` directly (no HBM-resident one-hot, no
    dense gather).  Ids are clamped to ``[0, V)`` like ``jnp.take``.

    The table is viewed as ``[V, 1, D]`` so a one-row block is the full
    extent of the two tiled (minor) dims — Mosaic refuses a ``(1, D)``
    block of a ``[V, D]`` array (a size-1 sublane block), for any D and
    dtype.  Each grid step fetches ``_GATHER_ROWS`` rows: the table is
    passed once per row slot, each with its own id-driven index map, so
    the pipeline keeps that many row DMAs in flight per step."""
    if resolve_impl(impl, "embedding_gather") == "reference":
        return embedding_gather_reference(table, ids)
    interpret = resolve_interpret(interpret)
    v, d = table.shape
    ids = jnp.asarray(ids)
    lead = ids.shape  # grid runs over the flattened id list
    n = 1
    for s in lead:
        n *= int(s)
    rows = _GATHER_ROWS
    npad = round_up(n, rows)
    safe = pad_axis(jnp.clip(ids.reshape(n).astype(jnp.int32), 0, v - 1),
                     0, npad)
    tbl = table.reshape(v, 1, d)

    def row_spec(r):
        return pl.BlockSpec((1, 1, d),
                            lambda i, ids_s: (ids_s[i * rows + r], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the id list rides SMEM
        grid=(npad // rows,),
        in_specs=[row_spec(r) for r in range(rows)],
        out_specs=pl.BlockSpec((rows, 1, d), lambda i, ids_s: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npad, 1, d), table.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(safe, *([tbl] * rows))
    return out[:n, 0].reshape(*lead, d)


# ---------------------------------------------------------------------------
# scatter-add
# ---------------------------------------------------------------------------

def embedding_scatter_add_reference(table, ids, rows):
    """jnp twin: ``table.at[ids].add(rows)`` with negative ids (the
    dedup pad slots) dropped.  Duplicate ids accumulate exactly."""
    ids = jnp.asarray(ids).astype(jnp.int32)
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    contrib = jnp.where(valid[:, None], rows, 0).astype(table.dtype)
    return table.at[safe].add(contrib)


def _scatter_kernel(ids_ref, rows_ref, tbl_ref, out_ref, acc_ref, *, bm):
    j = pl.program_id(0)
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = tbl_ref[...].astype(jnp.float32)

    local = ids_ref[...] - j * bm                          # [1, nk_ids]
    rowid = jax.lax.broadcasted_iota(jnp.int32, (bm, local.shape[1]), 0)
    onehot = (local == rowid).astype(jnp.float32)          # [bm, nk_ids]
    acc_ref[...] += jnp.dot(onehot, rows_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def embedding_scatter_add(table, ids, rows, *, impl: str = "auto",
                          interpret=None):
    """``table + scatter_add(ids -> rows)`` as a one-hot MXU contraction
    accumulated over id blocks: each table row-block carries an f32 VMEM
    accumulator across the id dimension, so every output row is written
    exactly once and duplicate ids sum exactly.  Negative ids (the dedup
    pad convention) contribute nothing."""
    if resolve_impl(impl, "embedding_scatter_add") == "reference":
        return embedding_scatter_add_reference(table, ids, rows)
    interpret = resolve_interpret(interpret)
    v, d = table.shape
    (n,) = ids.shape
    dpad = round_up(d, _LANES)
    bm = min(_SCATTER_ROW_BLOCK, round_up(v, 8))
    vpad = round_up(v, bm)
    nk = min(_SCATTER_ID_BLOCK, round_up(n, _LANES))
    npad = round_up(n, nk)

    tbl = pad_axis(pad_axis(table, 0, vpad), 1, dpad)
    rws = pad_axis(pad_axis(rows, 0, npad), 1, dpad)
    idv = pad_axis(jnp.asarray(ids).astype(jnp.int32)[None, :], 1,
                    npad)  # pad ids are 0-filled ...
    idv = jnp.where(jax.lax.broadcasted_iota(jnp.int32, idv.shape, 1) < n,
                    idv, -1)  # ... force the tail to the no-op id

    out = pl.pallas_call(
        functools.partial(_scatter_kernel, bm=bm),
        grid=(vpad // bm, npad // nk),
        in_specs=[
            pl.BlockSpec((1, nk), lambda j, k: (0, k)),
            pl.BlockSpec((nk, dpad), lambda j, k: (k, 0)),
            pl.BlockSpec((bm, dpad), lambda j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, dpad), lambda j, k: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((vpad, dpad), table.dtype),
        scratch_shapes=[pltpu.VMEM((bm, dpad), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idv, rws, tbl)
    return out[:v, :d]


# ---------------------------------------------------------------------------
# row-lazy optimizer update (SparseRowMatrix semantics)
# ---------------------------------------------------------------------------

def sparse_row_update_reference(p, g, v=None, *, lr=0.01, mu=0.0,
                                nesterov=False, weight_decay=0.0):
    """Row-lazy twin of the SGD/momentum rule: rows whose gradient is
    all-zero (untouched this step) keep their parameter AND slot
    bit-for-bit — no decay fold, no momentum advance — matching the
    reference's ``SparseRowMatrix`` update.  Touched rows follow
    ``fused_momentum_update_reference`` exactly (decay folded on touch).

    Returns ``(p', v')`` (``v'`` is ``None`` for plain SGD)."""
    p32 = p.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    touched = jnp.any(g32 != 0.0, axis=1, keepdims=True)
    if weight_decay:
        g32 = jnp.where(touched, g32 + weight_decay * p32, g32)
    if v is None:
        pn = (p32 - lr * g32).astype(p.dtype)
        return jnp.where(touched, pn, p), None
    v32 = v.astype(jnp.float32)
    vn = mu * v32 + g32
    delta = lr * (g32 + mu * vn) if nesterov else lr * vn
    pn = jnp.where(touched, (p32 - delta).astype(p.dtype), p)
    return pn, jnp.where(touched, vn, v32).astype(v.dtype)


def _sparse_mom_kernel(lr_ref, mu_ref, p_ref, g_ref, v_ref, po_ref, vo_ref,
                       *, nesterov, weight_decay):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lr = lr_ref[0, 0]
    mu = mu_ref[0, 0]
    touched = jnp.any(g != 0.0, axis=1, keepdims=True)
    if weight_decay:
        g = jnp.where(touched, g + weight_decay * p, g)
    vn = mu * v + g
    delta = lr * (g + mu * vn) if nesterov else lr * vn
    po_ref[...] = jnp.where(touched, (p - delta).astype(po_ref.dtype),
                            p_ref[...])
    vo_ref[...] = jnp.where(touched, vn, v).astype(vo_ref.dtype)


def _sparse_sgd_kernel(lr_ref, p_ref, g_ref, po_ref, *, weight_decay):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    lr = lr_ref[0, 0]
    touched = jnp.any(g != 0.0, axis=1, keepdims=True)
    if weight_decay:
        g = jnp.where(touched, g + weight_decay * p, g)
    po_ref[...] = jnp.where(touched, (p - lr * g).astype(po_ref.dtype),
                            p_ref[...])


def sparse_row_update(p, g, v=None, *, lr=0.01, mu=0.0, nesterov=False,
                      weight_decay=0.0, impl: str = "auto", interpret=None):
    """One read-modify-write pass of the row-lazy update over ``[V, D]``
    parameter / gradient / slot buffers (``input_output_aliases`` donates
    p and v, so the table is updated in place on its shard).  Untouched
    rows are written back unchanged — the out-block VMEM buffer is
    uninitialised, so the passthrough write is mandatory, and it is what
    keeps untouched rows bit-identical."""
    if resolve_impl(impl, "sparse_row_update") == "reference":
        return sparse_row_update_reference(
            p, g, v, lr=lr, mu=mu, nesterov=nesterov,
            weight_decay=weight_decay)
    interpret = resolve_interpret(interpret)
    rows, d = p.shape
    dpad = round_up(d, _LANES)
    bm = min(_UPDATE_ROW_BLOCK, round_up(rows, 8))
    rpad = round_up(rows, bm)

    pp = pad_axis(pad_axis(p, 0, rpad), 1, dpad)
    gp = pad_axis(pad_axis(g, 0, rpad), 1, dpad)
    blk = pl.BlockSpec((bm, dpad), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    params = tpu_compiler_params(dimension_semantics=("parallel",))

    if v is None:
        po = pl.pallas_call(
            functools.partial(_sparse_sgd_kernel,
                              weight_decay=float(weight_decay)),
            grid=(rpad // bm,),
            in_specs=[smem, blk, blk],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct(pp.shape, p.dtype),
            input_output_aliases={1: 0},
            compiler_params=params,
            interpret=interpret,
        )(_scalar(lr), pp, gp)
        return po[:rows, :d], None

    vp = pad_axis(pad_axis(v, 0, rpad), 1, dpad)
    po, vo = pl.pallas_call(
        functools.partial(_sparse_mom_kernel, nesterov=bool(nesterov),
                          weight_decay=float(weight_decay)),
        grid=(rpad // bm,),
        in_specs=[smem, smem, blk, blk, blk],
        out_specs=(blk, blk),
        out_shape=(jax.ShapeDtypeStruct(pp.shape, p.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)),
        input_output_aliases={2: 0, 4: 1},
        compiler_params=params,
        interpret=interpret,
    )(_scalar(lr), _scalar(mu), pp, gp, vp)
    return po[:rows, :d], vo[:rows, :d]


# ---------------------------------------------------------------------------
# fused lookup (custom_vjp composition)
# ---------------------------------------------------------------------------

def _lookup_fwd_impl(table, ids, padding_idx, impl, interpret):
    v, d = table.shape
    flat = ids.reshape(-1).astype(jnp.int32)
    uids, inv = dedup_ids(flat)
    rows = embedding_gather(table, uids, impl=impl, interpret=interpret)
    out = jnp.take(rows, inv, axis=0)
    if padding_idx is not None:
        out = jnp.where((flat == padding_idx)[:, None],
                        jnp.zeros((), out.dtype), out)
    return out.reshape(*ids.shape, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_embedding_lookup(table, ids, padding_idx=None, impl: str = "auto",
                           interpret=None):
    """Dedup-once embedding lookup: forward gathers each *unique* row of
    the batch exactly once (then re-expands in VMEM-sized space);
    backward segment-sums cotangents per unique row and scatter-adds
    each table row exactly once — the reference's sparse-row prefetch /
    sparse-update contract.  Matches ``jnp.take`` + padding-mask
    semantics (ids clamped to ``[0, V)``; ``padding_idx`` rows are zero
    with zero gradient)."""
    return _lookup_fwd_impl(table, ids, padding_idx, impl, interpret)


def _lookup_vjp_fwd(table, ids, padding_idx, impl, interpret):
    out = _lookup_fwd_impl(table, ids, padding_idx, impl, interpret)
    # zero-width stub: carries the table's static shape/dtype, no bytes
    return out, (ids, table[:, :0])


def _lookup_vjp_bwd(padding_idx, impl, interpret, res, ct):
    ids, stub = res
    v, tdtype = stub.shape[0], stub.dtype
    d = ct.shape[-1]
    flat = ids.reshape(-1).astype(jnp.int32)
    ctf = ct.reshape(flat.shape[0], d).astype(jnp.float32)
    if padding_idx is not None:
        ctf = jnp.where((flat == padding_idx)[:, None], 0.0, ctf)
    uids, inv = dedup_ids(flat)
    per_row = jax.ops.segment_sum(ctf, inv,
                                  num_segments=int(flat.shape[0]))
    dtable = embedding_scatter_add(
        jnp.zeros((v, d), jnp.float32), uids, per_row,
        impl=impl, interpret=interpret)
    return dtable.astype(tdtype), None


fused_embedding_lookup.defvjp(_lookup_vjp_fwd, _lookup_vjp_bwd)
