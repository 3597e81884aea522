"""The grouped product of a routed expert sublayer as ONE Pallas TPU
kernel: rows sorted by expert times each row's own expert's matrix,
``out[r] = rows[r] @ w[group of r]`` (``lax.ragged_dot``'s contract, which
is this kernel's reference twin and the CPU path).

``rows`` [M, K] lie group after group — ``sizes`` int32[G] rows each,
rows past their sum belong to no group and come out zero — and ``w`` is
the stacked matrices [G, K, N].  The rows are cut into tiles of ``tm``;
a *visit* is one (row tile, group) pair that holds rows, and the grid
walks ``(N tiles, visits)`` with the visits in group order, so

- consecutive visits of one group name the same ``[K, tn]`` block of its
  matrix, which the pipeline then does not fetch again: every group's
  matrix is read ONCE an N tile, whatever tiles its rows straddle;
- **a group with no rows has no visit, so its matrix is never read** —
  what a decode step's few rows save over a product masked over every
  held expert;
- consecutive visits of one row tile (the groups that meet in it) write
  the same output block, each its own rows: the first zero-fills the
  rest, the later ones keep what is there;
- the rows past the groups are one more group with no matrix, whose
  visits zero the tiles no expert wrote; the visit list is as long as
  the worst case (``tiles + G``), and visits past the last live one
  repeat its block indices and do nothing.

The visit list is index arithmetic on ``sizes`` (cumulative sums, one
``searchsorted``) made in XLA and handed over as scalar prefetch.  jax's
own ``megablox.gmm`` lays its metadata out alike; this kernel differs in
what a visit computes.

Two forms of one body.  *down*: ``rows @ w`` accumulated in float32, as
``out_dtype``.  *gate | up* (``act`` given): ``act(rows @ w)``, or under
``gate`` ``act(rows @ gate) * (rows @ w)``, every product and the
activation in float32 in VMEM and ONE cast to ``out_dtype`` — the float32
pair ``[M, N]`` never goes to HBM.  These are the rounding points of
``parallel/moe.moe_routed``'s plain form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import (mxu_precision, note_route, pad_axis,
                                   resolve_impl, resolve_interpret, round_up)

ROW_TILE = 128          # rows a visit: one pass of the MXU's 128 x 128
# the matrix blocks of a visit (one, or the gate | up pair) may take this
# much VMEM; the pipeline holds two of each
_BLOCK_BYTES = 12 << 20
_F32 = jnp.float32


def _n_tile(k: int, n: int, itemsize: int, mats: int) -> int | None:
    """The widest block of output columns whose ``mats`` matrix blocks
    ``[k, tn]`` fit ``_BLOCK_BYTES``: all of ``n``, or a multiple of 128
    that divides it.  None: no such block."""
    fits = lambda tn: k * tn * itemsize * mats <= _BLOCK_BYTES
    if fits(n):
        return n
    for tiles in range(2, n // 128 + 1):
        if n % (128 * tiles) == 0 and fits(n // tiles):
            return n // tiles
    return None


def supports(k: int, n: int, dtype, gated: bool = False) -> bool:
    """Whether the kernel takes matrices ``[k, n]`` of ``dtype``."""
    return _n_tile(k, n, jnp.dtype(dtype).itemsize, 1 + gated) is not None


def route(impl: str, *forms) -> str:
    """``impl``'s answer for products of ``forms`` (each the arguments of
    :func:`supports`): "kernel", "reference", or "reference_shape" where
    the kernel was asked for and takes no block of one of them."""
    path = resolve_impl(impl)
    if path == "kernel" and not all(supports(*form) for form in forms):
        path = "reference_shape"
    return path


def visits(sizes, m: int, tm: int):
    """The visit list of ``sizes`` int32[G] over ``m`` rows in tiles of
    ``tm``: (bounds int32[G + 2], group int32[V], tile int32[V], matrix
    int32[V], count int32[1]), V = tiles + G.  Group ``g``'s rows are
    ``[bounds[g], bounds[g + 1])``, group G the rows past the last; visit
    ``v < count`` is of ``group[v]`` in ``tile[v]`` and names block
    ``matrix[v]`` of the stack (the group's own; the last live group's
    where it has none); visits from ``count`` on repeat the last one."""
    i32 = jnp.int32
    g = sizes.shape[0]
    sizes = sizes.astype(i32)
    groups = jnp.concatenate([sizes, (m - jnp.sum(sizes))[None]])
    ends = jnp.cumsum(groups)
    starts = ends - groups
    first = starts // tm
    n_vis = jnp.where(groups > 0, (ends - 1) // tm - first + 1, 0)
    vis_end = jnp.cumsum(n_vis)
    count = vis_end[-1]
    v = jnp.minimum(jnp.arange(-(-m // tm) + g, dtype=i32), count - 1)
    # (all pairs compared: the default walks a ``while`` loop a call)
    group = jnp.searchsorted(vis_end, v, side="right",
                             method="compare_all").astype(i32)
    tile = first[group] + v - (vis_end[group] - n_vis[group])
    last_live = jnp.max(jnp.where(sizes > 0, jnp.arange(g, dtype=i32), 0))
    matrix = jnp.where(group < g, group, last_live)
    bounds = jnp.concatenate([jnp.zeros((1,), i32), ends])
    return (bounds.astype(i32), group, tile.astype(i32), matrix.astype(i32),
            count[None].astype(i32))


def _visit_kernel(bounds, group, tile, matrix, count, x_ref, *refs, tm,
                  groups, act, gated):
    del matrix                              # the index maps read it
    w_refs, o_ref = refs[:-1], refs[-1]
    v = pl.program_id(1)
    g, at = group[v], tile[v]
    live = v < count[0]
    # the first visit of a row tile owns what no group writes: zeros
    first = jnp.logical_or(v == 0, tile[jnp.maximum(v - 1, 0)] != at)

    @pl.when(jnp.logical_and(live, g < groups))
    def _expert():
        x = x_ref[...]
        dot = lambda w_ref: jnp.dot(x, w_ref[...],
                                    precision=mxu_precision(x_ref),
                                    preferred_element_type=_F32)
        y = dot(w_refs[0])
        if gated:
            y = y * act(dot(w_refs[1]))
        elif act is not None:
            y = act(y)
        y = y.astype(o_ref.dtype)
        r = at * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = jnp.logical_and(r >= bounds[g], r < bounds[g + 1])

        @pl.when(first)
        def _():
            o_ref[...] = jnp.where(mine, y, jnp.zeros_like(y))

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[...] = jnp.where(mine, y, o_ref[...])

    @pl.when(jnp.logical_and(live, jnp.logical_and(g == groups, first)))
    def _past():                            # a tile no group has rows in
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("act", "out_dtype", "interpret",
                                             "tm", "tn"))
def grouped_matmul_kernel(rows, w, sizes, gate=None, act=None,
                          out_dtype=_F32, interpret: bool = False,
                          tm: int | None = None, tn: int | None = None):
    """The kernel itself (``grouped_matmul`` has the contract): ``tm``
    rows a visit, ``tn`` output columns a block (None: ``ROW_TILE``, the
    widest that fits).  A ``jit`` of its own, so the sublayers of a
    program that is traced layer by layer (a decode step's) trace and
    lower each form once — ``act`` is static: hand over the same function
    object every time."""
    m, k = rows.shape
    groups, _, n = w.shape
    gated = gate is not None
    mats = (w, gate) if gated else (w,)
    tm = min(tm or ROW_TILE, round_up(m, 16))
    tn = tn or _n_tile(k, n, w.dtype.itemsize, len(mats))
    if tn is None or n % tn or (tn != n and tn % 128):
        raise ValueError(f"grouped_matmul takes no block of {tn} of {n} "
                         f"columns for matrices [{k}, {n}] of {w.dtype}")
    mp = round_up(m, tm)
    meta = visits(sizes, mp, tm)
    out_dtype = jnp.dtype(out_dtype)
    block = k * tn * w.dtype.itemsize * len(mats)
    need = 2 * (block + tm * k * rows.dtype.itemsize
                + tm * tn * out_dtype.itemsize) + tm * tn * 4 * (2 + gated)
    w_spec = pl.BlockSpec((None, k, tn),
                          lambda j, v, b, g, t, mx, c: (mx[v], 0, j))
    out = pl.pallas_call(
        functools.partial(_visit_kernel, tm=tm, groups=groups, act=act,
                          gated=gated),
        name="grouped_matmul_gate_up" if act is not None
        else "grouped_matmul_down",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, mp // tm + groups),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, v, b, g, t, mx, c: (t[v], 0)),
                      *[w_spec] * len(mats)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, b, g, t, mx, c: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        compiler_params=tpu_compiler_params(
            # visits in order: those of one row tile share an output block
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(max(need + (8 << 20), 32 << 20), 100 << 20)),
        interpret=interpret,
    )(*meta, pad_axis(rows, 0, mp), *mats)
    return out[:m]


def grouped_matmul_reference(rows, w, sizes, gate=None, act=None,
                             out_dtype=_F32):
    """The kernel's oracle and the CPU path, same arguments and result:
    ``lax.ragged_dot`` a matrix, float32 out of each."""
    y = lax.ragged_dot(rows, w, sizes, preferred_element_type=_F32)
    if gate is not None:
        y = y * act(lax.ragged_dot(rows, gate, sizes,
                                   preferred_element_type=_F32))
    elif act is not None:
        y = act(y)
    return y.astype(out_dtype)


def grouped_matmul(rows, w, sizes, gate=None, act=None, out_dtype=_F32,
                   impl: str = "auto", interpret=None):
    """rows [M, K] sorted by group; w [G, K, N] (and ``gate`` alike);
    sizes int32[G], summing to at most M.  Returns [M, N] ``out_dtype``:
    row r of group g is ``rows[r] @ w[g]`` — under ``act`` (a float32
    function) ``act(rows[r] @ w[g])``, under ``gate`` too ``act(rows[r] @
    gate[g]) * (rows[r] @ w[g])`` — computed in float32 and cast once;
    rows past the groups are zero.  ``impl``: "kernel", "reference"
    (``lax.ragged_dot``) or "auto" (kernel on a TPU); matrices the kernel
    takes no block of run the reference, and the routing census
    (``grouped_matmul``) says ``reference_shape``."""
    if gate is not None and act is None:
        raise ValueError("a gate needs an activation")
    path = route(impl, (rows.shape[1], w.shape[2], w.dtype, gate is not None))
    note_route("grouped_matmul", path)
    if path == "kernel":
        return grouped_matmul_kernel(rows, w, sizes, gate, act, out_dtype,
                                     resolve_interpret(interpret))
    return grouped_matmul_reference(rows, w, sizes, gate, act, out_dtype)
