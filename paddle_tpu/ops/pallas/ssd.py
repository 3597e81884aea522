"""The one-token Mamba-2 recurrence of one layer's decode step against
the state POOL as one Pallas TPU kernel (``ops/mamba2.py`` has the
recurrence; ``ssd_step`` there is the plain-XLA twin this is tested
against).

The pool ``[layers, slots, H, P, N]`` float32 stays in HBM, aliased to
the kernel's own output, and the layer's row arrives by scalar prefetch
(a Python number in an unrolled walk, traced inside a rolled one).  Grid
``(slot, block of heads)``: a step moves one slot's ``[heads, P, N]``
through VMEM ONCE — decays it, adds ``dt·x ⊗ B``, reads ``y = S'·C +
d·x`` out of the tile it has just made, and writes the tile back to the
rows it came from.  XLA's pair for the same sublayer reads the layer's state
twice: one fusion roots in the in-place ``dynamic-update-slice``, and a
second re-derives the new state under its ``reduce`` (PERF.md section 6,
PR 48).

What a head's tile ``[P, N]`` needs besides itself lies along its
sublanes (``x``, ``y``: one value a row) or along its lanes (B, C: one a
state column).  The lane vectors are rows of B and C as the model has
them.  The sublane vectors are x and y ``[heads, P]`` TRANSPOSED in the
kernel, once a step each (an XLU pass over eight registers): a head is
then a lane of every row, its x column picked out by a lane mask and a
lane sum, its y column put back by the same mask.  The numbers that are
one a head — the decay, dt, d — ride SMEM beside the row and the live
flags.  Nothing of the sublayer is left to XLA but ``exp(dt · a)``; the
two transposes cost the kernel what they cost XLA around it (0.05 ms a
layer either way: PERF.md section 6, PR 48).  The heads of a block
are a ``fori_loop`` ``UNROLL`` at a time: the kernel's text is traced and
lowered in every process that builds a decode program, and the loop
spelt out for 64 heads cost that a second for a schedule no shorter
(both wait for the tile's DMA).

An idle slot's tile is written back as it was read (``dt = 0`` would
leave it too, except a ``-0.0``), so no ``where`` over the layer is left
to XLA.  Float32 throughout; the read-out is a multiply and a lane sum,
as the twin's ``reduce`` is.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params

_F32 = jnp.float32
UNROLL = 4        # heads a turn of the kernel's loop
_SMEM_DECAYS = 1 << 16
HEAD_BLOCK = 64   # heads a grid step (PERF.md section 6, PR 48: the sweep)


def supports(heads: int, head_dim: int, state: int, groups: int,
             slots: int = 1) -> bool:
    """The shapes the kernel takes: a head's tile ``[head_dim, state]`` of
    whole (8, 128) float32 registers, whole B/C groups of heads, and a
    decay and a dt a slot and head in half of the chip's megabyte of SMEM."""
    return (state % 128 == 0 and head_dim % 8 == 0 and groups > 0
            and heads % groups == 0 and slots * heads <= _SMEM_DECAYS)


def _step_kernel(row_ref, live_ref, decay_ref, dt_ref, d_ref, x_ref, b_ref,
                 c_ref, s_ref, y_ref, o_ref, *, per_group):
    del row_ref                                  # the index maps read it
    slot, j = pl.program_id(0), pl.program_id(1)
    heads = s_ref.shape[0]
    live = live_ref[slot] > 0
    xt = x_ref[0].T                                          # [P, heads]
    lane = lax.broadcasted_iota(jnp.int32, xt.shape, 1)

    def head(h, yt):
        at = j * heads + h                       # the head, its B/C group
        g, of = pl.ds(at // per_group, 1), slot * pl.num_programs(1) * heads
        here = lane == h
        col = jnp.sum(jnp.where(here, xt, 0.0), axis=1, keepdims=True)
        old = s_ref[h]                                       # [P, N]
        new = old * decay_ref[of + at] \
            + (col * dt_ref[of + at]) * b_ref[0, g, :]
        o_ref[h] = jnp.where(live, new, old)
        out = jnp.sum(new * c_ref[0, g, :], axis=1, keepdims=True) \
            + d_ref[at] * col
        return jnp.where(here, out, yt)

    unroll = math.gcd(UNROLL, heads)
    few = lambda k, yt: functools.reduce(
        lambda yt, u: head(k * unroll + u, yt), range(unroll), yt)
    y_ref[0] = lax.fori_loop(0, heads // unroll, few, jnp.zeros_like(xt)).T


@functools.partial(jax.jit, static_argnames=("interpret", "head_block"))
def ssd_pool_step(pool, row, x, dt, a, b, c, d, live, interpret: bool = False,
                  head_block: int | None = None):
    """One token of layer ``row`` against ``pool`` [layers, slots, H, P, N]
    float32: x [B, H, P]; dt [B, H] (after softplus); a, d [H]; b, c
    [B, G, N]; live bool[B] (an idle slot keeps its state, bit for bit);
    B = slots.  Returns (y [B, H, P] float32, the pool with row ``row``
    replaced — the same buffer where the caller donates it).  A ``jit``
    of its own: a walk that spells a layer out at several positions
    traces and lowers it once."""
    _, slots, nh, p, n = pool.shape
    g = b.shape[1]
    if not supports(nh, p, n, g, slots):
        raise ValueError(f"ssd_pool_step takes states of whole (8, 128) "
                         f"tiles, whole groups of heads and {_SMEM_DECAYS} "
                         f"decays, got slots {slots}, heads {nh}, head_dim "
                         f"{p}, state {n}, groups {g}")
    # whole sublanes of x and y a block, or every head
    fits = lambda hb: nh % hb == 0 and (hb % 8 == 0 or hb == nh)
    hb = head_block or next(
        (h for h in range(min(nh, HEAD_BLOCK), 0, -1) if fits(h)), nh)
    if not fits(hb):
        raise ValueError(f"no blocks of {hb} of {nh} heads")
    dt = dt.astype(_F32)
    decay = jnp.exp(dt * a.astype(_F32))                       # [B, H]
    # (index maps: grid indices, then the five prefetched scalars)
    tile = pl.BlockSpec((None, None, hb, p, n),
                        lambda s, j, row, *_: (row[0], s, j, 0, 0))
    cols = pl.BlockSpec((1, hb, p), lambda s, j, *_: (s, j, 0))
    rows = pl.BlockSpec((1, g, n), lambda s, j, *_: (s, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, per_group=nh // g),
        name="ssd_pool_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(slots, nh // hb),
            in_specs=[cols, rows, rows, tile],
            out_specs=[cols, tile]),
        out_shape=[jax.ShapeDtypeStruct((slots, nh, p), _F32),
                   jax.ShapeDtypeStruct(pool.shape, _F32)],
        # operand 8 counts the five prefetched scalars: the pool, in place
        input_output_aliases={8: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(32 << 20, 6 * hb * p * n * 4)),
        interpret=interpret,
    )(jnp.asarray(row, jnp.int32).reshape(1), live.astype(jnp.int32),
      decay.reshape(-1), dt.reshape(-1), d.astype(_F32), x.astype(_F32),
      b.astype(_F32), c.astype(_F32), pool)


def ssd_pool_step_reference(pool, row, x, dt, a, b, c, d, live):
    """The kernel's oracle, same arguments and results: ``mamba2.ssd_step``
    on the layer's row, live rows kept."""
    from paddle_tpu.ops import mamba2

    return mamba2.ssd_pool_step(pool, row, x, dt, a, b, c, d, live,
                                impl="reference")
