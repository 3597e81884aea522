"""Fused softmax cross-entropy over a large vocabulary — Pallas TPU kernel.

The XLA lowering of ``logsumexp(logits.astype(f32)) - logits[target]`` costs
~6 full-vocab HBM passes at the 124M LM bench shape (f32 upcast
materialization, max-reduce, exp-sum, and the backward's recompute chain —
measured ~4.6 ms of a 63 ms step).  This kernel does the minimum traffic:

- forward: ONE bf16 read of the logits, online (max, sum-exp) accumulation
  in f32 VMEM scratch over vocabulary tiles → per-row lse;
- backward: one read + one write, computing
  ``d_logits = (exp(l - lse) - onehot(target)) * g_row`` tile by tile.

Numerically equal to the unfused form to f32 tolerance (exp/accumulation in
f32; only the logits storage is bf16).  API: ``softmax_xent(logits,
targets)`` -> per-row negative log-likelihood [N] (f32); callers mean it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import NEG_INF, round_up as _round_up


def _lse_kernel(l_ref, lse_ref, m_ref, s_ref, *, v, bv):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)

    x = l_ref[...].astype(jnp.float32)
    col = j * bv + lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < v, x, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
    s_new = (s_ref[:, :1] * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True))
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    s_ref[...] = jnp.broadcast_to(s_new, s_ref.shape)

    @pl.when(j == nv - 1)
    def _fin():
        lse_ref[...] = (m_ref[:, :1]
                        + jnp.log(jnp.maximum(s_ref[:, :1], 1e-30)))


def _dlogits_kernel(l_ref, lse_ref, tgt_ref, g_ref, dl_ref, *, v, bv):
    j = pl.program_id(1)
    x = l_ref[...].astype(jnp.float32)
    col = j * bv + lax.broadcasted_iota(jnp.int32, x.shape, 1)
    p = jnp.exp(x - lse_ref[:, :1])
    p = jnp.where(col < v, p, 0.0)
    onehot = (col == tgt_ref[:, :1]).astype(jnp.float32)
    dl_ref[...] = ((p - onehot) * g_ref[:, :1]).astype(dl_ref.dtype)


def _lse(logits, block_rows, block_v, interpret):
    """Grid over ceil-divided blocks of the UNPADDED array: Pallas serves
    partial edge blocks zero-padded, and the kernels mask by the true
    row/col bounds — no materialized pad copy of the logits."""
    n, v = logits.shape
    np_, vp = _round_up(n, block_rows), _round_up(v, block_v)
    lse = pl.pallas_call(
        functools.partial(_lse_kernel, v=v, bv=block_v),
        grid=(np_ // block_rows, vp // block_v),
        in_specs=[pl.BlockSpec((block_rows, block_v),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_rows, 128), jnp.float32),
                        pltpu.VMEM((block_rows, 128), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits)
    return lse[:n, 0], np_, vp


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def softmax_xent(logits, targets, block_rows=256, block_v=2048,
                 interpret=None):
    """Per-row NLL: ``logsumexp(logits[i]) - logits[i, targets[i]]``.

    logits [N, V] (any float dtype; accumulation is f32), targets [N] int.
    """
    nll, _ = _fwd(logits, targets, block_rows, block_v, interpret)
    return nll


def _fwd(logits, targets, block_rows, block_v, interpret):
    from paddle_tpu.ops.pallas import resolve_interpret

    interpret = resolve_interpret(interpret)
    lse, np_, vp = _lse(logits, block_rows, block_v, interpret)
    tgt = jnp.take_along_axis(logits, targets[:, None].astype(jnp.int32),
                              axis=-1)[:, 0].astype(jnp.float32)
    return lse - tgt, (logits, lse, targets, (logits.shape, np_, vp))


def _bwd(block_rows, block_v, interpret, res, g):
    from paddle_tpu.ops.pallas import resolve_interpret

    interpret = resolve_interpret(interpret)
    logits, lse, targets, ((n, v), np_, vp) = res
    # per-row side inputs are tiny; pallas zero-pads their edge blocks too.
    # padded rows produce garbage p but write into dl rows >= n, sliced off
    rspec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    dl = pl.pallas_call(
        functools.partial(_dlogits_kernel, v=v, bv=block_v),
        grid=(np_ // block_rows, vp // block_v),
        in_specs=[pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
                  rspec, rspec, rspec],
        out_specs=pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, vp), logits.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits, lse[:, None], targets.astype(jnp.int32)[:, None],
      g.astype(jnp.float32)[:, None])
    return dl[:n, :v], None


softmax_xent.defvjp(_fwd, _bwd)


def softmax_xent_reference(logits, targets):
    """Pure-jnp oracle of :func:`softmax_xent`: the unfused
    ``logsumexp - picked-logit`` formulation in f32 (per-row NLL)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, targets[:, None], axis=-1)[:, 0]
    return lse - picked
