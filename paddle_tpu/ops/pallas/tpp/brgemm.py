"""Batch-reduce GEMM — the TPP core microkernel.

``brgemm(a, b)`` computes ``sum_g a[g] @ b[g]`` over a stack of operand
blocks with a single f32 VMEM accumulator, then applies a fused epilogue
before the one HBM write of the result tile:

- affine: ``y * scale + shift`` per output column (the inference-mode
  batch-norm fold);
- relu;
- stats: per-column ``sum`` / ``sum of squares`` of the PRE-epilogue
  accumulator, reduced across the whole output in the same pass (the
  single-pass batch-norm statistics for the training-mode fusion — the
  separate reduction pass over the conv output in HBM disappears).

The batch dimension ``g`` is the reduce dimension of the TPP paper's
BRGEMM: callers hand it K-blocks of a matmul, the KH*KW shifted patch
planes of a convolution, or a genuine operand batch.  ``g`` iterates
innermost so the accumulator tile stays resident in VMEM across the
whole reduction.

``brgemm_reference`` is the jnp twin — the CPU production path and the
interpret-mode test oracle (see ``tools/check_kernel_parity.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import (mxu_precision, resolve_impl,
                                   resolve_interpret, round_up)


def _epilogue(y, scale, shift, act):
    if scale is not None:
        y = y * scale + shift
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y


def brgemm_reference(a, b, scale=None, shift=None, act=None,
                     stats=False, out_dtype=None):
    """jnp oracle: a [G, M, K] @ b [G, K, N] summed over G, accumulated in
    f32, epilogue applied last.  Returns y [M, N] (and (col_sum [N],
    col_sumsq [N]) of the pre-epilogue accumulator when ``stats``)."""
    acc = jnp.einsum("gmk,gkn->mn", a, b,
                     preferred_element_type=jnp.float32,
                     precision=mxu_precision(a))
    out_dtype = out_dtype or a.dtype
    y = _epilogue(acc, scale, shift, act).astype(out_dtype)
    if not stats:
        return y
    return y, jnp.sum(acc, axis=0), jnp.sum(acc * acc, axis=0)


def _kernel(a_ref, b_ref, *refs, g_total, act, affine, stats, out_dtype):
    i = 0
    scale_ref = shift_ref = sum_ref = sumsq_ref = None
    if affine:
        scale_ref, shift_ref = refs[i], refs[i + 1]
        i += 2
    o_ref = refs[i]
    i += 1
    if stats:
        sum_ref, sumsq_ref = refs[i], refs[i + 1]
        i += 2
    acc_ref = refs[i]

    mi = pl.program_id(1)
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                            preferred_element_type=jnp.float32,
                            precision=mxu_precision(a_ref))

    @pl.when(g == g_total - 1)
    def _finalize():
        y = acc_ref[...]
        if stats:
            # column partials accumulate across the mi grid dim: the
            # stats block's index map is constant in mi/g, so the buffer
            # stays resident for a whole ni column of tiles
            @pl.when(mi == 0)
            def _zero():
                sum_ref[...] = jnp.zeros_like(sum_ref)
                sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

            sum_ref[...] += jnp.sum(y, axis=0, keepdims=True)
            sumsq_ref[...] += jnp.sum(y * y, axis=0, keepdims=True)
        if affine:
            y = y * scale_ref[...] + shift_ref[...]
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        o_ref[...] = y.astype(out_dtype)


def _kernel_impl(a, b, scale, shift, act, stats, out_dtype,
                 block_m, block_n, interpret):
    g_total, m, k = a.shape
    n = b.shape[2]
    bm = min(round_up(m, 8), block_m)
    bn = min(round_up(n, 128), block_n)
    mp, np_ = round_up(m, bm), round_up(n, bn)
    # zero row/col padding: contributes nothing to dots OR stats sums
    if mp != m or np_ != n:
        a = jnp.pad(a, ((0, 0), (0, mp - m), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, np_ - n)))
    affine = scale is not None
    operands = [a, b]
    in_specs = [
        pl.BlockSpec((1, bm, k), lambda ni, mi, g: (g, mi, 0)),
        pl.BlockSpec((1, k, bn), lambda ni, mi, g: (g, 0, ni)),
    ]
    if affine:
        operands += [jnp.pad(scale.reshape(1, n).astype(jnp.float32),
                             ((0, 0), (0, np_ - n))),
                     jnp.pad(shift.reshape(1, n).astype(jnp.float32),
                             ((0, 0), (0, np_ - n)))]
        in_specs += [pl.BlockSpec((1, bn), lambda ni, mi, g: (0, ni)),
                     pl.BlockSpec((1, bn), lambda ni, mi, g: (0, ni))]
    out_shape = [jax.ShapeDtypeStruct((mp, np_), out_dtype)]
    out_specs = [pl.BlockSpec((bm, bn), lambda ni, mi, g: (mi, ni))]
    if stats:
        out_shape += [jax.ShapeDtypeStruct((1, np_), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((1, bn), lambda ni, mi, g: (0, ni))] * 2
    outs = pl.pallas_call(
        functools.partial(_kernel, g_total=g_total, act=act, affine=affine,
                          stats=stats, out_dtype=out_dtype),
        name="tpp_brgemm",
        # ni outermost so the resident stats block sees every (mi, g) of
        # its column before moving on; g innermost keeps the accumulator
        # tile live across the reduction
        grid=(np_ // bn, mp // bm, g_total),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=(("arbitrary",) * 3 if stats else
                                 ("parallel", "parallel", "arbitrary")),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(*operands)
    y = outs[0][:m, :n]
    if not stats:
        return y
    return y, outs[1][0, :n], outs[2][0, :n]


def brgemm(a, b, scale=None, shift=None, act=None, stats=False,
           out_dtype=None, block_m=256, block_n=256, impl="auto",
           interpret=None):
    """Batch-reduce GEMM with fused epilogue.

    a: [G, M, K]; b: [G, K, N]; scale/shift: optional [N] f32 affine
    epilogue; act: None | "relu"; stats: also return per-column
    (sum, sumsq) of the pre-epilogue f32 accumulator.  ``impl``:
    "kernel" | "reference" | "auto" (kernel on TPU, reference
    elsewhere — the paged_attention convention)."""
    if act not in (None, "relu"):
        raise ValueError(f"brgemm epilogue act must be None or 'relu', "
                         f"got {act!r}")
    if (scale is None) != (shift is None):
        raise ValueError("brgemm affine epilogue needs both scale and shift")
    out_dtype = out_dtype or a.dtype
    if resolve_impl(impl, "brgemm") == "reference":
        return brgemm_reference(a, b, scale=scale, shift=shift, act=act,
                                stats=stats, out_dtype=out_dtype)
    return _kernel_impl(a, b, scale, shift, act, stats, out_dtype,
                        block_m, block_n, resolve_interpret(interpret))
