"""Flash attention as a Pallas TPU kernel — forward and backward.

This is the MXU-resident replacement for the exact-attention einsum path in
``paddle_tpu/ops/attention.py``: tiled QK^T → online softmax → PV entirely in
VMEM, never materialising the [Tq, Tk] score matrix in HBM.  The backward
pass is the standard flash recurrence (recompute probabilities from the saved
log-sum-exp, one kernel for dQ and one for dK/dV).

The reference framework (2017) has no attention kernel at all — its NMT
demos hand-build additive attention from MixedLayer projections
(``python/paddle/trainer_config_helpers/networks.py`` simple_attention).
This kernel is the new-capability analog of its hand-CUDA class of kernels
(``paddle/cuda/src/hl_cuda_lstm.cu`` etc.), built for the MXU.

Layout: public API takes [B, T, H, D] (matching ops/attention.py); kernels
run on [B*H, T, D].  T is zero-padded to block multiples; padded keys are
masked inside the kernels, padded q rows are sliced off.  In causal mode,
tiles entirely above the diagonal are skipped (pl.when), halving the FLOPs.
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


def _causal_valid(bq, bk, qi0, ki0, t_k, causal):
    """[bq, bk] bool: key in range, and (if causal) key pos <= query pos.
    ``causal`` may be a block length ``B`` > 1 (an int, not a bool):
    causal over blocks of ``B`` positions, key block <= query block, so a
    query sees its whole block — generation by diffusion over blocks;
    ``True`` is ``B = 1``."""
    qi = qi0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    ki = ki0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = ki < t_k
    if causal:
        b = int(causal)
        valid &= qi >= ki if b == 1 else qi // b >= ki // b
    return valid


def _tile_live(i, j, bq, bk, causal):
    """False for a (query tile i, key tile j) wholly above the (block)
    diagonal: its first key lies past what the tile's last query sees."""
    last, b = i * bq + bq - 1, int(causal)
    if b > 1:
        last = last // b * b + b - 1
    return j * bk <= last


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, bq, bk, t_k, causal):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)

    # causal: tiles entirely above the diagonal contribute nothing — skip
    # their MXU work (roughly halves the FLOPs of the causal path)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        valid = _causal_valid(bq, bk, i * bq, j * bk, t_k, causal)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        pl.when(_tile_live(i, j, bq, bk, causal))(_tile)
    else:
        _tile()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:, :1] + jnp.log(safe_l)).astype(lse_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, bq, bk, t_k, causal):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    i = pl.program_id(1)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        valid = _causal_valid(bq, bk, i * bq, j * bk, t_k, causal)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    if causal:
        pl.when(_tile_live(i, j, bq, bk, causal))(_tile)
    else:
        _tile()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, bq, bk, t_k, causal):
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    j = pl.program_id(1)
    def _tile():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        valid = _causal_valid(bq, bk, i * bq, j * bk, t_k, causal)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc[...] += jnp.dot(p.astype(do.dtype).T, do,
                               preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jnp.dot(ds.astype(q.dtype).T, q,
                               preferred_element_type=jnp.float32)

    if causal:
        pl.when(_tile_live(i, j, bq, bk, causal))(_tile)
    else:
        _tile()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dqkv_single_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dk_ref, dv_ref, *, scale, t_k, causal):
    """Fused single-tile backward (whole sequence in one block): computes
    s/p once and does 5 matmuls where the two-kernel tiled path recomputes
    s/p per kernel and does 7 — used whenever T fits a single block, the
    common short-context training case."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    valid = _causal_valid(q.shape[0], k.shape[0], 0, 0, t_k, causal)
    s = jnp.where(valid, s, NEG_INF)
    p = jnp.exp(s - lse)
    pb = p.astype(do.dtype)
    dv_ref[0] = jnp.dot(pb.T, do,
                        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    dq_ref[0] = jnp.dot(ds, k,
                        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[0] = jnp.dot(ds.T, q,
                        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       *, scale, t_k, causal):
    """Single-tile forward (whole sequence in one block): plain softmax —
    no online-rescale machinery (m/l carry, acc correction), which is pure
    VPU overhead when nk == 1."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    valid = _causal_valid(q.shape[0], k.shape[0], 0, 0, t_k, causal)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.maximum(l, 1e-30)
    o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o_ref[0] = (o / safe_l).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(safe_l)).astype(lse_ref.dtype)


def _prep(q, k, v, block_q, block_k):
    """[B,T,H,D] → T-padded [BH,Tp,D].  D is kept as-is: a full-size minor
    block dim is always accepted by Mosaic, and zero-padding D to 128 would
    double the matmul FLOPs for the common head_dim=64."""
    b, t_q, h, d = q.shape
    tqp = _round_up(t_q, block_q)
    tkp = _round_up(k.shape[1], block_k)

    def to_bh(x, tp):
        x = x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
        return jnp.pad(x, ((0, 0), (0, tp - x.shape[1]), (0, 0)))

    return to_bh(q, tqp), to_bh(k, tkp), to_bh(v, tkp)


def _from_bh(x, b, h, t, d):
    return x[:, :t, :d].reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    from paddle_tpu.ops.pallas import resolve_interpret

    interpret = resolve_interpret(interpret)
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # sublane-aligned tiles, clamped so short sequences don't pad up to a
    # full default block (seq 16 with block 512 would do 1000x the work)
    block_q = min(_round_up(block_q, 8), _round_up(t_q, 8))
    block_k = min(_round_up(block_k, 8), _round_up(t_k, 8))
    qp, kp, vp = _prep(q, k, v, block_q, block_k)
    bh, tqp, dpad = qp.shape
    tkp = kp.shape[1]
    nq, nk = tqp // block_q, tkp // block_k

    if nq == 1 and nk == 1:
        bspec = lambda blk: pl.BlockSpec((1, blk, dpad), lambda b: (b, 0, 0))
        o, lse = pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale, t_k=t_k,
                              causal=causal),
            name="flash_attention_fwd",
            grid=(bh,),
            in_specs=[bspec(block_q), bspec(block_k), bspec(block_k)],
            out_specs=[bspec(block_q),
                       pl.BlockSpec((1, block_q, 1), lambda b: (b, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tqp, dpad), q.dtype),
                jax.ShapeDtypeStruct((bh, tqp, 1), jnp.float32),
            ],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
        )(qp, kp, vp)
        return o, lse, (qp, kp, vp)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, bq=block_q, bk=block_k, t_k=t_k,
        causal=causal,
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dpad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dpad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dpad), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dpad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tqp, dpad), q.dtype),
            jax.ShapeDtypeStruct((bh, tqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dpad), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return o, lse, (qp, kp, vp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=1024, block_k=1024, interpret=None):
    # default tiles 1024x1024 (chosen on a v5e, device-timed, before the
    # ledger; no cell sweeps them): the bwd kernels' f32 [bq, bk]
    # intermediates stay within VMEM
    """Flash attention on [B, T, H, D] tensors.

    Numerically equal (to fp tolerance) to
    ``attention.dot_product_attention(q, k, v, causal mask)``; O(T) memory.
    ``causal``: False | True | a block length > 1 (causal over blocks:
    ``_causal_valid``).
    ``interpret=None`` auto-selects interpreter mode off-TPU.
    """
    b, t_q, h, d = q.shape
    o, _, _ = _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret)
    return _from_bh(o, b, h, t_q, d)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    b, t_q, h, d = q.shape
    o, lse, (qp, kp, vp) = _fwd_impl(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return _from_bh(o, b, h, t_q, d), (qp, kp, vp, o, lse, (b, t_q, k.shape[1], h, d))


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    from paddle_tpu.ops.pallas import resolve_interpret

    interpret = resolve_interpret(interpret)
    qp, kp, vp, o, lse, (b, t_q, t_k, h, d) = res
    scale = scale if scale is not None else d ** -0.5
    block_q = min(_round_up(block_q, 8), _round_up(t_q, 8))  # match fwd
    block_k = min(_round_up(block_k, 8), _round_up(t_k, 8))
    bh, tqp, dpad = qp.shape
    tkp = kp.shape[1]
    nq, nk = tqp // block_q, tkp // block_k

    do = g.transpose(0, 2, 1, 3).reshape(bh, t_q, d)
    do = jnp.pad(do, ((0, 0), (0, tqp - t_q), (0, 0)))
    # delta_i = sum_d dO_i . O_i  (padded rows have dO == 0 -> delta == 0)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    if nq == 1 and nk == 1:
        bspec = lambda blk: pl.BlockSpec((1, blk, dpad), lambda b: (b, 0, 0))
        rspec = pl.BlockSpec((1, block_q, 1), lambda b: (b, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_dqkv_single_kernel, scale=scale,
                              t_k=t_k, causal=causal),
            grid=(bh,),
            in_specs=[bspec(block_q), bspec(block_k), bspec(block_k),
                      bspec(block_q), rspec, rspec],
            out_specs=[bspec(block_q), bspec(block_k), bspec(block_k)],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tqp, dpad), qp.dtype),
                jax.ShapeDtypeStruct((bh, tkp, dpad), kp.dtype),
                jax.ShapeDtypeStruct((bh, tkp, dpad), vp.dtype),
            ],
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
        )(qp, kp, vp, do, lse, delta)
        return (
            _from_bh(dq, b, h, t_q, d),
            _from_bh(dk, b, h, t_k, d),
            _from_bh(dv, b, h, t_k, d),
        )

    qspec = pl.BlockSpec((1, block_q, dpad), lambda b, i, j: (b, i, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=block_q, bk=block_k,
                          t_k=t_k, causal=causal),
        grid=(bh, nq, nk),
        in_specs=[
            qspec,
            pl.BlockSpec((1, block_k, dpad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dpad), lambda b, i, j: (b, j, 0)),
            qspec, rowspec, rowspec,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, tqp, dpad), qp.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dpad), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp, do, lse, delta)

    # dK/dV: grid iterates q-blocks innermost, k-block fixed per step
    kspec = pl.BlockSpec((1, block_k, dpad), lambda b, j, i: (b, j, 0))
    qspec2 = pl.BlockSpec((1, block_q, dpad), lambda b, j, i: (b, i, 0))
    rowspec2 = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=block_q, bk=block_k,
                          t_k=t_k, causal=causal),
        grid=(bh, nk, nq),
        in_specs=[qspec2, kspec, kspec, qspec2, rowspec2, rowspec2],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tkp, dpad), kp.dtype),
            jax.ShapeDtypeStruct((bh, tkp, dpad), vp.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dpad), jnp.float32),
            pltpu.VMEM((block_k, dpad), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp, do, lse, delta)

    return (
        _from_bh(dq, b, h, t_q, d),
        _from_bh(dk, b, h, t_k, d),
        _from_bh(dv, b, h, t_k, d),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_reference(q, k, v, causal=False, scale=None):
    """Pure-jnp oracle of :func:`flash_attention`: exact masked softmax
    attention on [B, T, H, D], f32 accumulation (the two-implementations
    test contract — see ``tools/check_kernel_parity.py``)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        ok = (jnp.arange(t_q)[:, None] // int(causal)
              >= jnp.arange(t_k)[None, :] // int(causal))
        s = jnp.where(ok[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
