"""Fused CTC forward-backward + greedy decode (Pallas TPU) — successor
of the reference's warp-ctc integration (``hl_warpctc_wrap.cc``,
``WarpCTCLayer``) as a hand kernel instead of a ``lax.scan``.

The scan in ``ops/ctc.py`` runs the alpha recursion as T tiny [B, 2L+1]
host-graph ops and gets its gradient from ``jax.grad`` re-tracing the
whole recursion (two passes over the [B, T, V] slab plus a scan of
scatter-adds in the backward).  Here ONE pallas program walks the time
grid twice per batch block — grid (B-blocks, 2, T):

- phase 0 ascends t: (optional) log-softmax on the [bb, V] frame, the
  emission gather at the extended labels, and the alpha recursion with
  the per-row freeze at ``input_lengths`` — the alpha slab [T, bb, S]
  stays in VMEM scratch, never in HBM; the per-row log-likelihood is
  banked at the last step;
- phase 1 descends t: the beta recursion (carried in scratch, the next
  frame's emission banked from the previous step) and the hand-derived
  CTC gradient gamma = exp(alpha + beta - ll), scattered back to the
  class axis and written as the [B, T, V] cotangent — warp-ctc's
  ``grad = y - gamma/p`` form when ``normalize`` (logits in), or
  ``-gamma/p`` for pre-normalized log-probs.

The transition tables (extended labels, validity, skip rule) come from
``ops/ctc.ctc_tables`` — built once, shared with the scan oracle.  The
custom_vjp stores the kernel-computed gradient as the only residual, so
the backward is a single multiply by the incoming cotangent.

``impl="auto"`` routes to the kernel on TPU and to the references (the
``ops/ctc.py`` scans) everywhere else — the CPU production path and the
ablation's bit-identity anchor, per the TPP kernel convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.ctc import (NEG_INF, compact_decoded, ctc_greedy_decode,
                                ctc_loss, ctc_tables)
from paddle_tpu.ops.pallas import (pad_axis, resolve_impl,
                                   resolve_interpret, round_up)


_SUBLANES = 8


def _batch_block(b: int, want: int = _SUBLANES) -> tuple[int, int]:
    """(block_rows, padded_batch): the batch rides the sublane axis of
    every block (S and V ride lanes), so Mosaic needs block_rows to be a
    multiple of 8 — the batch is zero-padded up to one, and the block is
    the largest multiple of 8 <= ``want`` that divides the padded size.
    Padded rows have input length 0: they freeze at t=0 and the callers
    slice their outputs away."""
    bpad = round_up(b, _SUBLANES)
    bb = max(min(want, bpad) // _SUBLANES * _SUBLANES, _SUBLANES)
    while bpad % bb:
        bb -= _SUBLANES
    return bb, bpad


def _logaddexp(a, b):
    m = jnp.maximum(a, b)
    return m + jnp.log1p(jnp.exp(jnp.minimum(a, b) - m))


def _ctc_kernel(logp_ref, ext_ref, skip_ref, valid_ref, ilen_ref, llen_ref,
                loss_ref, grad_ref,
                alpha_all, alpha_c, beta_c, emit_c, ll_c,
                *, tt, s, v, normalize):
    p = pl.program_id(1)
    t = pl.program_id(2)

    ext = ext_ref[...]                       # [bb, S] i32
    can_skip = skip_ref[...]                 # [bb, S] f32
    ext_valid = valid_ref[...]               # [bb, S] f32
    ilen = ilen_ref[...]                     # [bb, 1] i32
    llen = llen_ref[...]                     # [bb, 1] i32
    bb = ext.shape[0]

    z = logp_ref[0].astype(jnp.float32)                # [bb, V]
    if normalize:
        zm = jnp.max(z, axis=-1, keepdims=True)
        z = z - (zm + jnp.log(jnp.sum(jnp.exp(z - zm), axis=-1,
                                      keepdims=True)))
    # emission gather at the extended labels via a one-hot contraction
    # (TPU-friendly: no data-dependent gather on the lane axis)
    cmp = (ext[:, :, None]
           == jax.lax.broadcasted_iota(jnp.int32, (bb, s, v), 2))
    cmp_f = cmp.astype(jnp.float32)
    emit = jnp.sum(z[:, None, :] * cmp_f, axis=2)      # [bb, S]
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (bb, s), 1)
    neg = jnp.full((bb, s), NEG_INF, jnp.float32)

    def final_init():
        # beta at a row's LAST valid frame (emission excluded): only the
        # final blank / final label positions have non-empty suffixes
        fin = (s_idx == 2 * llen) | ((s_idx == 2 * llen - 1) & (llen > 0))
        return jnp.where(fin, 0.0, NEG_INF)

    @pl.when(p == 0)
    def _alpha_phase():
        @pl.when(t == 0)
        def _a0():
            a0 = jnp.where(
                s_idx == 0, emit,
                jnp.where((s_idx == 1) & (llen > 0), emit, neg))
            alpha_c[...] = a0
            alpha_all[0] = a0

        @pl.when(t > 0)
        def _arec():
            prev = alpha_c[...]
            from1 = jnp.concatenate([neg[:, :1], prev[:, :-1]], axis=1)
            from2 = jnp.concatenate([neg[:, :2], prev[:, :-2]], axis=1)
            from2 = jnp.where(can_skip > 0, from2, NEG_INF)
            new = _logaddexp(_logaddexp(prev, from1), from2) + emit
            new = jnp.where(ext_valid > 0, jnp.maximum(new, NEG_INF),
                            NEG_INF)
            a = jnp.where(t < ilen, new, prev)
            alpha_c[...] = a
            alpha_all[t] = a

        @pl.when(t == tt - 1)
        def _ll():
            a = alpha_c[...]
            idx_last = 2 * llen                        # [bb, 1]
            a_last = jnp.sum(jnp.where(s_idx == idx_last, a, 0.0),
                             axis=1, keepdims=True)
            a_prev = jnp.sum(
                jnp.where(s_idx == jnp.maximum(idx_last - 1, 0), a, 0.0),
                axis=1, keepdims=True)
            a_prev = jnp.where(llen > 0, a_prev, NEG_INF)
            ll = jnp.maximum(_logaddexp(a_last, a_prev), NEG_INF)
            ll_c[...] = ll
            loss_ref[...] = -ll

    @pl.when(p == 0)
    def _grad_zero():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    @pl.when(p == 1)
    def _beta_phase():
        tr = tt - 1 - t  # actual time index this step touches

        @pl.when(t == 0)
        def _binit():
            beta_c[...] = jnp.where(ilen - 1 == tt - 1, final_init(),
                                    NEG_INF)

        @pl.when(t > 0)
        def _brec():
            b_prev = beta_c[...]          # beta_{tr+1} (emission excl.)
            e_next = emit_c[...]          # emission at tr+1
            term0 = b_prev + e_next
            term1 = jnp.concatenate([term0[:, 1:], neg[:, :1]], axis=1)
            term2 = jnp.concatenate([term0[:, 2:], neg[:, :2]], axis=1)
            skip2 = jnp.concatenate([can_skip[:, 2:],
                                     jnp.zeros_like(can_skip[:, :2])],
                                    axis=1)
            term2 = jnp.where(skip2 > 0, term2, NEG_INF)
            trans = jnp.maximum(
                _logaddexp(_logaddexp(term0, term1), term2), NEG_INF)
            trans = jnp.where(ext_valid > 0, trans, NEG_INF)
            beta_c[...] = jnp.where(ilen - 1 == tr, final_init(), trans)

        beta = beta_c[...]
        emit_c[...] = emit
        ll = ll_c[...]                                  # [bb, 1]
        feasible = ll > NEG_INF * 0.5
        gam = alpha_all[tr] + beta - ll
        gam = jnp.where(feasible, gam, NEG_INF)
        post = jnp.exp(jnp.minimum(gam, 0.0))           # [bb, S]
        contrib = jnp.sum(post[:, :, None] * cmp_f, axis=1)  # [bb, V]
        if normalize:
            total = jnp.sum(contrib, axis=-1, keepdims=True)
            grad = jnp.exp(z) * total - contrib         # y - gamma/p
        else:
            grad = -contrib
        grad = jnp.where(tr < ilen, grad, 0.0)
        grad_ref[0] = grad.astype(grad_ref.dtype)


def _ctc_call(log_probs, ext, can_skip, ext_valid, ilen, llen, *,
              normalize, interpret):
    b, tt, v = log_probs.shape
    s = ext.shape[1]
    bb, bpad = _batch_block(b)
    nb = bpad // bb
    # time-major slab: each grid step's block is one (bb, V) frame tile —
    # batch on sublanes, classes on lanes (a [B, T, V] layout would need
    # a size-1 time block on the sublane axis, which Mosaic refuses)
    logp_t = pad_axis(jnp.swapaxes(log_probs, 0, 1), 1, bpad)
    ext, can_skip, ext_valid, ilen, llen = (
        pad_axis(a, 0, bpad) for a in (ext, can_skip, ext_valid, ilen,
                                        llen))
    kernel = functools.partial(_ctc_kernel, tt=tt, s=s, v=v,
                               normalize=normalize)
    # phase 0 walks t ascending, phase 1 descending — one index map
    row = lambda i, p, t: (t * (1 - p) + (tt - 1 - t) * p, i, 0)  # noqa: E731
    per_b = lambda i, p, t: (i, 0)                                # noqa: E731
    loss, grad = pl.pallas_call(
        kernel,
        grid=(nb, 2, tt),
        in_specs=[
            pl.BlockSpec((1, bb, v), row),               # log-probs/logits
            pl.BlockSpec((bb, s), per_b),                # extended labels
            pl.BlockSpec((bb, s), per_b),                # skip rule
            pl.BlockSpec((bb, s), per_b),                # position validity
            pl.BlockSpec((bb, 1), per_b),                # input lengths
            pl.BlockSpec((bb, 1), per_b),                # label lengths
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), per_b),                # loss
            pl.BlockSpec((1, bb, v), row),               # d loss / d input
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bpad, 1), jnp.float32),
            jax.ShapeDtypeStruct((tt, bpad, v), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tt, bb, s), jnp.float32),   # alpha slab (resident)
            pltpu.VMEM((bb, s), jnp.float32),       # alpha carry
            pltpu.VMEM((bb, s), jnp.float32),       # beta carry
            pltpu.VMEM((bb, s), jnp.float32),       # next-frame emission
            pltpu.VMEM((bb, 1), jnp.float32),       # banked log-lik
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(logp_t, ext, can_skip, ext_valid, ilen, llen)
    return loss[:b, 0], jnp.swapaxes(grad, 0, 1)[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ctc_fused(log_probs, ext, can_skip, ext_valid, ilen, llen,
               normalize, interpret):
    loss, _ = _ctc_call(log_probs, ext, can_skip, ext_valid, ilen, llen,
                        normalize=normalize, interpret=interpret)
    return loss


def _ctc_fused_fwd(log_probs, ext, can_skip, ext_valid, ilen, llen,
                   normalize, interpret):
    loss, grad = _ctc_call(log_probs, ext, can_skip, ext_valid, ilen,
                           llen, normalize=normalize, interpret=interpret)
    return loss, grad


def _ctc_fused_bwd(normalize, interpret, grad, g):
    # the forward-backward kernel already produced d loss_b / d input:
    # the vjp is one broadcast multiply by the incoming cotangent
    return (g[:, None, None] * grad, None, None, None, None, None)


_ctc_fused.defvjp(_ctc_fused_fwd, _ctc_fused_bwd)


def ctc_loss_fused(log_probs: jax.Array, input_lengths: jax.Array,
                   labels: jax.Array, label_lengths: jax.Array,
                   blank: int = 0, normalize: bool = False,
                   impl: str = "auto",
                   interpret: bool | None = None) -> jax.Array:
    """Fused CTC negative log-likelihood with a hand-derived gradient.

    Same contract as ``ops.ctc.ctc_loss`` ([B] losses), plus
    ``normalize=True`` to accept raw logits and fold the log-softmax
    into the kernel (the warp-ctc entry's form).  ``impl="auto"`` runs
    the Pallas forward-backward kernel on TPU and the scan references on
    other backends (bit-identical to the unfused path there)."""
    if resolve_impl(impl, "ctc_loss_fused") == "reference":
        return ctc_loss_fused_reference(log_probs, input_lengths, labels,
                                        label_lengths, blank, normalize)
    interpret = resolve_interpret(interpret)
    ext, ext_valid, can_skip = ctc_tables(labels, label_lengths, blank)
    return _ctc_fused(
        log_probs.astype(jnp.float32), ext,
        can_skip.astype(jnp.float32), ext_valid.astype(jnp.float32),
        input_lengths.astype(jnp.int32)[:, None],
        label_lengths.astype(jnp.int32)[:, None],
        normalize, interpret)


def ctc_loss_fused_reference(log_probs, input_lengths, labels,
                             label_lengths, blank: int = 0,
                             normalize: bool = False) -> jax.Array:
    """Pure-jnp oracle of :func:`ctc_loss_fused`: the ``ops/ctc.py``
    scan (gradient via jax.grad), with the log-softmax applied outside
    when ``normalize`` — exactly the unfused production path."""
    if normalize:
        log_probs = jax.nn.log_softmax(log_probs, axis=-1)
    return ctc_loss(log_probs, input_lengths, labels, label_lengths, blank)


# ---------------------------------------------------------------------------
# greedy decode
# ---------------------------------------------------------------------------


def _decode_kernel(logp_ref, ilen_ref, ids_ref, keep_ref, prev_scr,
                   *, blank):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        prev_scr[...] = jnp.full_like(prev_scr, -1)

    z = logp_ref[0]                                            # [bb, V]
    best = jnp.argmax(z, axis=-1).astype(jnp.int32)[:, None]   # [bb, 1]
    prev = prev_scr[...]
    valid = t < ilen_ref[...]
    keep = (best != blank) & (best != prev) & valid
    ids_ref[0] = best
    keep_ref[0] = keep.astype(jnp.int32)
    prev_scr[...] = best


_DECODE_BATCH_BLOCK = 512


def ctc_greedy_decode_fused(log_probs: jax.Array,
                            input_lengths: jax.Array, blank: int = 0,
                            impl: str = "auto",
                            interpret: bool | None = None):
    """Fused best-path decode for the serving/eval path: argmax and the
    blank/repeat collapse run inside one time-grid kernel (the [B, T, V]
    slab is read once; only the [B, T] ids/keep pair reaches HBM), then
    the kept frames are front-compacted.  Same contract as
    ``ops.ctc.ctc_greedy_decode``: (ids [B, T] padded with -1,
    lengths [B])."""
    if resolve_impl(impl, "ctc_greedy_decode_fused") == "reference":
        return ctc_greedy_decode_fused_reference(log_probs, input_lengths,
                                                 blank)
    interpret = resolve_interpret(interpret)
    b, tt, v = log_probs.shape
    bb, bpad = _batch_block(b, _DECODE_BATCH_BLOCK)
    kernel = functools.partial(_decode_kernel, blank=blank)
    # time-major like the loss kernel: one (bb, V) frame tile per step;
    # the per-frame outputs are (bb, 1) columns of a [T, B, 1] slab
    step = lambda i, t: (t, i, 0)   # noqa: E731
    ids, keep = pl.pallas_call(
        kernel,
        grid=(bpad // bb, tt),
        in_specs=[
            pl.BlockSpec((1, bb, v), step),
            pl.BlockSpec((bb, 1), lambda i, t: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bb, 1), step),
            pl.BlockSpec((1, bb, 1), step),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tt, bpad, 1), jnp.int32),
            jax.ShapeDtypeStruct((tt, bpad, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bb, 1), jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(pad_axis(jnp.swapaxes(log_probs, 0, 1), 1, bpad),
      pad_axis(input_lengths.astype(jnp.int32)[:, None], 0, bpad))
    ids, keep = (jnp.swapaxes(a[:, :b, 0], 0, 1) for a in (ids, keep))
    return compact_decoded(ids, keep.astype(bool))


def ctc_greedy_decode_fused_reference(log_probs, input_lengths,
                                      blank: int = 0):
    """Pure-jnp oracle of :func:`ctc_greedy_decode_fused` — the
    ``ops/ctc.py`` decode, shared compaction included."""
    return ctc_greedy_decode(log_probs, input_lengths, blank)
