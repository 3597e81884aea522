"""Ragged paged attention — the serving decode kernel over a paged KV-cache.

Training attention (``flash_attention.py``) assumes contiguous [B, T, H, D]
K/V.  Online serving can't: sequences join and retire every step
(continuous batching), lengths are ragged, and the cache must be allocated
in fixed-size **pages** so memory is reused without compaction (the
vLLM/"Ragged Paged Attention" design, PAPERS arxiv 2604.15464).  This
module owns that cache layout end to end:

- pools: ``k_pages``/``v_pages`` of shape **[H, P, page_size, D]** per
  layer (head-major so a kernel block is one (head, page) pair — a
  [page_size, D] tile, sublane/lane aligned without any transpose of the
  resident cache);
- per-sequence **page tables**: ``page_table[b, i]`` = pool page holding
  positions ``[i*page_size, (i+1)*page_size)`` of sequence ``b``.  Page 0
  is the NULL/scratch page: never allocated to a sequence, it absorbs the
  writes of idle batch rows (so the decode step needs no host-side
  gather/compact of active slots) and backs unused table entries (so
  block fetches of skipped pages stay in-bounds);
- ``seq_lens[b]`` = tokens resident INCLUDING the one being decoded; the
  decode query is the last token, so the length mask alone is the causal
  mask.

Two interchangeable implementations of the attention itself:

- a Pallas TPU kernel (grid (B, H, pages); the page table and lengths ride
  scalar prefetch so each block fetch DMAs exactly the page the table
  names — ragged batches never touch pages past ``seq_len``); the single
  decode query is broadcast over 8 sublanes to satisfy the f32 tile
  constraint (the 8x redundant VPU/MXU work is free: decode attention is
  bound by the K/V page reads, not compute);
- a pure-jnp reference (gather pages by table, mask, softmax) that is the
  CPU/interpret fallback AND the oracle the kernel is tested against.

``impl="auto"`` picks the kernel on TPU and the reference elsewhere,
mirroring the stub-fallback stance of this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import NEG_INF

_Q_SUBLANES = 8  # single decode query padded to a full f32 sublane tile


# -- cache layout helpers ------------------------------------------------------


def init_kv_pages(num_layers: int, num_heads: int, num_pages: int,
                  page_size: int, head_dim: int, dtype=jnp.float32):
    """(k_pages, v_pages) pools of shape [L, H, P, page_size, D], zeroed.

    Page 0 of every pool is the null/scratch page (see module docstring);
    allocators must hand out ids from 1."""
    shape = (num_layers, num_heads, num_pages, page_size, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def write_decode_kv(k_pages, v_pages, k, v, page_table, positions):
    """Write one new token's K/V per batch row into a single layer's pools.

    k/v: [B, H, D]; k_pages/v_pages: [H, P, page_size, D];
    page_table: [B, max_pages]; positions: [B] absolute token index.
    Idle rows (all-zero table rows) land in the null page."""
    ps = k_pages.shape[2]
    pages = jnp.take_along_axis(
        page_table, (positions // ps)[:, None], axis=1)[:, 0]
    offs = positions % ps
    k_pages = k_pages.at[:, pages, offs].set(k.swapaxes(0, 1))
    v_pages = v_pages.at[:, pages, offs].set(v.swapaxes(0, 1))
    return k_pages, v_pages


def write_prefill_kv(k_pages, v_pages, ks, vs, page_table, seq_lens,
                     starts=None):
    """Scatter a prefilled prompt batch into the stacked pools.

    ks/vs: [L, B, T, H, D] (padded prompts); k_pages/v_pages:
    [L, H, P, page_size, D]; page_table: [B, max_pages]; seq_lens: [B].
    Positions at or past ``seq_lens`` are redirected to the null page.

    ``starts`` [B] (chunked prefill / cached-prefix tails) offsets row
    ``b``'s writes to absolute positions ``starts[b] + [0, seq_lens[b])``
    — the same scatter, shifted; None keeps the from-zero behaviour
    bit-identically."""
    _, b, t, _, _ = ks.shape
    ps = k_pages.shape[3]
    t_idx = jnp.arange(t)
    valid = t_idx[None, :] < seq_lens[:, None]  # [B, T]
    pos = (jnp.broadcast_to(t_idx[None, :], (b, t)) if starts is None
           else starts[:, None] + t_idx[None, :])
    # mask the page slot BEFORE the gather: an offset row's padding can
    # point past the table row (starts + t >= max_pages * page_size)
    page_slot = jnp.where(valid, pos // ps, 0)
    pages = jnp.where(valid,
                      jnp.take_along_axis(page_table, page_slot, axis=1), 0)
    offs = pos % ps
    k_pages = k_pages.at[:, :, pages, offs].set(ks.transpose(0, 3, 1, 2, 4))
    v_pages = v_pages.at[:, :, pages, offs].set(vs.transpose(0, 3, 1, 2, 4))
    return k_pages, v_pages


def paged_prefill_attention(q, k_pages, v_pages, page_table, starts,
                            seq_lens, scale=None):
    """Chunk-prefill attention: queries over the whole resident paged
    context (prefix caching + chunked prefill's compute path).

    q: [B, C, H, D] — row ``b``'s queries sit at absolute positions
    ``starts[b] + t`` and attend causally over positions ``[0,
    starts[b] + t]`` of the paged cache: earlier chunks AND any shared
    cached prefix included.  The chunk's own K/V must already be written
    (``write_prefill_kv`` with ``starts``).  ``seq_lens`` [B] is the
    valid NEW tokens per row; rows with 0 produce zeros, query positions
    past it produce garbage the caller discards.  Returns [B, C, H, D].

    Pure jnp (gather + einsum) by design: it is the production CPU path
    and, under jit, lowers to an XLA gather + batched matmul on TPU —
    chunked prefill is bound by the chunk's dense matmuls, while the
    per-step decode hot loop keeps the Pallas kernel above."""
    h, _, ps, d = k_pages.shape
    b, c, _, _ = q.shape
    maxp = page_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # [H, B, maxp, ps, D] -> [B, H, maxp*ps, D]
    k = k_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        b, h, maxp * ps, d)
    v = v_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        b, h, maxp * ps, d)
    s = jnp.einsum("bchd,bhkd->bhck", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = starts[:, None] + jnp.arange(c)[None, :]   # [B, C] absolute
    kpos = jnp.arange(maxp * ps)
    # causal over ABSOLUTE positions: every key at or before the query
    # was written by the prefix/chunks already resident — stale pages
    # past the write frontier sit strictly above qpos and are masked
    mask = kpos[None, None, None, :] <= qpos[:, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhck,bhkd->bhcd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    out = jnp.where(seq_lens[:, None, None, None] > 0, out, 0.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# -- reference implementation --------------------------------------------------


def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     seq_lens, scale=None):
    """Pure-jnp oracle: gather each sequence's pages, mask, softmax.

    q: [B, H, D] (one decode token per row); k_pages/v_pages:
    [H, P, page_size, D]; returns [B, H, D].  Rows with ``seq_lens == 0``
    produce zeros (idle slots), not NaNs."""
    h, _, ps, d = k_pages.shape
    b, maxp = page_table.shape
    scale = scale if scale is not None else d ** -0.5
    # [H, B, maxp, ps, D] -> [B, H, maxp*ps, D]
    k = k_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        b, h, maxp * ps, d)
    v = v_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        b, h, maxp * ps, d)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(maxp * ps)
    s = jnp.where(pos[None, None, :] < seq_lens[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhk,bhkd->bhd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    # fully-masked rows: NEG_INF is finite, so p == 1 everywhere and the
    # sum above is a mean of null/stale pages — zero them explicitly to
    # match the kernel's l == 0 path
    out = jnp.where(seq_lens[:, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)


# -- the Pallas kernel ---------------------------------------------------------


def _decode_kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size):
    b = pl.program_id(0)
    i = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = lens_ref[b]

    # pages entirely past the sequence contribute nothing: skip their
    # compute (their block fetch targets the null page — in-bounds, unread)
    @pl.when(i * page_size < seq_len)
    def _page():
        q = q_ref[0, 0]  # [8, D] — the query broadcast over sublanes
        k = k_ref[0, 0]  # [page_size, D]
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        pos = i * page_size + lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(i == npages - 1)
    def _finalize():
        # idle rows (seq_len 0) never accumulated: l == 0 -> output 0
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _kernel_impl(q, k_pages, v_pages, page_table, seq_lens, scale,
                 interpret):
    b, h, d = q.shape
    _, _, page_size, _ = k_pages.shape
    maxp = page_table.shape[1]
    qb = jnp.broadcast_to(q[:, :, None, :], (b, h, _Q_SUBLANES, d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, seq_lens ride SMEM
        grid=(b, h, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, _Q_SUBLANES, d),
                         lambda bi, hi, pi, pt, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda bi, hi, pi, pt, lens: (hi, pt[bi, pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda bi, hi, pi, pt, lens: (hi, pt[bi, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, _Q_SUBLANES, d),
                               lambda bi, hi, pi, pt, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_Q_SUBLANES, d), jnp.float32),
            pltpu.VMEM((_Q_SUBLANES, 128), jnp.float32),
            pltpu.VMEM((_Q_SUBLANES, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size),
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, _Q_SUBLANES, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      qb, k_pages, v_pages)
    return out[:, :, 0, :]


def ragged_paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                           scale=None, impl="auto", interpret=None):
    """Decode-step attention of q [B, H, D] over a paged KV-cache.

    ``impl``: "kernel" (Pallas; ``interpret=None`` auto-selects
    interpreter mode off-TPU, the flash_attention convention), "reference"
    (pure jnp — the production CPU path: interpret-mode Pallas is a
    per-block Python loop, far too slow to serve from), or "auto"
    (kernel on TPU, reference elsewhere)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    from paddle_tpu.ops.pallas import resolve_impl, resolve_interpret

    if resolve_impl(impl, "ragged_paged_attention") == "reference":
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, seq_lens, scale=scale)
    return _kernel_impl(q, k_pages, v_pages, page_table, seq_lens, scale,
                        resolve_interpret(interpret))
