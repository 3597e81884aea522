"""Ragged paged attention — the serving decode kernel over a paged KV-cache.

Training attention (``flash_attention.py``) assumes contiguous [B, T, H, D]
K/V.  Online serving can't: sequences join and retire every step
(continuous batching), lengths are ragged, and the cache must be allocated
in fixed-size **pages** so memory is reused without compaction (the
vLLM/"Ragged Paged Attention" design, PAPERS arxiv 2604.15464).  This
module owns that cache layout end to end:

- pools: ``k_pages``/``v_pages`` of shape **[H, P, page_size, D]** per
  layer (head-major: one page of all heads is ``H`` strided
  [page_size, D] tiles, sublane/lane aligned without any transpose of
  the resident cache);
- per-sequence **page tables**: ``page_table[b, i]`` = pool page holding
  positions ``[i*page_size, (i+1)*page_size)`` of sequence ``b``.  Page 0
  is the NULL/scratch page: never allocated to a sequence, it absorbs the
  writes of idle batch rows (so the decode step needs no host-side
  gather/compact of active slots) and backs unused table entries, which
  the kernel never reads;
- ``seq_lens[b]`` = tokens resident INCLUDING the one being decoded; the
  decode query is the last token, so the length mask alone is the causal
  mask.

Two interchangeable implementations of the attention itself:

- a Pallas TPU kernel, ``paged_attention_decode``.  A grid step takes ALL
  heads of one sequence and a block of ``N`` consecutive page slots:
  ``N`` pieces of ``[H, page_size, D]`` per pool, each fetched by the page
  id a scalar-prefetched list names (the pipeline double-buffers them, so
  the next block's fetch runs under this block's arithmetic), put side by
  side as ``[H, N * page_size, D]`` and folded into float32 running
  max / sum / accumulator by two batched MXU passes (the one decode query
  rides 8 sublanes).  The grid is ONE dimension over a work list built
  from ``seq_lens`` — every row's live blocks in order, an idle row one
  step that writes its zeros — and its length is a run-time value: a
  block wholly past ``seq_len`` is not a step at all, so nothing is
  fetched or multiplied for it, and no length costs a compile.  Slots of
  a row's last block past its last live page name that page again (never
  the null page: what they hold is multiplied by ``p == 0`` and must be
  finite).  ``N`` comes from ``decode_block_pages`` — from ``page_size``,
  ``head_dim``, ``num_heads``, the pool dtype and the table's width — for
  every caller alike: one MXU pass of score columns (128 tokens), less
  where VMEM or the table is smaller.  Measured (PERF.md §6, PR 27), the
  kernel is bound by the pipeline's bookkeeping per block spec and grid
  step (≈ 0.2 µs each), then by the pages' bytes: 35–39% and 66–68% of
  the HBM roofline in the benchmark's two serve cells.  It is exactly one Mosaic call per cache layer; the
  benchmark's reducers count ``tpu_custom_call``s inside ``jit_decode``
  (``loop_passes_per_token``) and charge this name's time to the
  roofline, so a split or a fusion over layers would misread both;
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
from paddle_tpu.ops.pallas import NEG_INF, round_up

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

_LANES = 128  # a vreg's lanes = an MXU pass's columns: the block's token quantum
_VMEM_BUDGET = 6 << 20  # bytes of a step's K/V buffers, of 16 MB scoped VMEM


def decode_block_pages(num_heads: int, page_size: int, head_dim: int,
                       itemsize: int, max_pages: int,
                       vmem_budget: int = _VMEM_BUDGET) -> int:
    """``N``: how many consecutive page slots one grid step of the decode
    kernel covers, from the shapes alone (the same for every caller).

    A block of ``N * page_size`` tokens is one MXU pass wide (128 score
    columns), so ``N = 128 // page_size``: a narrower block pays the
    pass for fewer tokens, a wider one reads (and multiplies) more dead
    tokens past a short context's end.  Capped by what fits the VMEM
    budget — per slot, K and V, double-buffered by the pipeline plus the
    block assembled for the matmuls, each ``[H, page, D]`` padded to the
    dtype's (sublane, 128) tile — and by the table's width; at least 1."""
    sublanes = 8 * max(4 // itemsize, 1)
    tile = (num_heads * round_up(page_size, sublanes)
            * round_up(head_dim, _LANES) * itemsize)
    fits = vmem_budget // (2 * 3 * tile)
    return int(max(1, min(_LANES // page_size, fits, max_pages)))


def _decode_work(page_table, seq_lens, n, page_size):
    """The kernel's work list, from the lengths: ``(rows, blocks, pages,
    steps)`` — for grid step ``g`` the batch row, the row's block and the
    ``n`` pool pages to fetch (``pages[g * n + j]``); ``steps`` of the
    ``B * ceil(maxp / n)`` entries are live.  A row takes one step per
    block that holds a live token, an idle row one (its zeros)."""
    b, maxp = page_table.shape
    lens = seq_lens.astype(jnp.int32)
    per_row = jnp.maximum(-(-lens // (n * page_size)), 1)
    ends = jnp.cumsum(per_row)
    g = jnp.arange(b * pl.cdiv(maxp, n), dtype=jnp.int32)
    # entries past ``steps`` are never run; they only have to stay in bounds
    rows = jnp.minimum(jnp.sum(g[:, None] >= ends[None, :], axis=1), b - 1)
    blocks = jnp.maximum(g - (ends - per_row)[rows], 0)
    # slots of a tail block past the row's last live page repeat that page
    last = jnp.maximum(-(-lens // page_size) - 1, 0)[rows]
    slots = jnp.minimum(blocks[:, None] * n + jnp.arange(n), last[:, None])
    pages = page_table.astype(jnp.int32)[rows[:, None], slots]
    return (rows.astype(jnp.int32), blocks.astype(jnp.int32),
            pages.reshape(-1), ends[-1])


def _decode_kernel(rows_ref, blocks_ref, pages_ref, lens_ref, q_ref, *refs,
                   scale, page_size, n):
    k_refs, v_refs = refs[:n], refs[n:2 * n]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * n:]
    g = pl.program_id(0)
    i = blocks_ref[g]
    seq_len = lens_ref[rows_ref[g]]
    block = n * page_size

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * block < seq_len)  # false only for an idle row's one step
    def _block():
        q = q_ref[0]  # [H, 8, D] — each head's query broadcast over sublanes
        k = jnp.concatenate([r[...] for r in k_refs], axis=1)  # [H, block, D]
        v = jnp.concatenate([r[...] for r in v_refs], axis=1)
        s = jnp.einsum("hqd,hkd->hqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        pos = i * block + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hqk,hkd->hqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when((i + 1) * block >= seq_len)  # the row's last step
    def _finalize():
        # idle rows (seq_len 0) never accumulated: l == 0 -> output 0
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


def _kernel_impl(q, k_pages, v_pages, page_table, seq_lens, scale,
                 interpret):
    b, h, d = q.shape
    _, _, page_size, _ = k_pages.shape
    n = decode_block_pages(h, page_size, d, k_pages.dtype.itemsize,
                           page_table.shape[1])
    rows, blocks, pages, steps = _decode_work(page_table, seq_lens, n,
                                              page_size)
    qb = jnp.broadcast_to(q[:, :, None, :], (b, h, _Q_SUBLANES, d))
    row = pl.BlockSpec(
        (1, h, _Q_SUBLANES, d),
        lambda g, rows, blocks, pages, lens: (rows[g], 0, 0, 0))
    slots = [pl.BlockSpec(
        (h, None, page_size, d),
        lambda g, rows, blocks, pages, lens, j=j: (0, pages[g * n + j], 0, 0))
        for j in range(n)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # the work list and seq_lens ride SMEM
        grid=(steps,),
        in_specs=[row, *slots, *slots],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, _Q_SUBLANES, d), jnp.float32),
            pltpu.VMEM((h, _Q_SUBLANES, _LANES), jnp.float32),
            pltpu.VMEM((h, _Q_SUBLANES, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          n=n),
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, _Q_SUBLANES, d), q.dtype),
        compiler_params=tpu_compiler_params(
            # in order: a row's steps share its accumulators and output
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(rows, blocks, pages, seq_lens.astype(jnp.int32),
      qb, *[k_pages] * n, *[v_pages] * n)
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
