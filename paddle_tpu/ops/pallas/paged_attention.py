"""Ragged paged attention — the serving decode kernel over a paged KV-cache.

Training attention (``flash_attention.py``) assumes contiguous [B, T, H, D]
K/V.  Online serving can't: sequences join and retire every step
(continuous batching), lengths are ragged, and the cache must be allocated
in fixed-size **pages** so memory is reused without compaction (the
vLLM/"Ragged Paged Attention" design, PAPERS arxiv 2604.15464).  This
module owns that cache layout end to end:

- pools: ONE ``k_pool`` and ONE ``v_pool`` for the whole model, of shape
  **[cache_layers, H/g, P, page_size, g·D]** (``kv_pool_shape``, the only
  place the shape is spelt).  ``g = 128 // D`` heads (at least one, at
  most the model's ``H``) sit side by side in the lanes: head ``h`` is
  lane group ``h // g``, lanes ``[(h % g)·D, (h % g + 1)·D)``, and ``H``
  is padded up to whole groups with heads that stay zero.  So the two
  minor dimensions ``[page_size, g·D]`` are whole (sublane, 128-lane)
  tiles of the pool dtype wherever ``head_dim`` divides 128 or is a
  multiple of it: the layout the pools rest in between programs, the
  row-major layout a Mosaic call takes and the layout the writes produce
  are the same one, and XLA has nothing to re-tile.  At ``head_dim`` 128
  ``g`` is 1 and this is plain head-major ``[cache_layers, H, P,
  page_size, D]``.  No program value is ever "one layer's pool": every
  write and read below addresses the stacked pool at ``(cache_layer,
  page)``, so the pools ride the carry of the serving programs' loops
  and are updated where they are (PERF.md §6, PR 29: the copies this
  removed were 80% of a decode step);
- per-sequence **page tables**: ``page_table[b, i]`` = pool page holding
  positions ``[i*page_size, (i+1)*page_size)`` of sequence ``b``.  Page 0
  is the NULL/scratch page: never allocated to a sequence, it absorbs the
  scatters' writes of idle batch rows (so the decode step needs no
  host-side gather/compact of active slots; the decode kernel's own
  write skips such a row instead) and backs unused table entries, which
  the kernel never reads;
- ``seq_lens[b]`` = tokens resident INCLUDING the one being decoded; the
  decode query is the last token, so the length mask alone is the causal
  mask.

Two interchangeable implementations of the attention itself:

- a Pallas TPU kernel, ``paged_attention_decode``.  A grid step takes ALL
  heads of one sequence and a block of ``N`` consecutive page slots, and
  the kernel fetches them ITSELF: both pools enter the Mosaic call once,
  where they rest (``pl.ANY``), and a step's LIVE pages — a run-time
  count from ``seq_lens``; a slot past the row's last page is not
  fetched at all — are copied by ``pltpu.make_async_copy`` from
  ``pool.at[cache_layer, :, page]`` (``[H/g, page_size, g·D]``, the
  pools' resting layout; the layer, the work list and the page table are
  scalar-prefetched) into their place in one of two VMEM buffers a pool,
  ``[H/g, N * page_size, g·D]``.  Step ``s`` starts step ``s + 1``'s
  copies — another row's too — before it waits for its own, so the next
  block's fetch runs under this block's arithmetic.  (Fetched through
  ``N`` block specs a pool, as until PR 39, every page slot cost its
  pipeline bookkeeping each step and the block could not grow.)  The
  block is folded into float32 running max / sum / accumulator by two
  batched MXU passes; rows of a buffer past ``seq_len`` hold the page's
  own tail, an earlier step's pages or nothing yet — Inf and NaN bits
  maybe — so their scores are masked AND their V rows are made zero
  (``p == 0`` alone does not make ``0 · NaN`` zero).  The one decode
  query of a head rides 8 sublanes with zeros
  in the lanes of the other heads of its group, so a group's ``g`` heads
  are ``g · 8`` query rows whose extra products are exact zeros; each
  head's own lanes of the output are kept by the caller.  Where the cache
  holds fewer K/V heads than the model has query heads (``kv_heads``),
  the ``rep = H / kv_heads`` query heads of a K/V head ride those rows
  instead (``rep`` rounded up to 8): the pools, the pages copied and
  the kernel body are the same.  A pass that carries a BLOCK of ``T``
  positions a sequence (generation by diffusion over blocks:
  ``block_paged_attention``) needs no other kernel either: every
  position of the block sees the same context ``[0, block end)`` — the
  block's own K/V are written before the call — so its ``T`` positions
  ride as ``T * rep`` query heads of their K/V head and the length mask
  is still the whole mask.  The grid is ONE
  dimension over a work list built from ``seq_lens`` — every row's live
  blocks in order, an idle row one step that writes its zeros — and its
  length is a run-time value: a block wholly past ``seq_len`` is not a
  step at all, so nothing is fetched or multiplied for it, and no length
  costs a compile.  ``N`` comes from ``decode_block_pages`` — from
  ``page_size``, ``head_dim``, the cache's heads, the pool dtype and the
  table's width — for every caller alike: a step costs about half a
  microsecond beside its bytes, so the block is as many MXU passes of
  score columns (128 tokens) as make a step carry a megabyte of K and V
  at the cache's bytes a token — one pass where the cache holds sixteen
  lane groups, two at ten, eight (1,024 tokens) where it holds two —
  less where VMEM or the table is smaller.  The decode step's WRITE rides
  the same call (``decode_attention``): the work item whose block holds
  a row's write position puts the new token's K/V row into the page it
  has just copied into VMEM and sends that page back, one copy a pool,
  under the block's arithmetic — both pools are then outputs aliased to
  their inputs.  (As two XLA scatters ahead of the call the write cost
  0.08 µs a 128-lane ROW: 14% of a ``gpt2-large`` decode step for 24
  tokens a layer, PERF.md §6, PR 51.)  It is exactly one Mosaic call per
  cache layer;
  the benchmark's reducers count ``tpu_custom_call``s inside
  ``jit_decode`` (``loop_passes_per_token``) and charge this name's time
  to the roofline, so a split or a fusion over layers would misread both;
- a pure-jnp reference (gather pages by table, mask, softmax) that is the
  CPU/interpret fallback AND the oracle the kernel is tested against.

``impl="auto"`` picks the kernel on TPU and the reference elsewhere,
mirroring the stub-fallback stance of this package.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import NEG_INF, round_up

_Q_SUBLANES = 8  # single decode query padded to a full f32 sublane tile
_LANES = 128  # a vreg's lanes = an MXU pass's columns: the block's token quantum


# -- cache layout helpers ------------------------------------------------------


def head_group(num_heads: int, head_dim: int) -> int:
    """``g``: heads side by side in a pool row's lanes — as many as fill
    128 of them, never more than the model has."""
    return max(1, min(_LANES // head_dim, num_heads))


def kv_pool_shape(cache_layers: int, num_heads: int, num_pages: int,
                  page_size: int, head_dim: int) -> tuple:
    """THE shape of one K (or V) pool: [cache_layers, H/g, P, page_size,
    g·D], ``H`` rounded up to whole lane groups (module docstring)."""
    g = head_group(num_heads, head_dim)
    return (cache_layers, -(-num_heads // g), num_pages, page_size,
            g * head_dim)


def init_kv_pages(num_layers: int, num_heads: int, num_pages: int,
                  page_size: int, head_dim: int, dtype=jnp.float32):
    """(k_pool, v_pool) of ``kv_pool_shape``, zeroed.

    Page 0 of every pool is the null/scratch page (see module docstring);
    allocators must hand out ids from 1."""
    shape = kv_pool_shape(num_layers, num_heads, num_pages, page_size,
                          head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def copy_page(pool, src, dst):
    """``pool`` with page ``dst`` of every cache layer a copy of ``src``."""
    return pool.at[:, :, dst].set(pool[:, :, src])


def _pack_heads(x):
    """[..., H, D] -> [..., H/g, g·D]: a pool row's lanes."""
    *lead, h, d = x.shape
    g = head_group(h, d)
    if h % g:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, -h % g), (0, 0)])
    return x.reshape(*lead, -1, g * d)


def _write_tokens(pool, x, cache_layer, pages, offs):
    """x [B, T, H, D] into ``pool[cache_layer]`` at page ``pages[b, t]``,
    row ``offs[b, t]``.  Every index dimension of the scatter is a leading
    one and its window is the pool's minor dimension alone, so it takes
    the carried pool in the layout it rests in and updates it in place
    (a window over the head axis has XLA re-lay the whole pool out with
    ``[H/g, g·D]`` minor first)."""
    hg = jnp.arange(pool.shape[1])
    return pool.at[cache_layer, hg, pages[..., None], offs[..., None]].set(
        _pack_heads(x))


def _write_pages(pool, x, pages):
    """x [cache_layers, B, T, H, D], row ``b``'s tokens from position 0,
    into pool pages ``pages[b, i]`` (positions ``[i * page_size, (i + 1) *
    page_size)``), a whole page of every cache layer and head at a time:
    one in-place ``dynamic_update_slice`` of the carried pool per page.
    (A scatter whose window spans the layer and head axes has XLA re-lay
    the whole pool out, there and back, around it.)"""
    ps = pool.shape[3]
    x = _pack_heads(x)
    layers, b, t, groups, lanes = x.shape
    if t % ps:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, -t % ps), (0, 0), (0, 0)))
    # [B * T/ps, L, H/g, 1, page_size, g·D]: one update per page
    x = x.reshape(layers, -1, ps, groups, lanes).transpose(1, 0, 3, 2, 4)
    x = x[:, :, :, None].astype(pool.dtype)
    pages = pages.reshape(-1)
    return lax.fori_loop(0, pages.shape[0], lambda i, pool: (
        lax.dynamic_update_slice(pool, x[i], (0, 0, pages[i], 0, 0))), pool)


def write_prefill_kv(k_pool, v_pool, ks, vs, page_table, seq_lens):
    """Write a whole prompt pass (``forward_prefill``) into the pools.

    ks/vs: [cache_layers, B, T, H, D] (padded prompts, rows from position
    0); k_pool/v_pool: ``kv_pool_shape``; page_table: [B, max_pages];
    seq_lens: [B].  Written a page at a time (``_write_pages``): page
    slots wholly past ``seq_lens`` go to the null page, and the padding
    past ``seq_lens`` inside a row's last page lands in that page, past
    the frontier every reader masks and the next decode steps overwrite."""
    ps = k_pool.shape[3]
    slot = jnp.arange(-(-ks.shape[2] // ps))
    pages = jnp.where(
        slot[None, :] * ps < seq_lens[:, None],
        page_table[:, jnp.minimum(slot, page_table.shape[1] - 1)], 0)
    return _write_pages(k_pool, ks, pages), _write_pages(v_pool, vs, pages)


def write_chunk_kv(k_pool, v_pool, k, v, cache_layer, page_table, starts,
                   seq_lens):
    """Write one layer's chunk (``forward_prefill_chunk``: chunked prefill
    / cached-prefix tails) into cache layer ``cache_layer`` of the pools.

    k/v: [B, T, H, D], row ``b`` at absolute positions ``starts[b] + [0,
    seq_lens[b])``; positions at or past ``seq_lens`` are redirected to
    the null page.  A token at a time (``_write_tokens``): a chunk may
    start inside a page whose first rows another pass wrote."""
    ps = k_pool.shape[3]
    t_idx = jnp.arange(k.shape[1])
    valid = t_idx[None, :] < seq_lens[:, None]  # [B, T]
    pos = starts[:, None] + t_idx[None, :]
    # mask the page slot BEFORE the gather: an offset row's padding can
    # point past the table row (starts + t >= max_pages * page_size)
    page_slot = jnp.where(valid, pos // ps, 0)
    pages = jnp.where(valid,
                      jnp.take_along_axis(page_table, page_slot, axis=1), 0)
    return (_write_tokens(k_pool, k, cache_layer, pages, pos % ps),
            _write_tokens(v_pool, v, cache_layer, pages, pos % ps))


# -- a window layer's ring --------------------------------------------------------
#
# A layer that sees only its last ``window`` positions keeps, for each
# batch slot, ONE run of ``window / page_size`` pages of a second pool of
# the same layout (``kv_pool_shape(window layers, H, 1 + slots * window /
# page_size, page_size, D)``; page 0 the null page, as in the growing
# pool): slot ``s`` owns pages ``1 + s * n ... (s + 1) * n``, for good — no
# allocator, no table on the host.  Position ``p``'s K/V rest at row ``p
# mod window`` of the run, so a decode step overwrites the position that
# left the window and reads ``min(length, window)`` rows in whatever order
# they lie: exact where nothing but the mask tells positions apart (no
# position signal; softmax is a sum).  A prefill pass writes a slot's run
# whole, so a reused slot never reads an earlier request's rows.  The
# writes, the plan and the kernel are the growing pool's own, handed this
# pool, this table and these lengths.


def window_pool_pages(window: int, page_size: int, slots: int) -> int:
    """Pages of a ring pool: the null page and ``window / page_size`` a
    slot."""
    return 1 + slots * (window // page_size)


def window_table(pool, window: int, slots, live):
    """[B, window / page_size] int32: the ring pages of batch slots
    ``slots`` [B] in ``pool`` (a ring pool); a row that is not ``live``
    [B] names the null page throughout."""
    n = window // pool.shape[3]
    pages = 1 + slots[:, None] * n + jnp.arange(n)[None, :]
    return jnp.where(live[:, None], pages, 0).astype(jnp.int32)


def ring_rows(x, seq_lens, window: int):
    """x [B, T, H, D], row ``b`` valid to ``seq_lens[b]`` -> [B, window,
    H, D]: ring row ``r`` holds the LAST valid position congruent to ``r``
    mod ``window`` (rows no valid position maps to hold a copy of some
    position: every reader masks them by ``min(length, window)``)."""
    t = x.shape[1]
    r = jnp.arange(window)[None, :]
    last = seq_lens[:, None] - 1
    at = r + window * jnp.maximum((last - r) // window, 0)
    return jnp.take_along_axis(x, jnp.clip(at, 0, t - 1)[:, :, None, None],
                               axis=1)


def write_prefill_window(k_pool, v_pool, ks, vs, window: int, slots):
    """Write a prompt pass's rings (``ring_rows`` of every window layer:
    ks/vs [window layers, B, window, H, D]) into the ring pools, row
    ``b``'s into the run of batch slot ``slots[b]``, whole; a slack row
    (a slot the pool does not have) goes to the null page."""
    per = window // k_pool.shape[3]
    have = (k_pool.shape[2] - 1) // per
    pages = window_table(k_pool, window, slots, slots < have)
    return _write_pages(k_pool, ks, pages), _write_pages(v_pool, vs, pages)


class DecodePlan(NamedTuple):
    """What every cache layer of ONE decode step shares: where the new
    token's K/V rows go (``rows`` [B, H/g, 4]: cache layer 0, head group,
    page, row of the page — the scatter's index rows, so the REFERENCE
    path's alone: the kernel finds the page from ``positions`` itself, and
    a program built on it drops ``rows`` as dead code) and the kernel's
    work list (``work``: ``_decode_work``).  Index arithmetic over
    ``page_table``, ``positions`` and ``seq_lens`` alone — a program that
    walks the layers in a loop makes it once, before the loop, and hands
    it to :func:`decode_attention` (or :func:`write_decode_kv`) and
    :func:`ragged_paged_attention` (XLA moves none of it out of a
    ``while`` body: it was a third of the operations of a ``gpt2-large``
    decode step, PERF.md §6, PR 33)."""

    rows: jax.Array
    work: tuple


def _decode_rows(pool, page_table, positions):
    """[B, H/g, 4]: where row ``b``'s token at ``positions[b]`` goes in
    cache layer 0 of ``pool`` — (0, head group, page, row of the page)."""
    _, groups, _, ps, _ = pool.shape
    pages = jnp.take_along_axis(page_table, (positions // ps)[:, None],
                                axis=1)
    return jnp.stack(jnp.broadcast_arrays(
        0, jnp.arange(groups)[None, :], pages, (positions % ps)[:, None]),
        axis=-1).astype(jnp.int32)


def decode_plan(k_pool, page_table, positions, seq_lens, kv_heads: int,
                head_dim: int) -> DecodePlan:
    """The :class:`DecodePlan` of a decode step over pools shaped like
    ``k_pool`` holding ``kv_heads`` heads of ``head_dim``."""
    ps = k_pool.shape[3]
    n = decode_block_pages(kv_heads, ps, head_dim, k_pool.dtype.itemsize,
                           page_table.shape[1])
    return DecodePlan(_decode_rows(k_pool, page_table, positions),
                      _decode_work(page_table, seq_lens, n, ps))


_ROWS = lax.ScatterDimensionNumbers(
    update_window_dims=(2,), inserted_window_dims=(0, 1, 2, 3),
    scatter_dims_to_operand_dims=(0, 1, 2, 3))


def write_decode_kv(k_pool, v_pool, k, v, cache_layer, page_table, positions,
                    plan: DecodePlan | None = None):
    """Write one new token's K/V per batch row into cache layer
    ``cache_layer`` of the pools: the reference half of the decode step's
    one write (:func:`decode_attention`; on a TPU the kernel writes).

    k/v: [B, H, D]; k_pool/v_pool: ``kv_pool_shape``; page_table:
    [B, max_pages]; positions: [B] absolute token index.
    Idle rows (all-zero table rows) land in the null page.  ``plan``: the
    step's :func:`decode_plan` (made here if None).  The scatter is
    ``_write_tokens``'s — every leading dimension indexed, the window the
    pool's minor dimension alone, in place on the carried pool — with its
    index rows made once a step and only the layer added here."""
    rows = plan.rows if plan is not None else _decode_rows(
        k_pool, page_table, positions)
    at = rows + jnp.asarray([1, 0, 0, 0], jnp.int32) * cache_layer
    return tuple(lax.scatter(pool, at, _pack_heads(x).astype(pool.dtype),
                             _ROWS) for pool, x in ((k_pool, k), (v_pool, v)))


def _gather_context(pool, cache_layer, page_table, num_heads, head_dim):
    """Every table entry's page of one cache layer, as contiguous
    [B, H, max_pages * page_size, D] K (or V): ``_pack_heads`` undone,
    the padding heads dropped."""
    b, maxp = page_table.shape
    _, groups, _, ps, lanes = pool.shape
    g = lanes // head_dim
    # the advanced indices are split by the head slice, so [B, maxp] leads
    x = pool[cache_layer, :, page_table].reshape(
        b, maxp, groups, ps, g, head_dim).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(b, groups * g, maxp * ps, head_dim)[:, :num_heads]


def _grouped(q, kv_heads):
    """q [..., H, D] -> [..., KV, rep, D]: query head h reads K/V head
    h // rep."""
    *lead, h, d = q.shape
    return q.reshape(*lead, kv_heads, h // kv_heads, d)


def paged_prefill_attention(q, k_pool, v_pool, cache_layer, page_table,
                            starts, seq_lens, scale=None, kv_heads=None):
    """Chunk-prefill attention: queries over the whole resident paged
    context of cache layer ``cache_layer`` (prefix caching + chunked
    prefill's compute path).

    q: [B, C, H, D] — row ``b``'s queries sit at absolute positions
    ``starts[b] + t`` and attend causally over positions ``[0,
    starts[b] + t]`` of the paged cache: earlier chunks AND any shared
    cached prefix included.  The chunk's own K/V must already be written
    (``write_chunk_kv``).  ``seq_lens`` [B] is the
    valid NEW tokens per row; rows with 0 produce zeros, query positions
    past it produce garbage the caller discards.  Returns [B, C, H, D].

    Pure jnp (gather + einsum) by design: it is the production CPU path
    and, under jit, lowers to an XLA gather + batched matmul on TPU —
    chunked prefill is bound by the chunk's dense matmuls, while the
    per-step decode hot loop keeps the Pallas kernel above."""
    b, c, h, d = q.shape
    kv = kv_heads or h
    scale = scale if scale is not None else d ** -0.5
    k = _gather_context(k_pool, cache_layer, page_table, kv, d)
    v = _gather_context(v_pool, cache_layer, page_table, kv, d)
    s = jnp.einsum("bchrd,bhkd->bhrck", _grouped(q, kv).astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = starts[:, None] + jnp.arange(c)[None, :]   # [B, C] absolute
    kpos = jnp.arange(k.shape[2])
    # causal over ABSOLUTE positions: every key at or before the query
    # was written by the prefix/chunks already resident — stale pages
    # past the write frontier sit strictly above qpos and are masked
    mask = kpos[None, None, None, None, :] <= qpos[:, None, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhrck,bhkd->bchrd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    out = jnp.where(seq_lens[:, None, None, None, None] > 0, out, 0.0)
    return out.reshape(b, c, h, d).astype(q.dtype)


# -- reference implementation --------------------------------------------------


def _lane_group_values(v, head_dim: int):
    """v [B, KV, K, D] -> [B, KV, K, g·D]: every K/V head with the values
    of its whole lane group side by side (``wide_v``)."""
    b, kv, k, d = v.shape
    g = head_group(kv, head_dim)
    wide = v.reshape(b, kv // g, g, k, d).transpose(0, 1, 3, 2, 4).reshape(
        b, kv // g, 1, k, g * d)
    return jnp.broadcast_to(wide, (b, kv // g, g, k, g * d)).reshape(
        b, kv, k, g * d)


def ragged_paged_attention_reference(q, k_pool, v_pool, cache_layer,
                                     page_table, seq_lens, scale=None,
                                     kv_heads=None, wide_v=False):
    """Pure-jnp oracle: gather each sequence's pages of cache layer
    ``cache_layer``, mask, softmax.

    q: [B, H, D] (one decode token per row); k_pool/v_pool:
    ``kv_pool_shape``; returns [B, H, D] (``wide_v``: [B, H, g·D]).  Rows
    with ``seq_lens == 0`` produce zeros (idle slots), not NaNs."""
    b, h, d = q.shape
    kv = kv_heads or h
    scale = scale if scale is not None else d ** -0.5
    k = _gather_context(k_pool, cache_layer, page_table, kv, d)
    v = _gather_context(v_pool, cache_layer, page_table, kv, d)
    if wide_v:
        v = _lane_group_values(v, d)
    s = jnp.einsum("bhrd,bhkd->bhrk", _grouped(q, kv).astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(k.shape[2])
    s = jnp.where(pos < seq_lens[:, None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhrk,bhkd->bhrd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    # fully-masked rows: NEG_INF is finite, so p == 1 everywhere and the
    # sum above is a mean of null/stale pages — zero them explicitly to
    # match the kernel's l == 0 path
    out = jnp.where(seq_lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, h, v.shape[-1]).astype(q.dtype)


# -- the Pallas kernel ---------------------------------------------------------

_VMEM_BUDGET = 6 << 20  # bytes of a step's K/V buffers, of 16 MB scoped VMEM
# K/V bytes one grid step should carry: a step costs about 0.3 microseconds
# beside its pages' copies, and the kernel alone stopped gaining past a
# megabyte a step at 2, 4, 10 and 16 lane groups (PERF.md §6, PR 39: the
# chip sweep)
_STEP_BYTES = 1 << 20


def decode_block_pages(num_heads: int, page_size: int, head_dim: int,
                       itemsize: int, max_pages: int,
                       vmem_budget: int = _VMEM_BUDGET) -> int:
    """``N``: how many consecutive page slots one grid step of the decode
    kernel covers, from the shapes alone (the same for every caller;
    ``num_heads`` = the heads the CACHE holds).

    A block is whole MXU passes of score columns (128 tokens each), as
    many as make the step carry ``_STEP_BYTES`` of K and V at the cache's
    bytes a token (``H/g`` rows of ``g·D`` lanes, both pools): a cache
    with many heads carries that in one pass, one with few heads takes a
    longer block, or its steps cost more than their bytes.  Capped by
    what fits the VMEM budget — per page slot, K and V, the two buffers
    the copies land in plus the block as the body holds it for the
    matmuls (K loaded, V masked), each ``[H/g, page, g·D]`` padded to
    the dtype's (sublane, 128) tile — and by the table's width; at
    least 1."""
    _, groups, _, _, lanes = kv_pool_shape(1, num_heads, 1, page_size,
                                           head_dim)
    sublanes = 8 * max(4 // itemsize, 1)
    tile = (groups * round_up(page_size, sublanes)
            * round_up(lanes, _LANES) * itemsize)
    fits = vmem_budget // (2 * 3 * tile)
    per_pass = max(_LANES // page_size, 1)
    token = 2 * groups * lanes * itemsize
    passes = max(-(-_STEP_BYTES // (per_pass * page_size * token)), 1)
    return int(max(1, min(per_pass * passes, fits, max_pages)))


def decode_steps(seq_lens, block: int):
    """The grid steps each row takes at ``block`` tokens a step: one per
    block that holds a live token, an idle row one (its zeros).  Plain
    arithmetic, so numpy lengths on the host (the engine's ``kv_steps``)
    and traced ones (``_decode_work``) are counted by the same rule."""
    steps = -(-seq_lens // block)
    return steps + (steps == 0)


def _decode_work(page_table, seq_lens, n, page_size):
    """The kernel's work list, from the lengths: ``(rows, blocks, table,
    steps)`` — for grid step ``g`` the batch row and the row's block
    (whose page slots are ``table[row * maxp + block * n + j]``: the page
    table, flat); ``steps`` of the ``B * ceil(maxp / n)`` entries are
    live, ``decode_steps`` a row."""
    b, maxp = page_table.shape
    per_row = decode_steps(seq_lens.astype(jnp.int32), n * page_size)
    ends = jnp.cumsum(per_row)
    g = jnp.arange(b * pl.cdiv(maxp, n), dtype=jnp.int32)
    # entries past ``steps`` are never run; they only have to stay in bounds
    rows = jnp.minimum(jnp.sum(g[:, None] >= ends[None, :], axis=1), b - 1)
    blocks = jnp.maximum(g - (ends - per_row)[rows], 0)
    return (rows.astype(jnp.int32), blocks.astype(jnp.int32),
            page_table.astype(jnp.int32).reshape(-1), ends[-1:])


def _decode_kernel(layer_ref, rows_ref, blocks_ref, table_ref, steps_ref,
                   lens_ref, *refs, scale, page_size, n, token_at=None):
    if token_at is None:
        q_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    else:
        # the writing form: the rows' write positions, and the pools as
        # outputs aliased to the inputs — every copy, in and out, goes
        # through the output refs (one buffer on the chip; the interpreter
        # keeps two, and only the outputs see the writes)
        at_ref, q_ref, _, _, o_ref, k_hbm, v_hbm, *scratch = refs
    k_buf, v_buf, sems, acc_ref, m_ref, l_ref = scratch
    g = pl.program_id(0)
    i = blocks_ref[g]
    seq_len = lens_ref[rows_ref[g]]
    block = n * page_size
    slot = g % 2
    layer = layer_ref[0]
    maxp = table_ref.shape[0] // lens_ref.shape[0]  # the table's width

    def pages(step, buf, then):
        """``then(copy)`` for every live page of work item ``step``: the
        copy of both pools' page into ``buf`` at its place in the block."""
        row, blk = rows_ref[step], blocks_ref[step]
        live = jnp.clip(pl.cdiv(lens_ref[row], page_size) - blk * n, 0, n)
        at = row * maxp + blk * n

        def page(j, _):
            src = table_ref[at + j]
            to = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for p, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                then(pltpu.make_async_copy(hbm.at[layer, :, src],
                                           vmem.at[buf, :, to],
                                           sems.at[p, buf]))

        lax.fori_loop(0, live, page, None)

    @pl.when(g == 0)
    def _first():
        pages(g, slot, lambda copy: copy.start())

    # the next work item's pages, another row's too, under this one's
    # arithmetic
    @pl.when(g + 1 < steps_ref[0])
    def _next():
        pages(g + 1, 1 - slot, lambda copy: copy.start())

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if token_at is not None:
        at = at_ref[rows_ref[g]]
        # the block that holds the row's write position — the row's last
        # for a growing cache, any for a ring; never an idle row's step
        writes = (at // block == i) & (at < seq_len)
        # the token's page: its rows of the block, its place in the pools
        off = pl.ds(pl.multiple_of(at % block // page_size * page_size,
                                   page_size), page_size)
        dst = table_ref[rows_ref[g] * maxp + at // page_size]

        def token_page(then):
            """``then(copy)`` of the token's page, as the buffer holds it,
            back to its place in each pool."""
            for p, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                then(pltpu.make_async_copy(vmem.at[slot, :, off],
                                           hbm.at[layer, :, dst],
                                           sems.at[p, 2]))

    @pl.when(i * block < seq_len)  # false only for an idle row's one step
    def _block():
        pages(g, slot, lambda copy: copy.wait())
        if token_at is None:
            # [H/g, g * 8, g·D]: each head's query on 8 sublanes, zero in
            # the lanes of its group's other heads
            q = q_ref[0]
        else:
            q = q_ref[0, :, :acc_ref.shape[1]]      # the query rows

            @pl.when(writes)
            def _token():
                # the token's K and V rows are the first two of the
                # sublane tile behind the queries.  A select over the
                # page's rows puts them in: one row of a packed dtype is
                # half a sublane, no store of its own.  The page then goes
                # back under the block's arithmetic, which reads the
                # token from the buffer: HBM need not hold it yet.
                tile = q_ref[0, :, token_at:].astype(jnp.float32)
                here = lax.broadcasted_iota(
                    jnp.int32, (1, page_size, 1), 1) == at % page_size
                for p, buf in enumerate((k_buf, v_buf)):
                    page = buf.at[slot, :, off]
                    new = jnp.broadcast_to(tile[:, p:p + 1], page.shape)
                    page[...] = jnp.where(here, new.astype(buf.dtype),
                                          page[...])
                token_page(lambda copy: copy.start())

        k = k_buf[slot]                                 # [H/g, block, g·D]

        def live(shape, axis):
            """Which of the block's tokens, along ``axis``, the row has."""
            return i * block + lax.broadcasted_iota(
                jnp.int32, shape, axis) < seq_len

        # rows past the row's end hold what the buffer or the page's tail
        # held, Inf and NaN bits maybe: ``p == 0`` does not make them zero
        v = jnp.where(live((1, block, 1), 1), v_buf[slot], 0)
        s = jnp.einsum("hqd,hkd->hqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live(s.shape, 2), s, NEG_INF)
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

        if token_at is not None:
            # before the next work item's ``_next`` refills this buffer,
            # and before the call ends.  (Waited instead at the row's
            # NEXT write, out of a staging buffer: the same time on the
            # chip — PERF.md §6, PR 51 — so the copy hides as it stands)
            @pl.when(writes)
            def _written():
                token_page(lambda copy: copy.wait())

    @pl.when((i + 1) * block >= seq_len)  # the row's last step
    def _finalize():
        # idle rows (seq_len 0) never accumulated: l == 0 -> output 0
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


def _kernel_impl(q, k_pool, v_pool, cache_layer, page_table, seq_lens, scale,
                 interpret, kv_heads=None, work=None, wide_v=False,
                 token=None):
    """The Mosaic call.  ``token``: None, or the step's new ``(k, v)``
    [B, KV, D] and the rows' write positions [B] — the writing form,
    which returns ``(attention, k_pool, v_pool)``."""
    b, h, d = q.shape
    kv = kv_heads or h
    rep = h // kv
    _, groups, _, page_size, lanes = k_pool.shape
    g = lanes // d
    n = decode_block_pages(kv, page_size, d, k_pool.dtype.itemsize,
                           page_table.shape[1])
    rows, blocks, table, steps = work or _decode_work(
        page_table, seq_lens, n, page_size)
    # K/V head (group, j)'s ``rep`` query heads in lanes [j·D, (j+1)·D) of
    # rows [qr·j, qr·(j + 1)) of its group, zeros elsewhere: the products
    # with the other heads' lanes of a pool row are exact zeros.  One
    # query head a K/V head is repeated down its 8 sublanes.
    qr = round_up(rep, _Q_SUBLANES)
    own = jnp.eye(g, dtype=bool)[:, None, :, None]
    qg = _pack_heads(_grouped(q, kv).swapaxes(1, 2))   # [B, rep, H/g, g·D]
    qg = qg.reshape(b, rep, groups, 1, g, d).transpose(0, 2, 3, 1, 4, 5)
    qb = jnp.where(own, qg, 0)                         # [B, H/g, g, rep, g, D]
    if rep == 1:
        qb = jnp.broadcast_to(qb, (b, groups, g, qr, g, d))
    elif qr > rep:
        qb = jnp.pad(qb, [(0, 0)] * 3 + [(0, qr - rep)] + [(0, 0)] * 2)
    qb = qb.reshape(b, groups, g * qr, lanes)
    # (index maps: the grid index, then the prefetched scalars)
    by_row = lambda s, layer, rows, *_: (rows[s], 0, 0, 0)
    row = pl.BlockSpec((1, groups, g * qr, lanes), by_row)
    pool = pl.BlockSpec(memory_space=pl.ANY)  # where it rests: the kernel copies
    grid = (steps[0],)
    scalars = [jnp.asarray(cache_layer, jnp.int32).reshape(1), rows, blocks,
               table, steps, seq_lens.astype(jnp.int32)]
    out_row = jax.ShapeDtypeStruct((b, groups, g * qr, lanes), q.dtype)
    if token is None:
        q_row, token_at = row, None
        out_specs, out_shape, aliases = row, out_row, {}
    else:
        # the token rides the query's block, in the query's dtype (k, v
        # and q are one projection's): one more sublane tile a row, whose
        # first two rows are the K and V rows as the pool will hold them.
        # Two more block specs would cost their bookkeeping every grid
        # step (PERF.md §6, PR 27)
        k_new, v_new, at = token
        tile = 8 * max(4 // q.dtype.itemsize, 1)
        token_at = round_up(g * qr, tile)
        new = jnp.stack([_pack_heads(x).astype(k_pool.dtype).astype(q.dtype)
                         for x in (k_new, v_new)], axis=2)
        qb = jnp.concatenate([
            qb, jnp.zeros((b, groups, token_at - g * qr, lanes), q.dtype),
            new, jnp.zeros((b, groups, tile - 2, lanes), q.dtype)], axis=2)
        q_row = pl.BlockSpec((1, groups, token_at + tile, lanes), by_row)
        scalars.append(at.astype(jnp.int32))
        out_specs = [row, pool, pool]
        out_shape = [out_row, *(jax.ShapeDtypeStruct(x.shape, x.dtype)
                                for x in (k_pool, v_pool))]
        # operands count the prefetched scalars: both pools, in place
        aliases = {len(scalars) + 1: 1, len(scalars) + 2: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # the cache layer, the work list and seq_lens ride SMEM
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[q_row, pool, pool],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, groups, n * page_size, lanes), k_pool.dtype),
            pltpu.VMEM((2, groups, n * page_size, lanes), v_pool.dtype),
            # [pool, buffer | the token's page going back]
            pltpu.SemaphoreType.DMA((2, 2 if token is None else 3)),
            pltpu.VMEM((groups, g * qr, lanes), jnp.float32),
            pltpu.VMEM((groups, g * qr, _LANES), jnp.float32),
            pltpu.VMEM((groups, g * qr, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=page_size,
                          n=n, token_at=token_at),
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=tpu_compiler_params(
            # in order: a row's steps share its accumulators and output
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*scalars, qb, k_pool, v_pool)
    if token is not None:
        out, *pools = out
    # rows [qr·j, qr·(j + 1)) hold K/V head (group, j)'s query heads in
    # ITS lanes; the rest of each row is the other heads' values under
    # this head's weights
    j = jnp.arange(g)
    out = out.reshape(b, groups, g, qr, g, d)
    if wide_v:
        # every lane of a query head's row: its K/V head's whole lane
        # group's values under this head's weights (heads that exist)
        out = out[:, :, :, :rep].reshape(b, groups * g * rep, g * d)[:, :h]
    elif rep == 1:
        out = out[:, :, j, 0, j].reshape(b, groups * g, d)[:, :h]
    else:
        out = out[:, :, j, :rep, j]                    # [g, B, H/g, rep, D]
        out = out.transpose(1, 2, 0, 3, 4).reshape(
            b, groups * g * rep, d)[:, :h]
    return out if token is None else (out, *pools)


# traced and lowered once a program, however many layers an unrolled walk
# spells out (the reading form stays as it was: one call a cache layer)
_write_attend_kernel = jax.jit(
    _kernel_impl, static_argnames=("scale", "interpret", "kv_heads", "wide_v"))


def ragged_paged_attention(q, k_pool, v_pool, cache_layer, page_table,
                           seq_lens, scale=None, impl="auto", interpret=None,
                           kv_heads=None, plan: DecodePlan | None = None,
                           wide_v: bool = False):
    """Decode-step attention of q [B, H, D] over cache layer
    ``cache_layer`` of a paged KV-cache (k_pool/v_pool:
    ``kv_pool_shape`` of ``kv_heads`` heads, None = H: query head h reads
    K/V head ``h // (H // kv_heads)``).

    ``impl``: "kernel" (Pallas; ``interpret=None`` auto-selects
    interpreter mode off-TPU, the flash_attention convention), "reference"
    (pure jnp — the production CPU path: interpret-mode Pallas is a
    per-block Python loop, far too slow to serve from), or "auto"
    (kernel on TPU, reference elsewhere).  ``plan``: the step's
    :func:`decode_plan`, whose work list the kernel then takes instead of
    making its own.  ``wide_v``: a query head keeps ALL ``g·D`` lanes of
    its row — the values of every head of its K/V head's lane group under
    its own weights, [B, H, g·D] — where the caller otherwise keeps the
    head's own ``D`` (differential attention: a K/V pair ``[v1 | v2]`` IS
    a lane group, and each of a pair's two softmaxes weighs both; the
    cache's heads must fill whole lane groups)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    from paddle_tpu.ops.pallas import resolve_impl, resolve_interpret

    if resolve_impl(impl, "ragged_paged_attention") == "reference":
        return ragged_paged_attention_reference(
            q, k_pool, v_pool, cache_layer, page_table, seq_lens, scale=scale,
            kv_heads=kv_heads, **({"wide_v": True} if wide_v else {}))
    return _kernel_impl(q, k_pool, v_pool, cache_layer, page_table, seq_lens,
                        scale, resolve_interpret(interpret), kv_heads,
                        plan and plan.work, wide_v)


def decode_attention(q, k, v, k_pool, v_pool, cache_layer, page_table,
                     positions, seq_lens, scale=None, impl="auto",
                     interpret=None, kv_heads=None,
                     plan: DecodePlan | None = None, wide_v: bool = False):
    """A decode step's cache write and attention of one layer, as one:
    the new token's k/v [B, KV, D] go to ``positions`` [B] of cache layer
    ``cache_layer`` and q [B, H, D] attends ``[0, seq_lens)`` of it, the
    token included.  Returns ``(attention, (k_pool, v_pool))``; the other
    arguments are :func:`ragged_paged_attention`'s, and the pair routes
    under its name, once.

    "kernel": ONE Mosaic call.  The work item whose block holds a row's
    position puts the token into the page it has just copied into VMEM,
    runs the block's arithmetic over it, and copies that page
    (``[H/g, page_size, g·D]``) back to the pool, one DMA a pool; both
    pools are outputs aliased to their inputs, so a layer loop keeps them
    where they are.  The rest of the page goes back as it was read: the
    page must be the row's OWN (a page shared between rows is a full
    prefix page, which no row writes).  A row with ``seq_lens`` 0 (idle,
    or mid-prefill) writes nothing, the null page included.
    "reference": :func:`write_decode_kv` — whose scatter sends such a
    row's token to the null page, behind its all-zero table row — then
    :func:`ragged_paged_attention_reference`: the CPU path and the
    kernel's oracle."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    from paddle_tpu.ops.pallas import resolve_impl, resolve_interpret

    if resolve_impl(impl, "ragged_paged_attention") == "reference":
        pools = write_decode_kv(k_pool, v_pool, k, v, cache_layer, page_table,
                                positions, plan)
        return ragged_paged_attention_reference(
            q, *pools, cache_layer, page_table, seq_lens, scale=scale,
            kv_heads=kv_heads, wide_v=wide_v), pools
    out, *pools = _write_attend_kernel(
        q, k_pool, v_pool, cache_layer, page_table, seq_lens, scale=scale,
        interpret=resolve_interpret(interpret), kv_heads=kv_heads,
        work=plan and plan.work, wide_v=wide_v, token=(k, v, positions))
    return out, tuple(pools)


def block_paged_attention(q, k_pool, v_pool, cache_layer, page_table,
                          seq_lens, scale=None, impl="auto", interpret=None,
                          kv_heads=None):
    """A block pass's attention: q [B, T, H, D], the ``T`` positions of
    each row's in-progress block, every one of them over the row's whole
    resident context ``[0, seq_lens[b])`` — earlier blocks and the block
    itself, whose K/V are already in its pages (no mask inside a block).
    Since all ``T`` positions read the same keys, they are folded into
    the query heads: K/V head ``j``'s queries become its ``rep`` heads at
    position 0, then at position 1, ... (``T * rep`` heads), and
    :func:`ragged_paged_attention` runs as it is.  Returns [B, T, H, D]."""
    b, t, h, d = q.shape
    kv = kv_heads or h
    folded = _grouped(q, kv).swapaxes(1, 2).reshape(b, t * h, d)
    out = ragged_paged_attention(
        folded, k_pool, v_pool, cache_layer, page_table, seq_lens,
        scale=scale, impl=impl, interpret=interpret, kv_heads=kv)
    return out.reshape(b, kv, t, h // kv, d).swapaxes(1, 2).reshape(
        b, t, h, d)
