"""Paged KV-cache state: the refcounted page allocator (host), the
device-resident page pools + page tables it manages, and the
prompt-prefix trie that makes pages shareable across requests.

Design (PAPERS "Ragged Paged Attention", arxiv 2604.15464; layout details
in ``ops/pallas/paged_attention.py``): the cache is a fixed pool of
``num_pages`` pages of ``page_size`` token slots each, shared by every
resident sequence.  A sequence maps a list of pages named by its row of
the page table; on retirement its references drop and unreferenced pages
return to the free list and are reused verbatim (no zeroing needed —
``seq_lens`` masking means stale contents are never read).  Page 0 is
reserved as the null/scratch page: never allocated, it absorbs idle-row
writes and backs unused table entries.

Prefix caching (the vLLM copy-on-write recipe) layers on top.  Pages are
REFCOUNTED, so one physical page can back the same prompt prefix in many
sequences' table rows at once; ``free`` decrements and only a page's
last reference returns it to the free list.  Sharing is copy-on-write at
page granularity: only FULL pages of prompt tokens are ever shared (a
partially-filled page is written by its owner as generation proceeds, so
it stays private — every sequence's diverging suffix lands in its own
pages), and :meth:`PagedKVCache.cow_page` materialises a private copy
should a writer ever meet a shared page.  Beside the pages, a model
with recurrent layers keeps per sequence a FIXED state that no page
holds: :class:`PagedKVCache` then owns state pools addressed by batch
slot (``state``), written whole by a slot's prefill and rewritten by
every decode step.  The :class:`PrefixCache` trie
hashes page-granular prompt chunks to resident pages (longest-prefix
match), holds one reference on every cached page, and evicts LRU
refcount-0 entries (cached, no active user) under page pressure — so a
warm cache raises OutOfPages only when UNIQUE, actively mapped pages
exhaust the pool.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.core.enforce import enforce


class OutOfPages(RuntimeError):
    """Raised by :meth:`PageAllocator.alloc` when the pool can't cover a
    request — admission control catches this (or checks ``can_alloc``)
    and leaves the request queued."""


class PageAllocator:
    """Refcounted free-list allocator over page ids ``1..num_pages-1``
    (0 = null).

    LIFO reuse (retired pages are handed out first): the hottest pages
    stay resident in whatever cache hierarchy sits under the pool, and
    tests can assert reuse deterministically.  ``alloc`` hands out pages
    at refcount 1; ``retain`` adds a reference (prefix sharing maps one
    physical page into several table rows); ``free`` drops one and only
    the LAST reference returns the page to the free list — a refcount
    can never go negative, the attempt is a hard error."""

    def __init__(self, num_pages: int):
        enforce(num_pages >= 2, "need at least 2 pages (page 0 is null)")
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._refs: dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Physical pages allocated (each counted once however many
        references it carries): ``free_pages + live_pages`` is always
        ``num_pages - 1``."""
        return len(self._refs)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` pages off the free list at refcount 1; raises
        :class:`OutOfPages` without side effects if fewer are free."""
        if n > len(self._free):
            raise OutOfPages(
                f"requested {n} pages, {len(self._free)} free "
                f"(pool {self.num_pages})")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def retain(self, pages) -> None:
        """Add one reference per page — sharing an allocated page into
        another owner (a new slot's table row, or the prefix cache)."""
        for p in pages:
            enforce(p != 0, "page 0 (null) is never allocated or retained")
            enforce(p in self._refs, f"retain of unallocated page {p}")
            self._refs[p] += 1

    def free(self, pages) -> None:
        """Drop one reference per page; the last reference returns the
        page to the free list.  Over-freeing (a refcount going negative)
        and freeing the null page are hard errors (they would alias live
        sequences)."""
        for p in pages:
            enforce(p != 0, "page 0 (null) is never allocated or freed")
            refs = self._refs.get(p, 0)
            enforce(refs > 0, f"double free of page {p}")
            if refs == 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = refs - 1


class _PrefixNode:
    """One FULL page of prompt tokens in the trie: ``key`` is the
    page_size-token tuple, ``page`` the pool page holding its K/V."""

    __slots__ = ("key", "page", "parent", "children", "stamp")

    def __init__(self, key: tuple, page: int, parent: "_PrefixNode | None"):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: dict[tuple, _PrefixNode] = {}
        self.stamp = 0


class PrefixCache:
    """Page-granular prompt-prefix trie over the page pool.

    Each node names one FULL page of prompt tokens and the resident pool
    page holding that page's K/V; a path from the root is a prompt
    prefix already computed by some earlier request.  The cache holds
    one allocator reference on every cached page, and every sequence
    admitted through :meth:`PagedKVCache.assign_with_prefix` holds its
    own — "refcount 0" in scheduler terms means only the cache's
    reference remains, which makes the page reclaimable.  Matches are
    capped at ``len(prompt) - 1`` tokens so the uncached tail is never
    empty: the last prompt token must be prefilled to produce the
    first-token logits.

    Not thread-safe by design: like the allocator it is mutated only by
    the scheduler under the engine's single step driver."""

    def __init__(self, cache: "PagedKVCache"):
        self._cache = cache
        self._root: dict[tuple, _PrefixNode] = {}
        self._nodes: list[_PrefixNode] = []
        self._clock = 0
        # stats the engine mirrors into serving telemetry
        self.hits = 0           # committed lookups matching >= 1 page
        self.misses = 0
        self.hit_tokens = 0     # prompt tokens served from cache
        self.prompt_tokens = 0  # prompt tokens seen by committed lookups
        self.inserts = 0        # pages newly registered
        self.evictions = 0      # cached pages reclaimed under pressure

    @property
    def cached_pages(self) -> int:
        return len(self._nodes)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, prompt) -> list[_PrefixNode]:
        """Longest chain of cached FULL pages covering a strict prefix
        of ``prompt`` (pure lookup — no LRU stamping, no stats)."""
        ps = self._cache.page_size
        limit = (len(prompt) - 1) // ps  # full pages, tail never empty
        node_map, path = self._root, []
        for i in range(limit):
            node = node_map.get(tuple(prompt[i * ps:(i + 1) * ps]))
            if node is None:
                break
            path.append(node)
            node_map = node.children
        return path

    def peek(self, prompt) -> int:
        """Tokens a match would cover, without side effects — the fleet
        router's replica-affinity probe."""
        return len(self.match(prompt)) * self._cache.page_size

    def commit(self, path: list[_PrefixNode], prompt_len: int) -> int:
        """Record a successful admission over ``path``: stamp it
        most-recently-used and count the hit.  Returns tokens covered."""
        stamp = self._tick()
        for node in path:
            node.stamp = stamp
        covered = len(path) * self._cache.page_size
        self.prompt_tokens += prompt_len
        if path:
            self.hits += 1
            self.hit_tokens += covered
        else:
            self.misses += 1
        return covered

    def insert(self, prompt, pages) -> int:
        """Register a fully prefilled prompt's FULL pages (``pages`` is
        the owning slot's page list, prefix order).  Pages already
        cached — the match the slot rode in on — are stamped; new ones
        get a cache reference.  Returns the count of newly cached pages."""
        ps = self._cache.page_size
        node_map, parent = self._root, None
        stamp = self._tick()
        new = 0
        for i in range(len(prompt) // ps):
            key = tuple(prompt[i * ps:(i + 1) * ps])
            node = node_map.get(key)
            if node is None:
                node = _PrefixNode(key, pages[i], parent)
                self._cache.allocator.retain([node.page])
                node_map[key] = node
                self._nodes.append(node)
                new += 1
            node.stamp = stamp
            parent, node_map = node, node.children
        self.inserts += new
        return new

    def reclaimable(self) -> list[_PrefixNode]:
        """Trie leaves whose page only the cache references (allocator
        refcount 1): the LRU eviction candidates.  Leaf-first keeps the
        trie consistent — an interior page is never dropped while a
        longer cached prefix still needs the walk through it."""
        alloc = self._cache.allocator
        return [n for n in self._nodes
                if not n.children and alloc.refcount(n.page) == 1]

    def reclaimable_pages(self) -> int:
        """Count of cached pages :meth:`evict_until` could eventually
        reclaim — every refcount-1 node, not just current leaves (a
        refcount-1 interior node has no active mapper, since any
        sequence mapping a descendant walked through it; iterative
        leaf-first eviction frees the whole chain).  The health probe's
        \"effectively free\" headroom term."""
        alloc = self._cache.allocator
        return sum(1 for n in self._nodes if alloc.refcount(n.page) == 1)

    def evict_until(self, free_needed: int) -> bool:
        """Reclaim LRU refcount-0 cached prefixes until ``free_needed``
        pages are on the free list; True when satisfied.  OutOfPages is
        thus raised only when unique, actively mapped pages exhaust the
        pool — a warm cache never blocks an admission a cold pool would
        have taken."""
        alloc = self._cache.allocator
        while alloc.free_pages < free_needed:
            victims = self.reclaimable()
            if not victims:
                return False
            victim = min(victims, key=lambda n: (n.stamp, n.page))
            self._remove(victim)
            alloc.free([victim.page])
            self.evictions += 1
        return True

    def _remove(self, node: _PrefixNode) -> None:
        siblings = (self._root if node.parent is None
                    else node.parent.children)
        del siblings[node.key]
        self._nodes.remove(node)


class PagedKVCache:
    """Device page pools for every layer + the host-side page table.

    ``k``/``v``: the two pools of ``paged_attention.kv_pool_shape`` —
    [cache_layers, H/g, P, page_size, g·D], ``g`` heads side by side in
    the lanes — as jax arrays (functional: the jitted programs take them
    donated and return them, updated in place); ``page_table``: host
    int32 [max_slots, max_pages_per_seq], row ``s`` owned by batch slot
    ``s``.  The allocator spans the whole pool; slot bookkeeping
    (assign/release) keeps table rows, refcounts and the free list
    consistent.  With ``prefix_cache=True`` the :class:`PrefixCache`
    trie rides along and ``assign_with_prefix`` maps cached prefixes
    into new rows instead of recomputing them.

    Under a model that generates by blocks (``block_len`` > 1, a
    divisor of ``page_size``: a block never straddles a page) nothing
    here changes: a request's reservation — prompt + max_new_tokens, in
    whole pages — already holds its last block whole, so the pages of
    the block in progress are assigned before its first pass.  Every
    pass rewrites that block's rows; they stand only once a pass found
    nothing masked (the scheduler's ``_Block`` knows; the cache does
    not).

    ``num_heads`` is the heads the CACHE holds (a model's K/V heads).
    ``state``: {part: [layers, max_slots, *shape]} float32, one row per
    batch slot for every layer that keeps that part of a fixed state a
    sequence (``state_parts``: part -> (how many layers keep it, one
    layer's shape for one sequence) — a model's kinds of layer say, see
    ``TransformerConfig.state_kinds``: a recurrent layer's state stands
    in place of pages, a CCA attention layer's beside them; empty without
    such layers).  Slot ``s`` owns row ``s``: its prefill writes
    the row whole, so a reused slot needs no zeroing, and like the page
    pools the arrays go donated through the jitted programs and come
    back updated in place.  Nothing of it is shareable between requests:
    the prefix trie has no snapshot of it (``ServingEngine`` refuses the
    combination).

    ``window``: {"window_k", "window_v"}, the RINGS of a model's window
    layers (``window`` = (how many such layers, their window); empty
    without them): two more pools of the page pools' layout and type,
    ``1 + max_slots x window / page_size`` pages — slot ``s`` owns one
    run of them for good, position ``p``'s K/V at row ``p mod window``
    (``ops/pallas/paged_attention.py``: "a window layer's ring").  A
    slot's bytes there do not grow with its context, no allocator or
    table on the host knows them, and they ride the programs beside
    ``state`` (``carried`` / ``carry``), donated alike.

    ``tokens``: int32 [max_slots], the last token each slot's sequence
    sampled — the next decode step's input, kept where it was made: the
    from-zero prefill programs scatter a row's first token into it, the
    decode program reads its ``ids`` from it and writes what it samples
    back (rows that are not decoding keep what they had), so the host
    never sends a token it only just fetched.  Carried and donated like
    the pools.  Under a model that generates by blocks (``block_len`` > 1)
    it is int32 [max_slots, 2 x block_len]: each slot's block in progress,
    tokens | masked flags.  The block pass reads its input from it — or,
    where the host opens a block, from the row the host sends — and
    writes back what it unmasked; rows that ride no pass keep theirs."""

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_pages: int, page_size: int, max_slots: int,
                 max_pages_per_seq: int, dtype=None,
                 prefix_cache: bool = False,
                 state_parts: dict | None = None, block_len: int = 1,
                 window: tuple = (0, 0)):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.paged_attention import (
            init_kv_pages, window_pool_pages)

        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.k, self.v = init_kv_pages(
            num_layers, num_heads, num_pages, page_size, head_dim,
            dtype=dtype or jnp.float32)
        self.state = {
            part: jnp.zeros((layers, max_slots, *shape), jnp.float32)
            for part, (layers, shape) in (state_parts or {}).items()}
        self.window = {}
        if window[0]:
            enforce(window[1] % page_size == 0,
                    f"a window of {window[1]} positions is not whole pages "
                    f"of {page_size}: a slot's ring is a run of pages")
            self.window = dict(zip(("window_k", "window_v"), init_kv_pages(
                window[0], num_heads,
                window_pool_pages(window[1], page_size, max_slots),
                page_size, head_dim, dtype=dtype or jnp.float32)))
        self.tokens = jnp.zeros(
            (max_slots,) if block_len == 1 else (max_slots, 2 * block_len),
            jnp.int32)
        self.allocator = PageAllocator(num_pages)
        self.page_table = np.zeros((max_slots, max_pages_per_seq), np.int32)
        self._slot_pages: dict[int, list[int]] = {}
        self.prefix: PrefixCache | None = (
            PrefixCache(self) if prefix_cache else None)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of fixed state one sequence holds, over every part and
        every layer that keeps it."""
        return sum(int(a.nbytes) // a.shape[1] for a in self.state.values())

    @property
    def window_bytes_per_slot(self) -> int:
        """Bytes of ring one slot owns, K and V over every window layer
        (the null page left out)."""
        slots = self.page_table.shape[0]
        return sum(int(a.nbytes) // a.shape[2] * ((a.shape[2] - 1) // slots)
                   for a in self.window.values())

    def carried(self) -> dict:
        """What rides a program by batch slot, in one tree: the float32
        state pools and the rings."""
        return {**self.state, **self.window}

    def carry(self, tree: dict) -> None:
        """A program's ``carried`` tree back where it came from."""
        self.window = {n: tree[n] for n in self.window}
        self.state = {n: a for n, a in tree.items() if n not in self.window}

    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def _alloc(self, n: int) -> list[int]:
        """alloc with eviction backpressure: reclaim LRU cached
        prefixes before declaring the pool exhausted."""
        if self.prefix is not None and not self.allocator.can_alloc(n):
            self.prefix.evict_until(n)
        return self.allocator.alloc(n)

    def _write_row(self, slot: int, pages: list[int]) -> None:
        self._slot_pages[slot] = pages
        self.page_table[slot, :] = 0
        self.page_table[slot, :len(pages)] = pages

    def assign(self, slot: int, tokens: int) -> list[int]:
        """Allocate pages covering ``tokens`` positions to ``slot`` and
        write its table row.  Raises :class:`OutOfPages` (no partial
        state) when the pool can't cover it."""
        enforce(slot not in self._slot_pages, f"slot {slot} already assigned")
        n = self.pages_needed(tokens)
        enforce(n <= self.max_pages_per_seq,
                f"{tokens} tokens need {n} pages > max_pages_per_seq "
                f"{self.max_pages_per_seq}")
        pages = self._alloc(n)
        self._write_row(slot, pages)
        return pages

    def assign_with_prefix(self, slot: int, tokens: int,
                           prompt) -> tuple[list[int], int]:
        """Like :meth:`assign`, but the longest cached prefix of
        ``prompt`` is mapped (shared, retained) into the head of the row
        and fresh pages are allocated only for the remainder.  Returns
        ``(pages, cached_tokens)``; raises :class:`OutOfPages` with no
        state change when even eviction can't cover the fresh tail."""
        enforce(slot not in self._slot_pages, f"slot {slot} already assigned")
        n = self.pages_needed(tokens)
        enforce(n <= self.max_pages_per_seq,
                f"{tokens} tokens need {n} pages > max_pages_per_seq "
                f"{self.max_pages_per_seq}")
        if self.prefix is None:
            return self.assign(slot, tokens), 0
        path = self.prefix.match(prompt)
        shared = [node.page for node in path]
        # pin the matched pages FIRST: at refcount >= 2 they are not
        # eviction candidates while we squeeze the pool for the tail
        self.allocator.retain(shared)
        try:
            fresh = self._alloc(n - len(shared))
        except OutOfPages:
            self.allocator.free(shared)
            raise
        covered = self.prefix.commit(path, len(prompt))
        pages = shared + fresh
        self._write_row(slot, pages)
        return pages, covered

    def release(self, slot: int) -> None:
        """Retire a sequence: drop its page references (shared pages
        survive under the prefix cache's reference), zero its table row."""
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.allocator.free(pages)
        self.page_table[slot, :] = 0

    def slot_pages(self, slot: int) -> list[int]:
        return list(self._slot_pages.get(slot, ()))

    # -- copy-on-write ---------------------------------------------------------
    def cow_page(self, slot: int, page_index: int) -> int:
        """Give ``slot`` a private copy of its ``page_index``-th page if
        it is shared (refcount > 1): allocate a fresh page, copy the
        device page contents in both pools, repoint the table row, drop
        the old reference.  Returns the (possibly unchanged) page id."""
        enforce(slot in self._slot_pages, f"slot {slot} not assigned")
        pages = self._slot_pages[slot]
        old = pages[page_index]
        if self.allocator.refcount(old) <= 1:
            return old
        from paddle_tpu.ops.pallas.paged_attention import copy_page

        new = self._alloc(1)[0]
        self.k = copy_page(self.k, old, new)
        self.v = copy_page(self.v, old, new)
        pages[page_index] = new
        self.page_table[slot, page_index] = new
        self.allocator.free([old])
        return new

    def cow_for_write(self, slot: int, start: int, tokens: int) -> None:
        """Privatise every page covering positions ``[start,
        start + tokens)`` before a write — shared (cached-prefix) pages
        are read-only.  Page-granular sharing places all writes past the
        shared prefix, so this normally copies nothing; it is the
        invariant that keeps COW semantics explicit and cheap."""
        if tokens <= 0:
            return
        for idx in range(start // self.page_size,
                         self.pages_needed(start + tokens)):
            self.cow_page(slot, idx)

    # -- occupancy -------------------------------------------------------------
    def resident_report(self) -> dict:
        """Refcount-aware occupancy: ``mapped_pages`` sums every slot's
        page list (what per-slot accounting would charge), while
        ``unique_pages`` counts physical pages once — their difference,
        plus cache-only pages, is what sharing saves.  Invariant:
        ``free_pages + unique_pages == num_pages - 1``."""
        mapped = sum(len(p) for p in self._slot_pages.values())
        distinct = len({p for row in self._slot_pages.values()
                        for p in row})
        return {
            "mapped_pages": mapped,
            "unique_pages": self.allocator.live_pages,
            "shared_saved_pages": mapped - distinct,
            "cached_pages": (self.prefix.cached_pages
                             if self.prefix is not None else 0),
            "reclaimable_pages": (self.prefix.reclaimable_pages()
                                  if self.prefix is not None else 0),
            "free_pages": self.allocator.free_pages,
        }
