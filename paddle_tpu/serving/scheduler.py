"""Continuous-batching scheduler — the serving engine's control plane.

Every step interleaves (the Gemma-on-TPU serving recipe, PAPERS arxiv
2605.25645): retire finished sequences (their pages return to the free
list), admit queued requests into free batch slots (prefill), then run
one decode step for every live sequence.  Sequences join and leave the
decode batch **per step** — no waiting for a whole batch to finish, which
is where continuous batching's throughput over static batching comes
from.

Admission control is FIFO with head-of-line blocking: a request is
admitted only when (a) a batch slot is free, (b) the page pool can cover
its whole reservation (prompt + max_new_tokens — reserved up front so a
live sequence can never hit out-of-pages mid-decode), and (c) the
concurrent-token budget holds.  If the head doesn't fit, nothing behind
it is admitted either — deterministic and starvation-free.

Everything here is host-side bookkeeping (numpy/python) — the scheduler
decides WHAT to run; the jitted compute lives in ``engine.py``.  Given a
seed and an arrival order, the whole trace (admissions, batch
compositions, sampled tokens) is deterministic; wall-clock enters only
the telemetry.

The engine keeps one pass in flight (``engine.py``), so a sequence's
tokens come in two kinds: ``generated``, handed out, and ``in_flight``,
sampled by a dispatched pass the host has not read yet (:meth:`sent` /
:meth:`landed`).  Everything a decode step needs but its input token —
position, context length, sampling-key index — counts both, and the
reservation made at admission covers both, so the next step's arrays are
built before the last step's tokens are known.  The input token itself
never leaves the device (``PagedKVCache.tokens``).

Under a model that generates by blocks the same split runs through a
block's bookkeeping.  Both unmasking policies unmask exactly the count
they are given, so everything the host DECIDES follows from counts
known when a pass is dispatched (:meth:`sent`: a block's passes and
masked count, the pass that commits it, the next block's start, a
finish by length); what the device CHOSE — tokens, order, confidences —
is booked when the pass is read, one pass late (:meth:`block_landed`),
and a block's tokens and masked flags stay on the device meanwhile
(``PagedKVCache.tokens``).
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.serving.kv_cache import OutOfPages, PagedKVCache


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Engine + scheduler knobs (model shape comes from TransformerConfig)."""

    max_slots: int = 8           # decode batch size = max concurrent seqs
    page_size: int = 16
    num_pages: int = 256         # pool size incl. the null page
    max_prompt_len: int = 64     # longest prompt = the longest prefill pass
    max_new_tokens: int = 64     # per-request cap (requests may ask less)
    # admissions per step = a pass's most rows (``prefill_shapes``: one
    # where the passes are long)
    prefill_batch: int = 4
    # 0 = no budget; else cap on the summed reservations (prompt +
    # max_new_tokens) of resident sequences — bounds worst-case context
    max_concurrent_tokens: int = 0
    eos_id: int | None = None
    seed: int = 0
    attn_impl: str = "auto"      # paged-attention impl (see paged_attention)
    # -- per-token serving cost (both off = the prior engine bit-for-
    #    bit; greedy-sampled tokens are identical either way) --
    # share full KV pages between requests with a common prompt prefix
    # (refcounted copy-on-write pages + the PrefixCache trie): a hit
    # maps resident pages into the new slot's table row and prefills
    # only the uncached tail
    prefix_cache: bool = False
    # > 0: prefill at most this many prompt tokens per request per
    # step, interleaved with decode steps, so a long prompt stops
    # stalling the decode batch's TTFT; 0 = whole prompt in one pass
    prefill_chunk_tokens: int = 0
    # -- generation by diffusion over blocks (a model with ``block_len`` >
    #    1; read by nothing else) --
    # denoising passes a block gets at most before the pass that commits
    # it: quality traded against passes (1 = the whole block from one pass)
    denoise_steps: int = 2
    # which masked positions a denoising pass unmasks:
    # "low_confidence_static" — the ceil(masked / steps left) whose chosen
    # token has the highest softmax probability; "sequential" — as many,
    # left to right.  An unmasked position is never masked again
    unmask_policy: str = "low_confidence_static"

    @property
    def max_pages_per_seq(self) -> int:
        return -(-(self.max_prompt_len + self.max_new_tokens)
                 // self.page_size)

    @property
    def incremental_prefill(self) -> bool:
        """True when prompts are prefilled through the offset chunk path
        (prefix cache and/or chunking) instead of one from-zero pass."""
        return self.prefix_cache or self.prefill_chunk_tokens > 0


def prefill_rows(prefill_batch: int) -> tuple[int, ...]:
    """The row counts of the ROW ladder, smallest first: one, and
    ``prefill_batch`` — what :func:`prefill_shapes` gives every engine
    whose passes are short, each member ``max_prompt_len`` long.

    A pass's time follows its shape, and most passes carry one row (a
    closed loop admits a request the moment one finishes: 72-87% of the
    passes of the benchmark's serve cells), so the single row is where a
    second program pays.  ``prefill_batch`` rows hold whatever ``admit``
    hands over."""
    return tuple(sorted({1, prefill_batch}))


# From this many positions in HALF a pass the ladder's second member is
# spent on length, not rows.  A member's price is a constant: the host
# traces and lowers its program in every process, warm cache or not, 1-3 s
# of set-up (``ServingEngine._make_ready``), which is why there are two
# and no more.  What a member saves grows with the pass: a half-length
# member saves a short prompt 43 ms of a 104 ms pass at 4,096 positions
# and 2-6 ms at 512-768, where it was measured at + 2-5% tokens a second
# beside the row ladder's two (PERF.md section 6, PRs 31 and 44) and
# ``setup_s`` has no room for a third.  And a pass this long is
# compute-bound many times over, so rows buy nothing there: two rows cost
# two one-row passes (261.7 against 139 ms, PR 42).
LENGTH_LADDER_MIN_HALF = 1024
# what a member's length has to divide into: pages, the chunks of the
# state layers' scans, the flash kernel's blocks
_LENGTH_MULTIPLE = 256


def prefill_shapes(prefill_batch: int,
                   max_prompt_len: int) -> tuple[tuple[int, int], ...]:
    """The ``(rows, length)`` shapes a from-zero prefill pass may take,
    fewest positions first — the prefill ladder, at most TWO members for
    every model alike (the engine compiles every member before it
    serves, and a member costs set-up time in every process):

    - short passes: ``(1, L)`` and ``(prefill_batch, L)`` at ``L`` =
      ``max_prompt_len`` — :func:`prefill_rows`;
    - long passes (``L // 2`` a whole multiple of 256 and at least
      ``LENGTH_LADDER_MIN_HALF``): ``(1, L // 2)`` and ``(1, L)`` — a
      prompt of up to half the length stops paying for the whole of it,
      and ``admit`` hands over one request an iteration."""
    half = max_prompt_len // 2
    if half >= LENGTH_LADDER_MIN_HALF and half % _LENGTH_MULTIPLE == 0:
        return ((1, half), (1, max_prompt_len))
    return tuple((rows, max_prompt_len)
                 for rows in prefill_rows(prefill_batch))


@dataclasses.dataclass
class Request:
    """One generation request (ids are assigned by the engine, monotonic
    in submission order — they seed per-request sampling keys)."""

    id: int
    prompt: list[int]
    max_new_tokens: int
    temperature: float = 0.0
    arrival: float = 0.0


@dataclasses.dataclass
class RequestResult:
    id: int
    prompt: list[int]
    tokens: list[int]            # generated tokens (incl. eos if hit)
    finish_reason: str           # "length" | "eos"
    metrics: dict = dataclasses.field(default_factory=dict)
    # generation by blocks only: every generated POSITION of the blocks
    # the request committed, in position order — ``tokens`` (the handed-
    # out ones first; a last block's surplus and what followed an eos are
    # only here), ``steps`` (the denoising pass of its block, from 0, that
    # unmasked each) and ``confidence`` (the softmax probability its token
    # had in that pass): the order a block came out in
    trail: dict | None = None


@dataclasses.dataclass
class _Block:
    """A block a sequence is generating: ``start`` its first absolute
    position.  Counted when a pass is dispatched: ``passes`` = denoising
    passes sent, ``masked`` = positions still masked once they have run.
    Booked when a pass is read: per position the token (0 while masked),
    the denoising pass that unmasked it (-1: known from the prompt, None:
    still masked) and the confidence it had then; ``landed`` = denoising
    passes read."""

    start: int
    ids: list
    steps: list
    conf: list
    masked: int
    passes: int = 0
    landed: int = 0


@dataclasses.dataclass
class _Active:
    """A resident sequence: one batch slot + its page reservation."""

    request: Request
    slot: int
    reserved_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    finished: str | None = None  # finish reason once known
    # tokens a dispatched pass has sampled for this sequence that the host
    # has not read yet (the engine runs one pass ahead)
    in_flight: int = 0
    t_admit: float = 0.0
    t_first: float = 0.0
    cached_tokens: int = 0       # prompt tokens mapped from the prefix cache
    prefilled: int = 0           # prompt tokens whose K/V are resident
    prefill_chunks: int = 0      # incremental prefill passes run
    # generation by blocks: the block the next pass is for, block passes
    # dispatched for this request, the block of every pass in flight
    # (oldest first), and the trail its committed blocks leave
    # (``RequestResult.trail``)
    block: _Block | None = None
    block_passes: int = 0
    unread: collections.deque = dataclasses.field(
        default_factory=collections.deque)
    trail: dict | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.request.prompt)

    @property
    def sampled(self) -> int:
        """Tokens sampled for this sequence so far, read or not."""
        return len(self.generated) + self.in_flight

    @property
    def next_position(self) -> int:
        """Absolute index of the token the next decode step feeds (the
        last sampled token, not yet in the cache)."""
        return self.prompt_len + self.sampled - 1


class Scheduler:
    def __init__(self, serving: ServingConfig, cache: PagedKVCache,
                 block_len: int = 1):
        """``block_len`` > 1: the model generates a block of positions a
        pass (``TransformerConfig.block_len``); every admitted sequence
        then carries a ``_Block`` and ``decode_arrays`` builds block
        passes."""
        enforce(cache.page_table.shape[0] >= serving.max_slots,
                "cache has fewer slot rows than max_slots")
        enforce(block_len == 1 or serving.page_size % block_len == 0,
                f"page_size {serving.page_size} is not a multiple of the "
                f"model's block_len {block_len}: a block would straddle "
                "two pages")
        enforce(serving.unmask_policy in ("low_confidence_static",
                                          "sequential"),
                f"unknown unmask_policy {serving.unmask_policy!r}")
        enforce(serving.denoise_steps >= 1, "denoise_steps must be >= 1")
        self.serving = serving
        self.cache = cache
        self.block_len = block_len
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[_Active | None] = [None] * serving.max_slots
        self.rejected_admissions = 0  # out-of-pages/budget head blocks
        self.prefill_shapes = prefill_shapes(serving.prefill_batch,
                                             serving.max_prompt_len)

    # -- state views ----------------------------------------------------------
    @property
    def prefill_rows(self) -> tuple[int, ...]:
        """The rows of each of ``prefill_shapes``."""
        return tuple(rows for rows, _ in self.prefill_shapes)

    @property
    def active(self) -> list[_Active]:
        return [a for a in self.slots if a is not None]

    @property
    def live(self) -> list[_Active]:
        return [a for a in self.slots if a is not None and not a.finished]

    def has_work(self) -> bool:
        return bool(self.queue or self.active)

    def _reserved(self) -> int:
        return sum(a.reserved_tokens for a in self.active)

    # -- queue + admission ----------------------------------------------------
    def enqueue(self, req: Request) -> None:
        enforce(len(req.prompt) >= 1, "empty prompt")
        enforce(len(req.prompt) <= self.serving.max_prompt_len,
                f"prompt of {len(req.prompt)} tokens exceeds "
                f"max_prompt_len {self.serving.max_prompt_len}")
        enforce(req.max_new_tokens >= 1, "max_new_tokens must be >= 1")
        enforce(req.max_new_tokens <= self.serving.max_new_tokens,
                f"max_new_tokens {req.max_new_tokens} exceeds the "
                f"engine cap {self.serving.max_new_tokens}")
        # admission is FIFO with head-of-line blocking, so a request
        # whose reservation can NEVER be satisfied — more pages than the
        # whole pool (or a table row) holds, or a bigger reservation
        # than the concurrent-token budget — would park at the head and
        # starve everything behind it forever.  Reject it now with the
        # reason, instead of letting it wedge the queue.  (ServingEngine
        # configs can't construct this case — its __init__ liveness
        # checks guarantee one max-size request always fits an empty
        # engine — but a standalone Scheduler over a small pool can.)
        reserve = len(req.prompt) + req.max_new_tokens
        need = self.cache.pages_needed(reserve)
        pool = self.cache.allocator.num_pages - 1  # page 0 is null
        enforce(need <= self.cache.max_pages_per_seq,
                f"request {req.id}: {reserve}-token reservation needs "
                f"{need} pages > max_pages_per_seq "
                f"{self.cache.max_pages_per_seq} — it could never be "
                f"admitted and would block FIFO admission forever")
        enforce(need <= pool,
                f"request {req.id}: {reserve}-token reservation needs "
                f"{need} pages but the whole pool holds {pool} — it "
                f"could never be admitted and would block FIFO "
                f"admission forever")
        budget = self.serving.max_concurrent_tokens
        enforce(not budget or reserve <= budget,
                f"request {req.id}: {reserve}-token reservation exceeds "
                f"max_concurrent_tokens {budget} — it could never be "
                f"admitted and would block FIFO admission forever")
        self.queue.append(req)

    def admit(self, now: float = 0.0) -> list[_Active]:
        """Admit queued requests into free slots (FIFO, head-of-line
        blocking — see module docstring), as many as one prefill pass
        holds: ``prefill_batch``, or the rows of the largest member of
        ``prefill_shapes`` where every from-zero pass is one of those (one
        request an iteration under one-row members: the next goes out an
        iteration later, behind this one).  Allocates pages and table
        rows; the engine prefills the returned batch."""
        s = self.serving
        admitted: list[_Active] = []
        budget = s.max_concurrent_tokens or None
        most = (s.prefill_batch if s.incremental_prefill
                else max(self.prefill_rows))
        while self.queue and len(admitted) < most:
            free = [i for i, a in enumerate(self.slots) if a is None]
            if not free:
                break
            req = self.queue[0]
            reserve = len(req.prompt) + req.max_new_tokens
            if budget is not None and self._reserved() + reserve > budget:
                self.rejected_admissions += 1
                break
            slot = free[0]
            covered = 0
            try:
                if s.prefix_cache and self.cache.prefix is not None:
                    _, covered = self.cache.assign_with_prefix(
                        slot, reserve, req.prompt)
                else:
                    self.cache.assign(slot, reserve)
            except OutOfPages:
                self.rejected_admissions += 1
                break
            self.queue.popleft()
            a = _Active(request=req, slot=slot, reserved_tokens=reserve,
                        t_admit=now, cached_tokens=covered,
                        prefilled=covered)
            if self.block_len > 1:
                # the prompt's whole blocks are prefilled; its tail opens
                # the first generated block as known positions
                a.trail = {"tokens": [], "steps": [], "confidence": []}
                self._open_block(a, len(req.prompt) // self.block_len
                                 * self.block_len)
            self.slots[slot] = a
            admitted.append(a)
        return admitted

    # -- token append + retirement --------------------------------------------
    def append_token(self, a: _Active, token: int) -> None:
        """Hand out a sampled token; flips ``finished`` on eos/length."""
        a.generated.append(token)
        if self.serving.eos_id is not None and token == self.serving.eos_id:
            a.finished = "eos"
        elif len(a.generated) >= a.request.max_new_tokens:
            a.finished = "length"

    def sent(self, rows: list[_Active]) -> None:
        """A pass that samples for each of ``rows`` has been dispatched:
        one token a row — or, by blocks, one pass over the row's block,
        whose outcome in counts is known now: a denoising pass unmasks
        ``unmask_count`` positions, a pass over nothing masked commits
        the block and the next one opens."""
        for a in rows:
            a.in_flight += 1
            blk = a.block
            if blk is None:
                continue
            a.unread.append(blk)
            a.block_passes += 1
            if blk.masked:
                blk.masked -= self.unmask_count(blk)
                blk.passes += 1
            else:
                self._open_block(a, blk.start + self.block_len)

    def landed(self, a: _Active, token: int) -> bool:
        """One in-flight token of ``a`` has been read: hand it out — unless
        the sequence finished meanwhile (an eos is data, seen one pass
        late: what the surplus pass sampled is dropped).  True when handed
        out."""
        a.in_flight -= 1
        if a.finished:
            return False
        self.append_token(a, token)
        return True

    # -- generation by blocks --------------------------------------------------
    def _open_block(self, a: _Active, start: int) -> None:
        """``a`` starts the block at ``start``: prompt tokens inside it
        are known, the rest masked."""
        known = a.request.prompt[start:start + self.block_len]
        rest = self.block_len - len(known)
        a.block = _Block(start=start, ids=list(known) + [0] * rest,
                         steps=[-1] * len(known) + [None] * rest,
                         conf=[1.0] * len(known) + [0.0] * rest, masked=rest)

    def unmask_count(self, blk: _Block) -> int:
        """Positions the next pass over ``blk`` unmasks: its masked ones
        spread evenly over the denoising passes left (0: nothing is
        masked, the pass commits the block)."""
        left = max(self.serving.denoise_steps - blk.passes, 1)
        return -(-blk.masked // left)

    def block_landed(self, a: _Active, tokens, unmasked,
                     conf) -> tuple[int, int, bool]:
        """The oldest block pass of ``a`` in flight has been read:
        ``tokens`` / ``conf`` [block_len] what it chose at every position
        and how sure it was, ``unmasked`` [block_len] the positions its
        policy unmasked.  A pass that went in over nothing masked has left
        the block's K/V in the cache: the block is committed, its
        generated positions join the trail and are handed out
        (``append_token``), in position order, until the request has what
        it asked for.  An ``eos`` is data, seen one pass late: what the
        surplus pass behind it unmasked is dropped, its block never
        committed.  -> (tokens handed out, positions dropped, committed)."""
        a.in_flight -= 1
        blk = a.unread.popleft()
        if a.finished:
            return 0, int(sum(unmasked)), False
        if None in blk.steps:
            for t in range(self.block_len):
                if unmasked[t]:
                    enforce(blk.steps[t] is None,
                            "a block pass unmasked a known position")
                    blk.ids[t], blk.steps[t] = int(tokens[t]), blk.landed
                    blk.conf[t] = float(conf[t])
            blk.landed += 1
            return 0, 0, False
        first = max(a.prompt_len - blk.start, 0)   # the prompt's tail
        for name, vals in (("tokens", blk.ids), ("steps", blk.steps),
                           ("confidence", blk.conf)):
            a.trail[name].extend(vals[first:])
        handed = 0
        for token in blk.ids[first:]:
            if a.finished:
                break
            self.append_token(a, token)
            handed += 1
        return handed, self.block_len - first - handed, True

    def retire_finished(self) -> list[_Active]:
        """Free the pages + slots of finished sequences; returns them."""
        done = [a for a in self.slots if a is not None and a.finished]
        for a in done:
            self.cache.release(a.slot)
            self.slots[a.slot] = None
        return done

    # -- decode batch assembly ------------------------------------------------
    def decode_batch(self) -> dict | None:
        """Fixed-shape arrays for one decode step over all live
        sequences, or None when there are none.  Sequences still
        mid-prefill (incremental path: no token sampled yet) are not
        decoded, and neither is one whose ``max_new_tokens`` the tokens
        in flight already reach — by blocks, whose next block would open
        past the last position it asked for: it is known finished without
        reading anything and rides no further pass."""
        def rides(a):
            if a.block is not None:
                return (a.block.start
                        < a.prompt_len + a.request.max_new_tokens)
            return 0 < a.sampled < a.request.max_new_tokens

        live = [a for a in self.live if rides(a)]
        return self.decode_arrays(live) if live else None

    def decode_arrays(self, live: list[_Active]) -> dict:
        """The decode step's arrays with ``live`` decoding.  Every other
        slot rides along masked (seq_len 0, null-page table row) so the
        jitted step has a single compile signature — with no sequence at
        all it is the batch the engine compiles the decode program for
        (``ServingEngine._make_ready``).

        Under a block length ``B`` > 1 a row is its sequence's block in
        progress, under the same names: ``ids`` [slots, 2B + 2] = the
        block's tokens | its masked flags | how many masked positions
        this pass unmasks (0: the pass commits the block) | whether the
        pass OPENS the block: its first pass takes tokens and flags from
        this row (the prompt's tail known, the rest masked), every later
        one from the device's own copy (``PagedKVCache.tokens``) and the
        row's are zeros; ``positions`` the block's first position;
        ``seq_lens`` the context the block's positions read, the block
        itself included (start + B); ``gens`` the index of the row's
        first position in its request's sampling keys (block passes
        dispatched so far x B)."""
        if self.block_len > 1:
            return self._block_arrays(live)
        n = self.serving.max_slots
        positions = np.zeros((n,), np.int32)
        seq_lens = np.zeros((n,), np.int32)
        rids = np.zeros((n,), np.int32)
        gens = np.zeros((n,), np.int32)
        temps = np.zeros((n,), np.float32)
        for a in live:
            i = a.slot
            positions[i] = a.next_position
            seq_lens[i] = a.next_position + 1
            rids[i] = a.request.id
            gens[i] = a.sampled
            temps[i] = a.request.temperature
        # the idle-row contract of the decode step's write
        # (``paged_attention.decode_attention``): an all-zero table row
        # AND ``seq_len`` 0 -> nothing of the row's is written.  The
        # scatter (``write_decode_kv``, off a TPU) reads only the table —
        # "all-zero row → null page" — so a mid-prefill slot (mapped
        # pages, no token yet) handed over with its pages would have its
        # masked write at position 0 corrupt the first prompt page; the
        # kernel reads only ``seq_len``.  So only the decoding rows are
        # copied (a fresh array every step: the transfer may alias it)
        rows = [a.slot for a in live]
        table = np.zeros_like(self.cache.page_table)
        table[rows] = self.cache.page_table[rows]
        return {
            "positions": positions, "seq_lens": seq_lens,
            "page_table": table,
            "rids": rids, "gens": gens, "temps": temps, "live": live,
        }

    def _block_arrays(self, live: list[_Active]) -> dict:
        n, bl = self.serving.max_slots, self.block_len
        ids = np.zeros((n, 2 * bl + 2), np.int32)
        positions = np.zeros((n,), np.int32)
        seq_lens = np.zeros((n,), np.int32)
        rids = np.zeros((n,), np.int32)
        gens = np.zeros((n,), np.int32)
        temps = np.zeros((n,), np.float32)
        table = np.zeros_like(self.cache.page_table)
        for a in live:
            i, blk = a.slot, a.block
            if not blk.passes:      # no pass has touched it: as opened
                ids[i, :bl] = blk.ids
                ids[i, bl:2 * bl] = [s is None for s in blk.steps]
                ids[i, 2 * bl + 1] = 1
            ids[i, 2 * bl] = self.unmask_count(blk)
            positions[i] = blk.start
            seq_lens[i] = blk.start + bl
            rids[i] = a.request.id
            gens[i] = a.block_passes * bl
            temps[i] = a.request.temperature
            table[i] = self.cache.page_table[i]
        return {
            "ids": ids, "positions": positions, "seq_lens": seq_lens,
            "page_table": table,
            "rids": rids, "gens": gens, "temps": temps, "live": live,
        }

    def prefill_batch(self, admitted: list[_Active]) -> dict:
        """Arrays for one prefill pass over newly admitted sequences, at
        the first member of ``prefill_shapes`` (fewest positions first)
        that holds them and their longest prompt, not at the largest
        whatever they are: a pass's time follows its shape."""
        longest = max(a.prompt_len for a in admitted)
        return self.prefill_arrays(admitted, *next(
            (rows, length) for rows, length in self.prefill_shapes
            if rows >= len(admitted) and length >= longest))

    def prefill_arrays(self, admitted: list[_Active], rows: int,
                       length: int | None = None) -> dict:
        """A prefill pass's arrays at ``rows`` x ``length``
        (``max_prompt_len`` unless given).  Slack
        rows are masked with len 0 and the null-page table row — with no
        sequence at all it is the batch the engine compiles that member
        of the ladder for (``ServingEngine._make_ready``).  ``slots``
        names each row's batch slot — the row of the cache's state pools
        its recurrent state goes to; a slack row names ``max_slots``, a
        row that does not exist, and its write is dropped."""
        s = self.serving
        nb, t = rows, length or s.max_prompt_len
        ids = np.zeros((nb, t), np.int32)
        lens = np.zeros((nb,), np.int32)
        table = np.zeros((nb, self.cache.max_pages_per_seq), np.int32)
        rids = np.zeros((nb,), np.int32)
        temps = np.zeros((nb,), np.float32)
        slots = np.full((nb,), s.max_slots, np.int32)
        for j, a in enumerate(admitted):
            ids[j, :a.prompt_len] = a.request.prompt
            # under a block length only the prompt's whole blocks are
            # prefilled (its tail is the first generated block's)
            lens[j] = a.prompt_len // self.block_len * self.block_len
            table[j] = self.cache.page_table[a.slot]
            rids[j] = a.request.id
            temps[j] = a.request.temperature
            slots[j] = a.slot
        return {"ids": ids, "seq_lens": lens, "page_table": table,
                "rids": rids, "temps": temps, "slots": slots}

    # -- incremental prefill (prefix cache / chunked) --------------------------
    def last_tokens(self) -> np.ndarray:
        """int32 [max_slots]: the last token handed out for each resident
        sequence (0 elsewhere) — what the device's token array holds when
        nothing is in flight."""
        out = np.zeros((self.serving.max_slots,), np.int32)
        for a in self.active:
            if a.generated:
                out[a.slot] = a.generated[-1]
        return out

    def prefilling(self) -> list[_Active]:
        """Sequences admitted but not yet fully prompt-resident — the
        incremental-prefill work list, slot order (deterministic)."""
        return [a for a in self.slots
                if a is not None and not a.finished
                and a.prefilled < a.prompt_len]

    def prefill_chunk_batch(self) -> dict | None:
        """Fixed-shape arrays for one incremental prefill pass (the
        flag-on twin of :meth:`prefill_batch`), or None when nothing is
        mid-prefill: up to ``prefill_batch`` rows, each advancing by at
        most ``prefill_chunk_tokens`` of its remaining prompt (the whole
        uncached tail when chunking is off).  Rows carry an absolute
        ``starts`` offset; ``seq_lens`` is the valid NEW tokens this
        pass.  ``takes``/``rows`` let the engine advance bookkeeping and
        sample first tokens for rows whose prompt completes."""
        s = self.serving
        rows = self.prefilling()[:s.prefill_batch]
        if not rows:
            return None
        c = (min(s.prefill_chunk_tokens, s.max_prompt_len)
             if s.prefill_chunk_tokens > 0 else s.max_prompt_len)
        nb = s.prefill_batch
        ids = np.zeros((nb, c), np.int32)
        starts = np.zeros((nb,), np.int32)
        lens = np.zeros((nb,), np.int32)
        table = np.zeros((nb, self.cache.max_pages_per_seq), np.int32)
        rids = np.zeros((nb,), np.int32)
        temps = np.zeros((nb,), np.float32)
        takes: list[int] = []
        for j, a in enumerate(rows):
            take = min(c, a.prompt_len - a.prefilled)
            # shared (cached-prefix) pages are read-only: privatise any
            # page this chunk would write — a no-op under page-granular
            # sharing (writes land past the shared prefix), kept as the
            # explicit copy-on-write guard
            self.cache.cow_for_write(a.slot, a.prefilled, take)
            ids[j, :take] = a.request.prompt[a.prefilled:a.prefilled + take]
            starts[j] = a.prefilled
            lens[j] = take
            table[j] = self.cache.page_table[a.slot]
            rids[j] = a.request.id
            temps[j] = a.request.temperature
            takes.append(take)
        return {"ids": ids, "starts": starts, "seq_lens": lens,
                "page_table": table, "rids": rids, "temps": temps,
                "rows": rows, "takes": takes}
