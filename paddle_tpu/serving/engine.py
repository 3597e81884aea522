"""ServingEngine — the online inference front-end.

Owns the jitted prefill/decode closures over the paged KV-cache, drives
the continuous-batching :class:`~paddle_tpu.serving.scheduler.Scheduler`,
and exposes a thread-safe ``submit()/results()`` API:

    eng = ServingEngine(cfg, params, ServingConfig(max_slots=8))
    eng.start()                       # background step loop; or skip and
    rid = eng.submit([5, 17, 3], max_new_tokens=32, temperature=0.7)
    res = eng.results(n=1)[0]         # blocks until a request completes
    eng.stop()

Synchronous callers (CLIs, tests, benches) skip the thread:
``eng.generate(prompts)`` or ``submit(...)`` + ``run_until_idle()``.

**The order of an iteration** (:meth:`ServingEngine.step`).  The loop
keeps one pass in flight: the last token every slot sampled lives on the
device (``PagedKVCache.tokens``, carried and donated beside the pools), so
nothing a decode step needs waits for the step before it.  A model that
generates by blocks (``block_len`` > 1) runs the same loop, its block pass
the decode step: the array holds every slot's block in progress, tokens |
masked flags, and what the host decides — how many positions a pass
unmasks, which pass commits — it counts at dispatch.  An iteration

1. takes in new submissions, retires what the last iteration's reads
   finished and admits into the freed slots;
2. DISPATCHES the admitted requests' prefill pass, behind the decode step
   still running; it scatters each row's first token into the token
   array on the device, so the admitted ride the very next decode step;
3. builds that step from what the scheduler knows without reading
   anything — a sequence's position, context and key index count its
   tokens in flight — transfers its arrays and DISPATCHES it;
4. only then READS what was dispatched before that step, oldest first —
   the last iteration's decode step, this one's prefill pass — and hands
   the tokens out (``Scheduler.append_token``, one by one, in order).  A
   request's result is delivered once its last token has been read (by
   the retirement that follows).  One decode step stays in flight.  (By
   blocks a prefill pass samples nothing and is read an iteration later;
   a block is handed out at the read of the pass that committed it.)

A finish by length is known from the counts and the sequence rides no
further pass; an ``eos`` is data and is seen one pass late — the surplus
pass's token is dropped and counted (``serve_tokens_dropped_total``), its
K/V write lands inside the request's own reservation.  A device error
surfaces at the read, one pass late, and fails the loop as before; the
passes dispatched behind the failed one are lost with it, whoever met the
failure (the loop thread, a caller stepping by hand, a drain).

**Where the loop drains** (reads everything in flight before going on;
counter ``serve_loop_drains_total{why}``): when there is nothing left to
dispatch (``idle``: so ``step()`` keeps returning True while a pass is
unread, and ``run_until_idle`` leaves none), at ``stop()`` (``stop``),
before a weight swap (``swap``: :meth:`ServingEngine.set_params`), and in the
one configuration that keeps the synchronous order — dispatch, read,
then go on — for every pass: the incremental prefill path
(``incremental``: prefix cache / chunked prefill, whose pass's first
tokens the host seeds into the token array).  Which it is follows from
the engine's configuration; there is no switch.

Telemetry rides the shared :class:`MetricsRegistry`: histograms
``serve_queue_wait_ms`` / ``serve_prefill_ms`` / ``serve_decode_step_ms``
/ ``serve_ttft_ms`` / ``serve_tpot_ms``, counters ``serve_requests`` /
``serve_tokens`` / ``serve_layer_passes_total`` (decoder blocks run by
decode steps: batch × num_layers × loop_steps a step) /
``serve_decode_kv_writes_total{path=kernel|scatter}`` (cache layers and
rings a one-token decode step writes its tokens' K/V into, by who writes
them in the decode program — the paged-attention kernel, or a scatter a
pool ahead of it — which the ``serve_decode`` span says as ``kv_write``) /
``serve_loop_crashes`` (background loops that died —
pending ``results()`` callers get the loop's exception re-raised
instead of blocking forever) / ``serve_passes_ahead_total{kind=prefill|
decode}`` (passes dispatched while the one before was unread) /
``serve_loop_drains_total{why}`` (above), gauges ``serve_active_slots`` /
``serve_free_pages`` / ``serve_kv_bytes_per_token`` /
``serve_state_bytes_per_slot`` (set once, at construction); from the
from-zero prefill passes counters ``serve_prefill_padded_tokens_total``
(rows x length of each pass's shape) / ``serve_prefill_prompt_tokens_total``
(their ratio is the passes' fill share) / ``serve_prefill_passes_total{length}``
(how often each length of the ladder ran) and gauge
``serve_prefill_programs`` (the shapes a pass may take: the scheduler's
``prefill_shapes`` — two, spent on rows where a pass is short and on
length where it is long — compiled with the decode program by the step
that admits the engine's first request; set once, at construction); under routed
experts counters ``serve_moe_assignments_total{where=held|absent}`` /
``serve_moe_experts_touched_total`` /
``serve_moe_product_passes_total{path=masked|kernel|reference}`` (passes
by the arrangement their program gives the expert product:
``parallel.moe.product_path``, which the spans say as ``moe_path``) and gauge
``serve_moe_load_max_over_mean`` (decode steps: the busiest held
expert's tokens over the mean); under layers that keep a state a
sequence counter ``serve_state_bytes_total{kind=mamba|mamba1|kda|attn}``
(the state pools' bytes the passes read and wrote for their live rows: a
decode step both, a prefill pass the write); under cross-attention or
window layers counters ``serve_shared_kv_bytes_total`` (K/V of the
growing cache the decode steps' layer-reads streamed: its own layers AND
the layers that read them, every live context once a read) and
``serve_window_tokens_total`` (ring rows a window layer read:
``min(length, window)`` a live slot); under delta-rule (KDA)
layers counter ``serve_kda_chunk_tokens_total`` (tokens of the chunks
that held a prompt token); under a model that
generates by blocks
(``block_len`` > 1) gauge ``serve_block_length`` and counters
``serve_block_passes_total{kind=denoise|commit}`` (a row of a block
pass, by whether it went in with masked positions),
``serve_blocks_committed_total``, ``serve_block_positions_total``
(rows x block_len computed) and ``serve_tokens_dropped_total`` (positions
chosen and never handed out: a last block's surplus, and what the
surplus pass after an ``eos`` sampled or unmasked); with
``--prefix_cache`` /
``--prefill_chunk_tokens``
also counters ``serve_prefix_hit_tokens`` / ``serve_prefill_flops_saved``
/ ``serve_prefill_chunks`` and gauge ``serve_cached_pages``,
one ``kind="serve"`` record per completed request and a
``kind="serve_summary"`` record (TTFT/TPOT p50/p99) from
:meth:`emit_summary` — rendered by ``tools/metrics_to_md.py``'s
"Serving latency" table.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import threading
import time

import numpy as np

from paddle_tpu.core import logger as log
from paddle_tpu.core.enforce import enforce
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.scheduler import (
    Request,
    RequestResult,
    Scheduler,
    ServingConfig,
)
from paddle_tpu.telemetry import scopes, tracing
from paddle_tpu.telemetry.registry import geometric_buckets

# the latency histograms whose quantiles are read (the router's and the
# autoscaler's TTFT p99, SLO checks, the benchmark's per-layer metrics):
# geometric edges, so a quantile is within 5% of the observations around
# it at any magnitude (DEFAULT_BUCKETS' ... 10, 25, 50 ... put a 9.1 ms
# step at 7.3 and a 43.5 ms one at 34.5)
_LATENCY_BUCKETS = geometric_buckets(0.1, 60_000.0, 1.05)
_LATENCY_HELP = {
    "serve_queue_wait_ms": "request wait between arrival and admission",
    "serve_prefill_ms": "prefill pass wall ms (per admitted batch)",
    "serve_decode_step_ms": "one continuous-batching decode step, wall ms",
    "serve_ttft_ms": "time to first token",
    "serve_tpot_ms": "mean per-token decode latency",
}
_LAT_HISTS = tuple(_LATENCY_HELP)


def _latency(registry, name: str):
    return registry.histogram(name, _LATENCY_HELP[name],
                              buckets=_LATENCY_BUCKETS)


def drain_results(completed: "queue.Queue", loop_error_now, what: str,
                  n: int | None = None, timeout: float | None = None):
    """The shared ``results()`` back-end (ServingEngine and the fleet's
    FleetRouter): pop up to ``n`` completed results (all currently
    available if None), blocking up to ``timeout`` for the first.
    Blocking waits run in short slices re-checking ``loop_error_now``,
    so a dying loop thread fails blocked callers with its exception
    (labeled ``what``) instead of parking them forever — already-queued
    results are always handed out first."""
    def pop(block: bool, deadline: float | None, raise_on_crash: bool):
        while True:
            try:
                return completed.get(block=False)
            except queue.Empty:
                pass
            err = loop_error_now()
            if err is not None and raise_on_crash:
                raise RuntimeError(
                    f"{what} crashed; pending requests will never "
                    "complete") from err
            if not block:
                return None
            remaining = (None if deadline is None
                         else deadline - time.monotonic())
            if remaining is not None and remaining <= 0:
                return None
            try:
                return completed.get(
                    timeout=0.05 if remaining is None
                    else min(0.05, remaining))
            except queue.Empty:
                continue

    out: list = []
    deadline = None if timeout is None else time.monotonic() + timeout
    if n is None:
        # drain mode: optionally wait up to timeout for the first, then
        # take whatever else is already there
        r = pop(block=timeout is not None, deadline=deadline,
                raise_on_crash=True)
        while r is not None:
            out.append(r)
            r = pop(block=False, deadline=None, raise_on_crash=False)
        return out
    while len(out) < n:
        r = pop(block=True, deadline=deadline, raise_on_crash=not out)
        if r is None:
            break
        out.append(r)
    return out


@dataclasses.dataclass
class _Pass:
    """A dispatched pass whose tokens the host has not read."""

    kind: str           # "prefill" | "decode": its span and counters
    rows: list          # the sequences it samples for
    n_out: int          # tokens in ``out`` (routing counts ride behind)
    args: dict          # what its span says of it (empty: tracing off)
    t_from: float       # start of the interval it adds to the loop: when
                        # the loop turned to it, or the read-back before
                        # its own where that ended later
    out: object = None  # the int32 device array its tokens come out in
    span: object = None  # its open span
    t: int = 0          # rows its program runs a layer over


def _resident_bytes(engine, _) -> dict:
    """What a new engine put on its device (``engine_init``'s args)."""
    import jax

    nbytes = lambda tree: sum(int(x.nbytes) for x in jax.tree.leaves(tree))
    cache = engine.cache
    said = {"params_bytes": nbytes(engine.params),
            "pool_bytes": nbytes((cache.k, cache.v)),
            "state_bytes": nbytes(cache.state)}
    if cache.window:
        said["window_bytes"] = nbytes(cache.window)
    return said


class ServingEngine:
    @tracing.setup_span("engine_init", _resident_bytes)
    def __init__(self, cfg, params, serving: ServingConfig | None = None,
                 registry=None, device=None):
        """``cfg``: TransformerConfig; ``params``: the matching pytree
        (e.g. from ``serving.export.load_servable``); ``serving``:
        engine knobs; ``device``: the ``jax.Device`` this engine lives on
        — weights, KV page pools and every batch are committed there, so
        its jitted functions run there (a fleet gives each replica its
        own).  None keeps jax's default placement."""
        import jax

        from paddle_tpu import metrics as metrics_mod

        self.cfg = cfg
        self.serving = serving or ServingConfig()
        s = self.serving
        enforce(s.max_prompt_len <= cfg.max_seq_len
                and s.max_prompt_len + s.max_new_tokens <= cfg.max_seq_len,
                "max_prompt_len + max_new_tokens exceeds cfg.max_seq_len")
        # liveness: the largest admissible request must fit an EMPTY
        # engine, or a queue head could block forever (admission is FIFO)
        enforce(s.num_pages - 1 >= s.max_pages_per_seq,
                f"num_pages {s.num_pages} (1 reserved for the null page) "
                f"cannot hold one max-size request "
                f"({s.max_pages_per_seq} pages)")
        enforce(not s.max_concurrent_tokens or s.max_concurrent_tokens
                >= s.max_prompt_len + s.max_new_tokens,
                "max_concurrent_tokens is below one max-size request's "
                "reservation — nothing could ever be admitted")
        enforce(s.prefill_chunk_tokens >= 0,
                "prefill_chunk_tokens must be >= 0 (0 = chunking off)")
        if cfg.block_len > 1 and s.incremental_prefill:
            raise NotImplementedError(
                "prefix_cache / prefill_chunk_tokens with block_len > 1: the "
                "chunk path's attention is causal over tokens, not over "
                "blocks (paged_prefill_attention), a chunk would have to end "
                "on a block boundary, and a shared prefix page may hold the "
                "prompt's tail, which is the first generated block's to "
                "write; none of it is built")
        if (cfg.window_layers or cfg.cross_reads) and s.incremental_prefill:
            raise NotImplementedError(
                "prefix_cache / prefill_chunk_tokens with window ('W') or "
                "cross ('X') attention layers: a chunk would have to read "
                "and advance its slot's ring (forward_prefill_chunk walks "
                "neither kind), and a shared prefix has no ring to share")
        if cfg.state_layers and s.incremental_prefill:
            raise NotImplementedError(
                "prefix_cache / prefill_chunk_tokens with layers that keep "
                f"a state a sequence ({sorted(cfg.state_kinds)}: a "
                "recurrent layer's, a CCA attention layer's convolution "
                "tail and shifted value): a chunk must start from its "
                "slot's state and leave it behind (forward_prefill_chunk "
                "carries none), and a prefix hit needs a snapshot of the "
                "state at the shared prefix's last token (PrefixCache keeps "
                "pages only)")
        # GL-P-MEM serving path: with an --hbm_gb budget set, the static
        # KV pool + params bytes must fit BEFORE the pools are allocated
        # — an oversized pool fails here, not at the first admission
        from paddle_tpu.analysis.memory import (serving_budget_pass,
                                                serving_memory_report)
        from paddle_tpu.core import flags as _flags

        hbm_gb = float(_flags.get("hbm_gb"))
        if hbm_gb > 0:
            found = serving_budget_pass(
                serving_memory_report(cfg, s, params), hbm_gb=hbm_gb)
            enforce(not found,
                    found[0].message if found else "")
        self.device = device
        # one iteration at a time (the loop thread, a synchronous caller,
        # a swap's smoke decode): what is in flight has one owner
        self._pump = threading.RLock()
        # what the loop has on the device: the passes dispatched and not
        # read yet, oldest first (touched only under _pump)
        self._in_flight: collections.deque[_Pass] = collections.deque()
        self.registry = registry or metrics_mod.get_registry()
        self.params = self.place(params)
        # allocate the pools ON the engine's device (not on the default
        # device and then moved: a fleet's pools would all pass through
        # device 0), then commit them
        with jax.default_device(device):  # None = jax's default
            self.cache = PagedKVCache(
                cfg.cache_layers, cfg.kv_heads, cfg.head_dim, s.num_pages,
                s.page_size, s.max_slots, s.max_pages_per_seq,
                dtype=cfg.dtype, prefix_cache=s.prefix_cache,
                state_parts=cfg.state_parts, block_len=cfg.block_len,
                window=(cfg.window_layers, cfg.attn_window))
        (self.cache.k, self.cache.v, self.cache.state, self.cache.window,
         self.cache.tokens) = self.place(
            (self.cache.k, self.cache.v, self.cache.state, self.cache.window,
             self.cache.tokens))
        self.scheduler = Scheduler(s, self.cache, cfg.block_len)
        # 2·params is the standard per-token forward-FLOPs estimate —
        # what a prefix-cache hit's skipped recompute is booked at; a
        # looped stack passes its layer weights loop_steps times
        count = lambda tree: sum(int(x.size) for x in jax.tree.leaves(tree))
        self._flops_per_token = 2.0 * (
            count(params) + (cfg.loop_steps - 1) * count(params["blocks"]))
        # a pattern's routed layers: their counts ride behind a pass's
        # tokens, and a token passes top_k of the experts, not all held
        self._routed = bool(cfg.pattern and "E" in cfg.pattern)
        if self._routed:
            self._expert_slots = cfg.routed.num_held * cfg.pattern.count("E")
            # what ``moe_routed`` reads when a program of ``t`` rows is
            # traced: the arrangement of its expert product
            from paddle_tpu.parallel.moe import product_path

            w_in = next(b["w_in"] for b in params["blocks"] if "router" in b)
            w_in = jax.ShapeDtypeStruct(w_in.shape, w_in.dtype)
            self._moe_path = functools.cache(
                lambda t: product_path(t, cfg.routed, w_in))
            self._flops_per_token -= 2.0 * max(
                0.0, 1.0 - cfg.moe_top_k / cfg.moe_experts) * count(
                    [(b["w_in"], b["w_out"]) for b in params["blocks"]
                     if "router" in b])
        # one resident token's K and V over every cache layer
        self.kv_bytes_per_token = (
            2 * cfg.cache_layers * cfg.kv_heads * cfg.head_dim
            * self.cache.k.dtype.itemsize)
        self.registry.gauge(
            "serve_kv_bytes_per_token",
            "K and V bytes one resident token holds over every cache "
            "layer (attention layers x loop_steps) at the cache's "
            "kv_heads").set(self.kv_bytes_per_token)
        self.registry.gauge(
            "serve_state_bytes_per_slot",
            "recurrent-state bytes one resident sequence holds over every "
            "state layer (0 without such layers)").set(
                self.cache.state_bytes_per_slot)
        self.registry.gauge(
            "serve_prefill_programs",
            "shapes a from-zero prefill pass may take, all compiled "
            "before the engine's first pass").set(
                len(self.scheduler.prefill_shapes))
        # what every device pass's span says of the stack it ran
        self._loop_args = {"loop_steps": cfg.loop_steps,
                           "cache_layers": cfg.cache_layers}
        if cfg.pattern is not None:
            self._loop_args.update(kv_heads=cfg.kv_heads,
                                   state_layers=cfg.state_layers)
        # a decoder-hybrid-decoder pattern: layer-reads of the growing
        # cache a step ("*" and the "X" layers that read it), the window
        # layers' rings, and where a prefill pass narrows to its last
        # tokens (``_yoco_args``)
        self._yoco = bool(cfg.window_layers or cfg.cross_reads)
        if self._yoco:
            self._loop_args.update(kv_reads=cfg.kv_reads,
                                   window_layers=cfg.window_layers)
        # the state one slot holds, by the kind of layer that keeps it (a
        # pass's spans and the counter say what of it the pass moves)
        self._state_slot_bytes = {
            kind: sum(int(self.cache.state[part].nbytes) // s.max_slots
                      for part in shapes)
            for kind, (_, shapes) in cfg.state_kinds.items()}
        self._kda = "kda" in self._state_slot_bytes
        if self._kda:
            self._loop_args["kda_layers"] = cfg.pattern.count("K")
        self._block = cfg.block_len
        # the one configuration whose every pass is read before the next
        # is dispatched (module docstring)
        self._sync = s.incremental_prefill
        if self._sync:      # no pass goes out behind an unread one
            self._loop_args["ahead"] = 0
        # the decode step's batch fields in the program's order and the int32s
        # out: a token a slot — by blocks token | unmasked | confidence bits
        self._decode_fields = ("positions", "seq_lens", "page_table", "rids",
                               "gens", "temps")
        self._decode_out = s.max_slots
        if self._block > 1:
            self._decode_fields = ("ids", *self._decode_fields)
            self._decode_out *= 3 * self._block
            self._loop_args.update(block=self._block)
            self.registry.gauge(
                "serve_block_length",
                "positions a sequence's block pass carries (generation by "
                "diffusion over blocks)").set(self._block)
        # tokens one grid step of the decode kernel covers, and its rule
        # for the steps a row takes (``_context_args`` counts by it)
        from paddle_tpu.ops.pallas import paged_attention
        self._kv_block = s.page_size * paged_attention.decode_block_pages(
            cfg.kv_heads, s.page_size, cfg.head_dim,
            self.cache.k.dtype.itemsize, s.max_pages_per_seq)
        self._kv_steps = paged_attention.decode_steps
        self._chunk_passes = 0  # incremental prefill passes this engine ran
        # the compiled executables the step loop dispatches, by a prefill
        # pass's (rows, length) and "decode" (_make_ready, at the first
        # admission)
        self._programs: dict = {}
        self._base_key = self.place(jax.random.key(s.seed))
        self._lock = threading.Lock()
        self._incoming: collections.deque[Request] = collections.deque()
        self._completed: queue.Queue[RequestResult] = queue.Queue()
        self._next_id = 0
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._loop_error: BaseException | None = None
        self._stopped = False  # a stop()ed loop marks the engine dead
        self._build_fns()

    def _product_pass(self, t: int) -> dict:
        """Books a pass of ``t`` rows through routed expert layers under
        the arrangement its program gives the expert product (read from
        shapes, as ``moe_routed`` read it when the program was traced);
        returned as a span arg."""
        if not self._routed:
            return {}
        path = self._moe_path(t)
        self.registry.counter(
            "serve_moe_product_passes_total",
            "passes through routed expert layers, by the arrangement of "
            "the expert product in the program that ran").inc(path=path)
        return {"moe_path": path}

    def _split_counts(self, out, rows: int, where: str):
        """A pass's output -> its ``rows`` sampled tokens; the routing
        counts that ride behind them (``moe_routed``) are booked under
        ``where`` ("prefill" | "decode") and returned as span args."""
        out = np.asarray(out)
        if not self._routed:
            return out, {}
        held, absent, touched, busiest = (int(x) for x in out[rows:])
        reg = self.registry
        c = reg.counter("serve_moe_assignments_total",
                        "(token, expert) assignments routed, by whether "
                        "this device holds the expert")
        c.inc(held, where="held")
        c.inc(absent, where="absent")
        reg.counter("serve_moe_experts_touched_total",
                    "(layer, held expert) pairs that saw at least one "
                    "token, summed over passes").inc(touched)
        said = {"moe_assignments": held, "experts_touched": touched,
                "moe_load_max": busiest}
        if where == "decode" and held:
            over = busiest * self._expert_slots / held
            reg.gauge("serve_moe_load_max_over_mean",
                      "busiest held expert's tokens over the mean, last "
                      "decode step").set(over)
            said["moe_load_max_over_mean"] = round(over, 3)
        return out[:rows], said

    def place(self, tree):
        """Commit a pytree to this engine's device (identity when the
        engine has none)."""
        if self.device is None:
            return tree
        import jax

        return jax.device_put(tree, self.device)

    def set_params(self, params):
        """Serve ``params`` (same tree, shapes and types: the held
        executables take no other) from the next pass on; returns what
        was served before.  A weight swap goes through here, so a
        replica's params never drift to another replica's device, and
        whatever is in flight is read first, in the same hold of the loop
        as the assignment: no pass dispatched under the old weights is
        left for the new ones to follow, and a background loop dispatches
        none in between."""
        with self._pump:
            self._drain("swap")
            old, self.params = self.params, self.place(params)
        return old

    def _params(self):
        """The weights a pass is dispatched under.  Every caller is inside
        an iteration and holds ``_pump`` already; taken again here, where
        the attribute is read, because that hold is what keeps a swap from
        landing between a drain and a dispatch."""
        with self._pump:
            return self.params

    def _dev(self, batch: dict, *names):
        """Host batch fields -> arrays on this engine's device."""
        import jax

        return [jax.device_put(np.asarray(batch[n]), self.device)
                for n in names]

    # -- jitted compute -------------------------------------------------------
    def _build_fns(self) -> None:
        import dataclasses

        from paddle_tpu.ops.pallas import on_tpu

        cfg, attn_impl = self.cfg, self.serving.attn_impl
        # prefill runs cfg.attn_impl — but a TRAINING config may name a
        # mesh-dependent impl (ring/ulysses) or a Pallas kernel the
        # serving host can't run fast (flash off-TPU, where interpret
        # mode is a Python loop); degrade those to exact attention,
        # which is numerically equivalent at serving shapes
        if cfg.attn_impl in ("ring", "ulysses") or (
                cfg.attn_impl == "flash" and not on_tpu()):
            cfg = dataclasses.replace(cfg, attn_impl="exact")
        # what prefill really runs, for callers that must not be fooled
        # by the degrade above (chip_smoke.py asserts "flash" on the chip)
        self.prefill_attn_impl = cfg.attn_impl
        # donating the cache lets XLA update pages in place; CPU has no
        # donation and would warn every call
        donate = (2, 3) if on_tpu() else ()
        (self._prefill, self._prefill_chunk,
         self._decode) = _serving_fns(cfg, attn_impl, donate,
                                      self.serving.unmask_policy)
        # what a one-token decode step writes of the K/V caches — its
        # cache layers and rings (a cross layer writes nothing; a block
        # pass writes through the chunk's scatter) — and who writes it in
        # the program built above: ``paged_attention.decode_attention``
        from paddle_tpu.ops.pallas import resolve_impl

        self._kv_writes = (cfg.cache_layers + cfg.window_layers
                           if cfg.block_len == 1 else 0)
        self._kv_write_path = ("kernel" if resolve_impl(attn_impl) == "kernel"
                               else "scatter")

    # -- public API -----------------------------------------------------------
    def check_request(self, prompt,
                      max_new_tokens: int | None = None
                      ) -> tuple[list[int], int]:
        """Validate one request against the engine's caps and return the
        normalized ``(prompt, max_new_tokens)``.  Shared by :meth:`submit`
        and the fleet router (which must reject a bad request at its own
        front door instead of crashing a replica's step loop)."""
        s = self.serving
        prompt = [int(t) for t in prompt]
        n = s.max_new_tokens if max_new_tokens is None else max_new_tokens
        enforce(1 <= n <= s.max_new_tokens,
                f"max_new_tokens must be in [1, {s.max_new_tokens}], "
                f"got {n}")
        enforce(1 <= len(prompt) <= s.max_prompt_len,
                f"prompt length must be in [1, {s.max_prompt_len}], "
                f"got {len(prompt)}")
        v = self.cfg.vocab_size
        bad = [t for t in prompt if not 0 <= t < v]
        enforce(not bad, f"prompt ids {bad[:8]} outside [0, {v}) — jnp "
                "gather would clamp them silently")
        return prompt, n

    def submit(self, prompt, max_new_tokens: int | None = None,
               temperature: float = 0.0,
               request_id: int | None = None) -> int:
        """Queue one request (thread-safe); returns its request id.
        Prompt/limit validation errors raise here, not in the loop.

        ``request_id`` lets a fleet router pin the id (sampling keys are
        keyed by it, so a request re-dispatched to another replica after
        a failover samples the SAME tokens); uniqueness among in-flight
        ids is then the caller's contract.  A dead engine — background
        loop crashed, or ``stop()``\\ ed after running one — refuses the
        submit instead of enqueueing work nothing will ever serve."""
        prompt, n = self.check_request(prompt, max_new_tokens)
        err = self._loop_error_now()
        if err is not None:
            raise RuntimeError(
                "serving loop crashed; submit refused (restart the "
                "engine to forgive the crash)") from err
        with self._lock:
            if self._stopped:
                raise RuntimeError(
                    "engine is stopped; submit would enqueue into a dead "
                    "engine (call start() to serve again)")
            if request_id is None:
                rid = self._next_id
            else:
                rid = int(request_id)
                enforce(rid >= 0, f"request_id must be >= 0, got {rid}")
            self._next_id = max(self._next_id, rid + 1)
            self._incoming.append(Request(
                id=rid, prompt=prompt, max_new_tokens=n,
                temperature=float(temperature), arrival=time.perf_counter()))
        return rid

    def queued(self) -> int:
        """Requests accepted but not yet handed to the scheduler."""
        with self._lock:
            return len(self._incoming)

    def _loop_error_now(self) -> BaseException | None:
        # _loop_error is written by the background loop thread; every
        # access holds _lock (the GL-THREAD audited contract)
        with self._lock:
            return self._loop_error

    def results(self, n: int | None = None,
                timeout: float | None = None) -> list[RequestResult]:
        """Pop up to ``n`` completed results (all currently available if
        None), blocking up to ``timeout`` for the first.  If the
        background loop has died, callers that would otherwise come
        back empty-handed (or block forever) get the loop's exception
        re-raised instead — a pending future must fail, not hang."""
        return drain_results(self._completed, self._loop_error_now,
                             "serving loop", n=n, timeout=timeout)

    def generate(self, prompts, max_new_tokens: int | None = None,
                 temperature: float = 0.0) -> list[RequestResult]:
        """Synchronous convenience: submit every prompt, run the loop to
        idle, return results ordered by submission."""
        ids = [self.submit(p, max_new_tokens, temperature) for p in prompts]
        self.run_until_idle()
        got: dict[int, RequestResult] = {}
        mine = set(ids)
        for r in self.results():
            if r.id in mine:
                got[r.id] = r
            else:  # a concurrent submit()-er's result: leave it queued
                self._completed.put(r)
        return [got[i] for i in ids]

    def start(self) -> None:
        """Run the step loop on a background thread."""
        enforce(self._thread is None, "engine already started")
        with self._lock:
            self._loop_error = None  # a restart forgives the prior crash
            self._stopped = False
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="serving-engine", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop the background loop, read what it left in flight (tokens
        are handed out, requests whose last token that was are delivered)
        and emit the summary."""
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join()
            # a stopped background engine is DEAD until start(): a
            # submit() now would park in the queue forever, so refuse it
            # there.  Engines only ever driven synchronously (no thread)
            # keep accepting — generate()/run_until_idle still serve.
            with self._lock:
                self._stopped = True
        if self._drain("stop"):
            with self._pump:
                self._retire()
        self.emit_summary()

    def run_until_idle(self) -> None:
        """Drive the loop on the calling thread until no work remains,
        in flight included."""
        while self.step():
            pass

    # -- the step loop --------------------------------------------------------
    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                if not self.step():
                    time.sleep(1e-3)
        except BaseException as e:
            # a dead loop must not strand waiters: record the cause —
            # results() re-raises it to every pending caller — and
            # count it, so a crashed engine can't masquerade as idle
            with self._lock:
                self._loop_error = e
            from paddle_tpu.telemetry import safe_inc

            safe_inc("serve_loop_crashes",
                     "serving background loops that died",
                     registry=self.registry)
            log.error("serving loop crashed (%s: %s); failing pending "
                      "requests", type(e).__name__, e)

    def step(self) -> bool:
        """One iteration of the loop.  Returns False when fully idle: no
        submission, no resident sequence, no pass in flight.

        In order (module docstring): take in submissions, retire what the
        last iteration's reads finished, admit; DISPATCH the admitted
        requests' prefill pass; build and DISPATCH the next decode step,
        behind whatever is still running; only then READ what was
        dispatched before that step and hand the tokens out
        (``Scheduler.append_token``, in order; a request is delivered once
        its last token has been read).  So a decode step's tokens are
        handed out by the iteration after the one that dispatched it, a
        prefill pass's first tokens by its own, and the device always has
        the next step queued.  The incremental prefill path reads every
        pass before the next is dispatched instead (``_sync``); ``stop()``
        and a weight swap (``set_params``) read what is in flight; an
        engine with an unread pass is not idle.

        With span tracing on, an iteration that did work is one
        ``serve_step`` span (an idle one records nothing): its
        ``serve_schedule`` children are the calls that build or change
        scheduler / KV-cache state; ``serve_prefill`` / ``serve_decode``
        are leaves, one a pass, carrying the stack it ran (``loop_steps``,
        ``cache_layers``), ``ahead`` (1: dispatched while the pass before
        it was unread) and ``dispatch_ms`` (the dispatch call's own time);
        a from-zero prefill pass also the shape it ran and what was in it
        (``rows``, ``padded_tokens``, ``prompt_tokens``).  A pass's span
        closes at its read-back.  It opens after the read-back before it
        has ended, in the iteration that reads it: around the dispatch of
        the pass that follows it and the wait for its own tokens — or,
        where the loop reads every pass at once (``_sync``), around its
        own dispatch and the wait, as it always did.  Spans of one thread
        never overlap.  What is left over of ``serve_step``, its self
        time, is this loop's own Python: the small host-to-device
        transfers, the ``append_token`` loops, histograms and gauges.

        What a pass hands out: a prefill pass each admitted request's
        first token and a decode step one token a live sequence — or,
        under a model that generates by blocks (``block_len`` > 1), a
        prefill pass nothing (it leaves the K/V of the prompt's whole
        blocks) and a block pass 0 to ``block_len`` tokens a sequence:
        all of a block's at once, when the pass that went in over nothing
        masked, and so committed it, is read (``_land_blocks``)."""
        tracer = tracing.get_tracer()
        with self._pump:
            tk = None
            if tracer.enabled:
                tk = tracer.begin("serve_step", cat="serving",
                                  waiting=self.queued()
                                  + len(self.scheduler.queue),
                                  active=len(self.scheduler.active))
            worked = False
            try:
                worked = self._step(tracer)
            except BaseException:
                self._lose(tracer)
                raise
            finally:
                (tracer.end if worked else tracer.cancel)(tk)
        return worked

    def _scheduled(self, tracer, build, *args):
        """``build(*args)`` (a scheduler call that assembles a batch)
        under a ``serve_schedule`` span, kept only if there was one."""
        tk = tracer.begin("serve_schedule", cat="serving")
        batch = build(*args)
        (tracer.end if batch is not None else tracer.cancel)(tk)
        return batch

    def _context_args(self, seq_lens) -> dict:
        """What a decode pass's kernel had to read (every live row's
        resident context), what it fetched (the same in whole pages: it
        copies a row's live pages alone) and how (the tokens a grid step
        covers; the steps a cache layer: a step a block that holds a live
        token, an idle slot one), as span args — from the host's lengths."""
        page, block = self.serving.page_size, self._kv_block
        return {"context_tokens": int(seq_lens.sum()),
                "kv_block_tokens": int((-(-seq_lens // page)).sum() * page),
                "kv_block_len": block,
                "kv_steps": int(self._kv_steps(seq_lens, block).sum())}

    def _yoco_args(self, seq_lens) -> dict:
        """What a decode step of a pattern with cross or window layers
        reads beside its own cache layers, as span args and counters: the
        bytes of the growing cache its ``kv_reads`` layer-reads stream
        (every live context once a read) and the ring rows its window
        layers read (``min(length, window)`` a live slot, a layer)."""
        cfg, reg = self.cfg, self.registry
        ctx = int(seq_lens.sum())
        said = {"shared_kv_bytes": cfg.kv_reads * ctx * (
            self.kv_bytes_per_token // max(cfg.cache_layers, 1))}
        reg.counter(
            "serve_shared_kv_bytes_total",
            "K/V bytes of the growing cache the decode steps' layer-reads "
            "streamed: every '*' and 'X' layer reads every live context"
        ).inc(said["shared_kv_bytes"])
        if cfg.window_layers:
            said["window_tokens"] = int(
                np.minimum(seq_lens, cfg.attn_window).sum())
            reg.counter(
                "serve_window_tokens_total",
                "ring rows a window layer read over the decode steps: "
                "min(length, window) a live slot").inc(said["window_tokens"])
        return said

    def _make_ready(self) -> dict:
        """Compile every program this engine will dispatch, before the
        first of them serves (the step that admits the first request
        calls this ahead of its prefill pass): each member of the
        scheduler's prefill ladder (``prefill_shapes``: rows x length)
        and the decode program, lowered and
        compiled for a batch of slack rows only — the token array
        (``PagedKVCache.tokens``) among their arguments — and held: the
        step loop calls these executables, not the jitted functions.
        ``jax.jit`` would compile a shape the first time traffic brings
        it — seconds, in the middle of serving; an executable compiles
        nothing, and refuses arguments of another shape or type (a
        weight swap brings the same) instead.  Nothing runs here, so
        pools, state, token array and page table are what they were.
        Replicas share the jitted functions, so a fleet on one device
        traces, lowers and compiles once.  The incremental path has one
        prefill shape, compiled by its first pass as before.

        One ``engine_ready`` set-up span (under a layer pattern it says
        ``pattern_period`` and ``pattern_repeats``: what the programs'
        text holds of the pattern, and how often a scan repeats it)
        around one ``program_ready`` a
        program (``program``, ``rows``, ``length``: a prefill shape, or
        the decode step's slots x positions a pass), each with XLA's own
        trace / lower / compile-or-fetch under it
        (``tracing.XlaBuildListener``); the log lines read the same
        clock readings.  Under an armed tracer each ``program_ready``
        also says ``routes`` (the kernel-or-reference census of THAT
        program's trace) and ``op_scopes`` (its operations by sublayer:
        ``telemetry/scopes.py``)."""
        cache, sched = self.cache, self.scheduler
        shapes = (() if self.serving.incremental_prefill
                  else sched.prefill_shapes)
        tracer = tracing.get_tracer()
        programs = {}
        walk = {}
        if self.cfg.pattern is not None:    # how its programs walk it
            walk = dict(zip(("pattern_period", "pattern_repeats"),
                            self.cfg.pattern_roll))
        with tracer.timed("engine_ready", programs=len(shapes) + 1,
                          **walk) as ready:
            for rows, length in shapes:
                with tracer.timed("program_ready", program="prefill",
                                  rows=rows, length=length) as one:
                    args = self._dev(sched.prefill_arrays([], rows, length),
                                     "ids", "seq_lens", "page_table", "rids",
                                     "temps", "slots")
                    _, programs[rows, length] = scopes.compile_described(
                        one, lambda: self._prefill.lower(
                            self._params(), self._base_key, cache.k, cache.v,
                            *self._carried("prefill", args)))
                log.debug("prefill program of %d row(s) x %d ready after "
                          "%.2f s", rows, length, one.seconds)
            with tracer.timed("program_ready", program="decode",
                              rows=self.serving.max_slots,
                              length=self._block) as one:
                args = self._dev(sched.decode_arrays([]),
                                 *self._decode_fields)
                _, programs["decode"] = scopes.compile_described(
                    one, lambda: self._decode.lower(
                        self._params(), self._base_key, cache.k, cache.v,
                        *self._carried("decode", args)))
        with self._lock:    # all or none: a failure is met again
            self._programs = programs
        what = "block in progress" if self._block > 1 else "last token"
        log.info("serving engine ready: %d prefill program(s) of %s (rows x "
                 "length) + decode in %.2f s; %s, %s", len(shapes),
                 [f"{rows} x {length}" for rows, length in shapes],
                 ready.seconds,
                 f"the {what} of {self.serving.max_slots} slot(s) stays on "
                 "the device (the token array)",
                 "every pass is read before the next" if self._sync
                 else "the loop runs one pass ahead")
        return programs

    def _step(self, tracer) -> bool:
        sched, reg, flight = self.scheduler, self.registry, self._in_flight
        now = time.perf_counter()
        worked = False

        tk = tracer.begin("serve_schedule", cat="serving")
        with self._lock:
            while self._incoming:
                sched.enqueue(self._incoming.popleft())
                worked = True
            programs = self._programs
        if self._retire():      # what the last iteration's reads finished
            worked = True
        admitted = sched.admit(now=now)
        # a pass over empty queues and full slots is not scheduling work
        (tracer.end if worked or admitted else tracer.cancel)(tk)
        if admitted and not programs:
            # the first admission: nothing has been dispatched yet (an
            # idle step stays as cheap as it was: a fleet's router pumps
            # idle replicas all the time)
            programs = self._make_ready()
        # one pass ahead: the next decode step goes out first, and only
        # then is what was dispatched before it read
        waiting = len(flight)
        if admitted and not self.serving.incremental_prefill:
            # behind the decode step still in flight; its first tokens go
            # into the token array, so the admitted ride the step below
            self._send_prefill(tracer, programs, admitted)
            # and are this iteration's to hand out; by blocks the pass
            # samples nothing and is read behind the step below, next time
            waiting += self._block == 1
            worked = True

        if self.serving.incremental_prefill:
            if self._prefill_incremental(admitted, tracer, reg):
                worked = True

        batch = self._scheduled(tracer, sched.decode_batch)
        if waiting:
            # the span of the pass about to be read: the dispatch of the
            # step that follows it and the wait for its tokens
            self._open(tracer, flight[0])
        if batch is not None:
            self._send_decode(tracer, programs, batch)
        if self._sync:
            self._settle(tracer, "incremental")
        elif batch is None:     # nothing left to dispatch behind it
            self._settle(tracer, "idle")
        else:
            for _ in range(waiting):
                self._read(tracer)
        if waiting or batch is not None:
            worked = True

        reg.gauge("serve_active_slots",
                  "sequences resident in the decode batch").set(
                      len(sched.active))
        reg.gauge("serve_free_pages", "KV-cache pages on the free list").set(
            self.cache.allocator.free_pages)
        if self.cache.prefix is not None:
            # free + cached(unique, incl. mapped) + active-only pages ==
            # num_pages - 1: the refcounted-allocator identity
            # tests/test_serving.py asserts
            reg.gauge("serve_cached_pages",
                      "pages referenced by the prefix cache (LRU-"
                      "reclaimable once no sequence maps them)").set(
                          self.cache.prefix.cached_pages)
        return worked

    # -- passes in flight -------------------------------------------------------
    def _retire(self) -> bool:
        done = self.scheduler.retire_finished()
        for a in done:
            self._finish(a)
        return bool(done)

    def _send_prefill(self, tracer, programs, admitted) -> None:
        """Dispatch the from-zero prefill pass of ``admitted``.  It leaves
        each row's first token in the token array on the device, so the
        next decode step goes out before this pass is read (by blocks it
        samples nothing: a row's first block pass opens from the host's)."""
        reg = self.registry
        t0 = time.perf_counter()
        batch = self._scheduled(tracer, self.scheduler.prefill_batch,
                                admitted)
        args = self._dev(batch, "ids", "seq_lens", "page_table", "rids",
                         "temps", "slots")
        rows, length = batch["ids"].shape
        fill = {"rows": rows, "length": length,
                "padded_tokens": rows * length,
                "prompt_tokens": int(batch["seq_lens"].sum())}
        if self._block > 1:
            fill["blocks_written"] = fill["prompt_tokens"] // self._block
        if self._state_slot_bytes:      # the state the rows leave
            fill["state_bytes"] = self._state_moved(len(admitted), 1)
        if self._yoco:
            # positions that walked the layers behind the last producer:
            # the live rows where the pass narrows to their last tokens
            fill["cross_positions"] = (
                len(admitted) if self.cfg.narrow_at is not None
                else fill["prompt_tokens"])
        if self._kda:
            # the chunks that hold a token and the causal query-key pairs
            # of an attention layer
            chunk, lens = self.cfg.kda_chunk, batch["seq_lens"].astype(int)
            fill["kda_chunks"] = int((-(-lens // chunk)).sum())
            fill["attn_pairs"] = int((lens * (lens + 1) // 2).sum())
            reg.counter(
                "serve_kda_chunk_tokens_total",
                "tokens of the chunks of the chunked delta rule that held "
                "a prompt token, over the from-zero prefill passes").inc(
                    fill["kda_chunks"] * chunk)
        # the ratio of the two counters is the passes' fill share
        reg.counter(
            "serve_prefill_padded_tokens_total",
            "tokens the from-zero prefill passes computed (rows x "
            "length of each pass's shape)").inc(fill["padded_tokens"])
        reg.counter(
            "serve_prefill_prompt_tokens_total",
            "prompt tokens the from-zero prefill passes carried").inc(
                fill["prompt_tokens"])
        reg.counter(
            "serve_prefill_passes_total",
            "from-zero prefill passes, by the length of the ladder's "
            "member they ran at").inc(length=length)
        self._send(tracer, _Pass(
            "prefill", admitted, rows,
            dict(batch=len(admitted), **fill, **self._loop_args)
            if tracer.enabled else {}, t0, t=rows * length),
            programs[rows, length], *args)
        if self._block == 1:    # by blocks a prefill pass samples nothing
            self.scheduler.sent(admitted)

    def _state_moved(self, rows: int, times: int) -> int:
        """Bytes of the state pools a pass moves for ``rows`` live rows
        (``times``: 2 = read and written, 1 = written), booked by the
        kind of layer that keeps them."""
        booked = self.registry.counter(
            "serve_state_bytes_total",
            "bytes of the state pools (a recurrent layer's state and "
            "convolution tail, a CCA layer's) the passes read and wrote "
            "for their live rows, by the kind of layer")
        moved = 0
        for kind, per_slot in self._state_slot_bytes.items():
            booked.inc(times * rows * per_slot, kind=kind)
            moved += times * rows * per_slot
        return moved

    def _send_decode(self, tracer, programs, batch) -> None:
        """Dispatch one decode step over ``batch`` (``decode_batch``): a
        token for every live row, read from and written to the token
        array on the device (by blocks: a pass over every live row's block)."""
        live = batch.pop("live")
        t0 = time.perf_counter()
        args = self._dev(batch, *self._decode_fields)
        bl = self._block
        said = {}
        if tracer.enabled:
            # what the step reads, and of a pattern with recurrent state
            # the rows of the state pools it reads and rewrites
            said = dict(batch=len(live), **self._loop_args,
                        **self._context_args(batch["seq_lens"]))
            if self.cfg.state_layers:
                said["state_slots"] = len(live)
            if bl > 1:      # masked going in: the host's count
                said.update(positions=len(live) * bl,
                            masked_in=sum(a.block.masked for a in live))
            else:
                said["kv_write"] = self._kv_write_path
        if self._kv_writes:
            self.registry.counter(
                "serve_decode_kv_writes_total",
                "cache layers and rings the one-token decode steps wrote "
                "the new token's K/V into, by who writes it in the decode "
                "program: the paged-attention kernel itself, or an XLA "
                "scatter a pool ahead of it").inc(
                    self._kv_writes, path=self._kv_write_path)
        if self._yoco:      # counted always, said where a span listens
            reads = self._yoco_args(batch["seq_lens"])
            if tracer.enabled:
                said.update(reads)
        if self._state_slot_bytes:  # every live row's, read and written
            moved = self._state_moved(len(live), 2)
            if tracer.enabled:
                said["state_bytes"] = moved
        self._send(tracer, _Pass(
            "decode", live, self._decode_out, said, t0,
            t=self.serving.max_slots * bl), programs["decode"], *args)
        self.scheduler.sent(live)
        self.registry.counter(
            "serve_layer_passes_total",
            "decoder blocks run by decode steps (batch x num_layers x "
            "loop_steps a step)").inc(len(live) * bl * self.cfg.cache_layers)

    def _send(self, tracer, p: _Pass, program, *args) -> None:
        """Dispatch ``program`` (a held executable) for ``p`` over the
        carried arrays — pools, state, token array: donated, rebound to
        what comes back, touched by nothing in between — and put ``p`` in
        flight.  Nothing here waits for the device."""
        flight, cache = self._in_flight, self.cache
        ahead = int(bool(flight))
        if self._sync:
            # read before anything else is dispatched: its span is its
            # own dispatch and the wait
            self._open(tracer, p)
        t0 = time.perf_counter()
        p.out, cache.k, cache.v, by_slot, tokens = program(
            self._params(), self._base_key, cache.k, cache.v,
            *self._carried(p.kind, args))
        cache.carry(by_slot)
        if tokens is not None:
            cache.tokens = tokens
        if p.args:
            p.args.update(ahead=ahead, dispatch_ms=round(
                (time.perf_counter() - t0) * 1e3, 3))
        flight.append(p)
        self.registry.counter(
            "serve_passes_ahead_total",
            "passes dispatched while the pass before them was unread").inc(
                ahead, kind=p.kind)

    def _carried(self, kind: str, args) -> tuple:
        """A program's arguments behind the weights, the key and the pools:
        its batch fields and what is carried.  The token array is a
        one-token decode step's ids; a prefill pass by blocks never sees it."""
        cache = self.cache
        by_slot = cache.carried()   # the state pools and the rings
        if self._block == 1 and kind == "decode":
            return (cache.tokens, *args, by_slot)
        if self._block > 1 and kind == "prefill":
            return (*args, by_slot)
        return (*args, by_slot, cache.tokens)

    def _open(self, tracer, p: _Pass) -> None:
        if p.span is None:
            p.span = tracer.begin("serve_" + p.kind, cat="serving")

    def _read(self, tracer) -> None:
        """Wait for the tokens of the oldest pass in flight and hand them
        out (it stays in flight until they have come).  The interval the
        pass added to the loop — from the later of its dispatch and the
        read-back before it to its own read-back — is its observation in
        ``serve_prefill_ms`` / ``serve_decode_step_ms``."""
        sched, reg, flight = self.scheduler, self.registry, self._in_flight
        p = flight[0]
        self._open(tracer, p)
        toks, counts = self._split_counts(p.out, p.n_out, p.kind)
        counts.update(self._product_pass(p.t))
        flight.popleft()
        if self._block > 1 and p.kind == "decode":
            # booked inside the span, which says what came of the pass
            counts.update(self._land_blocks(p.rows, toks))
        tracer.end(p.span, **p.args, **counts)
        now = time.perf_counter()
        took_ms = (now - p.t_from) * 1e3
        for behind in flight:   # dispatched before this read-back ended
            behind.t_from = now
        handed = 0
        if p.kind == "decode":
            _latency(reg, "serve_decode_step_ms").observe(took_ms)
            if self._block == 1:
                for a in p.rows:
                    handed += sched.landed(a, int(toks[a.slot]))
                self._dropped(len(p.rows) - handed)
        else:
            _latency(reg, "serve_prefill_ms").observe(took_ms)
            for j, a in enumerate(p.rows):
                _latency(reg, "serve_queue_wait_ms").observe(
                    (a.t_admit - a.request.arrival) * 1e3)
                if self._block > 1:
                    # nothing sampled: the first block's commit hands out
                    # the first token
                    continue
                a.t_first = now
                _latency(reg, "serve_ttft_ms").observe(
                    (now - a.request.arrival) * 1e3)
                handed += sched.landed(a, int(toks[j]))
        reg.counter("serve_tokens", "tokens generated").inc(handed)

    def _settle(self, tracer, why: str) -> bool:
        """Read every pass in flight, oldest first (a drain, counted under
        ``why``); False when there was none."""
        flight = self._in_flight
        if not flight:
            return False
        while flight:
            self._read(tracer)
        self.registry.counter(
            "serve_loop_drains_total",
            "times the loop read everything in flight before going on, by "
            "why: idle | stop | swap | incremental").inc(1.0, why=why)
        return True

    def _drain(self, why: str) -> bool:
        """:meth:`_settle` from outside an iteration (``stop``,
        ``set_params``)."""
        tracer = tracing.get_tracer()
        with self._pump:
            try:
                return self._settle(tracer, why)
            except BaseException:
                self._lose(tracer)
                raise

    def _lose(self, tracer) -> None:
        """A pass failed (at its dispatch or, a device error, at its
        read): the passes behind it ran on the pools it was to hand on.
        None is read again — whoever steps, drains or stops next starts
        with nothing in flight, whichever caller met the failure."""
        flight = self._in_flight
        while flight:
            tracer.cancel(flight.pop().span)

    def _dropped(self, n: int) -> None:
        self.registry.counter(
            "serve_tokens_dropped_total",
            "positions chosen and never handed out (a last block's "
            "surplus, what followed an eos)").inc(n)

    def _land_blocks(self, rows, out) -> dict:
        """A block pass has been read.  ``out``: per slot and position
        the token the pass chose, whether the policy unmasked the
        position, the confidence's float32 bits.  Each of ``rows`` books
        its own (``Scheduler.block_landed``: a row that went in with
        nothing masked was committed by this pass and its tokens are
        handed out now).  -> what the pass's span says of it."""
        sched, reg, bl = self.scheduler, self.registry, self._block
        toks, unmasked, conf = out.reshape(3, -1, bl)
        conf = conf.view(np.float32)
        handed = dropped = commits = 0
        for a in rows:
            i, fresh = a.slot, not a.generated
            h, d, committed = sched.block_landed(a, toks[i], unmasked[i],
                                                 conf[i])
            if fresh and h:
                a.t_first = time.perf_counter()
                _latency(reg, "serve_ttft_ms").observe(
                    (a.t_first - a.request.arrival) * 1e3)
            handed, dropped, commits = (handed + h, dropped + d,
                                        commits + committed)
        reg.counter("serve_tokens", "tokens generated").inc(handed)
        self._dropped(dropped)
        passes = reg.counter(
            "serve_block_passes_total",
            "rows of block passes, by whether the row went in with masked "
            "positions (denoise) or with none (commit)")
        passes.inc(len(rows) - commits, kind="denoise")
        passes.inc(commits, kind="commit")
        reg.counter("serve_blocks_committed_total",
                    "blocks whose K/V a commit pass left in the cache").inc(
                        commits)
        reg.counter("serve_block_positions_total",
                    "positions block passes computed (rows x block_len)").inc(
                        len(rows) * bl)
        return {"unmasked": int(unmasked.sum()), "committed": commits,
                "commit_rows": commits, "tokens_out": handed}

    def _prefill_incremental(self, admitted, tracer, reg) -> bool:
        """The flag-on prefill path (prefix cache / chunked prefill):
        book admissions (queue wait, cache-hit savings), then run ONE
        offset prefill pass over up to ``prefill_batch`` mid-prefill
        sequences — each advances by at most ``prefill_chunk_tokens``
        (its whole uncached tail when chunking is off) — interleaved
        with the decode pass that follows in the same engine iteration,
        and read at once: this path keeps the synchronous order.
        A row whose prompt completes samples its first token from the
        pass's logits, and its full prompt pages are registered in the
        prefix cache for later requests to share."""
        sched = self.scheduler
        for a in admitted:
            _latency(reg, "serve_queue_wait_ms").observe(
                (a.t_admit - a.request.arrival) * 1e3)
            if a.cached_tokens:
                reg.counter(
                    "serve_prefix_hit_tokens",
                    "prompt tokens served from the prefix cache").inc(
                        a.cached_tokens)
                reg.counter(
                    "serve_prefill_flops_saved",
                    "prefill FLOPs not recomputed on prefix-cache hits "
                    "(2·params per token estimate)").inc(
                        self._flops_per_token * a.cached_tokens)
        batch = self._scheduled(tracer, sched.prefill_chunk_batch)
        if batch is None:
            return bool(admitted)
        rows, takes = batch.pop("rows"), batch.pop("takes")
        t0 = time.perf_counter()
        args = self._dev(batch, "ids", "starts", "seq_lens", "page_table",
                         "rids", "temps")
        tk = tracer.begin("serve_prefill", cat="serving",
                          batch=len(rows), chunked=True, **self._loop_args)
        toks, self.cache.k, self.cache.v, _ = self._prefill_chunk(
            self._params(), self._base_key, self.cache.k, self.cache.v,
            *args)
        toks, counts = self._split_counts(
            toks, self.serving.prefill_batch, "prefill")
        counts.update(self._product_pass(batch["ids"].size))
        if tk is not None:
            tracer.end(tk, **counts)
        t1 = time.perf_counter()
        _latency(reg, "serve_prefill_ms").observe((t1 - t0) * 1e3)
        reg.counter("serve_prefill_chunks",
                    "incremental prefill passes (chunk or cached "
                    "tail)").inc(len(rows))
        with self._lock:
            # emit_summary reads this from the caller's thread while the
            # background loop writes it (the GL-THREAD audited contract)
            self._chunk_passes += 1
        seeded = False
        for j, a in enumerate(rows):
            a.prefilled += takes[j]
            a.prefill_chunks += 1
            if a.prefilled >= a.prompt_len:
                # the pass's last-valid logits are this row's first-
                # token logits: its prompt is fully resident now
                a.t_first = t1
                _latency(reg, "serve_ttft_ms").observe(
                    (t1 - a.request.arrival) * 1e3)
                reg.counter("serve_tokens", "tokens generated").inc(1)
                sched.append_token(a, int(toks[j]))
                seeded = True
                if self.cache.prefix is not None:
                    self.cache.prefix.insert(
                        a.request.prompt, self.cache.slot_pages(a.slot))
        if seeded:
            # this path's program does not write the token array: seed it
            # from the host, which here has read every token there is
            self.cache.tokens, = self._dev(
                {"tokens": sched.last_tokens()}, "tokens")
        return True

    def _finish(self, a) -> None:
        now = time.perf_counter()
        n = len(a.generated)
        ttft_ms = (a.t_first - a.request.arrival) * 1e3
        tpot_ms = ((now - a.t_first) / max(n - 1, 1)) * 1e3
        total_ms = (now - a.request.arrival) * 1e3
        tracer = tracing.get_tracer()
        if tracer.enabled:
            # the request's lifecycle, reconstructed retrospectively at
            # retire time from its own timestamps: one parent "request"
            # span with queue → prefill → decode children, so a merged
            # timeline shows per-request phases next to the batch-level
            # serve_prefill/serve_decode spans
            rid = a.request.id
            parent = tracer.add_span(
                "request", a.request.arrival, now, cat="serving",
                request=rid, finish=a.finished, tokens=n)
            tracer.add_span("queue", a.request.arrival, a.t_admit,
                            cat="serving", parent_id=parent, request=rid)
            tracer.add_span("prefill", a.t_admit, a.t_first,
                            cat="serving", parent_id=parent, request=rid)
            tracer.add_span("decode", a.t_first, now, cat="serving",
                            parent_id=parent, request=rid)
        _latency(self.registry, "serve_tpot_ms").observe(tpot_ms)
        self.registry.counter(
            "serve_requests", "completed requests").inc(
                1.0, reason=a.finished)
        # per-request cost attribution, from the request's OWN
        # timestamps (no new clocks): the wall seconds of each
        # lifecycle phase it occupied, plus its KV-page
        # occupancy-seconds (pages held × admitted residency).  These
        # are occupancy figures — a batched prefill charges its wall to
        # every member — so summed attribution measures demand, the way
        # replica-seconds do.  The goodput ledger folds the counters
        # below into the run's closing cost-per-token split, and the
        # fleet router rolls them up across replicas.
        queue_s = max(0.0, a.t_admit - a.request.arrival)
        prefill_s = max(0.0, a.t_first - a.t_admit)
        decode_s = max(0.0, now - a.t_first)
        pages = self.cache.pages_needed(a.prompt_len + n)
        kv_page_s = pages * max(0.0, now - a.t_admit)
        reg = self.registry
        reg.counter("serve_queue_s",
                    "summed request queue-seconds").inc(queue_s)
        reg.counter("serve_prefill_compute_s",
                    "summed prefill-phase occupancy seconds").inc(prefill_s)
        reg.counter("serve_decode_compute_s",
                    "summed decode-phase occupancy seconds").inc(decode_s)
        reg.counter("serve_kv_page_s",
                    "summed KV-page occupancy-seconds").inc(kv_page_s)
        rec = {
            "request": a.request.id, "prompt_tokens": a.prompt_len,
            "new_tokens": n, "finish": a.finished,
            "queue_wait_ms": round((a.t_admit - a.request.arrival) * 1e3, 3),
            "ttft_ms": round(ttft_ms, 3), "tpot_ms": round(tpot_ms, 3),
            "total_ms": round(total_ms, 3),
            "queue_s": round(queue_s, 6),
            "prefill_s": round(prefill_s, 6),
            "decode_s": round(decode_s, 6),
            "kv_page_s": round(kv_page_s, 6),
            "cost_per_token_s": round((prefill_s + decode_s) / n, 9)
                                if n else None,
            "cached_tokens": a.cached_tokens,
            "prefill_chunks": a.prefill_chunks,
        }
        if self.registry.active:
            self.registry.emit(rec, kind="serve")
        self._completed.put(RequestResult(
            id=a.request.id, prompt=list(a.request.prompt),
            tokens=list(a.generated), finish_reason=a.finished,
            metrics=rec, trail=a.trail))

    def emit_summary(self) -> None:
        """One ``serve_summary`` record with the latency histograms'
        count/p50/p99/max — the SLO rollup operators read."""
        if not self.registry.active:
            return
        summary: dict = {}
        for name in _LAT_HISTS:
            h = self.registry.get(name)
            s = h.summary() if h is not None else None
            if s and s.get("count"):
                # zero-observation histograms are skipped, not rolled
                # up: an engine that served nothing must not report
                # p50/p99/max quantiles of an empty distribution
                summary[name] = {k: s[k] for k in
                                 ("count", "p50", "p99", "max")}
        rec = {"summary": summary,
               "rejected_admissions": self.scheduler.rejected_admissions}
        if self.cache.prefix is not None:
            p = self.cache.prefix
            denom = max(p.hits + p.misses, 1)
            rec["prefix"] = {
                "hits": p.hits, "misses": p.misses,
                "hit_tokens": p.hit_tokens,
                "prompt_tokens": p.prompt_tokens,
                "hit_rate": round(p.hit_tokens /
                                  max(p.prompt_tokens, 1), 4),
                "request_hit_rate": round(p.hits / denom, 4),
                "evictions": p.evictions, "inserts": p.inserts,
                "cached_pages": p.cached_pages,
                "flops_saved": self._flops_per_token * p.hit_tokens,
            }
        if self.serving.incremental_prefill:
            with self._lock:
                rec["prefill_chunks"] = self._chunk_passes
        self.registry.emit(rec, kind="serve_summary")


# (cfg, attn_impl, donate, policy) -> (prefill, prefill_chunk, decode).  The
# jitted serving closures are fully determined by this key — params,
# caches and batches all arrive as arguments — so engines built on the
# same config (every fleet replica, a restarted engine, a weight swap)
# share ONE set of jit objects and their compiled executables instead
# of paying XLA again per engine.  Populated under _FN_LOCK from
# whatever thread constructs the engine; the tuples are immutable.
_FN_MEMO: dict = {}
_FN_LOCK = threading.Lock()


def _serving_fns(cfg, attn_impl, donate, policy="low_confidence_static"):
    """``policy``: ``ServingConfig.unmask_policy``, compiled into the
    decode program of a model that generates by blocks (no other program
    reads it)."""
    key = (cfg, attn_impl, donate, policy if cfg.block_len > 1 else None)
    with _FN_LOCK:
        fns = _FN_MEMO.get(key)
        if fns is not None:
            return fns

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving import sampling
    from paddle_tpu.telemetry.scopes import part

    def with_counts(toks, extras):
        """What a pass hands the host; behind it the routed layers'
        counts of this pass (one small int32 array rides out, nothing
        else to wait for)."""
        counts = extras.get("moe_counts")
        with part("sample"):
            return toks if counts is None else jnp.concatenate(
                [toks.astype(jnp.int32), counts])

    def unpack(out):
        """A forward's result -> (logits, k, v, extras): a config without
        a layer pattern hands back no extras."""
        return out if len(out) == 4 else (*out, {})

    def prefill(params, base_key, kc, vc, ids, lens, table, rids,
                temps, slots=None, state=None, last=None):
        """``last``: the token array (``PagedKVCache.tokens``); each row's
        first token goes into it at the row's slot.  None under a block
        length, where nothing is sampled (and for a caller that only
        lowers the pass): the program is then the one it always was."""
        logits, ks, vs, extras = unpack(
            T.forward_prefill(cfg, params, ids, lens))
        # what the pass leaves in the pools: pages, state rows, rings
        with part("kv.write"):
            if ks is not None:
                kc, vc = pa.write_prefill_kv(kc, vc, ks, vs, table, lens)
            # each row's recurrent state, whole, into its slot's row of
            # every state layer (a slack row's slot does not exist: dropped)
            state = dict(state or {})
            for name, pool in state.items():
                if name not in extras["state"]:
                    continue    # a ring: below
                for i in range(pool.shape[0]):
                    pool = pool.at[i, slots].set(
                        extras["state"][name][i].astype(pool.dtype),
                        mode="drop")
                state[name] = pool
            if "window" in extras:
                # each row's last window, whole, into its slot's ring
                state["window_k"], state["window_v"] = \
                    pa.write_prefill_window(
                        state["window_k"], state["window_v"],
                        *extras["window"], cfg.attn_window, slots)
        if cfg.block_len > 1:
            # nothing is sampled: the pass leaves K/V (the head is dead
            # code here); the counts ride behind a row of zeros
            return (with_counts(jnp.zeros_like(rids), extras), kc, vc, state,
                    last)
        with part("sample"):
            keys = sampling.request_keys(
                base_key, rids, jnp.zeros_like(rids))
            toks = sampling.sample_tokens(logits, keys, temps)
            if last is not None:
                # a slack row's slot does not exist: dropped, as its state is
                last = last.at[slots].set(toks, mode="drop")
        return with_counts(toks, extras), kc, vc, state, last

    def decode(params, base_key, kc, vc, last, positions, lens, table,
               rids, gens, temps, state=None):
        """``last`` (``PagedKVCache.tokens``) is the step's ``ids``, and
        gets the tokens the step samples; rows that are not decoding keep
        what they had."""
        logits, kc, vc, extras = unpack(T.forward_decode(
            cfg, params, last, positions, lens, table, kc, vc,
            attn_impl=attn_impl, state=state))
        with part("sample"):
            keys = sampling.request_keys(base_key, rids, gens)
            toks = sampling.sample_tokens(logits, keys, temps)
        out = with_counts(toks, extras)
        with part("sample"):
            last = jnp.where(lens > 0, toks, last)
        return out, kc, vc, extras.get("state", {}), last

    def decode_block(params, base_key, kc, vc, ids, positions, lens, table,
                     rids, gens, temps, state=None, last=None):
        """The block pass (``Scheduler.decode_arrays`` under a block
        length: ``ids`` = tokens | masked flags | how many to unmask |
        whether the row opens its block).  ``last``
        (``PagedKVCache.tokens``): every slot's block in progress, tokens
        | masked flags.  A row that opens its block takes the host's, any
        other the device's own; what the pass unmasks is written back,
        and rows that ride no pass keep theirs.  None (a caller that only
        lowers the pass): ``ids`` as given, nothing carried.
        Out: per position the chosen token, whether the policy unmasked
        it, its confidence's float32 bits; then the routing counts."""
        bl = cfg.block_len
        with part("sample"):
            masked, known = ids[:, bl:2 * bl] > 0, ids[:, :bl]
            if last is not None:
                opens = ids[:, 2 * bl + 1:] > 0
                known = jnp.where(opens, known, last[:, :bl])
                masked = jnp.where(opens, masked, last[:, bl:] > 0)
        logits, kc, vc, extras = T.forward_decode_block(
            cfg, params, known, masked, positions, lens, table, kc,
            vc, attn_impl=attn_impl)
        with part("sample"):
            toks, conf = sampling.sample_block(
                logits, base_key, rids, gens, temps)
            chosen = sampling.choose_unmask(policy, masked, conf,
                                            ids[:, 2 * bl])
            out = jnp.concatenate([
                toks.reshape(-1), chosen.astype(jnp.int32).reshape(-1),
                jax.lax.bitcast_convert_type(conf, jnp.int32).reshape(-1)])
            if last is not None:
                last = jnp.where((lens > 0)[:, None], jnp.concatenate(
                    [jnp.where(chosen, toks, known),
                     (masked & ~chosen).astype(jnp.int32)], axis=1), last)
        return with_counts(out, extras), kc, vc, {}, last

    if cfg.block_len > 1:
        # under the same name: the device trace's module stays jit_decode
        decode_block.__name__ = "decode"
        decode = decode_block

    def prefill_chunk(params, base_key, kc, vc, ids, starts, lens,
                      table, rids, temps):
        logits, kc, vc, extras = unpack(T.forward_prefill_chunk(
            cfg, params, ids, starts, lens, table, kc, vc))
        with part("sample"):
            keys = sampling.request_keys(
                base_key, rids, jnp.zeros_like(rids))
            toks = sampling.sample_tokens(logits, keys, temps)
        return with_counts(toks, extras), kc, vc, {}

    # the state pools ride behind the batch and are donated with the page
    # pools, and so is the token array wherever a program is handed it (by
    # blocks the prefill programs are not, and the block pass takes it last)
    def donated(state_at, tokens_at):
        return tuple(donate) + (
            (state_at,) if donate and (cfg.state_layers
                                       or cfg.window_layers) else ()) + (
            (tokens_at,) if donate else ())

    fns = (jax.jit(prefill, donate_argnums=donated(10, 11)),
           jax.jit(prefill_chunk, donate_argnums=donate),
           jax.jit(decode, donate_argnums=donated(
               11, 4 if cfg.block_len == 1 else 12)))
    with _FN_LOCK:
        # a racing builder may have won; keep the first so every engine
        # shares one executable cache
        return _FN_MEMO.setdefault(key, fns)
