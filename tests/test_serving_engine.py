"""The serving engine and its scheduler: determinism given a seed and an
arrival order, admission control, eos, the background loop's failures,
per-request telemetry, prefix caching and chunked prefill as pure
optimisations, the static KV-pool gate.  Kernels:
``test_paged_attention.py``; the loop: ``test_serving_loop.py``; CLI and
servable: ``test_serving.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MemorySink, MetricsRegistry

from lm_toy import small_cfg

# one serving shape unless a test names its own: they share its programs
SERVING = dict(max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
               max_new_tokens=4, prefill_batch=2)


def _engine(key=1, registry=None, **serving):
    cfg = small_cfg()
    return ServingEngine(cfg, T.init_params(cfg, jax.random.key(key)),
                         ServingConfig(**{**SERVING, **serving}),
                         registry=registry)


class TestSchedulerAndEngine:
    def test_deterministic_given_seed_and_arrival_order(self, rng_np):
        prompts = [list(rng_np.integers(1, 64, size=5)) for _ in range(4)]

        def run():
            eng = _engine(2, max_new_tokens=6, seed=123)
            return [r.tokens for r in
                    eng.generate(prompts, max_new_tokens=6,
                                 temperature=0.8)]

        first, second = run(), run()
        assert first == second  # same seed + arrival order -> same trace
        # temperature actually samples (vs collapsing to argmax)
        from paddle_tpu.serving.sampling import request_keys, sample_tokens

        logits = jnp.asarray(rng_np.normal(size=(8, 64)).astype(np.float32))
        keys = request_keys(jax.random.key(123),
                            jnp.arange(8, dtype=jnp.int32),
                            jnp.zeros(8, jnp.int32))
        hot = sample_tokens(logits, keys, jnp.full((8,), 5.0))
        cold = sample_tokens(logits, keys, jnp.zeros((8,)))
        assert (np.asarray(hot) != np.asarray(cold)).any()
        np.testing.assert_array_equal(np.asarray(cold),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_eos_stops_and_frees_pages(self, rng_np):
        prompt = list(rng_np.integers(1, 64, size=4))
        one_slot = dict(max_slots=1, num_pages=16, max_new_tokens=8,
                        prefill_batch=1)
        tokens = _engine(**one_slot).generate(
            [prompt], max_new_tokens=8)[0].tokens
        eos = tokens[2]  # force an eos at the 3rd generated token
        eng = _engine(**one_slot, eos_id=eos)
        res = eng.generate([prompt], max_new_tokens=8)[0]
        assert res.finish_reason == "eos"
        # generation stops at the FIRST occurrence of eos (inclusive)
        assert res.tokens == tokens[:tokens.index(eos) + 1]
        assert eng.cache.allocator.free_pages == 15  # all pages returned

    def test_admission_blocks_on_pages_then_drains(self, rng_np):
        """More work than the pool can hold at once: requests queue,
        admission rejections are counted, everything still completes."""
        prompts = [list(rng_np.integers(1, 64, size=6)) for _ in range(6)]
        # pool: 7 usable pages; each request reserves (6+8)/4 -> 4 pages
        eng = _engine(max_slots=4, num_pages=8, max_new_tokens=8,
                      prefill_batch=4, seed=0)
        results = eng.generate(prompts, max_new_tokens=4)
        assert len(results) == 6
        assert all(len(r.tokens) == 4 for r in results)
        assert eng.scheduler.rejected_admissions > 0
        assert eng.cache.allocator.free_pages == 7

    def test_concurrent_token_budget(self, rng_np):
        prompts = [list(rng_np.integers(1, 64, size=4)) for _ in range(3)]
        eng = _engine(max_slots=4, num_pages=64, max_new_tokens=8,
                      prefill_batch=4,
                      max_concurrent_tokens=20)  # one (4+8) reservation + slack
        results = eng.generate(prompts, max_new_tokens=3)
        assert len(results) == 3
        assert eng.scheduler.rejected_admissions > 0

    def test_threaded_submit_results(self, rng_np):
        eng = _engine()
        eng.start()
        try:
            ids = [eng.submit(list(rng_np.integers(1, 64, size=4)),
                              max_new_tokens=3) for _ in range(3)]
            got = eng.results(n=3, timeout=60.0)
        finally:
            eng.stop()
        assert sorted(r.id for r in got) == sorted(ids)
        assert all(len(r.tokens) == 3 for r in got)

    def test_loop_crash_fails_pending_results(self, rng_np):
        """A dead background loop must FAIL blocked results() callers
        with its exception (and count the crash), not park them forever
        behind an engine that will never complete anything."""
        reg = MetricsRegistry("serve_crash")
        eng = _engine(registry=reg)
        boom = RuntimeError("injected decode fault")

        def bad_step():
            raise boom

        # submit BEFORE arming the crash: with the dead-engine guard a
        # post-crash submit refuses (asserted below), so the pending
        # request must predate the loop death
        eng.submit([1, 2, 3], max_new_tokens=3)
        eng.step = bad_step
        eng.start()
        try:
            with pytest.raises(RuntimeError,
                               match="serving loop crashed") as ei:
                eng.results(n=1, timeout=30.0)
            assert ei.value.__cause__ is boom
            # the non-blocking drain reports the crash too, rather than
            # returning an innocent-looking empty list
            with pytest.raises(RuntimeError, match="serving loop crashed"):
                eng.results()
            # ... and so does submit(): enqueueing into the dead engine
            # would park the request forever (PR 8 regression family)
            with pytest.raises(RuntimeError, match="submit refused"):
                eng.submit([1, 2, 3], max_new_tokens=3)
        finally:
            eng.stop()
        assert reg.counter("serve_loop_crashes", "").value() == 1.0

    def test_submit_after_stop_raises(self, rng_np):
        """stop() on a background engine marks it dead: a later submit
        must raise immediately, not enqueue into a loop that will never
        run again.  start() forgives (and sync-only engines that never
        ran a loop keep accepting)."""
        eng = _engine()
        eng.start()
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.results(n=1, timeout=60.0)
        eng.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit([1, 2, 3], max_new_tokens=2)
        eng.start()  # a restart re-opens the front door
        try:
            eng.submit([1, 2, 3], max_new_tokens=2)
            assert len(eng.results(n=1, timeout=60.0)) == 1
        finally:
            eng.stop()

    def test_impossible_reservation_rejected_at_enqueue(self):
        """A request whose prompt+max_new reservation exceeds the TOTAL
        page pool (or a table row, or the token budget) can never be
        admitted — FIFO admission would block forever behind it, so
        enqueue must reject it immediately with the reason."""
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        def mk(num_pages, max_pages_per_seq, budget=0):
            cache = PagedKVCache(1, 2, 16, num_pages, 4, 2,
                                 max_pages_per_seq)
            s = ServingConfig(max_slots=2, page_size=4,
                              num_pages=num_pages, max_prompt_len=64,
                              max_new_tokens=64,
                              max_concurrent_tokens=budget)
            return Scheduler(s, cache)

        # 8+8 tokens -> 4 pages, pool has 3 usable
        sched = mk(num_pages=4, max_pages_per_seq=8)
        with pytest.raises(Exception, match="whole pool"):
            sched.enqueue(Request(id=0, prompt=[1] * 8, max_new_tokens=8))
        assert not sched.queue  # nothing wedged at the head
        # table row too short even though the pool is big enough
        sched = mk(num_pages=64, max_pages_per_seq=2)
        with pytest.raises(Exception, match="max_pages_per_seq"):
            sched.enqueue(Request(id=1, prompt=[1] * 8, max_new_tokens=8))
        # reservation above the concurrent-token budget
        sched = mk(num_pages=64, max_pages_per_seq=32, budget=10)
        with pytest.raises(Exception, match="max_concurrent_tokens"):
            sched.enqueue(Request(id=2, prompt=[1] * 8, max_new_tokens=8))
        # a request that fits all three still queues, and drains
        sched = mk(num_pages=8, max_pages_per_seq=4, budget=16)
        sched.enqueue(Request(id=3, prompt=[1] * 4, max_new_tokens=4))
        assert len(sched.queue) == 1 and len(sched.admit()) == 1


class TestServeTelemetry:
    def test_per_request_records_and_percentiles(self, rng_np):
        reg = MetricsRegistry("serve_test")
        sink = MemorySink()
        reg.add_sink(sink)
        eng = _engine(registry=reg)
        prompts = [list(rng_np.integers(1, 64, size=4)) for _ in range(3)]
        eng.generate(prompts, max_new_tokens=4)
        eng.emit_summary()
        serves = [r for r in sink.records if r.get("kind") == "serve"]
        assert len(serves) == 3
        for r in serves:
            # the one place the tests spell the schema out: a bump of
            # ``telemetry/registry.py:SCHEMA`` is made here too
            assert r["schema"] == "paddle_tpu.metrics/16"
            for f in ("queue_wait_ms", "ttft_ms", "tpot_ms", "total_ms"):
                assert r[f] >= 0.0
            assert r["new_tokens"] == 4
        # TTFT/TPOT histograms expose asserted percentiles
        for name in ("serve_ttft_ms", "serve_tpot_ms"):
            h = reg.get(name)
            assert h.percentile(50) is not None
            assert h.percentile(50) <= h.percentile(99) <= h.summary()["max"]
        summaries = [r for r in sink.records
                     if r.get("kind") == "serve_summary"]
        assert summaries and "serve_ttft_ms" in summaries[-1]["summary"]
        assert reg.counter("serve_tokens").value() == 12.0

    @pytest.mark.parametrize("kind,parts,writes", [
        pytest.param("dense", {}, 2, id="dense"),
        pytest.param("looped", dict(loop_steps=2), 4, id="looped"),
        # the ring and the cache layer; the cross layer reads, writes nothing
        pytest.param("window_and_cross", dict(
            num_layers=6, pattern="W-*-X-", attn_window=8, positions="none"),
            2, id="window_and_cross"),
        # a block pass writes its block through the chunk's scatter
        pytest.param("block", dict(block_len=4, mask_id=63,
                                   positions="rotary"), 0, id="block")])
    def test_decode_kv_writes_are_counted_by_who_writes(self, rng_np, kind,
                                                        parts, writes):
        """``serve_decode_kv_writes_total{path}``: cache layers and rings a
        one-token decode step writes, times the steps, under the path the
        decode program was built with — off a TPU the scatter — which
        every ``serve_decode`` span says too."""
        import lm_toy

        cfg = small_cfg(**parts)
        reg = MetricsRegistry("kv_writes_" + kind)
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(3)),
                            ServingConfig(**SERVING), registry=reg)
        prompts = [list(rng_np.integers(1, 60, size=n)) for n in (5, 3)]
        _, spans = lm_toy.traced(
            lambda: eng.generate(prompts, max_new_tokens=4))
        steps = spans["serve_decode"]
        assert steps
        said = {s.args.get("kv_write") for s in steps}
        counted = reg.get("serve_decode_kv_writes_total")
        if not writes:
            assert counted is None and said == {None}
            return
        assert said == {"scatter"}
        assert counted.value(path="scatter") == writes * len(steps)
        assert counted.value(path="kernel") == 0

    def test_metrics_to_md_renders_serving_table(self, tmp_path, capsys):
        import json
        import sys

        sys.path.insert(0, "tools")
        try:
            import metrics_to_md
        finally:
            sys.path.pop(0)
        path = tmp_path / "m.jsonl"
        recs = [{"kind": "serve", "request": i, "prompt_tokens": 4,
                 "new_tokens": 8, "queue_wait_ms": 1.0 * i,
                 "ttft_ms": 10.0 + i, "tpot_ms": 2.0, "total_ms": 30.0}
                for i in range(5)]
        recs.append({"kind": "serve_summary", "rejected_admissions": 2,
                     "summary": {"serve_ttft_ms": {
                         "count": 5, "p50": 12.0, "p99": 14.9,
                         "max": 14.9}}})
        path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        metrics_to_md.main([str(path)])
        out = capsys.readouterr().out
        assert "## Serving latency" in out
        assert "TTFT" in out and "TPOT" in out
        assert "admission attempts" in out


class TestPrefixCacheAndChunkedPrefill:
    """The perf tentpole's correctness contract: prefix caching and
    chunked prefill are pure optimizations — greedy tokens identical in
    every flag combination, warm or cold — and the refcounted page
    accounting stays conservative throughout."""

    def _setup(self, rng_np, n_prompts=4, shared_head=8):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(3))
        head = list(rng_np.integers(1, 64, size=shared_head))
        prompts = [head + list(rng_np.integers(1, 64, size=4))
                   for _ in range(n_prompts)]
        prompts.append(list(rng_np.integers(1, 64, size=3)))  # no prefix
        return cfg, params, prompts

    def _run(self, cfg, params, prompts, registry=None, repeats=1, **kw):
        scfg = ServingConfig(max_slots=4, page_size=4, num_pages=64,
                             max_prompt_len=16, max_new_tokens=6,
                             prefill_batch=4, seed=0, **kw)
        eng = ServingEngine(cfg, params, scfg, registry=registry)
        out = []
        for _ in range(repeats):
            out.append([r.tokens for r in
                        eng.generate(prompts, temperature=0.0)])
        return eng, out

    def test_greedy_tokens_identical_across_all_flag_modes(self, rng_np):
        cfg, params, prompts = self._setup(rng_np)
        _, (base,) = self._run(cfg, params, prompts)
        # the prefix-only arm rides the warm-cache test's cold pass;
        # chunk 3 is the page-misaligned chunk boundary
        for kw in ({"prefill_chunk_tokens": 4},
                   {"prefill_chunk_tokens": 3},
                   {"prefix_cache": True, "prefill_chunk_tokens": 4}):
            _, (got,) = self._run(cfg, params, prompts, **kw)
            assert got == base, f"tokens diverged with {kw}"

    def test_warm_cache_identity_stats_and_page_conservation(self, rng_np):
        cfg, params, prompts = self._setup(rng_np)
        _, (base,) = self._run(cfg, params, prompts)
        reg = MetricsRegistry("serve_prefix")
        sink = MemorySink()
        reg.add_sink(sink)
        eng, (cold, warm) = self._run(cfg, params, prompts, registry=reg,
                                      repeats=2, prefix_cache=True)
        assert cold == base and warm == base
        p = eng.cache.prefix
        # warm round: 4 prompts share an 8-token (2-page) head; the
        # 3-token prompt has no full page to match
        assert p.hits >= 4 and p.hit_tokens >= 4 * 8
        assert reg.counter("serve_prefix_hit_tokens").value() >= 4 * 8
        assert reg.counter("serve_prefill_flops_saved").value() > 0
        # refcounted conservation: free + unique == pool - 1, with
        # cached pages resident and reclaimable after all releases
        rep = eng.cache.resident_report()
        assert rep["free_pages"] + rep["unique_pages"] == 63
        assert rep["cached_pages"] > 0
        assert rep["reclaimable_pages"] == rep["cached_pages"]
        # serve records carry the /14 fields
        serves = [r for r in sink.records if r.get("kind") == "serve"]
        assert sum(r["cached_tokens"] for r in serves) == p.hit_tokens
        eng.emit_summary()
        summ = [r for r in sink.records
                if r.get("kind") == "serve_summary"][-1]
        pre = summ["prefix"]
        assert pre["hit_tokens"] == p.hit_tokens
        assert 0.0 < pre["hit_rate"] <= 1.0
        assert pre["cached_pages"] == p.cached_pages
        assert pre["flops_saved"] > 0

    def test_chunked_prefill_interleaves_with_decode(self, rng_np):
        """A long prompt admitted behind a decoding sequence advances
        chunk-by-chunk while the resident sequence keeps decoding —
        TTFT for the long prompt no longer blocks the decode stream."""
        short = list(rng_np.integers(1, 64, size=4))
        long_p = list(rng_np.integers(1, 64, size=16))
        reg = MetricsRegistry("serve_chunk")
        sink = MemorySink()
        reg.add_sink(sink)
        long_prompts = dict(num_pages=64, max_prompt_len=16, max_new_tokens=6,
                            seed=0)
        eng = _engine(3, reg, **long_prompts, prefill_chunk_tokens=4)
        eng.submit(short, max_new_tokens=6, temperature=0.0)
        eng.step()  # short's first chunk == its whole prompt
        eng.submit(long_p, max_new_tokens=6, temperature=0.0)
        interleaved = 0
        for _ in range(30):
            if not eng.step():
                break
            live = {a.request.id: a for a in eng.scheduler.live}
            if (0 in live and live[0].generated
                    and 1 in live and not live[1].generated):
                interleaved += 1
        assert interleaved > 0, "decode never ran beside a mid-prefill row"
        res = {r.id: r.tokens for r in eng.results()}
        # chunk accounting: the long prompt took ceil(16/4) = 4 passes
        serves = [r for r in sink.records if r.get("kind") == "serve"]
        chunks = {r["request"]: r["prefill_chunks"] for r in serves}
        assert chunks[1] == 4 and chunks[0] == 1
        assert reg.counter("serve_prefill_chunks").value() >= 5.0
        # identity vs the whole-prompt engine
        eng2 = _engine(3, **long_prompts)
        eng2.submit(short, max_new_tokens=6, temperature=0.0)
        eng2.submit(long_p, max_new_tokens=6, temperature=0.0)
        eng2.run_until_idle()
        ref = {r.id: r.tokens for r in eng2.results()}
        assert res == ref

    def test_admission_under_pressure_evicts_cached_prefixes(self, rng_np):
        """A warm cache under page pressure: LRU cached prefixes are
        reclaimed instead of blocking admissions, OutOfPages never
        surfaces while reclaimable pages exist, and every request
        completes."""
        heads = [list(rng_np.integers(1, 64, size=8)) for _ in range(3)]
        prompts = [h + list(rng_np.integers(1, 64, size=2))
                   for h in heads for _ in range(2)]
        # pool of 11 usable pages; each request reserves
        # ceil((10 + 4)/4) = 4; three 2-page prefixes want caching, so
        # a full cache (6 pages) + two active rows (8, minus shared
        # heads) overflows the pool and forces LRU reclaim
        reg = MetricsRegistry("serve_evict")
        tight = dict(num_pages=12, max_prompt_len=16, seed=0)
        eng = _engine(3, reg, **tight, prefix_cache=True)
        results = eng.generate(prompts, max_new_tokens=4,
                               temperature=0.0)
        assert len(results) == 6
        assert all(len(r.tokens) == 4 for r in results)
        p = eng.cache.prefix
        assert p.evictions > 0, "pressure never reclaimed a cached page"
        rep = eng.cache.resident_report()
        assert rep["free_pages"] + rep["unique_pages"] == 11
        # identical tokens with the cache off
        eng2 = _engine(3, **tight)
        ref = eng2.generate(prompts, max_new_tokens=4, temperature=0.0)
        assert [r.tokens for r in results] == [r.tokens for r in ref]

    def test_serving_memory_report_counts_unique_resident_bytes(
            self, rng_np):
        from paddle_tpu.analysis.memory import serving_memory_report

        cfg, params, prompts = self._setup(rng_np, n_prompts=3)
        scfg = ServingConfig(max_slots=4, page_size=4, num_pages=64,
                             max_prompt_len=16, max_new_tokens=6,
                             prefill_batch=4, seed=0, prefix_cache=True)
        eng = ServingEngine(cfg, params, scfg)
        eng.generate(prompts, temperature=0.0)  # populate the cache
        rep = serving_memory_report(cfg, scfg, cache=eng.cache)
        page_bytes = rep["page_bytes"]
        assert page_bytes * scfg.num_pages == rep["kv_pool_bytes"]
        assert rep["unique_resident_bytes"] == (
            rep["unique_pages"] * page_bytes)
        assert rep["cached_pages"] > 0
        # all slots idle: unique resident == cached pages exactly
        assert rep["unique_pages"] == rep["cached_pages"]
        assert rep["free_pages"] + rep["unique_pages"] == 63


class TestKvPoolPreflightGate:
    """GL-P-MEM's serving path: the static KV page-pool accounting that
    fails engine construction instead of OOMing at first admission."""

    def test_serving_memory_report_exact_bytes(self):
        from paddle_tpu.analysis import serving_memory_report

        cfg = small_cfg()  # 2 layers, 2 heads, head_dim 16, f32
        scfg = ServingConfig(page_size=8, num_pages=32)
        rep = serving_memory_report(cfg, scfg)
        # k AND v pools: 2 · L·H·pages·page_size·head_dim·itemsize
        assert rep["kv_pool_bytes"] == 2 * 2 * 2 * 32 * 8 * 16 * 4
        assert rep["dtype"] == "float32"
        assert rep["total_bytes"] == rep["kv_pool_bytes"]
        params = T.init_params(cfg, jax.random.key(0))
        with_p = serving_memory_report(cfg, scfg, params)
        assert with_p["params_bytes"] > 0
        assert with_p["total_bytes"] == (rep["kv_pool_bytes"]
                                         + with_p["params_bytes"])

    def test_budget_pass_names_the_pool_and_clean_under_budget(self):
        from paddle_tpu.analysis import (serving_budget_pass,
                                         serving_memory_report)

        cfg = small_cfg()
        rep = serving_memory_report(cfg, ServingConfig(page_size=8,
                                                       num_pages=32))
        found = serving_budget_pass(rep, hbm_gb=1e-6)
        assert len(found) == 1
        f = found[0]
        assert f.rule == "GL-P-MEM" and f.anchor == "kv-pool-budget"
        assert "pages" in f.message and "first admission" in f.message
        # generous budget or report-only (0): clean
        assert serving_budget_pass(rep, hbm_gb=64.0) == []
        assert serving_budget_pass(rep, hbm_gb=0.0) == []

    def test_engine_construction_fails_preflight_not_oom(self):
        from paddle_tpu.core import flags
        from paddle_tpu.core.enforce import EnforceError

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        old = flags.get("hbm_gb")
        try:
            flags.set("hbm_gb", 1e-6)
            with pytest.raises(EnforceError, match="kv-pool|KV pool"):
                ServingEngine(cfg, params, ServingConfig(
                    max_slots=2, page_size=4, num_pages=32,
                    max_prompt_len=16, max_new_tokens=8))
            # under budget (or unset): constructs fine
            flags.set("hbm_gb", 0.0)
            ServingEngine(cfg, params, ServingConfig(
                max_slots=2, page_size=4, num_pages=32,
                max_prompt_len=16, max_new_tokens=8))
        finally:
            flags.set("hbm_gb", old)
