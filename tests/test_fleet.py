"""The serving fleet end to end (serving/fleet.py + router.py +
health.py), driven through ChaosSchedule and deterministic given seed +
arrival order: replica loss/hang mid-decode loses zero requests and the
survivors' results are token-identical to a fault-free run; overload
sheds with RetryAfter instead of queueing unboundedly; deadlines fail
fast; a rolling weight swap serves continuously and rolls back on a
corrupt servable; fleet telemetry renders through metrics_to_md."""

import threading
import time

import numpy as np
import pytest

import jax

from paddle_tpu.models import transformer as T
from paddle_tpu.resilience.chaos import ChaosSchedule
from paddle_tpu.serving import ServingConfig
from paddle_tpu.serving.engine import ServingEngine
from paddle_tpu.serving.export import export_servable
from paddle_tpu.serving.fleet import (
    FleetConfig,
    LocalReplica,
    build_local_fleet,
    fleet_launch_argv,
)
from paddle_tpu.serving.router import RetryAfter, SwapFailed
from paddle_tpu.telemetry import MemorySink, MetricsRegistry

pytestmark = pytest.mark.fleet


def small_cfg(**kw):
    base = dict(vocab_size=64, num_layers=2, num_heads=2, embed_dim=32,
                mlp_dim=64, max_seq_len=64, remat=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def small_scfg(**kw):
    base = dict(max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
                max_new_tokens=6, prefill_batch=2, seed=0)
    base.update(kw)
    return ServingConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = small_cfg()
    return cfg, T.init_params(cfg, jax.random.key(1))


def _mixed_requests(rng, n=8):
    """Ragged prompts, alternating greedy/temperature sampling — the
    identity assertions cover both."""
    return [(list(rng.integers(1, 64, size=3 + (i % 4))),
             3 + (i % 3), 0.0 if i % 2 == 0 else 0.8)
            for i in range(n)]


def _serve(model, chaos_spec=None, n_replicas=3, fleet=None,
           registry=None, requests=None):
    cfg, params = model
    reg = registry or MetricsRegistry("fleet_test")
    chaos = (ChaosSchedule(chaos_spec, registry=reg)
             if chaos_spec else None)
    router = build_local_fleet(cfg, params, small_scfg(), n=n_replicas,
                               registry=reg, chaos=chaos, fleet=fleet)
    rids = [router.submit(p, max_new_tokens=n, temperature=t)
            for p, n, t in requests]
    router.run_until_idle()
    res = {r.id: r for r in router.results()}
    return rids, res, router


class TestFailover:
    def test_replica_loss_mid_decode_zero_lost_token_identical(
            self, model, rng_np):
        """The acceptance property: killing a replica with requests in
        flight loses nothing, and every surviving result is token-for-
        token what the fault-free fleet produced — failover is
        invisible in the output stream."""
        reqs = _mixed_requests(rng_np)
        rids0, res0, r0 = _serve(model, None, requests=reqs)
        rids1, res1, r1 = _serve(model, "replica_loss@3:replica=1",
                                 requests=reqs)
        s1 = r1.stats()
        assert s1["requests_lost"] == 0
        assert s1["failovers"] == 1 and s1["requeued"] >= 1
        assert set(res1) == set(rids1)
        assert all(res1[r].finish_reason == "length" for r in rids1)
        assert {r: res0[r].tokens for r in rids0} \
            == {r: res1[r].tokens for r in rids1}
        assert r1.registry.counter("fleet_failovers").value() == 1.0
        assert r1.health.dead() == {1: "crash: chaos replica_loss"}

    def test_replica_hang_detected_and_failed_over(self, model, rng_np):
        """A wedged-but-alive replica (no crash to observe) is caught
        by no-progress detection and failed over, token-identically."""
        reqs = _mixed_requests(rng_np)
        _, res0, _ = _serve(model, None, requests=reqs)
        _, res1, r1 = _serve(model, "replica_hang@3:replica=0",
                             fleet=FleetConfig(hang_rounds=4),
                             requests=reqs)
        s1 = r1.stats()
        assert s1["requests_lost"] == 0 and s1["failovers"] == 1
        assert {i: r.tokens for i, r in res0.items()} \
            == {i: r.tokens for i, r in res1.items()}
        assert list(r1.health.dead()) == [0]
        assert r1.health.dead()[0].startswith("hang:")

    def test_chaos_run_is_deterministic(self, model, rng_np):
        """Same seed + arrival order + chaos spec -> the same trace,
        twice — the replay property every assertion above rests on."""
        reqs = _mixed_requests(rng_np)
        _, a, ra = _serve(model, "replica_loss@3:replica=1",
                          requests=reqs)
        _, b, rb = _serve(model, "replica_loss@3:replica=1",
                          requests=reqs)
        assert {i: r.tokens for i, r in a.items()} \
            == {i: r.tokens for i, r in b.items()}
        assert ra.stats() == rb.stats()

    def test_redial_budget_exhaustion_fails_request(self, model, rng_np):
        """With every replica dead and the RetryPolicy budget spent,
        requests FAIL (finish_reason="error") instead of looping — and
        still count as delivered, never lost."""
        reqs = _mixed_requests(rng_np, n=3)
        _, res, router = _serve(
            model, "replica_loss@1:replica=0",
            n_replicas=1, fleet=FleetConfig(redial_attempts=2),
            requests=reqs)
        s = router.stats()
        assert s["requests_lost"] == 0 and s["alive_replicas"] == 0
        assert len(res) == 3
        assert all(r.finish_reason == "error" for r in res.values())


class TestShedding:
    def test_queue_depth_sheds_with_retry_after(self, model):
        router = build_local_fleet(
            *model, small_scfg(), n=1,
            registry=MetricsRegistry("shed"),
            fleet=FleetConfig(shed_queue_depth=3, retry_after_s=0.75))
        accepted = []
        with pytest.raises(RetryAfter) as ei:
            for _ in range(10):
                accepted.append(router.submit([1, 2, 3],
                                              max_new_tokens=2))
        assert len(accepted) == 3  # bounded, not unbounded queueing
        assert ei.value.retry_after_s == 0.75
        assert "queue_depth" in ei.value.reason
        router.run_until_idle()
        # everything ACCEPTED still completes; sheds were never admitted
        assert {r.id for r in router.results()} == set(accepted)
        s = router.stats()
        assert s["shed"] == 1 and s["requests_lost"] == 0
        assert router.registry.counter("fleet_shed").value(
            reason="queue_depth") == 1.0

    def test_slo_ttft_breach_sheds(self, model):
        reg = MetricsRegistry("shed_slo")
        # a previously observed TTFT distribution far above the SLO
        reg.histogram("serve_ttft_ms", "ttft").observe(500.0)
        router = build_local_fleet(
            *model, small_scfg(), n=1, registry=reg,
            fleet=FleetConfig(slo_p99_ttft_ms=50.0))
        with pytest.raises(RetryAfter, match="slo_ttft"):
            router.submit([1, 2, 3], max_new_tokens=2)

    def test_free_page_watermark_sheds(self, model):
        router = build_local_fleet(
            *model, small_scfg(num_pages=8), n=1,
            registry=MetricsRegistry("shed_pages"),
            fleet=FleetConfig(shed_free_page_frac=0.6))
        # 4+6 tokens -> 3 of 7 usable pages reserved; 4/7 < 0.6 left
        router.submit([1, 2, 3, 4], max_new_tokens=6)
        router.pump()  # route + admit (allocates the pages)
        router.pump()  # probes now see the post-admission free list
        with pytest.raises(RetryAfter, match="pages"):
            router.submit([1, 2, 3, 4], max_new_tokens=6)

    def test_deadline_fails_fast_and_does_not_wedge_queue(self, model):
        clk = {"t": 0.0}
        router = build_local_fleet(
            *model, small_scfg(), n=1,
            registry=MetricsRegistry("ttl"), clock=lambda: clk["t"])
        ra = router.submit([1, 2, 3], max_new_tokens=2, ttl_s=5.0)
        clk["t"] = 10.0  # the head's deadline passes while queued
        rb = router.submit([1, 2, 3], max_new_tokens=2)
        router.run_until_idle()
        res = {r.id: r for r in router.results()}
        assert res[ra].finish_reason == "deadline"
        assert "deadline" in res[ra].metrics["error"]
        # the request BEHIND the expired head was served normally
        assert res[rb].finish_reason == "length"
        s = router.stats()
        assert s["deadline_expired"] == 1 and s["requests_lost"] == 0


class TestPrefixCacheFleet:
    def _requests(self, rng, n=6):
        head = list(rng.integers(1, 64, size=4))
        return [(head + list(rng.integers(1, 64, size=1 + (i % 3))),
                 3 + (i % 3), 0.0) for i in range(n)]

    def test_prefix_cache_identity_across_failover(self, model, rng_np):
        """The fleet acceptance property composed with the tentpole:
        --prefix_cache on/off and a mid-run replica loss all produce
        byte-identical greedy tokens."""
        reqs = self._requests(rng_np)
        runs = {}
        for name, scfg_kw, chaos in (
                ("off", {}, None),
                ("on", {"prefix_cache": True}, None),
                ("on_failover", {"prefix_cache": True},
                 "replica_loss@3:replica=1")):
            reg = MetricsRegistry(f"fleet_prefix_{name}")
            chaos_s = (ChaosSchedule(chaos, registry=reg)
                       if chaos else None)
            router = build_local_fleet(
                *model, small_scfg(**scfg_kw), n=3, registry=reg,
                chaos=chaos_s)
            rids = [router.submit(p, max_new_tokens=n, temperature=t)
                    for p, n, t in reqs]
            router.run_until_idle()
            res = {r.id: r for r in router.results()}
            assert set(res) == set(rids)
            runs[name] = [res[r].tokens for r in rids]
        assert runs["off"] == runs["on"] == runs["on_failover"]

    def test_router_affinity_prefers_warm_replica(self, model, rng_np):
        """Cache-aware routing: a repeat prompt lands on the replica
        whose prefix cache is warm (prefix_peek), instead of pure
        least-loaded round-robin spreading it cold."""
        prompt = list(rng_np.integers(1, 64, size=9))
        router = build_local_fleet(
            *model, small_scfg(prefix_cache=True, max_prompt_len=12),
            n=3, registry=MetricsRegistry("fleet_affinity"))
        router.submit(prompt, max_new_tokens=3, temperature=0.0)
        router.run_until_idle()
        router.results()
        warm = [i for i, rep in enumerate(router.replicas)
                if rep.engine.cache.prefix.cached_pages > 0]
        assert len(warm) == 1  # exactly one replica computed the prompt
        rep = router.replicas[warm[0]]
        assert rep.prefix_peek(prompt) == 8  # 2 full pages of 4
        before_hits = rep.engine.cache.prefix.hits
        for _ in range(3):  # repeats must all ride the warm cache
            router.submit(prompt, max_new_tokens=3, temperature=0.0)
            router.run_until_idle()
        router.results()
        assert rep.engine.cache.prefix.hits == before_hits + 3
        others = [r for i, r in enumerate(router.replicas)
                  if i != warm[0]]
        assert all(r.engine.cache.prefix.cached_pages == 0
                   for r in others)

    def test_probe_counts_reclaimable_pages_as_free(self, model, rng_np):
        """A warm (idle) cache must not read as memory pressure: the
        health probe's free_pages includes reclaimable cached pages, so
        shed_free_page_frac only fires on pages active sequences pin."""
        prompt = list(rng_np.integers(1, 64, size=9))
        router = build_local_fleet(
            *model, small_scfg(prefix_cache=True, max_prompt_len=12),
            n=1, registry=MetricsRegistry("fleet_probe"))
        router.submit(prompt, max_new_tokens=3, temperature=0.0)
        router.run_until_idle()
        router.results()
        rep = router.replicas[0]
        probe = rep.probe()
        assert rep.engine.cache.prefix.cached_pages == 2
        assert rep.engine.cache.allocator.free_pages == \
            probe.total_pages - 2
        assert probe.free_pages == probe.total_pages  # fully idle


class TestWeightSwap:
    def test_rolling_swap_serves_continuously(self, model, tmp_path):
        """Requests stream in while the swap rolls replica by replica:
        no submit fails, every request completes, and post-swap tokens
        come from the NEW weights."""
        cfg, params = model
        params2 = T.init_params(cfg, jax.random.key(2))
        sv = export_servable(str(tmp_path / "sv"), cfg, params2)
        scfg = small_scfg()
        # hang detection stays ON during the swap: a held (mid-swap)
        # replica's frozen progress must NOT read as a hang — the
        # health monitor skips held replicas (regression)
        router = build_local_fleet(cfg, params, scfg, n=2,
                                   registry=MetricsRegistry("swap"),
                                   fleet=FleetConfig(hang_rounds=4))
        router.start()
        try:
            rids = []

            def feeder():
                for i in range(16):
                    rids.append(router.submit(
                        [5, 6, (i % 50) + 1], max_new_tokens=3))
                    time.sleep(0.005)

            t = threading.Thread(target=feeder)
            t.start()
            report = router.swap_servable(sv)
            t.join()
            got = router.results(n=16, timeout=60.0)
        finally:
            router.stop()
        assert report == {0: "swapped", 1: "swapped"}
        assert len(got) == 16
        assert all(r.finish_reason == "length" for r in got)
        s = router.stats()
        assert s["requests_lost"] == 0 and s["swaps"] == 1
        assert router.health.dead() == {}  # no false hang verdicts
        assert s["alive_replicas"] == 2
        # a post-swap request serves the new weights
        ref = ServingEngine(cfg, params2, scfg).generate(
            [[5, 6, 7]], max_new_tokens=3)[0].tokens
        router2 = build_local_fleet(cfg, params, scfg, n=2,
                                    registry=MetricsRegistry("swap2"))
        router2.swap_servable(sv)
        rid = router2.submit([5, 6, 7], max_new_tokens=3)
        router2.run_until_idle()
        assert {r.id: r.tokens for r in router2.results()}[rid] == ref

    def test_corrupt_servable_rolls_back(self, model, tmp_path):
        """servable_corrupt chaos poisons the artifact before the 2nd
        per-replica load: sha256 verification refuses it, the already-
        swapped replica 0 rolls back, and the whole fleet keeps serving
        the OLD weights — never a mix."""
        cfg, params = model
        params2 = T.init_params(cfg, jax.random.key(2))
        sv = export_servable(str(tmp_path / "sv"), cfg, params2)
        scfg = small_scfg()
        reg = MetricsRegistry("swap_corrupt")
        sink = MemorySink()
        reg.add_sink(sink)
        router = build_local_fleet(
            cfg, params, scfg, n=2, registry=reg,
            chaos=ChaosSchedule("servable_corrupt@1", registry=reg))
        with pytest.raises(SwapFailed, match="hash mismatch"):
            router.swap_servable(sv)
        s = router.stats()
        assert s["swap_rollbacks"] == 1 and s["swaps"] == 0
        # BOTH replicas serve the old weights (replica 0 was reverted):
        # two concurrent submits load-balance one onto each
        old = ServingEngine(cfg, params, scfg).generate(
            [[5, 6, 7]], max_new_tokens=3)[0].tokens
        rids = [router.submit([5, 6, 7], max_new_tokens=3)
                for _ in range(2)]
        router.run_until_idle()
        got = {r.id: r.tokens for r in router.results()}
        assert [got[r] for r in rids] == [old, old]
        events = [r for r in sink.records if r.get("kind") == "fleet"]
        rb = [r for r in events if r.get("event") == "swap_rollback"]
        assert len(rb) == 1 and rb[0]["rolled_back"] == [0]

    def test_smoke_mismatch_rolls_back(self, model, tmp_path,
                                       monkeypatch):
        """A servable that loads clean but fails its smoke decode (the
        engine does not reproduce the model's own greedy continuation)
        is rolled back everywhere."""
        cfg, params = model
        params2 = T.init_params(cfg, jax.random.key(2))
        sv = export_servable(str(tmp_path / "sv"), cfg, params2)
        scfg = small_scfg()
        router = build_local_fleet(cfg, params, scfg, n=2,
                                   registry=MetricsRegistry("swap_smoke"))
        real = LocalReplica.smoke_decode

        def lying_smoke(self, prompt, n):
            toks = real(self, prompt, n)
            return [(t + 1) % 64 for t in toks] if self.index == 1 \
                else toks

        monkeypatch.setattr(LocalReplica, "smoke_decode", lying_smoke)
        with pytest.raises(SwapFailed, match="smoke decode"):
            router.swap_servable(sv)
        monkeypatch.undo()
        old = ServingEngine(cfg, params, scfg).generate(
            [[5, 6, 7]], max_new_tokens=3)[0].tokens
        rids = [router.submit([5, 6, 7], max_new_tokens=3)
                for _ in range(2)]
        router.run_until_idle()
        got = {r.id: r.tokens for r in router.results()}
        assert [got[r] for r in rids] == [old, old]


class TestOnePassAhead:
    """Every replica's engine keeps one pass in flight; the router's pump
    is ``ServingEngine.step``."""

    def test_pumped_replicas_serve_the_single_engines_tokens(self, model,
                                                             rng_np):
        cfg, params = model
        reqs = _mixed_requests(rng_np, n=10)
        reg = MetricsRegistry("fleet_ahead")
        rids, res, router = _serve(model, n_replicas=3, registry=reg,
                                   requests=reqs)
        assert all(not rep.engine._in_flight for rep in router.replicas)
        assert reg.get("serve_passes_ahead_total").value(kind="decode") > 0
        assert reg.get("serve_tokens_dropped_total").value() == 0
        # sampling keys are (seed, request id, token index): one engine
        # given the fleet's ids serves the fleet's tokens
        eng = ServingEngine(cfg, params, small_scfg())
        for rid, (p, n, t) in zip(rids, reqs):
            eng.submit(p, n, t, request_id=rid)
        eng.run_until_idle()
        assert ({r.id: r.tokens for r in eng.results()}
                == {rid: res[rid].tokens for rid in rids})

    def test_a_drained_replica_has_nothing_in_flight_for_a_swap(self, model,
                                                                tmp_path):
        """An eos leaves a surplus pass queued behind it.  The iteration
        that delivers the request's result also reads that pass (nothing
        is left to dispatch behind it), so a replica the router sees
        drained has nothing in flight under the old weights — and what
        ``set_params`` would read if it had (``tests/test_serving.py``) is
        nothing here.  The new weights serve."""
        cfg, params = model
        params2 = T.init_params(cfg, jax.random.key(2))
        sv = export_servable(str(tmp_path / "sv"), cfg, params2)
        prompt, hot = [5, 6, 7], 1.5
        free = ServingEngine(cfg, params, small_scfg())
        free.submit(prompt, 6, hot, request_id=0)
        free.run_until_idle()
        tokens = free.results()[0].tokens
        at = next(i for i in (2, 3, 4) if tokens[i] not in tokens[:i])
        reg = MetricsRegistry("swap_ahead")
        router = build_local_fleet(cfg, params,
                                   small_scfg(eos_id=tokens[at]), n=2,
                                   registry=reg)
        rid = router.submit(prompt, max_new_tokens=6, temperature=hot)
        assert rid == 0
        while router.stats()["delivered"] < 1:
            router.pump()
        assert router.results()[0].tokens == tokens[:at + 1]
        assert reg.get("serve_tokens_dropped_total").value() == 1
        assert all(not rep.engine._in_flight for rep in router.replicas)
        assert router.swap_servable(sv) == {0: "swapped", 1: "swapped"}
        assert all(not rep.engine._in_flight for rep in router.replicas)
        assert reg.get("serve_loop_drains_total").value(why="swap") == 0
        ref = ServingEngine(cfg, params2, small_scfg()).generate(
            [[5, 6, 7]], max_new_tokens=3)[0].tokens
        rid = router.submit([5, 6, 7], max_new_tokens=3)
        router.run_until_idle()
        assert {r.id: r.tokens for r in router.results()}[rid] == ref


class TestRouterLifecycle:
    def test_loop_crash_fails_pending_and_refuses_submit(self, model):
        router = build_local_fleet(*model, small_scfg(), n=1,
                                   registry=MetricsRegistry("crash"))
        boom = RuntimeError("injected router fault")

        def bad_pump():
            raise boom

        router.pump = bad_pump
        router.start()
        try:
            with pytest.raises(RuntimeError,
                               match="router loop crashed") as ei:
                router.results(n=1, timeout=30.0)
            assert ei.value.__cause__ is boom
            with pytest.raises(RuntimeError, match="submit refused"):
                router.submit([1, 2, 3], max_new_tokens=2)
        finally:
            router.stop()

    def test_submit_after_stop_raises(self, model):
        """A stopped background router refuses submits (nothing will
        ever pump them) — the engine's dead-engine contract."""
        router = build_local_fleet(*model, small_scfg(), n=1,
                                   registry=MetricsRegistry("stopped"))
        router.start()
        router.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            router.submit([1, 2, 3], max_new_tokens=2)
        # sync drive still works after a restart
        router.start()
        try:
            rid = router.submit([1, 2, 3], max_new_tokens=2)
            assert router.results(n=1, timeout=60.0)[0].id == rid
        finally:
            router.stop()

    def test_fleet_records_render_in_metrics_to_md(self, model,
                                                   tmp_path, capsys):
        import json
        import sys

        reqs = [([1, 2, 3], 2, 0.0) for _ in range(4)]
        reg = MetricsRegistry("md")
        sink = MemorySink()
        reg.add_sink(sink)
        _, _, router = _serve(model, "replica_loss@2:replica=0",
                              n_replicas=2, registry=reg, requests=reqs)
        router.emit_summary()
        events = [r for r in sink.records if r.get("kind") == "fleet"]
        assert {r["event"] for r in events} == {"replica_down",
                                                "summary"}
        path = tmp_path / "m.jsonl"
        path.write_text("\n".join(json.dumps(r) for r in sink.records)
                        + "\n")
        sys.path.insert(0, "tools")
        try:
            import metrics_to_md
        finally:
            sys.path.pop(0)
        metrics_to_md.main([str(path)])
        out = capsys.readouterr().out
        assert "## Serving fleet" in out
        assert "replica_down" in out and "re-queued" in out
        assert "requests lost: 0" in out

    def test_launch_argv_shape(self):
        argv = fleet_launch_argv(3, "/tmp/sv", "--max_new_tokens", 8)
        assert "--serving" in argv and "--nproc" in argv
        assert argv[argv.index("--nproc") + 1] == "3"
        assert argv[argv.index("--servable") + 1] == "/tmp/sv"


class TestCliFleetMode:
    def test_main_with_replicas_matches_single_engine(self, monkeypatch,
                                                      capsys):
        """`python -m paddle_tpu.serving --replicas 2` serves the same
        greedy tokens the single-engine CLI serves (placement never
        changes output)."""
        import io

        from paddle_tpu.serving.__main__ import main

        lines = "5 17 3\n9 9 9 9\n"
        outs = []
        for replicas in ("1", "2"):
            monkeypatch.setattr("sys.stdin", io.StringIO(lines))
            rc = main(["--random", "--vocab", "64", "--embed", "32",
                       "--max_new_tokens", "4", "--seed", "7",
                       "--replicas", replicas])
            assert rc == 0
            outs.append(capsys.readouterr().out)
        assert outs[0] == outs[1]
        got = [l for l in outs[1].splitlines() if l.strip()]
        assert len(got) == 2
        assert got[0].startswith("0:") and got[1].startswith("1:")
