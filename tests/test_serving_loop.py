"""The serving loop: the prefill ladder (the shapes a pass may take,
every program compiled by the step that admits the first request), one
pass ahead (pass n + 1 dispatched before pass n is read), and the text
the serving programs lower to.  Admission: ``test_serving_engine.py``."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy


# -- the prefill ladder ---------------------------------------------------------
#
# One serving shape for every ladder test: one row or four, 96 long, on
# models small enough that the CPU compiles both in a second.

LADDER_SERVING = dict(max_slots=4, page_size=16, num_pages=64,
                      max_prompt_len=96, max_new_tokens=4, prefill_batch=4,
                      seed=0)
LADDER = ((1, 96), (4, 96))     # rows (1, prefill_batch) x max_prompt_len
# one admitted batch each: 1 ... prefill_batch rows, prompts at both ends of
# the length, on a page's edge and either side of it
LADDER_BATCHES = [(1,), (15,), (16,), (17,), (95,), (96,), (50, 7),
                  (96, 96), (1, 1), (90, 20, 33), (16, 32, 48),
                  (5, 96, 64, 17), (33, 44, 55, 66), (96,) * 4]


def _ladder_cfg(kind):
    if kind == "plain":
        return lm_toy.small_cfg(max_seq_len=128)
    if kind == "looped":
        return lm_toy.small_cfg(max_seq_len=128, norm="rms", mlp="swiglu",
                                positions="rotary", loop_steps=3)
    return T.TransformerConfig(   # layers of three kinds, two with state
        vocab_size=64, num_layers=4, num_heads=4, kv_heads=2, head_dim=8,
        embed_dim=32, mlp_dim=24, max_seq_len=128, norm="rms",
        positions="none", mlp="relu2", tie_embeddings=False, pattern="ME*M",
        moe_experts=8, moe_router="sigmoid", moe_top_k=2, moe_shared_dim=16,
        moe_held=(0, 4), mamba_heads=4, mamba_head_dim=8, mamba_state=16,
        mamba_groups=2, mamba_conv=4, mamba_chunk=32, remat=False)


@functools.lru_cache(maxsize=None)
def _ladder_engines(kind):
    """(an engine that shapes its passes, its twin that runs every pass at
    the largest member); both see the same batches in the same order."""
    cfg = _ladder_cfg(kind)
    params = T.init_params(cfg, jax.random.key(3))
    shaped = ServingEngine(cfg, params, ServingConfig(**LADDER_SERVING))
    padded = ServingEngine(cfg, params, ServingConfig(**LADDER_SERVING))
    assert shaped.scheduler.prefill_shapes == LADDER
    padded.scheduler.prefill_shapes = LADDER[1:]
    return shaped, padded


class _Compiles:
    """The two ``jax.monitoring`` events the benchmark's ``CompileWatch``
    counts: a compile, or a fetch from the persistent cache."""

    count = 0
    listening = False

    @classmethod
    def listen(cls):
        if not cls.listening:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls.listening = True
        return cls

    @classmethod
    def _on(cls, event, duration, **kw):
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")):
            cls.count += 1


class TestPrefillLadder:
    @pytest.mark.parametrize("batch,longest,shape", [
        # the benchmark's six serve configurations
        pytest.param(4, 768, ((1, 768), (4, 768)), id="gpt2-large"),
        pytest.param(2, 192, ((1, 192), (2, 192)), id="ouro-2.6b"),
        pytest.param(4, 512, ((1, 512), (4, 512)), id="nemotron-3-nano"),
        pytest.param(4, 512, ((1, 512), (4, 512)), id="sdar-30b"),
        pytest.param(4, 512, ((1, 512), (4, 512)), id="zaya1-8b"),
        pytest.param(2, 4096, ((1, 2048), (1, 4096)), id="solar-open2"),
        (4, 96, LADDER),
        (8, 16, ((1, 16), (8, 16))),
        (3, 100, ((1, 100), (3, 100))),
        (1, 1024, ((1, 1024),)),     # one row is all such an engine admits
        (1, 2048, ((1, 1024), (1, 2048))),
        (4, 2048, ((1, 1024), (1, 2048))),   # the least that is long
        (4, 2046, ((1, 2046), (4, 2046))),   # half a pass under 1,024
        (2, 2304, ((1, 2304), (2, 2304))),   # half no multiple of 256
        (2, 8192, ((1, 4096), (1, 8192))),
    ])
    def test_ladder_from_two_numbers(self, batch, longest, shape):
        """Two programs whatever the model (a program costs set-up time):
        one row and ``prefill_batch`` rows at ``max_prompt_len`` where a
        pass is short; one row at half the length and one at all of it
        where half a pass is 1,024 positions or more and a whole number
        of 256.  The largest member holds whatever ``admit`` may hand
        over, and ``prefill_rows`` is what it was."""
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import (
            Scheduler,
            prefill_rows,
            prefill_shapes,
        )

        assert prefill_rows(batch) == tuple(sorted({1, batch}))
        assert prefill_shapes(batch, longest) == shape
        s = ServingConfig(**{**LADDER_SERVING, "prefill_batch": batch,
                             "max_prompt_len": longest, "max_slots": 8,
                             "num_pages": 8 * (-(-longest // 16) + 1) + 1})
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        assert sched.prefill_shapes == shape
        assert sched.prefill_rows == tuple(rows for rows, _ in shape)
        got = tuple(sched.prefill_arrays([], *member)["ids"].shape
                    for member in sched.prefill_shapes)
        assert got == shape
        assert got[-1][1] == longest
        # without a length a member is ``max_prompt_len`` long
        assert sched.prefill_arrays([], 1)["ids"].shape == (1, longest)

    @pytest.mark.parametrize("n,member", [
        (1, (1, 2048)), (512, (1, 2048)), (2047, (1, 2048)),
        (2048, (1, 2048)), (2049, (1, 4096)), (4096, (1, 4096)),
    ])
    def test_a_prompt_takes_the_shortest_member_that_holds_it(self, n,
                                                               member):
        """A prompt of 2,048 rides the half-length member, one of 2,049
        the full one; positions past the prompt are masked by ``seq_lens``
        at either length, slack as at every shape."""
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        s = ServingConfig(max_slots=2, page_size=16, num_pages=2 * 260 + 1,
                          max_prompt_len=4096, max_new_tokens=8,
                          prefill_batch=2)
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        sched.enqueue(Request(id=0, prompt=[3] * n, max_new_tokens=2))
        (a,) = sched.admit()
        batch = sched.prefill_batch([a])
        assert batch["ids"].shape == member
        assert batch["seq_lens"].tolist() == [n]
        assert batch["ids"][0, :n].tolist() == [3] * n
        assert not batch["ids"][0, n:].any()
        assert batch["slots"].tolist() == [a.slot]
        assert batch["page_table"].shape == (1, s.max_pages_per_seq)

    @pytest.mark.parametrize("batch,longest,queued,handed", [
        (4, 96, 6, [4, 2]),         # today's ladder: prefill_batch a step
        (2, 192, 3, [2, 1]),
        (1, 96, 2, [1, 1]),
        (2, 4096, 3, [1, 1, 1]),    # one-row members: one an iteration
        (4, 2048, 2, [1, 1]),
    ])
    def test_admit_hands_over_what_one_member_holds(self, batch, longest,
                                                    queued, handed):
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        s = ServingConfig(max_slots=8, page_size=16,
                          num_pages=8 * (-(-longest // 16) + 1) + 1,
                          max_prompt_len=longest, max_new_tokens=4,
                          prefill_batch=batch)
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        for i in range(queued):
            sched.enqueue(Request(id=i, prompt=[1 + i] * 5, max_new_tokens=2))
        got, order = [], []
        while sched.queue:
            admitted = sched.admit()
            got.append(len(admitted))
            order += [a.request.id for a in admitted]
            # every hand-over fits a member of the ladder
            assert sched.prefill_batch(admitted)["ids"].shape[0] >= len(
                admitted)
        assert got == handed
        assert order == list(range(queued))     # FIFO

    @pytest.mark.parametrize("kind", ["plain", "looped", "pattern"])
    def test_every_engine_has_the_same_ladder(self, kind):
        """The ladder comes from ``prefill_batch`` alone, whatever the
        model: a scanned stack, a looped one and a layer pattern with
        state pools all get the one-row program beside the full one."""
        cfg = _ladder_cfg(kind)
        reg = MetricsRegistry(f"ladder_{kind}")
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(6)),
                            ServingConfig(**LADDER_SERVING), registry=reg)
        assert eng.scheduler.prefill_rows == (1, 4)
        assert reg.get("serve_prefill_programs").value() == 2

    @pytest.mark.parametrize("lens,shape", [
        ((1,), (1, 96)), ((96,), (1, 96)), ((5, 5), (4, 96)),
        ((5, 96, 5), (4, 96)), ((96,) * 4, (4, 96)),
    ])
    def test_smallest_covering_member_is_picked(self, lens, shape):
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        s = ServingConfig(**LADDER_SERVING)
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        assert sched.prefill_rows == (1, 4)
        for i, n in enumerate(lens):
            sched.enqueue(Request(id=i, prompt=[1 + i] * n, max_new_tokens=2))
        admitted = sched.admit()
        batch = sched.prefill_batch(admitted)
        assert batch["ids"].shape == shape
        rows = len(lens)
        assert batch["seq_lens"].tolist() == list(lens) + [0] * (
            shape[0] - rows)
        # slack rows keep their contract at every shape
        assert (batch["slots"][rows:] == s.max_slots).all()
        assert not batch["page_table"][rows:].any()
        assert batch["page_table"].shape == (shape[0], s.max_pages_per_seq)
        for j, a in enumerate(admitted):
            assert batch["ids"][j, :a.prompt_len].tolist() == a.request.prompt
            assert not batch["ids"][j, a.prompt_len:].any()
            assert batch["slots"][j] == a.slot

    @pytest.mark.parametrize("lens", LADDER_BATCHES, ids=str)
    @pytest.mark.parametrize("kind", ["plain", "looped", "pattern"])
    def test_shaped_passes_serve_the_same_tokens(self, kind, lens, rng_np):
        """Leaving the padding out changes no answer: greedy tokens are
        those of the full-size pass; pages and recurrent state too, to a
        few float32 roundings (a matmul of another shape may sum in another
        order: 1e-5, set from the dtype before the first run)."""
        shaped, padded = _ladder_engines(kind)
        prompts = [list(rng_np.integers(1, 64, size=n)) for n in lens]
        seen = []
        real = shaped.scheduler.prefill_batch
        shaped.scheduler.prefill_batch = lambda admitted: seen.append(
            real(admitted)) or seen[-1]
        try:
            a = shaped.generate(prompts, max_new_tokens=3)
        finally:
            del shaped.scheduler.prefill_batch
        b = padded.generate(prompts, max_new_tokens=3)
        assert [x["ids"].shape for x in seen] == [
            LADDER[0] if len(lens) == 1 else LADDER[1]]
        assert [r.tokens for r in a] == [r.tokens for r in b]
        # the null page takes the slack rows' writes: no reader sees it
        for x, y in ((shaped.cache.k, padded.cache.k),
                     (shaped.cache.v, padded.cache.v)):
            np.testing.assert_allclose(np.asarray(x)[:, :, 1:],
                                       np.asarray(y)[:, :, 1:],
                                       rtol=1e-5, atol=1e-5)
        assert shaped.cache.state.keys() == padded.cache.state.keys()
        for name in shaped.cache.state:
            np.testing.assert_allclose(
                np.asarray(shaped.cache.state[name]),
                np.asarray(padded.cache.state[name]), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("kind", ["plain", "pattern"])
    def test_nothing_compiles_after_the_first_admission(self, kind, rng_np):
        """Every program is compiled by the step that admits the first
        request, whatever that request is (here one short row): no
        admissible batch compiles (or fetches from the persistent cache)
        afterwards.  An idle step compiles nothing: a fleet's router pumps
        idle replicas."""
        watch = _Compiles.listen()
        # a vocabulary no other test serves: its programs are not compiled yet
        cfg = dataclasses.replace(_ladder_cfg(kind), vocab_size=71)
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(4)),
                            ServingConfig(**LADDER_SERVING))
        before = watch.count
        assert eng.step() is False and watch.count == before
        eng.generate([[5, 17, 3]], max_new_tokens=2)
        ready = watch.count
        # the ladder and decode
        assert ready - before >= len(eng.scheduler.prefill_rows) + 1
        for lens in LADDER_BATCHES:
            eng.generate([list(rng_np.integers(1, 64, size=n))
                          for n in lens], max_new_tokens=3)
        assert watch.count == ready

    def test_length_members_serve_the_same_tokens(self, monkeypatch, rng_np):
        """Where half a pass is long enough (the constant lowered to a
        toy's size) the second member is HALF AS LONG, not wider: greedy
        tokens are those of an engine whose every pass is the full
        length, nothing compiles once the first request is admitted, and
        the passes are counted by the length they ran at."""
        from paddle_tpu.serving import scheduler

        monkeypatch.setattr(scheduler, "LENGTH_LADDER_MIN_HALF", 256)
        # attention, two state layers and an expert sublayer; a vocabulary
        # no other test serves: its programs are not compiled yet
        cfg = dataclasses.replace(_ladder_cfg("pattern"), vocab_size=73,
                                  max_seq_len=528)
        params = T.init_params(cfg, jax.random.key(8))
        serving = ServingConfig(max_slots=4, page_size=16, num_pages=4 * 33
                                + 1, max_prompt_len=512, max_new_tokens=4,
                                prefill_batch=2, seed=0)
        reg = MetricsRegistry("length_ladder")
        shaped = ServingEngine(cfg, params, serving, registry=reg)
        full = ServingEngine(cfg, params, serving)
        assert shaped.scheduler.prefill_shapes == ((1, 256), (1, 512))
        assert reg.get("serve_prefill_programs").value() == 2
        full.scheduler.prefill_shapes = ((1, 512),)
        seen = []
        real = shaped.scheduler.prefill_batch

        def recorded(admitted):
            batch = real(admitted)
            seen.append(batch["ids"].shape)
            return batch

        shaped.scheduler.prefill_batch = recorded
        watch = _Compiles.listen()
        shaped.generate([[5, 17, 3]], max_new_tokens=2)
        assert set(shaped._programs) == {(1, 256), (1, 512), "decode"}
        ready = watch.count
        lens = (1, 255, 256, 257, 300, 512, 40, 511)
        prompts = [list(rng_np.integers(1, 73, size=n)) for n in lens]
        a = shaped.generate(prompts, max_new_tokens=3)
        assert watch.count == ready
        b = full.generate(prompts, max_new_tokens=3)
        assert [r.tokens for r in a] == [r.tokens for r in b]
        # one request a pass, in arrival order, each at the shortest
        # member that holds it
        assert seen == [(1, 256)] + [(1, 256 if n <= 256 else 512)
                                     for n in lens]
        passes = reg.get("serve_prefill_passes_total")
        assert passes.value(length=256) == 1 + 4
        assert passes.value(length=512) == 4
        assert reg.get("serve_prefill_padded_tokens_total").value() == (
            5 * 256 + 4 * 512)

    def test_making_ready_leaves_the_cache_as_it_was(self, rng_np):
        """Getting every member of the ladder and the decode program
        ready compiles and runs nothing: the pages, the recurrent state
        and the page table are bit for bit what they were."""
        cfg = _ladder_cfg("pattern")
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(5)),
                            ServingConfig(**LADDER_SERVING))
        assert eng.scheduler.prefill_rows == (1, 4)  # beside state pools
        cache = eng.cache
        fill = lambda x: jnp.asarray(
            rng_np.normal(size=x.shape).astype(np.float32))
        cache.k, cache.v = fill(cache.k), fill(cache.v)
        cache.state = {n: fill(x) for n, x in cache.state.items()}
        cache.assign(1, 40)     # a resident sequence's table row
        k, v, state, table = (np.asarray(cache.k), np.asarray(cache.v),
                              {n: np.asarray(x) for n, x in
                               cache.state.items()}, cache.page_table.copy())
        assert state and table.any()
        eng._make_ready()
        assert np.array_equal(np.asarray(cache.k), k)
        assert np.array_equal(np.asarray(cache.v), v)
        for name, was in state.items():
            assert np.array_equal(np.asarray(cache.state[name]), was)
        assert np.array_equal(cache.page_table, table)
        assert eng.scheduler.active == [] and not eng.scheduler.queue


# -- one pass ahead ---------------------------------------------------------------
# The loop dispatches pass n + 1 before it reads pass n: a step's input
# token stays on the device, the scheduler counts tokens in flight.  Same
# tokens, request for request, as a plain forward of the same model -- or,
# for a model that generates by blocks (its input is the block in progress,
# tokens and masked flags; tests/test_block_lm.py holds it to its plain
# reference), as the same engine with every pass read before the next.

_AHEAD_CFGS = {
    "dense": dict(),
    "looped": dict(loop_steps=2, norm="rms", positions="rotary",
                   mlp="swiglu"),
    # a toy M / E / * pattern: recurrent state by slot beside the pages,
    # routing counts riding out behind the tokens
    "pattern": dict(
        vocab_size=97, num_layers=3, num_heads=4, kv_heads=2, head_dim=8,
        embed_dim=32, mlp_dim=24, norm="rms", positions="none", mlp="relu2",
        tie_embeddings=False, pattern="ME*", moe_experts=8,
        moe_router="sigmoid", moe_top_k=2, moe_scale=2.5, moe_shared_dim=40,
        moe_held=[0, 8], mamba_heads=4, mamba_head_dim=8, mamba_state=16,
        mamba_groups=2, mamba_conv=4, mamba_chunk=8),
    # generation by diffusion over blocks of 4: the decode step is a
    # block pass, a prefill pass samples nothing
    "block": dict(block_len=4, mask_id=63, norm="rms", positions="rotary",
                  qk_norm=True),
}
_PAD = 32   # every plain forward at one shape (causal: the tail is unseen)


@functools.lru_cache(maxsize=None)
def _ahead_model(kind):
    cfg = lm_toy.small_cfg(**_AHEAD_CFGS[kind])
    return cfg, T.init_params(cfg, jax.random.key(7))


def _plain_generation(cfg, params, prompt, n, rid, temperature, seed):
    """``n`` tokens after ``prompt``, one full forward a token: token i of
    request ``rid`` under ``fold_in(fold_in(key(seed), rid), i)``."""
    from paddle_tpu.serving.sampling import request_keys, sample_tokens

    fwd = lm_toy.jitted(T.forward, cfg)
    seq, out = list(prompt), []
    for i in range(n):
        ids = jnp.asarray([seq + [0] * (_PAD - len(seq))])
        logits = fwd(params, ids)[0, len(seq) - 1][None]
        keys = request_keys(jax.random.key(seed),
                            jnp.asarray([rid], jnp.int32),
                            jnp.asarray([i], jnp.int32))
        tok = int(sample_tokens(logits, keys,
                                jnp.asarray([temperature], jnp.float32))[0])
        out.append(tok)
        seq.append(tok)
    return out


def _ahead_engine(kind, reg=None, **kw):
    cfg, params = _ahead_model(kind)
    serving = dict(max_slots=3, page_size=4, num_pages=48, max_prompt_len=12,
                   max_new_tokens=8, prefill_batch=2, seed=5)
    serving.update(kw)
    return ServingEngine(cfg, params, ServingConfig(**serving),
                         registry=reg or MetricsRegistry("ahead"))


def _in_flight(eng):
    return len(eng._in_flight)


def _run_drained(eng):
    """The synchronous order: whatever an iteration left in flight is
    read before the next one builds anything."""
    while eng.step():
        eng._drain("sync")


class TestOnePassAhead:
    @pytest.mark.parametrize("temperature", [0.0, 0.9],
                             ids=["greedy", "seeded"])
    @pytest.mark.parametrize("kind", list(_AHEAD_CFGS))
    def test_generations_equal_a_plain_forward(self, kind, temperature,
                                               rng_np):
        """Seven requests through three slots, two rows a prefill pass,
        arriving while others decode and finishing at different steps: each
        gets the tokens a token-by-token forward of the model gives it."""
        cfg, params = _ahead_model(kind)
        eng = _ahead_engine(kind)
        lens, news = (3, 9, 12, 1, 6, 5, 10), (8, 3, 5, 1, 7, 2, 6)
        prompts = [[int(t) for t in rng_np.integers(1, cfg.vocab_size, n)]
                   for n in lens]
        ids = [eng.submit(p, n, temperature)
               for p, n in zip(prompts[:2], news[:2])]
        for _ in range(3):      # the first two are decoding
            assert eng.step()
        ids += [eng.submit(p, n, temperature)
                for p, n in zip(prompts[2:5], news[2:5])]
        for _ in range(2):
            assert eng.step()
        ids += [eng.submit(p, n, temperature)
                for p, n in zip(prompts[5:], news[5:])]
        eng.run_until_idle()
        assert _in_flight(eng) == 0
        got = {r.id: r for r in eng.results()}
        want = lambda rid, prompt, n: _plain_generation(
            cfg, params, prompt, n, rid, temperature, seed=5)
        if kind == "block":
            sync = _ahead_engine(kind)
            assert ids == [sync.submit(p, n, temperature)
                           for p, n in zip(prompts, news)]
            _run_drained(sync)
            drained = {r.id: r for r in sync.results()}
            want = lambda rid, prompt, n: drained[rid].tokens
            for rid in ids:
                assert got[rid].trail["tokens"] == drained[rid].trail["tokens"]
                assert got[rid].trail["steps"] == drained[rid].trail["steps"]
        for rid, prompt, n in zip(ids, prompts, news):
            assert got[rid].finish_reason == "length"
            assert got[rid].tokens == want(rid, prompt, n), (kind, rid)
            assert len(got[rid].tokens) == n

    def test_an_eos_is_seen_one_pass_late(self, rng_np):
        """The pass after the one that sampled an eos is already queued
        when the eos is read: that row's surplus token is dropped and
        counted, never handed out, and nobody else can tell — tokens, page
        tables and the K/V every other sequence wrote are what a run gives
        in which the request ends at that token by LENGTH (known without a
        read: no surplus pass)."""
        cfg, params = _ahead_model("dense")
        prompts = [[int(t) for t in rng_np.integers(1, 64, n)]
                   for n in (9, 5, 11, 7)]
        hot = 1.5       # sampled, not greedy: a drawn toy repeats itself
        eng = _ahead_engine("dense")
        for p in prompts:
            eng.submit(p, 6, hot)
        eng.run_until_idle()
        free = sorted(eng.results(), key=lambda r: r.id)
        # request 1 ends at its 4th or 5th token; page_size 8: (5 + 6),
        # (5 + 4) and (5 + 5) tokens reserve the same two pages
        others = {t for r in free if r.id != 1 for t in r.tokens}
        at = next(i for i in (3, 4) if free[1].tokens[i] not in others
                  and free[1].tokens[i] not in free[1].tokens[:i])
        eos = free[1].tokens[at]

        def run(**kw):
            reg = MetricsRegistry("eos_late")
            eng = _ahead_engine("dense", reg, page_size=8, **kw)
            handed, rows = [], {}
            inner, admit = eng.scheduler.append_token, eng.scheduler.admit

            def watch(a, token):
                handed.append((a.request.id, len(a.generated), token))
                inner(a, token)

            def admitted(now=0.0):
                out = admit(now=now)
                for a in out:
                    rows[a.request.id] = eng.cache.page_table[a.slot].copy()
                return out

            eng.scheduler.append_token = watch
            eng.scheduler.admit = admitted
            news = [6, at + 1 if "eos_id" not in kw else 6, 6, 6]
            for p, n in zip(prompts, news):
                eng.submit(p, n, hot)
            eng.run_until_idle()
            res = {r.id: r for r in eng.results()}
            return eng, reg, res, handed, rows

        late, reg, got, handed, rows = run(eos_id=eos)
        base, reg0, want, handed0, rows0 = run()
        assert got[1].finish_reason == "eos"
        assert want[1].finish_reason == "length"
        assert got[1].tokens == free[1].tokens[:at + 1] == want[1].tokens
        # every token once, in order, with ``generated`` what it was
        # before it; the surplus one never
        assert handed == handed0
        for rid in (0, 2, 3):
            assert got[rid].tokens == free[rid].tokens == want[rid].tokens
        assert reg.get("serve_tokens_dropped_total").value() == 1
        assert reg0.get("serve_tokens_dropped_total").value() == 0
        assert reg.get("serve_tokens").value() == 6 * 3 + at + 1
        # the surplus row did ride one decode step more
        layers = cfg.cache_layers
        assert (reg.get("serve_layer_passes_total").value()
                == reg0.get("serve_layer_passes_total").value() + layers)
        # the others' page tables, and their K/V wherever they wrote it
        k, v = np.asarray(late.cache.k), np.asarray(late.cache.v)
        k0, v0 = np.asarray(base.cache.k), np.asarray(base.cache.v)
        for rid in (0, 2, 3):
            np.testing.assert_array_equal(rows[rid], rows0[rid])
            written = len(prompts[rid]) + len(got[rid].tokens) - 1
            for pos in range(written):
                page, off = rows[rid][pos // 8], pos % 8
                np.testing.assert_array_equal(k[:, :, page, off],
                                              k0[:, :, page, off])
                np.testing.assert_array_equal(v[:, :, page, off],
                                              v0[:, :, page, off])
        assert late.cache.allocator.free_pages == 47

    @pytest.mark.parametrize("kind", ["dense", "block"])
    def test_a_busy_run_reads_every_pass_once_and_never_drains(self, kind,
                                                               rng_np):
        """Seven requests through three slots, all queued before the first
        iteration: every pass is read exactly once, in the order it was
        dispatched; every pass but the first went out behind an unread one
        (``serve_passes_ahead_total``); the loop drains once, when nothing
        is left to dispatch, after its last pass."""
        reg = MetricsRegistry("busy")
        eng = _ahead_engine(kind, reg)
        value = lambda name, **lab: (reg.get(name).value(**lab)
                                     if reg.get(name) else 0)
        drained = lambda: sum(value("serve_loop_drains_total", why=w)
                              for w in ("idle", "stop", "swap", "incremental"))
        sent, reads, drains_at_send = [], [], []
        send, split = eng._send, eng._split_counts

        def watch_send(tracer, p, program, *args):
            drains_at_send.append(drained())
            send(tracer, p, program, *args)
            sent.append(p)

        def watch_read(out, rows, where):
            reads.append(out)
            return split(out, rows, where)

        eng._send, eng._split_counts = watch_send, watch_read
        news = (8, 3, 5, 1, 7, 2, 6)
        for n in news:
            eng.submit([int(t) for t in rng_np.integers(1, 63, 1 + n)], n)
        eng.run_until_idle()
        assert sorted(len(r.tokens) for r in eng.results()) == sorted(news)
        assert len(reads) == len(sent) and _in_flight(eng) == 0
        assert all(out is p.out for out, p in zip(reads, sent))
        decodes = sum(p.kind == "decode" for p in sent)
        # a decode step always follows something unread: the step before
        # it, or the prefill pass that admitted its first rows
        assert value("serve_passes_ahead_total", kind="decode") == decodes
        assert value("serve_passes_ahead_total", kind="prefill") == (
            len(sent) - decodes - 1)
        assert set(drains_at_send) == {0} and drained() == 1
        assert value("serve_loop_drains_total", why="idle") == 1

    def test_a_finish_by_length_needs_no_read(self, rng_np):
        """A sequence whose ``max_new_tokens`` the tokens in flight reach
        rides no further pass: decode steps run one row-layer for every
        token they hand out, nothing is dropped."""
        reg = MetricsRegistry("by_length")
        eng = _ahead_engine("dense", reg)
        cfg = eng.cfg
        news = (1, 2, 5, 8, 3)
        eng.generate([[int(t) for t in rng_np.integers(1, 64, 6)]
                      for _ in news], max_new_tokens=None)
        # generate() asks every request for the engine's cap: ask again
        reg = eng.registry = MetricsRegistry("by_length_2")
        decoded = []
        inner = eng.scheduler.append_token

        def watch(a, token):
            decoded.append(bool(a.generated))
            inner(a, token)

        eng.scheduler.append_token = watch
        for n in news:
            eng.submit([int(t) for t in rng_np.integers(1, 64, 6)], n)
        eng.run_until_idle()
        assert sorted(len(r.tokens) for r in eng.results()) == sorted(news)
        assert reg.get("serve_tokens").value() == sum(news)
        assert sum(decoded) == sum(n - 1 for n in news)
        assert (reg.get("serve_layer_passes_total").value()
                == sum(decoded) * cfg.cache_layers)
        assert reg.get("serve_tokens_dropped_total").value() == 0

    def test_an_engine_with_a_pass_in_flight_is_not_idle(self, rng_np):
        """``step()`` is True while a pass is unread; ``run_until_idle``,
        ``stop()`` and a weight swap (``set_params``) leave none."""
        reg = MetricsRegistry("in_flight")
        eng = _ahead_engine("dense", reg)
        drains = lambda why: (reg.get("serve_loop_drains_total").value(why=why)
                              if reg.get("serve_loop_drains_total") else 0)
        eng.submit([3, 1, 4], 2)
        # prefill and the first decode step go out; the prefill is read
        assert eng.step() and _in_flight(eng) == 1
        assert len(eng.scheduler.slots[0].generated) == 1
        assert eng.step() and _in_flight(eng) == 0      # the step is read
        assert eng.scheduler.slots[0].finished == "length"
        assert drains("idle") == 1 and not eng.results()
        assert eng.step() and not eng.step()            # retired: delivered
        assert [len(r.tokens) for r in eng.results()] == [2]
        # a swap reads what the old weights left in flight
        eng.submit([2, 7, 1, 8], 3)
        assert eng.step() and _in_flight(eng) == 1
        eng.set_params(eng.params)
        assert _in_flight(eng) == 0 and drains("swap") == 1
        assert len(eng.scheduler.slots[0].generated) == 2
        eng.run_until_idle()
        assert _in_flight(eng) == 0
        assert [len(r.tokens) for r in eng.results()] == [3]
        # stop() reads too, and delivers what that finishes
        eng.submit([5, 9, 2], 2)
        assert eng.step() and _in_flight(eng) == 1 and not eng.results()
        eng.stop()
        assert _in_flight(eng) == 0 and drains("stop") == 1
        assert [len(r.tokens) for r in eng.results()] == [2]
        assert eng.cache.allocator.free_pages == 47

    def test_a_background_loop_leaves_nothing_in_flight(self, rng_np):
        eng = _ahead_engine("dense")
        eng.start()
        try:
            ids = [eng.submit([int(t) for t in rng_np.integers(1, 64, 5)], n)
                   for n in (4, 1, 6, 2, 8)]
            got = eng.results(n=5, timeout=120.0)
        finally:
            eng.stop()
        assert sorted(r.id for r in got) == ids and _in_flight(eng) == 0
        assert sorted(len(r.tokens) for r in got) == [1, 2, 4, 6, 8]

    @pytest.mark.parametrize("kind", ["dense", "block"])
    def test_drains_from_another_thread_race_nothing(self, kind, rng_np):
        """``set_params`` (a weight swap's drain) from the caller's thread while
        the background loop runs one pass ahead: every pass is read once,
        every request gets the tokens it gets alone."""
        import sys
        import threading

        cfg, params = _ahead_model(kind)
        prompts = [[int(t) for t in rng_np.integers(1, 63, 4 + i % 5)]
                   for i in range(12)]
        news = [2 + i % 6 for i in range(12)]
        eng = _ahead_engine(kind)
        swaps, done = [0], threading.Event()

        def swapper():
            while not done.is_set():
                eng.set_params(eng.params)
                swaps[0] += 1

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        t = threading.Thread(target=swapper)
        eng.start()
        try:
            t.start()
            ids = [eng.submit(p, n) for p, n in zip(prompts, news)]
            got = eng.results(n=len(ids), timeout=120.0)
        finally:
            done.set()
            t.join(timeout=60.0)
            eng.stop()
            sys.setswitchinterval(interval)
        assert not t.is_alive() and swaps[0] > 0 and _in_flight(eng) == 0
        assert sorted(r.id for r in got) == ids
        by_id = {r.id: r.tokens for r in got}
        want = lambda rid, prompt, n: _plain_generation(
            cfg, params, prompt, n, rid, 0.0, seed=5)
        if kind == "block":     # mid-block drains: the engine never drained
            sync = _ahead_engine(kind)
            assert ids == [sync.submit(p, n) for p, n in zip(prompts, news)]
            sync.run_until_idle()
            alone = {r.id: r.tokens for r in sync.results()}
            want = lambda rid, prompt, n: alone[rid]
        for rid, prompt, n in zip(ids, prompts, news):
            assert by_id[rid] == want(rid, prompt, n)

    def test_a_failing_pass_fails_the_pending_requests(self, rng_np):
        """A device error surfaces where the pass is read, one pass late:
        it still kills the loop, which fails every pending request."""
        reg = MetricsRegistry("late_fault")
        eng = _ahead_engine("dense", reg)
        boom = RuntimeError("injected device fault")
        real, reads = eng._split_counts, []

        def read(out, rows, where):
            reads.append(where)
            if where == "decode":
                raise boom
            return real(out, rows, where)

        eng._split_counts = read
        eng.submit([1, 2, 3], 4)
        eng.submit([4, 5, 6], 4)
        eng.start()
        try:
            with pytest.raises(RuntimeError,
                               match="serving loop crashed") as ei:
                eng.results(n=1, timeout=60.0)
            assert ei.value.__cause__ is boom
            with pytest.raises(RuntimeError, match="submit refused"):
                eng.submit([1, 2, 3], 2)
        finally:
            eng.stop()
        # the prefill was read and handed out before the fault was met
        assert reads[0] == "prefill" and reads.count("decode") == 1
        assert reg.counter("serve_loop_crashes", "").value() == 1.0
        assert _in_flight(eng) == 0

    @pytest.mark.parametrize("via", ["step", "run_until_idle", "set_params",
                                     "stop"])
    def test_a_failing_pass_is_lost_for_every_caller(self, via, rng_np):
        """No loop thread: whoever meets the failure — a caller stepping by
        hand, a swap's drain, ``stop()`` — the step dispatched behind the
        failed one is lost with it.  What comes next (a step, a swap, a
        second ``stop()``) reads neither again."""
        eng = _ahead_engine("dense")
        boom = RuntimeError("injected device fault")
        real, reads = eng._split_counts, []

        def read(out, rows, where):
            reads.append(where)
            if where == "decode":
                raise boom
            return real(out, rows, where)

        eng._split_counts = read
        eng.submit([1, 2, 3], 6)
        assert eng.step() and _in_flight(eng) == 1     # the first decode step
        meet = {"step": eng.step, "run_until_idle": eng.run_until_idle,
                "set_params": lambda: eng.set_params(eng.params),
                "stop": eng.stop}[via]
        with pytest.raises(RuntimeError) as ei:
            meet()
        assert ei.value is boom and _in_flight(eng) == 0
        # a stepping caller had the next step out behind the failed one
        assert reads == ["prefill", "decode"]
        eng.set_params(eng.params)
        eng.stop()
        assert reads == ["prefill", "decode"] and _in_flight(eng) == 0


# -- the programs' text -----------------------------------------------------------
# What generation by blocks added to the loop (PR 35: the block in
# progress carried on the device) is chosen by ``cfg.block_len``; with a
# block length of 1 the serving programs are the ones they were, and so
# are a block model's prefill programs and its block pass as a caller
# lowers it that hands it no block state.


def _program_texts(kind):
    """The StableHLO of the programs an engine of ``kind`` compiles at
    its first admission (``_make_ready``): each member of the prefill
    ladder and the decode step, token array among the arguments — by
    blocks the prefill programs without it and the block pass in the
    12-argument form of ``serve_block_lm.aot_programs``."""
    eng = _ahead_engine(kind)
    cache, sched, bl = eng.cache, eng.scheduler, eng.cfg.block_len
    head = (eng.params, eng._base_key, cache.k, cache.v)
    texts = {}
    for n, length in sched.prefill_shapes:
        args = eng._dev(sched.prefill_arrays([], n, length), "ids",
                        "seq_lens", "page_table", "rids", "temps", "slots")
        texts[f"prefill {n}"] = eng._prefill.lower(
            *head, *args, cache.state,
            *([cache.tokens] if bl == 1 else [])).as_text()
    batch = sched.decode_arrays([])
    args = eng._dev(batch, "positions", "seq_lens", "page_table", "rids",
                    "gens", "temps")
    ids = cache.tokens
    if bl > 1:
        ids = jnp.asarray(batch["ids"][:, :2 * bl + 1])
    texts["decode"] = eng._decode.lower(
        *head, ids, *args, cache.state).as_text()
    return texts


# sha256 of each text as the tree BEFORE PR 35 lowers it (commit ceaf5eb,
# this file's helper run against a checkout of it), under the jax the
# hashes were taken with
_PARENT_JAX = "0.9.0"
_PARENT_TEXTS = {
    "dense": {"prefill 1": "f51f01045fdddbbf", "prefill 2": "acb55758a61e8117",
              "decode": "4aa4c8ac1aff7f45"},
    "looped": {"prefill 1": "8381819b3f182b7d",
               "prefill 2": "0ce97baf97b3f688",
               "decode": "88a364c4a92ed73e"},
    "pattern": {"prefill 1": "b438709ba0f50853",
                "prefill 2": "a87c11c902af4a0f",
                "decode": "01201bb80137c1dc"},
    "block": {"prefill 1": "d51d030ccd1e4bb1", "prefill 2": "816adfd62cf8cff6",
              "decode": "fa73163fd5696615"},
}


@pytest.mark.skipif(jax.__version__ != _PARENT_JAX,
                    reason="the recorded texts are another jax's")
@pytest.mark.parametrize("kind", list(_AHEAD_CFGS))
def test_programs_lower_to_the_parents_text(kind):
    import hashlib

    got = {name: hashlib.sha256(text.encode()).hexdigest()[:16]
           for name, text in _program_texts(kind).items()}
    assert got == _PARENT_TEXTS[kind]


def test_the_block_pass_lowers_without_the_state_array():
    """The 12-argument call ``benchmarks/drivers/serve_block_lm.py:
    aot_programs`` makes: ``ids`` [slots, 2B + 1] as given and no block
    state.  Nothing is carried then, and the pass computes what the
    engine's own 13-argument program computes for a row that opens its
    block from the same ids."""
    eng = _ahead_engine("block")
    cache, sched, bl = eng.cache, eng.scheduler, eng.cfg.block_len
    eng.submit([3, 1, 4, 1, 5, 9], 4)
    with eng._pump:
        sched.enqueue(eng._incoming.popleft())
    live = sched.admit()
    batch = sched.decode_arrays(live)
    assert batch["ids"].shape == (3, 2 * bl + 2)
    assert batch["ids"][0].tolist() == [5, 9, 0, 0, 0, 0, 1, 1, 1, 1]
    head = (eng.params, eng._base_key, cache.k, cache.v)
    rest = eng._dev(batch, "positions", "seq_lens", "page_table", "rids",
                    "gens", "temps")
    ids = jnp.asarray(batch["ids"])
    lowered = eng._decode.lower(*head, ids[:, :2 * bl + 1], *rest, {})
    out, _, _, _, none = lowered.compile()(
        *head, ids[:, :2 * bl + 1], *rest, {})
    want, _, _, _, block = eng._decode(*head, ids, *rest, {}, cache.tokens)
    assert none is None and block.shape == (3, 2 * bl)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # and the state the engine's program leaves: what the pass unmasked
    n = 3 * bl
    toks, chosen = (np.asarray(want)[:n].reshape(3, bl),
                    np.asarray(want)[n:2 * n].reshape(3, bl))
    assert chosen[0].sum() == batch["ids"][0, 2 * bl] == 1
    assert not chosen[0, :2].any()              # the prompt's tail is known
    assert np.asarray(block)[0, :bl].tolist() == [
        t if c else k for t, c, k in zip(toks[0], chosen[0], [5, 9, 0, 0])]
    assert np.asarray(block)[0, bl:].tolist() == [
        int(m and not c) for m, c in zip([0, 0, 1, 1], chosen[0])]
    assert not np.asarray(block)[1:].any()      # rows that ride no pass
