"""Generation by diffusion over blocks through ``ServingEngine`` — a
decode pass that carries a block of positions a sequence — against the
plain float32 reference ``benchmarks/references/sdar.py`` on seeded random
weights at a toy size (2 layers of attention + 8 softmax-routed SwiGLU
experts top-2, per-head q/k norms, rotary, block length 4): the engine's
order of unmasking, tokens and confidences against the reference's plain
generation loop, for both policies and 1 / 2 / 4 denoising passes; the
``[noisy ; clean]`` layout against the block pass over the paged cache;
prompts whose tail opens the first block; a last block's surplus dropped;
one slot and many; a slot reused; what the counters and spans say of a
pass.  (The kernels' masks are held in ``tests/test_attention.py`` and
``tests/test_paged_attention.py``, the routed layer's scores, gate and
shares in ``tests/test_moe_arrangement.py``.)

Tolerances.  Program and reference both compute in float32 here (the CPU
backend's dots are exact float32), so they differ by the order of
summation only: logits of unit scale agree to ``TOL`` = 2e-4 and a
confidence (a probability) to 1e-5; with margins that wide apart the
order of unmasking and the tokens are equal.  bfloat16 in the program's
place flips one served token in seventy and int8 weights five times as
many, with wider gaps (``test_bf16_passes_and_int8_weights_fail``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import ServingConfig, sampling
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.scheduler import Request, Scheduler
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy

TOL = 2e-4
BL = 4
M = dict(vocab_size=97, num_layers=4, num_heads=4, kv_heads=2, head_dim=8,
         embed_dim=32, mlp_dim=16, max_seq_len=128, norm="rms",
         norm_eps=1e-6, positions="rotary", rope_theta=1e6, qk_norm=True,
         mlp="swiglu", tie_embeddings=False, pattern="*E*E", moe_experts=8,
         moe_router="softmax_topk", moe_top_k=2, block_len=BL, mask_id=96)
LENS = (5, 8, 3, 14, 1, 6, 7)       # L mod 4 = 1, 0, 3, 2, 1, 2, 3
SERVING = dict(max_slots=3, page_size=8, num_pages=40, max_prompt_len=24,
               max_new_tokens=16, prefill_batch=2)


def block_cfg(**kw):
    return T.TransformerConfig(**{**M, "remat": False, **kw})


ref, weights, params = lm_toy.fixtures("sdar", M, 7)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, size=n).tolist() for n in LENS]


def engine(params, cfg=None, registry=None, **serving):
    return lm_toy.engine(cfg or block_cfg(), params,
                         registry or MetricsRegistry("block_lm"),
                         **{**SERVING, **serving})


# -- the engine against the reference's generation loop --------------------------


@pytest.mark.parametrize("policy", ["low_confidence_static", "sequential"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_engine_equals_the_reference(steps, policy, ref, weights, params,
                                     prompts):
    """Seven requests through three slots (they join mid-flight), 10 new
    tokens each — not a multiple of the block length, prompts with every
    ``L mod B``: the same order of unmasking, the same tokens at every
    generated position (the dropped surplus included), confidences to
    1e-5, and exactly the asked number handed out."""
    eng = engine(params, denoise_steps=steps, unmask_policy=policy)
    for r in eng.generate(prompts, max_new_tokens=10):
        want = ref.generate(weights, M, r.prompt, 10, steps, policy)
        total = -(-(len(r.prompt) + 10) // BL) * BL - len(r.prompt)
        assert len(r.trail["tokens"]) == total == len(want["tokens"])
        assert r.trail["steps"] == want["steps"]
        assert r.trail["tokens"] == want["tokens"]
        assert r.tokens == want["tokens"][:10] and r.finish_reason == "length"
        np.testing.assert_allclose(r.trail["confidence"], want["confidence"],
                                   atol=1e-5)
        assert max(r.trail["steps"]) <= steps - 1


def test_block_pass_logits_equal_the_noisy_clean_layout(ref, weights, params):
    """A block pass over the paged cache, its earlier blocks prefilled
    under the block-causal mask, against ONE forward over ``[noisy ;
    clean]``: the logits of the block in progress, at every position,
    with two of them masked."""
    cfg = block_cfg()
    rng = np.random.default_rng(3)
    seq = rng.integers(0, 96, size=16).tolist()
    start = 12
    _, ks, vs, _ = lm_toy.jitted(T.forward_prefill, cfg)(
        params, jnp.asarray([seq]), jnp.asarray([start]))
    kc, vc = PA.init_kv_pages(2, 2, 8, 8, 8)
    table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, table, jnp.asarray([start]))
    masked = jnp.asarray([[False, True, False, True]])
    got, *_ = lm_toy.jitted(T.forward_decode_block, cfg,
                            attn_impl="reference")(
        params, jnp.asarray([seq[start:]]), masked, jnp.asarray([start]),
        jnp.asarray([start + BL]), table, kc, vc)
    clean = jnp.asarray(seq)
    noisy = clean.at[jnp.asarray([13, 15])].set(M["mask_id"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda noisy, clean: ref.denoise_logits(
            weights, noisy, clean, M))(noisy, clean)[start:]
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=TOL, rtol=TOL)


def test_forward_equals_the_reference_under_the_block_causal_mask(
        ref, weights, params):
    seq = np.random.default_rng(4).integers(0, 96, size=22).tolist()
    got = lm_toy.jitted(T.forward, block_cfg())(params,
                                                jnp.asarray([seq]))[0]
    # at its own length: a block sees all of itself, padding included
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda ids: ref.logits_fn(weights, ids, M))(
            jnp.asarray(seq))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)
    # and it is not the token-causal forward: position 0 sees position 3
    causal = lm_toy.jitted(T.forward, block_cfg(block_len=1, mask_id=None))(
        params, jnp.asarray([seq]))[0]
    assert float(jnp.max(jnp.abs(causal[0] - got[0]))) > 1e-2


def test_one_slot_and_a_slot_reused(ref, weights, params, prompts):
    """One slot: the requests follow each other through the same slot
    and the same pages; what the one before left there changes nothing."""
    eng = engine(params, max_slots=1, prefill_batch=1, num_pages=6)
    res = eng.generate(prompts[:4], max_new_tokens=7)
    for r in res:
        want = ref.generate(weights, M, r.prompt, 7, 2)
        assert (r.tokens, r.trail["steps"]) == (want["tokens"][:7],
                                                want["steps"])
    assert eng.cache.allocator.free_pages == 5


def test_served_gaps_reads_the_step_that_unmasked(ref, weights, params,
                                                  prompts):
    """The comparison the benchmark makes: in float32 every served token
    is the reference's best at the step that unmasked it (gap 0); with
    the order of unmasking shifted by one pass the same tokens are held
    against another state of their block and no longer are."""
    res = engine(params).generate(prompts, max_new_tokens=10)
    reqs = [(r.prompt, r.tokens, r.trail) for r in res]
    gaps = ref.served_gaps(M, weights, reqs, 40)
    assert len(gaps["served"]) == 10 * len(prompts)
    assert ref.summarise(gaps["served"])["widest"] < TOL
    other = [(p, t, dict(tr, steps=[1 - s for s in tr["steps"]]))
             for p, t, tr in reqs]
    assert ref.summarise(ref.served_gaps(M, weights, other, 40)
                         ["served"])["widest"] > 0.05
    with pytest.raises(ValueError, match="does not close the blocks"):
        ref.served_gaps(M, weights, [(reqs[0][0], reqs[0][1], dict(
            reqs[0][2], tokens=reqs[0][2]["tokens"][:-1]))], 40)


def test_bf16_passes_and_int8_weights_fail(ref, weights, params, prompts):
    """The same comparison with the program in the stated precision and
    in the one below it: bfloat16 weights and arithmetic stay inside
    limits that an engine serving int8-rounded weights misses."""
    low = lambda tree, f: jax.tree.map(f, tree)
    bf16 = low(params, lambda a: a.astype(jnp.bfloat16))
    int8 = low(params, lambda a: ref._int8(a, 0).astype(jnp.bfloat16)
               if a.ndim >= 2 else a.astype(jnp.bfloat16))
    mean = {}
    for name, tree in (("bf16", bf16), ("int8", int8)):
        res = engine(tree, block_cfg(dtype=jnp.bfloat16)).generate(
            prompts, max_new_tokens=10)
        gaps = ref.served_gaps(M, weights, [(r.prompt, r.tokens, r.trail)
                                            for r in res], 40)
        mean[name] = ref.summarise(gaps["served"])["mean"]
    # my CPU readings: 2.1e-6 and 4.1e-4 (a flipped token's gap, averaged
    # over 70 served tokens; the reference's median margin is 0.24)
    assert mean["bf16"] < 5e-5 < mean["int8"]


def test_eos_inside_a_block_ends_the_request_there(params, prompts):
    base = engine(params).generate(prompts[:1], max_new_tokens=10)[0]
    eos = base.tokens[1]
    r = engine(params, eos_id=eos).generate(prompts[:1], max_new_tokens=10)[0]
    cut = base.tokens.index(eos) + 1
    assert r.tokens == base.tokens[:cut] and r.finish_reason == "eos"
    # the block was committed whole: its other positions are in the trail
    assert len(r.trail["tokens"]) % BL == (-len(r.prompt)) % BL
    assert len(r.trail["tokens"]) >= cut


def test_temperature_draws_are_a_function_of_the_seed(params, prompts):
    draw = lambda seed: [r.tokens for r in engine(params, seed=seed).generate(
        prompts[:3], max_new_tokens=9, temperature=1.5)]
    a, b, c = draw(1), draw(1), draw(2)
    greedy = [r.tokens for r in engine(params).generate(
        prompts[:3], max_new_tokens=9)]
    assert a == b and a != c and a != greedy


# -- one pass ahead ---------------------------------------------------------------
# Block pass n + 1 is dispatched before pass n is read: a block's tokens
# and masked flags stay on the device, the scheduler counts at dispatch
# what the host decides and books at the read what the device chose.  The
# oracle is the same engine with every pass read before the next is built.


def _watched(eng):
    """Every hand-out of ``eng``, in order: (request, index, token)."""
    handed, inner = [], eng.scheduler.append_token

    def watch(a, token):
        handed.append((a.request.id, len(a.generated), token))
        inner(a, token)

    eng.scheduler.append_token = watch
    return handed


def _run_drained(eng):
    while eng.step():
        eng._drain("sync")


def _same_answers(got, want):
    assert sorted(got) == sorted(want)
    for rid, r in got.items():
        w = want[rid]
        assert (r.tokens, r.finish_reason) == (w.tokens, w.finish_reason)
        assert r.trail["tokens"] == w.trail["tokens"]
        assert r.trail["steps"] == w.trail["steps"]
        np.testing.assert_allclose(r.trail["confidence"],
                                   w.trail["confidence"], atol=1e-6)


@pytest.mark.parametrize("policy", ["low_confidence_static", "sequential"])
@pytest.mark.parametrize("steps", [1, 2, 3])
def test_the_background_loop_equals_the_loop_drained(steps, policy, params,
                                                     prompts):
    """Seven requests through three slots, queued before the loop starts:
    the background loop, one pass ahead, gives every request the tokens,
    the trail (tokens, steps, confidences) and the order of hand-out that
    the engine gives it when every pass is read before the next; nothing
    is left in flight and every pass sent was read."""
    def run(background):
        eng = engine(params, denoise_steps=steps, unmask_policy=policy)
        handed = _watched(eng)
        seen, admit = [], eng.scheduler.admit

        def admitted(now=0.0):
            out = admit(now=now)
            seen.extend(out)
            return out

        eng.scheduler.admit = admitted
        for p in prompts:
            eng.submit(p, 10)
        if background:
            eng.start()
            try:
                res = eng.results(n=len(prompts), timeout=300.0)
            finally:
                eng.stop()
        else:
            _run_drained(eng)
            res = eng.results()
        assert not eng._in_flight and len(seen) == len(prompts)
        assert all(a.in_flight == 0 and not a.unread for a in seen)
        return eng, {r.id: r for r in res}, handed

    ahead, got, handed = run(True)
    sync, want, handed0 = run(False)
    _same_answers(got, want)
    per = lambda h, rid: [(i, t) for r, i, t in h if r == rid]
    for rid in got:
        assert per(handed, rid) == per(handed0, rid)
        assert [t for _, t in per(handed, rid)] == got[rid].tokens
    val = lambda e, name, **lab: e.registry.get(name).value(**lab)
    # the same passes, row for row, whenever they were read
    for name, lab in (("serve_block_passes_total", {"kind": "denoise"}),
                      ("serve_block_passes_total", {"kind": "commit"}),
                      ("serve_block_positions_total", {}),
                      ("serve_tokens_dropped_total", {}),
                      ("serve_tokens", {})):
        assert val(ahead, name, **lab) == val(sync, name, **lab)
    assert val(ahead, "serve_passes_ahead_total", kind="decode") > 0
    assert ahead.cache.allocator.free_pages == SERVING["num_pages"] - 1


def test_the_order_of_hand_out_when_nobody_waits_for_a_slot(params, prompts):
    """Three requests in three slots: the passes are the same passes, so
    ahead and drained hand the same tokens out in the same order,
    request against request."""
    def run(drained):
        eng = engine(params)
        handed = _watched(eng)
        for p in prompts[:3]:
            eng.submit(p, 9)
        _run_drained(eng) if drained else eng.run_until_idle()
        return handed, eng

    handed, eng = run(False)
    handed0, sync = run(True)
    assert handed == handed0 and len(handed) == 27
    steps = lambda e: e.registry.get("serve_decode_step_ms").summary()["count"]
    assert steps(eng) == steps(sync)
    assert eng.registry.get("serve_loop_drains_total").value(why="idle") == 1


def test_an_eos_inside_a_block_is_seen_one_pass_late(params, prompts):
    """The first pass of the next block is out when the commit that holds
    the eos is read: that surplus pass is dropped and counted, its block
    never committed, nothing of it handed out, the pages freed -- against
    a run in which the request ends at that token by LENGTH, which is
    known at dispatch and costs no surplus pass."""
    hot = 1.5       # sampled, not greedy: a drawn toy repeats itself
    base = engine(params).generate(prompts[:3], max_new_tokens=10,
                                   temperature=hot)
    # a token nobody else draws, first drawn inside a block that is not
    # its request's last
    who, at = next(
        (k, i) for k, r in enumerate(base) for i, tok in enumerate(r.tokens)
        if tok not in r.tokens[:i]
        and all(tok not in o.tokens for o in base if o is not r)
        and (len(r.prompt) + i) // BL * BL + BL < len(r.prompt) + 10)
    eos, cut = base[who].tokens[at], at + 1

    def run(**kw):
        reg = MetricsRegistry("block_eos")
        eng = engine(params, registry=reg, **kw)
        news = [10] * 3
        if "eos_id" not in kw:
            news[who] = cut
        ids = [eng.submit(p, n, hot) for p, n in zip(prompts[:3], news)]
        eng.run_until_idle()
        got = {r.id: r for r in eng.results()}
        return eng, reg, [got[i] for i in ids]

    late, reg, got = run(eos_id=eos)
    by_len, reg0, want = run()
    for k, (r, w, b) in enumerate(zip(got, want, base)):
        assert r.tokens == w.tokens == (b.tokens[:cut] if k == who
                                        else b.tokens)
        assert r.trail == w.trail           # the committed blocks only
        assert (r.finish_reason, w.finish_reason) == (
            ("eos", "length") if k == who else ("length", "length"))
    val = lambda r, name: r.get(name).value()
    # one more row of one block pass, whatever it unmasked dropped
    rows = BL * late.cfg.cache_layers
    assert val(reg, "serve_layer_passes_total") == (
        val(reg0, "serve_layer_passes_total") + rows)
    assert val(reg, "serve_block_positions_total") == (
        val(reg0, "serve_block_positions_total") + BL)
    surplus = val(reg, "serve_tokens_dropped_total") - val(
        reg0, "serve_tokens_dropped_total")
    assert 1 <= surplus <= BL
    assert val(reg, "serve_tokens") == val(reg0, "serve_tokens") == cut + 20
    assert val(reg, "serve_blocks_committed_total") == val(
        reg0, "serve_blocks_committed_total")
    assert not late._in_flight
    assert late.cache.allocator.free_pages == SERVING["num_pages"] - 1


def test_a_reused_slot_opens_from_the_hosts_row(ref, weights, params,
                                                prompts):
    """One slot.  Whatever block its last sequence -- or anything else --
    left in the device's copy, a new request's first pass takes tokens
    and flags from the row the host sends; only the pass that OPENS a
    block carries one."""
    eng = engine(params, max_slots=1, prefill_batch=1, num_pages=6)
    eng.generate(prompts[:1], max_new_tokens=7)
    left = np.asarray(eng.cache.tokens)
    assert left.shape == (1, 2 * BL) and left[0, :BL].any()
    assert not left[0, BL:].any()       # a committed block: nothing masked
    # worse than a predecessor: every position "known", all the wrong token
    eng.cache.tokens = jnp.full_like(eng.cache.tokens, 5).at[:, BL:].set(0)
    sent, arrays = [], eng.scheduler.decode_arrays

    def watch(live):
        out = arrays(live)
        if live:
            sent.append(out["ids"][0].copy())
        return out

    eng.scheduler.decode_arrays = watch
    r, = eng.generate(prompts[1:2], max_new_tokens=7)
    want = ref.generate(weights, M, r.prompt, 7, 2)
    assert (r.tokens, r.trail["steps"]) == (want["tokens"][:7], want["steps"])
    opens = [int(row[2 * BL + 1]) for row in sent]
    # 8 prompt tokens, 7 new: two blocks of (open, denoise, commit)
    assert opens == [1, 0, 0, 1, 0, 0]
    assert all(not row[:2 * BL].any() for row, o in zip(sent, opens) if not o)
    assert [int(row[2 * BL]) for row in sent] == [2, 2, 0, 2, 2, 0]


@pytest.mark.parametrize("via", ["set_params", "stop"])
def test_a_drain_mid_block_loses_nothing(via, params, prompts):
    """A weight swap's drain, or ``stop()``, with a block half unmasked
    and its next pass in flight: the pass is read and booked, and the
    engine goes on to the same answers."""
    want = {r.id: r for r in engine(params).generate(
        prompts[:4], max_new_tokens=10)}
    reg = MetricsRegistry("mid_block")
    eng = engine(params, registry=reg)
    for p in prompts[:4]:
        eng.submit(p, 10)
    for _ in range(2):
        assert eng.step()
    # the third request's prefill pass and the second block pass
    assert [p.kind for p in eng._in_flight] == ["prefill", "decode"]
    mid = [a for a in eng.scheduler.active if a.unread]
    half = lambda blk: blk.landed == 1 and None in blk.steps
    assert [half(a.unread[0]) for a in mid] == [True, True, False]
    if via == "stop":
        eng.stop()
    else:
        eng.set_params(eng.params)
    assert not eng._in_flight and all(not a.unread for a in mid)
    assert reg.get("serve_loop_drains_total").value(why={
        "stop": "stop", "set_params": "swap"}[via]) == 1
    eng.run_until_idle()        # never threaded: it keeps serving
    _same_answers({r.id: r for r in eng.results()}, want)


# -- what a pass counts ----------------------------------------------------------


def test_counters_and_spans_count_what_a_block_pass_does(params, prompts):
    reg = MetricsRegistry("block_counts")
    res, spans = lm_toy.traced(lambda: engine(params, registry=reg).generate(
        prompts, max_new_tokens=10))
    val = lambda name, **lab: reg.get(name).value(**lab)
    blocks = sum(-(-(n + 10) // BL) - n // BL for n in LENS)
    positions = sum(len(r.trail["tokens"]) for r in res)
    assert val("serve_tokens") == 10 * len(LENS)
    assert val("serve_blocks_committed_total") == blocks
    assert val("serve_block_passes_total", kind="commit") == blocks
    assert val("serve_tokens_dropped_total") == positions - 10 * len(LENS)
    # a first block with one masked position takes one denoising pass
    denoise = sum(2 * (-(-(n + 10) // BL) - n // BL) - (n % BL == 3)
                  for n in LENS)
    assert val("serve_block_passes_total", kind="denoise") == denoise
    rows = blocks + denoise
    assert val("serve_block_positions_total") == rows * BL
    assert val("serve_layer_passes_total") == rows * BL * 2
    assert reg.get("serve_block_length").value() == BL
    dec = [s.args for s in spans["serve_decode"]]
    assert sum(a["batch"] for a in dec) == rows
    assert sum(a["committed"] for a in dec) == blocks
    assert sum(a["tokens_out"] for a in dec) == 10 * len(LENS)
    assert sum(a["unmasked"] for a in dec) == positions
    assert sum(a["masked_in"] for a in dec) >= positions
    assert all(a["block"] == BL and a["positions"] == a["batch"] * BL
               and a["context_tokens"] >= a["batch"] * BL
               and a["moe_assignments"] == a["positions"] * 2 * 2
               for a in dec)
    pre = [s.args for s in spans["serve_prefill"]]
    assert sum(a["blocks_written"] for a in pre) == sum(n // BL for n in LENS)
    assert sum(a["prompt_tokens"] for a in pre) == sum(
        n // BL * BL for n in LENS)


@pytest.mark.parametrize("steps,want", [(1, [4]), (2, [2, 2]), (3, [2, 1, 1]),
                                        (4, [1, 1, 1, 1]), (6, [1, 1, 1, 1])])
def test_unmask_count_spreads_the_block_over_the_passes(steps, want):
    s = ServingConfig(**SERVING, denoise_steps=steps)
    cache = PagedKVCache(1, 1, 8, s.num_pages, s.page_size, s.max_slots,
                         s.max_pages_per_seq)
    sched = Scheduler(s, cache, block_len=BL)
    sched.enqueue(Request(id=0, prompt=list(range(8)), max_new_tokens=4))
    a, = sched.admit()
    got, blk = [], a.block
    while blk.masked:
        n = sched.unmask_count(blk)
        got.append(n)
        ids = sched.decode_arrays([a])["ids"]
        assert ids.shape == (s.max_slots, 2 * BL + 2)
        assert ids[0, 2 * BL] == n
        # the pass that opens the block carries the host's row, a later
        # one nothing: its input is the device's own copy
        opens = len(got) == 1
        assert ids[0, 2 * BL + 1] == opens
        assert ids[0, BL:2 * BL].sum() == (BL if opens else 0)
        arrays = sched.decode_arrays([a])
        assert (arrays["positions"][0], arrays["seq_lens"][0]) == (8, 12)
        assert arrays["gens"][0] == (len(got) - 1) * BL
        before = blk.masked
        sched.sent([a])     # counted at dispatch: nothing has been read
        assert blk.masked == before - n and a.in_flight == len(got)
        assert blk.steps == [None] * BL
    assert got == want
    assert sched.unmask_count(blk) == 0
    sched.sent([a])         # the pass that commits it: the next block opens
    assert a.block is not blk and (a.block.start, a.block.masked) == (12, BL)
    assert a.block_passes == len(want) + 1 == a.in_flight
    # every position it asked for is in flight: it rides no further pass
    assert sched.decode_batch() is None and not a.finished
    # the reads, one pass late each
    for k, n in enumerate(want):
        free = [t for t in range(BL) if blk.steps[t] is None][:n]
        assert sched.block_landed(
            a, [7] * BL, [t in free for t in range(BL)],
            [0.5] * BL) == (0, 0, False)
        assert sorted(x for x in blk.steps if x is not None)[-1] == k
    assert not a.generated and a.trail["tokens"] == []
    assert sched.block_landed(a, [0] * BL, [False] * BL,
                              [0.0] * BL) == (BL, 0, True)
    assert a.generated == a.trail["tokens"] == [7] * BL
    assert a.finished == "length" and a.in_flight == 0 and not a.unread


@pytest.mark.parametrize("policy,want", [
    ("low_confidence_static", [[False, True, False, True],
                               [True, False, False, False]]),
    ("sequential", [[True, True, False, False],
                    [True, False, False, False]])])
def test_choose_unmask(policy, want):
    masked = jnp.asarray([[True, True, True, True],
                          [True, False, True, True]])
    # row 1: positions 0, 2 and 3 tie: the leftmost goes first
    conf = jnp.asarray([[0.1, 0.9, 0.2, 0.5], [0.3, 0.99, 0.3, 0.3]])
    got = sampling.choose_unmask(policy, masked, conf, jnp.asarray([2, 1]))
    assert np.asarray(got).tolist() == want
    none = sampling.choose_unmask(policy, masked, conf, jnp.asarray([0, 0]))
    assert not np.asarray(none).any()


# -- what is refused by name ------------------------------------------------------


@pytest.mark.parametrize("serving,match", [
    (dict(prefix_cache=True), "prefix_cache / prefill_chunk_tokens"),
    (dict(prefill_chunk_tokens=8), "prefix_cache / prefill_chunk_tokens"),
    (dict(page_size=6, num_pages=60), "not a multiple of the model's "
                                      "block_len"),
    (dict(unmask_policy="threshold"), "unknown unmask_policy"),
    (dict(denoise_steps=0), "denoise_steps must be >= 1")])
def test_the_engine_refuses_what_is_not_built(serving, match, params):
    with pytest.raises((NotImplementedError, Exception), match=match):
        engine(params, **serving)


@pytest.mark.parametrize("fields,match", [
    (dict(mask_id=None), "needs a mask_id"),
    (dict(mask_id=97), "needs a mask_id"),
    (dict(block_len=0), "block_len must be >= 1"),
    (dict(attn_impl="blockwise"), "only 'exact' and 'flash'"),
    (dict(pattern=None, num_layers=2, moe_experts=0, loop_steps=2),
     "loop_steps > 1 or Mamba / KDA layers"),
    (dict(pattern="*EKE", num_layers=4, kda_heads=2),
     "loop_steps > 1 or Mamba / KDA layers"),
    (dict(moe_router="softmax"), "dropless moe_router")])
def test_the_config_refuses_what_is_not_built(fields, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        block_cfg(**fields)


def test_loss_is_refused_under_a_block_length(params):
    with pytest.raises(NotImplementedError, match="diffusion objective"):
        T.loss_fn(block_cfg(), params, jnp.zeros((1, 9), jnp.int32))


def test_a_dense_stack_generates_by_blocks_too():
    """No layer pattern: the scanned stack of (attention, MLP) blocks
    runs the same block pass.  Over a block with nothing masked its
    logits are the block-causal forward's at those positions, and the
    engine serves it."""
    cfg = T.TransformerConfig(
        vocab_size=61, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=32,
        max_seq_len=64, block_len=BL, mask_id=60, remat=False)
    params = T.init_params(cfg, jax.random.key(1))
    seq = list(range(1, 13))
    _, ks, vs = lm_toy.jitted(T.forward_prefill, cfg)(
        params, jnp.asarray([seq]), jnp.asarray([8]))
    kc, vc = PA.init_kv_pages(2, 2, 8, 8, 8)
    table = jnp.asarray([[1, 2, 0]], jnp.int32)
    kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, table, jnp.asarray([8]))
    got, *_ = lm_toy.jitted(T.forward_decode_block, cfg,
                            attn_impl="reference")(
        params, jnp.asarray([seq[8:]]), jnp.zeros((1, BL), bool),
        jnp.asarray([8]), jnp.asarray([12]), table, kc, vc)
    want = lm_toy.jitted(T.forward, cfg)(params, jnp.asarray([seq]))[0, 8:]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=TOL, rtol=TOL)
    eng = engine(params, cfg, MetricsRegistry("dense_block"))
    r, = eng.generate([seq[:9]], max_new_tokens=6)
    assert len(r.tokens) == 6 and len(r.trail["tokens"]) == 7


def test_default_fields_leave_the_tree_and_the_programs_alone():
    """``block_len`` 1, no q/k norms, sigmoid routing: the parameter tree
    of a pattern with routed experts holds nothing of this module's
    additions, and the same key draws the same weights as before them
    (the programs themselves are held to the parent's text by the AOT
    comparison PERF.md records)."""
    cfg = T.TransformerConfig(
        vocab_size=61, num_layers=2, num_heads=2, embed_dim=16, mlp_dim=8,
        max_seq_len=32, norm="rms", positions="none", mlp="relu2",
        pattern="*E", moe_experts=4, moe_router="sigmoid", moe_top_k=2,
        remat=False)
    params = T.init_params(cfg, jax.random.key(0))
    assert set(params["blocks"][0]) == {"ln_g", "wq", "wk", "wv", "wo"}
    assert set(params["blocks"][1]) == {"ln_g", "router", "router_bias",
                                        "w_in", "w_out"}
    assert dataclasses.replace(cfg, block_len=1, qk_norm=False) == cfg
    assert cfg.moe_dropless and cfg.routed.score == "sigmoid" \
        and not cfg.routed.gated
    # the draws' order: router, (bias: zeros), w_in, w_out, as it was
    k = iter(jax.random.split(jax.random.key(0), 8 + 8 * 2))
    draw = lambda *shape: jax.random.normal(next(k), shape, cfg.dtype)
    draw(61, 16)
    for _ in range(4):
        draw(16, 16)
    np.testing.assert_array_equal(
        np.asarray(params["blocks"][1]["router"]),
        np.asarray(draw(16, 4) * 16 ** -0.5))
