"""A decoder-hybrid-decoder pattern (SambaY, as Phi-4-mini-flash-reasoning):
Mamba-1 layers, attention over a window served from a RING a slot, ONE
full-attention layer whose K/V the cross-attention layers behind it read,
gated memory units reading the last Mamba-1 layer's scan output,
differential attention on every attention layer, LayerNorm with bias and
biased projections — against the plain float32 reference
``benchmarks/references/phi4flash.py`` on seeded random weights at a toy
size: the forward; the engine's own cache (pages, rings, state rows) at
every position of requests that join mid-flight, on contexts that wrap
the ring three times and on prompts shorter than the window; a slot
reused after a longer request; the prefill pass that narrows to its rows'
last tokens; the chunked selective scan against its one-token step; the
pairing of the differential heads through the decode kernel (interpret
mode) at the published heads; every refusal of ``__post_init__``.

Tolerances.  Program and reference both compute in float32 here, so they
differ by the order of summation only: ``TOL`` = 2e-4 on logits of unit
spread (the largest seen is 3e-5).  bfloat16 in place of float32 misses it
sixty times over (``test_bfloat16_would_fail``).

Summed seconds (the tier-1 command in this sandbox): 45; every forward is
compiled once and shared through ``lm_toy.jitted``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import mamba1
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy
from lm_toy import PS

TOL = 2e-4
PAD = 40    # the reference's one compiled length
V = 97
W = 8       # the window: two pages of four
M = dict(vocab_size=V, num_layers=20, num_heads=8, kv_heads=4, head_dim=64,
         embed_dim=64, mlp_dim=96, max_seq_len=128, norm="layer",
         norm_eps=1e-5, positions="none", mlp="swiglu", tie_embeddings=True,
         pattern="S-W-S-W-S-*-G-X-G-X-", attn_window=W, attn_diff=True,
         attn_bias=True, mamba1_inner=128, mamba1_state=4, mamba1_conv=4,
         mamba1_dt_rank=4, mamba1_chunk=4, init={})


def yoco_cfg(**kw):
    return T.TransformerConfig(
        **{**{k: v for k, v in M.items() if k != "init"}, "remat": False,
           **kw})


ref, weights, params, seq, ref_logits = lm_toy.fixtures(
    "phi4flash", M, 11, seq_len=30, pad=PAD)


# -- the program is the reference ----------------------------------------------------


def test_forward_equals_the_reference(params, seq, ref_logits):
    cfg = yoco_cfg()
    assert (cfg.cache_layers, cfg.window_layers, cfg.cross_reads,
            cfg.kv_reads, cfg.narrow_at) == (1, 2, (0, 0), 3, 11)
    assert cfg.pattern_roll == (20, 1)      # no period: unrolled
    assert cfg.diff_depths == {"attn": (5,), "window": (1, 3),
                               "cross": (7, 9)}
    assert cfg.state_parts == {"ssm1": (3, (4, 128)), "conv1": (3, (3, 128))}
    got = lm_toy.jitted(T.forward, cfg)(params, jnp.asarray([seq]))[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL,
                               rtol=TOL)
    assert 0.5 < float(np.std(ref_logits)) < 2.0    # unit-spread logits


def test_bfloat16_would_fail(params, seq, ref_logits):
    """The same forward with weights and activations in bfloat16 misses
    the reference by far more than the tolerance holds float32 to."""
    cfg = yoco_cfg(dtype=jnp.bfloat16)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = lm_toy.jitted(T.forward, cfg)(low, jnp.asarray([seq]))[0]
    miss = float(np.max(np.abs(np.asarray(got, np.float32) - ref_logits)))
    assert miss > 50 * TOL, miss


def test_the_memory_is_the_scan_before_its_gate(ref, weights, params):
    """An S layer's mixer: the output is the reference's, and what it
    hands on (``carry``) is the reference's ``y`` — the scan with the D
    skip, BEFORE the gate by silu(z) — not the gated value."""
    from paddle_tpu.ops import mamba2

    cfg = yoco_cfg()
    i = 8       # the last S layer: the one the G layers read
    h = jax.random.normal(jax.random.key(3), (2, 21, 64))
    one = jax.jit(lambda l, x: ref.mamba1_mixer(ref._f32(l), x, M))
    want = [one(weights["layers"][i], h[b]) for b in range(2)]

    def mixer(h, layer):
        return T._mamba1_mixer(
            cfg, h, layer,
            lambda x, w, b: mamba2.conv_prefill(x, w, b)[0],
            lambda *a: mamba1.scan_prefill(*a, chunk=4)[0])

    out, mem = jax.jit(mixer)(h, params["blocks"][i])
    for b in range(2):
        np.testing.assert_allclose(out[b], want[b][0], atol=TOL, rtol=TOL)
        np.testing.assert_allclose(mem[b], want[b][1], atol=TOL, rtol=TOL)
    gated = np.asarray(mem) * np.asarray(jax.nn.silu(
        (h @ params["blocks"][i]["in_proj"])[..., 128:]))
    assert np.abs(gated - np.asarray(mem)).max() > 0.1


def test_chunked_scan_equals_its_own_step_and_hands_its_state_on():
    """``scan_prefill`` (chunks of 4, a padded row among them) against
    ``scan_step`` token by token; the state it hands back is the state at
    each row's last VALID token, and a step from there continues it."""
    rng = np.random.default_rng(0)
    b, t, d, n = 2, 11, 24, 4
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, bm, cm, dv = f(b, t, d), f(b, t, n), f(b, t, n), f(d)
    dt = jax.nn.softplus(f(b, t, d))
    a = -jnp.exp(f(n, d))
    lens = jnp.asarray([11, 6])
    y, last = jax.jit(lambda *v: mamba1.scan_prefill(
        *v, seq_lens=lens, chunk=4))(x, dt, a, bm, cm, dv)
    h = jnp.zeros((b, n, d))
    step = jax.jit(mamba1.scan_step)
    for s in range(t):
        y_s, new = step(h, x[:, s], dt[:, s], a, bm[:, s], cm[:, s], dv)
        h = jnp.where((s < lens)[:, None, None], new, h)
        for r in range(b):
            if s < lens[r]:
                np.testing.assert_allclose(y[r, s], y_s[r], atol=1e-5,
                                           rtol=1e-5)
    np.testing.assert_allclose(last, h, atol=1e-5, rtol=1e-5)
    assert mamba1.state_shapes(d, n, 4) == {"ssm1": (n, d), "conv1": (3, d)}


def test_ring_rows_hold_each_rows_last_window():
    """Position p of a row's last ``W`` rests at ring row p mod W; a row
    shorter than the window keeps its positions where they are."""
    x = jnp.arange(2 * 21, dtype=jnp.float32).reshape(2, 21, 1, 1)
    ring = PA.ring_rows(x, jnp.asarray([21, 5]), W)[:, :, 0, 0]
    for p in range(13, 21):
        assert ring[0, p % W] == x[0, p, 0, 0]
    np.testing.assert_array_equal(ring[1, :5], x[1, :5, 0, 0])
    table = PA.window_table(jnp.zeros((2, 2, 7, PS, 128)), W,
                            jnp.asarray([0, 2, 3]),
                            jnp.asarray([True, True, False]))
    assert table.tolist() == [[1, 2], [5, 6], [0, 0]]
    assert PA.window_pool_pages(W, PS, 3) == 7


def test_prefill_narrows_to_the_last_token_and_changes_nothing(params, seq,
                                                               ref_logits):
    """``forward_prefill`` walks the entries behind the full-attention
    layer for each row's last token only; its logits are those of the
    walk that does not narrow (``forward``) and the reference's."""
    cfg = yoco_cfg()
    ids = np.zeros((2, 32), np.int32)
    ids[0, :5], ids[1, :23] = seq[10:15], seq[:23]
    lens = jnp.asarray([5, 23])
    logits, ks, vs, extras = lm_toy.jitted(T.forward_prefill, cfg)(
        params, jnp.asarray(ids), lens)
    whole = lm_toy.jitted(T.forward, cfg)(params, jnp.asarray(ids))
    np.testing.assert_allclose(logits[1], whole[1, 22], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(logits[0], whole[0, 4], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(logits[1], ref_logits[22], atol=TOL, rtol=TOL)
    # ONE cache layer leaves K/V; the rings leave a window a layer
    assert ks.shape == (1, 2, 32, 4, 64)
    assert [a.shape for a in extras["window"]] == [(2, 2, W, 4, 64)] * 2
    assert set(extras["state"]) == {"ssm1", "conv1"}
    assert extras["state"]["ssm1"].shape == (3, 2, 4, 128)


def _engine(params, cfg=None, reg=None, **kw):
    return lm_toy.engine(
        cfg or yoco_cfg(), params, reg or MetricsRegistry("yoco"),
        **{**dict(max_slots=3, page_size=PS, num_pages=40, max_prompt_len=24,
                  max_new_tokens=12, prefill_batch=2), **kw})


def test_engine_cache_equals_the_reference_at_every_position(
        ref, weights, params, monkeypatch):
    """Seven requests through three slots, joining mid-flight: contexts
    to 36 tokens (the ring of 8 wraps four times) and prompts of 1, 3 and
    5 (shorter than the window).  Every request's tokens are the
    reference's greedy tokens, and the logits of EVERY position served
    through pages, rings and state rows are the reference's full forward
    (a recorder around the engine's sampler sees them)."""
    from paddle_tpu.serving import sampling

    seen = []
    sample = sampling.sample_tokens

    def recording(logits, keys, temps):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return sample(logits, keys, temps)

    monkeypatch.setattr(sampling, "sample_tokens", recording)
    cfg = yoco_cfg(max_seq_len=127)     # its own programs: the recorder's
    reg = MetricsRegistry("yoco")
    eng = _engine(params, cfg, reg)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, V, n)]
               for n in (7, 24, 3, 16, 1, 18, 5)]
    news = [6, 12, 5, 2, 9, 6, 12]
    ids = [eng.submit(prompts[0], news[0])]
    eng.step()
    eng.step()                      # request 0 is decoding
    ids += [eng.submit(p, n) for p, n in zip(prompts[1:], news[1:])]
    eng.run_until_idle()
    jax.effects_barrier()
    got = {r.id: r.tokens for r in eng.results()}
    rows = np.concatenate(seen, axis=0)
    for rid, prompt, n in zip(ids, prompts, news):
        assert got[rid] == lm_toy.greedy(ref, weights, M, prompt, n, PAD)
        want = lm_toy.ref_logits(ref, weights, M, prompt + got[rid], PAD)
        for pos in range(len(prompt) - 1, len(prompt) + n - 1):
            near = np.abs(rows - want[pos]).max(axis=1).min()
            assert near < TOL, (rid, pos, near)
    # what the cache holds: ONE growing cache layer, two rings of a run of
    # 2 pages a slot (+ the null page), three layers' float32 state
    cache = eng.cache
    assert cache.k.shape == PA.kv_pool_shape(1, 4, 40, PS, 64)
    assert {n: a.shape for n, a in cache.window.items()} == {
        n: PA.kv_pool_shape(2, 4, 1 + 3 * 2, PS, 64)
        for n in ("window_k", "window_v")}
    assert cache.window["window_k"].dtype == cache.k.dtype
    assert {n: a.shape for n, a in cache.state.items()} == {
        "ssm1": (3, 3, 4, 128), "conv1": (3, 3, 3, 128)}
    assert cache.window_bytes_per_slot == 2 * 2 * W * 4 * 64 * 4
    assert cache.state_bytes_per_slot == 3 * 4 * (4 * 128 + 3 * 128) \
        == reg.get("serve_state_bytes_per_slot").value()
    # one growing cache layer a token: what the gauge says
    assert reg.get("serve_kv_bytes_per_token").value() == 2 * 4 * 64 * 4
    assert reg.get("serve_state_bytes_total").value(kind="mamba1") > 0


def test_a_reused_slot_never_reads_the_request_before(ref, weights, params):
    """ONE slot: a long request (its ring wraps), then a prompt shorter
    than the window in the same slot, whose ring still holds the other's
    rows beyond its own: the tokens are the reference's."""
    eng = _engine(params, max_slots=1, prefill_batch=1)
    rng = np.random.default_rng(4)
    long = [int(t) for t in rng.integers(0, V, 22)]
    short = [int(t) for t in rng.integers(0, V, 3)]
    a, b = eng.generate([long, short], max_new_tokens=9)
    assert a.tokens == lm_toy.greedy(ref, weights, M, long, 9, PAD)
    assert b.tokens == lm_toy.greedy(ref, weights, M, short, 9, PAD)


def test_the_decode_kernel_writes_the_rings_and_the_cache(ref, weights,
                                                           params):
    """The same two requests through a decode program built on the
    (interpreted) Mosaic kernel: the cache layer and both rings — a ring
    that wraps: the token lands behind the rows the step reads — are
    written inside ``paged_attention_decode``, the cross layers read what
    it wrote, the tokens are the reference's, and the engine counts three
    writes a step under ``kernel``."""
    reg = MetricsRegistry("yoco_kernel")
    eng = _engine(params, reg=reg, max_slots=1, prefill_batch=1,
                  attn_impl="kernel")
    rng = np.random.default_rng(4)
    long = [int(t) for t in rng.integers(0, V, 22)]
    short = [int(t) for t in rng.integers(0, V, 3)]
    a, b = eng.generate([long, short], max_new_tokens=9)
    assert a.tokens == lm_toy.greedy(ref, weights, M, long, 9, PAD)
    assert b.tokens == lm_toy.greedy(ref, weights, M, short, 9, PAD)
    steps = reg.get("serve_decode_step_ms").summary()["count"]
    writes = reg.get("serve_decode_kv_writes_total")
    assert steps and writes.value(path="kernel") == 3 * steps
    assert writes.value(path="scatter") == 0


def test_spans_and_counters_say_what_a_pass_reads(params):
    """``serve_decode`` says the layer-reads of the growing cache, the
    window layers and the ring rows read; ``serve_prefill`` the positions
    that walked the cross-decoder (its live rows: the pass narrows)."""
    reg = MetricsRegistry("yoco_spans")
    rng = np.random.default_rng(6)
    prompts = [[int(t) for t in rng.integers(0, V, n)] for n in (20, 4, 11)]

    def serve():
        return _engine(params, reg=reg).generate(prompts, max_new_tokens=5)

    _, spans = lm_toy.traced(serve)
    for s in spans["serve_prefill"]:
        assert s.args["cross_positions"] == s.args["batch"] \
            < s.args["prompt_tokens"]
    shared = ring = 0
    for s in spans["serve_decode"]:
        a = s.args
        assert (a["kv_reads"], a["window_layers"], a["cache_layers"],
                a["kv_heads"]) == (3, 2, 1, 4)
        assert a["shared_kv_bytes"] == 3 * a["context_tokens"] * 2 * 4 * 64 * 4
        assert a["batch"] <= a["window_tokens"] <= min(
            a["context_tokens"], W * a["batch"])
        assert a["state_slots"] == a["batch"]
        shared += a["shared_kv_bytes"]
        ring += a["window_tokens"]
    assert reg.get("serve_shared_kv_bytes_total").value() == shared > 0
    assert reg.get("serve_window_tokens_total").value() == ring > 0


def test_the_pairs_split_through_the_decode_kernel_at_the_published_heads():
    """40 query heads over 20 K/V heads of 64, pages of 16, the Mosaic
    decode kernel interpreted: after ``_diff_order`` the cache's grouped
    map IS the pairing — query pair p (heads 2p, 2p+1) reads K/V pair p //
    2, q1 against k1 and q2 against k2, each over [v1 | v2] — and it is
    NOT what the grouped map gives on the published order."""
    cfg = T.TransformerConfig(
        vocab_size=8, num_layers=2, num_heads=40, kv_heads=20, head_dim=64,
        embed_dim=32, mlp_dim=8, pattern="*-", positions="none",
        attn_diff=True, mlp="swiglu")
    rng = np.random.default_rng(1)
    b, ctx, ps = 2, (37, 9), 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f(b, 40, 64), f(b, 48, 20, 64), f(b, 48, 20, 64)
    kc, vc = PA.init_kv_pages(1, 20, 7, ps, 64)
    table = jnp.arange(1, 7, dtype=jnp.int32).reshape(2, 3)
    lens = jnp.asarray(ctx)
    kc, vc = PA.write_prefill_kv(kc, vc, k[None], v[None], table, lens)
    run = jax.jit(lambda q, impl: PA.ragged_paged_attention(
        T._diff_order(cfg, q), kc, vc, 0, table, lens, impl=impl,
        interpret=True, kv_heads=20, wide_v=True), static_argnums=1)
    got = np.asarray(run(q, "kernel")).reshape(b, 10, 2, 2, 128)
    np.testing.assert_allclose(got.reshape(b, 40, 128), run(q, "reference"),
                               atol=2e-5, rtol=2e-5)
    for row in range(b):
        n = ctx[row]
        for p in (0, 1, 2, 7, 19):
            r = p // 2
            for s in (0, 1):    # q1 | q2
                sc = np.asarray(q[row, 2 * p + s] @ k[row, :n, 2 * r + s].T
                                ) / 8.0
                w = np.exp(sc - sc.max())
                w /= w.sum()
                want = np.concatenate([w @ np.asarray(v[row, :n, 2 * r]),
                                       w @ np.asarray(v[row, :n, 2 * r + 1])])
                np.testing.assert_allclose(got[row, r, s, p % 2], want,
                                           atol=2e-5, rtol=2e-5)
    # the published order through the grouped map h // 2 pairs q2 of pair
    # 0 with k1: not the same numbers
    plain = np.asarray(jax.jit(lambda q: PA.ragged_paged_attention(
        q, kc, vc, 0, table, lens, impl="reference", kv_heads=20,
        wide_v=True))(q))
    assert np.abs(plain - got.reshape(b, 40, 128)).max() > 0.1


@pytest.mark.parametrize("change, error, says", [
    (dict(pattern="G-S-W-*-X-" * 2), ValueError, "'G' layer"),
    (dict(pattern="S-W-X-*-G-" * 2), ValueError, "'X' layer"),
    (dict(attn_window=0), ValueError, "attn_window"),
    (dict(pattern="S-*-S-*-S-*-G-X-G-X-"), ValueError, "attn_window"),
    (dict(mamba1_dt_rank=0), ValueError, "'S' layer"),
    (dict(positions="rotary"), NotImplementedError, "position signal"),
    (dict(kv_heads=8, num_heads=8, head_dim=16), NotImplementedError,
     "lane group"),
    (dict(block_len=4, mask_id=0), NotImplementedError, "block_len"),
    (dict(cca_taps=(2, 2)), NotImplementedError, "cca_taps"),
    (dict(moe_router_hidden=8, moe_router="softmax_topk", moe_experts=4,
          moe_top_k=1), NotImplementedError, "moe_router_hidden"),
    (dict(pattern=None, num_layers=2, attn_window=0), NotImplementedError,
     "attn_diff without a layer pattern"),
])
def test_post_init_refuses_by_name(change, error, says):
    with pytest.raises(error, match=says):
        yoco_cfg(**change)


@pytest.mark.parametrize("serving", [dict(prefix_cache=True),
                                     dict(prefill_chunk_tokens=8)])
def test_engine_refuses_incremental_prefill_beside_a_ring(params, serving):
    with pytest.raises(NotImplementedError, match="window .* or cross"):
        _engine(params, **serving)
    cfg = yoco_cfg()
    with pytest.raises(NotImplementedError, match="'W' or 'X' layers"):
        T.forward_prefill_chunk(
            cfg, params, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), int),
            jnp.ones((1,), int), jnp.zeros((1, 2), jnp.int32), None, None)


def test_the_memory_report_counts_both_pools(params):
    from paddle_tpu.analysis.memory import serving_memory_report

    cfg = yoco_cfg()
    sv = ServingConfig(max_slots=3, page_size=PS, num_pages=40,
                       max_prompt_len=24, max_new_tokens=12)
    rep = serving_memory_report(cfg, sv, params)
    assert rep["kv_pool_bytes"] == 2 * 1 * 2 * 40 * PS * 128 * 4
    assert rep["window_pool_bytes"] == 2 * 2 * 2 * (1 + 3 * 2) * PS * 128 * 4
    assert rep["state_pool_bytes"] == 3 * 3 * 4 * (4 * 128 + 3 * 128)
    assert rep["total_bytes"] == sum(rep[k] for k in (
        "kv_pool_bytes", "window_pool_bytes", "state_pool_bytes",
        "params_bytes"))
    # a window that is not whole pages is refused where the ring is made
    with pytest.raises(Exception, match="whole pages"):
        ServingEngine(cfg, params, ServingConfig(
            max_slots=1, page_size=3, num_pages=40, max_prompt_len=24,
            max_new_tokens=12))
