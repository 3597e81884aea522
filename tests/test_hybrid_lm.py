"""A stack of layers of several kinds — Mamba-2 mixers, sigmoid-routed
experts of which this device holds a share, attention with fewer K/V
heads than query heads — each ONE mixer behind a pre-norm and a residual
add (``TransformerConfig.pattern``), against the plain float32 reference
``benchmarks/references/nemotron_h.py`` on seeded random weights at a toy
size: every mixer alone, the full forward, prefill + decode through the
paged cache AND the recurrent-state pool, the engine under requests that
join mid-flight.  (The scan alone: ``test_mamba2.py``; the experts'
shares: ``test_moe_arrangement.py``.)

Tolerances.  Program and reference both compute in float32 here (the CPU
backend's dots are exact float32), so they differ by the order of
summation only: logits of unit scale agree to ``TOL`` = 2e-4.  bfloat16
in the program's place moves them by 1e-2 or more
(``test_bf16_would_fail``), so the tolerance tells the stated precision
from the one below it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import mamba2
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.parallel import moe
from paddle_tpu.serving import ServingConfig
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy
from lm_toy import PS, REPO

TOL = 2e-4
PAD = 32    # the reference's one compiled length: 30 positions, 22 served
M = dict(vocab_size=97, num_layers=6, num_heads=4, kv_heads=2, head_dim=8,
         embed_dim=32, mlp_dim=24, max_seq_len=128, norm="rms",
         norm_eps=1e-5, positions="none", mlp="relu2", tie_embeddings=False,
         pattern="ME*EM*", moe_experts=16, moe_router="sigmoid", moe_top_k=3,
         moe_scale=2.5, moe_shared_dim=40, moe_held=[0, 8], mamba_heads=4,
         mamba_head_dim=8, mamba_state=16, mamba_groups=2, mamba_conv=4,
         mamba_chunk=8)


# one serving shape for every engine of this file
SERVING = dict(max_slots=2, page_size=PS, num_pages=24, max_prompt_len=16,
               max_new_tokens=6, prefill_batch=2)


def hybrid_cfg(**kw):
    return T.TransformerConfig(**{**M, "remat": False, **kw})


ref, weights, params, seq, ref_logits = lm_toy.fixtures(
    "nemotron_h", M, 11, seq_len=30, pad=PAD)


def _layer(params, kind):
    i = M["pattern"].index(kind)
    return i, params["blocks"][i]


# -- the mixers, one at a time --------------------------------------------------


@pytest.mark.parametrize("kind", ["*", "E", "M"])
def test_each_mixer_equals_the_reference(kind, ref, weights, params):
    cfg = hybrid_cfg()
    i, layer = _layer(params, kind)
    h = jax.random.normal(jax.random.key(3), (2, 21, 32))
    one = jax.jit(lambda l, x: ref._MIXERS[ref.KINDS[kind]](l, x, M))
    want = np.stack([np.asarray(one(weights["layers"][i], h[b]))
                     for b in range(2)])

    def mixer(h, layer):
        if kind == "*":
            q, k, v = T._qkv(cfg, h, layer, None)
            return T._attention(cfg, q, k, v, None).reshape(
                2, 21, -1) @ layer["wo"]
        if kind == "E":
            return moe.moe_routed(layer, h, cfg.routed)[0]
        return T._mamba_mixer(
            cfg, h, layer,
            lambda x, w, b: mamba2.conv_prefill(x, w, b)[0],
            lambda *a: mamba2.ssd_prefill(*a, chunk=8)[0])

    got = jax.jit(mixer)(h, layer)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=TOL)


def test_forward_equals_the_reference(params, seq, ref_logits):
    got = lm_toy.jitted(T.forward, hybrid_cfg())(params,
                                                 jnp.asarray([seq]))[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL,
                               rtol=TOL)


def test_bf16_would_fail(ref, weights, seq, ref_logits):
    """The program in bfloat16 misses the tolerance by far: it tells the
    stated precision from the one below."""
    cfg = hybrid_cfg(dtype=jnp.bfloat16)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                       ref.program_tree(weights))
    got = lm_toy.jitted(T.forward, cfg)(low, jnp.asarray([seq]))[0].astype(
        jnp.float32)
    assert float(np.max(np.abs(np.asarray(got) - ref_logits))) > 50 * TOL


# -- the experts -------------------------------------------------------------------


@pytest.mark.parametrize("lens", [[15, 9, 1, 0], [3, 0, 2, 0],
                                  [15, 15, 15, 15]])
def test_sorted_and_masked_arrangements_agree(lens, params, monkeypatch):
    """Above ``DENSE_MAX_TOKENS`` tokens go sorted through the grouped
    product; at or below, every held expert runs over every row — over
    the live rows gathered into the smallest bucket that holds them when
    the pass is padded (25, 5 and 60 live rows of 60 against buckets of
    8 and 32): one semantics, counts included."""
    cfg = hybrid_cfg()
    _, layer = _layer(params, "E")
    h = jax.random.normal(jax.random.key(6), (4, 15, 32))
    live = jnp.arange(15)[None, :] < jnp.asarray(lens)[:, None]
    # a closure a setting: the constants are read when it is traced
    routed = lambda: jax.jit(
        lambda: moe.moe_routed(layer, h, cfg.routed, live))()
    monkeypatch.setattr(moe, "DENSE_BUCKETS", ())
    dense, c_dense = routed()
    monkeypatch.setattr(moe, "DENSE_BUCKETS", (8, 32))
    bucketed, c_bucketed = routed()
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)
    sorted_, c_sorted = routed()
    for got, counts in ((bucketed, c_bucketed), (sorted_, c_sorted)):
        np.testing.assert_allclose(np.asarray(got)[np.asarray(live)],
                                   np.asarray(dense)[np.asarray(live)],
                                   atol=TOL, rtol=TOL)
        np.testing.assert_array_equal(np.asarray(c_dense), np.asarray(counts))
    if lens != [15, 9, 1, 0]:
        return
    held, absent, touched, busiest = (int(c) for c in c_dense)
    assert held + absent == 25 * 3 and 1 <= busiest <= 25
    assert 1 <= touched <= 8


def test_the_correction_bias_chooses_and_does_not_weigh(params):
    cfg = hybrid_cfg()
    _, layer = _layer(params, "E")
    h = jax.random.normal(jax.random.key(7), (11, 32))
    idx, w = moe.route_topk(h, layer["router"], layer["router_bias"],
                            cfg.routed)
    s = jax.nn.sigmoid(h @ layer["router"])
    want_idx = np.argsort(-np.asarray(s + layer["router_bias"]), -1)[:, :3]
    assert {tuple(sorted(r)) for r in np.asarray(idx).tolist()} == {
        tuple(sorted(r)) for r in want_idx.tolist()}
    chosen = np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)


# -- pages and state ------------------------------------------------------------


@pytest.mark.parametrize("attn_impl", ["reference", "kernel"])
def test_pages_and_state_equal_the_reference_at_every_position(
        attn_impl, params, seq, ref_logits):
    """Prefill 12 tokens of a padded 16 (row 1 of a batch whose row 0 is
    another prompt), put K/V in pages and the state in slot rows, then
    decode the rest token by token: every position's logits are the
    reference's full forward.  Query heads 4 over K/V heads 2, through
    the jnp route and the interpreted kernel."""
    ks, _, _ = lm_toy.walk_positions(hybrid_cfg(), params, seq, ref_logits, 12,
                                     16, attn_impl, TOL)
    assert ks.shape == (2, 2, 16, 2, 8)    # cache layers x B x T x KV x Dh


def test_prefill_state_is_the_references_state(ref, weights, params, seq):
    cfg = hybrid_cfg()
    ids = np.zeros((1, 16), np.int32)
    ids[0, :9] = seq[:9]
    _, _, _, extras = lm_toy.jitted(T.forward_prefill, cfg)(
        params, jnp.asarray(ids), jnp.asarray([9]))
    i = M["pattern"].index("M")    # the first layer: its input is the embedding
    x = weights["wte"][jnp.asarray(seq[:9])]
    h = ref._rms(x, weights["layers"][i]["g"], M["norm_eps"])
    with jax.default_matmul_precision("highest"):
        _, ssm, conv = ref.mamba_mixer(weights["layers"][i], h, M,
                                       with_state=True)
    np.testing.assert_allclose(np.asarray(extras["state"]["ssm"][0, 0]),
                               np.asarray(ssm), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(extras["state"]["conv"][0, 0]),
                               np.asarray(conv), atol=TOL, rtol=TOL)


# -- the engine ---------------------------------------------------------------------


def test_engine_serves_the_reference_greedy_tokens(ref, weights, params):
    """Requests join mid-flight, finish at different steps, and a slot is
    reused after retirement (5 requests through 2 slots): every request's
    tokens are the reference's greedy tokens, so a reused slot's state
    row was written whole by its prefill and an idle row's state never
    moved."""
    reg = MetricsRegistry("hybrid")
    eng = lm_toy.engine(hybrid_cfg(), params, reg, **SERVING)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 97, n)]
               for n in (7, 12, 3, 16, 1)]
    news = [6, 3, 5, 2, 4]
    ids = [eng.submit(prompts[0], news[0])]
    eng.step()
    eng.step()                      # request 0 is decoding
    ids += [eng.submit(p, n) for p, n in zip(prompts[1:], news[1:])]
    eng.run_until_idle()
    got = {r.id: r.tokens for r in eng.results()}
    for rid, prompt, n in zip(ids, prompts, news):
        assert got[rid] == lm_toy.greedy(ref, weights, M, prompt, n, PAD)
    cfg = eng.cfg
    assert eng.cache.k.shape == PA.kv_pool_shape(2, 2, 24, PS, 8)
    assert eng.cache.state["ssm"].shape == (2, 2, 4, 8, 16)
    assert eng.cache.state["conv"].shape == (2, 2, 3, 4 * 8 + 2 * 2 * 16)
    assert eng.kv_bytes_per_token == 2 * 2 * 2 * 8 * 4 == reg.get(
        "serve_kv_bytes_per_token").value()
    per_slot = 2 * 4 * (4 * 8 * 16 + 3 * 96)
    assert reg.get("serve_state_bytes_per_slot").value() == per_slot \
        == eng.cache.state_bytes_per_slot
    tokens = sum(len(p) for p in prompts) + sum(n - 1 for n in news)
    routed = reg.get("serve_moe_assignments_total")
    assert (routed.value(where="held") + routed.value(where="absent")
            == tokens * cfg.moe_top_k * cfg.pattern.count("E"))
    assert reg.get("serve_moe_experts_touched_total").value() > 0
    assert reg.get("serve_moe_load_max_over_mean").value() >= 1.0


@pytest.mark.parametrize("route", ["reference", "kernel"])
def test_the_decode_recurrence_serves_the_references_tokens_by_either_route(
        route, ref, monkeypatch):
    """At a state of whole lanes the decode step's recurrence is one
    kernel a layer on a TPU (``ops/pallas/ssd.py``; interpreted here, two
    B/C groups, the pool's row a number of the unrolled walk): by it as by
    ``ssd_step`` the engine serves the reference's greedy tokens through a
    reused slot, and the census says which ran."""
    m = {**M, "mamba_state": 128}
    weights = lm_toy.draw(ref, m, 11)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 97, n)] for n in (7, 12, 3, 1)]
    news = [6, 3, 5, 4]
    got, routes = lm_toy.serve_on_route(
        monkeypatch, route, hybrid_cfg(mamba_state=128),
        ref.program_tree(weights), prompts, news, **SERVING)
    for tokens, prompt, n in zip(got, prompts, news):
        assert tokens == lm_toy.greedy(ref, weights, m, prompt, n, PAD)
    assert {k: v for k, v in routes.items() if k[0] == "ssd_step"} \
        == {("ssd_step", route): M["pattern"].count("M")}


def test_decode_span_says_what_the_step_touched(params):
    _, spans = lm_toy.traced(lambda: lm_toy.engine(
        hybrid_cfg(), params, MetricsRegistry("s"), **SERVING).generate(
        [[1, 2, 3], [4, 5]], max_new_tokens=3))
    assert spans["serve_decode"]
    for s in spans["serve_decode"]:
        a = s.args
        assert a["kv_heads"] == 2 and a["cache_layers"] == 2
        assert a["state_slots"] == a["batch"] == 2
        assert 0 < a["moe_assignments"] <= 2 * 3 * 2
        assert 0 < a["experts_touched"] <= a["moe_assignments"]
    assert spans["serve_prefill"][0].args["moe_assignments"] > 0


def test_compiled_decode_updates_pages_and_state_in_place():
    """The structure of the compiled decode program, pools donated: the
    K/V pools and both state pools are aliased input to output, whole.
    (The CPU backend keeps each layer's new rows and their masked merge
    as temporaries of their own, about two state layers each; on the chip
    they fuse: PERF.md section 4's AOT lines, 28 MB of temp beside
    0.93 GB of pools.)"""
    cfg = hybrid_cfg(mamba_state=128, mamba_head_dim=64)
    params = T.init_params(cfg, jax.random.key(0))
    slots = 16
    kc, vc, state = lm_toy.pools(cfg, pages=64, slots=slots)
    pools = sum(a.size * a.dtype.itemsize
                for a in (kc, vc, *state.values()))
    i32 = lambda *shape: jnp.ones(shape, jnp.int32)
    fn = lambda p, kc, vc, state, ids, pos, lens, pt: T.forward_decode(
        cfg, p, ids, pos, lens, pt, kc, vc, state=state)
    mem = jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
        params, kc, vc, state, i32(slots), i32(slots), i32(slots),
        i32(slots, 4)).compile().memory_analysis()
    assert mem.alias_size_in_bytes == pools
    assert mem.temp_size_in_bytes < 3 * pools


# -- what it cannot do, what it still is -------------------------------------------


@pytest.mark.parametrize("serving, named", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(prefill_chunk_tokens=4), "chunk"),
])
def test_state_beside_incremental_prefill_raises_by_name(serving, named,
                                                         params):
    with pytest.raises(NotImplementedError) as e:
        lm_toy.engine(hybrid_cfg(), params, **SERVING, **serving)
    assert named in str(e.value)
    with pytest.raises(NotImplementedError):
        T.forward_prefill_chunk(hybrid_cfg(), params, jnp.zeros((1, 4), int),
                                jnp.zeros(1, int), jnp.ones(1, int),
                                jnp.zeros((1, 2), int), None, None)


@pytest.mark.parametrize("bad, err", [
    (dict(pattern="ME*"), ValueError),                    # not num_layers long
    (dict(pattern="ME*EMx"), ValueError),                 # unknown kind
    (dict(moe_router="softmax"), ValueError),             # E needs dropless
    (dict(mamba_heads=0), ValueError),
    (dict(kv_heads=3), ValueError),                       # 4 % 3
    (dict(moe_held=(8, 20)), ValueError),                 # outside [0, 16)
    # gated experts run since PR 32, beside a (gated) shared expert since
    # PR 42 (tests/test_kda_lm.py); a pattern under a sandwich norm does not
    (dict(norm_sandwich=True), NotImplementedError),
    (dict(loop_steps=2), NotImplementedError),
    (dict(positions="sinusoid"), ValueError),
])
def test_what_a_pattern_cannot_be_raises(bad, err):
    with pytest.raises(err):
        hybrid_cfg(**bad)


def test_no_sharded_layout_is_written_for_a_pattern():
    with pytest.raises(NotImplementedError) as e:
        T.param_shardings(hybrid_cfg())
    assert "expert exchange" in str(e.value)


def test_pattern_without_state_serves_chunked_and_cached(ref):
    """Attention, routed experts and the dense MLP alone keep no state
    beside pages: chunked prefill and the prefix cache serve them, and
    the tokens are the full forward's."""
    cfg = hybrid_cfg(pattern="*E-*", num_layers=4)
    params = T.init_params(cfg, jax.random.key(1))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3], [3, 1, 4, 1, 5, 9, 2, 6, 8]]
    for serving in (dict(prefill_chunk_tokens=4), dict(prefix_cache=True)):
        eng = lm_toy.engine(cfg, params, MetricsRegistry("c"), **SERVING,
                            **serving)
        for prompt, res in zip(prompts, eng.generate(prompts, 3)):
            assert res.tokens == lm_toy.forward_argmax(
                cfg, params, prompt, res.tokens, 16)


def test_homogeneous_block_takes_kv_heads_and_routed_experts():
    """The (attention, feed-forward) block of a homogeneous stack takes
    the same parts: fewer K/V heads and dropless routed experts in the
    feed-forward's place, under the layer scan, through the engine."""
    cfg = T.TransformerConfig(
        vocab_size=61, num_layers=2, num_heads=4, kv_heads=1, embed_dim=32,
        mlp_dim=16, max_seq_len=64, remat=False, mlp="relu2",
        moe_experts=4, moe_router="sigmoid", moe_top_k=2)
    params = T.init_params(cfg, jax.random.key(2))
    assert params["blocks"]["wk"].shape == (2, 32, 8)
    eng = lm_toy.engine(cfg, params, MetricsRegistry("h"), **SERVING)
    prompt = [5, 17, 3, 9]
    res = eng.generate([prompt], 4)[0]
    assert res.tokens == lm_toy.forward_argmax(cfg, params, prompt,
                                               res.tokens, 16)


def test_defaults_are_still_the_gpt2_block():
    """With no new field set the config is the block it was — the same
    leaves, ``kv_heads == num_heads``, no pattern, no state — and naming
    the defaults names the same config (one memo key, one set of compiled
    programs).  ``tests/test_looped_lm.py`` holds the default block to
    the GPT-2 forward as it was, bit for bit."""
    kw = dict(vocab_size=50, num_layers=2, num_heads=2, embed_dim=16,
              mlp_dim=32, max_seq_len=32, remat=False)
    cfg = T.TransformerConfig(**kw)
    assert (cfg.kv_heads, cfg.pattern, cfg.state_layers, cfg.cache_layers) \
        == (2, None, 0, 2)
    named = T.TransformerConfig(**kw, kv_heads=2, moe_router="softmax",
                                pattern=None, moe_held=None)
    assert named == cfg and hash(named) == hash(cfg)
    p = T.init_params(cfg, jax.random.key(0))
    assert sorted(p["blocks"]) == ["b_in", "b_out", "ln1_b", "ln1_g", "ln2_b",
                                   "ln2_g", "w_in", "w_out", "wk", "wo", "wq",
                                   "wv"]
    eng = lm_toy.engine(cfg, p, MetricsRegistry("d"), **SERVING)
    assert eng.cache.state == {} and eng.cache.state_bytes_per_slot == 0
    res = eng.generate([[3, 7, 1]], 3)[0]
    assert res.tokens == lm_toy.forward_argmax(cfg, p, [3, 7, 1], res.tokens,
                                               16)


def test_parameter_counts():
    """The published widths, this chip's share: per layer by kind and
    whole (ISSUE 30's arithmetic), from the configuration file."""
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        conf = json.load(f)
    m = {k: v for k, v in conf["model"].items() if k != "init"}
    cfg = T.TransformerConfig(**m, dtype=jnp.bfloat16, remat=False)
    tree = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    by_kind = {c: count(tree["blocks"][cfg.pattern.index(c)]) for c in "ME*"}
    assert by_kind == {"M": 38_744_896, "E": 658_885_376, "*": 23_399_040}
    assert count(tree) == 3_926_018_560 == conf["parameters"]
    assert (cfg.cache_layers, cfg.state_layers) == (2, 6)
    per_slot = 4 * 6 * sum(int(np.prod(s)) for s in cfg.state_shapes.values())
    assert per_slot == 6 * 2_170_880


def test_memory_report_and_servable_take_kv_heads_and_state(tmp_path, params):
    from paddle_tpu.analysis.memory import serving_memory_report
    from paddle_tpu.serving.export import export_servable, load_servable

    cfg = hybrid_cfg()
    scfg = ServingConfig(page_size=PS, num_pages=10, max_slots=3)
    rep = serving_memory_report(cfg, scfg)
    assert rep["kv_pool_bytes"] == 2 * 2 * 2 * 10 * PS * 8 * 4   # 2 KV heads
    assert rep["state_pool_bytes"] == 2 * 3 * 4 * (4 * 8 * 16 + 3 * 96)
    assert rep["total_bytes"] == rep["kv_pool_bytes"] + rep["state_pool_bytes"]
    export_servable(str(tmp_path / "s"), cfg, params)
    cfg2, params2 = load_servable(str(tmp_path / "s"))
    assert cfg2 == cfg and cfg2.moe_held == (0, 8)
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    np.testing.assert_array_equal(np.asarray(params2["blocks"][1]["w_in"]),
                                  np.asarray(params["blocks"][1]["w_in"]))


def test_serving_cli_serves_a_hybrid_stack(monkeypatch, capsys):
    """``python -m paddle_tpu.serving --random --model_json`` builds the
    pattern from the JSON's fields and serves the greedy tokens of the
    same seeded weights' full forward."""
    parts = {k: v for k, v in M.items() if k not in (
        "vocab_size", "num_layers", "num_heads", "embed_dim", "mlp_dim",
        "max_seq_len")}
    lm_toy.cli_serves_the_forward(monkeypatch, capsys, 97, 6, parts)
