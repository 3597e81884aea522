"""Attention in a compressed latent with a convolution over the sequence
and a shifted value (``TransformerConfig.cca_taps``), rotary on part of a
head (``rope_fraction``), top-1 experts chosen by an MLP router that
carries its state from layer to layer (``moe_router_hidden``,
``moe_renorm``) and scaled residual adds (``residual_scale``), against the
plain float32 reference ``benchmarks/references/zaya.py`` on seeded random
weights at a toy size: every mixer alone, prefill + decode through the
paged cache AND the per-slot state an attention layer keeps beside its
pages, the engine under requests that join mid-flight and reuse slots,
what the engine refuses, and that the new fields at their defaults draw
the weights they always drew.

Tolerances.  Program and reference both compute in float32 here (the CPU
backend's dots are exact float32), so they differ by the order of
summation only: logits of unit scale agree to ``TOL`` = 1e-4.  bfloat16
in the program's place moves them by 1e-2 or more.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import cca
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.parallel import moe
from paddle_tpu.serving import ServingConfig
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy
from lm_toy import PS, REPO

TOL = 1e-4
PAD = 32    # the reference's one compiled length: 30 positions, 22 served
M = dict(vocab_size=97, num_layers=6, num_heads=4, kv_heads=2, head_dim=8,
         embed_dim=32, mlp_dim=24, max_seq_len=128, norm="rms",
         norm_eps=1e-5, positions="rotary", rope_theta=5e6,
         rope_fraction=0.5, mlp="swiglu", tie_embeddings=True,
         pattern="*E*E*E", cca_taps=[2, 2], residual_scale=True,
         moe_experts=8, moe_router="softmax_topk", moe_top_k=1,
         moe_renorm=False, moe_router_hidden=12,
         init={"beta_std": 0.05, "temp_mean": 3.0})
FIELDS = {k: v for k, v in M.items() if k != "init"}


def cca_cfg(**kw):
    return T.TransformerConfig(**{**FIELDS, "remat": False, **kw})


ref, weights, params, seq, ref_logits = lm_toy.fixtures(
    "zaya", M, 11, seq_len=30, pad=PAD)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "references", "zaya.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]


# -- the mixers, one at a time --------------------------------------------------


def _prefill_window(seq_lens=None):
    kept = {}

    def window(part, x, n):
        win, kept[part] = cca.window_prefill(x, n, seq_lens)
        return win

    return window, kept


def test_cca_qkv_equals_the_reference(ref, weights, params):
    """q, k, v of one attention sublayer over two sequences: both
    convolutions, the mean added back, the norms and k's temperature,
    rotary on the first half of each head, the shifted half of v."""
    cfg = cca_cfg()
    h = jax.random.normal(jax.random.key(3), (2, 21, 32))
    rope = T._rope_table(cfg, jnp.arange(21)[None])

    def qkv(h, layer):      # what the window keeps rides out with q, k, v
        window, kept = _prefill_window()
        return T._cca_qkv(cfg, h, layer, rope, window), kept

    got, kept = jax.jit(qkv)(h, params["blocks"][0])
    l = ref._f32(weights["layers"][0]["attn"])
    one = jax.jit(lambda l, x: ref.cca_qkv(l, x, M))
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            want = one(l, h[b])
            for g, w in zip(got, want):
                np.testing.assert_allclose(np.asarray(g[b]), np.asarray(w),
                                           atol=TOL, rtol=TOL)
    assert {n: v.shape for n, v in kept.items()} == {
        "cca_u": (2, 1, 48), "cca_c": (2, 1, 48), "cca_v": (2, 1, 8)}
    assert cfg.state_kinds == {"attn": (3, {
        "cca_u": (1, 48), "cca_c": (1, 48), "cca_v": (1, 8)})}
    assert cfg.state_layers == 3 == cfg.cache_layers


def test_partial_rotary_is_not_full_rotary(params):
    """Half a head rotated: the other half of q is what it was, and a
    full rotation gives another q."""
    h = jax.random.normal(jax.random.key(3), (1, 9, 32))
    qs = {}
    for frac in (0.5, 1.0):
        cfg = cca_cfg(rope_fraction=frac)
        rope = T._rope_table(cfg, jnp.arange(9)[None])
        assert rope[0].shape[-1] == int(8 * frac)
        qs[frac] = np.asarray(T._cca_qkv(
            cfg, h, params["blocks"][0], rope, _prefill_window()[0])[0])
    none = np.asarray(T._cca_qkv(cca_cfg(positions="none"), h,
                                 params["blocks"][0], None,
                                 _prefill_window()[0])[0])
    np.testing.assert_array_equal(qs[0.5][..., 4:], none[..., 4:])
    assert np.abs(qs[0.5][:, 1:, :, :4] - none[:, 1:, :, :4]).max() > 0.1
    assert np.abs(qs[1.0][:, 1:, :, 4:] - none[:, 1:, :, 4:]).max() > 0.1
    with pytest.raises(ValueError, match="rope_fraction"):
        cca_cfg(rope_fraction=0.4)


def test_router_carries_its_state_from_layer_to_layer(ref, weights, params):
    """The second routed layer's choice and weight are the reference's
    GIVEN the first layer's router state, and another with that state
    zeroed: the carry is read."""
    cfg = cca_cfg()
    h0, h1 = jax.random.normal(jax.random.key(4), (2, 40, 32))
    l0, l1 = (weights["layers"][i]["moe"] for i in (0, 1))
    p0, p1 = (params["blocks"][i] for i in (1, 3))
    zero = jnp.zeros((40, 12))
    with jax.default_matmul_precision("highest"):
        _, _, r0 = ref.route(l0, h0, zero, M)
        e1, w1, r1 = ref.route(l1, h1, r0, M)
        e1_cold, _, _ = ref.route(l1, h1, zero, M)
    got_r0 = moe.router_state(p0, h0, zero)
    got_r1 = moe.router_state(p1, h1, got_r0)
    np.testing.assert_allclose(np.asarray(got_r1), np.asarray(r1), atol=TOL,
                               rtol=TOL)
    idx, w = moe.route_mlp(got_r1, p1, cfg.routed)
    np.testing.assert_array_equal(np.asarray(idx[:, 0]), np.asarray(e1))
    # the expert weighs its own probability: not renormalised to 1
    np.testing.assert_allclose(np.asarray(w[:, 0]), np.asarray(w1), atol=1e-6)
    assert float(w.max()) < 1.0
    cold, _ = moe.route_mlp(moe.router_state(p1, h1, zero), p1, cfg.routed)
    np.testing.assert_array_equal(np.asarray(cold[:, 0]),
                                  np.asarray(e1_cold))
    assert (np.asarray(cold) != np.asarray(idx)).any()
    # the whole sublayer, and the bias that chooses without weighing
    y, counts = moe.moe_routed(p1, h1, cfg.routed, None, got_r1)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe_mixer(l1, h1, r0, M)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=TOL,
                               rtol=TOL)
    assert int(counts[0]) == 40 and int(counts[1]) == 0
    tilted = {**p1, "router_bias": p1["router_bias"].at[5].add(10.0)}
    idx5, w5 = moe.route_mlp(got_r1, tilted, cfg.routed)
    assert (np.asarray(idx5) == 5).all() and float(w5.max()) < 1.0


def test_full_forward_equals_the_reference(params, seq, ref_logits):
    """Whole sequences through the training-side forward (no cache, no
    state kept): every position's logits."""
    logits = lm_toy.jitted(T.forward, cca_cfg())(params, jnp.asarray([seq]))
    np.testing.assert_allclose(np.asarray(logits[0]), ref_logits, atol=TOL,
                               rtol=TOL)


def test_the_loss_has_a_gradient_through_the_router_carry(params, seq):
    """Training is not refused: the walk is plain jax.numpy, so autodiff
    reaches the depth average's decay and k's temperature."""
    cfg = cca_cfg()
    g = jax.jit(jax.grad(
        lambda p: T.loss_fn(cfg, p, jnp.asarray([seq]))))(params)
    for i, name in ((3, "router_decay"), (1, "router_down"), (0, "cca_temp"),
                    (2, "cca_conv1_w"), (4, "res_y_g")):
        leaf = np.asarray(g["blocks"][i][name])
        assert np.isfinite(leaf).all() and np.abs(leaf).max() > 0, name


# -- pages and state ------------------------------------------------------------


@pytest.mark.parametrize("attn_impl, p_len", [
    ("reference", 10), ("reference", 11), ("reference", 12), ("kernel", 11)])
def test_pages_and_state_equal_the_reference_at_every_position(
        attn_impl, p_len, params, seq, ref_logits):
    """Prefill ``p_len`` tokens of a padded 16 (row 1 of a batch whose
    row 0 is another prompt; the lengths cover every length mod 3, the
    convolutions' reach), put K/V in pages and each attention layer's
    state in slot rows, then decode the rest token by token: every
    position's logits are the reference's full forward.  Row 0 idles
    through the decode and keeps its state."""
    ks, handed, state = lm_toy.walk_positions(
        cca_cfg(), params, seq, ref_logits, p_len, 16, attn_impl, TOL)
    assert ks.shape == (3, 2, 16, 2, 8)    # cache layers x B x T x KV x Dh
    assert handed == set(state) == {"cca_u", "cca_c", "cca_v"}
    assert all(np.abs(np.asarray(v[:, 1])).max() > 0 for v in state.values())


def test_prefill_state_is_the_last_valid_tokens(ref, weights, params, seq):
    """The state a prefill hands back is the reference's streams at each
    row's LAST VALID token, whatever the padding holds."""
    cfg = cca_cfg()
    ids = np.full((1, 16), 7, np.int32)     # padding: a real token's id
    ids[0, :9] = seq[:9]
    _, _, _, extras = lm_toy.jitted(T.forward_prefill, cfg)(
        params, jnp.asarray(ids), jnp.asarray([9]))
    l = ref._f32(weights["layers"][0]["attn"])
    x = weights["wte"][jnp.asarray(seq[:9])]
    h = ref._rms(x, l["g"], M["norm_eps"])
    with jax.default_matmul_precision("highest"):
        u = jnp.concatenate([h @ l["wq"], h @ l["wk"]], axis=-1)
        c0 = l["conv0_a"][0] * u + l["conv0_a"][1] * ref.shift(u, 1) \
            + l["conv0_b"]
        vb = h @ l["wv_b"]
    for part, want in (("cca_u", u), ("cca_c", c0), ("cca_v", vb)):
        np.testing.assert_allclose(np.asarray(extras["state"][part][0, 0, 0]),
                                   np.asarray(want[8]), atol=TOL, rtol=TOL)


# -- the engine ---------------------------------------------------------------------


def _engine(params, slots=3, reg=None, **kw):
    return lm_toy.engine(
        cca_cfg(), params, reg or MetricsRegistry("cca"),
        **{**dict(max_slots=slots, page_size=PS, num_pages=40,
                  max_prompt_len=16, max_new_tokens=6, prefill_batch=2),
           **kw})


def test_engine_serves_the_reference_greedy_tokens(ref, weights, params):
    """Seven requests through three slots, joining mid-flight and
    finishing at different steps, prompts of every length mod 3: every
    request's tokens are the reference's greedy tokens, so a reused
    slot's state rows were written whole by its prefill and an idle row's
    state never moved."""
    reg = MetricsRegistry("cca")
    eng = _engine(params, reg=reg)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 97, n)]
               for n in (7, 12, 3, 16, 1, 8, 5)]
    news = [6, 3, 5, 2, 4, 6, 3]
    ids = [eng.submit(prompts[0], news[0])]
    eng.step()
    eng.step()                      # request 0 is decoding
    ids += [eng.submit(p, n) for p, n in zip(prompts[1:], news[1:])]
    eng.run_until_idle()
    got = {r.id: r.tokens for r in eng.results()}
    for rid, prompt, n in zip(ids, prompts, news):
        assert got[rid] == lm_toy.greedy(ref, weights, M, prompt, n, PAD)
    cfg = eng.cfg
    assert eng.cache.k.shape == PA.kv_pool_shape(3, 2, 40, PS, 8)
    assert {n: v.shape for n, v in eng.cache.state.items()} == {
        "cca_u": (3, 3, 1, 48), "cca_c": (3, 3, 1, 48), "cca_v": (3, 3, 1, 8)}
    assert eng.kv_bytes_per_token == 2 * 3 * 2 * 8 * 4 == reg.get(
        "serve_kv_bytes_per_token").value()
    per_slot = 3 * 4 * (48 + 48 + 8)
    assert reg.get("serve_state_bytes_per_slot").value() == per_slot \
        == eng.cache.state_bytes_per_slot
    tokens = sum(len(p) for p in prompts) + sum(n - 1 for n in news)
    routed = reg.get("serve_moe_assignments_total")
    assert routed.value(where="held") == tokens * cfg.pattern.count("E")
    assert routed.value(where="absent") == 0
    assert reg.get("serve_moe_load_max_over_mean").value() >= 1.0


def test_a_reused_slot_starts_from_its_own_prefill(ref, weights, params):
    """One slot, two requests one after the other: the second's tokens
    are the reference's, so nothing of the first's state is read (its
    prefill wrote the rows whole, from zeros left of the sequence)."""
    eng = _engine(params, slots=1, prefill_batch=1)
    first = eng.generate([[5, 6, 7, 8, 9]], max_new_tokens=5)[0].tokens
    left = {n: np.asarray(v) for n, v in eng.cache.state.items()}
    assert all(np.abs(v).max() > 0 for v in left.values())
    second = eng.generate([[11, 3]], max_new_tokens=6)[0].tokens
    assert first == lm_toy.greedy(ref, weights, M, [5, 6, 7, 8, 9], 5, PAD)
    assert second == lm_toy.greedy(ref, weights, M, [11, 3], 6, PAD)


def test_spans_say_what_a_step_touched(params):
    _, spans = lm_toy.traced(lambda: _engine(params).generate(
        [[1, 2, 3], [4, 5]], max_new_tokens=3))
    assert spans["serve_decode"]
    for s in spans["serve_decode"]:
        a = s.args
        assert a["kv_heads"] == 2 and a["cache_layers"] == 3
        assert a["state_layers"] == 3
        assert a["state_slots"] == a["batch"] == 2
        assert a["moe_assignments"] == 2 * 3      # top-1, three layers
        assert 0 < a["experts_touched"] <= a["moe_assignments"]
        assert 1 <= a["moe_load_max"] <= 2
        assert a["moe_load_max_over_mean"] >= 1.0
    pre = spans["serve_prefill"][0]
    assert pre.args["moe_assignments"] == 5 * 3
    assert pre.args["moe_load_max"] >= 1
    assert spans["engine_init"][-1].args["state_bytes"] == (
        3 * 3 * 4 * (48 + 48 + 8))


def test_memory_report_counts_the_state_of_every_kind(params):
    from paddle_tpu.analysis.memory import serving_memory_report

    scfg = ServingConfig(max_slots=5, page_size=PS, num_pages=24,
                         max_prompt_len=8, max_new_tokens=4)
    assert serving_memory_report(cca_cfg(), scfg)["state_pool_bytes"] \
        == 4 * 5 * 3 * (48 + 48 + 8)
    both = cca_cfg(pattern="*EM*E-", mamba_heads=2, mamba_head_dim=4,
                   mamba_state=8)
    assert both.state_layers == 3 and set(both.state_parts) == {
        "ssm", "conv", "cca_u", "cca_c", "cca_v"}
    assert both.state_parts["conv"][0] == 1
    assert both.state_parts["cca_u"][0] == 2
    assert serving_memory_report(both, scfg)["state_pool_bytes"] == 4 * 5 * (
        2 * (48 + 48 + 8) + 2 * 4 * 8 + 3 * (2 * 4 + 2 * 8))


# -- precision ------------------------------------------------------------------------


def test_bf16_passes_and_int8_weights_fail(ref):
    """The comparison that decides ``correct`` (``served_gaps``) with the
    program in the stated precision and in the one below it: an engine in
    bfloat16 stays inside a mean gap that one serving int8-rounded
    weights misses; and a token altered after the fact reads wide.  At a
    size of its own (1,024 ids, 64 wide, the temperature and the
    router's gain the benchmark's configuration draws), where generation
    does not fall into one token and the reference's margins are small."""
    m = dict(M, vocab_size=1024, embed_dim=64, head_dim=16, mlp_dim=64,
             moe_router_hidden=16,
             init={"temp_mean": 4.0, "router_gain": 8.0, "beta_std": 0.01})
    cfg = T.TransformerConfig(
        **{k: v for k, v in m.items() if k != "init"}, remat=False,
        dtype=jnp.bfloat16)
    weights = lm_toy.draw(ref, m, 11)
    params = ref.program_tree(weights)
    low = lambda f: jax.tree.map(f, params)
    trees = {"bf16": low(lambda a: a.astype(jnp.bfloat16)),
             "int8": low(lambda a: ref._int8(a, -2).astype(jnp.bfloat16)
                         if a.ndim >= 2 else a.astype(jnp.bfloat16))}
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(0, 1024, n)]
               for n in (9, 14, 4, 16, 11, 7)]
    mean, served = {}, {}
    for name, tree in trees.items():
        eng = lm_toy.engine(
            cfg, tree, MetricsRegistry(name), max_slots=4, page_size=PS,
            num_pages=80, max_prompt_len=16, max_new_tokens=40,
            prefill_batch=2)
        served[name] = [(r.prompt, r.tokens)
                        for r in eng.generate(prompts, max_new_tokens=40)]
        gaps = ref.served_gaps(m, weights, served[name], 56)
        assert len(gaps["served"]) == 6 * 40
        mean[name] = ref.summarise(gaps["served"])["mean"]
    # my CPU readings: 0.0060 and 0.0412 (113 and 129 distinct ids of
    # 240; the reference's median margin is 0.3; bf16 flips 6% of the
    # served tokens, int8 15%)
    assert mean["bf16"] < 0.015 < mean["int8"], mean
    assert len({t for _, toks in served["bf16"] for t in toks}) > 60
    # the control's own arithmetic: int8 in the reference's matrices
    ctrl = ref.summarise(ref.served_gaps(m, weights, served["bf16"], 56,
                                         quant="int8")["control"])
    assert ctrl["mean"] > mean["bf16"] and ctrl["moved_share"] > 0.05
    bad = [(p, list(t)) for p, t in served["bf16"]]
    bad[0][1][3] = (bad[0][1][3] + 1) % 1024
    worst = ref.summarise(ref.served_gaps(m, weights, bad, 56)["served"])
    assert worst["widest"] > 1.0 and worst["mean"] > mean["bf16"]
    with pytest.raises(ValueError, match="unknown precision"):
        ref.served_gaps(m, weights, served["bf16"][:1], 56, quant="fp4")


def test_the_experts_branch_gain_moves_their_branch_scale_only(ref, weights):
    """The benchmark's init draws an expert sublayer's branch scale
    (``s_y``) around ``init.moe_branch_gain``: every other leaf, the
    attention sublayers' scale among them, is the draw it was; 1 is the
    default."""
    draw = lambda g: ref.init_weights(
        dict(M, init={**M["init"], "moe_branch_gain": g}), 11, jnp.float32)
    for a, b in zip(jax.tree.leaves(draw(1.0)), jax.tree.leaves(weights)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for new, old in zip(draw(0.25)["layers"], weights["layers"]):
        for kind in ("attn", "moe"):
            for name, leaf in new[kind].items():
                if (kind, name) == ("moe", "s_y"):
                    np.testing.assert_allclose(
                        np.asarray(leaf),
                        0.25 * np.asarray(old[kind][name]), rtol=1e-6)
                else:
                    np.testing.assert_array_equal(
                        np.asarray(leaf), np.asarray(old[kind][name]))


# -- what is refused, by name ----------------------------------------------------------


@pytest.mark.parametrize("serving, said", [
    (dict(prefix_cache=True), "CCA attention layer's convolution"),
    (dict(prefill_chunk_tokens=4), "CCA attention layer's convolution"),
])
def test_engine_refuses_incremental_prefill_beside_cca_state(
        serving, said, params):
    with pytest.raises(NotImplementedError, match=said) as e:
        _engine(params, **serving)
    assert "'attn'" in str(e.value)


@pytest.mark.parametrize("fields, err, said", [
    (dict(block_len=4, mask_id=96), NotImplementedError, "cca_taps with "
     "block_len"),
    (dict(loop_steps=2), NotImplementedError, "loop_steps"),
    (dict(pattern=None, num_layers=3), NotImplementedError,
     "without a layer pattern"),
    (dict(cca_taps=None, moe_router_hidden=0, pattern=None, num_layers=3,
          moe_experts=0), NotImplementedError,
     "residual_scale without a layer pattern"),
    (dict(cca_taps=[2, 1]), ValueError, "two tap counts"),
    (dict(kv_heads=1), ValueError, "even kv_heads"),
    (dict(qk_norm=True), ValueError, "qk_norm"),
    (dict(moe_router="sigmoid"), ValueError, "softmax_topk"),
])
def test_config_refuses_by_name(fields, err, said):
    with pytest.raises(err, match=said):
        cca_cfg(**fields)


def test_chunk_forward_refuses_cca_state(params):
    cfg = cca_cfg()
    kc, vc, _ = lm_toy.pools(cfg, pages=40)
    with pytest.raises(NotImplementedError, match="state layers"):
        T.forward_prefill_chunk(
            cfg, params, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.asarray([4]), jnp.zeros((1, 8), jnp.int32), kc, vc)


# -- the defaults are the parent's ---------------------------------------------------------


@pytest.mark.parametrize("fields", [
    dict(),
    dict(pattern="*E-", num_layers=3, moe_experts=4,
         moe_router="softmax_topk", mlp="swiglu", norm="rms",
         positions="rotary"),
    dict(pattern="ME*", num_layers=3, moe_experts=4, moe_router="sigmoid",
         mlp="relu2", mamba_heads=2, mamba_head_dim=4, mamba_state=8),
])
def test_default_fields_draw_the_parents_weights(fields):
    """With the new fields at their defaults ``init_params`` makes the
    leaves it made, from the same keys: the names below are the whole
    tree, and a configuration that turns the new parts on draws the old
    leaves' values unchanged beside its new ones."""
    base = dict(vocab_size=50, num_layers=2, num_heads=2, embed_dim=16,
                mlp_dim=24, max_seq_len=32)
    cfg = T.TransformerConfig(**{**base, **fields})
    assert (cfg.cca_taps, cfg.rope_fraction, cfg.moe_router_hidden,
            cfg.moe_renorm, cfg.residual_scale) == (None, 1.0, 0, True, False)
    p = lm_toy.jitted(T.init_params, cfg)(jax.random.key(0))
    names = {k for b in (p["blocks"] if cfg.pattern else [p["blocks"]])
             for k in b}
    assert not {n for n in names
                if n.startswith(("cca_", "res_", "router_w", "router_d",
                                 "router_n"))}
    if cfg.pattern and "*" in cfg.pattern and cfg.kv_heads % 2 == 0:
        on = dataclasses.replace(cfg, cca_taps=(2, 2), residual_scale=True)
        q = lm_toy.jitted(T.init_params, on)(jax.random.key(0))
        i = cfg.pattern.index("*")
        np.testing.assert_array_equal(np.asarray(q["embed"]),
                                      np.asarray(p["embed"]))
        np.testing.assert_array_equal(np.asarray(q["blocks"][i]["wq"]),
                                      np.asarray(p["blocks"][i]["wq"]))
        assert {"cca_conv0_w", "cca_conv1_w", "cca_temp", "res_x_g"} <= set(
            q["blocks"][i])
    if cfg.moe_experts:
        assert cfg.routed.renorm and cfg.routed.router_hidden == 0
