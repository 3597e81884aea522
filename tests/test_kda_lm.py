"""A gated delta-rule linear-attention layer (Kimi Delta Attention: a "K"
layer of a pattern, ``ops/kda.py``) beside gated attention without a
position signal (``attn_gate``) and sigmoid top-k SwiGLU experts with a
gated shared expert over a held share, against the plain float32
reference ``benchmarks/references/solar_open2.py`` on seeded random
weights at a toy size of the benchmark configuration's SHAPE
(``*EKEKEKE``, 4 heads x 16, 16 experts top-4 of which [0, 8) are held, a
sliced vocabulary): every mixer alone, prefill + decode through the
paged cache AND the per-slot delta-rule state, the engine under requests
that join mid-flight and reuse slots, what is refused, and that the new
fields at their defaults draw the weights they always drew.  (The
recurrence alone: ``test_pallas_kda.py``; the expert sublayer's shares
and its grouped product: ``test_moe_arrangement.py``.)

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
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.parallel import moe
from paddle_tpu.serving import ServingConfig
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy
from lm_toy import PS, REPO

TOL = 1e-4
PAD = 48    # the reference's one compiled length: 44 positions, 42 served
V = 67
M = dict(vocab_size=V, num_layers=8, num_heads=4, kv_heads=2, head_dim=16,
         embed_dim=32, mlp_dim=24, max_seq_len=128, norm="rms",
         norm_eps=1e-5, positions="none", mlp="swiglu",
         tie_embeddings=False, pattern="*EKEKEKE", attn_gate=True,
         moe_experts=16, moe_router="sigmoid", moe_top_k=4, moe_scale=1.0,
         moe_shared_dim=24, moe_held=[0, 8], kda_heads=4, kda_conv=4,
         kda_chunk=16,
         init={"router_bias_std": 0.05, "time_step_min": 0.01,
               "time_step_max": 0.5})
FIELDS = {k: v for k, v in M.items() if k != "init"}


def kda_cfg(**kw):
    return T.TransformerConfig(**{**FIELDS, "remat": False, **kw})


ref, weights, params, seq, ref_logits = lm_toy.fixtures(
    "solar_open2", M, 11, seq_len=44, pad=PAD)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "references",
                           "solar_open2.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert "kda_prefill" not in text and "lax.scan(step" in text


# -- the mixers, one at a time --------------------------------------------------


def _prefill_arrangement(cfg, seq_lens=None):
    from paddle_tpu.ops import mamba2

    kept = {}

    def conv(x, w, bias):
        out, kept["kda_conv"] = mamba2.conv_prefill(x, w, bias, seq_lens)
        return out

    def rule(*args):
        o, kept["kda_s"] = kda.kda_prefill(*args, seq_lens=seq_lens,
                                           chunk=cfg.kda_chunk)
        return o

    return (conv, rule), kept


def test_kda_mixer_equals_the_reference(ref, weights, params):
    """One KDA sublayer over two sequences: the three convolutions, the
    unit-length q and k, the decay through its low-rank pair, beta in (0,
    2), the recurrence, the per-head norm and the low-rank output gate;
    and what it leaves behind is the reference's state and conv tail."""
    cfg = kda_cfg()
    h = jax.random.normal(jax.random.key(3), (2, 37, 32))

    def mixer(h, layer):    # what the arrangement keeps rides out with it
        arrangement, kept = _prefill_arrangement(cfg)
        return T._kda_mixer(cfg, h, layer, *arrangement), kept

    got, kept = jax.jit(mixer)(h, params["blocks"][2])
    one = jax.jit(lambda l, x: ref.kda_mixer(l, x, M, with_state=True))
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            want, s, tail = one(weights["layers"][2], h[b])
            np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                       atol=TOL, rtol=TOL)
            np.testing.assert_allclose(np.asarray(kept["kda_s"][b]),
                                       np.asarray(s), atol=TOL, rtol=TOL)
            np.testing.assert_allclose(np.asarray(kept["kda_conv"][b]),
                                       np.asarray(tail), atol=TOL, rtol=TOL)
    assert cfg.state_kinds == {"kda": (3, {
        "kda_s": (4, 16, 16), "kda_conv": (3, 192)})}
    assert cfg.state_layers == 3 and cfg.cache_layers == 1
    beta = 2 * jax.nn.sigmoid(h @ params["blocks"][2]["w_beta"])
    assert float(beta.max()) > 1.0 > float(beta.min())


def test_gated_attention_equals_the_reference(ref, weights, params):
    cfg = kda_cfg()
    h = jax.random.normal(jax.random.key(4), (1, 19, 32))
    got, _, _ = jax.jit(lambda layer, h: T._pattern_layer(
        cfg, "attn", layer, h, None,
        lambda q, k, v: T._attention(cfg, q, k, v, None), None))(
        {**params["blocks"][0], "ln_g": jnp.ones((32,))}, h)
    with jax.default_matmul_precision("highest"):
        hn = ref._rms(h[0], 1.0, M["norm_eps"])
        want = h[0] + ref.attention_mixer(weights["layers"][0], hn, M)
        ungated = h[0] + ref.attention_mixer(weights["layers"][0], hn,
                                             dict(M, attn_gate=False))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=TOL, rtol=TOL)
    assert float(jnp.abs(want - ungated).max()) > 0.01


def test_expert_sublayer_equals_the_reference(ref, weights, params):
    """Sigmoid top-4 of 16 with a selection bias, SwiGLU experts [0, 8)
    and the GATED shared expert."""
    cfg = kda_cfg()
    h = jax.random.normal(jax.random.key(5), (2, 13, 32))
    routed = jax.jit(lambda layer: moe.moe_routed(layer, h, cfg.routed))
    got, counts = routed(params["blocks"][1])
    one = jax.jit(lambda l, x: ref.moe_mixer(l, x, M))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([one(weights["layers"][1], h[b]) for b in range(2)])
        no_gate = {k: v for k, v in params["blocks"][1].items()
                   if k != "shared_gate"}
        other, _ = routed(no_gate)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)
    assert int(counts[0] + counts[1]) == 2 * 13 * 4
    assert float(jnp.abs(other - got).max()) > 0.01


# -- the whole model ---------------------------------------------------------------


def test_full_forward_equals_the_reference(params, seq, ref_logits):
    got = lm_toy.jitted(T.forward, kda_cfg())(params, jnp.asarray([seq]))[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL,
                               rtol=TOL)
    assert float(np.abs(ref_logits).max()) > 1.0


@pytest.mark.parametrize("attn_impl, p_len", [
    ("reference", 15), ("reference", 16), ("reference", 17),
    ("reference", 33), ("kernel", 18)])
def test_pages_and_state_equal_the_reference_at_every_position(
        attn_impl, p_len, params, seq, ref_logits):
    """Prefill ``p_len`` tokens of a padded 36 (row 1 of a batch whose
    row 0 is another prompt; lengths around a chunk's end), put K/V in
    pages and each KDA layer's state in slot rows, then decode the rest
    token by token: every position's logits are the reference's full
    forward.  Row 0 idles through the decode and keeps its state."""
    ks, handed, state = lm_toy.walk_positions(
        kda_cfg(), params, seq, ref_logits, p_len, 36, attn_impl, TOL)
    assert ks.shape == (1, 2, 36, 2, 16)   # cache layers x B x T x KV x Dh
    assert handed == set(state) == {"kda_s", "kda_conv"}
    assert all(np.abs(np.asarray(v[:, 1])).max() > 0 for v in state.values())


# -- the engine ---------------------------------------------------------------------


def _engine(params, slots=3, reg=None, cfg=None, **kw):
    return lm_toy.engine(
        cfg or kda_cfg(), params, reg or MetricsRegistry("kda"),
        **{**dict(max_slots=slots, page_size=PS, num_pages=60,
                  max_prompt_len=36, max_new_tokens=6, prefill_batch=4),
           **kw})


@pytest.mark.parametrize("prefill_batch", [1, 4])
def test_engine_logits_are_the_references(monkeypatch, ref, weights, params,
                                          prefill_batch):
    """Seven requests through three slots, joining mid-flight and
    finishing at different steps, so slots are reused after a retire and
    a prefill pass of four rows carries slack rows: every request's tokens
    are the reference's greedy tokens, and at every served position the
    reference's full-forward LOGITS are among the rows the engine's passes
    sampled from, to 1e-4 (the engine hands out tokens only; a recorder
    around its sampler sees the logits)."""
    from paddle_tpu.serving import sampling

    seen = []
    sample = sampling.sample_tokens

    def recording(logits, keys, temps):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return sample(logits, keys, temps)

    monkeypatch.setattr(sampling, "sample_tokens", recording)
    # a config of its own: the engine's programs are memoised by config
    cfg = kda_cfg(max_seq_len=120 + prefill_batch)
    reg = MetricsRegistry("kda")
    eng = _engine(params, reg=reg, cfg=cfg, prefill_batch=prefill_batch)
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, V, n)]
               for n in (7, 33, 3, 16, 1, 18, 36)]
    news = [6, 3, 5, 2, 4, 6, 3]
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
    assert eng.cache.k.shape == PA.kv_pool_shape(1, 2, 60, PS, 16)
    assert {n: v.shape for n, v in eng.cache.state.items()} == {
        "kda_s": (3, 3, 4, 16, 16), "kda_conv": (3, 3, 3, 192)}
    per_slot = 3 * 4 * (4 * 16 * 16 + 3 * 192)
    assert reg.get("serve_state_bytes_per_slot").value() == per_slot \
        == eng.cache.state_bytes_per_slot
    tokens = sum(len(p) for p in prompts) + sum(n - 1 for n in news)
    routed = reg.get("serve_moe_assignments_total")
    assert routed.value(where="held") + routed.value(where="absent") \
        == tokens * 4 * cfg.pattern.count("E")
    # what the passes moved of the state: a prefill writes its rows', a
    # decode step reads and writes its live rows'
    steps = sum(n - 1 for n in news)
    assert reg.get("serve_state_bytes_total").value(kind="kda") == per_slot * (
        len(prompts) + 2 * steps)
    assert reg.get("serve_kda_chunk_tokens_total").value() == 16 * sum(
        -(-len(p) // 16) for p in prompts)


def test_a_reused_slot_starts_from_its_own_prefill(ref, weights, params):
    """One slot, two requests one after the other: the second's tokens
    are the reference's, so nothing of the first's state is read (its
    prefill wrote the rows whole, from a zero state)."""
    eng = _engine(params, slots=1, prefill_batch=1)
    first = eng.generate([[5, 6, 7, 8, 9]], max_new_tokens=5)[0].tokens
    left = {n: np.asarray(v) for n, v in eng.cache.state.items()}
    assert all(np.abs(v).max() > 0 for v in left.values())
    second = eng.generate([[11, 3]], max_new_tokens=6)[0].tokens
    assert first == lm_toy.greedy(ref, weights, M, [5, 6, 7, 8, 9], 5, PAD)
    assert second == lm_toy.greedy(ref, weights, M, [11, 3], 6, PAD)


def test_spans_say_what_a_pass_moved(params):
    traced, spans = lm_toy.traced(lambda: _engine(params).generate(
        [[1, 2, 3], list(range(20))], max_new_tokens=3))
    assert spans["serve_decode"]
    per_slot = 3 * 4 * (4 * 16 * 16 + 3 * 192)
    for s in spans["serve_decode"]:
        a = s.args
        assert a["kv_heads"] == 2 and a["cache_layers"] == 1
        assert a["state_layers"] == 3 == a["kda_layers"]
        assert a["state_slots"] == a["batch"] == 2
        assert a["state_bytes"] == 2 * 2 * per_slot
        assert 0 < a["experts_touched"] <= a["moe_assignments"] <= 2 * 4 * 4
    pre = spans["serve_prefill"][0]
    assert pre.args["kda_chunks"] == 1 + 2 and pre.args["rows"] == 4
    assert pre.args["state_bytes"] == 2 * per_slot
    assert pre.args["kda_layers"] == 3
    assert pre.args["prompt_tokens"] == 23
    assert spans["engine_init"][-1].args["state_bytes"] == 3 * per_slot
    # off: the same tokens
    plain = _engine(params).generate(
        [[1, 2, 3], list(range(20))], max_new_tokens=3)
    assert [r.tokens for r in plain] == [r.tokens for r in traced]


@pytest.mark.parametrize("limit, four_rows", [(2048, "masked"),
                                              (40, "reference")])
def test_engine_says_its_expert_products_arrangement(
        monkeypatch, ref, weights, params, limit, four_rows):
    """The arrangement of the expert product is chosen a PROGRAM, when it
    is traced.  Under a row limit of 40 the four-row pass (4 x 36 rows)
    sorts its assignments and runs the grouped product — its
    ``lax.ragged_dot`` twin off a TPU — while the one-row pass and the
    decode step stay masked: the census says so at the build, every
    ``serve_prefill`` / ``serve_decode`` span says its own program's
    (``moe_path``), the registry counts passes by it, and the tokens are
    the reference's on either arrangement."""
    from paddle_tpu.ops import pallas

    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", limit)
    # a config of its own: the engine's programs are memoised by config
    cfg = kda_cfg(max_seq_len=140 + limit % 7)
    reg = MetricsRegistry("kda")
    prompts = [[1, 2, 3], list(range(20)), [5, 6, 7]]

    def serve():
        eng = _engine(params, reg=reg, cfg=cfg)
        return eng.generate(prompts[:2], max_new_tokens=3) \
            + eng.generate(prompts[2:], max_new_tokens=2)

    with pallas.capture_routes() as routes:
        served, spans = lm_toy.traced(serve)
    built = {path for (op, path) in routes if op == "moe_experts"}
    assert built == {"masked", four_rows}
    pre = {s.args["rows"]: s.args["moe_path"] for s in spans["serve_prefill"]}
    assert pre == {4: four_rows, 1: "masked"}
    assert {s.args["moe_path"] for s in spans["serve_decode"]} == {"masked"}
    passes = reg.get("serve_moe_product_passes_total")
    n = len(spans["serve_prefill"]) + len(spans["serve_decode"])
    if four_rows == "masked":
        assert passes.value(path="masked") == n
    else:
        assert passes.value(path=four_rows) == 1
        assert passes.value(path="masked") == n - 1
    for r, prompt, new in zip(served, prompts, (3, 3, 2)):
        assert r.tokens == lm_toy.greedy(ref, weights, M, prompt, new, PAD)


def test_memory_report_counts_the_kda_state(params):
    from paddle_tpu.analysis.memory import serving_memory_report

    scfg = ServingConfig(max_slots=5, page_size=PS, num_pages=24,
                         max_prompt_len=8, max_new_tokens=4)
    rep = serving_memory_report(kda_cfg(), scfg)
    assert rep["state_pool_bytes"] == 4 * 5 * 3 * (4 * 16 * 16 + 3 * 192)
    both = kda_cfg(pattern="*EKEM-KE", mamba_heads=2, mamba_head_dim=4,
                   mamba_state=8)
    assert both.state_layers == 3 and set(both.state_parts) == {
        "ssm", "conv", "kda_s", "kda_conv"}
    assert both.state_parts["kda_s"][0] == 2
    assert both.state_parts["conv"][0] == 1


# -- what is refused, by name ----------------------------------------------------------


@pytest.mark.parametrize("serving", [
    dict(prefix_cache=True), dict(prefill_chunk_tokens=4)])
def test_engine_refuses_incremental_prefill_beside_kda_state(serving,
                                                             params):
    with pytest.raises(NotImplementedError,
                       match="prefix_cache / prefill_chunk_tokens") as e:
        _engine(params, **serving)
    assert "'kda'" in str(e.value)


@pytest.mark.parametrize("fields, err, said", [
    (dict(block_len=4, mask_id=66), NotImplementedError, "Mamba / KDA"),
    (dict(loop_steps=2), NotImplementedError, "loop_steps > 1"),
    (dict(cca_taps=[2, 2]), NotImplementedError, "'K' layer beside cca_taps"),
    (dict(pattern=None, num_layers=3, moe_experts=0, attn_gate=False),
     NotImplementedError, "kda_heads without a layer pattern"),
    (dict(pattern=None, num_layers=3, moe_experts=0, kda_heads=0),
     NotImplementedError, "attn_gate without a layer pattern"),
    (dict(kda_heads=0), ValueError, "needs kda_heads > 0"),
    (dict(kda_conv=1), ValueError, "kda_conv >= 2"),
    (dict(kda_chunk=24), ValueError, "multiple of 16"),
    (dict(pattern="*EKEKEKQ"), ValueError, "characters of"),
])
def test_config_refuses_by_name(fields, err, said):
    with pytest.raises(err, match=said):
        kda_cfg(**fields)


def test_chunk_forward_refuses_kda_state(params):
    cfg = kda_cfg()
    kc, vc, _ = lm_toy.pools(cfg, pages=17)
    with pytest.raises(NotImplementedError, match="state layers"):
        T.forward_prefill_chunk(
            cfg, params, jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.asarray([4]),
            jnp.zeros((1, 8), jnp.int32), kc, vc)


# -- the defaults are the parent's ---------------------------------------------------------


@pytest.mark.parametrize("fields", [
    dict(),
    dict(pattern="*E-", num_layers=3, moe_experts=4,
         moe_router="softmax_topk", mlp="swiglu", norm="rms",
         positions="rotary"),
    dict(pattern="ME*", num_layers=3, moe_experts=4, moe_router="sigmoid",
         mlp="relu2", mamba_heads=2, mamba_head_dim=4, mamba_state=8,
         moe_shared_dim=12),
])
def test_default_fields_draw_the_parents_weights(fields):
    """With the new fields at their defaults ``init_params`` makes the
    leaves it made, from the same keys; an ungated expert kind keeps an
    ungated shared expert; turning the gate on draws the old leaves'
    values unchanged beside the new one."""
    base = dict(vocab_size=50, num_layers=2, num_heads=2, embed_dim=16,
                mlp_dim=24, max_seq_len=32)
    cfg = T.TransformerConfig(**{**base, **fields})
    assert (cfg.kda_heads, cfg.kda_conv, cfg.kda_chunk, cfg.attn_gate) == (
        0, 4, 64, False)
    assert len(dataclasses.fields(T.TransformerConfig)) == 62
    p = lm_toy.jitted(T.init_params, cfg)(jax.random.key(0))
    names = {k for b in (p["blocks"] if cfg.pattern else [p["blocks"]])
             for k in b}
    assert not names & {"w_ogate", "shared_gate", "decay_a", "gate_a",
                        "w_beta"}
    if cfg.moe_shared_dim:
        assert "shared_in" in names
    if cfg.pattern and "*" in cfg.pattern:
        on = dataclasses.replace(cfg, attn_gate=True)
        q = lm_toy.jitted(T.init_params, on)(jax.random.key(0))
        i = cfg.pattern.index("*")
        for leaf in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(np.asarray(q["blocks"][i][leaf]),
                                          np.asarray(p["blocks"][i][leaf]))
        assert q["blocks"][i]["w_ogate"].shape == q["blocks"][i]["wq"].shape
