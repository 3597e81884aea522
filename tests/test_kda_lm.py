"""A gated delta-rule linear-attention layer (Kimi Delta Attention: a "K"
layer of a pattern, ``ops/kda.py``) beside gated attention without a
position signal (``attn_gate``) and sigmoid top-k SwiGLU experts with a
gated shared expert over a held share, against the plain float32
reference ``benchmarks/references/solar_open2.py`` on seeded random
weights at a toy size of the benchmark configuration's SHAPE
(``*EKEKEKE``, 4 heads x 16, 16 experts top-4 of which [0, 8) are held, a
sliced vocabulary): the chunked recurrence against the one-token step
and the reference's scan, every mixer alone, prefill + decode through the
paged cache AND the per-slot delta-rule state, the engine under requests
that join mid-flight and reuse slots, the shares of the expert sublayer
adding up, the grouped expert product over a held share, what is
refused, and that the new fields at their defaults draw the weights they
always drew.

Tolerances.  Program and reference both compute in float32 here (the CPU
backend's dots are exact float32), so they differ by the order of
summation only: logits of unit scale agree to ``TOL`` = 1e-4.  bfloat16
in the program's place moves them by 1e-2 or more.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.parallel import moe
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4
PS = 4
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


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name + "_reference",
        os.path.join(REPO, "benchmarks", "references", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("solar_open2")


@pytest.fixture(scope="module")
def weights(ref):
    return ref.init_weights(M, 11, jnp.float32)


@pytest.fixture(scope="module")
def params(ref, weights):
    return ref.program_tree(weights)


@pytest.fixture(scope="module")
def seq():
    return [int(t) for t in np.random.default_rng(5).integers(0, V, 44)]


@pytest.fixture(scope="module")
def ref_logits(ref, weights, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_fn(weights, jnp.asarray(seq), M))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "references",
                           "solar_open2.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert "kda_prefill" not in text and "lax.scan(step" in text


# -- the recurrence -----------------------------------------------------------------


def _rule_inputs(t, h=3, d=16, seed=0, decay=None, beta_shift=0.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (3, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (3, t, h, d)))
    v = jax.random.normal(ks[2], (3, t, h, d))
    g = -jnp.exp(jax.random.normal(ks[3], (3, t, h, d)) * 1.5 - 2.0)
    if decay is not None:     # a stretch whose decay underflows a chunk
        g = g.at[:, 20:20 + decay[0]].set(decay[1])
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (3, t, h))
                              + beta_shift)
    return q, k, v, g, beta


@pytest.mark.parametrize("t, lens, chunk, decay, beta_shift", [
    (64, (64, 64, 64), 64, None, 0.0),          # whole chunks
    (150, (150, 77, 1), 64, None, 0.0),         # ragged, not a multiple
    (150, (150, 77, 1), 16, None, 0.0),
    (100, (100, 33, 64), 32, None, 0.0),
    (150, (150, 90, 40), 64, (70, -12.0), 0.0),     # exp(-840) inside a chunk
    (150, (150, 90, 40), 64, (9, -60.0), 0.0),      # and inside a sub-block
    (90, (90, 17, 64), 64, None, 3.0),          # beta near 2
    (40, (0, 40, 3), 16, None, 0.0),            # a slack row
])
def test_chunked_rule_equals_the_step_and_the_references_scan(
        ref, t, lens, chunk, decay, beta_shift):
    """``kda_prefill`` over right-padded rows = ``kda_step`` token by
    token = the reference's scan, outputs at every valid position and the
    state at each row's LAST VALID token; padding neither decays nor
    writes."""
    q, k, v, g, beta = _rule_inputs(t, decay=decay, beta_shift=beta_shift)
    if beta_shift:
        assert float(beta.max()) > 1.9
    o, s = kda.kda_prefill(q, k, v, g, beta, jnp.asarray(lens), chunk=chunk)
    assert o.dtype == s.dtype == jnp.float32 and bool(jnp.isfinite(o).all())
    # the one-token step, all rows at once over the padded length
    step = jax.jit(lambda st, x: kda.kda_step(st, *x)[::-1])
    st, outs, at_last = jnp.zeros_like(s), [], [None] * 3
    for i in range(t):
        st, out = step(st, tuple(x[:, i] for x in (q, k, v, g, beta)))
        outs.append(out)
        for b, n in enumerate(lens):
            if i == n - 1:
                at_last[b] = st[b]
    outs = jnp.stack(outs, axis=1)
    for b, n in enumerate(lens):
        if not n:
            assert float(jnp.abs(s[b]).max()) == 0.0
            continue
        np.testing.assert_allclose(np.asarray(s[b]), np.asarray(at_last[b]),
                                   atol=TOL, rtol=TOL)
        np.testing.assert_allclose(np.asarray(o[b, :n]),
                                   np.asarray(outs[b, :n]),
                                   atol=TOL, rtol=TOL)
        with jax.default_matmul_precision("highest"):
            want, s_want = ref.delta_rule(q[b, :n], k[b, :n], v[b, :n],
                                          g[b, :n], beta[b, :n])
        np.testing.assert_allclose(np.asarray(o[b, :n]), np.asarray(want),
                                   atol=TOL, rtol=TOL)
        np.testing.assert_allclose(np.asarray(s[b]), np.asarray(s_want),
                                   atol=TOL, rtol=TOL)


def test_a_chunk_starts_from_the_state_it_is_given():
    q, k, v, g, beta = _rule_inputs(96, seed=3)
    whole, s_whole = kda.kda_prefill(q, k, v, g, beta, chunk=32)
    cut = lambda x, a, b: x[:, a:b]
    _, s0 = kda.kda_prefill(*(cut(x, 0, 40) for x in (q, k, v, g, beta)),
                            chunk=16)
    tail, s1 = kda.kda_prefill(*(cut(x, 40, 96) for x in (q, k, v, g, beta)),
                               chunk=16, state=s0)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(whole[:, 40:]),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s_whole),
                               atol=TOL, rtol=TOL)
    with pytest.raises(ValueError, match="multiple of 16"):
        kda.kda_prefill(q, k, v, g, beta, chunk=24)


def test_the_rule_in_bf16_keeps_a_float32_state():
    q, k, v, g, beta = _rule_inputs(70, seed=4)
    want, s_want = kda.kda_prefill(q, k, v, g, beta, chunk=32)
    low = lambda x: x.astype(jnp.bfloat16)
    o, s = kda.kda_prefill(low(q), low(k), low(v), g, beta, chunk=32)
    assert o.dtype == s.dtype == jnp.float32
    assert 1e-4 < float(jnp.abs(o - want).max()) < 0.1
    assert float(jnp.abs(s - s_want).max()) < 0.1


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
    arrangement, kept = _prefill_arrangement(cfg)
    got = T._kda_mixer(cfg, h, params["blocks"][2], *arrangement)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            want, s, tail = ref.kda_mixer(weights["layers"][2], h[b], M,
                                          with_state=True)
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
    got, _, _ = T._pattern_layer(
        cfg, "attn", {**params["blocks"][0],
                      "ln_g": jnp.ones((32,))}, h, None,
        lambda q, k, v: T._attention(cfg, q, k, v, None), None)
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
    got, counts = moe.moe_routed(params["blocks"][1], h, cfg.routed)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe_mixer(weights["layers"][1], h[b], M)
                          for b in range(2)])
        no_gate = {k: v for k, v in params["blocks"][1].items()
                   if k != "shared_gate"}
        other, _ = moe.moe_routed(no_gate, h, cfg.routed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)
    assert int(counts[0] + counts[1]) == 2 * 13 * 4
    assert float(jnp.abs(other - got).max()) > 0.01


def test_the_eight_shares_add_up_to_the_uncut_sublayer(ref):
    """The deployment's eight chips: 40 experts, share k holds [5k, 5k +
    5); every share routes over all 40 and computes its own experts' part;
    with the shared expert counted ONCE they add up to the uncut
    reference's expert sublayer, and so do the program's shares."""
    m = dict(M, moe_experts=40, moe_top_k=8, moe_held=None)
    whole = ref.init_weights(m, 3, jnp.float32)["layers"][1]
    h = jax.random.normal(jax.random.key(6), (23, 32))
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe_mixer(whole, h, m)
        total = prog = 0.0
        for k in range(8):
            lo, hi = 5 * k, 5 * k + 5
            share = {**whole, **{n: whole[n][lo:hi]
                                 for n in ("up", "gate", "down")}}
            total = total + ref.moe_mixer(share, h, m, held=(lo, hi),
                                          shared=k == 0)
            tree = {ref._PROGRAM.get(n, n): a for n, a in share.items()
                    if k == 0 or not n.startswith("shared")}
            cfg = kda_cfg(moe_experts=40, moe_top_k=8, moe_held=(lo, hi))
            y, counts = moe.moe_routed(tree, h, cfg.routed)
            prog = prog + y
            assert int(counts[0] + counts[1]) == 23 * 8
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(uncut),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("rows, held, note", [
    (300, (0, 8), "half the experts: more than one round of the gather"),
    (300, (4, 6), "an eighth of the experts"),
    (300, None, "every expert held: the bound is every assignment"),
    (700, (0, 1), "one expert, every token sent to it: past the bound"),
])
def test_grouped_experts_over_a_held_share(monkeypatch, ref, weights, params,
                                           rows, held, note):
    """Above ``DENSE_MAX_TOKENS`` rows the assignments to HELD experts are
    gathered (a static bound of rows at a time, as often as the count
    asks) and multiplied group by group: the result is the masked
    product's and the reference's."""
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 64)
    cfg = kda_cfg(moe_held=held)
    lo, hi = cfg.routed.held
    full = ref.init_weights(dict(M, moe_held=None), 11, jnp.float32)
    layer = dict(params["blocks"][1], **{
        ref._PROGRAM[n]: full["layers"][1][n][lo:hi]
        for n in ("up", "gate", "down")})
    if held == (0, 1):      # every token's first choice is expert 0
        layer["router_bias"] = layer["router_bias"].at[0].set(10.0)
    h = jax.random.normal(jax.random.key(7), (rows, 32))
    live = jnp.arange(rows) % 7 != 3
    got, counts = moe.moe_routed(layer, h, cfg.routed, live)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 4096)
    want, counts_dense = moe.moe_routed(layer, h, cfg.routed, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_dense))
    if held == (0, 1):
        assert int(counts[0]) == int(live.sum()) and int(counts[3]) == int(
            live.sum())


# -- the whole model ---------------------------------------------------------------


def test_full_forward_equals_the_reference(params, seq, ref_logits):
    got = T.forward(kda_cfg(), params, jnp.asarray([seq]))[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL,
                               rtol=TOL)
    assert float(np.abs(ref_logits).max()) > 1.0


def _pools(cfg, slots=2, pages=17):
    kc, vc = PA.init_kv_pages(cfg.cache_layers, cfg.kv_heads, pages, PS,
                              cfg.head_dim)
    state = {n: jnp.zeros((layers, slots, *s), jnp.float32)
             for n, (layers, s) in cfg.state_parts.items()}
    return kc, vc, state


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
    cfg = kda_cfg()
    kc, vc, state = _pools(cfg, pages=25)
    ids = np.zeros((2, 36), np.int32)
    ids[0, :5] = seq[10:15]
    ids[1, :p_len] = seq[:p_len]
    lens = jnp.asarray([5, p_len])
    logits, ks, vs, extras = T.forward_prefill(cfg, params, jnp.asarray(ids),
                                               lens)
    np.testing.assert_allclose(np.asarray(logits[1]), ref_logits[p_len - 1],
                               atol=TOL, rtol=TOL)
    assert ks.shape == (1, 2, 36, 2, 16)   # cache layers x B x T x KV x Dh
    table = jnp.asarray([list(range(1, 13)), list(range(13, 25))], jnp.int32)
    kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, table, lens)
    assert set(extras["state"]) == set(state) == {"kda_s", "kda_conv"}
    state = {n: extras["state"][n] for n in state}   # slot = row
    idle_state = {n: np.asarray(v[:, 0]) for n, v in state.items()}
    for pos in range(p_len, len(seq)):
        logits, kc, vc, extras = T.forward_decode(
            cfg, params, jnp.asarray([0, seq[pos]]), jnp.asarray([0, pos]),
            jnp.asarray([0, pos + 1]), table.at[0].set(0), kc, vc,
            attn_impl=attn_impl, state=state)
        state = extras["state"]
        np.testing.assert_allclose(np.asarray(logits[1]), ref_logits[pos],
                                   atol=TOL, rtol=TOL)
    for n, v in state.items():
        np.testing.assert_array_equal(np.asarray(v[:, 0]), idle_state[n])
        assert np.abs(np.asarray(v[:, 1])).max() > 0


# -- the engine ---------------------------------------------------------------------


_PADDED = {}


def _ref_logits_of(ref, weights, ids, pad=48):
    """The reference's logits over ``ids`` at a padded length (causal:
    what lies right of a position does not reach it)."""
    f = _PADDED.get(id(weights))
    if f is None:
        f = _PADDED[id(weights)] = jax.jit(
            lambda ids: ref.logits_fn(weights, ids, M))
    buf = np.zeros((pad,), np.int32)
    buf[:len(ids)] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(f(jnp.asarray(buf)))[:len(ids)]


def _greedy(ref, weights, prompt, n):
    out = list(prompt)
    for _ in range(n):
        out.append(int(np.argmax(_ref_logits_of(ref, weights, out)[-1])))
    return out[len(prompt):]


def _engine(params, slots=3, reg=None, cfg=None, **kw):
    return ServingEngine(
        cfg or kda_cfg(), params,
        ServingConfig(**{**dict(max_slots=slots, page_size=PS, num_pages=60,
                                max_prompt_len=36, max_new_tokens=6,
                                prefill_batch=4), **kw}),
        registry=reg or MetricsRegistry("kda"))


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
        assert got[rid] == _greedy(ref, weights, prompt, n)
        want = _ref_logits_of(ref, weights, prompt + got[rid])
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
    assert reg.get("serve_kda_state_bytes_total").value() == per_slot * (
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
    assert first == _greedy(ref, weights, [5, 6, 7, 8, 9], 5)
    assert second == _greedy(ref, weights, [11, 3], 6)


def test_spans_say_what_a_pass_moved(params):
    from paddle_tpu.telemetry import tracing

    tracing.configure_tracing(enabled=True)
    try:
        tracing.get_tracer().clear()
        eng = _engine(params, max_new_tokens=4)
        traced = eng.generate([[1, 2, 3], list(range(20))], max_new_tokens=3)
        spans = [s for s in tracing.get_tracer().spans
                 if s.name == "serve_decode"]
        assert spans
        per_slot = 3 * 4 * (4 * 16 * 16 + 3 * 192)
        for s in spans:
            a = s.args
            assert a["kv_heads"] == 2 and a["cache_layers"] == 1
            assert a["state_layers"] == 3 == a["kda_layers"]
            assert a["state_slots"] == a["batch"] == 2
            assert a["state_bytes"] == 2 * 2 * per_slot
            assert 0 < a["experts_touched"] <= a["moe_assignments"] <= 2 * 4 * 4
        pre = [s for s in tracing.get_tracer().spans
               if s.name == "serve_prefill"][0]
        assert pre.args["kda_chunks"] == 1 + 2 and pre.args["rows"] == 4
        assert pre.args["state_bytes"] == 2 * per_slot
        assert pre.args["kda_layers"] == 3
        assert pre.args["prompt_tokens"] == 23
        init = [s for s in tracing.get_tracer().spans
                if s.name == "engine_init"][-1]
        assert init.args["state_bytes"] == 3 * per_slot
    finally:
        tracing.configure_tracing(enabled=False)
        tracing.get_tracer().drain()
    # off: the same tokens
    plain = _engine(params, max_new_tokens=4).generate(
        [[1, 2, 3], list(range(20))], max_new_tokens=3)
    assert [r.tokens for r in plain] == [r.tokens for r in traced]


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
    (dict(pattern="*EKEKEKX"), ValueError, "characters of"),
])
def test_config_refuses_by_name(fields, err, said):
    with pytest.raises(err, match=said):
        kda_cfg(**fields)


def test_chunk_forward_refuses_kda_state(params):
    cfg = kda_cfg()
    kc, vc, _ = _pools(cfg)
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
    assert len(dataclasses.fields(T.TransformerConfig)) == 50
    p = T.init_params(cfg, jax.random.key(0))
    names = {k for b in (p["blocks"] if cfg.pattern else [p["blocks"]])
             for k in b}
    assert not names & {"w_ogate", "shared_gate", "decay_a", "gate_a",
                        "w_beta"}
    if cfg.moe_shared_dim:
        assert "shared_in" in names
    if cfg.pattern and "*" in cfg.pattern:
        on = dataclasses.replace(cfg, attn_gate=True)
        q = T.init_params(on, jax.random.key(0))
        i = cfg.pattern.index("*")
        for leaf in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(np.asarray(q["blocks"][i][leaf]),
                                          np.asarray(p["blocks"][i][leaf]))
        assert q["blocks"][i]["w_ogate"].shape == q["blocks"][i]["wq"].shape
