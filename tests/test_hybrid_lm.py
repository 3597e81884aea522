"""A stack of layers of several kinds — Mamba-2 mixers, sigmoid-routed
experts of which this device holds a share, attention with fewer K/V
heads than query heads — each ONE mixer behind a pre-norm and a residual
add (``TransformerConfig.pattern``), against the plain float32 reference
``benchmarks/references/nemotron_h.py`` on seeded random weights at a toy
size: every mixer alone, the full forward, the chunked scan against the
token-by-token recurrence, prefill + decode through the paged cache AND
the recurrent-state pool, the shares of the experts adding up to the
uncut layer, the engine under requests that join mid-flight.

Tolerances.  Program and reference both compute in float32 here (the CPU
backend's dots are exact float32), so they differ by the order of
summation only: logits of unit scale agree to ``TOL`` = 2e-4.  bfloat16
in the program's place moves them by 1e-2 or more
(``test_bf16_would_fail``), so the tolerance tells the stated precision
from the one below it.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops import mamba2
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.parallel import moe
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
PS = 4
M = dict(vocab_size=97, num_layers=6, num_heads=4, kv_heads=2, head_dim=8,
         embed_dim=32, mlp_dim=24, max_seq_len=128, norm="rms",
         norm_eps=1e-5, positions="none", mlp="relu2", tie_embeddings=False,
         pattern="ME*EM*", moe_experts=16, moe_router="sigmoid", moe_top_k=3,
         moe_scale=2.5, moe_shared_dim=40, moe_held=[0, 8], mamba_heads=4,
         mamba_head_dim=8, mamba_state=16, mamba_groups=2, mamba_conv=4,
         mamba_chunk=8)


def hybrid_cfg(**kw):
    return T.TransformerConfig(**{**M, "remat": False, **kw})


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference",
        os.path.join(REPO, "benchmarks", "references", "nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def weights(ref):
    return ref.init_weights(M, 11, jnp.float32)


@pytest.fixture(scope="module")
def params(ref, weights):
    return ref.program_tree(weights)


@pytest.fixture(scope="module")
def seq():
    return [int(t) for t in np.random.default_rng(5).integers(0, 97, 30)]


@pytest.fixture(scope="module")
def ref_logits(ref, weights, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_fn(weights, jnp.asarray(seq), M))


def _layer(params, kind):
    i = M["pattern"].index(kind)
    return i, params["blocks"][i]


# -- the mixers, one at a time --------------------------------------------------


@pytest.mark.parametrize("kind", ["*", "E", "M"])
def test_each_mixer_equals_the_reference(kind, ref, weights, params):
    cfg = hybrid_cfg()
    i, layer = _layer(params, kind)
    h = jax.random.normal(jax.random.key(3), (2, 21, 32))
    want = np.stack([np.asarray(ref._MIXERS[ref.KINDS[kind]](
        weights["layers"][i], h[b], M)) for b in range(2)])
    if kind == "*":
        q, k, v = T._qkv(cfg, h, layer, None)
        got = T._attention(cfg, q, k, v, None).reshape(2, 21, -1) @ layer["wo"]
    elif kind == "E":
        got, _ = moe.moe_routed(layer, h, cfg.routed)
    else:
        got = T._mamba_mixer(
            cfg, h, layer,
            lambda x, w, b: mamba2.conv_prefill(x, w, b)[0],
            lambda *a: mamba2.ssd_prefill(*a, chunk=8)[0])
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL, rtol=TOL)


def test_forward_equals_the_reference(params, seq, ref_logits):
    got = T.forward(hybrid_cfg(), params, jnp.asarray([seq]))[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL,
                               rtol=TOL)


def test_bf16_would_fail(ref, weights, seq, ref_logits):
    """The program in bfloat16 misses the tolerance by far: it tells the
    stated precision from the one below."""
    cfg = hybrid_cfg(dtype=jnp.bfloat16)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                       ref.program_tree(weights))
    got = T.forward(cfg, low, jnp.asarray([seq]))[0].astype(jnp.float32)
    assert float(np.max(np.abs(np.asarray(got) - ref_logits))) > 50 * TOL


# -- the scan --------------------------------------------------------------------


def _ssd_inputs(t=21, b=3, h=4, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (b, t, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0),
        a=-jnp.exp(jax.random.normal(ks[2], (h,))),
        b=jax.random.normal(ks[3], (b, t, g, n)),
        c=jax.random.normal(ks[4], (b, t, g, n)),
        d=jax.random.normal(ks[5], (h,)))


def _recurrence(i, upto=None):
    """Token by token through ``ssd_step``: (ys [B, T, H, P], state)."""
    bsz, t, h, p = i["x"].shape
    state = jnp.zeros((bsz, h, p, i["b"].shape[-1]))
    ys = []
    for s in range(t):
        dt = i["dt"][:, s]
        if upto is not None:
            dt = jnp.where((s < upto)[:, None], dt, 0.0)
        y, state = mamba2.ssd_step(state, i["x"][:, s], dt, i["a"],
                                   i["b"][:, s], i["c"][:, s], i["d"])
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_scan_equals_the_recurrence(chunk):
    i = _ssd_inputs()
    want_y, want_s = _recurrence(i)
    y, s = mamba2.ssd_prefill(i["x"], i["dt"], i["a"], i["b"], i["c"], i["d"],
                              chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=TOL,
                               rtol=TOL)


def test_a_padded_rows_state_is_the_state_at_its_last_valid_token():
    """Rows of 21, 13 and 1 valid tokens in one padded pass: each row's
    SSM and conv state are those of a pass over its valid tokens alone —
    padding decays nothing, adds nothing, is no conv tap."""
    i = _ssd_inputs()
    lens = jnp.asarray([21, 13, 1])
    _, s = mamba2.ssd_prefill(i["x"], i["dt"], i["a"], i["b"], i["c"], i["d"],
                              seq_lens=lens, chunk=8)
    xbc = jax.random.normal(jax.random.key(9), (3, 21, 10))
    w, bias = jax.random.normal(jax.random.key(8), (4, 10)), jnp.ones((10,))
    out, conv = mamba2.conv_prefill(xbc, w, bias, lens)
    for r, n in enumerate([21, 13, 1]):
        alone = {k: (v[r:r + 1, :n] if v.ndim > 1 else v)
                 for k, v in i.items()}
        _, want = mamba2.ssd_prefill(
            alone["x"], alone["dt"], alone["a"], alone["b"], alone["c"],
            alone["d"], chunk=8)
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(want[0]),
                                   atol=TOL, rtol=TOL)
        out1, conv1 = mamba2.conv_prefill(xbc[r:r + 1, :n], w, bias)
        np.testing.assert_array_equal(np.asarray(conv[r]),
                                      np.asarray(conv1[0]))
        np.testing.assert_allclose(np.asarray(out[r, :n]),
                                   np.asarray(out1[0]), atol=1e-6)
    # the one-token arrangement continues where the prefill stopped
    state = jnp.zeros((1, 3, 10))
    for t in range(5):
        o, state = mamba2.conv_step(state, xbc[:1, t], w, bias)
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(out[0, t]),
                                   atol=1e-6)


def test_a_scan_continues_from_a_state():
    i = _ssd_inputs()
    y, s = mamba2.ssd_prefill(i["x"], i["dt"], i["a"], i["b"], i["c"], i["d"],
                              chunk=8)
    cut = lambda v, sl: v[:, sl] if v.ndim > 1 else v
    head = {k: cut(v, slice(0, 9)) for k, v in i.items()}
    tail = {k: cut(v, slice(9, None)) for k, v in i.items()}
    _, mid = mamba2.ssd_prefill(*[head[k] for k in "x dt a b c d".split()],
                                chunk=8)
    y2, s2 = mamba2.ssd_prefill(*[tail[k] for k in "x dt a b c d".split()],
                                chunk=8, state=mid)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y[:, 9:]),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=TOL,
                               rtol=TOL)


# -- the experts -------------------------------------------------------------------


def test_the_shares_add_up(ref, weights, params):
    """16 experts over two devices: the routed parts of shares [0, 8) and
    [8, 16), with the shared expert counted once, are the uncut layer —
    in the program and in the reference."""
    m_all = {**M, "moe_held": [0, 16]}
    w_all = ref.init_weights(m_all, 11, jnp.float32)
    i = M["pattern"].index("E")
    l_all = w_all["layers"][i]
    h = jax.random.normal(jax.random.key(4), (19, 32))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.moe_mixer(l_all, h, m_all))
        shared = np.asarray(ref.moe_mixer(
            {**l_all, "up": l_all["up"][:0], "down": l_all["down"][:0]}, h,
            m_all, held=(0, 0)))
    p_all = ref.program_tree(w_all)["blocks"][i]
    parts = []
    for lo, hi in ((0, 8), (8, 16)):
        share = {**p_all, "w_in": p_all["w_in"][lo:hi],
                 "w_out": p_all["w_out"][lo:hi]}
        cfg = hybrid_cfg(moe_held=(lo, hi))
        y, counts = moe.moe_routed(share, h, cfg.routed)
        parts.append(np.asarray(y) - shared)
        ref_part = ref.moe_mixer(
            {**l_all, "up": l_all["up"][lo:hi], "down": l_all["down"][lo:hi]},
            h, m_all, held=(lo, hi), shared=False)
        np.testing.assert_allclose(parts[-1], np.asarray(ref_part), atol=TOL,
                                   rtol=TOL)
        assert int(counts[0]) + int(counts[1]) == 19 * 3
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole, atol=TOL,
                               rtol=TOL)


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
    monkeypatch.setattr(moe, "DENSE_BUCKETS", ())
    dense, c_dense = moe.moe_routed(layer, h, cfg.routed, live)
    monkeypatch.setattr(moe, "DENSE_BUCKETS", (8, 32))
    bucketed, c_bucketed = moe.moe_routed(layer, h, cfg.routed, live)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)
    sorted_, c_sorted = moe.moe_routed(layer, h, cfg.routed, live)
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


def _pools(cfg, pages=40, slots=2):
    kc, vc = PA.init_kv_pages(cfg.cache_layers, cfg.kv_heads, pages, PS,
                              cfg.head_dim)
    state = {n: jnp.zeros((cfg.state_layers, slots, *s))
             for n, s in cfg.state_shapes.items()}
    return kc, vc, state


@pytest.mark.parametrize("attn_impl", ["reference", "kernel"])
def test_pages_and_state_equal_the_reference_at_every_position(
        attn_impl, params, seq, ref_logits):
    """Prefill 12 tokens of a padded 16 (row 1 of a batch whose row 0 is
    another prompt), put K/V in pages and the state in slot rows, then
    decode the rest token by token: every position's logits are the
    reference's full forward.  Query heads 4 over K/V heads 2, through
    the jnp route and the interpreted kernel."""
    cfg = hybrid_cfg()
    kc, vc, state = _pools(cfg)
    p_len = 12
    ids = np.zeros((2, 16), np.int32)
    ids[0, :5] = seq[10:15]
    ids[1, :p_len] = seq[:p_len]
    lens = jnp.asarray([5, p_len])
    logits, ks, vs, extras = T.forward_prefill(cfg, params, jnp.asarray(ids),
                                               lens)
    np.testing.assert_allclose(np.asarray(logits[1]), ref_logits[p_len - 1],
                               atol=TOL, rtol=TOL)
    assert ks.shape == (2, 2, 16, 2, 8)    # cache layers x B x T x KV x Dh
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8],
                         [9, 10, 11, 12, 13, 14, 15, 16]], jnp.int32)
    kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, table, lens)
    state = {n: extras["state"][n] for n in state}   # slot = row
    # row 0 idles through the decode: its state must stay what it was
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


def test_prefill_state_is_the_references_state(ref, weights, params, seq):
    cfg = hybrid_cfg()
    ids = np.zeros((1, 16), np.int32)
    ids[0, :9] = seq[:9]
    _, _, _, extras = T.forward_prefill(cfg, params, jnp.asarray(ids),
                                        jnp.asarray([9]))
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


@pytest.mark.parametrize("h, kv, d", [(4, 2, 8), (6, 3, 64), (32, 2, 128),
                                      (3, 3, 64)])
def test_decode_kernel_with_fewer_kv_heads(h, kv, d):
    """The interpreted kernel against the jnp reference: rep query heads
    of a K/V head on the query rows, with and without lane groups of
    several heads, a padded last group, rep above and below 8."""
    ks = jax.random.split(jax.random.key(h * d), 3)
    b, pages, maxp = 3, 12, 3
    shape = PA.kv_pool_shape(2, kv, pages, PS, d)
    kc, vc = (jax.random.normal(k, shape) for k in ks[:2])
    q = jax.random.normal(ks[2], (b, h, d))
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    lens = jnp.asarray([11, 6, 0])
    for layer in (0, 1):
        want = PA.ragged_paged_attention(q, kc, vc, layer, table, lens,
                                         impl="reference", kv_heads=kv)
        got = PA.ragged_paged_attention(q, kc, vc, layer, table, lens,
                                        impl="kernel", kv_heads=kv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    # and the reference is attention with each K/V head repeated
    k = PA._gather_context(kc, 0, table, kv, d)[:1, :, :11]
    v = PA._gather_context(vc, 0, table, kv, d)[:1, :, :11]
    k, v = (jnp.repeat(x, h // kv, axis=1) for x in (k, v))
    p = jax.nn.softmax(jnp.einsum("hd,hkd->hk", q[0], k[0]) * d ** -0.5, -1)
    np.testing.assert_allclose(
        np.asarray(PA.ragged_paged_attention(
            q, kc, vc, 0, table, lens, impl="reference", kv_heads=kv)[0]),
        np.asarray(jnp.einsum("hk,hkd->hd", p, v[0])), atol=2e-5, rtol=2e-5)


# -- the engine ---------------------------------------------------------------------


def _greedy(ref, weights, prompt, n):
    out = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(n):
            out.append(int(jnp.argmax(
                ref.logits_fn(weights, jnp.asarray(out), M)[-1])))
    return out[len(prompt):]


def test_engine_serves_the_reference_greedy_tokens(ref, weights, params):
    """Requests join mid-flight, finish at different steps, and a slot is
    reused after retirement (5 requests through 2 slots): every request's
    tokens are the reference's greedy tokens, so a reused slot's state
    row was written whole by its prefill and an idle row's state never
    moved."""
    reg = MetricsRegistry("hybrid")
    eng = ServingEngine(
        hybrid_cfg(), params,
        ServingConfig(max_slots=2, page_size=PS, num_pages=24,
                      max_prompt_len=16, max_new_tokens=6, prefill_batch=2),
        registry=reg)
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
        assert got[rid] == _greedy(ref, weights, prompt, n)
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


def test_decode_span_says_what_the_step_touched(params):
    from paddle_tpu.telemetry import tracing

    tracing.configure_tracing(enabled=True)
    try:
        tracing.get_tracer().clear()
        eng = ServingEngine(
            hybrid_cfg(), params,
            ServingConfig(max_slots=3, page_size=PS, num_pages=24,
                          max_prompt_len=8, max_new_tokens=4,
                          prefill_batch=2), registry=MetricsRegistry("s"))
        eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)
        spans = [s for s in tracing.get_tracer().spans
                 if s.name == "serve_decode"]
        assert spans
        for s in spans:
            a = s.args
            assert a["kv_heads"] == 2 and a["cache_layers"] == 2
            assert a["state_slots"] == a["batch"] == 2
            assert 0 < a["moe_assignments"] <= 2 * 3 * 2
            assert 0 < a["experts_touched"] <= a["moe_assignments"]
        pre = [s for s in tracing.get_tracer().spans
               if s.name == "serve_prefill"][0]
        assert pre.args["moe_assignments"] > 0
    finally:
        tracing.configure_tracing(enabled=False)
        tracing.get_tracer().clear()


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
    kc, vc, state = _pools(cfg, pages=64, slots=slots)
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
        ServingEngine(hybrid_cfg(), params, ServingConfig(
            max_slots=2, page_size=PS, num_pages=24, max_prompt_len=8,
            max_new_tokens=4, **serving))
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
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=PS, num_pages=24, max_prompt_len=12,
            max_new_tokens=4, **serving), registry=MetricsRegistry("c"))
        for prompt, res in zip(prompts, eng.generate(prompts, 3)):
            full = prompt + res.tokens
            logits = T.forward(cfg, params, jnp.asarray([full]))
            assert res.tokens == [int(t) for t in jnp.argmax(
                logits[0, len(prompt) - 1:-1], axis=-1)]


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
    eng = ServingEngine(cfg, params, ServingConfig(
        max_slots=2, page_size=PS, num_pages=16, max_prompt_len=8,
        max_new_tokens=4), registry=MetricsRegistry("h"))
    prompt = [5, 17, 3, 9]
    res = eng.generate([prompt], 4)[0]
    logits = T.forward(cfg, params, jnp.asarray([prompt + res.tokens]))
    assert res.tokens == [int(t) for t in jnp.argmax(logits[0, 3:-1], -1)]


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
    eng = ServingEngine(cfg, p, ServingConfig(
        max_slots=2, page_size=PS, num_pages=16, max_prompt_len=8,
        max_new_tokens=4), registry=MetricsRegistry("d"))
    assert eng.cache.state == {} and eng.cache.state_bytes_per_slot == 0
    res = eng.generate([[3, 7, 1]], 3)[0]
    logits = T.forward(cfg, p, jnp.asarray([[3, 7, 1] + res.tokens]))
    assert res.tokens == [int(t) for t in jnp.argmax(logits[0, 2:-1], -1)]


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
    import io
    import json

    from paddle_tpu.serving.__main__ import main

    parts = {k: v for k, v in M.items() if k not in (
        "vocab_size", "num_layers", "num_heads", "embed_dim", "mlp_dim",
        "max_seq_len")}
    monkeypatch.setattr("sys.stdin", io.StringIO("5 17 3\n"))
    assert main(["--random", "--vocab", "97", "--embed", "32", "--layers",
                 "6", "--heads", "4", "--max_new_tokens", "4", "--seed", "7",
                 "--model_json", json.dumps(parts)]) == 0
    served = [int(t) for t in
              capsys.readouterr().out.strip().split(":")[1].split()]
    cfg = T.TransformerConfig(
        vocab_size=97, num_layers=6, num_heads=4, embed_dim=32, mlp_dim=128,
        max_seq_len=256, remat=False, **parts)
    weights = T.init_params(cfg, jax.random.key(7))
    out = [5, 17, 3]
    for _ in range(4):
        out.append(int(jnp.argmax(
            T.forward(cfg, weights, jnp.asarray([out]))[0, -1])))
    assert served == out[3:]
