"""A looped decoder stack (the same layers run ``loop_steps`` times over
shared weights, one K/V cache layer per (pass, layer)) with RMSNorm
sandwiches, rotary positions, SwiGLU, an untied head and an explicit
``head_dim``, against the plain float32 reference
``benchmarks/references/ouro.py`` on seeded random weights at a toy size:
full forward, prefill + decode through the paged cache, chunked prefill,
a prefix-cache hit, gradients, the exit gate's parameters.

Tolerances.  Program and reference both compute in float32 here (the CPU
backend's dots are exact float32), so they differ by the order of
summation only: logits of unit scale agree to ``TOL`` = 2e-4.  bfloat16
in the reference's place moves them by 1e-2 or more
(``test_bf16_would_fail``), so the tolerance tells the stated precision
from the one below it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import ServingConfig
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy
from lm_toy import PS

TOL = 2e-4
PAD = 24    # the reference's one compiled length: 23 positions, 19 served
LAYERS, STEPS = 3, 3
M = {"vocab_size": 96, "num_layers": LAYERS, "num_heads": 4, "head_dim": 12,
     "embed_dim": 32, "mlp_dim": 48, "norm_eps": 1e-6, "rope_theta": 1e6,
     "loop_steps": STEPS}


def looped_cfg(**kw):
    base = dict(vocab_size=M["vocab_size"], num_layers=LAYERS, num_heads=4,
                head_dim=12, embed_dim=32, mlp_dim=48, max_seq_len=4096,
                norm="rms", norm_eps=1e-6, norm_sandwich=True,
                positions="rotary", rope_theta=1e6, mlp="swiglu",
                tie_embeddings=False, loop_steps=STEPS, remat=False)
    base.update(kw)
    return T.TransformerConfig(**base)


ref, weights, params, seq, ref_logits = lm_toy.fixtures(
    "ouro", M, 2**31 + 7, seq_len=23, pad=PAD)


def _pools(cfg):
    return lm_toy.pools(cfg, pages=16)[:2]      # no state beside the pages


def _table(n_tokens, maxp=8, first=1):
    pt = np.zeros((1, maxp), np.int32)
    n = -(-n_tokens // PS)
    pt[0, :n] = np.arange(first, first + n)
    return jnp.asarray(pt)


def _decode_rest(cfg, params, seq, start, pt, kc, vc):
    """Teacher-forced decode of seq[start:]; logits at those positions."""
    out, decode = [], lm_toy.jitted(T.forward_decode, cfg)
    for p in range(start, len(seq)):
        logits, kc, vc = decode(
            params, jnp.asarray(seq[p:p + 1]), jnp.asarray([p]),
            jnp.asarray([p + 1]), pt, kc, vc)
        out.append(np.asarray(logits)[0])
    return np.stack(out), kc, vc


def test_forward_equals_the_reference(params, seq, ref_logits):
    got = lm_toy.jitted(T.forward, looped_cfg())(params,
                                                 jnp.asarray(seq)[None])
    np.testing.assert_allclose(np.asarray(got)[0], ref_logits, atol=TOL,
                               rtol=0)


def test_bf16_would_fail(ref, weights, seq, ref_logits):
    """The precision below float32 misses ``TOL`` by two orders."""
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                       weights)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.logits_fn(low, jnp.asarray(seq), M))
    assert np.abs(got - ref_logits).max() > 50 * TOL


@pytest.mark.parametrize("route", ["prefill", "chunks", "prefix_hit"])
def test_paged_cache_routes_equal_the_reference_at_every_position(
        route, params, seq, ref_logits):
    """Prompt of 13 tokens through one of the three prompt passes, then
    10 decode steps through the paged cache: the logits of every position
    from the prompt's last on equal the reference's full forward."""
    cfg, p_len = looped_cfg(), 13
    kc, vc = _pools(cfg)
    pt = _table(len(seq))
    ids = jnp.asarray(seq[:p_len])[None]
    prefill = lm_toy.jitted(T.forward_prefill, cfg)
    if route == "prefill":
        logits, ks, vs = prefill(params, ids, jnp.asarray([p_len]))
        assert ks.shape == (STEPS * LAYERS, 1, p_len, 4, 12)
        kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, pt, jnp.asarray([p_len]))
    else:
        if route == "chunks":       # 5 + 5 + 3 tokens, the same pages
            cuts = [(0, 5), (5, 10), (10, 13)]
        else:
            # another sequence's prefill left the first two pages (8
            # tokens) resident; this row maps them and computes the tail
            donor = _table(8)
            _, ks, vs = prefill(params, ids[:, :8], jnp.asarray([8]))
            kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, donor,
                                         jnp.asarray([8]))
            pt = jnp.asarray(np.concatenate(
                [[1, 2], np.arange(5, 11)])[None].astype(np.int32))
            cuts = [(8, 13)]
        for a, b in cuts:
            chunk = np.zeros((1, 5), np.int32)
            chunk[0, :b - a] = seq[a:b]
            logits, kc, vc = lm_toy.jitted(T.forward_prefill_chunk, cfg)(
                params, jnp.asarray(chunk), jnp.asarray([a]),
                jnp.asarray([b - a]), pt, kc, vc)
    np.testing.assert_allclose(np.asarray(logits)[0], ref_logits[p_len - 1],
                               atol=TOL, rtol=0)
    rest, _, _ = _decode_rest(cfg, params, seq, p_len, pt, kc, vc)
    np.testing.assert_allclose(rest, ref_logits[p_len:], atol=TOL, rtol=0)


def test_cache_layer_t_l_holds_pass_t_of_layer_l(ref, weights, params, seq):
    cfg = looped_cfg()
    ids = jnp.asarray(seq)[None]
    prefill = lm_toy.jitted(T.forward_prefill, cfg)
    _, ks, _ = prefill(params, ids, jnp.asarray([len(seq)]))
    # layer 0 of pass t reads the normed output of pass t-1 (the
    # embedding for pass 0): its K is the reference's, at row t * LAYERS
    with jax.default_matmul_precision("highest"):
        hs = ref.hidden_states(weights, jnp.asarray(seq), M)
        inputs = [weights["wte"][jnp.asarray(seq)], *hs[:-1]]
        for t, x in enumerate(inputs):
            k = ref._rope(
                (ref._rms(x, weights["g1"][0], M["norm_eps"])
                 @ weights["wk"][0]).reshape(len(seq), 4, 12),
                M["rope_theta"])
            np.testing.assert_allclose(np.asarray(ks[t * LAYERS, 0]),
                                       np.asarray(k), atol=TOL, rtol=0)
    # perturbing cache layer (1, 1) changes only what reads it: the
    # decode step rewrites the earlier cache layers identically and the
    # new token's K/V from there on (and the logits) differently
    kc, vc = _pools(cfg)
    pt = _table(len(seq))
    p_len = len(seq) - 1
    _, ks, vs = prefill(params, ids[:, :p_len], jnp.asarray([p_len]))
    kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, pt, jnp.asarray([p_len]))
    hit = 1 * LAYERS + 1
    base, kc1, _ = _decode_rest(cfg, params, seq, p_len, pt, kc, vc)
    moved, kc2, _ = _decode_rest(cfg, params, seq, p_len, pt,
                                 kc.at[hit, :, 1:3].add(0.5), vc)
    kc1, kc2 = np.asarray(kc1), np.asarray(kc2)
    page, off = int(pt[0, p_len // PS]), p_len % PS
    for c in range(STEPS * LAYERS):
        same = np.array_equal(kc1[c, :, page, off], kc2[c, :, page, off])
        assert same == (c <= hit), c
    assert np.abs(moved - base).max() > 1e-3


def _gpt2_forward_as_it_was(cfg, params, ids):
    """The GPT-2 block and forward as ``models/transformer.py`` wrote them
    before the block had parts (PR 25's tree), restated."""
    from paddle_tpu.ops import attention as attn_ops
    from paddle_tpu.ops.nn import layer_norm as ln

    b, t = ids.shape
    nh, hd = cfg.num_heads, cfg.embed_dim // cfg.num_heads
    x = params["embed"][ids] + params["pos_embed"][:t][None]

    def block(x, layer):
        h = ln(x, layer["ln1_g"], layer["ln1_b"])
        q = (h @ layer["wq"]).reshape(b, t, nh, hd)
        k = (h @ layer["wk"]).reshape(b, t, nh, hd)
        v = (h @ layer["wv"]).reshape(b, t, nh, hd)
        a = attn_ops.dot_product_attention(
            q, k, v, mask=attn_ops.causal_mask(t, t))
        x = x + a.reshape(b, t, nh * hd) @ layer["wo"]
        h = ln(x, layer["ln2_g"], layer["ln2_b"])
        h = jax.nn.gelu(h @ layer["w_in"] + layer["b_in"])
        return x + h @ layer["w_out"] + layer["b_out"], None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = ln(x, params["ln_f_g"], params["ln_f_b"])
    return x @ params["embed"].T


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_default_parts_are_the_gpt2_block_bit_for_bit(dtype):
    cfg = T.TransformerConfig(vocab_size=64, num_layers=3, num_heads=2,
                              embed_dim=32, mlp_dim=64, max_seq_len=32,
                              dtype=dtype, remat=False, scan_unroll=1)
    assert (cfg.head_dim, cfg.loop_steps, cfg.cache_layers) == (16, 1, 3)
    params = T.init_params(cfg, jax.random.key(3))
    assert sorted(params) == ["blocks", "embed", "ln_f_b", "ln_f_g",
                              "pos_embed"]
    assert sorted(params["blocks"]) == [
        "b_in", "b_out", "ln1_b", "ln1_g", "ln2_b", "ln2_g", "w_in", "w_out",
        "wk", "wo", "wq", "wv"]
    # biases that are not zero, so that their place in the sum shows
    params["blocks"]["b_out"] = 0.3 + params["blocks"]["b_out"]
    params["blocks"]["b_in"] = 0.1 + params["blocks"]["b_in"]
    ids = jax.random.randint(jax.random.key(4), (2, 17), 0, 64)
    old = jax.jit(lambda p, i: _gpt2_forward_as_it_was(cfg, p, i))(params, ids)
    new = jax.jit(lambda p, i: T.forward(cfg, p, i))(params, ids)
    assert np.array_equal(np.asarray(old, np.float32),
                          np.asarray(new, np.float32))
    last, ks, _ = jax.jit(lambda p, i: T.forward_prefill(
        cfg, p, i, jnp.asarray([17, 17])))(params, ids)
    assert ks.shape == (3, 2, 17, 2, 16)
    assert np.array_equal(np.asarray(last, np.float32),
                          np.asarray(old, np.float32)[:, -1])


def test_gradients_collect_every_pass(ref, weights, params, seq):
    """``jax.grad(loss_fn)`` of the looped toy equals the reference's
    gradient: each shared weight collects its ``loop_steps`` uses."""
    cfg = looped_cfg()
    ids = jnp.asarray(seq)[None]

    def ref_loss(w):
        logits = ref.logits_fn(w, ids[0, :-1], M)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[0, 1:, None], axis=-1)[:, 0]
        return jnp.mean(lse - tgt)

    # the eager function differentiated, then compiled: one dispatch
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(jax.value_and_grad(ref_loss))(weights)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: T.loss_fn(cfg, p, ids)))(params)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    want = ref.program_tree(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for path, g in flat:
        w = want
        for k in path:
            w = w[k.key]
        name = jax.tree_util.keystr(path)
        if "exit_" in name:     # the gate is off the loss's path
            assert not np.asarray(g).any(), name
            continue
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4 * scale, rtol=0, err_msg=name)
    # a one-pass stack has a third of the uses: the gradient differs
    one = jax.jit(jax.grad(lambda p: T.loss_fn(
        dataclasses.replace(cfg, loop_steps=1),
        {k: v for k, v in p.items() if not k.startswith("exit_")},
        ids)))(params)
    assert float(jnp.abs(one["blocks"]["wq"] - got["blocks"]["wq"]).max()) > 1e-3


def test_gate_parameters_give_the_reference_exit_distribution(
        ref, weights, params, seq):
    """The tree's ``exit_w`` / ``exit_b`` over the state that closes each
    pass (read through an identity head off a ``t``-pass stack) give the
    reference's lambda_t and exit distribution."""
    ids = jnp.asarray(seq)[None]
    with jax.default_matmul_precision("highest"):
        lam_ref, p_ref = ref.exit_distribution(weights, ids[0], M)
    assert np.allclose(np.asarray(p_ref).sum(0), 1.0, atol=1e-6)
    eye = dict(params, head=jnp.eye(M["embed_dim"]))
    lam = []
    for t in range(1, STEPS + 1):
        h = lm_toy.jitted(T.forward, looped_cfg(loop_steps=t))(eye, ids)[0]
        lam.append(jax.nn.sigmoid(h @ params["exit_w"] + params["exit_b"]))
    lam = np.asarray(jnp.stack(lam))
    np.testing.assert_allclose(lam, np.asarray(lam_ref), atol=TOL, rtol=0)
    stay = np.cumprod(1 - lam, axis=0)
    p = lam * np.concatenate([np.ones_like(stay[:1]), stay[:-1]])
    p[-1] = np.concatenate([np.ones_like(stay[:1]), stay[:-1]])[-1]
    np.testing.assert_allclose(p, np.asarray(p_ref), atol=TOL, rtol=0)


@pytest.mark.parametrize("bad, err", [
    (dict(early_exit_threshold=0.9), NotImplementedError),
    (dict(loop_steps=0), ValueError),
    (dict(norm="batch"), ValueError),
    (dict(positions="alibi"), ValueError),
    (dict(mlp="relu"), ValueError)])
def test_what_the_stack_cannot_do_raises(bad, err):
    with pytest.raises(err) as e:
        looped_cfg(**bad)
    if err is NotImplementedError:
        assert "varies by token" in str(e.value)


def test_parameter_counts():
    def count(cfg):
        tree = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    e, f, h, v = 32, 48, 48, M["vocab_size"]
    layer = 4 * e * h + 3 * e * f + 4 * e
    assert count(looped_cfg()) == LAYERS * layer + 2 * v * e + e + e + 1
    # the published model: 48 x 2048, 16 heads x 128, SwiGLU 5632, 4 passes
    assert count(looped_cfg(
        vocab_size=49152, num_layers=48, num_heads=16, head_dim=128,
        embed_dim=2048, mlp_dim=5632, loop_steps=4)) == 2_667_974_657
    cfg = looped_cfg()
    shard = T.param_shardings(cfg)
    tree = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    assert jax.tree.structure(tree) == jax.tree.structure(
        shard, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


@pytest.mark.parametrize("mode", [
    dict(), dict(prefill_chunk_tokens=5), dict(prefix_cache=True),
    dict(prefix_cache=True, prefill_chunk_tokens=5)],
    ids=["plain", "chunked", "prefix", "prefix+chunked"])
def test_engine_serves_the_reference_greedy_tokens(mode, ref, weights,
                                                   params, seq):
    cfg = looped_cfg()
    reg = MetricsRegistry("looped")
    prompts = [seq[:13].tolist(), seq[3:12].tolist(), seq[:13].tolist()]

    def serve():
        eng = lm_toy.engine(
            cfg, params, reg, max_slots=3, page_size=PS, num_pages=40,
            max_prompt_len=16, max_new_tokens=6, prefill_batch=2, **mode)
        # the second call: a prefix hit when the cache is on
        return eng, eng.generate(prompts[:2]), eng.generate(prompts[2:])

    (eng, first, again), spans = lm_toy.traced(serve)
    spans = spans["serve_decode"]
    assert eng.cache.k.shape == PA.kv_pool_shape(STEPS * LAYERS, 4, 40, PS, 12)
    assert eng.cache.k.shape == (STEPS * LAYERS, 1, 40, PS, 48)
    want = [lm_toy.greedy(ref, weights, M, p, 6, PAD) for p in prompts[:2]]
    assert [r.tokens for r in first] == want
    assert again[0].tokens == want[0]
    if mode.get("prefix_cache"):
        assert eng.cache.prefix.hit_tokens == 12       # three full pages
        # a hit's skipped recompute: the layer weights count once a pass
        blocks = sum(int(x.size) for x in jax.tree.leaves(params["blocks"]))
        rest = sum(int(x.size) for x in jax.tree.leaves(params)) - blocks
        assert reg.get("serve_prefill_flops_saved").value() == pytest.approx(
            2.0 * (STEPS * blocks + rest) * 12)
    # spans, counter and gauge say what stack ran
    assert spans and all(s.args["loop_steps"] == STEPS
                         and s.args["cache_layers"] == STEPS * LAYERS
                         for s in spans)
    assert reg.get("serve_layer_passes_total").value() == sum(
        s.args["batch"] for s in spans) * STEPS * LAYERS
    assert reg.get("serve_kv_bytes_per_token").value() == (
        2 * STEPS * LAYERS * 4 * 12 * 4) == eng.kv_bytes_per_token


def _lane_group_cfg(steps):
    """Two layers of three 64-wide heads: two heads a lane group, the
    second group half padding; gelu / LayerNorm / learned positions (the
    GPT-2 block) at ``steps`` 1 and the looped stack above it."""
    return T.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=3, head_dim=64, embed_dim=32,
        mlp_dim=64, max_seq_len=64, remat=False, loop_steps=steps)


@pytest.mark.parametrize("mode", [
    dict(), dict(prefill_chunk_tokens=5), dict(prefix_cache=True),
    dict(prefix_cache=True, prefill_chunk_tokens=5)],
    ids=["plain", "chunked", "prefix", "prefix+chunked"])
@pytest.mark.parametrize("steps", [1, 4])
def test_engine_tokens_equal_full_forward_argmax(steps, mode):
    """Incremental decode over the stacked, lane-grouped pools is
    token-for-token the argmax of repeated full ``forward``, whichever
    prompt pass filled the cache; a copy-on-write of a shared page in the
    middle of a generation changes nothing."""
    cfg = _lane_group_cfg(steps)
    params = T.init_params(cfg, jax.random.key(3))
    rng = np.random.default_rng(steps)
    head = rng.integers(1, 64, 9).tolist()        # two full pages + 1
    prompts = [head + rng.integers(1, 64, 4).tolist(), head[:7],
               head + rng.integers(1, 64, 2).tolist()]
    eng = lm_toy.engine(
        cfg, params, max_slots=3, page_size=PS, num_pages=40,
        max_prompt_len=16, max_new_tokens=6, prefill_batch=2, **mode)
    assert eng.cache.k.shape == (2 * steps, 2, 40, PS, 128)
    got = [r.tokens for r in eng.generate(prompts[:2], max_new_tokens=5)]
    eng.submit(prompts[2], max_new_tokens=5)
    eng.step()                                     # admitted and prefilled
    if mode.get("prefix_cache"):
        assert eng.cache.prefix.hit_tokens == 8    # the two full pages
        slot = next(iter(eng.cache._slot_pages))
        shared = eng.cache.slot_pages(slot)[0]
        assert eng.cache.allocator.refcount(shared) > 1
        private = eng.cache.cow_page(slot, 0)
        assert private != shared
        np.testing.assert_array_equal(np.asarray(eng.cache.k[:, :, private]),
                                      np.asarray(eng.cache.k[:, :, shared]))
    eng.run_until_idle()
    got.append(eng.results()[0].tokens)
    for prompt, tokens in zip(prompts, got):
        assert tokens == lm_toy.forward_argmax(cfg, params, prompt, tokens,
                                               PAD)


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
@pytest.mark.parametrize("steps", [1, 4])
def test_compiled_serving_programs_update_the_pools_in_place(steps, program):
    """The structure of the compiled program, pools donated: both pools
    are aliased input to output, and the temporaries stay below ONE cache
    layer's bytes (the CPU backend's gather of the live pages and its
    expanded scatter need no more) — so no loop slices a layer's pool
    out, stacks one back in, or re-lays a pool out.  (The tree before the
    pools rode the carry held 2.3 / 1.3 whole pools of temp here at
    ``steps`` 1 / 4.)  The TPU facts are PERF.md §4's AOT lines."""
    cfg = _lane_group_cfg(steps)
    params = T.init_params(cfg, jax.random.key(0))
    kc, vc = PA.init_kv_pages(cfg.cache_layers, 3, 256, 8, 64)
    pool = kc.size * kc.dtype.itemsize
    b, maxp = 2, 4
    i32 = lambda *shape: jnp.ones(shape, jnp.int32)
    if program == "decode":
        fn = lambda p, kc, vc, ids, pos, lens, pt: T.forward_decode(
            cfg, p, ids, pos, lens, pt, kc, vc)
        args = (i32(b), i32(b), i32(b), i32(b, maxp))
    else:
        fn = lambda p, kc, vc, ids, starts, lens, pt: T.forward_prefill_chunk(
            cfg, p, ids, starts, lens, pt, kc, vc)
        args = (i32(b, 8), i32(b), i32(b), i32(b, maxp))
    mem = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, kc, vc, *args).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 2 * pool
    assert mem.temp_size_in_bytes < pool // cfg.cache_layers


def test_memory_report_and_servable_take_cache_layers(tmp_path, params):
    from paddle_tpu.analysis.memory import serving_memory_report
    from paddle_tpu.serving.export import export_servable, load_servable

    cfg = looped_cfg()
    scfg = ServingConfig(page_size=PS, num_pages=10)
    rep = serving_memory_report(cfg, scfg)
    assert rep["kv_pool_bytes"] == 2 * STEPS * LAYERS * 4 * 10 * PS * 12 * 4
    one = serving_memory_report(dataclasses.replace(cfg, loop_steps=1), scfg)
    assert rep["kv_pool_bytes"] == STEPS * one["kv_pool_bytes"]
    export_servable(str(tmp_path / "s"), cfg, params)
    cfg2, params2 = load_servable(str(tmp_path / "s"))
    assert cfg2 == cfg and cfg2.head_dim == 12 and cfg2.cache_layers == 9
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    np.testing.assert_array_equal(np.asarray(params2["blocks"]["ln1_post_g"]),
                                  np.asarray(params["blocks"]["ln1_post_g"]))


def test_serving_cli_serves_a_looped_stack(monkeypatch, capsys):
    """``python -m paddle_tpu.serving --random --model_json`` builds the
    looped stack from the JSON's fields and serves the greedy tokens of
    the same seeded weights' full forward."""
    parts = dict(norm="rms", norm_sandwich=True, positions="rotary",
                 mlp="swiglu", head_dim=12, tie_embeddings=False,
                 loop_steps=STEPS)
    cfg = lm_toy.cli_serves_the_forward(monkeypatch, capsys, 96, LAYERS,
                                        parts)
    assert cfg.cache_layers == LAYERS * STEPS
