"""A pattern of Mamba-2, attention and dense-MLP layers that REPEATS its
period, with the four scalars of the Granite 4.0-H block (an embedding
multiplier, a softmax scale that is not head_dim^-1/2, a residual
multiplier, a divisor of the logits), against the plain float32 reference
``benchmarks/references/granite_hybrid.py`` on seeded random weights at a
toy size: the forward, prefill + decode through pages and state rows at
every position, the engine under requests that join mid-flight, each
scalar in turn set to 1 (must miss), and the ROLLED pattern walk (a scan
over the repeats of the period, ``TransformerConfig.pattern_roll``)
against the unrolled one on the same weights re-laid.

Tolerances.  Program and reference both compute in float32 here, so they
differ by the order of summation only: ``TOL`` = 2e-4 on logits of unit
spread.  The rolled and the unrolled walk run the same operations in the
same order on the same numbers: 1e-6.

Summed seconds (the tier-1 command in this sandbox): 71; every forward is
compiled once and shared through ``lm_toy.jitted``.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import export
from paddle_tpu.telemetry import MetricsRegistry

import lm_toy
from lm_toy import PS

TOL = 2e-4
PAD = 32    # the reference's one compiled length
V = 97
SCALARS = dict(embed_multiplier=12.0, attn_scale=1 / 24,
               residual_multiplier=0.22, logits_divisor=8.0)
M = dict(vocab_size=V, num_layers=12, num_heads=4, kv_heads=2, head_dim=16,
         embed_dim=64, mlp_dim=96, max_seq_len=128, norm="rms",
         norm_eps=1e-5, positions="none", mlp="swiglu", tie_embeddings=True,
         pattern="M-*-M-" * 2, mamba_heads=4, mamba_head_dim=16,
         mamba_state=16, mamba_groups=1, mamba_conv=4, mamba_chunk=8,
         **SCALARS, init={"qk_gain": 2.0, "out_gain": 8.0})


def ssm_cfg(**kw):
    return T.TransformerConfig(
        **{**{k: v for k, v in M.items() if k != "init"}, "remat": False,
           **kw})


ref, weights, params, seq, ref_logits = lm_toy.fixtures(
    "granite_hybrid", M, 11, seq_len=30, pad=PAD)


# -- the program is the reference ----------------------------------------------------


@pytest.mark.parametrize("kind, i", [("M", 6), ("*", 8), ("-", 9)])
def test_each_mixer_equals_the_reference(kind, i, ref, weights, params):
    """Layer ``i`` (of the second repeat) alone: the program's mixer over
    its slice of the position's stack against the reference's."""
    from paddle_tpu.ops import mamba2

    cfg = ssm_cfg()
    layer = T.layers_of(cfg, params["blocks"])[i]
    h = jax.random.normal(jax.random.key(3), (2, 21, 64))
    one = jax.jit(lambda l, x: ref._MIXERS[ref.KINDS[kind]](l, x, M))
    want = np.stack([np.asarray(one(ref.layer_of(weights, i, M), h[b]))
                     for b in range(2)])

    def mixer(h, layer):
        if kind == "*":
            q, k, v = T._qkv(cfg, h, layer, None)
            return T._attention(cfg, q, k, v, None).reshape(
                2, 21, -1) @ layer["wo"]
        if kind == "-":
            return T._mlp(cfg, h, layer)[0]
        return T._mamba_mixer(
            cfg, h, layer,
            lambda x, w, b: mamba2.conv_prefill(x, w, b)[0],
            lambda *a: mamba2.ssd_prefill(*a, chunk=8)[0])

    np.testing.assert_allclose(np.asarray(jax.jit(mixer)(h, layer)), want,
                               atol=TOL, rtol=TOL)


def test_forward_equals_the_reference(params, seq, ref_logits):
    cfg = ssm_cfg()
    assert cfg.pattern_roll == (6, 2)
    got = lm_toy.jitted(T.forward, cfg)(params, jnp.asarray([seq]))[0]
    np.testing.assert_allclose(np.asarray(got), ref_logits, atol=TOL,
                               rtol=TOL)
    # the draw goes through the multipliers: logits of about unit spread
    assert 0.5 < float(np.std(ref_logits)) < 2.0


@pytest.mark.parametrize("field", sorted(SCALARS))
def test_no_multiplier_is_invisible(field, params, seq, ref_logits):
    """Any of the four scalars at 1 (the softmax scale at its default,
    head_dim^-1/2) misses the reference by far more than the tolerance."""
    cfg = ssm_cfg(**{field: None if field == "attn_scale" else 1.0})
    got = lm_toy.jitted(T.forward, cfg)(params, jnp.asarray([seq]))[0]
    assert float(np.max(np.abs(np.asarray(got) - ref_logits))) > 100 * TOL


@pytest.mark.parametrize("attn_impl, p_len", [("reference", 9),
                                              ("kernel", 16)])
def test_pages_and_state_equal_the_reference_at_every_position(
        attn_impl, p_len, params, seq, ref_logits):
    """Prefill ``p_len`` tokens (row 1 of a padded batch), K/V into pages
    at the rolled walk's cache layers and each Mamba layer's state into
    its row of the pools, then decode the rest token by token: every
    position's logits are the reference's full forward."""
    cfg = ssm_cfg()
    ks, handed, state = lm_toy.walk_positions(
        cfg, params, seq, ref_logits, p_len, 16, attn_impl, TOL)
    assert ks.shape == (2, 2, 16, 2, 16)   # cache layers x B x T x KV x Dh
    assert handed == set(state) == {"ssm", "conv"}
    assert state["ssm"].shape == (4, 2, 4, 16, 16)
    assert all(np.abs(np.asarray(v[:, 1])).max() > 0 for v in state.values())


def _engine(params, cfg, reg=None, **kw):
    return lm_toy.engine(
        cfg, params, reg or MetricsRegistry("ssm"),
        **{**dict(max_slots=3, page_size=PS, num_pages=40, max_prompt_len=16,
                  max_new_tokens=6, prefill_batch=2), **kw})


def test_engine_serves_the_references_tokens_and_counts_the_state(
        ref, weights, params):
    """Five requests through three slots, one joining mid-flight: every
    request's tokens are the reference's greedy tokens (prefill, then
    decode through the engine's own pools); the state's bytes are counted
    for a Mamba layer as for any kind; the ready span says how the
    programs walk the pattern."""
    cfg, reg = ssm_cfg(), MetricsRegistry("ssm")
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, V, n)]
               for n in (7, 16, 3, 1, 12)]
    news = [6, 3, 5, 2, 4]

    def serve():
        eng = _engine(params, cfg, reg)
        ids = [eng.submit(prompts[0], news[0])]
        eng.step()
        eng.step()
        ids += [eng.submit(p, n) for p, n in zip(prompts[1:], news[1:])]
        eng.run_until_idle()
        return eng, {r.id: r.tokens for r in eng.results()}, ids

    (eng, got, ids), spans = lm_toy.traced(serve)
    for rid, prompt, n in zip(ids, prompts, news):
        assert got[rid] == lm_toy.greedy(ref, weights, M, prompt, n, PAD)
    assert eng.cache.k.shape == PA.kv_pool_shape(2, 2, 40, PS, 16)
    assert {n: v.shape for n, v in eng.cache.state.items()} == {
        "ssm": (4, 3, 4, 16, 16), "conv": (4, 3, 3, 96)}
    per_slot = 4 * 4 * (4 * 16 * 16 + 3 * 96)
    assert reg.get("serve_state_bytes_per_slot").value() == per_slot
    # a prefill writes its rows', a decode step reads and writes its live
    # rows'
    steps = sum(n - 1 for n in news)
    assert reg.get("serve_state_bytes_total").value(kind="mamba") \
        == per_slot * (len(prompts) + 2 * steps)
    decodes = spans["serve_decode"]
    assert all(s.args["state_bytes"] == 2 * per_slot * s.args["state_slots"]
               for s in decodes)
    assert sum(s.args["state_bytes"] for s in spans["serve_prefill"]) \
        == per_slot * len(prompts)
    (ready,) = spans["engine_ready"]
    assert (ready.args["pattern_period"], ready.args["pattern_repeats"]) \
        == (6, 2)


@pytest.mark.parametrize("route", ["reference", "kernel"])
def test_the_decode_recurrence_serves_the_references_tokens_by_either_route(
        route, ref, monkeypatch):
    """At a state of whole lanes the decode step's recurrence is one
    kernel a layer on a TPU (``ops/pallas/ssd.py``; interpreted here, the
    pool's row traced by the rolled walk): by it as by ``ssd_step`` the
    engine serves the reference's greedy tokens, live and idle rows mixed,
    and the census says which ran."""
    m = {**M, "mamba_state": 128}
    weights = lm_toy.draw(ref, m, 11)
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, V, n)] for n in (7, 16, 3, 12)]
    news = [6, 3, 5, 4]
    got, routes = lm_toy.serve_on_route(
        monkeypatch, route, ssm_cfg(mamba_state=128),
        ref.program_tree(weights), prompts, news, max_slots=3, page_size=PS,
        num_pages=40, max_prompt_len=16, max_new_tokens=6, prefill_batch=2)
    for tokens, prompt, n in zip(got, prompts, news):
        assert tokens == lm_toy.greedy(ref, weights, m, prompt, n, PAD)
    # two M positions of the period, one decode program
    assert {k: v for k, v in routes.items() if k[0] == "ssd_step"} \
        == {("ssd_step", route): 2}


# -- the rolled walk is the unrolled walk --------------------------------------------


def _unrolled(monkeypatch):
    """From here no pattern rolls: the predicate names no kind."""
    monkeypatch.setattr(T, "_ROLLED", frozenset())


def test_rolled_and_unrolled_walks_agree(monkeypatch, params, seq):
    """The same weights, stacked by position for the scan and re-laid one
    tree a layer for the unrolled walk: ``forward``, a padded prefill, its
    K/V and state in pool order row for row, and 8 decode steps through
    the pools, every pool compared whole after the last."""
    cfg = ssm_cfg()
    ids = np.zeros((2, 16), np.int32)
    ids[0, :5], ids[1, :11] = seq[10:15], seq[:11]
    lens = jnp.asarray([5, 11])
    per_row = 6
    table = jnp.arange(1, 2 * per_row + 1, dtype=jnp.int32).reshape(2, -1)

    def run(cfg, params):
        out = {"forward": jax.jit(functools.partial(T.forward, cfg))(
            params, jnp.asarray([seq]))}
        logits, ks, vs, extras = jax.jit(functools.partial(
            T.forward_prefill, cfg))(params, jnp.asarray(ids), lens)
        out.update(prefill=logits, ks=ks, vs=vs, **{
            "left_" + n: v for n, v in extras["state"].items()})
        kc, vc, _ = lm_toy.pools(cfg, pages=2 * per_row + 1)
        kc, vc = PA.write_prefill_kv(kc, vc, ks, vs, table, lens)
        state = extras["state"]
        decode = jax.jit(functools.partial(T.forward_decode, cfg,
                                           attn_impl="reference"))
        pos = np.asarray([5, 11])
        for t in range(8):
            logits, kc, vc, extras = decode(
                params, jnp.asarray([seq[15 + t], seq[11 + t]]),
                jnp.asarray(pos + t), jnp.asarray(pos + t + 1), table, kc,
                vc, state=state)
            state = extras["state"]
            out[f"decode_{t}"] = logits
        out.update(kc=kc, vc=vc, **state)
        return out

    rolled = run(cfg, params)
    assert len(params["blocks"]) == 6
    layers = T.layers_of(cfg, params["blocks"])
    _unrolled(monkeypatch)
    assert cfg.pattern_roll == (12, 1) and len(layers) == 12
    flat = run(cfg, dict(params, blocks=layers))
    assert set(rolled) == set(flat)
    for name in rolled:
        np.testing.assert_allclose(
            np.asarray(rolled[name]), np.asarray(flat[name]), atol=1e-6,
            rtol=1e-6, err_msg=name)
    assert rolled["ssm"].shape == (4, 2, 4, 16, 16)
    assert all(float(jnp.abs(rolled[n][i]).max()) > 0
               for n in ("ssm", "conv") for i in range(4))


def test_the_loss_differentiates_through_the_roll(monkeypatch, params, seq):
    cfg = ssm_cfg()
    batch = jnp.asarray([seq[:17], seq[13:]])
    grad = lambda: jax.jit(jax.value_and_grad(
        functools.partial(T.loss_fn, cfg)))
    loss, grads = grad()(params, batch)
    got = T.layers_of(cfg, grads["blocks"])
    layers = T.layers_of(cfg, params["blocks"])
    _unrolled(monkeypatch)
    flat_loss, flat = grad()(dict(params, blocks=layers), batch)
    np.testing.assert_allclose(float(loss), float(flat_loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(flat["blocks"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-4)


# -- which patterns roll ---------------------------------------------------------------


def _cfg(pattern, **kw):
    return T.TransformerConfig(**{**dict(
        vocab_size=50, num_layers=len(pattern), num_heads=2, embed_dim=16,
        mlp_dim=24, max_seq_len=32, pattern=pattern, mamba_heads=2,
        mamba_head_dim=4, mamba_state=8, positions="rotary", norm="rms"),
        **kw})


@pytest.mark.parametrize("pattern, kw, roll, why", [
    ("M-M-M-M-M-*-M-M-M-M-" * 4, {}, (20, 4), None),
    ("*-*-*-", {}, (2, 3), None),
    ("MMMM", {}, (1, 4), None),
    ("M-*-", {}, (4, 1), "not two or more repeats"),
    ("MEMEM*EMEMEM*", dict(moe_experts=4, moe_router="sigmoid"), (13, 1),
     "not two or more repeats"),
    ("*E*E", dict(moe_experts=4, moe_router="sigmoid"), (4, 1),
     r"kind \['E'\]"),
    ("KK", dict(kda_heads=2, positions="none"), (2, 1), r"kind \['K'\]"),
    ("*-*-", dict(cca_taps=(2, 2)), (4, 1), "cca_taps"),
    ("*E*E", dict(moe_experts=4, moe_router="softmax_topk",
                  moe_router_hidden=8), (4, 1), r"kind \['E'\]"),
    ("*-*-", dict(block_len=4, mask_id=0), (4, 1), "block_len"),
    (None, {}, (0, 1), "no layer pattern"),
])
def test_which_patterns_roll(pattern, kw, roll, why):
    """The rule is the pattern's own shape and what its layers keep: two
    or more whole periods of kinds the rolled walk carries."""
    cfg = _cfg(pattern, **kw) if pattern else T.TransformerConfig()
    assert cfg.pattern_roll == roll
    said = T._keeps_unrolled(cfg)
    if why is None:
        assert said is None
        blocks = jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.key(0)))["blocks"]
        assert len(blocks) == roll[0]
        assert all(a.shape[0] == roll[1] for b in blocks
                   for a in jax.tree.leaves(b))
    else:
        assert said is not None and re.search(why, said), said


def test_scalars_are_refused_where_nothing_applies_them():
    with pytest.raises(NotImplementedError, match="residual_multiplier"):
        T.TransformerConfig(residual_multiplier=0.5)
    with pytest.raises(NotImplementedError, match="attn_scale"):
        T.TransformerConfig(attn_scale=0.1, attn_impl="ring")
    # the three others apply to a homogeneous stack too
    cfg = lm_toy.small_cfg(embed_multiplier=3.0, attn_scale=0.2,
                           logits_divisor=4.0)
    base = lm_toy.small_cfg()
    p = lm_toy.jitted(T.init_params, base)(jax.random.key(0))
    ids = jnp.asarray([[5, 17, 3, 9]])
    a = lm_toy.jitted(T.forward, cfg)(p, ids)
    b = lm_toy.jitted(T.forward, base)(p, ids)
    assert float(jnp.abs(a - b).max()) > 1e-3


# -- the layout travels ------------------------------------------------------------------


def test_blocks_are_laid_by_position_and_back(params):
    cfg = ssm_cfg()
    layers = T.layers_of(cfg, params["blocks"])
    assert [set(l) for l in layers[:3]] == [set(params["blocks"][j])
                                            for j in range(3)]
    again = T.lay_blocks(cfg, layers)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(params["blocks"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # layer 8 = position 2 of repeat 1
    np.testing.assert_array_equal(np.asarray(layers[8]["wq"]),
                                  np.asarray(params["blocks"][2]["wq"][1]))
    # init_params draws a layer's leaves as the unrolled walk's would be
    once = dataclasses.replace(cfg, pattern="M-*-M-", num_layers=6)
    assert len(jax.eval_shape(lambda: T.init_params(
        once, jax.random.key(0)))["blocks"]) == 6


def test_a_servable_keeps_the_layout_and_an_older_one_is_relaid(
        tmp_path, params, seq):
    """Exported and loaded, the tree is the one the walk reads; a
    servable written one tree a LAYER (before the walk rolled) is stacked
    by position at load."""
    cfg = ssm_cfg()
    want = lm_toy.jitted(T.forward, cfg)(params, jnp.asarray([seq]))[0]
    export.export_servable(str(tmp_path / "new"), cfg, params)
    export.export_servable(str(tmp_path / "old"), cfg, dict(
        params, blocks=T.layers_of(cfg, params["blocks"])))
    for name in ("new", "old"):
        got_cfg, got = export.load_servable(str(tmp_path / name))
        assert got_cfg == cfg and len(got["blocks"]) == 6
        np.testing.assert_allclose(
            np.asarray(lm_toy.jitted(T.forward, cfg)(
                got, jnp.asarray([seq]))[0]), np.asarray(want), atol=1e-6)


def test_the_cli_serves_a_rolled_pattern(monkeypatch, capsys):
    """``python -m paddle_tpu.serving --random --model_json`` with a
    pattern of two periods and the four scalars serves its own forward's
    greedy tokens."""
    cfg = lm_toy.cli_serves_the_forward(monkeypatch, capsys, 61, 8, dict(
        pattern="M-*-M-*-", mlp="swiglu", norm="rms", positions="none",
        kv_heads=2, mamba_heads=4, mamba_head_dim=8, mamba_state=8,
        embed_multiplier=3.0, attn_scale=0.1, residual_multiplier=0.5,
        logits_divisor=2.0))
    assert cfg.pattern_roll == (4, 2)
