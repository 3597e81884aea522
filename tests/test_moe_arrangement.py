"""The routed expert sublayer (``parallel/moe.py:moe_routed``).  Which
arrangement it picks for a pass's rows: every held expert over every row
(masked by the combine weight) up to a limit, above it the assignments
sorted by expert through the grouped product (``ops/pallas/
grouped_matmul.py``: the kernel on a TPU, ``lax.ragged_dot`` here; the
cases that run the sorted side run it on both routes).  The masked product does
``num_experts / top_k`` times the assigned work, so past 32 x the limit
falls in proportion; it is never raised.  And what the arrangements owe:
each equals a plain loop over experts, they agree over a held share, and
the shares of a layer cut over devices add up to the uncut layer — on
layers drawn here and on the expert sublayers of ``test_hybrid_lm.py``'s
and ``test_kda_lm.py``'s toys (each file keeps its ``M``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import moe

import lm_toy
import test_hybrid_lm
import test_kda_lm

D, F = 16, 8
SOLAR = test_kda_lm.M   # sigmoid top-4 of 16 SwiGLU, a gated shared expert
TOL = 2e-4          # float32 against float32: the order of summation
TOL_SOLAR = 1e-4    # what tests/test_kda_lm.py holds its toy to


def _tree(cfg, key=None):
    """A routed layer's tree at toy widths: shapes alone without a key."""
    shapes = {"router": (D, cfg.num_experts),
              "w_in": (cfg.num_held, D, F), "w_gate": (cfg.num_held, D, F),
              "w_out": (cfg.num_held, F, D)}
    if key is None:
        return {n: jax.ShapeDtypeStruct(s, jnp.float32)
                for n, s in shapes.items()}
    keys = jax.random.split(key, len(shapes))
    return {n: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[-2])
            for k, (n, s) in zip(keys, shapes.items())}


def _grouped(cfg, t, impl="auto"):
    fn = lambda p, x, live: moe.moe_routed(p, x, cfg, live, impl=impl)
    text = str(jax.make_jaxpr(fn)(
        _tree(cfg), jax.ShapeDtypeStruct((t, D), jnp.float32),
        jax.ShapeDtypeStruct((t,), jnp.bool_)))
    return "ragged_dot" in text or "pallas_call" in text


# (num_experts, top_k, held) of the benchmark's four routed configurations
# and the rows of the programs their engines compile (decode step or block
# pass, the prefill ladder's members), with a row count either side of
# each limit.  ``on_chip``: the arrangement where the kernel runs; off the
# chip the sorted side is ``ragged_dot``'s ("reference") and the few-row
# side, which is the kernel's alone, stays masked.
NEMO3N, SDAR30, ZAYA1, SOLAR2 = ((128, 6, (0, 64)), (128, 8, None),
                                 (16, 1, None), (320, 8, (0, 40)))
SPARSE = moe._SPARSE_SHARE


@pytest.mark.parametrize("name,router,t,on_chip", [
    *[(name, router, t, "masked")
      for name, router in (("nemo3n", NEMO3N), ("sdar30", SDAR30),
                           ("zaya1", ZAYA1))
      for t in (64, 256, 512, 2048)],
    ("nemo3n", NEMO3N, 2049, "kernel"),
    ("sdar30", SDAR30, 4096, "kernel"),
    # 32 rows of 8 / 320 touch 55.5% of the held experts in expectation:
    # the decode step of ``solar-open2-250b`` reads only those
    ("solar2", SOLAR2, 32, "kernel"),
    ("solar2", SOLAR2, 74, "kernel"),       # 84.6%
    ("solar2", SOLAR2, 75, "masked"),       # 85.0%
    ("nemo3n", NEMO3N, 39, "kernel"),       # 84.6%: a step of 39 slots
    ("nemo3n", NEMO3N, 40, "masked"),       # 85.3%
    ("zaya1", ZAYA1, 29, "kernel"),         # 84.6%
    ("zaya1", ZAYA1, 30, "masked"),         # 85.6%
    ("solar2", SOLAR2, 1638, "masked"),
    ("solar2", SOLAR2, 1639, "kernel"),
    ("solar2", SOLAR2, 2048, "kernel"),
    ("solar2", SOLAR2, 4096, "kernel"),
    ("solar2", SOLAR2, 8192, "kernel"),
])
def test_arrangement_by_router_and_rows(name, router, t, on_chip):
    experts, top_k, held = router
    cfg = moe.RoutedConfig(num_experts=experts, top_k=top_k, held=held,
                           act="silu", gated=True)
    w_in = _tree(cfg)["w_in"]
    over = t > min(moe.DENSE_MAX_TOKENS, moe._DENSE_WASTE * top_k // experts)
    share = 1 - (1 - top_k / experts) ** t
    assert on_chip == ("kernel" if over or share < SPARSE else "masked")
    assert moe.product_path(t, cfg, w_in, "kernel") == on_chip
    assert moe.product_path(t, cfg, w_in, "reference") == (
        "reference" if over else "masked")
    assert _grouped(cfg, t, "kernel") is (on_chip == "kernel")
    assert _grouped(cfg, t) is over


def test_the_limit_is_read_when_called(monkeypatch):
    """Tests set the two module constants by ``monkeypatch``: a router
    under 32 x runs the arrangement they ask for, and a wasteful one never
    gets MORE masked rows than ``DENSE_MAX_TOKENS``."""
    toy = moe.RoutedConfig(num_experts=8, top_k=2, act="silu", gated=True)
    wide = moe.RoutedConfig(num_experts=320, top_k=8, held=(0, 40),
                            act="silu", gated=True)
    assert not _grouped(toy, 64) and not _grouped(wide, 64)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)
    assert _grouped(toy, 64) and _grouped(wide, 64)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 4096)
    assert not _grouped(toy, 4096)
    assert _grouped(wide, 2048) and not _grouped(wide, 1638)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("held", [(0, 10), (30, 40), None])
def test_masked_and_grouped_agree_past_32x(monkeypatch, held, impl):
    """80 experts, top-2 (40 x): 1,640 rows go through the grouped product
    (``impl``: ``ragged_dot`` | the kernel, interpreted) where the limit is
    1,638; the masked product over the same rows (the limit's rule lifted)
    gives the same result and counts, padding rows routed nowhere."""
    cfg = moe.RoutedConfig(num_experts=80, top_k=2, held=held, act="silu",
                           gated=True)
    tree = _tree(cfg, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1640, D), jnp.float32)
    live = jnp.arange(1640) % 5 != 2
    assert _grouped(cfg, 1640)
    assert moe.product_path(1640, cfg, tree["w_in"], impl) == impl
    got, counts = _routed(cfg, live, None, impl)(tree, x)
    monkeypatch.setattr(moe, "_DENSE_WASTE", 1 << 30)
    assert not _grouped(cfg, 1640)
    with jax.default_matmul_precision("highest"):
        want, counts_masked = _routed(cfg, live)(tree, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_masked))
    assert not np.asarray(got)[~np.asarray(live)].any()
    assert int(counts[0] + counts[1]) == int(live.sum()) * 2


def test_few_rows_of_a_wide_router_take_the_kernel_and_agree():
    """Ten rows of top-2 of 64 touch a quarter of the held experts in
    expectation: where the kernel runs they go through it (idle rows
    routed nowhere, the untouched experts never read), and the result and
    counts are the masked product's, which the same rows get off the chip."""
    from paddle_tpu.ops import pallas

    cfg = moe.RoutedConfig(num_experts=64, top_k=2, held=(8, 24), act="silu",
                           gated=True)
    tree = _tree(cfg, jax.random.key(13))
    x = jax.random.normal(jax.random.key(14), (10, D), jnp.float32)
    live = jnp.arange(10) != 3
    out = {}
    for impl, path in (("kernel", "kernel"), ("reference", "masked")):
        with pallas.capture_routes() as routes:
            out[path] = _routed(cfg, live, None, impl)(tree, x)
        assert routes == {("moe_experts", path): 1}
    (got, counts), (want, counts_masked) = out["kernel"], out["masked"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_masked))
    assert not np.asarray(got)[3].any() and int(counts[2]) < cfg.num_held


# -- the layer against a loop over experts, and its shares --------------------------


def _routed(cfg, *rest):
    """``moe_routed`` under ``cfg``, compiled: a closure a call, so the
    module constants a test patches are read when it is traced."""
    return jax.jit(lambda p, x: moe.moe_routed(p, x, cfg, *rest))


def _routed_layer(key, score, gated, experts=8, d=16, f=12):
    ks = jax.random.split(key, 5)
    p = {"router": jax.random.normal(ks[0], (d, experts)),
         "w_in": jax.random.normal(ks[1], (experts, d, f)) * d ** -0.5,
         "w_out": jax.random.normal(ks[2], (experts, f, d)) * f ** -0.5}
    if score == "sigmoid":
        p["router_bias"] = 0.1 * jax.random.normal(ks[3], (experts,))
    if gated:
        p["w_gate"] = jax.random.normal(ks[4], (experts, d, f)) * d ** -0.5
    return p


def _expert_loop(p, x, cfg):
    """The layer as a loop over experts, in plain form."""
    logits = x @ p["router"]
    s = jax.nn.sigmoid(logits) if cfg.score == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(s + p.get("router_bias", 0.0), cfg.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.scale
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        c = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        h = x @ p["w_in"][e]
        h = jax.nn.silu(x @ p["w_gate"][e]) * h if cfg.gated \
            else moe._act(cfg.act, h)
        y = y + c * (h @ p["w_out"][e])
    return y


@pytest.mark.parametrize("rows", [24, moe.DENSE_MAX_TOKENS + 8])
@pytest.mark.parametrize("score,gated", [("sigmoid", False),
                                         ("softmax", True)])
def test_routed_layer_equals_a_loop_over_experts(score, gated, rows):
    """Both arrangements of ``moe_routed`` (every expert over every row;
    rows sorted by expert) for both kinds of layer."""
    cfg = moe.RoutedConfig(num_experts=8, top_k=2, scale=1.5, score=score,
                           gated=gated, act="silu" if gated else "relu2")
    p = _routed_layer(jax.random.key(5), score, gated)
    x = jax.random.normal(jax.random.key(6), (rows, 16))
    got, counts = _routed(cfg)(p, x)
    want = jax.jit(lambda p, x: _expert_loop(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)
    assert int(counts[0]) == 2 * rows and int(counts[1]) == 0


@pytest.mark.parametrize("score,gated", [("sigmoid", False),
                                         ("softmax", True),
                                         ("mlp_top1", True)])
def test_the_shares_add_up_to_the_uncut_layer(score, gated):
    """Expert parallelism's unit: every device routes over all experts
    and computes its own share's part; the parts of all shares add up to
    what the uncut layer gives.  ``mlp_top1``: the experts chosen by an
    MLP router over its carried state, one a token, weighing its own
    probability (not renormalised), gated — the whole against the plain
    reference's sublayer (``references/zaya.py``)."""
    x = jax.random.normal(jax.random.key(8), (2, 9, 16))
    if score == "mlp_top1":
        ref = lm_toy.load_reference("zaya")
        m = dict(vocab_size=31, num_layers=2, num_heads=2, kv_heads=2,
                 head_dim=4, embed_dim=16, mlp_dim=12, cca_taps=[2, 2],
                 moe_experts=8, moe_router_hidden=6, norm_eps=1e-5,
                 init={"router_gain": 4.0})
        l = lm_toy.draw(ref, m, 3)["layers"][0]["moe"]
        p = {ref._MOE.get(n, ref._RES.get(n, n)): v for n, v in l.items()}
        prev = jax.random.normal(jax.random.key(9), (2, 9, 6))
        kw = dict(num_experts=8, top_k=1, score="softmax", gated=True,
                  act="silu", router_hidden=6, renorm=False)
        carry, k = moe.router_state(p, x, prev), 1
        with jax.default_matmul_precision("highest"):
            want, r = jax.jit(lambda l, x, r: ref.moe_mixer(l, x, r, m))(
                l, x.reshape(18, 16), prev.reshape(18, 6))
        np.testing.assert_allclose(np.asarray(carry).reshape(18, 6),
                                   np.asarray(r), atol=TOL, rtol=TOL)
    else:
        kw = dict(num_experts=8, top_k=3, score=score, gated=gated,
                  act="silu" if gated else "relu2")
        p, carry, k, want = (_routed_layer(jax.random.key(7), score, gated),
                             None, 3, None)
    whole, counts = _routed(moe.RoutedConfig(**kw), None, carry)(p, x)
    if want is not None:
        np.testing.assert_allclose(np.asarray(whole).reshape(18, 16),
                                   np.asarray(want), atol=TOL, rtol=TOL)
    parts, held = 0.0, 0
    for lo, hi in ((0, 3), (3, 4), (4, 8)):
        share = {k_: (v[lo:hi] if k_ in ("w_in", "w_out", "w_gate") else v)
                 for k_, v in p.items()}
        y, c = _routed(moe.RoutedConfig(held=(lo, hi), **kw), None, carry)(
            share, x)
        parts, held = parts + y, held + int(c[0])
        assert int(c[0]) + int(c[1]) == 18 * k
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=TOL, rtol=TOL)
    assert held == int(counts[0]) == 18 * k


# -- the expert sublayers of two toys ------------------------------------------------


def test_the_shares_add_up():
    """``test_hybrid_lm.py``'s toy (sigmoid top-3 of 16 relu2 experts)
    over two devices: the routed parts of shares [0, 8) and [8, 16), with
    the shared expert counted once, are the uncut layer — in the program
    and in the reference."""
    ref, M = lm_toy.load_reference("nemotron_h"), test_hybrid_lm.M
    m_all = {**M, "moe_held": [0, 16]}
    w_all = lm_toy.draw(ref, m_all, 11)
    i = M["pattern"].index("E")
    l_all = w_all["layers"][i]
    h = jax.random.normal(jax.random.key(4), (19, 32))
    mixer = lambda held, **kw: jax.jit(
        lambda l: ref.moe_mixer(l, h, m_all, held=held, **kw))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(mixer(None)(l_all))
        shared = np.asarray(mixer((0, 0))(
            {**l_all, "up": l_all["up"][:0], "down": l_all["down"][:0]}))
    p_all = ref.program_tree(w_all)["blocks"][i]
    parts = []
    for lo, hi in ((0, 8), (8, 16)):
        share = {**p_all, "w_in": p_all["w_in"][lo:hi],
                 "w_out": p_all["w_out"][lo:hi]}
        cfg = test_hybrid_lm.hybrid_cfg(moe_held=(lo, hi))
        y, counts = _routed(cfg.routed)(share, h)
        parts.append(np.asarray(y) - shared)
        ref_part = mixer((lo, hi), shared=False)(
            {**l_all, "up": l_all["up"][lo:hi], "down": l_all["down"][lo:hi]})
        np.testing.assert_allclose(parts[-1], np.asarray(ref_part), atol=TOL,
                                   rtol=TOL)
        assert int(counts[0]) + int(counts[1]) == 19 * 3
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole, atol=TOL,
                               rtol=TOL)


def test_the_eight_shares_add_up_to_the_uncut_sublayer():
    """The deployment's eight chips: 40 experts, share k holds [5k, 5k +
    5); every share routes over all 40 and computes its own experts' part;
    with the shared expert counted ONCE they add up to the uncut
    reference's expert sublayer, and so do the program's shares."""
    ref = lm_toy.load_reference("solar_open2")
    m = dict(SOLAR, moe_experts=40, moe_top_k=8, moe_held=None)
    whole = lm_toy.draw(ref, m, 3)["layers"][1]
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
            cfg = test_kda_lm.kda_cfg(moe_experts=40, moe_top_k=8,
                                      moe_held=(lo, hi))
            y, counts = _routed(cfg.routed)(tree, h)
            prog = prog + y
            assert int(counts[0] + counts[1]) == 23 * 8
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=TOL_SOLAR, rtol=TOL_SOLAR)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(uncut),
                               atol=TOL_SOLAR, rtol=TOL_SOLAR)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("rows, held, note", [
    (300, (0, 8), "half the experts: more than one round of the gather"),
    (300, (4, 6), "an eighth of the experts"),
    (300, None, "every expert held: the bound is every assignment"),
    (700, (0, 1), "one expert, every token sent to it: past the bound"),
])
def test_grouped_experts_over_a_held_share(monkeypatch, rows, held, note,
                                           impl):
    """Above ``DENSE_MAX_TOKENS`` rows the assignments to HELD experts are
    gathered (a static bound of rows at a time, as often as the count
    asks) and multiplied group by group (``impl``: ``ragged_dot`` | the
    kernel, interpreted): the result is the masked product's and the
    reference's."""
    ref = lm_toy.load_reference("solar_open2")
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 64)
    cfg = test_kda_lm.kda_cfg(moe_held=held)
    lo, hi = cfg.routed.held
    full = lm_toy.draw(ref, dict(SOLAR, moe_held=None), 11)
    params = ref.program_tree(lm_toy.draw(ref, SOLAR, 11))
    layer = dict(params["blocks"][1], **{
        ref._PROGRAM[n]: full["layers"][1][n][lo:hi]
        for n in ("up", "gate", "down")})
    if held == (0, 1):      # every token's first choice is expert 0
        layer["router_bias"] = layer["router_bias"].at[0].set(10.0)
    h = jax.random.normal(jax.random.key(7), (rows, 32))
    live = jnp.arange(rows) % 7 != 3
    got, counts = _routed(cfg.routed, live, None, impl)(layer, h)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 4096)
    want, counts_dense = _routed(cfg.routed, live)(layer, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL_SOLAR, rtol=TOL_SOLAR)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_dense))
    if held == (0, 1):
        assert int(counts[0]) == int(live.sum()) and int(counts[3]) == int(
            live.sum())
