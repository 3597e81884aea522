"""The serving engine end to end: ragged paged attention vs the dense
reference, bit-exact incremental decode vs repeated full-context forward,
scheduler determinism + admission control, per-request telemetry with
TTFT/TPOT percentiles, strict inference, servable export, and the
``python -m paddle_tpu.serving`` CLI loop (subprocess, ``serving``
marker)."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer as T
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MemorySink, MetricsRegistry


def small_cfg(**kw):
    base = dict(vocab_size=64, num_layers=2, num_heads=2, embed_dim=32,
                mlp_dim=64, max_seq_len=64, remat=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def as_pool(pages, layers=1, layer=0, fill=0.0):
    """Logical [H, P, page_size, D] pages as a pool of ``kv_pool_shape``,
    placed at cache layer ``layer`` of ``layers`` (the others hold ``fill``;
    padding heads are zero, as in a pool only the program wrote).  Spells the layout on its own: head
    ``h`` is lane group ``h // g``, lanes ``(h % g) * D ...``."""
    pages = np.asarray(pages, np.float32)
    h, p, ps, d = pages.shape
    shape = PA.kv_pool_shape(layers, h, p, ps, d)
    g = max(1, min(128 // d, h))
    assert shape == (layers, -(-h // g), p, ps, g * d)
    pool = np.full(shape, fill, np.float32)
    pool[layer] = 0.0
    for head in range(h):
        lanes = slice((head % g) * d, (head % g + 1) * d)
        pool[layer, head // g, :, :, lanes] = pages[head]
    return pool


def make_paged(rng, lens, H=2, D=16, ps=8, maxp=4, pool=16, layers=1,
               layer=0):
    """Random contiguous K/V + their paged twin for ragged ``lens``."""
    B = len(lens)
    pt = np.zeros((B, maxp), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(-(-int(lens[b]) // ps)):
            pt[b, i] = nxt
            nxt += 1
    assert nxt <= pool
    kp = np.zeros((H, pool, ps, D), np.float32)
    vp = np.zeros((H, pool, ps, D), np.float32)
    full_k = rng.normal(size=(B, maxp * ps, H, D)).astype(np.float32)
    full_v = rng.normal(size=(B, maxp * ps, H, D)).astype(np.float32)
    for b in range(B):
        for t in range(int(lens[b])):
            kp[:, pt[b, t // ps], t % ps] = full_k[b, t]
            vp[:, pt[b, t // ps], t % ps] = full_v[b, t]
    return (as_pool(kp, layers, layer), as_pool(vp, layers, layer), pt,
            full_k, full_v)


# H, D, page_size, dtype of the blocked-kernel cases (H = the heads the
# cache holds): the interpret-mode toy (both heads in one lane group; its
# block is the whole table), chip_smoke's float32 case, the five serve
# cells' caches in the pools' bf16 (two heads a lane group at head_dim 64,
# one at 128; the 2- and 4-head caches at their longer blocks), and an odd
# head count (the last lane group half padding).  Beside each: the page
# slots a step covers at a table of 66 pages (``decode_block_pages``)
_BLOCK_SHAPES = {"h2_d16_p8_f32": (2, 16, 8, "float32"),
                 "h12_d64_p16_f32": (12, 64, 16, "float32"),
                 "h20_d64_p16_bf16": (20, 64, 16, "bfloat16"),
                 "h16_d128_p16_bf16": (16, 128, 16, "bfloat16"),
                 "h5_d64_p16_bf16": (5, 64, 16, "bfloat16"),
                 "h2_d128_p16_bf16": (2, 128, 16, "bfloat16"),
                 "h4_d128_p16_bf16": (4, 128, 16, "bfloat16")}
_BLOCK_PAGES = {"h2_d16_p8_f32": 66, "h12_d64_p16_f32": 16,
                "h20_d64_p16_bf16": 16, "h16_d128_p16_bf16": 8,
                "h5_d64_p16_bf16": 48, "h2_d128_p16_bf16": 64,
                "h4_d128_p16_bf16": 32}
_BLOCK_LENGTHS = ("idle", "one", "one_block", "block_plus_1", "whole_table",
                  "ragged")
_LAYERS, _LAYER = 3, 1  # the blocked cases' pools, and the layer addressed


@functools.lru_cache(maxsize=None)
def _blocked_case(shape, maxp):
    """(N, block tokens, lens, kernel rows, reference rows) of one batch
    holding every length of ``_BLOCK_LENGTHS``; run once per (shape, maxp)."""
    h, d, ps, dtype = _BLOCK_SHAPES[shape]
    dtype = jnp.dtype(dtype)
    n = PA.decode_block_pages(h, ps, d, dtype.itemsize, maxp)
    block, cap = n * ps, maxp * ps
    rng = np.random.default_rng(maxp)
    # a block as wide as the table: "block + 1" is the whole table too
    lens = np.array([0, 1, block, min(block + 1, cap), cap,
                     int(rng.integers(block + 2 if block + 2 < cap else 2,
                                      cap))], np.int32)
    used = -(-lens // ps)
    pool = 1 + int(used.sum()) + 5
    ids = rng.permutation(np.arange(1, pool))  # scattered, out of order
    pt = np.zeros((len(lens), maxp), np.int32)  # unused entries: null page
    at = 0
    for b, u in enumerate(used):
        pt[b, :u] = ids[at:at + u]
        at += u
    kp = rng.normal(size=(h, pool, ps, d)).astype(np.float32)
    vp = rng.normal(size=(h, pool, ps, d)).astype(np.float32)
    kp[:, 0] = vp[:, 0] = 0.0
    q = jnp.asarray(rng.normal(size=(len(lens), h, d)), dtype)
    ref = PA.ragged_paged_attention(
        q, jnp.asarray(as_pool(kp, _LAYERS, _LAYER), dtype),
        jnp.asarray(as_pool(vp, _LAYERS, _LAYER), dtype), _LAYER, pt, lens,
        impl="reference")
    # the kernel's pools: NaN on the null page of the layer it reads and
    # everywhere in the layers it must not touch
    poison = lambda a: jnp.asarray(
        as_pool(a, _LAYERS, _LAYER, fill=np.nan), dtype
    ).at[_LAYER, :, 0].set(jnp.nan)
    ker = PA.ragged_paged_attention(q, poison(kp), poison(vp),
                                    jnp.int32(_LAYER), pt, lens,
                                    impl="kernel", interpret=True)
    as_f32 = lambda a: np.asarray(a.astype(jnp.float32))
    return n, block, lens, as_f32(ker), as_f32(ref)


class TestRaggedPagedAttention:
    def test_reference_matches_dense_on_ragged_batch(self, rng_np):
        from paddle_tpu.ops.attention import dot_product_attention

        lens = np.array([1, 7, 20, 0], np.int32)
        kp, vp, pt, full_k, full_v = make_paged(rng_np, lens, layers=2,
                                                layer=1)
        q = rng_np.normal(size=(4, 2, 16)).astype(np.float32)
        out = PA.ragged_paged_attention_reference(q, kp, vp, 1, pt, lens)
        out = np.asarray(out)
        for b, n in enumerate(lens):
            if n == 0:
                assert np.allclose(out[b], 0.0)  # idle row: zeros, no NaNs
                continue
            dense = dot_product_attention(
                q[b][None, None], full_k[b:b + 1, :n], full_v[b:b + 1, :n])
            np.testing.assert_allclose(out[b], np.asarray(dense)[0, 0],
                                       rtol=2e-5, atol=2e-5)

    def test_kernel_matches_reference_on_ragged_batch(self, rng_np):
        lens = np.array([3, 8, 17, 25], np.int32)
        kp, vp, pt, _, _ = make_paged(rng_np, lens)
        q = rng_np.normal(size=(4, 2, 16)).astype(np.float32)
        ref = PA.ragged_paged_attention(q, kp, vp, 0, pt, lens,
                                        impl="reference")
        ker = PA.ragged_paged_attention(q, kp, vp, 0, pt, lens,
                                        impl="kernel", interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("case", _BLOCK_LENGTHS)
    @pytest.mark.parametrize("maxp", [18, 66])  # no multiple of a shorter N
    @pytest.mark.parametrize("shape", sorted(_BLOCK_SHAPES))
    def test_blocked_kernel_matches_reference(self, shape, maxp, case):
        """One row per length of interest against the jnp oracle, at cache
        layer 1 of 3: live pages scattered out of order over the pool,
        unused table entries on a NaN-poisoned null page and NaN in every
        other cache layer, none of which may reach the result."""
        _, _, ps, dtype = _BLOCK_SHAPES[shape]
        n, block, lens, ker, ref = _blocked_case(shape, maxp)
        assert n == min(_BLOCK_PAGES[shape], maxp) and block == n * ps
        assert n == maxp or maxp % n
        row = _BLOCK_LENGTHS.index(case)
        assert lens[row] == {"idle": 0, "one": 1, "one_block": block,
                             "block_plus_1": min(block + 1, maxp * ps),
                             "whole_table": maxp * ps,
                             "ragged": lens[row]}[case]
        tol = 2e-5 if dtype == "float32" else 2e-2
        assert np.isfinite(ker[row]).all()
        np.testing.assert_allclose(ker[row], ref[row], rtol=tol, atol=tol)
        if case == "idle":
            assert not ker[row].any()

    @pytest.mark.parametrize("kv_heads", [2, 4])
    def test_a_block_pass_over_a_few_head_cache(self, rng_np, kv_heads):
        """``block_paged_attention`` (``T`` = 4 positions a row folded into
        the query heads, 8 query heads a K/V head) over a 2- and a 4-head
        cache of 128 lanes at their longer blocks: a row inside its first
        block, one a token into its second, an idle one."""
        ps, d, t, maxp = 16, 128, 4, 80
        n = PA.decode_block_pages(kv_heads, ps, d, 2, maxp)
        assert n == {2: 64, 4: 32}[kv_heads]
        lens = np.array([n * ps + 1, 0, 37, maxp * ps - 5], np.int32)
        kp, vp, pt, _, _ = make_paged(rng_np, lens, H=kv_heads, D=d, ps=ps,
                                      maxp=maxp, pool=160, layers=2, layer=1)
        q = jnp.asarray(rng_np.normal(size=(4, t, 8 * kv_heads, d)),
                        jnp.bfloat16)
        run = functools.partial(
            PA.block_paged_attention, q, jnp.asarray(kp, jnp.bfloat16),
            jnp.asarray(vp, jnp.bfloat16), 1, pt, lens, kv_heads=kv_heads)
        ker = np.asarray(run(impl="kernel", interpret=True), np.float32)
        ref = np.asarray(run(impl="reference"), np.float32)
        np.testing.assert_allclose(ker, ref, rtol=2e-2, atol=2e-2)
        assert not ker[1].any()

    @pytest.mark.parametrize("shape", ["h2_d16_p8_f32", "h20_d64_p16_bf16",
                                       "h2_d128_p16_bf16"])
    def test_dead_rows_and_pages_never_reach_the_result(self, shape):
        """The kernel copies a row's live pages into a buffer it reuses:
        past a row's end the buffer holds the page's own tail, an earlier
        step's pages or nothing yet.  So poison what no live token owns —
        every page no row lists and every row of a last page past
        ``seq_len``, NaN in K, Inf in V — and hold the result to the
        oracle's over clean pools: weighting by ``p == 0`` is not enough."""
        h, d, ps, dtype = _BLOCK_SHAPES[shape]
        dtype, maxp = jnp.dtype(dtype), 80
        block = ps * PA.decode_block_pages(h, ps, d, dtype.itemsize, maxp)
        rng = np.random.default_rng(7)
        # a long row, then short ones that leave most of its buffer stale
        lens = np.array([maxp * ps - 3, 1, 0, min(block + ps + 1, maxp * ps),
                         ps, 5], np.int32)
        used = -(-lens // ps)
        pool = 1 + int(used.sum()) + 6
        ids = rng.permutation(np.arange(1, pool))
        pt = np.zeros((len(lens), maxp), np.int32)
        at = 0
        for b, u in enumerate(used):
            pt[b, :u] = ids[at:at + u]
            at += u
        kp = rng.normal(size=(h, pool, ps, d)).astype(np.float32)
        vp = rng.normal(size=(h, pool, ps, d)).astype(np.float32)
        dead = np.ones((pool, ps), bool)
        for b, n in enumerate(lens):
            for tok in range(int(n)):
                dead[pt[b, tok // ps], tok % ps] = False
        assert dead[0].all() and dead[ids[at:]].all() and dead.sum() > 7 * ps
        q = jnp.asarray(rng.normal(size=(len(lens), h, d)), dtype)
        pools = lambda k_fill, v_fill: [
            jnp.asarray(as_pool(np.where(dead[None, :, :, None], fill, a),
                                _LAYERS, _LAYER, fill=fill), dtype)
            for a, fill in ((kp, k_fill), (vp, v_fill))]
        ref = PA.ragged_paged_attention(q, *pools(0.0, 0.0), _LAYER, pt, lens,
                                        impl="reference")
        for k_fill, v_fill in ((np.nan, np.inf), (-np.inf, np.nan)):
            ker = PA.ragged_paged_attention(
                q, *pools(k_fill, v_fill), jnp.int32(_LAYER), pt, lens,
                impl="kernel", interpret=True)
            ker = np.asarray(ker.astype(jnp.float32))
            assert np.isfinite(ker).all()
            tol = 2e-5 if dtype == jnp.float32 else 2e-2
            np.testing.assert_allclose(
                ker, np.asarray(ref.astype(jnp.float32)), rtol=tol, atol=tol)

    # the heads the CACHE holds, page, head_dim, itemsize, the table's
    # width -> page slots a grid step covers (PERF.md §6, PR 39)
    @pytest.mark.parametrize("name,args,want", [
        ("gpt2-large", (20, 16, 64, 2, 64), 16),         # 10 lane groups
        ("ouro-2.6b", (16, 16, 128, 2, 18), 8),          # a megabyte a pass
        ("nemotron-3-nano", (2, 16, 128, 2, 48), 48),    # 2 K/V heads: table
        ("sdar-30b", (4, 16, 128, 2, 48), 32),
        ("zaya1-8b", (2, 16, 128, 2, 128), 64),
        ("chip_smoke", (12, 16, 64, 4, 66), 16),         # float32
        ("one head", (1, 16, 128, 2, 256), 128),
        ("table-capped", (2, 16, 128, 2, 20), 20),
        ("toy, table-capped", (2, 8, 16, 4, 4), 4),
        ("a page wider than a block", (2, 256, 16, 4, 4), 4),
        ("a page of many passes", (16, 256, 128, 2, 4), 1),
    ])
    def test_decode_block_pages_follows_the_shapes(self, name, args, want):
        """Whole MXU passes of 128 tokens, as many as make a step carry
        a megabyte of K and V at the cache's bytes a token."""
        assert PA.decode_block_pages(*args) == want
        h, ps, d, itemsize, maxp = args
        groups, lanes = PA.kv_pool_shape(1, h, 1, ps, d)[1::3]
        carried = want * ps * 2 * groups * lanes * itemsize
        assert want == maxp or carried >= 1 << 20
        assert want == 1 or (want * ps) % 128 == 0 or want == maxp

    def test_decode_block_pages_fits_the_vmem_budget(self):
        got = [PA.decode_block_pages(20, 16, 64, 2, 64, vmem_budget=kb << 10)
               for kb in (1, 256, 512, 1024, 2048, 1 << 20)]
        assert got == sorted(got) and got[0] == 1 and got[-1] == 16
        assert 1 < got[2] < 16                    # the budget binds in between
        few = [PA.decode_block_pages(2, 16, 128, 2, 128, vmem_budget=kb << 10)
               for kb in (64, 512, 6 << 10)]
        assert few == [1, 10, 64]
        # a step's K/V in VMEM: two landing buffers a pool and the block
        # the body holds, each slot a padded [H/g, page, g·D] tile
        assert PA.decode_block_pages(32, 16, 128, 4, 32) == 4  # float32

    @pytest.mark.parametrize("heads,head_dim,shape", [
        (20, 64, (36, 10, 1537, 16, 128)),   # gpt2-large: two heads a group
        (16, 128, (192, 16, 145, 16, 128)),  # ouro-2.6b: plain head-major
        (5, 64, (36, 3, 1537, 16, 128)),     # odd: the last group half padding
        (2, 16, (36, 1, 1537, 16, 32)),      # fewer heads than the lanes hold
        (4, 256, (36, 4, 1537, 16, 256)),    # wider than the lanes
    ])
    def test_pool_shape_is_lane_whole(self, heads, head_dim, shape):
        layers, _, pages, ps, _ = shape
        assert PA.kv_pool_shape(layers, heads, pages, ps, head_dim) == shape
        kc, vc = PA.init_kv_pages(2, heads, 3, ps, head_dim, jnp.bfloat16)
        assert kc.shape == vc.shape == (2, *shape[1:2], 3, *shape[3:])

    def test_write_then_read_round_trip(self, rng_np):
        kc, vc = PA.init_kv_pages(3, 2, 8, 4, 16)
        pt = jnp.asarray(np.array([[1, 2], [3, 0]], np.int32))
        k = rng_np.normal(size=(2, 2, 16)).astype(np.float32)
        v = rng_np.normal(size=(2, 2, 16)).astype(np.float32)
        # row 0 writes position 5 (page 2, off 1); row 1 position 2
        kc1, vc1 = PA.write_decode_kv(kc, vc, jnp.asarray(k),
                                      jnp.asarray(v), 1, pt,
                                      jnp.asarray([5, 2]))
        # both heads share lane group 0: head h in lanes [16 h, 16 h + 16)
        np.testing.assert_allclose(
            np.asarray(kc1)[1, 0, 2, 1, :32].reshape(2, 16), k[0])
        np.testing.assert_allclose(
            np.asarray(vc1)[1, 0, 3, 2, :32].reshape(2, 16), v[1])
        want = as_pool(np.zeros((2, 8, 4, 16)), 3)
        want[1, 0, 2, 1, :32], want[1, 0, 3, 2, :32] = k[0].ravel(), k[1].ravel()
        np.testing.assert_array_equal(np.asarray(kc1), want)

    @pytest.mark.parametrize("heads,head_dim", [(4, 64), (3, 64), (2, 128)])
    @pytest.mark.parametrize("write", ["decode", "chunk", "prefill"])
    def test_write_touches_only_its_cache_layer(self, rng_np, write, heads,
                                                head_dim):
        """A write at cache layer 1 leaves every other layer's pages, and
        every page of layer 1 it does not name, bit-identical; what it
        wrote reads back through the oracle's gather; a whole-stack
        prefill writes every layer."""
        layers, pages, ps, b, t = 3, 12, 4, 2, 8
        shape = PA.kv_pool_shape(layers, heads, pages, ps, head_dim)
        kc = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
        vc = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0]], np.int32))
        lens = jnp.asarray([7, 5])
        new = lambda *lead: jnp.asarray(rng_np.normal(
            size=(*lead, heads, head_dim)).astype(np.float32))
        if write == "decode":
            k, v = new(b), new(b)
            kc1, vc1 = PA.write_decode_kv(kc, vc, k, v, 1, pt, lens - 1)
            named = [(1, 2, int(lens[0] - 1) % ps), (1, 5, int(lens[1] - 1) % ps)]
        elif write == "chunk":
            k, v = new(b, t), new(b, t)
            starts = jnp.asarray([2, 0])
            kc1, vc1 = PA.write_chunk_kv(kc, vc, k, v, 1, pt, starts,
                                         lens - starts)
        else:
            k, v = new(layers, b, t), new(layers, b, t)
            kc1, vc1 = PA.write_prefill_kv(kc, vc, k, v, pt, lens)
        before, after = np.asarray(kc), np.asarray(kc1)
        changed = np.argwhere((before != after).any(axis=(1, 4)))
        where = {tuple(int(i) for i in c) for c in changed}  # (layer, page, row)
        rows = lambda b_, lo, hi: {(int(pt[b_, p // ps]), p % ps)
                                   for p in range(lo, hi)}
        if write == "decode":
            assert where == set(named)
        where -= {(l, 0, r) for l in range(layers) for r in range(ps)}
        if write == "chunk":  # the null page takes the padding
            assert where == {(1, *r) for r in rows(0, 2, 7) | rows(1, 0, 5)}
        elif write == "prefill":  # whole pages of every layer
            assert where == {(l, int(pg), r) for l in range(layers)
                             for pg in (1, 2, 4, 5) for r in range(ps)}
        assert not (np.asarray(vc) != np.asarray(vc1)).any(
            axis=(1, 4))[[l for l in range(layers)
                          if write != "prefill" and l != 1]].any()
        # read back through the oracle's gather: [B, H, maxp * ps, D]
        got = np.asarray(PA._gather_context(kc1, 1, pt, heads, head_dim))
        if write == "decode":
            for b_ in range(b):
                np.testing.assert_array_equal(got[b_, :, int(lens[b_]) - 1],
                                              np.asarray(k)[b_])
        elif write == "chunk":
            np.testing.assert_array_equal(
                got[0, :, 2:7], np.asarray(k)[0, :5].swapaxes(0, 1))
            np.testing.assert_array_equal(
                got[1, :, 0:5], np.asarray(k)[1, :5].swapaxes(0, 1))
        else:
            for b_ in range(b):
                n = int(lens[b_])
                np.testing.assert_array_equal(
                    got[b_, :, :n], np.asarray(k)[1, b_, :n].swapaxes(0, 1))


def _body_primitives(jaxpr, inside=False, out=None):
    """(name, result dtype kind) of the primitives in the bodies of
    ``jaxpr``'s loops (``scan`` / ``while``), nested calls included, a
    Pallas kernel's own body left out."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if inside:
            out.add((name, eqn.outvars[0].aval.dtype.kind))
        if name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _body_primitives(sub, inside or name in ("scan", "while"),
                                     out)
    return out


class TestDecodePlan:
    """What a decode step's cache layers share is made once a step."""

    @pytest.mark.parametrize("heads,head_dim", [(4, 64), (3, 64), (2, 128)])
    def test_a_planned_write_is_the_one_token_chunk(self, rng_np, heads,
                                                    head_dim):
        """With the step's plan, without one and as a chunk of one token
        (what ``write_decode_kv`` was): the same pools, bit for bit, an
        idle row's token in the null page."""
        layers, pages, ps = 3, 12, 4
        shape = PA.kv_pool_shape(layers, heads, pages, ps, head_dim)
        kc = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
        vc = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0], [0, 0, 0]], np.int32))
        positions, lens = jnp.asarray([6, 4, 0]), jnp.asarray([7, 5, 0])
        k, v = (jnp.asarray(rng_np.normal(size=(3, heads, head_dim))
                            .astype(np.float32)) for _ in range(2))
        plan = PA.decode_plan(kc, pt, positions, lens, heads, head_dim)
        assert plan.rows.shape == (3, shape[1], 4)
        want = PA.write_chunk_kv(kc, vc, k[:, None], v[:, None], 1, pt,
                                 positions, jnp.ones_like(positions))
        for got in (PA.write_decode_kv(kc, vc, k, v, 1, pt, positions, plan),
                    PA.write_decode_kv(kc, vc, k, v, 1, pt, positions)):
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_the_kernel_takes_the_plans_work_list(self, rng_np, kv_heads):
        """The interpret-mode kernel over a plan's work list gives what it
        gives over its own, idle row and grouped query heads included."""
        lens = np.array([9, 0, 17, 3], np.int32)
        kc, vc, pt, _, _ = make_paged(rng_np, lens, H=kv_heads, layers=2,
                                      layer=1)
        q = jnp.asarray(rng_np.normal(size=(4, 4, 16)).astype(np.float32))
        kc, vc, pt, lens = (jnp.asarray(x) for x in (kc, vc, pt, lens))
        plan = PA.decode_plan(kc, pt, jnp.maximum(lens - 1, 0), lens,
                              kv_heads, 16)
        run = functools.partial(PA.ragged_paged_attention, q, kc, vc, 1, pt,
                                lens, impl="kernel", interpret=True,
                                kv_heads=kv_heads)
        np.testing.assert_array_equal(np.asarray(run(plan=plan)),
                                      np.asarray(run()))

    @pytest.mark.parametrize("kind", ["dense", "looped"])
    def test_the_layer_loop_holds_none_of_the_index_arithmetic(self, kind):
        """``forward_decode``'s layer loop: no cumulative sum (the work
        list) and no whole-number division or remainder (page and row of
        a position, blocks of a length) is left in its body."""
        cfg = small_cfg(**({"loop_steps": 2} if kind == "looped" else {}))
        params = T.init_params(cfg, jax.random.key(0))
        kc, vc = PA.init_kv_pages(cfg.cache_layers, cfg.kv_heads, 9, 4,
                                  cfg.head_dim)
        b = 3
        args = (jnp.zeros(b, jnp.int32), jnp.asarray([5, 0, 2]),
                jnp.asarray([6, 0, 3]), jnp.zeros((b, 4), jnp.int32))
        body = _body_primitives(jax.make_jaxpr(
            lambda *a: T.forward_decode(cfg, params, *a, kc, vc,
                                        attn_impl="kernel"))(*args).jaxpr)
        names = {name for name, _ in body}
        assert {"pallas_call", "scatter"} <= names and "cumsum" not in names
        assert not body & {("div", "i"), ("rem", "i"), ("floor_divide", "i")}


class TestBitExactDecode:
    def test_paged_incremental_equals_full_context_argmax(self, rng_np):
        """The acceptance bit-exactness property: engine tokens (paged
        cache + prefill/decode split + continuous batching) equal
        repeated full-context ``forward`` argmax per prompt."""
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        prompts = [list(rng_np.integers(1, 64, size=n)) for n in (3, 7, 12)]
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=32, max_prompt_len=16,
            max_new_tokens=8, prefill_batch=2, seed=0))
        results = eng.generate(prompts, max_new_tokens=5)
        for prompt, res in zip(prompts, results):
            assert res.finish_reason == "length"
            # one full-context pass over prompt+generated: position i's
            # argmax must equal token i+1 at EVERY step — equivalent to
            # re-running forward per step (greedy diverges at the first
            # mismatch, which the positional check would catch), but one
            # compile signature per prompt instead of one per length
            full = prompt + res.tokens
            logits = T.forward(cfg, params, jnp.asarray([full]))
            want = [int(t) for t in
                    jnp.argmax(logits[0, len(prompt) - 1:-1], axis=-1)]
            assert res.tokens == want


class TestSchedulerAndEngine:
    def test_deterministic_given_seed_and_arrival_order(self, rng_np):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(2))
        prompts = [list(rng_np.integers(1, 64, size=5)) for _ in range(4)]

        def run():
            eng = ServingEngine(cfg, params, ServingConfig(
                max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
                max_new_tokens=6, prefill_batch=2, seed=123))
            return [r.tokens for r in
                    eng.generate(prompts, max_new_tokens=6,
                                 temperature=0.8)]

        first, second = run(), run()
        assert first == second  # same seed + arrival order -> same trace
        # temperature actually samples (vs collapsing to argmax)
        from paddle_tpu.serving.sampling import request_keys, sample_tokens

        logits = jnp.asarray(rng_np.normal(size=(8, 64)).astype(np.float32))
        keys = request_keys(jax.random.key(123),
                            jnp.arange(8, dtype=jnp.int32),
                            jnp.zeros(8, jnp.int32))
        hot = sample_tokens(logits, keys, jnp.full((8,), 5.0))
        cold = sample_tokens(logits, keys, jnp.zeros((8,)))
        assert (np.asarray(hot) != np.asarray(cold)).any()
        np.testing.assert_array_equal(np.asarray(cold),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_eos_stops_and_frees_pages(self, rng_np):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        prompt = list(rng_np.integers(1, 64, size=4))
        ref = ServingEngine(cfg, params, ServingConfig(
            max_slots=1, page_size=4, num_pages=16, max_prompt_len=8,
            max_new_tokens=8, prefill_batch=1))
        tokens = ref.generate([prompt], max_new_tokens=8)[0].tokens
        eos = tokens[2]  # force an eos at the 3rd generated token
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=1, page_size=4, num_pages=16, max_prompt_len=8,
            max_new_tokens=8, prefill_batch=1, eos_id=eos))
        res = eng.generate([prompt], max_new_tokens=8)[0]
        assert res.finish_reason == "eos"
        # generation stops at the FIRST occurrence of eos (inclusive)
        assert res.tokens == tokens[:tokens.index(eos) + 1]
        assert eng.cache.allocator.free_pages == 15  # all pages returned

    def test_admission_blocks_on_pages_then_drains(self, rng_np):
        """More work than the pool can hold at once: requests queue,
        admission rejections are counted, everything still completes."""
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        prompts = [list(rng_np.integers(1, 64, size=6)) for _ in range(6)]
        # pool: 7 usable pages; each request reserves (6+8)/4 -> 4 pages
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=4, page_size=4, num_pages=8, max_prompt_len=8,
            max_new_tokens=8, prefill_batch=4, seed=0))
        results = eng.generate(prompts, max_new_tokens=4)
        assert len(results) == 6
        assert all(len(r.tokens) == 4 for r in results)
        assert eng.scheduler.rejected_admissions > 0
        assert eng.cache.allocator.free_pages == 7

    def test_concurrent_token_budget(self, rng_np):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        prompts = [list(rng_np.integers(1, 64, size=4)) for _ in range(3)]
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=4, page_size=4, num_pages=64, max_prompt_len=8,
            max_new_tokens=8, prefill_batch=4,
            max_concurrent_tokens=20))  # one (4+8)-token reservation + slack
        results = eng.generate(prompts, max_new_tokens=3)
        assert len(results) == 3
        assert eng.scheduler.rejected_admissions > 0

    def test_threaded_submit_results(self, rng_np):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
            max_new_tokens=4, prefill_batch=2))
        eng.start()
        try:
            ids = [eng.submit(list(rng_np.integers(1, 64, size=4)),
                              max_new_tokens=3) for _ in range(3)]
            got = eng.results(n=3, timeout=60.0)
        finally:
            eng.stop()
        assert sorted(r.id for r in got) == sorted(ids)
        assert all(len(r.tokens) == 3 for r in got)

    def test_loop_crash_fails_pending_results(self, rng_np):
        """A dead background loop must FAIL blocked results() callers
        with its exception (and count the crash), not park them forever
        behind an engine that will never complete anything."""
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        reg = MetricsRegistry("serve_crash")
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
            max_new_tokens=4, prefill_batch=2), registry=reg)
        boom = RuntimeError("injected decode fault")

        def bad_step():
            raise boom

        # submit BEFORE arming the crash: with the dead-engine guard a
        # post-crash submit refuses (asserted below), so the pending
        # request must predate the loop death
        eng.submit([1, 2, 3], max_new_tokens=3)
        eng.step = bad_step
        eng.start()
        try:
            with pytest.raises(RuntimeError,
                               match="serving loop crashed") as ei:
                eng.results(n=1, timeout=30.0)
            assert ei.value.__cause__ is boom
            # the non-blocking drain reports the crash too, rather than
            # returning an innocent-looking empty list
            with pytest.raises(RuntimeError, match="serving loop crashed"):
                eng.results()
            # ... and so does submit(): enqueueing into the dead engine
            # would park the request forever (PR 8 regression family)
            with pytest.raises(RuntimeError, match="submit refused"):
                eng.submit([1, 2, 3], max_new_tokens=3)
        finally:
            eng.stop()
        assert reg.counter("serve_loop_crashes", "").value() == 1.0

    def test_submit_after_stop_raises(self, rng_np):
        """stop() on a background engine marks it dead: a later submit
        must raise immediately, not enqueue into a loop that will never
        run again.  start() forgives (and sync-only engines that never
        ran a loop keep accepting)."""
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
            max_new_tokens=4, prefill_batch=2))
        eng.start()
        eng.submit([1, 2, 3], max_new_tokens=2)
        eng.results(n=1, timeout=60.0)
        eng.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit([1, 2, 3], max_new_tokens=2)
        eng.start()  # a restart re-opens the front door
        try:
            eng.submit([1, 2, 3], max_new_tokens=2)
            assert len(eng.results(n=1, timeout=60.0)) == 1
        finally:
            eng.stop()

    def test_impossible_reservation_rejected_at_enqueue(self):
        """A request whose prompt+max_new reservation exceeds the TOTAL
        page pool (or a table row, or the token budget) can never be
        admitted — FIFO admission would block forever behind it, so
        enqueue must reject it immediately with the reason."""
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        def mk(num_pages, max_pages_per_seq, budget=0):
            cache = PagedKVCache(1, 2, 16, num_pages, 4, 2,
                                 max_pages_per_seq)
            s = ServingConfig(max_slots=2, page_size=4,
                              num_pages=num_pages, max_prompt_len=64,
                              max_new_tokens=64,
                              max_concurrent_tokens=budget)
            return Scheduler(s, cache)

        # 8+8 tokens -> 4 pages, pool has 3 usable
        sched = mk(num_pages=4, max_pages_per_seq=8)
        with pytest.raises(Exception, match="whole pool"):
            sched.enqueue(Request(id=0, prompt=[1] * 8, max_new_tokens=8))
        assert not sched.queue  # nothing wedged at the head
        # table row too short even though the pool is big enough
        sched = mk(num_pages=64, max_pages_per_seq=2)
        with pytest.raises(Exception, match="max_pages_per_seq"):
            sched.enqueue(Request(id=1, prompt=[1] * 8, max_new_tokens=8))
        # reservation above the concurrent-token budget
        sched = mk(num_pages=64, max_pages_per_seq=32, budget=10)
        with pytest.raises(Exception, match="max_concurrent_tokens"):
            sched.enqueue(Request(id=2, prompt=[1] * 8, max_new_tokens=8))
        # a request that fits all three still queues, and drains
        sched = mk(num_pages=8, max_pages_per_seq=4, budget=16)
        sched.enqueue(Request(id=3, prompt=[1] * 4, max_new_tokens=4))
        assert len(sched.queue) == 1 and len(sched.admit()) == 1


# -- the prefill ladder ---------------------------------------------------------
#
# One serving shape for every ladder test: one row or four, 96 long, on
# models small enough that the CPU compiles both in a second.

LADDER_SERVING = dict(max_slots=4, page_size=16, num_pages=64,
                      max_prompt_len=96, max_new_tokens=4, prefill_batch=4,
                      seed=0)
LADDER = ((1, 96), (4, 96))     # rows (1, prefill_batch) x max_prompt_len
# one admitted batch each: 1 ... prefill_batch rows, prompts at both ends of
# the length, on a page's edge and either side of it
LADDER_BATCHES = [(1,), (15,), (16,), (17,), (95,), (96,), (50, 7),
                  (96, 96), (1, 1), (90, 20, 33), (16, 32, 48),
                  (5, 96, 64, 17), (33, 44, 55, 66), (96,) * 4]


def _ladder_cfg(kind):
    if kind == "plain":
        return small_cfg(max_seq_len=128)
    if kind == "looped":
        return small_cfg(max_seq_len=128, norm="rms", positions="rotary",
                         mlp="swiglu", loop_steps=3)
    return T.TransformerConfig(   # layers of three kinds, two with state
        vocab_size=64, num_layers=4, num_heads=4, kv_heads=2, head_dim=8,
        embed_dim=32, mlp_dim=24, max_seq_len=128, norm="rms",
        positions="none", mlp="relu2", tie_embeddings=False, pattern="ME*M",
        moe_experts=8, moe_router="sigmoid", moe_top_k=2, moe_shared_dim=16,
        moe_held=(0, 4), mamba_heads=4, mamba_head_dim=8, mamba_state=16,
        mamba_groups=2, mamba_conv=4, mamba_chunk=32, remat=False)


@functools.lru_cache(maxsize=None)
def _ladder_engines(kind):
    """(an engine that shapes its passes, its twin that runs every pass at
    the largest member); both see the same batches in the same order."""
    cfg = _ladder_cfg(kind)
    params = T.init_params(cfg, jax.random.key(3))
    shaped = ServingEngine(cfg, params, ServingConfig(**LADDER_SERVING))
    padded = ServingEngine(cfg, params, ServingConfig(**LADDER_SERVING))
    assert shaped.scheduler.prefill_shapes == LADDER
    padded.scheduler.prefill_shapes = LADDER[1:]
    return shaped, padded


class _Compiles:
    """The two ``jax.monitoring`` events the benchmark's ``CompileWatch``
    counts: a compile, or a fetch from the persistent cache."""

    count = 0
    listening = False

    @classmethod
    def listen(cls):
        if not cls.listening:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls.listening = True
        return cls

    @classmethod
    def _on(cls, event, duration, **kw):
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")):
            cls.count += 1


class TestPrefillLadder:
    @pytest.mark.parametrize("batch,longest,shape", [
        # the benchmark's six serve configurations
        pytest.param(4, 768, ((1, 768), (4, 768)), id="gpt2-large"),
        pytest.param(2, 192, ((1, 192), (2, 192)), id="ouro-2.6b"),
        pytest.param(4, 512, ((1, 512), (4, 512)), id="nemotron-3-nano"),
        pytest.param(4, 512, ((1, 512), (4, 512)), id="sdar-30b"),
        pytest.param(4, 512, ((1, 512), (4, 512)), id="zaya1-8b"),
        pytest.param(2, 4096, ((1, 2048), (1, 4096)), id="solar-open2"),
        (4, 96, LADDER),
        (8, 16, ((1, 16), (8, 16))),
        (3, 100, ((1, 100), (3, 100))),
        (1, 1024, ((1, 1024),)),     # one row is all such an engine admits
        (1, 2048, ((1, 1024), (1, 2048))),
        (4, 2048, ((1, 1024), (1, 2048))),   # the least that is long
        (4, 2046, ((1, 2046), (4, 2046))),   # half a pass under 1,024
        (2, 2304, ((1, 2304), (2, 2304))),   # half no multiple of 256
        (2, 8192, ((1, 4096), (1, 8192))),
    ])
    def test_ladder_from_two_numbers(self, batch, longest, shape):
        """Two programs whatever the model (a program costs set-up time):
        one row and ``prefill_batch`` rows at ``max_prompt_len`` where a
        pass is short; one row at half the length and one at all of it
        where half a pass is 1,024 positions or more and a whole number
        of 256.  The largest member holds whatever ``admit`` may hand
        over, and ``prefill_rows`` is what it was."""
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import (
            Scheduler,
            prefill_rows,
            prefill_shapes,
        )

        assert prefill_rows(batch) == tuple(sorted({1, batch}))
        assert prefill_shapes(batch, longest) == shape
        s = ServingConfig(**{**LADDER_SERVING, "prefill_batch": batch,
                             "max_prompt_len": longest, "max_slots": 8,
                             "num_pages": 8 * (-(-longest // 16) + 1) + 1})
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        assert sched.prefill_shapes == shape
        assert sched.prefill_rows == tuple(rows for rows, _ in shape)
        got = tuple(sched.prefill_arrays([], *member)["ids"].shape
                    for member in sched.prefill_shapes)
        assert got == shape
        assert got[-1][1] == longest
        # without a length a member is ``max_prompt_len`` long
        assert sched.prefill_arrays([], 1)["ids"].shape == (1, longest)

    @pytest.mark.parametrize("n,member", [
        (1, (1, 2048)), (512, (1, 2048)), (2047, (1, 2048)),
        (2048, (1, 2048)), (2049, (1, 4096)), (4096, (1, 4096)),
    ])
    def test_a_prompt_takes_the_shortest_member_that_holds_it(self, n,
                                                               member):
        """A prompt of 2,048 rides the half-length member, one of 2,049
        the full one; positions past the prompt are masked by ``seq_lens``
        at either length, slack as at every shape."""
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        s = ServingConfig(max_slots=2, page_size=16, num_pages=2 * 260 + 1,
                          max_prompt_len=4096, max_new_tokens=8,
                          prefill_batch=2)
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        sched.enqueue(Request(id=0, prompt=[3] * n, max_new_tokens=2))
        (a,) = sched.admit()
        batch = sched.prefill_batch([a])
        assert batch["ids"].shape == member
        assert batch["seq_lens"].tolist() == [n]
        assert batch["ids"][0, :n].tolist() == [3] * n
        assert not batch["ids"][0, n:].any()
        assert batch["slots"].tolist() == [a.slot]
        assert batch["page_table"].shape == (1, s.max_pages_per_seq)

    @pytest.mark.parametrize("batch,longest,queued,handed", [
        (4, 96, 6, [4, 2]),         # today's ladder: prefill_batch a step
        (2, 192, 3, [2, 1]),
        (1, 96, 2, [1, 1]),
        (2, 4096, 3, [1, 1, 1]),    # one-row members: one an iteration
        (4, 2048, 2, [1, 1]),
    ])
    def test_admit_hands_over_what_one_member_holds(self, batch, longest,
                                                    queued, handed):
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        s = ServingConfig(max_slots=8, page_size=16,
                          num_pages=8 * (-(-longest // 16) + 1) + 1,
                          max_prompt_len=longest, max_new_tokens=4,
                          prefill_batch=batch)
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        for i in range(queued):
            sched.enqueue(Request(id=i, prompt=[1 + i] * 5, max_new_tokens=2))
        got, order = [], []
        while sched.queue:
            admitted = sched.admit()
            got.append(len(admitted))
            order += [a.request.id for a in admitted]
            # every hand-over fits a member of the ladder
            assert sched.prefill_batch(admitted)["ids"].shape[0] >= len(
                admitted)
        assert got == handed
        assert order == list(range(queued))     # FIFO

    @pytest.mark.parametrize("kind", ["plain", "looped", "pattern"])
    def test_every_engine_has_the_same_ladder(self, kind):
        """The ladder comes from ``prefill_batch`` alone, whatever the
        model: a scanned stack, a looped one and a layer pattern with
        state pools all get the one-row program beside the full one."""
        cfg = _ladder_cfg(kind)
        reg = MetricsRegistry(f"ladder_{kind}")
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(6)),
                            ServingConfig(**LADDER_SERVING), registry=reg)
        assert eng.scheduler.prefill_rows == (1, 4)
        assert reg.get("serve_prefill_programs").value() == 2

    @pytest.mark.parametrize("lens,shape", [
        ((1,), (1, 96)), ((96,), (1, 96)), ((5, 5), (4, 96)),
        ((5, 96, 5), (4, 96)), ((96,) * 4, (4, 96)),
    ])
    def test_smallest_covering_member_is_picked(self, lens, shape):
        from paddle_tpu.serving.kv_cache import PagedKVCache
        from paddle_tpu.serving.scheduler import Request, Scheduler

        s = ServingConfig(**LADDER_SERVING)
        sched = Scheduler(s, PagedKVCache(1, 2, 16, s.num_pages, s.page_size,
                                          s.max_slots, s.max_pages_per_seq))
        assert sched.prefill_rows == (1, 4)
        for i, n in enumerate(lens):
            sched.enqueue(Request(id=i, prompt=[1 + i] * n, max_new_tokens=2))
        admitted = sched.admit()
        batch = sched.prefill_batch(admitted)
        assert batch["ids"].shape == shape
        rows = len(lens)
        assert batch["seq_lens"].tolist() == list(lens) + [0] * (
            shape[0] - rows)
        # slack rows keep their contract at every shape
        assert (batch["slots"][rows:] == s.max_slots).all()
        assert not batch["page_table"][rows:].any()
        assert batch["page_table"].shape == (shape[0], s.max_pages_per_seq)
        for j, a in enumerate(admitted):
            assert batch["ids"][j, :a.prompt_len].tolist() == a.request.prompt
            assert not batch["ids"][j, a.prompt_len:].any()
            assert batch["slots"][j] == a.slot

    @pytest.mark.parametrize("lens", LADDER_BATCHES, ids=str)
    @pytest.mark.parametrize("kind", ["plain", "looped", "pattern"])
    def test_shaped_passes_serve_the_same_tokens(self, kind, lens, rng_np):
        """Leaving the padding out changes no answer: greedy tokens are
        those of the full-size pass; pages and recurrent state too, to a
        few float32 roundings (a matmul of another shape may sum in another
        order: 1e-5, set from the dtype before the first run)."""
        shaped, padded = _ladder_engines(kind)
        prompts = [list(rng_np.integers(1, 64, size=n)) for n in lens]
        seen = []
        real = shaped.scheduler.prefill_batch
        shaped.scheduler.prefill_batch = lambda admitted: seen.append(
            real(admitted)) or seen[-1]
        try:
            a = shaped.generate(prompts, max_new_tokens=3)
        finally:
            del shaped.scheduler.prefill_batch
        b = padded.generate(prompts, max_new_tokens=3)
        assert [x["ids"].shape for x in seen] == [
            LADDER[0] if len(lens) == 1 else LADDER[1]]
        assert [r.tokens for r in a] == [r.tokens for r in b]
        # the null page takes the slack rows' writes: no reader sees it
        for x, y in ((shaped.cache.k, padded.cache.k),
                     (shaped.cache.v, padded.cache.v)):
            np.testing.assert_allclose(np.asarray(x)[:, :, 1:],
                                       np.asarray(y)[:, :, 1:],
                                       rtol=1e-5, atol=1e-5)
        assert shaped.cache.state.keys() == padded.cache.state.keys()
        for name in shaped.cache.state:
            np.testing.assert_allclose(
                np.asarray(shaped.cache.state[name]),
                np.asarray(padded.cache.state[name]), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("kind", ["plain", "pattern"])
    def test_nothing_compiles_after_the_first_admission(self, kind, rng_np):
        """Every program is compiled by the step that admits the first
        request, whatever that request is (here one short row): no
        admissible batch compiles (or fetches from the persistent cache)
        afterwards.  An idle step compiles nothing: a fleet's router pumps
        idle replicas."""
        watch = _Compiles.listen()
        # a vocabulary no other test serves: its programs are not compiled yet
        cfg = dataclasses.replace(_ladder_cfg(kind), vocab_size=71)
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(4)),
                            ServingConfig(**LADDER_SERVING))
        before = watch.count
        assert eng.step() is False and watch.count == before
        eng.generate([[5, 17, 3]], max_new_tokens=2)
        ready = watch.count
        # the ladder and decode
        assert ready - before >= len(eng.scheduler.prefill_rows) + 1
        for lens in LADDER_BATCHES:
            eng.generate([list(rng_np.integers(1, 64, size=n))
                          for n in lens], max_new_tokens=3)
        assert watch.count == ready

    def test_length_members_serve_the_same_tokens(self, monkeypatch, rng_np):
        """Where half a pass is long enough (the constant lowered to a
        toy's size) the second member is HALF AS LONG, not wider: greedy
        tokens are those of an engine whose every pass is the full
        length, nothing compiles once the first request is admitted, and
        the passes are counted by the length they ran at."""
        from paddle_tpu.serving import scheduler

        monkeypatch.setattr(scheduler, "LENGTH_LADDER_MIN_HALF", 256)
        # attention, two state layers and an expert sublayer; a vocabulary
        # no other test serves: its programs are not compiled yet
        cfg = dataclasses.replace(_ladder_cfg("pattern"), vocab_size=73,
                                  max_seq_len=528)
        params = T.init_params(cfg, jax.random.key(8))
        serving = ServingConfig(max_slots=4, page_size=16, num_pages=4 * 33
                                + 1, max_prompt_len=512, max_new_tokens=4,
                                prefill_batch=2, seed=0)
        reg = MetricsRegistry("length_ladder")
        shaped = ServingEngine(cfg, params, serving, registry=reg)
        full = ServingEngine(cfg, params, serving)
        assert shaped.scheduler.prefill_shapes == ((1, 256), (1, 512))
        assert reg.get("serve_prefill_programs").value() == 2
        full.scheduler.prefill_shapes = ((1, 512),)
        seen = []
        real = shaped.scheduler.prefill_batch

        def recorded(admitted):
            batch = real(admitted)
            seen.append(batch["ids"].shape)
            return batch

        shaped.scheduler.prefill_batch = recorded
        watch = _Compiles.listen()
        shaped.generate([[5, 17, 3]], max_new_tokens=2)
        assert set(shaped._programs) == {(1, 256), (1, 512), "decode"}
        ready = watch.count
        lens = (1, 255, 256, 257, 300, 512, 40, 511)
        prompts = [list(rng_np.integers(1, 73, size=n)) for n in lens]
        a = shaped.generate(prompts, max_new_tokens=3)
        assert watch.count == ready
        b = full.generate(prompts, max_new_tokens=3)
        assert [r.tokens for r in a] == [r.tokens for r in b]
        # one request a pass, in arrival order, each at the shortest
        # member that holds it
        assert seen == [(1, 256)] + [(1, 256 if n <= 256 else 512)
                                     for n in lens]
        passes = reg.get("serve_prefill_passes_total")
        assert passes.value(length=256) == 1 + 4
        assert passes.value(length=512) == 4
        assert reg.get("serve_prefill_padded_tokens_total").value() == (
            5 * 256 + 4 * 512)

    def test_making_ready_leaves_the_cache_as_it_was(self, rng_np):
        """Getting every member of the ladder and the decode program
        ready compiles and runs nothing: the pages, the recurrent state
        and the page table are bit for bit what they were."""
        cfg = _ladder_cfg("pattern")
        eng = ServingEngine(cfg, T.init_params(cfg, jax.random.key(5)),
                            ServingConfig(**LADDER_SERVING))
        assert eng.scheduler.prefill_rows == (1, 4)  # beside state pools
        cache = eng.cache
        fill = lambda x: jnp.asarray(
            rng_np.normal(size=x.shape).astype(np.float32))
        cache.k, cache.v = fill(cache.k), fill(cache.v)
        cache.state = {n: fill(x) for n, x in cache.state.items()}
        cache.assign(1, 40)     # a resident sequence's table row
        k, v, state, table = (np.asarray(cache.k), np.asarray(cache.v),
                              {n: np.asarray(x) for n, x in
                               cache.state.items()}, cache.page_table.copy())
        assert state and table.any()
        eng._make_ready()
        assert np.array_equal(np.asarray(cache.k), k)
        assert np.array_equal(np.asarray(cache.v), v)
        for name, was in state.items():
            assert np.array_equal(np.asarray(cache.state[name]), was)
        assert np.array_equal(cache.page_table, table)
        assert eng.scheduler.active == [] and not eng.scheduler.queue


class TestServeTelemetry:
    def test_per_request_records_and_percentiles(self, rng_np):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        reg = MetricsRegistry("serve_test")
        sink = MemorySink()
        reg.add_sink(sink)
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=32, max_prompt_len=8,
            max_new_tokens=4, prefill_batch=2), registry=reg)
        prompts = [list(rng_np.integers(1, 64, size=4)) for _ in range(3)]
        eng.generate(prompts, max_new_tokens=4)
        eng.emit_summary()
        serves = [r for r in sink.records if r.get("kind") == "serve"]
        assert len(serves) == 3
        for r in serves:
            assert r["schema"] == "paddle_tpu.metrics/16"
            for f in ("queue_wait_ms", "ttft_ms", "tpot_ms", "total_ms"):
                assert r[f] >= 0.0
            assert r["new_tokens"] == 4
        # TTFT/TPOT histograms expose asserted percentiles
        for name in ("serve_ttft_ms", "serve_tpot_ms"):
            h = reg.get(name)
            assert h.percentile(50) is not None
            assert h.percentile(50) <= h.percentile(99) <= h.summary()["max"]
        summaries = [r for r in sink.records
                     if r.get("kind") == "serve_summary"]
        assert summaries and "serve_ttft_ms" in summaries[-1]["summary"]
        assert reg.counter("serve_tokens").value() == 12.0

    def test_metrics_to_md_renders_serving_table(self, tmp_path, capsys):
        import json
        import sys

        sys.path.insert(0, "tools")
        try:
            import metrics_to_md
        finally:
            sys.path.pop(0)
        path = tmp_path / "m.jsonl"
        recs = [{"kind": "serve", "request": i, "prompt_tokens": 4,
                 "new_tokens": 8, "queue_wait_ms": 1.0 * i,
                 "ttft_ms": 10.0 + i, "tpot_ms": 2.0, "total_ms": 30.0}
                for i in range(5)]
        recs.append({"kind": "serve_summary", "rejected_admissions": 2,
                     "summary": {"serve_ttft_ms": {
                         "count": 5, "p50": 12.0, "p99": 14.9,
                         "max": 14.9}}})
        path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        metrics_to_md.main([str(path)])
        out = capsys.readouterr().out
        assert "## Serving latency" in out
        assert "TTFT" in out and "TPOT" in out
        assert "admission attempts" in out


class TestPrefixCacheAndChunkedPrefill:
    """The perf tentpole's correctness contract: prefix caching and
    chunked prefill are pure optimizations — greedy tokens identical in
    every flag combination, warm or cold — and the refcounted page
    accounting stays conservative throughout."""

    def _setup(self, rng_np, n_prompts=4, shared_head=8):
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(3))
        head = list(rng_np.integers(1, 64, size=shared_head))
        prompts = [head + list(rng_np.integers(1, 64, size=4))
                   for _ in range(n_prompts)]
        prompts.append(list(rng_np.integers(1, 64, size=3)))  # no prefix
        return cfg, params, prompts

    def _run(self, cfg, params, prompts, registry=None, repeats=1, **kw):
        scfg = ServingConfig(max_slots=4, page_size=4, num_pages=64,
                             max_prompt_len=16, max_new_tokens=6,
                             prefill_batch=4, seed=0, **kw)
        eng = ServingEngine(cfg, params, scfg, registry=registry)
        out = []
        for _ in range(repeats):
            out.append([r.tokens for r in
                        eng.generate(prompts, temperature=0.0)])
        return eng, out

    def test_greedy_tokens_identical_across_all_flag_modes(self, rng_np):
        cfg, params, prompts = self._setup(rng_np)
        _, (base,) = self._run(cfg, params, prompts)
        # the prefix-only arm rides the warm-cache test's cold pass;
        # chunk 3 is the page-misaligned chunk boundary
        for kw in ({"prefill_chunk_tokens": 4},
                   {"prefill_chunk_tokens": 3},
                   {"prefix_cache": True, "prefill_chunk_tokens": 4}):
            _, (got,) = self._run(cfg, params, prompts, **kw)
            assert got == base, f"tokens diverged with {kw}"

    def test_warm_cache_identity_stats_and_page_conservation(self, rng_np):
        cfg, params, prompts = self._setup(rng_np)
        _, (base,) = self._run(cfg, params, prompts)
        reg = MetricsRegistry("serve_prefix")
        sink = MemorySink()
        reg.add_sink(sink)
        eng, (cold, warm) = self._run(cfg, params, prompts, registry=reg,
                                      repeats=2, prefix_cache=True)
        assert cold == base and warm == base
        p = eng.cache.prefix
        # warm round: 4 prompts share an 8-token (2-page) head; the
        # 3-token prompt has no full page to match
        assert p.hits >= 4 and p.hit_tokens >= 4 * 8
        assert reg.counter("serve_prefix_hit_tokens").value() >= 4 * 8
        assert reg.counter("serve_prefill_flops_saved").value() > 0
        # refcounted conservation: free + unique == pool - 1, with
        # cached pages resident and reclaimable after all releases
        rep = eng.cache.resident_report()
        assert rep["free_pages"] + rep["unique_pages"] == 63
        assert rep["cached_pages"] > 0
        assert rep["reclaimable_pages"] == rep["cached_pages"]
        # serve records carry the /14 fields
        serves = [r for r in sink.records if r.get("kind") == "serve"]
        assert sum(r["cached_tokens"] for r in serves) == p.hit_tokens
        eng.emit_summary()
        summ = [r for r in sink.records
                if r.get("kind") == "serve_summary"][-1]
        pre = summ["prefix"]
        assert pre["hit_tokens"] == p.hit_tokens
        assert 0.0 < pre["hit_rate"] <= 1.0
        assert pre["cached_pages"] == p.cached_pages
        assert pre["flops_saved"] > 0

    def test_chunked_prefill_interleaves_with_decode(self, rng_np):
        """A long prompt admitted behind a decoding sequence advances
        chunk-by-chunk while the resident sequence keeps decoding —
        TTFT for the long prompt no longer blocks the decode stream."""
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(3))
        short = list(rng_np.integers(1, 64, size=4))
        long_p = list(rng_np.integers(1, 64, size=16))
        reg = MetricsRegistry("serve_chunk")
        sink = MemorySink()
        reg.add_sink(sink)
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=64, max_prompt_len=16,
            max_new_tokens=6, prefill_batch=2, seed=0,
            prefill_chunk_tokens=4), registry=reg)
        eng.submit(short, max_new_tokens=6, temperature=0.0)
        eng.step()  # short's first chunk == its whole prompt
        eng.submit(long_p, max_new_tokens=6, temperature=0.0)
        interleaved = 0
        for _ in range(30):
            if not eng.step():
                break
            live = {a.request.id: a for a in eng.scheduler.live}
            if (0 in live and live[0].generated
                    and 1 in live and not live[1].generated):
                interleaved += 1
        assert interleaved > 0, "decode never ran beside a mid-prefill row"
        res = {r.id: r.tokens for r in eng.results()}
        # chunk accounting: the long prompt took ceil(16/4) = 4 passes
        serves = [r for r in sink.records if r.get("kind") == "serve"]
        chunks = {r["request"]: r["prefill_chunks"] for r in serves}
        assert chunks[1] == 4 and chunks[0] == 1
        assert reg.counter("serve_prefill_chunks").value() >= 5.0
        # identity vs the whole-prompt engine
        eng2 = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=64, max_prompt_len=16,
            max_new_tokens=6, prefill_batch=2, seed=0))
        eng2.submit(short, max_new_tokens=6, temperature=0.0)
        eng2.submit(long_p, max_new_tokens=6, temperature=0.0)
        eng2.run_until_idle()
        ref = {r.id: r.tokens for r in eng2.results()}
        assert res == ref

    def test_admission_under_pressure_evicts_cached_prefixes(self, rng_np):
        """A warm cache under page pressure: LRU cached prefixes are
        reclaimed instead of blocking admissions, OutOfPages never
        surfaces while reclaimable pages exist, and every request
        completes."""
        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(3))
        heads = [list(rng_np.integers(1, 64, size=8)) for _ in range(3)]
        prompts = [h + list(rng_np.integers(1, 64, size=2))
                   for h in heads for _ in range(2)]
        # pool of 11 usable pages; each request reserves
        # ceil((10 + 4)/4) = 4; three 2-page prefixes want caching, so
        # a full cache (6 pages) + two active rows (8, minus shared
        # heads) overflows the pool and forces LRU reclaim
        reg = MetricsRegistry("serve_evict")
        eng = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=12, max_prompt_len=16,
            max_new_tokens=4, prefill_batch=2, seed=0,
            prefix_cache=True), registry=reg)
        results = eng.generate(prompts, max_new_tokens=4,
                               temperature=0.0)
        assert len(results) == 6
        assert all(len(r.tokens) == 4 for r in results)
        p = eng.cache.prefix
        assert p.evictions > 0, "pressure never reclaimed a cached page"
        rep = eng.cache.resident_report()
        assert rep["free_pages"] + rep["unique_pages"] == 11
        # identical tokens with the cache off
        eng2 = ServingEngine(cfg, params, ServingConfig(
            max_slots=2, page_size=4, num_pages=12, max_prompt_len=16,
            max_new_tokens=4, prefill_batch=2, seed=0))
        ref = eng2.generate(prompts, max_new_tokens=4, temperature=0.0)
        assert [r.tokens for r in results] == [r.tokens for r in ref]

    def test_serving_memory_report_counts_unique_resident_bytes(
            self, rng_np):
        from paddle_tpu.analysis.memory import serving_memory_report

        cfg, params, prompts = self._setup(rng_np, n_prompts=3)
        scfg = ServingConfig(max_slots=4, page_size=4, num_pages=64,
                             max_prompt_len=16, max_new_tokens=6,
                             prefill_batch=4, seed=0, prefix_cache=True)
        eng = ServingEngine(cfg, params, scfg)
        eng.generate(prompts, temperature=0.0)  # populate the cache
        rep = serving_memory_report(cfg, scfg, cache=eng.cache)
        page_bytes = rep["page_bytes"]
        assert page_bytes * scfg.num_pages == rep["kv_pool_bytes"]
        assert rep["unique_resident_bytes"] == (
            rep["unique_pages"] * page_bytes)
        assert rep["cached_pages"] > 0
        # all slots idle: unique resident == cached pages exactly
        assert rep["unique_pages"] == rep["cached_pages"]
        assert rep["free_pages"] + rep["unique_pages"] == 63


class TestStrictInference:
    def test_strict_raises_on_missing_parameters(self):
        import paddle_tpu as paddle
        from paddle_tpu.layers import api as layer
        from paddle_tpu.layers import data_type
        from paddle_tpu.trainer.inference import Inference

        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=2)
        empty = paddle.parameters.Parameters()  # no values loaded at all
        with pytest.raises(ValueError, match="incomplete"):
            Inference(out, empty, strict=True)
        # the default stays permissive (v2 back-compat)
        from paddle_tpu.layers import base as layer_base

        layer_base.reset_name_counters()
        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=2)
        inf = Inference(out, paddle.parameters.Parameters())
        assert inf.infer([ (np.zeros(4, np.float32),) ]).shape == (1, 2)

    def test_strict_passes_on_complete_parameters(self):
        import paddle_tpu as paddle
        from paddle_tpu.layers import api as layer
        from paddle_tpu.layers import data_type
        from paddle_tpu.trainer.inference import Inference

        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=2)
        params = paddle.parameters.create(paddle.topology.Topology(out))
        inf = Inference(out, params, strict=True)
        assert inf.infer([(np.zeros(4, np.float32),)]).shape == (1, 2)


class TestDenseBatcher:
    def test_coalesces_and_matches_direct(self):
        import threading

        from paddle_tpu.serving.dense import DenseBatcher

        calls = []

        def predict(rows):
            calls.append(len(rows))
            return np.asarray([[float(r), float(r) * 2] for r in rows])

        reg = MetricsRegistry("dense_test")
        b = DenseBatcher(predict, max_batch=8, max_wait_ms=20.0,
                         registry=reg)
        pending = []
        barrier = threading.Barrier(5)

        def client(i):
            barrier.wait()
            pending.append((i, b.submit(i)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, p in pending:
            np.testing.assert_allclose(p.result(10.0), [i, i * 2])
        b.close()
        assert sum(calls) == 5
        assert len(calls) < 5  # at least one coalesced batch
        assert reg.counter("serve_dense_requests").value() == 5.0

    def test_predict_error_fans_out(self):
        from paddle_tpu.serving.dense import DenseBatcher

        def boom(rows):
            raise RuntimeError("model exploded")

        b = DenseBatcher(boom, max_batch=4, max_wait_ms=1.0,
                         registry=MetricsRegistry("dense_err"))
        p = b.submit(1)
        with pytest.raises(RuntimeError, match="exploded"):
            p.result(10.0)
        b.close()


class TestExport:
    def test_round_trip_and_tamper_detection(self, tmp_path, rng_np):
        from paddle_tpu.serving.export import export_servable, load_servable

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(3))
        out = str(tmp_path / "servable")
        export_servable(out, cfg, params, meta={"note": "test"})
        cfg2, params2 = load_servable(out)
        assert cfg2 == cfg
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b)), params, params2)
        # served tokens from the loaded artifact match the live params
        prompt = list(rng_np.integers(1, 64, size=4))
        scfg = ServingConfig(max_slots=1, page_size=4, num_pages=16,
                             max_prompt_len=8, max_new_tokens=3,
                             prefill_batch=1)
        a = ServingEngine(cfg, params, scfg).generate([prompt])[0].tokens
        b = ServingEngine(cfg2, params2, scfg).generate([prompt])[0].tokens
        assert a == b
        # flip a byte -> load refuses
        payload = tmp_path / "servable" / "params.npz"
        raw = bytearray(payload.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        payload.write_bytes(bytes(raw))
        with pytest.raises(Exception, match="hash mismatch"):
            load_servable(out)

    def test_checkpoint_to_servable(self, tmp_path):
        from paddle_tpu.serving.export import (
            checkpoint_to_servable,
            load_servable,
        )
        from paddle_tpu.trainer.checkpoint import save_checkpoint

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(4))
        flat = {}

        def flatten(d, prefix=""):
            for k, v in d.items():
                if isinstance(v, dict):
                    flatten(v, f"{prefix}{k}/")
                else:
                    flat[f"{prefix}{k}"] = np.asarray(v)

        flatten(params)
        ckpt = str(tmp_path / "ckpts")
        save_checkpoint(ckpt, 0, flat)
        out = checkpoint_to_servable(ckpt, str(tmp_path / "servable"), cfg)
        cfg2, params2 = load_servable(out)
        np.testing.assert_allclose(np.asarray(params2["embed"]),
                                   np.asarray(params["embed"]))
        np.testing.assert_allclose(
            np.asarray(params2["blocks"]["wq"]),
            np.asarray(params["blocks"]["wq"]))

    def test_partial_manifest_cases_refuse_to_load(self, tmp_path):
        """load_servable must refuse, with the reason, every partial-
        artifact shape: a manifest-listed file missing from disk, a
        payload param set that drifted from the manifest inventory, and
        a per-param dtype mismatch — never serve garbage-shaped
        weights."""
        import json

        from paddle_tpu.serving.export import export_servable, load_servable

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(5))

        def fresh(name):
            out = str(tmp_path / name)
            export_servable(out, cfg, params)
            return out

        # (a) payload file listed in the manifest but deleted on disk
        out = fresh("missing_file")
        (tmp_path / "missing_file" / "params.npz").unlink()
        with pytest.raises(Exception, match="missing from disk"):
            load_servable(out)

        # (b) manifest inventory lists a param the payload lacks
        out = fresh("missing_param")
        mpath = tmp_path / "missing_param" / "servable.json"
        m = json.loads(mpath.read_text())
        m["params"]["blocks/extra_w"] = "float32"
        mpath.write_text(json.dumps(m))
        with pytest.raises(Exception, match="do not match the"):
            load_servable(out)

        # (c) dtype drift between manifest inventory and payload
        out = fresh("dtype_drift")
        mpath = tmp_path / "dtype_drift" / "servable.json"
        m = json.loads(mpath.read_text())
        key = next(k for k in m["params"])
        m["params"][key] = "float16"
        mpath.write_text(json.dumps(m))
        with pytest.raises(Exception, match="dtype mismatch"):
            load_servable(out)


@pytest.mark.serving
class TestCliLoop:
    def test_stdin_loop_subprocess(self):
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        lines = "5 17 3\n9 9 9 9\n"
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving", "--random",
             "--vocab", "64", "--embed", "32", "--max_new_tokens", "4",
             "--seed", "7"],
            input=lines, env=env, capture_output=True, text=True,
            timeout=300)
        assert out.returncode == 0, out.stderr[-800:]
        got = [l for l in out.stdout.splitlines() if l.strip()]
        assert len(got) == 2
        assert got[0].startswith("0:") and got[1].startswith("1:")
        toks = [int(t) for t in got[0].split(":")[1].split()]
        assert len(toks) == 4 and all(0 <= t < 64 for t in toks)
        # deterministic: same seed -> same bytes out
        out2 = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving", "--random",
             "--vocab", "64", "--embed", "32", "--max_new_tokens", "4",
             "--seed", "7"],
            input=lines, env=env, capture_output=True, text=True,
            timeout=300)
        assert out2.stdout == out.stdout


class TestKvPoolPreflightGate:
    """GL-P-MEM's serving path: the static KV page-pool accounting that
    fails engine construction instead of OOMing at first admission."""

    def test_serving_memory_report_exact_bytes(self):
        from paddle_tpu.analysis import serving_memory_report

        cfg = small_cfg()  # 2 layers, 2 heads, head_dim 16, f32
        scfg = ServingConfig(page_size=8, num_pages=32)
        rep = serving_memory_report(cfg, scfg)
        # k AND v pools: 2 · L·H·pages·page_size·head_dim·itemsize
        assert rep["kv_pool_bytes"] == 2 * 2 * 2 * 32 * 8 * 16 * 4
        assert rep["dtype"] == "float32"
        assert rep["total_bytes"] == rep["kv_pool_bytes"]
        params = T.init_params(cfg, jax.random.key(0))
        with_p = serving_memory_report(cfg, scfg, params)
        assert with_p["params_bytes"] > 0
        assert with_p["total_bytes"] == (rep["kv_pool_bytes"]
                                         + with_p["params_bytes"])

    def test_budget_pass_names_the_pool_and_clean_under_budget(self):
        from paddle_tpu.analysis import (serving_budget_pass,
                                         serving_memory_report)

        cfg = small_cfg()
        rep = serving_memory_report(cfg, ServingConfig(page_size=8,
                                                       num_pages=32))
        found = serving_budget_pass(rep, hbm_gb=1e-6)
        assert len(found) == 1
        f = found[0]
        assert f.rule == "GL-P-MEM" and f.anchor == "kv-pool-budget"
        assert "pages" in f.message and "first admission" in f.message
        # generous budget or report-only (0): clean
        assert serving_budget_pass(rep, hbm_gb=64.0) == []
        assert serving_budget_pass(rep, hbm_gb=0.0) == []

    def test_engine_construction_fails_preflight_not_oom(self):
        from paddle_tpu.core import flags
        from paddle_tpu.core.enforce import EnforceError

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(1))
        old = flags.get("hbm_gb")
        try:
            flags.set("hbm_gb", 1e-6)
            with pytest.raises(EnforceError, match="kv-pool|KV pool"):
                ServingEngine(cfg, params, ServingConfig(
                    max_slots=2, page_size=4, num_pages=32,
                    max_prompt_len=16, max_new_tokens=8))
            # under budget (or unset): constructs fine
            flags.set("hbm_gb", 0.0)
            ServingEngine(cfg, params, ServingConfig(
                max_slots=2, page_size=4, num_pages=32,
                max_prompt_len=16, max_new_tokens=8))
        finally:
            flags.set("hbm_gb", old)


# -- one pass ahead ---------------------------------------------------------------
# The loop dispatches pass n + 1 before it reads pass n: a step's input
# token stays on the device, the scheduler counts tokens in flight.  Same
# tokens, request for request, as a plain forward of the same model -- or,
# for a model that generates by blocks (its input is the block in progress,
# tokens and masked flags; tests/test_block_lm.py holds it to its plain
# reference), as the same engine with every pass read before the next.

_AHEAD_CFGS = {
    "dense": dict(),
    "looped": dict(loop_steps=2, norm="rms", positions="rotary",
                   mlp="swiglu"),
    # a toy M / E / * pattern: recurrent state by slot beside the pages,
    # routing counts riding out behind the tokens
    "pattern": dict(
        vocab_size=97, num_layers=3, num_heads=4, kv_heads=2, head_dim=8,
        embed_dim=32, mlp_dim=24, norm="rms", positions="none", mlp="relu2",
        tie_embeddings=False, pattern="ME*", moe_experts=8,
        moe_router="sigmoid", moe_top_k=2, moe_scale=2.5, moe_shared_dim=40,
        moe_held=[0, 8], mamba_heads=4, mamba_head_dim=8, mamba_state=16,
        mamba_groups=2, mamba_conv=4, mamba_chunk=8),
    # generation by diffusion over blocks of 4: the decode step is a
    # block pass, a prefill pass samples nothing
    "block": dict(block_len=4, mask_id=63, norm="rms", positions="rotary",
                  qk_norm=True),
}
_PAD = 32   # every plain forward at one shape (causal: the tail is unseen)


@functools.lru_cache(maxsize=None)
def _ahead_model(kind):
    cfg = small_cfg(**_AHEAD_CFGS[kind])
    return cfg, T.init_params(cfg, jax.random.key(7))


def _plain_generation(cfg, params, prompt, n, rid, temperature, seed):
    """``n`` tokens after ``prompt``, one full forward a token: token i of
    request ``rid`` under ``fold_in(fold_in(key(seed), rid), i)``."""
    from paddle_tpu.serving.sampling import request_keys, sample_tokens

    fwd = _plain_forward(cfg)
    seq, out = list(prompt), []
    for i in range(n):
        ids = jnp.asarray([seq + [0] * (_PAD - len(seq))])
        logits = fwd(params, ids)[0, len(seq) - 1][None]
        keys = request_keys(jax.random.key(seed),
                            jnp.asarray([rid], jnp.int32),
                            jnp.asarray([i], jnp.int32))
        tok = int(sample_tokens(logits, keys,
                                jnp.asarray([temperature], jnp.float32))[0])
        out.append(tok)
        seq.append(tok)
    return out


@functools.lru_cache(maxsize=None)
def _plain_forward(cfg):
    return jax.jit(functools.partial(T.forward, cfg))


def _ahead_engine(kind, reg=None, **kw):
    cfg, params = _ahead_model(kind)
    serving = dict(max_slots=3, page_size=4, num_pages=48, max_prompt_len=12,
                   max_new_tokens=8, prefill_batch=2, seed=5)
    serving.update(kw)
    return ServingEngine(cfg, params, ServingConfig(**serving),
                         registry=reg or MetricsRegistry("ahead"))


def _in_flight(eng):
    return len(eng._in_flight)


def _run_drained(eng):
    """The synchronous order: whatever an iteration left in flight is
    read before the next one builds anything."""
    while eng.step():
        eng._drain("sync")


class TestOnePassAhead:
    @pytest.mark.parametrize("temperature", [0.0, 0.9],
                             ids=["greedy", "seeded"])
    @pytest.mark.parametrize("kind", list(_AHEAD_CFGS))
    def test_generations_equal_a_plain_forward(self, kind, temperature,
                                               rng_np):
        """Seven requests through three slots, two rows a prefill pass,
        arriving while others decode and finishing at different steps: each
        gets the tokens a token-by-token forward of the model gives it."""
        cfg, params = _ahead_model(kind)
        eng = _ahead_engine(kind)
        lens, news = (3, 9, 12, 1, 6, 5, 10), (8, 3, 5, 1, 7, 2, 6)
        prompts = [[int(t) for t in rng_np.integers(1, cfg.vocab_size, n)]
                   for n in lens]
        ids = [eng.submit(p, n, temperature)
               for p, n in zip(prompts[:2], news[:2])]
        for _ in range(3):      # the first two are decoding
            assert eng.step()
        ids += [eng.submit(p, n, temperature)
                for p, n in zip(prompts[2:5], news[2:5])]
        for _ in range(2):
            assert eng.step()
        ids += [eng.submit(p, n, temperature)
                for p, n in zip(prompts[5:], news[5:])]
        eng.run_until_idle()
        assert _in_flight(eng) == 0
        got = {r.id: r for r in eng.results()}
        want = lambda rid, prompt, n: _plain_generation(
            cfg, params, prompt, n, rid, temperature, seed=5)
        if kind == "block":
            sync = _ahead_engine(kind)
            assert ids == [sync.submit(p, n, temperature)
                           for p, n in zip(prompts, news)]
            _run_drained(sync)
            drained = {r.id: r for r in sync.results()}
            want = lambda rid, prompt, n: drained[rid].tokens
            for rid in ids:
                assert got[rid].trail["tokens"] == drained[rid].trail["tokens"]
                assert got[rid].trail["steps"] == drained[rid].trail["steps"]
        for rid, prompt, n in zip(ids, prompts, news):
            assert got[rid].finish_reason == "length"
            assert got[rid].tokens == want(rid, prompt, n), (kind, rid)
            assert len(got[rid].tokens) == n

    def test_an_eos_is_seen_one_pass_late(self, rng_np):
        """The pass after the one that sampled an eos is already queued
        when the eos is read: that row's surplus token is dropped and
        counted, never handed out, and nobody else can tell — tokens, page
        tables and the K/V every other sequence wrote are what a run gives
        in which the request ends at that token by LENGTH (known without a
        read: no surplus pass)."""
        cfg, params = _ahead_model("dense")
        prompts = [[int(t) for t in rng_np.integers(1, 64, n)]
                   for n in (9, 5, 11, 7)]
        hot = 1.5       # sampled, not greedy: a drawn toy repeats itself
        eng = _ahead_engine("dense")
        for p in prompts:
            eng.submit(p, 6, hot)
        eng.run_until_idle()
        free = sorted(eng.results(), key=lambda r: r.id)
        # request 1 ends at its 4th or 5th token; page_size 8: (5 + 6),
        # (5 + 4) and (5 + 5) tokens reserve the same two pages
        others = {t for r in free if r.id != 1 for t in r.tokens}
        at = next(i for i in (3, 4) if free[1].tokens[i] not in others
                  and free[1].tokens[i] not in free[1].tokens[:i])
        eos = free[1].tokens[at]

        def run(**kw):
            reg = MetricsRegistry("eos_late")
            eng = _ahead_engine("dense", reg, page_size=8, **kw)
            handed, rows = [], {}
            inner, admit = eng.scheduler.append_token, eng.scheduler.admit

            def watch(a, token):
                handed.append((a.request.id, len(a.generated), token))
                inner(a, token)

            def admitted(now=0.0):
                out = admit(now=now)
                for a in out:
                    rows[a.request.id] = eng.cache.page_table[a.slot].copy()
                return out

            eng.scheduler.append_token = watch
            eng.scheduler.admit = admitted
            news = [6, at + 1 if "eos_id" not in kw else 6, 6, 6]
            for p, n in zip(prompts, news):
                eng.submit(p, n, hot)
            eng.run_until_idle()
            res = {r.id: r for r in eng.results()}
            return eng, reg, res, handed, rows

        late, reg, got, handed, rows = run(eos_id=eos)
        base, reg0, want, handed0, rows0 = run()
        assert got[1].finish_reason == "eos"
        assert want[1].finish_reason == "length"
        assert got[1].tokens == free[1].tokens[:at + 1] == want[1].tokens
        # every token once, in order, with ``generated`` what it was
        # before it; the surplus one never
        assert handed == handed0
        for rid in (0, 2, 3):
            assert got[rid].tokens == free[rid].tokens == want[rid].tokens
        assert reg.get("serve_tokens_dropped_total").value() == 1
        assert reg0.get("serve_tokens_dropped_total").value() == 0
        assert reg.get("serve_tokens").value() == 6 * 3 + at + 1
        # the surplus row did ride one decode step more
        layers = cfg.cache_layers
        assert (reg.get("serve_layer_passes_total").value()
                == reg0.get("serve_layer_passes_total").value() + layers)
        # the others' page tables, and their K/V wherever they wrote it
        k, v = np.asarray(late.cache.k), np.asarray(late.cache.v)
        k0, v0 = np.asarray(base.cache.k), np.asarray(base.cache.v)
        for rid in (0, 2, 3):
            np.testing.assert_array_equal(rows[rid], rows0[rid])
            written = len(prompts[rid]) + len(got[rid].tokens) - 1
            for pos in range(written):
                page, off = rows[rid][pos // 8], pos % 8
                np.testing.assert_array_equal(k[:, :, page, off],
                                              k0[:, :, page, off])
                np.testing.assert_array_equal(v[:, :, page, off],
                                              v0[:, :, page, off])
        assert late.cache.allocator.free_pages == 47

    @pytest.mark.parametrize("kind", ["dense", "block"])
    def test_a_busy_run_reads_every_pass_once_and_never_drains(self, kind,
                                                               rng_np):
        """Seven requests through three slots, all queued before the first
        iteration: every pass is read exactly once, in the order it was
        dispatched; every pass but the first went out behind an unread one
        (``serve_passes_ahead_total``); the loop drains once, when nothing
        is left to dispatch, after its last pass."""
        reg = MetricsRegistry("busy")
        eng = _ahead_engine(kind, reg)
        value = lambda name, **lab: (reg.get(name).value(**lab)
                                     if reg.get(name) else 0)
        drained = lambda: sum(value("serve_loop_drains_total", why=w)
                              for w in ("idle", "stop", "swap", "incremental"))
        sent, reads, drains_at_send = [], [], []
        send, split = eng._send, eng._split_counts

        def watch_send(tracer, p, program, *args):
            drains_at_send.append(drained())
            send(tracer, p, program, *args)
            sent.append(p)

        def watch_read(out, rows, where):
            reads.append(out)
            return split(out, rows, where)

        eng._send, eng._split_counts = watch_send, watch_read
        news = (8, 3, 5, 1, 7, 2, 6)
        for n in news:
            eng.submit([int(t) for t in rng_np.integers(1, 63, 1 + n)], n)
        eng.run_until_idle()
        assert sorted(len(r.tokens) for r in eng.results()) == sorted(news)
        assert len(reads) == len(sent) and _in_flight(eng) == 0
        assert all(out is p.out for out, p in zip(reads, sent))
        decodes = sum(p.kind == "decode" for p in sent)
        # a decode step always follows something unread: the step before
        # it, or the prefill pass that admitted its first rows
        assert value("serve_passes_ahead_total", kind="decode") == decodes
        assert value("serve_passes_ahead_total", kind="prefill") == (
            len(sent) - decodes - 1)
        assert set(drains_at_send) == {0} and drained() == 1
        assert value("serve_loop_drains_total", why="idle") == 1

    def test_a_finish_by_length_needs_no_read(self, rng_np):
        """A sequence whose ``max_new_tokens`` the tokens in flight reach
        rides no further pass: decode steps run one row-layer for every
        token they hand out, nothing is dropped."""
        reg = MetricsRegistry("by_length")
        eng = _ahead_engine("dense", reg)
        cfg = eng.cfg
        news = (1, 2, 5, 8, 3)
        eng.generate([[int(t) for t in rng_np.integers(1, 64, 6)]
                      for _ in news], max_new_tokens=None)
        # generate() asks every request for the engine's cap: ask again
        reg = eng.registry = MetricsRegistry("by_length_2")
        decoded = []
        inner = eng.scheduler.append_token

        def watch(a, token):
            decoded.append(bool(a.generated))
            inner(a, token)

        eng.scheduler.append_token = watch
        for n in news:
            eng.submit([int(t) for t in rng_np.integers(1, 64, 6)], n)
        eng.run_until_idle()
        assert sorted(len(r.tokens) for r in eng.results()) == sorted(news)
        assert reg.get("serve_tokens").value() == sum(news)
        assert sum(decoded) == sum(n - 1 for n in news)
        assert (reg.get("serve_layer_passes_total").value()
                == sum(decoded) * cfg.cache_layers)
        assert reg.get("serve_tokens_dropped_total").value() == 0

    def test_an_engine_with_a_pass_in_flight_is_not_idle(self, rng_np):
        """``step()`` is True while a pass is unread; ``run_until_idle``,
        ``stop()`` and a weight swap (``set_params``) leave none."""
        reg = MetricsRegistry("in_flight")
        eng = _ahead_engine("dense", reg)
        drains = lambda why: (reg.get("serve_loop_drains_total").value(why=why)
                              if reg.get("serve_loop_drains_total") else 0)
        eng.submit([3, 1, 4], 2)
        # prefill and the first decode step go out; the prefill is read
        assert eng.step() and _in_flight(eng) == 1
        assert len(eng.scheduler.slots[0].generated) == 1
        assert eng.step() and _in_flight(eng) == 0      # the step is read
        assert eng.scheduler.slots[0].finished == "length"
        assert drains("idle") == 1 and not eng.results()
        assert eng.step() and not eng.step()            # retired: delivered
        assert [len(r.tokens) for r in eng.results()] == [2]
        # a swap reads what the old weights left in flight
        eng.submit([2, 7, 1, 8], 3)
        assert eng.step() and _in_flight(eng) == 1
        eng.set_params(eng.params)
        assert _in_flight(eng) == 0 and drains("swap") == 1
        assert len(eng.scheduler.slots[0].generated) == 2
        eng.run_until_idle()
        assert _in_flight(eng) == 0
        assert [len(r.tokens) for r in eng.results()] == [3]
        # stop() reads too, and delivers what that finishes
        eng.submit([5, 9, 2], 2)
        assert eng.step() and _in_flight(eng) == 1 and not eng.results()
        eng.stop()
        assert _in_flight(eng) == 0 and drains("stop") == 1
        assert [len(r.tokens) for r in eng.results()] == [2]
        assert eng.cache.allocator.free_pages == 47

    def test_a_background_loop_leaves_nothing_in_flight(self, rng_np):
        eng = _ahead_engine("dense")
        eng.start()
        try:
            ids = [eng.submit([int(t) for t in rng_np.integers(1, 64, 5)], n)
                   for n in (4, 1, 6, 2, 8)]
            got = eng.results(n=5, timeout=120.0)
        finally:
            eng.stop()
        assert sorted(r.id for r in got) == ids and _in_flight(eng) == 0
        assert sorted(len(r.tokens) for r in got) == [1, 2, 4, 6, 8]

    @pytest.mark.parametrize("kind", ["dense", "block"])
    def test_drains_from_another_thread_race_nothing(self, kind, rng_np):
        """``set_params`` (a weight swap's drain) from the caller's thread while
        the background loop runs one pass ahead: every pass is read once,
        every request gets the tokens it gets alone."""
        import sys
        import threading

        cfg, params = _ahead_model(kind)
        prompts = [[int(t) for t in rng_np.integers(1, 63, 4 + i % 5)]
                   for i in range(12)]
        news = [2 + i % 6 for i in range(12)]
        eng = _ahead_engine(kind)
        swaps, done = [0], threading.Event()

        def swapper():
            while not done.is_set():
                eng.set_params(eng.params)
                swaps[0] += 1

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        t = threading.Thread(target=swapper)
        eng.start()
        try:
            t.start()
            ids = [eng.submit(p, n) for p, n in zip(prompts, news)]
            got = eng.results(n=len(ids), timeout=120.0)
        finally:
            done.set()
            t.join(timeout=60.0)
            eng.stop()
            sys.setswitchinterval(interval)
        assert not t.is_alive() and swaps[0] > 0 and _in_flight(eng) == 0
        assert sorted(r.id for r in got) == ids
        by_id = {r.id: r.tokens for r in got}
        want = lambda rid, prompt, n: _plain_generation(
            cfg, params, prompt, n, rid, 0.0, seed=5)
        if kind == "block":     # mid-block drains: the engine never drained
            sync = _ahead_engine(kind)
            assert ids == [sync.submit(p, n) for p, n in zip(prompts, news)]
            sync.run_until_idle()
            alone = {r.id: r.tokens for r in sync.results()}
            want = lambda rid, prompt, n: alone[rid]
        for rid, prompt, n in zip(ids, prompts, news):
            assert by_id[rid] == want(rid, prompt, n)

    def test_a_failing_pass_fails_the_pending_requests(self, rng_np):
        """A device error surfaces where the pass is read, one pass late:
        it still kills the loop, which fails every pending request."""
        reg = MetricsRegistry("late_fault")
        eng = _ahead_engine("dense", reg)
        boom = RuntimeError("injected device fault")
        real, reads = eng._split_counts, []

        def read(out, rows, where):
            reads.append(where)
            if where == "decode":
                raise boom
            return real(out, rows, where)

        eng._split_counts = read
        eng.submit([1, 2, 3], 4)
        eng.submit([4, 5, 6], 4)
        eng.start()
        try:
            with pytest.raises(RuntimeError,
                               match="serving loop crashed") as ei:
                eng.results(n=1, timeout=60.0)
            assert ei.value.__cause__ is boom
            with pytest.raises(RuntimeError, match="submit refused"):
                eng.submit([1, 2, 3], 2)
        finally:
            eng.stop()
        # the prefill was read and handed out before the fault was met
        assert reads[0] == "prefill" and reads.count("decode") == 1
        assert reg.counter("serve_loop_crashes", "").value() == 1.0
        assert _in_flight(eng) == 0

    @pytest.mark.parametrize("via", ["step", "run_until_idle", "set_params",
                                     "stop"])
    def test_a_failing_pass_is_lost_for_every_caller(self, via, rng_np):
        """No loop thread: whoever meets the failure — a caller stepping by
        hand, a swap's drain, ``stop()`` — the step dispatched behind the
        failed one is lost with it.  What comes next (a step, a swap, a
        second ``stop()``) reads neither again."""
        eng = _ahead_engine("dense")
        boom = RuntimeError("injected device fault")
        real, reads = eng._split_counts, []

        def read(out, rows, where):
            reads.append(where)
            if where == "decode":
                raise boom
            return real(out, rows, where)

        eng._split_counts = read
        eng.submit([1, 2, 3], 6)
        assert eng.step() and _in_flight(eng) == 1     # the first decode step
        meet = {"step": eng.step, "run_until_idle": eng.run_until_idle,
                "set_params": lambda: eng.set_params(eng.params),
                "stop": eng.stop}[via]
        with pytest.raises(RuntimeError) as ei:
            meet()
        assert ei.value is boom and _in_flight(eng) == 0
        # a stepping caller had the next step out behind the failed one
        assert reads == ["prefill", "decode"]
        eng.set_params(eng.params)
        eng.stop()
        assert reads == ["prefill", "decode"] and _in_flight(eng) == 0


# -- the programs' text -----------------------------------------------------------
# What generation by blocks added to the loop (PR 35: the block in
# progress carried on the device) is chosen by ``cfg.block_len``; with a
# block length of 1 the serving programs are the ones they were, and so
# are a block model's prefill programs and its block pass as a caller
# lowers it that hands it no block state.


def _program_texts(kind):
    """The StableHLO of the programs an engine of ``kind`` compiles at
    its first admission (``_make_ready``): each member of the prefill
    ladder and the decode step, token array among the arguments — by
    blocks the prefill programs without it and the block pass in the
    12-argument form of ``serve_block_lm.aot_programs``."""
    eng = _ahead_engine(kind)
    cache, sched, bl = eng.cache, eng.scheduler, eng.cfg.block_len
    head = (eng.params, eng._base_key, cache.k, cache.v)
    texts = {}
    for n, length in sched.prefill_shapes:
        args = eng._dev(sched.prefill_arrays([], n, length), "ids",
                        "seq_lens", "page_table", "rids", "temps", "slots")
        texts[f"prefill {n}"] = eng._prefill.lower(
            *head, *args, cache.state,
            *([cache.tokens] if bl == 1 else [])).as_text()
    batch = sched.decode_arrays([])
    args = eng._dev(batch, "positions", "seq_lens", "page_table", "rids",
                    "gens", "temps")
    ids = cache.tokens
    if bl > 1:
        ids = jnp.asarray(batch["ids"][:, :2 * bl + 1])
    texts["decode"] = eng._decode.lower(
        *head, ids, *args, cache.state).as_text()
    return texts


# sha256 of each text as the tree BEFORE PR 35 lowers it (commit ceaf5eb,
# this file's helper run against a checkout of it), under the jax the
# hashes were taken with
_PARENT_JAX = "0.9.0"
_PARENT_TEXTS = {
    "dense": {"prefill 1": "f51f01045fdddbbf", "prefill 2": "acb55758a61e8117",
              "decode": "4aa4c8ac1aff7f45"},
    "looped": {"prefill 1": "8381819b3f182b7d",
               "prefill 2": "0ce97baf97b3f688",
               "decode": "88a364c4a92ed73e"},
    "pattern": {"prefill 1": "b438709ba0f50853",
                "prefill 2": "a87c11c902af4a0f",
                "decode": "01201bb80137c1dc"},
    "block": {"prefill 1": "d51d030ccd1e4bb1", "prefill 2": "816adfd62cf8cff6",
              "decode": "fa73163fd5696615"},
}


@pytest.mark.skipif(jax.__version__ != _PARENT_JAX,
                    reason="the recorded texts are another jax's")
@pytest.mark.parametrize("kind", list(_AHEAD_CFGS))
def test_programs_lower_to_the_parents_text(kind):
    import hashlib

    got = {name: hashlib.sha256(text.encode()).hexdigest()[:16]
           for name, text in _program_texts(kind).items()}
    assert got == _PARENT_TEXTS[kind]


def test_the_block_pass_lowers_without_the_state_array():
    """The 12-argument call ``benchmarks/drivers/serve_block_lm.py:
    aot_programs`` makes: ``ids`` [slots, 2B + 1] as given and no block
    state.  Nothing is carried then, and the pass computes what the
    engine's own 13-argument program computes for a row that opens its
    block from the same ids."""
    eng = _ahead_engine("block")
    cache, sched, bl = eng.cache, eng.scheduler, eng.cfg.block_len
    eng.submit([3, 1, 4, 1, 5, 9], 4)
    with eng._pump:
        sched.enqueue(eng._incoming.popleft())
    live = sched.admit()
    batch = sched.decode_arrays(live)
    assert batch["ids"].shape == (3, 2 * bl + 2)
    assert batch["ids"][0].tolist() == [5, 9, 0, 0, 0, 0, 1, 1, 1, 1]
    head = (eng.params, eng._base_key, cache.k, cache.v)
    rest = eng._dev(batch, "positions", "seq_lens", "page_table", "rids",
                    "gens", "temps")
    ids = jnp.asarray(batch["ids"])
    lowered = eng._decode.lower(*head, ids[:, :2 * bl + 1], *rest, {})
    out, _, _, _, none = lowered.compile()(
        *head, ids[:, :2 * bl + 1], *rest, {})
    want, _, _, _, block = eng._decode(*head, ids, *rest, {}, cache.tokens)
    assert none is None and block.shape == (3, 2 * bl)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # and the state the engine's program leaves: what the pass unmasked
    n = 3 * bl
    toks, chosen = (np.asarray(want)[:n].reshape(3, bl),
                    np.asarray(want)[n:2 * n].reshape(3, bl))
    assert chosen[0].sum() == batch["ids"][0, 2 * bl] == 1
    assert not chosen[0, :2].any()              # the prompt's tail is known
    assert np.asarray(block)[0, :bl].tolist() == [
        t if c else k for t, c, k in zip(toks[0], chosen[0], [5, 9, 0, 0])]
    assert np.asarray(block)[0, bl:].tolist() == [
        int(m and not c) for m, c in zip([0, 0, 1, 1], chosen[0])]
    assert not np.asarray(block)[1:].any()      # rows that ride no pass
