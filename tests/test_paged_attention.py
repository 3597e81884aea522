"""Paged attention and the cache it reads (``ops/pallas/paged_attention.py``):
ragged and block attention against the jnp oracle (the kernels in
interpret mode), the writes — the decode kernel's own among them — the
decode step's plan, and bit-exact incremental decode.  The engine around them: ``tests/test_serving_engine.py``,
``tests/test_serving_loop.py``.  What an interpreted-kernel case is FOR is
its id; one that needs ``head_dim`` 128 or a whole pass says so.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer as T
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.serving import ServingConfig, ServingEngine

import lm_toy


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


@functools.cache
def _attend(impl, fn=PA.ragged_paged_attention, **static):
    """Ragged (or block) paged attention under one ``impl``, compiled
    (the interpreted kernel is a program like any other)."""
    if impl == "kernel":
        static["interpret"] = True
    return jax.jit(functools.partial(fn, impl=impl, **static))


# H, D, page_size, dtype of the blocked-kernel cases (H = the heads the
# cache holds), named by what each is for.  The page slots a grid step
# covers (``decode_block_pages``: beside each, at a table of 66 pages)
# follow from the cache's bytes a token, so a serve cell's case keeps the
# cell's heads, head_dim and page: fewer of either is another block.
# ``toy``: one lane group, a block as wide as the table; ``chip_smoke``:
# its float32 case; the cells' caches in bf16: ``gpt2l`` two heads a lane
# group, ``ouro`` one (a block = one 128-token pass), two / four K/V heads
# (nemotron and zaya, sdar) at their longer blocks; ``odd_heads``.
_BLOCK_SHAPES = {
    "toy_block_is_the_whole_table": (2, 16, 8, "float32"),
    "chip_smoke_f32_six_lane_groups": (12, 64, 16, "float32"),
    "gpt2l_ten_lane_groups_d64": (20, 64, 16, "bfloat16"),
    "ouro_one_128_token_pass_d128": (16, 128, 16, "bfloat16"),
    "odd_heads_last_group_half_padding": (5, 64, 16, "bfloat16"),
    "two_kv_heads_block_of_64_pages_d128": (2, 128, 16, "bfloat16"),
    "four_kv_heads_block_of_32_pages_d128": (4, 128, 16, "bfloat16")}
_BLOCK_PAGES = {
    "toy_block_is_the_whole_table": 66,
    "chip_smoke_f32_six_lane_groups": 16,
    "gpt2l_ten_lane_groups_d64": 16,
    "ouro_one_128_token_pass_d128": 8,
    "odd_heads_last_group_half_padding": 48,
    "two_kv_heads_block_of_64_pages_d128": 64,
    "four_kv_heads_block_of_32_pages_d128": 32}
_BLOCK_LENGTHS = ("idle", "one", "one_block", "block_plus_1", "whole_table",
                  "ragged")
_LAYERS, _LAYER = 3, 1  # the blocked cases' pools, and the layer addressed
# (heads, head_dim) of the write cases: what the lane groups look like
_GROUPINGS = [pytest.param(4, 64, id="two_whole_lane_groups"),
              pytest.param(3, 64, id="last_group_half_padding"),
              pytest.param(2, 128, id="one_head_a_group_d128")]


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
    ref = _attend("reference")(
        q, jnp.asarray(as_pool(kp, _LAYERS, _LAYER), dtype),
        jnp.asarray(as_pool(vp, _LAYERS, _LAYER), dtype), _LAYER, pt, lens)
    # the kernel's pools: NaN on the null page of the layer it reads and
    # everywhere in the layers it must not touch
    poison = lambda a: jnp.asarray(
        as_pool(a, _LAYERS, _LAYER, fill=np.nan), dtype
    ).at[_LAYER, :, 0].set(jnp.nan)
    ker = _attend("kernel")(q, poison(kp), poison(vp), jnp.int32(_LAYER),
                            pt, lens)
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
        ref = _attend("reference")(q, kp, vp, 0, pt, lens)
        ker = _attend("kernel")(q, kp, vp, 0, pt, lens)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("case", _BLOCK_LENGTHS)
    @pytest.mark.parametrize("maxp", [18, 66])  # no multiple of a shorter N
    @pytest.mark.parametrize("shape", list(_BLOCK_SHAPES))
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

    @pytest.mark.parametrize("kv_heads", [
        pytest.param(2, id="two_kv_heads_block_of_64_pages_d128"),
        pytest.param(4, id="four_kv_heads_block_of_32_pages_d128")])
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
        run = lambda impl: _attend(
            impl, PA.block_paged_attention, kv_heads=kv_heads)(
            q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
            1, pt, lens)
        ker = np.asarray(run("kernel"), np.float32)
        ref = np.asarray(run("reference"), np.float32)
        np.testing.assert_allclose(ker, ref, rtol=2e-2, atol=2e-2)
        assert not ker[1].any()

    @pytest.mark.parametrize("shape", [
        "toy_block_is_the_whole_table", "gpt2l_ten_lane_groups_d64",
        "two_kv_heads_block_of_64_pages_d128"])
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
        ref = _attend("reference")(q, *pools(0.0, 0.0), _LAYER, pt, lens)
        for k_fill, v_fill in ((np.nan, np.inf), (-np.inf, np.nan)):
            ker = _attend("kernel")(q, *pools(k_fill, v_fill),
                                    jnp.int32(_LAYER), pt, lens)
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
        (20, 64, (36, 10, 1537, 16, 128)), (16, 128, (192, 16, 145, 16, 128)),
        (5, 64, (36, 3, 1537, 16, 128)), (2, 16, (36, 1, 1537, 16, 32)),
        (4, 256, (36, 4, 1537, 16, 256)),
    ], ids=["gpt2l_two_heads_a_group", "ouro_plain_head_major",
            "odd_heads_last_group_half_padding",
            "fewer_heads_than_the_lanes_hold", "wider_than_the_lanes"])
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

    @pytest.mark.parametrize("heads,head_dim", _GROUPINGS)
    @pytest.mark.parametrize("write", ["decode", "fused", "chunk", "prefill"])
    def test_write_touches_only_its_cache_layer(self, rng_np, write, heads,
                                                head_dim):
        """A write at cache layer 1 leaves every other layer's pages, and
        every page of layer 1 it does not name, bit-identical; what it
        wrote reads back through the oracle's gather; a whole-stack
        prefill writes every layer.  ``fused``: the decode kernel's own
        write (``decode_attention``, interpreted)."""
        layers, pages, ps, b, t = 3, 12, 4, 2, 8
        shape = PA.kv_pool_shape(layers, heads, pages, ps, head_dim)
        kc = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
        vc = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0]], np.int32))
        lens = jnp.asarray([7, 5])
        new = lambda *lead: jnp.asarray(rng_np.normal(
            size=(*lead, heads, head_dim)).astype(np.float32))
        if write in ("decode", "fused"):
            k, v = new(b), new(b)
            if write == "decode":
                kc1, vc1 = PA.write_decode_kv(kc, vc, k, v, 1, pt, lens - 1)
            else:
                _, (kc1, vc1) = jax.jit(functools.partial(
                    PA.decode_attention, impl="kernel", interpret=True))(
                    new(b), k, v, kc, vc, 1, pt, lens - 1, lens)
            named = [(1, 2, int(lens[0] - 1) % ps), (1, 5, int(lens[1] - 1) % ps)]
            write = "decode"
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

    @pytest.mark.parametrize("heads,head_dim", _GROUPINGS)
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

    @pytest.mark.parametrize("kv_heads", [
        pytest.param(4, id="a_query_head_a_kv_head"),
        pytest.param(2, id="grouped_query_heads_rep_2")])
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
        run = functools.partial(_attend("kernel", kv_heads=kv_heads), q, kc,
                                vc, 1, pt, lens)
        np.testing.assert_array_equal(np.asarray(run(plan=plan)),
                                      np.asarray(run()))

    @pytest.mark.parametrize("impl", ["kernel", "reference"])
    @pytest.mark.parametrize("kind", ["dense", "looped"])
    def test_the_layer_loop_holds_none_of_the_index_arithmetic(self, kind,
                                                               impl):
        """``forward_decode``'s layer loop: no cumulative sum (the work
        list) and no whole-number division or remainder (page and row of
        a position, blocks of a length) is left in its body — and under
        the kernel no scatter either: the Mosaic call writes the token
        itself; the reference path keeps the scatter and has no call."""
        cfg = lm_toy.small_cfg(loop_steps=2 if kind == "looped" else 1)
        params = T.init_params(cfg, jax.random.key(0))
        kc, vc = PA.init_kv_pages(cfg.cache_layers, cfg.kv_heads, 9, 4,
                                  cfg.head_dim)
        b = 3
        args = (jnp.zeros(b, jnp.int32), jnp.asarray([5, 0, 2]),
                jnp.asarray([6, 0, 3]), jnp.zeros((b, 4), jnp.int32))
        body = _body_primitives(jax.make_jaxpr(
            lambda *a: T.forward_decode(cfg, params, *a, kc, vc,
                                        attn_impl=impl))(*args).jaxpr)
        names = {name for name, _ in body}
        mine, other = (("pallas_call", "scatter") if impl == "kernel"
                       else ("scatter", "pallas_call"))
        assert mine in names and other not in names and "cumsum" not in names
        assert not body & {("div", "i"), ("rem", "i"), ("floor_divide", "i")}


# -- the decode step's write inside the kernel ------------------------------------

_FUSED_ROWS = {"token_in_the_rows_last_block": (0,),
               "token_in_an_earlier_block_of_a_full_ring": (1, 5),
               "token_opens_a_new_page": (2,),
               "idle_and_mid_prefill_rows_write_nothing": (3, 4)}
_FUSED_FORMS = {"fewer_kv_heads_than_query_heads": dict(rep=2),
                "wide_v_over_whole_lane_groups": dict(rep=2, wide_v=True)}


@functools.lru_cache(maxsize=None)
def _fused_case(heads, head_dim, rep=1, wide_v=False):
    """One batch holding every row of ``_FUSED_ROWS`` through
    ``decode_attention`` at cache layer 1 of 3, kernel (interpreted) and
    reference: blocks of 128 tokens (8 pages of 16: ``_STEP_BYTES`` cut
    to nothing, or a toy's block is its whole table), a table of 20
    pages.  Row 0 writes its last position, in its second block; rows 1
    and 5 are rings full at 320 whose position lies in their first and
    second block of three; row 2's token is the first of its third page;
    row 3 is idle behind a zero table row; row 4 is mid-prefill: pages
    mapped, ``seq_len`` 0.  The d128 grouping runs in bfloat16 (a packed
    row is half a sublane)."""
    ps, maxp, layers = 16, 20, 3
    dtype = jnp.bfloat16 if head_dim == 128 else jnp.float32
    if wide_v:  # whole lane groups of K/V heads
        heads = -(-heads // PA.head_group(heads, head_dim)) * PA.head_group(
            heads, head_dim)
    lens = np.array([150, 320, 33, 0, 0, 320], np.int32)
    at = np.array([149, 37, 32, 0, 0, 200], np.int32)
    used = [10, 20, 3, 0, 2, 20]
    rng = np.random.default_rng(heads * head_dim + rep)
    pool = 1 + sum(used) + 4
    ids = rng.permutation(np.arange(1, pool))
    pt, nxt = np.zeros((len(lens), maxp), np.int32), 0
    for b, u in enumerate(used):
        pt[b, :u] = ids[nxt:nxt + u]
        nxt += u
    shape = PA.kv_pool_shape(layers, heads, pool, ps, head_dim)
    kc, vc = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(lens), heads * rep, head_dim)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(len(lens), heads, head_dim)), dtype)
            for _ in range(2))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(PA, "_STEP_BYTES", 1)
        assert PA.decode_block_pages(heads, ps, head_dim, dtype.dtype.itemsize,
                                     maxp) == 8
        run = lambda impl: jax.jit(functools.partial(
            PA.decode_attention, impl=impl, interpret=True, kv_heads=heads,
            wide_v=wide_v))(q, k, v, kc, vc, 1, pt, at, lens)
        (a, pools), (want_a, want) = run("kernel"), run("reference")
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    return (pt, lens, f32(a), f32(want_a), [f32(x) for x in pools],
            [f32(x) for x in want], [f32(kc), f32(vc)], dtype)


@pytest.mark.parametrize("case", [*_FUSED_ROWS, *_FUSED_FORMS])
@pytest.mark.parametrize("heads,head_dim", _GROUPINGS)
def test_the_fused_write_is_the_scatter_then_the_reference(heads, head_dim,
                                                           case):
    """``decode_attention`` under the interpreted kernel against
    ``write_decode_kv`` + the jnp oracle: the attention to the kernel
    tests' tolerance, BOTH pools bit for bit — every page but the null
    page, which the scatter gives an idle row's token and the kernel
    leaves as it was, as it does the pages of a row with ``seq_len`` 0."""
    pt, lens, a, want_a, pools, want, before, dtype = _fused_case(
        heads, head_dim, **_FUSED_FORMS.get(case, {}))
    rows = _FUSED_ROWS.get(case, range(len(lens)))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(a).all()
    np.testing.assert_allclose(a[list(rows)], want_a[list(rows)], rtol=tol,
                               atol=tol)
    untouched = [0, *pt[4][pt[4] > 0]]  # the null page, the mid-prefill row's
    for got, ref, was in zip(pools, want, before):
        np.testing.assert_array_equal(got[:, :, untouched],
                                      was[:, :, untouched])
        if case not in _FUSED_ROWS:     # a form: the whole of both pools
            ref = ref.copy()
            ref[:, :, untouched] = was[:, :, untouched]
            np.testing.assert_array_equal(got, ref)
            continue
        for b in rows:
            mine = pt[b][pt[b] > 0]
            if lens[b]:     # the scatter's pages, and the token is in them
                np.testing.assert_array_equal(got[:, :, mine], ref[:, :, mine])
                assert (got[1][:, mine] != was[1][:, mine]).any()
            else:
                assert not a[b].any()


class TestBitExactDecode:
    def test_paged_incremental_equals_full_context_argmax(self, rng_np):
        """The acceptance bit-exactness property: engine tokens (paged
        cache + prefill/decode split + continuous batching) equal
        repeated full-context ``forward`` argmax per prompt."""
        cfg = lm_toy.small_cfg()
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
            # compile at a padded length instead of one per length
            assert res.tokens == lm_toy.forward_argmax(
                cfg, params, prompt, res.tokens, 24)


# -- fewer K/V heads than query heads; a block of positions a row -----------------


@pytest.mark.parametrize("h, kv, d", [
    (4, 2, 8), (6, 3, 64), (32, 2, 128), (3, 3, 64)], ids=[
    "rep_2_both_kv_heads_in_one_lane_group",
    "rep_2_last_lane_group_half_padding",
    "rep_16_above_8_one_head_a_group_d128", "rep_1_padded_to_8_query_rows"])
def test_decode_kernel_with_fewer_kv_heads(h, kv, d):
    """The interpreted kernel against the jnp reference: rep query heads
    of a K/V head on the query rows, with and without lane groups of
    several heads, a padded last group, rep above and below 8."""
    ks = jax.random.split(jax.random.key(h * d), 3)
    b, pages, ps = 3, 12, 4
    shape = PA.kv_pool_shape(2, kv, pages, ps, d)
    kc, vc = (jax.random.normal(k, shape) for k in ks[:2])
    q = jax.random.normal(ks[2], (b, h, d))
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    lens = jnp.asarray([11, 6, 0])
    reference = _attend("reference", kv_heads=kv)
    for layer in (0, 1):
        want = reference(q, kc, vc, layer, table, lens)
        got = _attend("kernel", kv_heads=kv)(q, kc, vc, layer, table, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    # and the reference is attention with each K/V head repeated
    k = PA._gather_context(kc, 0, table, kv, d)[:1, :, :11]
    v = PA._gather_context(vc, 0, table, kv, d)[:1, :, :11]
    k, v = (jnp.repeat(x, h // kv, axis=1) for x in (k, v))
    p = jax.nn.softmax(jnp.einsum("hd,hkd->hk", q[0], k[0]) * d ** -0.5, -1)
    np.testing.assert_allclose(
        np.asarray(reference(q, kc, vc, 0, table, lens)[0]),
        np.asarray(jnp.einsum("hk,hkd->hd", p, v[0])), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_block_positions_ride_the_query_heads(impl):
    """``rep`` 8 x block 4 = 32 query rows a K/V head (the shape the
    benchmark's configuration runs), ragged lengths and an idle row: the
    folded call against plain attention of every position over the row's
    whole context; the kernel in interpret mode."""
    b, kv, rep, d, ps, maxp, bl = 3, 2, 8, 128, 8, 4, 4
    h = kv * rep
    ks = jax.random.split(jax.random.key(2), 4)
    q = jax.random.normal(ks[0], (b, bl, h, d))
    kc = jax.random.normal(ks[1], PA.kv_pool_shape(2, kv, 16, ps, d))
    vc = jax.random.normal(ks[2], PA.kv_pool_shape(2, kv, 16, ps, d))
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [0, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([28, 12, 0], jnp.int32)
    got = _attend(impl, PA.block_paged_attention, kv_heads=kv)(
        q, kc, vc, 1, table, lens)
    assert got.shape == q.shape
    kk = PA._gather_context(kc, 1, table, kv, d)     # [B, KV, T, D]
    vv = PA._gather_context(vc, 1, table, kv, d)
    s = jnp.einsum("btgrd,bgkd->btgrk", q.reshape(b, bl, kv, rep, d),
                   kk) * d ** -0.5
    s = jnp.where(jnp.arange(maxp * ps) < lens[:, None, None, None, None],
                  s, -1e30)
    want = jnp.einsum("btgrk,bgkd->btgrd", jax.nn.softmax(s, -1), vv)
    want = jnp.where(lens[:, None, None, None, None] > 0, want, 0.0)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.reshape(q.shape)), atol=2e-5)
