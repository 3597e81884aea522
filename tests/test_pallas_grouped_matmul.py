"""The grouped product of the routed expert sublayer
(``ops/pallas/grouped_matmul.py``, interpret mode on CPU) against its twin
``lax.ragged_dot`` AND a plain loop over groups: both forms of its one body
(*down*; *gate | up*, gated and ungated), bf16 operands accumulated in
float32, and the group sizes that try its visit list.  What a case is for
is its id.  ``tests/test_moe_arrangement.py`` holds the whole sublayer on
this route to the masked product and the reference; summed ≈ 15 s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.parallel import moe

M, K, N, G = 300, 64, 256, 6    # three row tiles of 128, two blocks of 128
BF, F32 = jnp.bfloat16, jnp.float32
FORMS = {"down": dict(out_dtype=F32),
         "gate_up": dict(act=jax.nn.silu, gated=True, out_dtype=BF),
         "act_up": dict(act=lambda h: jnp.square(jax.nn.relu(h)),
                        out_dtype=BF)}
SIZES = {
    "empty experts between live ones": [50, 0, 100, 30, 0, 60],
    "one expert holds every row": [0, 0, 300, 0, 0, 0],
    "groups straddle the row tiles": [70, 70, 70, 45, 44, 1],
    "a group ends on a tile's edge": [128, 128, 0, 0, 0, 44],
    "a total under the bound": [20, 0, 0, 90, 17, 0],
    "one row an expert": [1, 1, 1, 1, 1, 1],
    "no row at all": [0, 0, 0, 0, 0, 0],
}


@functools.cache
def _operands(dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    rows = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (G, K, N), dtype) * K ** -0.5
    gate = jax.random.normal(ks[2], (G, K, N), dtype) * K ** -0.5
    return rows, w, gate


@functools.cache
def _product(form, impl):
    """One form on one route, compiled once: sizes are an argument."""
    kw = dict(FORMS[form])
    gated = kw.pop("gated", False)
    if impl == "kernel":    # two blocks of columns, so the N axis is walked
        call = functools.partial(gm.grouped_matmul_kernel, interpret=True,
                                 tn=128)
    else:
        call = gm.grouped_matmul_reference
    return jax.jit(lambda rows, w, gate, sizes: call(
        rows, w, sizes, gate if gated else None, **kw))


def _loop(form, rows, w, gate, sizes):
    """The contract as a loop over groups, float32 all the way."""
    kw = FORMS[form]
    rows, w, gate = (np.asarray(a, np.float32) for a in (rows, w, gate))
    out, at = np.zeros((rows.shape[0], w.shape[2]), np.float32), 0
    for g, n in enumerate(sizes):
        y = rows[at:at + n] @ w[g]
        if kw.get("gated"):
            y = y * np.asarray(kw["act"](jnp.asarray(rows[at:at + n] @ gate[g])))
        elif "act" in kw:
            y = np.asarray(kw["act"](jnp.asarray(y)))
        out[at:at + n], at = y, at + n
    return out


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("what", list(SIZES))
def test_kernel_against_ragged_dot_and_a_loop(what, form):
    """bf16 operands, float32 accumulation: the twin's result to the last
    bit of the output's type or so, the loop's to bf16's rounding; rows
    past the groups exactly zero."""
    sizes = SIZES[what]
    rows, w, gate = _operands(BF)
    s = jnp.asarray(sizes, jnp.int32)
    got = _product(form, "kernel")(rows, w, gate, s)
    twin = _product(form, "reference")(rows, w, gate, s)
    assert got.dtype == twin.dtype == FORMS[form]["out_dtype"]
    assert got.shape == (M, N)
    got, twin = np.asarray(got, np.float32), np.asarray(twin, np.float32)
    tol = 1e-5 if form == "down" else 1e-2     # one bf16 rounding of O(1)
    np.testing.assert_allclose(got, twin, atol=tol, rtol=tol)
    np.testing.assert_allclose(got, _loop(form, rows, w, gate, sizes),
                               atol=2e-2, rtol=2e-2)
    assert not got[sum(sizes):].any()


def test_the_visit_list_names_every_pair_that_holds_rows_once_in_order():
    """Random group sizes against a plain count: the visits before
    ``count`` are exactly the (group, tile) pairs that hold rows — the
    rows past the groups as one more group — in group order, a tile's
    visits next to each other; no visit names an empty group's matrix;
    the visits from ``count`` on repeat the last."""
    rng, tm, m, g = np.random.default_rng(0), 16, 112, 9
    listed = jax.jit(lambda s: gm.visits(s, m, tm))
    for _ in range(40):
        sizes = rng.multinomial(rng.integers(0, m + 1), rng.dirichlet(
            np.full(g, 0.3))) * (rng.random(g) < 0.7)
        bounds, group, tile, matrix, count = (
            np.asarray(a) for a in listed(jnp.asarray(sizes, jnp.int32)))
        count = int(count[0])
        ends = np.cumsum(np.append(sizes, m - sizes.sum()))
        assert bounds.tolist() == [0, *ends]
        want = [(j, t) for j in range(g + 1) for t in range(m // tm)
                if max(bounds[j], t * tm) < min(bounds[j + 1], (t + 1) * tm)]
        assert list(zip(group[:count], tile[:count])) == want
        assert len(group) == m // tm + g >= count
        assert (group[count:] == group[count - 1]).all()
        assert (tile[count:] == tile[count - 1]).all()
        seen = tile[:count]
        assert all(a <= b for a, b in zip(seen, seen[1:]))
        if sizes.any():
            assert (sizes[matrix] > 0).all()
            live = group[:count] < g
            assert (matrix[:count][live] == group[:count][live]).all()


@pytest.mark.parametrize("form", ["down", "gate_up"])
def test_float32_operands_take_true_float32_products(form):
    rows, w, gate = _operands(F32)
    sizes = SIZES["groups straddle the row tiles"]
    got = _product(form, "kernel")(rows, w, gate, jnp.asarray(sizes))
    want = _loop(form, rows, w, gate, sizes)
    tol = 1e-4 if form == "down" else 1e-2      # gate | up comes out bf16
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("form", ["down", "gate_up"])
def test_an_empty_experts_matrices_are_never_read(form):
    """NaN in every matrix of the experts without rows: a visit of one
    would poison its tile (NaN x 0 is NaN), and none is made."""
    sizes = SIZES["empty experts between live ones"]
    rows, w, gate = _operands(BF)
    empty = jnp.asarray(sizes)[:, None, None] == 0
    got = _product(form, "kernel")(
        rows, jnp.where(empty, jnp.nan, w), jnp.where(empty, jnp.nan, gate),
        jnp.asarray(sizes, jnp.int32))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _loop(form, rows, w, gate, sizes), atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("form", ["down", "gate_up"])
def test_what_lies_past_the_groups_poisons_nothing(form):
    """The rows past the total weigh nothing: NaN there (in a tile a
    group shares, and in tiles no group touches) stays out of every
    group's rows, and those rows come out zero."""
    sizes = SIZES["a total under the bound"]
    total = sum(sizes)
    rows, w, gate = _operands(BF)
    dirty = rows.at[total:].set(jnp.nan)
    got = np.asarray(_product(form, "kernel")(
        dirty, w, gate, jnp.asarray(sizes, jnp.int32)), np.float32)
    assert np.isfinite(got).all() and not got[total:].any()
    np.testing.assert_allclose(got, _loop(form, rows, w, gate, sizes),
                               atol=2e-2, rtol=2e-2)


def test_a_short_batch_is_one_small_tile():
    """24 rows: one tile of 32 (bf16 packs 16 rows), padded and cut."""
    rows, w, gate = _operands(BF)
    sizes = jnp.asarray([3, 0, 9, 0, 5, 2], jnp.int32)
    got = jax.jit(lambda r, w, s: gm.grouped_matmul(
        r, w, s, impl="kernel"))(rows[:24], w, sizes)
    want = gm.grouped_matmul_reference(rows[:24], w, sizes)
    assert got.shape == (24, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("held", [(2, 5), None], ids=["held 3 of 8", "all"])
def test_the_sublayer_on_both_routes_over_a_held_share(monkeypatch, held):
    """``moe_routed``'s sorted side through the kernel and through
    ``ragged_dot``: the same result and counts, and the census says which
    ran."""
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)
    cfg = moe.RoutedConfig(num_experts=8, top_k=2, held=held, act="silu",
                           gated=True)
    ks = jax.random.split(jax.random.key(5), 5)
    p = {"router": jax.random.normal(ks[0], (K, 8)),
         **{n: jax.random.normal(k, (cfg.num_held, *s)) * s[0] ** -0.5
            for n, k, s in (("w_in", ks[1], (K, 128)),
                            ("w_gate", ks[2], (K, 128)),
                            ("w_out", ks[3], (128, K)))}}
    x = jax.random.normal(ks[4], (40, K))
    live = jnp.arange(40) % 6 != 1
    out = {}
    for impl in ("kernel", "reference"):
        with pallas.capture_routes() as routes:
            out[impl] = jax.jit(lambda p, x: moe.moe_routed(
                p, x, cfg, live, impl=impl))(p, x)
        assert routes == {("moe_experts", impl): 1}
    (y, counts), (y_ref, counts_ref) = out["kernel"], out["reference"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
    assert not np.asarray(y)[~np.asarray(live)].any()


@pytest.mark.parametrize("k,n,gated,takes", [
    (4096, 1280, True, True),       # solar-open2's gate | up: two blocks
    (1280, 4096, False, True),      # ... and down: one
    (2688, 1856, False, True),      # nemotron-3-nano's: whole, 14.5 vregs
    (8192, 4000, True, False),      # too wide whole, and no whole lanes
    (2048, 2048, True, True),       # zaya1's
])
def test_the_shapes_the_kernel_takes(k, n, gated, takes):
    assert gm.supports(k, n, BF, gated) is takes
    if takes:
        tn = gm._n_tile(k, n, 2, 1 + gated)
        assert tn == n or (n % tn == 0 and tn % 128 == 0)
        assert k * tn * 2 * (1 + gated) <= gm._BLOCK_BYTES


def test_entry_routes_and_refusals(monkeypatch):
    rows, w, gate = _operands(BF)
    sizes = jnp.asarray(SIZES["one row an expert"], jnp.int32)
    with pallas.capture_routes() as routes:
        a = gm.grouped_matmul(rows, w, sizes)               # auto, off a TPU
        monkeypatch.setattr(gm, "_BLOCK_BYTES", K * 100 * 2)
        b = gm.grouped_matmul(rows, w[:, :, :200], sizes, impl="kernel")
    assert routes == {("grouped_matmul", "reference"): 1,
                      ("grouped_matmul", "reference_shape"): 1}
    assert a.shape == (M, N) and b.shape == (M, 200)
    with pytest.raises(ValueError, match="activation"):
        gm.grouped_matmul(rows, w, sizes, gate=gate)
    with pytest.raises(ValueError, match="impl"):
        gm.grouped_matmul(rows, w, sizes, impl="fast")
