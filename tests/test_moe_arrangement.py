"""Which arrangement ``moe_routed`` picks for a pass's rows: every held
expert over every row (masked by the combine weight) up to a limit, above
it the assignments sorted by expert through ``lax.ragged_dot``.  The masked
product does ``num_experts / top_k`` times the assigned work, so past 32 x
the limit falls in proportion; it is never raised."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import moe

D, F = 16, 8


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


def _grouped(cfg, t):
    fn = lambda p, x, live: moe.moe_routed(p, x, cfg, live)
    text = str(jax.make_jaxpr(fn)(
        _tree(cfg), jax.ShapeDtypeStruct((t, D), jnp.float32),
        jax.ShapeDtypeStruct((t,), jnp.bool_)))
    return "ragged_dot" in text


# (num_experts, top_k, held) of the benchmark's four routed configurations
# and the rows of the programs their engines compile (decode step or block
# pass, the prefill ladder's members), with a row count either side of
# each limit
NEMO3N, SDAR30, ZAYA1, SOLAR2 = ((128, 6, (0, 64)), (128, 8, None),
                                 (16, 1, None), (320, 8, (0, 40)))


@pytest.mark.parametrize("name,router,t,grouped", [
    *[(name, router, t, False)
      for name, router in (("nemo3n", NEMO3N), ("sdar30", SDAR30),
                           ("zaya1", ZAYA1))
      for t in (64, 256, 512, 2048)],
    ("nemo3n", NEMO3N, 2049, True),
    ("sdar30", SDAR30, 4096, True),
    ("solar2", SOLAR2, 32, False),
    ("solar2", SOLAR2, 1638, False),
    ("solar2", SOLAR2, 1639, True),
    ("solar2", SOLAR2, 2048, True),
    ("solar2", SOLAR2, 4096, True),
    ("solar2", SOLAR2, 8192, True),
])
def test_arrangement_by_router_and_rows(name, router, t, grouped):
    experts, top_k, held = router
    cfg = moe.RoutedConfig(num_experts=experts, top_k=top_k, held=held,
                           act="silu", gated=True)
    assert _grouped(cfg, t) is grouped


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


@pytest.mark.parametrize("held", [(0, 10), (30, 40), None])
def test_masked_and_grouped_agree_past_32x(monkeypatch, held):
    """80 experts, top-2 (40 x): 1,640 rows go through the grouped product
    where the limit is 1,638; the masked product over the same rows (the
    limit's rule lifted) gives the same result and counts, padding rows
    routed nowhere."""
    cfg = moe.RoutedConfig(num_experts=80, top_k=2, held=held, act="silu",
                           gated=True)
    tree = _tree(cfg, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1640, D), jnp.float32)
    live = jnp.arange(1640) % 5 != 2
    assert _grouped(cfg, 1640)
    got, counts = moe.moe_routed(tree, x, cfg, live)
    monkeypatch.setattr(moe, "_DENSE_WASTE", 1 << 30)
    assert not _grouped(cfg, 1640)
    with jax.default_matmul_precision("highest"):
        want, counts_masked = moe.moe_routed(tree, x, cfg, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_masked))
    assert not np.asarray(got)[~np.asarray(live)].any()
    assert int(counts[0] + counts[1]) == int(live.sum()) * 2
