"""Parity tests for the sharded-embedding kernel pair
(ops/pallas/tpp/embedding.py): every ``pallas_call`` entry against its
``*_reference`` twin (the GL-KERNEL contract), plus the fused lookup's
custom_vjp against a dense one-device oracle.

Kernels run in interpret mode on the CPU testbed.  Touched rows compare
at float tolerance (separately-jitted programs fuse differently);
UNTOUCHED rows in the sparse row update must stay bit-identical — that
is the lazy-sparse optimizer contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.tpp import (
    dedup_ids, dedup_ids_reference,
    embedding_gather, embedding_gather_reference,
    embedding_scatter_add, embedding_scatter_add_reference,
    fused_embedding_lookup,
    sparse_row_update, sparse_row_update_reference,
)


# ---------------------------------------------------------------------------
# dedup_ids
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ids", [
    [3, 1, 3, 7, 1, 0],          # duplicates
    [5, 2, 9, 0],                # all unique
    [4, 4, 4, 4],                # all duplicate
    [11],                        # single id (ragged/odd n)
])
def test_dedup_ids_matches_reference(ids):
    ids = jnp.asarray(ids, jnp.int32)
    u_k, inv_k = dedup_ids(ids)
    u_r, inv_r = dedup_ids_reference(ids)
    np.testing.assert_array_equal(u_k, u_r)
    np.testing.assert_array_equal(inv_k, inv_r)
    # reconstruction: uids[inv] == ids, -1 fill only past the unique count
    np.testing.assert_array_equal(np.asarray(u_k)[np.asarray(inv_k)],
                                  np.asarray(ids).ravel())
    nuniq = len(set(np.asarray(ids).ravel().tolist()))
    assert (np.asarray(u_k)[:nuniq] >= 0).all()
    assert (np.asarray(u_k)[nuniq:] == -1).all()


def test_dedup_ids_capacity_and_2d():
    ids = jnp.asarray([[3, 1], [3, 7]], jnp.int32)
    u, inv = dedup_ids(ids, capacity=8)
    assert u.shape == (8,) and inv.shape == (4,)
    np.testing.assert_array_equal(np.asarray(u)[np.asarray(inv)],
                                  np.asarray(ids).ravel())


# ---------------------------------------------------------------------------
# embedding_gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [8, 130])  # below / past one lane tile
def test_embedding_gather_matches_reference(rng_np, dtype, d):
    v = 37
    table = jnp.asarray(rng_np.normal(size=(v, d)), dtype)
    ids = jnp.asarray(rng_np.integers(0, v, size=(11,)), jnp.int32)
    got = embedding_gather(table, ids, impl="kernel", interpret=True)
    ref = embedding_gather_reference(table, ids)
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(got, ref)


def test_embedding_gather_2d_ids(rng_np):
    table = jnp.asarray(rng_np.normal(size=(16, 8)), jnp.float32)
    ids = jnp.asarray(rng_np.integers(0, 16, size=(3, 5)), jnp.int32)
    got = embedding_gather(table, ids, impl="kernel", interpret=True)
    assert got.shape == (3, 5, 8)
    np.testing.assert_array_equal(got, embedding_gather_reference(table, ids))


# ---------------------------------------------------------------------------
# embedding_scatter_add
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["dup", "unique", "all_dup", "ragged"])
def test_embedding_scatter_add_matches_reference(rng_np, case):
    v, d = 40, 8
    table = jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
    ids = {
        "dup": [3, 1, 3, 7, 1, 3],
        "unique": [5, 2, 9, 0, 11, 38],
        "all_dup": [4, 4, 4, 4, 4],
        "ragged": [13],
    }[case]
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.asarray(rng_np.normal(size=(ids.shape[0], d)), jnp.float32)
    got = embedding_scatter_add(table, ids, rows, impl="kernel",
                                interpret=True)
    ref = embedding_scatter_add_reference(table, ids, rows)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # untouched rows pass through bit-identically
    touched = set(np.asarray(ids).tolist())
    keep = np.asarray([i for i in range(v) if i not in touched])
    np.testing.assert_array_equal(np.asarray(got)[keep],
                                  np.asarray(table)[keep])


def test_embedding_scatter_add_skips_negative_ids(rng_np):
    """-1 ids are the dedup fill convention: contribute nothing."""
    v, d = 16, 8
    table = jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
    ids = jnp.asarray([2, -1, 5, -1], jnp.int32)
    rows = jnp.asarray(rng_np.normal(size=(4, d)), jnp.float32)
    got = embedding_scatter_add(table, ids, rows, impl="kernel",
                                interpret=True)
    ref = embedding_scatter_add_reference(table, ids, rows)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    keep = np.asarray([i for i in range(v) if i not in (2, 5)])
    np.testing.assert_array_equal(np.asarray(got)[keep],
                                  np.asarray(table)[keep])


# ---------------------------------------------------------------------------
# sparse_row_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("momentum", [False, True])
@pytest.mark.parametrize("nesterov", [False, True])
def test_sparse_row_update_matches_reference(rng_np, momentum, nesterov):
    if nesterov and not momentum:
        pytest.skip("nesterov needs a velocity slot")
    v, d = 24, 8
    p = jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
    g = jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
    touched = jnp.asarray(rng_np.uniform(size=(v,)) < 0.3)
    g = jnp.where(touched[:, None], g, 0.0)  # sparse-row gradient
    vel = (jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
           if momentum else None)
    kw = dict(lr=0.1, weight_decay=0.02)
    if momentum:
        kw.update(mu=0.9, nesterov=nesterov)
    p_k, v_k = sparse_row_update(p, g, vel, impl="kernel", interpret=True,
                                 **kw)
    p_r, v_r = sparse_row_update_reference(p, g, vel, **kw)
    np.testing.assert_allclose(p_k, p_r, rtol=1e-5, atol=1e-6)
    keep = ~np.asarray(touched)
    # lazy-sparse contract: untouched rows bit-identical (param AND slot)
    np.testing.assert_array_equal(np.asarray(p_k)[keep], np.asarray(p)[keep])
    if momentum:
        np.testing.assert_allclose(v_k, v_r, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(v_k)[keep],
                                      np.asarray(vel)[keep])
    else:
        assert v_k is None and v_r is None


# ---------------------------------------------------------------------------
# fused_embedding_lookup (custom_vjp) vs the dense oracle
# ---------------------------------------------------------------------------


def _dense_oracle(table, ids, padding_idx=None):
    got = jnp.take(table, ids, axis=0)
    if padding_idx is not None:
        got = jnp.where((ids == padding_idx)[..., None], 0.0,
                        got.astype(jnp.float32)).astype(table.dtype)
    return got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ids", [
    [3, 1, 3, 7, 1, 0],          # duplicates
    [5, 2, 9, 0],                # all unique
    [4, 4, 4],                   # all duplicate
    [11],                        # ragged
])
def test_fused_embedding_lookup_fwd_and_vjp(rng_np, dtype, ids):
    v, d = 16, 8
    table = jnp.asarray(rng_np.normal(size=(v, d)), dtype)
    ids = jnp.asarray(ids, jnp.int32)
    got = fused_embedding_lookup(table, ids, None, "kernel", True)
    np.testing.assert_array_equal(got, _dense_oracle(table, ids))

    def loss_fused(tbl):
        out = fused_embedding_lookup(tbl, ids, None, "kernel", True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(tbl):
        return jnp.sum(_dense_oracle(tbl, ids).astype(jnp.float32) ** 2)

    gk = jax.jit(jax.grad(loss_fused))(table)
    gr = jax.jit(jax.grad(loss_dense))(table)
    assert gk.dtype == table.dtype
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 else \
        dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(gk, np.float32),
                               np.asarray(gr, np.float32), **tol)
    # duplicate ids accumulate exactly: rows never in ids get zero grad
    untouched = np.asarray([i for i in range(v)
                            if i not in set(np.asarray(ids).tolist())])
    np.testing.assert_array_equal(np.asarray(gk)[untouched], 0.0)


def test_fused_embedding_lookup_padding_idx(rng_np):
    v, d = 12, 8
    table = jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
    ids = jnp.asarray([0, 3, 0, 5], jnp.int32)
    got = fused_embedding_lookup(table, ids, 0, "kernel", True)
    np.testing.assert_array_equal(got, _dense_oracle(table, ids, 0))

    g = jax.jit(jax.grad(lambda tbl: jnp.sum(
        fused_embedding_lookup(tbl, ids, 0, "kernel", True))))(table)
    # the padding row receives NO gradient
    np.testing.assert_array_equal(np.asarray(g)[0], 0.0)
    np.testing.assert_array_equal(np.asarray(g)[3], 1.0)


def test_fused_embedding_lookup_2d_ids_under_jit(rng_np):
    v, d = 16, 8
    table = jnp.asarray(rng_np.normal(size=(v, d)), jnp.float32)
    ids = jnp.asarray(rng_np.integers(0, v, size=(3, 5)), jnp.int32)

    @jax.jit
    def f(tbl):
        out = fused_embedding_lookup(tbl, ids, None, "kernel", True)
        return jnp.sum(out ** 2)

    got = jax.jit(jax.grad(f))(table)
    ref = jax.jit(jax.grad(lambda tbl: jnp.sum(
        _dense_oracle(tbl, ids) ** 2)))(table)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
