"""Fused CTC forward-backward + greedy decode kernels (ops/pallas/ctc.py)
vs the ``ops/ctc.py`` scan oracles — interpret mode, ragged lengths,
gradients, both input conventions (log-probs and in-kernel log-softmax)
— plus the NEG_INF-hardening regression tests for the scan itself
(degenerate inputs must yield the pinned sentinel loss and exactly-zero
gradients, not drifting junk)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ctc as ctc_ops
from paddle_tpu.ops.ctc import NEG_INF
from paddle_tpu.ops.pallas.ctc import (
    ctc_greedy_decode_fused,
    ctc_greedy_decode_fused_reference,
    ctc_loss_fused,
    ctc_loss_fused_reference,
)


@pytest.fixture
def ragged_ctc(rng_np):
    B, T, V, L = 4, 9, 7, 3
    logits = jnp.asarray(rng_np.normal(size=(B, T, V)).astype(np.float32))
    ilen = jnp.asarray([9, 7, 5, 3], jnp.int32)
    labels = jnp.asarray(rng_np.integers(1, V, size=(B, L)), jnp.int32)
    llen = jnp.asarray([3, 2, 1, 0], jnp.int32)  # incl. zero-length row
    return logits, ilen, labels, llen


@pytest.mark.parametrize("normalize", [False, True])
def test_ctc_loss_fused_matches_reference_fwd_and_grad(ragged_ctc,
                                                       normalize):
    logits, ilen, labels, llen = ragged_ctc
    inp = logits if normalize else jax.nn.log_softmax(logits)
    weights = jnp.asarray([1.0, 2.0, 3.0, 4.0])

    def k_loss(x):
        return jnp.sum(weights * ctc_loss_fused(
            x, ilen, labels, llen, 0, normalize, impl="kernel",
            interpret=True))

    def r_loss(x):
        return jnp.sum(weights * ctc_loss_fused_reference(
            x, ilen, labels, llen, 0, normalize))

    lk = ctc_loss_fused(inp, ilen, labels, llen, 0, normalize,
                        impl="kernel", interpret=True)
    lr = ctc_loss_fused_reference(inp, ilen, labels, llen, 0, normalize)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lr),
                               rtol=1e-5, atol=1e-5)
    gk = jax.jit(jax.grad(k_loss))(inp)
    gr = jax.jit(jax.grad(r_loss))(inp)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               rtol=1e-4, atol=1e-5)


def test_ctc_loss_fused_reference_is_the_scan(ragged_ctc):
    """The reference twin (the CPU production path under impl='auto')
    must be bit-identical to the unfused ops/ctc scan — the ablation's
    bit-identity anchor."""
    logits, ilen, labels, llen = ragged_ctc
    lp = jax.nn.log_softmax(logits)
    via_auto = ctc_loss_fused(lp, ilen, labels, llen, 0)  # CPU -> reference
    direct = ctc_ops.ctc_loss(lp, ilen, labels, llen, 0)
    assert np.array_equal(np.asarray(via_auto), np.asarray(direct))


def test_ctc_fused_kernel_infeasible_pins_loss_and_zeroes_grad(rng_np):
    """Truly infeasible alignment (3 repeated labels need >= 5 frames,
    only 4 given): the kernel's loss pins at the sentinel and its
    hand-derived gradient is exactly zero — matching the hardened scan."""
    V = 5
    labels = jnp.asarray([[2, 2, 2]], jnp.int32)
    llen = jnp.asarray([3], jnp.int32)
    lp = jax.nn.log_softmax(
        jnp.asarray(rng_np.normal(size=(1, 4, V)).astype(np.float32)))
    ilen = jnp.asarray([4], jnp.int32)

    lk = ctc_loss_fused(lp, ilen, labels, llen, 0, impl="kernel",
                        interpret=True)
    assert float(lk[0]) == float(np.float32(-NEG_INF))
    gk = jax.jit(jax.grad(lambda x: jnp.sum(ctc_loss_fused(
        x, ilen, labels, llen, 0, impl="kernel", interpret=True))))(lp)
    assert np.array_equal(np.asarray(gk), np.zeros_like(np.asarray(gk)))


def test_ctc_scan_degenerate_inputs_regression(rng_np):
    """ops/ctc.py hardening: (a) a zero-length label row's loss is the
    pure-blank path probability; (b) an infeasible row (T too short for
    the repeat structure) reports the finite sentinel with EXACTLY zero
    gradient (jnp.maximum ties used to leak junk cotangents); (c) all
    values and grads stay finite."""
    V = 5
    # (a) zero-length labels: loss = -sum of blank log-probs over length
    lp = jax.nn.log_softmax(
        jnp.asarray(rng_np.normal(size=(1, 6, V)).astype(np.float32)))
    ilen = jnp.asarray([4], jnp.int32)
    loss0 = ctc_ops.ctc_loss(lp, ilen, jnp.zeros((1, 3), jnp.int32),
                             jnp.asarray([0], jnp.int32), 0)
    want = -float(jnp.sum(lp[0, :4, 0]))
    assert abs(float(loss0[0]) - want) < 1e-5

    # (b) infeasible: 3 repeated labels in 4 frames
    labels = jnp.asarray([[2, 2, 2]], jnp.int32)
    llen = jnp.asarray([3], jnp.int32)
    lp4 = jax.nn.log_softmax(
        jnp.asarray(rng_np.normal(size=(1, 4, V)).astype(np.float32)))
    loss = ctc_ops.ctc_loss(lp4, ilen, labels, llen, 0)
    assert float(loss[0]) == float(np.float32(-NEG_INF))  # pinned, finite
    g = jax.jit(jax.grad(lambda x: jnp.sum(ctc_ops.ctc_loss(
        x, ilen, labels, llen, 0))))(lp4)
    assert np.array_equal(np.asarray(g), np.zeros_like(np.asarray(g)))

    # (c) T < 2L+1 but feasible (distinct labels skip blanks): finite
    # loss, finite grads, kernel agrees
    labels2 = jnp.asarray([[1, 2, 3]], jnp.int32)
    lp5 = jax.nn.log_softmax(
        jnp.asarray(rng_np.normal(size=(1, 4, V)).astype(np.float32)))
    l_scan = ctc_ops.ctc_loss(lp5, ilen, labels2, llen, 0)
    l_kern = ctc_loss_fused(lp5, ilen, labels2, llen, 0, impl="kernel",
                            interpret=True)
    assert np.isfinite(float(l_scan[0])) and float(l_scan[0]) < 1e29
    np.testing.assert_allclose(np.asarray(l_kern), np.asarray(l_scan),
                               rtol=1e-5, atol=1e-5)
    g2 = jax.jit(jax.grad(lambda x: jnp.sum(ctc_ops.ctc_loss(
        x, ilen, labels2, llen, 0))))(lp5)
    assert np.all(np.isfinite(np.asarray(g2)))


def test_ctc_greedy_decode_fused_matches_reference(rng_np):
    B, T, V = 5, 11, 6
    lp = jax.nn.log_softmax(
        jnp.asarray(rng_np.normal(size=(B, T, V)).astype(np.float32) * 2))
    ilen = jnp.asarray([11, 9, 6, 3, 1], jnp.int32)
    for blank in (0, V - 1):
        idk, lnk = ctc_greedy_decode_fused(lp, ilen, blank, impl="kernel",
                                           interpret=True)
        idr, lnr = ctc_greedy_decode_fused_reference(lp, ilen, blank)
        assert np.array_equal(np.asarray(idk), np.asarray(idr))
        assert np.array_equal(np.asarray(lnk), np.asarray(lnr))
    # and the reference twin IS the production scan decode
    ids_a, len_a = ctc_greedy_decode_fused(lp, ilen, 0)  # CPU -> reference
    ids_s, len_s = ctc_ops.ctc_greedy_decode(lp, ilen, 0)
    assert np.array_equal(np.asarray(ids_a), np.asarray(ids_s))
    assert np.array_equal(np.asarray(len_a), np.asarray(len_s))


def test_ctc_fused_batch_blocking_covers_non_multiple_batches(rng_np):
    """The kernel grids over batch blocks (largest divisor <= 8): odd
    batch sizes must still produce per-row losses equal to the scan."""
    for B in (1, 3, 6, 16):
        T, V, L = 7, 5, 2
        lp = jax.nn.log_softmax(jnp.asarray(
            rng_np.normal(size=(B, T, V)).astype(np.float32)))
        ilen = jnp.asarray(rng_np.integers(3, T + 1, size=(B,)), jnp.int32)
        labels = jnp.asarray(rng_np.integers(1, V, size=(B, L)), jnp.int32)
        llen = jnp.asarray(rng_np.integers(0, L + 1, size=(B,)), jnp.int32)
        # a batch size a program: each compiled once, not walked op by op
        lk = jax.jit(lambda *a: ctc_loss_fused(
            *a, 0, impl="kernel", interpret=True))(lp, ilen, labels, llen)
        lr = jax.jit(lambda *a: ctc_ops.ctc_loss(*a, 0))(
            lp, ilen, labels, llen)
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lr),
                                   rtol=1e-5, atol=1e-5)
