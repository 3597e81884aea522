"""Fused Pallas LSTM/GRU sequence kernels vs the lax.scan cells.

The kernels (ops/pallas/{lstm,gru}.py) are the hand-kernel-class analog of
the reference's ``hl_lstm_parallel_forward`` (hl_cuda_lstm.cu:334) and
``KeGruForwardUnit`` (hl_gpu_gru.cuh:28).  On CPU they run in interpret
mode; these tests pin forward and gradient equality against the scan
implementations for ragged batches, peepholes, and both directions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.lod import SequenceBatch
from paddle_tpu.ops import rnn


def _grad(loss, argnums, static=()):
    """``jax.grad`` compiled (``static``: the positions of Python flags):
    the eager tape dispatches every step's ops one by one."""
    return jax.jit(jax.grad(loss, argnums=argnums), static_argnums=static)


@pytest.fixture
def ragged(rng_np):
    B, T, D = 4, 7, 8
    lens = np.asarray([7, 5, 3, 1], np.int32)
    return B, T, D, jnp.asarray(lens)


def test_lstm_fused_matches_scan_with_peephole(rng_np, ragged):
    B, T, D, lens = ragged
    xw = jnp.asarray(rng_np.normal(size=(B, T, 4 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    peep = jnp.asarray(rng_np.normal(size=(3 * D,)).astype(np.float32) * .2)
    sb = SequenceBatch(data=xw, length=lens)
    init = rnn.LSTMState(h=jnp.zeros((B, D)), c=jnp.zeros((B, D)))

    def scan_loss(wh, peep, reverse):
        def step(state, xt):
            return rnn.lstm_cell(xt, state, wh, peephole=peep)
        last, ys = rnn._masked_scan(step, sb, init, reverse=reverse)
        return (jnp.sum(ys.h * sb.mask()[:, :, None]) + jnp.sum(last.h)
                + 0.5 * jnp.sum(last.c))

    def fused_loss(wh, peep, reverse):
        ys, last = rnn.lstm_fused(sb, wh, init, peephole=peep,
                                  reverse=reverse)
        return (jnp.sum(ys.data * sb.mask()[:, :, None]) + jnp.sum(last.h)
                + 0.5 * jnp.sum(last.c))

    for reverse in (False, True):
        r = scan_loss(wh, peep, reverse)
        k = fused_loss(wh, peep, reverse)
        assert abs(float(r - k)) < 1e-5, (reverse, float(r), float(k))
        gr = _grad(scan_loss, (0, 1), (2,))(wh, peep, reverse)
        gk = _grad(fused_loss, (0, 1), (2,))(wh, peep, reverse)
        for a, b in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(b).reshape(a.shape),
                                       rtol=2e-5, atol=2e-5)


def test_lstm_fused_dxw_and_state_grads(rng_np, ragged):
    B, T, D, lens = ragged
    xw = jnp.asarray(rng_np.normal(size=(B, T, 4 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    init = rnn.LSTMState(h=jnp.asarray(
        rng_np.normal(size=(B, D)).astype(np.float32) * .2),
        c=jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2))
    mask = (np.arange(T)[None] < np.asarray(lens)[:, None])

    def scan_loss(xw_, h0, c0):
        sb = SequenceBatch(data=xw_, length=lens)

        def step(state, xt):
            return rnn.lstm_cell(xt, state, wh)
        last, ys = rnn._masked_scan(
            step, sb, rnn.LSTMState(h=h0, c=c0))
        return jnp.sum(ys.h * jnp.asarray(mask)[:, :, None]) + jnp.sum(last.c)

    def fused_loss(xw_, h0, c0):
        sb = SequenceBatch(data=xw_, length=lens)
        ys, last = rnn.lstm_fused(sb, wh, rnn.LSTMState(h=h0, c=c0))
        return jnp.sum(ys.data * jnp.asarray(mask)[:, :, None]) + jnp.sum(last.c)

    gr = _grad(scan_loss, (0, 1, 2))(xw, init.h, init.c)
    gk = _grad(fused_loss, (0, 1, 2))(xw, init.h, init.c)
    for a, b in zip(gr, gk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_gru_fused_matches_scan(rng_np, ragged):
    B, T, D, lens = ragged
    xw = jnp.asarray(rng_np.normal(size=(B, T, 3 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 2 * D)).astype(np.float32) * .3)
    whc = jnp.asarray(rng_np.normal(size=(D, D)).astype(np.float32) * .3)
    sb = SequenceBatch(data=xw, length=lens)
    init = jnp.zeros((B, D))

    def scan_loss(wh, whc, xw_, reverse):
        sbx = SequenceBatch(data=xw_, length=lens)

        def step(h, xt):
            return rnn.gru_cell(xt, h, wh, whc)
        last, ys = rnn._masked_scan(step, sbx, init, reverse=reverse)
        return jnp.sum(ys * sbx.mask()[:, :, None]) + jnp.sum(last)

    def fused_loss(wh, whc, xw_, reverse):
        sbx = SequenceBatch(data=xw_, length=lens)
        ys, last = rnn.gru_fused(sbx, wh, whc, init, reverse=reverse)
        return jnp.sum(ys.data * sbx.mask()[:, :, None]) + jnp.sum(last)

    for reverse in (False, True):
        r = scan_loss(wh, whc, xw, reverse)
        k = fused_loss(wh, whc, xw, reverse)
        assert abs(float(r - k)) < 1e-5
        gr = _grad(scan_loss, (0, 1, 2), (3,))(wh, whc, xw, reverse)
        gk = _grad(fused_loss, (0, 1, 2), (3,))(wh, whc, xw, reverse)
        for a, b in zip(gr, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_cast_for_matmul_mixed_pair_stays_narrow():
    """Under the f32 default, a mixed f32/bf16 operand pair (only possible
    under an explicit mixed-precision policy) must resolve to bf16 —
    promoting to f32+HIGHEST silently doubled the NMT step (measured
    11.8 -> 23.4 ms on a v5e)."""
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.core import flags

    assert flags.get("bf16") is False  # the default under test
    a = jnp.ones((4, 4), jnp.float32)
    b = jnp.ones((4, 4), jnp.bfloat16)
    ca, cb = dt.cast_for_matmul(a, b)
    assert ca.dtype == jnp.bfloat16 and cb.dtype == jnp.bfloat16
    # pure f32 stays f32 (reference numerics)
    ca, cb = dt.cast_for_matmul(a, jnp.ones((4, 4), jnp.float32))
    assert ca.dtype == jnp.float32 and cb.dtype == jnp.float32
    # and f32 pairs request true-f32 MXU passes
    assert dt.dot_precision(ca, cb) == jax.lax.Precision.HIGHEST
    assert dt.dot_precision(a, b) is None


def test_fused_falls_back_over_vmem_budget(monkeypatch):
    """Oversized weights (or f16) must take the lax.scan path instead of
    failing Mosaic compilation — and produce identical results."""
    import paddle_tpu.ops.rnn as rnn_mod

    B, T, D = 2, 5, 8
    g = np.random.default_rng(1)
    xw = jnp.asarray(g.normal(size=(B, T, 4 * D)).astype(np.float32) * .3)
    wh = jnp.asarray(g.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    sb = SequenceBatch(data=xw, length=jnp.asarray([5, 3], np.int32))
    init = rnn_mod.LSTMState(h=jnp.zeros((B, D)), c=jnp.zeros((B, D)))
    want, _ = rnn_mod.lstm_fused(sb, wh, init)

    calls = {"kernel": 0}
    from paddle_tpu.ops.pallas import lstm as klstm
    orig = klstm.lstm_seq
    def counting(*a, **k):
        calls["kernel"] += 1
        return orig(*a, **k)
    monkeypatch.setattr(klstm, "lstm_seq", counting)
    monkeypatch.setattr(rnn_mod, "_fused_fits", lambda *a: False)
    got, _ = rnn_mod.lstm_fused(sb, wh, init)
    assert calls["kernel"] == 0, "fallback still invoked the kernel"
    np.testing.assert_allclose(np.asarray(want.data), np.asarray(got.data),
                               rtol=2e-5, atol=2e-5)
    # f16 weights are rejected by the budget check itself
    assert not rnn_mod._fused_fits(2, 8, 4, wh.astype(jnp.float16))


def test_gru_group_fused_fast_path_matches_cell_scan(rng_np):
    """simple_gru/gru_group lowers to the fused GRU kernel (the group
    node's fn is the fused closure) and matches a hand scan of gru_cell
    over the same parameters."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type, networks

    base.reset_name_counters()
    x = layer.data(name="x", type=data_type.dense_vector_sequence(8))
    g = networks.simple_gru(input=x, size=16, name="sg")
    topo = Topology(g)
    grp = [n for n in topo.nodes
           if n.layer_type == "recurrent_layer_group"][0]
    assert grp.fn.__name__ == "fused_fwd"

    params = paddle.parameters.create(topo)
    feed = {"x": SequenceBatch(
        data=rng_np.normal(size=(3, 6, 8)).astype(np.float32),
        length=np.asarray([6, 4, 1], np.int32))}
    vals, _ = topo.forward(params.as_dict(), {}, feed, False,
                           jax.random.key(0))
    got = vals[g.name]

    # hand scan: xw = the transform mixed layer's output; w from the group
    xw = vals["sg_transform"]
    wname = grp.param_specs[0].name
    w = params[wname]
    bias = [s.name for s in grp.param_specs if "bias" in s.name]

    def step(h, xt):
        xt = xt + (params[bias[0]] if bias else 0.0)
        return rnn.gru_cell(xt, h, jnp.asarray(w[:, :32]),
                            jnp.asarray(w[:, 32:]))

    last, ys = rnn._masked_scan(step, xw, jnp.zeros((3, 16)))
    np.testing.assert_allclose(np.asarray(got.data), np.asarray(ys),
                               rtol=2e-5, atol=2e-5)


# -- fast kernel-vs-in-module-reference parity (the check_kernel_parity
# contract: small shapes, interpret mode, forward + vjp — kernel coverage
# no longer rides the slow CRNN convergence test) ----------------------------


def test_lstm_seq_matches_reference_fwd_and_vjp(rng_np):
    from paddle_tpu.ops.pallas.lstm import lstm_seq, lstm_seq_reference

    B, T, D = 2, 4, 8
    xw = jnp.asarray(rng_np.normal(size=(B, T, 4 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    peep = jnp.asarray(rng_np.normal(size=(3, D)).astype(np.float32) * .2)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([4, 2])[:, None]).astype(np.float32))
    h0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)
    c0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)

    for reverse in (False, True):
        def k_loss(xw, wh, peep, h0, c0):
            hs, (hT, cT) = lstm_seq(xw, mask, wh, peep, h0, c0, reverse,
                                    True)
            return (jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)
                    + 0.5 * jnp.sum(cT))

        def r_loss(xw, wh, peep, h0, c0):
            hs, (hT, cT) = lstm_seq_reference(xw, mask, wh, peep, h0, c0,
                                              reverse)
            return (jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)
                    + 0.5 * jnp.sum(cT))

        hs_k, (hT_k, cT_k) = lstm_seq(xw, mask, wh, peep, h0, c0, reverse,
                                      True)
        hs_r, (hT_r, cT_r) = lstm_seq_reference(xw, mask, wh, peep, h0, c0,
                                                reverse)
        np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_r),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_r),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(cT_k), np.asarray(cT_r),
                                   rtol=2e-5, atol=2e-5)
        gk = _grad(k_loss, (0, 1, 2, 3, 4))(xw, wh, peep, h0, c0)
        gr = _grad(r_loss, (0, 1, 2, 3, 4))(xw, wh, peep, h0, c0)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_lstm_seq_fi_matches_reference_fwd_and_vjp(rng_np):
    """Fused-input kernel (x @ W_x inside the time loop) vs the hoisted-
    projection oracle, both remat modes, both directions."""
    from paddle_tpu.ops.pallas.lstm import lstm_seq_fi, lstm_seq_fi_reference

    B, T, E, D = 2, 5, 6, 8
    x = jnp.asarray(rng_np.normal(size=(B, T, E)).astype(np.float32) * .4)
    wx = jnp.asarray(rng_np.normal(size=(E, 4 * D)).astype(np.float32) * .3)
    b = jnp.asarray(rng_np.normal(size=(4 * D,)).astype(np.float32) * .1)
    wh = jnp.asarray(rng_np.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    peep = jnp.asarray(rng_np.normal(size=(3, D)).astype(np.float32) * .2)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([5, 3])[:, None]).astype(np.float32))
    h0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)
    c0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)

    for reverse in (False, True):
        for remat in (False, True):
            def k_loss(x, wx, b, wh, peep, h0, c0):
                hs, (hT, cT) = lstm_seq_fi(x, mask, wx, b, wh, peep, h0,
                                           c0, reverse, True, remat)
                return (jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)
                        + 0.5 * jnp.sum(cT))

            def r_loss(x, wx, b, wh, peep, h0, c0):
                hs, (hT, cT) = lstm_seq_fi_reference(x, mask, wx, b, wh,
                                                     peep, h0, c0, reverse)
                return (jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)
                        + 0.5 * jnp.sum(cT))

            args = (x, wx, b, wh, peep, h0, c0)
            assert abs(float(k_loss(*args) - r_loss(*args))) < 1e-4
            gk = _grad(k_loss, tuple(range(7)))(*args)
            gr = _grad(r_loss, tuple(range(7)))(*args)
            for a, bb in zip(gk, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                           rtol=3e-5, atol=3e-5)


def test_lstm_seq_remat_bit_identical_to_stored_gates(rng_np):
    """remat is a pure memory knob: the recomputed-gates backward must
    reproduce the stored-residual gradients BIT-identically (the
    recomputation round-trips through the io dtype)."""
    from paddle_tpu.ops.pallas.lstm import lstm_seq

    B, T, D = 2, 5, 8
    xw = jnp.asarray(rng_np.normal(size=(B, T, 4 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    peep = jnp.asarray(rng_np.normal(size=(3, D)).astype(np.float32) * .2)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([5, 3])[:, None]).astype(np.float32))
    h0 = jnp.zeros((B, D))
    c0 = jnp.zeros((B, D))

    for reverse in (False, True):
        def grads(remat):
            def loss(xw, wh, peep):
                hs, (hT, cT) = lstm_seq(xw, mask, wh, peep, h0, c0,
                                        reverse, True, remat)
                return jnp.sum(hs * mask[:, :, None]) + jnp.sum(cT)
            return jax.grad(loss, argnums=(0, 1, 2))(xw, wh, peep)

        for a, bb in zip(grads(False), grads(True)):
            assert np.array_equal(np.asarray(a), np.asarray(bb))


def test_bilstm_seq_matches_reference_fwd_and_vjp(rng_np):
    """One-residency bidirectional kernel vs the composed fused-input
    references (fwd + rev), forward and gradients, both remat modes."""
    from paddle_tpu.ops.pallas.lstm import bilstm_seq, bilstm_seq_reference

    B, T, E, D = 2, 5, 6, 8
    x = jnp.asarray(rng_np.normal(size=(B, T, E)).astype(np.float32) * .4)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([5, 3])[:, None]).astype(np.float32))

    def w(scale, *shape):
        return jnp.asarray(rng_np.normal(size=shape).astype(np.float32)
                           * scale)

    wxf, wxb = w(.3, E, 4 * D), w(.3, E, 4 * D)
    bf, bb_ = w(.1, 4 * D), w(.1, 4 * D)
    whf, whb = w(.3, D, 4 * D), w(.3, D, 4 * D)
    pf, pb = w(.2, 3, D), jnp.zeros((3, D), jnp.float32)
    h0 = w(.2, B, D)
    c0 = w(.2, B, D)

    for remat in (False, True):
        def k_loss(x, wxf, whf, wxb, whb):
            hf, hb, (hTf, cTf), (hTb, cTb) = bilstm_seq(
                x, mask, wxf, bf, whf, pf, wxb, bb_, whb, pb,
                h0, c0, h0, c0, True, remat)
            return (jnp.sum((hf + 2 * hb) * mask[:, :, None])
                    + jnp.sum(hTf) + jnp.sum(cTb))

        def r_loss(x, wxf, whf, wxb, whb):
            hf, hb, (hTf, cTf), (hTb, cTb) = bilstm_seq_reference(
                x, mask, wxf, bf, whf, pf, wxb, bb_, whb, pb,
                h0, c0, h0, c0)
            return (jnp.sum((hf + 2 * hb) * mask[:, :, None])
                    + jnp.sum(hTf) + jnp.sum(cTb))

        args = (x, wxf, whf, wxb, whb)
        assert abs(float(k_loss(*args) - r_loss(*args))) < 1e-4
        gk = _grad(k_loss, tuple(range(5)))(*args)
        gr = _grad(r_loss, tuple(range(5)))(*args)
        for a, bb in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=3e-5, atol=3e-5)


def test_gru_seq_fi_matches_reference_fwd_and_vjp(rng_np):
    from paddle_tpu.ops.pallas.gru import gru_seq_fi, gru_seq_fi_reference

    B, T, E, D = 2, 5, 6, 8
    x = jnp.asarray(rng_np.normal(size=(B, T, E)).astype(np.float32) * .4)
    wx = jnp.asarray(rng_np.normal(size=(E, 3 * D)).astype(np.float32) * .3)
    b = jnp.asarray(rng_np.normal(size=(3 * D,)).astype(np.float32) * .1)
    wh = jnp.asarray(rng_np.normal(size=(D, 2 * D)).astype(np.float32) * .3)
    whc = jnp.asarray(rng_np.normal(size=(D, D)).astype(np.float32) * .3)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([3, 5])[:, None]).astype(np.float32))
    h0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)

    for reverse in (False, True):
        for remat in (False, True):
            def k_loss(x, wx, b, wh, whc, h0):
                hs, hT = gru_seq_fi(x, mask, wx, b, wh, whc, h0,
                                    reverse, True, remat)
                return jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)

            def r_loss(x, wx, b, wh, whc, h0):
                hs, hT = gru_seq_fi_reference(x, mask, wx, b, wh, whc,
                                              h0, reverse)
                return jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)

            args = (x, wx, b, wh, whc, h0)
            assert abs(float(k_loss(*args) - r_loss(*args))) < 1e-4
            gk = _grad(k_loss, tuple(range(6)))(*args)
            gr = _grad(r_loss, tuple(range(6)))(*args)
            for a, bb in zip(gk, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                           rtol=3e-5, atol=3e-5)


def test_gru_seq_remat_bit_identical_to_stored_gates(rng_np):
    """Given the SAME forward ``hs`` the remat backward (u/r/c recomputed
    per step) equals the stored-gates one bit for bit: ``array_equal``.
    Through ``jax.grad`` bits cannot be promised off the chip: the two
    modes run two forward programs (``emit_gates`` on / off), XLA:CPU
    fuses the interpreted body another way when the gates are an output
    too, and ``hs`` itself differs in its last bit (3e-8 on 20-24 of 80
    elements).  Measured gap: 1.5 eps x the gradient's largest element
    at most; asserted at 4."""
    from paddle_tpu.ops.pallas import gru

    B, T, D = 2, 5, 8
    xw = jnp.asarray(rng_np.normal(size=(B, T, 3 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 2 * D)).astype(np.float32) * .3)
    whc = jnp.asarray(rng_np.normal(size=(D, D)).astype(np.float32) * .3)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([5, 3])[:, None]).astype(np.float32))
    h0 = jnp.zeros((B, D))
    xw_t = jnp.swapaxes(xw, 0, 1)
    d_hs = jnp.swapaxes(jnp.broadcast_to(mask[:, :, None], (B, T, D)), 0, 1)
    d_hT = jnp.ones((B, D), jnp.float32)
    eps = float(np.finfo(np.float32).eps)

    for reverse in (False, True):
        hs, urc, _ = gru._fwd_call(xw_t, gru._mask3(mask), wh, whc, h0,
                                   reverse=reverse, interpret=True,
                                   emit_gates=True)
        stored, remat = (
            gru._gru_dxw_bwd(xw_t, mask, wh, whc, h0, hs, gates, d_hs,
                             d_hT, reverse, True, gates is None)
            for gates in (urc, None))
        for a, bb in zip(stored, remat):
            assert np.array_equal(np.asarray(a), np.asarray(bb))

        def grads(remat):
            def loss(xw, wh, whc):
                hs, hT = gru.gru_seq(xw, mask, wh, whc, h0, reverse, True,
                                     remat)
                return jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)
            return jax.grad(loss, argnums=(0, 1, 2))(xw, wh, whc)

        for a, bb in zip(grads(False), grads(True)):
            a, bb = np.asarray(a), np.asarray(bb)
            assert np.abs(a - bb).max() <= 4 * eps * np.abs(a).max()


def test_bilstm_layer_node_matches_composed_pair(rng_np):
    """layer.bilstm (ops/rnn.bilstm_fused unfused composition on CPU)
    must equal the explicit fc+lstmemory+concat build over the SAME
    parameter values — the checkpoint/ablation contract of the node."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import activation as act_mod
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    B, T, E, D = 3, 6, 8, 4
    base.reset_name_counters()
    x = layer.data(name="x", type=data_type.dense_vector_sequence(E))
    node = layer.bilstm(input=x, size=D, name="bi")
    topo = Topology(node)
    params = paddle.parameters.create(topo)
    feed = {"x": SequenceBatch(
        data=rng_np.normal(size=(B, T, E)).astype(np.float32),
        length=np.asarray([6, 4, 1], np.int32))}
    vals, _ = topo.forward(params.as_dict(), {}, feed, False,
                           jax.random.key(0))
    got = vals[node.name]
    assert got.data.shape == (B, T, 2 * D)

    # composed build with the node's weights copied in by name
    base.reset_name_counters()
    x2 = layer.data(name="x", type=data_type.dense_vector_sequence(E))
    fw = layer.lstmemory(input=layer.fc(
        input=x2, size=4 * D, act=act_mod.LinearActivation(),
        name="bi_fw_transform"), name="bi_fw")
    bw = layer.lstmemory(input=layer.fc(
        input=x2, size=4 * D, act=act_mod.LinearActivation(),
        name="bi_bw_transform"), name="bi_bw", reverse=True)
    cat = layer.concat(input=[fw, bw])
    topo2 = Topology(cat)
    params2 = paddle.parameters.create(topo2)
    for n in params2.names():
        params2[n] = np.asarray(params[n])
    vals2, _ = topo2.forward(params2.as_dict(), {}, feed, False,
                             jax.random.key(0))
    np.testing.assert_allclose(np.asarray(got.data),
                               np.asarray(vals2[cat.name].data),
                               rtol=2e-5, atol=2e-5)


def test_gru_seq_matches_reference_fwd_and_vjp(rng_np):
    from paddle_tpu.ops.pallas.gru import gru_seq, gru_seq_reference

    B, T, D = 2, 4, 8
    xw = jnp.asarray(rng_np.normal(size=(B, T, 3 * D)).astype(np.float32) * .4)
    wh = jnp.asarray(rng_np.normal(size=(D, 2 * D)).astype(np.float32) * .3)
    whc = jnp.asarray(rng_np.normal(size=(D, D)).astype(np.float32) * .3)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([3, 4])[:, None]).astype(np.float32))
    h0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)

    for reverse in (False, True):
        hs_k, hT_k = gru_seq(xw, mask, wh, whc, h0, reverse, True)
        hs_r, hT_r = gru_seq_reference(xw, mask, wh, whc, h0, reverse)
        np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_r),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(hT_k), np.asarray(hT_r),
                                   rtol=2e-5, atol=2e-5)

        def k_loss(xw, wh, whc, h0):
            hs, hT = gru_seq(xw, mask, wh, whc, h0, reverse, True)
            return jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)

        def r_loss(xw, wh, whc, h0):
            hs, hT = gru_seq_reference(xw, mask, wh, whc, h0, reverse)
            return jnp.sum(hs * mask[:, :, None]) + jnp.sum(hT)

        gk = _grad(k_loss, (0, 1, 2, 3))(xw, wh, whc, h0)
        gr = _grad(r_loss, (0, 1, 2, 3))(xw, wh, whc, h0)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_bigru_seq_matches_reference_fwd_and_vjp(rng_np):
    """One-residency bidirectional GRU kernel vs the composed
    fused-input references (fwd + rev), forward and gradients, both
    remat modes."""
    from paddle_tpu.ops.pallas.gru import bigru_seq, bigru_seq_reference

    B, T, E, D = 2, 5, 6, 8
    x = jnp.asarray(rng_np.normal(size=(B, T, E)).astype(np.float32) * .4)
    mask = jnp.asarray((np.arange(T)[None] <
                        np.asarray([5, 3])[:, None]).astype(np.float32))

    def w(scale, *shape):
        return jnp.asarray(rng_np.normal(size=shape).astype(np.float32)
                           * scale)

    wxf, wxb = w(.3, E, 3 * D), w(.3, E, 3 * D)
    bf, bb_ = w(.1, 3 * D), w(.1, 3 * D)
    whf, whb = w(.3, D, 2 * D), w(.3, D, 2 * D)
    whcf, whcb = w(.3, D, D), w(.3, D, D)
    h0f, h0b = w(.2, B, D), w(.2, B, D)

    for remat in (False, True):
        def k_loss(x, wxf, whf, whcf, wxb, whb, whcb):
            hf, hb, hTf, hTb = bigru_seq(
                x, mask, wxf, bf, whf, whcf, wxb, bb_, whb, whcb,
                h0f, h0b, True, remat)
            return (jnp.sum((hf + 2 * hb) * mask[:, :, None])
                    + jnp.sum(hTf) + jnp.sum(hTb))

        def r_loss(x, wxf, whf, whcf, wxb, whb, whcb):
            hf, hb, hTf, hTb = bigru_seq_reference(
                x, mask, wxf, bf, whf, whcf, wxb, bb_, whb, whcb,
                h0f, h0b)
            return (jnp.sum((hf + 2 * hb) * mask[:, :, None])
                    + jnp.sum(hTf) + jnp.sum(hTb))

        args = (x, wxf, whf, whcf, wxb, whb, whcb)
        assert abs(float(k_loss(*args) - r_loss(*args))) < 1e-4
        gk = _grad(k_loss, tuple(range(7)))(*args)
        gr = _grad(r_loss, tuple(range(7)))(*args)
        for a, bb in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=3e-5, atol=3e-5)


def test_bigru_layer_node_matches_composed_pair(rng_np):
    """layer.bigru (ops/rnn.bigru_fused unfused composition on CPU)
    must equal the explicit fc+grumemory+concat build over the SAME
    parameter values — the checkpoint/ablation contract of the node."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import activation as act_mod
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type

    B, T, E, D = 3, 6, 8, 4
    base.reset_name_counters()
    x = layer.data(name="x", type=data_type.dense_vector_sequence(E))
    node = layer.bigru(input=x, size=D, name="bi")
    topo = Topology(node)
    params = paddle.parameters.create(topo)
    feed = {"x": SequenceBatch(
        data=rng_np.normal(size=(B, T, E)).astype(np.float32),
        length=np.asarray([6, 4, 1], np.int32))}
    vals, _ = topo.forward(params.as_dict(), {}, feed, False,
                           jax.random.key(0))
    got = vals[node.name]
    assert got.data.shape == (B, T, 2 * D)

    # composed build with the node's weights copied in by name
    base.reset_name_counters()
    x2 = layer.data(name="x", type=data_type.dense_vector_sequence(E))
    fw = layer.grumemory(input=layer.fc(
        input=x2, size=3 * D, act=act_mod.LinearActivation(),
        name="bi_fw_transform"), name="bi_fw")
    bw = layer.grumemory(input=layer.fc(
        input=x2, size=3 * D, act=act_mod.LinearActivation(),
        name="bi_bw_transform"), name="bi_bw", reverse=True)
    cat = layer.concat(input=[fw, bw])
    topo2 = Topology(cat)
    params2 = paddle.parameters.create(topo2)
    for n in params2.names():
        params2[n] = np.asarray(params[n])
    vals2, _ = topo2.forward(params2.as_dict(), {}, feed, False,
                             jax.random.key(0))
    np.testing.assert_allclose(np.asarray(got.data),
                               np.asarray(vals2[cat.name].data),
                               rtol=2e-5, atol=2e-5)


def test_lstm_seq_batch_blocked_matches_reference(rng_np):
    """B past _BATCH_BLOCK splits the grid into batch blocks (padded to a
    block multiple); fwd and vjp must match the scan oracle exactly as in
    the single-block regime — including the cross-block dpeep
    accumulator and the remat variant."""
    from paddle_tpu.ops.pallas import lstm as klstm
    from paddle_tpu.ops.pallas.lstm import lstm_seq, lstm_seq_reference

    B, T, D = klstm._BATCH_BLOCK + 44, 4, 8  # 2 blocks, ragged pad
    xw = jnp.asarray(rng_np.normal(size=(B, T, 4 * D)).astype(np.float32) * .4)
    mask = jnp.asarray(
        (rng_np.uniform(size=(B, T)) < 0.8).astype(np.float32)
    ).at[:, 0].set(1.0)
    wh = jnp.asarray(rng_np.normal(size=(D, 4 * D)).astype(np.float32) * .3)
    peep = jnp.asarray(rng_np.normal(size=(3, D)).astype(np.float32) * .2)
    h0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)
    c0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)

    def loss_k(xw, wh, peep, h0, c0, reverse, remat):
        hs, (hT, cT) = lstm_seq(xw, mask, wh, peep, h0, c0, reverse,
                                True, remat)
        return jnp.sum(hs) + jnp.sum(hT) + 0.5 * jnp.sum(cT)

    def loss_r(xw, wh, peep, h0, c0, reverse):
        hs, (hT, cT) = lstm_seq_reference(xw, mask, wh, peep, h0, c0,
                                          reverse)
        return jnp.sum(hs) + jnp.sum(hT) + 0.5 * jnp.sum(cT)

    # (fwd, stored-gates) and (reverse, remat) cover both grid directions
    # and both backward variants without the full 4-combo sweep
    for reverse, remat in ((False, False), (True, True)):
        hs_k, (hT_k, cT_k) = lstm_seq(xw, mask, wh, peep, h0, c0,
                                      reverse, True, remat)
        assert hs_k.shape == (B, T, D) and hT_k.shape == (B, D)
        hs_r, (hT_r, cT_r) = lstm_seq_reference(
            xw, mask, wh, peep, h0, c0, reverse)
        np.testing.assert_allclose(hs_k, hs_r, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(cT_k, cT_r, rtol=2e-5, atol=2e-5)
        gk = _grad(loss_k, (0, 1, 2, 3, 4), (5, 6))(
            xw, wh, peep, h0, c0, reverse, remat)
        gr = _grad(loss_r, (0, 1, 2, 3, 4), (5,))(
            xw, wh, peep, h0, c0, reverse)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_gru_seq_batch_blocked_matches_reference(rng_np):
    """GRU sibling of the blocked-batch LSTM test (no cross-block
    accumulator, but the same pad-rows-are-inert contract)."""
    from paddle_tpu.ops.pallas import lstm as klstm
    from paddle_tpu.ops.pallas.gru import gru_seq, gru_seq_reference

    B, T, D = klstm._BATCH_BLOCK + 44, 4, 8
    xw = jnp.asarray(rng_np.normal(size=(B, T, 3 * D)).astype(np.float32) * .4)
    mask = jnp.asarray(
        (rng_np.uniform(size=(B, T)) < 0.8).astype(np.float32)
    ).at[:, 0].set(1.0)
    wh = jnp.asarray(rng_np.normal(size=(D, 2 * D)).astype(np.float32) * .3)
    whc = jnp.asarray(rng_np.normal(size=(D, D)).astype(np.float32) * .3)
    h0 = jnp.asarray(rng_np.normal(size=(B, D)).astype(np.float32) * .2)

    def loss_k(xw, wh, whc, h0, reverse, remat):
        hs, hT = gru_seq(xw, mask, wh, whc, h0, reverse, True, remat)
        return jnp.sum(hs) + jnp.sum(hT)

    def loss_r(xw, wh, whc, h0, reverse):
        hs, hT = gru_seq_reference(xw, mask, wh, whc, h0, reverse)
        return jnp.sum(hs) + jnp.sum(hT)

    for reverse, remat in ((False, False), (True, True)):
        hs_k, hT_k = gru_seq(xw, mask, wh, whc, h0, reverse, True, remat)
        assert hs_k.shape == (B, T, D) and hT_k.shape == (B, D)
        hs_r, hT_r = gru_seq_reference(xw, mask, wh, whc, h0, reverse)
        np.testing.assert_allclose(hs_k, hs_r, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(hT_k, hT_r, rtol=2e-5, atol=2e-5)
        gk = _grad(loss_k, (0, 1, 2, 3), (4, 5))(
            xw, wh, whc, h0, reverse, remat)
        gr = _grad(loss_r, (0, 1, 2, 3), (4,))(
            xw, wh, whc, h0, reverse)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
