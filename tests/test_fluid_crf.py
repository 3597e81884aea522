"""Fluid CRF kernels (≅ linear_chain_crf_op.cc / crf_decoding_op.cc +
their python op tests): log-likelihood against a numpy forward, gradient
check through jax.grad, and decode/mismatch semantics."""

from __future__ import annotations

import numpy as np


def _np_crf_nll(emission, labels, w, lengths):
    """Numpy linear-chain CRF NLL per sequence — independent reference
    mirroring LinearChainCrfForward (test_linear_chain_crf_op.py)."""
    a, b, trans = w[0], w[1], w[2:]
    out = []
    for i in range(emission.shape[0]):
        t_len = int(lengths[i])
        x = emission[i, :t_len]
        y = labels[i, :t_len]
        # path score
        s = a[y[0]] + x[0, y[0]]
        for t in range(1, t_len):
            s += trans[y[t - 1], y[t]] + x[t, y[t]]
        s += b[y[-1]]
        # partition
        alpha = a + x[0]
        for t in range(1, t_len):
            alpha = x[t] + _logsumexp(alpha[:, None] + trans, axis=0)
        logz = _logsumexp(alpha + b, axis=0)
        out.append(logz - s)
    return np.asarray(out)


def _logsumexp(v, axis):
    m = np.max(v, axis=axis, keepdims=True)
    return np.squeeze(m, axis) + np.log(
        np.sum(np.exp(v - m), axis=axis))


def test_linear_chain_crf_matches_numpy(rng_np):
    import jax

    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.fluid.ops import get_kernel

    B, T, C = 3, 5, 7
    lengths = np.array([5, 3, 2], np.int32)
    emission = rng_np.uniform(-1, 1, size=(B, T, C)).astype(np.float32)
    labels = rng_np.integers(0, C, size=(B, T)).astype(np.int32)
    trans = rng_np.uniform(-0.5, 0.5, size=(C + 2, C)).astype(np.float32)

    kernel = get_kernel("linear_chain_crf")
    out = kernel(
        {"Emission": [SequenceBatch(data=emission, length=lengths)],
         "Transition": [trans],
         "Label": [SequenceBatch(data=labels, length=lengths)]},
        {}, jax.random.key(0))
    ll = np.asarray(out["LogLikelihood"][0])[:, 0]
    ref = -_np_crf_nll(emission, labels, trans, lengths)
    np.testing.assert_allclose(ll, ref, rtol=1e-4, atol=1e-4)


def test_linear_chain_crf_gradient(rng_np):
    """Finite-difference check of d(mean NLL)/d(transition) — the check the
    reference runs as check_grad on the fluid op."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.fluid.ops import get_kernel

    B, T, C = 2, 4, 5
    lengths = np.array([4, 2], np.int32)
    emission = rng_np.uniform(-1, 1, size=(B, T, C)).astype(np.float32)
    labels = rng_np.integers(0, C, size=(B, T)).astype(np.int32)
    trans = rng_np.uniform(-0.5, 0.5, size=(C + 2, C)).astype(np.float32)
    kernel = get_kernel("linear_chain_crf")

    def loss(tr, em):
        out = kernel(
            {"Emission": [SequenceBatch(data=em, length=lengths)],
             "Transition": [tr],
             "Label": [SequenceBatch(data=labels, length=lengths)]},
            {}, jax.random.key(0))
        return -jnp.mean(out["LogLikelihood"][0])

    gt, ge = jax.jit(jax.grad(loss, argnums=(0, 1)))(jnp.asarray(trans),
                                            jnp.asarray(emission))
    eps = 1e-3
    for arr, g, idx in [(trans, gt, (1, 2)), (trans, gt, (4, 0)),
                        (emission, ge, (0, 1, 3)), (emission, ge, (1, 1, 0))]:
        up = arr.copy(); up[idx] += eps
        dn = arr.copy(); dn[idx] -= eps
        if arr is trans:
            fd = (float(loss(jnp.asarray(up), jnp.asarray(emission)))
                  - float(loss(jnp.asarray(dn), jnp.asarray(emission)))) / (2 * eps)
        else:
            fd = (float(loss(jnp.asarray(trans), jnp.asarray(up)))
                  - float(loss(jnp.asarray(trans), jnp.asarray(dn)))) / (2 * eps)
        an = float(np.asarray(g)[idx])
        assert abs(fd - an) < 5e-3, (idx, fd, an)
    # padded emission steps must carry no gradient
    assert np.all(np.asarray(ge)[1, 2:] == 0)


def test_crf_decoding_modes(rng_np):
    import jax

    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.fluid.ops import get_kernel

    B, T, C = 2, 4, 4
    lengths = np.array([4, 3], np.int32)
    emission = rng_np.uniform(-1, 1, size=(B, T, C)).astype(np.float32)
    trans = rng_np.uniform(-0.5, 0.5, size=(C + 2, C)).astype(np.float32)
    kernel = get_kernel("crf_decoding")
    seq = SequenceBatch(data=emission, length=lengths)

    path = kernel({"Emission": [seq], "Transition": [trans]},
                  {}, jax.random.key(0))["ViterbiPath"][0]
    assert path.data.shape == (B, T)
    assert np.asarray(path.data).dtype == np.int32

    # error-indicator mode: the decoded path vs itself mismatches nowhere
    err = kernel({"Emission": [seq], "Transition": [trans],
                  "Label": [path]}, {}, jax.random.key(0))["ViterbiPath"][0]
    assert np.all(np.asarray(err.data) == 0)
