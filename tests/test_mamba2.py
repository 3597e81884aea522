"""The Mamba-2 operators (``ops/mamba2.py``): the chunked scan against
the one-token recurrence, what a padded row leaves behind, a scan that
continues from a state, the causal convolution's two arrangements.
``tests/test_hybrid_lm.py`` holds the whole mixer to its reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import mamba2

TOL = 2e-4


@functools.cache
def _prefill(chunk):
    """``ssd_prefill`` at one chunk length, compiled once a shape."""
    return jax.jit(functools.partial(mamba2.ssd_prefill, chunk=chunk))


_step = jax.jit(mamba2.ssd_step)
_conv_prefill = jax.jit(mamba2.conv_prefill)


def _ssd_inputs(t=21, b=3, h=4, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (b, t, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0),
        a=-jnp.exp(jax.random.normal(ks[2], (h,))),
        b=jax.random.normal(ks[3], (b, t, g, n)),
        c=jax.random.normal(ks[4], (b, t, g, n)),
        d=jax.random.normal(ks[5], (h,)))


def _recurrence(i):
    """Token by token through ``ssd_step``: (ys [B, T, H, P], state)."""
    bsz, t, h, p = i["x"].shape
    state = jnp.zeros((bsz, h, p, i["b"].shape[-1]))
    ys = []
    for s in range(t):
        y, state = _step(state, i["x"][:, s], i["dt"][:, s], i["a"],
                         i["b"][:, s], i["c"][:, s], i["d"])
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_scan_equals_the_recurrence(chunk):
    i = _ssd_inputs()
    want_y, want_s = _recurrence(i)
    y, s = _prefill(chunk)(i["x"], i["dt"], i["a"], i["b"], i["c"], i["d"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=TOL,
                               rtol=TOL)


def test_a_padded_rows_state_is_the_state_at_its_last_valid_token():
    """Rows of 21, 13 and 1 valid tokens in one padded pass: each row's
    SSM and conv state are those of a pass over its valid tokens alone —
    padding decays nothing, adds nothing, is no conv tap."""
    i = _ssd_inputs()
    lens = jnp.asarray([21, 13, 1])
    _, s = _prefill(8)(i["x"], i["dt"], i["a"], i["b"], i["c"], i["d"],
                       seq_lens=lens)
    xbc = jax.random.normal(jax.random.key(9), (3, 21, 10))
    w, bias = jax.random.normal(jax.random.key(8), (4, 10)), jnp.ones((10,))
    out, conv = _conv_prefill(xbc, w, bias, lens)
    for r, n in enumerate([21, 13, 1]):
        alone = {k: (v[r:r + 1, :n] if v.ndim > 1 else v)
                 for k, v in i.items()}
        _, want = _prefill(8)(
            alone["x"], alone["dt"], alone["a"], alone["b"], alone["c"],
            alone["d"])
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(want[0]),
                                   atol=TOL, rtol=TOL)
        out1, conv1 = _conv_prefill(xbc[r:r + 1, :n], w, bias)
        np.testing.assert_array_equal(np.asarray(conv[r]),
                                      np.asarray(conv1[0]))
        np.testing.assert_allclose(np.asarray(out[r, :n]),
                                   np.asarray(out1[0]), atol=1e-6)
    # the one-token arrangement continues where the prefill stopped
    state = jnp.zeros((1, 3, 10))
    conv_step = jax.jit(mamba2.conv_step)
    for t in range(5):
        o, state = conv_step(state, xbc[:1, t], w, bias)
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(out[0, t]),
                                   atol=1e-6)


def test_a_scan_continues_from_a_state():
    i = _ssd_inputs()
    names = "x dt a b c d".split()
    y, s = _prefill(8)(*[i[k] for k in names])
    cut = lambda v, sl: v[:, sl] if v.ndim > 1 else v
    head = {k: cut(v, slice(0, 9)) for k, v in i.items()}
    tail = {k: cut(v, slice(9, None)) for k, v in i.items()}
    _, mid = _prefill(8)(*[head[k] for k in names])
    y2, s2 = _prefill(8)(*[tail[k] for k in names], state=mid)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y[:, 9:]),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=TOL,
                               rtol=TOL)
