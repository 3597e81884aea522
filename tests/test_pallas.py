"""Pallas flash-attention kernel vs the exact einsum path.

The reference's kernel-test pattern is compare-two-implementations
(``paddle/function/FunctionTest.h`` Compare2Function, CPU vs GPU); here the
two implementations are the Pallas kernel (interpret mode on CPU) and the
XLA einsum attention, for both forward values and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention as A
from paddle_tpu.ops.pallas import flash_attention


def _qkv(rng_np, b=2, t=100, h=2, d=32):
    mk = lambda: jnp.asarray(rng_np.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


# block sizes 32 so T=100/70 exercise the multi-block online-softmax
# recurrence (accumulator init/correction/finalize across grid steps)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_exact(rng_np, causal):
    q, k, v = _qkv(rng_np)
    mask = A.causal_mask(q.shape[1], k.shape[1]) if causal else None
    ref = A.dot_product_attention(q, k, v, mask=mask)
    out = flash_attention(q, k, v, causal, None, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_exact(rng_np, causal):
    q, k, v = _qkv(rng_np, b=1, t=70, h=2, d=16)
    mask = A.causal_mask(q.shape[1], k.shape[1]) if causal else None

    def loss_ref(q, k, v):
        return jnp.sum(A.dot_product_attention(q, k, v, mask=mask) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, 32, 32) ** 2)

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_cross_attention_rectangular(rng_np):
    """nq != nk grids, fwd and bwd (encoder-decoder attention shape)."""
    b, h, d = 2, 2, 16
    q = jnp.asarray(rng_np.normal(size=(b, 37, h, d)).astype(np.float32))
    k = jnp.asarray(rng_np.normal(size=(b, 150, h, d)).astype(np.float32))
    v = jnp.asarray(rng_np.normal(size=(b, 150, h, d)).astype(np.float32))
    ref = A.dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, False, None, 32, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_ref = jax.jit(jax.grad(
        lambda *a: jnp.sum(A.dot_product_attention(*a) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(
        lambda *a: jnp.sum(flash_attention(*a, False, None, 32, 64) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_under_jit_and_vmap(rng_np):
    q, k, v = _qkv(rng_np, b=1, t=64, h=1, d=8)
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
    ref = A.dot_product_attention(q, k, v, mask=A.causal_mask(64, 64))
    np.testing.assert_allclose(np.asarray(jitted(q, k, v)), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # vmap over a leading axis (batches the pallas_call + custom_vjp)
    qs = jnp.stack([q, q * 0.5])
    ks = jnp.stack([k, k])
    vs = jnp.stack([v, v * 2.0])
    outs = jax.vmap(lambda a, b_, c: flash_attention(a, b_, c, True))(qs, ks, vs)
    for i in range(2):
        ref_i = A.dot_product_attention(qs[i], ks[i], vs[i],
                                        mask=A.causal_mask(64, 64))
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref_i),
                                   rtol=2e-5, atol=2e-5)


def test_softmax_xent_matches_xla():
    """Fused-CE kernel (ops/pallas/softmax_xent.py): forward and backward
    equal the XLA logsumexp formulation (interpret mode on CPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent

    rng = np.random.default_rng(0)
    n, v = 70, 300
    logits = jnp.asarray(rng.normal(size=(n, v)).astype(np.float32) * 3)
    tgt = jnp.asarray(rng.integers(0, v, size=(n,)))

    nll = softmax_xent(logits, tgt, 32, 128)
    ref = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0])
    np.testing.assert_allclose(np.asarray(nll), np.asarray(ref), atol=1e-4)

    g1 = jax.jit(jax.grad(
        lambda l: jnp.mean(softmax_xent(l, tgt, 32, 128))))(logits)
    g2 = jax.jit(jax.grad(lambda l: jnp.mean(
        jax.nn.logsumexp(l, axis=-1)
        - jnp.take_along_axis(l, tgt[:, None], axis=-1)[:, 0])))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_flash_matches_in_module_reference(rng_np):
    """flash_attention vs flash_attention_reference (the in-module oracle
    the check_kernel_parity tool audits), fwd + grad, causal and not."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_reference,
    )

    q, k, v = _qkv(rng_np, b=1, t=48, h=2, d=16)
    for causal in (False, True):
        ref = flash_attention_reference(q, k, v, causal)
        out = flash_attention(q, k, v, causal, None, 32, 32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g_r = jax.jit(jax.grad(lambda *a: jnp.sum(
            flash_attention_reference(*a, causal) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        g_k = jax.jit(jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, causal, None, 32, 32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b_ in zip(g_k, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)


def test_softmax_xent_matches_in_module_reference():
    from paddle_tpu.ops.pallas.softmax_xent import (
        softmax_xent,
        softmax_xent_reference,
    )

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(40, 170)).astype(np.float32) * 3)
    tgt = jnp.asarray(rng.integers(0, 170, size=(40,)))
    np.testing.assert_allclose(
        np.asarray(softmax_xent(logits, tgt, 32, 128)),
        np.asarray(softmax_xent_reference(logits, tgt)), atol=1e-4)
    g1 = jax.jit(jax.grad(
        lambda l: jnp.mean(softmax_xent(l, tgt, 32, 128))))(logits)
    g2 = jax.jit(jax.grad(
        lambda l: jnp.mean(softmax_xent_reference(l, tgt))))(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)
