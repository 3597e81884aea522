"""Pipeline parallel == sequential stage application (forward and grads)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from paddle_tpu.parallel.pipeline import pipeline_apply


def _setup(n_stages=4, dim=8):
    r = np.random.default_rng(0)
    w = jnp.asarray(r.normal(size=(n_stages, dim, dim)).astype(np.float32) * 0.3)
    b = jnp.asarray(r.normal(size=(n_stages, dim)).astype(np.float32) * 0.1)
    x = jnp.asarray(r.normal(size=(8, dim)).astype(np.float32))
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]).reshape(n_stages), ("pipe",))

    def stage_fn(params, h):
        w, b = params
        return jnp.tanh(h @ w + b)

    def sequential(params, x):
        w, b = params
        h = x
        for i in range(n_stages):
            h = stage_fn((w[i], b[i]), h)
        return h

    return (w, b), x, mesh, stage_fn, sequential


def test_pipeline_forward_matches_sequential():
    params, x, mesh, stage_fn, sequential = _setup()
    ref = sequential(params, x)
    out = pipeline_apply(stage_fn, params, x, n_microbatches=4, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pipeline_grads_match_sequential():
    params, x, mesh, stage_fn, sequential = _setup()

    def loss_pipe(params):
        return jnp.sum(
            pipeline_apply(stage_fn, params, x, n_microbatches=4, mesh=mesh) ** 2
        )

    def loss_seq(params):
        return jnp.sum(sequential(params, x) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    for a, b in zip(g_pipe, g_seq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_pipeline_jits():
    params, x, mesh, stage_fn, sequential = _setup()
    f = jax.jit(
        lambda p, x: pipeline_apply(stage_fn, p, x, n_microbatches=2, mesh=mesh)
    )
    out = f(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(sequential(params, x)), atol=1e-5
    )
