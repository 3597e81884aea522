"""Plain reference of ``toy_models.toy_mlp_cost``: everything the
trainer's driver, the control and the roofline ask of a reference, for a
model family that came as files only."""

import jax
import jax.numpy as jnp
import numpy as np

SIZES = (("h1", 16, 32), ("out", 32, 4))


def init_weights(cfg: dict, seed: int) -> dict:
    key = jax.random.key(int(seed) & 0x7FFFFFFF)
    return {name: {"w": jax.random.normal(jax.random.fold_in(key, i),
                                          (fan_in, out)) * fan_in ** -0.5,
                   "b": jnp.zeros((out,))}
            for i, (name, fan_in, out) in enumerate(SIZES)}


def program_name(leaf: str) -> str:
    layer, part = leaf.split("/")
    return f"_{layer}." + {"w": "w0", "b": "wbias"}[part]


def feed_pool(cfg: dict, rng, batch: int) -> np.ndarray:
    return rng.standard_normal((batch, 16), dtype=np.float32)


def feed_labels(cfg: dict, rng, batch: int) -> np.ndarray:
    return rng.integers(0, 4, size=batch)


def reference_inputs(cfg: dict, pool: np.ndarray) -> np.ndarray:
    return pool


def gemm_table(cfg: dict, batch: int) -> list[dict]:
    """Each layer as a 1x1 convolution over a 1x1 image."""
    return [dict(name=name, n=batch, h=1, w=1, cin=fan_in, cout=out, k=1,
                 stride=1, pad=0) for name, fan_in, out in SIZES]


def _loss(w, x, y, quant):
    def q(a):
        return a if quant is None else a.astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    h = jax.nn.relu(q(x) @ q(w["h1"]["w"]) + w["h1"]["b"])
    logp = jax.nn.log_softmax(q(h) @ q(w["out"]["w"]) + w["out"]["b"])
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def train_reference(cfg, weights, batches, lr, momentum, shards=1,
                    quant=None) -> dict:
    norms = lambda t: jax.tree.map(  # noqa: E731
        lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a)))), t)
    w, v = weights, jax.tree.map(jnp.zeros_like, weights)
    losses, g1, first = [], None, None
    for x, y in batches:
        loss, g = jax.value_and_grad(_loss)(w, jnp.asarray(x),
                                            jnp.asarray(y), quant)
        if first is None:
            g1, first = norms(g), g
        v = jax.tree.map(lambda vv, gg: momentum * vv + gg, v, g)
        w = jax.tree.map(lambda ww, vv: ww - lr * vv, w, v)
        losses.append(float(loss))
    return {"losses": losses, "grad_norms": g1, "first_grad": first,
            "delta_norms": norms(jax.tree.map(lambda a, b: a - b, w,
                                              weights))}
