"""Plain reference: bottleneck ResNet (He et al., arXiv:1512.03385,
Table 1) with SGD + momentum, in straightforward jax.numpy float32.

No kernels, no cache, no mixed precision; matrix products at
``precision=HIGHEST``.  It imports nothing of the program and makes its
own weights from the seed.  Written from the paper, with the departures
the configuration file lists (they are the program's, and the reference
follows them so that the two compute the same function):

- stride 2 sits on the first 1x1 of a stage's first block (the paper's
  original placement, as in benchmark/paddle/image/resnet.py);
- the stem's 3x3/2 max pool has no padding and rounds up (Paddle's
  ceil mode) -- 112 -> 56 either way;
- batch-norm uses the batch's biased variance, eps 1e-5; under data
  parallelism the statistics are per shard (``shards`` > 1).

Memory: each bottleneck block is rematerialised in the backward pass,
so float32 activations of a 256-image batch fit beside nothing else.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


# -- the architecture as a table ----------------------------------------------

def layer_table(cfg: dict) -> list[dict]:
    """Every conv+BN unit in order: name, kernel, cin, cout, stride, pad,
    relu."""
    stem = cfg["stem"]
    rows = [dict(name="conv1", k=stem["kernel"], cin=cfg["image_channels"],
                 cout=stem["filters"], stride=stem["stride"], pad=stem["pad"],
                 relu=True)]
    cin = stem["filters"]
    for si, (blocks, f1, f2, stride) in enumerate(cfg["stages"]):
        for b in range(blocks):
            s = stride if b == 0 else 1
            nm = f"res{si + 2}_{b + 1}"
            if b == 0:
                rows.append(dict(name=nm + "_branch1", k=1, cin=cin, cout=f2,
                                 stride=s, pad=0, relu=False))
            rows.append(dict(name=nm + "_branch2a", k=1, cin=cin, cout=f1,
                             stride=s, pad=0, relu=True))
            rows.append(dict(name=nm + "_branch2b", k=3, cin=f1, cout=f1,
                             stride=1, pad=1, relu=True))
            rows.append(dict(name=nm + "_branch2c", k=1, cin=f1, cout=f2,
                             stride=1, pad=0, relu=False))
            cin = f2
    return rows


def conv_table(cfg: dict, batch: int) -> list[dict]:
    """Every convolution with its shapes at ``batch`` rows (n, h, w, cin,
    cout, k, stride, pad): what the roofline of the conv kernel family
    counts operations and bytes from."""
    def out(size, k, stride, pad):
        return (size + 2 * pad - k) // stride + 1

    rows, h = [], int(cfg["image_hw"])
    for r in layer_table(cfg):
        if r["name"] == "conv1":
            rows.append(dict(r, n=batch, h=h, w=h))
            h = out(h, r["k"], r["stride"], r["pad"])
            h = -(-(h - 3) // 2) + 1     # ceil-mode 3x3/2 max pool, no padding
            continue
        rows.append(dict(r, n=batch, h=h, w=h))
        if r["name"].endswith("_branch2a"):
            # the block's spatial size changes after its first 1x1 (a
            # first block's shortcut, listed before it, reads the input)
            h = out(h, r["k"], r["stride"], r["pad"])
    return rows


# -- what the trainer's driver asks of a reference ----------------------------

def program_name(leaf: str) -> str:
    """Reference leaf "layer/part" -> the program's parameter name."""
    layer, part = leaf.split("/")
    if layer == "fc":
        return "_fc_out." + {"w": "w0", "b": "wbias"}[part]
    return {"w": f"_{layer}_conv.w0", "gamma": f"_{layer}_bn.w0",
            "beta": f"_{layer}_bn.wbias"}[part]


def feed_pool(cfg: dict, rng: np.random.Generator, batch: int) -> np.ndarray:
    """One batch of samples as the v2 reader yields them: flat CHW
    float32 rows."""
    dim = cfg["image_channels"] * cfg["image_hw"] ** 2
    return rng.standard_normal((batch, dim), dtype=np.float32)


def feed_labels(cfg: dict, rng: np.random.Generator,
                batch: int) -> np.ndarray:
    return rng.integers(0, cfg["classes"], size=batch)


def reference_inputs(cfg: dict, pool: np.ndarray) -> np.ndarray:
    """The pool as ``train_reference`` takes it: NHWC."""
    c, hw = cfg["image_channels"], cfg["image_hw"]
    return pool.reshape(len(pool), c, hw, hw).transpose(0, 2, 3, 1)


def feed_struct(cfg: dict, batch: int) -> dict:
    """The step's feed by the data layers' names: (shape, dtype)."""
    dim = cfg["image_channels"] * cfg["image_hw"] ** 2
    return {"image": ((batch, dim), "float32"), "label": ((batch,), "int32")}


def seed_key(seed: int):
    """A key from any non-negative whole seed (also beyond 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(cfg: dict, seed: int) -> dict:
    """All weights on the device in ONE jitted call from the seed:
    ``{layer: {"w", "gamma", "beta"}}`` plus ``fc``.  He-normal convs,
    gamma 1 (``init.branch2c_gamma`` on the last batch-norm of every
    residual branch), beta 0, fc normal(0, 0.01); float32 master
    weights."""
    rows = layer_table(cfg)
    g2c = float(cfg.get("init", {}).get("branch2c_gamma", 1.0))
    cfin = cfg["stages"][-1][2]

    @jax.jit
    def make(key):
        out = {}
        for i, r in enumerate(rows):
            k = jax.random.fold_in(key, i)
            std = (2.0 / (r["k"] * r["k"] * r["cin"])) ** 0.5
            out[r["name"]] = {
                "w": std * jax.random.normal(
                    k, (r["k"], r["k"], r["cin"], r["cout"]), jnp.float32),
                "gamma": jnp.full(
                    (r["cout"],),
                    g2c if r["name"].endswith("_branch2c") else 1.0,
                    jnp.float32),
                "beta": jnp.zeros((r["cout"],), jnp.float32)}
        out["fc"] = {
            "w": 0.01 * jax.random.normal(
                jax.random.fold_in(key, len(rows)),
                (cfin, cfg["classes"]), jnp.float32),
            "b": jnp.zeros((cfg["classes"],), jnp.float32)}
        return out

    return make(seed_key(seed))


# -- lower-precision stand-in for the control ---------------------------------

def _round_to(x, kind):
    if kind == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    # fp8 with one scale per tensor: e4m3 forward, e5m2 for gradients
    dt, top = ((jnp.float8_e4m3fn, 448.0) if kind == "fp8"
               else (jnp.float8_e5m2, 57344.0))
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dt).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fake_quant(x, kind):
    """``x`` as a computation in ``kind`` ("fp8", "bf16", None = exact)
    would hold it: rounded on the way forward, and its gradient rounded
    on the way back (fp8: e5m2, the gradient format)."""
    return x if kind is None else _round_to(x, kind)


def _fq_fwd(x, kind):
    return _fake_quant(x, kind), None


def _fq_bwd(kind, _, g):
    if kind is None:
        return (g,)
    return (_round_to(g, "fp8_grad" if kind == "fp8" else kind),)


_fake_quant.defvjp(_fq_fwd, _fq_bwd)


# -- forward ------------------------------------------------------------------

def _conv_bn(x, p, r, eps, quant):
    y = lax.conv_general_dilated(
        _fake_quant(x, quant), _fake_quant(p["w"], quant),
        (r["stride"], r["stride"]), [(r["pad"], r["pad"])] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]
    return jax.nn.relu(y) if r["relu"] else y


def loss_fn(weights: dict, images, labels, cfg: dict, quant=None):
    """Mean cross-entropy of one batch (one BN group).  images
    [B, H, W, C] float32, labels [B] int32."""
    rows = {r["name"]: r for r in layer_table(cfg)}
    eps = cfg["bn_eps"]
    x = _conv_bn(images, weights["conv1"], rows["conv1"], eps, quant)
    # 3x3/2 max pool, no padding, ceil mode
    h = x.shape[1]
    out = -(-(h - 3) // 2) + 1
    extra = (out - 1) * 2 + 3 - h
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (0, extra), (0, extra), (0, 0)])
    for si, (blocks, _, _, _) in enumerate(cfg["stages"]):
        for b in range(blocks):
            nm = f"res{si + 2}_{b + 1}"

            def block(x, w, nm=nm, first=(b == 0)):
                short = (_conv_bn(x, w[nm + "_branch1"],
                                  rows[nm + "_branch1"], eps, quant)
                         if first else x)
                y = x
                for br in ("_branch2a", "_branch2b", "_branch2c"):
                    y = _conv_bn(y, w[nm + br], rows[nm + br], eps, quant)
                return jax.nn.relu(short + y)

            sub = {k: v for k, v in weights.items() if k.startswith(nm + "_")}
            x = jax.checkpoint(block)(x, sub)
    x = jnp.mean(x, axis=(1, 2))                       # global average pool
    logits = jnp.dot(_fake_quant(x, quant),
                     _fake_quant(weights["fc"]["w"], quant),
                     precision=HI) + weights["fc"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json: str, shards: int, quant):
    """Jitted (weights, images, labels) -> (mean loss, mean gradient) over
    ``shards`` equal row blocks, each its own batch-norm group, one
    after the other."""
    cfg = json.loads(cfg_json)

    def one(weights, images, labels):
        return jax.value_and_grad(loss_fn)(weights, images, labels, cfg,
                                           quant)

    def f(weights, images, labels):
        b = images.shape[0] // shards
        im = images.reshape((shards, b) + images.shape[1:])
        lb = labels.reshape((shards, b))
        losses, grads = lax.map(lambda il: one(weights, il[0], il[1]),
                                (im, lb))
        return jnp.mean(losses), jax.tree.map(lambda g: jnp.mean(g, 0),
                                              grads)

    return jax.jit(f)


def _sq_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def train_reference(cfg: dict, weights: dict, batches, lr: float,
                    momentum: float, shards: int = 1, quant=None) -> dict:
    """Follow ``batches`` (a list of (images NHWC f32, labels int32))
    with heavy-ball momentum: v = m v + g, w -= lr v.  Returns the loss
    of each step, the per-leaf norm of the first gradient and of the
    parameters' change after the last step."""
    step = _grad_fn(json.dumps(cfg, sort_keys=True), int(shards), quant)
    with jax.default_matmul_precision("highest"):
        w = weights
        v = jax.tree.map(jnp.zeros_like, w)
        losses, g1, first = [], None, None
        for images, labels in batches:
            loss, g = step(w, jnp.asarray(images),
                           jnp.asarray(labels, jnp.int32))
            if g1 is None:
                g1, first = jax.device_get(_sq_norms(g)), g
            v = jax.tree.map(lambda vv, gg: momentum * vv + gg, v, g)
            w = jax.tree.map(lambda ww, vv: ww - lr * vv, w, v)
            losses.append(float(loss))
        delta = jax.device_get(_sq_norms(
            jax.tree.map(lambda a, b: a - b, w, weights)))
    return {"losses": losses, "grad_norms": g1, "delta_norms": delta,
            "first_grad": first}
