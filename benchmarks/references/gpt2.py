"""Plain reference: GPT-2 (Radford et al. 2019), forward pass only, in
straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no KV cache, no batching: one sequence, every position
against the whole context.  Pre-LN decoder blocks, learned positions,
tanh GELU, LayerNorm eps 1e-5, output head tied to the token embedding.
It imports nothing of the program and makes its own weights from the
seed.  Departure the configuration lists (the program's, followed here
so both compute one function): the attention projections carry no bias.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


_PROGRAM = {  # reference leaf -> the program's pytree
    "wte": ("embed",), "wpe": ("pos_embed",), "lnf_g": ("ln_f_g",),
    "lnf_b": ("ln_f_b",), "w_fc": ("blocks", "w_in"),
    "b_fc": ("blocks", "b_in"), "w_proj": ("blocks", "w_out"),
    "b_proj": ("blocks", "b_out")}


def program_tree(weights: dict) -> dict:
    """The weights under the names the program's pytree gives them."""
    out: dict = {"blocks": {}}
    for k, v in weights.items():
        path = _PROGRAM.get(k, ("blocks", k))
        if len(path) == 1:
            out[path[0]] = v
        else:
            out["blocks"][path[1]] = v
    return out


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights on the device in ONE jitted call from the seed, in the
    type they are served in.  Layer weights are stacked on a leading
    ``num_layers`` axis.  Unit-gain matrices (std fan_in^-0.5), residual
    outputs scaled by (2 L)^-0.5 as in GPT-2's initialisation, positions
    normal(0, 0.02).  ``init.wte_std`` / ``init.wpe_std`` set the
    deviations of the token and position embeddings: the head is tied to
    the token embedding, and where that is large against the residual
    stream a random network answers every prompt with one repeated
    token, by a margin no rounding can flip -- so no comparison of
    greedy tokens could tell a lower precision from the stated one."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    layers, t = m["num_layers"], m["max_positions"]

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 8))

        def norm(shape, std):
            return (std * jax.random.normal(next(ks), shape, jnp.float32)
                    ).astype(dtype)

        res = (2 * layers) ** -0.5
        return {
            "wte": norm((v, e), float(m.get("init", {}).get(
                "wte_std", e ** -0.5))),
            "wpe": norm((t, e), float(m.get("init", {}).get(
                "wpe_std", 0.02))),
            "ln1_g": jnp.ones((layers, e), dtype),
            "ln1_b": jnp.zeros((layers, e), dtype),
            "wq": norm((layers, e, e), e ** -0.5),
            "wk": norm((layers, e, e), e ** -0.5),
            "wv": norm((layers, e, e), e ** -0.5),
            "wo": norm((layers, e, e), e ** -0.5 * res),
            "ln2_g": jnp.ones((layers, e), dtype),
            "ln2_b": jnp.zeros((layers, e), dtype),
            "w_fc": norm((layers, e, f), e ** -0.5),
            "b_fc": jnp.zeros((layers, f), dtype),
            "w_proj": norm((layers, f, e), f ** -0.5 * res),
            "b_proj": jnp.zeros((layers, e), dtype),
            "lnf_g": jnp.ones((e,), dtype),
            "lnf_b": jnp.zeros((e,), dtype),
        }

    return make(seed_key(seed))


def _int8(x, axis):
    """Symmetric int8 round trip with one scale per slice along
    ``axis`` -- the control's lower precision."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(x / scale).clip(-127, 127) * scale


def _mm(x, w, quant):
    if quant == "int8":        # per-token activations, per-column weights
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.dot(x, w, precision=HI)


def _ln(x, g, b, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def logits_fn(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> logits [T, V] float32 (causal)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    t = ids.shape[0]
    nh = m["num_heads"]
    hd = m["embed_dim"] // nh
    x = w["wte"][ids] + w["wpe"][:t]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def block(x, l):
        h = _ln(x, l["ln1_g"], l["ln1_b"])
        q = _mm(h, l["wq"], quant).reshape(t, nh, hd)
        k = _mm(h, l["wk"], quant).reshape(t, nh, hd)
        v = _mm(h, l["wv"], quant).reshape(t, nh, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, nh * hd)
        x = x + _mm(a, l["wo"], quant)
        h = _ln(x, l["ln2_g"], l["ln2_b"])
        h = jax.nn.gelu(_mm(h, l["w_fc"], quant) + l["b_fc"],
                        approximate=True)
        return x + _mm(h, l["w_proj"], quant) + l["b_proj"], None

    layers = {k: w[k] for k in ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
                                "ln2_g", "ln2_b", "w_fc", "b_fc", "w_proj",
                                "b_proj")}
    x, _ = lax.scan(block, x, layers)
    x = _ln(x, w["lnf_g"], w["lnf_b"])
    return _mm(x, w["wte"].T, quant)


@functools.lru_cache(maxsize=None)
def _gap_fn(m_json: str, quant):
    m = json.loads(m_json)

    def f(w, ids, targets):
        """Per position p: how far the logit of ``targets[p]`` lies below
        the float32 reference's best, at the position that predicts it;
        and the same for the token a lower precision puts first."""
        ref = logits_fn(w, ids, m, None)
        top2 = lax.top_k(ref, 2)[0]
        best, margin = top2[:, 0], top2[:, 0] - top2[:, 1]
        served = best - jnp.take_along_axis(ref, targets[:, None], 1)[:, 0]
        if quant is None:
            return served, served, margin
        low = jnp.argmax(logits_fn(w, ids, m, quant), -1)
        return (served,
                best - jnp.take_along_axis(ref, low[:, None], 1)[:, 0],
                margin)

    return jax.jit(f)


def served_gaps(m: dict, weights: dict, requests, pad_to: int,
                quant=None) -> dict:
    """``requests``: [(prompt ids, served ids), ...].  One reference pass
    over each prompt with its served tokens.  Returns the per-token gaps
    of the served tokens (``served``) and, with ``quant``, of the tokens
    the lower precision would have put first at the same positions
    (``control``), and the reference's own margin between its best and
    second token there (``margin``: how close the ties are)."""
    import numpy as np

    f = _gap_fn(json.dumps(m, sort_keys=True), quant)
    served, control, margin = [], [], []
    with jax.default_matmul_precision("highest"):
        for prompt, tokens in requests:
            seq = list(prompt) + list(tokens)
            n, p = len(seq), len(prompt)
            ids = np.zeros((pad_to,), np.int32)
            ids[:n] = seq
            targets = np.zeros((pad_to,), np.int32)
            targets[:n - 1] = seq[1:]
            s, c, g = jax.device_get(f(weights, jnp.asarray(ids),
                                       jnp.asarray(targets)))
            # position p-1 predicts the first served token
            served.extend(float(x) for x in s[p - 1:n - 1])
            control.extend(float(x) for x in c[p - 1:n - 1])
            margin.extend(float(x) for x in g[p - 1:n - 1])
    return {"served": served, "control": control, "margin": margin}


def summarise(gaps) -> dict:
    """The numbers compared: the widest gap (swings with the sample: it
    is there to catch a wrong token) and the mean gap over the sampled
    tokens (steady: it is what a lower precision moves)."""
    gaps = list(gaps)
    return {"widest": max(gaps) if gaps else None,
            "mean": sum(gaps) / len(gaps) if gaps else None,
            "moved_share": (sum(1 for g in gaps if g > 0) / len(gaps)
                            if gaps else None)}
