"""Plain reference: a looped decoder-only language model as ByteDance's
Ouro ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; huggingface.co/ByteDance/Ouro-2.6B), forward pass
only, in straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no KV cache, no batching: one sequence, every position
against the whole context.  ``loop_steps`` passes over the SAME stacked
layers; each layer is RMSNorm -> multi-head attention with rotate-half
RoPE -> RMSNorm on the branch -> residual add, then RMSNorm -> SwiGLU ->
RMSNorm on the branch -> residual add (a "sandwich"); the final RMSNorm
closes every pass and its output is the next pass's input; the untied
head reads the last pass.  After each pass the exit gate gives
lambda_t = sigmoid(w . h_t + b); the exit distribution is
p_t = lambda_t * prod_{s<t} (1 - lambda_s), the last pass taking what is
left.  At the published ``early_exit_threshold`` 1 no token leaves early,
so the served token is a function of the last pass alone.

It imports nothing of the program, makes its own weights from the seed,
and keeps them in the type they are served in: a layer is upcast to
float32 when the scan reaches it, so the full-width model fits one chip
beside its own activations.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST

departures = [
    "the published modeling code keeps one K/V cache per (pass, layer) and "
    "offers decode-time variants that read only the last pass's cache or an "
    "average: this reference has no cache at all, so it is the full "
    "recomputation those caches must reproduce",
    "one [E, H*Dh] matrix each for q, k, v and separate gate and up "
    "matrices, as published",
    "early exit is not taken (threshold 1, as published): every token runs "
    "every pass; the gate is evaluated only by exit_distribution()",
    "weights drawn by the benchmark from --seed in bf16; the published "
    "checkpoint is bf16 too",
]

_LAYER = ("g1", "wq", "wk", "wv", "wo", "g2", "g3", "w_gate", "w_up",
          "w_down", "g4")
_PROGRAM = {  # reference leaf -> the program's pytree
    "wte": ("embed",), "head": ("head",), "g_f": ("ln_f_g",),
    "gate_w": ("exit_w",), "gate_b": ("exit_b",),
    "g1": ("blocks", "ln1_g"), "g2": ("blocks", "ln1_post_g"),
    "g3": ("blocks", "ln2_g"), "g4": ("blocks", "ln2_post_g"),
    "w_down": ("blocks", "w_out")}


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
    type they are served in; layer weights stacked on a leading
    ``num_layers`` axis.  Matrices are unit-gain normal (std fan_in^-0.5);
    the sandwich norms make the function independent of the scale of the
    two branch outputs, so there is no residual down-scaling.  Norm gains
    are drawn around 1 (``init.gain_std``) so that a gain applied in the
    wrong place shows.  ``init.wte_std`` / ``init.head_std`` set the
    deviations of the embedding and of the untied head."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    h, layers = m["num_heads"] * m["head_dim"], m["num_layers"]
    init = m.get("init", {})

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 16))

        def norm(shape, std, mean=0.0):
            return (mean + std * jax.random.normal(next(ks), shape,
                                                   jnp.float32)).astype(dtype)

        gain = functools.partial(norm, std=float(init.get("gain_std", 0.1)),
                                 mean=1.0)
        return {
            "wte": norm((v, e), float(init.get("wte_std", 1.0))),
            "head": norm((e, v), float(init.get("head_std", e ** -0.5))),
            "g1": gain((layers, e)), "g2": gain((layers, e)),
            "g3": gain((layers, e)), "g4": gain((layers, e)),
            "wq": norm((layers, e, h), e ** -0.5),
            "wk": norm((layers, e, h), e ** -0.5),
            "wv": norm((layers, e, h), e ** -0.5),
            "wo": norm((layers, h, e), h ** -0.5),
            "w_gate": norm((layers, e, f), e ** -0.5),
            "w_up": norm((layers, e, f), e ** -0.5),
            "w_down": norm((layers, f, e), f ** -0.5),
            "g_f": gain((e,)),
            "gate_w": norm((e,), e ** -0.5),
            "gate_b": jnp.zeros((), dtype),
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


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, H, Dh] at positions 0..T-1; dims (i, i + Dh/2) are a pair."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> h [loop_steps, T, E] float32: the normed output
    of every pass (causal)."""
    f32 = lambda a: a.astype(jnp.float32)
    t = ids.shape[0]
    nh, hd, eps = m["num_heads"], m["head_dim"], m["norm_eps"]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def block(x, l):
        l = jax.tree.map(f32, l)   # one layer's weights at a time
        h = _rms(x, l["g1"], eps)
        q = _rope(_mm(h, l["wq"], quant).reshape(t, nh, hd), m["rope_theta"])
        k = _rope(_mm(h, l["wk"], quant).reshape(t, nh, hd), m["rope_theta"])
        v = _mm(h, l["wv"], quant).reshape(t, nh, hd)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, nh * hd)
        x = x + _rms(_mm(a, l["wo"], quant), l["g2"], eps)
        h = _rms(x, l["g3"], eps)
        mlp = _mm(jax.nn.silu(_mm(h, l["w_gate"], quant))
                  * _mm(h, l["w_up"], quant), l["w_down"], quant)
        return x + _rms(mlp, l["g4"], eps), None

    layers = {k: w[k] for k in _LAYER}

    def one_pass(x, _):
        x, _ = lax.scan(block, x, layers)
        x = _rms(x, f32(w["g_f"]), eps)
        return x, x

    _, hs = lax.scan(one_pass, f32(w["wte"][ids]), None,
                     length=m["loop_steps"])
    return hs


def logits_fn(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> logits [T, V] float32: the head over the LAST
    pass."""
    return _mm(hidden_states(w, ids, m, quant)[-1],
               w["head"].astype(jnp.float32), quant)


def exit_distribution(w: dict, ids, m: dict):
    """(lambda [loop_steps, T], p [loop_steps, T]): the exit gate after
    every pass and the distribution over the pass a token would leave
    at; the last pass takes the remainder, so p sums to 1."""
    hs = hidden_states(w, ids, m)
    lam = jax.nn.sigmoid(
        jnp.einsum("ste,e->st", hs, w["gate_w"].astype(jnp.float32),
                   precision=HI) + w["gate_b"].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = lam * before
    return lam, p.at[-1].set(before[-1])


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
