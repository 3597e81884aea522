"""Plain reference: a hybrid decoder-only language model as NVIDIA's
Nemotron-H family (``model_type: nemotron_h``;
huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), forward pass
only, in straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no cache, no batching, no chunks: one sequence, every
position against the whole context.  The stack is a pattern of layers,
each ONE mixer behind an RMSNorm and a residual add, ``x <- x +
mixer(RMSNorm(x))``; a final RMSNorm and an untied head; a token
embedding with no position signal of any kind.

- ``M`` Mamba-2: ``in_proj`` -> z | xBC | dt; xBC <- silu(causal
  depthwise conv + bias) -> x | B | C; dt <- softplus(dt + dt_bias);
  A = -exp(A_log); per head h (B/C group h // (H/G)) the recurrence,
  written as the recurrence (a scan over tokens):
  ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
  D_h x_t``; then ``RMSNorm over groups of d_inner/G (y * silu(z))`` and
  ``out_proj``.
- ``E`` experts: ``s = sigmoid(x W_r)`` in float32 over ALL the published
  experts; chosen = top-k of ``s + e_score_correction_bias``; weights =
  ``s[chosen] / sum s[chosen] * routed_scaling_factor``; expert e is
  ``relu(x U_e)^2 D_e`` (no gate, no bias); plus the shared expert, the
  same MLP at its own width, weight 1.  The experts are a loop.
- ``*`` attention: q over ``num_heads``, k and v over ``kv_heads`` heads
  (query head h reads K/V head h // (H/KV)), causal softmax at
  head_dim^-1/2, no rotary.

**The share.**  The configuration states a deployment in which a device
holds experts ``[lo, hi)`` of every expert layer and the first
``vocab_size`` ids; this reference is GIVEN the same share
(``moe_held``): it routes over all experts, adds the held ones' part and
the shared expert, and leaves out what the absent experts would have
added — in the program and here alike, that partial result goes on to
the next layer.  ``moe_mixer(..., held=(lo, hi))`` computes any share,
so the shares can be added up against the uncut layer.

It imports nothing of the program, makes its own weights from the seed,
and keeps them in the type they are served in: a layer (an expert, inside
the loop over experts) is upcast to float32 when it is reached, so 3.9 B
parameters fit one chip beside the reference's own activations.
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
    "the published modeling code runs the Mamba-2 mixer through fused "
    "chunked-scan kernels and keeps conv and SSM caches for decoding; this "
    "reference has neither: the recurrence is a scan over tokens from a zero "
    "state, so it is what chunks and caches must reproduce",
    "the experts are a loop over the held experts, each applied to every "
    "token and weighted by the router's (mostly zero) weight; the published "
    "code gathers each expert's tokens first -- the same sum",
    "the router's scores, its top-k and its weights are float32 whatever "
    "precision the control runs the matrices in: the published router is "
    "float32 in a bf16 model",
    "weights drawn by the benchmark from --seed in bf16; the published "
    "checkpoint is bf16 too; the SSM state is float32, as NVIDIA's serving "
    "note for the family asks of the cache",
]

KINDS = {"*": "attn", "E": "moe", "M": "mamba"}
_PROGRAM = {  # reference leaf -> the program's leaf
    "g": "ln_g", "bias": "router_bias", "up": "w_in", "down": "w_out",
    "shared_up": "shared_in", "shared_down": "shared_out"}


def program_tree(weights: dict) -> dict:
    """The weights under the names the program's pytree gives them (the
    same arrays: nothing is copied)."""
    return {"embed": weights["wte"], "head": weights["head"],
            "ln_f_g": weights["g_f"],
            "blocks": [{_PROGRAM.get(k, k): v for k, v in layer.items()}
                       for layer in weights["layers"]]}


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def sizes(m: dict) -> dict:
    """The widths the mixers share, from the model group."""
    nh, p = m["mamba_heads"], m["mamba_head_dim"]
    g, n = m["mamba_groups"], m["mamba_state"]
    lo, hi = m.get("moe_held") or (0, m["moe_experts"])
    return {"d_inner": nh * p, "conv_dim": nh * p + 2 * g * n,
            "in_proj": 2 * nh * p + 2 * g * n + nh, "held": hi - lo}


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights on the device from the seed, in the type they are
    served in; ``layers`` is a list of the layers' own trees in pattern
    order (nothing stacked: a layer's matrices are arrays of their own).
    Matrices are unit-gain normal (std fan_in^-0.5); the
    matrices that write to the residual stream (attention ``wo``, the
    Mamba ``out_proj``, the experts' ``down``) are scaled by
    num_layers^-1/2 (the published ``rescale_prenorm_residual``).  Norm
    gains are drawn around 1 and the conv bias and the router's
    correction bias small and non-zero, so that a gain, a bias or a
    correction applied in the wrong place shows.  dt_bias is the inverse
    softplus of a time step drawn log-uniform in [time_step_min,
    time_step_max], A uniform in [1, 16] (the family's initialisation), D
    around 1."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    h, hk = m["num_heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    sz, init = sizes(m), m.get("init", {})
    n_exp, shared = m["moe_experts"], m["moe_shared_dim"]
    nh, k_conv = m["mamba_heads"], m["mamba_conv"]
    out = m["num_layers"] ** -0.5
    gain_std = float(init.get("gain_std", 0.1))
    dt_lo, dt_hi = (float(init.get("time_step_min", 1e-3)),
                    float(init.get("time_step_max", 1e-1)))

    def norm(shape, std, mean=0.0):
        return lambda key: (mean + std * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    def dt_bias(key):
        dt = jnp.exp(jax.random.uniform(
            key, (nh,), jnp.float32, math.log(dt_lo), math.log(dt_hi)))
        dt = jnp.maximum(dt, float(init.get("time_step_floor", 1e-4)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key):
        return jnp.log(jax.random.uniform(key, (nh,), jnp.float32, 1.0, 16.0)
                       ).astype(dtype)

    gain = lambda shape: norm(shape, gain_std, 1.0)
    leaves = {
        "attn": {"g": gain((e,)), "wq": norm((e, h), e ** -0.5),
                 "wk": norm((e, hk), e ** -0.5),
                 "wv": norm((e, hk), e ** -0.5),
                 "wo": norm((h, e), h ** -0.5 * out)},
        "moe": {"g": gain((e,)), "router": norm((e, n_exp), e ** -0.5),
                "bias": norm((n_exp,), float(init.get("router_bias_std",
                                                      0.1))),
                "up": norm((sz["held"], e, f), e ** -0.5),
                "down": norm((sz["held"], f, e), f ** -0.5 * out),
                "shared_up": norm((e, shared), e ** -0.5),
                "shared_down": norm((shared, e), shared ** -0.5 * out)},
        "mamba": {"g": gain((e,)),
                  "in_proj": norm((e, sz["in_proj"]), e ** -0.5),
                  "conv_w": norm((k_conv, sz["conv_dim"]), k_conv ** -0.5),
                  "conv_b": norm((sz["conv_dim"],), 0.1),
                  "dt_bias": dt_bias, "a_log": a_log,
                  "d": gain((nh,)), "norm_g": gain((sz["d_inner"],)),
                  "out_proj": norm((sz["d_inner"], e),
                                   sz["d_inner"] ** -0.5 * out)},
    }

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 3 + 16 * len(m["pattern"])))
        return {
            "wte": norm((v, e), float(init.get("wte_std", 1.0)))(next(ks)),
            "head": norm((e, v), float(init.get("head_std", e ** -0.5)))(
                next(ks)),
            "g_f": gain((e,))(next(ks)),
            "layers": [{name: leaf(next(ks))
                        for name, leaf in leaves[KINDS[c]].items()}
                       for c in m["pattern"]],
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


_f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def attention_mixer(l: dict, h, m: dict, quant=None):
    """h [T, E] normed -> [T, E]; l: one layer's leaves."""
    l = _f32(l)
    t = h.shape[0]
    nh, kv, hd = m["num_heads"], m["kv_heads"], m["head_dim"]
    q = _mm(h, l["wq"], quant).reshape(t, kv, nh // kv, hd)
    k = _mm(h, l["wk"], quant).reshape(t, kv, hd)
    v = _mm(h, l["wv"], quant).reshape(t, kv, hd)
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HI) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(t, nh * hd)
    return _mm(a, l["wo"], quant)


def route(l: dict, h, m: dict):
    """The published router in float32: (chosen ids [T, k], weights
    [T, k]) over ALL experts."""
    s = jax.nn.sigmoid(jnp.dot(h, l["router"].astype(jnp.float32),
                               precision=HI))
    _, idx = lax.top_k(s + l["bias"].astype(jnp.float32), m["moe_top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * m["moe_scale"]


def moe_mixer(l: dict, h, m: dict, quant=None, held=None, shared=True):
    """h [T, E] normed -> [T, E]: the part of the layer's result that
    experts ``held`` = (lo, hi) give (default: the configuration's share;
    ``l["up"]`` / ``l["down"]`` hold exactly those experts), plus the
    shared expert when ``shared``."""
    lo, hi = held or m.get("moe_held") or (0, m["moe_experts"])
    idx, w = route(l, h, m)
    # [T, X]: the weight each expert has for each token (0: not chosen)
    comb = jnp.sum(w[..., None] * (idx[..., None] == jnp.arange(
        m["moe_experts"])), axis=1)

    def expert(y, e):
        up, down, c = e        # one expert, upcast as it is reached
        a = jnp.square(jax.nn.relu(_mm(h, up.astype(jnp.float32), quant)))
        return y + c[:, None] * _mm(a, down.astype(jnp.float32), quant), None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (l["up"], l["down"], comb[:, lo:hi].T))
    if shared:
        a = jnp.square(jax.nn.relu(
            _mm(h, l["shared_up"].astype(jnp.float32), quant)))
        y = y + _mm(a, l["shared_down"].astype(jnp.float32), quant)
    return y


def mamba_mixer(l: dict, h, m: dict, quant=None, with_state=False):
    """h [T, E] normed -> [T, E] (and, ``with_state``, the SSM state
    [H, P, N] after the last token and the last K-1 conv inputs)."""
    l = _f32(l)
    t = h.shape[0]
    nh, p = m["mamba_heads"], m["mamba_head_dim"]
    g, n, k = m["mamba_groups"], m["mamba_state"], m["mamba_conv"]
    di = nh * p
    z, xbc, dt = jnp.split(_mm(h, l["in_proj"], quant),
                           [di, 2 * di + 2 * g * n], axis=-1)
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], axis=0)
    conv = sum(pad[j:j + t] * l["conv_w"][j] for j in range(k)) + l["conv_b"]
    x, b, c = jnp.split(jax.nn.silu(conv), [di, di + g * n], axis=-1)
    x = x.reshape(t, nh, p)
    b = jnp.repeat(b.reshape(t, g, n), nh // g, axis=1)     # [T, H, N]
    c = jnp.repeat(c.reshape(t, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + l["dt_bias"])                  # [T, H]
    a = -jnp.exp(l["a_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HI) \
            + l["d"][:, None] * x_t

    s_last, y = lax.scan(step, jnp.zeros((nh, p, n)), (x, b, c, dt))
    y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                      + m["norm_eps"])
    out = _mm(y.reshape(t, di) * l["norm_g"], l["out_proj"], quant)
    return (out, s_last, pad[t:]) if with_state else out


_MIXERS = {"attn": attention_mixer, "moe": moe_mixer, "mamba": mamba_mixer}


def hidden_states(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> h [T, E] float32: the final RMSNorm's output
    (causal)."""
    x = w["wte"][ids].astype(jnp.float32)
    for ch, l in zip(m["pattern"], w["layers"]):
        h = _rms(x, l["g"].astype(jnp.float32), m["norm_eps"])
        x = x + _MIXERS[KINDS[ch]](l, h, m, quant)
    return _rms(x, w["g_f"].astype(jnp.float32), m["norm_eps"])


def logits_fn(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> logits [T, V] float32 over the held ids."""
    return _mm(hidden_states(w, ids, m, quant),
               w["head"].astype(jnp.float32), quant)


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
