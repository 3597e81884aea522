"""Plain reference: a hybrid decoder-only language model as IBM's
Granite 4.0-H family (``model_type: granitemoehybrid`` with no routed
experts; huggingface.co/ibm-granite/granite-4.0-h-micro), forward pass
only, in straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no cache, no batching, no chunks: one sequence, every
position against the whole context.

- ``x0 = embedding_multiplier * wte[ids]``; no position signal of any
  kind (``position_embedding_type: nope``).
- the stack is a pattern of layers, each ONE mixer behind an RMSNorm
  (gain only) and a residual add scaled by ``residual_multiplier``:
  ``x <- x + residual_multiplier * mixer(RMSNorm(x))``.  The published
  block is a mixer then the shared MLP; here each is a layer of its own
  (``M-`` / ``*-``), which is the same arithmetic.
- ``-`` the MLP: ``(silu(h W_g) * (h W_u)) W_d``, no bias.
- ``*`` attention: q over ``num_heads``, k and v over ``kv_heads`` heads
  (query head h reads K/V head h // (H/KV)), causal
  ``softmax(q k^T * attention_multiplier) v`` -- the multiplier is a
  number of the configuration, NOT head_dim^-1/2 -- no rotary, no bias.
- ``M`` Mamba-2: ``in_proj`` -> z | xBC | dt; xBC <- silu(causal
  depthwise conv + bias) -> x | B | C; dt <- softplus(dt + dt_bias);
  A = -exp(A_log); per head h (B/C group h // (H/G)) the recurrence,
  written as the recurrence (a scan over tokens):
  ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
  D_h x_t``; then ``RMSNorm over groups of d_inner/G (y * silu(z))``,
  a gain, and ``out_proj``.
- ``logits = RMSNorm(x) wte^T / logits_scaling`` (tied embedding).

The pattern repeats a period (``MMMMM*MMMM`` with an MLP behind each:
20 entries, four times).  The weights are kept one tree a POSITION of
the period, every leaf stacked over the repeats, in the type they are
served in, and the forward pass is a loop over the repeats around a loop
over the period; a layer is upcast to float32 when it is reached.  It
imports nothing of the program and makes its own weights from the seed.
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
    "chunked-scan kernels (chunk 256) and keeps conv and SSM caches for "
    "decoding; this reference has neither: the recurrence is a scan over "
    "tokens from a zero state, so it is what chunks and caches must "
    "reproduce",
    "the published decoder layer is a mixer followed by the shared MLP, each "
    "behind its own RMSNorm and its own residual add x residual_multiplier; "
    "here the two are two entries of the layer pattern (M- / *-): the same "
    "arithmetic in the same order",
    "the published block has a routed-experts branch beside the shared MLP; "
    "num_local_experts is 0 in this configuration, so the branch does not "
    "exist and is not written",
    "the recurrent state is float32 here and in the program's pool; the "
    "published code keeps the model's dtype for its caches",
    "weights drawn by the benchmark from --seed in bf16; nothing of the "
    "published checkpoint is read",
]

KINDS = {"*": "attn", "-": "mlp", "M": "mamba"}
_PROGRAM = {"g": "ln_g", "gate": "w_gate", "up": "w_up", "down": "w_out"}


def period(pattern: str) -> tuple:
    """(p, r): the pattern is r repeats of its first p characters, p the
    smallest such."""
    n = len(pattern)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and pattern == pattern[:p] * (n // p))
    return p, n // p


def program_tree(weights: dict) -> dict:
    """The weights under the names the program's pytree gives them (the
    same arrays: nothing is copied).  The program keeps a pattern that
    repeats its period one tree a position, stacked over the repeats, as
    here; a pattern of one period it keeps a tree a layer, unstacked."""
    once = weights["layers"][0]["g"].shape[0] == 1
    return {"embed": weights["wte"], "ln_f_g": weights["g_f"],
            "blocks": [{_PROGRAM.get(k, k): v[0] if once else v
                        for k, v in layer.items()}
                       for layer in weights["layers"]]}


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def sizes(m: dict) -> dict:
    """The widths the Mamba-2 mixer's parts share, from the model group."""
    nh, p = m["mamba_heads"], m["mamba_head_dim"]
    g, n = m["mamba_groups"], m["mamba_state"]
    return {"d_inner": nh * p, "conv_dim": nh * p + 2 * g * n,
            "in_proj": 2 * nh * p + 2 * g * n + nh}


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights on the device from the seed, in the type they are
    served in; ``layers`` is a list of one tree a position of the period,
    every leaf stacked ``[repeats, ...]``.

    The draw goes THROUGH the four multipliers.  The tied embedding has
    deviation ``logits_divisor / sqrt(E)`` (``wte_std``), so the logits --
    a unit-RMS state against its rows, divided by ``logits_divisor`` --
    have unit spread over the vocabulary; the stream then starts at
    ``embed_multiplier x wte_std`` (2.1 at the published numbers).  Every
    matrix is unit-gain normal (std fan_in^-0.5); the matrices that write
    to the stream (``wo``, ``out_proj``, ``down``) are not scaled DOWN by
    depth (the published ``residual_multiplier`` does that) but UP by
    ``out_gain``: with tied weights the logit of a position's own input
    token is cos(stream, its embedding) x sqrt(E), and at unit gain the
    80 branches x 0.22^2 add only about what the embedding brought -- the
    cosine is 0.7, that logit 30 among unit-spread ones, and every
    position predicts its own input (a collapsed generation compares
    nothing).  As in a trained model the stream has to grow well past the
    embedding it started from.  q and k projections carry
    ``qk_gain`` so that the scores have unit spread at the published
    ``attn_scale`` (1/64 at head_dim 64 wants 8^1/2 on each).  Norm
    gains are drawn around 1 and the conv bias small and non-zero, so
    that a gain or a bias applied in the wrong place shows.  dt_bias is
    the inverse softplus of a time step drawn log-uniform in
    [time_step_min, time_step_max], A uniform in [1, 16] (the Mamba-2
    initialisation), D around 1."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    h, hk = m["num_heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    sz, init = sizes(m), m.get("init", {})
    nh, k_conv = m["mamba_heads"], m["mamba_conv"]
    p, r = period(m["pattern"])
    gain_std = float(init.get("gain_std", 0.1))
    qk = float(init.get("qk_gain", 1.0))
    out = float(init.get("out_gain", 1.0))
    dt_lo, dt_hi = (float(init.get("time_step_min", 1e-3)),
                    float(init.get("time_step_max", 1e-1)))

    def norm(shape, std, mean=0.0):
        return lambda key: (mean + std * jax.random.normal(
            key, (r, *shape), jnp.float32)).astype(dtype)

    def dt_bias(key):
        dt = jnp.exp(jax.random.uniform(
            key, (r, nh), jnp.float32, math.log(dt_lo), math.log(dt_hi)))
        dt = jnp.maximum(dt, float(init.get("time_step_floor", 1e-4)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key):
        return jnp.log(jax.random.uniform(
            key, (r, nh), jnp.float32, 1.0, 16.0)).astype(dtype)

    gain = lambda shape: norm(shape, gain_std, 1.0)
    leaves = {
        "attn": {"g": gain((e,)), "wq": norm((e, h), e ** -0.5 * qk),
                 "wk": norm((e, hk), e ** -0.5 * qk),
                 "wv": norm((e, hk), e ** -0.5),
                 "wo": norm((h, e), h ** -0.5 * out)},
        "mlp": {"g": gain((e,)), "gate": norm((e, f), e ** -0.5),
                "up": norm((e, f), e ** -0.5),
                "down": norm((f, e), f ** -0.5 * out)},
        "mamba": {"g": gain((e,)),
                  "in_proj": norm((e, sz["in_proj"]), e ** -0.5),
                  "conv_w": norm((k_conv, sz["conv_dim"]), k_conv ** -0.5),
                  "conv_b": norm((sz["conv_dim"],), 0.1),
                  "dt_bias": dt_bias, "a_log": a_log,
                  "d": gain((nh,)), "norm_g": gain((sz["d_inner"],)),
                  "out_proj": norm((sz["d_inner"], e),
                                   sz["d_inner"] ** -0.5 * out)},
    }
    wte_std = float(init.get("wte_std", m["logits_divisor"] * e ** -0.5))

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 2 + 16 * p))
        return {
            "wte": (wte_std * jax.random.normal(
                next(ks), (v, e), jnp.float32)).astype(dtype),
            "g_f": gain((e,))(next(ks))[0],
            "layers": [{name: leaf(next(ks))
                        for name, leaf in leaves[KINDS[c]].items()}
                       for c in m["pattern"][:p]],
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
    scale = m.get("attn_scale")
    scale = hd ** -0.5 if scale is None else scale
    q = _mm(h, l["wq"], quant).reshape(t, kv, nh // kv, hd)
    k = _mm(h, l["wk"], quant).reshape(t, kv, hd)
    v = _mm(h, l["wv"], quant).reshape(t, kv, hd)
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HI) * scale
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(t, nh * hd)
    return _mm(a, l["wo"], quant)


def mlp_mixer(l: dict, h, m: dict, quant=None):
    """h [T, E] normed -> [T, E]: the gated MLP, no bias."""
    l = _f32(l)
    a = jax.nn.silu(_mm(h, l["gate"], quant)) * _mm(h, l["up"], quant)
    return _mm(a, l["down"], quant)


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


_MIXERS = {"attn": attention_mixer, "mlp": mlp_mixer, "mamba": mamba_mixer}


def layer_of(w: dict, i: int, m: dict) -> dict:
    """Layer ``i`` of the pattern: its own leaves, sliced out of its
    position's stack."""
    p, _ = period(m["pattern"])
    return {k: v[i // p] for k, v in w["layers"][i % p].items()}


def hidden_states(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> h [T, E] float32: the final RMSNorm's output
    (causal)."""
    p, _ = period(m["pattern"])
    x = m["embed_multiplier"] * w["wte"][ids].astype(jnp.float32)

    def repeat(x, layers):
        """One period: ``layers`` is this repeat's slice of every
        position's stack."""
        for ch, l in zip(m["pattern"][:p], layers):
            h = _rms(x, l["g"].astype(jnp.float32), m["norm_eps"])
            x = x + m["residual_multiplier"] * _MIXERS[KINDS[ch]](
                l, h, m, quant)
        return x, None

    x, _ = lax.scan(repeat, x, w["layers"])
    return _rms(x, w["g_f"].astype(jnp.float32), m["norm_eps"])


def logits_fn(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> logits [T, V] float32 (the tied embedding's
    transpose, divided by ``logits_divisor``)."""
    return _mm(hidden_states(w, ids, m, quant),
               w["wte"].astype(jnp.float32).T, quant) / m["logits_divisor"]


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
