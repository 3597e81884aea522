"""Plain reference: a hybrid linear-attention decoder-only language model
as Upstage's Solar Open 2 (``model_type: solar_open2``;
huggingface.co/upstage/Solar-Open2-250B), forward pass only, in
straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no cache, no batching, no chunks: one sequence, every position
against the whole context, the delta rule TOKEN BY TOKEN.  ``x`` is the
residual stream, ``n(.)`` RMSNorm at ``rms_norm_eps`` with a plain gain,
every sublayer ``x <- x + f(n(x))`` (pre-norm, no sandwich).  A published
layer is a mixer sublayer then an expert sublayer: two pattern layers.
Published layer ``i`` has a GQA mixer if ``i in gqa_layers`` (``i % 4 ==
0``), else a KDA mixer.  A final RMSNorm and an untied head; a token
embedding with no position signal of any kind (``use_rope: false``).

- ``*`` GQA, no position signal: ``q = h W_q`` (H heads x d), ``k = h W_k``,
  ``v = h W_v`` (KV heads x d), causal softmax at ``d^-1/2``, query head j
  reading K/V head ``j // (H / KV)``; ``o = attn * sigmoid(h W_g)`` with
  ``W_g`` [E, H d] (``use_gqa_gate``: elementwise, from the sublayer's
  normed input); ``y = o W_o``.  No bias, no q/k norm.
- ``K`` KDA (Kimi Delta Attention, arXiv:2510.26692; the delta rule
  arXiv:2406.06484, gated arXiv:2412.06464), H heads, d keys and values:
  ``q~, k~, v~ = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h
  W_v))``, each through its own depthwise causal convolution of
  ``short_conv_kernel_size`` taps over the sequence (no bias);
  ``q = q~ / sqrt(|q~|^2 + 1e-6) * d^-1/2``, ``k = k~ / sqrt(|k~|^2 +
  1e-6)`` per head, ``v = v~``; the decay per head AND key channel
  ``g = -exp(A_log_h) * softplus((h W_fa) W_fb + dt_bias)``, ``alpha =
  exp(g)`` in (0, 1); ``beta = 2 sigmoid(h W_beta)`` (the 2 is
  ``kda_allow_neg_eigval``: beta in (0, 2) lets ``I - beta k k^T`` have an
  eigenvalue in (-1, 1)); per head, state ``S`` [d, d] (keys x values),
  ``S_0 = 0``, a scan over tokens::

      S' = Diag(alpha_t) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  ``y = (RMSNorm_d(o) * w_o * sigmoid((h W_ga) W_gb + b_g)) W_o``: the norm
  over each head's d values with one gain vector [d].
- ``E`` experts, every layer: ``s = sigmoid(h W_r)`` in float32 over ALL
  the published experts; the top-k of ``s + b`` (``b`` chooses, it does not
  weigh) are chosen; weights ``s_e / sum_chosen s`` (``norm_topk_prob``) x
  ``routed_scaling_factor``; expert e is ``(silu(h G_e) * (h U_e)) D_e``;
  plus ONE shared expert of the same form and width over every token,
  weight 1.  The experts are a loop.

**Assumed** (the configuration file lists each): ``kda_use_full_proj:
false`` = the two low-rank pairs at rank ``head_dim``; the convolutions
have no bias; the eps values; values as wide as keys; the GQA gate
elementwise from the normed input; sigmoid scores with a selection bias
and ``n_group = topk_group = 1``.

**The share.**  The configuration states a deployment in which a device
holds experts ``[lo, hi)`` of every expert sublayer and the first
``vocab_size`` ids; this reference is GIVEN the same share
(``moe_held``): it routes over all experts, adds the held ones' part and
the shared expert, and leaves out what the absent experts would have
added -- in the program and here alike, that partial result goes on to
the next layer.  ``moe_mixer(..., held=(lo, hi))`` computes any share, so
the shares can be added up against the uncut layer.

It imports nothing of the program, makes its own weights from the seed,
and keeps them in the type they are served in: a layer (an expert, inside
the loop over experts) is upcast to float32 when it is reached, and
attention runs a K/V head at a time, so 3.3 B parameters fit one chip
beside the reference's own activations.
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
    "the published modeling code runs KDA through fused chunked kernels and "
    "keeps a convolution cache and a recurrent state for decoding; this "
    "reference has neither: the recurrence is a scan over tokens from a zero "
    "state, so it is what chunks and caches must reproduce",
    "the experts are a loop over the held experts, each applied to every "
    "token and weighted by the router's (mostly zero) weight; the published "
    "code gathers each expert's tokens first -- the same sum",
    "the router's scores, its top-k and its weights, the decay and beta are "
    "float32 whatever precision the control runs the matrices in",
    "weights drawn by the benchmark from --seed in bf16; the recurrent state "
    "is float32",
]

KINDS = {"*": "attn", "E": "moe", "K": "kda"}
_PROGRAM = {  # reference leaf -> the program's leaf
    "g": "ln_g", "w_g": "w_ogate", "bias": "router_bias", "up": "w_in",
    "gate": "w_gate", "down": "w_out", "shared_up": "shared_in",
    "shared_gate": "shared_gate", "shared_down": "shared_out",
    "w_fa": "decay_a", "w_fb": "decay_b", "w_ga": "gate_a", "w_gb": "gate_b",
    "b_g": "gate_bias", "w_o": "norm_g"}


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


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights on the device from the seed, in the type they are
    served in; ``layers`` is a list of the layers' own trees in pattern
    order.  Matrices are unit-gain normal (std fan_in^-0.5); the matrices
    that write to the residual stream (``wo``, the experts' ``down``) are
    scaled by num_layers^-1/2.  Norm gains are drawn around 1.  What
    ``m["init"]`` sets, and why, is in the configuration file's
    ``assumed.init``: the router's gain and selection bias, the time-step
    range the decay is drawn from (``alpha`` log-uniform in about 0.9 --
    0.999), A in [a_min, a_max], the spread of beta's logit (beta away
    from 1) and the output gate's bias (away from 0)."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    hd = m["head_dim"]
    h, hk = m["num_heads"] * hd, m["kv_heads"] * hd
    nk, kc = m["kda_heads"], m["kda_conv"]
    dk = nk * hd
    lo, hi = m.get("moe_held") or (0, m["moe_experts"])
    held, n_exp, shared = hi - lo, m["moe_experts"], m["moe_shared_dim"]
    init = m.get("init", {})
    out = m["num_layers"] ** -0.5
    gain_std = float(init.get("gain_std", 0.1))
    dt_lo, dt_hi = (float(init.get("time_step_min", 1e-3)),
                    float(init.get("time_step_max", 1e-1)))
    a_lo, a_hi = (float(init.get("a_min", 0.5)), float(init.get("a_max", 2.0)))

    def norm(shape, std, mean=0.0):
        return lambda key: (mean + std * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    def dt_bias(key):
        dt = jnp.exp(jax.random.uniform(
            key, (dk,), jnp.float32, math.log(dt_lo), math.log(dt_hi)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key):
        return jnp.log(jax.random.uniform(key, (nk,), jnp.float32, a_lo,
                                          a_hi)).astype(dtype)

    gain = lambda shape: norm(shape, gain_std, 1.0)
    leaves = {
        "attn": {"g": gain((e,)), "wq": norm((e, h), e ** -0.5),
                 "wk": norm((e, hk), e ** -0.5),
                 "wv": norm((e, hk), e ** -0.5),
                 "w_g": norm((e, h), e ** -0.5),
                 "wo": norm((h, e), h ** -0.5 * out)},
        "moe": {"g": gain((e,)),
                "router": norm((e, n_exp), e ** -0.5
                               * float(init.get("router_gain", 1.0))),
                "bias": norm((n_exp,), float(init.get("router_bias_std",
                                                      0.01))),
                "up": norm((held, e, f), e ** -0.5),
                "gate": norm((held, e, f), e ** -0.5),
                "down": norm((held, f, e), f ** -0.5 * out),
                "shared_up": norm((e, shared), e ** -0.5),
                "shared_gate": norm((e, shared), e ** -0.5),
                "shared_down": norm((shared, e), shared ** -0.5 * out)},
        "kda": {"g": gain((e,)), "wq": norm((e, dk), e ** -0.5),
                "wk": norm((e, dk), e ** -0.5),
                "wv": norm((e, dk), e ** -0.5),
                "conv_w": norm((kc, 3 * dk), kc ** -0.5),
                "w_fa": norm((e, hd), e ** -0.5),
                "w_fb": norm((hd, dk), hd ** -0.5
                             * float(init.get("decay_gain", 0.5))),
                "a_log": a_log, "dt_bias": dt_bias,
                "w_beta": norm((e, nk), e ** -0.5
                               * float(init.get("beta_gain", 1.5))),
                "w_ga": norm((e, hd), e ** -0.5),
                "w_gb": norm((hd, dk), hd ** -0.5),
                "b_g": norm((dk,), float(init.get("gate_bias_std", 0.5)),
                            float(init.get("gate_bias_mean", 0.5))),
                "w_o": gain((hd,)),
                "wo": norm((dk, e), dk ** -0.5 * out)},
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
    """h [T, E] normed -> [T, E]; l: one layer's leaves.  One K/V head
    (and the query heads that read it) at a time: the scores of all heads
    at once would be H x T x T."""
    l = _f32(l)
    t = h.shape[0]
    nh, kv, hd = m["num_heads"], m["kv_heads"], m["head_dim"]
    q = _mm(h, l["wq"], quant).reshape(t, kv, nh // kv, hd)
    k = _mm(h, l["wk"], quant).reshape(t, kv, hd)
    v = _mm(h, l["wv"], quant).reshape(t, kv, hd)
    mask = jnp.tril(jnp.ones((t, t), bool))

    def group(x):
        q, k, v = x                     # [T, R, D], [T, D], [T, D]
        s = jnp.einsum("qrd,kd->rqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", p, v, precision=HI)

    a = lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                        v.transpose(1, 0, 2)))       # [KV, T, R, D]
    a = a.transpose(1, 0, 2, 3).reshape(t, nh * hd)
    if m.get("attn_gate"):
        a = a * jax.nn.sigmoid(_mm(h, l["w_g"], quant))
    return _mm(a, l["wo"], quant)


def route(l: dict, h, m: dict):
    """The router in float32: (chosen ids [T, k], weights [T, k]) over
    ALL experts."""
    s = jax.nn.sigmoid(jnp.dot(h, l["router"].astype(jnp.float32),
                               precision=HI))
    _, idx = lax.top_k(s + l["bias"].astype(jnp.float32), m["moe_top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * m["moe_scale"]


def moe_mixer(l: dict, h, m: dict, quant=None, held=None, shared=True):
    """h [T, E] normed -> [T, E]: the part of the layer's result that
    experts ``held`` = (lo, hi) give (default: the configuration's share;
    ``l["up"]`` / ``l["gate"]`` / ``l["down"]`` hold exactly those
    experts), plus the shared expert when ``shared``."""
    lo, hi = held or m.get("moe_held") or (0, m["moe_experts"])
    idx, w = route(l, h, m)
    # [T, X]: the weight each expert has for each token (0: not chosen)
    comb = jnp.sum(w[..., None] * (idx[..., None] == jnp.arange(
        m["moe_experts"])), axis=1)
    f32 = jnp.float32

    def swiglu(up, gate, down):
        a = jax.nn.silu(_mm(h, gate.astype(f32), quant)) \
            * _mm(h, up.astype(f32), quant)
        return _mm(a, down.astype(f32), quant)

    def expert(y, e):
        up, gate, down, c = e       # one expert, upcast as it is reached
        return y + c[:, None] * swiglu(up, gate, down), None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (l["up"], l["gate"], l["down"], comb[:, lo:hi].T))
    if shared:
        y = y + swiglu(l["shared_up"], l["shared_gate"], l["shared_down"])
    return y


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token from a zero state.  q, k, v, g
    [T, H, d] (g the log of the decay, per key channel), beta [T, H].
    Returns (o [T, H, d], the state [H, d, d] after the last token)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        pred = jnp.einsum("hkv,hk->hv", s, k_t, precision=HI)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - pred),
                           precision=HI)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HI)

    h, d = q.shape[1:]
    s_last, o = lax.scan(step, jnp.zeros((h, d, d)), (q, k, v, g, beta))
    return o, s_last


def kda_mixer(l: dict, h, m: dict, quant=None, with_state=False):
    """h [T, E] normed -> [T, E] (and, ``with_state``, the state [H, d,
    d] after the last token and the last K-1 inputs of the convolutions
    over q | k | v)."""
    l = _f32(l)
    t = h.shape[0]
    nk, hd, kc = m["kda_heads"], m["head_dim"], m["kda_conv"]
    qkv = jnp.concatenate([_mm(h, l[n], quant) for n in ("wq", "wk", "wv")],
                          axis=-1)
    pad = jnp.concatenate([jnp.zeros((kc - 1, qkv.shape[1])), qkv], axis=0)
    conv = sum(pad[j:j + t] * l["conv_w"][j] for j in range(kc))
    q, k, v = (x.reshape(t, nk, hd)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q, k = unit(q) * hd ** -0.5, unit(k)
    dt = _mm(_mm(h, l["w_fa"], quant), l["w_fb"], quant) + l["dt_bias"]
    g = -jnp.exp(l["a_log"])[:, None] * jax.nn.softplus(dt).reshape(t, nk, hd)
    beta = 2.0 * jax.nn.sigmoid(_mm(h, l["w_beta"], quant))      # [T, H]

    o, s_last = delta_rule(q, k, v, g, beta)
    o = _rms(o, l["w_o"], m["norm_eps"]).reshape(t, nk * hd)
    gate = jax.nn.sigmoid(_mm(_mm(h, l["w_ga"], quant), l["w_gb"], quant)
                          + l["b_g"])
    out = _mm(o * gate, l["wo"], quant)
    return (out, s_last, pad[t:]) if with_state else out


_MIXERS = {"attn": attention_mixer, "moe": moe_mixer, "kda": kda_mixer}


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
