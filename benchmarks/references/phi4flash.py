"""Plain reference: a decoder-hybrid-decoder language model as
Microsoft's Phi-4-mini-flash-reasoning (``model_type: phi4flash``; the
SambaY architecture of arXiv:2507.06607), forward pass only, in
straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no cache, no ring, no batching, no chunks, no last-token
shortcut: one sequence, EVERY layer over EVERY position.

- ``x0 = wte[ids]``; no position signal of any kind.
- the stack is a pattern of layers, each ONE mixer behind a LayerNorm
  (gain and bias) and a plain residual add: ``x <- x + mixer(LN(x))``.
  The published layer is a mixer then a SwiGLU MLP; here each is an entry
  of its own, which is the same arithmetic.
- ``-`` the MLP: ``(silu(h W_g) * (h W_u)) W_d``, no bias.
- ``S`` Mamba-1: ``[x | z] = h W_in``; ``x <- silu(conv(x) + b_c)``
  (depthwise, causal, K taps); ``[delta | B | C] = x W_x``; ``dt =
  softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; the recurrence,
  written as the recurrence (a scan over tokens from a zero state)
  ``h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = h_t C_t +
  D x_t``; out ``= (y * silu(z)) W_out``.  ``y`` — before the gate — is
  the MEMORY the later ``G`` layers read.
- ``W`` attention over a window: a query sees itself and the ``window`` -
  1 positions before it (a banded mask over the whole T x T scores).
- ``*`` attention over everything before.
- ``G`` gated memory unit: ``(m * silu(h W_1)) W_2`` with ``m`` the
  memory of the nearest ``S`` layer before it, same position.
- ``X`` cross attention: ``q = h W_q + b_q`` only; K and V are those the
  nearest ``*`` layer before it computed from ITS input; causal.
- every attention is DIFFERENTIAL: biased q | k | v projections; query
  heads 2p, 2p+1 are pair p's q1, q2; K/V heads 2r, 2r+1 pair r's k1, k2
  and v1, v2; query pair p reads K/V pair ``p // (H / KV)``.  The four
  products of a pair written out::

      s1 = softmax(q1 k1^T / sqrt(D)),  s2 = softmax(q2 k2^T / sqrt(D))
      a1 = [s1 v1 | s1 v2],             a2 = [s2 v1 | s2 v2]
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
      out_p  = RMSNorm_2D(a1 - lambda a2) * g * (1 - lambda_init)

  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``, ``i`` the published layer's
  index (the MLP entries before this one); then ``W_o`` with bias.
- ``logits = LN(x) wte^T`` (tied embedding).

The weights are one tree a layer, in the type they are served in; a layer
is upcast to float32 when it is reached, and the walk is a Python loop
over one small jitted function a KIND of layer, so what is compiled does
not grow with the depth.  ``A_log`` is kept ``[d_state, d_inner]`` (the
state's layout here and in the program).  It imports nothing of the
program and makes its own weights from the seed.
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
    "the published modeling code keeps K/V, convolution and SSM caches, "
    "serves the window layers from a sliding cache and, in prefill, runs "
    "the cross-decoder for the last token only; this reference has none of "
    "it: every layer runs over every position from a zero state, a banded "
    "mask is the window, so it is what caches, rings and the last-token "
    "walk must reproduce",
    "the published decoder layer is a mixer followed by a SwiGLU MLP, each "
    "behind its own LayerNorm and residual add; here the two are two "
    "entries of the layer pattern (S- / W- / *- / G- / X-): the same "
    "arithmetic in the same order",
    "the published code fuses q | k | v into one biased projection; here "
    "they are three matrices and three biases: the same numbers",
    "the recurrent state is float32 here and in the program's pool, and "
    "A_log is stored [d_state, d_inner]; the published code keeps the "
    "model's dtype for its caches and [d_inner, d_state]",
    "weights drawn by the benchmark from --seed in bf16; nothing of the "
    "published checkpoint is read",
]

KINDS = {"*": "attn", "-": "mlp", "S": "mamba1", "W": "window", "G": "gmu",
         "X": "cross"}
_PROGRAM = {"g": "ln_g", "b": "ln_b", "gate": "w_gate", "up": "w_up",
            "down": "w_out", "w1": "gmu_in", "w2": "gmu_out",
            "lq1": "lambda_q1", "lk1": "lambda_k1", "lq2": "lambda_q2",
            "lk2": "lambda_k2", "subln": "subln_g"}


def program_tree(weights: dict) -> dict:
    """The weights under the names the program's pytree gives them (the
    same arrays: nothing is copied), one tree a layer."""
    return {"embed": weights["wte"], "ln_f_g": weights["g_f"],
            "ln_f_b": weights["b_f"],
            "blocks": [{_PROGRAM.get(k, k): v for k, v in layer.items()}
                       for layer in weights["layers"]]}


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights on the device from the seed, in the type they are
    served in; ``layers`` is a list of one tree a layer.

    The tied embedding has deviation ``wte_std`` (default ``E^-1/2``), so
    the logits — a unit-RMS state against its rows — have unit spread
    over the vocabulary, and the stream starts SMALL (0.02 at the
    published width): every branch reads a LayerNorm of the stream and
    writes about unit RMS x ``out_gain``, so after a handful of layers
    the embedding is a fraction of a percent of the stream and the logit
    of a position's own input token (its cosine with the stream x
    sqrt(E)) is noise, not the winner.  Every matrix is unit-gain normal
    (std fan_in^-0.5): the scores q k^T / sqrt(D) have unit spread as
    they are.  Norm gains, the pair norm's gain and D are drawn around 1,
    the biases (norms, projections, conv) small and non-zero, so that a
    gain or a bias applied in the wrong place shows.  dt_bias is the
    inverse softplus of a time step drawn log-uniform in
    [time_step_min, time_step_max]; A uniform in [1, 16] per channel and
    state column; the four lambda vectors normal(0, lambda_std) (the
    Differential Transformer's 0.1)."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    hd = m["head_dim"]
    h, hk = m["num_heads"] * hd, m["kv_heads"] * hd
    di, n, r = m["mamba1_inner"], m["mamba1_state"], m["mamba1_dt_rank"]
    k_conv = m["mamba1_conv"]
    init = m.get("init", {})
    gain_std = float(init.get("gain_std", 0.1))
    bias_std = float(init.get("bias_std", 0.1))
    out = float(init.get("out_gain", 1.0))
    lam_std = float(init.get("lambda_std", 0.1))
    dt_lo, dt_hi = (float(init.get("time_step_min", 1e-3)),
                    float(init.get("time_step_max", 1e-1)))

    def norm(shape, std, mean=0.0):
        return lambda key: (mean + std * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    def dt_bias(key):
        dt = jnp.exp(jax.random.uniform(
            key, (di,), jnp.float32, math.log(dt_lo), math.log(dt_hi)))
        dt = jnp.maximum(dt, float(init.get("time_step_floor", 1e-4)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key):
        return jnp.log(jax.random.uniform(
            key, (n, di), jnp.float32, 1.0, 16.0)).astype(dtype)

    gain = lambda shape: norm(shape, gain_std, 1.0)
    bias = lambda shape: norm(shape, bias_std)
    ln = {"g": gain((e,)), "b": bias((e,))}
    diff = {"lq1": norm((hd,), lam_std), "lk1": norm((hd,), lam_std),
            "lq2": norm((hd,), lam_std), "lk2": norm((hd,), lam_std),
            "subln": gain((2 * hd,))}
    q_o = {"wq": norm((e, h), e ** -0.5), "bq": bias((h,)),
           "wo": norm((h, e), h ** -0.5 * out), "bo": bias((e,))}
    k_v = {"wk": norm((e, hk), e ** -0.5), "bk": bias((hk,)),
           "wv": norm((e, hk), e ** -0.5), "bv": bias((hk,))}
    leaves = {
        "attn": {**ln, **q_o, **k_v, **diff},
        "window": {**ln, **q_o, **k_v, **diff},
        "cross": {**ln, **q_o, **diff},
        "mlp": {**ln, "gate": norm((e, f), e ** -0.5),
                "up": norm((e, f), e ** -0.5),
                "down": norm((f, e), f ** -0.5 * out)},
        "gmu": {**ln, "w1": norm((e, di), e ** -0.5),
                "w2": norm((di, e), di ** -0.5 * out)},
        "mamba1": {**ln, "in_proj": norm((e, 2 * di), e ** -0.5),
                   "conv_w": norm((k_conv, di), k_conv ** -0.5),
                   "conv_b": bias((di,)),
                   "x_proj": norm((di, r + 2 * n), di ** -0.5),
                   "dt_proj": norm((r, di), r ** -0.5),
                   "dt_bias": dt_bias, "a_log": a_log, "d": gain((di,)),
                   "out_proj": norm((di, e), di ** -0.5 * out)},
    }
    wte_std = float(init.get("wte_std", e ** -0.5))

    @functools.partial(jax.jit, static_argnums=1)
    def make_layer(key, kind):
        names = sorted(leaves[kind])
        return {name: leaves[kind][name](k)
                for name, k in zip(names, jax.random.split(key, len(names)))}

    @jax.jit
    def make_top(key):
        k0, k1, k2 = jax.random.split(key, 3)
        return {"wte": (wte_std * jax.random.normal(
                    k0, (v, e), jnp.float32)).astype(dtype),
                "g_f": gain((e,))(k1), "b_f": bias((e,))(k2)}

    keys = jax.random.split(seed_key(seed), 1 + len(m["pattern"]))
    return {**make_top(keys[0]),
            "layers": [make_layer(k, KINDS[c])
                       for k, c in zip(keys[1:], m["pattern"])]}


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


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


_f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def depth_of(m: dict, i: int) -> int:
    """The published layer entry ``i`` of the pattern belongs to: the MLP
    entries before it."""
    return m["pattern"][:i].count("-")


def attention_mixer(l: dict, h, m: dict, quant=None, window=0, kv=None,
                    depth=0):
    """h [T, E] normed -> ([T, E], (k, v)); l: one layer's leaves (float32).
    ``window`` > 0: a banded mask; ``kv``: another layer's (k, v) [T, KV,
    D] to read instead of projecting (a cross layer)."""
    t = h.shape[0]
    nh, nkv, hd = m["num_heads"], m["kv_heads"], m["head_dim"]
    rep = nh // nkv         # query pairs a K/V pair
    q = (_mm(h, l["wq"], quant) + l["bq"]).reshape(t, nh // 2, 2, hd)
    if kv is None:
        kv = ((_mm(h, l["wk"], quant) + l["bk"]).reshape(t, nkv, hd),
              (_mm(h, l["wv"], quant) + l["bv"]).reshape(t, nkv, hd))
    k, v = (a.reshape(t, nkv // 2, 2, hd) for a in kv)
    pos = jnp.arange(t)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))
    lam = (jnp.exp(jnp.sum(l["lq1"] * l["lk1"]))
           - jnp.exp(jnp.sum(l["lq2"] * l["lk2"])) + lam0)

    def pair(p):
        """Query pair p against K/V pair p // rep: [T, 2 D]."""
        qp = lax.dynamic_index_in_dim(q, p, 1, keepdims=False)      # [T, 2, D]
        kp = lax.dynamic_index_in_dim(k, p // rep, 1, keepdims=False)
        vp = lax.dynamic_index_in_dim(v, p // rep, 1, keepdims=False)

        def soft(j):    # softmax(q_j k_j^T / sqrt(D))
            s = jnp.dot(qp[:, j], kp[:, j].T, precision=HI) * hd ** -0.5
            return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)

        s1, s2 = soft(0), soft(1)
        v1, v2 = vp[:, 0], vp[:, 1]
        a1 = jnp.concatenate([jnp.dot(s1, v1, precision=HI),
                              jnp.dot(s1, v2, precision=HI)], -1)
        a2 = jnp.concatenate([jnp.dot(s2, v1, precision=HI),
                              jnp.dot(s2, v2, precision=HI)], -1)
        y = a1 - lam * a2
        y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + m["norm_eps"])
        return y * l["subln"] * (1.0 - lam0)

    a = lax.map(pair, jnp.arange(nh // 2))                  # [pairs, T, 2 D]
    a = a.transpose(1, 0, 2).reshape(t, nh * hd)
    return _mm(a, l["wo"], quant) + l["bo"], kv


def mlp_mixer(l: dict, h, m: dict, quant=None):
    """h [T, E] normed -> [T, E]: the gated MLP, no bias."""
    a = jax.nn.silu(_mm(h, l["gate"], quant)) * _mm(h, l["up"], quant)
    return _mm(a, l["down"], quant)


def gmu_mixer(l: dict, h, mem, m: dict, quant=None):
    """h [T, E] normed, mem [T, d_inner] the memory -> [T, E]."""
    return _mm(mem * jax.nn.silu(_mm(h, l["w1"], quant)), l["w2"], quant)


def mamba1_mixer(l: dict, h, m: dict, quant=None, with_state=False):
    """h [T, E] normed -> ([T, E], y [T, d_inner] before the gate) (and,
    ``with_state``, the state [N, D] after the last token and the last
    K-1 conv inputs)."""
    t = h.shape[0]
    n, r, k = m["mamba1_state"], m["mamba1_dt_rank"], m["mamba1_conv"]
    x, z = jnp.split(_mm(h, l["in_proj"], quant), 2, axis=-1)
    pad = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x], axis=0)
    x = jax.nn.silu(sum(pad[j:j + t] * l["conv_w"][j] for j in range(k))
                    + l["conv_b"])
    dt, b, c = jnp.split(_mm(x, l["x_proj"], quant), [r, r + n], axis=-1)
    dt = jax.nn.softplus(_mm(dt, l["dt_proj"], quant) + l["dt_bias"])
    a = -jnp.exp(l["a_log"])                                  # [N, D]

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t[None, :] * a) * s \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + l["d"] * x_t

    s_last, y = lax.scan(step, jnp.zeros_like(a), (x, b, c, dt))
    out = _mm(y * jax.nn.silu(z), l["out_proj"], quant)
    return (out, y, s_last, pad[t:]) if with_state else (out, y)


@functools.lru_cache(maxsize=None)
def _step(kind: str, m_json: str, quant):
    """One entry of the pattern, jitted once a kind: (layer, x, memory,
    the full-attention layer's (k, v), depth) -> the same three, moved on."""
    m = json.loads(m_json)

    def f(l, x, mem, kv, depth):
        l = _f32(l)
        h = _ln(x, l["g"], l["b"], m["norm_eps"])
        if kind == "mlp":
            y = mlp_mixer(l, h, m, quant)
        elif kind == "gmu":
            y = gmu_mixer(l, h, mem, m, quant)
        elif kind == "mamba1":
            y, mem = mamba1_mixer(l, h, m, quant)
        elif kind == "cross":
            y, _ = attention_mixer(l, h, m, quant, kv=kv, depth=depth)
        elif kind == "window":
            y, _ = attention_mixer(l, h, m, quant, m["attn_window"],
                                   depth=depth)
        else:
            y, kv = attention_mixer(l, h, m, quant, depth=depth)
        return x + y, mem, kv

    return jax.jit(f)


def hidden_states(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> h [T, E] float32: the final LayerNorm's output
    (causal)."""
    mj = json.dumps(m, sort_keys=True)
    x = w["wte"][ids].astype(jnp.float32)
    # what the walk hands on, zeros until a layer makes it (one shape
    # throughout: a kind compiles once)
    t = x.shape[0]
    mem = jnp.zeros((t, m["mamba1_inner"]), jnp.float32)
    kv = (jnp.zeros((t, m["kv_heads"], m["head_dim"]), jnp.float32),) * 2
    for i, (ch, l) in enumerate(zip(m["pattern"], w["layers"])):
        x, mem, kv = _step(KINDS[ch], mj, quant)(
            l, x, mem, kv, jnp.float32(depth_of(m, i)))
    return _ln(x, w["g_f"].astype(jnp.float32),
               w["b_f"].astype(jnp.float32), m["norm_eps"])


def logits_fn(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> logits [T, V] float32 (the tied embedding's
    transpose)."""
    return _mm(hidden_states(w, ids, m, quant),
               w["wte"].astype(jnp.float32).T, quant)


_HEAD_ROWS = 256    # positions the head reads at once: [256, V] float32


@functools.lru_cache(maxsize=None)
def _gap_head(quant):
    def f(wte, h, h_low, targets):
        """h, h_low [P, E] the reference's and the lower precision's final
        states at the positions that predict ``targets`` [P]: per position
        how far the target's logit lies below the float32 reference's
        best; the same for the token the lower precision puts first; the
        reference's own margin.  A block of positions at a time: [P, V]
        never exists whole."""
        head = wte.astype(jnp.float32).T

        def block(inp):
            hb, lb, tb = inp
            ref = jnp.dot(hb, head, precision=HI)
            top2 = lax.top_k(ref, 2)[0]
            best, margin = top2[:, 0], top2[:, 0] - top2[:, 1]
            at = lambda ids: jnp.take_along_axis(ref, ids[:, None], 1)[:, 0]
            served = best - at(tb)
            if quant is None:
                return served, served, margin
            low = jnp.argmax(_mm(lb, head, quant), -1)
            return served, best - at(low), margin

        p = h.shape[0]
        rows = lambda a: a.reshape(p // _HEAD_ROWS, _HEAD_ROWS, *a.shape[1:])
        out = lax.map(block, (rows(h), rows(h_low), rows(targets)))
        return tuple(a.reshape(p) for a in out)

    return jax.jit(f)


def served_gaps(m: dict, weights: dict, requests, pad_to: int,
                quant=None) -> dict:
    """``requests``: [(prompt ids, served ids), ...].  One reference pass
    over each prompt with its served tokens.  Returns the per-token gaps
    of the served tokens (``served``) and, with ``quant``, of the tokens
    the lower precision would have put first at the same positions
    (``control``), and the reference's own margin between its best and
    second token there (``margin``: how close the ties are).  The head
    (200,064 rows at the published size) reads only the positions that
    predict a served token."""
    import numpy as np

    head = _gap_head(quant)
    served, control, margin = [], [], []
    with jax.default_matmul_precision("highest"):
        for prompt, tokens in requests:
            seq = list(prompt) + list(tokens)
            n, p = len(seq), len(prompt)
            ids = np.zeros((pad_to,), np.int32)
            ids[:n] = seq
            # position p-1 predicts the first served token
            span = -(-len(tokens) // _HEAD_ROWS) * _HEAD_ROWS
            at = np.minimum(np.arange(p - 1, p - 1 + span), pad_to - 1)
            targets = np.zeros((span,), np.int32)
            targets[:len(tokens)] = tokens
            ids = jnp.asarray(ids)
            h = hidden_states(weights, ids, m)[at]
            h_low = h if quant is None else hidden_states(
                weights, ids, m, quant)[at]
            s, c, g = jax.device_get(head(weights["wte"], h, h_low,
                                          jnp.asarray(targets)))
            served.extend(float(x) for x in s[:len(tokens)])
            control.extend(float(x) for x in c[:len(tokens)])
            margin.extend(float(x) for x in g[:len(tokens)])
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
