"""Plain reference: a mixture-of-experts language model as Zyphra's ZAYA1
family (``model_type: zaya``; huggingface.co/Zyphra/ZAYA1-8B; the
attention is Compressed Convolutional Attention, arXiv:2510.04476; the
router and the residual scaling are the ZAYA1 report's, arXiv:2511.17127),
forward pass only, in straightforward jax.numpy float32 at
``precision=HIGHEST``.

No kernels, no cache, no state, no batching: one sequence, every position
against the whole context, every convolution an explicit sum of shifted
copies.  A published layer l is an attention sublayer then an expert
sublayer (the pattern ``*E``), each closing with a SCALED residual add,
``x <- (s_x * x + b_x) + (s_y * y + b_y)`` with four learned vectors of
its own; a final RMSNorm; the head is the embedding, transposed.  With
``d = head_dim``, ``H`` query heads over ``KV`` K/V heads:

*Attention (CCA).*  ``h = RMSNorm(x)``; ``q~ = h W_q`` [H, d], ``k~ = h
W_k`` [KV, d]; ``u = [q~ ; k~]`` (C = (H + KV) d channels).
  1. depthwise over the sequence: ``c0_t = sum_{j < k0} a_j * u_{t-j} +
     b0`` (zeros left of the sequence);
  2. a head's own matrix a tap: ``c1_t = sum_{j < k1} c0_{t-j} A_j + b1``,
     ``A_j`` block-diagonal over the H + KV heads, d x d a head (``c0``
     too is zero left of the sequence);
  3. the mean of the projections added back: ``q = c1_q + (q~ + k~ of its
     K/V head) / 2``, ``k = c1_k + (mean of its group's query heads' q~ +
     k~) / 2``;
  4. ``q <- sqrt(d) q / |q|``, ``k <- tau sqrt(d) k / |k|`` a head (tau:
     one learned scalar a K/V head; ``|x|^2 = sum x^2 + d eps``), then
     rotate-half RoPE on the FIRST ``partial_rotary_factor x d`` dims;
  5. ``v_t = [h_t W_v^a ; h_{t-1} W_v^b]``: the first half of the K/V
     heads is this token's projection, the second the PREVIOUS token's
     (zero at t = 0);
  6. causal softmax(q k^T / sqrt(d)) v, query head i reading K/V head
     i // (H / KV); ``y = a W_o``.

*Experts.*  ``h = RMSNorm(x)``; the router keeps a state of width R that
is averaged over DEPTH: ``r_l = h W_d + b_d + gamma_l * r_{l-1}`` (``r``
before the first layer is 0); ``s = W_3 gelu(W_2 gelu(W_1
RMSNorm(r_l)))`` (erf GELU, no bias); ``p = softmax(s)``; the expert is
``e = argmax(p + beta)`` (beta moves the choice only) and weighs ``p_e``,
its own probability: ``y = p_e (silu(h G_e) * (h U_e)) D_e``.  The experts
are a loop.

It imports nothing of the program, makes its own weights from the seed,
and keeps them in the type they are served in: a layer (an expert, inside
the loop over experts) is upcast to float32 when it is reached, and the
head's logits are taken a chunk of positions at a time (262,272 ids x
2,048 positions would be 2.1 GB).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
HEAD_CHUNK = 256        # positions whose logits exist at one time

departures = [
    "the published modeling code keeps a convolution state and a shifted "
    "value beside the K/V cache for decoding; this reference has neither: "
    "every convolution is a sum of shifted copies of the whole sequence, "
    "which is what prefill, cache and state must reproduce",
    "the experts are a loop over all experts, each applied to every token "
    "and weighted by the router's (mostly zero) weight; the published code "
    "gathers each expert's tokens first -- the same sum",
    "the router (its down-projection, depth average, norm, MLP, softmax "
    "and argmax) is float32 whatever precision the control runs the "
    "matrices in",
    "weights drawn by the benchmark from --seed in bf16; nothing of the "
    "published checkpoint is read",
    "the catalog describes the family as 'residual-scaled MoD'; config.json "
    "has no key that sizes a depth router, so none is built: every token "
    "passes every layer",
]

_ATTN = {"g": "ln_g", "conv0_b": "cca_conv0_b", "conv1_b": "cca_conv1_b",
         "tau": "cca_temp"}
_MOE = {"g": "ln_g", "r_down": "router_down", "r_down_b": "router_down_b",
        "gamma": "router_decay", "r_norm_g": "router_norm_g",
        "w1": "router_w1", "w2": "router_w2", "w3": "router",
        "beta": "router_bias", "gate": "w_gate", "up": "w_in",
        "down": "w_out"}
_RES = {"s_x": "res_x_g", "b_x": "res_x_b", "s_y": "res_y_g",
        "b_y": "res_y_b"}


def program_tree(weights: dict) -> dict:
    """The weights under the names and in the arrangement the program's
    pytree gives them: a list of per-layer trees, attention then experts
    for each layer held; the two value matrices side by side as one
    ``wv``; the convolutions' taps in the program's order (its LAST tap
    is the current token, here it is ``j = 0``).  The large arrays are the
    same arrays; only the small ones are rearranged."""
    blocks = []
    for layer in weights["layers"]:
        a = layer["attn"]
        blocks.append({
            **{_ATTN.get(k, _RES.get(k, k)): v for k, v in a.items()
               if k not in ("wv_a", "wv_b", "conv0_a", "conv1_A")},
            "wv": jnp.concatenate([a["wv_a"], a["wv_b"]], axis=1),
            "cca_conv0_w": a["conv0_a"][::-1],
            "cca_conv1_w": a["conv1_A"][::-1]})
        blocks.append({_MOE.get(k, _RES.get(k, k)): v
                       for k, v in layer["moe"].items()})
    return {"embed": weights["wte"], "ln_f_g": weights["g_f"],
            "blocks": blocks}


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def depth(m: dict) -> int:
    """Layers held: the pattern is ``*E`` a layer."""
    return m["num_layers"] // 2


def init_weights(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights on the device from the seed, in the type they are
    served in; ``layers`` a list of {"attn": ..., "moe": ...}.  Matrices
    are unit-gain normal (std fan_in^-0.5); the matrices that write to the
    residual stream (``wo``, the experts' ``down``) are scaled by (2 x
    layers held)^-1/2; the embedding, which is the head too, is normal(0,
    E^-1/2), so logits have unit spread and the stream is what the layers
    computed from the context.  Everything a misplaced term would hide
    behind is drawn away from its neutral value: norm gains and the four
    residual scales normal(1, ``gain_std``), the residual biases, the
    convolutions' biases, the router's bias normal(0, ``bias_std``), its
    choice bias beta normal(0, ``beta_std``) (small: it must not choose
    the experts), the depth average gamma normal(``gamma_mean``, 0.1), k's
    temperature tau normal(``temp_mean``, 0.1).  The router's second and
    third matrices are drawn with columns that sum to zero: a GELU's
    output has a positive mean, which a plain draw turns into a constant
    preference for a few experts (64% of the (layer, expert) pairs
    touched a step and the busiest at 8.6 x the mean on the chip, PR 38);
    a trained router's balancing takes that out, and so does this."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    hd, nh, kv = m["head_dim"], m["num_heads"], m["kv_heads"]
    k0, k1 = m["cca_taps"]
    g, c = nh + kv, (nh + kv) * hd
    v_half = kv * hd // 2
    x, r = m["moe_experts"], m["moe_router_hidden"]
    init = m.get("init", {})
    out = (2 * depth(m)) ** -0.5
    gain_std = float(init.get("gain_std", 0.1))
    bias_std = float(init.get("bias_std", 0.02))

    def norm(shape, std, mean=0.0):
        return lambda key: (mean + std * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    def centred(shape, std):
        """Normal(0, std) with every COLUMN summing to zero: what the
        matrix makes of a vector of equal entries is nothing."""
        def draw(key):
            w = std * jax.random.normal(key, shape, jnp.float32)
            return (w - jnp.mean(w, axis=0, keepdims=True)).astype(dtype)
        return draw

    gain = lambda shape: norm(shape, gain_std, 1.0)
    bias = lambda shape: norm(shape, bias_std)

    def res(branch):
        """The four vectors that close a sublayer; the branch's scale is
        drawn around ``branch`` (1: as every other gain)."""
        return {"s_x": gain((e,)), "b_x": bias((e,)),
                "s_y": norm((e,), gain_std * branch, branch),
                "b_y": bias((e,))}

    leaves = {
        "attn": {"g": gain((e,)), "wq": norm((e, nh * hd), e ** -0.5),
                 "wk": norm((e, kv * hd), e ** -0.5),
                 "wv_a": norm((e, v_half), e ** -0.5),
                 "wv_b": norm((e, v_half), e ** -0.5),
                 "wo": norm((nh * hd, e), (nh * hd) ** -0.5 * out),
                 "conv0_a": norm((k0, c), k0 ** -0.5),
                 "conv0_b": bias((c,)),
                 "conv1_A": norm((k1, g, hd, hd), (k1 * hd) ** -0.5),
                 "conv1_b": bias((c,)),
                 "tau": norm((kv,), 0.1, float(init.get("temp_mean", 1.0))),
                 **res(1.0)},
        "moe": {"g": gain((e,)), "r_down": norm((e, r), e ** -0.5),
                "r_down_b": bias((r,)),
                "gamma": norm((r,), 0.1, float(init.get("gamma_mean", 0.5))),
                "r_norm_g": gain((r,)),
                "w1": norm((r, r), r ** -0.5),
                "w2": centred((r, r), r ** -0.5),
                "w3": centred((r, x), r ** -0.5
                              * float(init.get("router_gain", 1.0))),
                "beta": norm((x,), float(init.get("beta_std", 0.01))),
                "gate": norm((x, e, f), e ** -0.5),
                "up": norm((x, e, f), e ** -0.5),
                "down": norm((x, f, e), f ** -0.5 * out),
                **res(float(init.get("moe_branch_gain", 1.0)))},
    }

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 2 + 40 * depth(m)))
        return {
            "wte": norm((v, e), float(init.get("wte_std", e ** -0.5)))(
                next(ks)),
            "g_f": gain((e,))(next(ks)),
            "layers": [{half: {name: leaf(next(ks))
                               for name, leaf in leaves[half].items()}
                        for half in ("attn", "moe")}
                       for _ in range(depth(m))],
        }

    return make(seed_key(seed))


def _int8(x, axis):
    """Symmetric int8 round trip with one scale per slice along
    ``axis`` -- the control's lower precision."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(x / scale).clip(-127, 127) * scale


def _mm(x, w, quant):
    """x [..., K] @ w [..., K, N] (leading dims of w: one matrix a head)."""
    if quant == "int8":        # per-token activations, per-column weights
        x, w = _int8(x, -1), _int8(w, -2)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    if w.ndim == 2:
        return jnp.dot(x, w, precision=HI)
    return jnp.einsum("tgd,gde->tge", x, w, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


_f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def shift(x, j: int):
    """x [T, ...] -> x_{t-j}: j tokens later, zeros left of the
    sequence."""
    if j == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:j]), x[:-j]], axis=0)


def rope(x, m: dict):
    """x [T, heads, d] at positions 0..T-1: rotate-half over the first
    ``rope_fraction x d`` dims, the rest untouched."""
    t, d = x.shape[0], x.shape[-1]
    rot = int(d * m.get("rope_fraction", 1.0))
    inv = m["rope_theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv       # [T, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def cca_qkv(l: dict, h, m: dict, quant=None):
    """h [T, E] normed -> q [T, H, d], k and v [T, KV, d]: steps 1-5 of
    the module docstring; l: one attention sublayer's leaves, float32."""
    t = h.shape[0]
    nh, kv, d = m["num_heads"], m["kv_heads"], m["head_dim"]
    k0, k1 = m["cca_taps"]
    qt = _mm(h, l["wq"], quant).reshape(t, nh, d)
    kt = _mm(h, l["wk"], quant).reshape(t, kv, d)
    u = jnp.concatenate([qt, kt], axis=1).reshape(t, (nh + kv) * d)
    c0 = sum(l["conv0_a"][j] * shift(u, j) for j in range(k0)) + l["conv0_b"]
    c1 = sum(_mm(shift(c0, j).reshape(t, nh + kv, d), l["conv1_A"][j], quant)
             for j in range(k1)) + l["conv1_b"].reshape(nh + kv, d)
    rep = nh // kv
    q = c1[:, :nh] + (qt + jnp.repeat(kt, rep, axis=1)) / 2
    k = c1[:, nh:] + (qt.reshape(t, kv, rep, d).mean(axis=2) + kt) / 2

    def unit(x):
        return math.sqrt(d) * x / jnp.sqrt(
            jnp.sum(x * x, -1, keepdims=True) + d * m["norm_eps"])

    q, k = rope(unit(q), m), rope(l["tau"][:, None] * unit(k), m)
    v = jnp.concatenate([_mm(h, l["wv_a"], quant),
                         shift(_mm(h, l["wv_b"], quant), 1)], axis=-1)
    return q, k, v.reshape(t, kv, d)


def attention_mixer(l: dict, h, m: dict, quant=None):
    """h [T, E] normed -> [T, E]; l: one attention sublayer's leaves."""
    l = _f32(l)
    t = h.shape[0]
    nh, kv, d = m["num_heads"], m["kv_heads"], m["head_dim"]
    q, k, v = cca_qkv(l, h, m, quant)
    q = q.reshape(t, kv, nh // kv, d)
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HI) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(t, nh * d)
    return _mm(a, l["wo"], quant)


def route(l: dict, h, r_prev, m: dict):
    """The router in float32: (chosen expert [T], its weight [T], this
    layer's router state r [T, R]) from the normed states h [T, E] and
    the router state of the layer before (zeros before the first)."""
    f = lambda name: l[name].astype(jnp.float32)
    r = jnp.dot(h, f("r_down"), precision=HI) + f("r_down_b") \
        + f("gamma") * r_prev
    a = _rms(r, f("r_norm_g"), m["norm_eps"])
    for name in ("w1", "w2"):
        a = jax.nn.gelu(jnp.dot(a, f(name), precision=HI), approximate=False)
    p = jax.nn.softmax(jnp.dot(a, f("w3"), precision=HI), axis=-1)
    e = jnp.argmax(p + f("beta"), axis=-1)
    return e, jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0], r


def moe_mixer(l: dict, h, r_prev, m: dict, quant=None, held=None):
    """h [T, E] normed -> ([T, E], r): the part of the sublayer's result
    that experts ``held`` = (lo, hi) give (default: all), so that shares
    can be added up against the whole; and the router's state."""
    lo, hi = held or m.get("moe_held") or (0, m["moe_experts"])
    e, w, r = route(l, h, r_prev, m)
    # [T, X]: the weight each expert has for each token (0: not chosen)
    comb = w[:, None] * (e[:, None] == jnp.arange(m["moe_experts"]))

    def expert(y, ex):
        gate, up, down, c = ex      # one expert, upcast as it is reached
        a = jax.nn.silu(_mm(h, gate.astype(jnp.float32), quant)) \
            * _mm(h, up.astype(jnp.float32), quant)
        return y + c[:, None] * _mm(a, down.astype(jnp.float32), quant), None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (l["gate"][lo:hi], l["up"][lo:hi], l["down"][lo:hi],
                     comb[:, lo:hi].T))
    return y, r


def residual(l: dict, x, y):
    f = lambda name: l[name].astype(jnp.float32)
    return (f("s_x") * x + f("b_x")) + (f("s_y") * y + f("b_y"))


def hidden_states(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> h [T, E] float32: the final RMSNorm's output
    (causal)."""
    x = w["wte"][ids].astype(jnp.float32)
    r = jnp.zeros((ids.shape[0], m["moe_router_hidden"]), jnp.float32)
    g = lambda l: l["g"].astype(jnp.float32)
    for layer in w["layers"]:
        a, e = layer["attn"], layer["moe"]
        x = residual(a, x, attention_mixer(
            a, _rms(x, g(a), m["norm_eps"]), m, quant))
        y, r = moe_mixer(e, _rms(x, g(e), m["norm_eps"]), r, m, quant)
        x = residual(e, x, y)
    return _rms(x, w["g_f"].astype(jnp.float32), m["norm_eps"])


def logits_fn(w: dict, ids, m: dict, quant=None):
    """ids [T] int32 -> logits [T, V] float32 (the head is the embedding):
    for short sequences; ``served_gaps`` takes them a chunk at a time."""
    return _mm(hidden_states(w, ids, m, quant),
               w["wte"].astype(jnp.float32).T, quant)


@functools.lru_cache(maxsize=None)
def _gap_fn(m_json: str, quant):
    m = json.loads(m_json)

    def f(w, ids, targets):
        """Per position p: how far the logit of ``targets[p]`` lies below
        the float32 reference's best, at the position that predicts it;
        and the same for the token a lower precision puts first."""
        t = ids.shape[0]
        n = -(-t // HEAD_CHUNK)
        pad = n * HEAD_CHUNK - t
        head = w["wte"].astype(jnp.float32).T
        chunks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                                   ).reshape(n, HEAD_CHUNK, *a.shape[1:])
        hs = chunks(hidden_states(w, ids, m, None))
        low = chunks(hidden_states(w, ids, m, quant)) if quant else hs
        low_head = _int8(head, 0) if quant else head

        def one(c):
            h, h_low, tg = c
            ref = jnp.dot(h, head, precision=HI)
            top2 = lax.top_k(ref, 2)[0]
            best = top2[:, 0]
            gap = lambda tok: best - jnp.take_along_axis(
                ref, tok[:, None], 1)[:, 0]
            if quant is None:
                return gap(tg), gap(tg), best - top2[:, 1]
            first = jnp.argmax(jnp.dot(_int8(h_low, -1), low_head,
                                       precision=HI), -1)
            return gap(tg), gap(first), best - top2[:, 1]

        return tuple(a.reshape(-1)[:t] for a in lax.map(
            one, (hs, low, chunks(targets))))

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

    if quant not in (None, "int8"):
        raise ValueError(f"unknown precision {quant!r}")
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
