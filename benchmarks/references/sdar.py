"""Plain reference: a block-diffusion mixture-of-experts language model as
JetLM's SDAR family (``model_type: sdar_moe``;
huggingface.co/JetLM/SDAR-30B-A3B-Chat; SDAR, arXiv:2510.06303), forward
pass only, in straightforward jax.numpy float32 at ``precision=HIGHEST``.

No kernels, no cache, no batching: one sequence, every position against
the whole context.  Every layer is ``x <- x + attention(RMSNorm(x))``
then ``x <- x + experts(RMSNorm(x))`` (the pattern ``*E`` a layer: each
half a pre-norm residual branch); a final RMSNorm and an untied head.

- attention: q over ``num_heads``, k and v over ``kv_heads`` heads (query
  head h reads K/V head h // (H/KV)); RMSNorm over head_dim on every head
  of q and of k (one gain each a layer), then rotate-half RoPE at
  ``rope_theta``; softmax at head_dim^-1/2 under the mask below; no bias.
- experts: ``s = softmax(x W_r)`` in float32 over ALL experts; chosen =
  top-k of ``s``; weights = ``s[chosen] / sum s[chosen]``
  (``norm_topk_prob``); expert e is ``(silu(x G_e) * (x U_e)) D_e``; no
  shared expert, no bias.  The experts are a loop.

**What diffusion over blocks changes** is the mask and how tokens come
out, not a layer's equations.  Positions come in blocks of ``B =
block_len``:

(a) ``logits_fn``: the forward pass under the BLOCK-CAUSAL mask —
    position i sees j iff ``j // B <= i // B``.  Position p's logits
    predict position p's own token (no shift).
(b) ``denoise_logits``: a block's denoising logits without a cache, by
    the family's own training layout: ONE forward over ``[x_noisy ;
    x_clean]`` (2 x length; both halves at positions 0..T-1) in which a
    noisy query of block b sees the noisy keys of block b and the clean
    keys of blocks < b, and a clean query of block b the clean keys of
    blocks <= b.  ``x_noisy`` holds the mask token wherever the position
    was still masked at that denoising step, so one such forward per step
    index gives every block's logits at that step.

Generation (what a server does; ``generate`` below is the loop in plain
form): a block starts as mask tokens (the prompt's tail inside it is
known), each denoising pass computes the block, chooses a token at every
masked position and unmasks some of them by the policy; when none is left
a last pass over the clean block stands for its K/V.  ``served_gaps``
holds every served token against this reference's logits AT THE STEP THAT
UNMASKED IT, the block in the state it had then; it takes the served
order of unmasking (``trail["steps"]``) as given, because with drawn
weights the confidence ranking flips on rounding as the largest logit
does.

It imports nothing of the program, makes its own weights from the seed,
and keeps them in the type they are served in: a layer (an expert, inside
the loop over experts) is upcast to float32 when it is reached.
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
    "the published generate loop keeps a K/V cache of the committed blocks "
    "and runs a denoising pass over the block alone; this reference has no "
    "cache: a denoising step is one forward over [noisy ; clean], the "
    "family's training layout, which is what cache and block pass must "
    "reproduce",
    "the experts are a loop over all experts, each applied to every token "
    "and weighted by the router's (mostly zero) weight; the published code "
    "gathers each expert's tokens first -- the same sum",
    "the router's softmax, its top-k and its weights are float32 whatever "
    "precision the control runs the matrices in",
    "weights drawn by the benchmark from --seed in bf16; nothing of the "
    "published checkpoint is read, so the mask token is a row of the "
    "embedding like any other",
    "block length, denoising steps and the unmasking policy are not in the "
    "published config.json: they are the configuration's `assumed`",
]

_PROGRAM = {"g": "ln_g", "up": "w_in", "gate": "w_gate", "down": "w_out",
            "q_g": "q_norm_g", "k_g": "k_norm_g"}


def program_tree(weights: dict) -> dict:
    """The weights under the names the program's pytree gives them (the
    same arrays: nothing is copied): a list of per-layer trees, attention
    then experts for each layer held."""
    blocks = []
    for layer in weights["layers"]:
        for half in ("attn", "moe"):
            blocks.append({_PROGRAM.get(k, k): v
                           for k, v in layer[half].items()})
    return {"embed": weights["wte"], "head": weights["head"],
            "ln_f_g": weights["g_f"], "blocks": blocks}


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
    layers held)^-1/2; norm gains (the q/k norms' too) are drawn around 1
    so that a gain applied in the wrong place shows; the embedding is
    normal(0, ``init.wte_std``) and the untied head normal(0, E^-1/2), so
    logits have unit spread.  ``init.q_gain_mean`` (default 1) centres the
    q norm's gain: it is the attention's temperature.  At 1 a drawn
    model's scores have unit spread, its attention is all but uniform
    over the context, every position of a sequence reads the same average
    and — the masked positions of a block sharing one input embedding —
    generation collapses to one token a sequence; a trained model's
    attention is sharp, and the benchmark's configuration draws it so."""
    e, f, v = m["embed_dim"], m["mlp_dim"], m["vocab_size"]
    hd = m["head_dim"]
    h, hk = m["num_heads"] * hd, m["kv_heads"] * hd
    n_exp, init = m["moe_experts"], m.get("init", {})
    out = (2 * depth(m)) ** -0.5
    gain_std = float(init.get("gain_std", 0.1))

    def norm(shape, std, mean=0.0):
        return lambda key: (mean + std * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    gain = lambda shape: norm(shape, gain_std, 1.0)
    leaves = {
        "attn": {"g": gain((e,)), "wq": norm((e, h), e ** -0.5),
                 "wk": norm((e, hk), e ** -0.5),
                 "wv": norm((e, hk), e ** -0.5),
                 "wo": norm((h, e), h ** -0.5 * out),
                 "q_g": norm((hd,), gain_std,
                             float(init.get("q_gain_mean", 1.0))),
                 "k_g": gain((hd,))},
        "moe": {"g": gain((e,)), "router": norm((e, n_exp), e ** -0.5),
                "up": norm((n_exp, e, f), e ** -0.5),
                "gate": norm((n_exp, e, f), e ** -0.5),
                "down": norm((n_exp, f, e), f ** -0.5 * out)},
    }

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 3 + 16 * depth(m)))
        return {
            "wte": norm((v, e), float(init.get("wte_std", 1.0)))(next(ks)),
            "head": norm((e, v), float(init.get("head_std", e ** -0.5)))(
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
    if quant == "int8":        # per-token activations, per-column weights
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.dot(x, w, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


_f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def block_causal_mask(t: int, block: int):
    """[T, T] bool: query i sees key j iff j // block <= i // block."""
    b = jnp.arange(t) // block
    return b[:, None] >= b[None, :]


def noisy_clean_mask(t: int, block: int):
    """[2T, 2T] bool over ``[noisy ; clean]``: a noisy query of block b
    sees the noisy keys of block b and the clean keys of blocks < b; a
    clean query of block b the clean keys of blocks <= b and no noisy
    key."""
    b = jnp.arange(t) // block
    same, before = b[:, None] == b[None, :], b[:, None] > b[None, :]
    return jnp.block([[same, before],
                      [jnp.zeros((t, t), bool), same | before]])


def _rope(x, positions, theta):
    """Rotate-half RoPE on x [T, ..., D] at integer ``positions`` [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1).reshape(
        x.shape[0], *[1] * (x.ndim - 2), x.shape[-1])
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention_mixer(l: dict, h, m: dict, positions, mask, quant=None):
    """h [T, E] normed -> [T, E]; ``positions`` [T], ``mask`` [T, T]."""
    l = _f32(l)
    t = h.shape[0]
    nh, kv, hd = m["num_heads"], m["kv_heads"], m["head_dim"]
    q = _mm(h, l["wq"], quant).reshape(t, kv, nh // kv, hd)
    k = _mm(h, l["wk"], quant).reshape(t, kv, hd)
    v = _mm(h, l["wv"], quant).reshape(t, kv, hd)
    q = _rope(_rms(q, l["q_g"], m["norm_eps"]), positions, m["rope_theta"])
    k = _rope(_rms(k, l["k_g"], m["norm_eps"]), positions, m["rope_theta"])
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HI) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(t, nh * hd)
    return _mm(a, l["wo"], quant)


def route(l: dict, h, m: dict):
    """The published router in float32: (chosen ids [T, k], weights
    [T, k]) over ALL experts."""
    s = jax.nn.softmax(jnp.dot(h, l["router"].astype(jnp.float32),
                               precision=HI), axis=-1)
    w, idx = lax.top_k(s, m["moe_top_k"])
    return idx, w / jnp.sum(w, -1, keepdims=True)


def moe_mixer(l: dict, h, m: dict, quant=None, held=None):
    """h [T, E] normed -> [T, E]: the part of the layer's result that
    experts ``held`` = (lo, hi) give (default: all; ``l["up"]`` etc. hold
    every expert, so any share can be taken and the shares added up)."""
    lo, hi = held or (0, m["moe_experts"])
    idx, w = route(l, h, m)
    # [T, X]: the weight each expert has for each token (0: not chosen)
    comb = jnp.sum(w[..., None] * (idx[..., None] == jnp.arange(
        m["moe_experts"])), axis=1)

    def expert(y, e):
        gate, up, down, c = e   # one expert, upcast as it is reached
        a = jax.nn.silu(_mm(h, gate.astype(jnp.float32), quant)) * _mm(
            h, up.astype(jnp.float32), quant)
        return y + c[:, None] * _mm(a, down.astype(jnp.float32), quant), None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (l["gate"][lo:hi], l["up"][lo:hi], l["down"][lo:hi],
                     comb[:, lo:hi].T))
    return y


def hidden_states(w: dict, ids, m: dict, quant=None, positions=None,
                  mask=None):
    """ids [T] -> h [T, E] float32, the final RMSNorm's output, under
    ``mask`` (default: block-causal) at ``positions`` (default 0..T-1)."""
    t = ids.shape[0]
    if positions is None:
        positions = jnp.arange(t)
    if mask is None:
        mask = block_causal_mask(t, m["block_len"])
    x = w["wte"][ids].astype(jnp.float32)
    for layer in w["layers"]:
        a, e = layer["attn"], layer["moe"]
        x = x + attention_mixer(
            a, _rms(x, a["g"].astype(jnp.float32), m["norm_eps"]), m,
            positions, mask, quant)
        x = x + moe_mixer(
            e, _rms(x, e["g"].astype(jnp.float32), m["norm_eps"]), m, quant)
    return _rms(x, w["g_f"].astype(jnp.float32), m["norm_eps"])


def logits_fn(w: dict, ids, m: dict, quant=None):
    """(a): ids [T] -> logits [T, V] float32 under the block-causal mask;
    position p's logits are of position p's own token."""
    return _mm(hidden_states(w, ids, m, quant),
               w["head"].astype(jnp.float32), quant)


def denoise_logits(w: dict, noisy, clean, m: dict, quant=None):
    """(b): noisy / clean [T] (T a multiple of the block length) -> logits
    [T, V] at the NOISY positions, from one forward over ``[noisy ;
    clean]``: what a denoising pass over each block computes when the
    earlier blocks are clean in its cache."""
    t = clean.shape[0]
    h = hidden_states(
        w, jnp.concatenate([noisy, clean]), m, quant,
        positions=jnp.concatenate([jnp.arange(t), jnp.arange(t)]),
        mask=noisy_clean_mask(t, m["block_len"]))
    return _mm(h[:t], w["head"].astype(jnp.float32), quant)


def unmask_choice(policy: str, masked, conf, count: int):
    """The policy in plain form: the positions (a list) of a block's
    ``masked`` [B] that a pass with confidences ``conf`` [B] unmasks."""
    cand = [t for t in range(len(masked)) if masked[t]]
    if policy == "low_confidence_static":
        cand.sort(key=lambda t: (-float(conf[t]), t))
    elif policy != "sequential":
        raise ValueError(f"unknown policy {policy!r}")
    return sorted(cand[:count])


@functools.lru_cache(maxsize=None)
def _denoise_fn(m_json: str):
    m = json.loads(m_json)
    return jax.jit(lambda w, noisy, clean: denoise_logits(w, noisy, clean, m))


def generate(w: dict, m: dict, prompt, max_new_tokens: int,
             denoise_steps: int, policy: str = "low_confidence_static",
             order=None):
    """The generation loop in plain form, greedy, no cache: returns
    {"tokens": every generated position's token (the caller keeps the
    first ``max_new_tokens``), "steps", "confidence"}.  ``order`` (a
    served ``steps`` list) replaces the policy's choice by the served
    one: the tokens are then the reference's at the served order.  Every pass is one ``denoise_logits`` over the sequence so
    far, padded to its final length (later blocks are invisible to
    earlier ones, so what the padding holds changes nothing)."""
    import numpy as np

    bl, mask_id = m["block_len"], m["mask_id"]
    seq = [int(t) for t in prompt]
    first = len(seq) // bl * bl
    total = -(-(len(seq) + max_new_tokens) // bl) * bl
    f = _denoise_fn(json.dumps(m, sort_keys=True))
    out = {"tokens": [], "steps": [], "confidence": []}
    with jax.default_matmul_precision("highest"):
        for start in range(first, total, bl):
            known = seq[start:start + bl]
            ids = known + [0] * (bl - len(known))
            steps = [-1] * len(known) + [None] * (bl - len(known))
            conf = [1.0] * bl
            passes = 0
            while any(s is None for s in steps):
                masked = [s is None for s in steps]
                clean = np.zeros((total,), np.int32)
                clean[:start + bl] = seq[:start] + ids
                noisy = clean.copy()
                noisy[start:start + bl][masked] = mask_id
                lg = np.asarray(f(w, jnp.asarray(noisy), jnp.asarray(clean))
                                )[start:start + bl]
                p = np.asarray(jax.nn.softmax(jnp.asarray(lg), -1))
                toks = lg.argmax(-1)
                left = max(denoise_steps - passes, 1)
                count = -(-sum(masked) // left)
                if order is None:
                    chosen = unmask_choice(
                        policy, masked, p[np.arange(bl), toks], count)
                else:
                    done = len(out["steps"])    # generated positions so far
                    served = [None] * len(known) + list(order)[
                        done:done + bl - len(known)]
                    chosen = [t for t in range(bl)
                              if masked[t] and served[t] == passes]
                for t in chosen:
                    ids[t], steps[t] = int(toks[t]), passes
                    conf[t] = float(p[t, toks[t]])
                passes += 1
            k = len(known)
            seq = seq[:start] + ids
            out["tokens"] += ids[k:]
            out["steps"] += steps[k:]
            out["confidence"] += conf[k:]
    return out


@functools.lru_cache(maxsize=None)
def _gap_fn(m_json: str, quant, n_steps: int):
    m = json.loads(m_json)

    def f(w, clean, steps):
        """``clean`` [T] the sequence with every served token in place,
        ``steps`` [T] the denoising pass that unmasked each position (-1:
        known, so never asked).  For each step index s one forward over
        ``[noisy_s ; clean]``, noisy_s holding the mask token wherever
        ``steps >= s``; at the positions with ``steps == s``: how far the
        logit of the served token lies below the float32 reference's
        best, the same for the token a lower precision puts first, and
        the reference's own best-to-second margin."""

        def one(s):
            noisy = jnp.where(steps >= s, m["mask_id"], clean)
            ref = denoise_logits(w, noisy, clean, m, None)
            top2 = lax.top_k(ref, 2)[0]
            best, margin = top2[:, 0], top2[:, 0] - top2[:, 1]
            served = best - jnp.take_along_axis(ref, clean[:, None], 1)[:, 0]
            if quant is None:
                return served, served, margin
            low = jnp.argmax(denoise_logits(w, noisy, clean, m, quant), -1)
            return (served,
                    best - jnp.take_along_axis(ref, low[:, None], 1)[:, 0],
                    margin)

        by_step = lax.map(one, jnp.arange(n_steps))      # 3 x [S, T]
        pick = jnp.clip(steps, 0, n_steps - 1)[None, :]
        return tuple(jnp.take_along_axis(x, pick, 0)[0] for x in by_step)

    return jax.jit(f)


def served_gaps(m: dict, weights: dict, requests, pad_to: int,
                quant=None) -> dict:
    """``requests``: [(prompt ids, served ids, trail), ...], ``trail`` the
    engine's account of every generated position of the committed blocks
    (``tokens``: the served ones first, then what was dropped; ``steps``:
    the denoising pass that unmasked each).  Returns the per-token gaps
    of the SERVED tokens (``served``), each at the step that unmasked it;
    with ``quant`` those of the tokens the lower precision would have put
    first at the same positions in the same state (``control``); and the
    reference's own best-to-second margins there (``margin``)."""
    import numpy as np

    bl = m["block_len"]
    n_steps = 1 + max((s for _, _, tr in requests for s in tr["steps"]),
                      default=0)
    f = _gap_fn(json.dumps(m, sort_keys=True), quant, n_steps)
    served, control, margin = [], [], []
    with jax.default_matmul_precision("highest"):
        for prompt, tokens, trail in requests:
            seq = list(prompt) + list(trail["tokens"])
            n, p = len(seq), len(prompt)
            if n % bl or n > pad_to or pad_to % bl or list(
                    trail["tokens"][:len(tokens)]) != list(tokens):
                raise ValueError(
                    f"a trail of {len(trail['tokens'])} positions does not "
                    f"close the blocks of a {p}-token prompt with "
                    f"{len(tokens)} served tokens (block {bl}, pad {pad_to})")
            ids = np.zeros((pad_to,), np.int32)
            ids[:n] = seq
            steps = np.full((pad_to,), -1, np.int32)
            steps[p:n] = trail["steps"]
            s, c, g = jax.device_get(f(weights, jnp.asarray(ids),
                                       jnp.asarray(steps)))
            end = p + len(tokens)      # the surplus was never served
            served.extend(float(x) for x in s[p:end])
            control.extend(float(x) for x in c[p:end])
            margin.extend(float(x) for x in g[p:end])
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
