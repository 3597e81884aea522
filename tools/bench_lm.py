"""Transformer LM step-time lab — reproduce the 124M baseline and measure
each candidate optimisation in isolation (VERDICT r2 task 2: where does the
107.8 ms go when the MXU-bound floor is ~31 ms?).

Usage: python tools/bench_lm.py [variant ...]   (from the repo root, as the
process that owns the chip)
Variants: see main()'s dispatch table (baseline, noremat, exact, dots, mp,
mp_full, mp_norm, mp16, mp32, bs16, bs32) or "breakdown".
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import transformer as T
from paddle_tpu.optimizer import Adam

VOCAB = 50257


def two_point(step_fn, warmup=2, n1=3, n2=13):
    def run(n):
        t0 = time.perf_counter()
        c = None
        for _ in range(n):
            c = step_fn()
        float(np.asarray(c).reshape(-1)[0])
        return time.perf_counter() - t0

    run(warmup)
    t1 = min(run(n1) for _ in range(2))
    t2 = min(run(n2) for _ in range(2))
    return max(t2 - t1, 1e-9) / (n2 - n1) * 1000.0


def gpt2_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, num_layers=12, num_heads=12, embed_dim=768,
        mlp_dim=3072, max_seq_len=2048, dtype=jnp.bfloat16, remat=True,
        attn_impl="flash", attn_block_size=512,
    )
    base.update(kw)
    return T.TransformerConfig(**base)


def n_params(params):
    return sum(x.size for x in jax.tree.leaves(params))


def run_variant(name: str, cfg, bs=8, seqlen=1024,
                opt=None, compute_dtype=None):
    key = jax.random.key(0)
    params = T.init_params(cfg, key)
    N = n_params(params)
    opt = opt or Adam(learning_rate=1e-4)
    opt_state = opt.init_tree(params)
    ids = jax.device_put(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          size=(bs, seqlen + 1)))

    jstep = T.build_train_step(cfg, opt, compute_dtype=compute_dtype)
    state = {"p": params, "o": opt_state}

    def one():
        state["p"], state["o"], loss = jstep(state["p"], state["o"], ids)
        return loss

    ms = two_point(one)
    tokens = bs * seqlen
    # 6ND + attention FLOPs (2*2*2 * L * B*T^2*HD per train step, causal /2)
    attn_fl = 12 * cfg.num_layers * bs * seqlen * seqlen * cfg.embed_dim / 2
    fl = 6.0 * N * tokens + attn_fl
    mfu = fl / (ms / 1e3) / 197e12
    print(f"{name:16s} {ms:8.2f} ms/step  {tokens / ms * 1000:10.0f} tok/s  "
          f"mfu {mfu * 100:5.1f}%  (N={N / 1e6:.1f}M)")
    return ms


def breakdown(cfg, bs=8, seqlen=1024):
    """Segment timing: full step vs grad-only vs fwd(+head, no CE) vs
    optimizer-only."""
    key = jax.random.key(0)
    params = T.init_params(cfg, key)
    opt = Adam(learning_rate=1e-4)
    opt_state = opt.init_tree(params)
    ids = jax.device_put(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          size=(bs, seqlen + 1)))

    lf = lambda p: T.loss_fn(cfg, p, ids)

    # fwd loss only
    fwd = jax.jit(lf)
    ms_fwd = two_point(lambda: fwd(params))
    print(f"fwd+loss only      {ms_fwd:8.2f} ms")

    # fwd through the LM head but without the CE loss
    def body_only(p):
        logits = T.forward(cfg, p, ids[:, :-1])
        return jnp.sum(logits.astype(jnp.float32))
    f2 = jax.jit(body_only)
    ms_body = two_point(lambda: f2(params))
    print(f"fwd incl head(sum) {ms_body:8.2f} ms")

    # grad only (no optimizer)
    vg = jax.jit(jax.value_and_grad(lf))
    ms_vg = two_point(lambda: vg(params)[0])
    print(f"value_and_grad     {ms_vg:8.2f} ms")

    # optimizer alone on unit grads
    grads = jax.tree.map(jnp.ones_like, params)
    grads = jax.device_put(grads)

    def opt_only(p, o, g):
        return opt.apply_tree(g, p, o)
    jopt = jax.jit(opt_only)
    st = {"p": params, "o": opt_state}

    def one():
        st["p"], st["o"] = jopt(st["p"], st["o"], grads)
        return st["o"]["step"]
    ms_opt = two_point(one)
    print(f"optimizer only     {ms_opt:8.2f} ms")


def main():
    variants = sys.argv[1:] or ["baseline"]
    if variants[0] == "breakdown":
        breakdown(gpt2_cfg())
        return
    for v in variants:
        if v == "baseline":
            run_variant(v, gpt2_cfg())
        elif v == "noremat":
            run_variant(v, gpt2_cfg(remat=False))
        elif v == "exact":
            run_variant(v, gpt2_cfg(attn_impl="exact"))
        elif v == "exact_noremat":
            run_variant(v, gpt2_cfg(attn_impl="exact", remat=False))
        elif v == "dots":
            run_variant(v, gpt2_cfg(remat="dots"))
        elif v == "mp":
            # proper mixed precision: f32 masters, bf16 compute
            run_variant(v, gpt2_cfg(remat="dots", dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16)
        elif v == "mp_full":
            run_variant(v, gpt2_cfg(remat=True, dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16)
        elif v == "mp_norm":
            run_variant(v, gpt2_cfg(remat=False, dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16)
        elif v == "mp16":
            run_variant(v, gpt2_cfg(remat="dots", dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16, bs=16)
        elif v == "bs16":
            run_variant(v, gpt2_cfg(remat="dots"), bs=16)
        elif v == "bs32":
            run_variant(v, gpt2_cfg(remat="dots"), bs=32)
        elif v == "mp32":
            run_variant(v, gpt2_cfg(remat="dots", dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16, bs=32)
        elif v == "mom16":
            # the bench shape (bs16, no remat, f32 masters) with bf16
            # Adam moments — the HBM lever on the ~5 ms Adam line
            run_variant(v, gpt2_cfg(remat=False, dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16, bs=16,
                        opt=Adam(learning_rate=1e-4,
                                 moment_dtype=jnp.bfloat16))
        elif v == "mom16_bs24":
            run_variant(v, gpt2_cfg(remat=False, dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16, bs=24,
                        opt=Adam(learning_rate=1e-4,
                                 moment_dtype=jnp.bfloat16))
        elif v == "mp16_ref":
            # f32-moment control at the identical bench shape
            run_variant(v, gpt2_cfg(remat=False, dtype=jnp.float32),
                        compute_dtype=jnp.bfloat16, bs=16)
        else:
            print(f"unknown variant {v}")


if __name__ == "__main__":
    main()
