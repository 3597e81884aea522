"""MoE transformer step-time on one chip (dense-dispatch path).

Measures a GPT-2-small-width MoE LM (top-2, capacity 1.25) against the
dense-FFN 124M baseline at matched active FLOPs — the capability row for
parallel/moe.py.  Device-side timing.

Usage: python tools/bench_moe.py   (from the repo root, as the process that
owns the chip)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import transformer as T
from paddle_tpu.optimizer import Adam
from paddle_tpu.profiler import device_step_ms

VOCAB = 50257


def run(name: str, cfg: T.TransformerConfig, bs=8, seqlen=1024):
    params = T.init_params(cfg, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    opt = Adam(learning_rate=1e-4, moment_dtype=jnp.bfloat16)
    st = {"p": params, "o": opt.init_tree(params)}
    ids = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(bs, seqlen + 1)))
    step = T.build_train_step(cfg, opt, compute_dtype=jnp.bfloat16)

    def one():
        st["p"], st["o"], loss = step(st["p"], st["o"], ids)
        return loss

    ms = device_step_ms(one, steps=10, warmup=3)
    tokens = bs * seqlen
    # active params per token: dense share + top_k/E of expert weights
    print(f"{name:22s} {ms:8.2f} ms/step  {tokens / ms * 1000:9.0f} tok/s  "
          f"(params {n / 1e6:.0f}M)")
    return ms


def main():
    base = dict(vocab_size=VOCAB, num_layers=12, num_heads=12,
                embed_dim=768, mlp_dim=3072, max_seq_len=2048,
                dtype=jnp.float32, remat=False, attn_impl="flash",
                attn_block_size=1024)
    import sys

    known = ["dense", "top2", "top1", "top2sort", "top1sort"]
    sel = sys.argv[1:] or known
    bad = [s for s in sel if s not in known]
    if bad:
        raise SystemExit(f"unknown variants {bad}; choose from {known}")
    if "dense" in sel:
        run("dense-124M", T.TransformerConfig(**base), bs=8)
    if "top2" in sel:
        run("moe-8e-top2", T.TransformerConfig(
            **base, moe_experts=8, moe_top_k=2,
            moe_capacity_factor=1.25), bs=8)
    if "top1" in sel:
        run("moe-8e-top1", T.TransformerConfig(
            **base, moe_experts=8, moe_top_k=1,
            moe_capacity_factor=1.25), bs=8)
    if "top2sort" in sel:
        run("moe-8e-top2-sort", T.TransformerConfig(
            **base, moe_experts=8, moe_top_k=2, moe_capacity_factor=1.25,
            moe_dispatch="sort"), bs=8)
    if "top1sort" in sel:
        run("moe-8e-top1-sort", T.TransformerConfig(
            **base, moe_experts=8, moe_top_k=1, moe_capacity_factor=1.25,
            moe_dispatch="sort"), bs=8)


if __name__ == "__main__":
    main()
