"""Floor of the from-zero prefill programs of a pattern of gated
attention, KDA (gated delta rule) and routed-expert sublayers: the FLOPs
the pass's PROMPT tokens need -- not its padded ones, and counted from
the recurrence, not from the chunked form that happens to compute it, so
that the same work reads the same whatever implements it -- at the chip's
peak FLOP/s.  Per pass, from the program's own ``serve_prefill`` span:
``prompt_tokens`` x what every token costs outside the routed experts
(the configuration's ``prefill_flops_per_token``), ``moe_assignments`` x
one expert (assignments to HELD experts only), ``attn_pairs`` (causal
query-key pairs, sum over rows of len (len + 1) / 2) x 4 x head_dim x
heads, ``batch`` x the head on each row's last token.  A program whose
spans lack ``attn_pairs`` reports nothing."""

NEEDS = ("prompt_tokens", "moe_assignments", "attn_pairs", "batch")


def pass_flops(sizes: dict, a: dict) -> float:
    """FLOPs one prefill pass with span args ``a`` must do."""
    return (a["prompt_tokens"] * sizes["per_token"]
            + a["moe_assignments"] * sizes["one_expert"]
            + a["attn_pairs"] * sizes["per_attn_pair"]
            + a["batch"] * sizes["head_per_row"])


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced passes, a note) or None."""
    sizes = run.config.get("prefill_flops_per_token")
    passes = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(
            layer, "serve_prefill") if all(k in a for k in NEEDS)]
    if not sizes or not passes:
        return None
    total = sum(share * pass_flops(sizes, a) for share, a in passes)
    n = sum(share for share, _ in passes)
    tokens = sum(s * a["prompt_tokens"] for s, a in passes)
    padded = sum(s * a["padded_tokens"] for s, a in passes)
    return total / run.peak["flops_per_s"], (
        f"{n:.2f} traced prefill passes: {total / n / 1e12:.3f} TFLOP a pass "
        f"over {tokens / n:.0f} prompt tokens of {padded / n:.0f} padded")
