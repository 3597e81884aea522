"""Floor of a whole block pass of a model that generates by diffusion
over blocks with routed experts: what it must stream from memory -- the
held layers' weights outside the experts once, one expert's three
matrices for every (layer, expert) the pass's positions touched, the head
once (every position of every row has logits), K and V of the context
each row's block reads -- at the chip's memory bandwidth.  The byte
counts are the configuration's (``block_decode_stream_bytes``); the
experts touched and the context tokens are the program's own counts
(``serve_decode``'s ``experts_touched``, ``context_tokens``), NOT the
configuration's maxima.  The block's own K/V written (rows x block x
``kv_per_token``: 3 MB of 8 GB) and the embedding rows read are left
out.  A program whose spans lack the counts, or that runs no block
passes (no ``block`` on the span), reports nothing."""

NEEDS = ("block", "experts_touched", "context_tokens")


def step_bytes(sizes: dict, a: dict) -> float:
    """Bytes one block pass with span args ``a`` must stream."""
    return (sizes["non_expert_layer_weights"] + sizes["head"]
            + a["experts_touched"] * sizes["one_expert"]
            + a["context_tokens"] * sizes["kv_per_token"])


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced passes, a note) or None."""
    sizes = run.config.get("block_decode_stream_bytes")
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if all(k in a for k in NEEDS)]
    if not sizes or not steps:
        return None
    total = sum(share * step_bytes(sizes, a) for share, a in steps)
    n = sum(share for share, _ in steps)
    return total / run.peak["bytes_per_s"], (
        f"{n:.2f} traced block passes: {total / n / 1e9:.3f} GB a pass at "
        f"{sum(s * a['experts_touched'] for s, a in steps) / n:.1f} experts "
        f"touched and {sum(s * a['context_tokens'] for s, a in steps) / n:.0f}"
        f" context tokens")
