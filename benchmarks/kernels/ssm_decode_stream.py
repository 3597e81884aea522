"""Floor of a whole decode step of a layer pattern with recurrent-state
layers and NO routed experts: what it must stream from memory -- every
layer's weights and the (tied) head once, the recurrent state of every
live slot read and written, K and V of the live contexts -- at the chip's
memory bandwidth.  The byte counts are the configuration's
(``ssm_decode_stream_bytes``); the live slots and the context tokens are
the program's own counts (``serve_decode``'s ``state_slots``,
``context_tokens``), NOT the configuration's maxima.  A program whose
spans lack them reports nothing."""

NEEDS = ("state_slots", "context_tokens")


def step_bytes(sizes: dict, a: dict) -> float:
    """Bytes one decode step with span args ``a`` must stream."""
    return (sizes["layer_weights_and_head"]
            + 2 * a["state_slots"] * sizes["state_per_slot"]
            + a["context_tokens"] * sizes["kv_per_token"])


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced steps, a note) or None."""
    sizes = run.config.get("ssm_decode_stream_bytes")
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if all(k in a for k in NEEDS)]
    if not sizes or not steps:
        return None
    total = sum(share * step_bytes(sizes, a) for share, a in steps)
    n = sum(share for share, _ in steps)
    return total / run.peak["bytes_per_s"], (
        f"{n:.2f} traced decode steps: {total / n / 1e9:.3f} GB a step at "
        f"{sum(s * a['state_slots'] for s, a in steps) / n:.1f} live slots "
        f"and {sum(s * a['context_tokens'] for s, a in steps) / n:.0f} "
        f"context tokens")
