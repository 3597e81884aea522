"""Floor of a whole decode step of a decoder-hybrid-decoder pattern
(Mamba-1 and window layers, ONE growing cache layer that several layers
read, no routed experts): what it must stream from memory -- every
layer's weights and the (tied) head once, the recurrent state of every
live slot read and written, K and V of the live contexts once a
LAYER-READ of the growing cache, the rings' live rows once a window layer
-- at the chip's memory bandwidth.  The byte counts are the
configuration's (``yoco_decode_stream_bytes``); the counts are the
program's own (``serve_decode``'s ``state_slots``, ``kv_reads``,
``context_tokens``, ``window_layers``, ``window_tokens``), NOT the
configuration's maxima.  A program whose spans lack them reports
nothing."""

NEEDS = ("state_slots", "kv_reads", "context_tokens", "window_layers",
         "window_tokens")


def kv_tokens(a: dict) -> float:
    """Token-rows of K/V one decode step with span args ``a`` reads."""
    return (a["kv_reads"] * a["context_tokens"]
            + a["window_layers"] * a["window_tokens"])


def step_bytes(sizes: dict, a: dict) -> float:
    """Bytes one decode step with span args ``a`` must stream."""
    return (sizes["layer_weights_and_head"]
            + 2 * a["state_slots"] * sizes["state_per_slot"]
            + kv_tokens(a) * sizes["kv_per_token"])


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced steps, a note) or None."""
    sizes = run.config.get("yoco_decode_stream_bytes")
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if all(k in a for k in NEEDS)]
    if not sizes or not steps:
        return None
    total = sum(share * step_bytes(sizes, a) for share, a in steps)
    n = sum(share for share, _ in steps)
    mean = lambda k: sum(s * a[k] for s, a in steps) / n
    return total / run.peak["bytes_per_s"], (
        f"{n:.2f} traced decode steps: {total / n / 1e9:.3f} GB a step at "
        f"{mean('state_slots'):.1f} live slots, {mean('context_tokens'):.0f} "
        f"context tokens x {steps[0][1]['kv_reads']} reads and "
        f"{mean('window_tokens'):.0f} ring rows x "
        f"{steps[0][1]['window_layers']} window layers")
