"""Floor of a whole decode step of a looped stack: what it must stream
from memory whatever the batch -- the layers' weights once a pass, the
head once, K and V of the live contexts -- at the chip's memory
bandwidth.  The byte counts are the configuration's
(``decode_stream_bytes``); passes and context tokens are the program's
own (``serve_decode``'s ``loop_steps`` and ``context_tokens``)."""


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced steps, a note) or None."""
    sizes = run.config.get("decode_stream_bytes")
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if "context_tokens" in a and "loop_steps" in a]
    if not sizes or not steps:
        return None
    weights = sum(share * (a["loop_steps"] * sizes["layer_weights"]
                           + sizes["head"]) for share, a in steps)
    kv = sum(share * a["context_tokens"] * sizes["kv_per_token"]
             for share, a in steps)
    return (weights + kv) / run.peak["bytes_per_s"], (
        f"{sum(s for s, _ in steps):.2f} traced decode steps: weights "
        f"{weights / 1e9:.3f} GB + K/V {kv / 1e9:.3f} GB")
