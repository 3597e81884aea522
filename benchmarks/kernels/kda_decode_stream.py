"""Floor of a whole decode step of a pattern of gated attention, KDA
(gated delta rule) and routed-expert sublayers: what it must stream from
memory -- the layers' weights outside the routed experts once, one
expert's three matrices for every (layer, held expert) the step's tokens
touched, the head's held slice once, the float32 delta-rule state and
convolution tail every live slot keeps on every KDA layer read and
written, K and V of the live contexts at the cache's heads -- at the
chip's memory bandwidth.  The byte counts are the configuration's
(``kda_decode_stream_bytes``); the sum is
``hybrid_decode_stream.step_bytes``, the same five terms; the experts
touched, the live slots and the context tokens are the program's own
counts (``serve_decode``'s ``experts_touched``, ``state_slots``,
``context_tokens``).  A program whose spans lack them reports nothing."""


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced steps, a note) or None."""
    hybrid = run.py("kernels", "hybrid_decode_stream")
    sizes = run.config.get("kda_decode_stream_bytes")
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if all(k in a for k in hybrid.NEEDS)]
    if not sizes or not steps:
        return None
    total = sum(share * hybrid.step_bytes(sizes, a) for share, a in steps)
    n = sum(share for share, _ in steps)
    return total / run.peak["bytes_per_s"], (
        f"{n:.2f} traced decode steps: {total / n / 1e9:.3f} GB a step at "
        f"{sum(s * a['experts_touched'] for s, a in steps) / n:.1f} experts "
        f"touched, {sum(s * a['state_slots'] for s, a in steps) / n:.1f} "
        f"live slots and "
        f"{sum(s * a['context_tokens'] for s, a in steps) / n:.0f} context "
        f"tokens")
