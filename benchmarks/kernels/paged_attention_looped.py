"""Floor of the decode step's paged-attention kernel under a looped
stack: the K and V bytes of every live context of the traced decode
steps, once per CACHE layer (``num_layers x loop_steps``: every pass
keeps and reads its own cache), at the chip's memory bandwidth.  The
context tokens are the program's own count (``serve_decode``'s
``context_tokens``), taken from the steps the device trace holds."""

from benchmarks.harness import roofline


def traced_steps(layer: dict, span: str = "serve_decode") -> list:
    """[(share, args)] of the ``span``s that overlap the device trace:
    ``share`` is the part of the span inside the traced window (a step
    the trace cuts counts by that part).  Needs the spans on the trace's
    clock (``span_offset_ns``); empty where there is no trace."""
    win, off = layer.get("profile_window"), layer.get("span_offset_ns")
    if not win or off is None:
        return []
    out = []
    for s in layer.get("spans", ()):
        if s["name"] != span:
            continue
        t0, t1 = s["t0"] * 1e9 + off, s["t1"] * 1e9 + off
        inside = min(t1, win[1]) - max(t0, win[0])
        if inside > 0 and t1 > t0:
            out.append((inside / (t1 - t0), s["args"]))
    return out


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced calls, a note) or None."""
    steps = [(share, a) for share, a in traced_steps(layer)
             if "context_tokens" in a and "cache_layers" in a]
    if not steps:
        return None
    m = run.config["model"]
    nbytes = sum(share * roofline.paged_attention_bytes(
        [a["context_tokens"]], m["num_heads"], m["head_dim"],
        a["cache_layers"], fam["floor"].get("kv_bytes", 2))
        for share, a in steps)
    tokens = sum(share * a["context_tokens"] for share, a in steps)
    return nbytes / run.peak["bytes_per_s"], (
        f"{tokens:.0f} context tokens read in {len(steps)} traced decode "
        f"steps x {steps[0][1]['cache_layers']} cache layers = "
        f"{nbytes / 1e9:.3f} GB")
