"""Floor of the decode step's paged-attention kernel where ONE growing
cache layer is read by several layers and window layers read bounded
rings: the K and V bytes of every live context once per LAYER-READ
(``serve_decode``'s ``kv_reads``) plus ``min(length, window)`` ring rows a
live slot once per window layer (``window_layers`` x ``window_tokens``),
at the cache's heads (``kv_heads``), at the chip's memory bandwidth.  All
counts are the program's own; a program whose spans lack them reports
nothing.  (``paged_attention_gqa`` counts ``cache_layers`` x
``context_tokens``: one layer here, where sixteen calls read.)"""

NEEDS = ("kv_reads", "context_tokens", "window_layers", "window_tokens",
         "kv_heads")


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced calls, a note) or None."""
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if all(k in a for k in NEEDS)]
    if not steps:
        return None
    rows = run.py("kernels", "yoco_decode_stream").kv_tokens
    hd = run.config["model"]["head_dim"]
    per_row = lambda a: 2 * a["kv_heads"] * hd * fam["floor"].get("kv_bytes", 2)
    nbytes = sum(share * rows(a) * per_row(a) for share, a in steps)
    a0 = steps[0][1]
    return nbytes / run.peak["bytes_per_s"], (
        f"{sum(s * a['context_tokens'] for s, a in steps):.0f} context "
        f"tokens x {a0['kv_reads']} layer-reads + "
        f"{sum(s * a['window_tokens'] for s, a in steps):.0f} ring rows x "
        f"{a0['window_layers']} window layers in {len(steps)} traced decode "
        f"steps x {a0['kv_heads']} K/V heads = {nbytes / 1e9:.3f} GB")
