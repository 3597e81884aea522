"""Floor of the decode step's paged-attention kernel over a cache that
holds fewer K/V heads than the model has query heads: the K and V bytes
of every live context of the traced decode steps at the CACHE's heads,
once per cache layer, at the chip's memory bandwidth.  Heads, cache
layers and context tokens are the program's own (``serve_decode``'s
``kv_heads``, ``cache_layers``, ``context_tokens``); a program whose
spans lack ``kv_heads`` reports nothing."""

from benchmarks.harness import roofline


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced calls, a note) or None."""
    steps = [(share, a) for share, a in run.py(
        "kernels", "paged_attention_looped").traced_steps(layer)
        if all(k in a for k in ("context_tokens", "cache_layers",
                                "kv_heads"))]
    if not steps:
        return None
    hd = run.config["model"]["head_dim"]
    nbytes = sum(share * roofline.paged_attention_bytes(
        [a["context_tokens"]], a["kv_heads"], hd, a["cache_layers"],
        fam["floor"].get("kv_bytes", 2)) for share, a in steps)
    tokens = sum(share * a["context_tokens"] for share, a in steps)
    a0 = steps[0][1]
    return nbytes / run.peak["bytes_per_s"], (
        f"{tokens:.0f} context tokens read in {len(steps)} traced decode "
        f"steps x {a0['cache_layers']} cache layers x {a0['kv_heads']} K/V "
        f"heads = {nbytes / 1e9:.3f} GB")
