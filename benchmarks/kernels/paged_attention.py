"""Floor of the decode step's paged-attention kernel: the K and V bytes
of every live context of the traced decode steps, once per layer, at
the chip's memory bandwidth (one query token per sequence: memory bound
by construction)."""

from benchmarks.harness import roofline


def floor(fam: dict, spec: dict, layer: dict, run):
    """(least seconds for the traced calls, a note) or None."""
    tokens = layer.get("decode_context_tokens")
    if not tokens:
        return None
    m = run.config["model"]
    nbytes = roofline.paged_attention_bytes(
        [tokens], m["num_heads"], m["head_dim"], m["num_layers"],
        fam["floor"].get("kv_bytes", 2))
    return nbytes / run.peak["bytes_per_s"], (
        f"{tokens} context tokens read in the traced decode steps = "
        f"{nbytes / 1e9:.3f} GB")
