"""Compressed Convolutional Attention (arXiv:2510.04476) — what its
attention layer keeps of the sequence BESIDE the K/V pages: q and k pass
two short causal convolutions over the sequence and half of v is the
previous token's projection, so a layer reads, at every token, the last
few inputs of three small streams.

Both arrangements serving needs hand the mixer the same thing, a
WINDOW: ``win[..., j, :]`` is the input ``n - j`` tokens back (``j = n``
the current token), zeros left of the sequence.

- :func:`window_prefill` windows a whole right-padded sequence and hands
  back the last ``n`` inputs at each row's last VALID token (padding lies
  to the right: never in a valid token's window, never in the state);
- :func:`window_step` windows one token against the state of its slot.

``state_shapes`` is the one place the per-slot state of a layer is spelt;
the serving cache sizes its state pools from it.  float32 throughout.
"""

from __future__ import annotations

import jax.numpy as jnp


def state_shapes(taps: tuple, heads: int, kv_heads: int,
                 head_dim: int) -> dict:
    """One CCA layer's state of one sequence: name -> shape.  ``cca_u``:
    the last inputs of the depthwise stage (q | k before any
    convolution), ``cca_c``: the last outputs of that stage (the grouped
    stage's inputs), ``cca_v``: the previous token's half of v."""
    ch = (heads + kv_heads) * head_dim
    return {"cca_u": (taps[0] - 1, ch), "cca_c": (taps[1] - 1, ch),
            "cca_v": (1, kv_heads * head_dim // 2)}


def window_prefill(x, n: int, seq_lens=None):
    """x [B, T, C] -> (win [B, T, n+1, C] float32, the last n inputs at
    each row's last valid token [B, n, C] float32)."""
    bsz, t, _ = x.shape
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (n, 0), (0, 0)))
    win = jnp.stack([xp[:, j:j + t] for j in range(n + 1)], axis=2)
    lens = jnp.full((bsz,), t) if seq_lens is None else seq_lens
    # inputs lens-n .. lens-1 sit at xp[lens .. lens+n-1]
    idx = lens[:, None] + jnp.arange(n)[None, :]
    return win, jnp.take_along_axis(xp, idx[:, :, None], axis=1)


def window_step(state, x):
    """One token.  state [B, n, C] float32; x [B, C].  Returns (win
    [B, n+1, C] float32, state' [B, n, C])."""
    win = jnp.concatenate([state, x[:, None].astype(state.dtype)], axis=1)
    return win, win[:, 1:]
