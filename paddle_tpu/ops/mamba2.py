"""Mamba-2 (state-space duality, arXiv:2405.21060) — the selective
state-space recurrence of one layer, in the two arrangements serving
needs.  Per head ``h`` (B/C group ``h // (H / G)``), with state ``S``
[P, N]::

    S_t = exp(dt_t · A_h) · S_{t-1} + dt_t · x_t ⊗ B_t
    y_t = S_t · C_t + D_h · x_t

- :func:`ssd_prefill` runs a whole padded prompt in chunks (quadratic
  inside a chunk, the recurrence between chunks: any arrangement equals
  the recurrence) and hands back the state at each row's last VALID
  token: positions at or past ``seq_lens`` get ``dt = 0``, which decays
  nothing (``exp(0)``) and adds nothing, so the state after the padded
  length is the state at ``seq_lens - 1``;
- :func:`ssd_step` is the one-token recurrence of a decode step, and
  :func:`ssd_pool_step` the same against one layer's row of the serving
  cache's state pool, live rows only.

The depthwise causal convolution in front of it keeps its own state —
the last ``K - 1`` inputs — with the same two arrangements
(:func:`conv_prefill`, :func:`conv_step`); padding is never a tap of a
valid token (it lies to the right) and never part of the state handed
back.

Plain ``jax.numpy`` (XLA): decay arithmetic in float32, the products in
the inputs' type with float32 accumulation.  On a TPU
:func:`ssd_pool_step` is one Pallas kernel a layer
(``ops/pallas/ssd.py``: a slot's state through VMEM once, the pool
written where it lies); :func:`ssd_step` is the CPU path and its oracle.
``state_shapes`` is the one place the per-slot state of a layer is
spelt; the serving cache sizes its state pool from it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def state_shapes(heads: int, head_dim: int, state: int, groups: int,
                 conv: int) -> dict:
    """One layer's recurrent state of one sequence: name -> shape."""
    return {"ssm": (heads, head_dim, state),
            "conv": (conv - 1, heads * head_dim + 2 * groups * state)}


def _per_head(bc, heads: int):
    """B or C [..., G, N] -> [..., H, N]: head h reads group h // (H/G)."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def ssd_step(state, x, dt, a, b, c, d):
    """One token.  state [B, H, P, N] float32; x [B, H, P]; dt [B, H]
    (after softplus; 0 leaves the state as it is); a, d [H]; b, c
    [B, G, N].  Returns (y [B, H, P] float32, state')."""
    f32 = jnp.float32
    h = x.shape[1]
    xf, dt = x.astype(f32), dt.astype(f32)
    bh, ch = _per_head(b.astype(f32), h), _per_head(c.astype(f32), h)
    decay = jnp.exp(dt * a.astype(f32))
    state = (state * decay[..., None, None]
             + (dt[..., None] * xf)[..., None] * bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", state, ch) + d.astype(f32)[:, None] * xf
    return y, state


def ssd_pool_step(pool, row, x, dt, a, b, c, d, live, impl: str = "auto",
                  interpret=None):
    """One token of the layer that keeps row ``row`` (a Python number or
    traced) of ``pool`` [layers, B, H, P, N] float32; x, dt, a, b, c, d as
    :func:`ssd_step` takes them; live bool[B]: an idle row keeps its state
    bit for bit.  ``impl``: "kernel" (``ops/pallas/ssd.py``), "reference"
    (:func:`ssd_step` on the row and a ``where``: the CPU path and the
    kernel's oracle) or "auto" (kernel on a TPU); a shape the kernel does
    not take runs the reference and the routing census (``ssd_step``) says
    so.  Returns (y [B, H, P] float32, the pool with that row replaced)."""
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import ssd as kernel

    route = pallas.resolve_impl(impl)
    slots, *head_shape = pool.shape[1:]
    if route == "kernel" and not kernel.supports(*head_shape, b.shape[1],
                                                   slots):
        route = "reference_shape"
    pallas.note_route("ssd_step", route)
    if route == "kernel":
        return kernel.ssd_pool_step(
            pool, row, x, dt, a, b, c, d, live,
            interpret=pallas.resolve_interpret(interpret))
    # (the row sliced twice, in this order: the text every CPU program of a
    # pattern lowered to before the kernel, which the serving tests hold)
    y, new = ssd_step(pool[row], x, dt, a, b, c, d)
    old = pool[row]
    return y, pool.at[row].set(
        jnp.where(live.reshape(-1, 1, 1, 1), new, old))


def _segsum(a):
    """a [..., Q] -> [..., Q, Q]: sum of a over (s, l], -inf above the
    diagonal (so its exp is the causal decay from s to l)."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg, -jnp.inf)


def ssd_prefill(x, dt, a, b, c, d, seq_lens=None, chunk: int = 128,
                state=None):
    """A whole (right-padded) sequence.  x [B, T, H, P]; dt [B, T, H]
    (after softplus); a, d [H]; b, c [B, T, G, N]; seq_lens [B] valid
    lengths (None = all T); ``state`` [B, H, P, N] the state to start
    from (None = zeros).  Returns (y [B, T, H, P] float32, the state at
    each row's last valid token [B, H, P, N] float32)."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    dt = dt.astype(f32)
    if seq_lens is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None] < seq_lens[:, None, None],
                       dt, 0.0)
    pad = -t % chunk
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // chunk
    dtype = x.dtype
    xd = (x.astype(f32) * dt[..., None]).astype(dtype).reshape(
        bsz, nc, chunk, h, p)
    g = b.shape[2]
    bq = b.reshape(bsz, nc, chunk, g, n)
    cq = c.reshape(bsz, nc, chunk, g, n)
    da = (dt * a.astype(f32)).reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)
    cum = jnp.cumsum(da, axis=-1)                       # [B, H, nc, Q]

    # inside a chunk: y_l += sum_{s <= l} (C_l . B_s) exp(sum da (s, l]) x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cq, bq,
                    preferred_element_type=f32)          # [B, nc, G, Q, Q]
    decay = jnp.exp(_segsum(da)).transpose(0, 2, 1, 3, 4)  # [B, nc, H, Q, Q]
    w = (jnp.repeat(cb, h // g, axis=2) * decay).astype(dtype)
    y = jnp.einsum("bchls,bcshp->bclhp", w, xd, preferred_element_type=f32)

    # what each chunk adds to the state by its end, and the carry
    to_end = jnp.exp(cum[..., -1:] - cum)               # [B, H, nc, Q]
    xw = (xd.astype(f32) * to_end.transpose(0, 2, 3, 1)[..., None]
          ).astype(dtype)
    adds = jnp.einsum("bcshp,bcshn->bchpn", xw, _per_head(bq, h),
                      preferred_element_type=f32)        # [B, nc, H, P, N]
    total = jnp.exp(cum[..., -1]).transpose(0, 2, 1)     # [B, nc, H]

    def carry(s, inp):
        add, tot = inp
        return s * tot[..., None, None] + add, s         # emits the state BEFORE

    s0 = jnp.zeros((bsz, h, p, n), f32) if state is None else state.astype(f32)
    final, before = lax.scan(
        carry, s0, (adds.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)))
    before = before.transpose(1, 0, 2, 3, 4)             # [B, nc, H, P, N]

    # across chunks: y_l += C_l . (state before the chunk) exp(sum da [0, l])
    from_start = jnp.exp(cum).transpose(0, 2, 3, 1)      # [B, nc, Q, H]
    y = y + jnp.einsum("bclhn,bchpn->bclhp", _per_head(cq, h).astype(f32),
                       before) * from_start[..., None]
    y = y.reshape(bsz, t + pad, h, p)[:, :t]
    y = y + d.astype(f32)[:, None] * x[:, :t].astype(f32)
    return y, final


def conv_prefill(x, w, bias, seq_lens=None, state=None):
    """Depthwise causal convolution over time.  x [B, T, C]; w [K, C]
    (tap K-1 is the current token); bias [C] or None; ``state`` [B, K-1,
    C] the inputs before position 0 (None = zeros).  Returns (out
    [B, T, C] float32, the last K-1 inputs at each row's last valid token
    [B, K-1, C], in x's type)."""
    k = w.shape[0]
    bsz, t, ch = x.shape
    left = (jnp.zeros((bsz, k - 1, ch), x.dtype) if state is None
            else state.astype(x.dtype))
    xp = jnp.concatenate([left, x], axis=1)              # [B, K-1+T, C]
    wf = w.astype(jnp.float32)
    out = sum(xp[:, j:j + t].astype(jnp.float32) * wf[j] for j in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    lens = jnp.full((bsz,), t) if seq_lens is None else seq_lens
    # inputs lens-K+1 .. lens-1 sit at xp[lens .. lens+K-2]
    idx = lens[:, None] + jnp.arange(k - 1)[None, :]
    return out, jnp.take_along_axis(xp, idx[:, :, None], axis=1)


def conv_step(state, x, w, bias):
    """One token.  state [B, K-1, C]; x [B, C].  Returns (out [B, C]
    float32, state' [B, K-1, C])."""
    window = jnp.concatenate([state, x[:, None].astype(state.dtype)], axis=1)
    out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                     w.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out, window[:, 1:]
