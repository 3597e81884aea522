"""Mamba-1 (the selective scan of arXiv:2312.00752) — the recurrence of
one layer, in the two arrangements serving needs.  No heads and no
matrix form: the decay is one number per CHANNEL AND STATE COLUMN.  With
``A`` [N, D] (negative), a channel ``d`` and a state column ``n``::

    h_t[n, d] = exp(dt_t[d] · A[n, d]) · h_{t-1}[n, d] + dt_t[d] · x_t[d] · B_t[n]
    y_t[d]    = sum_n h_t[n, d] · C_t[n] + D[d] · x_t[d]

The state is kept ``[N, D]`` — the ``D`` channels (thousands) in the
lanes, the ``N`` state columns (16) in the sublanes — everywhere: a
float32 ``[D, 16]`` rests on a TPU padded to 128 lanes, eight times its
bytes.

- :func:`scan_prefill` runs a whole padded prompt in chunks of ``Q``
  tokens.  Level one walks the ``Q`` steps of EVERY chunk at once, each
  from a zero state (``T / Q`` chunks side by side: ``Q`` sequential
  steps over ``[B, T/Q, N, D]``, never ``[T, N, D]`` whole); level two
  carries the state from chunk to chunk (``lax.scan``); level three adds
  what the state a chunk started from contributes to its outputs, decayed
  by the chunk's running sum of ``dt``.  Any arrangement equals the
  recurrence.  Positions at or past ``seq_lens`` get ``dt = 0``, which
  decays nothing and adds nothing, so the state handed back is the one at
  each row's last VALID token;
- :func:`scan_step` is the one-token recurrence of a decode step.

Both return ``y`` BEFORE any gate (with the ``D`` skip): the layer that
feeds the gated memory units hands exactly that on.  Plain ``jax.numpy``
(XLA), everything float32: the routing census says ``mamba1_prefill:xla``
/ ``mamba1_step:xla``.  The depthwise convolution in front is
``mamba2.conv_prefill`` / ``conv_step``.  ``state_shapes`` is the one
place the per-slot state of a layer is spelt.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def state_shapes(inner: int, state: int, conv: int) -> dict:
    """One layer's recurrent state of one sequence: name -> shape."""
    return {"ssm1": (state, inner), "conv1": (conv - 1, inner)}


def scan_step(h, x, dt, a, b, c, d):
    """One token.  h [B, N, D] float32; x, dt [B, D] (dt after softplus; 0
    leaves the state as it is); a [N, D]; b, c [B, N]; d [D].  Returns
    (y [B, D] float32, h')."""
    from paddle_tpu.ops import pallas

    pallas.note_route("mamba1_step", "xla")
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    h = (jnp.exp(dt[:, None, :] * a.astype(f32)) * h
         + (dt * xf)[:, None, :] * b.astype(f32)[:, :, None])
    y = jnp.sum(h * c.astype(f32)[:, :, None], axis=1) + d.astype(f32) * xf
    return y, h


def scan_prefill(x, dt, a, b, c, d, seq_lens=None, chunk: int = 64):
    """A whole (right-padded) sequence from a zero state.  x, dt
    [B, T, D] (dt after softplus); a [N, D]; b, c [B, T, N]; d [D];
    seq_lens [B] valid lengths (None = all T).  Returns (y [B, T, D]
    float32, the state at each row's last valid token [B, N, D]
    float32)."""
    from paddle_tpu.ops import pallas

    pallas.note_route("mamba1_prefill", "xla")
    f32 = jnp.float32
    bsz, t, di = x.shape
    n = a.shape[0]
    xf, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    b, c = b.astype(f32), c.astype(f32)
    if seq_lens is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None] < seq_lens[:, None, None],
                       dt, 0.0)
    q = min(chunk, t)
    pad = -t % q
    if pad:     # dt = 0 there: nothing decays, nothing is added
        xf, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                        for v in (xf, dt, b, c))
    nc = (t + pad) // q
    # step-major: [Q, B, nc, ...]
    steps = lambda v: v.reshape(bsz, nc, q, -1).transpose(2, 0, 1, 3)
    dts, bs, cs = steps(dt), steps(b), steps(c)
    dtx = steps(dt * xf)

    def step(carry, inp):
        """Token ``s`` of every chunk: the chunk's own state so far (from
        zero) and its running sum of dt."""
        h, run = carry
        dt_s, dtx_s, b_s, c_s = inp
        h = (jnp.exp(dt_s[:, :, None, :] * a) * h
             + dtx_s[:, :, None, :] * b_s[..., None])
        run = run + dt_s
        return (h, run), (jnp.sum(h * c_s[..., None], axis=2), run)

    (ends, total), (y, runs) = lax.scan(
        step, (jnp.zeros((bsz, nc, n, di), f32), jnp.zeros((bsz, nc, di), f32)),
        (dts, dtx, bs, cs))

    def carry_over(h, inp):
        end, tot = inp
        return jnp.exp(tot[:, None, :] * a) * h + end, h    # emits BEFORE

    final, before = lax.scan(
        carry_over, jnp.zeros((bsz, n, di), f32),
        (ends.transpose(1, 0, 2, 3), total.transpose(1, 0, 2)))
    before = before.transpose(1, 0, 2, 3)                    # [B, nc, N, D]
    # y_s += C_s . (exp(run_s A) * the state the chunk started from)
    y = y + jnp.sum(jnp.exp(runs[:, :, :, None, :] * a) * before[None]
                    * cs[..., None], axis=3)
    y = y.transpose(1, 2, 0, 3).reshape(bsz, t + pad, di)[:, :t]
    return y + d.astype(f32) * xf[:, :t], final
