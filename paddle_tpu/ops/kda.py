"""Kimi Delta Attention (arXiv:2510.26692; the delta rule arXiv:2406.06484,
gated arXiv:2412.06464) — the gated delta-rule recurrence of one
linear-attention layer, in the two arrangements serving needs.  Per head,
with state ``S`` [K, V] float32 (keys x values), a decay ``α_t = exp(g_t)``
per key CHANNEL and a step ``β_t`` per head::

    S' = Diag(α_t) · S_{t-1}
    S_t = S' + β_t · k_t ⊗ (v_t − S'ᵀ k_t)
    o_t = S_tᵀ q_t

The transition ``(I − β k kᵀ) Diag(α)`` is not diagonal, so a chunk is not
a cumulative product as Mamba-2's is: inside a chunk the tokens couple
through a unit-lower-triangular system (the WY / UT form).

- :func:`kda_prefill` runs a whole padded prompt in chunks.  With ``G`` the
  decay's running sum inside the chunk and ``S_0`` the state it starts
  from, the corrected values ``u_i = β_i (v_i − S'_iᵀ k_i)`` solve
  ``(I + tril(A, −1)) U = β ⊙ (V − (K ⊙ exp G) S_0)``, ``A_ij = β_i Σ_c
  k_ic k_jc exp(G_ic − G_jc)``; the nilpotent system inverts by doubling
  (``(I + L)⁻¹ = (I − L)(I + L²)(I + L⁴)…``: matrix products, no
  substitution loop); chunk to chunk a ``lax.scan`` carries ``S``.
  ``exp(−G_j)`` alone overflows under a strong decay, so a pair term is
  formed from differences that stay ≤ 0: sub-blocks of ``_SUB`` tokens,
  exact differences inside one, re-based at the later sub-block's start
  across two.  Positions at or past ``seq_lens`` get ``g = 0`` and ``β =
  0``: they decay nothing and write nothing, so the state after the padded
  length is the state at ``seq_lens − 1``;
- :func:`kda_step` is the one-token recurrence of a decode step.

The depthwise causal convolutions in front (q | k | v) are
``mamba2.conv_prefill`` / ``conv_step``.  Decay arithmetic and the
triangular solve in float32, the products against the state in the inputs'
type with float32 accumulation.  On a TPU :func:`kda_prefill` is one Pallas
kernel a layer (``ops/pallas/kda.py``: heads of 128, nothing but inputs and
outputs in HBM); the plain ``jax.numpy`` below is the CPU path and its
oracle.  ``state_shapes`` is the one place the per-slot state of a layer is
spelt; the serving cache sizes its state pools from it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_SUB = 16       # sub-block of a chunk inside which decay differences are exact
_HI = lax.Precision.HIGHEST


def state_shapes(heads: int, head_dim: int, conv: int) -> dict:
    """One KDA layer's state of one sequence: name -> shape.  ``kda_s``:
    the [keys, values] state of every head; ``kda_conv``: the last
    ``conv - 1`` inputs of the convolutions over q | k | v."""
    return {"kda_s": (heads, head_dim, head_dim),
            "kda_conv": (conv - 1, 3 * heads * head_dim)}


def kda_step(state, q, k, v, g, beta):
    """One token.  state [B, H, K, V] float32; q, k [B, H, K] (normalised,
    q scaled); v [B, H, V]; g [B, H, K] float32 log-decay (<= 0); beta
    [B, H] float32.  Returns (o [B, H, V] float32, state')."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = state * jnp.exp(g.astype(f32))[..., None]
    # both read S' once: S_tᵀ q = S'ᵀ q + (k · q) u
    pred = jnp.sum(s * k[..., None], axis=-2)
    sq = jnp.sum(s * q[..., None], axis=-2)
    u = beta.astype(f32)[..., None] * (v - pred)
    o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, s + k[..., None] * u[..., None, :]


def _pairs(a, b, cum, strict: bool):
    """``P_ij = Σ_c a_ic b_jc exp(cum_ic − cum_jc)`` for j <= i (j < i
    when ``strict``) inside each chunk, 0 elsewhere.  a, b, cum [..., Q,
    K] float32, ``cum`` the decay's inclusive running sum over the chunk
    (non-increasing along Q).  Returns [..., Q, Q] float32."""
    q = a.shape[-2]
    n = q // _SUB
    lead = a.shape[:-2]
    a4, b4, c4 = (x.reshape(*lead, n, _SUB, x.shape[-1])
                  for x in (a, b, cum))
    # inside a sub-block: the differences themselves, masked before exp
    i, j = jnp.arange(_SUB)[:, None], jnp.arange(_SUB)[None, :]
    keep = (i > j) if strict else (i >= j)
    diff = jnp.where(keep[..., None], c4[..., :, None, :]
                     - c4[..., None, :, :], -jnp.inf)
    diag = jnp.sum(a4[..., :, None, :] * b4[..., None, :, :] * jnp.exp(diff),
                   axis=-1)                                   # [.., n, S, S]
    rows = []
    for r in range(n):
        blocks = []
        if r:
            # against the earlier sub-blocks: both factors re-based at
            # the running sum just before sub-block r, so both are <= 1
            base = cum[..., r * _SUB - 1, :][..., None, :]
            left = a4[..., r, :, :] * jnp.exp(c4[..., r, :, :] - base)
            right = b[..., :r * _SUB, :] * jnp.exp(
                base - cum[..., :r * _SUB, :])
            blocks.append(jnp.einsum("...ic,...jc->...ij", left, right,
                                     precision=_HI))
        blocks.append(diag[..., r, :, :])
        if r < n - 1:
            blocks.append(jnp.zeros((*lead, _SUB, q - (r + 1) * _SUB),
                                    jnp.float32))
        rows.append(jnp.concatenate(blocks, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _unit_lower_inverse(low):
    """(I + low)⁻¹ for strictly lower-triangular ``low`` [..., Q, Q]: the
    Neumann series of a nilpotent matrix, as a product of doublings.  (On
    the chip the 64-wide products cost less than inverting diagonal blocks
    of 16 and merging pairs, a fourteenth of the products in 3.6 x the
    calls: 139 against 144 ms a 4,096-token pass, PERF.md section 6.)"""
    q = low.shape[-1]
    eye = jnp.eye(q, dtype=low.dtype)
    mm = lambda x, y: jnp.einsum("...ij,...jk->...ik", x, y, precision=_HI)
    power, inv, span = -low, eye - low, 2
    while span < q:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


def kda_prefill(q, k, v, g, beta, seq_lens=None, chunk: int = 64,
                state=None, impl: str = "auto", interpret=None):
    """A whole (right-padded) sequence.  q, k [B, T, H, K] (normalised, q
    scaled); v [B, T, H, V]; g [B, T, H, K] float32 log-decay (<= 0);
    beta [B, T, H] float32; seq_lens [B] valid lengths (None = all T);
    ``state`` [B, H, K, V] the state to start from (None = zeros).
    ``impl``: "kernel" (``ops/pallas/kda.py``), "reference" (the plain
    XLA below: the CPU path and the kernel's oracle) or "auto" (kernel on
    a TPU); a shape the kernel does not take runs the reference and the
    routing census says so.  Returns (o [B, T, H, V] float32, the state
    at each row's last valid token [B, H, K, V] float32)."""
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import kda as kernel

    f32 = jnp.float32
    t, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    if chunk % _SUB:
        raise ValueError(f"chunk {chunk} must be a multiple of {_SUB}")
    g, beta = g.astype(f32), beta.astype(f32)
    if seq_lens is not None:
        valid = jnp.arange(t)[None, :, None] < seq_lens[:, None, None]
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid, beta, 0.0)
    route = pallas.resolve_impl(impl)
    if route == "kernel" and not kernel.supports(dk, dv, chunk):
        route = "reference_shape"
    pallas.note_route("kda_prefill", route)
    if route == "kernel":
        return _fused(q, k, v, g, beta, state, chunk,
                      pallas.resolve_interpret(interpret))
    return _chunked(q, k, v, g, beta, state, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused(q, k, v, g, beta, state, chunk, interpret):
    """The kernel, differentiated as :func:`_chunked` is (it has no
    backward pass of its own: training a ``K`` layer is XLA's autodiff of
    the plain form, as it was)."""
    from paddle_tpu.ops.pallas import kda as kernel

    return kernel.kda_chunk_prefill(q, k, v, g, beta, chunk, state,
                                    interpret=interpret)


def _fused_fwd(q, k, v, g, beta, state, chunk, interpret):
    return (_fused(q, k, v, g, beta, state, chunk, interpret),
            (q, k, v, g, beta, state))


def _fused_bwd(chunk, interpret, saved, ct):
    return jax.vjp(lambda *a: _chunked(*a, chunk), *saved)[1](ct)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _chunked(q, k, v, g, beta, state, chunk):
    """:func:`kda_prefill` past its masking, in plain XLA: g, beta float32
    and already 0 at and past each row's length."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    dv, dtype = v.shape[-1], v.dtype
    pad = -t % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // chunk

    def chunks(x):      # [B, T, H, ...] -> [B, H, nc, Q, ...]
        x = x.reshape(bsz, nc, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    # (re-laid out in the inputs' type: the copies are half as wide)
    qf, kf, vf, g = (chunks(x).astype(f32) for x in (q, k, v, g))
    beta = chunks(beta)                                  # [B, H, nc, Q]
    # the running sum inside a chunk, inclusive and <= 0, as a product
    # with a triangle of ones (a cumsum lowers to a windowed reduction
    # several times as slow on the chip)
    cum = jnp.einsum("ij,...jc->...ic", jnp.tril(jnp.ones((chunk, chunk),
                                                          f32)), g,
                     precision=_HI)
    # the coupled system of a chunk, solved once for V's part and once for
    # the part that multiplies the state the chunk starts from
    inv = _unit_lower_inverse(
        beta[..., None] * _pairs(kf, kf, cum, strict=True))
    k_in = kf * jnp.exp(cum)                             # k read by S_0
    mm = lambda x, y: jnp.einsum("...ij,...jc->...ic", x, y, precision=_HI)
    w_v = mm(inv, beta[..., None] * vf).astype(dtype)
    w_k = mm(inv, beta[..., None] * k_in).astype(dtype)
    attn = _pairs(qf, kf, cum, strict=False).astype(dtype)
    q_in = (qf * jnp.exp(cum)).astype(dtype)
    total = cum[..., -1:, :]                             # [B, H, nc, 1, K]
    k_out = (kf * jnp.exp(total - cum)).astype(dtype)    # k by the chunk's end
    decay = jnp.exp(total[..., 0, :])                    # [B, H, nc, K]

    def step(s, x):
        w_v, w_k, attn, q_in, k_out, decay = x
        sd = s.astype(dtype)
        u = w_v.astype(f32) - jnp.einsum(
            "bhqk,bhkv->bhqv", w_k, sd, preferred_element_type=f32)
        o = jnp.einsum("bhqk,bhkv->bhqv", q_in, sd,
                       preferred_element_type=f32) + jnp.einsum(
            "bhqj,bhjv->bhqv", attn, u.astype(dtype),
            preferred_element_type=f32)
        s = s * decay[..., None] + jnp.einsum(
            "bhqk,bhqv->bhkv", k_out, u.astype(dtype),
            preferred_element_type=f32)
        return s, o

    s0 = jnp.zeros((bsz, h, dk, dv), f32) if state is None \
        else state.astype(f32)
    final, o = lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 2, 0) for x in (w_v, w_k, attn, q_in, k_out, decay)))
    # [nc, B, H, Q, V] -> [B, T, H, V]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(bsz, t + pad, h, dv)[:, :t]
    return o, final
