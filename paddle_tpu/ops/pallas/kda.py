"""The chunked gated delta rule of one KDA layer's prefill pass as ONE
Pallas TPU kernel (``ops/kda.py`` has the recurrence, the WY form and the
plain-XLA twin this is tested against).

Grid ``(row, head group, chunk)``: row and head group parallel, the chunk
axis in order.  A step reads one chunk of ``GROUP`` heads where they lie
— q, k, v ``[B, T, H·D]`` in the model's type, g float32, blocks ``(1,
chunk, GROUP·D)`` — and writes o ``[B, T, H·D]`` float32; the running
state of the group, ``[GROUP, D, D]`` float32, stays in VMEM scratch from
chunk 0, where it is loaded from ``state``, to the last, where it is
written out.  Everything between — the decay's running sum, the pair
sums, the triangular solve, the products against the state — lives in
VMEM.

Every operation of a step is ONE over all its heads (arrays ``[heads,
Q, ·]``, a product a head batched), for two reasons the chip gave
(PERF.md section 6, PR 43).  A head's stages — pair sums, six stages of
the solve, the products against the state — are a chain of dependent
64-row products, and walked head by head the solve alone was half of a
step; stage by stage over the heads the other heads' products fill the
MXUs meanwhile.  And the kernel is traced and lowered in EVERY process
that builds a prefill program, before the compilation cache is even
asked: unrolled head by head its text cost that 30 s a program.

Inside a step (``G`` the running sum of g over the chunk):

- pair sums ``Σ_c a_ic k_jc exp(G_ic − G_jc)`` (a = k for j < i, a = q
  for j <= i) from exponents that are never positive, as ``ops/kda._pairs``
  forms them: exact differences inside a sub-block of ``_SUB`` tokens (one
  token distance at a time: a sublane roll, an ``exp``, a lane sum), and
  across sub-blocks BOTH factors re-based at the later block's start, so
  both are <= 1 (blocks of ``_SUB``, ``2·_SUB``, ... paired off: one
  product a level);
- ``(I + β ⊙ A)⁻¹`` by doubling, each squaring and the product that uses
  it one matrix product (``P · [P | inv]``);
- ``u = w_v − w_k S``, ``o = (q ⊙ exp G) S + attn · u``, ``S ← S ⊙
  exp(G_end) + (k ⊙ exp(G_end − G))ᵀ u``.

Precision: decays, pair sums and the solve are float32 with
``Precision.HIGHEST`` products (Mosaic's ``fp32`` contract precision: six
MXU passes a product; the only other it offers is its one-pass default);
the three products against ``S`` take their operands in the inputs' type
and accumulate in float32, as the twin's ``step`` does (float32 inputs:
``HIGHEST`` there too).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.ops.pallas import NEG_INF, pad_axis, round_up

HEAD_DIM = 128  # a head's keys (and values) fill the lanes of a vreg
_SUB = 16       # as ops/kda._SUB: exact decay differences inside
GROUP = 8       # heads a grid step (PERF.md section 6, PR 43: the sweep)
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def supports(head_dim: int, value_dim: int, chunk: int) -> bool:
    """The shapes the kernel takes: 128 keys and values a head, a chunk
    of whole sub-blocks."""
    return head_dim == value_dim == HEAD_DIM and chunk % _SUB == 0


def _bmm(a, b, contract=(2, 1), precision=_HI):
    """One product a head: a, b [heads, ·, ·], contracting ``contract``."""
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
        precision=precision, preferred_element_type=_F32)


def _pair_sums(q, k, cum):
    """(strict pair sums of k with k, inclusive ones of q with k), both
    [heads, Q, Q] float32, of every head's chunk: q, k, cum [heads, Q, D]
    float32."""
    heads, n, _ = q.shape
    row = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    ri = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    ci = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # (lax primitives in the unrolled loop: a jnp call traces a jitted
    # function and broadcasts through three equations, fifteen times over)
    each = lambda m, shape: lax.broadcast_in_dim(m, shape, (1, 2))
    lanes = lambda x: lax.broadcast_in_dim(lax.reduce_sum(x, (2,)),
                                           (heads, n, n), (0, 1))
    never = jnp.full(cum.shape, NEG_INF, _F32)
    akk = jnp.zeros((heads, n, n), _F32)
    aqk = lax.select(each(ri == ci, akk.shape), lanes(q * k), akk)
    # inside a sub-block, one distance at a time: exact differences
    inside, apart = row % _SUB, ri - ci
    for dist in range(1, _SUB):
        diff = lax.select(each(inside >= dist, cum.shape),
                          lax.sub(cum, pltpu.roll(cum, dist, 1)), never)
        ke = lax.mul(pltpu.roll(k, dist, 1), lax.exp(diff))
        put = each(apart == dist, akk.shape)
        akk = lax.select(put, lanes(lax.mul(k, ke)), akk)
        aqk = lax.select(put, lanes(lax.mul(q, ke)), aqk)
    # across: blocks of ``size`` paired off, both factors re-based at the
    # running sum just before the later block of the pair (so both are
    # <= 1); one product a level and head: the later blocks' rows of k and
    # q against the earlier blocks' rows of k, a pair's own columns kept
    size = _SUB
    while size < n:
        base = jnp.concatenate([
            jnp.broadcast_to(cum[:, min(lo + size, n) - 1:min(lo + size, n)],
                             (heads, min(2 * size, n - lo), cum.shape[2]))
            for lo in range(0, n, 2 * size)], axis=1)
        later = (row // size) % 2 == 1
        fac = jnp.exp(jnp.where(later, cum - base, base - cum))
        kf, qf = k * fac, q * fac
        rows = [slice(lo + size, min(lo + 2 * size, n))
                for lo in range(0, n - size, 2 * size)]
        both = _bmm(jnp.concatenate([kf[:, r] for r in rows]
                                    + [qf[:, r] for r in rows], axis=1),
                    jnp.where(later, 0.0, kf), (2, 2))
        # back to [Q, Q]: zeros for the earlier blocks' rows, and of a
        # later block's row only the columns of its own pair
        half, at, kk, qk = both.shape[1] // 2, 0, [], []
        for lo in range(0, n, 2 * size):
            mid, hi = min(lo + size, n), min(lo + 2 * size, n)
            blank = jnp.zeros((heads, mid - lo, n), _F32)
            kk += [blank, both[:, at:at + hi - mid]]
            qk += [blank, both[:, half + at:half + at + hi - mid]]
            at += hi - mid
        own = ri // (2 * size) == ci // (2 * size)
        akk = akk + jnp.where(own, jnp.concatenate(kk, axis=1), 0.0)
        aqk = aqk + jnp.where(own, jnp.concatenate(qk, axis=1), 0.0)
        size *= 2
    return akk, aqk


def _unit_lower_inverse(low):
    """(I + low)⁻¹ for strictly lower-triangular ``low`` [heads, Q, Q]:
    ``Π_k (I + (−low)^(2^k))``, each squaring and the product that uses it
    one matrix product a head (``P · [P | inv]``)."""
    n = low.shape[1]
    ri = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    ci = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    inv = jnp.where(ri == ci, 1.0, 0.0) - low
    power, span = _bmm(low, low), 4
    while True:
        # ``low^(span // 2)`` has zeros above row ``span // 2``: whole tiles
        # of 8 such rows are left out of the product
        top, last = span // 2 // 8 * 8, span >= n
        tall = lambda x: x if not top else jnp.concatenate(
            [jnp.zeros((x.shape[0], top, n), _F32), x], axis=1)
        both = _bmm(power[:, top:],
                    inv if last else jnp.concatenate([power, inv], axis=2))
        inv = inv + tall(both[:, :, -n:])
        if last:
            return inv
        power = tall(both[:, :, :n])
        span *= 2


def _chunk_kernel(beta_ref, q_ref, k_ref, v_ref, g_ref, s0_ref, o_ref, s_ref,
                  z_ref, *, group, dtype):
    c = pl.program_id(2)

    # the state rides the chunks TRANSPOSED ([heads, values, keys]: a
    # chunk's decay is a vector over keys, which then lies along lanes)
    @pl.when(c == 0)
    def _load():
        z_ref[...] = jnp.swapaxes(s0_ref[0], 1, 2)

    n, d = q_ref.shape[1], HEAD_DIM
    # the products against the state: one pass in bf16, all of float32's
    near = _HI if dtype == _F32 else None
    ri = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    ci = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # the inclusive running sum of every head at once (g is a block of
    # lanes a head), then every array head-major
    heads = lambda x: jnp.stack(
        [x[:, h * d:(h + 1) * d] for h in range(group)])
    cum = heads(jnp.dot(jnp.where(ri >= ci, 1.0, 0.0), g_ref[0],
                        precision=_HI, preferred_element_type=_F32))
    q, k, v = (heads(x[0].astype(_F32)) for x in (q_ref, k_ref, v_ref))
    beta = jnp.stack([beta_ref[0, 0, :, h:h + 1] for h in range(group)])
    akk, aqk = _pair_sums(q, k, cum)
    inv = _unit_lower_inverse(beta * akk)
    grow = jnp.exp(cum)
    w = _bmm(inv, jnp.concatenate([beta * v, beta * (k * grow)], axis=2))
    w_v, w_k = w[:, :, :d].astype(dtype), w[:, :, d:].astype(dtype)
    total = cum[:, n - 1:n]                                    # [G, 1, D]
    zt = z_ref[...]                                            # [G, V, K]
    sd = zt.astype(dtype)
    u = (w_v.astype(_F32) - _bmm(w_k, sd, (2, 2), near)).astype(dtype)
    o = _bmm((q * grow).astype(dtype), sd, (2, 2), near) \
        + _bmm(aqk.astype(dtype), u, precision=near)
    for h in range(group):
        o_ref[0, :, h * d:(h + 1) * d] = o[h]
    k_out = (k * jnp.exp(total - cum)).astype(dtype)
    z_ref[...] = zt * jnp.exp(total) + _bmm(u, k_out, (1, 1), near)

    @pl.when(c == pl.num_programs(2) - 1)
    def _store():
        s_ref[0] = jnp.swapaxes(z_ref[...], 1, 2)


def kda_chunk_prefill(q, k, v, g, beta, chunk: int, state=None,
                      interpret: bool = False, group: int | None = None):
    """q, k, v [B, T, H, 128]; g [B, T, H, 128] float32 log-decay (0 past
    a row's length); beta [B, T, H] float32 (0 past it); state [B, H, K,
    V] float32 or None.  Returns (o [B, T, H, 128] float32, the state
    after T tokens [B, H, K, V] float32)."""
    bsz, t, h, d = q.shape
    if not supports(d, v.shape[-1], chunk):
        raise ValueError(f"kda_chunk_prefill takes heads of {HEAD_DIM} and "
                         f"chunks of whole sub-blocks of {_SUB}, got "
                         f"{d} x {v.shape[-1]}, chunk {chunk}")
    dtype = jnp.dtype(v.dtype)
    group = min(group or GROUP, h)
    hp, tp = round_up(h, group), round_up(t, chunk)
    s0 = jnp.zeros((bsz, h, d, d), _F32) if state is None \
        else state.astype(_F32)
    s0 = pad_axis(s0, 1, hp)
    # zero heads and zero positions write nothing and read as zeros
    flat = lambda x: pad_axis(pad_axis(x, 2, hp), 1, tp).reshape(
        bsz, tp, hp * d)
    beta = pad_axis(pad_axis(beta.astype(_F32), 2, hp), 1, tp)
    beta = beta.reshape(bsz, tp, hp // group, group).swapaxes(1, 2)
    wide = pl.BlockSpec((1, chunk, group * d), lambda b, j, c: (b, c, j))
    held = pl.BlockSpec((1, group, d, d), lambda b, j, c: (b, j, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_chunk_kernel, group=group, dtype=dtype),
        name="kda_chunk_prefill",
        grid=(bsz, hp // group, tp // chunk),
        in_specs=[pl.BlockSpec((1, 1, chunk, group),
                               lambda b, j, c: (b, j, c, 0)),
                  wide, wide, wide, wide, held],
        out_specs=[wide, held],
        scratch_shapes=[pltpu.VMEM((group, d, d), _F32)],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, hp * d), _F32),
                   jax.ShapeDtypeStruct((bsz, hp, d, d), _F32)],
        compiler_params=tpu_compiler_params(
            # in order along the chunks: they share the resident state
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(beta, flat(q), flat(k), flat(v), flat(g.astype(_F32)), s0)
    o = o.reshape(bsz, tp, hp, d)[:, :t, :h]
    return o, s[:, :h]


def kda_chunk_prefill_reference(q, k, v, g, beta, chunk: int, state=None):
    """The kernel's oracle, same arguments and results: the WY form in
    plain XLA (``ops/kda._chunked``: a ``lax.scan`` over chunks)."""
    from paddle_tpu.ops import kda

    return kda._chunked(q, k, v, g, beta, state, chunk)
