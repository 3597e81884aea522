"""Recurrent cells + masked scans — successor of the reference's hand-written
LSTM/GRU CUDA kernels (``paddle/cuda/src/hl_cuda_lstm.cu``,
``hl_gpu_gru.cuh``), ``LstmLayer``/``GruLayer``, and the SequenceToBatch
batch-parallel scheduler (``paddle/gserver/layers/SequenceToBatch.cpp``).

TPU-native design: the whole input projection (x @ W for all gates, the bulk
of the FLOPs) is hoisted OUT of the recurrence as one big MXU matmul over
[B*T, D]; only the small recurrent matmul runs inside ``lax.scan``.  Ragged
batches use masks to freeze state past each row's length — the same effect as
SequenceToBatch's same-length grouping, without data movement.

Gate layout follows the reference (``hl_lstm_ops``): LSTM gates ordered
[input, forget, cell(candidate), output]; GRU gates [update, reset, candidate].
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.lod import SequenceBatch
from paddle_tpu.ops import activations as act
from paddle_tpu.ops.math import matmul


class LSTMState(NamedTuple):
    h: jax.Array  # [B, D]
    c: jax.Array  # [B, D]


def lstm_cell(
    xw: jax.Array,  # [B, 4D] precomputed x @ W_x (+ bias)
    state: LSTMState,
    w_h: jax.Array,  # [D, 4D]
    gate_act=act.sigmoid,
    state_act=act.tanh,
    out_act=None,  # activation on c before the output gate (reference act)
    peephole: jax.Array | None = None,  # [3D]: W_ci, W_cf, W_co diagonals
) -> LSTMState:
    d = state.h.shape[-1]
    gates = xw + matmul(state.h, w_h)
    gi, gf, gg, go = (gates[:, k * d : (k + 1) * d] for k in range(4))
    if peephole is not None:
        # reference LstmLayer peephole connections (hl_cpu_lstm.h):
        # i/f see c_{t-1}, o sees c_t
        gi = gi + peephole[0 * d : 1 * d] * state.c
        gf = gf + peephole[1 * d : 2 * d] * state.c
    i = gate_act(gi)
    f = gate_act(gf)
    g = state_act(gg)
    c = f * state.c + i * g
    if peephole is not None:
        go = go + peephole[2 * d : 3 * d] * c
    o = gate_act(go)
    h = o * (out_act or state_act)(c)
    return LSTMState(h=h, c=c)


def gru_cell(
    xw: jax.Array,  # [B, 3D] precomputed x @ W_x (+ bias)
    h: jax.Array,  # [B, D]
    w_h: jax.Array,  # [D, 2D] update+reset recurrent weights
    w_hc: jax.Array,  # [D, D] candidate recurrent weights
    gate_act=act.sigmoid,
    state_act=act.tanh,
) -> jax.Array:
    d = h.shape[-1]
    ur = xw[:, : 2 * d] + matmul(h, w_h)
    u = gate_act(ur[:, :d])
    r = gate_act(ur[:, d : 2 * d])
    c = state_act(xw[:, 2 * d :] + matmul(r * h, w_hc))
    # reference gru: h' = u*h + (1-u)*c  (hl_gpu_gru.cuh frameOutput)
    return u * h + (1.0 - u) * c


def _masked_scan(step, x: SequenceBatch, init_state, reverse: bool = False):
    """Run `step` over time with per-row freezing past length.

    step: (state, xt[B, ...]) -> new_state; state is a pytree of [B, D] arrays.
    """
    mask = x.mask()  # [B, T]
    xs = jnp.swapaxes(x.data, 0, 1)  # [T, B, ...]
    ms = jnp.swapaxes(mask, 0, 1)  # [T, B]

    def body(state, inp):
        xt, mt = inp
        new = step(state, xt)
        mt = mt[:, None]
        frozen = jax.tree.map(lambda n, o: mt * n + (1.0 - mt) * o, new, state)
        return frozen, frozen

    last, ys = jax.lax.scan(body, init_state, (xs, ms), reverse=reverse)
    ys = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), ys)  # [B, T, D]
    return last, ys


def lstm(
    x: SequenceBatch,  # data [B, T, Din] already projected? no: raw input
    w_x: jax.Array,  # [Din, 4D]
    w_h: jax.Array,  # [D, 4D]
    b: jax.Array | None,  # [4D]
    reverse: bool = False,
    gate_act=act.sigmoid,
    state_act=act.tanh,
    init: LSTMState | None = None,
):
    """Full LSTM over a ragged batch. Returns (SequenceBatch of h, last LSTMState).

    (≅ LstmLayer with lstmemory semantics: the reference's ``lstmemory`` takes
    a pre-projected input from a preceding mixed/fc layer; here w_x may be
    identity-folded by passing the projection separately — the layer API keeps
    the reference contract.)
    """
    b_, t = x.batch_size, x.max_len
    d = w_h.shape[0]
    if init is None:
        init = LSTMState(
            h=jnp.zeros((b_, d), jnp.float32), c=jnp.zeros((b_, d), jnp.float32)
        )
    # standard activations + fused routing on: fold the input projection
    # into the time-loop kernel (x streams once, W_x and W_h both
    # VMEM-resident — the [B, T, 4D] xw slab never touches HBM)
    if (gate_act is act.sigmoid and state_act is act.tanh
            and fused_input_on() and _fused_fits(b_, d, 4, w_x, w_h)):
        return lstm_fi(x, w_x, b, w_h, init, reverse=reverse)
    xw = matmul(x.data.reshape(b_ * t, -1), w_x)
    if b is not None:
        xw = xw + b
    xw = xw.reshape(b_, t, 4 * d)

    # standard cell (sigmoid gates, tanh state) -> the fused Pallas
    # sequence kernel: one program iterates time with w_h VMEM-resident,
    # replacing the lax.scan whose per-step residual stacking dominates
    # (ops/pallas/lstm.py; ≅ hl_lstm_parallel_forward's role)
    if gate_act is act.sigmoid and state_act is act.tanh:
        return lstm_fused(SequenceBatch(xw, x.length), w_h, init,
                          reverse=reverse)

    def step(state, xt):
        return lstm_cell(xt, state, w_h, gate_act, state_act)

    last, ys = _masked_scan(step, SequenceBatch(xw, x.length), init, reverse=reverse)
    return SequenceBatch(data=ys.h, length=x.length), last


def _fused_fits(b: int, d: int, gates: int, *weights) -> bool:
    """VMEM budget check for the fused sequence kernels: resident weights
    plus ~8 double-buffered [B, gates*D] slabs must fit the 64 MB scoped
    limit (ops/pallas/lstm.py compiler_params) with headroom.  Float16 is
    rejected too (the kernels' io/cotangent plumbing is f32/bf16 only)."""
    if any(w.dtype == jnp.float16 for w in weights):
        return False
    resident = sum(w.nbytes for w in weights)
    slabs = 8 * b * gates * d * weights[0].dtype.itemsize
    return resident + slabs < 48 * 1024 * 1024


def fused_input_on() -> bool:
    """True when the fused-input / remat / bidirectional recurrence
    kernels should engage: the ``fused_kernels`` flag resolves on AND a
    real TPU is present.  The CPU path keeps the unfused composition
    (external x @ W_x matmul + the pre-projected kernels), so the bench
    ablation's flag-off/flag-on trajectories stay bit-identical there —
    the same convention as ops/nn's TPP conv routing."""
    from paddle_tpu.ops.pallas import on_tpu
    from paddle_tpu.ops.pallas.tpp import fused_enabled

    return fused_enabled() and on_tpu()


def lstm_fused(xw: SequenceBatch, w_h: jax.Array,
               init: LSTMState, peephole: jax.Array | None = None,
               reverse: bool = False, remat: bool | None = None):
    """Standard-activation LSTM over precomputed gate inputs via the fused
    Pallas sequence kernel (ops/pallas/lstm.py); the shared fast path of
    ``lstm`` and the ``lstmemory`` layer.  Falls back to the lax.scan
    cell when the weights exceed the kernel's VMEM budget.

    xw: SequenceBatch of [B, T, 4D] pre-projected gate inputs;
    peephole: optional [3D] flat [W_ci, W_cf, W_co] diagonals;
    remat (None = the ``fused_kernels`` flag on TPU): recompute gates in
    the reverse kernel instead of storing the [T, B, 4D] residual slab.
    Returns (SequenceBatch of h, last LSTMState).
    """
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.ops.pallas import default_interpret, note_route
    from paddle_tpu.ops.pallas.lstm import lstm_seq

    d = w_h.shape[0]
    mask = xw.mask().astype(jnp.float32)
    # honor the dtype policy exactly like matmul() would: the bf16 flag
    # (or a mixed policy pair) resolves both kernel operands to bf16,
    # the pure-f32 compat surface keeps true-f32 kernel matmuls
    data, w_h_c = dt.cast_for_matmul(xw.data, w_h)
    fits = _fused_fits(xw.batch_size, d, 4, w_h_c)
    note_route("lstm_seq", "kernel" if fits else "scan")
    if not fits:
        def step(state, xt):
            return lstm_cell(xt, state, w_h, peephole=peephole)
        last, ys = _masked_scan(
            step, SequenceBatch(xw.data, xw.length), init, reverse=reverse)
        return SequenceBatch(data=ys.h, length=xw.length), last
    peep = (jnp.zeros((3, d), w_h_c.dtype) if peephole is None
            else peephole.reshape(3, d).astype(w_h_c.dtype))
    if remat is None:
        remat = fused_input_on()
    hs, (hT, cT) = lstm_seq(
        data, mask, w_h_c, peep,
        init.h.astype(w_h_c.dtype), init.c, reverse, default_interpret(),
        remat)
    # outputs keep the CALLER's dtype, like matmul() does under the flag
    out_dtype = xw.data.dtype
    hs = hs.astype(out_dtype)
    return (SequenceBatch(data=hs, length=xw.length),
            LSTMState(h=hT.astype(out_dtype), c=cT.astype(out_dtype)))


def lstm_fi(x: SequenceBatch, w_x: jax.Array, b: jax.Array | None,
            w_h: jax.Array, init: LSTMState,
            peephole: jax.Array | None = None, reverse: bool = False):
    """Fused-input LSTM: raw x [B, T, E] + both weight matrices through
    the ``lstm_seq_fi`` kernel (x streams once, W_x/W_h VMEM-resident,
    no [T, B, 4D] gate-input slab in HBM).  Callers gate on
    :func:`fused_input_on` + :func:`_fused_fits`; dtype policy matches
    :func:`lstm_fused`.  Returns (SequenceBatch of h, last LSTMState)."""
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.ops.pallas import default_interpret, note_route
    from paddle_tpu.ops.pallas.lstm import lstm_seq_fi

    note_route("lstm_seq_fi", "kernel")
    d = w_h.shape[0]
    mask = x.mask().astype(jnp.float32)
    data, w_x_c, w_h_c = dt.cast_for_matmul(x.data, w_x, w_h)
    bias = (jnp.zeros((4 * d,), jnp.float32) if b is None
            else b.astype(jnp.float32))
    peep = (jnp.zeros((3, d), w_h_c.dtype) if peephole is None
            else peephole.reshape(3, d).astype(w_h_c.dtype))
    hs, (hT, cT) = lstm_seq_fi(
        data, mask, w_x_c, bias, w_h_c, peep,
        init.h.astype(w_h_c.dtype), init.c, reverse, default_interpret(),
        True)
    out_dtype = x.data.dtype
    return (SequenceBatch(data=hs.astype(out_dtype), length=x.length),
            LSTMState(h=hT.astype(out_dtype), c=cT.astype(out_dtype)))


def bilstm_fused(x: SequenceBatch, fw: tuple, bw: tuple):
    """Bidirectional LSTM over raw inputs: ONE kernel runs both
    directions over a single residency of all four weight matrices when
    the fused routing is on (``ops/pallas/lstm.bilstm_seq``); otherwise
    the exact unfused composition (two projections + two pre-projected
    passes).  ``fw``/``bw`` are (w_x [E, 4D], bias [4D] | None,
    w_h [D, 4D], peephole [3D] | None) per direction.  Returns the
    concatenated SequenceBatch [B, T, 2D] (forward features first)."""
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.ops.math import matmul
    from paddle_tpu.ops.pallas import default_interpret, note_route
    from paddle_tpu.ops.pallas.lstm import bilstm_seq

    w_x_f, b_f, w_h_f, peep_f = fw
    w_x_b, b_b, w_h_b, peep_b = bw
    d = w_h_f.shape[0]
    b_, t = x.batch_size, x.max_len
    zero_state = LSTMState(h=jnp.zeros((b_, d), jnp.float32),
                           c=jnp.zeros((b_, d), jnp.float32))
    use_kernel = (fused_input_on()
                  and _fused_fits(b_, d, 4, *dt.cast_for_matmul(
                      x.data, w_x_f, w_h_f, w_x_b, w_h_b)[1:]))
    note_route("bilstm_seq", "kernel" if use_kernel else "composed")
    if not use_kernel:
        def one(w_x, bias, w_h, peephole, reverse):
            xw = matmul(x.data.reshape(b_ * t, -1), w_x)
            if bias is not None:
                xw = xw + bias
            out, _ = lstm_fused(
                SequenceBatch(xw.reshape(b_, t, 4 * d), x.length), w_h,
                zero_state, peephole=peephole, reverse=reverse)
            return out

        f = one(w_x_f, b_f, w_h_f, peep_f, False)
        r = one(w_x_b, b_b, w_h_b, peep_b, True)
        return SequenceBatch(
            data=jnp.concatenate([f.data, r.data], axis=-1),
            length=x.length)

    data, wxf, whf, wxb, whb = dt.cast_for_matmul(
        x.data, w_x_f, w_h_f, w_x_b, w_h_b)
    mask = x.mask().astype(jnp.float32)

    def prep(bias, peephole):
        bias = (jnp.zeros((4 * d,), jnp.float32) if bias is None
                else bias.astype(jnp.float32))
        peep = (jnp.zeros((3, d), whf.dtype) if peephole is None
                else peephole.reshape(3, d).astype(whf.dtype))
        return bias, peep

    bf, pf = prep(b_f, peep_f)
    bb, pb = prep(b_b, peep_b)
    z = zero_state
    hs_f, hs_b, _, _ = bilstm_seq(
        data, mask, wxf, bf, whf, pf, wxb, bb, whb, pb,
        z.h.astype(whf.dtype), z.c, z.h.astype(whb.dtype), z.c,
        default_interpret(), True)
    out_dtype = x.data.dtype
    return SequenceBatch(
        data=jnp.concatenate([hs_f, hs_b], axis=-1).astype(out_dtype),
        length=x.length)


def gru_fused(xw: SequenceBatch, w_h: jax.Array, w_hc: jax.Array,
              init: jax.Array, reverse: bool = False,
              remat: bool | None = None):
    """Standard-activation GRU over precomputed gate inputs via the fused
    Pallas sequence kernel (ops/pallas/gru.py); shared fast path of
    ``gru`` and the ``grumemory`` layer.  ``remat`` (None = the
    ``fused_kernels`` flag on TPU) drops the u/r/c residual slab.
    Returns (SequenceBatch, last h).
    """
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.ops.pallas import default_interpret, note_route
    from paddle_tpu.ops.pallas.gru import gru_seq

    mask = xw.mask().astype(jnp.float32)
    # same dtype-policy rule as matmul() (see lstm_fused)
    data, w_h_c, w_hc_c = dt.cast_for_matmul(xw.data, w_h, w_hc)
    fits = _fused_fits(xw.batch_size, w_hc.shape[0], 3, w_h_c, w_hc_c)
    note_route("gru_seq", "kernel" if fits else "scan")
    if not fits:
        def step(h, xt):
            return gru_cell(xt, h, w_h, w_hc)
        last, ys = _masked_scan(
            step, SequenceBatch(xw.data, xw.length), init, reverse=reverse)
        return SequenceBatch(data=ys, length=xw.length), last
    if remat is None:
        remat = fused_input_on()
    hs, hT = gru_seq(data, mask, w_h_c, w_hc_c,
                     init.astype(w_h_c.dtype), reverse, default_interpret(),
                     remat)
    hs = hs.astype(xw.data.dtype)
    return (SequenceBatch(data=hs, length=xw.length),
            hT.astype(xw.data.dtype))


def gru_fi(x: SequenceBatch, w_x: jax.Array, b: jax.Array | None,
           w_h: jax.Array, w_hc: jax.Array, init: jax.Array,
           reverse: bool = False):
    """Fused-input GRU: raw x through the ``gru_seq_fi`` kernel (x
    streams once; W_x, W_h, W_hc VMEM-resident).  Callers gate on
    :func:`fused_input_on` + :func:`_fused_fits`.  Returns
    (SequenceBatch of h, last h)."""
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.ops.pallas import default_interpret, note_route
    from paddle_tpu.ops.pallas.gru import gru_seq_fi

    note_route("gru_seq_fi", "kernel")
    d = w_hc.shape[0]
    mask = x.mask().astype(jnp.float32)
    data, w_x_c, w_h_c, w_hc_c = dt.cast_for_matmul(x.data, w_x, w_h, w_hc)
    bias = (jnp.zeros((3 * d,), jnp.float32) if b is None
            else b.astype(jnp.float32))
    hs, hT = gru_seq_fi(
        data, mask, w_x_c, bias, w_h_c, w_hc_c,
        init.astype(w_h_c.dtype), reverse, default_interpret(), True)
    out_dtype = x.data.dtype
    return (SequenceBatch(data=hs.astype(out_dtype), length=x.length),
            hT.astype(out_dtype))


def bigru_fused(x: SequenceBatch, fw: tuple, bw: tuple):
    """Bidirectional GRU over raw inputs: ONE kernel runs both
    directions over a single residency of all six weight matrices when
    the fused routing is on (``ops/pallas/gru.bigru_seq``); otherwise
    the exact unfused composition (two projections + two pre-projected
    passes).  ``fw``/``bw`` are (w_x [E, 3D], bias [3D] | None,
    w_h [D, 2D], w_hc [D, D]) per direction.  Returns the concatenated
    SequenceBatch [B, T, 2D] (forward features first)."""
    from paddle_tpu.core import dtype as dt
    from paddle_tpu.ops.math import matmul
    from paddle_tpu.ops.pallas import default_interpret, note_route
    from paddle_tpu.ops.pallas.gru import bigru_seq

    w_x_f, b_f, w_h_f, w_hc_f = fw
    w_x_b, b_b, w_h_b, w_hc_b = bw
    d = w_hc_f.shape[0]
    b_, t = x.batch_size, x.max_len
    init = jnp.zeros((b_, d), jnp.float32)
    use_kernel = (fused_input_on()
                  and _fused_fits(b_, d, 3, *dt.cast_for_matmul(
                      x.data, w_x_f, w_h_f, w_hc_f,
                      w_x_b, w_h_b, w_hc_b)[1:]))
    note_route("bigru_seq", "kernel" if use_kernel else "composed")
    if not use_kernel:
        def one(w_x, bias, w_h, w_hc, reverse):
            xw = matmul(x.data.reshape(b_ * t, -1), w_x)
            if bias is not None:
                xw = xw + bias
            out, _ = gru_fused(
                SequenceBatch(xw.reshape(b_, t, 3 * d), x.length), w_h,
                w_hc, init, reverse=reverse)
            return out

        f = one(w_x_f, b_f, w_h_f, w_hc_f, False)
        r = one(w_x_b, b_b, w_h_b, w_hc_b, True)
        return SequenceBatch(
            data=jnp.concatenate([f.data, r.data], axis=-1),
            length=x.length)

    data, wxf, whf, whcf, wxb, whb, whcb = dt.cast_for_matmul(
        x.data, w_x_f, w_h_f, w_hc_f, w_x_b, w_h_b, w_hc_b)
    mask = x.mask().astype(jnp.float32)

    def prep(bias):
        return (jnp.zeros((3 * d,), jnp.float32) if bias is None
                else bias.astype(jnp.float32))

    hs_f, hs_b, _, _ = bigru_seq(
        data, mask, wxf, prep(b_f), whf, whcf, wxb, prep(b_b), whb, whcb,
        init.astype(whf.dtype), init.astype(whb.dtype),
        default_interpret(), True)
    out_dtype = x.data.dtype
    return SequenceBatch(
        data=jnp.concatenate([hs_f, hs_b], axis=-1).astype(out_dtype),
        length=x.length)


def gru(
    x: SequenceBatch,  # [B, T, Din]
    w_x: jax.Array,  # [Din, 3D]
    w_h: jax.Array,  # [D, 2D]
    w_hc: jax.Array,  # [D, D]
    b: jax.Array | None,  # [3D]
    reverse: bool = False,
    gate_act=act.sigmoid,
    state_act=act.tanh,
    init: jax.Array | None = None,
):
    """Full GRU over a ragged batch. Returns (SequenceBatch of h, last h)."""
    b_, t = x.batch_size, x.max_len
    d = w_h.shape[0]
    if init is None:
        init = jnp.zeros((b_, d), jnp.float32)
    # fused-input routing: see lstm() above
    if (gate_act is act.sigmoid and state_act is act.tanh
            and fused_input_on() and _fused_fits(b_, d, 3, w_x, w_h, w_hc)):
        return gru_fi(x, w_x, b, w_h, w_hc, init, reverse=reverse)
    xw = matmul(x.data.reshape(b_ * t, -1), w_x)
    if b is not None:
        xw = xw + b
    xw = xw.reshape(b_, t, 3 * d)

    if gate_act is act.sigmoid and state_act is act.tanh:
        return gru_fused(SequenceBatch(xw, x.length), w_h, w_hc, init,
                         reverse=reverse)

    def step(h, xt):
        return gru_cell(xt, h, w_h, w_hc, gate_act, state_act)

    last, ys = _masked_scan(step, SequenceBatch(xw, x.length), init, reverse=reverse)
    return SequenceBatch(data=ys, length=x.length), last


def simple_rnn(
    x: SequenceBatch,
    w_x: jax.Array,  # [Din, D]
    w_h: jax.Array,  # [D, D]
    b: jax.Array | None,
    activation=act.tanh,
    reverse: bool = False,
    init: jax.Array | None = None,
):
    """Vanilla RNN (≅ RecurrentLayer): h_t = act(x_t W + h_{t-1} U + b)."""
    b_, t = x.batch_size, x.max_len
    d = w_h.shape[0]
    xw = matmul(x.data.reshape(b_ * t, -1), w_x)
    if b is not None:
        xw = xw + b
    xw = xw.reshape(b_, t, d)
    if init is None:
        init = jnp.zeros((b_, d), jnp.float32)

    def step(h, xt):
        return activation(xt + matmul(h, w_h))

    last, ys = _masked_scan(step, SequenceBatch(xw, x.length), init, reverse=reverse)
    return SequenceBatch(data=ys, length=x.length), last


def bidirectional(fwd_fn, bwd_fn, x: SequenceBatch):
    """Run forward+reverse passes and concat features (≅ bidirectional_lstm
    in trainer_config_helpers/networks.py)."""
    f, _ = fwd_fn(x)
    r, _ = bwd_fn(x)
    return SequenceBatch(
        data=jnp.concatenate([f.data, r.data], axis=-1), length=x.length
    )
