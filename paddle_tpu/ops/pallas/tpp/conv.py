"""Direct convolution as BRGEMM over input patches + the fused
conv+BN+ReLU forward — the TPP instantiation for the conv hot paths.

im2col-free: instead of materializing the [N*OH*OW, KH*KW*Cin] patch
matrix (the reference's ``GemmConvOp``/``BlockExpandOp`` route), the
kernel iterates the KH*KW taps as the BRGEMM reduce dimension.  Grid
``(N, OH, KH)``: each step holds ONE padded input row in VMEM and, for
every kw tap, contracts the shifted (strided) row slice against the
``w[kh, kw]`` plane on the MXU — the patch "matrix" only ever exists as
a VMEM view.  The f32 accumulator tile carries across the KH steps and
is finished by the fused epilogue before its single HBM write:

- affine + ReLU (inference-mode conv+BN+ReLU: one pass, one write);
- per-channel sum/sum-of-squares of the raw conv output (training-mode
  BN statistics) accumulated in the same pass, so the separate
  reduction read of the conv output disappears.

1x1 stride-1 convolutions (over half of ResNet-50's FLOPs) lower to the
:func:`~paddle_tpu.ops.pallas.tpp.brgemm.brgemm` microkernel directly.

Backward passes never re-derive conv math: ``custom_vjp`` transposes
the SAME XLA convolution the reference path uses (``jax.linear_transpose``
— no forward recompute), and the BN+act backward is the exact vjp of the
reference normalize.  Gradients therefore match the unfused program to
accumulation-order tolerance.

``*_reference`` twins are the CPU production path and the test oracle
(``impl="auto"`` picks the kernel on TPU — the paged_attention
convention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.tpp.brgemm import (
    _kernel_impl as _brgemm_kernel_impl,
)
from paddle_tpu.compat import tpu_compiler_params
from paddle_tpu.core import dtype as dt
from paddle_tpu.ops.pallas import (
    mxu_precision,
    resolve_impl as _auto,
    resolve_interpret as _interpret,
    round_up,
)


def _pair(v):
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v)


# -- channel stats (single-pass BN statistics) --------------------------------


def channel_stats_reference(x):
    """(sum [C], sum of squares [C]) over all leading axes, f32."""
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    return jnp.sum(x2, axis=0), jnp.sum(x2 * x2, axis=0)


def _stats_kernel(x_ref, sum_ref, sumsq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

    xb = x_ref[...].astype(jnp.float32)
    sum_ref[...] += jnp.sum(xb, axis=0, keepdims=True)
    sumsq_ref[...] += jnp.sum(xb * xb, axis=0, keepdims=True)


def _stats_kernel_impl(x, interpret, block_rows=512):
    c = x.shape[-1]
    x2 = x.reshape(-1, c)
    r = x2.shape[0]
    bm = min(round_up(r, 8), block_rows)
    rp = round_up(r, bm)
    if rp != r:  # zero rows contribute nothing to either sum
        x2 = jnp.pad(x2, ((0, rp - r), (0, 0)))
    s, ss = pl.pallas_call(
        _stats_kernel,
        grid=(rp // bm,),
        in_specs=[pl.BlockSpec((bm, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * 2,
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2)
    return s[0], ss[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def channel_stats(x, impl="auto", interpret=None):
    """Fused per-channel (sum, sum-of-squares) over all leading axes —
    ONE read of ``x`` for both batch-norm moments."""
    if _auto(impl, "channel_stats") == "reference":
        return channel_stats_reference(x)
    return _stats_kernel_impl(x, _interpret(interpret))


def _channel_stats_fwd(x, impl, interpret):
    return channel_stats(x, impl, interpret), x


def _channel_stats_bwd(impl, interpret, x, cts):
    gs, gss = cts
    dx = (gs.astype(jnp.float32)
          + 2.0 * x.astype(jnp.float32) * gss.astype(jnp.float32))
    return (dx.astype(x.dtype),)


channel_stats.defvjp(_channel_stats_fwd, _channel_stats_bwd)


# -- direct convolution -------------------------------------------------------


def conv2d_direct_reference(x, w, stride=1, padding=0):
    """The unfused XLA convolution (``ops/nn.conv2d``'s lowering) — oracle
    and CPU path for :func:`conv2d_direct`."""
    from paddle_tpu.ops import nn

    return nn.conv2d_xla(x, w, stride=stride, padding=padding)


def _conv_kernel(x_ref, w_ref, *refs, kh_total, kw, sw, ow, act, affine,
                 stats, out_dtype):
    i = 0
    scale_ref = shift_ref = sum_ref = sumsq_ref = None
    if affine:
        scale_ref, shift_ref = refs[i], refs[i + 1]
        i += 2
    o_ref = refs[i]
    i += 1
    if stats:
        sum_ref, sumsq_ref = refs[i], refs[i + 1]
        i += 2
    acc_ref = refs[i]

    n = pl.program_id(0)
    oh = pl.program_id(1)
    kh = pl.program_id(2)

    @pl.when(kh == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xrow = x_ref[0, 0]  # [Wp, Cin] — one padded input row, VMEM-resident
    wk = w_ref[0]       # [KW, Cin, Cout] — this kh's tap planes
    acc = acc_ref[...]
    for kwi in range(kw):  # static tap loop: the BRGEMM over patches
        if sw == 1:
            a = xrow[kwi:kwi + ow, :]
        else:
            # strided patch rows via a leading-dim reshape (no strided
            # loads): take sw*ow contiguous columns, view as (ow, sw, C)
            a = xrow[kwi:kwi + sw * ow, :].reshape(ow, sw, -1)[:, 0, :]
        acc = acc + jnp.dot(a, wk[kwi],
                            preferred_element_type=jnp.float32,
                            precision=mxu_precision(w_ref))
    acc_ref[...] = acc

    @pl.when(kh == kh_total - 1)
    def _finalize():
        y = acc_ref[...]
        if stats:
            @pl.when((n == 0) & (oh == 0))
            def _zero():
                sum_ref[...] = jnp.zeros_like(sum_ref)
                sumsq_ref[...] = jnp.zeros_like(sumsq_ref)

            sum_ref[...] += jnp.sum(y, axis=0, keepdims=True)
            sumsq_ref[...] += jnp.sum(y * y, axis=0, keepdims=True)
        if affine:
            y = y * scale_ref[...] + shift_ref[...]
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        o_ref[0, 0] = y.astype(out_dtype)


def _direct_fwd_raw(x, w, strides, pads, scale, shift, act, stats,
                    interpret):
    """The fused conv pallas_call (no autodiff — wrapped by the custom_vjp
    entries).  Returns y [N, OH, OW, Cout] (+ (sum, sumsq) when stats)."""
    n, h, wdt, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = strides
    ph, pw = pads
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (wdt + 2 * pw - kw) // sw + 1
    out_dtype = x.dtype
    x_c, w_c = dt.cast_for_matmul(x, w)
    affine = scale is not None
    if affine:
        scale = scale.reshape(1, cout).astype(jnp.float32)
        shift = shift.reshape(1, cout).astype(jnp.float32)

    if kh == 1 and kw == 1 and ph == 0 and pw == 0:
        # 1x1 conv IS the BRGEMM microkernel (over half of ResNet-50's
        # FLOPs); stride just subsamples rows first
        xs = x_c[:, ::sh, ::sw, :] if (sh, sw) != (1, 1) else x_c
        a = xs.reshape(1, n * oh * ow, cin)
        b = w_c.reshape(1, cin, cout)
        outs = _brgemm_kernel_impl(a, b, scale[0] if affine else None,
                                   shift[0] if affine else None, act, stats,
                                   out_dtype, 256, 256, interpret)
        if stats:
            y, s, ss = outs
            return y.reshape(n, oh, ow, cout), s, ss
        return outs.reshape(n, oh, ow, cout)

    # padded width sized exactly for the widest strided tap slice
    need_w = kw - 1 + sw * ow
    xp = jnp.pad(x_c, ((0, 0), (ph, ph), (pw, need_w - wdt - 2 * pw + pw),
                       (0, 0)))
    operands = [xp, w_c]
    in_specs = [
        pl.BlockSpec((1, 1, need_w, cin),
                     lambda ni, ohi, khi: (ni, ohi * sh + khi, 0, 0)),
        pl.BlockSpec((1, kw, cin, cout), lambda ni, ohi, khi: (khi, 0, 0, 0)),
    ]
    if affine:
        operands += [scale, shift]
        in_specs += [pl.BlockSpec((1, cout), lambda ni, ohi, khi: (0, 0))] * 2
    out_shape = [jax.ShapeDtypeStruct((n, oh, ow, cout), out_dtype)]
    out_specs = [pl.BlockSpec((1, 1, ow, cout),
                              lambda ni, ohi, khi: (ni, ohi, 0, 0))]
    if stats:
        out_shape += [jax.ShapeDtypeStruct((1, cout), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((1, cout),
                                   lambda ni, ohi, khi: (0, 0))] * 2
    outs = pl.pallas_call(
        functools.partial(_conv_kernel, kh_total=kh, kw=kw, sw=sw, ow=ow,
                          act=act, affine=affine, stats=stats,
                          out_dtype=out_dtype),
        name="tpp_conv",
        grid=(n, oh, kh),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((ow, cout), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=(("arbitrary",) * 3 if stats else
                                 ("parallel", "parallel", "arbitrary")),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(*operands)
    if stats:
        return outs[0], outs[1][0], outs[2][0]
    return outs[0]


def _conv_input_grads(x, w, dy, strides, pads):
    """(dx, dw) by transposing the reference XLA convolution with
    ``jax.linear_transpose`` — the exact adjoint, no forward recompute."""
    x_c, w_c = dt.cast_for_matmul(x, w)
    prec = dt.dot_precision(x_c, w_c)
    ph, pw = pads
    pad = [(ph, ph), (pw, pw)]
    dn = ("NHWC", "HWIO", "NHWC")

    def f_x(xx):
        return lax.conv_general_dilated(xx, w_c, strides, pad,
                                        dimension_numbers=dn, precision=prec)

    def f_w(ww):
        return lax.conv_general_dilated(x_c, ww, strides, pad,
                                        dimension_numbers=dn, precision=prec)

    dy_c = dy.astype(x_c.dtype)
    dx = jax.linear_transpose(f_x, x_c)(dy_c)[0].astype(x.dtype)
    dw = jax.linear_transpose(f_w, w_c)(dy_c)[0].astype(w.dtype)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _direct(x, w, strides, pads, interpret):
    return _direct_fwd_raw(x, w, strides, pads, None, None, None, False,
                           interpret)


def _direct_fwd(x, w, strides, pads, interpret):
    return _direct(x, w, strides, pads, interpret), (x, w)


def _direct_bwd(strides, pads, interpret, res, dy):
    x, w = res
    return _conv_input_grads(x, w, dy, strides, pads)


_direct.defvjp(_direct_fwd, _direct_bwd)


def conv2d_direct(x, w, stride=1, padding=0, impl="auto", interpret=None):
    """Direct (im2col-free) 2-D convolution, NHWC / HWIO, groups=1,
    dilation=1.  Differentiable: backward transposes the XLA conv."""
    strides, pads = _pair(stride), _pair(padding)
    if _auto(impl, "conv2d_direct") == "reference":
        return conv2d_direct_reference(x, w, stride=strides, padding=pads)
    return _direct(x, w, strides, pads, _interpret(interpret))


# -- fused conv + batch-norm + activation -------------------------------------


def _bn_act_train(y_conv, gamma, beta, eps, act):
    """Reference train-mode BN(+act) ON a conv output — the exact math of
    ``ops/nn.batch_norm`` (single-pass E[x]/E[x^2], f32 moments,
    activation-dtype normalize).  Used both as the vjp target of the
    fused backward and inside the fused forward's normalize."""
    axes = tuple(range(y_conv.ndim - 1))
    mean = jnp.mean(y_conv, axis=axes, dtype=jnp.float32)
    m2 = jnp.mean(lax.square(y_conv.astype(jnp.float32)), axis=axes)
    var = jnp.maximum(m2 - lax.square(mean), 0.0)
    y = _bn_apply(y_conv, mean, var, gamma, beta, eps, act)
    return y, mean, var


def _bn_apply(y_conv, mean, var, gamma, beta, eps, act):
    inv = lax.rsqrt(var + eps) * gamma
    shift = beta - mean * inv
    y = y_conv * inv.astype(y_conv.dtype) + shift.astype(y_conv.dtype)
    if act == "relu":
        y = jax.nn.relu(y)
    return y


def conv2d_bn_act_reference(x, w, scale, bias, running_mean, running_var,
                            is_train, momentum=0.9, eps=1e-5, stride=1,
                            padding=0, act="relu"):
    """The unfused composition (XLA conv -> ``ops/nn.batch_norm`` math ->
    act) — bit-identical to the separate-layers path; oracle and CPU
    production path.  Returns (y, new_running_mean, new_running_var)."""
    from paddle_tpu.ops import nn

    y = nn.conv2d_xla(x, w, stride=stride, padding=padding)
    y, nm, nv = nn.batch_norm(y, scale, bias, running_mean, running_var,
                              is_train=is_train, momentum=momentum, eps=eps,
                              use_fused_stats=False)
    if act == "relu":
        y = jax.nn.relu(y)
    return y, nm, nv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _cbr_train(x, w, gamma, beta, strides, pads, eps, act, interpret):
    y_conv, s, ss = _direct_fwd_raw(x, w, strides, pads, None, None, None,
                                    True, interpret)
    count = y_conv.size // y_conv.shape[-1]
    mean = s / count
    var = jnp.maximum(ss / count - lax.square(mean), 0.0)
    y = _bn_apply(y_conv, mean, var, gamma, beta, eps, act)
    return y, mean, var


def _cbr_train_fwd(x, w, gamma, beta, strides, pads, eps, act, interpret):
    y_conv, s, ss = _direct_fwd_raw(x, w, strides, pads, None, None, None,
                                    True, interpret)
    count = y_conv.size // y_conv.shape[-1]
    mean = s / count
    var = jnp.maximum(ss / count - lax.square(mean), 0.0)
    y = _bn_apply(y_conv, mean, var, gamma, beta, eps, act)
    return (y, mean, var), (x, w, gamma, beta, y_conv)


def _cbr_train_bwd(strides, pads, eps, act, interpret, res, cts):
    x, w, gamma, beta, y_conv = res
    # exact BN(+act) adjoint, linearized at the saved conv output — the
    # elementwise+reduction recompute is cheap, the conv is NOT re-run
    _, vjp = jax.vjp(
        lambda yc, ga, be: _bn_act_train(yc, ga, be, eps, act),
        y_conv, gamma, beta)
    dyc, dga, dbe = vjp(cts)
    dx, dw = _conv_input_grads(x, w, dyc, strides, pads)
    return dx, dw, dga, dbe


_cbr_train.defvjp(_cbr_train_fwd, _cbr_train_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _cbr_eval(x, w, inv, shift, strides, pads, act, interpret):
    # inference-mode fusion: affine + act ride the conv epilogue — one
    # pass, one HBM write
    return _direct_fwd_raw(x, w, strides, pads, inv, shift, act, False,
                           interpret)


def _cbr_eval_fwd(x, w, inv, shift, strides, pads, act, interpret):
    return _cbr_eval(x, w, inv, shift, strides, pads, act, interpret), (
        x, w, inv, shift)


def _cbr_eval_bwd(strides, pads, act, interpret, res, dy):
    x, w, inv, shift = res
    # rare path (inference is not differentiated in the trainer): one
    # conv recompute, then the exact elementwise adjoint
    y_conv = conv2d_direct_reference(x, w, stride=strides, padding=pads)
    _, vjp = jax.vjp(
        lambda yc, s_, t_: (jax.nn.relu(yc * s_.astype(yc.dtype)
                                        + t_.astype(yc.dtype))
                            if act == "relu" else
                            yc * s_.astype(yc.dtype) + t_.astype(yc.dtype)),
        y_conv, inv, shift)
    dyc, dinv, dshift = vjp(dy)
    dx, dw = _conv_input_grads(x, w, dyc, strides, pads)
    return dx, dw, dinv, dshift


_cbr_eval.defvjp(_cbr_eval_fwd, _cbr_eval_bwd)


def conv2d_bn_act(x, w, scale, bias, running_mean, running_var, is_train,
                  momentum=0.9, eps=1e-5, stride=1, padding=0, act="relu",
                  impl="auto", interpret=None):
    """Fused conv + batch-norm + activation, NHWC (the ResNet/CRNN block
    primitive).  Training fuses the BN statistics into the conv epilogue
    (single pass over the conv output); inference folds the whole BN
    affine + ReLU into it (single pass, single write).  Gradients come
    from the exact adjoints of the reference composition (tolerance
    documented in README "Fused TPP microkernels").

    Returns ``(y, new_running_mean, new_running_var)`` like
    ``ops/nn.batch_norm``."""
    strides, pads = _pair(stride), _pair(padding)
    if act not in ("relu", None, ""):
        raise ValueError(f"conv2d_bn_act fuses act None or 'relu', "
                         f"got {act!r}")
    act = act or None
    if _auto(impl, "conv2d_bn_act") == "reference":
        return conv2d_bn_act_reference(
            x, w, scale, bias, running_mean, running_var, is_train,
            momentum=momentum, eps=eps, stride=strides, padding=pads,
            act=act or "")
    interp = _interpret(interpret)
    if is_train:
        y, mean, var = _cbr_train(x, w, scale, bias, strides, pads, eps,
                                  act, interp)
        new_mean = momentum * running_mean + (1 - momentum) * mean
        new_var = momentum * running_var + (1 - momentum) * var
        return y, new_mean, new_var
    inv = lax.rsqrt(running_var + eps) * scale
    shift = bias - running_mean * inv
    y = _cbr_eval(x, w, inv, shift, strides, pads, act, interp)
    return y, running_mean, running_var
