"""NN primitives: conv/pool/norm/dropout — successor of the reference's
cuDNN-backed layers (``paddle/cuda/hl_cuda_cudnn.cc``, ``ConvBaseLayer``,
``PoolLayer``, ``BatchNormalizationLayer``/``CudnnBatchNormLayer``,
``CMRProjectionNormLayer``) and the im2col/GemmConv stack in
``paddle/function/GemmConvOp.cpp``.

TPU-native choices: NHWC layout (XLA's preferred TPU conv layout), bf16 conv
operands with f32 accumulation, ``lax.reduce_window`` pooling, and batch-norm
as a pure function returning updated running stats (no mutable buffers)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import dtype as dt
from paddle_tpu.telemetry.scopes import part


def _pair(v):
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v)


def _tpp():
    """Late import of the fused-microkernel layer (ops/pallas/tpp) — the
    tpp references call back into this module, so neither side imports
    the other at module load."""
    from paddle_tpu.ops.pallas import tpp

    return tpp


def _tpp_kernels_on() -> bool:
    """True when conv/BN should route through the TPP Pallas kernels:
    the ``fused_kernels`` flag says on AND a real TPU backend is present.
    With the flag forced on over CPU, the tpp entry points still resolve
    to their jnp references — the identical op sequence to this module —
    so CPU trajectories stay bit-equal either way (the bench ablation's
    ``trajectory_identical`` contract)."""
    from paddle_tpu.ops.pallas import on_tpu

    return _tpp().fused_enabled() and on_tpu()


def conv2d(
    x: jax.Array,  # [N, H, W, Cin]
    w: jax.Array,  # [KH, KW, Cin // groups, Cout]
    stride=1,
    padding=0,
    dilation=1,
    groups: int = 1,
) -> jax.Array:
    """2-D convolution, NHWC (≅ ExpandConvLayer/CudnnConvLayer via GemmConv).

    Routes through the TPP direct-conv kernel (``ops/pallas/tpp/conv``,
    BRGEMM over shifted input patches) when the ``fused_kernels`` flag
    enables it and the config is the kernel's shape class (groups=1,
    dilation=1, numeric padding); everything else takes the XLA lowering
    below."""
    if (groups == 1 and _pair(dilation) == (1, 1)
            and not isinstance(padding, str) and x.ndim == 4
            and _tpp_kernels_on()):
        return _tpp().conv2d_direct(x, w, stride=stride, padding=padding)
    return conv2d_xla(x, w, stride=stride, padding=padding,
                      dilation=dilation, groups=groups)


def conv2d_xla(
    x: jax.Array,
    w: jax.Array,
    stride=1,
    padding=0,
    dilation=1,
    groups: int = 1,
) -> jax.Array:
    """The XLA ``lax.conv_general_dilated`` lowering — the reference
    numerics every fused path is measured against."""
    stride, dilation = _pair(stride), _pair(dilation)
    if isinstance(padding, str):
        pad = padding
    else:
        ph, pw = _pair(padding)
        pad = [(ph, ph), (pw, pw)]
    # bf16 operands tile onto the MXU.  Output dtype follows the caller's
    # input dtype: f32 callers get the stable f32 upcast; an end-to-end bf16
    # policy (build_train_step compute_dtype) keeps activations bf16, halving
    # HBM traffic.  (preferred_element_type=f32 with bf16 operands breaks the
    # conv transpose rule in jax 0.9, so we round to bf16 and upcast.)
    out_dtype = x.dtype
    x, w = dt.cast_for_matmul(x, w)
    y = lax.conv_general_dilated(
        x,
        w,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        precision=dt.dot_precision(x, w),
    )
    return y.astype(out_dtype)


def conv2d_transpose(
    x: jax.Array, w: jax.Array, stride=1, padding=0, groups: int = 1
) -> jax.Array:
    """Transposed conv (≅ ConvTransLayer / conv2d_transpose_op).
    ``w`` layout (kh, kw, c_out, c_in); grouped transposed conv is not
    supported (lax.conv_transpose has no feature_group_count)."""
    if groups != 1:
        raise NotImplementedError("conv2d_transpose with groups > 1")
    stride = _pair(stride)
    ph, pw = _pair(padding)
    kh, kw = w.shape[0], w.shape[1]
    out_dtype = x.dtype
    x, w = dt.cast_for_matmul(x, w)
    # padding here is the FORWARD conv's padding (out = (in-1)s + k - 2p);
    # lax.conv_transpose pads the dilated input, where that equals k-1-p
    y = lax.conv_transpose(
        x,
        w,
        strides=stride,
        padding=[(kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        transpose_kernel=True,
        precision=dt.dot_precision(x, w),
    )
    return y.astype(out_dtype)


def depthwise_conv2d(x: jax.Array, w: jax.Array, stride=1, padding=0) -> jax.Array:
    """Depthwise conv (≅ paddle/function DepthwiseConvOp)."""
    cin = x.shape[-1]
    return conv2d(x, w, stride=stride, padding=padding, groups=cin)


def max_pool2d(x: jax.Array, ksize, stride=None, padding=0) -> jax.Array:
    kh, kw = _pair(ksize)
    sh, sw = _pair(stride if stride is not None else ksize)
    ph, pw = _pair(padding)
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, sh, sw, 1),
        padding=((0, 0), (ph, ph), (pw, pw), (0, 0)),
    )


def avg_pool2d(x: jax.Array, ksize, stride=None, padding=0, exclude_pad: bool = True) -> jax.Array:
    """Average pooling; ``exclude_pad`` matches the reference's CudnnPool
    EXCLUDE_PADDING mode (divide by the true window size at borders)."""
    kh, kw = _pair(ksize)
    sh, sw = _pair(stride if stride is not None else ksize)
    ph, pw = _pair(padding)
    summed = lax.reduce_window(
        x,
        0.0,
        lax.add,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, sh, sw, 1),
        padding=((0, 0), (ph, ph), (pw, pw), (0, 0)),
    )
    if exclude_pad and (ph or pw):
        ones = jnp.ones(x.shape[:3] + (1,), x.dtype)
        counts = lax.reduce_window(
            ones,
            0.0,
            lax.add,
            window_dimensions=(1, kh, kw, 1),
            window_strides=(1, sh, sw, 1),
            padding=((0, 0), (ph, ph), (pw, pw), (0, 0)),
        )
        return summed / counts
    return summed / (kh * kw)


def global_avg_pool2d(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(1, 2))


def batch_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    running_mean: jax.Array,
    running_var: jax.Array,
    is_train: bool,
    momentum: float = 0.9,
    eps: float = 1e-5,
    use_fused_stats: bool | None = None,
):
    """Batch normalization over all but the last (channel) axis.

    Returns (y, new_running_mean, new_running_var).  The reference keeps
    moving stats as extra parameter buffers updated in the layer
    (``BatchNormBaseLayer``); here they are explicit state in/out so the
    train step stays pure.

    ``use_fused_stats`` (None = auto from the ``fused_kernels`` flag)
    computes the train-mode moments through the TPP single-pass
    sum/sum-of-squares kernel — one read of ``x`` instead of two
    reduction passes.
    """
    if is_train:
        # single-pass stats (E[x], E[x²]) accumulated in f32 from the native
        # dtype — the elementwise normalize then runs in the activation dtype
        # (bf16 under the mixed-precision policy), halving the HBM traffic of
        # the f32-upcast formulation (the ledger's device_ops put the BN
        # statistics fusions at the head of the ResNet-50 step, PERF.md §5).
        if use_fused_stats is None:
            use_fused_stats = _tpp_kernels_on()
        if use_fused_stats:
            s, ss = _tpp().channel_stats(x)
            count = x.size // x.shape[-1]
            mean = s / count
            var = jnp.maximum(ss / count - lax.square(mean), 0.0)
        else:
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            m2 = jnp.mean(lax.square(x.astype(jnp.float32)), axis=axes)
            var = jnp.maximum(m2 - lax.square(mean), 0.0)
        new_mean = momentum * running_mean + (1 - momentum) * mean
        new_var = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    inv = lax.rsqrt(var + eps) * scale
    shift = bias - mean * inv
    y = x * inv.astype(x.dtype) + shift.astype(x.dtype)
    return y, new_mean, new_var


def conv2d_bn_relu(
    x: jax.Array,          # [N, H, W, Cin]
    w: jax.Array,          # [KH, KW, Cin, Cout]
    scale: jax.Array,      # [Cout] BN gamma
    bias: jax.Array,       # [Cout] BN beta
    running_mean: jax.Array,
    running_var: jax.Array,
    is_train: bool,
    momentum: float = 0.9,
    eps: float = 1e-5,
    stride=1,
    padding=0,
    act: str = "relu",
):
    """Fused conv + batch-norm + activation (the ResNet/CRNN block entry
    point, ``act`` "relu" or "" for linear).  Returns
    ``(y, new_running_mean, new_running_var)``.

    With the ``fused_kernels`` flag on, lowers to the TPP fused kernel
    (``ops/pallas/tpp/conv.conv2d_bn_act``): training fuses the BN
    statistics into the conv epilogue, inference folds the whole affine
    + ReLU into it.  Otherwise (and always on CPU) it is exactly the
    ``conv2d`` -> ``batch_norm`` -> relu composition."""
    if _tpp().fused_enabled():
        # impl="auto": kernel on TPU, jnp reference (== this composition)
        # elsewhere — the flag only chooses routing, never numerics class
        return _tpp().conv2d_bn_act(
            x, w, scale, bias, running_mean, running_var, is_train,
            momentum=momentum, eps=eps, stride=stride, padding=padding,
            act=act or None)
    # the two halves of the one "conv_bn" node, by the plain layers' names
    with part("conv"):
        y = conv2d_xla(x, w, stride=stride, padding=padding)
    with part("batch_norm"):
        y, nm, nv = batch_norm(y, scale, bias, running_mean, running_var,
                               is_train=is_train, momentum=momentum, eps=eps,
                               use_fused_stats=False)
        if act == "relu":
            y = jax.nn.relu(y)
    return y, nm, nv


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5):
    """Single-pass LN: one f32 upcast, var = E[x^2] - E[x]^2 (one fused
    reduction pair instead of jnp.var's mean-then-moment second pass).
    The E[x^2] form's cancellation error is benign here:
    LN inputs are O(1)-O(10) activations and the subtraction happens in
    f32 regardless of x's dtype."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    msq = jnp.mean(xf * xf, axis=-1, keepdims=True)
    # clamp like batch_norm above: f32 rounding can leave msq - mean^2
    # slightly NEGATIVE for a constant row with large mean, and
    # rsqrt(negative + eps) would be NaN
    var = jnp.maximum(msq - mean * mean, 0.0)
    out = (xf - mean) * lax.rsqrt(var + eps)
    return out.astype(x.dtype) * scale + bias


def cross_map_normal(
    x: jax.Array, size: int = 5, scale: float = 1e-4, pow_: float = 0.75
) -> jax.Array:
    """Local response normalization across channels (≅ CMRProjectionNormLayer /
    paddle/function/CrossMapNormalOp, Fluid lrn_op). NHWC."""
    sq = x * x
    half = size // 2
    # sum over a channel window via padded cumulative trick
    padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, size - 1 - half)))
    window = sum(
        padded[..., i : i + x.shape[-1]] for i in range(size)
    )
    denom = jnp.power(1.0 + scale * window, pow_)
    return x / denom


def dropout(x: jax.Array, rate: float, key: jax.Array, is_train: bool) -> jax.Array:
    """Inverted dropout (≅ dropout_layer via ComputeDropoutMask)."""
    if not is_train or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def spatial_pyramid_pool(x: jax.Array, pyramid_height: int, pool_type: str = "max") -> jax.Array:
    """SPP layer (≅ SpatialPyramidPoolLayer): concat pooled bins at scales
    1,2,4,... Requires H/W divisible handling via padding."""
    n, h, w, c = x.shape
    outs = []
    for lvl in range(pyramid_height):
        bins = 2**lvl
        kh, kw = -(-h // bins), -(-w // bins)  # ceil
        ph, pw = kh * bins - h, kw * bins - w
        xp = jnp.pad(
            x,
            ((0, 0), (0, ph), (0, pw), (0, 0)),
            constant_values=-jnp.inf if pool_type == "max" else 0.0,
        )
        if pool_type == "max":
            p = max_pool2d(xp, (kh, kw), (kh, kw))
        else:
            p = avg_pool2d(xp, (kh, kw), (kh, kw))
        outs.append(p.reshape(n, -1))
    return jnp.concatenate(outs, axis=-1)


def bilinear_interp(x: jax.Array, out_h: int, out_w: int) -> jax.Array:
    """Bilinear resize NHWC (≅ BilinearInterpLayer)."""
    return jax.image.resize(
        x, (x.shape[0], out_h, out_w, x.shape[3]), method="bilinear"
    )


def maxout(x: jax.Array, groups: int) -> jax.Array:
    """Maxout over channel groups (≅ MaxOutLayer)."""
    n, h, w, c = x.shape
    return jnp.max(x.reshape(n, h, w, c // groups, groups), axis=-1)


def pad(x: jax.Array, pad_c, pad_h, pad_w) -> jax.Array:
    """Channel/spatial padding (≅ PadLayer / paddle/function PadOp), NHWC."""
    return jnp.pad(
        x,
        (
            (0, 0),
            tuple(pad_h),
            tuple(pad_w),
            tuple(pad_c),
        ),
    )


def crop(x: jax.Array, offsets, shape) -> jax.Array:
    """Crop to `shape` starting at `offsets` (≅ CropLayer), NHWC."""
    return lax.dynamic_slice(x, (0, *offsets, 0), (x.shape[0], *shape, x.shape[3]))


def resize(x: jax.Array, size: int) -> jax.Array:
    """Reshape rows to a new feature size (≅ ResizeLayer)."""
    return x.reshape(-1, size)


def featmap_expand(x: jax.Array, num_filters: int, as_row: bool = True) -> jax.Array:
    """Expand each feature map (≅ FeatureMapExpandLayer)."""
    if as_row:
        return jnp.repeat(x, num_filters, axis=-1)
    return jnp.tile(x, (1, num_filters))


def block_expand(x: jax.Array, block_h: int, block_w: int, stride_h: int, stride_w: int,
                 pad_h: int = 0, pad_w: int = 0):
    """im2col as a layer (≅ BlockExpandLayer / paddle/function BlockExpandOp):
    NHWC image -> sequence of flattened blocks, scanning left-right top-down."""
    n, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (pad_h, pad_h), (pad_w, pad_w), (0, 0)))
    patches = lax.conv_general_dilated_patches(
        xp.astype(jnp.float32),
        filter_shape=(block_h, block_w),
        window_strides=(stride_h, stride_w),
        padding=[(0, 0), (0, 0)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # [N, outH, outW, C*bh*bw]
    n_, oh, ow, f = patches.shape
    return patches.reshape(n_, oh * ow, f), oh, ow


def rotate(x: jax.Array) -> jax.Array:
    """90° CCW rotation of feature maps (≅ RotateLayer), NHWC."""
    return jnp.rot90(x, k=1, axes=(1, 2))


def flip_lr(x: jax.Array) -> jax.Array:
    return x[:, :, ::-1, :]
