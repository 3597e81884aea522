"""Pallas TPU kernels for the hot ops.

The reference ships hand-written CUDA kernels where cuBLAS/cuDNN fall short
(``paddle/cuda/src/hl_cuda_lstm.cu``, ``hl_top_k.cu``, …).  The TPU-native
analog is Pallas: MXU/VPU kernels compiled through Mosaic, with the same
"stub fallback" idea the reference uses for CPU-only builds
(``paddle/cuda/include/stub/``) realised here as interpret-mode execution on
non-TPU backends, so every kernel runs everywhere and tests are hermetic.
"""

from __future__ import annotations

import contextlib
import threading

import jax


def on_tpu() -> bool:
    """True when jax's default backend is a TPU.  Every "kernel or
    reference" decision in the package keys on this one observation."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """True when no TPU is present — run kernels in interpreter mode (the
    CPU-stub equivalent of the reference's ``paddle/cuda/include/stub/``)."""
    return not on_tpu()


def resolve_interpret(interpret):
    """``None`` -> :func:`default_interpret`.  An explicit ``True`` on a
    TPU backend is refused: a kernel entry never runs interpreted where
    Mosaic can compile it, or a chip run would pass on the interpreter."""
    if interpret is None:
        return default_interpret()
    if interpret and on_tpu():
        raise ValueError("interpret=True on a TPU backend: the kernel "
                         "would run in the Pallas interpreter, not on "
                         "the chip")
    return bool(interpret)


def resolve_impl(impl: str, op: str | None = None) -> str:
    """The shared dispatch rule of every kernel entry with a reference
    twin: ``auto`` = kernel on TPU, reference elsewhere; validates the
    name.  ``op`` names the entry for :func:`capture_routes`."""
    if impl == "auto":
        impl = "kernel" if on_tpu() else "reference"
    elif impl not in ("kernel", "reference"):
        raise ValueError(f"impl must be 'auto', 'kernel' or 'reference', "
                         f"got {impl!r}")
    if op is not None:
        note_route(op, impl)
    return impl


# -- routing census (trace time) ----------------------------------------------
#
# The kernel-or-reference decisions run in Python while jax traces a
# program, so they fire once per compiled signature.  A scoped capture
# around a trace (chip_smoke.py wraps each model's first step) collects
# them, so a run can print which path every op took instead of trusting
# that "auto" meant the kernel.

_routes = threading.local()


@contextlib.contextmanager
def capture_routes():
    """Collect ``{(op, path): count}`` for every routing decision traced
    inside the block.  ``path`` is "kernel", "reference", or a fallback
    name such as "scan" (``ops/rnn``'s VMEM-fit fallback)."""
    stack = getattr(_routes, "stack", None)
    if stack is None:
        stack = _routes.stack = []
    acc: dict[tuple[str, str], int] = {}
    stack.append(acc)
    try:
        yield acc
    finally:
        stack.pop()


def note_route(op: str, path: str) -> None:
    for acc in getattr(_routes, "stack", None) or ():
        acc[(op, path)] = acc.get((op, path), 0) + 1


NEG_INF = -1e30  # shared masking sentinel for the softmax-family kernels


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_axis(x, axis: int, to: int):
    """Zero-pad ``axis`` of ``x`` up to length ``to`` (identity when it
    is there already) — kernels pad ragged dims up to their block sizes."""
    import jax.numpy as jnp

    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


from paddle_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    ragged_paged_attention,
)

__all__ = ["flash_attention", "ragged_paged_attention", "on_tpu",
           "default_interpret", "resolve_interpret", "resolve_impl",
           "capture_routes", "note_route", "NEG_INF", "round_up",
           "pad_axis"]


def mxu_precision(ref):
    """Precision for a kernel-internal dot: true-f32 MXU passes for f32
    refs (the compat surface), native single pass for bf16."""
    import jax.lax
    import jax.numpy as jnp

    return (jax.lax.Precision.HIGHEST
            if ref.dtype == jnp.float32 else None)


def time_major_mask(mask):
    """[B, T] -> [T, B, 1] f32, the kernels' freeze-mask layout."""
    import jax.numpy as jnp

    return jnp.swapaxes(mask, 0, 1)[:, :, None].astype(jnp.float32)
