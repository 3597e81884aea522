"""TPP-style fused microkernel vocabulary (Tensor Processing Primitives,
arxiv 2104.05755) — the reusable kernel layer under the conv/RNN hot paths.

The one-off kernels in ``ops/pallas`` (flash attention, GRU/LSTM, paged
attention) each re-derive the same structure: a tiled MXU contraction with
an f32 accumulator carried in VMEM scratch, finished by a small fused
epilogue.  This package names that structure once and rebuilds the
non-transformer hot paths on it:

- :mod:`brgemm` — the core primitive: batch-reduce GEMM
  ``out = epilogue(sum_g a[g] @ b[g])`` with accumulate-in-fp32 and a
  pluggable epilogue (affine scale/shift, ReLU, fused per-channel
  sum/sum-of-squares for single-pass batch-norm statistics);
- :mod:`conv` — im2col-free direct convolution expressed as BRGEMM over
  shifted input-row patches, plus the fused conv+BN+ReLU forward with a
  matching ``custom_vjp`` (the ResNet/CRNN block primitive);
- :mod:`update` — the fused SGD/momentum weight update applied in place
  on the ZeRO-2 optimizer shard (one read-modify-write pass over p/g/v
  instead of the multi-op XLA update; arxiv 2004.13336 motivates fusing
  the update onto the shard the reduce-scatter already produced);
- :mod:`embedding` — the sparse pserver's row machinery: dedup-once
  gather driven by a scalar-prefetched id list, duplicate-exact
  scatter-add as a one-hot MXU contraction, and the row-lazy
  ``SparseRowMatrix`` optimizer update (untouched rows bit-identical).

Every kernel ships a pure-jnp ``*_reference`` twin that is BOTH the CPU
production path and the test oracle (the ``paged_attention``
``impl="auto"`` convention); ``tools/check_kernel_parity.py`` enforces
that pairing across the whole ``ops/pallas`` tree.

Routing is controlled by the ``fused_kernels`` core flag
(``PADDLE_TPU_FUSED_KERNELS``): ``auto`` (default) enables the kernels
on TPU only, so the CPU testbed keeps the reference composition —
bit-identical to the unfused program — while TPU runs take the fused
path.
"""

from __future__ import annotations

from paddle_tpu.core import flags
from paddle_tpu.ops.pallas import on_tpu


def fused_enabled() -> bool:
    """True when the conv/BN/update hot paths should route through the
    TPP kernels: the ``fused_kernels`` flag, with ``auto`` meaning
    on-TPU only (off on the CPU/interpret testbed)."""
    v = str(flags.get("fused_kernels")).strip().lower()
    if v in ("on", "1", "true", "yes"):
        return True
    if v in ("off", "0", "false", "no"):
        return False
    return on_tpu()


from paddle_tpu.ops.pallas.tpp.brgemm import (  # noqa: E402
    brgemm,
    brgemm_reference,
)
from paddle_tpu.ops.pallas.tpp.conv import (  # noqa: E402
    channel_stats,
    channel_stats_reference,
    conv2d_bn_act,
    conv2d_bn_act_reference,
    conv2d_direct,
    conv2d_direct_reference,
)
from paddle_tpu.ops.pallas.tpp.update import (  # noqa: E402
    fused_momentum_update,
    fused_momentum_update_reference,
    fused_sgd_update,
    fused_sgd_update_reference,
    fused_shard_apply,
)
from paddle_tpu.ops.pallas.tpp.embedding import (  # noqa: E402
    dedup_ids,
    dedup_ids_reference,
    embedding_gather,
    embedding_gather_reference,
    embedding_scatter_add,
    embedding_scatter_add_reference,
    fused_embedding_lookup,
    sparse_row_update,
    sparse_row_update_reference,
)

__all__ = [
    "fused_enabled",
    "brgemm", "brgemm_reference",
    "channel_stats", "channel_stats_reference",
    "conv2d_direct", "conv2d_direct_reference",
    "conv2d_bn_act", "conv2d_bn_act_reference",
    "fused_momentum_update", "fused_momentum_update_reference",
    "fused_sgd_update", "fused_sgd_update_reference",
    "fused_shard_apply",
    "dedup_ids", "dedup_ids_reference",
    "embedding_gather", "embedding_gather_reference",
    "embedding_scatter_add", "embedding_scatter_add_reference",
    "fused_embedding_lookup",
    "sparse_row_update", "sparse_row_update_reference",
]
