"""Ahead-of-time compiles of the main-path Pallas kernels for a described
(not attached) TPU v5e, at the shapes ``chip_smoke.py`` runs on the chip.

The TPU compiler is installed wherever jax[tpu] is, so these refuse what
the chip would refuse — a block that is not (8, 128)-aligned, a slice off
the tiling, too much VMEM — long before a chip run does; interpret mode
checks none of that.  A compile that passes is not a run: results and
times come from ``python chip_smoke.py`` on the chip.

The kernels are called with ``interpret=False`` directly (``impl="auto"``
would resolve to the references here: ``jax.default_backend()`` is the
CPU).  The persistent compilation cache is off around the compiles — an
entry written for a described device cannot be read back without one,
and the next compile would warn.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

# the cases of chip_smoke's `kernels` phase that compile in a few seconds
# (the whole file stays well under a minute), the three kernels ISSUE 21
# re-blocked among them
CASES = (
    "conv2d_bn_act", "lstm_seq_fi", "gru_seq_fi", "bilstm_seq",
    "flash_attention", "ragged_paged_attention",
    "ragged_paged_attention[gpt2l]", "ragged_paged_attention[ouro]",
    "ragged_paged_attention[sdar]", "ragged_paged_attention[zaya]",
    "ragged_paged_attention[vmem]",
    # the decode step's write inside the kernel: a row store or a page
    # copy off the tiling is refused here, before any chip run
    "decode_attention[gpt2l]", "decode_attention[ouro]",
    "decode_attention[zaya]", "decode_attention[ring]",
    "kda_prefill",  # the cell's one-row pass: ~5 s
    "grouped_matmul", "grouped_matmul[down]",   # that pass's expert product
    "ssd_step",     # a Mamba-2 layer of a decode step, the pool aliased
    "softmax_xent",
    "fused_momentum_update", "ctc_loss_fused", "ctc_loss_fused[logits]",
    "ctc_greedy_decode_fused", "embedding_gather", "embedding_scatter_add",
    "sparse_row_update",
)


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on a described v5e chip, with the compile
    cache off for the module; skips where the topology cannot be
    described (no TPU compiler installed)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / unknown topology: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(one_chip, name):
    sizes = chip_smoke.Sizes()
    case = next(c for c in chip_smoke._kernel_cases() if c.name == name)
    shape = sizes.kernel_shapes[case.shape_key]
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: case.make(shape, jax.random.key(0))))
    kernel = case.kernel(False, shape)  # interpret=False: Mosaic

    def fwd_bwd(*a):
        out = kernel(*a)
        if not case.diff:
            return out

        def loss(*b):
            return sum(jnp.sum(x.astype(jnp.float32))
                       for x in jax.tree.leaves(kernel(*b))
                       if jnp.issubdtype(x.dtype, jnp.floating))
        return out, jax.grad(loss, argnums=case.diff)(*a)

    compiled = jax.jit(fwd_bwd).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # one program at a time must fit the chip's 16 GB with room to spare
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 8e9
