"""Live-outer-memory nested generation: an inner
beam step whose recurrent memory boots from an OUTER ``memory()`` carries
state ACROSS subsequences — each subsequence's generation starts from the
state the previous one ended in (best beam), the reference's outer-frame
memory plumbing (RecurrentGradientMachine.cpp:1291, ScatterAgentLayer).

The model is hand-weighted so the expectation is computable on paper:

    inner step:  h_t = h_{t-1} + 1            ("hstate" fc, W=1, b=1)
                 logits = (0, h_t, 2.2-h_t, -10) over vocab 4, eos=3
    greedy (beam 1), max_length 2, outer memory = live "hstate"

With h booting at 0 for the FIRST subsequence only:
    sub 0: h = 1, 2     -> argmax tokens (2, 1), carry-out h = 2
    sub 1: h = 3, 4     -> tokens (1, 1)         (carry crossed frames!)
Without the live link (independent subsequences) sub 1 would repeat
sub 0's (2, 1) — which is exactly what this test distinguishes.
"""

from __future__ import annotations

import numpy as np
import pytest


def _build():
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type
    from paddle_tpu.layers.attr import ParamAttr
    from paddle_tpu.layers.recurrent_group import (
        GeneratedInput,
        StaticInput,
        SubsequenceInput,
        beam_search,
        memory,
        recurrent_group,
    )

    base.reset_name_counters()
    data = layer.data(name="src",
                      type=data_type.dense_vector_sub_sequence(2))

    def outer_step(x):
        om = memory(name="hstate", size=1)  # boots at zero

        def inner_step(sx, word):
            h = memory(name="hstate", size=1, boot_layer=om)
            hn = layer.fc_layer(
                input=h, size=1, name="hstate", act=act.LinearActivation(),
                param_attr=ParamAttr(name="w_h"),
                bias_attr=ParamAttr(name="b_h"))
            out = layer.fc_layer(
                input=hn, size=4, act=act.SoftmaxActivation(),
                param_attr=ParamAttr(name="w_out"),
                bias_attr=ParamAttr(name="b_out"))
            return out

        return beam_search(
            step=inner_step,
            input=[StaticInput(input=x, is_seq=True),
                   GeneratedInput(size=4, embedding_name="emb",
                                  embedding_size=1)],
            bos_id=0, eos_id=3, beam_size=1, max_length=2)

    gen = recurrent_group(step=outer_step, input=SubsequenceInput(data))
    return gen, data


def test_live_outer_memory_carries_state_across_subsequences():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core.lod import NestedSequenceBatch

    gen, data = _build()
    topo = Topology(gen)
    params = paddle.parameters.create(topo)
    params["w_h"] = np.asarray([[1.0]], np.float32)
    params["b_h"] = np.asarray([1.0], np.float32)
    params["w_out"] = np.asarray([[0.0, 1.0, -1.0, 0.0]], np.float32)
    params["b_out"] = np.asarray([0.0, 0.0, 2.2, -10.0], np.float32)
    params["emb"] = np.zeros((4, 1), np.float32)

    b, n_sub = 2, 2
    feed = {
        "src": NestedSequenceBatch(
            data=np.zeros((b, n_sub, 1, 2), np.float32),
            seq_length=np.asarray([2, 1], np.int32),
            sub_length=np.ones((b, n_sub), np.int32)),
    }
    values, _ = topo.forward(params.as_dict(), topo.init_states(), feed,
                             False, jax.random.key(0))
    res = values[gen.name]
    ids = np.asarray(jax.device_get(res.inner.ids)).reshape(b, n_sub, 1, 2)
    lens = np.asarray(jax.device_get(res.inner.length)).reshape(b, n_sub)

    # row 0 (2 live subsequences): carry crosses the frame boundary
    assert ids[0, 0, 0].tolist() == [2, 1], ids[0]
    assert ids[0, 1, 0].tolist() == [1, 1], ids[0]
    assert lens[0].tolist() == [2, 2]
    # row 1: first subsequence identical to row 0's first (same boot)
    assert ids[1, 0, 0].tolist() == [2, 1]
    # its outer sequence ends after 1 subsequence; the padded frame's
    # output is masked by seq_length for consumers
    assert int(np.asarray(res.seq_length)[1]) == 1


def test_without_live_memory_subsequences_are_independent():
    """Control: the SAME model minus the outer-memory link generates the
    same tokens for every subsequence (the pre-round-4 behavior)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core.lod import NestedSequenceBatch
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type
    from paddle_tpu.layers.attr import ParamAttr
    from paddle_tpu.layers.recurrent_group import (
        GeneratedInput,
        StaticInput,
        SubsequenceInput,
        beam_search,
        memory,
        recurrent_group,
    )

    base.reset_name_counters()
    data = layer.data(name="src",
                      type=data_type.dense_vector_sub_sequence(2))

    def outer_step(x):
        def inner_step(sx, word):
            h = memory(name="hstate", size=1)  # zero boot every frame
            hn = layer.fc_layer(
                input=h, size=1, name="hstate", act=act.LinearActivation(),
                param_attr=ParamAttr(name="w_h"),
                bias_attr=ParamAttr(name="b_h"))
            return layer.fc_layer(
                input=hn, size=4, act=act.SoftmaxActivation(),
                param_attr=ParamAttr(name="w_out"),
                bias_attr=ParamAttr(name="b_out"))

        return beam_search(
            step=inner_step,
            input=[StaticInput(input=x, is_seq=True),
                   GeneratedInput(size=4, embedding_name="emb",
                                  embedding_size=1)],
            bos_id=0, eos_id=3, beam_size=1, max_length=2)

    gen = recurrent_group(step=outer_step, input=SubsequenceInput(data))
    topo = Topology(gen)
    params = paddle.parameters.create(topo)
    params["w_h"] = np.asarray([[1.0]], np.float32)
    params["b_h"] = np.asarray([1.0], np.float32)
    params["w_out"] = np.asarray([[0.0, 1.0, -1.0, 0.0]], np.float32)
    params["b_out"] = np.asarray([0.0, 0.0, 2.2, -10.0], np.float32)
    params["emb"] = np.zeros((4, 1), np.float32)

    feed = {
        "src": NestedSequenceBatch(
            data=np.zeros((1, 2, 1, 2), np.float32),
            seq_length=np.asarray([2], np.int32),
            sub_length=np.ones((1, 2), np.int32)),
    }
    values, _ = topo.forward(params.as_dict(), topo.init_states(), feed,
                             False, jax.random.key(0))
    ids = np.asarray(jax.device_get(values[gen.name].inner.ids))
    ids = ids.reshape(1, 2, 1, 2)
    assert ids[0, 0, 0].tolist() == [2, 1]
    assert ids[0, 1, 0].tolist() == [2, 1]  # independent: repeats
