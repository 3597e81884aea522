"""Fluid dynamic RNN: the block-as-stepnet ``recurrent`` op + LoD-array
machinery, DIFFERENTIABLE end to end.

≅ the reference's fluid RNN surface: recurrent_op.cc:49-62 (step-net RNN
with a backward pass), test_recurrent_op.py (StaticRNN + PySimpleRNN
numeric parity), lod_rank_table_op.cc:19, lod_tensor_to_array_op /
array_to_lod_tensor_op / shrink_rnn_memory_op, and the requirement that a
fluid dynamic-RNN language model TRAINS (loss decreases with gradient flow
through the scan-lowered recurrent op).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid import framework, layers


def _reset():
    framework.reset_default_programs()


def test_static_rnn_matches_numpy_simple_rnn1(rng_np):
    """PySimpleRNN1 (test_recurrent_op.py:28): h_t = (x_t + h_{t-1})/2."""
    _reset()
    T, B, D = 4, 3, 5
    x_np = rng_np.normal(size=(T, B, D)).astype(np.float32)
    h_boot_np = rng_np.normal(size=(B, D)).astype(np.float32)

    x = layers.data("x", shape=[T, B, D], append_batch_size=False)
    h_boot = layers.data("h_boot", shape=[B, D], append_batch_size=False)
    rnn = layers.StaticRNN()
    with rnn.step():
        h_pre = rnn.memory(init=h_boot)
        x_t = rnn.step_input(x)
        h = layers.scale(x=layers.elementwise_add(x=h_pre, y=x_t), scale=0.5)
        rnn.update_memory(h_pre, h)
        rnn.output(h)
    out = rnn()

    exe = fluid.Executor()
    (y,) = exe.run(feed={"x": x_np, "h_boot": h_boot_np},
                   fetch_list=[out])

    ref = np.zeros((T, B, D), np.float32)
    h = h_boot_np
    for t in range(T):
        h = (h + x_np[t]) * 0.5
        ref[t] = h
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


def test_static_rnn_gradient_flows(rng_np):
    """jax.grad crosses the recurrent op: finite-diff check on the boot
    state through a 2-layer step net (the reference's recurrent_op grad)."""
    import jax
    import jax.numpy as jnp

    _reset()
    T, B, D = 3, 2, 4
    x_np = rng_np.normal(size=(T, B, D)).astype(np.float32)
    w_np = (rng_np.normal(size=(D, D)) * 0.4).astype(np.float32)
    boot_np = rng_np.normal(size=(B, D)).astype(np.float32)

    x = layers.data("x", shape=[T, B, D], append_batch_size=False)
    w = layers.data("w", shape=[D, D], append_batch_size=False)
    h_boot = layers.data("h_boot", shape=[B, D], append_batch_size=False)
    rnn = layers.StaticRNN()
    with rnn.step():
        h_pre = rnn.memory(init=h_boot)
        x_t = rnn.step_input(x)
        hw = layers.mul(x=h_pre, y=w)
        h = layers.tanh(x=layers.elementwise_add(x=hw, y=x_t))
        rnn.update_memory(h_pre, h)
        rnn.output(h)
    out = rnn()
    loss = layers.mean(x=out)

    prog = framework.default_main_program()
    from paddle_tpu.fluid.executor import _run_op

    def loss_fn(boot):
        env = {"x": jnp.asarray(x_np), "w": jnp.asarray(w_np),
               "h_boot": boot}
        rng = jax.random.key(0)
        for op in prog.global_block().ops:
            _run_op(op, env, rng, prog)
        return env[loss.name].reshape(())

    g = jax.jit(jax.grad(loss_fn))(jnp.asarray(boot_np))
    assert np.isfinite(np.asarray(g)).all()
    # finite differences
    eps = 1e-3
    base_p = np.asarray(loss_fn(jnp.asarray(boot_np + eps * 0)))
    for idx in [(0, 0), (1, 2)]:
        bumped = boot_np.copy()
        bumped[idx] += eps
        fd = (float(loss_fn(jnp.asarray(bumped))) - float(base_p)) / eps
        an = float(np.asarray(g)[idx])
        assert abs(fd - an) < 5e-3, (idx, fd, an)


def test_lod_array_ops_roundtrip(rng_np):
    """lod_rank_table sorts desc; to_array/array_to restore the original
    order; shrink masks rows whose sequence already ended."""
    import jax

    _reset()
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.fluid.ops import get_kernel

    B, T, D = 4, 5, 3
    lengths = np.array([2, 5, 3, 1], np.int32)
    data = rng_np.normal(size=(B, T, D)).astype(np.float32)
    for b in range(B):
        data[b, lengths[b]:] = 0.0
    x = SequenceBatch(data=data, length=lengths)
    rng = jax.random.key(0)

    table = get_kernel("lod_rank_table")({"X": [x]}, {}, rng)["Out"][0]
    np.testing.assert_array_equal(np.asarray(table["index"]), [1, 2, 0, 3])
    np.testing.assert_array_equal(np.asarray(table["length"]), [5, 3, 2, 1])

    arr = get_kernel("lod_tensor_to_array")(
        {"X": [x], "RankTable": [table]}, {}, rng)["Out"][0]
    assert arr.shape == (T, B, D)
    # step 3: only the longest sequence still lives
    live3 = np.asarray(arr[3])
    assert np.any(live3[0] != 0)
    assert np.all(live3[1:] == 0)

    back = get_kernel("array_to_lod_tensor")(
        {"X": [arr], "RankTable": [table]}, {}, rng)["Out"][0]
    np.testing.assert_allclose(np.asarray(back.data), data, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(back.length), lengths)

    mem = rng_np.normal(size=(B, D)).astype(np.float32)
    shrunk = get_kernel("shrink_rnn_memory")(
        {"X": [mem], "I": [np.asarray([2.0])], "RankTable": [table]},
        {}, rng)["Out"][0]
    # at step 2, table rows with length > 2 live: rows 0 (len5) and 1 (len3)
    np.testing.assert_allclose(np.asarray(shrunk[:2]), mem[:2], rtol=1e-6)
    assert np.all(np.asarray(shrunk[2:]) == 0)

    ml = get_kernel("max_sequence_len")({"RankTable": [table]}, {}, rng)
    assert int(np.asarray(ml["Out"][0])[0]) == 5


def test_dynamic_rnn_lm_trains(rng_np):
    """A fluid dynamic-RNN language model over VARIABLE-length sequences
    (lod_rank_table -> lod_tensor_to_array -> recurrent -> array_to_lod)
    trains: loss decreases, gradients flow through embedding, recurrent
    weights, and the softmax projection."""
    import jax
    import jax.numpy as jnp

    _reset()
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.fluid.executor import _run_op

    V, E, H, B, T = 17, 8, 12, 8, 6

    words = layers.data("words", shape=[B, T], append_batch_size=False,
                        dtype="int32", lod_level=1)
    table = layers.lod_rank_table(words)
    # embed then convert to a time-major array in rank order
    emb_w = layers.data("emb_w", shape=[V, E], append_batch_size=False)

    prog = framework.default_main_program()
    main = prog.global_block()
    emb = main.create_var(name="emb", shape=[B, T, E], lod_level=1)
    main.append_op("lookup_table", {"Ids": ["words"], "W": ["emb_w"]},
                   {"Out": ["emb"]}, {})
    arr = layers.lod_tensor_to_array(main.vars["emb"], table)

    w_ih = layers.data("w_ih", shape=[E, H], append_batch_size=False)
    w_hh = layers.data("w_hh", shape=[H, H], append_batch_size=False)
    w_out = layers.data("w_out", shape=[H, V], append_batch_size=False)
    boot = layers.data("boot", shape=[B, H], append_batch_size=False)
    lens = layers.data("lens_sorted", shape=[B], append_batch_size=False,
                       dtype="int32")

    rnn = layers.StaticRNN(sequence_lengths=lens)
    with rnn.step():
        h_pre = rnn.memory(init=boot)
        x_t = rnn.step_input(arr)
        a = layers.elementwise_add(
            x=layers.mul(x=x_t, y=w_ih), y=layers.mul(x=h_pre, y=w_hh))
        h = layers.tanh(x=a)
        logits = layers.mul(x=h, y=w_out)
        rnn.update_memory(h_pre, h)
        rnn.output(logits)
    logits_arr = rnn()
    seq_logits = layers.array_to_lod_tensor(logits_arr, table)

    # data: next-token = (token + 1) % V, variable lengths
    lengths = rng_np.integers(2, T + 1, size=(B,)).astype(np.int32)
    toks = (rng_np.integers(0, V, size=(B, T))).astype(np.int32)

    params = {
        "emb_w": jnp.asarray(rng_np.normal(size=(V, E)) * 0.1, jnp.float32),
        "w_ih": jnp.asarray(rng_np.normal(size=(E, H)) * 0.3, jnp.float32),
        "w_hh": jnp.asarray(rng_np.normal(size=(H, H)) * 0.3, jnp.float32),
        "w_out": jnp.asarray(rng_np.normal(size=(H, V)) * 0.3, jnp.float32),
    }

    x_seq = SequenceBatch(data=jnp.asarray(toks), length=jnp.asarray(lengths))
    targets = jnp.asarray((toks + 1) % V)

    def loss_fn(params):
        env = dict(params)
        env["words"] = x_seq
        env["boot"] = jnp.zeros((B, H), jnp.float32)
        # rank-order lengths for the recurrent mask
        order = jnp.argsort(-x_seq.length, stable=True)
        env["lens_sorted"] = x_seq.length[order]
        rng = jax.random.key(0)
        for op in prog.global_block().ops:
            _run_op(op, env, rng, prog)
        out = env[seq_logits.name]  # SequenceBatch [B, T, V]
        logp = jax.nn.log_softmax(out.data, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = out.mask()
        return jnp.sum(nll * mask) / jnp.sum(mask)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    first = last = None
    for i in range(60):
        l, g = grad_fn(params)
        params = jax.tree.map(lambda p, gg: p - 0.5 * gg, params, g)
        l = float(l)
        first = first if first is not None else l
        last = l
    assert np.isfinite(last)
    assert last < first * 0.5, (first, last)
    # every parameter received gradient
    for k, gv in g.items():
        assert float(jnp.max(jnp.abs(gv))) > 0, k
