"""Op-registry breadth batch: direct-kernel checks against numpy references
(the reference's op_test.py check_output pattern) plus one generic-grad
check through the fluid executor."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fluid import ops as O

KEY = jax.random.key(0)


def run(name, ins, attrs=None):
    ins = {k: [jnp.asarray(v) for v in vs] for k, vs in ins.items()}
    return O.get_kernel(name)(ins, attrs or {}, KEY)


def test_tensor_ops(rng_np):
    x = rng_np.normal(size=(4, 5)).astype(np.float32)
    np.testing.assert_allclose(run("sign", {"X": [x]})["Out"][0], np.sign(x))
    y = rng_np.normal(size=(4, 5)).astype(np.float32)
    np.testing.assert_allclose(run("minus", {"X": [x], "Y": [y]})["Out"][0],
                               x - y, rtol=1e-6)
    idx = np.asarray([2, 0])
    np.testing.assert_allclose(
        run("gather", {"X": [x], "Index": [idx]})["Out"][0], x[idx])
    upd = np.ones((2, 5), np.float32)
    got = run("scatter", {"Ref": [x], "Index": [idx], "Updates": [upd]})
    ref = x.copy(); ref[idx] = 1.0
    np.testing.assert_allclose(got["Out"][0], ref)
    parts = run("split", {"X": [x]}, {"axis": 1, "sections": [2, 3]})["Out"]
    assert parts[0].shape == (4, 2) and parts[1].shape == (4, 3)
    padded = run("pad", {"X": [x]}, {"paddings": [0, 1, 2, 0],
                                     "pad_value": 7.0})["Out"][0]
    assert padded.shape == (5, 7) and float(padded[-1, 0]) == 7.0
    cropped = run("crop", {"X": [x]}, {"offsets": [1, 2],
                                       "shape": [2, 3]})["Out"][0]
    np.testing.assert_allclose(cropped, x[1:3, 2:5])
    c = run("clip_by_norm", {"X": [x * 100]}, {"max_norm": 1.0})["Out"][0]
    np.testing.assert_allclose(float(jnp.linalg.norm(c)), 1.0, rtol=1e-4)


def test_loss_ops(rng_np):
    x = rng_np.normal(size=(6, 4)).astype(np.float32)
    y = rng_np.normal(size=(6, 4)).astype(np.float32)
    out = run("squared_l2_distance", {"X": [x], "Y": [y]})["Out"][0]
    np.testing.assert_allclose(
        np.asarray(out)[:, 0], ((x - y) ** 2).sum(-1), rtol=1e-5)
    h = run("huber_loss", {"X": [x], "Y": [y]}, {"delta": 1.0})["Out"][0]
    r = y - x
    np.testing.assert_allclose(
        np.asarray(h),
        np.where(np.abs(r) <= 1, 0.5 * r * r, np.abs(r) - 0.5), rtol=1e-5)
    lbl = (rng_np.random((6, 4)) > 0.5).astype(np.float32)
    s = run("sigmoid_cross_entropy_with_logits",
            {"X": [x], "Label": [lbl]})["Out"][0]
    expect = np.maximum(x, 0) - x * lbl + np.log1p(np.exp(-np.abs(x)))
    np.testing.assert_allclose(np.asarray(s), expect, rtol=1e-5)
    t = (rng_np.random((6, 1)) > 0.5).astype(np.float32)
    rl = run("rank_loss", {"Left": [x[:, :1]], "Right": [y[:, :1]],
                           "Label": [t]})["Out"][0]
    o = x[:, :1] - y[:, :1]
    np.testing.assert_allclose(np.asarray(rl), np.log1p(np.exp(o)) - t * o,
                               rtol=1e-5)


def test_optimizer_ops(rng_np):
    p = rng_np.normal(size=(8,)).astype(np.float32)
    g = rng_np.normal(size=(8,)).astype(np.float32)
    lr = np.asarray([0.1], np.float32)
    z = np.zeros_like(p)
    out = run("rmsprop", {"Param": [p], "Grad": [g], "MeanSquare": [z],
                          "Moment": [z], "LearningRate": [lr]},
              {"decay": 0.9, "epsilon": 1e-6})
    ms = 0.1 * g * g
    mo = 0.1 * g / np.sqrt(ms + 1e-6)
    np.testing.assert_allclose(out["ParamOut"][0], p - mo, rtol=1e-4)
    out = run("adadelta", {"Param": [p], "Grad": [g],
                           "AvgSquaredGrad": [z], "AvgSquaredUpdate": [z]},
              {"rho": 0.95, "epsilon": 1e-6})
    assert out["ParamOut"][0].shape == p.shape
    out = run("proximal_gd", {"Param": [p], "Grad": [g],
                              "LearningRate": [lr]}, {"l1": 0.0, "l2": 0.0})
    np.testing.assert_allclose(out["ParamOut"][0], p - 0.1 * g, rtol=1e-5)


def test_metric_ops(rng_np):
    probs = rng_np.random((32, 2)).astype(np.float32)
    labels = (probs[:, 1] > 0.5).astype(np.int32)  # perfectly separable
    auc = float(run("auc", {"Out": [probs], "Label": [labels]},
                    {"num_thresholds": 200})["AUC"][0][0])
    assert auc > 0.99
    preds = np.asarray([0, 1, 2, 1])
    lbls = np.asarray([0, 1, 2, 2])
    m = run("precision_recall", {"Indices": [preds], "Labels": [lbls]},
            {"class_number": 3})["BatchMetrics"][0]
    assert 0.5 < float(m[0]) <= 1.0  # macro precision sensible


def test_conv2d_transpose_and_pool_index(rng_np):
    x = rng_np.normal(size=(2, 3, 8, 8)).astype(np.float32)
    w = rng_np.normal(size=(3, 4, 3, 3)).astype(np.float32)  # ci,co,kh,kw
    y = run("conv2d_transpose", {"Input": [x], "Filter": [w]},
            {"strides": (2, 2), "paddings": (0, 0)})["Output"][0]
    assert y.shape[0:2] == (2, 4) and y.shape[2] > 8
    out = run("pool2d_with_index", {"X": [x]}, {"ksize": [2, 2],
                                                "strides": [2, 2]})
    assert out["Out"][0].shape == (2, 3, 4, 4)
    assert out["Mask"][0].shape == (2, 3, 4, 4)
    np.testing.assert_allclose(
        np.asarray(out["Out"][0])[0, 0, 0, 0], x[0, 0, :2, :2].max())


def test_generic_grad_covers_new_ops():
    """huber_loss through the executor backward (generic vjp kernel)."""
    from paddle_tpu import fluid
    from paddle_tpu.fluid import framework, layers

    framework.reset_default_programs()
    rng = np.random.default_rng(0)
    x_np = rng.normal(size=(4, 3)).astype(np.float32)
    y_np = rng.normal(size=(4, 3)).astype(np.float32)
    x = layers.data("x", [4, 3], append_batch_size=False)
    y = layers.data("y", [4, 3], append_batch_size=False)
    block = framework.default_main_program().global_block()
    res = block.create_var(name="resid", shape=(4, 3))
    out = block.create_var(name="hub", shape=(4, 3))
    block.append_op("huber_loss", {"X": ["x"], "Y": ["y"]},
                    {"Residual": ["resid"], "Out": ["hub"]}, {"delta": 1.0})
    loss = layers.mean(out)
    block.vars["x"].stop_gradient = False
    grads = fluid.append_backward_ops(loss, parameter_list=["x"])
    exe = fluid.Executor()
    got = exe.run(feed={"x": x_np, "y": y_np}, fetch_list=[grads[0][1]])[0]

    eps = 1e-3
    num = np.zeros_like(x_np)
    def f(xv):
        r = y_np - xv
        a = np.abs(r)
        return float(np.where(a <= 1, 0.5 * r * r, a - 0.5).mean())
    for i in np.ndindex(*x_np.shape):
        xp = x_np.copy(); xp[i] += eps
        xm = x_np.copy(); xm[i] -= eps
        num[i] = (f(xp) - f(xm)) / (2 * eps)
    np.testing.assert_allclose(got, num, rtol=1e-2, atol=1e-4)


def test_round3_straggler_ops(rng_np):
    """positive_negative_pair + compare/reduce/pool3d/conv3d stragglers."""
    # pnpair: q0 ordered pair agrees, q1 tie
    score = np.asarray([[.1, .9], [.2, .8], [.3, .5], [.4, .5]], np.float32)
    label = np.asarray([[1.], [0.], [1.], [0.]], np.float32)
    query = np.asarray([[0], [0], [1], [1]], np.int32)
    out = run("positive_negative_pair",
              {"Score": [score], "Label": [label], "QueryID": [query]},
              {"column": -1})
    assert float(out["PositivePair"][0][0]) == 1.0
    assert float(out["NegativePair"][0][0]) == 0.0
    assert float(out["NeutralPair"][0][0]) == 1.0
    # accumulators seed the counts
    out2 = run("positive_negative_pair",
               {"Score": [score], "Label": [label], "QueryID": [query],
                "AccumulatePositivePair": [np.asarray([2.0], np.float32)],
                "AccumulateNegativePair": [np.asarray([1.0], np.float32)],
                "AccumulateNeutralPair": [np.asarray([0.5], np.float32)]},
               {"column": -1})
    assert float(out2["PositivePair"][0][0]) == 3.0
    assert float(out2["NegativePair"][0][0]) == 1.0
    assert float(out2["NeutralPair"][0][0]) == 1.5

    x = rng_np.normal(size=(3, 4)).astype(np.float32)
    y = rng_np.normal(size=(3, 4)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(run("greater_than", {"X": [x], "Y": [y]})["Out"][0]), x > y)
    np.testing.assert_array_equal(
        np.asarray(run("less_equal", {"X": [x], "Y": [y]})["Out"][0]), x <= y)
    np.testing.assert_allclose(
        np.asarray(run("reduce_max", {"X": [x]}, {"dim": 1})["Out"][0]),
        x.max(1), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(run("reduce_min", {"X": [x]}, {"dim": 0})["Out"][0]),
        x.min(0), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(run("hard_shrink", {"X": [x]}, {"threshold": 0.5})["Out"][0]),
        np.where(np.abs(x) > 0.5, x, 0.0))
    np.testing.assert_allclose(
        np.asarray(run("thresholded_relu", {"X": [x]},
                       {"threshold": 0.3})["Out"][0]),
        np.where(x > 0.3, x, 0.0))

    # conv3d / pool3d / max_pool2d_with_index shapes + values
    v = np.ones((1, 1, 3, 3, 3), np.float32)
    w = np.ones((2, 1, 2, 2, 2), np.float32)
    c3 = np.asarray(run("conv3d", {"Input": [v], "Filter": [w]})["Output"][0])
    assert c3.shape == (1, 2, 2, 2, 2)
    np.testing.assert_allclose(c3, 8.0)
    p3 = np.asarray(run("pool3d", {"X": [v * 2]},
                        {"ksize": [3, 3, 3], "strides": [1, 1, 1],
                         "pooling_type": "avg"})["Out"][0])
    assert p3.shape == (1, 1, 1, 1, 1)
    np.testing.assert_allclose(p3, 2.0)
    img = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    mp = run("max_pool2d_with_index", {"X": [img]},
             {"ksize": [2, 2], "strides": [2, 2]})
    np.testing.assert_array_equal(
        np.asarray(mp["Mask"][0]).reshape(-1), [5, 7, 13, 15])
