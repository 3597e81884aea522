"""Model-zoo tests — shape/cost sanity for the benchmark nets
(reference: `benchmark/paddle/image/*.py`, run by `run.sh`).  ResNet-50 runs
at full size in the benchmark's train cells; here we keep CI fast:
smallnet trains a step, the big nets just build + serialize."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.config.topology import Topology
from paddle_tpu.models import image as M
from paddle_tpu.optimizer import Momentum
from paddle_tpu.trainer.step import build_train_step


def test_smallnet_trains_a_step():
    cost, predict, img, label = M.smallnet_cost()
    topo = Topology(cost)
    params = paddle.parameters.create(topo)
    opt = Momentum(momentum=0.9, learning_rate=0.01 / 16)
    step = build_train_step(topo, opt)
    feed = {
        "image": np.random.default_rng(0).normal(size=(16, 32 * 32 * 3)).astype(np.float32),
        "label": np.arange(16) % 10,
    }
    p = params.as_dict()
    before = {k: np.asarray(v).copy() for k, v in p.items()}  # step donates p
    opt_state = opt.init(p, {s.name: s for s in topo.param_specs()})
    p2, _, _, cost_val, metrics = step(p, opt_state, topo.init_states(), feed, jax.random.key(0))
    assert np.isfinite(float(cost_val))
    assert "classification_error_evaluator" in metrics
    moved = any(
        not np.allclose(np.asarray(p2[k]), v) for k, v in before.items()
    )
    assert moved


@pytest.mark.parametrize(
    "builder,n_params",
    [(M.alexnet_cost, 16), (M.resnet_cost, 161), (M.googlenet_cost, 116), (M.vgg_cost, 38)],
)
def test_big_nets_build(builder, n_params):
    cost, predict, img, label = builder()
    topo = Topology(cost)
    assert len(topo.param_specs()) == n_params
    # abstract evaluation (no FLOPs) validates every layer's shape math
    specs = {s.name: s for s in topo.param_specs()}
    feed = {
        "image": jax.ShapeDtypeStruct((2, 224 * 224 * 3), np.float32)
        if "alexnet" not in builder.__name__
        else jax.ShapeDtypeStruct((2, 227 * 227 * 3), np.float32),
        "label": jax.ShapeDtypeStruct((2,), np.int32),
    }
    params = {n: jax.ShapeDtypeStruct(s.shape, s.dtype) for n, s in specs.items()}
    states = {
        s.name: jax.ShapeDtypeStruct(s.shape, np.float32) for s in topo.state_specs()
    }
    out = jax.eval_shape(
        lambda p, st, f: topo.forward(p, st, f, False, jax.random.key(0))[0][
            predict.name
        ],
        params, states, feed,
    )
    assert out.shape == (2, 1000)
    assert topo.serialize()  # config record is stable/serializable
