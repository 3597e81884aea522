"""Worker for the multi-process jax.distributed integration test.

Run as: python _multihost_worker.py <process_id> <num_processes> <port> <out>

Each process owns 4 virtual CPU devices; together they form one 8-device
dp mesh.  The model/data/step are identical to what the single-process
reference path in tests/test_multihost_process.py builds via
``build_model`` / ``run_steps`` below — the test asserts final-parameter
equality.  (≅ the reference's in-process cluster tests,
``paddle/trainer/tests/test_CompareSparse.cpp:65-73``, redone for the
multi-controller SPMD runtime.)
"""

from __future__ import annotations

import os
import pickle
import sys


def _setup_env(local_devices: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "").split(
            " --xla_force_host_platform_device_count", 1)[0]
        + f" --xla_force_host_platform_device_count={local_devices}")
    os.environ.setdefault("JAX_ENABLE_X64", "0")


def build_model():
    """Tiny classifier (deterministic init) + its jitted dp train step."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core import rng
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.trainer.step import build_train_step

    base.reset_name_counters()
    rng.seed(7)
    x = layer.data(name="x", type=data_type.dense_vector(8))
    hidden = layer.fc(input=x, size=16, act=act.ReluActivation())
    predict = layer.fc(input=hidden, size=4, act=act.SoftmaxActivation())
    lbl = layer.data(name="label", type=data_type.integer_value(4))
    cost = layer.classification_cost(input=predict, label=lbl)
    topo = Topology(cost)
    params = paddle.parameters.create(topo).as_dict()
    opt = Momentum(momentum=0.9, learning_rate=0.05)
    specs = {s.name: s for s in topo.param_specs()}
    opt_state = opt.init(params, specs)
    states = topo.init_states()
    step = build_train_step(topo, opt)
    return params, opt_state, states, step


def global_feed(step_idx: int, batch: int = 16):
    """Deterministic global batch for step ``step_idx`` (same on all hosts)."""
    import numpy as np

    g = np.random.default_rng(1000 + step_idx)
    xs = g.normal(size=(batch, 8)).astype(np.float32)
    ys = g.integers(0, 4, size=(batch,)).astype(np.int32)
    return {"x": xs, "label": ys}


def run_steps(params, opt_state, states, step, place, n_steps: int = 4):
    """place(feed_np) -> on-device feed; returns final params as numpy."""
    import jax
    import numpy as np

    key = jax.random.key(0)
    for i in range(n_steps):
        feed = place(global_feed(i))
        params, opt_state, states, cost, _ = step(
            params, opt_state, states, feed, key)
    return {k: np.asarray(jax.device_get(v.addressable_data(0)))
            if hasattr(v, "addressable_data") else np.asarray(v)
            for k, v in params.items()}


def main() -> None:
    pid, nproc, port, out = (int(sys.argv[1]), int(sys.argv[2]),
                             sys.argv[3], sys.argv[4])
    _setup_env(local_devices=8 // nproc)
    import jax

    from paddle_tpu.distributed import multihost as mh

    mh.initialize(coordinator_address=f"127.0.0.1:{port}",
                  num_processes=nproc, process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    mesh = mh.pod_mesh(data=None)
    params, opt_state, states, step = build_model()

    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())

    def as_global(tree, sharding):
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, __import__("numpy").asarray(x)), tree)

    params = as_global(params, repl)
    opt_state = as_global(opt_state, repl)
    states = as_global(states, repl)

    def place(feed_np):
        # every host slices ITS rows of the deterministic global batch,
        # then assembles the globally-sharded array (the real multi-host
        # input path: mh.global_batch / make_array_from_process_local_data)
        n = feed_np["x"].shape[0]
        lo = pid * (n // nproc)
        hi = lo + n // nproc
        local = {k: v[lo:hi] for k, v in feed_np.items()}
        return mh.global_batch(local, mesh)

    final = run_steps(params, opt_state, states, step, place)
    if pid == 0:
        with open(out, "wb") as f:
            pickle.dump(final, f)
    # all processes must stay alive until the collective program finishes
    jax.effects_barrier()


if __name__ == "__main__":
    main()
