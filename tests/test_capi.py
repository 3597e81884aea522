"""C inference ABI: merge a trained model to one artifact, serve it from a
real C program linked against libpaddle_capi.so, and check the C outputs
equal python-side inference (the reference tests capi via
examples/model_inference + gradient_machine tests)."""

import os
import subprocess

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.build import native_binary
from paddle_tpu.models.lenet import lenet_cost
from paddle_tpu.utils.merge_model import MergedModel, merge_v2_model

_NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


def _train_tiny():
    cost, predict, img, label = lenet_cost()
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.SGD(learning_rate=0.01),
    )
    reader = paddle.reader.batch(paddle.dataset.mnist.train(), batch_size=32)
    trainer.train(reader=paddle.reader.firstn(reader, 3), num_passes=1)
    return predict, trainer.parameters


def test_merge_model_python_roundtrip(tmp_path):
    predict, parameters = _train_tiny()
    path = str(tmp_path / "model.tar")
    merge_v2_model(predict, parameters, path)

    samples = [s for _, s in zip(range(6), paddle.dataset.mnist.test()())]
    x = np.stack([s[0] for s in samples]).astype(np.float32)
    ref = paddle.infer(output_layer=predict, parameters=parameters,
                       input=[(s[0],) for s in samples])

    m = MergedModel.from_path(path)
    (probs,) = m.forward(x)
    np.testing.assert_allclose(probs, ref, rtol=1e-5, atol=1e-6)
    # a different batch size through the same artifact (symbolic batch dim)
    (probs2,) = m.forward(x[:2])
    np.testing.assert_allclose(probs2, ref[:2], rtol=1e-5, atol=1e-6)


def test_c_program_serves_model(tmp_path):
    predict, parameters = _train_tiny()
    model = str(tmp_path / "model.tar")
    merge_v2_model(predict, parameters, model)

    samples = [s for _, s in zip(range(4), paddle.dataset.mnist.test()())]
    x = np.stack([s[0] for s in samples]).astype("<f4")
    ref = paddle.infer(output_layer=predict, parameters=parameters,
                       input=[(s[0],) for s in samples])

    exe = native_binary("capi_infer")

    pypath = os.path.dirname(_NATIVE) + os.pathsep + \
        os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pypath)
    out = subprocess.run(
        [exe, model, str(x.shape[1]), str(x.shape[0]), "--use_cpu"],
        input=x.tobytes(), stdout=subprocess.PIPE, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-2000:]
    got = np.array([[float(v) for v in line.split()]
                    for line in out.stdout.decode().strip().splitlines()])
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_shared_param_machines(tmp_path):
    """create_shared_param: shared machines alias ONE loaded artifact (no
    per-machine weight copy) and produce identical outputs; the C-level
    multi-thread serving bench (serve_bench.c) runs green."""
    from paddle_tpu import capi_bridge

    predict, parameters = _train_tiny()
    model = str(tmp_path / "model.tar")
    merge_v2_model(predict, parameters, model)

    with open(model, "rb") as f:
        origin = capi_bridge.create_machine(f.read())
    shared = capi_bridge.create_shared_machine(origin)
    # exact aliasing: one MergedModel object behind both handles
    assert capi_bridge._machines[origin] is capi_bridge._machines[shared]

    x = np.random.default_rng(0).normal(size=(4, 784)).astype("<f4")
    a = capi_bridge.forward(origin, [x.tobytes()], 4)
    b = capi_bridge.forward(shared, [x.tobytes()], 4)
    assert a[0][0] == b[0][0]  # byte-identical outputs
    capi_bridge.destroy_machine(shared)
    # origin still serves after destroying the shared handle
    assert capi_bridge.forward(origin, [x.tobytes()], 4)[0][0] == a[0][0]
    capi_bridge.destroy_machine(origin)

    exe = native_binary("serve_bench")
    pypath = os.path.dirname(_NATIVE) + os.pathsep + \
        os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pypath)
    out = subprocess.run([exe, model, "8", "2", "3", "--use_cpu"],
                         stdout=subprocess.PIPE, env=env, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:]
    assert b"threads=2" in out.stdout


def test_forward_releases_gil_for_overlap(tmp_path):
    """Decides the serving thread-overlap question BY CONSTRUCTION:
    during ``MergedModel.forward`` — the exact call the
    C ABI's ``paddle_gradient_machine_forward`` lands in — the GIL is
    released by jaxlib's PJRT execute, so a concurrent thread makes
    Python progress while the device computes.  A 1 kHz ticker thread
    heartbeats through a multi-forward window; the assertion is on the
    LONGEST inter-heartbeat gap (see the comment below for why a tick
    count cannot discriminate), which is valid on a single-core host
    too."""
    import threading
    import time

    from paddle_tpu.layers import api as layer, base, data_type

    base.reset_name_counters()
    x = layer.data(name="gx", type=data_type.dense_vector(2048))
    h = x
    for _ in range(12):
        h = layer.fc(input=h, size=2048)
    parameters = paddle.parameters.create(paddle.topology.Topology(h))
    path = str(tmp_path / "big.tar")
    merge_v2_model(h, parameters, path)
    m = MergedModel.from_path(path)

    batch = np.random.default_rng(0).normal(
        size=(512, 2048)).astype(np.float32)
    m.forward(batch)  # compile outside the measured window

    stamps: list[float] = []
    stop = threading.Event()

    def ticker():
        while not stop.is_set():
            stamps.append(time.monotonic())
            time.sleep(0.001)

    # one forward's duration, marshalling included — the discriminating
    # statistic below is relative to it
    t0 = time.monotonic()
    m.forward(batch)
    per_fwd = time.monotonic() - t0

    t = threading.Thread(target=ticker, daemon=True)
    t.start()
    time.sleep(0.05)
    t0 = time.monotonic()
    for _ in range(4):
        m.forward(batch)
    t1 = time.monotonic()
    stop.set()
    t.join(timeout=2)

    # Discriminator: the LONGEST gap between ticker heartbeats inside
    # the forward window.  If PJRT held the GIL during device execution,
    # the ticker would starve for one whole execute stretch (most of
    # per_fwd) — interpreter switch intervals cannot preempt a C
    # extension that holds the GIL.  With the release in place, gaps
    # stay at scheduler scale even on one core.  (A mere tick COUNT
    # cannot distinguish these: ticks also accrue in the Python
    # marshalling slices between executes.)
    inside = [s for s in stamps if t0 - 0.002 <= s <= t1]
    if per_fwd < 0.05:
        import pytest

        pytest.skip(f"forward too fast ({per_fwd*1e3:.0f} ms) to "
                    "discriminate GIL starvation on this host")
    assert len(inside) >= 3, (len(stamps), per_fwd)
    gaps = [b - a for a, b in zip(inside, inside[1:])]
    max_gap = max(gaps + [t1 - inside[-1], inside[0] - t0])
    assert max_gap < 0.6 * per_fwd, (max_gap, per_fwd)
