"""Utils tooling (image pipeline, plot, topology dump) + profiler/MFU
harness."""

import os

import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.utils import image as I
from paddle_tpu.utils import format_topology, parse_log, plotcurve
from paddle_tpu.utils.plotcurve import Ploter


def test_image_pipeline(rng_np):
    im = (rng_np.random((48, 64, 3)) * 255).astype(np.uint8)
    r = I.resize_short(im, 32)
    assert min(r.shape[:2]) == 32 and r.shape[1] > r.shape[0]
    c = I.center_crop(r, 28)
    assert c.shape[:2] == (28, 28)
    rc = I.random_crop(r, 28, rng=rng_np)
    assert rc.shape[:2] == (28, 28)
    assert np.array_equal(I.left_right_flip(c), c[:, ::-1])
    chw = I.to_chw(c)
    assert chw.shape == (3, 28, 28)
    out = I.simple_transform(im, 36, 32, is_train=True, rng=rng_np,
                             mean=np.array([120.0, 120.0, 120.0]))
    assert out.shape == (3, 32, 32) and out.dtype == np.float32
    gray = I.simple_transform(im[:, :, 0], 36, 32, is_train=False)
    assert gray.shape == (1, 32, 32)


def test_plotcurve_and_ploter(tmp_path):
    log = tmp_path / "train.log"
    log.write_text("\n".join(
        f"I 0101 paddle_tpu] Pass 0, Batch {i}, Cost {3.0 / (i + 1):.4f}, {{}}"
        for i in range(10)))
    points = parse_log(log.read_text().splitlines())
    assert len(points) == 10 and points[0][2] == 3.0
    out = str(tmp_path / "curve.png")
    plotcurve(str(log), out)
    assert os.path.getsize(out) > 0

    p = Ploter("train", "test")
    p.append("train", 0, 1.0)
    p.append("train", 1, 0.5)
    p.plot(str(tmp_path / "ploter.png"))
    assert os.path.getsize(tmp_path / "ploter.png") > 0


def test_show_topology_dump():
    from paddle_tpu.models.lenet import lenet_cost

    cost, predict, img, label = lenet_cost()
    text = paddle.topology.Topology(cost).serialize()
    dump = format_topology(text)
    assert "total parameters:" in dump
    assert "conv" in dump and "fc" in dump


def test_profiler_benchmark_and_flops():
    dim = 256
    a = jnp.ones((dim, dim), jnp.float32)

    def fn(x):
        return x @ x

    flops = profiler.flops_of(fn, a)
    assert flops >= 2 * dim ** 3 * 0.9  # matmul flops dominate

    # counts and signs, never a time: a two-point timing of a 256^3
    # matmul on a machine six workers share says nothing about the code
    res = profiler.benchmark(fn, (a,), iters=5, warmup=2)
    assert res.seconds_per_step > 0
    assert res.mfu >= 0
    assert "ms/step" in repr(res)


def test_profile_trace_writes(tmp_path):
    with profiler.profile(str(tmp_path)):
        with profiler.trace_annotation("matmul"):
            x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
            x.block_until_ready()
    # a plugins/profile dir with at least one trace file appears
    found = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert found, "no profiler output written"
