"""Trainer CLI (`python -m paddle_tpu.trainer`) + v1 config-file e2e.

≅ TrainerMain.cpp job modes (train/test/time/checkgrad, :24-61) and the
reference's own trainer tests (test_Trainer.cpp, test_TrainerOnePass.cpp)
driving sample_trainer_config.conf; plus the v1_api_demo compatibility
claim: unmodified reference config files (light_mnist.py,
sample_trainer_config.conf) parse and train through the shim.
"""

from __future__ import annotations

import os
import textwrap

import numpy as np
import pytest

REF_CONF = "/root/reference/paddle/trainer/tests/sample_trainer_config.conf"
LIGHT_MNIST = "/root/reference/v1_api_demo/mnist/light_mnist.py"


def _write_digits_config(tmp_path):
    """A small v1 config + PyDataProvider2 provider over synthetic digits."""
    cfg = tmp_path / "digits.conf"
    cfg.write_text(textwrap.dedent("""
        from paddle.trainer_config_helpers import *

        define_py_data_sources2(
            train_list='{d}/train.list', test_list='{d}/test.list',
            module='digits_provider', obj='process')
        settings(batch_size=32, learning_rate=1e-2,
                 learning_method=AdamOptimizer())

        img = data_layer(name='pixel', size=64)
        hidden = fc_layer(input=img, size=32, act=ReluActivation())
        predict = fc_layer(input=hidden, size=4, act=SoftmaxActivation())
        lbl = data_layer(name='label', size=4)
        outputs(classification_cost(input=predict, label=lbl))
    """).format(d=tmp_path))
    (tmp_path / "digits_provider.py").write_text(textwrap.dedent("""
        import numpy as np
        from paddle.trainer.PyDataProvider2 import (
            provider, dense_vector, integer_value)

        @provider(input_types={'pixel': dense_vector(64),
                               'label': integer_value(4)})
        def process(settings, filename):
            rng = np.random.default_rng(int(filename.split('-')[-1]))
            for _ in range(256):
                y = int(rng.integers(0, 4))
                x = rng.normal(size=(64,)).astype(np.float32) * 0.1
                x[y * 16:(y + 1) * 16] += 1.0
                yield x, y
    """))
    (tmp_path / "train.list").write_text("seed-0\nseed-1\n")
    (tmp_path / "test.list").write_text("seed-7\n")
    return str(cfg)


def test_cli_train_test_and_checkpoint(tmp_path, capsys):
    from paddle_tpu.trainer import cli

    cfg = _write_digits_config(tmp_path)
    save = tmp_path / "out"
    rc = cli.main(["--config", cfg,
                   "--config_args", f"unused=1",
                   "--job", "train", "--num_passes", "2",
                   "--save_dir", str(save), "--log_period", "4"])
    assert rc == 0
    ckpt = save / "pass-00001.tar"
    assert ckpt.exists()
    out = capsys.readouterr().out
    costs = [float(ln.split("Cost ")[1].split(",")[0])
             for ln in out.splitlines() if "Cost " in ln]
    assert costs[-1] < costs[0] * 0.7, costs

    # --job=test with the trained parameters
    rc = cli.main(["--config", cfg, "--job", "test",
                   "--init_model_path", str(ckpt)])
    assert rc == 0
    out = capsys.readouterr().out
    test_cost = float(out.split("Test cost ")[1].split(",")[0])
    assert test_cost < 1.0  # well below ln(4)=1.386 after training


@pytest.mark.skipif(not os.path.exists(REF_CONF),
                    reason="reference checkout not available")
def test_cli_checkgrad_reference_conf():
    """checkgrad over the UNMODIFIED reference sample_trainer_config.conf."""
    from paddle_tpu.trainer import cli

    rc = cli.main(["--config", REF_CONF, "--job", "checkgrad",
                   "--checkgrad_samples", "4"])
    assert rc == 0


def test_cli_checkgrad_catches_broken_gradient(tmp_path):
    """A layer whose custom_vjp lies about its gradient must FAIL the check
    (≅ the reference using checkgrad to validate hand-written backward)."""
    cfg = tmp_path / "broken.conf"
    cfg.write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from paddle.trainer_config_helpers import *
        from paddle_tpu.layers.base import LayerOutput, gen_name

        settings(batch_size=8, learning_rate=1e-3)

        @jax.custom_vjp
        def lying_square(x):
            return x * x

        def _fwd(x):
            return x * x, x

        def _bwd(x, g):
            return (g * 3.0 * x,)  # WRONG: claims d(x^2)/dx = 3x

        lying_square.defvjp(_fwd, _bwd)

        din = data_layer(name='data', size=6)
        base = fc_layer(input=din, size=6, act=LinearActivation())

        def fwd(ctx, params, states, x):
            return lying_square(x)

        # piggyback an emitted layer type; only the runtime fn (and its
        # lying vjp) matter to checkgrad
        broken = LayerOutput(name=gen_name('fc_layer'),
                             layer_type='slope_intercept',
                             size=6, parents=(base,), fn=fwd,
                             attrs={'slope': 1.0, 'intercept': 0.0})
        outputs(broken)
    """))
    from paddle_tpu.trainer import cli

    rc = cli.main(["--config", str(cfg), "--job", "checkgrad"])
    assert rc == 1


@pytest.mark.skipif(not os.path.exists(REF_CONF),
                    reason="reference checkout not available")
def test_sample_trainer_config_trains():
    """The unmodified reference .conf file builds and LEARNS (v1 e2e)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.trainer.config_parser import parse_config
    from paddle_tpu.trainer.step import build_train_step
    from paddle_tpu.trainer_config_helpers.optimizers import (
        get_settings_optimizer,
    )

    parsed = parse_config(REF_CONF, "with_cost=1")
    topo = Topology(parsed.output_layers())
    opt = get_settings_optimizer()
    specs = {s.name: s for s in topo.param_specs()}
    params = paddle.parameters.create(topo).as_dict()
    opt_state = opt.init(params, specs)
    states = topo.init_states()
    step = build_train_step(topo, opt)
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    first = last = None
    for i in range(40):
        y = rng.integers(0, 3, size=(32,))
        x = (np.eye(3, dtype=np.float32)[y] * 2.0
             + rng.normal(size=(32, 3)).astype(np.float32) * 0.1)
        feed = {"input": x, "label": y}
        params, opt_state, states, c, _ = step(
            params, opt_state, states, feed, key)
        c = float(c)
        first = first if first is not None else c
        last = c
    assert last < first * 0.6, (first, last)


@pytest.mark.skipif(not os.path.exists(LIGHT_MNIST),
                    reason="reference checkout not available")
def test_light_mnist_parses_and_trains():
    """v1_api_demo/mnist/light_mnist.py — the named compatibility
    config — parses unmodified and its 4x[conv-BN-relu-pool] CNN learns."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.trainer.config_parser import parse_config
    from paddle_tpu.trainer.step import build_train_step
    from paddle_tpu.trainer_config_helpers.optimizers import (
        get_settings_optimizer,
    )

    parsed = parse_config(LIGHT_MNIST, "")
    assert parsed.opt_config.learning_method == "adam"
    assert parsed.trainer_config.data_config.load_data_module == (
        "mnist_provider")
    topo = Topology(parsed.output_layers())
    names = {n.layer_type for n in topo.nodes}
    assert "exconv" in names and "batch_norm" in names

    opt = get_settings_optimizer()
    specs = {s.name: s for s in topo.param_specs()}
    params = paddle.parameters.create(topo).as_dict()
    opt_state = opt.init(params, specs)
    states = topo.init_states()
    step = build_train_step(topo, opt)
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    batch = 4
    first = last = None
    for i in range(4):
        y = rng.integers(0, 10, size=(batch,))
        x = rng.normal(size=(batch, 28 * 28)).astype(np.float32) * 0.05
        x[np.arange(batch), y * 20] += 3.0  # learnable pixel cue
        feed = {"pixel": x, "label": y}
        params, opt_state, states, c, _ = step(
            params, opt_state, states, feed, key)
        c = float(c)
        first = first if first is not None else c
        last = c
    assert np.isfinite(last)
    assert last < first * 1.5  # trains without diverging in a few steps


def test_cli_gflags_passthrough_and_restore(tmp_path):
    """Unknown argparse args route to the gflags registry (TrainerMain's
    gflags convention), apply for the job, and restore afterwards."""
    from paddle_tpu.core import flags
    from paddle_tpu.trainer import cli

    cfg = tmp_path / "c.py"
    cfg.write_text(
        "from paddle.trainer_config_helpers import *\n"
        "settings(batch_size=4, learning_rate=0.1)\n"
        "x = data_layer('x', 4)\n"
        "y = fc_layer(input=x, size=2, act=LinearActivation())\n"
        "lab = data_layer('l', 2)\n"
        "outputs(mse_cost(input=y, label=lab))\n")
    assert flags.get("with_timer") is False
    rc = cli.main(["--config", str(cfg), "--job", "time",
                   "--with_timer", "--bf16"])
    assert rc == 0
    # restored after the in-process call
    assert flags.get("with_timer") is False
    assert flags.get("bf16") is False

    import pytest
    with pytest.raises(SystemExit):
        cli.main(["--config", str(cfg), "--job", "time", "--not_a_flag"])
