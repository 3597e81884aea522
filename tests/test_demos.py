"""v1_api_demo parity runners (paddle_tpu.demo.*): the REFERENCE configs
execute through our trainer — quick_start's trainer_config.lr.py runs
completely unmodified; traffic_prediction's config is byte-identical
with a py3 data provider; model_zoo's pretrained-binary-dir
load/extract mechanism round-trips."""

from __future__ import annotations

import os

import pytest

REF = os.environ.get("PADDLE_REFERENCE_ROOT", "/root/reference")

pytestmark = pytest.mark.skipif(
    not os.path.isdir(os.path.join(REF, "v1_api_demo")),
    reason="reference checkout absent")


def test_quick_start_reference_config(tmp_path, capsys):
    from paddle_tpu.demo.quick_start import run

    rc = run.main(["--workdir", str(tmp_path), "--passes", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "classification_error_evaluator" in out


def test_traffic_prediction_reference_config(tmp_path, capsys):
    from paddle_tpu.demo.traffic_prediction import run

    rc = run.main(["--workdir", str(tmp_path), "--passes", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Cost" in out
    # the reference config is used byte-identically
    with open(os.path.join(
            REF, "v1_api_demo/traffic_prediction/trainer_config.py")) as f:
        ref = f.read()
    with open(tmp_path / "trainer_config.py") as f:
        assert f.read() == ref


def test_model_zoo_feature_extraction(tmp_path, capsys):
    from paddle_tpu.demo.model_zoo import run

    rc = run.main(["--workdir", str(tmp_path), "--batches", "10"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "features from the reloaded binary-dir model match" in out


def test_sequence_tagging_reference_configs(tmp_path, capsys):
    from paddle_tpu.demo.sequence_tagging import run

    for cfg in ("linear_crf.py", "rnn_crf.py"):
        d = tmp_path / cfg.replace(".py", "")
        rc = run.main(["--workdir", str(d), "--passes", "1",
                       "--config", cfg])
        assert rc == 0
        with open(os.path.join(
                REF, "v1_api_demo/sequence_tagging", cfg)) as f:
            assert (d / cfg).read_text() == f.read()
    out = capsys.readouterr().out
    assert "chunk_f1" in out  # IOB chunk evaluator ran


def test_config_defaults_and_crf_coeff():
    """default_initial_std/default_decay_rate/default_initial_strategy are
    consumed (not silently dropped), crf coeff scales the cost, and both
    reset with the naming counters so they can't leak across builds."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.config import parse_state
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type, extras

    base.reset_name_counters()
    parse_state.default_initial_std(0.0)  # zero-init everything
    parse_state.default_decay_rate(0.25)
    x = layer.data(name="x", type=data_type.dense_vector(4))
    fc = layer.fc_layer(input=x, size=3, act=act.LinearActivation())
    topo = Topology(fc)
    spec = topo.param_specs()[0]
    assert spec.decay_rate == 0.25
    params = paddle.parameters.create(topo)
    assert float(np.abs(params[spec.name]).max()) == 0.0  # std 0 applied
    base.reset_name_counters()
    assert parse_state.G_DEFAULTS["initial_std"] is None  # reset with build

    # crf coeff scales the mean NLL
    base.reset_name_counters()
    from paddle_tpu.core.lod import SequenceBatch

    emis = layer.data(name="emis", type=data_type.dense_vector_sequence(3))
    lbl = layer.data(name="lab", type=data_type.integer_value_sequence(3))
    pa = paddle.attr.Param(name="crfw")
    c1 = extras.crf(input=emis, label=lbl, size=3, name="c1", param_attr=pa)
    c2 = extras.crf(input=emis, label=lbl, size=3, name="c2", coeff=0.5,
                    param_attr=pa)
    topo = Topology([c1, c2])
    params = paddle.parameters.create(topo)
    feed = {
        "emis": SequenceBatch(
            data=np.random.default_rng(0).normal(
                size=(2, 4, 3)).astype(np.float32),
            length=np.asarray([4, 2], np.int32)),
        "lab": SequenceBatch(data=np.zeros((2, 4), np.int32),
                             length=np.asarray([4, 2], np.int32)),
    }
    values, _ = topo.forward(params.as_dict(), {}, feed, False,
                             jax.random.key(0))
    assert abs(float(values["c2"]) - 0.5 * float(values["c1"])) < 1e-6


def test_chunk_evaluator_reads_ids_companion_v2_path():
    """v2 SGD (no CLI): a chunk evaluator on crf_decoding(label=...) must
    score the decoded PATH (the '#ids' companion auto-joins the
    topology), not the 0/1 error indicator."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.evaluator import declare
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type, extras
    from paddle_tpu.trainer_config_helpers.evaluators import chunk_evaluator

    base.reset_name_counters()
    declare.reset()
    x = layer.data(name="x", type=data_type.dense_vector_sequence(6))
    emis = layer.fc_layer(input=x, size=5, act=act.LinearActivation())
    lbl = layer.data(name="lab", type=data_type.integer_value_sequence(5))
    pa = paddle.attr.Param(name="crfw")
    dec = extras.crf_decoding(input=emis, size=5, label=lbl, name="dec",
                              param_attr=pa)
    cost = extras.crf(input=emis, label=lbl, size=5, param_attr=pa)
    chunk_evaluator(input=dec, label=lbl, chunk_scheme="IOB",
                    num_chunk_types=2, name="f1")
    params = paddle.parameters.create(paddle.topology.Topology(cost))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Adam(learning_rate=5e-2),
        declared_evaluators=declare.collect())
    assert "dec#ids" in {n.name for n in trainer.topology.nodes}

    rng = np.random.default_rng(0)
    def reader():
        for _ in range(32):
            y = rng.integers(0, 4, size=(6,)).astype(np.int32)
            xv = np.zeros((6, 6), np.float32)
            xv[np.arange(6), y] = 2.0
            yield xv, y
    seen = {}
    def on_event(ev):
        if isinstance(ev, paddle.event.EndPass):
            seen.update(ev.metrics)
    trainer.train(reader=paddle.reader.batch(reader, batch_size=8),
                  num_passes=10, event_handler=on_event)
    f1 = [v for k, v in seen.items() if "F1" in k]
    # the mapping is learnable; a real (path-scored) F1 climbs well above
    # what scoring the [B,1] error indicator could ever produce
    assert f1 and f1[0] > 0.5, seen


def test_mnist_reference_config(tmp_path, capsys):
    """light_mnist.py + mnist_provider.py run byte-identical (only
    mnist_util is a py3 port); the synthetic digits are learned."""
    from paddle_tpu.demo.mnist import run
    from paddle_tpu.parallel import mesh as mesh_mod

    prev = mesh_mod.get_mesh()
    try:
        # the config's batch_size=50 doesn't divide the 8-device test mesh
        mesh_mod.get_mesh({"data": 1})
        rc = run.main(["--workdir", str(tmp_path), "--passes", "2",
                       "--n-train", "512", "--n-test", "128"])
    finally:
        mesh_mod.set_mesh(prev)
    assert rc == 0
    for fn in ("light_mnist.py", "mnist_provider.py"):
        with open(os.path.join(REF, "v1_api_demo/mnist", fn)) as f:
            assert (tmp_path / fn).read_text() == f.read()
    out = capsys.readouterr().out
    last = [l for l in out.splitlines() if "Eval:" in l][-1]
    err = float(last.split("classification_error_evaluator=")[1].split()[0])
    assert err < 0.1, out


def test_gan_reference_config_alternating_machines(tmp_path):
    """gan_conf.py runs VERBATIM; the gan_trainer.py two-machine
    alternating loop trains both sides with finite oscillating losses."""
    import numpy as np

    from paddle_tpu.demo.gan import run as gan_run

    np.random.seed(0)
    dis_losses, gen_losses, sides, final = gan_run.run(
        data_source="uniform", num_iter=16,
        workdir=str(tmp_path / "gan"), log_period=8)
    assert len(dis_losses) == 16 and len(gen_losses) == 16
    assert np.isfinite(dis_losses).all() and np.isfinite(gen_losses).all()
    # both machines actually take update steps
    assert set(sides) == {"dis", "gen"}
    # the discriminator's loss moves (training is live, not a no-op)
    assert dis_losses[-1] != dis_losses[0]
    assert final.shape[1] == 2  # sample_dim from the verbatim config


def test_gan_image_reference_config_parses_and_steps(tmp_path):
    """gan_conf_image.py (conv+BN generator/discriminator) builds all
    three machines and completes alternating iterations."""
    import numpy as np

    from paddle_tpu.demo.gan import run as gan_run

    np.random.seed(0)
    dis_losses, gen_losses, sides, final = gan_run.run(
        data_source="mnist", num_iter=2,
        workdir=str(tmp_path / "ganimg"), log_period=1)
    assert np.isfinite(dis_losses).all() and np.isfinite(gen_losses).all()
    assert final.shape[1] == 784


def test_vae_reference_config_elbo_decreases(tmp_path):
    """vae_conf.py runs VERBATIM through the vae_train.py loop; the ELBO
    cost decreases and the decoder generates via the second machine."""
    import numpy as np

    from paddle_tpu.demo.vae import run as vae_run

    np.random.seed(0)
    losses, samples = vae_run.run(num_batches=24,
                                  workdir=str(tmp_path / "vae"),
                                  log_period=8)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert samples.shape[1] == 784
    assert 0.0 <= samples.min() and samples.max() <= 1.0  # sigmoid decoder
