"""The control of ``correct`` at a toy size: the plain reference in the
precision below the configuration's comes out as not correct."""

from benchmarks import control


def test_train_control_fails_a_limit(data_root):
    out = control.control("resnet_toy_control", seed=2**31 + 5, seconds=1.0,
                          roots=[data_root], on_chip=False)
    assert out["precision"] == "fp8" and not out["correct"]
    assert set(out["fails"]) & {"grad_gap", "delta_gap"}


def test_serve_control_fails_its_limit(data_root):
    out = control.control("gpt2_toy_control", seed=11, seconds=2.0,
                          roots=[data_root], on_chip=False)
    assert out["precision"] == "int8" and not out["correct"]
    assert out["control"]["served_logit_gap"] > \
        out["control"]["program_served_logit_gap"]
