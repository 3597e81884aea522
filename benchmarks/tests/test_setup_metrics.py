"""The set-up metrics (``layer_metrics/*.setup``): the reducer on
hand-made spans, the two toy cells whose traced CPU runs carry all nine
between them, and a program without the spans, which reports nothing."""

import json

import pytest

from benchmarks.harness import runner

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NINE = {"import_s.setup", "engine_ready_s.setup", "train_build_place_s.setup",
        "params_sync_s.setup", "xla_trace_lower_s.setup",
        "xla_compile_s.setup", "xla_cache_fetch_s.setup",
        "programs_built.setup", "setup_spanned_s.setup"}


def _run(cell, data_root, capsys, seconds=1.0, seed=2**31 + 34):
    out = runner.run_cell(cell, seed=seed, seconds=seconds, trace=True,
                          roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out and KEYS <= set(out)
    assert out["device"]["platform"] == "cpu" and out["correct"]
    return {k: m["value"] for k, m in out["metrics"].items()}


def _reduce(name, spans):
    spec = runner.load_json("layer_metrics", name, [runner.ROOT])
    reducer = runner.load_py("reducers", spec["reducer"], [runner.ROOT])
    return reducer.reduce(spec, {"spans": spans}, None)


def _span(name, t0, t1, i, parent=None, **args):
    return {"name": name, "thread": "MainThread", "t0": t0, "t1": t1,
            "id": i, "parent": parent, "args": args}


def test_set_up_metrics_of_a_serving_replica(data_root, capsys):
    """The window's ``clear()`` leaves set-up in ``layer["spans"]``:
    ``engine_ready`` with its programs, XLA's builds, the package's
    import -- read by the cell's metrics with no edit to a driver."""
    v = _run("gpt2_toy_setup", data_root, capsys)
    assert NINE - {"train_build_place_s.setup", "params_sync_s.setup"} <= set(v)
    # two prefill programs (1 and prefill_batch rows) + decode, at least
    assert v["programs_built.setup"] >= 3
    assert v["engine_ready_s.setup"] > 0.0 and v["import_s.setup"] > 0.0
    assert v["xla_trace_lower_s.setup"] > 0.0
    assert v["xla_compile_s.setup"] >= 0.0 and v["xla_cache_fetch_s.setup"] >= 0.0
    assert v["xla_compile_s.setup"] + v["xla_cache_fetch_s.setup"] > 0.0
    # the union holds engine_ready, and the import before it
    assert v["setup_spanned_s.setup"] >= (v["engine_ready_s.setup"]
                                          + v["import_s.setup"]) * 0.999
    assert v["decode_step_span_ms.serve"] > 0.0     # the window's own


def test_set_up_metrics_of_a_trainer(data_root, capsys):
    """Three ``train()`` calls of the driver: one build, a placement and
    two ``Parameters`` round trips each (to the device, and back)."""
    v = _run("resnet_toy_setup", data_root, capsys, seconds=1.5)
    assert NINE - {"engine_ready_s.setup"} <= set(v)
    assert v["train_build_place_s.setup"] > 0.0
    assert 0.0 < v["params_sync_s.setup"] <= v["setup_spanned_s.setup"]
    assert v["programs_built.setup"] >= 1      # the step, at the least
    assert v["setup_spanned_s.setup"] >= v["train_build_place_s.setup"]
    assert v["trainer_loop_self_ms.train"] >= 0.0   # the window's own


def test_the_nine_are_named_by_the_two_toy_cells(data_root):
    named = set()
    for cell in ("gpt2_toy_setup", "resnet_toy_setup"):
        named |= set(runner.load_json("workloads", cell,
                                      [data_root])["per_layer"])
    assert NINE <= named
    for name in NINE:
        spec = runner.load_json("layer_metrics", name, [runner.ROOT])
        assert (spec["layer"], spec["moves"], spec["source"]) == (
            "set-up", "setup_s", "program_span")


def test_span_total_s_on_hand_made_spans():
    spans = [
        _span("import_paddle_tpu", 0.0, 2.0, 1),
        _span("engine_ready", 10.0, 14.0, 2),
        _span("program_ready", 10.0, 11.5, 3, 2),
        _span("xla_trace", 10.0, 10.6, 4, 3),
        _span("xla_trace", 10.1, 10.3, 5, 3),       # a helper inside it
        _span("xla_lower", 10.6, 11.0, 6, 3),
        _span("xla_cache_fetch", 11.0, 11.5, 7, 3),
        _span("program_ready", 11.5, 14.0, 8, 2),
        _span("xla_trace", 11.5, 12.0, 9, 8),
        _span("xla_compile", 12.0, 14.0, 10, 8),
        _span("serve_decode", 20.0, 20.5, 11),
    ]
    assert _reduce("import_s.setup", spans) == pytest.approx(2.0)
    assert _reduce("engine_ready_s.setup", spans) == pytest.approx(4.0)
    # the nested trace is not counted twice
    assert _reduce("xla_trace_lower_s.setup", spans) == pytest.approx(1.5)
    assert _reduce("xla_compile_s.setup", spans) == pytest.approx(2.0)
    assert _reduce("xla_cache_fetch_s.setup", spans) == pytest.approx(0.5)
    assert _reduce("programs_built.setup", spans) == 2
    assert _reduce("setup_spanned_s.setup", spans) == pytest.approx(6.0)
    # no trainer here
    assert _reduce("params_sync_s.setup", spans) is None
    assert _reduce("train_build_place_s.setup", spans) is None
    # a warm process compiled nothing: 0, not missing
    warm = [s for s in spans if s["name"] != "xla_compile"]
    assert _reduce("xla_compile_s.setup", warm) == 0.0
    assert _reduce("programs_built.setup", warm) == 1


def test_a_program_without_the_spans_reports_nothing_and_does_not_raise():
    """Laid over the parent, whose tracer keeps no set-up span and hears
    no build, every one of the nine returns None."""
    old = [_span("step", 0.0, 1.0, 1), _span("serve_decode", 0.0, 0.3, 2)]
    for name in sorted(NINE):
        assert _reduce(name, old) is None, name
        assert _reduce(name, []) is None, name
