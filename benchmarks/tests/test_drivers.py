"""Each driver end to end at a toy size on the CPU (kernels resolve to
their interpreted/reference twins there), the contract's last line, the
refusal to measure without a chip, and a timed path broken underneath
coming out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import runner

REPO = os.path.dirname(runner.ROOT)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _checks(result):
    return {c["name"]: c for c in result["checks"]}


def _run(cell, data_root, capsys, trace=False, seconds=1.5, seed=2**31 + 77):
    out = runner.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                          roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out and KEYS <= set(out)
    assert out["device"]["platform"] == "cpu"
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    return out


def test_train_driver_end_to_end(data_root, capsys):
    out = _run("resnet_toy_train", data_root, capsys)
    assert set(out["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"])
    # the traced run reports per-layer metrics only; without a device
    # trace the trace readers return nothing and are left out of the line
    out = _run("resnet_toy_train", data_root, capsys, trace=True)
    assert set(out["metrics"]) == {"input_wait_ms.train"}


def test_train_driver_on_four_devices(data_root, capsys):
    """The data-parallel cell on 4 virtual devices: per-shard batch-norm
    in the program and in the reference."""
    out = _run("resnet_toy_dp4", data_root, capsys)
    assert out["device"]["count"] == 4 and out["correct"]


def test_files_only_additions_are_picked_up(data_root, capsys):
    """A new cell, per-layer metrics and a reducer exist only as files
    under tests/data; nothing under benchmarks/ was edited for them."""
    out = _run("resnet_toy_added", data_root, capsys, trace=True)
    assert set(out["metrics"]) == {"input_wait_ms.train",
                                   "fence_self_ms.train",
                                   "steps_counted.train"}
    assert out["metrics"]["steps_counted.train"]["value"] == out["attempted"]


def test_a_new_model_family_is_files_only(data_root, capsys):
    """A configuration of another model family (a perceptron on vectors),
    its plain reference with the names, the feed and the shapes it needs,
    and its control exist only as files under tests/data; the trainer's
    driver, the control and the harness were not edited for them."""
    from benchmarks import control

    out = _run("mlp_toy_train", data_root, capsys)
    assert out["correct"] and out["attempted"] > 0
    assert {c["name"] for c in out["checks"]} >= {"loss_gap", "grad_diff_p90"}
    ctl = control.control("mlp_toy_train", seed=3, seconds=1.0,
                          roots=[data_root], on_chip=False)
    assert not ctl["correct"] and "grad_diff_p90" in ctl["fails"]


def test_frozen_step_is_not_correct(data_root, capsys, monkeypatch):
    """The timed path broken underneath: a step that returns its state
    unchanged."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.trainer.trainer as trainer_mod

    real = trainer_mod.build_train_step

    def build(*a, **kw):
        step = real(*a, **kw)

        def frozen(params, opt_state, states, feed, key):
            keep = jax.tree.map(jnp.copy, (params, opt_state, states))
            out = step(params, opt_state, states, feed, key)
            return keep + tuple(out[3:])

        frozen.lower = step.lower
        return frozen

    monkeypatch.setattr(trainer_mod, "build_train_step", build)
    out = _run("resnet_toy_train", data_root, capsys)
    checks = _checks(out)
    assert not out["correct"]
    assert not checks["grad_gap"]["ok"] and not checks["delta_gap"]["ok"]
    assert checks["delta_gap"]["value"] == pytest.approx(1.0)


def test_serve_driver_end_to_end(data_root, capsys):
    out = _run("gpt2_toy_closed", data_root, capsys, seconds=2.0)
    assert set(out["metrics"]) == {"serve_tok_per_s", "serve_itl_p95_ms",
                                   "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 100
    out = _run("gpt2_toy_open", data_root, capsys, seconds=3.0, trace=True)
    assert {"loadgen_late_p95_ms.serve", "queue_wait_p95_ms.serve",
            "prefill_pass_ms.serve", "decode_step_host_ms.serve"} == set(
                out["metrics"])
    assert out["correct"]


def test_closed_loop_traced_run_reports_what_the_cell_names(data_root,
                                                            capsys):
    """A closed loop of a few clients sends tens of requests a window:
    every per-layer metric its cell names that is read off the host (all
    but the device trace's, which a CPU run has none of) is in the line.
    A tail over requests is not one of them: it would need 200."""
    out = _run("gpt2_toy_closed", data_root, capsys, seconds=1.0, trace=True)
    cell = runner.load_json("workloads", "gpt2_toy_closed", [data_root])
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [data_root, runner.ROOT])["source"]
        != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 6
    assert "loadgen_late_mean_ms.serve" in host


def test_altered_token_is_not_correct(data_root, capsys, monkeypatch):
    """A token altered where it is produced."""
    from paddle_tpu.serving import scheduler as sched_mod

    real = sched_mod.Scheduler.append_token

    def altered(self, a, token):
        return real(self, a, (token + 1) % 256
                    if len(a.generated) == 1 else token)

    monkeypatch.setattr(sched_mod.Scheduler, "append_token", altered)
    out = _run("gpt2_toy_closed", data_root, capsys, seconds=2.0)
    assert not out["correct"]
    assert not _checks(out)["served_logit_gap"]["ok"]


def test_no_chip_no_result():
    """The measurement path (the command of BENCHMARK.json) exits
    non-zero and prints no result line when jax finds no TPU."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    p = subprocess.run(
        [sys.executable] + bench["command"][1:] + [
            "--workload", bench["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == runner.EXIT_NO_CHIP
    assert '"correct"' not in p.stdout and "need 1 TPU" in p.stderr


@pytest.mark.parametrize("cell, driver", [("gpt2_toy_closed", "serve_engine"),
                                          ("ouro_toy_closed", "serve_lm")])
def test_a_slow_stop_trace_stalls_no_client(cell, driver, data_root, capsys,
                                            monkeypatch):
    """``stop_trace`` takes longer than the window and the grace together
    (as it does on the chip past ~650k device events): the clients go on
    sending meanwhile, nothing is failed, the driver joins the stop before
    it loads the trace, and the line says how long the stop took."""
    import threading
    import time

    from benchmarks.harness import trace as trace_mod

    cfg = runner.load_json("configs", runner.load_json(
        "workloads", cell, [data_root])["config"], [data_root])
    assert cfg["driver"] == driver
    seconds = 1.0
    grace = runner.load_py("drivers", "serve_engine", [runner.ROOT]).GRACE_S
    real_stop, real_attach, seen = trace_mod.stop, trace_mod.attach, {}

    def slow_stop():
        seen["stop_thread"] = threading.current_thread().name
        time.sleep(seconds + grace + 1.0)
        real_stop()
        seen["stopped"] = True

    def attach(*a, **kw):
        seen["stopped_before_attach"] = seen.get("stopped", False) and not [
            t for t in threading.enumerate() if t.name == "bench-trace-stop"]
        return real_attach(*a, **kw)

    monkeypatch.setattr(trace_mod, "stop", slow_stop)
    monkeypatch.setattr(trace_mod, "attach", attach)
    out = _run(cell, data_root, capsys, trace=True, seconds=seconds)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 8
    assert seen["stop_thread"] == "bench-trace-stop"
    assert seen["stopped_before_attach"] is True
    # requests were sent while stop_trace slept: a loop that waited for it
    # sends its clients' next requests ~11 s late
    assert out["metrics"]["loadgen_late_mean_ms.serve"]["value"] < 50.0
    notes = out["notes"]
    assert notes["stop_trace_s"] >= seconds + grace + 1.0
    assert notes["profile_events"] == 0          # no device plane on a CPU
    assert 0 < notes["watchdog_left_s"] < 3 * seconds + 240 - notes[
        "stop_trace_s"]
    assert notes["backlog_at_end"] <= 4


def test_an_untraced_line_carries_no_margin_notes(data_root, capsys):
    out = _run("gpt2_toy_closed", data_root, capsys, seconds=1.0)
    assert not {"stop_trace_s", "profile_events", "watchdog_left_s"} & set(
        out["notes"])


def _sending_loops(path):
    """What each outermost ``while`` loop of a driver that calls
    ``send(...)`` calls, anywhere in its body."""
    import ast

    with open(path) as f:
        tree = ast.parse(f.read())

    def calls(node):
        return [c.func for c in ast.walk(node) if isinstance(c, ast.Call)]

    loops = [w for w in ast.walk(tree) if isinstance(w, ast.While) and any(
        getattr(f, "id", None) == "send" for f in calls(w))]
    inner = {id(w) for outer in loops for w in ast.walk(outer)
             if w is not outer}
    return [calls(w) for w in loops if id(w) not in inner]


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(runner.ROOT, "drivers"))
    if f.startswith("serve") and f.endswith(".py")))
def test_no_serve_driver_stops_the_profile_inside_its_sending_loop(name):
    """A driver copied from an old one cannot bring the disease back:
    inside the loop that sends requests nothing calls ``trace.stop`` or
    ``stop_trace``; the loop hands the stop to ``stop_off_thread``."""
    loops = _sending_loops(os.path.join(runner.ROOT, "drivers", name))
    if name in ("serve_engine.py", "serve_lm.py"):
        assert len(loops) == 1
    for funcs in loops:
        attrs = [f.attr for f in funcs if hasattr(f, "attr")]
        assert "stop" not in attrs and "stop_trace" not in attrs, name
        assert "stop_off_thread" in attrs, name
