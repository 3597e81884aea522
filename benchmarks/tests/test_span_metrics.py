"""The span metrics of the feed pipeline, the trainer loop and the engine
step (``layer_metrics/feed_*``, ``trainer_loop_self_ms.train``,
``*_span_ms.serve``, ``engine_loop_self_ms.serve``, ``scheduler_ms.serve``,
``decode_kv_tokens_per_step.serve``): the new reducer on hand-made spans,
toy cells whose traced CPU runs carry every host-sourced one, readers
that return nothing over a program without the spans, and that every
metric a cell names has its files."""

import json
import os

import pytest

from benchmarks.harness import runner

REPO = os.path.dirname(runner.ROOT)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, data_root, capsys, trace=False, seconds=1.5, seed=2**31 + 77):
    out = runner.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                          roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out and KEYS <= set(out)
    assert out["device"]["platform"] == "cpu" and out["correct"]
    return out


def _host_metrics(cell_name, data_root):
    cell = runner.load_json("workloads", cell_name, [data_root])
    return {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [data_root, runner.ROOT])["source"]
        != "device_trace"}


def test_feed_and_trainer_loop_span_metrics_in_a_traced_train_run(
        data_root, capsys):
    """The spans inside the feed pipeline and the trainer loop's self
    time, read by the cell's metrics from a traced run: read + convert +
    place + stage are one batch's production on the worker thread."""
    out = _run("resnet_toy_spans", data_root, capsys, trace=True)
    host = _host_metrics("resnet_toy_spans", data_root)
    assert host == set(out["metrics"]) and len(host) == 7
    v = {k: m["value"] for k, m in out["metrics"].items()}
    # 4 rows of 32 x 32 x 3 float32 and 4 int32 labels
    assert v["feed_bytes_per_batch.train"] == 4 * 32 * 32 * 3 * 4 + 4 * 4
    assert all(v[k] >= 0.0 for k in v)
    assert v["feed_convert_ms.train"] > 0.0 and v["feed_read_ms.train"] > 0.0


def test_engine_step_span_metrics_in_a_traced_serve_run(data_root, capsys):
    """The engine step's spans, read by the cell's metrics: exact
    medians of the two batch spans, the loop's self time, the
    scheduler's share of a step, the KV tokens a decode step reads."""
    out = _run("gpt2_toy_spans", data_root, capsys, seconds=1.0, trace=True)
    host = _host_metrics("gpt2_toy_spans", data_root)
    assert host == set(out["metrics"]) and len(host) == 6
    v = {k: m["value"] for k, m in out["metrics"].items()}
    assert v["decode_step_span_ms.serve"] > 0.0
    assert v["prefill_pass_span_ms.serve"] > 0.0
    assert v["engine_loop_self_ms.serve"] > 0.0 and v["scheduler_ms.serve"] > 0.0
    # prompts of 4-64 tokens and answers of 2-32 on up to 4 slots
    slots = v["decode_occupancy_pct.serve"] / 100.0 * 4
    assert 4 * slots <= v["decode_kv_tokens_per_step.serve"] <= 96 * slots


def test_a_program_without_the_spans_reports_nothing_and_does_not_raise():
    """Laid over a program that has no such span (the parent of the PR
    that brought them), every new reader returns None."""
    old = [{"name": "step", "thread": "MainThread", "t0": 0.0, "t1": 1.0,
            "id": 1, "parent": None, "args": {}},
           {"name": "serve_decode", "thread": "serving-engine", "t0": 0.0,
            "t1": 0.3, "id": 2, "parent": None, "args": {"batch": 4}}]
    layer = {"spans": old, "records": [], "samples": {}, "sizes": {}}
    for name in ("feed_read_ms.train", "feed_convert_ms.train",
                 "feed_place_ms.train", "feed_stage_wait_ms.train",
                 "feed_bytes_per_batch.train", "prefill_pass_span_ms.serve",
                 "engine_loop_self_ms.serve", "scheduler_ms.serve",
                 "decode_kv_tokens_per_step.serve"):
        spec = runner.load_json("layer_metrics", name, [runner.ROOT])
        reducer = runner.load_py("reducers", spec["reducer"], [runner.ROOT])
        assert reducer.reduce(spec, layer, None) is None, name


def test_span_total_per_on_hand_made_spans():
    """``serve_schedule`` runs twice, once or not at all in a step; the
    metric is its total per step, not its mean duration."""
    def span(name, t0, t1, i, parent=None):
        return {"name": name, "thread": "serving-engine", "t0": t0, "t1": t1,
                "id": i, "parent": parent, "args": {}}

    spans = [span("serve_step", 0.0, 1.0, 1),
             span("serve_schedule", 0.0, 0.010, 2, 1),
             span("serve_schedule", 0.5, 0.520, 3, 1),
             span("serve_step", 1.0, 2.0, 4),
             span("serve_schedule", 1.0, 1.006, 5, 4),
             span("serve_step", 2.0, 3.0, 6),
             span("serve_decode", 2.0, 2.9, 7, 6)]
    spec = runner.load_json("layer_metrics", "scheduler_ms.serve",
                            [runner.ROOT])
    reducer = runner.load_py("reducers", spec["reducer"], [runner.ROOT])
    assert reducer.reduce(spec, {"spans": spans}, None) == pytest.approx(
        (10.0 + 20.0 + 6.0) / 3)
    assert reducer.reduce(spec, {"spans": spans[-1:]}, None) is None
    assert reducer.reduce(spec, {}, None) is None
    # the engine loop's self time on the same spans: a step minus what
    # its children cover
    spec = runner.load_json("layer_metrics", "engine_loop_self_ms.serve",
                            [runner.ROOT])
    reducer = runner.load_py("reducers", spec["reducer"], [runner.ROOT])
    assert reducer.reduce(spec, {"spans": spans}, None) == pytest.approx(
        (970.0 + 994.0 + 100.0) / 3)


def test_every_metric_a_cell_names_has_its_files(data_root):
    """Every name in a cell's ``per_layer`` (the benchmark's cells and
    the toy ones) has its ``layer_metrics/`` file and its reducer, and
    one a cell of ``BENCHMARK.json`` names has its entry there; every
    ``layer_metrics/`` file of the benchmark names a reducer that
    exists and the end-to-end metric of some cell."""
    import glob

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for root, listed in ((runner.ROOT, True), (data_root, False)):
        for path in glob.glob(os.path.join(root, "workloads", "*.json")):
            with open(path) as f:
                cell = json.load(f)
            for m in cell["per_layer"]:
                spec = runner.load_json("layer_metrics", m,
                                        [root, runner.ROOT])
                assert spec["name"] == m
                runner.find("reducers", spec["reducer"], ".py",
                            [root, runner.ROOT])
                if listed:
                    assert m in entries, (cell["name"], m)
    for path in glob.glob(os.path.join(runner.ROOT, "layer_metrics",
                                       "*.json")):
        with open(path) as f:
            spec = json.load(f)
        assert os.path.basename(path) == spec["name"] + ".json"
        assert spec["moves"] in e2e and spec["better"] in ("lower", "higher")
        runner.find("reducers", spec["reducer"], ".py", [runner.ROOT])
