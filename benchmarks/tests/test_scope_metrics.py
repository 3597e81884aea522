"""The metrics that split a device program by sublayer
(``reducers/trace_scope_ms.py``: ``decode_*_ms.serve``,
``prefill_*_ms.serve``, ``step_*_ms.train``, the three ``*_unscoped_pct``)
and ``held_programs_off_kernel.serve`` (``reducers/span_routes_off.py``):
the reducers on hand-made spans and profiles, two toy cells whose traced
CPU runs carry the spans -- a CPU profile holds no device line, so the
time of every operation is laid over the program's OWN ``op_scopes`` by
hand -- and a program without the instrument, which reports nothing."""

import json

import pytest

from benchmarks.harness import runner, trace

SERVE = ("decode_attn_ms.serve", "decode_matmul_ms.serve",
         "decode_state_ms.serve", "decode_head_sample_ms.serve",
         "decode_unscoped_pct.serve", "prefill_attn_ms.serve",
         "prefill_matmul_ms.serve", "prefill_state_ms.serve",
         "prefill_unscoped_pct.serve")
TRAIN = ("step_conv_ms.train", "step_bn_ms.train",
         "step_other_layers_ms.train", "step_update_ms.train",
         "step_unscoped_pct.train")
OFF = "held_programs_off_kernel.serve"
FIFTEEN = SERVE + TRAIN + (OFF,)
OP_NS = 1000     # every operation of the laid-over profile takes 1 us


class _Log:
    def __init__(self):
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def _reduce(name, layer, run=None):
    spec = runner.load_json("layer_metrics", name, [runner.ROOT])
    reducer = runner.load_py("reducers", spec["reducer"], [runner.ROOT])
    return reducer.reduce(spec, layer, run)


def _span(program, scopes=None, routes=None, name="program_ready", **more):
    args = {"program": program, **more}
    if scopes is not None:
        args["op_scopes"] = scopes
    if routes is not None:
        args["routes"] = routes
    return {"name": name, "thread": "MainThread", "t0": 0.0, "t1": 1.0,
            "id": id(args), "parent": None, "args": args}


def _profile(calls, devices=1):
    """``calls``: [(module event name, [operation names])] -> a profile
    in which the calls follow each other and every operation takes
    ``OP_NS``, a ``%while`` container around each call's operations."""
    mods, ops, t = [], [], 0
    for module, names in calls:
        start = t
        for n in names:
            ops.append([f"%{n} = f32[8]{{0}} fusion(%p), kind=kLoop", t,
                        OP_NS])
            t += OP_NS
        ops.append([f"%while.{len(mods)} = (s32[]) while(%t), body=%b",
                    start, t - start])
        mods.append([module, start, t - start])
        t += 500
    lines = {trace.MODULES: mods, trace.OPS: ops}
    return {"devices": {str(d): lines for d in range(devices)}, "host": []}


# -- the reducers on hand-made spans ------------------------------------------------


def test_trace_scope_ms_sums_a_program_by_part():
    scopes = {"attn.core": ["fusion.1", "custom-call.2"],
              "kv.write": ["fusion.3"], "attn.qkv": ["fusion.4"],
              "mamba2.proj": ["fusion.5", "fusion.6"],
              "mamba2.scan": ["custom-call.7"], "norm": ["fusion.8"],
              "head": ["fusion.9"], "sample": ["fusion.10"],
              "unscoped": ["copy.11"]}
    names = [n for v in scopes.values() for n in v] + ["copy.99"]
    layer = {"spans": [_span("decode", scopes)],
             "profile": _profile([("jit_decode(7)", names)] * 4
                                 + [("jit_other(1)", ["fusion.1"] * 50)],
                                 devices=2)}
    log = _Log()
    us = 1e-3       # ms an operation
    assert _reduce("decode_attn_ms.serve", layer, log) == pytest.approx(3 * us)
    assert _reduce("decode_matmul_ms.serve", layer) == pytest.approx(3 * us)
    assert _reduce("decode_state_ms.serve", layer) == pytest.approx(1 * us)
    assert _reduce("decode_head_sample_ms.serve", layer) == pytest.approx(
        2 * us)
    # copy.11, and copy.99 that no list holds, of twelve
    assert _reduce("decode_unscoped_pct.serve", layer) == pytest.approx(
        100 * 2 / 12)
    # the table is logged once, whole: norm is in no metric file
    (line,) = [m for m in log.lines if "by part" in m]
    assert "norm 0.0010" in line and "4 calls" in line
    # a cell without state layers reports no state metric
    del scopes["mamba2.scan"]
    layer = {"spans": [_span("decode", scopes)], "profile": layer["profile"]}
    assert _reduce("decode_state_ms.serve", layer) == 0.0
    # nothing to read: no span, a span without op_scopes, no profile
    assert _reduce("decode_attn_ms.serve", {"spans": []}) is None
    assert _reduce("decode_attn_ms.serve", {
        "spans": [_span("decode")], "profile": layer["profile"]}) is None
    assert _reduce("decode_attn_ms.serve", {
        "spans": [_span("decode", scopes)]}) is None
    assert _reduce("prefill_attn_ms.serve", layer) is None


def test_trace_scope_ms_tells_the_prefill_members_apart():
    """Two executables under one module name: by what their calls hold;
    members whose names coincide and agree are as good as one; members
    that disagree give None rather than a mixed number."""
    one = {"attn.core": ["fusion.1"], "ffn": ["fusion.2"],
           "unscoped": ["copy.3"]}
    four = {"attn.core": ["fusion.1"], "ffn": ["fusion.2", "fusion.4"],
            "kv.write": ["copy.3"]}
    calls = [("jit_prefill(11)", ["fusion.1", "fusion.2", "copy.3"]),
             ("jit_prefill(22)", ["fusion.1", "fusion.2", "fusion.4",
                                  "copy.3"])]
    layer = {"spans": [_span("prefill", one, rows=1),
                       _span("prefill", four, rows=4)],
             "profile": _profile(calls)}
    # the first call fits both members, and they disagree on copy.3
    assert _reduce("prefill_attn_ms.serve", layer) is None
    four["unscoped"] = four.pop("kv.write")
    layer = {"spans": layer["spans"], "profile": layer["profile"]}
    assert _reduce("prefill_attn_ms.serve", layer) == pytest.approx(1e-3)
    assert _reduce("prefill_matmul_ms.serve", layer) == pytest.approx(1.5e-3)
    assert _reduce("prefill_unscoped_pct.serve", layer) == pytest.approx(
        100 * 2 / 7)


def test_trace_scope_ms_counts_the_backward_pass_with_its_part():
    scopes = {"conv": ["fusion.1"], "conv|bwd": ["fusion.2", "fusion.3"],
              "batch_norm": ["fusion.4"], "batch_norm|bwd": ["fusion.5"],
              "pool": ["fusion.6"], "loss|bwd": ["fusion.7"],
              "update": ["fusion.8"], "comm": ["all-reduce.9"],
              "unscoped": ["copy.10"]}
    names = [n for v in scopes.values() for n in v]
    layer = {"spans": [_span("step", scopes)],
             "profile": _profile([("jit_step(3)", names)] * 2)}
    log = _Log()
    assert _reduce("step_conv_ms.train", layer, log) == pytest.approx(3e-3)
    assert _reduce("step_bn_ms.train", layer) == pytest.approx(2e-3)
    assert _reduce("step_other_layers_ms.train", layer) == pytest.approx(2e-3)
    assert _reduce("step_update_ms.train", layer) == pytest.approx(1e-3)
    assert _reduce("step_unscoped_pct.train", layer) == pytest.approx(10.0)
    (line,) = [m for m in log.lines if "by part" in m]
    assert "conv|bwd 0.0020" in line and "comm 0.0010" in line


def test_span_routes_off_counts_what_left_the_kernel():
    spans = [_span("prefill", routes={"flash_attention:kernel": 4,
                                      "mamba1_prefill:xla": 9,
                                      "moe_experts:masked": 3}),
             _span("decode", routes={"ragged_paged_attention:kernel": 5,
                                     "ssd_step:reference_shape": 9,
                                     "grouped_matmul:reference": 2}),
             _span("decode", name="serve_decode")]
    log = _Log()
    assert _reduce(OFF, {"spans": spans}, log) == 3
    assert "ssd_step:reference_shape" in log.lines[0]
    assert _reduce(OFF, {"spans": spans[:1]}) == 1
    assert _reduce(OFF, {"spans": [_span("decode", routes={})]}) == 0
    assert _reduce(OFF, {"spans": spans[2:]}) is None
    assert _reduce(OFF, {}) is None


def test_a_program_without_the_instrument_reports_nothing():
    """Laid over the parent, whose ``program_ready`` spans say neither
    ``op_scopes`` nor ``routes``, every one of the fifteen returns None
    and none raises."""
    old = [_span("prefill", rows=1, length=64), _span("decode", rows=4),
           _span("decode", name="serve_decode")]
    prof = _profile([("jit_decode(1)", ["fusion.1"]),
                     ("jit_prefill(2)", ["fusion.1"]),
                     ("jit_step(3)", ["fusion.1"])])
    for name in FIFTEEN:
        assert _reduce(name, {"spans": old, "profile": prof}) is None, name
        assert _reduce(name, {"spans": []}) is None, name


# -- toy cells: the spans of a real run, the profile laid over them ----------------------


def _traced(cell, data_root, capsys, seconds):
    """A traced CPU run of the toy cell: (its metrics, the tracer's
    ``program_ready`` spans -- set-up spans outlive the run)."""
    from paddle_tpu.telemetry import tracing

    tracing.get_tracer().drain()
    out = runner.run_cell(cell, seed=2**31 + 52, seconds=seconds, trace=True,
                          roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out and out["correct"]
    spans = [s for s in trace.span_dicts(tracing.get_tracer().spans)
             if s["name"] == "program_ready"]
    tracing.configure_tracing(enabled=False)
    tracing.get_tracer().drain()
    return {k: m["value"] for k, m in out["metrics"].items()}, spans


def _laid_over(spans, module):
    """A profile that calls every described program twice, each of its
    listed operations taking ``OP_NS``."""
    calls = []
    for i, s in enumerate(spans):
        names = [n for v in s["args"]["op_scopes"].values() for n in v]
        calls += [(f"{module[s['args']['program']]}({i})", names)] * 2
    return _profile(calls)


def test_a_serving_cell_reads_its_programs_by_sublayer(data_root, capsys):
    got, spans = _traced("granite_toy_scopes", data_root, capsys, 1.0)
    # no device line in a CPU profile: the span-sourced metric alone
    assert set(got) == {OFF}
    # the toy's decode kernel and recurrence run as references here
    assert got[OFF] >= 2
    assert [s["args"]["program"] for s in spans] == ["prefill", "prefill",
                                                     "decode"]
    for s in spans:
        assert len(s["args"]["op_scopes"]) > 8
    # the census of each program's own trace (the toy's prefill takes no
    # entry that says its route)
    assert [sorted(s["args"]["routes"]) for s in spans] == [
        [], [], ["ragged_paged_attention:reference", "ssd_step:reference"]]
    layer = {"spans": spans, "profile": _laid_over(
        spans, {"prefill": "jit_prefill", "decode": "jit_decode"})}
    log = _Log()
    v = {name: _reduce(name, layer, log) for name in SERVE}
    assert all(x is not None and x > 0.0 for x in v.values()), v
    for program in ("decode", "prefill"):
        listed = [s["args"]["op_scopes"] for s in spans
                  if s["args"]["program"] == program]
        total = sum(len(n) for sc in listed for n in sc.values()) \
            / len(listed) * OP_NS / 1e6
        parts = sum(v[f"{program}_{k}_ms.serve"]
                    for k in ("attn", "matmul", "state"))
        parts += v["decode_head_sample_ms.serve"] * (program == "decode")
        assert parts < total
        # (a CPU program is half layout copies of the compiler's own)
        assert 0.0 < v[f"{program}_unscoped_pct.serve"] < 100.0
        # with norm, stack and the rest of the logged table: all
        (line,) = [m for m in log.lines if f"jit_{program} by part" in m]
        table = dict(p.rsplit(" ", 1) for p in line.split("): ")[1].split(", "))
        assert sum(map(float, table.values())) == pytest.approx(total,
                                                                rel=1e-2)
        assert {"norm", "stack", "mamba2.scan"} <= set(table)


def test_a_train_cell_reads_its_step_by_layer_type(data_root, capsys):
    got, spans = _traced("resnet_toy_scopes", data_root, capsys, 1.5)
    assert got == {}        # every metric of the cell is the device's
    (span,) = spans
    assert span["args"]["program"] == "step"
    assert span["args"]["routes"] == {}
    layer = {"spans": spans, "profile": _laid_over(spans,
                                                   {"step": "jit_step"})}
    log = _Log()
    v = {name: _reduce(name, layer, log) for name in TRAIN}
    assert all(x is not None and x > 0.0 for x in v.values()), v
    total = sum(len(n) for n in span["args"]["op_scopes"].values()) \
        * OP_NS / 1e6
    assert sum(v[k] for k in TRAIN[:4]) + total * v[TRAIN[4]] / 100 \
        == pytest.approx(total, rel=1e-6)
    (line,) = [m for m in log.lines if "by part" in m]
    assert "conv|bwd" in line and "batch_norm|bwd" in line


def test_the_fifteen_files(data_root):
    """Each names a reducer this PR brings, a layer ``BENCHMARK.json``
    spells and an end-to-end metric of its kind; the two toy cells name
    them all between them."""
    with open(runner.find("", "BENCHMARK", ".json",
                          [runner.ROOT + "/.."])) as f:
        bench = json.load(f)
    layers = {m["layer"] for m in bench["per_layer"]}
    named = set()
    for cell in ("granite_toy_scopes", "resnet_toy_scopes"):
        named |= set(runner.load_json("workloads", cell,
                                      [data_root])["per_layer"])
    assert set(FIFTEEN) <= named
    for name in FIFTEEN:
        spec = runner.load_json("layer_metrics", name, [runner.ROOT])
        assert spec["layer"] in layers and spec["_note"]
        assert spec["moves"] == ("train_examples_per_s"
                                 if name.endswith(".train")
                                 else "serve_tok_per_s")
        assert (spec["reducer"], spec["source"]) == (
            ("span_routes_off", "program_span") if name == OFF
            else ("trace_scope_ms", "device_trace"))
        # no entry for a metric no cell of the benchmark reports
        assert name not in {m["name"] for m in bench["per_layer"]}
