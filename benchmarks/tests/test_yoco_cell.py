"""The Phi-4-mini-flash-reasoning configuration (SambaY: 9 Mamba-1 and 8
window-attention layers, ONE full-attention layer whose cache 7
cross-attention layers read, 7 gated memory units, differential attention,
a SwiGLU MLP behind each; the whole model on one chip as a 64-entry layer
pattern) and its cell: ``drivers/serve_lm.py`` end to end at a toy size on
the CPU, traced and untraced, the control, every metric the cell adds read
from recorded spans and a recorded device trace, the two floors against a
hand-worked step, a program without the new span arguments reporting
nothing, ``BENCHMARK.json``'s additions found BY NAME, and the
configuration file held to the catalog's row key for key and to the
program's own tree."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import runner

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
STREAM = "yoco_decode_stream_roofline_pct.serve"
ATTN = "paged_attn_yoco_roofline_pct.serve"
SHARED_KV = "shared_kv_mb_per_step.serve"
RING = "window_kv_tokens_per_step.serve"
CROSS = "prefill_cross_positions.serve"
SLOTS = "state_slots_per_step.serve_yoco"
HOST = ("prefill_pass_ms.serve_yoco", "itl_p95_ms.serve_yoco",
        "ttft_p50_ms.serve_yoco")
SHARED = ("loadgen_late_mean_ms.serve", "queue_wait_p95_ms.serve",
          "decode_occupancy_pct.serve", "decode_step_host_ms.serve",
          "decode_device_ms.serve", "device_idle_pct.serve")
NEW = (STREAM, ATTN, SHARED_KV, RING, CROSS, SLOTS) + HOST
CELL, CONFIG = "phi4mf_serve_closed64_reason", "phi-4-mini-flash-reasoning"
SLOT = 9 * 4 * (16 * 5120 + 3 * 5120)       # state bytes a slot
ROW = 2 * 20 * 64 * 2                       # K and V bytes a token a layer
PATTERN = "S-W-" * 8 + "S-*-" + "G-X-" * 7


def _run(config, roots):
    return runner.Run(workload="test", cell={}, config=config, seed=0,
                      seconds=1.0, trace=True, roots=roots, on_chip=False,
                      proc_t0=0.0, chips=1, peak=PEAK)


def _reduce(metric, layer, config, roots):
    spec = runner.load_json("layer_metrics", metric, roots)
    return runner.load_py("reducers", spec["reducer"], roots).reduce(
        spec, layer, _run(config, roots))


def test_serve_lm_end_to_end(data_root, capsys):
    """Untraced: the cell's two end-to-end metrics and a ``correct``
    line (a seed beyond 32 signed bits).  Traced: every per-layer metric
    of the cell that is read off the host, the shared reads and the
    narrowing among them."""
    out = runner.run_cell("phi4flash_toy_closed", seed=2**31 + 99,
                          seconds=2.0, trace=False, roots=[data_root],
                          on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 10
    assert out["notes"]["distinct_tokens"] >= 8
    assert {k.split(":")[0] for k in out["notes"]["routes"]} == {
        "mamba1_prefill", "mamba1_step", "ragged_paged_attention"}
    out = runner.run_cell("phi4flash_toy_closed", seed=6, seconds=2.0,
                          trace=True, roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "phi4flash_toy_closed", [data_root])
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert cell["per_layer"] == real["per_layer"] == list(SHARED + NEW)
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 11
    m = out["metrics"]
    assert 0 < m[SLOTS]["value"] <= 4
    # a pass narrows: the positions behind the full-attention layer are
    # its rows (1 or 2 of them), not its 64 padded tokens
    assert 1 <= m[CROSS]["value"] <= 2
    assert 0 < m[RING]["value"] <= 4 * 16
    assert m[SHARED_KV]["value"] > 0
    assert all(c["ok"] for c in out["checks"])


def test_the_control_reads_the_int8_gaps(data_root):
    import importlib

    control = importlib.import_module("benchmarks.control")
    out = control.control("phi4flash_toy_closed", seed=7, seconds=1.5,
                          roots=[data_root], on_chip=False)
    got = out["control"]
    assert out["precision"] == "int8" and got["tokens"] >= 16
    assert got["served_mean_gap"] > got["program_served_mean_gap"] >= 0
    assert got["served_logit_gap"] >= 0


def _recorded(steps, args=None):
    """Spans and a device trace as a run records them: ``steps`` of
    (t0 s, t1 s, context tokens, live slots, ring rows) decode steps on
    the host clock, their programs and kernels (sixteen paged-attention
    calls a step, and one other Mosaic call the pattern must NOT count)
    on a profile clock 5 s ahead, a profile window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    for i, (t0, t1, ctx, slots, ring) in enumerate(steps):
        a = {"batch": slots, "context_tokens": ctx, "loop_steps": 1,
             "cache_layers": 1, "kv_heads": 20, "state_layers": 9,
             "state_slots": slots, "state_bytes": 2 * slots * SLOT,
             "kv_reads": 8, "window_layers": 8, "window_tokens": ring,
             "shared_kv_bytes": 8 * ctx * ROW}
        spans.append({"name": "serve_decode", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": i + 1, "parent": None,
                      "args": a if args is None else
                      {k: v for k, v in a.items() if k in args}})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_decode(1)", s, e - s])
        for c in range(16):
            ops.append([f"%paged_attention_decode.{c} = custom-call(...), "
                        'custom_call_target="tpu_custom_call"',
                        s + c * (e - s) // 32, (e - s) // 64])
        ops.append(['%some_other_kernel = custom-call(...), '
                    'custom_call_target="tpu_custom_call"',
                    s + (e - s) // 2, (e - s) // 8])
    return {"spans": spans, "sizes": {"max_slots": 64},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


STEPS = [(0.5, 1.5, 131000, 64, 32768), (1.5, 2.5, 131100, 63, 32256),
         (2.5, 3.5, 131200, 60, 30720), (3.5, 4.5, 131300, 64, 32700)]
SHARES = [0.5, 1.0, 1.0, 0.5]    # of each step inside the window


def test_the_floors_of_a_hand_worked_step():
    """64 live slots at 2,050 tokens each (131,200): 7.71 GB of weights
    once, 2 x 3.5 MB x 64 of state, 5,120 B x (8 x 131,200 layer-reads +
    8 x 32,768 ring rows) = 14.87 GB, 18.2 ms at 819 GB/s -- the issue's
    14.9 GB and 18 ms; the kernel's own share is the last term."""
    roots = [runner.ROOT]
    sizes = runner.load_json("configs", CONFIG, roots)[
        "yoco_decode_stream_bytes"]
    fn = runner.load_py("kernels", "yoco_decode_stream", roots)
    a = {"state_slots": 64, "context_tokens": 131200, "kv_reads": 8,
         "window_layers": 8, "window_tokens": 32768}
    assert fn.kv_tokens(a) == 8 * 131200 + 8 * 32768 == 1_311_744
    got = fn.step_bytes(sizes, a)
    assert got == 7_705_125_888 + 2 * 64 * 3_502_080 + 1_311_744 * 5120 \
        == 14_869_521_408
    assert 18.1 < 1e3 * got / 819e9 < 18.2
    assert 0.36 < 8 * 131200 * 5120 / got < 0.37     # the shared reads
    assert fn.NEEDS == ("state_slots", "kv_reads", "context_tokens",
                        "window_layers", "window_tokens")


def test_new_metrics_from_recorded_spans_and_trace():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    sizes = cfg["yoco_decode_stream_bytes"]
    layer = _recorded(STEPS)
    rows = lambda s: 8 * s[2] + 8 * s[4]
    want = sum(sh * (sizes["layer_weights_and_head"]
                     + 2 * s[3] * sizes["state_per_slot"]
                     + rows(s) * sizes["kv_per_token"])
               for sh, s in zip(SHARES, STEPS))
    assert _reduce(STREAM, layer, cfg, roots) == pytest.approx(
        100 * want / 819e9 / 3.0, rel=1e-9)
    # the attention kernel alone: the 16 calls the pattern names (of the
    # 17 Mosaic calls a step, a sixty-fourth of the step each) against the
    # K/V rows they must read at 20 heads of 64 in bf16
    kv = sum(sh * rows(s) for sh, s in zip(SHARES, STEPS)) * ROW
    assert ROW == sizes["kv_per_token"] == 5120
    assert _reduce(ATTN, layer, cfg, roots) == pytest.approx(
        100 * kv / 819e9 / (3.0 * 16 / 64), rel=1e-6)
    mean = lambda i: sum(s[i] for s in STEPS) / 4
    assert _reduce(SLOTS, layer, cfg, roots) == pytest.approx(mean(3))
    assert _reduce(RING, layer, cfg, roots) == pytest.approx(mean(4))
    assert _reduce(SHARED_KV, layer, cfg, roots) == pytest.approx(
        8 * mean(2) * 5120 / 1e6)
    assert SLOT == sizes["state_per_slot"]
    # the narrowing, from a prefill pass's span
    pre = {"spans": [{"name": "serve_prefill", "t0": 0.0, "t1": 0.1,
                      "args": {"cross_positions": n, "prompt_tokens": 1800}}
                     for n in (1, 1, 2)]}
    assert _reduce(CROSS, pre, cfg, roots) == pytest.approx(4 / 3)


def test_the_floors_count_the_reads_not_the_cache_layers():
    """Eight layers read ONE cache layer: a floor that counted
    ``cache_layers`` x ``context_tokens`` would read an eighth."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    one = _reduce(ATTN, _recorded([(1.5, 2.5, 100000, 64, 0)]), cfg, roots)
    assert one == pytest.approx(
        100 * 8 * 100000 * ROW / 819e9 / (16 / 64), rel=1e-6)
    ring = _reduce(ATTN, _recorded([(1.5, 2.5, 100000, 64, 32768)]), cfg,
                   roots)
    assert ring - one == pytest.approx(
        100 * 8 * 32768 * ROW / 819e9 / (16 / 64), rel=1e-6)
    full = _reduce(STREAM, _recorded([(1.5, 2.5, 8000, 64, 0)]), cfg, roots)
    part = _reduce(STREAM, _recorded([(1.5, 2.5, 8000, 16, 0)]), cfg, roots)
    assert full - part == pytest.approx(
        100 * 2 * 48 * SLOT / 819e9 / 1.0, rel=1e-9)


def test_a_program_without_the_new_span_args_reports_nothing():
    """Over a program whose spans carry none of the arguments the readers
    need (the parent of this PR) every new reader returns None and none
    raises; the same without a trace, and over nothing."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    old = _recorded(STEPS, args=("batch", "context_tokens", "loop_steps",
                                 "cache_layers", "kv_heads", "state_layers"))
    no_trace = {"spans": _recorded(STEPS)["spans"]}
    for name in (STREAM, ATTN, SHARED_KV, RING, CROSS, SLOTS):
        assert _reduce(name, old, cfg, roots) is None, name
        assert _reduce(name, {}, cfg, roots) is None, name
    for name in (STREAM, ATTN):
        assert _reduce(name, no_trace, cfg, roots) is None, name
    # a configuration without the byte counts: nothing, not a KeyError
    bare = {k: v for k, v in cfg.items() if k != "yoco_decode_stream_bytes"}
    assert _reduce(STREAM, _recorded(STEPS), bare, roots) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    """What this PR appended, found by name: it sits behind what PR 46
    appended, and a later PR may append behind it."""
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    at = lambda entries, name: next(
        i for i, e in enumerate(entries) if e["name"] == name)
    ci = at(bench["configs"], CONFIG)
    conf = bench["configs"][ci]
    assert ci > at(bench["configs"], "granite-4.0-h-micro")
    assert conf["reduced"] == []
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    wi = at(bench["workloads"], CELL)
    cell = bench["workloads"][wi]
    assert wi > at(bench["workloads"], "granite4hm_serve_closed64_chat")
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG, 1, "closed64_reason2k")
    assert sum(1 for c in bench["workloads"] if c["config"] == CONFIG) == 1
    assert len(bench["workloads"]) >= 10
    assert sum(1 for c in bench["workloads"] if c["chips"] == 4) == 1
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert (real["traffic_name"], real["why"]) == (cell["traffic"],
                                                   cell["why"])
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    assert conf["source"] == runner.load_json(
        "configs", CONFIG, [runner.ROOT])["source_url"]
    rate = next(e for e in bench["end_to_end"]
                if e["name"] == "serve_tok_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "granite4hm_serve_closed64_chat")
    assert CELL not in next(e for e in bench["end_to_end"]
                            if e["name"] == "serve_itl_p95_ms")["workloads"]
    first = at(bench["per_layer"], NEW[0])
    assert first > at(bench["per_layer"], "ttft_p50_ms.serve_ssm")
    tail = bench["per_layer"][first:first + len(NEW)]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        spec = runner.load_json("layer_metrics", m["name"], [runner.ROOT])
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_per_s"
        assert {k: spec[k] for k in ("unit", "better", "source", "layer")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer")}
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    layers = {m["layer"] for m in bench["per_layer"][:first]}
    assert {m["layer"] for m in tail} <= layers
    # the six every serve cell shares carry no list: reported here too
    for name in SHARED:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert "workloads" not in entry and name in real["per_layer"]
    assert real["end_to_end"] == ["serve_tok_per_s", "setup_s"]
    t = real["traffic"]
    assert (t["loop"], t["clients"], t["pool"], t["lead_in_s"]) == (
        "closed", 64, 64, 12.0)
    assert t["prompt_len"] == {"median": 1536, "sigma": 0.5, "min": 512,
                               "max": 4096}
    assert t["output_len"] == {"median": 512, "sigma": 0.5, "min": 128,
                               "max": 1024}
    assert (t["profile_after_s"], t["profile_s"]) == (1.0, 2.0)
    lim = real["limits"]
    assert 0 < lim["served_mean_gap"] < lim["served_logit_gap"] < 9
    assert len(json.dumps(bench)) < 64 * 1024


def test_config_file_against_the_catalog_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.analysis.memory import serving_memory_report
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.scheduler import ServingConfig, prefill_shapes

    cfg = runner.load_json("configs", CONFIG, [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == cfg["source_url"])
    # every key of the source, verbatim; nothing reduced
    assert cfg["published"] == sorted(row["config"]) and cfg["reduced"] == []
    for k, v in row["config"].items():
        assert cfg[k] == v and type(cfg[k]) is type(v), k
    m = cfg["model"]
    layer = lambda i: ("S-" if i % 2 == 0 else "W-") if i <= 16 else (
        "*-" if i == 17 else ("G-" if i % 2 == 0 else "X-"))
    assert m["pattern"] == PATTERN == "".join(
        layer(i) for i in range(cfg["num_hidden_layers"]))
    assert cfg["mb_per_layer"] == 2     # a Mamba layer every second layer
    assert m["num_layers"] == len(PATTERN) == 2 * cfg["num_hidden_layers"]
    assert (m["embed_dim"], m["mlp_dim"], m["num_heads"], m["kv_heads"],
            m["head_dim"], m["vocab_size"], m["max_seq_len"], m["norm_eps"],
            m["tie_embeddings"], m["attn_window"]) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"], cfg["vocab_size"],
        cfg["max_position_embeddings"], cfg["layer_norm_eps"],
        cfg["tie_word_embeddings"], cfg["sliding_window"])
    assert (m["mamba1_inner"], m["mamba1_dt_rank"], m["mamba1_state"],
            m["mamba1_conv"]) == (2 * cfg["hidden_size"],
                                  cfg["hidden_size"] // 16, 16, 4)
    assert (m["positions"], m["norm"], cfg["hidden_act"], m["mlp"],
            m["attn_diff"], m["attn_bias"]) == ("none", "layer", "silu",
                                                "swiglu", True, True)
    assert cfg["mlp_bias"] is False and cfg["lm_head_bias"] is False
    assert m["init"]["wte_std"] == pytest.approx(m["embed_dim"] ** -0.5)
    for item in ("mamba1", "memory", "positions", "differential_attention",
                 "window", "cross_attention", "state_dtype", "head_dim",
                 "init", "serving"):
        assert cfg["assumed"][item], item
    # the program's tree at these fields: one tree a layer, unrolled
    driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    assert tcfg.pattern_roll == (64, 1) and tcfg.dtype == jnp.bfloat16
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 3_852_562_944
    assert len(tree["blocks"]) == 64 and "head" not in tree
    by = cfg["parameters_by_kind"]
    at = {c: PATTERN.index(c) for c in "SW*GX-"}
    assert [size(tree["blocks"][at[c]]) for c in "SW*GX-"] == [
        by["mamba1_layer"], by["window_or_full_attention_layer"],
        by["window_or_full_attention_layer"],
        by["gated_memory_unit_layer"], by["cross_attention_layer"],
        by["mlp_layer"]] == [41_246_720, 19_673_984, 19_673_984, 26_219_520,
                             13_117_824, 78_648_320]
    assert size(tree["embed"]) == by["embedding_and_tied_head"]
    assert 9 * by["mamba1_layer"] + 9 * by["window_or_full_attention_layer"] \
        + 7 * by["gated_memory_unit_layer"] + 7 * by["cross_attention_layer"] \
        + 32 * by["mlp_layer"] + by["embedding_and_tied_head"] \
        + by["final_norm"] == cfg["parameters"]
    assert (tcfg.cache_layers, tcfg.window_layers, tcfg.kv_reads,
            tcfg.state_layers, tcfg.narrow_at) == (1, 8, 8, 9, 35)
    assert tcfg.cross_reads == (0,) * 7
    assert tcfg.diff_depths == {"window": tuple(range(1, 16, 2)),
                                "attn": (17,),
                                "cross": tuple(range(19, 32, 2))}
    sizes = cfg["yoco_decode_stream_bytes"]
    assert sizes["layer_weights_and_head"] == 2 * size(tree)
    assert sizes["state_per_slot"] == SLOT == 4 * 9 * sum(
        int(np.prod(s)) for s in tcfg.state_shapes.values()) == 3_502_080
    assert sizes["kv_per_token"] == ROW == 5120
    sv = cfg["serving"]
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    assert (sv["max_slots"], sv["prefill_batch"], sv["prefix_cache"],
            sv["prefill_chunk_tokens"]) == (64, 1, False, 0)
    assert prefill_shapes(sv["prefill_batch"], sv["max_prompt_len"]) == (
        (1, 2048), (1, 4096))
    assert (cfg["dtype"], cfg["kv_dtype"], cfg["state_dtype"],
            cfg["control_precision"]) == ("bfloat16", "bfloat16", "float32",
                                          "int8")
    # what the chip holds: weights + pages + rings + state, 68% of 16 GB
    rep = serving_memory_report(tcfg, ServingConfig(**sv))
    assert rep["kv_pool_bytes"] == 20481 * 16 * 5120 == 1_677_803_520
    assert rep["window_pool_bytes"] == 8 * (1 + 64 * 32) * 16 * 5120 \
        == 1_342_832_640
    assert rep["state_pool_bytes"] == 64 * SLOT == 224_133_120
    held = rep["total_bytes"] + 2 * size(tree)
    assert 10.94e9 < held < 10.96e9 and 0.68 < held / 16e9 < 0.69
    # the toy twin's byte counts follow its own tree the same way
    toy = runner.load_json("configs", "phi4flash_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttcfg = driver._program_config(toy, T)
    ttree = jax.eval_shape(lambda: T.init_params(ttcfg, jax.random.key(0)))
    tsz = toy["yoco_decode_stream_bytes"]
    assert tsz["layer_weights_and_head"] == 2 * size(ttree)
    assert tsz["state_per_slot"] == 4 * sum(
        n * int(np.prod(s)) for n, s in ttcfg.state_parts.values())
    assert tsz["kv_per_token"] == 2 * ttcfg.kv_heads * ttcfg.head_dim * 2
    # a program that lacks a field the file names is refused at once: what
    # the parent of this PR does with this configuration's new fields
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)


def test_the_references_tree_is_the_programs(data_root):
    """The reference's weights, one tree a layer, are the program's tree
    under other names: nothing is copied."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    toy = runner.load_json("configs", "phi4flash_toy", [data_root])
    ref = runner.load_py("references", "phi4flash", [runner.ROOT])
    w = ref.init_weights(toy["model"], 2**31 + 5, jnp.float32)
    driver = runner.load_py("drivers", "serve_lm", [runner.ROOT])
    tcfg = driver._program_config(dict(toy, dtype="float32"), T)
    want = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    got = ref.program_tree(w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == jax.tree.map(
        lambda a: a.shape, want)
    assert got["blocks"][0]["in_proj"] is w["layers"][0]["in_proj"]
    assert got["embed"] is w["wte"] and "head" not in got
    assert float(jnp.std(w["wte"])) == pytest.approx(64 ** -0.5, rel=0.05)
    # two seeds draw two models; one seed draws one
    again = ref.init_weights(toy["model"], 2**31 + 5, jnp.float32)
    other = ref.init_weights(toy["model"], 2**31 + 6, jnp.float32)
    assert bool(jnp.all(again["wte"] == w["wte"]))
    assert not bool(jnp.all(other["wte"] == w["wte"]))
    # the reference imports nothing of the program
    with open(ref.__file__) as f:
        assert "paddle_tpu" not in f.read().split('"""', 2)[2]
