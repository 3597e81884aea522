"""The KDA configuration (a gated delta-rule linear-attention layer with a
float32 state a slot a layer beside one gated attention layer in four,
sigmoid top-k SwiGLU experts over a held share with a gated shared
expert) and its cell: ``drivers/serve_lm.py`` end to end at a toy size on
the CPU, traced and untraced, the control, every metric the cell adds
read from recorded spans and a recorded device trace, a program without
the new span arguments reporting nothing, ``BENCHMARK.json``'s additions
found BY NAME (so a later appended cell breaks nothing here), and the
configuration file held to the catalog's row key for key and to the
program's own tree."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import roofline, runner

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
PREFILL = "kda_prefill_roofline_pct.serve"
STREAM = "kda_decode_stream_roofline_pct.serve"
ATTN = "paged_attn_kda_roofline_pct.serve"
STATE = "kda_state_mb_per_step.serve"
COUNTS = ("moe_tokens_per_expert.serve_kda",
          "moe_experts_touched_pct.serve_kda",
          "moe_load_max_over_mean.serve_kda",
          "state_slots_per_step.serve_kda")
HOST = ("prefill_pass_ms.serve_kda", "itl_p95_ms.serve_kda",
        "ttft_p50_ms.serve_kda")
SHARED = ("loadgen_late_mean_ms.serve", "queue_wait_p95_ms.serve",
          "decode_occupancy_pct.serve", "decode_step_host_ms.serve",
          "decode_device_ms.serve", "device_idle_pct.serve")
NEW = (PREFILL, STREAM, ATTN, STATE) + COUNTS + HOST
CELL, CONFIG = "solar2_serve_closed32_doc", "solar-open2-250b"
SLOT = 3 * 4 * (64 * 128 * 128 + 3 * 24576)     # KDA state bytes a slot


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
    of the cell that is read off the host, the routing counts and the
    state's bytes among them."""
    out = runner.run_cell("solar2_toy_closed", seed=2**31 + 99, seconds=2.0,
                          trace=False, roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 10
    assert out["notes"]["distinct_tokens"] >= 1
    out = runner.run_cell("solar2_toy_closed", seed=6, seconds=2.0,
                          trace=True, roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "solar2_toy_closed", [data_root])
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert cell["per_layer"] == real["per_layer"] == list(SHARED + NEW)
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 12
    m = out["metrics"]
    # 4 slots x top-4 x 4 expert sublayers, half of them on held experts,
    # over 32 (layer, held expert) pairs
    assert 0 < m["moe_tokens_per_expert.serve_kda"]["value"] <= 4 * 4 * 4 / 32
    assert 0 < m["moe_experts_touched_pct.serve_kda"]["value"] <= 100
    assert m["moe_load_max_over_mean.serve_kda"]["value"] >= 1.0
    slots = m["state_slots_per_step.serve_kda"]["value"]
    assert 0 < slots <= 4
    toy = runner.load_json("configs", "solar2_toy", [data_root])
    assert m[STATE]["value"] == pytest.approx(
        2 * slots * toy["kda_decode_stream_bytes"]["state_per_slot"] / 1e6)
    assert all(c["ok"] for c in out["checks"])


def test_the_control_reads_the_int8_gaps(data_root):
    import importlib

    control = importlib.import_module("benchmarks.control")
    out = control.control("solar2_toy_closed", seed=7, seconds=1.5,
                          roots=[data_root], on_chip=False)
    got = out["control"]
    assert out["precision"] == "int8" and got["tokens"] >= 16
    assert got["served_mean_gap"] >= 0 and got["served_logit_gap"] >= 0
    assert got["program_served_mean_gap"] is not None


def _recorded(steps, passes=(), args=None):
    """Spans and a device trace as a run records them: ``steps`` of
    (t0 s, t1 s, context tokens, experts touched, live slots, busiest
    expert's tokens) decode steps and ``passes`` of (t0 s, t1 s, rows,
    [prompt lengths], held assignments) prefill passes on the host clock,
    their programs and kernels (one paged-attention call a decode step,
    and one other Mosaic call the pattern must NOT count) on a profile
    clock 5 s ahead, a profile window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    keep = lambda a: a if args is None else {
        k: v for k, v in a.items() if k in args}
    for i, (t0, t1, ctx, touched, slots, busiest) in enumerate(steps):
        a = {"batch": slots, "context_tokens": ctx, "loop_steps": 1,
             "cache_layers": 1, "kv_heads": 8, "state_layers": 3,
             "kda_layers": 3, "experts_touched": touched,
             "state_slots": slots, "state_bytes": 2 * slots * SLOT,
             "moe_assignments": 4 * slots, "moe_load_max": busiest,
             "moe_load_max_over_mean": round(busiest * 160 / (4 * slots), 3)}
        spans.append({"name": "serve_decode", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": i + 1, "parent": None,
                      "args": keep(a)})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_decode(1)", s, e - s])
        ops.append(['%paged_attention_decode.0 = custom-call(...), '
                    'custom_call_target="tpu_custom_call"', s, (e - s) // 8])
        ops.append(['%some_other_kernel = custom-call(...), '
                    'custom_call_target="tpu_custom_call"',
                    s + (e - s) // 2, (e - s) // 8])
    for i, (t0, t1, rows, lens, held) in enumerate(passes):
        a = {"batch": len(lens), "rows": rows, "padded_tokens": rows * 4096,
             "prompt_tokens": sum(lens), "loop_steps": 1, "cache_layers": 1,
             "kv_heads": 8, "state_layers": 3, "kda_layers": 3,
             "kda_chunks": sum(-(-n // 64) for n in lens),
             "attn_pairs": sum(n * (n + 1) // 2 for n in lens),
             "state_bytes": len(lens) * SLOT, "moe_assignments": held,
             "experts_touched": 160, "moe_load_max": 99}
        spans.append({"name": "serve_prefill", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": 100 + i, "parent": None,
                      "args": keep(a)})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_prefill(2)", s, e - s])
    return {"spans": spans, "sizes": {"max_slots": 32},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


STEPS = [(0.5, 1.5, 70000, 88, 32, 4), (1.5, 2.0, 70100, 90, 31, 5),
         (3.0, 3.5, 70200, 85, 30, 3), (4.5, 5.5, 70300, 91, 32, 4)]
SHARES = [0.5, 1.0, 1.0, 0.0]    # of each step inside the window
PASSES = [(2.0, 3.0, 1, [2048], 2100), (3.5, 4.5, 4, [512, 4096, 1000], 5500)]


def test_new_metrics_from_recorded_spans_and_trace():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    sizes = cfg["kda_decode_stream_bytes"]
    layer = _recorded(STEPS, PASSES)
    want = sum(sh * (sizes["non_expert_layer_weights"] + sizes["head"]
                     + touched * sizes["one_expert"]
                     + 2 * slots * sizes["state_per_slot"]
                     + ctx * sizes["kv_per_token"])
               for sh, (_, _, ctx, touched, slots, _) in zip(SHARES, STEPS))
    dev_s = 0.5 + 0.5 + 0.5             # jit_decode inside the window
    assert _reduce(STREAM, layer, cfg, roots) == pytest.approx(
        100 * want / 819e9 / dev_s, rel=1e-9)
    # a step at full occupancy and the issue's reckoning: about 5.3 GB
    full = runner.load_py("kernels", "hybrid_decode_stream", roots).step_bytes(
        sizes, {"experts_touched": 88, "state_slots": 32,
                "context_tokens": 32 * 2240})
    assert 5.2e9 < full < 5.4e9
    # the attention kernel alone: 8 K/V heads x 1 cache layer, and only
    # the call the pattern names (1 of the 2 Mosaic calls recorded a
    # step, an eighth of the step, at its start: the first step's lies
    # before the window)
    tokens = sum(sh * s[2] for sh, s in zip(SHARES, STEPS))
    kv = roofline.paged_attention_bytes([tokens], 8, 128, 1)
    assert kv == tokens * sizes["kv_per_token"] == tokens * 4096
    assert _reduce(ATTN, layer, cfg, roots) == pytest.approx(
        100 * kv / 819e9 / (2 * 0.5 / 8), rel=1e-6)
    # the prefill programs: the first pass whole, half of the second
    fl = cfg["prefill_flops_per_token"]
    one = lambda lens, held: (
        sum(lens) * fl["per_token"] + held * fl["one_expert"]
        + sum(n * (n + 1) // 2 for n in lens) * fl["per_attn_pair"]
        + len(lens) * fl["head_per_row"])
    flops = one([2048], 2100) + 0.5 * one([512, 4096, 1000], 5500)
    assert _reduce(PREFILL, layer, cfg, roots) == pytest.approx(
        100 * flops / 197e12 / 1.5, rel=1e-9)
    # a one-row pass of 4,096 prompt tokens: 5.3 TFLOP (the issue reckoned 5.7)
    assert 5.2e12 < one([4096], 4096) < 5.8e12
    mean = lambda f: sum(f(s) for s in STEPS) / 4
    assert _reduce(STATE, layer, cfg, roots) == pytest.approx(
        mean(lambda s: 2 * s[4] * SLOT) / 1e6)
    assert _reduce(COUNTS[0], layer, cfg, roots) == pytest.approx(
        mean(lambda s: 4 * s[4]) / 160)
    assert _reduce(COUNTS[1], layer, cfg, roots) == pytest.approx(
        100 * mean(lambda s: s[3]) / 160)
    assert _reduce(COUNTS[2], layer, cfg, roots) == pytest.approx(
        mean(lambda s: round(s[5] * 160 / (4 * s[4]), 3)))
    assert _reduce(COUNTS[3], layer, cfg, roots) == pytest.approx(
        mean(lambda s: s[4]))


def test_the_prefill_floor_counts_prompt_tokens_not_padding():
    """The same prompt in a pass of one row or of four padded rows is
    the same work: the floor does not move, so the share falls with the
    time the padding costs."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    one = _reduce(PREFILL, _recorded([], [(1.5, 2.5, 1, [1500], 1400)]), cfg,
                  roots)
    four = _reduce(PREFILL, _recorded([], [(1.5, 2.5, 4, [1500], 1400)]),
                   cfg, roots)
    assert one == pytest.approx(four, rel=1e-12) and 0 < one < 100
    more = _reduce(PREFILL, _recorded([], [(1.5, 2.5, 4, [1500], 2400)]),
                   cfg, roots)
    assert more - one == pytest.approx(
        100 * 1000 * cfg["prefill_flops_per_token"]["one_expert"] / 197e12)


def test_a_program_without_the_new_span_args_reports_nothing():
    """Over a program whose spans carry none of the arguments the new
    readers need (a parent of the PRs that brought them) every new reader
    returns None and none raises; the same without a trace, and over
    nothing at all."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    old = _recorded(STEPS, PASSES, args=(
        "batch", "context_tokens", "loop_steps", "cache_layers", "rows",
        "padded_tokens", "prompt_tokens"))
    no_trace = {k: v for k, v in _recorded(STEPS, PASSES).items()
                if k not in ("profile", "profile_window")}
    for metric in (PREFILL, STREAM, ATTN, STATE) + COUNTS:
        assert _reduce(metric, old, cfg, roots) is None, metric
        assert _reduce(metric, {"spans": []}, cfg, roots) is None, metric
    for metric in (PREFILL, STREAM, ATTN):
        assert _reduce(metric, no_trace, cfg, roots) is None, metric
    # the span arguments this PR added are what its readers need: a
    # program of PR 38 has every other one
    before = _recorded(STEPS, PASSES, args=(
        "batch", "context_tokens", "cache_layers", "kv_heads", "rows",
        "padded_tokens", "prompt_tokens", "experts_touched", "state_slots",
        "moe_assignments", "moe_load_max", "moe_load_max_over_mean"))
    assert _reduce(PREFILL, before, cfg, roots) is None
    assert _reduce(STATE, before, cfg, roots) is None
    assert _reduce(STREAM, before, cfg, roots) is not None
    # a configuration without the counts: nothing, not a KeyError
    for key, metric in (("kda_decode_stream_bytes", STREAM),
                        ("prefill_flops_per_token", PREFILL)):
        bare = {k: v for k, v in cfg.items() if k != key}
        assert _reduce(metric, _recorded(STEPS, PASSES), bare, roots) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    """What this PR appended, found by name: it sits behind what PR 38
    appended, and a later PR may append behind it."""
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    at = lambda entries, name: next(
        i for i, e in enumerate(entries) if e["name"] == name)
    ci = at(bench["configs"], CONFIG)
    conf = bench["configs"][ci]
    assert ci > at(bench["configs"], "zaya1-8b")
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    wi = at(bench["workloads"], CELL)
    cell = bench["workloads"][wi]
    assert wi > at(bench["workloads"], "zaya1_serve_closed64")
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG, 1, "closed32_doc")
    assert sum(1 for c in bench["workloads"] if c["config"] == CONFIG) == 1
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert (real["traffic_name"], real["why"]) == (cell["traffic"],
                                                   cell["why"])
    assert len(cell["why"]) <= 200 and "8x a share" in cell["why"]
    assert len(conf["why"]) <= 200 and len(conf["source"]) <= 200
    rate = next(e for e in bench["end_to_end"]
                if e["name"] == "serve_tok_per_s")
    assert CELL in rate["workloads"]
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "zaya1_serve_closed64")
    assert CELL not in next(e for e in bench["end_to_end"]
                            if e["name"] == "serve_itl_p95_ms")["workloads"]
    first = at(bench["per_layer"], NEW[0])
    assert first > at(bench["per_layer"], "ttft_p50_ms.serve_cca")
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
        "closed", 32, 32, 5.0)
    assert t["prompt_len"] == {"median": 2048, "sigma": 0.5, "min": 512,
                               "max": 4096}
    assert t["output_len"] == {"median": 128, "sigma": 0.5, "min": 32,
                               "max": 256}
    assert t["profile_s"] <= 3.0
    assert len(json.dumps(bench)) < 64 * 1024


def test_config_file_against_the_catalog_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T

    cfg = runner.load_json("configs", CONFIG, [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert cfg["source_url"] == row["source_url"]
    assert cfg["published"] == sorted(row["config"])
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    held = {"num_hidden_layers": 4, "n_routed_experts": 40,
            "vocab_size": 24576}
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published_values"][k] == v and cfg[k] == held[k]
        else:
            assert cfg[k] == v, k       # nested groups whole, widths all
    # the floors of a cut: a whole period, >= 8 experts, >= an eighth
    assert cfg["vocab_size"] * 8 >= cfg["published_values"]["vocab_size"]
    m = cfg["model"]
    lin = cfg["linear_attn_config"]
    assert m["pattern"] == "".join(
        ("*" if i in cfg["gqa_layers"] else "K") + "E"
        for i in range(cfg["num_hidden_layers"])) == "*EKEKEKE"
    assert cfg["gqa_layers_held"] == [0]
    assert (m["embed_dim"], m["mlp_dim"], m["num_layers"], m["num_heads"],
            m["kv_heads"], m["head_dim"], m["vocab_size"], m["max_seq_len"],
            m["norm_eps"], m["tie_embeddings"], m["moe_experts"],
            m["moe_held"], m["moe_top_k"], m["moe_scale"],
            m["moe_shared_dim"], m["kda_heads"], m["kda_conv"],
            m["attn_gate"]) == (
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        2 * cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
        cfg["max_position_embeddings"], cfg["rms_norm_eps"],
        cfg["tie_word_embeddings"], cfg["published_values"][
            "n_routed_experts"], [0, cfg["n_routed_experts"]],
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        lin["num_heads"], lin["short_conv_kernel_size"], cfg["use_gqa_gate"])
    assert lin["head_dim"] == m["head_dim"] and lin["num_kv_heads"] is None
    assert (m["mlp"], m["norm"], m["positions"], m["moe_router"]) == (
        "swiglu", "rms", "none", "sigmoid")
    assert cfg["use_rope"] is False and cfg["first_k_dense_replace"] == 0
    # every mark of the issue's equations is stated as assumed
    assert {"kda_low_rank", "kda_conv", "kda_norms", "kda_values",
            "kda_decay", "kda_beta", "gqa_gate", "no_rope", "experts",
            "router", "state_dtype", "init", "serving"} <= set(cfg["assumed"])
    assert any("computed whole" in d for d in cfg["departures"])
    # the program's tree at these fields, and the counts the floors use
    driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 3_308_377_920
    assert tcfg.dtype == jnp.bfloat16 and "head" in tree
    assert (tcfg.cache_layers, tcfg.state_layers) == (1, 3)
    assert tcfg.state_parts == {"kda_s": (3, (64, 128, 128)),
                                "kda_conv": (3, (3, 24576))}
    by = cfg["parameters_by_kind"]
    gqa, moe, kda = tree["blocks"][0], tree["blocks"][1], tree["blocks"][2]
    assert (size(gqa), size(kda), size(moe)) == (
        by["gqa_mixer_sublayer"], by["kda_mixer_sublayer"],
        by["expert_sublayer_held"]) == (109_056_000, 137_744_576,
                                        646_189_376)
    assert by["one_expert"] == 3 * 4096 * 1280
    assert by["expert_sublayer_outside_routed"] == size(moe) - 40 * by[
        "one_expert"]
    assert by["embedding_held"] == size(tree["embed"]) == size(tree["head"])
    assert cfg["parameters"] == size(gqa) + 3 * size(kda) + 4 * size(moe) \
        + 2 * by["embedding_held"] + by["final_norm"]
    totals = cfg["published_totals_check"]
    assert round(totals["total"] / 1e9, 1) == 250.3
    assert round(totals["active_per_token"] / 1e9, 1) == 14.7
    sizes = cfg["kda_decode_stream_bytes"]
    experts = sum(size((b["w_in"], b["w_out"], b["w_gate"]))
                  for b in tree["blocks"] if "router" in b)
    assert sizes["non_expert_layer_weights"] == 2 * (
        size(tree["blocks"]) - experts)
    assert sizes["one_expert"] * sizes["expert_slots"] == 2 * experts
    assert sizes["expert_slots"] == cfg["moe"]["expert_slots"] == 160
    assert sizes["head"] == 2 * size(tree["head"])
    assert sizes["state_per_slot"] == 4 * sum(
        n * int(np.prod(s)) for n, s in tcfg.state_parts.values()) == SLOT \
        == 13_467_648
    assert sizes["kv_per_token"] == 2 * 1 * 8 * 128 * 2 == 4096
    fl = cfg["prefill_flops_per_token"]
    mats = lambda b: sum(int(np.prod(x.shape)) for x in b.values()
                         if len(x.shape) == 2)
    shared = {k: v for k, v in moe.items()
              if k.startswith("shared") or k == "router"}
    assert fl["per_token"] == 2 * (mats(gqa) + 3 * mats(kda) + 4 * mats(
        shared)) + 3 * 7 * 64 * 128 * 128
    assert fl["one_expert"] == sizes["one_expert"] == 2 * by["one_expert"]
    assert fl["per_attn_pair"] == 4 * 128 * 64
    assert fl["head_per_row"] == 2 * size(tree["head"])
    sv = cfg["serving"]
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    assert not sv["prefix_cache"] and sv["prefill_chunk_tokens"] == 0
    # resident: weights + state pools + pages, of a 16 GB chip
    resident = 2 * cfg["parameters"] + sv["max_slots"] * SLOT \
        + sv["num_pages"] * sv["page_size"] * sizes["kv_per_token"]
    assert 0.45 < resident / 16e9 < 0.5
    # the toy twin's counts follow its own tree the same way
    toy = runner.load_json("configs", "solar2_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttcfg = driver._program_config(toy, T)
    ttree = jax.eval_shape(lambda: T.init_params(ttcfg, jax.random.key(0)))
    tsz = toy["kda_decode_stream_bytes"]
    texp = sum(size((b["w_in"], b["w_out"], b["w_gate"]))
               for b in ttree["blocks"] if "router" in b)
    assert tsz["non_expert_layer_weights"] == 2 * (size(ttree["blocks"])
                                                   - texp)
    assert tsz["one_expert"] * tsz["expert_slots"] == 2 * texp
    assert tsz["state_per_slot"] == 4 * sum(
        n * int(np.prod(s)) for n, s in ttcfg.state_parts.values())
    assert ttcfg.pattern == tcfg.pattern
    # a program that lacks a field the file names is refused at once: the
    # parent of this PR has no kda_heads
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit, match="does not have"):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)


def test_the_references_tree_is_the_programs(data_root):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    toy = runner.load_json("configs", "solar2_toy", [data_root])
    ref = runner.load_py("references", "solar_open2", [runner.ROOT])
    w = ref.init_weights(toy["model"], 2**31 + 5, jnp.float32)
    driver = runner.load_py("drivers", "serve_lm", [runner.ROOT])
    tcfg = driver._program_config(dict(toy, dtype="float32"), T)
    want = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    got = ref.program_tree(w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == jax.tree.map(
        lambda a: a.shape, want)
    assert got["blocks"][1]["w_in"] is w["layers"][1]["up"]
    assert got["embed"] is w["wte"] and got["head"] is w["head"]
    kda = w["layers"][2]
    alpha = jnp.exp(-jnp.exp(kda["a_log"])[:, None] * jax.nn.softplus(
        kda["dt_bias"]).reshape(4, 16))
    assert 0.5 < float(alpha.min()) < float(alpha.max()) < 1.0
    assert float(jnp.abs(kda["b_g"]).mean()) > 0.2
    assert float(jnp.max(jnp.abs(w["layers"][1]["bias"]))) > 0
