"""The CCA configuration (attention in a compressed latent with a
convolution over the sequence and a shifted value: a per-slot state on
every attention layer beside its pages; top-1 experts chosen by an MLP
router that carries its state from layer to layer) and its cell:
``drivers/serve_lm.py`` end to end at a toy size on the CPU, traced and
untraced, the control, every metric the cell adds read from recorded spans
and a recorded device trace, a program without the new span arguments
reporting nothing, ``BENCHMARK.json``'s additions in their order, and the
configuration file held to the catalog's row key for key and to the
program's own tree."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import roofline, runner

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
STREAM = "cca_decode_stream_roofline_pct.serve"
ATTN = "paged_attn_cca_roofline_pct.serve"
COUNTS = ("moe_tokens_per_expert.serve_top1",
          "moe_experts_touched_pct.serve_top1",
          "moe_load_max_over_mean.serve_top1",
          "state_slots_per_step.serve_cca")
HOST = ("prefill_pass_ms.serve_cca", "itl_p95_ms.serve_cca",
        "ttft_p50_ms.serve_cca")
SHARED = ("loadgen_late_mean_ms.serve", "queue_wait_p95_ms.serve",
          "decode_occupancy_pct.serve", "decode_step_host_ms.serve",
          "decode_device_ms.serve", "device_idle_pct.serve")
NEW = (STREAM, ATTN) + COUNTS + HOST
CELL, CONFIG = "zaya1_serve_closed64", "zaya1-8b"


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
    state rows among them."""
    out = runner.run_cell("zaya_toy_closed", seed=2**31 + 99, seconds=2.0,
                          trace=False, roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 20
    assert out["notes"]["distinct_tokens"] >= 1
    out = runner.run_cell("zaya_toy_closed", seed=6, seconds=2.0, trace=True,
                          roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "zaya_toy_closed", [data_root])
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert cell["per_layer"] == real["per_layer"] == list(SHARED + NEW)
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 11
    m = out["metrics"]
    # 4 slots x top-1 x 2 expert sublayers over 16 (layer, expert) pairs
    assert 0 < m["moe_tokens_per_expert.serve_top1"]["value"] <= 4 * 2 / 16
    assert 0 < m["moe_experts_touched_pct.serve_top1"]["value"] <= 50
    assert m["moe_load_max_over_mean.serve_top1"]["value"] >= 1.0
    assert 0 < m["state_slots_per_step.serve_cca"]["value"] <= 4
    assert all(c["ok"] for c in out["checks"])


def test_the_control_reads_the_int8_gaps(data_root):
    import importlib

    control = importlib.import_module("benchmarks.control")
    out = control.control("zaya_toy_closed", seed=7, seconds=1.5,
                          roots=[data_root], on_chip=False)
    got = out["control"]
    assert out["precision"] == "int8" and got["tokens"] >= 16
    assert got["served_mean_gap"] >= 0 and got["served_logit_gap"] >= 0
    assert got["program_served_mean_gap"] is not None


def _recorded(steps, args=None):
    """Spans and a device trace as a run records them: ``steps`` of
    (t0 s, t1 s, context tokens, experts touched, live slots, busiest
    expert's tokens) decode steps on the host clock, their programs and
    kernels (one paged-attention call per cache layer of two recorded,
    and one other Mosaic call the pattern must NOT count) on a profile
    clock 5 s ahead, a profile window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    for i, (t0, t1, ctx, touched, slots, busiest) in enumerate(steps):
        a = {"batch": slots, "context_tokens": ctx, "loop_steps": 1,
             "cache_layers": 20, "kv_heads": 2, "state_layers": 20,
             "experts_touched": touched, "state_slots": slots,
             "moe_assignments": 20 * slots, "moe_load_max": busiest,
             "moe_load_max_over_mean": round(busiest * 320 / (20 * slots),
                                             3)}
        spans.append({"name": "serve_decode", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": i + 1, "parent": None,
                      "args": a if args is None else
                      {k: v for k, v in a.items() if k in args}})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_decode(1)", s, e - s])
        for c in range(2):
            ops.append([f"%paged_attention_decode.{c} = custom-call(...), "
                        'custom_call_target="tpu_custom_call"',
                        s + c * (e - s) // 4, (e - s) // 8])
        ops.append(['%some_other_kernel = custom-call(...), '
                    'custom_call_target="tpu_custom_call"',
                    s + (e - s) // 2, (e - s) // 8])
    return {"spans": spans, "sizes": {"max_slots": 64},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


STEPS = [(0.5, 1.5, 33000, 314, 64, 11), (1.5, 2.5, 33100, 316, 63, 12),
         (2.5, 3.5, 33200, 310, 60, 10), (3.5, 4.5, 33300, 318, 64, 13)]
SHARES = [0.5, 1.0, 1.0, 0.5]    # of each step inside the window


def test_new_metrics_from_recorded_spans_and_trace():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    sizes = cfg["cca_decode_stream_bytes"]
    layer = _recorded(STEPS)
    want = sum(sh * (sizes["non_expert_layer_weights"] + sizes["head"]
                     + touched * sizes["one_expert"]
                     + 2 * slots * sizes["state_per_slot"]
                     + ctx * sizes["kv_per_token"])
               for sh, (_, _, ctx, touched, slots, _) in zip(SHARES, STEPS))
    got = _reduce(STREAM, layer, cfg, roots)
    assert got == pytest.approx(100 * want / 819e9 / 3.0, rel=1e-9)
    # a step at full occupancy: about 10.1 GB, 12.3 ms of stream
    full = runner.load_py("kernels", "hybrid_decode_stream", roots).step_bytes(
        sizes, {"experts_touched": 320, "state_slots": 64,
                "context_tokens": 64 * 520})
    assert 10.0e9 < full < 10.2e9
    # the attention kernel alone: 2 K/V heads x 20 cache layers, and only
    # the calls the pattern names (2 of the 3 Mosaic calls recorded a
    # step, an eighth of the step each)
    tokens = sum(sh * s[2] for sh, s in zip(SHARES, STEPS))
    kv = roofline.paged_attention_bytes([tokens], 2, 128, 20)
    assert kv == tokens * sizes["kv_per_token"] == tokens * 20480
    assert _reduce(ATTN, layer, cfg, roots) == pytest.approx(
        100 * kv / 819e9 / (3.0 * 2 / 8), rel=1e-6)
    mean = lambda f: sum(f(s) for s in STEPS) / 4
    assert _reduce(COUNTS[0], layer, cfg, roots) == pytest.approx(
        mean(lambda s: 20 * s[4]) / 320)
    assert _reduce(COUNTS[1], layer, cfg, roots) == pytest.approx(
        100 * mean(lambda s: s[3]) / 320)
    assert _reduce(COUNTS[2], layer, cfg, roots) == pytest.approx(
        mean(lambda s: round(s[5] * 320 / (20 * s[4]), 3)))
    assert _reduce(COUNTS[3], layer, cfg, roots) == pytest.approx(
        mean(lambda s: s[4]))


def test_the_stream_floor_counts_what_was_touched_not_the_maxima():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    sizes = cfg["cca_decode_stream_bytes"]
    full = _reduce(STREAM, _recorded([(1.5, 2.5, 30000, 320, 64, 9)]), cfg,
                   roots)
    part = _reduce(STREAM, _recorded([(1.5, 2.5, 30000, 160, 16, 9)]), cfg,
                   roots)
    less = 160 * sizes["one_expert"] + 2 * 48 * sizes["state_per_slot"]
    assert full - part == pytest.approx(100 * less / 819e9 / 1.0, rel=1e-9)


def test_a_program_without_the_new_span_args_reports_nothing():
    """Over a program whose spans carry none of the arguments the new
    readers need (a parent of the PRs that brought them) every new reader
    returns None and none raises; the same without a trace, and over
    nothing at all."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    old = _recorded(STEPS, args=("batch", "context_tokens", "loop_steps",
                                 "cache_layers"))
    no_trace = {k: v for k, v in _recorded(STEPS).items()
                if k not in ("profile", "profile_window")}
    for metric in (STREAM, ATTN) + COUNTS:
        assert _reduce(metric, old, cfg, roots) is None, metric
        assert _reduce(metric, {"spans": []}, cfg, roots) is None, metric
    for metric in (STREAM, ATTN):
        assert _reduce(metric, no_trace, cfg, roots) is None, metric
    # the span argument PR 38 added is the only one its reader needs
    before = _recorded(STEPS, args=("batch", "context_tokens", "cache_layers",
                                    "kv_heads", "experts_touched",
                                    "state_slots", "moe_assignments"))
    assert _reduce(COUNTS[2], before, cfg, roots) is None
    assert _reduce(COUNTS[0], before, cfg, roots) is not None
    # a configuration without the byte counts: nothing, not a KeyError
    bare = {k: v for k, v in cfg.items() if k != "cca_decode_stream_bytes"}
    assert _reduce(STREAM, _recorded(STEPS), bare, roots) is None


def test_benchmark_json_additions_in_order():
    """What PR 38 appended sits right behind what the benchmark had (5
    configurations, 6 cells, 35 per-layer metrics), in order; a later PR
    appends behind it, so nothing here says "last"."""
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = bench["configs"][5]
    assert conf["name"] == CONFIG
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert [c["name"] for c in bench["configs"][:5]] == [
        "resnet50", "gpt2-large", "ouro-2.6b", "nemotron-3-nano-30b-a3b",
        "sdar-30b-a3b-chat"]
    cell = bench["workloads"][6]
    assert (cell["name"], cell["config"], cell["chips"]) == (CELL, CONFIG, 1)
    assert bench["workloads"][5]["name"] == "sdar30_serve_closed64"
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert (real["traffic_name"], real["why"]) == (cell["traffic"],
                                                   cell["why"])
    assert len(cell["why"]) <= 200 and "embedding AND head" in cell["why"]
    assert len(conf["why"]) <= 200 and len(conf["source"]) <= 200
    rate = next(e for e in bench["end_to_end"]
                if e["name"] == "serve_tok_per_s")
    assert rate["workloads"][4] == CELL
    assert CELL not in next(e for e in bench["end_to_end"]
                            if e["name"] == "serve_itl_p95_ms")["workloads"]
    tail = bench["per_layer"][35:35 + len(NEW)]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        spec = runner.load_json("layer_metrics", m["name"], [runner.ROOT])
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_per_s"
        assert {k: spec[k] for k in ("unit", "better", "source", "layer")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer")}
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    layers = {m["layer"] for m in bench["per_layer"][:35]}
    assert {m["layer"] for m in tail} <= layers
    # the six every serve cell shares carry no list: reported here too
    for name in SHARED:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert "workloads" not in entry and name in real["per_layer"]
    assert real["end_to_end"] == ["serve_tok_per_s", "setup_s"]
    t = real["traffic"]
    assert (t["loop"], t["clients"], t["pool"], t["lead_in_s"]) == (
        "closed", 64, 64, 10.0)
    assert t["prompt_len"] == {"median": 128, "sigma": 0.6, "min": 32,
                               "max": 512}
    assert t["output_len"] == {"median": 512, "sigma": 0.5, "min": 128,
                               "max": 1536}


def test_config_file_against_the_catalog_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T

    cfg = runner.load_json("configs", CONFIG, [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "ZAYA1-8B")
    assert cfg["source_url"] == row["source_url"]
    assert cfg["published"] == sorted(row["config"])
    assert cfg["reduced"] == ["num_hidden_layers"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published_values"][k] == v == 40 and cfg[k] == 20
        else:
            assert cfg[k] == v, k
    m = cfg["model"]
    assert m["pattern"] == "*E" * cfg["num_hidden_layers"]
    assert (m["embed_dim"], m["mlp_dim"], m["num_layers"], m["num_heads"],
            m["kv_heads"], m["head_dim"], m["vocab_size"], m["max_seq_len"],
            m["norm_eps"], m["tie_embeddings"], m["moe_experts"],
            m["moe_top_k"], m["moe_router_hidden"], m["cca_taps"],
            m["rope_fraction"], m["rope_theta"]) == (
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        2 * cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
        cfg["max_position_embeddings"], cfg["rms_norm_eps"],
        cfg["tie_word_embeddings"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["router_hidden_size"],
        [cfg["cca_time0"], cfg["cca_time1"]], cfg["partial_rotary_factor"],
        cfg["rope_parameters"]["hybrid"]["rope_theta"])
    assert (cfg["hidden_act"], m["mlp"], m["norm"], m["positions"]) == (
        "silu", "swiglu", "rms", "rotary")
    assert (m["moe_router"], m["moe_renorm"], m["residual_scale"]) == (
        "softmax_topk", False, True)
    # every mark of the issue's equations is stated as assumed
    assert {"conv_stages", "qk_mean", "qk_norm", "partial_rotary",
            "value_shift", "residual_scaling", "router", "init",
            "serving"} <= set(cfg["assumed"])
    assert any("depth router" in d for d in cfg["departures"])
    # the program's tree at these fields, and the bytes the stream floor
    # charges a decode step
    driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 4_688_794_984
    assert tcfg.dtype == jnp.bfloat16 and "head" not in tree
    assert (tcfg.cache_layers, tcfg.state_layers) == (20, 20)
    by = cfg["parameters_by_kind"]
    attn, moe = tree["blocks"][0], tree["blocks"][1]
    assert (size(attn), size(moe)) == (by["attention_sublayer"],
                                       by["expert_sublayer"])
    assert by["published_layer"] == size(attn) + size(moe) == 207_582_994
    assert by["one_expert"] == 3 * 2048 * 2048
    assert by["router"] == size({k: v for k, v in moe.items()
                                 if k.startswith("router")}) == 660_240
    assert by["cca_convolutions"] == 332_800
    assert by["embedding_and_head_tied"] == size(tree["embed"])
    assert cfg["parameters"] == 20 * by["published_layer"] \
        + by["embedding_and_head_tied"] + by["final_norm"]
    sizes = cfg["cca_decode_stream_bytes"]
    experts = sum(size((b["w_in"], b["w_out"], b["w_gate"]))
                  for b in tree["blocks"] if "router" in b)
    assert sizes["non_expert_layer_weights"] == 2 * (
        size(tree["blocks"]) - experts)
    assert sizes["one_expert"] * sizes["expert_slots"] == 2 * experts
    assert sizes["expert_slots"] == cfg["moe"]["expert_slots"] == 320
    assert sizes["head"] == 2 * size(tree["embed"])
    assert sizes["state_per_slot"] == 4 * sum(
        n * int(np.prod(s)) for n, s in tcfg.state_parts.values()) == 215_040
    assert sizes["kv_per_token"] == 2 * 20 * 2 * 128 * 2 == 20_480
    sv = cfg["serving"]
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    assert not sv["prefix_cache"] and sv["prefill_chunk_tokens"] == 0
    # the toy twin's byte counts follow its own tree the same way
    toy = runner.load_json("configs", "zaya_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttcfg = driver._program_config(toy, T)
    ttree = jax.eval_shape(lambda: T.init_params(ttcfg, jax.random.key(0)))
    tsz = toy["cca_decode_stream_bytes"]
    texp = sum(size((b["w_in"], b["w_out"], b["w_gate"]))
               for b in ttree["blocks"] if "router" in b)
    assert tsz["non_expert_layer_weights"] == 2 * (size(ttree["blocks"])
                                                   - texp)
    assert tsz["one_expert"] * tsz["expert_slots"] == 2 * texp
    assert tsz["state_per_slot"] == 4 * sum(
        n * int(np.prod(s)) for n, s in ttcfg.state_parts.values())
    # a program that lacks a field the file names is refused at once
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit, match="does not have"):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)


def test_the_references_tree_is_the_programs(data_root):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    toy = runner.load_json("configs", "zaya_toy", [data_root])
    ref = runner.load_py("references", "zaya", [runner.ROOT])
    w = ref.init_weights(toy["model"], 2**31 + 5, jnp.float32)
    driver = runner.load_py("drivers", "serve_lm", [runner.ROOT])
    tcfg = driver._program_config(dict(toy, dtype="float32"), T)
    want = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    got = ref.program_tree(w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == jax.tree.map(
        lambda a: a.shape, want)
    assert got["blocks"][1]["w_in"] is w["layers"][0]["moe"]["up"]
    assert got["embed"] is w["wte"] and "head" not in got
    for name in ("beta", "gamma", "r_down_b"):
        assert float(jnp.max(jnp.abs(w["layers"][0]["moe"][name]))) > 0
