"""The block-diffusion configuration and its cell: ``drivers/
serve_block_lm.py`` end to end at a toy size on the CPU (the order of
unmasking reaching the reference), the control, the metrics the cell adds
read from recorded spans and a recorded device trace, a program without
the new span arguments reporting nothing, and the configuration file held
to the catalog's keys and to the program's own tree."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import roofline, runner

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
STREAM = "block_decode_stream_roofline_pct.serve"
ATTN = "paged_attn_block_roofline_pct.serve"
RATIOS = ("block_tokens_per_pass.serve_block",
          "block_passes_per_block.serve_block")
COUNTS = ("moe_tokens_per_expert.serve_block",
          "moe_experts_touched_pct.serve_block")
CELL, CONFIG = "sdar30_serve_closed64", "sdar-30b-a3b-chat"


def _run(config, roots):
    return runner.Run(workload="test", cell={}, config=config, seed=0,
                      seconds=1.0, trace=True, roots=roots, on_chip=False,
                      proc_t0=0.0, chips=1, peak=PEAK)


def _reduce(metric, layer, config, roots):
    spec = runner.load_json("layer_metrics", metric, roots)
    return runner.load_py("reducers", spec["reducer"], roots).reduce(
        spec, layer, _run(config, roots))


def test_serve_block_lm_end_to_end(data_root, capsys):
    """Untraced: the cell's two end-to-end metrics and a ``correct`` line
    (a seed beyond 32 signed bits): the reference held every served token
    against its logits at the step that unmasked it, so the order reached
    it.  Traced: every per-layer metric of the cell that is read off the
    host."""
    out = runner.run_cell("sdar_toy_closed", seed=2**31 + 77, seconds=2.0,
                          trace=False, roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 10
    assert out["notes"]["served_tokens_checked"] >= 16
    out = runner.run_cell("sdar_toy_closed", seed=5, seconds=2.0, trace=True,
                          roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "sdar_toy_closed", [data_root])
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 11
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["block_tokens_per_pass.serve_block"] <= 4 / 3
    assert 2.0 <= m["block_passes_per_block.serve_block"] <= 3.0
    # 4 rows x 4 positions x top-4 x 2 expert layers over 32 pairs
    assert 0 < m["moe_tokens_per_expert.serve_block"] <= 4 * 4 * 4 * 2 / 32
    assert 0 < m["moe_experts_touched_pct.serve_block"] <= 100
    assert m["itl_p95_ms.serve_block"] > 0 and m["ttft_p50_ms.serve_block"] > 0
    assert all(c["ok"] for c in out["checks"])


def test_the_control_reads_the_same_positions_in_the_same_state(data_root):
    from benchmarks import control

    out = control.control("sdar_toy_closed", 3, 1.5, roots=[data_root],
                          on_chip=False)
    got = out["control"]
    assert got["tokens"] >= 16 and out["precision"] == "int8"
    assert got["served_mean_gap"] >= 0 and got["moved_share"] >= 0
    assert got["program_served_mean_gap"] >= 0


def _recorded(steps, args=None):
    """Spans and a device trace as a run records them: ``steps`` of (t0
    s, t1 s, context tokens, experts touched, rows, blocks committed,
    tokens out) block passes on the host clock, their programs and
    kernels (one paged-attention call per cache layer, and one other
    Mosaic call the pattern must NOT count) on a profile clock 5 s ahead,
    a profile window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    for i, (t0, t1, ctx, touched, rows, commits, out) in enumerate(steps):
        a = {"batch": rows, "block": 4, "positions": 4 * rows,
             "context_tokens": ctx, "loop_steps": 1, "cache_layers": 6,
             "kv_heads": 4, "state_layers": 0, "experts_touched": touched,
             "moe_assignments": 16 * touched, "committed": commits,
             "commit_rows": commits, "tokens_out": out, "masked_in": 2 * rows,
             "unmasked": rows}
        spans.append({"name": "serve_decode", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": i + 1, "parent": None,
                      "args": a if args is None else
                      {k: v for k, v in a.items() if k in args}})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_decode(1)", s, e - s])
        for c in range(6):
            ops.append([f"%paged_attention_decode.{c} = custom-call(...), "
                        'custom_call_target="tpu_custom_call"',
                        s + c * (e - s) // 8, (e - s) // 32])
        ops.append(['%other_kernel = custom-call(...), '
                    'custom_call_target="tpu_custom_call"',
                    s + 7 * (e - s) // 8, (e - s) // 16])
    return {"spans": spans, "sizes": {"max_slots": 64},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


STEPS = [(0.5, 1.5, 25000, 760, 64, 20, 78), (1.5, 2.5, 25400, 764, 63, 22, 84),
         (2.5, 3.5, 25100, 758, 60, 21, 80), (3.5, 4.5, 25900, 766, 64, 21, 83)]
SHARES = [0.5, 1.0, 1.0, 0.5]    # of each pass inside the window


def test_new_metrics_from_recorded_spans_and_trace():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    sizes = cfg["block_decode_stream_bytes"]
    layer = _recorded(STEPS)
    want = sum(sh * (sizes["non_expert_layer_weights"] + sizes["head"]
                     + touched * sizes["one_expert"]
                     + ctx * sizes["kv_per_token"])
               for sh, (_, _, ctx, touched, *_) in zip(SHARES, STEPS))
    assert _reduce(STREAM, layer, cfg, roots) == pytest.approx(
        100 * want / 819e9 / 3.0, rel=1e-9)
    # a pass at full occupancy: 8.1 GB + 0.3 GB of K/V, 10.3 ms of stream
    full = runner.load_py("kernels", "block_decode_stream", roots).step_bytes(
        sizes, {"experts_touched": 768, "context_tokens": 64 * 400})
    assert 8.35e9 < full < 8.45e9
    # the attention kernel alone: 4 K/V heads x 6 cache layers, and only
    # the calls the pattern names (6 of the 7 Mosaic calls a pass, 1/32 of
    # the pass each)
    tokens = sum(sh * s[2] for sh, s in zip(SHARES, STEPS))
    kv = roofline.paged_attention_bytes([tokens], 4, 128, 6)
    assert kv == tokens * sizes["kv_per_token"] == tokens * 12288
    assert _reduce(ATTN, layer, cfg, roots) == pytest.approx(
        100 * kv / 819e9 / (3.0 * 6 / 32), rel=1e-6)
    total = lambda i: sum(s[i] for s in STEPS)
    assert _reduce(RATIOS[0], layer, cfg, roots) == pytest.approx(
        total(6) / total(4))
    assert _reduce(RATIOS[1], layer, cfg, roots) == pytest.approx(
        total(4) / total(5))
    assert _reduce(COUNTS[0], layer, cfg, roots) == pytest.approx(
        16 * total(3) / 4 / 768)
    assert _reduce(COUNTS[1], layer, cfg, roots) == pytest.approx(
        100 * total(3) / 4 / 768)


def test_a_ratio_of_two_sums_is_not_a_mean_of_ratios():
    """``span_sum_ratio``: passes weigh by their rows; a span without
    either argument does not count; a zero divisor reports nothing."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    layer = _recorded([(1.5, 2.5, 1, 1, 64, 30, 120),
                       (2.5, 3.5, 1, 1, 2, 0, 0)])
    assert _reduce(RATIOS[0], layer, cfg, roots) == pytest.approx(120 / 66)
    assert _reduce(RATIOS[1], layer, cfg, roots) == pytest.approx(66 / 30)
    layer["spans"][1]["args"].pop("tokens_out")
    assert _reduce(RATIOS[0], layer, cfg, roots) == pytest.approx(120 / 64)
    none = _recorded([(1.5, 2.5, 1, 1, 64, 0, 0)])
    assert _reduce(RATIOS[1], none, cfg, roots) is None
    spec = {"args": {"span": "serve_prefill", "arg": "prompt_tokens",
                     "per_arg": "padded_tokens", "percent": True}}
    pre = {"spans": [{"name": "serve_prefill", "args": {
        "prompt_tokens": 192, "padded_tokens": 512}}, {
        "name": "serve_prefill", "args": {"prompt_tokens": 600,
                                          "padded_tokens": 2048}}]}
    assert runner.load_py("reducers", "span_sum_ratio", roots).reduce(
        spec, pre, None) == pytest.approx(100 * 792 / 2560)


def test_a_program_without_the_new_span_args_reports_nothing():
    """Over a program whose spans carry none of the new arguments (the
    parent of the PR that brought them: one token a row a step) every new
    reader returns None and none raises; the same without a trace, and
    over nothing at all."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    old = _recorded(STEPS, args=("batch", "context_tokens", "loop_steps",
                                 "cache_layers"))
    no_trace = {"spans": _recorded(STEPS)["spans"]}
    for name in (STREAM, ATTN) + RATIOS + COUNTS:
        assert _reduce(name, old, cfg, roots) is None, name
        assert _reduce(name, {}, cfg, roots) is None, name
    for name in (STREAM, ATTN):
        assert _reduce(name, no_trace, cfg, roots) is None, name
    # the stream floor is a block pass's: a one-token step of a pattern
    # (kv_heads, experts_touched, no ``block``) is not charged it
    hybrid = _recorded(STEPS, args=(
        "batch", "context_tokens", "loop_steps", "cache_layers", "kv_heads",
        "experts_touched", "moe_assignments"))
    assert _reduce(STREAM, hybrid, cfg, roots) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = runner.load_json("workloads", CELL, [runner.ROOT])
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": cell["traffic_name"], "chips": 1,
                     "why": cell["why"]}
    assert len(cell["why"]) <= 200
    # found by name, in order among themselves: a later PR appends behind
    # them, so nothing here says "last"
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == cells.index("nemo3n_serve_closed64") + 1
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = runner.load_json("configs", CONFIG, [runner.ROOT])
    assert (conf["name"], conf["source"], conf["reduced"]) == (
        CONFIG, cfg["source_url"], ["num_hidden_layers"])
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert set(cell["per_layer"]) == {n for n in listed
                                      if moves[n] == "serve_tok_per_s"}
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == cell["per_layer"][6:]
    at = bench["per_layer"].index(new[0])
    assert bench["per_layer"][at:at + len(new)] == new     # one block, in order
    for name in cell["per_layer"]:
        spec = runner.load_json("layer_metrics", name, [runner.ROOT])
        decl = next(m for m in bench["per_layer"] if m["name"] == name)
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: decl[k] for k in ("unit", "better", "source", "layer",
                                 "moves")}, name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    rate = e2e["serve_tok_per_s"]["workloads"]
    assert CELL in rate and rate == [n for n in cells if n in rate]
    assert set(cell["end_to_end"]) - {"setup_s"} == {
        n for n, m in e2e.items() if CELL in m.get("workloads", ())}
    assert set(cell["limits"]) == {"served_logit_gap", "served_mean_gap"}


def test_configuration_file_agrees_with_its_source_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.scheduler import ServingConfig

    cfg = runner.load_json("configs", CONFIG, [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == cfg["source_url"])
    assert row["name"] == "SDAR-30B-A3B-Chat"
    assert cfg["published"] == sorted(row["config"])
    assert cfg["reduced"] == ["num_hidden_layers"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published_values"][k] == v and cfg[k] < v, k
        else:
            assert cfg[k] == v, k
    m = cfg["model"]
    # the guide's floors: at least four layers; every expert and every id
    assert cfg["num_hidden_layers"] == 6 >= 4
    assert m["pattern"] == "*E" * cfg["num_hidden_layers"]
    assert m["num_layers"] == len(m["pattern"]) == 12
    assert (m["embed_dim"], m["mlp_dim"], m["num_heads"], m["kv_heads"],
            m["head_dim"], m["vocab_size"], m["max_seq_len"], m["norm_eps"],
            m["tie_embeddings"], m["moe_experts"], m["moe_top_k"],
            m["rope_theta"]) == (
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["vocab_size"], cfg["max_position_embeddings"],
        cfg["rms_norm_eps"], cfg["tie_word_embeddings"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["rope_theta"])
    assert (cfg["hidden_act"], m["mlp"], m["moe_router"], m["norm"],
            m["positions"], m["qk_norm"], cfg["norm_topk_prob"]) == (
        "silu", "swiglu", "softmax_topk", "rms", "rotary", True, True)
    assert "moe_held" not in m and "moe_shared_dim" not in m
    assert (m["block_len"], 0 <= m["mask_id"] < m["vocab_size"]) == (4, True)
    assert {"block_len", "denoise_steps", "unmask_policy", "mask_id",
            "no_shift", "commit_pass", "qk_norm", "router",
            "init"} <= set(cfg["assumed"])
    assert "8 pipeline stages of 6 layers" in cfg["deployment"]
    # the program's tree at these fields, and the bytes the stream floor
    # charges a block pass
    driver = runner.load_py("drivers", "serve_lm", [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 4_361_055_744
    assert tcfg.dtype == jnp.bfloat16
    assert (tcfg.cache_layers, tcfg.state_layers) == (6, 0)
    by = cfg["parameters_by_kind"]
    assert size(tree["blocks"][0]) == (by["attention"] + by["qk_norms"]
                                       + by["layer_norms"] // 2)
    assert size(tree["blocks"][1]) == (by["experts_per_layer"] + by["router"]
                                       + by["layer_norms"] // 2)
    assert size(tree["blocks"][:2]) == by["layer"] == 623_120_640
    assert by["experts_per_layer"] == 128 * by["one_expert"]
    assert (size(tree["embed"]), size(tree["head"]), size(tree["ln_f_g"])) \
        == (by["embedding"], by["head"], by["final_norm"])
    sizes = cfg["block_decode_stream_bytes"]
    experts = sum(size((b["w_in"], b["w_gate"], b["w_out"]))
                  for b in tree["blocks"] if "router" in b)
    assert sizes["non_expert_layer_weights"] == 2 * (
        size(tree["blocks"]) - experts)
    assert sizes["one_expert"] * sizes["expert_slots"] == 2 * experts
    assert sizes["expert_slots"] == cfg["moe"]["expert_slots"] == 6 * 128
    assert sizes["head"] == 2 * size(tree["head"])
    assert sizes["kv_per_token"] == 6 * 2 * 4 * 128 * 2 == 12288
    sv = cfg["serving"]
    scfg = ServingConfig(**sv)
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    assert sv["page_size"] % m["block_len"] == 0
    assert (scfg.denoise_steps, scfg.unmask_policy) == (
        2, "low_confidence_static")
    # the toy twin's byte counts follow its own tree the same way
    toy = runner.load_json("configs", "sdar_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttcfg = driver._program_config(toy, T)
    ttree = jax.eval_shape(lambda: T.init_params(ttcfg, jax.random.key(0)))
    tsz = toy["block_decode_stream_bytes"]
    texp = sum(size((b["w_in"], b["w_gate"], b["w_out"]))
               for b in ttree["blocks"] if "router" in b)
    assert tsz["non_expert_layer_weights"] == 2 * (size(ttree["blocks"])
                                                   - texp)
    assert tsz["one_expert"] * tsz["expert_slots"] == 2 * texp
    # a program that lacks a field the file names is refused at once
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)


def test_the_reference_tree_is_the_programs_tree(data_root):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    toy = runner.load_json("configs", "sdar_toy", [data_root])
    ref = runner.load_py("references", "sdar", [runner.ROOT])
    w = ref.init_weights(toy["model"], 2**31 + 5, jnp.float32)
    driver = runner.load_py("drivers", "serve_lm", [runner.ROOT])
    tcfg = driver._program_config(dict(toy, dtype="float32"), T)
    want = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    got = ref.program_tree(w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == jax.tree.map(
        lambda a: a.shape, want)
    assert got["blocks"][1]["w_gate"] is w["layers"][0]["moe"]["gate"]
    assert "router_bias" not in got["blocks"][1]
    assert ref.departures and all(isinstance(d, str) for d in ref.departures)
