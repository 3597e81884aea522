"""The hybrid configuration (layers of several kinds, a share of the
routed experts, a recurrent-state pool, fewer K/V heads) and its cell:
``drivers/serve_lm.py`` end to end at a toy size on the CPU, the metrics
the cell adds read from recorded spans and a recorded device trace, the
stream floor counting the experts touched and the live slots (not the
configuration's maxima), a program without the new span arguments
reporting nothing, and the configuration file held to the catalog's keys
and to the program's own tree."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import roofline, runner

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
STREAM = "hybrid_decode_stream_roofline_pct.serve"
GQA = "paged_attn_gqa_roofline_pct.serve"
COUNTS = ("moe_tokens_per_expert.serve", "moe_experts_touched_pct.serve",
          "state_slots_per_step.serve")
CELL = "nemo3n_serve_closed64"


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
    of the cell that is read off the host, the routing counts among
    them."""
    out = runner.run_cell("nemo_toy_closed", seed=2**31 + 99, seconds=2.0,
                          trace=False, roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 20
    out = runner.run_cell("nemo_toy_closed", seed=6, seconds=2.0, trace=True,
                          roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "nemo_toy_closed", [data_root])
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 9
    m = out["metrics"]
    # 4 slots x top-3 x 2 expert layers, about half on the 8 held of 16
    assert 0 < m["moe_tokens_per_expert.serve"]["value"] <= 4 * 3 * 2 / 16
    assert 0 < m["moe_experts_touched_pct.serve"]["value"] <= 100
    assert 0 < m["state_slots_per_step.serve"]["value"] <= 4
    assert all(c["ok"] for c in out["checks"])


def _recorded(steps, args=None):
    """Spans and a device trace as a run records them: ``steps`` of
    (t0 s, t1 s, context tokens, experts touched, live slots) decode
    steps on the host clock, their programs and kernels (one
    paged-attention call per cache layer, and one other Mosaic call the
    GQA pattern must NOT count) on a profile clock 5 s ahead, a profile
    window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    for i, (t0, t1, ctx, touched, slots) in enumerate(steps):
        a = {"batch": slots, "context_tokens": ctx, "loop_steps": 1,
             "cache_layers": 2, "kv_heads": 2, "state_layers": 6,
             "experts_touched": touched, "state_slots": slots,
             "moe_assignments": 3 * touched}
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
        ops.append(['%ragged-dot-metadata = custom-call(...), '
                    'custom_call_target="tpu_custom_call"',
                    s + (e - s) // 2, (e - s) // 8])
    return {"spans": spans, "sizes": {"max_slots": 64},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


STEPS = [(0.5, 1.5, 9000, 300, 64), (1.5, 2.5, 9100, 304, 63),
         (2.5, 3.5, 9200, 296, 60), (3.5, 4.5, 9300, 310, 64)]
SHARES = [0.5, 1.0, 1.0, 0.5]    # of each step inside the window


def test_new_metrics_from_recorded_spans_and_trace():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", "nemotron-3-nano-30b-a3b", roots)
    sizes = cfg["hybrid_decode_stream_bytes"]
    layer = _recorded(STEPS)
    want = sum(sh * (sizes["non_expert_layer_weights"] + sizes["head"]
                     + touched * sizes["one_expert"]
                     + 2 * slots * sizes["state_per_slot"]
                     + ctx * sizes["kv_per_token"])
               for sh, (_, _, ctx, touched, slots) in zip(SHARES, STEPS))
    got = _reduce(STREAM, layer, cfg, roots)
    assert got == pytest.approx(100 * want / 819e9 / 3.0, rel=1e-9)
    # a step at full occupancy: about 8.9 GB, 10.8 ms of stream
    full = runner.load_py("kernels", "hybrid_decode_stream", roots).step_bytes(
        sizes, {"experts_touched": 304, "state_slots": 64,
                "context_tokens": 64 * 400})
    assert 8.8e9 < full < 9.0e9
    # the attention kernel alone: 2 K/V heads x 2 cache layers, and only
    # the calls the pattern names (2 of the 3 Mosaic calls a step, an
    # eighth of the step each)
    tokens = sum(sh * s[2] for sh, s in zip(SHARES, STEPS))
    kv = roofline.paged_attention_bytes([tokens], 2, 128, 2)
    assert kv == tokens * sizes["kv_per_token"] == tokens * 2048
    assert _reduce(GQA, layer, cfg, roots) == pytest.approx(
        100 * kv / 819e9 / (3.0 * 2 / 8), rel=1e-6)
    mean = lambda i: sum(s[i] for s in STEPS) / 4
    assert _reduce(COUNTS[0], layer, cfg, roots) == pytest.approx(
        3 * mean(3) / 320)
    assert _reduce(COUNTS[1], layer, cfg, roots) == pytest.approx(
        100 * mean(3) / 320)
    assert _reduce(COUNTS[2], layer, cfg, roots) == pytest.approx(mean(4))


def test_the_stream_floor_counts_what_was_touched_not_the_maxima():
    """Half the experts touched and a quarter of the slots live: the
    floor falls by exactly those bytes — it does not charge the
    configuration's 320 experts or 64 slots."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", "nemotron-3-nano-30b-a3b", roots)
    sizes = cfg["hybrid_decode_stream_bytes"]
    full = _reduce(STREAM, _recorded([(1.5, 2.5, 8000, 320, 64)]), cfg, roots)
    part = _reduce(STREAM, _recorded([(1.5, 2.5, 8000, 160, 16)]), cfg, roots)
    less = 160 * sizes["one_expert"] + 2 * 48 * sizes["state_per_slot"]
    assert full - part == pytest.approx(100 * less / 819e9 / 1.0, rel=1e-9)


def test_a_program_without_the_new_span_args_reports_nothing():
    """Over a program whose spans carry none of the new arguments (the
    parent of the PR that brought them) every new reader returns None and
    none raises; the same without a trace, and over nothing at all."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", "nemotron-3-nano-30b-a3b", roots)
    old = _recorded(STEPS, args=("batch", "context_tokens", "loop_steps",
                                 "cache_layers"))
    no_trace = {"spans": _recorded(STEPS)["spans"]}
    for name in (STREAM, GQA) + COUNTS:
        assert _reduce(name, old, cfg, roots) is None, name
        assert _reduce(name, {}, cfg, roots) is None, name
    for name in (STREAM, GQA):
        assert _reduce(name, no_trace, cfg, roots) is None, name


def test_benchmark_json_names_the_cell_and_its_metrics():
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = runner.load_json("workloads", CELL, [runner.ROOT])
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": cell["config"],
                     "traffic": cell["traffic_name"], "chips": 1,
                     "why": cell["why"]}
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert set(cell["per_layer"]) == {n for n in listed
                                      if moves[n] == "serve_tok_per_s"}
    for name in cell["per_layer"]:
        spec = runner.load_json("layer_metrics", name, [runner.ROOT])
        decl = next(m for m in bench["per_layer"] if m["name"] == name)
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == {
            k: decl[k] for k in ("unit", "better", "source", "layer",
                                 "moves")}, name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tok_per_s"]["workloads"]
    assert set(cell["end_to_end"]) - {"setup_s"} == {
        n for n, m in e2e.items() if CELL in m.get("workloads", ())}


def test_configuration_file_agrees_with_its_source_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T

    cfg = runner.load_json("configs", "nemotron-3-nano-30b-a3b",
                           [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == cfg["source_url"])
    assert cfg["published"] == sorted(row["config"])
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published_values"][k] == v and cfg[k] < v, k
        else:
            assert cfg[k] == v, k
    m = cfg["model"]
    # the floors of the model-configs guide: a whole period and four
    # layers, 8 experts, an eighth of the vocabulary
    assert cfg["hybrid_override_pattern"].startswith(m["pattern"])
    assert m["pattern"] == cfg["hybrid_override_pattern_held"] \
        and len(m["pattern"]) == cfg["num_hidden_layers"] == 13
    assert (m["pattern"].count("M"), m["pattern"].count("E"),
            m["pattern"].count("*")) == (6, 5, 2)
    assert (m["embed_dim"], m["mlp_dim"], m["num_layers"], m["num_heads"],
            m["kv_heads"], m["head_dim"], m["vocab_size"], m["max_seq_len"],
            m["norm_eps"], m["tie_embeddings"], m["moe_top_k"],
            m["moe_scale"], m["moe_shared_dim"], m["moe_held"],
            m["mamba_heads"], m["mamba_head_dim"], m["mamba_state"],
            m["mamba_groups"], m["mamba_conv"], m["mamba_chunk"]) == (
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
        cfg["max_position_embeddings"], cfg["layer_norm_epsilon"],
        cfg["tie_word_embeddings"], cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"],
        cfg["moe_shared_expert_intermediate_size"],
        [0, cfg["n_routed_experts"]], cfg["mamba_num_heads"],
        cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
        cfg["conv_kernel"], cfg["chunk_size"])
    # the router keeps its published width; the experts held are a share
    assert m["moe_experts"] == cfg["published_values"]["n_routed_experts"]
    assert (cfg["mlp_hidden_act"], m["mlp"]) == ("relu2", "relu2")
    assert (m["positions"], m["norm"], m["moe_router"]) == (
        "none", "rms", "sigmoid")
    # the program's tree at these fields: the held count, and the bytes
    # the stream floor charges a decode step
    driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 3_926_018_560
    assert tcfg.dtype == jnp.bfloat16
    assert (tcfg.cache_layers, tcfg.state_layers) == (2, 6)
    sizes = cfg["hybrid_decode_stream_bytes"]
    experts = sum(size((b["w_in"], b["w_out"])) for b in tree["blocks"]
                  if "router" in b)
    assert sizes["non_expert_layer_weights"] == 2 * (
        size(tree["blocks"]) - experts)
    assert sizes["one_expert"] * sizes["expert_slots"] == 2 * experts
    assert sizes["expert_slots"] == cfg["moe"]["expert_slots"] == 5 * 64
    assert sizes["head"] == 2 * size(tree["head"])
    assert sizes["state_per_slot"] == 4 * 6 * sum(
        int(np.prod(s)) for s in tcfg.state_shapes.values())
    assert sizes["kv_per_token"] == 2 * 2 * 2 * 128 * 2 == 2048
    by = cfg["parameters_by_kind"]
    assert [size(b) for b in tree["blocks"][:3]] == [
        by["mamba_layer"], by["moe_layer_held"], by["mamba_layer"]]
    assert size(tree["blocks"][5]) == by["attention_layer"]
    assert by["moe_layer_whole"] - by["moe_layer_held"] == 64 * by["one_expert"]
    sv = cfg["serving"]
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    # the toy twin's byte counts follow its own tree the same way
    toy = runner.load_json("configs", "nemo_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttcfg = driver._program_config(toy, T)
    ttree = jax.eval_shape(lambda: T.init_params(ttcfg, jax.random.key(0)))
    tsz = toy["hybrid_decode_stream_bytes"]
    texp = sum(size((b["w_in"], b["w_out"])) for b in ttree["blocks"]
               if "router" in b)
    assert tsz["non_expert_layer_weights"] == 2 * (size(ttree["blocks"])
                                                   - texp)
    assert tsz["one_expert"] * tsz["expert_slots"] == 2 * texp
    assert tsz["state_per_slot"] == 4 * 2 * sum(
        int(np.prod(s)) for s in ttcfg.state_shapes.values())
    # a program that lacks a field the file names is refused at once
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)


def test_the_reference_is_given_the_same_share(data_root):
    """The reference's weights hold the experts the configuration says
    are held, and its tree is the program's tree under other names."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    toy = runner.load_json("configs", "nemo_toy", [data_root])
    ref = runner.load_py("references", "nemotron_h", [runner.ROOT])
    w = ref.init_weights(toy["model"], 2**31 + 5, jnp.float32)
    driver = runner.load_py("drivers", "serve_lm", [runner.ROOT])
    tcfg = driver._program_config(dict(toy, dtype="float32"), T)
    want = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    got = ref.program_tree(w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, got) == jax.tree.map(
        lambda a: a.shape, want)
    assert got["blocks"][1]["w_in"].shape[0] == 8      # of 16 routed
    assert got["blocks"][1]["router"].shape[1] == 16   # the router: all
    assert got["blocks"][1]["w_in"] is w["layers"][1]["up"]  # not a copy
    assert float(jnp.max(jnp.abs(w["layers"][1]["bias"]))) > 0
