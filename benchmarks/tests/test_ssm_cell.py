"""The Granite 4.0-H configuration (36 Mamba-2 layers beside 4 NoPE GQA
layers, a SwiGLU MLP behind each, four scalar multipliers, the whole model
on one chip as an 80-entry layer pattern rolled over its 20-entry period)
and its cell: ``drivers/serve_lm.py`` end to end at a toy size on the CPU,
traced and untraced, the control, every metric the cell adds read from
recorded spans and a recorded device trace, the stream floor against a
hand-worked step, a program without the new span arguments reporting
nothing, ``BENCHMARK.json``'s additions found BY NAME, and the
configuration file held to the catalog's row key for key and to the
program's own tree."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import roofline, runner

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
STREAM = "ssm_decode_stream_roofline_pct.serve"
STATE = "ssm_state_mb_per_step.serve"
ATTN = "paged_attn_ssm_roofline_pct.serve"
SLOTS = "state_slots_per_step.serve_ssm"
HOST = ("prefill_pass_ms.serve_ssm", "itl_p95_ms.serve_ssm",
        "ttft_p50_ms.serve_ssm")
SHARED = ("loadgen_late_mean_ms.serve", "queue_wait_p95_ms.serve",
          "decode_occupancy_pct.serve", "decode_step_host_ms.serve",
          "decode_device_ms.serve", "device_idle_pct.serve")
NEW = (STREAM, STATE, ATTN, SLOTS) + HOST
CELL, CONFIG = "granite4hm_serve_closed64_chat", "granite-4.0-h-micro"
SLOT = 36 * 4 * (64 * 64 * 128 + 3 * 4352)     # state bytes a slot
PATTERN = "M-M-M-M-M-*-M-M-M-M-" * 4


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
    of the cell that is read off the host, the state's bytes among
    them."""
    out = runner.run_cell("granite_toy_closed", seed=2**31 + 99, seconds=2.0,
                          trace=False, roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 10
    assert out["notes"]["distinct_tokens"] >= 1
    out = runner.run_cell("granite_toy_closed", seed=6, seconds=2.0,
                          trace=True, roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "granite_toy_closed", [data_root])
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert cell["per_layer"] == real["per_layer"] == list(SHARED + NEW)
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 9
    m = out["metrics"]
    slots = m[SLOTS]["value"]
    assert 0 < slots <= 4
    toy = runner.load_json("configs", "granite_toy", [data_root])
    assert m[STATE]["value"] == pytest.approx(
        2 * slots * toy["ssm_decode_stream_bytes"]["state_per_slot"] / 1e6)
    assert all(c["ok"] for c in out["checks"])


def test_the_control_reads_the_int8_gaps(data_root):
    import importlib

    control = importlib.import_module("benchmarks.control")
    out = control.control("granite_toy_closed", seed=7, seconds=1.5,
                          roots=[data_root], on_chip=False)
    got = out["control"]
    assert out["precision"] == "int8" and got["tokens"] >= 16
    assert got["served_mean_gap"] >= 0 and got["served_logit_gap"] >= 0
    assert got["program_served_mean_gap"] is not None


def _recorded(steps, args=None):
    """Spans and a device trace as a run records them: ``steps`` of
    (t0 s, t1 s, context tokens, live slots) decode steps on the host
    clock, their programs and kernels (one paged-attention call a cache
    layer, and one other Mosaic call the pattern must NOT count) on a
    profile clock 5 s ahead, a profile window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    for i, (t0, t1, ctx, slots) in enumerate(steps):
        a = {"batch": slots, "context_tokens": ctx, "loop_steps": 1,
             "cache_layers": 4, "kv_heads": 8, "state_layers": 36,
             "state_slots": slots, "state_bytes": 2 * slots * SLOT}
        spans.append({"name": "serve_decode", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": i + 1, "parent": None,
                      "args": a if args is None else
                      {k: v for k, v in a.items() if k in args}})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_decode(1)", s, e - s])
        for c in range(4):
            ops.append([f"%paged_attention_decode.{c} = custom-call(...), "
                        'custom_call_target="tpu_custom_call"',
                        s + c * (e - s) // 8, (e - s) // 16])
        ops.append(['%some_other_kernel = custom-call(...), '
                    'custom_call_target="tpu_custom_call"',
                    s + (e - s) // 2, (e - s) // 8])
    return {"spans": spans, "sizes": {"max_slots": 64},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


STEPS = [(0.5, 1.5, 29000, 64), (1.5, 2.5, 29100, 63),
         (2.5, 3.5, 29200, 60), (3.5, 4.5, 29300, 64)]
SHARES = [0.5, 1.0, 1.0, 0.5]    # of each step inside the window


def test_the_floor_of_a_hand_worked_step():
    """63 live slots at 29,000 context tokens: 6.38 GB of weights once,
    2 x 77.4 MB x 63 of state, 8 KiB a context token = 16.37 GB, 20.0 ms
    at 819 GB/s; state is 60% of it."""
    roots = [runner.ROOT]
    sizes = runner.load_json("configs", CONFIG, roots)[
        "ssm_decode_stream_bytes"]
    fn = runner.load_py("kernels", "ssm_decode_stream", roots)
    got = fn.step_bytes(sizes, {"state_slots": 63, "context_tokens": 29000})
    assert got == 6_382_792_192 + 2 * 63 * 77_377_536 + 29000 * 8192 \
        == 16_369_929_728
    assert 19.9 < 1e3 * got / 819e9 < 20.1
    assert 0.59 < 2 * 63 * 77_377_536 / got < 0.61
    assert fn.NEEDS == ("state_slots", "context_tokens")


def test_new_metrics_from_recorded_spans_and_trace():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    sizes = cfg["ssm_decode_stream_bytes"]
    layer = _recorded(STEPS)
    want = sum(sh * (sizes["layer_weights_and_head"]
                     + 2 * slots * sizes["state_per_slot"]
                     + ctx * sizes["kv_per_token"])
               for sh, (_, _, ctx, slots) in zip(SHARES, STEPS))
    assert _reduce(STREAM, layer, cfg, roots) == pytest.approx(
        100 * want / 819e9 / 3.0, rel=1e-9)
    # the attention kernel alone: 8 K/V heads of 64 x 4 cache layers, and
    # only the calls the pattern names (4 of the 5 Mosaic calls a step, a
    # sixteenth of the step each)
    tokens = sum(sh * s[2] for sh, s in zip(SHARES, STEPS))
    kv = roofline.paged_attention_bytes([tokens], 8, 64, 4)
    assert kv == tokens * sizes["kv_per_token"] == tokens * 8192
    assert _reduce(ATTN, layer, cfg, roots) == pytest.approx(
        100 * kv / 819e9 / (3.0 * 4 / 16), rel=1e-6)
    mean = sum(s[3] for s in STEPS) / 4
    assert _reduce(SLOTS, layer, cfg, roots) == pytest.approx(mean)
    assert _reduce(STATE, layer, cfg, roots) == pytest.approx(
        2 * mean * SLOT / 1e6)
    assert SLOT == sizes["state_per_slot"]


def test_the_stream_floor_counts_the_live_slots_not_the_maximum():
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    full = _reduce(STREAM, _recorded([(1.5, 2.5, 8000, 64)]), cfg, roots)
    part = _reduce(STREAM, _recorded([(1.5, 2.5, 8000, 16)]), cfg, roots)
    assert full - part == pytest.approx(
        100 * 2 * 48 * SLOT / 819e9 / 1.0, rel=1e-9)


def test_a_program_without_the_new_span_args_reports_nothing():
    """Over a program whose spans carry none of the arguments the readers
    need (a parent of the PRs that brought them) every new reader returns
    None and none raises; the same without a trace, and over nothing."""
    roots = [runner.ROOT]
    cfg = runner.load_json("configs", CONFIG, roots)
    old = _recorded(STEPS, args=("batch", "context_tokens", "loop_steps",
                                 "cache_layers"))
    no_trace = {"spans": _recorded(STEPS)["spans"]}
    for name in (STREAM, STATE, ATTN, SLOTS):
        assert _reduce(name, old, cfg, roots) is None, name
        assert _reduce(name, {}, cfg, roots) is None, name
    for name in (STREAM, ATTN):
        assert _reduce(name, no_trace, cfg, roots) is None, name
    # a configuration without the byte counts: nothing, not a KeyError
    bare = {k: v for k, v in cfg.items() if k != "ssm_decode_stream_bytes"}
    assert _reduce(STREAM, _recorded(STEPS), bare, roots) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    """What this PR appended, found by name: it sits behind what PR 42
    appended, and a later PR may append behind it."""
    with open(os.path.join(os.path.dirname(runner.ROOT),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    at = lambda entries, name: next(
        i for i, e in enumerate(entries) if e["name"] == name)
    ci = at(bench["configs"], CONFIG)
    conf = bench["configs"][ci]
    assert ci > at(bench["configs"], "solar-open2-250b")
    assert conf["reduced"] == []
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    wi = at(bench["workloads"], CELL)
    cell = bench["workloads"][wi]
    assert wi > at(bench["workloads"], "solar2_serve_closed32_doc")
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG, 1, "closed64_chat")
    assert sum(1 for c in bench["workloads"] if c["config"] == CONFIG) == 1
    real = runner.load_json("workloads", CELL, [runner.ROOT])
    assert (real["traffic_name"], real["why"]) == (cell["traffic"],
                                                   cell["why"])
    assert len(cell["why"]) <= 200 and len(conf["why"]) <= 200
    assert conf["source"] == runner.load_json(
        "configs", CONFIG, [runner.ROOT])["source_url"]
    rate = next(e for e in bench["end_to_end"]
                if e["name"] == "serve_tok_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "solar2_serve_closed32_doc")
    assert CELL not in next(e for e in bench["end_to_end"]
                            if e["name"] == "serve_itl_p95_ms")["workloads"]
    first = at(bench["per_layer"], NEW[0])
    assert first > at(bench["per_layer"], "ttft_p50_ms.serve_kda")
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
        "closed", 64, 64, 8.0)
    assert t["prompt_len"] == {"median": 192, "sigma": 0.6, "min": 32,
                               "max": 512}
    assert t["output_len"] == {"median": 256, "sigma": 0.5, "min": 64,
                               "max": 512}
    assert (t["profile_after_s"], t["profile_s"] <= 2.0) == (1.0, True)
    lim = real["limits"]
    assert 0 < lim["served_mean_gap"] < lim["served_logit_gap"] < 9
    assert len(json.dumps(bench)) < 64 * 1024


def test_config_file_against_the_catalog_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T

    cfg = runner.load_json("configs", CONFIG, [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == cfg["source_url"])
    # every key of the source, verbatim; nothing reduced
    assert cfg["published"] == sorted(row["config"]) and cfg["reduced"] == []
    for k, v in row["config"].items():
        assert cfg[k] == v, k
    m = cfg["model"]
    entry = {"mamba": "M-", "attention": "*-"}
    assert m["pattern"] == PATTERN == "".join(
        entry[t] for t in cfg["layer_types"])
    assert m["num_layers"] == len(PATTERN) == 2 * cfg["num_hidden_layers"]
    assert (m["embed_dim"], m["mlp_dim"], m["num_heads"], m["kv_heads"],
            m["head_dim"], m["vocab_size"], m["max_seq_len"], m["norm_eps"],
            m["tie_embeddings"], m["mamba_heads"], m["mamba_head_dim"],
            m["mamba_state"], m["mamba_groups"], m["mamba_conv"],
            m["mamba_chunk"], m["embed_multiplier"], m["attn_scale"],
            m["residual_multiplier"], m["logits_divisor"]) == (
        cfg["hidden_size"], cfg["shared_intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["hidden_size"] // cfg["num_attention_heads"], cfg["vocab_size"],
        cfg["max_position_embeddings"], cfg["rms_norm_eps"],
        cfg["tie_word_embeddings"], cfg["mamba_n_heads"],
        cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"],
        cfg["mamba_d_conv"], cfg["mamba_chunk_size"],
        cfg["embedding_multiplier"], cfg["attention_multiplier"],
        cfg["residual_multiplier"], cfg["logits_scaling"])
    assert cfg["mamba_expand"] * cfg["hidden_size"] \
        == m["mamba_heads"] * m["mamba_head_dim"]
    assert cfg["num_local_experts"] == cfg["num_experts_per_tok"] == 0
    assert (cfg["position_embedding_type"], m["positions"], m["norm"],
            cfg["hidden_act"], m["mlp"]) == ("nope", "none", "rms", "silu",
                                             "swiglu")
    # the multiplier is NOT head_dim^-1/2, and no scalar is at its default
    assert m["attn_scale"] == 1 / 64 != m["head_dim"] ** -0.5
    assert 1 not in (m["embed_multiplier"], m["residual_multiplier"],
                     m["logits_divisor"])
    # the draw goes through the multipliers
    assert m["init"]["wte_std"] == pytest.approx(
        m["logits_divisor"] / m["embed_dim"] ** 0.5)
    assert m["init"]["qk_gain"] ** 2 * m["attn_scale"] == pytest.approx(
        m["head_dim"] ** -0.5)
    # ... and past the embedding: 80 branches x (0.22 x out_gain)^2 leave
    # the input token's own embedding a few percent of the stream
    assert 20 < m["residual_multiplier"] * m["init"]["out_gain"] * 80 ** 0.5 \
        / (m["embed_multiplier"] * m["init"]["wte_std"]) < 120
    # the program's tree at these fields: rolled over the period
    driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    assert tcfg.pattern_roll == (20, 4) and tcfg.dtype == jnp.bfloat16
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 3_191_396_096
    assert len(tree["blocks"]) == 20 and "head" not in tree
    by = cfg["parameters_by_kind"]
    assert [size(b) // 4 for b in tree["blocks"][:2]] == [
        by["mamba_layer"], by["mlp_layer"]]
    assert size(tree["blocks"][10]) // 4 == by["attention_layer"]
    assert size(tree["embed"]) == by["embedding_and_tied_head"]
    assert 36 * by["mamba_layer"] + 4 * by["attention_layer"] \
        + 40 * by["mlp_layer"] + by["embedding_and_tied_head"] \
        + by["final_norm"] == cfg["parameters"]
    assert (tcfg.cache_layers, tcfg.state_layers) == (4, 36)
    sizes = cfg["ssm_decode_stream_bytes"]
    assert sizes["layer_weights_and_head"] == 2 * size(tree)
    assert sizes["state_per_slot"] == SLOT == 4 * 36 * sum(
        int(np.prod(s)) for s in tcfg.state_shapes.values()) == 77_377_536
    assert sizes["kv_per_token"] == 2 * 4 * 8 * 64 * 2 == 8192
    sv = cfg["serving"]
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    assert (sv["max_slots"], sv["prefill_batch"], sv["prefix_cache"],
            sv["prefill_chunk_tokens"]) == (64, 4, False, 0)
    assert (cfg["dtype"], cfg["kv_dtype"], cfg["state_dtype"],
            cfg["control_precision"]) == ("bfloat16", "bfloat16", "float32",
                                          "int8")
    # what the chip holds: weights + state pool + pages, 74% of 16 GB
    held = 2 * size(tree) + SLOT * 64 + sv["num_pages"] * 16 * 8192
    assert 0.73 < held / 16e9 < 0.76
    # the toy twin's byte counts follow its own tree the same way
    toy = runner.load_json("configs", "granite_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttcfg = driver._program_config(toy, T)
    ttree = jax.eval_shape(lambda: T.init_params(ttcfg, jax.random.key(0)))
    tsz = toy["ssm_decode_stream_bytes"]
    assert ttcfg.pattern_roll == (6, 2)
    assert tsz["layer_weights_and_head"] == 2 * size(ttree)
    assert tsz["state_per_slot"] == 4 * 4 * sum(
        int(np.prod(s)) for s in ttcfg.state_shapes.values())
    # a program that lacks a field the file names is refused at once: what
    # the parent of this PR does with the four scalars
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)


def test_the_references_tree_is_the_programs(data_root):
    """The reference's weights, stacked by position in the period, are
    the program's tree under other names: nothing is copied."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    toy = runner.load_json("configs", "granite_toy", [data_root])
    ref = runner.load_py("references", "granite_hybrid", [runner.ROOT])
    assert ref.period(PATTERN) == (20, 4)
    assert ref.period(toy["model"]["pattern"]) == (6, 2)
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
    assert float(jnp.std(w["wte"])) == pytest.approx(
        8 / 64 ** 0.5, rel=0.05)
    # the reference imports nothing of the program
    with open(ref.__file__) as f:
        assert "paddle_tpu" not in f.read().split('"""', 2)[2]
