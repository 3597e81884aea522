"""The looped-stack configuration and its cell: ``drivers/serve_lm.py``
end to end at a toy size on the CPU, the three metrics the cell adds read
from recorded spans and a recorded device trace (the passes counted on
the trace, not taken from the configuration), the byte counts of their
floors held to the program's own tree, a program without the spans
reporting nothing, and the configuration file held to its published
keys."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import roofline, runner

REPO = os.path.dirname(runner.ROOT)
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
NEW = ("loop_passes_per_token.serve", "paged_attn_looped_roofline_pct.serve",
       "decode_stream_roofline_pct.serve")


def _reduce(metric, layer, config, roots):
    run = runner.Run(workload="test", cell={}, config=config, seed=0,
                     seconds=1.0, trace=True, roots=roots, on_chip=False,
                     proc_t0=0.0, chips=1, peak=PEAK)
    spec = runner.load_json("layer_metrics", metric, roots)
    return runner.load_py("reducers", spec["reducer"], roots).reduce(
        spec, layer, run)


def test_serve_lm_end_to_end(data_root, capsys):
    """Untraced: the three end-to-end metrics and a ``correct`` line.
    Traced (the profile stopped off the load generator's thread): every
    per-layer metric of the cell that is read off the host, the tail and
    the two quantities the rate-only cell reports under ``.serve_rate``
    among them (the device trace's have nothing to read on a CPU)."""
    out = runner.run_cell("ouro_toy_closed", seed=2**31 + 77, seconds=2.0,
                          trace=False, roots=[data_root], on_chip=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == out
    assert set(out["metrics"]) == {"serve_tok_per_s", "serve_itl_p95_ms",
                                   "setup_s"}
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 20
    out = runner.run_cell("ouro_toy_closed", seed=5, seconds=2.0, trace=True,
                          roots=[data_root], on_chip=False)
    cell = runner.load_json("workloads", "ouro_toy_closed", [data_root])
    host = {m for m in cell["per_layer"] if runner.load_json(
        "layer_metrics", m, [runner.ROOT])["source"] != "device_trace"}
    assert host == set(out["metrics"]) and len(host) == 9
    assert (out["metrics"]["ttft_p50_ms.serve_rate"]
            == out["metrics"]["ttft_p50_ms.serve"])
    assert out["metrics"]["itl_p95_ms.serve_rate"]["value"] > 0
    assert all(c["ok"] for c in out["checks"])


def _recorded(steps, calls=6):
    """Spans and a device trace as a run records them: ``steps`` of
    (t0 s, t1 s, context tokens) decode steps on the host clock, their
    programs and kernels (``calls`` Mosaic calls a step) on a profile
    clock 5 s ahead, a profile window of [6.0, 9.0] s."""
    off = int(5e9)
    spans, mods, ops = [], [], []
    for i, (t0, t1, ctx) in enumerate(steps):
        spans.append({"name": "serve_decode", "thread": "serving-engine",
                      "t0": t0, "t1": t1, "id": i + 1, "parent": None,
                      "args": {"batch": 4, "context_tokens": ctx,
                               "loop_steps": 3, "cache_layers": 6}})
        s, e = int(t0 * 1e9) + off, int(t1 * 1e9) + off
        mods.append(["jit_decode(1)", s, e - s])
        # one Mosaic call per cache layer, each a twelfth of the step
        for c in range(calls):
            ops.append(["%paged_attention_decode = custom-call(...), "
                        'custom_call_target="tpu_custom_call"',
                        s + c * (e - s) // 6, (e - s) // 12])
    spans.append({"name": "serve_prefill", "thread": "serving-engine",
                  "t0": 1.0, "t1": 1.05, "id": 99, "parent": None,
                  "args": {"batch": 2, "loop_steps": 3, "cache_layers": 6}})
    return {"spans": spans, "sizes": {"max_slots": 4},
            "profile": {"devices": {"0": {"XLA Modules": mods,
                                          "XLA Ops": ops}}, "host": []},
            "profile_window": (int(6e9), int(9e9)), "span_offset_ns": off}


def test_new_metrics_from_recorded_spans_and_trace(data_root):
    roots = [runner.ROOT, data_root]
    cfg = runner.load_json("configs", "ouro_toy", roots)
    sizes = cfg["decode_stream_bytes"]
    # on the profile clock the steps are [5.5, 6.5], [6.5, 7.5], [7.5, 8.5],
    # [8.5, 9.5]: the window holds half of the first and of the last
    layer = _recorded([(0.5, 1.5, 100), (1.5, 2.5, 104), (2.5, 3.5, 108),
                       (3.5, 4.5, 112)])
    assert _reduce(NEW[0], layer, cfg, roots) == 3.0
    shares = [0.5, 1.0, 1.0, 0.5]
    tokens = sum(s * c for s, c in zip(shares, (100, 104, 108, 112)))
    kv = tokens * 2 * 4 * 24 * 2 * 6          # K and V, 4 heads x 24, bf16, 6 cache layers
    assert kv == roofline.paged_attention_bytes([tokens], 4, 24, 6)
    # kernels: six calls of 1/12 step each in every whole step; the cut
    # steps keep the calls (or parts) inside the window
    got = _reduce(NEW[1], layer, cfg, roots)
    assert got == pytest.approx(100 * kv / 819e9 / 1.5, rel=1e-6)
    weights = 3.0 * (3 * sizes["layer_weights"] + sizes["head"])
    got = _reduce(NEW[2], layer, cfg, roots)
    assert got == pytest.approx(
        100 * (weights + tokens * sizes["kv_per_token"]) / 819e9 / 3.0,
        rel=1e-6)
    assert sizes["kv_per_token"] * tokens == kv


def test_looped_floor_is_loop_steps_times_the_plain_floor(data_root):
    """The same traced steps under the plain family's floor (which
    multiplies by ``num_layers``) and the looped one's (``num_layers x
    loop_steps`` cache layers): ``loop_steps`` times the bytes."""
    roots = [runner.ROOT, data_root]
    cfg = runner.load_json("configs", "ouro_toy", roots)
    layer = _recorded([(1.5, 2.5, 120), (2.5, 3.5, 124)])
    layer["decode_context_tokens"] = 244     # what the plain floor reads
    run = runner.Run(workload="test", cell={}, config=cfg, seed=0,
                     seconds=1.0, trace=True, roots=roots, on_chip=False,
                     proc_t0=0.0, chips=1, peak=PEAK)
    plain = run.py("kernels", "paged_attention").floor(
        run.json("kernels", "paged_attn"), {}, layer, run)[0]
    looped = run.py("kernels", "paged_attention_looped").floor(
        run.json("kernels", "paged_attn_looped"), {}, layer, run)[0]
    assert looped == pytest.approx(cfg["model"]["loop_steps"] * plain)


def test_passes_are_counted_on_the_trace_not_read_from_the_config(data_root):
    """A program that ran 2 of its 3 passes (4 Mosaic calls a step over 2
    layers) reads 2.0 though its spans still say ``loop_steps`` 3; the
    two executions the window cuts are left out of the count."""
    roots = [runner.ROOT, data_root]
    cfg = runner.load_json("configs", "ouro_toy", roots)
    steps = [(0.5, 1.5, 100), (1.5, 2.5, 104), (2.5, 3.5, 108),
             (3.5, 4.5, 112)]
    assert _reduce(NEW[0], _recorded(steps, calls=4), cfg, roots) == 2.0
    cut = _recorded(steps)
    ops = cut["profile"]["devices"]["0"]["XLA Ops"]
    # the trace begins inside the first step and ends inside the last
    cut["profile"]["devices"]["0"]["XLA Ops"] = [
        o for o in ops if int(6e9) <= o[1] < int(9e9)]
    assert _reduce(NEW[0], cut, cfg, roots) == 3.0


def test_a_program_without_the_loop_args_reports_nothing(data_root):
    """Over a program whose spans carry no ``loop_steps`` /
    ``cache_layers`` (the parent of the PR that brought them), the two
    floors return None; without a trace every new reader does, and none
    raises."""
    roots = [runner.ROOT, data_root]
    cfg = runner.load_json("configs", "ouro_toy", roots)
    old = _recorded([(1.5, 2.5, 120)])
    for s in old["spans"]:
        s["args"] = {k: v for k, v in s["args"].items()
                     if k in ("batch", "context_tokens")}
    no_trace = {"spans": _recorded([(1.5, 2.5, 120)])["spans"]}
    for name in NEW[1:]:
        assert _reduce(name, old, cfg, roots) is None, name
    for name in NEW:
        assert _reduce(name, no_trace, cfg, roots) is None, name
        assert _reduce(name, {}, cfg, roots) is None, name


def test_configuration_file_agrees_with_its_source_and_the_program():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T

    cfg = runner.load_json("configs", "ouro-2.6b", [runner.ROOT])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == cfg["source_url"])
    assert cfg["published"] == sorted(row["config"]) and cfg["reduced"] == []
    for k, v in row["config"].items():
        assert cfg[k] == v, k
    m = cfg["model"]
    assert (m["embed_dim"], m["mlp_dim"], m["num_layers"], m["num_heads"],
            m["head_dim"], m["vocab_size"], m["max_seq_len"], m["norm_eps"],
            m["rope_theta"], m["loop_steps"], m["tie_embeddings"],
            m["early_exit_threshold"]) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["head_dim"], cfg["vocab_size"], cfg["max_position_embeddings"],
        cfg["rms_norm_eps"], cfg["rope_theta"], cfg["total_ut_steps"],
        cfg["tie_word_embeddings"], cfg["early_exit_threshold"])
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert (cfg["hidden_act"], m["mlp"]) == ("silu", "swiglu")
    # the program's tree at these fields: the published count, and the
    # bytes the stream floor charges a decode step
    driver = runner.load_py("drivers", cfg["driver"], [runner.ROOT])
    tcfg = driver._program_config(cfg, T)
    tree = jax.eval_shape(lambda: T.init_params(tcfg, jax.random.key(0)))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert size(tree) == cfg["parameters"] == 2_667_974_657
    assert tcfg.dtype == jnp.bfloat16 and tcfg.cache_layers == 192
    sizes = cfg["decode_stream_bytes"]
    norms = sum(int(np.prod(v.shape)) for k, v in tree["blocks"].items()
                if k.endswith("_g"))
    assert sizes["layer_weights"] == 2 * size(tree["blocks"])
    assert norms == 48 * 4 * 2048
    assert sizes["head"] == 2 * size(tree["head"])
    assert sizes["kv_per_token"] == 2 * 192 * 16 * 128 * 2 == 1_572_864
    sv = cfg["serving"]
    assert (sv["num_pages"] - 1) * sv["page_size"] == sv["max_slots"] * (
        sv["max_prompt_len"] + sv["max_new_tokens"])
    # the toy twin's byte counts follow its own tree the same way
    toy = runner.load_json("configs", "ouro_toy", [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")])
    ttree = jax.eval_shape(lambda: T.init_params(
        driver._program_config(toy, T), jax.random.key(0)))
    assert toy["decode_stream_bytes"]["layer_weights"] == 2 * size(
        ttree["blocks"])
    # a program that lacks a field the file names is refused at once
    fields = [f.name for f in dataclasses.fields(T.TransformerConfig)]
    assert set(m) - {"init"} <= set(fields)
    with pytest.raises(SystemExit):
        driver._program_config(
            dict(cfg, model=dict(m, no_such_part="x")), T)
