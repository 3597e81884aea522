"""The yardstick's arithmetic: load generator, statistics, roofline,
trace reduction, and that the files under ``benchmarks/`` agree with
``BENCHMARK.json``."""

import collections
import json
import os

import pytest

from benchmarks.harness import loadgen, roofline, runner, stats, trace

REPO = os.path.dirname(runner.ROOT)
TRAFFIC = {"pool": 96, "rate_rps": 8.0,
           "prompt_len": {"median": 256, "sigma": 0.8, "min": 16, "max": 768},
           "output_len": {"median": 96, "sigma": 0.6, "min": 16, "max": 256}}


def test_loadgen_is_a_pure_function_of_the_seed():
    a = loadgen.make_requests(TRAFFIC, 2**31 + 12345, 50257)
    b = loadgen.make_requests(TRAFFIC, 2**31 + 12345, 50257)
    c = loadgen.make_requests(TRAFFIC, 7, 50257)
    assert a == b and a != c
    assert loadgen.arrival_times(TRAFFIC, 5, 50) == \
        loadgen.arrival_times(TRAFFIC, 5, 50)


def test_every_seed_gets_the_same_sizes_in_another_order():
    def sizes(seed):
        reqs = loadgen.make_requests(TRAFFIC, seed, 50257)
        return (collections.Counter(len(r["prompt"]) for r in reqs),
                collections.Counter(r["max_new_tokens"] for r in reqs),
                [len(r["prompt"]) for r in reqs])
    p1, o1, order1 = sizes(1)
    p2, o2, order2 = sizes(2)
    assert p1 == p2 and o1 == o2 and order1 != order2
    assert min(p1) >= 16 and max(p1) <= 768 and max(o1) <= 256
    g1 = loadgen.arrival_times(TRAFFIC, 1, 400)
    g2 = loadgen.arrival_times(TRAFFIC, 2, 400)
    assert g1 != g2
    # the same gaps in another order: the same last arrival, mean 1/rate
    assert g1[-1] == pytest.approx(g2[-1], rel=1e-9)
    assert g1[-1] / 400 == pytest.approx(1 / 8.0, rel=1e-9)
    assert all(b > a for a, b in zip(g1, g1[1:]))


def test_tail_needs_ten_samples_beyond_it():
    assert stats.tail(range(199), 0.95) == (None, 199)
    v, n = stats.tail(range(201), 0.95)
    assert n == 201 and v == pytest.approx(190.0)
    assert stats.median([3, 1, 2]) == 2
    assert stats.iqr_share([10, 11, 12, 13, 14, 15]) == pytest.approx(
        (14.25 - 10.75) / 12.5)


PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_roofline_of_two_hand_computed_layers():
    # res2 3x3: 256 x 56 x 56 x 64 -> 64, stride 1, pad 1, bf16
    c = roofline.conv2d_call(256, 56, 56, 64, 64, 3, 1, 1)
    assert c["flops"] == 2 * 256 * 56 * 56 * 9 * 64 * 64 == 59190018048
    assert c["bytes"] == (2 * 256 * 56 * 56 * 64 * 2) + 9 * 64 * 64 * 2
    t, which = roofline.min_seconds(c["flops"], c["bytes"], PEAK)
    assert which == "compute" and t == pytest.approx(59190018048 / 197e12)
    # res2 first 1x1: 64 -> 64 at 56 x 56: memory bound
    c = roofline.conv2d_call(256, 56, 56, 64, 64, 1, 1, 0)
    assert c["flops"] == 2 * 256 * 56 * 56 * 64 * 64
    t, which = roofline.min_seconds(c["flops"], c["bytes"], PEAK)
    assert which == "memory"
    assert t == pytest.approx((2 * 256 * 3136 * 64 * 2 + 64 * 64 * 2) / 819e9)


def test_resnet50_table_matches_the_configuration():
    cfg = runner.load_json("configs", "resnet50", [runner.ROOT])
    rows = runner.load_py("references", cfg["reference"],
                          [runner.ROOT]).conv_table(cfg, 1)
    assert len(rows) == 53
    assert roofline.calls_floor(rows, PEAK, train=False)["flops"] == sum(
        roofline.conv2d_call(**{k: r[k] for k in (
            "n", "h", "w", "cin", "cout", "k", "stride", "pad")})["flops"]
        for r in rows)
    fwd = sum(roofline.conv2d_call(**{k: r[k] for k in (
        "n", "h", "w", "cin", "cout", "k", "stride", "pad")})["flops"]
        for r in rows) + 2 * 2048 * 1000
    assert fwd == pytest.approx(cfg["flops_per_example"]["forward"], rel=1e-3)
    assert cfg["flops_per_example"]["train"] == pytest.approx(3 * fwd,
                                                              rel=1e-3)
    assert roofline.paged_attention_bytes([100, 28], 20, 64, 36) == \
        128 * 2 * 20 * 64 * 2 * 36


@pytest.fixture(scope="module")
def small(data_root):
    with open(os.path.join(data_root, "small_trace.json")) as f:
        return json.load(f)


def test_trace_reduction_on_the_recorded_trace(small):
    win = (trace.host_marker(small, "bench:marker"),
           trace.host_marker(small, "bench:end"))
    assert win == (0, 3500)
    b = trace.busy(small, win)
    # per device: two steps of 1000 ns busy + 350 ns of the decode program
    assert b["busy_s"] == pytest.approx(2350e-9)
    assert b["window_s"] == pytest.approx(3500e-9)
    assert trace.idle_pct(small, win) == pytest.approx(100 * (1 - 2350 / 3500))
    m = trace.module_ms(small, "jit_step", win)
    assert m["count"] == 2 and m["total_ms"] == pytest.approx(2000e-6)
    k = trace.kernel_seconds(small, "_kernel", win, inside="jit_step")
    assert k["calls"] == 4 and k["seconds"] == pytest.approx(1200e-9)
    k = trace.kernel_seconds(small, "_kernel", win, inside="jit_decode")
    assert k["calls"] == 1 and k["seconds"] == pytest.approx(100e-9)
    assert trace.kernel_seconds(small, "_kernel", win)["calls"] == 5
    c = trace.exposed_collective_s(small, win)
    assert c["collective_s"] == pytest.approx(600e-9)
    assert c["exposed_s"] == pytest.approx(400e-9)   # 200 of each 300 alone
    top = trace.top_ops(small, win, top=2)
    assert top[0][0] == "_conv_kernel.1" and top[0][1] == pytest.approx(800e-9)
    gaps = trace.idle_gaps(small, [("feed", 1000, 1400), ("fence", 1400, 1500),
                                   ("loop", 2500, 3050)], win)
    named = dict(gaps)
    assert named["feed"] == pytest.approx(500e-9)      # whole gap -> widest cover
    assert named["loop"] == pytest.approx(550e-9)
    # cutting the window cuts the events
    assert trace.busy(small, (0, 500))["busy_s"] == pytest.approx(495e-9)


def _reduce(metric, layer, config, roots, chips=1):
    run = runner.Run(workload="test", cell={}, config=config, seed=0,
                     seconds=1.0, trace=True, roots=roots, on_chip=False,
                     proc_t0=0.0, chips=chips, peak=PEAK)
    spec = runner.load_json("layer_metrics", metric, roots)
    return runner.load_py("reducers", spec["reducer"], roots).reduce(
        spec, layer, run)


def test_roofline_share_finds_its_floor_function_by_name(small, data_root):
    """``kernels/<family>.json`` names ``kernels/<floor.fn>.py``; a family
    and a floor function that exist only under tests/data are found."""
    roots = [runner.ROOT, data_root]
    win = (trace.host_marker(small, "bench:marker"),
           trace.host_marker(small, "bench:end"))
    cfg = runner.load_json("configs", "mlp_toy", roots)
    layer = {"profile": small, "profile_window": win, "batch": 8, "steps": 2}
    got = _reduce("gemm_roofline_pct.train", layer, cfg, roots)
    # both products are memory bound: bf16 operands and results, once each
    nbytes = (8 * 16 + 16 * 32 + 8 * 32) * 2 + (8 * 32 + 32 * 4 + 8 * 4) * 2
    assert got == pytest.approx(100 * 2 * nbytes / 819e9 / 1200e-9)
    # nothing to read (no trace): nothing returned
    assert _reduce("gemm_roofline_pct.train", {"batch": 8}, cfg, roots) is None


def test_conv_roofline_share_of_the_recorded_step(data_root):
    """The ResNet-50 conv family on the step the v5e recorded: 53 Mosaic
    calls against the forward floor of the configuration's conv table."""
    import gzip

    with gzip.open(os.path.join(data_root, "recorded_step.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    win = (trace.host_marker(rec, "bench:marker"),
           trace.host_marker(rec, "bench:end"))
    cfg = runner.load_json("configs", "resnet50", [runner.ROOT])
    got = _reduce("conv_roofline_pct.train",
                  {"profile": rec, "profile_window": win, "batch": 256},
                  cfg, [runner.ROOT])
    assert 3.5 < got < 4.3      # 16.4 ms of floor over 420 ms traced


def test_files_agree_with_BENCHMARK_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    roots = [runner.ROOT]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    per = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in bench["configs"]:
        cfg = runner.load_json("configs", c["name"], roots)
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert cfg["reduced"] == c["reduced"]
        runner.find("drivers", cfg["driver"], ".py", roots)
        runner.find("references", cfg["reference"], ".py", roots)
    for name, w in cells.items():
        cell = runner.load_json("workloads", name, roots)
        assert (cell["config"], cell["chips"], cell["traffic_name"],
                cell["why"]) == (w["config"], w["chips"], w["traffic"],
                                 w["why"])
        assert "setup_s" in cell["end_to_end"]
        for m in cell["end_to_end"]:
            spec = runner.load_json("end_to_end", m, roots)
            assert (spec["unit"], spec["better"]) == (e2e[m]["unit"],
                                                      e2e[m]["better"])
            assert name in e2e[m].get("workloads", cells)
        for m in cell["per_layer"]:
            spec = runner.load_json("layer_metrics", m, roots)
            assert (spec["unit"], spec["layer"], spec["moves"],
                    spec["source"]) == (per[m]["unit"], per[m]["layer"],
                                        per[m]["moves"], per[m]["source"])
            assert spec["moves"] in cell["end_to_end"]
            assert name in per[m].get("workloads", cells)
            runner.find("reducers", spec["reducer"], ".py", roots)
            if "family" in spec["args"]:
                runner.load_json("kernels", spec["args"]["family"], roots)
    # a metric without a `workloads` key is reported by every cell that
    # reports the end-to-end metric it moves
    for m, spec in per.items():
        for name in spec.get("workloads") or [
                n for n in cells if spec["moves"] in runner.load_json(
                    "workloads", n, roots)["end_to_end"]]:
            assert m in runner.load_json("workloads", name,
                                         roots)["per_layer"], (m, name)
    with open(os.path.join(runner.ROOT, "peaks.json")) as f:
        assert json.load(f)["TPU v5 lite"]["flops_per_s"] == 197e12


def test_trace_reduction_on_a_step_recorded_on_the_chip(data_root):
    """One ResNet-50 batch-256 train step as the v5e's profiler wrote it
    (names are whole HLO texts; kernels are Mosaic custom calls)."""
    import gzip

    with gzip.open(os.path.join(data_root, "recorded_step.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    win = (trace.host_marker(rec, "bench:marker"),
           trace.host_marker(rec, "bench:end"))
    fam = runner.load_json("kernels", "resnet_conv", [runner.ROOT])
    m = trace.module_ms(rec, "jit_step", win)
    assert m["count"] == 1 and 570 < m["total_ms"] < 590
    k = trace.kernel_seconds(rec, fam["pattern"], win, fam["inside"])
    assert k["calls"] == fam["calls_per_step"] == 53
    assert 0.41 < k["seconds"] < 0.43
    assert 0 < trace.idle_pct(rec, win) < 2
    name, secs = trace.top_ops(rec, win, 1)[0]
    assert "custom-call[tpu_custom_call]" in name and "112,112,64" in name
    assert 0.29 < secs < 0.30          # the 7x7 stem: half of the step
    assert trace.exposed_collective_s(rec, win)["collective_s"] == 0


def _idle_gaps_oracle(profile, host_spans=(), window=None, top=10):
    """``trace.idle_gaps`` as it was until PR 41: every span for every
    gap, the device chosen by a ``busy`` a device.  Kept as the oracle."""
    window = window or trace.span_of(profile)
    dev = min(profile["devices"],
              key=lambda d: trace.busy(profile, window)["per_device_s"][d])
    lines = profile["devices"][dev]
    merged = trace._union((s, e) for _, s, e in trace._clip(
        lines.get(trace.OPS) or lines.get(trace.MODULES) or [], window))
    gaps, cur = [], window[0]
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if window[1] > cur:
        gaps.append((cur, window[1]))
    named = {}
    for gs, ge in gaps:
        best, cover = "host:untraced", 0
        for name, ss, se in host_spans:
            c = min(ge, se) - max(gs, ss)
            if c > cover:
                best, cover = name, c
        named[best] = named.get(best, 0.0) + (ge - gs) / 1e9
    return [[k, v] for k, v in sorted(named.items(),
                                      key=lambda kv: -kv[1])[:top]]


def _random_profile(rng, devices, ops):
    """Operations of 1-40 ns with gaps of 0-200 ns between them; host
    spans of 0-400 ns on up to three threads (none overlap on a thread,
    any may across threads), many of equal length, handed over shuffled."""
    devs = {}
    for d in range(devices):
        t, evs = rng.randrange(50), []
        for i in range(ops):
            dur = rng.randrange(1, 40)
            evs.append([f"op.{i % 7}", t, dur])
            t += dur + rng.choice([0, 0, 0, 1, 5, 30, 200])
        devs[str(d)] = {trace.OPS: evs}
    spans = []
    for th in range(rng.choice([1, 2, 3])):
        t = rng.randrange(100)
        for _ in range(rng.randrange(0, 60)):
            dur = rng.choice([0, 1, 10, 50, 50, 400])
            spans.append((f"{rng.choice('abc')}@{th}", t, t + dur))
            t += dur + rng.choice([0, 0, 3, 50])
    rng.shuffle(spans)
    return {"devices": devs, "host": []}, spans


@pytest.mark.parametrize("devices", [1, 4])
def test_idle_gaps_names_and_seconds_are_the_quadratic_scans(devices):
    import random

    rng = random.Random(41 + devices)
    seen = set()
    for case in range(150):
        prof, spans = _random_profile(rng, devices, rng.randrange(1, 200))
        if case % 7 == 0:
            spans = []
        lo, hi = trace.span_of(prof)
        win = None if case % 3 == 0 else (lo + rng.randrange(-20, 50),
                                          hi - rng.randrange(-20, 50))
        want = _idle_gaps_oracle(prof, spans, win)
        assert trace.idle_gaps(prof, spans, win) == want, case
        seen.update(n for n, _ in want)
    # gaps under no span, and spans of every thread, did occur
    assert "host:untraced" in seen and {"a@0", "b@1", "c@2"} <= seen


def test_idle_gaps_of_equal_covers_takes_the_first_span_handed_over():
    prof = {"devices": {"0": {trace.OPS: [["a", 0, 10], ["b", 110, 10]]}},
            "host": []}
    spans = [("late", 5, 200), ("first", 0, 300), ("second", 10, 110)]
    assert trace.idle_gaps(prof, spans) == _idle_gaps_oracle(prof, spans) \
        == [["late", 100e-9]]
    assert trace.idle_gaps(prof, spans[1:])[0][0] == "first"
    assert trace.idle_gaps(prof, [("half", 10, 60)] + spans[1:])[0][0] \
        == "first"


def test_idle_gaps_is_near_linear_in_gaps_and_spans():
    """200k gaps x 1.2k spans (a traced `zaya1_serve_closed64` run holds
    657k x 1.2k, which took the quadratic scan 3 min of a 5 min watchdog)."""
    import time

    step, n = 4500, 200_000
    prof = {"devices": {"0": {trace.OPS: [
        ["%fusion." + str(i % 500), i * step, 3000] for i in range(n)]}},
        "host": []}
    width = n * step // 1200
    spans = [("serve_decode@loop", i * width, (i + 1) * width - 10)
             for i in range(1200)]
    t0 = time.perf_counter()
    got = trace.idle_gaps(prof, spans)
    assert time.perf_counter() - t0 < 5.0
    assert got[0][0] == "serve_decode@loop"
    assert got[0][1] == pytest.approx((n - 1) * 1500e-9, rel=1e-3)


@pytest.mark.parametrize("window, total_ns, count", [
    # five calls of 900-1100 ns every 1500; the window's ends cut the first
    # and the last in half: the 3 whole ones + 1000 ns in units of their mean
    ((500, 6500), 4000, 4.0),
    ((0, 7000), 5000, 5),             # none cut
    ((1200, 6500), 3500, 3.5),        # an end in a pause cuts no call
    (None, 5000, 5)])
def test_module_ms_counts_a_cut_call_by_its_time_inside(window, total_ns,
                                                        count):
    prof = {"devices": {"0": {trace.MODULES: [
        ["jit_decode(7)", 1500 * i, d]
        for i, d in enumerate((1000, 900, 1000, 1100, 1000))] + [
        ["jit_prefill(3)", 1100, 300]]}}, "host": []}
    m = trace.module_ms(prof, "jit_decode", window)
    assert m["total_ms"] == pytest.approx(total_ns / 1e6)
    assert m["count"] == pytest.approx(count)
    # time over count is the mean of the calls that lie whole inside,
    # whatever the ends cut; counting the two halves as calls read 800
    assert m["total_ms"] / m["count"] == pytest.approx(1000 / 1e6)
    if window == (500, 6500):
        # the profile's own end cut the last call short as well (a profile
        # ends within milliseconds of the window): its length is not used
        prof["devices"]["0"][trace.MODULES][4][2] = 600
        m = trace.module_ms(prof, "jit_decode", window)
        assert m["total_ms"] / m["count"] == pytest.approx(1000 / 1e6)
        # nothing lies whole inside: the calls are counted as they come
        m = trace.module_ms(prof, "jit_decode", (1600, 2300))
        assert m["count"] == 1 and m["total_ms"] == pytest.approx(700e-6)
