"""``chip_smoke.py`` off the chip: its phases at toy widths on the CPU
(sizes overridden HERE, not through a program option), its refusal to
pass without a TPU, and the pieces it stands on — places that raise,
the compile-cache placement, the per-shard data-parallel lowering."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _smallnet_cost():
    from paddle_tpu.models import image

    return image.smallnet_cost()[0]


TOY = chip_smoke.Sizes(
    train_cost=_smallnet_cost, train_image_dim=32 * 32 * 3, train_classes=10,
    train_batch=8, train_steps=3,
    vocab=256, layers=2, heads=2, embed=64, mlp=128, max_seq_len=128,
    attn_block=64, slots=4, page_size=16, num_pages=64, max_prompt_len=64,
    prompt_lens=(10, 50), new_tokens=6, requests=5,
    kernel_shapes=dict(
        ctc_loss_fused=(4, 9, 7, 3), ctc_greedy_decode_fused=(5, 11, 6),
        fused_momentum_update=(3, 3, 4, 8), embedding_gather=(20, 8, 13),
        lstm_seq=(4, 6, 8), gru_seq=(4, 6, 8), kda_prefill=(1, 32, 2, 16),
        grouped_matmul=(40, 5, 16, 128), ssd_step=(3, 3, 4, 8, 128, 2),
        decode_attention_ring=(3, 2, 64, 13, 16, 4)),
    model_shapes=dict(
        lstm=(4, 6, 8, 50), nmt=(3, 5, 8, 40),
        ctr=(8, 30, 12, 3, 4, (8, 4)), crnn=(4, 32, 32, 3, 10),
        transformer=(2, 16)),
    multi_steps=2, replicas=4)


def test_real_script_fails_without_a_tpu():
    """The script as the driver runs it, on this CPU-only box: non-zero
    exit, and a last line that says so in the contract's schema."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": last["device"]["count"]}
    assert "no TPU" in out.stderr


def test_last_line_schema(capsys, monkeypatch):
    """On success the last line is exactly {"ok": true, "device":
    {platform, kind, count}} — checked with the phases stubbed out."""
    monkeypatch.setattr(chip_smoke, "run",
                        lambda chips, only, sz, report: report.update(
                            device=chip_smoke.device_report()))
    assert chip_smoke.main([]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last) == ["ok", "device"] and last["ok"] is True
    dev = jax.devices()[0]
    assert last["device"] == {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())}


def test_tpu_place_raises_without_a_tpu():
    from paddle_tpu.core.place import CPUPlace, TPUPlace, default_place

    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        TPUPlace().device()
    assert CPUPlace().device().platform == "cpu"
    assert isinstance(default_place(), CPUPlace)  # a choice, not a disguise


def test_import_does_not_initialise_a_backend():
    """A launcher parent imports the package and must leave the chip to
    its children: importing never calls ``jax.devices()``."""
    code = ("import paddle_tpu, paddle_tpu.distributed.launch\n"
            "import jax._src.xla_bridge as xb\n"
            "assert not xb.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


def test_compile_cache_placement(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.core import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: the helper sets nothing in code
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        # not placed: one fixed path inside the checkout
        monkeypatch.delenv(compile_cache.ENV_VAR)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.configure() == want  # never a fresh name
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    """A TPU whose device_kind has no published peak / profile on file
    fails loudly instead of borrowing the CPU testbed's numbers."""
    from paddle_tpu import profiler
    from paddle_tpu.analysis import hw_profile

    class Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v5 lite")])
    assert profiler.device_peak_flops() == 197e12
    assert hw_profile("auto").name == "v5e"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        profiler.device_peak_flops()
    with pytest.raises(ValueError, match="TPU v99"):
        hw_profile("auto")


def test_interpret_is_refused_on_a_tpu_backend(monkeypatch):
    from paddle_tpu.ops import pallas

    assert pallas.resolve_interpret(None) is True      # CPU: interpreter
    assert pallas.resolve_impl("auto") == "reference"
    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    assert pallas.resolve_interpret(None) is False
    assert pallas.resolve_impl("auto") == "kernel"
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        pallas.resolve_interpret(True)


def test_train_phase_toy():
    out = chip_smoke.phase_train(TOY, chip_smoke.CacheCounter())
    assert len(out["losses"]) == TOY.train_steps
    assert out["kernels"] == {}  # CPU: the references, no Mosaic call


def test_serve_phase_toy():
    assert chip_smoke.phase_serve(TOY) == {"diverged": 0}


def test_serve_mismatch_must_be_a_near_tie():
    """The token comparison accepts a mismatch only when the two
    candidates' logits are within the stated tolerance."""
    cfg, params = chip_smoke._serve_model(TOY)
    prompts = chip_smoke._prompts(TOY)[:1]
    want = [[1, 2, 3]]
    with pytest.raises(chip_smoke.SmokeFailure, match="not a near-tie"):
        chip_smoke._compare_tokens(
            chip_smoke.dataclasses.replace(TOY, serve_logit_tol=0.0),
            cfg, params, prompts, [[1, 9, 3]], want, "t")
    loose = chip_smoke.dataclasses.replace(TOY, serve_logit_tol=1e9)
    assert chip_smoke._compare_tokens(loose, cfg, params, prompts,
                                      [[1, 9, 3]], want, "t") == 1


def test_kernels_phase_toy(monkeypatch):
    """The comparison machinery on a few cheap cases, in interpret mode
    (every kernel's own parity test lives with the kernel)."""
    names = ("ctc_loss_fused", "ctc_greedy_decode_fused",
             "decode_attention[ring]", "kda_prefill", "ssd_step",
             "grouped_matmul", "fused_momentum_update", "embedding_gather")
    rows = chip_smoke.phase_kernels(TOY, names=names, interpret=True)["rows"]
    assert [r["kernel"] for r in rows] == list(names)
    assert all(r["pass"] for r in rows)
    # a bound the kernel cannot meet fails the phase, naming the kernel
    tight = [chip_smoke.dataclasses.replace(c, tol=-1.0)
             for c in chip_smoke._kernel_cases()
             if c.name == "fused_momentum_update"]
    monkeypatch.setattr(chip_smoke, "_kernel_cases", lambda: tight)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="fused_momentum_update"):
        chip_smoke.phase_kernels(TOY, interpret=True)


@pytest.mark.parametrize("name", chip_smoke.MODELS)
def test_models_phase_toy(name):
    """``--only models`` off the chip: each family's builder (the lifted
    ``lstm_classify_cost``, ``_topology_step``) takes two steps."""
    (row,) = chip_smoke.phase_models(TOY, names=(name,))["rows"]
    assert row["ok"] and len(row["losses"]) == 2  # ok = both finite


def test_multichip_phase_toy():
    """The --chips 4 phase on the forced host devices: one-device vs DP
    vs ZeRO-2 losses, state on every device, collectives in the compiled
    step, one fleet replica per device with identical tokens."""
    out = chip_smoke.phase_multichip(TOY)
    n = len(jax.devices())
    assert out["runs"][f"dp{n}"]["collectives"]["all-reduce"] > 0
    assert out["runs"][f"dp{n}-zero2"]["collectives"]["reduce-scatter"] > 0
    assert out["fleet_diverged"] == 0


def test_per_shard_forward_matches_gspmd_at_zero0():
    """On a TPU the data-parallel step runs forward/backward per shard
    inside shard_map at every ZeRO stage (GSPMD cannot partition Mosaic
    kernels).  Pinned here with lowering="explicit" at zero=0: same
    trajectory as the GSPMD lowering, all-reduce in the program."""
    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core import rng
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import base, data_type
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.parallel.mesh import MeshContext, make_mesh
    from paddle_tpu.trainer.step import build_train_step

    mesh = MeshContext(make_mesh({"data": 4}, devices=jax.devices()[:4]))
    data = np.random.default_rng(0)
    feed = mesh.shard_batch({
        "x": data.normal(size=(16, 8)).astype(np.float32),
        "label": data.integers(0, 4, size=(16,)).astype(np.int32)})

    def run(lowering):
        base.reset_name_counters()
        rng.seed(7)
        x = layer.data(name="x", type=data_type.dense_vector(8))
        h = layer.fc(input=x, size=16, act=act.ReluActivation())
        p = layer.fc(input=h, size=4, act=act.SoftmaxActivation())
        lbl = layer.data(name="label", type=data_type.integer_value(4))
        topo = Topology(layer.classification_cost(input=p, label=lbl))
        opt = Momentum(momentum=0.9, learning_rate=0.05)
        params = mesh.replicate(paddle.parameters.create(topo).as_dict())
        state = mesh.replicate(opt.init(
            params, {s.name: s for s in topo.param_specs()}))
        states = mesh.replicate(topo.init_states())
        step = build_train_step(topo, opt, mesh, zero=0, lowering=lowering)
        text = step.lower(params, state, states, feed,
                          jax.random.key(0)).compile().as_text()
        costs = []
        for _ in range(3):
            params, state, states, cost, _ = step(
                params, state, states, feed, jax.random.key(0))
            costs.append(float(cost))
        return costs, {k: np.asarray(v) for k, v in params.items()}, text

    costs_e, params_e, text_e = run("explicit")
    costs_g, params_g, _ = run("gspmd")
    assert "all-reduce" in text_e
    np.testing.assert_allclose(costs_e, costs_g, rtol=1e-5)
    for k in params_g:
        np.testing.assert_allclose(params_e[k], params_g[k], rtol=1e-4,
                                   atol=1e-6)
