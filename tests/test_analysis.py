"""graftlint (paddle_tpu/analysis) — the static-analysis suite.

Three layers of coverage:

1. the repo-wide gate: every codebase pass over the actual tree must
   come up clean modulo the checked-in baseline (this is the tier-1
   enforcement of the suite — a regression anywhere in the repo fails
   HERE with the finding id);
2. seeded-defect fixtures: for each pass, a tiny module/program with
   exactly one planted violation asserts the pass fires exactly once
   with its stable ID, plus a clean twin asserting no false positive;
3. the ``trainer --preflight`` CLI: clean configs exit 0; the
   ``preflight_inject`` flag's seeded host-sync and collective-mismatch
   defects exit 1 through the real CLI (including the ZeRO-2 dual-
   lowering comparison on the forced 8-device mesh).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- 1. the repo-wide gate ------------------------------------------------------


def test_repo_wide_suite_clean():
    from paddle_tpu.analysis import (
        apply_baseline,
        load_baseline,
        run_codebase,
    )

    findings = run_codebase()
    unsup, sup, stale = apply_baseline(findings, load_baseline())
    assert not unsup, "unsuppressed findings:\n" + "\n".join(
        f.render() for f in unsup)
    assert not stale, f"stale baseline suppressions: {stale}"
    # the baseline documents the canonical telemetry guards — if it
    # goes empty the suppression machinery itself is untested
    assert sup, "expected the baselined telemetry guards to match"


def test_analysis_cli_exits_zero():
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_lint_changed_mode_runs():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--changed"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr


def test_lock_registry_covers_threaded_subsystems():
    from paddle_tpu.analysis import lock_registry

    reg = lock_registry()
    # _pump: one iteration (or drain) of the step loop at a time
    assert reg["paddle_tpu/serving/engine.py"]["ServingEngine"] == [
        "_lock", "_pump"]
    assert "_mesh_lock" in \
        reg["paddle_tpu/reader/prefetch.py"]["DevicePrefetcher"]
    assert reg["paddle_tpu/resilience/elastic.py"]["ElasticCoordinator"] \
        == ["_lock"]
    assert reg["paddle_tpu/trainer/checkpoint.py"]["AsyncCheckpointer"] \
        == ["_lock"]
    # the serving-fleet threads (PR 11) ride the same audit: the router
    # runs a pump thread, so its books live under declared locks; the
    # replica/health modules are registered (thread-free today — a
    # thread added later is audited the moment it appears)
    assert reg["paddle_tpu/serving/router.py"]["FleetRouter"] \
        == ["_lock", "_pump_lock"]
    from paddle_tpu.analysis.codebase import THREADED_MODULES

    assert "paddle_tpu/serving/fleet.py" in THREADED_MODULES
    assert "paddle_tpu/serving/health.py" in THREADED_MODULES


# -- 2. codebase-pass fixtures --------------------------------------------------


def _corpus(tmp_path, rel, src):
    from paddle_tpu.analysis.codebase import iter_corpus

    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    return iter_corpus(str(tmp_path), files=[rel])


def test_swallow_except_fires_once_with_stable_id(tmp_path):
    from paddle_tpu.analysis.codebase import pass_swallow_except

    corpus = _corpus(tmp_path, "paddle_tpu/mod.py", """\
        import logging
        log = logging.getLogger(__name__)

        def silent():
            try:
                risky()
            except Exception:
                pass            # the planted defect

        def logged():
            try:
                risky()
            except Exception as e:
                log.warning("failed: %s", e)

        def narrow():
            try:
                risky()
            except (OSError, ValueError):
                pass

        def propagated(q):
            try:
                risky()
            except Exception as e:
                q.put(e)
        """)
    found = pass_swallow_except(corpus, str(tmp_path))
    assert len(found) == 1, [f.fid for f in found]
    assert found[0].fid == "GL-EXCEPT:paddle_tpu/mod.py:silent"


def test_swallow_except_clean_fixture_negative(tmp_path):
    from paddle_tpu.analysis.codebase import pass_swallow_except

    corpus = _corpus(tmp_path, "paddle_tpu/mod.py", """\
        def f():
            try:
                risky()
            except Exception:
                raise RuntimeError("wrapped")
        """)
    assert pass_swallow_except(corpus, str(tmp_path)) == []


def test_env_pass_fires_on_unregistered_read(tmp_path):
    from paddle_tpu.analysis.codebase import pass_env_registration

    corpus = _corpus(tmp_path, "paddle_tpu/mod.py", """\
        import os
        A = os.environ.get("PADDLE_TPU_NOT_A_FLAG")     # planted
        B = os.environ.get("PADDLE_TPU_ZERO")           # flag override
        C = os.environ.get("JAX_PLATFORMS")             # declared env
        D = os.environ.get(dynamic_name)                # non-literal: skip
        """)
    found = pass_env_registration(corpus, str(tmp_path))
    assert [f.fid for f in found] == \
        ["GL-ENV:paddle_tpu/mod.py:<module>"]
    assert "PADDLE_TPU_NOT_A_FLAG" in found[0].message


def test_env_pass_clean_fixture_negative(tmp_path):
    from paddle_tpu.analysis.codebase import pass_env_registration

    corpus = _corpus(tmp_path, "paddle_tpu/mod.py", """\
        import os
        B = os.getenv("PADDLE_TPU_SEED")
        os.environ["PADDLE_TPU_WHATEVER"] = "writes are the launcher's"
        """)
    assert pass_env_registration(corpus, str(tmp_path)) == []


def test_schema_pass_fires_on_unknown_kind(tmp_path):
    from paddle_tpu.analysis.codebase import pass_schema_kinds

    corpus = _corpus(tmp_path, "paddle_tpu/mod.py", """\
        def a(reg):
            reg.emit({"x": 1}, kind="good")

        def b(reg):
            rec = {"kind": "planted_bad", "x": 1}
            reg.emit(dict(rec))

        LAYER_ATTR = {"kind": "embedding"}   # never emitted: not a record
        """)
    found = pass_schema_kinds(corpus, str(tmp_path),
                              known=frozenset({"good"}))
    assert len(found) == 1, [f.fid for f in found]
    assert found[0].fid == "GL-SCHEMA:paddle_tpu/mod.py:b"
    assert "planted_bad" in found[0].message


def test_schema_pass_reports_stale_registered_kind(tmp_path):
    from paddle_tpu.analysis.codebase import pass_schema_kinds

    corpus = _corpus(tmp_path, "paddle_tpu/mod.py", """\
        def a(reg):
            reg.emit({"x": 1}, kind="good")
        """)
    found = pass_schema_kinds(corpus, str(tmp_path),
                              known=frozenset({"good", "never_made"}))
    assert len(found) == 1
    assert "never_made" in found[0].message


_THREAD_FIXTURE = """\
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._state = None
            self._t = threading.Thread(target=self._work)

        def _work(self):
            {worker_body}

        def read(self):
            {consumer_body}
    """


def test_thread_pass_fires_on_unlocked_cross_thread_attr(tmp_path):
    from paddle_tpu.analysis.codebase import pass_thread_safety

    rel = "paddle_tpu/fix_thread.py"
    corpus = _corpus(tmp_path, rel, _THREAD_FIXTURE.format(
        worker_body="self._state = 1    # planted: no lock",
        consumer_body="return self._state"))
    found = pass_thread_safety(corpus, str(tmp_path), modules=(rel,))
    assert [f.fid for f in found] == \
        [f"GL-THREAD:{rel}:Worker._state"]


def test_thread_pass_clean_when_locked(tmp_path):
    from paddle_tpu.analysis.codebase import pass_thread_safety

    rel = "paddle_tpu/fix_thread.py"
    corpus = _corpus(tmp_path, rel, _THREAD_FIXTURE.format(
        worker_body="""
            with self._lock:
                self._state = 1""",
        consumer_body="""
            with self._lock:
                return self._state"""))
    assert pass_thread_safety(corpus, str(tmp_path), modules=(rel,)) == []


def test_lock_order_cycle_detected(tmp_path):
    from paddle_tpu.analysis.codebase import pass_lock_order

    rel = "paddle_tpu/fix_locks.py"
    corpus = _corpus(tmp_path, rel, """\
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self._t = threading.Thread(target=self._work)

            def _work(self):
                with self._a:
                    with self._b:       # a -> b
                        pass

            def other(self):
                with self._b:
                    with self._a:       # b -> a: the planted cycle
                        pass
        """)
    found = pass_lock_order(corpus, str(tmp_path), modules=(rel,))
    assert [f.fid for f in found] == [f"GL-LOCKORDER:{rel}:TwoLocks"]
    assert "_a" in found[0].message and "_b" in found[0].message


def test_lock_order_clean_when_consistent(tmp_path):
    from paddle_tpu.analysis.codebase import pass_lock_order

    rel = "paddle_tpu/fix_locks.py"
    corpus = _corpus(tmp_path, rel, """\
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def other(self):
                with self._a:
                    with self._b:
                        pass
        """)
    assert pass_lock_order(corpus, str(tmp_path), modules=(rel,)) == []


def test_kernel_parity_pass_fires_without_reference_twin(tmp_path):
    from paddle_tpu.analysis.kernel_parity import kernel_parity_findings

    pallas = tmp_path / "paddle_tpu" / "ops" / "pallas"
    pallas.mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (pallas / "badkernel.py").write_text(textwrap.dedent("""\
        def fused_op(x):
            return pallas_call(x)   # planted: no jnp reference twin
        """))
    found = kernel_parity_findings(str(tmp_path))
    assert [f.fid for f in found] == \
        ["GL-KERNEL:paddle_tpu/ops/pallas/badkernel.py:<module>"]
    # add the twin + a parity test: the pass goes quiet
    (pallas / "badkernel.py").write_text(textwrap.dedent("""\
        def fused_op(x):
            return pallas_call(x)

        def fused_op_reference(x):
            return x
        """))
    (tmp_path / "tests" / "test_parity.py").write_text(
        "# fused_op vs fused_op_reference interpret-mode parity\n")
    assert kernel_parity_findings(str(tmp_path)) == []


def test_stable_ids_survive_line_drift(tmp_path):
    from paddle_tpu.analysis.codebase import pass_swallow_except

    body = """\
        def silent():
            try:
                risky()
            except Exception:
                pass
        """
    a = pass_swallow_except(_corpus(tmp_path, "paddle_tpu/mod.py", body),
                            str(tmp_path))
    shifted = "# one\n# two\n# three\n" + textwrap.dedent(body)
    b = pass_swallow_except(_corpus(tmp_path, "paddle_tpu/mod.py", shifted),
                            str(tmp_path))
    assert a[0].fid == b[0].fid
    assert a[0].line != b[0].line


# -- 2b. program-pass fixtures --------------------------------------------------


def test_host_sync_pass_fires_on_injected_callback():
    import jax

    from paddle_tpu.analysis import host_sync_pass

    def dirty(x):
        jax.debug.callback(lambda: None)
        return x * 2

    found = host_sync_pass(dirty, 1.0, name="p", sync_period=8)
    assert [f.fid for f in found] == ["GL-P-SYNC:<program:p>:debug_callback"]
    assert "sync_period=8" in found[0].message

    def clean(x):
        return x * 2

    assert host_sync_pass(clean, 1.0, name="p") == []


def test_recompile_pass_shape_and_dtype_churn():
    from paddle_tpu.analysis import recompile_hazard_pass

    base = (("x", (32, 64), "float32"), ("y", (32,), "int32"))

    def with_batch(n):
        return (("x", (n, 64), "float32"), ("y", (n,), "int32"))

    # full batch + one tail = the expected ceiling: clean
    assert recompile_hazard_pass([with_batch(32), with_batch(8)]) == []
    # three dims variants of one structure: shape churn
    churn = recompile_hazard_pass(
        [with_batch(32), with_batch(31), with_batch(30)])
    assert any(f.anchor == "shape-churn" for f in churn)
    # dtype flip
    flipped = (("x", (32, 64), "float64"), ("y", (32,), "int32"))
    dt = recompile_hazard_pass([base, flipped])
    assert any(f.anchor == "dtype-churn" for f in dt)
    # signature-count ceiling
    many = [with_batch(n) for n in range(20)]
    cnt = recompile_hazard_pass(many)
    assert any(f.anchor == "signature-count" for f in cnt)


def test_donation_pass_flags_undonated_update_buffer():
    import jax
    import numpy as np

    from paddle_tpu.analysis import donation_pass

    def update(p, g):
        return p - 0.1 * g, (g * g).sum()

    a = np.zeros((64, 64), np.float32)  # 16 KiB
    undonated = jax.jit(update).lower(a, a).as_text()
    found = donation_pass(undonated, name="p", min_bytes=1 << 10)
    # one update-shaped output: exactly one donation candidate flagged
    assert [f.fid for f in found] == ["GL-P-DONATE:<program:p>:arg0"]

    donated = jax.jit(update, donate_argnums=(0,)).lower(a, a).as_text()
    assert donation_pass(donated, name="p", min_bytes=1 << 10) == []


def test_collective_sequence_extraction_and_mismatch():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import compat
    from paddle_tpu.analysis import (
        collective_sequence_from_hlo_text,
        collective_sequence_from_jaxpr,
        compare_collective_lowerings,
    )

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def body(x):
        s = jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                 tiled=True)
        return jax.lax.all_gather(s, "data", tiled=True)

    f = compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))
    seq = collective_sequence_from_jaxpr(f, jnp.ones((8,)))
    assert seq == ["reduce_scatter", "all_gather"]

    # the seeded defect: one lowering never reduces gradients
    bad = compare_collective_lowerings(
        ["reduce_scatter", "all_gather"], ["all_gather"], name="p")
    assert [f_.fid for f_ in bad] == ["GL-P-COLL:<program:p>:kind-set"]
    # class-equivalent lowerings are clean (combiner/decomposition)
    assert compare_collective_lowerings(
        ["reduce_scatter", "all_gather"],
        ["all_reduce", "all_gather"], name="p") == []
    # same-family order check
    order = compare_collective_lowerings(
        ["reduce_scatter", "all_gather"],
        ["all_gather", "reduce_scatter"], name="p", check_order=True)
    assert [f_.anchor for f_ in order] == ["order"]

    # HLO-text extraction normalizes the all-reduce+slice decomposition
    hlo = textwrap.dedent("""\
        %all-reduce.3 = f32[64]{0} all-reduce(f32[64]{0} %p), to_apply=%sum
        %ds.4 = f32[8]{0} dynamic-slice(f32[64]{0} %all-reduce.3, s32[] %i)
        %ag.5 = f32[64]{0} all-gather(f32[8]{0} %ds.4), dimensions={0}
        %use.6 = f32[64]{0} add(f32[64]{0} %ag.5, f32[64]{0} %all-reduce.3)
        """)
    assert collective_sequence_from_hlo_text(hlo) == \
        ["all_reduce", "reduce_scatter", "all_gather"]


def test_f32_upcast_pass_flags_pre_matmul_upcast():
    import jax.numpy as jnp

    from paddle_tpu.analysis import f32_upcast_pass

    x = jnp.ones((8, 16), jnp.bfloat16)
    w = jnp.ones((16, 4), jnp.bfloat16)

    def dirty(x, w):
        return (x.astype(jnp.float32) @ w.astype(jnp.float32)).sum()

    found = f32_upcast_pass(dirty, x, w, name="p")
    assert found and all(f.rule == "GL-P-UPCAST" for f in found)
    assert found[0].anchor == "dot_general"

    def clean(x, w):
        return (x @ w).astype(jnp.float32).sum()  # sanctioned: post-dot

    assert f32_upcast_pass(clean, x, w, name="p") == []


# -- 3. trainer --preflight through the real CLI --------------------------------


def _write_preflight_config(tmp_path):
    cfg = tmp_path / "digits.conf"
    cfg.write_text(textwrap.dedent("""\
        from paddle.trainer_config_helpers import *

        define_py_data_sources2(
            train_list='{d}/train.list', test_list=None,
            module='digits_provider', obj='process')
        settings(batch_size=16, learning_rate=1e-2)

        img = data_layer(name='pixel', size=64)
        hidden = fc_layer(input=img, size=32, act=ReluActivation())
        predict = fc_layer(input=hidden, size=4, act=SoftmaxActivation())
        lbl = data_layer(name='label', size=4)
        outputs(classification_cost(input=predict, label=lbl))
        """).format(d=tmp_path))
    (tmp_path / "digits_provider.py").write_text(textwrap.dedent("""\
        import numpy as np
        from paddle.trainer.PyDataProvider2 import (
            provider, dense_vector, integer_value)

        @provider(input_types={'pixel': dense_vector(64),
                               'label': integer_value(4)})
        def process(settings, filename):
            rng = np.random.default_rng(0)
            for _ in range(64):
                yield (rng.normal(size=(64,)).astype(np.float32),
                       int(rng.integers(0, 4)))
        """))
    (tmp_path / "train.list").write_text("seed-0\n")
    return str(cfg)


def _run_preflight(cfg, *extra, inject="", devices=0, jsonl=None):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_PREFLIGHT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    if inject:
        env["PADDLE_TPU_PREFLIGHT_INJECT"] = inject
    if devices:
        flag = f"--xla_force_host_platform_device_count={devices}"
        prev = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in prev:
            env["XLA_FLAGS"] = (prev + " " + flag).strip()
    cmd = [sys.executable, "-m", "paddle_tpu.trainer",
           "--config", cfg, "--preflight", *extra]
    if jsonl:
        cmd += ["--metrics_jsonl", jsonl]
    return subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=600)


def test_preflight_cli_clean_config_exits_zero(tmp_path):
    from paddle_tpu.telemetry.registry import SCHEMA

    cfg = _write_preflight_config(tmp_path)
    jsonl = str(tmp_path / "metrics.jsonl")
    out = _run_preflight(cfg, jsonl=jsonl)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "preflight: OK" in out.stdout
    # the schema/7 preflight record reached the sink
    recs = [json.loads(line) for line in open(jsonl)]
    pf = [r for r in recs if r.get("kind") == "preflight"]
    assert pf and pf[0]["clean"] is True
    assert pf[0]["schema"] == SCHEMA
    # the schema/9 GL-P-MEM memory report rode along
    mem = pf[0]["memory"]
    assert mem["params_bytes"] > 0 and mem["opt_state_bytes"] > 0
    assert mem["total_bytes"] >= mem["params_bytes"] + mem["opt_state_bytes"]
    assert mem["activation_source"] in ("jaxpr-liveness",
                                        "xla-memory-analysis")
    # the schema/13 GL-P-COST roofline rode along: predicted step_ms /
    # MFU / named bottleneck, with the matmul class carrying the FLOPs
    cost = pf[0]["cost"]
    assert cost["step_ms"] > 0 and 0 < cost["mfu_pct"] <= 100
    assert cost["bottleneck"]
    assert cost["by_class"]["matmul"]["flops"] > 0
    assert cost["flops_source"] in ("jaxpr-walk", "xla-cost-analysis")
    assert "predicted step" in out.stdout
    # and metrics_to_md renders it, budget + static-cost tables included
    md = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_to_md.py"),
         jsonl], capture_output=True, text=True)
    assert md.returncode == 0
    assert "Preflight (static analysis)" in md.stdout
    assert "Memory budget (GL-P-MEM" in md.stdout
    assert "Static cost (GL-P-COST" in md.stdout


def test_preflight_cli_catches_injected_host_sync(tmp_path):
    cfg = _write_preflight_config(tmp_path)
    out = _run_preflight(cfg, inject="host_sync")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "GL-P-SYNC" in out.stdout


def test_preflight_cli_zero2_dual_lowering_clean(tmp_path):
    cfg = _write_preflight_config(tmp_path)
    out = _run_preflight(cfg, "--zero", "2", devices=8)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "zero=2, data=8" in out.stdout


def test_preflight_cli_catches_injected_collective_mismatch(tmp_path):
    cfg = _write_preflight_config(tmp_path)
    out = _run_preflight(cfg, "--zero", "2", devices=8,
                         inject="collective_mismatch")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "GL-P-COLL" in out.stdout


def test_preflight_record_emission_in_process():
    from paddle_tpu.analysis.core import Finding
    from paddle_tpu.analysis.preflight import emit_preflight_record
    from paddle_tpu.telemetry import MemorySink, MetricsRegistry

    reg = MetricsRegistry("t")
    sink = MemorySink()
    reg.add_sink(sink)
    f = Finding("GL-P-SYNC", "<program:p>", 0, "debug_callback", "m")
    rec = emit_preflight_record([f], [], registry=reg, config="c.conf")
    assert rec["kind"] == "preflight" and rec["clean"] is False
    assert rec["by_rule"] == {"GL-P-SYNC": 1}
    assert sink.records[-1]["ids"] == [f.fid]
    assert reg.get("preflight_findings").value(rule="GL-P-SYNC") == 1.0


# -- 4. graftlint v2: memory / sharding / divergence / rng ----------------------


def test_activation_liveness_walk_counts_intermediates():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis import activation_peak_bytes

    def f(x, w):
        h = x @ w              # 32x128 f32 intermediate
        h2 = jnp.tanh(h)       # second one, while h is still live
        return (h2 * h).sum()

    x, w = jnp.ones((32, 64)), jnp.ones((64, 128))
    peak = activation_peak_bytes(jax.jit(f), x, w)
    # h and h2 (16 KiB each) overlap; the product makes a third
    assert peak >= 2 * 32 * 128 * 4
    assert peak < 1 << 20


def test_memory_budget_hbm_fires_once_with_stable_id():
    from paddle_tpu.analysis import memory_budget_pass

    report = {"zero": 0, "dp": 1, "params_bytes": 3 << 20,
              "opt_state_bytes": 6 << 20, "states_bytes": 0,
              "feed_bytes": 1 << 20, "activation_bytes": 2 << 20,
              "total_bytes": 12 << 20, "pallas_vmem": []}
    found = memory_budget_pass(report, name="p", hbm_gb=0.001)
    assert [f.fid for f in found] == ["GL-P-MEM:<program:p>:hbm-budget"]
    assert "0.013 GB" in found[0].message  # 12 MiB total named
    # generous budget and report-only mode are both clean
    assert memory_budget_pass(report, name="p", hbm_gb=16.0) == []
    assert memory_budget_pass(report, name="p", hbm_gb=0.0) == []


def test_pallas_vmem_fixture_fires_once_with_stable_id():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.analysis import (
        memory_budget_pass,
        pallas_vmem_estimates,
    )

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def big(x):  # 64 MiB in + 64 MiB out of VMEM-resident blocks
        return pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
            (4096, 4096), jnp.float32), interpret=True)(x)

    est = pallas_vmem_estimates(
        jax.make_jaxpr(big)(jnp.ones((4096, 4096), jnp.float32)))
    assert len(est) == 1 and est[0][1] == 2 * 4096 * 4096 * 4
    report = {"total_bytes": 0, "zero": 0, "dp": 1,
              "pallas_vmem": [{"kernel": k, "bytes": b} for k, b in est]}
    found = memory_budget_pass(report, name="p", vmem_mb=64.0)
    assert len(found) == 1 and found[0].rule == "GL-P-MEM"
    assert found[0].anchor.startswith("vmem:")
    # the same kernel on small blocks is clean
    assert memory_budget_pass(report, name="p", vmem_mb=256.0) == []


def test_fused_input_lstm_fits_default_vmem_budget():
    """GL-P-MEM follow-through for the persistent-recurrence kernels:
    the fused-input LSTM at the bench shapes (embed 128 -> h512, bs 64,
    T 100, bf16) must fit the default --vmem_mb 128 budget, and an
    oversized config (h4096 f32: the resident W_h alone is 256 MB) must
    fail the PREFLIGHT budget pass — not Mosaic compilation."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.analysis import memory_budget_pass, pallas_vmem_estimates
    from paddle_tpu.ops.pallas.lstm import lstm_seq_fi

    def estimates(b, t, e, d, dt):
        args = (np.zeros((b, t, e), dt), np.zeros((b, t), np.float32),
                np.zeros((e, 4 * d), dt), np.zeros((4 * d,), np.float32),
                np.zeros((d, 4 * d), dt), np.zeros((3, d), dt),
                np.zeros((b, d), dt), np.zeros((b, d), np.float32))
        est = pallas_vmem_estimates(
            lambda *a: lstm_seq_fi(*a, False, True, True), *args)
        assert est, "no pallas_call found in the fused-input LSTM trace"
        return {"total_bytes": 0, "zero": 0, "dp": 1,
                "pallas_vmem": [{"kernel": k, "bytes": v} for k, v in est]}

    bench = estimates(64, 100, 128, 512, jnp.bfloat16)
    assert memory_budget_pass(bench, name="lstm_fi", vmem_mb=128.0) == []

    big = estimates(64, 100, 128, 4096, jnp.float32)
    found = memory_budget_pass(big, name="lstm_fi", vmem_mb=128.0)
    assert len(found) == 1 and found[0].rule == "GL-P-MEM"
    assert found[0].anchor == "vmem:_fwd_fi_kernel"


def test_opt_state_bytes_agree_with_zero_census():
    """Static GL-P-MEM param+opt accounting vs the runtime census on a
    forced-8-device mesh: at every zero mode the static slot bytes must
    equal the placed addressable shard bytes (the scalar `step` slot is
    the only delta — the census counts slots only)."""
    script = textwrap.dedent("""\
        import jax, numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.layers import api as layer, base, data_type
        from paddle_tpu.layers import activation as act
        from paddle_tpu.config.topology import Topology
        from paddle_tpu.optimizer import Adam
        from paddle_tpu.parallel import zero as Z
        from paddle_tpu.parallel.mesh import get_mesh
        from paddle_tpu.analysis import opt_state_bytes_per_device
        from paddle_tpu.analysis.memory import tree_bytes

        base.reset_name_counters()
        x = layer.data(name='x', type=data_type.dense_vector(64))
        h = layer.fc(input=x, size=128, act=act.ReluActivation())
        p = layer.fc(input=h, size=8, act=act.SoftmaxActivation())
        y = layer.data(name='y', type=data_type.integer_value(8))
        topo = Topology(layer.classification_cost(input=p, label=y))
        specs = {s.name: s for s in topo.param_specs()}
        params = paddle.parameters.create(topo).as_dict()
        opt = Adam(learning_rate=1e-2)
        opt_state = opt.init(params, specs)
        mesh = get_mesh().mesh
        step_bytes = tree_bytes({"step": opt_state["step"]})
        for zero in (0, 1, 2):
            static = opt_state_bytes_per_device(opt_state, params, mesh,
                                                zero)
            if zero == 0:
                measured = sum(
                    leaf.size * leaf.dtype.itemsize for leaf in
                    jax.tree.leaves(opt_state["slots"]))
            else:
                placed = Z.shard_opt_state(opt_state, params, mesh)
                measured = Z.state_bytes_per_device(placed)
            assert static - step_bytes == measured, (
                zero, static, measured, step_bytes)
        print("CENSUS_AGREE")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=8"])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=REPO,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "CENSUS_AGREE" in out.stdout


def test_sharding_flow_replicated_intermediate_fixture():
    from paddle_tpu.analysis import sharding_flow_pass

    big = "1024x4096xf32"  # 16 MiB
    stablehlo = textwrap.dedent("""\
        func.func public @main(%arg0: tensor<{big}> {{tf.aliasing_output = 0 : i32}}, %arg1: tensor<8x{big}>) -> (tensor<{big}>) {{
          %0 = stablehlo.custom_call @Sharding(%arg1) {{backend_config = "", mhlo.sharding = "{{replicated}}"}} : (tensor<8x{big}>) -> tensor<8x{big}>
          %1 = stablehlo.custom_call @Sharding(%arg0) {{backend_config = "", mhlo.sharding = "{{replicated}}"}} : (tensor<{big}>) -> tensor<{big}>
          return %1 : tensor<{big}>
        }}
        """).format(big=big)
    found = sharding_flow_pass(stablehlo, None, name="p")
    # the donated param pin (%arg0's type) is sanctioned pre-ZeRO-3;
    # the 8x-sized activation pin is the planted defect, firing once
    assert [f.fid for f in found] == \
        ["GL-P-SHARD:<program:p>:replicated:f32[8,1024,4096]"]
    # allowlisting the reviewed type silences it
    assert sharding_flow_pass(stablehlo, None, name="p",
                              allowlist=("f32[8,1024,4096]",)) == []
    # small intermediates never fire (byte-gated like GL-P-DONATE)
    assert sharding_flow_pass(stablehlo, None, name="p",
                              min_bytes=1 << 30) == []


def test_sharding_flow_implicit_reshard_fixture():
    from paddle_tpu.analysis import sharding_flow_pass

    stablehlo = textwrap.dedent("""\
        func.func public @main(%arg0: tensor<1024x4096xf32> {tf.aliasing_output = 0 : i32}, %arg1: tensor<32x4096xf32>) -> (tensor<1024x4096xf32>) {
          return %arg0 : tensor<1024x4096xf32>
        }
        """)
    compiled = textwrap.dedent("""\
        %ag.1 = f32[1024,4096]{1,0} all-gather(f32[128,4096]{1,0} %p0), dimensions={0}
        %ag.2 = f32[4096,4096]{1,0} all-gather(f32[4096,512]{1,0} %act), dimensions={1}
        %ag.3 = f32[8,8]{1,0} all-gather(f32[1,8]{1,0} %tiny), dimensions={0}
        """)
    found = sharding_flow_pass(stablehlo, compiled, name="p")
    # ag.1 rebuilds the donated param type (the ZeRO all-gather) and
    # ag.3 is below the byte gate; ag.2 is the planted implicit reshard
    assert [f.fid for f in found] == \
        ["GL-P-SHARD:<program:p>:reshard:f32[4096,4096]"]
    assert "67.1 MB" in found[0].message  # the payload is named
    # TPU HLO emits collectives as async start/done pairs with a TUPLE
    # result type — the start op must fire identically, the done op
    # (referencing the same result) must not double-count
    async_compiled = textwrap.dedent("""\
        %ags = (f32[4096,512]{1,0}, f32[4096,4096]{1,0}) all-gather-start(f32[4096,512]{1,0} %act), dimensions={1}
        %agd = f32[4096,4096]{1,0} all-gather-done(%ags)
        """)
    found = sharding_flow_pass(stablehlo, async_compiled, name="p")
    assert [f.fid for f in found] == \
        ["GL-P-SHARD:<program:p>:reshard:f32[4096,4096]"]


def test_rng_key_reuse_fixture_fires_once_with_stable_id(tmp_path):
    from paddle_tpu.analysis.rng import pass_rng_discipline

    rel = "paddle_tpu/fix_rng.py"
    corpus = _corpus(tmp_path, rel, """\
        import jax

        def reused(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))    # planted: same key
            return a + b

        def split_ok(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (2,)) + \\
                jax.random.uniform(k2, (2,))

        def branch_ok(key, flag):
            if flag:
                return jax.random.normal(key, (2,))
            else:
                return jax.random.uniform(key, (2,))

        def refold_ok(key):
            a = jax.random.normal(key, (2,))
            key = jax.random.fold_in(key, 1)
            return a + jax.random.normal(key, (2,))
        """)
    found = pass_rng_discipline(corpus, str(tmp_path), modules=(rel,))
    assert [f.fid for f in found] == [f"GL-RNG:{rel}:reused"]
    assert "without an intervening split/fold_in" in found[0].message


def test_rng_literal_key_fixture(tmp_path):
    from paddle_tpu.analysis.rng import pass_rng_discipline

    rel = "paddle_tpu/fix_rng.py"
    corpus = _corpus(tmp_path, rel, """\
        import jax

        def literal_draw():
            return jax.random.normal(jax.random.PRNGKey(0), (2,))

        def literal_bound():
            k = jax.random.key(0)
            return jax.random.uniform(k, (2,))

        def seed_only():
            return jax.random.key(0)   # a seed, never drawn from: fine

        def threaded(key):
            return jax.random.normal(key, (2,))
        """)
    found = pass_rng_discipline(corpus, str(tmp_path), modules=(rel,))
    assert sorted(f.fid for f in found) == [
        f"GL-RNG:{rel}:literal_bound",
        f"GL-RNG:{rel}:literal_draw",
    ]


def test_rng_fold_pass_flags_unfolded_shard_map_draw():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import compat
    from paddle_tpu.analysis import rng_fold_pass

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def nofold(x, key):
        return x * jax.random.uniform(key, x.shape)

    def folded(x, key):
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        return x * jax.random.uniform(key, x.shape)

    x, key = jnp.ones((8, 4)), jax.random.key(0)
    bad = compat.shard_map(nofold, mesh=mesh, in_specs=(P("data"), P()),
                           out_specs=P("data"))
    good = compat.shard_map(folded, mesh=mesh, in_specs=(P("data"), P()),
                            out_specs=P("data"))
    found = rng_fold_pass(bad, x, key, name="p")
    assert [f.fid for f in found] == ["GL-RNG:<program:p>:shard-fold"]
    assert rng_fold_pass(good, x, key, name="p") == []


def test_rng_pass_clean_on_repo():
    from paddle_tpu.analysis.codebase import iter_corpus
    from paddle_tpu.analysis.rng import pass_rng_discipline

    found = pass_rng_discipline(iter_corpus(REPO), REPO)
    assert found == [], [f.fid for f in found]


def test_program_fingerprint_canonicalization():
    from paddle_tpu.analysis import program_fingerprint

    a = ("%1 = f32[8]{0} add(f32[8]{0} %p0, f32[8]{0} %p1), "
         "metadata={op_name=\"x\" source_line=3}\n"
         "%2 = f32[8]{0} all-gather(f32[8]{0} %1)")
    # SSA renumbering + metadata churn canonicalize away
    b = ("%41 = f32[8]{0} add(f32[8]{0} %arg0, f32[8]{0} %arg1), "
         "metadata={op_name=\"y\" source_line=99}\n"
         "%55 = f32[8]{0} all-gather(f32[8]{0} %41)")
    fa, fb = program_fingerprint(a), program_fingerprint(b)
    assert fa["hash"] == fb["hash"]
    assert fa["ops"] == ["add", "all-gather"]
    # a real op change does not
    c = a.replace("all-gather", "reduce-scatter")
    assert program_fingerprint(c)["hash"] != fa["hash"]


def test_divergence_pass_names_the_diff():
    from paddle_tpu.analysis import divergence_pass, program_fingerprint

    same = "%1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)\n" \
           "%2 = f32[8]{0} all-gather(f32[8]{0} %1)"
    diff = same.replace("all-gather", "reduce-scatter")
    fps = {0: program_fingerprint(same, rank=0),
           1: program_fingerprint(same, rank=1),
           2: program_fingerprint(diff, rank=2)}
    found = divergence_pass(fps, name="p")
    assert [f.fid for f in found] == ["GL-P-DIVERGE:<program:p>:rank-2"]
    assert "op[1]: reduce-scatter vs all-gather" in found[0].message
    # agreement is clean
    assert divergence_pass({0: fps[0], 1: fps[1]}, name="p") == []


def test_exchange_fingerprints_roundtrip_and_timeout(tmp_path):
    from paddle_tpu.analysis import (
        exchange_fingerprints,
        program_fingerprint,
    )
    from paddle_tpu.analysis.diverge import publish_fingerprint

    d = str(tmp_path / "rdv")
    fp1 = program_fingerprint("%1 = f32[8]{0} add(f32[8]{0} %a)", rank=1)
    publish_fingerprint(fp1, d, 1)
    fp0 = program_fingerprint("%1 = f32[8]{0} add(f32[8]{0} %a)", rank=0)
    fps = exchange_fingerprints(fp0, d, 0, 2, timeout_s=10)
    assert set(fps) == {0, 1} and fps[1]["hash"] == fp0["hash"]
    # a missing rank times out naming who never published
    with pytest.raises(TimeoutError, match=r"rank\(s\) \[2\]"):
        exchange_fingerprints(fp0, d, 0, 3, timeout_s=0.3)


# -- 4b. graftlint v2 through the real CLI --------------------------------------


def test_preflight_cli_hbm_budget(tmp_path):
    cfg = _write_preflight_config(tmp_path)
    # a deliberately over-budget device (10 KB of HBM) fails with the
    # GL-P-MEM finding; a real budget passes and is echoed
    out = _run_preflight(cfg, "--hbm_gb", "0.00001")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "GL-P-MEM" in out.stdout and "hbm-budget" in out.stdout
    out = _run_preflight(cfg, "--hbm_gb", "16")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "16.0 GB budget" in out.stdout


def test_preflight_cli_zero2_with_budget_clean(tmp_path):
    cfg = _write_preflight_config(tmp_path)
    out = _run_preflight(cfg, "--zero", "2", "--hbm_gb", "16", devices=8)
    assert out.returncode == 0, out.stdout + out.stderr


def test_preflight_cli_catches_injected_eval_host_sync(tmp_path):
    cfg = _write_preflight_config(tmp_path)
    out = _run_preflight(cfg, inject="host_sync_eval")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "GL-P-SYNC:<program:eval_step>" in out.stdout


def _run_preflight_rank(cfg, rank, nproc, rdv, inject=""):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_PREFLIGHT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_TRAINER_ID"] = str(rank)
    env["PADDLE_TPU_NPROC"] = str(nproc)
    env["PADDLE_TPU_PREFLIGHT_RENDEZVOUS"] = rdv
    if inject:
        env["PADDLE_TPU_PREFLIGHT_INJECT"] = inject
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.trainer", "--config", cfg,
         "--preflight"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env)


def test_preflight_cli_rank_divergence_aborts_with_named_diff(tmp_path):
    """The GL-P-DIVERGE acceptance: two ranks preflight the same config
    through the real CLI with the chaos hook perturbing rank 1's
    program — BOTH abort with the named diff instead of a fleet that
    would deadlock in its first collective; without the injection the
    exchange agrees and both pass."""
    cfg = _write_preflight_config(tmp_path)
    rdv = str(tmp_path / "rdv")
    procs = [_run_preflight_rank(cfg, r, 2, rdv, inject="rank_divergence")
             for r in range(2)]
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 1, out
        assert "GL-P-DIVERGE" in out
        assert "chaos.divergence" in out  # the diff names the alien op
    # the clean twin: same fleet, no injection, agreement
    rdv2 = str(tmp_path / "rdv2")
    procs = [_run_preflight_rank(cfg, r, 2, rdv2) for r in range(2)]
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out


# -- 4c. baseline staleness + machine-readable counts ---------------------------


def _baseline_with_bogus_entry(tmp_path):
    from paddle_tpu.analysis import load_baseline

    sup = load_baseline()
    sup["GL-EXCEPT:paddle_tpu/does_not_exist.py:gone"] = "stale on purpose"
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"suppressions": sup}))
    return str(path)


def test_analysis_json_reports_suppressed_and_stale_counts(tmp_path):
    bl = _baseline_with_bogus_entry(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--json",
         "--baseline", bl],
        capture_output=True, text=True, cwd=REPO)
    data = json.loads(out.stdout)
    assert out.returncode == 1          # stale entry fails the full run
    assert data["clean"] is False
    assert data["findings"] == []       # no real findings — only stale
    assert data["suppressed_count"] == len(data["suppressed"]) >= 3
    assert data["suppressed"][0]["fid"]  # full finding objects, not fids
    assert data["stale_count"] == 1
    assert data["stale_suppressions"] == \
        ["GL-EXCEPT:paddle_tpu/does_not_exist.py:gone"]


def test_lint_full_run_fails_on_stale_baseline_entry(tmp_path):
    bl = _baseline_with_bogus_entry(tmp_path)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--baseline", bl],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 1, out.stdout + out.stderr
    # the dead entry is named in the failure
    assert "GL-EXCEPT:paddle_tpu/does_not_exist.py:gone" in out.stdout
    assert "stale baseline" in out.stdout
    # --changed subset runs can't evaluate staleness: still green
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         "--changed", "--baseline", bl],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr


def test_divergence_pass_shape_only_drift_names_the_line():
    """Same op kinds, different dims (the classic batch-size config
    drift) must still name the divergent instruction — the op-kind diff
    comes up empty, so the canonical-line diff takes over."""
    from paddle_tpu.analysis import divergence_pass, program_fingerprint

    a = "%1 = f32[32,64]{1,0} add(f32[32,64]{1,0} %p0, f32[32,64]{1,0} %p1)"
    b = "%1 = f32[64,64]{1,0} add(f32[64,64]{1,0} %p0, f32[64,64]{1,0} %p1)"
    found = divergence_pass({0: program_fingerprint(a, rank=0),
                             1: program_fingerprint(b, rank=1)}, name="p")
    assert len(found) == 1
    assert "line[0]" in found[0].message
    assert "f32[64,64]" in found[0].message


# ---------------------------------------------------------------------------
# GL-P-COST: the static roofline cost model (analysis/cost.py)
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_hw_profile_table_and_auto(self):
        from paddle_tpu.analysis import HW_PROFILES, hw_profile

        assert {"v5p", "cpu-testbed"} <= set(HW_PROFILES)
        v5p = hw_profile("v5p")
        assert v5p.peak_flops > 1e14 and v5p.hbm_gb == 95.0
        # auto on the CPU testbed resolves to the calibrated profile
        assert hw_profile("auto").name == "cpu-testbed"

    def test_unknown_profile_is_clean_error_not_keyerror(self):
        from paddle_tpu.analysis import hw_profile

        with pytest.raises(ValueError) as ei:
            hw_profile("v9000")
        # names the table so the fix is obvious; never a raw KeyError
        assert "v9000" in str(ei.value)
        assert "v5p" in str(ei.value) and "cpu-testbed" in str(ei.value)

    def test_cost_report_charges_matmul_exactly(self):
        import jax.numpy as jnp

        from paddle_tpu.analysis import cost_report

        def f(x, w):
            return jnp.sum(x @ w)

        x = np.zeros((8, 32), np.float32)
        w = np.zeros((32, 16), np.float32)
        rep = cost_report(f, x, w, profile="v5p")
        # 2·M·N·K for the single dot
        assert rep["by_class"]["matmul"]["flops"] == 2 * 8 * 16 * 32
        assert rep["flops_source"] == "jaxpr-walk"
        assert rep["step_ms"] > 0 and 0 < rep["mfu_pct"] <= 100
        assert set(rep["by_class"]) == {"matmul", "conv", "elementwise",
                                        "reduce", "gather", "layout"}
        assert rep["bottleneck"]

    def test_collective_wire_model_and_zero_schedule(self):
        from paddle_tpu.analysis import zero_collective_bytes
        from paddle_tpu.analysis.cost import collective_wire_bytes

        # ring all-reduce: 2(n-1)/n of the payload crosses each link
        assert collective_wire_bytes("all_reduce", 8 * 10 ** 9, 8) == (
            pytest.approx(2 * 7 / 8 * 8e9))
        assert collective_wire_bytes("all_gather", 1e9, 4) == (
            pytest.approx(3 / 4 * 1e9))
        assert collective_wire_bytes("all_reduce", 1e9, 1) == 0.0
        # analytic ZeRO schedule when the trace has no collectives
        assert zero_collective_bytes(100, 1, 0) == []
        assert [c["kind"] for c in zero_collective_bytes(100, 8, 0)] == [
            "all_reduce"]
        assert [c["kind"] for c in zero_collective_bytes(100, 8, 1)] == [
            "reduce_scatter", "all_gather"]

    def test_dp_mesh_scales_work_and_can_bind_on_collectives(self):
        import jax.numpy as jnp

        from paddle_tpu.analysis import cost_report

        def f(x, w):
            return jnp.sum(x @ w)

        x = np.zeros((8, 32), np.float32)
        w = np.zeros((32, 16), np.float32)

        class Shim:  # plan_search's _MeshShim shape
            shape = {"data": 8}
            axis_names = ("data",)

        solo = cost_report(f, x, w, profile="v5p")
        # tiny compute + a fat analytic all-reduce: collective-bound
        dp = cost_report(f, x, w, profile="v5p", mesh=Shim(), zero=0,
                         params_bytes=10 ** 9)
        assert dp["dp"] == 8
        # GSPMD global-shape trace: per-device flops are 1/dp
        assert dp["flops"] == solo["flops"] // 8
        assert dp["comm_ms"] > 0 and dp["bottleneck"] == "collective-bound"
        assert dp["overlap_headroom_ms"] < 0

    def test_mfu_floor_finding_round_trips_analysis_json(self):
        """GL-P-COST findings survive the exact ``--json`` wire format
        (vars + fid) the analysis CLI emits — fid stable, fields intact."""
        import jax.numpy as jnp

        from paddle_tpu.analysis import Finding, cost_report
        from paddle_tpu.analysis.cost import cost_budget_pass

        def f(x):
            return jnp.sum(x * 2.0)  # elementwise-only: terrible MFU

        rep = cost_report(f, np.zeros((64,), np.float32), profile="v5p")
        found = cost_budget_pass(rep, name="train_step", mfu_floor=99.0)
        assert len(found) == 1
        f0 = found[0]
        assert f0.rule == "GL-P-COST" and f0.anchor == "mfu-floor"
        assert "bottleneck" in f0.message
        wire = json.loads(json.dumps(vars(f0) | {"fid": f0.fid}))
        back = Finding(**{k: v for k, v in wire.items() if k != "fid"})
        assert back.fid == wire["fid"] == f0.fid
        assert back == f0
        # floor 0 = report-only: no finding
        assert cost_budget_pass(rep, mfu_floor=0.0) == []
