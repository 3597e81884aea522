"""Serving's shell: strict inference, the dense batcher, servable export
and the ``python -m paddle_tpu.serving`` CLI loop (subprocess, ``serving``
marker).  The engine: ``test_serving_engine.py``, ``test_serving_loop.py``;
its kernels: ``test_paged_attention.py``."""

import numpy as np
import pytest

import jax

from paddle_tpu.models import transformer as T
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.telemetry import MetricsRegistry

from lm_toy import small_cfg


class TestStrictInference:
    def test_strict_raises_on_missing_parameters(self):
        import paddle_tpu as paddle
        from paddle_tpu.layers import api as layer
        from paddle_tpu.layers import data_type
        from paddle_tpu.trainer.inference import Inference

        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=2)
        empty = paddle.parameters.Parameters()  # no values loaded at all
        with pytest.raises(ValueError, match="incomplete"):
            Inference(out, empty, strict=True)
        # the default stays permissive (v2 back-compat)
        from paddle_tpu.layers import base as layer_base

        layer_base.reset_name_counters()
        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=2)
        inf = Inference(out, paddle.parameters.Parameters())
        assert inf.infer([ (np.zeros(4, np.float32),) ]).shape == (1, 2)

    def test_strict_passes_on_complete_parameters(self):
        import paddle_tpu as paddle
        from paddle_tpu.layers import api as layer
        from paddle_tpu.layers import data_type
        from paddle_tpu.trainer.inference import Inference

        x = layer.data(name="x", type=data_type.dense_vector(4))
        out = layer.fc(input=x, size=2)
        params = paddle.parameters.create(paddle.topology.Topology(out))
        inf = Inference(out, params, strict=True)
        assert inf.infer([(np.zeros(4, np.float32),)]).shape == (1, 2)


class TestDenseBatcher:
    def test_coalesces_and_matches_direct(self):
        import threading

        from paddle_tpu.serving.dense import DenseBatcher

        calls = []

        def predict(rows):
            calls.append(len(rows))
            return np.asarray([[float(r), float(r) * 2] for r in rows])

        reg = MetricsRegistry("dense_test")
        b = DenseBatcher(predict, max_batch=8, max_wait_ms=20.0,
                         registry=reg)
        pending = []
        barrier = threading.Barrier(5)

        def client(i):
            barrier.wait()
            pending.append((i, b.submit(i)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, p in pending:
            np.testing.assert_allclose(p.result(10.0), [i, i * 2])
        b.close()
        assert sum(calls) == 5
        assert len(calls) < 5  # at least one coalesced batch
        assert reg.counter("serve_dense_requests").value() == 5.0

    def test_predict_error_fans_out(self):
        from paddle_tpu.serving.dense import DenseBatcher

        def boom(rows):
            raise RuntimeError("model exploded")

        b = DenseBatcher(boom, max_batch=4, max_wait_ms=1.0,
                         registry=MetricsRegistry("dense_err"))
        p = b.submit(1)
        with pytest.raises(RuntimeError, match="exploded"):
            p.result(10.0)
        b.close()


class TestExport:
    def test_round_trip_and_tamper_detection(self, tmp_path, rng_np):
        from paddle_tpu.serving.export import export_servable, load_servable

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(3))
        out = str(tmp_path / "servable")
        export_servable(out, cfg, params, meta={"note": "test"})
        cfg2, params2 = load_servable(out)
        assert cfg2 == cfg
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b)), params, params2)
        # served tokens from the loaded artifact match the live params
        prompt = list(rng_np.integers(1, 64, size=4))
        scfg = ServingConfig(max_slots=1, page_size=4, num_pages=16,
                             max_prompt_len=8, max_new_tokens=3,
                             prefill_batch=1)
        a = ServingEngine(cfg, params, scfg).generate([prompt])[0].tokens
        b = ServingEngine(cfg2, params2, scfg).generate([prompt])[0].tokens
        assert a == b
        # flip a byte -> load refuses
        payload = tmp_path / "servable" / "params.npz"
        raw = bytearray(payload.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        payload.write_bytes(bytes(raw))
        with pytest.raises(Exception, match="hash mismatch"):
            load_servable(out)

    def test_checkpoint_to_servable(self, tmp_path):
        from paddle_tpu.serving.export import (
            checkpoint_to_servable,
            load_servable,
        )
        from paddle_tpu.trainer.checkpoint import save_checkpoint

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(4))
        flat = {}

        def flatten(d, prefix=""):
            for k, v in d.items():
                if isinstance(v, dict):
                    flatten(v, f"{prefix}{k}/")
                else:
                    flat[f"{prefix}{k}"] = np.asarray(v)

        flatten(params)
        ckpt = str(tmp_path / "ckpts")
        save_checkpoint(ckpt, 0, flat)
        out = checkpoint_to_servable(ckpt, str(tmp_path / "servable"), cfg)
        cfg2, params2 = load_servable(out)
        np.testing.assert_allclose(np.asarray(params2["embed"]),
                                   np.asarray(params["embed"]))
        np.testing.assert_allclose(
            np.asarray(params2["blocks"]["wq"]),
            np.asarray(params["blocks"]["wq"]))

    def test_partial_manifest_cases_refuse_to_load(self, tmp_path):
        """load_servable must refuse, with the reason, every partial-
        artifact shape: a manifest-listed file missing from disk, a
        payload param set that drifted from the manifest inventory, and
        a per-param dtype mismatch — never serve garbage-shaped
        weights."""
        import json

        from paddle_tpu.serving.export import export_servable, load_servable

        cfg = small_cfg()
        params = T.init_params(cfg, jax.random.key(5))

        def fresh(name):
            out = str(tmp_path / name)
            export_servable(out, cfg, params)
            return out

        # (a) payload file listed in the manifest but deleted on disk
        out = fresh("missing_file")
        (tmp_path / "missing_file" / "params.npz").unlink()
        with pytest.raises(Exception, match="missing from disk"):
            load_servable(out)

        # (b) manifest inventory lists a param the payload lacks
        out = fresh("missing_param")
        mpath = tmp_path / "missing_param" / "servable.json"
        m = json.loads(mpath.read_text())
        m["params"]["blocks/extra_w"] = "float32"
        mpath.write_text(json.dumps(m))
        with pytest.raises(Exception, match="do not match the"):
            load_servable(out)

        # (c) dtype drift between manifest inventory and payload
        out = fresh("dtype_drift")
        mpath = tmp_path / "dtype_drift" / "servable.json"
        m = json.loads(mpath.read_text())
        key = next(k for k in m["params"])
        m["params"][key] = "float16"
        mpath.write_text(json.dumps(m))
        with pytest.raises(Exception, match="dtype mismatch"):
            load_servable(out)


@pytest.mark.serving
class TestCliLoop:
    def test_stdin_loop_subprocess(self):
        import os
        import subprocess
        import sys

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        lines = "5 17 3\n9 9 9 9\n"
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving", "--random",
             "--vocab", "64", "--embed", "32", "--max_new_tokens", "4",
             "--seed", "7"],
            input=lines, env=env, capture_output=True, text=True,
            timeout=300)
        assert out.returncode == 0, out.stderr[-800:]
        got = [l for l in out.stdout.splitlines() if l.strip()]
        assert len(got) == 2
        assert got[0].startswith("0:") and got[1].startswith("1:")
        toks = [int(t) for t in got[0].split(":")[1].split()]
        assert len(toks) == 4 and all(0 <= t < 64 for t in toks)
        # deterministic: same seed -> same bytes out
        out2 = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving", "--random",
             "--vocab", "64", "--embed", "32", "--max_new_tokens", "4",
             "--seed", "7"],
            input=lines, env=env, capture_output=True, text=True,
            timeout=300)
        assert out2.stdout == out.stdout
