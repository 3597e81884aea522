"""Checkpoint/resume — mirrors the Go pserver checkpoint tests
(``go/pserver/service.go:342-391`` behavior: manifest+hash, newest-valid
recovery) and ParamUtil pass-snapshot semantics."""

import os

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.trainer import checkpoint as ckpt


def _tiny_trainer():
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import data_type

    x = layer.data(name="x", type=data_type.dense_vector(4))
    y = layer.data(name="y", type=data_type.dense_vector(1))
    fc = layer.fc(input=x, size=1, act=paddle.activation.LinearActivation(),
                  name="out")
    cost = layer.mse_cost(input=fc, label=y)
    params = paddle.parameters.create(paddle.topology.Topology(cost))
    tr = paddle.trainer.SGD(cost=cost, parameters=params,
                            update_equation=paddle.optimizer.Momentum(
                                momentum=0.9, learning_rate=0.05))
    return tr


def _reader():
    rs = np.random.RandomState(0)
    w = np.array([1.0, -2.0, 0.5, 3.0])

    def r():
        for _ in range(16):
            x = rs.randn(4).astype(np.float32)
            yield x, np.array([x @ w], np.float32)
    return paddle.reader.batch(r, batch_size=8)


def test_save_load_roundtrip(tmp_path):
    d = str(tmp_path)
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    opt = {"m": {"w": jnp.ones((2, 3))}, "step": jnp.zeros(())}
    states = {"bn.mean": np.full((3,), 0.5, np.float32)}
    path = ckpt.save_checkpoint(d, 3, params, opt_state=opt, states=states,
                                meta={"note": "hi"})
    assert os.path.basename(path) == "pass-00003"
    found = ckpt.latest_checkpoint(d)
    assert found is not None and found[1]["pass_id"] == 3
    template = {"m": {"w": jnp.zeros((2, 3))}, "step": jnp.ones(())}
    p2, o2, s2, manifest = ckpt.load_checkpoint(path, template)
    np.testing.assert_array_equal(p2["w"], params["w"])
    np.testing.assert_array_equal(np.asarray(o2["m"]["w"]), 1.0)
    np.testing.assert_array_equal(s2["bn.mean"], 0.5)
    assert manifest["meta"]["note"] == "hi"


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 0, {"w": np.zeros(2, np.float32)})
    ckpt.save_checkpoint(d, 1, {"w": np.ones(2, np.float32)})
    # corrupt the newest payload
    with open(os.path.join(d, "pass-00001", "params.npz"), "ab") as f:
        f.write(b"garbage")
    path, manifest = ckpt.latest_checkpoint(d)
    assert manifest["pass_id"] == 0
    p, _, _, _ = ckpt.load_checkpoint(path)
    np.testing.assert_array_equal(p["w"], 0.0)


def test_latest_checkpoint_skips_concurrent_writer_debris(tmp_path):
    """latest_checkpoint racing a concurrent writer: visible pass-*/
    batch-* dirs whose manifest is missing, torn (half-written JSON),
    empty, or pointing at not-yet-written payload files must be SKIPPED
    — selection falls back to the newest complete checkpoint instead of
    crashing (the Go pserver newest-VALID recovery rule, extended to
    mid-write states a non-atomic writer or lagging NFS can expose)."""
    import json
    d = str(tmp_path)
    good = ckpt.save_checkpoint(d, 0, {"w": np.zeros(2, np.float32)},
                                batch_id=3)

    # 1) dir exists, manifest not yet written
    os.makedirs(os.path.join(d, "pass-00000-batch-000005"))
    # 2) manifest torn mid-write (truncated JSON)
    torn = os.path.join(d, "pass-00000-batch-000007")
    os.makedirs(torn)
    with open(os.path.join(torn, ckpt.MANIFEST), "w") as f:
        f.write('{"uuid": "abc", "pass_id": 0, "files": {"par')
    # 3) manifest empty (open()'d but nothing flushed)
    empty = os.path.join(d, "pass-00000-batch-000008")
    os.makedirs(empty)
    open(os.path.join(empty, ckpt.MANIFEST), "w").close()
    # 4) manifest complete but a payload file it names is missing
    missing = os.path.join(d, "pass-00000-batch-000009")
    os.makedirs(missing)
    with open(os.path.join(missing, ckpt.MANIFEST), "w") as f:
        json.dump({"uuid": "x", "pass_id": 0,
                   "cursor": {"pass_id": 0, "batch_id": 9},
                   "files": {"params.npz": "0" * 64}, "meta": {}}, f)
    # 5) a stray FILE named like a checkpoint dir
    with open(os.path.join(d, "pass-00000-batch-000011"), "w") as f:
        f.write("not a directory")
    # 6) the writer's own tmp staging dir (never selectable)
    os.makedirs(os.path.join(d, "pass-00000-batch-000012.tmp-deadbeef"))

    found = ckpt.latest_checkpoint(d)
    assert found is not None
    path, manifest = found
    assert path == good
    assert manifest["cursor"] == {"pass_id": 0, "batch_id": 3}


def test_latest_checkpoint_empty_and_debris_only_dir(tmp_path):
    """No valid checkpoint at all -> None, not an exception."""
    d = str(tmp_path)
    assert ckpt.latest_checkpoint(d) is None  # dir doesn't even exist yet
    os.makedirs(os.path.join(d, "pass-00000-batch-000001"))
    torn = os.path.join(d, "pass-00002")
    os.makedirs(torn)
    with open(os.path.join(torn, ckpt.MANIFEST), "w") as f:
        f.write("{")
    assert ckpt.latest_checkpoint(d) is None


def test_gc_keeps_last_n(tmp_path):
    d = str(tmp_path)
    for i in range(5):
        ckpt.save_checkpoint(d, i, {"w": np.zeros(1, np.float32)},
                             keep_last=2)
    left = sorted(x for x in os.listdir(d) if x.startswith("pass-"))
    assert left == ["pass-00003", "pass-00004"]


def test_trainer_checkpoint_and_resume(tmp_path):
    d = str(tmp_path / "ckpt")
    tr = _tiny_trainer()
    tr.train(reader=_reader(), num_passes=2, checkpoint_dir=d)
    assert ckpt.latest_checkpoint(d)[1]["pass_id"] == 1
    w_after = tr.parameters["_out.w0"].copy()

    # fresh trainer resumes: starts at pass 2, parameters restored
    tr2 = _tiny_trainer()
    seen_passes = []

    def handler(e):
        if isinstance(e, paddle.event.BeginPass):
            seen_passes.append(e.pass_id)

    tr2.train(reader=_reader(), num_passes=4, checkpoint_dir=d,
              event_handler=handler)
    assert seen_passes == [2, 3]
    # resumed from the saved weights, then kept training
    assert ckpt.latest_checkpoint(d)[1]["pass_id"] == 3

    # resume with num_passes already done -> trains nothing
    tr3 = _tiny_trainer()
    seen = []
    tr3.train(reader=_reader(), num_passes=4, checkpoint_dir=d,
              event_handler=lambda e: seen.append(e))
    assert not any(isinstance(e, paddle.event.EndIteration) for e in seen)
    np.testing.assert_allclose(
        tr3.parameters["_out.w0"],
        ckpt.load_checkpoint(ckpt.latest_checkpoint(d)[0])[0]["_out.w0"])
    del w_after


def test_sigterm_preemption_checkpoints_and_resumes(tmp_path):
    """SIGTERM mid-training -> cursor checkpoint at the batch boundary ->
    a fresh trainer resumes the SAME pass from the next batch (SURVEY §5
    preemption handling + the resilience mid-pass replay cursor)."""
    import os
    import signal
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.layers import api as layer, base, data_type

    def build():
        base.reset_name_counters()
        x = layer.data(name="sx", type=data_type.dense_vector(4))
        h = layer.fc(input=x, size=4)
        lbl = layer.data(name="sy", type=data_type.integer_value(4))
        cost = layer.classification_cost(input=h, label=lbl)
        parameters = paddle.parameters.create(paddle.topology.Topology(cost))
        return paddle.trainer.SGD(
            cost=cost, parameters=parameters,
            update_equation=paddle.optimizer.SGD(learning_rate=0.1))

    rng = np.random.default_rng(0)

    def reader():
        for i in range(16):
            if i == 4:  # simulate the pod eviction signal mid-pass
                os.kill(os.getpid(), signal.SIGTERM)
            yield rng.normal(size=(4,)).astype(np.float32), int(i % 4)

    ckdir = str(tmp_path / "ck")
    trainer = build()
    trainer.train(reader=paddle.reader.batch(reader, 8), num_passes=50,
                  checkpoint_dir=ckdir)
    from paddle_tpu.trainer import checkpoint as ckpt

    found = ckpt.latest_checkpoint(ckdir)
    assert found is not None
    saved_pass = found[1]["pass_id"]
    assert saved_pass < 49  # preempted long before the end
    # mid-pass preemption records a replay cursor into the SAME pass
    cursor = found[1]["cursor"]
    assert cursor["pass_id"] == saved_pass and cursor["batch_id"] >= 1
    assert found[1]["meta"]["preempted"] is True

    # resume re-enters the preempted pass at the cursor batch
    passes = []
    trainer2 = build()
    trainer2.train(
        reader=paddle.reader.batch(
            lambda: ((rng.normal(size=(4,)).astype(np.float32), 0)
                     for _ in range(8)), 8),
        num_passes=saved_pass + 3, checkpoint_dir=ckdir,
        event_handler=lambda e: passes.append(e.pass_id)
        if isinstance(e, paddle.event.BeginPass) else None)
    assert passes and passes[0] == saved_pass


def test_async_checkpointer_writes_and_raises(tmp_path):
    """AsyncCheckpointer: identical artifacts to the sync path, one write
    in flight, deferred errors re-raise on wait()."""
    import pytest

    d = str(tmp_path / "a")
    w = ckpt.AsyncCheckpointer()
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    w.save(d, 0, params, states={"s": np.ones(2, np.float32)},
           meta={"tag": 1})
    w.wait()
    path, manifest = ckpt.latest_checkpoint(d)
    assert manifest["pass_id"] == 0 and manifest["meta"] == {"tag": 1}
    loaded, _, states, _ = ckpt.load_checkpoint(path)
    np.testing.assert_array_equal(loaded["w"], params["w"])
    np.testing.assert_array_equal(states["s"], np.ones(2, np.float32))

    # a failing write surfaces at the next wait(), not silently
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("x")
    w.save(str(blocker / "denied"), 1, params)
    with pytest.raises(OSError):
        w.wait()
    w.wait()  # error consumed; idempotent afterwards


def test_trainer_async_checkpoint_and_resume(tmp_path):
    """checkpoint_async=True produces the same resumable checkpoints."""
    d = str(tmp_path / "ckpt")
    tr = _tiny_trainer()
    tr.train(reader=_reader(), num_passes=2, checkpoint_dir=d,
             checkpoint_async=True)
    # train() returned only after the writer flushed
    assert ckpt.latest_checkpoint(d)[1]["pass_id"] == 1

    tr2 = _tiny_trainer()
    seen = []
    tr2.train(reader=_reader(), num_passes=3, checkpoint_dir=d,
              checkpoint_async=True,
              event_handler=lambda e: seen.append(
                  e.pass_id) if isinstance(e, paddle.event.BeginPass)
              else None)
    assert seen == [2]
    np.testing.assert_allclose(
        tr2.parameters["_out.w0"],
        ckpt.load_checkpoint(ckpt.latest_checkpoint(d)[0])[0]["_out.w0"])


def test_bf16_params_dtype_roundtrip(tmp_path):
    """Params saved bf16/fp8 must come
    back bf16/fp8 — the npz layer stores them f32, and without the
    manifest dtype record a resume would silently recompile the train
    step under an f32 signature."""
    d = str(tmp_path / "c")
    params = {
        "w_bf16": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
        "w_f32": np.arange(4, dtype=np.float32),
        "w_f16": np.arange(4, dtype=np.float16),  # native: untouched
    }
    states = {"bn.mean": jnp.full((3,), 0.5, jnp.bfloat16)}
    ckpt.save_checkpoint(d, 0, params, states=states)
    path, manifest = ckpt.latest_checkpoint(d)
    assert manifest["dtypes"]["params"] == {"w_bf16": "bfloat16"}
    assert manifest["dtypes"]["states"] == {"bn.mean": "bfloat16"}
    p2, _, s2, _ = ckpt.load_checkpoint(path)
    assert str(p2["w_bf16"].dtype) == "bfloat16"
    assert p2["w_f32"].dtype == np.float32
    assert p2["w_f16"].dtype == np.float16
    assert str(s2["bn.mean"].dtype) == "bfloat16"
    np.testing.assert_array_equal(
        np.asarray(p2["w_bf16"], np.float32),
        np.asarray(params["w_bf16"], np.float32))

    # pre-dtype-manifest checkpoints (no "dtypes" key) still load
    import json as _json
    mpath = os.path.join(path, ckpt.MANIFEST)
    m = _json.load(open(mpath))
    del m["dtypes"]
    with open(mpath, "w") as f:
        _json.dump(m, f)
    # manifest hash doesn't cover itself, so the edit is legal
    p3, _, _, _ = ckpt.load_checkpoint(path)
    assert p3["w_bf16"].dtype == np.float32  # legacy behavior preserved


def test_bf16_moment_opt_state_roundtrip(tmp_path):
    """npz loses extension dtypes (bfloat16 -> |V2); the checkpoint layer
    stores them f32 and restores the template dtype, so
    Adam(moment_dtype=bf16) states resume exactly."""
    from paddle_tpu.optimizer import Adam

    opt = Adam(learning_rate=1e-3, moment_dtype=jnp.bfloat16)
    params = {"w": jnp.arange(8, dtype=jnp.float32).reshape(2, 4)}
    state = opt.init_tree(params)
    grads = {"w": jnp.full((2, 4), 0.5, jnp.float32)}
    params, state = opt.apply_tree(grads, params, state)
    assert state["slots"][0]["m"].dtype == jnp.bfloat16

    d = str(tmp_path / "c")
    ckpt.save_checkpoint(d, 0, {"w": np.asarray(params["w"])},
                         opt_state=state)
    template = Adam(learning_rate=1e-3,
                    moment_dtype=jnp.bfloat16).init_tree(params)
    _, restored, _, _ = ckpt.load_checkpoint(
        ckpt.latest_checkpoint(d)[0], opt_state_template=template)
    assert restored["slots"][0]["m"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["slots"][0]["m"].astype(jnp.float32)),
        np.asarray(state["slots"][0]["m"].astype(jnp.float32)))
    assert int(restored["step"]) == int(state["step"])
