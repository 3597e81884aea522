"""v1 optimizer-parity stragglers: sparse_momentum wiring
and equivalence, loud unknown-learning_method errors, per-parameter
momentum application, and model-average apply at eval.

Reference anchors: ``paddle/parameter/FirstOrderOptimizer.{h,cpp}``
(SparseMomentumParameterOptimizer, sgdUpdate's paraConfig.momentum()),
``paddle/parameter/AverageOptimizer.h:63-64`` (apply/restore), and
``paddle/trainer/tests/test_CompareTwoOpts.cpp`` (convergence-equality
test style)."""

import numpy as np
import pytest

import paddle_tpu.optimizer as opt
from paddle_tpu.core.parameters import ParamSpec


def _spec(name, shape, **kw):
    from paddle_tpu.core import initializer as I

    return ParamSpec(name=name, shape=shape, initializer=I.constant(0.0), **kw)


def _run(optimizer, params, grads_seq, specs=None):
    state = optimizer.init(params, specs)
    for g in grads_seq:
        params, state = optimizer.apply(g, params, state, specs)
    return params, state


def _toy_problem(steps=25, seed=0):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp

    params = {"w": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
    grads = [{"w": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
             for _ in range(steps)]
    return params, grads


class TestSparseMomentum:
    def test_equals_dense_momentum_all_rows(self):
        """All rows touched + constant lr => float-equal to heavy-ball
        momentum (test_CompareTwoOpts-style equality)."""
        params, grads = _toy_problem()
        dense, _ = _run(opt.Momentum(momentum=0.9, learning_rate=0.05),
                        dict(params), grads)
        sparse, _ = _run(opt.SparseMomentum(momentum=0.9, learning_rate=0.05),
                         dict(params), grads)
        np.testing.assert_allclose(np.asarray(dense["w"]),
                                   np.asarray(sparse["w"]),
                                   rtol=2e-4, atol=2e-5)

    def test_threshold_restart_preserves_trajectory(self):
        """The alpha>threshold restart (FirstOrderOptimizer.cpp:86-113
        needSpecialTraversal + finishBatch) rescales u and reassigns v
        without changing the represented parameter."""
        params, grads = _toy_problem(steps=40)
        ref, _ = _run(opt.SparseMomentum(momentum=0.9, learning_rate=0.05),
                      dict(params), grads)
        restarting = opt.SparseMomentum(momentum=0.9, learning_rate=0.05)
        restarting.threshold = 5.0  # alpha=1/0.9^t crosses 5 every ~15 steps
        got, state = _run(restarting, dict(params), grads)
        assert float(state["slots"]["w"]["alpha"]) < 5.0 / 0.9 + 1e-3
        np.testing.assert_allclose(np.asarray(ref["w"]), np.asarray(got["w"]),
                                   rtol=2e-4, atol=2e-5)

    def test_decay_is_decoupled_weight_decay(self):
        """beta carries the decay term as true decoupled weight decay:

            mom_t   = k * mom_{t-1} - lr * g_t
            theta_t = (1 - lambda*lr) * theta_{t-1} + mom_t

        NOTE this deliberately fixes the reference's sign
        (FirstOrderOptimizer.cpp:54 divides beta by (1 + lambda*gamma),
        under which decay GROWS theta by (1+lambda*lr) per step — verified
        against a direct transcription; see the SparseMomentum docstring)."""
        params, grads = _toy_problem()
        lam, lr, k = 0.01, 0.05, 0.9
        specs = {"w": _spec("w", (8, 4), decay_rate=lam)}
        sparse, _ = _run(opt.SparseMomentum(momentum=k, learning_rate=lr),
                         dict(params), grads, specs)
        theta = np.asarray(params["w"], np.float64)
        mom = np.zeros_like(theta)
        for g in grads:
            mom = k * mom - lr * np.asarray(g["w"], np.float64)
            theta = (1.0 - lam * lr) * theta + mom
        np.testing.assert_allclose(theta, np.asarray(sparse["w"]),
                                   rtol=2e-4, atol=2e-5)

    def test_decay_shrinks_with_zero_gradient(self):
        """With g=0, decay must shrink the parameter, never amplify it."""
        import jax.numpy as jnp

        o = opt.SparseMomentum(momentum=0.9, learning_rate=0.05,
                               regularization=opt.L2Regularization(0.1))
        params = {"w": jnp.ones((4,), jnp.float32)}
        state = o.init(params)
        for _ in range(50):
            params, state = o.apply({"w": jnp.zeros((4,))}, params, state)
        assert float(np.abs(np.asarray(params["w"])).max()) < 1.0

    def test_spec_zero_momentum_rejected_per_param(self):
        import jax.numpy as jnp

        o = opt.SparseMomentum(momentum=0.9, learning_rate=0.05)
        specs = {"w": _spec("w", (4,), momentum=0.0)}
        params = {"w": jnp.ones((4,))}
        state = o.init(params, specs)
        with pytest.raises(ValueError, match="momentum > 0"):
            o.apply({"w": jnp.ones((4,))}, params, state, specs)

    def test_zero_momentum_rejected(self):
        with pytest.raises(ValueError, match="momentum > 0"):
            opt.SparseMomentum(momentum=0.0)


class TestFactorySurfaces:
    def test_from_config_sparse_momentum(self):
        class Cfg:
            learning_method = "sparse_momentum"
            learning_rate = 0.1
            gradient_clipping_threshold = 0.0
            learning_rate_schedule = "constant"
            learning_rate_decay_a = 0.0
            learning_rate_decay_b = 0.0
            learning_rate_warmup_steps = 0
            l1_rate = 0.0
            l2_rate = 0.0
            average_window = 0.0
            max_average_window = 0
            momentum = 0.9

        o = opt.from_config(Cfg())
        assert isinstance(o, opt.SparseMomentum)

    def test_from_config_unknown_method_is_loud(self):
        class Cfg:
            learning_method = "adamw_totally_unknown"
            learning_rate = 0.1
            gradient_clipping_threshold = 0.0
            learning_rate_schedule = "constant"
            learning_rate_decay_a = 0.0
            learning_rate_decay_b = 0.0
            learning_rate_warmup_steps = 0
            l1_rate = 0.0
            l2_rate = 0.0
            average_window = 0.0
            max_average_window = 0

        with pytest.raises(ValueError, match="unknown learning_method"):
            opt.from_config(Cfg())

    def test_settings_every_reference_method_builds(self):
        """No reference settings() learning_method form may KeyError."""
        import paddle_tpu.trainer_config_helpers as tch

        for method in ("momentum", "torch_momentum", "sparse_momentum",
                       "adagrad", "decayed_adagrad", "adadelta", "rmsprop",
                       "adam", "adamax", "sgd", "ftrl", None):
            tch.settings(batch_size=16, learning_rate=0.1,
                         learning_method=method)
            o = tch.optimizers.get_settings_optimizer()
            assert isinstance(o, opt.Optimizer), method
        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method="sparse_momentum")
        assert isinstance(tch.optimizers.get_settings_optimizer(),
                          opt.SparseMomentum)

    def test_settings_unknown_method_is_loud(self):
        import paddle_tpu.trainer_config_helpers as tch

        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method="lbfgs_not_a_method")
        with pytest.raises(ValueError, match="not a supported"):
            tch.optimizers.get_settings_optimizer()

    def test_momentum_object_sparse_selects_sparse_momentum(self):
        """MomentumOptimizer(momentum, sparse=True) is the reference's
        spelling for sparse_momentum (optimizers.py:100)."""
        import paddle_tpu.trainer_config_helpers as tch

        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method=tch.MomentumOptimizer(0.9, sparse=True))
        o = tch.optimizers.get_settings_optimizer()
        assert isinstance(o, opt.SparseMomentum)
        assert o.momentum == 0.9
        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method=tch.MomentumOptimizer(0.8))
        o = tch.optimizers.get_settings_optimizer()
        assert isinstance(o, opt.Momentum) and not isinstance(
            o, opt.SparseMomentum)
        assert o.momentum == 0.8


class TestFactoryEdgeCases:
    def test_sgd_spec_momentum_survives_apply_without_specs(self):
        """The coefficient rides in the velocity slot: init with specs then
        apply without them (checkpoint-restored generic step) must not
        crash and must keep the momentum trajectory."""
        import jax.numpy as jnp

        o = opt.SGD(learning_rate=0.1)
        specs = {"w": _spec("w", (4,), momentum=0.9)}
        params = {"w": jnp.ones((4,))}
        state = o.init(params, specs)
        g = {"w": jnp.ones((4,))}
        p_spec, s_spec = o.apply(g, dict(params), o.init(params, specs), specs)
        p_none, _ = o.apply(g, dict(params), state)  # no specs passed
        np.testing.assert_allclose(np.asarray(p_spec["w"]),
                                   np.asarray(p_none["w"]))

    def test_settings_string_path_forwards_momentum(self):
        import paddle_tpu.trainer_config_helpers as tch

        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method="sparse_momentum", momentum=0.5)
        o = tch.optimizers.get_settings_optimizer()
        assert isinstance(o, opt.SparseMomentum) and o.momentum == 0.5
        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method="momentum", momentum=0.4)
        o = tch.optimizers.get_settings_optimizer()
        assert isinstance(o, opt.Momentum) and o.momentum == 0.4

    def test_settings_forwards_model_average(self):
        """settings(model_average=ModelAverage(...)) must reach the built
        optimizer (else the apply-at-eval feature is silently inert)."""
        import paddle_tpu.trainer_config_helpers as tch

        tch.settings(batch_size=16, learning_rate=0.1,
                     learning_method="momentum",
                     model_average=tch.optimizers.ModelAverage(
                         average_window=0.5, max_average_window=300))
        o = tch.optimizers.get_settings_optimizer()
        assert o.model_average is not None
        assert o.model_average.average_window == 0.5
        assert o.model_average.max_average_window == 300
        import jax.numpy as jnp

        state = o.init({"w": jnp.zeros((2,))})
        assert "avg" in state

    def test_from_config_momentum_from_extra_kwargs(self):
        """settings()-built configs keep momentum in extra kwargs (the
        OptimizationConfig proto has no global momentum field)."""

        class Cfg:
            learning_method = "sparse_momentum"
            learning_rate = 0.1
            gradient_clipping_threshold = 0.0
            learning_rate_schedule = "constant"
            learning_rate_decay_a = 0.0
            learning_rate_decay_b = 0.0
            learning_rate_warmup_steps = 0
            l1_rate = 0.0
            l2_rate = 0.0
            average_window = 0.0
            max_average_window = 0
            extra = {"momentum": 0.7}

        assert opt.from_config(Cfg()).momentum == 0.7


class TestPerParamMomentum:
    def test_spec_momentum_under_sgd_equals_momentum_optimizer(self):
        """ParameterConfig.momentum drives the update even under plain sgd
        (reference SgdOptimizer::update uses paraConfig.momentum())."""
        params, grads = _toy_problem()
        specs = {"w": _spec("w", (8, 4), momentum=0.9)}
        via_spec, _ = _run(opt.SGD(learning_rate=0.05), dict(params), grads,
                           specs)
        via_opt, _ = _run(opt.Momentum(momentum=0.9, learning_rate=0.05),
                          dict(params), grads)
        np.testing.assert_allclose(np.asarray(via_spec["w"]),
                                   np.asarray(via_opt["w"]), rtol=1e-6)

    def test_spec_momentum_overrides_optimizer_momentum(self):
        params, grads = _toy_problem()
        specs = {"w": _spec("w", (8, 4), momentum=0.5)}
        overridden, _ = _run(opt.Momentum(momentum=0.9, learning_rate=0.05),
                             dict(params), grads, specs)
        direct, _ = _run(opt.Momentum(momentum=0.5, learning_rate=0.05),
                         dict(params), grads)
        np.testing.assert_allclose(np.asarray(overridden["w"]),
                                   np.asarray(direct["w"]), rtol=1e-6)

    def test_default_momentum_flows_into_param_specs(self):
        """config-level default_momentum() lands in ParamSpec.momentum
        (the reference's g_default_momentum -> ParameterConfig path)."""
        from paddle_tpu.config import parse_state
        from paddle_tpu.layers import api as layer, base, data_type

        base.reset_name_counters()
        parse_state.reset_defaults()
        parse_state.default_momentum(0.75)
        try:
            x = layer.data(name="dmx", type=data_type.dense_vector(4))
            h = layer.fc(input=x, size=2, bias_attr=False)
            spec = [s for s in h.param_specs if "w" in s.name.lower()
                    or s.shape == (4, 2)][0]
            assert spec.momentum == 0.75
        finally:
            parse_state.reset_defaults()


class TestModelAverage:
    def test_averaged_eval_beats_raw_on_noisy_toy(self):
        """Noisy-gradient quadratic: the averaged iterate is closer to the
        optimum than the oscillating raw iterate (the reason
        AverageOptimizer::apply() exists)."""
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        target = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
        o = opt.SGD(learning_rate=0.35,
                    model_average=opt.ModelAverage(average_window=0.5,
                                                   max_average_window=200))
        params = {"w": jnp.zeros((16,), jnp.float32)}
        state = o.init(params)
        for _ in range(120):
            noise = jnp.asarray(rng.normal(
                scale=2.0, size=(16,)).astype(np.float32))
            grads = {"w": (params["w"] - target) + noise}
            params, state = o.apply(grads, params, state)
        avg = o.averaged(state)
        assert avg is not None
        err_raw = float(jnp.linalg.norm(params["w"] - target))
        err_avg = float(jnp.linalg.norm(avg["w"] - target))
        assert err_avg < err_raw, (err_avg, err_raw)

    def test_trainer_test_applies_average(self):
        """SGD.test() swaps averaged parameters in when an average is kept;
        on the noisy toy that must beat evaluating the raw weights."""
        import paddle_tpu as paddle

        rng = np.random.default_rng(0)
        from paddle_tpu.layers import activation, api as layer, base, data_type

        base.reset_name_counters()
        x = layer.data(name="avx", type=data_type.dense_vector(8))
        y = layer.data(name="avy", type=data_type.dense_vector(1))
        pred = layer.fc(input=x, size=1, act=activation.LinearActivation(),
                        bias_attr=False)
        cost = layer.square_error_cost(input=pred, label=y)
        parameters = paddle.parameters.create(paddle.topology.Topology(cost))
        optimizer = paddle.optimizer.SGD(
            learning_rate=0.6,
            model_average=opt.ModelAverage(average_window=0.5,
                                           max_average_window=400))
        trainer = paddle.trainer.SGD(cost=cost, parameters=parameters,
                                     update_equation=optimizer)
        w_true = rng.normal(size=(8, 1)).astype(np.float32)

        def train_reader():
            r = np.random.default_rng(1)
            for _ in range(80):
                v = r.normal(size=(8,)).astype(np.float32)
                noise = r.normal(scale=1.5)
                yield v, (v @ w_true + noise).astype(np.float32)

        def test_reader():
            r = np.random.default_rng(2)
            for _ in range(16):
                v = r.normal(size=(8,)).astype(np.float32)
                yield v, (v @ w_true).astype(np.float32)

        trainer.train(reader=paddle.reader.batch(train_reader, 8),
                      num_passes=1)
        assert trainer.optimizer.averaged(trainer._opt_state) is not None
        cost_avg = trainer.test(
            reader=paddle.reader.batch(test_reader, 16)).cost
        # drop the average and re-test: raw weights must do worse
        trainer._opt_state = {k: v for k, v in trainer._opt_state.items()
                              if k not in ("avg", "avg_count")}
        cost_raw = trainer.test(
            reader=paddle.reader.batch(test_reader, 16)).cost
        assert cost_avg < cost_raw, (cost_avg, cost_raw)

    def test_averaged_parameters_for_inference(self):
        """averaged_parameters() hands the averaged weights to infer()."""
        import paddle_tpu as paddle
        from paddle_tpu.layers import activation, api as layer, base, data_type

        base.reset_name_counters()
        x = layer.data(name="aix", type=data_type.dense_vector(4))
        y = layer.data(name="aiy", type=data_type.dense_vector(1))
        pred = layer.fc(input=x, size=1, act=activation.LinearActivation(),
                        bias_attr=False, param_attr=paddle.attr.Param(
                            name="ai_w"))
        cost = layer.square_error_cost(input=pred, label=y)
        parameters = paddle.parameters.create(paddle.topology.Topology(cost))
        optimizer = paddle.optimizer.SGD(
            learning_rate=0.5,
            model_average=opt.ModelAverage(average_window=0.5,
                                           max_average_window=100))
        trainer = paddle.trainer.SGD(cost=cost, parameters=parameters,
                                     update_equation=optimizer)
        rng = np.random.default_rng(0)

        def reader():
            for _ in range(24):
                v = rng.normal(size=(4,)).astype(np.float32)
                yield v, np.asarray([v.sum()], dtype=np.float32)

        trainer.train(reader=paddle.reader.batch(reader, 8), num_passes=1)
        avg_params = trainer.averaged_parameters()
        raw = np.asarray(trainer.parameters["ai_w"])
        avg = np.asarray(avg_params["ai_w"])
        assert avg.shape == raw.shape
        assert not np.allclose(raw, avg)  # oscillating weights => differ


class TestAdamMomentDtype:
    """Opt-in low-precision Adam moments (the LM accounting's HBM lever):
    storage dtype changes, update math stays f32, trajectory stays close
    to the f32-moment baseline."""

    def test_default_unchanged_f32(self):
        import jax.numpy as jnp

        params, grads = _toy_problem()
        a = opt.Adam(learning_rate=1e-2)
        p_ref, st = _run(a, dict(params), grads)
        assert st["slots"]["w"]["m"].dtype == jnp.float32

    def test_bf16_moments_dtype_and_close_trajectory(self):
        import jax.numpy as jnp

        params, grads = _toy_problem(steps=50)
        ref, _ = _run(opt.Adam(learning_rate=1e-2), dict(params), grads)
        a16 = opt.Adam(learning_rate=1e-2, moment_dtype=jnp.bfloat16)
        got, st = _run(a16, dict(params), grads)
        assert st["slots"]["w"]["m"].dtype == jnp.bfloat16
        assert st["slots"]["w"]["v"].dtype == jnp.bfloat16
        # parameters remain f32 and track the f32-moment run closely
        assert got["w"].dtype == jnp.float32
        diff = float(jnp.max(jnp.abs(got["w"] - ref["w"])))
        scale = float(jnp.max(jnp.abs(ref["w"] - params["w"])))
        assert diff < 0.05 * scale, (diff, scale)

    def test_bf16_moments_tree_api_converges(self):
        """apply_tree path (the transformer family): a least-squares
        problem reaches the same loss region as f32 moments."""
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        w_true = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
        y = x @ w_true

        def losses_for(optimizer, steps=120):
            params = {"w": jnp.zeros((16, 4), jnp.float32)}
            state = optimizer.init_tree(params)

            @jax.jit
            def step(params, state):
                def loss_fn(p):
                    return jnp.mean((x @ p["w"] - y) ** 2)

                l, g = jax.value_and_grad(loss_fn)(params)
                params, state = optimizer.apply_tree(g, params, state)
                return params, state, l

            out = []
            for _ in range(steps):
                params, state, l = step(params, state)
                out.append(float(l))
            return out

        ref = losses_for(opt.Adam(learning_rate=5e-2))
        got = losses_for(opt.Adam(learning_rate=5e-2,
                                  moment_dtype=jnp.bfloat16))
        assert got[-1] < ref[0] * 0.05       # actually converges
        assert got[-1] < max(ref[-1] * 3.0, 1e-3), (got[-1], ref[-1])
