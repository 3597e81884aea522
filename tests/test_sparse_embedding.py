"""Sparse/embedding-parallel path — mirrors the reference's sparse tests
(``test_CompareSparse.cpp``: sparse-vs-dense training equality;
selected_rows_functor tests) on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.ops import selected_rows as sr_ops
from paddle_tpu.parallel import embedding as emb_par
from paddle_tpu.parallel.mesh import make_mesh


class TestSelectedRows:
    def test_to_dense_and_merge(self):
        sr = sr_ops.SelectedRows(
            rows=jnp.asarray([2, 0, 2], jnp.int32),
            values=jnp.asarray([[1., 1.], [2., 2.], [3., 3.]]),
            height=4)
        dense = np.asarray(sr.to_dense())
        np.testing.assert_allclose(dense[2], [4., 4.])
        np.testing.assert_allclose(dense[0], [2., 2.])
        np.testing.assert_allclose(dense[1], 0.0)
        merged = sr_ops.merge_rows(sr)
        d2 = np.asarray(merged.to_dense())
        np.testing.assert_allclose(d2, dense)

    def test_sgd_update_equals_dense(self):
        rs = np.random.RandomState(0)
        table = jnp.asarray(rs.randn(6, 3).astype(np.float32))
        ids = jnp.asarray([1, 4, 1], jnp.int32)
        ct = jnp.asarray(rs.randn(3, 3).astype(np.float32))
        grad = sr_ops.embedding_grad(ids, ct, 6)
        sparse = sr_ops.sgd_update(table, grad, lr=0.1)
        dense = table - 0.1 * grad.to_dense()
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   rtol=1e-6)

    def test_adagrad_touched_rows_only(self):
        table = jnp.zeros((5, 2))
        accum = jnp.zeros((5, 2))
        grad = sr_ops.SelectedRows(
            rows=jnp.asarray([3, 3], jnp.int32),
            values=jnp.asarray([[1., 0.], [1., 0.]]), height=5)
        new_t, new_a = sr_ops.adagrad_update(table, accum, grad, lr=0.5)
        assert float(new_a[3, 0]) == 4.0  # merged grad 2 -> squared
        assert float(new_t[3, 0]) < 0
        np.testing.assert_allclose(np.asarray(new_t)[[0, 1, 2, 4]], 0.0)
        np.testing.assert_allclose(np.asarray(new_a)[[0, 1, 2, 4]], 0.0)

    def test_momentum_and_decay_on_touch(self):
        table = jnp.ones((4, 2))
        vel = jnp.zeros((4, 2))
        grad = sr_ops.SelectedRows(
            rows=jnp.asarray([1], jnp.int32),
            values=jnp.asarray([[1., 1.]]), height=4)
        t2, v2 = sr_ops.momentum_update(table, vel, grad, lr=0.1, mu=0.9)
        np.testing.assert_allclose(np.asarray(v2)[1], 1.0)
        np.testing.assert_allclose(np.asarray(t2)[1], 0.9)
        np.testing.assert_allclose(np.asarray(t2)[0], 1.0)
        t3 = sr_ops.decay_on_touch(table, grad, l2_rate=0.5, lr=0.1)
        np.testing.assert_allclose(np.asarray(t3)[1], 1.0 - 0.05)
        np.testing.assert_allclose(np.asarray(t3)[2], 1.0)


class TestShardedEmbedding:
    def test_sharded_lookup_matches_dense(self):
        mesh = make_mesh({"model": 4})
        rs = np.random.RandomState(1)
        table = jnp.asarray(rs.randn(16, 5).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 16, (3, 7)), jnp.int32)
        sharded = emb_par.shard_table(table, mesh)
        got = emb_par.sharded_lookup(sharded, ids, mesh)
        want = jnp.take(table, ids, axis=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_sharded_lookup_grad_matches_dense(self):
        mesh = make_mesh({"model": 4})
        rs = np.random.RandomState(2)
        table = jnp.asarray(rs.randn(8, 3).astype(np.float32))
        ids = jnp.asarray([0, 5, 5, 7], jnp.int32)

        def loss_sharded(t):
            return jnp.sum(emb_par.sharded_lookup(t, ids, mesh) ** 2)

        def loss_dense(t):
            return jnp.sum(jnp.take(t, ids, axis=0) ** 2)

        g1 = jax.jit(jax.grad(loss_sharded))(emb_par.shard_table(table, mesh))
        g2 = jax.jit(jax.grad(loss_dense))(table)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)


def test_wide_and_deep_learns():
    from paddle_tpu.models.ctr import wide_and_deep_ctr

    cost, predict, _ = wide_and_deep_ctr(
        wide_dim=32, categorical_vocab_sizes=[10, 8], embedding_size=4,
        hidden_sizes=(16,))
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=parameters,
        update_equation=paddle.optimizer.Adam(learning_rate=0.02))

    rs = np.random.RandomState(0)

    def corpus():
        def r():
            for _ in range(256):
                wide_ids = rs.randint(0, 32, 3).tolist()
                c0 = int(rs.randint(0, 10))
                c1 = int(rs.randint(0, 8))
                label = int((c0 % 2) ^ (c1 % 2))  # learnable rule
                yield wide_ids, c0, c1, label
        return r

    costs = []

    def handler(e):
        if isinstance(e, paddle.event.EndIteration):
            costs.append(e.cost)

    feeding = {"wide_input": 0, "cat_0": 1, "cat_1": 2, "label": 3}
    trainer.train(reader=paddle.reader.batch(corpus(), batch_size=32),
                  num_passes=6, event_handler=handler, feeding=feeding)
    assert costs[-1] < costs[0] * 0.6, (costs[0], costs[-1])
    # embedding tables exist and carry the EP sharding annotation
    spec = parameters.spec("emb_0")
    assert spec.sharding == ("model", None)


class TestShardedEmbeddingClass:
    """The production ShardedEmbedding wrapper: vocab padding, both
    lowering paths, clamp-and-zero, exact duplicate-id gradients."""

    def _emb(self, path, vocab=10, dim=4):
        mesh = make_mesh({"model": 4})
        return emb_par.ShardedEmbedding(vocab=vocab, dim=dim, mesh=mesh,
                                        path=path)

    def test_layout_math(self):
        emb = self._emb("gspmd")
        assert emb.padded_vocab == 12 and emb.rows_per_shard == 3
        assert emb.total_bytes() == 12 * 4 * 4
        assert emb.per_device_bytes() == 3 * 4 * 4
        assert emb.total_bytes() == 4 * emb.per_device_bytes()

    def test_paths_agree_and_match_dense_oracle(self):
        rs = np.random.RandomState(3)
        dense = jnp.asarray(rs.randn(10, 4).astype(np.float32))
        ids = jnp.asarray([0, 7, 7, 9, 3], jnp.int32)
        want = jnp.take(dense, ids, axis=0)
        outs = {}
        for path in ("gspmd", "shard_map"):
            emb = self._emb(path)
            table = emb.place(dense)
            assert table.shape == (12, 4)
            outs[path] = np.asarray(emb.lookup(table, ids))
            np.testing.assert_allclose(outs[path], np.asarray(want),
                                       rtol=1e-6)
        # GL-P-COLL-style path agreement: same numbers both lowerings
        np.testing.assert_array_equal(outs["gspmd"], outs["shard_map"])

    def test_out_of_vocab_ids_clamp_and_zero(self):
        """Ids outside the LOGICAL vocab (including ids that would land in
        the pad rows) read as zero rows and receive zero gradient."""
        rs = np.random.RandomState(4)
        dense = jnp.asarray(rs.randn(10, 4).astype(np.float32))
        # 10, 11 fall in the pad rows; -1 and 99 are plain out-of-range
        ids = jnp.asarray([2, 10, 11, -1, 99], jnp.int32)
        for path in ("gspmd", "shard_map"):
            emb = self._emb(path)
            table = emb.place(dense)
            got = np.asarray(emb.lookup(table, ids))
            np.testing.assert_allclose(got[0], np.asarray(dense)[2],
                                       rtol=1e-6)
            np.testing.assert_array_equal(got[1:], 0.0)

            def loss(t):
                return jnp.sum(emb.lookup(t, ids) ** 2)

            g = np.asarray(jax.jit(jax.grad(loss))(table))
            # only the one valid id gets gradient; pad rows get none
            assert np.any(g[2] != 0)
            mask = np.ones(12, bool)
            mask[2] = False
            np.testing.assert_array_equal(g[mask], 0.0)

    def test_duplicate_ids_exact_scatter_add_grads(self):
        """Duplicate ids accumulate gradients exactly — compared against
        the dense one-device oracle on the same loss, both paths."""
        rs = np.random.RandomState(5)
        dense = jnp.asarray(rs.randn(10, 4).astype(np.float32))
        ids = jnp.asarray([7, 7, 7, 1, 1, 0], jnp.int32)
        ct = jnp.asarray(rs.randn(6, 4).astype(np.float32))

        def oracle(t):
            return jnp.sum(jnp.take(t, ids, axis=0) * ct)

        g_dense = np.asarray(jax.jit(jax.grad(oracle))(dense))
        for path in ("gspmd", "shard_map"):
            emb = self._emb(path)
            table = emb.place(dense)

            def loss(t):
                return jnp.sum(emb.lookup(t, ids) * ct)

            g = np.asarray(jax.jit(jax.grad(loss))(table))
            np.testing.assert_allclose(g[:10], g_dense, rtol=1e-5,
                                       atol=1e-6)
            np.testing.assert_array_equal(g[10:], 0.0)


class TestLazySparseOptimizer:
    """The SparseRowMatrix row-lazy contract on SGD/Momentum: rows a batch
    does not touch keep parameter AND slot bit-for-bit, even with weight
    decay on (decay folds only on touch)."""

    def _spec(self, decay=0.25):
        from paddle_tpu.core.parameters import ParamSpec
        from paddle_tpu.layers.attr import ParamAttr

        return ParamSpec(
            name="emb", shape=(8, 4),
            initializer=lambda k, s, d: jnp.zeros(s, d),
            decay_rate=decay, sparse=True,
            attr=ParamAttr(name="emb", sparse_update=True))

    def _grad(self, rs, rows):
        g = np.zeros((8, 4), np.float32)
        for r in rows:
            g[r] = rs.randn(4)
        return jnp.asarray(g)

    def test_momentum_untouched_rows_bit_identical(self):
        from paddle_tpu.optimizer import Momentum

        rs = np.random.RandomState(6)
        spec = self._spec()
        p = jnp.asarray(rs.randn(8, 4).astype(np.float32))
        opt = Momentum(momentum=0.9, learning_rate=0.1)
        state = opt.init({"emb": p}, {"emb": spec})
        # step 1 touches {1, 3} -> their velocity becomes nonzero
        p1, state = opt.apply({"emb": self._grad(rs, [1, 3])}, {"emb": p},
                              state, {"emb": spec})
        # step 2 touches {3, 5}: row 1 must keep param AND velocity
        p2, state2 = opt.apply({"emb": self._grad(rs, [3, 5])}, p1,
                               state, {"emb": spec})
        v1 = np.asarray(state["slots"]["emb"]["velocity"])
        v2 = np.asarray(state2["slots"]["emb"]["velocity"])
        np.testing.assert_array_equal(np.asarray(p2["emb"])[1],
                                      np.asarray(p1["emb"])[1])
        np.testing.assert_array_equal(v2[1], v1[1])
        assert np.any(v1[1] != 0)  # row 1 carried real momentum to freeze
        # touched rows DID move (decay + momentum on touch)
        assert np.any(np.asarray(p2["emb"])[3] != np.asarray(p1["emb"])[3])
        assert np.any(np.asarray(p2["emb"])[5] != np.asarray(p1["emb"])[5])

    def test_sgd_untouched_rows_bit_identical(self):
        from paddle_tpu.optimizer import SGD

        rs = np.random.RandomState(7)
        spec = self._spec()
        p = jnp.asarray(rs.randn(8, 4).astype(np.float32))
        opt = SGD(learning_rate=0.1)
        state = opt.init({"emb": p}, {"emb": spec})
        p1, _ = opt.apply({"emb": self._grad(rs, [2])}, {"emb": p}, state,
                          {"emb": spec})
        keep = [r for r in range(8) if r != 2]
        np.testing.assert_array_equal(np.asarray(p1["emb"])[keep],
                                      np.asarray(p)[keep])
        assert np.any(np.asarray(p1["emb"])[2] != np.asarray(p)[2])

    def test_dense_param_still_decays_everywhere(self):
        """A plain dense parameter under the same optimizer still gets the
        global decay fold — laziness is opt-in per ParamAttr."""
        from paddle_tpu.core.parameters import ParamSpec
        from paddle_tpu.optimizer import SGD

        spec = ParamSpec(name="w", shape=(4, 4),
                         initializer=lambda k, s, d: jnp.zeros(s, d),
                         decay_rate=0.5)
        p = jnp.ones((4, 4), jnp.float32)
        opt = SGD(learning_rate=0.1)
        state = opt.init({"w": p}, {"w": spec})
        p1, _ = opt.apply({"w": jnp.zeros((4, 4))}, {"w": p}, state,
                          {"w": spec})
        # zero grad but decay still applies to every entry
        np.testing.assert_allclose(np.asarray(p1["w"]), 0.95, rtol=1e-6)


def test_ctr_vocab_exceeds_one_device_budget():
    """The tentpole end-to-end: a wide&deep CTR whose embedding tables do
    NOT fit one device's HBM budget trains on a {data:2, model:4} mesh
    because row-sharding splits each table 4 ways.  Asserted BOTH ways:
    runtime census over addressable shards and the static GL-P-MEM byte
    model."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.analysis import memory as mem
    from paddle_tpu.layers import base
    from paddle_tpu.models.ctr import wide_and_deep_ctr
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.trainer.step import build_train_step

    base.reset_name_counters()
    vocab, emb_dim, wide_dim, bs = 6000, 32, 16, 16
    cost, _, _ = wide_and_deep_ctr(
        wide_dim=wide_dim, categorical_vocab_sizes=[vocab, vocab],
        embedding_size=emb_dim, hidden_sizes=(16,), pad_vocab_to=4)
    topo = paddle.topology.Topology(cost)
    params0 = paddle.parameters.create(topo).as_dict()
    specs = {s.name: s for s in topo.param_specs()}

    from paddle_tpu.parallel import mesh as mesh_mod
    ctx = mesh_mod.MeshContext(
        mesh=mesh_mod.make_mesh({"data": 2, "model": 4}))
    params = ctx.place_params(
        {k: jnp.array(v) for k, v in params0.items()}, specs)

    emb_names = sorted(n for n in params if n.startswith("emb_"))
    assert len(emb_names) == 2
    # vocab 6000 pads to 6000 (already % 4) — tables [6000, 32] f32
    table_total = sum(int(params[n].size) * params[n].dtype.itemsize
                     for n in emb_names)
    assert table_total == 2 * 6000 * emb_dim * 4

    # the budget one device gets: LESS than the tables want replicated,
    # MORE than the sharded layout needs
    budget = table_total * 2 // 3

    # (1) runtime census: bytes device 0 actually holds
    dev0 = ctx.mesh.devices.flat[0]
    census = 0
    for n, v in params.items():
        for sh in v.addressable_shards:
            if sh.device == dev0:
                census += int(np.prod(sh.data.shape)) * v.dtype.itemsize
    assert census < budget < table_total, (census, budget, table_total)
    # each table's shard on dev0 is exactly rows/4
    for n in emb_names:
        shard0 = [s for s in params[n].addressable_shards
                  if s.device == dev0]
        assert len(shard0) == 1 and shard0[0].data.shape == (1500, emb_dim)

    # (2) static GL-P-MEM byte model agrees without touching devices
    base_specs = {
        n: (P(*specs[n].sharding) if specs[n].sharding else P())
        for n in params
    }
    static_bytes = mem.params_bytes_per_device(params, ctx.mesh, base_specs)
    assert static_bytes < budget < mem.tree_bytes(params)
    assert static_bytes == census

    # and it trains: two steps, finite cost, tables stay sharded
    opt = Momentum(momentum=0.9, learning_rate=0.05)
    opt_state = ctx.replicate(opt.init(params, specs))
    states = ctx.replicate(topo.init_states())
    step = build_train_step(topo, opt, mesh=ctx)
    rs = np.random.default_rng(9)
    for _ in range(2):
        wide = np.zeros((bs, wide_dim), np.float32)
        for r in range(bs):
            wide[r, rs.integers(0, wide_dim, size=3)] = 1.0
        feed = ctx.shard_batch({
            "wide_input": jnp.asarray(wide),
            "cat_0": jnp.asarray(rs.integers(0, vocab, size=(bs,))),
            "cat_1": jnp.asarray(rs.integers(0, vocab, size=(bs,))),
            "label": jnp.asarray(rs.integers(0, 2, size=(bs,))),
        })
        params, opt_state, states, cost_v, _ = step(
            params, opt_state, states, feed, jax.random.key(0))
    assert np.isfinite(float(cost_v))
    post = 0
    for n in emb_names:
        for sh in params[n].addressable_shards:
            if sh.device == dev0:
                post += int(np.prod(sh.data.shape)) * params[n].dtype.itemsize
    assert post == table_total // 4  # still sharded after the step


def test_ctr_serving_routes_through_dense_batcher():
    """CTR inference behind DenseBatcher.from_inference — the serving leg
    of the train->serve loop for the sharded-embedding model."""
    from paddle_tpu.layers import base
    from paddle_tpu.models.ctr import wide_and_deep_ctr
    from paddle_tpu.serving.dense import DenseBatcher

    base.reset_name_counters()
    cost, predict, _ = wide_and_deep_ctr(
        wide_dim=16, categorical_vocab_sizes=[12, 10], embedding_size=4,
        hidden_sizes=(8,), pad_vocab_to=4)
    parameters = paddle.parameters.create(paddle.topology.Topology(cost))
    feeding = {"wide_input": 0, "cat_0": 1, "cat_1": 2}
    batcher = DenseBatcher.from_inference(
        predict, parameters, feeding=feeding, max_batch=8, max_wait_ms=20.0)
    try:
        rows = [([i % 16, (2 * i) % 16], i % 12, i % 10) for i in range(5)]
        pendings = [batcher.submit(r) for r in rows]
        outs = np.stack([p.result(30.0) for p in pendings])
        assert outs.shape[0] == 5
        assert np.all((outs >= 0.0) & (outs <= 1.0))
        # batching must be transparent: same numbers as direct inference
        from paddle_tpu.trainer.inference import Inference
        direct = np.asarray(Inference(predict, parameters).infer(
            rows, feeding=feeding))
        np.testing.assert_allclose(outs.reshape(direct.shape), direct,
                                   rtol=1e-6, atol=1e-6)
    finally:
        batcher.close()
