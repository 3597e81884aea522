"""CRF + CTC numerics — mirrors the reference's compare-two-implementations
test strategy (``test_LinearChainCRF.cpp``, ``test_CTCLayerGrad.cpp``,
``test_WarpCTCLayer.cpp``): brute-force enumeration for CRF, torch's
``ctc_loss`` as the independent oracle for CTC."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.lod import SequenceBatch
from paddle_tpu.ops import crf as crf_ops
from paddle_tpu.ops import ctc as ctc_ops


def _brute_force_crf(x, w, length):
    """Enumerate all label paths for one sequence. x: [T, C], w: [C+2, C]."""
    a, b, trans = w[0], w[1], w[2:]
    t, c = length, x.shape[1]
    scores = {}
    for path in itertools.product(range(c), repeat=t):
        s = a[path[0]] + b[path[-1]] + sum(x[i, path[i]] for i in range(t))
        s += sum(trans[path[i], path[i + 1]] for i in range(t - 1))
        scores[path] = s
    logz = np.logaddexp.reduce(np.array(list(scores.values())))
    best = max(scores, key=scores.get)
    return scores, logz, best


class TestCRF:
    def setup_method(self, _):
        rs = np.random.RandomState(0)
        self.c = 3
        self.t = 4
        self.x = rs.randn(2, self.t, self.c).astype(np.float32)
        self.w = rs.randn(self.c + 2, self.c).astype(np.float32) * 0.5
        self.lengths = np.array([4, 3], np.int32)
        self.emis = SequenceBatch(jnp.asarray(self.x),
                                  jnp.asarray(self.lengths))

    def test_log_partition_matches_brute_force(self):
        got = np.asarray(crf_ops.crf_log_partition(self.emis,
                                                   jnp.asarray(self.w)))
        for i in range(2):
            _, logz, _ = _brute_force_crf(self.x[i], self.w, self.lengths[i])
            np.testing.assert_allclose(got[i], logz, rtol=1e-5)

    def test_path_score_and_nll(self):
        rs = np.random.RandomState(1)
        y = rs.randint(0, self.c, (2, self.t)).astype(np.int32)
        labels = SequenceBatch(jnp.asarray(y), jnp.asarray(self.lengths))
        score = np.asarray(crf_ops.crf_path_score(self.emis, labels,
                                                  jnp.asarray(self.w)))
        nll = np.asarray(crf_ops.crf_nll(self.emis, labels,
                                         jnp.asarray(self.w)))
        for i in range(2):
            scores, logz, _ = _brute_force_crf(self.x[i], self.w,
                                               self.lengths[i])
            want = scores[tuple(y[i, :self.lengths[i]])]
            np.testing.assert_allclose(score[i], want, rtol=1e-5)
            np.testing.assert_allclose(nll[i], logz - want, rtol=1e-4)
            assert nll[i] > 0  # -log p, p < 1

    def test_viterbi_matches_brute_force(self):
        path = crf_ops.crf_decode(self.emis, jnp.asarray(self.w))
        got = np.asarray(path.data)
        for i in range(2):
            _, _, best = _brute_force_crf(self.x[i], self.w, self.lengths[i])
            np.testing.assert_array_equal(got[i, :self.lengths[i]],
                                          np.array(best))

    def test_crf_grad_finite(self):
        rs = np.random.RandomState(1)
        y = rs.randint(0, self.c, (2, self.t)).astype(np.int32)
        labels = SequenceBatch(jnp.asarray(y), jnp.asarray(self.lengths))

        def loss(w, x):
            return jnp.mean(crf_ops.crf_nll(
                SequenceBatch(x, self.emis.length), labels, w))

        gw, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(jnp.asarray(self.w),
                                                jnp.asarray(self.x))
        assert np.all(np.isfinite(np.asarray(gw)))
        assert np.all(np.isfinite(np.asarray(gx)))
        # padded timestep of row 1 must not receive gradient
        np.testing.assert_allclose(np.asarray(gx)[1, 3], 0.0, atol=1e-7)


class TestCTC:
    def _torch_ctc(self, log_probs, in_lens, labels, lbl_lens, blank):
        import torch
        import torch.nn.functional as F

        lp = torch.tensor(np.asarray(log_probs)).permute(1, 0, 2)  # [T,B,V]
        return F.ctc_loss(
            lp, torch.tensor(np.asarray(labels)),
            torch.tensor(np.asarray(in_lens)),
            torch.tensor(np.asarray(lbl_lens)),
            blank=blank, reduction="none", zero_infinity=False).numpy()

    @pytest.mark.parametrize("blank", [0, 4])
    def test_matches_torch(self, blank):
        rs = np.random.RandomState(2)
        b, t, v, l = 3, 7, 5, 3
        logits = rs.randn(b, t, v).astype(np.float32)
        log_probs = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        in_lens = np.array([7, 5, 6], np.int32)
        lbl_lens = np.array([3, 2, 1], np.int32)
        labels = np.zeros((b, l), np.int32)
        for i in range(b):
            choices = [k for k in range(v) if k != blank]
            labels[i, :lbl_lens[i]] = rs.choice(choices, lbl_lens[i])
        got = np.asarray(ctc_ops.ctc_loss(
            log_probs, jnp.asarray(in_lens), jnp.asarray(labels),
            jnp.asarray(lbl_lens), blank=blank))
        want = self._torch_ctc(log_probs, in_lens, labels, lbl_lens, blank)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_grad_matches_torch(self):
        import torch
        import torch.nn.functional as F

        rs = np.random.RandomState(5)
        b, t, v, l = 2, 6, 4, 2
        logits = rs.randn(b, t, v).astype(np.float32)
        in_lens = np.array([6, 4], np.int32)
        lbl_lens = np.array([2, 1], np.int32)
        labels = np.array([[1, 2], [3, 0]], np.int32)

        def loss_jax(lg):
            lp = jax.nn.log_softmax(lg, axis=-1)
            return jnp.sum(ctc_ops.ctc_loss(
                lp, jnp.asarray(in_lens), jnp.asarray(labels),
                jnp.asarray(lbl_lens), blank=0))

        g_jax = np.asarray(jax.jit(jax.grad(loss_jax))(jnp.asarray(logits)))

        lg_t = torch.tensor(logits, requires_grad=True)
        lp_t = F.log_softmax(lg_t, dim=-1).permute(1, 0, 2)
        loss_t = F.ctc_loss(lp_t, torch.tensor(labels),
                            torch.tensor(in_lens), torch.tensor(lbl_lens),
                            blank=0, reduction="sum")
        loss_t.backward()
        np.testing.assert_allclose(g_jax, lg_t.grad.numpy(), rtol=1e-3,
                                   atol=1e-4)

    def test_greedy_decode(self):
        # [blank a a blank b] -> "a b"
        v = 3  # blank=0, a=1, b=2
        frames = np.array([[0, 1, 1, 0, 2]], np.int32)
        lp = np.full((1, 5, v), -10.0, np.float32)
        for t, k in enumerate(frames[0]):
            lp[0, t, k] = 0.0
        ids, lens = ctc_ops.ctc_greedy_decode(jnp.asarray(lp),
                                              jnp.asarray([5]))
        assert int(lens[0]) == 2
        np.testing.assert_array_equal(np.asarray(ids)[0, :2], [1, 2])


def test_crf_layers_end_to_end():
    """crf + crf_decoding layer surface, shared transitions by param name."""
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import data_type
    from paddle_tpu.layers.attr import ParamAttr
    from paddle_tpu.layers.extras import crf, crf_decoding

    c = 4
    feat = layer.data(name="feat", type=data_type.dense_vector_sequence(8))
    emis = layer.fc(input=feat, size=c, act=None, bias_attr=False,
                    name="emission")
    lbl = layer.data(name="lbl", type=data_type.integer_value_sequence(c))
    cost = crf(input=emis, label=lbl, size=c,
               param_attr=ParamAttr(name="crf_w"))
    decode = crf_decoding(input=emis, size=c,
                          param_attr=ParamAttr(name="crf_w"))
    topo = Topology([cost, decode])
    params = Parameters.from_specs(topo.param_specs(),
                                   key=jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    feed = {
        "feat": SequenceBatch(jnp.asarray(rs.randn(2, 5, 8), jnp.float32),
                              jnp.asarray([5, 3])),
        "lbl": SequenceBatch(jnp.asarray(rs.randint(0, c, (2, 5))),
                             jnp.asarray([5, 3])),
    }
    vals, _ = topo.forward(params.as_dict(), {}, feed, is_train=True)
    assert np.isfinite(float(vals[cost.name]))
    path = vals[decode.name]
    assert np.asarray(path.data).shape == (2, 5)
    # one shared transition parameter
    assert sum(1 for s in topo.param_specs() if s.name == "crf_w") == 1
