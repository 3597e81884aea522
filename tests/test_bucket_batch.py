"""calc_batch_size / bucketed dynamic batching (PyDataProvider2.py:367-374
semantics on static XLA shapes)."""

import textwrap

import numpy as np


def test_bucket_batch_cost_balances_by_length():
    from paddle_tpu.reader.decorator import bucket_batch

    rng = np.random.default_rng(0)
    samples = []
    for _ in range(200):
        n = int(rng.integers(4, 120))
        samples.append(([0] * n, n))  # (sequence, label)

    token_budget = 256

    def calc(sample):
        return len(sample[0])

    batches = list(bucket_batch(lambda: iter(samples), token_budget,
                                calc_batch_size=calc)())
    assert sum(len(b) for b in batches) == len(samples)
    from paddle_tpu.core.lod import bucket_length

    sizes_by_bucket = {}
    for b in batches[:-4]:  # tail flush batches may be under budget
        lens = [len(s[0]) for s in b]
        # one static shape per batch: all members share the bucket
        bkt = {bucket_length(n) for n in lens}
        assert len(bkt) == 1
        # approximately cost-balanced around the token budget (the first
        # flush pins the bucket's batch size; later costs fluctuate with
        # the length mix inside the bucket)
        assert token_budget * 0.5 <= sum(lens) < token_budget + 128
        sizes_by_bucket.setdefault(bkt.pop(), set()).add(len(b))
    # shape discipline: ONE batch size per bucket -> bounded jit signatures
    for bkt, sizes in sizes_by_bucket.items():
        assert len(sizes) == 1, (bkt, sizes)
    # long sequences ride in smaller batches than short ones
    short = [len(b) for b in batches if bucket_length(len(b[0][0])) <= 16]
    long_ = [len(b) for b in batches if bucket_length(len(b[0][0])) >= 128]
    if short and long_:
        assert min(short) > max(long_)


def test_cli_trains_with_calc_batch_size(tmp_path, capsys):
    """An NMT-style variable-length provider declaring calc_batch_size
    trains under the CLI with bucketed cost-balanced batches."""
    from paddle_tpu.trainer import cli

    cfg = tmp_path / "seq.conf"
    cfg.write_text(textwrap.dedent("""
        from paddle.trainer_config_helpers import *

        define_py_data_sources2(
            train_list='{d}/train.list', test_list=None,
            module='seq_provider', obj='process')
        settings(batch_size=128, learning_rate=1e-2,
                 learning_method=AdamOptimizer())

        words = data_layer(name='words', size=32)
        emb = embedding_layer(input=words, size=16)
        pooled = pooling_layer(input=emb)
        predict = fc_layer(input=pooled, size=2, act=SoftmaxActivation())
        lbl = data_layer(name='label', size=2)
        outputs(classification_cost(input=predict, label=lbl))
    """).format(d=tmp_path))
    (tmp_path / "seq_provider.py").write_text(textwrap.dedent("""
        import numpy as np
        from paddle.trainer.PyDataProvider2 import (
            provider, integer_value_sequence, integer_value)

        @provider(input_types={'words': integer_value_sequence(32),
                               'label': integer_value(2)},
                  calc_batch_size=lambda sample: len(sample[0]),
                  pool_size=512)
        def process(settings, filename):
            rng = np.random.default_rng(0)
            for _ in range(160):
                n = int(rng.integers(3, 40))
                y = int(rng.integers(0, 2))
                words = rng.integers(y * 16, y * 16 + 16, size=n)
                yield [int(w) for w in words], y
    """))
    (tmp_path / "train.list").write_text("f-0\n")

    rc = cli.main(["--config", str(cfg), "--job", "train",
                   "--num_passes", "2", "--log_period", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    costs = [float(ln.split("Cost ")[1].split(",")[0])
             for ln in out.splitlines() if "Cost " in ln]
    assert costs and costs[-1] < costs[0]
