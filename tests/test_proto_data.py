"""ProtoDataProvider parity: the binary DataFormat.proto stream
(varint-delimited DataHeader + DataSamples, ProtoReader.h framing) is
read back into trainer feeds, sequences regrouped by ``is_beginning``,
and a TrainData(ProtoData(...)) config trains end-to-end through the
CLI.  MultiData zips two sources into one sample stream."""

from __future__ import annotations

import os
import textwrap

import numpy as np

from paddle_tpu.proto.build import message_class
from paddle_tpu.reader import proto_data as pdata

DataHeader = message_class("DataHeader")
DataSample = message_class("DataSample")


def _mk_header(slots):
    h = DataHeader()
    for t, d in slots:
        sd = h.slot_defs.add()
        sd.type = t
        sd.dim = d
    return h


def _dense_index_file(path, n=32, dim=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    header = _mk_header([(pdata.VECTOR_DENSE, dim), (pdata.INDEX, classes)])
    samples = []
    for _ in range(n):
        y = int(rng.integers(0, classes))
        x = rng.normal(size=(dim,)).astype(np.float32) * 0.1
        x[y * 2:(y + 1) * 2] += 1.0
        s = DataSample()
        vs = s.vector_slots.add()
        vs.values.extend(x.tolist())
        s.id_slots.append(y)
        samples.append(s)
    pdata.write_proto_stream(path, header, samples)


def test_proto_stream_roundtrip(tmp_path):
    p = str(tmp_path / "d.bin")
    _dense_index_file(p, n=5)
    header, samples = pdata.read_proto_stream(p)
    assert len(header.slot_defs) == 2 and len(samples) == 5
    assert header.slot_defs[0].dim == 8
    rows = list(pdata.proto_reader([p])())
    assert len(rows) == 5
    x, y = rows[0]
    assert len(x) == 8 and isinstance(y, int)
    # gzip variant
    pz = str(tmp_path / "d.bin.gz")
    _dense_index_file(pz, n=5)
    assert len(list(pdata.proto_reader([pz])())) == 5


def test_proto_sequences_regroup(tmp_path):
    header = _mk_header([(pdata.INDEX, 10)])
    samples = []
    for begin, val in [(True, 1), (False, 2), (False, 3),
                       (True, 4), (False, 5)]:
        s = DataSample()
        s.is_beginning = begin
        s.id_slots.append(val)
        samples.append(s)
    p = str(tmp_path / "seq.bin")
    pdata.write_proto_stream(p, header, samples)
    rows = list(pdata.proto_reader([p])())
    assert rows == [([1, 2, 3],), ([4, 5],)]
    (t,) = pdata.input_types_from_header(p)
    assert t.seq_type != 0  # sequence detected


def test_cli_trains_from_proto_data(tmp_path):
    _dense_index_file(str(tmp_path / "train.bin"), n=256)
    (tmp_path / "train.list").write_text(str(tmp_path / "train.bin") + "\n")
    cfg = tmp_path / "proto.conf"
    cfg.write_text(textwrap.dedent(f"""
        from paddle.trainer_config_helpers import *

        TrainData(ProtoData(files='{tmp_path}/train.list'))
        settings(batch_size=32, learning_rate=1e-2,
                 learning_method=AdamOptimizer())
        x = data_layer(name='x', size=8)
        pred = fc_layer(input=x, size=4, act=SoftmaxActivation())
        lbl = data_layer(name='label', size=4)
        outputs(classification_cost(input=pred, label=lbl))
    """))
    from paddle_tpu.trainer import cli

    rc = cli.main(["--config", str(cfg), "--job", "train",
                   "--num_passes", "4"])
    assert rc == 0


def test_multi_reader_zips_sources(tmp_path):
    p1, p2 = str(tmp_path / "a.bin"), str(tmp_path / "b.bin")
    _dense_index_file(p1, n=6, seed=1)
    _dense_index_file(p2, n=9, seed=2)
    r1 = pdata.proto_reader([p1])
    r2 = pdata.proto_reader([p2])
    rows = list(pdata.multi_reader([r1, r2])())
    assert len(rows) == 6  # shortest source bounds the zip
    assert len(rows[0]) == 4  # 2 slots from each source


def test_show_pb_and_torch2paddle(tmp_path, capsys):
    """The small-utils family: show_pb prints the stream; torch2paddle
    writes reference-binary params a Parameters object loads back."""
    p = str(tmp_path / "d.bin")
    _dense_index_file(p, n=2)
    from paddle_tpu.utils import show_pb

    assert show_pb.main([p]) == 0
    out = capsys.readouterr().out
    assert "slot_defs" in out and "vector_slots" in out

    import torch

    from paddle_tpu.core.parameters import load_reference_param
    from paddle_tpu.utils.torch2paddle import convert_state_dict

    state = {"fc.weight": torch.arange(6, dtype=torch.float32).reshape(2, 3),
             "fc.bias": torch.ones(2)}
    written = convert_state_dict(state, str(tmp_path / "params"))
    assert sorted(written) == ["fc_bias", "fc_weight"]
    w = load_reference_param(str(tmp_path / "params" / "fc_weight"))
    # [out=2, in=3] transposed to paddle [in, out] layout
    np.testing.assert_array_equal(
        w.reshape(3, 2), np.arange(6, dtype=np.float32).reshape(2, 3).T)


def test_image_multiproc_transformer(tmp_path):
    from PIL import Image

    from paddle_tpu.utils.image_multiproc import MultiProcessImageTransformer

    rows = []
    rng = np.random.default_rng(0)
    for i in range(4):
        p = tmp_path / f"im{i}.png"
        Image.fromarray(
            rng.integers(0, 255, size=(40, 30, 3), dtype=np.uint8)).save(p)
        rows.append((str(p), i))
    tf = MultiProcessImageTransformer(procnum=2, resize_size=32, crop_size=24)
    out = list(tf.run(rows))
    assert [lab for _, lab in out] == [0, 1, 2, 3]  # order preserved
    assert out[0][0].shape == (3, 24, 24)


def test_length_one_sequences_keep_list_shape(tmp_path):
    """A sequence dataset containing a length-1 sequence must still yield
    per-timestep LISTS for every row (review finding r4)."""
    header = _mk_header([(pdata.INDEX, 10)])
    samples = []
    for begin, val in [(True, 1), (False, 2), (True, 7), (True, 3),
                       (False, 4)]:
        s = DataSample()
        s.is_beginning = begin
        s.id_slots.append(val)
        samples.append(s)
    p = str(tmp_path / "seq1.bin")
    pdata.write_proto_stream(p, header, samples)
    rows = list(pdata.proto_reader([p], sequential=True)())
    assert rows == [([1, 2],), ([7],), ([3, 4],)]


def test_proto_config_emits_reference_dataconfig(tmp_path):
    """TrainData(ProtoData(...)) serializes as DataConfig.type='proto'
    with usage_ratio, like the reference's config_parser emission."""
    import textwrap

    from paddle_tpu.trainer.config_parser import parse_config

    cfg = tmp_path / "p.conf"
    cfg.write_text(textwrap.dedent("""
        from paddle.trainer_config_helpers import *
        TrainData(ProtoData(files='train.list', usage_ratio=0.5))
        settings(batch_size=8, learning_rate=1e-2)
        x = data_layer(name='x', size=4)
        pred = fc_layer(input=x, size=2, act=SoftmaxActivation())
        lbl = data_layer(name='label', size=2)
        outputs(classification_cost(input=pred, label=lbl))
    """))
    parsed = parse_config(str(cfg), "")
    dc = parsed.trainer_config.data_config
    assert dc.type == "proto"
    assert dc.files == "train.list"
    assert abs(dc.usage_ratio - 0.5) < 1e-9


def test_cli_trains_from_multi_data(tmp_path):
    """MultiData: two ProtoData sub-providers zip into one sample stream
    through the CLI (MultiDataProvider parity), and the TrainerConfig
    emits nested sub_data_configs."""
    import textwrap

    # source A: dense features; source B: the label
    ha = _mk_header([(pdata.VECTOR_DENSE, 8)])
    hb = _mk_header([(pdata.INDEX, 4)])
    rng = np.random.default_rng(0)
    sa, sb = [], []
    for _ in range(128):
        y = int(rng.integers(0, 4))
        x = rng.normal(size=(8,)).astype(np.float32) * 0.1
        x[y * 2:(y + 1) * 2] += 1.0
        s = DataSample()
        s.vector_slots.add().values.extend(x.tolist())
        sa.append(s)
        s = DataSample()
        s.id_slots.append(y)
        sb.append(s)
    pdata.write_proto_stream(str(tmp_path / "a.bin"), ha, sa)
    pdata.write_proto_stream(str(tmp_path / "b.bin"), hb, sb)
    (tmp_path / "a.list").write_text(str(tmp_path / "a.bin") + "\n")
    (tmp_path / "b.list").write_text(str(tmp_path / "b.bin") + "\n")
    cfg = tmp_path / "multi.conf"
    cfg.write_text(textwrap.dedent(f"""
        from paddle.trainer_config_helpers import *

        TrainData(MultiData([ProtoData(files='{tmp_path}/a.list'),
                             ProtoData(files='{tmp_path}/b.list')]))
        settings(batch_size=32, learning_rate=1e-2,
                 learning_method=AdamOptimizer())
        x = data_layer(name='x', size=8)
        pred = fc_layer(input=x, size=4, act=SoftmaxActivation())
        lbl = data_layer(name='label', size=4)
        outputs(classification_cost(input=pred, label=lbl))
    """))
    from paddle_tpu.trainer import cli
    from paddle_tpu.trainer.config_parser import parse_config

    parsed = parse_config(str(cfg), "")
    dc = parsed.trainer_config.data_config
    assert dc.type == "multi" and len(dc.sub_data_configs) == 2
    assert dc.sub_data_configs[0].type == "proto"

    rc = cli.main(["--config", str(cfg), "--job", "train",
                   "--num_passes", "4"])
    assert rc == 0


def test_preprocess_img_dataset_roundtrip(tmp_path):
    """preprocess_img: label-dir tree -> batched npz + labels/meta, and
    the reader streams (image, label) samples back."""
    from PIL import Image

    from paddle_tpu.utils.preprocess_img import (
        ImageClassificationDatasetCreater,
        batch_reader,
    )

    rng = np.random.default_rng(0)
    for lab in ("cat", "dog"):
        os.makedirs(tmp_path / lab)
        for i in range(6):
            Image.fromarray(rng.integers(
                0, 255, size=(40, 30, 3), dtype=np.uint8)).save(
                tmp_path / lab / f"{i}.png")
    out = ImageClassificationDatasetCreater(
        str(tmp_path), 16, test_ratio=0.25).create_dataset()
    assert (open(os.path.join(out, "labels.txt")).read().split()
            == ["cat", "dog"])
    train = list(batch_reader(os.path.join(out, "train"))())
    test = list(batch_reader(os.path.join(out, "test"))())
    assert len(train) == 9 and len(test) == 3
    im, lab = train[0]
    assert im.shape == (3, 16, 16) and lab in (0, 1)


def test_sparse_value_slot_reader_feeder_roundtrip(tmp_path):
    """VECTOR_SPARSE_VALUE slots yield (index, value) PAIRS — the v2
    sparse_float convention the feeder densifies (an
    (ids_list, values_list) tuple would unpack wrong for 2-id timesteps)."""
    from paddle_tpu.layers import data_type as dt
    from paddle_tpu.reader.feeder import DataFeeder

    p = str(tmp_path / "sv.bin")
    header = _mk_header([(pdata.VECTOR_SPARSE_VALUE, 16),
                         (pdata.INDEX, 4)])
    samples = []
    truth = []
    rng = np.random.default_rng(0)
    for _ in range(6):
        ids = sorted(rng.choice(16, size=2, replace=False).tolist())
        vals = rng.normal(size=(2,)).astype(np.float32).tolist()
        truth.append((ids, vals))
        s = DataSample()
        vs = s.vector_slots.add()
        vs.ids.extend(ids)
        vs.values.extend(vals)
        s.id_slots.append(1)
        samples.append(s)
    pdata.write_proto_stream(p, header, samples)

    rows = list(pdata.proto_reader([p])())
    assert len(rows) == 6
    pairs, label = rows[0]
    # exactly-two-ids timestep: must be [(i0,v0),(i1,v1)], not (ids, vals)
    assert len(pairs) == 2 and len(pairs[0]) == 2
    assert [i for i, _ in pairs] == truth[0][0]
    np.testing.assert_allclose([v for _, v in pairs], truth[0][1], rtol=1e-6)

    types = pdata.input_types_from_header(p)
    assert types[0].kind == dt.DataKind.SPARSE_FLOAT
    feeder = DataFeeder({"sx": types[0], "sy": types[1]})
    feed = feeder(rows)
    dense = np.asarray(feed["sx"])
    assert dense.shape == (6, 16)
    for r, (ids, vals) in enumerate(truth):
        np.testing.assert_allclose(dense[r, ids], vals, rtol=1e-6)
        assert float(np.abs(dense[r]).sum()) == float(
            np.abs(np.asarray(vals)).sum()) or np.isclose(
            np.abs(dense[r]).sum(), np.abs(np.asarray(vals)).sum(),
            rtol=1e-5)


def test_usage_ratio_subsamples_sequences(tmp_path):
    """usage_ratio < 1 consumes only that fraction of each file's
    sequences (ProtoDataProvider.cpp:397-399 truncation semantics)."""
    p = str(tmp_path / "ur.bin")
    _dense_index_file(p, n=40)
    full = list(pdata.proto_reader([p])())
    half = list(pdata.proto_reader([p], usage_ratio=0.5)())
    quarter = list(pdata.proto_reader([p], usage_ratio=0.25)())
    assert len(full) == 40 and len(half) == 20 and len(quarter) == 10
    assert len(list(pdata.proto_reader([p], usage_ratio=1.0)())) == 40
    # the shuffle precedes the cut (reference sequenceLoop order), so
    # repeated passes sample DIFFERENT subsets — no fixed tail is starved
    full_keys = {tuple(row[0]) for row in full}
    seen: set = set()
    r = pdata.proto_reader([p], usage_ratio=0.5)
    for _ in range(12):
        for row in r():
            assert tuple(row[0]) in full_keys
            seen.add(tuple(row[0]))
    assert len(seen) > 20, "usage_ratio subsets never rotate"
