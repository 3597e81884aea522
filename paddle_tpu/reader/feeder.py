"""DataFeeder — successor of ``python/paddle/v2/data_feeder.py:28``
(DataProviderConverter → SWIG Arguments).  Converts a Python batch (list of
sample tuples) into the jit feed dict: dense arrays, int ids, or
SequenceBatch/NestedSequenceBatch for *_sequence types.  Sparse inputs are
densified host-side (the TPU path treats them as dense one/multi-hot rows —
embedding lookups take the integer-sequence path instead).

What comes out lives on the HOST: every non-sequence kind and the
uniform-length sequence path are one stack into the target dtype, a
``np.ndarray``.  Placement is the caller's — ``mesh.shard_batch`` sends
each shard to its own device, ``jit`` places what has no mesh — so a batch
crosses to the device once, never by way of the default device.  (The
ragged paths of ``core/lod.py`` still build device arrays; placement takes
either.)"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.core.lod import (
    SequenceBatch,
    bucket_length,
    from_nested_ragged,
    from_ragged,
)
from paddle_tpu.layers.data_type import DataKind, SeqType


def _densify_ids(rows, dim: int) -> np.ndarray:
    """id lists (one per row) -> dense 0/1 [len(rows), dim].

    One flat fancy-indexed scatter instead of a per-row Python loop: the
    row index of every id comes from ``np.repeat`` over the per-row
    counts, so the whole batch densifies in a single C-level assignment
    (duplicate ids within a row collapse to 1, as before)."""
    rows = [r if hasattr(r, "__len__") else list(r) for r in rows]
    n = len(rows)
    dense = np.zeros((n, dim), np.float32)
    counts = np.fromiter((len(r) for r in rows), np.int64, count=n)
    total = int(counts.sum())
    if total:
        cols = np.fromiter((int(j) for r in rows for j in r), np.int64,
                           count=total)
        dense[np.repeat(np.arange(n), counts), cols] = 1.0
    return dense


def _densify_pairs(rows, dim: int) -> np.ndarray:
    """(index, value) pair lists -> dense [len(rows), dim].

    One flat fancy-indexed assignment for the whole batch.  Duplicate
    indices within a row keep the seed's last-write-wins semantic
    (numpy applies repeated-index assignments in order), so existing
    sparse_float datasets produce bit-identical feeds.  The per-row
    ``reshape(len(r), 2)`` keeps the seed's fail-fast on malformed
    pairs (arity != 2) — a flat scan would silently misalign every
    later pair instead."""
    rows = [r if hasattr(r, "__len__") else list(r) for r in rows]
    n = len(rows)
    dense = np.zeros((n, dim), np.float32)
    counts = np.fromiter((len(r) for r in rows), np.int64, count=n)
    if int(counts.sum()):
        flat = np.concatenate(
            [np.asarray(r, dtype=np.float64).reshape(len(r), 2)
             for r in rows if len(r)], axis=0)
        cols = flat[:, 0].astype(np.int64)
        if not np.array_equal(cols, flat[:, 0]):
            # the seed's per-element indexing raised on j=1.5; a silent
            # truncation here would train on corrupted features
            raise IndexError(
                "sparse_float pair indices must be integers; got a "
                "fractional index")
        dense[np.repeat(np.arange(n), counts),
              cols] = flat[:, 1].astype(np.float32)
    return dense


def _stack(col, dtype, staging: dict | None = None, name=None) -> np.ndarray:
    """A column of samples as ONE array of ``dtype``: the batch's single
    host copy.  With ``staging`` a column of ndarrays is copied into the
    array this slot filled on the dict's last trip, as long as the shape
    still fits (a new one is kept otherwise).  Pages the process already
    holds take a 154 MB image batch in 16 ms; a fresh allocation of that
    size faults them in one by one and takes 167 ms (chip host, PR 25)."""
    if staging is None or not col or not isinstance(col[0], np.ndarray):
        return np.asarray(col, dtype=dtype)
    shape = (len(col),) + col[0].shape
    buf = staging.get(name)
    if buf is None or buf.shape != shape:
        buf = staging[name] = np.empty(shape, dtype)
    return np.stack(col, out=buf, casting="unsafe")


def _stack_uniform(col, dtype) -> np.ndarray | None:
    """[B] list of equal-length samples -> one stacked [B, T, ...] array
    via a single conversion, or None when the column is ragged/opaque —
    the vectorized fast path for sequence columns."""
    try:
        first_len = len(col[0])
        if all(len(s) == first_len for s in col):
            arr = np.asarray(col, dtype=dtype)
            return arr if arr.ndim >= 2 else None
    except (TypeError, ValueError):
        pass
    return None


def parse_seq_buckets(spec) -> tuple[int, ...] | None:
    """Bucket-table spec -> sorted tuple or None (use the default table).
    Accepts a comma-separated string (the ``--seq_buckets`` CLI /
    ``PADDLE_TPU_SEQ_BUCKETS`` env form, e.g. ``"8,16,32,64"``), any
    int sequence, or empty/None."""
    if spec is None:
        return None
    if isinstance(spec, str):
        spec = [s for s in spec.replace(" ", "").split(",") if s]
    table = tuple(sorted(int(b) for b in spec))
    return table or None


def padding_stats(feed: Mapping) -> tuple[int, int]:
    """(padded, total) timesteps across the SequenceBatch slots of a feed
    — the numerator/denominator of the per-step ``padding_ratio``
    telemetry field.  Host-side and cheap: only the tiny [B] length
    vectors are read."""
    padded = total = 0
    for v in feed.values():
        length = getattr(v, "length", None)
        data = getattr(v, "data", None)
        if length is None or data is None:
            continue
        try:
            lens = np.asarray(length)
            t = int(data.shape[1])
            total += int(lens.size) * t
            padded += int(np.sum(np.maximum(t - lens, 0)))
        except (TypeError, ValueError, IndexError):
            continue  # exotic slot shapes carry no padding signal
    return padded, total


class DataFeeder:
    def __init__(self, data_types: Mapping[str, object] | Sequence[tuple],
                 feeding: Mapping[str, int] | Sequence[str] | None = None,
                 seq_buckets: Sequence[int] | None = None):
        """data_types: {layer_name: InputType} or [(name, InputType), ...];
        feeding: {layer_name: index in sample tuple} (defaults to order);
        seq_buckets: override the default length-quantization table for
        sequence slots — MUST match the reader's ``bucket_by_length``
        table so every batch of a bucket compiles to one static shape."""
        if not isinstance(data_types, Mapping):
            data_types = dict(data_types)
        self.types = dict(data_types)
        self.seq_buckets = (tuple(sorted(int(b) for b in seq_buckets))
                            if seq_buckets else None)
        if feeding is None:
            self.feeding = {n: i for i, n in enumerate(self.types)}
        elif isinstance(feeding, Mapping):
            self.feeding = dict(feeding)
        else:
            self.feeding = {n: i for i, n in enumerate(feeding)}

    def __call__(self, batch, staging: dict | None = None):
        return self.feed(batch, staging)

    def feed(self, batch, staging: dict | None = None) -> dict:
        """batch (list of samples) -> {layer name: host array or sequence
        batch}.  ``staging``, a dict the CALLER owns and passes again, lets
        the dense and integer slots reuse their arrays from its last trip
        (:func:`_stack`); the caller must be done with that trip's feed —
        its transfer fenced, nothing else holding the arrays — first."""
        out = {}
        for name, itype in self.types.items():
            enforce(
                name in self.feeding,
                f"feeding map is missing data layer {name!r} "
                f"(feeding keys: {sorted(self.feeding)})",
            )
            idx = self.feeding[name]
            # providers may yield dict samples keyed by layer name
            # (PyDataProvider2.py supports both; dataprovider_bow yields
            # {'word': ..., 'label': ...})
            col = [sample[name] if isinstance(sample, Mapping)
                   else sample[idx] for sample in batch]
            out[name] = self._convert(col, itype, name, staging)
        return out

    def _convert(self, col, itype, name, staging=None):
        kind, seq = itype.kind, itype.seq_type
        if seq == SeqType.NO_SEQUENCE:
            if kind == DataKind.DENSE:
                arr = _stack(col, np.float32, staging, name).reshape(
                    len(col), -1)
                enforce(
                    arr.shape[1] == itype.dim,
                    f"data layer {name!r} expects dim {itype.dim}, "
                    f"got samples of dim {arr.shape[1]}",
                )
                return arr
            if kind == DataKind.INTEGER:
                return _stack(col, np.int32, staging, name).reshape(len(col))
            if kind == DataKind.SPARSE_BINARY:
                return _densify_ids(col, itype.dim)
            if kind == DataKind.SPARSE_FLOAT:
                return _densify_pairs(col, itype.dim)
        elif seq == SeqType.SEQUENCE:
            if kind in (DataKind.INTEGER, DataKind.DENSE):
                # uniform-length columns (the common synthetic/bucketed
                # case): ONE stacked conversion + one bucket-pad alloc
                # instead of a per-row asarray loop through pad_sequences
                dt = np.int32 if kind == DataKind.INTEGER else np.float32
                stacked = _stack_uniform(col, dt)
                if stacked is not None:
                    t_true = stacked.shape[1]
                    t = (bucket_length(t_true) if self.seq_buckets is None
                         else bucket_length(t_true, self.seq_buckets))
                    if t != t_true:
                        padded = np.zeros(
                            (len(col), t) + stacked.shape[2:], dt)
                        padded[:, :t_true] = stacked
                        stacked = padded
                    return SequenceBatch(
                        data=stacked,
                        length=np.full((len(col),), t_true, np.int32))
            if kind == DataKind.INTEGER:
                seqs = [np.asarray(s, dtype=np.int32) for s in col]
            elif kind == DataKind.SPARSE_BINARY:
                # per-timestep id lists -> dense [T, dim] rows.  KNOWN
                # INEFFICIENCY for very wide slots (sequence_tagging's
                # 76k-dim features build ~40 MB/batch of mostly zeros):
                # the byte-lean alternative is an embedding-style gather
                # of weight rows at the ids, which needs the consuming
                # projection to accept id lists — tracked as future work
                seqs = [_densify_ids(s, itype.dim) for s in col]
            elif kind == DataKind.SPARSE_FLOAT:
                seqs = [_densify_pairs(s, itype.dim) for s in col]
            else:
                seqs = [np.asarray(s, dtype=np.float32) for s in col]
            return from_ragged(seqs, buckets=self.seq_buckets)
        elif seq == SeqType.SUB_SEQUENCE:
            dt = np.int32 if kind == DataKind.INTEGER else np.float32
            nested = [[np.asarray(s, dtype=dt) for s in subs] for subs in col]
            return from_nested_ragged(nested)
        enforce(False, f"unsupported input type for {name!r}: {itype}")
