"""Asynchronous device-feed pipeline — the overlap layer between a batch
reader and the jitted train step.

The synchronous v2 loop runs ``DataFeeder.feed`` (host numpy), device
placement (``mesh.shard_batch``) and the step strictly in sequence, so the
TPU idles during every Python-side conversion and the host idles during
every step.  :class:`DevicePrefetcher` moves the host half onto a worker
thread and keeps a bounded queue (default depth 2) of device-resident
sharded feeds staged ahead of the consumer — ``jax.device_put`` is async,
so by the time the step loop dequeues a feed its transfer has typically
already overlapped prior compute.

Both iterators here yield :class:`FeedBatch` ``(examples, feed,
input_wait_ms)`` so the trainer accounts input wait identically for the
overlapped and the synchronous path:

- ``DevicePrefetcher`` — reader + feeder + shard on a worker thread;
  ``input_wait_ms`` is the time the consumer spent blocked on the queue
  (0 when the pipeline keeps up).  With span tracing on, the worker's
  lane shows where a batch's production time goes: ``prefetch`` ⊃
  ``feed_read`` / ``feed_convert`` / ``feed_place`` / ``feed_stage``
  (``telemetry/tracing.py``).
- ``SynchronousFeeds`` — the seed behavior (everything inline on the
  consumer thread); ``input_wait_ms`` is the full conversion+placement
  time, all of it on the critical path.

Error/shutdown contract (the parts thread pipelines usually get wrong):

- a reader or feeder exception is re-raised at the consumer's ``next()``,
  not swallowed into a truncated stream;
- ``close()`` stops the producer, drains staged feeds and joins the
  thread — the trainer calls it on preemption (SIGTERM) and on any exit
  from the pass loop, so the checkpoint path always sees a consistent
  batch boundary and no thread is left blocked in ``Queue.put``;
- the consumer waits with a timeout and re-checks producer liveness, so
  a killed producer can never hang the step loop (and on the main
  thread the timed wait stays signal-interruptible for SIGTERM).

Partial final batches: ``remainder="drop"`` / ``"pad"`` apply
:func:`paddle_tpu.parallel.mesh.apply_remainder` before sharding so the
last batch of a pass cannot break mesh divisibility (see that function
for the exact semantics); ``"error"`` keeps ``shard_batch``'s strict
check.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, NamedTuple

from paddle_tpu.core.enforce import enforce
from paddle_tpu.reader.decorator import (
    _drain_and_join,
    _guarded_put,
    _ProducerError,
)


class FeedBatch(NamedTuple):
    """One step's worth of input, ready for the jitted step."""

    examples: int          # samples in the ORIGINAL batch (pre drop/pad)
    feed: dict             # sharded feed pytree
    input_wait_ms: float   # host time this batch kept the step loop waiting
    padded_timesteps: int = 0   # padded steps across SequenceBatch slots
    total_timesteps: int = 0    # all steps across SequenceBatch slots


class _EndOfStream:
    pass


_END = _EndOfStream()


def skip_feed_batches(reader, skip: int, replicas: int = 1,
                      remainder: str = "error", heartbeat=None):
    """Fast-forward a batch reader past its first ``skip`` *yieldable*
    batches — the mid-pass-resume cursor replay (``SGD.train`` restores a
    ``(pass, batch)`` checkpoint cursor and must re-enter the pass at the
    exact batch boundary).

    Skipped batches are counted the way the trainer counts them: a batch
    that ``remainder="drop"`` would discard entirely (fewer samples than
    the mesh's ``replicas``) never reached the step loop, so it does not
    count against ``skip`` — the cursor stays aligned with the original
    run no matter the partial-batch policy.  Skipping does no feed
    conversion, no device placement and consumes no RNG keys; the cost of
    a resume is one pull per already-applied batch.  ``heartbeat``
    (optional, called with the skipped-batch index) keeps a staleness
    watchdog fed through a long fast-forward over a slow reader.
    """
    if skip <= 0:
        return reader
    m = max(int(replicas), 1)

    def skipped_reader():
        remaining = skip
        it = iter(reader())
        for batch in it:
            if remaining > 0:
                n = len(batch) if hasattr(batch, "__len__") else 0
                if remainder != "drop" or n >= m:
                    remaining -= 1
                if heartbeat is not None:
                    heartbeat(skip - remaining)
                continue
            yield batch

    return skipped_reader


def _examples(batch) -> int:
    return len(batch) if hasattr(batch, "__len__") else 0


def _feed_bytes(feed) -> int:
    import jax

    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(feed))


def read_batch(it):
    """``next(it)`` under a ``feed_read`` span (``examples``): the pull
    from the reader iterator, which a ``for`` would hide.
    ``StopIteration`` passes through and records nothing."""
    from paddle_tpu.telemetry.tracing import get_tracer

    tracer = get_tracer()
    tk = tracer.begin("feed_read", cat="reader")
    try:
        batch = next(it)
    except BaseException:
        tracer.cancel(tk)
        raise
    if tk is not None:
        tracer.end(tk, examples=_examples(batch))
    return batch


def convert_batch(batch, feeder, mesh, remainder: str):
    """batch -> (examples, sharded feed, mesh used, padded_timesteps,
    total_timesteps) | None (batch fully dropped).  The mesh rides along
    so a consumer whose mesh changed between staging and use (elastic
    resharding — ``rebind_mesh``) can detect and re-place a stale feed
    instead of handing the step arrays committed to dead devices.  The
    padding stats are taken host-side pre-shard (producer thread under
    prefetch — off the step loop's critical path).

    The one place a batch is converted, so the one place its two phases
    are spans: ``feed_convert`` (feeder, padding stats, remainder policy;
    ``bytes`` of the host arrays) and ``feed_place`` (``shard_batch``:
    this thread's time, the transfers are async and not fenced;
    ``bytes``, ``shards``)."""
    from paddle_tpu.reader.feeder import padding_stats
    from paddle_tpu.telemetry.tracing import get_tracer

    tracer = get_tracer()
    examples = _examples(batch)
    tk = tracer.begin("feed_convert", cat="reader")
    feed = feeder(batch) if feeder is not None else batch
    padded, total = padding_stats(feed) if isinstance(feed, dict) else (0, 0)
    if mesh is not None and remainder != "error":
        from paddle_tpu.parallel.mesh import apply_remainder

        feed = apply_remainder(
            feed, mesh.mesh.shape.get("data", 1), remainder)
        if feed is None:  # "drop" left nothing: skip the batch
            tracer.end(tk)
            return None
    nbytes = 0
    if tk is not None:
        nbytes = _feed_bytes(feed)
        tracer.end(tk, bytes=nbytes)
    if mesh is not None:
        tk = tracer.begin("feed_place", cat="reader")
        feed = mesh.shard_batch(feed)
        if tk is not None:
            tracer.end(tk, bytes=nbytes, shards=mesh.num_replicas)
    return examples, feed, mesh, padded, total


def _replace_feed(feed, mesh, remainder: str):
    """Re-place a staged feed onto a different mesh: device_get the old
    placement and shard onto the new one, re-applying the remainder
    policy in case the new degree no longer divides the staged batch.

    The device_get reads the OLD mesh's devices — fine on a simulated
    loss (every device stays attached) and on scale-up, but after a
    REAL host loss a batch-sharded feed's slice on the dead host is
    gone.  That is unrecoverable here (the reader already advanced past
    this batch), so it raises a clear error instead of silently
    skipping data; the checkpoint-fallback / supervisor ladder is the
    recovery path then."""
    import jax

    try:
        host = jax.device_get(feed)
    except Exception as e:
        raise RuntimeError(
            "elastic rebind: a staged feed's shard is unreachable (its "
            "device died before the feed was consumed); the batch "
            "cannot be reconstructed — recover via the cursor "
            "checkpoint") from e
    return mesh.shard_batch(host, remainder=remainder)


class SynchronousFeeds:
    """The non-overlapped baseline: conversion + placement inline on the
    consumer thread, with the same FeedBatch/close contract as
    :class:`DevicePrefetcher` so the trainer has one code path."""

    def __init__(self, reader: Callable, feeder=None, mesh=None,
                 remainder: str = "error"):
        self._it = iter(reader())
        self._feeder = feeder
        self._mesh = mesh
        self._remainder = remainder

    def __iter__(self):
        return self

    def __next__(self) -> FeedBatch:
        t0 = time.perf_counter()
        while True:
            batch = read_batch(self._it)  # StopIteration ends the pass
            item = convert_batch(batch, self._feeder, self._mesh,
                                 self._remainder)
            if item is not None:
                examples, feed, _, padded, total = item
                return FeedBatch(
                    examples, feed, (time.perf_counter() - t0) * 1e3,
                    padded, total)

    def rebind_mesh(self, mesh) -> None:
        """Adopt a rebuilt mesh (elastic resharding): nothing is staged
        here, so the next conversion simply places onto it."""
        self._mesh = mesh

    def close(self) -> None:
        self._it = iter(())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class DevicePrefetcher:
    """Stage up to ``depth`` converted, device-resident feeds ahead of the
    step loop (see module docstring for the full contract).

    :param reader: zero-arg callable returning an iterator of batches
        (the ``paddle.batch(...)`` output ``SGD.train`` consumes).
    :param feeder: optional ``DataFeeder`` (or any batch -> feed callable)
        run on the worker thread.
    :param mesh: optional ``MeshContext``; when given, each feed is placed
        with ``shard_batch`` (async device_put) before being queued.
    :param depth: bounded queue size — feeds staged ahead of the consumer.
    :param remainder: "error" (strict divisibility, the default), "drop"
        (trim the batch to the largest mesh multiple) or "pad" (repeat the
        last sample up to the next multiple; see ``mesh.apply_remainder``).
    """

    def __init__(self, reader: Callable, feeder=None, mesh=None,
                 depth: int = 2, remainder: str = "error"):
        enforce(depth >= 1, f"prefetch depth must be >= 1, got {depth}")
        self._reader = reader
        self._feeder = feeder
        # _mesh is written by rebind_mesh (consumer thread, elastic
        # resharding) while the producer reads it per batch — every
        # access holds _mesh_lock (the GL-THREAD audited contract)
        self._mesh_lock = threading.Lock()
        self._mesh = mesh
        self._remainder = remainder
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(
            target=self._produce, name="paddle-tpu-prefetch", daemon=True)
        self._thread.start()

    # -- producer (worker thread) ---------------------------------------------
    def _produce(self) -> None:
        from paddle_tpu.telemetry.tracing import get_tracer

        tracer = get_tracer()  # spans land in this worker's own lane
        try:
            it = iter(self._reader())
            while True:
                # one batch from pull to staged; its children are
                # feed_read / feed_convert / feed_place / feed_stage
                tk = tracer.begin("prefetch", cat="reader",
                                  staged=self._q.qsize())
                try:
                    try:
                        batch = read_batch(it)
                    except StopIteration:
                        tracer.cancel(tk)
                        return
                    if self._stop.is_set():
                        tracer.cancel(tk)
                        return
                    with self._mesh_lock:
                        mesh = self._mesh
                    item = convert_batch(batch, self._feeder, mesh,
                                         self._remainder)
                    if item is None:
                        continue
                    # blocks while the queue is full: the worker is
                    # ahead of the device
                    with tracer.span("feed_stage", cat="reader"):
                        staged = _guarded_put(self._q, item, self._stop)
                    if not staged:
                        return
                finally:
                    tracer.end(tk)
        except BaseException as e:  # propagate to the consumer, not stderr
            _guarded_put(self._q, _ProducerError(e), self._stop)
        finally:
            _guarded_put(self._q, _END, self._stop)

    # -- consumer ---------------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self) -> FeedBatch:
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        while True:
            try:
                # timed wait: stays SIGTERM-interruptible on the main
                # thread and lets us detect a dead producer
                item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if not self._thread.is_alive() and self._q.empty():
                    self._done = True
                    raise RuntimeError(
                        "prefetch producer died without signaling "
                        "end-of-stream") from None
        wait_ms = (time.perf_counter() - t0) * 1e3
        if item is _END:
            self._done = True
            self._thread.join(timeout=5.0)
            raise StopIteration
        if isinstance(item, _ProducerError):
            self._done = True
            self._thread.join(timeout=5.0)
            raise item.exc
        examples, feed, used_mesh, padded, total = item
        with self._mesh_lock:
            mesh_now = self._mesh
        if mesh_now is not None and used_mesh is not mesh_now:
            # staged under a mesh that has since been rebuilt (elastic
            # resharding): re-place on the consumer thread rather than
            # dropping — the reader already advanced past this batch,
            # so dropping would silently skip data
            feed = _replace_feed(feed, mesh_now, self._remainder)
        return FeedBatch(examples, feed, wait_ms, padded, total)

    def rebind_mesh(self, mesh) -> None:
        """Adopt a rebuilt mesh (elastic resharding).  The producer
        picks it up for every batch it converts from now on; feeds
        already staged (or mid-conversion) under the old mesh are
        detected by their mesh tag at ``__next__`` and re-placed, so
        the stream stays gapless and in order."""
        with self._mesh_lock:
            self._mesh = mesh

    # -- shutdown ---------------------------------------------------------------
    def close(self) -> None:
        """Stop the producer and drain staged feeds.  Idempotent; called by
        the trainer on preemption and on every pass-loop exit so a consumer
        that abandons the stream early never strands the worker in
        ``Queue.put``."""
        self._done = True
        _drain_and_join(self._q, [self._thread], self._stop, deadline_s=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
