"""Asynchronous device-feed pipeline — the overlap layer between a batch
reader and the jitted train step.

The synchronous v2 loop runs the reader, ``DataFeeder.feed`` (one stack of
the batch into host arrays), device placement (``mesh.shard_batch``: each
shard of a host array goes straight to the device that holds it, the
transfers started together) and the step strictly in sequence, so the TPU
idles during every Python-side conversion and the host idles during every
step.  :class:`DevicePrefetcher` takes the host half off the step loop:

- ONE reader thread (``paddle-tpu-prefetch``) pulls batches — generators
  are not thread-safe, and the reader's order and side effects stay those
  of a plain loop — and waits for a free slot before it hands a batch on;
- a pool of ``depth`` workers (``paddle-tpu-prefetch_<n>``) converts and
  places: a unit of work is one batch, stacked once on the host and put
  once on each device of the mesh, and up to ``depth`` units are in flight
  at a time, so batch *n+1*'s stack overlaps batch *n*'s transfer;
- the consumer takes units strictly in reader order, whatever order they
  finish in.  Units converting plus units staged never exceed ``depth``;
  with the one the step holds that is ``depth`` + 1 feeds a device.

Each worker keeps its own host staging arrays (the feeder stacks into
them: memory the process already holds, not a fresh mapping that faults
in page by page) and ends a unit only after ``block_until_ready`` on the
arrays placed from them: the runtime reads the host buffer until the
transfer is done, so nothing refills it earlier.

Both iterators here yield :class:`FeedBatch` ``(examples, feed,
input_wait_ms)`` so the trainer accounts input wait identically for the
overlapped and the synchronous path:

- ``DevicePrefetcher`` — ``input_wait_ms`` is the time the consumer spent
  blocked on the next unit (0 when the pipeline keeps up).  With span
  tracing on, the reader thread's lane holds ``feed_read`` and
  ``feed_stage`` (its wait for a free slot), each worker's lane one
  ``prefetch`` (``staged``, ``in_flight``) per unit ⊃ ``feed_convert``
  (host only) / ``feed_place`` (the transfer, fenced; ``from_host``)
  (``telemetry/tracing.py``).
- ``SynchronousFeeds`` — the seed behavior (everything inline on the
  consumer thread, through the same feeder and the same placement, no
  staging, no fence); ``input_wait_ms`` is the full conversion+placement
  time, all of it on the critical path.

Error/shutdown contract (the parts thread pipelines usually get wrong):

- a reader or feeder exception is re-raised at the consumer's ``next()``
  at its place in the order — after every good batch before it — not
  swallowed into a truncated stream;
- ``close()`` stops the reader thread and the pool, drops staged feeds
  and waits (within its deadline) for the units in flight — the trainer
  calls it on preemption (SIGTERM) and on any exit from the pass loop, so
  the checkpoint path always sees a consistent batch boundary and no
  thread is left blocked;
- the consumer waits with a timeout and re-checks reader liveness, so a
  killed reader thread can never hang the step loop (and on the main
  thread the timed wait stays signal-interruptible for SIGTERM).

Partial final batches: ``remainder="drop"`` / ``"pad"`` apply
:func:`paddle_tpu.parallel.mesh.apply_remainder` before sharding so the
last batch of a pass cannot break mesh divisibility (see that function
for the exact semantics); ``"error"`` keeps ``shard_batch``'s strict
check.
"""

from __future__ import annotations

import concurrent.futures
import queue
import threading
import time
from typing import Callable, NamedTuple

import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.reader.decorator import _ProducerError
from paddle_tpu.reader.feeder import DataFeeder


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

# how long close() waits for the reader thread and the units in flight
CLOSE_DEADLINE_S = 5.0


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


def _feed_bytes(feed, kind=object) -> int:
    """Bytes of the feed's leaves (of those that are a ``kind``)."""
    import jax

    return sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(feed)
               if isinstance(x, kind))


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


def _settle(staging: dict, feed) -> None:
    """Make ``staging`` safe to fill again.  Waits until the arrays
    placed from it are on their devices (the runtime reads a host buffer
    until its transfer is done), then gives up every buffer the feed
    still lives in: one that never left the host, or one the runtime
    kept as the device buffer instead of copying (the CPU backend adopts
    aligned host memory).  That memory is the feed's now."""
    import jax

    jax.block_until_ready(feed)
    for name, buf in list(staging.items()):
        shards = getattr(feed[name], "addressable_shards", None)
        lo = buf.ctypes.data
        if shards is None or any(
                lo <= s.data.unsafe_buffer_pointer() < lo + buf.nbytes
                for s in shards):
            del staging[name]


def convert_batch(batch, feeder, mesh, remainder: str,
                  staging: dict | None = None):
    """batch -> (examples, sharded feed, mesh used, padded_timesteps,
    total_timesteps) | None (batch fully dropped).  The mesh rides along
    so a consumer whose mesh changed between staging and use (elastic
    resharding — ``rebind_mesh``) can detect and re-place a stale feed
    instead of handing the step arrays committed to dead devices.  The
    padding stats are taken host-side pre-shard (a worker thread under
    prefetch — off the step loop's critical path).

    ``staging`` (a prefetch worker's, with a ``DataFeeder`` and a mesh)
    is the feeder's to stack into; the placement is then fenced and the
    dict settled (:func:`_settle`) before this returns.

    The one place a batch is converted, so the one place its two phases
    are spans: ``feed_convert`` (feeder, padding stats, remainder policy,
    all on the host; ``bytes`` of the host arrays) and ``feed_place``
    (``shard_batch``, the only transfer: this thread's time, which under
    ``staging`` runs to the end of the transfer; ``bytes``, ``shards``,
    and ``from_host``, the bytes that were host arrays — 0 says a device
    array was placed again, i.e. the feed went by way of another device)."""
    from paddle_tpu.reader.feeder import padding_stats
    from paddle_tpu.telemetry.tracing import get_tracer

    tracer = get_tracer()
    examples = _examples(batch)
    tk = tracer.begin("feed_convert", cat="reader")
    if feeder is None:
        feed = batch
    elif staging is None:
        feed = feeder(batch)
    else:
        feed = feeder(batch, staging)
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
        from_host = _feed_bytes(feed, np.ndarray) if tk is not None else 0
        feed = mesh.shard_batch(feed)
        if staging is not None:
            _settle(staging, feed)
        if tk is not None:
            tracer.end(tk, bytes=nbytes, shards=mesh.num_replicas,
                       from_host=from_host)
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
    """Keep up to ``depth`` batches converting or staged, device-resident,
    ahead of the step loop (see module docstring for the full contract).

    :param reader: zero-arg callable returning an iterator of batches
        (the ``paddle.batch(...)`` output ``SGD.train`` consumes); pulled
        on one thread, in order.
    :param feeder: optional ``DataFeeder`` (or any batch -> feed callable)
        run on the worker pool.
    :param mesh: optional ``MeshContext``; when given, each feed is placed
        with ``shard_batch`` by the worker that converted it.
    :param depth: units (batches) converting or staged at a time, and the
        size of the worker pool.
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
        # resharding) while the reader thread reads it per batch — every
        # access holds _mesh_lock (the GL-THREAD audited contract)
        self._mesh_lock = threading.Lock()
        self._mesh = mesh
        self._remainder = remainder
        # the hand-off, in reader order: a unit's future is queued the
        # moment it is submitted, so the consumer meets the batches as
        # they were read, whatever order the workers finish in
        self._units: queue.SimpleQueue = queue.SimpleQueue()
        # one slot a unit, held from submission until the consumer has
        # taken its feed: converting + staged <= depth
        self._slots = threading.Semaphore(depth)
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=depth, thread_name_prefix="paddle-tpu-prefetch")
        # each worker's own staging arrays, by slot name (only a
        # DataFeeder stacks into them)
        self._use_staging = isinstance(feeder, DataFeeder)
        self._worker = threading.local()
        self._count_lock = threading.Lock()
        self._in_flight = 0     # units a worker is converting or placing
        self._staged = 0        # units done, not yet taken
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(
            target=self._produce, name="paddle-tpu-prefetch", daemon=True)
        self._thread.start()

    # -- reader thread ----------------------------------------------------------
    def _produce(self) -> None:
        from paddle_tpu.telemetry.tracing import get_tracer

        tracer = get_tracer()  # spans land in this thread's own lane
        try:
            it = iter(self._reader())
            while True:
                try:
                    batch = read_batch(it)
                except StopIteration:
                    return
                # blocks while every slot is taken: the pipeline is
                # ahead of the device
                with tracer.span("feed_stage", cat="reader"):
                    while not self._slots.acquire(timeout=0.05):
                        if self._stop.is_set():
                            return
                with self._mesh_lock:
                    mesh = self._mesh
                self._units.put(self._pool.submit(self._unit, batch, mesh))
        except BaseException as e:  # propagate to the consumer, not stderr
            self._units.put(_ProducerError(e))
        finally:
            self._units.put(_END)

    # -- workers ----------------------------------------------------------------
    def _unit(self, batch, mesh):
        """One batch from samples to a placed feed (``convert_batch``'s
        tuple, or None for a batch the remainder policy dropped whole),
        inside a ``prefetch`` span: ``in_flight`` = units other workers
        had in hand when this one started (0 on every span: nothing ever
        overlapped), ``staged`` = units done and waiting then."""
        from paddle_tpu.telemetry.tracing import get_tracer

        tracer = get_tracer()
        with self._count_lock:
            in_flight, staged = self._in_flight, self._staged
            self._in_flight += 1
        tk = tracer.begin("prefetch", cat="reader", staged=staged,
                          in_flight=in_flight)
        staging = None
        if self._use_staging and mesh is not None:
            staging = self._worker.__dict__.setdefault("staging", {})
        try:
            return convert_batch(batch, self._feeder, mesh,
                                 self._remainder, staging)
        finally:
            with self._count_lock:
                self._in_flight -= 1
                self._staged += 1
            tracer.end(tk)

    # -- consumer ---------------------------------------------------------------
    def __iter__(self):
        return self

    def _next_unit(self):
        """The next unit's result, in reader order; timed waits: they
        stay SIGTERM-interruptible on the main thread and let us detect
        a dead reader thread."""
        while True:
            try:
                unit = self._units.get(timeout=0.1)
                break
            except queue.Empty:
                if not self._thread.is_alive() and self._units.empty():
                    raise RuntimeError(
                        "prefetch producer died without signaling "
                        "end-of-stream") from None
        if unit is _END:
            raise StopIteration
        if isinstance(unit, _ProducerError):
            raise unit.exc
        while not unit.done():
            concurrent.futures.wait([unit], timeout=0.1)
        with self._count_lock:
            self._staged -= 1
        self._slots.release()
        return unit.result()    # raises what the feeder or the placing raised

    def __next__(self) -> FeedBatch:
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        item = None
        while item is None:     # None: the remainder policy dropped it
            try:
                item = self._next_unit()
            except BaseException:
                # end of stream or an error at its place in the order:
                # either way the stream is over
                self.close()
                raise
        wait_ms = (time.perf_counter() - t0) * 1e3
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
        """Adopt a rebuilt mesh (elastic resharding).  The reader thread
        picks it up for every batch it hands on from now on; feeds
        already staged (or mid-conversion) under the old mesh are
        detected by their mesh tag at ``__next__`` and re-placed, so
        the stream stays gapless and in order."""
        with self._mesh_lock:
            self._mesh = mesh

    # -- shutdown ---------------------------------------------------------------
    def close(self) -> None:
        """Stop the reader thread and the pool, drop what is staged and
        wait for the units in flight, all within ``CLOSE_DEADLINE_S``.
        Idempotent; called by the trainer on preemption and on every
        pass-loop exit so a consumer that abandons the stream early never
        strands a thread.  (A reader blocked in its own IO stays a daemon
        thread rather than hanging us.)"""
        self._done = True
        self._stop.set()
        self._pool.shutdown(wait=False, cancel_futures=True)
        deadline = time.monotonic() + CLOSE_DEADLINE_S
        self._thread.join(timeout=CLOSE_DEADLINE_S)
        units = []
        while not self._units.empty():
            unit = self._units.get_nowait()
            if isinstance(unit, concurrent.futures.Future):
                units.append(unit)
        concurrent.futures.wait(
            units, timeout=max(deadline - time.monotonic(), 0.0))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
