"""The v2 SGD trainer — keeps the contract of
``python/paddle/v2/trainer.py:24`` (``SGD.train:124``: reader → DataFeeder →
forwardBackward → update → events) while replacing the SWIG GradientMachine +
ParameterUpdater stack with one jitted, mesh-sharded train step.

The updater lifecycle the reference exposes (startPass/startBatch/update/
finishBatch/finishPass, ``ParameterUpdater.h:38``) collapses into the compiled
step; pass/batch iteration stays in Python exactly as in v2."""

from __future__ import annotations

import os
import time as _time
from typing import Callable

import jax
import numpy as np

from paddle_tpu.config.topology import Topology
from paddle_tpu.core import flags, rng
from paddle_tpu.core import logger as log
from paddle_tpu.core import stat
from paddle_tpu.core.enforce import enforce
from paddle_tpu.core.lod import SequenceBatch
from paddle_tpu.core.parameters import Parameters
from paddle_tpu.layers.base import LayerOutput
from paddle_tpu.parallel.mesh import MeshContext, get_mesh
from paddle_tpu.reader.feeder import DataFeeder, parse_seq_buckets
from paddle_tpu.telemetry import scopes
from paddle_tpu.telemetry import tracing as tracing_mod
from paddle_tpu.trainer import event as v2_event
from paddle_tpu.trainer.step import build_eval_step, build_train_step


class _ElasticReplay(Exception):
    """Control flow, not an error: a checkpoint-fallback elastic rebuild
    restored state behind the current position, so the pass loop must
    re-enter at the restored cursor (reader fast-forward included) —
    the in-process analog of a supervisor restart.  Carries the
    re-placed state so ``_train_loop`` re-enters without another
    restore."""

    def __init__(self, pass_id: int, batch_id: int, params, opt_state,
                 states):
        super().__init__(f"elastic replay from pass {pass_id} "
                         f"batch {batch_id}")
        self.pass_id = pass_id
        self.batch_id = batch_id
        self.params = params
        self.opt_state = opt_state
        self.states = states


def _feed_signature(feed: dict) -> tuple:
    sig = []
    for k in sorted(feed):
        v = feed[k]
        if isinstance(v, SequenceBatch):
            sig.append((k, tuple(v.data.shape), str(v.data.dtype), "seq"))
        else:
            sig.append((k, tuple(v.shape), str(v.dtype)))
    return tuple(sig)


class SGD:
    """v2 ``paddle.trainer.SGD``.

    :param cost: the cost LayerOutput to minimize.
    :param parameters: ``paddle.parameters.create(topology)`` result.
    :param update_equation: a ``paddle_tpu.optimizer.Optimizer``.
    :param extra_layers: additional layers to keep alive (e.g. for evaluators).
    :param is_local: kept for API compat; distribution now comes from the mesh.
    :param mesh: optional MeshContext; default = all devices on the data axis.
    """

    def __init__(self, cost, parameters: Parameters, update_equation,
                 extra_layers=None, is_local: bool = True, pserver_spec=None,
                 use_etcd: bool = False, mesh: MeshContext | None = None,
                 compute_dtype=None, declared_evaluators=None,
                 zero: int | None = None):
        self.compute_dtype = compute_dtype  # e.g. jnp.bfloat16 for the MXU
        # weight-update sharding over the mesh data axis (parallel/zero.py
        # — the pserver's sharded aggregation, in-mesh): 0 = replicated
        # update (the v2 behavior), 1 = 1/n-sharded optimizer state,
        # 2 = reduce-scatter grads + sharded update + all-gather params.
        # Default: the --zero flag (PADDLE_TPU_ZERO).
        self.zero = flags.get("zero") if zero is None else int(zero)
        # v1 *_evaluator declarations (EvaluatorSpecs or a prebuilt
        # DeclaredEvaluators) executed host-side per batch, like
        # GradientMachine::eval driving Evaluator.cpp
        from paddle_tpu.evaluator import runtime as _ev_runtime

        if declared_evaluators is None:
            self.declared_evaluators = _ev_runtime.build([])
        elif isinstance(declared_evaluators, _ev_runtime.DeclaredEvaluators):
            self.declared_evaluators = declared_evaluators
        else:
            self.declared_evaluators = _ev_runtime.build(declared_evaluators)
        self._tap_grads = None
        self._tap_grads_eval = None
        if isinstance(cost, LayerOutput):
            cost = [cost]
        # dual-output companions ("#ids") of declared evaluator inputs
        # join the topology automatically, so the v2 path works like the
        # CLI's without the caller passing extra_layers
        from paddle_tpu.layers import base as layer_base
        from paddle_tpu.layers.base import companion_name

        ev_inputs = {n for b in self.declared_evaluators.bound
                     for n in b.spec.input_layers}
        wanted_extra = ev_inputs | {companion_name(n) for n in ev_inputs}
        # data layers stay OUT: evaluator data inputs outside the topology
        # are resolved from the eval feed (runtime.eval_batch), and forcing
        # them in would make DataFeeder demand feed slots for them
        companions = [lo for lo in layer_base.layer_registry()
                      if lo.name in wanted_extra
                      and lo.layer_type != "data"]
        extra_layers = list(extra_layers or []) + [
            c for c in companions
            if not any(c is e for e in (extra_layers or []))]
        self.topology = Topology(cost, extra_layers=extra_layers)
        self.parameters = parameters
        for spec in self.topology.param_specs():
            self.parameters.add(spec)
        self.parameters.init_missing()
        self.optimizer = update_equation
        self.mesh = mesh if mesh is not None else get_mesh()
        self.states = self.topology.init_states()
        # warm-started Parameters may carry BN moving stats (saved as static
        # entries by save_parameter_to_tar) — load them back
        for sname in list(self.states):
            if sname in self.parameters:
                self.states[sname] = jax.numpy.asarray(self.parameters[sname])
        self._specs = {s.name: s for s in self.topology.param_specs()}
        self._trainable = {n for n, s in self._specs.items() if not s.is_static}
        self._opt_state = None
        self._train_step = None
        self._eval_step = None
        self._compiled_sigs: set = set()
        self._setup_span = None  # train()'s open train_setup span
        self._telemetry = None  # StepTelemetry, bound by train()
        self._telemetry_costs: dict = {}  # per-signature cost analysis
        self.__gradient_machine__ = self  # v2 attr some user code touches

    # -- internal -------------------------------------------------------------
    @tracing_mod.setup_span("params_sync", lambda self, out: _tree_size(out))
    def _params_dict(self):
        """``Parameters`` -> a dict of device arrays, one ``__getitem__``
        (a host copy) and one ``asarray`` (back to the device) an array,
        at every ``train()``: the ``params_sync`` set-up span."""
        return {n: jax.numpy.asarray(self.parameters[n]) for n in self.parameters.names()}

    def _zero_active(self) -> bool:
        return (self.zero >= 1
                and self.mesh.mesh.shape.get("data", 1) > 1)

    def _place_opt_state(self, opt_state):
        """Device placement for the optimizer state: ZeRO runs shard the
        slots 1/n over the data axis (parallel/zero.py), the replicated
        update keeps full copies everywhere — ONE placement point shared
        by train() init, checkpoint resume and the guard's rollback, so
        every path agrees on the layout the jitted step expects."""
        if not self._zero_active():
            return self.mesh.replicate(opt_state)
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.zero import shard_opt_state

        params = {n: jax.numpy.asarray(self.parameters[n])
                  for n in self._trainable}
        base = {
            n: (P(*s.sharding) if getattr(s, "sharding", None) else P())
            for n, s in self._specs.items() if n in self._trainable}
        return shard_opt_state(opt_state, params, self.mesh.mesh,
                               param_specs=base)

    def _ensure_built(self):
        if self._train_step is not None:
            return
        with tracing_mod.get_tracer().span("build_step",
                                           cat=tracing_mod.SETUP_CAT):
            node_names = {n.name for n in self.topology.nodes}
            wanted = {
                name
                for b in (self.declared_evaluators.bound
                          if self.declared_evaluators else [])
                for name in b.spec.input_layers
            }
            # "#ids" companions (crf_decoding's decoded path) ride along so
            # evaluators can read the ids side of a dual-output layer
            from paddle_tpu.layers.base import companion_name
            wanted |= {companion_name(n) for n in set(wanted)}
            fetch = sorted(wanted & node_names)
            self._train_step = build_train_step(
                self.topology, self.optimizer, self.mesh,
                compute_dtype=self.compute_dtype, fetch_layers=fetch,
                zero=self.zero)
            self._eval_step = build_eval_step(self.topology, self.mesh)
            taps = (self.declared_evaluators.grad_tap_layers()
                    if self.declared_evaluators else [])
            if taps:
                from paddle_tpu.trainer.step import build_tap_grads

                self._tap_grads = build_tap_grads(self.topology, taps)

    @tracing_mod.setup_span("place_state", lambda self, out: _tree_size(out))
    def _placed_state(self):
        """(params, states, opt_state) on the mesh, as the jitted step
        takes them: parameters and states replicated, the optimizer
        state carried over from the last run or freshly initialised in
        its ZeRO layout.  The ``place_state`` set-up span (``arrays``,
        ``bytes`` of the three), around its ``params_sync``."""
        params = self.mesh.replicate(self._params_dict())
        states = self.mesh.replicate(self.states)
        opt_state = self._opt_state
        if opt_state is None:
            opt_state = self._place_opt_state(self.optimizer.init(
                {k: params[k] for k in self._trainable}, self._specs))
        return params, states, opt_state

    def lower_train_step(self, data_batch, feeding=None):
        """The jax ``Lowered`` of the train step for one reader batch —
        the program :meth:`train` compiles for that feed signature, on
        this trainer's mesh and state layout.  Nothing is executed.  For
        inspection: ``.as_text()`` shows the Pallas kernels in the step
        (``tpu_custom_call``), ``.compile()`` the collectives and the
        memory (``chip_smoke.py`` prints both)."""
        self._ensure_built()
        feed = self.mesh.shard_batch(
            self._default_feeder(feeding)(data_batch))
        params, states, opt_state = self._placed_state()
        return self._train_step.lower(params, opt_state, states, feed,
                                      jax.random.key(0))

    def _default_feeder(self, feeding, seq_buckets=None):
        dl = self.topology.data_layers()
        types = {}
        for name, node in dl.items():
            from paddle_tpu.layers.data_type import DataKind, InputType

            types[name] = InputType(
                dim=node.attrs["dim"],
                seq_type=node.attrs.get("seq_type", 0),
                kind=node.attrs.get("data_type", DataKind.DENSE),
            )
        if seq_buckets is None:
            seq_buckets = parse_seq_buckets(flags.get("seq_buckets"))
        return DataFeeder(types, feeding, seq_buckets=seq_buckets)

    # -- the v2 train loop ----------------------------------------------------
    def train(self, reader, num_passes: int = 1,
              event_handler: Callable | None = None, feeding=None,
              checkpoint_dir: str | None = None, checkpoint_period: int = 1,
              resume: bool = True, checkpoint_async: bool = False,
              metrics_registry=None, sync_period: int | None = None,
              prefetch: int | None = None, nan_policy: str | None = None,
              checkpoint_batch_period: int | None = None,
              checkpoint_keep: int | None = None, elastic=None,
              seq_buckets=None):
        """reader yields BATCHES (lists of sample tuples), i.e. the output of
        ``paddle.batch(...)`` exactly as in v2.

        Input overlap (``reader/prefetch.py``): with ``prefetch`` > 0
        (default: the ``prefetch_depth`` flag, 0 — synchronous, matching
        v2; the CLI defaults to ``--prefetch=2``) one thread pulls the
        reader, in order, and a pool of ``prefetch`` workers runs
        ``DataFeeder.feed`` (one stack of the batch into host staging
        arrays a worker reuses) + ``mesh.shard_batch`` (each shard
        straight to its own device, fenced on the worker) ahead of the
        step loop, keeping up to ``prefetch`` batches converting or
        staged; they are handed over strictly in reader order.  0 keeps
        everything on the consumer thread with no read-ahead (the same
        feeder and placement, inline; feed conversion then happens when
        the batch is pulled, just before that batch's
        ``BeginIteration``).  The training trajectory is
        bit-identical either way (same batches, same RNG key order) — but
        with ``prefetch`` > 0 the READER is consumed up to ``prefetch`` + 1
        batches ahead on its own thread, so a reader that must run in
        lockstep with the event stream (e.g. curriculum state mutated by
        the event handler) should stay at 0.
        Host-fed workloads should opt in (``prefetch=2`` or
        ``PADDLE_TPU_PREFETCH_DEPTH=2``) — it is the structural fix for
        the device idling through every Python-side feed conversion.

        ``sync_period`` (default: the ``sync_period`` flag, 1) defers the
        per-step device fence: costs/metrics stay device arrays and are
        fetched with ONE ``jax.device_get`` every N steps, so the host
        keeps dispatching while the device computes.  ``EndIteration``
        events still carry real floats but arrive in bursts of N (and a
        batch's ``BeginIteration`` may precede the PREVIOUS batch's
        ``EndIteration``); 1 keeps exact v2 per-batch event cadence.
        Host-side evaluators / gradient taps force an effective period
        of 1 — they fence every batch anyway.

        ``checkpoint_dir`` enables full crash-safe checkpoints (parameters +
        optimizer slots + states + a ``(pass, batch)`` cursor + the RNG
        stream, uuid/sha manifest — see ``trainer/checkpoint.py``); with
        ``resume`` the newest VALID one is loaded (corrupt ones are
        skipped) and training continues from the cursor — for a mid-pass
        cursor the reader is fast-forwarded to the exact batch boundary
        and the restored RNG stream makes the replayed trajectory
        bit-identical to an uninterrupted run.
        ``checkpoint_batch_period`` (default: the flag, 0 = off)
        additionally checkpoints every N batches mid-pass, bounding lost
        work to N batches instead of a whole pass.
        ``checkpoint_async`` moves the disk write off the step loop
        (``AsyncCheckpointer``: host snapshot taken synchronously, npz +
        manifest written by a worker thread; the preemption save stays
        synchronous).

        ``nan_policy`` (default: the ``nan_policy`` flag, "none") arms
        the numeric guard (``resilience/guard.py``): "skip" discards a
        non-finite batch's update and keeps training; "rollback"
        restores the newest valid checkpoint and re-enters at a reduced
        step size for a rescue window.  Either policy fences every batch
        (effective ``sync_period=1``) and keeps a one-batch device-side
        state snapshot while armed.  With the ``heartbeat_stale_s`` flag
        set, a watchdog thread dumps the flight ring and fails fast when
        this host's train-loop heartbeat goes stale — a hung collective
        becomes a diagnosable crash instead of a silent barrier wait.

        Telemetry (see ``paddle_tpu/metrics.py``): one structured record
        per step — {step, loss, step_ms, examples_per_sec, tokens_per_sec,
        mfu_pct, hbm_gbps, comm_bytes, metrics} — flows through
        ``metrics_registry`` (default: the process-global registry, JSONL
        sink attachable via ``--metrics_jsonl``/``PADDLE_TPU_METRICS_JSONL``
        or ``metrics.configure``).  Every record also lands in the
        multihost flight recorder, whose ring buffer is dumped to disk on
        exception or SIGTERM (``distributed/multihost.py``).

        ``elastic`` (an :class:`~paddle_tpu.resilience.elastic.
        ElasticCoordinator`) arms live resharding: membership events
        (host loss, scale-up) queued on the coordinator are consumed at
        batch boundaries — the deferred-fence backlog is drained, a
        cursor checkpoint marks the boundary, the mesh is rebuilt at the
        new data-parallel degree, and params/optimizer state are
        re-placed from the live shards (or restored from the newest
        cursor checkpoint when a shard is unrecoverable, replaying from
        its cursor) — all without leaving this call."""
        from paddle_tpu import metrics as metrics_mod
        from paddle_tpu.distributed import multihost as mh
        from paddle_tpu.telemetry import StepTelemetry
        from paddle_tpu.telemetry import introspect as introspect_mod

        if sync_period is None:
            sync_period = flags.get("sync_period")
        if prefetch is None:
            prefetch = flags.get("prefetch_depth")
        if nan_policy is None:
            nan_policy = flags.get("nan_policy")
        if checkpoint_batch_period is None:
            checkpoint_batch_period = flags.get("checkpoint_batch_period")
        if checkpoint_keep is None:
            checkpoint_keep = flags.get("checkpoint_keep")
        if event_handler is None:
            event_handler = _default_event_handler
        metrics_mod.configure_from_flags(metrics_registry)
        # the cost cache lives on the SGD (same lifetime as _train_step):
        # a second train() on this trainer hits the jit trace cache, so
        # re-lowering would yield empty comm captures — reuse instead
        self._telemetry = StepTelemetry(
            registry=metrics_registry, run="train",
            flight=mh.flight_recorder(),
            cost_cache=self._telemetry_costs)
        # span tracing (--trace_spans): flag-on arms the global tracer;
        # a tracer a test already enabled stays enabled (never disarmed
        # here).  With tracing off, every span call site below resolves
        # to a shared no-op — the bit-identical-trajectory guarantee.
        if flags.get("trace_spans"):
            tracing_mod.configure_tracing(enabled=True)
        # goodput ledger (--goodput_ledger): a fold over the span ring,
        # so arming it arms tracing too.  Started before the build so
        # pre-step-0 wall (build, placement) lands in "idle" instead of
        # silently missing from the account.
        self._goodput_ledger = None
        if flags.get("goodput_ledger"):
            from paddle_tpu.telemetry import goodput as goodput_mod

            tracing_mod.configure_tracing(enabled=True)
            self._goodput_ledger = goodput_mod.GoodputLedger(
                registry=self._telemetry.registry).start()
        # set-up, from here to the first step (the step loop ends it; the
        # finally below does where no step ever came)
        tracer = tracing_mod.get_tracer()
        self._setup_span = tracer.begin("train_setup",
                                        cat=tracing_mod.SETUP_CAT)
        prev_debug_nans = jax.config.jax_debug_nans
        if flags.get("debug_nans"):
            # the documented jax nan-checking traps at the originating op;
            # the finite-cost check below remains as a cheap backstop
            jax.config.update("jax_debug_nans", True)
        self._ensure_built()
        # seq_buckets (None = the reader's own table, then the flag):
        # length-quantization table for the feeder's sequence slots —
        # it must be the SAME table the reader's bucket_by_length stage
        # used so every bucket is one jit signature.  bucket_by_length
        # readers (the dataset bucketed_batches helpers) carry theirs as
        # reader.seq_buckets, so bucketed input pads to bucket ceilings
        # by default, no repeated knob.
        if seq_buckets is None:
            seq_buckets = getattr(reader, "seq_buckets", None)
        feeder = self._default_feeder(feeding, seq_buckets)
        params, states, opt_state = self._placed_state()

        # preemption handling (SURVEY §5/§7.8): on SIGTERM (the TPU-pod
        # eviction signal) the flight ring is dumped ALWAYS; with a
        # checkpoint_dir the run additionally finishes the current batch,
        # checkpoints, and exits — resume picks up from the saved pass.
        # Without one, the pre-train disposition is re-delivered after
        # the dump (the process still dies, but the post-mortem exists).
        preempted = {"flag": False}
        prev = {"handler": None, "installed": False}
        import signal

        def _on_sigterm(signum, frame):
            mh.flight_recorder().dump(reason="SIGTERM")
            if checkpoint_dir:
                preempted["flag"] = True
                log.info("SIGTERM received: checkpointing at the next "
                         "batch boundary")
                return
            mh.chain_signal(signum, frame, prev["handler"])

        try:
            prev["handler"] = signal.signal(signal.SIGTERM, _on_sigterm)
            prev["installed"] = True
        except ValueError:  # non-main thread: no handler, no preemption
            pass

        # heartbeat-staleness watchdog (multihost hang -> fail-fast dump):
        # the train loop heartbeats every batch; a stall past the flag's
        # threshold dumps the flight ring and interrupts the main thread
        watchdog = None
        stale_s = float(flags.get("heartbeat_stale_s") or 0.0)
        if stale_s > 0:
            watchdog = mh.HeartbeatWatchdog(recorder=mh.flight_recorder(),
                                            stale_after_s=stale_s)
            watchdog.start()

        if elastic is not None:
            elastic.bind(self, checkpoint_dir)
        # live introspection (--status_port / PADDLE_TPU_STATUS_PORT):
        # /metrics /healthz /snapshot /trace served for the duration of
        # this train() call — the flight ring becomes inspectable
        # BEFORE a crash, not only in its post-mortem dump.  Started
        # HERE, immediately before the try whose finally stops it: a
        # build failure above must not leak a bound port into a
        # supervisor-retried train() (EADDRINUSE on the retry).
        status_server = introspect_mod.server_from_flags(
            registry=self._telemetry.registry,
            flight=mh.flight_recorder())
        try:
            self._train_loop(reader, num_passes, event_handler, feeder,
                             params, states, opt_state, checkpoint_dir,
                             checkpoint_period, resume, preempted,
                             checkpoint_async=checkpoint_async,
                             sync_period=sync_period, prefetch=prefetch,
                             nan_policy=nan_policy,
                             checkpoint_batch_period=checkpoint_batch_period,
                             checkpoint_keep=checkpoint_keep,
                             elastic=elastic)
        finally:
            tracer.end(self._setup_span)
            self._setup_span = None
            jax.config.update("jax_debug_nans", prev_debug_nans)
            if watchdog is not None:
                watchdog.stop()
            profile_window = getattr(self, "_profile_window", None)
            if profile_window is not None:
                # a run shorter than the window's B (or an abort inside
                # it) still stops the device trace and emits the record
                profile_window.close()
                self._profile_window = None
            ledger = getattr(self, "_goodput_ledger", None)
            if ledger is not None and ledger.started:
                # close the wall-clock account (idle absorbs whatever
                # no span covered) and emit the ledger record BEFORE
                # the status server stops, so a last /healthz scrape
                # sees the final goodput_fraction
                ledger_dir = flags.get("ledger_dir")
                ledger.finish(path=os.path.join(ledger_dir, "ledger.jsonl")
                              if ledger_dir else None)
                self._goodput_ledger = None
            if status_server is not None:
                status_server.stop()
            trace_dir = flags.get("trace_dir")
            if trace_dir and tracing_mod.get_tracer().enabled:
                # the per-rank Chrome trace tools/trace_merge.py folds
                # into one fleet timeline (same host-index stamp as the
                # flight dump, so lanes line up across artifacts)
                from paddle_tpu.telemetry import host_index

                tracing_mod.get_tracer().dump(os.path.join(
                    trace_dir, f"trace-host{host_index()}.json"))
            if prev["installed"] and prev["handler"] is not None:
                signal.signal(signal.SIGTERM, prev["handler"])

    def _restore_checkpoint_state(self, found, opt_state_template,
                                  states_fallback):
        """(path, manifest) -> (params, opt_state, states) replicated,
        with ``self.parameters`` updated and the RNG stream restored to
        the manifest's — shared by startup resume and the numeric
        guard's rollback path.  The restore wall time lands in the
        ``checkpoint_restore_ms`` gauge (the recovery-time observable)."""
        from paddle_tpu.distributed import multihost as mh
        from paddle_tpu.telemetry.tracing import get_tracer
        from paddle_tpu.trainer.checkpoint import load_checkpoint

        path, manifest = found
        t0 = _time.perf_counter()
        # tracer-clock twin of t0 for the retrospective "restore" span
        # below (the goodput ledger's checkpoint_restore bucket) — same
        # measurement window, the tracer's timeline
        tracer = get_tracer()
        tk0 = tracer.clock() if tracer.enabled else 0.0
        # heartbeat-free phases look like hangs to the staleness
        # watchdog; mark the restore so a slow load stays a sign of life
        mh.flight_recorder().heartbeat("restore", path=path)
        cp, copt, cstates, _ = load_checkpoint(
            path, opt_state_template=opt_state_template)
        for name, arr in cp.items():
            if name in self.parameters:
                self.parameters[name] = arr
        params = self.mesh.replicate(self._params_dict())
        opt_state = (self._place_opt_state(copt) if copt is not None
                     else opt_state_template)
        if cstates:
            # restore each state at its template dtype (bf16/f8
            # states were stored f32 by the npz layer)
            tmpl = self.states
            states = self.mesh.replicate({
                k: jax.numpy.asarray(
                    v, dtype=getattr(tmpl.get(k), "dtype", None))
                for k, v in cstates.items()})
        else:
            states = states_fallback
        if manifest.get("meta", {}).get("rng") is not None:
            rng.set_state(np.asarray(manifest["meta"]["rng"],
                                     dtype=np.uint32))
        mh.flight_recorder().heartbeat("restored", path=path)
        if tracer.enabled:
            # under train_setup at a resume, top-level in a guard's rescue
            tracer.add_span("restore", tk0, tracer.clock(), cat="trainer",
                            parent_id=getattr(self._setup_span, "span_id",
                                              None), path=path)
        if self._telemetry is not None:
            self._telemetry.registry.gauge(
                "checkpoint_restore_ms",
                "wall ms to restore the newest checkpoint").set(
                (_time.perf_counter() - t0) * 1e3)
        return params, opt_state, states

    def _train_loop(self, reader, num_passes, event_handler, feeder,
                    params, states, opt_state, checkpoint_dir,
                    checkpoint_period, resume, preempted,
                    checkpoint_async=False, sync_period=1, prefetch=0,
                    nan_policy="none", checkpoint_batch_period=0,
                    checkpoint_keep=3, elastic=None):
        from paddle_tpu.trainer import checkpoint as ckpt

        writer = ckpt.AsyncCheckpointer() if (
            checkpoint_async and checkpoint_dir) else None

        start_pass = flags.get("start_pass")
        start_batch = 0
        if checkpoint_dir and resume:
            found = ckpt.latest_checkpoint(checkpoint_dir)
            if found is not None:
                path, manifest = found
                params, opt_state, states = self._restore_checkpoint_state(
                    found, opt_state, states)
                cursor = manifest.get("cursor")
                if cursor is not None:
                    # resume at the exact batch boundary the manifest
                    # recorded; an explicitly higher --start_pass wins
                    # (and starts that pass from its first batch)
                    if cursor["pass_id"] > start_pass:
                        start_pass = cursor["pass_id"]
                        start_batch = int(cursor.get("batch_id", 0))
                    elif cursor["pass_id"] == start_pass:
                        start_batch = int(cursor.get("batch_id", 0))
                else:  # pre-cursor manifests: continue with the next pass
                    start_pass = max(start_pass, manifest["pass_id"] + 1)
                log.info("resumed from %s (pass %d, next batch %d)", path,
                         start_pass, start_batch)
        try:
            while True:
                try:
                    self._run_passes(
                        start_pass, num_passes, reader, event_handler,
                        feeder, params, states, opt_state,
                        checkpoint_dir, checkpoint_period, preempted,
                        writer, sync_period=sync_period,
                        prefetch=prefetch, start_batch=start_batch,
                        nan_policy=nan_policy,
                        checkpoint_batch_period=checkpoint_batch_period,
                        checkpoint_keep=checkpoint_keep,
                        elastic=elastic)
                    break
                except _ElasticReplay as r:
                    # checkpoint-fallback elastic rebuild: re-enter the
                    # pass loop at the restored cursor with the re-placed
                    # state — the same replay a supervisor restart would
                    # do, minus the process restart
                    params, opt_state, states = (r.params, r.opt_state,
                                                 r.states)
                    start_pass, start_batch = r.pass_id, r.batch_id
                    log.info("elastic: replaying from pass %d batch %d "
                             "at the new mesh degree", start_pass,
                             start_batch)
        except BaseException as e:
            # post-mortem: the flight ring (last N step records +
            # heartbeats) goes to disk so pod hangs/desyncs are
            # diagnosable after the process is gone; dump() never raises
            from paddle_tpu.distributed import multihost as mh

            path = mh.flight_recorder().dump(
                reason=f"{type(e).__name__}: {e}"[:200])
            if path:
                log.info("flight recorder dumped to %s", path)
            raise
        finally:
            if writer is not None:
                import sys

                if sys.exc_info()[0] is None:
                    writer.wait()  # surface deferred write errors; flush
                else:
                    # a training exception is already propagating — don't
                    # let a checkpoint IO error supersede it
                    try:
                        writer.wait()
                    except Exception as e:
                        log.warning(
                            "async checkpoint write failed during "
                            "abort: %s", e)

    def _run_passes(self, start_pass, num_passes, reader, event_handler,
                    feeder, params, states, opt_state, checkpoint_dir,
                    checkpoint_period, preempted, writer,
                    sync_period=1, prefetch=0, start_batch=0,
                    nan_policy="none", checkpoint_batch_period=0,
                    checkpoint_keep=3, elastic=None):
        from paddle_tpu.reader.prefetch import (
            DevicePrefetcher,
            SynchronousFeeds,
            convert_batch,
            read_batch,
            skip_feed_batches,
        )
        from paddle_tpu.telemetry import tokens_in_feed
        from paddle_tpu.trainer import checkpoint as ckpt

        sync_period = max(int(sync_period or 1), 1)
        prefetch = max(int(prefetch or 0), 0)
        checkpoint_batch_period = max(int(checkpoint_batch_period or 0), 0)
        remainder = flags.get("batch_remainder")
        # host-side evaluators / gradient taps read concrete layer values
        # every batch, i.e. they fence anyway — deferring the cost fence
        # around them would only reorder events for zero overlap
        if sync_period > 1 and (self.declared_evaluators
                                or self._tap_grads is not None):
            log.info("sync_period=%d requested, but host-side evaluators/"
                     "grad taps fence every batch; using sync_period=1",
                     sync_period)
            sync_period = 1
        telem = self._telemetry
        # phase spans (tracing.py; no-ops when --trace_spans is off) +
        # the --profile_steps windowed device capture, keyed by the
        # DISPATCH step counter (fence-time counters lag under deferred
        # fencing, so the window brackets what actually runs)
        tracer = tracing_mod.get_tracer()
        prev_window = getattr(self, "_profile_window", None)
        if prev_window is not None:
            # an elastic replay re-enters _run_passes: a window the
            # aborted entry left open must stop its device trace first
            prev_window.close()
        profile = self._profile_window = tracing_mod.ProfileWindow(
            flags.get("profile_steps"),
            trace_dir=flags.get("profile_dir") or None,
            registry=telem.registry if telem is not None else None,
            tracer=tracer)
        dispatched = {"n": 0}
        # the staleness watchdog reads the global flight ring, so the
        # loop must heartbeat even with telemetry inactive (a ring
        # append — cheap enough to pay unconditionally)
        from paddle_tpu.distributed import multihost as mh

        flight = telem.flight if (telem is not None and
                                  telem.flight is not None) \
            else mh.flight_recorder()

        guard = None
        if nan_policy and nan_policy != "none":
            from paddle_tpu.resilience.guard import NumericGuard

            guard = NumericGuard(
                policy=nan_policy,
                max_consecutive=flags.get("guard_max_consecutive"),
                rescue_batches=flags.get("guard_rescue_batches"),
                rescue_scale=flags.get("guard_rescue_scale"),
                registry=telem.registry if telem is not None else None,
                flight=telem.flight if telem is not None else None)
            if sync_period > 1:
                # the non-finite check must observe each cost before the
                # NEXT step is dispatched, or poisoned parameters spread
                # through the whole deferred window
                log.info("nan_policy=%r fences every batch; using "
                         "sync_period=1", nan_policy)
                sync_period = 1

        def restore_fn_for(opt_template, states_now):
            """Rollback loader for the guard: newest valid checkpoint ->
            replicated state tuple, or None when none exists yet."""
            def restore():
                found = ckpt.latest_checkpoint(checkpoint_dir)
                if found is None:
                    return None
                return self._restore_checkpoint_state(
                    found, opt_template, states_now)

            return restore if checkpoint_dir else (lambda: None)

        def cursor_meta(batches_done, extra=None):
            """Manifest meta for a mid-pass cursor checkpoint: the RNG
            stream (bit-identical replay) + the reader/prefetch cursor
            state resume needs to fast-forward to the same boundary."""
            meta = {
                "completed_pass": False,
                "rng": rng.get_state().tolist(),
                "reader_cursor": {
                    "batches_consumed": batches_done,
                    "shard_index": jax.process_index(),
                    "shard_count": jax.process_count(),
                },
                # staged prefetch feeds are read-ahead only — they are
                # discarded on death and re-derived from the reader on
                # resume, so "drained" is the only state to record
                "prefetch": {"depth": prefetch,
                             "staged_discarded_on_resume": True},
            }
            meta.update(extra or {})
            return meta

        for pass_id in range(start_pass, num_passes):
            event_handler(v2_event.BeginPass(pass_id))
            batch_costs, batch_metrics = [], []
            if self.declared_evaluators:
                self.declared_evaluators.start()

            # steps dispatched but not yet fenced (device arrays for
            # cost/metrics); flushed every sync_period steps with ONE
            # jax.device_get of the whole backlog
            pending: list[dict] = []
            window = {"t0": _time.perf_counter()}

            def flush_pending():
                if not pending:
                    return
                # the deferred-fence drain: nested under the current
                # step span when a batch triggered it, top-level for
                # the end-of-pass / elastic-drain backlog flushes
                tk_fence = tracer.begin("fence", cat="trainer",
                                        steps=len(pending))
                t_f0 = _time.perf_counter()
                host_vals = jax.device_get(
                    [(p["cost"], p["metrics"]) for p in pending])
                t_f1 = _time.perf_counter()
                stall_ms = (t_f1 - t_f0) * 1e3 / len(pending)
                # per-step time: with per-step fencing, dispatch+fence —
                # the seed's device-bounded step_ms.  Under deferred
                # fencing the device time of ONE step is unobservable
                # (that is the point), so step_ms becomes the amortized
                # WALL time per step over the window (input wait
                # included) — the honest throughput number; derived
                # rates (ex/s, MFU%) then measure achieved throughput
                # rather than an inflated dispatch-only figure
                amort_ms = (t_f1 - window["t0"]) * 1e3 / len(pending)
                for p, (cost_h, metrics_h) in zip(pending, host_vals):
                    cost_f = float(cost_h)
                    if not np.isfinite(cost_f) and flags.get("debug_nans"):
                        # ≅ the reference's feenableexcept FP trapping
                        # (TrainerMain.cpp:49): stop at the poisoned batch
                        raise FloatingPointError(
                            f"non-finite cost {cost_f} at pass "
                            f"{p['pass_id']} batch {p['batch_id']} "
                            f"(flags.debug_nans)")
                    metrics_f = {k: float(v) for k, v in metrics_h.items()}
                    batch_costs.append(cost_f)
                    batch_metrics.append(metrics_f)
                    if telem is not None:
                        telem.record_step(
                            loss=cost_f,
                            step_ms=(p["dispatch_ms"] + stall_ms
                                     if sync_period == 1 else amort_ms),
                            examples=p["examples"], tokens=p["tokens"],
                            flops=p["flops"], bytes_accessed=p["bytes"],
                            pass_id=p["pass_id"], batch_id=p["batch_id"],
                            metrics=metrics_f, comm=p["comm"],
                            input_wait_ms=p["wait_ms"],
                            host_stall_ms=stall_ms,
                            padding_ratio=(p["padded_ts"] / p["total_ts"]
                                           if p["total_ts"] else None))
                    event_handler(v2_event.EndIteration(
                        p["pass_id"], p["batch_id"], cost_f, metrics_f,
                        self))
                pending.clear()
                tracer.end(tk_fence)
                ledger = getattr(self, "_goodput_ledger", None)
                if ledger is not None:
                    # the flush cadence is the ledger's fold cadence:
                    # frequent enough that the span ring can't wrap a
                    # whole fold interval on any realistic run
                    ledger.fold()
                window["t0"] = _time.perf_counter()

            # mid-pass resume: fast-forward the reader past the batches
            # the checkpoint already applied (no feed conversion, no
            # device placement, no RNG keys consumed — the manifest's
            # restored stream stays aligned with the replayed batches)
            skip = start_batch if pass_id == start_pass else 0
            if skip:
                log.info("pass %d: fast-forwarding the reader past %d "
                         "already-applied batches", pass_id, skip)
                pass_reader = skip_feed_batches(
                    reader, skip, replicas=self.mesh.num_replicas,
                    remainder=remainder,
                    heartbeat=lambda i: flight.heartbeat(
                        "fast_forward", pass_id=pass_id, batch_id=i))
            else:
                pass_reader = reader
            # the unmodified v2 configuration (no prefetch, strict
            # remainder) keeps the SEED's exact event order — batch pull,
            # BeginIteration, THEN feed conversion, so a handler may still
            # mutate feeder/curriculum state for the CURRENT batch; any
            # opt-in overlap/remainder feature converts before the event
            v2_order = prefetch == 0 and remainder == "error"
            if prefetch > 0:
                feeds = DevicePrefetcher(pass_reader, feeder, self.mesh,
                                         depth=prefetch,
                                         remainder=remainder)
            elif not v2_order:
                feeds = SynchronousFeeds(pass_reader, feeder, self.mesh,
                                         remainder=remainder)
            else:
                feeds = None
                raw_it = iter(pass_reader())
            pass_complete = False

            def maybe_cursor_checkpoint():
                # mid-pass cursor checkpoint: bounds lost work to
                # checkpoint_batch_period batches; resume replays from
                # this exact boundary.  The carried arrays already
                # include every dispatched step, so no fence beyond the
                # save's own host copy is needed.  Called on BOTH the
                # finite path and the guard's skip path — a NaN landing
                # on a period boundary must not stretch the bound to 2N
                if not (checkpoint_dir and checkpoint_batch_period
                        and batch_id > skip
                        and batch_id % checkpoint_batch_period == 0):
                    return
                flight.heartbeat("checkpoint", pass_id=pass_id,
                                 batch_id=batch_id)
                save = (ckpt.save_checkpoint if writer is None
                        else writer.save)
                with tracer.span("checkpoint", cat="trainer",
                                 pass_id=pass_id, batch_id=batch_id):
                    save(checkpoint_dir, pass_id,
                         {n: np.asarray(params[n]) for n in params},
                         opt_state=opt_state, states=dict(states),
                         keep_last=checkpoint_keep, batch_id=batch_id,
                         meta=cursor_meta(batch_id))

            def drain_checkpoint(host_params, host_opt, host_states):
                # elastic drain boundary: persist the exact state the
                # rebuild re-places, so (a) a crash mid-reshard resumes
                # here and (b) a fresh run at the new degree resuming
                # from this cursor replays the identical trajectory —
                # the bit-identity anchor the elastic tests assert
                if writer is not None:
                    try:  # a stale deferred write error must not mask
                        writer.wait()  # the drain save
                    except Exception as e:
                        log.warning("async checkpoint write had failed "
                                    "(%s); writing the elastic drain "
                                    "checkpoint synchronously", e)
                flight.heartbeat("checkpoint", pass_id=pass_id,
                                 batch_id=batch_id)
                with tracer.span("drain", cat="elastic",
                                 pass_id=pass_id, batch_id=batch_id):
                    ckpt.save_checkpoint(
                        checkpoint_dir, pass_id,
                        {n: np.asarray(v)
                         for n, v in host_params.items()},
                        opt_state=host_opt, states=dict(host_states),
                        keep_last=checkpoint_keep, batch_id=batch_id,
                        meta=cursor_meta(batch_id,
                                         {"elastic_drain": True}))

            def maybe_elastic():
                # elastic drain point (once per batch boundary): consume
                # pending membership events — flush the deferred-fence
                # backlog first so every dispatched step retires on the
                # old mesh, then rebuild and re-place.  The feed
                # pipeline is re-bound to the new mesh (staged prefetch
                # feeds are re-placed, not dropped: no reader batch is
                # lost or replayed on the live path).
                nonlocal params, opt_state, states
                if elastic is None or not elastic.pending():
                    return
                flush_pending()
                while elastic.pending():
                    out = elastic.apply(
                        self, params, opt_state, states, pass_id,
                        batch_id,
                        drain_checkpoint=(drain_checkpoint
                                          if checkpoint_dir else None))
                    if out is None:
                        break
                    params, opt_state, states = (out.params,
                                                 out.opt_state,
                                                 out.states)
                    if feeds is not None:
                        feeds.rebind_mesh(self.mesh)
                    if out.replay_cursor is not None:
                        raise _ElasticReplay(
                            int(out.replay_cursor["pass_id"]),
                            int(out.replay_cursor.get("batch_id", 0)),
                            params, opt_state, states)

            tk_step = None
            try:
                batch_id = skip
                feed_it = iter(feeds) if feeds is not None else None
                while True:
                    # one "step" span per batch, with feed / compute /
                    # fence / checkpoint / guard_rescue children — the
                    # timeline the /trace endpoint and trace_merge
                    # render.  Both tokens are canceled (not recorded)
                    # when the pull turns out to be the end-of-pass
                    # sentinel.
                    if self._setup_span is not None:
                        tracer.end(self._setup_span)    # the first step
                        self._setup_span = None
                    tk_step = tracer.begin("step", cat="trainer",
                                           pass_id=pass_id,
                                           batch_id=batch_id)
                    tk_feed = tracer.begin("feed", cat="trainer")
                    if v2_order:
                        # input_wait_ms covers the reader pull AND the
                        # conversion — the same accounting as the feed
                        # iterators, so the host-starvation signal doesn't
                        # change meaning with the knobs
                        t_feed0 = _time.perf_counter()
                        try:
                            data_batch = read_batch(raw_it)
                        except StopIteration:
                            tracer.cancel(tk_feed)
                            tracer.cancel(tk_step)
                            pass_complete = True
                            break
                        event_handler(v2_event.BeginIteration(pass_id,
                                                              batch_id))
                        with stat.timer("feed"):
                            examples, feed, _, padded_ts, total_ts = \
                                convert_batch(data_batch, feeder, self.mesh,
                                              remainder)
                        wait_ms = (_time.perf_counter() - t_feed0) * 1e3
                    else:
                        with stat.timer("feed"):
                            try:
                                fb = next(feed_it)
                            except StopIteration:
                                tracer.cancel(tk_feed)
                                tracer.cancel(tk_step)
                                pass_complete = True
                                break
                            examples, feed, wait_ms = (
                                fb.examples, fb.feed, fb.input_wait_ms)
                            padded_ts, total_ts = (fb.padded_timesteps,
                                                   fb.total_timesteps)
                        event_handler(v2_event.BeginIteration(pass_id,
                                                              batch_id))
                    tracer.end(tk_feed)
                    sig = _feed_signature(feed)
                    new_sig = sig not in self._compiled_sigs
                    if new_sig:
                        self._compiled_sigs.add(sig)
                        if len(self._compiled_sigs) > 1:
                            log.info("train step: compiling new feed "
                                     "signature %s", sig)
                    step_key = rng.next_key()
                    if telem is not None and telem.registry.active:
                        # FLOPs/bytes/comm of THIS signature's program
                        # (cached; lower() only traces — the live args are
                        # not read).  Under an armed tracer that lowering
                        # is a kept ``program_ready`` span with the
                        # program's ``routes`` and ``op_scopes``: its
                        # ``compile()`` is what the dispatch below fetches
                        def lower_step():
                            lower = lambda: self._train_step.lower(
                                params, opt_state, states, feed, step_key)
                            if not tracer.enabled:
                                return lower()
                            with tracer.timed("program_ready",
                                              program="step") as one:
                                return scopes.compile_described(one,
                                                                lower)[0]

                        step_flops, step_bytes, step_comm = telem.cost_for(
                            sig, lower_step)
                    else:
                        step_flops, step_bytes, step_comm = 0.0, 0.0, {}
                    if self._tap_grads is not None:
                        # same key as the step: the printed d(cost)/d(layer)
                        # corresponds to the exact update being taken
                        tap_grads = self._tap_grads(params, states, feed,
                                                    step_key)
                    else:
                        tap_grads = None
                    # pre-step heartbeat: a hang inside the step leaves
                    # "begin_batch" as this host's last sign of life.
                    # pass/batch ids are stamped explicitly — under
                    # deferred fencing global_step lags dispatch by up
                    # to sync_period-1 steps (it advances at fence
                    # time), so step alone would misattribute a hang
                    flight.heartbeat(
                        "begin_batch",
                        step=telem.global_step if telem is not None else -1,
                        pass_id=pass_id, batch_id=batch_id)
                    if guard is not None:
                        # the jitted step donates its inputs; these
                        # copies are the only way to undo the update
                        prev_snap = guard.snapshot(params, opt_state,
                                                   states)
                    if new_sig:
                        # must be the NEWEST beat when the step call
                        # below triggers XLA compilation: the staleness
                        # watchdog grants a "compiling" tag its own
                        # (long) grace window — compiles are minutes of
                        # legitimate heartbeat silence
                        flight.heartbeat("compiling", pass_id=pass_id,
                                         batch_id=batch_id)
                    n_disp = dispatched["n"]
                    profile.maybe_start(n_disp)
                    t_step0 = _time.perf_counter()
                    with stat.timer("forwardBackward+update"):
                        tk_compute = tracer.begin("compute", cat="trainer")
                        params, opt_state, states, cost, metrics = \
                            self._train_step(params, opt_state,
                                             states, feed, step_key)
                        if tk_compute is not None:
                            # compile=True marks the dispatch under which
                            # XLA's backend compiled (the listener's count;
                            # a fetch from the persistent cache is not one)
                            # — the goodput ledger books the whole span as
                            # "recompile", not "compute"
                            tracer.end(tk_compute, compile=bool(
                                tk_compute.args.get("compiles")))
                    dispatched["n"] = n_disp + 1
                    profile.maybe_stop(n_disp + 1, fence=cost)
                    if guard is not None:
                        cost_now = float(jax.device_get(cost))
                        if not np.isfinite(cost_now):
                            with tracer.span("guard_rescue", cat="trainer",
                                             policy=nan_policy):
                                params, opt_state, states = \
                                    guard.handle_nonfinite(
                                        cost_now, pass_id, batch_id,
                                        prev_snap,
                                        restore_fn_for(prev_snap[1],
                                                       prev_snap[2]))
                            # the poisoned update never happened: no
                            # events, no step record — but the batch and
                            # its RNG key stay consumed, so a later
                            # kill-and-resume replays this exact skip
                            batch_id += 1
                            if preempted["flag"]:
                                flush_pending()
                                tracer.end(tk_step)
                                break
                            maybe_cursor_checkpoint()
                            maybe_elastic()
                            tracer.end(tk_step)
                            continue
                        params = guard.after_finite_step(prev_snap[0],
                                                         params)
                    if self.declared_evaluators or tap_grads is not None:
                        # host-side evaluators read device values right
                        # below, which would absorb the device wait
                        # OUTSIDE both timers; fence here (the readback
                        # waits for the step) so step_ms stays
                        # device-bounded exactly like the seed's
                        # float(cost)
                        jax.device_get(cost)
                    dispatch_ms = (_time.perf_counter() - t_step0) * 1e3
                    if self.declared_evaluators:
                        # layer values ride along in the metrics dict from
                        # the SAME forward the update used (fetch_layers) —
                        # no second pass
                        layer_vals = {
                            k[len("layer:"):]: v for k, v in metrics.items()
                            if k.startswith("layer:")}
                        self.declared_evaluators.eval_batch(
                            layer_vals, grads=tap_grads, feed=feed)
                    metrics = {k: v for k, v in metrics.items()
                               if not k.startswith("layer:")}
                    event_handler(v2_event.EndForwardBackward(
                        pass_id, batch_id, self))
                    pending.append({
                        "pass_id": pass_id, "batch_id": batch_id,
                        "cost": cost, "metrics": metrics,
                        "examples": examples,
                        "tokens": tokens_in_feed(feed),
                        "flops": step_flops, "bytes": step_bytes,
                        "comm": step_comm, "wait_ms": wait_ms,
                        "dispatch_ms": dispatch_ms,
                        "padded_ts": padded_ts, "total_ts": total_ts,
                    })
                    batch_id += 1
                    if len(pending) >= sync_period or preempted["flag"]:
                        flush_pending()
                    if preempted["flag"]:
                        tracer.end(tk_step)
                        break
                    maybe_cursor_checkpoint()
                    maybe_elastic()
                    tracer.end(tk_step)
                flush_pending()  # end-of-pass backlog
            finally:
                # an exception mid-batch (elastic replay, a supervisor-
                # retryable fault) must not leave the in-flight step
                # token on this thread's span stack, or every span of
                # the NEXT attempt would be mis-parented under it —
                # cancel truncates the stack from the token up
                # (idempotent for a cleanly ended one)
                tracer.cancel(tk_step)
                # preemption-drain / early exit: stop the prefetch worker
                # and drop staged feeds, so the checkpoint below sits on a
                # consistent batch boundary and no thread leaks
                if feeds is not None:
                    feeds.close()
            # write back for checkpoint/event access
            with tracer.span("params_sync", cat=tracing_mod.SETUP_CAT,
                             back=True) as tk_sync:
                self.parameters.update_from(params)
                if tk_sync is not None:
                    tk_sync.args.update(_tree_size(params))
            self.states = dict(states)
            self._opt_state = opt_state
            if preempted["flag"] and not pass_complete:
                # mid-pass eviction: checkpoint the partial pass with its
                # (pass, batch) cursor — no EndPass fires, the save
                # ignores checkpoint_period, and resume replays THIS pass
                # from the exact batch boundary (bit-identically: the
                # manifest carries the RNG stream and the reader is
                # fast-forwarded past the applied batches).
                if checkpoint_dir:
                    if writer is not None:
                        # eviction save must be durable AND must not be
                        # skipped by a stale deferred write error
                        try:
                            writer.wait()
                        except Exception as e:
                            log.warning("async checkpoint write had "
                                        "failed (%s); writing eviction "
                                        "checkpoint synchronously", e)
                    flight.heartbeat("checkpoint", pass_id=pass_id,
                                     batch_id=batch_id)
                    ckpt.save_checkpoint(
                        checkpoint_dir, pass_id,
                        {n: np.asarray(params[n]) for n in params},
                        opt_state=opt_state, states=dict(states),
                        keep_last=checkpoint_keep, batch_id=batch_id,
                        meta=cursor_meta(batch_id, {"preempted": True}),
                    )
                    log.info("preempted in pass %d: cursor checkpoint "
                             "written; resume replays pass %d from "
                             "batch %d", pass_id, pass_id, batch_id)
                break
            avg_metrics = _mean_dicts(batch_metrics)
            if self.declared_evaluators:
                avg_metrics.update(self.declared_evaluators.finish())
            event_handler(v2_event.EndPass(pass_id, avg_metrics))
            save_dir = flags.get("save_dir")
            if save_dir and (pass_id % max(flags.get("saving_period"), 1) == 0):
                self.save_parameter_to_tar_path(
                    os.path.join(save_dir, f"pass-{pass_id:05d}.tar")
                )
            if checkpoint_dir and (pass_id % max(checkpoint_period, 1) == 0
                                   or preempted["flag"]):
                flight.heartbeat("checkpoint", pass_id=pass_id)
                save = ckpt.save_checkpoint if writer is None else writer.save
                save(
                    checkpoint_dir, pass_id,
                    {n: np.asarray(params[n]) for n in params},
                    opt_state=opt_state, states=dict(states),
                    keep_last=checkpoint_keep,
                    meta={"avg_metrics": avg_metrics,
                          "rng": rng.get_state().tolist()},
                )
            stat.global_stat.print_all_status()
            if preempted["flag"]:
                # SIGTERM landed exactly as the pass finished: the normal
                # end-of-pass checkpoint above is the resume point
                break

    def test(self, reader, feeding=None) -> v2_event.TestResult:
        """≅ SGD.test: forward-only over a reader of batches.  When the
        optimizer keeps a model average (``settings(..., model_average=
        ModelAverage(average_window=...))``), the averaged parameters are
        swapped in for the duration of the test, exactly as the reference's
        ``AverageOptimizer::apply()``/``restore()`` bracket
        (``paddle/parameter/AverageOptimizer.h:63-64``) does around
        ``Trainer::test`` — being functional, nothing needs restoring."""
        self._ensure_built()
        feeder = self._default_feeder(feeding)
        params = self._params_dict()
        avg = self.optimizer.averaged(self._opt_state)
        if avg is not None:
            params.update(avg)
        states = self.states
        costs, metrics_list, n = [], [], 0
        if self.declared_evaluators:
            self.declared_evaluators.start()
        taps = (self.declared_evaluators.grad_tap_layers()
                if self.declared_evaluators else [])
        if taps and self._tap_grads_eval is None:
            from paddle_tpu.trainer.step import build_tap_grads

            # eval-mode forward (dropout off), matching _eval_step's pass;
            # cached: build_tap_grads jits, one compile per topology
            self._tap_grads_eval = build_tap_grads(self.topology, taps,
                                                   is_train=False)
        tap_grads_eval = self._tap_grads_eval
        from paddle_tpu.reader.prefetch import SynchronousFeeds

        # same partial-batch policy as training, so a non-divisible final
        # eval batch doesn't kill a multi-device run ("drop" keeps metrics
        # exact and skips fully-dropped batches; "pad" over-weights the
        # last sample)
        for fb in SynchronousFeeds(
                reader, feeder, self.mesh,
                remainder=flags.get("batch_remainder")):
            feed = fb.feed
            values, cost, metrics = self._eval_step(params, states, feed)
            if self.declared_evaluators:
                grads = None
                if tap_grads_eval is not None:
                    grads = tap_grads_eval(params, states, feed,
                                           jax.random.key(0))
                self.declared_evaluators.eval_batch(values, grads=grads,
                                                    feed=feed)
            costs.append(float(cost))
            metrics_list.append({k: float(v) for k, v in metrics.items()})
            n += 1
        enforce(n > 0, "test reader yielded no batches")
        metrics = _mean_dicts(metrics_list)
        if self.declared_evaluators:
            metrics.update(self.declared_evaluators.finish())
        return v2_event.TestResult(metrics, float(np.mean(costs)))

    def averaged_parameters(self) -> Parameters:
        """A ``Parameters`` copy with the model-averaged values swapped in
        (≅ reading PARAMETER_APPLY after ``AverageOptimizer::apply()``) —
        hand this to ``paddle.infer(parameters=...)`` to run inference on
        the averaged weights.  Falls back to the raw parameters when no
        average is kept."""
        import copy

        out = copy.copy(self.parameters)
        out._values = dict(self.parameters._values)
        avg = self.optimizer.averaged(self._opt_state)
        if avg is not None:
            for name, val in avg.items():
                out._values[name] = jax.numpy.asarray(val)
        return out

    # -- checkpointing (ParamUtil / Parameters.to_tar parity) -----------------
    def save_parameter_to_tar(self, f) -> None:
        self._merge_states_into_parameters()
        self.parameters.to_tar(f)

    def save_parameter_to_tar_path(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "wb") as f:
            self.save_parameter_to_tar(f)
        log.info("saved checkpoint %s", path)

    def _merge_states_into_parameters(self):
        from paddle_tpu.core import initializer as I
        from paddle_tpu.core.parameters import ParamSpec

        for name, v in self.states.items():
            arr = np.asarray(v)
            if name not in self.parameters:
                self.parameters.add(ParamSpec(
                    name=name, shape=tuple(arr.shape),
                    initializer=I.constant(0.0), is_static=True,
                ))
            self.parameters._values[name] = jax.numpy.asarray(arr)


def _tree_size(tree) -> dict:
    """``arrays`` and ``bytes`` of a pytree's leaves (set-up span args)."""
    leaves = jax.tree.leaves(tree)
    return {"arrays": len(leaves),
            "bytes": sum(int(getattr(x, "nbytes", 0)) for x in leaves)}


def _mean_dicts(dicts: list[dict]) -> dict:
    if not dicts:
        return {}
    keys = dicts[0].keys()
    return {k: float(np.mean([d[k] for d in dicts if k in d])) for k in keys}


def _default_event_handler(e) -> None:
    if isinstance(e, v2_event.EndIteration):
        if e.batch_id % flags.get("log_period") == 0:
            log.info(
                "Pass %d, Batch %d, Cost %f, %s", e.pass_id, e.batch_id, e.cost,
                e.metrics,
            )
    elif isinstance(e, v2_event.EndPass):
        log.info("Pass %d done, %s", e.pass_id, e.metrics)
