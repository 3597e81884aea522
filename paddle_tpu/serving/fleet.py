"""The serving fleet: replica engines + the router that makes them one.

PR 6's :class:`~paddle_tpu.serving.engine.ServingEngine` serves from one
host; this module grows it into the fleet ROADMAP item 1 asks for — N
replica engines behind a :class:`~paddle_tpu.serving.router.FleetRouter`
that load-balances, health-checks, fails over, sheds overload and swaps
weights with zero downtime (the router module documents each).  Two
deployment shapes share the code:

- **in-process** (:func:`build_local_fleet`) — N
  :class:`LocalReplica`\\ s, each its own ServingEngine over its own
  paged KV-cache, pumped by the router.  This is the deterministic
  shape the chaos tests drive, and
  a fine production shape for one host with per-replica page pools.
- **subprocess** (``distributed.launch --serving``;
  :func:`fleet_launch_argv` builds the command) — one
  ``python -m paddle_tpu.serving`` process per replica, rank death
  downgraded to a membership event the health monitor consumes
  (:meth:`~paddle_tpu.serving.health.FleetHealth.observe_membership`)
  instead of killing the fleet.

Every replica shares ONE (model cfg, serving cfg) — including the
sampling seed — and request ids are fleet-global, so WHERE a request
runs never changes WHAT it generates: the failover re-dispatch in
``router.py`` is token-for-token invisible, which
``tests/test_fleet.py`` asserts against a fault-free run.
"""

from __future__ import annotations

import dataclasses
import sys
import time

from paddle_tpu.core.enforce import enforce
from paddle_tpu.serving.engine import ServingEngine
from paddle_tpu.serving.health import HealthProbe
from paddle_tpu.serving.router import FleetRouter, ReplicaLost


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet-level knobs (per-replica knobs stay in ServingConfig)."""

    # -- overload shedding (0 disables each watermark) --
    slo_p99_ttft_ms: float = 0.0   # shed once observed p99 TTFT breaches
    shed_queue_depth: int = 0      # shed once pending+inflight reaches this
    shed_free_page_frac: float = 0.0  # shed once fleet free pages dip below
    retry_after_s: float = 0.25    # client back-off hint on RetryAfter
    default_ttl_s: float = 0.0     # per-request deadline (0 = none)
    # -- failover --
    redial_attempts: int = 3       # RetryPolicy bound: total dispatches
    stale_after_s: float = 60.0    # wall-clock heartbeat backstop
    hang_rounds: int = 0           # no-progress rounds before "hang" (0=off)
    # -- weight swap --
    smoke_prompt: tuple = (1, 2, 3)
    smoke_tokens: int = 4


class LocalReplica:
    """One in-process replica: a ServingEngine the router pumps.

    The engine runs WITHOUT its background thread — the router is the
    single driver, which keeps the whole fleet deterministic (and one
    pump thread is the right amount of host CPU for N engines whose
    real work is jitted).  ``kill()``/``hang()`` are the chaos surface:
    kill abandons the engine (a crashed process), hang wedges the pump
    while staying "alive" (the stuck-worker failure mode health
    detection exists for)."""

    def __init__(self, index: int, cfg, params, serving, registry=None,
                 clock=time.monotonic):
        self.index = index
        self.cfg = cfg
        self.serving = serving
        self.engine = ServingEngine(cfg, params, serving,
                                    registry=registry,
                                    device=replica_device(index))
        self._clock = clock
        self._dead: str | None = None
        self._hung = False
        self._progress = 0
        self._last_beat = clock()

    # -- router surface --------------------------------------------------------
    def check(self, prompt, max_new_tokens=None):
        return self.engine.check_request(prompt, max_new_tokens)

    def prefix_peek(self, prompt) -> int:
        """Tokens of this prompt already resident in the replica's
        prefix cache — the router's cache-affinity signal.  Pure read:
        no LRU touch, no hit/miss stats."""
        if self._dead is not None:
            return 0
        prefix = self.engine.cache.prefix
        return 0 if prefix is None else prefix.peek(prompt)

    def submit(self, prompt, max_new_tokens, temperature,
               request_id: int) -> None:
        if self._dead is not None:
            raise ReplicaLost(
                f"replica {self.index} is dead ({self._dead})")
        self.engine.submit(prompt, max_new_tokens, temperature,
                           request_id=request_id)

    def pump(self) -> bool:
        """One engine step; False when idle, dead or hung."""
        if self._dead is not None or self._hung:
            return False
        worked = self.engine.step()
        if worked:
            self._progress += 1
            self._last_beat = self._clock()
        return worked

    def collect(self):
        """Drain completed results (non-blocking)."""
        if self._dead is not None:
            return []
        return self.engine.results()

    def probe(self) -> HealthProbe:
        sched = self.engine.scheduler
        cache = self.engine.cache
        free = cache.allocator.free_pages
        if cache.prefix is not None:
            # cached-but-unmapped pages are reclaimable on demand (LRU
            # eviction runs before OutOfPages), so a warm cache must not
            # look like memory pressure to shed_free_page_frac
            free += cache.prefix.reclaimable_pages()
        return HealthProbe(
            replica=self.index, alive=self._dead is None,
            queued=self.engine.queued() + len(sched.queue),
            active=len(sched.active),
            free_pages=free,
            total_pages=self.serving.num_pages - 1,
            progress=self._progress, last_beat=self._last_beat,
            reason=self._dead or "")

    # -- chaos surface ---------------------------------------------------------
    def kill(self, reason: str = "killed") -> None:
        """Simulate process death: the engine and everything in it is
        gone (the router re-dispatches its in-flight work)."""
        self._dead = reason

    def hang(self) -> None:
        """Wedge the replica: alive by every cheap measure, but the
        pump makes no progress — only no-progress detection catches
        this one."""
        self._hung = True

    # -- weight-swap surface ---------------------------------------------------
    def swap_params(self, cfg, params):
        """Replace the served weights (the replica must be drained and
        held by the caller).  The model config must be IDENTICAL — the
        jitted prefill/decode closures were built for it; a shape
        change is a new fleet, not a swap.  Returns the old params for
        rollback."""
        enforce(cfg == self.cfg,
                f"replica {self.index}: servable config does not match "
                "the running engine's — a weight swap cannot change "
                "the model shape")
        return self.engine.set_params(params)

    def smoke_decode(self, prompt: list[int], n: int) -> list[int]:
        """Greedy-decode ``n`` tokens through the full serving path
        (the swap's post-swap verification).  Uses a reserved
        high-band request id so fleet ids never collide with it."""
        rid = (1 << 30) + self.index
        self.engine.submit(list(prompt), max_new_tokens=n,
                           request_id=rid)
        self.engine.run_until_idle()
        out = None
        for r in self.engine.results():
            if r.id == rid:
                out = r
            else:  # a router result raced in: leave it for collect()
                self.engine._completed.put(r)
        if out is None:
            raise RuntimeError(
                f"replica {self.index}: smoke decode produced no result")
        return list(out.tokens)


def replica_device(index: int):
    """Replica ``index``'s device: local devices round-robin.  On a
    four-chip host four replicas are four one-chip servers; on one
    device every replica shares it, as before.  Placement never changes
    tokens (same weights, same seed, fleet-global request ids)."""
    import jax

    devices = jax.local_devices()
    return devices[index % len(devices)]


def smoke_check(cfg, params, prompt: list[int],
                tokens: list[int]) -> bool:
    """True iff ``tokens`` is the greedy continuation of ``prompt``
    under ``(cfg, params)`` by one full-context forward pass — the
    engine-vs-model consistency oracle the swap's smoke decode is
    judged against (one compile signature, the test-suite idiom)."""
    if not tokens:
        return False
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    full = list(prompt) + list(tokens)
    logits = T.forward(cfg, params, jnp.asarray([full]))
    want = [int(t) for t in
            jnp.argmax(logits[0, len(prompt) - 1:-1], axis=-1)]
    return list(tokens) == want


def clone_replica(index: int, source: LocalReplica,
                  registry=None, clock=None) -> LocalReplica:
    """Replica factory for :meth:`FleetRouter.add_replica`: a fresh
    :class:`LocalReplica` serving the SOURCE's currently-served weights
    — ``source.engine.params``, not the boot-time params, so a replica
    added after a rolling weight swap joins on the swapped servable —
    under the same model/serving config and sampling seed (placement
    never changes tokens).  Compile-free: engines share the jitted
    closure memo keyed by config.  The autoscaler passes this (wrapped
    with its registry/clock) straight through to ``add_replica``."""
    return LocalReplica(
        index, source.cfg, source.engine.params, source.serving,
        registry=registry if registry is not None
        else source.engine.registry,
        clock=clock if clock is not None else source._clock)


def build_local_fleet(cfg, params, serving, n: int, fleet=None,
                      registry=None, chaos=None,
                      clock=time.monotonic) -> FleetRouter:
    """N in-process replicas (shared model + serving config, shared
    sampling seed, per-replica KV-cache) behind one FleetRouter."""
    enforce(n >= 1, "a fleet needs at least one replica")
    replicas = [LocalReplica(i, cfg, params, serving, registry=registry,
                             clock=clock) for i in range(n)]
    return FleetRouter(replicas, fleet=fleet, registry=registry,
                       chaos=chaos, clock=clock)


def fleet_launch_argv(nreplicas: int, servable: str,
                      *extra: str) -> list[str]:
    """The ``distributed.launch --serving`` command line that runs this
    fleet as one serving process per replica (rank death becomes a
    membership event, not fleet death — see ``launch.py``)."""
    return [sys.executable, "-m", "paddle_tpu.distributed.launch",
            "--serving", "--nproc", str(nreplicas), "--",
            sys.executable, "-m", "paddle_tpu.serving",
            "--servable", servable, *[str(a) for a in extra]]
