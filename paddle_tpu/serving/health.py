"""Replica health: liveness verdicts for the serving fleet.

The reference's Go master judged trainers by etcd lease expiry and
re-queued a dead trainer's tasks; the fleet router needs the same
verdict for replica ServingEngines.  This module is the judgment only —
:class:`FleetHealth` consumes a stream of per-replica
:class:`HealthProbe` snapshots (the router gathers one per pump round)
and decides who is dead and why; the router applies the consequence
(failover, re-dispatch).  Keeping the verdict pure makes it
deterministic: given the same probe stream, the same replicas die at
the same rounds, which is what lets ``tests/test_fleet.py`` assert
token-identical recovery.

Three ways a replica dies (the ``HeartbeatWatchdog`` classification at fleet
granularity):

- **crash** — the probe reports ``alive=False`` (engine loop died, or a
  chaos ``replica_loss`` killed it);
- **hang**  — the replica has work but its monotonic ``progress``
  counter hasn't moved for ``hang_rounds`` consecutive probes (the
  wedged-but-not-crashed worker that burns a fleet; round-based so the
  deterministic tests need no wall clock);
- **stale** — the replica's last productive heartbeat is older than
  ``stale_after_s`` (the wall-clock backstop for threaded/subprocess
  fleets, where a probe itself may be the thing that stopped flowing).

Subprocess fleets (``distributed.launch --serving``) additionally feed
the launcher's membership file through :meth:`observe_membership`: a
replica rank the launcher removed is dead, no probe needed.

Verdicts are permanent: a dead replica stays dead (its in-flight work
was already re-dispatched — letting it back in would duplicate results;
the router's request-id idempotence is the second line of defense).
"""

from __future__ import annotations

import dataclasses
import time

from paddle_tpu.core import logger as log


@dataclasses.dataclass(frozen=True)
class HealthProbe:
    """One replica's instantaneous health snapshot (router-gathered)."""

    replica: int
    alive: bool                 # loop/process up (False = crashed)
    queued: int                 # requests waiting inside the replica
    active: int                 # sequences resident in the decode batch
    free_pages: int             # KV-cache pages on the free list
    total_pages: int            # pool capacity (for watermark shedding)
    progress: int               # monotonic productive-work counter
    last_beat: float            # clock() stamp of the last productive step
    reason: str = ""            # crash detail when alive=False

    @property
    def busy(self) -> bool:
        return self.queued > 0 or self.active > 0


class FleetHealth:
    """Per-replica liveness from the probe stream (see module doc).

    ``hang_rounds=0`` disables no-progress detection (a fleet driven
    slower than its requests arrive would false-positive);
    ``stale_after_s=0`` disables the wall-clock backstop.  ``clock`` is
    injectable so deadline/staleness tests are deterministic.
    """

    def __init__(self, stale_after_s: float = 60.0, hang_rounds: int = 0,
                 clock=time.monotonic, registry=None):
        self.stale_after_s = float(stale_after_s)
        self.hang_rounds = int(hang_rounds)
        self.clock = clock
        self._registry = registry
        self._dead: dict[int, str] = {}
        self._progress: dict[int, int] = {}
        self._stalled: dict[int, int] = {}

    # -- verdicts --------------------------------------------------------------
    def is_dead(self, replica: int) -> bool:
        return replica in self._dead

    def dead(self) -> dict[int, str]:
        """{replica index: reason} for every replica judged dead."""
        return dict(self._dead)

    def alive_count(self, total: int) -> int:
        return total - len(self._dead)

    # -- the judgment ----------------------------------------------------------
    def observe(self, probes: list[HealthProbe]
                ) -> list[tuple[int, str]]:
        """Consume one round of probes; returns the NEWLY dead replicas
        as ``(index, reason)`` (each reported exactly once — the router
        fails over on report)."""
        newly: list[tuple[int, str]] = []
        now = self.clock()
        for p in probes:
            if p.replica in self._dead:
                continue
            reason = self._judge(p, now)
            if reason is None:
                continue
            self._dead[p.replica] = reason
            newly.append((p.replica, reason))
            log.warning("fleet health: replica %d judged dead (%s)",
                        p.replica, reason)
            from paddle_tpu.telemetry import safe_inc

            safe_inc("fleet_replica_down",
                     "serving replicas judged dead by the health monitor",
                     registry=self._registry,
                     reason=reason.split(":")[0])
        return newly

    def _judge(self, p: HealthProbe, now: float) -> str | None:
        if not p.alive:
            return f"crash: {p.reason or 'loop died'}"
        last = self._progress.get(p.replica)
        self._progress[p.replica] = p.progress
        if self.hang_rounds and p.busy and last == p.progress:
            self._stalled[p.replica] = self._stalled.get(p.replica, 0) + 1
            if self._stalled[p.replica] >= self.hang_rounds:
                return (f"hang: no progress for {self._stalled[p.replica]} "
                        f"rounds with {p.queued + p.active} requests "
                        f"resident")
        else:
            self._stalled[p.replica] = 0
        if self.stale_after_s and p.busy \
                and now - p.last_beat > self.stale_after_s:
            return (f"stale: last productive step "
                    f"{now - p.last_beat:.1f}s ago")
        return None

    def observe_membership(self, membership,
                           expected_ranks) -> list[tuple[int, str]]:
        """Subprocess fleets: ranks the launcher's
        :class:`~paddle_tpu.distributed.multihost.Membership` file no
        longer lists are dead — the launch-side verdict (process exit)
        arrives through the same epoch-bumped file elastic training
        uses.  Returns the newly dead, like :meth:`observe`."""
        newly: list[tuple[int, str]] = []
        for rank in membership.missing(expected_ranks):
            if rank in self._dead:
                continue
            reason = (f"membership: rank {rank} removed at epoch "
                      f"{membership.epoch}")
            self._dead[rank] = reason
            newly.append((rank, reason))
            log.warning("fleet health: replica %d judged dead (%s)",
                        rank, reason)
            from paddle_tpu.telemetry import safe_inc

            safe_inc("fleet_replica_down",
                     "serving replicas judged dead by the health monitor",
                     registry=self._registry, reason="membership")
        return newly
