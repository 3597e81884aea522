"""paddle_tpu.deploy — the train→serve control plane.

ROADMAP item 5's spine: the pieces that existed below this package —
trainer checkpoints with sha256 manifests, ``export_servable``, the
fleet router's zero-downtime ``swap_servable``, ``scrape_replicas``
rollups, and ``ElasticCoordinator``'s live mesh reshard — but nothing
connected them, so a weight push, a traffic spike or a diurnal load
shift was an operator's manual job.  The reference ran this loop in
production (pserver fleets continuously absorbing trainer updates while
serving, PAPER.md §pserver); these three controllers close it here:

- ``controller``  — :class:`DeploymentController`: watches a checkpoint
  dir (cursor order, sha256-valid manifests only), exports each new
  checkpoint as a servable, rolls it across the fleet replica-by-replica
  while traffic flows, smoke-verified against the model's own greedy
  continuation — full rollback on any failure, one ledger record per
  attempt, RetryPolicy-bounded redial on transient export I/O;
- ``autoscaler``  — :class:`SloAutoscaler` + :class:`AutoscalePolicy`:
  p99 TTFT / queue depth / shed counters / free-page watermark through
  a hysteresis-banded policy (scale up fast on SLO breach, scale down
  slow on sustained idle, cooldowns between actions; deterministic
  under an injectable fake clock) driving the router's
  ``add_replica`` / ``remove_replica`` — the scale-down victim drains
  through the failover re-queue path, so zero requests are lost;
- ``arbiter``     — :class:`PoolArbiter`: one accelerator pool, two
  tenants.  Serving pressure borrows a host from the training mesh
  (``ElasticCoordinator`` drain→reshard down); sustained serving idle
  gives it back (reshard up) — the diurnal curve.

``tests/test_deploy.py`` drives the loop: checkpoints roll out under
traffic, a ``servable_corrupt`` chaos fault forces a clean rollback with
no request lost, and scale/rollout/rollback timings land in the
``deploy`` / ``autoscale`` telemetry records (``tools/metrics_to_md.py``
renders both tables).

Every background loop here follows the serving crash contract: a loop
death is stored, counted (``serve_loop_crashes``) and re-raised at the
next public call — deployments never stop silently.
"""

from paddle_tpu.deploy.arbiter import PoolArbiter  # noqa: F401
from paddle_tpu.deploy.autoscaler import (  # noqa: F401
    AutoscalePolicy,
    SloAutoscaler,
    rollup_from_router,
    rollup_from_scrape,
)
from paddle_tpu.deploy.controller import DeploymentController  # noqa: F401
