"""paddle_tpu.serving — the online inference engine (TPU-native serving).

The reference framework served models through the C gradient-machine API
(``paddle/capi/gradient_machine.h``; MIGRATION.md maps it).  This package
is its production-scale successor: continuous batching over a paged
KV-cache for the transformer LM, plus a micro-batching dense path for the
CTR/recommender models.

- ``kv_cache``   — PageAllocator (free-list, null page 0) + PagedKVCache
  (device page pools + host page tables, and the token array: the last
  token every slot sampled, int32[max_slots], kept on the device beside
  the pools so a decode step's input never passes through the host);
- ``scheduler``  — continuous-batching request scheduler: admission
  control by free pages / concurrent-token budget, prefill/decode
  interleave, per-step join/retire; deterministic given seed + arrival
  order;
- ``engine``     — ServingEngine: thread-safe submit()/results() over a
  background step loop (or synchronous ``run_until_idle`` for CLIs and
  tests), jitted prefill/decode closures, per-request telemetry
  (queue wait, TTFT, TPOT) through the MetricsRegistry.  The loop runs
  one pass ahead: it dispatches decode step n + 1 — built from the
  scheduler's count of tokens in flight, its input read from the token
  array — before it reads step n's tokens, hands those out one
  iteration later, and drains only where it must (idle, ``stop()``, a
  weight swap, the incremental prefill path, a model that generates by
  blocks);
- ``sampling``   — greedy + temperature sampling under explicit PRNG keys;
- ``export``     — checkpoint -> servable artifact (sha256 manifest, the
  trainer checkpoint format's serving twin);
- ``dense``      — DenseBatcher: micro-batching front-end for the batch
  v2 ``Inference`` path (CTR / recommender scoring);
- ``fleet``      — FleetConfig + LocalReplica + build_local_fleet: N
  replica engines behind one router (``distributed.launch --serving``
  is the subprocess twin);
- ``router``     — FleetRouter: load balancing, health-checked
  failover (idempotent by fleet-global request id), overload shedding
  with RetryAfter, per-request deadlines, zero-downtime weight swap;
- ``health``     — HealthProbe/FleetHealth: per-replica liveness
  verdicts (crash / hang / stale / membership);
- ``client``     — backoff_submit: the shared client-side RetryAfter
  back-off loop (deterministic capped jitter);
- ``__main__``   — ``python -m paddle_tpu.serving`` stdin CLI loop
  (``--replicas N`` serves through a local fleet).

Attention kernel: ``ops/pallas/paged_attention.py`` (ragged paged
attention; Pallas on TPU, pure-jnp reference elsewhere).
"""

from paddle_tpu.serving.client import backoff_submit  # noqa: F401
from paddle_tpu.serving.engine import ServingEngine  # noqa: F401
from paddle_tpu.serving.fleet import (  # noqa: F401
    FleetConfig,
    LocalReplica,
    build_local_fleet,
    clone_replica,
    fleet_launch_argv,
)
from paddle_tpu.serving.health import FleetHealth, HealthProbe  # noqa: F401
from paddle_tpu.serving.router import (  # noqa: F401
    FleetRouter,
    ReplicaLost,
    RetryAfter,
    SwapFailed,
)
from paddle_tpu.serving.export import (  # noqa: F401
    checkpoint_path_to_servable,
    checkpoint_to_servable,
    export_servable,
    load_servable,
)
from paddle_tpu.serving.kv_cache import PageAllocator, PagedKVCache  # noqa: F401
from paddle_tpu.serving.scheduler import (  # noqa: F401
    Request,
    RequestResult,
    Scheduler,
    ServingConfig,
)
from paddle_tpu.serving.sampling import sample_tokens  # noqa: F401
