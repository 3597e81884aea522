"""paddle_tpu.telemetry — the unified metrics/observability layer.

See :mod:`paddle_tpu.metrics` (the user-facing facade) for the overview;
this package holds the implementation:

- ``registry``     — MetricsRegistry + Counter/Gauge/Histogram + comm
  accounting used by the collective wrappers;
- ``sinks``        — JsonlSink / MemorySink / LoggingSink;
- ``step_metrics`` — StepTelemetry, the per-step record builder behind
  ``SGD.train`` and ``trainer/cli.py``;
- ``tracing``      — Span/Tracer phase timeline (Chrome-trace export)
  + the ``--profile_steps`` ProfileWindow;
- ``scopes``       — ``part(name)``: the named scopes that say which
  sublayer issued a device operation, ``part_of`` / ``op_scopes``: their
  readers (a held program's operations by part);
- ``introspect``   — the per-process ``--status_port`` HTTP server
  (/metrics /healthz /snapshot /trace) + the Prometheus scrape
  helpers the fleet aggregator uses.
"""

from paddle_tpu.telemetry.registry import (  # noqa: F401
    SCHEMA,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    capture_comm,
    census_by_kind,
    comm_snapshot,
    get_default_registry,
    host_index,
    record_comm,
    safe_inc,
    swallow,
)
from paddle_tpu.telemetry.scopes import (  # noqa: F401
    op_scopes,
    part,
    part_of,
)
from paddle_tpu.telemetry.sinks import (  # noqa: F401
    JsonlSink,
    LoggingSink,
    MemorySink,
    json_default,
)
from paddle_tpu.telemetry.step_metrics import (  # noqa: F401
    StepTelemetry,
    tokens_in_feed,
)
from paddle_tpu.telemetry.tracing import (  # noqa: F401
    ProfileWindow,
    Span,
    Tracer,
    configure_tracing,
    get_tracer,
    parse_profile_steps,
)
