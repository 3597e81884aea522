"""Central runtime-flag registry — successor of ``paddle/utils/Flags.h:19-43``.

The reference declares ~60 gflags centrally (``use_gpu``, ``trainer_count``,
``trainer_id``, ``num_gradient_servers``, ``port``, ``saving_period``, …) and
reads them from every layer of the C++ stack.  Here flags are a typed registry
with env-var override (``PADDLE_TPU_<NAME>``) and CLI parsing, shared by the
trainer CLI and the Python API.  CUDA-era flags are replaced by TPU-era ones
(``use_tpu``, ``mesh_shape``) per the north-star requirement.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable


@dataclasses.dataclass
class _Flag:
    name: str
    default: Any
    help: str
    parser: Callable[[str], Any]
    value: Any = None


_REGISTRY: dict[str, _Flag] = {}


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


def define(name: str, default: Any, help: str = "") -> None:
    if name in _REGISTRY:
        raise ValueError(f"flag {name!r} already defined")
    if isinstance(default, bool):
        parser: Callable[[str], Any] = _parse_bool
    elif isinstance(default, int):
        parser = int
    elif isinstance(default, float):
        parser = float
    else:
        parser = str
    flag = _Flag(name, default, help, parser)
    env = os.environ.get(f"PADDLE_TPU_{name.upper()}")
    if env is not None:
        flag.value = parser(env)
    _REGISTRY[name] = flag


def get(name: str) -> Any:
    f = _REGISTRY[name]
    return f.default if f.value is None else f.value


def set(name: str, value: Any) -> None:  # noqa: A001 - mirrors gflags SetCommandLineOption
    f = _REGISTRY[name]
    f.value = value


def is_set(name: str) -> bool:
    """True when the flag was explicitly overridden (env var, parse_args
    or flags.set) rather than resting at its default — lets callers with
    their own defaults (the trainer CLI) still honor an operator's
    PADDLE_TPU_* override."""
    return _REGISTRY[name].value is not None


def snapshot_raw() -> dict:
    """{name: raw override or None} — the exact override state.  Use
    with :func:`restore_raw` for save/restore: restoring a default
    through ``flags.set`` would leave the flag marked explicitly set
    (poisoning :func:`is_set`), while restoring the raw value does not."""
    return {n: f.value for n, f in _REGISTRY.items()}


def restore_raw(snap: dict) -> None:
    for n, v in snap.items():
        if n in _REGISTRY:
            _REGISTRY[n].value = v


def parse_args(argv: list[str]) -> list[str]:
    """Parse ``--name=value`` / ``--name value`` style args; returns leftovers."""
    rest: list[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("--"):
            body = a[2:]
            if "=" in body:
                k, v = body.split("=", 1)
            else:
                k = body
                if k in _REGISTRY and not isinstance(_REGISTRY[k].default, bool):
                    i += 1
                    v = argv[i] if i < len(argv) else ""
                else:
                    v = "true"
            if k in _REGISTRY:
                f = _REGISTRY[k]
                f.value = f.parser(v)
            else:
                rest.append(a)
        else:
            rest.append(a)
        i += 1
    return rest


def all_flags() -> dict[str, Any]:
    return {n: get(n) for n in _REGISTRY}


# -- declared env passthroughs -------------------------------------------------
#
# Some configuration is process-environment by nature — the launcher's
# per-rank rendezvous variables, externally owned knobs like
# JAX_PLATFORMS — and cannot be a flag (a flag is per-invocation; these
# are per-process and set by another program).  They still must be
# REGISTERED so every env read in the tree is discoverable in one place:
# the GL-ENV static-analysis pass (paddle_tpu/analysis) rejects any
# literal os.environ/os.getenv read whose name is neither a defined
# flag's PADDLE_TPU_<NAME> override nor declared here.

_ENV_REGISTRY: dict[str, str] = {}


def declare_env(name: str, help: str = "") -> None:
    """Register an environment variable read directly (not through a
    flag) somewhere in the tree, with a one-line description."""
    _ENV_REGISTRY[name] = help


def declared_env() -> dict[str, str]:
    return dict(_ENV_REGISTRY)


def known_env_names() -> set[str]:
    """Every env name the tree may legitimately read: each flag's
    PADDLE_TPU_<NAME> override plus the declared passthroughs."""
    # NB: the builtin set() is shadowed by the gflags-mirror set() above
    return {f"PADDLE_TPU_{n.upper()}" for n in _REGISTRY} | {*_ENV_REGISTRY}


# --- The central flag set (TPU-era rewrite of Flags.h:19-43) -----------------
define("use_tpu", True, "run compute on TPU when available (was: use_gpu)")
define("trainer_count", 1, "data-parallel replicas on this host (mesh batch axis)")
define("trainer_id", 0, "distinct id of this trainer process")
define("num_hosts", 1, "number of participating hosts (was: num_gradient_servers)")
define("mesh_shape", "", "device mesh as 'dp,tp' or 'dp,tp,pp' (empty = all-dp)")
define("zero", 0, "weight-update sharding over the mesh data axis (the "
                  "pserver's sharded aggregation, in-mesh): 0 = replicated "
                  "update | 1 = 1/n-sharded optimizer state | 2 = "
                  "reduce-scatter grads + sharded update + all-gather params")
define("seed", 1, "global RNG seed (0 = nondeterministic)")
define("log_period", 100, "log every N batches")
define("test_period", 0, "test every N batches (0 = every pass)")
define("saving_period", 1, "checkpoint every N passes")
define("save_dir", "", "checkpoint output directory")
define("init_model_path", "", "checkpoint to warm-start from")
define("start_pass", 0, "first pass number when resuming")
define("show_parameter_stats_period", 0, "dump parameter stats every N batches")
define("enable_grad_share", True, "bucket gradients for all-reduce overlap")
define("dot_period", 1, "print a progress dot every N batches")
define("prev_batch_state", False, "carry RNN state across batches")
define("loadsave_parameters_in_pserver", False, "kept for API compat; no-op on TPU")
define("rdma_tcp", "tcp", "kept for API compat; ICI/DCN is used on TPU")
define("with_timer", False, "enable Stat timers (was: WITH_TIMER build flag)")
define("debug_nans", False, "enable jax nan-checking (was: feenableexcept)")
# OFF by default: the reference computes f32 end to end
# (paddle/math/Matrix.h:79 `real`), so unmodified configs must reproduce
# its numerics.  Opt in via --bf16 / PADDLE_TPU_BF16=1 / flags.set, or —
# preferred — an explicit mixed-precision policy (build_train_step's
# compute_dtype / SGD(compute_dtype=bfloat16)).
define("bf16", False, "force bfloat16 MXU compute for float32 operands")
# telemetry (see paddle_tpu/metrics.py): the structured per-step stream
# and the multihost flight recorder's crash-dump location
define("metrics_jsonl", "", "append one JSON metrics record per train step "
                            "to this file (empty = no JSONL sink)")
define("flight_recorder_dir", "", "directory for flight-recorder crash dumps "
                                  "(empty = <tmpdir>/paddle_tpu_flight)")
define("flight_recorder_size", 256, "step records kept in the flight ring")
# input pipeline & overlapped step loop (reader/prefetch.py, SGD.train)
# 0 (synchronous) by default for the v2 API, matching sync_period=1: an
# unmodified train() call must not move the user's reader onto a worker
# thread behind their back.  The trainer CLI and bench default to the
# overlapped configuration (--prefetch=2 --sync_period=8).
define("prefetch_depth", 0, "device-resident feeds the input pipeline stages "
                            "ahead of the step loop (0 = synchronous feed)")
define("sync_period", 1, "fence device costs every N steps; 1 = exact v2 "
                         "per-batch events, larger defers EndIteration into "
                         "bursts so the host never blocks on the device "
                         "mid-window")
define("batch_remainder", "error", "partial-batch policy for mesh sharding: "
                                   "error | drop | pad (see mesh."
                                   "apply_remainder)")
# fault tolerance (paddle_tpu/resilience/): the numeric guard, the run
# supervisor's restart budget, mid-pass checkpoint cadence, the chaos
# harness and the multihost heartbeat watchdog
define("nan_policy", "none", "non-finite-loss policy: none (die, the v2 "
                             "behavior) | skip (drop the poisoned update) | "
                             "rollback (restore the last checkpoint + "
                             "reduced-LR rescue window)")
define("guard_max_consecutive", 8, "consecutive non-finite batches before "
                                   "the guard gives up (FloatingPointError)")
define("guard_rescue_batches", 8, "batches trained at reduced step size "
                                  "after a rollback")
define("guard_rescue_scale", 0.1, "step-size factor inside the rescue window")
define("max_restarts", 0, "worker faults the trainer-CLI supervisor absorbs "
                          "by restart-and-resume (0 = no supervisor)")
define("checkpoint_batch_period", 0, "also checkpoint every N batches "
                                     "mid-pass (0 = per-pass only); the "
                                     "manifest cursor lets resume replay "
                                     "from the exact batch boundary")
define("checkpoint_keep", 3, "retention GC: keep the newest N checkpoints "
                             "(0 = keep everything); the newest VALID one "
                             "and any pinned mid-export are never deleted")
define("chaos", "", "deterministic fault-injection schedule, e.g. "
                    "'reader_error@3,nan@5,sigterm@7' (see "
                    "resilience/chaos.py; TESTING ONLY)")
define("chaos_seed", 0, "seed for the chaos schedule's injectors")
define("heartbeat_stale_s", 0.0, "multihost watchdog: dump the flight ring "
                                 "and fail fast when this host's train-loop "
                                 "heartbeat goes stale for this many "
                                 "seconds (0 = watchdog off)")
# elastic fleet (resilience/elastic.py): live mesh resharding at batch
# boundaries when membership changes — host loss reshards down from the
# surviving ZeRO shards (cursor-checkpoint fallback when a shard is
# unrecoverable), a scale-up notice reshards up; no process restarts
define("elastic", False, "arm live resharding on host-loss/scale events "
                         "(ElasticCoordinator consumed at batch "
                         "boundaries)")
define("elastic_membership", "", "membership file to watch for elastic "
                                 "events (written by distributed.launch "
                                 "--elastic; empty = the launcher's "
                                 "PADDLE_TPU_MEMBERSHIP env, if set)")
# TPP-style fused microkernels (ops/pallas/tpp): conv+BN+ReLU forward,
# direct-conv BRGEMM, single-pass BN stats, and the fused optimizer-shard
# update.  "auto" routes through the kernels on TPU only — the CPU path
# keeps the reference XLA composition (bit-identical to the unfused
# program), which the bench ablation relies on.
define("fused_kernels", "auto", "route conv/BN/optimizer hot paths through "
                                "the TPP fused Pallas microkernels "
                                "(ops/pallas/tpp): auto = on-TPU only | "
                                "on | off")
# sequence bucketing (reader/decorator.bucket_by_length + DataFeeder
# seq_buckets): one quantization table shared by the bucketed reader and
# the feeder's sequence-slot padding, so every bucket is ONE jit
# signature and padded timesteps stop burning flops/bytes
define("seq_buckets", "", "length-quantization bucket table for sequence "
                          "feeds, e.g. '8,16,32,64' (empty = the default "
                          "doubling table); wire the SAME table into "
                          "bucket_by_length readers")
# static analysis / preflight (paddle_tpu/analysis): the jaxpr/HLO
# program passes run by `trainer --preflight` before any step executes
define("preflight_inject", "", "seed a deterministic defect into the "
                               "preflight program checks to prove they "
                               "fire: host_sync | host_sync_eval | "
                               "collective_mismatch | rank_divergence "
                               "(TESTING ONLY)")
define("hbm_gb", 0.0, "per-device HBM budget for the GL-P-MEM preflight "
                      "check: static params + optimizer slots (under the "
                      "active zero mode) + activation liveness must fit "
                      "(0 = report only, no gate)")
define("vmem_mb", 128.0, "per-kernel VMEM budget for the GL-P-MEM "
                         "preflight check: each pallas_call's static "
                         "block footprint must fit (0 = no gate; v5e "
                         "cores carry 128 MB)")
define("hw_profile", "auto", "hardware profile for the GL-P-COST static "
                             "roofline (peak FLOP/s, HBM and per-link "
                             "ICI bandwidth): v5e | v5p | cpu-testbed | auto "
                             "(resolve from the attached devices)")
define("mfu_floor", 0.0, "minimum predicted MFU%% for the GL-P-COST "
                         "preflight gate: a config whose static roofline "
                         "falls below this fails preflight with a named "
                         "bottleneck (0 = report only, no gate)")
define("preflight_rendezvous", "", "shared directory where preflight "
                                   "ranks exchange program fingerprints "
                                   "(GL-P-DIVERGE); with "
                                   "PADDLE_TPU_NPROC > 1 a rank tracing "
                                   "a different program aborts preflight "
                                   "instead of deadlocking in the first "
                                   "collective")
# live introspection & span tracing (telemetry/tracing.py,
# telemetry/introspect.py): the per-process status server, the span
# ring behind its /trace endpoint, and the --profile_steps windowed
# device capture.  All off by default — tracing disabled is a no-op
# guard (bit-identical trajectory, asserted).
define("status_port", 0, "serve /metrics /healthz /snapshot /trace on "
                         "this port while training/serving (0 = off; "
                         "distributed.launch --status_port_base stamps "
                         "base+rank per process)")
define("trace_spans", False, "record phase spans (trainer step "
                             "feed/compute/fence, the feed pipeline's "
                             "read/convert/place/stage, the serving "
                             "engine step and request lifecycle, "
                             "fleet router, elastic rebuilds) into "
                             "the trace ring served at /trace, and "
                             "mirror them into any jax.profiler trace "
                             "taken meanwhile")
define("trace_ring_size", 8192, "completed spans kept in the trace "
                                "ring (oldest dropped first)")
define("trace_dir", "", "dump this host's span ring as a Chrome trace "
                        "to <trace_dir>/trace-host<k>.json when a "
                        "train() call ends (merge the per-rank files "
                        "with tools/trace_merge.py; empty = no dump)")
define("profile_steps", "", "capture a jax.profiler device trace over "
                            "dispatch steps A:B of the train loop "
                            "(half-open, e.g. '2:4'); arms span "
                            "tracing, whose spans the capture holds "
                            "as host events beside the device "
                            "timeline; emits one 'profile' telemetry "
                            "record")
define("profile_dir", "", "output directory for the --profile_steps "
                          "capture (empty = <tmpdir>/paddle_tpu_"
                          "profile_host<k>)")
define("goodput_ledger", False, "classify every wall-clock second of "
                                "the run into productive compute vs. "
                                "named badput buckets (input_wait, "
                                "fence, recompile, checkpoint, "
                                "guard_rescue, restart, elastic, "
                                "idle), folded from the trace-span "
                                "ring; arms --trace_spans; emits one "
                                "'ledger' record at run end and sets "
                                "the goodput_fraction gauge")
define("ledger_dir", "", "append this run's closing ledger record to "
                         "<ledger_dir>/ledger.jsonl (render with "
                         "tools/goodput_report.py; empty = no file, "
                         "the record still lands in the telemetry "
                         "stream)")

# -- env passthroughs read directly (see declare_env above) --------------------
declare_env("PADDLE_TPU_COORDINATOR",
            "launcher rendezvous: coordinator host:port for "
            "jax.distributed.initialize (distributed/multihost.py)")
declare_env("PADDLE_TPU_NPROC",
            "launcher rendezvous: total participating processes "
            "(distributed.launch sets it per rank)")
declare_env("PADDLE_TPU_TRAINER_ID",
            "launcher rendezvous: this process's rank; also the "
            "telemetry host-index fallback before backend init")
declare_env("PADDLE_TPU_RENDEZVOUS_EPOCH",
            "elastic fleet: membership epoch this process joined under "
            "(distributed.launch --elastic)")
declare_env("PADDLE_TPU_REPLICA_ID",
            "serving replica id stamped per process by "
            "`distributed.launch --serving`")
declare_env("PADDLE_TPU_NREPLICAS",
            "serving fleet size stamped by `distributed.launch "
            "--serving`")
declare_env("PADDLE_TPU_MEMBERSHIP",
            "elastic fleet: membership file the launcher rewrites on "
            "host loss/scale events")
declare_env("JAX_PLATFORMS",
            "externally owned jax backend selector, read by jax at "
            "import (paddle_init --use_cpu sets it before the "
            "interpreter starts)")
declare_env("JAX_COMPILATION_CACHE_DIR",
            "externally owned jax compile-cache directory; when set, "
            "core/compile_cache.configure() sets no path in code, "
            "otherwise the cache lives in <checkout>/.jax_cache")
declare_env("PADDLE_REFERENCE_ROOT",
            "demo runners: checkout of the reference framework for "
            "side-by-side parity runs")
declare_env("PADDLE_TPU_IMDB_SYNTH_N",
            "demo/benchmark: synthetic IMDB corpus size override")
