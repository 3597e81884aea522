"""``python -m paddle_tpu.trainer`` — the classic trainer CLI.

≅ ``paddle/trainer/TrainerMain.cpp:24-61``: ``--config=<file>``,
``--job=train|test|time|checkgrad``, ``--config_args=k=v,...``,
``--num_passes``, ``--init_model_path``, ``--save_dir``.  The config file is
a v1 config (trainer_config_helpers) compiled by
:mod:`paddle_tpu.trainer.config_parser`; training runs the same jitted step
the v2 API uses.

Job modes:

- ``train``: pass loop over the config's PyDataProvider2 data source
  (``define_py_data_sources2``), saving pass checkpoints under --save_dir
  (≅ Trainer::train, ParamUtil).
- ``test``: forward over the test source, printing cost + evaluators
  (≅ Trainer::test / Tester.cpp).
- ``time``: ``--job=time`` benchmark of the train step
  (≅ TrainerBenchmark.cpp): ms/batch on the device's own clock and the
  step's split by part (layer type, cost, update; forward | backward),
  or the two-point method where no device trace is to be had.
- ``checkgrad``: finite-difference vs ``jax.grad`` on every parameter
  (≅ Trainer::checkGradient, Trainer.cpp:332); exits nonzero on mismatch.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

import numpy as np


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.trainer",
        description="paddle_tpu trainer (TrainerMain analog)",
    )
    p.add_argument("--config", required=True, help="v1 config file")
    p.add_argument("--job", default="train",
                   choices=["train", "test", "time", "checkgrad"])
    p.add_argument("--preflight", action="store_true",
                   help="build the configured train AND eval steps and "
                        "run the static program checks (paddle_tpu/"
                        "analysis: host-sync points, un-donated update "
                        "buffers, bf16 upcasts, per-device memory vs "
                        "--hbm_gb / --vmem_mb budgets, sharding-flow "
                        "audit, RNG fold-in discipline, ZeRO collective-"
                        "lowering mismatch, cross-rank program-"
                        "fingerprint divergence under --preflight_"
                        "rendezvous) instead of training; exit 1 on any "
                        "unsuppressed finding — the config_parser-style "
                        "reject-before-running gate.  --hbm_gb, "
                        "--vmem_mb and --preflight_rendezvous are "
                        "registry flags (PADDLE_TPU_* overridable)")
    p.add_argument("--config_args", default="",
                   help="var=val,... exposed via get_config_arg")
    p.add_argument("--num_passes", type=int, default=1)
    p.add_argument("--save_dir", default=None)
    p.add_argument("--init_model_path", default=None)
    p.add_argument("--log_period", type=int, default=100)
    p.add_argument("--test_period", type=int, default=0,
                   help="accepted for v1 compat")
    p.add_argument("--trainer_count", type=int, default=1,
                   help="data-parallel shards (mesh 'data' axis)")
    p.add_argument("--use_gpu", default=None, help="accepted for v1 compat")
    p.add_argument("--dot_period", type=int, default=1,
                   help="accepted for v1 compat")
    p.add_argument("--saving_period", type=int, default=1,
                   help="save a pass checkpoint every N passes")
    # input pipeline / overlap (see README "Input pipeline & overlap"):
    # unlike the v2 API (whose flag defaults prefetch_depth=0 /
    # sync_period=1 keep exact v2 semantics), the CLI defaults to the
    # overlapped configuration — operators get the win out of the box, at
    # the cost of burst-delivered EndIteration log lines.  Resolution
    # order (cmd_train): explicit CLI arg > PADDLE_TPU_* flag override >
    # CLI default (2 / 8).
    p.add_argument("--prefetch", type=int, default=None,
                   help="device feeds staged ahead of the step loop "
                        "(0 = synchronous input; default 2)")
    p.add_argument("--sync_period", type=int, default=None,
                   help="fence device costs every N steps (1 = per-batch "
                        "v2 event cadence; default 8)")
    p.add_argument("--seq_buckets", default=None,
                   help="comma-separated length-bucket table (e.g. "
                        "'8,16,32,64'): batch the training reader by "
                        "quantized sequence length and pad feeds only to "
                        "each bucket's ceiling — padded timesteps stop "
                        "burning recurrent flops (empty = off)")
    # weight-update sharding (README "Weight-update sharding (ZeRO-1/2)"):
    # the pserver's sharded aggregation re-expressed in-mesh
    p.add_argument("--zero", type=int, default=None, choices=[0, 1, 2],
                   help="ZeRO weight-update sharding over the mesh data "
                        "axis: 0 = replicated update (default) | 1 = "
                        "1/n-sharded optimizer state | 2 = reduce-scatter "
                        "grads + sharded update + all-gather params")
    # fault tolerance (README "Fault tolerance & recovery"): crash-safe
    # cursor checkpoints, the numeric guard, the restart-budget
    # supervisor and the deterministic chaos harness
    p.add_argument("--checkpoint_dir", default=None,
                   help="crash-safe checkpoint directory (params + "
                        "optimizer + states + (pass,batch) cursor); "
                        "resume is automatic")
    p.add_argument("--checkpoint_period", type=int, default=1,
                   help="full checkpoint every N passes")
    p.add_argument("--checkpoint_batch_period", type=int, default=None,
                   help="also checkpoint every N batches mid-pass "
                        "(0 = per-pass only)")
    p.add_argument("--checkpoint_keep", type=int, default=None,
                   help="retention GC: keep the newest N checkpoints "
                        "(0 = keep everything); the newest valid one and "
                        "any pinned mid-export are never deleted")
    p.add_argument("--nan_policy", default=None,
                   choices=["none", "skip", "rollback"],
                   help="non-finite-loss policy: none (die) | skip "
                        "(drop the poisoned update) | rollback (restore "
                        "the last checkpoint + reduced-LR rescue window)")
    p.add_argument("--max_restarts", type=int, default=None,
                   help="worker faults absorbed by restart-and-resume "
                        "(0 = die on the first fault); needs "
                        "--checkpoint_dir to resume rather than rewind")
    p.add_argument("--chaos", default=None,
                   help="deterministic fault-injection schedule, e.g. "
                        "'reader_error@3,nan@5,sigterm@7,host_loss@9:dp=4'"
                        "; serving-fleet kinds (replica_loss/replica_"
                        "hang@k:replica=i, servable_corrupt@k) arm via "
                        "FleetRouter(chaos=...) — TESTING ONLY (see "
                        "resilience/chaos.py)")
    p.add_argument("--elastic", action="store_true", default=None,
                   help="arm live resharding on host-loss/scale events: "
                        "membership changes rebuild the mesh at the new "
                        "data-parallel degree at a batch boundary "
                        "instead of killing the run (resilience/"
                        "elastic.py)")
    p.add_argument("--elastic_membership", default=None,
                   help="membership file to watch for elastic events "
                        "(default: the launcher's PADDLE_TPU_MEMBERSHIP "
                        "env when --elastic is set)")
    p.add_argument("--seq_dim", type=int, default=8,
                   help="timesteps per synthetic sequence for --job=time/"
                        "checkgrad feeds (the reference RNN benchmark pads "
                        "to 100, benchmark/paddle/rnn/rnn.py:8)")
    # checkgrad knobs (Trainer.cpp:332 checkgrad_eps analog)
    p.add_argument("--checkgrad_eps", type=float, default=1e-3,
                   help="tolerance scale for the gradient check")
    p.add_argument("--checkgrad_samples", type=int, default=6,
                   help="random entries probed per parameter")
    return p


def _provider_args(rec: dict) -> dict:
    """define_py_data_sources2 args=... -> init_hook kwargs (dict or
    'k=v,...' string form)."""
    args = rec.get("args") or {}
    if isinstance(args, str):
        args = dict(f.split("=", 1) for f in args.split(",") if "=" in f)
    return args


def _raw_reader_from_data_config(rec: dict, topo, input_order):
    """DataConfig -> (unbatched reader, provider-ish object).

    Dispatches on the config's data source type: PyDataProvider2 modules
    ("py"/define_py_data_sources2), binary DataFormat.proto files
    ("proto", ProtoDataProvider), or several sub-sources zipped into one
    sample stream ("multi", MultiDataProvider.h:24)."""
    from paddle_tpu.reader.py_data_provider2 import read_file_list

    kind = rec.get("type")
    if kind == "proto":
        from paddle_tpu.reader import proto_data

        files = read_file_list(rec["files"])
        types = proto_data.input_types_from_header(files[0])
        # row shape must match the header-derived types dataset-wide
        sequential = any(t.seq_type != 0 for t in types)
        reader = proto_data.proto_reader(
            files, sequential=sequential,
            usage_ratio=rec.get("usage_ratio"))

        class _ProtoObj:  # reader metadata the batching code consults
            should_shuffle = True
            calc_batch_size = None
            input_types = types

        if topo is not None:
            _apply_provider_types(topo, _ProtoObj, input_order)
        return reader, _ProtoObj
    if kind == "multi":
        from paddle_tpu.reader import proto_data

        subs = [_raw_reader_from_data_config(sub, None, None)
                for sub in rec["sub"]]
        reader = proto_data.multi_reader([r for r, _ in subs])
        # merge type declarations preserving names where present: a dict
        # binds by layer name, so mixing forms positionally would scramble
        # layers — flatten dicts ONLY when every sub uses the list form
        if any(isinstance(getattr(o, "input_types", None), dict)
               for _, o in subs):
            types = {}
            for _, o in subs:
                sub_types = getattr(o, "input_types", None) or {}
                enforce_dict = isinstance(sub_types, dict)
                if not enforce_dict:
                    raise ValueError(
                        "MultiData: mixing dict-typed and list-typed "
                        "sub-providers is ambiguous; declare all "
                        "input_types as {layer: type} dicts")
                types.update(sub_types)
        else:
            types = []
            for _, o in subs:
                types.extend(getattr(o, "input_types", None) or [])

        class _MultiObj:
            should_shuffle = True
            calc_batch_size = None
            input_types = types

        if topo is not None and types:
            _apply_provider_types(topo, _MultiObj, input_order)
        return reader, _MultiObj

    mod = importlib.import_module(rec["module"])
    obj = getattr(mod, rec["obj"])
    files = read_file_list(rec["files"])
    # config-supplied provider kwargs (define_py_data_sources2 args=...)
    # reach the init_hook; types may be declared there rather than in the
    # decorator, so bind them AFTER make_reader ran the hook
    reader = obj.make_reader(files, **_provider_args(rec))
    if topo is not None:
        _apply_provider_types(topo, obj, input_order)
    return reader, obj


def _reader_from_data_config(rec: dict, batch_size: int, shuffle: bool,
                             topo=None, input_order=None,
                             drop_last: bool | None = None,
                             seq_buckets=None):
    """DataConfig(py2) -> batched paddle reader via the provider module.
    The provider's declared ``input_types`` override the data layers' dense
    placeholders (reference: types live in the provider, not the config).
    ``seq_buckets`` (a table from ``--seq_buckets``) batches by quantized
    length instead of arrival order, so padded timesteps stop burning
    flops in the recurrent sweeps."""
    import paddle_tpu as paddle

    reader, obj = _raw_reader_from_data_config(rec, topo, input_order)
    if shuffle and getattr(obj, "should_shuffle", True) is not False:
        reader = paddle.reader.shuffle(reader, buf_size=4096)
    if seq_buckets:
        from paddle_tpu.parallel.mesh import get_mesh
        from paddle_tpu.reader.decorator import bucket_by_length

        # remainder="pad": leftover pools fill to the FULL batch size, so
        # every bucket stays ONE jit signature — the same recompile
        # discipline the drop_last rule below applies to plain batching
        # (a "drop"-trimmed tail would mint a fresh (batch, time) shape
        # every pass under shuffle)
        return bucket_by_length(
            reader, batch_size, buckets=seq_buckets, remainder="pad",
            size_multiple=get_mesh().num_replicas)
    if drop_last is None:
        # train (shuffle=True): tail flushes would emit non-pinned batch
        # sizes and recompile every pass (shuffle reorders the tail).
        # test: metrics must cover every sample, so flush tails — the tail
        # shapes are deterministic so at most one extra compile per shape.
        drop_last = shuffle
    calc = getattr(obj, "calc_batch_size", None)
    if calc is not None:
        # PyDataProvider2 dynamic-batch semantics: cost-balanced batches
        # per length bucket (one static shape each), trimmed to the mesh
        # replica count for sharding divisibility
        from paddle_tpu.parallel.mesh import get_mesh
        from paddle_tpu.reader.decorator import bucket_batch

        return bucket_batch(reader, batch_size, calc_batch_size=calc,
                            size_multiple=get_mesh().num_replicas,
                            drop_last=drop_last)
    batched = paddle.reader.batch(reader, batch_size=batch_size,
                                  drop_last=drop_last)
    if drop_last:
        return batched
    # tail batches must still divide the mesh data axis (shard_batch
    # enforces batch % replicas == 0); trim like bucket_batch does
    from paddle_tpu.parallel.mesh import get_mesh

    m = get_mesh().num_replicas

    def trimmed():
        dropped = 0
        for b in batched():
            if len(b) == batch_size:
                # full batches pass through: a batch_size that doesn't
                # divide the mesh is a config error shard_batch reports
                yield b
                continue
            n = (len(b) // m) * m
            dropped += len(b) - n
            if n:
                yield b[:n]
        if dropped:
            from paddle_tpu.core import logger as log

            log.info("test reader: dropped %d tail samples not divisible "
                     "by the %d-replica mesh", dropped, m)

    return trimmed if m > 1 else batched


def _add_config_dir_to_path(config_path: str) -> None:
    d = os.path.dirname(os.path.abspath(config_path))
    if d not in sys.path:
        sys.path.insert(0, d)


def _apply_provider_types(topo, obj, input_order):
    """Bind the provider's declared input_types onto the data layers (the
    reference keeps types in the provider, not the config).  Accepts both
    the dict form ({layer: type}) and the positional list form (matched to
    the config's input order)."""
    types = getattr(obj, "input_types", None)
    if types is None:
        return
    if isinstance(types, dict):
        items = types.items()
    else:
        order = input_order or list(topo.data_layers())
        items = zip(order, types)
    for lname, itype in items:
        node = topo.data_layers().get(lname)
        if node is not None:
            node.attrs.update(data_type=itype.kind,
                              seq_type=itype.seq_type, dim=itype.dim)


def _load_provider_types(args, parsed, topo):
    """For jobs that never build a reader (time/checkgrad): still bind the
    provider's input_types so synthetic feeds have the right kinds."""
    from paddle_tpu.config import parse_state

    rec = parse_state.STATE.data_config or parse_state.STATE.test_data_config
    if not rec:
        return
    if rec.get("type") in ("proto", "multi"):
        # header-derived types (no provider module to import)
        try:
            _raw_reader_from_data_config(rec, topo, parsed.input_layer_names)
        except Exception as e:
            from paddle_tpu.core import logger as log

            log.debug("proto/multi data files unavailable (%s); dense "
                      "placeholders stand", e)
        return
    if not rec.get("module"):
        return
    _add_config_dir_to_path(args.config)
    try:
        mod = importlib.import_module(rec["module"])
        obj = getattr(mod, rec["obj"])
    except Exception as e:
        from paddle_tpu.core import logger as log

        log.debug("data provider %s unavailable (%s); dense placeholders "
                  "stand", rec.get("module"), e)
        return
    if getattr(obj, "input_types", None) is None:
        # init_hook providers declare types on ``settings`` at reader
        # construction (benchmark/paddle/image/provider.py pattern); run
        # the hook over an empty file list just to harvest them
        try:
            obj.make_reader([], **_provider_args(rec))
        except Exception as e:
            from paddle_tpu.core import logger as log

            log.warning(
                "provider init_hook type harvest failed (%s); synthetic "
                "feeds fall back to dense placeholders — --job=time may "
                "benchmark a different input topology", e)
    _apply_provider_types(topo, obj, parsed.input_layer_names)


def _build(parsed):
    """ParsedConfig -> (topology, optimizer, data_types, feeding)."""
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.trainer_config_helpers.optimizers import (
        get_settings_optimizer,
    )

    # evaluator inputs may name layers off the cost path (the reference's
    # GradientMachine computes every configured layer, so evaluators can
    # tap any of them) — keep those alive as extra topology roots
    from paddle_tpu.layers.base import layer_registry

    ev_names = {n for s in (getattr(parsed, "evaluators", None) or [])
                for n in s.input_layers}
    from paddle_tpu.layers.base import companion_name

    ev_names |= {companion_name(n) for n in set(ev_names)}
    extra = [lo for lo in layer_registry() if lo.name in ev_names]
    topo = Topology(parsed.output_layers(), extra_layers=extra)
    opt = get_settings_optimizer()
    from paddle_tpu.layers.data_type import InputType

    data_layers = topo.data_layers()
    order = [n for n in parsed.input_layer_names if n in data_layers]
    if not order:
        order = list(data_layers)
    # data layers reached only via evaluator extra roots still need a feed
    # slot (the provider yields fields for every configured data layer)
    order += [n for n in data_layers if n not in order]
    types = [
        (n, InputType(data_layers[n].attrs.get("dim", data_layers[n].size),
                      data_layers[n].attrs.get("seq_type", 0),
                      data_layers[n].attrs.get("data_type", "dense")))
        for n in order
    ]
    feeding = {n: i for i, (n, _) in enumerate(types)}
    return topo, opt, types, feeding


def cmd_preflight(args, parsed) -> int:
    """--preflight: static program checks over the step cmd_train would
    run — the config_parser-style validation gate, but over the
    compiled program instead of the config text."""
    import jax.numpy as jnp

    from paddle_tpu.analysis.preflight import run_preflight
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.parallel.mesh import get_mesh

    topo, opt, types, feeding = _build(parsed)
    _load_provider_types(args, parsed, topo)
    mesh = get_mesh()
    dp = mesh.mesh.shape.get("data", 1)
    batch_size = parsed.opt_config.batch_size or 32
    if batch_size % dp:  # the probe batch must shard like a real batch
        batch_size += dp - batch_size % dp
    feed = _synthetic_feed(topo, batch_size, seq_dim=args.seq_dim)
    zero = args.zero if args.zero is not None else _flags.get("zero")
    compute_dtype = jnp.bfloat16 if _flags.get("bf16") else None
    sync_period = args.sync_period if args.sync_period is not None \
        else _flags.get("sync_period")
    # fleet identity comes from the launcher's rendezvous env (the same
    # vars distributed.launch stamps per rank); with a rendezvous dir
    # and nproc > 1 the GL-P-DIVERGE fingerprint exchange is armed
    rank = int(os.environ.get("PADDLE_TPU_TRAINER_ID", "0"))
    nproc = int(os.environ.get("PADDLE_TPU_NPROC", "1"))
    epoch = int(os.environ.get("PADDLE_TPU_RENDEZVOUS_EPOCH", "0"))
    cost: dict = {}
    unsup, sup = run_preflight(
        topo, opt, feed, mesh, zero=zero, compute_dtype=compute_dtype,
        sync_period=sync_period, inject=_flags.get("preflight_inject"),
        config=os.path.basename(args.config),
        hbm_gb=_flags.get("hbm_gb"), vmem_mb=_flags.get("vmem_mb"),
        hw_profile=_flags.get("hw_profile"),
        mfu_floor=_flags.get("mfu_floor"),
        rendezvous_dir=_flags.get("preflight_rendezvous"),
        rank=rank, nproc=nproc, rendezvous_epoch=epoch, cost_out=cost)
    for f in unsup:
        print(f.render())
    if sup:
        print(f"({len(sup)} finding(s) suppressed by baseline)")
    if cost:
        print(f"preflight cost [{cost.get('profile')}]: predicted step "
              f"{cost.get('step_ms', 0.0):.2f} ms, MFU "
              f"{cost.get('mfu_pct', 0.0):.1f}%, bottleneck "
              f"{cost.get('bottleneck', '?')}")
    if unsup:
        print(f"preflight: {len(unsup)} unsuppressed finding(s) — "
              f"fix the program or baseline them with a reason")
        return 1
    budget = (f", {float(_flags.get('hbm_gb')):.1f} GB budget"
              if _flags.get("hbm_gb") else "")
    print(f"preflight: OK — {args.config} (zero={zero}, data={dp}"
          f"{budget})")
    return 0


def cmd_train(args, parsed) -> int:
    import paddle_tpu as paddle

    topo, opt, types, feeding = _build(parsed)
    batch_size = parsed.opt_config.batch_size or 32
    rec = __import__("paddle_tpu.config.parse_state", fromlist=["STATE"])
    data_rec = rec.STATE.data_config
    if data_rec is None:
        print("config defines no data source (define_py_data_sources2)",
              file=sys.stderr)
        return 2
    _add_config_dir_to_path(args.config)
    from paddle_tpu.core import flags as _bflags
    from paddle_tpu.reader.feeder import parse_seq_buckets

    seq_buckets = parse_seq_buckets(
        args.seq_buckets if args.seq_buckets is not None
        else _bflags.get("seq_buckets"))
    reader = _reader_from_data_config(data_rec, batch_size, shuffle=True,
                                      topo=topo,
                                      input_order=parsed.input_layer_names,
                                      seq_buckets=seq_buckets)

    params = paddle.parameters.create(topo)
    if args.init_model_path:
        with open(args.init_model_path, "rb") as f:
            params = paddle.parameters.Parameters.from_tar(f)

    from paddle_tpu.core import flags as _zflags

    trainer = paddle.trainer.SGD(
        cost=topo.outputs, parameters=params, update_equation=opt,
        extra_layers=topo.extra_layers,
        declared_evaluators=getattr(parsed, "evaluators", None),
        zero=(args.zero if args.zero is not None
              else _zflags.get("zero")))

    def on_event(event):
        if isinstance(event, paddle.event.EndIteration):
            if event.batch_id % args.log_period == 0:
                print(f"Pass {event.pass_id}, Batch {event.batch_id}, "
                      f"Cost {event.cost:.6f}, {event.metrics}")
        elif isinstance(event, paddle.event.EndPass):
            if event.metrics:
                # ≅ the reference's "Eval: name=value" pass summary line
                evals = " ".join(f"{k}={v:.6g}" if isinstance(v, float)
                                 else f"{k}={v}"
                                 for k, v in event.metrics.items())
                print(f"Pass {event.pass_id} Eval: {evals}")
            due = (event.pass_id % args.saving_period == args.saving_period - 1
                   or event.pass_id == args.num_passes - 1)
            if args.save_dir and due:
                os.makedirs(args.save_dir, exist_ok=True)
                path = os.path.join(
                    args.save_dir, f"pass-{event.pass_id:05d}.tar")
                with open(path, "wb") as f:
                    trainer.save_parameter_to_tar(f)
                print(f"saved {path}")

    from paddle_tpu.core import flags as _flags

    def _resolve(arg_val, flag_name, cli_default):
        if arg_val is not None:  # explicit CLI arg wins
            return arg_val
        if _flags.is_set(flag_name):  # then an operator's env/flag override
            return _flags.get(flag_name)
        return cli_default

    # deterministic chaos harness (TESTING ONLY): one schedule object for
    # the whole run, so once-faults stay fired across supervisor restarts
    chaos_spec = _resolve(args.chaos, "chaos", "")
    handler, train_reader, schedule = on_event, reader, None
    if chaos_spec:
        from paddle_tpu.resilience.chaos import ChaosSchedule

        schedule = ChaosSchedule(chaos_spec,
                                 seed=_flags.get("chaos_seed"))
        handler = schedule.wrap_event_handler(on_event)
        train_reader = schedule.wrap_reader(reader)

    # elastic fleet: membership events rebuild the mesh live at batch
    # boundaries (resilience/elastic.py); host-loss/scale-up chaos
    # faults and the launcher's membership file both feed the queue
    elastic = None
    if _resolve(args.elastic, "elastic", False):
        from paddle_tpu.resilience.elastic import ElasticCoordinator

        elastic = ElasticCoordinator(checkpoint_dir=args.checkpoint_dir)
        membership = _resolve(args.elastic_membership,
                              "elastic_membership",
                              os.environ.get("PADDLE_TPU_MEMBERSHIP", ""))
        if membership:
            # baseline = the fleet this rank JOINED: a peer that died
            # before our first file read must still read as a loss
            from paddle_tpu.distributed import multihost as _mh

            elastic.seed_membership(
                _mh.rendezvous_epoch(),
                int(os.environ.get("PADDLE_TPU_NPROC", "1")))
            elastic.watch_membership(membership)
            elastic.arm_signal(membership)
        if schedule is not None:
            schedule.bind_elastic(elastic)

    def run_train():
        if schedule is not None:
            # per-attempt index re-base: fault positions stay aligned
            # with the attempt's own batch/step stream across restarts
            # (fired-state persists, so once-faults still fire once;
            # ':always' faults re-fire at the same per-attempt spot)
            schedule.reset_counters()
        trainer.train(
            reader=train_reader, num_passes=args.num_passes,
            event_handler=handler, feeding=feeding,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_period=args.checkpoint_period,
            checkpoint_batch_period=_resolve(
                args.checkpoint_batch_period, "checkpoint_batch_period", 0),
            checkpoint_keep=_resolve(
                args.checkpoint_keep, "checkpoint_keep", 3),
            nan_policy=_resolve(args.nan_policy, "nan_policy", "none"),
            sync_period=_resolve(args.sync_period, "sync_period", 8),
            prefetch=_resolve(args.prefetch, "prefetch_depth", 2),
            elastic=elastic, seq_buckets=seq_buckets)

    max_restarts = _resolve(args.max_restarts, "max_restarts", 0)
    try:
        if max_restarts > 0:
            # the run supervisor: worker faults restart the loop; each
            # retry resumes from the newest valid checkpoint's
            # (pass, batch) cursor — and drops any queued elastic event
            # the restored state already reflects
            from paddle_tpu.resilience.supervisor import Supervisor

            Supervisor(max_restarts=max_restarts,
                       elastic=elastic).run(run_train)
        else:
            run_train()
    finally:
        if elastic is not None:
            elastic.stop()
    return 0


def cmd_test(args, parsed) -> int:
    import paddle_tpu as paddle

    topo, opt, types, feeding = _build(parsed)
    batch_size = parsed.opt_config.batch_size or 32
    from paddle_tpu.config import parse_state

    rec = parse_state.STATE.test_data_config or parse_state.STATE.data_config
    if rec is None:
        print("config defines no test data source", file=sys.stderr)
        return 2
    _add_config_dir_to_path(args.config)
    reader = _reader_from_data_config(rec, batch_size, shuffle=False,
                                      topo=topo,
                                      input_order=parsed.input_layer_names)

    params = paddle.parameters.create(topo)
    if args.init_model_path:
        with open(args.init_model_path, "rb") as f:
            params = paddle.parameters.Parameters.from_tar(f)
    trainer = paddle.trainer.SGD(
        cost=topo.outputs, parameters=params, update_equation=opt,
        extra_layers=topo.extra_layers,
        declared_evaluators=getattr(parsed, "evaluators", None))
    result = trainer.test(reader=reader, feeding=feeding)
    print(f"Test cost {result.cost:.6f}, {result.metrics}")
    return 0


def cmd_time(args, parsed) -> int:
    """--job=time: benchmark one jitted train step on synthetic data."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.trainer.step import build_train_step

    topo, opt, types, feeding = _build(parsed)
    _load_provider_types(args, parsed, topo)
    batch_size = parsed.opt_config.batch_size or 32
    specs = {s.name: s for s in topo.param_specs()}
    params = paddle.parameters.create(topo).as_dict()
    opt_state = opt.init(params, specs)
    states = topo.init_states()
    step = build_train_step(topo, opt)
    feed = _synthetic_feed(topo, batch_size, seq_dim=args.seq_dim)
    key = jax.random.key(0)

    def one(params, opt_state, states):
        p, o, s, c, _ = step(params, opt_state, states, feed, key)
        return c

    # device-side timing where a profiler trace is available (host
    # dispatch gaps dominate wall-clock timing of sub-10 ms steps); fall
    # back to the two-point benchmark
    carry = {"s": (params, opt_state, states)}

    def stateful():
        p, o, s, c, _ = step(*carry["s"], feed, key)
        carry["s"] = (p, o, s)
        return c

    def _deleted(x):
        try:
            return x.is_deleted()
        except (AttributeError, TypeError):  # plain numpy leaf
            return False

    def wall():
        # the donating step consumes its inputs, so if it raised MID-call
        # during the device-timing attempt, carry["s"] references deleted
        # buffers and the retry would die on an unrelated deleted-buffer
        # error — the state is synthetic, so rebuild it
        if any(_deleted(leaf) for leaf in jax.tree.leaves(carry["s"])):
            p2 = paddle.parameters.create(topo).as_dict()
            carry["s"] = (p2, opt.init(p2, specs), topo.init_states())
        res = profiler.benchmark(one, carry["s"],
                                 name=os.path.basename(args.config))
        return res.seconds_per_step * 1000.0

    parts: list = []    # the device step's split by sublayer
    ms, how, why = profiler.step_ms_with_fallback(stateful, wall, parts=parts)
    if why:
        from paddle_tpu.core import logger as log

        log.warning("--job=time device timing unavailable (%s); "
                    "wall-clock two-point used", why)
    # the benchmark result joins the structured metrics stream (kind
    # "bench"; JSONL sink via --metrics_jsonl)
    from paddle_tpu import metrics as metrics_mod

    reg = metrics_mod.get_registry()
    if reg.active:
        reg.emit({
            "metric": "trainer_time_ms_per_batch",
            "value": round(ms, 3), "unit": "ms", "run": "time",
            "config": os.path.basename(args.config),
            "batch_size": batch_size, "timing": how,
        }, kind="bench")
    print(f"TrainerBenchmark {args.config}: {ms:.3f} ms/batch "
          f"(batch_size={batch_size}, {how})")
    if parts:
        # which layer types, the cost and the update the device's time
        # went to (telemetry/scopes.py), forward | backward
        print(profiler.format_parts(parts))
    return 0


def _synthetic_feed(topo, batch_size: int, seq_dim: int = 8):
    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.layers.data_type import DataKind, SeqType

    rng = np.random.default_rng(0)
    feed = {}
    for name, node in topo.data_layers().items():
        t = node.attrs
        kind, seq = t.get("data_type"), t.get("seq_type")
        dim = t.get("dim", node.size)
        if kind == DataKind.INTEGER:
            data = rng.integers(0, dim, size=(batch_size,))
        else:
            data = rng.normal(size=(batch_size, dim)).astype(np.float32)
        if seq and seq != SeqType.NO_SEQUENCE:
            tdim = seq_dim
            if kind == DataKind.INTEGER:
                data = rng.integers(0, dim, size=(batch_size, tdim))
            else:
                data = rng.normal(size=(batch_size, tdim, dim)).astype(
                    np.float32)
            feed[name] = SequenceBatch(
                data=data, length=np.full((batch_size,), tdim, np.int32))
        else:
            feed[name] = data
    return feed


def cmd_checkgrad(args, parsed) -> int:
    """Finite differences vs jax.grad on every parameter
    (≅ Trainer::checkGradient, Trainer.cpp:332)."""
    import jax

    # finite differences need more mantissa than the training dtype; the
    # globals are restored before returning (cli.main may be called
    # in-process).  A user-set --bf16 is also suspended: central
    # differences with eps=1e-3 on a bf16-rounded function would fail
    # every parameter spuriously.
    from paddle_tpu.core import flags as _flags

    prev_x64 = jax.config.jax_enable_x64
    prev_prec = jax.config.jax_default_matmul_precision
    prev_bf16 = _flags.get("bf16")
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_default_matmul_precision", "highest")
    _flags.set("bf16", False)
    import jax.numpy as jnp

    import paddle_tpu as paddle

    topo, opt, types, feeding = _build(parsed)
    _load_provider_types(args, parsed, topo)
    batch_size = min(parsed.opt_config.batch_size or 8, 8)
    params = {
        k: jnp.asarray(np.asarray(v), jnp.float64)
        for k, v in paddle.parameters.create(topo).as_dict().items()
    }
    states = {k: jnp.asarray(np.asarray(v), jnp.float64)
              for k, v in topo.init_states().items()}
    feed = _synthetic_feed(topo, batch_size, seq_dim=args.seq_dim)
    key = jax.random.key(0)

    @jax.jit
    def loss_fn(p):
        values, _ = topo.forward(p, states, feed, True, key)
        total = 0.0
        for out in topo.outputs:
            v = values[out.name]
            v = v.data if hasattr(v, "data") else v
            total = total + jnp.sum(v)
        return total

    from jax.test_util import check_grads

    failures = []
    for name, value in params.items():
        def one_param(v, name=name):
            return loss_fn({**params, name: v})

        try:
            # reverse-mode vs numerical jacobian along random directions
            # (jax's own methodology; ≅ Trainer::checkGradient's
            # whole-parameter perturbation, Trainer.cpp:332)
            check_grads(one_param, (value,), order=1, modes=("rev",),
                        atol=args.checkgrad_eps * 10,
                        rtol=args.checkgrad_eps * 10)
            print(f"checkgrad {name}: ok")
        except AssertionError as e:
            failures.append((name, str(e).splitlines()[0][:120]))
            print(f"checkgrad {name}: FAIL")
    jax.config.update("jax_enable_x64", prev_x64)
    jax.config.update("jax_default_matmul_precision", prev_prec)
    _flags.set("bf16", prev_bf16)
    if failures:
        for name, msg in failures[:10]:
            print(f"  MISMATCH {name}: {msg}", file=sys.stderr)
        return 1
    print(f"checkgrad PASSED over {len(params)} parameters")
    return 0


def main(argv=None) -> int:
    # args argparse doesn't know go to the gflags registry (TrainerMain
    # passes unparsed argv into gflags the same way) — e.g. --bf16,
    # --with_timer, --debug_nans
    args, extra = build_argparser().parse_known_args(argv)
    changed: dict = {}
    if extra:
        from paddle_tpu.core import flags as _flags

        before = _flags.snapshot_raw()
        leftover = _flags.parse_args(extra)
        # cli.main may be called in-process (demo runners, tests):
        # restore exactly the flags THIS call changed, on every exit
        # path — as RAW override values, so restoring a default doesn't
        # leave the flag marked explicitly-set (flags.is_set)
        after = _flags.snapshot_raw()
        changed = {k: before[k] for k in before if after[k] != before[k]}
        if leftover:
            _flags.restore_raw(changed)
            build_argparser().error(
                f"unrecognized arguments: {' '.join(leftover)}")
    from paddle_tpu.trainer.config_parser import parse_config

    # --metrics_jsonl=PATH (a registry flag, not argparse): attach the
    # JSONL sink so every job mode emits through the telemetry stream
    from paddle_tpu import metrics as _metrics

    _metrics.configure_from_flags()
    try:
        parsed = parse_config(args.config, args.config_args)
        if args.preflight:
            return cmd_preflight(args, parsed)
        jobs = {
            "train": cmd_train,
            "test": cmd_test,
            "time": cmd_time,
            "checkgrad": cmd_checkgrad,
        }
        return jobs[args.job](args, parsed)
    finally:
        if changed:
            from paddle_tpu.core import flags as _flags

            _flags.restore_raw(changed)


if __name__ == "__main__":
    sys.exit(main())
