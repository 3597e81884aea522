"""``python -m paddle_tpu.serving`` — a stdin request loop over the
serving engine (the dependency-free stand-in for an HTTP front-end; the
same ``submit()/results()`` surface a real server would wrap).

One request per line: whitespace-separated token ids, e.g.::

    echo "5 17 3" | python -m paddle_tpu.serving --random --max_new_tokens 8

Each completed request prints ``<id>: <generated ids>``.  With
``--servable DIR`` the engine loads an exported artifact
(``serving/export.py``); ``--random`` serves seeded random weights (smoke
tests / latency rehearsal).  ``--metrics_jsonl PATH`` streams the
per-request records + the final serve_summary for
``tools/metrics_to_md.py``.  ``--replicas N`` serves through a local
fleet (``serving/fleet.py``): N replica engines behind the FleetRouter,
same loop, same output.  Under ``distributed.launch --serving`` each
process announces its ``PADDLE_TPU_REPLICA_ID`` on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving",
        description="paddle_tpu online serving CLI loop")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--servable", help="exported servable directory")
    src.add_argument("--random", action="store_true",
                     help="serve seeded random weights (smoke testing)")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--embed", type=int, default=64)
    p.add_argument("--model_json", default="{}",
                   help="with --random: further TransformerConfig fields "
                        "as JSON, e.g. '{\"norm\": \"rms\", \"positions\": "
                        "\"rotary\", \"mlp\": \"swiglu\", \"head_dim\": 48, "
                        "\"loop_steps\": 3}' (a servable carries its own)")
    p.add_argument("--max_new_tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--page_size", type=int, default=16)
    p.add_argument("--num_pages", type=int, default=64)
    p.add_argument("--max_prompt_len", type=int, default=32,
                   help="longest prompt accepted, and the length of the "
                        "longest prefill pass; the engine compiles its "
                        "prefill ladder (two programs: one row and "
                        "prefill_batch rows at this length, or, from "
                        "2048 on, one row at half of it and one row at "
                        "this length) and the decode program when its "
                        "first request arrives, before serving it, not "
                        "when traffic first needs each")
    p.add_argument("--prefix_cache", action="store_true",
                   help="share full KV pages across requests with a "
                        "common prompt prefix (copy-on-write, LRU "
                        "eviction under page pressure); greedy tokens "
                        "are identical on/off")
    p.add_argument("--prefill_chunk_tokens", type=int, default=0,
                   help="split long-prompt prefill into chunks of this "
                        "many tokens interleaved with decode steps "
                        "(0 = whole-prompt prefill, today's behavior)")
    p.add_argument("--denoise_steps", type=int, default=2,
                   help="a model that generates by blocks (--model_json "
                        "block_len > 1): denoising passes a block gets "
                        "before the pass that commits it")
    p.add_argument("--unmask_policy", default="low_confidence_static",
                   choices=("low_confidence_static", "sequential"),
                   help="which masked positions a denoising pass unmasks")
    p.add_argument("--metrics_jsonl", default=None)
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a local fleet of N replica "
                        "engines behind the FleetRouter (default: one "
                        "bare engine)")
    p.add_argument("--status_port", type=int, default=None,
                   help="serve /metrics /healthz /snapshot /trace on "
                        "this port while the loop runs (default: the "
                        "status_port flag / PADDLE_TPU_STATUS_PORT — "
                        "what `launch --serving --status_port_base N` "
                        "stamps per replica; 0 = off)")
    return p


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)

    from paddle_tpu import metrics
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.scheduler import ServingConfig

    if args.metrics_jsonl:
        metrics.configure(jsonl=args.metrics_jsonl)

    if args.servable:
        from paddle_tpu.serving.export import load_servable

        cfg, params = load_servable(args.servable)
    else:
        import jax

        from paddle_tpu.models import transformer as T

        cfg = T.TransformerConfig(
            vocab_size=args.vocab, num_layers=args.layers,
            num_heads=args.heads, embed_dim=args.embed,
            mlp_dim=args.embed * 4, max_seq_len=256, remat=False,
            **json.loads(args.model_json))
        params = T.init_params(cfg, jax.random.key(args.seed))

    scfg = ServingConfig(
        max_slots=args.slots, page_size=args.page_size,
        num_pages=args.num_pages, max_prompt_len=args.max_prompt_len,
        max_new_tokens=args.max_new_tokens, seed=args.seed,
        prefix_cache=args.prefix_cache,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        denoise_steps=args.denoise_steps, unmask_policy=args.unmask_policy)
    if args.replicas > 1:
        from paddle_tpu.serving.fleet import build_local_fleet

        eng = build_local_fleet(cfg, params, scfg, n=args.replicas)
    else:
        eng = ServingEngine(cfg, params, scfg)

    # a replica spawned by `distributed.launch --serving` announces its
    # identity so the per-rank logs are attributable
    replica = os.environ.get("PADDLE_TPU_REPLICA_ID")
    if replica is not None:
        print(f"serving: replica {replica} of "
              f"{os.environ.get('PADDLE_TPU_NREPLICAS', '?')}",
              file=sys.stderr)

    # live introspection (--status_port / the launcher's per-replica
    # PADDLE_TPU_STATUS_PORT): the replica's /metrics is what the
    # FleetRouter-side aggregator (scrape_replicas) folds into the
    # fleet summary
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.telemetry import introspect as introspect_mod

    if args.status_port is not None:
        _flags.set("status_port", int(args.status_port))
    status = introspect_mod.server_from_flags(
        registry=metrics.get_registry())
    if status is not None:
        print(f"serving: introspection on http://127.0.0.1:"
              f"{status.port}", file=sys.stderr, flush=True)

    # synchronous per-line loop: submit, drain, print — deterministic
    # output order for scripted callers; a long-lived front-end would
    # eng.start() and stream results instead
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            prompt = [int(t) for t in line.split()]
            eng.submit(prompt, max_new_tokens=args.max_new_tokens,
                       temperature=args.temperature)
        except Exception as e:  # bad ids / too long / out of vocab:
            # report and keep serving the rest of the stream
            print(f"error: rejected {line!r}: {e}", file=sys.stderr)
            continue
        eng.run_until_idle()
        for res in eng.results():
            print(f"{res.id}: {' '.join(str(t) for t in res.tokens)}",
                  flush=True)
    eng.emit_summary()
    metrics.get_registry().flush()
    if status is not None:
        status.stop()
    return 0


if __name__ == "__main__":
    from paddle_tpu.core import compile_cache

    compile_cache.configure()  # process entry: before the first compile
    sys.exit(main())
