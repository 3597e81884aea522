"""Summarize a JSONL telemetry stream (the ``paddle_tpu.metrics`` schema)
into markdown: a per-step table with loss/latency/throughput/MFU, an
aggregate row, the comm-bytes breakdown, and any bench-kind rows.

The stream is whatever a JSONL sink captured — ``SGD.train`` /
``trainer/cli.py`` step records (``--metrics_jsonl=PATH`` or
``metrics.configure(jsonl=...)``) and ``--job=time``'s bench-kind row.

Usage: python tools/metrics_to_md.py /path/to/metrics.jsonl [--last N]
"""

from __future__ import annotations

import json
import sys


def _fmt(v, nd=2):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:,.{nd}f}"
    return str(v)


def load(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                pass  # torn tail line of a live file
    return records


def step_table(steps: list[dict], last: int | None = None) -> None:
    if last:
        shown = steps[-last:]
        if len(shown) < len(steps):
            print(f"_showing the last {len(shown)} of {len(steps)} steps_\n")
    else:
        shown = steps
    has_tok = any("tokens_per_sec" in r for r in shown)
    has_hbm = any("hbm_gbps" in r for r in shown)
    has_wait = any("input_wait_ms" in r for r in shown)
    has_stall = any("host_stall_ms" in r for r in shown)
    has_pad = any("padding_ratio" in r for r in shown)
    hdr = ["step", "pass", "loss", "step ms", "ex/s"]
    if has_tok:
        hdr.append("tok/s")
    hdr.append("MFU %")
    if has_hbm:
        hdr.append("HBM GB/s")
    if has_wait:
        hdr.append("in-wait ms")
    if has_stall:
        hdr.append("stall ms")
    if has_pad:
        hdr.append("pad %")
    print("| " + " | ".join(hdr) + " |")
    print("|" + "---|" * len(hdr))
    for r in shown:
        row = [str(r.get("step", "-")), str(r.get("pass_id", "-")),
               _fmt(r.get("loss"), 5), _fmt(r.get("step_ms")),
               _fmt(r.get("examples_per_sec"), 1)]
        if has_tok:
            row.append(_fmt(r.get("tokens_per_sec"), 0))
        row.append(_fmt(r.get("mfu_pct")))
        if has_hbm:
            row.append(_fmt(r.get("hbm_gbps")))
        if has_wait:
            # ⚠ = host-bound step: input wait exceeds 20% of step time,
            # i.e. the device idled for the feed — raise prefetch depth
            # or move preprocessing into the reader
            row.append(_fmt(r.get("input_wait_ms"))
                       + (" ⚠" if _host_bound(r) else ""))
        if has_stall:
            row.append(_fmt(r.get("host_stall_ms")))
        if has_pad:
            # ⚠ = padding-bound feed: >25% of the fed timesteps are
            # padding — bucket the reader by length (--seq_buckets)
            pr = r.get("padding_ratio")
            row.append((_fmt(pr * 100, 1) if pr is not None else "-")
                       + (" ⚠" if _padding_bound(r) else ""))
        print("| " + " | ".join(row) + " |")

    n = len(steps)
    ms = [r["step_ms"] for r in steps if "step_ms" in r]
    exs = [r["examples_per_sec"] for r in steps if "examples_per_sec" in r]
    mfu = [r["mfu_pct"] for r in steps if "mfu_pct" in r]
    print(f"\n**{n} steps** · step ms min/mean/max = "
          f"{_fmt(min(ms))}/{_fmt(sum(ms) / len(ms))}/{_fmt(max(ms))}"
          if ms else f"\n**{n} steps**", end="")
    if exs:
        print(f" · mean {_fmt(sum(exs) / len(exs), 1)} ex/s", end="")
    if mfu:
        print(f" · mean MFU {_fmt(sum(mfu) / len(mfu))}%", end="")
    print()
    bound = [r for r in steps if _host_bound(r)]
    if bound:
        waits = [r["input_wait_ms"] for r in bound]
        ids = ", ".join(str(r.get("step", "?")) for r in bound[:12])
        more = f" (+{len(bound) - 12} more)" if len(bound) > 12 else ""
        print(f"\n**⚠ {len(bound)}/{n} steps host-bound** (input wait > "
              f"20% of step time): steps {ids}{more} · worst wait "
              f"{_fmt(max(waits))} ms — the input pipeline is starving "
              f"the device; raise --prefetch or vectorize the reader.")
    padded = [r for r in steps if _padding_bound(r)]
    if padded:
        worst = max(r["padding_ratio"] for r in padded)
        print(f"\n**⚠ {len(padded)}/{n} steps padding-bound** (>25% of "
              f"fed timesteps are padding, worst "
              f"{_fmt(worst * 100, 1)}%) — bucket the reader by length "
              f"(--seq_buckets / reader.bucket_by_length) so the "
              f"recurrent sweep stops burning flops on pad rows.")


def _host_bound(r: dict) -> bool:
    """input wait exceeding 20% of step time = the device idled on input."""
    wait, ms = r.get("input_wait_ms"), r.get("step_ms")
    return bool(wait and ms and wait > 0.2 * ms)


def _padding_bound(r: dict) -> bool:
    """>25% padded timesteps = a quarter of the recurrent flops/bytes
    ran on padding; the reader should bucket by length."""
    pr = r.get("padding_ratio")
    return bool(pr is not None and pr > 0.25)


def _census_by_kind(comm: dict) -> dict:
    """Per-kind rollup of an {"op/axis": bytes} map (standalone twin of
    ``paddle_tpu.telemetry.census_by_kind`` — this tool must run on a
    bare checkout without importing the package)."""
    out: dict = {}
    for key, nbytes in comm.items():
        kind, _, axis = key.partition("/")
        row = out.setdefault(kind, {"bytes": 0.0, "sites": 0, "axes": []})
        row["bytes"] += float(nbytes)
        row["sites"] += 1
        if axis and axis not in row["axes"]:
            row["axes"].append(axis)
    return out


def comm_table(steps: list[dict]) -> None:
    comm = None
    for r in reversed(steps):  # counters are cumulative: latest wins
        if r.get("comm_bytes"):
            comm = r["comm_bytes"]
            break
    if not comm:
        return
    print("\n## Collective traffic (per-step bytes, traced)\n")
    print("| collective/axis | bytes/step |")
    print("|---|---|")
    for key, v in sorted(comm.items(), key=lambda kv: -kv[1]):
        print(f"| {key} | {v:,.0f} |")
    # the per-kind census: under ZeRO-2 the gradient flow's all_reduce
    # row drops to (near) zero, replaced by reduce_scatter + all_gather
    # at 1/n per-device payload — the collective swap, visible at a
    # glance
    census = _census_by_kind(comm)
    total = sum(r["bytes"] for r in census.values()) or 1.0
    print("\n## Collective census (per kind)\n")
    print("| kind | bytes/step/device | share | call sites | axes |")
    print("|---|---|---|---|---|")
    for kind, row in sorted(census.items(), key=lambda kv: -kv[1]["bytes"]):
        print(f"| {kind} | {row['bytes']:,.0f} "
              f"| {100.0 * row['bytes'] / total:.1f}% "
              f"| {row['sites']} | {', '.join(row['axes'])} |")
    if "reduce_scatter" in census and \
            census.get("all_reduce", {}).get("bytes", 0.0) \
            < 0.01 * census["reduce_scatter"]["bytes"]:
        print("\n_reduce-scatter carries the gradient flow (all-reduce "
              "≈ 0): the weight update is ZeRO-sharded._")


def recovery_table(faults: list[dict], recoveries: list[dict]) -> None:
    """Render the schema /3 fault-tolerance stream: one row per injected/
    handled fault and per supervisor restart, with a loud flag on any
    run that needed a restart — a dirty run must not read as clean."""
    if not faults and not recoveries:
        return
    print("\n## Faults & recovery\n")
    if recoveries:
        worst = max(r.get("recovery_ms", 0) or 0 for r in recoveries)
        print(f"**⚠ run restarted {len(recoveries)} time(s)** (worst "
              f"supervisor recovery {_fmt(float(worst))} ms) — the "
              f"trajectory is checkpoint-replayed, but investigate the "
              f"faults below.\n")
    print("| event | detail | pass | batch | loss / recovery ms |")
    print("|---|---|---|---|---|")
    for r in faults:
        print(f"| fault | {r.get('fault', '-')} | {r.get('pass_id', '-')} "
              f"| {r.get('batch_id', '-')} | {_fmt(r.get('loss'), 5)} |")
    for r in recoveries:
        print(f"| restart #{r.get('restart', '?')} "
              f"| {r.get('error', '-')} | - | - "
              f"| {_fmt(r.get('recovery_ms'))} |")


def elastic_table(events: list[dict]) -> None:
    """Render the schema /6 elastic-fleet stream: one row per live mesh
    rebuild (host loss / scale-up), with a loud flag on any recovery
    that had to fall back to a cursor checkpoint — a fallback means the
    lost host's shard was unrecoverable and progress was replayed, so
    it must not read as a clean live reshard."""
    if not events:
        return
    print("\n## Elastic events\n")
    print("| event | dp degree | recovery ms | shard source "
          "| pass | batch |")
    print("|---|---|---|---|---|---|")
    fallbacks = []
    for r in events:
        src = r.get("shard_source", "-")
        if src == "checkpoint":
            fallbacks.append(r)
            src += " ⚠"
        print(f"| {r.get('event', '-')} "
              f"| {r.get('old_dp', '?')} → {r.get('new_dp', '?')} "
              f"| {_fmt(r.get('recovery_ms'))} | {src} "
              f"| {r.get('pass_id', '-')} | {r.get('batch_id', '-')} |")
    worst = max((r.get("recovery_ms", 0) or 0 for r in events),
                default=0)
    print(f"\n**{len(events)} elastic rebuild(s)** · worst recovery "
          f"{_fmt(float(worst))} ms — training continued in-process; "
          f"no fleet restart.")
    if fallbacks:
        cursors = ", ".join(
            f"pass {r.get('replay_cursor', {}).get('pass_id', '?')} "
            f"batch {r.get('replay_cursor', {}).get('batch_id', '?')}"
            for r in fallbacks)
        print(f"\n**⚠ {len(fallbacks)} checkpoint-fallback "
              f"recover{'y' if len(fallbacks) == 1 else 'ies'}** — live "
              f"shards were unrecoverable and the trajectory replayed "
              f"from {cursors}; work since those cursors was redone.  "
              f"Shorten --checkpoint_batch_period if this recurs.")


def fleet_table(events: list[dict]) -> None:
    """Render the schema /8 serving-fleet stream: one row per fleet
    event (replica_down / swap / swap_rollback), then the newest
    availability summary — with loud flags on lost requests and
    rolled-back swaps, which must never read as a healthy fleet."""
    if not events:
        return
    print("\n## Serving fleet\n")
    rows = [r for r in events if r.get("event") != "summary"]
    if rows:
        print("| event | detail |")
        print("|---|---|")
        for r in rows:
            ev = r.get("event", "-")
            if ev == "replica_down":
                detail = (f"replica {r.get('replica', '?')} "
                          f"({r.get('reason', '?')}) — "
                          f"{r.get('requeued', 0)} request(s) re-queued"
                          + (f", {r['failed']} failed ⚠"
                             if r.get("failed") else ""))
            elif ev == "swap":
                detail = (f"servable `{r.get('servable', '?')}` rolled "
                          f"across {len(r.get('replicas') or {})} "
                          f"replica(s), zero downtime")
            elif ev == "swap_rollback":
                detail = (f"⚠ servable `{r.get('servable', '?')}` "
                          f"REFUSED ({r.get('error', '?')}); rolled "
                          f"back {len(r.get('rolled_back') or [])} "
                          f"replica(s)")
            elif ev == "replica_added":
                detail = (f"replica {r.get('replica', '?')} joined "
                          f"(cloned from replica "
                          f"{r.get('source', '?')}) — fleet now "
                          f"{r.get('alive', '?')} alive")
            elif ev == "replica_retired":
                detail = (f"replica {r.get('replica', '?')} retired "
                          f"({r.get('reason', '?')}) — "
                          f"{r.get('requeued', 0)} request(s) "
                          f"re-queued, fleet now "
                          f"{r.get('alive', '?')} alive")
            else:
                detail = str({k: v for k, v in r.items()
                              if k not in ("event", "kind", "schema",
                                           "ts", "host")})
            print(f"| {ev} | {detail} |")
    summaries = [r for r in events if r.get("event") == "summary"]
    for s in summaries[-1:]:
        lost = s.get("requests_lost", 0)
        print(f"\n**fleet summary** · {s.get('submitted', 0)} submitted "
              f"· {s.get('delivered', 0)} delivered "
              f"· {s.get('failovers', 0)} failover(s) "
              f"· {s.get('shed', 0)} shed "
              f"· {s.get('swaps', 0)} swap(s) "
              f"· {s.get('alive_replicas', '?')} replica(s) alive "
              f"· requests lost: "
              f"{'**' + str(lost) + '** ⚠' if lost else '0'}")
        if lost:
            print("\n**⚠ requests were lost** — an accepted request "
                  "neither delivered a result nor remains queued; the "
                  "failover/idempotence contract is broken.  This is a "
                  "bug, not load.")
        if s.get("shed"):
            print("\n_shedding engaged: clients received retry-after "
                  "rejections while the fleet was past its admission "
                  "watermarks — raise capacity or relax the SLO if "
                  "this recurs under normal load._")


def deploy_table(deploys: list[dict]) -> None:
    """Render the schema /15 deployment ledger (``kind="deploy"``,
    paddle_tpu/deploy/controller.py): one row per rollout attempt with
    its export/swap/total timings — a rolled-back or failed attempt is
    flagged loudly, because a fleet that silently stops taking weight
    pushes is a serving incident, not a detail."""
    if not deploys:
        return
    print("\n## Deployments\n")
    print("| attempt | checkpoint | outcome | export ms | swap ms "
          "| total ms |")
    print("|---|---|---|---|---|---|")
    bad = []
    for r in deploys:
        outcome = r.get("outcome", "-")
        if outcome != "deployed":
            bad.append(r)
            outcome = f"**{outcome}** ⚠"
        print(f"| {r.get('attempt', '?')} | `{r.get('checkpoint', '-')}` "
              f"| {outcome} | {_fmt(r.get('export_ms'))} "
              f"| {_fmt(r.get('swap_ms'))} | {_fmt(r.get('total_ms'))} |")
    ok = len(deploys) - len(bad)
    print(f"\n**{len(deploys)} rollout attempt(s)** · {ok} deployed · "
          f"{len(bad)} failed/rolled back")
    for r in bad:
        print(f"\n**⚠ {r.get('outcome')}**: `{r.get('checkpoint')}` "
              f"(attempt {r.get('attempt', '?')}) — "
              f"{r.get('error', 'no error recorded')}.  A rollback means "
              f"the fleet kept serving the PREVIOUS weights; if every "
              f"attempt for a checkpoint fails it is marked bad and the "
              f"next checkpoint deploys over it.")


def autoscale_table(events: list[dict]) -> None:
    """Render the schema /15 autoscale stream (``kind="autoscale"``,
    paddle_tpu/deploy/autoscaler.py + arbiter.py): one row per scale
    action and per pool shift — the chaos-ramp bench's evidence that
    the fleet followed the load curve both ways."""
    if not events:
        return
    print("\n## Autoscaling\n")
    print("| event | detail |")
    print("|---|---|")
    ups = downs = 0
    for r in events:
        ev = r.get("event", "-")
        if ev == "scale_up":
            ups += 1
            detail = (f"replica {r.get('replica', '?')} added "
                      f"({r.get('reason', '?')}) in "
                      f"{_fmt(r.get('scale_ms'))} ms")
        elif ev == "scale_down":
            downs += 1
            detail = (f"replica {r.get('replica', '?')} retired "
                      f"({r.get('reason', '?')}), "
                      f"{r.get('requeued', 0)} request(s) re-queued, in "
                      f"{_fmt(r.get('scale_ms'))} ms")
        elif ev in ("pool_borrow", "pool_return"):
            detail = (f"{r.get('reason', '?')} — pool now "
                      f"{r.get('trainer_hosts', '?')} trainer / "
                      f"{r.get('serving_hosts', '?')} serving host(s)")
        else:
            detail = str({k: v for k, v in r.items()
                          if k not in ("event", "kind", "schema",
                                       "ts", "host")})
        print(f"| {ev} | {detail} |")
    if ups or downs:
        print(f"\n**{ups} scale-up(s) · {downs} scale-down(s)** — "
              f"scale-downs drain through the failover re-queue path, "
              f"so they never lose requests.")


def _pctl(vals: list[float], q: float) -> float:
    """Nearest-rank-with-interpolation percentile over raw values (the
    per-request serve records carry exact latencies, so no bucket
    estimate is needed here)."""
    vs = sorted(vals)
    if len(vs) == 1:
        return vs[0]
    rank = (q / 100.0) * (len(vs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (rank - lo)


def serving_table(serves: list[dict], summaries: list[dict]) -> None:
    """Render the schema /4 serving stream: per-request latency
    percentiles (TTFT / TPOT / queue wait / total) from the
    ``kind="serve"`` records, plus the engine's own histogram rollup
    (``serve_summary``) when present."""
    if not serves and not summaries:
        return
    print("\n## Serving latency\n")
    if serves:
        toks = sum(r.get("new_tokens", 0) for r in serves)
        cached = sum(r.get("cached_tokens", 0) for r in serves)
        chunks = sum(r.get("prefill_chunks", 0) for r in serves)
        extra = ""
        if cached:
            extra += f" · {cached} prompt tokens from prefix cache"
        if chunks:
            extra += f" · {chunks} prefill chunks"
        print(f"**{len(serves)} requests** · {toks} generated "
              f"tokens{extra}\n")
        print("| metric | count | p50 ms | p99 ms | max ms |")
        print("|---|---|---|---|---|")
        for field, label in (("ttft_ms", "TTFT"), ("tpot_ms", "TPOT"),
                             ("queue_wait_ms", "queue wait"),
                             ("total_ms", "total")):
            vals = [float(r[field]) for r in serves if field in r]
            if not vals:
                continue
            print(f"| {label} | {len(vals)} | {_pctl(vals, 50):,.2f} "
                  f"| {_pctl(vals, 99):,.2f} | {max(vals):,.2f} |")
    for s in summaries[-1:]:  # the newest rollup wins
        rows = s.get("summary") or {}
        if rows:
            print("\n_engine histogram rollup (bucket-interpolated):_\n")
            print("| histogram | count | p50 ms | p99 ms | max ms |")
            print("|---|---|---|---|---|")
            for name, h in rows.items():
                print(f"| {name} | {h.get('count', '-')} "
                      f"| {_fmt(h.get('p50'))} | {_fmt(h.get('p99'))} "
                      f"| {_fmt(h.get('max'))} |")
        p = s.get("prefix")
        if p:
            print("\n_prefix cache (schema /14):_\n")
            print("| prefix_hit_rate | hit tokens | prompt tokens "
                  "| prefill_chunks | evictions | cached pages "
                  "| recompute FLOPs saved |")
            print("|---|---|---|---|---|---|---|")
            print(f"| {p.get('hit_rate', 0):.2%} "
                  f"| {p.get('hit_tokens', 0)} "
                  f"| {p.get('prompt_tokens', 0)} "
                  f"| {s.get('prefill_chunks', 0)} "
                  f"| {p.get('evictions', 0)} "
                  f"| {p.get('cached_pages', 0)} "
                  f"| {p.get('flops_saved', 0):,.3g} |")
        elif s.get("prefill_chunks"):
            print(f"\n_{s['prefill_chunks']} incremental prefill "
                  "passes (chunked prefill on, prefix cache off)._")
        if s.get("rejected_admissions"):
            print(f"\n_⚠ {s['rejected_admissions']} admission attempts "
                  "blocked on pages/budget — requests queued while the "
                  "cache was full; grow num_pages or max_concurrent_"
                  "tokens if TTFT p99 matters more than memory._")


def preflight_table(records: list[dict],
                    steps: list[dict] | None = None) -> None:
    """Render the schema /7 static-analysis stream: one row per
    ``trainer --preflight`` / analysis run, with a loud flag on any run
    that was not clean — a program that failed its preflight must not
    read as a healthy run."""
    if not records:
        return
    print("\n## Preflight (static analysis)\n")
    print("| config | clean | findings | suppressed | by rule |")
    print("|---|---|---|---|---|")
    dirty = []
    for r in records:
        clean = r.get("clean", not r.get("findings"))
        if not clean:
            dirty.append(r)
        rules = ", ".join(f"{k}×{v}" for k, v in
                          (r.get("by_rule") or {}).items()) or "-"
        print(f"| {r.get('config') or '-'} | {'yes' if clean else '**NO** ⚠'} "
              f"| {r.get('findings', 0)} | {r.get('suppressed', 0)} "
              f"| {rules} |")
    if dirty:
        ids = "; ".join(i for r in dirty for i in (r.get("ids") or [])[:4])
        print(f"\n**⚠ {len(dirty)} preflight run(s) failed** — the "
              f"program carries statically detectable hazards "
              f"({ids}); fix them or baseline them with a reason "
              f"before trusting the run.")
    _memory_budget_table([r for r in records if r.get("memory")])
    _static_cost_table([r for r in records if r.get("cost")], steps or [])


def _memory_budget_table(records: list[dict]) -> None:
    """The schema /9 GL-P-MEM budget table: static per-device byte
    accounting of each preflighted step (params + zero-mode optimizer
    slots + activation liveness), the future sharding/kernel PR's
    citable byte-count assertion."""
    if not records:
        return
    print("\n### Memory budget (GL-P-MEM, static per device)\n")
    print("| config | zero | dp | params MB | opt MB | acts MB "
          "| total MB | activations via |")
    print("|---|---|---|---|---|---|---|---|")
    for r in records:
        m = r["memory"]
        print(f"| {r.get('config') or '-'} | {m.get('zero', 0)} "
              f"| {m.get('dp', 1)} "
              f"| {_fmt(m.get('params_bytes', 0) / 1e6)} "
              f"| {_fmt(m.get('opt_state_bytes', 0) / 1e6)} "
              f"| {_fmt(m.get('activation_bytes', 0) / 1e6)} "
              f"| **{_fmt(m.get('total_bytes', 0) / 1e6)}** "
              f"| {m.get('activation_source', '-')} |")
    vmem = [(r.get("config"), k) for r in records
            for k in (r["memory"].get("pallas_vmem") or ())]
    if vmem:
        print("\n| config | pallas kernel | VMEM MB |")
        print("|---|---|---|")
        for cfg, k in vmem:
            print(f"| {cfg or '-'} | {k.get('kernel')} "
                  f"| {_fmt(k.get('bytes', 0) / 1e6)} |")


def _measured_for(run: str, steps: list[dict]) -> tuple:
    """Median measured (step_ms, mfu_pct) of the step records that match
    a preflight record's run — a single-run stream matches regardless of
    the name (the common local flow: preflight, then train, one file)."""
    runs = {r.get("run", "train") for r in steps}
    mine = [r for r in steps
            if r.get("run", "train") == run or len(runs) == 1]
    ms = sorted(r["step_ms"] for r in mine
                if isinstance(r.get("step_ms"), (int, float)))
    mfu = sorted(r["mfu_pct"] for r in mine
                 if isinstance(r.get("mfu_pct"), (int, float))
                 and r["mfu_pct"] > 0)
    return (ms[len(ms) // 2] if ms else None,
            mfu[len(mfu) // 2] if mfu else None)


def _static_cost_table(records: list[dict], steps: list[dict]) -> None:
    """The schema /13 GL-P-COST roofline table: predicted step_ms / MFU
    per preflighted config vs the measured medians when a matching step
    stream exists, ⚠-flagging rows under the MFU target with the named
    bottleneck — the static claim and the measured truth side by side."""
    if not records:
        return
    print("\n### Static cost (GL-P-COST roofline)\n")
    print("| config | profile | pred step ms | pred MFU % | meas step ms "
          "| meas MFU % | bottleneck |")
    print("|---|---|---|---|---|---|---|")
    below = []
    for r in records:
        c = r["cost"]
        meas_ms, meas_mfu = _measured_for(r.get("run", "preflight"), steps)
        mfu = c.get("mfu_pct")
        cell = _fmt(mfu)
        bottleneck = c.get("bottleneck", "-")
        if isinstance(mfu, (int, float)) and mfu < MFU_TARGET_PCT:
            cell += " ⚠"
            below.append((r.get("config") or "-", mfu, bottleneck))
        print(f"| {r.get('config') or '-'} | {c.get('profile', '-')} "
              f"| {_fmt(c.get('step_ms'))} | {cell} "
              f"| {_fmt(meas_ms) if meas_ms is not None else '-'} "
              f"| {_fmt(meas_mfu) if meas_mfu is not None else '-'} "
              f"| {bottleneck} |")
    if below:
        names = "; ".join(f"{cfg} ({mfu:.1f}%, {b})"
                          for cfg, mfu, b in below)
        print(f"\n**⚠ {len(below)} config(s) predicted below the "
              f"{MFU_TARGET_PCT:.0f}% MFU target:** {names} — the named "
              f"bottleneck is where the next batching/fusion/sharding "
              f"change should land.")


def trace_table(profiles: list[dict]) -> None:
    """Render the schema /11 live-introspection stream: one block per
    ``--profile_steps`` capture (``kind="profile"``) with the tracer's
    per-phase duration table — p50/p99/total per phase name — and a
    loud flag on any fence or queue phase consuming more than 20% of
    the step phase's total time (the host is stalling on the device
    fence, or requests are parked in admission: the deferred-fencing /
    admission knobs are the lever)."""
    if not profiles:
        return
    print("\n## Trace spans (windowed device profiles)\n")
    for r in profiles:
        window = f"steps [{r.get('start_step', '?')}, " \
                 f"{r.get('end_step', '?')})"
        print(f"**profile** · {window} · wall "
              f"{_fmt(r.get('wall_ms'))} ms · trace "
              f"`{r.get('trace_dir', '-')}`\n")
        spans = r.get("spans") or {}
        if not spans:
            print("_no spans recorded in the window (run with "
                  "--trace_spans for the phase table)_")
            continue
        step_total = (spans.get("step") or {}).get("total_ms", 0.0)
        print("| phase | count | p50 ms | p99 ms | total ms "
              "| of step |")
        print("|---|---|---|---|---|---|")
        hot = []
        for name, s in spans.items():
            share = (s.get("total_ms", 0.0) / step_total
                     if step_total else None)
            cell = f"{share * 100:.1f}%" if share is not None else "-"
            flagged = (share is not None and share > 0.2
                       and ("fence" in name or "queue" in name))
            if flagged:
                cell += " ⚠"
                hot.append((name, share))
            print(f"| {name} | {s.get('count', '-')} "
                  f"| {_fmt(s.get('p50_ms'))} | {_fmt(s.get('p99_ms'))} "
                  f"| {_fmt(s.get('total_ms'))} | {cell} |")
        for name, share in hot:
            what = ("the deferred-fence drain is eating the step — "
                    "raise --sync_period or shrink the readback"
                    if "fence" in name else
                    "requests sit in admission — grow pages/slots or "
                    "shed earlier")
            print(f"\n**⚠ `{name}` is {share * 100:.0f}% of step "
                  f"time** — {what}.")


def goodput_table(ledgers: list[dict]) -> None:
    """Render the schema /12 goodput ledger (``kind="ledger"``,
    telemetry/goodput.py): the wall-clock account — one row per badput
    bucket with its share of wall (``startup`` since /16: set-up spans
    and persistent-cache fetches, which ``idle`` used to hide) — plus the
    serving cost-per-token split when the run served.  Buckets above 10% of wall are flagged:
    they are the lever the ledger exists to point at."""
    if not ledgers:
        return
    print("\n## Goodput\n")
    for r in ledgers:
        wall = r.get("wall_s") or 0.0
        frac = r.get("goodput_fraction")
        print(f"**ledger** · wall {_fmt(wall)} s · goodput "
              f"**{frac * 100:.1f}%**" if frac is not None
              else f"**ledger** · wall {_fmt(wall)} s")
        buckets = r.get("buckets_s") or {}
        if buckets:
            print("\n| bucket | seconds | of wall |")
            print("|---|---|---|")
            hot = []
            for name, secs in buckets.items():
                share = secs / wall if wall else 0.0
                cell = f"{share * 100:.1f}%"
                if share > 0.10 and name not in ("compute",):
                    cell += " ⚠"
                    hot.append((name, share))
                print(f"| {name} | {_fmt(secs, 3)} | {cell} |")
            if hot:
                names = ", ".join(f"`{n}` ({s * 100:.0f}%)"
                                  for n, s in hot)
                print(f"\n**⚠ badput over 10% of wall-clock:** {names} "
                      f"— the levers this ledger points at.")
        serving = r.get("serving") or {}
        if serving.get("cost_per_token_s") is not None:
            print("\n| cost per token | seconds |")
            print("|---|---|")
            for k, label in (("cost_per_token_s", "total (compute)"),
                             ("cost_per_token_prefill_s", "prefill"),
                             ("cost_per_token_decode_s", "decode"),
                             ("cost_per_token_queue_s", "queue")):
                if serving.get(k) is not None:
                    print(f"| {label} | {serving[k]:.6g} |")
            print(f"\n_{_fmt(serving.get('tokens', 0), 0)} tokens · "
                  f"KV-page occupancy "
                  f"{_fmt(serving.get('kv_page_s'))} page·s_")


MFU_TARGET_PCT = 50.0  # the ROADMAP north-star floor


def bench_table(rows: list[dict]) -> None:
    if not rows:
        return
    print("\n## Bench rows\n")
    print("| metric | value | MFU % |")
    print("|---|---|---|")
    below = []
    for r in rows:
        if "metric" not in r:
            continue
        val = f"{r.get('value', '-')} {r.get('unit', '')}".strip()
        mfu = r.get("mfu_pct", "-")
        cell = str(mfu)
        if isinstance(mfu, (int, float)) and mfu < MFU_TARGET_PCT:
            cell += " ⚠"
            below.append((r["metric"], mfu))
        print(f"| {r['metric']} | **{val}** | {cell} |")
    # the TPP fused-kernel ablation sub-rows: speedup + which path is
    # trusted (bit-identical trajectory vs tolerance-bounded)
    abl = [r for r in rows
           if str(r.get("metric", "")).endswith("fused_ablation_speedup")
           and "unfused_ms" in r]
    if abl:
        print("\n### Fused-kernel ablation (TPP)\n")
        print("| workload | unfused ms | fused ms | speedup | trajectory |")
        print("|---|---|---|---|---|")
        for r in abl:
            traj = ("bit-identical" if r.get("trajectory_identical")
                    else f"≤{r.get('trajectory_max_rel_diff', 0):.1e} rel")
            print(f"| {r['metric'].replace('_fused_ablation_speedup', '')} "
                  f"| {_fmt(r.get('unfused_ms'))} "
                  f"| {_fmt(r.get('fused_ms'))} "
                  f"| **{_fmt(r.get('value'))}x** | {traj} |")
    if below:
        names = ", ".join(f"{m} ({v}%)" for m, v in below)
        print(f"\n**⚠ {len(below)} row(s) below the {MFU_TARGET_PCT:.0f}% "
              f"MFU target:** {names} — candidates for the next fused-"
              f"kernel/batching pass.")


def main(argv: list[str]) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 2
    last = None
    if "--last" in argv:
        i = argv.index("--last")
        last = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    records = load(argv[0])
    steps = [r for r in records if r.get("kind") == "step"]
    faults = [r for r in records if r.get("kind") == "fault"]
    recoveries = [r for r in records if r.get("kind") == "recovery"]
    serves = [r for r in records if r.get("kind") == "serve"]
    serve_summaries = [r for r in records
                       if r.get("kind") == "serve_summary"]
    elastics = [r for r in records if r.get("kind") == "elastic_event"]
    fleets = [r for r in records if r.get("kind") == "fleet"]
    preflights = [r for r in records if r.get("kind") == "preflight"]
    profiles = [r for r in records if r.get("kind") == "profile"]
    ledgers = [r for r in records if r.get("kind") == "ledger"]
    deploys = [r for r in records if r.get("kind") == "deploy"]
    autoscales = [r for r in records if r.get("kind") == "autoscale"]
    bench = [r for r in records
             if r.get("kind") == "bench" or
             ("metric" in r and "kind" not in r)]  # pre-schema bench rows
    print(f"# Telemetry summary — {argv[0]}\n")
    if steps:
        by_run: dict[str, list] = {}
        for r in steps:
            by_run.setdefault(r.get("run", "train"), []).append(r)
        for run, rs in by_run.items():
            print(f"## Steps — run `{run}`\n")
            step_table(rs, last=last)
        comm_table(steps)
    recovery_table(faults, recoveries)
    elastic_table(elastics)
    fleet_table(fleets)
    deploy_table(deploys)
    autoscale_table(autoscales)
    serving_table(serves, serve_summaries)
    preflight_table(preflights, steps)
    trace_table(profiles)
    goodput_table(ledgers)
    bench_table(bench)
    if not steps and not bench and not faults and not recoveries \
            and not serves and not serve_summaries and not elastics \
            and not fleets and not preflights and not profiles \
            and not ledgers and not deploys and not autoscales:
        print("_no step, fault, serve or bench records found_")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
