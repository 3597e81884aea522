"""Driver: a transformer through ``serving.ServingEngine``
(``submit`` / background loop / ``results``).

One process: the engine's loop thread owns the chip, the load generator
runs on the main thread.  Set-up makes the weights from the seed, warms
the two programs (prefill, decode) and runs the cell's traffic for a
lead-in so the window opens on a steady state.  Every token is stamped
where the engine hands it out (a wrapper around the scheduler's
``append_token``: the program has no streaming callback yet), so time to
first token and time per output token are the benchmark's own clock.
After the window the plain reference reads a seeded sample of the served
requests.  In a traced run the profile is stopped off this thread
(``harness/trace.stop_off_thread``): ``stop_trace`` takes tens of
seconds, and a load generator that waits for it sends nothing meanwhile.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks.harness import loadgen, stats
from benchmarks.harness import trace as trace_mod

GRACE_S = 10.0

class _Stamps:
    """Per-request token times on the benchmark's clock."""

    def __init__(self, scheduler):
        self.first: dict[int, float] = {}
        self.last: dict[int, float] = {}
        self.count: dict[int, int] = {}
        self.times: list[float] = []
        self.per_request: dict[int, list[float]] = {}
        self.profiling = False
        self.decode_context_tokens = 0
        self.decode_tokens = 0
        inner = scheduler.append_token

        def stamped(a, token):
            if self.profiling and a.generated:
                # this token came from a decode step that read the
                # sequence's whole resident context
                self.decode_context_tokens += a.prompt_len + len(a.generated)
                self.decode_tokens += 1
            inner(a, token)
            now = time.perf_counter()
            rid = a.request.id
            self.first.setdefault(rid, now)
            self.last[rid] = now
            self.count[rid] = self.count.get(rid, 0) + 1
            self.times.append(now)
            self.per_request.setdefault(rid, []).append(now)

        scheduler.append_token = stamped

    def step_end(self, t: float, slack: float = 0.002) -> float | None:
        """The end of the engine step that hands out the first token at
        or after ``t`` (a step's tokens are stamped within microseconds
        of each other)."""
        import bisect

        i = bisect.bisect_left(self.times, t)
        if i >= len(self.times):
            return None
        while (i + 1 < len(self.times)
               and self.times[i + 1] - self.times[i] < slack):
            i += 1
        return self.times[i]


def run(run) -> dict:
    import jax.numpy as jnp

    from paddle_tpu import metrics as metrics_mod
    from paddle_tpu.models import transformer as T
    from paddle_tpu.ops import pallas
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.scheduler import ServingConfig
    from paddle_tpu.telemetry import tracing as tracing_mod

    cfg, traffic = run.config, run.cell["traffic"]
    m, sv = cfg["model"], cfg["serving"]
    ref = run.py("references", cfg["reference"])
    weights = ref.init_weights(m, run.seed, getattr(jnp, cfg["dtype"]))
    tcfg = T.TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=m["num_layers"],
        num_heads=m["num_heads"], embed_dim=m["embed_dim"],
        mlp_dim=m["mlp_dim"], max_seq_len=m["max_positions"],
        dtype=getattr(jnp, cfg["dtype"]), remat=False,
        attn_impl=cfg["prefill_attn_impl"])
    scfg = ServingConfig(seed=run.seed & 0x7FFFFFFF,
                         attn_impl=cfg["decode_attn_impl"], **sv)
    if run.trace:
        tracing_mod.configure_tracing(enabled=True)
    engine = ServingEngine(
        tcfg, ref.program_tree(weights), scfg,
        registry=metrics_mod.MetricsRegistry(f"bench_{run.workload}_warm"),
        device=run.devices[0])
    stamps = _Stamps(engine.scheduler)
    pool = loadgen.make_requests(traffic, run.seed, m["vocab_size"])

    # -- warm both programs on this thread (routes are traced here) -----------
    warm = loadgen.rng_for(run.seed, 3).integers(
        0, m["vocab_size"], size=(sv["prefill_batch"] + 1, 8)).tolist()
    with pallas.capture_routes() as routes:
        engine.generate(warm, max_new_tokens=3)
    routes = {f"{op}:{path}": n for (op, path), n in sorted(routes.items())}
    prefill_impl = engine.prefill_attn_impl
    run.log(f"warmed prefill + decode; routes {routes}; prefill attention "
            f"{prefill_impl}")
    engine.start()

    # -- the load generator ---------------------------------------------------
    closed = traffic["loop"] == "closed"
    clients = int(traffic.get("clients", 0))
    lead_s = float(traffic.get("lead_in_s", 0.0))
    info: dict[int, dict] = {}      # rid -> due, sent, pool index
    results: dict[int, object] = {}
    sent_by: dict[int, int] = {}    # client (0 in an open loop) -> sent
    refused = {"n": 0}

    def send(due: float, client: int = 0, share: float = 1.0) -> None:
        # client c's k-th request is pool entry c + k * clients: which
        # sizes a client sends does not depend on who answered first
        k = sent_by.get(client, 0)
        sent_by[client] = k + 1
        i = client + k * max(clients, 1)
        req = pool[i % len(pool)]
        try:
            rid = engine.submit(req["prompt"], max_new_tokens=max(
                2, int(np.ceil(share * req["max_new_tokens"]))))
        except Exception as e:   # refused: counts as failed
            refused["n"] += 1
            run.log(f"submit refused: {type(e).__name__}: {e}")
            return
        sent = time.perf_counter()
        info[rid] = {"due": due, "sent": sent, "client": client}

    def collect(timeout: float) -> list:
        got = engine.results(timeout=max(timeout, 0.0))
        for r in got:
            results[r.id] = r
        return got

    t_start = time.perf_counter()
    t_open = t_start + lead_s
    t_close = t_open + run.seconds
    if closed:
        # the first wave is caught mid-flight (a seeded share of each
        # answer is still to come), so the slots are out of step from the
        # start, as they are after minutes of service
        share = (loadgen.rng_for(run.seed, 5).permutation(clients)
                 + 0.5) / clients
        for c in range(clients):
            send(time.perf_counter(), c, float(share[c]))
        due_times = []
    else:
        n_due = int((lead_s + run.seconds) * traffic["rate_rps"] * 1.5) + 64
        due_times = [t_start + x for x in loadgen.arrival_times(
            traffic, run.seed, n_due)]
    di = 0
    opened = False
    prof = {"state": "off" if run.trace else "done", "marker_ns": None}
    profile_dir = os.path.join(run.scratch, "profile", run.workload)
    prof_from = t_open + float(traffic.get("profile_after_s", 2.0))
    prof_len = float(traffic.get("profile_s", 3.0))
    mark = setup_s = None
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            # the window opens: fresh histograms, count compiles from here
            engine.registry = metrics_mod.MetricsRegistry(
                f"bench_{run.workload}")
            tracing_mod.get_tracer().clear()
            mark = run.compiles.mark()
            setup_s = now - run.proc_t0
            opened = True
            run.window_opens()
        if now >= t_close and (stamps.step_end(t_close) is not None
                               or now >= t_close + 5.0):
            break
        if prof["state"] == "off" and now >= prof_from:
            prof["marker_ns"] = trace_mod.start(profile_dir)
            stamps.profiling = True
            prof["state"], prof["t0"] = "on", time.perf_counter()
        elif prof["state"] == "on" and now >= prof["t0"] + prof_len:
            # off this thread: the loop keeps collecting answers and
            # sending the clients' next requests while stop_trace runs
            stamps.profiling = False
            prof["stopper"] = trace_mod.stop_off_thread(prof)
            prof["state"] = "done"
        if closed:
            for r in collect(0.002):
                # the client sends its next request when this one answered
                send(stamps.last.get(r.id, time.perf_counter()),
                     info[r.id]["client"])
        else:
            while di < len(due_times) and due_times[di] <= now:
                if due_times[di] < t_close:
                    send(due_times[di])
                di += 1
            nxt_due = due_times[di] if di < len(due_times) else t_close
            collect(min(0.002, max(nxt_due - now, 0.0)))
    if prof["state"] == "on":
        stamps.profiling = False
        trace_mod.stop()
        prof["state"] = "cut"
    compiles = run.compiles.mark() - (mark or 0)
    # the window opens and closes on the end of an engine step
    t_open = stamps.step_end(t_open) or t_open
    t_close = stamps.step_end(t_close) or t_close
    window_s = t_close - t_open
    window = [rid for rid, d in info.items() if t_open <= d["due"] < t_close]
    # a request due in the window that has its first token within the
    # grace is being served (an answer can take longer than any window);
    # one that has none is failed
    deadline = t_close + GRACE_S
    while (any(rid not in stamps.first for rid in window)
           and time.perf_counter() < deadline):
        collect(0.05)
    collect(0.0)
    backlog = sum(1 for rid in info if rid not in results)
    engine.stop()
    if "stopper" in prof:
        # after the engine: while this thread waits here no client sends
        prof["stopper"].join()
        run.log(f"stop_trace took {prof['stop_s']:.1f} s, off this thread")
    mem = run.devices[0].memory_stats() or {}
    # live buffers and the programs' reserved scratch are separate pools
    peak_bytes = int(mem.get("peak_bytes_in_use", 0)) + int(
        mem.get("peak_bytes_reserved", 0))

    # -- end-to-end -----------------------------------------------------------
    done = [rid for rid in window if rid in stamps.first]
    failed = len(window) - len(done) + refused["n"]
    ttft = [(stamps.first[rid] - info[rid]["due"]) * 1e3 for rid in done]
    tokens = sum(1 for t in stamps.times if t_open < t <= t_close)
    itl = [(b - a) * 1e3 for ts in stamps.per_request.values()
           for a, b in zip(ts, ts[1:]) if t_open < b <= t_close]
    itl_p95, n_itl = stats.tail(itl, 0.95)
    finished_in = sum(1 for rid, r in results.items()
                      if t_open < stamps.last.get(rid, 0.0) <= t_close)
    e2e = {"serve_tok_per_s": tokens / window_s,
           "serve_itl_p95_ms": itl_p95, "setup_s": setup_s}
    run.log(f"window {window_s:.3f} s: {tokens} tokens out "
            f"({tokens / window_s:.2f}/s), {finished_in} requests finished "
            f"in it, {len(window)} due in it of which {len(done)} had their "
            f"first token within the grace; time between a request's tokens median "
            f"{stats.median(itl)} p95 {itl_p95} ms over {n_itl}; TTFT median "
            f"{stats.median(ttft)} ms over {len(ttft)}; unanswered at the "
            f"end {backlog}")
    if itl_p95 is None:
        run.check("tail_samples", n_itl, stats.TAIL_MIN_BEYOND * 20,
                  ok=False, note="too few tokens for a 95th percentile")

    spans = trace_mod.span_dicts(tracing_mod.get_tracer().spans)
    registry = engine.registry
    window_late = [(info[rid]["sent"] - info[rid]["due"]) * 1e3
                   for rid in window]

    # -- free the program, then the plain reference ---------------------------
    # every request the engine finished (whenever it was due): a seeded
    # sample of them, with the longest in it
    served = {rid: (list(r.prompt), list(r.tokens))
              for rid, r in results.items()}
    del engine
    gc.collect()
    pad_to = sv["max_prompt_len"] + sv["max_new_tokens"]
    n_check = int(run.cell["check_requests"])
    order = sorted(served, key=lambda r: -(len(served[r][0])
                                           + len(served[r][1])))
    picks = order[:1]
    rest = order[1:]
    if not picks:
        run.check("served_requests", 0, 1, ok=False,
                  note="the window finished no request to compare")
    if rest:
        idx = loadgen.rng_for(run.seed, 4).permutation(len(rest))
        picks += [rest[i] for i in idx[:n_check - 1]]
    t_ref = time.perf_counter()
    gaps = ref.served_gaps(m, weights, [served[r] for r in picks], pad_to)
    summ = ref.summarise(gaps["served"])
    distinct = len({t for r in picks for t in served[r][1]})
    longest = max([len(p) + len(t) for p, t in
                   (served[r] for r in picks)], default=0)
    run.log(f"reference read {len(picks)} served requests "
            f"({len(gaps['served'])} tokens, {distinct} distinct, the "
            f"longest {longest} long, median margin of the reference's best "
            f"{stats.median(gaps['margin'])}) in "
            f"{time.perf_counter() - t_ref:.1f} s")
    lim = run.cell["limits"]
    run.check("served_logit_gap", summ["widest"], lim["served_logit_gap"],
              note="widest gap of a served token below the reference's best")
    run.check("served_mean_gap", summ["mean"], lim["served_mean_gap"],
              note=f"mean gap; {summ['moved_share']} of the tokens are not "
              "the reference's best")
    run.check("failed_requests", failed, 0)
    if run.on_chip:
        run.check("reference_routes",
                  sum(n for k, n in routes.items()
                      if k.endswith(":reference")), 0)
        run.check("prefill_attn_degraded",
                  int(prefill_impl != cfg["prefill_attn_impl"]), 0,
                  note=f"prefill ran {prefill_impl!r}")

    layer = {"records": [], "spans": spans, "registry": registry,
             "samples": {"loadgen_late_ms": window_late, "ttft_ms": ttft},
             "sizes": {"max_slots": sv["max_slots"]},
             "decode_context_tokens": stamps.decode_context_tokens,
             "served_sample": [served[r] for r in picks]}
    notes = {"window_s": window_s, "requests_due": len(window),
             "requests_finished": finished_in, "tokens": tokens,
             "ttft_median_ms": stats.median(ttft),
             "itl_median_ms": stats.median(itl),
             "routes": routes, "served_tokens_checked": len(gaps["served"]),
             "distinct_tokens": distinct, "backlog_at_end": backlog}
    if run.trace and prof["state"] == "done":
        trace_mod.attach(layer, profile_dir, prof["marker_ns"],
                         only=("serve_prefill", "serve_decode"))
        notes.update(trace_mod.margin_notes(layer, prof))
    return {"end_to_end": e2e, "attempted": len(window) + refused["n"],
            "failed": failed, "layer": layer,
            "compiles_in_window": compiles, "memory_peak_bytes": peak_bytes,
            "notes": notes}


def control(cell_run) -> dict:
    """The control of ``correct`` (``benchmarks/control.py``): the program
    serves the cell's traffic for its ``seconds``; then, at each position
    of the sampled prompts and served tokens, the reference reads the gap
    of the token that ``control_precision`` puts first."""
    import jax.numpy as jnp

    cfg = cell_run.config
    ref = cell_run.py("references", cfg["reference"])
    out = run(cell_run)
    weights = ref.init_weights(cfg["model"], cell_run.seed,
                               getattr(jnp, cfg["dtype"]))
    sv = cfg["serving"]
    gaps = ref.served_gaps(cfg["model"], weights,
                           out["layer"]["served_sample"],
                           sv["max_prompt_len"] + sv["max_new_tokens"],
                           quant=cfg["control_precision"])
    ctrl, prog = ref.summarise(gaps["control"]), ref.summarise(gaps["served"])
    return {"served_logit_gap": ctrl["widest"],
            "served_mean_gap": ctrl["mean"],
            "moved_share": ctrl["moved_share"],
            "program_served_logit_gap": prog["widest"],
            "program_served_mean_gap": prog["mean"],
            "program_moved_share": prog["moved_share"],
            "tokens": len(gaps["control"])}


def aot_programs(cell: dict, cfg: dict, roots, topo, struct) -> list:
    """``benchmarks/aot_check.py``: the engine's prefill and decode
    programs, lowered at full size for one described device.
    [(tag, lowered)]."""
    import json

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.engine import _serving_fns
    from paddle_tpu.serving.scheduler import ServingConfig

    m, sv = cfg["model"], cfg["serving"]
    dtype = getattr(jnp, cfg["dtype"])
    tcfg = T.TransformerConfig(
        vocab_size=m["vocab_size"], num_layers=m["num_layers"],
        num_heads=m["num_heads"], embed_dim=m["embed_dim"],
        mlp_dim=m["mlp_dim"], max_seq_len=m["max_positions"], dtype=dtype,
        remat=False, attn_impl=cfg["prefill_attn_impl"])
    scfg = ServingConfig(attn_impl=cfg["decode_attn_impl"], **sv)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    params = struct(jax.eval_shape(
        lambda: T.init_params(tcfg, jax.random.key(0))), one)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    pool = jax.ShapeDtypeStruct(
        (m["num_layers"], m["num_heads"], sv["num_pages"], sv["page_size"],
         m["head_dim"]), dtype, sharding=one)
    key = struct(jax.eval_shape(lambda: jax.random.key(0)), one)

    def arr(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    nb, t, s, mp = (sv["prefill_batch"], sv["max_prompt_len"],
                    sv["max_slots"], scfg.max_pages_per_seq)
    prefill, _, decode = _serving_fns(tcfg, scfg.attn_impl, (2, 3))
    print(json.dumps({"parameters": n, "pool_GB_each":
                      2 * int(np.prod(pool.shape)) / 1e9}), flush=True)
    return [
        (f"{cell['name']}: prefill {nb} x {t}", prefill.lower(
            params, key, pool, pool, arr((nb, t)), arr((nb,)),
            arr((nb, mp)), arr((nb,)), arr((nb,), jnp.float32))),
        (f"{cell['name']}: decode, {s} slots", decode.lower(
            params, key, pool, pool, arr((s,)), arr((s,)), arr((s,)),
            arr((s, mp)), arr((s,)), arr((s,)), arr((s,), jnp.float32)))]
