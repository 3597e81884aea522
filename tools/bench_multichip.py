"""ICI scaling harness (VERDICT r4 #7): run the dp/sp/tp/pp parallelism
grid on WHATEVER mesh exists and emit a per-step compute/collective
split per configuration.

The reference's analog is its 4-GPU scaling tables
(``benchmark/README.md:68-83``); here the same question — "what does
adding chips buy, and what does communication cost" — is answered with
jax.sharding meshes + XLA collectives instead of NCCL.

Today (single chip / no pod) the grid runs on a virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python tools/bench_multichip.py

On a pod host the SAME command (no flags) lays the meshes over the real
chips and the split rides the profiler's device-side op durations:

    python tools/bench_multichip.py --steps 20 --layers 12 --embed 1024

Timing sources, best available first: device-side chrome-trace op
durations (collective vs compute classified by HLO op name), else
wall-clock totals with the collective INVENTORY from the compiled HLO
text — so the harness degrades gracefully on backends whose profiler
lacks per-op rows, and the collective census is exact either way.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

# HLO op-name prefixes that are cross-device communication
# (partition-id/replica-id are device-LOCAL and deliberately excluded)
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)


def _is_collective(name: str) -> bool:
    s = name.lower()
    return any(p in s for p in COLLECTIVE_PREFIXES)


def grid_for(n: int) -> list[dict]:
    """The parallelism configs that fit an n-device world."""
    cfgs = [{"name": "dp%d" % n, "kind": "transformer",
             "mesh": {"data": n}}]
    if n >= 4:
        cfgs.append({"name": "dp%d_tp2" % (n // 2), "kind": "transformer",
                     "mesh": {"data": n // 2, "model": 2}})
    if n >= 8:
        cfgs.append({"name": "dp%d_sp2_tp2" % (n // 4), "kind": "transformer",
                     "mesh": {"data": n // 4, "seq": 2, "model": 2}})
        cfgs.append({"name": "tp%d" % n, "kind": "transformer",
                     "mesh": {"model": n}})
        # ZeRO weight-update sharding rows: same dp mesh, sharded
        # optimizer state (zero1) / reduce-scattered grad flow (zero2) —
        # the census should show all-reduce replaced by reduce-scatter +
        # all-gather on the zero2 row
        cfgs.append({"name": "dp%d_zero1" % n, "kind": "transformer",
                     "mesh": {"data": n}, "zero": 1})
        cfgs.append({"name": "dp%d_zero2" % n, "kind": "transformer",
                     "mesh": {"data": n}, "zero": 2})
    if n >= 2:
        cfgs.append({"name": "pp%d" % min(4, n), "kind": "pipeline",
                     "stages": min(4, n)})
    return cfgs


def _build_transformer_step(mesh_axes: dict, layers: int, embed: int,
                            seq_len: int, batch_per_replica: int,
                            zero: int = 0):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.models import transformer as T
    from paddle_tpu.optimizer import Adam

    names = tuple(mesh_axes)
    shape = tuple(mesh_axes.values())
    used = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:used]).reshape(shape)
    mesh = Mesh(devs, names)
    cfg = T.TransformerConfig(
        vocab_size=256, num_layers=layers, num_heads=4, embed_dim=embed,
        mlp_dim=embed * 4, max_seq_len=seq_len, remat=False,
        attn_impl="ring" if "seq" in names else "exact",
    )
    params = T.place_params(T.init_params(cfg, jax.random.key(0)), mesh, cfg)
    opt = Adam(learning_rate=1e-4)
    state = opt.init_tree(params)
    if zero >= 1:
        from paddle_tpu.parallel import zero as zero_mod

        state = zero_mod.shard_opt_state(
            state, params, mesh, param_specs=T.param_shardings(cfg))
    step = T.build_train_step(cfg, opt, mesh=mesh, zero=zero)
    b = batch_per_replica * mesh.shape.get("data", 1)
    ids = np.random.default_rng(0).integers(0, 256, (b, seq_len + 1))
    spec = P("data", None) if "data" in mesh.shape else P(None, None)
    ids = jax.device_put(jnp.asarray(ids), NamedSharding(mesh, spec))

    holder = {"params": params, "state": state}

    def run_once():
        holder["params"], holder["state"], loss = step(
            holder["params"], holder["state"], ids)
        return loss

    def hlo_text():
        return step.lower(holder["params"], holder["state"],
                          ids).compile().as_text()

    return run_once, mesh, hlo_text


def _build_pipeline_step(stages: int, width: int, batch: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.parallel.pipeline import pipeline_apply

    devs = np.asarray(jax.devices()[:stages]).reshape(stages)
    mesh = Mesh(devs, ("pipe",))
    r = np.random.default_rng(0)
    w = jnp.asarray(r.normal(size=(stages, width, width)).astype(np.float32) * 0.2)
    b = jnp.asarray(r.normal(size=(stages, width)).astype(np.float32) * 0.1)
    x = jnp.asarray(r.normal(size=(batch, width)).astype(np.float32))
    y = jnp.asarray(r.normal(size=(batch, width)).astype(np.float32))

    def stage_fn(p, h):
        return jnp.tanh(h @ p[0] + p[1])

    @jax.jit
    def train_step(params, x, y):
        def loss_fn(params):
            out = pipeline_apply(stage_fn, params, x, n_microbatches=4,
                                 mesh=mesh)
            return jnp.mean((out - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return jax.tree.map(lambda p, g: p - 0.01 * g, params, grads), loss

    holder = {"params": (w, b)}

    def run_once():
        holder["params"], loss = train_step(holder["params"], x, y)
        return loss

    def hlo_text():
        return train_step.lower(holder["params"], x, y).compile().as_text()

    return run_once, mesh, hlo_text


def _collective_census_from_trace(run_once, steps: int):
    """Per-op durations from a device trace, split compute/collective.
    Returns (compute_ms, collective_ms, census) or None if the backend's
    trace has no per-op rows."""
    import jax

    if jax.devices()[0].platform == "cpu":
        return None  # CPU traces carry no XLA-Ops durations; HLO census
    try:
        from xprof import profile_step
    except ImportError:
        return None
    try:
        rows, _ = profile_step(run_once, steps=steps, top=0)
    except Exception as e:
        print(f"bench_multichip: trace census unavailable ({e}); "
              f"falling back to the HLO census", file=sys.stderr)
        return None
    if not rows:
        return None
    comp = coll = 0.0
    census: dict[str, float] = {}
    for r in rows:
        # dur_us is the CROSS-step total; r["ms"] is per-step
        ms = r.get("ms", r["dur_us"] / 1000.0 / max(steps, 1))
        name = r.get("name", "")
        if _is_collective(name):
            coll += ms
            key = name.split(".")[0].split("-start")[0].split("-done")[0]
            census[key] = census.get(key, 0.0) + ms
        else:
            comp += ms
    if comp + coll <= 0.0:
        return None  # backend trace had no usable per-op durations
    return comp, coll, census


def _collective_census_from_hlo(hlo_text_fn) -> dict[str, int]:
    """STATIC collective op inventory from the compiled HLO text (works
    on every backend).  These are program-text counts, not per-step
    execution counts: an op inside a while/fori loop body appears once
    here but executes once per iteration (e.g. pipeline_apply's permutes
    run ~n_microbatches+n_stages-1 times per step).  Per-step EXECUTION
    time comes from the trace split where available."""
    import re

    try:
        text = hlo_text_fn()
    except Exception as e:
        print(f"bench_multichip: compiled HLO text unavailable ({e}); "
              f"no static collective census", file=sys.stderr)
        return {}
    # HLO op syntax: `%name = TYPE all-reduce(...)` (TYPE may be a long
    # tuple); match the opcode immediately before its operand paren —
    # operand REFERENCES (%all-reduce.30) don't match because they carry
    # an id suffix before the paren
    # async collectives appear as -start/-done PAIRS on TPU; count each
    # op once by matching only the base or -start form
    pat = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|"
                     r"collective-permute|all-to-all)"
                     r"(?:-start)?\(")
    census: dict[str, int] = {}
    for mt in pat.finditer(text):
        k = mt.group(1)
        census[k] = census.get(k, 0) + 1
    return census


def bench_config(cfg: dict, steps: int, layers: int, embed: int,
                 seq_len: int, batch_per_replica: int) -> dict:
    import jax

    if cfg["kind"] == "pipeline":
        run_once, mesh, hlo_text = _build_pipeline_step(
            cfg["stages"], width=embed, batch=8 * cfg["stages"])
    else:
        run_once, mesh, hlo_text = _build_transformer_step(
            cfg["mesh"], layers, embed, seq_len, batch_per_replica,
            zero=cfg.get("zero", 0))

    loss = run_once()  # compile
    float(np.asarray(loss).reshape(-1)[0])
    t0 = time.monotonic()
    for _ in range(steps):
        loss = run_once()
    float(np.asarray(loss).reshape(-1)[0])  # fence: the readback waits
    wall_ms = (time.monotonic() - t0) * 1000.0 / steps

    row = {
        "config": cfg["name"],
        "mesh": cfg.get("mesh") or {"pipe": cfg.get("stages")},
        "devices": int(np.prod(list((cfg.get("mesh")
                                     or {"p": cfg.get("stages")}).values()))),
        "wall_ms_per_step": round(wall_ms, 3),
        "loss": float(np.asarray(loss).reshape(-1)[0]),
    }
    row["collectives_hlo"] = _collective_census_from_hlo(hlo_text)
    split = _collective_census_from_trace(run_once, steps=min(steps, 5))
    if split is not None:
        comp, coll, census = split
        row["compute_ms"] = round(comp, 3)
        row["collective_ms"] = round(coll, 3)
        row["collective_pct"] = round(
            100.0 * coll / max(comp + coll, 1e-9), 1)
        row["collectives"] = {k: round(v, 3) for k, v in census.items()}
    return row


def run_grid(steps: int = 8, layers: int = 2, embed: int = 64,
             seq_len: int = 64, batch_per_replica: int = 2,
             configs: list[dict] | None = None) -> list[dict]:
    """Run the grid; returns one dict per config (also usable tiny from
    the dryrun path)."""
    import jax

    n = len(jax.devices())
    rows = []
    for cfg in (configs or grid_for(n)):
        rows.append(bench_config(cfg, steps, layers, embed, seq_len,
                                 batch_per_replica))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--embed", type=int, default=64)
    ap.add_argument("--seq_len", type=int, default=64)
    ap.add_argument("--batch_per_replica", type=int, default=2)
    args = ap.parse_args(argv)
    for row in run_grid(args.steps, args.layers, args.embed, args.seq_len,
                        args.batch_per_replica):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
