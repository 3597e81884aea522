"""Driver: a language model that generates by diffusion over blocks,
through ``serving.ServingEngine`` -- ``drivers/serve_lm.py`` itself (the
same engine, load generator, token stamps, window rules, metrics and
checks, by import), plus the one thing its ``(prompt, tokens)`` cannot
carry: the ORDER in which a block's positions were unmasked.

The plain reference holds every served token against its own logits at
the denoising step that unmasked it, the block in the state it had then
(``references/sdar.served_gaps``), and takes the served order as given:
with drawn weights the confidence ranking flips on rounding, as the
largest logit does.  The engine's account of that order is
``RequestResult.trail`` (``_Active.trail`` while a request is resident);
this driver keeps it beside the token stamps and hands it to the
reference with each sampled request.  Nothing else differs, so a number
of this cell means what it means in the other serve cells.
"""

from __future__ import annotations

import types

import numpy as np


class _Run:
    """The ``Run`` that ``serve_lm`` is handed: the real one, except that
    the token stamps also keep each request's trail and the reference's
    ``served_gaps`` gets it back with the request."""

    def __init__(self, run):
        self._run = run
        self.trails: dict = {}      # request id -> (prompt, generated, trail)

    def __getattr__(self, name):
        return getattr(self._run, name)

    def py(self, kind: str, name: str):
        mod = self._run.py(kind, name)
        if kind == "drivers" and name == "serve_engine":
            return types.SimpleNamespace(
                GRACE_S=mod.GRACE_S, _Stamps=self._stamps(mod._Stamps))
        if kind == "references":
            return self._reference(mod)
        return mod

    def _stamps(self, base):
        trails = self.trails

        class Stamps(base):
            def __init__(self, scheduler):
                super().__init__(scheduler)
                stamped = scheduler.append_token

                def kept(a, token):
                    stamped(a, token)
                    if a.request.id not in trails:
                        # the lists grow in place: read when the run is over
                        trails[a.request.id] = (a.request.prompt,
                                                a.generated, a.trail)

                scheduler.append_token = kept

        return Stamps

    def _reference(self, ref):
        def served_gaps(m, weights, served, pad_to, quant=None):
            by_tokens = {(tuple(p), tuple(g)): t
                         for p, g, t in self.trails.values()}
            return ref.served_gaps(
                m, weights, [(p, t, by_tokens[tuple(p), tuple(t)])
                             for p, t in served], pad_to, quant=quant)

        return types.SimpleNamespace(
            init_weights=ref.init_weights, program_tree=ref.program_tree,
            summarise=ref.summarise, served_gaps=served_gaps)


def run(run) -> dict:
    return run.py("drivers", "serve_lm").run(_Run(run))


def control(cell_run) -> dict:
    """``benchmarks/control.py``: as ``serve_lm.control``, the lower
    precision's first token read at each served position in the state the
    block had at the step that unmasked it."""
    return cell_run.py("drivers", "serve_lm").control(_Run(cell_run))


def aot_programs(cell: dict, cfg: dict, roots, topo, struct) -> list:
    """``benchmarks/aot_check.py``: every member of the prefill ladder and
    the block pass, lowered at full size for one described device, with
    the pools of ``kv_pool_shape`` at the cache's heads.
    [(tag, lowered)]."""
    import json

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import runner
    from paddle_tpu.models import transformer as T
    from paddle_tpu.ops.pallas.paged_attention import kv_pool_shape
    from paddle_tpu.serving.engine import _serving_fns
    from paddle_tpu.serving.scheduler import ServingConfig, prefill_rows

    lm = runner.load_py("drivers", "serve_lm", roots)
    sv = cfg["serving"]
    dtype = getattr(jnp, cfg["dtype"])
    tcfg = lm._program_config(cfg, T)
    scfg = ServingConfig(attn_impl=cfg["decode_attn_impl"], **sv)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    params = struct(jax.eval_shape(
        lambda: T.init_params(tcfg, jax.random.key(0))), one)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    pool = jax.ShapeDtypeStruct(kv_pool_shape(
        tcfg.cache_layers, tcfg.kv_heads, sv["num_pages"], sv["page_size"],
        tcfg.head_dim), dtype, sharding=one)
    key = struct(jax.eval_shape(lambda: jax.random.key(0)), one)

    def arr(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    t, s, mp = sv["max_prompt_len"], sv["max_slots"], scfg.max_pages_per_seq
    bl = tcfg.block_len
    prefill, _, decode = _serving_fns(tcfg, scfg.attn_impl, (2, 3),
                                      scfg.unmask_policy)
    print(json.dumps({"parameters": n, "pool_GB_each":
                      2 * int(np.prod(pool.shape)) / 1e9}), flush=True)
    out = [(f"{cell['name']}: prefill {nb} x {t}", prefill.lower(
        params, key, pool, pool, arr((nb, t)), arr((nb,)), arr((nb, mp)),
        arr((nb,)), arr((nb,), jnp.float32), arr((nb,)), {}))
        for nb in prefill_rows(sv["prefill_batch"])]
    return out + [
        (f"{cell['name']}: block pass, {s} slots x {bl}", decode.lower(
            params, key, pool, pool, arr((s, 2 * bl + 1)), arr((s,)),
            arr((s,)), arr((s, mp)), arr((s,)), arr((s,)),
            arr((s,), jnp.float32), {}))]
