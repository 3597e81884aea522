#!/usr/bin/env python3
"""First run on the chip: drive the main paths once and check the results.

    python chip_smoke.py                # one chip: device, train, serve, kernels
    python chip_smoke.py --chips 4      # four chips: DP / ZeRO-2 training + a
                                        #   four-replica serving fleet, nothing else
    python chip_smoke.py --only models  # two steps of each other model family

Phases (each fails the run; nothing carries on past a failed phase):

- ``device``   jax must list a TPU — there is no CPU continuation — and
               ``block_until_ready`` must really wait for the device.
- ``train``    ResNet-50, 224x224x3, 1000 classes, batch 128, bf16 compute on
               f32 master parameters, Momentum, through
               ``paddle.trainer.SGD(...).train(reader=..., event_handler=...)``
               on a seeded synthetic reader.  Loss finite on every step,
               parameters changed, and the Pallas kernels counted in the
               program the trainer compiled.
- ``serve``    ``ServingEngine`` at the 124M widths (12 layers, 768 wide, 12
               heads, vocab 50257): 8 requests through ``submit()`` /
               ``results()`` — scheduler, paged KV cache, flash prefill, the
               Pallas paged-decode kernel — and the greedy tokens compared
               with the same engine on reference attention.
- ``kernels``  every Pallas kernel a production route can reach, called with
               ``interpret=False`` at its bench shape, forward and backward,
               against its ``*_reference`` twin on the same chip.
- ``models``   (not in the default run) two train steps each of the LSTM, NMT,
               CTR, CRNN and 124M-transformer models.
- ``multichip`` (``--chips 4`` only) see above.

The LAST stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
everything else is printed before it.  Exit code 0 only when ``ok``.
One process, one chip: the script starts no child process.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import re
import sys
import time
import traceback
from typing import Callable


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str = "") -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# sizes — the real ones; tests/test_chip_smoke.py passes toy ones
# ---------------------------------------------------------------------------


def _resnet50_cost():
    from paddle_tpu.models import image

    return image.resnet_cost(depth=50)[0]


@dataclasses.dataclass(frozen=True)
class Sizes:
    seed: int = 0
    # train: ResNet-50 at bs128, 224x224x3, 1000 classes
    train_cost: Callable = _resnet50_cost
    train_image_dim: int = 224 * 224 * 3
    train_classes: int = 1000
    train_batch: int = 128
    train_steps: int = 6            # the first one pays the compile
    # serve (and the models phase's transformer): the 124M widths
    vocab: int = 50257
    layers: int = 12
    heads: int = 12
    embed: int = 768
    mlp: int = 3072
    max_seq_len: int = 2048
    attn_block: int = 1024
    slots: int = 8
    page_size: int = 16
    num_pages: int = 2048
    max_prompt_len: int = 1024
    prompt_lens: tuple = (100, 900)
    new_tokens: int = 32
    requests: int = 8
    # a greedy-token mismatch must come from a near-tie: the two candidates'
    # logits (std ~1 with these random weights) closer than this
    serve_logit_tol: float = 0.05
    # kernels: case -> shape tuple (_kernel_cases() gives each its meaning)
    kernel_shapes: dict = dataclasses.field(default_factory=lambda: dict(
        lstm_seq=(256, 100, 512),               # B, T, D   (LSTM h512 bs256)
        lstm_seq_fi=(256, 100, 128, 512),       # B, T, E, D
        bilstm_seq=(512, 24, 256, 64),          # CRNN bs512 columns
        gru_seq=(512, 32, 512),                 # NMT bs512 (2 batch blocks)
        gru_seq_fi=(512, 32, 512, 512),
        bigru_seq=(512, 32, 512, 512),
        ctc_loss_fused=(512, 24, 27, 5),        # B, T, V, L  (CRNN bs512)
        ctc_loss_fused_logits=(512, 48, 96, 12),  # warp-ctc form, wider
        ctc_greedy_decode_fused=(512, 24, 27),
        flash_attention=(8, 1024, 12, 64),      # B, T, H, D
        ragged_paged_attention=(8, 12, 64, 2048, 16, 66),  # B,H,D,P,ps,maxp
        # the benchmark's serve cells (bf16 pools): gpt2-large, ouro-2.6b,
        # the caches of few heads at their longer blocks (32 and 64 page
        # slots a grid step): sdar-30b's 4 heads, zaya1-8b's 2, and a
        # float32 cache of 32 heads, whose block the VMEM budget cuts to 4
        ragged_paged_attention_gpt2l=(24, 20, 64, 1537, 16, 64),
        ragged_paged_attention_ouro=(8, 16, 128, 145, 16, 18),
        ragged_paged_attention_sdar=(64, 4, 128, 3073, 16, 48),
        ragged_paged_attention_zaya=(64, 2, 128, 8193, 16, 128),
        ragged_paged_attention_vmem=(4, 32, 128, 129, 16, 32),
        # the writing form (``decode_attention``) runs at the gpt2l, ouro
        # and zaya shapes above and at phi-4-mini-flash's ring: 64 slots'
        # runs of 32 pages, 20 K/V heads of 64 under 40 query heads
        decode_attention_ring=(64, 20, 64, 2049, 16, 32),
        # a one-row prefill pass of solar-open2-250b's KDA layer
        kda_prefill=(1, 4096, 64, 64),          # B, T, H, chunk  (D = 128)
        # a round of that pass's sorted expert product: gate | up, down
        grouped_matmul=(3072, 40, 4096, 1280),  # rows, experts, K, N
        grouped_matmul_down=(3072, 40, 1280, 4096),
        # a Mamba-2 layer of nemotron-3-nano's decode step against its pool
        ssd_step=(6, 64, 64, 64, 128, 8),       # layers, B, H, P, N, groups
        softmax_xent=(4096, 50257),             # N, V
        conv2d_bn_act=(128, 56, 64, 64, 3, 1, 1),  # N, HW, Cin, Cout, k, s, p
        conv2d_direct=(128, 224, 3, 64, 7, 2, 3),  # the ResNet stem
        channel_stats=(128, 56, 64),            # N, HW, C
        brgemm=(1, 128 * 56 * 56, 64, 256),     # G, M, K, N  (a 1x1 conv)
        fused_sgd_update=(3, 3, 512, 512),      # a ResNet res5 filter
        fused_momentum_update=(3, 3, 512, 512),
        embedding_gather=(1000, 64, 16384),     # V, D, n   (CTR bs16384)
        embedding_scatter_add=(1000, 64, 16384),
        sparse_row_update=(1000, 64),
    ))
    # models: name -> shape tuple (the transformer's widths are serve's)
    model_shapes: dict = dataclasses.field(default_factory=lambda: dict(
        lstm=(256, 100, 512, 30000),            # B, T, hidden, vocab
        nmt=(64, 32, 512, 30000),               # B, T, width, vocab
        ctr=(16384, 10000, 1000, 8, 64, (256, 128)),  # B, wide, vocab, fields,
                                                      #   embed, hidden
        crnn=(512, 32, 96, 5, 26),              # B, H, W, label len, classes
        transformer=(16, 1024),                 # B, T
    ))
    # multichip: 3 steps each of one-device / DP / ZeRO-2 at one global batch
    multi_steps: int = 3
    # DP runs batch-norm on per-shard statistics (batch/4 samples), the
    # one-device run on the whole batch: losses agree to this, not to rounding
    multi_loss_rtol: float = 2e-2
    # replicated DP vs ZeRO-2 differ only in collective order — but the
    # loss comes out of a bf16 forward, so "equal" means within one bf16
    # ulp of it (2^-7 relative at most)
    multi_zero_rtol: float = 2.0 ** -7
    replicas: int = 4


# ---------------------------------------------------------------------------
# compile-cache accounting
# ---------------------------------------------------------------------------


class CacheCounter:
    """Counts jax's persistent-compilation-cache events in this process."""

    def __init__(self):
        import jax

        self.counts = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        key = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}


def _cache_entries(path: str) -> int:
    import os

    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------


def device_report() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(chips: int, dev: dict) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.core.place import TPUPlace
    from paddle_tpu.ops import pallas

    log(f"device: {dev}")
    check(dev["platform"] == "tpu",
          f"no TPU: jax.devices() is {jax.devices()} — this script has no "
          "CPU continuation")
    check(dev["count"] == chips,
          f"expected {chips} chip(s), jax lists {dev['count']}")
    check(TPUPlace().device() == jax.devices()[0],
          "TPUPlace().device() is not jax.devices()[0]")
    check(pallas.on_tpu() and pallas.resolve_impl("auto") == "kernel"
          and not pallas.default_interpret(),
          'impl="auto" does not resolve to the compiled kernels on this '
          "backend")
    peak = profiler.device_peak_flops()  # raises for an unknown TPU kind
    # does block_until_ready fence?  n chained 4096^3 bf16 matmuls cannot
    # finish faster than their FLOPs over the published peak
    n, m = 20, 4096
    x = jnp.ones((m, m), jnp.bfloat16)
    chain = jax.jit(lambda a: jax.lax.fori_loop(
        0, n, lambda _, c: (c @ a) * jnp.bfloat16(1.0 / m), a))
    jax.block_until_ready(chain(x))                      # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(chain(x))
    dt = time.perf_counter() - t0
    floor = n * 2 * m ** 3 / peak
    log(f"device: fence check — {n} chained {m}^3 bf16 matmuls took "
        f"{dt * 1e3:.2f} ms under block_until_ready; peak-rate floor "
        f"{floor * 1e3:.2f} ms")
    check(dt >= floor,
          f"block_until_ready returned after {dt * 1e3:.3f} ms, before "
          f"the work could have finished ({floor * 1e3:.3f} ms at peak): "
          "it does not fence on this backend")


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------


def _image_batches(sz: Sizes, steps: int):
    """``steps`` reader batches (lists of (image, label) samples) drawn
    from one seeded pool of ``train_batch`` images; labels differ per
    step."""
    import numpy as np

    rng = np.random.default_rng(sz.seed)
    pool = rng.normal(size=(sz.train_batch, sz.train_image_dim)).astype(
        np.float32)
    return [[(pool[i], int(lbl)) for i, lbl in enumerate(
        rng.integers(0, sz.train_classes, size=sz.train_batch))]
        for _ in range(steps)]


def _build_trainer(sz: Sizes, mesh=None, zero: int = 0):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import rng as prng
    from paddle_tpu.layers import base

    base.reset_name_counters()
    prng.seed(7)
    cost = sz.train_cost()
    params = paddle.parameters.create(paddle.topology.Topology(cost))
    return paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(
            momentum=0.9, learning_rate=0.1 / sz.train_batch),
        compute_dtype=jnp.bfloat16, mesh=mesh, zero=zero)


def _kernel_census(lowered_text: str) -> dict:
    """{kernel name: count} of the Pallas calls in a lowered program."""
    census: dict[str, int] = {}
    for name in re.findall(r'kernel_name = "([^"]+)"', lowered_text):
        census[name] = census.get(name, 0) + 1
    return census


def _train(trainer, batches, tag: str):
    """Run ``trainer.train`` over ``batches``; returns (losses, step
    records, seconds to the end of the first step)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import metrics as metrics_mod

    sink = metrics_mod.MemorySink()
    reg = metrics_mod.MetricsRegistry(f"chip_smoke_{tag}")
    reg.add_sink(sink)
    losses, marks = [], {}
    t0 = time.perf_counter()

    def on_event(e):
        if isinstance(e, paddle.event.EndIteration):
            marks.setdefault("first", time.perf_counter() - t0)
            losses.append(float(e.cost))
            log(f"{tag}: step {e.batch_id} loss {float(e.cost):.5f}")

    trainer.train(reader=lambda: iter(batches), num_passes=1,
                  event_handler=on_event, metrics_registry=reg)
    records = [r for r in sink.records if r.get("kind") == "step"]
    check(len(losses) == len(batches),
          f"{tag}: {len(losses)} steps ran, expected {len(batches)}")
    check(bool(np.all(np.isfinite(losses))),
          f"{tag}: non-finite loss in {losses}")
    return losses, records, marks["first"]


def phase_train(sz: Sizes, cache: CacheCounter) -> dict:
    import numpy as np

    from paddle_tpu.ops import pallas

    before = cache.snapshot()
    trainer = _build_trainer(sz)
    names = list(trainer.parameters.names())
    start = {n: np.array(trainer.parameters[n]) for n in names}
    batches = _image_batches(sz, sz.train_steps)
    with pallas.capture_routes() as routes:
        losses, records, first_s = _train(trainer, batches, "train")
    moved = sum(bool(np.any(np.asarray(trainer.parameters[n]) != start[n]))
                for n in names)
    check(moved > 0, "train: no parameter changed")
    stamp = {bool(r.get("fused_kernels")) for r in records}
    census = _kernel_census(trainer.lower_train_step(batches[0]).as_text())
    routes = {f"{op}:{path}": n for (op, path), n in sorted(routes.items())}
    log(f"train: {len(losses)} steps through SGD.train, "
        f"{moved}/{len(names)} parameters changed, compile + first step "
        f"{first_s:.1f} s, compile cache {cache.since(before)}")
    log(f"train: routing decisions while tracing {routes}")
    log(f"train: Pallas kernels in the lowered step {census}; step records "
        f"stamp fused_kernels={sorted(stamp)}")
    if pallas.on_tpu():
        check(stamp == {True}, "train: step records do not stamp "
                               "fused_kernels=True on a TPU")
        check(census.get("tpp_conv", 0) + census.get("tpp_brgemm", 0) > 0,
              "train: no TPP conv+BN+ReLU kernel (tpu_custom_call) in the "
              "step the trainer compiled")
        check(not any(k.endswith(":reference") for k in routes),
              f"train: a kernel entry resolved to its reference: {routes}")
        # the fused momentum update is routed only under the explicit
        # ZeRO-2 lowering (trainer/step.py), i.e. on a multi-chip mesh:
        # the --chips 4 phase shows it; `kernels` runs it directly
        log("train: fused momentum kernel in this one-device step: "
            f"{'_mom_kernel' in census} (routed under ZeRO-2 only)")
    return {"losses": losses, "first_step_s": first_s, "kernels": census}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def _serve_model(sz: Sizes):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=sz.vocab, num_layers=sz.layers, num_heads=sz.heads,
        embed_dim=sz.embed, mlp_dim=sz.mlp, max_seq_len=sz.max_seq_len,
        dtype=jnp.float32, remat=False, attn_impl="flash",
        attn_block_size=sz.attn_block)
    return cfg, T.init_params(cfg, jax.random.key(sz.seed))


def _serving_config(sz: Sizes, attn_impl: str = "auto"):
    from paddle_tpu.serving.scheduler import ServingConfig

    return ServingConfig(
        max_slots=sz.slots, page_size=sz.page_size, num_pages=sz.num_pages,
        max_prompt_len=sz.max_prompt_len, max_new_tokens=sz.new_tokens,
        seed=sz.seed, attn_impl=attn_impl)


def _prompts(sz: Sizes) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(sz.seed + 1)
    lo, hi = sz.prompt_lens
    return [rng.integers(0, sz.vocab, size=int(n)).tolist()
            for n in rng.integers(lo, hi + 1, size=sz.requests)]


def _serve(server, prompts, sz: Sizes) -> list[list[int]]:
    """Submit every prompt, drive the loop to idle, return the generated
    tokens in submission order (an engine and a fleet router share this
    surface)."""
    ids = [server.submit(p, max_new_tokens=sz.new_tokens) for p in prompts]
    server.run_until_idle()
    got = {r.id: r for r in server.results()}
    check(sorted(got) == sorted(ids),
          f"serve: results for {sorted(got)}, submitted {sorted(ids)}")
    for r in got.values():
        check(len(r.tokens) == sz.new_tokens and r.finish_reason == "length",
              f"serve: request {r.id} ended {r.finish_reason!r} after "
              f"{len(r.tokens)} tokens")
        check(all(0 <= t < sz.vocab for t in r.tokens),
              f"serve: request {r.id} produced ids outside the vocabulary")
    return [list(got[i].tokens) for i in ids]


def _compare_tokens(sz: Sizes, cfg, params, prompts, got, want,
                    tag: str) -> int:
    """``got`` vs ``want`` greedy tokens per request.  A mismatch passes
    only as a near-tie: at the first differing position the exact-
    attention logits of the two candidates are within
    ``sz.serve_logit_tol``.  Returns how many requests diverged."""
    import dataclasses as dc

    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import transformer as T

    exact = dc.replace(cfg, attn_impl="exact")
    width = sz.max_prompt_len + sz.new_tokens
    diverged = 0
    for i, (a, b) in enumerate(zip(got, want)):
        if a == b:
            continue
        diverged += 1
        pos = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
        ctx = list(prompts[i]) + a[:pos]
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(ctx)] = ctx
        logits, _, _ = T.forward_prefill(
            exact, params, jnp.asarray(ids), jnp.asarray([len(ctx)]))
        logits = np.asarray(logits[0], np.float32)
        gap = abs(float(logits[a[pos]]) - float(logits[b[pos]]))
        log(f"{tag}: request {i} first differs at new token {pos} "
            f"({a[pos]} vs {b[pos]}); exact-attention logits "
            f"{logits[a[pos]]:.5f} vs {logits[b[pos]]:.5f}, gap {gap:.5f}, "
            f"tolerance {sz.serve_logit_tol}")
        check(gap <= sz.serve_logit_tol,
              f"{tag}: request {i} token {pos} differs with a logit gap of "
              f"{gap:.5f} > {sz.serve_logit_tol}: not a near-tie")
    log(f"{tag}: {len(got) - diverged}/{len(got)} requests identical, "
        f"{diverged} diverged at a near-tie")
    return diverged


def phase_serve(sz: Sizes) -> dict:
    import dataclasses as dc

    from paddle_tpu import metrics as metrics_mod
    from paddle_tpu.ops import pallas
    from paddle_tpu.serving.engine import ServingEngine

    cfg, params = _serve_model(sz)
    prompts = _prompts(sz)
    log(f"serve: {sz.layers} layers x {sz.embed} wide, {sz.heads} heads, "
        f"vocab {sz.vocab}; {sz.slots} slots, {sz.num_pages} pages of "
        f"{sz.page_size}; prompts {[len(p) for p in prompts]}, "
        f"{sz.new_tokens} new tokens each")
    out = {}
    for tag, ecfg, impl in (
            ("kernel", cfg, "auto"),
            ("reference", dc.replace(cfg, attn_impl="exact"), "reference")):
        t0 = time.perf_counter()
        eng = ServingEngine(ecfg, params, _serving_config(sz, impl),
                            registry=metrics_mod.MetricsRegistry(
                                f"chip_smoke_serve_{tag}"))
        with pallas.capture_routes() as routes:
            out[tag] = _serve(eng, prompts, sz)
        routes = {f"{op}:{path}": n for (op, path), n in routes.items()}
        log(f"serve[{tag}]: prefill attention {eng.prefill_attn_impl!r}, "
            f"decode routes {routes}, {len(prompts)} requests in "
            f"{time.perf_counter() - t0:.1f} s (compiles included)")
        if tag == "kernel" and pallas.on_tpu():
            check(eng.prefill_attn_impl == "flash",
                  "serve: prefill did not run flash attention on a TPU")
            check(routes == {"ragged_paged_attention:kernel": 1},
                  f"serve: decode did not route the Pallas paged-attention "
                  f"kernel: {routes}")
        del eng  # frees this engine's page pools before the next one
        gc.collect()
    diverged = _compare_tokens(sz, cfg, params, prompts, out["kernel"],
                               out["reference"], "serve")
    log(f"serve: first request's tokens {out['kernel'][0]}")
    return {"diverged": diverged}


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

# Tolerances, from the arithmetic each kernel does (every bound is on
# max|kernel - reference| / max|reference| per output / gradient leaf):
F32_TOL = 2e-4      # f32 io, no dots or true-f32 (HIGHEST) in-kernel dots
MXU_TOL = 1e-2      # f32 io, in-kernel dots at the MXU's default precision
BF16_TOL = 2e-2     # bf16 io, f32 accumulation: one output rounding
# f32 log-space alpha/beta recursions over 2T steps: eps * |log-lik| *
# sqrt(2T) is ~3e-4 at T=48, V=96 (|log-lik| ~ T ln V); 3x headroom
CTC_TOL = 1e-3


def bf16_seq_tol(shape) -> float:
    """bf16 state carried through T recurrent steps (shape = (B, T, ...)):
    a half-ulp (2^-8) rounding per step, random-walking over T steps,
    with 2x headroom — 7.8e-2 at T=100, 4.4e-2 at T=32."""
    return 2 * 2.0 ** -8 * shape[1] ** 0.5


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel-vs-reference comparison.  ``make(shape, key)`` builds
    the arguments on the device; ``kernel(interpret)`` / ``reference``
    return callables over them; ``diff`` are the argnums differentiated
    for the backward check (empty: forward only); ``tol`` (a number or
    a function of the shape) bounds ``max|k - r| / max|r|`` of every
    output and gradient leaf (integer leaves must be equal)."""

    name: str
    make: Callable
    kernel: Callable
    reference: Callable
    diff: tuple = ()
    tol: object = F32_TOL
    shape_key: str = ""


def _kernel_cases() -> list[KernelCase]:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import kda, mamba2
    from paddle_tpu.ops.pallas import ctc, gru, lstm
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import kda as kda_kernel
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_reference)
    from paddle_tpu.ops.pallas import softmax_xent as sx
    from paddle_tpu.ops.pallas import tpp

    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(key, i, shape, dtype=f32, scale=1.0):
        return (jax.random.normal(jax.random.fold_in(key, i), shape, f32)
                * scale).astype(dtype)

    def ragged_mask(key, b, t):
        lens = jax.random.randint(jax.random.fold_in(key, 99), (b,),
                                  max(t // 2, 1), t + 1)
        return (jnp.arange(t)[None, :] < lens[:, None]).astype(f32)

    # -- recurrences (bf16 io, the mixed-precision policy's dtype) ------------
    def lstm_weights(key, i, e, d):
        return (normal(key, i, (e, 4 * d), bf16, e ** -0.5),        # w_x
                normal(key, i + 1, (4 * d,), f32, 0.1),             # bias
                normal(key, i + 2, (d, 4 * d), bf16, d ** -0.5),    # w_h
                normal(key, i + 3, (3, d), bf16, 0.1))              # peephole

    def make_lstm(shape, key):
        b, t, d = shape
        _, _, w_h, peep = lstm_weights(key, 10, d, d)
        return (normal(key, 0, (b, t, 4 * d), bf16, 0.5),
                ragged_mask(key, b, t), w_h, peep,
                normal(key, 1, (b, d), bf16, 0.2), normal(key, 2, (b, d)))

    def make_lstm_fi(shape, key):
        b, t, e, d = shape
        return (normal(key, 0, (b, t, e), bf16), ragged_mask(key, b, t),
                *lstm_weights(key, 10, e, d),
                normal(key, 1, (b, d), bf16, 0.2),
                normal(key, 2, (b, d), f32, 0.2))

    def make_bilstm(shape, key):
        b, t, e, d = shape
        z16, z32 = jnp.zeros((b, d), bf16), jnp.zeros((b, d), f32)
        return (normal(key, 0, (b, t, e), bf16), ragged_mask(key, b, t),
                *lstm_weights(key, 10, e, d), *lstm_weights(key, 20, e, d),
                z16, z32, z16, z32)

    def gru_weights(key, i, e, d):
        return (normal(key, i, (e, 3 * d), bf16, e ** -0.5),        # w_x
                normal(key, i + 1, (3 * d,), f32, 0.1),             # bias
                normal(key, i + 2, (d, 2 * d), bf16, d ** -0.5),    # w_h
                normal(key, i + 3, (d, d), bf16, d ** -0.5))        # w_hc

    def make_gru(shape, key):
        b, t, d = shape
        _, _, w_h, w_hc = gru_weights(key, 10, d, d)
        return (normal(key, 0, (b, t, 3 * d), bf16, 0.5),
                ragged_mask(key, b, t), w_h, w_hc,
                normal(key, 1, (b, d), bf16, 0.2))

    def make_gru_fi(shape, key):
        b, t, e, d = shape
        return (normal(key, 0, (b, t, e), bf16), ragged_mask(key, b, t),
                *gru_weights(key, 10, e, d),
                normal(key, 1, (b, d), bf16, 0.2))

    def make_bigru(shape, key):
        b, t, e, d = shape
        z = jnp.zeros((b, d), bf16)
        return (normal(key, 0, (b, t, e), bf16), ragged_mask(key, b, t),
                *gru_weights(key, 10, e, d), *gru_weights(key, 20, e, d),
                z, z)

    # -- CTC -------------------------------------------------------------------
    def make_ctc(shape, key, normalized=True):
        b, t, v, lab = shape
        x = normal(key, 0, (b, t, v))
        if normalized:
            x = jax.nn.log_softmax(x, axis=-1)
        ilen = jax.random.randint(jax.random.fold_in(key, 1), (b,),
                                  2 * lab + 1, t + 1)
        labels = jax.random.randint(jax.random.fold_in(key, 2), (b, lab),
                                    1, v)
        llen = jax.random.randint(jax.random.fold_in(key, 3), (b,), 1,
                                  lab + 1)
        return x, ilen, labels, llen

    def make_decode(shape, key):
        b, t, v = shape
        return (jax.nn.log_softmax(normal(key, 0, (b, t, v), f32, 2.0)),
                jax.random.randint(jax.random.fold_in(key, 1), (b,), 1,
                                   t + 1))

    # -- attention -------------------------------------------------------------
    def make_flash(shape, key):
        return tuple(normal(key, i, shape, bf16) for i in range(3))

    def make_paged(shape, key, dtype=f32):
        b, h, d, pages, ps, maxp = shape
        lens = jax.random.randint(jax.random.fold_in(key, 3), (b,), 1,
                                  maxp * ps + 1)
        # each row owns its own run of pages (page 0 is the null page)
        table = 1 + jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)
        # the pools as the engine builds them, two cache layers deep; the
        # call addresses the second
        pool = pa.kv_pool_shape(2, h, pages, ps, d)
        return (normal(key, 0, (b, h, d), dtype),
                normal(key, 1, pool, dtype), normal(key, 2, pool, dtype),
                jnp.int32(1), table % pages, lens)

    def make_token(ring):
        """``decode_attention``'s arguments over ``make_paged``'s pools:
        the token of every row at its last position or, ``ring``, anywhere
        in a run that is full (so mostly not in the row's last block),
        under two query heads a K/V head."""
        def make(shape, key):
            b, h, d, _, ps, maxp = shape
            q, kc, vc, layer, table, lens = make_paged(shape, key, bf16)
            at = lens - 1
            if ring:
                q = normal(key, 4, (b, 2 * h, d), bf16)
                lens = jnp.full((b,), maxp * ps, jnp.int32)
                at = jax.random.randint(jax.random.fold_in(key, 5), (b,), 0,
                                        maxp * ps)
            return (q, normal(key, 6, (b, h, d), bf16),
                    normal(key, 7, (b, h, d), bf16), kc, vc, layer, table,
                    at, lens)
        return make

    def make_kda(shape, key):
        b, t, h, _ = shape
        d = kda_kernel.HEAD_DIM
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True))
        # as the mixer hands them over: q and k of unit length a head,
        # decays from a softplus, steps in (0, 2); a ragged row
        return (unit(normal(key, 0, (b, t, h, d))).astype(bf16) * d ** -0.5,
                unit(normal(key, 1, (b, t, h, d))).astype(bf16),
                normal(key, 2, (b, t, h, d), bf16),
                -jnp.exp(normal(key, 3, (b, t, h, d), f32, 1.5) - 3.0),
                2 * jax.nn.sigmoid(normal(key, 4, (b, t, h))),
                jax.random.randint(jax.random.fold_in(key, 5), (b,),
                                   max(t // 2, 1), t + 1))

    def make_ssd(shape, key):
        layers, b, h, p, n, g = shape
        # the pool, a traced row, the mixer's six, and one idle slot
        return (normal(key, 0, (layers, b, h, p, n)), jnp.int32(layers - 2),
                normal(key, 1, (b, h, p), bf16),
                jax.nn.softplus(normal(key, 2, (b, h))),
                -jnp.exp(normal(key, 3, (h,))),
                normal(key, 4, (b, g, n), bf16), normal(key, 5, (b, g, n), bf16),
                normal(key, 6, (h,)), jnp.arange(b) != 1)

    def make_grouped(shape, key):
        m, g, k, n = shape
        # two thirds of the rows lie in groups, every seventh expert has none
        ids = jax.random.randint(jax.random.fold_in(key, 3), (2 * m // 3,),
                                 0, g)
        sizes = jnp.bincount(ids, length=g) * (jnp.arange(g) % 7 != 3)
        return (normal(key, 0, (m, k), bf16),
                normal(key, 1, (g, k, n), bf16, k ** -0.5),
                normal(key, 2, (g, k, n), bf16, k ** -0.5),
                sizes.astype(jnp.int32))

    def grouped(gated, impl, interp=None):
        form = dict(act=jax.nn.silu, out_dtype=bf16) if gated else {}
        return lambda rows, w, gate, sizes: gm.grouped_matmul(
            rows, w, sizes, gate if gated else None, impl=impl,
            interpret=interp, **form)

    def make_xent(shape, key):
        n, v = shape
        return (normal(key, 0, (n, v), f32, 2.0),
                jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, v))

    # -- conv / GEMM (bf16 io) --------------------------------------------------
    def make_conv(shape, key):
        n, hw, cin, cout, k, _, _ = shape
        return (normal(key, 0, (n, hw, hw, cin), bf16),
                normal(key, 1, (k, k, cin, cout), bf16,
                       (k * k * cin) ** -0.5))

    def make_cbr(shape, key):
        cout = shape[3]
        return (*make_conv(shape, key),
                1.0 + normal(key, 2, (cout,), f32, 0.1),
                normal(key, 3, (cout,), f32, 0.1),
                jnp.zeros((cout,), f32), jnp.ones((cout,), f32))

    def make_stats(shape, key):
        n, hw, c = shape
        return (normal(key, 0, (n, hw, hw, c), bf16),)

    def make_brgemm(shape, key):
        g, m, k, n = shape
        return (normal(key, 0, (g, m, k), bf16),
                normal(key, 1, (g, k, n), bf16, k ** -0.5))

    # -- optimizer updates / embedding rows (f32) -------------------------------
    def make_update(shape, key, velocity=True):
        out = (normal(key, 0, shape), normal(key, 1, shape, f32, 0.1))
        return out + ((normal(key, 2, shape, f32, 0.1),) if velocity
                      else ())

    def make_gather(shape, key):
        v, d, n = shape
        return (normal(key, 0, (v, d)),
                jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, v))

    def make_scatter(shape, key):
        v, d, n = shape
        ids = jax.random.randint(jax.random.fold_in(key, 1), (n,), -1, v)
        return normal(key, 0, (v, d)), ids, normal(key, 2, (n, d))

    def make_sparse(shape, key):
        v, d = shape
        touched = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.3,
                                       (v, 1))
        return (normal(key, 0, (v, d)),
                jnp.where(touched, normal(key, 1, (v, d)), 0.0),
                normal(key, 2, (v, d), f32, 0.1))

    def conv_kw(shape):
        return dict(stride=shape[5], padding=shape[6])

    def case(name, make, kernel, reference, diff=(), tol=F32_TOL,
             shape_key=None):
        return KernelCase(name, make, kernel, reference, diff, tol,
                          shape_key or name)

    def paged_kernel(interp, s):
        return lambda *a: pa.ragged_paged_attention(
            *a, impl="kernel", interpret=interp)

    def decode_case(cell, ring=False):
        """The decode step's write + attention in one call against the
        scatter and the oracle: the attention, then both pools whole."""
        def run(impl, interp, s):
            # the ring's: two query heads a K/V head, wide values
            form = dict(kv_heads=s[1], wide_v=True) if ring else {}
            return lambda *a: pa.decode_attention(
                *a, impl=impl, interpret=interp, **form)
        return case(
            f"decode_attention[{cell}]", make_token(ring),
            lambda interp, s: run("kernel", interp, s),
            lambda s: run("reference", None, s), tol=BF16_TOL,
            shape_key=("decode_attention_" if ring
                       else "ragged_paged_attention_") + cell)

    return [
        case("lstm_seq", make_lstm,
             lambda interp, s: lambda *a: lstm.lstm_seq(*a, False, interp,
                                                        True),
             lambda s: lambda *a: lstm.lstm_seq_reference(*a, False),
             diff=(0, 2, 3, 4, 5), tol=bf16_seq_tol),
        case("lstm_seq_fi", make_lstm_fi,
             lambda interp, s: lambda *a: lstm.lstm_seq_fi(*a, False, interp,
                                                           True),
             lambda s: lambda *a: lstm.lstm_seq_fi_reference(*a, False),
             diff=(0, 2, 3, 4, 5, 6, 7), tol=bf16_seq_tol),
        case("bilstm_seq", make_bilstm,
             lambda interp, s: lambda *a: lstm.bilstm_seq(*a, interp, True),
             lambda s: lstm.bilstm_seq_reference,
             diff=(0, 2, 3, 4, 5, 6, 7, 8, 9), tol=bf16_seq_tol),
        case("gru_seq", make_gru,
             lambda interp, s: lambda *a: gru.gru_seq(*a, False, interp,
                                                      True),
             lambda s: lambda *a: gru.gru_seq_reference(*a, False),
             diff=(0, 2, 3, 4), tol=bf16_seq_tol),
        case("gru_seq_fi", make_gru_fi,
             lambda interp, s: lambda *a: gru.gru_seq_fi(*a, False, interp,
                                                         True),
             lambda s: lambda *a: gru.gru_seq_fi_reference(*a, False),
             diff=(0, 2, 3, 4, 5, 6), tol=bf16_seq_tol),
        case("bigru_seq", make_bigru,
             lambda interp, s: lambda *a: gru.bigru_seq(*a, interp, True),
             lambda s: gru.bigru_seq_reference,
             diff=(0, 2, 3, 4, 5, 6, 7, 8, 9), tol=bf16_seq_tol),
        case("ctc_loss_fused", make_ctc,
             lambda interp, s: lambda x, il, lab, ll: ctc.ctc_loss_fused(
                 x, il, lab, ll, 0, False, "kernel", interp),
             lambda s: lambda x, il, lab, ll: ctc.ctc_loss_fused_reference(
                 x, il, lab, ll, 0, False),
             diff=(0,), tol=CTC_TOL),
        case("ctc_loss_fused[logits]",
             lambda shape, key: make_ctc(shape, key, normalized=False),
             lambda interp, s: lambda x, il, lab, ll: ctc.ctc_loss_fused(
                 x, il, lab, ll, 0, True, "kernel", interp),
             lambda s: lambda x, il, lab, ll: ctc.ctc_loss_fused_reference(
                 x, il, lab, ll, 0, True),
             diff=(0,), tol=CTC_TOL, shape_key="ctc_loss_fused_logits"),
        case("ctc_greedy_decode_fused", make_decode,
             lambda interp, s: lambda x, il: ctc.ctc_greedy_decode_fused(
                 x, il, 0, "kernel", interp),
             lambda s: lambda x, il: ctc.ctc_greedy_decode_fused_reference(
                 x, il, 0)),
        case("flash_attention", make_flash,
             lambda interp, s: lambda q, k, v: flash_attention(
                 q, k, v, True, None, 1024, 1024, interp),
             lambda s: lambda q, k, v: flash_attention_reference(
                 q, k, v, True),
             diff=(0, 1, 2), tol=BF16_TOL),
        case("ragged_paged_attention", make_paged, paged_kernel,
             lambda s: pa.ragged_paged_attention_reference, tol=MXU_TOL),
        *(case(f"ragged_paged_attention[{cell}]",
               functools.partial(make_paged, dtype=bf16), paged_kernel,
               lambda s: pa.ragged_paged_attention_reference, tol=BF16_TOL,
               shape_key=f"ragged_paged_attention_{cell}")
          for cell in ("gpt2l", "ouro", "sdar", "zaya")),
        case("ragged_paged_attention[vmem]", make_paged, paged_kernel,
             lambda s: pa.ragged_paged_attention_reference, tol=MXU_TOL,
             shape_key="ragged_paged_attention_vmem"),
        *(decode_case(cell) for cell in ("gpt2l", "ouro", "zaya")),
        decode_case("ring", ring=True),
        case("kda_prefill", make_kda,
             lambda interp, s: lambda *a: kda.kda_prefill(
                 *a, chunk=s[3], impl="kernel", interpret=interp),
             lambda s: lambda *a: kda.kda_prefill(
                 *a, chunk=s[3], impl="reference"), tol=BF16_TOL),
        case("ssd_step", make_ssd,
             lambda interp, s: lambda *a: mamba2.ssd_pool_step(
                 *a, impl="kernel", interpret=interp),
             lambda s: lambda *a: mamba2.ssd_pool_step(*a, impl="reference")),
        case("grouped_matmul", make_grouped,
             lambda interp, s: grouped(True, "kernel", interp),
             lambda s: grouped(True, "reference"), tol=BF16_TOL),
        case("grouped_matmul[down]", make_grouped,
             lambda interp, s: grouped(False, "kernel", interp),
             lambda s: grouped(False, "reference"), tol=BF16_TOL,
             shape_key="grouped_matmul_down"),
        case("softmax_xent", make_xent,
             lambda interp, s: lambda lg, tg: sx.softmax_xent(
                 lg, tg, 256, 2048, interp),
             lambda s: sx.softmax_xent_reference, diff=(0,)),
        case("conv2d_bn_act", make_cbr,
             lambda interp, s: lambda *a: tpp.conv2d_bn_act(
                 *a, True, impl="kernel", interpret=interp, **conv_kw(s)),
             lambda s: lambda *a: tpp.conv2d_bn_act_reference(
                 *a, True, **conv_kw(s)),
             diff=(0, 1, 2, 3), tol=BF16_TOL),
        case("conv2d_direct", make_conv,
             lambda interp, s: lambda x, w: tpp.conv2d_direct(
                 x, w, impl="kernel", interpret=interp, **conv_kw(s)),
             lambda s: lambda x, w: tpp.conv2d_direct_reference(
                 x, w, **conv_kw(s)),
             diff=(0, 1), tol=BF16_TOL),
        case("channel_stats", make_stats,
             lambda interp, s: lambda x: tpp.channel_stats(x, "kernel",
                                                           interp),
             lambda s: tpp.channel_stats_reference, diff=(0,),
             tol=BF16_TOL),
        case("brgemm", make_brgemm,
             lambda interp, s: lambda a, b: tpp.brgemm(
                 a, b, impl="kernel", interpret=interp),
             lambda s: tpp.brgemm_reference, tol=BF16_TOL),
        case("fused_sgd_update",
             lambda shape, key: make_update(shape, key, velocity=False),
             lambda interp, s: lambda p, g: tpp.fused_sgd_update(
                 p, g, 0.05, 1e-4, impl="kernel", interpret=interp),
             lambda s: lambda p, g: tpp.fused_sgd_update_reference(
                 p, g, 0.05, 1e-4), tol=1e-6),
        case("fused_momentum_update", make_update,
             lambda interp, s: lambda p, g, v: tpp.fused_momentum_update(
                 p, g, v, 0.05, 0.9, False, 1e-4, impl="kernel",
                 interpret=interp),
             lambda s: lambda p, g, v: tpp.fused_momentum_update_reference(
                 p, g, v, 0.05, 0.9, False, 1e-4), tol=1e-6),
        case("embedding_gather", make_gather,
             lambda interp, s: lambda t, i: tpp.embedding_gather(
                 t, i, impl="kernel", interpret=interp),
             lambda s: tpp.embedding_gather_reference, tol=0.0),
        case("embedding_scatter_add", make_scatter,
             lambda interp, s: lambda t, i, r: tpp.embedding_scatter_add(
                 t, i, r, impl="kernel", interpret=interp),
             lambda s: tpp.embedding_scatter_add_reference),
        case("sparse_row_update", make_sparse,
             lambda interp, s: lambda p, g, v: tpp.sparse_row_update(
                 p, g, v, lr=0.05, mu=0.9, weight_decay=1e-4,
                 impl="kernel", interpret=interp),
             lambda s: lambda p, g, v: tpp.sparse_row_update_reference(
                 p, g, v, lr=0.05, mu=0.9, weight_decay=1e-4), tol=1e-6),
    ]


def _tree_error(got, want) -> tuple[float, float, int]:
    """(max abs error, max error normalised by each leaf's max|want|,
    index of the leaf with that worst normalised error) over float
    leaves; integer/bool leaves must be equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    g_leaves, w_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
    check(len(g_leaves) == len(w_leaves),
          f"{len(g_leaves)} output leaves vs {len(w_leaves)} in the "
          "reference")
    worst_abs = worst_rel = 0.0
    worst_leaf = 0
    for i, (g, w) in enumerate(zip(g_leaves, w_leaves)):
        check(g.shape == w.shape, f"shape {g.shape} vs reference {w.shape}")
        if not jnp.issubdtype(w.dtype, jnp.floating):
            check(bool(np.array_equal(np.asarray(g), np.asarray(w))),
                  "integer outputs differ")
            continue
        g = np.asarray(g.astype(jnp.float32))
        w = np.asarray(w.astype(jnp.float32))
        check(bool(np.all(np.isfinite(g))), "non-finite kernel output")
        err = float(np.max(np.abs(g - w))) if g.size else 0.0
        worst_abs = max(worst_abs, err)
        rel = err / max(float(np.max(np.abs(w))), 1e-30)
        if rel > worst_rel:
            worst_rel, worst_leaf = rel, i
    return worst_abs, worst_rel, worst_leaf


def _run_kernel_case(case: KernelCase, shape, seed: int, interpret) -> dict:
    import jax
    import jax.numpy as jnp

    args = case.make(shape, jax.random.key(seed))
    kernel = case.kernel(interpret, shape)
    reference = case.reference(shape)

    def high(fn):
        # per-call precision for the oracle only: the MXU's default bf16
        # passes would put ~5e-3 noise on the reference itself, and a
        # GLOBAL "highest" breaks the kernels' in-kernel bf16 dots
        def wrapped(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return wrapped

    def weighted(fn):
        # a fixed random cotangent per output leaf (a plain sum would
        # give softmax-like outputs a zero gradient)
        def loss(*a):
            out = [x for x in jax.tree.leaves(fn(*a))
                   if jnp.issubdtype(x.dtype, jnp.floating)]
            return sum(
                jnp.sum(x.astype(jnp.float32) * jax.random.normal(
                    jax.random.key(1000 + i), x.shape, jnp.float32))
                for i, x in enumerate(out))
        return loss

    tol = case.tol(shape) if callable(case.tol) else case.tol
    row = {"kernel": case.name, "shape": shape, "tol": tol}
    row["fwd_abs"], row["fwd_rel"], row["fwd_worst"] = _tree_error(
        jax.jit(kernel)(*args), jax.jit(high(reference))(*args))
    if case.diff:
        row["bwd_abs"], row["bwd_rel"], worst = _tree_error(
            jax.jit(jax.grad(weighted(kernel), argnums=case.diff))(*args),
            jax.jit(jax.grad(weighted(high(reference)),
                             argnums=case.diff))(*args))
        row["bwd_worst"] = case.diff[worst]  # the argument it belongs to
    row["pass"] = (row["fwd_rel"] <= tol
                   and row.get("bwd_rel", 0.0) <= tol)
    return row


def phase_kernels(sz: Sizes, names=None, interpret=False) -> dict:
    """Each case: kernel (``interpret=False`` — Mosaic) vs its reference
    twin, forward and backward.  One line per kernel; every case runs so
    one call shows every fault, then the phase fails if any did."""
    from paddle_tpu.ops import rnn

    rows = []
    for case in _kernel_cases():
        if names is not None and case.name not in names:
            continue
        shape = sz.kernel_shapes[case.shape_key]
        t0 = time.perf_counter()
        try:
            row = _run_kernel_case(case, shape, sz.seed, interpret)
        except Exception as e:  # report this kernel, go on to the next
            traceback.print_exc()
            row = {"kernel": case.name, "shape": shape, "tol": float("nan"),
                   "pass": False,
                   "error": f"{type(e).__name__}: {e}"[:300]}
        row["seconds"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        fmt = lambda k: (f"{row[k]:.3e}" if k in row else "-")  # noqa: E731
        log(f"kernels: {'PASS' if row['pass'] else 'FAIL'} "
            f"{row['kernel']:<26} shape {str(shape):<34} "
            f"fwd abs {fmt('fwd_abs')} rel {fmt('fwd_rel')} | "
            f"bwd abs {fmt('bwd_abs')} rel {fmt('bwd_rel')} "
            f"(worst: d/d arg {row.get('bwd_worst', '-')}) | "
            f"tol {row['tol']:.2g} | {row['seconds']} s"
            + (f" | {row['error']}" if "error" in row else ""))
        gc.collect()
    # ops/rnn's VMEM-fit rule decides kernel vs lax.scan per shape: say
    # which way the recurrence shapes above would go in a model
    import jax.numpy as jnp

    for key, gates in (("lstm_seq", 4), ("gru_seq", 3)):
        if names is None or key in names:
            b, _, d = sz.kernel_shapes[key]
            fits = rnn._fused_fits(
                b, d, gates, jnp.zeros((d, gates * d), jnp.bfloat16))
            log(f"kernels: ops/rnn routes {key} B={b} D={d} (bf16) to the "
                f"{'kernel' if fits else 'lax.scan cell (VMEM budget)'}")
    failed = [r["kernel"] for r in rows if not r["pass"]]
    check(not failed, f"kernels: {len(failed)} of {len(rows)} failed: "
                      f"{failed}")
    log(f"kernels: {len(rows)} of {len(rows)} within tolerance")
    return {"rows": rows}


# ---------------------------------------------------------------------------
# phase: models (not in the default run)
# ---------------------------------------------------------------------------


def _topology_step(cost_fn, feed_fn, optimizer):
    """A v2-layer-API model's bf16 train step as a closure that chains its
    own state, the feed resident on the device as a placed feed is (a host
    feed would cross again on every call)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.config.topology import Topology
    from paddle_tpu.layers import base
    from paddle_tpu.trainer.step import build_train_step

    base.reset_name_counters()
    topo = Topology(cost_fn())
    specs = {s.name: s for s in topo.param_specs()}
    params = paddle.parameters.create(topo).as_dict()
    state = {"p": params, "o": optimizer.init(params, specs),
             "s": topo.init_states()}
    step = build_train_step(topo, optimizer, compute_dtype=jnp.bfloat16)
    feed = jax.device_put(feed_fn())
    key = jax.random.key(0)

    def one():
        state["p"], state["o"], state["s"], c, _ = step(
            state["p"], state["o"], state["s"], feed, key)
        return c

    return one


MODELS = ("lstm", "nmt", "ctr", "crnn", "transformer")


def phase_models(sz: Sizes, names=None) -> dict:
    """Two train steps each of the other model families, through their
    normal builders (``_topology_step`` = Topology + ``build_train_step``;
    ``transformer.build_train_step``), at ``sz.model_shapes``.  Every
    model runs; the phase fails if any did not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.lod import SequenceBatch
    from paddle_tpu.layers.data_type import (integer_value,
                                             sparse_binary_vector)
    from paddle_tpu.models import seqtoseq, transformer as T
    from paddle_tpu.models.ctr import wide_and_deep_ctr
    from paddle_tpu.models.ocr_crnn import crnn_ctc_cost
    from paddle_tpu.models.rnn import lstm_classify_cost
    from paddle_tpu.ops import pallas
    from paddle_tpu.optimizer import AdaGrad, Adam
    from paddle_tpu.reader.feeder import DataFeeder

    rng = np.random.default_rng(sz.seed)

    def seq(bs, t, vocab):
        return SequenceBatch(data=rng.integers(0, vocab, size=(bs, t)),
                             length=np.full((bs,), t, np.int32))

    def adam(lr):
        return Adam(learning_rate=lr, moment_dtype=jnp.bfloat16)

    def lstm(bs, t, hidden, vocab):
        return _topology_step(
            lambda: lstm_classify_cost(hidden, vocab),
            lambda: {"data": seq(bs, t, vocab),
                     "label": rng.integers(0, 2, size=(bs,))},
            adam(2e-3))

    def nmt(bs, t, dim, vocab):
        return _topology_step(
            lambda: seqtoseq.seqtoseq_net(
                vocab, vocab, word_vector_dim=dim, encoder_size=dim,
                decoder_size=dim),
            lambda: {k: seq(bs, t, vocab) for k in (
                "source_language_word", "target_language_word",
                "target_language_next_word")},
            adam(5e-4))

    def ctr(bs, wide_dim, vocab, fields, embed, hidden):
        types = {"wide_input": sparse_binary_vector(wide_dim)}
        types.update({f"cat_{i}": integer_value(vocab)
                      for i in range(fields)})
        types["label"] = integer_value(2)  # feed order = row order below
        wide = rng.integers(0, wide_dim, size=(bs, 3)).tolist()
        cats = rng.integers(0, vocab, size=(bs, fields)).tolist()
        labels = rng.integers(0, 2, size=(bs,)).tolist()
        batch = [(w, *c, y) for w, c, y in zip(wide, cats, labels)]
        return _topology_step(
            lambda: wide_and_deep_ctr(
                wide_dim=wide_dim, categorical_vocab_sizes=[vocab] * fields,
                embedding_size=embed, hidden_sizes=hidden)[0],
            lambda: DataFeeder(types).feed(batch),
            AdaGrad(learning_rate=1e-2))

    def crnn(bs, height, width, label_len, classes):
        return _topology_step(
            lambda: crnn_ctc_cost(image_height=height, image_width=width,
                                  num_classes=classes)[0],
            lambda: {"image": rng.normal(size=(bs, height * width)).astype(
                         np.float32),
                     "label": seq(bs, label_len, classes)},
            adam(1e-3))

    def transformer(bs, t):
        cfg = T.TransformerConfig(
            vocab_size=sz.vocab, num_layers=sz.layers, num_heads=sz.heads,
            embed_dim=sz.embed, mlp_dim=sz.mlp, max_seq_len=sz.max_seq_len,
            dtype=jnp.float32, remat=False, attn_impl="flash",
            attn_block_size=sz.attn_block)
        params = T.init_params(cfg, jax.random.key(sz.seed))
        opt = adam(1e-4)
        state = {"p": params, "o": opt.init_tree(params)}
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(bs, t + 1)))
        step = T.build_train_step(cfg, opt, compute_dtype=jnp.bfloat16)

        def one():
            state["p"], state["o"], loss = step(state["p"], state["o"], ids)
            return loss
        return one

    builders = dict(lstm=lstm, nmt=nmt, ctr=ctr, crnn=crnn,
                    transformer=transformer)
    rows = []
    for name in names or MODELS:
        shape = sz.model_shapes[name]
        t0 = time.perf_counter()
        row = {"model": name, "shape": shape}
        try:
            with pallas.capture_routes() as routes:
                one = builders[name](*shape)
                losses = [float(np.asarray(one())) for _ in range(2)]
            row.update(losses=losses, ok=bool(np.all(np.isfinite(losses))),
                       routes={f"{op}:{path}": n
                               for (op, path), n in sorted(routes.items())})
            del one
        except Exception as e:  # report this model, go on to the next
            traceback.print_exc()
            row.update(ok=False, error=f"{type(e).__name__}: {e}"[:300])
        row["seconds"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        log(f"models: {'PASS' if row['ok'] else 'FAIL'} {name}: {row}")
        gc.collect()
    failed = [r["model"] for r in rows if not r["ok"]]
    check(not failed, f"models: did not take a step: {failed}")
    return {"rows": rows}


# ---------------------------------------------------------------------------
# phase: multichip (--chips 4)
# ---------------------------------------------------------------------------


def _collectives(compiled_text: str) -> dict:
    return {op: len(re.findall(rf"\s{op}(?:-start)?\(", compiled_text))
            for op in ("all-reduce", "reduce-scatter", "all-gather")}


def _leaf_devices(tree) -> set:
    import jax

    return set().union(*(leaf.devices() for leaf in jax.tree.leaves(tree)
                         if hasattr(leaf, "devices")))


def phase_multichip(sz: Sizes) -> dict:
    import jax
    import numpy as np

    from paddle_tpu import metrics as metrics_mod
    from paddle_tpu.parallel.mesh import MeshContext, make_mesh
    from paddle_tpu.serving.fleet import build_local_fleet

    n = len(jax.devices())
    check(n >= 2, f"multichip needs several devices, jax lists {n}")
    batches = _image_batches(sz, sz.multi_steps)
    runs = {}
    for tag, mesh, zero in (
            ("one-device", MeshContext(make_mesh(
                {"data": 1}, devices=jax.devices()[:1])), 0),
            (f"dp{n}", None, 0),          # SGD's default mesh: all on data
            (f"dp{n}-zero2", None, 2)):
        trainer = _build_trainer(sz, mesh=mesh, zero=zero)
        losses, _, first_s = _train(trainer, batches, tag)
        lowered = trainer.lower_train_step(batches[0])
        colls = _collectives(lowered.compile().as_text())
        census = _kernel_census(lowered.as_text())
        state_devs = _leaf_devices(trainer._opt_state)
        big = max(jax.tree.leaves(trainer._opt_state), key=lambda x: x.size)
        shard_shapes = (tuple(big.shape),
                        tuple(big.addressable_shards[0].data.shape))
        runs[tag] = {"losses": losses, "collectives": colls,
                     "kernels": census, "state_devices": len(state_devs)}
        log(f"{tag}: mesh {dict(trainer.mesh.mesh.shape)}, losses {losses}, "
            f"compile + first step {first_s:.1f} s, collectives {colls}, "
            f"kernels {census}, optimizer state on {len(state_devs)} "
            f"device(s), largest slot (global, shard) shapes {shard_shapes}")
        del trainer, lowered
        gc.collect()
    one, dp, z2 = (runs[k] for k in ("one-device", f"dp{n}", f"dp{n}-zero2"))
    np.testing.assert_allclose(dp["losses"], one["losses"],
                               rtol=sz.multi_loss_rtol)
    np.testing.assert_allclose(z2["losses"], dp["losses"],
                               rtol=sz.multi_zero_rtol)
    log(f"multichip: DP and ZeRO-2 losses match one device to "
        f"rtol {sz.multi_loss_rtol} and each other to {sz.multi_zero_rtol}")
    check(one["state_devices"] == 1 and dp["state_devices"] == n
          and z2["state_devices"] == n,
          "multichip: optimizer state is not on every device of its mesh")
    check(dp["collectives"]["all-reduce"] > 0,
          "multichip: no all-reduce in the compiled DP step")
    check(z2["collectives"]["reduce-scatter"] > 0
          and z2["collectives"]["all-gather"] > 0,
          "multichip: no reduce-scatter/all-gather in the ZeRO-2 step")

    # -- the serving fleet: one replica per device ---------------------------
    cfg, params = _serve_model(sz)
    prompts = _prompts(sz)
    scfg = _serving_config(sz)
    want = _serve(build_local_fleet(
        cfg, params, scfg, n=1,
        registry=metrics_mod.MetricsRegistry("chip_smoke_fleet1")),
        prompts, sz)
    gc.collect()
    fleet = build_local_fleet(
        cfg, params, scfg, n=sz.replicas,
        registry=metrics_mod.MetricsRegistry("chip_smoke_fleet"))
    placed = []
    for rep in fleet.replicas:
        eng = rep.engine
        devs = (_leaf_devices(eng.params)
                | _leaf_devices((eng.cache.k, eng.cache.v)))
        check(devs == {eng.device},
              f"replica {rep.index}: weights/KV pools on {devs}, engine "
              f"device {eng.device}")
        placed.append(eng.device)
    log(f"fleet: {len(placed)} replicas on {placed}")
    check(len(set(placed)) == min(sz.replicas, n),
          f"fleet: replicas share devices: {placed}")
    got = _serve(fleet, prompts, sz)
    served = [rep.probe().progress for rep in fleet.replicas]
    log(f"fleet: engine steps per replica {served}")
    check(all(v > 0 for v in served),
          f"fleet: a replica served nothing: {served}")
    diverged = _compare_tokens(sz, cfg, params, prompts, got, want, "fleet")
    return {"runs": runs, "fleet_diverged": diverged}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

DEFAULT_PHASES = ("train", "serve", "kernels")
PHASES = DEFAULT_PHASES + ("models",)


def run(chips: int, only, sz: Sizes, report: dict) -> None:
    from paddle_tpu.core import compile_cache

    cache_dir = compile_cache.configure()  # before the first compile
    cache = CacheCounter()
    log(f"compile cache: {cache_dir} ({_cache_entries(cache_dir)} entries "
        "at start)")
    report["device"] = device_report()
    phase_device(chips, report["device"])
    if chips > 1:
        phases = {"multichip": lambda: phase_multichip(sz)}
    else:
        table = {"train": lambda: phase_train(sz, cache),
                 "serve": lambda: phase_serve(sz),
                 "kernels": lambda: phase_kernels(sz),
                 "models": lambda: phase_models(sz)}
        phases = {k: table[k] for k in (only or DEFAULT_PHASES)}
    for name, fn in phases.items():
        t0 = time.perf_counter()
        log(f"== {name}")
        fn()
        log(f"== {name} passed in {time.perf_counter() - t0:.1f} s")
        gc.collect()
    log(f"compile cache: {cache.snapshot()} over the run, "
        f"{_cache_entries(cache_dir)} entries at end")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase (DP/ZeRO-2 "
                         "training, four-replica fleet)")
    ap.add_argument("--only", default="",
                    help="comma-separated one-chip phases to run instead "
                         f"of the default {','.join(DEFAULT_PHASES)}: "
                         f"{','.join(PHASES)}")
    args = ap.parse_args(argv)
    only = [p for p in args.only.split(",") if p]
    bad = [p for p in only if p not in PHASES]
    if bad or (only and args.chips > 1):
        ap.error(f"--only takes {','.join(PHASES)} (one chip only)")
    report = {"ok": False, "device": None}
    try:
        run(args.chips, only, Sizes(), report)
        report["ok"] = True
    except Exception:
        # the one boundary: say why, print the result line, exit non-zero
        traceback.print_exc()
    sys.stderr.flush()
    print(json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
