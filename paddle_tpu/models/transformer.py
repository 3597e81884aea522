"""Transformer LM — the long-context flagship (new capability; the 2017
reference predates transformers, its sequence flagship being the
MixedLayer-attention NMT demo).  Designed TPU-first:

- pre-LN decoder blocks under ``lax.scan`` over stacked layer params (one
  compiled block, S iterations — fast compiles at any depth);
- ``jax.checkpoint`` per block (rematerialisation trades FLOPs for HBM);
- 4D parallelism on one ``{data, seq, model, pipe}`` mesh:
  * dp  — batch dim sharded over ``data`` (gradient all-reduce over ICI);
  * tp  — Megatron pattern: qkv/mlp-in weights column-sharded over
    ``model``, wo/mlp-out row-sharded, so each block needs exactly two
    activation all-reduces (inserted by GSPMD from the shardings);
  * sp  — ring attention over ``seq`` (ops/attention.py) with the sequence
    dim of activations sharded;
  * pp  — blocks split into stages via parallel/pipeline.py (optional).

Everything is pure functions over a params pytree; sharding is data, not
code: ``param_shardings`` returns a matching pytree of PartitionSpecs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.telemetry.scopes import part, scoped


# a layer pattern's characters -> the kind's name in ``params["blocks"]``
_KINDS = {"*": "attn", "-": "mlp", "E": "moe", "M": "mamba", "K": "kda",
          "S": "mamba1", "W": "window", "G": "gmu", "X": "cross"}
# the kinds whose mixer is an attention: "*" over everything before it, "W"
# over its last ``attn_window`` positions, "X" with its own queries over
# the K/V of the nearest "*" before it
_ATTENDS = ("attn", "window", "cross")
# queries a step of the plain blocked attention takes at once (the window
# layers' band, and every attention under ``attn_diff``)
_ATTN_BLOCK = 512
# the kinds the ROLLED pattern walk carries (``_keeps_unrolled``): their
# pools ride a scan's carry and they hand nothing from layer to layer
_ROLLED = frozenset("*-M")


def _period(pattern: str) -> int:
    """The smallest p such that ``pattern`` is its first p characters
    repeated (its length where it repeats nothing)."""
    n = len(pattern)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and pattern == pattern[:p] * (n // p))


def _keeps_unrolled(cfg) -> str | None:
    """Why ``cfg``'s pattern walk stays unrolled — every layer written
    into the program's text, ``params["blocks"]`` one tree a layer — or
    None where it ROLLS: a pattern that is two or more repeats of its
    smallest period runs as ``lax.scan`` over the repeats, the body
    walking one period (``_run_pattern``).  The rule is the pattern's own
    shape and what its layers keep; there is no switch."""
    if cfg.pattern is None:
        return "no layer pattern: the homogeneous stack has its own scan"
    if _period(cfg.pattern) == len(cfg.pattern):
        return "the pattern is not two or more repeats of a period"
    kinds = sorted(set(cfg.pattern) - _ROLLED)
    if kinds:
        return (f"layers of kind {kinds}: a routed layer's counts and a "
                "delta-rule layer's state pools do not ride the rolled "
                "walk's carry yet")
    if cfg.cca_taps is not None:
        return ("cca_taps: a CCA layer's state beside its pages does not "
                "ride the rolled walk's carry yet")
    if cfg.moe_router_hidden:
        return ("moe_router_hidden: the MLP router's state goes from layer "
                "to layer across the period's end")
    if cfg.block_len > 1:
        return ("block_len > 1: the block pass walks its pools outside the "
                "rolled walk's carry")
    return None


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 8
    embed_dim: int = 512
    mlp_dim: int = 2048
    max_seq_len: int = 2048
    dtype: object = jnp.float32
    # rematerialisation policy for the per-layer checkpoint: True = full
    # remat (recompute everything; cheapest memory, for long context),
    # "dots" = save matmul/attention outputs and recompute only the
    # elementwise tail (measured fastest at train shapes), False = none.
    remat: object = True
    # attention implementation: "exact" | "blockwise" | "flash" (Pallas
    # kernel, ops/pallas/flash_attention.py) | "ring" | "ulysses" (the
    # last two need a mesh with a seq axis and activations sharded over
    # it; ring rotates K/V via ppermute, ulysses all_to_alls the
    # sequence<->head sharding — see ops/attention.py)
    attn_impl: str = "exact"
    attn_block_size: int = 1024
    # layer-scan unrolling: "auto" fully unrolls shallow stacks (<= 16
    # layers), trading ~2x compile time for the scan's per-iteration
    # dynamic-slice/update overhead (measured 70.7 -> 63.0 ms/step on the
    # 124M bench, +12%); deep stacks keep the rolled scan's fast compiles
    scan_unroll: object = "auto"
    # Mixture-of-Experts: >0 replaces every block's dense FFN with
    # moe_experts expert FFNs (parallel/moe.py GShard/Switch routing);
    # experts shard over an "expert" mesh axis with all_to_all dispatch
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    moe_dispatch: str = "sort"  # "einsum" = dense one-hot GShard tensors
    # "softmax" = the CAPACITY paths above (``moe_ffn``: top-1/2, overflow
    # dropped, biased GELU experts) and nothing else; the two others are
    # DROPLESS routing (``parallel.moe.moe_routed``): scores over all
    # ``moe_experts`` in float32 — "sigmoid" (top-``moe_top_k`` of score +
    # a selection-only bias) | "softmax_topk" (softmax over all experts,
    # no bias) — the chosen scores normalised and scaled by ``moe_scale``;
    # experts are ``mlp_dim`` wide with no bias, ``mlp`` their kind
    # ("relu2" | "gelu": act(x W_in) W_out; "swiglu": gated, (silu(x
    # W_gate) * (x W_in)) W_out); a shared expert of ``moe_shared_dim`` (0
    # = none; of the experts' kind, gated where they are) takes every
    # token.  ``moe_held`` = [lo, hi): the experts THIS device holds and
    # computes (None = all) — expert parallelism's share of the layer
    moe_router: str = "softmax"
    moe_scale: float = 1.0
    moe_shared_dim: int = 0
    moe_held: tuple | None = None
    # -- the block's parts; the defaults are the GPT-2 block -------------
    # width of one attention head; None = embed_dim // num_heads
    head_dim: int | None = None
    # K/V heads; None = num_heads.  Fewer: query head h reads K/V head
    # h // (num_heads // kv_heads), and the cache holds kv_heads
    kv_heads: int | None = None
    # "layer" (scale + bias) | "rms" (scale only); ``norm_sandwich`` adds a
    # second norm on each branch's OUTPUT, before the residual add
    norm: str = "layer"
    norm_eps: float = 1e-5
    norm_sandwich: bool = False
    # "learned" (a [max_seq_len, E] table added to the embedding) |
    # "rotary" (rotate-half RoPE on q and k at ``rope_theta``; no table,
    # so max_seq_len costs nothing) | "none" (order comes from layers
    # that read the sequence in order: a recurrent layer, causal masking)
    positions: str = "learned"
    rope_theta: float = 1e4
    # RMSNorm over head_dim on every head of q and of k, before RoPE, with
    # one gain each a layer (``q_norm_g``, ``k_norm_g``) at ``norm_eps``
    qk_norm: bool = False
    # "gelu" (w_in/w_out with biases) | "swiglu" (gate/up/down, no bias)
    # | "relu2" (w_in/w_out, squared ReLU, no bias)
    mlp: str = "gelu"
    tie_embeddings: bool = True  # False: a separate [E, V] "head"
    # looped stack: the SAME num_layers weights run ``loop_steps`` times,
    # the final norm closing every pass; pass t's K/V of layer l live in
    # cache layer t * num_layers + l (``cache_layers`` pools).  The exit
    # gate's parameters (exit_w, exit_b) live in the tree; at threshold 1
    # no token leaves early and the served path never evaluates them.
    loop_steps: int = 1
    early_exit_threshold: float = 1.0
    # layers of several kinds: one character per layer, each layer ONE
    # mixer behind a pre-norm and a residual add — "*" attention, "-" the
    # dense MLP, "E" routed experts (dropless: ``moe_router`` "sigmoid" or
    # "softmax_topk"), "M" a Mamba-2 mixer, "K" a gated delta-rule
    # linear-attention mixer (KDA), "S" a Mamba-1 mixer (selective scan),
    # "W" attention over the last ``attn_window`` positions, "G" a gated
    # memory unit (the nearest "S" before it hands its scan output on,
    # gated here by a projection of this layer's input), "X" attention
    # that projects q only and reads the K/V of the nearest "*" before it
    # (nine kinds).  None = ``num_layers`` blocks of (attention, MLP).
    # ``params["blocks"]`` is then a list of per-layer trees in pattern
    # order, walked by the pattern — or, where the pattern repeats a
    # period and the walk rolls over it (``pattern_roll``), one tree a
    # position in the period, stacked over the repeats.
    pattern: str | None = None
    # the Mamba-2 mixer: ``mamba_heads`` heads of ``mamba_head_dim``,
    # ``mamba_state`` state columns, B/C in ``mamba_groups`` groups, a
    # depthwise causal conv of ``mamba_conv`` taps, prefill in chunks of
    # ``mamba_chunk``
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 128
    # generation by diffusion over blocks: positions come ``block_len`` at
    # a time.  The attention mask is causal over BLOCKS (position i sees j
    # iff j // block_len <= i // block_len) and a served sequence is
    # generated a block a time: the block starts as ``mask_id`` tokens, a
    # pass computes all its positions (``forward_decode_block``) and some
    # are unmasked; 1 = one token a pass, every program of before
    block_len: int = 1
    mask_id: int | None = None
    # Compressed Convolutional Attention (arXiv:2510.04476) on every "*"
    # layer of a pattern: None = plain attention (q, k, v three products
    # of the normed state, token by token); (k0, k1) = q | k pass two
    # causal convolutions over the SEQUENCE — depthwise of k0 taps, then
    # one head_dim x head_dim matrix a head a tap of k1 taps — and get the
    # mean of the projections they came from added back, q and k are
    # L2-normalised to sqrt(head_dim) a head (k times a learned
    # temperature a K/V head), and the second half of the K/V heads' v is
    # the PREVIOUS token's projection.  Such a layer keeps a state a
    # sequence beside its pages (``ops/cca.py``)
    cca_taps: tuple | None = None
    # the share of a head RoPE rotates: its first ``head_dim x
    # rope_fraction`` dims (rotate-half inside them), the rest pass
    rope_fraction: float = 1.0
    # the routed experts' router: 0 = one matrix over the normed state; >
    # 0 = the state is projected down to this width, averaged over DEPTH
    # with the previous routed layer's (r_l = z_l + decay_l * r_{l-1},
    # carried by the pattern walk beside x, zero before the first), and an
    # RMSNorm and a three-layer GELU MLP of this width score the experts
    moe_router_hidden: int = 0
    # True: the chosen experts' scores are normalised to sum to
    # ``moe_scale``; False: an expert weighs its own score x ``moe_scale``
    moe_renorm: bool = True
    # a pattern layer's residual add: False = x + y; True = (g_x * x +
    # b_x) + (g_y * y + b_y), four learned vectors a layer
    residual_scale: bool = False
    # the Kimi Delta Attention mixer of a "K" layer (arXiv:2510.26692):
    # ``kda_heads`` heads of ``head_dim`` keys and values, q | k | v each
    # through a depthwise causal conv of ``kda_conv`` taps, a decay per key
    # channel and an output gate, both through a low-rank pair at rank
    # ``head_dim``, a step beta in (0, 2); prefill in chunks of
    # ``kda_chunk`` (``ops/kda.py``)
    kda_heads: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64
    # a "*" layer's output gate: a * sigmoid(h W_g), elementwise over the
    # heads' outputs, h the layer's normed input
    attn_gate: bool = False
    # four scalars of a block, each default what every program was: the
    # token embedding times ``embed_multiplier``; softmax(q k^T x
    # ``attn_scale``) (None = head_dim^-1/2); a pattern layer's residual
    # add x + ``residual_multiplier`` x mixer(norm(x)); the logits divided
    # by ``logits_divisor``
    embed_multiplier: float = 1.0
    attn_scale: float | None = None
    residual_multiplier: float = 1.0
    logits_divisor: float = 1.0
    # a "W" layer's window: a query sees itself and the ``attn_window`` - 1
    # positions before it (0 = the pattern has no such layer).  Served
    # from a RING a slot (``serving/kv_cache.py``): position p's K/V rest
    # at ``p mod attn_window``, which is exact only because such a model
    # has no position signal (``positions`` "none") and softmax does not
    # care in which order keys arrive
    attn_window: int = 0
    # differential attention (arXiv:2410.05258) on every "*", "W", "X"
    # layer: query heads 2p, 2p+1 are pair p's q1, q2, K/V heads 2r, 2r+1
    # pair r's k1, k2 and v1, v2, query pair p reads K/V pair p //
    # (num_heads / kv_heads); a1 = softmax(q1 k1^T) [v1 | v2], a2 likewise,
    # the pair's output RMSNorm_2D(a1 - lambda a2) (1 - lambda_init) with
    # lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, lambda_init =
    # 0.8 - 0.6 exp(-0.3 depth), depth the layer's block (``diff_depths``)
    attn_diff: bool = False
    # biases on a pattern's attention projections (bq, bk, bv, bo)
    attn_bias: bool = False
    # the Mamba-1 mixer of an "S" layer: ``mamba1_inner`` channels,
    # ``mamba1_state`` state columns a channel, a depthwise causal conv of
    # ``mamba1_conv`` taps, dt through a low-rank pair at ``mamba1_dt_rank``,
    # prefill in chunks of ``mamba1_chunk`` (``ops/mamba1.py``)
    mamba1_inner: int = 0
    mamba1_state: int = 16
    mamba1_conv: int = 4
    mamba1_dt_rank: int = 0
    mamba1_chunk: int = 64

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.embed_dim // self.num_heads)
        if self.kv_heads is None:
            object.__setattr__(self, "kv_heads", self.num_heads)
        if self.moe_held is not None:
            object.__setattr__(self, "moe_held", tuple(self.moe_held))
        if self.cca_taps is not None:
            object.__setattr__(self, "cca_taps", tuple(self.cca_taps))
        for field, allowed in (("norm", ("layer", "rms")),
                               ("positions", ("learned", "rotary", "none")),
                               ("mlp", ("gelu", "swiglu", "relu2")),
                               ("moe_router", ("softmax", "sigmoid",
                                               "softmax_topk"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"{field} must be one of {allowed}, got "
                                 f"{getattr(self, field)!r}")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"num_heads {self.num_heads} is not a multiple "
                             f"of kv_heads {self.kv_heads}")
        if self.moe_experts and self.moe_dropless:
            self.routed  # validates top_k and the held share
        if self.block_len < 1:
            raise ValueError(f"block_len must be >= 1, got {self.block_len}")
        if self.block_len > 1:
            if self.mask_id is None or not (
                    0 <= self.mask_id < self.vocab_size):
                raise ValueError(
                    f"block_len {self.block_len} > 1 needs a mask_id inside "
                    f"[0, {self.vocab_size}), got {self.mask_id!r}")
            if self.attn_impl not in ("exact", "flash"):
                raise NotImplementedError(
                    f"attn_impl {self.attn_impl!r} under block_len > 1: only "
                    "'exact' and 'flash' take the block-causal mask")
            if self.loop_steps > 1 or (self.pattern
                                       and set("MK") & set(self.pattern)):
                raise NotImplementedError(
                    "block_len > 1 with loop_steps > 1 or Mamba / KDA "
                    "layers: a block pass over a looped stack or a "
                    "recurrent state is not built")
        if self.pattern is not None:
            bad = sorted(set(self.pattern) - set(_KINDS))
            if bad or len(self.pattern) != self.num_layers:
                raise ValueError(
                    f"pattern {self.pattern!r} must be num_layers "
                    f"({self.num_layers}) characters of {sorted(_KINDS)}")
            if "E" in self.pattern and not (
                    self.moe_experts and self.moe_dropless):
                raise ValueError("an 'E' layer needs moe_experts > 0 and a "
                                 "dropless moe_router ('sigmoid' or "
                                 "'softmax_topk')")
            if "M" in self.pattern and not (
                    self.mamba_heads and self.mamba_heads
                    % self.mamba_groups == 0):
                raise ValueError("an 'M' layer needs mamba_heads > 0, a "
                                 "multiple of mamba_groups")
            if "K" in self.pattern:
                if self.kda_heads < 1 or self.kda_conv < 2 \
                        or self.kda_chunk % 16:
                    raise ValueError(
                        "a 'K' layer needs kda_heads > 0, kda_conv >= 2 "
                        "taps and a kda_chunk that is a multiple of 16")
                if self.cca_taps is not None:
                    raise NotImplementedError(
                        "a 'K' layer beside cca_taps: KDA state and CCA "
                        "state in one cache is not built")
            if self.loop_steps > 1 or self.norm_sandwich:
                raise NotImplementedError(
                    "a layer pattern with loop_steps > 1 or norm_sandwich "
                    "(whatever its kinds: '*', '-', 'E', 'M', 'K'): the "
                    "pattern walk runs one pass of pre-norm layers")
            self._check_yoco()
        if not 0.0 < self.rope_fraction <= 1.0 or int(
                self.head_dim * self.rope_fraction) % 2:
            raise ValueError(
                f"rope_fraction {self.rope_fraction} must lie in (0, 1] and "
                f"rotate an even number of head_dim {self.head_dim}'s dims")
        walked = self.pattern is not None
        for field, on in (("cca_taps", self.cca_taps is not None),
                          ("moe_router_hidden", self.moe_router_hidden),
                          ("residual_scale", self.residual_scale),
                          ("kda_heads", self.kda_heads),
                          ("attn_gate", self.attn_gate),
                          ("attn_diff", self.attn_diff),
                          ("attn_bias", self.attn_bias),
                          ("residual_multiplier",
                           self.residual_multiplier != 1.0)):
            if on and not walked:
                raise NotImplementedError(
                    f"{field} without a layer pattern: the homogeneous "
                    "stack's scan carries neither a state a layer, nor the "
                    "router's state from layer to layer, nor a layer's "
                    "residual scales or multiplier, gate matrix or KDA "
                    "mixer; only the pattern walk does")
        if self.attn_scale is not None and self.attn_impl in ("ring",
                                                              "ulysses"):
            raise NotImplementedError(
                f"attn_scale under attn_impl {self.attn_impl!r}: the "
                "sequence-parallel wrappers take no softmax scale")
        if self.cca_taps is not None:
            if len(self.cca_taps) != 2 or min(self.cca_taps) < 2:
                raise ValueError(
                    f"cca_taps {self.cca_taps!r} must be two tap counts >= 2 "
                    "(a stage of one tap reads no other token: no state)")
            if self.kv_heads % 2:
                raise ValueError(
                    f"cca_taps needs an even kv_heads (half of them take the "
                    f"previous token's v), got {self.kv_heads}")
            if self.qk_norm:
                raise ValueError(
                    "cca_taps with qk_norm: CCA normalises q and k itself")
            if self.block_len > 1:
                raise NotImplementedError(
                    "cca_taps with block_len > 1: a block pass rewrites its "
                    "block's positions pass after pass, and the state a CCA "
                    "layer keeps stands for ONE last token; a state per "
                    "block boundary is not built (loop_steps > 1 is refused "
                    "with every pattern)")
        if self.moe_router_hidden and self.moe_router != "softmax_topk":
            raise ValueError(
                "moe_router_hidden > 0 scores the experts by a softmax: "
                f"moe_router must be 'softmax_topk', got {self.moe_router!r}")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps must be >= 1, got {self.loop_steps}")
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.early_exit_threshold} < 1 lets "
                "a token leave the loop before its last pass: a step count "
                "that varies by token, which neither the scans here nor the "
                "scheduler's equal-work-per-token batches have")

    def _check_yoco(self):
        """What the four kinds of a decoder-hybrid-decoder pattern ("S",
        "W", "G", "X") need of the rest of the configuration."""
        pat = self.pattern
        new = sorted(set("SWGX") & set(pat))
        if "G" in pat and "S" not in pat[:pat.index("G")]:
            raise ValueError("a 'G' layer (gated memory unit) needs an 'S' "
                             "layer before it: it gates that layer's scan "
                             "output")
        if "X" in pat and "*" not in pat[:pat.index("X")]:
            raise ValueError("an 'X' layer (cross attention) needs a '*' "
                             "layer before it: it reads that layer's K/V")
        if ("W" in pat) != (self.attn_window > 0):
            raise ValueError(
                f"'W' layers and attn_window go together: pattern "
                f"{pat!r}, attn_window {self.attn_window}")
        if "S" in pat and not (self.mamba1_inner > 0 and self.mamba1_dt_rank
                               > 0 and self.mamba1_conv >= 2):
            raise ValueError("an 'S' layer needs mamba1_inner > 0, "
                             "mamba1_dt_rank > 0 and mamba1_conv >= 2 taps")
        if "W" in pat and self.positions != "none":
            raise NotImplementedError(
                "'W' layers with a position signal: the served window is a "
                "ring (position p at p mod attn_window), which needs the "
                "positions of what it holds once q and k carry them")
        from paddle_tpu.ops.pallas.paged_attention import head_group

        if self.attn_diff and (
                self.kv_heads % 2 or self.num_heads % 2 or self.qk_norm
                or head_group(self.kv_heads, self.head_dim) != 2):
            raise NotImplementedError(
                "attn_diff needs even num_heads and kv_heads (pairs), no "
                "qk_norm, and a K/V pair that is exactly one lane group of "
                "the cache (paged_attention.head_group = 2: head_dim 64, or "
                "2 K/V heads of at most 64): the decode kernel's caller "
                "keeps a lane group's values whole")
        if new and (self.block_len > 1 or self.cca_taps is not None
                    or self.moe_router_hidden):
            raise NotImplementedError(
                f"layers of kind {new} under block_len > 1, cca_taps or "
                "moe_router_hidden: a block pass, a CCA state and the MLP "
                "router's carry are not built beside a ring, a Mamba-1 "
                "state or the memory the walk hands on")
        if (self.attn_diff or self.attn_bias) and (
                self.cca_taps is not None or self.block_len > 1):
            raise NotImplementedError(
                "attn_diff / attn_bias under cca_taps or block_len > 1")

    @property
    def cache_layers(self) -> int:
        """Cache layers of the GROWING paged K/V cache — what a served
        token occupies for as long as its sequence lives: one per (pass,
        layer) of a homogeneous stack; under a pattern one per "*" layer.
        A "W" layer's bounded ring is counted by ``window_layers``, an
        "X" layer holds nothing (``cross_reads``)."""
        if self.pattern is not None:
            return self.pattern.count("*")
        return self.num_layers * self.loop_steps

    @property
    def window_layers(self) -> int:
        """"W" layers: each keeps the K/V of a sequence's last
        ``attn_window`` positions in a ring a slot, whatever the context."""
        return (self.pattern or "").count("W")

    @property
    def cross_reads(self) -> tuple:
        """For each "X" layer, in order, the cache layer it reads: that of
        the nearest "*" before it."""
        pat = self.pattern or ""
        return tuple(pat[:i].count("*") - 1
                     for i, c in enumerate(pat) if c == "X")

    @property
    def kv_reads(self) -> int:
        """Layer-reads of the growing cache a decode step makes: its "*"
        layers and the "X" layers that read them."""
        return self.cache_layers + len(self.cross_reads)

    @property
    def diff_depths(self) -> dict:
        """Under ``attn_diff``: kind -> each layer of the kind's depth, the
        block it belongs to (a block = a mixer and the MLP behind it: the
        "-" / "E" entries before the layer), which sets ``lambda_init``."""
        out = {k: [] for k in _ATTENDS}
        for i, c in enumerate(self.pattern or ""):
            if _KINDS[c] in out:
                out[_KINDS[c]].append(sum(self.pattern[:i].count(m)
                                          for m in "-E"))
        return {k: tuple(v) for k, v in out.items()}

    @property
    def narrow_at(self) -> int | None:
        """Where a prefill pass NARROWS to each row's last token: the
        index of the first layer behind the last one that leaves anything
        in a cache (K/V, a ring, a state), if the pattern has "X" layers
        and only "G", "X", "-" follow — nothing later reads the other
        positions (the decoder-hybrid-decoder rule).  None: no narrowing."""
        pat = self.pattern or ""
        if "X" not in pat:
            return None
        at = 1 + max(i for i, c in enumerate(pat) if c not in "GX-")
        return at if at < len(pat) else None

    @property
    def pattern_roll(self) -> tuple:
        """(period, repeats) of the pattern walk: the pattern's smallest
        period and how often ``lax.scan`` repeats it, or (its length, 1)
        where the walk stays unrolled (``_keeps_unrolled`` says why).
        ``params["blocks"]`` is a list of ``period`` trees, one a
        POSITION in the period, each leaf stacked ``[repeats, ...]``
        where repeats > 1 (``lay_blocks``)."""
        n = len(self.pattern or "")
        if _keeps_unrolled(self) is not None:
            return n, 1
        p = _period(self.pattern)
        return p, n // p

    @property
    def state_kinds(self) -> dict:
        """The kinds of layer that keep a fixed float32 state per sequence:
        kind -> (how many layers of it the pattern has, {part: one layer's
        shape for one sequence}).  A Mamba-2 layer keeps a state and no
        pages, nor does a KDA or a Mamba-1 layer; a CCA attention layer
        keeps one BESIDE its pages.  (A window layer's ring is K/V, in
        the K/V type: ``window_layers``.)"""
        from paddle_tpu.ops import cca, kda, mamba2

        kinds = {}
        if self.pattern is None:
            return kinds
        if "M" in self.pattern:
            kinds["mamba"] = (self.pattern.count("M"), mamba2.state_shapes(
                self.mamba_heads, self.mamba_head_dim, self.mamba_state,
                self.mamba_groups, self.mamba_conv))
        if "K" in self.pattern:
            kinds["kda"] = (self.pattern.count("K"), kda.state_shapes(
                self.kda_heads, self.head_dim, self.kda_conv))
        if "S" in self.pattern:
            from paddle_tpu.ops import mamba1

            kinds["mamba1"] = (self.pattern.count("S"), mamba1.state_shapes(
                self.mamba1_inner, self.mamba1_state, self.mamba1_conv))
        if self.cca_taps is not None and "*" in self.pattern:
            kinds["attn"] = (self.pattern.count("*"), cca.state_shapes(
                self.cca_taps, self.num_heads, self.kv_heads, self.head_dim))
        return kinds

    @property
    def state_layers(self) -> int:
        """Layers that keep a fixed state per sequence, of any kind."""
        return sum(n for n, _ in self.state_kinds.values())

    @property
    def state_parts(self) -> dict:
        """What the serving cache holds by batch slot: part -> (layers
        that keep it, one layer's shape for one sequence)."""
        return {part: (n, shape) for n, shapes in self.state_kinds.values()
                for part, shape in shapes.items()}

    @property
    def state_shapes(self) -> dict:
        """Every part of every kind's state: part -> one layer's shape."""
        return {part: shape for part, (_, shape) in self.state_parts.items()}

    @property
    def moe_dropless(self) -> bool:
        """The experts run through ``moe_routed`` (no capacity)."""
        return self.moe_router in ("sigmoid", "softmax_topk")

    @property
    def routed(self):
        from paddle_tpu.parallel.moe import RoutedConfig

        gated = self.mlp == "swiglu"
        return RoutedConfig(
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            scale=self.moe_scale, held=self.moe_held,
            act="silu" if gated else self.mlp, gated=gated,
            score="sigmoid" if self.moe_router == "sigmoid" else "softmax",
            router_hidden=self.moe_router_hidden, renorm=self.moe_renorm,
            eps=self.norm_eps)

    @property
    def moe(self):
        from paddle_tpu.parallel.moe import MoEConfig

        if not self.moe_experts:
            return None
        return MoEConfig(num_experts=self.moe_experts, mlp_dim=self.mlp_dim,
                         top_k=self.moe_top_k,
                         capacity_factor=self.moe_capacity_factor,
                         aux_loss_weight=self.moe_aux_weight,
                         dispatch=self.moe_dispatch)


def _ffn_params(cfg: TransformerConfig, norm, zeros, lead: tuple,
                depth: int, experts: bool = True) -> dict:
    """The feed-forward leaves of ``lead`` stacked layers, by the config's
    parts (``experts`` False: the dense MLP of a config that also has
    expert layers); ``depth`` scales the output matrices down (GPT-2's
    residual scaling)."""
    e, m = cfg.embed_dim, cfg.mlp_dim
    experts = experts and cfg.moe_experts
    if experts and cfg.moe_dropless:
        ex, held = cfg.moe_experts, cfg.routed.num_held
        r = cfg.moe_router_hidden
        if r:
            # the MLP router (``parallel.moe.route_mlp``): ``router`` is
            # its last matrix; the depth average starts at a half
            p = {"router_down": norm(*lead, e, r) * (e ** -0.5),
                 "router_down_b": zeros(*lead, r),
                 "router_decay": jnp.full((*lead, r), 0.5, cfg.dtype),
                 "router_norm_g": jnp.ones((*lead, r), cfg.dtype),
                 "router_w1": norm(*lead, r, r) * (r ** -0.5),
                 "router_w2": norm(*lead, r, r) * (r ** -0.5),
                 "router": norm(*lead, r, ex) * (r ** -0.5),
                 "router_bias": zeros(*lead, ex)}
        else:
            p = {"router": norm(*lead, e, ex) * (e ** -0.5)}
            if cfg.moe_router == "sigmoid":
                p["router_bias"] = zeros(*lead, ex)
        p.update(w_in=norm(*lead, held, e, m) * (e ** -0.5),
                 w_out=norm(*lead, held, m, e) * (m ** -0.5)
                 / (2 * depth) ** 0.5)
        if cfg.routed.gated:
            p["w_gate"] = norm(*lead, held, e, m) * (e ** -0.5)
        if cfg.moe_shared_dim:
            sh = cfg.moe_shared_dim
            p["shared_in"] = norm(*lead, e, sh) * (e ** -0.5)
            if cfg.routed.gated:
                p["shared_gate"] = norm(*lead, e, sh) * (e ** -0.5)
            p["shared_out"] = norm(*lead, sh, e) * (sh ** -0.5) \
                / (2 * depth) ** 0.5
        return p
    if experts:
        ex = cfg.moe_experts
        return {
            "wg": norm(*lead, e, ex) * (e ** -0.5),
            "w1": norm(*lead, ex, e, m) * (2.0 / e) ** 0.5,
            "b1": zeros(*lead, ex, m),
            "w2": norm(*lead, ex, m, e) * (m ** -0.5) / (2 * depth) ** 0.5,
            "b2": zeros(*lead, ex, e),
        }
    if cfg.mlp == "swiglu":
        return {
            "w_gate": norm(*lead, e, m) * (e ** -0.5),
            "w_up": norm(*lead, e, m) * (e ** -0.5),
            "w_out": norm(*lead, m, e) * (m ** -0.5) / (2 * depth) ** 0.5,
        }
    if cfg.mlp == "relu2":
        return {"w_in": norm(*lead, e, m) * (e ** -0.5),
                "w_out": norm(*lead, m, e) * (m ** -0.5) / (2 * depth) ** 0.5}
    return {
        "w_in": norm(*lead, e, m) * (e ** -0.5),
        "b_in": zeros(*lead, m),
        "w_out": norm(*lead, m, e) * (m ** -0.5) / (2 * depth) ** 0.5,
        "b_out": zeros(*lead, e),
    }


def _qk_norm_params(cfg: TransformerConfig, lead: tuple) -> dict:
    """The per-head q/k norm gains of ``lead`` stacked layers (none
    without ``qk_norm``)."""
    if not cfg.qk_norm:
        return {}
    return {"q_norm_g": jnp.ones((*lead, cfg.head_dim), cfg.dtype),
            "k_norm_g": jnp.ones((*lead, cfg.head_dim), cfg.dtype)}


def _pattern_params(cfg: TransformerConfig, norm, zeros, norm_p) -> list:
    """``params["blocks"]`` under a layer pattern: one tree per LAYER, in
    pattern order, its leaves by the layer's kind; every layer carries its
    pre-norm (``ln_g``, ``ln_b``).  Nothing is stacked: a layer's matrices
    are arrays of their own, so no program can copy or slice a stack to
    reach them (stacked per kind and indexed statically, XLA copied every
    639 MB expert matrix of the prefill program: 6 GB of temporaries)."""
    e, s = cfg.embed_dim, cfg.num_layers
    h, hk = cfg.num_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    nh = cfg.mamba_heads
    di = nh * cfg.mamba_head_dim
    conv_dim = di + 2 * cfg.mamba_groups * cfg.mamba_state

    def cca():
        """A CCA layer's leaves beside the four projections: the two
        convolutions over q | k (tap K-1 is the current token) and k's
        temperature a K/V head."""
        if cfg.cca_taps is None:
            return {}
        (k0, k1), hd = cfg.cca_taps, cfg.head_dim
        heads = cfg.num_heads + cfg.kv_heads
        return {"cca_conv0_w": norm(k0, heads * hd) * (k0 ** -0.5),
                "cca_conv0_b": zeros(heads * hd),
                "cca_conv1_w": norm(k1, heads, hd, hd) * ((k1 * hd) ** -0.5),
                "cca_conv1_b": zeros(heads * hd),
                "cca_temp": jnp.ones((cfg.kv_heads,), cfg.dtype)}

    def attn_extras(cross=False):
        """Biases on the projections and the differential leaves (four
        lambda vectors a head wide, one gain over a pair's 2 x head_dim);
        a cross layer has neither K nor V."""
        p = {}
        if cfg.attn_bias:
            p.update(bq=zeros(h), bo=zeros(e))
            if not cross:
                p.update(bk=zeros(hk), bv=zeros(hk))
        if cfg.attn_diff:
            p.update({f"lambda_{n}": 0.1 * norm(cfg.head_dim)
                      for n in ("q1", "k1", "q2", "k2")},
                     subln_g=jnp.ones((2 * cfg.head_dim,), cfg.dtype))
        return p

    def layer(kind):
        if kind in ("attn", "window"):
            # the gate is drawn last: the leaves before it are the draws
            # they were without one
            return {"wq": norm(e, h) * (e ** -0.5),
                    "wk": norm(e, hk) * (e ** -0.5),
                    "wv": norm(e, hk) * (e ** -0.5),
                    "wo": norm(h, e) * (h ** -0.5) / (2 * s) ** 0.5,
                    **_qk_norm_params(cfg, ()), **cca(),
                    **({"w_ogate": norm(e, h) * (e ** -0.5)}
                       if cfg.attn_gate else {}), **attn_extras()}
        if kind == "cross":
            return {"wq": norm(e, h) * (e ** -0.5),
                    "wo": norm(h, e) * (h ** -0.5) / (2 * s) ** 0.5,
                    **attn_extras(cross=True)}
        if kind == "gmu":
            d1 = cfg.mamba1_inner
            return {"gmu_in": norm(e, d1) * (e ** -0.5),
                    "gmu_out": norm(d1, e) * (d1 ** -0.5) / (2 * s) ** 0.5}
        if kind == "mamba1":
            # dt_bias around softplus^-1(0.01), A = -exp(a_log) <= -1 and
            # drawn per channel and state column (kept [state, inner]: the
            # state's layout), so a swapped axis shows
            d1, n1, r1 = (cfg.mamba1_inner, cfg.mamba1_state,
                          cfg.mamba1_dt_rank)
            return {"in_proj": norm(e, 2 * d1) * (e ** -0.5),
                    "conv_w": norm(cfg.mamba1_conv, d1)
                    * (cfg.mamba1_conv ** -0.5),
                    "conv_b": zeros(d1),
                    "x_proj": norm(d1, r1 + 2 * n1) * (d1 ** -0.5),
                    "dt_proj": norm(r1, d1) * (r1 ** -0.5),
                    "dt_bias": -4.6 + 0.5 * norm(d1),
                    "a_log": jnp.abs(norm(n1, d1)),
                    "d": jnp.ones((d1,), cfg.dtype),
                    "out_proj": norm(d1, e) * (d1 ** -0.5) / (2 * s) ** 0.5}
        if kind in ("mlp", "moe"):
            return _ffn_params(cfg, norm, zeros, (), s, kind == "moe")
        if kind == "kda":
            # decays alpha = exp(-exp(a_log) softplus(. + dt_bias)) around
            # 0.99 and drawn, so a swapped head or channel shows
            hd, dk = cfg.head_dim, cfg.kda_heads * cfg.head_dim
            return {
                "wq": norm(e, dk) * (e ** -0.5),
                "wk": norm(e, dk) * (e ** -0.5),
                "wv": norm(e, dk) * (e ** -0.5),
                "conv_w": norm(cfg.kda_conv, 3 * dk) * (cfg.kda_conv ** -0.5),
                "decay_a": norm(e, hd) * (e ** -0.5),
                "decay_b": norm(hd, dk) * (hd ** -0.5),
                "a_log": 0.5 * norm(cfg.kda_heads),
                "dt_bias": -4.6 + 0.5 * norm(dk),
                "w_beta": norm(e, cfg.kda_heads) * (e ** -0.5),
                "gate_a": norm(e, hd) * (e ** -0.5),
                "gate_b": norm(hd, dk) * (hd ** -0.5),
                "gate_bias": zeros(dk),
                "norm_g": jnp.ones((hd,), cfg.dtype),
                "wo": norm(dk, e) * (dk ** -0.5) / (2 * s) ** 0.5}
        # dt_bias around softplus^-1(0.01) and A = -exp(a_log) <= -1 (the
        # usual ranges); drawn, not constant, so a swapped head shows
        return {
            "in_proj": norm(e, 2 * di + 2 * cfg.mamba_groups
                            * cfg.mamba_state + nh) * (e ** -0.5),
            "conv_w": norm(cfg.mamba_conv, conv_dim)
            * (cfg.mamba_conv ** -0.5),
            "conv_b": zeros(conv_dim),
            "dt_bias": -4.6 + 0.5 * norm(nh),
            "a_log": jnp.abs(norm(nh)),
            "d": jnp.ones((nh,), cfg.dtype),
            "norm_g": jnp.ones((di,), cfg.dtype),
            "out_proj": norm(di, e) * (di ** -0.5) / (2 * s) ** 0.5}

    def res():
        if not cfg.residual_scale:
            return {}
        return {"res_x_g": jnp.ones((e,), cfg.dtype), "res_x_b": zeros(e),
                "res_y_g": jnp.ones((e,), cfg.dtype), "res_y_b": zeros(e)}

    return [{**norm_p("ln"), **layer(_KINDS[c]), **res()}
            for c in cfg.pattern]


def lay_blocks(cfg: TransformerConfig, layers: list) -> list:
    """A pattern's per-LAYER trees (pattern order) as ``params["blocks"]``
    holds them: themselves where the walk is unrolled; where it rolls
    (``pattern_roll`` = (p, r)), ``p`` trees, position j's the layers j,
    j + p, ... stacked ``[r, ...]`` — per POSITION, not per kind, so the
    scan slices every stack along its leading axis and nothing else."""
    p, r = cfg.pattern_roll
    if r == 1:
        return list(layers)
    return [jax.tree.map(lambda *a: jnp.stack(a), *layers[j::p])
            for j in range(p)]


def layers_of(cfg: TransformerConfig, blocks: list) -> list:
    """``lay_blocks``' inverse: ``params["blocks"]`` -> one tree a layer,
    in pattern order."""
    p, r = cfg.pattern_roll
    if r == 1:
        return list(blocks)
    return [jax.tree.map(lambda a: a[i // p], blocks[i % p])
            for i in range(p * r)]


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    """Stacked-layer params: block weights have leading dim num_layers
    (under a layer ``pattern``: a list of per-layer trees,
    ``_pattern_params``).
    Which leaves exist follows the config's parts (norm biases only under
    "layer", ``pos_embed`` only under "learned" positions, ``head`` only
    when untied, the exit gate only for a looped stack)."""
    e, h, v_sz = cfg.embed_dim, cfg.num_heads * cfg.head_dim, cfg.vocab_size
    hk = cfg.kv_heads * cfg.head_dim
    s = cfg.num_layers
    k = iter(jax.random.split(
        key, 14 if cfg.pattern is None else 8 + (
            12 if set("KSWGX") & set(cfg.pattern) or cfg.attn_diff
            else 8) * cfg.num_layers))
    norm = lambda *shape: jax.random.normal(next(k), shape, cfg.dtype)
    zeros = lambda *shape: jnp.zeros(shape, cfg.dtype)

    def norm_p(name, *lead):
        p = {f"{name}_g": jnp.ones((*lead, e), cfg.dtype)}
        if cfg.norm == "layer":
            p[f"{name}_b"] = zeros(*lead, e)
        return p

    if cfg.pattern is not None:
        params = {"embed": norm(v_sz, e) * (e ** -0.5)}
        if cfg.positions == "learned":
            params["pos_embed"] = norm(cfg.max_seq_len, e) * 0.02
        params["blocks"] = lay_blocks(
            cfg, _pattern_params(cfg, norm, zeros, norm_p))
        params.update(norm_p("ln_f"))
        if not cfg.tie_embeddings:
            params["head"] = norm(e, v_sz) * (e ** -0.5)
        return params

    ffn = _ffn_params(cfg, norm, zeros, (s,), s)
    params = {"embed": norm(v_sz, e) * (e ** -0.5)}
    pos = norm(cfg.max_seq_len, e) * 0.02 if cfg.positions == "learned" \
        else None
    blocks = {
        **norm_p("ln1", s),
        "wq": norm(s, e, h) * (e ** -0.5),
        "wk": norm(s, e, hk) * (e ** -0.5),
        "wv": norm(s, e, hk) * (e ** -0.5),
        "wo": norm(s, h, e) * (h ** -0.5) / (2 * s) ** 0.5,
        **norm_p("ln2", s),
        **ffn,
        **_qk_norm_params(cfg, (s,)),
    }
    if cfg.norm_sandwich:
        blocks.update(**norm_p("ln1_post", s), **norm_p("ln2_post", s))
    if pos is not None:
        params["pos_embed"] = pos
    params["blocks"] = blocks
    params.update(norm_p("ln_f"))
    if not cfg.tie_embeddings:
        params["head"] = norm(e, v_sz) * (e ** -0.5)
    if cfg.loop_steps > 1:
        params["exit_w"], params["exit_b"] = zeros(e), zeros()
    return params


def param_shardings(cfg: TransformerConfig) -> dict:
    """PartitionSpec pytree matching init_params — the Megatron TP layout
    (axis names degrade to replicated if absent from the mesh via
    MeshContext.param_sharding semantics; used directly with NamedSharding
    they must exist)."""
    if cfg.pattern is not None or (cfg.moe_experts and cfg.moe_dropless):
        raise NotImplementedError(
            "param_shardings: no tensor- or expert-parallel layout is "
            "written for a layer pattern or for dropless routed experts "
            "(the expert exchange across devices is not built)")
    col, row = P(None, None, "model"), P(None, "model", None)
    if cfg.moe_experts:
        # experts over the "expert" axis (layer-stack dim first)
        ffn = {
            "wg": P(),
            "w1": P(None, "expert", None, None),
            "b1": P(None, "expert", None),
            "w2": P(None, "expert", None, None),
            "b2": P(None, "expert", None),
        }
    elif cfg.mlp == "swiglu":
        ffn = {"w_gate": col, "w_up": col, "w_out": row}
    elif cfg.mlp == "relu2":
        ffn = {"w_in": col, "w_out": row}
    else:
        ffn = {"w_in": col, "b_in": P(None, "model"),
               "w_out": row, "b_out": P()}

    def norm_p(name):
        return {f"{name}_{x}": P()
                for x in ("gb" if cfg.norm == "layer" else "g")}

    specs = {
        "embed": P("model", None),  # vocab-sharded table (in-mesh pserver)
        "blocks": {
            **norm_p("ln1"),
            "wq": col, "wk": col, "wv": col,
            "wo": row,
            **norm_p("ln2"),
            **ffn,
        },
        **norm_p("ln_f"),
    }
    if cfg.norm_sandwich:
        specs["blocks"].update(**norm_p("ln1_post"), **norm_p("ln2_post"))
    if cfg.qk_norm:
        specs["blocks"].update(q_norm_g=P(), k_norm_g=P())
    if cfg.positions == "learned":
        specs["pos_embed"] = P()
    if not cfg.tie_embeddings:
        specs["head"] = P(None, "model")
    if cfg.loop_steps > 1:
        specs["exit_w"], specs["exit_b"] = P(), P()
    return specs


def place_params(params: dict, mesh, cfg: TransformerConfig | None = None) -> dict:
    """device_put per the TP layout, degrading absent axes to replicated."""
    present = set(mesh.axis_names)

    def fix(spec):
        return P(*[a if a in present else None for a in spec])

    specs = jax.tree.map(
        fix, param_shardings(cfg or TransformerConfig()),
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), params, specs
    )


from paddle_tpu.ops.nn import layer_norm as _ln  # shared with the v2 path


@scoped("norm")
def _norm(cfg: TransformerConfig, x, p, name):
    """The config's norm over the last axis with the parameters
    ``p[name + "_g"]`` (and ``"_b"`` under "layer")."""
    if cfg.norm == "rms":
        return _rms(cfg, x, p[name + "_g"])
    return _ln(x, p[name + "_g"], p[name + "_b"], cfg.norm_eps)


def _rms(cfg: TransformerConfig, x, gain):
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                        + cfg.norm_eps)
    return xf.astype(x.dtype) * gain


def _rope_table(cfg: TransformerConfig, positions):
    """(cos, sin) [..., 1, head_dim] float32 for integer ``positions``
    [...] — broadcast over the head axis of q/k [..., H, head_dim]; under
    a ``rope_fraction`` below 1 only that share of head_dim wide."""
    half = int(cfg.head_dim * cfg.rope_fraction) // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, table):
    """Rotate-half RoPE: dims (i, i + head_dim/2) are one pair.  A table
    narrower than x rotates x's first dims and passes the rest."""
    cos, sin = table
    rot = cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [_rope(x[..., :rot], table), x[..., rot:]], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


@scoped("embed")
def _embed(cfg: TransformerConfig, params, ids, positions=None):
    """Token states entering the stack, and the RoPE table of their
    positions (None under learned positions, which are added here).
    ``positions`` None = ids [B, T] sit at 0..T-1."""
    x = params["embed"][ids]
    if cfg.embed_multiplier != 1.0:
        x = x * cfg.embed_multiplier
    if cfg.positions == "learned":
        x = x + (params["pos_embed"][:ids.shape[1]][None]
                 if positions is None else params["pos_embed"][positions])
        return x, None
    if cfg.positions == "none":
        return x, None
    return x, _rope_table(cfg, jnp.arange(ids.shape[1])[None]
                          if positions is None else positions)


@scoped("head")
def _head(cfg: TransformerConfig, params, x, out_dtype=None):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ w if out_dtype is None else jnp.matmul(
        x, w, preferred_element_type=out_dtype)
    if cfg.logits_divisor != 1.0:
        logits = logits / cfg.logits_divisor
    return logits


def _attention(cfg: TransformerConfig, q, k, v, mesh):
    if cfg.kv_heads != cfg.num_heads:
        # contiguous attention over [B, T, H, Dh]: every query head gets
        # its K/V head's copy (the cache keeps the kv_heads that exist)
        rep = cfg.num_heads // cfg.kv_heads
        k, v = jnp.repeat(k, rep, axis=-2), jnp.repeat(v, rep, axis=-2)
    if cfg.attn_impl in ("ring", "ulysses"):
        assert mesh is not None and "seq" in mesh.axis_names, (
            f"{cfg.attn_impl} attention needs a mesh with a 'seq' axis"
        )
        fn = (attn_ops.attention_with_sequence_parallel
              if cfg.attn_impl == "ring"
              else attn_ops.attention_with_ulysses)
        return fn(
            q, k, v, mesh, causal=True,
            head_axis="model" if "model" in mesh.axis_names else None,
        )
    if cfg.attn_impl == "blockwise":
        return attn_ops.blockwise_attention(
            q, k, v, block_size=min(cfg.attn_block_size, q.shape[1]),
            causal=True, scale=cfg.attn_scale
        )
    # True = causal over tokens; an int > 1 = causal over blocks of it
    causal = True if cfg.block_len == 1 else cfg.block_len
    if cfg.attn_impl == "flash":
        from paddle_tpu.ops.pallas import flash_attention

        bs = cfg.attn_block_size
        if mesh is None:
            return flash_attention(q, k, v, causal, cfg.attn_scale, bs, bs)
        # pallas_call has no GSPMD partitioning rule — run the kernel
        # per-device under shard_map (batch over data, heads over model;
        # sequence sharding needs attn_impl="ring" or "ulysses" instead)
        assert "seq" not in mesh.axis_names, (
            "attn_impl='flash' does not shard the sequence; use 'ring' "
            "or 'ulysses'"
        )
        from paddle_tpu.compat import shard_map

        spec = P(
            "data" if "data" in mesh.axis_names else None,
            None,
            "model" if "model" in mesh.axis_names else None,
            None,
        )
        fn = shard_map(
            lambda q, k, v: flash_attention(q, k, v, causal, cfg.attn_scale,
                                            bs, bs),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)
    t = q.shape[1]
    if cfg.block_len > 1:
        blk = jnp.arange(t) // cfg.block_len
        mask = (blk[:, None] >= blk[None, :])[None, None]
    else:
        mask = attn_ops.causal_mask(t, t)
    return attn_ops.dot_product_attention(q, k, v, mask=mask,
                                          scale=cfg.attn_scale)


def _blocked_attention(cfg: TransformerConfig, q, k, v, window: int = 0,
                       seq_lens=None):
    """Causal attention in plain XLA, a block of ``_ATTN_BLOCK`` queries
    at a time against only the keys the block can see — everything before
    it, or under ``window`` > 0 the band (a query sees itself and the
    ``window`` - 1 positions before it): key blocks wholly outside are
    never multiplied, not masked.  q [B, T, H, D] in the cache's order
    (query head h reads K head ``h // (H / KV)``; under ``attn_diff``
    after ``_diff_order``), k and v [B, Tk, KV, D].  Under ``attn_diff``
    K head 2r + s reads the VALUES of its whole pair, [v_2r | v_2r+1],
    and the result is [B, T, H, 2 D]; otherwise [B, T, H, D].
    ``seq_lens`` [B] with T = 1: q is each row's LAST token (position
    ``seq_lens - 1``) and sees keys ``[0, seq_lens)``."""
    from paddle_tpu.ops.pallas import NEG_INF

    b, t, h, d = q.shape
    tk, kv = k.shape[1], k.shape[2]
    s = 2 if cfg.attn_diff else 1
    r, rep = kv // s, h // kv
    scale = cfg.attn_scale if cfg.attn_scale is not None else d ** -0.5
    kg = k.reshape(b, tk, r, s, d)
    vg = v.reshape(b, tk, r, s * d)

    def attend(qb, lo, hi, mask):
        """Queries qb [B, Q, H, D] over keys [lo, hi); mask [B or 1, Q,
        hi - lo]."""
        qg = qb.reshape(b, -1, r, s, rep, d)
        sc = jnp.einsum("bqrsjd,bkrsd->brsjqk", qg, kg[:, lo:hi],
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(mask[:, None, None, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        out = jnp.einsum("brsjqk,bkre->bqrsje", p, vg[:, lo:hi],
                         preferred_element_type=jnp.float32)
        return out.reshape(b, -1, h, s * d).astype(q.dtype)

    if seq_lens is not None:
        assert t == 1, "seq_lens: one query a row, its last token"
        assert not window, "the last-token form takes no band"
        mask = jnp.arange(tk)[None, None, :] < seq_lens[:, None, None]
        return attend(q, 0, tk, mask)
    assert t == tk, "whole sequences: a key a query"
    bq = min(_ATTN_BLOCK, t)
    behind = -(-(window - 1) // bq) if window else None   # key blocks back
    outs = []
    for i in range(-(-t // bq)):
        q0, q1 = i * bq, min((i + 1) * bq, t)
        lo = 0 if behind is None else max(0, (i - behind) * bq)
        qpos = jnp.arange(q0, q1)[:, None]
        kpos = jnp.arange(lo, q1)[None, :]
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        outs.append(attend(q[:, q0:q1], lo, q1, mask[None]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _diff_order(cfg: TransformerConfig, q):
    """q [..., H, D] with heads 2p, 2p+1 pair p's q1, q2 -> the cache's
    order: K head 2r + s (pair r's k1 | k2) is read by the q_(s+1) of the
    ``H / KV`` query pairs of its K/V pair, and a cache built for grouped
    queries gives K head j the query heads ``[j rep, (j + 1) rep)`` —
    inside every K/V pair's 2 rep heads the order (pair, s) becomes (s,
    pair): (0, 2, 1, 3) at rep 2.  It is NOT the grouped-query map
    h // rep on the published order."""
    *lead, h, d = q.shape
    rep = cfg.num_heads // cfg.kv_heads
    return q.reshape(*lead, h // (2 * rep), rep, 2, d).swapaxes(
        -3, -2).reshape(*lead, h, d)


def _diff_combine(cfg: TransformerConfig, a, layer, depth):
    """a [..., H, 2 D] (heads in ``_diff_order``: K/V pair r, then s, then
    the pair in r; each its softmax over [v1 | v2]) -> the pairs' outputs
    [..., H D] in the published order: RMSNorm_2D(a1 - lambda a2) (1 -
    lambda_init), one gain a layer."""
    f32 = jnp.float32
    *lead, h, e = a.shape
    rep = cfg.num_heads // cfg.kv_heads
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
    dot = lambda x, y: jnp.sum(layer["lambda_" + x].astype(f32)
                               * layer["lambda_" + y].astype(f32))
    lam = jnp.exp(dot("q1", "k1")) - jnp.exp(dot("q2", "k2")) + lam0
    a = a.astype(f32).reshape(*lead, h // (2 * rep), 2, rep, e)
    y = a[..., 0, :, :] - lam * a[..., 1, :, :]
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = y * layer["subln_g"].astype(f32) * (1.0 - lam0)
    return y.reshape(*lead, h * e // 2)


@scoped("mamba1.proj")
def _mamba1_mixer(cfg: TransformerConfig, h, layer, conv, scan):
    """The Mamba-1 mixer over normed states h [..., E] -> (its output
    [..., E], the scan's output y [..., d_inner] float32 BEFORE the gate
    by z, with the D skip: what a gated memory unit reads).  ``conv(x, w,
    bias)`` and ``scan(x, dt, a, b, c, d)`` are the caller's arrangement
    (``ops/mamba1.py``, ``mamba2.conv_*``), both returning float32."""
    f32 = jnp.float32
    n, r = cfg.mamba1_state, cfg.mamba1_dt_rank
    x, z = jnp.split(h @ layer["in_proj"], 2, axis=-1)
    with part("mamba1.conv"):
        x = jax.nn.silu(conv(x, layer["conv_w"], layer["conv_b"])
                        ).astype(h.dtype)
    dt, b, c = jnp.split(x @ layer["x_proj"], [r, r + n], axis=-1)
    dt = jax.nn.softplus((dt @ layer["dt_proj"]).astype(f32)
                         + layer["dt_bias"].astype(f32))
    with part("mamba1.scan"):
        y = scan(x, dt, -jnp.exp(layer["a_log"].astype(f32)), b, c,
                 layer["d"])
    out = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
    return out @ layer["out_proj"], y


@scoped("attn.qkv")
def _qkv(cfg: TransformerConfig, h, layer, rope):
    """Normed states h [..., E] -> q [..., H, Dh], k and v [..., KV, Dh],
    RoPE applied from ``rope``.  A layer with no ``wk`` (a cross layer:
    it reads another layer's K/V) gives (q, None, None)."""
    lead, hd = h.shape[:-1], cfg.head_dim

    def proj(name, heads):
        y = h @ layer["w" + name]
        if cfg.attn_bias:
            y = y + layer["b" + name]
        return y.reshape(*lead, heads, hd)

    q = proj("q", cfg.num_heads)
    if "wk" not in layer:
        return (q if rope is None else _rope(q, rope)), None, None
    k, v = proj("k", cfg.kv_heads), proj("v", cfg.kv_heads)
    if cfg.qk_norm:
        q, k = (_rms(cfg, q, layer["q_norm_g"]),
                _rms(cfg, k, layer["k_norm_g"]))
    if rope is not None:
        q, k = _rope(q, rope), _rope(k, rope)
    return q, k, v


@scoped("attn.qkv")
def _cca_qkv(cfg: TransformerConfig, h, layer, rope, window):
    """CCA's q [..., H, Dh], k and v [..., KV, Dh] from normed states h
    [..., E] (``TransformerConfig.cca_taps``).  ``window(part, x, n) ->
    [..., n+1, C]`` float32 is the caller's arrangement of "x's last n
    inputs before each token, and the token" (``ops/cca.py``: whole padded
    prompts in prefill, one token against its slot's state in decode); it
    keeps the state it must hand back in the caller's own variables."""
    f32 = jnp.float32
    lead, hd = h.shape[:-1], cfg.head_dim
    nh, kv = cfg.num_heads, cfg.kv_heads
    k0, k1 = cfg.cca_taps
    u = jnp.concatenate([h @ layer["wq"], h @ layer["wk"]], axis=-1)
    # depthwise over the sequence, then a head's own matrix a tap
    with part("cca.conv"):
        c0 = jnp.einsum("...kc,kc->...c", window("cca_u", u, k0 - 1),
                        layer["cca_conv0_w"].astype(f32)) \
            + layer["cca_conv0_b"].astype(f32)
        win = window("cca_c", c0, k1 - 1).astype(h.dtype).reshape(
            *lead, k1, nh + kv, hd)
        c1 = jnp.einsum("...kgd,kgde->...ge", win, layer["cca_conv1_w"],
                        preferred_element_type=f32) \
            + layer["cca_conv1_b"].astype(f32).reshape(nh + kv, hd)
    # the mean of the projections q and k came from, of a K/V head and
    # the query heads that read it, added back
    uh = u.astype(f32).reshape(*lead, nh + kv, hd)
    qt, kt = uh[..., :nh, :], uh[..., nh:, :]
    q = c1[..., :nh, :] + 0.5 * (qt + jnp.repeat(kt, nh // kv, axis=-2))
    k = c1[..., nh:, :] + 0.5 * (
        jnp.mean(qt.reshape(*lead, kv, nh // kv, hd), axis=-2) + kt)
    # sqrt(Dh) x / |x| is an RMS norm with no gain; k's temperature is
    # one a K/V head
    q = _rms(cfg, q, 1.0).astype(h.dtype)
    k = _rms(cfg, k, layer["cca_temp"].astype(f32)[:, None]).astype(h.dtype)
    if rope is not None:
        q, k = _rope(q, rope), _rope(k, rope)
    # the first half of the K/V heads' v is this token's, the second half
    # the previous token's
    hv = h @ layer["wv"]
    half = kv * hd // 2
    with part("cca.conv"):
        prev = window("cca_v", hv[..., half:], 1)[..., 0, :].astype(h.dtype)
    v = jnp.concatenate([hv[..., :half], prev], axis=-1)
    return q, k, v.reshape(*lead, kv, hd)


def _mlp(cfg: TransformerConfig, h, layer, mesh=None, live=None,
         experts: bool = True, carry=None):
    """The feed-forward branch over normed states h [..., E], by the
    config's parts (``experts`` False: the dense MLP of a config that
    also has expert layers): (y, the output bias to add or None, aux).
    aux is the capacity MoE's load-balancing loss, the routed MoE's
    counts (``parallel.moe.moe_routed``), None for a dense FFN.  ``carry``:
    an MLP router's state of this layer (``moe_router_hidden``)."""
    experts = experts and cfg.moe_experts
    if experts and cfg.moe_dropless:
        from paddle_tpu.parallel.moe import moe_routed

        y, counts = moe_routed(layer, h, cfg.routed, live, carry)
        return y, None, counts
    if experts:
        from paddle_tpu.parallel.moe import moe_ffn, moe_ffn_sharded

        moe_p = {n: layer[n] for n in ("wg", "w1", "b1", "w2", "b2")}
        # the capacity form: gate, dispatch, experts, combine in one
        with part("moe.product"):
            if mesh is not None and "expert" in mesh.axis_names:
                y, aux = moe_ffn_sharded(moe_p, h, cfg.moe, mesh)
            else:
                y, aux = moe_ffn(moe_p, h, cfg.moe)
        return y, None, aux
    with part("ffn"):
        if cfg.mlp == "swiglu":
            return (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])
                    ) @ layer["w_out"], None, None
        if cfg.mlp == "relu2":
            return jnp.square(jax.nn.relu(h @ layer["w_in"])) \
                @ layer["w_out"], None, None
        h = jax.nn.gelu(h @ layer["w_in"] + layer["b_in"])
        return h @ layer["w_out"], layer["b_out"], None


def _block(cfg: TransformerConfig, x, layer, attend, rope=None, mesh=None,
           remat_dots=False):
    """THE decoder block of a homogeneous stack — the pair (attention,
    feed-forward) of the residual-branch units a layer ``pattern`` walks
    one at a time (``_run_pattern``); x [..., E] (training and prefill
    [B, T, E], decode [B, E]).  ``attend(q, k, v) -> (a, kept)`` is the
    caller's cache write and attention over q [..., H, Dh] and k/v
    [..., KV, Dh] (RoPE already applied from ``rope``); ``kept`` is handed
    back untouched — the K/V a prefill captures, the pools a chunk or
    decode pass updated, None in training.  Returns (x, aux, kept); aux
    is the capacity MoE's load-balancing loss, None otherwise.

    ``remat_dots`` checkpoints the two dense segments with the
    dots-saveable policy while leaving the attention call OUTSIDE any
    checkpoint: a policy cannot save a custom-vjp's internal residuals
    (the flash kernel's log-sum-exp), so a whole-block checkpoint re-runs
    the flash forward in the backward scan — measured 9 ms/step at the
    124M bench shape."""
    lead = x.shape[:-1]
    nh, hd = cfg.num_heads, cfg.head_dim

    def qkv_fn(x, layer):
        return _qkv(cfg, _norm(cfg, x, layer, "ln1"), layer, rope)

    def branch_out(x, y, bias, post):
        """The residual add of one branch: under a sandwich the branch's
        output (bias included) is normed first; otherwise the bias goes on
        after the add, GPT-2's order of rounding."""
        with part("norm"):
            if cfg.norm_sandwich:
                return x + _norm(cfg, y if bias is None else y + bias,
                                 layer, post)
            x = x + y
            return x if bias is None else x + bias

    def tail_fn(x, a, layer):
        with part("attn.out"):
            y = a.reshape(*lead, nh * hd) @ layer["wo"]
        x = branch_out(x, y, None, "ln1_post")
        y, bias, aux = _mlp(cfg, _norm(cfg, x, layer, "ln2"), layer, mesh)
        if cfg.moe_dropless:
            aux = None  # routing counts: the pattern walk's to report
        return branch_out(x, y, bias, "ln2_post"), aux

    if remat_dots:
        policy = jax.checkpoint_policies.dots_saveable
        qkv_fn = jax.checkpoint(qkv_fn, policy=policy)
        tail_fn = jax.checkpoint(tail_fn, policy=policy)
    q, k, v = qkv_fn(x, layer)
    with part("attn.core"):
        a, kept = attend(q, k, v)
    x, aux = tail_fn(x, a, layer)
    return x, aux, kept


@scoped("mamba2.proj")
def _mamba_mixer(cfg: TransformerConfig, h, layer, conv, ssd):
    """The Mamba-2 mixer over normed states h [..., E].  ``conv(xbc, w,
    bias)`` and ``ssd(x, dt, a, b, c, d)`` are the caller's arrangement of
    the causal convolution and of the recurrence (whole padded prompts in
    prefill, one token against the state pool in decode:
    ``ops/mamba2.py``), both returning float32."""
    f32 = jnp.float32
    lead = h.shape[:-1]
    nh, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                   cfg.mamba_state)
    di = nh * p
    z, xbc, dt = jnp.split(h @ layer["in_proj"], [di, 2 * di + 2 * g * n],
                           axis=-1)
    with part("mamba2.conv"):
        xbc = jax.nn.silu(conv(xbc, layer["conv_w"], layer["conv_b"])
                          ).astype(h.dtype)
    x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"].astype(f32))
    with part("mamba2.scan"):
        y = ssd(x.reshape(*lead, nh, p), dt,
                -jnp.exp(layer["a_log"].astype(f32)),
                b.reshape(*lead, g, n), c.reshape(*lead, g, n), layer["d"])
    # gate, then RMSNorm over each of the g groups of d_inner / g
    y = (y.reshape(*lead, di) * jax.nn.silu(z.astype(f32))
         ).reshape(*lead, g, di // g)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = y.reshape(*lead, di).astype(h.dtype) * layer["norm_g"]
    return y @ layer["out_proj"]


@scoped("kda.proj")
def _kda_mixer(cfg: TransformerConfig, h, layer, conv, rule):
    """The Kimi Delta Attention mixer over normed states h [..., E].
    ``conv(x, w, bias)`` and ``rule(q, k, v, g, beta)`` are the caller's
    arrangement of the causal convolution over q | k | v and of the gated
    delta rule (whole padded prompts in prefill, one token against the
    state pools in decode: ``ops/mamba2.py``, ``ops/kda.py``), both
    returning float32."""
    f32 = jnp.float32
    lead = h.shape[:-1]
    nh, hd = cfg.kda_heads, cfg.head_dim
    qkv = jnp.concatenate([h @ layer["wq"], h @ layer["wk"],
                           h @ layer["wv"]], axis=-1)
    with part("kda.conv"):
        q, k, v = jnp.split(jax.nn.silu(conv(qkv, layer["conv_w"], None)),
                            3, axis=-1)
    # q and k to unit length a head (q then scaled as attention scales)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                   + 1e-6)
    q = (unit(q.reshape(*lead, nh, hd)) * hd ** -0.5).astype(h.dtype)
    k = unit(k.reshape(*lead, nh, hd)).astype(h.dtype)
    v = v.reshape(*lead, nh, hd).astype(h.dtype)
    # the decay, a log <= 0 per head and key channel; the step in (0, 2):
    # past 1 the transition I - beta k k^T has a negative eigenvalue
    dt = ((h @ layer["decay_a"]) @ layer["decay_b"]).astype(f32) \
        + layer["dt_bias"].astype(f32)
    g = -jnp.exp(layer["a_log"].astype(f32))[:, None] \
        * jax.nn.softplus(dt).reshape(*lead, nh, hd)
    beta = 2.0 * jax.nn.sigmoid((h @ layer["w_beta"]).astype(f32))
    with part("kda.rule"):
        o = rule(q, k, v, g, beta)
    # RMSNorm over each head's values, one gain; then the output gate
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    gate = jax.nn.sigmoid(
        ((h @ layer["gate_a"]) @ layer["gate_b"]).astype(f32)
        + layer["gate_bias"].astype(f32))
    y = (o * layer["norm_g"].astype(f32)).reshape(*lead, nh * hd) * gate
    return y.astype(h.dtype) @ layer["wo"]


def _pattern_layer(cfg: TransformerConfig, kind, layer, x, rope, attend,
                   mamba, live=None, mesh=None, carry=None, window=None,
                   depth=None):
    """One layer of a ``pattern``: ``x + mixer(norm(x))`` (under
    ``residual_scale`` both terms scaled and shifted by the layer's own
    vectors), the mixer by the layer's ``kind`` (a value of ``_KINDS``).
    ``attend(q, k, v) -> a`` is the caller's cache write and attention of
    this attention layer, ``mamba = (conv, ssd)`` this state layer's
    arrangement (``_mamba_mixer``; of a KDA layer ``(conv, rule)``,
    ``_kda_mixer``), ``window`` a CCA layer's
    (``_cca_qkv``); all keep what they must hand back in the caller's own
    variables.  ``live`` (bool, x's leading shape) marks the rows that are
    tokens, for the routing counts.  ``carry`` [..., moe_router_hidden]
    float32 is the router's state of the routed layer before (None
    without an MLP router): a routed layer reads and replaces it, every
    other kind passes it on.  In a pattern with Mamba-1 layers ``carry``
    is instead the MEMORY: the last "S" layer's scan output [...,
    mamba1_inner] float32, which a "G" layer gates (``__post_init__``
    refuses the two together).  ``attend`` gets k and v None from an "X"
    layer; under ``attn_diff`` it gets q in ``_diff_order`` and returns
    each head's [..., H, 2 Dh], and ``depth`` is the layer's block
    (``diff_depths``).  Returns (x, counts, carry): a routed layer's
    ``moe_routed`` counts, else None."""
    lead = x.shape[:-1]
    counts = None
    h = _norm(cfg, x, layer, "ln")
    if kind in _ATTENDS:
        q, k, v = (_qkv(cfg, h, layer, rope) if cfg.cca_taps is None
                   else _cca_qkv(cfg, h, layer, rope, window))
        with part("attn.core"):
            a = attend(_diff_order(cfg, q) if cfg.attn_diff else q, k, v)
        with part("attn.out"):
            if cfg.attn_diff:
                # each head's softmax was over its pair's [v1 | v2]: now
                # the pair
                a = _diff_combine(cfg, a, layer, depth).astype(h.dtype)
            else:
                a = a.reshape(*lead, cfg.num_heads * cfg.head_dim)
            if cfg.attn_gate:
                a = a * jax.nn.sigmoid(h @ layer["w_ogate"])
            y = a @ layer["wo"]
            if cfg.attn_bias:
                y = y + layer["bo"]
    elif kind == "mamba1":
        # the scan's output before its gate is what the walk hands on
        y, carry = _mamba1_mixer(cfg, h, layer, *mamba)
    elif kind == "gmu":
        with part("gmu.proj"):
            gate = jax.nn.silu((h @ layer["gmu_in"]).astype(jnp.float32))
            y = (carry * gate).astype(h.dtype) @ layer["gmu_out"]
    elif kind == "mamba":
        y = _mamba_mixer(cfg, h, layer, *mamba)
    elif kind == "kda":
        y = _kda_mixer(cfg, h, layer, *mamba)
    else:
        if kind == "moe" and cfg.moe_router_hidden:
            from paddle_tpu.parallel.moe import router_state

            with part("moe.route"):
                carry = router_state(layer, h, carry)
        y, bias, aux = _mlp(cfg, h, layer, mesh, live, kind == "moe", carry)
        if bias is not None:
            y = y + bias
        if kind == "moe":
            counts = aux
    with part("norm"):      # the residual add
        if cfg.residual_multiplier != 1.0:
            y = y * cfg.residual_multiplier
        if cfg.residual_scale:
            return ((x * layer["res_x_g"] + layer["res_x_b"])
                    + (y * layer["res_y_g"] + layer["res_y_b"])), counts, \
                carry
        return x + y, counts, carry


@scoped("stack")
def _run_pattern(cfg: TransformerConfig, params, x, layer_fn, held=None,
                 narrow=None):
    """The stack of a layer ``pattern``, the final norm closing it.
    ``layer_fn(kind, i, layer, x, carry, held) -> (x, counts, carry, held,
    left)`` is the caller's arrangement of ``_pattern_layer`` for the
    ``i``-th layer of its kind (an attention layer's ``i`` is its cache
    layer, a state layer's its row of the state pools); ``carry`` is the
    MLP router's state, walked beside x from layer to layer (None without
    one: nothing is carried); ``held`` is what the caller carries WHOLE
    through the walk and every layer may replace (the K/V pools, the
    state pools: None where there are none) and ``left`` {name: tree}
    what the layer leaves behind (a prefill's K/V and state; None or
    empty: nothing).

    Where the walk is unrolled, ``params["blocks"]`` is the list of the
    layers' own trees and ``i`` a Python number: nothing is sliced out of
    a stack, statically or dynamically.  Where it ROLLS
    (``TransformerConfig.pattern_roll`` = (p, r), r > 1) one ``lax.scan``
    runs the r repeats and its body walks the p positions of the period:
    position j's tree is ``params["blocks"][j]`` sliced along its leading
    axis by the scan, x and ``held`` are the scan's carry, and ``i`` =
    repeat x (layers of the kind a period) + the layer's offset among
    them, traced.

    ``narrow(x, carry) -> (x, carry)`` (unrolled walk only) is applied
    once, in front of layer ``cfg.narrow_at``: a prefill pass that goes
    on with each row's last token alone.

    Returns (x, counts, held, left): the routed layers' ``moe_routed``
    counts summed (the busiest expert's tokens: the largest; None without
    routed layers), and every name's leavings stacked over its layers in
    pool order ``[layers that leave it, ...]``."""
    p, r = cfg.pattern_roll
    seen = dict.fromkeys(_KINDS.values(), 0)
    counts, left = None, {}

    def leave(kept):
        for name, v in (kept or {}).items():
            left.setdefault(name, []).append(v)

    if r > 1:
        # layers of each kind a period: what a repeat advances ``i`` by
        per = {k: cfg.pattern[:p].count(c) for c, k in _KINDS.items()}

        def period(walked, xs):
            x, held = walked
            layers, rep = xs
            at, lefts = dict(seen), []
            for c, layer in zip(cfg.pattern[:p], layers):
                kind = _KINDS[c]
                x, _, _, held, kept = layer_fn(
                    kind, rep * per[kind] + at[kind], layer, x, None, held)
                at[kind] += 1
                lefts.append(kept)
            return (x, held), lefts

        (x, held), lefts = lax.scan(
            period, (x, held), (params["blocks"], jnp.arange(r)))
        for kept in lefts:
            leave(kept)
        # a position's leavings came out stacked [r, ...]: repeat-major
        # over the positions that leave the name is pool order
        pooled = lambda *a: jnp.stack(a, 1).reshape(-1, *a[0].shape[1:])
    else:
        carry = jnp.zeros((*x.shape[:-1], cfg.moe_router_hidden),
                          jnp.float32) if cfg.moe_router_hidden else None
        for n, (c, layer) in enumerate(zip(cfg.pattern, params["blocks"])):
            kind = _KINDS[c]
            if narrow is not None and n == cfg.narrow_at:
                x, carry = narrow(x, carry)
            x, aux, carry, held, kept = layer_fn(kind, seen[kind], layer, x,
                                                 carry, held)
            seen[kind] += 1     # the layer's index among its kind
            leave(kept)
            if aux is not None:
                counts = aux if counts is None else jnp.concatenate(
                    [counts[:3] + aux[:3], jnp.maximum(counts[3:], aux[3:])])
        pooled = lambda *a: jnp.stack(a)
    x = _norm(cfg, x, params, "ln_f")
    # K/V first, then the state parts in the cache's order
    with part("kv.write"):
        left = {name: jax.tree.map(pooled, *left[name])
                for name in ("kv", "window", *cfg.state_parts)
                if name in left}
    return x, counts, held, left


@scoped("stack")
def _run_stack(cfg: TransformerConfig, params, x, layer_fn, pools=None,
               unroll=1):
    """``loop_steps`` passes of the layer scan over the ONE stacked
    ``blocks`` tree, the final norm closing each pass and feeding the
    next.

    Without ``pools`` ``layer_fn(x, layer) -> (x, y)`` is the layer scan's
    body and the result is (x, ys): every (pass, layer)'s ``y`` stacked
    [cache_layers, ...].  With ``pools`` — the K/V pools of
    ``paged_attention.kv_pool_shape`` — they ride the carry of the layer
    loop and of the pass loop, whole, for every ``loop_steps`` alike:
    ``layer_fn(x, layer, cache_layer, *pools) -> (x, pools)`` is told
    which cache layer (``pass * num_layers + layer``) it is and addresses
    the pools there, so no loop slices a layer's pool out or stacks one
    back in and the buffers that entered the program are updated where
    they are.  Returns (x, pools)."""
    steps, layers = cfg.loop_steps, cfg.num_layers

    def one_pass(x, pools, t):
        if pools is None:
            x, ys = lax.scan(layer_fn, x, params["blocks"], unroll=unroll)
        else:
            def layer(carry, a):
                x, pools = carry
                return layer_fn(x, a[0], t * layers + a[1], *pools), None

            (x, ys), _ = lax.scan(
                layer, (x, pools), (params["blocks"], jnp.arange(layers)),
                unroll=unroll)
        return _norm(cfg, x, params, "ln_f"), ys

    if steps == 1:  # no outer loop
        return one_pass(x, pools, 0)
    if pools is None:
        x, ys = lax.scan(lambda x, _: one_pass(x, None, 0), x, None,
                         length=steps)
        return x, jax.tree.map(
            lambda y: y.reshape(steps * layers, *y.shape[2:]), ys)
    return lax.scan(lambda carry, t: (one_pass(*carry, t), None),
                    (x, pools), jnp.arange(steps))[0]


def forward(cfg: TransformerConfig, params: dict, ids: jax.Array,
            mesh=None) -> jax.Array:
    """ids [B, T] -> logits [B, T, V]."""
    return forward_with_aux(cfg, params, ids, mesh=mesh)[0]


def forward_with_aux(cfg: TransformerConfig, params: dict, ids: jax.Array,
                     mesh=None):
    """(logits [B, T, V], aux): aux is the mean MoE load-balancing loss
    across layers (0.0 for dense FFNs)."""
    x, rope = _embed(cfg, params, ids)

    if cfg.remat != "dots" and not isinstance(cfg.remat, bool):
        raise ValueError(f"remat must be True, False or 'dots', got "
                         f"{cfg.remat!r}")
    if cfg.pattern is not None:
        # whole sequences, nothing kept (no remat policy is applied: the
        # backward of the chunked scan is XLA's autodiff of it, untuned)
        x, _, _ = _prefill_pattern(cfg, params, x, rope, None, mesh)
        return _head(cfg, params, x), jnp.zeros((), jnp.float32)
    attn = functools.partial(_attention, cfg, mesh=mesh)
    if cfg.remat == "dots" and cfg.attn_impl != "flash":
        # non-custom-vjp impls would otherwise save O(T^2) softmax
        # residuals per layer; recompute them in the backward instead
        attn = jax.checkpoint(attn)

    def block(x, layer):
        x, aux, _ = _block(
            cfg, x, layer, lambda q, k, v: (attn(q, k, v), None), rope,
            mesh, remat_dots=cfg.remat == "dots")
        return x, jnp.zeros((), jnp.float32) if aux is None else aux

    if cfg.remat is True:
        block = jax.checkpoint(block)

    unroll = cfg.scan_unroll
    if unroll == "auto":
        unroll = cfg.num_layers if cfg.num_layers <= 16 else 1
    elif not isinstance(unroll, (bool, int)):
        raise ValueError(f"scan_unroll must be 'auto', a bool, or an int; "
                         f"got {unroll!r}")
    x, auxes = _run_stack(cfg, params, x, block, unroll=unroll)
    return _head(cfg, params, x), jnp.mean(auxes)


# -- incremental inference (the serving path) ---------------------------------
#
# Training runs the whole context through `forward` every step; serving
# can't — decode is one token per sequence per step over a ragged,
# continuously re-batched population.  The entry points below split
# the forward into the standard prefill/decode pair over the paged
# KV-cache of ops/pallas/paged_attention.py (layout and page-table
# semantics documented there; paddle_tpu/serving/ owns allocation and
# scheduling).  All run `_block` — each hands it its own cache write and
# attention — so incremental decode is token-for-token equal to repeated
# full-context `forward` argmax (asserted in tests/test_serving.py and
# tests/test_looped_lm.py).  The pools are two arrays for the whole model
# (`paged_attention.kv_pool_shape`: [cache_layers, H/g, P, page_size,
# g*Dh], one cache layer per (pass, layer) of a looped stack).  The chunk
# and decode programs carry them WHOLE through `_run_stack`'s loops: a
# block writes and reads at `(cache_layer, page)`, never a layer's pool as
# a value of its own, so the donated buffers are updated where they are
# (tests/test_looped_lm.py holds the compiled programs to it).


@scoped("head")
def _last_valid(x, seq_lens):
    return jnp.take_along_axis(
        x, jnp.maximum(seq_lens - 1, 0)[:, None, None], axis=1)[:, 0]


def forward_prefill(cfg: TransformerConfig, params: dict, ids: jax.Array,
                    seq_lens: jax.Array, mesh=None):
    """Prompt pass: ids [B, T] right-padded, seq_lens [B] valid lengths.

    Returns (last-token logits [B, V], k [cache_layers, B, T, H, Dh], v
    likewise) — the K/V stacks are scattered into the paged cache by the
    caller (``paged_attention.write_prefill_kv``).  Causal masking means
    padded positions are never attended by valid queries, so plain
    right-padding is exact; rows with ``seq_lens == 0`` (slack in a
    fixed-size prefill batch) produce garbage logits the caller
    discards."""
    x, rope = _embed(cfg, params, ids)
    if cfg.pattern is not None:
        x, (ks, vs), extras = _prefill_pattern(cfg, params, x, rope,
                                               seq_lens, mesh)
        # a pass that narrowed comes back one position a row: the last
        last = x[:, 0] if cfg.narrow_at is not None else _last_valid(
            x, seq_lens)
        return _head(cfg, params, last), ks, vs, extras

    def layer_fn(x, layer):
        x, _, kv = _block(
            cfg, x, layer,
            lambda q, k, v: (_attention(cfg, q, k, v, mesh), (k, v)), rope)
        return x, kv

    x, (ks, vs) = _run_stack(cfg, params, x, layer_fn)
    return _head(cfg, params, _last_valid(x, seq_lens)), ks, vs


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _prefill_layer(cfg: TransformerConfig, kind, mesh, layer, x, rope,
                   seq_lens, carry=None, shared=None, depth=None):
    """One layer of a pattern over whole right-padded sequences x [B, T,
    E]: (x, what the layer leaves behind, counts, the router's carry).  A
    jitted function of its own, so the program that calls it traces and
    lowers each KIND of layer once and not each layer: getting a
    pattern's prefill program ready is the host tracing and lowering it,
    seconds an engine (PERF.md section 6, PR 31); XLA inlines the
    calls.  ``shared``: the (k, v) [B, T, KV, Dh] of the nearest "*"
    layer before an "X" layer; ``depth``: an attention layer's block
    under ``attn_diff`` (traced: one text a kind).  An x of ONE position
    a row is each row's last token (the pass narrowed, ``narrow_at``)."""
    from paddle_tpu.ops import cca, kda, mamba1, mamba2
    from paddle_tpu.ops.pallas import paged_attention as pa

    kept = {}

    def attend(q, k, v):
        if kind == "cross":     # its own queries over another layer's K/V
            return _blocked_attention(
                cfg, q, *shared,
                seq_lens=seq_lens if x.shape[1] != shared[0].shape[1]
                else None)
        if kind == "window":
            if seq_lens is not None:    # each row's last window, as a ring
                with part("kv.write"):
                    kept["window"] = tuple(
                        pa.ring_rows(a, seq_lens, cfg.attn_window)
                        for a in (k, v))
            return _blocked_attention(cfg, q, k, v, cfg.attn_window)
        kept["kv"] = (k, v)
        if cfg.attn_diff:
            return _blocked_attention(cfg, q, k, v)
        return _attention(cfg, q, k, v, mesh)

    def conv(xbc, w, bias):
        part = {"kda": "kda_conv", "mamba1": "conv1"}.get(kind, "conv")
        out, kept[part] = mamba2.conv_prefill(xbc, w, bias, seq_lens)
        return out

    def scan1(*args):
        y, kept["ssm1"] = mamba1.scan_prefill(*args, seq_lens=seq_lens,
                                              chunk=cfg.mamba1_chunk)
        return y

    def rule(*args):
        o, kept["kda_s"] = kda.kda_prefill(*args, seq_lens=seq_lens,
                                           chunk=cfg.kda_chunk)
        return o

    def ssd(*args):
        y, kept["ssm"] = mamba2.ssd_prefill(*args, seq_lens=seq_lens,
                                            chunk=cfg.mamba_chunk)
        return y

    def window(part, x, n):
        win, kept[part] = cca.window_prefill(x, n, seq_lens)
        return win

    live = None if seq_lens is None else (
        jnp.arange(x.shape[1])[None, :] < seq_lens[:, None])
    x, counts, carry = _pattern_layer(
        cfg, kind, layer, x, rope, attend,
        (conv, {"kda": rule, "mamba1": scan1}.get(kind, ssd)), live, mesh,
        carry=carry, window=window, depth=depth)
    return x, kept, counts, carry


def _prefill_pattern(cfg: TransformerConfig, params, x, rope, seq_lens, mesh):
    """Whole right-padded sequences x [B, T, E] through a layer pattern
    (``seq_lens`` None = training: every position is a token).  Returns
    (x, (ks, vs) [cache_layers, B, T, KV, Dh] or (None, None), extras):
    ``extras["state"]`` = {part: [layers that keep it, B, ...]}, each
    row's state at its last valid token; under window layers
    ``extras["window"]`` = (ks, vs) [window_layers, B, attn_window, KV,
    Dh], each row's last window in ring order
    (``paged_attention.ring_rows``).

    A pattern with a cross-decoder (``cfg.narrow_at``) serves the layers
    behind its last producer — nothing later reads what they compute at
    any position but the last — for each row's LAST token only: x (and
    the memory the walk carries) is cut to ``[B, 1, ...]`` there, the
    "X" layers read that one query against the K/V in hand, and x comes
    back ``[B, 1, E]``.  Training walks every position."""
    shared = []     # the nearest "*" layer's (k, v), for the "X" layers
    depths = cfg.diff_depths if cfg.attn_diff else {}

    def layer_fn(kind, i, layer, x, carry, held):
        more = {}       # (none for the kinds that take none: their call
        if kind == "cross":                     # is what it always was)
            more["shared"] = shared[-1]
        if kind in depths:
            more["depth"] = jnp.float32(depths[kind][i])
        x, left, counts, carry = _prefill_layer(
            cfg, kind, mesh, layer, x, rope, seq_lens, carry, **more)
        if kind == "attn" and cfg.cross_reads:
            shared.append(left["kv"])
        return x, counts, carry, held, left

    def narrow(x, carry):
        cut = lambda a: _last_valid(a, seq_lens)[:, None]
        return cut(x), None if carry is None else cut(carry)

    x, counts, _, left = _run_pattern(
        cfg, params, x, layer_fn,
        narrow=narrow if seq_lens is not None else None)
    extras = {"state": {n: left[n] for n in cfg.state_parts},
              "moe_counts": counts}
    if "window" in left:
        extras["window"] = left["window"]
    return x, left.get("kv", (None, None)), extras


def forward_prefill_chunk(cfg: TransformerConfig, params: dict,
                          ids: jax.Array, starts: jax.Array,
                          seq_lens: jax.Array, page_table: jax.Array,
                          k_cache, v_cache):
    """Incremental prompt pass over the paged cache — the chunked-
    prefill / cached-prefix-tail twin of :func:`forward_prefill`.

    ids [B, C] right-padded chunk tokens, starts [B] the absolute
    position of each row's first token, seq_lens [B] valid NEW tokens
    this pass (0 = idle row), page_table [B, max_pages], k_cache/v_cache
    the pools of ``paged_attention.kv_pool_shape``.  Each block writes the chunk's
    K/V into the mapped pages, then attends the chunk queries causally
    over the WHOLE resident context — earlier chunks and any shared
    cached prefix included — so a prompt split across passes (or riding a
    prefix-cache hit) computes the same math as one full prefill.
    Returns (last-valid logits [B, V], k_cache', v_cache'): the row
    whose chunk completes its prompt samples its first token from these
    logits; mid-prompt rows' logits are discarded by the caller."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    b, c = ids.shape
    # padding of offset rows can index past max_seq_len — clip (valid
    # positions satisfy starts + t < max_prompt_len <= max_seq_len)
    with part("embed"):
        pos = jnp.clip(starts[:, None] + jnp.arange(c)[None, :], 0,
                       cfg.max_seq_len - 1)
    x, rope = _embed(cfg, params, ids, pos)
    if cfg.pattern is not None:
        if cfg.window_layers or cfg.cross_reads:
            raise NotImplementedError(
                "forward_prefill_chunk with 'W' or 'X' layers: a chunk "
                "would have to read and advance its slot's ring, and a "
                "prefix hit share one; neither is built")
        if cfg.state_layers:
            raise NotImplementedError(
                "forward_prefill_chunk with state layers: a chunk would "
                "have to start from its slot's recurrent state and leave "
                "it behind, and a prefix-cache hit needs a snapshot of the "
                "state at the shared prefix's last token; neither is built")
        live = jnp.arange(c)[None, :] < seq_lens[:, None]

        def layer_fn(kind, i, layer, x, carry, pools):
            pools = list(pools)

            def attend_chunk(q, k, v):
                with part("kv.write"):
                    pools[:] = pa.write_chunk_kv(*pools, k, v, i, page_table,
                                                 starts, seq_lens)
                return pa.paged_prefill_attention(
                    q, *pools, i, page_table, starts, seq_lens,
                    scale=cfg.attn_scale, kv_heads=cfg.kv_heads)

            x, counts, carry = _pattern_layer(
                cfg, kind, layer, x, rope, attend_chunk, None, live,
                carry=carry)
            return x, counts, carry, tuple(pools), None

        x, counts, pools, _ = _run_pattern(cfg, params, x, layer_fn,
                                           (k_cache, v_cache))
        return (_head(cfg, params, _last_valid(x, seq_lens)), *pools,
                {"state": {}, "moe_counts": counts})

    def layer_fn(x, layer, cache_layer, kc, vc):
        def attend(q, k, v):
            with part("kv.write"):
                pools = pa.write_chunk_kv(kc, vc, k, v, cache_layer,
                                          page_table, starts, seq_lens)
            return pa.paged_prefill_attention(
                q, *pools, cache_layer, page_table, starts, seq_lens,
                scale=cfg.attn_scale, kv_heads=cfg.kv_heads), pools

        x, _, pools = _block(cfg, x, layer, attend, rope)
        return x, pools

    x, (k_cache, v_cache) = _run_stack(cfg, params, x, layer_fn,
                                       (k_cache, v_cache))
    return _head(cfg, params, _last_valid(x, seq_lens)), k_cache, v_cache


def forward_decode(cfg: TransformerConfig, params: dict, ids: jax.Array,
                   positions: jax.Array, seq_lens: jax.Array,
                   page_table: jax.Array, k_cache, v_cache,
                   attn_impl: str = "auto", mesh=None, state=None):
    """One incremental decode step over the paged KV-cache (and, under a
    pattern with state layers, over the ``state`` pools {part: [layers
    that keep it, B, ...]}, row = batch row).

    ids [B] current tokens, positions [B] their absolute indices,
    seq_lens [B] = positions + 1 on live rows and 0 on idle rows,
    page_table [B, max_pages], k_cache/v_cache the pools of
    ``paged_attention.kv_pool_shape`` (``init_kv_pages``).  Each block
    hands the new token's K/V and its query to
    ``paged_attention.decode_attention``, which writes the token into its
    page and attends the whole resident context, the token included: on a
    TPU one Mosaic call that does both, elsewhere a scatter and the jnp
    reference.  Returns (logits [B, V], k_cache', v_cache'); idle rows
    read zeros and write nothing but, on the reference path, the null
    page.

    ``attn_impl`` is the paged-attention implementation ("auto" =
    Pallas kernel on TPU, jnp reference elsewhere) — deliberately
    separate from ``cfg.attn_impl``, which describes TRAINING attention
    over contiguous sequences."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    x, rope = _embed(cfg, params, ids, positions)
    if cfg.pattern is not None:
        return _decode_pattern(cfg, params, x, rope, positions, seq_lens,
                               page_table, k_cache, v_cache, attn_impl,
                               state, mesh)

    # what the step's cache layers share, made once: XLA leaves it in the
    # layer loop's body otherwise
    with part("attn.core"):
        plan = pa.decode_plan(k_cache, page_table, positions, seq_lens,
                              cfg.kv_heads, cfg.head_dim)

    def layer_fn(x, layer, cache_layer, kc, vc):
        def attend(q, k, v):
            return pa.decode_attention(
                q, k, v, kc, vc, cache_layer, page_table, positions,
                seq_lens, scale=cfg.attn_scale, impl=attn_impl,
                kv_heads=cfg.kv_heads, plan=plan)

        x, _, pools = _block(cfg, x, layer, attend, rope)
        return x, pools

    x, (k_cache, v_cache) = _run_stack(cfg, params, x, layer_fn,
                                       (k_cache, v_cache))
    return _head(cfg, params, x), k_cache, v_cache


def forward_decode_block(cfg: TransformerConfig, params: dict,
                         ids: jax.Array, masked: jax.Array,
                         starts: jax.Array, seq_lens: jax.Array,
                         page_table: jax.Array, k_cache, v_cache,
                         attn_impl: str = "auto"):
    """One pass over each row's in-progress block of ``block_len``
    positions — ``forward_decode``'s sibling for generation by diffusion
    over blocks.

    ids [B, T] the block's tokens (``T = block_len``), masked [B, T] bool
    the positions still to be generated (they embed ``mask_id`` whatever
    ``ids`` holds there), starts [B] the block's first absolute position,
    seq_lens [B] = starts + T on live rows and 0 on idle rows.  Each
    attention layer writes the block's K/V into its pages — over what the
    pass before left there: a block's K/V stand only once a pass found
    nothing masked — then every position attends the row's whole context
    ``[0, seq_lens)``, earlier blocks and its own block alike, unmasked
    (``paged_attention.block_paged_attention``).  Returns (float32 logits
    [B, T, V], k_cache', v_cache', extras) — each position's logits
    predict that position's OWN token."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    t = ids.shape[1]
    with part("embed"):
        ids = jnp.where(masked, cfg.mask_id, ids)
        pos = jnp.clip(starts[:, None] + jnp.arange(t)[None, :], 0,
                       cfg.max_seq_len - 1)
    x, rope = _embed(cfg, params, ids, pos)
    live = seq_lens > 0
    new = jnp.where(live, t, 0)

    def attend(cache_layer, kc, vc, q, k, v):
        with part("kv.write"):
            pools = pa.write_chunk_kv(kc, vc, k, v, cache_layer, page_table,
                                      starts, new)
        return pa.block_paged_attention(
            q, *pools, cache_layer, page_table, seq_lens,
            scale=cfg.attn_scale, impl=attn_impl, kv_heads=cfg.kv_heads), pools

    if cfg.pattern is not None:
        def layer_fn(kind, i, layer, x, carry, pools):
            pools = list(pools)

            def attend_layer(q, k, v):
                a, pools[:] = attend(i, *pools, q, k, v)
                return a

            x, counts, carry = _pattern_layer(
                cfg, kind, layer, x, rope, attend_layer, None,
                jnp.broadcast_to(live[:, None], ids.shape), carry=carry)
            return x, counts, carry, tuple(pools), None

        x, counts, pools, _ = _run_pattern(cfg, params, x, layer_fn,
                                           (k_cache, v_cache))
        return (_head(cfg, params, x, jnp.float32), *pools,
                {"state": {}, "moe_counts": counts})

    def layer_fn(x, layer, cache_layer, kc, vc):
        x, _, pools = _block(cfg, x, layer,
                             functools.partial(attend, cache_layer, kc, vc),
                             rope)
        return x, pools

    x, (k_cache, v_cache) = _run_stack(cfg, params, x, layer_fn,
                                       (k_cache, v_cache))
    return _head(cfg, params, x, jnp.float32), k_cache, v_cache, {}


def _decode_pattern(cfg: TransformerConfig, params, x, rope, positions,
                    seq_lens, page_table, k_cache, v_cache, attn_impl, state,
                    mesh=None):
    """One token per row x [B, E] through a layer pattern.  Every pool —
    K, V and the state parts — is carried through the walk and rebound as
    a layer updates it at its own index (a Python number in the unrolled
    walk, traced in the rolled one): the buffers that entered the program
    are written where they are."""
    from paddle_tpu.ops import cca, kda, mamba1, mamba2
    from paddle_tpu.ops.pallas import paged_attention as pa

    live = seq_lens > 0

    with part("attn.core"):
        plan = pa.decode_plan(k_cache, page_table, positions, seq_lens,
                              cfg.kv_heads, cfg.head_dim)
    arrangement = {"mamba": ("conv", "ssm"), "kda": ("kda_conv", "kda_s"),
                   "mamba1": ("conv1", "ssm1")}
    # GSPMD cannot partition a Mosaic kernel: under a mesh the plain form
    ssd_impl = "auto" if mesh is None else "reference"
    # the differential layers keep each head's whole pair of value lanes
    wide = {"wide_v": True} if cfg.attn_diff else {}
    depths = cfg.diff_depths if cfg.attn_diff else {}
    if cfg.window_layers:
        # the rings ride with the state pools (``window_k``, ``window_v``:
        # ``PagedKVCache.window``); a slot's ring is its own run of pages,
        # the token goes to ``position mod window`` and the step reads the
        # ``min(length, window)`` entries there are, in whatever order
        w = cfg.attn_window
        with part("attn.core"):
            ring_table = pa.window_table(
                state["window_k"], w, jnp.arange(seq_lens.shape[0]), live)
            ring_at, ring_lens = positions % w, jnp.minimum(seq_lens, w)
            ring_plan = pa.decode_plan(
                state["window_k"], ring_table, ring_at, ring_lens,
                cfg.kv_heads, cfg.head_dim)

    def layer_fn(kind, i, layer, x, carry, held):
        pools, state = list(held[:2]), dict(held[2])

        def attend(q, k, v):
            shared = dict(scale=cfg.attn_scale, impl=attn_impl,
                          kv_heads=cfg.kv_heads, **wide)
            if kind == "window":
                a, ring = pa.decode_attention(
                    q, k, v, state["window_k"], state["window_v"], i,
                    ring_table, ring_at, ring_lens, plan=ring_plan, **shared)
                state["window_k"], state["window_v"] = ring
                return a
            if kind == "cross":     # another layer's cache layer: no write
                return pa.ragged_paged_attention(
                    q, *pools, cfg.cross_reads[i], page_table, seq_lens,
                    plan=plan, **shared)
            a, pools[:] = pa.decode_attention(
                q, k, v, *pools, i, page_table, positions, seq_lens,
                plan=plan, **shared)
            return a

        def keep(name, new):
            """Layer i's rows of pool ``name`` <- ``new`` on live rows."""
            old = state[name][i]
            mask = live.reshape(-1, *[1] * (old.ndim - 1))
            state[name] = state[name].at[i].set(
                jnp.where(mask, new.astype(old.dtype), old))

        def recurrent(conv_part, state_part):
            """A state layer's arrangement against row i of its two
            pools: the convolution's tail and the recurrence's state."""
            def conv(x, w, bias):
                out, new = mamba2.conv_step(state[conv_part][i], x, w, bias)
                keep(conv_part, new)
                return out

            def rule(*args):
                if kind == "mamba":     # the pool itself, live rows of row i
                    y, state[state_part] = mamba2.ssd_pool_step(
                        state[state_part], i, *args, live, impl=ssd_impl)
                    return y
                step = mamba1.scan_step if kind == "mamba1" else kda.kda_step
                y, new = step(state[state_part][i], *args)
                keep(state_part, new)
                return y

            return conv, rule

        def window(part, x, n):
            win, new = cca.window_step(state[part][i], x)
            keep(part, new)
            return win

        x, counts, carry = _pattern_layer(
            cfg, kind, layer, x, rope, attend,
            recurrent(*arrangement[kind]) if kind in arrangement else None,
            live, carry=carry, window=window,
            depth=depths[kind][i] if kind in depths else None)
        return x, counts, carry, (*pools, state), None

    x, counts, (k_cache, v_cache, state), _ = _run_pattern(
        cfg, params, x, layer_fn, (k_cache, v_cache, dict(state or {})))
    return (_head(cfg, params, x), k_cache, v_cache,
            {"state": state, "moe_counts": counts})


def loss_fn(cfg: TransformerConfig, params: dict, ids: jax.Array,
            mesh=None) -> jax.Array:
    """Next-token mean cross-entropy (targets = ids shifted left).

    Computed as logsumexp(logits) - logits[target] so the [B,T,V]
    log-softmax is never materialised (one fused f32 reduction instead of
    three full-vocab passes).  A Pallas fused-CE kernel exists
    (ops/pallas/softmax_xent.py) but measured SLOWER here (70.7 vs
    63.0 ms/step at the 124M bench): XLA fuses the CE chain into the
    LM-head backward matmuls, which the opaque pallas_call boundary
    prevents — kept as a library op and a documented negative result."""
    if cfg.block_len > 1:
        raise NotImplementedError(
            "loss_fn under block_len > 1: the diffusion objective (masked "
            "blocks beside their clean copy) is not built; next-token "
            "cross-entropy is not this model's loss")
    logits, aux = forward_with_aux(cfg, params, ids[:, :-1], mesh=mesh)
    with part("loss"):
        targets = ids[:, 1:]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None],
                                  axis=-1)[..., 0]
        ce = jnp.mean(lse - tgt.astype(jnp.float32))
        if cfg.moe_experts:
            ce = ce + cfg.moe_aux_weight * aux
        return ce


def build_train_step(cfg: TransformerConfig, optimizer, mesh=None,
                     compute_dtype=None, zero1=False, zero=None):
    """(params, opt_state, ids) -> (params, opt_state, loss), jitted.
    With a mesh: batch sharded ("data","seq" on time), params per TP layout;
    GSPMD inserts every collective.

    ``compute_dtype=jnp.bfloat16`` is the proper mixed-precision policy:
    master params (and Adam moments) stay f32; the forward/backward run on
    a bf16 cast, and the cast's cotangent upcasts grads back to f32.

    ``zero`` = 0|1|2 selects weight-update sharding over the ``data``
    axis (parallel/zero.py — the pserver's sharded-aggregation property,
    in-mesh): 1 pins the optimizer slots 1/n-sharded; 2 additionally
    replaces the gradient all-reduce with reduce-scatter + sharded
    update + parameter all-gather.  ``zero1=True`` is the original
    spelling of ``zero=1``.  Pair with ``zero.shard_opt_state`` for the
    initial state placement.

    On a pure-data mesh the zero=2 gradient flow is lowered explicitly
    (shard_map + ``collective.reduce_scatter``/``all_gather`` — the
    telemetry census sees the real payloads); with live TP/seq/expert
    axes the GSPMD constraint lowering is used (composes with the TP
    layout and the MoE expert axis)."""
    from paddle_tpu.parallel import zero as zero_mod

    zero = int(zero) if zero is not None else (1 if zero1 else 0)
    dp = mesh.shape.get("data", 1) if mesh is not None else 1
    zero_on = zero >= 1 and mesh is not None and dp > 1
    explicit = (zero_on and zero >= 2
                and zero_mod.explicit_lowering_ok(mesh))
    pspecs = param_shardings(cfg)

    def step(params, opt_state, ids):
        def lf(p, ids, inner_mesh):
            if compute_dtype is not None:
                from paddle_tpu.trainer.step import _cast_floats
                p = _cast_floats(p, compute_dtype)
            return loss_fn(cfg, p, ids, mesh=inner_mesh)

        gspecs = (zero_mod.grad_specs(params, mesh, param_specs=pspecs)
                  if zero_on else None)
        if explicit:
            from jax.sharding import PartitionSpec as P

            from paddle_tpu import compat

            def local_step(p, ids):
                # per-shard forward/backward: the data axis is manual
                # here, so inner batch constraints are skipped
                # (mesh=None) — on a pure-data mesh they were only
                # batch-dim hints
                loss, grads = jax.value_and_grad(lf)(p, ids, None)
                # loss_fn is a MEAN over the batch: the global value is
                # the pmean of equal-sized shard means, and the global
                # gradient is the 1/n-scaled psum of shard gradients —
                # scale before the (sum-)reduce-scatter
                loss = jax.lax.pmean(loss, "data")
                grads = jax.tree.map(lambda g: g / dp, grads)
                grads = zero_mod.sync_grads(grads, gspecs)
                return loss, grads

            region = compat.shard_map(
                local_step, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), params),
                          P("data", None)),
                out_specs=(P(), gspecs),
                check_vma=False)
            loss, grads = region(params, ids)
        else:
            loss, grads = jax.value_and_grad(lf)(params, ids, mesh)
            if zero_on and zero >= 2:
                grads = zero_mod.constrain_grads(grads, gspecs, mesh)
        with part("update"):
            new_params, new_opt = optimizer.apply_tree(grads, params,
                                                       opt_state)
        if zero_on:
            sspecs = zero_mod.state_specs(new_opt, params, mesh,
                                          param_specs=pspecs)
            new_opt = zero_mod.constrain_opt_state(new_opt, sspecs, mesh)
            if explicit:
                new_params = zero_mod.gather_params(new_params, gspecs,
                                                    mesh)
            elif zero >= 2:
                new_params = zero_mod.constrain_params(
                    new_params, mesh, param_specs=pspecs,
                    zero_specs=gspecs)
        return new_params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))
