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
    # -- the block's parts; the defaults are the GPT-2 block -------------
    # width of one attention head; None = embed_dim // num_heads
    head_dim: int | None = None
    # "layer" (scale + bias) | "rms" (scale only); ``norm_sandwich`` adds a
    # second norm on each branch's OUTPUT, before the residual add
    norm: str = "layer"
    norm_eps: float = 1e-5
    norm_sandwich: bool = False
    # "learned" (a [max_seq_len, E] table added to the embedding) |
    # "rotary" (rotate-half RoPE on q and k at ``rope_theta``; no table,
    # so max_seq_len costs nothing)
    positions: str = "learned"
    rope_theta: float = 1e4
    # "gelu" (w_in/w_out with biases) | "swiglu" (gate/up/down, no bias)
    mlp: str = "gelu"
    tie_embeddings: bool = True  # False: a separate [E, V] "head"
    # looped stack: the SAME num_layers weights run ``loop_steps`` times,
    # the final norm closing every pass; pass t's K/V of layer l live in
    # cache layer t * num_layers + l (``cache_layers`` pools).  The exit
    # gate's parameters (exit_w, exit_b) live in the tree; at threshold 1
    # no token leaves early and the served path never evaluates them.
    loop_steps: int = 1
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.embed_dim // self.num_heads)
        for field, allowed in (("norm", ("layer", "rms")),
                               ("positions", ("learned", "rotary")),
                               ("mlp", ("gelu", "swiglu"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"{field} must be one of {allowed}, got "
                                 f"{getattr(self, field)!r}")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps must be >= 1, got {self.loop_steps}")
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.early_exit_threshold} < 1 lets "
                "a token leave the loop before its last pass: a step count "
                "that varies by token, which neither the scans here nor the "
                "scheduler's equal-work-per-token batches have")

    @property
    def cache_layers(self) -> int:
        """K/V cache layers a served token occupies: one per (pass, layer)."""
        return self.num_layers * self.loop_steps

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


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    """Stacked-layer params: block weights have leading dim num_layers.
    Which leaves exist follows the config's parts (norm biases only under
    "layer", ``pos_embed`` only under "learned" positions, ``head`` only
    when untied, the exit gate only for a looped stack)."""
    e, h, m, v_sz = cfg.embed_dim, cfg.num_heads * cfg.head_dim, cfg.mlp_dim, cfg.vocab_size
    s = cfg.num_layers
    k = iter(jax.random.split(key, 14))
    norm = lambda *shape: jax.random.normal(next(k), shape, cfg.dtype)
    zeros = lambda *shape: jnp.zeros(shape, cfg.dtype)
    if cfg.moe_experts:
        ex = cfg.moe_experts
        ffn = {
            "wg": norm(s, e, ex) * (e ** -0.5),
            "w1": norm(s, ex, e, m) * (2.0 / e) ** 0.5,
            "b1": zeros(s, ex, m),
            "w2": norm(s, ex, m, e) * (m ** -0.5) / (2 * s) ** 0.5,
            "b2": zeros(s, ex, e),
        }
    elif cfg.mlp == "swiglu":
        ffn = {
            "w_gate": norm(s, e, m) * (e ** -0.5),
            "w_up": norm(s, e, m) * (e ** -0.5),
            "w_out": norm(s, m, e) * (m ** -0.5) / (2 * s) ** 0.5,
        }
    else:
        ffn = {
            "w_in": norm(s, e, m) * (e ** -0.5),
            "b_in": zeros(s, m),
            "w_out": norm(s, m, e) * (m ** -0.5) / (2 * s) ** 0.5,
            "b_out": zeros(s, e),
        }

    def norm_p(name, *lead):
        p = {f"{name}_g": jnp.ones((*lead, e), cfg.dtype)}
        if cfg.norm == "layer":
            p[f"{name}_b"] = zeros(*lead, e)
        return p

    params = {"embed": norm(v_sz, e) * (e ** -0.5)}
    pos = norm(cfg.max_seq_len, e) * 0.02 if cfg.positions == "learned" \
        else None
    blocks = {
        **norm_p("ln1", s),
        "wq": norm(s, e, h) * (e ** -0.5),
        "wk": norm(s, e, h) * (e ** -0.5),
        "wv": norm(s, e, h) * (e ** -0.5),
        "wo": norm(s, h, e) * (h ** -0.5) / (2 * s) ** 0.5,
        **norm_p("ln2", s),
        **ffn,
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


def _norm(cfg: TransformerConfig, x, p, name):
    """The config's norm over the last axis with the parameters
    ``p[name + "_g"]`` (and ``"_b"`` under "layer")."""
    if cfg.norm == "rms":
        xf = x.astype(jnp.float32)
        xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + cfg.norm_eps)
        return xf.astype(x.dtype) * p[name + "_g"]
    return _ln(x, p[name + "_g"], p[name + "_b"], cfg.norm_eps)


def _rope_table(cfg: TransformerConfig, positions):
    """(cos, sin) [..., 1, head_dim] float32 for integer ``positions``
    [...] — broadcast over the head axis of q/k [..., H, head_dim]."""
    half = cfg.head_dim // 2
    inv_freq = cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, table):
    """Rotate-half RoPE: dims (i, i + head_dim/2) are one pair."""
    cos, sin = table
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


def _embed(cfg: TransformerConfig, params, ids, positions=None):
    """Token states entering the stack, and the RoPE table of their
    positions (None under learned positions, which are added here).
    ``positions`` None = ids [B, T] sit at 0..T-1."""
    x = params["embed"][ids]
    if cfg.positions == "learned":
        x = x + (params["pos_embed"][:ids.shape[1]][None]
                 if positions is None else params["pos_embed"][positions])
        return x, None
    return x, _rope_table(cfg, jnp.arange(ids.shape[1])[None]
                          if positions is None else positions)


def _head(cfg: TransformerConfig, params, x):
    return x @ (params["embed"].T if cfg.tie_embeddings else params["head"])


def _attention(cfg: TransformerConfig, q, k, v, mesh):
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
            causal=True
        )
    if cfg.attn_impl == "flash":
        from paddle_tpu.ops.pallas import flash_attention

        bs = cfg.attn_block_size
        if mesh is None:
            return flash_attention(q, k, v, True, None, bs, bs)
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
            lambda q, k, v: flash_attention(q, k, v, True, None, bs, bs),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)
    t = q.shape[1]
    return attn_ops.dot_product_attention(
        q, k, v, mask=attn_ops.causal_mask(t, t)
    )


def _block(cfg: TransformerConfig, x, layer, attend, rope=None, mesh=None,
           remat_dots=False):
    """THE decoder block; x [..., E] (training and prefill [B, T, E],
    decode [B, E]).  ``attend(q, k, v) -> (a, kept)`` is the caller's
    cache write and attention over q/k/v [..., H, Dh] (RoPE already
    applied from ``rope``); ``kept`` is handed back untouched — the K/V a
    prefill captures, the pools a chunk or decode pass updated, None in
    training.  Returns (x, aux, kept); aux is the MoE load-balancing
    loss, None for a dense FFN.

    ``remat_dots`` checkpoints the two dense segments with the
    dots-saveable policy while leaving the attention call OUTSIDE any
    checkpoint: a policy cannot save a custom-vjp's internal residuals
    (the flash kernel's log-sum-exp), so a whole-block checkpoint re-runs
    the flash forward in the backward scan — measured 9 ms/step at the
    124M bench shape."""
    lead = x.shape[:-1]
    nh, hd = cfg.num_heads, cfg.head_dim

    def qkv_fn(x, layer):
        h = _norm(cfg, x, layer, "ln1")
        q = (h @ layer["wq"]).reshape(*lead, nh, hd)
        k = (h @ layer["wk"]).reshape(*lead, nh, hd)
        v = (h @ layer["wv"]).reshape(*lead, nh, hd)
        if rope is not None:
            q, k = _rope(q, rope), _rope(k, rope)
        return q, k, v

    def branch_out(x, y, bias, post):
        """The residual add of one branch: under a sandwich the branch's
        output (bias included) is normed first; otherwise the bias goes on
        after the add, GPT-2's order of rounding."""
        if cfg.norm_sandwich:
            return x + _norm(cfg, y if bias is None else y + bias, layer,
                             post)
        x = x + y
        return x if bias is None else x + bias

    def tail_fn(x, a, layer):
        x = branch_out(x, a.reshape(*lead, nh * hd) @ layer["wo"], None,
                       "ln1_post")
        h = _norm(cfg, x, layer, "ln2")
        aux, bias = None, None
        if cfg.moe_experts:
            from paddle_tpu.parallel.moe import moe_ffn, moe_ffn_sharded

            moe_p = {n: layer[n] for n in ("wg", "w1", "b1", "w2", "b2")}
            if mesh is not None and "expert" in mesh.axis_names:
                y, aux = moe_ffn_sharded(moe_p, h, cfg.moe, mesh)
            else:
                y, aux = moe_ffn(moe_p, h, cfg.moe)
        elif cfg.mlp == "swiglu":
            y = (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])
                 ) @ layer["w_out"]
        else:
            h = jax.nn.gelu(h @ layer["w_in"] + layer["b_in"])
            y, bias = h @ layer["w_out"], layer["b_out"]
        return branch_out(x, y, bias, "ln2_post"), aux

    if remat_dots:
        policy = jax.checkpoint_policies.dots_saveable
        qkv_fn = jax.checkpoint(qkv_fn, policy=policy)
        tail_fn = jax.checkpoint(tail_fn, policy=policy)
    q, k, v = qkv_fn(x, layer)
    a, kept = attend(q, k, v)
    x, aux = tail_fn(x, a, layer)
    return x, aux, kept


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


def _dense_only(cfg: TransformerConfig) -> None:
    if cfg.moe_experts:
        raise NotImplementedError(
            "serving prefill/decode cover the dense-FFN transformer; "
            "quantized/MoE decode is future work")


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
    _dense_only(cfg)
    x, rope = _embed(cfg, params, ids)

    def layer_fn(x, layer):
        x, _, kv = _block(
            cfg, x, layer,
            lambda q, k, v: (_attention(cfg, q, k, v, mesh), (k, v)), rope)
        return x, kv

    x, (ks, vs) = _run_stack(cfg, params, x, layer_fn)
    return _head(cfg, params, _last_valid(x, seq_lens)), ks, vs


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
    _dense_only(cfg)
    from paddle_tpu.ops.pallas import paged_attention as pa

    b, c = ids.shape
    # padding of offset rows can index past max_seq_len — clip (valid
    # positions satisfy starts + t < max_prompt_len <= max_seq_len)
    pos = jnp.clip(starts[:, None] + jnp.arange(c)[None, :], 0,
                   cfg.max_seq_len - 1)
    x, rope = _embed(cfg, params, ids, pos)

    def layer_fn(x, layer, cache_layer, kc, vc):
        def attend(q, k, v):
            pools = pa.write_chunk_kv(kc, vc, k, v, cache_layer, page_table,
                                      starts, seq_lens)
            return pa.paged_prefill_attention(
                q, *pools, cache_layer, page_table, starts, seq_lens), pools

        x, _, pools = _block(cfg, x, layer, attend, rope)
        return x, pools

    x, (k_cache, v_cache) = _run_stack(cfg, params, x, layer_fn,
                                       (k_cache, v_cache))
    return _head(cfg, params, _last_valid(x, seq_lens)), k_cache, v_cache


def forward_decode(cfg: TransformerConfig, params: dict, ids: jax.Array,
                   positions: jax.Array, seq_lens: jax.Array,
                   page_table: jax.Array, k_cache, v_cache,
                   attn_impl: str = "auto", mesh=None):
    """One incremental decode step over the paged KV-cache.

    ids [B] current tokens, positions [B] their absolute indices,
    seq_lens [B] = positions + 1 on live rows and 0 on idle rows,
    page_table [B, max_pages], k_cache/v_cache the pools of
    ``paged_attention.kv_pool_shape`` (``init_kv_pages``).  Each block
    writes the new token's K/V into its pages, then runs ragged paged
    attention over the whole resident context.  Returns (logits [B, V],
    k_cache', v_cache'); idle rows write the null page and read zeros.

    ``attn_impl`` is the paged-attention implementation ("auto" =
    Pallas kernel on TPU, jnp reference elsewhere) — deliberately
    separate from ``cfg.attn_impl``, which describes TRAINING attention
    over contiguous sequences."""
    _dense_only(cfg)
    from paddle_tpu.ops.pallas import paged_attention as pa

    x, rope = _embed(cfg, params, ids, positions)

    def layer_fn(x, layer, cache_layer, kc, vc):
        def attend(q, k, v):
            pools = pa.write_decode_kv(kc, vc, k, v, cache_layer, page_table,
                                       positions)
            return pa.ragged_paged_attention(
                q, *pools, cache_layer, page_table, seq_lens,
                impl=attn_impl), pools

        x, _, pools = _block(cfg, x, layer, attend, rope)
        return x, pools

    x, (k_cache, v_cache) = _run_stack(cfg, params, x, layer_fn,
                                       (k_cache, v_cache))
    return _head(cfg, params, x), k_cache, v_cache


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
    logits, aux = forward_with_aux(cfg, params, ids[:, :-1], mesh=mesh)
    targets = ids[:, 1:]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
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
        new_params, new_opt = optimizer.apply_tree(grads, params, opt_state)
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
