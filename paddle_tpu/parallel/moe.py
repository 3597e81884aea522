"""Mixture-of-Experts with expert parallelism over an ``expert`` mesh axis.

The reference has no MoE (2017); its closest capability is the sparse
pserver path — only-touched rows move over the wire
(``SparseRemoteParameterUpdater``, ``SparseRowMatrix.h:204``).  This
module is the TPU-native upgrade of that idea, designed from the GShard /
Switch-Transformer formulation (PAPERS.md): conditional computation where
each token activates ``top_k`` of ``num_experts`` FFNs, experts are
sharded across devices, and tokens move to their experts via
``lax.all_to_all`` riding ICI — the role NCCL alltoall plays in GPU MoE
stacks.

Everything is static-shaped for XLA: routing assigns each (token,
choice) a fixed slot in its expert's capacity buffer (capacity ``C``
tokens per expert per group; overflow tokens are dropped, the standard
capacity-factor semantics).  Token movement has two equivalent forms —
``dispatch="sort"`` (default): scatter/gather by flat slot id, O(T·D)
data movement; ``dispatch="einsum"``: the GShard dense one-hot
``[T, E, C]`` dispatch/combine tensors.  Either way the layer is a few
array ops + one pair of all_to_alls, all differentiable (gates
included) under ``jax.grad``/``shard_map``.

Two execution paths with identical math:

- ``moe_ffn(...)``         — single-group dense dispatch (no mesh): the
                             reference implementation and single-chip path.
- ``moe_ffn_sharded(...)`` — tokens AND experts sharded over the mesh's
                             ``expert`` axis; per-shard routing (each shard
                             is one GShard "group"), all_to_all exchanges
                             ``[E, C, D] -> [E_local, shards*C, D]``,
                             local expert FFNs, all_to_all back, combine.

``aux_load_balancing_loss`` is the Switch loss: E * mean(load_fraction *
mean_gate_prob) per expert, pushing the router toward uniform load.

Beside the capacity paths, :func:`moe_routed` is the DROPLESS layer of
today's large sparse models, for a device that holds a contiguous share
``[lo, hi)`` of the experts (expert parallelism's unit; all of them on
one device): scores in float32 over ALL experts — sigmoid, or softmax
(``RoutedConfig.score``) — top-k of score + a selection-only correction
bias where the tree has one, the chosen scores normalised and scaled,
every assignment to a held expert computed whatever the load,
assignments to absent experts left to the devices that hold them, plus
an optional shared expert every token passes.  An expert is ``act(x
W_in) W_out`` or, gated (``RoutedConfig.gated``), ``(silu(x W_gate) * (x
W_in)) W_out``.  No exchange, no capacity, nothing that stands in for
the absent share.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from paddle_tpu.compat import shard_map
from paddle_tpu.parallel import collective
from paddle_tpu.telemetry.scopes import part, scoped


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    mlp_dim: int
    top_k: int = 2              # 1 = Switch routing, 2 = GShard routing
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    # token movement: "sort" (default) = scatter/gather by flat slot
    # index e*C+pos — O(T*D) data movement; "einsum" = the dense one-hot
    # GShard tensors [T,E,C], O(T*E*C*D) FLOPs.  Same routing decisions
    # exactly (tests pin value+grad equality); sort measured +56%/+36%
    # tok/s (top-2/top-1) at the 8-expert GPT-2-width bench shape.
    dispatch: str = "sort"

    def __post_init__(self):
        if self.top_k not in (1, 2):
            raise ValueError(
                f"top_k must be 1 (Switch) or 2 (GShard); got {self.top_k}")
        if self.dispatch not in ("einsum", "sort"):
            raise ValueError(
                f"dispatch must be 'einsum' or 'sort'; got {self.dispatch}")


def init_moe_params(key: jax.Array, embed_dim: int, cfg: MoEConfig,
                    dtype=jnp.float32) -> dict:
    """Router + per-expert FFN weights (experts stacked on axis 0)."""
    kg, k1, k2 = jax.random.split(key, 3)
    E, D, H = cfg.num_experts, embed_dim, cfg.mlp_dim
    return {
        "wg": (jax.random.normal(kg, (D, E)) * (1.0 / D ** 0.5)).astype(dtype),
        "w1": (jax.random.normal(k1, (E, D, H)) * (2.0 / D) ** 0.5).astype(dtype),
        "b1": jnp.zeros((E, H), dtype),
        "w2": (jax.random.normal(k2, (E, H, D)) * (1.0 / H) ** 0.5).astype(dtype),
        "b2": jnp.zeros((E, D), dtype),
    }


def capacity(tokens_per_group: int, cfg: MoEConfig) -> int:
    """Static per-expert buffer size for one routing group."""
    c = int(cfg.capacity_factor * cfg.top_k * tokens_per_group
            / cfg.num_experts)
    return max(c, 1)


def _positions(mask: jax.Array, cap: int, offset=None):
    """mask [T, E] 0/1 -> (kept mask [T, E], positions [T, E] float).

    A token's position inside its expert's buffer is its running count
    (cumsum over the group's token order); positions >= cap drop out —
    the deterministic, order-based capacity rule (GShard §3.2).
    """
    pos = jnp.cumsum(mask, axis=0) - 1.0
    if offset is not None:
        pos = pos + offset[None, :]
    keep = mask * (pos < cap).astype(mask.dtype)
    return keep, pos


def route_choices(x: jax.Array, wg: jax.Array, cfg: MoEConfig, cap: int):
    """Routing core shared by both dispatch forms.

    Returns (choices, aux): ``choices`` is a list over the top_k
    assignment slots of dicts with per-token ``eid`` (expert id, int),
    ``pos`` (position in the expert's capacity buffer, int), ``keep``
    (0/1 f32 survived capacity), and ``w`` (the renormalized combine
    weight, already zeroed for dropped tokens).  Gradients flow into
    the router through ``w``.

    Top-2 gate normalization convention (intentional divergence from
    GShard): the two gates are renormalized over the SURVIVING choices
    only — ``w_i = g_i * keep_i / max(g1*keep1 + g2*keep2, eps)`` — so a
    token whose first choice is capacity-dropped routes with full
    weight 1.0 to its second expert.  GShard's reference formulation
    normalizes by ``g1 + g2`` computed BEFORE capacity drops, which
    down-weights such tokens by their lost first-choice share.
    Post-drop renormalization keeps every surviving token's combine
    weights summing to 1 (no silent output scaling under congestion);
    switch the ``denom`` below to the pre-drop ``gate1 + gate2`` to
    reproduce GShard exactly.
    """
    f32 = jnp.float32
    logits = x.astype(f32) @ wg.astype(f32)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    E = cfg.num_experts

    idx1 = jnp.argmax(probs, axis=-1)                # [T]
    mask1 = jax.nn.one_hot(idx1, E, dtype=f32)
    gate1 = jnp.sum(probs * mask1, axis=-1)          # [T]

    # Switch aux loss over the FIRST choice: fraction of tokens routed
    # to each expert x mean router prob, scaled by E (minimum 1.0 at
    # uniform load)
    load = jnp.mean(mask1, axis=0)
    importance = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(load * importance)

    def per_token(grid, eid):
        return jnp.take_along_axis(grid, eid[:, None], axis=1)[:, 0]

    keep1g, pos1g = _positions(mask1, cap)
    k1 = per_token(keep1g, idx1)
    choices = [{"eid": idx1, "pos": per_token(pos1g, idx1).astype(jnp.int32),
                "keep": k1, "w": gate1 * k1}]

    if cfg.top_k >= 2:
        probs2 = probs * (1.0 - mask1)               # mask out the winner
        idx2 = jnp.argmax(probs2, axis=-1)
        mask2 = jax.nn.one_hot(idx2, E, dtype=f32)
        gate2 = jnp.sum(probs * mask2, axis=-1)
        # second choices queue BEHIND every first-choice token
        # (GShard: the expert's buffer fills greedily by priority)
        expert_load1 = jnp.sum(keep1g, axis=0)       # [E]
        keep2g, pos2g = _positions(mask2, cap, offset=expert_load1)
        k2 = per_token(keep2g, idx2)
        # renormalize the two gates over what survived
        g1, g2 = gate1 * k1, gate2 * k2
        denom = jnp.maximum(g1 + g2, 1e-9)
        choices[0]["w"] = g1 / denom * k1
        choices.append(
            {"eid": idx2, "pos": per_token(pos2g, idx2).astype(jnp.int32),
             "keep": k2, "w": g2 / denom * k2})
    return choices, aux


def route(x: jax.Array, wg: jax.Array, cfg: MoEConfig, cap: int):
    """Tokens [T, D] -> (dispatch [T,E,C], combine [T,E,C], aux_loss):
    the dense one-hot tensors built from ``route_choices``.  combine
    carries the (renormalized) gate probabilities; dispatch is its 0/1
    support."""
    choices, aux = route_choices(x, wg, cfg, cap)
    E = cfg.num_experts
    f32 = jnp.float32
    combine = 0.0
    for c in choices:
        oh = (jax.nn.one_hot(c["eid"], E, dtype=f32)[:, :, None]
              * jax.nn.one_hot(c["pos"], cap, dtype=f32)[:, None, :])
        combine = combine + c["w"][:, None, None] * oh  # w already keep-zeroed
    dispatch = (combine > 0.0).astype(f32)
    return dispatch, combine, aux


def _slot_ids(choices, E: int, cap: int):
    """Per choice: flat buffer slot e*C+pos for kept tokens, E*C (the
    junk row) for dropped ones."""
    return [jnp.where(c["keep"] > 0, c["eid"] * cap + c["pos"], E * cap)
            for c in choices]


def _scatter_tokens(x2, choices, E: int, cap: int):
    """Tokens -> expert buffers [E, C, D] by scatter (no [T,E,C] tensor).

    Slots are unique by construction (each (expert, pos<C) pair belongs
    to exactly one (token, choice)), so the scatter-add never collides
    except in the junk row."""
    d = x2.shape[1]
    slots = _slot_ids(choices, E, cap)
    s = jnp.concatenate(slots)
    upd = jnp.concatenate([x2] * len(choices), axis=0)
    xe_flat = jnp.zeros((E * cap + 1, d), x2.dtype).at[s].add(upd)
    return xe_flat[:-1].reshape(E, cap, d), slots


def _gather_tokens(ye, choices, slots):
    """Expert outputs [E, C, D] -> tokens [T, D] by weighted gather."""
    e, cap, d = ye.shape
    ye_pad = jnp.concatenate(
        [ye.reshape(e * cap, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    y = 0.0
    for c, s in zip(choices, slots):
        y = y + c["w"].astype(ye.dtype)[:, None] * ye_pad[s]
    return y


def _expert_ffn(w1, b1, w2, b2, xe):
    """xe [E, C, D] through each expert's FFN (batched einsum)."""
    f32 = jnp.float32
    h = jnp.einsum("ecd,edh->ech", xe, w1.astype(xe.dtype)) + b1[:, None, :]
    h = jax.nn.gelu(h.astype(f32)).astype(xe.dtype)
    return jnp.einsum("ech,ehd->ecd", h, w2.astype(xe.dtype)) + b2[:, None, :]


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig,
            cap: int | None = None):
    """Dense-dispatch MoE over one token group.

    x: [T, D] (or [B, T, D], flattened to one group).  Returns
    (y like x, aux_loss scalar).
    """
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    T = x2.shape[0]
    c = cap or capacity(T, cfg)
    E = cfg.num_experts
    if cfg.dispatch == "sort":
        choices, aux = route_choices(x2, params["wg"], cfg, c)
        xe, slots = _scatter_tokens(x2, choices, E, c)
        ye = _expert_ffn(params["w1"], params["b1"], params["w2"],
                         params["b2"], xe)
        y = _gather_tokens(ye, choices, slots)
    else:
        dispatch, combine, aux = route(x2, params["wg"], cfg, c)
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(x2.dtype), x2)
        ye = _expert_ffn(params["w1"], params["b1"], params["w2"],
                         params["b2"], xe)
        y = jnp.einsum("tec,ecd->td", combine.astype(x2.dtype), ye)
    return y.reshape(shape), aux


def moe_ffn_sharded(params: dict, x: jax.Array, cfg: MoEConfig, mesh,
                    axis: str = "expert",
                    batch_axes: tuple[str, ...] | None = None,
                    cap: int | None = None):
    """Expert-parallel MoE: tokens and experts sharded over ``axis``.

    x: [T, D] (or [B, T, D]) with the leading dim divisible by the
    sharding axes; params["w1"/"b1"/"w2"/"b2"] sharded on their expert
    dim, ``wg`` replicated.  Each shard routes its local tokens (one
    GShard "group"), all_to_all sends each expert's ``[E, C, D]`` slice
    to the expert's owner (becoming ``[E_local, n*C, D]``), the local
    FFNs run, and the reverse all_to_all brings expert outputs home for
    the combine.

    ``batch_axes``: additional mesh axes the token batch is sharded
    over (e.g. ``("data",)`` inside a dp+ep step) — experts stay
    replicated across them; the all_to_all runs within each batch
    slice.  Defaults to ``("data",)`` when the mesh has one.  Returns
    (y, aux_loss averaged over every shard).
    """
    n = mesh.shape[axis]
    E = cfg.num_experts
    if E % n:
        raise ValueError(f"num_experts {E} not divisible by mesh axis "
                         f"'{axis}' size {n}")
    if batch_axes is None:
        batch_axes = ("data",) if "data" in mesh.axis_names else ()
    n_tok_shards = n
    for a in batch_axes:
        n_tok_shards *= mesh.shape[a]
    T = x.reshape(-1, x.shape[-1]).shape[0]
    c = cap or capacity(T // n_tok_shards, cfg)
    all_axes = tuple(batch_axes) + (axis,)

    def body(wg, w1, b1, w2, b2, xs):
        x2 = xs.reshape(-1, xs.shape[-1])
        if cfg.dispatch == "sort":
            choices, aux = route_choices(x2, wg, cfg, c)
            xe, slots = _scatter_tokens(x2, choices, E, c)
        else:
            dispatch, combine, aux = route(x2, wg, cfg, c)
            xe = jnp.einsum("tec,td->ecd", dispatch.astype(x2.dtype), x2)
        # [E, C, D] -> [E_local, n*C, D]: tokens travel to expert owners
        # (collective.all_to_all: trace-annotated + comm-bytes-counted)
        xe = collective.all_to_all(xe, axis, split_axis=0, concat_axis=1,
                                   tiled=True)
        ye = _expert_ffn(w1, b1, w2, b2, xe)
        # [E_local, n*C, D] -> [E, C, D]: results return to token owners
        ye = collective.all_to_all(ye, axis, split_axis=1, concat_axis=0,
                                   tiled=True)
        if cfg.dispatch == "sort":
            y = _gather_tokens(ye, choices, slots)
        else:
            y = jnp.einsum("tec,ecd->td", combine.astype(x2.dtype), ye)
        return y.reshape(xs.shape), lax.pmean(aux, all_axes)

    tok = P(all_axes) if x.ndim == 2 else P(all_axes, *([None] * (x.ndim - 1)))
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None),
                  P(axis, None, None), P(axis, None), tok),
        out_specs=(tok, P()),
        check_vma=False,
    )
    return fn(params["wg"], params["w1"], params["b1"], params["w2"],
              params["b2"], x)


# -- dropless routing over a held share ----------------------------------------

# Up to this many rows every held expert runs over every row (masked by
# the combine weight): the layer streams its weights once, which is what a
# decode batch costs whatever the arrangement, and on a v5e the MXU hides
# the wasted rows for a while — both matmuls over 64 experts of 2688 x
# 1856 take 1.8 / 2.0 / 3.5 / 7.1 ms at 64 / 256 / 512 / 1024 rows (PERF.md
# section 6, PRs 30 and 47).  Above it the assignments are sorted by expert
# and go through the grouped product (``ops/pallas/grouped_matmul.py``),
# which multiplies only the assignments made to experts held here and
# reads each expert's matrices once: 40 held experts of 4096 x 1280, three
# matrices, take 3.7 ms at 2,048 rows and 5.7 at 4,096 (gate | up 1.46,
# down 0.74, the rest the sort, the gather and the scatter-add in XLA),
# where the three ``lax.ragged_dot`` calls it replaced took 6.5 and 8.6
# (PERF.md section 6, PR 47).
DENSE_MAX_TOKENS = 2048
# The masked product does ``num_experts / top_k`` times the assigned work,
# and the limit above was measured at 21 x (128 experts, top-6; 16 x at
# 128 / 8 and 16 / 1 read alike).  Past 32 x the limit falls in proportion
# -- ``_DENSE_WASTE * top_k // num_experts`` rows, 1,638 at 320 / 8's 40 x
# -- and is never raised: 40 held experts of 4096 x 1280 over 2,048 rows
# take 15.3 ms masked (7.7 where a bucket of 1,024 holds the live rows).
# Both limits date from ``ragged_dot``'s fixed 4.6 ms a sublayer; against
# the kernel the masked product of that router loses from 256 rows on
# (2.4 | 2.1 ms, 4.4 | 2.4 at 600, 11.8 | 3.1 at 1,638: PERF.md section 7
# has what lowering them would take).
_DENSE_WASTE = 65_536
# The masked product streams every held expert's matrices whatever the
# rows; the kernel streams those of the experts its rows chose.  Where an
# even router is expected to leave more than one held expert in seven without
# a row -- ``1 - (1 - top_k / num_experts) ** rows`` under this share --
# a pass under the limits above takes the kernel all the same: 32 rows of
# 8 / 320 (55% touched) 1.83 ms masked | 1.15 through the kernel, 64 rows
# (80%) 1.85 | 1.52; at 95-98% the masked product is level or ahead (128
# rows of 8 / 320 2.08 | 1.92, 64 rows of 6 / 128 1.81 | 2.03, of 1 / 16
# 0.65 | 0.67: PERF.md section 6, PR 47).
_SPARSE_SHARE = 0.85
# a padded pass is mostly padding: its live rows are gathered to the front
# and the masked product runs over the smallest of these row counts that
# holds them (chosen at run time from ``live``; the last is all rows)
DENSE_BUCKETS = (256, 512, 1024)


@dataclass(frozen=True)
class RoutedConfig:
    num_experts: int            # the router's width: ALL experts
    top_k: int
    scale: float = 1.0          # on the normalised weights of the chosen
    held: tuple = None          # [lo, hi) of the experts computed here
    act: str = "relu2"          # "relu2" | "gelu" | "silu" (no bias)
    # what the router's logits become before the top-k: "sigmoid" (each
    # expert scored alone) | "softmax" (over all ``num_experts``); under
    # ``renorm`` the chosen scores are normalised to sum to ``scale``
    score: str = "sigmoid"
    # True: an expert is (act(x W_gate) * (x W_in)) W_out — SwiGLU with
    # ``act="silu"`` — and the tree holds ``w_gate`` beside ``w_in``
    gated: bool = False
    # 0: the logits are one product of the token's state; > 0: they come
    # from an MLP of this width over the ROUTER's state, which the caller
    # carries from routed layer to routed layer (``router_state``,
    # ``route_mlp``; its RMSNorm at ``eps``)
    router_hidden: int = 0
    eps: float = 1e-5
    # False: a chosen expert weighs its own score x ``scale``, whatever
    # the others chosen with it scored
    renorm: bool = True

    def __post_init__(self):
        held = (0, self.num_experts) if self.held is None else tuple(self.held)
        object.__setattr__(self, "held", held)
        lo, hi = held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"held experts [{lo}, {hi}) must lie inside "
                             f"[0, {self.num_experts})")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} outside [1, "
                             f"{self.num_experts}]")
        if self.score not in ("sigmoid", "softmax"):
            raise ValueError(f"score must be 'sigmoid' or 'softmax', got "
                             f"{self.score!r}")

    @property
    def num_held(self) -> int:
        return self.held[1] - self.held[0]


# (one function object a name: the grouped kernel's jit is keyed by it)
_ACTS = {"relu2": lambda h: jnp.square(jax.nn.relu(h)),
         "gelu": jax.nn.gelu, "silu": jax.nn.silu}


def _act(name: str, h):
    return _ACTS[name](h)


def _choose(s, bias, cfg: RoutedConfig):
    """Router logits s [T, X] float32 -> (expert ids [T, k], weights
    [T, k]).  The correction ``bias`` (None: the router has none) moves
    which experts are chosen and never what they weigh."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(s) if cfg.score == "sigmoid" else jax.nn.softmax(s, -1)
    _, idx = lax.top_k(s if bias is None else s + bias.astype(f32),
                       cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if not cfg.renorm:
        return idx, w * cfg.scale
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * cfg.scale


def route_topk(x2, router, bias, cfg: RoutedConfig):
    """The published linear router, in float32: (expert ids [T, k],
    weights [T, k]) of token states x2 [T, D]."""
    f32 = jnp.float32
    s = jnp.dot(x2.astype(f32), router.astype(f32),
                precision=lax.Precision.HIGHEST)
    return _choose(s, bias, cfg)


def router_state(params: dict, x, prev):
    """An MLP router's state of this layer, float32 [..., R]: the token's
    normed state x [..., D] projected down, plus the state of the routed
    layer before (``prev``, zeros before the first) decayed by this
    layer's own vector — an exponential average over DEPTH, which is why
    the caller carries it from layer to layer beside x."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    z = jnp.dot(x.astype(f32), params["router_down"].astype(f32),
                precision=hi) + params["router_down_b"].astype(f32)
    return z + params["router_decay"].astype(f32) * prev


def route_mlp(r2, params: dict, cfg: RoutedConfig):
    """The MLP router over its state r2 [T, R] (``router_state``), in
    float32: RMSNorm, two GELU layers of width R, ``router`` [R, X] to
    the logits; (expert ids [T, k], weights [T, k])."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    h = r2 * lax.rsqrt(jnp.mean(r2 * r2, axis=-1, keepdims=True) + cfg.eps) \
        * params["router_norm_g"].astype(f32)
    for name in ("router_w1", "router_w2"):
        h = jax.nn.gelu(jnp.dot(h, params[name].astype(f32), precision=hi),
                        approximate=False)
    s = jnp.dot(h, params["router"].astype(f32), precision=hi)
    return _choose(s, params.get("router_bias"), cfg)


def product_path(t: int, cfg: RoutedConfig, w_in, impl: str = "auto") -> str:
    """The arrangement :func:`moe_routed` gives the expert product of a
    pass of ``t`` rows over matrices ``w_in`` [held, D, F] — read at trace
    time from shapes alone, so a caller that builds a program can say what
    it was built with: "masked" (every held expert over every row), or the
    sorted side's route — "kernel" (``ops/pallas/grouped_matmul.py``),
    "reference" (``lax.ragged_dot``: ``impl``'s answer off a TPU) or
    "reference_shape" (matrices the kernel takes no block of)."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    _, d, f = w_in.shape
    route = gm.route(impl, (d, f, w_in.dtype, cfg.gated), (f, d, w_in.dtype))
    if t > min(DENSE_MAX_TOKENS, _DENSE_WASTE * cfg.top_k // cfg.num_experts):
        return route
    # few rows of a wide router leave held experts without a row, and the
    # kernel (it alone) does not read those
    touched = 1.0 - (1.0 - cfg.top_k / cfg.num_experts) ** t
    return route if route == "kernel" and touched < _SPARSE_SHARE \
        else "masked"


@scoped("ffn")
def _shared_expert(params: dict, x2, cfg: RoutedConfig):
    f32 = jnp.float32
    hs = jnp.dot(x2, params["shared_in"], preferred_element_type=f32)
    if "shared_gate" in params:
        hs = hs * _act(cfg.act, jnp.dot(x2, params["shared_gate"],
                                        preferred_element_type=f32))
    else:
        hs = _act(cfg.act, hs)
    return jnp.dot(hs.astype(x2.dtype), params["shared_out"],
                   preferred_element_type=f32)


@scoped("moe.route")
def moe_routed(params: dict, x: jax.Array, cfg: RoutedConfig, live=None,
               carry=None, impl: str = "auto"):
    """x [..., D] -> (y like x, counts int32 [4]).  ``carry`` [..., R]:
    this layer's router state under ``cfg.router_hidden``
    (``router_state``), which then chooses the experts in x's place.
    ``impl``: the sorted side's kernel-or-reference choice
    (:func:`product_path`; the routing census says ``moe_experts``).

    ``params``: ``router`` [D, X] and, optionally, ``router_bias`` [X]
    over all X experts; ``w_in`` [held, D, F] and ``w_out`` [held, F, D]
    of the held ones (gated: ``w_gate`` [held, D, F] too); optionally
    ``shared_in`` [D, S] / ``shared_out`` [S, D] (gated: ``shared_gate``
    [D, S] too).  ``y``
    is the held experts' part of the layer's result plus the shared
    expert.  ``live`` (bool, x's leading shape) masks rows that are no
    token (idle decode rows, padding): they are routed nowhere.
    ``counts`` = assignments on held experts, assignments on absent ones,
    held experts with at least one token, the busiest held expert's
    tokens — over live rows.

    Its parts (``telemetry/scopes.py``): ``moe.route`` — the router, the
    top-k, the sort and the gather / scatter-add around the products;
    ``moe.product`` — the expert matrices; the shared expert is ``ffn``."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import note_route, resolve_interpret

    f32 = jnp.float32
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    t, k = x2.shape[0], cfg.top_k
    lo, hi = cfg.held
    if cfg.router_hidden:
        idx, w = route_mlp(carry.reshape(t, cfg.router_hidden), params, cfg)
    else:
        idx, w = route_topk(x2, params["router"], params.get("router_bias"),
                            cfg)
    held = (idx >= lo) & (idx < hi)
    if live is not None:
        alive = live.reshape(-1, 1)
        absent = jnp.sum(~held & alive)
        held = held & alive
    else:
        absent = jnp.sum(~held)
    w = jnp.where(held, w, 0.0)
    local = jnp.where(held, idx - lo, cfg.num_held)   # num_held = nowhere
    w_in, w_out = params["w_in"], params["w_out"]
    w_gate = params["w_gate"] if cfg.gated else None
    path = product_path(t, cfg, w_in, impl)
    note_route("moe_experts", path)
    if path == "masked":
        # [T, held] combine weights; every held expert over every row
        comb = jnp.sum(w[..., None] * (local[..., None] == jnp.arange(
            cfg.num_held)), axis=1)
        loads = jnp.sum(comb > 0, axis=0)

        @scoped("moe.product")
        def masked(rows, comb):
            h = jnp.einsum("td,xdf->xtf", rows, w_in,
                           preferred_element_type=f32)
            if cfg.gated:
                h = h * _act(cfg.act, jnp.einsum(
                    "td,xdf->xtf", rows, w_gate, preferred_element_type=f32))
            else:
                h = _act(cfg.act, h)
            h = (h * comb.T[..., None]).astype(x.dtype)
            return jnp.einsum("xtf,xfd->td", h, w_out,
                              preferred_element_type=f32)

        buckets = [b for b in DENSE_BUCKETS if b < t]
        if live is None or not buckets:
            y = masked(x2, comb)
        else:
            # live rows first; the rest of a bucket weighs nothing
            front = jnp.argsort(~live.reshape(-1), stable=True)

            def over(n):
                idx = front[:n]
                return lambda: jnp.zeros((t, shape[-1]), f32).at[idx].set(
                    masked(x2[idx], comb[idx]))

            y = lax.switch(
                jnp.searchsorted(jnp.asarray(buckets), jnp.sum(live)),
                [over(b) for b in buckets] + [lambda: masked(x2, comb)])
    else:
        # assignments sorted by held expert (the absent ones last); only
        # the held ones are gathered and multiplied, each expert's rows
        # through its own matrices (the grouped product: gate | up to the
        # activations' type, then down), ``bound`` sorted assignments at a time
        # -- a static count a quarter above the held share of an even
        # router -- as often as the count of held ones asks (a held eighth
        # of the experts: one round, an eighth of the rows and of ``ys``)
        flat = local.reshape(-1)
        order = jnp.argsort(flat)
        loads = jnp.bincount(flat, length=cfg.num_held + 1)[:-1]
        ends = jnp.cumsum(loads)
        bound = min(t * k, -(-5 * t * k * cfg.num_held
                             // (4 * cfg.num_experts)) // 512 * 512 + 512)
        order_p = jnp.pad(order, (0, bound))
        ws_all = w.reshape(-1)
        if path == "kernel":
            product = functools.partial(gm.grouped_matmul_kernel,
                                        interpret=resolve_interpret(None))
        else:
            product = gm.grouped_matmul_reference
        act = _ACTS[cfg.act]

        def gathered(c, y):
            at = c * bound
            sel = lax.dynamic_slice(order_p, (at,), (bound,))
            # this round's rows of every expert's group
            sizes = jnp.clip(ends - at, 0, bound) \
                - jnp.clip(ends - loads - at, 0, bound)
            rows = x2[sel // k]
            with part("moe.product"):
                h = product(rows, w_in, sizes, w_gate, act, x.dtype)
                ys = product(h, w_out, sizes)
            ws = jnp.where(at + jnp.arange(bound) < ends[-1], ws_all[sel],
                           0.0)
            ys = jnp.where(ws[:, None] > 0, ys * ws[:, None], 0.0)
            return y.at[sel // k].add(ys)

        y = jnp.zeros((t, shape[-1]), f32)
        if bound == t * k:      # every assignment fits one round
            y = gathered(0, y)
        else:
            y = lax.fori_loop(0, -(-ends[-1] // bound), gathered, y)
    if "shared_in" in params:
        y = y + _shared_expert(params, x2, cfg)
    counts = jnp.stack([jnp.sum(held), absent, jnp.sum(loads > 0),
                        jnp.max(loads)]).astype(jnp.int32)
    return y.astype(x.dtype).reshape(shape), counts


def place_moe_params(params: dict, mesh, axis: str = "expert") -> dict:
    """Device-put expert-stacked weights sharded over ``axis``."""
    from jax.sharding import NamedSharding

    def put(name, v):
        if name == "wg":
            return jax.device_put(v, NamedSharding(mesh, P()))
        spec = P(axis, *([None] * (v.ndim - 1)))
        return jax.device_put(v, NamedSharding(mesh, spec))

    return {k: put(k, v) for k, v in params.items()}
