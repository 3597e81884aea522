"""Layer-graph core: LayerOutput nodes + evaluation context.

The reference builds a ``ModelConfig`` proto from layer-helper calls
(``config_parser.py``), then C++ materializes ``Layer`` objects with
``forward``/``backward`` (``paddle/gserver/layers/Layer.h:62``).  Here each
helper call creates a :class:`LayerOutput` node carrying (a) a config record
(`attrs`, the ModelConfig analog, used for golden-serialization tests), (b)
parameter/state specs, and (c) a pure forward function.  ``backward`` does not
exist anywhere: ``jax.grad`` of the compiled forward is the whole autodiff
story (replacing per-layer backward + ``framework/backward.cc``)."""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Sequence

import jax

from paddle_tpu.core.enforce import enforce, error_scope
from paddle_tpu.core.lod import NestedSequenceBatch, SequenceBatch
from paddle_tpu.core.parameters import ParamSpec
from paddle_tpu.telemetry.scopes import part

Value = Any  # jax.Array | SequenceBatch | NestedSequenceBatch


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """Non-trainable persistent state (BN moving stats, etc.)."""

    name: str
    shape: tuple[int, ...]
    init_value: float = 0.0
    dtype: Any = None


class Context:
    """Per-step evaluation context: train/test mode + per-layer RNG keys."""

    def __init__(self, is_train: bool, key: jax.Array | None = None):
        self.is_train = is_train
        self._key = key

    def key_for(self, name: str) -> jax.Array:
        enforce(self._key is not None, f"layer {name!r} needs an RNG key")
        # deterministic per-layer stream derived from the step key (crc32 so
        # runs are replayable across processes, unlike salted hash())
        import zlib

        h = zlib.crc32(name.encode()) & 0x7FFFFFFF
        return jax.random.fold_in(self._key, h)


_name_counters: dict[str, itertools.count] = {}

# every LayerOutput registers here at construction, in creation order — the
# analog of config_parser's g_layer_map/g_config.model_config.layers, which
# appends a LayerConfig per helper call.  Proto emission walks this (not the
# DFS order) so protostr layer ordering matches the reference byte-for-byte.
# Strong references on purpose: nodes are frequently created inline
# (``outputs(classification_cost(...))``) with no other owner, and emission
# must still see them.  Like the reference's ``g_config`` globals, the
# registry grows until ``reset_name_counters()`` — which every model builder
# and ``parse_config`` call first (≅ ``init_config_environment``).
_layer_registry: list["LayerOutput"] = []


def layer_registry() -> list["LayerOutput"]:
    return list(_layer_registry)


def gen_name(layer_type: str) -> str:
    c = _name_counters.setdefault(layer_type, itertools.count())
    return f"__{layer_type}_{next(c)}__"


def reset_name_counters() -> None:
    _name_counters.clear()
    _layer_registry.clear()
    # config-level g_default_* must not outlive the model build they were
    # declared in (every model builder/test resets counters first)
    from paddle_tpu.config import parse_state

    parse_state.reset_defaults()


@dataclasses.dataclass(eq=False)
class LayerOutput:
    """A node in the layer DAG (≅ v2 ``LayerOutput`` over a LayerConfig)."""

    name: str
    layer_type: str
    size: int  # output feature size (v2 `size` semantics); 0 if n/a
    parents: tuple["LayerOutput", ...] = ()
    param_specs: tuple[ParamSpec, ...] = ()
    state_specs: tuple[StateSpec, ...] = ()
    fn: Callable | None = None  # (ctx, params, states, *parent_values) -> value | (value, states)
    attrs: dict = dataclasses.field(default_factory=dict)
    # height/width for image layers (ModelConfig LayerConfig.height/width)
    height: int = 0
    width: int = 0
    depth: int = 1  # channels for image layers

    def __post_init__(self):
        _layer_registry.append(self)

    def config_record(self) -> dict:
        """Serializable config (the ModelConfig-protostr analog for golden tests)."""
        return {
            "name": self.name,
            "type": self.layer_type,
            "size": self.size,
            "inputs": [p.name for p in self.parents],
            "attrs": {k: v for k, v in sorted(self.attrs.items()) if _jsonable(v)},
            "params": [
                {"name": s.name, "shape": list(s.shape)} for s in self.param_specs
            ],
        }

    def __repr__(self):
        return f"LayerOutput({self.name}, type={self.layer_type}, size={self.size})"


def _jsonable(v) -> bool:
    if isinstance(v, (int, float, str, bool, type(None))):
        return True
    if isinstance(v, (list, tuple)):
        # containers must be jsonable all the way down (runtime attrs
        # like __emit_parent_nodes__ hold LayerOutput objects)
        return all(_jsonable(x) for x in v)
    return False


def topo_sort(outputs: Sequence[LayerOutput]) -> list[LayerOutput]:
    """Deterministic post-order DFS over parents (≅ config_parser's layer
    ordering; NeuralNetwork executes layers in config order)."""
    seen: dict[int, LayerOutput] = {}
    order: list[LayerOutput] = []

    def visit(node: LayerOutput, stack: set[int]):
        nid = id(node)
        if nid in seen:
            return
        enforce(nid not in stack, f"cycle in layer graph at {node.name!r}")
        stack.add(nid)
        for p in node.parents:
            visit(p, stack)
        stack.remove(nid)
        seen[nid] = node
        order.append(node)

    for out in outputs:
        visit(out, set())
    return order


def evaluate(
    nodes: Sequence[LayerOutput],
    ctx: Context,
    params: dict[str, jax.Array],
    states: dict[str, jax.Array],
    feed: dict[str, Value],
    taps: dict[str, jax.Array] | None = None,
) -> tuple[dict[str, Value], dict[str, jax.Array]]:
    """Evaluate the DAG once; returns ({layer_name: value}, new_states).

    ``taps`` adds a zero-valued array to the named layers' outputs; taking
    jax.grad of a cost w.r.t. the tap yields d(cost)/d(layer) — the
    mechanism behind gradient_printer_evaluator (GradientPrinter's backward
    hook in the reference)."""
    values: dict[str, Value] = {}
    new_states = dict(states)
    for node in topo_sort(nodes):
        if node.fn is None and node.name in feed:
            # leaves only — data layers and injected recurrent_group leaves
            # (placeholders, memories).  Computed layers (fn set) are never
            # shadowed by a same-named feed key.
            values[node.name] = feed[node.name]
            continue
        if node.layer_type == "data":
            enforce(node.name in feed, f"missing feed for data layer {node.name!r}")
            values[node.name] = feed[node.name]
            continue
        parent_vals = [values[p.name] for p in node.parents]
        pvals = {s.name: params[s.name] for s in node.param_specs}
        svals = {s.name: new_states[s.name] for s in node.state_specs}
        # every operation the node traces says whose it is: the part is
        # the layer TYPE (telemetry/scopes.py)
        with error_scope(node.name), \
                part(f"{node.layer_type}/{node.name}"):
            result = node.fn(ctx, pvals, svals, *parent_vals)
        if isinstance(result, tuple) and len(result) == 2 and isinstance(result[1], dict):
            value, supd = result
            new_states.update(supd)
        else:
            value = result
        if taps and node.name in taps:
            tap = taps[node.name]
            if isinstance(value, SequenceBatch):
                value = SequenceBatch(data=value.data + tap,
                                      length=value.length)
            else:
                value = value + tap
        values[node.name] = value
    return values, new_states


# ---- value helpers shared by layer impls -----------------------------------


IDS_SUFFIX = "#ids"  # dual-output companions (crf_decoding's path side)


def companion_name(name: str) -> str:
    """Hidden runtime-only companion carrying a layer's ids side (the
    reference Argument's value/ids duality)."""
    return name + IDS_SUFFIX


def is_sequence(v: Value) -> bool:
    return isinstance(v, SequenceBatch)


def is_nested_sequence(v: Value) -> bool:
    return isinstance(v, NestedSequenceBatch)


def raw(v: Value):
    """Underlying dense array."""
    if isinstance(v, (SequenceBatch, NestedSequenceBatch)):
        return v.data
    return v


def map_data(fn: Callable, v: Value) -> Value:
    """Apply fn to the dense data, preserving sequence metadata.  This is how
    per-timestep layers (fc, mixed, activation...) act on sequence input, like
    the reference running them over the flattened [sum_len, D] Argument."""
    if isinstance(v, SequenceBatch):
        return SequenceBatch(data=fn(v.data), length=v.length)
    if isinstance(v, NestedSequenceBatch):
        return NestedSequenceBatch(
            data=fn(v.data), seq_length=v.seq_length, sub_length=v.sub_length
        )
    return fn(v)


def like(v: Value, data) -> Value:
    if isinstance(v, SequenceBatch):
        return SequenceBatch(data=data, length=v.length)
    if isinstance(v, NestedSequenceBatch):
        return NestedSequenceBatch(
            data=data, seq_length=v.seq_length, sub_length=v.sub_length
        )
    return data
