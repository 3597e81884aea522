"""Parts of a device program: which sublayer issued which operation.

The spans of :mod:`tracing` end at a dispatch; what runs behind it is one
XLA program whose operations carry XLA's own names (``%fusion.835``).
This module reaches inside with what jax already keeps for every
operation, its NAME STACK: the program's source wraps each sublayer in
``with part("attn.qkv"):`` (a ``jax.named_scope`` of ``pt:attn.qkv``),
the label rides every operation traced inside — through ``jvp``,
``transpose``, ``scan``, ``checkpoint``, ``shard_map`` and inner ``jit``\\ s
— into the ``metadata={op_name=...}`` of the optimized HLO and into the
``tf_op`` of a profile's "XLA Ops" events.  Scopes are metadata only: the
optimized program, metadata stripped, is what it was
(``tests/test_op_scopes.py`` holds it), so they are always on.

- :func:`part` — the scope.  The names in use (PERF.md section 3 has the
  metric that reads each): ``embed``, ``norm`` (norms and the residual
  adds), ``attn.qkv``, ``cca.conv``, ``attn.core``, ``attn.out``,
  ``kv.write``, ``ffn``, ``moe.route``, ``moe.product``,
  ``mamba2.proj`` / ``.conv`` / ``.scan``, ``mamba1.proj`` / ``.conv`` /
  ``.scan``, ``kda.proj`` / ``.conv`` / ``.rule``, ``gmu.proj``,
  ``head``, ``sample``, ``stack`` (the layer loop's own: a layer's
  weights sliced out of the stacked tree, the counters, the routing
  counts summed); on the v2 surface ``<layer type>/<layer name>`` a node of
  the layer graph (the part is the layer TYPE; inside a fused
  ``conv_bn`` node ``conv`` and ``batch_norm``), ``loss`` and ``update``
  in the train step.  ``parallel/collective.py``'s
  ``comm.<op>.<axis>`` scopes read as ``comm``.
- :func:`part_of` — an operation's innermost part and its direction.
- :func:`op_scopes` — a compiled program's operations by part, from its
  own text (what the compiler made itself, a weight's prefetch, goes to
  the operation that reads it): what a held program says of itself under
  an armed tracer
  (:func:`compile_described`: the ``program_ready`` spans' ``op_scopes``
  and ``routes``), so a reader that has only the profile's operation
  names (``benchmarks/reducers/trace_scope_ms.py``) can sum them by part.
  The operator's reader, with ``tf_op`` in hand, is
  ``profiler.device_ms_by_part``.

jax's compilation-cache key leaves metadata out: an executable fetched
from an entry that a checkout WITHOUT these scopes wrote has none, and
:func:`compile_described` compiles it again past the cache (traced runs
only, and as long as that entry lives; the cache is left as it is).  An
entry written under OTHER names for the same program would be read as
that checkout named it: rename a scope only together with the program it
is in.
"""

from __future__ import annotations

import functools
import re

PREFIX = "pt:"
UNSCOPED = "unscoped"
BWD = "|bwd"        # an ``op_scopes`` key of the backward pass: part + BWD

# a label opens a path element of the name stack: "pt:<part>" or one of
# the collective wrappers' "comm.<op>.<axis>"
_LABEL = re.compile(r"(?:^|[/(])(pt:|comm\.)([^/()]+)")
# "  [ROOT] %name = <type> opcode(", the type possibly a tuple
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:condition|body|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
# containers: their bodies' operations are instructions (and events of a
# profile) themselves
CONTAINERS = frozenset({"while", "conditional", "call", "async-start"})
# never an event of the device's timeline
_NO_EVENT = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                       "bitcast", "after-all", "partition-id", "replica-id"})


def part(name: str):
    """``with part("attn.qkv"):`` — every operation traced inside belongs
    to that sublayer (the innermost scope wins)."""
    import jax

    return jax.named_scope(PREFIX + name)


def scoped(name: str):
    """Decorator form of :func:`part`: the whole call is the sublayer.
    (A scope of its own a call: jax's context manager keeps its way back
    in itself, so one shared by two threads that trace would not do.)"""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with part(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def part_of(op_name: str) -> tuple[str | None, str]:
    """(the INNERMOST part of a jax name stack or None, "fwd" | "bwd"):
    ``jit(step)/transpose(jvp(pt:conv/c1))/pt:batch_norm/mul`` ->
    ("batch_norm", "bwd").  A layer scope's part is the layer type."""
    way = "bwd" if "transpose(" in op_name else "fwd"
    hits = _LABEL.findall(op_name)
    if not hits:
        return None, way
    kind, name = hits[-1]
    return ("comm" if kind == "comm." else name), way


def _rows(text: str):
    """``instructions`` with each instruction's operand names."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps[m.group(2)] = []
                entry = m.group(2) if m.group(1) else entry
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            cur.append((m.group(1), m.group(2), line))
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for inst, opcode, line in comps[name]:
            if opcode in CONTAINERS:
                todo.extend(_CALLED.findall(line))
                for group in _BRANCHES.findall(line):
                    todo.extend(n.strip().lstrip("%")
                                for n in group.split(","))
            m = _OP_NAME.search(line)
            # operands: what stands between the opcode's parentheses
            args = line[line.index(opcode + "(") + len(opcode) + 1:]
            yield (inst, opcode, m.group(1) if m else "",
                   _OPERAND.findall(args[:args.index(")")]
                                    if ")" in args else args))


def instructions(text: str):
    """An optimized HLO module's text -> the instructions ``(name,
    opcode, op_name)`` of every computation that runs as operations of
    its own: the entry and, from there, loop bodies and conditions,
    branches, calls -- not the bodies of fusions nor the regions of
    reductions, which run inside their caller."""
    for inst, opcode, op_name, _ in _rows(text):
        yield inst, opcode, op_name


def op_scopes(compiled) -> dict[str, list[str]]:
    """{part: [instruction names]} of a compiled program (anything with
    ``as_text()``, or the text), by each instruction's own
    ``metadata={op_name=...}`` through :func:`part_of`: a fusion counts
    by its own metadata, the backward pass under ``part + "|bwd"``.  An
    instruction the COMPILER made (no jax name stack at all: a weight's
    prefetch ``copy-start`` / ``slice-done``, a re-laid copy of a
    parameter, a rewritten reduction) belongs to the first instruction
    that reads it and has a part, through up to four such hands; one the
    source issued outside every scope, or that nothing with a part
    reads, is ``unscoped``.  Left out: what is never an event of the
    device's timeline (parameters, constants, tuples, bitcasts) and the
    containers (``while``, ``conditional``, ``call``), whose bodies'
    instructions are listed themselves."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    rows = list(_rows(text))
    own = {inst: part_of(op_name) for inst, _, op_name, _ in rows}
    made = {inst for inst, _, op_name, _ in rows
            if not op_name.startswith("jit(")}     # the compiler's own
    readers: dict[str, list[str]] = {}
    for inst, _, _, operands in rows:
        for operand in operands:
            readers.setdefault(operand, []).append(inst)

    def of_a_reader(inst, hands=4):
        for reader in readers.get(inst, ()):
            found = own[reader] if own[reader][0] else (
                of_a_reader(reader, hands - 1)
                if hands and reader in made else None)
            if found:
                return found
        return None

    out: dict[str, list[str]] = {}
    for inst, opcode, _, _ in rows:
        if opcode in _NO_EVENT or opcode in CONTAINERS:
            continue
        name, way = own[inst]
        if name is None and inst in made:
            name, way = of_a_reader(inst) or (None, way)
        key = (name or UNSCOPED) + (BWD if way == "bwd" else "")
        out.setdefault(key, []).append(inst)
    return out


def compile_described(timed, lower):
    """(``lower()``, its ``compile()``) for a program that is HELD
    (``lower`` -> a jax ``Lowered``), inside the ``program_ready`` span
    ``timed`` (``Tracer.timed``).  Under an armed tracer the lowering runs
    under ``ops.pallas.capture_routes`` and the span gets ``routes`` —
    THIS program's census, ``{"ssd_step:kernel": 9, "mamba1_step:xla":
    9}`` (empty where jax re-used a trace made earlier in the process:
    the decisions run while it traces) — and ``op_scopes``
    (:func:`op_scopes`).  With tracing off: neither, and no
    ``as_text()``."""
    if not timed.tracer.enabled:
        lowered = lower()
        return lowered, lowered.compile()
    from paddle_tpu.ops.pallas import capture_routes

    with capture_routes() as routes:
        lowered = lower()
    compiled = lowered.compile()
    said = op_scopes(compiled)
    if set(said) <= {UNSCOPED, UNSCOPED + BWD} \
            and PREFIX in lowered.as_text(debug_info=True):
        compiled = _compile_with_its_metadata(lowered)
        said = op_scopes(compiled)
    timed.args.update(
        routes={f"{op}:{path}": n for (op, path), n in sorted(routes.items())},
        op_scopes=said)
    return lowered, compiled


def _compile_with_its_metadata(lowered):
    """jax's compilation-cache key leaves metadata out, so the cache may
    hand back an executable that another checkout compiled from the same
    program under ITS name stacks -- none, before the scopes existed.
    Compiled again past that entry (a key that holds the metadata misses
    it) and NOT written back (a floor no compile time reaches): what an
    untraced run finds in the cache, and so its set-up time, is what it
    was.  A ``Lowered`` keeps the executable it compiled; an option that
    changes nothing (jax leaves the dump options out of the key) makes it
    build anew."""
    import jax

    from paddle_tpu.core import logger as log

    log.warning("a held program's executable came from the compilation "
                "cache without this program's scopes (an entry of a "
                "checkout that had none): compiling it again, past the "
                "cache")
    flips = {"jax_compilation_cache_include_metadata_in_key": True,
             "jax_persistent_cache_min_compile_time_secs": 1e9}
    before = {name: getattr(jax.config, name) for name in flips}
    for name, value in flips.items():
        jax.config.update(name, value)
    try:
        return lowered.compile(
            compiler_options={"xla_dump_disable_metadata": False})
    finally:
        for name, value in before.items():
            jax.config.update(name, value)


def scope_counts(args: dict) -> dict:
    """A span's args for an export: ``op_scopes`` as {part: how many}
    (the lists are for the readers inside the process)."""
    scopes = args.get("op_scopes")
    if not isinstance(scopes, dict):
        return args
    return {**args, "op_scopes": {k: len(v) for k, v in scopes.items()}}
