"""Codebase passes — static analysis over the repo's own AST.

Five passes share one corpus (every ``.py`` under the scanned roots,
parsed once):

- ``GL-EXCEPT``    swallow-all ``except`` detector: a broad handler
  (bare / ``Exception`` / ``BaseException``) that neither re-raises nor
  logs nor routes through ``telemetry.safe_inc`` silently eats the
  error — the PR 4 ``safe_inc`` regression class.
- ``GL-THREAD``    cross-thread attribute audit of the threaded
  subsystems: an attribute written outside ``__init__`` and touched
  from more than one thread domain (worker-thread entry points vs the
  public API) must hold the class's declared lock at every access.
- ``GL-LOCKORDER`` lock-order-cycle detection from the per-module lock
  registry built by the same audit (lock A held while acquiring B and
  elsewhere B while acquiring A = a deadlock waiting for contention).
- ``GL-ENV``       env-var reads without a ``core/flags`` registration:
  every literal ``os.environ``/``os.getenv`` read must name either a
  defined flag's ``PADDLE_TPU_<NAME>`` override or an explicitly
  declared env passthrough (``flags.declare_env``).
- ``GL-SCHEMA``    telemetry record-kind drift: every ``kind`` a record
  carries (``emit(..., kind=...)`` or a ``{"kind": ...}`` literal) must
  be listed in ``telemetry.registry.RECORD_KINDS``, and every listed
  kind must actually be produced somewhere.

Thread-domain model (GL-THREAD): worker entries are methods passed as
``threading.Thread(target=self.m)`` (or a nested function passed as
``target=``/a ``signal.signal`` handler — both run asynchronously to
the caller); the consumer domain is the public API (public methods and
dunders).  A private helper reachable from both counts in both.
Attributes whose ``__init__`` value is itself a synchronization-safe
type (``queue.Queue``, ``threading.Event``/``Lock``/…) are exempt;
mutations through container methods (``append``/``clear``/…),
subscript stores and augmented assignment count as writes.
"""

from __future__ import annotations

import ast
import os

from paddle_tpu.analysis.core import Finding, finalize, repo_root

# -- corpus ---------------------------------------------------------------------

DEFAULT_ROOTS = ("paddle_tpu", "tools")

# the threaded subsystems under the GL-THREAD / GL-LOCKORDER audit
THREADED_MODULES = (
    "paddle_tpu/reader/prefetch.py",
    "paddle_tpu/serving/engine.py",
    "paddle_tpu/serving/dense.py",
    "paddle_tpu/serving/fleet.py",
    "paddle_tpu/serving/router.py",
    "paddle_tpu/serving/health.py",
    "paddle_tpu/resilience/elastic.py",
    "paddle_tpu/resilience/supervisor.py",
    "paddle_tpu/deploy/controller.py",
    "paddle_tpu/deploy/autoscaler.py",
    "paddle_tpu/deploy/arbiter.py",
    "paddle_tpu/trainer/checkpoint.py",
    "paddle_tpu/telemetry/tracing.py",
    "paddle_tpu/telemetry/introspect.py",
    "paddle_tpu/telemetry/goodput.py",
)


def iter_corpus(root: str | None = None, files: list[str] | None = None,
                roots: tuple = DEFAULT_ROOTS) -> dict[str, tuple[str, ast.AST]]:
    """{repo-relative path: (source, parsed tree)} for every scanned
    ``.py`` file.  ``files`` (repo-relative) restricts the corpus (the
    ``--changed`` mode); unparseable files are skipped (syntax errors
    are the interpreter's job, not the linter's)."""
    root = root or repo_root()
    paths: list[str] = []
    if files is not None:
        # a subset still only covers the lintable roots: tests/ etc.
        # legitimately break package rules (broad excepts in fixtures)
        def in_roots(f: str) -> bool:
            return any(f == r or f.startswith(r.rstrip("/") + "/")
                       for r in roots)

        paths = [f for f in files if f.endswith(".py") and in_roots(f)
                 and os.path.exists(os.path.join(root, f))]
    else:
        for r in roots:
            full = os.path.join(root, r)
            if os.path.isfile(full):
                paths.append(r)
                continue
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for f in sorted(filenames):
                    if f.endswith(".py"):
                        paths.append(os.path.relpath(
                            os.path.join(dirpath, f), root))
    corpus: dict[str, tuple[str, ast.AST]] = {}
    for rel in sorted(set(paths)):
        try:
            with open(os.path.join(root, rel)) as fh:
                src = fh.read()
            corpus[rel] = (src, ast.parse(src, filename=rel))
        except (OSError, SyntaxError, ValueError):
            continue
    return corpus


def _qualname_index(tree: ast.AST) -> dict[ast.AST, str]:
    """node -> enclosing qualified name ("Class.method", "fn.<locals>.g"
    collapsed to "fn.g", or "<module>")."""
    out: dict[ast.AST, str] = {}

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            s = stack
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                s = stack + [child.name]
            out[child] = ".".join(s) if s else "<module>"
            walk(child, s)

    out[tree] = "<module>"
    walk(tree, [])
    return out


# -- GL-EXCEPT: swallow-all except detector -------------------------------------

_BROAD = {"Exception", "BaseException"}
_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical", "log"}


def _is_broad(h: ast.ExceptHandler) -> bool:
    t = h.type
    if t is None:
        return True
    names = []
    for n in ([t] if not isinstance(t, ast.Tuple) else t.elts):
        if isinstance(n, ast.Name):
            names.append(n.id)
        elif isinstance(n, ast.Attribute):
            names.append(n.attr)
    return any(n in _BROAD for n in names)


def _handler_records(h: ast.ExceptHandler) -> bool:
    """True when the handler re-raises, logs, routes through a
    ``safe_*`` accounting helper, or *uses the caught exception value*
    (``except ... as e`` with ``e`` referenced — the propagate-to-
    consumer pattern, e.g. ``_ProducerError(e)`` or ``self._err = e``)
    — i.e. the swallow is not silent."""
    for node in ast.walk(h):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            fn = node.func
            name = None
            if isinstance(fn, ast.Attribute):
                name = fn.attr
            elif isinstance(fn, ast.Name):
                name = fn.id
            if name in _LOG_METHODS or (name or "").startswith("safe_"):
                return True
        if h.name and isinstance(node, ast.Name) and node.id == h.name \
                and isinstance(node.ctx, ast.Load):
            return True
    return False


def pass_swallow_except(corpus, root) -> list[Finding]:
    findings = []
    for rel, (_src, tree) in corpus.items():
        qn = _qualname_index(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if _is_broad(node) and not _handler_records(node):
                caught = ("bare except" if node.type is None
                          else ast.unparse(node.type))
                findings.append(Finding(
                    "GL-EXCEPT", rel, node.lineno, qn.get(node, "<module>"),
                    f"broad `except {caught}` swallows the error silently "
                    f"(no raise / log / safe_* accounting) — narrow the "
                    f"types, log it, or route through telemetry.safe_inc"))
    return findings


# -- GL-ENV: env reads without a core/flags registration ------------------------


def _env_read_name(node: ast.AST) -> tuple[str, int] | None:
    """Literal env-var name of an ``os.environ.get/[]`` / ``os.getenv``
    read, or None for writes / non-literal names."""
    if isinstance(node, ast.Call):
        fn = node.func
        # os.environ.get("X") / environ.get("X")
        if (isinstance(fn, ast.Attribute) and fn.attr == "get"
                and isinstance(fn.value, (ast.Attribute, ast.Name))):
            base = (fn.value.attr if isinstance(fn.value, ast.Attribute)
                    else fn.value.id)
            if base == "environ" and node.args and isinstance(
                    node.args[0], ast.Constant) and isinstance(
                    node.args[0].value, str):
                return node.args[0].value, node.lineno
        # os.getenv("X")
        if (isinstance(fn, ast.Attribute) and fn.attr == "getenv"
                or isinstance(fn, ast.Name) and fn.id == "getenv"):
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                return node.args[0].value, node.lineno
    # os.environ["X"] — loads only (ctx Store/Del = launcher-style writes)
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        v = node.value
        if isinstance(v, ast.Attribute) and v.attr == "environ" or \
                isinstance(v, ast.Name) and v.id == "environ":
            s = node.slice
            if isinstance(s, ast.Constant) and isinstance(s.value, str):
                return s.value, node.lineno
    return None


def registered_env_names() -> set[str]:
    from paddle_tpu.core import flags

    return flags.known_env_names()


def pass_env_registration(corpus, root,
                          registered: set[str] | None = None) -> list[Finding]:
    if registered is None:
        registered = registered_env_names()
    findings = []
    for rel, (_src, tree) in corpus.items():
        if not rel.startswith("paddle_tpu"):
            continue  # tools/tests read ad-hoc env by design
        qn = _qualname_index(tree)
        for node in ast.walk(tree):
            got = _env_read_name(node)
            if got is None:
                continue
            name, line = got
            if name not in registered:
                findings.append(Finding(
                    "GL-ENV", rel, line, qn.get(node, "<module>"),
                    f"env var {name!r} read without a core/flags "
                    f"registration — define a flag (PADDLE_TPU_* "
                    f"override) or flags.declare_env({name!r}, ...)"))
    return findings


# -- GL-SCHEMA: telemetry record-kind drift -------------------------------------


def known_record_kinds() -> frozenset:
    from paddle_tpu.telemetry.registry import RECORD_KINDS

    return frozenset(RECORD_KINDS)


def _dict_kind(node: ast.Dict) -> str | None:
    for k, v in zip(node.keys, node.values):
        if isinstance(k, ast.Constant) and k.value == "kind" \
                and isinstance(v, ast.Constant) and isinstance(v.value, str):
            return v.value
    return None


def _emitted_kinds(tree: ast.AST) -> list[tuple[str, int, ast.AST]]:
    """(kind literal, line, call node) for every record the module
    emits: ``.emit(..., kind="x")`` kwargs, ``.emit({..."kind": "x"...})``
    dict-literal args, and ``rec = {...}; .emit(rec)`` / ``.emit(
    dict(rec))`` one-hop dataflow.  Dict literals that never reach an
    emit call are NOT records (layer attrs etc.) and are ignored."""
    named: dict[str, tuple[str, int]] = {}   # var -> (kind, dict line)
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                and isinstance(node.value, ast.Dict):
            kind = _dict_kind(node.value)
            if kind is not None:
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Name):
                        named[t.id] = (kind, node.value.lineno)
    out: list[tuple[str, int, ast.AST]] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and node.func.attr == "emit"):
            continue
        for kw in node.keywords:
            if kw.arg == "kind" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                out.append((kw.value.value, node.lineno, node))
        for arg in node.args:
            if isinstance(arg, ast.Dict):
                kind = _dict_kind(arg)
                if kind is not None:
                    out.append((kind, node.lineno, node))
            elif isinstance(arg, ast.Name) and arg.id in named:
                out.append((named[arg.id][0], node.lineno, node))
            elif isinstance(arg, ast.Call) and isinstance(
                    arg.func, ast.Name) and arg.func.id == "dict" \
                    and arg.args and isinstance(arg.args[0], ast.Name) \
                    and arg.args[0].id in named:
                out.append((named[arg.args[0].id][0], node.lineno, node))
    return out


def pass_schema_kinds(corpus, root, known: frozenset | None = None,
                      full_corpus: bool = True) -> list[Finding]:
    if known is None:
        known = known_record_kinds()
    findings = []
    produced: set[str] = set()
    for rel, (_src, tree) in corpus.items():
        if not rel.startswith("paddle_tpu"):
            continue  # offline renderers (tools/) only consume kinds
        qn = _qualname_index(tree)
        for kind, line, node in _emitted_kinds(tree):
            produced.add(kind)
            if kind not in known:
                findings.append(Finding(
                    "GL-SCHEMA", rel, line, qn.get(node, "<module>"),
                    f"record kind {kind!r} is not listed in "
                    f"telemetry.registry.RECORD_KINDS — bump the "
                    f"SCHEMA changelog and register it"))
    if full_corpus:  # a file subset can't prove a kind is unproduced
        for kind in sorted(known - produced):
            findings.append(Finding(
                "GL-SCHEMA", "paddle_tpu/telemetry/registry.py", 0,
                "RECORD_KINDS",
                f"record kind {kind!r} is registered but nothing in the "
                f"scanned tree produces it — stale schema entry"))
    return findings


# -- GL-THREAD / GL-LOCKORDER: threaded-subsystem audit -------------------------

_LOCK_TYPES = {"Lock", "RLock", "Condition"}
_SAFE_TYPES = {"Queue", "LifoQueue", "PriorityQueue", "SimpleQueue",
               "Event", "Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore", "Barrier", "local"}
_MUTATORS = {"append", "appendleft", "extend", "extendleft", "insert",
             "clear", "pop", "popleft", "remove", "discard", "add",
             "update", "setdefault", "popitem", "sort"}


class _Access:
    __slots__ = ("attr", "write", "line", "locks")

    def __init__(self, attr, write, line, locks):
        self.attr = attr
        self.write = write
        self.line = line
        self.locks = frozenset(locks)


def _self_attr(node) -> str | None:
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


class _UnitVisitor(ast.NodeVisitor):
    """Collect self-attribute accesses (with held-lock context), self
    method calls, lock acquisitions and thread/signal targets of ONE
    code unit (a method body or a nested function)."""

    def __init__(self, lock_attrs: set[str]):
        self.lock_attrs = lock_attrs
        self.accesses: list[_Access] = []
        self.calls: list[tuple[str, frozenset]] = []   # (method, locks held)
        self.acquired: list[tuple[str, frozenset]] = []  # (lock, held before)
        self.thread_targets: list[str] = []   # self.<m> Thread targets
        self.local_targets: list[str] = []    # nested-function targets
        self._held: list[str] = []

    # -- lock scoping ----------------------------------------------------------
    def visit_With(self, node: ast.With):
        entered = []
        for item in node.items:
            a = _self_attr(item.context_expr)
            if a in self.lock_attrs:
                self.acquired.append((a, frozenset(self._held)))
                self._held.append(a)
                entered.append(a)
            else:
                self.visit(item.context_expr)
        for stmt in node.body:
            self.visit(stmt)
        for a in entered:
            self._held.remove(a)

    # -- nested functions are separate units -----------------------------------
    def visit_FunctionDef(self, node):  # noqa: N802
        pass

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Attribute(self, node: ast.Attribute):
        a = _self_attr(node)
        if a is not None:
            write = isinstance(node.ctx, (ast.Store, ast.Del))
            self.accesses.append(_Access(a, write, node.lineno, self._held))
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        a = _self_attr(node.value)
        if a is not None and isinstance(node.ctx, (ast.Store, ast.Del)):
            self.accesses.append(_Access(a, True, node.lineno, self._held))
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        a = _self_attr(node.target)
        if a is not None:
            self.accesses.append(_Access(a, True, node.lineno, self._held))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        fn = node.func
        # self.m(...) — intra-class call edge
        if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name) \
                and fn.value.id == "self":
            self.calls.append((fn.attr, frozenset(self._held)))
        # self.attr.mutator(...) — counts as a write to attr
        if isinstance(fn, ast.Attribute) and fn.attr in _MUTATORS:
            a = _self_attr(fn.value)
            if a is not None:
                self.accesses.append(
                    _Access(a, True, node.lineno, self._held))
        # threading.Thread(target=...)
        if isinstance(fn, ast.Attribute) and fn.attr == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    t = _self_attr(kw.value)
                    if t is not None:
                        self.thread_targets.append(t)
                    elif isinstance(kw.value, ast.Name):
                        self.local_targets.append(kw.value.id)
        # signal.signal(sig, handler) — handler runs asynchronously
        if isinstance(fn, ast.Attribute) and fn.attr == "signal" \
                and len(node.args) >= 2:
            h = node.args[1]
            t = _self_attr(h)
            if t is not None:
                self.thread_targets.append(t)
            elif isinstance(h, ast.Name):
                self.local_targets.append(h.id)
        self.generic_visit(node)


class _ClassAudit:
    """Thread-domain model of one class (see module docstring)."""

    def __init__(self, cls: ast.ClassDef):
        self.cls = cls
        self.methods: dict[str, ast.FunctionDef] = {}
        for n in cls.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.methods[n.name] = n
        self.lock_attrs: set[str] = set()
        self.safe_attrs: set[str] = set()
        self._find_attr_types()
        # units: method name or "method.<nested>" -> visitor
        self.units: dict[str, _UnitVisitor] = {}
        self.worker_entries: set[str] = set()
        self._visit_units()

    def _find_attr_types(self):
        for m in self.methods.values():
            for node in ast.walk(m):
                if not isinstance(node, ast.Assign):
                    continue
                v = node.value
                if not (isinstance(v, ast.Call)
                        and isinstance(v.func, (ast.Attribute, ast.Name))):
                    continue
                ctor = (v.func.attr if isinstance(v.func, ast.Attribute)
                        else v.func.id)
                for tgt in node.targets:
                    a = _self_attr(tgt)
                    if a is None:
                        continue
                    if ctor in _LOCK_TYPES:
                        self.lock_attrs.add(a)
                    if ctor in _SAFE_TYPES:
                        self.safe_attrs.add(a)

    def _visit_units(self):
        for name, m in self.methods.items():
            uv = _UnitVisitor(self.lock_attrs)
            for stmt in m.body:
                uv.visit(stmt)
            self.units[name] = uv
            for t in uv.thread_targets:
                if t in self.methods:
                    self.worker_entries.add(t)
            # nested functions used as thread/signal targets
            nested = {n.name: n for n in ast.walk(m)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))}
            for t in uv.local_targets:
                if t in nested:
                    unit = f"{name}.{t}"
                    nv = _UnitVisitor(self.lock_attrs)
                    for stmt in nested[t].body:
                        nv.visit(stmt)
                    self.units[unit] = nv
                    self.worker_entries.add(unit)

    def _reachable(self, entries: set[str]) -> set[str]:
        seen = set()
        todo = [e for e in entries if e in self.units]
        while todo:
            u = todo.pop()
            if u in seen:
                continue
            seen.add(u)
            for callee, _held in self.units[u].calls:
                if callee in self.units and callee not in seen:
                    todo.append(callee)
        return seen

    def domains(self) -> dict[str, set[str]]:
        """{unit: set of domains} — "worker" and/or "consumer"."""
        worker = self._reachable(self.worker_entries)
        consumer_entries = {
            n for n in self.units
            if "." not in n and n not in self.worker_entries
            and (not n.startswith("_") or (n.startswith("__")
                                           and n.endswith("__")))
            and n != "__init__"}
        consumer = self._reachable(consumer_entries)
        out: dict[str, set[str]] = {}
        for u in self.units:
            if u == "__init__":
                continue
            d = set()
            if u in worker:
                d.add("worker")
            if u in consumer:
                d.add("consumer")
            if d:
                out[u] = d
        return out

    def findings(self, rel: str) -> list[Finding]:
        if not self.worker_entries:
            return []
        per_attr: dict[str, dict] = {}
        for unit, doms in self.domains().items():
            for acc in self.units[unit].accesses:
                if acc.attr in self.safe_attrs or acc.attr in self.lock_attrs:
                    continue
                rec = per_attr.setdefault(acc.attr, {
                    "domains": set(), "write": False,
                    "unlocked": None, "line": acc.line})
                rec["domains"] |= doms
                rec["write"] |= acc.write
                if not acc.locks and rec["unlocked"] is None:
                    rec["unlocked"] = (unit, acc.line)
        out = []
        for attr, rec in sorted(per_attr.items()):
            if len(rec["domains"]) < 2 or not rec["write"] \
                    or rec["unlocked"] is None:
                continue
            unit, line = rec["unlocked"]
            lock = (f"`self.{sorted(self.lock_attrs)[0]}`"
                    if self.lock_attrs else "a lock (none declared!)")
            out.append(Finding(
                "GL-THREAD", rel, line, f"{self.cls.name}.{attr}",
                f"attribute `self.{attr}` is shared between the worker "
                f"and consumer thread domains with a write outside "
                f"__init__, but `{unit}` touches it without holding "
                f"{lock}"))
        return out

    def lock_order_edges(self) -> set[tuple[str, str]]:
        """(held, acquired) pairs: direct `with` nesting plus one level
        of self-call propagation (calling a method that acquires B while
        holding A)."""
        edges: set[tuple[str, str]] = set()
        for uv in self.units.values():
            for lock, held in uv.acquired:
                for h in held:
                    if h != lock:
                        edges.add((h, lock))
            for callee, held in uv.calls:
                if not held or callee not in self.units:
                    continue
                for lock, _ in self.units[callee].acquired:
                    for h in held:
                        if h != lock:
                            edges.add((h, lock))
        return edges


def _find_cycle(edges: set[tuple[str, str]]) -> list[str] | None:
    graph: dict[str, set[str]] = {}
    for a, b in edges:
        graph.setdefault(a, set()).add(b)
    state: dict[str, int] = {}
    stack: list[str] = []

    def dfs(n):
        state[n] = 1
        stack.append(n)
        for m in graph.get(n, ()):
            if state.get(m, 0) == 1:
                return stack[stack.index(m):] + [m]
            if state.get(m, 0) == 0:
                c = dfs(m)
                if c:
                    return c
        state[n] = 2
        stack.pop()
        return None

    for n in sorted(graph):
        if state.get(n, 0) == 0:
            c = dfs(n)
            if c:
                return c
    return None


def _audit_modules(corpus, modules) -> dict[str, list[_ClassAudit]]:
    out = {}
    for rel in modules:
        if rel not in corpus:
            continue
        _src, tree = corpus[rel]
        out[rel] = [_ClassAudit(n) for n in tree.body
                    if isinstance(n, ast.ClassDef)]
    return out


def pass_thread_safety(corpus, root,
                       modules: tuple = THREADED_MODULES) -> list[Finding]:
    findings = []
    for rel, audits in _audit_modules(corpus, modules).items():
        for a in audits:
            findings.extend(a.findings(rel))
    return findings


def pass_lock_order(corpus, root,
                    modules: tuple = THREADED_MODULES) -> list[Finding]:
    findings = []
    for rel, audits in _audit_modules(corpus, modules).items():
        for a in audits:
            cycle = _find_cycle(a.lock_order_edges())
            if cycle:
                findings.append(Finding(
                    "GL-LOCKORDER", rel, a.cls.lineno, a.cls.name,
                    f"lock-order cycle {' -> '.join(cycle)} — two code "
                    f"paths acquire these locks in opposite order; under "
                    f"contention they deadlock"))
    return findings


def lock_registry(root: str | None = None,
                  modules: tuple = THREADED_MODULES) -> dict:
    """{module: {class: sorted lock attrs}} — the per-module lock
    registry the lock-order pass works from (exposed for tests and the
    CLI's --locks listing)."""
    corpus = iter_corpus(root, files=list(modules))
    return {rel: {a.cls.name: sorted(a.lock_attrs)
                  for a in audits if a.lock_attrs}
            for rel, audits in _audit_modules(corpus, modules).items()}


# -- GL-KERNEL rides in from kernel_parity (registered here) --------------------


def pass_kernel_parity(corpus, root) -> list[Finding]:
    from paddle_tpu.analysis.kernel_parity import kernel_parity_findings

    return kernel_parity_findings(root)


# -- GL-RNG rides in from analysis/rng (registered here) ------------------------


def pass_rng(corpus, root) -> list[Finding]:
    from paddle_tpu.analysis.rng import pass_rng_discipline

    return pass_rng_discipline(corpus, root)


CODEBASE_PASSES = {
    "except": pass_swallow_except,
    "thread": pass_thread_safety,
    "lockorder": pass_lock_order,
    "env": pass_env_registration,
    "schema": pass_schema_kinds,
    "kernel": pass_kernel_parity,
    "rng": pass_rng,
}


def run_codebase(root: str | None = None, files: list[str] | None = None,
                 passes: list[str] | None = None) -> list[Finding]:
    """Run the codebase passes over the repo (or a ``files`` subset);
    returns finalized findings in (pass, path, line) order."""
    root = root or repo_root()
    corpus = iter_corpus(root, files=files)
    selected = passes or list(CODEBASE_PASSES)
    findings: list[Finding] = []
    for name in selected:
        if name == "kernel" and files is not None:
            # the parity rule is corpus-global (tests/ must mention the
            # pair) — a changed-files subset can't evaluate it
            continue
        if name == "schema":
            findings.extend(pass_schema_kinds(
                corpus, root, full_corpus=files is None))
            continue
        findings.extend(CODEBASE_PASSES[name](corpus, root))
    findings.sort(key=lambda f: (f.rule, f.path, f.line))
    return finalize(findings)
