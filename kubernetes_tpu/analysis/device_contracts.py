"""Device-contract lint (DC6xx): the accelerator disciplines the wave
path lives by — donation, host-sync budget, stable compiled shapes, and
clone-on-write snapshot hygiene — enforced structurally instead of by
comments and reviewer memory.

The pass scans ``ops/`` plus ``models/snapshot.py`` (the device seam)
and reuses the shapes proven out by the races/tracecov passes: lexical
annotations with mandatory reasons, name-level summaries propagated to
a fixed point, and over-approximation toward SILENCE — anything the
analysis cannot prove is dropped, never flagged.

Rules
-----
- **DC600** — a file in scope does not parse (same contract as RL300 /
  TC500).
- **DC601** — *use-after-donate*: a call through a jit wrapper built
  with ``donate_argnums`` (directly, or through a factory chain —
  ``_loop_runner`` → ``_loop_runner_for`` → ``self._loop``) consumes
  the donated actuals' buffers; any READ of a donated actual (a
  ``self.<attr>`` path or a local name) after the dispatch and at or
  before the next rebind — in the same function, or in a callee
  (same-class method / sibling nested def) invoked in that window — is
  a read of dead memory.
- **DC602** — *host-sync budget*: a host-materialization call
  (``.item()`` / ``.tolist()`` / ``float()``/``int()``/``bool()`` on a
  device-tainted value, ``np.asarray``/``np.array`` of one,
  ``jax.device_get``, ``.block_until_ready()``) inside a wave-hot-path
  module must sit at a site annotated ``# device: sync — <reason>``
  (same line or the line above).  ``.copy_to_host_async()`` is not a
  sync.  :func:`sanctioned_sync_sites` counts the sanctioned sites per
  function so the PR-11 O(compactions + 1) budget is auditable — and a
  tier-1 test holds the runtime ``host_syncs`` stat to the static
  count.
- **DC603** — *recompile guard*: shape-bearing expressions flowing into
  compiled-program identity must route through the sticky-bucket
  helpers or carry a ``# device: static`` annotation: (a) a
  ``_pad_to(...)`` call outside a ``_sticky_pad``/``_bucket`` wrapper,
  (b) a ``_pow2_width(...)`` call (each distinct width is its own
  executable — the annotation records the accepted ≤ log2(N) compile
  budget), (c) an argument at a compile-keyed factory boundary (an
  ``lru_cache``-decorated function returning a jitted callable) that is
  not a normalized scalar (``int()``/``bool()``/``tuple()``/constant/
  bool- or int-annotated parameter).
- **DC604** — *CoW snapshot writes*: in any scanned function that
  receives the scheduler snapshot (a ``node_info_map`` parameter, or a
  ``dict(node_info_map)`` working copy), mutating a ``NodeInfo``
  obtained from that map (``.add_pod`` / ``.add_pod_counted`` /
  ``.add_pods_counted`` / ``.remove_pod`` / ``.replace_pod`` /
  ``.set_node`` / ``.remove_node``, or an attribute store) without
  flowing through ``mutable_info`` is an error — the ROADMAP's "must
  route through mutable_info" caveat, gated.
- **DC605** — a stale or reasonless device annotation: a
  ``# device: sync`` with no materialization-shaped call on its line or
  the next (the check is LEXICAL so an annotation stays valid even
  where the taint under-approximates), a sync annotation with no
  reason, or a ``# device: static`` sanctioning no shape site.

Deliberately NOT modeled (over-approximating toward silence): donation
through containers or across instance-method boundaries (only the
rebind window inside the dispatching function plus one callee hop);
taint through functions defined outside the scanned module (a value
returned by an unscanned helper is host until proven device); CoW
aliasing through collaborator objects (``PriorityContext(work_map)``)
— the map handed to a constructor is trusted read-only.
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from .core import Finding, iter_py_files
from .tracecov import HOT_PATH_MODULES

DEFAULT_PATHS = [
    "kubernetes_tpu/ops",
    "kubernetes_tpu/models/snapshot.py",
    "kubernetes_tpu/parallel",
]

#: NodeInfo's mutating surface (scheduler/nodeinfo.py); ``clone()`` is
#: deliberately absent — cloning IS the sanctioned CoW step.
NODEINFO_MUTATORS = {
    "add_pod", "add_pod_counted", "add_pods_counted", "remove_pod",
    "replace_pod", "set_node", "remove_node",
}

#: array metadata — reading these never materializes device memory
_METADATA_ATTRS = {"shape", "ndim", "size", "dtype", "nbytes"}

#: module roots whose calls produce device values
_DEVICE_ROOTS = {"jnp", "lax"}

_SYNC_ANN_RE = re.compile(
    r"#\s*device:\s*sync\s*(?:—|–|-{1,2})?\s*(.*)$")
_STATIC_ANN_RE = re.compile(r"#\s*device:\s*static\b")
#: lexical materialization shapes for the DC605 stale-sync check — kept
#: looser than the AST forms so a sanctioned site the taint misses does
#: not round-trip into a stale-annotation finding
_SYNC_LEXEME_RE = re.compile(
    r"\.item\(|\.tolist\(|\bint\(|\bfloat\(|\bbool\(|np\.asarray\(|"
    r"np\.array\(|device_get\(|block_until_ready\(")


class _Func:
    __slots__ = ("node", "qualname", "name", "parent")

    def __init__(self, node, qualname: str, parent: "Optional[_Func]"):
        self.node = node
        self.qualname = qualname
        self.name = node.name
        self.parent = parent  # enclosing _Func, None at module/class level


def _collect_funcs(tree: ast.Module) -> list[_Func]:
    out: list[_Func] = []

    def visit(node, prefix: str, parent: Optional[_Func]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}.{child.name}" if prefix else child.name
                f = _Func(child, qual, parent)
                out.append(f)
                visit(child, qual, f)
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}.{child.name}" if prefix
                      else child.name, parent)
            else:
                visit(child, prefix, parent)

    visit(tree, "", None)
    return out


def _enclosing(funcs: list[_Func], line: int) -> Optional[_Func]:
    best: Optional[_Func] = None
    for f in funcs:
        if f.node.lineno <= line <= (f.node.end_lineno or f.node.lineno):
            if best is None or f.node.lineno > best.node.lineno:
                best = f
    return best


def _attr_root(node: ast.expr) -> Optional[str]:
    """``jnp.sum`` / ``jax.lax.scan`` -> the base Name ("jnp"/"jax")."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _self_attr(node: ast.expr) -> Optional[str]:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _callee_attr_name(call: ast.Call) -> Optional[str]:
    """The method name of ``X.m(...)``; None for bare calls."""
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return None


def _own_returns(fn: ast.FunctionDef) -> list[ast.Return]:
    """Return statements owned by ``fn`` itself, nested defs excluded."""
    out: list[ast.Return] = []

    def walk(node) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.Return):
                out.append(child)
            walk(child)

    walk(fn)
    return out


def _own_statements(fn: ast.FunctionDef):
    """Statement-level nodes owned by ``fn``, nested def bodies excluded."""
    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            yield child
            yield from walk(child)

    yield from walk(fn)


def _donate_from_keywords(call: ast.Call) -> tuple[int, ...]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                idx = tuple(e.value for e in v.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, int))
                return idx
    return ()


def _is_jax_jit(expr: ast.expr) -> bool:
    return (isinstance(expr, ast.Attribute) and expr.attr == "jit"
            and isinstance(expr.value, ast.Name) and expr.value.id == "jax")


def _decorated_jit(fn: ast.FunctionDef) -> Optional[tuple[int, ...]]:
    """Donation of an ``@jax.jit`` / ``@partial(jax.jit, ...)`` decorator,
    or None when the function is not jit-decorated."""
    for dec in fn.decorator_list:
        if _is_jax_jit(dec):
            return ()
        if (isinstance(dec, ast.Call) and _is_jax_jit(dec.func)):
            return _donate_from_keywords(dec)
        if (isinstance(dec, ast.Call) and isinstance(dec.func, ast.Name)
                and dec.func.id == "partial" and dec.args
                and _is_jax_jit(dec.args[0])):
            return _donate_from_keywords(dec)
    return None


def _has_lru_cache(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        name = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(name, ast.Name) and name.id == "lru_cache":
            return True
        if isinstance(name, ast.Attribute) and name.attr == "lru_cache":
            return True
    return False


class _ModuleIndex:
    """Per-module summaries: jit factories (+ donation), compile-keyed
    factory names, device-returning module functions, class attribute
    taint, and per-function local environments."""

    def __init__(self, tree: ast.Module, funcs: list[_Func]):
        self.tree = tree
        self.funcs = funcs
        self.top_fns: dict[str, ast.FunctionDef] = {}
        self.classes: dict[str, ast.ClassDef] = {}
        for child in ast.iter_child_nodes(tree):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.top_fns[child.name] = child
            elif isinstance(child, ast.ClassDef):
                self.classes[child.name] = child
        # name -> donate indices of the callable the factory returns
        self.factories: dict[str, tuple[int, ...]] = {}
        self.compile_keyed: set[str] = set()
        self.device_fns: set[str] = set()
        # class name -> (device attrs, callable attrs -> donate)
        self.cls_attrs: dict[str, set[str]] = {}
        self.cls_callables: dict[str, dict[str, tuple[int, ...]]] = {}
        self._build_factories()
        self._build_device_summaries()

    # -- jit factories ------------------------------------------------------

    def _build_factories(self) -> None:
        changed = True
        while changed:
            changed = False
            for name, fn in self.top_fns.items():
                if name in self.factories:
                    continue
                donate = self._factory_donate(fn)
                if donate is not None:
                    self.factories[name] = donate
                    if _has_lru_cache(fn):
                        self.compile_keyed.add(name)
                    changed = True

    def _factory_donate(self, fn: ast.FunctionDef) -> Optional[tuple[int, ...]]:
        nested = {c.name: c for c in ast.iter_child_nodes(fn)
                  if isinstance(c, ast.FunctionDef)}
        for ret in _own_returns(fn):
            v = ret.value
            if v is None:
                continue
            if isinstance(v, ast.Call) and _is_jax_jit(v.func):
                return _donate_from_keywords(v)
            if (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                    and v.func.id in self.factories):
                return self.factories[v.func.id]
            if isinstance(v, ast.Name) and v.id in nested:
                donate = _decorated_jit(nested[v.id])
                if donate is not None:
                    return donate
        return None

    # -- device-value summaries --------------------------------------------

    def _build_device_summaries(self) -> None:
        for _round in range(3):  # module fns x class attrs to a fixed point
            before = (len(self.device_fns),
                      sum(len(s) for s in self.cls_attrs.values()),
                      sum(len(s) for s in self.cls_callables.values()))
            for name, fn in self.top_fns.items():
                if name in self.factories or name in self.device_fns:
                    continue
                env = self.local_env(fn, cls=None)
                returns = _own_returns(fn)
                if returns and all(
                        r.value is not None
                        and self.expr_is_device(r.value, env)
                        for r in returns):
                    self.device_fns.add(name)
            for cname, cls in self.classes.items():
                attrs = self.cls_attrs.setdefault(cname, set())
                callables = self.cls_callables.setdefault(cname, {})
                for item in ast.walk(cls):
                    if not isinstance(item, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)):
                        continue
                    env = self.local_env(item, cls=cname)
                    for stmt in _own_statements(item):
                        self._class_taint_stmt(stmt, env, attrs, callables)
            after = (len(self.device_fns),
                     sum(len(s) for s in self.cls_attrs.values()),
                     sum(len(s) for s in self.cls_callables.values()))
            if after == before:
                break

    def _class_taint_stmt(self, stmt, env, attrs: set[str],
                          callables: dict[str, tuple[int, ...]]) -> None:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                self._class_taint_pair(t, stmt.value, env, attrs, callables)
        elif isinstance(stmt, ast.AugAssign):
            a = _self_attr(stmt.target)
            if a is not None and self.expr_is_device(stmt.value, env):
                attrs.add(a)
        elif isinstance(stmt, ast.Call):
            # self.X.append(device-ish) taints the container attr
            if (isinstance(stmt.func, ast.Attribute)
                    and stmt.func.attr in ("append", "extend")
                    and stmt.args):
                a = _self_attr(stmt.func.value)
                if a is not None and self._any_device(stmt.args[0], env):
                    attrs.add(a)

    def _class_taint_pair(self, target, value, env, attrs, callables) -> None:
        a = _self_attr(target)
        if a is not None:
            donate = self.callable_donate(value, env)
            if donate is not None:
                callables[a] = donate
            elif self.expr_is_device(value, env):
                attrs.add(a)
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            if (isinstance(value, (ast.Tuple, ast.List))
                    and len(value.elts) == len(target.elts)):
                for t, v in zip(target.elts, value.elts):
                    self._class_taint_pair(t, v, env, attrs, callables)
            elif self.expr_is_device(value, env):
                for t in target.elts:
                    at = _self_attr(t)
                    if at is not None:
                        attrs.add(at)

    # -- environments -------------------------------------------------------

    def local_env(self, fn, cls: Optional[str]):
        """(tainted locals, callable locals -> donate, class name) for
        ``fn``, flow-insensitive, two sweeps for ordering independence."""
        tainted: set[str] = set()
        callables: dict[str, tuple[int, ...]] = {}
        env = (tainted, callables, cls)
        for _sweep in range(2):
            for stmt in _own_statements(fn):
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        self._env_pair(t, stmt.value, env)
                elif isinstance(stmt, ast.AugAssign):
                    if (isinstance(stmt.target, ast.Name)
                            and self.expr_is_device(stmt.value, env)):
                        tainted.add(stmt.target.id)
                elif isinstance(stmt, ast.For):
                    if self.expr_is_device(stmt.iter, env):
                        for n in ast.walk(stmt.target):
                            if isinstance(n, ast.Name):
                                tainted.add(n.id)
                elif isinstance(stmt, ast.Call):
                    if (isinstance(stmt.func, ast.Attribute)
                            and stmt.func.attr in ("append", "extend")
                            and stmt.args
                            and isinstance(stmt.func.value, ast.Name)
                            and self._any_device(stmt.args[0], env)):
                        tainted.add(stmt.func.value.id)
        return env

    def _env_pair(self, target, value, env) -> None:
        tainted, callables, _cls = env
        if isinstance(target, ast.Name):
            donate = self.callable_donate(value, env)
            if donate is not None:
                callables[target.id] = donate
            elif self.expr_is_device(value, env):
                tainted.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            if (isinstance(value, (ast.Tuple, ast.List))
                    and len(value.elts) == len(target.elts)):
                for t, v in zip(target.elts, value.elts):
                    self._env_pair(t, v, env)
            elif self.expr_is_device(value, env):
                for n in target.elts:
                    if isinstance(n, ast.Name):
                        tainted.add(n.id)

    # -- expression classification ------------------------------------------

    def callable_donate(self, expr, env) -> Optional[tuple[int, ...]]:
        """Donate indices when ``expr`` evaluates to a jit-compiled
        callable (factory call / ``jax.jit(...)``); None otherwise."""
        _tainted, callables, cls = env
        if isinstance(expr, ast.Call):
            if isinstance(expr.func, ast.Name):
                if expr.func.id in self.factories:
                    return self.factories[expr.func.id]
            if _is_jax_jit(expr.func):
                return _donate_from_keywords(expr)
        elif isinstance(expr, ast.Name) and expr.id in callables:
            return callables[expr.id]
        else:
            a = _self_attr(expr)
            if a is not None and cls is not None:
                got = self.cls_callables.get(cls, {}).get(a)
                if got is not None:
                    return got
        return None

    def _any_device(self, expr, env) -> bool:
        """ANY-part device — used only for container taint, where a tuple
        holding one device array makes the container device-bearing."""
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self._any_device(e, env) for e in expr.elts)
        return self.expr_is_device(expr, env)

    def expr_is_device(self, expr, env) -> bool:
        tainted, callables, cls = env
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        if isinstance(expr, ast.Attribute):
            if expr.attr in _METADATA_ATTRS:
                return False
            a = _self_attr(expr)
            if a is not None:
                return cls is not None and a in self.cls_attrs.get(cls, set())
            return self.expr_is_device(expr.value, env)
        if isinstance(expr, ast.Subscript):
            return self.expr_is_device(expr.value, env)
        if isinstance(expr, ast.Starred):
            return self.expr_is_device(expr.value, env)
        if isinstance(expr, ast.Call):
            root = _attr_root(expr.func)
            if root in _DEVICE_ROOTS:
                return True
            if root == "jax":
                # jax.jit -> callable, jax.profiler.* -> context manager,
                # jax.device_get -> HOST by definition
                if isinstance(expr.func, ast.Attribute) and expr.func.attr in (
                        "jit", "device_get"):
                    return False
                if (isinstance(expr.func, ast.Attribute)
                        and isinstance(expr.func.value, ast.Attribute)
                        and expr.func.value.attr == "profiler"):
                    return False
                return True
            if isinstance(expr.func, ast.Name):
                if expr.func.id in self.device_fns:
                    return True
                if expr.func.id in callables:
                    return True
                if (expr.func.id[:1].isupper()
                        and any(self._any_device(a, env) for a in expr.args)
                        or expr.func.id[:1].isupper()
                        and any(kw.value is not None
                                and self._any_device(kw.value, env)
                                for kw in expr.keywords)):
                    # pytree constructor (ScanState/StaticArrays) over
                    # device leaves
                    return True
            if isinstance(expr.func, ast.Attribute):
                if expr.func.attr == "_replace" and self.expr_is_device(
                        expr.func.value, env):
                    return True
                a = _self_attr(expr.func)
                if a is not None and cls is not None \
                        and a in self.cls_callables.get(cls, {}):
                    return True
            return False
        if isinstance(expr, (ast.Tuple, ast.List)):
            return bool(expr.elts) and all(
                self.expr_is_device(e, env) for e in expr.elts)
        if isinstance(expr, ast.BinOp):
            return (self.expr_is_device(expr.left, env)
                    or self.expr_is_device(expr.right, env))
        if isinstance(expr, ast.BoolOp):
            return any(self.expr_is_device(v, env) for v in expr.values)
        if isinstance(expr, ast.UnaryOp):
            return self.expr_is_device(expr.operand, env)
        if isinstance(expr, ast.Compare):
            return (self.expr_is_device(expr.left, env)
                    or any(self.expr_is_device(c, env)
                           for c in expr.comparators))
        if isinstance(expr, ast.IfExp):
            return (self.expr_is_device(expr.body, env)
                    and self.expr_is_device(expr.orelse, env))
        return False


# -- annotations ------------------------------------------------------------


def _scan_annotations(src_lines: list[str]):
    """(sync annotations: line -> reason-or-None, static annotation
    lines).  Lines are 1-based."""
    sync: dict[int, Optional[str]] = {}
    static: set[int] = set()
    for i, line in enumerate(src_lines, start=1):
        m = _SYNC_ANN_RE.search(line)
        if m:
            reason = (m.group(1) or "").strip()
            sync[i] = reason or None
        elif _STATIC_ANN_RE.search(line):
            static.add(i)
    return sync, static


def _sync_sanctioned(sync_ann: dict[int, Optional[str]], line: int) -> bool:
    """A site is sanctioned by a reasoned sync annotation on its own line
    or the line above."""
    return bool(sync_ann.get(line) or sync_ann.get(line - 1))


def _static_sanctioned(static_ann: set[int], line: int) -> bool:
    return line in static_ann or (line - 1) in static_ann


def _materialization(call: ast.Call):
    """(operand expr, form label) when ``call`` is a host-materialization
    shape; None otherwise."""
    if isinstance(call.func, ast.Name):
        if call.func.id in ("int", "float", "bool") and len(call.args) == 1:
            return call.args[0], call.func.id
        return None
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        if attr in ("item", "tolist", "block_until_ready") and not call.args:
            return call.func.value, attr
        root = _attr_root(call.func)
        if root == "np" and attr in ("asarray", "array") and call.args:
            return call.args[0], f"np.{attr}"
        if root == "jax" and attr == "device_get" and call.args:
            return call.args[0], "device_get"
    return None


def _expr_label(expr: ast.expr) -> Optional[str]:
    """A stable dotted label for a simple operand (``self._state.round_robin``
    -> ``_state.round_robin``); None for complex expressions."""
    parts: list[str] = []
    while True:
        if isinstance(expr, ast.Attribute):
            parts.append(expr.attr)
            expr = expr.value
        elif isinstance(expr, ast.Subscript):
            expr = expr.value
        elif isinstance(expr, ast.Call):
            # jnp.sum(x) -> label through the call's first operand
            if expr.args:
                expr = expr.args[0]
            else:
                return None
        elif isinstance(expr, ast.Name):
            if expr.id != "self":
                parts.append(expr.id)
            return ".".join(reversed(parts)) if parts else None
        else:
            return None


# -- the pass ---------------------------------------------------------------


def _analyze_file(rel: str, tree: ast.Module, src_lines: list[str],
                  hot: set[str]) -> list[Finding]:
    findings: list[Finding] = []
    funcs = _collect_funcs(tree)
    idx = _ModuleIndex(tree, funcs)
    sync_ann, static_ann = _scan_annotations(src_lines)
    # functions whose bodies are TRACED (inside a jit factory or directly
    # jit-decorated): host-materialization there is trace-safety's beat
    # (TS101), not a sync-budget question
    traced: set[int] = set()
    for f in funcs:
        if isinstance(f.node, ast.FunctionDef) \
                and _decorated_jit(f.node) is not None:
            traced.add(id(f))
        p = f.parent
        while p is not None:
            if p.name in idx.factories or id(p) in traced:
                traced.add(id(f))
                break
            p = p.parent

    def cls_of(f: _Func) -> Optional[str]:
        parts = f.qualname.split(".")
        return parts[0] if parts[0] in idx.classes else None

    env_cache: dict[int, tuple] = {}

    def env_of(f: _Func):
        got = env_cache.get(id(f))
        if got is None:
            got = idx.local_env(f.node, cls=cls_of(f))
            # closure visibility: merge the enclosing chain's taint so a
            # nested def reading an outer device local stays modeled
            p = f.parent
            while p is not None:
                pt, pc, _ = env_of(p)
                got[0].update(pt)
                got[1].update(pc)
                p = p.parent
            env_cache[id(f)] = got
        return got

    _dc601(rel, findings, funcs, idx, env_of, cls_of)
    if rel in hot:
        _dc602(rel, findings, funcs, idx, env_of, traced, sync_ann)
    used_static = _dc603(rel, findings, funcs, idx, env_of, static_ann)
    _dc604(rel, findings, funcs, idx)
    _dc605(rel, findings, funcs, src_lines, sync_ann, static_ann, used_static)
    return findings


def _dc601(rel, findings, funcs, idx, env_of, cls_of) -> None:
    for f in funcs:
        env = env_of(f)
        _tainted, callables, _cls = env
        cname = cls_of(f)
        for node in ast.walk(f.node):
            if not isinstance(node, ast.Call):
                continue
            donate: tuple[int, ...] = ()
            callee_desc = None
            if isinstance(node.func, ast.Name) \
                    and node.func.id in callables:
                donate = callables[node.func.id]
                callee_desc = node.func.id
            else:
                a = _self_attr(node.func)
                if a is not None and cname is not None:
                    donate = idx.cls_callables.get(cname, {}).get(a, ())
                    callee_desc = f"self.{a}"
            if not donate:
                continue
            enc = _enclosing(funcs, node.lineno)
            if enc is None or enc.node is not f.node:
                continue  # the innermost owner reports it, once
            for di in donate:
                if di >= len(node.args):
                    continue
                actual = node.args[di]
                path = _donated_path(actual)
                if path is None:
                    continue
                _check_donated_use(rel, findings, funcs, idx, f, node,
                                   path, di, callee_desc)


def _donated_path(expr: ast.expr):
    a = _self_attr(expr)
    if a is not None:
        return ("self", a)
    if isinstance(expr, ast.Name):
        return ("name", expr.id)
    return None


def _path_loads(tree_node, path, lo: int, hi: int) -> list[int]:
    kind, name = path
    out = []
    for n in ast.walk(tree_node):
        if not (lo < n.lineno <= hi if hasattr(n, "lineno") else False):
            continue
        if kind == "self":
            if (_self_attr(n) == name and isinstance(n, ast.Attribute)
                    and isinstance(n.ctx, ast.Load)):
                out.append(n.lineno)
        else:
            if (isinstance(n, ast.Name) and n.id == name
                    and isinstance(n.ctx, ast.Load)):
                out.append(n.lineno)
    return out


def _path_stores(tree_node, path, lo: int) -> list[int]:
    kind, name = path
    out = []
    for n in ast.walk(tree_node):
        if not hasattr(n, "lineno") or n.lineno <= lo:
            continue
        if kind == "self":
            if (_self_attr(n) == name and isinstance(n, ast.Attribute)
                    and isinstance(n.ctx, ast.Store)):
                out.append(n.lineno)
        else:
            if (isinstance(n, ast.Name) and n.id == name
                    and isinstance(n.ctx, ast.Store)):
                out.append(n.lineno)
    return out


def _check_donated_use(rel, findings, funcs, idx, f, call, path, di,
                       callee_desc) -> None:
    kind, name = path
    call_end = call.end_lineno or call.lineno
    fn_end = f.node.end_lineno or f.node.lineno
    stores = _path_stores(f.node, path, call_end)
    rebind = min(stores) if stores else fn_end + 1
    window_hi = min(rebind, fn_end)
    label = f"self.{name}" if kind == "self" else name
    loads = _path_loads(f.node, path, call_end, window_hi)
    for ln in loads:
        findings.append(Finding(
            code="DC601", path=rel, line=ln,
            symbol=f"{f.qualname}.{name}",
            message=(
                f"use after donate: `{label}` was donated (arg {di} of "
                f"`{callee_desc}(...)`, line {call.lineno}) — its buffer "
                f"is dead the moment the dispatch returns, but it is read "
                f"here before the next rebind; rebind from the call's "
                f"outputs first"
            ),
        ))
    if kind != "self":
        return
    # one callee hop: a method/nested-def invoked inside the window that
    # reads the donated attribute is the same bug, one frame down
    cls_name = f.qualname.split(".")[0]
    methods = {m.name: m for m in funcs
               if m.qualname.startswith(cls_name + ".")
               and m.node is not f.node}
    for n in ast.walk(f.node):
        if not isinstance(n, ast.Call) or not hasattr(n, "lineno"):
            continue
        if not (call_end < n.lineno <= window_hi):
            continue
        m = _self_attr(n.func)
        if m is None and isinstance(n.func, ast.Name):
            m = n.func.id
        callee = methods.get(m) if m else None
        if callee is None:
            continue
        if _path_loads(callee.node, path, 0, 10 ** 9):
            findings.append(Finding(
                code="DC601", path=rel, line=n.lineno,
                symbol=f"{f.qualname}.{name}.{callee.name}",
                message=(
                    f"use after donate: `{callee.qualname}` (called here, "
                    f"before `{label}` is rebound) reads `{label}`, whose "
                    f"buffer was donated at line {call.lineno}"
                ),
            ))


def _dc602(rel, findings, funcs, idx, env_of, traced, sync_ann) -> None:
    for f in funcs:
        if id(f) in traced:
            continue
        env = env_of(f)
        for node in ast.walk(f.node):
            if not isinstance(node, ast.Call):
                continue
            enc = _enclosing(funcs, node.lineno)
            if enc is None or enc.node is not f.node:
                continue
            got = _materialization(node)
            if got is None:
                continue
            operand, form = got
            if not idx.expr_is_device(operand, env):
                continue
            if _sync_sanctioned(sync_ann, node.lineno):
                continue
            label = _expr_label(operand) or form
            findings.append(Finding(
                code="DC602", path=rel, line=node.lineno,
                symbol=f"{f.qualname}.{label}",
                message=(
                    f"host sync outside the budget: `{form}` materializes "
                    f"a device value in wave-hot-path function "
                    f"`{f.qualname}` with no `# device: sync — <reason>` "
                    f"annotation — every blocking device→host round-trip "
                    f"on this path must be a declared, counted site"
                ),
            ))


def _dc603(rel, findings, funcs, idx, env_of, static_ann) -> set[int]:
    """Returns the annotation lines actually consumed (for DC605)."""
    used: set[int] = set()

    def consume(line: int) -> bool:
        hit = False
        for ln in (line, line - 1):
            if ln in static_ann:
                used.add(ln)
                hit = True
        return hit

    # _pad_to calls nested under a sticky wrapper are sanctioned
    sticky_wrapped: set[int] = set()
    for node in ast.walk(idx.tree):
        if isinstance(node, ast.Call):
            cal = _callee_attr_name(node) or (
                node.func.id if isinstance(node.func, ast.Name) else None)
            if cal in ("_sticky_pad", "_bucket"):
                for sub in ast.walk(node):
                    if sub is not node and isinstance(sub, ast.Call):
                        sticky_wrapped.add(id(sub))

    for node in ast.walk(idx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = node.func.id if isinstance(node.func, ast.Name) else None
        enc = _enclosing(funcs, node.lineno)
        qual = enc.qualname if enc is not None else "<module>"
        if name == "_pad_to":
            if id(node) in sticky_wrapped:
                continue
            if enc is not None and enc.name in ("_pad_to", "_sticky_pad",
                                                "_bucket"):
                continue
            if consume(node.lineno):
                continue
            findings.append(Finding(
                code="DC603", path=rel, line=node.lineno,
                symbol=f"{qual}._pad_to",
                message=(
                    "shape-bearing pad outside the sticky buckets: a bare "
                    "`_pad_to(...)` result that reaches the device keys a "
                    "fresh XLA compile every time it moves — route it "
                    "through `_sticky_pad`/`_bucket`, or annotate the site "
                    "`# device: static` with the stability argument"
                ),
            ))
        elif name == "_pow2_width":
            if enc is not None and enc.name == "_pow2_width":
                continue
            if consume(node.lineno):
                continue
            findings.append(Finding(
                code="DC603", path=rel, line=node.lineno,
                symbol=f"{qual}._pow2_width",
                message=(
                    "shape-bearing width at a jit boundary: each distinct "
                    "`_pow2_width(...)` result is its own compiled "
                    "executable — annotate the site `# device: static` to "
                    "declare the accepted <= log2(N) compile budget"
                ),
            ))
        elif name in idx.compile_keyed:
            if consume(node.lineno):
                continue  # one annotation sanctions the whole boundary
            if enc is None:
                continue
            for i, arg in enumerate(list(node.args)
                                    + [kw.value for kw in node.keywords]):
                if _compile_key_ok(arg, enc.node, idx):
                    continue
                if consume(arg.lineno):
                    continue
                desc = _expr_label(arg) or f"arg{i}"
                findings.append(Finding(
                    code="DC603", path=rel, line=arg.lineno,
                    symbol=f"{qual}.{name}.{desc}",
                    message=(
                        f"un-normalized compile key: argument `{desc}` of "
                        f"compile-keyed factory `{name}(...)` is not a "
                        f"normalized scalar (`int()`/`bool()`/`tuple()`/"
                        f"constant/typed parameter) — a drifting value "
                        f"here recompiles per distinct value; normalize "
                        f"it or annotate the call `# device: static`"
                    ),
                ))
    return used


def _compile_key_ok(arg: ast.expr, enc_fn, idx: _ModuleIndex) -> bool:
    if isinstance(arg, ast.Constant):
        return True
    if isinstance(arg, ast.Call):
        if isinstance(arg.func, ast.Name):
            if arg.func.id in ("int", "bool", "float", "tuple", "str", "len"):
                return True
            callee = idx.top_fns.get(arg.func.id)
            if callee is not None and isinstance(callee.returns, ast.Name) \
                    and callee.returns.id in ("int", "bool", "str", "float"):
                return True
        return False
    if isinstance(arg, ast.Name):
        # bool/int-annotated parameter of the enclosing function
        for a in (enc_fn.args.args + enc_fn.args.kwonlyargs
                  + enc_fn.args.posonlyargs):
            if a.arg == arg.id:
                return (isinstance(a.annotation, ast.Name)
                        and a.annotation.id in ("bool", "int", "str",
                                                "float", "tuple"))
        # local single-assigned to an ok value
        assigns = [s for s in _own_statements(enc_fn)
                   if isinstance(s, ast.Assign)
                   and any(isinstance(t, ast.Name) and t.id == arg.id
                           for t in s.targets)]
        if len(assigns) == 1:
            return _compile_key_ok(assigns[0].value, enc_fn, idx)
    return False


def _dc604(rel, findings, funcs, idx) -> None:
    for f in funcs:
        if f.parent is not None:
            continue  # analyze each outermost function's whole subtree
        roots: set[str] = set()
        for g in funcs:
            if g is not f and not g.qualname.startswith(f.qualname + "."):
                continue
            for a in (g.node.args.args + g.node.args.kwonlyargs
                      + g.node.args.posonlyargs):
                if a.arg == "node_info_map":
                    roots.add(a.arg)
        # working copies: w = dict(root) / w = root
        changed = True
        while changed:
            changed = False
            for node in ast.walk(f.node):
                if not isinstance(node, ast.Assign):
                    continue
                v = node.value
                src = None
                if (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                        and v.func.id == "dict" and len(v.args) == 1
                        and isinstance(v.args[0], ast.Name)):
                    src = v.args[0].id
                elif isinstance(v, ast.Name):
                    src = v.id
                if src in roots:
                    for t in node.targets:
                        if isinstance(t, ast.Name) and t.id not in roots:
                            roots.add(t.id)
                            changed = True
        if not roots:
            continue

        def from_root(expr) -> bool:
            """``root[k]`` / ``root.get(k)`` — a NodeInfo straight off the
            snapshot map."""
            if isinstance(expr, ast.Subscript):
                return (isinstance(expr.value, ast.Name)
                        and expr.value.id in roots)
            if (isinstance(expr, ast.Call)
                    and isinstance(expr.func, ast.Attribute)
                    and expr.func.attr == "get"
                    and isinstance(expr.func.value, ast.Name)):
                return expr.func.value.id in roots
            return False

        snapshot_names: set[str] = set()
        sanctioned_names: set[str] = set()
        for node in ast.walk(f.node):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if not isinstance(t, ast.Name):
                        continue
                    v = node.value
                    if from_root(v):
                        snapshot_names.add(t.id)
                    elif (isinstance(v, ast.Call)
                          and ((isinstance(v.func, ast.Name)
                                and v.func.id == "mutable_info")
                               or _callee_attr_name(v) == "mutable_info")):
                        sanctioned_names.add(t.id)
            elif isinstance(node, ast.For):
                # for name, info in root.items() / for info in root.values()
                it = node.iter
                if (isinstance(it, ast.Call)
                        and isinstance(it.func, ast.Attribute)
                        and isinstance(it.func.value, ast.Name)
                        and it.func.value.id in roots):
                    if (it.func.attr == "items"
                            and isinstance(node.target, ast.Tuple)
                            and len(node.target.elts) == 2
                            and isinstance(node.target.elts[1], ast.Name)):
                        snapshot_names.add(node.target.elts[1].id)
                    elif (it.func.attr == "values"
                          and isinstance(node.target, ast.Name)):
                        snapshot_names.add(node.target.id)
        # a name ever sanctioned wins (over-approximate toward silence)
        snapshot_only = snapshot_names - sanctioned_names

        for node in ast.walk(f.node):
            enc = _enclosing(funcs, getattr(node, "lineno", 0)) if hasattr(
                node, "lineno") else None
            qual = enc.qualname if enc is not None else f.qualname
            if isinstance(node, ast.Call) and isinstance(node.func,
                                                         ast.Attribute) \
                    and node.func.attr in NODEINFO_MUTATORS:
                recv = node.func.value
                label = None
                if isinstance(recv, ast.Name) and recv.id in snapshot_only:
                    label = recv.id
                elif from_root(recv):
                    label = _expr_label(recv) or "<snapshot>"
                if label is not None:
                    findings.append(Finding(
                        code="DC604", path=rel, line=node.lineno,
                        symbol=f"{qual}.{label}.{node.func.attr}",
                        message=(
                            f"snapshot write bypasses clone-on-write: "
                            f"`.{node.func.attr}(...)` mutates a NodeInfo "
                            f"taken straight from the snapshot map — it "
                            f"corrupts the scheduler cache's CoW snapshot; "
                            f"obtain the target via `mutable_info(...)` "
                            f"so the first write clones"
                        ),
                    ))
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id in snapshot_only):
                        findings.append(Finding(
                            code="DC604", path=rel, line=node.lineno,
                            symbol=f"{qual}.{t.value.id}.{t.attr}",
                            message=(
                                f"snapshot write bypasses clone-on-write: "
                                f"attribute store `{t.value.id}.{t.attr} ="
                                f" ...` on a NodeInfo taken straight from "
                                f"the snapshot map — route the mutation "
                                f"through `mutable_info(...)`"
                            ),
                        ))


def _dc605(rel, findings, funcs, src_lines, sync_ann, static_ann,
           used_static) -> None:
    n = len(src_lines)
    for ln, reason in sorted(sync_ann.items()):
        enc = _enclosing(funcs, ln)
        qual = enc.qualname if enc is not None else "<module>"
        if reason is None:
            findings.append(Finding(
                code="DC605", path=rel, line=ln, symbol=f"{qual}.L{ln}",
                message=(
                    "sync annotation without a reason: `# device: sync` "
                    "must carry `— <why this round-trip is in the budget>` "
                    "— a reasonless sanction is a silent waiver"
                ),
            ))
            continue
        here = src_lines[ln - 1]
        below = src_lines[ln] if ln < n else ""
        if not (_SYNC_LEXEME_RE.search(here)
                or _SYNC_LEXEME_RE.search(below)):
            findings.append(Finding(
                code="DC605", path=rel, line=ln, symbol=f"{qual}.L{ln}",
                message=(
                    "stale sync annotation: neither this line nor the next "
                    "contains a host-materialization call — the sanctioned "
                    "site moved or was removed; delete or move the "
                    "annotation so the sync budget stays honest"
                ),
            ))
    for ln in sorted(static_ann - used_static):
        enc = _enclosing(funcs, ln)
        qual = enc.qualname if enc is not None else "<module>"
        findings.append(Finding(
            code="DC605", path=rel, line=ln, symbol=f"{qual}.L{ln}",
            message=(
                "stale static annotation: `# device: static` sanctions no "
                "pad/width/compile-key site on this line or the next — "
                "delete or move it"
            ),
        ))


def run(
    root: str,
    paths: Optional[list[str]] = None,
    hot_modules: Optional[list[str]] = None,
) -> list[Finding]:
    """``hot_modules`` (default: tracecov's HOT_PATH_MODULES) bounds the
    DC602 sync-budget rule; it is intersected with the scanned set, so
    hot entries outside this pass's scope (store/, client/, …) are
    simply not DC602-checked here — tracecov's own fail-loud covers
    typos in the shared list."""
    files = iter_py_files(root, paths or DEFAULT_PATHS)
    hot = set(hot_modules if hot_modules is not None else HOT_PATH_MODULES)
    findings: list[Finding] = []
    for abs_path, rel in files:
        try:
            with open(abs_path, "r", encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src)
        except SyntaxError as e:
            findings.append(Finding(
                code="DC600", path=rel, line=e.lineno or 1,
                symbol="<parse>",
                message=f"file does not parse: {e.msg}"))
            continue
        findings.extend(
            _analyze_file(rel, tree, src.splitlines(), hot))
    return findings


def sanctioned_sync_sites(
    root: str,
    paths: Optional[list[str]] = None,
) -> dict[str, dict[str, int]]:
    """Per-file, per-function count of VALID ``# device: sync`` sites —
    the static sync budget.  Lexical (annotation + materialization
    lexeme on the annotated or following line), matching DC605's
    validity rule, so the count equals what the pass sanctions.  The
    tier-1 runtime cross-check holds ``FrontierRun.stats['host_syncs']``
    to this bound."""
    out: dict[str, dict[str, int]] = {}
    for abs_path, rel in iter_py_files(root, paths or DEFAULT_PATHS):
        try:
            with open(abs_path, "r", encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src)
        except SyntaxError:
            continue
        lines = src.splitlines()
        funcs = _collect_funcs(tree)
        sync_ann, _static = _scan_annotations(lines)
        per_fn: dict[str, int] = {}
        for ln, reason in sync_ann.items():
            if reason is None:
                continue
            here = lines[ln - 1]
            below = lines[ln] if ln < len(lines) else ""
            site = ln if _SYNC_LEXEME_RE.search(here) else (
                ln + 1 if _SYNC_LEXEME_RE.search(below) else None)
            if site is None:
                continue
            enc = _enclosing(funcs, site)
            qual = enc.qualname if enc is not None else "<module>"
            per_fn[qual] = per_fn.get(qual, 0) + 1
        if per_fn:
            out[rel] = per_fn
    return out
