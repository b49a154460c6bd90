"""wfverify: object-level static verifier for kernels and jit sites.

The pre-flight checker (``analysis/preflight.py``) type-checks the
dataflow abstractly; the contracts that actually burn TPU runs — host
sync inside a traced kernel, recompile storms, unsafe buffer donation,
nondeterministic replay — were caught only *after* dispatch, by the
wf_jit watcher's recompile tripwire (PR 4), the sweep ledger's
donation-miss audit (PR 6), and the chaos harness's record diffs
(PR 8).  This module is their static twin: it analyzes the **actual
function objects** handed to the device operators (map/filter/flatmap
kernels, reduce combiners, FFAT lift/comb, key extractors, sink
callbacks) plus the framework's own wf_jit wrapper bodies, via
``inspect`` + AST with closure/``__globals__`` resolution and bounded
call-depth following — before any batch is staged.

Four pass families (codes in ``analysis/diagnostics.py``):

* **trace-safety (WF80x)** — host materialization of traced values
  (``float()``/``int()``/``.item()``/``np.asarray`` on parameters),
  Python ``if``/``while`` branching on traced values, mutation of
  closure/global/default-arg state inside traced code, bare ``print``.
* **recompile hazards (WF81x)** — trace-time reads that can vary per
  call (``len()`` of a mutable closure container, ``next()``, wall
  clock/RNG baked as constants) and data-dependent output shapes
  (``nonzero``/``unique``/one-arg ``where``/boolean-mask indexing).
* **donation safety (WF82x)** — operands handed to a
  ``donate_argnums`` program and read again after the dispatch on any
  path (the donated buffer is dead; XLA may have overwritten it).
* **determinism for replay (WF61x)** — RNG without an explicitly
  threaded key, wall-clock reads, ``id()``/``hash()`` identity, and
  set-iteration-order dependence in kernels and sink callbacks of a
  durability-enabled graph (docs/DURABILITY.md "Determinism
  requirements", mechanized).

Split of responsibilities: ``tools/wf_lint.py`` stays a pure-AST,
jax-free repo-wide lint; wfverify IMPORTS the graph and inspects the
live callables (closures resolved to their current values, donation
read off the real ``WfJit`` wrappers), so it sees exactly the objects
the runtime will trace.  Entry points: :func:`verify_graph` (wired into
``PipeGraph.check()``), :func:`verify_callable` (one function), and the
CLI ``tools/wf_verify.py``.

Inline suppression (mirrors the wf_lint broad-except convention): a
``# wfverify: ok (reason)`` comment on the flagged line or within the
two lines above suppresses the finding; the reason is mandatory — a
bare ``wfverify: ok`` is rejected and the finding reported with a note.
"""

from __future__ import annotations

import ast
import functools
import inspect
import linecache
import os
import re
import time
import types
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from windflow_tpu.analysis.diagnostics import Diagnostic

#: inline suppression token (reason mandatory, in parentheses)
SUPPRESS_TOKEN = "wfverify: ok"
_SUPPRESS_RE = re.compile(r"wfverify:\s*ok\s*\(\s*[^)\s][^)]*\)")

#: bounded interprocedural following: kernels calling helpers calling
#: helpers — beyond this depth the callee is treated as opaque
MAX_CALL_DEPTH = 3

#: attribute reads on a traced value that yield STATIC Python values
#: (legal to branch on / materialize under jit)
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "itemsize",
                 "at", "aval", "weak_type", "sharding"}

#: builtins whose result is static even over traced arguments
_STATIC_FNS = {"len", "isinstance", "issubclass", "hasattr", "getattr",
               "callable", "type", "repr", "str", "format", "dir"}

#: receiver roots that are jax-side (materialization-safe: jnp.asarray
#: of a tracer stays abstract)
_JAX_ROOTS = {"jnp", "jax", "lax"}

#: method names that mutate their receiver in place
_MUTATORS = {"append", "extend", "insert", "remove", "pop", "clear",
             "add", "discard", "update", "setdefault", "popitem",
             "appendleft", "extendleft", "sort", "reverse"}

#: data-dependent-shape producers (WF812) when fed traced data
_SHAPE_DYNAMIC = {"nonzero", "flatnonzero", "argwhere", "unique",
                  "compress", "extract"}

_WALLCLOCK_TIME_ATTRS = {"time", "time_ns", "monotonic", "monotonic_ns",
                         "perf_counter", "perf_counter_ns", "clock_gettime"}
_WALLCLOCK_DT_ATTRS = {"now", "utcnow", "today"}

_MUTABLE_CONTAINERS = (list, dict, set, bytearray)


# ---------------------------------------------------------------------------
# source / object resolution
# ---------------------------------------------------------------------------

_FILE_CACHE: Dict[str, Optional[Tuple[ast.Module, List[str]]]] = {}


def _file_ast(path: str):
    """Parsed module AST + source lines for a file, cached; None when the
    source is unavailable (builtins, C extensions, REPL frames)."""
    hit = _FILE_CACHE.get(path)
    if hit is not None or path in _FILE_CACHE:
        return hit
    lines = linecache.getlines(path)
    out = None
    if lines:
        try:
            out = (ast.parse("".join(lines), filename=path), lines)
        except SyntaxError:
            out = None
    _FILE_CACHE[path] = out
    return out


def _unwrap(fn):
    fn = inspect.unwrap(fn)
    if isinstance(fn, functools.partial):
        fn = inspect.unwrap(fn.func)
    return fn


def _callable_node(fn) -> Optional[Tuple[ast.AST, str]]:
    """``(function/lambda AST node, file path)`` of a live Python
    function, located by parsing its defining file and matching the code
    object's first line (robust for lambdas inside larger expressions,
    where ``inspect.getsource`` returns unparseable fragments)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return None
    path = code.co_filename
    parsed = _file_ast(path)
    if parsed is None:
        return None
    tree, _ = parsed
    name = getattr(fn, "__name__", "<lambda>")
    argnames = list(code.co_varnames[:code.co_argcount])
    fallback = None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name != name:
                continue
            first = node.decorator_list[0].lineno if node.decorator_list \
                else node.lineno
            if first <= code.co_firstlineno <= node.lineno:
                return node, path
            fallback = fallback or (node, path)
        elif isinstance(node, ast.Lambda) and name == "<lambda>":
            if node.lineno == code.co_firstlineno \
                    and [a.arg for a in node.args.args] == argnames:
                return node, path
    return fallback


class _Env:
    """Name resolution for one function object: closure cells first, then
    ``__globals__``, then builtins — the 'object-level' half of the
    verifier (a closure over an actual ``set`` is provably
    iteration-order dependent; a pure-AST pass could only guess)."""

    def __init__(self, fn) -> None:
        self.closure: Dict[str, Any] = {}
        code = getattr(fn, "__code__", None)
        cells = getattr(fn, "__closure__", None)
        if code is not None and cells:
            for nm, cell in zip(code.co_freevars, cells):
                try:
                    self.closure[nm] = cell.cell_contents
                except ValueError:      # empty cell (still being built)
                    pass
        self.globals = getattr(fn, "__globals__", {}) or {}
        self.free = set(self.closure)

    def resolve(self, name: str) -> Tuple[bool, Any]:
        if name in self.closure:
            return True, self.closure[name]
        if name in self.globals:
            return True, self.globals[name]
        bi = self.globals.get("__builtins__")
        bi = bi.__dict__ if isinstance(bi, types.ModuleType) else (bi or {})
        if isinstance(bi, dict) and name in bi:
            return True, bi[name]
        return False, None

    def resolve_expr(self, node) -> Tuple[bool, Any]:
        """Resolve a Name / dotted-attribute chain to a live object."""
        if isinstance(node, ast.Name):
            return self.resolve(node.id)
        if isinstance(node, ast.Attribute):
            ok, base = self.resolve_expr(node.value)
            if ok:
                try:
                    return True, getattr(base, node.attr)
                except AttributeError:
                    return False, None
        return False, None


def _root_name(node) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_chain(node) -> List[str]:
    """``a.b.c`` -> ["a", "b", "c"]; [] when not a pure dotted chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


# ---------------------------------------------------------------------------
# suppression
# ---------------------------------------------------------------------------

def suppression_at(path: str, lineno: int) -> Optional[str]:
    """``"ok"`` when a justified ``# wfverify: ok (reason)`` covers the
    line (same line or the two above), ``"missing-reason"`` when the
    token is present without a parenthesized reason, else None."""
    lines = linecache.getlines(path)
    window = lines[max(0, lineno - 3):lineno]
    text = "".join(window)
    if SUPPRESS_TOKEN not in text:
        return None
    return "ok" if _SUPPRESS_RE.search(text) else "missing-reason"


# ---------------------------------------------------------------------------
# per-function verification
# ---------------------------------------------------------------------------

class _Finding:
    __slots__ = ("code", "message", "path", "lineno", "hint")

    def __init__(self, code, message, path, lineno, hint=None):
        self.code = code
        self.message = message
        self.path = path
        self.lineno = lineno
        self.hint = hint


class _FnCheck:
    """One function's walk.  ``traced``: the function is jit-traced
    (trace-safety + recompile families apply, parameters are traced
    values); ``durable``: the graph checkpoints (determinism family
    applies).  Findings collect as (code, message, file:line)."""

    def __init__(self, fn, node, path, *, traced: bool, durable: bool,
                 depth: int, findings: List[_Finding],
                 visited: Set[Tuple[Any, bool, bool]]) -> None:
        self.fn = fn
        self.node = node
        self.path = path
        self.traced = traced
        self.durable = durable
        self.depth = depth
        self.findings = findings
        self.visited = visited
        self.env = _Env(fn)
        args = node.args
        names = [a.arg for a in (args.posonlyargs + args.args
                                 + args.kwonlyargs)]
        if args.vararg:
            names.append(args.vararg.arg)
        if args.kwarg:
            names.append(args.kwarg.arg)
        self.params = set(names)
        self.tainted: Set[str] = set(names) if traced else set()
        #: params with mutable defaults (shared across calls: mutating
        #: one inside traced code is cross-trace state)
        self.mutable_defaults: Set[str] = set()
        defaults = getattr(fn, "__defaults__", None) or ()
        pos = (args.posonlyargs + args.args)[-len(defaults):] \
            if defaults else []
        for a, d in zip(pos, defaults):
            if isinstance(d, _MUTABLE_CONTAINERS):
                self.mutable_defaults.add(a.arg)
        # local scope: every Store-ed name is local unless declared
        # global/nonlocal (Python scoping) — mutations of NON-locals are
        # the cross-trace state the WF803 pass hunts
        self.declared: Set[str] = set()
        self.locals: Set[str] = set(self.params)
        body = node.body if isinstance(node.body, list) else [node.body]
        for n in ast.walk(node):
            if isinstance(n, (ast.Global, ast.Nonlocal)):
                self.declared.update(n.names)
            elif isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                self.locals.add(n.id)
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.locals.add(n.name)
        self.locals -= self.declared
        #: inner ``def``s, followable when called or passed to jax HOFs
        self.local_defs = {
            n.name: n for n in ast.walk(node)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n is not node}
        #: (lineno, col) nodes the determinism pass claimed, so the
        #: recompile pass does not double-report the same call
        self._det_hits: Set[Tuple[int, int]] = set()
        self._body = body

    # -- taint ---------------------------------------------------------------
    def expr_tainted(self, e) -> bool:
        if e is None or isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, ast.Attribute):
            if e.attr in _STATIC_ATTRS:
                return False
            return self.expr_tainted(e.value)
        if isinstance(e, ast.Call):
            fname = e.func.id if isinstance(e.func, ast.Name) else None
            if fname in _STATIC_FNS:
                return False
            if self.expr_tainted(e.func):
                return True
            return any(self.expr_tainted(a) for a in e.args) \
                or any(self.expr_tainted(k.value) for k in e.keywords)
        if isinstance(e, ast.Lambda):
            return False
        for child in ast.iter_child_nodes(e):
            if isinstance(child, (ast.expr, ast.comprehension)) \
                    and self.expr_tainted(child):
                return True
            if isinstance(child, ast.comprehension) \
                    and self.expr_tainted(child.iter):
                return True
        return False

    def _taint_target(self, tgt, is_tainted: bool) -> None:
        for n in ast.walk(tgt):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                if is_tainted:
                    self.tainted.add(n.id)
                else:
                    self.tainted.discard(n.id)

    # -- findings ------------------------------------------------------------
    def emit(self, code: str, node, message: str,
             hint: Optional[str] = None) -> None:
        self.findings.append(_Finding(
            code, message, self.path, getattr(node, "lineno", 0), hint))

    # -- walk ----------------------------------------------------------------
    def run(self) -> None:
        for stmt in self._body:
            if isinstance(stmt, ast.stmt):
                self._stmt(stmt)
            else:       # lambda body: one bare expression
                self._expr(stmt)

    def _stmt(self, s) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return      # inner defs are analyzed when called/passed
        if isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = s.value
            if value is not None:
                self._expr(value)
            tainted = self.expr_tainted(value) if value is not None \
                else False
            targets = s.targets if isinstance(s, ast.Assign) \
                else [s.target]
            for t in targets:
                self._check_store(t, s)
                if isinstance(s, ast.AugAssign):
                    tainted = tainted or self.expr_tainted(t)
                self._taint_target(t, tainted)
            return
        if isinstance(s, (ast.If, ast.While)):
            self._branch_test(s.test)
            self._expr(s.test)
            for b in s.body:
                self._stmt(b)
            for b in s.orelse:
                self._stmt(b)
            return
        if isinstance(s, ast.Assert):
            self._branch_test(s.test)
            self._expr(s.test)
            return
        if isinstance(s, ast.For):
            self._expr(s.iter)
            self._order_dep(s.iter)
            self._taint_target(s.target, self.expr_tainted(s.iter))
            for b in s.body + s.orelse:
                self._stmt(b)
            return
        if isinstance(s, ast.With):
            for item in s.items:
                self._expr(item.context_expr)
            for b in s.body:
                self._stmt(b)
            return
        if isinstance(s, ast.Try):
            for b in (s.body + s.orelse + s.finalbody):
                self._stmt(b)
            for h in s.handlers:
                for b in h.body:
                    self._stmt(b)
            return
        if isinstance(s, ast.Return) and s.value is not None:
            self._expr(s.value)
            return
        if isinstance(s, ast.Expr):
            self._expr(s.value)
            return
        for child in ast.iter_child_nodes(s):
            if isinstance(child, ast.stmt):
                self._stmt(child)
            elif isinstance(child, ast.expr):
                self._expr(child)

    # -- stores (WF803: mutation of non-local state) -------------------------
    def _check_store(self, tgt, stmt) -> None:
        if not self.traced:
            return
        for n in ast.walk(tgt):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store) \
                    and n.id in self.declared:
                self.emit(
                    "WF803", stmt,
                    f"assignment to '{n.id}' (declared global/nonlocal) "
                    "inside a jit-traced kernel — runs at trace time "
                    "only, then never again for cached dispatches",
                    hint="thread state through the function's inputs and "
                         "outputs instead")
            elif isinstance(n, ast.Subscript):
                root = _root_name(n.value)
                if root is not None and root not in self.locals \
                        and isinstance(n.ctx, ast.Store):
                    ok, val = self.env.resolve(root)
                    if ok and isinstance(val, _MUTABLE_CONTAINERS):
                        self.emit(
                            "WF803", stmt,
                            f"subscript write to closure/global "
                            f"'{root}' inside a jit-traced kernel — a "
                            "trace-time side effect, silently skipped "
                            "on cached dispatches",
                            hint="return the value instead of mutating "
                                 "enclosing state")

    # -- branch tests (WF802) ------------------------------------------------
    def _branch_test(self, test) -> None:
        if not self.traced:
            return
        bad = self._violating_test(test)
        if bad is not None:
            self.emit(
                "WF802", bad,
                "Python control flow branches on a traced value — jit "
                "tracing cannot concretize it "
                f"({ast.unparse(bad)[:60]!r})",
                hint="use jnp.where / lax.cond / lax.select, or lift the "
                     "decision to a static argument")

    def _violating_test(self, t):
        if isinstance(t, ast.BoolOp):
            for v in t.values:
                bad = self._violating_test(v)
                if bad is not None:
                    return bad
            return None
        if isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not):
            return self._violating_test(t.operand)
        if isinstance(t, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in t.ops):
                return None     # identity/membership: Python-level checks
        if isinstance(t, ast.Call):
            fname = t.func.id if isinstance(t.func, ast.Name) else None
            if fname in _STATIC_FNS:
                return None
        return t if self.expr_tainted(t) else None

    # -- expressions ---------------------------------------------------------
    def _expr(self, e) -> None:
        for node in ast.walk(e):
            if isinstance(node, ast.Call):
                self._call(node)
            elif isinstance(node, ast.Subscript) and self.traced:
                self._subscript(node)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                for gen in node.generators:
                    self._order_dep(gen.iter)
            elif isinstance(node, ast.IfExp):
                self._branch_test(node.test)

    def _subscript(self, node: ast.Subscript) -> None:
        # boolean-mask indexing: x[mask] with a traced comparison mask
        # changes the output shape per batch content (WF812)
        sl = node.slice
        if isinstance(sl, ast.Compare) and self.expr_tainted(sl) \
                and self.expr_tainted(node.value):
            self.emit(
                "WF812", node,
                "boolean-mask indexing of a traced array "
                f"({ast.unparse(node)[:60]!r}) — the output shape "
                "depends on batch content; jit fails to trace it (or "
                "recompiles per survivor count)",
                hint="keep a fixed shape: jnp.where(mask, x, fill) or a "
                     "validity lane")

    # -- calls: the heart of every family ------------------------------------
    def _call(self, node: ast.Call) -> None:
        func = node.func
        fname = func.id if isinstance(func, ast.Name) else None
        attr = func.attr if isinstance(func, ast.Attribute) else None
        chain = _attr_chain(func) if isinstance(func, ast.Attribute) else []
        resolved, obj = self.env.resolve_expr(func) \
            if isinstance(func, (ast.Name, ast.Attribute)) else (False, None)

        if self.durable:
            self._determinism_call(node, fname, attr, chain, resolved, obj)
        if self.traced:
            self._trace_safety_call(node, fname, attr, chain, resolved, obj)
            self._recompile_call(node, fname, attr, chain, resolved, obj)
        self._maybe_follow(node, fname, resolved, obj)

    # .. trace-safety (WF80x) ................................................
    def _trace_safety_call(self, node, fname, attr, chain, resolved,
                           obj) -> None:
        args_tainted = any(self.expr_tainted(a) for a in node.args)
        if fname in ("float", "int", "bool", "complex") and args_tainted:
            self.emit(
                "WF801", node,
                f"{fname}() materializes a traced value on host — "
                "raises ConcretizationTypeError at the first batch",
                hint="stay in jnp (astype / jnp.asarray) or make the "
                     "value a static argument")
            return
        if attr in ("item", "tolist") \
                and self.expr_tainted(node.func.value):
            self.emit(
                "WF801", node,
                f".{attr}() pulls a traced value to host inside a "
                "jit-traced kernel",
                hint="keep the value on device; materialize outside jit")
            return
        if attr in ("asarray", "array") and chain and args_tainted:
            root = chain[0]
            ok, mod = self.env.resolve(root)
            is_np = (ok and getattr(mod, "__name__", "") == "numpy") \
                or (not ok and root in ("np", "numpy"))
            if is_np:
                self.emit(
                    "WF801", node,
                    f"{root}.{attr}() forces a traced value to a host "
                    "numpy array inside a jit-traced kernel",
                    hint="use jnp.asarray (stays abstract under trace)")
                return
        if (attr == "device_get" or attr == "block_until_ready") \
                and (args_tainted or (attr == "block_until_ready"
                                      and self.expr_tainted(
                                          node.func.value))):
            self.emit(
                "WF801", node,
                f"{attr} synchronizes the host on a traced value "
                "inside a jit-traced kernel", hint=None)
            return
        if fname == "print":
            self.emit(
                "WF804", node,
                "print() inside a jit-traced kernel runs at trace time "
                "only (once per compile), never per batch",
                hint="use jax.debug.print for per-dispatch output")

    # .. recompile hazards (WF81x) ...........................................
    def _recompile_call(self, node, fname, attr, chain, resolved,
                        obj) -> None:
        key = (node.lineno, node.col_offset)
        if key in self._det_hits:
            return      # the determinism pass already owns this call
        if fname == "len" and node.args:
            arg = node.args[0]
            if isinstance(arg, (ast.Name, ast.Attribute)):
                ok, val = self.env.resolve_expr(arg)
                root = _root_name(arg)
                if ok and isinstance(val, _MUTABLE_CONTAINERS) \
                        and root not in self.locals:
                    self.emit(
                        "WF811", node,
                        f"len({ast.unparse(arg)}) of a mutable "
                        f"closure/global {type(val).__name__} is baked "
                        "at trace time — growing it later silently "
                        "keeps the old value, or recompiles per length "
                        "in a shape position",
                        hint="freeze the container (tuple) or pass the "
                             "length as an explicit static argument")
            return
        if fname == "next":
            self.emit(
                "WF811", node,
                "next() advances host state at trace time — each "
                "re-trace reads a different value (baked constant / "
                "recompile driver)",
                hint="thread the value in as an argument")
            return
        if not self.durable:
            # wall clock / RNG in a NON-checkpointed traced kernel is
            # not a replay hazard but still a trace-time bake: the
            # determinism pass owns these under durability
            wall = self._wallclock_target(node, chain, resolved, obj)
            if wall:
                self.emit(
                    "WF811", node,
                    f"{wall} runs at trace time inside a jit-traced "
                    "kernel — its value is baked into the compiled "
                    "program as a constant (stale for every cached "
                    "dispatch)",
                    hint="compute it on host and pass it as an operand")
        if attr in _SHAPE_DYNAMIC:
            recv_root = chain[0] if chain else None
            recv_tainted = isinstance(node.func, ast.Attribute) \
                and self.expr_tainted(node.func.value)
            args_tainted = any(self.expr_tainted(a) for a in node.args)
            if (recv_root in _JAX_ROOTS and args_tainted) or recv_tainted:
                self.emit(
                    "WF812", node,
                    f"{attr}() has a data-dependent output shape — "
                    "fails under jit, or recompiles per distinct "
                    "result size",
                    hint="use the size= keyword (jnp.nonzero/unique) or "
                         "a masked fixed-shape formulation")
            return
        if attr == "where" and chain and chain[0] in _JAX_ROOTS \
                and len(node.args) == 1 \
                and self.expr_tainted(node.args[0]):
            self.emit(
                "WF812", node,
                "one-argument where() returns data-dependent-shape "
                "indices — fails under jit, or recompiles per batch",
                hint="use the three-argument jnp.where(cond, x, y)")

    def _wallclock_target(self, node, chain, resolved,
                          obj) -> Optional[str]:
        """Dotted name of a wall-clock read, or None.  Resolution is
        object-level first (the closure may alias ``import time as t``),
        name-based as a fallback."""
        if resolved and isinstance(obj, types.BuiltinFunctionType) \
                and getattr(obj, "__module__", "") == "time" \
                and obj.__name__ in _WALLCLOCK_TIME_ATTRS:
            return f"time.{obj.__name__}"
        if resolved and getattr(obj, "__name__", "") \
                in _WALLCLOCK_DT_ATTRS \
                and "datetime" in getattr(obj, "__qualname__", ""):
            return f"datetime.{obj.__name__}"
        if resolved and getattr(obj, "__name__", "") \
                == "current_time_usecs":
            return "current_time_usecs"
        if len(chain) >= 2:
            if chain[-2] == "time" and chain[-1] in _WALLCLOCK_TIME_ATTRS:
                return ".".join(chain)
            if chain[-2] in ("datetime", "date") \
                    and chain[-1] in _WALLCLOCK_DT_ATTRS:
                return ".".join(chain)
        return None

    # .. determinism (WF61x) .................................................
    def _determinism_call(self, node, fname, attr, chain, resolved,
                          obj) -> None:
        key = (node.lineno, node.col_offset)
        wall = self._wallclock_target(node, chain, resolved, obj)
        if wall:
            self._det_hits.add(key)
            self.emit(
                "WF612", node,
                f"{wall} read in a kernel/callback of a checkpointed "
                "graph — a replay re-reads a DIFFERENT clock, so the "
                "exactly-once fence dedupes records that no longer "
                "match (docs/DURABILITY.md determinism requirements)",
                hint="derive times from the record's event timestamp "
                     "lane, never the host clock")
            return
        if fname == "id":
            self._det_hits.add(key)
            self.emit(
                "WF613", node,
                "id() is a process-lifetime address — differs on every "
                "replay of a checkpointed graph", hint=None)
            return
        if fname == "hash":
            self._det_hits.add(key)
            self.emit(
                "WF613", node,
                "hash() of str/bytes is salted per process "
                "(PYTHONHASHSEED) — a restored run computes different "
                "hashes than the checkpointed one",
                hint="use a content hash (hashlib) or an integer key")
            return
        rng = self._rng_target(node, chain, resolved, obj)
        if rng:
            self._det_hits.add(key)
            self.emit(
                "WF611", node,
                f"{rng} draws from hidden RNG state in a "
                "kernel/callback of a checkpointed graph — replays "
                "diverge from the committed prefix",
                hint="thread a jax.random key derived from the record/"
                     "batch index, or a seeded generator captured in "
                     "the checkpoint")

    def _rng_target(self, node, chain, resolved, obj) -> Optional[str]:
        mod = (getattr(obj, "__module__", "") or "") if resolved else ""
        recv = getattr(obj, "__self__", None) if resolved else None
        if recv is not None:
            # bound methods of stdlib/numpy RNG objects (random.random is
            # a bound method of the module-level Random singleton, with
            # __module__ None — identify it by its receiver's type)
            rt = type(recv)
            rmod = getattr(rt, "__module__", "") or ""
            if rmod == "random" or rmod.startswith("numpy.random"):
                return f"{rmod}.{rt.__name__}." \
                       f"{getattr(obj, '__name__', '?')}"
        if resolved and (mod == "random" or mod.startswith("numpy.random")):
            return f"{mod}.{getattr(obj, '__name__', chain[-1] if chain else '?')}"
        if resolved and mod.startswith("jax.") and "random" in mod:
            # jax.random with the key THREADED from the function's
            # parameters is the explicitly-deterministic pattern;
            # PRNGKey(constant) is deterministic too
            name = getattr(obj, "__name__", "")
            if name in ("PRNGKey", "key"):
                if all(isinstance(a, ast.Constant) for a in node.args):
                    return None
                return f"jax.random.{name} seeded from a non-constant"
            if node.args and self.expr_tainted(node.args[0]):
                return None
            return f"jax.random.{name} with an unthreaded key"
        if not resolved and len(chain) >= 2 and "random" in chain[:-1]:
            if chain[0] == "jax":
                return None     # unresolvable jax.random: assume threaded
            return ".".join(chain)
        if isinstance(node.func, ast.Attribute):
            ok_recv, recv = self.env.resolve_expr(node.func.value)
            tn = type(recv).__name__ if ok_recv else ""
            if tn in ("Generator", "RandomState") and ok_recv \
                    and type(recv).__module__.startswith("numpy.random"):
                return f"numpy.random.{tn}.{node.func.attr}"
        return None

    # .. iteration order (WF614) .............................................
    def _order_dep(self, it) -> None:
        if not self.durable:
            return
        src = self._setish(it)
        if src is not None:
            self.emit(
                "WF614", it,
                f"iteration over a set ({src}) in a kernel/callback of "
                "a checkpointed graph — set order is salted per process "
                "(PYTHONHASHSEED), so a restored run emits a different "
                "order than the checkpointed one",
                hint="iterate sorted(...) or use a list/dict (insertion "
                     "order is deterministic)")

    def _setish(self, e) -> Optional[str]:
        if isinstance(e, (ast.Set, ast.SetComp)):
            return "set literal"
        if isinstance(e, ast.Call):
            fname = e.func.id if isinstance(e.func, ast.Name) else None
            if fname in ("set", "frozenset"):
                return f"{fname}(...)"
            if fname in ("vars", "globals", "locals"):
                return f"{fname}()"
            if fname in ("sorted", "min", "max", "sum", "list", "tuple",
                         "enumerate", "reversed"):
                # order-insensitive consumers are fine; list()/tuple()
                # PRESERVE the inner order, so look through them
                if fname in ("list", "tuple", "enumerate", "reversed") \
                        and e.args:
                    return self._setish(e.args[0])
                return None
        if isinstance(e, (ast.Name, ast.Attribute)):
            ok, val = self.env.resolve_expr(e)
            if ok and isinstance(val, (set, frozenset)):
                return f"'{ast.unparse(e)}' (a {type(val).__name__})"
        return None

    # .. mutation via method calls (WF803) + interprocedural follow ..........
    def _maybe_follow(self, node: ast.Call, fname, resolved, obj) -> None:
        func = node.func
        # closure/global container mutation through a method call
        if self.traced and isinstance(func, ast.Attribute) \
                and func.attr in _MUTATORS:
            root = _root_name(func.value)
            if root is not None and root not in self.locals \
                    and root not in self.params:
                ok, val = self.env.resolve(root)
                if (ok and isinstance(val, _MUTABLE_CONTAINERS)) \
                        or (not ok and root in self.env.free):
                    self.emit(
                        "WF803", node,
                        f"'{root}.{func.attr}()' mutates closure/global "
                        "state inside a jit-traced kernel — runs at "
                        "trace time only, silently skipped on every "
                        "cached dispatch",
                        hint="return the data instead of accumulating "
                             "into enclosing state")
            elif root in self.mutable_defaults:
                self.emit(
                    "WF803", node,
                    f"'{root}.{func.attr}()' mutates a mutable default "
                    "argument inside a jit-traced kernel — state shared "
                    "across calls, written only at trace time",
                    hint="default to None and construct per call")
        # bounded call-depth following
        if self.depth <= 0:
            return
        callee = None
        call_args = node.args
        if resolved and inspect.isfunction(_unwrap(obj)):
            callee = _unwrap(obj)
        elif fname in self.local_defs:
            self._follow_local(self.local_defs[fname], call_args)
            return
        elif isinstance(func, ast.Call):
            # jax higher-order wrappers: vmap(fn)(...) / tree.map-style —
            # the function ARGUMENT is what gets traced
            inner = func
            for a in inner.args:
                if isinstance(a, ast.Name) and a.id in self.local_defs:
                    self._follow_local(self.local_defs[a.id], call_args)
                elif isinstance(a, (ast.Name, ast.Attribute)):
                    ok, f = self.env.resolve_expr(a)
                    if ok and inspect.isfunction(_unwrap(f)):
                        _verify_into(_unwrap(f), traced=self.traced,
                                     durable=self.durable,
                                     depth=self.depth - 1,
                                     findings=self.findings,
                                     visited=self.visited,
                                     taint_all=True)
            return
        if callee is None and isinstance(func, (ast.Name, ast.Attribute)):
            # fn passed as argument to a HOF (jax.vmap(self.fn) handled
            # above); plain calls with function-valued args: follow them
            for a in node.args:
                if isinstance(a, ast.Name) and a.id in self.local_defs:
                    self._follow_local(self.local_defs[a.id], [])
                elif isinstance(a, (ast.Name, ast.Attribute)):
                    ok, f = self.env.resolve_expr(a)
                    if ok and inspect.isfunction(_unwrap(f)) \
                            and _followable(_unwrap(f)):
                        _verify_into(_unwrap(f), traced=self.traced,
                                     durable=self.durable,
                                     depth=self.depth - 1,
                                     findings=self.findings,
                                     visited=self.visited, taint_all=True)
        if callee is not None and _followable(callee):
            any_taint = any(self.expr_tainted(a) for a in call_args) \
                or not self.traced
            _verify_into(callee, traced=self.traced,
                         durable=self.durable, depth=self.depth - 1,
                         findings=self.findings, visited=self.visited,
                         taint_all=any_taint)

    def _follow_local(self, defnode, call_args) -> None:
        """Analyze an inner ``def`` with this function's environment
        (approximation: inner defs close over our scope)."""
        sub = _FnCheck(self.fn, defnode, self.path, traced=self.traced,
                       durable=self.durable, depth=self.depth - 1,
                       findings=self.findings, visited=self.visited)
        key = (defnode, self.traced, self.durable)
        if key in self.visited:
            return
        self.visited.add(key)
        sub.run()


def _followable(fn) -> bool:
    """Follow user/package functions; treat jax/numpy/stdlib as opaque
    (their internals are not the user's kernel code)."""
    mod = getattr(fn, "__module__", "") or ""
    if mod.startswith(("jax", "numpy", "scipy", "builtins", "functools",
                       "itertools", "threading", "json", "math")):
        return False
    return getattr(fn, "__code__", None) is not None


def _verify_into(fn, *, traced: bool, durable: bool, depth: int,
                 findings: List[_Finding], visited: Set,
                 taint_all: bool = True) -> None:
    fn = _unwrap(fn)
    code = getattr(fn, "__code__", None)
    if code is None:
        return
    key = (code, traced, durable)
    if key in visited:
        return
    visited.add(key)
    located = _callable_node(fn)
    if located is None:
        return
    node, path = located
    chk = _FnCheck(fn, node, path, traced=traced and taint_all,
                   durable=durable, depth=depth, findings=findings,
                   visited=visited)
    chk.run()


# ---------------------------------------------------------------------------
# donation pass (WF82x)
# ---------------------------------------------------------------------------

def _possible_tuples(node, assigns: Dict[str, list]) -> Set[tuple]:
    """Every tuple of ints a ``donate_argnums`` expression may evaluate
    to, over literal tuples, conditional expressions, concatenation and
    single-assignment names — conservative union ("may be donated")."""
    if isinstance(node, ast.Tuple):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                vals.append(e.value)
            else:
                return set()
        return {tuple(vals)}
    if isinstance(node, ast.Constant):
        if isinstance(node.value, int):
            return {(node.value,)}
        return set()
    if isinstance(node, ast.IfExp):
        return _possible_tuples(node.body, assigns) \
            | _possible_tuples(node.orelse, assigns)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _possible_tuples(node.left, assigns)
        right = _possible_tuples(node.right, assigns)
        return {a + b for a in left for b in right}
    if isinstance(node, ast.Name):
        out: Set[tuple] = set()
        for v in assigns.get(node.id, []):
            out |= _possible_tuples(v, assigns)
        return out
    return set()


def _donating_positions_in_source(fnode: ast.AST) -> Set[int]:
    """Union of argument positions a function's ``wf_jit``/``jax.jit``
    calls MAY donate, resolved from literals and local assignments."""
    assigns: Dict[str, list] = {}
    for n in ast.walk(fnode):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name):
            assigns.setdefault(n.targets[0].id, []).append(n.value)
    positions: Set[int] = set()
    for n in ast.walk(fnode):
        if not isinstance(n, ast.Call):
            continue
        fname = n.func.id if isinstance(n.func, ast.Name) \
            else (n.func.attr if isinstance(n.func, ast.Attribute)
                  else None)
        if fname not in ("wf_jit", "jit"):
            continue
        for kw in n.keywords:
            if kw.arg == "donate_argnums":
                for tup in _possible_tuples(kw.value, assigns):
                    positions.update(tup)
    return positions


_CLASS_DONATION_CACHE: Dict[type, Dict[str, Set[int]]] = {}


def _class_donation_map(cls: type) -> Dict[str, Set[int]]:
    """attr/method name -> positions it may donate, for one operator
    class: a method whose body creates a ``donate_argnums`` jit donates
    those positions when called-then-called (``self._get_step(c)(...)``),
    and an attribute assigned from such a method (``self._jit_step =
    self._build_step(...)``) donates them when dispatched directly."""
    hit = _CLASS_DONATION_CACHE.get(cls)
    if hit is not None:
        return hit
    out: Dict[str, Set[int]] = {}
    for klass in cls.__mro__:
        if klass in (object,):
            continue
        try:
            src = textwrap_dedent_source(klass)
        except (OSError, TypeError):
            continue
        if src is None:
            continue
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        cnode = next((n for n in ast.walk(tree)
                      if isinstance(n, ast.ClassDef)), None)
        if cnode is None:
            continue
        method_pos: Dict[str, Set[int]] = {}
        for m in cnode.body:
            if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)):
                pos = _donating_positions_in_source(m)
                if pos:
                    method_pos[m.name] = pos
        for name, pos in method_pos.items():
            out.setdefault(name, set()).update(pos)
        # self.ATTR = self.METHOD(...) anywhere in the class
        for n in ast.walk(cnode):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                vchain = _attr_chain(n.value.func)
                if len(vchain) == 2 and vchain[0] == "self" \
                        and vchain[1] in method_pos:
                    for t in n.targets:
                        tchain = _attr_chain(t)
                        if len(tchain) == 2 and tchain[0] == "self":
                            out.setdefault(tchain[1], set()).update(
                                method_pos[vchain[1]])
    _CLASS_DONATION_CACHE[cls] = out
    return out


def textwrap_dedent_source(obj) -> Optional[str]:
    import textwrap
    try:
        return textwrap.dedent(inspect.getsource(obj))
    except (OSError, TypeError):
        return None


class _DonationCheck:
    """Abstract interpretation of one dispatcher function: donated
    operand expressions go live at each donating call and are flagged
    when read again on any later path (branch analysis unions the
    per-path live sets; a store to the expression kills it)."""

    def __init__(self, fn, node, path, owner, findings: List[_Finding],
                 env: Optional[_Env] = None) -> None:
        self.fn = fn
        self.node = node
        self.path = path
        self.owner = owner          # object bound to the first parameter
        self.findings = findings
        self.env = env or _Env(fn)
        args = node.args
        self.self_name = args.args[0].arg if args.args else None
        #: local jit names: X = wf_jit(..., donate_argnums=L) in-body
        self.local_donors = self._local_donors(node)
        self.class_map = _class_donation_map(type(owner)) \
            if owner is not None else {}

    @staticmethod
    def _local_donors(fnode) -> Dict[str, Set[int]]:
        assigns: Dict[str, list] = {}
        for n in ast.walk(fnode):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                assigns.setdefault(n.targets[0].id, []).append(n.value)
        out: Dict[str, Set[int]] = {}
        for name, values in assigns.items():
            for v in values:
                if isinstance(v, ast.Call):
                    fname = v.func.id if isinstance(v.func, ast.Name) \
                        else (v.func.attr
                              if isinstance(v.func, ast.Attribute)
                              else None)
                    if fname in ("wf_jit", "jit"):
                        for kw in v.keywords:
                            if kw.arg == "donate_argnums":
                                for tup in _possible_tuples(kw.value,
                                                            assigns):
                                    out.setdefault(name, set()).update(tup)
        return out

    def donated_positions(self, call: ast.Call) -> Set[int]:
        func = call.func
        # 1. object-level: the callee resolves to a live WfJit wrapper
        if isinstance(func, (ast.Name, ast.Attribute)):
            obj = None
            chain = _attr_chain(func)
            if chain and chain[0] == self.self_name \
                    and self.owner is not None:
                obj = self.owner
                for part in chain[1:]:
                    obj = getattr(obj, part, None)
                    if obj is None:
                        break
            else:
                ok, obj = self.env.resolve_expr(func)
                if not ok:
                    obj = None
            donate = getattr(obj, "_donate", None)
            if donate:
                return set(donate)
            # 2. class-level: self.<attr> assigned from a donating method
            if chain and len(chain) == 2 and chain[0] == self.self_name:
                pos = self.class_map.get(chain[1])
                if pos:
                    return set(pos)
            # 3. in-body: X = wf_jit(..., donate_argnums=...)
            if isinstance(func, ast.Name) \
                    and func.id in self.local_donors:
                return set(self.local_donors[func.id])
        # 4. call-of-call: self._get_step(...)(args) — the inner method
        #    builds and returns the donating jit
        if isinstance(func, ast.Call):
            ichain = _attr_chain(func.func)
            if len(ichain) == 2 and ichain[0] == self.self_name:
                pos = self.class_map.get(ichain[1])
                if pos:
                    return set(pos)
            if len(ichain) == 2 and ichain[0] == self.self_name \
                    and self.owner is not None:
                meth = getattr(type(self.owner), ichain[1], None)
                if meth is not None:
                    msrc = textwrap_dedent_source(meth)
                    if msrc:
                        try:
                            pos = _donating_positions_in_source(
                                ast.parse(msrc))
                        except SyntaxError:
                            pos = set()
                        if pos:
                            return pos
        return set()

    @staticmethod
    def _trackable(e) -> Optional[str]:
        """Stable unparse of a donated operand expression (names and
        attribute/subscript chains only — a computed operand cannot be
        'read again' syntactically)."""
        n = e
        while isinstance(n, (ast.Attribute, ast.Subscript)):
            if isinstance(n, ast.Subscript) \
                    and not isinstance(n.slice, (ast.Name, ast.Constant)):
                return None
            n = n.value
        if isinstance(n, ast.Name):
            return ast.unparse(e)
        return None

    # -- abstract interpretation over statements ----------------------------
    def run(self) -> None:
        self._block(self.node.body, {})

    def _block(self, stmts, live: Dict[str, ast.AST]) -> Dict[str, ast.AST]:
        for s in stmts:
            live = self._stmt(s, live)
        return live

    def _stmt(self, s, live) -> Dict[str, ast.AST]:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return live
        if isinstance(s, ast.If):
            cond_live = dict(live)
            self._events(s.test, cond_live)
            a = self._block(s.body, dict(cond_live))
            b = self._block(s.orelse, dict(cond_live))
            return {**a, **b}
        if isinstance(s, (ast.For, ast.While)):
            if isinstance(s, ast.For):
                self._events(s.iter, live)
            else:
                self._events(s.test, live)
            once = self._block(s.body, dict(live))
            # second pass with the post-body state folded in: a donate
            # late in the body is read by an early statement on the
            # NEXT iteration
            twice = self._block(s.body, {**live, **once})
            merged = {**live, **once, **twice}
            return self._block(s.orelse, merged)
        if isinstance(s, ast.Try):
            out = self._block(s.body, dict(live))
            for h in s.handlers:
                out = {**out, **self._block(h.body, dict(live))}
            out = self._block(s.orelse, out)
            return self._block(s.finalbody, out)
        if isinstance(s, ast.With):
            for item in s.items:
                self._events(item.context_expr, live)
            return self._block(s.body, live)
        # straight-line statement: evaluate value side (loads + calls in
        # positional order), then apply stores
        value_exprs = []
        targets = []
        if isinstance(s, ast.Assign):
            value_exprs = [s.value]
            targets = s.targets
        elif isinstance(s, (ast.AugAssign, ast.AnnAssign)):
            if s.value is not None:
                value_exprs = [s.value]
            targets = [s.target]
            if isinstance(s, ast.AugAssign):
                self._events(s.target, live)    # aug reads before write
        elif isinstance(s, ast.Return):
            if s.value is not None:
                value_exprs = [s.value]
        elif isinstance(s, ast.Expr):
            value_exprs = [s.value]
        else:
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    value_exprs.append(child)
        for e in value_exprs:
            self._events(e, live)
        for t in targets:
            self._kill(t, live)
        return live

    def _events(self, e, live: Dict[str, ast.AST]) -> None:
        """Process one expression tree in approximate evaluation order:
        loads of live donated exprs are violations; donating calls make
        their operands live."""
        if e is None:
            return
        for node in self._ordered(e):
            if isinstance(node, ast.Call):
                donated = self.donated_positions(node)
                if donated:
                    for i, a in enumerate(node.args):
                        if i in donated:
                            expr = self._trackable(a)
                            if expr is not None:
                                live[expr] = node
            elif isinstance(node, (ast.Name, ast.Attribute, ast.Subscript)) \
                    and isinstance(getattr(node, "ctx", None), ast.Load):
                key = None
                try:
                    key = ast.unparse(node)
                except Exception:  # noqa: BLE001 - lint: broad-except-ok
                    # (unparse of synthetic/odd nodes must never break
                    # verification; an unprintable expr is untrackable)
                    key = None
                if key is not None and key in live:
                    call = live[key]
                    self.findings.append(_Finding(
                        "WF821",
                        f"'{key}' was donated to the compiled program "
                        f"at line {call.lineno} and read again after "
                        "the dispatch — the donated buffer is dead "
                        "(XLA may already have overwritten it in "
                        "place)",
                        self.path, node.lineno,
                        hint="read every needed value BEFORE the "
                             "donating call, or drop it from "
                             "donate_argnums"))
                    del live[key]   # one report per donate/read pair

    def _ordered(self, e) -> list:
        """Nodes of an expression in (lineno, col) order — approximate
        left-to-right evaluation order; nested loads inside a donating
        call's own arguments are NOT post-dispatch reads, so calls mask
        their own subtree's loads."""
        calls = [n for n in ast.walk(e) if isinstance(n, ast.Call)
                 and self.donated_positions(n)]
        masked = set()
        for c in calls:
            for sub in ast.walk(c):
                if sub is not c:
                    masked.add(id(sub))
        out = [n for n in ast.walk(e) if id(n) not in masked]
        return sorted(out, key=lambda n: (getattr(n, "lineno", 0),
                                          getattr(n, "col_offset", 0)))

    def _kill(self, t, live: Dict[str, ast.AST]) -> None:
        for n in ast.walk(t):
            if isinstance(n, (ast.Name, ast.Attribute, ast.Subscript)) \
                    and isinstance(getattr(n, "ctx", None), ast.Store):
                try:
                    key = ast.unparse(n)
                except Exception:  # noqa: BLE001 - lint: broad-except-ok
                    # (same stance as the load side: unprintable target
                    # just kills nothing)
                    continue
                live.pop(key, None)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

_KERNEL_CACHE: Dict[Tuple[Any, bool, bool], List[_Finding]] = {}


def verify_callable(fn, *, traced: bool, durable: bool = False,
                    depth: int = MAX_CALL_DEPTH) -> List[_Finding]:
    """Raw findings (pre-suppression) for one function object, cached by
    code object so graphs rebuilt with the same kernels re-pay nothing.
    Functions WITH closure cells are never cached: the findings depend
    on the cell values (a framework step closure resolves ``self.fn`` to
    a different user kernel per operator instance), and one code object
    is shared by every instance."""
    fn = _unwrap(fn)
    code = getattr(fn, "__code__", None)
    if code is None:
        return []
    cacheable = not getattr(fn, "__closure__", None)
    key = (code, traced, durable)
    if cacheable:
        hit = _KERNEL_CACHE.get(key)
        if hit is not None:
            return hit
    findings: List[_Finding] = []
    _verify_into(fn, traced=traced, durable=durable, depth=depth,
                 findings=findings, visited=set())
    if cacheable:
        _KERNEL_CACHE[key] = findings
    return findings


def verify_dispatcher(fn, owner=None) -> List[_Finding]:
    """Donation pass (WF82x) over one dispatcher function/method —
    ``owner`` binds the first parameter so ``self.X`` resolves on the
    live object (WfJit ``_donate`` sets, lazily-built step tables)."""
    fn = _unwrap(fn)
    located = _callable_node(fn)
    if located is None:
        return []
    node, path = located
    if isinstance(node, ast.Lambda):
        return []
    findings: List[_Finding] = []
    _DonationCheck(fn, node, path, owner, findings).run()
    return findings


class VerifyReport:
    """Outcome of :func:`verify_graph`: reportable diagnostics,
    suppressed findings (justified inline), and the wall cost."""

    def __init__(self) -> None:
        self.diagnostics: List[Diagnostic] = []
        self.suppressed: List[Diagnostic] = []
        self.checked = 0
        self.check_ms = 0.0

    def to_json(self) -> dict:
        return {
            "checked_callables": self.checked,
            "check_ms": self.check_ms,
            "findings": len(self.diagnostics),
            "suppressed": len(self.suppressed),
            "diagnostics": [d.to_json() for d in self.diagnostics],
            "suppressed_diagnostics": [d.to_json()
                                       for d in self.suppressed],
        }


def _graph_callables(graph):
    """Yield ``(fn, op_name, role, traced)`` for every user callable the
    runtime will invoke: device kernels (traced) and host callbacks
    (determinism surface).  Degrades per-attribute: unknown operator
    types contribute whatever standard attributes they carry."""
    from windflow_tpu.ops.chained import ChainedHost, ChainedTPU
    seen: Set[int] = set()

    def one(fn, name, role, traced):
        if fn is None or not callable(fn) or id(fn) in seen:
            return None
        seen.add(id(fn))
        return (fn, name, role, traced)

    for op in graph._topo_operators():
        is_tpu = getattr(op, "is_tpu", False)
        if isinstance(op, (ChainedTPU, ChainedHost)):
            for kind, fn, *_ in op.specs:
                got = one(fn, op.name, f"{kind} stage", is_tpu)
                if got:
                    yield got
        for attr, role in (("fn", "kernel"), ("comb", "combiner"),
                           ("lift", "window lift"),
                           ("batch_fn", "batch generator"),
                           ("ts_fn", "timestamp kernel"),
                           ("gen_fn", "generator"),
                           ("deser_fn", "deserializer"),
                           ("ser_fn", "serializer"),
                           ("wm_fn", "watermark fn"),
                           ("ts_extractor", "timestamp extractor"),
                           ("closing_func", "closing callback")):
            fn = getattr(op, attr, None)
            traced = is_tpu and attr in ("fn", "comb", "lift",
                                         "batch_fn", "ts_fn")
            got = one(fn, op.name, role, traced)
            if got:
                yield got
        kx = getattr(op, "key_extractor", None)
        got = one(kx, op.name, "key extractor", is_tpu)
        if got:
            yield got


def _framework_traced_bodies(graph):
    """The framework's own wf_jit wrapper bodies reachable from the
    graph's operators RIGHT NOW (pre-start): the functions held by live
    ``WfJit`` wrappers.  Lazily-built step programs (reduce/ffat/
    stateful) close over the same user kernels verified directly."""
    out = []
    seen: Set[int] = set()
    for op in graph._topo_operators():
        for holder in (getattr(op, "_jit_step", None),
                       *(getattr(op, "_jit_steps", {}) or {}).values()):
            fn = getattr(holder, "_fn", None)
            if fn is not None and id(fn) not in seen:
                seen.add(id(fn))
                out.append((fn, op.name))
        chain = getattr(op, "_chain", None)
        if chain is not None:
            fn = getattr(getattr(chain, "_jit", None), "_fn", None)
            if fn is not None and id(fn) not in seen:
                seen.add(id(fn))
                out.append((fn, op.name))
    return out


def _dispatch_methods(graph):
    """Per-operator dispatcher bodies for the donation pass: the class
    ``_step`` methods that hand operands to donating programs."""
    out = []
    seen: Set[Tuple[type, str]] = set()
    for op in graph._topo_operators():
        cls = type(op)
        for mname in ("_step",):
            meth = getattr(cls, mname, None)
            if meth is None or (cls, mname) in seen:
                continue
            seen.add((cls, mname))
            out.append((meth, op, op.name))
    return out


_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _apply_suppressions(findings: List[_Finding], op_name: Optional[str],
                        report: VerifyReport,
                        seen: Optional[Set[Tuple]] = None) -> None:
    for f in findings:
        if seen is not None:
            key = (f.code, f.path, f.lineno)
            if key in seen:
                continue    # one report per site: a kernel reached both
                #             directly and through a wrapper body's
                #             closure counts once
            seen.add(key)
        sup = suppression_at(f.path, f.lineno)
        path = f.path
        if path.startswith(_REPO + os.sep):
            path = os.path.relpath(path, _REPO)
        d = Diagnostic(f.code, f.message, node=op_name,
                       location=f"{path}:{f.lineno}", hint=f.hint)
        if sup == "ok":
            report.suppressed.append(d)
        elif sup == "missing-reason":
            d.message += (" [a 'wfverify: ok' suppression without a "
                          "(reason) was ignored — justify it]")
            report.diagnostics.append(d)
        else:
            report.diagnostics.append(d)


def verify_graph(graph) -> VerifyReport:
    """Run all four wfverify families over a composed PipeGraph's live
    callables.  The determinism family (WF61x) activates when the
    graph's config enables durability; trace-safety/recompile apply to
    device-traced kernels; the donation pass covers every operator's
    dispatcher.  ``PipeGraph.check()`` folds the resulting diagnostics
    into the preflight list (severity policy follows
    ``Config.preflight`` exactly like the WF1xx-WF6xx codes)."""
    t0 = time.perf_counter()
    report = VerifyReport()
    seen: Set[Tuple] = set()
    durable = bool(getattr(graph.config, "durability", ""))
    for fn, op_name, role, traced in _graph_callables(graph):
        findings = verify_callable(fn, traced=traced, durable=durable)
        report.checked += 1
        _apply_suppressions(findings, op_name, report, seen)
    for fn, op_name in _framework_traced_bodies(graph):
        findings = verify_callable(fn, traced=True, durable=durable)
        report.checked += 1
        _apply_suppressions(findings, op_name, report, seen)
    for meth, owner, op_name in _dispatch_methods(graph):
        findings = verify_dispatcher(meth, owner)
        report.checked += 1
        _apply_suppressions(findings, op_name, report, seen)
    report.check_ms = round((time.perf_counter() - t0) * 1e3, 3)
    return report
