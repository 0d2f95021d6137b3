"""Name-based call-graph approximation over the package.

The ``traced-branch`` and jit-scoped ``host-sync`` checks need to know
which functions can execute *under a trace* — a ``float(x)`` in a
helper is harmless Python until some ``@jax.jit`` entry point calls it
with a tracer. Whole-program points-to analysis is out of scope for a
linter; this module builds the standard cheap approximation:

* **entries** — functions wrapped by ``jax.jit`` / ``pjit`` /
  ``shard_map`` (decorator form, ``jax.jit(f)`` call form, and lambdas
  passed to them), plus anything passed to ``lax`` control-flow
  combinators (``lax.scan``/``cond``/``while_loop``/``fori_loop`` run
  their operands traced);
* **edges** — resolved by NAME, within the defining module first, then
  through that module's explicit imports (``from paddle_tpu.x import
  f`` / ``import paddle_tpu.x as m; m.f(...)``). ``self.f(...)`` and
  ``cls.f(...)`` resolve to any same-module method called ``f``.

Tracing wrappers are matched by their public names, and function
operands wrapped in ``functools.partial(f, ...)`` are peeled
(``shard_map(partial(local, axis_name=ax), ...)`` marks ``local``).

False edges (two modules defining the same helper name) only ever make
the dependent rules MORE conservative — a function is flagged as
jit-reachable when it is not — and the baseline + inline suppressions
absorb that. Missed edges (getattr dispatch, callables threaded
through dicts like the fused-decode plans) are the approximation's
documented blind spot; the runtime sanitizer (analysis/runtime.py) is
the enforcement layer that does not depend on static reachability.
"""

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

__all__ = ["CallGraph", "build_callgraph"]

#: callables whose function-valued arguments execute traced
_TRACING_WRAPPERS = {
    "jit", "pjit", "shard_map", "scan", "cond", "while_loop",
    "fori_loop", "switch", "associative_scan", "checkpoint", "remat",
    "custom_vjp", "custom_jvp", "vmap", "pmap", "grad", "value_and_grad",
}

#: module aliases that are never package-internal call targets
_EXTERNAL_ROOTS = {
    "np", "numpy", "jnp", "jax", "lax", "os", "sys", "math", "time",
    "json", "logging", "re", "ast", "threading", "functools",
    "itertools", "collections", "heapq", "bisect",
}


class _FuncInfo:
    __slots__ = ("module", "qualname", "name", "node", "calls", "entry")

    def __init__(self, module: str, qualname: str, node):
        self.module = module
        self.qualname = qualname
        self.name = qualname.rsplit(".", 1)[-1]
        self.node = node
        # (kind, name) call targets: kind 'local' (bare / self.) or
        # ('module', alias) for alias.attr(...) calls
        self.calls: List[Tuple[str, str]] = []
        self.entry = False


class CallGraph:
    """Jit-reachability oracle: ``is_traced(module, qualname)``."""

    def __init__(self):
        self.funcs: Dict[Tuple[str, str], _FuncInfo] = {}
        self._by_module_name: Dict[Tuple[str, str], List[_FuncInfo]] = {}
        # per module: local name -> (source module, original name) for
        # from-imports (the original name, so `from x import f as g`
        # resolves g back to x.f), and alias -> module path for module
        # imports — `import paddle_tpu.x as m` AND the module form of a
        # from-import, `from paddle_tpu import helpers as h` (both make
        # `alias.f(...)` calls resolvable)
        self.from_imports: Dict[str, Dict[str, Tuple[str, str]]] = {}
        self.module_imports: Dict[str, Dict[str, str]] = {}
        self._traced: Set[Tuple[str, str]] = set()

    def add(self, info: _FuncInfo):
        self.funcs[(info.module, info.qualname)] = info
        self._by_module_name.setdefault(
            (info.module, info.name), []).append(info)

    def _resolve(self, module: str, name: str) -> List[_FuncInfo]:
        hits = self._by_module_name.get((module, name))
        if hits:
            return hits
        src = self.from_imports.get(module, {}).get(name)
        if src is not None:
            src_module, orig = src
            return self._by_module_name.get((src_module, orig), [])
        return []

    def finalize(self):
        """BFS the traced set from the entry functions."""
        work = [f for f in self.funcs.values() if f.entry]
        self._traced = {(f.module, f.qualname) for f in work}
        while work:
            f = work.pop()
            for kind, name in f.calls:
                if kind == "local":
                    targets = self._resolve(f.module, name)
                else:
                    mod = self.module_imports.get(f.module, {}).get(kind)
                    targets = (self._by_module_name.get((mod, name), [])
                               if mod is not None else [])
                for t in targets:
                    key = (t.module, t.qualname)
                    if key not in self._traced:
                        self._traced.add(key)
                        work.append(t)

    def is_traced(self, module: str, qualname: str) -> bool:
        return (module, qualname) in self._traced

    def traced_functions(self) -> Set[Tuple[str, str]]:
        return set(self._traced)


def _call_root(node) -> Optional[Tuple[str, str]]:
    """('local', name) for f(...) / self.f(...), (alias, attr) for
    alias.f(...); None for anything deeper (a.b.c(...))."""
    fn = node.func
    if isinstance(fn, ast.Name):
        return ("local", fn.id)
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
        base = fn.value.id
        if base in ("self", "cls"):
            return ("local", fn.attr)
        return (base, fn.attr)
    return None


def _is_tracing_wrapper(fn) -> bool:
    """Does this callee trace its function arguments (jax.jit, pjit,
    lax.scan, functools.partial(jax.jit, ...))?"""
    if isinstance(fn, ast.Name):
        return fn.id in _TRACING_WRAPPERS
    if isinstance(fn, ast.Attribute):
        return fn.attr in _TRACING_WRAPPERS
    if isinstance(fn, ast.Call):        # partial(jax.jit, ...)
        return any(_is_tracing_wrapper(a) for a in fn.args) \
            or _is_tracing_wrapper(fn.func)
    return False


def _is_partial(fn) -> bool:
    """functools.partial / partial — the wrapper the pipeline and
    context-parallel code curry shard_map bodies through."""
    if isinstance(fn, ast.Name):
        return fn.id == "partial"
    return isinstance(fn, ast.Attribute) and fn.attr == "partial"


class _ModuleVisitor(ast.NodeVisitor):
    def __init__(self, graph: CallGraph, module: str,
                 pending_entries: List[Tuple[str, str]]):
        self.graph = graph
        self.module = module
        self.stack: List[str] = []          # qualname parts
        self.func_stack: List[_FuncInfo] = []
        # (module, name) entry marks, resolved AFTER every module's
        # defs exist — a jax.jit(f) in module A may name a function
        # module A imports from module B
        self._pending = pending_entries
        graph.from_imports.setdefault(module, {})
        graph.module_imports.setdefault(module, {})

    # -------------------------------------------------------- imports
    def visit_ImportFrom(self, node):
        if node.module and node.level == 0:
            for a in node.names:
                local = a.asname or a.name
                self.graph.from_imports[self.module][local] = (
                    node.module, a.name)
                # the imported name may itself be a MODULE (`from
                # paddle_tpu.ops import rope as rope_ops`): also record
                # the candidate submodule path so `local.f(...)` calls
                # resolve — a wrong guess just resolves to no defs
                self.graph.module_imports[self.module][local] = (
                    f"{node.module}.{a.name}")
        self.generic_visit(node)

    def visit_Import(self, node):
        for a in node.names:
            alias = a.asname or a.name.split(".")[0]
            if alias not in _EXTERNAL_ROOTS:
                self.graph.module_imports[self.module][alias] = a.name
        self.generic_visit(node)

    # ------------------------------------------------------------ defs
    def _visit_func(self, node):
        qual = ".".join(self.stack + [node.name])
        info = _FuncInfo(self.module, qual, node)
        for dec in node.decorator_list:
            if _is_tracing_wrapper(dec) or (
                    isinstance(dec, ast.Call)
                    and _is_tracing_wrapper(dec.func)):
                info.entry = True
        self.graph.add(info)
        self.stack.append(node.name)
        self.func_stack.append(info)
        self.generic_visit(node)
        self.func_stack.pop()
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def visit_Lambda(self, node):
        # lambdas passed to jit are handled at the Call site (their
        # body's calls attribute to the enclosing function, which is
        # correct: if the enclosing function builds a jitted lambda,
        # the names the lambda calls run traced)
        self.generic_visit(node)

    # ----------------------------------------------------------- calls
    def visit_Call(self, node):
        if self.func_stack:
            root = _call_root(node)
            if root is not None:
                kind, name = root
                if kind == "local" or kind not in _EXTERNAL_ROOTS:
                    self.func_stack[-1].calls.append((kind, name))
        if _is_tracing_wrapper(node.func):
            # jax.jit(f) / lax.scan(step, ...): every function-valued
            # argument becomes a trace entry
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, ast.Call) and _is_partial(a.func) \
                        and a.args:
                    # peel partial(f, ...): the curried callable is the
                    # traced operand (shard_map(partial(local, ...)))
                    a = a.args[0]
                if isinstance(a, ast.Name):
                    self._mark_entry(a.id)
                elif isinstance(a, ast.Attribute) \
                        and isinstance(a.value, ast.Name) \
                        and a.value.id in ("self", "cls"):
                    self._mark_entry(a.attr)
                elif isinstance(a, ast.Lambda) and self.func_stack:
                    # treat the enclosing function's recorded calls as
                    # potentially-traced: mark targets the lambda body
                    # names directly
                    for sub in ast.walk(a):
                        if isinstance(sub, ast.Call):
                            r = _call_root(sub)
                            if r is not None and r[0] == "local":
                                self._mark_entry(r[1])
        self.generic_visit(node)

    def _mark_entry(self, name: str):
        self._pending.append((self.module, name))


def build_callgraph(files: Dict[str, ast.Module]) -> CallGraph:
    """``files`` maps repo-relative module paths to parsed ASTs."""
    graph = CallGraph()
    pending: List[Tuple[str, str]] = []
    for path, tree in files.items():
        module = os.path.splitext(path)[0].replace(os.sep, ".")
        if module.endswith(".__init__"):
            module = module[: -len(".__init__")]
        _ModuleVisitor(graph, module, pending).visit(tree)
    # entries recorded by (module, name) resolve only after every
    # module's defs exist
    for module, name in pending:
        for t in graph._resolve(module, name):
            t.entry = True
    graph.finalize()
    return graph
