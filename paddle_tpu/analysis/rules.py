"""The tpu-lint rule set — repo-specific hot-path invariants as checks.

Every rule yields :class:`Finding`s; the driver (analysis/lint.py)
applies inline suppressions (``# tpu-lint: allow(<rule>)``) and the
checked-in baseline on top, so a rule is free to be *conservative*
(flag everything that is shaped like a violation) and let intentional
sites be annotated where they live.

Rule catalog (docs/ANALYSIS.md has the workflow):

``host-sync``
    Implicit host synchronization: ``.item()``, ``np.asarray`` /
    ``np.array`` / ``np.ascontiguousarray`` on non-literal arguments
    (a device array operand forces a D2H pull), ``jax.device_get``,
    ``block_until_ready``, and — inside jit-reachable functions only —
    ``float()/int()/bool()`` on array-shaped values (a concretization
    sync under trace). One stray site on the decode hot path regresses
    dispatch latency silently; every intentional site must say why.

``traced-branch``
    Python ``if``/``while``/``assert``/ternary on a value produced by
    a ``jnp``/``lax`` computation inside a function reachable from a
    ``jax.jit``/``pjit`` entry point (analysis/callgraph.py) — under
    trace this is a ConcretizationError at best, a silent
    recompile-per-value at worst. Static extractions (``.shape``,
    ``.ndim``, ``.dtype``, ``len()``, ``is None``) are exempt.

``default-dtype``
    Kernel files (``ops/``, ``inference/``, ``serving/``): numpy array
    creation with the implicit float64/int64 default dtype, and any
    explicit ``float64`` — a float64 operand silently doubles memory
    traffic and detunes TPU-shaped kernels.

``metric-drift``
    Every ``counter/gauge/histogram/sketch("serving.|resilience.|
    decode.*")`` literal in the package must appear in
    docs/OBSERVABILITY.md (the PR 7 drift grep, promoted to a rule —
    tests/test_slo.py delegates here).

``span-drift``
    Every ``serving.``/``decode.`` span-name literal
    (``tracer.span(...)`` / ``tr.record(...)``, the engine's
    ``self._phase(...)`` and a bare ``TraceAnnotation(...)``) must
    appear in docs/OBSERVABILITY.md's span table — metric-drift's twin
    for the tracing plane, so neither timeline output nor a profile
    carries spans a reader cannot look up.

``fault-site``
    ``maybe_fire(...)`` / ``Fault(...)`` site literals must be
    registered in ``resilience.faults.KNOWN_SITES`` — an unregistered
    site is a hook the fault-injection docs and chaos tooling cannot
    see.

``snapshot-coverage``
    The state-protocol audit (docs/SERVING.md §Snapshot contract).
    For every class carrying a snapshot protocol — it defines a save
    method (``snapshot``/``to_config``, or journal-emitting methods)
    AND a load method (``restore``/``recover``) — every MUTABLE
    ``self._x`` assigned in ``__init__`` (mutable = reassigned or
    mutated by another method) must be referenced by both the save and
    load sides, or carry ``# tpu-lint: volatile(reason)``. Asymmetric
    coverage (saved but never restored, or vice versa) is its own
    finding. Owned state classes (``_Slot``) are checked against their
    owner's protocol. "New engine field added, snapshot() silently
    loses it" becomes a lint failure, not a chaos-soak surprise.

``journal-coverage``
    Every terminal request transition in ``serving/`` — a
    ``RequestResult(...)`` construction, a ``results[...]`` store, a
    tick transition-marker append — must live in a function that emits
    a journal event or carries an annotation; every
    ``journal.append("<kind>")`` literal must be registered in
    ``serving.journal.KNOWN_EVENTS``, and every registered kind must
    be emitted somewhere (stale-registry detection). The fault-site
    rule's design, applied to the durability log.

``rng-stream``
    In ``serving/``/``inference/`` (request-serving code), every
    ``jax.random.*`` draw must be keyed by a ``fold_in`` of a request
    stream — locally, via a fold-returning helper, or via a parameter
    whose in-package call sites all pass folded keys (callgraph-
    resolved, with violating CALL SITES flagged). Raw ``PRNGKey`` /
    ``split`` references are findings: an ad-hoc stream in serving
    code silently breaks the batch-composition-invariant sampling
    contract (tests/test_serving.py's parity pins).

``collective-axis``
    Every named-axis collective (``lax.psum``/``pmean``/``pmax``/
    ``pmin``/``ppermute``/``all_gather``/``psum_scatter``/
    ``all_to_all``/``axis_index``/``axis_size``/``pcast``/
    ``pbroadcast``) whose
    axis-name argument resolves to a string literal (directly, via a
    parameter default, a local assign, or a module constant) must name
    an axis registered in ``parallel.topology.KNOWN_AXES`` — the axis
    set the hybrid mesh can bind and the multichip dryrun validates. A
    typo'd or out-of-registry axis is a lint finding at author time
    instead of an unbound-axis trace error on a v5p mesh. Calls whose
    axis is genuinely dynamic (an un-defaulted parameter) are the
    documented blind spot. ``axis_name=`` keywords on ANY call (the
    ``partial(local, axis_name=...)`` currying sites) are checked too.

``pspec-axis``
    Every ``PartitionSpec`` literal must reference registered axes
    (same registry and same literal resolution as ``collective-axis``);
    where a spec is attached to a statically-known shape
    (``jax.ShapeDtypeStruct((4, 6), ..., sharding=NamedSharding(mesh,
    P("dp", None)))``), each sharded dim must divide by the axis's
    validated degree — the AOT feasibility path fails on indivisible
    dims only at lowering time on the real mesh.

``donation``
    A jitted function whose array argument flows through an RMW chain
    (``x.at[...].set/add``, ``lax.dynamic_update_slice``) into an
    output — directly, through tuple-unpacked aliases, through
    ``lax.scan``/``while_loop``/``fori_loop`` carries, or through
    calls into other package functions (cross-module fixpoint) — must
    donate that argnum, or every dispatch pays a full buffer copy (the
    BENCH_r06 O(prompt²/chunk) carry-copy class). The inverse hazard
    is also flagged: an argument donated at a jit site and then read
    again by the caller after the dispatch is a use-after-free. The
    sanctioned conditional-donation spelling is
    ``inference.carry_donate_argnums(...)`` — the rule reads the
    argnums through it. ``*args``-signature impls whose positions
    can't be mapped are the documented blind spot (the runtime
    ``analysis.runtime.donation_report`` guard covers them).
"""

import ast
import collections
import os
import re
from typing import Dict, Iterator, List, Optional, Set

__all__ = ["Finding", "ALL_RULES", "KERNEL_DIRS", "SNAPSHOT_OWNED",
           "collect_metric_names", "collect_span_names",
           "known_fault_sites",
           "known_journal_events", "known_mesh_axes", "run_rules"]

KERNEL_DIRS = ("paddle_tpu/ops", "paddle_tpu/inference",
               "paddle_tpu/serving")


def child_nodes(node) -> tuple:
    """``ast.iter_child_nodes`` as a tuple kept on the node: every rule
    walks every tree, and listing a node's fields anew each time was two
    fifths of the check's time."""
    try:
        return node._child_nodes
    except AttributeError:
        kids = node._child_nodes = tuple(ast.iter_child_nodes(node))
        return kids


def walk(node) -> list:
    """``ast.walk`` (the same breadth-first order) over ``child_nodes``,
    as a list kept on the node it starts from."""
    try:
        return node._walk
    except AttributeError:
        out, todo = [], collections.deque([node])
        while todo:
            n = todo.popleft()
            todo.extend(child_nodes(n))
            out.append(n)
        node._walk = out
        return out


class _Visitor(ast.NodeVisitor):
    """``ast.NodeVisitor`` whose ``generic_visit`` goes over
    ``child_nodes`` (the same order)."""

    def generic_visit(self, node):
        for child in child_nodes(node):
            self.visit(child)

_NUMPY_CREATORS = {"zeros", "ones", "empty", "full", "arange",
                   "linspace", "eye", "identity"}
_DTYPE_NAMES = {"float32", "float16", "bfloat16", "float64", "int8",
                "int16", "int32", "int64", "uint8", "uint16", "uint32",
                "uint64", "bool_", "complex64", "intp", "float0"}
#: jnp/lax attribute calls that return static METADATA, not traced data
_STATIC_MODULE_CALLS = {"dtype", "issubdtype", "result_type",
                        "promote_types", "iinfo", "finfo", "shape",
                        "ndim", "size"}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize",
                 "weak_type", "sharding", "nbytes"}
_TRACED_ROOTS = {"jnp", "lax"}
_TRACED_JAX_SUBMODULES = {"nn", "random", "numpy", "lax", "scipy"}

_METRIC_CALL = re.compile(
    r'(?:counter|gauge|histogram|sketch)\(\s*'
    r'"((?:serving|resilience|decode)\.[a-z0-9_.]+)"')

# span-name literals — Tracer span/record calls, the serving engine's
# phase helper (``self._phase(...)``) and bare profiler annotations
# (``TraceAnnotation(...)``, ``StepTraceAnnotation(...)``) whose first
# argument is a ``serving.``/``decode.``-prefixed string: the
# span-drift rule pins every one against the span table in
# docs/OBSERVABILITY.md, exactly like _METRIC_CALL pins metric names
_SPAN_CALL = re.compile(
    r'(?:\.record|\.span|record_span|\._phase|TraceAnnotation)\(\s*'
    r'"((?:serving|decode)\.[a-z0-9_.]+)"')


class Finding:
    """One lint violation. ``code`` is the stripped source line — the
    baseline matches on (rule, path, code), so findings survive
    unrelated edits that only shift line numbers."""

    __slots__ = ("rule", "path", "line", "col", "message", "code")

    def __init__(self, rule: str, path: str, line: int, col: int,
                 message: str, code: str = ""):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.message = message
        self.code = code

    def key(self):
        return (self.rule, self.path, self.code)

    def sort_key(self):
        return (self.path, self.line, self.col, self.rule)

    def to_json(self) -> Dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "code": self.code}

    def __repr__(self):
        return (f"{self.path}:{self.line}:{self.col}: [{self.rule}] "
                f"{self.message}")


class SourceFile:
    __slots__ = ("path", "source", "lines", "tree")

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: str, node, message: str) -> Finding:
        return Finding(rule, self.path, node.lineno, node.col_offset,
                       message, self.line_text(node.lineno))


# --------------------------------------------------------------- helpers

def _numpy_aliases(tree: ast.Module) -> Set[str]:
    names = set()
    for node in walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    names.add(a.asname or "numpy")
        elif isinstance(node, ast.ImportFrom) and node.module == "numpy":
            names.add("__from_numpy__")
    return names


def _attr_root(node) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_host_literal(node) -> bool:
    """Arguments that are host data by construction: literals,
    comprehensions, and pure-numpy expressions."""
    if isinstance(node, (ast.Constant, ast.List, ast.Tuple, ast.Dict,
                         ast.ListComp, ast.GeneratorExp, ast.DictComp,
                         ast.SetComp)):
        return True
    if isinstance(node, ast.UnaryOp):
        return _is_host_literal(node.operand)
    if isinstance(node, ast.BinOp):
        return _is_host_literal(node.left) and _is_host_literal(node.right)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        # list(...)/sorted(...) results are host objects by construction
        return node.func.id in ("list", "tuple", "sorted", "range")
    return False


def _looks_like_dtype(node) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr in _DTYPE_NAMES or node.attr == "dtype"
    if isinstance(node, ast.Name):
        return node.id in _DTYPE_NAMES or "dtype" in node.id.lower()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value in _DTYPE_NAMES
    if isinstance(node, ast.Call):
        # np.dtype(...), jnp.dtype(...), x.astype's operand etc.
        return (isinstance(node.func, ast.Attribute)
                and node.func.attr == "dtype")
    return False


def _static_extraction(node) -> bool:
    """Expressions whose VALUE is static under trace even when the
    operand is traced: shape/dtype attributes, len(), isinstance(),
    identity comparisons."""
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return _static_extraction(node.value)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("len", "isinstance", "hasattr", "getattr",
                                "type")
    return False


def _tainted(node, traced: Set[str]) -> bool:
    """Does this expression's value depend on traced array DATA (as
    opposed to static metadata)?"""
    if node is None or isinstance(node, ast.Constant):
        return False
    if _static_extraction(node):
        return False
    if isinstance(node, ast.Name):
        return node.id in traced
    if isinstance(node, ast.Attribute):
        return _tainted(node.value, traced)
    if isinstance(node, ast.Subscript):
        return _tainted(node.value, traced)
    if isinstance(node, ast.Call):
        root = _attr_root(node.func)
        if root in _TRACED_ROOTS:
            return (not isinstance(node.func, ast.Attribute)
                    or node.func.attr not in _STATIC_MODULE_CALLS)
        if root == "jax" and isinstance(node.func, ast.Attribute):
            # jax.nn.softmax(x) / jax.random.fold_in(...) return traced
            # data; jax.default_backend() and friends do not
            chain = _jax_chain(node.func)
            if len(chain) >= 2 and chain[1] in _TRACED_JAX_SUBMODULES:
                return True
        args = list(node.args) + [kw.value for kw in node.keywords]
        if isinstance(node.func, ast.Attribute) \
                and _tainted(node.func.value, traced):
            return True         # x.astype(...), x.sum() on tainted x
        return any(_tainted(a, traced) for a in args)
    if isinstance(node, ast.BinOp):
        return _tainted(node.left, traced) or _tainted(node.right, traced)
    if isinstance(node, ast.UnaryOp):
        return _tainted(node.operand, traced)
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
               for op in node.ops):
            return False        # identity / membership: host semantics
        return _tainted(node.left, traced) \
            or any(_tainted(c, traced) for c in node.comparators)
    if isinstance(node, ast.BoolOp):
        return any(_tainted(v, traced) for v in node.values)
    if isinstance(node, ast.IfExp):
        return _tainted(node.body, traced) or _tainted(node.orelse, traced)
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_tainted(e, traced) for e in node.elts)
    return False


def _jax_chain(node) -> List[str]:
    chain = []
    while isinstance(node, ast.Attribute):
        chain.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        chain.append(node.id)
    return list(reversed(chain))


class _FuncScoper(_Visitor):
    """Shared walk that attributes nodes to their enclosing function's
    qualname (matching analysis/callgraph.py) before dispatching to a
    per-rule ``handle(node, qualname)``."""

    def __init__(self):
        self.stack: List[str] = []

    def _visit_func(self, node):
        self.stack.append(node.name)
        self.enter_function(node, ".".join(self.stack))
        self.generic_visit(node)
        self.exit_function(node)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def enter_function(self, node, qualname):   # pragma: no cover
        pass

    def exit_function(self, node):              # pragma: no cover
        pass


# ----------------------------------------------------------- host-sync

class _HostSyncVisitor(_FuncScoper):
    def __init__(self, sf: SourceFile, np_aliases: Set[str],
                 is_traced_fn, findings: List[Finding]):
        super().__init__()
        self.sf = sf
        self.np = np_aliases
        self.is_traced_fn = is_traced_fn
        self.findings = findings

    def visit_Call(self, node):
        f = node.func
        sf = self.sf
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not node.args:
                self.findings.append(sf.finding(
                    "host-sync", node,
                    ".item() forces a device sync + D2H scalar pull"))
            elif f.attr == "block_until_ready":
                self.findings.append(sf.finding(
                    "host-sync", node,
                    "block_until_ready blocks the host on device work"))
            elif f.attr == "device_get" and _attr_root(f) == "jax":
                self.findings.append(sf.finding(
                    "host-sync", node,
                    "jax.device_get is an explicit D2H transfer"))
            elif (f.attr in ("asarray", "array", "ascontiguousarray")
                  and isinstance(f.value, ast.Name)
                  and f.value.id in self.np and node.args
                  and not _is_host_literal(node.args[0])
                  and not self._numpy_arg(node.args[0])):
                self.findings.append(sf.finding(
                    "host-sync", node,
                    f"np.{f.attr} on a possibly-device value syncs and "
                    f"copies to host"))
        elif isinstance(f, ast.Name):
            if f.id == "block_until_ready":
                self.findings.append(sf.finding(
                    "host-sync", node,
                    "block_until_ready blocks the host on device work"))
            elif f.id in ("float", "int", "bool") and len(node.args) == 1 \
                    and self._in_traced_function() \
                    and self._concretizes(node.args[0]):
                self.findings.append(sf.finding(
                    "host-sync", node,
                    f"{f.id}() on an array value in jit-reachable code "
                    f"is a concretization sync"))
        self.generic_visit(node)

    def _numpy_arg(self, node) -> bool:
        """np.asarray(np.stack(...)) — already host, not a sync."""
        return (isinstance(node, ast.Call)
                and _attr_root(node.func) in self.np)

    def _in_traced_function(self) -> bool:
        return bool(self.stack) and self.is_traced_fn(
            ".".join(self.stack))

    def _concretizes(self, arg) -> bool:
        """float(x)-style casts that force a device value concrete:
        calls and subscripts of non-static expressions. Plain names and
        static metadata (shape/len/...) stay un-flagged — config casts
        are the common benign case."""
        if _static_extraction(arg) or isinstance(arg, (ast.Constant,
                                                       ast.Name,
                                                       ast.Attribute)):
            # plain names and attribute reads are the benign config-cast
            # case; only value-producing expressions (calls, subscripts)
            # can force a device array concrete
            return False
        if isinstance(arg, (ast.Call, ast.Subscript)):
            return not _static_extraction(arg)
        if isinstance(arg, ast.BinOp):
            return self._concretizes(arg.left) \
                or self._concretizes(arg.right)
        if isinstance(arg, ast.UnaryOp):
            return self._concretizes(arg.operand)
        return False


def check_host_sync(sf: SourceFile, graph) -> List[Finding]:
    module = _module_name(sf.path)
    findings: List[Finding] = []
    v = _HostSyncVisitor(
        sf, _numpy_aliases(sf.tree),
        lambda qual: graph.is_traced(module, qual), findings)
    v.visit(sf.tree)
    return findings


# -------------------------------------------------------- traced-branch

class _TracedBranchVisitor(_FuncScoper):
    def __init__(self, sf: SourceFile, is_traced_fn,
                 findings: List[Finding]):
        super().__init__()
        self.sf = sf
        self.is_traced_fn = is_traced_fn
        self.findings = findings
        self.traced_vars: List[Set[str]] = []

    def enter_function(self, node, qualname):
        # locals assigned from jnp/lax computations are traced values;
        # two forward passes so `y = x + 1` after `x = jnp.sum(...)`
        # taints even with one-pass visiting order quirks
        traced: Set[str] = set()
        for _ in range(2):
            for sub in walk(node):
                if isinstance(sub, ast.Assign) and _tainted(sub.value,
                                                            traced):
                    for t in sub.targets:
                        self._taint_target(t, traced)
                elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)) \
                        and sub.value is not None \
                        and _tainted(sub.value, traced):
                    self._taint_target(sub.target, traced)
        self.traced_vars.append(traced)

    def exit_function(self, node):
        self.traced_vars.pop()

    @staticmethod
    def _taint_target(t, traced: Set[str]):
        if isinstance(t, ast.Name):
            traced.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                _TracedBranchVisitor._taint_target(e, traced)

    def _check_test(self, test, what: str):
        if not self.traced_vars or not self.stack:
            return
        if not self.is_traced_fn(".".join(self.stack)):
            return
        if _tainted(test, self.traced_vars[-1]):
            self.findings.append(self.sf.finding(
                "traced-branch", test,
                f"Python {what} on a traced value in jit-reachable "
                f"code — use lax.cond/jnp.where or hoist the check"))

    def visit_If(self, node):
        self._check_test(node.test, "branch")
        self.generic_visit(node)

    def visit_While(self, node):
        self._check_test(node.test, "while-loop")
        self.generic_visit(node)

    def visit_IfExp(self, node):
        self._check_test(node.test, "conditional expression")
        self.generic_visit(node)

    def visit_Assert(self, node):
        self._check_test(node.test, "assert")
        self.generic_visit(node)


def check_traced_branch(sf: SourceFile, graph) -> List[Finding]:
    module = _module_name(sf.path)
    findings: List[Finding] = []
    v = _TracedBranchVisitor(
        sf, lambda qual: graph.is_traced(module, qual), findings)
    v.visit(sf.tree)
    return findings


# -------------------------------------------------------- default-dtype

class _DefaultDtypeVisitor(_Visitor):
    def __init__(self, sf: SourceFile, np_aliases: Set[str],
                 findings: List[Finding]):
        self.sf = sf
        self.np = np_aliases
        self.findings = findings

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in self.np:
            if f.attr in _NUMPY_CREATORS:
                has_dtype = any(kw.arg == "dtype" for kw in node.keywords) \
                    or any(_looks_like_dtype(a) for a in node.args)
                if not has_dtype:
                    self.findings.append(self.sf.finding(
                        "default-dtype", node,
                        f"np.{f.attr} without an explicit dtype defaults "
                        f"to float64/int64 in kernel code"))
                for a in node.args:
                    # a POSITIONAL float64 dtype must not escape just
                    # because it satisfied has_dtype
                    if self._is_float64(a):
                        self.findings.append(self.sf.finding(
                            "default-dtype", a,
                            "explicit float64 dtype in kernel code"))
            elif f.attr == "float64":
                self.findings.append(self.sf.finding(
                    "default-dtype", node,
                    "explicit float64 scalar in kernel code"))
            elif f.attr in ("asarray", "array") and node.args:
                for a in node.args[1:]:     # positional dtype
                    if self._is_float64(a):
                        self.findings.append(self.sf.finding(
                            "default-dtype", a,
                            "explicit float64 dtype in kernel code"))
                if self._bare_float_literal(node.args[0]) \
                        and not any(kw.arg == "dtype"
                                    for kw in node.keywords) \
                        and not any(_looks_like_dtype(a)
                                    for a in node.args[1:]):
                    self.findings.append(self.sf.finding(
                        "default-dtype", node,
                        "bare float literal arrayified at float64"))
        for kw in getattr(node, "keywords", []):
            if kw.arg == "dtype" and self._is_float64(kw.value):
                self.findings.append(self.sf.finding(
                    "default-dtype", kw.value,
                    "explicit float64 dtype in kernel code"))
        self.generic_visit(node)

    @staticmethod
    def _is_float64(node) -> bool:
        if isinstance(node, ast.Attribute):
            return node.attr == "float64"
        if isinstance(node, ast.Constant):
            return node.value in ("float64", "double")
        return False

    @staticmethod
    def _bare_float_literal(node) -> bool:
        """A float scalar, or a list/tuple literal containing one —
        numpy infers float64 for both."""
        if isinstance(node, ast.Constant):
            return isinstance(node.value, float)
        if isinstance(node, (ast.List, ast.Tuple)):
            return any(_DefaultDtypeVisitor._bare_float_literal(e)
                       for e in node.elts)
        return False


def check_default_dtype(sf: SourceFile, graph=None) -> List[Finding]:
    norm = sf.path.replace(os.sep, "/")
    if not any(norm.startswith(d + "/") or os.path.dirname(norm) == d
               for d in KERNEL_DIRS):
        return []
    findings: List[Finding] = []
    _DefaultDtypeVisitor(sf, _numpy_aliases(sf.tree) | {"np"},
                         findings).visit(sf.tree)
    return findings


# --------------------------------------------------------- metric-drift

def collect_metric_names(sources: Dict[str, str]) -> Dict[str, List]:
    """name -> [(path, line)] for every serving./resilience./decode.*
    metric literal created in the package. The ONE implementation both
    the lint rule and tests/test_slo.py use. Scans whole files (the
    ``\\s*`` crosses newlines), so a call wrapped for line length is
    still seen."""
    names: Dict[str, List] = {}
    for path, src in sources.items():
        for m in _METRIC_CALL.finditer(src):
            line = src.count("\n", 0, m.start()) + 1
            names.setdefault(m.group(1), []).append((path, line))
    return names


def check_metric_drift(sources: Dict[str, str], docs_text: str,
                       line_lookup) -> List[Finding]:
    findings = []
    for name, sites in sorted(collect_metric_names(sources).items()):
        if name in docs_text:
            continue
        for path, line in sites:
            findings.append(Finding(
                "metric-drift", path, line, 0,
                f"metric {name!r} is not documented in "
                f"docs/OBSERVABILITY.md", line_lookup(path, line)))
    return findings


# ----------------------------------------------------------- span-drift

def collect_span_names(sources: Dict[str, str]) -> Dict[str, List]:
    """name -> [(path, line)] for every ``serving.``/``decode.`` span
    literal created in the package (``tracer.span("...")`` /
    ``tr.record("...")`` / ``self._phase("...")`` /
    ``TraceAnnotation("...")``). The span twin of
    :func:`collect_metric_names` — whole-file scan, wrapped calls
    included."""
    names: Dict[str, List] = {}
    for path, src in sources.items():
        for m in _SPAN_CALL.finditer(src):
            line = src.count("\n", 0, m.start()) + 1
            names.setdefault(m.group(1), []).append((path, line))
    return names


def check_span_drift(sources: Dict[str, str], docs_text: str,
                     line_lookup) -> List[Finding]:
    """Every span-name literal must appear in docs/OBSERVABILITY.md's
    span table — an undocumented span is timeline output a reader
    cannot interpret, the exact drift metric-drift catches for metric
    names."""
    findings = []
    for name, sites in sorted(collect_span_names(sources).items()):
        if name in docs_text:
            continue
        for path, line in sites:
            findings.append(Finding(
                "span-drift", path, line, 0,
                f"span {name!r} is not documented in "
                f"docs/OBSERVABILITY.md", line_lookup(path, line)))
    return findings


# ----------------------------------------------------------- fault-site

def known_fault_sites(faults_source: str) -> Set[str]:
    """Parse resilience/faults.py for the KNOWN_SITES literal — the
    linter must not import the package (no jax import on the lint
    path)."""
    tree = ast.parse(faults_source)
    for node in walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "KNOWN_SITES":
                    return {e.value for e in node.value.elts
                            if isinstance(e, ast.Constant)}
    return set()


def check_fault_site(sf: SourceFile, sites: Set[str]) -> List[Finding]:
    if sf.path.replace(os.sep, "/").endswith("resilience/faults.py"):
        return []       # the registry itself (defaults, docstrings)
    findings: List[Finding] = []
    for node in walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if name not in ("maybe_fire", "Fault"):
            continue
        site = None
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            site = node.args[0].value
        else:
            for kw in node.keywords:
                if kw.arg == "site" and isinstance(kw.value, ast.Constant):
                    site = kw.value.value
        if site is not None and site not in sites:
            findings.append(sf.finding(
                "fault-site", node,
                f"fault site {site!r} is not registered in "
                f"resilience.faults.KNOWN_SITES"))
    return findings


# ---------------------------------------------------- snapshot-coverage

#: method names that SAVE a class's state / LOAD it back
_SAVE_METHOD_NAMES = ("snapshot", "to_config")
_LOAD_METHOD_NAMES = ("restore", "recover")
#: methods whose self-stores do NOT make a field "mutable runtime
#: state": construction, teardown, and the protocol methods themselves
_MUTABILITY_EXEMPT = {"__init__", "close", "__exit__"}
#: method calls that mutate their receiver in place — self._queue.push,
#: self._open.add, self.prefix_cache.insert are state mutations even
#: though no attribute store appears
_MUTATOR_CALLS = {"append", "appendleft", "add", "insert", "update",
                  "pop", "popleft", "push", "remove", "discard",
                  "clear", "extend", "setdefault", "free"}
#: state classes with no protocol of their own whose fields ride an
#: owner's snapshot/restore (same file): owner class name per state
#: class. The engine serializes _Slot state as resumable requests.
SNAPSHOT_OWNED = {"_Slot": "ServingEngine"}


def _store_target_attr(node, receiver: Optional[str] = "self"):
    """The attribute name a store targets, peeling subscripts:
    ``self.x = / self.x[i] = / self.x[i][:] =`` all mutate ``x``.
    ``receiver=None`` matches any simple-name receiver."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and (receiver is None or node.value.id == receiver):
        return node.attr
    return None


def _mutated_attrs(fn, receiver="self") -> Set[str]:
    """Attribute names this function mutates on ``receiver``: direct /
    subscript / augmented stores plus in-place mutator calls."""
    out: Set[str] = set()
    for sub in walk(fn):
        if isinstance(sub, ast.Assign):
            for t in sub.targets:
                elts = (t.elts if isinstance(t, (ast.Tuple, ast.List))
                        else [t])
                for e in elts:
                    a = _store_target_attr(e, receiver)
                    if a:
                        out.add(a)
        elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
            a = _store_target_attr(sub.target, receiver)
            if a:
                out.add(a)
        elif isinstance(sub, ast.Call) \
                and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr in _MUTATOR_CALLS:
            a = _store_target_attr(sub.func.value, receiver)
            if a:
                out.add(a)
    return out


def _name_refs(fns) -> Set[str]:
    """Every attribute name and string constant referenced in the given
    function bodies — the (deliberately generous) "this side of the
    protocol mentions the field" test. Engine fields are matched by
    attribute reads (``self._seeds_issued``), owned-class fields by the
    serialized dict keys (``rs["tokens"]``)."""
    names: Set[str] = set()
    for fn in fns:
        for sub in walk(fn):
            if isinstance(sub, ast.Attribute):
                names.add(sub.attr)
            elif isinstance(sub, ast.Constant) \
                    and isinstance(sub.value, str):
                names.add(sub.value)
    return names


def _init_fields(init) -> Dict[str, ast.stmt]:
    """attr -> FIRST ``self.x = ...`` statement in ``__init__`` (the
    line findings anchor to and ``volatile(...)`` pragmas annotate)."""
    fields: Dict[str, ast.stmt] = {}
    if init is None:
        return fields
    for sub in walk(init):
        targets = []
        if isinstance(sub, ast.Assign):
            targets = sub.targets
        elif isinstance(sub, (ast.AnnAssign, ast.AugAssign)):
            targets = [sub.target]
        for t in targets:
            if isinstance(t, ast.Attribute) \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id == "self":
                fields.setdefault(t.attr, sub)
    return fields


def _journal_emitters(methods: Dict[str, ast.FunctionDef]) -> List[str]:
    """Methods (other than __init__) containing a journal append — the
    Router's save side IS its journal writes."""
    out = []
    for name, fn in methods.items():
        if name == "__init__":
            continue
        if any(_journal_append_kind(sub) is not _NOT_JOURNAL
               for sub in walk(fn) if isinstance(sub, ast.Call)):
            out.append(name)
    return out


def _class_methods(cls: ast.ClassDef) -> Dict[str, ast.FunctionDef]:
    return {n.name: n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def check_snapshot_coverage(sf: SourceFile) -> List[Finding]:
    findings: List[Finding] = []
    classes = {n.name: n for n in walk(sf.tree)
               if isinstance(n, ast.ClassDef)}
    protocols = {}      # class name -> (save fns, load fns, methods)
    for cname, cls in classes.items():
        methods = _class_methods(cls)
        save = [methods[n] for n in _SAVE_METHOD_NAMES if n in methods]
        save += [methods[n] for n in _journal_emitters(methods)
                 if methods[n] not in save]
        load = [methods[n] for n in _LOAD_METHOD_NAMES if n in methods]
        if "to_config" in methods and "__init__" in methods \
                and not load:
            # the SpecConfig pattern: to_config() round-trips through
            # the constructor (restore does SpecConfig(**cfg))
            load = [methods["__init__"]]
        if save and load:
            protocols[cname] = (save, load, methods)

    def _fmt(fns):
        names = sorted({f.name for f in fns})
        if len(names) > 3:
            return f"{names[0]}, {names[1]} (+{len(names) - 2} more)"
        return ", ".join(names)

    def _audit(fields, mutated, save, load, what, volatile_hint):
        save_refs = _name_refs(save)
        load_refs = _name_refs(load)
        save_names = _fmt(save)
        load_names = _fmt(load)
        for attr in sorted(fields):
            if attr not in mutated:
                continue        # assigned once at construction: config
            node = fields[attr]
            saved = attr in save_refs or attr.lstrip("_") in save_refs
            loaded = attr in load_refs or attr.lstrip("_") in load_refs
            if saved and loaded:
                continue
            if saved:
                findings.append(sf.finding(
                    "snapshot-coverage", node,
                    f"{what}.{attr} is saved by {save_names}() but "
                    f"never restored by {load_names}() — asymmetric "
                    f"snapshot coverage"))
            elif loaded:
                findings.append(sf.finding(
                    "snapshot-coverage", node,
                    f"{what}.{attr} is restored by {load_names}() but "
                    f"never saved by {save_names}() — asymmetric "
                    f"snapshot coverage"))
            else:
                findings.append(sf.finding(
                    "snapshot-coverage", node,
                    f"{what}.{attr} is mutable state not covered by "
                    f"the snapshot protocol: serialize it in "
                    f"{save_names}() + {load_names}(), or annotate "
                    f"{volatile_hint}"))

    for cname, (save, load, methods) in protocols.items():
        fields = _init_fields(methods.get("__init__"))
        if "__init__" in [f.name for f in load]:
            # to_config-style: a field is loaded iff the constructor
            # takes it back as a parameter
            load_params = set()
            for fn in load:
                a = fn.args
                load_params |= {p.arg for p in (a.posonlyargs + a.args
                                                + a.kwonlyargs)}
            mutated = set()
        else:
            load_params = set()
            exempt = _MUTABILITY_EXEMPT \
                | {f.name for f in save} | {f.name for f in load}
            mutated = set()
            for mname, fn in methods.items():
                if mname not in exempt:
                    mutated |= _mutated_attrs(fn)
        if load_params:
            # to_config classes: flag fields that don't round-trip
            save_refs = _name_refs(save)
            for attr in sorted(fields):
                if attr in load_params:
                    continue
                if attr in save_refs:
                    continue    # serialized but constructor-external
                findings.append(sf.finding(
                    "snapshot-coverage", fields[attr],
                    f"{cname}.{attr} does not round-trip through "
                    f"to_config() -> __init__(**cfg)"))
            continue
        _audit(fields, mutated, save, load, cname,
               "`# tpu-lint: volatile(reason)`")

    # owned state classes ride their owner's protocol: their fields
    # must appear in the owner's save AND load bodies (serialized dict
    # keys count), or be annotated volatile at their __init__ line
    for owned_name, owner_name in sorted(SNAPSHOT_OWNED.items()):
        if owned_name not in classes or owner_name not in protocols:
            continue
        save, load, owner_methods = protocols[owner_name]
        owned_methods = _class_methods(classes[owned_name])
        fields = _init_fields(owned_methods.get("__init__"))
        exempt = _MUTABILITY_EXEMPT \
            | {f.name for f in save} | {f.name for f in load}
        mutated = set()
        for mname, fn in owner_methods.items():
            if mname not in exempt:
                # stores on any receiver: the owner mutates slot
                # objects through locals (s.pos = ..., s.tokens.append)
                mutated |= _mutated_attrs(fn, receiver=None)
        _audit(fields, mutated, save, load, owned_name,
               "`# tpu-lint: volatile(reason)`")
    return findings


# ----------------------------------------------------- journal-coverage

_JOURNAL_SCOPE = "paddle_tpu/serving/"
_JOURNAL_REGISTRY_PATH = "paddle_tpu/serving/journal.py"
#: the engine's per-tick transition markers: an append to one IS a
#: request-state transition site (preempt/resume/retire/shed/finish)
_TRANSITION_MARKERS = {"_tick_preempted", "_tick_resumed",
                       "_tick_retired", "_tick_shed", "_finished_tick",
                       "_pending_finished"}
_NOT_JOURNAL = object()


def _journal_append_kind(call: ast.Call):
    """For ``<...journal...>.append(kind, ...)`` calls: the kind (a str
    literal, or None for a non-literal kind). ``_NOT_JOURNAL`` for any
    other call. The receiver chain must mention "journal" so list
    appends and the tick markers never match."""
    f = call.func
    if not isinstance(f, ast.Attribute) or f.attr != "append":
        return _NOT_JOURNAL
    node, mentions = f.value, False
    while isinstance(node, ast.Attribute):
        mentions = mentions or "journal" in node.attr
        node = node.value
    if isinstance(node, ast.Name):
        mentions = mentions or "journal" in node.id
    if not mentions:
        return _NOT_JOURNAL
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


def known_journal_events(journal_source: str) -> Set[str]:
    """Parse serving/journal.py for the KNOWN_EVENTS literal (dict or
    tuple) without importing it — no jax on the lint path."""
    tree = ast.parse(journal_source)
    for node in walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "KNOWN_EVENTS":
                    v = node.value
                    if isinstance(v, ast.Dict):
                        return {k.value for k in v.keys
                                if isinstance(k, ast.Constant)}
                    if isinstance(v, (ast.Tuple, ast.List, ast.Set)):
                        return {e.value for e in v.elts
                                if isinstance(e, ast.Constant)}
    return set()


class _JournalVisitor(_FuncScoper):
    def __init__(self, sf: SourceFile, events: Set[str],
                 findings: List[Finding], emitted: Set[str]):
        super().__init__()
        self.sf = sf
        self.events = events
        self.findings = findings
        self.emitted = emitted
        # per function-frame: (anchor nodes, emits journal?)
        self.frames: List = [[[], False]]

    def enter_function(self, node, qualname):
        self.frames.append([[], False])

    def exit_function(self, node):
        anchors, emits = self.frames.pop()
        if emits or not anchors:
            return
        # ONE finding per transition function, anchored at its first
        # transition statement — the site is the function, and one
        # annotation should classify it
        self.findings.append(self.sf.finding(
            "journal-coverage", anchors[0],
            f"terminal request transition in "
            f"{'.'.join(self.stack) or '<module>'} emits no "
            f"journal event — journal it (a KNOWN_EVENTS kind) or "
            f"annotate why the protocol covers it elsewhere"))

    def _anchor(self, node):
        self.frames[-1][0].append(node)

    def visit_Call(self, node):
        kind = _journal_append_kind(node)
        if kind is not _NOT_JOURNAL:
            self.frames[-1][1] = True
            if kind is None:
                self.findings.append(self.sf.finding(
                    "journal-coverage", node,
                    "journal event kind must be a string literal so "
                    "the registry pin can see it"))
            else:
                self.emitted.add(kind)
                if kind not in self.events:
                    self.findings.append(self.sf.finding(
                        "journal-coverage", node,
                        f"journal event {kind!r} is not registered in "
                        f"serving.journal.KNOWN_EVENTS"))
        else:
            f = node.func
            name = (f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute)
                    else None)
            if name == "RequestResult":
                self._anchor(node)
            elif isinstance(f, ast.Attribute) \
                    and f.attr == "append" \
                    and _store_target_attr(f.value, None) \
                    in _TRANSITION_MARKERS:
                self._anchor(node)
        self.generic_visit(node)

    def _check_store(self, target):
        if isinstance(target, ast.Subscript) \
                and _store_target_attr(target, None) == "results":
            self._anchor(target)

    def visit_Assign(self, node):
        for t in node.targets:
            self._check_store(t)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_store(node.target)
        self.generic_visit(node)

    def exit_module(self):
        anchors, emits = self.frames[0]
        if anchors and not emits:
            self.findings.append(self.sf.finding(
                "journal-coverage", anchors[0],
                "terminal request transition at module level emits "
                "no journal event"))


def check_journal_coverage(files: Dict[str, "SourceFile"]
                           ) -> List[Finding]:
    reg_sf = files.get(_JOURNAL_REGISTRY_PATH)
    events = (known_journal_events(reg_sf.source)
              if reg_sf is not None else set())
    findings: List[Finding] = []
    emitted: Set[str] = set()
    for path, sf in files.items():
        if not path.startswith(_JOURNAL_SCOPE) \
                or path == _JOURNAL_REGISTRY_PATH:
            continue
        v = _JournalVisitor(sf, events, findings, emitted)
        v.visit(sf.tree)
        v.exit_module()
    if reg_sf is not None:
        for kind in sorted(events - emitted):
            # anchor at the KNOWN_EVENTS entry so the finding names the
            # rotting registry line
            line = next((i for i, text in enumerate(reg_sf.lines, 1)
                         if f'"{kind}"' in text), 1)
            findings.append(Finding(
                "journal-coverage", reg_sf.path, line, 0,
                f"KNOWN_EVENTS kind {kind!r} is registered but never "
                f"emitted anywhere in serving/ — stale registry entry",
                reg_sf.line_text(line)))
    return findings


# ---------------------------------------------------------- rng-stream

_RNG_SCOPE = ("paddle_tpu/serving/", "paddle_tpu/inference/")
#: jax.random samplers whose first argument is a PRNG key
_RANDOM_DRAWS = {"categorical", "uniform", "normal", "gumbel",
                 "bernoulli", "randint", "truncated_normal",
                 "exponential", "choice", "permutation", "laplace",
                 "logistic", "beta", "gamma", "poisson", "rademacher",
                 "dirichlet", "shuffle"}
#: raw stream constructors: creating/forking a stream in serving code
#: is the finding — request code derives keys via fold_in
_RAW_STREAMS = {"PRNGKey", "split", "key"}


def _is_jax_random(node, random_aliases: Set[str]):
    """(kind, name) when ``node`` references jax.random.<name> — via
    the attribute chain or a from-import alias; (None, None) else."""
    if isinstance(node, ast.Attribute):
        base = node.value
        chain = []
        while isinstance(base, ast.Attribute):
            chain.append(base.attr)
            base = base.value
        if isinstance(base, ast.Name):
            chain.append(base.id)
        if "random" in chain:
            return ("attr", node.attr)
    if isinstance(node, ast.Name) and node.id in random_aliases:
        return ("name", node.id)
    return (None, None)


def _random_from_imports(tree: ast.Module) -> Set[str]:
    """Local names bound by ``from jax.random import X [as y]``."""
    out: Set[str] = set()
    for node in walk(tree):
        if isinstance(node, ast.ImportFrom) \
                and node.module == "jax.random":
            for a in node.names:
                out.add(a.asname or a.name)
    return out


class _RngFuncInfo:
    """One function's rng-relevant facts, kept for the cross-function
    call-site pass."""

    __slots__ = ("sf", "qualname", "params", "folded", "param_draws",
                 "calls")

    def __init__(self, sf, qualname, params):
        self.sf = sf
        self.qualname = qualname
        self.params = params            # name -> position
        self.folded: Set[str] = set()   # locals carrying folded keys
        self.param_draws: List = []     # (param_name, draw node)
        self.calls: List = []           # (callee name, call node)


def _expr_is_folded(node, folded_vars: Set[str],
                    folding_fns: Set[str]) -> bool:
    """Does this expression derive from a fold_in? True when any node
    within it references ``fold_in`` (jax.random.fold_in, vmapped or
    not), calls a known fold-returning helper, or reads a local already
    carrying a folded key."""
    for sub in walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "fold_in":
            return True
        if isinstance(sub, ast.Name) and (sub.id == "fold_in"
                                          or sub.id in folded_vars):
            return True
        if isinstance(sub, ast.Call):
            f = sub.func
            callee = (f.id if isinstance(f, ast.Name)
                      else f.attr if isinstance(f, ast.Attribute)
                      else None)
            if callee in folding_fns:
                return True
    return False


def _fn_params(args: ast.arguments) -> Dict[str, int]:
    params = {}
    for i, p in enumerate(args.posonlyargs + args.args):
        params[p.arg] = i
    for p in args.kwonlyargs:
        params[p.arg] = -1
    return params


class _RngVisitor(_Visitor):
    def __init__(self, sf: SourceFile, random_aliases: Set[str],
                 folding_fns: Set[str], infos: Dict[str, "_RngFuncInfo"],
                 findings: List[Finding]):
        self.sf = sf
        self.aliases = random_aliases
        self.folding_fns = folding_fns
        self.infos = infos
        self.findings = findings
        self.stack: List[_RngFuncInfo] = []
        self.qual: List[str] = []
        # Lambda node -> positional application args (the
        # ``jax.vmap(lambda k, ...)(key, ...)`` pattern): a draw keyed
        # by a lambda param resolves through the applied argument. The
        # application Call is visited BEFORE the Lambda it contains, so
        # the mapping exists when the lambda frame is pushed.
        self.lambda_apps: Dict[int, List] = {}
        self.lambda_frames: List = []   # (params, applied args or None)

    # ------------------------------------------------------------ defs
    def _visit_func(self, node):
        self.qual.append(node.name)
        info = _RngFuncInfo(self.sf, ".".join(self.qual),
                            _fn_params(node.args))
        # two-pass local taint: locals assigned from folded expressions
        for _ in range(2):
            for sub in walk(node):
                if isinstance(sub, ast.Assign) and _expr_is_folded(
                        sub.value, info.folded, self.folding_fns):
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            info.folded.add(t.id)
                        elif isinstance(t, (ast.Tuple, ast.List)):
                            for e in t.elts:
                                if isinstance(e, ast.Name):
                                    info.folded.add(e.id)
        self.infos.setdefault(node.name, []).append(info)
        self.stack.append(info)
        self.generic_visit(node)
        self.stack.pop()
        self.qual.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node):
        self.qual.append(node.name)
        self.generic_visit(node)
        self.qual.pop()

    def visit_Lambda(self, node):
        self.lambda_frames.append((_fn_params(node.args),
                                   self.lambda_apps.get(id(node))))
        self.generic_visit(node)
        self.lambda_frames.pop()

    # ----------------------------------------------------------- calls
    def visit_Call(self, node):
        # record lambda applications: (vmap-ish(lambda ...))(args) or
        # (lambda ...)(args) — maps lambda params to applied exprs
        if isinstance(node.func, ast.Lambda):
            self.lambda_apps[id(node.func)] = list(node.args)
        elif isinstance(node.func, ast.Call):
            for a in node.func.args:
                if isinstance(a, ast.Lambda):
                    self.lambda_apps[id(a)] = list(node.args)
        kind, name = _is_jax_random(node.func, self.aliases)
        if kind and name in _RANDOM_DRAWS:
            self._check_draw(node)
        elif self.stack:
            f = node.func
            callee = (f.id if isinstance(f, ast.Name)
                      else f.attr if isinstance(f, ast.Attribute)
                      else None)
            if callee is not None:
                self.stack[-1].calls.append((callee, node))
        self.generic_visit(node)

    def visit_Attribute(self, node):
        self._check_raw(node)
        self.generic_visit(node)

    def visit_Name(self, node):
        self._check_raw(node)
        self.generic_visit(node)

    def _check_raw(self, node):
        kind, name = _is_jax_random(node, self.aliases)
        if name in _RAW_STREAMS and isinstance(getattr(
                node, "ctx", None), ast.Load):
            # flag the OUTERMOST reference only (jax.random.PRNGKey is
            # one finding, not one per chain link); Name hits only for
            # from-imports
            if kind == "attr" or (kind == "name"
                                  and name in self.aliases):
                self.findings.append(self.sf.finding(
                    "rng-stream", node,
                    f"raw jax.random.{name} in request-serving code — "
                    f"derive per-request keys via fold_in (or annotate "
                    f"the sanctioned base-key builder)"))

    def _key_expr(self, node: ast.Call):
        if node.args:
            return node.args[0]
        for kw in node.keywords:
            if kw.arg == "key":
                return kw.value
        return None

    def _check_draw(self, node: ast.Call):
        key = self._key_expr(node)
        info = self.stack[-1] if self.stack else None
        folded = info.folded if info else set()
        if key is None or _expr_is_folded(key, folded,
                                          self.folding_fns):
            return
        if isinstance(key, ast.Name):
            # a lambda param resolves through its application site:
            # ``jax.vmap(lambda k, lg: draw(k, lg))(key, logits)``
            # draws from whatever was applied at k's position
            for params, applied in reversed(self.lambda_frames):
                if key.id in params:
                    pos = params[key.id]
                    if applied is None or not 0 <= pos < len(applied):
                        return          # unapplied lambda: blind spot
                    key = applied[pos]
                    break
        if _expr_is_folded(key, folded, self.folding_fns):
            return
        if isinstance(key, ast.Name) and info is not None \
                and key.id in info.params:
            info.param_draws.append((key.id, node))
            return
        self.findings.append(self.sf.finding(
            "rng-stream", node,
            "jax.random draw keyed by a non-fold_in stream — request-"
            "serving draws must fold a request seed (fold_in(key, t))"))


def check_rng_stream(files: Dict[str, "SourceFile"]) -> List[Finding]:
    scope = {p: sf for p, sf in files.items()
             if p.startswith(_RNG_SCOPE)}
    findings: List[Finding] = []
    # fold-returning helpers, by bare name across the scope: a function
    # whose body references fold_in returns folded keys (_fold_rows)
    folding_fns: Set[str] = set()
    for sf in scope.values():
        for node in walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(isinstance(s, ast.Attribute)
                       and s.attr == "fold_in"
                       or isinstance(s, ast.Name) and s.id == "fold_in"
                       for s in walk(node)):
                    folding_fns.add(node.name)
    infos: Dict[str, List[_RngFuncInfo]] = {}
    for sf in scope.values():
        v = _RngVisitor(sf, _random_from_imports(sf.tree), folding_fns,
                        infos, findings)
        v.visit(sf.tree)
    # cross-function pass: a function drawing from its own parameter is
    # fine IFF every in-scope call site passes a folded key (or its own
    # parameter, which propagates the obligation) — flag the call site
    forwarding: Dict[str, Set[int]] = {}    # fn name -> key positions
    for name, fn_infos in infos.items():
        for info in fn_infos:
            for pname, _ in info.param_draws:
                pos = info.params.get(pname, -1)
                if pos >= 0:
                    forwarding.setdefault(name, set()).add(pos)
    changed = True
    flagged: Set[int] = set()
    while changed:
        changed = False
        for fn_infos in infos.values():
            for info in fn_infos:
                for callee, call in info.calls:
                    for pos in forwarding.get(callee, ()):
                        if pos >= len(call.args):
                            continue
                        arg = call.args[pos]
                        if isinstance(arg, ast.Constant) \
                                and arg.value is None:
                            continue    # key=None: greedy, no draw
                        if _expr_is_folded(arg, info.folded,
                                           folding_fns):
                            continue
                        if isinstance(arg, ast.Name) \
                                and arg.id in info.params:
                            p = info.params[arg.id]
                            name = info.qualname.rsplit(".", 1)[-1]
                            if p >= 0 and p not in forwarding.get(
                                    name, set()):
                                forwarding.setdefault(name,
                                                      set()).add(p)
                                changed = True
                            continue
                        if id(call) not in flagged:
                            flagged.add(id(call))
                            findings.append(info.sf.finding(
                                "rng-stream", call,
                                f"passes a non-fold_in key into "
                                f"{callee}(), which draws from it — "
                                f"fold a request seed at this call "
                                f"site"))
    return findings


# ------------------------------------------- mesh-axis literal support

def known_mesh_axes(topology_source: str) -> Dict[str, Optional[int]]:
    """Parse parallel/topology.py for the KNOWN_AXES dict literal —
    axis name -> validated degree (or None) — without importing the
    package (no jax on the lint path)."""
    tree = ast.parse(topology_source)
    for node in walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id == "KNOWN_AXES" \
                    and isinstance(node.value, ast.Dict):
                out: Dict[str, Optional[int]] = {}
                for k, v in zip(node.value.keys, node.value.values):
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        out[k.value] = (v.value if isinstance(
                            v, ast.Constant)
                            and isinstance(v.value, int) else None)
                return out
    return {}


class _AxisScopes:
    """Literal resolution for axis-name expressions: a Name resolves
    through the enclosing function's parameter defaults and local
    string assigns, then module-level string constants. Returns the
    resolved string or None (dynamic — the documented blind spot)."""

    def __init__(self, tree: ast.Module):
        self.module_consts: Dict[str, str] = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.module_consts[t.id] = node.value.value
        self.stack: List[Dict[str, str]] = []

    @staticmethod
    def _fn_scope(node) -> Dict[str, str]:
        scope: Dict[str, str] = {}
        a = node.args
        pos = a.posonlyargs + a.args
        for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
            if isinstance(d, ast.Constant) and isinstance(d.value, str):
                scope[p.arg] = d.value
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if isinstance(d, ast.Constant) and isinstance(d.value, str):
                scope[p.arg] = d.value
        # shallow: a nested function's locals shadow — they are pushed
        # as their own frame when the scoper enters them, and must not
        # leak into (or override) the enclosing scope here. Assigns
        # apply in TEXT order (the shallow walk's stack order is not
        # source order), so `ax = 'tmp'; ax = 'mp'` resolves to 'mp' —
        # the value in effect at any later call site
        assigns = [s for s in _walk_shallow(node)
                   if isinstance(s, ast.Assign)
                   and isinstance(s.value, ast.Constant)
                   and isinstance(s.value.value, str)]
        for s in sorted(assigns, key=lambda s: s.lineno):
            for t in s.targets:
                if isinstance(t, ast.Name):
                    scope[t.id] = s.value.value
        return scope

    def push(self, node):
        self.stack.append(self._fn_scope(node))

    def pop(self):
        self.stack.pop()

    def resolve_name(self, name: str) -> Optional[str]:
        for scope in reversed(self.stack):
            if name in scope:
                return scope[name]
        return self.module_consts.get(name)

    def axis_literals(self, node) -> List[str]:
        """Every axis-name string this expression statically resolves
        to: a constant, a tuple/list of constants, or resolvable
        Names. Dynamic parts resolve to nothing (never a false
        positive from an unresolvable expression)."""
        if isinstance(node, ast.Constant):
            return ([node.value] if isinstance(node.value, str) else [])
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            out: List[str] = []
            for e in node.elts:
                out.extend(self.axis_literals(e))
            return out
        if isinstance(node, ast.Name):
            v = self.resolve_name(node.id)
            return [v] if v is not None else []
        return []


# ------------------------------------------------------ collective-axis

#: named-axis collectives -> positional index of the axis-name operand
#: (pcast/pbroadcast are the vma-cast pair)
_COLLECTIVE_AXIS_POS = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "ppermute": 1,
    "all_gather": 1, "psum_scatter": 1, "all_to_all": 1, "pshuffle": 1,
    "pcast": 1, "pbroadcast": 1, "axis_index": 0, "axis_size": 0,
}
#: keyword spellings of the axis operand on those calls
_COLLECTIVE_AXIS_KW = ("axis_name", "axes")


class _CollectiveAxisVisitor(_FuncScoper):
    def __init__(self, sf: SourceFile, axes: Dict[str, Optional[int]],
                 lax_aliases: Dict[str, str], findings: List[Finding]):
        super().__init__()
        self.sf = sf
        self.axes = axes
        self.lax_aliases = lax_aliases
        self.scopes = _AxisScopes(sf.tree)
        self.findings = findings

    def enter_function(self, node, qualname):
        self.scopes.push(node)

    def exit_function(self, node):
        self.scopes.pop()

    def _collective_name(self, func) -> Optional[str]:
        """The CANONICAL collective name when this callee is one:
        jax.lax.psum / lax.psum / a from-import or module-level alias
        of one (``from jax.lax import psum as ps`` resolves to
        ``psum``)."""
        if isinstance(func, ast.Attribute) \
                and func.attr in _COLLECTIVE_AXIS_POS:
            chain = _jax_chain(func)
            if "lax" in chain[:-1] or chain[0] in ("jax", "collective"):
                return func.attr
        if isinstance(func, ast.Name):
            return self.lax_aliases.get(func.id)
        return None

    def _check_axis_expr(self, node, expr, what: str):
        for axis in self.scopes.axis_literals(expr):
            if axis not in self.axes:
                registered = ", ".join(sorted(self.axes)) or "<none>"
                self.findings.append(self.sf.finding(
                    "collective-axis", node,
                    f"{what} names mesh axis {axis!r}, which is not "
                    f"registered in parallel.topology.KNOWN_AXES "
                    f"({registered}) — a typo'd or out-of-scope axis "
                    f"only fails at trace time on a multichip mesh"))

    def visit_Call(self, node):
        name = self._collective_name(node.func)
        if name is not None:
            pos = _COLLECTIVE_AXIS_POS[name]
            expr = node.args[pos] if pos < len(node.args) else None
            if expr is None:
                for kw in node.keywords:
                    if kw.arg in _COLLECTIVE_AXIS_KW:
                        expr = kw.value
                        break
            if expr is not None:
                self._check_axis_expr(node, expr, f"lax.{name}")
        else:
            # currying sites: axis_name= on any call (partial(local,
            # axis_name=ax), shard_map(..., axis_names={...}))
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis_names"):
                    self._check_axis_expr(node, kw.value,
                                          f"{kw.arg}= keyword")
        self.generic_visit(node)


def _lax_collective_aliases(tree: ast.Module) -> Dict[str, str]:
    """Local name -> CANONICAL collective name for ``from jax.lax
    import psum [as ps]`` bindings and module-level ``psum =
    jax.lax.psum`` re-exports (parallel/collective.py's in-jit
    primitives)."""
    out: Dict[str, str] = {}
    for node in walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax.lax":
            for a in node.names:
                if a.name in _COLLECTIVE_AXIS_POS:
                    out[a.asname or a.name] = a.name
        elif isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Attribute) \
                and node.value.attr in _COLLECTIVE_AXIS_POS \
                and "lax" in _jax_chain(node.value)[:-1]:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = node.value.attr
    return out


def check_collective_axis(sf: SourceFile,
                          axes: Dict[str, Optional[int]]
                          ) -> List[Finding]:
    findings: List[Finding] = []
    _CollectiveAxisVisitor(sf, axes, _lax_collective_aliases(sf.tree),
                           findings).visit(sf.tree)
    return findings


# ---------------------------------------------------------- pspec-axis

def _pspec_aliases(tree: ast.Module) -> Set[str]:
    out = {"PartitionSpec"}
    for node in walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name == "PartitionSpec":
                    out.add(a.asname or a.name)
    return out


class _PspecVisitor(_FuncScoper):
    def __init__(self, sf: SourceFile, axes: Dict[str, Optional[int]],
                 p_names: Set[str], findings: List[Finding]):
        super().__init__()
        self.sf = sf
        self.axes = axes
        self.p_names = p_names
        self.scopes = _AxisScopes(sf.tree)
        self.findings = findings

    def enter_function(self, node, qualname):
        self.scopes.push(node)

    def exit_function(self, node):
        self.scopes.pop()

    def _is_pspec(self, func) -> bool:
        if isinstance(func, ast.Name):
            return func.id in self.p_names
        return isinstance(func, ast.Attribute) \
            and func.attr == "PartitionSpec"

    def _dim_axes(self, expr) -> List[str]:
        return self.scopes.axis_literals(expr)

    def visit_Call(self, node):
        if self._is_pspec(node.func):
            for arg in node.args:
                for axis in self._dim_axes(arg):
                    if axis not in self.axes:
                        registered = ", ".join(sorted(self.axes)) \
                            or "<none>"
                        self.findings.append(self.sf.finding(
                            "pspec-axis", node,
                            f"PartitionSpec references mesh axis "
                            f"{axis!r}, which is not registered in "
                            f"parallel.topology.KNOWN_AXES "
                            f"({registered})"))
        else:
            self._check_divisibility(node)
        self.generic_visit(node)

    def _check_divisibility(self, node):
        """jax.ShapeDtypeStruct((4, 6), ..., sharding=NamedSharding(
        mesh, P("dp", None))) — the statically-knowable case: each
        sharded dim must divide by the axis's validated degree."""
        f = node.func
        name = (f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None)
        if name != "ShapeDtypeStruct" or not node.args:
            return
        shape = node.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)):
            return
        dims = [e.value if isinstance(e, ast.Constant)
                and isinstance(e.value, int) else None
                for e in shape.elts]
        spec = None
        for kw in node.keywords:
            if kw.arg == "sharding":
                for sub in walk(kw.value):
                    if isinstance(sub, ast.Call) \
                            and self._is_pspec(sub.func):
                        spec = sub
                        break
        if spec is None:
            return
        for i, arg in enumerate(spec.args):
            if i >= len(dims) or dims[i] is None:
                continue
            degree = 1
            for axis in self._dim_axes(arg):
                d = self.axes.get(axis)
                degree *= d if d else 1
            if degree > 1 and dims[i] % degree:
                self.findings.append(self.sf.finding(
                    "pspec-axis", spec,
                    f"dim {i} of size {dims[i]} is sharded over axes "
                    f"of validated degree {degree} "
                    f"(parallel.topology.KNOWN_AXES) but is not "
                    f"divisible by it — this spec fails at lowering "
                    f"time on the real mesh"))


def check_pspec_axis(sf: SourceFile, axes: Dict[str, Optional[int]]
                     ) -> List[Finding]:
    findings: List[Finding] = []
    _PspecVisitor(sf, axes, _pspec_aliases(sf.tree),
                  findings).visit(sf.tree)
    return findings


# ------------------------------------------------------------ donation

#: .at[...].<mutator> suffixes — the RMW half of the donation contract
_AT_MUTATORS = {"set", "add", "subtract", "multiply", "divide", "power",
                "min", "max", "apply"}
_DUS_NAMES = {"dynamic_update_slice", "dynamic_update_slice_in_dim",
              "dynamic_update_index_in_dim"}
#: the sanctioned conditional-donation helper (inference.
#: carry_donate_argnums): the rule reads argnums through a call to any
#: name with this suffix
_DONATION_HELPER_SUFFIX = "donate_argnums"


class _FnEntry:
    """One function with its lexical scope links — the donation rule's
    unit of analysis."""

    __slots__ = ("sf", "module", "qualname", "node", "parent", "locals",
                 "params", "vararg", "nparams", "rmw", "taint", "calls")

    def __init__(self, sf, module, qualname, node, parent):
        self.sf = sf
        self.module = module
        self.qualname = qualname
        self.node = node
        self.parent = parent            # enclosing _FnEntry or None
        self.locals: Dict[str, "_FnEntry"] = {}
        a = node.args
        pos = a.posonlyargs + a.args
        self.params = {p.arg: i for i, p in enumerate(pos)}
        self.nparams = len(pos)
        self.vararg = a.vararg.arg if a.vararg else None
        #: (param position, carry component) pairs RMW'd into an
        #: output; component None = the whole argument, an int = the
        #: i-th element of a tuple-valued argument (so a scan carry
        #: whose POOL component is RMW'd does not taint its token and
        #: position components)
        self.rmw: Set[tuple] = set()
        #: _fn_taint cache — taint depends only on this function's own
        #: params/assigns, never on other entries' facts, so it is
        #: invariant across fixpoint sweeps
        self.taint: Optional[Dict[str, Set[tuple]]] = None
        #: the body's own calls (``_walk_shallow``), listed once for
        #: every fixpoint sweep
        self.calls: Optional[List[ast.Call]] = None

    def rmw_argnums(self) -> Set[int]:
        return {p for p, _ in self.rmw}

    def param_label(self, pos: int) -> str:
        for name, i in self.params.items():
            if i == pos:
                return name
        if self.vararg is not None and pos >= self.nparams:
            return f"*{self.vararg}[{pos - self.nparams}]"
        return f"argnum {pos}"


def _walk_shallow(node):
    """Walk a function body without descending into nested function /
    class definitions (their params shadow; they are entries of their
    own)."""
    stack = list(child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            stack.extend(child_nodes(n))


class _DonationIndex:
    """All functions in the package, with lexical-scope and
    cross-module (from-import / module-alias) name resolution."""

    def __init__(self, files: Dict[str, SourceFile], graph):
        self.entries: List[_FnEntry] = []
        self.by_module: Dict[str, Dict[str, List[_FnEntry]]] = {}
        self.by_node: Dict[int, _FnEntry] = {}
        self.graph = graph
        for path, sf in files.items():
            module = _module_name(path)
            self.by_module.setdefault(module, {})
            self._collect(sf, module, sf.tree, None, [])

    def _collect(self, sf, module, node, parent, qual):
        for child in child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                q = ".".join(qual + [child.name])
                e = _FnEntry(sf, module, q, child, parent)
                self.entries.append(e)
                self.by_node[id(child)] = e
                self.by_module[module].setdefault(child.name,
                                                  []).append(e)
                if parent is not None:
                    parent.locals.setdefault(child.name, e)
                self._collect(sf, module, child, e, qual + [child.name])
            elif isinstance(child, ast.ClassDef):
                self._collect(sf, module, child, parent,
                              qual + [child.name])
            else:
                self._collect(sf, module, child, parent, qual)

    def resolve(self, entry: _FnEntry, func) -> List[_FnEntry]:
        """Callee candidates for a Call's func expression: nearest
        lexical scope, then module, then explicit imports (the same
        name discipline as analysis/callgraph.py)."""
        if isinstance(func, ast.Name):
            name = func.id
            e = entry
            while e is not None:
                if name in e.locals:
                    return [e.locals[name]]
                e = e.parent
            hits = self.by_module.get(entry.module, {}).get(name)
            if hits:
                return hits
            src = self.graph.from_imports.get(entry.module,
                                              {}).get(name)
            if src is not None:
                return self.by_module.get(src[0], {}).get(src[1], [])
            return []
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name):
            base = func.value.id
            if base in ("self", "cls"):
                return self.by_module.get(entry.module,
                                          {}).get(func.attr, [])
            mod = self.graph.module_imports.get(entry.module,
                                                {}).get(base)
            if mod is not None:
                return self.by_module.get(mod, {}).get(func.attr, [])
        return []


def _root_name(node) -> Optional[ast.AST]:
    """Peel subscripts down to the Name a buffer expression roots at
    (``carry[1]`` -> carry); attribute reads are NOT peeled (``x.T``
    is a view of a different object in the taint sense we need)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    return node if isinstance(node, ast.Name) else None


def _taint_positions(node, taint: Dict[str, Set[tuple]],
                     entry: _FnEntry) -> Set[tuple]:
    """(param position, component) pairs this expression's BUFFER may
    alias: a tainted Name, a subscript of one (``carry[1]``), or — for
    the vararg — a constant subscript resolving to ``nparams + i``."""
    if isinstance(node, ast.IfExp):
        return _taint_positions(node.body, taint, entry) \
            | _taint_positions(node.orelse, taint, entry)
    if isinstance(node, ast.Subscript) \
            and isinstance(node.value, ast.Name) \
            and entry.vararg is not None \
            and node.value.id == entry.vararg:
        idx = node.slice
        if isinstance(idx, ast.Constant) and isinstance(idx.value, int):
            return {(entry.nparams + idx.value, None)}
        return set()
    root = _root_name(node)
    if root is not None:
        return taint.get(root.id, set())
    return set()


def _fn_taint(entry: _FnEntry) -> Dict[str, Set[tuple]]:
    """name -> (param position, component) pairs whose buffer the
    local may alias. Buffer-preserving flows only: plain rebinds,
    tuple unpacks and subscripts — ``y = kv + 1`` is a NEW buffer and
    must not taint. A tuple unpack from a whole-argument name tags
    each target with its component index (``tok, kv, keys = carry``:
    kv is component 1 of carry's buffer tree — an RMW on kv must not
    implicate tok)."""
    taint: Dict[str, Set[tuple]] = {n: {(p, None)}
                                    for n, p in entry.params.items()}
    for _ in range(2):
        for sub in _walk_shallow(entry.node):
            if not isinstance(sub, ast.Assign):
                continue
            pairs = _taint_positions(sub.value, taint, entry)
            for t in sub.targets:
                if isinstance(t, ast.Name) and pairs:
                    taint.setdefault(t.id, set()).update(pairs)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    if isinstance(sub.value, (ast.Tuple, ast.List)) \
                            and len(sub.value.elts) == len(t.elts):
                        # element-wise: (a, b) = (x, y)
                        for te, ve in zip(t.elts, sub.value.elts):
                            p = _taint_positions(ve, taint, entry)
                            if isinstance(te, ast.Name) and p:
                                taint.setdefault(te.id,
                                                 set()).update(p)
                    elif pairs:
                        # `tok, kv, keys = carry`: component-tagged
                        for i, te in enumerate(t.elts):
                            if not isinstance(te, ast.Name):
                                continue
                            tagged = {(p, i if c is None else c)
                                      for p, c in pairs}
                            taint.setdefault(te.id,
                                             set()).update(tagged)
    return taint


#: lax control-flow combinators: (callee positional index of the body
#: fn, positional index of the carry operand, carry's param position
#: in the body fn)
_CARRY_COMBINATORS = {"scan": (0, 1, 0), "while_loop": (1, 2, 0),
                      "fori_loop": (2, 3, 1)}


def _peel_partial(func_expr):
    """(callable expr, n leading curried positional args) — peeling
    functools.partial(f, a, b) so body-param indexing shifts. The
    partial predicate is SHARED with the callgraph's entry marking
    (analysis/callgraph.py) so the two passes never disagree on what
    counts as a curried callable."""
    from paddle_tpu.analysis.callgraph import _is_partial
    if isinstance(func_expr, ast.Call) and _is_partial(func_expr.func) \
            and func_expr.args:
        return func_expr.args[0], len(func_expr.args) - 1
    return func_expr, 0


def _rmw_pass(index: _DonationIndex) -> bool:
    """One fixpoint sweep: grow each function's RMW'd-param set from
    direct RMW sites, resolvable callees' facts, and control-flow
    carries. Returns whether anything changed."""
    changed = False
    for entry in index.entries:
        if entry.taint is None:
            entry.taint = _fn_taint(entry)
        taint = entry.taint
        if entry.calls is None:
            entry.calls = [sub for sub in _walk_shallow(entry.node)
                           if isinstance(sub, ast.Call)]
        found: Set[tuple] = set()
        for sub in entry.calls:
            f = sub.func
            # x.at[...].set(...) — receiver buffer is RMW'd
            if isinstance(f, ast.Attribute) and f.attr in _AT_MUTATORS \
                    and isinstance(f.value, ast.Subscript) \
                    and isinstance(f.value.value, ast.Attribute) \
                    and f.value.value.attr == "at":
                found |= _taint_positions(f.value.value.value, taint,
                                          entry)
                continue
            name = (f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute)
                    else None)
            if name in _DUS_NAMES and sub.args:
                found |= _taint_positions(sub.args[0], taint, entry)
                continue
            if name in _CARRY_COMBINATORS and len(sub.args) \
                    > _CARRY_COMBINATORS[name][1]:
                body_i, carry_i, carry_pos = _CARRY_COMBINATORS[name]
                body_expr, offset = _peel_partial(sub.args[body_i])
                init = sub.args[carry_i]
                for cand in index.resolve(entry, body_expr):
                    for pos, comp in cand.rmw:
                        if pos != carry_pos + offset:
                            continue
                        if comp is not None and isinstance(
                                init, (ast.Tuple, ast.List)) \
                                and comp < len(init.elts):
                            # only the RMW'd carry COMPONENT taints
                            found |= _taint_positions(init.elts[comp],
                                                      taint, entry)
                        else:
                            elts = (init.elts if isinstance(
                                init, (ast.Tuple, ast.List))
                                else [init])
                            for e in elts:
                                found |= _taint_positions(e, taint,
                                                          entry)
                continue
            # ordinary call into a function with known RMW facts
            for cand in index.resolve(entry, f):
                if not cand.rmw:
                    continue
                # bound-method calls (self.scatter(pool, i)) consume
                # the callee's param 0 as the receiver: caller arg j
                # binds callee param j+1
                recv = 1 if (isinstance(f, ast.Attribute)
                             and isinstance(f.value, ast.Name)
                             and f.value.id in ("self", "cls")
                             and (cand.params.get("self") == 0
                                  or cand.params.get("cls") == 0)) \
                    else 0
                for pos in cand.rmw_argnums():
                    ai = pos - recv
                    if 0 <= ai < len(sub.args):
                        found |= _taint_positions(sub.args[ai], taint,
                                                  entry)
                rmw_names = {n for n, i in cand.params.items()
                             if i in cand.rmw_argnums()}
                for kw in sub.keywords:
                    if kw.arg in rmw_names:
                        found |= _taint_positions(kw.value, taint,
                                                  entry)
        if not found <= entry.rmw:
            entry.rmw |= found
            changed = True
    return changed


def _donated_argnums(jit_call: ast.Call) -> Optional[Set[int]]:
    """The donated set a jit site declares: a tuple/int literal, an
    ``(...) if cond else ()`` conditional (counted as donated — the
    enabled branch is the contract), or a call to the sanctioned
    ``*_donate_argnums`` helper. NO donate_argnums keyword returns
    ``set()`` (nothing donated — the rule's main flagging case); an
    UNRESOLVABLE expression returns None and the rule skips the site
    rather than guessing. A ``donate_argnames=`` spelling also returns
    None: this rule reasons by position, and a by-name donation must
    not be flagged as undonated."""
    expr = None
    if any(kw.arg == "donate_argnames" for kw in jit_call.keywords):
        return None
    for kw in jit_call.keywords:
        if kw.arg == "donate_argnums":
            expr = kw.value
            break
    if expr is None:
        return set()

    def parse(e) -> Optional[Set[int]]:
        if isinstance(e, ast.Constant):
            return {e.value} if isinstance(e.value, int) else None
        if isinstance(e, (ast.Tuple, ast.List)):
            out: Set[int] = set()
            for el in e.elts:
                if isinstance(el, ast.Constant) \
                        and isinstance(el.value, int):
                    out.add(el.value)
                else:
                    return None
            return out
        if isinstance(e, ast.IfExp):
            a, b = parse(e.body), parse(e.orelse)
            if a is None and b is None:
                return None
            return (a or set()) | (b or set())
        if isinstance(e, ast.BinOp) and isinstance(e.op, ast.Add):
            a, b = parse(e.left), parse(e.right)
            if a is None or b is None:
                return None
            return a | b
        if isinstance(e, ast.Call):
            f = e.func
            name = (f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute)
                    else "")
            if name.endswith(_DONATION_HELPER_SUFFIX):
                out = set()
                for el in e.args:
                    if isinstance(el, ast.Constant) \
                            and isinstance(el.value, int):
                        out.add(el.value)
                    else:
                        return None
                return out
        return None

    return parse(expr)


def _is_jit_callee(func) -> bool:
    if isinstance(func, ast.Name):
        return func.id in ("jit", "pjit")
    return isinstance(func, ast.Attribute) and func.attr in ("jit",
                                                             "pjit")


class _DonationVisitor(_FuncScoper):
    """Per-file pass over jit sites: undonated-RMW findings plus the
    donated-then-reused caller hazard."""

    def __init__(self, sf: SourceFile, index: _DonationIndex,
                 module: str, findings: List[Finding]):
        super().__init__()
        self.sf = sf
        self.index = index
        self.module = module
        self.findings = findings
        # per function frame: local name -> (donated set, jit Call);
        # plus (call node, donated arg Name, position) dispatch records
        self.frames: List[Dict] = [{"jitted": {}, "dispatches": []}]
        self.entry_stack: List[Optional[_FnEntry]] = [None]

    def enter_function(self, node, qualname):
        self.frames.append({"jitted": {}, "dispatches": []})
        entry = self.index.by_node.get(id(node))
        self.entry_stack.append(entry)
        if entry is not None:
            self._check_decorators(node, entry)

    def _check_decorators(self, node, entry):
        """@jax.jit / @functools.partial(jax.jit, donate_argnums=...)
        — the decorator-form jit site."""
        for dec in node.decorator_list:
            call = None
            if isinstance(dec, ast.Call) and _is_jit_callee(dec.func):
                call = dec
            elif isinstance(dec, ast.Call) \
                    and any(_is_jit_callee(a) for a in dec.args):
                call = dec                  # partial(jax.jit, ...)
            elif _is_jit_callee(dec):
                for pos in sorted(entry.rmw_argnums()):
                    self.findings.append(self._rmw_finding(dec, entry,
                                                           pos))
                return
            if call is None:
                continue
            donated = _donated_argnums(call)
            if donated is None:
                return
            for pos in sorted(entry.rmw_argnums() - donated):
                self.findings.append(self._rmw_finding(call, entry,
                                                       pos))
            return

    def _rmw_finding(self, node, cand: _FnEntry, pos: int) -> Finding:
        return self.sf.finding(
            "donation", node,
            f"{cand.qualname}() RMWs its {cand.param_label(pos)} "
            f"(argnum {pos}) into an output, but this jit site does "
            f"not donate it — every dispatch copies the buffer (the "
            f"BENCH_r06 carry-copy class); add it to donate_argnums "
            f"or annotate why the copy is intended")

    def exit_function(self, node):
        self._flush_frame(node)
        self.entry_stack.pop()

    def exit_module(self):
        self._flush_frame(self.sf.tree)

    def _flush_frame(self, scope_node):
        frame = self.frames.pop()
        for call, arg_name, pos in frame["dispatches"]:
            self._check_reuse(scope_node, call, arg_name, pos)

    def _check_reuse(self, scope_node, call, arg_name, pos):
        """A donated argument read again after the dispatch line (with
        no intervening rebind) is a use-after-free on any backend that
        honors donation."""
        end = getattr(call, "end_lineno", call.lineno)
        stores = []
        loads = []
        for sub in _walk_shallow(scope_node):
            if not isinstance(sub, ast.Name) or sub.id != arg_name:
                continue
            # stores ON the dispatch line count as rebinds — the
            # canonical `kv = j(kv, xs)` spelling rebinds the name to
            # the program output in the dispatch statement itself;
            # loads on that line are the dispatch arguments, not reuse
            if isinstance(sub.ctx, ast.Store) \
                    and sub.lineno >= call.lineno:
                stores.append(sub.lineno)
            elif isinstance(sub.ctx, ast.Load) and sub.lineno > end:
                loads.append(sub.lineno)
        for ln in sorted(loads):
            # strictly-earlier stores only: `kv = kv + 1` READS the
            # donated buffer before its own same-line store
            if any(s < ln for s in stores):
                break           # rebound before this read: fresh value
            self.findings.append(Finding(
                "donation", self.sf.path, ln, 0,
                f"{arg_name!r} is donated to the dispatch on line "
                f"{call.lineno} and read again here — donated buffers "
                f"are deleted; this is a use-after-free wherever "
                f"donation is honored", self.sf.line_text(ln)))
            break               # one finding per dispatch

    def visit_Call(self, node):
        if _is_jit_callee(node.func) and node.args:
            donated = _donated_argnums(node)
            target_expr = node.args[0]
            entry = self.entry_stack[-1]
            candidates = []
            if entry is not None:
                candidates = self.index.resolve(entry, target_expr)
            elif isinstance(target_expr, ast.Name):
                candidates = self.index.by_module.get(
                    self.module, {}).get(target_expr.id, [])
            if donated is not None and candidates:
                cand = candidates[0]
                for pos in sorted(cand.rmw_argnums() - donated):
                    self.findings.append(self._rmw_finding(node, cand,
                                                           pos))
        else:
            # dispatch through a jitted local: record donated-arg names
            f = node.func
            frame = self.frames[-1]
            rec = None
            if isinstance(f, ast.Name):
                # nearest enclosing frame holding the handle — a
                # module-level `j = jax.jit(...)` dispatched inside a
                # function is still a donation site
                for fr in reversed(self.frames):
                    if f.id in fr["jitted"]:
                        rec = fr["jitted"][f.id]
                        break
            elif isinstance(f, ast.Call) and _is_jit_callee(f.func):
                d = _donated_argnums(f)
                rec = d if d else None
            if rec:
                for pos in rec:
                    if pos < len(node.args) \
                            and isinstance(node.args[pos], ast.Name) \
                            and not any(isinstance(a, ast.Starred)
                                        for a in node.args[:pos]):
                        frame["dispatches"].append(
                            (node, node.args[pos].id, pos))
        self.generic_visit(node)

    def visit_Assign(self, node):
        # `jitted = jax.jit(impl, donate_argnums=...)` — remember the
        # local handle's donated set for dispatch-site reuse checks
        if isinstance(node.value, ast.Call) \
                and _is_jit_callee(node.value.func):
            donated = _donated_argnums(node.value)
            if donated:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.frames[-1]["jitted"][t.id] = donated
        self.generic_visit(node)


def check_donation(files: Dict[str, SourceFile], graph
                   ) -> List[Finding]:
    index = _DonationIndex(files, graph)
    for _ in range(8):              # cross-module fixpoint
        if not _rmw_pass(index):
            break
    findings: List[Finding] = []
    for path, sf in files.items():
        v = _DonationVisitor(sf, index, _module_name(path), findings)
        v.visit(sf.tree)
        v.exit_module()
    return findings


# -------------------------------------------------------------- driver

def _module_name(path: str) -> str:
    module = os.path.splitext(path.replace(os.sep, "/"))[0].replace(
        "/", ".")
    if module.endswith(".__init__"):
        module = module[: -len(".__init__")]
    return module


ALL_RULES = ("host-sync", "traced-branch", "default-dtype",
             "metric-drift", "span-drift", "fault-site",
             "snapshot-coverage", "journal-coverage", "rng-stream",
             "collective-axis", "pspec-axis", "donation")


def run_rules(files: Dict[str, SourceFile], graph, docs_text: str,
              fault_sites: Set[str],
              rules=ALL_RULES,
              known_axes: Optional[Dict[str, Optional[int]]] = None
              ) -> List[Finding]:
    findings: List[Finding] = []
    axes = known_axes or {}
    per_file = {"host-sync": lambda sf: check_host_sync(sf, graph),
                "traced-branch": lambda sf: check_traced_branch(sf, graph),
                "default-dtype": check_default_dtype,
                "fault-site": lambda sf: check_fault_site(sf, fault_sites),
                "snapshot-coverage": check_snapshot_coverage,
                "collective-axis":
                    lambda sf: check_collective_axis(sf, axes),
                "pspec-axis": lambda sf: check_pspec_axis(sf, axes)}
    aggregate = {"journal-coverage": check_journal_coverage,
                 "rng-stream": check_rng_stream,
                 "donation": lambda fs: check_donation(fs, graph)}
    docs_checks = {"metric-drift": check_metric_drift,
                   "span-drift": check_span_drift}
    for rule in rules:
        if rule in docs_checks:
            sources = {p: sf.source for p, sf in files.items()}
            findings.extend(docs_checks[rule](
                sources, docs_text,
                lambda p, ln: files[p].line_text(ln)))
            continue
        if rule in aggregate:
            findings.extend(aggregate[rule](files))
            continue
        fn = per_file[rule]
        for sf in files.values():
            findings.extend(fn(sf))
    findings.sort(key=Finding.sort_key)
    return findings
