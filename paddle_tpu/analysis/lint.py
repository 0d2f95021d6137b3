"""tpu-lint driver: file walking, suppressions, baseline filtering.

The performance claims this repo makes — "no steady-state H2D",
"byte-identical hot path when disarmed", "untraced path bit-identical"
— are invariants about *where the code syncs, recompiles and branches
on traced values*. This pass makes them structural: analysis/rules.py
holds the checks, this module turns them into a repeatable gate:

* ``run_lint(root)`` — all findings for the package;
* inline ``# tpu-lint: allow(<rule>[, <rule>...]): reason`` on the
  flagged line (or the line directly above it) suppresses an
  *intentional* site — the reason is the point: every suppression is a
  classified sync;
* ``# tpu-lint: allow-file(<rule>): reason`` in a module's first 30
  lines suppresses a rule for a whole eager-only module (the
  data-dependent-shape helpers in tensor/extra_ops.py, vision/ops.py);
* the checked-in ``analysis/baseline.json`` pins violations that
  predate the linter, so ``--check`` fails only on NEW ones
  (analysis/baseline.py; ``--update-baseline`` regenerates it).

The lint path never imports jax — ``python -m paddle_tpu.analysis``
must stay fast enough (<20 s, pinned by tests/test_analysis.py) to run
as a tier-1 test and as the gate the future to_static/compile-cache
layer is validated against.
"""

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from paddle_tpu.analysis import baseline as baseline_mod
from paddle_tpu.analysis import callgraph as callgraph_mod
from paddle_tpu.analysis import rules as rules_mod
from paddle_tpu.analysis.rules import ALL_RULES, Finding, SourceFile
from paddle_tpu.analysis.rules import walk as rules_walk

__all__ = ["ALL_RULES", "Finding", "LintResult", "repo_root",
           "package_sources", "run_lint"]

_ALLOW_LINE = re.compile(
    r"#\s*tpu-lint:\s*allow\(([a-z0-9_,\- ]+)\)")
_ALLOW_FILE = re.compile(
    r"#\s*tpu-lint:\s*allow-file\(([a-z0-9_,\- ]+)\)")
# `# tpu-lint: volatile(reason)` — the snapshot-coverage rule's
# field-level classification: "this mutable field is rebuilt, not
# serialized, and here is why". Sugar for allow(snapshot-coverage)
# with the reason inside the parens (docs/ANALYSIS.md).
_VOLATILE_LINE = re.compile(r"#\s*tpu-lint:\s*volatile\(")
_ALLOW_FILE_SCAN_LINES = 30


def repo_root() -> str:
    """The directory holding the ``paddle_tpu`` package (and docs/)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _iter_py_files(pkg_dir: str):
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames[:] = sorted(d for d in dirnames
                             if d != "__pycache__")
        for f in sorted(filenames):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def package_sources(root: Optional[str] = None) -> Dict[str, SourceFile]:
    """Repo-relative path -> SourceFile for every module in
    ``paddle_tpu/`` (deterministic order: sorted walk)."""
    root = root or repo_root()
    pkg = os.path.join(root, "paddle_tpu")
    files: Dict[str, SourceFile] = {}
    for abspath in _iter_py_files(pkg):
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        with open(abspath, encoding="utf-8") as fh:
            src = fh.read()
        try:
            tree = ast.parse(src, filename=rel)
        except SyntaxError as e:    # pragma: no cover - package parses
            raise SyntaxError(f"tpu-lint cannot parse {rel}: {e}") from e
        files[rel] = SourceFile(rel, src, tree)
    return files


def _suppressions(sf: SourceFile) -> Tuple[Dict[int, set], set]:
    """(line -> allowed rules, file-level allowed rules).

    An inline pragma (code + comment on one line) covers its own line.
    A comment-ONLY pragma line covers the next statement — its full
    multi-line span for a simple statement (an annotation above a
    wrapped expression reaches a finding on any continuation line),
    but only the HEADER of a compound statement (if/for/with/def):
    covering the whole block would let a future violation inside it
    ride an annotation written for the header."""
    spans = []
    for node in rules_walk(sf.tree):
        if not isinstance(node, ast.stmt):
            continue
        body = getattr(node, "body", None)
        if isinstance(body, list) and body \
                and isinstance(body[0], ast.stmt):
            end = max(node.lineno, body[0].lineno - 1)
        else:
            end = getattr(node, "end_lineno", node.lineno)
        spans.append((node.lineno, end))
    spans.sort()
    per_line: Dict[int, set] = {}
    file_level: set = set()
    for i, line in enumerate(sf.lines, 1):
        m = _ALLOW_LINE.search(line)
        if m:
            allowed = {r.strip() for r in m.group(1).split(",")}
        elif _VOLATILE_LINE.search(line):
            allowed = {"snapshot-coverage"}
        else:
            allowed = None
        if allowed:
            per_line.setdefault(i, set()).update(allowed)
            if line.lstrip().startswith("#"):
                # comment-only pragma: cover the next statement's span
                # (an inline pragma covers ONLY its own line — spilling
                # onto the next line would silently waive the rule for
                # an unannotated neighbour)
                nxt = next((s for s in spans if s[0] > i), None)
                cover = (range(nxt[0], nxt[1] + 1) if nxt
                         else range(i + 1, i + 2))
                for ln in cover:
                    per_line.setdefault(ln, set()).update(allowed)
        if i <= _ALLOW_FILE_SCAN_LINES:
            m = _ALLOW_FILE.search(line)
            if m:
                file_level.update(
                    r.strip() for r in m.group(1).split(","))
    return per_line, file_level


class LintResult:
    """Everything one lint run produced, pre-partitioned."""

    def __init__(self, findings, suppressed, baselined, stale_baseline):
        #: unsuppressed, non-baselined findings — the ones that FAIL
        self.findings: List[Finding] = findings
        self.suppressed: List[Finding] = suppressed
        self.baselined: List[Finding] = baselined
        #: baseline entries no longer produced (fixed or drifted) —
        #: informational; --update-baseline clears them
        self.stale_baseline: List[Tuple] = stale_baseline

    @property
    def ok(self) -> bool:
        return not self.findings

    def summary(self) -> str:
        return (f"{len(self.findings)} finding(s), "
                f"{len(self.suppressed)} suppressed, "
                f"{len(self.baselined)} baselined"
                + (f", {len(self.stale_baseline)} stale baseline "
                   f"entr(y/ies)" if self.stale_baseline else ""))


def run_lint(root: Optional[str] = None,
             rules: Sequence[str] = ALL_RULES,
             paths: Optional[Sequence[str]] = None,
             respect_suppressions: bool = True,
             respect_baseline: bool = True,
             files: Optional[Dict[str, SourceFile]] = None) -> LintResult:
    """Run the rule set over the package (or a pre-built ``files``
    mapping for tests). ``paths`` restricts the *reported* findings to
    repo-relative prefixes while still building the call graph over the
    whole package (reachability is a whole-package property)."""
    root = root or repo_root()
    for r in rules:
        if r not in ALL_RULES:
            raise ValueError(f"unknown rule {r!r}; one of {ALL_RULES}")
    if files is None:
        files = package_sources(root)
    # ONE call graph, built once and reused by every rule that needs
    # reachability or import resolution (host-sync/traced-branch jit
    # reachability, the donation rule's cross-module RMW fixpoint); a
    # metric-drift-only run (tests/test_slo.py's delegate) skips the
    # whole-package walk
    if {"host-sync", "traced-branch", "donation"} & set(rules):
        graph = callgraph_mod.build_callgraph(
            {p: sf.tree for p, sf in files.items()})
    else:
        graph = callgraph_mod.CallGraph()
    docs_path = os.path.join(root, "docs", "OBSERVABILITY.md")
    if os.path.exists(docs_path):
        with open(docs_path, encoding="utf-8") as fh:
            docs_text = fh.read()
    else:
        # installed-package run: docs/ is not shipped. An empty docs
        # text would flag EVERY metric/span literal as undocumented —
        # drop both docs-pinned rules instead of failing --check with
        # spurious findings
        docs_text = ""
        rules = tuple(r for r in rules
                      if r not in ("metric-drift", "span-drift"))
    faults_rel = "paddle_tpu/resilience/faults.py"
    fault_sites = (rules_mod.known_fault_sites(files[faults_rel].source)
                   if faults_rel in files else set())
    # the mesh-axis registry: from the files mapping when present
    # (normal runs), else from the tree on disk (synthetic-files test
    # runs); with neither, the axis rules are dropped like metric-drift
    topo_rel = "paddle_tpu/parallel/topology.py"
    topo_disk = os.path.join(root, "paddle_tpu", "parallel",
                             "topology.py")
    if topo_rel in files:
        known_axes = rules_mod.known_mesh_axes(files[topo_rel].source)
    elif os.path.exists(topo_disk):
        with open(topo_disk, encoding="utf-8") as fh:
            known_axes = rules_mod.known_mesh_axes(fh.read())
    else:
        known_axes = {}
        rules = tuple(r for r in rules
                      if r not in ("collective-axis", "pspec-axis"))

    all_findings = rules_mod.run_rules(files, graph, docs_text,
                                       fault_sites, rules=rules,
                                       known_axes=known_axes)
    if paths:
        norm = [p.rstrip("/") for p in paths]
        all_findings = [f for f in all_findings
                        if any(f.path == p or f.path.startswith(p + "/")
                               for p in norm)]

    suppressed: List[Finding] = []
    kept: List[Finding] = []
    if respect_suppressions:
        sup_cache: Dict[str, Tuple[Dict[int, set], set]] = {}
        for f in all_findings:
            if f.path not in sup_cache:
                sup_cache[f.path] = _suppressions(files[f.path])
            per_line, file_level = sup_cache[f.path]
            if f.rule in file_level or f.rule in per_line.get(f.line,
                                                              ()):
                suppressed.append(f)
            else:
                kept.append(f)
    else:
        kept = list(all_findings)

    baselined: List[Finding] = []
    stale: List[Tuple] = []
    if respect_baseline:
        pinned = baseline_mod.load(root)
        kept, baselined, stale = baseline_mod.apply(kept, pinned)
        if paths or set(rules) != set(ALL_RULES):
            # a filtered run sees a SUBSET of findings — out-of-scope
            # pins are not stale, they are merely unobserved
            stale = []
    return LintResult(kept, suppressed, baselined, stale)
