"""tpu-lint + dispatch sanitizer (paddle_tpu.analysis).

Two layers under test. Static: the AST rules fire on synthetic
violations, suppressions and the baseline absorb classified sites, the
package itself lints clean, and the pin regenerates deterministically.
Runtime: the transfer/recompile guards work on first principles, and
then the repo's own claims become properties — a steady-state
``ServingEngine.step()`` performs ZERO H2D transfers and ZERO
recompiles after warmup, join/leave compiles exactly the expected
prefill-shape set, and a warm ``generate`` (bf16 and int8, disarmed
FaultPlan armed) re-dispatches with no transfer and no compile.
"""

import ast
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu.analysis import baseline as baseline_mod
from paddle_tpu.analysis import lint
from paddle_tpu.analysis import rules as rules_mod
from paddle_tpu.analysis import runtime as rt
from paddle_tpu.analysis.rules import SourceFile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _files(**named_sources):
    """name -> source text, as a run_lint-ready files mapping. Names
    map to fake package paths (``mod`` -> paddle_tpu/mod.py)."""
    out = {}
    for name, src in named_sources.items():
        path = f"paddle_tpu/{name.replace('.', '/')}.py"
        out[path] = SourceFile(path, src, ast.parse(src))
    return out


def _lint(files, rules=lint.ALL_RULES, **kw):
    kw.setdefault("respect_baseline", False)
    return lint.run_lint(ROOT, rules=rules, files=files, **kw)


# ------------------------------------------------------------ rule units

def test_host_sync_rule_fires_and_skips_host_literals():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def f(x, it):\n"
        "    a = np.asarray(x)            # flagged: maybe device\n"
        "    b = np.asarray([1, 2])       # literal: host\n"
        "    c = np.asarray(list(it))     # list(): host\n"
        "    d = np.asarray([e for e in it])  # comprehension: host\n"
        "    e = np.asarray(np.stack([x]))    # np-of-np: host already\n"
        "    v = x.item()                 # flagged\n"
        "    w = jax.device_get(x)        # flagged\n"
        "    x.block_until_ready()        # flagged\n"
        "    return a, b, c, d, e, v, w\n")
    res = _lint(_files(mod=src), rules=("host-sync",))
    lines = sorted(f.line for f in res.findings)
    assert lines == [4, 9, 10, 11], res.findings


def test_host_sync_concretization_only_in_jit_reachable_code():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def helper(x):\n"
        "    return float(x.sum())\n"          # reachable via entry
        "def eager_helper(x):\n"
        "    return float(x.sum())\n"          # nothing jits this
        "@jax.jit\n"
        "def entry(x):\n"
        "    return helper(x)\n")
    res = _lint(_files(mod=src), rules=("host-sync",))
    assert [f.line for f in res.findings] == [4]
    # config casts on plain names never flag, even under jit
    src2 = (
        "import jax\n"
        "@jax.jit\n"
        "def entry(x, temperature):\n"
        "    t = float(temperature)\n"
        "    n = int(x.shape[0])\n"
        "    return x * t * n\n")
    assert not _lint(_files(mod=src2), rules=("host-sync",)).findings


def test_traced_branch_rule():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def entry(x, flag):\n"
        "    s = jnp.sum(x)\n"
        "    if s > 0:\n"                      # flagged: traced data
        "        x = x + 1\n"
        "    if x.shape[0] > 2:\n"             # static metadata: fine
        "        x = x * 2\n"
        "    if flag:\n"                       # plain param: fine
        "        x = x - 1\n"
        "    y = s + 1\n"
        "    assert y > 0\n"                   # flagged: propagated taint
        "    return x\n")
    res = _lint(_files(mod=src), rules=("traced-branch",))
    assert sorted(f.line for f in res.findings) == [6, 13]


def test_traced_branch_reaches_through_jit_call_and_scan():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def step(carry, i):\n"
        "    m = jnp.max(carry)\n"
        "    if m > 0:\n"                      # flagged: lax.scan body
        "        carry = carry - m\n"
        "    return carry, i\n"
        "def outer(x):\n"
        "    fn = jax.jit(lambda c: lax.scan(step, c, None, length=3))\n"
        "    return fn(x)\n")
    res = _lint(_files(mod=src), rules=("traced-branch",))
    assert [f.line for f in res.findings] == [6]


def test_default_dtype_rule_kernel_dirs_only():
    src = (
        "import numpy as np\n"
        "def f(n):\n"
        "    a = np.zeros(n)\n"                # flagged
        "    b = np.zeros(n, np.int32)\n"      # positional dtype: fine
        "    c = np.arange(n, dtype=np.float32)\n"
        "    d = np.full((n,), 0.0, dtype=np.float64)\n"  # flagged
        "    e = np.zeros(n, np.float64)\n"    # flagged: positional f64
        "    g = np.asarray(x, np.float64)\n"  # flagged: positional f64
        "    h = np.array([1.5, 2.0])\n"       # flagged: implicit f64
        "    k = np.array([1.5], np.float32)\n"
        "    return a, b, c, d, e, g, h, k\n")
    res = _lint(_files(**{"ops.mod": src}), rules=("default-dtype",))
    assert sorted(f.line for f in res.findings) == [3, 6, 7, 8, 9]
    # same source outside a kernel dir: clean
    assert not _lint(_files(**{"io.mod": src}),
                     rules=("default-dtype",)).findings


def test_fault_site_rule():
    faults_src = 'KNOWN_SITES = ("train.step", "decode.dispatch")\n'
    src = (
        "from paddle_tpu.resilience import faults as _faults\n"
        "def f():\n"
        '    _faults.maybe_fire("decode.dispatch")\n'   # registered
        '    _faults.maybe_fire("bogus.site")\n')       # flagged
    files = _files(mod=src)
    fp = "paddle_tpu/resilience/faults.py"
    files[fp] = SourceFile(fp, faults_src, ast.parse(faults_src))
    res = _lint(files, rules=("fault-site",))
    assert [f.line for f in res.findings] == [4]


def test_metric_drift_skipped_without_docs_file(tmp_path):
    """Installed-package run (docs/ not shipped): the rule is dropped
    instead of flagging every metric literal as undocumented."""
    src = 'registry().counter("serving.undocumented").inc()\n'
    res = lint.run_lint(str(tmp_path), rules=("metric-drift",),
                        files=_files(mod=src), respect_baseline=False)
    assert res.ok


@pytest.mark.slow
def test_filtered_run_reports_no_stale_baseline():
    """--rules/--paths runs see a subset of findings; out-of-scope
    pins are unobserved, not stale."""
    res = lint.run_lint(ROOT, rules=("metric-drift",))
    assert res.ok and not res.stale_baseline
    res = lint.run_lint(ROOT, paths=["paddle_tpu/serving"])
    assert res.ok and not res.stale_baseline


def test_metric_drift_rule_shared_implementation():
    sources = {"paddle_tpu/a.py":
               'registry().counter("serving.good").inc()\n'
               'registry().gauge("serving.rotten").set(1)\n'
               # wrapped across lines: the scan must still see it
               'registry().histogram(\n'
               '    "serving.wrapped_rotten").observe(2)\n'}
    docs = "| `serving.good` | documented |\n"
    found = rules_mod.check_metric_drift(sources, docs,
                                         lambda p, ln: "")
    assert [(f.rule, f.line) for f in found] == [
        ("metric-drift", 2), ("metric-drift", 3)]
    names = rules_mod.collect_metric_names(sources)
    assert set(names) == {"serving.good", "serving.rotten",
                          "serving.wrapped_rotten"}


def test_span_drift_rule_shared_implementation():
    sources = {"paddle_tpu/a.py":
               'tr.record("serving.good_span", ts=0.0)\n'
               'tr.record("serving.rotten_span", ts=0.0)\n'
               # wrapped across lines: the scan must still see it
               'with tracer.span(\n'
               '        "decode.wrapped_rotten_span"):\n'
               '    pass\n'}
    docs = "| `serving.good_span` | documented |\n"
    found = rules_mod.check_span_drift(sources, docs, lambda p, ln: "")
    assert [(f.rule, f.line) for f in found] == [
        ("span-drift", 3), ("span-drift", 2)]
    assert all("not documented in docs/OBSERVABILITY.md" in f.message
               for f in found)
    names = rules_mod.collect_span_names(sources)
    assert set(names) == {"serving.good_span", "serving.rotten_span",
                          "decode.wrapped_rotten_span"}


def test_span_drift_sees_phase_helper_and_profiler_annotations():
    """The serving engine's phase spans are opened through
    ``self._phase(...)`` and bare profiler annotations, not a Tracer:
    the same rule pins those names to the span table."""
    sources = {"paddle_tpu/a.py":
               'with self._phase("serving.step.good", rows=1):\n'
               '    pass\n'
               'with self._phase(\n'
               '        "serving.step.rotten"):\n'
               '    pass\n'
               'with jax.profiler.TraceAnnotation(\n'
               '        "serving.submit_rotten", request_id=1):\n'
               '    pass\n'
               'with jax.profiler.StepTraceAnnotation("serving.tick_rotten",\n'
               '                                      step_num=3):\n'
               '    pass\n'}
    docs = "| `serving.step.good` | documented |\n"
    found = rules_mod.check_span_drift(sources, docs, lambda p, ln: "")
    assert sorted(f.line for f in found) == [3, 6, 9]
    assert set(rules_mod.collect_span_names(sources)) == {
        "serving.step.good", "serving.step.rotten",
        "serving.submit_rotten", "serving.tick_rotten"}


def test_span_drift_skipped_without_docs_file(tmp_path):
    """Installed-package run (docs/ not shipped): span-drift is dropped
    like metric-drift instead of flagging every span literal."""
    src = 'tr.record("serving.undocumented_span", ts=0.0)\n'
    res = lint.run_lint(str(tmp_path), rules=("span-drift",),
                        files=_files(mod=src), respect_baseline=False)
    assert res.ok


def test_span_names_documented_in_observability_table():
    """Every serving.*/decode.* span literal in paddle_tpu/ must appear
    in docs/OBSERVABILITY.md's span-name table — the timeline
    export's track names cannot silently rot. Same shared-implementation
    pattern as the metric-drift delegate in tests/test_slo.py:
    suppressions and the baseline are DISABLED here."""
    files = lint.package_sources(ROOT)
    names = rules_mod.collect_span_names(
        {p: sf.source for p, sf in files.items()})
    assert len(names) >= 5, f"span scan found only {sorted(names)}"
    res = lint.run_lint(ROOT, rules=("span-drift",), files=files,
                        respect_suppressions=False,
                        respect_baseline=False)
    assert res.ok, "undocumented spans:\n" + "\n".join(
        map(repr, res.findings))


# -------------------------------------- state-protocol rules (PR 13)

def test_snapshot_coverage_rule():
    """A class with snapshot()+restore(): mutable fields must round-trip
    or carry volatile(...); asymmetric coverage is its own finding."""
    src = (
        "class Engine:\n"
        "    def __init__(self, cap):\n"
        "        self.cap = cap\n"                  # immutable: config
        "        self._count = 0\n"                 # covered both ways
        "        self._lost = 0\n"                  # flagged: uncovered
        "        self._half = 0\n"                  # flagged: asymmetric
        "        self._tmp = None  # tpu-lint: volatile(scratch)\n"
        "    def bump(self):\n"
        "        self._count += 1\n"
        "        self._lost += 1\n"
        "        self._half += 1\n"
        "        self._tmp = 3\n"
        "    def snapshot(self):\n"
        "        return {'count': self._count, 'half': self._half}\n"
        "    def restore(self, snap):\n"
        "        self._count = snap['count']\n")
    res = _lint(_files(mod=src), rules=("snapshot-coverage",))
    assert sorted(f.line for f in res.findings) == [5, 6], res.findings
    msgs = {f.line: f.message for f in res.findings}
    assert "not covered" in msgs[5]
    assert "never restored" in msgs[6]
    assert len(res.suppressed) == 1     # the volatile(...) pragma

    # a class without BOTH protocol halves is out of scope entirely
    src_noload = src.replace("    def restore(self, snap):\n"
                             "        self._count = snap['count']\n", "")
    assert not _lint(_files(mod=src_noload),
                     rules=("snapshot-coverage",)).findings


def test_snapshot_coverage_mutator_calls_and_tuple_stores():
    """In-place mutator calls (self._q.push) and tuple-unpack stores
    (a, self._pool, b = ...) both count as mutation."""
    src = (
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._q = []\n"
        "        self._pool = None\n"
        "    def run(self):\n"
        "        self._q.append(1)\n"
        "        x, self._pool = f()\n"
        "    def snapshot(self):\n"
        "        return {}\n"
        "    def restore(self, snap):\n"
        "        pass\n")
    res = _lint(_files(mod=src), rules=("snapshot-coverage",))
    assert sorted(f.line for f in res.findings) == [3, 4], res.findings


def test_journal_coverage_rule():
    """Terminal transitions must journal-or-annotate; event kinds pin
    against KNOWN_EVENTS; registered-but-never-emitted kinds are stale."""
    journal_src = ('KNOWN_EVENTS = {"finish": "terminal",\n'
                   '                "ghost": "never emitted"}\n')
    mod = (
        "class E:\n"
        "    def good(self, rid, res):\n"
        "        self.results[rid] = res\n"
        "        if self.journal is not None:\n"
        "            self.journal.append('finish', rid=rid)\n"
        "    def bad_kind(self):\n"
        "        self.journal.append('bogus')\n"      # unregistered
        "    def uncovered(self, rid, res):\n"
        "        self.results[rid] = res\n"           # flagged
        "    def maker(self, req):\n"
        "        return RequestResult(req)\n"         # flagged anchor
        "    def annotated(self, rid, res):\n"
        "        # tpu-lint: allow(journal-coverage): router covers\n"
        "        self.results[rid] = res\n")
    files = _files(**{"serving.journal": journal_src,
                      "serving.mod": mod})
    res = _lint(files, rules=("journal-coverage",))
    by_path = {}
    for f in res.findings:
        by_path.setdefault(f.path, []).append(f.line)
    assert sorted(by_path["paddle_tpu/serving/mod.py"]) == [7, 9, 11], \
        res.findings
    # the stale "ghost" registry entry anchors in journal.py
    assert by_path["paddle_tpu/serving/journal.py"] == [2]
    assert len(res.suppressed) == 1
    # outside serving/, the same source is out of scope
    res2 = _lint(_files(**{"serving.journal": journal_src, "mod": mod}),
                 rules=("journal-coverage",))
    assert {f.path for f in res2.findings} == {
        "paddle_tpu/serving/journal.py"}    # only the stale ghost


def test_rng_stream_rule():
    """Raw PRNGKey/split and non-fold_in-keyed draws are findings; the
    fold taint flows through locals, helpers and parameters — a bad
    key is flagged at the CALL SITE of a key-forwarding function."""
    src = (
        "import jax\n"
        "def bad(x):\n"
        "    k = jax.random.PRNGKey(0)\n"             # raw stream
        "    return jax.random.categorical(k, x)\n"   # unfolded draw
        "def good(x, base, t):\n"
        "    k = jax.random.fold_in(base, t)\n"
        "    return jax.random.categorical(k, x)\n"
        "def vmapped(x, base, t):\n"
        "    k = jax.random.fold_in(base, t)\n"
        "    return jax.vmap(\n"
        "        lambda kk, lg: jax.random.categorical(kk, lg))(k, x)\n"
        "def helper(logits, key):\n"
        "    return jax.random.categorical(key, logits)\n"
        "def call_bad(x, raw_key):\n"
        "    return helper(x, raw_key)\n"             # propagates: param
        "def call_good(x, base, t):\n"
        "    return helper(x, jax.random.fold_in(base, t))\n"
        "def outer_bad(x):\n"
        "    return call_bad(x, jax.random.split(None)[0])\n")
    res = _lint(_files(**{"serving.mod": src}), rules=("rng-stream",))
    lines = sorted(f.line for f in res.findings)
    # 3: PRNGKey, 4: unfolded draw, 19: split (raw) + call-site into
    # the call_bad->helper forwarding chain
    assert lines == [3, 4, 19, 19], res.findings
    # same module outside serving//inference/: out of scope
    assert not _lint(_files(mod=src), rules=("rng-stream",)).findings


def test_new_rules_in_all_and_filterable():
    """--rules accepts the three new names and the tree is clean under
    them (the serving/resilience burn-down, pinned)."""
    assert {"snapshot-coverage", "journal-coverage",
            "rng-stream"} <= set(lint.ALL_RULES)
    res = lint.run_lint(ROOT, rules=("snapshot-coverage",
                                     "journal-coverage", "rng-stream"))
    assert res.ok, res.findings


# --------------------------------- mesh/donation rules (this PR)

def test_known_axes_registry_parses_and_matches_import():
    """The statically-parsed registry equals the importable one, and
    the multichip-validated axes carry their dryrun degrees."""
    from paddle_tpu.parallel.topology import KNOWN_AXES
    with open(os.path.join(ROOT, "paddle_tpu", "parallel",
                           "topology.py"), encoding="utf-8") as fh:
        parsed = rules_mod.known_mesh_axes(fh.read())
    assert parsed == KNOWN_AXES
    assert {"dp", "pp", "sharding", "sep", "mp"} <= set(parsed)
    assert parsed["mp"] == 2 and parsed["dp"] == 2


def test_collective_axis_rule():
    """Axis-name literals on named-axis collectives pin against
    KNOWN_AXES — resolved through parameter defaults, locals and
    module constants; dynamic axes are the documented blind spot."""
    src = (
        "import jax\n"
        "from jax import lax\n"
        "PIPE = 'pp'\n"
        "def good(x):\n"
        "    return jax.lax.psum(x, 'mp')\n"
        "def const(x):\n"
        "    return lax.pmean(x, PIPE)\n"
        "def typo(x):\n"
        "    return lax.psum(x, 'modelp')\n"              # flagged
        "def via_default(x, axis_name='sharding'):\n"
        "    return lax.ppermute(x, axis_name, [(0, 1)])\n"
        "def bad_default(x, axis_name='shard'):\n"
        "    return lax.all_gather(x, axis_name)\n"       # flagged
        "def tupled(x):\n"
        "    return jax.lax.pcast(x, ('pp', 'bogus'), to='varying')\n"
        "def kw_form(x):\n"
        "    return lax.pmax(x, axis_name='dq')\n"        # flagged
        "def dynamic(x, axis_name):\n"
        "    return lax.pmax(x, axis_name)\n"              # blind spot
        "def shadowed(x, axis_name):\n"
        "    def inner():\n"
        "        axis_name = 'bogus'\n"        # inner scope must NOT
        "        return axis_name\n"           # leak into outer's pmax
        "    return lax.pmax(x, axis_name), inner\n")
    res = _lint(_files(**{"parallel.mod": src}),
                rules=("collective-axis",))
    assert sorted(f.line for f in res.findings) == [9, 13, 15, 17], \
        res.findings


def test_collective_axis_resolves_import_aliases():
    """`from jax.lax import psum as ps` resolves to the canonical
    collective (and must not crash the run), and a reassigned axis
    local resolves to the assignment in TEXT order (last write wins)."""
    src = (
        "from jax.lax import psum as ps, pmean\n"
        "def good(x):\n"
        "    return ps(x, 'mp')\n"
        "def bad(x):\n"
        "    return ps(x, 'mpp') + pmean(x, 'dq')\n")      # 2 findings
    res = _lint(_files(**{"parallel.mod": src}),
                rules=("collective-axis",))
    assert [f.line for f in res.findings] == [5, 5], res.findings
    src2 = (
        "import jax\n"
        "def rebound(x):\n"
        "    ax = 'tmp_not_an_axis'\n"
        "    ax = 'mp'\n"
        "    return jax.lax.psum(x, ax)\n")                # clean: 'mp'
    assert not _lint(_files(**{"parallel.mod": src2}),
                     rules=("collective-axis",)).findings


def test_collective_axis_sees_curried_axis_name_kwargs():
    """axis_name= keywords at currying sites (partial(local_fn,
    axis_name=...)) are checked even though the collective itself is
    inside the curried function — the shard_map composition sites."""
    src = (
        "from functools import partial\n"
        "def local_fn(x, axis_name):\n"
        "    import jax\n"
        "    return jax.lax.psum(x, axis_name)\n"
        "def compose(x):\n"
        "    good = partial(local_fn, axis_name='sep')\n"
        "    bad = partial(local_fn, axis_name='sepp')\n"  # flagged
        "    return good, bad\n")
    res = _lint(_files(**{"parallel.mod": src}),
                rules=("collective-axis",))
    assert [f.line for f in res.findings] == [7], res.findings


def test_pspec_axis_rule_and_divisibility():
    """PartitionSpec literals pin against KNOWN_AXES; a spec attached
    to a statically-known shape additionally checks sharded-dim
    divisibility by the axis's validated degree."""
    src = (
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "AX = 'mp'\n"
        "def specs(axis='dp'):\n"
        "    good = P(None, axis)\n"
        "    alias = P(AX)\n"
        "    bad = P('rows')\n"                            # flagged
        "    multi = P(('dp', 'cols'), None)\n"            # flagged
        "    return good, alias, bad, multi\n"
        "def divis(mesh):\n"
        "    ok = jax.ShapeDtypeStruct((4, 6), 'f4',\n"
        "        sharding=NamedSharding(mesh, P('dp', None)))\n"
        "    bad = jax.ShapeDtypeStruct((5, 6), 'f4',\n"
        "        sharding=NamedSharding(mesh, P('dp', None)))\n"
        "    return ok, bad\n")
    res = _lint(_files(**{"parallel.mod": src}), rules=("pspec-axis",))
    lines = sorted(f.line for f in res.findings)
    assert lines == [7, 8, 14], res.findings
    assert "divisible" in [f for f in res.findings
                           if f.line == 14][0].message


def test_donation_rule_rmw_carry():
    """A jitted function whose argument flows through an RMW chain —
    here via a lax.scan carry component — must donate that argnum; the
    carry_donate_argnums helper spelling is sanctioned; a donated site
    is clean; non-RMW'd carry components never flag."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def carry_donate_argnums(*a):\n"
        "    return tuple(a)\n"
        "def make(n):\n"
        "    def impl(state, carry, steps):\n"
        "        def body(c, i):\n"
        "            tok, kv = c\n"
        "            kv = kv.at[i].set(tok)\n"
        "            return (tok, kv), tok\n"
        "        c, toks = lax.scan(body, carry, jnp.arange(steps))\n"
        "        return c, toks\n"
        "    bad = jax.jit(impl)\n"                        # flagged
        "    good = jax.jit(impl, donate_argnums=(1,))\n"
        "    blessed = jax.jit(impl,\n"
        "        donate_argnums=carry_donate_argnums(1))\n"
        "    return bad, good, blessed\n")
    res = _lint(_files(mod=src), rules=("donation",))
    assert [f.line for f in res.findings] == [14], res.findings
    assert "argnum 1" in res.findings[0].message


def test_donation_rule_vararg_and_dus():
    """dynamic_update_slice counts as RMW, and a const-indexed vararg
    (the verify program's *hist pattern) maps to its argnum."""
    src = (
        "import jax\n"
        "from jax import lax\n"
        "def impl(x, *hist):\n"
        "    h = hist[0]\n"
        "    h2 = lax.dynamic_update_slice(h, x, (0,))\n"
        "    return h2\n"
        "bad = jax.jit(impl)\n"                            # flagged
        "good = jax.jit(impl, donate_argnums=(1,))\n")
    res = _lint(_files(mod=src), rules=("donation",))
    assert [f.line for f in res.findings] == [7], res.findings
    assert "*hist[0]" in res.findings[0].message


def test_donation_rule_method_receiver_and_argnames():
    """A bound-method RMW callee (self.scatter) maps caller args past
    the receiver — a correctly-donated site must stay clean; and a
    jit site donating BY NAME (donate_argnames=) is skipped, not
    flagged as undonated."""
    src = (
        "import jax\n"
        "class Pool:\n"
        "    def scatter(self, kv, idx):\n"
        "        return kv.at[idx].set(0)\n"
        "    def build(self):\n"
        "        def impl(pool, idx):\n"
        "            return self.scatter(pool, idx)\n"
        "        ok = jax.jit(impl, donate_argnums=(0,))\n"
        "        named = jax.jit(impl, donate_argnames='pool')\n"
        "        leaky = jax.jit(impl)\n"              # flagged: pool
        "        return ok, named, leaky\n")
    res = _lint(_files(mod=src), rules=("donation",))
    assert [f.line for f in res.findings] == [10], res.findings
    assert "pool (argnum 0)" in res.findings[0].message


def test_donation_rule_cross_module_and_decorator():
    """RMW facts propagate through package calls (the
    fused_decode_step seam), and decorator-form jit sites are checked
    like call-form ones."""
    kernel = (
        "def rmw_step(x, cache, pos):\n"
        "    return cache.at[pos].set(x)\n")
    mod = (
        "import jax\n"
        "import functools\n"
        "from paddle_tpu.kernel import rmw_step\n"
        "@jax.jit\n"
        "def leaky(x, cache):\n"
        "    return rmw_step(x, cache, 0)\n"               # flagged @4
        "@functools.partial(jax.jit, donate_argnums=(1,))\n"
        "def clean(x, cache):\n"
        "    return rmw_step(x, cache, 0)\n")
    res = _lint(_files(kernel=kernel, mod=mod), rules=("donation",))
    assert [(f.path, f.line) for f in res.findings] == [
        ("paddle_tpu/mod.py", 4)], res.findings


def test_donation_rule_donated_then_reused():
    """The reverse hazard: a donated argument read by the caller after
    the dispatch is flagged (use-after-free wherever donation is
    honored); a rebind before the read clears it."""
    src = (
        "import jax\n"
        "def impl(kv, x):\n"
        "    return kv.at[0].set(x)\n"
        "def driver(kv, xs):\n"
        "    j = jax.jit(impl, donate_argnums=(0,))\n"
        "    out = j(kv, xs)\n"
        "    total = kv.sum()\n"                           # flagged
        "    kv = out\n"
        "    out2 = j(kv, xs)\n"
        "    return out2, total\n")
    res = _lint(_files(mod=src), rules=("donation",))
    assert [f.line for f in res.findings] == [7], res.findings
    assert "use-after-free" in res.findings[0].message
    # a module-level jitted handle dispatched inside a function is
    # still a donation site, and a same-line store must not mask its
    # own RHS read (`kv = kv + 1` reads the donated buffer first)
    src2 = (
        "import jax\n"
        "def impl(kv, x):\n"
        "    return kv.at[0].set(x)\n"
        "j = jax.jit(impl, donate_argnums=(0,))\n"
        "def driver(kv, xs):\n"
        "    out = j(kv, xs)\n"
        "    kv = kv + 1\n"                                # flagged
        "    return out, kv\n"
        "def canonical(kv, xs):\n"
        "    kv = j(kv, xs)\n"        # same-line rebind: NOT reuse
        "    return kv\n")
    res2 = _lint(_files(mod=src2), rules=("donation",))
    assert [f.line for f in res2.findings] == [7], res2.findings


def test_callgraph_partial_peeling():
    """partial(f, ...) operands of a tracing wrapper are peeled, so
    shard_map(partial(local, ...)) puts `local` in the traced set."""
    src = (
        "import jax\n"
        "from functools import partial\n"
        "def local_fn(x):\n"
        "    return float(x.sum())\n"          # flagged iff reachable
        "def outer(x, mesh):\n"
        "    return jax.shard_map(partial(local_fn), mesh=mesh)(x)\n")
    res = _lint(_files(mod=src), rules=("host-sync",))
    assert [(f.path, f.line) for f in res.findings] == [
        ("paddle_tpu/mod.py", 4)], res.findings


# ------------------------------------------- suppressions and baseline

def test_inline_and_statement_suppressions():
    src = (
        "import numpy as np\n"
        "def f(x, y):\n"
        "    a = np.asarray(x)  # tpu-lint: allow(host-sync): classified\n"
        "    z = np.asarray(y)\n"  # NOT covered by line 3's inline pragma
        "    # tpu-lint: allow(host-sync): covers the whole statement\n"
        "    b = np.concatenate([x,\n"
        "                        np.asarray(y)])\n"
        "    c = np.asarray(y)\n"              # NOT suppressed
        "    return a, z, b, c\n")
    res = _lint(_files(mod=src), rules=("host-sync",))
    assert [f.line for f in res.findings] == [4, 8]
    assert len(res.suppressed) == 2


def test_comment_pragma_covers_header_not_compound_body():
    """A pragma above an `if` covers the header only — a violation
    added inside the block must NOT ride the header's annotation."""
    src = (
        "import numpy as np\n"
        "def f(x, flag):\n"
        "    # tpu-lint: allow(host-sync): header classified\n"
        "    if np.asarray(x).sum() > 0:\n"
        "        y = np.asarray(x)\n"          # inside the block: flagged
        "        return y.item()\n"            # flagged
        "    return flag\n")
    res = _lint(_files(mod=src), rules=("host-sync",))
    assert sorted(f.line for f in res.findings) == [5, 6]
    assert len(res.suppressed) == 1


def test_callgraph_resolves_module_aliases():
    """`from paddle_tpu.x import mod as alias; alias.f(...)` and
    `from x import f as g; g(...)` both feed jit-reachability."""
    helper = ("def work(x):\n"
              "    return float(x.sum())\n"    # flagged iff reachable
              "def spare(x):\n"
              "    return float(x.sum())\n")   # never reached
    entry = ("import jax\n"
             "from paddle_tpu import helpers as h\n"
             "from paddle_tpu.helpers import work as aliased_work\n"
             "@jax.jit\n"
             "def entry(x):\n"
             "    return h.work(x) + aliased_work(x)\n")
    res = _lint(_files(helpers=helper, mod=entry),
                rules=("host-sync",))
    assert [(f.path, f.line) for f in res.findings] == [
        ("paddle_tpu/helpers.py", 2)]


def test_cli_update_baseline_refuses_filters():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis",
         "--update-baseline", "--paths", "paddle_tpu/serving"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "cannot be combined" in proc.stderr


def test_file_level_suppression():
    src = (
        "# tpu-lint: allow-file(host-sync): host pipeline by contract\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    return np.asarray(x).item()\n")
    res = _lint(_files(mod=src), rules=("host-sync",))
    assert res.ok and len(res.suppressed) == 2


def test_baseline_pins_by_code_not_line_number():
    src_v1 = ("import numpy as np\n"
              "def f(x):\n"
              "    return np.asarray(x)\n")
    from collections import Counter
    res1 = _lint(_files(mod=src_v1), rules=("host-sync",))
    assert len(res1.findings) == 1
    # pin the finding, then shift it down two lines: still baselined
    pin = Counter(f.key() for f in res1.findings)
    src_v2 = ("import numpy as np\n# moved\n# down\n"
              "def f(x):\n"
              "    return np.asarray(x)\n")
    res2 = _lint(_files(mod=src_v2), rules=("host-sync",))
    new, baselined, stale = baseline_mod.apply(res2.findings, pin)
    assert not new and len(baselined) == 1 and not stale
    # but a NEW identical site on top of the pinned one fails
    src_v3 = src_v2 + "def g(x):\n    return np.asarray(x)\n"
    res3 = _lint(_files(mod=src_v3), rules=("host-sync",))
    new, baselined, _ = baseline_mod.apply(res3.findings, pin)
    assert len(new) == 1 and len(baselined) == 1


# --------------------------------------------------- whole-package gate

def test_package_lints_clean_under_budget():
    """The tier-1 gate: zero unsuppressed non-baselined findings over
    paddle_tpu/, no stale baseline entries (the pin matches the tree
    exactly), in well under the 20 s CLI budget."""
    t0 = time.perf_counter()
    res = lint.run_lint(ROOT)
    wall = time.perf_counter() - t0
    assert res.ok, "NEW lint findings:\n" + "\n".join(
        map(repr, res.findings))
    assert not res.stale_baseline, (
        "stale baseline entries (fixed sites still pinned — run "
        "--update-baseline): " + repr(res.stale_baseline))
    assert wall < 20.0, f"lint took {wall:.1f}s (budget 20s)"


def test_burned_down_dirs_have_no_baseline_entries():
    """The hot-path dirs are at ZERO baseline debt: every host-sync
    site in serving/, ops/ and inference/ is either fixed or carries a
    classified `# tpu-lint: allow(...)` annotation — and the mesh/
    donation rules hold parallel/ (plus those dirs) at zero debt too:
    a new unregistered axis, rotten PartitionSpec or undonated RMW
    carry in the hybrid-parallel layer fails --check outright."""
    with open(baseline_mod.baseline_path(ROOT)) as fh:
        entries = json.load(fh)["findings"]
    hot = [e for e in entries if e["path"].startswith(
        ("paddle_tpu/serving/", "paddle_tpu/ops/",
         "paddle_tpu/inference/"))]
    assert not hot, hot
    mesh_rules = {"collective-axis", "pspec-axis", "donation"}
    mesh_debt = [e for e in entries if e["rule"] in mesh_rules
                 and e["path"].startswith(
                     ("paddle_tpu/parallel/", "paddle_tpu/serving/",
                      "paddle_tpu/ops/", "paddle_tpu/inference/"))]
    assert not mesh_debt, mesh_debt
    res = lint.run_lint(ROOT, rules=tuple(mesh_rules),
                        paths=["paddle_tpu/parallel", "paddle_tpu/ops",
                               "paddle_tpu/inference"])
    assert res.ok, res.findings


@pytest.mark.slow
def test_update_baseline_deterministic_and_committed():
    """Two regenerations are byte-identical, and match the checked-in
    baseline.json — the pin cannot drift silently."""
    r1 = lint.run_lint(ROOT, respect_baseline=False)
    r2 = lint.run_lint(ROOT, respect_baseline=False)
    doc1 = baseline_mod.render(r1.findings)
    doc2 = baseline_mod.render(r2.findings)
    assert doc1 == doc2
    with open(baseline_mod.baseline_path(ROOT), encoding="utf-8") as fh:
        committed = fh.read()
    assert doc1 == committed, (
        "baseline.json does not match the tree — run "
        "`python -m paddle_tpu.analysis --update-baseline`")


def test_cli_check_passes():
    """`python -m paddle_tpu.analysis --check` — the exact tier-1
    command — exits 0 on the current tree."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--check"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    wall = time.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    assert wall < 20.0, f"CLI took {wall:.1f}s (budget 20s)"


def test_check_fails_on_new_violation(tmp_path):
    """A NEW host-sync site (not annotated, not pinned) fails the
    check. Runs in-process against the real package sources plus an
    injected canary module — the tree on disk is never touched (a
    killed test must not leave a violation in the source tree)."""
    files = lint.package_sources(ROOT)
    canary = "paddle_tpu/_lint_canary.py"
    src = ("import numpy as np\n"
           "def leak(x):\n"
           "    return np.asarray(x).item()\n")
    files[canary] = SourceFile(canary, src, ast.parse(src))
    res = lint.run_lint(ROOT, files=files)
    assert not res.ok
    assert {f.path for f in res.findings} == {canary}, res.findings
    assert len(res.findings) == 2       # np.asarray + .item()


# ------------------------------------------------------- runtime guards

def test_count_compiles_and_no_recompile():
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    f = jax.jit(lambda a: a * 2 + 1)
    # arrays built OUTSIDE the counted regions: an eager arange can
    # itself compile a tiny iota program the first time
    x7, x9, x3 = jnp.arange(7), jnp.arange(9), jnp.arange(3)
    with rt.count_compiles() as c:
        f(x7)
    assert c.count == 1
    with rt.count_compiles() as c:
        f(x7)                               # cache hit
    assert c.count == 0
    with rt.no_recompile(what="warm region"):
        f(x7)
    with pytest.raises(rt.RecompileError, match="cold region"):
        with rt.no_recompile(what="cold region"):
            f(x9)                           # new shape -> compile
    # the expected-compile form
    g = jax.jit(lambda a: a - 1)
    with rt.no_recompile(allow=1):
        g(x3)


def test_no_transfer_blocks_h2d():
    f = jax.jit(lambda a: a + 1)
    host = np.ones(5, np.float32)
    f(host)                                 # warm (uploads)
    dev = jnp.ones(5, jnp.float32)
    f(dev)
    with rt.no_transfer(what="device-resident region"):
        f(dev)                              # fine: no upload
    with pytest.raises(rt.TransferError):
        with rt.no_transfer(what="leaky region"):
            f(host)                         # jit arg placement = H2D
    with pytest.raises(rt.TransferError):
        with rt.no_transfer():
            jnp.asarray(host)               # explicit upload


def test_donation_report_first_principles():
    """donation_report proves input->output aliasing: a donated RMW
    carry shows every leaf wired into the compiled module's
    input_output_alias table; the undonated twin shows the copy."""
    def impl(state, carry, n):
        kv = carry[1]
        return carry[0] + 1.0, kv.at[0].set(state.sum())

    args = (jnp.ones(3), (jnp.zeros(2), jnp.zeros((2, 4))), 4)
    j = jax.jit(impl, static_argnums=(2,), donate_argnums=(1,))
    rep = rt.donation_report(j, *args, static_argnums=(2,),
                             what="donated carry")
    assert rep.donated_argnums == [1]
    assert rep.args[1] == {"leaves": 2, "donated": 2, "aliased": 2}
    rep.expect_aliased(1)
    with pytest.raises(rt.DonationError, match="argnum 0"):
        rep.expect_aliased(0)
    # the undonated twin: same program, no aliasing — the per-dispatch
    # copy donation exists to remove, made visible
    j2 = jax.jit(impl, static_argnums=(2,))
    rep2 = rt.donation_report(j2, *args, static_argnums=(2,),
                              what="undonated carry")
    assert rep2.donated_argnums == [] and rep2.aliased_argnums == []


# ------------------------------- the repo's invariants, as properties

def _tiny_llama(L=2):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=L,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return m


def test_serving_steady_state_zero_h2d_zero_recompiles():
    """THE serving claim, enforced: after warmup, an event-free
    ``step()`` performs no host->device transfer and compiles nothing.
    block_tokens=32 with a 12+16-token request never crosses a block
    boundary after prefill, so every post-warmup step is steady."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(0)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=128, sanitize=True) as eng:
        for _ in range(2):
            eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                       max_new_tokens=16))
        eng.step()          # admission: prefill + first dispatch compile
        guarded = 0
        while eng.active_slots and guarded < 8:
            # external guard on the WHOLE tick (engine-internal
            # sanitize mode additionally wraps just the dispatch)
            with rt.no_transfer(what="steady serving tick"), \
                    rt.count_compiles() as c:
                eng.step()
            assert c.count == 0
            guarded += 1
        assert guarded == 8
        assert eng.stats["sanitized_steps"] >= guarded
        eng.drain()


def test_offload_idle_steady_state_zero_h2d_zero_recompiles():
    """Arming the hierarchical KV tier must cost NOTHING while idle:
    with ``offload=True`` and no preemption in flight, steady ticks run
    the exact same program as the unarmed engine — 0 H2D transfers, 0
    compiles (the swap hooks are gated on parked work existing)."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(0)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=128, sanitize=True,
                               offload=True) as eng:
        for _ in range(2):
            eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                       max_new_tokens=16))
        eng.step()          # admission: prefill + first dispatch compile
        guarded = 0
        while eng.active_slots and guarded < 8:
            with rt.no_transfer(what="steady offload-idle tick"), \
                    rt.count_compiles() as c:
                eng.step()
            assert c.count == 0
            guarded += 1
        assert guarded == 8
        assert eng.stats["swap_outs"] == 0
        eng.drain()


def test_join_leave_compile_set_is_exactly_prefill_shapes():
    """Join/leave churn compiles exactly the expected programs: the
    first admission pays one prefill program + one step program; a
    same-shape join pays ZERO compiles; a new prompt-shape bucket pays
    exactly ONE (its prefill program)."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(1)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=128,
                               prefix_caching=False) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=16)
        assert c.count == 2, c.events       # prefill(s_pad=32) + step fn
        # same shape bucket (any prompt len in (0, 32]): zero compiles
        eng.submit(serving.Request(rng.randint(3, 500, (20,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=16)
        assert c.count == 0, c.events
        # new shape bucket (s_pad=64): exactly the one prefill program
        eng.submit(serving.Request(rng.randint(3, 500, (40,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=16)
        assert c.count == 1, c.events


@pytest.mark.slow
def test_chunked_compile_set_is_exactly_chunk_buckets():
    """The one-program tick keeps the compile set small and EXACTLY
    pinned: each chunk tick dispatches ONE fused program (chunk half +
    decode half — no separate chunk+step programs), keyed by the chunk
    bucket (kind, cursor, rows, feed bucket, chunk size). First
    admission pays one fused-tick program per chunk bucket plus the
    step program (chunkless decode ticks); any prompt whose buckets
    are covered pays ZERO compiles; a longer prompt pays exactly its
    NEW buckets (the resident carry's feed bucket rides the key, so a
    new feed bucket recompiles its whole chain). Steady chunked
    decode ticks AND steady mid-prefill fused ticks stay 0 H2D + 0
    compiles under the same guards as the monolithic engine (the
    sanitize=True invariant — every chunk input is device-resident
    from admission)."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(2)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=256, chunk_tokens=32,
                               prefix_caching=False,
                               sanitize=True) as eng:
        # 70 tokens @ chunk 32 (feed bucket 96) -> fused ticks mid(0)
        # + mid(32) + last(64), + the chunkless step fn
        eng.submit(serving.Request(rng.randint(3, 500, (70,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=60)
        assert c.count == 4, c.events
        # 80 and 90 tokens land in the SAME buckets: zero compiles
        for n in (80, 90):
            eng.submit(serving.Request(rng.randint(3, 500, (n,)),
                                       max_new_tokens=4))
            with rt.count_compiles() as c:
                eng.drain(max_steps=60)
            assert c.count == 0, (n, c.events)
        # 100 tokens -> feed bucket 128: exactly its four fused-tick
        # buckets mid(0)+mid(32)+mid(64)+last(96) (the resident carry
        # is shaped by the feed bucket, so none are shared with 96)
        eng.submit(serving.Request(rng.randint(3, 500, (100,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=60)
        assert c.count == 4, c.events
        # steady-state chunked decode ticks: 0 H2D + 0 compiles
        eng.submit(serving.Request(rng.randint(3, 500, (40,)),
                                   max_new_tokens=24))
        eng.step()                  # admit + chunk 0 (+2 compiles:
        eng.step()                  # bucket 64) ... last chunk + adopt
        eng.step()                  # first steady re-dispatch
        guarded = 0
        while eng.active_slots and guarded < 6:
            with rt.no_transfer(what="steady chunked tick"), \
                    rt.count_compiles() as c:
                eng.step()
            assert c.count == 0
            guarded += 1
        assert guarded == 6
        assert eng.stats["sanitized_steps"] >= guarded
        # steady FUSED ticks: a covered-bucket prompt admitted while
        # the 40-token slot still decodes — after the admission tick
        # (group creation = a join event), every mid-prefill chunk
        # tick re-dispatches warm fused programs with NO H2D upload
        assert eng.active_slots == 1
        eng.submit(serving.Request(rng.randint(3, 500, (70,)),
                                   max_new_tokens=4))
        eng.step()                  # admit + fused chunk 0 (dirty)
        fused_guarded = 0
        while any(s is not None and s.prefilling for s in eng._slots):
            with rt.no_transfer(what="steady fused chunk tick"), \
                    rt.count_compiles() as c:
                eng.step()          # fused mid/last chunk tick
            assert c.count == 0, c.events
            fused_guarded += 1
        assert fused_guarded >= 2   # mid(32) + last(64) at least
        eng.drain()


def test_speculative_compile_set_and_steady_tick():
    """Speculative decoding's compile set is EXACTLY pinned: arming the
    n-gram proposer costs ONE verify program on top of the prefill
    shapes (the proposer runs inside it — no separate program); the
    draft proposer adds exactly its prefill + round programs. A
    covered-shape join still pays ZERO compiles, and steady speculative
    ticks hold the sanitize invariant: 0 H2D + 0 compiles."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(3)
    with serving.ServingEngine(
            m, max_slots=2, block_tokens=32, max_seq_len=128,
            prefix_caching=False, sanitize=True,
            speculate=serving.SpecConfig(k=2)) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=30)
        assert c.count == 2, c.events   # prefill(s_pad=32) + verify
        # covered shape bucket: zero compiles, proposals re-prime on
        # device without any new program
        eng.submit(serving.Request(rng.randint(3, 500, (20,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=30)
        assert c.count == 0, c.events
        # steady speculative ticks: 0 H2D + 0 compiles
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=16))
        eng.step()          # admission tick (dirty upload)
        eng.step()          # first steady re-dispatch
        guarded = 0
        while eng.active_slots and guarded < 6:
            with rt.no_transfer(what="steady speculative tick"), \
                    rt.count_compiles() as c:
                eng.step()
            assert c.count == 0, c.events
            guarded += 1
        assert guarded == 6
        assert eng.stats["sanitized_steps"] >= guarded
        eng.drain()
    # draft proposer: + draft prefill (per feed shape) + draft round
    draft = _tiny_llama()
    with serving.ServingEngine(
            m, max_slots=2, block_tokens=32, max_seq_len=128,
            prefix_caching=False,
            speculate=serving.SpecConfig(
                k=2, proposer="draft", draft_model=draft)) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=30)
        # prefill + draft_prefill(s_pad=32) + draft round + verify
        assert c.count == 4, c.events
        eng.submit(serving.Request(rng.randint(3, 500, (20,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=30)
        assert c.count == 0, c.events


def test_router_steady_state_zero_h2d_zero_recompiles():
    """The replicated tier inherits the engine's steady-state claim:
    after warmup, an event-free router tick — heartbeats, health
    bookkeeping and one fused dispatch per replica — performs no
    host->device transfer and compiles nothing, with every replica
    running ``sanitize=True``."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(4)
    with serving.Router(m, replicas=2, max_slots=2, block_tokens=32,
                        max_seq_len=128, sanitize=True) as router:
        # short prompts (no full affinity block) spread least-loaded
        # across both replicas; each replica's prefill + step programs
        # compile during these warmup ticks. All four are placed while
        # both replicas are cold, so only the queue depths decide: an
        # estimated TTFT from two ticks of a loaded machine now and then
        # put three on one replica, and the loop below never ran
        for i in range(4):
            router.submit(serving.Request(rng.randint(3, 500, (12,)),
                                          max_new_tokens=24, seed=i))
        for _ in range(4):
            router.step()
        assert all(e.active_slots
                   for e in (router.replica_engine(0),
                             router.replica_engine(1)))
        router.step()           # first steady re-dispatch per replica
        guarded = 0
        while router.active_slots == 4 and guarded < 6:
            with rt.no_transfer(what="steady router tick"), \
                    rt.count_compiles() as c:
                router.step()
            assert c.count == 0, c.events
            guarded += 1
        assert guarded == 6
        assert router.stats["sanitized_steps"] >= 2 * guarded
        router.drain(max_steps=200)


def _mp2_mesh():
    from paddle_tpu.parallel.topology import build_mesh
    return build_mesh({"mp": 2}, devices=jax.devices()[:2])


def test_sharded_steady_state_zero_h2d_zero_recompiles():
    """The steady-state claim survives tensor parallelism: an mp=2
    engine's event-free ``step()`` — per-shard attention, one tiled
    all_gather at the o-proj boundary, replicated sampling — performs
    no host->device transfer and compiles nothing after warmup. Every
    dispatch input is mesh-committed at admission (``_up``/constructor
    placement), so sharding adds collectives, never uploads."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(0)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=128, sanitize=True,
                               mesh=_mp2_mesh()) as eng:
        for _ in range(2):
            eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                       max_new_tokens=16))
        eng.step()          # admission: prefill + first dispatch compile
        guarded = 0
        while eng.active_slots and guarded < 8:
            with rt.no_transfer(what="steady sharded tick"), \
                    rt.count_compiles() as c:
                eng.step()
            assert c.count == 0, c.events
            guarded += 1
        assert guarded == 8
        assert eng.stats["sanitized_steps"] >= guarded
        eng.drain()


def test_sharded_join_leave_compile_set_matches_mp1_pin():
    """The mp=2 engine keeps the EXACT compile-set pins of the mp=1
    engine (test_join_leave_compile_set_is_exactly_prefill_shapes):
    first admission = prefill + step program, a covered shape bucket =
    ZERO compiles, a new bucket = exactly its one prefill program.
    shard_map wrapping must not fragment the program set."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(1)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=128, prefix_caching=False,
                               mesh=_mp2_mesh()) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=16)
        assert c.count == 2, c.events       # prefill(s_pad=32) + step fn
        eng.submit(serving.Request(rng.randint(3, 500, (20,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=16)
        assert c.count == 0, c.events
        eng.submit(serving.Request(rng.randint(3, 500, (40,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=16)
        assert c.count == 1, c.events


def test_donation_report_sharded_pool_step():
    """Donation survives sharding: the mp=2 pool-step program aliases
    its (per-shard) KV pool buffer in place — the report computes each
    donated leaf's LOCAL shard shape for the alias-table match, so 'the
    sharded tick aliases the pool away' is a checked property on the
    real mesh-committed program, exactly like the mp=1 pin."""
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(7)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=128,
                               mesh=_mp2_mesh()) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=6))
        for _ in range(3):
            eng.step()
        assert eng._step_fn is not None
        rep = rt.donation_report(eng._step_fn, eng.kv_pool, *eng._dev,
                                 what="sharded pool step")
        # lowered-call positions: state=0, stacked=1, pool=2
        assert rep.donated_argnums == [2]
        rep.expect_aliased(2)
        eng.drain(max_steps=100)


@pytest.mark.slow
def test_donation_report_serving_pool_step_and_chunk_programs():
    """THE donation pins: the serving pool-step program aliases its KV
    pool input into the pool output (every leaf); the bf16 fused chunk
    tick aliases the pool (its carry-free mid chunks gather the
    processed prefix FROM the pool); and the int8 fused mid-chunk tick
    aliases the pool AND the resident bf16 KV carry in-place — 'the
    TPU path aliases it away' as a checked property instead of a prose
    caveat (SCALE.md §Donation aliasing). The engine program handles
    carry .jitted/.bound so the report lowers the REAL programs with
    their bound state."""
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(7)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=256, chunk_tokens=32,
                               prefix_caching=False) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (70,)),
                                   max_new_tokens=6))
        for _ in range(5):          # chunks + adopt + first decode
            eng.step()
        assert eng._step_fn is not None
        rep = rt.donation_report(eng._step_fn, eng.kv_pool, *eng._dev,
                                 what="serving pool step")
        # argnums are lowered-call positions: state=0, stacked=1, pool=2
        assert rep.donated_argnums == [2]
        rep.expect_aliased(2)
        assert rep.args[2]["leaves"] == 1
        # bf16 fused mid tick: ("tick", kind, int8, start, n, C_pad,
        # CT, R, K) — carry-free (pool gather), pool donated + aliased
        tick_fn = eng._jit_cache.get(
            ("tick", "mid", False, 32, 1, 96, 32, 0, 0))
        assert tick_fn is not None, list(eng._jit_cache)
        ids = jnp.zeros((1, 96), jnp.int32)
        bids = jnp.zeros((1, 3), jnp.int32)
        crep = rt.donation_report(tick_fn, eng.kv_pool, ids, bids,
                                  *eng._dev,
                                  what="fused mid-chunk tick (bf16)")
        assert crep.donated_argnums == [2], crep
        crep.expect_aliased(2)
        eng.drain(max_steps=200)
    # int8: the resident carry rides the fused tick as a donated
    # in-place buffer — pool (2) AND carry (3) aliased in the compiled
    # module (the staging-buffer round trip BENCH_r06 caveated, gone)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=256, chunk_tokens=32,
                               cache_dtype=jnp.int8,
                               prefix_caching=False) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (70,)),
                                   max_new_tokens=6))
        for _ in range(5):
            eng.step()
        tick_fn = eng._jit_cache.get(
            ("tick", "mid", True, 32, 1, 96, 32, 0, 0))
        assert tick_fn is not None, list(eng._jit_cache)
        L, dkv2 = eng._num_layers, eng._cache_lanes
        carry = jnp.zeros((L, 1, 96, dkv2), jnp.bfloat16)
        ids = jnp.zeros((1, 96), jnp.int32)
        bids = jnp.zeros((1, 3), jnp.int32)
        crep = rt.donation_report(tick_fn, eng.kv_pool, carry, ids,
                                  bids, *eng._dev,
                                  what="fused mid-chunk tick (int8)")
        assert crep.donated_argnums == [2, 3], crep
        crep.expect_aliased(2, 3)
        eng.drain(max_steps=200)


@pytest.mark.slow
def test_chunk_autotune_transitions_compile_exactly_new_buckets():
    """The chunk autotuner re-evaluates ONLY at admission boundaries,
    so the compile set stays pinnable: a stable pick reuses its
    fused-tick programs (0 compiles), and a bucket transition compiles
    exactly the NEW bucket's programs — here one, because the larger
    chunk covers the prompt in a single fused tick."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(11)
    with serving.ServingEngine(m, max_slots=2, block_tokens=32,
                               max_seq_len=256, chunk_tokens=32,
                               chunk_autotune=True, slo_tpot_s=0.04,
                               prefix_caching=False) as eng:
        # cold: no per-token EWMA -> the configured 32-token bucket.
        # 60 tokens @ 32 -> mid(0) + last(32) fused ticks + step fn
        p = rng.randint(3, 500, (60,))
        eng.submit(serving.Request(p, max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=60)
        assert c.count == 3, c.events
        assert eng._chunk_choice == 32
        # warm but stable: pred(32)=0.032 fits 0.04, pred(64)=0.064
        # does not -> the pick holds and the covered bucket compiles
        # nothing
        eng._ewma_prefill_tok.value = 1e-3
        eng._ewma_step.value = 0.0
        eng.submit(serving.Request(rng.randint(3, 500, (60,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=60)
        assert c.count == 0, c.events
        assert eng._chunk_choice == 32
        # faster EWMA -> pred(64)=0.032 fits, pred(128)=0.064 doesn't:
        # the tuner steps up one bucket, which covers the 60-token
        # prompt in ONE fused last(0) tick = exactly one new compile
        eng._ewma_prefill_tok.value = 5e-4
        eng._ewma_step.value = 0.0
        eng.submit(serving.Request(rng.randint(3, 500, (60,)),
                                   max_new_tokens=4))
        with rt.count_compiles() as c:
            eng.drain(max_steps=60)
        assert c.count == 1, c.events
        assert eng._chunk_choice == 64
        from paddle_tpu.observability import registry
        assert registry().gauge("serving.chunk_autotune").value == 64


@pytest.mark.slow
def test_donation_report_spec_verify_history():
    """The speculative verify program donates BOTH RMW'd inputs: the
    KV pool and the ngram history buffer — the donation lint rule's
    first real catch (undonated, the history cost one full
    (max_slots, max_seq_len) copy per speculative tick)."""
    from paddle_tpu import serving
    m = _tiny_llama()
    rng = np.random.RandomState(8)
    with serving.ServingEngine(
            m, max_slots=2, block_tokens=32, max_seq_len=128,
            prefix_caching=False,
            speculate=serving.SpecConfig(k=2)) as eng:
        eng.submit(serving.Request(rng.randint(3, 500, (12,)),
                                   max_new_tokens=8))
        steps = 0
        while not eng._verify_fns and steps < 10:
            eng.step()
            steps += 1
        assert eng._verify_fns, "verify program never built"
        K = next(iter(eng._verify_fns))
        vfn = eng._verify_fns[K]
        props, nprop = eng._dev_prop
        args = (eng.kv_pool, *eng._dev, props, nprop, eng._dev_cap,
                eng._dev_hist)
        rep = rt.donation_report(vfn, *args, what="spec verify step")
        # state=0, stacked=1, pool=2, ..., history=12 (+2 bound)
        assert rep.donated_argnums == [2, 12], rep
        rep.expect_aliased(2, 12)
        eng.drain(max_steps=200)


def test_donation_report_inference_chunk_carry():
    """The traced chunk-decode program's KV carry is donated and fully
    aliased (carry_donate_argnums), on every backend."""
    from paddle_tpu.inference import generate
    m = _tiny_llama()
    state = m.state_dict(include_buffers=False)
    rng = np.random.RandomState(9)
    ids = jnp.asarray(rng.randint(3, 500, (2, 16)))
    seeds = jnp.asarray(np.asarray([5, 6], np.uint32))
    generate(m, ids, max_new_tokens=8, state=state, deadline_s=60.0,
             request_seeds=seeds)
    traced = [v for k, v in m._generate_jit_cache.items()
              if isinstance(k, tuple) and k and k[-1] == "traced"]
    assert traced, "traced chunk programs not built"
    pf, dc = traced[0]
    carry, aux = pf(state, ids, seeds)
    rep = rt.donation_report(dc, state, carry, aux, 1, 4,
                             static_argnums=(4,),
                             what="chunk-carry decode program")
    assert rep.donated_argnums == [1]
    rep.expect_aliased(1)


@pytest.mark.parametrize("cache_dtype", ["bf16", "int8"])
def test_warm_generate_zero_transfers_zero_recompiles(cache_dtype):
    """A warm ``generate`` with device-resident inputs re-dispatches
    with zero H2D transfers and zero compiles — and an armed-but-
    never-firing FaultPlan (the disarmed hot path) adds none and keeps
    tokens bit-identical."""
    if not rt.compile_events_supported():
        pytest.skip("jax.monitoring compile events unavailable")
    from paddle_tpu.inference import generate
    from paddle_tpu.resilience import Fault, faults
    m = _tiny_llama()
    dt = jnp.int8 if cache_dtype == "int8" else jnp.bfloat16
    state = m.state_dict(include_buffers=False)
    rng = np.random.RandomState(2)
    # device-resident inputs: ids AND seeds (the default-seed path
    # builds its stream array eagerly — a legitimate per-REQUEST
    # upload, but this test pins the device-resident case at zero)
    ids = jnp.asarray(rng.randint(3, 500, (2, 16)))
    seeds = jnp.asarray(np.asarray([5, 6], np.uint32))
    out_warm = generate(m, ids, max_new_tokens=8, state=state,
                        cache_dtype=dt, request_seeds=seeds)
    with faults.plan(Fault("decode.dispatch", at=10 ** 9)):
        with rt.no_transfer(what="warm generate"), \
                rt.no_recompile(what="warm generate"):
            out_guard = generate(m, ids, max_new_tokens=8, state=state,
                                 cache_dtype=dt, request_seeds=seeds)
    np.testing.assert_array_equal(np.asarray(out_warm),
                                  np.asarray(out_guard))
