"""Runtime dispatch sanitizer: transfer and recompile guards.

The static pass (analysis/lint.py) sees where code *could* sync; this
module enforces what a region *actually does* at runtime:

* :func:`no_transfer` — a context in which implicit AND explicit
  host->device transfers raise (``jax.transfer_guard_host_to_device
  ("disallow_explicit")``): the enforcement form of the serving
  engine's "no steady-state H2D" claim. D2H is allowed by default —
  the one sampled-token pull per step IS the completion fence — and
  guardable with ``d2h=True``. (On the CPU backend D2H is zero-copy
  and the guard never fires; H2D fires at jit argument placement and
  ``jnp.asarray`` alike, so the invariant is testable without a TPU.)
* :func:`no_recompile` / :func:`count_compiles` — XLA backend-compile
  events captured via ``jax.monitoring`` (one
  ``/jax/core/compile/backend_compile_duration`` event per real
  compile; jit-cache hits emit nothing): a region that claims "warm"
  must compile nothing.
* :func:`sanitize` — both at once; what ``ServingEngine(sanitize=True)``
  wraps steady-state dispatches in and the benches arm under
  ``--sanitize``.
* :func:`snapshot_roundtrip` — the STATE-protocol guard (the runtime
  half of the ``snapshot-coverage`` lint rule): snapshot → restore →
  snapshot must be byte-identical in canonical form, or a serialized
  field is rotting. ``ServingEngine(sanitize="roundtrip"|"all")`` runs
  it on every ``save_snapshot``; ``chaos_bench --roundtrip_every N``
  exercises it mid-soak.

Guards compose with ``with`` nesting and are thread-visible the way
jax's own context managers are; the compile listener is registered
once, process-wide, and costs one list-append per *compile* (never on
a cache-hit dispatch), so leaving it registered is free on the hot
path.
"""

import json
import re
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

import jax

__all__ = ["CompileCounter", "DonationError", "DonationReport",
           "RecompileError", "SnapshotDriftError", "TransferError",
           "canonical_snapshot", "canonical_snapshot_bytes",
           "compare_snapshots", "count_compiles", "donation_report",
           "no_recompile", "no_transfer", "sanitize",
           "snapshot_roundtrip", "compile_events_supported"]

#: the monitoring event one real XLA backend compile emits;
#: trace-only events (jaxpr_trace) deliberately NOT counted — a
#: retrace that hits the compile cache costs µs, a backend compile
#: costs seconds
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileError(RuntimeError):
    """A ``no_recompile`` region compiled."""


class TransferError(RuntimeError):
    """Raised by :func:`no_transfer` wrapping for a uniform excepting
    type; the underlying jax error is chained as ``__cause__``."""


class CompileCounter:
    """Collects backend-compile events while registered as active."""

    __slots__ = ("events",)

    def __init__(self):
        self.events: List[str] = []

    @property
    def count(self) -> int:
        return len(self.events)


_active_counters: List[CompileCounter] = []
_listener_lock = threading.Lock()
_listener_state = {"registered": False, "supported": None}


def _on_event(name: str, dur: float, **kwargs):
    if name == BACKEND_COMPILE_EVENT and _active_counters:
        for c in list(_active_counters):
            c.events.append(name)


def _ensure_listener() -> bool:
    with _listener_lock:
        if not _listener_state["registered"]:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_event)
            _listener_state["supported"] = True
            _listener_state["registered"] = True
    return bool(_listener_state["supported"])


def compile_events_supported() -> bool:
    """Whether this jax exposes the monitoring seam the compile guards
    need."""
    return _ensure_listener()


@contextmanager
def count_compiles():
    """``with count_compiles() as c: ...; c.count`` — the number of XLA
    backend compiles the block performed."""
    _ensure_listener()
    c = CompileCounter()
    _active_counters.append(c)
    try:
        yield c
    finally:
        _active_counters.remove(c)


@contextmanager
def no_recompile(allow: int = 0, what: str = "region"):
    """Raise :class:`RecompileError` if the block backend-compiles more
    than ``allow`` programs. The expected-compile form (``allow=n``)
    pins e.g. "a join at a NEW prompt shape compiles exactly one
    prefill program"."""
    with count_compiles() as c:
        yield c
    if c.count > allow:
        raise RecompileError(
            f"{what} compiled {c.count} program(s) "
            f"(allowed {allow}) — a warm hot path must not recompile; "
            f"shapes or static arguments are churning")


@contextmanager
def no_transfer(h2d: bool = True, d2h: bool = False, d2d: bool = False,
                what: str = "region"):
    """Disallow device transfers inside the block (explicit AND
    implicit — a ``jnp.asarray`` upload and a jit-argument placement
    both count). Violations raise jax's ``XlaRuntimeError`` at the
    transfer site, chained into :class:`TransferError` with the region
    name."""
    ctxs = []
    if h2d:
        ctxs.append(jax.transfer_guard_host_to_device("disallow_explicit"))
    if d2h:
        ctxs.append(jax.transfer_guard_device_to_host("disallow_explicit"))
    if d2d:
        ctxs.append(
            jax.transfer_guard_device_to_device("disallow_explicit"))
    try:
        for c in ctxs:
            c.__enter__()
        try:
            yield
        finally:
            for c in reversed(ctxs):
                c.__exit__(None, None, None)
    except Exception as e:
        if "Disallowed" in str(e) and "transfer" in str(e):
            raise TransferError(
                f"{what} performed a guarded device transfer: {e}") from e
        raise


@contextmanager
def sanitize(what: str = "region", h2d: bool = True, d2h: bool = False,
             allow_compiles: int = 0):
    """The combined guard: no H2D transfers (optionally D2H) and no
    backend compiles. The ``ServingEngine(sanitize=True)`` steady-state
    contract and the benches' ``--sanitize`` wrap."""
    with no_transfer(h2d=h2d, d2h=d2h, what=what), \
            no_recompile(allow=allow_compiles, what=what):
        yield


# ----------------------------------------------------- donation report

class DonationError(RuntimeError):
    """A ``DonationReport.expect_aliased`` pin failed: an input the
    program was expected to alias into an output is being copied."""


#: one `{out...}: (param, {...}, kind)` entry in the compiled HLO
#: module header's input_output_alias table
_ALIAS_ENTRY = re.compile(
    r"\{[\d,\s]*\}:\s*\((\d+),\s*\{[^}]*\},\s*([a-z-]+)\)")


def _alias_table(hlo_text: str) -> str:
    """The brace-balanced body of ``input_output_alias={...}`` in a
    compiled module header ('' when the program aliases nothing)."""
    key = "input_output_alias={"
    i = hlo_text.find(key)
    if i < 0:
        return ""
    depth, j = 1, i + len(key)
    while j < len(hlo_text) and depth:
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
        j += 1
    return hlo_text[i + len(key):j - 1]


def _entry_param_types(hlo_text: str) -> List[str]:
    """Layout-stripped parameter type strings ('bf16[2,34,32,128]')
    from the compiled module's entry_computation_layout, in parameter
    order. The OPTIMIZED module's parameter numbering — XLA dead-codes
    unused inputs and renumbers — so alias entries must be matched to
    jax-level arguments by type, not by flat position."""
    m = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo_text)
    if not m:
        return []
    out = []
    for tok in m.group(1).split(", "):
        tok = re.sub(r"/\*[^*]*\*/", "", tok)       # /*index=N*/
        out.append(re.sub(r"\{[^}]*\}", "", tok).strip())
    return out


#: numpy dtype name -> HLO primitive-type name
_HLO_DTYPES = {"float32": "f32", "float64": "f64", "float16": "f16",
               "bfloat16": "bf16", "int8": "s8", "int16": "s16",
               "int32": "s32", "int64": "s64", "uint8": "u8",
               "uint16": "u16", "uint32": "u32", "uint64": "u64",
               "bool": "pred", "complex64": "c64", "complex128": "c128"}


def _aval_type(aval) -> str:
    dt = _HLO_DTYPES.get(str(aval.dtype), str(aval.dtype))
    return f"{dt}[{','.join(str(d) for d in aval.shape)}]"


class DonationReport:
    """What ONE lowered+compiled program actually does with its
    inputs: per python-argnum leaf counts, how many leaves the caller
    DECLARED donated (``jax.jit(..., donate_argnums=)``), and how many
    XLA actually wired into the input_output_alias table (with the
    alias kind — ``may-alias``/``must-alias``). The static half of the
    donation story is the ``donation`` lint rule; this is the runtime
    proof that "the TPU path aliases the carry away" — or the evidence
    that a backend quietly copies instead."""

    __slots__ = ("what", "args", "alias_kinds")

    def __init__(self, what: str):
        self.what = what
        #: argnum -> {"leaves", "donated", "aliased"}
        self.args: Dict[int, Dict] = {}
        self.alias_kinds: List[str] = []

    @property
    def donated_argnums(self) -> List[int]:
        return sorted(a for a, d in self.args.items() if d["donated"])

    @property
    def aliased_argnums(self) -> List[int]:
        return sorted(a for a, d in self.args.items() if d["aliased"])

    def fully_aliased(self, argnum: int) -> bool:
        d = self.args.get(argnum)
        return bool(d) and d["aliased"] == d["leaves"]

    def expect_aliased(self, *argnums: int):
        """Assert every listed argnum has ALL its leaves aliased into
        outputs — the test-pin form. Returns self for chaining."""
        for a in argnums:
            if not self.fully_aliased(a):
                d = self.args.get(a, {"leaves": 0, "donated": 0,
                                      "aliased": 0})
                raise DonationError(
                    f"{self.what}: argnum {a} expected input->output "
                    f"aliasing but got {d['aliased']}/{d['leaves']} "
                    f"leaves aliased ({d['donated']} declared donated) "
                    f"— the dispatch copies this buffer")
        return self

    def __repr__(self):
        rows = ", ".join(
            f"{a}: {d['aliased']}/{d['leaves']} aliased"
            f"{' (donated)' if d['donated'] else ''}"
            for a, d in sorted(self.args.items()))
        return f"DonationReport({self.what}: {rows})"


def donation_report(fn, *args, static_argnums=(), what="program",
                    **kwargs) -> DonationReport:
    """Lower AND compile ``fn(*args, **kwargs)`` and report which
    inputs actually aliased outputs — the runtime half of the
    ``donation`` lint rule (docs/ANALYSIS.md §donation).

    ``fn`` is a jitted callable (anything with ``.lower``), or an
    engine program handle carrying ``.jitted``/``.bound`` attributes
    (the serving engine's step/verify/chunk lambdas expose these so
    test pins can audit the live programs with their bound state).
    ``static_argnums`` must repeat the jit wrapper's, so flat
    parameters map back to the right python argnums. Argnums are
    positions in the LOWERED call — bound leading arguments included.

    The declared side comes from ``Lowered.args_info`` (per-leaf
    ``donated`` flags); the actual side is parsed from the compiled
    module's ``input_output_alias`` header — one entry per flat
    parameter XLA wired to an output buffer. A program that cannot
    use a donation shows declared > aliased."""
    target = fn
    bound = ()
    if not hasattr(target, "lower"):
        jitted = getattr(fn, "jitted", None)
        if jitted is None:
            raise TypeError(
                f"donation_report needs a jitted callable (or an "
                f"engine program handle with .jitted/.bound); got "
                f"{type(fn).__name__}")
        b = getattr(fn, "bound", ())
        bound = tuple(b() if callable(b) else b)
        target = jitted
    lowered = target.lower(*bound, *args, **kwargs)
    compiled = lowered.compile()

    report = DonationReport(what)
    info_args, _info_kwargs = lowered.args_info
    statics = set(static_argnums)
    # python argnums of the DYNAMIC positional args, in order (statics
    # never reach args_info or the parameter list)
    n_total = len(info_args) + len(statics)
    dyn_argnums = [i for i in range(n_total) if i not in statics]

    # the OPTIMIZED module renumbers parameters (DCE drops unused
    # inputs — the step program dead-codes most state leaves), so
    # alias entries map back to jax arguments by TYPE: only donated
    # leaves are alias candidates. Identically-typed donated leaves
    # are indistinguishable in the table, so a type is credited only
    # when the aliased supply covers EVERY donated leaf of that type —
    # a partially-aliased ambiguous type counts as copied for all of
    # them (expect_aliased fails closed instead of false-passing on
    # whichever argnum is visited first).
    hlo = compiled.as_text()
    param_types = _entry_param_types(hlo)
    aliased_types: Dict[str, int] = {}
    for entry in _ALIAS_ENTRY.finditer(_alias_table(hlo)):
        idx = int(entry.group(1))
        report.alias_kinds.append(entry.group(2))
        if idx < len(param_types):
            t = param_types[idx]
            aliased_types[t] = aliased_types.get(t, 0) + 1

    # a SHARDED module's entry layout lists per-shard parameter shapes,
    # so each leaf's matching type is its LOCAL shape under the actual
    # argument's sharding (shard_shape) — matching global avals instead
    # would make every sharded donated buffer look copied. The real
    # argument leaves align with args_info's dynamic trees; unsharded
    # arrays degrade to the global shape (SingleDeviceSharding's
    # shard_shape is the identity).
    all_pos = list(bound) + list(args)
    value_leaves = []
    for i in dyn_argnums:
        value_leaves.extend(jax.tree_util.tree_leaves(all_pos[i]))

    def _leaf_type(leaf, flat_i: int) -> Optional[str]:
        aval = getattr(leaf, "_aval", None) or getattr(leaf, "aval",
                                                       None)
        if aval is None:
            return None
        shape = tuple(aval.shape)
        if flat_i < len(value_leaves):
            sh = getattr(value_leaves[flat_i], "sharding", None)
            if sh is not None:
                try:
                    shape = tuple(sh.shard_shape(shape))
                except Exception:   # noqa: BLE001 — keep global shape
                    pass
        dt = _HLO_DTYPES.get(str(aval.dtype), str(aval.dtype))
        return f"{dt}[{','.join(str(d) for d in shape)}]"

    donated_demand: Dict[str, int] = {}
    flat_i = 0
    for tree in info_args:
        for leaf in jax.tree_util.tree_leaves(tree):
            if getattr(leaf, "donated", False):
                t = _leaf_type(leaf, flat_i)
                if t is not None:
                    donated_demand[t] = donated_demand.get(t, 0) + 1
            flat_i += 1

    flat_i = 0
    for argnum, tree in zip(dyn_argnums, info_args):
        leaves = jax.tree_util.tree_leaves(tree)
        donated = aliased = 0
        for leaf in leaves:
            if getattr(leaf, "donated", False):
                donated += 1
                t = _leaf_type(leaf, flat_i)
                if t is not None and aliased_types.get(t, 0) \
                        >= donated_demand.get(t, 0):
                    aliased += 1
            flat_i += 1
        report.args[argnum] = {"leaves": len(leaves),
                               "donated": donated, "aliased": aliased}
    return report


# ------------------------------------------------- snapshot round trip

class SnapshotDriftError(RuntimeError):
    """snapshot -> restore -> snapshot was not byte-identical in
    canonical form: a serialized field is being lost, re-derived
    differently, or restored asymmetrically."""


def canonical_snapshot(snap: Dict) -> Dict:
    """The canonical form of a ``paddle_tpu.engine_snapshot/v1`` dict:
    everything the protocol promises to round-trip, nothing that is
    volatile by contract. Slots and queue merge into ONE scheduling-
    ordered request list — a just-restored engine holds every request
    in its queue, so slot-vs-queue placement is scheduling state, not
    protocol state. Excluded as volatile BY CONTRACT (docs/SERVING.md
    §Snapshot contract): ``ts`` (wall clock), ``step_seq`` (restore
    bumps it), ``prefix_keys`` (postmortem info; the cache rebuilds
    from traffic), per-request ``chunk_filled`` (restore re-prefills
    from tokens) and ``deadline_remaining_s`` (re-anchored to the
    restore wall clock — only its None-ness is protocol state), and
    the ``sanitize``/``flight_dump_path`` config knobs (debug guard
    and postmortem sink — the roundtrip itself restores with the guard
    off and the sink detached)."""
    from paddle_tpu.serving.engine import _PRIORITY_RANK

    reqs = []
    for e in list(snap.get("slots", ())) + list(snap.get("queue", ())):
        d = {k: v for k, v in e.items()
             if k not in ("chunk_filled", "deadline_remaining_s")}
        d["has_deadline"] = e.get("deadline_remaining_s") is not None
        reqs.append(d)
    reqs.sort(key=lambda d: (-_PRIORITY_RANK.get(d.get("priority",
                                                       "normal"), 1),
                             d.get("seq", 0)))
    results = sorted(snap.get("results", ()),
                     key=lambda r: r["request_id"])
    config = {k: v for k, v in snap.get("config", {}).items()
              if k not in ("sanitize", "flight_dump_path")}
    return {"schema": snap.get("schema"), "config": config,
            "model": snap.get("model"), "requests": reqs,
            "results": results,
            "seeds_issued": snap.get("seeds_issued"),
            "submit_seq": snap.get("submit_seq")}


def canonical_snapshot_bytes(snap: Dict) -> bytes:
    return json.dumps(canonical_snapshot(snap), sort_keys=True,
                      separators=(",", ":")).encode()


def compare_snapshots(snap1: Dict, snap2: Dict,
                      what: str = "snapshot roundtrip"):
    """Raise :class:`SnapshotDriftError` naming the first diverging
    canonical section when the two snapshots differ."""
    c1, c2 = canonical_snapshot(snap1), canonical_snapshot(snap2)
    if c1 == c2:
        return
    for key in c1:
        if c1[key] != c2[key]:
            raise SnapshotDriftError(
                f"{what}: canonical section {key!r} diverged —\n"
                f"  before restore: {json.dumps(c1[key], sort_keys=True)[:400]}\n"
                f"  after restore:  {json.dumps(c2[key], sort_keys=True)[:400]}")
    raise SnapshotDriftError(f"{what}: snapshots diverged "
                             f"(keys {sorted(c1)} vs {sorted(c2)})")


def snapshot_roundtrip(engine, snap: Optional[Dict] = None):
    """The state-protocol sanitizer: assert that restoring ``engine``'s
    snapshot and re-snapshotting reproduces the SAME canonical bytes —
    no field silently lost, none re-derived differently. Builds a real
    restored engine (its own pool + programs) and closes it, so this is
    a debug/chaos tier, not a hot-path guard. Returns the verified
    snapshot. Raises :class:`SnapshotDriftError` on drift.

    Wired in: ``ServingEngine(sanitize="roundtrip"|"all")`` runs this
    inside every ``save_snapshot`` (the snapshot you are about to trust
    is the one checked), and ``examples/chaos_bench.py
    --roundtrip_every N`` calls it mid-soak."""
    from paddle_tpu.observability import registry

    snap1 = snap if snap is not None else engine.snapshot()
    # the restored twin must neither recurse the roundtrip nor dump
    # into the live engine's flight sink; the draft proposer's model
    # does not serialize, so hand the live SpecConfig back
    overrides = dict(sanitize=False, flight_dump_path=None)
    if getattr(engine, "speculate", None) is not None:
        overrides["speculate"] = engine.speculate
    # snapshots are mesh-free: the twin must be re-handed the live
    # engine's mesh/layout or it would restore single-device and the
    # roundtrip would "pass" without exercising the sharded paths
    if getattr(engine, "mesh", None) is not None:
        overrides["mesh"] = engine.mesh
        overrides["layout"] = engine.layout
    eng2 = type(engine).restore(engine.model, snap1,
                                state=engine._state, **overrides)
    try:
        snap2 = eng2.snapshot()
    finally:
        eng2.close()
    compare_snapshots(snap1, snap2)
    engine.stats["roundtrip_checks"] = (
        engine.stats.get("roundtrip_checks", 0) + 1)
    registry().counter("serving.snapshot_roundtrips").inc()
    return snap1
