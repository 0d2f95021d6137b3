"""Layers ``engine`` and ``programs``, from inside the tick.

``ServingEngine.step()`` is six phases, each a ``serving.step.<phase>``
span in the profiler's trace (on the device's clock) and a
``step_<phase>_s`` counter in ``engine.stats``: admit (with its
children prefill and upload), dispatch, sync, commit, tail. The trace
readers here share every idle gap of the first device that is
``HOST_GAP_S`` or longer out **by overlap** among the innermost span the
host was in at each instant of the gap (self time), so a gap that
straddles two phases is split between them:

    admit       under ``serving.step.admit`` or its child ``.upload``
                (upload is a part of admit, told apart in ``upload_ms``)
    prefill, dispatch, sync, commit, tail
                under that phase's span
    unattributed  inside ``bench.step`` or ``serving.step``, in no phase
    outside     everything else: the caller's loop between two ticks,
                ``serving.submit`` among it (``submit_ms``)

The window is the ``bench.window`` span, as in ``trace_reduce.reduce``;
"a decode tick" divides by the decode steps of the traced part.

The device's events and the host's spans are stamped by two clocks that
the profiler aligns once, and not always well: a trace of the v5e read
the device resuming 0.2 ms BEFORE the dispatch span that launched it
opened (PERF.md, PR 25). Such an offset slides every gap along the
host's phases: it moves idle time between ``sync``, where a gap begins,
and ``dispatch``, where it ends, and leaves their sum and the phases
between them (commit, tail, outside, admit) alone. ``launch_ms`` (device
resumes - dispatch opens) and ``return_ms`` (sync closes - device stops),
medians over the gaps, say how far to trust the split: a launch cannot
come before its dispatch, and a pull cannot return before the device
has stopped.

A trace without ``serving.step`` spans (a program from before they existed) and
a run without a trace read as ``None``: the metric is left off the line.
"""

import bisect
import functools
import statistics

from harness import trace_reduce

STEP = "serving.step"
SUBMIT = "serving.submit"
BENCH_STEP = "bench.step"
PHASES = ("admit", "prefill", "dispatch", "sync", "commit", "tail")
SIX = tuple(f"step_{p}_s" for p in PHASES)
# innermost span -> the bucket its idle time is counted in; a span
# this file does not know is ``unattributed`` inside a tick and
# ``outside`` otherwise
BUCKET = {f"{STEP}.{p}": p for p in PHASES}
BUCKET.update({f"{STEP}.upload": "admit", BENCH_STEP: "unattributed",
               SUBMIT: "outside", None: "outside"})


def bucket(name) -> str:
    if name in BUCKET:
        return BUCKET[name]
    return "unattributed" if name.startswith(STEP) else "outside"


def innermost(spans) -> list:
    """[(start, end, name), ...], disjoint and sorted: for every instant
    some span covers, the name of the innermost one. ``spans`` nest
    (one thread's annotations); instants no span covers are left out."""
    out, stack, t = [], [], None

    def emit(until):
        if stack and until > t:
            out.append((t, until, stack[-1][2]))

    for span in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= span[0]:
            emit(stack[-1][1])
            t = max(t, stack.pop()[1])
        emit(span[0])
        t = span[0]
        stack.append(span)
    while stack:
        emit(stack[-1][1])
        t = max(t, stack.pop()[1])
    return out


def share_out(gaps, pieces) -> tuple:
    """({name: ns of the gaps under it}, [(gap ns, {name: ns}), ...]):
    every gap's overlap with every piece of ``innermost``; what no
    piece covers goes to ``None``. Both lists are sorted and disjoint,
    so one pass serves."""
    total, per_gap, i = {}, [], 0
    for lo, hi in gaps:
        while i < len(pieces) and pieces[i][1] <= lo:
            i += 1
        own, j, covered = {}, i, 0
        while j < len(pieces) and pieces[j][0] < hi:
            s, e, name = pieces[j]
            cut = min(e, hi) - max(s, lo)
            own[name] = own.get(name, 0) + cut
            covered += cut
            j += 1
        if hi - lo > covered:
            own[None] = hi - lo - covered
        for name, ns in own.items():
            total[name] = total.get(name, 0) + ns
        per_gap.append((hi - lo, own))
    return total, per_gap


def nearest_offsets(edges, stamps) -> list:
    """For every instant of ``edges``, its signed distance to the
    nearest of the sorted ``stamps`` (edge - stamp)."""
    out = []
    for t in edges:
        i = bisect.bisect_left(stamps, t)
        out.append(min((t - stamps[j] for j in (i - 1, i)
                        if 0 <= j < len(stamps)), key=abs))
    return out


@functools.lru_cache(maxsize=2)
def split(trace_path: str):
    """The idle split of one trace, in ns, or ``None`` when the trace
    has no ``serving.step`` span. Loaded once for all the readers."""
    profile = trace_reduce.load(trace_path)
    ops = trace_reduce.device_ops(profile)
    serving = trace_reduce.host_spans(profile, prefix="serving.")
    if not ops or not any(s[2] == STEP for s in serving):
        return None
    bench = trace_reduce.host_spans(profile)
    window = [s for s in bench if s[2] == trace_reduce.WINDOW_SPAN]
    first = ops[sorted(ops)[0]]
    if window:
        lo, hi = window[0][0], window[-1][1]
    else:
        lo, hi = first[0][0], max(e for _, e, _ in first)
    host_gaps = [(s, e) for s, e in trace_reduce.gaps(
        trace_reduce.union(first, lo, hi), lo, hi)
        if (e - s) * 1e-9 >= trace_reduce.HOST_GAP_S]
    pieces = innermost(serving + [s for s in bench if s[2] == BENCH_STEP])
    by_name, per_gap = share_out(host_gaps, pieces)
    buckets = dict.fromkeys(PHASES + ("unattributed", "outside"), 0)
    for name, ns in by_name.items():
        buckets[bucket(name)] += ns
    longest, under = max(per_gap, key=lambda g: g[0], default=(0, {}))
    prefills = [s for s in serving if s[2] == f"{STEP}.prefill"
                and s[0] >= lo and s[1] <= hi]
    opens = sorted(s[0] for s in serving if s[2] == f"{STEP}.dispatch")
    closes = sorted(s[1] for s in serving if s[2] == f"{STEP}.sync")
    # a gap the window cuts has an edge that is no launch and no stop
    whole = [g for g in host_gaps if g[0] > lo and g[1] < hi]
    launch = opens and nearest_offsets([e for _, e in whole], opens)
    back = closes and nearest_offsets([s for s, _ in whole], closes)
    return dict(
        launch=statistics.median(launch) if launch else None,
        back=-statistics.median(back) if back else None,
        buckets=buckets, gaps=len(host_gaps), longest=longest,
        longest_in=bucket(max(under, key=under.get)) if under else None,
        upload=by_name.get(f"{STEP}.upload", 0),
        submit=by_name.get(SUBMIT, 0),
        prefill_spans=len(prefills),
        prefill_span_ns=sum(e - s for s, e, _ in prefills),
        prefill_idle_ns=share_out(host_gaps, prefills)[0].get(
            f"{STEP}.prefill", 0))


def traced(obs):
    """(the split, decode steps of the traced part) or ``None``."""
    path = obs.get("trace_path")
    if not path or obs.get("trace") is None:
        return None
    lo, hi = obs["trace_steps"]
    got = split(path)
    if got is None or hi <= lo:
        return None
    return got, hi - lo


def idle_ms(obs):
    """{bucket: device-idle ms a decode tick}, with ``upload`` and
    ``submit`` beside the buckets they are parts of, or ``None``."""
    got = traced(obs)
    if got is None:
        return None
    sp, steps = got
    return {k: ns * 1e-6 / steps for k, ns in dict(
        sp["buckets"], upload=sp["upload"], submit=sp["submit"]).items()}


def idle_ms_admit(obs):
    """Under ``serving.step.admit`` itself or its ``upload`` child."""
    ms = idle_ms(obs)
    if ms is None:
        return None
    s = obs["stats"]
    return dict(value=ms["admit"], upload_ms=ms["upload"],
                upload_tick_share=(s["upload_ticks"] / s["steps"]
                                   if s.get("steps") else None))


def idle_ms_prefill(obs):
    ms = idle_ms(obs)
    return ms and ms["prefill"]


def _ms(ns):
    return None if ns is None else ns * 1e-6


def idle_ms_dispatch(obs):
    """``launch_ms``: the device resumes that long after the dispatch
    span opens (median); negative, and the trace's two clocks are off
    by at least that much."""
    ms = idle_ms(obs)
    return ms and dict(value=ms["dispatch"],
                       launch_ms=_ms(traced(obs)[0]["launch"]))


def idle_ms_sync(obs):
    """The program has ended and the pull has not returned.
    ``return_ms``: the sync span closes that long after the device
    stops (median)."""
    ms = idle_ms(obs)
    return ms and dict(value=ms["sync"],
                       return_ms=_ms(traced(obs)[0]["back"]))


def idle_ms_commit(obs):
    ms = idle_ms(obs)
    return ms and ms["commit"]


def idle_ms_tail(obs):
    """What the per-tick telemetry costs the device."""
    ms = idle_ms(obs)
    return ms and ms["tail"]


def idle_ms_outside(obs):
    """Between two ``serving.step`` spans: the caller's loop and
    ``serving.submit``. Carries what belongs to the split as a whole:
    what no phase covers inside a tick, and the longest single gap."""
    ms = idle_ms(obs)
    if ms is None:
        return None
    sp = traced(obs)[0]
    return dict(value=ms["outside"], submit_ms=ms["submit"],
                unattributed_ms=ms["unattributed"], host_gaps=sp["gaps"],
                longest_gap_ms=sp["longest"] * 1e-6,
                longest_gap_phase=sp["longest_in"])


def prefill_idle_share(obs):
    """Device-idle seconds (host-scale gaps) inside the
    ``serving.step.prefill`` spans of the window over those spans'
    seconds: whether the wave's host part starves a long prefill."""
    got = traced(obs)
    if got is None or not got[0]["prefill_span_ns"]:
        return None
    sp = got[0]
    return dict(value=100.0 * sp["prefill_idle_ns"] / sp["prefill_span_ns"],
                waves=sp["prefill_spans"],
                prefill_span_s=sp["prefill_span_ns"] * 1e-9,
                idle_s=sp["prefill_idle_ns"] * 1e-9)


def tick_host_ms(obs):
    """Host ms a decode tick in the phases that wait for nothing
    (admit, dispatch, commit, tail), from ``engine.stats`` over the
    untraced part of the window; every segment beside it, and how much
    of the benchmark's own clock around ``eng.step()`` the six cover."""
    s = obs["stats"]
    if "step_commit_s" not in s or not s["steps"]:
        return None
    n = s["steps"]
    lo, hi = obs["host_span"]
    wall = sum(d for t, d, _ in obs["ticks"] if lo <= t < hi)
    out = {f"{p}_ms": 1e3 * s[k] / n for p, k in zip(PHASES, SIX)}
    return dict(out, value=1e3 * (s["step_admit_s"] + s["step_dispatch_s"]
                                  + s["step_commit_s"] + s["step_tail_s"]) / n,
                upload_ms=1e3 * s["step_upload_s"] / n,
                covered=sum(s[k] for k in SIX) / wall if wall else None)
