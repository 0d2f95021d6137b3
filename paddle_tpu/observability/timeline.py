"""Chrome trace-event (Perfetto) timeline export for the serving tier.

The flight rings say what each replica's last N ticks did, the tracer
says what each request cost, and the router journal says which replica
touched which request when — three true but disjoint views. This module
folds all three into ONE Chrome trace-event JSON file
(``chrome://tracing`` / https://ui.perfetto.dev): per-replica process
tracks, per-request thread tracks, tick-segment duration events
(admit with its prefill inside / dispatch / sync / commit,
reconstructed from the step breakdown each flight event carries),
journal instants, and **flow
arrows keyed by ``trace_id``** — so a request that was preempted,
resumed, or migrated off a killed replica renders as one connected
chain across process tracks instead of disconnected fragments. This is
the serving-tier analog of the reference profiler's chrome-tracing
export (``paddle/fluid/platform/profiler`` + the timeline tool), driven
by host telemetry instead of device events.

Clock model: every producer stamps wall-clock ``ts`` (spans via the
retirement mapping, flight events directly, journal appends directly)
plus, where available, a monotonic ``ts_mono``. A per-process
:func:`clock_anchor` — ONE ``(perf_counter, time.time)`` pair — lets
the builder re-derive wall time from ``ts_mono`` so cross-replica
ordering is immune to wall-clock steps mid-run; with no anchor the
wall ``ts`` is used as-is.

Nothing here imports jax; the module is postmortem/CLI-side only.
"""

import json
import time
from typing import Dict, Iterable, List, Optional, Sequence

__all__ = ["clock_anchor", "build_timeline", "write_timeline",
           "verify_trace_continuity", "TICK_SEGMENTS"]

#: the per-tick step segments, in dispatch order, with their flight
#: event fields (docs/OBSERVABILITY.md §Timelines). ``commit`` is the
#: last phase before the tail stamps the event, which is why the
#: segments end at the stamp; ``prefill`` runs inside admission. An
#: event tick that landed the step program in flight before its upload
#: (docs/SERVING.md §The tick's order) is drawn in this order too: the
#: lengths are its own, its sync and commit ran before the dispatch
TICK_SEGMENTS = (("admit", "t_admit_s"), ("prefill", "t_prefill_s"),
                 ("dispatch", "t_dispatch_s"), ("sync", "t_sync_s"),
                 ("commit", "t_commit_s"))

#: flight tick-event list fields that name requests → per-request
#: instant events; (field, event name, entry shape)
_REQUEST_FIELDS = (("admitted", "admit"), ("retired", "retire"),
                   ("preempted", "preempt"), ("resumed", "resume"),
                   ("shed", "shed"))


def clock_anchor() -> Dict[str, float]:
    """One wall/monotonic clock pair — sample once per process and pass
    it to :func:`build_timeline` so ``ts_mono`` timestamps from that
    process land on the shared wall-clock axis."""
    return {"mono": time.perf_counter(), "wall": time.time()}


def _us(ts: float) -> int:
    return int(round(float(ts) * 1e6))


def _event_ts(evt: Dict, anchor: Optional[Dict]) -> Optional[float]:
    """An event's wall-clock seconds: anchored monotonic when both
    sides exist (immune to wall steps), the recorded wall ``ts``
    otherwise."""
    if anchor is not None and evt.get("ts_mono") is not None:
        return anchor["wall"] + (float(evt["ts_mono"]) - anchor["mono"])
    return evt.get("ts")


class _Builder:
    def __init__(self):
        self.events: List[Dict] = []
        # (pid, rid) -> tid; per-request thread tracks are allocated
        # densely per process above the fixed segment/marker threads
        self._req_tid: Dict = {}
        # trace_id -> [(ts_us, pid, tid, rid)] flow touch points
        self.touches: Dict[str, List] = {}

    def meta(self, pid: int, name: str):
        self.events.append({"ph": "M", "name": "process_name", "pid": pid,
                            "tid": 0, "args": {"name": name}})
        for tid, tname in ((0, "ticks"), (1, "spans"), (2, "markers"),
                           (3, "journal")):
            self.events.append({"ph": "M", "name": "thread_name",
                                "pid": pid, "tid": tid,
                                "args": {"name": tname}})

    def req_tid(self, pid: int, rid) -> int:
        key = (pid, rid)
        tid = self._req_tid.get(key)
        if tid is None:
            tid = 16 + sum(1 for (p, _) in self._req_tid if p == pid)
            self._req_tid[key] = tid
            self.events.append({"ph": "M", "name": "thread_name",
                                "pid": pid, "tid": tid,
                                "args": {"name": f"req {rid}"}})
        return tid

    def duration(self, pid, tid, name, ts_s, dur_s, args=None):
        self.events.append({"ph": "X", "name": name, "pid": pid,
                            "tid": tid, "ts": _us(ts_s),
                            "dur": max(_us(dur_s), 1),
                            "args": args or {}})

    def instant(self, pid, tid, name, ts_s, args=None):
        self.events.append({"ph": "i", "s": "t", "name": name, "pid": pid,
                            "tid": tid, "ts": _us(ts_s),
                            "args": args or {}})

    def touch(self, trace_id, ts_s, pid, tid, rid=None):
        if trace_id:
            self.touches.setdefault(str(trace_id), []).append(
                (_us(ts_s), pid, tid, rid))

    def flows(self):
        """One flow chain per trace_id over its touch points in time
        order — a migrated request's arrow crosses process tracks, the
        failover rendered as geometry."""
        for trace_id, pts in sorted(self.touches.items()):
            pts = sorted(pts)
            if len(pts) < 2:
                continue
            for j, (ts, pid, tid, _) in enumerate(pts):
                ph = "s" if j == 0 else ("f" if j == len(pts) - 1 else "t")
                evt = {"ph": ph, "name": "request", "cat": "trace",
                       "id": trace_id, "pid": pid, "tid": tid, "ts": ts}
                if ph == "f":
                    evt["bp"] = "e"
                self.events.append(evt)


def _flight_event(b: _Builder, pid: int, evt: Dict,
                  anchor: Optional[Dict], trace_map: Dict):
    ts = _event_ts(evt, anchor)
    if ts is None:
        return
    if "kind" in evt:           # marker (mark()): restore/failover/...
        args = {k: v for k, v in evt.items()
                if k not in ("kind", "ts", "ts_mono")
                and isinstance(v, (int, float, str, bool, type(None)))}
        b.instant(pid, 2, evt["kind"], ts, args)
        return
    if "step" not in evt:
        return
    # tick event: the segments partition the tick up to the record
    # stamp (an older dump has no t_commit_s and is drawn late by the
    # commit's length). t_admit_s is admission's time LESS the wave
    # prefill that runs inside it, right behind the queue pop: admit is
    # drawn around its prefill, not in front of it
    dur = {nm: float(evt.get(f) or 0.0) for nm, f in TICK_SEGMENTS}
    start = cursor = ts - sum(dur.values())
    dur["admit"] += dur["prefill"]
    for nm, _ in TICK_SEGMENTS:
        inside = nm == "prefill"
        if dur[nm] > 0.0:
            b.duration(pid, 0, nm, start if inside else cursor, dur[nm],
                       {"step": evt.get("step")})
        if not inside:
            cursor += dur[nm]
    if evt.get("err"):
        b.instant(pid, 0, "tick_error", ts, {"err": evt["err"]})
    # per-request instants on their own thread tracks, flow-touched
    for field, name in _REQUEST_FIELDS:
        for entry in evt.get(field) or ():
            rid, extra = (entry[0], entry[1:]) \
                if isinstance(entry, (list, tuple)) else (entry, ())
            args = {"step": evt.get("step")}
            if extra:
                args["detail"] = list(extra)
            tid = b.req_tid(pid, rid)
            b.instant(pid, tid, name, ts, args)
            b.touch(trace_map.get(rid), ts, pid, tid, rid)


def build_timeline(processes: Sequence[Dict],
                   journal: Iterable[Dict] = (),
                   trace_map: Optional[Dict] = None) -> Dict:
    """Fold telemetry into a Chrome trace-event document.

    ``processes``: one dict per process track —
    ``{"name": str, "flight": [events], "spans": [span dicts],
    "anchor": clock_anchor() or None, "pid": optional}``. ``journal``:
    replayed router-journal events (``RouterJournal.replay``); an event
    naming a ``replica`` lands on the process named ``replica_<i>``
    when present, else on the first process. ``trace_map``
    (``{request_id: trace_id}``) supplements the trace ids the journal
    itself carries — single-engine runs (no journal) pass the map from
    their ``RequestResult.trace_id``s.

    Returns ``{"traceEvents": [...], "displayTimeUnit": "ms",
    "otherData": {"trace_count": N}}`` — Perfetto-loadable as-is.
    """
    b = _Builder()
    trace_map = dict(trace_map or {})
    journal = list(journal)
    for evt in journal:         # journal trace ids feed the shared map
        if evt.get("trace_id") is not None and evt.get("rid") is not None:
            trace_map.setdefault(evt["rid"], evt["trace_id"])
    name_to_pid: Dict[str, int] = {}
    for i, proc in enumerate(processes):
        pid = int(proc.get("pid", i))
        name = str(proc.get("name", f"process_{i}"))
        name_to_pid[name] = pid
        b.meta(pid, name)
        anchor = proc.get("anchor")
        for evt in proc.get("flight") or ():
            _flight_event(b, pid, evt, anchor, trace_map)
        for span in proc.get("spans") or ():
            attrs = dict(span.get("attrs") or {})
            rid = attrs.get("request_id")
            trace_id = attrs.get("trace_id") or trace_map.get(rid)
            args = {k: v for k, v in attrs.items()
                    if isinstance(v, (int, float, str, bool, type(None)))}
            tid = b.req_tid(pid, rid) if rid is not None else 1
            b.duration(pid, tid, span["name"], span["ts"],
                       span.get("dur_s", 0.0), args)
            if trace_id:
                b.touch(trace_id, span["ts"], pid, tid, rid)
    jpid = next(iter(name_to_pid.values()), 0)
    for evt in journal:
        kind = evt.get("kind")
        if kind is None or evt.get("ts") is None:
            continue
        pid = name_to_pid.get(f"replica_{evt.get('replica')}", jpid)
        args = {k: v for k, v in evt.items()
                if k not in ("kind", "ts", "tokens", "prompt")
                and isinstance(v, (int, float, str, bool, type(None)))}
        b.instant(pid, 3, f"journal:{kind}", evt["ts"], args)
        if evt.get("rid") is not None:
            b.touch(trace_map.get(evt["rid"]), evt["ts"], pid, 3,
                    evt["rid"])
    b.flows()
    b.events.sort(key=lambda e: (e["ph"] != "M", e.get("ts", 0)))
    return {"traceEvents": b.events, "displayTimeUnit": "ms",
            "otherData": {"trace_count": len(b.touches)}}


def write_timeline(path: str, *, processes: Sequence[Dict],
                   journal: Iterable[Dict] = (),
                   trace_map: Optional[Dict] = None) -> Dict:
    """:func:`build_timeline` to a file; returns
    ``{"path", "events", "trace_count"}`` (the bench-record fields)."""
    doc = build_timeline(processes, journal=journal, trace_map=trace_map)
    with open(path, "w") as f:
        json.dump(doc, f)
    return {"path": path, "events": len(doc["traceEvents"]),
            "trace_count": doc["otherData"]["trace_count"]}


def verify_trace_continuity(journal_events: Iterable[Dict],
                            accepted_rids: Optional[Iterable] = None,
                            require_finish: bool = False) -> List[str]:
    """Check that every accepted request's journal events form ONE
    causally-linked ``trace_id`` chain — the acceptance gate
    ``examples/chaos_bench.py`` runs after a kill-replica chaos drive
    (a broken chain exits non-zero there).

    A chain is broken when an ``accept`` lacks a ``trace_id``, when a
    later ``place``/``finish`` for the same request carries a DIFFERENT
    trace_id (an orphan fragment — e.g. a migration that re-minted
    instead of carrying the id), or when a rid in ``accepted_rids``
    never got an accept event at all. ``require_finish=True``
    additionally demands a finish event per accepted request (the
    zero-loss drain contract). Returns human-readable problems; empty
    means every chain is connected.
    """
    accepts: Dict = {}
    problems: List[str] = []
    for evt in journal_events:
        kind = evt.get("kind")
        rid = evt.get("rid")
        if kind == "accept":
            if rid in accepts:
                problems.append(f"rid {rid}: duplicate accept")
            accepts[rid] = {"trace_id": evt.get("trace_id"),
                            "finished": False}
            if evt.get("trace_id") is None:
                problems.append(f"rid {rid}: accept has no trace_id")
        elif kind in ("place", "finish") and rid in accepts:
            want = accepts[rid]["trace_id"]
            got = evt.get("trace_id")
            if got is None:
                problems.append(f"rid {rid}: {kind} has no trace_id")
            elif want is not None and got != want:
                problems.append(
                    f"rid {rid}: {kind} trace_id {got!r} != accept "
                    f"trace_id {want!r} (orphan fragment)")
            if kind == "finish":
                accepts[rid]["finished"] = True
    rids = set(accepts) if accepted_rids is None else set(accepted_rids)
    for rid in sorted(rids, key=str):
        if rid not in accepts:
            problems.append(f"rid {rid}: accepted but never journaled")
        elif require_finish and not accepts[rid]["finished"]:
            problems.append(f"rid {rid}: no finish event (chain never "
                            f"terminates)")
    return problems
