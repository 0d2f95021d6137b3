"""Perfetto timeline export (observability.timeline): the trace-event
builder (process/thread tracks, tick segments, per-request instants,
journal instants, trace_id flow arrows), the clock-anchor model, and
the trace-continuity checker the chaos harness gates on.

Builder tests run on synthetic events only — nothing here needs jax
(the module itself never imports it; postmortem/CLI-side tooling)."""

import json

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import timeline as tl


def _tick(step, ts, *, admitted=(), retired=(), preempted=(),
          resumed=(), shed=(), err=None, **seg):
    """One synthetic flight tick event in the engine's recorded shape."""
    evt = {"step": step, "ts": ts, "active": 1, "queued": 0,
           "admitted": list(admitted),
           "retired": [list(r) for r in retired],
           "preempted": list(preempted), "resumed": list(resumed),
           "shed": list(shed),
           "t_admit_s": seg.get("admit", 0.0),
           "t_prefill_s": seg.get("prefill", 0.0),
           "t_dispatch_s": seg.get("dispatch", 0.0),
           "t_sync_s": seg.get("sync", 0.0)}
    if "commit" in seg:     # flight dumps from before PR 25 have none
        evt["t_commit_s"] = seg["commit"]
    if err is not None:
        evt["err"] = err
    return evt


# ---- clock model ------------------------------------------------------------

def test_clock_anchor_rederives_wall_from_mono():
    anchor = tl.clock_anchor()
    assert set(anchor) == {"mono", "wall"}
    # anchored: wall time is re-derived from the monotonic stamp, so a
    # wall-clock step recorded into ts is IGNORED when ts_mono exists
    evt = {"ts": anchor["wall"] + 9999.0, "ts_mono": anchor["mono"] + 2.0}
    assert tl._event_ts(evt, anchor) == pytest.approx(
        anchor["wall"] + 2.0)
    # no anchor (or no ts_mono): the recorded wall ts is used as-is
    assert tl._event_ts(evt, None) == evt["ts"]
    assert tl._event_ts({"ts": 5.0}, anchor) == 5.0
    assert tl._event_ts({}, anchor) is None


# ---- builder structure ------------------------------------------------------

def test_build_timeline_tracks_segments_and_instants():
    flight = [
        _tick(0, 100.0, admitted=[7], admit=0.5, prefill=0.25,
              dispatch=0.125, sync=0.125),
        _tick(1, 101.0, retired=[(7, "length")], dispatch=0.25,
              err="boom"),
        {"kind": "restore", "ts": 102.0, "restored": 2},
    ]
    doc = tl.build_timeline([{"name": "engine", "flight": flight}])
    evts = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in evts if e["ph"] == "M"]
    assert {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "engine"}} in meta
    tnames = {e["tid"]: e["args"]["name"] for e in meta
              if e["name"] == "thread_name"}
    assert tnames[0] == "ticks" and tnames[3] == "journal"
    assert tnames[16] == "req 7"            # dense per-request track

    # tick 0: the segments in TICK_SEGMENTS order, summing back to the
    # tick's total and ending at the record stamp; prefill is drawn
    # INSIDE admit (t_admit_s is admission's time less it), the rest
    # follow one another
    segs = [e for e in evts if e["ph"] == "X" and e["tid"] == 0
            and e["args"].get("step") == 0]
    assert [e["name"] for e in segs] == ["admit", "prefill", "dispatch",
                                         "sync"]
    admit, prefill, dispatch, sync = segs
    assert admit["ts"] == prefill["ts"] == tl._us(100.0 - 1.0)
    assert admit["dur"] == tl._us(0.75) and prefill["dur"] == tl._us(0.25)
    assert admit["ts"] + admit["dur"] == dispatch["ts"]
    assert dispatch["ts"] + dispatch["dur"] == sync["ts"]
    assert sync["ts"] + sync["dur"] == tl._us(100.0)

    # tick 1: zero-duration segments are dropped, the error instants
    inst = {(e["name"], e["tid"]) for e in evts if e["ph"] == "i"}
    assert ("tick_error", 0) in inst
    assert ("admit", 16) in inst and ("retire", 16) in inst
    assert ("restore", 2) in inst           # mark() -> marker thread
    # one request, never >1 touch point -> no flows, no chain counted
    assert doc["otherData"]["trace_count"] == 0
    # meta events sort first, then everything by timestamp
    kinds = [e["ph"] for e in evts]
    assert kinds[:len(meta)] == ["M"] * len(meta)
    stamped = [e.get("ts", 0) for e in evts if e["ph"] != "M"]
    assert stamped == sorted(stamped)


def test_tick_segments_end_at_the_stamp_because_of_commit():
    """The flight stamp is taken in the tail, after the commit: with
    ``t_commit_s`` in the event every segment is drawn where it
    happened, and without it (an older dump) the four old ones still
    end at the stamp as they used to."""
    assert tl.TICK_SEGMENTS[-1] == ("commit", "t_commit_s")
    new = _tick(3, 50.0, admit=0.002, prefill=0.004, dispatch=0.001,
                sync=0.005, commit=0.003)
    old = _tick(4, 60.0, admit=0.002, prefill=0.004, dispatch=0.001,
                sync=0.005)
    evts = tl.build_timeline([{"name": "engine", "flight": [new, old]}]
                             )["traceEvents"]

    def segs(step):
        return {e["name"]: (e["ts"], e["ts"] + e["dur"]) for e in evts
                if e["ph"] == "X" and e["args"].get("step") == step}

    s = segs(3)
    assert list(s) == ["admit", "prefill", "dispatch", "sync", "commit"]
    assert s["commit"] == (tl._us(50.0 - 0.003), tl._us(50.0))
    assert s["sync"] == (tl._us(50.0 - 0.008), tl._us(50.0 - 0.003))
    assert s["dispatch"][1] == s["sync"][0]
    # admit spans its own 2 ms AND the 4 ms of prefill inside it
    assert s["admit"] == (tl._us(50.0 - 0.015), tl._us(50.0 - 0.009))
    assert s["admit"][0] == s["prefill"][0]
    assert s["prefill"][1] <= s["admit"][1] == s["dispatch"][0]
    s = segs(4)
    assert list(s) == ["admit", "prefill", "dispatch", "sync"]
    assert s["sync"][1] == tl._us(60.0)
    assert s["admit"][0] == tl._us(60.0 - 0.012)


def test_build_timeline_flows_cross_process_tracks():
    """A request admitted on replica_0 and finished (journal) after a
    migration must render as ONE s->t->f flow chain keyed by trace_id,
    crossing process tracks — the failover made visible as geometry."""
    flight0 = [_tick(0, 10.0, admitted=[3], admit=0.1)]
    flight1 = [_tick(5, 12.0, retired=[(3, "length")], admit=0.1)]
    journal = [
        {"kind": "accept", "ts": 10.0, "rid": 3, "trace_id": "t3",
         "replica": 0},
        {"kind": "place", "ts": 11.0, "rid": 3, "trace_id": "t3",
         "replica": 1},
        {"kind": "finish", "ts": 12.5, "rid": 3, "trace_id": "t3",
         "replica": 1, "finish": "length"},
    ]
    doc = tl.build_timeline(
        [{"name": "router", "flight": []},
         {"name": "replica_0", "flight": flight0},
         {"name": "replica_1", "flight": flight1}],
        journal=journal)        # trace_map fed by the journal itself
    evts = doc["traceEvents"]
    assert doc["otherData"]["trace_count"] == 1
    flows = [e for e in evts if e.get("cat") == "trace"]
    assert [e["ph"] for e in flows] == ["s", "t", "t", "t", "f"]
    assert all(e["id"] == "t3" for e in flows)
    assert flows[-1]["bp"] == "e"           # bind the finish enclosingly
    # the chain crosses from replica_0's track onto replica_1's
    assert {e["pid"] for e in flows} == {1, 2}
    # journal instants land on the replica's process, kind-labeled
    ji = [e for e in evts if e["ph"] == "i" and e["tid"] == 3]
    assert {e["name"] for e in ji} == {"journal:accept", "journal:place",
                                       "journal:finish"}
    accept = next(e for e in ji if e["name"] == "journal:accept")
    assert accept["pid"] == 1 and accept["args"]["trace_id"] == "t3"


def test_build_timeline_spans_and_trace_map():
    """Tracer spans land on per-request threads (request_id attr) or
    the spans thread, and an explicit trace_map links span + flight
    touch points into a flow (the single-engine, no-journal path)."""
    spans = [{"name": "serving.request", "ts": 20.0, "dur_s": 1.5,
              "attrs": {"request_id": 9, "trace_id": "t9",
                        "finish": "eos"}},
             {"name": "serving.spec_verify", "ts": 20.5, "dur_s": 0.1,
              "attrs": {"slots": 2}}]
    flight = [_tick(0, 20.2, admitted=[9], admit=0.05)]
    doc = tl.build_timeline(
        [{"name": "engine", "flight": flight, "spans": spans}],
        trace_map={9: "t9"})
    evts = doc["traceEvents"]
    req = next(e for e in evts if e["name"] == "serving.request")
    verify = next(e for e in evts if e["name"] == "serving.spec_verify")
    assert req["tid"] == verify["tid"] + 15     # req track vs tid 1
    assert req["args"]["finish"] == "eos"
    assert doc["otherData"]["trace_count"] == 1
    assert sum(1 for e in evts if e.get("cat") == "trace") == 2


def test_write_timeline_roundtrip(tmp_path):
    p = str(tmp_path / "t.json")
    info = tl.write_timeline(
        p, processes=[{"name": "e",
                       "flight": [_tick(0, 1.0, admitted=[1],
                                        admit=0.1)]}])
    assert info["path"] == p and info["trace_count"] == 0
    doc = json.load(open(p))
    assert len(doc["traceEvents"]) == info["events"]
    assert doc["otherData"]["trace_count"] == 0
    # the package facade exports the same callables
    assert obs.write_timeline is tl.write_timeline
    assert obs.build_timeline is tl.build_timeline


# ---- trace-continuity checker ----------------------------------------------

def test_verify_trace_continuity_clean_chain_is_empty():
    events = [
        {"kind": "accept", "rid": 1, "trace_id": "a"},
        {"kind": "place", "rid": 1, "trace_id": "a"},
        {"kind": "finish", "rid": 1, "trace_id": "a"},
    ]
    assert tl.verify_trace_continuity(events, accepted_rids=[1],
                                      require_finish=True) == []


def test_verify_trace_continuity_flags_breaks():
    events = [
        {"kind": "accept", "rid": 1},                       # no trace_id
        {"kind": "accept", "rid": 2, "trace_id": "b"},
        {"kind": "place", "rid": 2, "trace_id": "FORK"},    # orphan
        {"kind": "finish", "rid": 2, "trace_id": "b"},
        {"kind": "accept", "rid": 3, "trace_id": "c"},
        {"kind": "finish", "rid": 3},                       # id dropped
    ]
    probs = tl.verify_trace_continuity(events, accepted_rids=[1, 2, 3, 4])
    assert any("rid 1" in p and "no trace_id" in p for p in probs)
    assert any("rid 2" in p and "orphan fragment" in p for p in probs)
    assert any("rid 3" in p and "finish has no trace_id" in p
               for p in probs)
    assert any("rid 4" in p and "never journaled" in p for p in probs)
    # require_finish: an accepted request whose chain never terminates
    probs2 = tl.verify_trace_continuity(
        [{"kind": "accept", "rid": 5, "trace_id": "e"}],
        require_finish=True)
    assert probs2 == ["rid 5: no finish event (chain never terminates)"]
