"""The serving tick measured from inside (``ServingEngine._phase``).

One helper times each phase of ``step()`` once and feeds that one number
to the profiler span, ``engine.stats``, the ``serving.step_*_s``
histogram and the flight event. Pinned here: the six self times
partition the tick; ``step_upload_s`` is a part of admit and
``upload_ticks`` counts the ticks after a join or a leave; the four
segments that existed before keep their boundaries; a profile holds the
``serving.step.*`` spans properly nested, on the plain, the speculative
and the chunked tick alike; a tick that dies closes what it opened.

Timings here are CPU host seconds at toy widths: counts of work that
must add up, never a speed.
"""

import glob
import os
import time

import numpy as np
import pytest

import jax

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import faults
from paddle_tpu.serving import SpecConfig

SIX = ("step_admit_s", "step_prefill_s", "step_dispatch_s", "step_sync_s",
       "step_commit_s", "step_tail_s")
PHASES = ("admit", "prefill", "upload", "dispatch", "sync", "commit",
          "tail")
PROMPT = 20     # one 64-token block holds prompt + 40 tokens: no lazy
                # block, so only joins and leaves dirty the mirrors


def tiny_llama():
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return m


def make_engine(**kw):
    opts = dict(max_slots=3, block_tokens=64, max_seq_len=512,
                prefix_caching=False)
    opts.update(kw)
    return serving.ServingEngine(tiny_llama(), **opts)


def prompt(rng, n=PROMPT):
    return rng.randint(3, 512, (n,))


def warm(eng, rng, n=PROMPT):
    """Compile the prefill bucket and the step program outside what a
    test measures."""
    eng.submit(serving.Request(prompt(rng, n), max_new_tokens=3))
    eng.drain(max_steps=50)
    eng.reset_stats()


def run_ticks(eng, rng, n_ticks, joins):
    """``n_ticks`` ticks with one long request running throughout and a
    short one submitted before each tick in ``joins`` ({tick: max_new}).
    Returns one record a tick: wall seconds around ``step()``, the
    change of every ``stats`` value, and the tick's flight event."""
    eng.submit(serving.Request(prompt(rng), max_new_tokens=40))
    out = []
    for i in range(n_ticks):
        if i in joins:
            eng.submit(serving.Request(prompt(rng), max_new_tokens=joins[i]))
        before = dict(eng.stats)
        t0 = time.perf_counter()
        eng.step()
        wall = time.perf_counter() - t0
        out.append(dict(
            wall=wall, evt=eng.flight.events()[-1],
            d={k: eng.stats[k] - before[k] for k in before}))
    return out


@pytest.fixture(scope="module")
def ticks():
    """30 warm ticks of a plain engine: a wave prefill at tick 0, joins
    at ticks 5 and 15, their leaves five and three ticks later."""
    rng = np.random.RandomState(0)
    eng = make_engine()
    warm(eng, rng)
    recs = run_ticks(eng, rng, 30, {5: 6, 15: 4})
    eng.close()
    return recs


def test_six_segments_sum_to_the_wall_time_of_step(ticks):
    assert sum(1 for r in ticks if r["evt"]["admitted"]) == 3
    assert sum(1 for r in ticks if r["evt"]["retired"]) == 2
    six = sum(r["d"][k] for r in ticks for k in SIX)
    wall = sum(r["wall"] for r in ticks)
    assert six <= wall
    assert six >= 0.97 * wall, (six, wall)
    # and tick by tick nothing is counted twice
    for r in ticks:
        assert sum(r["d"][k] for k in SIX) <= r["wall"]
        assert all(r["d"][k] >= 0.0 for k in SIX)


def test_upload_is_a_part_of_admit_on_the_ticks_after_an_event(ticks):
    kinds = set()
    for prev, r in zip([None] + ticks[:-1], ticks):
        joined = bool(r["evt"]["admitted"])
        left = prev is not None and bool(prev["evt"]["retired"])
        kinds.add((joined, left))
        # a join dirties the mirrors in its own tick's admission, a
        # leave in its tick's commit: the upload runs the tick after
        assert r["d"]["upload_ticks"] == int(joined or left), r["evt"]
        assert 0.0 <= r["d"]["step_upload_s"] <= r["d"]["step_admit_s"]
        assert (r["d"]["step_upload_s"] > 0.0) == (joined or left)
    assert kinds == {(True, False), (False, True), (False, False)}


def test_the_four_old_segments_keep_their_boundaries(ticks):
    for r in ticks:
        d, evt = r["d"], r["evt"]
        if evt["prefills"]:
            assert d["step_prefill_s"] > 0.0
            # admit excludes it: were the wave inside admit too, the
            # segments would add up to more than the tick
            assert (d["step_admit_s"] + d["step_prefill_s"]
                    + d["step_dispatch_s"] + d["step_sync_s"]) <= r["wall"]
        else:
            assert d["step_prefill_s"] == 0.0       # exactly: no _Phase ran
        # the flight event and stats hold the same numbers (a join
        # behind a step program in flight has no sync phase: the joint
        # pull is a part of its prefill, and the field reads None)
        for key, field in (("step_admit_s", "t_admit_s"),
                           ("step_prefill_s", "t_prefill_s"),
                           ("step_dispatch_s", "t_dispatch_s"),
                           ("step_sync_s", "t_sync_s"),
                           ("step_commit_s", "t_commit_s")):
            assert (evt[field] or 0.0) == pytest.approx(d[key], abs=2e-6)


def test_sync_ends_when_the_pull_returns_and_commit_takes_the_rest():
    rng = np.random.RandomState(1)
    eng = make_engine()
    warm(eng, rng)
    eng.submit(serving.Request(prompt(rng), max_new_tokens=8))
    eng.step()
    eng.step()
    reg = obs.registry()
    n_commit = reg.histogram("serving.step_commit_s").count
    n_tail = reg.histogram("serving.step_tail_s").count

    def slowed(fn):
        def wrapper(*a, **kw):
            out = fn(*a, **kw)
            time.sleep(0.05)
            return out
        return wrapper

    def delta(attr):
        plain = getattr(eng, attr)
        setattr(eng, attr, slowed(plain))
        before = dict(eng.stats)
        eng.step()
        setattr(eng, attr, plain)
        return {k: eng.stats[k] - before[k] for k in SIX}

    d = delta("_fence_chunk_pulls")       # the pull itself
    assert d["step_sync_s"] >= 0.05 > d["step_commit_s"]
    d = delta("_commit_plain")            # everything behind it
    assert d["step_commit_s"] >= 0.05 > d["step_sync_s"]
    d = delta("_record_flight")           # the tick's own telemetry
    assert d["step_tail_s"] >= 0.05 > d["step_commit_s"]
    assert reg.histogram("serving.step_commit_s").count == n_commit + 3
    assert reg.histogram("serving.step_tail_s").count == n_tail + 3
    eng.close()


@pytest.mark.parametrize("kind", ["speculative", "chunked"])
def test_the_same_phases_on_a_speculative_and_a_chunked_engine(kind):
    rng = np.random.RandomState(2)
    if kind == "speculative":
        eng = make_engine(speculate=SpecConfig(k=3, proposer="ngram"))
        n = PROMPT
    else:
        eng = make_engine(block_tokens=16, chunk_tokens=32)
        n = 70          # three chunks: two mid ticks and a last one
    warm(eng, rng, n)
    eng.submit(serving.Request(prompt(rng, n), max_new_tokens=6))
    wall = 0.0
    events = []
    while not eng.idle:
        t0 = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t0
        events.append(eng.flight.events()[-1])
    st = eng.stats
    if kind == "speculative":
        assert st["spec_ticks"] > 0
    else:
        assert st["prefill_chunks"] == 3
        # a fused chunk tick is a dispatch, a sync and a commit
        # (_commit_chunk) like any other
        assert all(e["t_commit_s"] > 0.0 for e in events if e["chunks"])
    assert all(st[k] > 0.0 for k in SIX if k != "step_prefill_s")
    assert 0.97 * wall <= sum(st[k] for k in SIX) <= wall
    assert all(e["t_dispatch_s"] is not None and e["t_commit_s"] is not None
               for e in events)
    eng.close()


def test_the_phase_helper_costs_microseconds_a_tick():
    """With no profile running a phase is one ``_Phase``, one
    annotation (a C++ flag test) and two clock reads. An event tick
    opens six of them inside the step annotation; the budget for that
    is 20 us (0.3 % of a 7 ms tick), which a warm loop meets with room
    (PERF.md has the number), and this bound leaves a loaded test
    machine five times as much."""
    eng = make_engine()
    spent = []
    for i in range(3000):
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("serving.step", step_num=i):
            eng._tick_s = {}
            with eng._phase("serving.step.admit"):
                with eng._phase("serving.step.upload"):
                    pass
            with eng._phase("serving.step.dispatch"):
                pass
            with eng._phase("serving.step.sync"):
                pass
            with eng._phase("serving.step.commit") as ph:
                ph.set(retired=0)
            with eng._phase("serving.step.tail"):
                pass
        spent.append(time.perf_counter() - t0)
    eng.close()
    per_tick = sorted(spent)[len(spent) // 2]
    assert per_tick < 100e-6, f"{per_tick * 1e6:.1f} us a tick"
    assert set(eng._tick_s) == {"step_admit_s", "step_upload_s",
                                "step_dispatch_s", "step_sync_s",
                                "step_commit_s", "step_tail_s"}


# ---- the spans, read back from a profile ------------------------------------

def _capture(run, out_dir):
    """Run ``run()`` under the profiler (our annotations and XLA's, no
    Python tracer) and return every ``serving.*`` host event as
    (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("serving.")]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """One capture for both span tests: a join tick and two steady
    ticks, then a tick killed at its dispatch, then two more."""
    rng = np.random.RandomState(3)
    eng = make_engine()
    warm(eng, rng)
    seen = {}

    def run():
        seen["rid"] = eng.submit(
            serving.Request(prompt(rng), max_new_tokens=12))
        seen["first"] = eng._step_seq
        for _ in range(3):
            eng.step()
        seen["killed"] = eng._step_seq
        with faults.plan(faults.Fault("decode.dispatch", kind="raise",
                                      at=0)):
            with pytest.raises(RuntimeError, match="injected fault"):
                eng.step()
        for _ in range(2):
            eng.step()

    seen["events"] = _capture(run, str(tmp_path_factory.mktemp("prof")))
    seen["flight"] = eng.flight.events()
    eng.close()
    return seen


def test_a_profile_holds_one_step_span_a_tick_with_its_phases_inside(
        profile):
    events, first = profile["events"], profile["first"]
    steps = [e for e in events if e[0] == "serving.step"]
    by_num = {e[3]["step_num"]: e for e in steps}
    assert sorted(by_num) == list(range(first, first + 6))     # one a tick
    names = {e[0] for e in events}
    assert names == ({"serving.submit", "serving.step"}
                     | {f"serving.step.{p}" for p in PHASES})
    phases = [e for e in events if e[0].startswith("serving.step.")]
    assert all(_inside(e, steps) for e in phases)
    admits = [e for e in events if e[0] == "serving.step.admit"]
    for e in events:
        if e[0] in ("serving.step.prefill", "serving.step.upload"):
            assert _inside(e, admits), e
    # the three good ticks: the join tick holds the wave and the upload,
    # the steady ones neither; phases of one tick follow one another
    for k, num in enumerate(range(first, first + 3)):
        tick = [e[0][len("serving.step."):] for e in phases
                if _inside(e, [by_num[num]])]
        assert tick == (list(PHASES) if k == 0 else
                        ["admit", "dispatch", "sync", "commit", "tail"])
        own = [e for e in phases if _inside(e, [by_num[num]])
               and e[0][len("serving.step."):] not in ("prefill", "upload")]
        assert all(a[2] <= b[1] for a, b in zip(own, own[1:]))
    # attributes are event stats, never part of the name
    (wave,) = [e for e in events if e[0] == "serving.step.prefill"]
    assert wave[3] == {"rows": 1, "s_pad": 64, "R": 0}
    (sub,) = [e for e in events if e[0] == "serving.submit"]
    assert sub[3] == {"request_id": profile["rid"]}
    assert all("retired" in e[3] for e in events
               if e[0] == "serving.step.commit")


def test_a_killed_tick_leaves_no_span_open(profile):
    events, killed = profile["events"], profile["killed"]
    by_num = {e[3]["step_num"]: e for e in events if e[0] == "serving.step"}
    dead = by_num[killed]
    inside = [e[0] for e in events if e is not dead and _inside(e, [dead])]
    # it died in admission, at the dispatch fault site: the two spans
    # that were open closed, and nothing later was ever opened
    assert inside == ["serving.step.admit"]
    # the ticks after it are whole, and no span of theirs is the child
    # of one the dead tick left behind
    for num in (killed + 1, killed + 2):
        nxt = by_num[num]
        assert nxt[1] >= dead[2]
        assert [e[0] for e in events if e is not nxt
                and _inside(e, [nxt])] == [
            f"serving.step.{p}" for p in
            ("admit", "dispatch", "sync", "commit", "tail")]
    # and the partial flight event is written as before
    evt = next(e for e in profile["flight"] if e.get("step") == killed)
    assert "injected fault" in evt["err"]
    assert evt["t_admit_s"] > 0.0
    assert evt["t_dispatch_s"] is None and evt["t_commit_s"] is None
