"""python -m pytest benchmark/tests  (JAX_PLATFORMS=cpu; no chip needed)"""

import json
import os

import numpy as np
import pytest

from harness import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-1k", "chat-2k"])
def test_one_seed_one_schedule(name):
    a = loadgen.open_schedule(mix(name), 5.0, 20.0, 50257, seed=7)
    b = loadgen.open_schedule(mix(name), 5.0, 20.0, 50257, seed=7)
    c = loadgen.open_schedule(mix(name), 5.0, 20.0, 50257, seed=8)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    # another seed replays the mix's one trace (instants, lengths) with
    # other token ids
    assert [r.due_s for r in a] == [r.due_s for r in c]
    assert [(len(r.prompt), r.max_new) for r in a] == [
        (len(r.prompt), r.max_new) for r in c]
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["chat-1k", "chat-2k", "offline-2k"])
def test_lengths_inside_clips_and_context(name):
    t = mix(name)
    rng = np.random.default_rng(0)
    reqs = loadgen.make_requests(t, 500, 50257, rng, rng)
    plo, phi = loadgen.length_range(t["prompt_len"])
    olo, ohi = loadgen.length_range(t["output_len"])
    assert all(plo <= len(r.prompt) <= phi for r in reqs)
    assert all(olo <= r.max_new <= ohi for r in reqs)
    assert phi + ohi <= t["engine"]["max_seq_len"]
    assert all(3 <= r.prompt.min() and r.prompt.max() < 50257 for r in reqs)
    buckets = loadgen.prompt_buckets(t["prompt_len"],
                                     t["engine"]["block_tokens"])
    assert all(-(-len(r.prompt) // 128) * 128 in buckets for r in reqs)


def test_every_trace_offers_the_same_work(monkeypatch):
    t = mix("chat-2k")
    a = loadgen.open_schedule(t, 5.0, 40.0, 92544, seed=1)
    monkeypatch.setattr(loadgen, "TRACE_SEED", 5)
    b = loadgen.open_schedule(t, 5.0, 40.0, 92544, seed=1)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    measured = lambda rs: [r for r in rs if r.due_s >= 0]
    assert len(measured(a)) == len(measured(b)) == 200
    assert (sorted(len(r.prompt) for r in measured(a))
            == sorted(len(r.prompt) for r in measured(b)))
    assert (sorted(r.max_new for r in measured(a))
            == sorted(r.max_new for r in measured(b)))
    assert all(-t["warm_s"] <= r.due_s < 40.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


def test_stratified_lengths_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 200, "sigma": 0.8,
            "min": 16, "max": 768}
    x = loadgen.draw_lengths(spec, 1001, np.random.default_rng(0))
    assert abs(np.median(x) - 200) <= 1
    flat = {"dist": "uniform", "min": 1537, "max": 2048}
    y = loadgen.draw_lengths(flat, 512, np.random.default_rng(0))
    assert sorted(y) == list(range(1537, 2049))
    assert loadgen.prompt_buckets(flat, 128) == [1664, 1792, 1920, 2048]


def test_poisson_arrivals_keep_the_count_and_the_window():
    rng = np.random.default_rng(3)
    due = loadgen.arrivals({"process": "poisson"}, 400, 0.0, 40.0, rng)
    assert len(due) == 400 and (np.diff(due) >= 0).all()
    assert due.min() >= 0.0 and due.max() < 40.0
    with pytest.raises(ValueError, match="unknown arrival process"):
        loadgen.arrivals({"process": "onoff"}, 4, 0.0, 1.0, rng)


def test_lateness_is_counted_from_the_due_time():
    reqs = [loadgen.Req(i, d, np.zeros(4, np.int32), 4)
            for i, d in enumerate([0.10, 0.20, 0.21, 0.90])]
    f = loadgen.Feeder(reqs)
    f.poll(0.05)
    assert not f.waiting and f.next_due() == 0.10
    f.poll(0.25)            # the loop was busy from 0.05 to 0.25
    assert [r.index for r in f.waiting] == [0, 1, 2]
    late = [f.seen_s[r.index] - r.due_s for r in f.waiting]
    assert late == pytest.approx([0.15, 0.05, 0.04])
    f.waiting.clear()
    assert not f.exhausted
    f.poll(1.0)
    f.waiting.clear()
    assert f.exhausted and f.next_due() is None


class _FakeResult:
    def __init__(self, n):
        self.tokens, self.finish = np.zeros(n, np.int32), "length"
        self.ttft_s, self.tpot_s = 0.01, 0.001


class _FakeEngine:
    """One slot, one token a step; enough to drive the serving loop."""

    def __init__(self):
        self.stats = dict(steps=0)
        self._queue, self._slot, self._left = [], None, 0
        self.submitted = []

    queued = property(lambda self: len(self._queue))
    idle = property(lambda self: self._slot is None and not self._queue)

    def submit(self, req):
        self._queue.append(req)
        self.submitted.append(req)
        return len(self.submitted)

    def step(self):
        finished = []
        if self._slot is None and self._queue:
            self._slot = len(self.submitted) - len(self._queue) + 1
            self._left = self._queue.pop(0).max_new_tokens
        if self._slot is not None:
            self.stats["steps"] += 1
            self._left -= 1
            if self._left == 0:
                finished, self._slot = [self._slot], None
        self._clock.t += 0.01
        return dict(queued=len(self._queue), finished=finished)

    def pop_result(self, rid):
        return _FakeResult(self.submitted[rid - 1].max_new_tokens)


def test_the_loop_hands_over_one_request_while_the_engine_queue_is_empty():
    from harness import capture, serve

    class Clock:
        t = 0.0

        def __call__(self):
            self.t += 1e-4
            return self.t

    class Request:
        def __init__(self, prompt, max_new_tokens):
            self.prompt, self.max_new_tokens = prompt, max_new_tokens

    class Serving:
        pass

    Serving.Request = Request
    eng, clock = _FakeEngine(), Clock()
    eng._clock = clock
    reqs = [loadgen.Req(i, 0.0, np.zeros(4, np.int32), 5) for i in range(4)]
    recs, ticks, marks = serve.drive(
        eng, Serving, loadgen.Feeder(reqs), seconds=1.0, warm_s=0.0,
        cap=capture.Capture(False, "", 0.0), clock=clock)
    assert len(recs) == 4 and all(r["result"] is not None
                                  for r in recs.values())
    # all four were due at 0; the engine's queue never held more than one
    submits = sorted(r["submit"] for r in recs.values())
    assert submits[1] - submits[0] < 0.02 and submits[3] > 0.09
    # the wait in the generator's own queue is inside (submit - due)
    assert max(r["submit"] - r["due"] for r in recs.values()) > 0.09
    assert marks["open"]["t"] < marks["middle"]["t"] < marks["close"]["t"]
