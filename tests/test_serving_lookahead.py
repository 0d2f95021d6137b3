"""One decode step program kept in flight (``ServingEngine._decode``).

On a plain steady tick ``step()`` dispatches the NEXT step program
before it pulls the tokens of the one in flight, so the device has a
program queued while the pull travels back and the host commits. Pinned
here, at toy widths on the CPU (order and tokens, never a speed): when
the lookahead engages and what every call commits; that the tokens are
those of an isolated ``generate``; what happens to the one token
computed for a row that has left (EOS, length); joins, leaves, deadline
sweeps, preemptions, snapshots, ``close`` and a killed tick with a
program in flight; that speculative, chunked and offloading engines keep
their own order on their special ticks; and that the six phases still
partition the tick when a landing runs inside admit or prefill.
"""

import json
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.inference import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import faults
from paddle_tpu.serving import SpecConfig

SIX = ("step_admit_s", "step_prefill_s", "step_dispatch_s", "step_sync_s",
       "step_commit_s", "step_tail_s")


def tiny_llama():
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return m


def tiny_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle_tpu.seed(0)
    g = GPTPretrainModel(cfg)
    g.eval()
    return g


@pytest.fixture(scope="module")
def llama():
    return tiny_llama()


def make_engine(model, **kw):
    opts = dict(max_slots=3, block_tokens=64, max_seq_len=256,
                prefix_caching=False)
    opts.update(kw)
    return serving.ServingEngine(model, **opts)


def isolated(model, prompts, max_new, **kw):
    return [np.asarray(generate(model, p[None], max_new_tokens=n,
                                temperature=0.0, **kw))[0, len(p):].tolist()
            for p, n in zip(prompts, max_new)]


def warm(eng, rng, vocab=256):
    """Compile the prefill bucket and the step program, and let the
    estimator see one step: the lookahead waits for a warm program."""
    eng.submit(serving.Request(rng.randint(3, vocab, (20,)),
                               max_new_tokens=4))
    eng.drain(max_steps=50)
    assert not eng._flight_q
    eng.reset_stats()


def tokens_held(eng):
    return {s.req.request_id: len(s.tokens) for s in eng._slots
            if s is not None}


# (a) ---------------------------------------------------------------------

def test_a_steady_run_looks_ahead_and_every_call_commits_one_step(llama):
    rng = np.random.RandomState(0)
    eng = make_engine(llama)
    warm(eng, rng)
    rids = [eng.submit(serving.Request(rng.randint(3, 512, (20,)),
                                       max_new_tokens=n))
            for n in (24, 24, 30)]
    calls = 0
    while not eng.idle:
        before, steps0 = tokens_held(eng), eng.stats["steps"]
        st = eng.step()
        calls += 1
        after = tokens_held(eng)
        landed = eng.stats["steps"] - steps0
        assert landed in (0, 1)
        for rid, n in after.items():
            # a row that was decoding gains exactly the landed step's
            # token; a row that joined in this call holds its first
            if rid in before:
                assert n - before[rid] == landed
        for rid in st["finished"]:
            assert len(eng.results[rid].tokens) == before.get(rid, 0) + 1
        if eng.flight.events()[-1]["lookahead"]:
            assert len(eng._flight_q) == 1 and landed == 1
    st = eng.stats
    # three rows, no lazy block (64-token blocks): the only events are
    # the join and two leaves, every other decode tick looks ahead
    assert st["lookahead_ticks"] >= st["steps"] - 4 > 20
    assert st["decode_tokens"] == 24 + 24 + 30 - 3
    # each request left at its length one token after a program that
    # still held its row had gone out
    assert 0 < st["lookahead_discarded_tokens"] <= 3
    assert calls <= st["steps"] + 2
    assert obs.registry().counter("serving.lookahead_ticks").value >= \
        st["lookahead_ticks"]
    assert all(eng.results[r].finish == "length" for r in rids)
    eng.close()


# (b) ---------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama", "gpt"])
@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_b_tokens_are_those_of_requests_run_one_at_a_time(arch, cache):
    model = tiny_llama() if arch == "llama" else tiny_gpt()
    vocab = 512 if arch == "llama" else 256
    kw = dict(cache_dtype=jnp.int8) if cache == "int8" else {}
    rng = np.random.RandomState(2)
    prompts = [rng.randint(3, vocab, (n,)) for n in (7, 19, 33, 12)]
    max_new = [22, 9, 26, 15]
    ref = isolated(model, prompts, max_new, **kw)
    # 16-token blocks: rows cross a block boundary every few ticks, so
    # lazy blocks, joins and leaves all interleave with the lookahead
    eng = make_engine(model, block_tokens=16, max_seq_len=128, **kw)
    free0 = eng.pool.free_blocks
    rids = []
    for p, n in zip(prompts, max_new):
        rids.append(eng.submit(serving.Request(p, max_new_tokens=n)))
        eng.step()
        eng.step()
    eng.drain(max_steps=300)
    for rid, want in zip(rids, ref):
        assert eng.results[rid].tokens.tolist() == want
    assert eng.stats["lookahead_ticks"] > 10
    assert eng.stats["decode_tokens"] == sum(max_new) - len(prompts)
    assert eng.pool.free_blocks == free0 and not eng._flight_q
    eng.close()


# (c) ---------------------------------------------------------------------

def test_c_the_token_after_eos_is_thrown_away(llama):
    rng = np.random.RandomState(4)
    p = rng.randint(3, 512, (11,))
    (full,) = isolated(llama, [p], [14])
    eos = full[6]
    assert eos not in full[:6]

    def run(slots):
        eng = make_engine(llama, max_slots=slots, eos_token_id=eos)
        warm(eng, rng, 512)
        free0 = eng.pool.free_blocks
        rid = eng.submit(serving.Request(p, max_new_tokens=14))
        other = None
        if slots > 1:
            other = eng.submit(serving.Request(rng.randint(3, 512, (9,)),
                                               max_new_tokens=12))
        eng.drain(max_steps=100)
        return eng, free0, eng.results[rid], other

    serial, free0, want, _ = run(1)
    assert want.finish == "eos" and want.gen_len == 6
    assert want.tokens.tolist() == full[:7]
    # the program after the one that sampled EOS was already out
    assert serial.stats["lookahead_discarded_tokens"] == 1
    assert serial.stats["decode_tokens"] == 6       # not 7
    assert serial.pool.free_blocks == free0 and serial.idle
    assert not serial._flight_q     # all its rows gone: dropped, no pull
    serial.close()

    eng, free0, got, other = run(2)
    assert (got.finish, got.gen_len, got.tokens.tolist()) == (
        "eos", 6, want.tokens.tolist())
    res = eng.results[other]
    assert len(res.tokens) in (12, res.gen_len + 1)
    assert eng.stats["lookahead_discarded_tokens"] >= 1
    assert eng.pool.free_blocks == free0 and eng.idle
    eng.close()


# (d) ---------------------------------------------------------------------

def test_d_a_join_and_a_leave_with_a_program_in_flight(llama):
    rng = np.random.RandomState(5)
    prompts = [rng.randint(3, 512, (n,)) for n in (20, 31, 9)]
    max_new = [30, 8, 12]
    ref = isolated(llama, prompts, max_new)
    eng = make_engine(llama)
    warm(eng, rng, 512)
    a = eng.submit(serving.Request(prompts[0], max_new_tokens=30))
    b = eng.submit(serving.Request(prompts[1], max_new_tokens=8))
    for _ in range(4):
        eng.step()
    assert len(eng._flight_q) == 1 and eng.stats["lookahead_ticks"] >= 2
    held = tokens_held(eng)
    # the join: the wave goes out behind the program in flight, and its
    # first token and that step's tokens come back in one pull
    c = eng.submit(serving.Request(prompts[2], max_new_tokens=12))
    pulls = []
    plain = eng._fence_chunk_pulls
    eng._fence_chunk_pulls = lambda g, k, outs, head: (
        pulls.append(len(head)) or plain(g, k, outs, head))
    eng.step()
    eng._fence_chunk_pulls = plain
    assert pulls == [2]             # step tokens + the wave's, one pull
    evt = eng.flight.events()[-1]
    assert evt["admitted"] == [c] and not evt["lookahead"]
    # that pull is a part of the prefill phase, which has always held
    # the wait for the wave's program, and feeds no step estimate
    assert evt["t_sync_s"] is None and evt["t_prefill_s"] > 0.0
    assert evt["t_commit_s"] > 0.0 and eng._tick_decode_s() is None
    now = tokens_held(eng)
    assert now[a] == held[a] + 1 and now[b] == held[b] + 1 and now[c] == 1
    assert len(eng._flight_q) == 1  # the pipeline refilled at once
    assert eng.stats["upload_ticks"] >= 2
    # the leave: b finishes while a and c decode on
    eng.drain(max_steps=100)
    for rid, want in zip((a, b, c), ref):
        assert eng.results[rid].tokens.tolist() == want
    assert eng.pool.used_blocks == 0
    eng.close()


# (e) ---------------------------------------------------------------------

def test_e_snapshot_and_restore_with_a_program_in_flight(llama):
    rng = np.random.RandomState(6)
    prompts = [rng.randint(3, 512, (n,)) for n in (14, 25)]
    ref = isolated(llama, prompts, [20, 16])
    eng = make_engine(llama)
    warm(eng, rng, 512)
    rids = [eng.submit(serving.Request(p, max_new_tokens=n))
            for p, n in zip(prompts, (20, 16))]
    for _ in range(5):
        eng.step()
    assert len(eng._flight_q) == 1
    held = tokens_held(eng)
    snap = json.loads(json.dumps(eng.snapshot()))
    # the snapshot landed it: every token the device was asked for
    assert not eng._flight_q
    assert {d["request_id"]: len(d["tokens"]) for d in snap["slots"]} == {
        r: n + 1 for r, n in held.items()}
    # the engine decodes on, token-exact, after the landing outside a tick
    eng.drain(max_steps=100)
    eng2 = serving.ServingEngine.restore(llama, snap)
    eng2.drain(max_steps=200)
    for rid, want in zip(rids, ref):
        assert eng.results[rid].tokens.tolist() == want
        assert eng2.results[rid].tokens.tolist() == want
    assert eng.pool.used_blocks == 0 and eng2.pool.used_blocks == 0
    eng.close()
    eng2.close()


def test_e_a_request_that_finishes_in_a_landing_outside_a_tick_is_reported(
        llama):
    rng = np.random.RandomState(7)
    eng = make_engine(llama)
    warm(eng, rng, 512)
    rid = eng.submit(serving.Request(rng.randint(3, 512, (10,)),
                                     max_new_tokens=30))
    short = eng.submit(serving.Request(rng.randint(3, 512, (10,)),
                                       max_new_tokens=5))
    finished = []
    while tokens_held(eng).get(short, 0) < 4:   # one before its last
        finished += eng.step()["finished"]
    assert len(eng._flight_q) == 1 and not finished
    eng.snapshot()
    assert short in eng.results and eng.active_slots == 1
    finished += eng.step()["finished"]
    assert finished == [short]
    eng.drain(max_steps=100)
    assert len(eng.results[rid].tokens) == 30
    eng.close()


def test_e_close_with_a_program_in_flight_leaks_nothing(llama):
    rng = np.random.RandomState(8)
    eng = make_engine(llama)
    warm(eng, rng, 512)
    eng.submit(serving.Request(rng.randint(3, 512, (10,)),
                               max_new_tokens=30))
    for _ in range(4):
        eng.step()
    assert len(eng._flight_q) == 1
    eng.close()
    assert eng.closed and not eng._flight_q and eng.kv_pool is None
    eng.close()                     # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()


# (f) ---------------------------------------------------------------------

def test_f_a_killed_tick_keeps_the_program_in_flight(llama, tmp_path):
    rng = np.random.RandomState(9)
    prompts = [rng.randint(3, 512, (n,)) for n in (12, 21)]
    ref = isolated(llama, prompts, [18, 18])
    dump = str(tmp_path / "flight.jsonl")
    eng = make_engine(llama, flight_dump_path=dump)
    warm(eng, rng, 512)
    rids = [eng.submit(serving.Request(p, max_new_tokens=18))
            for p in prompts]
    for _ in range(4):
        eng.step()
    assert len(eng._flight_q) == 1
    held, steps = tokens_held(eng), eng.stats["steps"]
    with faults.plan(faults.Fault("decode.dispatch", kind="raise", at=0)) \
            as plan:
        with pytest.raises(RuntimeError, match="injected fault"):
            eng.step()
        assert [f.site for f in plan.fired()] == ["decode.dispatch"]
    # the dead tick changed nothing: same program in flight, no token
    assert len(eng._flight_q) == 1 and tokens_held(eng) == held
    assert eng.stats["steps"] == steps
    eng.step()                      # the retried tick looks ahead again
    assert eng.flight.events()[-1]["lookahead"]
    assert tokens_held(eng) == {r: n + 1 for r, n in held.items()}
    eng.drain(max_steps=100)
    for rid, want in zip(rids, ref):
        assert eng.results[rid].tokens.tolist() == want
    dumps = [json.loads(ln) for ln in open(dump)]
    reasons = [d["reason"] for d in dumps if d.get("kind") == "flight_dump"]
    assert reasons.count("error:RuntimeError") == 1
    assert set(reasons) == {"error:RuntimeError",
                            "fault:decode.dispatch:raise"}
    eng.close()


# (g) ---------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["speculate", "chunk_tokens", "offload"])
def test_g_special_ticks_land_first_and_keep_their_order(llama, kind):
    rng = np.random.RandomState(10)
    opts = dict(speculate=dict(speculate=SpecConfig(k=3, proposer="ngram")),
                chunk_tokens=dict(block_tokens=16, chunk_tokens=32),
                offload=dict(offload=True, host_pool_blocks=16,
                             block_tokens=16))[kind]
    prompts = [rng.randint(3, 512, (n,)) for n in (70, 40, 24)]
    max_new = [14, 20, 10]
    ref = isolated(llama, prompts, max_new)
    eng = make_engine(llama, max_slots=2, **opts)
    warm(eng, rng, 512)
    rids = [eng.submit(serving.Request(p, max_new_tokens=n))
            for p, n in zip(prompts[:2], max_new[:2])]
    special = plain = ticks = 0
    while not eng.idle:
        if ticks == 6:      # both slots taken: it preempts one of them
            rids.append(eng.submit(serving.Request(
                prompts[2], max_new_tokens=max_new[2], priority="high")))
        ticks += 1
        q0 = len(eng._flight_q)
        eng.step()
        evt = eng.flight.events()[-1]
        if (evt["chunks"] or evt["spec_proposed"] is not None
                or evt["swapped_out"] or evt["swapped_in"]
                or evt["preempted"]):
            # the special tick: nothing was launched ahead of a pull in
            # it, and it leaves nothing in flight behind it
            special += 1
            assert not evt["lookahead"]
            assert not eng._flight_q or not (evt["chunks"]
                                             or evt["spec_proposed"])
        else:
            plain += evt["lookahead"]
        assert q0 <= 1
    assert special > 0
    if kind == "speculate":
        assert eng.stats["lookahead_ticks"] == 0 == plain
    else:
        assert eng.stats["lookahead_ticks"] == plain > 0
    for rid, want in zip(rids, ref):
        assert eng.results[rid].tokens.tolist() == want
    eng.close()


# ---- what lands the program in flight -----------------------------------

def test_a_deadline_sweep_lands_first_so_the_row_keeps_its_token(llama):
    rng = np.random.RandomState(11)
    eng = make_engine(llama)
    warm(eng, rng, 512)
    keep = eng.submit(serving.Request(rng.randint(3, 512, (10,)),
                                      max_new_tokens=30))
    cut = eng.submit(serving.Request(rng.randint(3, 512, (10,)),
                                     max_new_tokens=30, deadline_s=300.0))
    for _ in range(5):
        eng.step()
    assert len(eng._flight_q) == 1
    held = tokens_held(eng)
    eng._slots[1].deadline_at = time.perf_counter() - 1.0
    st = eng.step()
    assert st["finished"] == [cut]
    res = eng.results[cut]
    assert res.finish == "deadline" and len(res.tokens) == held[cut] + 1
    (want,) = isolated(llama, [res.prompt], [len(res.tokens)])
    assert res.tokens.tolist() == want
    eng.drain(max_steps=100)
    assert len(eng.results[keep].tokens) == 30
    assert eng.pool.used_blocks == 0
    eng.close()


def test_a_preemption_lands_first_and_the_victim_resumes_token_exact(llama):
    rng = np.random.RandomState(12)
    prompts = [rng.randint(3, 512, (n,)) for n in (16, 23, 11)]
    max_new = [26, 26, 8]
    ref = isolated(llama, prompts, max_new)
    eng = make_engine(llama, max_slots=2)
    warm(eng, rng, 512)
    low = [eng.submit(serving.Request(p, max_new_tokens=n))
           for p, n in zip(prompts[:2], max_new[:2])]
    for _ in range(5):
        eng.step()
    assert len(eng._flight_q) == 1
    held = tokens_held(eng)
    high = eng.submit(serving.Request(prompts[2], max_new_tokens=8,
                                      priority="high"))
    eng.step()
    evt = eng.flight.events()[-1]
    assert len(evt["preempted"]) == 1 and evt["admitted"] == [high]
    (victim,) = evt["preempted"]
    queued = {r.request_id: r for r in eng._queue.items()}
    # requeued with every token that was computed for it
    assert len(queued[victim]._resume_tokens) == held[victim] + 1
    eng.drain(max_steps=200)
    for rid, want in zip(low + [high], ref):
        assert eng.results[rid].tokens.tolist() == want
    assert eng.stats["preemptions"] == 1
    eng.close()


def test_release_request_lands_first(llama):
    rng = np.random.RandomState(13)
    p = rng.randint(3, 512, (15,))
    (want,) = isolated(llama, [p], [20])
    eng = make_engine(llama)
    warm(eng, rng, 512)
    rid = eng.submit(serving.Request(p, max_new_tokens=20))
    for _ in range(4):
        eng.step()
    assert len(eng._flight_q) == 1
    n = tokens_held(eng)[rid]
    toks = eng.release_request(rid)
    assert toks == want[:n + 1] and not eng._flight_q and eng.idle
    other = make_engine(llama)
    other.admit_resumable(serving.Request(p, max_new_tokens=20,
                                          request_id=rid), tokens=toks)
    other.drain(max_steps=100)
    assert other.results[rid].tokens.tolist() == want
    eng.close()
    other.close()


# ---- the phases with a landing inside admit or prefill ------------------

def test_the_six_phases_partition_event_ticks_that_land_inside_admit(llama):
    rng = np.random.RandomState(14)
    eng = make_engine(llama)
    warm(eng, rng, 512)
    eng.submit(serving.Request(rng.randint(3, 512, (20,)),
                               max_new_tokens=40))
    joins = {6: 5, 14: 4}
    six = wall = 0.0
    kinds, uncovered = set(), []
    for i in range(30):
        if i in joins:
            eng.submit(serving.Request(rng.randint(3, 512, (20,)),
                                       max_new_tokens=joins[i]))
        before = dict(eng.stats)
        q0 = len(eng._flight_q)
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
        d = {k: eng.stats[k] - before[k] for k in before}
        evt = eng.flight.events()[-1]
        assert all(d[k] >= 0.0 for k in SIX), d
        assert sum(d[k] for k in SIX) <= dt
        six += sum(d[k] for k in SIX)
        wall += dt
        uncovered.append((dt - sum(d[k] for k in SIX), dt))
        assert 0.0 <= d["step_upload_s"] <= d["step_admit_s"]
        for key, field in (("step_admit_s", "t_admit_s"),
                           ("step_prefill_s", "t_prefill_s"),
                           ("step_dispatch_s", "t_dispatch_s"),
                           ("step_sync_s", "t_sync_s"),
                           ("step_commit_s", "t_commit_s")):
            assert (evt[field] or 0.0) == pytest.approx(d[key], abs=2e-6)
        assert d["steps"] == 1
        kinds.add((bool(evt["admitted"]), bool(d["upload_ticks"]),
                   evt["lookahead"], q0))
    # a join behind a program in flight (its landing runs inside the
    # prefill), a leave (the landing runs inside admit, before the
    # upload) and the steady tick, each with a program in flight before
    assert {(True, True, False, 1), (False, True, False, 1),
            (False, False, True, 1)} <= kinds
    assert six <= wall
    # what no phase covers is the few lines between two phases' clock
    # reads (30 us a tick here). Another process can be given the core
    # between any two reads, and five other workers load it, so the
    # partition is judged tick by tick with an absolute slack, and four
    # ticks in five have to meet it: a phase left untimed would show in
    # every tick, a pre-emption shows in the one it hit
    met = sum(gap <= 0.03 * dt + 60e-6 for gap, dt in uncovered)
    assert met >= 24, sorted(uncovered)[-8:]
    eng.close()


def test_the_estimator_is_fed_the_period_of_a_lookahead_tick(llama):
    rng = np.random.RandomState(15)
    eng = make_engine(llama)
    warm(eng, rng, 512)
    eng.submit(serving.Request(rng.randint(3, 512, (20,)),
                               max_new_tokens=30))
    for _ in range(6):
        eng.step()
    assert eng.flight.events()[-1]["lookahead"]
    # a slow caller between two calls is no part of the program's time
    time.sleep(0.2)
    plain = eng._fence_chunk_pulls

    def slow_pull(*a):
        time.sleep(0.05)
        return plain(*a)

    eng._fence_chunk_pulls = slow_pull
    eng.step()
    eng._fence_chunk_pulls = plain
    t = eng._tick_s
    assert eng.flight.events()[-1]["lookahead"]
    # from the pull before it to its own pull, on the engine's clock:
    # this tick's admit + dispatch + sync and the tail of the last one
    assert 0.05 <= eng._tick_decode_s() < 0.15
    assert eng._tick_decode_s() >= (t["step_admit_s"] + t["step_dispatch_s"]
                                    + t["step_sync_s"])
    eng.close()
