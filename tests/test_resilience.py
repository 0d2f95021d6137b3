"""Fault-tolerant training & serving (paddle_tpu.resilience): fault
injection determinism, checkpoint integrity + verified resume, retry/
backoff, and the decode degradation ladder (docs/RESILIENCE.md).

The acceptance scenario rides here end-to-end on CPU: corrupt the
latest checkpoint AND kill step N → ElasticTrainLoop resumes from the
last *verified* step and the final state matches an uninterrupted run.
"""

import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import observability as obs
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.parallel.checkpoint import CheckpointManager
from paddle_tpu.parallel.elastic import (CoordinationServiceStore,
                                         ElasticManager, ElasticTrainLoop,
                                         FileHeartbeatStore, HeartbeatStore)
from paddle_tpu.resilience import (Fault, RetryPolicy, backoff_delays,
                                   call_with_retry, faults, integrity)


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()
    set_flags({"FLAGS_fused_decode": True, "FLAGS_pallas_interpret": False})


def _counter(name, **labels):
    """Current value of a default-registry counter (0 if absent)."""
    for snap in obs.registry().snapshot():
        if snap["name"] == name and all(
                snap["labels"].get(k) == str(v) for k, v in labels.items()):
            return snap["value"]
    return 0


# ---- fault plans ------------------------------------------------------------

def test_fault_plan_fires_deterministically_and_exhausts():
    with faults.plan(Fault("train.step", at=2)) as p:
        assert faults.maybe_fire("train.step", 1) is None
        with pytest.raises(RuntimeError, match="injected fault"):
            faults.maybe_fire("train.step", 2)
        # the fire budget is spent: a REPLAY of step 2 (post-resume)
        # must not crash-loop forever
        assert faults.maybe_fire("train.step", 2) is None
        assert p.faults[0].fired == 1 and not p.pending()
    assert faults.armed() is None


def test_fault_plan_call_counter_indexing_and_kinds():
    with faults.plan(
            Fault("decode.dispatch", kind="resource_exhausted", at=1),
            Fault("checkpoint.save", kind="corrupt_checkpoint", at=0,
                  mode="flip")) as p:
        assert faults.maybe_fire("decode.dispatch") is None   # call 0
        from paddle_tpu.resilience import SimulatedResourceExhausted
        with pytest.raises(SimulatedResourceExhausted,
                           match="RESOURCE_EXHAUSTED"):
            faults.maybe_fire("decode.dispatch")              # call 1
        # cooperative kinds RETURN the fault for the site to apply
        f = faults.maybe_fire("checkpoint.save", 0)
        assert f is p.faults[1] and f.payload["mode"] == "flip"
    # zero-overhead contract: disarmed is one global read, returns None
    assert faults.armed() is None
    assert faults.maybe_fire("decode.dispatch") is None


def test_fault_plan_nesting_restores_previous():
    outer = faults.arm(faults.FaultPlan(Fault("kv.op", at=99)))
    with faults.plan(Fault("kv.op", at=0, kind="drop_heartbeat")):
        assert faults.armed() is not outer
    assert faults.armed() is outer
    faults.disarm()


# ---- retry / backoff --------------------------------------------------------

def test_backoff_delays_sequence():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, backoff=3.0,
                    max_delay_s=1.0)
    np.testing.assert_allclose(list(backoff_delays(p)), [0.1, 0.3, 0.9, 1.0])


def test_backoff_jitter_is_deterministic_seeded_and_bounded():
    """The seeded jitter regression: the schedule is a PURE function of
    the policy — same seed = same schedule (pinned numerically), every
    rung inside [1-j, 1+j] x the unjittered rung (cap applied BEFORE
    jitter), different seeds de-correlate, jitter=0 is byte-identical
    to the unjittered sequence."""
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, backoff=3.0,
                    max_delay_s=1.0, jitter=0.5, seed=7)
    a = list(backoff_delays(p))
    assert a == list(backoff_delays(p))     # reproducible, no PRNG state
    base = [0.1, 0.3, 0.9, 1.0]
    for got, b in zip(a, base):
        assert 0.5 * b <= got <= 1.5 * b
    # the pinned schedule for (seed=7, jitter=0.5) — a hash-fold change
    # is a behavior change and must show up here
    import zlib
    expect = []
    for k, b in enumerate(base, start=1):
        u = zlib.crc32(f"7:{k}".encode()) / 0xFFFFFFFF
        expect.append(b * (1.0 + 0.5 * (2.0 * u - 1.0)))
    np.testing.assert_allclose(a, expect, rtol=1e-12, atol=0)
    # de-correlation: a different seed yields a different schedule
    b2 = list(backoff_delays(RetryPolicy(
        max_attempts=5, base_delay_s=0.1, backoff=3.0, max_delay_s=1.0,
        jitter=0.5, seed=8)))
    assert a != b2
    # jitter=0 keeps the legacy schedule exactly
    np.testing.assert_allclose(
        list(backoff_delays(RetryPolicy(
            max_attempts=5, base_delay_s=0.1, backoff=3.0,
            max_delay_s=1.0))), base)


def test_call_with_retry_recovers_counts_and_sleeps():
    before = _counter("resilience.retries", op="flaky")
    calls, sleeps = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient")
        return 7

    out = call_with_retry(flaky, policy=RetryPolicy(max_attempts=4,
                                                    base_delay_s=0.05),
                          describe="flaky", sleep=sleeps.append)
    assert out == 7 and len(calls) == 3
    np.testing.assert_allclose(sleeps, [0.05, 0.1])
    assert _counter("resilience.retries", op="flaky") == before + 2


def test_call_with_retry_filters_and_exhausts():
    # retry_if False → immediate propagation, no sleeps
    sleeps = []
    with pytest.raises(ValueError, match="fatal"):
        call_with_retry(lambda: (_ for _ in ()).throw(ValueError("fatal")),
                        retry_if=lambda e: "fatal" not in str(e),
                        sleep=sleeps.append)
    assert sleeps == []
    # budget exhausted → the last error surfaces after max_attempts calls
    calls = []

    def always():
        calls.append(1)
        raise ValueError("still down")

    with pytest.raises(ValueError, match="still down"):
        call_with_retry(always, policy=RetryPolicy(max_attempts=3,
                                                   base_delay_s=0.0),
                        sleep=lambda d: None)
    assert len(calls) == 3


class _FakeKVClient:
    """Coordination-service client double: fails the first N calls."""

    def __init__(self, fail_first=0, exc=None):
        self.fail_first = fail_first
        self.exc = exc or RuntimeError("UNAVAILABLE: connection reset")
        self.calls = {"set": 0, "dir_get": 0, "delete": 0}
        self.kv = {}

    def _maybe_fail(self, op):
        self.calls[op] += 1
        if sum(self.calls.values()) <= self.fail_first:
            raise self.exc

    def key_value_set(self, k, v, allow_overwrite=True):
        self._maybe_fail("set")
        self.kv[k] = v

    def key_value_dir_get(self, prefix):
        self._maybe_fail("dir_get")
        items = [(k, v) for k, v in self.kv.items()
                 if k.startswith(prefix + "/")]
        if not items:
            raise RuntimeError("NOT_FOUND: no keys")
        return items

    def key_value_delete(self, k):
        self._maybe_fail("delete")
        self.kv.pop(k, None)


def test_coordination_store_retries_transient_put():
    client = _FakeKVClient(fail_first=1)
    store = CoordinationServiceStore(
        client=client, retry=RetryPolicy(max_attempts=3, base_delay_s=0.0))
    store.put("0", {"rank": 0, "ts": 1.0})
    assert client.calls["set"] == 2          # one failure, one success
    assert store.members() == {"0": {"rank": 0, "ts": 1.0}}


def test_coordination_store_not_found_is_empty_not_retried():
    client = _FakeKVClient()
    store = CoordinationServiceStore(
        client=client, retry=RetryPolicy(max_attempts=3, base_delay_s=0.0))
    assert store.members() == {}
    assert client.calls["dir_get"] == 1      # NOT_FOUND never retried


def test_kv_op_fault_injected_then_retried():
    """An injected kv.op hiccup is absorbed by the store's retry."""
    client = _FakeKVClient()
    store = CoordinationServiceStore(
        client=client, retry=RetryPolicy(max_attempts=3, base_delay_s=0.0))
    with faults.plan(Fault("kv.op", kind="raise", at=0)) as p:
        store.put("3", {"rank": 3, "ts": 2.0})
    assert p.faults[0].fired == 1
    assert store.members() == {"3": {"rank": 3, "ts": 2.0}}


# ---- checkpoint integrity ---------------------------------------------------

def test_manifest_commit_verify_and_corruption(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), max_to_keep=4,
                          async_save=False)
    m.save(0, {"w": jnp.arange(8.0), "n": {"b": jnp.ones((3,))}})
    m.save(1, {"w": jnp.arange(8.0) * 2, "n": {"b": jnp.ones((3,))}})
    assert os.path.isfile(integrity.manifest_path(str(tmp_path / "run"), 1))
    assert m.verify_step(1) == (True, "ok")
    assert m.verify_step(1, deep=True) == (True, "ok")
    assert m.verified_latest_step() == 1

    before = _counter("resilience.checkpoint_corrupt_skipped")
    integrity.corrupt_checkpoint(m._step_dir(1), mode="flip")
    ok, reason = m.verify_step(1)
    assert not ok and "crc" in reason
    assert m.verified_latest_step() == 0
    assert _counter("resilience.checkpoint_corrupt_skipped") == before + 1
    # the corrupt step was quarantined: latest_step can't land on it
    assert m.all_steps() == [0]
    back = m.restore(0)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.arange(8.0))
    m.close()


def test_truncated_file_detected(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), async_save=False)
    m.save(0, {"w": jnp.arange(64.0)})
    integrity.corrupt_checkpoint(m._step_dir(0), mode="truncate")
    ok, reason = m.verify_step(0)
    assert not ok and ("size" in reason or "crc" in reason)
    assert m.verified_latest_step() is None   # nothing valid left
    m.close()


def test_async_manifest_is_commit_marker(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), async_save=True)
    m.save(0, {"w": jnp.ones((4,))})
    m.save(1, {"w": jnp.ones((4,)) * 2})
    m.wait_until_finished()
    root = str(tmp_path / "run")
    assert os.path.isfile(integrity.manifest_path(root, 0))
    assert os.path.isfile(integrity.manifest_path(root, 1))
    # async saves default to file-level manifests only: per-tensor
    # checksums would host-pull the state on the caller thread,
    # defeating the async save's point
    assert integrity.read_manifest(root, 0)["tensors"] == {}
    # crash between data-durable and manifest-commit == missing marker
    os.unlink(integrity.manifest_path(root, 1))
    assert m.verified_latest_step() == 0
    m.close()


def test_legacy_checkpoints_without_manifests_still_resume(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), async_save=False,
                          integrity=False)
    m.save(0, {"w": jnp.ones(2)})
    m.save(2, {"w": jnp.ones(2) * 3})
    assert m.verified_latest_step() == 2     # falls back to latest_step
    m.close()


def test_mixed_legacy_and_manifested_walkback(tmp_path):
    """Steps saved BEFORE integrity was enabled stay resumable: a corrupt
    post-upgrade step must walk back to the newest legacy step, not
    strand every pre-upgrade checkpoint and restart from scratch."""
    root = str(tmp_path / "run")
    m0 = CheckpointManager(root, async_save=False, integrity=False)
    m0.save(0, {"w": jnp.ones(2)})
    m0.save(1, {"w": jnp.ones(2) * 2})
    m0.close()
    m1 = CheckpointManager(root, async_save=False)
    m1.save(2, {"w": jnp.ones(2) * 3})
    integrity.corrupt_checkpoint(m1._step_dir(2), mode="flip")
    assert m1.verified_latest_step() == 1    # legacy-accepted, not None
    m1.close()


# ---- elastic train loop -----------------------------------------------------

def _sum_state():
    return {"s": jnp.zeros(())}


def _sum_step(state, step):
    return {"s": state["s"] + step}


def test_kill_at_step_n_resume_parity(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.plan(Fault("train.step", kind="raise", at=5)) as p:
        loop = ElasticTrainLoop(m, _sum_step, _sum_state, max_restarts=2,
                                save_every=2)
        final = loop.run(total_steps=10)
    assert p.faults[0].fired == 1
    assert float(final["s"]) == sum(range(10))   # parity with clean run
    m.close()


def test_resume_past_corrupt_latest_end_to_end(tmp_path):
    """Acceptance: corrupt the latest checkpoint + kill step N → the loop
    resumes from the last VERIFIED step and the final state matches an
    uninterrupted run."""
    mb = CheckpointManager(str(tmp_path / "base"), async_save=False)
    baseline = ElasticTrainLoop(mb, _sum_step, _sum_state,
                                save_every=2).run(total_steps=10)
    mb.close()

    before = _counter("resilience.checkpoint_corrupt_skipped")
    m = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.plan(
            # saves land after steps 1,3,5,7,9; corrupt the step-5 save,
            # then kill step 6 → restart must walk back to verified 3
            Fault("checkpoint.save", kind="corrupt_checkpoint", at=5),
            Fault("train.step", kind="raise", at=6)) as p:
        loop = ElasticTrainLoop(m, _sum_step, _sum_state, max_restarts=2,
                                save_every=2)
        final = loop.run(total_steps=10)
    assert [f.fired for f in p.faults] == [1, 1]
    assert float(final["s"]) == float(baseline["s"])
    assert _counter("resilience.checkpoint_corrupt_skipped") == before + 1
    # re-saved past the quarantined step after catching back up
    assert m.verified_latest_step() == 9
    m.close()


def test_nonfinite_skip_policy(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.plan(Fault("train.step", kind="nan_grads", at=3,
                           count=2)) as p:
        loop = ElasticTrainLoop(m, _sum_step, _sum_state, save_every=100,
                                nonfinite_policy="skip")
        final = loop.run(total_steps=8)
    assert p.faults[0].fired == 2
    assert loop.nonfinite_skipped == 2
    # steps 3 and 4 were dropped (state kept), everything else applied
    assert float(final["s"]) == sum(range(8)) - 3 - 4
    m.close()


def test_nonfinite_rewind_policy(tmp_path):
    m = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.plan(Fault("train.step", kind="nan_grads", at=4,
                           count=2)) as p:
        loop = ElasticTrainLoop(m, _sum_step, _sum_state, max_restarts=2,
                                save_every=2, nonfinite_policy="rewind",
                                nonfinite_limit=2)
        final = loop.run(total_steps=8)
    # steps 4,5 poisoned → streak hits the limit → rewind to ckpt step 3
    # → replay runs clean (the fault budget is spent) → full-sum parity
    assert p.faults[0].fired == 2
    assert loop.nonfinite_skipped == 2
    assert float(final["s"]) == sum(range(8))
    m.close()


def test_restart_budget_resets_after_clean_window(tmp_path):
    # two crashes far apart: each alone fits max_restarts=1, together
    # they only survive because the budget resets after save_every
    # clean steps
    m = CheckpointManager(str(tmp_path / "run"), async_save=False)
    with faults.plan(Fault("train.step", at=3), Fault("train.step", at=9)):
        loop = ElasticTrainLoop(m, _sum_step, _sum_state, max_restarts=1,
                                save_every=2)
        final = loop.run(total_steps=12)
    assert float(final["s"]) == sum(range(12))
    m.close()

    # with the reset disabled the second crash exceeds the budget
    m2 = CheckpointManager(str(tmp_path / "run2"), async_save=False)
    with faults.plan(Fault("train.step", at=3), Fault("train.step", at=9)):
        loop2 = ElasticTrainLoop(m2, _sum_step, _sum_state, max_restarts=1,
                                 save_every=2, restart_reset_steps=0)
        with pytest.raises(RuntimeError, match="injected fault"):
            loop2.run(total_steps=12)
    m2.close()


# ---- elastic manager --------------------------------------------------------

def test_heartbeat_drop_injected(tmp_path):
    before = _counter("resilience.heartbeat_dropped")
    store = FileHeartbeatStore(str(tmp_path))
    mgr = ElasticManager(store, rank=0, world_size=1,
                         heartbeat_interval=10.0)
    with faults.plan(Fault("elastic.heartbeat", kind="drop_heartbeat",
                           at=0)):
        mgr.register()                       # dropped: host goes silent
        assert store.members() == {}
    mgr.register()
    assert "0" in store.members()
    assert _counter("resilience.heartbeat_dropped") == before + 1


class _SeqStore(HeartbeatStore):
    """Scripted membership snapshots; counts members() polls."""

    def __init__(self, snaps):
        self.snaps = list(snaps)
        self.calls = 0

    def members(self):
        self.calls += 1
        return (self.snaps.pop(0) if len(self.snaps) > 1
                else dict(self.snaps[0]))

    def put(self, member, payload):
        pass

    def remove(self, member):
        pass


def test_watch_alive_dead_from_one_snapshot():
    now = time.time()
    fresh = lambda r: {"rank": r, "ts": now + 3600}  # fresh all test long
    store = _SeqStore([{"0": fresh(0), "1": fresh(1)}, {"0": fresh(0)}])
    mgr = ElasticManager(store, rank=0, world_size=2,
                         heartbeat_interval=0.05)
    events = []
    mgr.watch(lambda alive, dead: events.append((set(alive), set(dead))),
              poll_interval=0.02)
    deadline = time.time() + 5.0
    while time.time() < deadline and not events:
        time.sleep(0.02)
    mgr.stop(deregister=False)
    # snapshot 2 is the loss poll: alive and dead derive from the SAME
    # members() read, so they partition the world consistently
    assert events and events[0] == ({0}, {1})
    assert store.calls >= 2


# ---- decode degradation ladder ---------------------------------------------

@pytest.fixture(scope="module")
def llama():
    paddle_tpu.seed(0)
    # nkv=4 → dkv = 4*32 = 128: kernel-eligible, so the slow interpret
    # twin exercises the REAL halved-chunk path (two 64-token chunks)
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 6)))
    base = generate(m, prompt, max_new_tokens=8, temperature=0.0)
    return cfg, m, prompt, base


def test_untouched_hot_path_without_plan_or_deadline(llama):
    """No plan, no deadline → the single-dispatch program and nothing
    else (the acceptance bit-identical / no-added-dispatches pin; the
    traced twin only appears for deadline/tracer requests)."""
    cfg, m, prompt, base = llama
    assert faults.armed() is None
    keys = list(m._generate_jit_cache)
    assert len(keys) == 1 and "traced" not in keys[0]
    again = generate(m, prompt, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(again))
    assert len(m._generate_jit_cache) == 1    # no retrace, no new program


def test_decode_oom_halved_chunk_token_parity(llama):
    cfg, m, prompt, base = llama
    before = _counter("resilience.decode_degraded", stage="halved_chunk")
    with faults.plan(Fault("decode.dispatch", kind="resource_exhausted",
                           at=0)) as p:
        out = generate(m, prompt, max_new_tokens=8, temperature=0.0)
    assert p.faults[0].fired == 1
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
    assert _counter("resilience.decode_degraded",
                    stage="halved_chunk") == before + 1


def test_decode_oom_ladder_to_layered_token_parity(llama):
    cfg, m, prompt, base = llama
    # the final rung rides the layered path, so parity is against the
    # layered baseline (same jit-cache key as _force_layered: in bf16
    # the fused reference and the layered scan may greedy-tie-break
    # differently — degradation promises the layered path's tokens)
    set_flags({"FLAGS_fused_decode": False})
    try:
        layered = generate(m, prompt, max_new_tokens=8, temperature=0.0)
    finally:
        set_flags({"FLAGS_fused_decode": True})
    before = _counter("resilience.decode_degraded", stage="layered")
    with faults.plan(Fault("decode.dispatch", kind="resource_exhausted",
                           at=0, count=2)) as p:
        out = generate(m, prompt, max_new_tokens=8, temperature=0.0)
    assert p.faults[0].fired == 2            # fused + halved both "OOM'd"
    np.testing.assert_array_equal(np.asarray(layered), np.asarray(out))
    assert _counter("resilience.decode_degraded",
                    stage="layered") == before + 1


def test_decode_deadline_partial_and_full(llama):
    cfg, m, prompt, base = llama
    before = _counter("resilience.deadline_exceeded")
    # an already-expired budget still yields the prefill's first token
    out = generate(m, prompt, max_new_tokens=8, temperature=0.0,
                   deadline_s=1e-9)
    assert prompt.shape[1] + 1 <= out.shape[1] < prompt.shape[1] + 8
    np.testing.assert_array_equal(np.asarray(base[:, :out.shape[1]]),
                                  np.asarray(out))
    assert _counter("resilience.deadline_exceeded") == before + 1
    # a generous budget returns the full, bit-identical sequence
    full = generate(m, prompt, max_new_tokens=8, temperature=0.0,
                    deadline_s=1e9)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(full))


@pytest.mark.slow
def test_decode_oom_halved_chunk_interpret_kernel(llama):
    """Interpret-mode twin of the halved-chunk rung: the REAL Pallas
    kernel (interpret=True on CPU) decodes with ck=64 after the injected
    OOM and stays token-exact vs the un-faulted kernel run."""
    cfg, m, prompt, base = llama
    m._generate_jit_cache = {}
    set_flags({"FLAGS_pallas_interpret": True})
    try:
        ref = generate(m, prompt, max_new_tokens=8, temperature=0.0)
        m._generate_jit_cache = {}
        with faults.plan(Fault("decode.dispatch",
                               kind="resource_exhausted", at=0)) as p:
            out = generate(m, prompt, max_new_tokens=8, temperature=0.0)
        assert p.faults[0].fired == 1
    finally:
        set_flags({"FLAGS_pallas_interpret": False})
        m._generate_jit_cache = {}
    assert np.asarray(ref).tolist() == np.asarray(out).tolist()
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(base))


def test_stacked_oom_halved_chunk_token_parity():
    from paddle_tpu.inference.stacked import StackedLlamaDecoder

    paddle_tpu.seed(0)
    cfg = LlamaConfig(vocab_size=256, hidden_size=128, num_layers=2,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=256)
    dec = StackedLlamaDecoder.from_config(cfg, int8=False, seed=1)
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 256, (1, 5)))
    base = dec.generate(prompt, max_new_tokens=6, temperature=0.0)
    before = _counter("resilience.decode_degraded", stage="halved_chunk")
    with faults.plan(Fault("decode.dispatch", kind="resource_exhausted",
                           at=0)) as p:
        out = dec.generate(prompt, max_new_tokens=6, temperature=0.0)
    assert p.faults[0].fired == 1
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
    assert _counter("resilience.decode_degraded",
                    stage="halved_chunk") == before + 1
