"""Speculative decoding (ServingEngine(speculate=SpecConfig(...))).

The contract under test: speculation is a SCHEDULING change, not a
numerics change — a request's tokens through a speculative engine are
bit-identical to the non-speculative engine AND to an isolated
``generate`` call (greedy and sampled, bf16 and int8 KV pools, n-gram
and draft proposers, through preempt-then-resume and
snapshot/restore), while accepted proposals cut the fused dispatches
per generated token. Plus the satellites: the device n-gram matcher
against its python specification, the accepted-length EWMA feeding the
TTFT estimator (no over-shedding when speculation multiplies
tokens/tick), the interpret-mode kernel twin for
``fused_paged_verify_step``, and the spec observability surface
(counters, flight fields). The speculative compile-set pin lives in
tests/test_analysis.py next to the other compile pins.
"""

import os
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.spec import (SpecConfig, ngram_propose,
                                     ngram_propose_host)


def tiny_llama(L=2, seed=0):
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=L,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(seed)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return cfg, m


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_fused_decode": True, "FLAGS_pallas_interpret": False})


def _spec_workload(rng):
    """Mixed prompts with a repetitive member (so the n-gram proposer
    actually fires — greedy decoding of a random model also tends to
    cycle, which is the self-speculation the matcher exploits)."""
    motif = rng.randint(3, 512, (8,))
    prompts = [np.tile(motif, 5), rng.randint(3, 512, (19,)),
               np.concatenate([motif, motif, motif])]
    max_new = [16, 8, 12]
    seeds = [101, 202, 303]
    return prompts, max_new, seeds


def _isolated(m, prompts, max_new, seeds, cache_dtype, **kw):
    return [np.asarray(generate(m, p[None], max_new_tokens=mn,
                                cache_dtype=cache_dtype,
                                request_seeds=[s], **kw))[0, len(p):]
            for p, mn, s in zip(prompts, max_new, seeds)]


# ------------------------------------------------ n-gram proposer unit

def test_ngram_propose_matches_host_reference():
    rng = np.random.RandomState(5)
    cases = []
    motif = rng.randint(3, 100, (4,))
    cases.append(np.tile(motif, 4))                   # periodic
    cases.append(rng.randint(3, 100, (20,)))          # random
    cases.append(np.asarray([7] * 12))                # constant
    seq = rng.randint(3, 100, (10,))
    cases.append(np.concatenate([seq, seq[:5]]))      # prefix echo
    cases.append(np.asarray([3, 4]))                  # too short
    k, nmax, nmin = 4, 3, 1
    S = 48
    hist = np.zeros((len(cases), S), np.int32)
    lengths = np.zeros(len(cases), np.int32)
    for i, cseq in enumerate(cases):
        hist[i, :len(cseq)] = cseq
        lengths[i] = len(cseq)
    props, nprop = ngram_propose(jnp.asarray(hist), jnp.asarray(lengths),
                                 k, nmax, nmin)
    props, nprop = np.asarray(props), np.asarray(nprop)
    for i, cseq in enumerate(cases):
        ref_p, ref_n = ngram_propose_host(cseq, k, nmax, nmin)
        assert nprop[i] == ref_n, (i, nprop[i], ref_n)
        assert props[i, :ref_n].tolist() == ref_p[:ref_n].tolist(), i


def test_spec_config_validation():
    with pytest.raises(ValueError):
        SpecConfig(k=0)
    with pytest.raises(ValueError):
        SpecConfig(proposer="oracle")
    with pytest.raises(ValueError):
        SpecConfig(ngram_min=3, ngram_max=2)
    with pytest.raises(ValueError):
        SpecConfig(proposer="draft")            # needs a draft model
    cfg = SpecConfig(k=3).to_config()
    assert cfg == {"k": 3, "proposer": "ngram", "ngram_max": 3,
                   "ngram_min": 1, "adaptive": False, "k_min": 1,
                   "acceptance_floor": 0.35, "acceptance_ceiling": 0.65,
                   "adapt_every": 4, "share_embeddings": True}
    with pytest.raises(ValueError, match="k_min"):
        SpecConfig(k=2, k_min=3)
    with pytest.raises(ValueError, match="acceptance_floor"):
        SpecConfig(acceptance_floor=1.5)
    with pytest.raises(ValueError, match="thrash"):
        SpecConfig(acceptance_floor=0.8, acceptance_ceiling=0.2)
    with pytest.raises(ValueError, match="adapt_every"):
        SpecConfig(adapt_every=0)
    _, m = tiny_llama()
    with pytest.raises(ValueError):
        serving.ServingEngine(m, speculate="yes")   # not a SpecConfig


# --------------------------------------- speculative-vs-isolated parity

def _run_parity(m, cache_dtype, temperature, proposer="ngram",
                draft_model=None, chunk_tokens=None):
    """Every token through a speculative engine matches isolated
    generate — and at least one verify tick ran (the speculative path,
    not a fallback, produced them)."""
    kw = (dict(temperature=temperature, top_k=40, top_p=0.9)
          if temperature else dict(temperature=0.0))
    rng = np.random.RandomState(7)
    prompts, max_new, seeds = _spec_workload(rng)
    iso = _isolated(m, prompts, max_new, seeds, cache_dtype, **kw)
    spec = SpecConfig(k=3, proposer=proposer, draft_model=draft_model)
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, cache_dtype=cache_dtype,
                                speculate=spec, chunk_tokens=chunk_tokens,
                                **kw)
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn, seed=s))
            for p, mn, s in zip(prompts, max_new, seeds)]
    eng.drain(max_steps=400)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()
    assert eng.stats["spec_ticks"] > 0
    assert eng.stats["steps"] == eng.stats["spec_ticks"]
    # retirement freed every slot-held block (prefix cache refs remain)
    cache_held = (sum(1 for e in eng.prefix_cache._entries.values()
                      if e.block_id is not None)
                  if eng.prefix_cache is not None else 0)
    assert eng.pool.used_blocks == cache_held
    if proposer == "draft":
        assert eng._draft_pool_blocks.used_blocks == 0
    eng.close()
    return eng.stats


def test_spec_parity_bf16_greedy_ngram():
    cfg, m = tiny_llama()
    stats = _run_parity(m, jnp.bfloat16, 0.0)
    # greedy decoding of a cyclic workload must actually speculate:
    # more tokens committed than verify dispatches run
    assert stats["spec_accepted"] > 0
    assert stats["decode_tokens"] > stats["steps"]


@pytest.mark.slow
def test_spec_parity_int8_sampled_ngram():
    cfg, m = tiny_llama()
    _run_parity(m, jnp.int8, 0.8)


@pytest.mark.slow
def test_spec_parity_bf16_greedy_draft():
    cfg, m = tiny_llama()
    _, draft = tiny_llama(seed=0)   # same-weights draft: max acceptance
    stats = _run_parity(m, jnp.bfloat16, 0.0, proposer="draft",
                        draft_model=draft)
    assert stats["spec_accepted"] > 0
    assert stats["decode_tokens"] > stats["steps"]


@pytest.mark.slow
def test_spec_parity_bf16_sampled_ngram():
    cfg, m = tiny_llama()
    _run_parity(m, jnp.bfloat16, 0.8)


@pytest.mark.slow
def test_spec_parity_int8_greedy_ngram():
    cfg, m = tiny_llama()
    _run_parity(m, jnp.int8, 0.0)


@pytest.mark.slow
def test_spec_parity_int8_sampled_draft():
    cfg, m = tiny_llama()
    _, draft = tiny_llama(seed=1)   # different draft weights: rejects
    _run_parity(m, jnp.int8, 0.8, proposer="draft", draft_model=draft)


@pytest.mark.slow
def test_spec_parity_chunked_prefill():
    cfg, m = tiny_llama()
    _run_parity(m, jnp.bfloat16, 0.0, chunk_tokens=16)


@pytest.mark.slow
def test_spec_parity_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle_tpu.seed(0)
    g = GPTPretrainModel(cfg)
    g.eval()
    rng = np.random.RandomState(22)
    motif = rng.randint(3, 256, (6,))
    p = np.tile(motif, 5)
    iso = np.asarray(generate(g, p[None], max_new_tokens=10,
                              temperature=0.0))[0, len(p):]
    eng = serving.ServingEngine(g, max_slots=2, block_tokens=16,
                                max_seq_len=128,
                                speculate=SpecConfig(k=3))
    rid = eng.submit(serving.Request(p, max_new_tokens=10))
    eng.drain(max_steps=200)
    assert eng.results[rid].tokens.tolist() == iso.tolist()
    eng.close()


# ------------------------------------- spec x non-spec engine equality

@pytest.mark.slow
def test_spec_engine_matches_nonspec_engine():
    """The same submissions through a speculative and a plain engine
    produce byte-identical result rows — speculation is invisible."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(9)
    prompts, max_new, seeds = _spec_workload(rng)
    outs = []
    for spec in (None, SpecConfig(k=3)):
        eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                    max_seq_len=128, speculate=spec)
        rids = [eng.submit(serving.Request(p, max_new_tokens=mn, seed=s))
                for p, mn, s in zip(prompts, max_new, seeds)]
        eng.drain(max_steps=400)
        outs.append([eng.results[r].tokens.tolist() for r in rids])
        eng.close()
    assert outs[0] == outs[1]


# ------------------------------------------- preempt/resume + snapshot

@pytest.mark.slow
def test_spec_preempt_resume_token_exact():
    cfg, m = tiny_llama()
    rng = np.random.RandomState(3)
    motif = rng.randint(3, 512, (6,))
    p_low = np.tile(motif, 6)
    p_high = rng.randint(3, 512, (14,))
    iso_low = np.asarray(generate(m, p_low[None], max_new_tokens=20,
                                  request_seeds=[11]))[0, len(p_low):]
    iso_high = np.asarray(generate(m, p_high[None], max_new_tokens=6,
                                   request_seeds=[22]))[0, len(p_high):]
    eng = serving.ServingEngine(m, max_slots=1, block_tokens=16,
                                max_seq_len=128, num_blocks=8,
                                speculate=SpecConfig(k=3))
    rl = eng.submit(serving.Request(p_low, max_new_tokens=20, seed=11,
                                    priority="low"))
    for _ in range(4):
        eng.step()
    rh = eng.submit(serving.Request(p_high, max_new_tokens=6, seed=22,
                                    priority="high"))
    eng.drain(max_steps=400)
    assert eng.stats["preemptions"] >= 1
    assert eng.results[rl].tokens.tolist() == iso_low.tolist()
    assert eng.results[rh].tokens.tolist() == iso_high.tolist()
    eng.close()


@pytest.mark.slow
def test_spec_snapshot_restore_token_exact(tmp_path):
    cfg, m = tiny_llama()
    rng = np.random.RandomState(3)
    motif = rng.randint(3, 512, (6,))
    p0 = np.tile(motif, 6)
    p1 = rng.randint(3, 512, (14,))
    iso0 = np.asarray(generate(m, p0[None], max_new_tokens=20,
                               request_seeds=[11]))[0, len(p0):]
    iso1 = np.asarray(generate(m, p1[None], max_new_tokens=6,
                               request_seeds=[22]))[0, len(p1):]
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, speculate=SpecConfig(k=3))
    r0 = eng.submit(serving.Request(p0, max_new_tokens=20, seed=11))
    r1 = eng.submit(serving.Request(p1, max_new_tokens=6, seed=22))
    for _ in range(3):
        eng.step()
    root = str(tmp_path / "snap")
    eng.save_snapshot(root)
    snap = eng.snapshot()
    assert snap["config"]["speculate"] == {
        "k": 3, "proposer": "ngram", "ngram_max": 3, "ngram_min": 1,
        "adaptive": False, "k_min": 1, "acceptance_floor": 0.35,
        "acceptance_ceiling": 0.65, "adapt_every": 4,
        "share_embeddings": True}
    eng.close()
    eng2 = serving.ServingEngine.restore(m, root)
    assert eng2.speculate is not None and eng2.speculate.k == 3
    eng2.drain(max_steps=400)
    assert eng2.results[r0].tokens.tolist() == iso0.tolist()
    assert eng2.results[r1].tokens.tolist() == iso1.tolist()
    eng2.close()


def test_spec_draft_snapshot_demands_model_override(tmp_path):
    cfg, m = tiny_llama()
    _, draft = tiny_llama(seed=0)
    eng = serving.ServingEngine(
        m, max_slots=1, block_tokens=16, max_seq_len=64,
        speculate=SpecConfig(k=2, proposer="draft", draft_model=draft))
    root = str(tmp_path / "snap")
    eng.save_snapshot(root)
    eng.close()
    with pytest.raises(ValueError, match="draft"):
        serving.ServingEngine.restore(m, root)
    # override paths: a fresh SpecConfig, or no speculation at all
    eng2 = serving.ServingEngine.restore(
        m, root, speculate=SpecConfig(k=2, proposer="draft",
                                      draft_model=draft))
    assert eng2.speculate.proposer == "draft"
    eng2.close()
    eng3 = serving.ServingEngine.restore(m, root, speculate=None)
    assert eng3.speculate is None
    eng3.close()


# -------------------------------------------- TTFT estimator satellite

@pytest.mark.slow
def test_estimator_prices_speculative_tokens_per_tick():
    """The accepted-length EWMA must divide the decode work ahead: an
    engine committing ~3 tokens/tick estimates ~3x less queue wait
    than one token/tick — otherwise shed_infeasible rejects deadlines
    speculation would easily meet (the PR 10 bimodal fix's speculative
    sibling)."""
    cfg, m = tiny_llama()
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=256,
                                speculate=SpecConfig(k=3))
    rid = eng.submit(serving.Request(np.arange(3, 19, dtype=np.int32),
                                     max_new_tokens=200))
    eng.step()
    # synthetic steady state: 10 ms/tick, queue of decode work ahead
    eng._ewma_step.value = 0.010
    eng._ewma_prefill_tok.value = 0.0
    probe = serving.Request(np.arange(3, 19, dtype=np.int32),
                            max_new_tokens=8, deadline_s=1.0)
    eng._ewma_spec_tokens.value = 1.0
    est_serial = eng.estimated_ttft_s(probe)
    eng._ewma_spec_tokens.value = 3.0
    est_spec = eng.estimated_ttft_s(probe)
    assert est_serial is not None and est_spec is not None
    assert abs(est_serial - 3.0 * est_spec) < 1e-9
    # a real speculative engine actually feeds the EWMA
    del rid
    eng.drain(max_steps=400)
    assert eng._ewma_spec_tokens.value is not None
    assert eng._ewma_spec_tokens.value >= 1.0
    eng.close()


# ------------------------------------------------ observability surface

def test_spec_metrics_and_flight_fields():
    from paddle_tpu.observability import registry
    cfg, m = tiny_llama()
    rng = np.random.RandomState(7)
    motif = rng.randint(3, 512, (8,))
    eng = serving.ServingEngine(m, max_slots=1, block_tokens=16,
                                max_seq_len=128,
                                speculate=SpecConfig(k=3))
    r = registry()
    base_prop = r.counter("serving.spec_proposed").value
    base_acc = r.counter("serving.spec_accepted").value
    rid = eng.submit(serving.Request(np.tile(motif, 5),
                                     max_new_tokens=24, seed=1))
    eng.drain(max_steps=200)
    st = eng.stats
    assert st["spec_ticks"] == st["steps"] > 0
    assert st["spec_proposed"] >= st["spec_accepted"] > 0
    assert r.counter("serving.spec_proposed").value - base_prop \
        == st["spec_proposed"]
    assert r.counter("serving.spec_accepted").value - base_acc \
        == st["spec_accepted"]
    assert 0.0 < r.gauge("serving.spec_acceptance_rate").value <= 1.0
    # every tick's flight event carries the speculation fields
    events = eng.flight.events()
    decode_evts = [e for e in events if e["spec_proposed"] is not None]
    assert decode_evts, events
    assert all(e["spec_k"] == 3 for e in events)
    assert sum(e["spec_accepted"] for e in decode_evts) \
        == st["spec_accepted"]
    del rid
    eng.close()


# ------------------------------------- interpret-mode kernel twin (slow)

def _verify_twin_case(cache_dtype):
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops import rope as rope_ops

    cfg, m = tiny_llama()
    state = m.state_dict(include_buffers=False)
    plan = m.fused_decode_plan(state)
    params = plan["params"]
    nh, nkv = plan["num_heads"], plan["num_kv_heads"]
    hd = plan["head_dim"]
    dkv = nkv * hd
    b, NB, BT, K1 = 2, 12, 16, 4
    L = cfg.num_layers
    rng = np.random.RandomState(0)
    pool_f = rng.randn(L, NB, BT, 2 * dkv)
    if jnp.dtype(cache_dtype) == jnp.int8:
        kv_scales = jnp.asarray(
            np.abs(rng.randn(L, b, 2 * dkv)) * 0.05 + 0.01, jnp.float32)
        pool = jnp.asarray(np.clip(np.round(pool_f * 20), -127, 127),
                           jnp.int8)
    else:
        kv_scales = None
        pool = jnp.asarray(pool_f, jnp.bfloat16)
    tables = np.zeros((b, 4), np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :2] = [4, 5]
    positions = np.asarray([33, 17], np.int32)      # mid-block appends
    cos_tab, sin_tab = rope_ops.rope_cos_sin(64, hd,
                                             base=plan["rope_base"])
    posm = positions[:, None] + np.arange(K1)[None]
    cos = jnp.asarray(np.asarray(cos_tab)[posm])
    sin = jnp.asarray(np.asarray(sin_tab)[posm])
    x = jnp.asarray(rng.randn(b, K1, cfg.hidden_size), jnp.bfloat16)
    kw = dict(num_heads=nh, num_kv_heads=nkv, eps=plan["eps"],
              arch="llama", kv_scales=kv_scales)
    yr, pr = fd.fused_paged_verify_reference(
        x, params, pool, jnp.asarray(tables), jnp.asarray(positions),
        cos, sin, **kw)
    set_flags({"FLAGS_pallas_interpret": True})
    yk, pk = fd.fused_paged_verify_step(
        x, params, pool, jnp.asarray(tables), jnp.asarray(positions),
        cos, sin, rope_base=plan["rope_base"], blocks=None, **kw)
    set_flags({"FLAGS_pallas_interpret": False})
    yr32 = np.asarray(yr, np.float32)
    yk32 = np.asarray(yk, np.float32)
    # hidden states agree to bf16 resolution (the kernel computes rope
    # in-kernel; the decode twins carry the same tolerance)
    np.testing.assert_allclose(yk32, yr32, atol=2e-2, rtol=2e-2)
    # the appended KV in MAPPED blocks matches (scratch is garbage by
    # contract on both paths)
    mapped = sorted({int(t) for t in tables.ravel() if t != 0})
    prn = np.asarray(pr, np.float32)[:, mapped]
    pkn = np.asarray(pk, np.float32)[:, mapped]
    tol = 1.0 if jnp.dtype(cache_dtype) == jnp.int8 else 2e-2
    np.testing.assert_allclose(pkn, prn, atol=tol, rtol=0)


@pytest.mark.slow
def test_paged_verify_kernel_interpret_twin_bf16():
    _verify_twin_case(jnp.bfloat16)


@pytest.mark.slow
def test_paged_verify_kernel_interpret_twin_int8():
    _verify_twin_case(jnp.int8)


@pytest.mark.slow
def test_spec_engine_on_interpret_kernel_token_exact():
    """Whole speculative engine with the interpret-mode Pallas verify
    kernel underneath: tokens still match the engine's own reference-
    path run (kernel vs reference is token-exact end to end)."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(7)
    motif = rng.randint(3, 512, (8,))
    p = np.tile(motif, 5)

    def run():
        eng = serving.ServingEngine(m, max_slots=1, block_tokens=16,
                                    max_seq_len=128,
                                    speculate=SpecConfig(k=3))
        rid = eng.submit(serving.Request(p, max_new_tokens=16, seed=1))
        eng.drain(max_steps=200)
        toks = eng.results[rid].tokens.tolist()
        st = dict(eng.stats)
        eng.close()
        return toks, st

    ref_toks, _ = run()
    set_flags({"FLAGS_pallas_interpret": True})
    try:
        kern_toks, st = run()
    finally:
        set_flags({"FLAGS_pallas_interpret": False})
    assert kern_toks == ref_toks
    assert st["spec_ticks"] > 0


# ----------------------------------------------- per-slot adaptive k

@pytest.mark.slow
def test_adaptive_k_decays_on_low_acceptance_token_exact():
    """A draft proposer with DIFFERENT weights proposes k tokens every
    tick that almost never match the target's samples: the per-slot
    acceptance EWMA decays the slot's k to k_min=0, after which most
    ticks ride the plain per-token dispatch (no verify tail, no draft
    round — ``stats["steps"] > stats["spec_ticks"]``), with the
    periodic one-proposal recovery probe (PR 13) re-observing every
    ``adapt_every`` parked ticks. Tokens stay bit-identical to
    isolated generate at every k along the way."""
    cfg, m = tiny_llama()
    _, draft = tiny_llama(seed=7)       # different weights on purpose
    rng = np.random.RandomState(11)
    p = rng.randint(3, 512, (12,))
    ref = np.asarray(generate(m, p[None], max_new_tokens=24,
                              request_seeds=[42]))[0, len(p):]
    eng = serving.ServingEngine(
        m, max_slots=2, block_tokens=16, max_seq_len=64,
        speculate=SpecConfig(k=3, proposer="draft", draft_model=draft,
                             adaptive=True, k_min=0, adapt_every=3,
                             acceptance_floor=0.5))
    rid = eng.submit(serving.Request(p, max_new_tokens=24, seed=42))
    eng.drain(max_steps=400)
    assert eng.results[rid].tokens.tolist() == ref.tolist()
    st = eng.stats
    # the slot adapted down: later ticks ran WITHOUT the verify tail
    assert st["spec_ticks"] < st["steps"], st
    assert st["steps"] - st["spec_ticks"] >= 4, st
    # ... and the parked slot kept probing (and kept being rejected —
    # the mismatched draft never earns its k back)
    assert st["spec_k_probes"] >= 1, st
    eng.close()


@pytest.mark.slow
def test_spec_k_zero_probe_reobserves_and_climbs_back():
    """The k=0 recovery probe (ROADMAP carry-over): a slot parked at
    ``k_min=0`` proposes nothing, so without probing its acceptance
    EWMA could never observe again. Every ``adapt_every`` parked ticks
    the engine raises its cap to ONE proposal (counted under
    ``serving.spec_k_probes``); with the draft == the target, every
    probe accepts, the EWMA crosses the ceiling and the slot CLIMBS
    back above k=0 — and the tokens stay bit-identical to isolated
    generate through park, probe and climb."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(13)
    p = rng.randint(3, 512, (12,))
    ref = np.asarray(generate(m, p[None], max_new_tokens=24,
                              request_seeds=[44]))[0, len(p):]
    eng = serving.ServingEngine(
        m, max_slots=1, block_tokens=16, max_seq_len=64,
        speculate=SpecConfig(k=2, proposer="draft", draft_model=m,
                             adaptive=True, k_min=0, adapt_every=2,
                             acceptance_floor=0.0,
                             acceptance_ceiling=0.0))
    rid = eng.submit(serving.Request(p, max_new_tokens=24, seed=44))
    eng.step()      # admit + first speculative tick
    i = next(j for j, s in enumerate(eng._slots) if s is not None)
    # park the slot directly (the decay path has its own pin above)
    eng._spec_k_slot[i] = 0
    eng._spec_cap[i] = 0
    eng._dirty = True
    ticks = 0
    while eng._slots[i] is not None and eng._spec_k_slot[i] == 0 \
            and ticks < 20:
        eng.step()
        ticks += 1
    assert eng.stats["spec_k_probes"] >= 1, eng.stats
    assert eng._slots[i] is None or eng._spec_k_slot[i] > 0, (
        "parked slot never climbed back despite perfect acceptance")
    eng.drain(max_steps=200)
    assert eng.results[rid].tokens.tolist() == ref.tolist()
    eng.close()


@pytest.mark.slow
def test_adaptive_k_holds_on_high_acceptance_token_exact():
    """A repetitive prompt keeps the n-gram acceptance EWMA above the
    ceiling: k never decays (every tick stays speculative) and tokens
    stay bit-identical to isolated generate."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(12)
    motif = rng.randint(3, 512, (6,))
    p = np.tile(motif, 5)
    ref = np.asarray(generate(m, p[None], max_new_tokens=20,
                              request_seeds=[43]))[0, len(p):]
    eng = serving.ServingEngine(
        m, max_slots=2, block_tokens=16, max_seq_len=64,
        speculate=SpecConfig(k=3, adaptive=True, k_min=1,
                             adapt_every=2))
    rid = eng.submit(serving.Request(p, max_new_tokens=20, seed=43))
    eng.drain(max_steps=400)
    assert eng.results[rid].tokens.tolist() == ref.tolist()
    st = eng.stats
    assert st["spec_ticks"] == st["steps"], st
    # acceptance was genuinely high enough to hold k up
    assert st["spec_accepted"] > 0
    # k_min=1 never parks a slot, so the k=0 recovery probe never fires
    assert st["spec_k_probes"] == 0, st
    eng.close()


@pytest.mark.slow
def test_adaptive_config_survives_snapshot_roundtrip(tmp_path):
    cfg, m = tiny_llama()
    eng = serving.ServingEngine(
        m, max_slots=2, block_tokens=16, max_seq_len=64,
        speculate=SpecConfig(k=4, adaptive=True, k_min=2,
                             acceptance_floor=0.2,
                             acceptance_ceiling=0.9, adapt_every=3))
    eng.submit(serving.Request(np.arange(10) + 3, max_new_tokens=6,
                               seed=9))
    eng.step()
    snap = eng.snapshot()
    eng.close()
    eng2 = serving.ServingEngine.restore(m, snap)
    sc = eng2.speculate
    assert (sc.adaptive, sc.k_min, sc.acceptance_floor,
            sc.acceptance_ceiling, sc.adapt_every) == (True, 2, 0.2,
                                                       0.9, 3)
    eng2.drain(max_steps=200)
    eng2.close()
