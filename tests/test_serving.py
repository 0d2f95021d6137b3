"""Continuous-batching serving engine (paddle_tpu.serving).

The parity contract: a request's tokens from a merged continuously-
batched run are identical to an isolated `generate` call — greedy and
sampled, bf16 and int8 KV pools, reference path and (slow twins) the
interpret-mode paged Pallas kernel. Plus the host-side invariants:
block-table append/free, prefix-cache copy-on-write isolation, deadline
eviction, admission control.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import _filter_logits, generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving.pool import (SCRATCH_BLOCK, BlockPool,
                                     PoolExhausted, PrefixCache)


def tiny_llama(L=3):
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=L,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return cfg, m


def tiny_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle_tpu.seed(0)
    g = GPTPretrainModel(cfg)
    g.eval()
    return cfg, g


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_fused_decode": True, "FLAGS_pallas_interpret": False})


# ---------------------------------------------------------------- block pool

def test_block_pool_alloc_free_invariants():
    p = BlockPool(8, 16)
    assert p.free_blocks == 7            # block 0 is scratch
    a = p.alloc(3)
    assert SCRATCH_BLOCK not in a and len(set(a)) == 3
    assert p.used_blocks == 3
    p.ref(a[0])                          # shared
    assert p.free(a[0]) is False         # still referenced
    assert p.free(a[0]) is True          # now back on the free list
    with pytest.raises(ValueError):
        p.free(a[0])                     # double free
    p.free(a[1]), p.free(a[2])
    assert p.free_blocks == 7
    with pytest.raises(PoolExhausted):
        p.alloc(8)
    with pytest.raises(ValueError):
        p.ref(SCRATCH_BLOCK)


def test_block_pool_lifo_reuse():
    p = BlockPool(6, 8)
    a = p.alloc(2)
    p.free(a[1])
    assert p.alloc(1) == [a[1]]          # hottest block re-issued first


def test_prefix_cache_chain_and_eviction():
    p = BlockPool(16, 8)
    c = PrefixCache(p, capacity_blocks=2)
    prompt = np.arange(25)               # 3 full blocks of 8
    assert c.lookup(prompt) == []
    bids = p.alloc(3)
    c.insert(prompt, 0, block_ids=bids)  # capacity 2: one LRU-evicted
    assert len(c) == 2
    hits = c.lookup(prompt)
    # eviction is LRU by insertion tick: block 0 went first, so the
    # chain walk stops immediately
    assert [e.depth for e in hits] == []
    # refcounts: cache holds refs for its 2 retained entries
    assert sum(p.refcount(b) == 2 for b in bids) == 2
    c.clear()
    assert all(p.refcount(b) == 1 for b in bids)


def test_prefix_cache_divergent_suffix_misses():
    p = BlockPool(16, 8)
    c = PrefixCache(p, capacity_blocks=8)
    a = np.arange(16)
    b = np.concatenate([np.arange(8), np.arange(40, 48)])
    c.insert(a, 0, block_ids=p.alloc(2))
    hits = c.lookup(b)
    assert [e.depth for e in hits] == [0]     # shared first block only


# ------------------------------------------------------- join/leave parity

def _isolated(m, prompts, max_new, **kw):
    return [np.asarray(generate(m, p[None], max_new_tokens=mn, **kw))
            [0, len(p):] for p, mn in zip(prompts, max_new)]


@pytest.mark.slow
def test_join_leave_parity_llama_bf16():
    """4 mixed-length requests through 3 slots: the late request joins
    mid-flight when the first retires; every token matches isolated
    generate (greedy, reference path)."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 512, (n,)) for n in (7, 19, 33, 12)]
    max_new = [10, 6, 14, 9]
    iso = _isolated(m, prompts, max_new, temperature=0.0)
    eng = serving.ServingEngine(m, max_slots=3, block_tokens=16,
                                max_seq_len=128)
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    eng.drain(max_steps=200)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()
    # leave == immediate slot reuse: 4 requests never needed a 4th slot,
    # and no eos-padding steps ran (decode tokens == sum(max_new) - 4
    # prefill-sampled tokens)
    assert eng.stats["decode_tokens"] == sum(max_new) - len(prompts)
    # retirement freed every slot-held block; only the prefix cache's
    # own refs on cached full prompt blocks remain
    cache_held = sum(1 for e in eng.prefix_cache._entries.values()
                     if e.block_id is not None)
    assert eng.pool.used_blocks == cache_held


@pytest.mark.slow
def test_join_leave_parity_llama_int8():
    cfg, m = tiny_llama()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(3, 512, (n,)) for n in (9, 21, 30)]
    max_new = [8, 12, 6]
    iso = _isolated(m, prompts, max_new, temperature=0.0,
                    cache_dtype=jnp.int8)
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, cache_dtype=jnp.int8)
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    eng.drain(max_steps=200)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()


@pytest.mark.slow
def test_join_leave_parity_gpt():
    # slow lane (tier-1 budget): not-slow engine-vs-isolated parity
    # rides test_prefix_reuse_parity_and_cow_isolation (llama); the gpt
    # paged path also has its own interpret-kernel twin below
    cfg, g = tiny_gpt()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(3, 256, (n,)) for n in (6, 17)]
    iso = _isolated(g, prompts, [9, 9], temperature=0.0)
    eng = serving.ServingEngine(g, max_slots=2, block_tokens=16,
                                max_seq_len=128)
    rids = [eng.submit(serving.Request(p, max_new_tokens=9))
            for p in prompts]
    eng.drain(max_steps=100)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()


@pytest.mark.slow
def test_sampled_parity_per_request_streams():
    """Sampled tokens ride per-request RNG streams: a request in a merged
    batch draws the same tokens as `generate(request_seeds=[seed])`
    whatever its batch composition."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, 512, (n,)) for n in (9, 21, 30)]
    max_new = [8, 12, 6]
    seeds = [101, 202, 303]
    iso = [np.asarray(generate(m, p[None], max_new_tokens=mn,
                               temperature=0.8, top_k=40, top_p=0.9,
                               request_seeds=[s]))[0, len(p):]
           for p, mn, s in zip(prompts, max_new, seeds)]
    eng = serving.ServingEngine(m, max_slots=3, block_tokens=16,
                                max_seq_len=128, temperature=0.8,
                                top_k=40, top_p=0.9)
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn, seed=s))
            for p, mn, s in zip(prompts, max_new, seeds)]
    eng.drain(max_steps=200)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()


def test_eos_retires_slot_and_frees_blocks():
    cfg, m = tiny_llama()
    rng = np.random.RandomState(4)
    p = rng.randint(3, 512, (11,))
    full = np.asarray(generate(m, p[None], max_new_tokens=12,
                               temperature=0.0))[0, len(p):]
    eos = int(full[4])              # force an eos 5 tokens in
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, eos_token_id=eos,
                                prefix_caching=False)
    rid = eng.submit(serving.Request(p, max_new_tokens=12))
    eng.drain(max_steps=100)
    res = eng.results[rid]
    assert res.finish == "eos"
    assert res.gen_len == 4
    assert res.tokens.tolist() == full[:5].tolist()
    assert eng.pool.used_blocks == 0          # blocks freed immediately
    assert eng.stats["decode_tokens"] == 4    # no eos-padding steps
    # the programs that ran, lowered from what they ran on
    progs = eng.lowered_programs()
    assert sorted(k[0] for k in progs) == ["prefill", "step"]
    assert list(eng.lowered_programs("step")) == [("step",)]
    pool_type = "x".join(map(str, eng.kv_pool.shape)) + "xbf16"
    assert pool_type in progs[("step",)].as_text()


# ------------------------------------------------------------ prefix reuse

@pytest.mark.slow
def test_prefix_reuse_parity_and_cow_isolation():
    """Two requests sharing a 40-token system prefix: the second reuses
    the cached full blocks (prefill FLOPs skipped), tokens still match
    isolated generate, and the writer NEVER mutates a shared block —
    appends land only in private blocks."""
    cfg, m = tiny_llama()
    rng = np.random.RandomState(5)
    sys_p = rng.randint(3, 512, (40,))
    pr_a = np.concatenate([sys_p, rng.randint(3, 512, (5,))])
    pr_b = np.concatenate([sys_p, rng.randint(3, 512, (9,))])
    iso = _isolated(m, [pr_a, pr_b], [8, 8], temperature=0.0)
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128)
    ra = eng.submit(serving.Request(pr_a, max_new_tokens=8))
    eng.drain()
    # snapshot the shared blocks' payload before the second request
    shared_hits = eng.prefix_cache.lookup(pr_b, len(pr_b) // 16)
    assert len(shared_hits) == 2              # 40 tokens -> 2 full blocks
    shared_bids = [e.block_id for e in shared_hits]
    before = np.asarray(eng.kv_pool[:, shared_bids].astype(jnp.float32))
    rb = eng.submit(serving.Request(pr_b, max_new_tokens=8))
    eng.drain()
    after = np.asarray(eng.kv_pool[:, shared_bids].astype(jnp.float32))
    np.testing.assert_array_equal(before, after)   # copy-on-write: no writes
    assert eng.results[ra].tokens.tolist() == iso[0].tolist()
    assert eng.results[rb].tokens.tolist() == iso[1].tolist()
    assert eng.results[rb].prefix_hit_blocks == 2
    assert eng.stats["prefill_tokens_reused"] == 32


@pytest.mark.slow
def test_prefix_reuse_parity_int8_requantizes():
    """int8 pool: shared prefixes ride host-side bf16 copies and are
    re-quantized with the adopting request's own scales — tokens still
    match the isolated int8 generate."""
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(6)
    sys_p = rng.randint(3, 512, (32,))
    pr_a = np.concatenate([sys_p, rng.randint(3, 512, (6,))])
    pr_b = np.concatenate([sys_p, rng.randint(3, 512, (11,))])
    iso = _isolated(m, [pr_a, pr_b], [6, 6], temperature=0.0,
                    cache_dtype=jnp.int8)
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, cache_dtype=jnp.int8)
    ra = eng.submit(serving.Request(pr_a, max_new_tokens=6))
    eng.drain()
    rb = eng.submit(serving.Request(pr_b, max_new_tokens=6))
    eng.drain()
    assert eng.results[rb].prefix_hit_blocks == 2
    assert eng.results[ra].tokens.tolist() == iso[0].tolist()
    assert eng.results[rb].tokens.tolist() == iso[1].tolist()
    # int8 blocks are never shared: the cache holds no pool references
    assert eng.pool.used_blocks == 0


# --------------------------------------------------------------- scheduling

def test_deadline_evicted_slot_frees_blocks():
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(7)
    p = rng.randint(3, 512, (10,))
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, prefix_caching=False)
    rid = eng.submit(serving.Request(p, max_new_tokens=64,
                                     deadline_s=1e-9))
    eng.step()                      # admit + prefill
    # expired before the next dispatch: retired with >= 1 token, blocks
    # returned, reservation released
    eng.step()
    res = eng.results[rid]
    assert res.finish == "deadline"
    assert len(res.tokens) >= 1
    assert eng.pool.used_blocks == 0
    assert eng._reserved == 0
    from paddle_tpu.observability import registry
    snap = [s for s in registry().snapshot()
            if s["name"] == "resilience.deadline_exceeded"]
    assert snap and snap[0]["value"] >= 1


def test_admission_bounded_by_pool_blocks():
    """A request that cannot ever fit raises; one that does not fit NOW
    queues until blocks free up (head-of-line order kept)."""
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(8)
    # pool with 6 usable blocks of 16 tokens
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128, num_blocks=7,
                                prefix_caching=False)
    with pytest.raises(PoolExhausted):
        # 90+32 tokens -> 8 blocks: fits a slot (max_seq_len/16 = 8)
        # but can never fit the 6-usable-block pool
        eng.submit(serving.Request(rng.randint(3, 512, (90,)),
                                   max_new_tokens=32))
    # two requests each reserving 4 blocks: only one admitted at a time
    r1 = eng.submit(serving.Request(rng.randint(3, 512, (40,)),
                                    max_new_tokens=24))
    r2 = eng.submit(serving.Request(rng.randint(3, 512, (40,)),
                                    max_new_tokens=24))
    eng.step()
    assert eng.active_slots == 1 and eng.queued == 1
    eng.drain(max_steps=200)
    assert set(eng.results) == {r1, r2}
    assert eng.pool.used_blocks == 0 and eng._reserved == 0


def test_int8_admission_ignores_prefix_hits_as_capacity():
    """int8 prefix hits skip prefill FLOPs but share NO physical blocks
    (the slot allocates every prompt block, quantized with its own
    scales) — admission must reserve the FULL worst case or lazy
    allocation exhausts the pool mid-flight (regression: hits were
    subtracted from the reservation like bf16 shared blocks)."""
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(21)
    prompt = rng.randint(3, 512, (32,))          # 2 full 16-token blocks
    eng = serving.ServingEngine(m, max_slots=1, block_tokens=16,
                                max_seq_len=128, num_blocks=7,
                                cache_dtype=jnp.int8)
    # seed the prefix cache (host-side bf16 copies), then free the pool
    ra = eng.submit(serving.Request(prompt, max_new_tokens=2))
    eng.drain(max_steps=50)
    assert eng.results[ra].finish == "length"
    assert eng.pool.used_blocks == 0
    # 32+80 tokens -> worst 7 blocks > 6 usable; 2 cached-prefix hits
    # must NOT make it look admissible — it queues (and the engine keeps
    # stepping without PoolExhausted), never crashes mid-flight
    rb = eng.submit(serving.Request(prompt, max_new_tokens=80))
    for _ in range(5):
        eng.step()
    assert eng.queued == 1 and eng.active_slots == 0
    assert rb not in eng.results
    # an unbounded drain() must detect the permanent stall (idle engine,
    # inadmissible head) instead of spinning forever
    with pytest.raises(serving.PoolExhausted):
        eng.drain()


def test_occupancy_and_queue_gauges_exported():
    from paddle_tpu.observability import registry
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(9)
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=64)
    eng.submit(serving.Request(rng.randint(3, 512, (8,)),
                               max_new_tokens=4))
    eng.drain(max_steps=50)
    names = {s["name"] for s in registry().snapshot()}
    for g in ("serving.batch_occupancy", "serving.queue_depth",
              "serving.pool_blocks_used", "serving.pool_blocks_total",
              "serving.prefix_hit_rate", "serving.tokens_generated",
              "serving.steps"):
        assert g in names, g


def test_request_spans_reuse_tracing():
    from paddle_tpu import observability as obs
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(10)
    with obs.trace() as tr:
        eng = serving.ServingEngine(m, max_slots=1, block_tokens=16,
                                    max_seq_len=64)
        eng.submit(serving.Request(rng.randint(3, 512, (8,)),
                                   max_new_tokens=4))
        eng.drain(max_steps=50)
    spans = [s for s in tr.span_dicts() if s["name"] == "serving.request"]
    assert len(spans) == 1
    a = spans[0]["attrs"]
    assert a["tokens"] == 4 and a["ttft_s"] > 0 and a["tpot_s"] > 0


# ----------------------------------------------- interpret-mode kernel twins

@pytest.mark.slow
class TestInterpretKernelParity:
    """The paged Pallas kernel itself (CPU interpret mode) against the
    contiguous-kernel isolated generate — the CI-side guard for the
    block-table DMA walk; tests_tpu re-runs these shapes on-chip."""

    @pytest.fixture(autouse=True)
    def _interp(self):
        set_flags({"FLAGS_pallas_interpret": True})
        yield
        set_flags({"FLAGS_pallas_interpret": False})

    @pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
    def test_llama_paged_kernel_token_exact(self, cache_dtype):
        cfg, m = tiny_llama(L=2)
        rng = np.random.RandomState(11)
        prompts = [rng.randint(3, 512, (n,)) for n in (7, 21)]
        iso = _isolated(m, prompts, [6, 6], temperature=0.0,
                        cache_dtype=cache_dtype)
        eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                    max_seq_len=64,
                                    cache_dtype=cache_dtype)
        rids = [eng.submit(serving.Request(p, max_new_tokens=6))
                for p in prompts]
        eng.drain(max_steps=50)
        for rid, ref in zip(rids, iso):
            assert eng.results[rid].tokens.tolist() == ref.tolist()

    def test_gpt_paged_kernel_token_exact(self):
        cfg, g = tiny_gpt()
        rng = np.random.RandomState(12)
        prompts = [rng.randint(3, 256, (n,)) for n in (6, 13)]
        iso = _isolated(g, prompts, [5, 5], temperature=0.0)
        eng = serving.ServingEngine(g, max_slots=2, block_tokens=16,
                                    max_seq_len=64)
        rids = [eng.submit(serving.Request(p, max_new_tokens=5))
                for p in prompts]
        eng.drain(max_steps=50)
        for rid, ref in zip(rids, iso):
            assert eng.results[rid].tokens.tolist() == ref.tolist()


# ----------------------------------------------------- inference satellites

def test_top_p_tie_handling_keeps_nucleus_tight():
    """Duplicate logits straddling the top_p boundary: the rank-based
    cutoff keeps exactly the smallest prefix reaching top_p — a
    value-based cutoff (`logits < cutoff`) would keep every duplicate
    and overshoot the nucleus."""
    logits = jnp.log(jnp.asarray([[0.4, 0.3, 0.3, 0.3, 0.3]])
                     / 1.6)              # 4-way tie at the boundary
    kept = np.asarray(_filter_logits(logits, top_p=0.5)[0])
    finite = np.isfinite(kept)
    # 0.25 + 0.1875 >= 0.5 after renorm... rank-based: probs are
    # [.25, .1875 x4]; cumulative .25, .4375, .625 -> keep 3 ranks
    assert finite.tolist() == [True, True, True, False, False]
    # top_p == 0.0 keeps the top-1 token (rank 0 unconditionally kept;
    # an all-masked row would make categorical() emit token id 0)
    kept0 = np.isfinite(np.asarray(_filter_logits(logits, top_p=0.0)[0]))
    assert kept0.tolist() == [True, False, False, False, False]


def test_top_p_rank_cutoff_no_duplicates_matches_value_cutoff():
    rng = np.random.RandomState(13)
    logits = jnp.asarray(rng.randn(2, 64), jnp.float32)
    kept = np.isfinite(np.asarray(_filter_logits(logits, top_p=0.7)))
    # smallest prefix property: kept mass reaches 0.7, dropping the
    # smallest kept logit falls below 0.7
    p = np.exp(np.asarray(logits, np.float64))
    p /= p.sum(-1, keepdims=True)
    for r in range(2):
        mass = p[r][kept[r]].sum()
        assert mass >= 0.7 - 1e-6
        smallest = p[r][kept[r]].min()
        assert mass - smallest < 0.7 + 1e-6


def test_generate_return_lengths():
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(14)
    p = rng.randint(3, 512, (2, 9))
    full = np.asarray(generate(m, p, max_new_tokens=8, temperature=0.0))
    eos = int(full[0, 9 + 3])           # row 0 hits "eos" 4 tokens in
    out, lens = generate(m, p, max_new_tokens=8, temperature=0.0,
                         eos_token_id=eos, return_lengths=True)
    assert lens.dtype == np.int32 and lens.shape == (2,)
    assert lens[0] == 3
    row1 = full[1, 9:]
    assert lens[1] == (8 if eos not in row1.tolist()
                       else row1.tolist().index(eos))


def test_request_seeds_batch_composition_invariant():
    """generate: row r's sampled tokens depend only on its own seed —
    the same request sampled alone or inside a batch draws identically
    (the join/leave parity primitive)."""
    cfg, m = tiny_llama(L=2)
    rng = np.random.RandomState(15)
    prompts = rng.randint(3, 512, (3, 11))
    batched = np.asarray(generate(m, prompts, max_new_tokens=7,
                                  temperature=0.9, top_k=0, top_p=0.95,
                                  request_seeds=[7, 8, 9]))
    for r, s in enumerate([7, 8, 9]):
        solo = np.asarray(generate(m, prompts[r][None], max_new_tokens=7,
                                   temperature=0.9, top_k=0, top_p=0.95,
                                   request_seeds=[s]))
        assert solo[0].tolist() == batched[r].tolist()
