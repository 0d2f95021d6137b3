"""``ServingEngine`` on MiniMax-M3: the ``msa_moe`` plan's seam (``[k |
v]`` rows for every layer, the pooled keys as a second paged leaf, no
state a slot), prefill then paged decode against the plain reference's
full forward (logits, not tokens), the step's block and expert
counters, the waves' kernel counts, a preempted request's rebuild, and
the options the gate refuses by name.

The model is float32: the engine's chunked prefill, its decode step over
the pool and the reference differ by summation order only.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import minimax_m3 as mm
from paddle_tpu.models.minimax_m3 import (STEP_COUNTERS, MiniMaxM3Config,
                                          MiniMaxM3ForCausalLM)
from paddle_tpu.ops import moe_grouped
from paddle_tpu.serving.spec import SpecConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import reference_minimax_m3 as ref  # noqa: E402
from test_minimax_m3 import published_keys  # noqa: E402

# a served token's reference logit against the reference maximum. The
# weights are float32 but the engine's cache is bf16 (rows and pooled
# keys): logits of size 0.3 move by up to 3e-3 (2.4e-3 seen). Where a
# choice stood near a tie (two block scores 0.7 % apart, the 4th and 5th
# expert 1e-4) that rounding takes the other block or expert, and the
# row, and the rows whose attention reads its keys, move by up to 0.4:
# most rows are held to TOL, every row to WILD
TOL, WILD, WITHIN = 5e-3, 0.6, 0.8


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def tiny_model(**over):
    cfg = MiniMaxM3Config.tiny(**over)
    paddle_tpu.seed(0)
    m = MiniMaxM3ForCausalLM(cfg)
    m.eval()
    return cfg, m


def serve_staggered(m, prompts, max_new, **opts):
    eng = serving.ServingEngine(m, prefix_caching=False, **opts)
    pending = list(zip(prompts, max_new))
    rids, results, ticks = [], {}, 0
    while pending or not eng.idle:
        if pending and ticks % 2 == 0:
            p, n = pending.pop(0)
            rids.append(eng.submit(serving.Request(p, max_new_tokens=n)))
        for rid in eng.step()["finished"]:
            results[rid] = eng.pop_result(rid)
        ticks += 1
        assert ticks < 800
    return eng, [results[r] for r in rids]


def check_against_the_reference(results, prompts, max_new, m, cfg):
    state = m.state_dict(include_buffers=False)
    keys = published_keys(cfg)
    margins = []
    for p, n, res in zip(prompts, max_new, results):
        assert res.finish == "length" and len(res.tokens) == n
        ids = jnp.asarray(res.ids[None], jnp.int32)
        # logits at t predict t + 1: the first comes from the prefill,
        # the rest from the decode step over rows and pooled keys
        lg = np.asarray(ref.logits_at(
            state, ids, jnp.arange(len(p) - 1, len(p) + n - 1), keys))
        margin = lg.max(-1) - lg[np.arange(n), res.tokens]
        assert margin.max() < WILD, margin
        margins.append(margin)
    margins = np.concatenate(margins)
    assert (margins < TOL).mean() > WITHIN, margins


def test_prefill_then_decode_through_the_engine_agree_with_the_reference():
    """Six requests on three slots (every slot is reused after a request
    leaves), waves of three buckets, prompts under and over ``dense_len``
    32, decodes that cross it and that complete blocks across a page's
    edge."""
    cfg, m = tiny_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (70, 100, 21, 120, 90, 30)]
    max_new = [12, 9, 40, 9, 20, 6]
    eng, results = serve_staggered(m, prompts, max_new, max_slots=3,
                                   block_tokens=32, max_seq_len=160)
    check_against_the_reference(results, prompts, max_new, m, cfg)
    s = eng.stats
    L = cfg.num_hidden_layers
    served = s["decode_tokens"] + s["lookahead_discarded_tokens"]
    # a group reads 1 + 1 + 2 blocks past dense_len, all of them under it
    assert 0 < s["sparse_blocks_read"] < s["sparse_blocks_visible"]
    assert 0 < s["sparse_dense_rows"] < served * L
    assert s["lightning_rows"] == 0
    assert s["moe_layer_steps"] == 2 * s["steps"]
    assert s["moe_picks"] == served * 4 * 2
    assert 0 < s["moe_rows"] < s["moe_picks"]      # 16 of 32 held
    # six waves of one to four chunks of 32; the chunks that end past
    # dense_len 32 (the second on) select
    chunks = [-(-len(p) // 32) for p in prompts]
    assert s["msa_prefill_calls"] == L * sum(chunks)
    assert s["msa_select_calls"] == L * sum(c - 1 for c in chunks)
    # the CPU's prefill experts take ragged_dot: no kernel call, no row
    assert s["prefill_moe_calls"] == s["prefill_moe_rows"] == 0
    events = [e for e in eng.flight.events() if "moe_picks" in e]
    assert sum(e["moe_picks"] for e in events) == s["moe_picks"]
    assert set(STEP_COUNTERS) <= set(events[0])
    # the pool: rows for every layer, the pooled keys 8x shorter (a row
    # a block of 8) and float32, no state a slot
    assert eng.arch == "msa_moe"
    rows, aux = eng.kv_pool["pool"]
    gd = cfg.num_key_value_heads * cfg.head_dim
    assert rows.shape == (L, 3 * 5 + 1, 32, 2 * gd)
    assert aux.shape == (L, 3 * 5 + 1, 4, gd)
    assert aux.dtype == jnp.float32
    assert eng.kv_pool["state"] == {}
    assert eng.block_bytes == L * (32 * 2 * gd * 2 + 4 * gd * 4)
    assert sorted({k[0] for k in eng.lowered_programs()}) == [
        "prefill", "step"]
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1
    eng.close()


def test_the_kernels_in_interpret_mode_through_the_engine():
    """Heads of 128 lanes, blocks of 16: the sparse decode and prefill
    kernels and both expert kernels (swigluoai) interpreted, and the
    waves count the expert kernel's calls, one an expert layer a group
    of chunks."""
    cfg, m = tiny_model(
        hidden_size=128, intermediate_size=128, shared_intermediate_size=128,
        dense_intermediate_size=128, num_attention_heads=2,
        num_key_value_heads=1, head_dim=128, rotary_dim=64,
        msa_index_heads=2, msa_block_size=16, msa_topk=2, prefill_chunk=64,
        num_hidden_layers=2, moe_layer_freq=[0, 1])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (200, 100)]
    max_new = [5, 30]
    set_flags({"FLAGS_pallas_interpret": True})
    eng, results = serve_staggered(m, prompts, max_new, max_slots=2,
                                   block_tokens=128, max_seq_len=256)
    set_flags({"FLAGS_pallas_interpret": False})
    check_against_the_reference(results, prompts, max_new, m, cfg)
    # waves of 256 and 128 positions in chunks of 64, 8 rows an expert a
    # chunk: each wave's chunks are one group
    assert [mm.prefill_groups(cfg, s)[1] for s in (256, 128)] == [[4], [2]]
    assert eng.stats["prefill_moe_calls"] == 1 + 1
    assert eng.stats["prefill_moe_rows"] > 0
    eng.close()


def test_a_wave_counts_the_expert_kernel_calls_it_makes(monkeypatch):
    """The expert kernel's calls a wave's forward makes, counted as they
    run (a counting stand-in, no jit), are the wave's
    ``prefill_moe_calls`` (``_wave_kernels``, what the roofline reads) at
    5, 6 and 7 chunks of 64, in groups of two (the rule cut to two: the
    tiny widths' own takes every chunk) and a last group of one where
    two do not divide the chunks."""
    cfg, m = tiny_model(
        hidden_size=128, intermediate_size=128, shared_intermediate_size=128,
        dense_intermediate_size=128, num_attention_heads=2,
        num_key_value_heads=1, head_dim=128, rotary_dim=64,
        msa_index_heads=2, msa_block_size=16, msa_topk=2, prefill_chunk=64,
        num_hidden_layers=2, moe_layer_freq=[0, 1])
    monkeypatch.setattr(mm, "chunks_a_call",
                        lambda cfg, C, chunks: min(2, chunks))
    set_flags({"FLAGS_pallas_interpret": True})    # the kernel's path
    eng = serving.ServingEngine(m, prefix_caching=False, max_slots=1,
                                block_tokens=64, max_seq_len=448)
    set_flags({"FLAGS_pallas_interpret": False})
    rows = []

    def counting(x, *args, **kw):
        rows.append(x.shape[0])
        return moe_grouped.moe_prefill_ragged_dot(x, *args, **kw)

    monkeypatch.setattr(moe_grouped, "moe_grouped_ffn_prefill", counting)
    state = m.state_dict(include_buffers=False)
    rng = np.random.default_rng(3)
    for chunks, groups in ((5, [2, 2, 1]), (6, [2, 2, 2]), (7, [2, 2, 2, 1])):
        s = 64 * chunks
        assert mm.prefill_groups(cfg, s) == (64, groups)
        ids = jnp.asarray(rng.integers(3, cfg.vocab_size, (1, s)), jnp.int32)
        rows.clear()
        with jax.disable_jit():
            mm.hidden_forward(state, cfg, ids, mm.init_cache(cfg, 1, s))
        assert rows == [64 * g for g in groups]     # one expert layer
        assert eng._wave_kernels(0, s, 1)["prefill_moe_calls"] == len(rows)
    eng.close()


def test_preempted_request_rebuilds_its_pooled_keys_by_prefill_and_replay():
    """A resume re-prefills the prompt and replays the generated tokens
    through the step, whose running max rebuilds the pooled keys: the
    tokens are those of a run that was never interrupted."""
    cfg, m = tiny_model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (80, 70)]

    def serve(preempt):
        eng = serving.ServingEngine(m, prefix_caching=False, max_slots=2,
                                    block_tokens=32, max_seq_len=160)
        rids = [eng.submit(serving.Request(p, max_new_tokens=24))
                for p in prompts]
        for _ in range(8):
            eng.step()
        if preempt:
            victim = next(i for i, s in enumerate(eng._slots)
                          if s is not None)
            eng._land_all()
            eng._preempt(victim)
        results = {}
        for _ in range(200):
            for rid in eng.step()["finished"]:
                results[rid] = eng.pop_result(rid)
            if eng.idle:
                break
        assert eng.stats["requests_resumed"] == int(preempt)
        eng.close()
        return [results[r] for r in rids]

    resumed, whole = serve(True), serve(False)
    assert [list(r.tokens) for r in resumed] == [list(r.tokens)
                                                 for r in whole]
    check_against_the_reference(resumed, prompts, [24, 24], m, cfg)


@pytest.mark.parametrize("option, kwargs", [
    ("prefix_caching", dict(prefix_caching=True)),
    ("cache_dtype", dict(cache_dtype=jnp.int8)),
    ("speculate", dict(speculate=SpecConfig(k=2))),
    ("chunk_tokens", dict(chunk_tokens=32)),
    ("offload", dict(offload=True)),
    ("layout", dict(layout=object())),
])
def test_the_gate_refuses_by_name(option, kwargs):
    _, m = tiny_model()
    opts = dict(dict(prefix_caching=False, max_slots=2, block_tokens=32,
                     max_seq_len=64), **kwargs)
    with pytest.raises(ValueError, match=f"{option!r}.*'msa_moe'"):
        serving.ServingEngine(m, **opts)


def test_a_page_must_hold_whole_blocks():
    _, m = tiny_model()
    with pytest.raises(ValueError, match="second pool leaf"):
        serving.ServingEngine(m, prefix_caching=False, max_slots=2,
                              block_tokens=12, max_seq_len=48)
