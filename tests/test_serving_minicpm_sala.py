"""``ServingEngine`` on MiniCPM-SALA: the hybrid plan's seam (pool rows
for the sparse layers only, their compressed keys as a second paged
leaf, the lightning states as a fixed-size leaf a slot), prefill then
paged decode against the plain reference's full forward (logits, not
tokens), the step's counters and the waves' spans, the options the gate
refuses by name, and the program sets of the plans that were there.

The model is float32: the engine's chunked prefill, its decode step over
the pool and the state, and the reference's token scan then differ by
summation order only.
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
from paddle_tpu.models.minicpm_sala import (STEP_COUNTERS, LIGHTNING, SPARSE,
                                            MiniCPMSALAConfig,
                                            MiniCPMSALAForCausalLM)
from paddle_tpu.serving.spec import SpecConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import reference_minicpm_sala as ref  # noqa: E402
from test_minicpm_sala import published_keys  # noqa: E402

# a served token's reference logit against the reference maximum: float32
# sums in another order over up to 129 positions; every served token has
# been the reference's argmax (margin 0), logits are of size 1
TOL = 1e-4


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def tiny_model(**over):
    cfg = MiniCPMSALAConfig.tiny(**over)
    paddle_tpu.seed(0)
    m = MiniCPMSALAForCausalLM(cfg)
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
    for p, n, res in zip(prompts, max_new, results):
        assert res.finish == "length" and len(res.tokens) == n
        ids = jnp.asarray(res.ids[None], jnp.int32)
        # logits at t predict t + 1: the first comes from the prefill,
        # the rest from the decode step over pool, compressed keys, state
        lg = np.asarray(ref.logits_at(
            state, ids, jnp.arange(len(p) - 1, len(p) + n - 1), keys))
        margin = lg.max(-1) - lg[np.arange(n), res.tokens]
        assert margin.max() < TOL, margin


def test_prefill_then_decode_through_the_engine_agree_with_the_reference():
    """Six requests on three slots (every slot is reused after a request
    leaves), waves of four different buckets, prompts under and over
    ``dense_len`` 64, decodes that cross it and that complete compressed
    keys across a page's edge."""
    cfg, m = tiny_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (70, 100, 33, 120, 90, 70)]
    max_new = [12, 9, 40, 9, 20, 6]
    eng, results = serve_staggered(m, prompts, max_new, max_slots=3,
                                   block_tokens=32, max_seq_len=192)
    check_against_the_reference(results, prompts, max_new, m, cfg)
    s = eng.stats
    n_sparse, n_light = (len(cfg.layers_of(SPARSE)),
                         len(cfg.layers_of(LIGHTNING)))
    served = s["decode_tokens"] + s["lookahead_discarded_tokens"]
    assert s["lightning_rows"] == served * n_light
    # a group reads 1 + 2 + 2 blocks past dense_len, all of them under it
    assert 0 < s["sparse_blocks_read"] < s["sparse_blocks_visible"]
    assert 0 < s["sparse_dense_rows"] < served * n_sparse
    # six waves, one to four chunks of 32 each
    chunks = sum(-(-len(p) // 32) for p in prompts)
    assert s["lightning_calls"] == n_light * chunks
    assert s["sparse_calls"] == n_sparse * chunks
    # of them the chunks that end past dense_len 64 (the third on) select
    assert s["select_calls"] == n_sparse * sum(
        max(-(-len(p) // 32) - 2, 0) for p in prompts)
    events = [e for e in eng.flight.events() if "lightning_rows" in e]
    assert sum(e["lightning_rows"] for e in events) == s["lightning_rows"]
    assert set(STEP_COUNTERS) <= set(events[0])
    # the pool: rows for the sparse layers only, the second leaf 4x
    # shorter (kernel_stride 4), a float32 state a slot beside them
    assert eng._stacked is None and eng.arch == "sala"
    rows, aux = eng.kv_pool["pool"]
    gd = cfg.num_kv_heads * cfg.head_dim
    assert rows.shape == (n_sparse, 3 * 6 + 1, 32, 2 * gd)
    assert aux.shape == (n_sparse, 3 * 6 + 1, 8, gd)
    st = eng.kv_pool["state"]["lightning"]
    assert st.shape == (n_light, 3, cfg.lightning_nh, 16, 16)
    assert st.dtype == jnp.float32
    assert eng.block_bytes == n_sparse * (32 * 2 * gd + 8 * gd) * 2
    assert sorted({k[0] for k in eng.lowered_programs()}) == [
        "prefill", "step"]
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1
    eng.close()


def test_the_kernels_in_interpret_mode_through_the_engine():
    """Heads of 128 lanes, the five Mosaic kernels interpreted."""
    cfg, m = tiny_model(
        hidden_size=128, num_heads=2, num_kv_heads=1, head_dim=128,
        lightning_nh=2, lightning_nkv=2, lightning_head_dim=128,
        num_layers=2, mixer_types=[SPARSE, LIGHTNING], prefill_chunk=64,
        sparse_config=dict(kernel_size=32, kernel_stride=16, block_size=64,
                           topk=1, window_size=64, init_blocks=1,
                           dense_len=128))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (200, 100)]
    max_new = [5, 30]
    set_flags({"FLAGS_pallas_interpret": True})
    eng, results = serve_staggered(m, prompts, max_new, max_slots=2,
                                   block_tokens=128, max_seq_len=256)
    set_flags({"FLAGS_pallas_interpret": False})
    check_against_the_reference(results, prompts, max_new, m, cfg)
    eng.close()


def test_preempted_request_rebuilds_its_state_by_prefill_and_replay():
    """A resume re-prefills the prompt (state at its true length) and
    replays the generated tokens through the step, the other rows idle:
    their states must not move."""
    cfg, m = tiny_model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (80, 70)]
    eng = serving.ServingEngine(m, prefix_caching=False, max_slots=2,
                                block_tokens=32, max_seq_len=192)
    rids = [eng.submit(serving.Request(p, max_new_tokens=24))
            for p in prompts]
    for _ in range(8):
        eng.step()
    victim = next(i for i, s in enumerate(eng._slots) if s is not None)
    eng._land_all()
    eng._preempt(victim)
    results = {}
    for _ in range(200):
        for rid in eng.step()["finished"]:
            results[rid] = eng.pop_result(rid)
        if eng.idle:
            break
    assert eng.stats["requests_resumed"] == 1
    check_against_the_reference([results[r] for r in rids], prompts,
                                [24, 24], m, cfg)
    eng.close()


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
    with pytest.raises(ValueError, match=f"{option!r}.*'sala'"):
        serving.ServingEngine(m, **opts)


def test_a_block_must_hold_whole_compressed_rows():
    _, m = tiny_model()
    with pytest.raises(ValueError, match="second pool leaf"):
        serving.ServingEngine(m, prefix_caching=False, max_slots=2,
                              block_tokens=6, max_seq_len=48)




def test_mla_moe_and_llama_plans_answer_the_new_keys_with_nothing():
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
    paddle_tpu.seed(0)
    models = [DeepseekV2ForCausalLM(DeepseekV2Config.tiny()),
              Xing4ForCausalLM(Xing4Config.tiny()),
              LlamaForCausalLM(LlamaConfig.tiny())]
    for m in models:
        m.eval()
        meta = m.fused_decode_plan(m.state_dict(include_buffers=False),
                                   probe=True)
        assert not {"pool_layers", "pool_aux", "slot_state", "to_state",
                    "prefill_calls"} & set(meta)
        eng = serving.ServingEngine(m, max_slots=2, block_tokens=8,
                                    max_seq_len=32)
        # one array, every layer, as before the seam grew
        assert isinstance(eng.kv_pool, jax.Array)
        assert eng.kv_pool.shape[0] == m.cfg.num_layers
        assert eng.block_bytes == (m.cfg.num_layers * 8
                                   * eng.kv_pool.shape[-1] * 2)
        rid = eng.submit(serving.Request(np.arange(3, 12, dtype=np.int32),
                                         max_new_tokens=4))
        while not eng.idle:
            eng.step()
        assert len(eng.pop_result(rid).tokens) == 4
        assert sorted({k[0] for k in eng.lowered_programs()}) == [
            "prefill", "step"]
        eng.close()
