"""``ServingEngine`` on an architecture that brings its own step body
(Xing4: a latent pool, routed experts, four residual streams): the
parity contract of ``tests/test_serving.py`` (a request's tokens equal
an isolated ``generate``, whoever joins or leaves beside it), the step
counters, the refused options, and the llama engine left as it was.

The model is float32: the engine's absorbed decode step and
``generate``'s expanded one then differ by 1e-6 in a logit, far below
the gap between a greedy token and its runner-up.
"""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import generate
from paddle_tpu.models.xing4 import (STEP_COUNTERS, Xing4Config,
                                     Xing4ForCausalLM)
from paddle_tpu.serving.spec import SpecConfig


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


@functools.lru_cache(maxsize=None)
def tiny_xing4():
    """One model for the whole file, so that ``generate``'s programs
    (cached on the model) compile once; four Sinkhorn rounds, because
    the engine does not care how many and the CPU compiles them all."""
    cfg = Xing4Config.tiny(num_nextn_predict_layers=0, hc_sinkhorn_iters=4)
    paddle_tpu.seed(0)
    m = Xing4ForCausalLM(cfg)
    m.eval()
    return cfg, m


def serve_staggered(m, prompts, max_new, **opts):
    """Submit one request every other tick into fewer slots than there
    are requests, so joins and leaves interleave with decoding."""
    eng = serving.ServingEngine(m, **opts)
    pending = list(zip(prompts, max_new))
    rids, results, ticks = [], {}, 0
    while pending or not eng.idle:
        if pending and ticks % 2 == 0:
            p, n = pending.pop(0)
            rids.append(eng.submit(serving.Request(p, max_new_tokens=n)))
        for rid in eng.step()["finished"]:
            results[rid] = eng.pop_result(rid)
        ticks += 1
        assert ticks < 500
    return eng, [results[r] for r in rids]


def check_parity(interpret: bool):
    cfg, m = tiny_xing4()
    set_flags({"FLAGS_pallas_interpret": interpret})
    rng = np.random.default_rng(0)
    # two shapes, each three times: two prefill programs, two isolated
    # generate programs
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 17, 5, 17, 17, 5)]
    max_new = [6, 9, 6, 9, 9, 6]
    eng, results = serve_staggered(m, prompts, max_new, max_slots=3,
                                   block_tokens=8, max_seq_len=64)
    set_flags({"FLAGS_pallas_interpret": False})
    for p, n, res in zip(prompts, max_new, results):
        want = np.asarray(generate(m, p[None], max_new_tokens=n))[0, len(p):]
        assert res.tokens.tolist() == want.tolist()
        assert res.finish == "length"
    return cfg, eng


def test_joined_and_leaving_requests_equal_isolated_generate():
    cfg, eng = check_parity(interpret=False)
    s = eng.stats
    expert_layers = cfg.num_layers - cfg.first_k_dense_replace
    # the program counts the rows it served: those committed and those a
    # look-ahead program computed for rows that left at the pull before
    # it (no eos here, so no program in flight is ever dropped unpulled)
    served = s["decode_tokens"] + s["lookahead_discarded_tokens"]
    assert s["lookahead_ticks"] > 0
    assert s["moe_layer_steps"] == s["steps"] * expert_layers
    assert s["moe_rows"] == cfg.num_experts_per_tok * served * expert_layers, (
        {k: s[k] for k in ("steps", "decode_tokens", "lookahead_ticks",
                           "lookahead_discarded_tokens", "upload_ticks",
                           *STEP_COUNTERS)},
        [(e["step"], e["active"], e.get("moe_rows"), e["lookahead"],
          e["admitted"], e["retired"]) for e in eng.flight.events()])
    assert (s["moe_layer_steps"] <= s["moe_experts_touched"]
            <= min(s["moe_rows"], cfg.n_routed_experts * s["moe_layer_steps"]))
    assert (s["moe_rows"] / cfg.n_routed_experts <= s["moe_rows_max"]
            <= served * expert_layers)
    # the same four in the flight event of a decode tick
    events = [e for e in eng.flight.events() if "moe_rows" in e]
    assert 0 < len(events) <= s["steps"]
    assert sum(e["moe_rows"] for e in events) == s["moe_rows"]
    assert set(STEP_COUNTERS) <= set(events[0])
    # the weights are held once, the pool holds latent rows
    assert eng._stacked is None
    lanes = eng.kv_pool.shape[-1]
    assert lanes == 256 and lanes >= cfg.latent_dim
    assert eng.block_bytes == cfg.num_layers * 8 * lanes * 2
    assert eng.pool.used_blocks == 0 or eng.prefix_cache is not None
    assert sorted({k[0] for k in eng.lowered_programs()}) == [
        "prefill", "step"]
    eng.close()


def test_parity_through_the_kernels_in_interpret_mode(monkeypatch):
    from paddle_tpu.ops import mla_decode, moe_grouped
    traced = []
    for mod, name in ((mla_decode, "_mla_paged_decode_pallas"),
                      (moe_grouped, "_moe_grouped_ffn_pallas")):
        def spy(*a, _f=getattr(mod, name), _n=name, **kw):
            traced.append(_n)
            assert kw["interpret"]
            return _f(*a, **kw)
        monkeypatch.setattr(mod, name, spy)
    cfg, eng = check_parity(interpret=True)
    # the step program was traced once, through both kernels
    assert traced.count("_mla_paged_decode_pallas") == cfg.num_layers
    assert traced.count("_moe_grouped_ffn_pallas") == (
        cfg.num_layers - cfg.first_k_dense_replace)
    eng.close()


def test_the_engine_counts_the_latent_walk_of_the_active_rows():
    """`engine.stats` sums the decode kernel's walk over the landed steps
    with the kernel's own list counts (`mla_decode.mla_walk_counts`), on
    the host's positions and tables: the idle slots, which the own step
    advances, walk nothing."""
    from paddle_tpu.ops import mla_decode
    cfg, m = tiny_xing4()
    eng = serving.ServingEngine(m, max_slots=4, block_tokens=8,
                                max_seq_len=64, prefix_caching=False)
    rng = np.random.default_rng(5)
    for n, new in ((20, 14), (37, 6), (9, 10)):
        eng.submit(serving.Request(
            rng.integers(3, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=new))
    walked = dense = rows = 0
    while not eng.idle:
        steps0 = eng.stats["steps"]
        eng.step()
        if eng.stats["steps"] > steps0:
            w, d, r = mla_decode.mla_walk_counts(eng._positions,
                                                 eng._tables, 8)
            assert r == int((eng._tables[:, 0] != 0).sum())
            walked, dense, rows = walked + w, dense + d, rows + r
    s = eng.stats
    assert s["steps"] >= 12
    assert (s["kv_blocks_walked"], s["kv_blocks_dense"],
            s["kv_rows_walked"]) == (walked, dense, rows)
    assert 0 < s["kv_blocks_walked"] <= s["kv_blocks_dense"]
    # three requests in four slots: a slot is always idle
    assert 0 < s["kv_rows_walked"] < 4 * s["steps"]
    eng.reset_stats()
    assert eng.stats["kv_rows_walked"] == eng.stats["kv_blocks_walked"] == 0
    eng.close()


def test_a_shared_prefix_is_read_back_from_the_latent_pool():
    cfg, m = tiny_xing4()
    rng = np.random.default_rng(4)
    head = rng.integers(3, cfg.vocab_size, 24).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(
        3, cfg.vocab_size, n).astype(np.int32)]) for n in (5, 9)]
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=8,
                                max_seq_len=64)
    for p in prompts:       # one after the other: the second hits
        rid = eng.submit(serving.Request(p, max_new_tokens=6))
        eng.drain(max_steps=100)
        want = np.asarray(generate(m, p[None], max_new_tokens=6))[0, len(p):]
        assert eng.results[rid].tokens.tolist() == want.tolist()
    assert eng.stats["prefill_tokens_reused"] == 24
    eng.close()


@pytest.mark.parametrize("option, value", [
    ("cache_dtype", jnp.int8),
    ("speculate", SpecConfig(k=2, proposer="ngram")),
    ("chunk_tokens", 8),
    ("offload", True),
    ("layout", "anything"),
])
def test_options_not_carried_to_the_architecture_are_refused(option, value):
    _, m = tiny_xing4()
    with pytest.raises(ValueError, match=f"'{option}'.*'mla_moe'"):
        serving.ServingEngine(m, max_slots=2, block_tokens=8,
                              max_seq_len=64, **{option: value})


def test_a_mesh_is_refused(mesh8):
    _, m = tiny_xing4()
    with pytest.raises(ValueError, match="'mesh'.*'mla_moe'"):
        serving.ServingEngine(m, max_slots=2, block_tokens=8,
                              max_seq_len=64, mesh=mesh8)


def test_a_llama_engine_is_what_it_was():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=256,
        max_position_embeddings=512)).bfloat16()
    m.eval()
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=64)
    rid = eng.submit(serving.Request(np.arange(3, 12), max_new_tokens=5))
    eng.drain(max_steps=50)
    assert len(eng.results[rid].tokens) == 5
    assert not any(k.startswith("moe_") for k in eng.stats)
    assert eng._stacked is not None and len(eng._toks) == 2
    assert eng.kv_pool.shape[-1] == 2 * 2 * 32
    progs = eng.lowered_programs()
    assert sorted(k[0] for k in progs) == ["prefill", "step"]
    step = progs[("step",)].as_text()
    assert "mla_paged_decode" not in step
    # tokens in, tokens out: no counters ride a llama step's pull
    assert step.count("tensor<2xi32>") >= 2
    eng.close()
