"""``ServingEngine`` on DeepSeek-V2 as one chip's share of an
expert-parallel layer: the ``mla_moe`` seam taken as it is (no new
option), prefill then paged decode against the plain reference's full
forward (logits, not tokens), the step's five counters and the wave's
counted rows, the options the seam still refuses, and the program set.

The model is float32: the engine's absorbed decode step and the
reference's expanded attention then differ by 1e-6 in a logit.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import _inference_state, generate
from paddle_tpu.models.deepseek_v2 import (STEP_COUNTERS, DeepseekV2Config,
                                           DeepseekV2ForCausalLM)
from paddle_tpu.ops import moe_grouped as mg
from paddle_tpu.serving.spec import SpecConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import reference_deepseek_v2 as ref  # noqa: E402
from test_deepseek_v2 import published_keys  # noqa: E402

# a served token's reference logit against the reference maximum: float32
# sums in another order (tests/test_deepseek_v2.py), twice for a margin
TOL = 4e-4


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


@functools.lru_cache(maxsize=None)
def tiny_share(**over):
    """Chip 1 of 2: experts 8..15 of the router's 16 (groups 2 and 3)."""
    cfg = DeepseekV2Config.tiny(experts_held=8, expert_offset=8, **over)
    paddle_tpu.seed(0)
    m = DeepseekV2ForCausalLM(cfg)
    m.eval()
    return cfg, m


def serve_staggered(m, prompts, max_new, **opts):
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


def check_against_the_reference(interpret: bool, **over):
    cfg, m = tiny_share(**over)
    state = m.state_dict(include_buffers=False)
    set_flags({"FLAGS_pallas_interpret": interpret})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 17, 5, 17, 17, 5)]
    max_new = [6, 9, 6, 9, 9, 6]
    eng, results = serve_staggered(m, prompts, max_new, max_slots=3,
                                   block_tokens=8, max_seq_len=64)
    set_flags({"FLAGS_pallas_interpret": False})
    keys = published_keys(cfg)
    for p, n, res in zip(prompts[:3], max_new, results):
        assert res.finish == "length" and len(res.tokens) == n
        ids = jnp.asarray(res.ids[None], jnp.int32)
        # logits at t predict t + 1: the first comes from the prefill,
        # the rest from the paged decode step
        lg = np.asarray(ref.logits_at(
            state, ids, jnp.arange(len(p) - 1, len(p) + n - 1), keys))
        margin = lg.max(-1) - lg[np.arange(n), res.tokens]
        assert margin.max() < TOL, margin
    return cfg, eng, prompts, max_new, results


def test_prefill_then_paged_decode_agree_with_the_reference():
    cfg, eng, prompts, max_new, results = check_against_the_reference(False)
    # and with an isolated generate, whoever joined or left beside it
    _, m = tiny_share()
    for p, n, res in zip(prompts[3:], max_new[3:], results[3:]):
        want = np.asarray(generate(m, p[None], max_new_tokens=n))[0, len(p):]
        assert res.tokens.tolist() == want.tolist()
    s = eng.stats
    layers = cfg.num_layers - cfg.first_k_dense_replace
    served = s["decode_tokens"] + s["lookahead_discarded_tokens"]
    assert s["moe_layer_steps"] == s["steps"] * layers
    # every pick of the served rows, and the part of them that fell here
    assert s["moe_picks"] == cfg.num_experts_per_tok * served * layers
    assert 0 < s["moe_rows"] < s["moe_picks"]
    assert (s["moe_layer_steps"] <= s["moe_experts_touched"]
            <= min(s["moe_rows"], cfg.experts_held * s["moe_layer_steps"]))
    assert s["moe_rows"] / cfg.experts_held <= s["moe_rows_max"]
    events = [e for e in eng.flight.events() if "moe_picks" in e]
    assert sum(e["moe_picks"] for e in events) == s["moe_picks"]
    assert set(STEP_COUNTERS) <= set(events[0])
    # the weights are held once, the pool holds latent rows
    assert eng._stacked is None and eng.arch == "mla_moe"
    assert eng.kv_pool.shape[-1] == 256 >= cfg.latent_dim
    assert sorted({k[0] for k in eng.lowered_programs()}) == [
        "prefill", "step"]
    # no kernel ran on this CPU: the waves counted no rows
    assert s["prefill_moe_calls"] == s["prefill_moe_rows"] == 0
    eng.close()


def test_the_kernels_in_interpret_mode_and_the_waves_counted_rows(
        monkeypatch):
    """Widths the grouped prefill kernel tiles, sliced (the budget is
    set so that two whole experts of 128 x 384 do not fit)."""
    monkeypatch.setattr(mg, "_VMEM_BUDGET", mg._prefill_vmem(128, 128, 256))
    assert mg._slice_width(128, 384) == 128
    sent = []
    monkeypatch.setattr(
        mg, "_moe_prefill_pallas",
        lambda *a, _f=mg._moe_prefill_pallas, **kw: (
            sent.append((kw["tf"], kw["tm"])), _f(*a, **kw))[1])
    cfg, eng, prompts, *_ = check_against_the_reference(
        True, hidden_size=128, moe_intermediate_size=384)
    layers = cfg.num_layers - cfg.first_k_dense_replace
    s = eng.stats
    assert s["prefill_moe_calls"] == layers * len(prompts)
    # picks on held experts, pad positions included: fewer than every pick
    s_pads = [-(-len(p) // 8) * 8 for p in prompts]
    assert 0 < s["prefill_moe_rows"] < (
        cfg.num_experts_per_tok * sum(s_pads) * layers)
    assert set(sent) == {(128, 256)}
    set_flags({"FLAGS_pallas_interpret": True})
    meta = eng.model.fused_decode_plan(_inference_state(eng.model),
                                       probe=True)
    assert meta["prefill_moe"] == dict(layers=layers, k=3, path="kernel",
                                       rows="counted")
    eng.close()


@pytest.mark.parametrize("option, value", [
    ("cache_dtype", jnp.int8),
    ("speculate", SpecConfig(k=2, proposer="ngram")),
    ("chunk_tokens", 8),
    ("offload", True),
    ("layout", "anything"),
])
def test_options_not_carried_to_the_architecture_are_refused(option, value):
    _, m = tiny_share()
    with pytest.raises(ValueError, match=f"'{option}'.*'mla_moe'"):
        serving.ServingEngine(m, max_slots=2, block_tokens=8,
                              max_seq_len=64, **{option: value})


def test_a_mesh_is_refused(mesh8):
    _, m = tiny_share()
    with pytest.raises(ValueError, match="'mesh'.*'mla_moe'"):
        serving.ServingEngine(m, max_slots=2, block_tokens=8,
                              max_seq_len=64, mesh=mesh8)
