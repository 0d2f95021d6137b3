"""DeepSeek-V2 at tiny widths, whole and as one chip's share: the program
against the benchmark's plain reference
(``benchmark/harness/reference_deepseek_v2.py``, which imports nothing of
``paddle_tpu``), the router against a hand case, the shares of a layer
adding up to the uncut layer, and the vocabulary's slice.

Tolerances. Model and reference are float32 here and differ only in the
order of their sums (the reference's matmuls run at HIGHEST precision,
the program's at the CPU's default float32): logits of magnitude 0.5
agree to 2e-4, as ``tests/test_xing4.py`` found for the same attention.
A forward in bfloat16 misses that by two orders of magnitude.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.models import deepseek_v2 as dsv2
from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                           DeepseekV2ForCausalLM)
from paddle_tpu.nn.layers.moe import group_limited_topk_routing
from paddle_tpu.ops import moe_grouped

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import reference_deepseek_v2 as ref  # noqa: E402

TOL = 2e-4
# (experts held, the first one's id) of the tiny router's 16 in 4 groups
SHARES = {"whole": (None, 0), "chip0_of_2": (8, 0), "chip1_of_2": (8, 8),
          "chip2_of_4": (4, 8)}


def published_keys(cfg: DeepseekV2Config) -> dict:
    """The configuration-file keys the reference reads."""
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.first_k_dense_replace,
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        n_routed_experts=cfg.experts_held,
        router_experts=cfg.n_routed_experts, expert_offset=cfg.expert_offset,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.num_experts_per_tok, n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling,
        vocab_size=cfg.vocab_size)


@functools.lru_cache(maxsize=None)
def tiny(share="chip0_of_2", seed=0, **over):
    held, offset = SHARES[share]
    cfg = DeepseekV2Config.tiny(experts_held=held, expert_offset=offset,
                                **over)
    paddle_tpu.seed(seed)
    m = DeepseekV2ForCausalLM(cfg)
    m.eval()
    return cfg, m, m.state_dict(include_buffers=False)


def jitted(m, **static):
    call = paddle_tpu.nn.functional_call
    return jax.jit(lambda state, ids, **kw: call(m, state, ids, **kw,
                                                 **static))


def some_ids(cfg, b, s, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        3, cfg.vocab_size, (b, s)), jnp.int32)


# ------------------------------------------------- model against reference
@pytest.mark.parametrize("share", sorted(SHARES))
def test_prefill_logits_match_the_reference(share):
    cfg, m, state = tiny(share)
    assert state["model.layers.1.mlp.gate.weight"].shape == (64, 16)
    assert state["model.layers.1.mlp.experts.w_gate"].shape[0] == (
        SHARES[share][0] or 16)
    ids = some_ids(cfg, 2, 24)
    got = np.asarray(jitted(m)(state, ids))
    for r in range(2):
        want = np.asarray(ref.logits_at(state, ids[r:r + 1], jnp.arange(24),
                                        published_keys(cfg)))
        assert np.abs(got[r] - want).max() < TOL
    assert np.abs(got).max() > 0.1      # not a comparison of zeros
    # the head at requested positions only, and the picks that fell here
    at, rows = jitted(m, moe_rows=True)(state, ids,
                                        positions=jnp.asarray([5, 23]))
    assert np.abs(np.asarray(at) - got[[0, 1], [5, 23]]).max() < 1e-6
    picks = 2 * 24 * cfg.num_experts_per_tok * 2      # two expert layers
    assert (int(rows) == picks if share == "whole"
            else 0 < int(rows) < picks)


def test_a_share_differs_from_the_whole_model():
    # the partial result goes on: not the whole model's logits
    cfg, m, state = tiny("chip0_of_2")
    _, whole, wstate = tiny("whole")
    ids = some_ids(cfg, 1, 24)
    assert np.abs(np.asarray(jitted(m)(state, ids))
                  - np.asarray(jitted(whole)(wstate, ids))).max() > 100 * TOL


def test_prefill_then_decode_through_the_latent_cache():
    cfg, m, state = tiny()
    ids = some_ids(cfg, 1, 20, seed=3)
    want = np.asarray(ref.logits_at(state, ids, jnp.arange(20),
                                    published_keys(cfg)))
    cache = m.init_cache(1, 24, dtype=jnp.float32)
    assert cache[0]["ckv"].shape == (1, 24, cfg.latent_dim)
    step = jitted(m)     # one program a shape; start_pos is traced
    lg, cache = step(state, ids[:, :13], cache=cache, start_pos=0)
    assert np.abs(np.asarray(lg)[0] - want[:13]).max() < TOL
    for t in range(13, 20):
        lg, cache = step(state, ids[:, t:t + 1], cache=cache,
                         start_pos=jnp.asarray(t))
        assert np.abs(np.asarray(lg)[0, 0] - want[t]).max() < TOL


def test_bf16_forward_fails_the_tolerance():
    cfg, m, state = tiny()
    ids = some_ids(cfg, 1, 24)
    want = np.asarray(ref.logits_at(state, ids, jnp.arange(24),
                                    published_keys(cfg)))
    low = {k: v.astype(jnp.bfloat16) for k, v in state.items()}
    got = np.asarray(jitted(m)(low, ids), np.float32)[0]
    assert np.abs(got - want).max() > 10 * TOL


# ---------------------------------------------------------------- router
def test_router_keeps_three_groups_and_a_large_score_outside_them_is_lost():
    # 10 experts in 5 groups of 2, 3 groups kept, top-4. Scores (softmax
    # of the logits) by group: (0.25, 0.02) (0.20, 0.04) (0.15, 0.03)
    # (0.12, 0.06) (0.08, 0.05). Groups 0, 1 and 2 are kept. 0.12 is the
    # 4th-largest score overall and lies in the 4th-best group: NOT
    # picked; the picks are 0.25, 0.20, 0.15 and 0.04.
    s = np.asarray([[0.25, 0.02, 0.20, 0.04, 0.15, 0.03, 0.12, 0.06, 0.08,
                     0.05]])
    logits = jnp.log(jnp.asarray(s, jnp.float32))
    idx, w = group_limited_topk_routing(logits, 4, n_group=5, topk_group=3,
                                        scaling=16.0)
    assert np.asarray(idx).tolist() == [[0, 2, 4, 3]]
    assert np.abs(np.asarray(w) - 16.0 * np.asarray(
        [[0.25, 0.20, 0.15, 0.04]])).max() < 1e-5
    # normalised: the picks' own scores to a sum of 1, then the scaling
    _, wn = group_limited_topk_routing(logits, 4, n_group=5, topk_group=3,
                                       scaling=2.0, normalize_topk=True)
    assert np.abs(np.asarray(wn) - 2.0 * np.asarray(
        [[0.25, 0.20, 0.15, 0.04]]) / 0.64).max() < 1e-5


def test_router_against_a_loop_over_random_scores():
    rng = np.random.default_rng(1)
    T, E, G, kg, k = 40, 16, 4, 2, 3
    logits = rng.standard_normal((T, E)).astype(np.float32)
    idx, w = group_limited_topk_routing(jnp.asarray(logits), k, n_group=G,
                                        topk_group=kg, scaling=16.0)
    p = np.exp(logits.astype(np.float64))
    p /= p.sum(-1, keepdims=True)
    lost = 0
    for t in range(T):
        groups = sorted(range(G), key=lambda g: -p[t, 4 * g:4 * g + 4].max())
        allowed = [e for g in groups[:kg] for e in range(4 * g, 4 * g + 4)]
        chosen = sorted(allowed, key=lambda e: -p[t, e])[:k]
        assert np.asarray(idx[t]).tolist() == chosen
        assert np.abs(np.asarray(w[t]) - 16.0 * p[t, chosen]).max() < 1e-5
        lost += chosen != sorted(range(E), key=lambda e: -p[t, e])[:k]
    assert lost > 0        # the group limit changed a choice
    # the reference's router: the same picks and weights, dense
    z = ref.sizes(published_keys(DeepseekV2Config.tiny()))._replace(
        groups=G, top_groups=kg, top_k=k)
    dense, gap = ref.route(jnp.asarray(logits), jnp.eye(E), z)
    want = np.zeros((T, E), np.float32)
    np.put_along_axis(want, np.asarray(idx), np.asarray(w), axis=-1)
    assert np.abs(np.asarray(dense) - want).max() < 1e-5
    assert 0 < float(gap.min()) and float(gap.max()) < 1


def test_held_rows_maps_the_routers_ids_onto_the_stack():
    idx = jnp.asarray([[0, 39, 40, 159], [79, 80, 41, 5]], jnp.int32)
    assert np.asarray(moe_grouped.held_rows(idx, 0, 40)).tolist() == [
        [0, 39, 40, 40], [40, 40, 40, 5]]
    assert np.asarray(moe_grouped.held_rows(idx, 40, 40)).tolist() == [
        [40, 40, 0, 40], [39, 40, 1, 40]]
    # a pick that is not here weighs nothing and is not counted
    held = moe_grouped.held_rows(idx, 0, 40)
    dense = moe_grouped.dense_weights(held, jnp.ones((2, 4)),
                                      jnp.asarray([True, True]), 40)
    assert np.asarray(dense.sum(-1)).tolist() == [2.0, 1.0]
    assert np.asarray(moe_grouped.routing_counts(
        held, jnp.asarray([True, True]), 40)).tolist() == [3, 1, 3]


def test_a_share_must_lie_inside_the_router():
    with pytest.raises(ValueError, match="outside the router"):
        DeepseekV2Config.tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="multiple of n_group"):
        DeepseekV2Config.tiny(n_routed_experts=18)


# ----------------------------------------------------------------- share
@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(path):
    """The guide's share test: what each of the four chips computes for
    its own experts, plus what every chip computes alike (the shared
    experts) counted once, is the uncut reference's layer."""
    cfg, _, state = tiny("whole")
    w = dsv2._sub(state, "model.layers.1.mlp.")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((37, cfg.hidden_size)), jnp.float32)
    z = ref.sizes(published_keys(cfg))
    routed, shared, _ = jax.jit(lambda x, w: ref.layer_parts(x, w, z))(x, w)
    total, counted = jnp.zeros_like(x), 0
    for chip in range(4):
        part = DeepseekV2Config.tiny(experts_held=4, expert_offset=4 * chip)
        rows = slice(4 * chip, 4 * chip + 4)
        wp = dict(w, **{k: w[k][rows] for k in (
            "experts.w_gate", "experts.w_up", "experts.w_down")})
        if path == "prefill":
            y, n = jax.jit(lambda x, wp, part=part: dsv2.moe_prefill(
                wp, part, x))(x, wp)
            y = y - shared          # every chip computes the shared part
        else:
            idx, wts = dsv2.route(wp, part, x)
            n = (idx < 4).sum()
            y = moe_grouped.moe_grouped_ffn_decode(
                x, moe_grouped.dense_weights(idx, wts, jnp.ones(37, bool), 4),
                wp["experts.w_gate"], wp["experts.w_up"],
                wp["experts.w_down"])
        # the same share in the reference
        want, _, _ = ref.layer_parts(x, wp, z._replace(offset=4 * chip))
        assert np.abs(np.asarray(y - want)).max() < 1e-5
        total, counted = total + y, counted + int(n)
    assert counted == 37 * cfg.num_experts_per_tok     # every pick, once
    assert np.abs(np.asarray(total + shared - (routed + shared))).max() < 1e-5
    assert np.abs(np.asarray(routed)).max() > 1e-3


def test_the_vocabulary_slice_is_those_rows_of_the_whole_head():
    cfg, whole, wstate = tiny("chip0_of_2")
    _, m, _ = tiny("chip0_of_2", vocab_size=64)
    state = dict(wstate)
    state["model.embed_tokens.weight"] = wstate[
        "model.embed_tokens.weight"][:64]
    state["lm_head.weight"] = wstate["lm_head.weight"][:, :64]
    ids = jnp.asarray(np.random.default_rng(0).integers(3, 64, (1, 16)),
                      jnp.int32)
    got = np.asarray(jitted(m)(state, ids))
    want = np.asarray(jitted(whole)(wstate, ids))[..., :64]
    assert got.shape == (1, 16, 64)
    assert np.abs(got - want).max() < 1e-6


def test_yarn_scale_of_the_published_configuration():
    cfg = DeepseekV2Config()
    m = 0.1 * 0.707 * np.log(40) + 1
    assert abs(m - 1.2608) < 1e-4 and abs(m * m - 1.5896) < 1e-4
    assert abs(cfg.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    # mscale == mscale_all_dim: the cos/sin factor is 1
    cos, _ = dsv2.rope_tables(cfg, jnp.zeros(1, jnp.int32))
    assert np.abs(np.asarray(cos) - 1.0).max() < 1e-6
    assert cfg.latent_dim == 576 and cfg.experts_held == 160
