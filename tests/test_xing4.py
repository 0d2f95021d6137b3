"""Xing4.0-29B-A4B at tiny widths: the program against the benchmark's
plain reference (``benchmark/harness/reference_xing4.py``, which imports
nothing of ``paddle_tpu``), the pieces against hand-written mathematics,
and the two decode kernels against their ``jnp`` references.

Tolerances. Model and reference are float32 here and differ only in the
order of their sums (the reference's matmuls run at HIGHEST precision,
the program's at the CPU's default float32; absorbed against expanded
attention regroups a product of three matrices): logits of magnitude 0.5
agree to 2e-4. A forward in bfloat16 misses that by two orders of
magnitude (``test_bf16_forward_fails_the_tolerance``).
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import xing4
from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from paddle_tpu.nn.layers import hyper_connection as hc
from paddle_tpu.nn.layers.moe import sigmoid_topk_routing
from paddle_tpu.ops import mla_decode, moe_grouped
from paddle_tpu.ops import rope as rope_ops

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import reference_xing4 as ref  # noqa: E402

TOL = 2e-4


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def published_keys(cfg: Xing4Config) -> dict:
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
        n_routed_experts=cfg.n_routed_experts,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, hc_mult=cfg.hc_mult,
        hc_sinkhorn_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
        mhc_h_res_clamp_min=cfg.mhc_h_res_clamp_min,
        mhc_h_res_clamp_max=cfg.mhc_h_res_clamp_max,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling=cfg.rope_scaling, vocab_size=cfg.vocab_size)


@functools.lru_cache(maxsize=None)
def tiny(seed=0, **over):
    """A float32 model whose gains, biases and correction bias are NOT
    the defaults, so that every term of the mixers and the router's
    bias are exercised."""
    # three Sinkhorn rounds: what 20 converge to is held in the mixer's
    # own test below, and the CPU compiles every round of every mixer
    cfg = Xing4Config.tiny(**{"hc_sinkhorn_iters": 3, **over})
    paddle_tpu.seed(seed)
    m = Xing4ForCausalLM(cfg)
    m.eval()
    rng = np.random.default_rng(seed + 1)
    state = m.state_dict(include_buffers=False)
    for k, v in state.items():
        if k.endswith("bias") or "alpha_" in k:
            base = 1.0 if "alpha_" in k else 0.0
            state[k] = jnp.asarray(
                base + 0.3 * rng.standard_normal(v.shape), v.dtype)
    m.set_state_dict(state)
    return cfg, m, m.state_dict(include_buffers=False)


def jitted(m, **static):
    """``m``'s forward as one compiled program over (state, ids, ...):
    run eagerly it compiles every small op of every layer by itself."""
    call = paddle_tpu.nn.functional_call
    return jax.jit(lambda state, ids, **kw: call(m, state, ids, **kw,
                                                 **static))


def some_ids(cfg, b, s, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        3, cfg.vocab_size, (b, s)), jnp.int32)


# ------------------------------------------------- model against reference
def test_prefill_logits_match_the_reference():
    cfg, m, state = tiny()
    ids = some_ids(cfg, 2, 24)
    got = np.asarray(jitted(m)(state, ids))
    pos = jnp.arange(24)
    for r in range(2):
        want = np.asarray(ref.logits_at(state, ids[r:r + 1], pos,
                                        published_keys(cfg)))
        assert np.abs(got[r] - want).max() < TOL
    assert np.abs(got).max() > 0.1      # not a comparison of zeros
    # the head at requested positions only
    at = np.asarray(jitted(m)(state, ids, positions=jnp.asarray([5, 23])))
    assert np.abs(at - got[[0, 1], [5, 23]]).max() < 1e-6


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


def test_mtp_forward_matches_the_reference():
    cfg, m, state = tiny()
    ids = some_ids(cfg, 1, 16, seed=5)
    logits, mtp = jitted(m, mtp=True)(state, ids)
    want = np.asarray(ref.mtp_logits(state, ids, published_keys(cfg)))
    assert mtp.shape == (1, 15, cfg.vocab_size)
    assert np.abs(np.asarray(mtp)[0] - want).max() < TOL
    # the main model's logits do not depend on the MTP layer
    assert np.abs(np.asarray(logits)
                  - np.asarray(jitted(m)(state, ids))).max() < 1e-6
    with pytest.raises(ValueError, match="mtp=True"):
        m(ids, mtp=True, cache=m.init_cache(1, 16))


def test_bf16_forward_fails_the_tolerance():
    cfg, m, state = tiny()
    ids = some_ids(cfg, 1, 24)
    want = np.asarray(ref.logits_at(state, ids, jnp.arange(24),
                                    published_keys(cfg)))
    low = {k: v.astype(jnp.bfloat16) for k, v in state.items()}
    got = np.asarray(jitted(m)(low, ids), np.float32)[0]
    assert np.abs(got - want).max() > 10 * TOL


def test_absorbed_and_expanded_attention_agree():
    cfg, m, state = tiny()
    w = xing4._sub(state, "model.layers.1.self_attn.")
    rng = np.random.default_rng(2)
    b, S = 3, 11
    x = jnp.asarray(rng.standard_normal((b, S, cfg.hidden_size)), jnp.float32)
    cos, sin = xing4.rope_tables(cfg, jnp.arange(S))
    q_n, q_r, lat = xing4.mla_project(w, cfg, x, cos, sin)
    want = xing4.mla_expanded(w, cfg, q_n, q_r, lat, 0)[:, -1]
    # the last position, absorbed: scores and the weighted sum over the
    # cached rows themselves
    lanes = mla_decode.pool_lanes(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    assert lanes == 256 and lanes > cfg.latent_dim
    q = xing4.mla_absorb_query(w, cfg, q_n[:, -1], q_r[:, -1], lanes)
    rows = mla_decode.pad_lanes(lat, lanes)
    s = jnp.einsum("bhp,bsp->bhs", q, rows) * cfg.softmax_scale
    o_c = jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(s, -1),
                     rows[..., :cfg.kv_lora_rank])
    got = xing4.mla_absorb_out(w, cfg, o_c)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.abs(np.asarray(want)).max() > 1e-3


# ------------------------------------------------------------------- mHC
def test_h_res_is_doubly_stochastic_and_identity_mixers_are_the_residual():
    rng = np.random.default_rng(0)
    n, C = 4, 32
    # logits of about +-1: 20 rounds converge to 1e-4 there (at the +-5
    # of a near-permutation they leave the columns off by a per cent,
    # which the published hc_sinkhorn_iters accepts)
    layer = hc.HyperConnection(n, C, initializer_range=0.05)
    X = jnp.asarray(rng.standard_normal((5, 7, n, C)), jnp.float32)
    h_pre, h_post, h_res = layer(X)
    assert h_pre.shape == (5, 7, n) and h_res.shape == (5, 7, n, n)
    assert np.abs(np.asarray(h_res.sum(-1)) - 1).max() < 1e-4
    assert np.abs(np.asarray(h_res.sum(-2)) - 1).max() < 1e-4
    assert float(h_res.min()) > 0 and float(h_res.std()) > 0.02
    assert 0 < float(h_pre.min()) and float(h_post.max()) < 2
    # clamped logits stay finite through exp
    big = hc.sinkhorn(jnp.clip(jnp.full((n, n), 1e4).at[0, 0].set(-1e4),
                               -30, 30), 20, 1e-6)
    assert np.isfinite(np.asarray(big)).all()
    # H_pre = H_post = e_1, H_res = I: stream 0 is x + F(x), the rest stay
    e1 = jnp.zeros(n).at[0].set(1.0)
    F = lambda x: 3.0 * x + 1.0
    y = F(hc.hc_read(X, jnp.broadcast_to(e1, (5, 7, n))))
    out = hc.hc_write(X, y, jnp.broadcast_to(e1, (5, 7, n)),
                      jnp.broadcast_to(jnp.eye(n), (5, 7, n, n)))
    assert np.abs(np.asarray(out[..., 0, :] - (X[..., 0, :]
                                               + F(X[..., 0, :])))).max() < 1e-5
    assert np.abs(np.asarray(out[..., 1:, :] - X[..., 1:, :])).max() == 0.0


# ---------------------------------------------------------------- router
def test_router_against_a_hand_written_top4_with_a_correction_bias():
    rng = np.random.default_rng(1)
    T, E, k = 9, 16, 4
    logits = rng.standard_normal((T, E)).astype(np.float32)
    bias = (0.4 * rng.standard_normal(E)).astype(np.float32)
    idx, w = sigmoid_topk_routing(jnp.asarray(logits), jnp.asarray(bias), k,
                                  scaling=2.0, normalize_topk=True)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    moved = 0
    for t in range(T):
        chosen = sorted(range(E), key=lambda e: -(s[t, e] + bias[e]))[:k]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(chosen)
        moved += sorted(chosen) != sorted(
            sorted(range(E), key=lambda e: -s[t, e])[:k])
        for j, e in enumerate(np.asarray(idx[t])):
            want = s[t, e] / (sum(s[t, c] for c in chosen) + 1e-20) * 2.0
            assert abs(float(w[t, j]) - want) < 1e-6
    assert moved > 0        # the bias changed a choice, never a weight
    assert np.abs(np.asarray(w.sum(-1)) - 2.0).max() < 1e-5


def test_yarn_frequencies():
    plain = rope_ops._freqs(64, 10000.0)
    yarn = rope_ops._yarn_freqs(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    want = ref.yarn_inv_freq(ref.sizes(dict(
        published_keys(Xing4Config()))))
    assert np.abs(yarn - want).max() < 1e-7
    # fast dimensions keep their frequency, slow ones are divided by 64
    assert yarn[0] == plain[0] and abs(yarn[-1] * 64 / plain[-1] - 1) < 1e-6
    assert (np.diff(yarn) < 0).all()
    assert abs(Xing4Config().softmax_scale
               - 192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9


# --------------------------------------------------------------- kernels
def _tables_for(pos, active, BT, MB):
    """Each active row's own blocks, fresh, up to its new token's block;
    an idle row's table is all scratch (block 0)."""
    tables, nxt = np.zeros((len(pos), MB), np.int32), 1
    for r, (p, a) in enumerate(zip(pos, active)):
        if a:
            n = p // BT + 1
            tables[r, :n] = np.arange(nxt, nxt + n)
            nxt += n
    return tables, nxt


# (positions, active rows or None for every row, heads), with BT 16, MB 4
_MLA_CASES = {
    # idle rows at positions the own step has advanced them to (one
    # past the table: an idle row reads none of it)
    "idle_advanced": ([5, 20, 300, 33], [False, True, False, True], 4),
    # a row at 0 is its new token alone; a row at 7 has 7 cached tokens
    # in its first block, walked before the new token
    "short_rows": ([0, 7, 0, 12], [True, True, False, True], 4),
    # rows that end on a block edge and just past one
    "block_edges": ([15, 16, 17, 32, 33, 47], None, 4),
    "scattered": ([9, 40, 3, 0, 25, 61, 2, 18],
                  [False, True, False, True, False, True, False, True], 4),
    # a one-block row before a long one: the ring runs across rows
    "ring_across_rows": ([5, 63, 3, 50], None, 4),
    "heads_16": ([40, 0, 17, 1, 33], [True, True, True, False, True], 16),
}


@pytest.mark.parametrize("case", ["uneven", *_MLA_CASES])
def test_mla_paged_decode_kernel_matches_its_reference_uneven_rows(case):
    """The kernel walks the active rows' blocks only; an idle row (scratch
    table row, at whatever position) writes nothing into the pool and
    returns zeros, in the kernel as in the reference."""
    rng = np.random.default_rng(0)
    L, BT, P, dc, MB = 2, 16, 256, 128, 4
    if case == "uneven":
        H, NB = 4, 10
        # rows of 41, 1, 18, 0 (idle, scratch) and 32 tokens; 16 and 32
        # sit on block edges
        tables = np.asarray([[1, 2, 3], [4, 0, 0], [5, 6, 0], [0, 0, 0],
                             [7, 8, 9]], np.int32)
        pos = np.asarray([40, 0, 17, 0, 32], np.int32)
    else:
        pos, active, H = _MLA_CASES[case]
        pos = np.asarray(pos, np.int32)
        active = [True] * len(pos) if active is None else active
        tables, NB = _tables_for(pos, active, BT, MB)
    pool = jnp.asarray(rng.standard_normal((L, NB, BT, P)), jnp.bfloat16)
    b = len(pos)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    q = jnp.asarray(rng.standard_normal((b, H, P)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((b, P)), jnp.bfloat16)
    kw = dict(layer=1, d_c=dc, scale=0.07)
    want_o, want_pool = mla_decode.mla_paged_decode_reference(
        q, new, pool, tables, pos, **kw)
    set_flags({"FLAGS_pallas_interpret": True})
    got_o, got_pool = jax.jit(
        lambda *a: mla_decode.mla_paged_decode(*a, **kw))(
            q, new, pool, tables, pos)
    idle = np.asarray(tables[:, 0] == 0)
    got_o, want_o = np.asarray(got_o), np.asarray(want_o)
    # the probabilities go to the matrix unit in bfloat16
    assert np.abs(got_o - want_o)[~idle].max() < 5e-3
    assert (got_o[idle] == 0).all() and (want_o[idle] == 0).all()
    assert (np.asarray(got_pool) == np.asarray(want_pool)).all()
    # an idle row wrote nothing: the scratch block is as it was
    assert (np.asarray(got_pool[:, 0]) == np.asarray(pool[:, 0])).all()
    assert (np.asarray(got_pool[0]) == np.asarray(pool[0])).all()
    for r in np.flatnonzero(~idle):
        p = int(pos[r])
        assert (np.asarray(got_pool[1, int(tables[r, p // BT]), p % BT])
                == np.asarray(new[r])).all()


def test_mla_walk_list():
    """`mla_walk`: the active rows' items, row-major, one a block that
    holds a cached token and at least one (a row at position 0 has its
    new token alone); none for an idle row, whatever its position; the
    idle rows in order; numpy in gives numpy out and agrees with the
    traced lists and with the engine's counts."""
    BT, MB = 16, 4
    pos = np.asarray([0, 5, 32, 63, 11, 40, 17], np.int32)
    tables = np.ones((len(pos), MB), np.int32)
    tables[[4, 6]] = 0                          # idle, at 11 and 17
    items = mla_decode.mla_walk_items(pos, tables, BT)
    assert items.tolist() == [1, 1, 2, 4, 0, 3, 0]
    row, chunk, total, idle, n_idle = mla_decode.mla_walk(pos, tables, BT)
    assert isinstance(row, np.ndarray) and row.shape == (len(pos) * MB,)
    assert row.dtype == chunk.dtype == idle.dtype == np.int32
    assert int(total) == 11 and int(n_idle) == 2
    assert list(zip(row[:11].tolist(), chunk[:11].tolist())) == [
        (0, 0), (1, 0), (2, 0), (2, 1), (3, 0), (3, 1), (3, 2), (3, 3),
        (5, 0), (5, 1), (5, 2)]
    assert idle[:2].tolist() == [4, 6]
    assert row.max() < len(pos) and chunk.min() >= 0 and chunk.max() < MB
    got = jax.jit(lambda p, t: mla_decode.mla_walk(p, t, BT))(
        jnp.asarray(pos), jnp.asarray(tables))
    for a, b in zip(got, (row, chunk, total, idle, n_idle)):
        assert (np.asarray(a) == b).all()
    assert mla_decode.mla_walk_counts(pos, tables, BT) == (11, 7 * 4, 5)
    # every row idle: no item, every row's output zeroed
    none = np.zeros_like(tables)
    assert int(mla_decode.mla_walk(pos, none, BT)[2]) == 0
    assert int(mla_decode.mla_walk(pos, none, BT)[4]) == len(pos)
    assert mla_decode.mla_walk_counts(pos, none, BT) == (0, 0, 0)


def test_moe_grouped_ffn_kernel_matches_its_reference_untouched_experts():
    rng = np.random.default_rng(0)
    b, E, C, F, k = 5, 8, 128, 256, 2
    x = jnp.asarray(rng.standard_normal((b, C)), jnp.bfloat16)
    wg, wu = (jnp.asarray(0.05 * rng.standard_normal((E, C, F)), jnp.bfloat16)
              for _ in range(2))
    wd = jnp.asarray(0.05 * rng.standard_normal((E, F, C)), jnp.bfloat16)
    idx = jnp.asarray([[0, 3], [3, 5], [5, 0], [1, 2], [3, 0]], jnp.int32)
    w = jnp.asarray(rng.uniform(size=(b, k)), jnp.float32)
    active = jnp.asarray([True, True, True, False, True])
    dense = moe_grouped.dense_weights(idx, w, active, E)
    # experts 1, 2 (an idle row's), 4, 6, 7 are untouched
    assert np.asarray((dense != 0).any(0)).tolist() == [
        True, False, False, True, False, True, False, False]
    assert np.asarray(moe_grouped.routing_counts(idx, active, E)).tolist() \
        == [3, 3, 8]
    want = moe_grouped.moe_grouped_ffn_reference(x, dense, wg, wu, wd)
    by_hand = np.zeros((b, C), np.float32)
    f32 = lambda a: np.asarray(a, np.float32)
    for r in range(b):
        for j in range(k):
            if active[r]:
                e = int(idx[r, j])
                h, u = f32(x[r]) @ f32(wg[e]), f32(x[r]) @ f32(wu[e])
                by_hand[r] += float(w[r, j]) * (
                    (h / (1 + np.exp(-h)) * u) @ f32(wd[e]))
    assert np.abs(f32(want) - by_hand).max() < 0.02 * np.abs(by_hand).max()
    set_flags({"FLAGS_pallas_interpret": True})
    got = jax.jit(moe_grouped.moe_grouped_ffn_decode)(x, dense, wg, wu, wd)
    assert np.abs(f32(got) - f32(want)).max() <= 2 ** -8 * np.abs(
        f32(want)).max()
    assert np.abs(f32(got[3])).max() == 0.0         # the idle row
    # no active row at all: nothing is touched, the result is zero
    none = jnp.zeros_like(dense)
    assert np.abs(f32(jax.jit(moe_grouped.moe_grouped_ffn_decode)(
        x, none, wg, wu, wd))).max() == 0.0
