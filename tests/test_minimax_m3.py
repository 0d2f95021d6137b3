"""MiniMax-M3 against its plain reference
(``benchmark/harness/reference_minimax_m3.py``), at tiny widths, on the
CPU: the chunked forward's logits and the blocks every query of every
layer selects, a padded row's pooled keys, the running max of a decode
step against a batch max-pool, the decode step's selection through the
block table, the ``swigluoai`` expert kernels (interpreted) against
their ``jnp`` references with the SiLU path unchanged, the block-level
selection of the sparse prefill kernel, the expert shares adding up to
the uncut layer, and a control for each piece of the mathematics that
FAILS when the piece is left out.

The model is float32 and matmuls run at full precision, so the chunked
program and the reference differ by summation order only. Blocks of 8
tokens and a top 2 make the selection bite past 32 tokens.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import minimax_m3 as mm
from paddle_tpu.models.minimax_m3 import MiniMaxM3Config, MiniMaxM3ForCausalLM
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.ops import moe_grouped
from paddle_tpu.ops import msa_index as msa
from paddle_tpu.ops import sparse_paged as spg

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import model as hmodel  # noqa: E402
from harness import reference_minimax_m3 as ref  # noqa: E402

# float32 sums in another order: 160 tokens through 3 layers, logits of
# size 10
TOL = 5e-5


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def published_keys(cfg: MiniMaxM3Config) -> dict:
    """The configuration-file keys the reference reads, from a program
    config (the reference never sees the program's own object)."""
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_hidden_layers,
        moe_layer_freq=cfg.moe_layer_freq,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rotary_dim=cfg.rotary_dim, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps, msa_index_heads=cfg.msa_index_heads,
        msa_block_size=cfg.msa_block_size, msa_topk=cfg.msa_topk,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        swiglu_alpha=cfg.swiglu_alpha, swiglu_limit=cfg.swiglu_limit,
        expert_offset=cfg.expert_offset,
        intermediate_size=cfg.intermediate_size,
        dense_intermediate_size=cfg.dense_intermediate_size,
        shared_intermediate_size=cfg.shared_intermediate_size,
        n_shared_experts=cfg.n_shared_experts,
        num_local_experts=cfg.num_local_experts,
        router_experts=cfg.router_experts, vocab_size=cfg.vocab_size)


def tiny(seed: int = 3, std: float = 0.1, **over):
    cfg = MiniMaxM3Config.tiny(**over)
    with paddle_tpu.LazyGuard():
        m = MiniMaxM3ForCausalLM(cfg)
    m.eval()
    state = hmodel.make_state(m.state_dict(include_buffers=False), seed, std,
                              jnp.float32)
    return cfg, m, state


def reference_logits(state, ids, cfg):
    keys = published_keys(cfg)
    return jnp.stack([ref.logits_at(state, ids[i:i + 1],
                                    jnp.arange(ids.shape[1]), keys)
                      for i in range(ids.shape[0])])


IDS = jax.random.randint(jax.random.key(5), (2, 160), 3, 256)


def agree_with_the_reference(cfg, m, state, ids):
    """Logits within ``TOL`` of the reference's and the SAME blocks at
    every query of every layer (row 0) -> (the reference's logits, its
    blocks a layer)."""
    n, s = ids.shape
    with jax.default_matmul_precision("highest"):
        lg = functional_call(m, state, ids)
        want = reference_logits(state, ids, cfg)
        _, _, _, blocks = mm.hidden_forward(
            state, cfg, ids, mm.init_cache(cfg, n, s, jnp.float32),
            return_blocks=True)
    assert float(jnp.abs(lg - want).max()) < TOL
    keys = published_keys(cfg)
    sels = [np.asarray(ref.blocks(state, ids[0], keys, layer))
            for layer in range(cfg.num_hidden_layers)]
    for layer, sel in enumerate(sels):
        assert (np.asarray(blocks[layer, 0]) == sel).all()
    return want, sels


def test_forward_and_selection_agree_with_the_reference():
    """160 tokens in five chunks of 32, ``dense_len`` 32: most queries
    select. Logits close, and the SAME blocks at every query of every
    layer."""
    cfg, m, state = tiny()
    _, sels = agree_with_the_reference(cfg, m, state, IDS)
    for sel in sels:
        # past dense_len a query reads the first, its own and 2 more
        # blocks of the up to 20 it could; under it every block
        assert (sel[40:].sum(-1) == 4).all()
        assert (sel[:32].sum(-1) == (np.arange(32) // 8 + 1)[:, None]).all()


@pytest.mark.parametrize("per_call, groups", [(2, [2, 2, 1]), (1, [1] * 5)],
                         ids=["a_smaller_last_group", "a_call_a_chunk"])
def test_groups_of_chunks_agree_with_the_reference(monkeypatch, per_call,
                                                   groups):
    """At the tiny widths a chunk of 32 gives a held expert 4 rows, so
    the rule sends all five chunks of 160 tokens through one call of the
    expert kernel; cut to ``per_call`` chunks a call, the program runs
    two groups of two and a last group of one, or a group a chunk: the
    reference's logits, at a row's last position too (in a middle group
    and in the last one), and its blocks at every layer."""
    cfg, m, state = tiny()
    assert mm.prefill_groups(cfg, 160) == (32, [5])
    monkeypatch.setattr(mm, "chunks_a_call",
                        lambda cfg, C, chunks: min(per_call, chunks))
    assert mm.prefill_groups(cfg, 160) == (32, groups)
    want, _ = agree_with_the_reference(cfg, m, state, IDS)
    at = jnp.array([70, 159])
    with jax.default_matmul_precision("highest"):
        lg = functional_call(m, state, IDS, positions=at)
    assert float(jnp.abs(lg - want[jnp.arange(2), at]).max()) < TOL


def test_three_published_chunks_share_a_call_of_the_expert_kernel():
    """A chunk of 2,048 gives a held expert 64 rows (4 picks of 128
    experts): three chunks, 192 rows and three standard deviations, fit
    the kernel's row tile of 256 and four do not. The ``longdoc-32k``
    cell's buckets of 5 to 16 chunks, a wave shorter than a group, and a
    chunk that fills the tile on its own: a call a chunk."""
    cfg = MiniMaxM3Config(num_local_experts=16, router_experts=128)
    assert mm.chunks_a_call(cfg, 2048, 16) == 3
    assert mm.prefill_groups(cfg, 10240) == (2048, [3, 2])
    assert mm.prefill_groups(cfg, 12288) == (2048, [3, 3])
    assert mm.prefill_groups(cfg, 16384) == (2048, [3, 3, 2])
    assert mm.prefill_groups(cfg, 32768) == (2048, [3] * 5 + [1])
    assert mm.prefill_groups(cfg, 4096) == (2048, [2])
    assert mm.prefill_groups(cfg, 1024) == (1024, [1])
    wide = MiniMaxM3Config(num_local_experts=16, router_experts=128,
                           prefill_chunk=8192)
    assert mm.prefill_groups(wide, 32768) == (8192, [1] * 4)


@pytest.mark.parametrize("chunk", [40, 80])
def test_chunks_across_dense_len_select_as_the_reference(chunk):
    """A chunk that straddles ``dense_len`` takes the selecting path,
    one past it selects: every query of every layer reads the
    reference's blocks."""
    cfg, m, state = tiny(prefill_chunk=chunk)
    ids = IDS[:1]
    with jax.default_matmul_precision("highest"):
        _, _, _, blocks = mm.hidden_forward(
            state, cfg, ids, mm.init_cache(cfg, 1, 160, jnp.float32),
            return_blocks=True)
        keys = published_keys(cfg)
        for layer in range(cfg.num_hidden_layers):
            sel = np.asarray(ref.blocks(state, ids[0], keys, layer))
            assert (np.asarray(blocks[layer, 0]) == sel).all()


def test_a_padded_row_pools_its_true_tokens_only():
    """A wave row of 77 true tokens padded to 96: its pooled keys are
    the max over its own tokens (the partial block's too), its logits
    at 76 are the reference's over the 77."""
    cfg, m, state = tiny()
    ids = IDS[:1, :96]
    with jax.default_matmul_precision("highest"):
        cache = mm.init_cache(cfg, 1, 96, jnp.float32)
        lg, cache, _ = functional_call(m, state, ids, cache=cache,
                                       positions=jnp.array([76]),
                                       moe_rows=True)
        want = ref.logits_at(state, ids[:, :77], jnp.array([76]),
                             published_keys(cfg))
        assert float(jnp.abs(lg - want).max()) < TOL
        G, d = cfg.num_key_value_heads, cfg.head_dim
        k = np.asarray(cache["kv"][:, 0, :77, :G * d])
        pk = np.asarray(cache["pk"][:, 0])              # (L, 12, G d)
        full = k[:, :72].reshape(-1, 9, 8, G * d).max(2)
        assert np.array_equal(pk[:, :9], full)
        assert np.array_equal(pk[:, 9], k[:, 72:77].max(1))


def test_the_running_max_is_a_batch_max_pool():
    """Tokens appended one a step through a shuffled block table: every
    block's pooled row is the max of its keys so far, as
    :func:`pool_chunk` takes it of a whole chunk."""
    sp = msa.msa_config(8, 2)
    L, NBp, BT, lanes = 2, 6, 32, 16
    n = 90
    keys = jax.random.normal(jax.random.key(2), (n, lanes))
    tables = jnp.array([[4, 2, 5]], jnp.int32)
    pool = jnp.zeros((L, NBp, BT, 2 * lanes))
    pk = jnp.full((L, NBp, BT // 8, lanes), 7.0)   # stale rows of another
    for t in range(n):
        pool, pk = msa.append_kv(pool, pk, tables, jnp.array([t]),
                                 keys[t:t + 1], -keys[t:t + 1],
                                 jnp.array([True]), layer=1, sp=sp)
    got = np.asarray(pk[1][tables[0]]).reshape(-1, lanes)[:12]
    pad = jnp.pad(keys, ((0, 6), (0, 0)))
    want = np.asarray(msa.pool_chunk(pad[None], jnp.arange(96)[None] < n,
                                     sp)[0])
    assert np.array_equal(got, want)
    rows = np.asarray(pool[1][tables[0]]).reshape(-1, 2 * lanes)[:n]
    assert np.array_equal(rows, np.asarray(jnp.concatenate([keys, -keys], 1)))
    assert np.array_equal(np.asarray(pk[0]), np.full((NBp, 4, lanes), 7.0))


def test_the_decode_selection_through_the_table_is_the_reference():
    """Rows at several positions against pooled keys paged through
    shuffled tables: the listed blocks are the reference's, first and
    local included; the counts are read and visible blocks."""
    sp = msa.msa_config(8, 2)
    G, I, d, BT = 2, 3, 16, 32
    b, MB = 4, 5
    NB = MB * BT // 8
    kp = jax.random.normal(jax.random.key(3), (b, NB, G, d))
    u = jax.random.normal(jax.random.key(4), (b, G, I, d))
    positions = jnp.array([5, 40, 77, 150], jnp.int32)
    tables = jnp.array([[3, 9, 1, 12, 7], [2, 4, 6, 8, 10],
                        [11, 13, 5, 14, 15], [16, 17, 18, 19, 20]], jnp.int32)
    pk_pool = jnp.zeros((1, 21, BT // 8, G * d))
    pk_pool = pk_pool.at[0, tables].set(kp.reshape(b, MB, BT // 8, G * d))
    active = jnp.array([True, True, True, False])
    blocks, counts = msa.decode_select(u, pk_pool, tables, positions, active,
                                       layer=0, sp=sp)
    z = ref.sizes(dict(published_keys(MiniMaxM3Config.tiny()),
                       msa_block_size=8, msa_topk=2, head_dim=d))
    read = visible = 0
    for r in range(b):
        want = ref.select(u[r][None], kp[r], positions[r][None], z)
        got = np.zeros((G, NB), bool)
        for g in range(G):
            listed = np.asarray(blocks[r, g])
            got[g, listed[listed >= 0]] = True
        assert (got == np.asarray(want[0])).all(), r
        if active[r]:
            read += got.sum()
            visible += G * (int(positions[r]) // 8 + 1)
    assert list(np.asarray(counts)) == [read, visible, 1]


def _experts(C, F, E, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (24, C)).astype(jnp.bfloat16)
    wg, wu = [(0.3 * jax.random.normal(k, (E, C, F))).astype(jnp.bfloat16)
              for k in ks[1:3]]
    wd = (0.1 * jax.random.normal(ks[3], (E, F, C))).astype(jnp.bfloat16)
    return x, wg, wu, wd


ACT = ("swigluoai", 1.702, 7.0)


def _close_in_bf16(got, want):
    """Two bf16 roundings apart (the kernels round the activation once,
    ragged_dot its products too): within 2 % of the largest output."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()


def test_swigluoai_is_its_definition():
    h = jnp.linspace(-12, 12, 97)
    u = jnp.linspace(10, -10, 97)
    g = np.minimum(np.asarray(h), 7.0)
    want = g / (1 + np.exp(-1.702 * g)) * (np.clip(np.asarray(u), -7, 7) + 1)
    np.testing.assert_allclose(moe_grouped.swigluoai(h, u, 1.702, 7.0), want,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("act", ["silu", ACT], ids=["silu", "swigluoai"])
def test_the_decode_expert_kernel_interpreted_is_its_reference(act):
    x, wg, wu, wd = _experts(128, 256, 4)
    idx = jax.random.randint(jax.random.key(1), (24, 2), 0, 5)   # 4: not here
    w = jax.random.uniform(jax.random.key(2), (24, 2))
    dense = moe_grouped.dense_weights(idx, w, jnp.arange(24) < 20, 4)
    want = moe_grouped.moe_grouped_ffn_reference(x, dense, wg, wu, wd, act)
    set_flags({"FLAGS_pallas_interpret": True})
    got = moe_grouped.moe_grouped_ffn_decode(x, dense, wg, wu, wd, act=act)
    _close_in_bf16(got, want)


@pytest.mark.parametrize("act", ["silu", ACT], ids=["silu", "swigluoai"])
def test_the_prefill_expert_kernel_interpreted_is_ragged_dot(act):
    x, wg, wu, wd = _experts(128, 256, 4, seed=1)
    idx = jax.random.randint(jax.random.key(3), (24, 2), 0, 4)
    w = jax.random.uniform(jax.random.key(4), (24, 2))
    want = moe_grouped.moe_prefill_ragged_dot(x, idx, w, wg, wu, wd, act)
    set_flags({"FLAGS_pallas_interpret": True})
    assert moe_grouped.prefill_path(128, 256) == "kernel"
    got = moe_grouped.moe_grouped_ffn_prefill(x, idx, w, wg, wu, wd, act=act)
    _close_in_bf16(got, want)


def test_the_silu_path_is_the_old_formula_and_swigluoai_another():
    """``act`` left out is SiLU as it was written before the variant
    came (the jnp reference, exactly), and the two activations
    differ."""
    x, wg, wu, wd = _experts(128, 256, 4, seed=2)
    dense = jax.random.uniform(jax.random.key(5), (24, 4))
    f32 = jnp.float32
    h = jnp.einsum("bc,ecf->ebf", x, wg, preferred_element_type=f32)
    u = jnp.einsum("bc,ecf->ebf", x, wu, preferred_element_type=f32)
    a = (jax.nn.silu(h) * u * dense.T[:, :, None]).astype(x.dtype)
    old = jnp.einsum("ebf,efc->bc", a, wd,
                     preferred_element_type=f32).astype(x.dtype)
    silu = moe_grouped.moe_grouped_ffn_reference(x, dense, wg, wu, wd)
    assert np.array_equal(np.asarray(silu, f32), np.asarray(old, f32))
    other = moe_grouped.moe_grouped_ffn_reference(x, dense, wg, wu, wd, ACT)
    assert float(jnp.abs(other.astype(f32) - silu.astype(f32)).max()) > 0.5
    with pytest.raises(ValueError, match="act must be"):
        moe_grouped.moe_grouped_ffn_decode(x, dense, wg, wu, wd, act="gelu")


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips hold 4 of 32 experts each: the eight routed parts,
    with the shared expert counted once, are the layer of a chip that
    holds all 32; and the program's own expert layer on each share is
    the reference's part of it."""
    cfg, _, state = tiny(num_local_experts=32)
    keys = published_keys(cfg)
    z = ref.sizes(keys)
    w = ref._group(state, "model.layers.1.mlp.")
    x = jax.random.normal(jax.random.key(8), (40, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.layer_parts(x, w, z)
        total = jnp.zeros_like(x)
        for j in range(8):
            held = slice(4 * j, 4 * j + 4)
            share = dict(w, **{f"experts.{k}": w[f"experts.{k}"][held]
                               for k in ("w_gate", "w_up", "w_down")})
            part_j, shared_j = ref.layer_parts(x, share,
                                               z._replace(offset=4 * j))
            total = total + part_j
            scfg = MiniMaxM3Config.tiny(num_local_experts=4,
                                        expert_offset=4 * j)
            y, rows = mm.moe_prefill(share, scfg, x)
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(part_j + shared_j),
                                       atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(total + shared),
                                   np.asarray(routed + shared), atol=1e-4,
                                   rtol=1e-5)
        assert float(jnp.abs(routed).max()) > 0.1


@pytest.mark.parametrize("change", [
    dict(rotary_dim=16), dict(swiglu_limit=1e9), dict(msa_topk=3),
    dict(rope_theta=5e6), dict(routed_scaling_factor=1.0)],
    ids=["rope_on_every_lane", "no_clip", "another_topk", "another_base",
         "no_scaling"])
def test_a_control_for_each_piece_fails(change):
    """The reference with one piece of the mathematics changed is far
    from the program: each piece is exercised by the tiny model."""
    cfg, m, state = tiny()
    ids = IDS[:1]
    with jax.default_matmul_precision("highest"):
        lg = functional_call(m, state, ids)
        other = ref.logits_at(state, ids, jnp.arange(160),
                              dict(published_keys(cfg), **change))
    assert float(jnp.abs(lg[0] - other).max()) > 100 * TOL
