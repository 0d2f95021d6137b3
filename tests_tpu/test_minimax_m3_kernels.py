"""MiniMax-M3's kernels at the published widths (64 heads of 128 in 4
groups, blocks of 128 and a top 16, pages of 2,048 tokens; 16 held
experts of 6144 x 3072, swigluoai) against their ``jnp`` paths, on the
chip.

Inputs are bf16 as the engine serves them; the ``jnp`` paths upcast and
run float32 matmuls at full precision, the attention kernels feed bf16
operands to the matrix unit with float32 accumulation, so outputs agree
to bf16's rounding of a probability (4e-3 of an output of size 1); the
expert kernels round the activation to bf16 once where ``ragged_dot``
rounds its products too, so they agree to 2 % of the largest output.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_grouped as mg
from paddle_tpu.ops import msa_index as msa
from paddle_tpu.ops import sparse_paged as spg

H, G, I, D, BT = 64, 4, 4, 128, 2048
SP = msa.msa_config()
ACT = ("swigluoai", 1.702, 7.0)


def _normal(seed, shape, dtype=jnp.bfloat16, scale=1.0):
    return (scale * jax.random.normal(jax.random.key(seed), shape,
                                      jnp.float32)).astype(dtype)


def _unit_heads(x):
    """RMS 1 a head, as the model's q and k norms leave them."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
            ).astype(jnp.bfloat16)


def _close_in_bf16(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()


@pytest.mark.parametrize("act", ["silu", ACT], ids=["silu", "swigluoai"])
def test_the_decode_expert_kernel_at_the_published_widths(act):
    x = _normal(1, (8, 6144))
    wg, wu = (_normal(s, (16, 6144, 3072), scale=0.02) for s in (2, 3))
    wd = _normal(4, (16, 3072, 6144), scale=0.02)
    idx = jax.random.randint(jax.random.key(5), (8, 4), 0, 17)  # 16: away
    w = jax.random.uniform(jax.random.key(6), (8, 4))
    dense = mg.dense_weights(idx, w, jnp.arange(8) < 7, 16)
    got = jax.jit(lambda *a: mg.moe_grouped_ffn_decode(*a, act=act))(
        x, dense, wg, wu, wd)
    want = jax.jit(lambda *a: mg.moe_grouped_ffn_reference(*a, act))(
        x, dense, wg, wu, wd)
    _close_in_bf16(got, want)


@pytest.mark.parametrize("act", ["silu", ACT], ids=["silu", "swigluoai"])
def test_the_prefill_expert_kernel_at_the_published_widths(act):
    """A chunk of 2,048 positions, 4 picks each, half of them away: the
    sliced path (slots of 512, tiles of 256) against ragged_dot."""
    T = 2048
    x = _normal(1, (T, 6144))
    wg, wu = (_normal(s, (16, 6144, 3072), scale=0.02) for s in (2, 3))
    wd = _normal(4, (16, 3072, 6144), scale=0.02)
    idx = jax.random.randint(jax.random.key(5), (T, 4), 0, 32)
    w = jax.random.uniform(jax.random.key(6), (T, 4))
    here = idx < 16
    assert mg.prefill_path(6144, 3072) == "kernel"
    got = jax.jit(lambda *a: mg.moe_grouped_ffn_prefill(*a, act=act))(
        x, jnp.where(here, idx, 16), jnp.where(here, w, 0.0), wg, wu, wd)
    want = jax.jit(lambda *a: mg.moe_prefill_ragged_dot(*a, act))(
        x, jnp.minimum(idx, 15), jnp.where(here, w, 0.0), wg, wu, wd)
    _close_in_bf16(got, want)


def test_the_prefill_kernel_under_a_block_selection():
    """A chunk of 2,048 queries at 4,096..6,143 over 8,192 cached rows,
    18 blocks of 128 a query and group: the kernel, which reads the
    selection down the sublanes of a (keys, queries) tile, against the
    ``jnp`` path under the token mask."""
    n, C, S, kv_len = 1, 2048, 8192, 6144
    q = _unit_heads(_normal(3, (n, C, H, D)))
    kv = _normal(4, (n, S, 2 * G * D))
    t = jnp.broadcast_to(jnp.arange(kv_len - C, kv_len), (n, C))
    u = _normal(5, (n, C, G, I, D))
    kp = _normal(6, (n, S // 128, G, D))
    blocks = jax.jit(lambda u, kp: msa.prefill_select(
        u, kp, jnp.int32(kv_len - C), SP))(u, kp)
    assert (np.asarray(blocks).sum(-1) == 18).all()
    got = spg.sparse_prefill_attention(q, kv, blocks, jnp.int32(kv_len),
                                       groups=G, block_size=128)
    mask = spg.prefill_token_mask(blocks, t, S, SP.block_size)
    want = spg.sparse_prefill_attention_reference(q, kv, mask, groups=G)
    assert float(jnp.abs(got - want).max()) < 2e-2


@pytest.mark.parametrize("n", [2000, 9000, 33000])
def test_the_decode_step_selects_and_walks_through_the_pool(n):
    """8 rows near ``n`` tokens: the pooled keys' running max against a
    batch max-pool, the selection through the block table against the
    same scores on dense arrays, and the walk over 18 blocks a group
    against its ``jnp`` path."""
    b, MB = 8, 17
    L, NB = 2, b * MB + 1
    positions = jnp.asarray(n + 97 * np.arange(b) % 1500, jnp.int32)
    tables = jnp.asarray(1 + np.random.default_rng(0).permutation(
        b * MB).reshape(b, MB), jnp.int32)
    active = jnp.arange(b) != 5
    pool = _normal(7, (L, NB, BT, 2 * G * D))
    keys = _unit_heads(_normal(8, (b, MB * BT, G * D)))
    # the pooled keys of every row, by a batch max-pool, in its pages
    pooled = keys.reshape(b, -1, 128, G * D).max(2)
    pk_pool = jnp.zeros((L, NB, BT // 128, G * D), jnp.bfloat16)
    pk_pool = pk_pool.at[1, tables].set(pooled.reshape(b, MB, 16, G * D))
    k_new = _unit_heads(_normal(9, (b, G * D)))
    v_new = _normal(10, (b, G * D))
    pool2, pk2 = jax.jit(lambda *a: msa.append_kv(*a, layer=1, sp=SP))(
        pool, pk_pool, tables, positions, k_new, v_new, active)
    # the new key folded into its block's max (set where it starts one)
    at = np.asarray(positions) // 128
    row = np.asarray(pk2[1][tables]).reshape(b, -1, G * D)[np.arange(b), at]
    first = (np.asarray(positions) % 128 == 0)[:, None]
    old = np.asarray(pooled)[np.arange(b), at]
    want_row = np.where(first, np.asarray(k_new),
                        np.maximum(old, np.asarray(k_new)))
    assert np.array_equal(row[np.asarray(active)],
                          want_row[np.asarray(active)])
    u = _normal(11, (b, G, I, D))
    blocks, counts = jax.jit(lambda *a: msa.decode_select(
        *a, layer=1, sp=SP))(u, pk2, tables, positions, active)
    want = jax.jit(lambda u, kp, t: spg.select_list(
        msa.index_scores(u[:, None], kp, t[:, None], SP)[:, 0], t, SP))(
        u, pk2[1][tables].reshape(b, -1, G, D), positions)
    # the same scores through the table and on dense arrays; XLA may sum
    # them in another order, which can swap a near-tie at the 16th place
    same = (np.asarray(blocks) == np.asarray(want)).all(-1)
    assert same.mean() >= 0.9, same
    assert int(counts[0]) == int(((np.asarray(blocks) >= 0).sum((1, 2))
                                  * np.asarray(active)).sum())
    q = _unit_heads(_normal(12, (b, H, D)))
    o = spg.sparse_paged_decode(q, pool2, tables, positions, blocks, layer=1,
                                sp=SP)
    ref = spg.sparse_paged_decode_reference(q, pool2, tables, positions,
                                            blocks, layer=1, sp=SP)
    assert float(jnp.abs(o - ref).max()) < 2e-2
