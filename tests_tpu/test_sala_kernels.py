"""MiniCPM-SALA's five Mosaic kernels at the published widths (32 heads
of 128, 2 KV heads, pages of 2,048 tokens, requests of 9k and 33k
tokens) against their ``jnp`` paths, on the chip.

Inputs are bf16 as the engine serves them; the ``jnp`` paths upcast and
run float32 matmuls at full precision, the kernels feed bf16 operands to
the matrix unit with float32 accumulation (the sparse ones) or run
float32 at full precision (the lightning ones), so outputs agree to
bf16's rounding of a probability (4e-3 of an output of size 1) and the
float32 states to 1e-3 of their size (100).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import lightning_attention as la
from paddle_tpu.ops import sparse_paged as spg

H, G, D, BT = 32, 2, 128, 2048
SP = spg.SparseConfig()


def _normal(seed, shape, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32
                             ).astype(dtype)


def _unit_heads(x):
    """RMS 1 a head, as the model's q and k norms leave them."""
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
            ).astype(jnp.bfloat16)


def test_lightning_prefill_parity_and_true_length():
    q, k, v = (_unit_heads(_normal(i, (2, 2048, H, D))) for i in range(3))
    S0 = _normal(9, (2, H, D, D), jnp.float32)
    nv = jnp.array([2048, 1100], jnp.int32)
    o, S = la.lightning_prefill(q, k, v, S0, nv)
    ro, rS = la.lightning_prefill_reference(q, k, v, S0, nv)
    assert float(jnp.abs(o[0] - ro[0]).max()) < 2e-2
    assert float(jnp.abs(o[1, :1100] - ro[1, :1100]).max()) < 2e-2
    assert float(jnp.abs(S - rS).max()) < 1e-1 * 1e-1


def test_lightning_decode_parity_in_place():
    q, k, v = (_unit_heads(_normal(i, (16, H, D))) for i in range(3))
    state = _normal(5, (12, 16, H, D, D), jnp.float32)
    active = jnp.arange(16) % 3 != 0
    want_o, want = la.lightning_decode_reference(q, k, v, state, active,
                                                 layer=7)
    o, got = la.lightning_decode(q, k, v, state, active, layer=7)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs((o - want_o)[active]).max()) < 1e-3
    assert np.array_equal(np.asarray(got[7][~active]),
                          np.asarray(state[7][~active]))


@pytest.mark.parametrize("n", [9000, 33000])
def test_sparse_select_and_walk_parity(n):
    b, MB = 4, 17
    nb = 1 + b * MB
    pool = _normal(1, (4, nb, BT, 2 * G * D))
    pool = pool.at[..., :G * D].set(_unit_heads(
        pool[..., :G * D].reshape(4, nb, BT, G, D)).reshape(
            4, nb, BT, G * D))
    tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(b, MB)
    keys = pool[2][tables].reshape(b, MB * BT, 2 * G * D)[..., :G * D]
    ext = jnp.concatenate([keys, jnp.zeros((b, 16, G * D), keys.dtype)], 1)
    ck = spg.compress(ext, SP).astype(jnp.bfloat16).reshape(
        b, MB, BT // 16, G * D)
    ck_pool = jnp.zeros((4, nb, BT // 16, G * D), jnp.bfloat16
                        ).at[2, tables].set(ck)
    q = _unit_heads(_normal(2, (b, H, D)))
    positions = jnp.array([n - 1, n - 700, 8191, 8192], jnp.int32)
    active = jnp.ones(b, bool)
    r = spg._stage1_pallas(q, ck_pool, tables, positions, layer=2, sp=SP,
                           interpret=False)
    rr = spg._stage1_reference(q, ck_pool, tables, positions, layer=2, sp=SP)
    ok = np.isfinite(np.asarray(rr))
    assert (np.isfinite(np.asarray(r)) == ok).all()
    # a group's 16 summed probabilities: bf16 scores against float32
    assert float(np.abs(np.asarray(r)[ok] - np.asarray(rr)[ok]).max()) < 5e-3
    blocks, counts = spg.sparse_select(q, ck_pool, tables, positions, active,
                                       layer=2, sp=SP)
    got = np.asarray(blocks)
    assert ((got >= 0).sum(-1) == np.minimum(
        np.asarray(positions)[:, None] // 64 + 1,
        np.where(np.asarray(positions)[:, None] + 1 <= 8192, 128, 97))).all()
    assert int(counts[2]) == 1          # the row at 8192 tokens is dense
    o = spg.sparse_paged_decode(q, pool, tables, positions, blocks, layer=2,
                                sp=SP)
    want = spg.sparse_paged_decode_reference(q, pool, tables, positions,
                                             blocks, layer=2, sp=SP)
    assert float(jnp.abs(o - want).max()) < 2e-2


def test_sparse_prefill_attention_parity():
    n, C, S, kv_len = 1, 2048, 10240, 6144
    q = _unit_heads(_normal(3, (n, C, H, D)))
    kv = _normal(4, (n, S, 2 * G * D))
    t = jnp.broadcast_to(jnp.arange(kv_len - C, kv_len), (n, C))
    blocks = jax.random.bernoulli(jax.random.key(5), 0.3,
                                  (n, C, G, S // 64))
    blocks = blocks | jax.nn.one_hot(t // 64, S // 64, dtype=bool)[:, :, None]
    mask = spg.prefill_token_mask(blocks, t, S, SP)
    got = spg.sparse_prefill_attention(q, kv, mask, jnp.int32(kv_len),
                                       groups=G)
    want = spg.sparse_prefill_attention_reference(q, kv, mask, groups=G)
    assert float(jnp.abs(got - want).max()) < 2e-2


def _compressed_keys(S, seed):
    """(1, S / 16, G, D): the means of keys normed a head, as the model
    leaves them."""
    k = _unit_heads(_normal(seed, (1, S + 16, G, D)))
    return spg.compress(k.reshape(1, S + 16, G * D), SP).astype(
        jnp.bfloat16).reshape(1, S // 16, G, D)


def _top_k_select_mask(R, t, sp):
    """:func:`select_mask` as it was while ``lax.top_k`` found the
    threshold."""
    NB = R.shape[-1]
    k = min(sp.topk, NB)
    visible, forced = spg._kinds(t, sp, NB)
    Rc = jnp.where((visible & ~forced)[..., None, :], R, -jnp.inf)
    kth = jax.lax.top_k(Rc, k)[0][..., -1:]
    above = Rc > kth
    tied = (Rc == kth) & (Rc > -jnp.inf)
    room = k - above.sum(-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, -1) <= room))
    dense = (t + 1 <= sp.dense_len)[..., None, None]
    return jnp.where(dense, visible[..., None, :],
                     forced[..., None, :] | chosen)


@pytest.mark.parametrize("S", [10240, 16384])
def test_a_wave_selects_what_the_sort_selected(S):
    """The blocks of a whole prefill through one sparse layer's
    selection (no score under ``dense_len``, the threshold searched for
    past it) against stage 1, block maxima and the ``lax.top_k``
    threshold for every query: equal, bit for bit, on the chip too."""
    C, NB = 2048, S // 64
    from paddle_tpu.models import minicpm_sala as sala
    q_all, kc = _unit_heads(_normal(11, (1, S, H, D))), _compressed_keys(S, 12)
    new = jax.jit(lambda q, c0: sala.prefill_select(q, kc, c0, SP, NB))

    @jax.jit
    def old(q, c0):
        t = c0 + jnp.arange(256)[None]
        return _top_k_select_mask(
            spg.block_scores(spg.stage1(q, kc, t, SP), SP, NB), t, SP)

    got = np.concatenate([np.asarray(new(q_all[:, c0:c0 + C], jnp.int32(c0)))
                          for c0 in range(0, S, C)], 1)
    want = np.concatenate([np.asarray(old(q_all[:, c0:c0 + 256],
                                          jnp.int32(c0)))
                           for c0 in range(0, S, 256)], 1)
    assert got.shape == (1, S, G, NB)
    # under dense_len every visible block, past it 1 + 32 + 64
    assert (want.sum(-1)[0, :8192] == (np.arange(8192) // 64 + 1)[:, None]
            ).all()
    assert (want.sum(-1)[0, 8192:] == 97).all()
    assert (got == want).all()


def test_the_threshold_search_is_top_k_on_the_chip():
    """A decode step's candidates (16 rows, 2 groups, 544 blocks) with
    ties and ``-inf``: the same bits."""
    R = np.array(jax.random.uniform(jax.random.key(3), (16, 2, 544)))
    R[:, :, ::5] = np.round(R[:, :, ::5] * 8) / 8
    R[:, :, 1::7] = -np.inf
    R = jnp.asarray(R, jnp.float32)
    want = jax.jit(lambda x: jax.lax.top_k(x, 64)[0][..., -1:])(R)
    got = jax.jit(lambda x: spg.kth_largest(x, 64))(R)
    assert np.array_equal(np.asarray(got).view(np.int32),
                          np.asarray(want).view(np.int32))
