"""MiniCPM-SALA against its plain reference
(``benchmark/harness/reference_minicpm_sala.py``), at tiny widths, on
the CPU: the chunked forward's logits and the blocks every query
selects, the lightning kernels' algebra against the token scan, a padded
row's state, the sparse decode ops against dense attention over the
selected set, and a control for each piece of the mathematics that
FAILS when the piece is left out.

The model is float32 and matmuls run at full precision, so the chunked
program and the token-by-token reference differ by summation order only.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import minicpm_sala as sala
from paddle_tpu.models.minicpm_sala import (LIGHTNING, SPARSE,
                                            MiniCPMSALAConfig,
                                            MiniCPMSALAForCausalLM)
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.ops import lightning_attention as la
from paddle_tpu.ops import sparse_paged as spg

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness import model as hmodel  # noqa: E402
from harness import reference_minicpm_sala as ref  # noqa: E402

# float32 sums in another order: 160 tokens through 4 layers, logits of
# size 2.5; the worst seen is 2e-6
TOL = 2e-5


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def published_keys(cfg: MiniCPMSALAConfig) -> dict:
    """The configuration-file keys the reference reads, from a program
    config (the reference never sees the program's own object)."""
    return dict(
        hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers, mixer_types=cfg.mixer_types,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        lightning_nh=cfg.lightning_nh,
        lightning_head_dim=cfg.lightning_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        scale_emb=cfg.scale_emb, scale_depth=cfg.scale_depth,
        scale_depth_layers=cfg.scale_depth_layers,
        dim_model_base=cfg.dim_model_base, vocab_size=cfg.vocab_size,
        sparse_config=cfg.sparse_config)


def tiny(seed: int = 3, std: float = 0.3, **over):
    cfg = MiniCPMSALAConfig.tiny(**over)
    with paddle_tpu.LazyGuard():
        m = MiniCPMSALAForCausalLM(cfg)
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


def test_forward_and_selection_agree_with_the_reference():
    """160 tokens in five chunks of 32, ``dense_len`` 64: most queries
    select. Logits close, and the SAME blocks at every query of every
    sparse layer."""
    cfg, m, state = tiny()
    with jax.default_matmul_precision("highest"):
        lg = functional_call(m, state, IDS)
        want = reference_logits(state, IDS, cfg)
        _, _, blocks = sala.hidden_forward(
            state, cfg, IDS, sala.init_cache(cfg, 2, 160, jnp.float32),
            return_blocks=True)
        assert float(jnp.abs(lg - want).max()) < TOL
        keys = published_keys(cfg)
        for i, layer in enumerate(cfg.layers_of(SPARSE)):
            sel = np.asarray(ref.selection(state, IDS[0], keys, layer))
            got = np.asarray(blocks[i, 0])
            assert (got == sel).all()
            # the selection bites: past dense_len a query reads 1 + 2 + 2
            # blocks of the up to 20 it could
            assert sel[100:].sum(-1).max() == 5 < sel.shape[-1]
            assert (sel[:64].sum(-1) == (np.arange(64) // 8 + 1)[:, None]).all()


@pytest.mark.parametrize("chunk, s", [(32, 160), (48, 144), (80, 160)],
                         ids=["under_and_past", "under_straddling_past",
                              "straddling_and_past"])
def test_chunks_on_either_side_of_dense_len_select_as_the_reference(chunk, s):
    """``dense_len`` 64: a chunk that ends at or under it computes no
    score, one that straddles it takes the full path and its rows under
    it are overridden, one past it selects: every query of every sparse
    layer reads the reference's blocks."""
    cfg, m, state = tiny(prefill_chunk=chunk)
    ids = IDS[:1, :s]
    with jax.default_matmul_precision("highest"):
        _, _, blocks = sala.hidden_forward(
            state, cfg, ids, sala.init_cache(cfg, 1, s, jnp.float32),
            return_blocks=True)
        keys = published_keys(cfg)
        for i, layer in enumerate(cfg.layers_of(SPARSE)):
            sel = np.asarray(ref.selection(state, ids[0], keys, layer))
            assert (np.asarray(blocks[i, 0]) == sel).all()
            assert sel[100:].sum(-1).max() == 5 < sel.shape[-1]


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


def _scores(case, rows=64, NB=40):
    R = np.array(jax.random.uniform(jax.random.key(7), (rows, 2, NB)))
    if case == "equal_rows":
        R[:] = R[:, :, :1]
    elif case == "ties_at_the_kth_place":
        R = np.round(R * 4) / 4             # five values: ties everywhere
    elif case == "minus_infinity_among_the_candidates":
        R[:, :, ::3] = -np.inf
    elif case == "fewer_than_k_candidates":
        R[:, :, 5:] = -np.inf
    elif case == "zeros_of_both_signs":
        R[:, :, ::2] = 0.0
        R[:, :, 1::4] = -0.0
    return jnp.asarray(R, jnp.float32)


@pytest.mark.parametrize("case", [
    "random", "equal_rows", "ties_at_the_kth_place",
    "minus_infinity_among_the_candidates", "fewer_than_k_candidates",
    "zeros_of_both_signs", "fewer_blocks_than_k"])
def test_the_threshold_search_is_top_k_bit_for_bit(case):
    """``kth_largest`` against ``lax.top_k(...)[0][..., -1:]`` and the
    masks that follow from either, exactly."""
    NB = 6 if case == "fewer_blocks_than_k" else 40
    R = _scores(case, NB=NB)
    bits = lambda x: np.asarray(x).view(np.int32)
    for k in {1, min(8, NB), min(17, NB), NB}:
        want = jax.lax.top_k(R, k)[0][..., -1:]
        assert np.array_equal(bits(spg.kth_largest(R, k)), bits(want)), k
    # queries past dense_len 64 whose window leaves 4 to 37 candidates
    sp = spg.SparseConfig(kernel_size=8, kernel_stride=4, block_size=8,
                          topk=8, window_size=16, init_blocks=1, dense_len=64)
    t = jnp.arange(64, 64 + R.shape[0] * 4, 4)
    got = spg.select_mask(R, t, sp)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_top_k_select_mask(R, t, sp)))
    picked = np.asarray(got).sum(-1)
    if case in ("random", "equal_rows", "ties_at_the_kth_place"):
        want = np.minimum(np.asarray(t) // 8 + 1, 3 + 8)
        assert (picked == np.minimum(want, NB)[:, None]).all()


def test_block_scores_are_the_overlap_definition():
    sp = spg.SparseConfig(kernel_size=8, kernel_stride=4, block_size=8,
                          topk=2, window_size=16, init_blocks=1, dense_len=64)
    z = ref.sizes(dict(published_keys(MiniCPMSALAConfig.tiny()),
                       sparse_config=dataclasses.asdict(sp)))
    J, NB = 23, 12
    r = jax.random.uniform(jax.random.key(0), (3, J))
    want = np.where(ref.overlap(J, NB, z)[None], np.asarray(r)[:, :, None],
                    -np.inf).max(1)
    assert np.array_equal(np.asarray(spg.block_scores(r, sp, NB)), want)


def _scan(q, k, v, S, nvalid):
    """The recurrence a token at a time; a row stops at ``nvalid``."""
    n, s, H, d = q.shape
    lam = jnp.exp(-jnp.asarray(la.slopes(H)))[None, :, None, None]

    def tok(S, x):
        qt, kt, vt, t = x
        S2 = lam * S + kt[..., :, None] * vt[..., None, :]
        o = jnp.einsum("nhd,nhde->nhe", qt / math.sqrt(d), S2,
                       precision="highest")
        return jnp.where((t < nvalid)[:, None, None, None], S2, S), o

    S, o = jax.lax.scan(tok, S, tuple(jnp.moveaxis(a, 1, 0)
                                      for a in (q, k, v)) + (jnp.arange(s),))
    return jnp.moveaxis(o, 0, 1), S


def _qkvs(n=2, s=64, H=4, d=128, seed=0):
    key = jax.random.key(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (n, s, H, d))
               for i in range(3))
    return q, k, v, jax.random.normal(jax.random.fold_in(key, 9),
                                      (n, H, d, d))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernel_interpreted"])
def test_chunked_lightning_prefill_is_the_token_scan(interpret):
    """Chunks of 16 against one token at a time; row 1 has 37 true
    tokens of 64: its outputs up to there and its STATE are those of the
    unpadded row, the pad neither added nor decayed anything."""
    q, k, v, S0 = _qkvs()
    nv = jnp.array([64, 37])
    want_o, want_S = _scan(q, k, v, S0, nv)
    set_flags({"FLAGS_pallas_interpret": interpret})
    o, S = la.lightning_prefill(q, k, v, S0, nv, chunk=16)
    # sums of 64 decayed terms of size 128: relative 1e-6
    assert float(jnp.abs(o[0] - want_o[0]).max()) < 1e-4
    assert float(jnp.abs(o[1, :37] - want_o[1, :37]).max()) < 1e-4
    assert float(jnp.abs(S - want_S).max()) < 1e-4
    _, short_S = _scan(q[1:, :37], k[1:, :37], v[1:, :37], S0[1:],
                             jnp.array([37]))
    assert float(jnp.abs(S[1] - short_S[0]).max()) < 1e-4


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernel_interpreted"])
def test_lightning_decode_continues_a_prefill_state(interpret):
    """48 tokens by prefill then 16 by decode steps equal 64 by scan; an
    idle row's state stays, another layer's state stays."""
    q, k, v, S0 = _qkvs()
    want_o, want_S = _scan(q, k, v, S0, jnp.array([64, 64]))
    set_flags({"FLAGS_pallas_interpret": interpret})
    _, S = la.lightning_prefill(q[:, :48], k[:, :48], v[:, :48], S0,
                                jnp.array([48, 48]), chunk=16)
    state = jnp.stack([S0, S])              # layer 1 is the one that runs
    active = jnp.array([True, False])
    for t in range(48, 64):
        o, state = la.lightning_decode(q[:, t], k[:, t], v[:, t], state,
                                       active, layer=1)
        assert float(jnp.abs(o[0] - want_o[0, t]).max()) < 1e-4
    assert float(jnp.abs(state[1, 0] - want_S[0]).max()) < 1e-4
    assert np.array_equal(np.asarray(state[1, 1]), np.asarray(S[1]))
    assert np.array_equal(np.asarray(state[0]), np.asarray(S0))


def _paged(seed=1, b=3, H=4, G=2, d=128, BT=256, MB=4, L=2):
    key = jax.random.key(seed)
    sp = spg.SparseConfig(kernel_size=32, kernel_stride=16, block_size=64,
                          topk=3, window_size=128, init_blocks=1,
                          dense_len=256)
    nb = 1 + b * MB
    pool = jax.random.normal(key, (L, nb, BT, 2 * G * d))
    tables = jnp.arange(1, nb).reshape(b, MB).astype(jnp.int32)
    # each row's compressed keys are what its keys make them
    keys = pool[1][tables].reshape(b, MB * BT, 2 * G * d)[..., :G * d]
    ext = jnp.concatenate([keys, jnp.zeros((b, 16, G * d))], 1)
    ck = spg.compress(ext, sp).reshape(b, MB, BT // 16, G * d)
    ck_pool = jnp.zeros((L, nb, BT // 16, G * d)).at[1, tables].set(ck)
    q = jax.random.normal(jax.random.fold_in(key, 2), (b, H, d))
    return sp, pool, ck_pool, tables, q


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernel_interpreted"])
def test_sparse_decode_is_attention_over_the_selected_blocks(interpret):
    """Rows at 900 (sparse), 100 (dense) and 511 (sparse) tokens: the
    listed blocks are the reference's mask, and the walk's output is
    dense attention under that mask."""
    sp, pool, ck_pool, tables, q = _paged()
    positions = jnp.array([900, 100, 511], jnp.int32)
    active = jnp.array([True, True, True])
    b, H, d = q.shape
    G = 2
    set_flags({"FLAGS_pallas_interpret": interpret})
    blocks, counts = spg.sparse_select(q, ck_pool, tables, positions, active,
                                       layer=1, sp=sp)
    o = spg.sparse_paged_decode(q, pool, tables, positions, blocks, layer=1,
                                sp=sp)
    set_flags({"FLAGS_pallas_interpret": False})
    z = ref.sizes(dict(published_keys(MiniCPMSALAConfig.tiny()),
                       sparse_config=dataclasses.asdict(sp)))
    kv = pool[1][tables].reshape(b, -1, 2, G, d)
    NB = kv.shape[1] // sp.block_size
    for r in range(b):
        n = int(positions[r]) + 1
        kc = ref.compressed_keys(kv[r, :n, 0], z)
        mask = np.asarray(ref.block_mask(q[r:r + 1], kc, positions[r:r + 1],
                                         z, NB))[0]            # (G, NB)
        for g in range(G):
            listed = sorted(int(x) for x in blocks[r, g] if x >= 0)
            assert listed == list(np.flatnonzero(mask[g]))
        see = np.repeat(mask, sp.block_size, -1) & (np.arange(
            NB * sp.block_size) < n)
        s = jnp.einsum("ghd,ngd->ghn", q[r].reshape(G, H // G, d),
                       kv[r, :, 0], precision="highest") / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(see[:, None], s, -1e30), -1)
        want = jnp.einsum("ghn,ngd->ghd", p, kv[r, :, 1],
                          precision="highest").reshape(H, d)
        assert float(jnp.abs(o[r] - want).max()) < 1e-5
    # rows 0 and 2 read 1 + 2 + 3 blocks a group, row 1 its 2 visible ones
    assert counts.tolist() == [2 * 6 + 2 * 2 + 2 * 6,
                               2 * (15 + 2 + 8), 1]


def test_append_completes_a_compressed_key_from_the_pool():
    sp, pool, ck_pool, tables, _ = _paged()
    G, d = 2, 128
    k = jax.random.normal(jax.random.key(7), (3, G * d))
    v = jax.random.normal(jax.random.key(8), (3, G * d))
    # 287 + 1 = 32 + 16 x 16 tokens: row 16 completes, in page 1; 300 + 1
    # completes nothing; row 2 is idle
    positions = jnp.array([287, 300, 287], jnp.int32)
    active = jnp.array([True, True, False])
    pool2, ck2 = spg.append_kv(pool, ck_pool, tables, positions, k, v,
                               active, layer=1, sp=sp)
    assert np.allclose(pool2[1, tables[0, 1], 287 - 256], jnp.concatenate(
        [k[0], v[0]]))
    keys = pool2[1][tables[0]].reshape(-1, 2 * G * d)[256:288, :G * d]
    assert np.allclose(ck2[1, tables[0, 1], 0], keys.mean(0), atol=1e-6)
    changed = np.asarray((ck2 != ck_pool).any(-1))
    assert changed[1, tables[0, 1], 0] and changed[1, 1:].sum() == 1
    assert np.array_equal(np.asarray(pool2[1, tables[2]]),
                          np.asarray(pool[1, tables[2]]))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernel_interpreted"])
def test_prefill_attention_under_a_block_selection(interpret):
    """The model's call: the (queries, groups, blocks) selection with
    ``block_size`` 64, against the reference under the token mask."""
    sp = spg.SparseConfig()
    key = jax.random.key(3)
    n, C, S, H, G, d = 2, 64, 256, 4, 2, 128
    q = jax.random.normal(key, (n, C, H, d))
    kv = jax.random.normal(jax.random.fold_in(key, 1), (n, S, 2 * G * d))
    kv_len = 192
    t = jnp.broadcast_to(jnp.arange(kv_len - C, kv_len), (n, C))
    blocks = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.6,
                                  (n, C, G, S // 64))
    blocks = blocks | jax.nn.one_hot(t // 64, S // 64, dtype=bool)[:, :, None]
    blocks = blocks.at[..., 0].set(True)        # a forced block
    mask = spg.prefill_token_mask(blocks, t, S, sp.block_size)
    want = spg.sparse_prefill_attention_reference(q, kv, mask, groups=G)
    set_flags({"FLAGS_pallas_interpret": interpret})
    got = spg.sparse_prefill_attention(q, kv, blocks, jnp.int32(kv_len),
                                       groups=G, block_size=sp.block_size)
    assert float(jnp.abs(got - want).max()) < 1e-5


# ---- controls: each FAILS the tolerance that the program passes ----
def _forward_error(monkeypatch, patch):
    cfg, m, state = tiny()
    patch(monkeypatch)
    with jax.default_matmul_precision("highest"):
        lg = functional_call(m, state, IDS)
        return float(jnp.abs(lg - reference_logits(state, IDS, cfg)).max())


def test_control_selection_left_out_fails(monkeypatch):
    """Forced blocks only (first block and window, no top-k)."""
    def patch(mp):
        real = spg.select_mask
        mp.setattr(spg, "select_mask", lambda R, t, sp: real(
            jnp.full_like(R, -jnp.inf), t, sp))
    assert _forward_error(monkeypatch, patch) > 20 * TOL


def test_control_decay_left_out_fails(monkeypatch):
    def patch(mp):
        mp.setattr(la, "slopes", lambda heads: np.zeros(heads, np.float32))
    assert _forward_error(monkeypatch, patch) > 20 * TOL


def test_control_bf16_state_fails(monkeypatch):
    """The lightning state rounded to bf16 after every chunk."""
    def patch(mp):
        real = la.lightning_prefill_reference
        mp.setattr(la, "lightning_prefill",
                   lambda q, k, v, S, nv, chunk=256: real(
                       q, k, v, S, nv, chunk=chunk,
                       state_dtype=jnp.bfloat16))
    assert _forward_error(monkeypatch, patch) > 20 * TOL


def test_config_refuses_what_is_not_implemented():
    with pytest.raises(ValueError, match="mixer_types"):
        MiniCPMSALAConfig.tiny(mixer_types=[SPARSE, LIGHTNING])
    with pytest.raises(ValueError, match="lightning_nkv"):
        MiniCPMSALAConfig.tiny(lightning_nkv=2)
    with pytest.raises(ValueError, match="dense_len"):
        MiniCPMSALAConfig.tiny(sparse_config=dict(
            kernel_size=8, kernel_stride=4, block_size=8, topk=2,
            window_size=64, init_blocks=1, dense_len=64))
    _, m, state = tiny()
    with pytest.raises(ValueError, match="position 0"):
        functional_call(m, state, IDS, start_pos=32)
    published = MiniCPMSALAConfig()
    assert published.mixer_types.count(SPARSE) == 8
    assert published.mixer_types.count(LIGHTNING) == 24
    assert published.sparse.max_blocks == 128
