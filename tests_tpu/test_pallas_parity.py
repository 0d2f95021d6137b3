"""Pallas-vs-XLA numeric parity on the real TPU (a kernel that raises fails).

Covers every Pallas kernel in paddle_tpu/ops: flash attention (forward,
backward, LSE variant, GQA), the fused decode-step kernel, and the rms_norm
kernel kept for benchmarking. CPU CI never executes these paths
(use_pallas() is False off-TPU); this suite is the hardware leg of the
reference's OpTest discipline (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.ops import flash_attention as fa


def rand(key, *shape, dtype=jnp.bfloat16, scale=0.5):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale).astype(
        dtype)


def assert_close(a, b, rtol=2e-2, atol=2e-2, frac=0.995):
    """bf16-tolerant: allclose on >=99.5% of entries, tight on the mean."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ok = np.isclose(a, b, rtol=rtol, atol=atol).mean()
    assert ok >= frac, f"only {ok:.4f} of entries close"
    assert np.abs(a - b).mean() < atol, np.abs(a - b).mean()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("nkv", [8, 2])   # MHA / GQA
def test_flash_forward_parity(causal, nkv):
    b, s, h, d = 2, 1024, 8, 64
    q = rand(0, b, s, h, d)
    k = rand(1, b, s, nkv, d)
    v = rand(2, b, s, nkv, d)
    pal = fa._flash_attention_pallas(q, k, v, causal, None)
    ref = fa._xla_attention(q, k, v, is_causal=causal)
    assert_close(pal, ref)


def test_flash_backward_parity():
    b, s, h, d = 2, 1024, 4, 64
    q = rand(3, b, s, h, d)
    k = rand(4, b, s, h, d)
    v = rand(5, b, s, h, d)

    def pal_loss(q, k, v):
        return jnp.sum(fa._flash_attention_vjp(q, k, v, True, None)
                       .astype(jnp.float32) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(fa._xla_attention(q, k, v, is_causal=True)
                       .astype(jnp.float32) ** 2)

    gp = jax.jit(jax.grad(pal_loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gr):
        assert_close(a, b_, rtol=5e-2, atol=5e-2)


def _against_float32(q, k, v, w):
    """Largest absolute errors of out, dq, dk, dv of the kernels against
    `_xla_attention` on float32 copies at Precision.HIGHEST; q, k, v, w
    are (b, s, h, d), w the output's cotangent."""
    f32 = lambda x: x.astype(jnp.float32)

    def run(fn, *a):
        out, pull = jax.vjp(fn, *a)
        return (out,) + pull(w.astype(out.dtype))
    got = jax.jit(lambda *a: run(
        lambda q, k, v: fa._flash_call(q, k, v, True, None, None, None, None),
        *a))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: run(
            lambda q, k, v: fa._xla_attention(q, k, v, is_causal=True),
            *a))(f32(q), f32(k), f32(v))
    return [float(jnp.abs(f32(g) - r).max()) for g, r in zip(got, want)]


def _cell_inputs(shape, dtype):
    """`_chip/flash_alone.py`'s operands: head-major draws, handed over
    as (b, s, h, d)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return [jnp.swapaxes((jax.random.normal(k, shape, jnp.float32)
                          * 0.5).astype(dtype), 1, 2) for k in ks]


@pytest.mark.parametrize("dtype,limits", [
    # 1.5 x what the PARENT's kernels read on these same inputs
    # (`_chip/flash_alone.py`, PR 37's chip run): out 0.00466, dq 0.00182,
    # dk 0.00158, dv 0.00786
    (jnp.bfloat16, (0.0070, 0.0027, 0.0024, 0.0118)),
    # float32 in: the parent read 0.00389, 0.00161, 0.00257, 0.00874. A
    # float32 operand is rounded to bf16 INSIDE Mosaic's default-precision
    # product (the probe, PERF.md PR 37), so float32 inputs read like
    # bf16 ones on the chip; that the kernels hand the matrix unit
    # float32 is shown by tests/test_flash_kernels.py
    (jnp.float32, (0.0058, 0.0024, 0.0039, 0.0131)),
])
def test_flash_parity_at_the_train_cells_width(dtype, limits):
    """gpt2-345m.train-b8s1024's attention: b8 x 16 heads x s1024 x d64,
    causal, forward and gradients against the float32 reference."""
    errs = _against_float32(*_cell_inputs((8, 16, 1024, 64), dtype))
    for name, err, limit in zip(("out", "dq", "dk", "dv"), errs, limits):
        assert err <= limit, (name, err, limit)


@pytest.mark.parametrize("s", [1664, 2048])
def test_flash_forward_parity_at_the_offline_cells_widths(s):
    """internlm2-1.8b.offline-2k's prefill: one row, 16 heads on 8 KV
    heads of 128, a bucket whose last blocks are short (1664 = 3 x 512 +
    128 in queries and in keys) and one they divide. The parent's
    kernels read 0.0035 to 0.0045 over the four buckets
    (`_chip/flash_alone.py`, PR 37); the limit is 1.5 x the largest."""
    q = rand(40, 1, s, 16, 128)
    k = rand(41, 1, s, 8, 128)
    v = rand(42, 1, s, 8, 128)
    out, _ = fa._flash_fwd(q, k, v, True, None)
    with jax.default_matmul_precision("highest"):
        ref = fa._xla_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                is_causal=True)
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    assert err <= 0.0068, err


def test_flash_uneven_seq_parity():
    """s=1280 (not a 512-multiple) rides the Pallas path through the
    adaptive block size; fwd and bwd must match the XLA reference
    (moved here from tests/test_api_breadth.py, where it only skipped)."""
    rng = np.random.RandomState(0)
    b, s, h, d = 1, 1280, 2, 128
    q, k, v = (jnp.asarray(rng.standard_normal(
        (b, s, h, d)).astype(np.float32) * 0.3) for _ in range(3))
    o1, g1 = jax.value_and_grad(
        lambda *a: fa._flash_attention_vjp(*a, True, None).sum(),
        argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(
        lambda *a: fa._xla_attention(*a, is_causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert np.allclose(float(o1), float(o2), rtol=2e-3)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-2, atol=5e-3)


def test_flash_lse_parity():
    b, s, h, d = 2, 1024, 4, 64
    q = rand(6, b, s, h, d)
    k = rand(7, b, s, h, d)
    v = rand(8, b, s, h, d)
    out_p, lse_p = fa._flash_fwd(q, k, v, True, None)
    out_r, lse_r = fa._xla_fwd_lse(q, k, v, True, None)
    assert_close(out_p, out_r)
    assert_close(lse_p, lse_r, rtol=1e-2, atol=1e-2)


def test_sdpa_dispatches_pallas_on_tpu():
    """The public API path must actually take the kernel (strict mode would
    raise on kernel failure; this guards the dispatch predicate)."""
    b, s, h, d = 2, 1024, 4, 64
    q = rand(9, b, s, h, d)
    k = rand(10, b, s, h, d)
    v = rand(11, b, s, h, d)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    ref = fa._xla_attention(q, k, v, is_causal=True)
    assert_close(out, ref)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_cross_attention_parity(causal):
    """sq != sk (the UNet cross-attn shape), bottom-right causal."""
    b, h, d = 2, 4, 64
    q = rand(20, b, 1024, h, d)
    k = rand(21, b, 256, h, d)
    v = rand(22, b, 256, h, d)
    pal = fa._flash_call(q, k, v, causal, None, None, None, None)
    ref = fa._xla_attention(q, k, v, is_causal=causal)
    assert_close(pal, ref)


def test_flash_kv_lens_and_segments_parity():
    """Structured masks (padding lengths + packed segments), fwd + bwd,
    including fully-masked rows (out 0, grads 0 — both paths)."""
    b, h, d, s = 2, 4, 64, 1024
    q = rand(23, b, s, h, d)
    k = rand(24, b, s, h, d)
    v = rand(25, b, s, h, d)
    lens = jnp.asarray([700, 1024])
    seg = jnp.asarray(np.repeat(np.arange(8), 128)[None].repeat(b, 0))

    pal = fa._flash_call(q, k, v, True, None, lens, seg, seg)
    ref = fa._xla_attention(q, k, v, is_causal=True, kv_lens=lens,
                            seg_q=seg, seg_k=seg)
    assert_close(pal, ref)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    gp = jax.jit(jax.grad(loss(lambda *a: fa._flash_call(
        *a, True, None, lens, seg, seg)), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(lambda *a: fa._xla_attention(
        *a, is_causal=True, kv_lens=lens, seg_q=seg, seg_k=seg)),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gr):
        assert_close(a, b_, rtol=5e-2, atol=5e-2)


def test_flash_public_api_structured_masks():
    """The public sdpa args dispatch to the kernel in strict mode."""
    from paddle_tpu.nn import functional as F
    b, h, d, s = 2, 4, 64, 1024
    q = rand(26, b, s, h, d)
    k = rand(27, b, s, h, d)
    v = rand(28, b, s, h, d)
    lens = jnp.asarray([512, 1024])
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         kv_lens=lens)
    ref = fa._xla_attention(q, k, v, is_causal=True, kv_lens=lens)
    assert_close(out, ref)


@pytest.mark.parametrize("kind", ["bool", "float"])
def test_flash_dense_mask_parity(kind):
    """Arbitrary dense attn_mask tiles (round 5 — the last mask-surface
    gap): kernel fwd+bwd == XLA reference under a random (b, 1, s, s)
    mask, bool and additive-float forms."""
    from paddle_tpu.ops import flash_attention as fa

    r = np.random.RandomState(0)
    b, s, h, d = 2, 512, 2, 64
    q, k, v = (jnp.asarray(r.standard_normal((b, s, h, d)) * 0.3,
                           jnp.float32) for _ in range(3))
    mb = r.rand(b, 1, s, s) > 0.3
    mb[:, :, :, 0] = True            # no fully-masked rows
    if kind == "bool":
        mask_x = jnp.asarray(mb)
        mask_k = mask_x.astype(jnp.int8)
    else:
        mask_x = jnp.asarray(np.where(mb, r.standard_normal(
            (b, 1, s, s)) * 0.5, -1e30), jnp.float32)
        mask_k = mask_x

    def loss_k(q, k, v):
        return fa._flash_call(q, k, v, False, None, None, None, None,
                              mask=mask_k).astype(jnp.float32).sum()

    def loss_x(q, k, v):
        return fa._xla_attention(q, k, v, attn_mask=mask_x,
                                 is_causal=False).astype(
            jnp.float32).sum()

    ok, gk = jax.value_and_grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    ox, gx = jax.value_and_grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    assert np.allclose(float(ok), float(ox), rtol=2e-3)
    for a, b_ in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-2, atol=5e-3)


def test_flash_dense_mask_block_skipping():
    """A mask whose valid region covers only the first quarter of the
    keys must produce identical results to the unskipped dense form —
    the prefix/suffix block-skipping bounds are exact."""
    from paddle_tpu.ops import flash_attention as fa

    r = np.random.RandomState(1)
    b, s, h, d = 1, 512, 2, 64
    q, k, v = (jnp.asarray(r.standard_normal((b, s, h, d)) * 0.3,
                           jnp.float32) for _ in range(3))
    mask = np.zeros((1, 1, s, s), bool)
    mask[:, :, :, :128] = True       # only k-block 0 valid
    out = fa._flash_call(q, k, v, False, None, None, None, None,
                         mask=jnp.asarray(mask, jnp.int8))
    ref = fa._xla_attention(q, k, v, attn_mask=jnp.asarray(mask),
                            is_causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-3)


def test_flash_dropout_in_kernel():
    """In-kernel attention dropout (round 5 — the last kernel-surface
    gap): deterministic per seed, unbiased vs the no-dropout output, and
    the backward regenerates the forward's mask (finite-difference check
    through the kernel with a pinned seed)."""
    import paddle_tpu
    from paddle_tpu.ops import flash_attention as fa

    r = np.random.RandomState(0)
    b, s, h, d = 2, 512, 4, 64
    q, k, v = (jnp.asarray(r.standard_normal((b, s, h, d)) * 0.3,
                           jnp.float32) for _ in range(3))
    p = 0.3

    def run(seed_int, dropout=p):
        paddle_tpu.seed(seed_int)      # pins the kernel's dropout seed
        return fa._flash_call(q, k, v, True, None, None, None, None,
                              dropout_p=dropout)

    o1 = np.asarray(run(7), np.float32)
    o2 = np.asarray(run(7), np.float32)
    np.testing.assert_array_equal(o1, o2)          # deterministic
    o3 = np.asarray(run(8), np.float32)
    assert np.abs(o1 - o3).max() > 1e-4            # seed matters
    base = np.asarray(run(7, dropout=0.0), np.float32)
    # unbiased: averaging over many seeds approaches the no-drop output
    acc = np.zeros_like(base)
    n_seeds = 24
    for sd in range(n_seeds):
        acc += np.asarray(run(100 + sd), np.float32)
    err = np.abs(acc / n_seeds - base).mean() / (np.abs(base).mean())
    assert err < 0.15, err

    # backward consistency. Pointwise FD on dq is hopeless here: the
    # projected-loss reduction carries ~1e-3 of f32 noise while dq
    # signals are ~1e-4 (measured; the formula itself is verified
    # against autodiff with an explicit mask in the numpy twin). Three
    # checks that ARE decisive:
    proj = jnp.asarray(r.standard_normal((b, s, h, d)), jnp.float32)

    def loss_of(qq, vv, p_, seed_int=7):
        paddle_tpu.seed(seed_int)
        out = fa._flash_call(qq, k, vv, True, None, None, None, None,
                             dropout_p=p_)
        return (out * proj).astype(jnp.float32).sum()

    # (a) p -> 0 limit: the dropout backward must reduce EXACTLY to the
    # no-dropout backward (threshold saturates to keep-all)
    g_p0 = np.asarray(jax.grad(lambda qq: loss_of(qq, v, 0.0))(q))
    g_eps = np.asarray(jax.grad(lambda qq: loss_of(qq, v, 1e-9))(q))
    np.testing.assert_array_equal(g_p0, g_eps)

    # (b) dv finite difference — dv entries are O(1), far above the
    # noise floor; a mask mismatch between the fwd and dkv kernels
    # would break this immediately
    gv = np.asarray(jax.grad(lambda vv: loss_of(q, vv, p))(v))
    for idx in [(0, 3, 1, 5), (1, 100, 2, 17)]:
        fd = (float(loss_of(q, v.at[idx].add(1e-2), p))
              - float(loss_of(q, v.at[idx].add(-1e-2), p))) / 2e-2
        assert abs(fd - gv[idx]) < 0.05 * max(0.2, abs(fd)), (idx, fd,
                                                              gv[idx])

    # (c) gradient unbiasedness: dq averaged over seeds approaches the
    # p=0 gradient (a wrong mask in the dq kernel cannot average out)
    gacc = np.zeros_like(g_p0)
    for sd in range(n_seeds):
        gacc += np.asarray(jax.grad(
            lambda qq: loss_of(qq, v, p, 100 + sd))(q))
    gmean = gacc / n_seeds
    denom = np.abs(g_p0).mean()
    assert np.abs(gmean - g_p0).mean() / denom < 0.25, \
        np.abs(gmean - g_p0).mean() / denom


def test_flash_dropout_shard_draws_the_whole_calls_masks():
    """A partitioned flash call (`fa.partitioned`, multi-chip training)
    hands each shard its first row and head with the seed. The shard must
    then draw exactly the dropout masks the whole call draws for those
    rows and heads, forward and in both backward kernels — otherwise
    every dp shard and every mp head-shard would repeat one mask."""
    b, s, h, d = 4, 512, 8, 64
    q, k, v = (rand(i, b, s, h, d, dtype=jnp.float32, scale=0.3)
               for i in range(3))
    proj = rand(3, b, s, h, d, dtype=jnp.float32, scale=1.0)
    flags = (False, False, False, False, True)
    none = (jnp.zeros((1,), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.float32),
            jnp.zeros((1, 1, 1, 1), jnp.int8))

    def run(rows, heads):
        cut = lambda x: x[rows, :, heads]
        seed = jnp.asarray([1234, rows.start, heads.start, h], jnp.int32)

        def loss(q, k, v):
            out = fa._flash_vjp_entry(q, k, v, *none, seed, flags, True,
                                      None, None, 0.3)
            return (out * cut(proj)).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(cut(q), cut(k), cut(v))
        return (out,) + grads

    whole = run(slice(0, b), slice(0, h))
    rows, heads = slice(2, 4), slice(4, 8)
    for part, full in zip(run(rows, heads), whole):
        np.testing.assert_array_equal(np.asarray(part),
                                      np.asarray(full[rows, :, heads]))
    # and without its place the shard draws other masks (rows 0.., heads
    # 0.. of the whole call): the place is what the masks are keyed on
    seed0 = jnp.asarray([1234, 0, 0, h], jnp.int32)
    cut = lambda x: x[rows, :, heads]
    out0 = fa._flash_vjp_entry(cut(q), cut(k), cut(v), *none, seed0, flags,
                               True, None, None, 0.3)
    assert np.abs(np.asarray(out0)
                  - np.asarray(whole[0][rows, :, heads])).max() > 1e-3


# ---------------------------------------------------------------------------
# fused decode step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nkv,rep", [(4, 1), (2, 2)])
def test_fused_decode_kernel_parity(nkv, rep):
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, b, S, hd, h, ffn = 3, 8, 256, 64, 256, 512
    nh = nkv * rep
    if nkv * hd % 128:
        pytest.skip("dkv not a lane multiple")
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, (nh + 2 * nkv) * hd),
              "wo": f(L, nh * hd, h), "ln2": jnp.ones((L, h), jnp.bfloat16),
              "wg": f(L, h, ffn), "wu": f(L, h, ffn), "wd": f(L, ffn, h)}
    x = f(b, h)
    kv = f(L, b, S, 2 * nkv * hd)
    pos = 130
    cos, sin = rope_cos_sin(S, hd)

    xr, kvr = jax.jit(lambda *a: fd.fused_decode_reference(
        *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5))(
        x, params, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1])
    xp, kvp = jax.jit(lambda x, p, kv: fd._fused_decode_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        eps=1e-5))(x, params, kv)

    assert_close(xp, xr)
    # cache: identical except bf16-ulp noise at the written token
    d = np.abs(np.asarray(kvr, np.float32) - np.asarray(kvp, np.float32))
    touched = sorted(set(np.argwhere(d > 1e-3)[:, 2].tolist()))
    assert touched in ([], [pos]), touched
    assert d.max() < 0.05, d.max()


@pytest.mark.parametrize("int8", [False, True])
def test_fused_decode_qsplit_parity(int8):
    """The 7B-scale kernel shape: qkv streamed in column phases (block 0
    STRADDLES the q|k boundary) + FFN zero-padded to 128-multiple blocks.
    Forced via an explicit decode_block_plan-style dict on a small config
    so the exact code path Llama-2-7B rides is parity-tested on chip."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, b, S, hd, h, ffn = 3, 4, 256, 64, 256, 384
    nh = nkv = 4                       # MHA, like llama2-7b
    dq, dkv = nh * hd, nkv * hd        # 256, 256; dqkv = 768
    blocks = {"q_split": 2, "qblk": 384, "ffn_blocks": 2, "fblk": 256,
              "ffn_pad": 512}
    r = np.random.RandomState(0)
    bf = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "ln2": jnp.ones((L, h), jnp.bfloat16)}
    shapes = {"wqkv": (L, h, dq + 2 * dkv), "wo": (L, dq, h),
              "wg": (L, h, ffn), "wu": (L, h, ffn), "wd": (L, ffn, h)}
    for k, s in shapes.items():
        if int8:
            params[k] = jnp.asarray(r.randint(-127, 128, s), jnp.int8)
            params[f"{k}_s"] = jnp.full((L, 1, s[-1]), 4e-4, jnp.float32)
        else:
            params[k] = bf(*s)
    params = fd._pad_ffn(params, blocks["ffn_pad"])
    x = bf(b, h)
    kv = bf(L, b, S, 2 * dkv)
    pos = 77
    cos, sin = rope_cos_sin(S, hd)

    xr, kvr = jax.jit(lambda *a: fd.fused_decode_reference(
        *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5))(
        x, params, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1])
    xp, kvp = jax.jit(lambda x, p, kv: fd._fused_decode_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        eps=1e-5, blocks=blocks))(x, params, kv)

    assert_close(xp, xr)
    d = np.abs(np.asarray(kvr, np.float32) - np.asarray(kvp, np.float32))
    touched = sorted(set(np.argwhere(d > 1e-3)[:, 2].tolist()))
    assert touched in ([], [pos]), touched
    assert d.max() < 0.05, d.max()


def test_stacked_decoder_generate_on_tpu():
    """StackedLlamaDecoder (the 7B serving engine) == layered generate,
    token for token, with the fused kernel engaged (strict mode)."""
    import paddle_tpu
    from paddle_tpu.inference import generate
    from paddle_tpu.inference.stacked import StackedLlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=256, num_layers=3,
                      num_heads=4, num_kv_heads=2, intermediate_size=512,
                      max_position_embeddings=512)
    m = LlamaForCausalLM(cfg).bfloat16()
    state = m.state_dict(include_buffers=False)
    dec = StackedLlamaDecoder.from_state_dict(cfg, state)
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out_layered = generate(m, prompt, max_new_tokens=20, temperature=0.0)
    out_stacked = dec.generate(prompt, max_new_tokens=20, temperature=0.0)
    assert (np.asarray(out_layered).tolist()
            == np.asarray(out_stacked).tolist())


def test_fused_generate_matches_layered_on_tpu():
    import paddle_tpu
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.inference import generate
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=256, num_layers=3,
                      num_heads=4, num_kv_heads=2, intermediate_size=512,
                      max_position_embeddings=512)
    m = LlamaForCausalLM(cfg).bfloat16()
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out_fused = generate(m, prompt, max_new_tokens=20, temperature=0.0)
    m._generate_jit_cache = {}
    set_flags({"FLAGS_fused_decode": False})
    out_ref = generate(m, prompt, max_new_tokens=20, temperature=0.0)
    set_flags({"FLAGS_fused_decode": True})
    assert np.asarray(out_fused).tolist() == np.asarray(out_ref).tolist()


# ---------------------------------------------------------------------------
# rms_norm bench kernel
# ---------------------------------------------------------------------------

def test_rms_norm_pallas_parity():
    from paddle_tpu.ops import rms_norm as rn
    x = rand(12, 4, 512, 1024, dtype=jnp.bfloat16)
    w = rand(13, 1024, dtype=jnp.bfloat16, scale=1.0)
    pal = rn._rms_norm_pallas(x, w, 1e-5)
    ref = rn._rms_norm_ref(x, w, 1e-5)
    assert_close(pal, ref, rtol=1e-2, atol=1e-2)


def test_fused_decode_int8_generate_on_tpu():
    """Int8 weights inside the fused kernel (fused_multi_transformer_int8
    analog): greedy decode must track the unfused int8 scan decoder."""
    import paddle_tpu
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.inference import generate
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.quantization import quantize_model, quantized_state

    paddle_tpu.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=256, num_layers=3,
                      num_heads=4, num_kv_heads=2, intermediate_size=512,
                      max_position_embeddings=512)
    m = LlamaForCausalLM(cfg).bfloat16()
    quantize_model(m)
    state = quantized_state(m)
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out_fused = generate(m, prompt, max_new_tokens=16, temperature=0.0,
                         state=state)
    m._generate_jit_cache = {}
    set_flags({"FLAGS_fused_decode": False})
    out_ref = generate(m, prompt, max_new_tokens=16, temperature=0.0,
                       state=state)
    set_flags({"FLAGS_fused_decode": True})
    match = (np.asarray(out_fused) == np.asarray(out_ref)).mean()
    assert match >= 0.9, match    # int8 near-ties may flip a token


def test_fused_decode_gpt_arch_on_tpu():
    """arch='gpt' kernel branch (LayerNorm+bias / MHA / no rope / GELU):
    greedy decode must match the layered scan decoder."""
    import paddle_tpu
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.inference import generate
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel

    paddle_tpu.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=3,
                    num_heads=2, max_position_embeddings=512,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    g = GPTPretrainModel(cfg).bfloat16()
    g.eval()
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out_fused = generate(g, prompt, max_new_tokens=16, temperature=0.0)
    g._generate_jit_cache = {}
    set_flags({"FLAGS_fused_decode": False})
    out_ref = generate(g, prompt, max_new_tokens=16, temperature=0.0)
    set_flags({"FLAGS_fused_decode": True})
    match = (np.asarray(out_fused) == np.asarray(out_ref)).mean()
    assert match >= 0.95, match


@pytest.mark.parametrize("b", [1, 2])
def test_fused_decode_moe_kernel_parity(b):
    """arch='moe' kernel: attention + in-kernel router + data-dependent
    expert-weight streaming vs the jnp reference twin."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, S, hd, h, ffn, E, k = 3, 256, 64, 256, 512, 8, 2
    nkv, rep = 2, 2
    nh = nkv * rep
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, (nh + 2 * nkv) * hd),
              "wo": f(L, nh * hd, h), "ln2": jnp.ones((L, h), jnp.bfloat16),
              "gate": f(L, E, h),
              "weg": f(L, E, h, ffn), "weu": f(L, E, h, ffn),
              "wed": f(L, E, ffn, h)}
    x = f(b, h)
    kv = f(L, b, S, 2 * nkv * hd)
    pos = 130
    cos, sin = rope_cos_sin(S, hd)

    xr, kvr = jax.jit(lambda *a: fd.fused_decode_reference(
        *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch="moe",
        top_k=k))(x, params, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1])
    xp, kvp = jax.jit(lambda x, p, kv: fd._fused_decode_moe_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        top_k=k, eps=1e-5))(x, params, kv)

    assert_close(xp, xr)
    d = np.abs(np.asarray(kvr, np.float32) - np.asarray(kvp, np.float32))
    touched = sorted(set(np.argwhere(d > 1e-3)[:, 2].tolist()))
    assert touched in ([], [pos]), touched
    assert d.max() < 0.05, d.max()


def test_fused_decode_moe_shared_experts_parity():
    """DeepSeekMoE shape: shared experts stream as Mosaic-pipelined dense
    SwiGLU blocks next to the routed top-k manual pipeline; k=4 multi-slot
    routing. Kernel vs the jnp reference twin."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, S, hd, h, ffn, E, k = 3, 256, 64, 256, 256, 16, 4
    fs = 2 * ffn                             # 2 shared experts
    nkv, rep, b = 2, 2, 2
    nh = nkv * rep
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, (nh + 2 * nkv) * hd),
              "wo": f(L, nh * hd, h), "ln2": jnp.ones((L, h), jnp.bfloat16),
              "gate": f(L, E, h),
              "weg": f(L, E, h, ffn), "weu": f(L, E, h, ffn),
              "wed": f(L, E, ffn, h),
              "wsg": f(L, h, fs), "wsu": f(L, h, fs), "wsd": f(L, fs, h)}
    x = f(b, h)
    kv = f(L, b, S, 2 * nkv * hd)
    pos = 130
    cos, sin = rope_cos_sin(S, hd)

    xr, kvr = jax.jit(lambda *a: fd.fused_decode_reference(
        *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch="moe",
        top_k=k))(x, params, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1])
    xp, kvp = jax.jit(lambda x, p, kv: fd._fused_decode_moe_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        top_k=k, eps=1e-5))(x, params, kv)

    assert_close(xp, xr)
    d = np.abs(np.asarray(kvr, np.float32) - np.asarray(kvp, np.float32))
    touched = sorted(set(np.argwhere(d > 1e-3)[:, 2].tolist()))
    assert touched in ([], [pos]), touched
    assert d.max() < 0.05, d.max()


def test_fused_decode_moe_generate_on_tpu():
    """End-to-end: Mixtral generate() rides the MoE kernel and matches the
    layered scan decoder greedily."""
    import paddle_tpu
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.inference import generate
    from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    paddle_tpu.seed(0)
    cfg = MixtralConfig(vocab_size=512, hidden_size=256, num_layers=3,
                        num_heads=4, num_kv_heads=2, intermediate_size=512,
                        max_position_embeddings=512, num_experts=8, top_k=2)
    m = MixtralForCausalLM(cfg).bfloat16()
    m.eval()
    # random-init expert probs are near-ties: one bf16-ulp difference
    # between the kernel and the scan path flips an expert and the greedy
    # sequences diverge (both valid). Scale the router weights so routing
    # is DECISIVE — then the two paths must agree token-for-token.
    for layer in m.model.layers:
        layer.moe.gate.proj.weight = layer.moe.gate.proj.weight * 8.0
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out_fused = generate(m, prompt, max_new_tokens=16, temperature=0.0)
    m._generate_jit_cache = {}
    set_flags({"FLAGS_fused_decode": False})
    out_ref = generate(m, prompt, max_new_tokens=16, temperature=0.0)
    set_flags({"FLAGS_fused_decode": True})
    assert np.asarray(out_fused).tolist() == np.asarray(out_ref).tolist()


def test_flash_padded_head_dim_and_kv_parity():
    """Padded dispatch (SD-1.5 shapes): head_dim 40 zero-padded to 64 and
    cross-attn KV 77 padded to 128 under kv_lens must match the XLA path."""
    from paddle_tpu.ops import flash_attention as fa

    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.standard_normal(s) * 0.3, jnp.bfloat16)
    # self-attention, hd=40, s=1024
    q, k, v = f(2, 1024, 8, 40), f(2, 1024, 8, 40), f(2, 1024, 8, 40)
    out = fa.scaled_dot_product_attention(q, k, v)
    ref = fa._xla_attention(q, k, v)
    assert_close(out, ref)
    # cross-attention, hd=40, sk=77 (pads to 128 with kv_lens masking)
    kc, vc = f(2, 77, 8, 40), f(2, 77, 8, 40)
    out = fa.scaled_dot_product_attention(q, kc, vc)
    ref = fa._xla_attention(q, kc, vc)
    assert_close(out, ref)
    # grads for ALL operands flow through the pad/slice (dk/dv exercise
    # the bwd kernels on padded shapes; pad-region grads must vanish)
    def loss(q, kc, vc):
        return jnp.sum(fa.scaled_dot_product_attention(
            q, kc, vc).astype(jnp.float32) ** 2)
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, kc, vc)
    def loss_ref(q, kc, vc):
        return jnp.sum(fa._xla_attention(q, kc, vc).astype(jnp.float32) ** 2)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, kc, vc)
    for a, b in zip(g, g_ref):
        assert_close(a, b, rtol=5e-2, atol=5e-2)
    # segment ids with a padded KV: pad columns carry id -1 (matches no
    # query segment); a regression that pads with 0 would attend to
    # garbage KV rows
    seg_q = jnp.zeros((2, 1024), jnp.int32)
    seg_kc = jnp.zeros((2, 77), jnp.int32)
    out = fa.scaled_dot_product_attention(q, kc, vc, segment_ids=seg_q,
                                          kv_segment_ids=seg_kc)
    ref = fa._xla_attention(q, kc, vc, seg_q=seg_q, seg_k=seg_kc)
    assert_close(out, ref)


def test_flash_sliding_window_parity():
    """Causal sliding window (Mistral-style) in the kernels, fwd + all
    grads, vs the XLA dense-mask path."""
    b, s, h, d = 2, 1024, 4, 64
    q = rand(30, b, s, h, d)
    k = rand(31, b, s, h, d)
    v = rand(32, b, s, h, d)
    for w in (128, 200):     # block-aligned and unaligned windows
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             window_size=w)
        ref = fa._xla_attention(q, k, v, is_causal=True, window=w)
        assert_close(out, ref)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    gp = jax.jit(jax.grad(loss(lambda *a: F.scaled_dot_product_attention(
        *a, is_causal=True, window_size=200)), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(lambda *a: fa._xla_attention(
        *a, is_causal=True, window=200)), argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gr):
        assert_close(a, b_, rtol=5e-2, atol=5e-2)


def test_flash_alibi_parity():
    """ALiBi per-head linear bias inside the online softmax, fwd + grads,
    composed with the sliding window."""
    b, s, h, d = 2, 1024, 4, 64
    q = rand(33, b, s, h, d)
    k = rand(34, b, s, h, d)
    v = rand(35, b, s, h, d)
    slopes = jnp.asarray([2.0 ** (-i) for i in range(1, h + 1)],
                         jnp.float32)
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         alibi_slopes=slopes)
    ref = fa._xla_attention(q, k, v, is_causal=True, alibi_slopes=slopes)
    assert_close(out, ref)
    # composed: window + alibi
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         window_size=256,
                                         alibi_slopes=slopes)
    ref = fa._xla_attention(q, k, v, is_causal=True, window=256,
                            alibi_slopes=slopes)
    assert_close(out, ref)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    gp = jax.jit(jax.grad(loss(lambda *a: F.scaled_dot_product_attention(
        *a, is_causal=True, alibi_slopes=slopes)),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(lambda *a: fa._xla_attention(
        *a, is_causal=True, alibi_slopes=slopes)),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gp, gr):
        assert_close(a, b_, rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# batched-head attention + int8 KV cache (decode-path overhaul PR)
# ---------------------------------------------------------------------------

def test_fused_decode_int8_cache_kernel_parity():
    """int8 KV cache mode on chip: the kernel (quantized RMW append +
    int8 chunk streaming + on-path dequant) vs the int8 reference twin —
    exact int8 cache agreement, close hidden state."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, b, S, hd, h, ffn = 3, 8, 256, 64, 256, 512
    nh = nkv = 4
    dq = dkv = nh * hd
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, 3 * dq), "wo": f(L, dq, h),
              "ln2": jnp.ones((L, h), jnp.bfloat16),
              "wg": f(L, h, ffn), "wu": f(L, h, ffn), "wd": f(L, ffn, h)}
    x = f(b, h)
    # cache magnitudes must match the append distribution (post-RMS-norm
    # qkv products ~O(1)) so the calibrated scales cover the new token
    kvb = jnp.asarray(r.randn(L, b, S, 2 * dkv), jnp.bfloat16)
    kvi, scales = fd.quantize_kv_cache(kvb, nkv)
    pos = 130
    cos, sin = rope_cos_sin(S, hd)

    xr, kvr = jax.jit(lambda x, p, kv, s: fd.fused_decode_reference(
        x, p, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1],
        num_heads=nh, num_kv_heads=nkv, eps=1e-5, kv_scales=s))(
        x, params, kvi, scales)
    xp, kvp = jax.jit(lambda x, p, kv, s: fd._fused_decode_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        eps=1e-5, kv_scales=s))(x, params, kvi, scales)

    assert_close(xp, xr)
    d = np.abs(np.asarray(kvr, np.int32) - np.asarray(kvp, np.int32))
    touched = sorted(set(np.argwhere(d > 1)[:, 2].tolist()))
    assert touched in ([], [pos]), touched   # off-append rows untouched
    assert d.max() <= 1, d.max()             # append rounding ulp at most


def test_fused_decode_int8_cache_long_context():
    """s >= 2048: the regime the int8 cache targets (cache bytes dominate
    the decode roofline). Kernel vs int8 reference at pos near the end of
    a 2048-slot cache."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, b, S, hd, h, ffn = 2, 4, 2048, 64, 256, 512
    nh = nkv = 4
    dq = dkv = nh * hd
    r = np.random.RandomState(1)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, 3 * dq), "wo": f(L, dq, h),
              "ln2": jnp.ones((L, h), jnp.bfloat16),
              "wg": f(L, h, ffn), "wu": f(L, h, ffn), "wd": f(L, ffn, h)}
    x = f(b, h)
    kvb = jnp.asarray(r.randn(L, b, S, 2 * dkv), jnp.bfloat16)
    kvi, scales = fd.quantize_kv_cache(kvb, nkv)
    pos = 2005
    cos, sin = rope_cos_sin(S, hd)

    xr, _ = jax.jit(lambda x, p, kv, s: fd.fused_decode_reference(
        x, p, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1],
        num_heads=nh, num_kv_heads=nkv, eps=1e-5, kv_scales=s))(
        x, params, kvi, scales)
    xp, _ = jax.jit(lambda x, p, kv, s: fd._fused_decode_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        eps=1e-5, kv_scales=s))(x, params, kvi, scales)
    assert_close(xp, xr)


@pytest.mark.parametrize("b", [1, 2])
def test_fused_decode_moe_int8_cache_kernel_parity(b):
    """MoE kernel int8 KV-cache mode on chip (b=1 exercises the
    prefetch-two-ahead expert pipeline at its worst slot count): k-scales
    folded into the block-diagonal q, v-scales on the attention output,
    quantized RMW append — vs the int8 reference twin."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, S, hd, h, ffn, E, k = 3, 256, 64, 256, 512, 8, 2
    nkv, rep = 2, 2
    nh = nkv * rep
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, (nh + 2 * nkv) * hd),
              "wo": f(L, nh * hd, h), "ln2": jnp.ones((L, h), jnp.bfloat16),
              "gate": f(L, E, h),
              "weg": f(L, E, h, ffn), "weu": f(L, E, h, ffn),
              "wed": f(L, E, ffn, h)}
    x = f(b, h)
    kvb = jnp.asarray(r.randn(L, b, S, 2 * nkv * hd), jnp.bfloat16)
    kvi, scales = fd.quantize_kv_cache(kvb, nkv)
    pos = 130
    cos, sin = rope_cos_sin(S, hd)

    xr, kvr = jax.jit(lambda x, p, kv, s: fd.fused_decode_reference(
        x, p, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1],
        num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch="moe", top_k=k,
        kv_scales=s))(x, params, kvi, scales)
    xp, kvp = jax.jit(lambda x, p, kv, s: fd._fused_decode_moe_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        top_k=k, eps=1e-5, kv_scales=s,
        blocks={"cache_wbytes": 1}))(x, params, kvi, scales)

    assert_close(xp, xr)
    d = np.abs(np.asarray(kvr, np.int32) - np.asarray(kvp, np.int32))
    touched = sorted(set(np.argwhere(d > 1)[:, 2].tolist()))
    assert touched in ([], [pos]), touched
    assert d.max() <= 1, d.max()


def test_fused_decode_moe_int8_generate_on_tpu():
    """End-to-end Mixtral generate(cache_dtype=int8) on the MoE kernel
    tracks the bf16-cache kernel run (prefill-calibrated scales)."""
    import paddle_tpu
    from paddle_tpu.inference import generate
    from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    paddle_tpu.seed(0)
    cfg = MixtralConfig(vocab_size=512, hidden_size=256, num_layers=3,
                        num_heads=4, num_kv_heads=2, intermediate_size=512,
                        max_position_embeddings=512, num_experts=8, top_k=2)
    m = MixtralForCausalLM(cfg).bfloat16()
    m.eval()
    for layer in m.model.layers:     # decisive routing (see moe generate
        layer.moe.gate.proj.weight = layer.moe.gate.proj.weight * 8.0
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out16 = generate(m, prompt, max_new_tokens=16, temperature=0.0)
    m._generate_jit_cache = {}
    out8 = generate(m, prompt, max_new_tokens=16, temperature=0.0,
                    cache_dtype=jnp.int8)
    match = (np.asarray(out16) == np.asarray(out8)).mean()
    assert match >= 0.9, match   # int8-cache near-ties may flip a token


def test_fused_decode_moe_prefetch_many_slots_on_tpu():
    """k=4 routing at b=2 (8 expert-FFN steps): the triple-buffered
    prefetch pipeline reuses every VMEM buffer — strict on-chip parity."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin

    L, S, hd, h, ffn, E, k, b = 2, 256, 64, 256, 256, 16, 4, 2
    nkv, rep = 2, 2
    nh = nkv * rep
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, (nh + 2 * nkv) * hd),
              "wo": f(L, nh * hd, h), "ln2": jnp.ones((L, h), jnp.bfloat16),
              "gate": f(L, E, h),
              "weg": f(L, E, h, ffn), "weu": f(L, E, h, ffn),
              "wed": f(L, E, ffn, h)}
    x = f(b, h)
    kv = f(L, b, S, 2 * nkv * hd)
    pos = 77
    cos, sin = rope_cos_sin(S, hd)
    xr, _ = jax.jit(lambda *a: fd.fused_decode_reference(
        *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch="moe",
        top_k=k))(x, params, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1])
    xp, _ = jax.jit(lambda x, p, kv: fd._fused_decode_moe_pallas(
        x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
        top_k=k, eps=1e-5))(x, params, kv)
    assert_close(xp, xr)


def test_stacked_decoder_int8_cache_generate_on_tpu():
    """StackedLlamaDecoder int8-cache greedy decode tracks the bf16-cache
    run (prefill-calibrated scales)."""
    import paddle_tpu
    from paddle_tpu.inference.stacked import StackedLlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=256, num_layers=3,
                      num_heads=4, num_kv_heads=2, intermediate_size=512,
                      max_position_embeddings=512)
    m = LlamaForCausalLM(cfg).bfloat16()
    dec = StackedLlamaDecoder.from_state_dict(
        cfg, m.state_dict(include_buffers=False))
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 9)))
    out16 = dec.generate(prompt, max_new_tokens=20, temperature=0.0)
    out8 = dec.generate(prompt, max_new_tokens=20, temperature=0.0,
                        cache_dtype=jnp.int8)
    match = (np.asarray(out16) == np.asarray(out8)).mean()
    assert match >= 0.9, match   # int8-cache near-ties may flip a token


# ---------------------------------------------------------------------------
# continuous-batching serving engine (paged KV pool on the fused kernel)
# ---------------------------------------------------------------------------

def _serving_llama(L=3):
    import paddle_tpu
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=L,
                      num_heads=4, num_kv_heads=4, intermediate_size=256,
                      max_position_embeddings=512)
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return m


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
def test_serving_paged_kernel_token_exact_on_tpu(cache_dtype):
    """On-chip twin of tests/test_serving.py TestInterpretKernelParity:
    the real paged Pallas kernel (block-table DMA walk, strict mode)
    under the continuous-batching engine — merged-batch tokens must be
    identical to isolated contiguous-kernel generate, bf16 and int8
    pools."""
    from paddle_tpu import serving
    from paddle_tpu.inference import generate

    m = _serving_llama()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(3, 512, (n,)) for n in (7, 21, 33)]
    max_new = [6, 6, 9]
    iso = [np.asarray(generate(m, p[None], max_new_tokens=mn,
                               temperature=0.0, cache_dtype=cache_dtype))
           [0, len(p):] for p, mn in zip(prompts, max_new)]
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=64, cache_dtype=cache_dtype)
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    eng.drain(max_steps=100)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()


def test_serving_prefix_reuse_on_tpu():
    """Prefix-cache hit on the real chip: the second request adopts the
    cached blocks (no re-prefill of the shared prefix) and still matches
    isolated generate token-exact; shared block payloads stay untouched
    (copy-on-write)."""
    from paddle_tpu import serving
    from paddle_tpu.inference import generate

    m = _serving_llama()
    rng = np.random.RandomState(5)
    sys_p = rng.randint(3, 512, (40,))
    pr_a = np.concatenate([sys_p, rng.randint(3, 512, (5,))])
    pr_b = np.concatenate([sys_p, rng.randint(3, 512, (9,))])
    iso = [np.asarray(generate(m, p[None], max_new_tokens=8,
                               temperature=0.0))[0, len(p):]
           for p in (pr_a, pr_b)]
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=128)
    ra = eng.submit(serving.Request(pr_a, max_new_tokens=8))
    eng.drain()
    shared = [e.block_id for e in
              eng.prefix_cache.lookup(pr_b, len(pr_b) // 16)]
    assert len(shared) == 2
    before = np.asarray(eng.kv_pool[:, shared].astype(jnp.float32))
    rb = eng.submit(serving.Request(pr_b, max_new_tokens=8))
    eng.drain()
    after = np.asarray(eng.kv_pool[:, shared].astype(jnp.float32))
    np.testing.assert_array_equal(before, after)
    assert eng.results[ra].tokens.tolist() == iso[0].tolist()
    assert eng.results[rb].tokens.tolist() == iso[1].tolist()
    assert eng.results[rb].prefix_hit_blocks == 2


def test_serving_gpt_paged_on_tpu():
    """GPT arch through the paged kernel on-chip (pre-LN + learned
    position embeddings take the gpt branch of the chunk walk)."""
    import paddle_tpu
    from paddle_tpu import serving
    from paddle_tpu.inference import generate
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel

    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle_tpu.seed(0)
    g = GPTPretrainModel(cfg)
    g.eval()
    rng = np.random.RandomState(12)
    prompts = [rng.randint(3, 256, (n,)) for n in (6, 13)]
    iso = [np.asarray(generate(g, p[None], max_new_tokens=5,
                               temperature=0.0))[0, len(p):]
           for p in prompts]
    eng = serving.ServingEngine(g, max_slots=2, block_tokens=16,
                                max_seq_len=64)
    rids = [eng.submit(serving.Request(p, max_new_tokens=5))
            for p in prompts]
    eng.drain(max_steps=50)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
def test_serving_chunked_prefill_on_tpu(cache_dtype):
    """On-chip twin of tests/test_serving_chunked.py: chunked prefill
    (chunk programs appending block-aligned KV into the pool the real
    paged kernel then walks) must be token-identical to isolated
    generate — bf16 appends per chunk, int8 defers calibration+
    quantization to the last chunk. On TPU the chunk programs alias
    the donated pool (no CPU copy-per-chunk caveat — BENCH_r06)."""
    from paddle_tpu import serving
    from paddle_tpu.inference import generate

    m = _serving_llama()
    rng = np.random.RandomState(13)
    prompts = [rng.randint(3, 512, (n,)) for n in (40, 21, 9)]
    max_new = [6, 6, 8]
    iso = [np.asarray(generate(m, p[None], max_new_tokens=mn,
                               temperature=0.0, cache_dtype=cache_dtype))
           [0, len(p):] for p, mn in zip(prompts, max_new)]
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=64, cache_dtype=cache_dtype,
                                chunk_tokens=16)
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    eng.drain(max_steps=200)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()
    assert eng.stats["prefill_chunks"] >= 3 + 2 + 1
    eng.close()


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
def test_serving_speculative_on_tpu(cache_dtype):
    """On-chip twin of tests/test_serving_spec.py: the real paged
    VERIFY kernel (chunk walk + k-token causal tail, multi-token
    segment RMW appends, strict mode) under the speculative engine —
    committed tokens must be identical to isolated generate, bf16 and
    int8 pools, and the repetitive prompt must actually speculate
    (accepted > 0, tokens > dispatches)."""
    from paddle_tpu import serving
    from paddle_tpu.inference import generate

    m = _serving_llama()
    rng = np.random.RandomState(14)
    motif = rng.randint(3, 512, (8,))
    prompts = [np.tile(motif, 4), rng.randint(3, 512, (21,))]
    max_new = [16, 8]
    iso = [np.asarray(generate(m, p[None], max_new_tokens=mn,
                               temperature=0.0, cache_dtype=cache_dtype))
           [0, len(p):] for p, mn in zip(prompts, max_new)]
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                max_seq_len=64, cache_dtype=cache_dtype,
                                speculate=serving.SpecConfig(k=3))
    rids = [eng.submit(serving.Request(p, max_new_tokens=mn))
            for p, mn in zip(prompts, max_new)]
    eng.drain(max_steps=100)
    for rid, ref in zip(rids, iso):
        assert eng.results[rid].tokens.tolist() == ref.tolist()
    assert eng.stats["spec_accepted"] > 0
    assert eng.stats["decode_tokens"] > eng.stats["steps"]
    eng.close()


def _assert_same_up_to_near_tie(m, prompt, got, ref, tol=0.02):
    """Token-exact, or the FIRST divergence is a bf16 near-tie: the two
    tokens' logits from an fp32 full forward differ by < tol (a few bf16
    ulps at these logit magnitudes). Past a tie the sequences are free."""
    from paddle_tpu.nn.layer import functional_call

    got, ref = list(got), list(ref)
    d = next((i for i in range(len(ref)) if got[i] != ref[i]), None)
    if d is None:
        return
    st32 = {k: (v.astype(jnp.float32)
                if jnp.issubdtype(v.dtype, jnp.floating) else v)
            for k, v in m.trainable_state().items()}
    ids = np.concatenate([prompt, ref[:d]])[None]
    lg = np.asarray(functional_call(m, st32, jnp.asarray(ids)))[0, -1]
    gap = abs(float(lg[got[d]]) - float(lg[ref[d]]))
    assert gap < tol and lg.max() - max(lg[got[d]], lg[ref[d]]) < tol, \
        (d, got[d], ref[d], gap, got, ref)


def test_serving_speculative_draft_on_tpu():
    """Draft-model proposer on-chip: the draft rides its own paged
    pool through the real kernels (round = scanned paged decode steps,
    prefill scatter), target verify through the verify kernel — tokens
    bit-identical to the non-speculative engine, near-total acceptance
    for a same-weights draft, and equal to isolated generate up to a
    bf16 near-tie (on the v5e this prompt hits one at token 2: fp32
    logits 0.5753 vs 0.5720, where the paged and the contiguous kernel
    round apart; every engine variant agrees with the fp32 argmax)."""
    from paddle_tpu import serving
    from paddle_tpu.inference import generate

    m = _serving_llama()
    draft = _serving_llama()
    rng = np.random.RandomState(15)
    prompts = [rng.randint(3, 512, (n,)) for n in (9, 21)]
    iso = [np.asarray(generate(m, p[None], max_new_tokens=10,
                               temperature=0.0))[0, len(p):]
           for p in prompts]

    def served(**kw):
        eng = serving.ServingEngine(m, max_slots=2, block_tokens=16,
                                    max_seq_len=64, **kw)
        rids = [eng.submit(serving.Request(p, max_new_tokens=10))
                for p in prompts]
        eng.drain(max_steps=100)
        out = [eng.results[rid].tokens.tolist() for rid in rids]
        stats = dict(eng.stats)
        eng.close()
        return out, stats

    plain, _ = served()
    spec, stats = served(speculate=serving.SpecConfig(
        k=3, proposer="draft", draft_model=draft))
    assert spec == plain
    assert stats["spec_accepted"] > 0
    for p, got, ref in zip(prompts, spec, iso):
        _assert_same_up_to_near_tie(m, p, got, ref)


# ---------------------------------------------------------------------------
# Xing4's decode kernels at the published widths (PR 27)
# ---------------------------------------------------------------------------

def test_mla_paged_decode_parity_at_published_widths():
    """32 heads over 640-lane pool rows (512 latent + 64 rope + 64 zero),
    blocks of 256: rows of uneven length, on block edges, and idle."""
    from paddle_tpu.ops import mla_decode as md
    L, BT, P, dc, H, MB = 2, 256, 640, 512, 32, 4
    pos = np.asarray([1000, 0, 255, 256, 257, 0, 700, 1023], np.int32)
    b = len(pos)
    tables = np.zeros((b, MB), np.int32)
    nxt = 1
    for r in (0, 2, 3, 4, 6, 7):        # rows 1 and 5 idle against scratch
        n = pos[r] // BT + 1
        tables[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pool = rand(0, L, nxt, BT, P)
    q = rand(1, b, H, P, scale=0.2)
    new = rand(2, b, P)
    kw = dict(layer=1, d_c=dc, scale=0.1447)
    want_o, want_pool = jax.jit(
        lambda *a: md.mla_paged_decode_reference(*a, **kw))(
            q, new, pool, jnp.asarray(tables), jnp.asarray(pos))
    got_o, got_pool = jax.jit(
        lambda *a: md._mla_paged_decode_pallas(*a, **kw))(
            q, new, pool, jnp.asarray(tables), jnp.asarray(pos))
    live = [0, 2, 3, 4, 6, 7]
    assert_close(np.asarray(got_o)[live], np.asarray(want_o)[live],
                 rtol=2e-2, atol=5e-3)
    assert (np.asarray(got_o)[[1, 5]] == 0).all()
    # the appended rows, bit for bit; the idle rows write nothing
    got_pool, want_pool = np.asarray(got_pool), np.asarray(want_pool)
    assert (got_pool == want_pool).all()
    assert (got_pool[:, 0] == np.asarray(pool)[:, 0]).all()


def test_moe_grouped_ffn_parity_at_published_widths():
    """64 experts of 3584 x 1024, 64 rows top-4, a third of the rows
    idle and a block of experts that nobody chose."""
    from paddle_tpu.ops import moe_grouped as mg
    b, E, C, F, k = 64, 64, 3584, 1024, 4
    x = rand(0, b, C, scale=1.0)
    wg, wu = rand(1, E, C, F, scale=0.02), rand(2, E, C, F, scale=0.02)
    wd = rand(3, E, F, C, scale=0.02)
    rng = np.random.default_rng(0)
    idx = np.stack([rng.choice(40, k, replace=False) + 8
                    for _ in range(b)]).astype(np.int32)  # experts 8..47
    w = jnp.asarray(rng.uniform(0.2, 0.8, (b, k)), jnp.float32)
    active = jnp.asarray(np.arange(b) % 3 != 0)
    dense = mg.dense_weights(jnp.asarray(idx), w, active, E)
    want = jax.jit(mg.moe_grouped_ffn_reference)(x, dense, wg, wu, wd)
    got = jax.jit(mg._moe_grouped_ffn_pallas)(x, dense, wg, wu, wd)
    assert_close(got, want, rtol=2e-2, atol=2e-2 * float(
        np.abs(np.asarray(want, np.float32)).max()))
    assert np.abs(np.asarray(got, np.float32)[::3]).max() == 0.0
    assert np.abs(np.asarray(want, np.float32)).max() > 0.1


@pytest.mark.parametrize("R", [1024, 4096, 14336])
def test_moe_prefill_parity_at_published_widths(R, monkeypatch):
    """The grouped prefill kernel at the cell's widths (64 experts of
    3584 x 1024, top-4) over its smallest, its median and its largest
    bucket's routed rows, against ``ragged_dot``: a few experts left
    empty, one a good deal fuller than the rest."""
    from paddle_tpu.ops import moe_grouped as mg
    E, C, F, k = 64, 3584, 1024, 4
    T = R // k
    x = rand(0, T, C, scale=1.0)
    wg, wu = rand(1, E, C, F, scale=0.02), rand(2, E, C, F, scale=0.02)
    wd = rand(3, E, F, C, scale=0.02)
    score = np.array(jax.random.normal(jax.random.PRNGKey(4), (T, E)))
    score[:, [0, 17, 63]] = -1e9        # nobody picks these
    score[:, 5] += 1.0                  # the fullest by far
    idx = jax.lax.top_k(jnp.asarray(score), k)[1].astype(jnp.int32)
    w = jnp.asarray(np.random.default_rng(0).uniform(0.2, 0.8, (T, k)),
                    jnp.float32)
    sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    assert sizes[[0, 17, 63]].sum() == 0 and sizes[5] > 2 * np.median(sizes)
    want = jax.jit(mg.moe_prefill_ragged_dot)(x, idx, w, wg, wu, wd)
    got = jax.jit(lambda *a: mg._moe_prefill_pallas(
        *a, tm=mg._row_tile(R, E)))(x, idx, w, wg, wu, wd)
    # and through the wrapper, which pads the tokens to their bucket
    # with picks of no expert
    monkeypatch.setattr(mg, "prefill_path", lambda hidden, ffn: "kernel")
    padded = jax.jit(lambda *a: mg.moe_grouped_ffn_prefill(*a))(
        x, idx, w, wg, wu, wd)
    assert (np.asarray(padded) == np.asarray(got)).all()
    top = float(np.abs(np.asarray(want, np.float32)).max())
    assert top > 0.1
    assert_close(got, want, rtol=2e-2, atol=2e-2 * top)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 0.05 * top


def test_serving_xing4_prefill_kernel_against_ragged_dot(monkeypatch):
    """A Xing4 of moderate widths served twice on the chip: its wave
    prefills through the grouped kernel, and through ``ragged_dot`` as
    the parent ran them. Token-exact, or parting at a near-tie of the
    float32 forward; the prefill programs hold the kernel, and the
    engine counts its calls and rows."""
    import paddle_tpu
    from paddle_tpu import serving
    from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
    from paddle_tpu.ops import moe_grouped as mg
    cfg = Xing4Config.tiny(
        vocab_size=512, hidden_size=512, intermediate_size=1024,
        moe_intermediate_size=256, n_routed_experts=16,
        num_experts_per_tok=4, num_nextn_predict_layers=0,
        hc_sinkhorn_iters=4, max_position_embeddings=1024)
    paddle_tpu.seed(0)
    m = Xing4ForCausalLM(cfg).bfloat16()
    m.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, 512, (n,)) for n in (40, 200, 333)]

    def served():
        eng = serving.ServingEngine(m, max_slots=4, block_tokens=128,
                                    max_seq_len=512)
        rids = [eng.submit(serving.Request(p, max_new_tokens=8))
                for p in prompts]
        eng.drain(max_steps=200)
        out = [eng.pop_result(r).tokens.tolist() for r in rids]
        text = "".join(low.as_text() for low in
                       eng.lowered_programs("prefill").values())
        stats = dict(eng.stats)
        eng.close()
        return out, text, stats

    assert mg.prefill_path(cfg.hidden_size, cfg.moe_intermediate_size) \
        == "kernel"
    got, text, stats = served()
    assert mg.PREFILL_KERNEL_NAME in text
    layers = cfg.num_layers - cfg.first_k_dense_replace
    assert stats["prefill_moe_calls"] >= layers
    assert stats["prefill_moe_rows"] >= 4 * (128 + 256 + 384) * layers
    monkeypatch.setattr(mg, "prefill_path", lambda hidden, ffn: "ragged_dot")
    ref, text, stats = served()
    assert mg.PREFILL_KERNEL_NAME not in text
    assert stats["prefill_moe_calls"] == stats["prefill_moe_rows"] == 0
    for p, a, b in zip(prompts, got, ref):
        _assert_same_up_to_near_tie(m, p, a, b, tol=0.05)


# ---------------------------------------------------------------------------
# DeepSeek-V2's share at the published widths (PR 32): the same kernels at
# 128 heads and at 40 held experts of 5120 x 1536
# ---------------------------------------------------------------------------

def test_mla_paged_decode_parity_at_128_heads():
    """128 heads over 640-lane pool rows, blocks of 256: rows of uneven
    length, on block edges, and idle."""
    from paddle_tpu.ops import mla_decode as md
    L, BT, P, dc, H, MB = 2, 256, 640, 512, 128, 4
    pos = np.asarray([1000, 0, 255, 256, 257, 0, 700, 1023], np.int32)
    b = len(pos)
    tables = np.zeros((b, MB), np.int32)
    nxt = 1
    for r in (0, 2, 3, 4, 6, 7):        # rows 1 and 5 idle against scratch
        n = pos[r] // BT + 1
        tables[r, :n] = np.arange(nxt, nxt + n)
        nxt += n
    pool = rand(0, L, nxt, BT, P)
    q = rand(1, b, H, P, scale=0.2)
    new = rand(2, b, P)
    kw = dict(layer=1, d_c=dc, scale=192 ** -0.5 * 1.5896)
    want_o, want_pool = jax.jit(
        lambda *a: md.mla_paged_decode_reference(*a, **kw))(
            q, new, pool, jnp.asarray(tables), jnp.asarray(pos))
    got_o, got_pool = jax.jit(
        lambda *a: md._mla_paged_decode_pallas(*a, **kw))(
            q, new, pool, jnp.asarray(tables), jnp.asarray(pos))
    live = [0, 2, 3, 4, 6, 7]
    assert_close(np.asarray(got_o)[live], np.asarray(want_o)[live],
                 rtol=2e-2, atol=5e-3)
    assert (np.asarray(got_o)[[1, 5]] == 0).all()
    # the appended rows, bit for bit; the idle rows write nothing
    got_pool, want_pool = np.asarray(got_pool), np.asarray(want_pool)
    assert (got_pool == want_pool).all()
    assert (got_pool[:, 0] == np.asarray(pool)[:, 0]).all()


def _mla_walk_twin(H, b, active, seed):
    """The decode kernel against its reference at the published widths,
    with ``active`` of ``b`` slots holding rows of ``chat-4k``'s lengths
    (prompts lognormal about 1,024 in 128-3,584 plus part of an output of
    up to 512) at scattered slots, in blocks scattered over the pool, and
    the idle slots at positions the own step has advanced them to."""
    from paddle_tpu.ops import mla_decode as md
    L, BT, P, dc, MB = 2, 256, 640, 512, 16
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.choice(b, active, replace=False))
    pos = rng.integers(1, 40, b).astype(np.int32)
    pos[rows] = (np.clip(rng.lognormal(np.log(1024), 0.8, active), 128,
                         3584) + rng.integers(0, 512, active)).astype(np.int32)
    need = pos[rows] // BT + 1
    NB = 1 + int(need.sum()) + 64
    ids = 1 + rng.permutation(NB - 1)
    tables = np.zeros((b, MB), np.int32)
    k = 0
    for r, n in zip(rows, need):
        tables[r, :n] = ids[k:k + n]
        k += n
    pool = rand(seed, L, NB, BT, P)
    q = rand(seed + 1, b, H, P, scale=0.2)
    new = rand(seed + 2, b, P)
    kw = dict(layer=1, d_c=dc, scale=192 ** -0.5 * 1.5896)
    args = (q, new, pool, jnp.asarray(tables), jnp.asarray(pos))
    want_o, want_pool = jax.jit(
        lambda *a: md.mla_paged_decode_reference(*a, **kw))(*args)
    got_o, got_pool = jax.jit(
        lambda *a: md._mla_paged_decode_pallas(*a, **kw))(*args)
    idle = np.setdiff1d(np.arange(b), rows)
    got_o, want_o = np.asarray(got_o), np.asarray(want_o)
    assert_close(got_o[rows], want_o[rows], rtol=2e-2, atol=5e-3)
    assert (got_o[idle] == 0).all()
    got_pool, want_pool = np.asarray(got_pool), np.asarray(want_pool)
    assert (got_pool == want_pool).all()
    assert (got_pool[:, 0] == np.asarray(pool)[:, 0]).all()


def test_mla_paged_decode_walk_at_128_heads_and_128_slots():
    """DeepSeek-V2's shape: 28 of 128 slots active."""
    _mla_walk_twin(128, 128, 28, seed=11)


def test_mla_paged_decode_walk_at_32_heads_and_64_slots():
    """Xing4.0's shape: 24 of 64 slots active."""
    _mla_walk_twin(32, 64, 24, seed=12)


def _share_picks(T, seed):
    """(T, 6) picks of a 160-wide group-limited router (8 groups, 3
    kept) mapped onto the 40 experts held by chip 0: about a quarter
    fall here, the rest are 40 (no expert); experts 3 and 17 get none."""
    from paddle_tpu.nn.layers.moe import group_limited_topk_routing
    from paddle_tpu.ops import moe_grouped as mg
    logits = np.array(jax.random.normal(jax.random.PRNGKey(seed), (T, 160)))
    logits[:, [3, 17]] = -1e9
    idx, w = group_limited_topk_routing(jnp.asarray(logits), 6, n_group=8,
                                        topk_group=3, scaling=16.0)
    return mg.held_rows(idx, 0, 40), w


def test_moe_grouped_ffn_parity_for_a_share_at_published_widths():
    """40 held experts of 5120 x 1536 under 128 rows top-6 of a router
    160 wide: three quarters of the picks lie on other chips."""
    from paddle_tpu.ops import moe_grouped as mg
    b, E, C, F = 128, 40, 5120, 1536
    x = rand(0, b, C, scale=1.0)
    wg, wu = rand(1, E, C, F, scale=0.02), rand(2, E, C, F, scale=0.02)
    wd = rand(3, E, F, C, scale=0.02)
    idx, w = _share_picks(b, 4)
    here = float((np.asarray(idx) < E).mean())
    assert 0.15 < here < 0.35
    active = jnp.asarray(np.arange(b) % 3 != 0)
    dense = mg.dense_weights(idx, w, active, E)
    want = jax.jit(mg.moe_grouped_ffn_reference)(x, dense, wg, wu, wd)
    got = jax.jit(mg._moe_grouped_ffn_pallas)(x, dense, wg, wu, wd)
    assert_close(got, want, rtol=2e-2, atol=2e-2 * float(
        np.abs(np.asarray(want, np.float32)).max()))
    assert np.abs(np.asarray(got, np.float32)[::3]).max() == 0.0
    assert np.abs(np.asarray(want, np.float32)).max() > 0.1


@pytest.mark.parametrize("T", [256, 1024, 3584])
def test_moe_prefill_parity_for_a_share_at_published_widths(T):
    """The grouped prefill kernel with its weights in slices (two whole
    experts of 5120 x 1536 do not fit VMEM) over the cell's smallest,
    median and largest bucket, against ``ragged_dot``, through the
    wrapper: picks of no expert add nothing."""
    from paddle_tpu.ops import moe_grouped as mg
    E, C, F = 40, 5120, 1536
    assert mg._slice_width(C, F) == 512 and mg.prefill_path(C, F) == "kernel"
    x = rand(0, T, C, scale=1.0)
    wg, wu = rand(1, E, C, F, scale=0.02), rand(2, E, C, F, scale=0.02)
    wd = rand(3, E, F, C, scale=0.02)
    idx, w = _share_picks(T, 5)
    w = jnp.where(idx < E, w, 0.0)
    sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=E + 1)
    assert sizes[[3, 17]].sum() == 0 and sizes[E] > 2 * sizes[:E].sum()
    want = jax.jit(mg.moe_prefill_ragged_dot)(x, idx, w, wg, wu, wd)
    got = jax.jit(mg.moe_grouped_ffn_prefill)(x, idx, w, wg, wu, wd)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    top = float(np.abs(np.asarray(want, np.float32)).max())
    assert top > 0.1
    assert_close(got, want, rtol=2e-2, atol=2e-2 * top)


# ---------------------------------------------------------------------------
# the paged decode kernel's ragged walk at the chat cells' widths (PR 28)
# ---------------------------------------------------------------------------

def _paged_cell(arch, b, L):
    """(shapes of) a step of the paged kernel at a chat cell's widths:
    gpt2-345m (MHA, 16 heads of 64, 8 blocks a slot) or internlm2-1.8b
    (GQA, 16 heads of 128 over 8, 16 blocks a slot); blocks of 128."""
    from paddle_tpu.ops import fused_decode as fd
    if arch == "gpt":
        h, nh, nkv, hd, ffn, MB = 1024, 16, 16, 64, 4096, 8
    else:
        h, nh, nkv, hd, ffn, MB = 2048, 16, 8, 128, 8192, 16
    dq, dkv = nh * hd, nkv * hd
    shapes = {"ln1": (L, h), "ln2": (L, h), "wqkv": (L, h, dq + 2 * dkv),
              "wo": (L, dq, h), "wg": (L, h, ffn), "wd": (L, ffn, h)}
    if arch == "gpt":
        shapes.update(ln1_b=(L, h), ln2_b=(L, h), bqkv=(L, dq + 2 * dkv),
                      bo=(L, h), bg=(L, ffn), bd=(L, h))
    else:
        shapes["wu"] = (L, h, ffn)
    kw = dict(num_heads=nh, num_kv_heads=nkv, arch=arch, eps=1e-5)
    blocks = fd.decode_block_plan(h, dq + 2 * dkv, dq, hd, ffn, 2)
    return shapes, (L, b * MB + 1, 128, 2 * dkv), MB, hd, h, kw, blocks


@pytest.mark.parametrize("K1", [1, 5], ids=["decode", "tail5"])
@pytest.mark.parametrize("arch, b", [("gpt", 32), ("llama", 16)])
def test_paged_decode_ragged_parity_at_cell_widths(arch, b, K1):
    """Rows of every kind in one batch, at the two chat cells' widths and
    slot counts: idle against scratch, 5 tokens, on a block boundary, at
    `max_seq_len` - 1, released with an advanced position, and the rest
    of uneven lengths; the output and the pool against the jnp twin. A
    tail of one token is a decode step, a tail of five the verify step
    of `speculate` k = 4 (the row at `max_seq_len` - 1 then appends four
    tokens past its table: to scratch, their outputs garbage by
    contract)."""
    from paddle_tpu.ops import fused_decode as fd
    from paddle_tpu.ops.rope import rope_cos_sin
    L = 3
    shapes, pool_shape, MB, hd, h, kw, blocks = _paged_cell(arch, b, L)
    params = {k: rand(10 + i, *s, scale=0.01)
              for i, (k, s) in enumerate(sorted(shapes.items()))}
    params["ln1"], params["ln2"] = 1 + params["ln1"], 1 + params["ln2"]
    S = MB * 128
    pos = np.random.RandomState(0).randint(1, S - K1, b).astype(np.int32)
    pos[:5] = [0, 5, 256, S - 1, 11]
    live = np.ones(b, bool)
    live[[0, 4, b - 1, b - 3]] = False          # idle or released rows
    pos[[b - 1, b - 3]] = 0
    tables = np.zeros((b, MB), np.int32)
    for r in np.flatnonzero(live):
        tables[r] = 1 + r * MB + np.arange(MB)
    pool = rand(1, *pool_shape)
    cos, sin = rope_cos_sin(S + K1, hd)
    tail = pos[:, None] + np.arange(K1)                      # (b, K1)
    if K1 == 1:
        x, ref = rand(2, b, h), fd.fused_paged_decode_reference
        cos, sin = cos[pos], sin[pos]
    else:
        x, ref = rand(2, b, K1, h), fd.fused_paged_verify_reference
        cos, sin = cos[tail], sin[tail]
    want_x, want_pool = jax.jit(lambda x, p, pool: ref(
        x, p, pool, tables, pos, cos, sin, **kw))(x, params, pool)
    # the kernel takes the tail token-major flat
    got_x, got_pool = jax.jit(
        lambda x, p, pool: fd._fused_paged_decode_pallas(
            x, p, pool, tables, pos, head_dim=hd, blocks=blocks, **kw))(
                x if K1 == 1 else x.transpose(1, 0, 2).reshape(K1 * b, h),
                params, pool)
    got_x, want_x = np.asarray(got_x, np.float32), \
        np.asarray(want_x, np.float32)
    if K1 > 1:
        inside = (tail < S)[..., None]
        got_x = np.where(inside, got_x.reshape(K1, b, h).transpose(1, 0, 2),
                         0)
        want_x = np.where(inside, want_x, 0)
    assert_close(got_x[live], want_x[live])
    got_pool = np.asarray(got_pool, np.float32)
    want_pool = np.asarray(want_pool, np.float32)
    assert_close(got_pool[:, 1:], want_pool[:, 1:], frac=1.0)
    # no row but the appended ones is written (scratch takes the idle rows'
    # and what a tail appends past its table)
    p0 = np.asarray(pool, np.float32)
    for r in np.flatnonzero(live):
        for q in range(pos[r], min(pos[r] + K1, S)):
            p0[:, tables[r, q // 128], q % 128] = \
                got_pool[:, tables[r, q // 128], q % 128]
    assert (got_pool[:, 1:] == p0[:, 1:]).all()


@pytest.mark.parametrize("arch, b, K1", [
    ("gpt", 64, 1), ("llama", 32, 1), ("gpt", 64, 3), ("llama", 32, 3),
    ("gpt", 48, 5), ("llama", 24, 5)])
def test_paged_decode_compiles_at_twice_the_cells_slots(arch, b, K1):
    """The walk's scratch does not grow with the slots (a ring of block
    buffers for 1 MiB a slot), so 64 slots at the 345 M widths and 32 at
    the 1.8 B widths, which the dense walk's scratch did not fit, compile
    at the cells' 24 layers: a decode step, and a verify step of `k` = 2.
    What grows with slots x K1 is a row's state (`q_s`, `o_s`): a tail of
    five (`k` = 4) compiles at one and a half times the cells' slots, and
    at twice them exceeds the scoped VMEM limit by 5.3 and 3.5 MiB of
    100 (the parent's verify kernel did not fit the cells' own 32 and
    16). A fact for PERF.md §7; no cell uses it."""
    from paddle_tpu.ops import fused_decode as fd
    shapes, pool_shape, MB, hd, h, kw, blocks = _paged_cell(arch, b, 24)
    bf = jnp.bfloat16
    S = jax.ShapeDtypeStruct
    jax.jit(lambda x, p, pool, t, q: fd._fused_paged_decode_pallas(
        x, p, pool, t, q, head_dim=hd, blocks=blocks, **kw)).lower(
            S((K1 * b, h), bf), {k: S(s, bf) for k, s in shapes.items()},
            S(pool_shape, bf), S((b, MB), jnp.int32),
            S((b,), jnp.int32)).compile()


# ---------------------------------------------------------------------------
# the prefill's expanded latent attention (PR 33): mla_flash_prefill
# against the jnp reference at the published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H, s, R", [
    (128, 3584, 0),     # DeepSeek-V2's largest bucket
    (32, 3584, 0),      # Xing4.0's
    (128, 768, 256),    # a cached prefix, and keys left over behind the
    (32, 1280, 256),    # blocks of 512
    (128, 256, 0),      # the smallest bucket: one block, the diagonal
])
def test_mla_flash_prefill_parity_at_published_widths(H, s, R):
    from paddle_tpu.ops import mla_prefill as mp
    dn, dr, dv, S = 128, 64, 128, R + s
    q_n, q_r = rand(0, 1, s, H, dn), rand(1, 1, s, H, dr)
    k_n, v = rand(2, 1, H, S, dn), rand(3, 1, H, S, dv)
    k_r = rand(4, 1, S, dr)
    scale = 192 ** -0.5 * 1.5896
    assert mp.kernel_plan(s, S, R, dn, dr, dv) is not None
    got = jax.jit(lambda *a: mp.mla_flash_prefill(
        *a, scale=scale, start_pos=R))(q_n, q_r, k_n, v, k_r)
    want = jax.jit(lambda *a: mp.reference(
        a[0], a[1], jnp.swapaxes(a[2], 1, 2), jnp.swapaxes(a[3], 1, 2),
        a[4], scale, R))(q_n, q_r, k_n, v, k_r)
    assert got.shape == want.shape == (1, s, H * dv)
    assert_close(got, want, rtol=2e-2, atol=5e-3)
    # the first query sees R + 1 keys and the last all S: both rows are
    # weighted means of v, and differ from each other
    assert np.abs(np.asarray(want, np.float32)[0, 0]
                  - np.asarray(want, np.float32)[0, -1]).max() > 0.05


def test_mla_flash_prefill_grad_is_the_references():
    """``jax.grad`` through the kernel on the chip: the forward is the
    kernel's, the backward the reference's."""
    from paddle_tpu.ops import mla_prefill as mp
    H, s, dn, dr, dv = 4, 256, 128, 64, 128
    ops = (rand(0, 1, s, H, dn), rand(1, 1, s, H, dr), rand(2, 1, H, s, dn),
           rand(3, 1, H, s, dv), rand(4, 1, s, dr))
    loss = lambda f: lambda *a: (f(*a).astype(jnp.float32) ** 2).sum()
    got = jax.jit(jax.grad(loss(lambda *a: mp.mla_flash_prefill(
        *a, scale=0.1, start_pos=0)), argnums=(0, 1, 2, 3, 4)))(*ops)
    want = jax.jit(jax.grad(loss(lambda *a: mp._flash_reference(
        *a, 0.1, 0)), argnums=(0, 1, 2, 3, 4)))(*ops)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert_close(g, w, rtol=5e-2, atol=2e-2)


def test_serving_deepseek_v2_prefill_through_the_flash_kernel(monkeypatch):
    """A DeepSeek-V2 of moderate widths served twice on the chip: its
    wave prefills' attention through ``mla_flash_prefill`` and through
    the ``jnp`` reference. Token-exact, or parting at a near-tie; the
    prefill programs hold one kernel call a layer and no loop over
    score blocks, and the engine counts the calls."""
    import paddle_tpu
    from paddle_tpu import serving
    from paddle_tpu.models import xing4
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    from paddle_tpu.ops import mla_prefill as mp
    cfg = DeepseekV2Config.tiny(
        vocab_size=512, hidden_size=512, intermediate_size=1024,
        moe_intermediate_size=256, num_heads=8, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=256,
        q_lora_rank=256, max_position_embeddings=1024)
    paddle_tpu.seed(0)
    m = DeepseekV2ForCausalLM(cfg).bfloat16()
    m.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, 512, (n,)) for n in (40, 300, 600)]

    def served():
        eng = serving.ServingEngine(m, max_slots=4, block_tokens=128,
                                    max_seq_len=1024)
        rids = [eng.submit(serving.Request(p, max_new_tokens=8))
                for p in prompts]
        eng.drain(max_steps=200)
        out = [eng.pop_result(r).tokens.tolist() for r in rids]
        text = "".join(low.as_text() for low in
                       eng.lowered_programs("prefill").values())
        stats = dict(eng.stats)
        eng.close()
        return out, text, stats

    got, text, stats = served()
    assert mp.KERNEL_NAME in text
    assert stats["prefill_attn_calls"] == cfg.num_layers * len(prompts)
    monkeypatch.setattr(xing4, "_attn_plan", lambda *a: None)
    ref, text, stats = served()
    assert mp.KERNEL_NAME not in text
    assert stats["prefill_attn_calls"] == 0
    for p, a, b in zip(prompts, got, ref):
        _assert_same_up_to_near_tie(m, p, a, b, tol=0.05)
