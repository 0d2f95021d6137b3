"""Numeric tests of nn.functional ops vs NumPy references (SURVEY.md §4 OpTest
pattern: run op against a NumPy reference, check_output)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn import functional as F

rs = np.random.RandomState(0)


def test_linear_matches_numpy():
    x = rs.randn(4, 8).astype(np.float32)
    w = rs.randn(8, 3).astype(np.float32)
    b = rs.randn(3).astype(np.float32)
    out = F.linear(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), x @ w + b, rtol=1e-5)


def test_softmax_cross_entropy_matches_numpy():
    logits = rs.randn(6, 10).astype(np.float32)
    labels = rs.randint(0, 10, (6,))
    out = F.cross_entropy(jnp.asarray(logits), jnp.asarray(labels))
    # numpy reference
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = -np.log(p[np.arange(6), labels]).mean()
    np.testing.assert_allclose(float(out), ref, rtol=1e-5)


def test_cross_entropy_ignore_index():
    logits = rs.randn(4, 5).astype(np.float32)
    labels = np.array([1, 2, -100, 3])
    out = F.cross_entropy(jnp.asarray(logits), jnp.asarray(labels),
                          ignore_index=-100)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    valid = labels != -100
    ref = -np.log(p[np.arange(4), np.where(valid, labels, 0)])[valid].mean()
    np.testing.assert_allclose(float(out), ref, rtol=1e-5)


def test_layer_norm_matches_numpy():
    x = rs.randn(2, 3, 8).astype(np.float32)
    w = rs.randn(8).astype(np.float32)
    b = rs.randn(8).astype(np.float32)
    out = F.layer_norm(jnp.asarray(x), (8,), jnp.asarray(w), jnp.asarray(b))
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mu) / np.sqrt(var + 1e-5) * w + b
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_rms_norm_matches_numpy():
    x = rs.randn(2, 4, 16).astype(np.float32)
    w = rs.randn(16).astype(np.float32)
    out = F.rms_norm(jnp.asarray(x), jnp.asarray(w), 1e-6)
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_conv2d_matches_scipy_style():
    x = rs.randn(1, 2, 5, 5).astype(np.float32)
    w = rs.randn(3, 2, 3, 3).astype(np.float32)
    out = F.conv2d(jnp.asarray(x), jnp.asarray(w), padding=1)
    assert out.shape == (1, 3, 5, 5)
    # check center element against direct computation
    patch = x[0, :, 1:4, 1:4]
    ref = (patch * w[0]).sum()
    np.testing.assert_allclose(float(out[0, 0, 2, 2]), ref, rtol=1e-4)


def test_pools():
    x = jnp.arange(16.0).reshape(1, 1, 4, 4)
    mx = F.max_pool2d(x, 2, 2)
    av = F.avg_pool2d(x, 2, 2)
    np.testing.assert_array_equal(np.asarray(mx)[0, 0], [[5, 7], [13, 15]])
    np.testing.assert_allclose(np.asarray(av)[0, 0], [[2.5, 4.5], [10.5, 12.5]])


def test_attention_matches_reference():
    b, s, h, d = 2, 16, 4, 8
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    # numpy reference
    qn, kn, vn = map(np.asarray, (q, k, v))
    scores = np.einsum("bqhd,bkhd->bhqk", qn, kn) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    scores = np.where(mask[None, None], scores, -1e30)
    e = np.exp(scores - scores.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, vn)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_attention_gqa():
    b, s, hq, hkv, d = 1, 8, 8, 2, 16
    q = jnp.asarray(rs.randn(b, s, hq, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    assert out.shape == (b, s, hq, d)


def test_attention_soft_penalty_mask_routes_to_xla(monkeypatch):
    """A concrete float mask with FINITE entries <= -1e9 that are not
    -inf (a -1e10 soft penalty) must skip the Pallas path — the kernel
    would block-skip it exactly while XLA suppresses it exponentially.
    Force use_pallas() True: the penalty mask must come back via XLA
    (no kernel error), while an eligible bool mask proves the patch
    really drives the kernel path (raises off-TPU)."""
    import paddle_tpu.ops as ops_pkg

    b, s, h, d = 1, 1024, 2, 64       # >= 1024: kernel-eligible seq
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    penalty = jnp.zeros((1, 1, s, s), jnp.float32).at[..., s // 2:].set(
        -1e10)
    ref = F.scaled_dot_product_attention(q, q, q, attn_mask=penalty)
    monkeypatch.setattr(ops_pkg, "use_pallas", lambda: True)
    out = F.scaled_dot_product_attention(q, q, q, attn_mask=penalty)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # -1e10 entries are NOT fully masked on the XLA path: they must
    # still contribute (exp(-1e10 - max) == 0 in fp32 — but rows
    # fully under the penalty keep finite outputs, no NaNs)
    assert np.isfinite(np.asarray(out)).all()
    with pytest.raises(Exception):
        # an eligible bool mask heads INTO the kernel path — which
        # cannot lower off-TPU, proving the routing check (not the
        # patch) is what saved the penalty mask above
        F.scaled_dot_product_attention(
            q, q, q, attn_mask=jnp.ones((1, 1, s, s), bool))


def test_attention_kv_lens_masks_padding():
    """kv_lens=L must equal slicing k/v to length L."""
    b, s, h, d = 2, 16, 2, 8
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    out = F.scaled_dot_product_attention(q, k, v,
                                         kv_lens=jnp.asarray([10, 16]))
    ref0 = F.scaled_dot_product_attention(q[:1], k[:1, :10], v[:1, :10])
    ref1 = F.scaled_dot_product_attention(q[1:], k[1:], v[1:])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref0[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref1[0]),
                               rtol=1e-5, atol=1e-6)


def test_attention_segment_ids_block_diagonal():
    """Packed segments == running each segment separately."""
    b, s, h, d = 1, 12, 2, 8
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    seg = jnp.asarray([[0] * 5 + [1] * 7])
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                         segment_ids=seg)
    ref_a = F.scaled_dot_product_attention(q[:, :5], k[:, :5], v[:, :5],
                                           is_causal=True)
    ref_b = F.scaled_dot_product_attention(q[:, 5:], k[:, 5:], v[:, 5:],
                                           is_causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :5]), np.asarray(ref_a),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[:, 5:]), np.asarray(ref_b),
                               rtol=1e-5, atol=1e-6)


def test_attention_cross_causal_bottom_right():
    """Causal cross-attention aligns bottom-right; fully-masked rows are 0."""
    b, sq, sk, h, d = 1, 6, 4, 2, 8
    q = jnp.asarray(rs.randn(b, sq, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, sk, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, sk, h, d).astype(np.float32))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    # rows 0..sq-sk-1 see nothing -> exactly 0 (flash-attn-2 convention)
    np.testing.assert_array_equal(np.asarray(out[:, :sq - sk]), 0.0)
    # the last row sees everything
    ref = F.scaled_dot_product_attention(q[:, -1:], k, v)
    np.testing.assert_allclose(np.asarray(out[:, -1:]), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_rope():
    from paddle_tpu.ops.rope import fused_rotary_position_embedding, rope_cos_sin
    b, s, h, d = 2, 8, 2, 16
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    q2, k2, _ = fused_rotary_position_embedding(q, k)
    assert q2.shape == q.shape and k2.shape == k.shape
    # position 0 is unrotated
    np.testing.assert_allclose(np.asarray(q2[:, 0]), np.asarray(q[:, 0]),
                               rtol=1e-5)
    # norms preserved (rotation)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(q2), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-4)


def test_dropout_scaling():
    x = jnp.ones((1000,))
    paddle.seed(3)
    y = F.dropout(x, 0.5, training=True)
    kept = float((np.asarray(y) > 0).mean())
    assert 0.4 < kept < 0.6
    np.testing.assert_allclose(np.asarray(y)[np.asarray(y) > 0], 2.0)
    # eval mode: identity
    np.testing.assert_array_equal(np.asarray(F.dropout(x, 0.5, training=False)),
                                  np.asarray(x))


def test_activations():
    x = jnp.asarray([-2.0, -0.5, 0.0, 0.5, 2.0])
    np.testing.assert_allclose(np.asarray(F.relu(x)), [0, 0, 0, 0.5, 2.0])
    np.testing.assert_allclose(np.asarray(F.silu(x)),
                               np.asarray(x) / (1 + np.exp(-np.asarray(x))),
                               rtol=1e-5)


def test_interpolate_nearest():
    x = jnp.arange(4.0).reshape(1, 1, 2, 2)
    y = F.interpolate(x, scale_factor=2, mode="nearest")
    assert y.shape == (1, 1, 4, 4)
    np.testing.assert_array_equal(np.asarray(y[0, 0, :2, :2]),
                                  [[0, 0], [0, 0]])
    np.testing.assert_array_equal(np.asarray(y[0, 0, 2:, 2:]),
                                  [[3, 3], [3, 3]])


def test_conv1d_padding_regression():
    # regression: padding once leaked onto the lifted width axis
    x = jnp.ones((1, 1, 5))
    w = jnp.ones((1, 1, 3))
    y = F.conv1d(x, w, padding=1)
    assert y.shape == (1, 1, 5)
    np.testing.assert_allclose(np.asarray(y)[0, 0], [2, 3, 3, 3, 2])


def test_conv2d_transpose_output_padding():
    x = jnp.ones((1, 2, 4, 4))
    w = jnp.ones((2, 3, 3, 3))
    y0 = F.conv2d_transpose(x, w, stride=2, padding=1)
    y1 = F.conv2d_transpose(x, w, stride=2, padding=1, output_padding=1)
    assert y0.shape == (1, 3, 7, 7)
    assert y1.shape == (1, 3, 8, 8)


def test_dropout_downscale_in_infer():
    x = jnp.ones((8,))
    y = F.dropout(x, 0.25, training=False, mode="downscale_in_infer")
    np.testing.assert_allclose(np.asarray(y), 0.75)


def test_transformer_encoder_independent_layers():
    from paddle_tpu import nn
    enc = nn.TransformerEncoder(nn.TransformerEncoderLayer(16, 2, 32), 3)
    names = [n for n, _ in enc.named_parameters()]
    assert len(names) == len(set(names))
    l0 = enc.layers[0]
    l1 = enc.layers[1]
    assert l0 is not l1
    l1.linear1._parameters["weight"].value = jnp.zeros_like(l1.linear1.weight)
    assert float(jnp.abs(l0.linear1.weight).sum()) > 0


def test_dataloader_shuffles_each_epoch_and_propagates_errors():
    import paddle_tpu.io as io
    ds = io.TensorDataset([np.arange(32)])
    dl = io.DataLoader(ds, batch_size=32, shuffle=True)
    e1 = np.concatenate([b[0] for b in dl])
    e2 = np.concatenate([b[0] for b in dl])
    assert not np.array_equal(e1, e2)

    class Bad(io.Dataset):
        def __len__(self):
            return 4
        def __getitem__(self, i):
            if i == 2:
                raise ValueError("corrupt record")
            return np.zeros(2)

    dl2 = io.DataLoader(Bad(), batch_size=1, num_workers=2)
    with pytest.raises(ValueError, match="corrupt record"):
        list(dl2)


def test_initializer_conv_fans():
    from paddle_tpu.nn.initializer import _fan_in_out
    assert _fan_in_out((64, 3, 3, 3)) == (27, 576)
    assert _fan_in_out((8, 16)) == (8, 16)


def test_flash_dropout_under_jit_without_rng_raises():
    """In-kernel attention dropout traced with no bound 'dropout' rng
    stream must RAISE (the seed would bake into the executable as a
    constant — one dropout mask reused every call), not UserWarning."""
    from paddle_tpu.ops import flash_attention as fa

    q = jnp.zeros((1, 128, 2, 64), jnp.float32)

    def run(q):
        return fa._flash_call(q, q, q, is_causal=True, scale=None,
                              kv_lens=None, seg_q=None, seg_k=None,
                              dropout_p=0.5)

    with pytest.raises(RuntimeError, match="dropout"):
        jax.jit(run)(q)
    # with a bound stream the seed draw itself is legal (tracing may
    # still proceed into the kernels, which need a TPU — only assert the
    # rng gate here)
    from paddle_tpu.core.rng import rng_guard
    try:
        with rng_guard(dropout=jax.random.PRNGKey(0)):
            jax.jit(run)(q)
    except RuntimeError as e:
        assert "dropout" not in str(e)
    except Exception:
        pass    # CPU cannot lower the Pallas kernels; the gate passed


def test_flash_partition_specs():
    """shard_map specs of a partitioned flash call: the batch over the
    data axes and the heads over the head axis, each only where it
    divides; dummies and the seed replicated."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.flash_attention import _partition_specs

    shape = {"dp": 2, "sharding": 2, "mp": 2, "pp": 1}
    none = (False,) * 5
    ins, out, B, H = _partition_specs(
        shape, ("dp", "sharding"), "mp", (8, 1024, 16, 64), 16, none,
        (1, 1, 1, 1))
    assert (B, H) == (("dp", "sharding"), "mp")
    assert out == ins[0] == ins[2] == P(("dp", "sharding"), None, "mp", None)
    assert ins[3:] == (P(),) * 6
    # kv_lens, segment ids, ALiBi slopes and a (1, h, sq, sk) mask follow
    ins, _, _, _ = _partition_specs(
        shape, ("dp", "sharding"), "mp", (8, 1024, 16, 64), 16,
        (True, True, True, True, True), (1, 16, 1024, 1024))
    assert ins[3:] == (P(B), P(B, None), P(B, None), P("mp"),
                       P(None, "mp", None, None), P())
    # batch 6 is not a multiple of 4, 2 kv heads not of mp 4: replicate
    _, out, B, H = _partition_specs(
        {"dp": 2, "sharding": 2, "mp": 4}, ("dp", "sharding"), "mp",
        (6, 1024, 16, 64), 2, none, (1, 1, 1, 1))
    assert (B, H, out) == (None, None, P(None, None, None, None))
    # an axis of size one is not named
    _, _, B, H = _partition_specs(
        {"dp": 4, "sharding": 1, "mp": 1}, ("dp", "sharding"), "mp",
        (8, 1024, 16, 64), 16, none, (1, 1, 1, 1))
    assert (B, H) == (("dp",), None)


def test_flash_partitioned_scope(monkeypatch):
    """Only the owner of a mesh partitions a flash call, and only inside
    its `partitioned` scope: a mesh the process merely holds (fleet.init)
    changes nothing. Inside, each shard learns its first row and head, so
    its dropout masks are those of the unpartitioned call. The kernels
    need a TPU; a stand-in shows what each shard was handed."""
    from paddle_tpu.core.rng import rng_guard
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.parallel.strategy import DistributedStrategy
    from paddle_tpu.parallel.topology import (
        HybridCommunicateGroup, set_hybrid_communicate_group)

    seen = []

    def stand_in(q, k, v, lens, sq, sk, alibi, mask, seed, *static):
        seen.append(q.shape)
        return (jnp.zeros_like(q) + seed[1] * 100 + seed[2]
                + seed[3] * 10000).astype(q.dtype)

    monkeypatch.setattr(fa, "_flash_entry_jit", stand_in)
    q = jnp.zeros((8, 128, 4, 64), jnp.float32)

    def call(q):
        with rng_guard(dropout=jax.random.PRNGKey(0)):
            return fa._flash_call(q, q, q, True, None, None, None, None,
                                  dropout_p=0.5)

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "sharding_degree": 2,
                               "mp_degree": 2}
    hcg = HybridCommunicateGroup(strategy=strategy)
    mesh = hcg.mesh
    set_hybrid_communicate_group(hcg)
    try:
        bare = jax.jit(call)(q)
    finally:
        set_hybrid_communicate_group(None)
    assert seen.pop() == q.shape          # whole operands, no shard_map
    assert np.unique(np.asarray(bare)).tolist() == [40000.0]

    def owner(q):
        with fa.partitioned(mesh, ("dp", "sharding"), "mp"):
            return call(q)

    out = np.asarray(jax.jit(owner)(q))
    assert seen.pop() == (2, 128, 2, 64)  # 8 rows / 4, 4 heads / 2
    assert fa._partition.spec is None     # closed again
    want = (np.arange(8)[:, None, None, None] // 2 * 2 * 100
            + np.arange(4)[None, None, :, None] // 2 * 2 + 40000)
    np.testing.assert_array_equal(out, np.broadcast_to(want, out.shape))
