"""API breadth: tensor math/manipulation extras, linalg, fft, new layers.

Oracles: numpy/scipy semantics via jnp, and torch (CPU) for CTC loss —
mirroring the reference's OpTest-vs-numpy pattern (SURVEY.md §4)."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.tensor as pt
from paddle_tpu import fft as pfft
from paddle_tpu import linalg as pl


R = np.random.RandomState(7)


def test_math_elementwise_sample():
    x = R.standard_normal((3, 4)).astype(np.float32)
    y = np.abs(R.standard_normal((3, 4))).astype(np.float32) + 0.5
    np.testing.assert_allclose(pt.log1p(jnp.asarray(y)), np.log1p(y), rtol=1e-6)
    np.testing.assert_allclose(pt.atan2(jnp.asarray(x), jnp.asarray(y)),
                               np.arctan2(x, y), rtol=1e-6)
    np.testing.assert_allclose(pt.hypot(jnp.asarray(x), jnp.asarray(y)),
                               np.hypot(x, y), rtol=1e-6)
    np.testing.assert_allclose(pt.copysign(jnp.asarray(y), jnp.asarray(x)),
                               np.copysign(y, x), rtol=1e-6)
    np.testing.assert_allclose(pt.frac(jnp.asarray(x)), x - np.trunc(x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        pt.lerp(jnp.asarray(x), jnp.asarray(y), 0.3), x + 0.3 * (y - x),
        rtol=1e-6)


def test_math_reductions_and_cumulative():
    x = R.standard_normal((4, 5)).astype(np.float32)
    np.testing.assert_allclose(pt.logsumexp(jnp.asarray(x), axis=1),
                               np.log(np.sum(np.exp(x), axis=1)), rtol=1e-5)
    np.testing.assert_allclose(pt.median(jnp.asarray(x)), np.median(x),
                               rtol=1e-6)
    np.testing.assert_allclose(pt.cumprod(jnp.asarray(x), dim=1),
                               np.cumprod(x, axis=1), rtol=1e-5)
    vals, idx = pt.cummax(jnp.asarray(x), axis=1)
    np.testing.assert_allclose(vals, np.maximum.accumulate(x, axis=1),
                               rtol=1e-6)
    assert np.all(np.take_along_axis(x, np.asarray(idx), axis=1) ==
                  np.asarray(vals))
    vals, _ = pt.cummin(jnp.asarray(x), axis=1)
    np.testing.assert_allclose(vals, np.minimum.accumulate(x, axis=1),
                               rtol=1e-6)
    k_vals, k_idx = pt.kthvalue(jnp.asarray(x), 2, axis=1)
    np.testing.assert_allclose(k_vals, np.sort(x, axis=1)[:, 1], rtol=1e-6)


def test_manipulation_sample():
    x = R.standard_normal((2, 6)).astype(np.float32)
    out = pt.unflatten(jnp.asarray(x), 1, (2, 3))
    assert out.shape == (2, 2, 3)
    parts = pt.unbind(jnp.asarray(x), axis=0)
    assert len(parts) == 2 and parts[0].shape == (6,)
    np.testing.assert_allclose(
        pt.masked_fill(jnp.asarray(x), jnp.asarray(x) > 0, -1.0),
        np.where(x > 0, -1.0, x))
    np.testing.assert_allclose(pt.rot90(jnp.asarray(x)), np.rot90(x))
    idx = jnp.asarray([0, 1])
    np.testing.assert_allclose(
        pt.index_add(jnp.asarray(x), idx, 0, jnp.ones((2, 6))), x + 1.0)
    s = pt.put_along_axis(jnp.asarray(x), jnp.asarray([[2], [3]]),
                          jnp.asarray([[9.0], [8.0]]), 1)
    assert s[0, 2] == 9.0 and s[1, 3] == 8.0
    np.testing.assert_allclose(
        pt.diag_embed(jnp.asarray(np.float32([1, 2, 3]))),
        np.diag(np.float32([1, 2, 3])))
    g = pt.gather_nd(jnp.asarray(x), jnp.asarray([[0, 1], [1, 2]]))
    np.testing.assert_allclose(g, x[[0, 1], [1, 2]])


def test_searchsorted_histogram_bincount():
    seq = jnp.asarray(np.float32([1, 3, 5, 7]))
    v = jnp.asarray(np.float32([0, 4, 8]))
    np.testing.assert_array_equal(pt.searchsorted(seq, v), [0, 2, 4])
    h = pt.histogram(jnp.asarray(np.float32([1, 2, 1])), bins=4, min=0, max=3)
    assert int(h.sum()) == 3
    np.testing.assert_array_equal(pt.bincount(jnp.asarray([0, 1, 1, 3])),
                                  [1, 2, 0, 1])


def test_linalg_sample():
    a = R.standard_normal((4, 4)).astype(np.float32)
    spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    L = pl.cholesky(jnp.asarray(spd))
    np.testing.assert_allclose(L @ L.T, spd, rtol=1e-4, atol=1e-4)
    q, r = pl.qr(jnp.asarray(a))
    np.testing.assert_allclose(q @ r, a, rtol=1e-4, atol=1e-4)
    u, s, vt = pl.svd(jnp.asarray(a))
    np.testing.assert_allclose((u * s) @ vt, a, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pl.inv(jnp.asarray(spd)) @ spd,
                               np.eye(4), rtol=1e-3, atol=1e-4)
    sign, logdet = pl.slogdet(jnp.asarray(spd))
    np.testing.assert_allclose(float(sign) * np.exp(float(logdet)),
                               np.linalg.det(spd), rtol=1e-3)
    b = R.standard_normal((4,)).astype(np.float32)
    xs = pl.solve(jnp.asarray(spd), jnp.asarray(b))
    np.testing.assert_allclose(spd @ np.asarray(xs), b, rtol=1e-3, atol=1e-4)
    lu_mat, piv = pl.lu(jnp.asarray(a))
    P, L2, U = pl.lu_unpack(lu_mat, piv)
    np.testing.assert_allclose(np.asarray(P @ L2 @ U), a, rtol=1e-4,
                               atol=1e-4)


def test_fft_roundtrip():
    x = R.standard_normal((8,)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pfft.ifft(pfft.fft(jnp.asarray(x)))).real,
                               x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(pfft.irfft(pfft.rfft(jnp.asarray(x)), n=8)), x,
        rtol=1e-5, atol=1e-5)
    x2 = R.standard_normal((4, 4)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(pfft.ifft2(pfft.fft2(jnp.asarray(x2)))).real, x2,
        rtol=1e-5, atol=1e-5)


def test_new_activations():
    x = jnp.asarray(R.standard_normal((3, 4)).astype(np.float32))
    for layer, fn in [
        (nn.SELU(), F.selu), (nn.CELU(), F.celu),
        (nn.Softshrink(), F.softshrink), (nn.Hardshrink(), F.hardshrink),
        (nn.Hardtanh(), F.hardtanh), (nn.LogSigmoid(), F.log_sigmoid),
        (nn.Tanhshrink(), F.tanhshrink), (nn.Softsign(), F.softsign),
        (nn.ThresholdedReLU(), F.thresholded_relu), (nn.Swish(), F.silu),
    ]:
        np.testing.assert_allclose(layer(x), fn(x), rtol=1e-6)
    np.testing.assert_allclose(nn.Maxout(2)(jnp.asarray(
        R.standard_normal((2, 4, 3, 3)).astype(np.float32))).shape,
        (2, 2, 3, 3))
    prelu = nn.PReLU(num_parameters=4)
    y = prelu(jnp.asarray(R.standard_normal((2, 4)).astype(np.float32)))
    assert y.shape == (2, 4)


@pytest.mark.slow  # tier-1 budget (PR 3 offset): sibling coverage stays tier-1
def test_new_losses_match_torch():
    torch = pytest.importorskip("torch")
    x = R.standard_normal((4, 5)).astype(np.float32)
    t = R.standard_normal((4, 5)).astype(np.float32)
    tx, tt = torch.tensor(x), torch.tensor(t)
    np.testing.assert_allclose(
        float(F.smooth_l1_loss(jnp.asarray(x), jnp.asarray(t))),
        float(torch.nn.functional.smooth_l1_loss(tx, tt)), rtol=1e-5)
    np.testing.assert_allclose(
        float(F.huber_loss(jnp.asarray(x), jnp.asarray(t))),
        float(torch.nn.functional.huber_loss(tx, tt)), rtol=1e-5)
    lbl = np.sign(R.standard_normal(4)).astype(np.float32)
    np.testing.assert_allclose(
        float(F.margin_ranking_loss(jnp.asarray(x[:, 0]), jnp.asarray(t[:, 0]),
                                    jnp.asarray(lbl))),
        float(torch.nn.functional.margin_ranking_loss(
            tx[:, 0], tt[:, 0], torch.tensor(lbl))), rtol=1e-5)
    p = 1.0 / (1.0 + np.exp(-x))
    tgt = (R.uniform(size=(4, 5)) > 0.5).astype(np.float32)
    np.testing.assert_allclose(
        float(F.binary_cross_entropy(jnp.asarray(p), jnp.asarray(tgt))),
        float(torch.nn.functional.binary_cross_entropy(
            torch.tensor(p), torch.tensor(tgt))), rtol=1e-5)
    a = R.standard_normal((3, 6)).astype(np.float32)
    pos = R.standard_normal((3, 6)).astype(np.float32)
    neg = R.standard_normal((3, 6)).astype(np.float32)
    np.testing.assert_allclose(
        float(F.triplet_margin_loss(jnp.asarray(a), jnp.asarray(pos),
                                    jnp.asarray(neg))),
        float(torch.nn.functional.triplet_margin_loss(
            torch.tensor(a), torch.tensor(pos), torch.tensor(neg))),
        rtol=1e-4)


def test_ctc_loss_matches_torch():
    torch = pytest.importorskip("torch")
    T, B, C, L = 12, 3, 6, 4
    logits = R.standard_normal((T, B, C)).astype(np.float32)
    log_probs = np.asarray(jnp.asarray(logits) -
                           np.log(np.sum(np.exp(logits), axis=-1,
                                         keepdims=True)))
    labels = R.randint(1, C, (B, L)).astype(np.int32)
    input_lengths = np.asarray([12, 10, 8], np.int32)
    label_lengths = np.asarray([4, 3, 2], np.int32)

    ours = F.ctc_loss(jnp.asarray(log_probs), jnp.asarray(labels),
                      jnp.asarray(input_lengths), jnp.asarray(label_lengths),
                      blank=0, reduction="none")
    ref = torch.nn.functional.ctc_loss(
        torch.tensor(log_probs), torch.tensor(labels.astype(np.int64)),
        torch.tensor(input_lengths.astype(np.int64)),
        torch.tensor(label_lengths.astype(np.int64)),
        blank=0, reduction="none")
    np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-4,
                               atol=1e-4)


def test_pixel_and_channel_ops():
    x = R.standard_normal((1, 8, 3, 3)).astype(np.float32)
    up = nn.PixelShuffle(2)(jnp.asarray(x))
    assert up.shape == (1, 2, 6, 6)
    back = nn.PixelUnshuffle(2)(up)
    np.testing.assert_allclose(back, x, rtol=1e-6)
    cs = nn.ChannelShuffle(2)(jnp.asarray(x))
    assert cs.shape == x.shape
    np.testing.assert_allclose(np.asarray(cs)[0, 1], x[0, 4])


def test_unfold_fold_roundtrip():
    x = R.standard_normal((1, 2, 4, 4)).astype(np.float32)
    cols = F.unfold(jnp.asarray(x), 2, strides=2)
    assert cols.shape == (1, 8, 4)
    y = F.fold(cols, 4, 2, strides=2)
    np.testing.assert_allclose(y, x, rtol=1e-6)


def test_pool_and_norm_variants():
    x1 = jnp.asarray(R.standard_normal((2, 3, 8)).astype(np.float32))
    assert nn.MaxPool1D(2)(x1).shape == (2, 3, 4)
    assert nn.AvgPool1D(2)(x1).shape == (2, 3, 4)
    x3 = jnp.asarray(R.standard_normal((1, 2, 4, 4, 4)).astype(np.float32))
    assert nn.MaxPool3D(2)(x3).shape == (1, 2, 2, 2, 2)
    assert nn.AvgPool3D(2)(x3).shape == (1, 2, 2, 2, 2)
    x2 = jnp.asarray(R.standard_normal((2, 4, 6, 6)).astype(np.float32))
    assert nn.AdaptiveMaxPool2D(3)(x2).shape == (2, 4, 3, 3)
    inorm = nn.InstanceNorm2D(4)
    y = inorm(x2)
    m = np.asarray(y).mean(axis=(2, 3))
    np.testing.assert_allclose(m, np.zeros_like(m), atol=1e-5)
    lrn = nn.LocalResponseNorm(3)
    assert lrn(x2).shape == x2.shape
    conv3 = nn.Conv3D(2, 4, 3, padding=1)
    assert conv3(x3).shape == (1, 4, 4, 4, 4)


def test_instance_norm_matches_torch():
    torch = pytest.importorskip("torch")
    x = R.standard_normal((2, 3, 5, 5)).astype(np.float32)
    ours = F.instance_norm(jnp.asarray(x))
    ref = torch.nn.functional.instance_norm(torch.tensor(x))
    np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-4,
                               atol=1e-5)


def test_local_response_norm_matches_torch():
    torch = pytest.importorskip("torch")
    x = np.abs(R.standard_normal((2, 6, 4, 4))).astype(np.float32)
    ours = F.local_response_norm(jnp.asarray(x), 3, alpha=1e-4, beta=0.75,
                                 k=1.0)
    ref = torch.nn.functional.local_response_norm(torch.tensor(x), 3,
                                                  alpha=1e-4, beta=0.75, k=1.0)
    np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-4,
                               atol=1e-5)


def test_bilinear_and_distance():
    bl = nn.Bilinear(3, 4, 5)
    x1 = jnp.asarray(R.standard_normal((2, 3)).astype(np.float32))
    x2 = jnp.asarray(R.standard_normal((2, 4)).astype(np.float32))
    assert bl(x1, x2).shape == (2, 5)
    pd = nn.PairwiseDistance()
    d = pd(jnp.asarray(np.float32([[0, 0]])), jnp.asarray(np.float32([[3, 4]])))
    np.testing.assert_allclose(np.asarray(d), [5.0], rtol=1e-4)


def test_transformer_decoder_shapes_and_causality():
    paddle_tpu.seed(0)
    t = nn.Transformer(d_model=16, nhead=4, num_encoder_layers=1,
                       num_decoder_layers=1, dim_feedforward=32, dropout=0.0)
    src = jnp.asarray(R.standard_normal((2, 6, 16)).astype(np.float32))
    tgt = jnp.asarray(R.standard_normal((2, 5, 16)).astype(np.float32))
    mask = nn.Transformer.generate_square_subsequent_mask(5)
    out = t(src, tgt, tgt_mask=mask)
    assert out.shape == (2, 5, 16)
    # causality: perturbing tgt[t>0] must not change out[:, 0]
    tgt2 = tgt.at[:, 3:].add(10.0)
    out2 = t(src, tgt2, tgt_mask=mask)
    np.testing.assert_allclose(out[:, 0], out2[:, 0], rtol=1e-4, atol=1e-5)


def test_dropout_variants_preserve_shape_and_scale():
    paddle_tpu.seed(0)
    x = jnp.ones((4, 8, 5, 5))
    d2 = nn.Dropout2D(0.5)
    d2.train()
    y = d2(x)
    assert y.shape == x.shape
    # channel-wise: each channel entirely kept (scaled) or dropped
    arr = np.asarray(y)
    per_chan = arr.reshape(4, 8, -1)
    assert all(len(np.unique(c)) <= 1 for b in per_chan for c in b)
    ad = nn.AlphaDropout(0.3)
    ad.train()
    assert ad(x).shape == x.shape
    ad.eval()
    np.testing.assert_allclose(ad(x), x)


# ---- regressions from round-2 code review ----------------------------------

def test_cholesky_solve_both_triangles():
    a = R.standard_normal((4, 4)).astype(np.float32)
    spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    b = R.standard_normal((4, 2)).astype(np.float32)
    Lf = pl.cholesky(jnp.asarray(spd), upper=False)
    Uf = pl.cholesky(jnp.asarray(spd), upper=True)
    for factor, upper in ((Lf, False), (Uf, True)):
        xs = pl.cholesky_solve(jnp.asarray(b), factor, upper=upper)
        np.testing.assert_allclose(spd @ np.asarray(xs), b, rtol=1e-3,
                                   atol=1e-3)


def test_ctc_loss_mean_raw_logits_matches_torch():
    # reference contract: raw logits in, reduction='mean' divides each
    # sequence's loss by its label length before averaging (ADVICE r2)
    torch = pytest.importorskip("torch")
    T, B, C, L = 12, 3, 6, 4
    logits = R.standard_normal((T, B, C)).astype(np.float32)
    labels = R.randint(1, C, (B, L)).astype(np.int32)
    input_lengths = np.asarray([12, 10, 8], np.int32)
    label_lengths = np.asarray([4, 3, 2], np.int32)
    ours = F.ctc_loss(jnp.asarray(logits), jnp.asarray(labels),
                      jnp.asarray(input_lengths), jnp.asarray(label_lengths),
                      blank=0, reduction="mean")
    ref = torch.nn.functional.ctc_loss(
        torch.tensor(logits).log_softmax(-1),
        torch.tensor(labels.astype(np.int64)),
        torch.tensor(input_lengths.astype(np.int64)),
        torch.tensor(label_lengths.astype(np.int64)),
        blank=0, reduction="mean")
    np.testing.assert_allclose(float(ours), float(ref), rtol=1e-4, atol=1e-4)


def test_lu_pivots_one_based():
    a = R.standard_normal((5, 5)).astype(np.float32)
    _, piv = pl.lu(jnp.asarray(a))
    assert int(np.asarray(piv).min()) >= 1  # LAPACK/reference convention


def test_ctc_loss_empty_label_matches_torch():
    torch = pytest.importorskip("torch")
    T, B, C = 8, 2, 5
    logits = R.standard_normal((T, B, C)).astype(np.float32)
    log_probs = logits - np.log(np.sum(np.exp(logits), axis=-1,
                                       keepdims=True))
    labels = np.asarray([[1, 2], [0, 0]], np.int32)
    input_lengths = np.asarray([8, 6], np.int32)
    label_lengths = np.asarray([2, 0], np.int32)  # second row EMPTY
    ours = F.ctc_loss(jnp.asarray(log_probs), jnp.asarray(labels),
                      jnp.asarray(input_lengths), jnp.asarray(label_lengths),
                      reduction="none")
    ref = torch.nn.functional.ctc_loss(
        torch.tensor(log_probs), torch.tensor(labels.astype(np.int64)),
        torch.tensor(input_lengths.astype(np.int64)),
        torch.tensor(label_lengths.astype(np.int64)), reduction="none")
    np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-4,
                               atol=1e-4)


def test_pool_ceil_mode_matches_torch():
    torch = pytest.importorskip("torch")
    x = R.standard_normal((1, 2, 10)).astype(np.float32)
    ours = F.max_pool1d(jnp.asarray(x), 3, stride=2, ceil_mode=True)
    ref = torch.nn.functional.max_pool1d(torch.tensor(x), 3, stride=2,
                                         ceil_mode=True)
    assert ours.shape == tuple(ref.shape)
    np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-6)


def test_pad2d_channels_last():
    x = R.standard_normal((1, 3, 4, 2)).astype(np.float32)  # NHWC
    out = nn.Pad2D([1, 1, 2, 2], data_format="NHWC")(jnp.asarray(x))
    # width padded by 1+1, height by 2+2, channels UNTOUCHED
    assert out.shape == (1, 7, 6, 2)
    out_cf = nn.Pad2D([1, 1, 2, 2])(jnp.asarray(np.moveaxis(x, -1, 1)))
    assert out_cf.shape == (1, 2, 7, 6)


def test_matrix_rank_absolute_tol():
    d = np.diag(np.float32([1e3, 1.0, 1e-5, 0.0]))
    assert int(pl.matrix_rank(jnp.asarray(d), tol=1e-6)) == 3
    assert int(pl.matrix_rank(jnp.asarray(d), tol=1e-6, hermitian=True)) == 3
    assert int(pl.matrix_rank(jnp.asarray(d), tol=1e-2)) == 2


def test_dropout3d_channels_last():
    paddle_tpu.seed(0)
    d = nn.Dropout3D(0.5, data_format="NDHWC")
    d.train()
    x = jnp.ones((2, 3, 3, 3, 8))
    y = np.asarray(d(x))
    # whole channels (last axis) dropped or kept uniformly
    per_chan = np.moveaxis(y, -1, 1).reshape(2, 8, -1)
    assert all(len(np.unique(c)) <= 1 for b in per_chan for c in b)


def test_counted_api_surface_floors():
    """Regression floors for the counted public surface (round 5: 391
    UNIQUE tensor-family functions — tensor ∪ linalg ∪ fft ∪ signal,
    re-exports counted once; paddle.signal's stft/istft are part of the
    upstream tensor-API family SURVEY.md §2.7 counts toward ~400 — 141
    nn.Layer subclasses, and 111 nn.functional functions. The residue vs
    upstream is in-place `_` variants on immutable jax Arrays and
    CUDA-only handles."""
    import inspect

    import paddle_tpu.fft as fft_mod
    import paddle_tpu.linalg as linalg_mod
    import paddle_tpu.signal as signal_mod
    import paddle_tpu.tensor as tensor_mod
    from paddle_tpu import nn as nn_mod
    from paddle_tpu.nn import functional as f_mod

    def fns(mod):
        return {n for n in dir(mod) if not n.startswith("_")
                and callable(getattr(mod, n))
                and not inspect.isclass(getattr(mod, n))}

    total = len(fns(tensor_mod) | fns(linalg_mod) | fns(fft_mod)
                | fns(signal_mod))
    assert total >= 390, total
    layers = [n for n in dir(nn_mod)
              if not n.startswith("_")
              and inspect.isclass(getattr(nn_mod, n))
              and issubclass(getattr(nn_mod, n), nn_mod.Layer)]
    assert len(layers) >= 141, len(layers)
    assert len(fns(f_mod)) >= 111, len(fns(f_mod))
