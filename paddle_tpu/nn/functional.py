"""Functional NN ops (`paddle.nn.functional` parity).

Ref: python/paddle/nn/functional/ — activations, linear, conv, pooling, norm,
loss, attention. Each op is a jnp/lax composition that XLA fuses; the hot fused
paths (flash attention, rms_norm, rope) additionally have Pallas TPU kernels in
`paddle_tpu.ops`, which these wrappers dispatch to when profitable.
"""

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import rng as _rng


# ---- activations -----------------------------------------------------------

def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jnp.minimum(jax.nn.relu(x), 6.0)


def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x):
    return jax.nn.silu(x)


swish = silu


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def hardsigmoid(x):
    return jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def softplus(x, beta=1.0):
    return jax.nn.softplus(beta * x) / beta


def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


# ---- linear / embedding ----------------------------------------------------

def linear(x, weight, bias=None):
    """y = x @ W (+ b). Weight layout (in, out) — matches the reference."""
    y = jnp.matmul(x, weight)
    if bias is not None:
        y = y + bias
    return y


def embedding(ids, weight, padding_idx=None):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


# ---- dropout ---------------------------------------------------------------

def dropout(x, p=0.5, training=True, mode="upscale_in_train", rng_name="dropout"):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training and p > 0.0:
            return x * (1.0 - p)  # reference contract: infer scales by (1-p)
        return x
    key = _rng.next_rng_key(rng_name)
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


# ---- normalization ---------------------------------------------------------

def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(x.ndim - len(tuple(normalized_shape)
                 if not isinstance(normalized_shape, int) else (normalized_shape,)), x.ndim))
    cdt = jnp.promote_types(x.dtype, jnp.float32)  # bf16→f32, f64 stays f64
    mean = jnp.mean(x.astype(cdt), axis=axes, keepdims=True)
    var = jnp.var(x.astype(cdt), axis=axes, keepdims=True)
    y = (x.astype(cdt) - mean) * lax.rsqrt(var + epsilon)
    y = y.astype(x.dtype)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


def rms_norm(x, weight=None, epsilon=1e-6):
    from paddle_tpu.ops import rms_norm as _rms
    return _rms.rms_norm(x, weight, epsilon)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW"):
    ch_axis = 1 if data_format == "NCHW" else -1
    axes = tuple(i for i in range(x.ndim) if i != (ch_axis % x.ndim))
    if training:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    shape = [1] * x.ndim
    shape[ch_axis % x.ndim] = x.shape[ch_axis % x.ndim]
    y = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y, new_rm, new_rv


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    g = x.reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    g = (g - mean) * lax.rsqrt(var + epsilon)
    y = g.reshape(n, c, *spatial)
    if weight is not None:
        shape = (1, c) + (1,) * len(spatial)
        y = y * weight.reshape(shape)
        if bias is not None:
            y = y + bias.reshape(shape)
    if data_format == "NHWC":
        y = jnp.moveaxis(y, 1, -1)
    return y


# ---- conv / pool -----------------------------------------------------------

def _pair(v):
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    """weight layout: (out_ch, in_ch/groups, kh, kw) — reference layout."""
    stride, dilation = _pair(stride), _pair(dilation)
    if isinstance(padding, str):
        pad = padding.upper()
    elif isinstance(padding, (tuple, list)) and padding and \
            isinstance(padding[0], (tuple, list)):
        pad = [tuple(p) for p in padding]
    else:
        p = _pair(padding)
        pad = [(p[0], p[0]), (p[1], p[1])]
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC"))
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None)
    y = y.astype(x.dtype)
    if bias is not None:
        shape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        y = y + bias.reshape(shape)
    return y


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1):
    # lift (N,C,L) → (N,C,L,1); pad only the L axis
    pad = padding if isinstance(padding, str) else ((padding, padding), (0, 0))
    y = conv2d(x[..., None], weight[..., None], None, (stride, 1), pad,
               (dilation, 1), groups)[..., 0]
    if bias is not None:
        y = y + bias.reshape(1, -1, 1)
    return y


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, data_format="NCHW"):
    """weight layout: (in_ch, out_ch, kh, kw) — reference layout."""
    stride = _pair(stride)
    p = _pair(padding)
    op = _pair(output_padding)
    kh, kw = weight.shape[2], weight.shape[3]
    # out = (in-1)*stride - 2*pad + k + output_padding: extra rows go on the
    # high side of the dilated input
    pad = [(kh - 1 - p[0], kh - 1 - p[0] + op[0]),
           (kw - 1 - p[1], kw - 1 - p[1] + op[1])]
    dn = lax.conv_dimension_numbers(
        x.shape, (weight.shape[1], weight.shape[0], kh, kw),
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "OIHW", "NHWC"))
    w = jnp.flip(jnp.swapaxes(weight, 0, 1), axis=(2, 3))
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad, lhs_dilation=stride,
        dimension_numbers=dn)
    if bias is not None:
        shape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        y = y + bias.reshape(shape)
    return y


def max_pool2d(x, kernel_size, stride=None, padding=0, data_format="NCHW"):
    k, s = _pair(kernel_size), _pair(stride or kernel_size)
    p = _pair(padding)
    if data_format == "NCHW":
        window = (1, 1, k[0], k[1])
        strides = (1, 1, s[0], s[1])
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    else:
        window = (1, k[0], k[1], 1)
        strides = (1, s[0], s[1], 1)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)


def avg_pool2d(x, kernel_size, stride=None, padding=0, data_format="NCHW"):
    k, s = _pair(kernel_size), _pair(stride or kernel_size)
    p = _pair(padding)
    if data_format == "NCHW":
        window = (1, 1, k[0], k[1])
        strides = (1, 1, s[0], s[1])
        pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    else:
        window = (1, k[0], k[1], 1)
        strides = (1, s[0], s[1], 1)
        pads = ((0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0))
    summed = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window, strides, pads)
    return summed / counts


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    out = _pair(output_size)
    if data_format == "NCHW":
        h, w = x.shape[2], x.shape[3]
    else:
        h, w = x.shape[1], x.shape[2]
    assert h % out[0] == 0 and w % out[1] == 0, "adaptive pool needs divisible sizes"
    return avg_pool2d(x, (h // out[0], w // out[1]), (h // out[0], w // out[1]),
                      0, data_format)


def interpolate(x, scale_factor=None, size=None, mode="nearest",
                data_format="NCHW"):
    if data_format == "NCHW":
        n, c, h, w = x.shape
    else:
        n, h, w, c = x.shape
    if size is None:
        sf = _pair(scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    method = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[mode]
    if data_format == "NCHW":
        y = jax.image.resize(x, (n, c, size[0], size[1]), method=method)
    else:
        y = jax.image.resize(x, (n, size[0], size[1], c), method=method)
    return y.astype(x.dtype)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW"):
    """`pad` is paddle-style: flat list, last dim first pairs for NCHW 4-tuples."""
    if len(pad) == x.ndim * 2:
        cfg = [(pad[2 * i], pad[2 * i + 1]) for i in range(x.ndim)]
    else:
        # pad applies to trailing spatial dims, reference order (left,right,top,bottom)
        cfg = [(0, 0)] * x.ndim
        n_spatial = len(pad) // 2
        for i in range(n_spatial):
            axis = x.ndim - 1 - i
            cfg[axis] = (pad[2 * i], pad[2 * i + 1])
    if mode == "constant":
        return jnp.pad(x, cfg, constant_values=value)
    return jnp.pad(x, cfg, mode={"reflect": "reflect", "replicate": "edge"}[mode])


# ---- losses ----------------------------------------------------------------

@jax.custom_vjp
def _token_nll(logits, label):
    """-log softmax(logits)[label] over the LAST axis, per token.

    Memory-lean at LM scale: residuals are the ORIGINAL-dtype logits plus
    the (…,) fp32 lse — autodiff of log_softmax/logsumexp instead keeps a
    full-vocab fp32 tensor alive ((b, s, V) ≈ 1 GiB at V=32k b4 s2048).
    The backward's softmax-minus-onehot is one elementwise fusion emitting
    grads in the logits dtype."""
    return _token_nll_fwd(logits, label)[0]


def _token_nll_fwd(logits, label):
    cdt = jnp.promote_types(logits.dtype, jnp.float32)
    # both consumers of the fp32 cast are REDUCTIONS, so XLA fuses the
    # cast into their loops instead of materializing a full-vocab fp32
    # tensor; `picked` is a one-hot masked sum rather than a gather (a
    # gather on the class axis trips the SPMD partitioner when the logits
    # are vocab-sharded — ParallelCrossEntropy's mp path)
    lse = jax.scipy.special.logsumexp(logits.astype(cdt), axis=-1)
    oh = (jnp.arange(logits.shape[-1], dtype=label.dtype)
          == label[..., None])
    picked = jnp.sum(jnp.where(oh, logits.astype(cdt), 0), axis=-1)
    return lse - picked, (logits, label, lse)


def _token_nll_bwd(res, g):
    logits, label, lse = res
    cdt = jnp.promote_types(logits.dtype, jnp.float32)
    p = jnp.exp(logits.astype(cdt) - lse[..., None])
    oh = (jnp.arange(logits.shape[-1], dtype=label.dtype)
          == label[..., None])
    dz = (p - oh) * g[..., None]
    return dz.astype(logits.dtype), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def cross_entropy(logits, label, reduction="mean", soft_label=False,
                  ignore_index=-100, axis=-1, label_smoothing=0.0):
    cdt = jnp.promote_types(logits.dtype, jnp.float32)
    if soft_label:
        logp = jax.nn.log_softmax(logits.astype(cdt), axis=axis)
        loss = -jnp.sum(label * logp, axis=axis)
    else:
        label = label.astype(jnp.int32)
        ax = axis % logits.ndim
        # reference softmax_with_cross_entropy convention: hard labels may
        # carry a singleton at the class axis; the loss keeps that dim
        keep_axis = label.ndim == logits.ndim and label.shape[ax] == 1
        if keep_axis:
            label = jnp.squeeze(label, ax)
        if label_smoothing > 0.0:
            z = logits.astype(cdt)
            lse = jax.scipy.special.logsumexp(z, axis=ax)
            oh = (jax.lax.broadcasted_iota(label.dtype, z.shape, ax)
                  == jnp.expand_dims(label, ax))
            picked = jnp.sum(jnp.where(oh, z, 0), axis=ax)
            # -sum(oh·logp), oh = (1-ls)·onehot + ls/n
            n = z.shape[ax]
            mean_nll = lse - jnp.sum(z, axis=ax) / n
            loss = ((1.0 - label_smoothing) * (lse - picked)
                    + label_smoothing * mean_nll)
        else:
            z = logits if ax == logits.ndim - 1 else jnp.moveaxis(
                logits, ax, -1)
            loss = _token_nll(z, label)
        valid = (label != ignore_index)
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1)
        if keep_axis:
            loss = jnp.expand_dims(loss, ax)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def softmax_with_cross_entropy(logits, label, soft_label=False, axis=-1):
    return cross_entropy(logits, label, reduction="none", soft_label=soft_label,
                         axis=axis)


def mse_loss(input, label, reduction="mean"):
    loss = jnp.square(input - label)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def l1_loss(input, label, reduction="mean"):
    loss = jnp.abs(input - label)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def binary_cross_entropy_with_logits(logit, label, reduction="mean"):
    loss = jnp.maximum(logit, 0) - logit * label + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def nll_loss(log_probs, label, reduction="mean"):
    picked = jnp.take_along_axis(log_probs, label[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    loss = -picked
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def kl_div(input, label, reduction="mean"):
    loss = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction in ("sum", "batchmean"):
        s = jnp.sum(loss)
        return s / input.shape[0] if reduction == "batchmean" else s
    return loss


# ---- attention -------------------------------------------------------------

def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None,
                                 kv_lens=None, segment_ids=None,
                                 kv_segment_ids=None, window_size=None,
                                 alibi_slopes=None):
    """q/k/v: (batch, seq, heads, head_dim) — the reference's layout.

    Dispatches to the Pallas flash kernel on TPU when profitable
    (paddle_tpu.ops.flash_attention), else the XLA softmax path. Supports
    cross-attention (sq != sk) and the structured-mask extensions
    `kv_lens` / `segment_ids` / `window_size` / `alibi_slopes` (see
    ops.flash_attention).

    Float `attn_mask` entries ≤ −1e9 mean "fully masked" on the Pallas
    path (whole blocks below the threshold are skipped); keep finite soft
    penalties well above −1e9 or the Pallas and XLA paths diverge — see
    ops.flash_attention.scaled_dot_product_attention for details.
    """
    from paddle_tpu.ops import flash_attention as fa
    return fa.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=dropout_p, is_causal=is_causal,
        training=training, scale=scale, kv_lens=kv_lens,
        segment_ids=segment_ids, kv_segment_ids=kv_segment_ids,
        window_size=window_size, alibi_slopes=alibi_slopes)


# ---- misc ------------------------------------------------------------------

def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes)


def label_smooth(label, epsilon=0.1):
    n = label.shape[-1]
    return label * (1 - epsilon) + epsilon / n


def normalize(x, p=2, axis=1, epsilon=1e-12):
    denom = jnp.maximum(jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True), epsilon)
    return x / denom


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot_ = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot_ / jnp.maximum(n1 * n2, eps)


# ---- activation breadth (reference: python/paddle/nn/functional/activation.py)

def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def celu(x, alpha=1.0):
    return jnp.maximum(x, 0.0) + jnp.minimum(
        0.0, alpha * jnp.expm1(x / alpha))


def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold,
                               jnp.zeros_like(x)))


def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, jnp.zeros_like(x))


def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def tanhshrink(x):
    return x - jnp.tanh(x)


def thresholded_relu(x, threshold=1.0):
    return jnp.where(x > threshold, x, jnp.zeros_like(x))


def softsign(x):
    return x / (1.0 + jnp.abs(x))


def prelu(x, weight):
    """weight: scalar or per-channel (dim 1) negative-slope parameter."""
    w = weight
    if w.ndim == 1 and x.ndim > 1 and w.shape[0] > 1:
        w = w.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x >= 0, x, w * x)


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=False):
    """Randomized leaky ReLU; eval uses the mean slope (reference parity)."""
    if training:
        from paddle_tpu.core import rng as _rng_mod
        key = _rng_mod.next_rng_key("rrelu")
        slope = jax.random.uniform(key, x.shape, minval=lower, maxval=upper)
    else:
        slope = (lower + upper) / 2.0
    return jnp.where(x >= 0, x, slope * x)


def maxout(x, groups, axis=1):
    c = x.shape[axis]
    assert c % groups == 0, f"channels {c} not divisible by groups {groups}"
    new_shape = (x.shape[:axis] + (c // groups, groups) +
                 x.shape[axis + 1:])
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    from paddle_tpu.core import rng as _rng_mod
    key = _rng_mod.next_rng_key("gumbel")
    g = jax.random.gumbel(key, x.shape, dtype=x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:  # straight-through: one-hot forward, soft gradient
        idx = jnp.argmax(y, axis=axis)
        hard_y = jax.nn.one_hot(idx, y.shape[axis], axis=axis, dtype=y.dtype)
        y = jax.lax.stop_gradient(hard_y - y) + y
    return y


# ---- loss breadth (reference: python/paddle/nn/functional/loss.py) ---------

def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    d = jnp.abs(input - label)
    loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    return _reduce_loss(loss, reduction)


def huber_loss(input, label, reduction="mean", delta=1.0):
    d = jnp.abs(input - label)
    loss = jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    return _reduce_loss(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    loss = jnp.maximum(0.0, -label * (input - other) + margin)
    return _reduce_loss(loss, reduction)


def soft_margin_loss(input, label, reduction="mean"):
    # softplus form: log(1 + exp(z)) without overflow at large |z|
    loss = jax.nn.softplus(-label * input)
    return _reduce_loss(loss, reduction)


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean"):
    loss = -(label * jax.nn.log_sigmoid(input) +
             (1.0 - label) * jax.nn.log_sigmoid(-input))
    if weight is not None:
        loss = loss * weight
    loss = jnp.mean(loss, axis=-1)
    return _reduce_loss(loss, reduction)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def dist(a, b):
        return jnp.power(jnp.sum(jnp.power(jnp.abs(a - b) + epsilon, p),
                                 axis=-1), 1.0 / p)

    d_pos = dist(input, positive)
    d_neg = dist(input, negative)
    if swap:
        d_neg = jnp.minimum(d_neg, dist(positive, negative))
    loss = jnp.maximum(0.0, d_pos - d_neg + margin)
    return _reduce_loss(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    sim = cosine_similarity(input1, input2, axis=-1)
    loss = jnp.where(label > 0, 1.0 - sim, jnp.maximum(0.0, sim - margin))
    return _reduce_loss(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label > 0, input, jnp.maximum(0.0, margin - input))
    return _reduce_loss(loss, reduction)


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean"):
    if log_input:
        loss = jnp.exp(input) - label * input
    else:
        loss = input - label * jnp.log(input + epsilon)
    if full:
        # Stirling approximation for label! (label > 1)
        stirling = (label * jnp.log(label) - label +
                    0.5 * jnp.log(2.0 * jnp.pi * label))
        loss = loss + jnp.where(label > 1, stirling, jnp.zeros_like(label))
    return _reduce_loss(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         epsilon=1e-12):
    p = jnp.clip(input, epsilon, 1.0 - epsilon)
    loss = -(label * jnp.log(p) + (1.0 - label) * jnp.log1p(-p))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """Connectionist Temporal Classification (reference: warpctc kernel,
    paddle.nn.functional.ctc_loss).

    log_probs: (T, B, C) raw logits — log_softmax is applied internally,
    matching the reference contract (warpctc softmaxes internally).
    Passing already-log-softmaxed inputs is also fine: log_softmax is
    idempotent. labels: (B, L) int32 padded; input_lengths (B,),
    label_lengths (B,). Forward DP in the log semiring runs as one
    lax.scan over time — static shapes, TPU-friendly.

    reduction='mean' divides each sequence's loss by its label length
    before averaging (reference/torch semantics).
    """
    log_probs = jax.nn.log_softmax(log_probs, axis=-1)
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    neg_inf = jnp.asarray(-1e30, log_probs.dtype)

    # extended label sequence: blank, l1, blank, l2, ... blank
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
    pos = jnp.arange(S)[None, :]

    # transitions: from s, s-1 always; from s-2 iff ext[s] != blank and
    # ext[s] != ext[s-2]
    ext_m2 = jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :S]
    can_skip = (ext != blank) & (ext != ext_m2)

    def emit(t_logp, a):       # a: (B, S) alphas
        return jnp.take_along_axis(t_logp, ext, axis=-1) + a

    a0 = jnp.full((B, S), neg_inf)
    a0 = a0.at[:, 0].set(log_probs[0, jnp.arange(B), ext[:, 0]])
    valid1 = (label_lengths > 0)
    a0 = a0.at[:, 1].set(jnp.where(
        valid1, log_probs[0, jnp.arange(B), ext[:, 1]], neg_inf))

    def step(a, t_logp):
        a_m1 = jnp.pad(a, ((0, 0), (1, 0)), constant_values=neg_inf)[:, :S]
        a_m2 = jnp.pad(a, ((0, 0), (2, 0)), constant_values=neg_inf)[:, :S]
        a_m2 = jnp.where(can_skip, a_m2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(a, a_m1), a_m2)
        return emit(t_logp, merged), merged

    def scan_step(carry, xs):
        t_idx, t_logp = xs
        a = carry
        new_a, _ = step(a, t_logp)
        # freeze alphas past each sequence's input length
        new_a = jnp.where((t_idx < input_lengths)[:, None], new_a, a)
        return new_a, None

    alphas, _ = jax.lax.scan(
        scan_step, a0, (jnp.arange(1, T), log_probs[1:]))

    end = 2 * label_lengths          # blank after last label
    end_m1 = jnp.maximum(end - 1, 0)  # last label
    ll_blank = jnp.take_along_axis(alphas, end[:, None], axis=1)[:, 0]
    ll_label = jnp.take_along_axis(alphas, end_m1[:, None], axis=1)[:, 0]
    # empty-label rows have only the all-blank path — don't count it twice
    ll_label = jnp.where(label_lengths > 0, ll_label, neg_inf)
    ll = jnp.logaddexp(ll_blank, ll_label)
    loss = -ll
    if norm_by_times:
        loss = loss / input_lengths.astype(loss.dtype)
    if reduction == "mean":
        denom = jnp.maximum(label_lengths, 1).astype(loss.dtype)
        return jnp.mean(loss / denom)
    return _reduce_loss(loss, reduction)


# ---- misc breadth -----------------------------------------------------------

def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c, h, w = x.shape
    oc = c // (r * r)
    x = x.reshape(n, oc, r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    x = x.reshape(n, oc, h * r, w * r)
    if data_format == "NHWC":
        x = jnp.moveaxis(x, 1, -1)
    return x


def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // r, r, w // r, r)
    x = x.transpose(0, 1, 3, 5, 2, 4)
    x = x.reshape(n, c * r * r, h // r, w // r)
    if data_format == "NHWC":
        x = jnp.moveaxis(x, 1, -1)
    return x


def channel_shuffle(x, groups, data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c, h, w = x.shape
    x = x.reshape(n, groups, c // groups, h, w)
    x = x.transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)
    if data_format == "NHWC":
        x = jnp.moveaxis(x, 1, -1)
    return x


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    """im2col (reference unfold): (N, C, H, W) → (N, C·kh·kw, L)."""
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)],
        rhs_dilation=(dh, dw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, ckk, oh, ow = patches.shape
    return patches.reshape(n, ckk, oh * ow)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1):
    """col2im: inverse of unfold by scatter-add."""
    oh, ow = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    n, ckk, L = x.shape
    c = ckk // (kh * kw)
    nh = (oh + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    nw = (ow + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    cols = x.reshape(n, c, kh, kw, nh, nw)
    out = jnp.zeros((n, c, oh + 2 * ph, ow + 2 * pw), x.dtype)
    for i in range(kh):
        for j in range(kw):
            hi = i * dh
            wj = j * dw
            out = out.at[:, :, hi:hi + nh * sh:sh,
                         wj:wj + nw * sw:sw].add(cols[:, :, i, j])
    return out[:, :, ph:ph + oh, pw:pw + ow]


def instance_norm(x, weight=None, bias=None, epsilon=1e-5,
                  data_format="NCHW"):
    ch_axis = 1 if data_format == "NCHW" else -1
    axes = tuple(i for i in range(2, x.ndim)) if ch_axis == 1 else \
        tuple(i for i in range(1, x.ndim - 1))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW"):
    ch_axis = 1 if data_format == "NCHW" else x.ndim - 1
    sq = jnp.square(x)
    half = size // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[ch_axis] = (half, size - 1 - half)
    sq = jnp.pad(sq, pad_cfg)
    win = sum(jax.lax.slice_in_dim(sq, i, i + x.shape[ch_axis], axis=ch_axis)
              for i in range(size))
    return x / jnp.power(k + alpha * win / size, beta)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    d = jnp.abs(x - y) + epsilon
    return jnp.power(jnp.sum(jnp.power(d, p), axis=-1), 1.0 / p) if not \
        keepdim else jnp.power(jnp.sum(jnp.power(d, p), axis=-1,
                                       keepdims=True), 1.0 / p)


# ---- 3-D / 1-D conv & pooling breadth --------------------------------------

def _ntuple(v, n):
    return tuple(v) if isinstance(v, (tuple, list)) else (v,) * n


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    """weight layout: (out_ch, in_ch/groups, kd, kh, kw)."""
    stride = _ntuple(stride, 3)
    dilation = _ntuple(dilation, 3)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _ntuple(padding, 3)
        pad = [(p[0], p[0]), (p[1], p[1]), (p[2], p[2])]
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCDHW", "OIDHW", "NCDHW") if data_format == "NCDHW"
        else ("NDHWC", "OIDHW", "NDHWC"))
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.promote_types(x.dtype, jnp.float32)
        if x.dtype != jnp.bfloat16 else None)
    y = y.astype(x.dtype)
    if bias is not None:
        shape = (1, -1, 1, 1, 1) if data_format == "NCDHW" else (1, 1, 1, 1, -1)
        y = y + bias.reshape(shape)
    return y


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, data_format="NCDHW"):
    """weight layout: (in_ch, out_ch, kd, kh, kw)."""
    stride = _ntuple(stride, 3)
    p = _ntuple(padding, 3)
    op = _ntuple(output_padding, 3)
    k = weight.shape[2:]
    pad = [(k[i] - 1 - p[i], k[i] - 1 - p[i] + op[i]) for i in range(3)]
    w = jnp.flip(weight, axis=(2, 3, 4))
    w = jnp.swapaxes(w, 0, 1)       # (out, in, ...)
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, ("NCDHW", "OIDHW", "NCDHW"))
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1, 1), padding=pad, lhs_dilation=stride,
        dimension_numbers=dn)
    y = y.astype(x.dtype)
    if bias is not None:
        y = y + bias.reshape(1, -1, 1, 1, 1)
    return y


def _pool(x, kernel, stride, padding, nd, reducer, init_val, avg=False,
          ceil_mode=False):
    kernel = _ntuple(kernel, nd)
    stride = _ntuple(stride if stride is not None else kernel, nd)
    p = _ntuple(padding, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    pads = [(0, 0), (0, 0)]
    for i, (ki, si, pi) in enumerate(zip(kernel, stride, p)):
        hi = pi
        if ceil_mode:
            # last partial window counts, but no window may START in the
            # right padding (reference pooling rule) — compute the exact
            # output count and the (possibly negative) high pad for it
            n = x.shape[2 + i]
            out = -(-(n + 2 * pi - ki) // si) + 1
            if (out - 1) * si >= n + pi:
                out -= 1
            hi = (out - 1) * si + ki - n - pi
        pads.append((pi, hi))
    y = lax.reduce_window(x, init_val, reducer, window, strides, pads)
    if avg:
        # divide by the REAL element count per window (padding excluded —
        # reference exclusive=True semantics)
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        y = y / counts
    return y


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, 1, lax.max, -jnp.inf,
                 ceil_mode=ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, 1, lax.add, 0.0, avg=True,
                 ceil_mode=ceil_mode)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, 3, lax.max, -jnp.inf,
                 ceil_mode=ceil_mode)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, 3, lax.add, 0.0, avg=True,
                 ceil_mode=ceil_mode)


def adaptive_max_pool2d(x, output_size):
    oh, ow = _pair(output_size)
    n, c, h, w = x.shape
    assert h % oh == 0 and w % ow == 0, \
        f"adaptive pool needs divisible sizes, got {(h, w)} -> {(oh, ow)}"
    return jnp.max(x.reshape(n, c, oh, h // oh, ow, w // ow), axis=(3, 5))


def adaptive_avg_pool1d(x, output_size):
    n, c, l = x.shape
    o = output_size if isinstance(output_size, int) else output_size[0]
    assert l % o == 0
    return jnp.mean(x.reshape(n, c, o, l // o), axis=-1)


def adaptive_avg_pool3d(x, output_size):
    od, oh, ow = _ntuple(output_size, 3)
    n, c, d, h, w = x.shape
    assert d % od == 0 and h % oh == 0 and w % ow == 0
    return jnp.mean(
        x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow),
        axis=(3, 5, 7))


# reference path: paddle.nn.functional.flash_attention.flash_attention
from paddle_tpu.ops.flash_attention import flash_attention  # noqa: F401,E402


# ---- long-tail functional parity (reference python/paddle/nn/functional) ---

def square_error_cost(input, label):
    return jnp.square(input - label)


def log_loss(input, label, epsilon=1e-4):
    return (-label * jnp.log(input + epsilon)
            - (1.0 - label) * jnp.log(1.0 - input + epsilon))


def sequence_mask(x, maxlen=None, dtype="int64"):
    """(..., n) lengths → (..., n, maxlen) 0/1 mask.

    With ``maxlen=None`` the mask width is inferred as ``max(x)``, which
    needs a concrete value — inside jit/grad/scan pass ``maxlen`` explicitly
    (XLA requires static shapes).
    """
    from paddle_tpu.core.dtype import to_jax_dtype
    x = jnp.asarray(x)
    if maxlen is None:
        if isinstance(x, jax.core.Tracer):
            raise ValueError(
                "sequence_mask(maxlen=None) infers the mask width from "
                "max(x), which is unavailable under jit/grad/scan tracing "
                "(the output shape would be data-dependent). Pass an "
                "explicit static maxlen.")
        m = int(jnp.max(x))
    else:
        m = int(maxlen)
    return (jnp.arange(m) < x[..., None]).astype(to_jax_dtype(dtype))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25,
                       gamma=2.0, reduction="sum"):
    p = jax.nn.sigmoid(logit.astype(jnp.float32))
    lab = label.astype(jnp.float32)
    ce = (jnp.maximum(logit, 0) - logit * lab
          + jnp.log1p(jnp.exp(-jnp.abs(logit)))).astype(jnp.float32)
    p_t = p * lab + (1.0 - p) * (1.0 - lab)
    a_t = alpha * lab + (1.0 - alpha) * (1.0 - lab)
    loss = a_t * ((1.0 - p_t) ** gamma) * ce
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce_loss(loss, reduction)   # shared helper (loss section)


def dice_loss(input, label, epsilon=1e-5):
    """input (N, ..., C) probabilities, label (N, ..., 1) int classes."""
    c = input.shape[-1]
    oh = jax.nn.one_hot(jnp.squeeze(label, -1), c, dtype=input.dtype)
    red = tuple(range(1, input.ndim))
    inter = jnp.sum(input * oh, axis=red)
    union = jnp.sum(input, axis=red) + jnp.sum(oh, axis=red)
    return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """Reference paddle npair_loss: softmax CE over anchor·positiveᵀ with
    same-label targets + L2 on the embeddings."""
    a = anchor.astype(jnp.float32)
    p = positive.astype(jnp.float32)
    labels = labels.reshape(-1)
    sim = jnp.matmul(a, p.T,
                     preferred_element_type=jnp.float32)   # (n, n)
    tgt = (labels[:, None] == labels[None, :]).astype(jnp.float32)
    tgt = tgt / jnp.sum(tgt, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.mean(jnp.sum(tgt * logp, axis=1))
    # Beta = 0.25 — the reference's (and TF's) npair regularizer weight
    reg = 0.25 * l2_reg * (jnp.mean(jnp.sum(a * a, 1)) +
                           jnp.mean(jnp.sum(p * p, 1)))
    return ce + reg


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean"):
    var = jnp.maximum(variance.astype(jnp.float32), epsilon)
    loss = 0.5 * (jnp.log(var)
                  + jnp.square(input - label).astype(jnp.float32) / var)
    if full:
        loss = loss + 0.5 * math.log(2 * math.pi)
    return _reduce_loss(loss, reduction)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    """TSM channel shift across the segment (time) axis."""
    if data_format == "NHWC":
        x = jnp.transpose(x, (0, 3, 1, 2))
    nt, c, h, w = x.shape
    n = nt // seg_num
    xr = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.pad(xr[:, 1:, :fold], ((0, 0), (0, 1), (0, 0), (0, 0),
                                      (0, 0)))
    right = jnp.pad(xr[:, :-1, fold:2 * fold],
                    ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    out = jnp.concatenate([left, right, xr[:, :, 2 * fold:]], axis=2)
    out = out.reshape(nt, c, h, w)
    if data_format == "NHWC":
        out = jnp.transpose(out, (0, 2, 3, 1))
    return out


def upsample(x, size=None, scale_factor=None, mode="nearest",
             data_format="NCHW"):
    return interpolate(x, scale_factor=scale_factor, size=size, mode=mode,
                       data_format=data_format)


def zeropad2d(x, padding, data_format="NCHW"):
    from paddle_tpu import nn as _nn
    return _nn.ZeroPad2D(padding, data_format=data_format)(x)


def alpha_dropout(x, p=0.5, training=True):
    from paddle_tpu import nn as _nn
    layer = _nn.AlphaDropout(p)
    layer.training = training
    return layer(x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    from paddle_tpu import nn as _nn
    layer = _nn.Dropout2D(p, data_format=data_format)
    layer.training = training
    return layer(x)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    from paddle_tpu import nn as _nn
    layer = _nn.Dropout3D(p, data_format=data_format)
    layer.training = training
    return layer(x)


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None):
    from paddle_tpu import nn as _nn
    return _nn.MaxUnPool1D(kernel_size, stride, padding, data_format,
                           output_size)(x, indices)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None):
    from paddle_tpu import nn as _nn
    return _nn.MaxUnPool2D(kernel_size, stride, padding, data_format,
                           output_size)(x, indices)


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None):
    from paddle_tpu import nn as _nn
    return _nn.MaxUnPool3D(kernel_size, stride, padding, data_format,
                           output_size)(x, indices)


def lp_pool1d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCL"):
    from paddle_tpu import nn as _nn
    return _nn.LPPool1D(norm_type, kernel_size, stride, padding, ceil_mode,
                        data_format)(x)


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW"):
    from paddle_tpu import nn as _nn
    return _nn.LPPool2D(norm_type, kernel_size, stride, padding, ceil_mode,
                        data_format)(x)


def bilinear(x1, x2, weight, bias=None):
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2,
                     preferred_element_type=jnp.float32).astype(x1.dtype)
    return out + bias if bias is not None else out


def affine_grid(theta, out_shape, align_corners=True):
    """theta (N, 2, 3) → sampling grid (N, H, W, 2) in [-1, 1] coords."""
    n, _, h, w = (out_shape if len(out_shape) == 4
                  else (out_shape[0], 1, out_shape[1], out_shape[2]))

    def base(steps):
        if align_corners:
            return jnp.linspace(-1.0, 1.0, steps)
        half = 1.0 - 1.0 / steps
        return jnp.linspace(-half, half, steps)

    ys = base(h)
    xs = base(w)
    ones = jnp.ones((h, w))
    grid = jnp.stack([jnp.broadcast_to(xs[None, :], (h, w)),
                      jnp.broadcast_to(ys[:, None], (h, w)), ones],
                     axis=-1)                       # (H, W, 3)
    theta = jnp.asarray(theta, jnp.float32)
    # fp32 accumulation: default TPU matmul precision (bf16 passes) puts
    # ~1e-2 error on the [-1, 1] grid coords ≈ pixels at high resolution
    return jnp.einsum("hwk,nok->nhwo", grid, theta,
                      preferred_element_type=jnp.float32)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    """4-D grid sampling (reference paddle.nn.functional.grid_sample):
    x (N, C, H, W), grid (N, Hg, Wg, 2) with xy in [-1, 1]."""
    n, c, h, w = x.shape
    gx = grid[..., 0].astype(jnp.float32)
    gy = grid[..., 1].astype(jnp.float32)

    def unnorm(g, size):
        if align_corners:
            return (g + 1.0) / 2.0 * (size - 1)
        return ((g + 1.0) * size - 1.0) / 2.0

    fx = unnorm(gx, w)
    fy = unnorm(gy, h)

    def reflect(v, lo, hi):
        # reflect into [lo, hi] (continuous coordinates, period 2*(hi-lo))
        rng_ = hi - lo
        v = jnp.abs(v - lo) % (2 * rng_)
        return lo + jnp.where(v > rng_, 2 * rng_ - v, v)

    if padding_mode == "border":
        fx = jnp.clip(fx, 0, w - 1)
        fy = jnp.clip(fy, 0, h - 1)
    elif padding_mode == "reflection":
        if align_corners:
            fx = reflect(fx, 0.0, w - 1.0)
            fy = reflect(fy, 0.0, h - 1.0)
        else:
            fx = jnp.clip(reflect(fx, -0.5, w - 0.5), 0, w - 1)
            fy = jnp.clip(reflect(fy, -0.5, h - 0.5), 0, h - 1)

    def gather(ix, iy):
        valid = ((ix >= 0) & (ix < w) & (iy >= 0) & (iy < h))
        ixc = jnp.clip(ix, 0, w - 1)
        iyc = jnp.clip(iy, 0, h - 1)
        vals = x[jnp.arange(n)[:, None, None], :, iyc, ixc]  # (N,Hg,Wg,C)
        return jnp.where(valid[..., None], vals, 0.0)

    if mode == "nearest":
        out = gather(jnp.round(fx).astype(jnp.int32),
                     jnp.round(fy).astype(jnp.int32))
    else:
        x0 = jnp.floor(fx).astype(jnp.int32)
        y0 = jnp.floor(fy).astype(jnp.int32)
        x1, y1 = x0 + 1, y0 + 1
        wx = fx - x0
        wy = fy - y0
        out = (gather(x0, y0) * ((1 - wx) * (1 - wy))[..., None]
               + gather(x1, y0) * (wx * (1 - wy))[..., None]
               + gather(x0, y1) * ((1 - wx) * wy)[..., None]
               + gather(x1, y1) * (wx * wy)[..., None])
    return jnp.transpose(out, (0, 3, 1, 2)).astype(x.dtype)  # (N,C,Hg,Wg)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, return_softmax=False,
                         reduction="mean"):
    """ArcFace-family margins: target logit cosθ → cos(m1·θ + m2) − m3,
    all logits scaled by `scale`, then softmax CE."""
    # clip strictly inside (−1, 1): arccos has infinite slope at the
    # endpoints, and normalized embeddings routinely hit cos == ±1.0 —
    # the gradient would be NaN and poison the whole step
    eps = 1e-6
    cos = jnp.clip(logits.astype(jnp.float32), -1.0 + eps, 1.0 - eps)
    theta = jnp.arccos(cos)
    tgt = jnp.cos(margin1 * theta + margin2) - margin3
    oh = jax.nn.one_hot(label.reshape(-1), logits.shape[-1],
                        dtype=jnp.float32)
    adjusted = scale * jnp.where(oh > 0, tgt, cos)
    loss = cross_entropy(adjusted, label.reshape(-1), reduction=reduction)
    if return_softmax:
        return loss, jax.nn.softmax(adjusted, axis=-1)
    return loss


def adaptive_log_softmax_with_loss(input, label, head_weight, tail_weights,
                                   cutoffs, head_bias=None):
    """Functional form of nn.AdaptiveLogSoftmaxWithLoss (same math, params
    passed explicitly). Returns (per-sample logprob of the target, mean
    NLL loss)."""
    n_clusters = len(tail_weights)
    head_logits = input @ head_weight
    if head_bias is not None:
        head_logits = head_logits + head_bias
    head_logp = jax.nn.log_softmax(head_logits, axis=-1)
    shortlist = cutoffs[0]
    out = jnp.zeros(input.shape[0], jnp.float32)
    in_short = label < shortlist
    idx_short = jnp.clip(label, 0, shortlist - 1)
    out = jnp.where(
        in_short,
        jnp.take_along_axis(head_logp, idx_short[:, None], 1)[:, 0], out)
    for ci in range(n_clusters):
        lo = cutoffs[ci]
        hi = cutoffs[ci + 1]
        in_c = (label >= lo) & (label < hi)
        w1, w2 = tail_weights[ci]
        tail_logp = jax.nn.log_softmax((input @ w1) @ w2, axis=-1)
        rel = jnp.clip(label - lo, 0, hi - lo - 1)
        lp = (head_logp[:, shortlist + ci]
              + jnp.take_along_axis(tail_logp, rel[:, None], 1)[:, 0])
        out = jnp.where(in_c, lp, out)
    return out, -jnp.mean(out)


def rnnt_loss(logits, labels, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.0, reduction="mean"):
    """RNN-Transducer loss (reference paddle.nn.functional.rnnt_loss over
    the warprnnt kernel; canonical python/paddle/nn/functional/loss.py).

    logits (B, T, U+1, V) UNNORMALIZED joint-network outputs; labels
    (B, U) int; input_lengths (B,), label_lengths (B,). Forward DP in the
    log semiring: alpha[t,u] = logaddexp(alpha[t-1,u] + blank[t-1,u],
    alpha[t,u-1] + emit[t,u-1]). TPU-native shape: ONE lax.scan over T
    whose inner u-recurrence (a first-order log-semiring linear
    recurrence) is solved with lax.associative_scan — O(T) sequential
    steps, O(log U) inner depth, no host loop. Gradients via jax.grad are
    the exact RNNT gradients (the warprnnt backward computes the same
    quantity analytically).

    fastemit_lambda shapes the GRADIENT in the reference kernel (FastEmit
    regularization); only 0.0 is supported here — autodiff supplies the
    exact lambda=0 gradient.
    """
    if fastemit_lambda:
        raise NotImplementedError(
            "rnnt_loss: fastemit_lambda != 0 reshapes the backward pass "
            "inside the reference's warprnnt kernel; the autodiff "
            "gradient here is the exact fastemit_lambda=0 one")
    neg = -1e30
    lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    labels = jnp.asarray(labels).astype(jnp.int32)
    input_lengths = jnp.asarray(input_lengths, jnp.int32)
    label_lengths = jnp.asarray(label_lengths, jnp.int32)
    B, T, U1, V = lp.shape
    blank_lp = lp[..., blank]                               # (B, T, U+1)
    emit = jnp.take_along_axis(
        lp[:, :, :U1 - 1, :], labels[:, None, :, None], axis=-1)[..., 0]
    emit = jnp.pad(emit, ((0, 0), (0, 0), (0, 1)), constant_values=neg)

    def assoc(e1, e2):
        # element u encodes x_u = logaddexp(x_{u-1} + a_u, b_u)
        a1, b1 = e1
        a2, b2 = e2
        return a1 + a2, jnp.logaddexp(b1 + a2, b2)

    def solve_row(a_coef, b_vals):
        _, row = jax.lax.associative_scan((lambda x, y: assoc(x, y)),
                                          (a_coef, b_vals), axis=1)
        return row

    shift = lambda em: jnp.pad(em[:, :-1], ((0, 0), (1, 0)),
                               constant_values=neg)
    # t = 0: alpha[0,u] = cumsum of emit[0, :u]
    b0 = jnp.full((B, U1), neg).at[:, 0].set(0.0)
    row0 = solve_row(shift(emit[:, 0]), b0)

    def step(prev_row, xs):
        bl_prev, em_t = xs                                  # (B, U+1) each
        from_top = prev_row + bl_prev
        row = solve_row(shift(em_t), from_top)
        return row, row

    xs = (jnp.moveaxis(blank_lp, 1, 0)[:-1],                # blank[t-1]
          jnp.moveaxis(emit, 1, 0)[1:])                     # emit[t]
    _, rows = jax.lax.scan(step, row0, xs)                  # (T-1, B, U+1)
    alphas = jnp.concatenate([row0[None], rows], axis=0)    # (T, B, U+1)
    tb = jnp.clip(input_lengths - 1, 0, T - 1)
    ub = jnp.clip(label_lengths, 0, U1 - 1)
    bi = jnp.arange(B)
    ll = alphas[tb, bi, ub] + blank_lp[bi, tb, ub]
    loss = -ll
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss
