"""Rotary position embedding (fused_rope parity).

Reference: paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu, python veneer
paddle.incubate.nn.functional.fused_rotary_position_embedding. On TPU the
sin/cos gather + rotate is fully fused by XLA into surrounding matmuls, so the
XLA path is the production path; layout is (batch, seq, heads, head_dim) and
rotation follows the reference's interleaved-halves ("NeoX") convention.
"""

import functools
import math

import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=32)
def _freqs(head_dim: int, base: float):
    return 1.0 / (base ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def _angles(inv_freq, seq_len, position_ids):
    """position x frequency, the half-width angles repeated to head_dim."""
    inv_freq = jnp.asarray(inv_freq)
    if position_ids is None:
        t = jnp.arange(seq_len, dtype=jnp.float32)
    else:
        t = position_ids.astype(jnp.float32)
    freqs = jnp.einsum("...s,d->...sd", t, inv_freq)
    return jnp.concatenate([freqs, freqs], axis=-1)


def rope_cos_sin(seq_len, head_dim, base=10000.0, dtype=jnp.float32,
                 position_ids=None):
    emb = _angles(_freqs(head_dim, float(base)), seq_len, position_ids)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary_pos_emb(x, cos, sin):
    """x: (b, s, h, d); cos/sin: (s, d) or (b, s, d)."""
    while cos.ndim < x.ndim:
        cos = cos[None] if cos.ndim == 2 and x.ndim == 4 else cos[..., None, :]
        sin = sin[None] if sin.ndim == 2 and x.ndim == 4 else sin[..., None, :]
    # after loop: (1, s, 1, d) broadcastable — rebuild explicitly for clarity
    return (x * cos + _rotate_half(x) * sin).astype(x.dtype)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    base=10000.0):
    """Apply RoPE to q/k (v passes through) — reference API parity."""
    b, s, h, d = q.shape
    if cos is None or sin is None:
        cos, sin = rope_cos_sin(s, d, base=base, dtype=jnp.float32,
                                position_ids=position_ids)
    if cos.ndim == 2:
        cos_b = cos[None, :, None, :]
        sin_b = sin[None, :, None, :]
    elif cos.ndim == 3:
        cos_b = cos[:, :, None, :]
        sin_b = sin[:, :, None, :]
    else:
        cos_b, sin_b = cos, sin
    qf = q.astype(jnp.float32)
    out_q = (qf * cos_b + _rotate_half(qf) * sin_b).astype(q.dtype)
    out_k = None
    if k is not None:
        kf = k.astype(jnp.float32)
        out_k = (kf * cos_b + _rotate_half(kf) * sin_b).astype(k.dtype)
    return out_q, out_k, v


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``
    (1 for ``factor <= 1``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@functools.lru_cache(maxsize=32)
def _yarn_freqs(head_dim: int, base: float, factor: float, original_max: int,
                beta_fast: float, beta_slow: float):
    """YaRN inverse frequencies (arXiv:2309.00071, as DeepSeek-V2
    publishes them): dimensions that turn more than ``beta_fast`` times
    inside the original context keep their frequency, those that turn
    fewer than ``beta_slow`` times are interpolated by ``factor``, and a
    linear ramp joins the two."""
    half = head_dim // 2
    extra = _freqs(head_dim, base)
    inter = extra / factor

    def corr_dim(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_cos_sin(seq_len, head_dim, *, base=10000.0, factor=1.0,
                 original_max_position_embeddings=4096, beta_fast=32.0,
                 beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0,
                 dtype=jnp.float32, position_ids=None):
    """``rope_cos_sin`` at YaRN frequencies. The tables carry YaRN's
    ``mscale / mscale_all_dim`` ratio (1 when the two are equal); the
    ``mscale_all_dim`` temperature itself belongs to the attention's
    softmax scale (:func:`yarn_mscale`)."""
    emb = _angles(_yarn_freqs(
        int(head_dim), float(base), float(factor),
        int(original_max_position_embeddings), float(beta_fast),
        float(beta_slow)), seq_len, position_ids)
    ratio = (yarn_mscale(factor, mscale)
             / yarn_mscale(factor, mscale_all_dim))
    return ((jnp.cos(emb) * ratio).astype(dtype),
            (jnp.sin(emb) * ratio).astype(dtype))
