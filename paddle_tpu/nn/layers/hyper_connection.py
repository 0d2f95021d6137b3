"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
residual path as ``n`` parallel streams that every sub-layer reads from
and writes to through three small learned, input-dependent mixers.

For one token the state is ``X`` (n, C). Around a sub-layer ``F``::

    x~     = vec(X) / rms(vec(X))                       float32
    m      = x~ @ phi            -> m_pre (n), m_post (n), M_res (n, n)
    H_pre  = sigmoid(alpha_pre * m_pre + pre_bias)
    H_post = 2 * sigmoid(alpha_post * m_post + post_bias)
    H_res  = sinkhorn(clip(alpha_res * M_res + res_bias, lo, hi))
    y      = F(norm(H_pre @ X))                         one mixed stream
    X'     = H_res @ X + H_post[:, None] * y

``H_res`` is kept on the manifold of doubly stochastic matrices (rows
and columns sum to 1), so the residual path neither amplifies nor damps
the streams' mean however deep the stack. With ``H_pre = H_post = e_1``
and ``H_res = I`` stream 0 is the plain residual ``x + F(norm(x))``.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.nn import initializer as init
from paddle_tpu.nn.layer import Layer

_HI = jax.lax.Precision.HIGHEST


def sinkhorn(logits, iters: int, eps: float):
    """``exp`` then ``iters`` rounds of column then row normalisation
    (denominators + ``eps``), float32: (..., n, n) -> doubly stochastic."""
    m = jnp.exp(logits.astype(jnp.float32))
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def hc_mixers(X, phi, alpha_pre, alpha_post, alpha_res, pre_bias, post_bias,
              res_bias, *, sinkhorn_iters: int, eps: float, norm_eps: float,
              clamp=(-30.0, 30.0)):
    """X (..., n, C) -> (H_pre (..., n), H_post (..., n), H_res (..., n, n)),
    all float32. The projection is a float32 matmul at full precision:
    it has 2n + n^2 outputs, and ``exp`` magnifies what it rounds."""
    n = X.shape[-2]
    f32 = lambda a: a.astype(jnp.float32)
    v = f32(X).reshape(X.shape[:-2] + (n * X.shape[-1],))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + norm_eps)
    m = jnp.matmul(v, f32(phi), precision=_HI)
    h_pre = jax.nn.sigmoid(f32(alpha_pre) * m[..., :n] + f32(pre_bias))
    h_post = 2.0 * jax.nn.sigmoid(
        f32(alpha_post) * m[..., n:2 * n] + f32(post_bias))
    res = (f32(alpha_res) * m[..., 2 * n:].reshape(m.shape[:-1] + (n, n))
           + f32(res_bias))
    h_res = sinkhorn(jnp.clip(res, clamp[0], clamp[1]), sinkhorn_iters, eps)
    return h_pre, h_post, h_res


def hc_read(X, h_pre):
    """The one stream the sub-layer sees: ``H_pre @ X`` -> (..., C)."""
    return jnp.einsum("...n,...nc->...c", h_pre,
                      X.astype(jnp.float32)).astype(X.dtype)


def hc_write(X, y, h_post, h_res):
    """``H_res @ X + H_post (x) y`` -> (..., n, C), summed in float32."""
    out = (jnp.einsum("...ij,...jc->...ic", h_res, X.astype(jnp.float32))
           + h_post[..., None] * y.astype(jnp.float32)[..., None, :])
    return out.astype(X.dtype)


class HyperConnection(Layer):
    """The mixer parameters of one sub-layer: ``phi`` (n*C, 2n + n^2),
    three gains and three biases. ``forward(X)`` returns
    ``(H_pre, H_post, H_res)``; the caller reads with :func:`hc_read`,
    runs its sub-layer and writes with :func:`hc_write`."""

    def __init__(self, streams: int, hidden_size: int, *, sinkhorn_iters=20,
                 eps=1e-6, norm_eps=1e-6, clamp=(-30.0, 30.0),
                 initializer_range=0.02):
        super().__init__()
        n = streams
        self.phi = self.create_parameter(
            (n * hidden_size, 2 * n + n * n),
            default_initializer=init.Normal(0.0, initializer_range))
        one, zero = init.Constant(1.0), init.Constant(0.0)
        self.alpha_pre = self.create_parameter((), default_initializer=one)
        self.alpha_post = self.create_parameter((), default_initializer=one)
        self.alpha_res = self.create_parameter((), default_initializer=one)
        self.pre_bias = self.create_parameter((n,), default_initializer=zero)
        self.post_bias = self.create_parameter((n,), default_initializer=zero)
        self.res_bias = self.create_parameter((n, n),
                                              default_initializer=zero)
        self.options = dict(sinkhorn_iters=sinkhorn_iters, eps=eps,
                            norm_eps=norm_eps, clamp=tuple(clamp))

    def forward(self, X):
        return hc_mixers(X, self.phi, self.alpha_pre, self.alpha_post,
                         self.alpha_res, self.pre_bias, self.post_bias,
                         self.res_bias, **self.options)
