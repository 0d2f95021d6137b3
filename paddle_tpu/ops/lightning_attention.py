"""Lightning linear attention: a decayed ``d x d`` state a head in place
of a cache of keys and values.

Per head h with decay ``lambda_h = exp(-s_h)``, ``s_h = 2^(-8 (h + 1) /
H)`` (Lightning Attention-2's slopes)::

    S_t = lambda_h S_{t-1} + k_t^T v_t        o_t = (q_t / sqrt(d)) S_t

The state is float32 whatever the activations are: rounding it to bf16
every step loses the old tokens (``tests/test_minicpm_sala.py`` has the
control that shows it).

Two entry points, each with a ``jnp`` path (the CPU, the parity oracle)
and a Mosaic kernel named as the function in traces:

``lightning_prefill``  s tokens a row in chunks of C: inside a chunk
    the decayed causal product ``((q k^T) * M) v`` with ``M_ij =
    lambda^(i - j)`` for ``i >= j``, across chunks the state. A row has
    ``nvalid`` true tokens of the s (a wave pads on the right); the
    state that comes back is the one after its LAST TRUE token: pad
    tokens add nothing and decay nothing. Outputs at pad positions are
    whatever they are. Every power of lambda is computed as ``exp(-s
    m)`` with ``m >= 0``, never as a ratio, so nothing overflows.

``lightning_decode``  one token a row for every row of a decode step,
    one layer: the state is read, decayed, updated and written in place
    (``(layers, slots, H, d, d)``, the kernel aliases it), and the
    output taken from the updated state. A row that is not ``active``
    keeps its state.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
PREFILL_KERNEL = "lightning_prefill"
DECODE_KERNEL = "lightning_decode"


def slopes(heads: int) -> np.ndarray:
    """``s_h``, float32 (H,); ``lambda_h = exp(-s_h)``."""
    return (2.0 ** (-8.0 * (np.arange(heads, dtype=np.float32) + 1) / heads)
            ).astype(np.float32)


def chunk_size(s: int, chunk: int) -> int:
    """The chunk s tokens are cut into: ``chunk`` where it divides s."""
    return s if s <= chunk else (chunk if s % chunk == 0
                                 else math.gcd(s, chunk))


# ---------------------------------------------------------------- prefill
def lightning_prefill_reference(q, k, v, state, nvalid, *, chunk: int = 256,
                                state_dtype=jnp.float32):
    """q, k, v (n, s, H, d); state (n, H, d, d) float32; nvalid (n,) the
    true tokens among the s -> (o (n, s, H, d) float32, the state after
    each row's ``nvalid`` tokens). ``state_dtype`` other than float32 is
    the tests' control: the state rounded there after every chunk."""
    n, s, H, d = q.shape
    C = chunk_size(s, chunk)
    sl = jnp.asarray(slopes(H))
    f = lambda a: jnp.moveaxis(
        a.astype(jnp.float32).reshape(n, s // C, C, H, d), 1, 0)
    i = jnp.arange(C)
    diff = i[:, None] - i[None, :]
    M = jnp.where(diff >= 0, jnp.exp(-sl[:, None, None]
                                     * jnp.maximum(diff, 0)), 0.0)  # (H, C, C)
    into = jnp.exp(-sl[None, :] * (i[:, None] + 1.0))               # (C, H)

    def body(S, xs):
        qc, kc, vc, c = xs
        nv = jnp.clip(nvalid - c * C, 0, C)                         # (n,)
        a = jnp.einsum("nihd,njhd->nhij", qc, kc, precision=_HI) * M[None]
        o = jnp.einsum("nhij,njhd->nihd", a, vc, precision=_HI)
        o = o + (jnp.einsum("nihd,nhde->nihe", qc, S, precision=_HI)
                 * into[None, :, :, None])
        left = nv[:, None] - 1 - i[None, :]                         # (n, C)
        w = jnp.where(left[..., None] >= 0,
                      jnp.exp(-sl * jnp.maximum(left, 0)[..., None]), 0.0)
        S = (jnp.exp(-sl[None, :] * nv[:, None])[..., None, None] * S
             + jnp.einsum("njhd,njhe->nhde", kc * w[..., None], vc,
                          precision=_HI))
        return S.astype(state_dtype).astype(jnp.float32), o

    S, o = lax.scan(body, state.astype(jnp.float32),
                    (f(q) / math.sqrt(d), f(k), f(v), jnp.arange(s // C)))
    return jnp.moveaxis(o, 0, 1).reshape(n, s, H, d), S


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _lightning_prefill_pallas(q, k, v, state, nvalid, *, chunk: int,
                              interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, s, H, d = q.shape
    C = chunk_size(s, chunk)
    nc = s // C
    scale = 1.0 / math.sqrt(d)
    # a head is a lane-aligned slice of a token's row, as the projection
    # leaves it: no transpose (XLA would fold one into the projection's
    # weights and keep a re-laid copy of every layer's)
    flat = lambda a: a.reshape(n, s, H * d)

    def kernel(sl_ref, nv_ref, q_ref, k_ref, v_ref, s_in, o_ref, s_out, S):
        r, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(c == 0)
        def _():
            S[...] = s_in[...]

        sl = sl_ref[h]
        nv = jnp.clip(nv_ref[r] - c * C, 0, C)
        qc = q_ref[...].astype(jnp.float32) * scale
        kc = k_ref[...].astype(jnp.float32)
        vc = v_ref[...].astype(jnp.float32)
        row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        diff = (row - col).astype(jnp.float32)
        M = jnp.where(diff >= 0, jnp.exp(-sl * jnp.maximum(diff, 0.0)), 0.0)
        a = lax.dot_general(qc, kc, (((1,), (1,)), ((), ())), precision=_HI,
                            preferred_element_type=jnp.float32) * M
        o = lax.dot_general(a, vc, (((1,), (0,)), ((), ())), precision=_HI,
                            preferred_element_type=jnp.float32)
        ti = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        into = jnp.exp(-sl * (ti + 1).astype(jnp.float32))
        o = o + lax.dot_general(qc, S[...], (((1,), (0,)), ((), ())),
                                precision=_HI,
                                preferred_element_type=jnp.float32) * into
        o_ref[...] = o.astype(o_ref.dtype)
        left = nv - 1 - ti
        w = jnp.where(left >= 0,
                      jnp.exp(-sl * jnp.maximum(left, 0).astype(jnp.float32)),
                      0.0)
        keep = jnp.exp(jnp.zeros((1, d), jnp.float32)
                       - sl * nv.astype(jnp.float32))
        S[...] = keep * S[...] + lax.dot_general(
            kc * w, vc, (((0,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)

        @pl.when(c == nc - 1)
        def _():
            s_out[...] = S[...]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tok = pl.BlockSpec((None, C, d), lambda r, h, c: (r, c, h))
    st = pl.BlockSpec((None, None, d, d), lambda r, h, c: (r, h, 0, 0))
    o, S = pl.pallas_call(
        kernel, grid=(n, H, nc),
        in_specs=[smem, smem, tok, tok, tok, st],
        out_specs=[tok, st],
        out_shape=[jax.ShapeDtypeStruct((n, s, H * d), jnp.float32),
                   jax.ShapeDtypeStruct((n, H, d, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=PREFILL_KERNEL, interpret=interpret,
    )(jnp.asarray(slopes(H)), nvalid.astype(jnp.int32), flat(q), flat(k),
      flat(v), state.astype(jnp.float32))
    return o.reshape(n, s, H, d), S


def lightning_prefill(q, k, v, state, nvalid, *, chunk: int = 256):
    """The Mosaic kernel on a TPU (or under ``FLAGS_pallas_interpret``)
    where a head is whole 128-lane registers and a chunk whole sublanes,
    the ``jnp`` path elsewhere. As :func:`lightning_prefill_reference`."""
    from paddle_tpu.ops import pallas_mode
    s, d = q.shape[1], q.shape[3]
    on, interp = pallas_mode()
    if on and d % 128 == 0 and chunk_size(s, chunk) % 8 == 0:
        return _lightning_prefill_pallas(q, k, v, state, nvalid, chunk=chunk,
                                         interpret=interp)
    return lightning_prefill_reference(q, k, v, state, nvalid, chunk=chunk)


# ----------------------------------------------------------------- decode
def lightning_decode_reference(q, k, v, state, active, *, layer: int,
                               state_dtype=jnp.float32):
    """q, k, v (b, H, d); state (layers, b, H, d, d) float32; active (b,)
    bool -> (o (b, H, d) float32, state with layer ``layer`` updated on
    the active rows)."""
    H, d = q.shape[1:]
    lam = jnp.exp(-jnp.asarray(slopes(H)))[None, :, None, None]
    f32 = lambda a: a.astype(jnp.float32)
    S = state[layer]
    S2 = lam * S + f32(k)[..., :, None] * f32(v)[..., None, :]
    S2 = S2.astype(state_dtype).astype(jnp.float32)
    o = jnp.einsum("bhd,bhde->bhe", f32(q) / math.sqrt(d), S2, precision=_HI)
    S2 = jnp.where(active[:, None, None, None], S2, S)
    return o, state.at[layer].set(S2)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _lightning_decode_pallas(q, k, v, state, active, *, layer: int,
                             interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, H, d = q.shape
    hb = 8 if H % 8 == 0 else H
    f32 = lambda a: a.astype(jnp.float32)
    # a head's q and k as columns (d, 1): heads on the lanes
    cols = lambda a: jnp.swapaxes(f32(a).reshape(b, H // hb, hb, d), 2, 3)

    def kernel(lam_ref, act_ref, qt_ref, kt_ref, v_ref, s_in, o_ref, s_out):
        r, g = pl.program_id(0), pl.program_id(1)
        act = act_ref[r] != 0
        on = jnp.where(act, 1.0, 0.0)
        for hh in range(hb):
            lam = jnp.where(act, lam_ref[g * hb + hh], 1.0)
            S = s_in[hh]
            S2 = lam * S + (kt_ref[:, hh:hh + 1] * on) * v_ref[hh:hh + 1, :]
            s_out[hh] = S2
            o_ref[hh:hh + 1, :] = jnp.sum(qt_ref[:, hh:hh + 1] * S2, axis=0,
                                          keepdims=True)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    col = pl.BlockSpec((None, None, d, hb), lambda r, g: (r, g, 0, 0))
    row = pl.BlockSpec((None, hb, d), lambda r, g: (r, g, 0))
    st = pl.BlockSpec((None, None, hb, d, d),
                      lambda r, g: (layer, r, g, 0, 0))
    o, state = pl.pallas_call(
        kernel, grid=(b, H // hb),
        in_specs=[smem, smem, col, col, row, st],
        out_specs=[row, st],
        out_shape=[jax.ShapeDtypeStruct((b, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name=DECODE_KERNEL, interpret=interpret,
    )(jnp.exp(-jnp.asarray(slopes(H))), active.astype(jnp.int32),
      cols(q) / math.sqrt(d), cols(k), f32(v), state)
    return o, state


def lightning_decode(q, k, v, state, active, *, layer: int):
    """The Mosaic kernel on a TPU (or under ``FLAGS_pallas_interpret``)
    where a head is whole 128-lane registers, the ``jnp`` path
    elsewhere. As :func:`lightning_decode_reference`."""
    from paddle_tpu.ops import pallas_mode
    on, interp = pallas_mode()
    if on and q.shape[-1] % 128 == 0 and state.dtype == jnp.float32:
        return _lightning_decode_pallas(q, k, v, state, active, layer=layer,
                                        interpret=interp)
    return lightning_decode_reference(q, k, v, state, active, layer=layer)
