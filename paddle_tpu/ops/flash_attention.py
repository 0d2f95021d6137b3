"""Flash attention — XLA path + Pallas TPU kernels (forward AND backward).

Reference: phi flash_attn kernel wrapping the vendored flash-attention-2 CUDA
library (paddle/phi/kernels/gpu/flash_attn_kernel.cu, cmake/external/
flashattn.cmake; python veneer paddle.nn.functional.flash_attention).

Layouts follow the reference: q/k/v are (batch, seq, num_heads, head_dim).
GQA/MQA supported via num_kv_heads < num_heads. The Pallas path (blockwise
online-softmax, fp32 accumulators, causal block skipping, LSE saved for the
backward; dq and dk/dv backward kernels recompute probabilities per block so
the (s, s) matrix is never materialized) covers, on TPU:

* self-attention AND cross-attention (sq != sk, causal aligned bottom-right
  like the reference / flash-attn-2),
* per-batch KV valid lengths (`kv_lens` — the padding-mask form the CUDA
  kernel takes via cu_seqlens),
* segment ids (`segment_ids` / `kv_segment_ids` — packed-sequence masking,
  the TPU-native equivalent of flash_attn_unpadded's varlen batches),
* causal sliding windows (`window_size` — Mistral-style, with k-block
  skipping on both ends) and ALiBi (`alibi_slopes` — per-head linear
  bias applied inside the online softmax),
* odd head dims / short cross-KV via zero-padding (`_pad_for_kernel`),
* ARBITRARY DENSE MASKS (`attn_mask` (b|1, h|1, sq, sk), bool or
  additive float) — streamed as (blk_q, blk_k) tiles with all-masked
  prefix/suffix block skipping (`_mask_block_bounds`),
* IN-KERNEL ATTENTION DROPOUT — counter-based PRNG keyed on
  (seed, b, h, q-block, k-block) so the backward kernels regenerate the
  exact forward mask (`_dropout_keep`; the vendored flash-attn-2 does
  dropout in-kernel the same way),

forward and backward — the kernel-surface exclusion list is now EMPTY.
Kernels compute internally in (b, h, s, d) so the trailing block dims
meet TPU tiling (8, 128).
"""

import contextlib
import functools
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG_INF = -1e30
LANES = 128


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def _structured_mask(sq, sk, is_causal, kv_lens, seg_q, seg_k,
                     window=None):
    """Dense (b, 1, sq, sk) or (1, 1, sq, sk) bool mask for the XLA path."""
    masks = []
    if is_causal:
        masks.append(jnp.tril(jnp.ones((sq, sk), bool),
                              k=sk - sq)[None, None])
    if window is not None:
        # sliding window (bottom-right aligned): q row i sees the last
        # `window` keys up to i + (sk - sq)
        dist = ((jnp.arange(sq)[:, None] + (sk - sq))
                - jnp.arange(sk)[None, :])
        masks.append((dist < window)[None, None])
    if kv_lens is not None:
        masks.append((jnp.arange(sk)[None, :] <
                      kv_lens[:, None])[:, None, None, :])
    if seg_q is not None:
        masks.append((seg_q[:, :, None] ==
                      seg_k[:, None, :])[:, None])
    if not masks:
        return None
    m = masks[0]
    for extra in masks[1:]:
        m = m & extra
    return m


def _xla_attention(q, k, v, attn_mask=None, is_causal=False, scale=None,
                   dropout_p=0.0, training=True, kv_lens=None,
                   seg_q=None, seg_k=None, window=None, alibi_slopes=None):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # (b, h, sq, sk) scores in fp32 (f64 under x64 — keeps numeric-grad
    # checks meaningful)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.promote_types(
                            q.dtype, jnp.float32)) * scale
    if alibi_slopes is not None:
        dist = (jnp.arange(sk)[None, :]
                - (jnp.arange(sq)[:, None] + (sk - sq)))
        scores = scores + (alibi_slopes.astype(scores.dtype)[None, :, None,
                                                             None]
                           * dist.astype(scores.dtype)[None, None])
    structured = _structured_mask(sq, sk, is_causal, kv_lens, seg_q, seg_k,
                                  window=window)
    if structured is not None:
        scores = jnp.where(structured, scores, NEG_INF)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, NEG_INF)
        else:
            scores = scores + attn_mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if structured is not None and (kv_lens is not None or seg_q is not None
                                   or sk < sq):
        # fully-masked rows emit 0 (flash-attn-2 convention; the Pallas
        # kernels match) instead of softmax's uniform garbage. Plain causal
        # self-attention can't produce empty rows — skip the extra pass.
        probs = jnp.where(structured.any(-1, keepdims=True), probs, 0.0)
    if dropout_p > 0.0 and training:
        from paddle_tpu.core import rng as _rng
        key = _rng.next_rng_key("dropout")
        keep = 1.0 - dropout_p
        mask = jax.random.bernoulli(key, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0).astype(probs.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)


def flash_attention(q, k, v, dropout=0.0, causal=False, attn_mask=None,
                    training=True, scale=None, kv_lens=None,
                    segment_ids=None, kv_segment_ids=None,
                    window_size=None, alibi_slopes=None):
    """paddle.nn.functional.flash_attention parity. Returns (out, None)."""
    out = scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=dropout, is_causal=causal,
        training=training, scale=scale, kv_lens=kv_lens,
        segment_ids=segment_ids, kv_segment_ids=kv_segment_ids,
        window_size=window_size, alibi_slopes=alibi_slopes)
    return out, None


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None,
                                 kv_lens=None, segment_ids=None,
                                 kv_segment_ids=None, window_size=None,
                                 alibi_slopes=None):
    """Attention with the fused-kernel dispatch.

    TPU-native extensions beyond the reference veneer: `kv_lens` (b,) valid
    KV lengths (padding mask), `segment_ids` (b, sq) / `kv_segment_ids`
    (b, sk) packed-sequence masks (attention only within equal ids),
    `window_size` (int — causal sliding window, Mistral-style: each query
    sees the last `window_size` keys) and `alibi_slopes` ((num_heads,)
    fp32 — ALiBi linear bias, score += slope·(k_pos − q_pos)). All run
    inside the Pallas kernels forward AND backward; on other backends
    they lower to dense masks/bias on the XLA path.

    Float `attn_mask` caveat — the ≤ −1e9 "effectively masked" threshold:
    the Pallas path treats additive-mask entries ≤ −1e9 as FULLY masked
    (`_mask_block_bounds` skips blocks whose entries are all below it, and
    such scores never survive the online softmax). Use ≤ −1e9 (or −inf)
    to mean "masked", and keep finite soft penalties (score biases you
    want softmax to weigh) well above it. CONCRETE masks holding finite
    entries at or below the threshold that are not −inf (e.g. a −1e10
    soft penalty) are routed to the XLA path automatically so the two
    backends agree; a TRACED mask (built inside jit) can't be inspected,
    so there the threshold convention above is on the caller.
    """
    from paddle_tpu.ops import use_pallas
    seg_q = segment_ids
    seg_k = kv_segment_ids if kv_segment_ids is not None else segment_ids
    if (seg_q is None) != (seg_k is None):
        raise ValueError("segment_ids and kv_segment_ids must be given "
                         "together (or segment_ids alone when sq == sk)")
    if (segment_ids is not None and kv_segment_ids is None
            and q.shape[1] != k.shape[1]):
        raise ValueError(
            "segment_ids alone requires sq == sk; pass kv_segment_ids "
            f"explicitly for cross-attention (sq={q.shape[1]}, "
            f"sk={k.shape[1]})")
    if window_size is not None:
        window_size = int(window_size)
        if not is_causal:
            raise ValueError("window_size requires is_causal=True "
                             "(causal sliding window)")
        if window_size < 1:
            raise ValueError(f"window_size must be >= 1, got {window_size}")
    if alibi_slopes is not None:
        if not is_causal:
            raise ValueError(
                "alibi_slopes requires is_causal=True (the ALiBi bias is "
                "defined over causal distances; a non-causal form would "
                "reward distant FUTURE keys)")
        # slopes are fixed constants in the ALiBi formulation (a geometric
        # head schedule, not learned) — stop_gradient keeps the Pallas and
        # XLA paths consistent (the kernels do not compute dL/dslopes)
        alibi_slopes = jax.lax.stop_gradient(
            jnp.asarray(alibi_slopes, jnp.float32))
        if alibi_slopes.shape != (q.shape[2],):
            raise ValueError(
                f"alibi_slopes must be (num_heads,)=({q.shape[2]},), got "
                f"{alibi_slopes.shape}")
    # Pallas path: TPU, seq dims multiples of 128 and long enough to beat
    # XLA. Shapes the kernel can't take directly may still ride it via
    # _pad_for_kernel (odd head dims, short cross-KV). Round 5 closed the
    # last two kernel-surface gaps: ARBITRARY DENSE MASKS ((b|1, h|1, sq,
    # sk) bool or additive float, streamed as tiles with all-masked-block
    # skipping) and IN-KERNEL ATTENTION DROPOUT (counter-based PRNG keyed
    # on (seed, b, h, q-block, k-block), identical fwd/bwd masks).
    eff_dropout = float(dropout_p) if training else 0.0
    kmask = _kernel_mask(attn_mask, q.shape, k.shape)
    pallas_ok = use_pallas() and (attn_mask is None or kmask is not None)
    if (pallas_ok and kmask is not None
            and jnp.issubdtype(kmask.dtype, jnp.floating)
            and not isinstance(kmask, jax.core.Tracer)):
        # Finite soft penalties at/below the −1e9 "effectively masked"
        # threshold (e.g. −1e10) would be block-skipped EXACTLY on the
        # Pallas path but only exponentially suppressed by XLA's softmax.
        # A concrete mask can be inspected: route such masks to the XLA
        # path so the backends agree (−inf means "masked" and stays
        # kernel-eligible). The reduction runs ON DEVICE — only the bool
        # verdict syncs to host, not the (b, h, sq, sk) mask itself —
        # and the verdict is CACHED per mask object, so only the first
        # eager call with a given mask pays it (under jit the whole
        # branch traces once; r5 item flagged by the PR 3 review).
        # tpu-lint: allow(traced-branch): guarded by the Tracer
        # isinstance above — this branch only runs on CONCRETE masks
        if _float_mask_probe(attn_mask, kmask):
            pallas_ok = False
    if pallas_ok:
        padded = _pad_for_kernel(q, k, v, is_causal, scale, kv_lens, seg_k)
        if padded is not None:
            qp, kp, vp, scale_p, klp, skp, hd = padded
            if kmask is not None and kp.shape[1] != kmask.shape[3]:
                pad_v = False if kmask.dtype == jnp.int8 else 0.0
                kmask = jnp.pad(
                    kmask, ((0, 0), (0, 0), (0, 0),
                            (0, kp.shape[1] - kmask.shape[3])),
                    constant_values=pad_v)   # pad cols masked via kv_lens
            out = _flash_call(qp, kp, vp, is_causal, scale_p, klp,
                              seg_q, skp, window=window_size,
                              alibi_slopes=alibi_slopes, mask=kmask,
                              dropout_p=eff_dropout)
            return out if out.shape[-1] == hd else out[..., :hd]
    return _xla_attention(q, k, v, attn_mask=attn_mask, is_causal=is_causal,
                          scale=scale, dropout_p=dropout_p,
                          training=training, kv_lens=kv_lens,
                          seg_q=seg_q, seg_k=seg_k, window=window_size,
                          alibi_slopes=alibi_slopes)


# verdict cache for the eager concrete-float-mask probe, keyed by the
# id() of the USER-PASSED mask object with a weakref guard: the guard
# proves the id still names the same live array (a dead entry is removed
# by the weakref callback during dealloc, before the id can be reused,
# and `ref() is mask` re-checks anyway). Only IMMUTABLE jax.Arrays are
# cached — a numpy mask can be written in place between calls, which
# would make a cached verdict silently stale. Bounded by mask lifetimes,
# not call count — serving loops reuse one mask array across thousands
# of eager calls and now pay the full-mask reduction + host sync once.
_float_mask_verdicts = {}


def _float_mask_probe(attn_mask, kmask) -> bool:
    """True when the concrete float mask holds finite entries at/below
    the −1e9 threshold (not −inf) — i.e. must route to the XLA path."""
    import weakref

    cacheable = isinstance(attn_mask, jax.Array) \
        and not isinstance(attn_mask, jax.core.Tracer)
    mid = id(attn_mask)
    if cacheable:
        entry = _float_mask_verdicts.get(mid)
        if entry is not None and entry[0]() is attn_mask:
            return entry[1]
    # tpu-lint: allow(host-sync): deliberate one-time sync — only the
    # bool verdict crosses to host, cached per mask object (weakref)
    verdict = bool(jnp.any((kmask <= -1e9) & ~jnp.isneginf(kmask)))
    if not cacheable:
        return verdict
    try:
        ref = weakref.ref(attn_mask,
                          lambda _r, _i=mid: _float_mask_verdicts.pop(_i,
                                                                      None))
    except TypeError:        # array type without weakref support
        return verdict
    _float_mask_verdicts[mid] = (ref, verdict)
    return verdict


def _kernel_mask(attn_mask, q_shape, k_shape):
    """Canonicalize a dense attn_mask for the kernels: 4-D with
    broadcastable batch/head dims and exact (sq, sk) trailing dims.
    bool masks become int8 (Mosaic has no bool operands); additive float
    masks pass through. Returns None when the shape can't ride."""
    if attn_mask is None:
        return None
    m = jnp.asarray(attn_mask)
    if m.ndim == 2:
        m = m[None, None]
    elif m.ndim == 3:
        m = m[:, None]
    if m.ndim != 4:
        return None
    b, sq, h = q_shape[0], q_shape[1], q_shape[2]
    sk = k_shape[1]
    if m.shape[2:] != (sq, sk):
        return None
    if m.shape[0] not in (1, b) or m.shape[1] not in (1, h):
        return None
    if m.dtype == jnp.bool_:
        return m.astype(jnp.int8)
    if jnp.issubdtype(m.dtype, jnp.floating):
        return m.astype(jnp.float32)
    return None


def _pad_for_kernel(q, k, v, is_causal, scale, kv_lens, seg_k):
    """Kernel-eligible (q, k, v, scale, kv_lens, seg_k, orig_hd), padding
    where needed — or None when the shape can't ride the kernel.

    Odd head_dims (SD-1.5's 40/80/160) zero-pad to the next supported lane
    width — exact: zero q/k lanes add 0 to every score and the v pad lanes
    are sliced away by the caller. Short cross-attention KV (e.g. 77 text
    tokens) pads to the next 128 block with kv_lens masking (pad seg ids
    get -1, matching no query segment). Causal with a padded KV is
    excluded (the bottom-right alignment would shift)."""
    hd = q.shape[-1]
    sk = k.shape[1]
    hd_t = hd if hd in (64, 128, 256) else next(
        (t for t in (64, 128, 256) if t >= hd), None)
    sk_t = -(-sk // 128) * 128
    if (hd_t is None or not _pallas_seq_ok(q.shape[1], sk_t)
            or (is_causal and sk_t != sk)):
        return None
    if hd_t == hd and sk_t == sk:
        return q, k, v, scale, kv_lens, seg_k, hd
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if sk_t != sk:
        kv_lens = (jnp.full((q.shape[0],), sk, jnp.int32)
                   if kv_lens is None else jnp.minimum(kv_lens, sk))
        if seg_k is not None:
            seg_k = jnp.pad(seg_k, ((0, 0), (0, sk_t - sk)),
                            constant_values=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, hd_t - hd)))
    pad_kv = ((0, 0), (0, sk_t - sk), (0, 0), (0, hd_t - hd))
    return q, jnp.pad(k, pad_kv), jnp.pad(v, pad_kv), scale, kv_lens, \
        seg_k, hd


# ---- Pallas kernels (internal layout (b, h, s, d)) -------------------------

def _pick_blk(s):
    """Largest block in (512, 256, 128) dividing s — lets the kernels
    cover any s % 128 == 0, not just 512-multiples."""
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    raise ValueError(f"seq {s} not a multiple of 128")


def _causal_nk(qi, blk_q, blk_k, off, sk):
    """Number of k-blocks a causal q-block attends to (bottom-right
    aligned: q row i sees k cols <= i + off)."""
    hi = qi * blk_q + blk_q - 1 + off          # last visible k col
    return jnp.clip((hi // blk_k) + 1, 0, sk // blk_k)


def _block_mask(s_blk, qi, ki, blk_q, blk_k, off, is_causal,
                kvlen_b, segq_blk, segk_ref, window=None, alibi=None,
                mask_at=None):
    """Apply the structured masks to one (blk_q, blk_k) score block.

    kvlen_b: scalar valid length or None; segq_blk: (blk_q, 1) ids or
    None; segk_ref: callable ki -> (1, blk_k) ids; window: static int
    sliding-window width (causal: q row i sees the last `window` keys up
    to i + off); alibi: this head's ALiBi slope (traced fp32 scalar) —
    score += slope · (k_pos − q_pos − off), the standard ≤ 0 linear bias;
    mask_at: callable ki -> (blk_q, blk_k) DENSE mask tile — bool (False
    = masked) or additive float (the reference attn_mask semantics)."""
    k_pos = ki * blk_k + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    if is_causal or window is not None or alibi is not None:
        q_pos = qi * blk_q + lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0)
    if alibi is not None:
        s_blk = s_blk + alibi * (k_pos - q_pos - off).astype(jnp.float32)
    if is_causal:
        s_blk = jnp.where(q_pos + off >= k_pos, s_blk, NEG_INF)
    if window is not None:
        s_blk = jnp.where(q_pos + off - k_pos < window, s_blk, NEG_INF)
    if kvlen_b is not None:
        s_blk = jnp.where(k_pos < kvlen_b, s_blk, NEG_INF)
    if segq_blk is not None:
        s_blk = jnp.where(segq_blk == segk_ref(ki), s_blk, NEG_INF)
    if mask_at is not None:
        mb = mask_at(ki)
        if mb.dtype in (jnp.bool_, jnp.int8):   # bool masks ride as int8
            s_blk = jnp.where(mb != 0, s_blk, NEG_INF)
        else:
            s_blk = s_blk + mb.astype(jnp.float32)
    return s_blk


def _dropout_keep(pltpu, seed_ref, block_id, blk_q, blk_k, keep_p):
    """Counter-based in-kernel dropout mask for one (qi, ki) score block
    (the vendored flash-attn-2 does dropout in-kernel the same way —
    canonical phi/kernels/gpu/flash_attn_kernel.cu). Reseeding the Mosaic
    PRNG on (seed, block_id) — block_id folds (b, h, q-block, k-block)
    into one int32, Mosaic's prng_seed takes at most two values — makes
    the mask a pure function of the block coordinates, so the dq (loops
    ki per qi) and dk/dv (loops qi per ki) backward kernels regenerate
    the exact forward mask regardless of their iteration order."""
    pltpu.prng_seed(seed_ref[0], block_id)
    bits = pltpu.bitcast(pltpu.prng_random_bits((blk_q, blk_k)),
                         jnp.uint32)
    return bits < jnp.uint32(min(int(keep_p * 4294967296.0), 4294967295))


def _drop_block_id(seed_ref, bi, hi, qi, ki, nq, nk):
    """(b, h, q-block, k-block) as one int32, with b and h counted in the
    WHOLE call: seed_ref is (seed, first row, first head, heads), so one
    shard of a partitioned call (`partitioned`) draws exactly the masks
    the unpartitioned kernel draws for its rows and heads."""
    return (((bi + seed_ref[1]) * seed_ref[3] + hi + seed_ref[2]) * nq
            + qi) * nk + ki


def _mask_block_bounds(mask, b, h, nq, nk, blk_q, blk_k, axis_q=True):
    """Per-(b, h, row-block) [lo, hi) k-block bounds (or per-k-block q
    bounds when axis_q=False) for all-masked-block SKIPPING: prefix and
    suffix blocks with no unmasked entry are never touched. Returns two
    (b, h, n) int32 arrays (broadcast dims expanded)."""
    valid = (mask != 0) if mask.dtype in (jnp.bool_, jnp.int8) \
        else (mask > -1e9)
    mb, mh = valid.shape[0], valid.shape[1]
    blocks = valid.reshape(mb, mh, nq, blk_q, nk, blk_k).any(axis=(3, 5))
    if not axis_q:
        blocks = jnp.swapaxes(blocks, 2, 3)       # (mb, mh, nk, nq)
    n = blocks.shape[3]
    has = blocks.any(-1)
    lo = jnp.where(has, jnp.argmax(blocks, -1), 0).astype(jnp.int32)
    hi = jnp.where(has, n - jnp.argmax(blocks[..., ::-1], -1),
                   0).astype(jnp.int32)
    tgt = (b, h, blocks.shape[2])
    return (jnp.broadcast_to(lo, tgt), jnp.broadcast_to(hi, tgt))


def _window_k0(qi, blk_q, blk_k, off, window):
    """First k-block a sliding-window q-block can see (block skipping):
    q row q_pos attends k in (q_pos + off − window, q_pos + off]."""
    lo = qi * blk_q + off - window + 1          # first visible k col
    return jnp.clip(lo // blk_k, 0, None)


def _seg_specs():
    """Builder for (b, 1, s) segment-id BlockSpecs: spec(blk, full) blocks
    the axis by `blk` indexed by the grid's third dim, or takes the whole
    `full` axis when blk is None."""
    from jax.experimental import pallas as pl

    def spec(blk, full):
        if blk is None:
            return pl.BlockSpec((None, 1, full),
                                lambda bi, hi, i: (bi, 0, 0))
        return pl.BlockSpec((None, 1, blk), lambda bi, hi, i: (bi, 0, i))

    return spec


def _build_operands(qt, kt, vt, kv_lens, seg_q, seg_k, extra,
                    alibi_slopes=None, mask=None, bounds=None, seed=None):
    """Shared operand assembly: [q, k, v, (lens), (segq, segk), (alibi),
    (mask, lo, hi), (seed)] + extra."""
    ops = [qt, kt, vt]
    if kv_lens is not None:
        ops.append(kv_lens.astype(jnp.int32))
    if seg_q is not None:
        ops.append(seg_q.astype(jnp.int32)[:, None])   # (b, 1, sq)
        ops.append(seg_k.astype(jnp.int32)[:, None])   # (b, 1, sk)
    if alibi_slopes is not None:
        ops.append(alibi_slopes.astype(jnp.float32))   # (h,)
    if mask is not None:
        ops.append(mask)                               # (mb, mh, sq, sk)
        ops.extend(bounds)                             # lo, hi (b, h, n)
    if seed is not None:
        ops.append(seed)            # (4,) int32, _drop_block_id
    return ops + extra


def _mask_specs(pl, pltpu, mask, blk_row, full_col, row_axis_q=True):
    """BlockSpecs for [mask-tile, lo, hi]: the mask streams one
    (blk_q, sk) row band (or (sq, blk_k) column band for the dkv kernel)
    per grid step, broadcast dims pinned by index-map clamping; the lo/hi
    skip bounds ride SMEM whole."""
    mb, mh = mask.shape[0], mask.shape[1]

    def imap(bi, hi, i):
        bm = jnp.minimum(bi, mb - 1)
        hm = jnp.minimum(hi, mh - 1)
        return (bm, hm, i, 0) if row_axis_q else (bm, hm, 0, i)

    shape = ((None, None, blk_row, full_col) if row_axis_q
             else (None, None, full_col, blk_row))
    return [pl.BlockSpec(shape, imap),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM)]


def _fwd_kernels(qt, kt, vt, is_causal, sc, kv_lens=None, seg_q=None,
                 seg_k=None, window=None, alibi_slopes=None, mask=None,
                 dropout_p=0.0, seed=None):
    """qt (b,h,sq,d), kt/vt (b,h,sk,d) → (out (b,h,sq,d), lse (b,h,sq)).

    mask: dense (mb, mh, sq, sk) bool/float attn_mask (broadcast dims
    allowed) streamed as (blk_q, sk) row bands, with all-masked prefix/
    suffix k-blocks skipped. dropout_p/seed: in-kernel counter-based
    attention dropout (see _dropout_keep) — probabilities drop AFTER the
    softmax statistics accumulate, matching standard dropout(softmax(s))
    semantics; the output folds the 1/keep rescale into the final
    normalization."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    blk_q = _pick_blk(sq)
    blk_k = _pick_blk(sk)
    off = sk - sq
    grid = (b, h, sq // blk_q)
    has_len = kv_lens is not None
    has_seg = seg_q is not None
    has_alibi = alibi_slopes is not None
    has_mask = mask is not None
    has_drop = dropout_p > 0.0
    keep_p = 1.0 - dropout_p
    bounds = (_mask_block_bounds(mask, b, h, sq // blk_q, sk // blk_k,
                                 blk_q, blk_k) if has_mask else None)

    def kernel(*refs):
        i = 3
        lens_ref = refs[i] if has_len else None
        i += has_len
        segq_ref = refs[i] if has_seg else None
        segk_ref = refs[i + 1] if has_seg else None
        i += 2 * has_seg
        slopes_ref = refs[i] if has_alibi else None
        i += has_alibi
        mask_ref = refs[i] if has_mask else None
        mlo_ref = refs[i + 1] if has_mask else None
        mhi_ref = refs[i + 2] if has_mask else None
        i += 3 * has_mask
        seed_ref = refs[i] if has_drop else None
        i += has_drop
        q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
        o_ref, lse_ref = refs[i], refs[i + 1]

        bi = pl.program_id(0)
        hi_ = pl.program_id(1)
        qi = pl.program_id(2)
        qv = q_ref[...].astype(jnp.float32) * sc  # (blk_q, d)
        kvlen_b = lens_ref[bi] if has_len else None
        alibi = slopes_ref[hi_] if has_alibi else None
        segq_blk = (jnp.transpose(segq_ref[...], (1, 0))
                    if has_seg else None)          # (blk_q, 1)
        seg_at = (lambda ki: segk_ref[:, pl.ds(ki * blk_k, blk_k)]) \
            if has_seg else None
        mask_at = (lambda ki: mask_ref[:, pl.ds(ki * blk_k, blk_k)]) \
            if has_mask else None

        def body(ki, carry):
            acc, m_prev, l_prev = carry
            kv = k_ref[pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
            vv = v_ref[pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
            s_blk = qv @ kv.T  # (blk_q, blk_k)
            s_blk = _block_mask(s_blk, qi, ki, blk_q, blk_k, off,
                                is_causal, kvlen_b, segq_blk, seg_at,
                                window=window, alibi=alibi,
                                mask_at=mask_at)
            m_cur = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1))
            alpha = jnp.exp(m_prev - m_cur)
            # rows with no valid entry yet keep m at NEG_INF — their p
            # must be 0, not exp(0), so fully-masked rows emit 0
            p = jnp.where(m_cur[:, None] <= NEG_INF * 0.5, 0.0,
                          jnp.exp(s_blk - m_cur[:, None]))
            l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
            if has_drop:   # l accumulates UNdropped p (flash-attn-2)
                p = jnp.where(
                    _dropout_keep(pltpu, seed_ref,
                                  _drop_block_id(seed_ref, bi, hi_, qi, ki,
                                                 sq // blk_q, sk // blk_k),
                                  blk_q, blk_k, keep_p), p, 0.0)
            acc = acc * alpha[:, None] + p @ vv
            return acc, m_cur, l_cur

        acc0 = jnp.zeros((blk_q, d), jnp.float32)
        m0 = jnp.full((blk_q,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((blk_q,), jnp.float32)
        n_k = _causal_nk(qi, blk_q, blk_k, off, sk) if is_causal \
            else sk // blk_k
        if has_len:   # skip k-blocks entirely past the valid length
            n_k = jnp.minimum(n_k, (kvlen_b + blk_k - 1) // blk_k)
        k0 = _window_k0(qi, blk_q, blk_k, off, window) if window else 0
        if has_mask:  # all-masked prefix/suffix block skipping
            k0 = jnp.maximum(k0, mlo_ref[bi, hi_, qi])
            n_k = jnp.minimum(n_k, mhi_ref[bi, hi_, qi])
        acc, m, l = lax.fori_loop(k0, n_k, body, (acc0, m0, l0))
        lsafe = jnp.where(l == 0.0, 1.0, l)
        norm = lsafe * keep_p if has_drop else lsafe
        o_ref[...] = (acc / norm[:, None]).astype(o_ref.dtype)
        # TPU tiling wants 2-D trailing blocks: replicate lse across lanes
        lse_ref[...] = jnp.broadcast_to((m + jnp.log(lsafe))[:, None],
                                        (qv.shape[0], LANES))

    qspec = pl.BlockSpec((None, None, blk_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0))
    kfull = lambda: pl.BlockSpec((None, None, sk, d),
                                 lambda bi, hi, qi: (bi, hi, 0, 0))
    in_specs = [qspec, kfull(), kfull()]
    if has_len:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_seg:
        spec = _seg_specs()
        in_specs += [spec(blk_q, sq), spec(None, sk)]
    if has_alibi:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_mask:
        in_specs += _mask_specs(pl, pltpu, mask, blk_q, sk)
    if has_drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, blk_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, blk_q, LANES),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
        ],
        name="flash_attention_fwd",
    )(*_build_operands(qt, kt, vt, kv_lens, seg_q, seg_k, [],
                       alibi_slopes=alibi_slopes, mask=mask, bounds=bounds,
                       seed=seed))
    return out, lse


def _bwd_dq_kernel(qt, kt, vt, dot, lse, delta, is_causal, sc,
                   kv_lens=None, seg_q=None, seg_k=None, window=None,
                   alibi_slopes=None, mask=None, dropout_p=0.0, seed=None):
    """dq: loop over k-blocks for each q-block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    blk_q = _pick_blk(sq)
    blk_k = _pick_blk(sk)
    off = sk - sq
    grid = (b, h, sq // blk_q)
    has_len = kv_lens is not None
    has_seg = seg_q is not None
    has_alibi = alibi_slopes is not None
    has_mask = mask is not None
    has_drop = dropout_p > 0.0
    keep_p = 1.0 - dropout_p
    bounds = (_mask_block_bounds(mask, b, h, sq // blk_q, sk // blk_k,
                                 blk_q, blk_k) if has_mask else None)

    def kernel(*refs):
        i = 3
        lens_ref = refs[i] if has_len else None
        i += has_len
        segq_ref = refs[i] if has_seg else None
        segk_ref = refs[i + 1] if has_seg else None
        i += 2 * has_seg
        slopes_ref = refs[i] if has_alibi else None
        i += has_alibi
        mask_ref = refs[i] if has_mask else None
        mlo_ref = refs[i + 1] if has_mask else None
        mhi_ref = refs[i + 2] if has_mask else None
        i += 3 * has_mask
        seed_ref = refs[i] if has_drop else None
        i += has_drop
        q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
        do_ref, lse_ref, dl_ref, dq_ref = refs[i:i + 4]

        bi = pl.program_id(0)
        hi_ = pl.program_id(1)
        qi = pl.program_id(2)
        qv = q_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)          # (blk_q, d)
        lse_q = lse_ref[...][:, 0]                    # (blk_q,)
        delta_q = dl_ref[...][:, 0]                   # (blk_q,)
        kvlen_b = lens_ref[bi] if has_len else None
        alibi = slopes_ref[hi_] if has_alibi else None
        segq_blk = (jnp.transpose(segq_ref[...], (1, 0))
                    if has_seg else None)
        seg_at = (lambda ki: segk_ref[:, pl.ds(ki * blk_k, blk_k)]) \
            if has_seg else None
        mask_at = (lambda ki: mask_ref[:, pl.ds(ki * blk_k, blk_k)]) \
            if has_mask else None

        def body(ki, dq_acc):
            kv = k_ref[pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
            vv = v_ref[pl.ds(ki * blk_k, blk_k), :].astype(jnp.float32)
            s_blk = (qv @ kv.T) * sc
            s_blk = _block_mask(s_blk, qi, ki, blk_q, blk_k, off,
                                is_causal, kvlen_b, segq_blk, seg_at,
                                window=window, alibi=alibi,
                                mask_at=mask_at)
            p = jnp.where(lse_q[:, None] <= NEG_INF * 0.5, 0.0,
                          jnp.exp(s_blk - lse_q[:, None]))
            dp = do @ vv.T                            # (blk_q, blk_k)
            if has_drop:   # regenerate the forward's block mask
                dp = jnp.where(
                    _dropout_keep(pltpu, seed_ref,
                                  _drop_block_id(seed_ref, bi, hi_, qi, ki,
                                                 sq // blk_q, sk // blk_k),
                                  blk_q, blk_k, keep_p),
                    dp * (1.0 / keep_p), 0.0)
            ds = p * (dp - delta_q[:, None])
            return dq_acc + (ds @ kv) * sc

        n_k = _causal_nk(qi, blk_q, blk_k, off, sk) if is_causal \
            else sk // blk_k
        if has_len:
            n_k = jnp.minimum(n_k, (kvlen_b + blk_k - 1) // blk_k)
        k0 = _window_k0(qi, blk_q, blk_k, off, window) if window else 0
        if has_mask:
            k0 = jnp.maximum(k0, mlo_ref[bi, hi_, qi])
            n_k = jnp.minimum(n_k, mhi_ref[bi, hi_, qi])
        dq = lax.fori_loop(k0, n_k, body,
                           jnp.zeros((blk_q, d), jnp.float32))
        dq_ref[...] = dq.astype(dq_ref.dtype)

    kfull = lambda: pl.BlockSpec((None, None, sk, d),
                                 lambda bi, hi, qi: (bi, hi, 0, 0))
    qblk = lambda: pl.BlockSpec((None, None, blk_q, d),
                                lambda bi, hi, qi: (bi, hi, qi, 0))
    row = lambda: pl.BlockSpec((None, None, blk_q, LANES),
                               lambda bi, hi, qi: (bi, hi, qi, 0))
    in_specs = [qblk(), kfull(), kfull()]
    if has_len:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_seg:
        spec = _seg_specs()
        in_specs += [spec(blk_q, sq), spec(None, sk)]
    if has_alibi:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_mask:
        in_specs += _mask_specs(pl, pltpu, mask, blk_q, sk)
    if has_drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    in_specs += [qblk(), row(), row()]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=qblk(),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
        name="flash_attention_bwd_dq",
    )(*_build_operands(qt, kt, vt, kv_lens, seg_q, seg_k,
                       [dot, lse, delta], alibi_slopes=alibi_slopes,
                       mask=mask, bounds=bounds, seed=seed))


def _bwd_dkv_kernel(qt, kt, vt, dot, lse, delta, is_causal, sc,
                    kv_lens=None, seg_q=None, seg_k=None, window=None,
                    alibi_slopes=None, mask=None, dropout_p=0.0,
                    seed=None):
    """dk, dv: loop over q-blocks for each k-block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    blk_q = _pick_blk(sq)
    blk_k = _pick_blk(sk)
    off = sk - sq
    grid = (b, h, sk // blk_k)
    has_len = kv_lens is not None
    has_seg = seg_q is not None
    has_alibi = alibi_slopes is not None
    has_mask = mask is not None
    has_drop = dropout_p > 0.0
    keep_p = 1.0 - dropout_p
    bounds = (_mask_block_bounds(mask, b, h, sq // blk_q, sk // blk_k,
                                 blk_q, blk_k, axis_q=False)
              if has_mask else None)

    def kernel(*refs):
        i = 3
        lens_ref = refs[i] if has_len else None
        i += has_len
        segq_ref = refs[i] if has_seg else None
        segk_ref = refs[i + 1] if has_seg else None
        i += 2 * has_seg
        slopes_ref = refs[i] if has_alibi else None
        i += has_alibi
        mask_ref = refs[i] if has_mask else None
        mlo_ref = refs[i + 1] if has_mask else None
        mhi_ref = refs[i + 2] if has_mask else None
        i += 3 * has_mask
        seed_ref = refs[i] if has_drop else None
        i += has_drop
        q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
        do_ref, lse_ref, dl_ref, dk_ref, dv_ref = refs[i:i + 5]

        bi = pl.program_id(0)
        hi_ = pl.program_id(1)
        ki = pl.program_id(2)
        kv = k_ref[...].astype(jnp.float32)           # (blk_k, d)
        vv = v_ref[...].astype(jnp.float32)
        kvlen_b = lens_ref[bi] if has_len else None
        alibi = slopes_ref[hi_] if has_alibi else None
        # k-side ids for THIS block, as (1, blk_k); q-side read per block
        segk_blk = segk_ref[...] if has_seg else None
        seg_at = (lambda _ki: segk_blk) if has_seg else None

        def body(qi, carry):
            dk_acc, dv_acc = carry
            qv = q_ref[pl.ds(qi * blk_q, blk_q), :].astype(jnp.float32)
            do = do_ref[pl.ds(qi * blk_q, blk_q), :].astype(jnp.float32)
            lse_q = lse_ref[pl.ds(qi * blk_q, blk_q), 0]
            delta_q = dl_ref[pl.ds(qi * blk_q, blk_q), 0]
            s_blk = (qv @ kv.T) * sc                  # (blk_q, blk_k)
            segq_blk = (jnp.transpose(
                segq_ref[:, pl.ds(qi * blk_q, blk_q)], (1, 0))
                if has_seg else None)
            # mask column band for THIS k-block, rows sliced per q-block
            # (slice the REF, not a loaded value — dynamic starts only
            # exist at the ref level)
            mask_at = ((lambda _ki: mask_ref[pl.ds(qi * blk_q, blk_q), :])
                       if has_mask else None)
            s_blk = _block_mask(s_blk, qi, ki, blk_q, blk_k, off,
                                is_causal, kvlen_b, segq_blk, seg_at,
                                window=window, alibi=alibi,
                                mask_at=mask_at)
            p = jnp.where(lse_q[:, None] <= NEG_INF * 0.5, 0.0,
                          jnp.exp(s_blk - lse_q[:, None]))
            dp = do @ vv.T
            if has_drop:   # same (bi, hi, qi, ki)-keyed mask as forward
                dmask = _dropout_keep(pltpu, seed_ref,
                                      _drop_block_id(seed_ref, bi, hi_, qi,
                                                     ki, sq // blk_q,
                                                     sk // blk_k),
                                      blk_q, blk_k, keep_p)
                dv_acc = dv_acc + jnp.where(
                    dmask, p * (1.0 / keep_p), 0.0).T @ do
                dp = jnp.where(dmask, dp * (1.0 / keep_p), 0.0)
            else:
                dv_acc = dv_acc + p.T @ do
            ds = p * (dp - delta_q[:, None])
            dk_acc = dk_acc + (ds.T @ qv) * sc
            return dk_acc, dv_acc

        n_q = sq // blk_q
        if is_causal:
            # only q rows with q_pos + off >= ki*blk_k see this k-block
            q0 = jnp.clip((ki * blk_k - off) // blk_q, 0, n_q)
        else:
            q0 = 0
        q_hi = n_q
        if window is not None:
            # sliding window: q rows past k_pos + window - 1 - off can't
            # see this k-block (loose block bound; the mask is exact)
            q_hi = jnp.clip(
                (ki * blk_k + blk_k - 1 + window - off) // blk_q + 1,
                0, n_q)
        if has_mask:
            q0 = jnp.maximum(q0, mlo_ref[bi, hi_, ki])
            q_hi = jnp.minimum(q_hi, mhi_ref[bi, hi_, ki])
        dk, dv = lax.fori_loop(q0, q_hi, body,
                               (jnp.zeros((blk_k, d), jnp.float32),
                                jnp.zeros((blk_k, d), jnp.float32)))
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)

    qfull = lambda: pl.BlockSpec((None, None, sq, d),
                                 lambda bi, hi, ki: (bi, hi, 0, 0))
    kblk = lambda: pl.BlockSpec((None, None, blk_k, d),
                                lambda bi, hi, ki: (bi, hi, ki, 0))
    frow = lambda: pl.BlockSpec((None, None, sq, LANES),
                                lambda bi, hi, ki: (bi, hi, 0, 0))
    in_specs = [qfull(), kblk(), kblk()]
    if has_len:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_seg:
        spec = _seg_specs()
        in_specs += [spec(None, sq), spec(blk_k, sk)]
    if has_alibi:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_mask:
        in_specs += _mask_specs(pl, pltpu, mask, blk_k, sq,
                                row_axis_q=False)
    if has_drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    in_specs += [qfull(), frow(), frow()]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[kblk(), kblk()],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), qt.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), qt.dtype)],
        name="flash_attention_bwd_dkv",
    )(*_build_operands(qt, kt, vt, kv_lens, seg_q, seg_k,
                       [dot, lse, delta], alibi_slopes=alibi_slopes,
                       mask=mask, bounds=bounds, seed=seed))


@functools.partial(jax.jit, static_argnames=("is_causal", "scale"))
def _flash_attention_pallas(q, k, v, is_causal: bool, scale: Optional[float]):
    """Forward-only entry (bench/eval); (b, s, h, d) in and out."""
    out, _ = _flash_fwd(q, k, v, is_causal, scale)
    return out


def _flash_fwd(q, k, v, is_causal, scale, kv_lens=None, seg_q=None,
               seg_k=None, window=None, alibi_slopes=None, mask=None,
               dropout_p=0.0, seed=None):
    b, sq, h, d = q.shape
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out_t, lse = _fwd_kernels(qt, kt, vt, is_causal, sc, kv_lens=kv_lens,
                              seg_q=seg_q, seg_k=seg_k, window=window,
                              alibi_slopes=alibi_slopes, mask=mask,
                              dropout_p=dropout_p, seed=seed)
    return jnp.transpose(out_t, (0, 2, 1, 3)), lse


def _float0_like(a):
    return np.zeros(a.shape, jax.dtypes.float0) if a is not None else None


def _flash_call(q, k, v, is_causal, scale, kv_lens, seg_q, seg_k,
                window=None, alibi_slopes=None, mask=None,
                dropout_p=0.0):
    """Differentiable entry covering all structured-mask forms, dense
    masks and in-kernel dropout."""
    flags = (kv_lens is not None, seg_q is not None,
             alibi_slopes is not None, mask is not None, dropout_p > 0.0)
    dummy_len = kv_lens if flags[0] else jnp.zeros((1,), jnp.int32)
    dummy_sq = seg_q if flags[1] else jnp.zeros((1, 1), jnp.int32)
    dummy_sk = seg_k if flags[1] else jnp.zeros((1, 1), jnp.int32)
    dummy_al = (alibi_slopes if flags[2]
                else jnp.zeros((1,), jnp.float32))
    dummy_mk = mask if flags[3] else jnp.zeros((1, 1, 1, 1), jnp.int8)
    if flags[4]:
        from paddle_tpu.core import rng as _rng
        if not _rng.has_rng("dropout"):
            # Staged out (jit) with no bound stream, the fallback key
            # would be baked into the executable as a CONSTANT: every call
            # of the compiled function reapplies the exact same dropout
            # mask — silently biased training. Unlike the eager-friendly
            # warning in next_rng_key, in-kernel dropout refuses to trace.
            # An eager jax.grad keeps concrete values and draws a fresh
            # key per call, so it passes.
            if not jax.core.is_concrete(q):
                raise RuntimeError(
                    "flash_attention dropout under jit with no bound "
                    "'dropout' rng stream: the kernel seed would become a "
                    "compile-time constant, reusing one dropout mask for "
                    "every call. Bind a stream with rng_guard(dropout=key)"
                    " or functional_call(..., rngs={'dropout': key}).")
        seed = jax.random.randint(_rng.next_rng_key("dropout"),
                                  (1,), -2 ** 31, 2 ** 31 - 1, jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    # (seed, first row, first head, heads): see _drop_block_id
    seed = jnp.concatenate(
        [seed, jnp.asarray([0, 0, q.shape[2]], jnp.int32)])

    def kernels(*arrays):
        return _flash_vjp_entry(*arrays, flags, is_causal, scale, window,
                                float(dropout_p))

    arrays = (q, k, v, dummy_len, dummy_sq, dummy_sk, dummy_al, dummy_mk,
              seed)
    part = _partition.spec
    if part is None or not jax.sharding.get_abstract_mesh().empty:
        # one device, or a trace already inside a shard_map, whose
        # caller owns the axes
        return kernels(*arrays)
    mesh, batch_axes, head_axis = part
    in_specs, out_spec, B, H = _partition_specs(
        dict(mesh.shape), batch_axes, head_axis, q.shape, k.shape[2],
        flags, dummy_mk.shape)

    def per_shard(*arrays):
        *rest, seed = arrays
        if flags[4]:   # this shard's place in the whole call
            b_loc, h_loc = rest[0].shape[0], rest[0].shape[2]
            row0 = lax.axis_index(B) * b_loc if B else 0
            head0 = lax.axis_index(H) * h_loc if H else 0
            seed = seed.at[1].set(row0).at[2].set(head0)
        return kernels(*rest, seed)

    return jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)(*arrays)


class _Partition(threading.local):
    spec = None      # (mesh, batch_axes, head_axis) inside `partitioned`


_partition = _Partition()


@contextlib.contextmanager
def partitioned(mesh, batch_axes=(), head_axis=None):
    """For the owner of a multi-device mesh, opened around the trace of
    its GSPMD jit (`parallel.fleet.make_train_step` does): GSPMD cannot
    partition a Mosaic call ("wrap the call in a shard_map"), so while
    this is open the flash kernels run per shard under `shard_map` over
    `mesh` — the batch over `batch_axes`, the heads over `head_axis`,
    each only where it divides. Outside it a flash call is a bare Mosaic
    call on the operands' device, whatever meshes the process holds."""
    prev = _partition.spec
    _partition.spec = ((mesh, tuple(batch_axes), head_axis)
                       if mesh.size > 1 else None)
    try:
        yield
    finally:
        _partition.spec = prev


def _partition_specs(mesh_shape, batch_axes, head_axis, q_shape, n_kv,
                     flags, mask_shape):
    """shard_map specs for `_flash_call`'s nine operands and its output,
    and the axes actually used: (in_specs, out_spec, B, H). B is the
    tuple of `batch_axes` larger than one when their product divides the
    batch, else None; H is `head_axis` when it divides both head counts,
    else None. q/k/v are (b, s, h, d)."""
    from jax.sharding import PartitionSpec as P
    b, h = q_shape[0], q_shape[2]
    B = tuple(a for a in batch_axes if mesh_shape.get(a, 1) > 1)
    if not B or b % math.prod(mesh_shape[a] for a in B):
        B = None
    n = mesh_shape.get(head_axis, 1)
    H = head_axis if n > 1 and h % n == 0 and n_kv % n == 0 else None
    has_len, has_seg, has_alibi, has_mask = flags[:4]
    qkv = P(B, None, H, None)
    in_specs = (
        qkv, qkv, qkv,
        P(B) if has_len else P(),
        P(B, None) if has_seg else P(),
        P(B, None) if has_seg else P(),
        P(H) if has_alibi else P(),
        P(B if mask_shape[0] == b else None,
          H if mask_shape[1] == h else None, None, None)
        if has_mask else P(),
        P())                                    # seed: per_shard places it
    return in_specs, qkv, B, H


def _mask_kw(kv_lens, seg_q, seg_k, alibi, flags, window, mask=None,
             seed=None, dropout_p=0.0):
    has_len, has_seg, has_alibi = flags[:3]
    has_mask = len(flags) > 3 and flags[3]
    has_drop = len(flags) > 4 and flags[4]
    return dict(kv_lens=kv_lens if has_len else None,
                seg_q=seg_q if has_seg else None,
                seg_k=seg_k if has_seg else None,
                window=window,
                alibi_slopes=alibi if has_alibi else None,
                mask=mask if has_mask else None,
                dropout_p=dropout_p if has_drop else 0.0,
                seed=seed if has_drop else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def _flash_vjp_entry(q, k, v, kv_lens, seg_q, seg_k, alibi, mask, seed,
                     flags, is_causal, scale, window, dropout_p):
    """Pallas forward + Pallas backward (dq / dk+dv block kernels)."""
    out, _ = _flash_fwd(q, k, v, is_causal, scale,
                        **_mask_kw(kv_lens, seg_q, seg_k, alibi, flags,
                                   window, mask, seed, dropout_p))
    return out


def _flash_vjp_fwd(q, k, v, kv_lens, seg_q, seg_k, alibi, mask, seed,
                   flags, is_causal, scale, window, dropout_p):
    out, lse = _flash_fwd(q, k, v, is_causal, scale,
                          **_mask_kw(kv_lens, seg_q, seg_k, alibi, flags,
                                     window, mask, seed, dropout_p))
    return out, (q, k, v, out, lse, kv_lens, seg_q, seg_k, alibi, mask,
                 seed)


def _pallas_bwd_impl(q, k, v, out, lse, g, is_causal, scale, g_lse=None,
                     kv_lens=None, seg_q=None, seg_k=None, window=None,
                     alibi_slopes=None, mask=None, dropout_p=0.0,
                     seed=None):
    """Shared Pallas backward. `lse` is (b, h, sq, LANES). When `g_lse`
    (b, h, sq) is given (cotangent on the returned LSE, e.g. from a ring
    merge), it folds into the softmax-grad correction: dS = P·(dP − Δ)
    with Δ_eff = rowsum(dout·out) − g_lse, since ∂lse/∂S = P."""
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    n_rep = h // n_kv
    sk = k.shape[1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    kr = _repeat_kv(k, n_rep)
    vr = _repeat_kv(v, n_rep)
    to_t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    qt, kt, vt = to_t(q), to_t(kr), to_t(vr)
    dot = to_t(g)
    out_t = to_t(out)
    # delta = rowsum(dout * out) (fp32) — the softmax-grad correction term
    delta = jnp.sum(dot.astype(jnp.float32) * out_t.astype(jnp.float32),
                    axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    kw = dict(kv_lens=kv_lens, seg_q=seg_q, seg_k=seg_k, window=window,
              alibi_slopes=alibi_slopes, mask=mask, dropout_p=dropout_p,
              seed=seed)
    dq_t = _bwd_dq_kernel(qt, kt, vt, dot, lse, delta, is_causal, sc, **kw)
    dk_t, dv_t = _bwd_dkv_kernel(qt, kt, vt, dot, lse, delta, is_causal,
                                 sc, **kw)

    from_t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    dq = from_t(dq_t).astype(q.dtype)
    dk = from_t(dk_t)
    dv = from_t(dv_t)
    if n_rep != 1:    # GQA: sum grads over the repeated head groups
        dk = dk.reshape(b, sk, n_kv, n_rep, d).sum(axis=3)
        dv = dv.reshape(b, sk, n_kv, n_rep, d).sum(axis=3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_vjp_bwd(flags, is_causal, scale, window, dropout_p, res, g):
    q, k, v, out, lse, kv_lens, seg_q, seg_k, alibi, mask, seed = res
    kw = _mask_kw(kv_lens, seg_q, seg_k, alibi, flags, window, mask, seed,
                  dropout_p)
    dq, dk, dv = _pallas_bwd_impl(q, k, v, out, lse, g, is_causal,
                                  scale, **kw)
    # kv_lens/segments are integer primals → float0; alibi is fp32 (a dummy
    # zeros(1) on non-ALiBi calls) so its cotangent must be a real float
    # zero — float0 for a float primal breaks under custom_vjp aval checks.
    # Dense masks are non-differentiable inputs (float masks get a real
    # zero cotangent, int8/bool get float0); the seed is int32 → float0.
    mask_ct = (_float0_like(res[9])
               if res[9].dtype in (jnp.bool_, jnp.int8)
               else jnp.zeros(res[9].shape, res[9].dtype))
    return (dq, dk, dv, _float0_like(res[5]), _float0_like(res[6]),
            _float0_like(res[7]), jnp.zeros(res[8].shape, res[8].dtype),
            mask_ct, _float0_like(res[10]))


_flash_vjp_entry.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)

# Back-compat alias used by benches/tests: plain self-attention entry.
def _flash_attention_vjp(q, k, v, is_causal, scale):
    return _flash_call(q, k, v, is_causal, scale, None, None, None)


# ---- forward + LSE (ring-attention building block) ------------------------

def _pallas_seq_ok(sq: int, sk: Optional[int] = None) -> bool:
    """Shared dispatch predicate: long enough to beat XLA and divisible by
    a supported block size (see _pick_blk)."""
    sk = sq if sk is None else sk
    return (max(sq, sk) >= 1024 and sq % 128 == 0 and sk % 128 == 0)


def _pallas_lse_ok(q, k):
    from paddle_tpu.ops import use_pallas
    s = q.shape[1]
    return (use_pallas() and s == k.shape[1] and _pallas_seq_ok(s)
            and q.shape[-1] in (64, 128, 256))


def _xla_fwd_lse(q, k, v, is_causal, scale):
    """XLA fallback: (out (b,s,h,d), lse (b,h,s) fp32)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n_rep = h // k.shape[2]
    kr = _repeat_kv(k, n_rep)
    vr = _repeat_kv(v, n_rep)
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                        preferred_element_type=jnp.float32) * sc
    if is_causal:
        causal = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(causal[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", (p / l[..., None]).astype(q.dtype),
                     vr)
    return out.astype(q.dtype), m + jnp.log(l)


def _fwd_lse_dispatch(q, k, v, is_causal, scale):
    if _pallas_lse_ok(q, k):
        out, lse = _flash_fwd(q, k, v, is_causal, scale)
        return out, lse[..., 0]
    return _xla_fwd_lse(q, k, v, is_causal, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_fwd_lse(q, k, v, is_causal=False, scale=None):
    """Attention forward returning (out, lse) for blockwise/ring merging.

    out (b, s, h, d) is the normalized chunk attention; lse (b, h, s) fp32
    is the log-sum-exp of the (scaled, masked) scores — together they let a
    caller merge several KV chunks exactly (ring attention, SURVEY.md
    §5-long-context). Pallas blockwise kernels on TPU when shapes allow
    (memory bounded by the 512-block tiles, never s²); XLA otherwise.
    Differentiable, including the lse output (the cotangent folds into the
    softmax-grad delta)."""
    return _fwd_lse_dispatch(q, k, v, is_causal, scale)


def _fwd_lse_vjp_fwd(q, k, v, is_causal, scale):
    out, lse = _fwd_lse_dispatch(q, k, v, is_causal, scale)
    return (out, lse), (q, k, v, out, lse)


def _fwd_lse_vjp_bwd(is_causal, scale, res, cts):
    q, k, v, out, lse = res
    g_out, g_lse = cts
    if _pallas_lse_ok(q, k):
        lse_lanes = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
        return _pallas_bwd_impl(q, k, v, out, lse_lanes, g_out,
                                is_causal, scale, g_lse=g_lse)
    _, pull = jax.vjp(
        lambda q_, k_, v_: _xla_fwd_lse(q_, k_, v_, is_causal, scale),
        q, k, v)
    return pull((g_out, g_lse))


flash_fwd_lse.defvjp(_fwd_lse_vjp_fwd, _fwd_lse_vjp_bwd)
