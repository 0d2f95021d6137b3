"""Flash attention — XLA path + Pallas TPU kernels (forward AND backward).

Reference: phi flash_attn kernel wrapping the vendored flash-attention-2 CUDA
library (paddle/phi/kernels/gpu/flash_attn_kernel.cu, cmake/external/
flashattn.cmake; python veneer paddle.nn.functional.flash_attention).

Layouts follow the reference: q/k/v are (batch, seq, num_heads, head_dim).
GQA/MQA supported via num_kv_heads < num_heads. The Pallas path is a
blockwise online softmax that never materializes the (s, s) matrix:

* What reaches the matrix unit: q, k, v and the output's cotangent in
  the dtype they were given (bf16 in training and serving, float32 for
  float32 inputs), the softmax scale folded into q or k once a block,
  and the probabilities ``p`` and ``ds`` rounded to that dtype before
  the second products (`_xla_attention`'s own semantics). Every product
  accumulates in float32; scores, the running maximum and sum, ``lse``,
  ``delta`` and the dq / dk / dv accumulators are float32. (Mosaic's
  default precision rounds a float32 operand to bf16 inside the matrix
  unit: one pass either way, measured, PERF.md PR 37.)
* A score block is (keys, queries): the softmax's maximum and sum run
  down the sublanes, ``lse`` and ``delta`` travel as (b, h, sq) with the
  positions on the lanes, and the backward's ``p @ do`` and ``ds @ q``
  are plain products. The (queries, keys) form spent a quarter of the
  forward on cross-lane reductions and transposed two score-sized
  blocks a backward step.
* Forward: key blocks for each block of queries; blocks behind the
  causal edge are skipped, blocks every query sees whole take no mask,
  and the selects that guard an empty row run only where kv_lens,
  segments, a window, a dense mask or sk < sq can empty one. Blocks of
  512, the keys and queries left over as one last shorter block.
* Backward: one kernel a key block (`flash_attention_bwd_dkv_dq`) takes
  every score block once for dq, dk and dv, with a float32 dq held in
  VMEM over a (batch, head); where that does not fit (long context) a dq
  kernel and a dk/dv kernel each recompute the scores (`_fused_bwd_fits`).

It covers, on TPU (or under ``FLAGS_pallas_interpret`` elsewhere):

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
Kernels take their operands as (b, h, s, d) so the trailing block dims
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
    from paddle_tpu.ops import pallas_mode
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
    pallas_ok = pallas_mode()[0] and (attn_mask is None or kmask is not None)
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

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b
_VMEM_LIMIT = 64 << 20              # of a v5e's 128 MiB, for one kernel
_FUSED_BWD_BUDGET = 40 << 20        # what the one-kernel backward may hold


def _compiler_params(pltpu):
    """Grids are (batch, head, block): the last axis revisits what a
    (batch, head) holds in VMEM, so it runs in order."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _dot(a, b, dims):
    """One product on the matrix unit: the operands in the dtype they
    come in, float32 out. (In interpret mode the operands are widened
    first, which changes no product: XLA's CPU runtime has no bf16 dot
    for every layout a kernel's loop asks of it.)"""
    from paddle_tpu.ops import pallas_mode
    if pallas_mode()[1]:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _pick_blk(s):
    """Largest block in (512, 256, 128) dividing s — lets the kernels
    cover any s % 128 == 0, not just 512-multiples."""
    for blk in (512, 256, 128):
        if s % blk == 0:
            return blk
    raise ValueError(f"seq {s} not a multiple of 128")


def _blocks(sq, sk, even):
    """(queries, keys) of a block. Where ``even`` both divide their
    sequence (the backward kernels, and the forward under dropout, whose
    block ids all three kernels must cut alike). Else blocks of 512 with
    what is left over as one last shorter block: a forward in blocks of
    128 costs twice one in blocks of 512 (measured, PERF.md PR 37), and a
    query block's columns are independent, so a last block that reaches
    past ``sq`` computes columns nobody reads."""
    if even:
        return _pick_blk(sq), _pick_blk(sk)
    return min(512, sq), min(512, sk)


class _Extras:
    """Which optional operands a call has, in `_build_operands`' order,
    and what follows from them for every kernel."""

    def __init__(self, is_causal, off, kv_lens, seg_q, window, alibi_slopes,
                 mask, dropout_p):
        self.is_causal = is_causal
        self.window = window
        self.has_len = kv_lens is not None
        self.has_seg = seg_q is not None
        self.has_alibi = alibi_slopes is not None
        self.has_mask = mask is not None
        self.has_drop = dropout_p > 0.0
        self.keep_p = 1.0 - dropout_p
        # a mask besides the causal edge: every block walked is masked
        self.structured = (self.has_len or self.has_seg or self.has_alibi
                           or self.has_mask or window is not None)
        # a query row may see no key at all: plain causal self-attention
        # (and no mask) cannot, and skips the selects that guard it
        self.can_empty = (self.has_len or self.has_seg or self.has_mask
                          or window is not None or (is_causal and off < 0))

    def unpack(self, refs):
        """(lens, segq, segk, slopes, mask, mask_lo, mask_hi, seed) refs,
        None where absent, and the refs after them."""
        it = iter(refs)
        take = lambda have, n=1: [next(it) if have else None
                                  for _ in range(n)]
        out = (take(self.has_len) + take(self.has_seg, 2)
               + take(self.has_alibi) + take(self.has_mask, 3)
               + take(self.has_drop))
        return out, list(it)

    def specs(self, pl, pltpu, mask, blk, sq, sk, by_q):
        """Their BlockSpecs, for a grid whose third axis walks blocks of
        ``blk`` queries (``by_q``) or keys: the walked side's segment
        ids and mask band by block, the other side's whole."""
        smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
        seg = lambda blocked, full: pl.BlockSpec(
            (None, 1, blk if blocked else full),
            (lambda bi, hi, i: (bi, 0, i)) if blocked
            else (lambda bi, hi, i: (bi, 0, 0)))
        out = []
        if self.has_len:
            out.append(smem())
        if self.has_seg:
            out += [seg(by_q, sq), seg(not by_q, sk)]
        if self.has_alibi:
            out.append(smem())
        if self.has_mask:
            out += _mask_specs(pl, pltpu, mask, blk, sk if by_q else sq,
                               by_q)
        if self.has_drop:
            out.append(smem())
        return out


def _block_mask(s_blk, rel, k0, ex, kvlen_b, segq_blk, segk_blk, alibi,
                mask_blk):
    """Apply the masks to one (keys, queries) score block whose first
    query sees up to key ``k0 + rel`` (bottom-right aligned causal: entry
    (r, c), key r against query c, is live where r - c <= rel).

    kvlen_b: scalar valid length or None; segq_blk: (1, queries) ids and
    segk_blk: (keys, 1) ids, or None; ``ex.window``: static sliding-window
    width; alibi: this head's ALiBi slope (traced fp32 scalar) — score +=
    slope · (k_pos − q_pos − off), the standard ≤ 0 linear bias;
    mask_blk: the (keys, queries) tile of a DENSE mask — bool as int8 (0 =
    masked) or additive float (the reference attn_mask semantics)."""
    shape = s_blk.shape
    if ex.is_causal or ex.window is not None or alibi is not None:
        ahead = (lax.broadcasted_iota(jnp.int32, shape, 0)
                 - lax.broadcasted_iota(jnp.int32, shape, 1))   # r - c
    if alibi is not None:
        s_blk = s_blk + alibi * (ahead - rel).astype(jnp.float32)
    if ex.is_causal:
        s_blk = jnp.where(ahead <= rel, s_blk, NEG_INF)
    if ex.window is not None:
        s_blk = jnp.where(ahead > rel - ex.window, s_blk, NEG_INF)
    if kvlen_b is not None:
        row = lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
        s_blk = jnp.where(row < kvlen_b - k0, s_blk, NEG_INF)
    if segq_blk is not None:
        s_blk = jnp.where(segk_blk == segq_blk, s_blk, NEG_INF)
    if mask_blk is not None:
        if mask_blk.dtype in (jnp.bool_, jnp.int8):   # bool rides as int8
            s_blk = jnp.where(mask_blk != 0, s_blk, NEG_INF)
        else:
            s_blk = s_blk + mask_blk.astype(jnp.float32)
    return s_blk


def _dropout_keep(pltpu, seed_ref, block_id, blk_k, blk_q, keep_p):
    """Counter-based in-kernel dropout mask for one (keys, queries) block
    (the vendored flash-attn-2 does dropout in-kernel the same way —
    canonical phi/kernels/gpu/flash_attn_kernel.cu). Reseeding the Mosaic
    PRNG on (seed, block_id) — block_id folds (b, h, q-block, k-block)
    into one int32, Mosaic's prng_seed takes at most two values — makes
    the mask a pure function of the block coordinates, so the backward
    kernels (which walk q-blocks per k-block, or the reverse) regenerate
    the exact forward mask regardless of their iteration order."""
    pltpu.prng_seed(seed_ref[0], block_id)
    bits = pltpu.bitcast(pltpu.prng_random_bits((blk_k, blk_q)),
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
        ops.append(jnp.swapaxes(mask, 2, 3))           # (mb, mh, sk, sq)
        ops.extend(bounds)                             # lo, hi (b, h, n)
    if seed is not None:
        ops.append(seed)            # (4,) int32, _drop_block_id
    return ops + extra


def _mask_specs(pl, pltpu, mask_t, blk, full, by_q):
    """BlockSpecs for [mask-tile, lo, hi]. The mask comes TRANSPOSED,
    (mb, mh, sk, sq), keys by queries like a score block, and streams one
    (sk, blk_q) band of query columns (or one (blk_k, sq) band of keys
    for the dk/dv walk) per grid step, broadcast dims pinned by index-map
    clamping; the lo/hi skip bounds ride SMEM whole."""
    mb, mh = mask_t.shape[0], mask_t.shape[1]

    def imap(bi, hi, i):
        bm = jnp.minimum(bi, mb - 1)
        hm = jnp.minimum(hi, mh - 1)
        return (bm, hm, 0, i) if by_q else (bm, hm, i, 0)

    shape = ((None, None, full, blk) if by_q else (None, None, blk, full))
    return [pl.BlockSpec(shape, imap),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM)]


def _scaled(x, sc):
    """``x * sc`` in ``x``'s dtype (the product taken in float32): the
    softmax scale folded into one operand of the score product, once a
    block, instead of into every score."""
    return (x.astype(jnp.float32) * sc).astype(x.dtype)


def _column(row):
    """(1, n) -> (n, 1): the keys' segment ids, stored with the
    positions on the lanes, as the column a score block's rows take."""
    return jnp.transpose(row, (1, 0))


def _lo(a, b):
    """min of two loop bounds; Python's where both are static (a
    ``jnp`` constant would be captured by the kernel)."""
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _hi(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _walk_keys(step, ex, qi, blk_q, blk_k, sk, off, kvlen_b, mlo, mhi,
               carry, split=True):
    """Run ``step(masked, width)(ki, carry)`` over the key blocks one
    block of queries sees, counted in blocks of ``blk_k`` keys: first
    those live for every query whole without a mask (only plain causal,
    or no mask at all, has any), then the masked ones, then the keys left
    over as a last shorter block (a loop of no or one round). Without
    ``split`` one loop masks every block: a second copy of the backward's
    body costs more than the masks it saves (measured at s 1024, PERF.md
    PR 37)."""
    whole, tail = divmod(sk, blk_k)
    nkb = whole + bool(tail)
    q0 = qi * blk_q
    start, stop = 0, nkb
    if ex.is_causal:   # query i sees keys <= i + off
        stop = jnp.clip((q0 + blk_q - 1 + off) // blk_k + 1, 0, nkb)
    if ex.has_len:     # skip k-blocks entirely past the valid length
        stop = _lo(stop, (kvlen_b + blk_k - 1) // blk_k)
    if ex.window is not None:
        # query q_pos attends k in (q_pos + off − window, q_pos + off]
        start = jnp.clip((q0 + off - ex.window + 1) // blk_k, 0, None)
    if ex.has_mask:    # all-masked prefix/suffix block skipping
        start, stop = _hi(start, mlo), _lo(stop, mhi)
    masks = ex.is_causal or ex.structured
    if not ex.structured and (split or not masks):
        full = stop
        if ex.is_causal:
            full = jnp.clip((q0 + off + 1) // blk_k, 0, stop)
        full = _lo(full, whole)
        carry = lax.fori_loop(start, full, step(False, blk_k), carry)
        start = full
    if masks:
        carry = lax.fori_loop(start, _lo(stop, whole), step(True, blk_k),
                              carry)
    if tail:
        carry = lax.fori_loop(_hi(start, whole), stop, step(masks, tail),
                              carry)
    return carry


def _fwd_kernels(qt, kt, vt, is_causal, sc, kv_lens=None, seg_q=None,
                 seg_k=None, window=None, alibi_slopes=None, mask=None,
                 dropout_p=0.0, seed=None):
    """qt (b,h,sq,d), kt/vt (b,h,sk,d) → (out (b,h,sq,d), lse (b,h,sq)).

    A score block is (keys, queries): the softmax's maximum and sum run
    down the sublanes, element by element, where a (queries, keys) block
    needs a reduction across the 128 lanes of every row (measured: a
    quarter of the forward, PERF.md PR 37), and a query's statistics sit
    on the lanes, as ``lse`` is stored. q (with the scale folded in), k
    and v reach the matrix unit in the dtype they come in; scores,
    maximum, sum and the (d, queries) accumulator are float32; ``p`` is
    rounded to v's dtype before ``v^T @ p`` (what `_xla_attention` does).
    mask: dense (mb, mh, sq, sk) bool/float attn_mask (broadcast dims
    allowed) streamed as (sk, blk_q) bands of its transpose, with
    all-masked prefix/suffix k-blocks skipped. dropout_p/seed: in-kernel
    counter-based attention dropout (see _dropout_keep) — probabilities
    drop AFTER the softmax statistics accumulate, matching standard
    dropout(softmax(s)) semantics; the output folds the 1/keep rescale
    into the final normalization."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_mode

    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    off = sk - sq
    ex = _Extras(is_causal, off, kv_lens, seg_q, window, alibi_slopes, mask,
                 dropout_p)
    blk_q, blk_k = _blocks(sq, sk, even=ex.has_drop)
    nq, nkb = -(-sq // blk_q), -(-sk // blk_k)
    bounds = None
    if mask is not None:
        # whole blocks for the bounds and the bands; the pad is read for
        # queries past sq alone, whose columns nobody reads
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, nq * blk_q - sq),
                              (0, nkb * blk_k - sk)))
        bounds = _mask_block_bounds(mask, b, h, nq, nkb, blk_q, blk_k)

    def kernel(q_ref, k_ref, v_ref, *refs):
        (lens_ref, segq_ref, segk_ref, slopes_ref, mask_ref, mlo_ref,
         mhi_ref, seed_ref), (o_ref, lse_ref) = ex.unpack(refs)
        bi, hi_, qi = (pl.program_id(i) for i in range(3))
        q = _scaled(q_ref[...], sc)                    # (blk_q, d)
        kvlen_b = lens_ref[bi] if ex.has_len else None
        alibi = slopes_ref[hi_] if ex.has_alibi else None
        segq_blk = segq_ref[...] if ex.has_seg else None     # (1, blk_q)

        def step(masked, width):
            def body(ki, carry):
                acc, m_prev, l_prev = carry
                k0 = ki * blk_k
                ks = pl.ds(pl.multiple_of(k0, 128), width)
                s_blk = _dot(k_ref[ks, :], q, _NT)     # (width, blk_q)
                if masked:
                    s_blk = _block_mask(
                        s_blk, qi * blk_q + off - k0, k0, ex, kvlen_b,
                        segq_blk,
                        _column(segk_ref[:, ks]) if ex.has_seg else None,
                        alibi, mask_ref[ks, :] if ex.has_mask else None)
                m_cur = jnp.maximum(
                    m_prev, jnp.max(s_blk, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)
                p = jnp.exp(s_blk - m_cur)
                if ex.can_empty:
                    # queries with no valid key yet keep m at NEG_INF —
                    # their p must be 0, not exp(0), so fully-masked
                    # rows emit 0
                    p = jnp.where(m_cur <= NEG_INF * 0.5, 0.0, p)
                l_cur = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
                if ex.has_drop:   # l accumulates UNdropped p (flash-attn-2)
                    p = jnp.where(
                        _dropout_keep(pltpu, seed_ref,
                                      _drop_block_id(seed_ref, bi, hi_, qi,
                                                     ki, nq, nkb),
                                      width, blk_q, ex.keep_p), p, 0.0)
                acc = acc * alpha + _dot(v_ref[ks, :],
                                         p.astype(v_ref.dtype), _TN)
                return acc, m_cur, l_cur
            return body

        acc, m, l = _walk_keys(
            step, ex, qi, blk_q, blk_k, sk, off, kvlen_b,
            mlo_ref[bi, hi_, qi] if ex.has_mask else None,
            mhi_ref[bi, hi_, qi] if ex.has_mask else None,
            (jnp.zeros((d, blk_q), jnp.float32),
             jnp.full((1, blk_q), NEG_INF, jnp.float32),
             jnp.zeros((1, blk_q), jnp.float32)))
        lsafe = jnp.where(l == 0.0, 1.0, l) if ex.can_empty else l
        norm = lsafe * ex.keep_p if ex.has_drop else lsafe
        o_ref[...] = jnp.transpose(acc / norm, (1, 0)).astype(o_ref.dtype)
        lse_ref[...] = m + jnp.log(lsafe)

    qblk = lambda: pl.BlockSpec((None, None, blk_q, d),
                                lambda bi, hi, qi: (bi, hi, qi, 0))
    kfull = lambda: pl.BlockSpec((None, None, sk, d),
                                 lambda bi, hi, qi: (bi, hi, 0, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq),
        in_specs=[qblk(), kfull(), kfull()]
        + ex.specs(pl, pltpu, mask, blk_q, sq, sk, by_q=True),
        out_specs=[qblk(), pl.BlockSpec((None, None, 1, blk_q),
                                        lambda bi, hi, qi: (bi, hi, 0, qi))],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
        ],
        compiler_params=_compiler_params(pltpu),
        name="flash_attention_fwd",
        interpret=pallas_mode()[1],
    )(*_build_operands(qt, kt, vt, kv_lens, seg_q, seg_k, [],
                       alibi_slopes=alibi_slopes, mask=mask, bounds=bounds,
                       seed=seed))
    return out, lse[:, :, 0]


def _bwd_dq_kernel(qt, kt, vt, dot, lse, delta, is_causal, sc,
                   kv_lens=None, seg_q=None, seg_k=None, window=None,
                   alibi_slopes=None, mask=None, dropout_p=0.0, seed=None):
    """dq: the forward's walk again, key blocks for each block of
    queries, (keys, queries) score blocks. lse and delta are
    (b, h, 1, sq)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_mode

    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    off = sk - sq
    ex = _Extras(is_causal, off, kv_lens, seg_q, window, alibi_slopes, mask,
                 dropout_p)
    blk_q, blk_k = _blocks(sq, sk, even=True)
    nq, nkb = sq // blk_q, sk // blk_k
    bounds = (_mask_block_bounds(mask, b, h, nq, nkb, blk_q, blk_k)
              if ex.has_mask else None)

    def kernel(q_ref, k_ref, v_ref, *refs):
        (lens_ref, segq_ref, segk_ref, slopes_ref, mask_ref, mlo_ref,
         mhi_ref, seed_ref), (do_ref, lse_ref, dl_ref, dq_ref) = \
            ex.unpack(refs)
        bi, hi_, qi = (pl.program_id(i) for i in range(3))
        q = _scaled(q_ref[...], sc)
        do = do_ref[...]                               # (blk_q, d)
        lse_q, delta_q = lse_ref[...], dl_ref[...]     # (1, blk_q)
        kvlen_b = lens_ref[bi] if ex.has_len else None
        alibi = slopes_ref[hi_] if ex.has_alibi else None
        segq_blk = segq_ref[...] if ex.has_seg else None

        def step(masked, width):
            def body(ki, dq_acc):
                k0 = ki * blk_k
                ks = pl.ds(pl.multiple_of(k0, 128), width)
                k_blk = k_ref[ks, :]
                s_blk = _dot(k_blk, q, _NT)            # (width, blk_q)
                if masked:
                    s_blk = _block_mask(
                        s_blk, qi * blk_q + off - k0, k0, ex, kvlen_b,
                        segq_blk,
                        _column(segk_ref[:, ks]) if ex.has_seg else None,
                        alibi, mask_ref[ks, :] if ex.has_mask else None)
                p = jnp.exp(s_blk - lse_q)
                if ex.can_empty:
                    p = jnp.where(lse_q <= NEG_INF * 0.5, 0.0, p)
                dp = _dot(v_ref[ks, :], do, _NT)
                if ex.has_drop:   # regenerate the forward's block mask
                    dp = jnp.where(
                        _dropout_keep(pltpu, seed_ref,
                                      _drop_block_id(seed_ref, bi, hi_, qi,
                                                     ki, nq, nkb),
                                      width, blk_q, ex.keep_p),
                        dp * (1.0 / ex.keep_p), 0.0)
                ds = (p * (dp - delta_q)).astype(k_blk.dtype)
                return dq_acc + _dot(k_blk, ds, _TN)   # (d, blk_q)
            return body

        dq = _walk_keys(step, ex, qi, blk_q, blk_k, sk, off, kvlen_b,
                        mlo_ref[bi, hi_, qi] if ex.has_mask else None,
                        mhi_ref[bi, hi_, qi] if ex.has_mask else None,
                        jnp.zeros((d, blk_q), jnp.float32), split=False)
        dq_ref[...] = jnp.transpose(dq * sc, (1, 0)).astype(dq_ref.dtype)

    kfull = lambda: pl.BlockSpec((None, None, sk, d),
                                 lambda bi, hi, qi: (bi, hi, 0, 0))
    qblk = lambda: pl.BlockSpec((None, None, blk_q, d),
                                lambda bi, hi, qi: (bi, hi, qi, 0))
    row = lambda: pl.BlockSpec((None, None, 1, blk_q),
                               lambda bi, hi, qi: (bi, hi, 0, qi))
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq),
        in_specs=[qblk(), kfull(), kfull()]
        + ex.specs(pl, pltpu, mask, blk_q, sq, sk, by_q=True)
        + [qblk(), row(), row()],
        out_specs=qblk(),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
        compiler_params=_compiler_params(pltpu),
        name="flash_attention_bwd_dq",
        interpret=pallas_mode()[1],
    )(*_build_operands(qt, kt, vt, kv_lens, seg_q, seg_k,
                       [dot, lse, delta], alibi_slopes=alibi_slopes,
                       mask=mask, bounds=bounds, seed=seed))


def _fused_bwd_fits(sq, sk, d, itemsize):
    """Whether one (batch, head)'s q, do and dq (whole), the float32 dq
    it accumulates, lse and delta and a block of k, v, dk and dv fit in
    VMEM beside the score blocks: what decides between the one-kernel
    backward and dq and dk/dv kernels apart."""
    blk_q, blk_k = _blocks(sq, sk, even=True)
    held = 3 * sq * d * itemsize + 2 * 8 * sq * 4
    blocks = 4 * blk_k * d * itemsize
    work = sq * d * 4 + 6 * blk_q * blk_k * 4
    return 2 * (held + blocks) + work <= _FUSED_BWD_BUDGET


def _bwd_dkv_kernel(qt, kt, vt, dot, lse, delta, is_causal, sc,
                    kv_lens=None, seg_q=None, seg_k=None, window=None,
                    alibi_slopes=None, mask=None, dropout_p=0.0,
                    seed=None, with_dq=False):
    """dk, dv: blocks of queries for each block of keys, (keys, queries)
    score blocks, so ``dv += p @ do`` and ``dk += ds @ q`` are plain
    products. ``with_dq``: the one-kernel backward, which also adds every
    block's ``k^T @ ds`` into a float32 (d, sq) dq that stays in VMEM
    over a (batch, head)'s key blocks — scores, ``exp`` and ``dp`` taken
    once for all three gradients. lse and delta are (b, h, 1, sq).
    Returns (dk, dv) or (dk, dv, dq)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops import pallas_mode

    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    off = sk - sq
    ex = _Extras(is_causal, off, kv_lens, seg_q, window, alibi_slopes, mask,
                 dropout_p)
    blk_q, blk_k = _blocks(sq, sk, even=True)
    nq, nkb = sq // blk_q, sk // blk_k
    bounds = (_mask_block_bounds(mask, b, h, nq, nkb, blk_q, blk_k,
                                 axis_q=False) if ex.has_mask else None)

    def kernel(q_ref, k_ref, v_ref, *refs):
        (lens_ref, segq_ref, segk_ref, slopes_ref, mask_ref, mlo_ref,
         mhi_ref, seed_ref), rest = ex.unpack(refs)
        do_ref, lse_ref, dl_ref, dk_ref, dv_ref = rest[:5]
        dq_ref, dq_acc = rest[5:] if with_dq else (None, None)
        bi, hi_, ki = (pl.program_id(i) for i in range(3))
        k0 = ki * blk_k
        k_s = _scaled(k_ref[...], sc)                  # (blk_k, d)
        v_blk = v_ref[...]
        kvlen_b = lens_ref[bi] if ex.has_len else None
        alibi = slopes_ref[hi_] if ex.has_alibi else None
        # k-side ids for THIS block, as a column; q-side read per block
        segk_blk = _column(segk_ref[...]) if ex.has_seg else None

        if with_dq:
            k_t = jnp.transpose(k_s, (1, 0))           # (d, blk_k)

            @pl.when(ki == 0)
            def _():
                dq_acc[...] = jnp.zeros_like(dq_acc)

        def step(masked):
            def body(qi, carry):
                dk_acc, dv_acc = carry
                cols = pl.ds(pl.multiple_of(qi * blk_q, 128), blk_q)
                q_blk, do = q_ref[cols, :], do_ref[cols, :]
                s_blk = _dot(k_s, q_blk, _NT)          # (blk_k, blk_q)
                if masked:
                    # the mask's band of THIS k-block, columns sliced per
                    # q-block (slice the REF, not a loaded value — dynamic
                    # starts only exist at the ref level)
                    s_blk = _block_mask(
                        s_blk, qi * blk_q + off - k0, k0, ex, kvlen_b,
                        segq_ref[:, cols] if ex.has_seg else None,
                        segk_blk, alibi,
                        mask_ref[:, cols] if ex.has_mask else None)
                lse_q = lse_ref[:, cols]               # (1, blk_q)
                p = jnp.exp(s_blk - lse_q)
                if ex.can_empty:
                    p = jnp.where(lse_q <= NEG_INF * 0.5, 0.0, p)
                dp = _dot(v_blk, do, _NT)
                pd = p
                if ex.has_drop:   # same (bi, hi, qi, ki)-keyed mask as fwd
                    keep = _dropout_keep(
                        pltpu, seed_ref,
                        _drop_block_id(seed_ref, bi, hi_, qi, ki, nq, nkb),
                        blk_k, blk_q, ex.keep_p)
                    pd = jnp.where(keep, p * (1.0 / ex.keep_p), 0.0)
                    dp = jnp.where(keep, dp * (1.0 / ex.keep_p), 0.0)
                dv_acc = dv_acc + _dot(pd.astype(do.dtype), do, _NN)
                ds = (p * (dp - dl_ref[:, cols])).astype(q_blk.dtype)
                dk_acc = dk_acc + _dot(ds, q_blk, _NN)
                if with_dq:     # (k * sc)^T @ ds: dq's scale rides in k_s
                    dq_acc[:, cols] += _dot(k_t, ds, _NN)
                return dk_acc, dv_acc
            return body

        # the blocks of queries that see this key block, in ONE loop that
        # masks every block if any (see `_walk_keys`)
        q_lo, q_hi = 0, nq
        if is_causal:
            # only queries with q_pos + off >= k0 see this k-block
            q_lo = jnp.clip((k0 - off) // blk_q, 0, nq)
        if window is not None:
            # sliding window: queries past k_pos + window - 1 - off can't
            # see this k-block (loose block bound; the mask is exact)
            q_hi = jnp.clip(
                (k0 + blk_k - 1 + window - off) // blk_q + 1, 0, nq)
        if ex.has_mask:
            q_lo = _hi(q_lo, mlo_ref[bi, hi_, ki])
            q_hi = _lo(q_hi, mhi_ref[bi, hi_, ki])
        dk, dv = lax.fori_loop(
            q_lo, q_hi, step(is_causal or ex.structured),
            (jnp.zeros((blk_k, d), jnp.float32),
             jnp.zeros((blk_k, d), jnp.float32)))
        dk_ref[...] = (dk * sc).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)
        if with_dq:
            @pl.when(ki == nkb - 1)
            def _():
                dq_ref[...] = jnp.transpose(
                    dq_acc[...], (1, 0)).astype(dq_ref.dtype)

    qfull = lambda: pl.BlockSpec((None, None, sq, d),
                                 lambda bi, hi, ki: (bi, hi, 0, 0))
    kblk = lambda: pl.BlockSpec((None, None, blk_k, d),
                                lambda bi, hi, ki: (bi, hi, ki, 0))
    frow = lambda: pl.BlockSpec((None, None, 1, sq),
                                lambda bi, hi, ki: (bi, hi, 0, 0))
    kv_shape = jax.ShapeDtypeStruct((b, h, sk, d), qt.dtype)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nkb),
        in_specs=[qfull(), kblk(), kblk()]
        + ex.specs(pl, pltpu, mask, blk_k, sq, sk, by_q=False)
        + [qfull(), frow(), frow()],
        out_specs=[kblk(), kblk()] + ([qfull()] if with_dq else []),
        out_shape=[kv_shape, kv_shape] + (
            [jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype)]
            if with_dq else []),
        scratch_shapes=([pltpu.VMEM((d, sq), jnp.float32)]
                        if with_dq else []),
        compiler_params=_compiler_params(pltpu),
        name=("flash_attention_bwd_dkv_dq" if with_dq
              else "flash_attention_bwd_dkv"),
        interpret=pallas_mode()[1],
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
        return _flash_entry_jit(*arrays, flags, is_causal, scale, window,
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
    """Shared Pallas backward. `lse` is (b, h, sq). When `g_lse`
    (b, h, sq) is given (cotangent on the returned LSE, e.g. from a ring
    merge), it folds into the softmax-grad correction: dS = P·(dP − Δ)
    with Δ_eff = rowsum(dout·out) − g_lse, since ∂lse/∂S = P.

    One algorithm in two forms, chosen by shape: where a (batch, head)'s
    float32 dq fits in VMEM (`_fused_bwd_fits`) one kernel takes every
    score block once for dq, dk and dv; else a dq kernel and a dk/dv
    kernel each take it."""
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
    # positions on the lanes: (b, h, 1, sq)
    stats = [dot, lse[:, :, None], delta[:, :, None]]

    kw = dict(kv_lens=kv_lens, seg_q=seg_q, seg_k=seg_k, window=window,
              alibi_slopes=alibi_slopes, mask=mask, dropout_p=dropout_p,
              seed=seed)
    if _fused_bwd_fits(sq, sk, d, q.dtype.itemsize):
        dk_t, dv_t, dq_t = _bwd_dkv_kernel(qt, kt, vt, *stats, is_causal,
                                           sc, with_dq=True, **kw)
    else:
        dq_t = _bwd_dq_kernel(qt, kt, vt, *stats, is_causal, sc, **kw)
        dk_t, dv_t = _bwd_dkv_kernel(qt, kt, vt, *stats, is_causal, sc,
                                     **kw)

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
# jitted on its own: the layers of one program share ONE trace of the
# kernels' bodies (every `jnp` call in a body is a nested trace on the
# host; 24 layers x 4 prefill programs traced apart were 5 s of a
# serving engine's warm set-up, PERF.md PR 37)
_flash_entry_jit = jax.jit(_flash_vjp_entry,
                           static_argnums=(9, 10, 11, 12, 13))

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
    from paddle_tpu.ops import pallas_mode
    s = q.shape[1]
    return (pallas_mode()[0] and s == k.shape[1] and _pallas_seq_ok(s)
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
        return _flash_fwd(q, k, v, is_causal, scale)
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
        return _pallas_bwd_impl(q, k, v, out, lse, g_out, is_causal, scale,
                                g_lse=g_lse)
    _, pull = jax.vjp(
        lambda q_, k_, v_: _xla_fwd_lse(q_, k_, v_, is_causal, scale),
        q, k, v)
    return pull((g_out, g_lse))


flash_fwd_lse.defvjp(_fwd_lse_vjp_fwd, _fwd_lse_vjp_bwd)
