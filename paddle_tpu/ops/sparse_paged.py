"""InfLLM-v2 block-sparse attention over a paged pool: a query reads a
few blocks of ``block_size`` tokens, chosen by its own scores against
compressed keys, instead of every cached token.

The pool holds, a sparse layer and a page of ``BT`` tokens, the rows
``[k (G d) | v (G d)]`` and, in a second leaf on the same block tables,
``BT / kernel_stride`` compressed keys ``(G d)``: row j of a request is
the mean of its keys ``[stride j, stride j + kernel)`` and lives in the
page of its first token. A query at position t with n = t + 1 tokens:

1. ``stage 1`` (:func:`stage1`, and :func:`sparse_select` for a decode
   step, through the block table): ``p^h = softmax_j(q^h . Kc_j /
   sqrt(d))`` over the j with ``stride j + kernel <= n``, exactly; ``r_j
   = sum_h p^h_j`` over a group's heads.
2. ``R_b = max r_j`` over the j whose span meets block b
   (:func:`block_scores`); forced blocks (the first ``init_blocks`` and
   the ``window_size / block_size`` that end at t's own) and the
   ``topk`` others of largest ``R_b``: :func:`select_mask` for a
   prefill's queries, :func:`select_list` for a decode step's rows. A
   query with ``n <= dense_len`` reads every block up to its own. The
   ``topk``-th score is found exactly, by a search over bit patterns
   (:func:`kth_largest`), not by a sort.
3. softmax attention over the tokens ``<= t`` of those blocks:
   :func:`sparse_paged_decode` walks the chosen ``block_size``-token
   sub-blocks of the pool's pages; :func:`sparse_prefill_attention`
   is a prefill's, under the per-token selection.

Each of the three device functions has a ``jnp`` path (the CPU, the
parity oracle) and a Mosaic kernel named as the function in traces
(``sparse_select``, ``sparse_paged_decode``, ``sparse_prefill_attn``).
The kernels want ``d == 128`` (a head is a lane-aligned slice of a pool
row) and take whatever else the ``jnp`` paths take.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import pallas_mode

_HI = lax.Precision.HIGHEST
NEG = -1e30
SELECT_KERNEL = "sparse_select"
DECODE_KERNEL = "sparse_paged_decode"
PREFILL_KERNEL = "sparse_prefill_attn"


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """MiniCPM4's ``sparse_config``."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    dense_len: int = 8192

    def __post_init__(self):
        if (self.kernel_size % self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.window_size % self.block_size):
            raise ValueError(
                f"sparse_config: kernel_size {self.kernel_size} and "
                f"block_size {self.block_size} must be multiples of "
                f"kernel_stride {self.kernel_stride}, window_size "
                f"{self.window_size} of block_size")
        if self.dense_len < self.window_size + self.init_blocks \
                * self.block_size:
            raise ValueError(
                f"sparse_config: dense_len {self.dense_len} must cover the "
                f"window and the initial blocks, or they would overlap")

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def max_blocks(self) -> int:
        """The most blocks one query reads."""
        return max(self.init_blocks + self.window_blocks + self.topk,
                   -(-self.dense_len // self.block_size))


def compress(k_ext, sp: SparseConfig):
    """k_ext (n, stride (m + kernel / stride - 1), lanes): m windows of
    ``kernel_size`` keys, ``kernel_stride`` apart -> their means (n, m,
    lanes), float32."""
    n, length, lanes = k_ext.shape
    per = sp.kernel_size // sp.kernel_stride
    halves = k_ext.astype(jnp.float32).reshape(
        n, length // sp.kernel_stride, sp.kernel_stride, lanes).mean(2)
    m = halves.shape[1] - per + 1
    return sum(halves[:, o:o + m] for o in range(per)) / per


def stage1(q, kc, t, sp: SparseConfig):
    """q (B, Q, H, d), kc (B, J, G, d), t (B, Q) positions -> r (B, Q, G,
    J): a group's summed softmax over the compressed keys a query may
    see, ``-inf`` at the others."""
    B, Q, H, d = q.shape
    J, G = kc.shape[1:3]
    s = jnp.einsum("bqghd,bjgd->bqghj",
                   q.astype(jnp.float32).reshape(B, Q, G, H // G, d),
                   kc.astype(jnp.float32), precision=_HI) / math.sqrt(d)
    valid = (jnp.arange(J) * sp.kernel_stride + sp.kernel_size
             <= t[..., None] + 1)[:, :, None, None, :]
    s = jnp.where(valid, s, NEG)
    p = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.where(valid[:, :, :, 0], p.sum(3), -jnp.inf)


def block_scores(r, sp: SparseConfig, NB: int):
    """r (..., J) -> R (..., NB): the largest ``r_j`` over the j whose
    span ``[stride j, stride j + kernel)`` meets block b. With m = block
    / stride and per = kernel / stride those are j = m b - (per - 1) ..
    m b + m - 1."""
    m = sp.block_size // sp.kernel_stride
    per = sp.kernel_size // sp.kernel_stride
    J = r.shape[-1]
    need = m * NB + per - 1
    lead = [(0, 0)] * (r.ndim - 1)
    rp = jnp.pad(r, lead + [(per - 1, max(need - (per - 1) - J, 0))],
                 constant_values=-jnp.inf)[..., :need]
    return functools.reduce(jnp.maximum, [
        lax.slice_in_dim(rp, o, o + m * (NB - 1) + 1, m, axis=-1)
        for o in range(m + per - 1)])


def visible_blocks(t, sp: SparseConfig, NB: int):
    """t (...,) -> (..., NB) bool: the blocks up to the query's own."""
    return jnp.arange(NB) <= (t // sp.block_size)[..., None]


def _kinds(t, sp: SparseConfig, NB: int):
    """t (...,) -> (visible, forced) (..., NB) bool: of the visible
    blocks, those every query reads."""
    b = jnp.arange(NB)
    tb = (t // sp.block_size)[..., None]
    visible = visible_blocks(t, sp, NB)
    return visible, visible & ((b < sp.init_blocks)
                               | (b > tb - sp.window_blocks))


def kth_largest(x, k: int):
    """x (..., N) float32 without NaN, 1 <= k <= N -> the k-th largest
    entry of every row, (..., 1): what ``lax.top_k(x, k)[0][..., -1:]``
    is, bit for bit, without the sort. A float's bits, the magnitude
    flipped where the sign is set, order as the floats do; the answer is
    the largest pattern v with ``count(x >= v) >= k``, built four bits a
    pass from the top: the 15 candidates ``v | digit`` are counted in
    one read of x (counts fall as the digit rises, so the digit is the
    number of candidates with k entries or more at or over them)."""
    N = x.shape[-1]
    i = lax.bitcast_convert_type(x, jnp.int32)
    top = jnp.int32(-2 ** 31)
    # unsigned patterns in the floats' order: -inf < ... < -0.0 < 0.0 < ...;
    # the candidates' axis first, so that a count is adds of whole
    # registers and not a reduction inside each
    u = jnp.moveaxis(lax.bitcast_convert_type(
        jnp.where(i < 0, ~i, i ^ top), jnp.uint32), -1, 0).reshape(N, 1, -1)
    v = jnp.zeros(u.shape[1:], jnp.uint32)
    for shift in range(28, -1, -4):
        cands = v | (jnp.arange(1, 16, dtype=jnp.uint32) << shift)[:, None]
        enough = (u >= cands).sum(0, dtype=jnp.int32) >= k
        v = v | (enough.sum(0, keepdims=True).astype(jnp.uint32) << shift)
    v = lax.bitcast_convert_type(v.reshape(x.shape[:-1] + (1,)), jnp.int32)
    return lax.bitcast_convert_type(jnp.where(v < 0, v ^ top, ~v),
                                    jnp.float32)


def select_mask(R, t, sp: SparseConfig):
    """R (..., G, NB), t (...,) -> the blocks each query reads, (..., G,
    NB) bool. Neighbouring blocks share the compressed keys on their
    border, so two ``R_b`` are often EQUAL: of the blocks tied at the
    ``topk``-th place the lower indices are taken, exactly ``topk`` in
    all."""
    NB = R.shape[-1]
    k = min(sp.topk, NB)
    visible, forced = _kinds(t, sp, NB)
    cand = (visible & ~forced)[..., None, :]
    Rc = jnp.where(cand, R, -jnp.inf)
    kth = kth_largest(Rc, k)
    above = Rc > kth
    tied = (Rc == kth) & (Rc > -jnp.inf)
    room = k - above.sum(-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, -1) <= room))
    dense = (t + 1 <= sp.dense_len)[..., None, None]
    return jnp.where(dense, visible[..., None, :],
                     forced[..., None, :] | chosen)


def select_list(R, t, sp: SparseConfig):
    """:func:`select_mask` for the rows of a decode step, as a list: R
    (b, G, NB), t (b,) -> (b, G, max_blocks) int32, ascending, ``-1``
    where unused."""
    NB = R.shape[-1]
    mask = select_mask(R, t, sp)
    K = min(sp.max_blocks, NB)
    rank, idx = lax.top_k(jnp.where(mask, NB - jnp.arange(NB), 0), K)
    blocks = jnp.where(rank > 0, idx, -1).astype(jnp.int32)
    return jnp.pad(blocks, [(0, 0), (0, 0), (0, sp.max_blocks - K)],
                   constant_values=-1)


# ------------------------------------------------------- the decode step
def append_kv(pool, ck_pool, tables, positions, k, v, active, *, layer: int,
              sp: SparseConfig):
    """Write each active row's new ``[k | v]`` (b, G d) at its position
    and, where that completes a window of ``kernel_size`` keys, the
    window's compressed key. pool (L, NB, BT, 2 G d), ck_pool (L, NB, BT /
    stride, G d). Idle rows write to the scratch block."""
    BT, lanes = pool.shape[2], k.shape[-1]
    b = k.shape[0]
    rows = jnp.arange(b)
    bid = jnp.where(active, tables[rows, positions // BT], 0)
    pool = pool.at[layer, bid, positions % BT].set(
        jnp.concatenate([k, v], -1).astype(pool.dtype))
    n = positions + 1
    done = active & (n >= sp.kernel_size) \
        & ((n - sp.kernel_size) % sp.kernel_stride == 0)
    # the window that ends here, as kernel / stride slabs of ``stride``
    # tokens: each lies inside one page, so each is ONE slice of the pool
    # (a gather of single rows is a loop of b x kernel steps on the chip)
    first = jnp.maximum(n - sp.kernel_size, 0) // sp.kernel_stride \
        * sp.kernel_stride
    at = first[:, None] + sp.kernel_stride * jnp.arange(
        sp.kernel_size // sp.kernel_stride)                 # (b, slabs)
    slab = jax.vmap(jax.vmap(lambda page, row: lax.dynamic_slice(
        pool, (layer, page, row, 0), (1, 1, sp.kernel_stride, lanes))))(
            tables[rows[:, None], at // BT], at % BT)
    kc = compress(slab.reshape(b, sp.kernel_size, lanes), sp)[:, 0]
    bid2 = jnp.where(done, tables[rows, first // BT], 0)
    ck_pool = ck_pool.at[layer, bid2, (first % BT) // sp.kernel_stride].set(
        kc.astype(ck_pool.dtype))
    return pool, ck_pool


def _stage1_reference(q, ck_pool, tables, positions, *, layer, sp):
    b, H, d = q.shape
    G = ck_pool.shape[-1] // d
    kc = ck_pool[layer][tables].reshape(b, -1, G, d)
    return stage1(q[:, None], kc, positions[:, None], sp)[:, 0]


@functools.partial(jax.jit, static_argnames=("layer", "sp", "interpret"))
def _stage1_pallas(q, ck_pool, tables, positions, *, layer, sp, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, H, d = q.shape
    _, _, rows, lanes = ck_pool.shape
    G = lanes // d
    Hg = H // G
    MB = tables.shape[1]
    BT = rows * sp.kernel_stride
    J = MB * rows
    scale = 1.0 / math.sqrt(d)

    def kernel(pos_ref, tab_ref, q_ref, ck_ref, r_ref, buf, sem):
        r = pl.program_id(0)
        n = pos_ref[r] + 1
        pages = (n + BT - 1) // BT
        copies = [pltpu.make_async_copy(ck_ref.at[layer, tab_ref[r, p]],
                                        buf.at[p], sem.at[p])
                  for p in range(MB)]
        for p, c in enumerate(copies):
            pl.when(p < pages)(c.start)
        for p, c in enumerate(copies):
            pl.when(p < pages)(c.wait)
        j = lax.broadcasted_iota(jnp.int32, (1, J), 1)
        valid = j * sp.kernel_stride + sp.kernel_size <= n
        for g in range(G):
            kc = buf[:, :, g * d:(g + 1) * d].reshape(J, d)
            s = lax.dot_general(
                q_ref[g * Hg:(g + 1) * Hg, :], kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG)
            p_ = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
            p_ = p_ / jnp.maximum(p_.sum(-1, keepdims=True), 1e-30)
            r_ref[g:g + 1, :] = jnp.where(
                valid, p_.sum(0, keepdims=True), -jnp.inf)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((None, H, d), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
            out_specs=pl.BlockSpec((None, G, J), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((MB, rows, lanes), ck_pool.dtype),
                            pltpu.SemaphoreType.DMA((MB,))]),
        out_shape=jax.ShapeDtypeStruct((b, G, J), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=SELECT_KERNEL, interpret=interpret,
    )(positions.astype(jnp.int32), tables.astype(jnp.int32),
      q.astype(ck_pool.dtype), ck_pool)


def sparse_select(q, ck_pool, tables, positions, active, *, layer: int,
                  sp: SparseConfig):
    """The blocks every row of a decode step reads in sparse layer
    ``layer``: q (b, H, d) against the row's compressed keys through its
    block table -> (blocks (b, G, max_blocks) int32 with ``-1`` unused,
    int32 (3,): blocks read and blocks visible, summed over the active
    rows and the groups, and the active rows at or under
    ``dense_len``)."""
    d = q.shape[-1]
    G = ck_pool.shape[-1] // d
    NB = tables.shape[1] * ck_pool.shape[2] * sp.kernel_stride \
        // sp.block_size
    on, interp = pallas_mode()
    if on and d % 128 == 0 and ck_pool.shape[2] % 8 == 0:
        r = _stage1_pallas(q, ck_pool, tables, positions, layer=layer,
                           sp=sp, interpret=interp)
    else:
        r = _stage1_reference(q, ck_pool, tables, positions, layer=layer,
                              sp=sp)
    blocks = select_list(block_scores(r, sp, NB), positions, sp)
    act = active.astype(jnp.int32)
    read = ((blocks >= 0).sum((1, 2), dtype=jnp.int32) * act).sum()
    visible = ((positions // sp.block_size + 1) * G * act).sum()
    dense = ((positions + 1 <= sp.dense_len) & active).sum(dtype=jnp.int32)
    return blocks, jnp.stack([read, visible.astype(jnp.int32), dense])


def _token_positions(blocks, sp: SparseConfig):
    """blocks (..., K) -> the position of every token of the listed
    blocks (..., K block_size); a huge one where a block is unused."""
    tok = blocks[..., None] * sp.block_size + jnp.arange(
        sp.block_size, dtype=jnp.int32)
    tok = jnp.where(blocks[..., None] >= 0, tok, jnp.iinfo(jnp.int32).max)
    return tok.reshape(*blocks.shape[:-1], -1)


def sparse_paged_decode_reference(q, pool, tables, positions, blocks, *,
                                  layer: int, sp: SparseConfig):
    """q (b, H, d), pool (L, NB, BT, 2 G d), blocks (b, G, K) -> o (b, H,
    d) float32: softmax attention of each head over the tokens ``<=
    positions`` of its group's listed blocks."""
    b, H, d = q.shape
    BT = pool.shape[2]
    G = pool.shape[-1] // (2 * d)
    per = BT // sp.block_size
    sub = pool[layer].reshape(-1, sp.block_size, 2, G, d)
    safe = jnp.maximum(blocks, 0)
    page = jnp.take_along_axis(tables[:, None, :], safe // per, axis=2)
    got = sub[page * per + safe % per, :, :, jnp.arange(G)[None, :, None]]
    # got (b, G, K, block, 2, d)
    kv = got.astype(jnp.float32).reshape(b, G, -1, 2, d)
    see = _token_positions(blocks, sp) <= positions[:, None, None]
    s = jnp.einsum("bghd,bgnd->bghn",
                   q.astype(jnp.float32).reshape(b, G, H // G, d),
                   kv[:, :, :, 0], precision=_HI) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(see[:, :, None], s, NEG), -1)
    p = jnp.where(see[:, :, None], p, 0.0)
    return jnp.einsum("bghn,bgnd->bghd", p, kv[:, :, :, 1],
                      precision=_HI).reshape(b, H, d)


@functools.partial(jax.jit, static_argnames=("layer", "sp", "interpret"))
def _sparse_paged_decode_pallas(q, pool, tables, positions, blocks, *, layer,
                                sp, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, H, d = q.shape
    BT = pool.shape[2]
    G = pool.shape[-1] // (2 * d)
    Hg = H // G
    K = blocks.shape[-1]
    bs = sp.block_size
    per = BT // bs
    scale = 1.0 / math.sqrt(d)

    def kernel(pos_ref, tab_ref, blk_ref, q_ref, tok_ref, pool_ref, o_ref,
               kbuf, vbuf, sem):
        r, g = pl.program_id(0), pl.program_id(1)

        def copies(e):
            blk = blk_ref[(r * G + g) * K + e]
            safe = jnp.maximum(blk, 0)
            src = pool_ref.at[layer, tab_ref[r, safe // per],
                              pl.ds(pl.multiple_of((safe % per) * bs, bs), bs)]
            return blk, (
                pltpu.make_async_copy(
                    src.at[:, pl.ds(pl.multiple_of(g * d, 128), d)],
                    kbuf.at[e], sem.at[0, e]),
                pltpu.make_async_copy(
                    src.at[:, pl.ds(pl.multiple_of((G + g) * d, 128), d)],
                    vbuf.at[e], sem.at[1, e]))

        # an unused entry keeps what an earlier row left there, which is
        # finite and weighs 0; only what the buffer held BEFORE the call
        # may be anything (0 x NaN), so it is cleared once
        @pl.when((r == 0) & (g == 0))
        def _():
            vbuf[...] = jnp.zeros_like(vbuf)

        def start(e, _):
            blk, (ck, cv) = copies(e)

            @pl.when(blk >= 0)
            def _():
                ck.start()
                cv.start()
            return 0

        def wait(e, _):
            blk, (ck, cv) = copies(e)

            @pl.when(blk >= 0)
            def _():
                ck.wait()
                cv.wait()
            return 0

        lax.fori_loop(0, K, start, 0)
        lax.fori_loop(0, K, wait, 0)
        see = tok_ref[...] <= pos_ref[r]                    # (1, K bs)
        s = lax.dot_general(q_ref[...], kbuf[...].reshape(K * bs, d),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(see, s, NEG)
        p = jnp.where(see, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
        l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        o = lax.dot_general(p.astype(vbuf.dtype),
                            vbuf[...].reshape(K * bs, d),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        o_ref[...] = o / l

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, G),
            in_specs=[
                pl.BlockSpec((None, Hg, d), lambda r, g, *_: (r, g, 0)),
                pl.BlockSpec((None, None, 1, K * bs),
                             lambda r, g, *_: (r, g, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
            out_specs=pl.BlockSpec((None, Hg, d), lambda r, g, *_: (r, g, 0)),
            scratch_shapes=[pltpu.VMEM((K, bs, d), pool.dtype),
                            pltpu.VMEM((K, bs, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, K))]),
        out_shape=jax.ShapeDtypeStruct((b, H, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=DECODE_KERNEL, interpret=interpret,
    )(positions.astype(jnp.int32), tables.astype(jnp.int32),
      blocks.reshape(-1).astype(jnp.int32), q.astype(pool.dtype),
      _token_positions(blocks, sp)[:, :, None, :], pool)


def sparse_paged_decode(q, pool, tables, positions, blocks, *, layer: int,
                        sp: SparseConfig):
    """The Mosaic kernel on a TPU (or under ``FLAGS_pallas_interpret``)
    where ``d`` is 128 lanes, the ``jnp`` path elsewhere. As
    :func:`sparse_paged_decode_reference`."""
    on, interp = pallas_mode()
    if on and q.shape[-1] == 128 and sp.block_size % 16 == 0:
        return _sparse_paged_decode_pallas(
            q, pool, tables, positions, blocks, layer=layer, sp=sp,
            interpret=interp)
    return sparse_paged_decode_reference(q, pool, tables, positions, blocks,
                                         layer=layer, sp=sp)


# ---------------------------------------------------------------- prefill
def prefill_token_mask(blocks, t, S: int, sp: SparseConfig):
    """blocks (n, C, G, NB) bool, t (n, C) the queries' positions -> (n,
    G, C, S) int8: query i may read key j."""
    per_key = jnp.repeat(jnp.swapaxes(blocks, 1, 2), sp.block_size,
                         axis=-1)[..., :S]
    causal = jnp.arange(S)[None, None, :] <= t[:, :, None]
    return (per_key & causal[:, None]).astype(jnp.int8)


def sparse_prefill_attention_reference(q, kv, mask, *, groups: int,
                                       rows: int = 256):
    """q (n, C, H, d), kv (n, S, 2 G d) ``[k | v]``, mask (n, G, C, S) ->
    o (n, C, H, d) float32: each head's softmax attention over the keys
    its group's mask admits."""
    n, C, H, d = q.shape
    G = groups
    S = kv.shape[1]
    k, v = (kv[..., i * G * d:(i + 1) * G * d].astype(jnp.float32)
            .reshape(n, S, G, d) for i in range(2))

    def part(args):
        qb, mb = args                   # (n, m, H, d), (n, G, m, S)
        s = jnp.einsum("nmghd,nsgd->ngmhs",
                       qb.astype(jnp.float32).reshape(n, -1, G, H // G, d), k,
                       precision=_HI) / math.sqrt(d)
        see = (mb != 0)[:, :, :, None, :]
        p = jax.nn.softmax(jnp.where(see, s, NEG), -1)
        return jnp.einsum("ngmhs,nsgd->nmghd", p, v,
                          precision=_HI).reshape(n, -1, H, d)

    if C <= rows or C % rows:
        return part((q, mask))
    out = lax.map(part, (
        jnp.moveaxis(q.reshape(n, C // rows, rows, H, d), 1, 0),
        jnp.moveaxis(mask.reshape(n, G, C // rows, rows, S), 2, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(n, C, H, d)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _sparse_prefill_pallas(q, kv, mask, kv_len, *, groups, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, C, H, d = q.shape
    G = groups
    Hg = H // G
    S = kv.shape[1]
    tq = 256 if C % 256 == 0 else C
    tk = 512 if S % 512 == 0 else S
    nj = S // tk
    scale = 1.0 / math.sqrt(d)
    prec = _HI if q.dtype == jnp.float32 else None

    def edge(i, kvl):
        """The last key tile the queries of tile ``i`` may see."""
        return (kvl[0] - C + (i + 1) * tq - 1) // tk

    def kernel(kvl, q_ref, k_ref, v_ref, m_ref, o_ref, acc, mx, l):
        i, j = pl.program_id(2), pl.program_id(3)

        @pl.when(j == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
            mx[...] = jnp.full_like(mx, NEG)
            l[...] = jnp.zeros_like(l)

        @pl.when(j <= edge(i, kvl))
        def _():
            kt, vt = k_ref[...], v_ref[...]
            see = m_ref[...].astype(jnp.float32) > 0
            for hh in range(Hg):
                s = lax.dot_general(q_ref[:, hh * d:(hh + 1) * d], kt,
                                    (((1,), (1,)), ((), ())),
                                    precision=prec,
                                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(see, s, NEG)
                m_new = jnp.maximum(mx[hh], s.max(-1, keepdims=True))
                p = jnp.where(see, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(mx[hh] - m_new)
                l[hh] = alpha * l[hh] + p.sum(-1, keepdims=True)
                acc[hh] = alpha * acc[hh] + lax.dot_general(
                    p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
                    precision=prec, preferred_element_type=jnp.float32)
                mx[hh] = m_new

        @pl.when(j == nj - 1)
        def _():
            for hh in range(Hg):
                o_ref[:, hh * d:(hh + 1) * d] = (
                    acc[hh] / jnp.maximum(l[hh], 1e-30)).astype(o_ref.dtype)

    seen = lambda i, j, kvl: jnp.minimum(j, edge(i, kvl))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n, G, C // tq, nj),
            in_specs=[
                # a group's heads are a lane-aligned slice of a token's
                # row, as the projection leaves it: no transpose
                pl.BlockSpec((None, tq, Hg * d),
                             lambda r, g, i, j, kvl: (r, i, g)),
                pl.BlockSpec((None, tk, d),
                             lambda r, g, i, j, kvl: (r, seen(i, j, kvl), g)),
                pl.BlockSpec((None, tk, d), lambda r, g, i, j, kvl: (
                    r, seen(i, j, kvl), G + g)),
                pl.BlockSpec((None, None, tq, tk), lambda r, g, i, j, kvl: (
                    r, g, i, seen(i, j, kvl)))],
            out_specs=pl.BlockSpec((None, tq, Hg * d),
                                   lambda r, g, i, j, kvl: (r, i, g)),
            scratch_shapes=[pltpu.VMEM((Hg, tq, d), jnp.float32),
                            pltpu.VMEM((Hg, tq, 1), jnp.float32),
                            pltpu.VMEM((Hg, tq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, C, H * d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=PREFILL_KERNEL, interpret=interpret,
    )(jnp.reshape(kv_len, (1,)).astype(jnp.int32), q.reshape(n, C, H * d),
      kv, kv, mask)
    return out.reshape(n, C, H, d)


def sparse_prefill_attention(q, kv, mask, kv_len, *, groups: int):
    """A prefill chunk's attention under its per-token selection: the C
    queries at positions ``kv_len - C .. kv_len - 1`` over the first
    ``kv_len`` rows of ``kv`` (the mask is causal already; ``kv_len``
    tells the kernel which key tiles no query can see, and those it
    never loads). The Mosaic kernel on a TPU (or under
    ``FLAGS_pallas_interpret``) where ``d`` is 128 lanes and the chunk
    and the cache whole tiles, the ``jnp`` path elsewhere. As
    :func:`sparse_prefill_attention_reference`."""
    n, C, H, d = q.shape
    S = kv.shape[1]
    on, interp = pallas_mode()
    if on and d == 128 and C % 32 == 0 and (S % 512 == 0 or S % 128 == 0
                                            and S <= 2048):
        return _sparse_prefill_pallas(q, kv, mask, kv_len, groups=groups,
                                      interpret=interp)
    return sparse_prefill_attention_reference(q, kv, mask, groups=groups)
