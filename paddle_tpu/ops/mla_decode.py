"""Absorbed latent (MLA) attention for one decode step over a paged pool.

DeepSeek-V2's multi-head latent attention caches, per token and layer,
one compressed vector ``c_kv`` (``kv_lora_rank`` wide) and one rotary
key ``k_r`` shared by all heads. In the absorbed form a decode step
never expands them into per-head keys and values: the query's no-rope
part is multiplied into the latent space once (``q_c = q_n W_kvb,k^T``),
scores are ``q_c . c_kv + q_r . k_r``, and the weighted sum is taken
over ``c_kv`` itself (``o_c``); the value projection follows outside.

**Pool layout.** A token's row in the pool is :func:`pool_lanes` wide:
``[c_kv | k_r | 0...]``, the rotary part padded to a whole 128-lane
vector register (512 + 64 + 64 zero lanes = 640 for the published
sizes). The query is laid out the same way (``[q_c | q_r | 0]``), so a
score is ONE contraction over the row, and ``c_kv`` for the weighted sum
is a lane-aligned prefix. The zero lanes cost a ninth more cache bytes
and nothing else; an unpadded 576-lane row would be padded to 640 by
the device's tiled layout anyway.

Two implementations with one contract (``mla_paged_decode``):
``_reference`` in plain ``jnp`` (gathers each row's blocks; the CPU
path and the parity oracle) and ``_pallas``, a Mosaic kernel named
``mla_paged_decode`` in traces: a grid over rows, each row walking its
own blocks through the block table with double-buffered DMA and an
online softmax, so VMEM holds two blocks however many slots there are.
Both append the new token's row at ``positions[r]`` and attend to it.
"""

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
KERNEL_NAME = "mla_paged_decode"


def pool_lanes(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of one token's pool row: the latent, then the rotary key
    padded to a multiple of 128 lanes."""
    return kv_lora_rank + -(-rope_dim // 128) * 128


def pad_lanes(x, lanes: int):
    """Zero-pad the last dim of ``x`` to ``lanes``."""
    extra = lanes - x.shape[-1]
    if extra == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def mla_paged_decode_reference(q, new, pool, tables, positions, *, layer: int,
                               d_c: int, scale: float):
    """q (b, H, P) ``[q_c | q_r | 0]``, new (b, P) the new token's row,
    pool (L, NB, BT, P), tables (b, MB), positions (b,) -> (o_c (b, H, d_c)
    float32, pool with ``new`` written at ``positions``)."""
    BT = pool.shape[2]
    b, MB = tables.shape
    rows = jnp.arange(b)
    bid = tables[rows, positions // BT]
    pool = pool.at[layer, bid, positions % BT].set(new.astype(pool.dtype))
    kv = pool[layer][tables].reshape(b, MB * BT, pool.shape[-1])
    s = jnp.einsum("bhp,bsp->bhs", q.astype(jnp.float32),
                   kv.astype(jnp.float32)) * scale
    live = jnp.arange(MB * BT)[None, None, :] <= positions[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    o = jnp.einsum("bhs,bsc->bhc", p, kv[..., :d_c].astype(jnp.float32))
    return o, pool


def _mla_paged_decode_pallas(q, new, pool, tables, positions, *, layer: int,
                             d_c: int, scale: float, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, NB, BT, P = pool.shape
    b, H, _ = q.shape
    assert P % 128 == 0 and d_c % 128 == 0 and BT % 8 == 0, (P, d_c, BT)

    def kernel(pos_ref, bt_ref, q_ref, new_ref, pool_in, o_ref, pool_ref,
               kv_s, rmw_s, rsem, wsem):
        del pool_in                     # aliased with pool_ref
        r = pl.program_id(0)
        pos = pos_ref[r]
        off8 = (pos % BT) // 8 * 8
        last = pool_ref.at[layer, bt_ref[r, pos // BT], pl.ds(off8, 8)]
        rmw_read = pltpu.make_async_copy(last, rmw_s, wsem.at[0])
        rmw_write = pltpu.make_async_copy(rmw_s, last, wsem.at[0])

        def block_copy(j, slot):
            return pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[r, j]], kv_s.at[slot],
                rsem.at[slot])

        rmw_read.start()
        nbc = (pos + BT - 1) // BT      # blocks that hold a cached token

        @pl.when(nbc > 0)
        def _():
            block_copy(0, 0).start()

        qv = q_ref[...]                                     # (H, P)

        def merge(carry, blk, live):
            m, l, acc = carry
            s = lax.dot_general(qv, blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(live, s, NEG_INF)                 # (H, w)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            acc = acc * alpha + jnp.dot(
                p.astype(blk.dtype), blk[:, :d_c],
                preferred_element_type=jnp.float32)
            return m_new, l * alpha + jnp.sum(p, -1, keepdims=True), acc

        def body(j, carry):
            slot = lax.rem(j, 2)

            @pl.when(j + 1 < nbc)
            def _():
                block_copy(j + 1, 1 - slot).start()

            block_copy(j, slot).wait()
            idx = j * BT + lax.broadcasted_iota(jnp.int32, (1, BT), 1)
            return merge(carry, kv_s[slot], idx < pos)

        carry = lax.fori_loop(0, nbc, body, (
            jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, d_c), jnp.float32)))

        # the new token: merged into its 8-row group in VMEM, attended
        # from there, written back; the block walk above masked it out
        rmw_read.wait()
        row = lax.broadcasted_iota(jnp.int32, (8, 1), 0) + off8
        rmw_s[...] = jnp.where(
            row == pos % BT, new_ref[...].astype(jnp.float32),
            rmw_s[...].astype(jnp.float32)).astype(rmw_s.dtype)
        rmw_write.start()
        idx8 = (pos // BT) * BT + off8 + lax.broadcasted_iota(
            jnp.int32, (1, 8), 1)
        _, l, acc = merge(carry, rmw_s[...], idx8 == pos)
        o_ref[...] = (acc / l).astype(o_ref.dtype)
        rmw_write.wait()

    o, pool = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # positions
            pl.BlockSpec(memory_space=pltpu.SMEM),              # tables
            pl.BlockSpec((None, H, P), lambda r: (r, 0, 0)),    # q
            pl.BlockSpec((None, 1, P), lambda r: (r, 0, 0)),    # new
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),   # pool
        ],
        out_specs=[
            pl.BlockSpec((None, H, d_c), lambda r: (r, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, d_c), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, BT, P), pool.dtype),     # kv_s, double buffer
            pltpu.VMEM((8, P), pool.dtype),         # rmw_s
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(jnp.asarray(positions, jnp.int32), jnp.asarray(tables, jnp.int32),
      q.astype(pool.dtype), new.astype(pool.dtype)[:, None, :], pool)
    return o, pool


def mla_paged_decode(q, new, pool, tables, positions, *, layer: int,
                     d_c: int, scale: float):
    """One layer's absorbed latent attention for every row of a decode
    step, over the paged pool: the Mosaic kernel on a TPU (or under
    ``FLAGS_pallas_interpret``), the ``jnp`` reference elsewhere.
    Arguments and results as :func:`mla_paged_decode_reference`."""
    from paddle_tpu.core.flags import flag
    from paddle_tpu.ops import use_pallas
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    interp = bool(flag("FLAGS_pallas_interpret")) and not use_pallas()
    if use_pallas() or interp:
        return _mla_paged_decode_pallas(
            q, new, pool, tables, positions, layer=layer, d_c=d_c,
            scale=scale, interpret=interp)
    return mla_paged_decode_reference(
        q, new, pool, tables, positions, layer=layer, d_c=d_c, scale=scale)
