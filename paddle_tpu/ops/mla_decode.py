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
``mla_paged_decode`` in traces. Both append an ACTIVE row's new token at
``positions[r]`` and attend to it. An IDLE row, whose table row is the
scratch block (a free slot, at whatever position the step has advanced
it to), writes nothing into the pool and its output is zeros.

The kernel is one loop over a flat work list (:func:`mla_walk`): the
active rows' blocks that hold a cached token, row-major, a row's last
item also taking its new token. Blocks arrive through a ring of buffers
whose DMAs run ahead across row boundaries, and a row's query, new token
and group of 8 pool rows arrive while the row before it is computed.
The online softmax of the row being walked is the only state, so VMEM
does not grow with the slots, and a step's time follows its active rows
and their blocks. An idle row costs one DMA of zeros.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.ops.fused_decode import walk_list

NEG_INF = -1e30
KERNEL_NAME = "mla_paged_decode"
# the pool's block 0, which no row owns: an idle slot's table row is all
# of it (``serving.pool.SCRATCH_BLOCK``)
SCRATCH_BLOCK = 0
# Block buffers of the walk: an item's block is started `_RING - 1` items
# ahead of its merge, across rows. Chosen on a TPU v5e (PERF.md §6, PR 44):
# device ms a call at 128 heads, 28 of 128 slots active, rings of 2 / 3 /
# 4 / 6: 0.246 / 0.221 / 0.218 / 0.219; at 32 heads, 24 of 64: 0.144 /
# 0.123 / 0.123 / 0.123.
_RING = 4


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


def mla_walk_items(positions, tables, block_tokens: int):
    """Items of each row in the decode kernel's walk.

    A row is ACTIVE when its table's first entry is not the pool's
    scratch block (``SCRATCH_BLOCK``, 0): an idle slot's table row is all
    scratch, whatever position the step program has advanced it to. An
    active row at position p has one item for each block that holds a
    cached token, ``ceil(p / block_tokens)``, and at least one: its last
    item also takes the new token (at p = 0 that is all it does). An idle
    row has none.

    ``positions`` and ``tables`` as numpy arrays give a numpy result (the
    serving engine's counter, from its host mirrors); anything else is
    traced with ``jnp`` (the kernel's wrapper, inside the step program).
    """
    xp = np if isinstance(positions, np.ndarray) else jnp
    pos = xp.asarray(positions, xp.int32).reshape(-1)
    active = xp.asarray(tables)[:, 0] != SCRATCH_BLOCK
    cached = (pos + block_tokens - 1) // block_tokens
    return xp.where(active, xp.maximum(cached, 1), 0).astype(xp.int32)


def mla_walk(positions, tables, block_tokens: int):
    """The decode kernel's walk as flat lists (``fused_decode.walk_list``).

    Returns ``(row, chunk, total, idle, n_idle)``: item t < total is
    block ``chunk[t]`` of row ``row[t]``, the active rows' items
    row-major (:func:`mla_walk_items`); ``idle[k]`` for k < n_idle are
    the idle rows in order. Masked reductions only: no search loop, no
    gather or scatter of computed indices.
    """
    items = mla_walk_items(positions, tables, block_tokens)
    b, max_blocks = tables.shape
    row, chunk, total = walk_list(items, b * max_blocks)
    idle, _, n_idle = walk_list((items == 0).astype(items.dtype), b)
    return row, chunk, total, idle, n_idle


def mla_walk_counts(positions, tables, block_tokens: int):
    """``(walked, dense, rows)`` of one decode step's walk, from numpy
    ``positions`` and ``tables`` on the host: the items walked, slots x
    the longest row's items (what a walk of every slot to the longest
    row's length covers), and the active rows. What
    ``serving.ServingEngine`` sums into ``kv_blocks_walked``,
    ``kv_blocks_dense`` and ``kv_rows_walked``."""
    items = mla_walk_items(positions, tables, block_tokens)
    return (int(items.sum()), items.shape[0] * int(items.max(initial=0)),
            int((items > 0).sum()))


def mla_paged_decode_reference(q, new, pool, tables, positions, *, layer: int,
                               d_c: int, scale: float):
    """q (b, H, P) ``[q_c | q_r | 0]``, new (b, P) the new token's row,
    pool (L, NB, BT, P), tables (b, MB), positions (b,) -> (o_c (b, H, d_c)
    float32, pool with ``new`` written at ``positions``). An idle row
    (:func:`mla_walk_items`) writes nothing and returns zeros."""
    BT = pool.shape[2]
    b, MB = tables.shape
    rows = jnp.arange(b)
    active = tables[:, 0] != SCRATCH_BLOCK
    # an idle row's write falls off the pool and is dropped
    bid = jnp.where(active, tables[rows, positions // BT], pool.shape[1])
    pool = pool.at[layer, bid, positions % BT].set(new.astype(pool.dtype),
                                                   mode="drop")
    kv = pool[layer][tables].reshape(b, MB * BT, pool.shape[-1])
    s = jnp.einsum("bhp,bsp->bhs", q.astype(jnp.float32),
                   kv.astype(jnp.float32)) * scale
    live = jnp.arange(MB * BT)[None, None, :] <= positions[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    o = jnp.einsum("bhs,bsc->bhc", p, kv[..., :d_c].astype(jnp.float32))
    return jnp.where(active[:, None, None], o, 0.0), pool


def _mla_paged_decode_pallas(q, new, pool, tables, positions, *, layer: int,
                             d_c: int, scale: float, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, NB, BT, P = pool.shape
    b, H, _ = q.shape
    assert P % 128 == 0 and d_c % 128 == 0 and BT % 8 == 0, (P, d_c, BT)
    ring = _RING

    def kernel(pos_ref, bt_ref, wrow_ref, wchunk_ref, idle_ref, n_ref,
               q_hbm, new_hbm, pool_in, o_hbm, pool_ref,
               kv_s, q_s, new_s, rmw_s, o_s, zero_s, m_s, l_s, acc_s,
               ksem, qsem, nsem, rsem, wsem, osem, zsem):
        del pool_in                     # aliased with pool_ref
        total, n_idle = n_ref[0], n_ref[1]

        def cached(r):                  # blocks that hold a cached token
            return (pos_ref[r] + BT - 1) // BT

        def group(r):                   # the new token's group of 8 rows
            pos = pos_ref[r]
            return pool_ref.at[layer, bt_ref[r, pos // BT],
                               pl.ds((pos % BT) // 8 * 8, 8)]

        # row r's buffers sit in slot s, the parity of its rank among the
        # active rows: the next row's arrive while this one is computed
        def q_read(r, s):
            return pltpu.make_async_copy(q_hbm.at[r], q_s.at[s], qsem.at[s])

        def new_read(r, s):
            return pltpu.make_async_copy(new_hbm.at[r], new_s.at[s],
                                         nsem.at[s])

        def rmw_read(r, s):
            return pltpu.make_async_copy(group(r), rmw_s.at[s], rsem.at[s])

        def rmw_write(r, s):
            return pltpu.make_async_copy(rmw_s.at[s], group(r), wsem.at[s])

        def o_write(r, s):
            return pltpu.make_async_copy(o_s.at[s], o_hbm.at[r], osem.at[s])

        def start_row(r, s):
            q_read(r, s).start()
            new_read(r, s).start()
            rmw_read(r, s).start()

        def block_copy(t):
            slot = lax.rem(t, ring)
            return pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[wrow_ref[t], wchunk_ref[t]]],
                kv_s.at[slot], ksem.at[slot])

        def start_block(t):
            # the ring runs ahead across rows; a tail-only item has none
            @pl.when(t < total)
            def _():
                @pl.when(wchunk_ref[t] < cached(wrow_ref[t]))
                def _():
                    block_copy(t).start()

        def zero_copy(k):               # an idle row's output
            return pltpu.make_async_copy(zero_s, o_hbm.at[idle_ref[k]],
                                         zsem.at[0])

        def merge(qv, blk, live):
            s = lax.dot_general(qv, blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(live, s, NEG_INF)                 # (H, w)
            m, l = m_s[...], l_s[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(
                p.astype(blk.dtype), blk[:, :d_c],
                preferred_element_type=jnp.float32)
            m_s[...] = m_new
            l_s[...] = l * alpha + jnp.sum(p, -1, keepdims=True)

        zero_s[...] = jnp.zeros_like(zero_s)

        @pl.when(total > 0)
        def _():
            start_row(wrow_ref[0], 0)

        for t in range(ring - 1):
            start_block(t)

        def body(t, k):
            """Item t, of the k-th active row."""
            r, c = wrow_ref[t], wchunk_ref[t]
            pos, nc = pos_ref[r], cached(r)
            n = jnp.maximum(nc, 1)
            s = lax.rem(k, 2)
            start_block(t + ring - 1)

            @pl.when(t < n_idle)
            def _():
                zero_copy(t).start()

            @pl.when(c == 0)
            def _():
                q_read(r, s).wait()
                m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
                l_s[...] = jnp.zeros_like(l_s)
                acc_s[...] = jnp.zeros_like(acc_s)

            @pl.when(c < nc)
            def _():
                block_copy(t).wait()
                idx = c * BT + lax.broadcasted_iota(jnp.int32, (1, BT), 1)
                merge(q_s[s], kv_s[lax.rem(t, ring)], idx < pos)

            @pl.when(c == 0)
            def _():
                # the next row's q, new token and group of 8, into the
                # slot that the row before this one has written back from
                @pl.when(k > 0)
                def _():
                    rmw_write(r, 1 - s).wait()

                @pl.when(t + n < total)
                def _():
                    start_row(wrow_ref[t + n], 1 - s)

            @pl.when(c == n - 1)
            def _():
                # the new token: merged into its 8-row group in VMEM,
                # attended from there, written back; the block walk above
                # masked it out
                rmw_read(r, s).wait()
                new_read(r, s).wait()
                off8 = (pos % BT) // 8 * 8
                row = lax.broadcasted_iota(jnp.int32, (8, 1), 0) + off8
                rmw_s[s] = jnp.where(
                    row == pos % BT, new_s[s],
                    rmw_s[s].astype(jnp.float32)).astype(rmw_s.dtype)
                rmw_write(r, s).start()
                idx8 = (pos // BT) * BT + off8 + lax.broadcasted_iota(
                    jnp.int32, (1, 8), 1)
                merge(q_s[s], rmw_s[s], idx8 == pos)

                @pl.when(k >= 2)
                def _():
                    o_write(r, s).wait()

                o_s[s] = acc_s[...] / l_s[...]
                o_write(r, s).start()

            return k + (c == n - 1).astype(jnp.int32)

        rows = lax.fori_loop(0, total, body, jnp.int32(0))

        def start_zero(k, carry):
            zero_copy(k).start()
            return carry

        def wait_zero(k, carry):
            zero_copy(k).wait()
            return carry

        lax.fori_loop(jnp.minimum(total, n_idle), n_idle, start_zero, 0)
        lax.fori_loop(0, n_idle, wait_zero, 0)
        # the last row's group and the last two rows' outputs
        for s in range(2):
            @pl.when(rows > s)
            def _():
                o_write(0, s).wait()

        @pl.when(rows > 0)
        def _():
            rmw_write(wrow_ref[0], lax.rem(rows - 1, 2)).wait()

    row, chunk, total, idle, n_idle = mla_walk(positions, tables, BT)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    o, pool = pl.pallas_call(
        kernel,
        in_specs=[smem] * 6 + [hbm] * 3,
        out_specs=[hbm, hbm],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, d_c), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((ring, BT, P), pool.dtype),  # kv_s, the ring
            pltpu.VMEM((2, H, P), pool.dtype),      # q_s
            pltpu.VMEM((2, 1, P), jnp.float32),     # new_s
            pltpu.VMEM((2, 8, P), pool.dtype),      # rmw_s
            pltpu.VMEM((2, H, d_c), jnp.float32),   # o_s
            pltpu.VMEM((H, d_c), jnp.float32),      # zero_s
            pltpu.VMEM((H, 1), jnp.float32),        # m_s
            pltpu.VMEM((H, 1), jnp.float32),        # l_s
            pltpu.VMEM((H, d_c), jnp.float32),      # acc_s
            pltpu.SemaphoreType.DMA((ring,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        input_output_aliases={8: 1},
        name=KERNEL_NAME,
        interpret=interpret,
    )(jnp.asarray(positions, jnp.int32), jnp.asarray(tables, jnp.int32),
      row, chunk, idle, jnp.stack([total, n_idle]).astype(jnp.int32),
      q.astype(pool.dtype),
      # float32, so that one row is a whole tile of the DMA's slice
      new.astype(pool.dtype).astype(jnp.float32)[:, None, :], pool)
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
