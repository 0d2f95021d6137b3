"""Routed-expert SwiGLU for one decode step, grouped by expert.

A decode step has at most ``max_slots`` rows and each row picks ``k``
distinct experts of ``E``. What the step costs is the experts' weights:
three ``hidden x ffn`` matrices an expert, read from HBM once a step for
every expert at least one row picked, and not at all for the others.
So the kernel (``moe_grouped_ffn_decode`` in traces) walks the TOUCHED
experts: its grid is (experts, ffn tiles), the touched experts' ids come
first in a scalar-prefetched list, and the steps past the last touched
expert repeat that expert's last tile, which Pallas does not fetch
again. The rows stay where they are: an expert's group is picked out by
its column of the dense (rows, experts) weight matrix, zero for a row
that did not choose it. With the expert's weights as the stationary
operand of the matrix unit, 64 rows stream through a weight tile in the
time 4 would, so gathering each group's rows would save nothing and cost
a sort, a gather and a scatter a layer.

``moe_grouped_ffn_reference`` is the same sum in plain ``jnp`` (every
expert, masked): the CPU path and the parity oracle.

A prefill is the other case: thousands of rows, each through ``k``
experts, so multiplying every row by every touched expert would be
``E / k`` times the flops. There the rows ARE gathered by expert
(``moe_grouped_ffn_prefill`` in traces): each expert's group starts at a
row the DMA engine can address (a multiple of ``_ROW_ALIGN``), the grid
walks the touched experts, an expert's three matrices cross HBM once
(fetched whole into one of two VMEM slots while the expert before it
computes), and its rows stream past them in tiles of ``_row_tile`` rows
with a trip count of ``ceil(rows / tile)``. A group's last tile runs over
into the rows behind it; they are another expert's, whose own tiles are
written later and in order, so nothing is masked. The wrapper is traced
for a few token counts only (``_token_bucket``; the rows that fill a
bucket pick no expert). Where two whole experts do not fit VMEM
(``_slice_width``: 5120 x 1536 needs 94 MB) an expert arrives in slices
of its width instead, each row tile streaming all of them: with 256
rows a tile the matrix unit takes as long over a slice as the slice
takes to arrive (2 flops a weight a row against 2 bytes a weight), so
an expert whose group is several tiles long reads its weights once a
tile and loses nothing by it. ``prefill_path`` says
which of the two a prefill takes here; ``moe_prefill_ragged_dot`` (sort
+ ``jax.lax.ragged_dot``) is the path elsewhere and the parity oracle.

The experts stacked here may be a SHARE of the router's
(:func:`held_rows`): a pick of ``E`` is no expert.
"""

import functools

import jax
import jax.numpy as jnp

KERNEL_NAME = "moe_grouped_ffn_decode"
PREFILL_KERNEL_NAME = "moe_grouped_ffn_prefill"
_ROW_TILE = 128     # rows of one pass over an expert's weights (_row_tile)
_ROW_ALIGN = 16     # a group starts on a whole (16, 128) bf16 tile


def held_rows(idx, offset: int, held: int):
    """The router's picks (global expert ids) -> rows of the ``held``
    experts stacked here, which are the global ids ``offset ..
    offset + held - 1``; a pick that lies on another chip becomes
    ``held``: no expert, which joins no group, weighs nothing in
    :func:`dense_weights` and is not counted by :func:`routing_counts`."""
    local = idx - offset
    return jnp.where((local >= 0) & (local < held), local,
                     held).astype(jnp.int32)


def dense_weights(idx, w, active, num_experts: int):
    """(rows, k) choices and weights -> (rows, E) float32, zero where a
    row did not choose the expert or is not ``active``."""
    hot = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)  # (b, k, E)
    dense = jnp.einsum("bk,bke->be", w.astype(jnp.float32), hot)
    return dense * active.astype(jnp.float32)[:, None]


def routing_counts(idx, active, num_experts: int):
    """int32 (3,): experts that got at least one active row, the fullest
    expert's rows, and active rows x k."""
    hot = jax.nn.one_hot(idx, num_experts, dtype=jnp.int32)
    per = (hot * active.astype(jnp.int32)[:, None, None]).sum((0, 1))
    return jnp.stack([(per > 0).sum(), per.max(), per.sum()]).astype(
        jnp.int32)


def moe_grouped_ffn_reference(x, dense, wg, wu, wd):
    """x (b, C), dense (b, E) -> sum_e dense[:, e] * SwiGLU_e(x), (b, C)
    in ``x.dtype``; the experts' products in float32."""
    f32 = jnp.float32
    h = jnp.einsum("bc,ecf->ebf", x, wg, preferred_element_type=f32)
    u = jnp.einsum("bc,ecf->ebf", x, wu, preferred_element_type=f32)
    a = (jax.nn.silu(h) * u * dense.T[:, :, None]).astype(x.dtype)
    y = jnp.einsum("ebf,efc->bc", a, wd, preferred_element_type=f32)
    return y.astype(x.dtype)


def _pick_tile(ffn: int, limit: int = 512) -> int:
    for t in (limit, 256, 128):
        if t <= ffn and ffn % t == 0:
            return t
    return ffn


def _moe_grouped_ffn_pallas(x, dense, wg, wu, wd, *, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, C = x.shape
    E, _, F = wg.shape
    tf = _pick_tile(F)
    nft = F // tf
    rep = max(tf // 128, 1)
    lanes = min(tf, 128)

    # the touched experts, in order, then the last of them again and again
    touched = (dense != 0).any(axis=0)
    nt = touched.sum().astype(jnp.int32)
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    eids = jnp.where(jnp.arange(E) < nt, order,
                     order[jnp.maximum(nt - 1, 0)])
    # an expert's column of weights, one value a row, across 128 lanes
    wcol = jnp.broadcast_to(dense.T[:, :, None], (E, b, lanes))

    def kernel(eids_ref, nt_ref, x_ref, wcol_ref, wg_ref, wu_ref, wd_ref,
               o_ref, acc_s):
        del eids_ref
        g, ft = pl.program_id(0), pl.program_id(1)

        @pl.when((g == 0) & (ft == 0))
        def _():
            acc_s[...] = jnp.zeros_like(acc_s)

        @pl.when(g < nt_ref[0])
        def _():
            xv = x_ref[...]
            h = jnp.dot(xv, wg_ref[...], preferred_element_type=jnp.float32)
            u = jnp.dot(xv, wu_ref[...], preferred_element_type=jnp.float32)
            wt = wcol_ref[...]
            if rep > 1:
                wt = jnp.concatenate([wt] * rep, axis=-1)
            a = (h * jax.nn.sigmoid(h) * u * wt).astype(xv.dtype)
            acc_s[...] += jnp.dot(a, wd_ref[...],
                                  preferred_element_type=jnp.float32)

        @pl.when((g == E - 1) & (ft == nft - 1))
        def _():
            o_ref[...] = acc_s[...].astype(o_ref.dtype)

    def tile(g, ft, nt_ref):
        # past the last touched expert: its last tile again (no fetch)
        return jnp.where(g < nt_ref[0], ft, nft - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, nft),
        in_specs=[
            pl.BlockSpec((b, C), lambda g, ft, e, n: (0, 0)),
            pl.BlockSpec((None, b, lanes), lambda g, ft, e, n: (e[g], 0, 0)),
            pl.BlockSpec((None, C, tf),
                         lambda g, ft, e, n: (e[g], 0, tile(g, ft, n))),
            pl.BlockSpec((None, C, tf),
                         lambda g, ft, e, n: (e[g], 0, tile(g, ft, n))),
            pl.BlockSpec((None, tf, C),
                         lambda g, ft, e, n: (e[g], tile(g, ft, n), 0)),
        ],
        out_specs=pl.BlockSpec((b, C), lambda g, ft, e, n: (0, 0)),
        scratch_shapes=[pltpu.VMEM((b, C), jnp.float32)],
    )
    wbytes = jnp.dtype(wg.dtype).itemsize
    vmem = 2 * 3 * C * tf * wbytes + 6 * b * C * 4 + (8 << 20)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, C), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(eids, nt.reshape(1), x, wcol, wg, wu, wd)


def moe_grouped_ffn_decode(x, dense, wg, wu, wd):
    """The routed experts' part of one decode step: x (b, C), dense
    (b, E) float32 routing weights (:func:`dense_weights`), wg and wu
    (E, C, F), wd (E, F, C) -> (b, C). The Mosaic kernel on a TPU (or
    under ``FLAGS_pallas_interpret``), the ``jnp`` reference elsewhere."""
    from paddle_tpu.core.flags import flag
    from paddle_tpu.ops import use_pallas
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    interp = bool(flag("FLAGS_pallas_interpret")) and not use_pallas()
    if use_pallas() or interp:
        return _moe_grouped_ffn_pallas(x, dense, wg, wu, wd,
                                       interpret=interp)
    return moe_grouped_ffn_reference(x, dense, wg, wu, wd)


def prefill_path(hidden: int, ffn: int) -> str:
    """``"kernel"`` or ``"ragged_dot"``: what the routed experts of a
    prefill of these widths go through HERE. The kernel on a TPU (or
    under ``FLAGS_pallas_interpret``) where it tiles the widths (both
    multiples of 128), ``jax.lax.ragged_dot`` elsewhere. Decided from
    backend and widths when a program is traced; nothing else asks."""
    from paddle_tpu.core.flags import flag
    from paddle_tpu.ops import use_pallas
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    if not (use_pallas() or bool(flag("FLAGS_pallas_interpret"))):
        return "ragged_dot"
    return "kernel" if _slice_width(hidden, ffn) else "ragged_dot"


_VMEM_BUDGET = 100 << 20    # of a v5e's 128 MiB, for one kernel


def _prefill_vmem(C: int, tf: int, tm: int, wbytes: int = 2) -> int:
    """Bytes of VMEM the prefill kernel asks for with weight slots of
    width ``tf``: two slots of three matrices, the row tiles in and out,
    the float32 products of one tile, and room for the compiler."""
    return (2 * 3 * C * tf * wbytes + 3 * tm * C * 2
            + tm * (3 * tf + 2 * C) * 4 + (8 << 20))


def _slice_width(hidden: int, ffn: int) -> int:
    """The width of the weight slots the prefill kernel keeps in VMEM at
    these widths: ``ffn`` (whole experts) where two of them fit, else
    the decode kernel's tile of ``ffn`` (:func:`_pick_tile`), else 0: no
    kernel tiles these widths."""
    if hidden % 128 or ffn % 128:
        return 0
    for tf in (ffn, _pick_tile(ffn)):
        if _prefill_vmem(hidden, tf, 2 * _ROW_TILE) <= _VMEM_BUDGET:
            return tf
    return 0


def moe_prefill_ragged_dot(x, idx, wts, wg, wu, wd):
    """x (T, C), idx and wts (T, k) -> ``sum_k wts[t, k] SwiGLU_idx[t, k]
    (x_t)`` (T, C) by sort + ``jax.lax.ragged_dot`` (no token dropped)."""
    t, c = x.shape
    k = idx.shape[1]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    xs = jnp.take(x, order // k, axis=0)
    sizes = jnp.bincount(flat, length=wg.shape[0]).astype(jnp.int32)
    h = jax.lax.ragged_dot(xs, wg, sizes)
    u = jax.lax.ragged_dot(xs, wu, sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(h) * u, wd, sizes)
    ys = jnp.zeros_like(ys).at[order].set(ys).reshape(t, k, c)
    return jnp.einsum("tk,tkc->tc", wts.astype(x.dtype), ys)


def _row_tile(rows: int, num_experts: int, sliced: bool = False) -> int:
    """Rows of one pass over an expert's weights: 256 where the mean
    group fills them (at the cell's widths a pass of 256 costs the v5e's
    matrix unit 35 us and two of 128 cost 49, PERF.md §6, PR 30), else
    128, a pass of which hides behind the 27 us its expert's 22 MB take
    to arrive. Always 256 where the weights arrive in slices a tile
    (module docstring): a tile of 128 would wait for them."""
    if sliced or rows >= _ROW_TILE * num_experts:
        return 2 * _ROW_TILE
    return _ROW_TILE


def _token_bucket(tokens: int) -> int:
    """Tokens the kernel's wrapper is traced for: the next power of two,
    1,024 at least. Tracing the wrapper costs a serving host half a
    second, and an engine has a prefill program a prompt bucket (14 in
    the cell), so they share four traces; what a bucket adds to a call
    is a longer sort and some unread rows in two gathers."""
    return max(1024, 1 << (tokens - 1).bit_length())


# jitted on its own so that a program's expert layers, and the programs
# of one token bucket, share ONE trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def _moe_prefill_pallas(x, idx, wts, wg, wu, wd, *, tm, tf=None,
                        interpret=False):
    """``idx`` may hold ``E`` (no expert): such a pick joins no group
    and adds nothing to its token's sum. ``tf``: the width of the weight
    slots, ``None`` for whole experts (:func:`_slice_width`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, C = x.shape
    k = idx.shape[1]
    E, _, F = wg.shape
    R, al = t * k, _ROW_ALIGN
    # every group rounded up to ``al``, and one tile of overrun
    N = R + E * (al - 1)
    Rp = -(-N // al) * al + tm

    flat = idx.reshape(-1)
    sizes = (flat[:, None] == jnp.arange(E, dtype=flat.dtype)).sum(
        0, dtype=jnp.int32)
    asize = -(-sizes // al) * al
    astart = jnp.cumsum(asize) - asize
    ntile = -(-sizes // tm)
    voff = jnp.cumsum(ntile) - ntile
    # the aligned layout in ONE stable sort by expert: behind the R
    # picks go ``al - 1`` fillers an expert, of which a group keeps what
    # rounds it up to ``al`` (the rest sort past the last group; a filler
    # holds token 0's row, which nothing reads). Sorts and row gathers
    # only: a gather or scatter of R scalars costs the TPU's compiler
    # seconds a layer.
    experts = jnp.arange(E, dtype=jnp.int32)[:, None]
    kept = (jnp.arange(al - 1, dtype=jnp.int32)[None, :]
            < (asize - sizes)[:, None])
    keys = jnp.concatenate([flat.astype(jnp.int32),
                            jnp.where(kept, experts, E).reshape(-1)])
    token = jnp.pad(jnp.arange(R, dtype=jnp.int32) // k, (0, N - R))
    here = jnp.arange(N, dtype=jnp.int32)
    _, token, came_from = jax.lax.sort((keys, token, here), num_keys=1,
                                       is_stable=True)
    xs = x.at[jnp.pad(token, (0, Rp - N))].get(
        mode="promise_in_bounds")                            # (Rp, C)
    # each pick's row in it: the inverse of that sort
    at = jax.lax.sort((came_from, here), num_keys=1)[1][:R].reshape(t, k)
    # the touched experts, in order, then the last of them again
    nt = (sizes > 0).sum(dtype=jnp.int32)
    by_touch = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    eids = jnp.where(jnp.arange(E) < nt, by_touch,
                     by_touch[jnp.maximum(nt - 1, 0)])

    tf = F if tf is None else tf
    nf = F // tf        # slices of an expert's width; 1: whole experts

    def kernel(eids_ref, nt_ref, astart_ref, ntile_ref, voff_ref,
               x_hbm, wg_hbm, wu_hbm, wd_hbm, o_hbm,
               wg_s, wu_s, wd_s, x_s, o_s, wsem, xsem, osem):
        """Whole experts (``nf`` 1): an expert's weights are fetched
        once, into slot ``g % 2``, while the expert before it computes.
        Slices: every row tile streams its expert's ``nf`` slices, and
        the slices of the whole call form one chain through the two
        slots (slice ``f`` of the call's ``v``-th tile in slot
        ``(v nf + f) % 2``), each fetched while the one before it is
        multiplied."""
        g = pl.program_id(0)
        n_touched = nt_ref[0]

        def weights(e, f, slot):
            if nf == 1:
                srcs = (wg_hbm.at[e], wu_hbm.at[e], wd_hbm.at[e])
            else:
                cols = pl.ds(f * tf, tf)
                srcs = (wg_hbm.at[e, :, cols], wu_hbm.at[e, :, cols],
                        wd_hbm.at[e, cols, :])
            return tuple(
                pltpu.make_async_copy(src, dst.at[slot], wsem.at[slot, i])
                for i, (src, dst) in enumerate(zip(srcs,
                                                   (wg_s, wu_s, wd_s))))

        def rows(e, j):
            return pl.ds(pl.multiple_of(astart_ref[e] + j * tm, al), tm)

        def x_tile(e, j, slot):
            return pltpu.make_async_copy(x_hbm.at[rows(e, j)], x_s.at[slot],
                                         xsem.at[slot])

        def o_tile(e, j):
            return pltpu.make_async_copy(o_s, o_hbm.at[rows(e, j)],
                                         osem.at[0])

        @pl.when((g == 0) & (n_touched > 0))
        def _():
            first = eids_ref[0]
            for cp in weights(first, 0, 0):
                cp.start()
            x_tile(first, 0, 0).start()
            # one write is always in flight, so that every tile waits
            # for the one before it: this first one lands on rows the
            # first expert's own tiles overwrite
            o_tile(first, 0).start()

        @pl.when(g < n_touched)
        def _():
            e, slot = eids_ref[g], g % 2
            if nf == 1:
                for cp in weights(e, 0, slot):
                    cp.wait()

                @pl.when(g + 1 < n_touched)
                def _():
                    for cp in weights(eids_ref[g + 1], 0, 1 - slot):
                        cp.start()

            nj, v0 = ntile_ref[e], voff_ref[e]

            def tile(j, carry):
                xslot = (v0 + j) % 2
                x_tile(e, j, xslot).wait()

                @pl.when(j + 1 < nj)
                def _():
                    x_tile(e, j + 1, 1 - xslot).start()

                @pl.when((j + 1 == nj) & (g + 1 < n_touched))
                def _():
                    x_tile(eids_ref[g + 1], 0, 1 - xslot).start()

                xv = x_s[xslot]
                y = None
                for f in range(nf):
                    ws = slot
                    if nf > 1:
                        ws = ((v0 + j) * nf + f) % 2
                        for cp in weights(e, f, ws):
                            cp.wait()
                        if f + 1 < nf:
                            for cp in weights(e, f + 1, 1 - ws):
                                cp.start()
                        else:
                            @pl.when(j + 1 < nj)
                            def _():
                                for cp in weights(e, 0, 1 - ws):
                                    cp.start()

                            @pl.when((j + 1 == nj) & (g + 1 < n_touched))
                            def _():
                                for cp in weights(eids_ref[g + 1], 0,
                                                  1 - ws):
                                    cp.start()
                    h = jnp.dot(xv, wg_s[ws],
                                preferred_element_type=jnp.float32)
                    u = jnp.dot(xv, wu_s[ws],
                                preferred_element_type=jnp.float32)
                    a = (h * jax.nn.sigmoid(h) * u).astype(xv.dtype)
                    part = jnp.dot(a, wd_s[ws],
                                   preferred_element_type=jnp.float32)
                    y = part if y is None else y + part
                # writes land in order: a group's last tile runs into
                # the next groups' rows, which their own tiles rewrite
                o_tile(e, j).wait()
                o_s[...] = y.astype(o_s.dtype)
                o_tile(e, j).start()
                return carry

            jax.lax.fori_loop(0, nj, tile, 0)

        @pl.when((g == E - 1) & (n_touched > 0))
        def _():
            o_tile(eids_ref[0], 0).wait()

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(E,),
        in_specs=[hbm, hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((2, C, tf), wg.dtype),
            pltpu.VMEM((2, C, tf), wu.dtype),
            pltpu.VMEM((2, tf, C), wd.dtype),
            pltpu.VMEM((2, tm, C), x.dtype),
            pltpu.VMEM((tm, C), x.dtype),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    vmem = _prefill_vmem(C, tf, tm, jnp.dtype(wg.dtype).itemsize)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, C), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem)),
        name=PREFILL_KERNEL_NAME,
        interpret=interpret,
    )(eids, nt.reshape(1), astart, ntile, voff, xs, wg, wu, wd)
    # each token's k rows of the aligned layout, weighted and summed
    # (one (T, C) gather a pick: a (T, k, C) array pads k to a whole
    # tile); a pick of no expert finds a row that nothing wrote
    y = sum(jnp.where(idx[:, j, None] < E,
                      wts[:, j, None].astype(jnp.float32)
                      * out.at[at[:, j]].get(mode="promise_in_bounds"), 0.0)
            for j in range(k))
    return y.astype(x.dtype)


def moe_grouped_ffn_prefill(x, idx, wts, wg, wu, wd):
    """The routed experts of a prefill: x (T, C), idx (T, k) expert
    picks and wts (T, k) their routing weights, wg and wu (E, C, F), wd
    (E, F, C) -> ``sum_k wts[t, k] SwiGLU_idx[t, k](x_t)`` (T, C) in
    ``x.dtype``. The path is :func:`prefill_path`'s."""
    if prefill_path(x.shape[1], wg.shape[2]) != "kernel":
        return moe_prefill_ragged_dot(x, idx, wts, wg, wu, wd)
    from paddle_tpu.ops import use_pallas
    t, E = x.shape[0], wg.shape[0]
    tf = _slice_width(x.shape[1], wg.shape[2])
    # up to the bucket with tokens that pick no expert and weigh nothing
    rows = ((0, _token_bucket(t) - t), (0, 0))
    y = _moe_prefill_pallas(
        jnp.pad(x, rows), jnp.pad(idx, rows, constant_values=E),
        jnp.pad(wts, rows), wg, wu, wd,
        tm=_row_tile(t * idx.shape[1], E, sliced=tf < wg.shape[2]), tf=tf,
        interpret=not use_pallas())
    return y[:t]
