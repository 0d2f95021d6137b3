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
"""

import jax
import jax.numpy as jnp

KERNEL_NAME = "moe_grouped_ffn_decode"


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
