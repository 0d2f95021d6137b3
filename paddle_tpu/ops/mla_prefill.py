"""Expanded latent (MLA) attention for a prefill: a causal flash kernel.

A prefill of DeepSeek-V2's multi-head latent attention expands the
cached rows into per-head keys ``k_n`` and values ``v`` (``W_kvb``) and
keeps ONE rotary key ``k_r`` for all heads; a score is
``q_n . k_n + q_r . k_r``. The ``s`` queries sit at positions
``start_pos + arange(s)`` and see the ``S = start_pos + s`` keys up to
their own (bottom-right aligned causal): ``start_pos`` rows of a cached
prefix, then the block's own.

Two implementations with one contract (``q_n (b, s, H, d_n)``, ``q_r
(b, s, H, d_r)``, ``k_r (b, S, d_r)`` -> ``(b, s, H * d_v)``; they
differ in where they want the head axis of ``k_n`` and ``v``):

``reference`` in plain ``jnp`` takes the scores of a block of
``_Q_BLOCK`` queries against ALL keys as one float32 array, masks it and
takes a softmax over it: the CPU path, the parity oracle, and every
shape the kernel does not take. At 128 heads and 3,584 keys that block
is 470 MB, written and read again a dozen times.

``mla_flash_prefill`` (so named in traces) never writes a score to HBM.
Its grid is (row, head, block of queries); a head's whole
``k_n`` and ``v`` and the shared ``k_r`` stay in VMEM across its query
blocks, and each query block walks the key blocks up to its own causal
edge with an online softmax: the blocks every query of it sees whole
without a mask, the one or two on the diagonal masked, those behind the
edge not at all. Operands go to the matrix unit as they come (bf16 in
serving) with float32 accumulation; scores, running maximum, sum and
accumulator are float32; ``p`` is cast to ``v``'s dtype before
``p @ v``: the precision of the reference. Head sizes are zero-padded to
whole 128-lane registers (the rotary 64 becomes 128, as in the pool's
rows). It is forward-only; its ``custom_vjp`` differentiates the
reference.

:func:`kernel_plan` is the one predicate: what ``models.xing4.
mla_expanded`` dispatches on and what a serving plan counts a wave's
``prefill_attn_calls`` with.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.ops.mla_decode import pad_lanes
from paddle_tpu.profiler.parts import part

NEG_INF = -1e30
KERNEL_NAME = "mla_flash_prefill"
_Q_BLOCK = 256      # query rows of one block of the reference
_LANES = 128
_VMEM_BUDGET = 96 << 20     # of a v5e's 128 MiB, for one kernel


def _pad_to(n: int, m: int = _LANES) -> int:
    return -(-n // m) * m


def reference(q_n, q_r, k_n, v, k_r, scale, start_pos):
    """q_n (b, s, H, d_n), q_r (b, s, H, d_r), k_n (b, S, H, d_n),
    v (b, S, H, d_v), k_r (b, S, d_r) -> (b, s, H * d_v). Scores in
    blocks of ``_Q_BLOCK`` query rows, softmax in float32."""
    b, s, H, _ = q_n.shape
    S, dv = v.shape[1], v.shape[-1]
    kpos = jnp.arange(S)

    def block(args):
        qn, qr, qpos = args                    # (b, qb, H, .), (qb,)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_n,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bkd->bhqk", qr, k_r,
                           preferred_element_type=jnp.float32)) * scale
        live = kpos[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(live[None, None], sc, NEG_INF), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    qpos = start_pos + jnp.arange(s)
    if s <= _Q_BLOCK or s % _Q_BLOCK:
        out = block((q_n, q_r, qpos))
    else:
        nq = s // _Q_BLOCK
        split = lambda a: jnp.moveaxis(
            a.reshape(b, nq, _Q_BLOCK, *a.shape[2:]), 1, 0)
        out = jax.lax.map(block, (split(q_n), split(q_r),
                                  qpos.reshape(nq, _Q_BLOCK)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, H, dv)
    return out.reshape(b, s, H * dv)


def _vmem_bytes(tq: int, tk: int, S: int, dn: int, dr: int, dv: int,
                itemsize: int) -> int:
    """What one grid step holds: a head's keys and values and the rotary
    key whole, a block of queries and of outputs, each twice (the
    pipeline's two buffers); the float32 scores, ``p`` and accumulator
    in flight; room for the compiler."""
    held = S * (dn + dv + dr) * itemsize
    blocks = tq * (dn + dr + dv) * itemsize
    work = tq * (3 * tk + 2 * dv) * 4
    return 2 * (held + blocks) + work + (4 << 20)


def kernel_plan(s: int, S: int, start_pos, d_n: int, d_r: int, d_v: int,
                itemsize: int = 2):
    """``{"tq", "tk"}`` (query rows and keys of a block) where the
    kernel takes this attention HERE, else None: on a TPU (or under
    ``FLAGS_pallas_interpret``), ``start_pos`` a Python int with
    ``S == start_pos + s``, ``s`` and ``S`` multiples of 128, and a
    head's keys and values within VMEM. The block sizes follow from the
    shapes alone: 512 keys a block (at 256 the walk is a quarter slower,
    measured) with the keys left over as one shorter block, and 512 or
    256 query rows (as fast as each other; 128 where ``s`` is an odd
    multiple of 128)."""
    from paddle_tpu.core.flags import flag
    from paddle_tpu.ops import use_pallas
    # tpu-lint: allow(host-sync): flag() is a host-side config read
    if not (use_pallas() or bool(flag("FLAGS_pallas_interpret"))):
        return None
    if isinstance(start_pos, bool) or not isinstance(start_pos, int):
        return None
    if start_pos < 0 or s <= 0 or S != start_pos + s or s % 128 or S % 128:
        return None
    tq = next(t for t in (512, 256, 128) if s % t == 0)
    tk = min(512, S)
    if _vmem_bytes(tq, tk, S, _pad_to(d_n), _pad_to(d_r), _pad_to(d_v),
                   itemsize) > _VMEM_BUDGET:
        return None
    return {"tq": tq, "tk": tk}


def _flash_pallas(qn, qr, kn, v, kr, *, scale, start_pos, tq, tk,
                  interpret=False):
    """Head-major, lane-aligned operands: qn (b, H, s, dn), qr
    (b, H, s, dr), kn (b, H, S, dn), v (b, H, S, dv), kr (b, S, dr)
    -> (b, s, H * dv)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, H, s, dn = qn.shape
    S, dr, dv = kn.shape[2], qr.shape[-1], v.shape[-1]
    R = start_pos
    assert S == R + s and s % tq == 0 and tk <= S, (S, R, s, tq, tk)
    whole, tail = divmod(S, tk)     # key blocks of tk, keys of a last one
    nt = (((1,), (1,)), ((), ()))       # a . b^T

    # The body is written in ``lax`` primitives: every ``jnp`` call (an
    # operator too) inside a kernel is traced as a jit of its own, some
    # seventy here, and a serving host traces the kernel for every prefill
    # program: written in ``jnp`` and traced once a layer it cost a
    # 14-program engine 22 s of warm set-up (0.3 s a call, measured).
    f32, i32 = jnp.float32, np.int32
    rows = lambda x, d: lax.broadcast_in_dim(x, (tq, d), (0, 1))
    col = lambda x: lax.expand_dims(x, (1,))

    def kernel(qn_ref, qr_ref, kn_ref, v_ref, kr_ref, o_ref):
        # the first query's position; the key blocks every query of the
        # block sees whole, and those any of them sees
        q_lo = lax.add(lax.mul(pl.program_id(2), i32(tq)), i32(R))
        full = lax.div(lax.add(q_lo, i32(1)), i32(tk))
        edge = lax.div(lax.add(q_lo, i32(tq + tk - 1)), i32(tk))
        q_n, q_r = qn_ref[...], qr_ref[...]

        def walk(masked, width=tk):
            def body(j, carry):
                m, l, acc = carry
                k0 = lax.mul(j, i32(tk))
                ks = pl.ds(pl.multiple_of(k0, 128), width)
                sc = lax.mul(lax.add(
                    lax.dot_general(q_n, kn_ref[ks, :], nt,
                                    preferred_element_type=f32),
                    lax.dot_general(q_r, kr_ref[ks, :], nt,
                                    preferred_element_type=f32)), f32(scale))
                if masked:      # key k0 + c is live for query q_lo + r
                    ahead = lax.sub(
                        lax.broadcasted_iota(jnp.int32, (tq, width), 1),
                        lax.broadcasted_iota(jnp.int32, (tq, width), 0))
                    sc = lax.select(lax.le(ahead, lax.sub(q_lo, k0)), sc,
                                    lax.full_like(sc, NEG_INF))
                m_new = lax.max(m, col(lax.reduce_max(sc, (1,))))
                alpha = lax.exp(lax.sub(m, m_new))
                p = lax.exp(lax.sub(sc, rows(m_new, width)))
                pv = lax.dot_general(
                    lax.convert_element_type(p, v_ref.dtype), v_ref[ks, :],
                    (((1,), (0,)), ((), ())), preferred_element_type=f32)
                return (m_new,
                        lax.add(lax.mul(l, alpha),
                                col(lax.reduce_sum(p, (1,)))),
                        lax.add(lax.mul(acc, rows(alpha, dv)), pv))
            return body

        carry = (lax.full((tq, 1), NEG_INF, f32), lax.full((tq, 1), 0, f32),
                 lax.full((tq, dv), 0, f32))
        # key 0 is live for every query, so the first block walked
        # leaves every row a finite maximum
        carry = lax.fori_loop(0, full, walk(False), carry)
        carry = lax.fori_loop(full, lax.min(edge, i32(whole)), walk(True),
                              carry)
        if tail:
            # the keys left over, for the query blocks that reach them:
            # a loop of no or one round
            carry = lax.fori_loop(i32(whole), lax.max(edge, i32(whole)),
                                  walk(True, tail), carry)
        _, l, acc = carry
        o_ref[...] = lax.convert_element_type(
            lax.div(acc, rows(l, dv)), o_ref.dtype)

    per_head = lambda d: pl.BlockSpec(
        (None, None, S, d), lambda bi, hi, qi: (bi, hi, 0, 0))
    per_block = lambda d: pl.BlockSpec(
        (None, None, tq, d), lambda bi, hi, qi: (bi, hi, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, H, s // tq),
        in_specs=[per_block(dn), per_block(dr), per_head(dn), per_head(dv),
                  pl.BlockSpec((None, S, dr), lambda bi, hi, qi: (bi, 0, 0))],
        out_specs=pl.BlockSpec((None, tq, dv), lambda bi, hi, qi: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s, H * dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        name=KERNEL_NAME,
        interpret=interpret,
    )(qn, qr, kn, v, kr)


def _lanes(x):
    """Zero-pad the last dim of ``x`` to whole 128-lane registers."""
    return pad_lanes(x, _pad_to(x.shape[-1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q_n, q_r, k_n, v, k_r, scale, start_pos, interpret):
    b, s, H, d_n = q_n.shape
    S, d_v = v.shape[2], v.shape[-1]
    plan = kernel_plan(s, S, start_pos, d_n, q_r.shape[-1], d_v,
                       q_n.dtype.itemsize)
    assert plan is not None, (q_n.shape, v.shape, start_pos)
    with part("attn_in"):       # the head-major, lane-padded copies
        operands = (_lanes(jnp.swapaxes(q_n, 1, 2)),
                    _lanes(jnp.swapaxes(q_r, 1, 2)), _lanes(k_n), _lanes(v),
                    _lanes(k_r))
    with part("attn"):
        out = _flash_pallas(*operands, scale=scale, start_pos=start_pos,
                            interpret=interpret, **plan)
        if _pad_to(d_v) != d_v:
            out = out.reshape(b, s, H, -1)[..., :d_v].reshape(b, s, H * d_v)
    return out


def _flash_reference(q_n, q_r, k_n, v, k_r, scale, start_pos):
    return reference(q_n, q_r, jnp.swapaxes(k_n, 1, 2),
                     jnp.swapaxes(v, 1, 2), k_r, scale, start_pos)


def _flash_fwd(q_n, q_r, k_n, v, k_r, scale, start_pos, interpret):
    return (_flash(q_n, q_r, k_n, v, k_r, scale, start_pos, interpret),
            (q_n, q_r, k_n, v, k_r))


def _flash_bwd(scale, start_pos, interpret, operands, g):
    del interpret
    _, vjp = jax.vjp(lambda *a: _flash_reference(*a, scale, start_pos),
                     *operands)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)
# jitted on its own: the layers of one program share one trace of it
_flash_jit = jax.jit(_flash, static_argnums=(5, 6, 7))


def mla_flash_prefill(q_n, q_r, k_n, v, k_r, *, scale, start_pos):
    """The kernel, for an attention that :func:`kernel_plan` takes:
    q_n (b, s, H, d_n), q_r (b, s, H, d_r), the expanded keys and values
    HEAD-MAJOR, k_n (b, H, S, d_n) and v (b, H, S, d_v) (what the
    expansion can write at no cost, so that no operand is transposed on
    the way in), k_r (b, S, d_r) -> (b, s, H * d_v). A ``jax.grad``
    through it differentiates :func:`reference`."""
    from paddle_tpu.ops import use_pallas
    return _flash_jit(q_n, q_r, k_n, v, k_r, float(scale), start_pos,
                      not use_pallas())
