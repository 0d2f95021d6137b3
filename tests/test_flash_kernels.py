"""The flash attention kernels' bodies, in interpret mode on the CPU:
forward and gradients against `_xla_attention` on float32 copies of the
same inputs. (`tests_tpu/test_pallas_parity.py` runs them compiled, at
the cells' widths.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.flags import set_flags
from paddle_tpu.ops import flash_attention as fa


@pytest.fixture
def interpret():
    """Interpret mode, and no trace of the kernels kept from another
    case: the entry is jitted, and a case may spy on what it calls."""
    set_flags({"FLAGS_pallas_interpret": True})
    fa._flash_entry_jit.clear_cache()
    yield
    fa._flash_entry_jit.clear_cache()
    set_flags({"FLAGS_pallas_interpret": False})


def _rand(i, shape, dtype):
    return (jax.random.normal(jax.random.PRNGKey(i), shape) * 0.5).astype(
        dtype)


def _lower_left(sq, sk):
    """A dense bool mask with whole blocks empty at both ends of a row."""
    r, c = np.arange(sq)[:, None], np.arange(sk)[None, :]
    return jnp.asarray((c <= r + 64) & (c >= r - 200))


# name: (sq, sk, heads, kv heads, head size, dtype, causal, extras)
CASES = {
    "d64_bf16": (384, 384, 2, 2, 64, jnp.bfloat16, True, {}),
    "d128_bf16": (384, 384, 2, 2, 128, jnp.bfloat16, True, {}),
    "gqa_2_to_1": (384, 384, 4, 2, 64, jnp.bfloat16, True, {}),
    "not_causal": (256, 384, 2, 2, 64, jnp.bfloat16, False, {}),
    "causal_sq_lt_sk": (128, 384, 2, 2, 64, jnp.bfloat16, True, {}),
    "causal_sq_gt_sk": (384, 128, 2, 2, 64, jnp.bfloat16, True, {}),
    "kv_lens": (256, 384, 2, 2, 64, jnp.bfloat16, False,
                {"kv_lens": [200]}),
    "segments": (384, 384, 2, 2, 64, jnp.bfloat16, True,
                 {"segments": [130]}),
    "window": (384, 384, 2, 2, 64, jnp.bfloat16, True, {"window": 100}),
    "dense_bool_mask": (384, 384, 2, 2, 64, jnp.bfloat16, False,
                        {"mask": True}),
    "float32": (384, 384, 2, 2, 64, jnp.float32, True, {}),
    # 640 keys: a block of 512 and a last one of 128 in the forward; the
    # last query block sees the first key block whole, unmasked
    "short_last_block": (640, 640, 1, 1, 64, jnp.bfloat16, True, {}),
    "short_last_block_not_causal": (128, 640, 1, 1, 128, jnp.bfloat16,
                                    False, {}),
    "short_last_block_dense_mask": (640, 640, 1, 1, 64, jnp.bfloat16, False,
                                    {"mask": True}),
    # short_last_block's input (the one-kernel backward) through the
    # other form of the backward
    "backward_two_kernels": (640, 640, 1, 1, 64, jnp.bfloat16, True,
                             {"two_kernels": True}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_flash_kernels_match_the_float32_reference(case, interpret,
                                                   monkeypatch):
    sq, sk, h, n_kv, d, dtype, causal, extras = CASES[case]
    b = 1
    q = _rand(0, (b, sq, h, d), dtype)
    k = _rand(1, (b, sk, n_kv, d), dtype)
    v = _rand(2, (b, sk, n_kv, d), dtype)
    w = _rand(3, (b, sq, h, d), jnp.float32)      # the output's cotangent
    kv_lens = (jnp.asarray(extras["kv_lens"], jnp.int32)
               if "kv_lens" in extras else None)
    seg_q = seg_k = None
    if "segments" in extras:
        cut = jnp.asarray(extras["segments"])[:, None]
        seg_q = (jnp.arange(sq)[None] >= cut).astype(jnp.int32)
        seg_k = (jnp.arange(sk)[None] >= cut).astype(jnp.int32)
    window = extras.get("window")
    mask = _lower_left(sq, sk) if "mask" in extras else None
    kernels_run = []
    for name in ("_fwd_kernels", "_bwd_dq_kernel", "_bwd_dkv_kernel"):
        def spy(*a, _f=getattr(fa, name), _n=name, **kw):
            kernels_run.append((_n, kw.get("with_dq", False)))
            return _f(*a, **kw)
        monkeypatch.setattr(fa, name, spy)
    if extras.get("two_kernels"):
        monkeypatch.setattr(fa, "_fused_bwd_fits", lambda *a: False)

    def kernel(q, k, v):
        out = fa._flash_call(q, k, v, causal, None, kv_lens, seg_q, seg_k,
                             window=window,
                             mask=fa._kernel_mask(mask, q.shape, k.shape))
        return jnp.sum(out.astype(jnp.float32) * w), out

    def reference(q, k, v):
        out = fa._xla_attention(q, k, v, attn_mask=mask, is_causal=causal,
                                kv_lens=kv_lens, seg_q=seg_q, seg_k=seg_k,
                                window=window)
        return jnp.sum(out * w), out

    (_, out), grads = jax.value_and_grad(kernel, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.value_and_grad(
            reference, (0, 1, 2), has_aux=True)(f32(q), f32(k), f32(v))

    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    two = ("_bwd_dq_kernel", False) in kernels_run
    assert two == bool(extras.get("two_kernels")), kernels_run
    assert ("_bwd_dkv_kernel", not two) in kernels_run, kernels_run
    # float32 inputs reach the products as float32: bf16 operands would
    # leave errors of 1e-3 and more
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, got, ref in zip(("out", "dq", "dk", "dv"), (out, *grads),
                              (want, *want_grads)):
        err = float(jnp.abs(f32(got) - ref).max())
        big = float(jnp.abs(ref).max())
        assert err <= tol * max(big, 1.0), (name, err, big)


def _dots(jaxpr, found):
    """Every dot_general equation under ``jaxpr``, kernels' bodies and
    their loops included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dots(sub, found)
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernels_hand_the_matrix_unit_the_dtype_they_were_given(dtype):
    """The kernels as the chip compiles them (no interpret mode): every
    product's operands are in the inputs' dtype, float32 for float32
    inputs, ``p`` and ``ds`` included, and every product accumulates in
    float32."""
    x = jax.ShapeDtypeStruct((1, 2, 1024, 64), dtype)
    row = jax.ShapeDtypeStruct((1, 2, 1, 1024), jnp.float32)
    traced = [
        jax.make_jaxpr(lambda q, k, v: fa._fwd_kernels(
            q, k, v, True, 0.125))(x, x, x),
        jax.make_jaxpr(lambda *a: fa._bwd_dq_kernel(
            *a, True, 0.125))(x, x, x, x, row, row),
        jax.make_jaxpr(lambda *a: fa._bwd_dkv_kernel(
            *a, True, 0.125, with_dq=True))(x, x, x, x, row, row),
    ]
    for closed, n_products in zip(traced, (2, 3, 5)):
        dots = _dots(closed.jaxpr, [])
        # each loop of a kernel holds one copy of its body's products
        assert dots and len(dots) % n_products == 0, len(dots)
        for eqn in dots:
            assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype]
            assert eqn.outvars[0].aval.dtype == jnp.float32
