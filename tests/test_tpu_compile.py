"""Kernels of the main path compiled at their real widths for a v5e
that is described and not attached (the TPU's compiler is installed
here): what Mosaic refuses (a slice off the tiling, too much VMEM) it
refuses here, at no chip time. Nothing runs, so nothing here says a
result is right or fast.

Every such compile belongs in THIS file: only one process at a time may
hold the TPU's library, the topology is described inside a fixture (so
every xdist worker collects the same tests and only the one given this
file loads the library), and where it cannot be described the tests
skip.
"""

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    import os
    # the compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu")))
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as e:  # noqa: BLE001 — no compiler, or its lock held
        pytest.skip(f"no v5e topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


# E, C, F, k: Xing4.0's 64 experts of 3584 x 1024, top-4, over the cell's
# median and largest bucket (both row tiles), whole experts in two VMEM
# slots; DeepSeek-V2's 40 held experts of 5120 x 1536, top-6, over its
# largest bucket, an expert in three slices of 512
@pytest.mark.parametrize("widths, positions, tile, slot", [
    ((64, 3584, 1024, 4), 1024, 128, 1024),
    ((64, 3584, 1024, 4), 3584, 256, 1024),
    ((40, 5120, 1536, 6), 3584, 256, 512),
])
def test_moe_prefill_kernel_compiles_at_the_cells_widths(one_chip, widths,
                                                         positions, tile,
                                                         slot):
    """``moe_grouped_ffn_prefill`` at the cells' widths: the weight
    slots fit VMEM, row tiles are DMA'd from 16-aligned offsets."""
    from paddle_tpu.ops import moe_grouped as mg
    E, C, F, k = widths
    assert mg._slice_width(C, F) == slot
    assert mg._row_tile(positions * k, E, slot < F) == tile
    tokens = mg._token_bucket(positions)    # what the wrapper is traced at
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    compiled = jax.jit(lambda *a: mg._moe_prefill_pallas(
        *a, tm=tile, tf=slot)).lower(
        shape((tokens, C)), shape((tokens, k), jnp.int32),
        shape((tokens, k), jnp.float32), shape((E, C, F)),
        shape((E, C, F)), shape((E, F, C))).compile()
    text = compiled.as_text()
    # the kernel, once, and no ragged-dot beside it. (Not ``"ragged" not
    # in text``: the text's table of source locations holds the names of
    # functions this PROCESS traced before, ``moe_ragged`` among them
    # where the worker ran tests/test_xing4.py first.)
    assert mg.PREFILL_KERNEL_NAME in text
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ragged-dot" not in text
    # the aligned copy of the rows and the kernel's output, no more
    rows = tokens * k + E * 16 + tile
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * C * 2


# heads, queries, cached prefix: DeepSeek-V2's 128 heads and Xing4.0's 32
# at the cells' smallest and largest bucket, with and without a prefix
# (behind 256 cached rows 3,584 + 256 keys leave 256 over after the
# blocks of 512)
@pytest.mark.parametrize("H", [128, 32])
@pytest.mark.parametrize("s, R", [(256, 0), (256, 256), (3584, 0),
                                  (3584, 256)])
def test_mla_flash_prefill_compiles_at_the_cells_widths(one_chip,
                                                        monkeypatch, H, s, R):
    """``mla_flash_prefill`` at the published head sizes: a head's keys
    and values fit VMEM, one Mosaic call, no loop over score blocks
    beside it, and nothing kept in HBM but the head-major queries."""
    import paddle_tpu.ops as ops
    from paddle_tpu.ops import mla_prefill as mp
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    dn, dr, dv, S = 128, 64, 128, R + s
    plan = mp.kernel_plan(s, S, R, dn, dr, dv)
    assert plan == dict(tq=512 if s % 512 == 0 else 256, tk=min(512, S))
    shape = lambda *d: jax.ShapeDtypeStruct(d, jnp.bfloat16,
                                            sharding=one_chip)
    compiled = jax.jit(lambda *a: mp.mla_flash_prefill(
        *a, scale=0.1147, start_pos=R)).lower(
        shape(1, s, H, dn), shape(1, s, H, dr), shape(1, H, S, dn),
        shape(1, H, S, dv), shape(1, S, dr)).compile()
    text = compiled.as_text()
    assert mp.KERNEL_NAME in text
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in text
    # under the expanded k and v plus the output: the two head-major
    # copies of the queries (the rotary one padded to 128 lanes)
    expanded = S * H * (dn + dv) * 2 + s * H * dv * 2
    assert compiled.memory_analysis().temp_size_in_bytes < expanded


# MiniCPM-SALA's five kernels at the published widths (32 heads of 128,
# 2 KV heads) and the cell's sizes: 16 slots, pages of 2,048 tokens, 17
# pages a slot, a prefill chunk of 2,048 over the largest bucket
@pytest.mark.parametrize("kernel", [
    "lightning_prefill", "lightning_decode", "sparse_select",
    "sparse_paged_decode", "sparse_prefill_attn"])
def test_sala_kernels_compile_at_the_published_widths(one_chip, kernel):
    from paddle_tpu.ops import lightning_attention as la
    from paddle_tpu.ops import sparse_paged as spg
    sp = spg.SparseConfig()
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    shape = lambda s, dt=bf: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    b, H, d, MB, NB = 16, 32, 128, 17, 273
    tok = shape((1, 2048, H, d))
    calls = {
        "lightning_prefill": (
            lambda *a: la._lightning_prefill_pallas(*a, chunk=256,
                                                    interpret=False),
            (tok, tok, tok, shape((1, H, d, d), f32), shape((1,), i32))),
        "lightning_decode": (
            lambda *a: la._lightning_decode_pallas(*a, layer=11,
                                                   interpret=False),
            (shape((b, H, d)),) * 3 + (shape((12, b, H, d, d), f32),
                                      shape((b,), jnp.bool_))),
        "sparse_select": (
            lambda *a: spg._stage1_pallas(*a, layer=3, sp=sp,
                                          interpret=False),
            (shape((b, H, d)), shape((4, NB, 128, 256)),
             shape((b, MB), i32), shape((b,), i32))),
        "sparse_paged_decode": (
            lambda *a: spg._sparse_paged_decode_pallas(
                *a, layer=3, sp=sp, interpret=False),
            (shape((b, H, d)), shape((4, NB, 2048, 512)),
             shape((b, MB), i32), shape((b,), i32),
             shape((b, 2, sp.max_blocks), i32))),
        "sparse_prefill_attn": (
            lambda *a: spg._sparse_prefill_pallas(
                *a, groups=2, block_size=sp.block_size, interpret=False),
            (tok, shape((1, 32768, 512)), shape((1, 2, 512, 2048)),
             shape((), i32))),
    }
    fn, args = calls[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert kernel in text


# MiniMax-M3's MSA and expert kernels at the published widths (64 heads
# of 128 in 4 groups, 16 held experts of 6144 x 3072, swigluoai) and the
# cell's sizes: 8 slots, pages of 2,048 tokens, 17 pages a slot, blocks
# of 128 and 18 read, a prefill chunk of 2,048 over the largest bucket
@pytest.mark.parametrize("kernel", [
    "sparse_paged_decode", "sparse_prefill_attn", "moe_grouped_ffn_decode",
    "moe_grouped_ffn_prefill"])
def test_minimax_m3_kernels_compile_at_the_published_widths(one_chip,
                                                            kernel):
    from paddle_tpu.ops import moe_grouped as mg
    from paddle_tpu.ops import msa_index as msa
    from paddle_tpu.ops import sparse_paged as spg
    sp = msa.msa_config()
    act = ("swigluoai", 1.702, 7.0)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    shape = lambda s, dt=bf: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    b, H, d, MB, NB, E, C, F = 8, 64, 128, 17, 137, 16, 6144, 3072
    assert (mg._slice_width(C, F), mg._row_tile(2048 * 4, E, True)) == (
        512, 256)
    calls = {
        "sparse_paged_decode": (
            lambda *a: spg._sparse_paged_decode_pallas(
                *a, layer=4, sp=sp, interpret=False),
            (shape((b, H, d)), shape((5, NB, 2048, 1024)),
             shape((b, MB), i32), shape((b,), i32),
             shape((b, 4, sp.max_blocks), i32))),
        "sparse_prefill_attn": (
            lambda *a: spg._sparse_prefill_pallas(
                *a, groups=4, block_size=128, interpret=False),
            (shape((1, 2048, H, d)), shape((1, 32768, 1024)),
             shape((1, 4, 256, 2048)), shape((), i32))),
        "moe_grouped_ffn_decode": (
            lambda *a: mg._moe_grouped_ffn_pallas(*a, act=act),
            (shape((b, C)), shape((b, E), f32), shape((E, C, F)),
             shape((E, C, F)), shape((E, F, C)))),
        "moe_grouped_ffn_prefill": (
            lambda *a: mg._moe_prefill_pallas(*a, tm=256, tf=512, act=act),
            (shape((2048, C)), shape((2048, 4), i32), shape((2048, 4), f32),
             shape((E, C, F)), shape((E, C, F)), shape((E, F, C)))),
    }
    fn, args = calls[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert kernel in text
