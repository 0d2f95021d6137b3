"""The routed experts of a prefill (``ops.moe_grouped``): the grouped
kernel (``moe_grouped_ffn_prefill``, in interpret mode here) against the
``ragged_dot`` path and the decode kernel's ``jnp`` reference over group
shapes that break a naive walk, the one function that says which path a
prefill takes, the plan's meta agreeing with it, the engine's two
counters against the arithmetic, and ``moe_ragged`` on a CPU returning
what it returned before the kernel existed.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import xing4
from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from paddle_tpu.ops import moe_grouped as mg

TM = mg._ROW_TILE


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def f32(a):
    return np.asarray(a, np.float32)


def weights(rng, E, C, F):
    wg, wu = (jnp.asarray(0.05 * rng.standard_normal((E, C, F)),
                          jnp.bfloat16) for _ in range(2))
    return wg, wu, jnp.asarray(0.05 * rng.standard_normal((E, F, C)),
                               jnp.bfloat16)


def picks(rng, sizes, k):
    """(T, k) picks whose expert counts are exactly ``sizes`` and whose
    k picks a row are distinct experts where the counts allow it: the
    flat list dealt out column by column."""
    flat = np.repeat(np.arange(len(sizes)), sizes)
    assert len(flat) % k == 0
    return jnp.asarray(rng.permutation(flat).reshape(k, -1).T, jnp.int32)


# (name, expert sizes, k): E = 4 experts, R = sum(sizes) routed rows
GROUPS = [
    ("an_empty_expert", [40, 0, 24, 16], 2),
    ("one_expert_holds_every_row", [0, 0, 2 * TM + 40, 0], 1),
    ("a_group_ends_inside_a_row_tile", [TM + 5, 3, TM - 1, 2 * TM + 9], 2),
    ("rows_below_one_tile", [3, 1, 0, 2], 2),
    ("rows_not_a_multiple_of_the_tile", [TM, 7, 50, 2 * TM + 1], 1),
    ("the_first_and_last_experts_empty", [0, 33, TM + 31, 0], 2),
]


@pytest.mark.parametrize("sizes, k", [g[1:] for g in GROUPS],
                         ids=[g[0] for g in GROUPS])
def test_prefill_kernel_matches_ragged_dot_and_the_reference(sizes, k):
    rng = np.random.default_rng(len(sizes) + sum(sizes))
    E, C, F = len(sizes), 256, 128
    idx = picks(rng, sizes, k)
    T = idx.shape[0]
    assert np.bincount(np.asarray(idx).reshape(-1),
                       minlength=E).tolist() == sizes
    x = jnp.asarray(rng.standard_normal((T, C)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (T, k)), jnp.float32)
    wg, wu, wd = weights(rng, E, C, F)
    want = jax.jit(mg.moe_prefill_ragged_dot)(x, idx, w, wg, wu, wd)
    set_flags({"FLAGS_pallas_interpret": True})
    assert mg.prefill_path(C, F) == "kernel"
    got = jax.jit(mg.moe_grouped_ffn_prefill)(x, idx, w, wg, wu, wd)
    assert got.shape == (T, C) and got.dtype == x.dtype
    scale = np.abs(f32(want)).max()
    # one rounding to bfloat16 of silu(h) * u for ragged_dot's two, and
    # the routing weights in float32 for its bfloat16
    assert np.abs(f32(got) - f32(want)).max() <= 2 ** -6 * scale
    # against the decode kernel's reference: the same sum over dense
    # (rows, experts) weights
    dense = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].add(w)
    ref = mg.moe_grouped_ffn_reference(x, dense, wg, wu, wd)
    assert np.abs(f32(got) - f32(ref)).max() <= 2 ** -6 * np.abs(
        f32(ref)).max()


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_sliced_prefill_kernel_matches_ragged_dot(monkeypatch, share):
    """Widths at which two whole experts do not fit the (here: lowered)
    VMEM budget: an expert arrives in three slices of 128, every row
    tile streaming all of them. Groups of several tiles, an empty
    expert, and, for a share, picks of no expert (``E``)."""
    E, C, F, k = 4, 256, 384, 3
    monkeypatch.setattr(mg, "_VMEM_BUDGET", mg._prefill_vmem(C, 128, 2 * TM))
    assert mg._slice_width(C, F) == 128 and mg._slice_width(C, 128) == 128
    assert mg._row_tile(8, E, True) == 2 * TM
    rng = np.random.default_rng(7)
    sizes = [4 * TM + 9, 0, 2 * TM + 44, 5]
    flat = np.repeat(np.arange(E), sizes)
    flat = np.concatenate([flat, np.full(
        (-len(flat)) % k + (7 * k if share else 0), E if share else 3)])
    idx = jnp.asarray(rng.permutation(flat).reshape(k, -1).T, jnp.int32)
    T = idx.shape[0]
    x = jnp.asarray(rng.standard_normal((T, C)), jnp.bfloat16)
    w = jnp.where(idx < E, jnp.asarray(rng.uniform(0.2, 1.0, (T, k)),
                                       jnp.float32), 0.0)
    wg, wu, wd = weights(rng, E, C, F)
    want = jax.jit(mg.moe_prefill_ragged_dot)(x, idx, w, wg, wu, wd)
    set_flags({"FLAGS_pallas_interpret": True})
    assert mg.prefill_path(C, F) == "kernel"
    got = jax.jit(lambda *a: mg.moe_grouped_ffn_prefill(*a))(
        x, idx, w, wg, wu, wd)
    assert np.isfinite(f32(got)).all()
    assert np.abs(f32(got) - f32(want)).max() <= 2 ** -6 * np.abs(
        f32(want)).max()
    # no slot wide enough: no kernel at these widths
    monkeypatch.setattr(mg, "_VMEM_BUDGET", 1 << 20)
    assert mg.prefill_path(C, F) == "ragged_dot"


def test_the_cells_widths_get_the_plan_that_fits_vmem():
    # Xing4.0: whole experts, as before; DeepSeek-V2: slices of 512
    assert mg._slice_width(3584, 1024) == 1024
    assert mg._prefill_vmem(5120, 1536, 256) > mg._VMEM_BUDGET
    assert mg._slice_width(5120, 1536) == 512
    assert mg._slice_width(3584, 1000) == 0 == mg._slice_width(200, 128)


def test_a_token_bucket_is_shared_by_the_prompt_buckets_under_it():
    assert [mg._token_bucket(t) for t in (1, 256, 1024, 1025, 2048, 3584)] \
        == [1024, 1024, 1024, 2048, 2048, 4096]
    # the cell's 14 prefill programs trace the wrapper four times
    assert len({(mg._token_bucket(s), mg._row_tile(4 * s, 64))
                for s in range(256, 3585, 256)}) == 4


def test_the_row_tile_follows_the_mean_group():
    # both tiles are walked by the cases above
    assert mg._row_tile(4 * TM - 1, 4) == TM and mg._row_tile(4 * TM, 4) \
        == 2 * TM
    assert {mg._row_tile(sum(g[1]), 4) for g in GROUPS} == {TM, 2 * TM}
    # the cell: 64 experts, 4 picks a position, 14 buckets of 256
    assert [mg._row_tile(4 * s, 64) for s in (256, 1024, 1792, 2048, 3584)] \
        == [128, 128, 128, 256, 256]


@pytest.mark.parametrize("interpret, hidden, ffn, want", [
    (False, 3584, 1024, "ragged_dot"),      # a CPU: no kernel at any width
    (True, 3584, 1024, "kernel"),
    (True, 256, 128, "kernel"),
    (True, 64, 32, "ragged_dot"),           # the tiny model's widths
    (True, 3584, 1000, "ragged_dot"),
    (True, 200, 128, "ragged_dot"),
])
def test_prefill_path_answers_from_backend_and_widths(interpret, hidden, ffn,
                                                      want):
    set_flags({"FLAGS_pallas_interpret": interpret})
    assert mg.prefill_path(hidden, ffn) == want


def test_the_two_kernels_are_told_apart_by_name():
    # the reader of kernels.moe_ffn_roofline matches by substring
    assert mg.PREFILL_KERNEL_NAME == "moe_grouped_ffn_prefill"
    assert mg.KERNEL_NAME not in mg.PREFILL_KERNEL_NAME
    assert mg.PREFILL_KERNEL_NAME not in mg.KERNEL_NAME
    set_flags({"FLAGS_pallas_interpret": True})
    rng = np.random.default_rng(0)
    x = jnp.zeros((8, 128), jnp.bfloat16)
    idx = jnp.zeros((8, 1), jnp.int32)
    args = (x, idx, jnp.ones((8, 1), jnp.float32),
            *weights(rng, 2, 128, 128))
    # a fresh function a flag: traces are cached on the function alone
    text = str(jax.make_jaxpr(lambda *a: mg.moe_grouped_ffn_prefill(*a))(
        *args))
    assert "ragged_dot" not in text and "pallas_call" in text
    assert mg.PREFILL_KERNEL_NAME in text
    set_flags({"FLAGS_pallas_interpret": False})
    text = str(jax.make_jaxpr(lambda *a: mg.moe_grouped_ffn_prefill(*a))(
        *args))
    assert "ragged_dot" in text and "pallas_call" not in text


@functools.lru_cache(maxsize=None)
def small_xing4(hidden: int, ffn: int):
    """Tiny but for the two widths the path is decided on."""
    cfg = Xing4Config.tiny(num_nextn_predict_layers=0, hc_sinkhorn_iters=2,
                           hidden_size=hidden, moe_intermediate_size=ffn)
    paddle_tpu.seed(0)
    m = Xing4ForCausalLM(cfg)
    m.eval()
    return cfg, m


@pytest.mark.parametrize("interpret, hidden, ffn", [
    (False, 128, 128), (True, 128, 128), (True, 64, 32)])
def test_the_plans_meta_agrees_with_the_path(interpret, hidden, ffn):
    cfg, m = small_xing4(hidden, ffn)
    set_flags({"FLAGS_pallas_interpret": interpret})
    from paddle_tpu.inference import _inference_state
    meta = m.fused_decode_plan(_inference_state(m), probe=True)
    assert meta["prefill_moe"] == dict(
        layers=cfg.num_layers - cfg.first_k_dense_replace,
        k=cfg.num_experts_per_tok, path=mg.prefill_path(hidden, ffn))
    assert meta["prefill_moe"]["path"] == (
        "kernel" if interpret and hidden == 128 else "ragged_dot")


@pytest.mark.parametrize("interpret, hidden, ffn", [
    (True, 128, 128), (True, 64, 32), (False, 128, 128)])
def test_engine_counts_a_waves_kernel_calls_and_routed_rows(
        monkeypatch, interpret, hidden, ffn):
    cfg, m = small_xing4(hidden, ffn)
    set_flags({"FLAGS_pallas_interpret": interpret})
    kernel = interpret and hidden == 128
    traced = []
    monkeypatch.setattr(
        mg, "_moe_prefill_pallas",
        lambda *a, _f=mg._moe_prefill_pallas, **kw: (
            traced.append(a[0].shape[0]), _f(*a, **kw))[1])
    eng = serving.ServingEngine(m, max_slots=3, block_tokens=8,
                                max_seq_len=64)
    assert eng.stats["prefill_moe_calls"] == 0
    assert eng.stats["prefill_moe_rows"] == 0
    rng = np.random.default_rng(1)
    lengths = (5, 17, 9, 17)        # buckets of 8: s_pad 8, 24, 16, 24
    rids = []
    for n in lengths:               # one row a wave
        rids.append(eng.submit(serving.Request(
            rng.integers(3, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=3)))
        eng.step()
    while not eng.idle:
        eng.step()
    layers = cfg.num_layers - cfg.first_k_dense_replace
    s_pads = [-(-n // 8) * 8 for n in lengths]
    s = eng.stats
    if kernel:
        assert s["prefill_moe_calls"] == layers * len(lengths)
        assert s["prefill_moe_rows"] == (
            cfg.num_experts_per_tok * sum(s_pads) * layers)
        # each of the three prefill programs went to the kernel once a
        # layer, every one at the same token bucket
        assert traced == 3 * layers * [mg._token_bucket(24)]
    else:
        assert s["prefill_moe_calls"] == s["prefill_moe_rows"] == 0
        assert traced == []
    # the step's own counters are the step's alone
    assert s["moe_layer_steps"] == s["steps"] * layers
    eng.reset_stats()
    assert eng.stats["prefill_moe_calls"] == 0
    eng.close()


def test_a_llama_engine_has_no_prefill_moe_counters():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=8,
                                max_seq_len=32)
    assert "prefill_moe_calls" not in eng.stats
    eng.close()


def test_moe_ragged_on_a_cpu_returns_what_it_returned_before():
    """Bit for bit: the path is unchanged there. The oracle is the body
    ``moe_ragged`` had before the kernel."""
    cfg = Xing4Config.tiny()
    rng = np.random.default_rng(0)
    C, E, F = cfg.hidden_size, cfg.n_routed_experts, cfg.moe_intermediate_size
    n = lambda *shape: jnp.asarray(0.1 * rng.standard_normal(shape),
                                   jnp.float32)
    w = {"gate.weight": n(C, E), "gate.e_score_correction_bias": n(E),
         "experts.w_gate": n(E, C, F), "experts.w_up": n(E, C, F),
         "experts.w_down": n(E, F, C),
         "shared_experts.gate_proj.weight": n(C, F),
         "shared_experts.up_proj.weight": n(C, F),
         "shared_experts.down_proj.weight": n(F, C)}
    x = n(37, C)

    def before(w, cfg, x):
        t, c = x.shape
        k, e = cfg.num_experts_per_tok, cfg.n_routed_experts
        idx, wts = xing4.route(w, cfg, x)
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        xs = jnp.take(x, order // k, axis=0)
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        h = jax.lax.ragged_dot(xs, w["experts.w_gate"], sizes)
        u = jax.lax.ragged_dot(xs, w["experts.w_up"], sizes)
        ys = jax.lax.ragged_dot(jax.nn.silu(h) * u, w["experts.w_down"],
                                sizes)
        ys = jnp.zeros_like(ys).at[order].set(ys).reshape(t, k, c)
        y = jnp.einsum("tk,tkc->tc", wts.astype(x.dtype), ys)
        return y + xing4._swiglu(xing4._sub(w, "shared_experts."), x)

    got = jax.jit(lambda w, x: xing4.moe_ragged(w, cfg, x))(w, x)
    want = jax.jit(lambda w, x: before(w, cfg, x))(w, x)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert np.abs(np.asarray(got)).max() > 0
