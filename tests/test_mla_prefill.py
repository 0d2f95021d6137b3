"""The prefill's expanded latent attention (``ops.mla_prefill``): the
flash kernel (``mla_flash_prefill``, in interpret mode here) against the
``jnp`` reference over rows, cached prefixes, block counts and head
counts; the one predicate that says which of the two an attention takes;
the models' ``mla_expanded`` through the kernel; and the engine's
``prefill_attn_calls`` against the plan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import serving
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import xing4
from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                           DeepseekV2ForCausalLM)
from paddle_tpu.ops import mla_decode
from paddle_tpu.ops import mla_prefill as mp

DN, DR, DV = 16, 8, 16      # toy head sizes: each padded to 128 lanes


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_pallas_interpret": False})


def f32(a):
    return np.asarray(a, np.float32)


def operands(b, s, R, H, dtype, seed=0, dims=(DN, DR, DV)):
    """The contract's operands in the model's layout: q_n, q_r, k_n
    (b, S, H, d_n), v (b, S, H, d_v), k_r."""
    dn, dr, dv = dims
    S = R + s
    rng = np.random.default_rng(seed)
    mk = lambda *sh: jnp.asarray(rng.standard_normal(sh), dtype)
    return (mk(b, s, H, dn), mk(b, s, H, dr), mk(b, S, H, dn),
            mk(b, S, H, dv), mk(b, S, dr))


def flash(q_n, q_r, k_n, v, k_r, scale, R):
    return mp.mla_flash_prefill(
        q_n, q_r, jnp.swapaxes(k_n, 1, 2), jnp.swapaxes(v, 1, 2), k_r,
        scale=scale, start_pos=R)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("H", [4, 3])
@pytest.mark.parametrize("s", [128, 256, 384])
@pytest.mark.parametrize("R", [0, 128])
@pytest.mark.parametrize("b", [1, 2])
def test_kernel_matches_the_reference(b, R, s, H, dtype):
    set_flags({"FLAGS_pallas_interpret": True})
    ops = operands(b, s, R, H, dtype, seed=b + R + s + H)
    assert mp.kernel_plan(s, R + s, R, DN, DR, DV) is not None
    got = flash(*ops, 0.3, R)
    want = mp.reference(*ops, 0.3, R)
    assert got.shape == want.shape == (b, s, H * DV)
    assert got.dtype == want.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert np.abs(f32(got) - f32(want)).max() < tol
    assert np.abs(f32(want)).max() > 0.5


@pytest.mark.parametrize("s, R", [(1024, 0), (768, 256), (640, 128)])
def test_kernel_walks_blocks_of_512_and_the_keys_left_over(s, R):
    """Several key blocks a query block, unmasked ones among them, and
    (768 + 256 = 1,024 aside) keys left over behind the last block of
    512, which only the last query blocks reach."""
    set_flags({"FLAGS_pallas_interpret": True})
    ops = operands(1, s, R, 2, jnp.float32, seed=s)
    plan = mp.kernel_plan(s, R + s, R, DN, DR, DV, 4)
    assert plan["tk"] == 512 and s % plan["tq"] == 0
    got, want = flash(*ops, 0.3, R), mp.reference(*ops, 0.3, R)
    assert np.abs(f32(got) - f32(want)).max() < 2e-5


def test_a_query_sees_the_keys_up_to_its_own():
    """Changing the keys and values behind a query's position changes
    nothing for it; changing its own does."""
    set_flags({"FLAGS_pallas_interpret": True})
    s, R, cut = 256, 128, 100
    q_n, q_r, k_n, v, k_r = operands(1, s, R, 2, jnp.float32)
    base = flash(q_n, q_r, k_n, v, k_r, 0.3, R)
    bump = lambda a, at: a.at[:, at:].add(1.0)
    later = flash(q_n, q_r, bump(k_n, R + cut + 1), bump(v, R + cut + 1),
                  bump(k_r, R + cut + 1), 0.3, R)
    assert np.abs(f32(later - base))[0, :cut + 1].max() == 0
    assert np.abs(f32(later - base))[0, cut + 1:].max() > 1e-3
    own = flash(q_n, q_r, k_n, bump(v, R + cut), k_r, 0.3, R)
    assert np.abs(f32(own - base))[0, cut].max() > 1e-3
    assert np.abs(f32(own - base))[0, :cut].max() == 0


def test_the_plan_takes_what_the_kernel_can_walk():
    sizes = (128, 64, 128)
    # no TPU and no interpret flag: the reference, whatever the shape
    assert mp.kernel_plan(256, 256, 0, *sizes) is None
    set_flags({"FLAGS_pallas_interpret": True})
    assert mp.kernel_plan(256, 256, 0, *sizes) == dict(tq=256, tk=256)
    assert mp.kernel_plan(3584, 3584, 0, *sizes) == dict(tq=512, tk=512)
    assert mp.kernel_plan(3328, 3584, 256, *sizes) == dict(tq=256, tk=512)
    assert mp.kernel_plan(384, 384, 0, *sizes) == dict(tq=128, tk=384)
    # queries that are not whole blocks of 128, keys that are not
    assert mp.kernel_plan(200, 200, 0, *sizes) is None
    assert mp.kernel_plan(128, 228, 100, *sizes) is None
    # the cache is longer than the prefix and the block's own rows
    assert mp.kernel_plan(128, 512, 128, *sizes) is None
    # a position only the program knows
    assert mp.kernel_plan(128, 256, jnp.int32(128), *sizes) is None
    assert mp.kernel_plan(128, 256, np.int32(128), *sizes) is None
    traced = []
    jax.jit(lambda p: traced.append(
        mp.kernel_plan(128, 256, p, *sizes)) or p)(128)
    assert traced == [None]
    # a head's keys and values past VMEM
    assert mp.kernel_plan(512, 65536, 65024, *sizes) is None
    assert mp.kernel_plan(4096, 4096, 0, *sizes, itemsize=4) is not None


def tiny_attention(S, dtype=jnp.float32):
    cfg = xing4.Xing4Config.tiny()
    paddle_tpu.seed(0)
    m = xing4.Xing4ForCausalLM(cfg)
    state = {k: v.astype(dtype) for k, v in m.trainable_state().items()}
    w = xing4._sub(state, "model.layers.1.self_attn.")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, S, cfg.hidden_size)), dtype)
    cos, sin = xing4.rope_tables(cfg, jnp.arange(S))
    return cfg, w, xing4.mla_project(w, cfg, x, cos, sin)


def test_mla_expanded_dispatches_on_its_input(monkeypatch):
    """``mla_expanded`` takes the kernel where the plan takes the shapes
    and the reference elsewhere, and the two agree."""
    cfg, w, (q_n, q_r, lat) = tiny_attention(256)
    calls = []
    monkeypatch.setattr(
        mp, "mla_flash_prefill",
        lambda *a, _f=mp.mla_flash_prefill, **kw: (
            calls.append(kw["start_pos"]), _f(*a, **kw))[1])
    want = xing4.mla_expanded(w, cfg, q_n, q_r, lat, 0)
    assert calls == []              # a CPU without the flag
    set_flags({"FLAGS_pallas_interpret": True})
    got = xing4.mla_expanded(w, cfg, q_n, q_r, lat, 0)
    assert calls == [0]
    assert np.abs(f32(got - want)).max() < 1e-5
    # the last 128 queries behind a prefix of 128 cached rows
    tail = xing4.mla_expanded(w, cfg, q_n[:, 128:], q_r[:, 128:], lat, 128)
    assert calls == [0, 128]
    assert np.abs(f32(tail - want[:, 128:])).max() < 1e-5
    # shapes the kernel does not take: 100 queries; a traced position
    xing4.mla_expanded(w, cfg, q_n[:, :100], q_r[:, :100], lat[:, :100], 0)
    jax.jit(lambda p: xing4.mla_expanded(
        w, cfg, q_n[:, 128:], q_r[:, 128:], lat, p))(128)
    assert calls == [0, 128]


def test_absorbed_and_expanded_attention_agree_through_the_kernel():
    """``tests/test_xing4.py``'s check of the two forms of MLA, with the
    expanded form through the flash kernel: the last of 128 positions."""
    set_flags({"FLAGS_pallas_interpret": True})
    cfg, w, (q_n, q_r, lat) = tiny_attention(128)
    assert xing4._attn_plan(cfg, 128, 128, 0, 4) is not None
    want = xing4.mla_expanded(w, cfg, q_n, q_r, lat, 0)[:, -1]
    lanes = mla_decode.pool_lanes(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    q = xing4.mla_absorb_query(w, cfg, q_n[:, -1], q_r[:, -1], lanes)
    rows = mla_decode.pad_lanes(lat, lanes)
    s = jnp.einsum("bhp,bsp->bhs", q, rows) * cfg.softmax_scale
    o_c = jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(s, -1),
                     rows[..., :cfg.kv_lora_rank])
    got = xing4.mla_absorb_out(w, cfg, o_c)
    assert np.abs(f32(got - want)).max() < 1e-5
    assert np.abs(f32(want)).max() > 1e-3


def test_grad_through_the_kernel_is_the_references():
    set_flags({"FLAGS_pallas_interpret": True})
    ops = operands(1, 128, 128, 2, jnp.float32)
    loss = lambda f: lambda *a: (f(*a, 0.3, 128) ** 2).sum()
    got = jax.grad(loss(flash), argnums=(0, 1, 2, 3, 4))(*ops)
    want = jax.grad(loss(mp.reference), argnums=(0, 1, 2, 3, 4))(*ops)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        assert np.abs(f32(g - w)).max() < 1e-4
    assert np.abs(f32(want[3])).max() > 1e-2


@pytest.mark.parametrize("interpret", [True, False])
def test_engine_counts_the_layers_whose_attention_took_the_kernel(
        monkeypatch, interpret):
    cfg = DeepseekV2Config.tiny()
    paddle_tpu.seed(0)
    m = DeepseekV2ForCausalLM(cfg)
    m.eval()
    set_flags({"FLAGS_pallas_interpret": interpret})
    traced = []
    monkeypatch.setattr(
        mp, "_flash_pallas",
        lambda *a, _f=mp._flash_pallas, **kw: (
            traced.append(a[0].shape[2]), _f(*a, **kw))[1])
    eng = serving.ServingEngine(m, max_slots=3, block_tokens=128,
                                max_seq_len=512)
    assert eng.stats["prefill_attn_calls"] == 0
    assert eng.meta["prefill_attn_calls"](0, 256) == (
        cfg.num_layers if interpret else 0)
    assert eng.meta["prefill_attn_calls"](0, 200) == 0
    rng = np.random.default_rng(1)
    lengths = (40, 200, 130)        # blocks of 128: s_pad 128, 256, 256
    for n in lengths:               # one row a wave
        eng.submit(serving.Request(
            rng.integers(3, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=2))
        eng.step()
    while not eng.idle:
        eng.step()
    if interpret:
        assert eng.stats["prefill_attn_calls"] == (
            cfg.num_layers * len(lengths))
        # two prefill programs; the kernel's entry is jitted on its own,
        # so the layers of a program share ONE trace of it
        assert traced == [128, 256]
    else:
        assert eng.stats["prefill_attn_calls"] == 0
        assert traced == []
    eng.reset_stats()
    assert eng.stats["prefill_attn_calls"] == 0
    eng.close()


def test_a_llama_engine_has_no_prefill_attn_counter():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    eng = serving.ServingEngine(m, max_slots=2, block_tokens=8,
                                max_seq_len=32)
    assert "prefill_attn_calls" not in eng.stats
    eng.close()
