"""Fused decode-step (fused_multi_transformer analog) — CPU-side numerics.

The Pallas kernel itself only runs on TPU (tests_tpu/ has the on-chip
parity suite); here the jnp twin `fused_decode_reference` — which the
kernel is tested against on hardware — is validated against the layered
decode path, and the generate() integration is checked end to end.

Reference: paddle/phi/kernels/fusion/gpu/fused_multi_transformer_op.cu
(SURVEY.md §2.2 fusion row, §7 stage 6).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.core.flags import set_flags
from paddle_tpu.inference import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import fused_decode as fd
from paddle_tpu.ops.rope import rope_cos_sin


def tiny_model(nkv=2):
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=3,
                      num_heads=4, num_kv_heads=nkv, intermediate_size=256,
                      max_position_embeddings=512)
    return cfg, LlamaForCausalLM(cfg).bfloat16()


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    set_flags({"FLAGS_fused_decode": True})


def test_build_fused_params_shapes():
    cfg, m = tiny_model()
    p = fd.build_fused_params(m.state_dict(include_buffers=False),
                              cfg.num_layers)
    L, h, hd = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    assert p["wqkv"].shape == (L, h, (cfg.num_heads + 2 * cfg.kv_heads) * hd)
    assert p["wo"].shape == (L, cfg.num_heads * hd, h)
    assert p["wg"].shape == (L, h, cfg.intermediate_size)
    assert p["ln1"].shape == (L, h)


@pytest.mark.parametrize("nkv", [
    # GQA case in the slow lane (tier-1 budget): GQA reference parity is
    # sibling-covered by test_generate_fused_matches_unfused + the
    # interpret-kernel twins
    pytest.param(2, marks=pytest.mark.slow),
    4,
])  # GQA and MHA
def test_reference_step_matches_layered_decode(nkv):
    """One fused_decode_reference step == the layered cache forward."""
    cfg, m = tiny_model(nkv)
    state = m.state_dict(include_buffers=False)
    plan = m.fused_decode_plan(state)
    assert plan is not None
    b, prompt, S = 2, 7, 128
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, prompt)))

    # layered prefill + one layered decode step
    cache = m.init_cache(b, S)
    logits, cache = m(ids, cache=cache, start_pos=0)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)
    logits2, cache2 = m(tok[:, None], cache=cache, start_pos=prompt)

    # fused reference step from the same stacked cache
    kv = jnp.stack([jnp.concatenate(
        [c["k"].reshape(b, S, -1), c["v"].reshape(b, S, -1)], axis=-1)
        for c in cache])
    cos, sin = rope_cos_sin(S, cfg.head_dim, base=cfg.rope_base)
    x = plan["embed"](tok, prompt)
    x, kv = fd.fused_decode_reference(
        x, plan["params"], kv, prompt, cos[prompt:prompt + 1],
        sin[prompt:prompt + 1], num_heads=cfg.num_heads,
        num_kv_heads=cfg.kv_heads, eps=cfg.rms_norm_eps)
    fused_logits = plan["head"](x)

    ref = np.asarray(logits2[:, -1, :], np.float32)
    got = np.asarray(fused_logits, np.float32)
    assert np.argmax(ref, -1).tolist() == np.argmax(got, -1).tolist()
    np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)
    # cache rows at `prompt` were appended
    kref = cache2[1]["k"][:, prompt].reshape(b, -1)
    kgot = kv[1, :, prompt, :kref.shape[-1]]
    np.testing.assert_allclose(np.asarray(kgot, np.float32),
                               np.asarray(kref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_generate_fused_matches_unfused():
    cfg, m = tiny_model()
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 9)))
    set_flags({"FLAGS_fused_decode": False})
    out_ref = generate(m, prompt, max_new_tokens=16, temperature=0.0)
    m._generate_jit_cache = {}
    set_flags({"FLAGS_fused_decode": True})
    out_fused = generate(m, prompt, max_new_tokens=16, temperature=0.0)
    assert np.asarray(out_ref).tolist() == np.asarray(out_fused).tolist()


def test_plan_gates_on_quantized_state():
    cfg, m = tiny_model()
    state = m.state_dict(include_buffers=False)
    bad = {k: v for k, v in state.items()
           if "q_proj" not in k}          # missing keys -> no plan
    assert m.fused_decode_plan(bad) is None


@pytest.mark.slow
def test_gpt_fused_reference_matches_unfused():
    """arch='gpt' jnp twin == the layered GPT decode, token for token."""
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel

    paddle_tpu.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=3,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    g = GPTPretrainModel(cfg)
    g.eval()
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 7)))
    set_flags({"FLAGS_fused_decode": False})
    out_ref = generate(g, prompt, max_new_tokens=12, temperature=0.0)
    g._generate_jit_cache = {}
    set_flags({"FLAGS_fused_decode": True})
    out_fused = generate(g, prompt, max_new_tokens=12, temperature=0.0)
    assert np.asarray(out_ref).tolist() == np.asarray(out_fused).tolist()


def test_quantize_kv_cache_roundtrip():
    """int8 cache quant: shapes, per-head scales, small roundtrip error."""
    rng = np.random.RandomState(0)
    L, b, S, nkv, hd = 2, 3, 64, 2, 64
    kv = jnp.asarray(rng.randn(L, b, S, 2 * nkv * hd), jnp.float32)
    q, scales = fd.quantize_kv_cache(kv, nkv)
    assert q.dtype == jnp.int8 and q.shape == kv.shape
    assert scales.shape == (L, 1, 2 * nkv * hd)
    # scales are lane-replicated per head
    sc = np.asarray(scales).reshape(L, 2 * nkv, hd)
    assert (sc == sc[:, :, :1]).all()
    deq = np.asarray(q, np.float32) * np.asarray(scales)[:, None]
    err = np.abs(deq - np.asarray(kv))
    step = np.repeat(sc[:, None, None, :, 0], hd, axis=-1)
    assert (err <= 0.5 * step + 1e-6).all()   # within half a quant step


def test_decode_block_plan_cache_wbytes_recorded():
    plan = fd.decode_block_plan(128, 256, 128, 32, 256, wbytes=2)
    assert plan["cache_wbytes"] == 2
    plan8 = fd.decode_block_plan(128, 256, 128, 32, 256, wbytes=2,
                                 cache_wbytes=1)
    assert plan8["cache_wbytes"] == 1


def test_moe_plan_threads_cache_wbytes():
    """arch='moe' plans carry a decode_block_plan whose cache_wbytes the
    kernel consistency-checks against the actual cache dtype."""
    from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    paddle_tpu.seed(0)
    cfg = MixtralConfig(vocab_size=256, hidden_size=128,
                        intermediate_size=256, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_position_embeddings=512,
                        num_experts=8, top_k=2)
    m = MixtralForCausalLM(cfg).bfloat16()
    plan = m.fused_decode_plan(m.state_dict(include_buffers=False),
                               probe=True)
    assert plan["arch"] == "moe"
    assert plan["blocks"]["cache_wbytes"] == 2
    # a bf16 plan driving an int8 cache (or vice versa) must be refused
    r = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    L, h, hd, nkv, nh, E, ffn = 2, 256, 64, 2, 4, 8, 256
    params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
              "wqkv": f(L, h, (nh + 2 * nkv) * hd), "wo": f(L, nh * hd, h),
              "ln2": jnp.ones((L, h), jnp.bfloat16), "gate": f(L, E, h),
              "weg": f(L, E, h, ffn), "weu": f(L, E, h, ffn),
              "wed": f(L, E, ffn, h)}
    kv = f(L, 1, 128, 2 * nkv * hd)
    with pytest.raises(AssertionError, match="cache"):
        fd._fused_decode_moe_pallas(
            f(1, h), params, kv, 5, num_heads=nh, num_kv_heads=nkv,
            head_dim=hd, top_k=2, blocks={"cache_wbytes": 1},
            interpret=True)
    # on a kernel-eligible backend the dispatcher refuses BEFORE its
    # Pallas-failure fallback, so a stale plan can never silently demote
    # decode to the jnp reference (the pure-reference CPU path ignores
    # `blocks` — checked by the fused-path tests running f32 caches)
    cos = jnp.zeros((1, hd), jnp.float32)
    set_flags({"FLAGS_pallas_interpret": True})
    try:
        with pytest.raises(ValueError, match="cache"):
            fd.fused_decode_step(
                f(1, h), params, kv, 5, cos, cos, num_heads=nh,
                num_kv_heads=nkv, arch="moe", top_k=2,
                blocks={"cache_wbytes": 1})
    finally:
        set_flags({"FLAGS_pallas_interpret": False})


def test_pick_expert_blocks_nbuf_accounting():
    """The triple-buffered (prefetch-two-ahead) pipeline budgets 3 expert
    block sets: under a tight budget nbuf=3 must pick blocks no larger
    than nbuf=2 would, and both stay 128-lane multiples."""
    h, ffn = 1024, 4096
    j2, f2 = fd._pick_expert_blocks(ffn, h, fixed_bytes=0, wbytes=2,
                                    budget=40 * 2 ** 20, nbuf=2)
    j3, f3 = fd._pick_expert_blocks(ffn, h, fixed_bytes=0, wbytes=2,
                                    budget=40 * 2 ** 20, nbuf=3)
    assert f3 <= f2 and f3 % 128 == 0 and j3 * f3 == ffn
    # roomy budget: whole-ffn blocks either way
    j, fb = fd._pick_expert_blocks(512, 256, fixed_bytes=0, wbytes=2,
                                   nbuf=3)
    assert (j, fb) == (1, 512)


def test_int8_cache_reference_cosine_parity():
    """Reference twin, int8 KV cache (prefill = calibration) vs bf16
    cache: same greedy token, cosine > 0.99 on the logits."""
    cfg, m = tiny_model()
    state = m.state_dict(include_buffers=False)
    plan = m.fused_decode_plan(state)
    b, prompt, S = 2, 7, 128
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, prompt)))
    cache = m.init_cache(b, S)
    logits, cache = m(ids, cache=cache, start_pos=0)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)
    kv = jnp.stack([jnp.concatenate(
        [c["k"].reshape(b, S, -1), c["v"].reshape(b, S, -1)], axis=-1)
        for c in cache])
    cos, sin = rope_cos_sin(S, cfg.head_dim, base=cfg.rope_base)
    x = plan["embed"](tok, prompt)

    x16, _ = fd.fused_decode_reference(
        x, plan["params"], kv, prompt, cos[prompt:prompt + 1],
        sin[prompt:prompt + 1], num_heads=cfg.num_heads,
        num_kv_heads=cfg.kv_heads, eps=cfg.rms_norm_eps)
    kv8, scales = fd.quantize_kv_cache(kv, cfg.kv_heads)
    x8, kv8b = fd.fused_decode_reference(
        x, plan["params"], kv8, prompt, cos[prompt:prompt + 1],
        sin[prompt:prompt + 1], num_heads=cfg.num_heads,
        num_kv_heads=cfg.kv_heads, eps=cfg.rms_norm_eps, kv_scales=scales)
    assert kv8b.dtype == jnp.int8
    l16 = np.asarray(plan["head"](x16), np.float32)
    l8 = np.asarray(plan["head"](x8), np.float32)
    assert np.argmax(l16, -1).tolist() == np.argmax(l8, -1).tolist()
    for r in range(b):
        a, c = l16[r], l8[r]
        cossim = (a * c).sum() / (np.linalg.norm(a) * np.linalg.norm(c))
        assert cossim > 0.99, cossim


@pytest.mark.slow
def test_generate_int8_cache_matches_bf16():
    """generate(cache_dtype=int8): greedy tokens match the bf16-cache run
    (tiny model; int8 cache noise stays below the argmax margin)."""
    cfg, m = tiny_model()
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 9)))
    out16 = generate(m, prompt, max_new_tokens=12, temperature=0.0)
    m._generate_jit_cache = {}
    out8 = generate(m, prompt, max_new_tokens=12, temperature=0.0,
                    cache_dtype=jnp.int8)
    assert np.asarray(out16).tolist() == np.asarray(out8).tolist()


def test_generate_int8_cache_requires_fused_plan():
    cfg, m = tiny_model()
    prompt = jnp.zeros((1, 4), jnp.int32)
    set_flags({"FLAGS_fused_decode": False})
    with pytest.raises(ValueError, match="int8"):
        generate(m, prompt, max_new_tokens=4, cache_dtype=jnp.int8)


class TestInterpretKernelParity:
    """The Pallas kernel itself, on CPU via interpret mode — the
    CI-side guard for the batched-head attention + int8 cache paths
    (tests_tpu/ re-runs these shapes on the real chip)."""

    @pytest.fixture(autouse=True)
    def _interp(self):
        set_flags({"FLAGS_pallas_interpret": True})
        yield
        set_flags({"FLAGS_pallas_interpret": False})

    # nkv=2 (dkv=64) is below the kernel's 128-lane gate and rides the
    # jnp reference — sibling-covered by test_generate_fused_matches_
    # unfused, so it runs tier-2; nkv=4 is the real interpret kernel
    @pytest.mark.parametrize(
        "nkv", [pytest.param(2, marks=pytest.mark.slow), 4])
    def test_llama_generate_token_exact(self, nkv):  # GQA and MHA o-proj
        cfg, m = tiny_model(nkv)                     # (sum-trick o-proj)
        rng = np.random.RandomState(1)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 9)))
        set_flags({"FLAGS_pallas_interpret": False})
        out_ref = generate(m, prompt, max_new_tokens=12, temperature=0.0)
        m._generate_jit_cache = {}
        set_flags({"FLAGS_pallas_interpret": True})
        out_k = generate(m, prompt, max_new_tokens=12, temperature=0.0)
        assert np.asarray(out_ref).tolist() == np.asarray(out_k).tolist()

    @pytest.mark.slow
    def test_llama_int8_cache_token_exact(self):
        cfg, m = tiny_model()
        rng = np.random.RandomState(2)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 9)))
        set_flags({"FLAGS_pallas_interpret": False})
        out_ref = generate(m, prompt, max_new_tokens=12, temperature=0.0,
                           cache_dtype=jnp.int8)
        m._generate_jit_cache = {}
        set_flags({"FLAGS_pallas_interpret": True})
        out_k = generate(m, prompt, max_new_tokens=12, temperature=0.0,
                         cache_dtype=jnp.int8)
        assert np.asarray(out_ref).tolist() == np.asarray(out_k).tolist()

    @pytest.mark.slow
    def test_gpt_generate_token_exact(self):
        from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel

        paddle_tpu.seed(0)
        cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                        num_heads=2, max_position_embeddings=256,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        g = GPTPretrainModel(cfg)
        g.eval()
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 7)))
        set_flags({"FLAGS_pallas_interpret": False})
        out_ref = generate(g, prompt, max_new_tokens=10, temperature=0.0)
        g._generate_jit_cache = {}
        set_flags({"FLAGS_pallas_interpret": True})
        out_k = generate(g, prompt, max_new_tokens=10, temperature=0.0)
        assert np.asarray(out_ref).tolist() == np.asarray(out_k).tolist()

    @pytest.mark.slow
    def test_moe_generate_token_exact(self):
        # slow lane (tier-1 budget): the bf16 moe path is sibling-covered
        # not-slow by test_moe_generate_int8_cache_token_exact (same
        # end-to-end pipeline) + the prefetch many-slots case
        from paddle_tpu.models.mixtral import (MixtralConfig,
                                               MixtralForCausalLM)

        paddle_tpu.seed(0)
        cfg = MixtralConfig(vocab_size=256, hidden_size=128,
                            intermediate_size=256, num_layers=2,
                            num_heads=4, num_kv_heads=2,
                            max_position_embeddings=512, num_experts=8,
                            top_k=2)
        mm = MixtralForCausalLM(cfg).bfloat16()
        mm.eval()
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (1, 7)))
        set_flags({"FLAGS_pallas_interpret": False})
        out_ref = generate(mm, prompt, max_new_tokens=8, temperature=0.0)
        mm._generate_jit_cache = {}
        set_flags({"FLAGS_pallas_interpret": True})
        out_k = generate(mm, prompt, max_new_tokens=8, temperature=0.0)
        assert np.asarray(out_ref).tolist() == np.asarray(out_k).tolist()

    @staticmethod
    def _moe_setup(b, ffn=512, E=8, k=2, L=3):
        S, hd, h = 256, 64, 256
        nkv, rep = 2, 2
        nh = nkv * rep
        r = np.random.RandomState(0)
        f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
        params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
                  "wqkv": f(L, h, (nh + 2 * nkv) * hd),
                  "wo": f(L, nh * hd, h),
                  "ln2": jnp.ones((L, h), jnp.bfloat16),
                  "gate": f(L, E, h),
                  "weg": f(L, E, h, ffn), "weu": f(L, E, h, ffn),
                  "wed": f(L, E, ffn, h)}
        return params, f(b, h), f(L, b, S, 2 * nkv * hd), nh, nkv, hd, S

    @pytest.mark.slow  # tier-1 budget: the granular int8 append check is
    # sibling-covered not-slow by the end-to-end int8 generate twin
    @pytest.mark.parametrize("b", [1, 2])
    def test_moe_int8_cache_kernel_parity(self, b):
        """The MoE kernel's int8 KV-cache mode (k-scales folded into the
        block-diagonal q, v-scales on the attention output, quantized RMW
        append) vs the jnp reference — b=1 and b=2, CPU interpret."""
        params, x, kv, nh, nkv, hd, S = self._moe_setup(b)
        pos = 130
        cos, sin = rope_cos_sin(S, hd)
        kv8, scales = fd.quantize_kv_cache(kv, nkv)
        xr, kvr = jax.jit(lambda *a: fd.fused_decode_reference(
            *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch="moe",
            top_k=2, kv_scales=scales))(
            x, params, kv8, pos, cos[pos:pos + 1], sin[pos:pos + 1])
        xp, kvp = jax.jit(lambda x, p, kv: fd._fused_decode_moe_pallas(
            x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
            top_k=2, eps=1e-5, kv_scales=scales,
            blocks={"cache_wbytes": 1}, interpret=True))(x, params, kv8)
        assert kvp.dtype == jnp.int8
        np.testing.assert_allclose(np.asarray(xp, np.float32),
                                   np.asarray(xr, np.float32),
                                   rtol=5e-2, atol=5e-2)
        # the appended int8 rows must match the reference EXACTLY and no
        # other cache row may be touched
        d = np.abs(np.asarray(kvr, np.int32) - np.asarray(kvp, np.int32))
        touched = sorted(set(np.argwhere(d > 0)[:, 2].tolist()))
        assert touched == [], touched

    def test_moe_prefetch_pipeline_many_slots(self):
        """k=4 over E=16 at b=2 → 8 expert-FFN steps: every buffer of the
        prefetch-two-ahead triple-buffered pipeline is reused at least
        twice, so a wait/start ordering bug would corrupt a slot matmul."""
        params, x, kv, nh, nkv, hd, S = self._moe_setup(
            2, ffn=256, E=16, k=4, L=2)
        pos = 77
        cos, sin = rope_cos_sin(S, hd)
        xr, _ = jax.jit(lambda *a: fd.fused_decode_reference(
            *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch="moe",
            top_k=4))(x, params, kv, pos, cos[pos:pos + 1],
                      sin[pos:pos + 1])
        xp, _ = jax.jit(lambda x, p, kv: fd._fused_decode_moe_pallas(
            x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
            top_k=4, eps=1e-5, interpret=True))(x, params, kv)
        np.testing.assert_allclose(np.asarray(xp, np.float32),
                                   np.asarray(xr, np.float32),
                                   rtol=5e-2, atol=5e-2)

    @pytest.mark.slow
    def test_moe_generate_int8_cache_token_exact(self):
        """generate(cache_dtype=int8) on Mixtral through the interpret-mode
        kernel == the jnp-reference int8 run, token for token."""
        from paddle_tpu.models.mixtral import (MixtralConfig,
                                               MixtralForCausalLM)

        paddle_tpu.seed(0)
        cfg = MixtralConfig(vocab_size=256, hidden_size=128,
                            intermediate_size=256, num_layers=2,
                            num_heads=4, num_kv_heads=2,
                            max_position_embeddings=512, num_experts=8,
                            top_k=2)
        mm = MixtralForCausalLM(cfg).bfloat16()
        mm.eval()
        # decisive routing: near-tie experts can flip on one bf16 ulp
        for layer in mm.model.layers:
            layer.moe.gate.proj.weight = layer.moe.gate.proj.weight * 8.0
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 7)))
        set_flags({"FLAGS_pallas_interpret": False})
        out_ref = generate(mm, prompt, max_new_tokens=8, temperature=0.0,
                           cache_dtype=jnp.int8)
        mm._generate_jit_cache = {}
        set_flags({"FLAGS_pallas_interpret": True})
        out_k = generate(mm, prompt, max_new_tokens=8, temperature=0.0,
                         cache_dtype=jnp.int8)
        assert np.asarray(out_ref).tolist() == np.asarray(out_k).tolist()

    def test_qsplit_int8_weights_kernel(self):
        """The 7B code path (qkv column split + int8 weights) through the
        interpret-mode kernel, single step vs the reference."""
        L, b, S, hd, h, ffn = 2, 4, 256, 64, 256, 384
        nh = nkv = 4
        dq, dkv = nh * hd, nkv * hd
        blocks = {"q_split": 2, "qblk": 384, "ffn_blocks": 2, "fblk": 256,
                  "ffn_pad": 512}
        r = np.random.RandomState(0)
        params = {"ln1": jnp.ones((L, h), jnp.bfloat16),
                  "ln2": jnp.ones((L, h), jnp.bfloat16)}
        shapes = {"wqkv": (L, h, dq + 2 * dkv), "wo": (L, dq, h),
                  "wg": (L, h, ffn), "wu": (L, h, ffn), "wd": (L, ffn, h)}
        for k, s in shapes.items():
            params[k] = jnp.asarray(r.randint(-127, 128, s), jnp.int8)
            params[f"{k}_s"] = jnp.full((L, 1, s[-1]), 4e-4, jnp.float32)
        params = fd._pad_ffn(params, blocks["ffn_pad"])
        x = jnp.asarray(r.randn(b, h) * 0.05, jnp.bfloat16)
        kv = jnp.asarray(r.randn(L, b, S, 2 * dkv) * 0.05, jnp.bfloat16)
        pos = 77
        cos, sin = rope_cos_sin(S, hd)
        xr, _ = jax.jit(lambda *a: fd.fused_decode_reference(
            *a, num_heads=nh, num_kv_heads=nkv, eps=1e-5))(
            x, params, kv, pos, cos[pos:pos + 1], sin[pos:pos + 1])
        xp, _ = jax.jit(lambda x, p, kv: fd._fused_decode_pallas(
            x, p, kv, pos, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
            eps=1e-5, blocks=blocks, interpret=True))(x, params, kv)
        np.testing.assert_allclose(np.asarray(xp, np.float32),
                                   np.asarray(xr, np.float32),
                                   rtol=5e-2, atol=5e-2)


# ------------------------------------------------ the paged kernel's walk

def _paged_setup(arch, int8_pool):
    """A ragged batch over a tiny pool (BT 16, 4 blocks a slot, so
    `max_seq_len` 64): an idle slot, a row below one group of 8, a row on
    a block boundary, a row at `max_seq_len` - 1, a released row whose
    position the program advanced (its table row is scratch), a row in
    the middle of a block."""
    L, h, hd, ffn, BT, MB = 2, 128, 64, 256, 16, 4
    nh, nkv = (2, 2) if arch == "gpt" else (4, 2)
    dq, dkv = nh * hd, nkv * hd
    positions = np.asarray([0, 5, 32, MB * BT - 1, 11, 21], np.int32)
    live = [False, True, True, True, False, True]
    b = len(positions)
    r = np.random.RandomState(3)
    f = lambda *s: jnp.asarray(r.randn(*s) * 0.05, jnp.bfloat16)
    params = {"ln1": 1 + f(L, h), "ln2": 1 + f(L, h),
              "wqkv": f(L, h, dq + 2 * dkv), "wo": f(L, dq, h),
              "wg": f(L, h, ffn), "wd": f(L, ffn, h)}
    if arch == "gpt":
        params.update(ln1_b=f(L, h), ln2_b=f(L, h), bqkv=f(L, dq + 2 * dkv),
                      bo=f(L, h), bg=f(L, ffn), bd=f(L, h))
    else:
        params["wu"] = f(L, h, ffn)
    NB = 1 + b * MB
    tables = np.zeros((b, MB), np.int32)          # 0 is the scratch block
    for i in range(b):
        if live[i]:
            tables[i] = 1 + i * MB + np.arange(MB)
    pool = f(L, NB, BT, 2 * dkv)
    scales = None
    if int8_pool:
        scales = jnp.asarray(
            0.002 + 0.001 * r.rand(L, b, 2 * dkv), jnp.float32)
        pool = jnp.asarray(r.randint(-127, 128, pool.shape), jnp.int8)
    cos, sin = rope_cos_sin(MB * BT, hd)
    return dict(x=f(b, h), params=params, pool=pool, tables=tables,
                positions=positions, cos=cos[positions], sin=sin[positions],
                kw=dict(num_heads=nh, num_kv_heads=nkv, eps=1e-5, arch=arch,
                        kv_scales=scales), hd=hd)


@pytest.mark.parametrize("K1", [1, 3], ids=["decode", "tail3"])
@pytest.mark.parametrize("int8_pool", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("arch", ["gpt", "llama"])
def test_paged_kernel_ragged_batch(arch, int8_pool, K1):
    """The paged kernel (interpret mode) on rows of every kind at once,
    against the jnp reference: the step's output and the pool. A tail of
    one token is a decode step; a tail of three is a verify step, whose
    row at `max_seq_len` - 1 appends two tokens past its table (they go
    to the scratch block, which is left out of the comparison)."""
    s = _paged_setup(arch, int8_pool)
    b, h = s["x"].shape
    tables, positions = s["tables"], s["positions"]
    BT = s["pool"].shape[2]
    cap = tables.shape[1] * BT
    if K1 == 1:
        x, ref, cos, sin = (s["x"], fd.fused_paged_decode_reference,
                            s["cos"], s["sin"])
    else:
        x = jnp.asarray(np.random.RandomState(4).randn(b, K1, h) * 0.05,
                        jnp.bfloat16)
        ref = fd.fused_paged_verify_reference
        tail = positions[:, None] + np.arange(K1)
        cos, sin = rope_cos_sin(cap + K1, s["hd"])
        cos, sin = cos[tail], sin[tail]
    xr, pr = jax.jit(lambda x, p, pool: ref(
        x, p, pool, tables, positions, cos, sin, **s["kw"]))(
        x, s["params"], s["pool"])
    # the kernel takes the tail token-major flat
    xk, pk = jax.jit(lambda x, p, pool: fd._fused_paged_decode_pallas(
        x, p, pool, tables, positions, head_dim=s["hd"], interpret=True,
        **s["kw"]))(
        x if K1 == 1 else x.transpose(1, 0, 2).reshape(K1 * b, h),
        s["params"], s["pool"])
    xk, xr = np.asarray(xk, np.float32), np.asarray(xr, np.float32)
    if K1 > 1:
        # a tail token past the cap has no place in the table: its output
        # is garbage by contract (the engine never commits it)
        inside = (tail < cap)[..., None]
        xk = np.where(inside, xk.reshape(K1, b, h).transpose(1, 0, 2), 0)
        xr = np.where(inside, xr, 0)
    np.testing.assert_allclose(xk, xr, rtol=2e-2, atol=2e-2)
    # block 0 is the scratch block: with a tail, what idle rows and the
    # row at the cap append there collides, in no order that is promised
    lo = 0 if K1 == 1 else 1
    pr, pk = np.asarray(pr, np.float32)[:, lo:], \
        np.asarray(pk, np.float32)[:, lo:]
    np.testing.assert_allclose(pk, pr, atol=1.0 if int8_pool else 2e-2)
    # nothing but the appended rows is written
    p0 = np.asarray(s["pool"], np.float32)[:, lo:]
    for i, pos in enumerate(positions):
        for q in range(pos, min(pos + K1, cap)):
            bid = tables[i, q // BT] - lo
            if bid >= 0:
                p0[:, bid, q % BT] = pk[:, bid, q % BT]
    np.testing.assert_array_equal(pk, p0)


def test_paged_walk_list():
    """`paged_walk`: each row's own blocks, row-major, as long as
    `paged_walk_blocks` says; numpy in gives numpy out and agrees with the
    traced list; a full batch of equal rows is the dense walk."""
    BT, MB = 16, 4
    pos = np.asarray([0, 5, 32, 63, 11, 21], np.int32)
    nc, walked, dense = fd.paged_walk_blocks(pos, BT)
    # whole groups of 8 below the position, in blocks
    assert nc.tolist() == [0, 0, 2, 4, 1, 1]
    assert int(walked) == 8 and int(dense) == 6 * 4
    row, chunk, total = fd.paged_walk(pos, BT, MB)
    assert isinstance(row, np.ndarray) and row.shape == (len(pos) * MB,)
    assert row.dtype == chunk.dtype == np.int32 and int(total) == walked
    assert list(zip(row[:8].tolist(), chunk[:8].tolist())) == [
        (2, 0), (2, 1), (3, 0), (3, 1), (3, 2), (3, 3), (4, 0), (5, 0)]
    assert row.max() < len(pos) and chunk.min() >= 0 and chunk.max() < MB
    jrow, jchunk, jtotal = jax.jit(
        lambda p: fd.paged_walk(p, BT, MB))(jnp.asarray(pos))
    assert (np.asarray(jrow) == row).all()
    assert (np.asarray(jchunk) == chunk).all()
    assert int(jtotal) == total
    equal = np.full(5, 40, np.int32)
    _, walked, dense = fd.paged_walk_blocks(equal, BT)
    row, chunk, total = fd.paged_walk(equal, BT, MB)
    assert int(total) == int(walked) == int(dense) == 15
    assert row[:15].tolist() == [r for r in range(5) for _ in range(3)]
    assert chunk[:15].tolist() == [0, 1, 2] * 5
    full = np.full(3, MB * BT - 1, np.int32)        # every entry a pair
    row, chunk, total = fd.paged_walk(full, BT, MB)
    assert int(total) == 3 * MB and chunk.tolist() == list(range(MB)) * 3
    _, walked, dense = fd.paged_walk_blocks(np.zeros(3, np.int32), BT)
    assert int(walked) == 0 and int(dense) == 0
    assert int(fd.paged_walk(np.zeros(3, np.int32), BT, MB)[2]) == 0


def test_engine_counts_the_walk_and_idle_rows_cost_none():
    """`engine.stats` sums the length of `paged_walk`'s list and its dense
    counterpart over the landed steps, from the host's positions; and the
    step program leaves an idle row (scratch table row) at position 0, so
    the device's positions stay the host's and the row has no pair."""
    from paddle_tpu import serving
    cfg, m = tiny_model(4)
    m.eval()
    eng = serving.ServingEngine(m, max_slots=4, block_tokens=16,
                                max_seq_len=128, prefix_caching=False)
    rng = np.random.RandomState(5)
    for n, new in ((20, 14), (37, 6), (9, 10)):
        eng.submit(serving.Request(rng.randint(3, 512, (n,)),
                                   max_new_tokens=new))
    walked = dense = 0
    while not eng.idle:
        steps0 = eng.stats["steps"]
        eng.step()
        if eng.stats["steps"] > steps0:
            t = fd.paged_walk(eng._positions, 16, 8)[2]
            d = fd.paged_walk_blocks(eng._positions, 16)[2]
            walked, dense = walked + int(t), dense + int(d)
        if not eng._dirty and eng._dev is not None:
            # between uploads the program has advanced the live rows only
            dev = np.asarray(eng._dev[1])
            idle = [i for i, sl in enumerate(eng._slots) if sl is None]
            assert (dev[idle] == 0).all(), dev
    assert eng.stats["steps"] >= 12
    assert eng.stats["kv_blocks_walked"] == walked > 0
    assert eng.stats["kv_blocks_dense"] == dense > walked
    eng.reset_stats()
    assert eng.stats["kv_blocks_walked"] == eng.stats["kv_blocks_dense"] == 0
    eng.close()


def test_vmem_mib_flag_dispatch():
    """FLAGS_vmem_mib: >0 overrides; -1 asks the Mosaic probe, which
    raises off-TPU (no silent table answer); 0 = kind table on a TPU,
    the v5e planning size for interpret mode elsewhere."""
    from paddle_tpu.ops.fused_decode import _vmem_mib, _VMEM_MIB_OFF_TPU
    try:
        set_flags({"FLAGS_vmem_mib": 192})
        assert _vmem_mib() == 192
        set_flags({"FLAGS_vmem_mib": -1})
        with pytest.raises(RuntimeError, match="needs a TPU"):
            _vmem_mib()
        set_flags({"FLAGS_vmem_mib": 0})
        assert _vmem_mib() == _VMEM_MIB_OFF_TPU
    finally:
        set_flags({"FLAGS_vmem_mib": 0})
