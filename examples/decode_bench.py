"""Decode-step benchmark vs HBM roofline (fused_multi_transformer parity).

The reference's inference crown jewel is the fused decode-step kernel
(phi/kernels/fusion/gpu/fused_multi_transformer_op.cu +
masked_multihead_attention): one token per step, the whole layer stack in
one kernel chain. The TPU-native equivalent is the scan-fused decode in
`paddle_tpu.inference.generate` — the entire decode loop is ONE XLA
program, so XLA fuses per-layer matmul→rope→cache-update→attention chains
the way the CUDA kernel hand-fuses them.

Decode is HBM-bandwidth bound: every step must read all parameters once
(batch-amortized) plus each sequence's KV cache. This bench measures
achieved decode tokens/s and compares against that roofline:

    bytes/step  =  param_bytes  +  B · kv_bytes(cache_len)
    roofline tok/s  =  B · HBM_BW / bytes_per_step

Run: python examples/decode_bench.py [--model llama-1b|gpt2-345m]
[--batch 8] [--int8] [--cache_int8]. Prints one JSON line; SCALE.md
records the measured table (fused decode-step kernel, device-clock
timing). The long-context int8-KV-cache row (cache bytes dominate):
python examples/decode_bench.py --model llama-345m --prompt_len 2048
--new_tokens 256 --cache_int8
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# HBM bandwidth by device kind (public spec sheets, GB/s)
HBM_BW = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v4": 1228e9,
    "TPU v6 lite": 1640e9,
}


def build_model(name):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if name == "gpt2-345m":
        from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
        cfg = GPTConfig.gpt2_medium()
        cfg.hidden_dropout_prob = 0.0
        cfg.attention_dropout_prob = 0.0
        m = GPTPretrainModel(cfg).bfloat16()
        m.eval()
        return cfg, m
    if name == "llama-tiny":  # CPU smoke
        cfg = LlamaConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                          num_heads=4, num_kv_heads=4, intermediate_size=256,
                          max_position_embeddings=512)
    elif name == "llama-345m":
        cfg = LlamaConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                          num_heads=16, num_kv_heads=16,
                          intermediate_size=2816,
                          max_position_embeddings=2048)
    elif name == "llama-1b":  # TinyLlama-1.1B shape
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, num_layers=22,
                          num_heads=32, num_kv_heads=4,
                          intermediate_size=5632,
                          max_position_embeddings=2048)
    elif name == "llama2-7b":
        # Llama-2-7B, served int8 weight-only via the stacked-weight
        # engine (inference.stacked): ~6.6 GiB int8 weights + KV cache fit
        # the 16 GiB v5e with ONE weight image; the fused kernel streams
        # qkv in column phases (decode_block_plan q_split) because the 7B
        # attention weights cannot double-buffer whole in VMEM
        cfg = LlamaConfig.llama2_7b()
        return cfg, None          # built via StackedLlamaDecoder below
    elif name == "mixtral-1b":
        # the moe_bench shape (0.93 B total / 0.31 B activated): 12L ×
        # 8 experts top-2 — decodes through the fused MoE kernel, which
        # streams only the routed experts' weights per token
        from paddle_tpu.models.mixtral import (MixtralConfig,
                                               MixtralForCausalLM)
        cfg = MixtralConfig(vocab_size=32000, hidden_size=1024,
                            intermediate_size=2816, num_layers=12,
                            num_heads=16, num_kv_heads=8,
                            max_position_embeddings=2048,
                            num_experts=8, top_k=2)
        m = MixtralForCausalLM(cfg).bfloat16()
        m.eval()
        return cfg, m
    elif name == "deepseek-16b-d4":
        # DeepSeekMoE-16B cross-section (BASELINE #4's first-named MoE):
        # the full 28-layer width — 64 fine-grained experts top-6 + 2
        # shared experts, vocab 102400 — depth-reduced to 4 layers so the
        # layered-prefill + stacked-decode weight pair fits a 16 GiB v5e.
        # The fused kernel streams the 2 shared experts as dense SwiGLU
        # blocks and exactly 6 routed experts per token.
        import dataclasses
        from paddle_tpu.models.mixtral import (MixtralConfig,
                                               MixtralForCausalLM)
        cfg = dataclasses.replace(MixtralConfig.deepseek_moe_16b(),
                                  num_layers=4,
                                  max_position_embeddings=2048)
        m = MixtralForCausalLM(cfg).bfloat16()
        m.eval()
        return cfg, m
    else:
        raise SystemExit(f"unknown model {name}")
    return cfg, LlamaForCausalLM(cfg).bfloat16()


def kv_bytes_per_token(cfg, dtype_bytes=2):
    head_dim = cfg.hidden_size // cfg.num_heads
    nkv = getattr(cfg, "kv_heads", None) or cfg.num_kv_heads
    return 2 * cfg.num_layers * nkv * head_dim * dtype_bytes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="default 8 (1 for mixtral-1b: the fused MoE "
                    "kernel's no-drop gate caps batch at 2)")
    ap.add_argument("--prompt_len", type=int, default=128)
    ap.add_argument("--new_tokens", type=int, default=256)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 (halves the weight stream — "
                    "the fused_multi_transformer_int8 analog)")
    ap.add_argument("--cache_int8", action="store_true",
                    help="int8 KV cache (fused_multi_transformer_int8 "
                    "cache_kv quant analog): prefill calibrates per-head "
                    "scales, decode streams int8 KV — the long-context "
                    "(s >= 2048) row where cache bytes dominate runs "
                    "--prompt_len 2048 --cache_int8")
    ap.add_argument("--traced", action="store_true",
                    help="attach an observability.Tracer for the final "
                    "timed run: emits request spans (TTFT/TPOT/per-chunk "
                    "decode) into the BENCH json and "
                    "/tmp/decode_bench_spans.jsonl — the per-phase "
                    "evidence the SCALE.md re-measure rows ask for")
    ap.add_argument("--report_plan", default=None, metavar="PATH",
                    help="write the analytic roofline plan here; feed it "
                    "to `python examples/scale_report.py --report "
                    "/tmp/decode_bench_prof --plan PATH` for the "
                    "per-phase %%-of-roofline table")
    ap.add_argument("--sanitize", action="store_true",
                    help="pin the warm path: after warmup, one "
                         "generate pair runs under no_recompile "
                         "and dies on any compile "
                         "(paddle_tpu.analysis.runtime)")
    ap.add_argument("--reps", type=int, default=3,
                    help="wall-timing repetitions (CI smoke uses 1)")
    ap.add_argument("--eos", type=int, default=None,
                    help="eos token id: adds a static-path pad-waste "
                    "accounting pass — generate(return_lengths=True) "
                    "reports per-row generated length, and every decode "
                    "step past a row's eos is waste the continuous-"
                    "batching engine (examples/serving_bench.py) "
                    "reclaims")
    ap.add_argument("--device_time", action="store_true",
                    help="force the xplane device-clock pass off-TPU "
                    "(on TPU it always runs; the CPU backend yields no "
                    "device plane and trace start/stop costs ~15 s on "
                    "the bare container, so CPU smoke skips it)")
    ns = ap.parse_args()

    import paddle_tpu
    from paddle_tpu.inference import generate

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    name = ns.model or ("llama-345m" if on_tpu else "llama-tiny")
    if ns.batch is None:
        ns.batch = 1 if name in ("mixtral-1b", "deepseek-16b-d4") else 8
    if not on_tpu:
        ns.batch, ns.prompt_len, ns.new_tokens = 2, 8, 16

    if name == "llama2-7b" and not ns.int8:
        print("note: llama2-7b implies --int8 (bf16 weights alone exceed "
              "a 16 GiB v5e)", file=sys.stderr)
        ns.int8 = True

    paddle_tpu.seed(0)
    cfg, model = build_model(name)
    if model is None:        # stacked-weight engine (7B-class)
        from paddle_tpu.inference.stacked import StackedLlamaDecoder
        model = StackedLlamaDecoder.from_config(cfg, int8=ns.int8)
    n_params = model.num_params()
    moe = name in ("mixtral-1b", "deepseek-16b-d4")
    if moe:
        # the streaming roofline below describes the fused MoE kernel;
        # refuse to silently measure the all-experts scan fallback
        # (an ineligible config raises nothing: it dispatches elsewhere)
        plan = model.fused_decode_plan(model.trainable_state(), probe=True)
        if plan is None:
            raise SystemExit(
                f"{name} config is ineligible for the fused MoE decode "
                "kernel (fused_decode_plan returned None) — it would "
                "silently measure the all-experts scan fallback")
        if ns.batch > plan["max_batch"]:
            raise SystemExit(
                f"{name} fused decode needs batch <= "
                f"{plan['max_batch']}; got {ns.batch}")
    stacked = name == "llama2-7b"
    if stacked:
        state = None              # the engine owns its (int8) stacks
    elif ns.int8:
        from paddle_tpu.quantization import quantize_model, quantized_state
        quantize_model(model)
        state = quantized_state(model)
    else:
        state = model.trainable_state()

    cache_dtype = jnp.int8 if ns.cache_int8 else jnp.bfloat16

    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (ns.batch, ns.prompt_len)))

    # The whole decode loop is ONE dispatch. (a) Completion is forced by
    # pulling a value that depends on the last token, (b) two decode
    # lengths are timed and the marginal time per token is used, which
    # cancels prefill and the fixed dispatch cost.
    def timed(n_tokens):
        if stacked:
            out = model.generate(prompt, max_new_tokens=n_tokens,
                                 temperature=0.0, cache_dtype=cache_dtype)
        else:
            out = generate(model, prompt, max_new_tokens=n_tokens,
                           temperature=0.0, state=state,
                           cache_dtype=cache_dtype)
        return int(out[:, -1].sum())  # sync on dependent value

    n_short = max(8, ns.new_tokens // 4)
    timed(n_short)            # compile both lengths
    timed(ns.new_tokens)
    if ns.sanitize:
        # warm-path pin: the measured reps below must be pure cache
        # hits — a recompile here is exactly the silent regression the
        # sanitizer exists to catch (docs/ANALYSIS.md)
        from paddle_tpu.analysis import runtime as _sanitizer
        with _sanitizer.no_recompile(
                what="warm decode_bench generate pair"):
            timed(n_short)
            timed(ns.new_tokens)
    # measure the DEVICE clock via the xplane parser when available
    # (min-of-reps wall marginal as fallback), marginal between the two
    # decode lengths to cancel prefill + fixed costs
    # wall reps run UNTRACED (the r2 methodology, clean fallback); one
    # traced pair afterwards supplies the device-clock numbers
    reps = max(ns.reps, 1)
    t_short, t_long = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        timed(n_short)
        t_short.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        timed(ns.new_tokens)
        t_long.append(time.perf_counter() - t0)

    def device_time(n):
        import shutil
        d = "/tmp/decode_bench_prof"
        shutil.rmtree(d, ignore_errors=True)
        with jax.profiler.trace(d):
            timed(n)
        from paddle_tpu.profiler import xplane
        return xplane.device_total_seconds(d, "jit_run")

    try:
        # any accelerator gets the device-clock pass (the xplane parser
        # reads GPU planes too); only the CPU backend — which yields no
        # device plane and pays ~15 s of trace start/stop on the bare
        # container — skips it unless forced
        if dev.platform != "cpu" or ns.device_time:
            d_short, d_long = (device_time(n_short),
                               device_time(ns.new_tokens))
        else:
            d_short = d_long = None
    except Exception:
        d_short = d_long = None
    if d_short is not None and d_long is not None:
        dt = d_long - d_short
        timing = "device(xplane)"
        n_eff = ns.new_tokens - n_short
    else:
        dt = min(t_long) - min(t_short)
        timing = "wall(min-of-reps)"
        n_eff = ns.new_tokens - n_short
        if dt <= 0:
            # a loaded host can make the short run's best wall exceed
            # the long run's (seen at --reps 1 in the CI smoke): the
            # marginal is pure noise — report the absolute long-run
            # rate instead of a negative throughput
            dt = min(t_long)
            n_eff = ns.new_tokens
            timing = "wall(absolute)"

    tok_s = ns.batch * n_eff / dt
    per_seq = n_eff / dt

    # roofline: average cache length over the decode window. The UNTIED
    # embedding table is NOT streamed per step — decode gathers b rows
    # from it; only a TIED head (gpt2) re-reads it as the unembedding
    # matmul. (Round-5 correction: earlier rooflines counted the unread
    # embed table, inflating bytes/step — the deepseek row came out at
    # "114% of roofline", which is how the bug surfaced. Historical rows
    # in SCALE.md are re-derived under this definition.) int8 quantizes
    # every linear INCLUDING lm_head; the bf16 embed table is excluded
    # either way. MoE: the fused kernel streams only min(b·k, E) routed
    # experts per layer per step.
    avg_len = ns.prompt_len + ns.new_tokens / 2
    tied = bool(getattr(cfg, "tie_word_embeddings", False)) \
        or name == "gpt2-345m"
    embed_params = 0 if tied else cfg.vocab_size * cfg.hidden_size
    if moe:
        # routed stacks stream only min(b·k, E) experts/layer; DENSE params
        # (attention, router, shared experts, head) stream whole
        expert_params = 3 * cfg.hidden_size * cfg.intermediate_size
        dense_params = (n_params - embed_params
                        - cfg.num_layers * cfg.num_experts * expert_params)
        streamed = (dense_params + cfg.num_layers * min(
            ns.batch * cfg.top_k, cfg.num_experts) * expert_params)
        param_bytes = 2 * streamed
    elif ns.int8:
        param_bytes = n_params - embed_params
    else:
        param_bytes = 2 * (n_params - embed_params)
    cache_bytes = kv_bytes_per_token(cfg, 1 if ns.cache_int8 else 2)
    step_bytes = param_bytes + ns.batch * cache_bytes * avg_len
    bw = HBM_BW.get(dev.device_kind, 819e9 if on_tpu else 50e9)
    roofline_tok_s = ns.batch * bw / step_bytes

    # ---- unified telemetry: BENCH schema + roofline plan + spans ----------
    from paddle_tpu import observability as obs

    # the analytic per-phase plan scale_report --report joins against an
    # xplane capture (decode_bench's own trace lands in
    # /tmp/decode_bench_prof); substring attribution is best-effort, so
    # the catch-all phases keep the unmatched time visible
    roofline_plan = {
        "hbm_gbps": round(bw / 1e9, 1),
        "steps": ns.new_tokens,
        "phases": [
            {"name": "decode_kernel",
             "match": ["fused_decode", "pallas", "custom-call"],
             "bytes_per_step": step_bytes},
            {"name": "glue_matmul", "match": ["dot", "einsum", "convolution"],
             "bytes_per_step": 0},
            {"name": "sampling_glue",
             "match": ["argmax", "reduce", "iota", "sort", "top-k", "top_k",
                       "select", "compare"],
             "bytes_per_step": 0},
        ],
    }
    if ns.report_plan:
        with open(ns.report_plan, "w") as f:
            json.dump(roofline_plan, f)

    spans = None
    if ns.traced:
        # traced run: generate() switches to prefill + chunked decode
        # dispatches so TTFT/TPOT are host-measured; tokens unchanged.
        # The first traced call compiles the prefill/chunk programs (the
        # untraced warmups above cached only the single-dispatch
        # program), so warm up once and measure the second request. The
        # measured request runs INSIDE a jax.profiler capture into the
        # --report dir, so the decode.request/prefill/chunk
        # TraceAnnotations land in the same xplane the roofline join
        # reads (skipped on bare CPU unless --device_time: trace
        # start/stop costs ~15 s there and yields no device plane).
        import contextlib
        import shutil
        with obs.trace(decode_chunk=32):
            timed(ns.new_tokens)
        if dev.platform != "cpu" or ns.device_time:
            shutil.rmtree("/tmp/decode_bench_prof", ignore_errors=True)
            capture = jax.profiler.trace("/tmp/decode_bench_prof")
        else:
            capture = contextlib.nullcontext()
        with capture, obs.trace(decode_chunk=32) as tracer:
            timed(ns.new_tokens)
        spans = tracer.span_dicts()
        obs.validate_spans(spans, require_request=True)
        tracer.export_jsonl("/tmp/decode_bench_spans.jsonl")

    pad_waste = None
    if ns.eos is not None:
        if stacked:
            print("note: --eos pad-waste accounting needs "
                  "generate(return_lengths=True); the stacked engine "
                  "reports ids only — skipped", file=sys.stderr)
        else:
            # static-batch pad waste: every row decodes the full
            # new_tokens budget; tokens after a row's eos are pure
            # padding (the scheduling gap serving_bench's continuous
            # engine closes — its A/B record quotes this number)
            _, lens = generate(model, prompt, max_new_tokens=ns.new_tokens,
                               temperature=0.0, state=state,
                               cache_dtype=cache_dtype, eos_token_id=ns.eos,
                               return_lengths=True)
            useful = int(np.minimum(lens + 1, ns.new_tokens).sum())
            pad_waste = round(1 - useful / (ns.batch * ns.new_tokens), 3)

    tag = (" int8" if ns.int8 else "") + (" kv8" if ns.cache_int8 else "")
    rec = obs.bench_record(
        f"{name}{tag} decode tokens/s (batch={ns.batch})",
        round(tok_s, 1), "tokens/s",
        device=dev.device_kind,
        tokens_per_sec_per_seq=round(per_seq, 1),
        roofline_tokens_per_sec=round(roofline_tok_s, 1),
        frac_of_roofline=round(tok_s / roofline_tok_s, 3),
        params=n_params,
        batch=ns.batch, prompt_len=ns.prompt_len,
        new_tokens=ns.new_tokens,
        step_time_ms=round(1000 * dt / n_eff, 3),
        timing=timing,
        **({"pad_waste_frac": pad_waste} if pad_waste is not None else {}),
        roofline_plan=roofline_plan,
        memory=obs.memory.memory_snapshot(),
        **({"request_span": next(
            s["attrs"] for s in spans if s["name"] == "decode.request")}
           if spans else {}),
    )
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
