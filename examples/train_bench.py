"""Single-chip training throughput for larger-than-headline models.

BASELINE's scale story needs evidence beyond GPT-2 345M: this benches the
largest Llama config that fits one v5e chip (16 GiB) with pure-bf16 AdamW
(moments in bf16, no fp32 master — 6 bytes/param of optimizer state).
Same timing discipline as bench.py: the whole step loop is ONE lax.scan
inside jit, synced by pulling the final loss to the host.

Run: python examples/train_bench.py [--model llama-1b3] [--steps 10]
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,
}


def build(name):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    shapes = {
        # ~1.36 B params — GPT-3 XL-ish shape, fits v5e with bf16 AdamW
        "llama-1b3": dict(vocab_size=32000, hidden_size=2048, num_layers=24,
                          num_heads=32, num_kv_heads=32,
                          intermediate_size=5632,
                          max_position_embeddings=2048),
        # TinyLlama-1.1B shape (GQA)
        "llama-1b": dict(vocab_size=32000, hidden_size=2048, num_layers=22,
                         num_heads=32, num_kv_heads=4,
                         intermediate_size=5632,
                         max_position_embeddings=2048),
        "llama-tiny": dict(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=4, num_kv_heads=4,
                           intermediate_size=256,
                           max_position_embeddings=512),
    }
    cfg = LlamaConfig(**shapes[name])
    cfg.recompute = name != "llama-tiny"  # per-layer remat for the big runs
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="default 4 (2 for llama-1b3: the core_attn save "
                    "set + 1.36B state only fits 16 GiB at b2)")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--per_step_dispatch", action="store_true",
                    help="one jit call per step (halves state memory: no "
                    "scan double-buffer) — wall timing then includes one "
                    "dispatch per step; MFU still uses the device clock")
    ap.add_argument("--granularity", default=None,
                    choices=["full", "full_attn", "core_attn"],
                    help="recompute_granularity (reference fleet "
                    "recompute): default core_attn for the 1B configs "
                    "(q/k/v + FFN matmul outputs saved — fits v5e now "
                    "that multi_precision=False keeps bf16 moments), "
                    "full elsewhere")
    ns = ap.parse_args()

    import paddle_tpu
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.optimizer import AdamW

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    name = ns.model or ("llama-1b3" if on_tpu else "llama-tiny")
    if ns.batch is None:
        ns.batch = 2 if name == "llama-1b3" else 4
    if not on_tpu:
        ns.batch, ns.seq, ns.steps = 2, 128, 2

    paddle_tpu.seed(0)
    cfg = build(name)
    if ns.granularity is not None:
        cfg.recompute_granularity = ns.granularity
    elif name in ("llama-1b", "llama-1b3"):
        # selective remat + bf16 moments: 1.1B 43.3 → 57.1% measured; the
        # saved matmul outputs need the no-scan-double-buffer layout
        cfg.recompute_granularity = "core_attn"
        ns.per_step_dispatch = True
    if name in ("llama-1b", "llama-1b3"):
        cfg.loss_seq_chunks = 4   # never materialize (b, s, 32000) logits
    from paddle_tpu.models.llama import LlamaForCausalLM
    model = LlamaForCausalLM(cfg).bfloat16()
    n_params = model.num_params()
    # pure-bf16 AdamW: moments live in the param dtype (no fp32 master)
    opt = AdamW(learning_rate=1e-4, multi_precision=False)
    state = model.trainable_state()
    opt_state = opt.init_state(state)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (ns.batch, ns.seq + 1)))
    x, y = ids[:, :-1], ids[:, 1:]

    def one_step(carry, _):
        state, opt_state = carry

        def loss_fn(s):
            return functional_call(model, s, x, y, method="train_loss")

        loss, grads = jax.value_and_grad(loss_fn)(state)
        state, opt_state = opt.update(grads, opt_state, state)
        return (state, opt_state), loss

    # donate the carried state — without this the old buffers stay live
    # across the dispatch and the 1B+ configs don't fit
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_steps(state, opt_state):
        (state, opt_state), losses = jax.lax.scan(
            one_step, (state, opt_state), None, length=ns.steps)
        return state, opt_state, losses

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_one(state, opt_state):
        (state, opt_state), loss = one_step((state, opt_state), None)
        return state, opt_state, loss

    if ns.per_step_dispatch:
        state, opt_state, loss = run_one(state, opt_state)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(ns.steps):
            state, opt_state, loss = run_one(state, opt_state)
            loss = float(loss)  # sync every step
        dt = time.perf_counter() - t0
        jit_name = "jit_run_one"
    else:
        state, opt_state, losses = run_steps(state, opt_state)
        float(losses[-1])  # compile+warmup, real sync
        t0 = time.perf_counter()
        state, opt_state, losses = run_steps(state, opt_state)
        loss = losses[-1]
        loss = float(loss)
        dt = time.perf_counter() - t0
        jit_name = "jit_run_steps"

    # device-clock step time via the xplane parser (MFU uses the device
    # number when available, wall is reported alongside)
    dt_dev = None
    if on_tpu:
        try:
            import shutil
            from paddle_tpu.profiler import xplane
            shutil.rmtree("/tmp/train_bench_prof", ignore_errors=True)
            with jax.profiler.trace("/tmp/train_bench_prof"):
                if ns.per_step_dispatch:
                    for _ in range(ns.steps):
                        state, opt_state, loss = run_one(state, opt_state)
                        loss = float(loss)
                else:
                    state, opt_state, losses = run_steps(state, opt_state)
                    float(losses[-1])
            dt_dev = xplane.device_total_seconds("/tmp/train_bench_prof",
                                                 jit_name)
        except Exception:
            pass

    tokens_per_step = ns.batch * ns.seq
    tok_s = tokens_per_step * ns.steps / (dt_dev or dt)
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * ns.seq
    peak = PEAK_FLOPS.get(dev.device_kind, 197e12 if on_tpu else 1e12)
    mfu = tok_s * flops_per_token / peak

    from paddle_tpu import observability as obs

    rec = obs.bench_record(
        f"{name} train tokens/sec/chip", round(tok_s, 1), "tokens/s",
        device=dev.device_kind,
        mfu=round(mfu, 4),
        mfu_basis="dense_6n",
        vs_baseline=round(mfu / 0.45, 4),
        params=n_params,
        batch=ns.batch, seq=ns.seq, steps=ns.steps,
        step_time_ms=round(1000 * (dt_dev or dt) / ns.steps, 2),
        wall_step_time_ms=round(1000 * dt / ns.steps, 2),
        timing="device(xplane)" if dt_dev else "wall",
        final_loss=round(loss, 4),
        memory=obs.memory.memory_snapshot(),
    )
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
