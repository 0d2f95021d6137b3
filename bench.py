"""Headline benchmark: GPT-2 345M pretrain tokens/sec/chip (+ MFU).

BASELINE.md config #1 ("GPT-2 345M single-device"). The reference repo
publishes no numbers (BASELINE.json "published": {}), so `vs_baseline`
reports measured MFU relative to the driver's north-star 45% MFU target —
1.0 means the north star is met on this chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


# bf16 peak FLOP/s per chip by device kind (public spec sheets)
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e / trillium
}


def main():
    import paddle_tpu
    from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.optimizer import AdamW

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    paddle_tpu.seed(0)
    cfg = GPTConfig.gpt2_medium()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    if not on_tpu:          # CPU smoke: shrink so the bench still completes
        cfg = GPTConfig(vocab_size=50304, hidden_size=256, num_layers=4,
                        num_heads=8, max_position_embeddings=1024,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)

    model = GPTPretrainModel(cfg).bfloat16()
    n_params = model.num_params()

    # b8 is the single-chip sweet spot on v5e (b16 triggers XLA spilling)
    B, S = (8, 1024) if on_tpu else (2, 256)
    opt = AdamW(learning_rate=1e-4)
    state = model.trainable_state()
    opt_state = opt.init_state(state)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S + 1)))
    x, y = ids[:, :-1], ids[:, 1:]

    n_steps = 20 if on_tpu else 3

    def one_step(carry, _):
        state, opt_state = carry
        def loss_fn(s):
            logits = functional_call(model, s, x)
            return model.loss(logits, y)
        loss, grads = jax.value_and_grad(loss_fn)(state)
        state, opt_state = opt.update(grads, opt_state, state)
        return (state, opt_state), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_steps(state, opt_state):
        (state, opt_state), losses = jax.lax.scan(
            one_step, (state, opt_state), None, length=n_steps)
        return state, opt_state, losses

    # warmup/compile (one dispatch covers all n_steps)
    state, opt_state, losses = run_steps(state, opt_state)
    float(losses[-1])

    t0 = time.perf_counter()
    state, opt_state, losses = run_steps(state, opt_state)
    loss = losses[-1]
    float(loss)          # full host sync
    dt = time.perf_counter() - t0

    # device-side step time from the xplane trace; both numbers are
    # reported, MFU uses the device clock when available
    dt_dev = None
    if on_tpu:
        try:
            import shutil
            from paddle_tpu.profiler import xplane
            shutil.rmtree("/tmp/bench_prof", ignore_errors=True)
            with jax.profiler.trace("/tmp/bench_prof"):
                state, opt_state, losses = run_steps(state, opt_state)
                loss = losses[-1]
                float(loss)
            dt_dev = xplane.device_total_seconds("/tmp/bench_prof",
                                                 "jit_run_steps")
        except Exception:
            pass

    tokens_per_step = B * S
    tok_s = tokens_per_step * n_steps / (dt_dev or dt)

    # train FLOPs/token ≈ 6N + attention term 12·L·h·S (h=hidden, causal ½·2)
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * S
    peak = PEAK_FLOPS.get(dev.device_kind, 197e12 if on_tpu else 1e12)
    mfu = tok_s * flops_per_token / peak

    from paddle_tpu import observability as obs

    rec = obs.bench_record(
        "gpt2-345m tokens/sec/chip", round(tok_s, 1), "tokens/s",
        device=dev.device_kind,
        vs_baseline=round(mfu / 0.45, 4),
        mfu=round(mfu, 4),
        mfu_basis="dense_6n",
        params=n_params,
        batch=B, seq=S, steps=n_steps,
        step_time_ms=round(1000 * (dt_dev or dt) / n_steps, 2),
        wall_step_time_ms=round(1000 * dt / n_steps, 2),
        timing="device(xplane)" if dt_dev else "wall",
        final_loss=round(float(loss), 4),
        memory=obs.memory.memory_snapshot(),
    )
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
