"""SD UNet denoise-step benchmark (BASELINE config #5) — device clock.

Measures the UNet forward (the diffusion sampling hot loop) on SD-1.5
shapes: latents (b, 4, 64, 64), text context (b, 77, 768). The step loop
is ONE lax.scan inside jit (output fed back as input so XLA can't hoist),
timed on the device clock via the xplane parser; MFU comes from the
compiled executable's own cost analysis (XLA-counted FLOPs, not an
analytic estimate). The conv-vs-attention split comes from an ABLATION
(the same shapes with attention_levels=() and an Identity mid-attn) —
fusion names in the xplane trace don't reveal their contents, a timing
subtraction does — so the "does a Pallas conv/GroupNorm fusion earn its
keep" question is answered by measurement.

Note: SD-1.5 attention head_dims are 40/80/160 — outside the flash
kernel's (64, 128, 256) support — so attention lowers to the XLA path by
design; the breakdown shows how much that costs.

Run: python examples/unet_bench.py [--batch 2] [--steps 10] [--train]
"""

import argparse
import functools
import json
import os
import re
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--train", action="store_true",
                    help="bench a DDPM training step instead of inference")
    ns = ap.parse_args()

    import paddle_tpu
    from paddle_tpu.models.unet import UNetConfig, UNetModel
    from paddle_tpu.nn.layer import functional_call

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    paddle_tpu.seed(0)
    cfg = UNetConfig.sd15() if on_tpu else UNetConfig.tiny()
    res = 64 if on_tpu else 16
    ctx_len = 77 if on_tpu else 8
    if not on_tpu:
        ns.batch, ns.steps = 1, 2

    model = UNetModel(cfg).bfloat16()
    model.eval()
    n_params = model.num_params() if hasattr(model, "num_params") else sum(
        int(np.prod(p.shape)) for _, p in model.named_parameters())
    state = model.trainable_state()

    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.standard_normal(
        (ns.batch, cfg.in_channels, res, res)), jnp.bfloat16)
    t = jnp.asarray(rng.randint(0, 1000, (ns.batch,)))
    ctx = jnp.asarray(rng.standard_normal(
        (ns.batch, ctx_len, cfg.context_dim)), jnp.bfloat16)

    if ns.train:
        from paddle_tpu.optimizer import AdamW
        opt = AdamW(learning_rate=1e-4, multi_precision=False)
        opt_state = opt.init_state(state)
        # DDPM epsilon-prediction objective: the model denoises x_t =
        # sqrt(abar)·x0 + sqrt(1-abar)·noise and regresses the noise
        noise = jnp.asarray(rng.standard_normal(x0.shape), jnp.bfloat16)
        abar = jnp.asarray(rng.uniform(0.2, 0.98, (ns.batch, 1, 1, 1)),
                           jnp.float32)
        xt = (jnp.sqrt(abar) * x0.astype(jnp.float32)
              + jnp.sqrt(1 - abar) * noise.astype(jnp.float32)).astype(
            jnp.bfloat16)

        def one(carry, _):
            st, ost = carry

            def loss_fn(s):
                eps = functional_call(model, s, xt, t, ctx)
                return jnp.mean(jnp.square(
                    eps.astype(jnp.float32) - noise.astype(jnp.float32)))

            loss, grads = jax.value_and_grad(loss_fn)(st)
            st, ost = opt.update(grads, ost, st)
            return (st, ost), loss

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def run(st, ost):
            (st, ost), losses = jax.lax.scan(one, (st, ost), None,
                                             length=ns.steps)
            return st, ost, losses[-1]

        args = (state, opt_state)
        runner = lambda a: run(*a)[:3]
        sync = lambda out: float(out[2])
        rebind = lambda out: (out[0], out[1])
    else:
        @jax.jit
        def run(state, x):
            def one(x, _):
                eps = functional_call(model, state, x, t, ctx)
                return eps.astype(x.dtype), ()
            x, _ = jax.lax.scan(one, x, None, length=ns.steps)
            return x

        args = (state, x0)
        runner = lambda a: run(*a)
        sync = lambda out: float(jnp.sum(out.astype(jnp.float32)))
        rebind = lambda out: (state, x0)

    # compile + warmup, guarded: the b4 training program reproducibly
    # crashed the compiler (ROADMAP r5). Report WHICH config died — with
    # the bisect pointer — instead of dying with a bare traceback; a
    # hard compiler abort (SIGABRT) still kills the process, which is
    # what examples/unet_b4_repro.py's subprocess bisect is for.
    try:
        out = runner(args)
        sync(out)
    except Exception as e:
        print(json.dumps({
            "metric": "sd15-unet COMPILER/RUNTIME CRASH",
            "crash_config": {
                "batch": ns.batch, "train": bool(ns.train), "res": res,
                "attention_levels": list(cfg.attention_levels),
                "channel_mult": list(cfg.channel_mult),
                "device": dev.device_kind,
            },
            "error": f"{type(e).__name__}: {e}"[:400],
            "bisect": "python examples/unet_b4_repro.py --max_batch "
                      f"{ns.batch}",
        }))
        sys.exit(1)
    args = rebind(out)

    t0 = time.perf_counter()
    out = runner(args)
    sync(out)
    dt = time.perf_counter() - t0
    args = rebind(out)

    dt_dev = None
    if on_tpu:
        try:
            import shutil
            from paddle_tpu.profiler import xplane
            shutil.rmtree("/tmp/unet_prof", ignore_errors=True)
            with jax.profiler.trace("/tmp/unet_prof"):
                out = runner(args)
                sync(out)
            dt_dev = xplane.device_total_seconds("/tmp/unet_prof", "jit_run")
        except Exception:
            pass

    step_s = (dt_dev or dt) / ns.steps

    # attention ablation: same shapes, attention_levels=() — the step-time
    # difference IS the transformer blocks' cost (fwd only; the inference
    # path is where the conv/attn fusion question lives)
    attn_ms = None
    if on_tpu and not ns.train:
        import dataclasses
        import shutil
        from paddle_tpu.profiler import xplane
        cfg_na = dataclasses.replace(cfg, attention_levels=())
        paddle_tpu.seed(0)
        model_na = UNetModel(cfg_na).bfloat16()
        model_na.eval()
        # mid_attn is unconditional in the model; identity it out (the
        # model calls it with (h, context))
        class _PassThrough(paddle_tpu.nn.Layer):
            def forward(self, x, ctx=None):
                return x
        model_na.mid_attn = _PassThrough()
        state_na = model_na.trainable_state()

        @jax.jit
        def run_na(state, x):
            def one(x, _):
                eps = functional_call(model_na, state, x, t, ctx)
                return eps.astype(x.dtype), ()
            x, _ = jax.lax.scan(one, x, None, length=ns.steps)
            return x

        float(jnp.sum(run_na(state_na, x0).astype(jnp.float32)))
        shutil.rmtree("/tmp/unet_prof_na", ignore_errors=True)
        with jax.profiler.trace("/tmp/unet_prof_na"):
            float(jnp.sum(run_na(state_na, x0).astype(jnp.float32)))
        dt_na = xplane.device_total_seconds("/tmp/unet_prof_na",
                                            "jit_run_na")
        if dt_na is not None:
            attn_ms = (step_s - dt_na / ns.steps) * 1e3

    # XLA's own FLOP count for ONE model evaluation (the scanned program
    # reports a single while-body iteration)
    flops = None
    try:
        @jax.jit
        def one_fwd(state, x):
            return functional_call(model, state, x, t, ctx)
        cost = one_fwd.lower(state if not ns.train else args[0],
                             x0).compile().cost_analysis()
        flops = cost.get("flops") if isinstance(cost, dict) else None
        if flops and ns.train:
            flops *= 3.0          # fwd + bwd ≈ 3× fwd for convnets
    except Exception:
        pass
    peak = PEAK_FLOPS.get(dev.device_kind, 197e12 if on_tpu else 1e12)
    mfu = flops / step_s / peak if flops else None

    from paddle_tpu import observability as obs

    mode = "train" if ns.train else "denoise"
    rec = obs.bench_record(
        f"sd15-unet {mode} steps/s (batch={ns.batch})",
        round(1.0 / step_s, 2), "steps/s",
        device=dev.device_kind,
        images_per_sec=round(ns.batch / step_s, 2),
        step_time_ms=round(step_s * 1e3, 2),
        wall_step_time_ms=round(dt / ns.steps * 1e3, 2),
        timing="device(xplane)" if dt_dev else "wall",
        mfu=round(mfu, 4) if mfu else None,
        mfu_basis="xla_counted",
        params=int(n_params),
        batch=ns.batch, res=res, steps=ns.steps,
        attention_ms_of_step=(round(attn_ms, 2)
                              if attn_ms is not None else None),
        memory=obs.memory.memory_snapshot(),
    )
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
