"""Single-chip MoE training throughput (Mixtral-style).

Exercises the token-dispatch hot path (the global_scatter/gather
mechanism analog — SURVEY.md §2.6-EP) under real training on one chip;
the default 'fused' dispatch gathers expert input blocks directly from
the token rows and combines with an inverse-gather segment-sum (the r5
dispatch-residual redesign). MFU uses activated FLOPs (top-k experts per
token, not all E), the standard MoE accounting. `--xplane_breakdown`
dumps the bucketed per-op attribution (dispatch / expert matmul /
optimizer / attention) so the residual can be tracked across rounds.

Run: python examples/moe_bench.py [--layers 12 --experts 8]
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

PEAK = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5": 459e12,
        "TPU v4": 275e12, "TPU v6 lite": 918e12}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--ffn", type=int, default=2816)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dispatch", default="fused",
                    choices=["scatter", "sort", "fused", "einsum",
                             "alltoall", "dropless"])
    ap.add_argument("--xplane_breakdown", action="store_true",
                    help="dump the per-op residual attribution (dispatch / "
                         "expert matmul / optimizer / attention) from an "
                         "xplane trace of the timed step")
    # cf=1.0 in this parametrization (cap = cf*k*T/E) IS the GShard top-2
    # capacity convention (2.0*T/E); 1.25 adds headroom at 25% extra
    # expert compute
    ap.add_argument("--capacity_factor", type=float, default=1.0)
    ns = ap.parse_args()

    import paddle_tpu
    from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.optimizer import AdamW

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        ns.layers, ns.hidden, ns.ffn, ns.seq, ns.steps = 2, 128, 256, 128, 2

    paddle_tpu.seed(0)
    cfg = MixtralConfig(
        vocab_size=32000 if on_tpu else 512, hidden_size=ns.hidden,
        intermediate_size=ns.ffn, num_layers=ns.layers,
        num_heads=max(4, ns.hidden // 64), num_kv_heads=max(4, ns.hidden // 128),
        max_position_embeddings=max(2048, ns.seq),
        num_experts=ns.experts, top_k=2,
        capacity_factor=ns.capacity_factor,
        moe_dispatch="scatter" if ns.dispatch == "dropless" else ns.dispatch,
        moe_dropless=ns.dispatch == "dropless")
    model = MixtralForCausalLM(cfg).bfloat16()
    n_params = model.num_params()
    opt = AdamW(learning_rate=1e-4, multi_precision=False)
    state = model.trainable_state()
    opt_state = opt.init_state(state)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (ns.batch, ns.seq + 1)))
    x, y = ids[:, :-1], ids[:, 1:]

    def one_step(carry, _):
        state, opt_state = carry

        def loss_fn(s):
            out = functional_call(model, s, x)
            return model.loss(out, y)

        loss, grads = jax.value_and_grad(loss_fn)(state)
        state, opt_state = opt.update(grads, opt_state, state)
        return (state, opt_state), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(state, opt_state):
        (state, opt_state), losses = jax.lax.scan(
            one_step, (state, opt_state), None, length=ns.steps)
        return state, opt_state, losses

    state, opt_state, losses = run(state, opt_state)
    float(losses[-1])
    t0 = time.perf_counter()
    state, opt_state, losses = run(state, opt_state)
    loss = float(losses[-1])
    dt = time.perf_counter() - t0

    # device-side step time via xplane (the profiler reads the TPU's
    # own clock)
    dt_dev = None
    if on_tpu:
        try:
            import shutil
            from paddle_tpu.profiler import xplane
            shutil.rmtree("/tmp/moe_bench_prof", ignore_errors=True)
            with jax.profiler.trace("/tmp/moe_bench_prof"):
                state, opt_state, losses = run(state, opt_state)
                float(losses[-1])
            dt_dev = xplane.device_total_seconds("/tmp/moe_bench_prof",
                                                 "jit_run")
        except Exception:
            pass

    # --xplane_breakdown: bucketed per-op attribution so the next round
    # can verify the dispatch residual shrank (works on the CPU sim too —
    # host planes are used when no device plane exists)
    breakdown = top_ops = None
    if ns.xplane_breakdown:
        try:
            import shutil
            from paddle_tpu.profiler import xplane
            shutil.rmtree("/tmp/moe_bench_bd", ignore_errors=True)
            with jax.profiler.trace("/tmp/moe_bench_bd"):
                state, opt_state, losses = run(state, opt_state)
                float(losses[-1])
            planes = xplane.load_latest("/tmp/moe_bench_bd")
            rows = xplane.op_summary(planes)
            if not rows:            # CPU sim: no TPU/GPU plane
                rows = xplane.op_summary(planes, device_only=False)
            breakdown = {k: round(v / ns.steps, 3) for k, v in
                         xplane.bucket_summary(rows).items()}
            top_ops = [{"name": r["name"][:64],
                        "total_ms": round(r["total_ms"], 3),
                        "pct": round(r["pct"], 2)} for r in rows[:10]]
        except Exception as e:
            breakdown = {"error": f"{type(e).__name__}: {e}"[:200]}

    tok_s = ns.batch * ns.seq * ns.steps / (dt_dev or dt)
    # activated params: attention + top_k of E experts + embeddings
    h, f, e, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts, \
        cfg.num_layers
    expert_params = 3 * h * f
    act_params = n_params - L * e * expert_params + L * cfg.top_k * expert_params
    flops_tok = 6 * act_params + 12 * L * h * ns.seq
    mfu = tok_s * flops_tok / PEAK.get(dev.device_kind,
                                       197e12 if on_tpu else 1e12)
    from paddle_tpu import observability as obs

    rec = obs.bench_record(
        f"mixtral-{ns.layers}L-{ns.experts}e train tokens/s/chip",
        round(tok_s, 1), "tokens/s",
        device=dev.device_kind,
        dispatch=ns.dispatch,
        mfu=round(mfu, 4),
        mfu_basis="activated",
        params=n_params,
        params_activated=act_params,
        batch=ns.batch, seq=ns.seq, steps=ns.steps,
        step_time_ms=round(1000 * (dt_dev or dt) / ns.steps, 2),
        wall_step_time_ms=round(1000 * dt / ns.steps, 2),
        timing="device(xplane)" if dt_dev else "wall",
        final_loss=round(loss, 4),
        memory=obs.memory.memory_snapshot(),
        **({"xplane_breakdown_ms_per_step": breakdown,
            "xplane_top_ops": top_ops} if ns.xplane_breakdown else {}),
    )
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
