"""GPT-2 pretrain end-to-end (BASELINE config #1), exercising the full stack:
native data pipeline → fleet train step (any hybrid config) → checkpoints →
metrics. Runs on one TPU chip or the CPU simulator.

  python examples/pretrain_gpt.py --steps 20 --preset tiny
  python examples/pretrain_gpt.py --preset 345m --amp bfloat16 \
      --dp 1 --mp 1 --steps 100 --ckpt-dir /tmp/gpt_run
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.io.lm_dataset import PackedTokenDataset
from paddle_tpu.models.gpt import GPTConfig, GPTPretrainModel
from paddle_tpu.optimizer import AdamW, lr as lr_mod, ClipGradByGlobalNorm
from paddle_tpu.parallel import fleet
from paddle_tpu.parallel.checkpoint import CheckpointManager
from paddle_tpu.parallel.strategy import DistributedStrategy
from paddle_tpu.profiler import MetricsLogger, StepTimer, model_flops_per_token


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "345m"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1)
    ap.add_argument("--zero", type=int, default=0)
    ap.add_argument("--amp", default=None, choices=[None, "bfloat16", "float16"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--metrics", default="metrics.jsonl")
    args = ap.parse_args()

    paddle.seed(0)
    if args.preset == "tiny":
        cfg = GPTConfig.tiny(vocab_size=4096)
    else:
        cfg = GPTConfig.gpt2_medium()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    if args.pp > 1:
        cfg.tie_word_embeddings = False

    s = DistributedStrategy()
    s.hybrid_configs = {"dp_degree": args.dp, "mp_degree": args.mp,
                        "pp_degree": args.pp,
                        "sharding_degree": args.sharding}
    if args.zero:
        s.sharding = True
        s.sharding_configs.stage = args.zero
    if args.pp > 1:
        s.pipeline = True
        s.pipeline_configs.accumulate_steps = max(2, args.pp)
    if args.amp:
        s.amp = True
        s.amp_configs.dtype = args.amp
    fleet.init(is_collective=True, strategy=s)

    model = GPTPretrainModel(cfg)
    print(f"model: {model.num_params() / 1e6:.1f}M params, "
          f"mesh={dict(fleet.get_fleet().mesh.shape)}")

    # synthetic corpus through the native packing pipeline
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, cfg.vocab_size, 2_000_00).astype(np.int32)
    ds = PackedTokenDataset(tokens, seq_len=args.seq, eos_id=0)

    sched = lr_mod.LinearWarmup(lr_mod.CosineAnnealingDecay(3e-4, args.steps),
                                warmup_steps=max(2, args.steps // 20),
                                start_lr=0.0, end_lr=3e-4)
    opt = AdamW(learning_rate=sched, weight_decay=0.01,
                grad_clip=ClipGradByGlobalNorm(1.0))
    loss_fn = (None if args.pp > 1
               else lambda logits, b: model.loss(logits, b["labels"]))
    step_fn, init_fn = fleet.make_train_step(model, opt, loss_fn, strategy=s)
    state, opt_state = init_fn()

    mngr = (CheckpointManager(args.ckpt_dir, max_to_keep=2)
            if args.ckpt_dir else None)
    metrics = MetricsLogger(args.metrics)
    timer = StepTimer(model_flops_per_token(model.num_params()))

    step = 0
    while step < args.steps:
        for batch in ds.epoch_batches(args.batch, seed=step):
            if step >= args.steps:
                break
            with timer:
                state, opt_state, loss = step_fn(
                    state, opt_state,
                    {"input": jnp.asarray(batch["input"]),
                     "labels": jnp.asarray(batch["labels"])})
                jax.block_until_ready(loss)
            step += 1
            if step % 10 == 0 or step == args.steps:
                tps = timer.tokens_per_sec(args.batch * args.seq)
                print(f"step {step:5d}  loss {float(loss):.4f}  "
                      f"{tps:,.0f} tok/s")
                metrics.log(step=step, loss=float(loss), tokens_per_sec=tps,
                            mfu=timer.mfu(args.batch * args.seq))
            if mngr and step % 50 == 0:
                mngr.save(step, {"model": state, "opt": opt_state})
    if mngr:
        mngr.save(args.steps, {"model": state, "opt": opt_state}, force=True)
        mngr.wait_until_finished()
        print(f"checkpoints: {mngr.all_steps()}")


if __name__ == "__main__":
    main()
