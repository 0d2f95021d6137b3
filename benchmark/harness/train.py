"""Training cells (``kind: train``).

The system under test is ``paddle_tpu.parallel.fleet.make_train_step`` as
a user builds it (the calls ``chip_smoke.build_trainer`` makes, copied):
bf16 parameters with fp32 masters, AdamW with clipping and decay under
the warmup-cosine schedule of ``examples/pretrain_gpt.py``, fresh batches
from ``PackedTokenDataset`` through the native pipeline over a seeded
corpus. The clocks, the corpus, the weights and the reference loss are
the benchmark's own.
"""

import importlib
import time

import numpy as np

from . import capture, log, model

LOSS_TOLERANCE = 0.02       # step-0 loss vs the float32 reference (bf16 forward)


def corpus(spec: dict, vocab: int, seed: int) -> np.ndarray:
    """A seeded token stream with a Zipf-like unigram distribution
    (p ~ 1 / (rank + offset)), so that there is something to learn and
    the loss falls inside a short window."""
    rng = np.random.default_rng([int(seed), 4])
    p = 1.0 / (np.arange(1, vocab) + float(spec["zipf_offset"]))
    return (1 + rng.choice(vocab - 1, size=int(spec["tokens"]),
                           p=p / p.sum())).astype(np.int32)


def batches(ds, batch: int, seed: int):
    """Fresh batches for ever: epoch after epoch, each with its own
    shuffle."""
    epoch = 0
    while True:
        yield from ds.epoch_batches(batch, seed=seed * 1000 + epoch)
        epoch += 1


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.io.lm_dataset import PackedTokenDataset
    from paddle_tpu.optimizer import AdamW, ClipGradByGlobalNorm, lr
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.strategy import DistributedStrategy

    cfg, traffic = ctx["config"], ctx["traffic"]
    seconds, seed = float(ctx["seconds"]), ctx["seed"]
    tr, opt_spec = traffic["trainer"], traffic["optimizer"]
    batch, seq = int(tr["batch"]), int(tr["seq"])
    phases = {}

    t = time.perf_counter()
    strategy = DistributedStrategy()
    strategy.hybrid_configs = dict(tr["hybrid"])
    strategy.amp = True
    strategy.amp_configs.dtype = tr["amp_dtype"]
    fleet.init(is_collective=True, strategy=strategy,
               devices=jax.devices()[:ctx["chips"]])
    mdl = model.build_model(cfg)
    total = int(opt_spec["schedule_steps"])
    schedule = lr.LinearWarmup(
        lr.CosineAnnealingDecay(opt_spec["peak_lr"], total),
        warmup_steps=int(total * opt_spec["warmup_fraction"]), start_lr=0.0,
        end_lr=opt_spec["peak_lr"])
    opt = AdamW(learning_rate=schedule,
                weight_decay=opt_spec["weight_decay"],
                grad_clip=ClipGradByGlobalNorm(opt_spec["clip_norm"]))
    step_fn, _ = fleet.make_train_step(
        mdl, opt, lambda logits, b: mdl.loss(logits, b["labels"]),
        strategy=strategy)
    state = model.make_state(mdl.trainable_state(), seed, cfg["init_std"],
                             jnp.dtype(tr["amp_dtype"]))
    opt_state = opt.init_state(state)
    # the step donates its state: keep step 0's for the reference
    state0 = jax.tree_util.tree_map(jnp.copy, state)
    phases["build_s"] = time.perf_counter() - t

    t = time.perf_counter()
    vocab = cfg["vocab_size"]
    ds = PackedTokenDataset(corpus(traffic["corpus"], vocab, seed),
                            seq_len=seq, eos_id=0)
    feed = batches(ds, batch, seed)
    phases["data_s"] = time.perf_counter() - t

    def next_batch():
        b = next(feed)
        return {k: b[k] for k in ("input", "labels")}

    # warm-up: the step that compiles (its loss is step 0's, on batch0)
    # and one more, so the window starts on a steady program
    t = time.perf_counter()
    batch0 = next_batch()
    state, opt_state, loss0 = step_fn(state, opt_state, batch0)
    state, opt_state, loss1 = step_fn(state, opt_state, next_batch())
    jax.block_until_ready(loss1)
    phases["warm_steps_s"] = time.perf_counter() - t

    trace_s = min(3.0, seconds / 3)
    cap = capture.Capture(bool(ctx["trace"]), ctx["trace_dir"],
                          seconds - trace_s)
    clock = time.perf_counter
    compiles_open = ctx["clock"].compiles
    losses, waits = [], []
    prev = None
    t0 = clock()
    setup_s = t0 - ctx["t_start"]
    host_end = None             # (seconds, steps) where the traced part began
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if cap.due(now):
            jax.block_until_ready(prev)
            host_end = (clock() - t0, len(losses))
            cap.start(clock)
            continue
        w0 = clock()
        with cap.span("bench.next_batch"):
            b = next_batch()
        waits.append(clock() - w0)
        with cap.span("bench.train_step"):
            state, opt_state, loss = step_fn(state, opt_state, b)
            # at most two steps in flight: the host prepares the next
            # batch while the device works, and never runs further ahead
            if prev is not None:
                jax.block_until_ready(prev)
        prev = loss
        losses.append(loss)
    jax.block_until_ready(prev)
    elapsed = clock() - t0
    trace_path = cap.stop()
    compiles = ctx["clock"].compiles - compiles_open

    steps = len(losses)
    losses = [float(x) for x in losses]
    tokens_per_step = batch * seq
    if host_end is None:
        host_end = (elapsed, steps)
    e2e = {"train_tokens_per_s":
           steps * tokens_per_step / elapsed / ctx["chips"]}

    t = time.perf_counter()
    ref = importlib.import_module(f"{__package__}.reference_{cfg['arch']}")
    del state, opt_state
    ref_loss = float(ref.loss(state0, jnp.asarray(batch0["input"]),
                              jnp.asarray(batch0["labels"]), cfg))
    phases["reference_s"] = time.perf_counter() - t
    loss0 = float(loss0)
    finite = all(np.isfinite(losses)) and np.isfinite(loss0)
    k = min(5, steps // 2)      # five and five; fewer only in a rehearsal
    falling = k >= 1 and np.mean(losses[-k:]) < np.mean(losses[:k])
    near = abs(loss0 - ref_loss) <= LOSS_TOLERANCE
    detail = dict(loss0=loss0, reference_loss0=ref_loss,
                  tolerance=LOSS_TOLERANCE, first=losses[:k],
                  last=losses[-k:], steps=steps)
    log(phase="train", setup_s=setup_s, setup=phases, reference=detail,
        end_to_end=e2e, elapsed_s=elapsed)
    return dict(
        kind="train", correct=bool(finite and falling and near),
        attempted=steps, failed=0 if finite else steps, setup_s=setup_s,
        end_to_end=e2e, config=cfg, traffic=traffic,
        cell=ctx["cell"], seconds=seconds, data_waits=waits[:host_end[1]],
        host_tokens_per_s=host_end[1] * tokens_per_step / host_end[0]
        / ctx["chips"], seq=seq, batch=batch,
        compiles_in_window=compiles, trace_path=trace_path)
