"""chip_smoke.py — does paddle_tpu still start on the chip?

Drives the two main paths once on ONE TPU chip, in one process, through the
entry points a user calls, at the published widths of GPT-2 345M
(``GPTConfig.gpt2_medium()``: hidden 1024, 24 layers, 16 heads of 64,
vocabulary 50304, context 1024; bf16, seeded random weights):

* server  — ``serving.ServingEngine(model, max_slots=8, block_tokens=128,
  max_seq_len=1024, sanitize=True)`` answering 12 staggered requests
  (more requests than slots), once with the default constructor (wave
  prefill + step program) and once with ``chunk_tokens=256`` (the
  one-program tick);
* trainer — ``fleet.init`` + ``fleet.make_train_step``, b8 x s1024, AdamW
  under the warmup schedule of ``examples/pretrain_gpt.py``, five steps
  on one repeated seeded batch.

It checks what comes out (every request finished legally, each greedy
token within a bf16 margin of a plain full-forward replay, loss finite,
near ln(vocab) at step 0, unchanged by the update at lr 0 and falling at
every step after it, flash loss == XLA-attention loss), shows from the
lowered programs that the Pallas kernels are in them, and asserts that
no resilience (degradation/recovery) counter moved.

    python3 chip_smoke.py          # from anywhere; nothing is installed

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
only if every phase passed. Without a TPU it exits non-zero at once.
"""

import json
import math
import os
import re
import sys
import time

MAX_SLOTS, BLOCK_TOKENS, MAX_SEQ_LEN, CHUNK_TOKENS = 8, 128, 1024, 256
# 12 requests over 8 slots; prompts from tens to several hundred tokens
# (five 128-token prefill buckets), 16..64 new tokens each
PROMPT_LENS = (24, 57, 93, 120, 150, 200, 250, 300, 380, 450, 500, 600)
NEW_TOKENS = (16, 24, 32, 48, 64, 20, 40, 56, 28, 36, 44, 52)
# replay length: >= max(prompt + new); < 1024 keeps it on XLA attention
REPLAY_LEN = 768
# greedy token vs full-forward replay: the chosen token's reference logit
# may trail the reference maximum by bf16 rounding only (worst seen on
# the v5e: 0.0235, PERF.md)
MARGIN_TOL = 0.1
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 5
# the trainer takes the first steps of a RUN_STEPS-long run under the
# schedule of examples/pretrain_gpt.py: linear warmup from 0 to PEAK_LR
# over 5% of the run, cosine after. AdamW at a constant 3e-4 from step 0
# overshoots at this width, here and under optax alike (PERF.md, PR 22).
PEAK_LR, RUN_STEPS = 3e-4, 2000

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_KERNEL_RE = re.compile(r'@tpu_custom_call\b[^\n]*?kernel_name = "([^"]+)"')


def kernels_in(lowered) -> set:
    """Names of the Mosaic (Pallas TPU) custom calls in a lowered program."""
    return set(_KERNEL_RE.findall(lowered.as_text()))


class CompileClock:
    """Backend compiles, their seconds, and persistent-cache hits/misses,
    from jax.monitoring (one listener for the whole run)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, dur, **kw):
        if name == _COMPILE_EVENT:
            self.compiles += 1
            self.seconds += dur

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.compiles, self.seconds, self.hits, self.misses)

    def since(self, m):
        return dict(compiles=self.compiles - m[0],
                    compile_s=round(self.seconds - m[1], 2),
                    cache_hits=self.hits - m[2],
                    cache_misses=self.misses - m[3])


def require_tpu():
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; jax.devices()[0].platform is "
                 f"{dev.platform!r} ({dev.device_kind!r}, "
                 f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "unknown"
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={info['count']}  jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"python={sys.version.split()[0]}", flush=True)
    return info


def gpt2_345m():
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig.gpt2_medium()
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    return cfg


# ------------------------------------------------------------------ server

def serve(model, prompts, new_tokens, clock, label, **engine_kw):
    """One engine, staggered traffic; returns (results, report) with the
    Mosaic kernels found in the step (and tick) programs it ran."""
    from paddle_tpu import serving

    chunk_tokens = engine_kw.get("chunk_tokens")
    m0, t0 = clock.mark(), time.perf_counter()
    eng = serving.ServingEngine(model, max_slots=MAX_SLOTS,
                                block_tokens=BLOCK_TOKENS,
                                max_seq_len=MAX_SEQ_LEN, sanitize=True,
                                **engine_kw)
    pending = [serving.Request(p, max_new_tokens=n, seed=i)
               for i, (p, n) in enumerate(zip(prompts, new_tokens))]
    rids, ticks, steady_s = [], 0, 0.0
    setup_s = time.perf_counter() - t0          # engine construction
    while pending or not eng.idle:
        c0, t1 = clock.compiles, time.perf_counter()
        if pending:                             # one join per tick
            rids.append(eng.submit(pending.pop(0)))
        eng.step()
        dt = time.perf_counter() - t1
        if clock.compiles > c0:
            setup_s += dt
        else:
            steady_s += dt
        ticks += 1
        if ticks > 2000:
            raise RuntimeError(f"server[{label}]: not drained after "
                               f"{ticks} ticks")
    results = [eng.pop_result(r) for r in rids]
    stats = dict(eng.stats)

    # the Mosaic calls in the step and tick programs the engine ran: a
    # kernel counts for "step" / "tick/mid" / "tick/last" only if every
    # program of that name holds it
    progs = {}
    for key, lowered in eng.lowered_programs("step", "tick").items():
        name = "/".join(k for k in key if isinstance(k, str))
        ks = kernels_in(lowered)
        progs[name] = progs.get(name, ks) & ks
    need = {"step", "tick/mid", "tick/last"} if chunk_tokens else {"step"}
    if not need <= set(progs):
        raise RuntimeError(f"server[{label}]: programs {sorted(need)} "
                           f"should have run; ran {sorted(progs)}")
    eng.close()

    vocab = model.cfg.vocab_size
    for r, n_new in zip(results, new_tokens):
        if r.finish != "length" or len(r.tokens) != n_new:
            raise RuntimeError(f"server[{label}]: request {r.request_id} "
                               f"finish={r.finish!r} with {len(r.tokens)} of "
                               f"{n_new} tokens")
        if r.tokens.min() < 0 or r.tokens.max() >= vocab:
            raise RuntimeError(f"server[{label}]: request {r.request_id} "
                               f"emitted a token outside [0, {vocab})")
        if r.ttft_s is None or not r.ttft_s > 0:
            raise RuntimeError(f"server[{label}]: request {r.request_id} "
                               f"has no ttft_s")
    if stats["sanitized_steps"] < 1:
        raise RuntimeError(f"server[{label}]: sanitize=True guarded no tick")
    if chunk_tokens and stats["prefill_chunks"] < len(prompts):
        raise RuntimeError(f"server[{label}]: {stats['prefill_chunks']} "
                           f"prefill chunks for {len(prompts)} prompts")
    report = dict(label=label, requests=len(results), ticks=ticks,
                  sanitized_steps=stats["sanitized_steps"],
                  prefill_chunks=stats["prefill_chunks"],
                  decode_tokens=stats["decode_tokens"],
                  kernels={k: sorted(v) for k, v in progs.items()},
                  setup_s=round(setup_s, 2), steady_s=round(steady_s, 2),
                  **clock.since(m0))
    print(f"server[{label}]: {json.dumps(report)}", flush=True)
    return results, report


def make_replay(model):
    """check(results, label): greedy tokens vs a plain full forward
    (layered model, XLA attention, no Pallas kernel) over prompt +
    generated tokens — at every generated position the engine's token must
    be within MARGIN_TOL of the reference maximum (exact argmax up to bf16
    near-ties). One compiled reference serves every engine of the model."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.inference import _inference_state
    from paddle_tpu.nn.layer import functional_call

    state = _inference_state(model)
    rows = 4

    @jax.jit
    def margins(state, ids, nxt):
        logits = functional_call(model, state, ids).astype(jnp.float32)
        chosen = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return logits.max(-1) - chosen, logits.argmax(-1) == nxt

    def check(results, label):
        worst, exact, total = 0.0, 0, 0
        for i in range(0, len(results), rows):
            ids = np.zeros((rows, REPLAY_LEN), np.int32)
            nxt = np.zeros((rows, REPLAY_LEN), np.int32)
            mask = np.zeros((rows, REPLAY_LEN), bool)
            for j, r in enumerate(results[i:i + rows]):
                full = r.ids
                p = len(r.prompt)
                ids[j, :len(full)] = full
                # logits at position t predict token t+1
                nxt[j, p - 1:len(full) - 1] = r.tokens
                mask[j, p - 1:len(full) - 1] = True
            gap, hit = margins(state, jnp.asarray(ids), jnp.asarray(nxt))
            gap, hit = np.asarray(gap)[mask], np.asarray(hit)[mask]
            if not np.isfinite(gap).all():
                raise RuntimeError(
                    f"replay[{label}]: non-finite reference logits")
            worst = max(worst, float(gap.max()))
            exact += int(hit.sum())
            total += int(mask.sum())
        print(f"replay[{label}]: {total} generated tokens, exact argmax "
              f"{exact}/{total}, worst margin {worst:.4f} "
              f"(tol {MARGIN_TOL})", flush=True)
        if worst > MARGIN_TOL:
            raise RuntimeError(
                f"replay[{label}]: a served token trails the full-forward "
                f"maximum by {worst:.4f} > {MARGIN_TOL}")
        return dict(tokens=total, exact=exact, worst_margin=round(worst, 4))

    return check


def server_phase(clock):
    import numpy as np
    import paddle_tpu
    from paddle_tpu.models.gpt import GPTPretrainModel

    paddle_tpu.seed(0)
    model = GPTPretrainModel(gpt2_345m()).bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, model.cfg.vocab_size, (n,)).astype(np.int32)
               for n in PROMPT_LENS]
    replay = make_replay(model)
    reports, tokens = {}, []
    for label, kw in (("default", {}),
                      (f"chunk_tokens={CHUNK_TOKENS}",
                       {"chunk_tokens": CHUNK_TOKENS})):
        results, rep = serve(model, prompts, NEW_TOKENS, clock, label, **kw)
        for name, ks in rep["kernels"].items():
            if "fused_paged_decode_step" not in ks:
                raise RuntimeError(
                    f"server[{label}]: the {name} program holds no "
                    f"fused_paged_decode_step Mosaic call (found {ks})")
        rep["replay"] = replay(results, label)
        reports[label] = rep
        tokens.append([r.tokens for r in results])
    same = sum(int((x == y).sum()) for x, y in zip(*tokens))
    total = sum(len(x) for x in tokens[0])
    print(f"server: wave-prefill vs chunked engines agree on {same}/{total} "
          f"tokens", flush=True)
    return reports


# ----------------------------------------------------------------- trainer

def warmup_schedule():
    from paddle_tpu.optimizer import lr
    return lr.LinearWarmup(lr.CosineAnnealingDecay(PEAK_LR, RUN_STEPS),
                           warmup_steps=RUN_STEPS // 20, start_lr=0.0,
                           end_lr=PEAK_LR)


def build_trainer(hybrid, devices=None, learning_rate=None):
    """fleet.init + fleet.make_train_step for GPT-2 345M under `hybrid`
    (bf16 params, fp32 masters, AdamW under `learning_rate`, by default
    warmup_schedule()) and one seeded b8 x s1024 batch from the
    packed-token pipeline. Returns (model, batch, step_fn, init_fn,
    data_backend)."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu
    from paddle_tpu.io import native
    from paddle_tpu.io.lm_dataset import PackedTokenDataset
    from paddle_tpu.models.gpt import GPTPretrainModel
    from paddle_tpu.optimizer import AdamW, ClipGradByGlobalNorm
    from paddle_tpu.parallel import fleet
    from paddle_tpu.parallel.strategy import DistributedStrategy

    paddle_tpu.seed(0)
    cfg = gpt2_345m()
    s = DistributedStrategy()
    s.hybrid_configs = dict({"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                             "sharding_degree": 1}, **hybrid)
    s.amp = True
    s.amp_configs.dtype = "bfloat16"
    fleet.init(is_collective=True, strategy=s, devices=devices)
    model = GPTPretrainModel(cfg)

    rng = np.random.RandomState(0)
    corpus = rng.randint(1, cfg.vocab_size, 200_000).astype(np.int32)
    ds = PackedTokenDataset(corpus, seq_len=TRAIN_SEQ, eos_id=0)
    backend = ("native (csrc/libpaddle_tpu_data.so, built by g++ on first "
               "use)" if native.native_available() else "NumPy fallback")
    batch = next(iter(ds.epoch_batches(TRAIN_BATCH, seed=0)))
    batch = {k: jnp.asarray(batch[k]) for k in ("input", "labels")}
    if batch["input"].shape != (TRAIN_BATCH, TRAIN_SEQ):
        raise RuntimeError(f"trainer: batch shape {batch['input'].shape}")

    opt = AdamW(learning_rate=(warmup_schedule() if learning_rate is None
                               else learning_rate),
                weight_decay=0.01, grad_clip=ClipGradByGlobalNorm(1.0))
    step_fn, init_fn = fleet.make_train_step(
        model, opt, lambda logits, b: model.loss(logits, b["labels"]),
        strategy=s)
    return model, batch, step_fn, init_fn, backend


def forward_loss(model):
    """(state, batch) -> loss through the plain forward (no optimizer)."""
    from paddle_tpu.nn.layer import functional_call

    def fwd_loss(st, b):
        return model.loss(functional_call(model, st, b["input"]), b["labels"])
    return fwd_loss


def check_falling(losses, who):
    """The schedule starts at lr 0: the first update must change nothing
    (same batch, no dropout, so bit for bit the same loss: a step that
    reads a donated or stale buffer shows here), and every later step on
    the repeated batch must lower the loss."""
    if losses[1] != losses[0]:
        raise RuntimeError(f"{who}: the update at lr 0 changed the loss: "
                           f"{losses}")
    if not all(b < a for a, b in zip(losses[1:], losses[2:])):
        raise RuntimeError(f"{who}: loss is not falling: {losses}")


def trainer_phase(clock):
    import jax
    import paddle_tpu

    m0, t0 = clock.mark(), time.perf_counter()
    model, batch, step_fn, init_fn, backend = build_trainer(
        {}, devices=jax.devices()[:1])
    state, opt_state = init_fn()

    ks = kernels_in(step_fn.lower(TRAIN_BATCH, TRAIN_SEQ))
    need = {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"}
    if not need <= ks:
        raise RuntimeError(f"trainer: the train step lacks the flash "
                           f"kernels {sorted(need - ks)} (found {sorted(ks)})")

    # flash loss vs XLA-attention loss on two rows of the same batch
    small = {k: v[:2] for k, v in batch.items()}
    fwd_loss = forward_loss(model)
    loss_flash = float(jax.jit(fwd_loss)(state, small))
    paddle_tpu.set_flags({"FLAGS_use_pallas_kernels": False})
    try:
        loss_xla = float(jax.jit(fwd_loss)(state, small))
    finally:
        paddle_tpu.set_flags({"FLAGS_use_pallas_kernels": True})
    if not abs(loss_flash - loss_xla) < 2e-2:
        raise RuntimeError(f"trainer: flash loss {loss_flash} vs XLA "
                           f"attention loss {loss_xla}")

    # block_until_ready must be a fence: the pull after it finds the
    # value already on the host side of the step
    losses, step_s, pull_s = [], [], []
    for _ in range(TRAIN_STEPS):
        t1 = time.perf_counter()
        state, opt_state, loss = step_fn(state, opt_state, batch)
        jax.block_until_ready(loss)
        t2 = time.perf_counter()
        losses.append(float(loss))
        step_s.append(round(t2 - t1, 3))
        pull_s.append(round(time.perf_counter() - t2, 4))
    ln_v = math.log(model.cfg.vocab_size)
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"trainer: non-finite loss {losses}")
    if abs(losses[0] - ln_v) > 0.7:
        raise RuntimeError(f"trainer: step-0 loss {losses[0]:.3f} is not "
                           f"near ln(vocab)={ln_v:.3f}")
    check_falling(losses, "trainer")
    report = dict(params=model.num_params(), batch=TRAIN_BATCH,
                  seq=TRAIN_SEQ, losses=[round(x, 5) for x in losses],
                  loss_flash=round(loss_flash, 4),
                  loss_xla_attention=round(loss_xla, 4),
                  kernels=sorted(ks), data_backend=backend,
                  first_step_s=step_s[0], later_step_s=step_s[1:],
                  pull_after_block_s=pull_s,
                  total_s=round(time.perf_counter() - t0, 2),
                  **clock.since(m0))
    print(f"trainer: {json.dumps(report)}", flush=True)
    return report


def resilience_clean():
    """No recovery/degradation event may have fired on a healthy run."""
    from paddle_tpu.observability import registry
    moved = {f"{m['name']}{m['labels']}": m["value"]
             for m in registry().snapshot()
             if m["name"].startswith("resilience.") and m.get("value")}
    if moved:
        raise RuntimeError(f"resilience counters moved: {moved}")
    print("resilience: every resilience.* counter is zero", flush=True)


def main():
    t0 = time.perf_counter()
    device = require_tpu()

    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.enable()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({entries} entries at start: "
          f"{'warm' if entries else 'cold'})", flush=True)
    clock = CompileClock()

    server = server_phase(clock)
    trainer = trainer_phase(clock)
    resilience_clean()

    total = clock.since((0, 0.0, 0, 0))
    print("summary: " + json.dumps(dict(
        cache="warm" if entries else "cold",
        wall_s=round(time.perf_counter() - t0, 1),
        server_setup_s={k: v["setup_s"] for k, v in server.items()},
        trainer_first_step_s=trainer["first_step_s"], **total)), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
