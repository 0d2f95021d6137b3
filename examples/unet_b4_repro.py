"""Minimized repro / bisect harness for the UNet b4 compiler crash.

ROADMAP r5: SD-1.5 UNet *training* at batch 4 reproducibly crashes the
compiler ("TPU compiler subprocess" on chip; also reported against
the CPU sim) while every shape passes in isolation. This script bisects
the two axes the crash correlates with — the BATCH and the number of
ATTENTION LEVELS carrying transformer blocks — and prints the minimal
failing config.

Every candidate compiles in a fresh SUBPROCESS: a compiler abort
(SIGABRT/SIGSEGV in the XLA subprocess takes the Python process with it)
kills only that child, so the bisect loop survives and can attribute the
crash to a config instead of dying with it. A non-zero child exit that
isn't a clean Python failure is reported with its signal/returncode.

Run:  python examples/unet_b4_repro.py                # full bisect
      python examples/unet_b4_repro.py --max_batch 8  # wider batch axis
Internal: --one --batch B --levels 0,1,2  runs a single candidate
(one jitted train step) and exits 0 on success.
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_one(batch: int, levels, train: bool) -> None:
    """One candidate: build the UNet at the bench shapes with the given
    attention levels, jit ONE step (train or fwd), run it."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu
    from paddle_tpu.models.unet import UNetConfig, UNetModel
    from paddle_tpu.nn.layer import functional_call

    on_tpu = jax.devices()[0].platform == "tpu"
    paddle_tpu.seed(0)
    cfg = UNetConfig.sd15() if on_tpu else UNetConfig.tiny()
    cfg = dataclasses.replace(cfg, attention_levels=tuple(levels))
    res = 64 if on_tpu else 16
    ctx_len = 77 if on_tpu else 8

    model = UNetModel(cfg).bfloat16()
    if not train:
        model.eval()
    state = model.trainable_state()
    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.standard_normal(
        (batch, cfg.in_channels, res, res)), jnp.bfloat16)
    t = jnp.asarray(rng.randint(0, 1000, (batch,)))
    ctx = jnp.asarray(rng.standard_normal(
        (batch, ctx_len, cfg.context_dim)), jnp.bfloat16)

    if train:
        from paddle_tpu.optimizer import AdamW
        opt = AdamW(learning_rate=1e-4, multi_precision=False)
        opt_state = opt.init_state(state)
        noise = jnp.asarray(rng.standard_normal(x0.shape), jnp.bfloat16)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(st, ost):
            def loss_fn(s):
                eps = functional_call(model, s, x0, t, ctx)
                return jnp.mean(jnp.square(
                    eps.astype(jnp.float32) - noise.astype(jnp.float32)))
            loss, grads = jax.value_and_grad(loss_fn)(st)
            st, ost = opt.update(grads, ost, st)
            return st, ost, loss

        _, _, loss = step(state, opt_state)
        float(loss)
    else:
        out = jax.jit(
            lambda s, x: functional_call(model, s, x, t, ctx))(state, x0)
        float(jnp.sum(out.astype(jnp.float32)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", action="store_true",
                    help="internal: run a single candidate in-process")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--levels", default="0,1,2",
                    help="comma-separated attention levels ('' = none)")
    ap.add_argument("--max_batch", type=int, default=4)
    ap.add_argument("--fwd", action="store_true",
                    help="bisect the forward pass instead of training")
    ap.add_argument("--timeout", type=int, default=900,
                    help="per-candidate compile+run timeout (s)")
    ns = ap.parse_args()
    levels = tuple(int(v) for v in ns.levels.split(",") if v != "")

    if ns.one:
        run_one(ns.batch, levels, train=not ns.fwd)
        print("OK")
        return

    # full attention-level set from the bench config (sd15: (0, 1, 2))
    batches = [b for b in (1, 2, 4, 8, 16) if b <= ns.max_batch]
    level_sets = [levels[:i] for i in range(len(levels) + 1)]
    rows = []
    first_fail = None
    for b in batches:
        for ls in level_sets:
            cmd = [sys.executable, os.path.abspath(__file__), "--one",
                   "--batch", str(b),
                   "--levels", ",".join(map(str, ls))]
            if ns.fwd:
                cmd.append("--fwd")
            try:
                p = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=ns.timeout)
                ok = p.returncode == 0 and "OK" in p.stdout
                status = ("ok" if ok else
                          f"exit {p.returncode}"
                          + (f" (signal {-p.returncode})"
                             if p.returncode < 0 else ""))
                tail = "" if ok else p.stderr.strip().splitlines()[-1:] or ""
            except subprocess.TimeoutExpired:
                ok, status, tail = False, f"timeout {ns.timeout}s", ""
            row = {"batch": b, "attention_levels": list(ls),
                   "status": status}
            if tail:
                row["stderr_tail"] = tail[0] if isinstance(tail, list) \
                    else tail
            rows.append(row)
            print(json.dumps(row), flush=True)
            if not ok and first_fail is None:
                first_fail = row
    print(json.dumps({
        "mode": "fwd" if ns.fwd else "train",
        "minimal_failing_config": first_fail,
        "n_failed": sum(r["status"] != "ok" for r in rows),
        "n_total": len(rows),
    }))


if __name__ == "__main__":
    main()
