"""Four chips in one process: the chip_smoke phases over a 2x2 host.

Run by a builder through the chip tool (`--chips 4`), not by the driver:

    python examples/four_chip_check.py

* trainer — `fleet.make_train_step` over `hybrid_configs = {dp_degree: 2,
  mp_degree: 2}` at GPT-2 345M widths, b8 x s1024: every device of
  `jax.devices()` holds the parameters, the first-step loss matches a
  one-device forward of the same state and batch (flash kernels on), the loss
  falls, the flash kernels (run per shard under shard_map) are in the step;
* flash dropout — a partitioned flash call with attention dropout equals
  the one-device call, forward and backward (same masks on every shard's
  own rows and heads);
* server — `ServingEngine(..., mesh=build_mesh({"mp": 4}))`: requests
  finish and replay against the plain forward. Under `mp_axis` the paged
  step takes the jnp reference by design, so the Mosaic kernels found in
  the step program are printed, not required.

Last stdout line: {"ok": true, "device": {...}}; non-zero exit otherwise.
"""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402

LOSS_TOL = 2e-2     # bf16 forward, one device vs dp2 x mp2


def trainer(clock):
    import jax
    from paddle_tpu.parallel import fleet

    m0 = clock.mark()
    model, batch, step_fn, init_fn, _ = cs.build_trainer(
        {"dp_degree": 2, "mp_degree": 2})
    state, opt_state = init_fn()
    devices = set(jax.devices())
    missing = {k for k, v in state.items()
               if v.sharding.device_set != devices}
    if missing:
        raise RuntimeError(f"parameters not on every device: "
                           f"{sorted(missing)[:4]}")

    # the reference: the same forward on one device, flash kernels and
    # all (outside make_train_step's trace nothing partitions them)
    one = jax.devices()[0]
    fwd_one = jax.jit(cs.forward_loss(model))
    args_one = jax.device_put((state, batch), one)
    ks_one = cs.kernels_in(fwd_one.lower(*args_one))
    if "flash_attention_fwd" not in ks_one:
        raise RuntimeError(f"the one-device forward holds no flash kernel "
                           f"(found {sorted(ks_one)})")
    loss_one = float(fwd_one(*args_one))

    losses = []
    for _ in range(cs.TRAIN_STEPS):
        state, opt_state, loss = step_fn(state, opt_state, batch)
        losses.append(float(jax.block_until_ready(loss)))
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss {losses}")
    if abs(losses[0] - loss_one) > LOSS_TOL:
        raise RuntimeError(f"dp2 x mp2 first-step loss {losses[0]} vs "
                           f"one-device loss {loss_one} (tol {LOSS_TOL})")
    cs.check_falling(losses, "trainer[dp2 x mp2]")
    ks = cs.kernels_in(step_fn.lower(cs.TRAIN_BATCH, cs.TRAIN_SEQ))
    if "flash_attention_fwd" not in ks or "flash_attention_bwd_dq" not in ks:
        raise RuntimeError(f"the dp2 x mp2 step lacks the flash kernels "
                           f"(found {sorted(ks)})")
    rep = dict(mesh=dict(fleet.get_fleet().mesh.shape),
               losses=[round(x, 5) for x in losses],
               loss_one_device=round(loss_one, 4), kernels=sorted(ks),
               **clock.since(m0))
    print(f"trainer[dp2 x mp2]: {json.dumps(rep)}", flush=True)


def flash_dropout():
    """Attention dropout through a partitioned flash call (dp2 x mp2, as
    make_train_step opens it) against the same call on one device: every
    shard draws the masks of its own rows and heads, forward and
    backward, so out, dq, dk and dv are those of the one-device call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.core.rng import rng_guard
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.parallel import fleet

    mesh = fleet.get_fleet().mesh
    b, s, h, d = cs.TRAIN_BATCH, cs.TRAIN_SEQ, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    # every row and head alike, so only the masks tell them apart
    q, k, v = (jnp.broadcast_to(
        jax.random.normal(kk, (1, s, 1, d), jnp.bfloat16) * 0.5,
        (b, s, h, d)) for kk in keys)

    def run(q, k, v, key):
        def loss(q, k, v):
            with rng_guard(dropout=key):
                out = fa._flash_call(q, k, v, True, None, None, None, None,
                                     dropout_p=0.1)
            return out.astype(jnp.float32).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    def run_partitioned(*a):
        with fa.partitioned(mesh, ("dp", "sharding"), "mp"):
            return run(*a)

    key = jax.random.PRNGKey(7)
    one = [np.asarray(x, np.float32) for x in jax.jit(run)(
        *jax.device_put((q, k, v, key), jax.devices()[0]))]
    rows = NamedSharding(mesh, P("dp"))
    four = jax.jit(run_partitioned)(
        *jax.device_put((q, k, v), rows), key)
    if len(four[0].sharding.device_set) != 4:
        raise RuntimeError("the partitioned flash call ran on "
                           f"{four[0].sharding.device_set}")
    unequal = {}
    for name, a, ref in zip(("out", "dq", "dk", "dv"), four, one):
        a = np.asarray(a, np.float32)
        # another mask moves early rows by O(|v|); rounding moves nothing
        if not np.abs(a - ref).max() <= 1e-2 * np.abs(ref).max():
            raise RuntimeError(
                f"flash dropout: {name} over dp2 x mp2 is not the "
                f"one-device {name} (max diff {np.abs(a - ref).max()}, "
                f"max {np.abs(ref).max()})")
        unequal[name] = int((a != ref).sum())
    out = one[0]
    if np.array_equal(out[0], out[b // 2]) or \
            np.array_equal(out[:, :, 0], out[:, :, h // 2]):
        raise RuntimeError("flash dropout: two dp shards or two mp "
                           "head-shards drew the same masks")
    print(f"flash dropout[dp2 x mp2]: out, dq, dk, dv are the one-device "
          f"call's (elements not bit-equal: {unequal}); masks differ "
          f"across rows and heads", flush=True)


def server(clock):
    import numpy as np
    import paddle_tpu
    from paddle_tpu.models.gpt import GPTPretrainModel
    from paddle_tpu.parallel.topology import build_mesh

    paddle_tpu.seed(0)
    model = GPTPretrainModel(cs.gpt2_345m()).bfloat16()
    model.eval()
    lens, new = (24, 57, 93, 120, 150, 200), (16, 24, 32, 20, 28, 36)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, model.cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    results, _ = cs.serve(model, prompts, new, clock, "mp=4",
                          mesh=build_mesh({"mp": 4}))
    cs.make_replay(model)(results, "mp=4")


def main():
    device = cs.require_tpu()
    if device["count"] != 4:
        sys.exit(f"four_chip_check: needs 4 devices, JAX reports "
                 f"{device['count']}")
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    clock = cs.CompileClock()
    trainer(clock)
    flash_dropout()
    server(clock)
    cs.resilience_clean()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
