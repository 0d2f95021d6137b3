"""A cell run with the PROGRAM in a lower precision than its
configuration states and the reference left as it is: ``correct`` has
to come out false, by the same limits that pass the program as stated.

    python3 benchmark/control.py <control> --workload <cell> --seed <n> --seconds <s> --trace 0

Everything after ``<control>`` is ``run.py``'s own command line, and the
run is ``run.py``'s: the same files, traffic, warm-up, window and
reference. Controls (configurations of arch ``xing4``):

``int8_weights``   every matrix the engine serves from (projections,
                   experts, shared expert, head; not the embedding, the
                   router or the mHC mixers) rounded to int8 with one
                   scale an output channel, held as bf16.
``bf16_router``    the router's logits computed and rounded in bf16 (the
                   program computes them in float32).

The weights are rounded in place, leaf by leaf (two copies of 11 GB do
not fit one chip), and made anew from ``--seed`` for the reference once
the engine is closed: the same seed on the same chip gives the same
weights (``harness/model.py``).
"""

import functools
import gc
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_KEPT = ("embed", "phi", "gate.weight")     # embedding, mixers, router


def _round_int8(state: dict) -> None:
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def q8(w):
        f = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(f).max(axis=-2, keepdims=True),
                            1e-30) / 127.0
        return (jnp.round(f / scale) * scale).astype(w.dtype)

    for name in sorted(state):
        w = state[name]
        if (w.ndim >= 2 and not name.endswith("bias")
                and not any(k in name for k in _KEPT)):
            state[name] = q8(w)


def _bf16_router() -> None:
    import jax.numpy as jnp
    from paddle_tpu.models import xing4

    def route(w, cfg, x):
        logits = jnp.matmul(x.astype(jnp.bfloat16),
                            w["gate.weight"].astype(jnp.bfloat16))
        return xing4.sigmoid_topk_routing(
            logits.astype(jnp.float32), w["gate.e_score_correction_bias"],
            cfg.num_experts_per_tok, scaling=cfg.routed_scaling_factor,
            normalize_topk=cfg.norm_topk_prob)

    xing4.route = route


def main():
    controls = ("int8_weights", "bf16_router")
    if len(sys.argv) < 2 or sys.argv[1] not in controls:
        sys.exit(f"usage: control.py {{{'|'.join(controls)}}} <run.py's "
                 f"arguments>")
    control = sys.argv.pop(1)
    if "--rehearse" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [ROOT, HERE]
    import run
    import jax
    import jax.numpy as jnp
    from harness import log, model, serve
    from paddle_tpu import serving

    engines = []
    real_engine, real_check = serving.ServingEngine, serve.check_outputs

    def engine(mdl, state=None, **options):
        if control == "int8_weights":
            _round_int8(state)          # the harness's own dict, in place
        eng = real_engine(mdl, state=state, **options)
        engines.append(eng)
        return eng

    def check(measured, state, cfg, max_seq_len, n_out, seed):
        if control == "int8_weights":
            shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in state.items()}
            state.clear()
            for eng in engines:         # closed; let go of the rounded leaves
                eng._state = None
            gc.collect()
            log(phase="control", control=control,
                live_bytes_before_new_weights=sum(
                    a.nbytes for a in jax.live_arrays()))
            state.update(model.make_state(shapes, seed, cfg["init_std"],
                                          jnp.bfloat16))
        return real_check(measured, state, cfg, max_seq_len, n_out, seed)

    if control == "bf16_router":
        _bf16_router()
    serving.ServingEngine, serve.check_outputs = engine, check
    log(phase="control", control=control)
    run.main()


if __name__ == "__main__":
    main()
