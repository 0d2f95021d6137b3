"""A configuration file -> the program's model object and seeded weights.

The model is built under ``paddle_tpu.LazyGuard`` (shapes only, no
buffers), and its weights are made on the device in ONE jitted call from
``--seed``, in the dtype they are served or trained in: never read from
disk, never drawn on the host, never leaf by leaf.
"""

import importlib


def load_object(dotted: str):
    module, name = dotted.rsplit(".", 1)
    return getattr(importlib.import_module(module), name)


def effective_config(config: dict, rehearse: bool) -> dict:
    """The configuration as run: the file itself, or under ``--rehearse``
    the file with its ``rehearse`` group (tiny widths) laid over it."""
    if not rehearse:
        return config
    out = dict(config)
    over = dict(config["rehearse"])
    out["model"] = dict(config["model"], config_kwargs=over.pop("config_kwargs"))
    out.update(over)
    return out


def build_model(config: dict):
    """The program's own model class over its own config class, as a
    user would build it, but with no parameter buffers."""
    import paddle_tpu
    spec = config["model"]
    cfg = load_object(spec["config_class"])(**spec["config_kwargs"])
    with paddle_tpu.LazyGuard():
        model = load_object(spec["class"])(cfg)
    return model


def make_state(shapes: dict, seed: int, std: float, dtype):
    """{name: array} for {name: ShapeDtypeStruct}: matrices ~ N(0, std),
    vectors 1 (norm scales) or 0 (``*.bias``), floating leaves in
    ``dtype``. One program, one dispatch."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    def init(key):
        out = {}
        for i, name in enumerate(names):
            s = shapes[name]
            dt = dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype
            if name.endswith("bias"):
                out[name] = jnp.zeros(s.shape, dt)
            elif len(s.shape) < 2:
                out[name] = jnp.ones(s.shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                out[name] = (jax.random.normal(k, s.shape, jnp.float32)
                             * std).astype(dt)
        return out

    # rbg: the chip's own bit generator; cheap to compile and to run for
    # two billion values. The same seed on the same chip gives the same
    # weights.
    return jax.jit(init)(jax.random.key(int(seed), impl="rbg"))
