"""Device control veneer (`paddle.set_device` parity).

On TPU, device placement is owned by XLA + shardings; this module exposes the
query surface (`get_device`, device counts) and maps `set_device` onto JAX's
default-device mechanism.
"""

import jax

_current = [None]


def set_device(device: str):
    """Accepts 'tpu', 'cpu', 'tpu:0' etc. Sets JAX default device."""
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    try:
        plat_devices = jax.devices() if name == "any" else jax.devices(name)
    except RuntimeError as e:
        raise ValueError(
            f"set_device({device!r}): JAX has no {name!r} backend here "
            f"(default backend: {jax.default_backend()!r})") from e
    if idx >= len(plat_devices):
        raise ValueError(
            f"set_device({device!r}): JAX has {len(plat_devices)} "
            f"{name!r} device(s)")
    dev = plat_devices[idx]
    jax.config.update("jax_default_device", dev)
    _current[0] = device
    return dev


def get_device() -> str:
    if _current[0] is not None:
        return _current[0]
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_compiled_with_cuda() -> bool:
    return False
