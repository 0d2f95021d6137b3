"""On-TPU Pallas parity suite.

Runs on the chip only (`python -m pytest tests_tpu -q` through the chip
tool) — unlike tests/, which pins the 8-device CPU simulator, this conftest
leaves JAX's default backend in place. Without a TPU every test FAILS: a
machine whose chip did not initialise must not report a green suite. A
kernel that raises is a failure too (the ops have no XLA fallback arm).

Reference discipline: the OpTest pattern (SURVEY.md §4) — every Pallas
kernel checked against its XLA twin, forward and backward, on hardware.
"""

import functools

import jax
import pytest


@functools.lru_cache(maxsize=None)
def _platform() -> str:
    return jax.devices()[0].platform


def require_tpu():
    if _platform() != "tpu":
        pytest.fail(f"tests_tpu needs a TPU: jax.devices()[0].platform is "
                    f"{_platform()!r}", pytrace=False)


@pytest.fixture(autouse=True)
def _on_chip_with_pallas():
    require_tpu()
    from paddle_tpu.core.flags import get_flags, set_flags
    prior = get_flags(["FLAGS_use_pallas_kernels"])
    set_flags({"FLAGS_use_pallas_kernels": True})
    import paddle_tpu
    paddle_tpu.seed(0)
    yield
    set_flags(prior)
