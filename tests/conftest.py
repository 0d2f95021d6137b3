"""Test rig: CPU backend simulating 8 devices.

SURVEY.md §4-lessons: parallelism-invariance tests run on a CPU-simulated
multi-device backend (strictly better than the reference's subprocess
pattern). The platform is pinned via jax.config before any backend use
(the driver also sets JAX_PLATFORMS=cpu, which JAX honours).
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8"
                           # XLA:CPU bug workaround (see examples/
                           # scale_report.py): AllReducePromotion check-fails
                           # on shardy's copy-rooted bf16 psum combiners
                           " --xla_disable_hlo_passes=all-reduce-promotion")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu
    paddle_tpu.seed(0)
    yield


@pytest.fixture
def mesh8():
    from paddle_tpu.parallel.topology import build_mesh
    return build_mesh({"dp": 2, "mp": 2, "sharding": 2})
