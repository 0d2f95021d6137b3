"""Where JAX's persistent compilation cache lives for this checkout.

Entry points (``chip_smoke.py``, ``bench.py``, the ``examples/*_bench.py``
mains, ``serving.worker.worker_main``) call :func:`enable` once before
their first compile; ``import paddle_tpu`` never does. The cache key
includes the directory, so the path is fixed: a moving one never hits.
"""

import os

import jax

# <checkout>/.jax_compile_cache (git-ignored)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing
    is configured here. Unset: the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
