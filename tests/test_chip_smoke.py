"""Nothing may pass for a chip run on a machine without a chip: the smoke
and the on-chip suite fail on the CPU, and the compile cache sits where
the outside put it or at one fixed path in the checkout."""

import os
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout      # no result line


def test_tests_tpu_fail_without_a_tpu():
    """Every tests_tpu test runs this check first (autouse fixture)."""
    import importlib.util

    import pytest

    spec = importlib.util.spec_from_file_location(
        "tests_tpu_conftest", os.path.join(ROOT, "tests_tpu", "conftest.py"))
    conftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conftest)
    with pytest.raises(pytest.fail.Exception, match="needs a TPU"):
        conftest.require_tpu()


def test_compile_cache_placement(monkeypatch):
    from paddle_tpu.core import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.enable() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_compile_cache")
        assert compile_cache.enable() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert compile_cache.enable() == fixed      # same path every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_process_tier_refuses_a_parent_that_holds_the_chip(monkeypatch):
    import pytest

    from paddle_tpu.serving import router

    assert router._accelerator_held() is None       # CPU here
    monkeypatch.setattr(router, "_accelerator_held", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the chip"):
        router.Router(None, replicas=2, processes=True,
                      model_factory=dict)
