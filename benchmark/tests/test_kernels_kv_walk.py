"""The reader of ``layer_metrics/kernels_kv_walk.py`` on hand-made
``engine.stats`` deltas: with the counters, without them (the parent, or
an engine with its own step), and over a window that walked nothing."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_kernels_kv_walk", os.path.join(
            os.path.dirname(HERE), "layer_metrics", "kernels_kv_walk.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


kv_walk = _load()


@pytest.mark.parametrize("stats, want", [
    (dict(steps=400, kv_blocks_walked=17600, kv_blocks_dense=83200),
     dict(value=100.0 * 17600 / 83200, kv_blocks_walked=17600,
          kv_blocks_dense=83200, blocks_a_step=44.0)),
    (dict(steps=300, kv_blocks_walked=70500, kv_blocks_dense=79200),
     dict(value=100.0 * 70500 / 79200, kv_blocks_walked=70500,
          kv_blocks_dense=79200, blocks_a_step=235.0)),
    (dict(steps=400, lookahead_ticks=244), None),   # the parent: no counter
    (dict(steps=0, kv_blocks_walked=0, kv_blocks_dense=0), None),
])
def test_kv_walk_share(stats, want):
    assert kv_walk.kv_walk_share(dict(stats=stats)) == want
