"""The reader of ``layer_metrics/engine_lookahead.py`` on hand-made
``engine.stats`` deltas: with the counter, without it (a program from
before it existed), and over a window that decoded nothing."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_engine_lookahead", os.path.join(
            os.path.dirname(HERE), "layer_metrics", "engine_lookahead.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lookahead = _load()


@pytest.mark.parametrize("stats, want", [
    (dict(steps=400, lookahead_ticks=244, lookahead_discarded_tokens=37),
     dict(value=61.0, lookahead_ticks=244, steps=400, discarded_tokens=37)),
    (dict(steps=400, lookahead_ticks=0, lookahead_discarded_tokens=0),
     dict(value=0.0, lookahead_ticks=0, steps=400, discarded_tokens=0)),
    (dict(steps=400, decode_tokens=8000), None),    # the parent: no counter
    (dict(steps=0, lookahead_ticks=0, lookahead_discarded_tokens=0), None),
])
def test_lookahead_share(stats, want):
    assert lookahead.lookahead_share(dict(stats=stats)) == want
