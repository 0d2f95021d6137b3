"""``BENCHMARK.json`` against the files the harness finds by name, and the
sums the residency and roofline readers take from the request records."""

import importlib.util
import json
import os

import numpy as np

from harness import serve

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_per_layer_metric_has_one_descriptor_that_agrees():
    bench = read(ROOT, "BENCHMARK.json")
    folder = os.path.join(BENCH, "layer_metrics")
    found = {f[:-5]: read(folder, f) for f in os.listdir(folder)
             if f.endswith(".json")}
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert sorted(found) == sorted(entries)
    for name, d in found.items():
        assert d["name"] == name
        for key in ("layer", "unit", "moves"):
            assert d[key] == entries[name][key], (name, key)
        fname, func = d["reader"].split(":")
        spec = importlib.util.spec_from_file_location(
            "lm_" + fname[:-3], os.path.join(folder, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(getattr(mod, func)), d["reader"]


def test_every_cell_has_its_files_and_its_metrics():
    bench = read(ROOT, "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = w["name"]
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        traffic = read(BENCH, "traffic", w["traffic"] + ".json")
        if traffic["kind"] == "serve_open":
            assert read(BENCH, "cells", cell + ".json")["rate_rps"] > 0
        e2e = [m["name"] for m in bench["end_to_end"] if reports(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        layer = [m for m in bench["per_layer"] if reports(m, cell)]
        assert layer, cell
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in e2e for m in layer), cell


class _Result:
    def __init__(self, n):
        self.tokens = np.zeros(n, np.int32)


def test_live_blocks_and_attended_tokens_by_hand():
    # 100 prompt tokens, 30 tokens out, the last at decode step 40: its 29
    # decode steps are 12..40, and at the j-th it holds 100 + j tokens
    a = dict(prompt_len=100, finish_step=40, result=_Result(30))
    # 250 prompt tokens, 10 out, finished at step 20: steps 12..20
    b = dict(prompt_len=250, finish_step=20, result=_Result(10))
    unfinished = dict(prompt_len=64, finish_step=None, result=None)
    recs = [a, b, unfinished]
    # steps 21..40: only a runs, holding 110..129 tokens: one block of 128
    # for 19 steps and two for the last
    assert serve.live_blocks(recs, 20, 40, 128) == (19 * 1 + 2) / 20
    # steps 13..20: a holds 102..109 (one block), b 252..259 (two, then
    # three from 257 on: steps 18, 19, 20)
    assert serve.live_blocks(recs, 12, 20, 128) == (8 + 5 * 2 + 3 * 3) / 8
    assert serve.live_blocks(recs, 40, 40, 128) is None
    rows, tokens = serve.attended_tokens(recs, 12, 20)
    assert rows == 16
    # the j-th decode step reads P + j - 1 cached tokens
    assert tokens == sum(range(101, 109)) + sum(range(251, 259))
