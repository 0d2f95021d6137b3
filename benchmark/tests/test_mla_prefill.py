"""The reader of ``layer_metrics/mla_prefill.py`` on traces written
here: waves whole in the window, a wave cut by its edge, the parent (no
kernel, no counter), a program on the ``jnp`` path; and the flop and byte
function against sums done by hand."""

import importlib.util
import json
import os

import pytest

from harness import opcount, opcount_mla_prefill, trace_reduce
from test_xing4_prefill import MS, PEAKS, write_trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = ("%mla_flash_prefill.{n} = bf16[1,2048,16384] "
          "custom-call(bf16[1,128,2048,128] %a)")
DECODE = ("%mla_paged_decode.9 = (f32[128,128,512], bf16[5,2048,256,640]) "
          "custom-call(s32[128] %a)")
WHILE = ("%while.{n} = (s32[], bf16[8,1,256,128,128]) "
         "while((s32[], bf16[8,1,256,128,128]) %t)")


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_mla_prefill",
        os.path.join(BENCH, "layer_metrics", "mla_prefill.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reader = _load()


def config(name="deepseek-v2"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def wave(t0, s_pad, call_ms, layers=5, R=0, counter=True, kernel=KERNEL):
    """One wave from ``t0``: a span of (layers x call_ms + 20) ms and
    under it ``layers`` kernel calls, 2 ms apart."""
    ops, t = [], t0 + 10 * MS
    for n in range(layers):
        ops.append((t, t + int(call_ms * MS), kernel.format(n=n)))
        t += int(call_ms * MS) + 2 * MS
    stats = dict(rows=1, s_pad=s_pad, R=R)
    if counter:
        stats.update(prefill_attn_calls=layers)
    return ops, (t0, t + 10 * MS, "serving.step.prefill", stats)


def observe(path, name="deepseek-v2"):
    red = trace_reduce.reduce(trace_reduce.load(path))
    return dict(config=config(name), trace=red, trace_path=path, peaks=PEAKS)


def test_a_call_by_hand():
    d = opcount.dims(config())
    # one layer of a 2,048-position wave at 128 heads: the causal
    # triangle of 2,098,176 scores a head, 640 flops a score
    got = opcount_mla_prefill.mla_prefill_call(d, 2048)
    assert 2048 * 2049 // 2 == 2_098_176
    assert got["flops"] == 128 * 2_098_176 * 640
    assert 5 * got["flops"] == 5 * 128 * 2_098_176 * 640
    # q (128 + 64), k and v (128 + 128) and the output (128) a head a
    # position, the rotary key once: 2 bytes each
    assert got["bytes"] == 2 * (2048 * 128 * (192 + 256 + 128) + 2048 * 64)
    # 0.87 ms of matrix work against 0.37 ms of bytes: bound by flops
    assert got["flops"] / 197e12 == pytest.approx(0.873e-3, rel=0.01)
    assert got["flops"] / 197e12 > 2 * got["bytes"] / 819e9
    # behind 256 cached positions every query sees 256 keys more, and the
    # keys and values of 2,304 positions are read
    pre = opcount_mla_prefill.mla_prefill_call(d, 2048, R=256, rows=2)
    assert pre["flops"] == 2 * 128 * (2_098_176 + 256 * 2048) * 640
    assert pre["bytes"] == 2 * 2 * (2048 * 128 * (192 + 128)
                                    + 2304 * 128 * 256 + 2304 * 64)
    # Xing4.0: a quarter of the heads
    x = opcount_mla_prefill.mla_prefill_call(
        opcount.dims(config("xing4.0-29b-a4b")), 2048)
    assert 4 * x["flops"] == got["flops"]


def test_roofline_over_the_waves_that_lie_whole_in_the_window(tmp_path):
    # two whole waves (1,024 and 2,048 positions, 0.8 and 2.4 ms a call),
    # the decode kernel between them, and a third wave that the window's
    # end cuts: its last calls never ran under the trace
    o1, s1 = wave(10 * MS, 1024, 0.8)
    o2, s2 = wave(200 * MS, 2048, 2.4)
    o3, s3 = wave(900 * MS, 3584, 6.0)
    between = [(100 * MS, 102 * MS, DECODE)]
    path = write_trace(tmp_path / "t.xplane.pb", o1 + between + o2 + o3[:3],
                       [s1, s2, s3], window=(0, 950 * MS))
    got = reader.mla_prefill_roofline(observe(path))
    d = opcount.dims(config())
    flops = 5 * 128 * 640 * (1024 * 1025 // 2 + 2048 * 2049 // 2)
    assert flops == 5 * (
        opcount_mla_prefill.mla_prefill_call(d, 1024)["flops"]
        + opcount_mla_prefill.mla_prefill_call(d, 2048)["flops"])
    sec = 5 * 0.8e-3 + 5 * 2.4e-3
    assert got["bound"] == "flops"
    assert got["value"] == pytest.approx(100 * flops / 197e12 / sec)
    assert 30 < got["value"] < 40
    assert (got["calls"], got["waves_traced"], got["waves_cut"]) == (10, 2, 1)
    assert got["ms_a_wave"] == pytest.approx(8.0)
    assert got["positions_a_wave"] == 1536
    assert got["mean_call_ms"] == pytest.approx(1.6)
    # the step program's reader finds its kernel by ``mla_paged_decode``:
    # the new kernel's name does not hold it, nor the other way round
    red = trace_reduce.reduce(trace_reduce.load(path))
    assert trace_reduce.name_seconds(red, "mla_paged_decode") == (
        pytest.approx(2e-3), 1)
    assert trace_reduce.name_seconds(red, reader.KERNEL)[1] == 13
    assert "mla_paged_decode" not in reader.KERNEL


def test_a_cached_prefix_counts_its_keys(tmp_path):
    ops, span = wave(10 * MS, 2048, 1.0, layers=7, R=256)
    path = write_trace(tmp_path / "r.xplane.pb", ops, [span])
    got = reader.mla_prefill_roofline(observe(path, "xing4.0-29b-a4b"))
    flops = 7 * 32 * 640 * (2048 * 2049 // 2 + 256 * 2048)
    assert got["bound"] == "flops"
    assert got["value"] == pytest.approx(100 * flops / 197e12 / 7e-3)
    assert got["calls"] == 7


def test_nothing_to_read_is_none(tmp_path):
    cfg = config()
    # no trace at all (--trace 0, or the CPU)
    assert reader.mla_prefill_roofline(dict(config=cfg, trace=None,
                                            trace_path=None)) is None
    # the parent: XLA's loops over score blocks under spans without the
    # counter
    ops, span = wave(10 * MS, 2048, 35.3, counter=False, kernel=WHILE)
    path = write_trace(tmp_path / "parent.xplane.pb", ops, [span])
    assert reader.mla_prefill_roofline(observe(path)) is None
    # the kernel under spans that say nothing: nothing to count it by
    ops, span = wave(10 * MS, 2048, 2.4, counter=False)
    path = write_trace(tmp_path / "bare.xplane.pb", ops, [span])
    assert reader.mla_prefill_roofline(observe(path)) is None
    # the counter of a program on the jnp path: 0 calls, no kernel
    ops, span = wave(10 * MS, 2048, 35.3, kernel=WHILE)
    span[3].update(prefill_attn_calls=0)
    path = write_trace(tmp_path / "zero.xplane.pb", ops, [span])
    assert reader.mla_prefill_roofline(observe(path)) is None
    # only a wave cut by the window
    ops, span = wave(10 * MS, 2048, 2.4)
    path = write_trace(tmp_path / "cut.xplane.pb", ops[:2], [span])
    assert reader.mla_prefill_roofline(observe(path)) is None
