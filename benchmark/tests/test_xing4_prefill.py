"""The reader of ``layer_metrics/xing4_prefill.py`` on traces written
here: waves whole in the window, a wave cut by its edge, the parent (no
kernel, no counters), a wave with too few picks an expert; and the byte
and flop function against sums done by hand."""

import importlib.util
import json
import os

import pytest

from harness import opcount, opcount_xing4_prefill, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = dict(flops_bf16=197e12, hbm_bytes_per_s=819e9)
KERNEL = ("%moe_grouped_ffn_prefill.{n} = bf16[4480,3584] "
          "custom-call(s32[64] %a)")
DECODE = "%moe_grouped_ffn_decode.9 = bf16[64,3584] custom-call(s32[64] %a)"
RAGGED = "%ragged-dot.1 = bf16[4096,1024] ragged-dot(bf16[4096,3584] %c)"
MS = 1_000_000      # ns


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_xing4_prefill",
        os.path.join(BENCH, "layer_metrics", "xing4_prefill.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reader = _load()


def config():
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def write_trace(path, ops, spans, window=(0, 1000 * MS)):
    """A trace of one device (``ops``: [(start, end, name)]) and one
    host line of ``spans``: [(start, end, name, {stat: int})]."""
    from jax.profiler import ProfileData

    def plane(name, line, events):
        ids, stat_ids, rows = {}, {}, ""
        for s, e, n, stats in events:
            ids.setdefault(n, len(ids) + 1)
            st = "".join(
                f" stats {{ metadata_id: "
                f"{stat_ids.setdefault(k, len(stat_ids) + 1)} "
                f"int64_value: {v} }}" for k, v in stats.items())
            rows += (f"    events {{ metadata_id: {ids[n]} offset_ps: "
                     f"{s * 1000} duration_ps: {(e - s) * 1000}{st} }}\n")
        meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                       f'"{n}" }} }}\n' for n, i in ids.items())
        meta += "".join(f'  stat_metadata {{ key: {i} value {{ id: {i} name: '
                        f'"{n}" }} }}\n' for n, i in stat_ids.items())
        return (f'planes {{ name: "{name}"\n  lines {{ name: "{line}" '
                f'timestamp_ns: 0\n{rows}  }}\n{meta}}}\n')

    text = plane("/device:TPU:0", "XLA Ops",
                 [(s, e, n, {}) for s, e, n in ops]) + plane(
        "/host:CPU", "bench",
        [(window[0], window[1], "bench.window", {})] + list(spans))
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def wave(t0, s_pad, call_ms, layers=6, k=4, counters=True, kernel=KERNEL):
    """One wave from ``t0``: a span of (layers x call_ms + 20) ms and
    under it ``layers`` kernel calls, 2 ms apart."""
    ops, t = [], t0 + 10 * MS
    for n in range(layers):
        ops.append((t, t + int(call_ms * MS), kernel.format(n=n)))
        t += int(call_ms * MS) + 2 * MS
    stats = dict(rows=1, s_pad=s_pad, R=0)
    if counters:
        stats.update(prefill_moe_calls=layers,
                     prefill_moe_rows=k * s_pad * layers)
    return ops, (t0, t + 10 * MS, "serving.step.prefill", stats)


def observe(path):
    red = trace_reduce.reduce(trace_reduce.load(path))
    return dict(config=config(), trace=red, trace_path=path, peaks=PEAKS)


def test_calls_by_hand():
    d = opcount.dims(config())
    # one wave of 1,024 padded positions: 6 calls, 4,096 routed rows each
    got = opcount_xing4_prefill.moe_prefill_calls(d, 6, 6 * 4096)
    expert = 3 * 3584 * 1024 * 2
    assert expert == 22_020_096
    assert got["bytes"] == 6 * 64 * expert + 6 * 4096 * 2 * 3584 * 2
    assert got["flops"] == 6 * 4096 * 3 * 2 * 3584 * 1024
    # 8.46 GB of weights a wave: 10.3 ms at 819 GB/s, the flops a third
    assert 6 * 64 * expert / 819e9 == pytest.approx(10.3e-3, rel=0.01)
    assert got["bytes"] / 819e9 > 3 * got["flops"] / 197e12
    # the largest bucket is all but balanced: 14,336 rows a call
    big = opcount_xing4_prefill.moe_prefill_calls(d, 6, 6 * 14336)
    assert big["bytes"] / 819e9 == pytest.approx(
        big["flops"] / 197e12, rel=0.25)
    assert opcount_xing4_prefill.every_expert_touched(d, 512)
    assert not opcount_xing4_prefill.every_expert_touched(d, 511)


def test_roofline_over_the_waves_that_lie_whole_in_the_window(tmp_path):
    # two whole waves (1,024 and 2,048 positions, 2.0 and 2.5 ms a call),
    # a decode kernel and XLA between them, and a third wave that the
    # window's end cuts: its last calls never ran under the trace
    o1, s1 = wave(10 * MS, 1024, 2.0)
    o2, s2 = wave(200 * MS, 2048, 2.5)
    o3, s3 = wave(900 * MS, 3584, 4.0)
    between = [(100 * MS, 102 * MS, DECODE), (110 * MS, 111 * MS, RAGGED)]
    path = write_trace(tmp_path / "t.xplane.pb",
                       o1 + between + o2 + o3[:3], [s1, s2, s3],
                       window=(0, 950 * MS))
    got = reader.moe_prefill_roofline(observe(path))
    d = opcount.dims(config())
    need = opcount_xing4_prefill.moe_prefill_calls(
        d, 12, 4 * 6 * (1024 + 2048))
    sec = 6 * 2.0e-3 + 6 * 2.5e-3
    assert got["bound"] == "hbm"
    assert got["value"] == pytest.approx(100 * need["bytes"] / 819e9 / sec)
    assert 60 < got["value"] < 100
    assert (got["calls"], got["waves_traced"], got["waves_cut"]) == (12, 2, 1)
    assert got["ms_a_wave"] == pytest.approx(13.5)
    assert got["routed_rows_a_wave"] == 4 * 6 * 1536
    assert got["mean_call_ms"] == pytest.approx(2.25)
    # the decode kernel's reader does not see the new kernel, nor this
    # one the decode kernel's
    red = trace_reduce.reduce(trace_reduce.load(path))
    assert trace_reduce.name_seconds(red, "moe_grouped_ffn_decode") == (
        pytest.approx(2e-3), 1)
    assert trace_reduce.name_seconds(red, reader.KERNEL)[1] == 15


def test_nothing_to_read_is_none(tmp_path):
    cfg = config()
    # no trace at all (--trace 0, or the CPU)
    assert reader.moe_prefill_roofline(dict(config=cfg, trace=None,
                                            trace_path=None)) is None
    # the parent: ragged_dot under spans without the counters
    ops, span = wave(10 * MS, 1024, 5.0, counters=False, kernel=RAGGED)
    path = write_trace(tmp_path / "parent.xplane.pb", ops, [span])
    assert reader.moe_prefill_roofline(observe(path)) is None
    # the kernel under spans that say nothing (a program from before the
    # counters): nothing to divide by
    ops, span = wave(10 * MS, 1024, 2.0, counters=False)
    path = write_trace(tmp_path / "bare.xplane.pb", ops, [span])
    assert reader.moe_prefill_roofline(observe(path)) is None
    # counters of a program on ragged_dot: 0 calls, no kernel
    ops, span = wave(10 * MS, 1024, 5.0, kernel=RAGGED)
    span[3].update(prefill_moe_calls=0, prefill_moe_rows=0)
    path = write_trace(tmp_path / "zero.xplane.pb", ops, [span])
    assert reader.moe_prefill_roofline(observe(path)) is None
    # a wave of 64 padded positions: 4 picks an expert, not every expert
    # can be taken as touched
    ops, span = wave(10 * MS, 64, 1.8)
    path = write_trace(tmp_path / "small.xplane.pb", ops, [span])
    assert reader.moe_prefill_roofline(observe(path)) is None
    # only cut waves
    ops, span = wave(10 * MS, 1024, 2.0)
    path = write_trace(tmp_path / "cut.xplane.pb", ops[:2], [span])
    assert reader.moe_prefill_roofline(observe(path)) is None
