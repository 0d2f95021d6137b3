"""The reduction from a profiler trace to device metrics, on a trace
built by hand and on ``sample.xplane.pb``, a few ticks cut from this
benchmark's first traced run on the chip
(``trace_reduce.excerpt_text_proto``)."""

import os

import pytest

from harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

# device: three ops, two of them overlapping; host: the window and two
# bench spans. Times in ps in the text, ns below.
HAND_BUILT = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0        duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000  duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000  duration_ps: 1000000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fused_paged_decode_step" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } } }
planes { name: "/host:CPU"
  lines { name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0        duration_ps: 11000000 }
    events { metadata_id: 2 offset_ps: 500000   duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4500000  duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 7500000  duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.sleep_until_due" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(step)" } } }
'''


@pytest.fixture(scope="module")
def hand_built():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(HAND_BUILT))


def test_union_and_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 7)]
    assert trace_reduce.union([(0, 10)], lo=2, hi=5) == [(2, 5)]
    assert trace_reduce.gaps([(1, 2), (4, 6)], 0, 10) == [
        (0, 1), (2, 4), (6, 10)]


def test_hand_built_trace(hand_built):
    red = trace_reduce.reduce(hand_built)
    # window: bench.window, 0 .. 11000 ns
    assert red.window_s == pytest.approx(11e-6)
    # busy: [1000, 4000] merged from two overlapping ops, and [8000, 9000];
    # the module-level line is not counted twice
    assert red.busy_s == pytest.approx(4e-6)
    assert red.n_devices == 1
    assert red.by_name["fusion.1"] == pytest.approx((3e-6, 2))
    assert trace_reduce.name_seconds(red, "paged_decode") == pytest.approx(
        (2e-6, 1))
    # gaps: 0-1000 (middle 500: bench.step starts at 500 -> covered),
    # 4000-8000 (middle 6000: the sleep), 9000-11000 (middle 10000: step)
    assert [(round(s * 1e9), n) for s, n in red.gaps] == [
        (1000, "bench.step"), (4000, "bench.sleep_until_due"),
        (2000, "bench.step")]
    idle_share = 1 - red.busy_s / red.window_s
    assert idle_share == pytest.approx(7 / 11)


def test_breakdown_lists(hand_built):
    b = trace_reduce.breakdown(trace_reduce.reduce(hand_built))
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(3e-6)]
    assert b["idle_gaps"][:2] == [
        ["bench.sleep_until_due: sum of the 1 gaps under 0.1 ms",
         pytest.approx(4e-6)],
        ["bench.step: sum of the 2 gaps under 0.1 ms", pytest.approx(3e-6)]]
    assert b["idle_gaps"][2] == ["bench.sleep_until_due: one gap",
                                 pytest.approx(4e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_excerpt_round_trip(hand_built):
    from jax.profiler import ProfileData
    text = trace_reduce.excerpt_text_proto(hand_built, 0.0, 12000.0)
    again = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    a, b = trace_reduce.reduce(hand_built), trace_reduce.reduce(again)
    assert a.busy_s == pytest.approx(b.busy_s)
    assert a.window_s == pytest.approx(b.window_s)
    assert a.gaps == pytest.approx(b.gaps) or [n for _, n in a.gaps] == [
        n for _, n in b.gaps]


def test_a_trace_without_device_ops_is_refused():
    from jax.profiler import ProfileData
    host_only = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { name: "/host:CPU" }'))
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(host_only)


@pytest.fixture(scope="module")
def sample():
    return trace_reduce.reduce(
        trace_reduce.load(os.path.join(HERE, "sample.xplane.pb")))


def test_recorded_sample_busy_and_idle(sample):
    """Four ticks of gpt2-345m.chat-1k on the v5e (PR 23's first traced
    run): one with a 128-token prefill wave, three plain decode ticks."""
    assert sample.n_devices == 1
    assert sample.window_s == pytest.approx(27.771518e-3)
    assert sample.busy_s == pytest.approx(14.895953e-3)
    assert sum(s for s, _ in sample.gaps) == pytest.approx(
        sample.window_s - sample.busy_s)
    # every gap falls inside the host's eng.step(); six are host-scale,
    # the other thousand are the microseconds between ops of one program
    assert {n for _, n in sample.gaps} == {"bench.step"}
    host = [s for s, _ in sample.gaps if s >= trace_reduce.HOST_GAP_S]
    assert len(host) == 6 and sum(host) == pytest.approx(12.87142e-3)


def test_recorded_sample_kernel_time_by_name(sample):
    sec, calls = trace_reduce.name_seconds(sample, "fused_paged_decode_step")
    assert calls == 4 and sec == pytest.approx(12.869788e-3)
    # ops that read the kernel's output name it among their operands;
    # only the kernel's own events carry its label
    assert [k for k in sample.by_name if "decode" in k] == [
        "fused_paged_decode_step"]
    top = trace_reduce.breakdown(sample)["device_ops"]
    assert top[0][0] == "fused_paged_decode_step"
    assert top[1][0] == "fusion.9 bf16[32,50304]"      # the output head


def test_op_label():
    label = trace_reduce.op_label
    assert label('%fused_paged_decode_step.1 = (bf16[32,1024]{1,0:T(8,128)}, '
                 'bf16[24,953,128,2048]{3,2,1,0}) custom-call(s32[32]{0} '
                 '%positions.1), custom_call_target="tpu_custom_call"'
                 ) == "fused_paged_decode_step"
    assert label("%jvp_flash_attention_fwd_.37 = bf16[8,16,1024,64]{3,2,1,0} "
                 "custom-call(bf16[8,16,1024,64]{3,2,1,0} %x)"
                 ) == "jvp_flash_attention_fwd_"
    assert label("%fusion.5 = bf16[16,92544]{1,0:T(8,128)(2,1)S(1)} fusion("
                 "bf16[16,2048]{1,0} %fused_paged_decode_step.1), kind=kOutput"
                 ) == "fusion.5 bf16[16,92544]"
    assert label("bench.step") == "bench.step"
