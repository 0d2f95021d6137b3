"""The readers of ``layer_metrics/engine_phases.py`` on a trace written by
hand: one device line and two ticks of nested ``serving.step.*`` host
spans whose overlaps with the device's idle gaps are known to the
nanosecond, on ``sample.xplane.pb`` (a trace from before the program
had such spans) and on observations without a trace."""

import importlib.util
import os

import pytest

from harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _load():
    spec = importlib.util.spec_from_file_location(
        "lm_engine_phases", os.path.join(
            os.path.dirname(HERE), "layer_metrics", "engine_phases.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


phases = _load()

# Times in the comments are us (the text holds ps). The window is 0-2000.
# Device busy 0-300, 700-1000, 1050-1400, 1900-2000, so it idles
#   300-700   (400 us: host-scale; straddles six phases of tick 1)
#   1000-1050 (50 us: under HOST_GAP_S, ignored)
#   1400-1900 (500 us: host-scale; between the ticks and into tick 2)
NAMES = ["bench.window", "bench.step", "bench.submit", "serving.submit",
         "serving.step", "serving.step.admit", "serving.step.prefill",
         "serving.step.upload", "serving.step.dispatch",
         "serving.step.sync", "serving.step.commit", "serving.step.tail"]
HOST = [
    ("bench.window", 0, 2000),
    # tick 1
    ("bench.step", 100, 900), ("serving.step", 110, 890),
    ("serving.step.admit", 120, 400),
    ("serving.step.prefill", 150, 250),     # the device is busy: no idle
    ("serving.step.upload", 320, 380),
    ("serving.step.dispatch", 400, 450),    # 450-460: in the step, no phase
    ("serving.step.sync", 460, 600),
    ("serving.step.commit", 600, 680),
    ("serving.step.tail", 680, 880),
    # between the ticks
    ("bench.submit", 1410, 1460), ("serving.submit", 1420, 1450),
    # tick 2; 1500-1520 is inside bench.step and before serving.step
    ("bench.step", 1500, 1950), ("serving.step", 1520, 1940),
    ("serving.step.admit", 1520, 1600),
    ("serving.step.prefill", 1530, 1590),
    ("serving.step.dispatch", 1600, 1650),
    ("serving.step.sync", 1650, 1930),
]
DEVICE = [(0, 300), (700, 1000), (1050, 1400), (1900, 2000)]


def text_proto(host, device):
    ev = "    events {{ metadata_id: {} offset_ps: {} duration_ps: {} }}\n"
    dev = "".join(ev.format(1, s * 10**6, (e - s) * 10**6)
                  for s, e in device)
    hst = "".join(ev.format(NAMES.index(n) + 1, s * 10**6, (e - s) * 10**6)
                  for n, s, e in host)
    meta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(NAMES))
    return (f'planes {{ name: "/device:TPU:0"\n'
            f'  lines {{ name: "XLA Ops" timestamp_ns: 0\n{dev}  }}\n'
            f'  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}'
            f' }}\nplanes {{ name: "/host:CPU"\n'
            f'  lines {{ name: "main" timestamp_ns: 0\n{hst}  }}\n{meta}}}\n')


def write_trace(path, host=HOST, device=DEVICE):
    from jax.profiler import ProfileData
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            text_proto(host, device)))
    return str(path)


def observations(path, steps=2):
    return dict(trace_path=path, trace=object(), trace_steps=(10, 10 + steps),
                stats=dict(steps=400, upload_ticks=100))


@pytest.fixture(scope="module")
def obs(tmp_path_factory):
    return observations(write_trace(
        tmp_path_factory.mktemp("trace") / "hand.xplane.pb"))


def test_innermost_flattens_nested_spans():
    spans = [(0, 100, "a"), (10, 40, "b"), (20, 30, "c"), (40, 60, "d"),
             (200, 300, "e")]
    assert phases.innermost(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 60, "d"), (60, 100, "a"), (200, 300, "e")]


def test_each_idle_ms_exactly(obs):
    # us of the two host-scale gaps under each phase, over 2 decode ticks
    want = dict(admit=20 + 60 + 20 + 10 + 10, prefill=60,
                dispatch=50 + 50, sync=140 + 250, commit=80, tail=20)
    for phase, us in want.items():
        got = getattr(phases, f"idle_ms_{phase}")(obs)
        got = got["value"] if isinstance(got, dict) else got
        assert got == pytest.approx(us * 1e-3 / 2), phase
    admit = phases.idle_ms_admit(obs)
    assert admit["upload_ms"] == pytest.approx(0.060 / 2)
    assert admit["upload_tick_share"] == 0.25


def test_a_gap_straddling_phases_is_split_and_the_seven_add_up(obs):
    sp = phases.split(obs["trace_path"])
    assert sp["gaps"] == 2          # the 50 us gap is under HOST_GAP_S
    out = phases.idle_ms_outside(obs)
    # between the ticks: 1400-1500, serving.submit 30 us of it
    assert out["value"] == pytest.approx(0.100 / 2)
    assert out["submit_ms"] == pytest.approx(0.030 / 2)
    # 450-460 inside serving.step, 1500-1520 inside bench.step only
    assert out["unattributed_ms"] == pytest.approx(0.030 / 2)
    assert out["longest_gap_ms"] == pytest.approx(0.500)
    assert out["longest_gap_phase"] == "sync"       # 250 of its 500 us
    assert out["host_gaps"] == 2
    seven = sum(
        (v["value"] if isinstance(v, dict) else v) for v in (
            getattr(phases, f"idle_ms_{p}")(obs)
            for p in phases.PHASES + ("outside",)))
    # the host-scale idle time a tick, as trace_reduce sees it
    red = trace_reduce.reduce(trace_reduce.load(obs["trace_path"]))
    host_scale = sum(s for s, _ in red.gaps if s >= trace_reduce.HOST_GAP_S)
    assert host_scale == pytest.approx(900e-6)
    assert seven + out["unattributed_ms"] == pytest.approx(
        host_scale * 1e3 / 2)


def test_launch_and_return_say_how_far_the_two_clocks_agree(tmp_path):
    host = [("bench.window", 0, 2000),
            ("serving.step", 0, 900), ("serving.step.sync", 100, 800),
            ("serving.step.commit", 800, 850), ("serving.step.tail", 850, 900),
            ("serving.step", 1000, 1900), ("serving.step.admit", 1000, 1050),
            ("serving.step.dispatch", 1050, 1250),
            ("serving.step.sync", 1250, 1900)]

    def read(name, device):
        o = observations(write_trace(tmp_path / name, host, device), steps=1)
        return {p: getattr(phases, f"idle_ms_{p}")(o)
                for p in ("dispatch", "sync", "commit", "tail")}

    # the device stops at 500 and resumes at 1200, inside the dispatch
    good = read("good.xplane.pb", [(0, 500), (1200, 1800)])
    assert good["dispatch"] == dict(value=pytest.approx(0.150),
                                    launch_ms=pytest.approx(0.150))
    assert good["sync"]["return_ms"] == pytest.approx(0.300)
    # the same with the device's clock 250 us ahead of the host's: the
    # launch now comes BEFORE its dispatch opens, which no program can
    # do; dispatch's idle time has moved into sync, and the phases
    # between the two read as they did
    early = read("early.xplane.pb", [(0, 250), (950, 1550)])
    assert early["dispatch"] == dict(value=0.0,
                                     launch_ms=pytest.approx(-0.100))
    assert early["sync"]["return_ms"] == pytest.approx(0.550)
    assert early["sync"]["value"] > good["sync"]["value"]
    assert (early["commit"], early["tail"]) == (good["commit"], good["tail"])
    assert good["commit"] == pytest.approx(0.050)


def test_a_gap_between_two_steps_is_outside(tmp_path):
    host = [("bench.window", 0, 1000), ("serving.step", 0, 200),
            ("serving.step.sync", 0, 200), ("serving.step", 800, 1000),
            ("serving.step.admit", 800, 1000)]
    o = observations(write_trace(tmp_path / "t.xplane.pb", host,
                                 [(0, 300), (700, 1000)]), steps=1)
    assert phases.idle_ms_outside(o)["value"] == pytest.approx(0.4)
    assert phases.idle_ms_outside(o)["longest_gap_phase"] == "outside"
    assert phases.idle_ms_sync(o)["value"] == 0.0
    assert phases.idle_ms_admit(o)["value"] == 0.0


def test_prefill_idle_share(obs):
    got = phases.prefill_idle_share(obs)
    # two waves, 100 and 60 us; the device idles through the second
    assert got["waves"] == 2
    assert got["value"] == pytest.approx(100.0 * 60 / 160)
    assert got["idle_s"] == pytest.approx(60e-6)


def test_no_trace_and_a_trace_without_the_spans_read_none(obs):
    readers = [getattr(phases, f"idle_ms_{p}")
               for p in phases.PHASES + ("outside",)]
    readers.append(phases.prefill_idle_share)
    no_trace = dict(obs, trace_path=None, trace=None)
    # what the parent of the PR that added the spans records: device ops
    # and bench.* spans only
    old = dict(obs, trace_path=os.path.join(HERE, "sample.xplane.pb"))
    for o in (no_trace, old):
        assert [r(o) for r in readers] == [None] * len(readers)
    # and its engine.stats have no step_commit_s
    assert phases.tick_host_ms(dict(stats=dict(
        steps=3, step_admit_s=1.0, step_prefill_s=0.0, step_dispatch_s=1.0,
        step_sync_s=1.0))) is None


def test_tick_host_ms_from_the_counters():
    stats = dict(steps=100, step_admit_s=0.10, step_prefill_s=0.30,
                 step_dispatch_s=0.05, step_sync_s=0.40, step_commit_s=0.03,
                 step_tail_s=0.02, step_upload_s=0.04, upload_ticks=25)
    ticks = [(t * 0.01, 0.0100, True) for t in range(100)]
    ticks += [(-0.5, 9.0, True), (1.0, 9.0, True)]      # not in the span
    got = phases.tick_host_ms(dict(stats=stats, ticks=ticks,
                                   host_span=(0.0, 1.0)))
    assert got["value"] == pytest.approx(2.0)       # 0.20 s over 100 ticks
    assert got["sync_ms"] == pytest.approx(4.0)
    assert got["upload_ms"] == pytest.approx(0.4)
    assert got["covered"] == pytest.approx(0.90)
    assert set(got) == {"value", "covered", "upload_ms"} | {
        f"{p}_ms" for p in phases.PHASES}
