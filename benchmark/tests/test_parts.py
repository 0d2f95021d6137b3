"""``layer_metrics/parts.py`` on ``parts_fixture``'s small trace, against
values computed by hand from the times in its docstring."""

import importlib.util
import os
import sys

import pytest

from harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLE = os.path.join(HERE, "sample.xplane.pb")
# the reader under test is the program's own (as under run.py)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)
import parts_fixture  # noqa: E402


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from paddle_tpu.profiler import xplane
    if not hasattr(xplane, "parts_report"):
        pytest.skip("the program under test has no reader of parts")
    return parts_fixture.write(
        str(tmp_path_factory.mktemp("parts") / "parts.xplane.pb"))

def readers():
    spec = importlib.util.spec_from_file_location(
        "lm_parts", os.path.join(os.path.dirname(HERE), "layer_metrics",
                                 "parts.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def obs(path):
    return dict(trace=trace_reduce.reduce(trace_reduce.load(path)),
                trace_path=path)


def approx(x):
    return pytest.approx(x, rel=1e-9)


def sums_to_the_program(got, whole):
    parts = sum(v for k, v in got.items() if k.startswith("ms."))
    assert parts + got["unscoped_ms"] == approx(got[whole])


def test_a_wave_by_part(trace):
    got = readers().prefill_outside_kernels_share(obs(trace))
    # while.5 keeps 7000 - (2000 + 3000) of its own: a loop is not
    # counted twice
    assert got["ms_a_wave"] == approx(8.5e-3) and got["waves_traced"] == 1
    assert got["value"] == approx(100 * (1 - 3000 / 8500))
    assert (got["ms.attn_in"], got["ms.attn"], got["ms.head"]) == (
        approx(2e-3), approx(3e-3), approx(1e-3))
    assert got["unscoped_ms"] == approx(2.5e-3)
    assert got["unscoped_top"].startswith("while.5 (s32[], bf16[4,8]): 0.0020")
    assert "copy.3 bf16[4,8]: 0.0005" in got["unscoped_top"]
    assert got["runs_cut"] == 0 and got["reader_s"] > 0
    sums_to_the_program(got, "ms_a_wave")


def test_a_step_outside_its_kernels(trace):
    got = readers().step_xla_ms(obs(trace))
    # run 0 starts before the window and is cut, with its op
    assert got["runs"] == 2 and got["runs_cut"] == 1
    assert got["step_ms"] == approx(10500e-6 / 2)
    assert got["value"] == approx(4500e-6 / 2)
    assert got["ms.ffn"] == approx(4500e-6 / 2)
    assert got["ms.layers"] == approx(3e-3) and got["unscoped_ms"] == 0
    sums_to_the_program(got, "step_ms")


def test_a_train_step_outside_flash_forward_and_backward(trace):
    got = readers().outside_flash_ms(obs(trace))
    assert got["step_ms"] == approx(8e-3) and got["flash_ms"] == approx(2e-3)
    assert got["value"] == approx(6e-3)
    assert (got["ms.ffn.fwd"], got["ms.ffn.bwd"]) == (approx(2e-3),
                                                      approx(3e-3))
    assert (got["ms.attn.fwd"], got["ms.attn.bwd"]) == (approx(1e-3),
                                                        approx(1e-3))
    assert got["ms.optimizer.fwd"] == approx(5e-4)
    assert got["ms.loss.fwd"] == approx(5e-4) and got["ms.loss.bwd"] == 0
    sums_to_the_program(got, "step_ms")


@pytest.mark.parametrize("reader", ["prefill_outside_kernels_share",
                                    "step_xla_ms", "outside_flash_ms"])
def test_nothing_to_read_is_none(reader):
    fn = getattr(readers(), reader)
    # a trace without program names or parts (the parent's), and no trace
    assert fn(obs(SAMPLE)) is None
    assert fn(dict(trace=None, trace_path=None)) is None
    assert fn({}) is None
