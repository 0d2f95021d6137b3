"""Per-layer metrics that split a program's device time by MODEL PART:
what XLA compiles around the Mosaic kernels, by name.

The program names its parts (``paddle_tpu/profiler/parts.py``: one
``jax.named_scope`` vocabulary, ``part.embed`` ... ``part.optimizer``,
the same for every architecture) and its programs (a run on the trace's
``XLA Modules`` line reads ``jit_serving_prefill``, ``jit_serving_step``,
``jit_train_step``). An op's part is in the trace: the ``tf_op`` stat of
the op's METADATA entry holds its framework name, which
``jax.profiler.ProfileData`` does not show, so the package's own reader
(``paddle_tpu.profiler.xplane.parts_report``) decodes the device
plane's bytes and gives SELF seconds by program and part: a ``while``
event holds its body's events, so a loop is split among its children
and nothing counts twice. The runs counted lie whole in ``bench.window``.

Beside each value, flat: ``ms.<part>`` (device ms a run of the program,
kernels included; the train step's split ``.fwd`` / ``.bwd`` by
``transpose(`` in the op's name), ``unscoped_ms`` (ops that carry no
part: XLA's own copies, loop conditions) with the largest of them in
``unscoped_top``, ``runs``, ``runs_cut``, ``reader_s`` (what the reader
cost). The ``ms.*`` and ``unscoped_ms`` sum to the program's device ms.
A run without a trace, a program that lacks the reader, the names or
the parts (the parent of PR 36) reads ``None``: the metric is left off
the line.
"""

import functools
import time

from harness import trace_reduce

PREFILL = "jit_serving_prefill"
STEP = "jit_serving_step"
TRAIN = "jit_train_step"


@functools.lru_cache(maxsize=2)
def _table(trace_path: str):
    """(``parts_report`` of the trace's window, the seconds it took), or
    None where the program under test has no such reader."""
    try:
        from paddle_tpu.profiler import xplane
        report = xplane.parts_report
    except (ImportError, AttributeError):
        return None
    t0 = time.perf_counter()
    window = [s for s in trace_reduce.host_spans(trace_reduce.load(trace_path))
              if s[2] == trace_reduce.WINDOW_SPAN]
    lo, hi = (window[0][0], window[-1][1]) if window else (None, None)
    return report(trace_path, lo, hi), time.perf_counter() - t0


def _program(obs, kind):
    """(the program's row, reader seconds), or None."""
    if obs.get("trace") is None or not obs.get("trace_path"):
        return None
    got = _table(obs["trace_path"])
    if got is None:
        return None
    row = got[0].get(kind)
    if not row or not row["runs"] or not row["parts"]:
        return None
    return row, got[1]


def _beside(row, reader_s, split=False):
    n = row["runs"]
    out = {}
    for part, (fwd, bwd) in sorted(row["parts"].items()):
        if split:
            out[f"ms.{part}.fwd"] = 1e3 * fwd / n
            out[f"ms.{part}.bwd"] = 1e3 * bwd / n
        else:
            out[f"ms.{part}"] = 1e3 * (fwd + bwd) / n
    top = sorted(row["unscoped"].items(), key=lambda kv: -kv[1])[:4]
    out.update(unscoped_ms=1e3 * sum(row["unscoped"].values()) / n,
               unscoped_top="; ".join(f"{label}: {1e3 * sec / n:.4f} ms"
                                      for label, sec in top),
               runs=n, runs_cut=row["cut"], reader_s=reader_s)
    return out


def prefill_outside_kernels_share(obs):
    """What XLA's part of a WAVE PREFILL costs: over the runs of
    ``jit_serving_prefill`` that lie whole in the window, 1 - the
    Mosaic kernels' self seconds over the runs' device seconds, in per
    cent; ``ms_a_wave`` and ``waves_traced`` beside it."""
    got = _program(obs, PREFILL)
    if got is None:
        return None
    row, reader_s = got
    return dict(value=100.0 * (1.0 - row["kernel_s"] / row["device_s"]),
                ms_a_wave=1e3 * row["device_s"] / row["runs"],
                kernels_ms=1e3 * row["kernel_s"] / row["runs"],
                waves_traced=row["runs"], **_beside(row, reader_s))


def step_xla_ms(obs):
    """Device ms a run of ``jit_serving_step`` OUTSIDE the Mosaic
    kernels (self time): XLA's part of a decode step; ``step_ms`` (the
    whole run's device ms) beside it."""
    got = _program(obs, STEP)
    if got is None:
        return None
    row, reader_s = got
    n = row["runs"]
    return dict(value=1e3 * (row["device_s"] - row["kernel_s"]) / n,
                step_ms=1e3 * row["device_s"] / n,
                kernels_ms=1e3 * row["kernel_s"] / n,
                **_beside(row, reader_s))


def outside_flash_ms(obs):
    """Device ms a run of ``jit_train_step`` outside its Mosaic kernels
    (the three flash kernels: the step has no other); ``step_ms`` beside
    it, the parts split forward / backward."""
    got = _program(obs, TRAIN)
    if got is None:
        return None
    row, reader_s = got
    n = row["runs"]
    return dict(value=1e3 * (row["device_s"] - row["kernel_s"]) / n,
                step_ms=1e3 * row["device_s"] / n,
                flash_ms=1e3 * row["kernel_s"] / n,
                **_beside(row, reader_s, split=True))
