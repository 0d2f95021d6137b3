"""Profiler veneer (≈ paddle.profiler) + training observability.

Reference (SURVEY.md §5): Profiler with scheduler windows, RecordEvent ranges,
chrome-trace export (python/paddle/profiler/, CUPTI CudaTracer). TPU-native:
jax.profiler emits XPlane traces viewable in TensorBoard/Perfetto;
RecordEvent maps to jax.profiler ranges. MFU/tokens-per-sec metrics are
first-class (BASELINE.md north star) via `StepTimer`/`MetricsLogger`.
"""

import atexit
import contextlib
import json
import os
import time
from typing import Optional

import jax


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, log_dir="./profiler_log"):
        self.log_dir = log_dir
        self.timer_only = timer_only
        self._active = False
        self._step = 0
        self._atexit_registered = False
        self._window_started = False
        self._window_active = False   # the WINDOW opened the live trace
        self.scheduler = scheduler  # (start_batch, end_batch) window
        self.on_trace_ready = on_trace_ready

    def start(self):
        if not self.timer_only:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            if not self._atexit_registered:
                # a trace left open at process exit is never flushed —
                # guard against callers that exit inside the scheduler
                # window (or never call stop())
                self._atexit_registered = True
                atexit.register(self._atexit_stop)

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._window_active = False
            if self._atexit_registered:
                # atexit holds a strong ref to self (and anything the
                # on_trace_ready closure captured) — release it, or every
                # Profiler ever started leaks until process exit
                self._atexit_registered = False
                atexit.unregister(self._atexit_stop)
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)

    def _atexit_stop(self):
        try:
            self.stop()
        except Exception:   # interpreter teardown: never raise from atexit
            pass

    def step(self):
        self._step += 1
        if self.scheduler and not self.timer_only:
            start, end = self.scheduler
            # range (not ==) checks so a counter that jumps PAST a window
            # boundary can't leave the trace open forever; the
            # started-this-window flag keeps the window one-shot — a
            # manual stop() mid-window must not re-arm on the next step
            if start <= self._step < end and not self._active \
                    and not self._window_started:
                self._window_started = True
                self.start()
                self._window_active = True
            elif self._step >= end and self._active \
                    and self._window_active:
                # only close the trace the WINDOW opened — a manual
                # post-window start() stays under the caller's control
                self.stop()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", device_only=True, limit=30):
        """Per-op time table parsed from the captured xplane trace
        (reference: paddle.profiler summary tables), then, where the
        trace names them (a TPU's does), each device's time by program
        and model part (``xplane.part_seconds``: self time, a loop split
        among its body's parts)."""
        from paddle_tpu.profiler import xplane

        planes = xplane.load_latest(self.log_dir)
        if not planes:
            return f"no traces captured in {self.log_dir}"
        rows = xplane.op_summary(planes, device_only=device_only)
        if not rows:  # e.g. CPU-only run: fall back to host planes
            rows = xplane.op_summary(planes, device_only=False)
        text = xplane.format_summary(rows, time_unit=time_unit, limit=limit)
        for plane in planes:
            table = (xplane.part_seconds(plane)
                     if xplane.is_device_plane(plane.name) else None)
            if table:
                text += (f"\n\n{plane.name}: device time by program and "
                         f"part\n{xplane.format_parts(table)}")
        return text

    def export_chrome_trace(self, out_path=None):
        from paddle_tpu.profiler import xplane

        return xplane.export_chrome_trace(self.log_dir, out_path)


@contextlib.contextmanager
def RecordEvent(name: str, event_type=None):
    """User range (reference RecordEvent) → jax named trace annotation."""
    with jax.profiler.TraceAnnotation(name):
        yield


def export_chrome_tracing(dir_name: str):
    """on_trace_ready handler: write catapult trace.json next to the xplane
    dump (reference: paddle.profiler.export_chrome_tracing)."""
    def handler(prof):
        from paddle_tpu.profiler import xplane

        os.makedirs(dir_name, exist_ok=True)
        return xplane.export_chrome_trace(
            prof.log_dir, os.path.join(dir_name, "trace.json"))
    return handler


# ---- MFU / throughput metrics ---------------------------------------------

# bf16 peak FLOPs/chip for known TPU generations (approx, dense)
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


def detect_peak_flops() -> Optional[float]:
    """bf16 peak FLOP/s of device 0, or None for a device kind that is
    not in the table (a CPU included): no peak, no MFU."""
    kind = jax.devices()[0].device_kind.lower()
    for k, v in TPU_PEAK_FLOPS.items():
        if k in kind:
            return v
    return None


class StepTimer:
    """Per-step wall timing with warmup discard; reports tokens/s/chip + MFU.

    Each completed step's duration is also observed into the process-wide
    metrics registry (histogram ``train.step_seconds``) so exporters see
    training cadence without a second timer."""

    def __init__(self, model_flops_per_token: Optional[float] = None,
                 warmup: int = 2):
        self.times = []
        self.warmup = warmup
        self.flops_per_token = model_flops_per_token
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self.times.append(dt)
        # get-or-create each time (one dict lookup): caching the
        # Histogram object would orphan it across registry().reset()
        from paddle_tpu.observability.registry import registry
        registry().histogram("train.step_seconds").observe(dt)

    def mean_step_time(self):
        """Mean post-warmup step seconds; None before any step completes
        (a 0.0 here used to propagate into ZeroDivisionError in
        tokens_per_sec/mfu)."""
        xs = self.times[self.warmup:] or self.times
        if not xs:
            return None
        return sum(xs) / len(xs)

    def tokens_per_sec(self, tokens_per_step, n_chips=1):
        mst = self.mean_step_time()
        if not mst:
            return None     # no completed step yet (or 0-duration steps)
        return tokens_per_step / mst / n_chips

    def mfu(self, tokens_per_step, n_chips=1, peak=None):
        if self.flops_per_token is None:
            return None
        mst = self.mean_step_time()
        if not mst:
            return None     # no completed step yet
        peak = peak or detect_peak_flops()
        if peak is None:
            return None     # unknown device kind: no guessed peak
        achieved = self.flops_per_token * tokens_per_step / mst
        return achieved / (peak * n_chips)


class MetricsLogger:
    """Structured JSONL metrics (SURVEY.md §5-metrics: step time, tokens/s/chip,
    MFU as first-class outputs).

    Each line is written with ONE ``os.write`` on an ``O_APPEND`` fd —
    POSIX appends are atomic per write, so per-rank writers under
    ``parallel/launch.py`` sharing a path can't interleave partial JSON
    (the old buffered ``open(..., "a").write`` could split a line across
    stdio flushes). Numeric fields are mirrored into the process-wide
    metrics registry as ``metrics.<key>`` gauges."""

    def __init__(self, path="metrics.jsonl", mirror_to_registry=True):
        self.path = path
        self.mirror_to_registry = mirror_to_registry

    def log(self, **metrics):
        from paddle_tpu.observability.registry import append_jsonl_lines
        metrics.setdefault("ts", time.time())
        append_jsonl_lines(self.path, [json.dumps(metrics)])
        if self.mirror_to_registry:
            from paddle_tpu.observability.registry import registry
            reg = registry()
            for k, v in metrics.items():
                if k != "ts" and isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    reg.gauge(f"metrics.{k}").set(v)
            reg.counter("metrics.lines").inc()


def model_flops_per_token(n_params: int) -> float:
    """Transformer ≈ 6 * N flops/token for fwd+bwd (standard estimate)."""
    return 6.0 * n_params


def roofline_report(log_dir: str, plan):
    """Join the latest xplane capture in `log_dir` against an analytic
    roofline plan → per-phase "% of roofline, named residual" table (the
    artifact the SCALE.md re-measure items ask for). See
    `profiler.xplane.roofline_report` for the plan shape; benches embed
    one as `roofline_plan` in their BENCH json, and
    `examples/scale_report.py --report <log_dir> --plan <json>` prints
    the table from the command line."""
    from paddle_tpu.profiler import xplane

    return xplane.roofline_report(log_dir, plan)
