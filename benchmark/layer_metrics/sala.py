"""Per-layer metrics of arch ``minicpm_sala``: the five Mosaic kernels
against their rooflines, how much of what a sparse layer could read it
does read, and how much of the device's time the new kernels are.

Kernel seconds come from the traced part of the window, operations and
bytes from ``harness/opcount_sala.py``. The decode step's rows and
their positions are the benchmark's own request records over the traced
decode steps; a wave prefill's true length, padded length and kernel
calls are on its ``serving.step.prefill`` span (``true_len``, ``s_pad``,
``rows``, ``lightning_calls``, ``sparse_calls``), and a wave counts
where its span lies whole in ``bench.window`` and the device ran as many
kernel calls under it as the span says. A trace without a kernel (the
parent, a program on the ``jnp`` path), spans without the counters, or
a run without a trace read as ``None``: the metric is left off the line.
"""

import numpy as np

from harness import log, opcount, opcount_sala, serve, trace_reduce

LIGHTNING_PREFILL = "lightning_prefill"
LIGHTNING_DECODE = "lightning_decode"
SPARSE_SELECT = "sparse_select"
SPARSE_DECODE = "sparse_paged_decode"
SPARSE_PREFILL = "sparse_prefill_attn"
KERNELS = (LIGHTNING_PREFILL, LIGHTNING_DECODE, SPARSE_SELECT, SPARSE_DECODE,
           SPARSE_PREFILL)
PREFILL_SPAN = "serving.step.prefill"
CLOCK_SLACK_NS = 1_000_000      # the trace's two clocks can be 0.8 ms apart


def _kernel(obs, name):
    """(seconds, calls) of a kernel in the traced part, or None."""
    red = obs.get("trace")
    if red is None or not obs.get("peaks"):
        return None
    sec, calls = trace_reduce.name_seconds(red, name)
    return (sec, calls) if calls else None


def _traced_rows(obs):
    """The tokens each active row saw (itself included), one entry a row
    a traced decode step, or None."""
    lo, hi = obs["trace_steps"]
    if hi <= lo:
        return None
    n = [p + np.arange(j_lo, j_hi + 1)
         for p, j_lo, j_hi in serve.decode_spans(obs["all_requests"], lo, hi)]
    return np.concatenate(n) if n else None


def _share(need, sec, obs, **beside):
    r = opcount.roofline(need["flops"], need["bytes"], sec, obs["peaks"])
    return dict(value=100.0 * r["share"], bound=r["bound"], **beside)


def lightning_decode_roofline(obs):
    """``lightning_decode``: the active rows' states read and written, a
    lightning layer a step, over the kernel's device time."""
    got, n = _kernel(obs, LIGHTNING_DECODE), _traced_rows(obs)
    if got is None or n is None:
        return None
    sec, calls = got
    d = opcount.dims(obs["config"])
    steps = obs["trace_steps"][1] - obs["trace_steps"][0]
    per = opcount_sala.lightning_decode_call(d, len(n) / steps)
    return _share({k: v * calls for k, v in per.items()}, sec, obs,
                  calls=calls, mean_call_ms=1e3 * sec / calls,
                  mean_rows=len(n) / steps)


def sparse_decode_roofline(obs):
    """``sparse_select`` and ``sparse_paged_decode`` together: the
    compressed keys scored and the keys and values of the blocks read,
    a sparse layer a step, over the two kernels' device time."""
    sel, walk, n = (_kernel(obs, SPARSE_SELECT), _kernel(obs, SPARSE_DECODE),
                    _traced_rows(obs))
    if sel is None or walk is None or n is None:
        return None
    d = opcount.dims(obs["config"])
    steps = obs["trace_steps"][1] - obs["trace_steps"][0]
    # a call is one layer of one step; every sparse layer reads the same
    # rows, and the traced steps' rows are spread evenly over the calls
    per = opcount_sala.sparse_decode_call(d, n)
    calls = walk[1]
    scale = calls / steps
    sec = sel[0] + walk[0]
    return _share({k: v * scale for k, v in per.items()}, sec, obs,
                  calls=calls, select_ms=1e3 * sel[0] / sel[1],
                  walk_ms=1e3 * walk[0] / walk[1], mean_rows=len(n) / steps,
                  mean_blocks_read=float(
                      opcount_sala.blocks_read(d, n).mean()))


def _traced_waves(trace_path: str, kernel: str, counter: str):
    """([(calls, rows, s_pad, true_len, kernel seconds)] of the waves
    that lie whole in the traced window, how many were cut)."""
    profile = trace_reduce.load(trace_path)
    spans, window = [], None
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_reduce.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name != PREFILL_SPAN:
                    continue
                st = dict(e.stats)
                if counter in st and "true_len" in st:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  int(st[counter]), int(st["rows"]),
                                  int(st["s_pad"]), int(st["true_len"])))
    ops = trace_reduce.device_ops(profile)
    first = ops[sorted(ops)[0]] if ops else []
    ran = [(s, e) for s, e, name in first
           if kernel in trace_reduce.op_label(name)]
    waves, cut = [], 0
    for lo, hi, calls, rows, s_pad, true_len in sorted(spans):
        under = [e - s for s, e in ran
                 if lo - CLOCK_SLACK_NS <= s and e <= hi + CLOCK_SLACK_NS]
        whole = (window is None or window[0] <= lo and hi <= window[1])
        if whole and calls and len(under) == calls:
            waves.append((calls, rows, s_pad, true_len, sum(under) * 1e-9))
        elif calls:
            cut += 1
    return waves, cut


def _prefill_roofline(obs, kernel: str, counter: str, need_of_wave,
                      one_row: bool = False):
    """``one_row``: the count cannot split a span's ``true_len`` (the sum
    over the wave's rows), so waves of several rows are left out on both
    sides, as the cut ones are."""
    path = obs.get("trace_path")
    if not path or _kernel(obs, kernel) is None:
        return None
    waves, cut = _traced_waves(path, kernel, counter)
    if one_row:
        cut += sum(1 for w in waves if w[1] != 1)
        waves = [w for w in waves if w[1] == 1]
    if not waves:
        return None
    d = opcount.dims(obs["config"])
    need = {"flops": 0.0, "bytes": 0.0}
    for calls, rows, s_pad, true_len, _ in waves:
        for k, v in need_of_wave(d, calls, rows, s_pad, true_len).items():
            need[k] += v
    sec = sum(w[4] for w in waves)
    calls = sum(w[0] for w in waves)
    out = _share(need, sec, obs, calls=calls, waves_traced=len(waves),
                 waves_cut=cut, ms_a_wave=1e3 * sec / len(waves),
                 true_tokens_a_wave=sum(w[3] for w in waves) / len(waves),
                 mean_call_ms=1e3 * sec / calls)
    log(phase=kernel, **out)
    return out


def lightning_prefill_roofline(obs):
    """``lightning_prefill`` over the wave prefills of the traced part:
    the recurrence of the TRUE tokens, a lightning layer a wave."""
    def need(d, calls, rows, s_pad, true_len):
        per = opcount_sala.lightning_prefill_call(d, true_len, rows)
        return {k: v * d["lightning_layers"] for k, v in per.items()}

    return _prefill_roofline(obs, LIGHTNING_PREFILL, "lightning_calls", need)


def sparse_prefill_roofline(obs):
    """``sparse_prefill_attn`` over the wave prefills of the traced
    part: every true query's attention over its selected set exactly, a
    sparse layer a wave. The harness sends one request a wave, so a
    wave's ``true_len`` is one request's (a wave of several rows is left
    out)."""

    def need(d, calls, rows, s_pad, true_len):
        per = opcount_sala.sparse_prefill_call(d, true_len)
        return {k: v * d["sparse_layers"] for k, v in per.items()}

    return _prefill_roofline(obs, SPARSE_PREFILL, "sparse_calls", need,
                             one_row=True)


def sparse_read_share(obs):
    """Blocks the sparse layers' decode steps read over the blocks they
    could have read (``sparse_blocks_read / sparse_blocks_visible`` from
    ``engine.stats`` over the window), in per cent."""
    s = obs["stats"]
    if not s.get("sparse_blocks_visible"):
        return None
    return dict(value=100.0 * s["sparse_blocks_read"]
                / s["sparse_blocks_visible"],
                blocks_read_a_step=s["sparse_blocks_read"] / s["steps"],
                dense_rows=s["sparse_dense_rows"],
                lightning_rows_a_step=s["lightning_rows"] / s["steps"])


def mixer_share(obs):
    """The five kernels' device seconds over the device's busy seconds
    in the traced part, in per cent; the prefill's two and the step's
    three apart beside it."""
    red = obs.get("trace")
    if red is None:
        return None
    sec = {k: trace_reduce.name_seconds(red, k) for k in KERNELS}
    if not any(calls for _, calls in sec.values()):
        return None
    share = lambda names: 100.0 * sum(sec[k][0] for k in names) / red.busy_s
    return dict(value=share(KERNELS),
                prefill_share=share((LIGHTNING_PREFILL, SPARSE_PREFILL)),
                step_share=share((LIGHTNING_DECODE, SPARSE_SELECT,
                                  SPARSE_DECODE)),
                **{k + "_s": v[0] for k, v in sec.items()})
