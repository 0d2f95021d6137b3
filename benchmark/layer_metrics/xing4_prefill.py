"""Layer ``kernels``: the routed experts of arch ``xing4``'s wave
prefills against their roofline.

``moe_grouped_ffn_prefill`` runs once an expert layer in every wave
prefill. The engine writes on each wave's ``serving.step.prefill`` span
what the wave sent through it: ``prefill_moe_calls`` (expert layers) and
``prefill_moe_rows`` (top_k x padded positions x rows x layers). Both
sides of the share come from the TRACED part alone: a wave counts where
its span lies inside ``bench.window`` and the device ran as many kernel
calls under it as the span says; their seconds are those calls' own. A
wave cut by the window's edge is left out on both sides.

What the calls need is ``harness/opcount_xing4_prefill.py``'s: every
expert's weights once a call (every expert is taken as touched, which
holds at 8 picks an expert or more) plus the routed rows in and out,
against the routed rows' flops. A trace without the kernel (the parent,
a program on ``ragged_dot``), spans without the counters, a run without
a trace, or a wave with fewer than 8 picks an expert read as ``None``:
the metric is left off the line.
"""

from harness import log, opcount, opcount_xing4_prefill, trace_reduce

KERNEL = "moe_grouped_ffn_prefill"
PREFILL_SPAN = "serving.step.prefill"
CLOCK_SLACK_NS = 1_000_000      # the trace's two clocks can be 0.8 ms apart


def _traced_waves(trace_path: str):
    """([(calls, routed rows, kernel seconds)] of the waves that lie
    whole in the traced window, how many were cut); no wave where the
    trace holds no span with the counters."""
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
                if "prefill_moe_calls" in st:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  int(st["prefill_moe_calls"]),
                                  int(st["prefill_moe_rows"])))
    ops = trace_reduce.device_ops(profile)
    first = ops[sorted(ops)[0]] if ops else []
    kernel = [(s, e) for s, e, name in first
              if KERNEL in trace_reduce.op_label(name)]
    waves, cut = [], 0
    for lo, hi, calls, rows in sorted(spans):
        under = [e - s for s, e in kernel
                 if lo - CLOCK_SLACK_NS <= s and e <= hi + CLOCK_SLACK_NS]
        whole = (window is None or window[0] <= lo and hi <= window[1])
        if whole and calls and len(under) == calls:
            waves.append((calls, rows, sum(under) * 1e-9))
        elif calls:
            cut += 1
    return waves, cut


def moe_prefill_roofline(obs):
    """``moe_grouped_ffn_prefill`` over the wave prefills of the traced
    part: the least time the chip could take for their calls over the
    calls' device time, in per cent."""
    path = obs.get("trace_path")
    if not path or obs.get("trace") is None or not obs.get("peaks"):
        return None
    if not trace_reduce.name_seconds(obs["trace"], KERNEL)[1]:
        return None
    waves, cut = _traced_waves(path)
    if not waves:
        return None
    d = opcount.dims(obs["config"])
    if not all(opcount_xing4_prefill.every_expert_touched(d, rows / calls)
               for calls, rows, _ in waves):
        return None
    calls = sum(w[0] for w in waves)
    rows = sum(w[1] for w in waves)
    sec = sum(w[2] for w in waves)
    need = opcount_xing4_prefill.moe_prefill_calls(d, calls, rows)
    r = opcount.roofline(need["flops"], need["bytes"], sec, obs["peaks"])
    out = dict(value=100.0 * r["share"], bound=r["bound"], calls=calls,
               waves_traced=len(waves), waves_cut=cut,
               ms_a_wave=1e3 * sec / len(waves),
               routed_rows_a_wave=rows / len(waves),
               mean_call_ms=1e3 * sec / calls)
    log(phase="moe_prefill", **out)
    return out
