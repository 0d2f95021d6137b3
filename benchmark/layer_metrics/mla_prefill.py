"""Layer ``kernels``: the expanded latent attention of the wave prefills
(``mla_flash_prefill``) against its roofline.

The kernel runs once a layer in every wave prefill whose shape it takes.
The engine writes on each wave's ``serving.step.prefill`` span what the
wave is (``rows``, ``s_pad``, ``R``) and ``prefill_attn_calls``: the
layers whose attention took the kernel. Both sides of the share come
from the TRACED part alone: a wave counts where its span lies inside
``bench.window`` and the device ran as many kernel calls under it as the
span says; their seconds are those calls' own. A wave cut by the
window's edge is left out on both sides.

What a call needs is ``harness/opcount_mla_prefill.py``'s: the causal
triangle's flops exactly, against every operand once. A trace without
the kernel (the parent, a program on the ``jnp`` path), spans without
the counter, or a run without a trace read as ``None``: the metric is
left off the line.
"""

from harness import log, opcount, opcount_mla_prefill, trace_reduce

KERNEL = "mla_flash_prefill"
PREFILL_SPAN = "serving.step.prefill"
CLOCK_SLACK_NS = 1_000_000      # the trace's two clocks can be 0.8 ms apart


def _traced_waves(trace_path: str):
    """([(calls, rows, s_pad, R, kernel seconds)] of the waves that lie
    whole in the traced window, how many were cut); no wave where the
    trace holds no span with the counter."""
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
                if "prefill_attn_calls" in st:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  int(st["prefill_attn_calls"]),
                                  int(st["rows"]), int(st["s_pad"]),
                                  int(st["R"])))
    ops = trace_reduce.device_ops(profile)
    first = ops[sorted(ops)[0]] if ops else []
    kernel = [(s, e) for s, e, name in first
              if KERNEL in trace_reduce.op_label(name)]
    waves, cut = [], 0
    for lo, hi, calls, rows, s_pad, R in sorted(spans):
        under = [e - s for s, e in kernel
                 if lo - CLOCK_SLACK_NS <= s and e <= hi + CLOCK_SLACK_NS]
        whole = (window is None or window[0] <= lo and hi <= window[1])
        if whole and calls and len(under) == calls:
            waves.append((calls, rows, s_pad, R, sum(under) * 1e-9))
        elif calls:
            cut += 1
    return waves, cut


def mla_prefill_roofline(obs):
    """``mla_flash_prefill`` over the wave prefills of the traced part:
    the least time the chip could take for their calls over the calls'
    device time, in per cent."""
    path = obs.get("trace_path")
    if not path or obs.get("trace") is None or not obs.get("peaks"):
        return None
    if not trace_reduce.name_seconds(obs["trace"], KERNEL)[1]:
        return None
    waves, cut = _traced_waves(path)
    if not waves:
        return None
    d = opcount.dims(obs["config"])
    flops = nbytes = 0
    for calls, rows, s_pad, R, _ in waves:
        need = opcount_mla_prefill.mla_prefill_call(d, s_pad, R, rows)
        flops += calls * need["flops"]
        nbytes += calls * need["bytes"]
    calls = sum(w[0] for w in waves)
    sec = sum(w[4] for w in waves)
    r = opcount.roofline(flops, nbytes, sec, obs["peaks"])
    out = dict(value=100.0 * r["share"], bound=r["bound"], calls=calls,
               waves_traced=len(waves), waves_cut=cut,
               ms_a_wave=1e3 * sec / len(waves),
               positions_a_wave=sum(w[1] * w[2] for w in waves) / len(waves),
               mean_call_ms=1e3 * sec / calls)
    log(phase="mla_prefill", **out)
    return out
