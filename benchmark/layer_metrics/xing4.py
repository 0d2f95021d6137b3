"""Per-layer metrics of arch ``xing4``'s decode step: the two Mosaic
kernels against their rooflines, how many experts a step touches, and
what the rest of the step program (XLA's part: projections, norms, the
hyper-connection mixers, router, shared expert, head) costs.

Kernel seconds come from the traced part of the window, operations and
bytes from ``harness/opcount_xing4.py``; rows and cached tokens of the
traced decode steps from the benchmark's request records. The experts
the traced steps touched are read from the trace itself: the engine's
``serving.step.commit`` span of each landed step program carries that
program's counters (``moe_layer_steps``, ``moe_experts_touched``,
``moe_rows_max``, ``moe_rows``) as attributes. ``engine.stats`` holds
the same counters summed over the untraced part of the window, which
``engine.moe_touched_share`` reads. A program without the kernels or
the counters reads as ``None``: the metric is left off the line.
"""

import functools

from harness import opcount, opcount_xing4, serve, trace_reduce

MLA_KERNEL = "mla_paged_decode"
MOE_KERNEL = "moe_grouped_ffn_decode"


def _traced_steps(obs):
    """(steps, mean active rows, mean cached tokens read) of the traced
    decode steps, or None."""
    lo, hi = obs["trace_steps"]
    if hi <= lo:
        return None
    rows, tokens = serve.attended_tokens(obs["all_requests"], lo, hi)
    return hi - lo, rows / (hi - lo), tokens / (hi - lo)


def _kernel(obs, name):
    red = obs.get("trace")
    if red is None:
        return None
    sec, calls = trace_reduce.name_seconds(red, name)
    return (sec, calls) if calls else None


COMMIT_SPAN = "serving.step.commit"


@functools.lru_cache(maxsize=2)
def _commit_counters(trace_path: str) -> dict:
    """The step counters summed over the commit spans of one trace."""
    total = {}
    for plane in trace_reduce.load(trace_path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != COMMIT_SPAN:
                    continue
                for key, v in e.stats:
                    if key.startswith("moe_"):
                        total[key] = total.get(key, 0) + int(v)
    return total


def _moe_means(obs):
    """(experts touched, routed assignments) a layer a step in the
    traced part, or None."""
    path = obs.get("trace_path")
    s = _commit_counters(path) if path and obs.get("trace") else {}
    if not s.get("moe_layer_steps"):
        return None
    return (s["moe_experts_touched"] / s["moe_layer_steps"],
            s["moe_rows"] / s["moe_layer_steps"])


def mla_decode_roofline(obs):
    """``mla_paged_decode``: the cached latent rows of the active slots,
    one new row each, queries in and outputs out, over the kernel's
    device time in the traced part."""
    got, steps = _kernel(obs, MLA_KERNEL), _traced_steps(obs)
    if got is None or steps is None:
        return None
    sec, calls = got
    _, rows, tokens = steps
    d = opcount.dims(obs["config"])
    # a call is one layer of one step: every layer reads the same rows
    per = opcount_xing4.mla_decode_call(d, rows, tokens)
    r = opcount.roofline(per["flops"] * calls, per["bytes"] * calls, sec,
                         obs["peaks"])
    return dict(value=100.0 * r["share"], bound=r["bound"], calls=calls,
                mean_call_ms=1e3 * sec / calls, mean_rows=rows,
                mean_cached_tokens=tokens)


def moe_ffn_roofline(obs):
    """``moe_grouped_ffn_decode``: the touched experts' matrices, once a
    call, and the routed rows' flops, over the kernel's device time."""
    got, steps, moe = (_kernel(obs, MOE_KERNEL), _traced_steps(obs),
                       _moe_means(obs))
    if got is None or steps is None or moe is None:
        return None
    sec, calls = got
    touched, assigned = moe
    d = opcount.dims(obs["config"])
    per = opcount_xing4.moe_ffn_call(d, assigned / d["top_k"], touched)
    r = opcount.roofline(per["flops"] * calls, per["bytes"] * calls, sec,
                         obs["peaks"])
    return dict(value=100.0 * r["share"], bound=r["bound"], calls=calls,
                mean_call_ms=1e3 * sec / calls,
                mean_rows=assigned / d["top_k"], mean_touched=touched)


def moe_touched_share(obs):
    """Experts that got at least one row, over experts x expert layers x
    decode steps, in per cent; beside it the fullest expert's rows over
    the mean expert's."""
    s = obs["stats"]
    if not s.get("moe_layer_steps"):
        return None
    experts = opcount.dims(obs["config"])["experts"]
    return dict(
        value=100.0 * s["moe_experts_touched"]
        / (experts * s["moe_layer_steps"]),
        rows_max_over_mean=(s["moe_rows_max"] * experts / s["moe_rows"]
                            if s["moe_rows"] else None),
        layer_steps=s["moe_layer_steps"])


MODULES_LINE = "XLA Modules"


@functools.lru_cache(maxsize=2)
def _program_seconds(trace_path: str):
    """Device seconds of the first device in the window, split by
    program: a run of a program is one event of the device's ``XLA
    Modules`` line, and a run that holds ``mla_paged_decode`` is a run
    of the STEP program. -> dict(step_s, step_runs, kernels_s, other_s)
    (busy seconds as unions of op intervals), or None where the trace
    has no such line or no such run."""
    profile = trace_reduce.load(trace_path)
    ops = trace_reduce.device_ops(profile)
    if not ops:
        return None
    first = sorted(ops)[0]
    plane = next(p for p in profile.planes if p.name == first)
    runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for line in plane.lines if line.name == MODULES_LINE
                  for e in line.events)
    if not runs:
        return None
    window = [sp for sp in trace_reduce.host_spans(profile)
              if sp[2] == trace_reduce.WINDOW_SPAN]
    lo = window[0][0] if window else ops[first][0][0]
    hi = window[-1][1] if window else max(e for _, e, _ in ops[first])
    inside = [[] for _ in runs]         # ops by the run they start in
    outside, r = [], 0
    for op in ops[first]:               # sorted by start, as the runs are
        while r + 1 < len(runs) and runs[r + 1][0] <= op[0]:
            r += 1
        (inside[r] if runs[r][0] <= op[0] < runs[r][1]
         else outside).append(op)

    def busy(intervals):
        return sum(e - s for s, e in trace_reduce.union(intervals, lo, hi))

    step_s = kernels_s = other_s = 0
    step_runs = 0
    for members in inside:
        labels = [trace_reduce.op_label(name) for _, _, name in members]
        if MLA_KERNEL in labels:
            step_s += busy(members)
            step_runs += 1
            kernels_s += busy([m for m, label in zip(members, labels)
                               if label in (MLA_KERNEL, MOE_KERNEL)])
        else:
            other_s += busy(members)
    if not step_runs or not step_s:
        return None
    return dict(step_s=step_s * 1e-9, step_runs=step_runs,
                kernels_s=kernels_s * 1e-9,
                other_s=(other_s + busy(outside)) * 1e-9)


def outside_kernels_share(obs):
    """What XLA's part of the STEP program costs (projections, norms,
    the 14 mixers, router, shared expert, head, sampling): 1 - (the two
    kernels' device seconds) / (the step program's device seconds), in
    per cent, over the step program's runs in the traced part. Beside
    it: the other programs' device seconds (the wave prefills), a run's
    mean device time, and that time against what a step has to read
    from HBM (``opcount_xing4.step_weight_bytes`` at the experts the
    traced steps touched, plus the cached latent rows): the whole
    step's share of the HBM peak. Where the trace cannot be split by
    program the share is taken over every program's seconds and says so
    (``scope``)."""
    mla, moe = _kernel(obs, MLA_KERNEL), _kernel(obs, MOE_KERNEL)
    if mla is None or moe is None:
        return None
    busy = obs["trace"].busy_s
    whole = 100.0 * (1.0 - (mla[0] + moe[0]) / busy)
    try:
        split = _program_seconds(obs["trace_path"])
    except Exception:   # noqa: BLE001 — a trace of another shape
        split = None
    if split is None:
        return dict(value=whole, scope="every program", mla_s=mla[0],
                    moe_s=moe[0], busy_s=busy)
    out = dict(value=100.0 * (1.0 - split["kernels_s"] / split["step_s"]),
               scope="step program", step_runs=split["step_runs"],
               step_ms=1e3 * split["step_s"] / split["step_runs"],
               other_programs_s=split["other_s"],
               every_program_share=whole, mla_s=mla[0], moe_s=moe[0],
               busy_s=busy)
    steps, experts = _traced_steps(obs), _moe_means(obs)
    if steps is not None and experts is not None and obs.get("peaks"):
        d = opcount.dims(obs["config"])
        _, rows, tokens = steps
        need = (opcount_xing4.step_weight_bytes(d, experts[0])
                + opcount_xing4.latent_bytes_per_token(d) * (tokens + rows))
        out["step_hbm_share"] = 100.0 * need / obs["peaks"][
            "hbm_bytes_per_s"] / (split["step_s"] / split["step_runs"])
    return out
