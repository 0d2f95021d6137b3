"""Serving cells: ``serve_open`` (an open loop at a fixed rate) and
``serve_backlog`` (a closed backlog that is never empty).

The system under test is ``paddle_tpu.serving.ServingEngine`` as a user
builds it; the harness calls ``submit``, ``step``, ``pop_result`` and
``close`` and reads ``stats``, ``queued``, ``idle`` and the pool's size
(``pool.num_blocks``, ``block_bytes``). Everything else
(traffic, clocks, percentiles, the reference) is the benchmark's own.

The generator keeps the waiting requests itself and hands the engine at
most one while the engine's own queue is empty, so every prefill wave
has one row and the set of programs is (prompt buckets) + one step
program: what warm-up runs, once each. Waits are counted from the due
time, so that queue is inside ``ttft``.
"""

import time

import numpy as np

from . import capture, loadgen, log, model

DRAIN_LIMIT_S = 120.0
# the four host segments engine.stats times per tick
SEGMENTS = ("step_admit_s", "step_prefill_s", "step_dispatch_s", "step_sync_s")


def engine_options(traffic: dict, cell: dict) -> dict:
    """Keyword arguments of ``ServingEngine``: the mix's, with the cell's
    laid over them. No cell sets ``pool_bytes`` or ``num_blocks``, so the
    pool is the engine's default: every slot to ``max_seq_len``, which is
    all the live requests can ever hold."""
    opts = dict(traffic["engine"])
    opts.update(cell.get("engine", {}))
    return opts


def warm_programs(eng, serving, buckets, max_new: int, vocab: int, rng):
    """Run every program the window will use, once: one prefill wave of
    one row per prompt bucket (submitted one a tick, as the window does),
    and the step program behind them."""
    pending = [rng.integers(3, vocab, b, dtype=np.int32) for b in buckets]
    rids = []
    while pending or not eng.idle:
        if pending and eng.queued == 0:
            rids.append(eng.submit(serving.Request(
                pending.pop(0), max_new_tokens=max_new)))
        eng.step()
    for rid in rids:
        eng.pop_result(rid)


def drive(eng, serving, feeder, seconds: float, warm_s: float, cap,
          compiles=lambda: 0, clock=time.perf_counter):
    """The one serving loop. Times are seconds from the start of the
    measured window (negative while the batch fills). Returns the
    per-request records, the per-tick records and the marks the layer
    metrics need: engine stats, generator queue and compile count at the
    window's start, middle and end, and where the traced part began."""
    origin = clock() + warm_s
    recs, by_rid, ticks, marks = {}, {}, [], {}

    def mark(now):
        return dict(t=now, stats=dict(eng.stats), steps=eng.stats["steps"],
                    waiting=len(feeder.waiting), compiles=compiles())

    draining, drain_deadline = False, None
    while True:
        now = clock() - origin
        if "open" not in marks and now >= 0:
            marks["open"] = mark(now)
        if "middle" not in marks and now >= seconds / 2:
            marks["middle"] = mark(now)
        if cap.due(now):
            # the host-clock layer metrics of a traced run stop here
            marks["host_end"] = mark(now)
            cap.start(clock)
            continue
        if not draining and now >= seconds:
            draining = True
            marks["close"] = mark(now)
            marks["trace_path"] = cap.stop()
            feeder.close()
            drain_deadline = clock() + DRAIN_LIMIT_S
            continue
        if draining and (feeder.exhausted and eng.idle
                         or clock() > drain_deadline):
            return recs, ticks, marks
        feeder.poll(now)
        if feeder.waiting and eng.queued == 0:
            r = feeder.waiting.popleft()
            with cap.span("bench.submit"):
                t_sub = clock() - origin
                rid = eng.submit(serving.Request(
                    r.prompt, max_new_tokens=r.max_new))
            recs[r.index] = dict(
                index=r.index, due=t_sub if r.due_s is None else r.due_s,
                seen=feeder.seen_s.get(r.index, t_sub), submit=t_sub,
                prompt_len=len(r.prompt), max_new=r.max_new, result=None)
            by_rid[rid] = r.index
        if eng.idle:
            nxt = feeder.next_due()
            if nxt is not None:
                with cap.span("bench.sleep_until_due"):
                    # sleep to just short of the due time, then poll
                    time.sleep(max(0.0, min(nxt - now - 2e-4, 0.05)))
            continue
        steps0, t1 = eng.stats["steps"], clock()
        with cap.span("bench.step"):
            st = eng.step()
        t2 = clock()
        ticks.append((t1 - origin, t2 - t1, eng.stats["steps"] > steps0))
        for rid in st["finished"]:
            rec = recs[by_rid.pop(rid)]
            rec["result"] = eng.pop_result(rid)
            rec["finish_t"] = t2 - origin
            rec["finish_step"] = eng.stats["steps"]


def finished_at_length(rec) -> bool:
    res = rec["result"]
    return (res is not None and res.finish == "length"
            and len(res.tokens) == rec["max_new"])


def decode_spans(recs, step_lo: int, step_hi: int):
    """(P, j_lo, j_hi) for every request that decoded inside the decode
    steps ``step_lo < step <= step_hi``: a request of P prompt tokens
    that finished with n tokens at decode step f took its n - 1 decode
    steps f - n + 2 .. f, and j_lo .. j_hi of them (counted from 1) fall
    inside."""
    for r in recs:
        res = r["result"]
        if res is None or len(res.tokens) < 2:
            continue
        first = r["finish_step"] - (len(res.tokens) - 1) + 1
        lo, hi = max(first, step_lo + 1), min(r["finish_step"], step_hi)
        if lo <= hi:
            yield r["prompt_len"], lo - first + 1, hi - first + 1


def attended_tokens(recs, step_lo: int, step_hi: int):
    """(rows, cached tokens read) summed over those decode steps: a
    request's j-th decode step reads P + j - 1 cached tokens."""
    rows = tokens = 0
    for p, j_lo, j_hi in decode_spans(recs, step_lo, step_hi):
        cnt = j_hi - j_lo + 1
        rows += cnt
        tokens += cnt * (p - 1) + (j_lo + j_hi) * cnt // 2
    return rows, tokens


def live_blocks(recs, step_lo: int, step_hi: int, block_tokens: int):
    """Mean over those decode steps of the pool blocks that hold a
    running request's keys and values: after its j-th decode step a
    request holds P + j tokens in ceil((P + j) / block_tokens) blocks.
    Blocks the prefix cache keeps after a request has gone are not live."""
    if step_hi <= step_lo:
        return None
    total = sum(int((-(-(p + np.arange(j_lo, j_hi + 1)) // block_tokens)).sum())
                for p, j_lo, j_hi in decode_spans(recs, step_lo, step_hi))
    return total / (step_hi - step_lo)


def check_outputs(measured, state, cfg, max_seq_len: int, n_out: int,
                  seed: int):
    """``correct``: every measured request finished at its length, and
    for four of them (the two shortest and two at random) every served
    token's reference logit is within the configuration's
    ``reference_tolerance`` of the reference maximum at its position.
    Runs after the engine is closed."""
    import importlib
    import jax.numpy as jnp
    ref = importlib.import_module(
        f"{__package__}.reference_{cfg['arch']}")
    done = [r for r in measured if r["result"] is not None]
    legal = all(finished_at_length(r) for r in done)
    if not done:
        return False, dict(reason="no finished request")
    order = sorted(done, key=lambda r: r["prompt_len"] + r["max_new"])
    rng = np.random.default_rng([int(seed), 3])
    rest = order[2:]
    picks = order[:2] + [rest[i] for i in
                         rng.choice(len(rest), min(2, len(rest)), False)]
    gaps, exact = [], 0
    for r in picks:
        res = r["result"]
        p, n = r["prompt_len"], len(res.tokens)
        ids = np.zeros((1, max_seq_len), np.int32)
        ids[0, :p + n] = res.ids
        pos = np.zeros(n_out, np.int32)
        pos[:n] = np.arange(p - 1, p + n - 1)   # logits at t predict t + 1
        lg = np.asarray(ref.logits_at(state, jnp.asarray(ids),
                                      jnp.asarray(pos), cfg))[:n]
        if not np.isfinite(lg).all():
            return False, dict(reason="non-finite reference logits")
        gaps.append(lg.max(-1) - lg[np.arange(n), res.tokens])
        exact += int((lg.argmax(-1) == res.tokens).sum())
    gaps = np.concatenate(gaps)
    worst, tolerance = float(gaps.max()), float(cfg["reference_tolerance"])
    detail = dict(checked=len(picks), tokens=len(gaps), exact_argmax=exact,
                  worst_margin=worst, margin_p99=float(np.percentile(gaps, 99)),
                  tolerance=tolerance, all_finished_at_length=legal)
    return legal and worst <= tolerance, detail


def run(ctx) -> dict:
    """One serving cell, end to end. ``ctx`` carries the parsed files and
    arguments (run.py); returns the observations the metrics read."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import serving

    cfg, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    backlog = traffic["kind"] == "serve_backlog"
    seconds, seed = float(ctx["seconds"]), ctx["seed"]
    phases = {}

    t = time.perf_counter()
    mdl = model.build_model(cfg).bfloat16()
    mdl.eval()
    state = model.make_state(mdl.state_dict(include_buffers=False), seed,
                             cfg["init_std"], jnp.bfloat16)
    jax.block_until_ready(state)
    phases["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    opts = engine_options(traffic, cell)
    eng = serving.ServingEngine(mdl, state=state, **opts)
    pool_blocks = eng.pool.num_blocks - 1       # block 0 is never handed out
    pool_bytes = pool_blocks * eng.block_bytes
    phases["engine_s"] = time.perf_counter() - t

    vocab = cfg["vocab_size"]
    t = time.perf_counter()
    buckets = loadgen.prompt_buckets(traffic["prompt_len"],
                                     opts["block_tokens"])
    warm_programs(eng, serving, buckets, traffic["warm_new_tokens"], vocab,
                  np.random.default_rng([int(seed), 0]))
    phases["warm_programs_s"] = time.perf_counter() - t
    phases["programs"] = len(buckets) + 1

    if backlog:
        feeder = loadgen.BacklogFeeder(
            loadgen.backlog_stream(traffic, vocab, seed))
        rate = None
    else:
        rate = float(ctx["rate_rps"] or cell["rate_rps"])
        feeder = loadgen.Feeder(loadgen.open_schedule(
            traffic, rate, seconds, vocab, seed))
    trace_s = min(3.0, seconds / 3)
    cap = capture.Capture(bool(ctx["trace"]), ctx["trace_dir"],
                          seconds - trace_s)
    warm_s = float(traffic["warm_s"])
    clock = ctx["clock"]
    t_loop = time.perf_counter()
    recs, ticks, marks = drive(eng, serving, feeder, seconds, warm_s, cap,
                               compiles=lambda: clock.compiles)
    setup_s = t_loop + warm_s - ctx["t_start"]
    phases["warm_traffic_s"] = warm_s

    eng.close()

    allr = list(recs.values())
    if backlog:
        measured = [r for r in allr if 0 <= r["submit"] < seconds]
        unsent = 0
    else:
        measured = [r for r in allr if 0 <= r["due"] < seconds]
        # still in the generator's queue when the drain gave up
        unsent = sum(1 for r in feeder.waiting if 0 <= r.due_s < seconds)
    attempted = len(measured) + unsent
    ok = [r for r in measured if finished_at_length(r)]
    failed = attempted - len(ok)

    e2e, shape = {}, None       # shape: [ttft, tpot] ms at a few percentiles,
                                # for the bench: line
    if backlog:
        toks = sum(r["prompt_len"] + len(r["result"].tokens) for r in allr
                   if r["result"] is not None
                   and 0 <= r["finish_t"] < seconds)
        e2e["serve_tokens_per_s"] = toks / seconds
    elif ok:
        ttft = [(r["submit"] - r["due"] + r["result"].ttft_s) * 1e3
                for r in ok]
        tpot = [r["result"].tpot_s * 1e3 for r in ok
                if r["result"].tpot_s is not None]
        e2e["ttft_ms_p50"] = loadgen.percentile(ttft, 50)
        e2e["tpot_ms_p50"] = loadgen.percentile(tpot, 50)
        shape = {f"p{q}": [loadgen.percentile(ttft, q),
                           loadgen.percentile(tpot, q)]
                 for q in (50, 80, 90, 95, 99)}

    t = time.perf_counter()
    correct, detail = check_outputs(
        measured, state, cfg, opts["max_seq_len"],
        loadgen.length_range(traffic["output_len"])[1], seed)
    phases["reference_s"] = time.perf_counter() - t

    host_end = marks.get("host_end", marks["close"])
    host_steps = (marks["open"]["steps"], host_end["steps"])
    live = live_blocks(allr, *host_steps, opts["block_tokens"])
    log(phase="serve", rate_rps=rate, attempted=attempted, failed=failed,
        waiting_open=marks["open"]["waiting"],
        waiting_middle=marks["middle"]["waiting"],
        waiting_close=marks["close"]["waiting"], ticks=len(ticks),
        compiles_in_window=(marks["close"]["compiles"]
                            - marks["open"]["compiles"]),
        pool=dict(blocks=pool_blocks, live_blocks_mean=live,
                  bytes=pool_bytes),
        setup_s=setup_s, setup=phases, reference=detail, end_to_end=e2e,
        percentiles=shape)
    return dict(
        kind=traffic["kind"], correct=correct, attempted=attempted,
        failed=failed, setup_s=setup_s, end_to_end=e2e,
        config=cfg, traffic=traffic, cell=cell, seconds=seconds,
        requests=measured, all_requests=allr, ticks=ticks,
        host_span=(marks["open"]["t"], host_end["t"]),
        stats={k: host_end["stats"][k] - marks["open"]["stats"][k]
               for k in host_end["stats"]},
        max_slots=opts["max_slots"], pool_blocks=pool_blocks,
        live_blocks_mean=live,
        compiles_in_window=(marks["close"]["compiles"]
                            - marks["open"]["compiles"]),
        trace_path=marks["trace_path"],
        trace_steps=(host_end["steps"], marks["close"]["steps"]))
