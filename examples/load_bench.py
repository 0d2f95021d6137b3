"""Open-loop serving load harness: the latency-vs-throughput curve.

``serving_bench.py`` answers "how much faster is continuous batching
than static batching" with a *closed* virtual clock — useful for the
A/B, useless for SLOs: closed-loop arrival generators slow down when
the server slows down, which hides exactly the queueing tails
production traffic produces. This harness drives the
``ServingEngine`` **open-loop**: arrivals land at wall-clock times
drawn independently of engine progress (Poisson, or bursty on-off),
so when the engine falls behind, the queue — and TTFT — grow the way
they do under real overload.

The sweep: offered load is expressed as multiples of the engine's
*calibrated* capacity (a closed-loop saturated drain measures
tokens/s, converted to requests/s via the mean budget), so
``--loads 0.5,0.9,1.5`` means the same thing on a laptop CPU and a
v5e. Each point emits one ``paddle_tpu.bench/v1`` record carrying the
percentile fields (``observability.SLOReport``): p50/p95/p99
TTFT/TPOT, token-weighted **goodput-under-SLO** against the
``(--slo_ttft_s, --slo_tpot_s)`` target, offered vs achieved request
rate, and the per-segment step-time breakdown from the engine stats.
A final record names the **goodput knee** — the highest offered load
whose goodput still clears ``--knee_goodput`` — which is the serving
headline ROADMAP's SLO item asks for (and the regression baseline the
chunked-prefill / speculative PRs will move). Run:

    python examples/load_bench.py [--model llama-medium]
        [--arrivals poisson|bursty] [--loads 0.5,0.9,1.5]
        [--slo_ttft_s 2.0] [--slo_tpot_s 0.25]
        [--flight_dump /tmp/flight.jsonl]
        [--shed [--max_queue N] [--deadline_s D]]
        [--priority_mix "low:1,normal:2,high:1"]

``--shed`` arms the PR 8 overload controls (bounded queue +
deadline-infeasibility rejection) for the measured points — the A/B
against unshedded collapse: past the knee the unshedded queue grows
without bound and ``ttft_p99_s`` explodes, while the shedded run keeps
the ADMITTED requests' tails flat and reports the drop as
``shed_rate``. ``--priority_mix`` adds classes, which also exercises
displacement shedding and slot preemption (``preemptions`` field).

``--chunk_tokens N`` + ``--prompt_mix long`` is the chunked-prefill
A/B (docs/SERVING.md §Chunked prefill; BENCH_r06): under a bimodal
prompt mix a monolithic wave prefill stalls every decode slot per
long prompt (``tpot_p99_s`` grows with load), while the chunked
engine bounds the stall at one chunk — run both arms on the same box
with the same seed and compare ``tpot_p99_s``/goodput per point
(records carry ``chunk_tokens``/``prefill_chunks``).

``--speculate k`` + ``--prompt_mix repeat`` is the speculative A/B
(docs/SERVING.md §Speculative decoding; BENCH_r07): motif-tiled
prompts make the n-gram proposer fire, and each record carries
``acceptance_rate``/``accepted_len_hist``/``dispatches_per_token`` —
the CPU gate is fused dispatches per committed token (CPU wall time
is compute-bound and pays the verify tail's extra matmuls; the TPU
kernel streams weights once per tail, so dispatches/token is the
proxy for the on-chip speedup).

Prefix caching is off here (random prompts never share blocks) and
prompt lengths quantize to few pad shapes, keeping prefill compile
churn out of the measured tails; the first sweep point still pays any
residual compiles, so compare points within a run, not across runs.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from serving_bench import (add_mesh_args, add_offload_args,
                           add_timeline_arg, build_engine_mesh,
                           build_model, build_speculate, mesh_fields,
                           offload_engine_kwargs, offload_fields,
                           spec_fields, spec_hist_base, timeline_fields)


def parse_priority_mix(spec):
    """``"low:1,normal:2,high:1"`` -> (names, weights). Empty/None means
    every request rides the default class."""
    if not spec:
        return None
    names, weights = [], []
    for part in spec.split(","):
        name, _, w = part.partition(":")
        names.append(name.strip())
        weights.append(float(w) if w else 1.0)
    total = sum(weights)
    return names, [w / total for w in weights]


def make_requests(ns, rng):
    """N requests with uniform prompt lengths / budgets (the queueing
    dynamics, not the length mix, are under test here); ``--priority_mix``
    assigns classes, ``--deadline_s`` attaches a deadline to every
    request (what infeasibility shedding prices).

    ``--prompt_mix long`` makes the length mix bimodal: ``--long_frac``
    of the requests carry a ``--long_prompt``-token prompt — the
    head-of-line regime where one monolithic wave prefill stalls every
    active decode slot (the chunked-prefill A/B; docs/SERVING.md
    §Chunked prefill)."""
    mix = parse_priority_mix(getattr(ns, "priority_mix", None))
    pmix = getattr(ns, "prompt_mix", "uniform")
    long_mix = pmix == "long"
    # 'repeat': each prompt tiles a short per-request motif — the
    # extraction/quoting-style repetitive regime where the n-gram
    # proposer's suffix match actually fires (the speculative A/B mix;
    # greedy decode of a repetitive prompt also cycles, which
    # self-speculation exploits)
    repeat_mix = pmix == "repeat"
    reqs = []
    for _ in range(ns.requests):
        if long_mix and rng.random_sample() < ns.long_frac:
            plen = int(ns.long_prompt)
        else:
            plen = int(rng.randint(ns.min_prompt, ns.max_prompt + 1))
        budget = int(rng.randint(ns.min_new, ns.max_new + 1))
        prio = (mix[0][int(rng.choice(len(mix[0]), p=mix[1]))]
                if mix else "normal")
        if repeat_mix:
            motif = rng.randint(3, ns.vocab, (max(2, plen // 4),))
            prompt = np.tile(motif, -(-plen // len(motif)))[:plen]
        else:
            prompt = rng.randint(3, ns.vocab, (plen,))
        reqs.append(dict(prompt=prompt,
                         budget=budget, priority=prio,
                         deadline=getattr(ns, "deadline_s", None)))
    return reqs


def gen_arrivals(n, rps, mode, rng, on_s=0.5, off_s=0.5):
    """Wall-clock arrival offsets (seconds from t0) for ``n`` requests
    at mean rate ``rps``.

    ``poisson``: i.i.d. exponential gaps. ``bursty``: on-off modulated
    Poisson — exponential ON windows (mean ``on_s``) arriving at
    ``rps / duty`` so the long-run mean is still ``rps``, separated by
    exponential OFF gaps (mean ``off_s``); the bursts are what stress
    admission and the queue."""
    if mode == "poisson":
        return np.cumsum(rng.exponential(1.0 / rps, n))
    duty = on_s / (on_s + off_s)
    rate_on = rps / duty
    out = []
    t = 0.0
    while len(out) < n:
        on_end = t + rng.exponential(on_s)
        while len(out) < n:
            t += rng.exponential(1.0 / rate_on)
            if t > on_end:
                break
            out.append(t)
        t = max(t, on_end) + rng.exponential(off_s)
    return np.asarray(out[:n])


def drive_open_loop(eng, reqs, arrivals):
    """Submit request i once the wall clock passes ``arrivals[i]``,
    stepping the engine regardless of queue state (open loop). Returns
    (wall seconds from first arrival epoch to full drain, rejected
    count) — with shedding enabled a submit may raise
    ``serving.Rejected`` (queue full / deadline infeasible), which is a
    *measured outcome* here, not an error."""
    from paddle_tpu import serving

    n = len(reqs)
    i = 0
    rejected = 0
    t0 = time.perf_counter()
    while i < n or not eng.idle:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            r = reqs[i]
            try:
                eng.submit(serving.Request(
                    r["prompt"], max_new_tokens=r["budget"],
                    priority=r.get("priority", "normal"),
                    deadline_s=r.get("deadline")))
            except serving.Rejected:
                rejected += 1
            i += 1
        if eng.idle and i < n:
            # nothing in flight: sleep toward the next arrival instead
            # of spinning the scheduler against an empty batch
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
            continue
        eng.step()
    return time.perf_counter() - t0, rejected


def calibrate(eng, reqs, reps=1):
    """Closed-loop saturated pass: submit everything at t=0, drain.
    Doubles as compile warmup (prefill shapes + the step program) and
    yields the capacity estimate the load multiples are scaled by.

    ``reps`` > 1 keeps the BEST pass (highest tokens/s) — the same
    best-of-reps convention serving_bench uses for its interleaved A/B
    pairs. The box's CPU budget swings ~2x over tens of seconds, and
    the chunked-vs-monolithic A/B runs its arms as back-to-back
    processes: a single calibration pass landing in a slow window
    would deflate that arm's re-measured capacity (and inflate its
    absolute offered rates) by pure scheduling noise. Best-of filters
    the contention the way adjacent interleaved passes do."""
    from paddle_tpu import serving

    best_tok_s = 0.0
    mean_budget = sum(r["budget"] for r in reqs) / len(reqs)
    for _ in range(max(1, reps)):
        eng.reset_stats()
        eng.results.clear()
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(serving.Request(r["prompt"],
                                       max_new_tokens=r["budget"]))
            eng.step()      # staggered submits compile small-wave shapes
        eng.drain()
        wall = time.perf_counter() - t0
        st = eng.stats
        tok_s = (st["decode_tokens"] + st["requests_finished"]) / wall
        best_tok_s = max(best_tok_s, tok_s)
    return best_tok_s, best_tok_s / mean_budget     # tokens/s, requests/s


def step_breakdown(stats):
    steps = max(stats["steps"], 1)
    return {k: round(stats[f"step_{k}_s"] / steps, 6)
            for k in ("admit", "prefill", "dispatch", "sync", "commit",
                      "tail")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--requests", type=int, default=48,
                    help="requests per offered-load point")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block_tokens", type=int, default=32)
    ap.add_argument("--max_seq_len", type=int, default=None)
    ap.add_argument("--min_prompt", type=int, default=8)
    ap.add_argument("--max_prompt", type=int, default=24)
    ap.add_argument("--min_new", type=int, default=8)
    ap.add_argument("--max_new", type=int, default=32)
    ap.add_argument("--prompt_mix", choices=("uniform", "long", "repeat"),
                    default="uniform",
                    help="'long' = bimodal prompt lengths: --long_frac "
                    "of requests carry a --long_prompt-token prompt "
                    "(the prefill head-of-line-blocking regime the "
                    "chunked-prefill A/B measures); 'repeat' = "
                    "motif-tiled repetitive prompts (the regime the "
                    "speculative n-gram proposer accelerates — the "
                    "--speculate A/B mix)")
    ap.add_argument("--long_prompt", type=int, default=256,
                    help="long-prompt length for --prompt_mix long")
    ap.add_argument("--long_frac", type=float, default=0.25,
                    help="fraction of long prompts for --prompt_mix "
                    "long")
    ap.add_argument("--chunk_tokens", type=int, default=None,
                    help="arm chunked prefill: prompts prefill this "
                    "many tokens per program, interleaved with decode "
                    "(None = monolithic wave prefill — the A/B "
                    "baseline). Must be a multiple of --block_tokens")
    ap.add_argument("--decode_per_chunk", type=int, default=1,
                    help="decode dispatches guaranteed between "
                    "consecutive prefill chunks")
    ap.add_argument("--arrivals", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--burst_on_s", type=float, default=0.5)
    ap.add_argument("--burst_off_s", type=float, default=0.5)
    ap.add_argument("--loads", default="0.5,0.9,1.5",
                    help="offered load as multiples of calibrated "
                    "capacity (comma list; >1 is deliberate overload — "
                    "that is where the knee lives)")
    ap.add_argument("--slo_ttft_s", type=float, default=2.0)
    ap.add_argument("--slo_tpot_s", type=float, default=0.25)
    ap.add_argument("--knee_goodput", type=float, default=0.9,
                    help="goodput threshold defining the knee")
    ap.add_argument("--cache_int8", action="store_true")
    ap.add_argument("--shed", action="store_true",
                    help="enable load shedding: bounded queue "
                    "(--max_queue) + deadline-infeasibility rejection — "
                    "the A/B against unshedded overload collapse")
    ap.add_argument("--max_queue", type=int, default=None,
                    help="queue bound when --shed (default 4*slots)")
    ap.add_argument("--priority_mix", default=None,
                    help='e.g. "low:1,normal:2,high:1" — weighted '
                    "random priority classes (exercises displacement "
                    "shedding and slot preemption)")
    ap.add_argument("--deadline_s", type=float, default=None,
                    help="per-request deadline (what --shed's "
                    "infeasibility estimator prices)")
    ap.add_argument("--flight_dump", default=None,
                    help="flight-recorder auto-dump path (postmortems "
                    "on fault/pool/deadline events)")
    ap.add_argument("--sanitize", action="store_true",
                    help="arm the dispatch sanitizer: steady-state "
                         "engine steps must perform 0 H2D transfers "
                         "and 0 recompiles or the bench dies "
                         "(paddle_tpu.analysis.runtime)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="arm speculative decoding with k proposals "
                    "per slot per tick (0 = off) — pair with "
                    "--prompt_mix repeat for the goodput A/B; records "
                    "grow acceptance_rate/accepted_len_hist/"
                    "dispatches_per_token")
    ap.add_argument("--proposer", choices=("ngram", "draft"),
                    default="ngram",
                    help="speculative proposer (see serving_bench)")
    ap.add_argument("--draft_model", default="llama-tiny",
                    help="draft model name for --proposer draft")
    ap.add_argument("--replicas", type=int, default=1,
                    help="drive the replicated tier (serving.Router "
                    "over N engine replicas, prefix-affinity + least-"
                    "loaded placement) instead of one engine — the "
                    "tier's latency/throughput curve")
    ap.add_argument("--calib_reps", type=int, default=3,
                    help="warm calibration passes (best tokens/s kept) "
                    "— best-of-reps filters CPU-contention noise out of "
                    "the capacity estimate, matching serving_bench's "
                    "interleaved-pair convention")
    ap.add_argument("--chunk_autotune", action="store_true",
                    help="autotune the chunk size per admission: the "
                    "engine picks the largest power-of-two chunk bucket "
                    "whose predicted fused-tick time fits under "
                    "--slo_tpot_s (requires --chunk_tokens as the cold "
                    "default)")
    add_mesh_args(ap)
    add_offload_args(ap)
    add_timeline_arg(ap)
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args()

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    name = ns.model or ("llama-345m" if dev.platform == "tpu"
                        else "llama-medium")
    cfg, model = build_model(name)
    ns.vocab = cfg.vocab_size
    if ns.max_seq_len is None:
        top_prompt = (max(ns.max_prompt, ns.long_prompt)
                      if ns.prompt_mix == "long" else ns.max_prompt)
        need = top_prompt + ns.max_new
        ns.max_seq_len = -(-need // ns.block_tokens) * ns.block_tokens

    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    max_queue = (ns.max_queue if ns.max_queue is not None
                 else 4 * ns.slots) if ns.shed else None
    ekw = dict(
        max_slots=ns.slots, block_tokens=ns.block_tokens,
        max_seq_len=ns.max_seq_len,
        cache_dtype=jnp.int8 if ns.cache_int8 else jnp.bfloat16,
        prefix_caching=False, flight_dump_path=ns.flight_dump,
        chunk_tokens=ns.chunk_tokens,
        decode_per_chunk=ns.decode_per_chunk,
        speculate=build_speculate(ns),
        mesh=build_engine_mesh(ns),
        sanitize=ns.sanitize,
        **offload_engine_kwargs(ns))
    if ns.chunk_autotune:
        ekw.update(chunk_autotune=True, slo_tpot_s=ns.slo_tpot_s)
    if ns.replicas > 1:
        eng = serving.Router(model, replicas=ns.replicas,
                             snapshot_every=None, **ekw)
    else:
        eng = serving.ServingEngine(model, **ekw)

    rng = np.random.RandomState(ns.seed)
    reqs = make_requests(ns, rng)
    calibrate(eng, reqs)                # cold pass: compiles dominate
    # warm passes, best-of-reps: the capacity estimate (and the chunked
    # A/B's re-measured absolute capacity) filters CPU-contention noise
    cap_tok_s, cap_rps = calibrate(eng, reqs, reps=ns.calib_reps)
    print(f"# calibrated capacity: {cap_tok_s:.1f} tokens/s "
          f"~ {cap_rps:.2f} req/s", file=sys.stderr)
    # shedding arms AFTER calibration (the saturated closed-loop pass
    # would otherwise shed its own warmup) — the measured points see the
    # bounded queue + infeasibility estimator
    if ns.replicas > 1:
        eng.set_overload_controls(max_queue=max_queue,
                                  shed_infeasible=ns.shed)
    else:
        eng.max_queue = max_queue
        eng.shed_infeasible = ns.shed

    curve = []
    loads = [float(x) for x in ns.loads.split(",") if x]
    for mult in loads:
        rps = mult * cap_rps
        arrivals = gen_arrivals(ns.requests, rps, ns.arrivals, rng,
                                ns.burst_on_s, ns.burst_off_s)
        eng.reset_stats()
        eng.results.clear()
        # accepted-length histogram base: the registry histogram is
        # process-global, so each point's record diffs against this
        # snapshot (calibration + earlier points must not leak in)
        hist_base = spec_hist_base(ns)
        wall, rejected = drive_open_loop(eng, reqs, arrivals)
        rep = obs.SLOReport(ns.slo_ttft_s, ns.slo_tpot_s)
        served = 0
        for res in eng.results.values():
            if res.finish == "shed":
                continue        # displaced: counted in shed_rate, not
            served += 1         # in the served-latency percentiles
            rep.add(res.ttft_s, res.tpot_s, tokens=max(1, res.gen_len))
        st = eng.stats
        shed = rejected + st["requests_shed"]
        tok_s = (st["decode_tokens"] + served) / wall
        rec = obs.bench_record(
            f"{name} open-loop {ns.arrivals} {mult:g}x tokens/s",
            round(tok_s, 1), "tokens/s", device=dev.device_kind,
            timing="wall", batch=ns.slots, mode=ns.arrivals,
            load_mult=mult, n_requests=ns.requests,
            offered_rps=round(rps, 4),
            achieved_rps=round(served / wall, 4),
            occupancy=round(st["decode_tokens"] / max(
                st["decode_tokens"] + st["idle_slot_steps"], 1), 3),
            step_breakdown_s=step_breakdown(st),
            shed_rate=round(shed / ns.requests, 4),
            preemptions=st["preemptions"],
            replicas=ns.replicas,
            prompt_mix=ns.prompt_mix,
            chunk_tokens=ns.chunk_tokens,
            prefill_chunks=st["prefill_chunks"],
            # the speculative perf gate's metric: fused dispatches a
            # slot pays per committed token (1.0 without speculation)
            dispatches_per_token=round(
                st["decode_slot_dispatches"]
                / max(st["decode_tokens"], 1), 4),
            **spec_fields(eng, ns, hist_base),
            **offload_fields(eng, ns),
            **({"tier_prefix_hit_rate":
                round(eng.tier_prefix_hit_rate, 4)}
               if ns.replicas > 1 else {}),
            **mesh_fields(ns, ekw["mesh"]), **rep.bench_fields())
        print(json.dumps(rec))
        curve.append(dict(load_mult=mult, offered_rps=round(rps, 4),
                          tokens_per_s=round(tok_s, 1),
                          goodput=rec["goodput"],
                          shed_rate=rec["shed_rate"],
                          ttft_p99_s=rec["ttft_p99_s"],
                          tpot_p99_s=rec["tpot_p99_s"]))

    # the knee: highest offered load still clearing the goodput bar —
    # the number a capacity planner actually provisions against
    good = [c for c in curve if c["goodput"] >= ns.knee_goodput]
    knee = max(good, key=lambda c: c["offered_rps"]) if good else None
    rec = obs.bench_record(
        f"{name} goodput-under-SLO knee ({ns.arrivals})",
        knee["offered_rps"] if knee else 0.0, "req/s",
        device=dev.device_kind, timing="wall",
        slo_ttft_s=ns.slo_ttft_s, slo_tpot_s=ns.slo_tpot_s,
        knee_goodput=ns.knee_goodput,
        knee_load_mult=knee["load_mult"] if knee else None,
        prompt_mix=ns.prompt_mix, chunk_tokens=ns.chunk_tokens,
        calibrated_capacity_rps=round(cap_rps, 4), curve=curve,
        # the flight ring (and results) cover the LAST sweep point —
        # the timeline is that point's postmortem window
        **timeline_fields(ns, eng))
    print(json.dumps(rec))
    eng.close()         # free the KV pool (long sweeps, repeated runs)


if __name__ == "__main__":
    main()
