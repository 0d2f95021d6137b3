"""Serving throughput A/B: continuous batching vs static `generate`.

After PR 1/2 drove the per-step fused decode kernel toward roofline, the
remaining serving throughput loss is SCHEDULING waste: a static batch
pads every slot to the longest member's budget (a finished request burns
decode steps emitting padding) and a late arrival waits for the whole
batch to drain. This bench runs the SAME synthetic workload — Poisson
arrivals, mixed prompt lengths, mixed token budgets, an optional shared
system prefix — through both paths:

* **static** — requests grouped into fixed batches of ``--slots`` in
  arrival order; each batch is one ``inference.generate`` call padded to
  the batch max prompt/budget (the pre-serving deployment model). Useful
  tokens are each request's own budget; everything past it is pad waste
  (``generate(return_lengths=True)`` is the per-row accounting).
* **continuous** — one ``serving.ServingEngine`` with ``--slots`` decode
  slots over the paged KV pool: requests join mid-flight as arrivals
  land (virtual clock: arrival times are measured in decode steps),
  retire at budget at slot granularity, and block-aligned shared
  prefixes ride the content-hashed prefix cache.

Both sides emit one ``paddle_tpu.bench/v1`` JSON line (static first);
the continuous record carries the headline ``speedup_vs_static`` plus
the occupancy / pad-waste / prefix-hit / queue-depth gauges the engine
exports through the observability registry. Run:

    python examples/serving_bench.py [--requests 24] [--slots 8]
        [--sys_prompt_len 32] [--seed 0]

CPU-sized by default (llama-medium, the jnp reference decode path — the
same program the interpret-mode parity twins in tests/test_serving.py
pin against the Pallas kernel; --model llama-tiny for smoke runs); on
TPU the default is llama-345m.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def build_model(name):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if name == "llama-tiny":
        cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=4, num_kv_heads=4,
                          intermediate_size=256,
                          max_position_embeddings=512)
    elif name == "llama-small":
        cfg = LlamaConfig(vocab_size=2048, hidden_size=512, num_layers=4,
                          num_heads=8, num_kv_heads=8,
                          intermediate_size=1024,
                          max_position_embeddings=512)
    elif name == "llama-medium":
        # the CPU A/B size: big enough that per-step model compute (not
        # per-dispatch overhead, which a static `generate`'s lax.scan
        # amortizes but a per-token serving dispatch pays in full) sets
        # the step time — the regime where the scheduling win
        # (occupancy) decides the headline, as it does on TPU
        cfg = LlamaConfig(vocab_size=2048, hidden_size=640, num_layers=6,
                          num_heads=10, num_kv_heads=10,
                          intermediate_size=1664,
                          max_position_embeddings=512)
    elif name == "llama-345m":
        cfg = LlamaConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                          num_heads=16, num_kv_heads=16,
                          intermediate_size=2816,
                          max_position_embeddings=2048)
    else:
        raise SystemExit(f"unknown model {name}")
    import paddle_tpu
    paddle_tpu.seed(0)
    m = LlamaForCausalLM(cfg).bfloat16()
    m.eval()
    return cfg, m


def build_model_only(name):
    """Module-level (hence picklable) model factory half for the
    cross-process serving tier: each worker process rebuilds the model
    itself, and ``paddle_tpu.seed(0)`` inside `build_model` makes every
    replica's weights bit-identical to the parent's reference copy."""
    return build_model(name)[1]


def make_workload(ns, rng):
    """N requests: Poisson arrivals (exp gaps, in decode-step units),
    mixed prompt lengths and LONG-TAILED token budgets, optional shared
    system prefix.

    Budgets are bimodal — a ``1 - long_frac`` majority of short
    chat-style replies (uniform ``[min_new, max_new/4]``) and a
    ``long_frac`` tail of long generations (uniform
    ``[max_new/2, max_new]``). That tail is the serving regime the Orca
    lineage targets: one long request in a static batch pads EVERY
    sibling to its budget, while the continuous engine retires the short
    ones at slot granularity and back-fills from the queue."""
    sys_prefix = rng.randint(3, ns.vocab, (ns.sys_prompt_len,))
    reqs = []
    t = 0.0
    short_hi = max(ns.min_new + 1, ns.max_new // 4)
    long_lo = max(ns.min_new, ns.max_new // 2)
    mean_budget = ((1 - ns.long_frac) * (ns.min_new + short_hi) / 2
                   + ns.long_frac * (long_lo + ns.max_new) / 2)
    # offered load a multiple of slot capacity: the queue stays busy
    # (saturation), which is the regime where occupancy is the honest
    # headline
    rate = ns.load * ns.slots / mean_budget      # requests per step
    for i in range(ns.requests):
        t += rng.exponential(1.0 / rate)
        plen = rng.randint(ns.min_prompt, ns.max_prompt + 1)
        prompt = np.concatenate(
            [sys_prefix, rng.randint(3, ns.vocab, (plen,))])
        if rng.random_sample() < ns.long_frac:
            budget = int(rng.randint(long_lo, ns.max_new + 1))
        else:
            budget = int(rng.randint(ns.min_new, short_hi + 1))
        reqs.append(dict(arrival_step=t, prompt=prompt, budget=budget))
    return reqs


# ---------------------------------------------------------------- static A/B

def run_static(model, state, reqs, slots, cache_dtype=jnp.bfloat16):
    """Arrival-order batches of ``slots`` through one padded `generate`
    each (same KV-cache dtype as the engine side — a fair A/B). Returns
    (wall_s, useful_tokens, emitted_slot_tokens)."""
    from paddle_tpu.inference import generate

    wall = 0.0
    useful = emitted = 0
    for k in range(0, len(reqs), slots):
        batch = reqs[k:k + slots]
        pmax = max(len(r["prompt"]) for r in batch)
        nmax = max(r["budget"] for r in batch)
        ids = np.ones((len(batch), pmax), np.int32)   # right-pad token 1
        for i, r in enumerate(batch):
            ids[i, :len(r["prompt"])] = r["prompt"]
        ids = jnp.asarray(ids)
        t0 = time.perf_counter()
        out, lens = generate(model, ids, max_new_tokens=nmax,
                             temperature=0.0, state=state,
                             cache_dtype=cache_dtype,
                             return_lengths=True)
        int(out[:, -1].sum())                         # sync
        wall += time.perf_counter() - t0
        # every row decodes nmax steps; a request is only USEFUL up to
        # its own budget — the rest is the pad waste static batching
        # cannot avoid (lens reports eos cuts when an eos id is set)
        useful += sum(min(r["budget"], int(n)) for r, n in zip(batch, lens))
        emitted += len(batch) * nmax
    return wall, useful, emitted


# ------------------------------------------------------------ continuous A/B

def build_speculate(ns):
    """SpecConfig from the bench flags (None when --speculate 0). The
    draft proposer drafts with --draft_model (llama-tiny by default —
    the tiny-drafts-for-medium pairing the ROADMAP names)."""
    from paddle_tpu import serving

    k = getattr(ns, "speculate", 0)
    if not k:
        return None
    proposer = getattr(ns, "proposer", "ngram")
    draft = None
    if proposer == "draft":
        _, draft = build_model(getattr(ns, "draft_model", "llama-tiny"))
    return serving.SpecConfig(k=k, proposer=proposer, draft_model=draft)


def add_mesh_args(ap):
    """--mp/--fsdp flags shared by serving_bench/load_bench/chaos_bench:
    shard EACH engine replica over a {fsdp, mp} submesh
    (serving.ServingLayout; docs/SERVING.md §Tensor-parallel
    replicas)."""
    ap.add_argument("--mp", type=int, default=1,
                    help="tensor-parallel shards per replica: attention "
                    "heads + ffn columns + the paged KV pool split "
                    "over the mp mesh axis (1 = unsharded; tokens are "
                    "bit-identical at every degree)")
    ap.add_argument("--fsdp", type=int, default=1,
                    help="layer-dim weight shards per replica (gathered "
                    "at use; must divide num_layers)")


def build_engine_mesh(ns):
    """Mesh from --mp/--fsdp (None when both are 1 — the engine then
    takes the exact unsharded program path)."""
    mp = getattr(ns, "mp", 1) or 1
    fsdp = getattr(ns, "fsdp", 1) or 1
    if mp <= 1 and fsdp <= 1:
        return None
    from paddle_tpu.parallel import topology
    dims = {}
    if fsdp > 1:
        dims["fsdp"] = fsdp
    if mp > 1:
        dims["mp"] = mp
    return topology.build_mesh(dims)


def mesh_fields(ns, mesh):
    """Typed-optional tensor-parallel BENCH fields (schema.py)."""
    if mesh is None:
        return {}
    return dict(mp_degree=getattr(ns, "mp", 1) or 1,
                fsdp_degree=getattr(ns, "fsdp", 1) or 1,
                mesh_shape={str(k): int(v)
                            for k, v in mesh.shape.items()})


def add_offload_args(ap):
    """--offload flags shared by serving_bench/load_bench/chaos_bench:
    arm the hierarchical KV tier (docs/SERVING.md §Hierarchical KV) —
    a preempted request's KV blocks swap to a host-RAM block store
    (D2H overlapped with serving ticks) and resume token-exact from a
    gather-back instead of a re-prefill + replay recompute."""
    ap.add_argument("--offload", action="store_true",
                    help="swap preempted requests' KV blocks to a "
                    "host-RAM block store and resume them bitwise from "
                    "a gather (zero replay dispatches) instead of "
                    "recomputing; records grow host_blocks_total/"
                    "swap_out_bytes/swap_in_bytes/prefetch_hit_rate")
    ap.add_argument("--host_pool_blocks", type=int, default=None,
                    help="host-tier capacity in KV blocks per replica "
                    "(default: 4x the device pool)")


def offload_engine_kwargs(ns):
    """Engine kwargs from the --offload flags ({} when unarmed)."""
    if not getattr(ns, "offload", False):
        return {}
    kw = dict(offload=True)
    if getattr(ns, "host_pool_blocks", None):
        kw["host_pool_blocks"] = ns.host_pool_blocks
    return kw


def offload_fields(eng, ns):
    """Typed-optional hierarchical-KV BENCH fields (schema.py). ``eng``
    is a ServingEngine or the Router; a cross-process replica proxy has
    no reachable host store, so its capacity contribution falls back to
    the configured --host_pool_blocks."""
    if not getattr(ns, "offload", False):
        return {}
    st = eng.stats
    hits = int(st.get("prefetch_hits", 0))
    probes = hits + int(st.get("prefetch_misses", 0))
    if hasattr(eng, "replica_engine"):          # Router tier
        total = 0
        for i in range(eng.num_replicas):
            rep = eng.replica_engine(i)
            hs = getattr(rep, "host_store", None)
            if hs is not None:
                total += hs.capacity
            elif rep is not None:
                total += int(getattr(ns, "host_pool_blocks", 0) or 0)
    else:
        hs = getattr(eng, "host_store", None)
        total = hs.capacity if hs is not None else 0
    return dict(
        host_blocks_total=int(total),
        swap_out_bytes=int(st.get("swap_out_bytes", 0)),
        swap_in_bytes=int(st.get("swap_in_bytes", 0)),
        prefetch_hit_rate=round(hits / probes, 4) if probes else 0.0)


def add_timeline_arg(ap):
    """--timeline flag shared by serving_bench/load_bench/chaos_bench."""
    ap.add_argument("--timeline", default=None, metavar="OUT.json",
                    help="write a Perfetto-loadable Chrome trace-event "
                    "timeline of the run: flight-ring tick segments, "
                    "per-request instants and trace_id flow chains "
                    "(plus the router journal in --replicas mode — "
                    "docs/OBSERVABILITY.md §Timelines); the bench "
                    "record gains timeline_path/trace_count")


def timeline_fields(ns, eng, journal_path=None):
    """Write ``--timeline`` (empty dict when unset) and return the
    BENCH fields ``{timeline_path, trace_count}``. ``eng`` is a
    ServingEngine or the Router — a router contributes its own flight
    ring plus one process track per replica engine, and the replayed
    request journal when the tier keeps one at ``journal_path``. The
    flight rings cover their engines' LAST ``flight_capacity`` ticks
    (and, single-engine chaos, only the latest restore incarnation) —
    the timeline is a postmortem window, not a full-run archive."""
    if not getattr(ns, "timeline", None):
        return {}
    from paddle_tpu.observability import timeline as tl
    from paddle_tpu.serving.journal import RouterJournal

    anchor = tl.clock_anchor()
    trace_map = {rid: res.trace_id for rid, res in eng.results.items()
                 if getattr(res, "trace_id", None)}
    if hasattr(eng, "replica_engine"):          # Router tier
        processes = [{"name": "router", "flight": eng.flight.events(),
                      "anchor": anchor}]
        for i in range(eng.num_replicas):
            rep = eng.replica_engine(i)
            if rep is not None:
                processes.append({"name": f"replica_{i}",
                                  "flight": rep.flight.events(),
                                  "anchor": anchor})
    else:
        processes = [{"name": "engine", "flight": eng.flight.events(),
                      "anchor": anchor}]
    journal = ()
    if journal_path and os.path.isfile(journal_path):
        journal, _corrupt = RouterJournal.replay(journal_path)
    info = tl.write_timeline(ns.timeline, processes=processes,
                             journal=journal, trace_map=trace_map)
    print(f"# timeline: {info['path']} ({info['events']} events, "
          f"{info['trace_count']} trace chains)", file=sys.stderr)
    return dict(timeline_path=info["path"],
                trace_count=info["trace_count"])


def spec_hist_base(ns):
    """Snapshot of the serving.spec_accepted_len bucket counts, taken
    BEFORE a measured pass so ``spec_fields(hist_base=...)`` can report
    the pass's own distribution — the registry histogram is
    process-global and would otherwise accumulate calibration passes
    and earlier sweep points into every record."""
    if not getattr(ns, "speculate", 0):
        return None
    from paddle_tpu.observability import registry
    return list(registry().histogram("serving.spec_accepted_len").counts)


def spec_fields(eng, ns, hist_base=None):
    """Typed-optional speculative BENCH fields (schema.py): cumulative
    acceptance over the measured pass + the accepted-length histogram
    (diffed against a ``spec_hist_base`` pre-pass snapshot when
    given)."""
    if not getattr(ns, "speculate", 0):
        return {}
    from paddle_tpu.observability import registry
    st = eng.stats
    h = registry().histogram("serving.spec_accepted_len")
    counts = list(h.counts)
    if hist_base is not None:
        counts = [c - b for c, b in zip(counts, hist_base)]
    hist = {str(int(b)): c for b, c in zip(h.bounds, counts)}
    hist["+Inf"] = counts[-1]
    rate = (st["spec_accepted"] / st["spec_proposed"]
            if st["spec_proposed"] else 0.0)
    return dict(speculate_k=ns.speculate,
                proposer=getattr(ns, "proposer", "ngram"),
                acceptance_rate=round(rate, 4),
                accepted_len_hist=hist)


def run_continuous(model, reqs, ns):
    """Drive a ServingEngine (or, with ``--replicas N``, the
    replicated serving.Router tier — same submit/step surface): virtual
    clock in decode steps — request i joins the queue once
    ``arrival_step`` steps have run. Returns (wall_s, engine)."""
    from paddle_tpu import serving

    ekw = dict(
        max_slots=ns.slots, block_tokens=ns.block_tokens,
        max_seq_len=ns.max_seq_len,
        cache_dtype=jnp.int8 if ns.cache_int8 else jnp.bfloat16,
        chunk_tokens=getattr(ns, "chunk_tokens", None),
        speculate=build_speculate(ns),
        mesh=build_engine_mesh(ns),
        sanitize=getattr(ns, "sanitize", False),
        **offload_engine_kwargs(ns))
    if getattr(ns, "chunk_autotune", False):
        ekw.update(chunk_autotune=True,
                   slo_tpot_s=getattr(ns, "slo_tpot_s", None) or 0.25)
    if getattr(ns, "replicas", 1) > 1:
        eng = serving.Router(model, replicas=ns.replicas,
                             snapshot_every=None, **ekw)
    else:
        eng = serving.ServingEngine(model, **ekw)
    return drive(eng, reqs), eng


def drive(eng, reqs):
    from paddle_tpu import serving

    pending = sorted(reqs, key=lambda r: r["arrival_step"])
    i = 0
    vstep = 0
    t0 = time.perf_counter()
    while i < len(pending) or not eng.idle:
        while i < len(pending) and pending[i]["arrival_step"] <= vstep:
            r = pending[i]
            eng.submit(serving.Request(r["prompt"],
                                       max_new_tokens=r["budget"]))
            i += 1
        eng.step()
        vstep += 1
    return time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--block_tokens", type=int, default=32,
                    help="pool block size; 32 keeps the default shared "
                    "32-token system prefix exactly one full "
                    "(shareable) block and halves the block-table "
                    "dirty-upload rate vs 16")
    ap.add_argument("--max_seq_len", type=int, default=None)
    ap.add_argument("--min_prompt", type=int, default=8)
    ap.add_argument("--max_prompt", type=int, default=48)
    ap.add_argument("--min_new", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=128,
                    help="budget ceiling; the default 128 vs min_new=4 "
                    "gives the wide generation-length spread of real "
                    "chat traffic (short replies + a long tail) — the "
                    "regime static batching pads worst")
    ap.add_argument("--sys_prompt_len", type=int, default=32,
                    help="shared system prefix (0 disables): block-"
                    "aligned full blocks are content-hash shared, so "
                    "every request after the first skips that prefill")
    ap.add_argument("--cache_int8", action="store_true")
    ap.add_argument("--chunk_tokens", type=int, default=None,
                    help="arm chunked prefill on the engine side: "
                    "prompts prefill this many tokens per program "
                    "interleaved with decode (multiple of "
                    "--block_tokens; None = monolithic wave prefill)")
    ap.add_argument("--chunk_autotune", action="store_true",
                    help="autotune the chunk size per admission: the "
                    "largest power-of-two bucket whose predicted "
                    "fused-tick time fits under --slo_tpot_s "
                    "(defaults to 0.25s when no SLO is given)")
    ap.add_argument("--load", type=float, default=3.0,
                    help="offered load as a multiple of slot capacity")
    ap.add_argument("--long_frac", type=float, default=0.25,
                    help="fraction of long-generation requests (budget "
                    "uniform in [max_new/2, max_new]; the rest draw "
                    "short chat budgets in [min_new, max_new/4])")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved static/continuous pass pairs "
                    "(best wall per side kept)")
    ap.add_argument("--slo_ttft_s", type=float, default=None,
                    help="TTFT target: with either SLO set the "
                    "continuous record reports token-weighted "
                    "goodput-under-SLO (examples/load_bench.py is the "
                    "open-loop harness built around that number)")
    ap.add_argument("--slo_tpot_s", type=float, default=None)
    ap.add_argument("--sanitize", action="store_true",
                    help="arm the dispatch sanitizer: steady-state "
                         "engine steps must perform 0 H2D transfers "
                         "and 0 recompiles or the bench dies "
                         "(paddle_tpu.analysis.runtime)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="arm speculative decoding with k proposals "
                    "per slot per tick (0 = off); the continuous "
                    "record grows acceptance_rate/accepted_len_hist")
    ap.add_argument("--proposer", choices=("ngram", "draft"),
                    default="ngram",
                    help="speculative proposer: device n-gram suffix "
                    "match (no extra model) or a draft model")
    ap.add_argument("--draft_model", default="llama-tiny",
                    help="draft model name for --proposer draft")
    ap.add_argument("--replicas", type=int, default=1,
                    help="drive the continuous arm through the "
                    "replicated tier (serving.Router over N engine "
                    "replicas) instead of one engine")
    add_mesh_args(ap)
    add_offload_args(ap)
    add_timeline_arg(ap)
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args()

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    # CPU default is llama-small: big enough that per-step compute (not
    # host dispatch) dominates — the regime where the A/B measures
    # scheduling, which is what the engine changes. llama-tiny stays
    # available for fast smoke runs (the CI schema test uses it).
    name = ns.model or ("llama-345m" if on_tpu else "llama-medium")
    if ns.requests is None:
        # enough requests that the ramp/drain edge effects (slots
        # filling at t=0, the batch thinning as the last arrivals
        # finish) stop dominating occupancy — real traffic has no drain
        ns.requests = 96

    cfg, model = build_model(name)
    ns.vocab = cfg.vocab_size
    if ns.max_seq_len is None:
        need = ns.sys_prompt_len + ns.max_prompt + ns.max_new
        ns.max_seq_len = -(-need // ns.block_tokens) * ns.block_tokens
    state = model.trainable_state()

    rng = np.random.RandomState(ns.seed)
    reqs = make_workload(ns, rng)
    n_useful = sum(r["budget"] for r in reqs)

    # ---- warmups: static compiles the per-batch-shape programs; the
    # engine gets two passes (pass 1 compiles the cold-prefix prefill
    # variants, pass 2 the warm-prefix ones)
    cdt = jnp.int8 if ns.cache_int8 else jnp.bfloat16
    run_static(model, state, reqs, ns.slots, cdt)
    _, eng = run_continuous(model, reqs, ns)
    drive(eng, reqs)

    # ---- measurement: INTERLEAVED static/continuous pairs, best-of-reps
    # wall per side. The container's CPU budget swings by 2x over tens of
    # seconds; running all static passes then all continuous passes would
    # hand whichever side lands in the fast window a phantom speedup,
    # while adjacent interleaved passes see (and best-of filters) the
    # same contention.
    wall_s = wall_c = float("inf")
    for _ in range(ns.reps):
        w, useful_s, emitted_s = run_static(model, state, reqs,
                                            ns.slots, cdt)
        wall_s = min(wall_s, w)
        if ns.replicas > 1:
            eng.clear_prefix_caches()
        elif eng.prefix_cache is not None:
            eng.prefix_cache.clear()
        eng.reset_stats()
        # drop warmup/prior-rep results: ttft_p50 must cover ONE
        # measured pass, not compile-stall warmup TTFTs
        eng.results.clear()
        wall_c = min(wall_c, drive(eng, reqs))
    static_tok_s = useful_s / wall_s
    static_occ = useful_s / emitted_s
    st = eng.stats
    # each request's FIRST token is sampled by its prefill program, not
    # a decode step; drive() runs to idle so requests_finished counts
    # exactly one prefill sample per request — omitting them would bias
    # the A/B low (the static side's useful counts full budgets)
    cont_tok_s = (st["decode_tokens"] + st["requests_finished"]) / wall_c
    cont_occ = st["decode_tokens"] / max(
        st["decode_tokens"] + st["idle_slot_steps"], 1)
    prefix_hit = (eng.prefix_hit_rate if ns.replicas > 1
                  else (eng.prefix_cache.hit_rate
                        if eng.prefix_cache is not None else 0.0))

    from paddle_tpu import observability as obs
    # per-request tail latency over the measured pass (the sketch's 1%
    # relative error is far under run-to-run CPU noise)
    slo = obs.SLOReport(ns.slo_ttft_s, ns.slo_tpot_s)
    for r in eng.results.values():
        slo.add(r.ttft_s, r.tpot_s, tokens=max(1, r.gen_len))
    common = dict(device=dev.device_kind, batch=ns.slots,
                  n_requests=ns.requests,
                  prompt_len=ns.sys_prompt_len + ns.max_prompt,
                  new_tokens=ns.max_new, useful_tokens=n_useful,
                  workload=dict(min_prompt=ns.min_prompt,
                                max_prompt=ns.max_prompt,
                                min_new=ns.min_new, max_new=ns.max_new,
                                sys_prompt_len=ns.sys_prompt_len,
                                arrivals=f"poisson({ns.load:g}x-capacity)",
                                seed=ns.seed))
    tag = " kv8" if ns.cache_int8 else ""
    print(json.dumps(obs.bench_record(
        f"{name}{tag} static batch tokens/s (b={ns.slots})",
        round(static_tok_s, 1), "tokens/s", mode="static",
        occupancy=round(static_occ, 3),
        pad_waste_frac=round(1 - static_occ, 3),
        emitted_slot_tokens=emitted_s, **common)))
    print(json.dumps(obs.bench_record(
        f"{name}{tag} continuous serving tokens/s (slots={ns.slots})",
        round(cont_tok_s, 1), "tokens/s", mode="continuous",
        speedup_vs_static=round(cont_tok_s / static_tok_s, 3),
        occupancy=round(cont_occ, 3),
        prefix_hit_rate=round(prefix_hit, 3),
        prefill_tokens=st["prefill_tokens"],
        prefill_tokens_reused=st["prefill_tokens_reused"],
        chunk_tokens=ns.chunk_tokens,
        prefill_chunks=st["prefill_chunks"],
        replicas=ns.replicas,
        **({"tier_prefix_hit_rate": round(eng.tier_prefix_hit_rate, 4)}
           if ns.replicas > 1 else {}),
        pool_blocks=(eng.pool_blocks_total if ns.replicas > 1
                     else eng.pool.num_blocks - 1),
        block_tokens=ns.block_tokens, **spec_fields(eng, ns),
        **offload_fields(eng, ns),
        **mesh_fields(ns, build_engine_mesh(ns)),
        **timeline_fields(ns, eng),
        **slo.bench_fields(), **common)))
    eng.close()         # free the KV pool (back-to-back bench runs)


if __name__ == "__main__":
    main()
