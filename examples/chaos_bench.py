"""Chaos soak: overload + injected faults + crash/restore, zero loss.

``load_bench.py`` measures how latency degrades under overload;
this harness asserts the engine *survives* it. It drives Poisson
arrivals at ``--load`` times the calibrated capacity (default 1.5 —
deliberately past the goodput knee) with the PR 8 overload controls
armed (bounded queue, deadline-infeasibility shedding, priority mix →
displacement + preemption), while a ``FaultPlan`` fires
raise / RESOURCE_EXHAUSTED faults at the serving ``decode.dispatch``
site every ``--fault_every`` dispatches. Every crash takes the
snapshot → integrity-manifest commit → ``ServingEngine.restore`` path
(a fresh engine re-admits all in-flight and queued work via
token-exact resume).

Exit contract (the acceptance bar, enforced with a non-zero exit):

* **zero loss** — every accepted submit ends in ``results`` with a
  finish reason (``eos``/``length``/``deadline``/``shed``); nothing
  vanishes across any number of crashes;
* **token parity across restores** — ``--verify`` randomly chosen
  completed requests are replayed through isolated ``generate`` and
  must match token-for-token (greedy default);
* **reported shedding** — the final ``paddle_tpu.bench/v1`` record
  carries ``shed_rate``, ``preemptions``, ``restores`` and
  ``lost_requests`` (== 0), and the flight ring/dump holds the
  preempt/shed/restore markers a postmortem would replay;
* **trace continuity** (``--replicas`` mode) — every accepted
  request's journal events must form ONE connected ``trace_id`` chain
  (accept/place/finish all carry the same id — a migration off a
  killed replica must not fork the chain); a broken chain exits 4.
  ``--timeline out.json`` additionally exports the run as a
  Perfetto-loadable timeline (docs/OBSERVABILITY.md §Timelines).

``--offload`` arms the hierarchical KV tier (docs/SERVING.md
§Hierarchical KV): preemptions swap KV blocks to the host-RAM store
and resume token-exact from a gather. ``--swap_fault_every M`` then
fires ``offload.swap`` faults — raising faults must downgrade to the
legacy recompute/replay resume, and hang faults dwell inside the swap
window so ``--kill_mode sigkill`` lands MID-SWAP — all under the same
zero-loss exit contract.

Run::

    python examples/chaos_bench.py [--model llama-tiny] [--requests 40]
        [--load 1.5] [--fault_every 25] [--deadline_frac 0.25]
        [--flight_dump /tmp/chaos_flight.jsonl]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from load_bench import calibrate, gen_arrivals, make_requests
from serving_bench import (add_mesh_args, add_offload_args,
                           add_timeline_arg, build_engine_mesh,
                           build_model, build_speculate, mesh_fields,
                           offload_engine_kwargs, offload_fields,
                           timeline_fields)


def engine_kwargs(ns, flight_dump, speculate=None):
    kw = dict(
        max_slots=ns.slots, block_tokens=ns.block_tokens,
        max_seq_len=ns.max_seq_len,
        cache_dtype=jnp.int8 if ns.cache_int8 else jnp.bfloat16,
        flight_dump_path=flight_dump,
        chunk_tokens=getattr(ns, "chunk_tokens", None),
        speculate=speculate,
        mesh=build_engine_mesh(ns),
        max_queue=ns.max_queue, shed_infeasible=True,
        **offload_engine_kwargs(ns))
    if getattr(ns, "chunk_autotune", False):
        # crash/restore through AUTOTUNED fused chunk ticks: the chunk
        # size is re-chosen per admission, so a restore mid-prefill may
        # resume at a different bucket — the zero-loss contract must
        # not care (tokens are the state, the cursor is volatile)
        kw.update(chunk_autotune=True,
                  slo_tpot_s=getattr(ns, "slo_tpot_s", 0.25))
    return kw


def build_engine(model, ns, flight_dump, speculate=None):
    from paddle_tpu import serving

    return serving.ServingEngine(
        model, **engine_kwargs(ns, flight_dump, speculate))


def drive_chaos(model, eng, ns, reqs, arrivals, snap_root,
                speculate=None):
    """Open-loop drive with crash/restore: any exception out of
    ``step()`` (an injected fault, a simulated device OOM) snapshots
    the engine through the integrity-manifest path, closes it, and
    resumes on a restored engine. Returns
    (engine, accepted_ids, rejected, restores, wall_s)."""
    from paddle_tpu import serving

    from paddle_tpu.analysis import runtime as rt_guard

    n = len(reqs)
    i = rejected = restores = tick = 0
    accepted = []
    t0 = time.perf_counter()
    while i < n or not eng.idle:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            r = reqs[i]
            try:
                rid = eng.submit(serving.Request(
                    r["prompt"], max_new_tokens=r["budget"],
                    priority=r.get("priority", "normal"),
                    deadline_s=r.get("deadline")))
                accepted.append(rid)
            except serving.Rejected:
                rejected += 1
            i += 1
        if eng.idle and i < n:
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
            continue
        try:
            eng.step()
        except Exception as e:      # noqa: BLE001 — chaos is the point
            print(f"# crash #{restores + 1}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            eng.save_snapshot(snap_root)
            eng.close()
            # the draft proposer's model doesn't serialize — hand the
            # SAME SpecConfig back as a restore override (a no-op for
            # ngram/None, which restore rebuilds from the snapshot);
            # snapshots are likewise mesh-free, so a sharded soak hands
            # its mesh/layout back or the restored engine would come
            # back single-device
            ovr = {"speculate": speculate} if speculate is not None else {}
            if getattr(eng, "mesh", None) is not None:
                ovr["mesh"] = eng.mesh
                ovr["layout"] = eng.layout
            eng = type(eng).restore(model, snap_root, **ovr)
            restores += 1
        tick += 1
        if ns.roundtrip_every and tick % ns.roundtrip_every == 0:
            # state-protocol sanitizer: snapshot -> restore -> snapshot
            # must be byte-identical mid-soak; SnapshotDriftError
            # propagates (deliberately outside the chaos catch) and
            # exits the bench non-zero
            rt_guard.snapshot_roundtrip(eng)
    return eng, accepted, rejected, restores, time.perf_counter() - t0


def drive_chaos_router(rt, ns, reqs, arrivals):
    """Open-loop drive of the replicated tier with whole-replica kills:
    every ``--kill_replica_every`` router ticks a live replica is
    killed abruptly (device state, queue, slots and uncollected results
    dropped — the process-kill analog), alternating the restore path
    (snapshots intact) with the redistribute path (the victim's
    snapshot directory wiped first, so failover must re-place its
    journaled requests onto the survivors). Engine-level faults
    (``--fault_every``) still fire inside replica ticks — the router
    absorbs those as replica step-crashes, never a driver crash.
    Returns (accepted_ids, rejected, kills, wall_s)."""
    from paddle_tpu import serving
    from paddle_tpu.analysis import runtime as rt_guard

    n = len(reqs)
    i = rejected = kills = 0
    kill_cursor = roundtrip_cursor = 0
    accepted = []
    tick = 0
    t0 = time.perf_counter()
    while i < n or not rt.idle:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            r = reqs[i]
            try:
                rid = rt.submit(serving.Request(
                    r["prompt"], max_new_tokens=r["budget"],
                    priority=r.get("priority", "normal"),
                    deadline_s=r.get("deadline")))
                accepted.append(rid)
            except serving.Rejected:
                rejected += 1
            i += 1
        if rt.idle and i < n:
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
            continue
        rt.step()
        tick += 1
        if ns.roundtrip_every and tick % ns.roundtrip_every == 0:
            live = rt.live_replicas
            if live:
                # round-robin the roundtrip sanitizer over live
                # replicas; drift propagates and fails the bench. A
                # cross-process replica runs the check INSIDE its
                # worker (the twin engine must live beside the real
                # one); SnapshotDriftError keeps its type through the
                # RPC error envelope.
                victim = live[roundtrip_cursor % len(live)]
                roundtrip_cursor += 1
                veng = rt.replica_engine(victim)
                if hasattr(veng, "snapshot_roundtrip"):
                    veng.snapshot_roundtrip()
                else:
                    rt_guard.snapshot_roundtrip(veng)
        if ns.kill_replica_every and tick % ns.kill_replica_every == 0 \
                and kills < ns.max_kills:
            live = rt.live_replicas
            if len(live) > 1:
                victim = live[kill_cursor % len(live)]
                kill_cursor += 1
                mode = "redistribute" if kills % 2 else "restore"
                if mode == "redistribute":
                    # wipe the victim's snapshots: failover MUST take
                    # the journal re-placement path
                    root = rt.replica_snapshot_root(victim)
                    if root:
                        shutil.rmtree(root, ignore_errors=True)
                print(f"# kill #{kills + 1}: replica {victim} "
                      f"(forcing {mode}, {ns.kill_mode})",
                      file=sys.stderr)
                rt.kill_replica(victim, mode=ns.kill_mode)
                kills += 1
    return accepted, rejected, kills, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-tiny")
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--block_tokens", type=int, default=16)
    ap.add_argument("--max_seq_len", type=int, default=None)
    ap.add_argument("--min_prompt", type=int, default=6)
    ap.add_argument("--max_prompt", type=int, default=20)
    ap.add_argument("--min_new", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=16)
    ap.add_argument("--load", type=float, default=1.5,
                    help="offered load as a multiple of calibrated "
                    "capacity (>1 = deliberate overload)")
    ap.add_argument("--fault_every", type=int, default=25,
                    help="fire a fault every N decode.dispatch calls "
                    "(alternating raise / RESOURCE_EXHAUSTED)")
    ap.add_argument("--max_faults", type=int, default=4)
    ap.add_argument("--max_queue", type=int, default=8)
    ap.add_argument("--priority_mix", default="low:1,normal:2,high:1")
    ap.add_argument("--deadline_frac", type=float, default=0.25,
                    help="fraction of requests carrying a --deadline_s "
                    "deadline (the infeasibility-shed targets)")
    ap.add_argument("--deadline_s", type=float, default=5.0)
    ap.add_argument("--cache_int8", action="store_true")
    ap.add_argument("--chunk_tokens", type=int, default=None,
                    help="arm chunked prefill (multiple of "
                    "--block_tokens): the zero-loss exit contract then "
                    "also covers crashes landing MID-PREFILL — a "
                    "chunked slot snapshots as a resumable request "
                    "with its chunk cursor and re-prefills losslessly")
    ap.add_argument("--chunk_autotune", action="store_true",
                    help="autotune the chunk size per admission "
                    "against --slo_tpot_s (chaos coverage: crash/"
                    "restore with the tuner mid-flight)")
    ap.add_argument("--slo_tpot_s", type=float, default=0.25,
                    help="TPOT budget the chunk autotuner fits fused "
                    "ticks under")
    ap.add_argument("--speculate", type=int, default=0,
                    help="arm speculative decoding (k proposals per "
                    "slot per tick): the zero-loss + token-parity exit "
                    "contract then also covers crashes landing on a "
                    "speculative tick (accepted tokens survive, "
                    "in-flight speculation is recomputed)")
    ap.add_argument("--proposer", choices=("ngram", "draft"),
                    default="ngram")
    ap.add_argument("--draft_model", default="llama-tiny")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run the replicated tier: N engine replicas "
                    "behind serving.Router (1 = single engine, the "
                    "pre-router behavior). The zero-loss exit contract "
                    "then covers WHOLE-REPLICA death: kills alternate "
                    "the snapshot-restore and journal-redistribute "
                    "failover paths")
    ap.add_argument("--kill_replica_every", type=int, default=0,
                    help="router mode: abruptly kill a live replica "
                    "every N router ticks (0 = no kills), up to "
                    "--max_kills")
    ap.add_argument("--max_kills", type=int, default=3)
    ap.add_argument("--processes", action="store_true",
                    help="router mode: one OS process per replica "
                    "(serving.worker.ReplicaProxy over the CRC-framed "
                    "transport). The zero-loss exit contract then "
                    "covers REAL process death — --kill_mode sigkill "
                    "sends an actual SIGKILL mid-step — plus torn-"
                    "frame transport faults (--transport_fault_every)")
    ap.add_argument("--kill_mode", choices=("close", "sigkill"),
                    default="close",
                    help="how --kill_replica_every kills: 'close' "
                    "drops the engine in-process; 'sigkill' "
                    "(--processes only) sends a real SIGKILL armed to "
                    "land mid-step")
    ap.add_argument("--transport_fault_every", type=int, default=0,
                    help="processes mode: raise an injected "
                    "TransportCorruption (torn frame) at every Nth "
                    "transport.recv, alternating a single torn frame "
                    "(the CRC rejection -> idempotent retry path) with "
                    "a burst long enough to exhaust the retry budget "
                    "(broken proxy -> reap -> failover)")
    ap.add_argument("--max_transport_faults", type=int, default=2)
    ap.add_argument("--snapshot_every", type=int, default=8,
                    help="router mode: round-robin one replica "
                    "snapshot through the integrity-manifest path "
                    "every N router ticks")
    ap.add_argument("--roundtrip_every", type=int, default=0,
                    help="run the snapshot_roundtrip sanitizer every N "
                    "driver ticks (0 = off): snapshot -> restore -> "
                    "snapshot must be byte-identical in canonical form "
                    "mid-soak; any drift exits non-zero (router mode "
                    "round-robins the check over live replicas)")
    ap.add_argument("--verify", type=int, default=3,
                    help="completed requests spot-checked token-exact "
                    "against isolated generate (greedy only)")
    ap.add_argument("--swap_fault_every", type=int, default=0,
                    help="fire an offload.swap fault every N swap "
                    "attempts (needs --offload), up to "
                    "--max_swap_faults: even slots inject a RAISING "
                    "fault — the swap must downgrade to the legacy "
                    "recompute / token-exact-replay resume with zero "
                    "loss; odd slots a hang INSIDE the swap window, so "
                    "a --kill_mode sigkill can land MID-SWAP (device "
                    "and host tiers must both stay consistent)")
    ap.add_argument("--max_swap_faults", type=int, default=4)
    ap.add_argument("--snapshot_dir", default=None)
    ap.add_argument("--flight_dump", default=None)
    add_offload_args(ap)
    add_mesh_args(ap)
    add_timeline_arg(ap)
    ap.add_argument("--seed", type=int, default=0)
    ns = ap.parse_args()

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    dev = jax.devices()[0]
    cfg, model = build_model(ns.model)
    ns.vocab = cfg.vocab_size
    if ns.max_seq_len is None:
        need = ns.max_prompt + ns.max_new
        ns.max_seq_len = -(-need // ns.block_tokens) * ns.block_tokens

    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.resilience import faults

    snap_root = ns.snapshot_dir or tempfile.mkdtemp(prefix="chaos_snap_")
    flight_dump = ns.flight_dump or os.path.join(snap_root,
                                                 "flight.jsonl")

    rng = np.random.RandomState(ns.seed)
    reqs = make_requests(ns, rng)
    for r in reqs:      # only a fraction carries a deadline
        if rng.rand() >= ns.deadline_frac:
            r["deadline"] = None

    speculate = build_speculate(ns)
    if ns.swap_fault_every and not ns.offload:
        raise SystemExit("--swap_fault_every needs --offload")
    if ns.processes and ns.replicas < 2:
        raise SystemExit("--processes needs --replicas >= 2")
    if ns.kill_mode == "sigkill" and not ns.processes:
        raise SystemExit("--kill_mode sigkill needs --processes (an "
                         "in-process replica has no pid to SIGKILL)")
    if ns.transport_fault_every and not ns.processes:
        raise SystemExit("--transport_fault_every needs --processes")
    if ns.processes:
        import functools

        from serving_bench import build_model_only
        ekw = engine_kwargs(ns, flight_dump, speculate)
        ekw.pop("flight_dump_path")     # router forwards its own
        for k in ("mesh", "speculate"):     # in-process-only knobs
            if ekw.get(k) is not None:
                raise SystemExit(f"--processes does not support {k}")
            ekw.pop(k, None)
        eng = serving.Router(
            None, replicas=ns.replicas, processes=True,
            model_factory=functools.partial(build_model_only, ns.model),
            root=snap_root, snapshot_every=ns.snapshot_every,
            flight_dump_path=flight_dump, **ekw)
    elif ns.replicas > 1:
        ekw = engine_kwargs(ns, flight_dump, speculate)
        ekw.pop("flight_dump_path")     # router forwards its own
        eng = serving.Router(
            model, replicas=ns.replicas, root=snap_root,
            snapshot_every=ns.snapshot_every,
            flight_dump_path=flight_dump, **ekw)
    else:
        eng = build_engine(model, ns, flight_dump, speculate)
    # calibration runs unshedded (the saturated closed-loop warmup
    # would shed itself against the bounded queue)
    if ns.replicas > 1:
        eng.set_overload_controls(max_queue=None, shed_infeasible=False)
    else:
        eng.shed_infeasible = False
        eng.max_queue = None
    calibrate(eng, reqs)
    eng.reset_stats()
    eng.results.clear()
    cap_tok_s, cap_rps = calibrate(eng, reqs)
    eng.reset_stats()
    eng.results.clear()
    if ns.replicas > 1:
        eng.set_overload_controls(max_queue=ns.max_queue,
                                  shed_infeasible=True)
    else:
        eng.shed_infeasible = True
        eng.max_queue = ns.max_queue
    print(f"# calibrated capacity: {cap_tok_s:.1f} tokens/s "
          f"~ {cap_rps:.2f} req/s; offering {ns.load:g}x",
          file=sys.stderr)

    # offload.swap chaos (--swap_fault_every; needs --offload): even
    # slots a RAISING fault, which the engine absorbs by downgrading
    # that swap to the legacy recompute / token-exact-replay resume
    # (never a step crash); odd slots a hang dwelling INSIDE the swap
    # window — the spot where --kill_mode sigkill lands mid-swap
    swap_specs = [
        {"site": "offload.swap",
         "kind": ("raise" if k % 2 == 0 else "hang"),
         "at": (k + 1) * ns.swap_fault_every,
         **({"seconds": 0.2} if k % 2 else {})}
        for k in range(ns.max_swap_faults if ns.swap_fault_every else 0)]
    if ns.processes:
        # engine-level faults live IN the workers — ship the schedule
        # over the arm_faults RPC so each worker fires its own
        # decode.dispatch crashes (a worker step crash rides the typed
        # error envelope back and lands in the router's step-crash →
        # failover path, same accounting as in-process). The parent
        # plan carries the TRANSPORT faults: the wire is parent-side.
        wspecs = [
            {"site": "decode.dispatch",
             "kind": ("raise" if k % 2 == 0 else "resource_exhausted"),
             "at": (k + 1) * ns.fault_every}
            for k in range(ns.max_faults)]
        for ri in eng.live_replicas:
            eng.replica_engine(ri).arm_faults(wspecs + swap_specs)
        pfaults = []
        if ns.transport_fault_every:
            from paddle_tpu.serving.transport import TransportCorruption
            burst = eng.retry_policy.max_attempts + 1
            for k in range(ns.max_transport_faults):
                # even slots: ONE torn frame (CRC rejection — an
                # idempotent retry absorbs it); odd slots: a burst
                # outlasting the retry budget (exhaustion → broken
                # proxy → reap → failover)
                pfaults.append(faults.Fault(
                    "transport.recv", kind="raise",
                    at=(k + 1) * ns.transport_fault_every,
                    count=(1 if k % 2 == 0 else burst),
                    exc=TransportCorruption(
                        "injected: torn frame (chaos)")))
        plan = faults.FaultPlan(*pfaults)
    else:
        plan = faults.FaultPlan(
            *([faults.Fault("decode.dispatch",
                            kind=("raise" if k % 2 == 0
                                  else "resource_exhausted"),
                            at=(k + 1) * ns.fault_every)
               for k in range(ns.max_faults)]
              + [faults.Fault(s["site"], kind=s["kind"], at=s["at"],
                              **{k2: v for k2, v in s.items()
                                 if k2 not in ("site", "kind", "at")})
                 for s in swap_specs]))
    faults.arm(plan)
    arrivals = gen_arrivals(ns.requests, ns.load * cap_rps, "poisson",
                            rng)
    from paddle_tpu.analysis.runtime import SnapshotDriftError

    kills = 0
    failovers = None
    try:
        if ns.replicas > 1:
            accepted, rejected, kills, wall = drive_chaos_router(
                eng, ns, reqs, arrivals)
            failovers = eng.router_stats["failovers"]
            restores = failovers
        else:
            eng, accepted, rejected, restores, wall = drive_chaos(
                model, eng, ns, reqs, arrivals, snap_root, speculate)
    except SnapshotDriftError as e:
        # the exit contract: a snapshot that does not restore
        # byte-identically is state-protocol corruption, not chaos
        print(f"# SNAPSHOT ROUNDTRIP DRIFT: {e}", file=sys.stderr)
        sys.exit(3)
    finally:
        faults.disarm()

    # ---- the contract ----------------------------------------------------
    lost = [rid for rid in accepted if rid not in eng.results]
    finishes = {}
    for rid in accepted:
        if rid in eng.results:
            f = eng.results[rid].finish
            finishes[f] = finishes.get(f, 0) + 1
    shed = rejected + finishes.get("shed", 0)
    fired = len(plan.fired())
    # offload.swap faults are ABSORBED by design — the engine
    # downgrades the faulted swap to the legacy recompute/replay resume
    # instead of crashing the step — so they never demand a restore and
    # must not trip the fired-but-no-restore gate below
    absorbed = sum(1 for f in plan.fired() if f.site == "offload.swap")
    if ns.processes:
        # worker-side fires (decode.dispatch inside replicas). A killed
        # worker takes its count with it — telemetry undercount, never
        # an overcount, so the fired-but-no-restore gate stays sound.
        fired += sum(eng.replica_engine(ri).faults_fired()
                     for ri in eng.live_replicas)
        if ns.swap_fault_every:
            # the worker fire count is one opaque total (absorbed swap
            # fires can't be separated out), so the crash-path gate is
            # waived for this mode — the zero-loss gate still holds
            absorbed = fired
    # whole-run marker census: the auto-dump file spans every engine
    # incarnation (each crash + each restore dumped); the live ring only
    # covers the last one
    markers = {"preempted": 0, "shed": 0, "restore": 0}

    def _count(evt):
        if evt.get("kind") == "restore":
            markers["restore"] += 1
        markers["preempted"] += len(evt.get("preempted", []))
        markers["shed"] += len(evt.get("shed", []))

    if os.path.isfile(flight_dump):
        seen = set()
        with open(flight_dump) as f:
            for ln in f:
                evt = json.loads(ln)
                if evt.get("kind") == "flight_dump":
                    continue
                # dumps overlap (each snapshots the whole ring): dedup
                # step events by (step, ts), markers by ts
                key = (evt.get("step"), evt.get("kind"), evt.get("ts"))
                if key in seen:
                    continue
                seen.add(key)
                _count(evt)
    else:
        for evt in eng.flight.events():
            _count(evt)

    # trace-continuity gate (router mode): every accepted request's
    # journal events must form ONE connected trace_id chain — a
    # failover/drain migration that re-minted (or dropped) the id is an
    # orphan fragment and fails the run with exit code 4
    journal_path = (os.path.join(snap_root, "journal.jsonl")
                    if ns.replicas > 1 else None)
    trace_problems = []
    if journal_path and os.path.isfile(journal_path):
        from paddle_tpu.observability.timeline import \
            verify_trace_continuity
        from paddle_tpu.serving.journal import RouterJournal
        events, _corrupt = RouterJournal.replay(journal_path)
        trace_problems = verify_trace_continuity(
            events, accepted_rids=accepted, require_finish=True)
    tfields = timeline_fields(ns, eng, journal_path=journal_path)

    parity_checked = 0
    if ns.verify and eng.temperature == 0.0:
        from paddle_tpu.inference import generate
        done = [rid for rid in accepted
                if rid in eng.results
                and eng.results[rid].finish in ("eos", "length")]
        rng.shuffle(done)
        for rid in done[:ns.verify]:
            res = eng.results[rid]
            ref = np.asarray(generate(
                model, res.prompt[None],
                max_new_tokens=len(res.tokens), temperature=0.0,
                cache_dtype=jnp.int8 if ns.cache_int8
                else jnp.bfloat16))[0, len(res.prompt):]
            if res.tokens.tolist() != ref.tolist():
                print(f"# PARITY FAILURE request {rid}: finish={res.finish} "
                      f"got={res.tokens.tolist()} ref={ref.tolist()}",
                      file=sys.stderr)
                sys.exit(2)
            parity_checked += 1

    reg = obs.registry()
    ofields = offload_fields(eng, ns)
    swaps = (0, 0)
    if ofields:
        if ns.replicas == 1:
            # each restore rebuilds the engine with fresh stats — the
            # whole-run swap byte totals ride the registry the way
            # preemptions does (router mode absorbs retired-engine
            # stats itself)
            ofields.update(
                swap_out_bytes=int(reg.counter_total(
                    "serving.offload.swap_out_bytes")),
                swap_in_bytes=int(reg.counter_total(
                    "serving.offload.swap_in_bytes")))
        st_all = eng.stats
        swaps = (max(int(st_all.get("swap_outs", 0)),
                     int(reg.counter_total("serving.offload.swap_outs"))),
                 max(int(st_all.get("swap_ins", 0)),
                     int(reg.counter_total("serving.offload.swap_ins"))))
    rec = obs.bench_record(
        f"{ns.model} chaos soak {ns.load:g}x survivors",
        float(len(accepted) - len(lost)), "requests",
        device=dev.device_kind, timing="wall",
        load_mult=ns.load, n_requests=ns.requests,
        offered_rps=round(ns.load * cap_rps, 4),
        faults_fired=fired, restores=restores,
        replicas=ns.replicas, replica_kills=kills,
        failovers=failovers,
        preemptions=reg.counter_total("serving.preemptions"),
        chunk_tokens=ns.chunk_tokens,
        # registry counter, not engine stats: each restore rebuilds the
        # engine with fresh stats — the whole-run chunk count must
        # survive the crash/restore loop like preemptions does
        prefill_chunks=reg.counter_total("serving.prefill_chunks"),
        shed_rate=round(shed / ns.requests, 4),
        # registry counter (survives engine restores, spans replicas)
        roundtrip_checks=reg.counter_total(
            "serving.snapshot_roundtrips"),
        lost_requests=len(lost), finishes=finishes,
        flight_markers=markers, parity_checked=parity_checked,
        **ofields,
        **({"tier_prefix_hit_rate": round(eng.tier_prefix_hit_rate, 4)}
           if ns.replicas > 1 else {}),
        **mesh_fields(ns, build_engine_mesh(ns)), **tfields,
        wall_s=round(wall, 3))
    print(json.dumps(rec))
    eng.close()
    if ns.snapshot_dir is None:
        shutil.rmtree(snap_root, ignore_errors=True)

    if lost:
        print(f"# LOST {len(lost)} accepted requests: {lost}",
              file=sys.stderr)
        sys.exit(1)
    if fired - absorbed > 0 and restores == 0:
        print("# faults fired but no restore happened — the chaos path "
              "was not exercised", file=sys.stderr)
        sys.exit(1)
    if ns.replicas > 1 and ns.kill_replica_every:
        if kills == 0:
            print("# kill schedule armed but no replica was killed — "
                  "the replica-death path was not exercised",
                  file=sys.stderr)
            sys.exit(1)
        if failovers < kills:
            print(f"# {kills} kills but only {failovers} failovers — "
                  f"a dead replica was never rebuilt", file=sys.stderr)
            sys.exit(1)
    if trace_problems:
        for p in trace_problems[:10]:
            print(f"# TRACE CHAIN BROKEN: {p}", file=sys.stderr)
        print(f"# {len(trace_problems)} trace-continuity problem(s) — "
              f"a request's journal events do not form one connected "
              f"trace_id chain", file=sys.stderr)
        sys.exit(4)
    if ns.offload:
        print(f"# offload: {swaps[0]} swap-outs / {swaps[1]} swap-ins "
              f"({len(swap_specs)} swap faults armed)", file=sys.stderr)
    print(f"# zero loss across {restores} restores / {fired} faults"
          + (f" / {kills} replica kills" if kills else "")
          + f"; shed {shed}/{ns.requests}, parity x{parity_checked} OK",
          file=sys.stderr)


if __name__ == "__main__":
    main()
