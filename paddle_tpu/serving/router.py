"""Replicated serving tier: N engine replicas behind one fault-tolerant
router (docs/SERVING.md §Replicated tier).

One ``ServingEngine`` is overload-safe and crash-recoverable (PR 8),
but it is still ONE engine: a dead engine takes its queue and in-flight
slots with it, and nothing notices. This module is the data-parallel
tier on top — the serving analog of the reference's Fleet/elastic layer
(ElasticManager heartbeats + coordination-service membership, PAPER.md):
a :class:`Router` owns N in-process ``ServingEngine`` replicas behind
one submit/step/drain surface and keeps the tier serving through
replica death, drain and growth.

Three mechanisms:

* **Placement** — prefix-affinity first: the block-aligned prompt
  prefix is content-hashed (the same full-block rule the
  ``PrefixCache`` keys by) and routed to a stable replica slot, so
  repeat prefixes land where their KV blocks already live. Ties (no
  full prefix block) and overloaded affinity targets fall back to
  least-loaded, ordered by each replica's public
  ``estimated_ttft_s(request, default=0.0)`` (cold = maximally
  available, the documented convention) and its
  ``serving.pool_blocks_*`` occupancy. A replica that sheds
  (``Rejected``) just means "try the next one"; only when EVERY
  placeable replica sheds does the router raise
  ``Rejected(reason="tier_saturated")`` — tier-level typed shedding.
* **Health + zero-loss failover** — every router tick heartbeats each
  live replica through the ``router.heartbeat`` fault site
  (``resilience.faults.KNOWN_SITES``): a raising fault IS a missed
  heartbeat, and consecutive misses drive the per-replica state
  machine healthy → suspect → dead (a closed engine, or an exception
  out of ``replica.step()``, is declared dead immediately). A dead
  replica is rebuilt zero-loss: restore from its last
  ``save_snapshot()`` if the integrity manifest verifies, else
  RE-PLACE every journaled accepted request — with its
  generated-so-far tokens through the PR 8 token-exact resume path
  (``ServingEngine.admit_resumable``) — onto surviving replicas.
  Either way the final tokens are bit-identical to an unfailed run:
  resume continues each request's own ``fold_in(seed, count)`` stream,
  and a from-scratch re-run is the same pure function of
  (prompt, seed, sampling config).
* **Elastic drain / growth** — :meth:`Router.drain_replica` stops
  placement to a replica, snapshots it, migrates its in-flight and
  queued work onto the survivors (same resume path) and removes it;
  :meth:`Router.add_replica` joins a new replica warm (its prefill +
  step programs compiled before it takes traffic). The tier scales
  under load without dropping a request.

**The durable request journal.** With a ``root`` directory configured
the router appends every accept / placement / progress / finish to an
append-only CRC-framed JSONL journal (``paddle_tpu.router_journal/v1``)
through the shared ``RetryPolicy``, and snapshots replicas round-robin
every ``snapshot_every`` ticks through the PR 4 integrity-manifest
commit path. Replica death is survived from the in-memory mirror of
that journal; a whole-ROUTER crash is survived by
:meth:`Router.recover`, which replays the journal (skipping corrupt
lines — ``resilience.journal_corrupt_skipped``), restores every replica
whose snapshot verifies and re-places the rest. ``root=None`` runs the
tier memory-only: replica failover still loses nothing (the router
process is alive), only router-process durability is waived.
"""

import hashlib
import logging
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu.serving.engine import (DrainTimeout, Rejected, Request,
                                       RequestResult, RestoreError,
                                       ServingEngine)
from paddle_tpu.serving.journal import (ROUTER_JOURNAL_SCHEMA,
                                        RouterJournal)
from paddle_tpu.serving.pool import (PoolExhausted, TierPrefixStore,
                                     chain_keys)

logger = logging.getLogger("paddle_tpu.serving")

__all__ = ["Router", "RouterJournal", "ROUTER_JOURNAL_SCHEMA",
           "REPLICA_STATES", "REPLICA_ROLES", "ReplicaRole"]

#: replica health states. healthy/suspect take placements (suspect only
#: when no healthy replica can), draining serves but takes none, dead is
#: awaiting failover, removed is a retired slot (kept so prefix-affinity
#: hashing stays stable as the tier grows).
REPLICA_STATES = ("healthy", "suspect", "dead", "draining", "removed")
_STATE_RANK = {s: i for i, s in enumerate(REPLICA_STATES)}


def _accelerator_held() -> Optional[str]:
    """The accelerator platform this process's JAX has already
    initialised (and so holds), or None. Never initialises a backend."""
    import jax
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


class ReplicaRole:
    """Splitwise/DistServe-style role disaggregation: a ``prefill``
    replica takes fresh admissions and releases each request at first
    token; a ``decode`` replica takes the migrated resume work;
    ``mixed`` (the default) does both. Placement filters candidates by
    the request's phase and FALLS BACK to any placeable replica rather
    than strand work — roles are a routing preference, never a
    correctness gate (migration rides the token-exact resume path, so
    a roled run is bit-identical to a mixed one)."""

    PREFILL = "prefill"
    DECODE = "decode"
    MIXED = "mixed"


REPLICA_ROLES = (ReplicaRole.PREFILL, ReplicaRole.DECODE,
                 ReplicaRole.MIXED)


class _Tracked:
    """Router-side mirror of one accepted request — everything needed
    to re-place it token-exactly if its replica dies."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "seed", "priority",
                 "deadline_s", "t_accept", "replica", "tokens",
                 "finished", "journaled_tokens", "trace_id")

    def __init__(self, rid, prompt, max_new_tokens, seed, priority,
                 deadline_s, replica, trace_id=None):
        self.rid = rid
        self.trace_id = trace_id        # causal chain key, accept-minted
        self.prompt = prompt            # np.int32 host ids
        self.max_new_tokens = max_new_tokens
        self.seed = seed
        self.priority = priority
        self.deadline_s = deadline_s
        self.t_accept = time.perf_counter()
        self.replica = replica
        self.tokens: List[int] = []     # last observed generated prefix
        self.finished = False
        self.journaled_tokens = 0       # progress length last journaled

    def remaining_deadline(self) -> Optional[float]:
        if self.deadline_s is None:
            return None
        return max(self.deadline_s
                   - (time.perf_counter() - self.t_accept), 1e-9)

    def as_request(self) -> Request:
        # trace_id rides along: a failover/drain re-placement is the
        # SAME causal request — its chain must not fork at migration
        return Request(self.prompt, self.max_new_tokens, seed=self.seed,
                       deadline_s=self.remaining_deadline(),
                       priority=self.priority, request_id=self.rid,
                       trace_id=self.trace_id)


class _Replica:
    __slots__ = ("engine", "state", "misses", "root", "role")

    def __init__(self, engine, root, role: str = ReplicaRole.MIXED):
        self.engine = engine
        self.state = "healthy"
        self.misses = 0
        self.root = root
        self.role = role


class Router:
    """N in-process ``ServingEngine`` replicas behind one
    submit/step/drain surface (module docstring has the design).

    ``replicas`` engines are built from ``model`` + ``engine_kwargs``
    (every constructor knob ``ServingEngine`` takes), sharing one
    inference state dict so N replicas don't hold N weight copies.
    ``root`` arms durability: the request journal at
    ``<root>/journal.jsonl`` plus per-replica snapshot roots
    ``<root>/replica_<i>`` written round-robin every ``snapshot_every``
    ticks. ``suspect_after``/``dead_after`` are the consecutive
    missed-heartbeat thresholds of the health state machine;
    ``retry_policy`` (PR 4 ``RetryPolicy``) governs journal appends and
    snapshot commits. The router duck-types the engine's bench surface
    (``submit``/``step``/``drain``/``results``/``stats``/``idle``/
    ``close``), so the serving benches drive either interchangeably."""

    def __init__(self, model, *, replicas: int = 2, state=None,
                 root: Optional[str] = None,
                 suspect_after: int = 1, dead_after: int = 3,
                 snapshot_every: Optional[int] = 16,
                 journal_progress_every: int = 8,
                 retry_policy=None,
                 affinity_overload_factor: float = 4.0,
                 rebuild_dead: bool = True,
                 flight_capacity: int = 256,
                 flight_dump_path: Optional[str] = None,
                 watchdog=None,
                 processes: bool = False,
                 model_factory=None,
                 roles: Optional[Sequence[str]] = None,
                 rpc_timeout_s: float = 180.0,
                 heartbeat_timeout_s: float = 10.0,
                 start_timeout_s: float = 300.0,
                 tier_prefix_blocks: Optional[int] = 256,
                 seed: int = 0, **engine_kwargs):
        from paddle_tpu.inference import _inference_state
        from paddle_tpu.observability.flight import FlightRecorder
        from paddle_tpu.resilience.retry import RetryPolicy

        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if suspect_after < 1 or dead_after < suspect_after:
            raise ValueError(
                f"need 1 <= suspect_after <= dead_after, got "
                f"suspect_after={suspect_after} dead_after={dead_after}")
        self.processes = bool(processes)
        self.model_factory = model_factory
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.start_timeout_s = float(start_timeout_s)
        if self.processes:
            if model_factory is None:
                raise ValueError(
                    "processes=True requires model_factory= (a picklable "
                    "zero-arg callable; each worker builds its OWN model "
                    "— weights must be deterministic so replicas agree)")
            held = _accelerator_held()
            if held is not None:
                raise RuntimeError(
                    f"Router(processes=True): this process has already "
                    f"initialised JAX on platform {held!r} and therefore "
                    f"holds the chip; a chip belongs to one process, so no "
                    f"worker could reach it (it would wait out "
                    f"start_timeout_s or come up on the CPU). Start the "
                    f"process tier from a parent that never touches JAX, "
                    f"or use in-process replicas (processes=False)")
            for k in ("mesh", "layout", "speculate"):
                if engine_kwargs.get(k) is not None:
                    raise ValueError(
                        f"processes=True does not support engine kwarg "
                        f"{k!r} yet — run mesh/speculative replicas "
                        f"in-process")
        elif model is None:
            raise ValueError("model is required for in-process replicas "
                             "(processes=False)")
        if roles is None:
            roles = [ReplicaRole.MIXED] * replicas
        roles = [str(r) for r in roles]
        if len(roles) != replicas:
            raise ValueError(f"roles must name one role per replica: "
                             f"got {len(roles)} for {replicas} replicas")
        for r in roles:
            if r not in REPLICA_ROLES:
                raise ValueError(f"unknown replica role {r!r}; one of "
                                 f"{REPLICA_ROLES}")
        self.model = model
        if state is not None:
            self._state = state
        else:
            # processes mode: the workers build their own models and
            # inference state; the parent never touches device weights
            self._state = (None if self.processes
                           else _inference_state(model))
        # tpu-lint: volatile(constructor config — recover() rebuilds it
        # from router_kwargs; set_overload_controls re-arms post-bench)
        self._engine_kwargs = dict(engine_kwargs)
        # one postmortem file for the whole tier: replica engines
        # inherit the router's dump path unless given their own, so
        # engine-level preempt/shed/restore markers land beside the
        # router's failover/kill markers
        if flight_dump_path is not None \
                and "flight_dump_path" not in self._engine_kwargs:
            self._engine_kwargs["flight_dump_path"] = flight_dump_path
        self.seed = int(seed)
        self._seeds_issued = 0
        self.root = root
        self.suspect_after = int(suspect_after)
        self.dead_after = int(dead_after)
        self.snapshot_every = (int(snapshot_every)
                               if snapshot_every else None)
        self.journal_progress_every = max(int(journal_progress_every), 1)
        self.retry_policy = retry_policy or RetryPolicy()
        self.affinity_overload_factor = float(affinity_overload_factor)
        self.rebuild_dead = bool(rebuild_dead)
        self.journal = (RouterJournal(os.path.join(root, "journal.jsonl"),
                                      self.retry_policy)
                        if root is not None else None)
        self._replicas: List[_Replica] = []
        for i in range(replicas):
            self._replicas.append(
                _Replica(self._new_engine(i), self._replica_root(i),
                         role=roles[i]))
        self._requests: Dict[int, _Tracked] = {}
        self._open: set = set()         # accepted, not yet finished
        self.results: Dict[int, RequestResult] = {}
        # tpu-lint: volatile(recover() rebuilds orphans through
        # _queue_replace from the journal fold)
        self._pending_replace: List[_Tracked] = []
        # tpu-lint: volatile(journal/snapshot cadence counter)
        self._tick = 0
        # tpu-lint: volatile(round-robin snapshot cursor)
        self._snap_cursor = 0
        self._closed = False
        self.flight = FlightRecorder(capacity=flight_capacity,
                                     auto_dump_path=flight_dump_path,
                                     name="serving-router")
        # SLO burn-rate watchdog (observability.slo.BurnRateWatchdog):
        # checked on its own tick cadence; a trip dumps flight rings +
        # a timeline slice (docs/OBSERVABILITY.md §Burn-rate watchdog)
        self.watchdog = watchdog
        # tpu-lint: volatile(tier telemetry; the registry counters are
        # the cross-recovery accounting)
        self.router_stats = dict(
            placed=0, rejected_tier=0, heartbeat_misses=0,
            replica_deaths=0, failovers=0, replaced=0, drains=0,
            replica_kills=0, snapshots=0, prefix_shared_blocks=0)
        # tpu-lint: volatile(absorbed stats of retired engines —
        # telemetry, not protocol state)
        self._stats_base: Dict[str, float] = {}
        # tpu-lint: volatile(absorbed prefix hit/lookup counters of
        # retired engines — telemetry, not protocol state)
        self._prefix_base = [0, 0]
        # the tier-wide prefix index + host payload cache
        # (docs/SERVING.md §Hierarchical KV). Losing it costs only
        # future block copies — it is rebuilt organically from
        # placements, so it lives outside the journal/snapshot protocol.
        # tpu-lint: volatile(hint index + host cache; recover() and
        # failover repopulate it from live placements)
        self._tier_prefix = (TierPrefixStore(int(tier_prefix_blocks))
                             if tier_prefix_blocks else None)
        # tpu-lint: volatile(rids mid role-migration this tick — picks
        # the journal kind for the block share at re-placement)
        self._migrating: set = set()
        if self.journal is not None:
            self.journal.append("header", schema=ROUTER_JOURNAL_SCHEMA,
                                replicas=replicas, seed=self.seed)
        self._update_gauges()

    # ------------------------------------------------------------ plumbing
    def _replica_root(self, i: int) -> Optional[str]:
        return (os.path.join(self.root, f"replica_{i}")
                if self.root is not None else None)

    def _new_engine(self, i: int, restore_root: Optional[str] = None):
        """Build replica ``i``'s engine. Every replica's metric series
        carry a ``replica="<i>"`` label (a registry view — storage
        stays process-global), so :meth:`metrics_snapshot` can merge
        the tier and a dashboard can still tell replicas apart.
        ``processes=True`` spawns a worker process behind a
        :class:`~paddle_tpu.serving.worker.ReplicaProxy` instead —
        with ``restore_root`` the WORKER attempts the snapshot restore
        itself and reports restored/covered in its handshake."""
        if self.processes:
            from paddle_tpu.serving.worker import ReplicaProxy
            return ReplicaProxy.start(
                self.model_factory, engine_kwargs=self._engine_kwargs,
                replica=i, seed=self.seed, restore_root=restore_root,
                rpc_timeout_s=self.rpc_timeout_s,
                start_timeout_s=self.start_timeout_s,
                retry_policy=self.retry_policy)
        return ServingEngine(self.model, state=self._state,
                             seed=self.seed,
                             metrics_labels={"replica": str(i)},
                             **self._engine_kwargs)

    def _restore_overrides(self, i: int) -> Dict:
        """Overrides every replica restore needs: the live SpecConfig
        (draft models don't serialize — without this a draft-proposer
        tier could never take the restore path; restore would raise
        ``RestoreError("draft_model_missing")`` and every failover
        would silently degrade to redistribution), and the live
        mesh/layout (snapshots are mesh-free, so a sharded router's
        restored replica must be re-handed its mesh explicitly or it
        would come back single-device) — plus the replica metric label,
        which is a live-construction knob snapshots never carry."""
        out = {"metrics_labels": {"replica": str(i)}}
        for key in ("speculate", "mesh", "layout"):
            v = self._engine_kwargs.get(key)
            if v is not None:
                out[key] = v
        return out

    @property
    def num_replicas(self) -> int:
        """Replica SLOTS (incl. removed) — the stable affinity modulus."""
        return len(self._replicas)

    @property
    def live_replicas(self) -> List[int]:
        return [i for i, r in enumerate(self._replicas)
                if r.state in ("healthy", "suspect", "draining")
                and r.engine is not None and not r.engine.closed]

    def health(self) -> List[str]:
        """Per-slot health states, index-aligned with the replicas."""
        return [r.state for r in self._replicas]

    def replica_engine(self, i: int) -> Optional[ServingEngine]:
        return self._replicas[i].engine

    def replica_snapshot_root(self, i: int) -> Optional[str]:
        return self._replicas[i].root

    @property
    def temperature(self) -> float:
        return float(self._engine_kwargs.get("temperature", 0.0))

    def _update_gauges(self):
        from paddle_tpu.observability import registry
        r = registry()
        r.gauge("serving.router.replicas_live").set(
            len(self.live_replicas))
        # tier-merged prefix reuse: every replica's counters (incl.
        # retired engines' absorbed base) folded into ONE rate, plus
        # the cross-replica share rate of the tier store — the numbers
        # router-mode benches report (per-replica rates alone hid the
        # tier-level reuse picture)
        r.gauge("serving.router.prefix_hit_rate").set(
            self.prefix_hit_rate)
        if self._tier_prefix is not None:
            r.gauge("serving.router.tier_prefix_hit_rate").set(
                self._tier_prefix.hit_rate)
        for i, rep in enumerate(self._replicas):
            r.gauge("serving.router.replica_state",
                    replica=str(i)).set(_STATE_RANK[rep.state])

    # ----------------------------------------------------------- placement
    def _affinity_slot(self, prompt) -> Optional[int]:
        """Stable replica slot for a prompt's block-aligned prefix, or
        None when the prompt has no full block to share (the same
        ``(P-1)//block_tokens`` rule the ``PrefixCache`` caps lookups
        at, so affinity exists exactly when there is cacheable KV)."""
        live = self.live_replicas
        if not live:
            return None
        bt = self._replicas[live[0]].engine.block_tokens
        n_full = (len(prompt) - 1) // bt
        if n_full == 0:
            return None
        # tpu-lint: allow(host-sync): hashing host token ids (never device)
        digest = hashlib.blake2b(
            np.ascontiguousarray(prompt[:n_full * bt],
                                 dtype=np.int64).tobytes(),
            digest_size=8).digest()
        return int.from_bytes(digest, "big") % self.num_replicas

    def _placeable(self, phase: Optional[str] = None) -> List[int]:
        """Replica indices that take new placements: healthy first;
        suspect only when no healthy replica exists (a suspect replica
        is probably alive — better than shedding the tier). When
        ``phase`` is given ("prefill" / "decode"), replicas whose role
        matches it (or is mixed) are PREFERRED — but a role mismatch
        never strands work: if no role-compatible replica is placeable
        the full candidate set is returned."""
        healthy = [i for i, r in enumerate(self._replicas)
                   if r.state == "healthy" and r.engine is not None
                   and not r.engine.closed]
        base = healthy or [i for i, r in enumerate(self._replicas)
                           if r.state == "suspect" and r.engine is not None
                           and not r.engine.closed]
        if phase is None:
            return base
        pref = [i for i in base
                if self._replicas[i].role in (phase, ReplicaRole.MIXED)]
        return pref or base

    def _placement_order(self, request: Request,
                         phase: Optional[str] = None):
        """(ordered candidate indices, policy): the affinity slot first
        unless its load exceeds ``affinity_overload_factor`` x the
        least-loaded candidate, then the rest by ascending load score
        — ``estimated_ttft_s(request, default=0.0)`` (cold = maximally
        available) tie-broken by pool-block occupancy and queue
        depth, the same signals the ``serving.pool_blocks_*`` /
        ``serving.queue_depth`` gauges export."""
        cands = self._placeable(phase)
        if not cands:
            return [], "none"
        loads = {}
        for i in cands:
            eng = self._replicas[i].engine
            est = eng.estimated_ttft_s(request, default=0.0)
            pool_frac = eng.pool.used_blocks / max(
                eng.pool.num_blocks - 1, 1)
            loads[i] = (est, pool_frac, eng.queued)
        by_load = sorted(cands, key=lambda i: loads[i])
        aff = self._affinity_slot(request.prompt)
        if aff is None:
            return by_load, "least_loaded"
        # linear probe from the stable slot to the first candidate, so
        # affinity survives membership churn (a dead slot's prefixes
        # consistently land on its successor, not scattered)
        n = self.num_replicas
        aff = next(((aff + off) % n for off in range(n)
                    if (aff + off) % n in loads), None)
        if aff is None:
            return by_load, "least_loaded"
        la, lmin = loads[aff][0], loads[by_load[0]][0]
        if la > self.affinity_overload_factor * (lmin + 1e-3):
            # the affinity target is drowning while someone else is
            # near-idle: prefix reuse is not worth the queueing delay
            # (the tier prefix store then turns the lost affinity into
            # a block copy instead of a recompute — _share_prefix)
            return by_load, "least_loaded"
        return ([aff] + [i for i in by_load if i != aff]), "affinity"

    def _share_prefix(self, idx: int, prompt, *, rid=None,
                      event: str = "prefix_share") -> int:
        """Stage finished prefill blocks from the tier onto replica
        ``idx`` ahead of a placement (docs/SERVING.md §Hierarchical
        KV): the prompt's block-aligned chain keys are probed against
        the :class:`TierPrefixStore`; the leading run replica ``idx``
        lacks but a sibling (or the store's host cache) can supply is
        fetched — in-process via ``export_prefix_blocks``, cross-
        process via the ``block_fetch`` RPC — cached host-side, and
        delivered via ``import_prefix_blocks`` so the admission-time
        prefix lookup hits blocks prefilled on ANOTHER replica.
        Best-effort by construction: an evicted entry, a dead owner or
        a full pool just shortens the copied run (and trims the hint);
        the placement itself never depends on the share."""
        from paddle_tpu.observability import registry

        store = self._tier_prefix
        eng = self._replicas[idx].engine
        if store is None or eng is None or eng.closed \
                or not hasattr(eng, "import_prefix_blocks"):
            return 0
        bt = eng.block_tokens
        n_full = (len(prompt) - 1) // bt    # the PrefixCache lookup cap
        if n_full <= 0:
            return 0
        # tpu-lint: allow(host-sync): prompts are host token ids
        keys = chain_keys(np.asarray(prompt)[:n_full * bt], bt)
        store.lookup_blocks += len(keys)
        missing = store.missing_run(keys, idx)
        # the placed request prefills (or copy-adopts) these blocks on
        # idx either way — record the hint AFTER the missing-run probe
        store.note_owner(keys, idx)
        if not missing:
            return 0
        payloads: Dict[str, tuple] = {}
        fetch: List[str] = []
        for k in missing:
            hit = store.cached(k)
            if hit is not None:
                payloads[k] = hit
            else:
                fetch.append(k)
        if fetch:
            by_owner: Dict[int, List[str]] = {}
            for k in fetch:
                o = store.owner_of(k, exclude=idx)
                if o is not None:
                    by_owner.setdefault(o, []).append(k)
            for o, ks in sorted(by_owner.items()):
                src = self._replicas[o].engine
                if src is None or src.closed \
                        or not hasattr(src, "export_prefix_blocks"):
                    continue
                try:
                    out = src.export_prefix_blocks(ks)
                except Exception:   # noqa: BLE001 — best-effort fetch
                    logger.warning("router: tier prefix fetch from "
                                   "replica %d failed", o, exc_info=True)
                    continue
                for k, (depth, kv) in out.items():
                    store.put(k, depth, kv)
                    payloads[k] = (depth, kv)
                gone = [k for k in ks if k not in out]
                if gone:
                    # the owner evicted these — trim the stale hint
                    store.forget(gone, o)
        run: Dict[str, tuple] = {}
        for k in missing:
            if k not in payloads:
                break   # chain broken: a gapped copy is never hit
            run[k] = payloads[k]
        if not run:
            return 0
        try:
            imported = int(eng.import_prefix_blocks(run))
        except Exception:   # noqa: BLE001 — best-effort delivery
            logger.warning("router: tier prefix import into replica %d "
                           "failed", idx, exc_info=True)
            return 0
        if imported:
            store.hit_blocks += imported
            self.router_stats["prefix_shared_blocks"] = \
                self.router_stats.get("prefix_shared_blocks", 0) + imported
            registry().counter("serving.router.prefix_shared_blocks",
                               event=event).inc(imported)
            self.flight.mark(event, replica=idx, blocks=imported,
                             rid=rid)
            if self.journal is not None:
                if event == "migrate_blocks":
                    self.journal.append("migrate_blocks", rid=rid,
                                        replica=idx, blocks=imported)
                else:
                    self.journal.append("prefix_share", rid=rid,
                                        replica=idx, blocks=imported)
        return imported

    def submit(self, request) -> int:
        """Place a request on the tier (accepts a :class:`Request` or a
        1-D prompt) and return its id; the result lands in
        ``self.results``. Seeds are assigned by the ROUTER before
        placement, so a re-placed request reproduces its tokens
        bit-identically on any replica. Raises ``ValueError`` /
        ``PoolExhausted`` for structurally impossible requests (every
        config-identical replica would refuse) and
        ``Rejected(reason="tier_saturated")`` when every placeable
        replica sheds it."""
        from paddle_tpu.observability import registry

        if self._closed:
            raise RuntimeError("Router is closed")
        if not isinstance(request, Request):
            request = Request(request)
        if request.seed is None:
            request.seed = self.seed + self._seeds_issued
            self._seeds_issued += 1
        phase = ("decode" if getattr(request, "_resume_tokens", None)
                 else "prefill")
        order, policy = self._placement_order(request, phase)
        r = registry()
        if not order:
            self.router_stats["rejected_tier"] += 1
            r.counter("serving.router.rejected",
                      reason="tier_saturated").inc()
            raise Rejected("tier_saturated",
                           "no live replica can take placements")
        last_pool_exhausted = None
        n_pool_exhausted = 0
        for j, idx in enumerate(order):
            eng = self._replicas[idx].engine
            try:
                rid = eng.submit(request)
            except Rejected:
                continue
            except PoolExhausted as e:
                last_pool_exhausted = e
                n_pool_exhausted += 1
                continue
            t = _Tracked(rid, request.prompt, request.max_new_tokens,
                         request.seed, request.priority,
                         request.deadline_s, idx,
                         trace_id=request.trace_id)
            self._requests[rid] = t
            self._open.add(rid)
            self.router_stats["placed"] += 1
            r.counter("serving.router.placed",
                      policy=policy if j == 0 else "least_loaded").inc()
            if self.journal is not None:
                self.journal.append(
                    "accept", rid=rid, trace_id=request.trace_id,
                    prompt=[int(x) for x in request.prompt],
                    max_new_tokens=request.max_new_tokens,
                    seed=request.seed, priority=request.priority,
                    deadline_s=request.deadline_s, replica=idx)
            try:
                self._share_prefix(idx, request.prompt, rid=rid)
            except Exception:   # noqa: BLE001 — share is best-effort
                logger.warning("router: tier prefix share failed",
                               exc_info=True)
            return rid
        if n_pool_exhausted == len(order):
            # every replica said never-fits — structural, not load
            raise last_pool_exhausted
        self.router_stats["rejected_tier"] += 1
        r.counter("serving.router.rejected", reason="tier_saturated").inc()
        raise Rejected(
            "tier_saturated",
            f"all {len(order)} placeable replicas shed the request")

    # ------------------------------------------------------ health machine
    def _heartbeat(self, i: int, rep: _Replica):
        """One heartbeat probe: the ``router.heartbeat`` fault site
        (a raising fault IS a miss), then liveness (a closed engine is
        definitively dead — no grace period). Cross-process replicas
        add a WALL-CLOCK ping: a worker that does not answer inside
        ``heartbeat_timeout_s`` — hung, not just dead — is a miss, and
        an EOF (the process is gone) is declared dead immediately."""
        from paddle_tpu.observability import registry
        from paddle_tpu.resilience import faults as _faults

        ok = True
        try:
            _faults.maybe_fire("router.heartbeat")
        except Exception:   # noqa: BLE001 — injected miss, any kind
            ok = False
        if rep.engine is None or rep.engine.closed:
            self._declare_dead(i, rep, "engine_closed")
            return
        if ok and hasattr(rep.engine, "ping"):
            ok = rep.engine.ping(timeout_s=self.heartbeat_timeout_s)
            if rep.engine.closed:
                self._declare_dead(i, rep, "worker_gone")
                return
        if ok:
            rep.misses = 0
            if rep.state == "suspect":
                rep.state = "healthy"
            return
        rep.misses += 1
        self.router_stats["heartbeat_misses"] += 1
        registry().counter("serving.router.heartbeat_misses",
                           replica=str(i)).inc()
        if rep.misses >= self.dead_after:
            self._declare_dead(i, rep, "missed_heartbeats")
        elif rep.misses >= self.suspect_after \
                and rep.state == "healthy":
            rep.state = "suspect"

    def _declare_dead(self, i: int, rep: _Replica, why: str):
        from paddle_tpu.observability import registry
        if rep.state == "dead":
            return
        rep.state = "dead"
        self.router_stats["replica_deaths"] += 1
        registry().counter("serving.router.replica_deaths").inc()
        if self._tier_prefix is not None:
            # its cached blocks died with it — drop every stale hint
            self._tier_prefix.forget_replica(i)
        self.flight.mark("replica_dead", replica=i, why=why)
        logger.warning("router: replica %d declared dead (%s)", i, why)

    # ------------------------------------------------------------ failover
    def _absorb_stats(self, eng: Optional[ServingEngine]):
        """Accumulate a retiring engine's cumulative stats so the
        tier-level ``stats`` survives replica replacement."""
        if eng is None or not isinstance(getattr(eng, "stats", None),
                                         dict):
            return
        for k, v in eng.stats.items():
            if isinstance(v, (int, float)):
                self._stats_base[k] = self._stats_base.get(k, 0) + v
        pc = getattr(eng, "prefix_cache", None)
        if pc is not None:
            try:
                self._prefix_base[0] += int(pc.hit_blocks)
                self._prefix_base[1] += int(pc.lookup_blocks)
            except Exception:   # noqa: BLE001 — telemetry best-effort
                pass

    def _restore_engine(self, i: int, rep: _Replica):
        """Try to bring replica ``i`` back from its snapshot root.
        Returns ``(engine_or_None, covered_rids, mode)`` where mode is
        "restore" (the engine resumed its snapshotted slots/queue
        token-exactly) or "redistribute" (nothing restored — the caller
        re-places tracked work).  In processes mode the RESTORE RUNS IN
        THE CHILD: a fresh worker is spawned with ``restore_root`` and
        reports what it covered through the handshake, so the parent
        never deserializes worker state."""
        if self.processes:
            try:
                eng = self._new_engine(i, restore_root=rep.root)
            except Exception:   # noqa: BLE001 — spawn/handshake failed
                logger.warning("router: replica %d worker respawn "
                               "failed", i, exc_info=True)
                return None, set(), "redistribute"
            if getattr(eng, "restored", False):
                return eng, set(eng.covered), "restore"
            return eng, set(), "redistribute"
        try:
            snap = ServingEngine.load_snapshot(rep.root)
            eng = ServingEngine.restore(self.model, snap,
                                        state=self._state,
                                        **self._restore_overrides(i))
            covered = {rs["request_id"]
                       for rs in snap["slots"] + snap["queue"]}
            return eng, covered, "restore"
        except FileNotFoundError:
            return None, set(), "redistribute"   # never snapshotted
        except (RestoreError, ValueError, KeyError):
            logger.warning("router: replica %d snapshot unusable; "
                           "redistributing", i, exc_info=True)
            return None, set(), "redistribute"

    def _failover(self, i: int):
        """Rebuild dead replica ``i`` zero-loss: restore from its last
        committed-and-verified snapshot when possible (the restored
        engine resumes its own slots/queue token-exactly), else rebuild
        it empty; every tracked unfinished request the restored
        snapshot does NOT cover is re-placed with its generated-so-far
        tokens through the resume path."""
        from paddle_tpu.observability import registry

        rep = self._replicas[i]
        tracked = [t for t in self._requests.values()
                   if t.replica == i and not t.finished]
        old = rep.engine
        self._absorb_stats(old)
        if old is not None:
            try:
                old.close()
            except Exception:   # noqa: BLE001 — best-effort release
                pass
        eng = None
        covered = set()
        mode = "redistribute"
        if rep.root is not None:
            eng, covered, mode = self._restore_engine(i, rep)
        if eng is not None and mode != "restore" and not self.rebuild_dead:
            # a fresh (nothing-restored) worker came up but the tier is
            # configured to shrink on death rather than rebuild
            try:
                eng.close()
            except Exception:   # noqa: BLE001 — best-effort release
                pass
            eng = None
        if eng is None and self.rebuild_dead:
            try:
                eng = self._new_engine(i)
            except Exception:   # noqa: BLE001 — spawn/build failed
                logger.warning("router: replica %d rebuild failed; "
                               "removing from tier", i, exc_info=True)
                eng = None
        if eng is not None:
            rep.engine = eng
            rep.state = "healthy"
            rep.misses = 0
        else:
            rep.engine = None
            rep.state = "removed"
        # a request the snapshot covers is already queued for resume on
        # the restored engine; anything newer (accepted after the
        # snapshot) or uncovered re-places across the tier
        for t in tracked:
            if mode == "restore" and t.rid in covered:
                continue
            self._queue_replace(t)
        self.router_stats["failovers"] += 1
        registry().counter("serving.router.failovers", mode=mode).inc()
        self.flight.mark("failover", replica=i, mode=mode,
                         covered=len(covered), replaced=len(
                             [t for t in tracked
                              if not (mode == "restore"
                                      and t.rid in covered)]))
        if self.journal is not None:
            self.journal.append("failover", replica=i, mode=mode)
        self.flight.auto_dump("failover")

    def _queue_replace(self, t: _Tracked):
        t.replica = None
        if t not in self._pending_replace:
            self._pending_replace.append(t)

    def _drain_pending_replacements(self):
        """Re-place queued orphans onto the tier (ALL of them or raise
        only structurally — a momentary no-placeable-replica window
        just leaves them pending for the next tick)."""
        from paddle_tpu.observability import registry
        if not self._pending_replace:
            return
        still = []
        for t in self._pending_replace:
            req = t.as_request()
            phase = "decode" if t.tokens else "prefill"
            order, _ = self._placement_order(req, phase)
            if not order:
                still.append(t)
                continue
            idx = order[0]
            # ship the prompt's finished prefill blocks ahead of the
            # resume so its re-prefill is a block copy, not a recompute
            # — for a role migration this IS the block-transfer path
            # PR 19 left open (journaled as "migrate_blocks")
            try:
                self._share_prefix(
                    idx, t.prompt, rid=t.rid,
                    event=("migrate_blocks"
                           if t.rid in self._migrating
                           else "prefix_share"))
            except Exception:   # noqa: BLE001 — share is best-effort
                logger.warning("router: tier prefix share failed",
                               exc_info=True)
            self._migrating.discard(t.rid)
            # admit_resumable bypasses the overload controls: this
            # request was ACCEPTED — shedding it now would be data loss
            try:
                self._replicas[idx].engine.admit_resumable(
                    req, tokens=t.tokens)
            except Rejected:
                # the worker became unreachable between the placement
                # decision and the RPC — stay pending for the next tick
                # (its failover runs first)
                still.append(t)
                continue
            t.replica = idx
            self.router_stats["replaced"] += 1
            registry().counter("serving.router.replaced").inc()
            if self.journal is not None:
                self.journal.append("place", rid=t.rid, replica=idx,
                                    trace_id=t.trace_id,
                                    tokens=len(t.tokens))
        self._pending_replace = still

    # ------------------------------------------------------------ stepping
    def step(self) -> Dict:
        """One tier tick: heartbeat every replica, fail over the dead,
        re-place orphans, step every live replica once, mirror
        generated-so-far progress, collect finished results, and run
        the journal/snapshot cadences. Returns
        ``{"active", "queued", "finished"}`` aggregated over the tier.
        An exception out of a replica's ``step()`` is a replica-level
        event (snapshot + declare dead + failover), never a router
        crash."""
        if self._closed:
            raise RuntimeError("Router is closed")
        self._tick += 1
        finished: List[int] = []
        for i, rep in enumerate(self._replicas):
            if rep.state in ("healthy", "suspect", "draining"):
                self._heartbeat(i, rep)
        for i, rep in enumerate(self._replicas):
            if rep.state == "dead":
                self._failover(i)
        self._drain_pending_replacements()
        for i, rep in enumerate(self._replicas):
            if rep.state not in ("healthy", "suspect", "draining") \
                    or rep.engine is None or rep.engine.closed:
                continue
            if rep.engine.idle:
                continue
            try:
                out = rep.engine.step()
            except Exception as e:      # noqa: BLE001 — replica crash
                self._on_step_crash(i, rep, e)
                continue
            self._collect(i, rep, out["finished"], finished)
        self._track_progress()
        self._migrate_roles()
        self._heal_orphans()
        if self.journal is not None \
                and self._tick % self.journal_progress_every == 0:
            self._journal_progress()
        if self.snapshot_every \
                and self._tick % self.snapshot_every == 0:
            self._snapshot_next()
        active = sum(r.engine.active_slots for r in self._replicas
                     if r.engine is not None and not r.engine.closed)
        queued = sum(r.engine.queued for r in self._replicas
                     if r.engine is not None and not r.engine.closed)
        queued += len(self._pending_replace)
        self.flight.record({
            "step": self._tick, "ts": round(time.time(), 6),
            "ts_mono": round(time.perf_counter(), 6),
            "active": active, "queued": queued,
            "finished": list(finished),
            "pending_replace": len(self._pending_replace),
            "replicas": [
                {"i": i, "state": r.state, "misses": r.misses,
                 "active": (r.engine.active_slots
                            if r.engine is not None
                            and not r.engine.closed else 0),
                 "queued": (r.engine.queued
                            if r.engine is not None
                            and not r.engine.closed else 0)}
                for i, r in enumerate(self._replicas)]})
        self._update_gauges()
        if self.watchdog is not None \
                and self._tick % self.watchdog.check_every == 0:
            self.watchdog.check(source=self)
        return dict(active=active, queued=queued, finished=finished)

    def _on_step_crash(self, i: int, rep: _Replica, exc: BaseException):
        """A replica's tick died. The PR 8 contract keeps the engine's
        host scheduler state consistent across an aborted tick, so
        snapshot it NOW — failover then restores with zero recompute —
        and if even the snapshot fails, the in-memory journal mirror
        still re-places everything (redistribute path)."""
        from paddle_tpu.resilience.retry import call_with_retry

        logger.warning("router: replica %d step crashed: %s: %s",
                       i, type(exc).__name__, exc)
        self.flight.mark("replica_step_crash", replica=i,
                         err=f"{type(exc).__name__}: {exc}")
        if rep.root is not None and rep.engine is not None \
                and not rep.engine.closed:
            try:
                call_with_retry(
                    lambda: rep.engine.save_snapshot(rep.root),
                    policy=self.retry_policy, retry_on=(OSError,),
                    describe="router.snapshot")
            except Exception:   # noqa: BLE001 — fall back to re-place
                logger.warning("router: crash snapshot of replica %d "
                               "failed; will redistribute", i,
                               exc_info=True)
        self._declare_dead(i, rep, "step_exception")
        self._failover(i)

    def _rescue_shed(self, t: _Tracked, res: RequestResult,
                     exclude: int) -> bool:
        """An engine displaced a queued ACCEPTED request to make room
        for higher-priority work (``finish="shed"``). At tier level
        that is only final if the whole tier is out of room — try the
        OTHER replicas through the normal overload-controlled submit
        first: a terminal shed while a sibling replica sits idle is a
        router failure, but shedding at true tier saturation is the
        correct typed outcome (every displacement victim is strictly
        lower-priority than its displacer, so rescue chains terminate).
        Returns True when the request found a new home."""
        from paddle_tpu.observability import registry

        req = t.as_request()
        req._resume_tokens = [int(x) for x in res.tokens] or None
        phase = "decode" if req._resume_tokens else "prefill"
        order, _ = self._placement_order(req, phase)
        for idx in order:
            if idx == exclude:
                continue
            try:
                self._replicas[idx].engine.submit(req)
            except (Rejected, PoolExhausted):
                continue
            t.replica = idx
            t.tokens = [int(x) for x in res.tokens]
            self.router_stats["replaced"] += 1
            registry().counter("serving.router.replaced").inc()
            if self.journal is not None:
                self.journal.append("place", rid=t.rid, replica=idx,
                                    trace_id=t.trace_id,
                                    tokens=len(t.tokens))
            return True
        return False

    def _collect(self, i: int, rep: _Replica, finished_ids, finished):
        for rid in finished_ids:
            res = rep.engine.results.pop(rid, None)
            if res is None:
                continue
            t = self._requests.get(rid)
            if t is not None and t.finished:
                continue        # duplicate re-run after a failover
            if res.finish == "shed" and t is not None \
                    and self._rescue_shed(t, res, exclude=i):
                continue        # re-placed on a replica with room
            if t is not None:
                t.finished = True
                t.tokens = [int(x) for x in res.tokens]
            self._open.discard(rid)
            if rid in self.results:
                continue
            self.results[rid] = res
            finished.append(rid)
            if self.journal is not None and t is not None:
                self.journal.append(
                    "finish", rid=rid, finish=res.finish,
                    trace_id=t.trace_id,
                    tokens=[int(x) for x in res.tokens],
                    gen_len=res.gen_len, ttft_s=res.ttft_s,
                    tpot_s=res.tpot_s)

    def _track_progress(self):
        """Mirror each live replica's generated-so-far tokens into the
        tracked map — what failover re-places with. Any PREFIX of the
        true stream is token-exact under resume, so a stale mirror only
        costs recompute, never correctness."""
        for rep in self._replicas:
            if rep.engine is None or rep.engine.closed:
                continue
            for rid, toks in rep.engine.inflight_tokens().items():
                t = self._requests.get(rid)
                if t is not None and not t.finished:
                    t.tokens = toks

    def _migrate_roles(self):
        """Disaggregated role scheduling (PAPERS.md: prefill/decode
        separation): a request on a PREFILL-role replica migrates to a
        decode-capable replica at its first token, through the same
        token-exact release → re-admit path failover uses. Roles are a
        routing preference, never a correctness gate: with no
        decode-capable replica placeable the request degrades in place
        (the prefill replica keeps decoding it)."""
        from paddle_tpu.observability import registry

        if all(r.role == ReplicaRole.MIXED for r in self._replicas):
            return
        moved = 0
        for t in self._requests.values():
            if t.finished or t.replica is None or not t.tokens:
                continue
            rep = self._replicas[t.replica]
            if rep.role != ReplicaRole.PREFILL or rep.engine is None \
                    or rep.engine.closed:
                continue
            if not any(self._replicas[i].role in
                       (ReplicaRole.DECODE, ReplicaRole.MIXED)
                       for i in self._placeable()):
                continue    # nowhere decode-capable — degrade in place
            toks = rep.engine.release_request(t.rid)
            if toks is None:
                continue    # already finished/collected — not held
            t.tokens = [int(x) for x in toks]
            self._queue_replace(t)
            # the prefill replica's cache still holds the prompt's
            # finished blocks (its own refs survive the release) — mark
            # the re-placement a migration so the share journals
            # "migrate_blocks" when the decode side adopts them
            self._migrating.add(t.rid)
            moved += 1
        if moved:
            self.router_stats["role_migrations"] = \
                self.router_stats.get("role_migrations", 0) + moved
            registry().counter("serving.router.role_migrations").inc(moved)
            self.flight.mark("role_migration", moved=moved)
            # re-place NOW (journals "place" with the trace_id, so the
            # accept→place→finish chain stays connected) rather than
            # waiting a tick with the request in limbo
            self._drain_pending_replacements()

    def _heal_orphans(self):
        """A tracked unfinished request held by NO live replica (e.g. a
        failover raced a retirement, or a kill dropped an uncollected
        result) re-enters placement — the belt under the suspenders
        that makes ``drain()`` always terminate or raise loudly."""
        held = set()
        for rep in self._replicas:
            if rep.engine is None or rep.engine.closed:
                continue
            held.update(rep.engine.inflight_tokens().keys())
            held.update(rep.engine.results.keys())
        pending = {t.rid for t in self._pending_replace}
        for t in self._requests.values():
            if not t.finished and t.rid not in held \
                    and t.rid not in pending:
                self._queue_replace(t)

    def _journal_progress(self):
        changed = {}
        for t in self._requests.values():
            if not t.finished and len(t.tokens) > t.journaled_tokens:
                changed[str(t.rid)] = t.tokens
                t.journaled_tokens = len(t.tokens)
        if changed:
            self.journal.append("progress", tokens=changed)

    def _snapshot_next(self):
        """Round-robin one live replica through the integrity-manifest
        snapshot path (one per cadence tick bounds the stall)."""
        from paddle_tpu.observability import registry
        from paddle_tpu.resilience.retry import call_with_retry

        live = self.live_replicas
        if not live or self.root is None:
            return
        i = live[self._snap_cursor % len(live)]
        self._snap_cursor += 1
        rep = self._replicas[i]
        try:
            call_with_retry(
                lambda: rep.engine.save_snapshot(rep.root),
                policy=self.retry_policy, retry_on=(OSError,),
                describe="router.snapshot")
            self.router_stats["snapshots"] += 1
            registry().counter("serving.router.snapshots").inc()
        except Exception:   # noqa: BLE001 — cadence must not kill a tick
            logger.warning("router: periodic snapshot of replica %d "
                           "failed", i, exc_info=True)
            self.flight.mark("snapshot_failed", replica=i)

    # --------------------------------------------------------- elasticity
    def drain_replica(self, i: int,
                      timeout_s: Optional[float] = None) -> List[int]:
        """Elastic drain: stop placement to replica ``i``, snapshot it
        (postmortem trail), migrate its in-flight and queued work onto
        the survivors via the token-exact resume path, and remove it.
        Returns the migrated request ids. Draining the last live
        replica raises — the work would have nowhere to go. With
        ``timeout_s`` a cross-process replica that does not answer a
        liveness ping inside the budget raises :class:`DrainTimeout`
        naming the stuck replica and its queue depth — a hung worker
        must surface as a typed error, not an indefinite drain."""
        from paddle_tpu.observability import registry
        from paddle_tpu.resilience.retry import call_with_retry

        rep = self._replicas[i]
        if rep.state not in ("healthy", "suspect", "draining") \
                or rep.engine is None or rep.engine.closed:
            raise ValueError(f"replica {i} is {rep.state}; only a live "
                             f"replica can be drained")
        if len(self.live_replicas) <= 1:
            raise ValueError("cannot drain the last live replica — its "
                             "work would have nowhere to migrate "
                             "(add_replica first)")
        if timeout_s is not None and hasattr(rep.engine, "ping") \
                and not rep.engine.ping(timeout_s=timeout_s):
            depth = 0
            try:
                depth = int(rep.engine.queued)
            except Exception:   # noqa: BLE001 — best-effort depth
                pass
            raise DrainTimeout(
                f"drain_replica({i}): worker did not answer a liveness "
                f"ping within {timeout_s}s (queue depth {depth})",
                replica=i, queue_depth=depth)
        rep.state = "draining"
        if rep.root is not None:
            try:
                call_with_retry(
                    lambda: rep.engine.save_snapshot(rep.root),
                    policy=self.retry_policy, retry_on=(OSError,),
                    describe="router.snapshot")
            except Exception:   # noqa: BLE001 — drain proceeds anyway
                logger.warning("router: drain snapshot of replica %d "
                               "failed", i, exc_info=True)
        # freshest possible resume state, straight from the live engine
        inflight = rep.engine.inflight_tokens()
        migrated = []
        for rid, toks in inflight.items():
            t = self._requests.get(rid)
            if t is None or t.finished:
                continue
            t.tokens = toks
            self._queue_replace(t)
            migrated.append(rid)
        self._absorb_stats(rep.engine)
        try:
            rep.engine.close()
        except Exception:   # noqa: BLE001 — best-effort release
            pass
        rep.engine = None
        rep.state = "removed"
        if self._tier_prefix is not None:
            self._tier_prefix.forget_replica(i)
        self._drain_pending_replacements()
        self.router_stats["drains"] += 1
        registry().counter("serving.router.drains").inc()
        self.flight.mark("drain", replica=i, migrated=len(migrated))
        if self.journal is not None:
            self.journal.append("drain", replica=i, migrated=migrated)
        self.flight.auto_dump("drain")
        self._update_gauges()
        return migrated

    def add_replica(self, warm: bool = True,
                    role: str = ReplicaRole.MIXED) -> int:
        """Grow the tier by one replica; returns its index. With
        ``warm=True`` (default) a throwaway one-block request is run to
        completion first, so the replica's smallest prefill bucket and
        its step program are compiled BEFORE it takes traffic — "joins
        warm". For a tensor-parallel tier the warmup runs UNDER THE
        REPLICA'S OWN MESH context (asserted below): the engine's
        programs carry their mesh explicitly through ``shard_map``, but
        entering the context pins any ambient-mesh-sensitive lowering
        (and any future jit cache keyed on the mesh context) to the
        same programs the replica will re-dispatch under traffic — a
        warmup compiled under a DIFFERENT ambient mesh would be paid
        for twice. Affinity hashing uses the slot count, so existing
        prefixes keep their homes and only the new slot's share moves."""
        import contextlib

        from paddle_tpu.observability import registry

        if role not in REPLICA_ROLES:
            raise ValueError(f"unknown replica role {role!r} "
                             f"(choose from {REPLICA_ROLES})")
        idx = len(self._replicas)
        rep = _Replica(self._new_engine(idx), self._replica_root(idx),
                       role=role)
        if warm:
            mesh = rep.engine.mesh
            with (mesh if mesh is not None else contextlib.nullcontext()):
                if mesh is not None:
                    from jax.interpreters import pxla
                    active = pxla.thread_resources.env.physical_mesh
                    assert active is mesh, (
                        "add_replica warmup must run under the "
                        "replica's own mesh context")
                bt = rep.engine.block_tokens
                # tpu-lint: allow(host-sync): host-built warmup prompt
                prompt = np.full(min(bt, rep.engine.max_seq_len - 2), 3,
                                 np.int32)
                rid = rep.engine.submit(Request(prompt, max_new_tokens=1,
                                                seed=0))
                rep.engine.drain(max_steps=64)
                rep.engine.results.pop(rid, None)
                rep.engine.reset_stats()
        self._replicas.append(rep)
        registry().counter("serving.router.replicas_added").inc()
        self.flight.mark("add_replica", replica=idx, warm=warm)
        if self.journal is not None:
            self.journal.append("add_replica", replica=idx)
        self._update_gauges()
        return idx

    def kill_replica(self, i: int, mode: str = "close"):
        """Chaos hook: simulate abrupt replica death. ``mode="close"``
        (default, works for any tier) drops the engine's device state,
        queue, slots AND uncollected results on the floor — no
        snapshot, no goodbye. ``mode="sigkill"`` (cross-process tiers
        only) sends a REAL ``SIGKILL`` to the worker process, armed to
        land MID-STEP when the worker is busy — the kernel tears the
        process down between a request's tokens, the hardest point in
        a tick. Either way the router only finds out at the next
        tick's heartbeat, exactly like a real crash; the zero-loss
        contract must hold anyway (tests/test_serving_router.py,
        examples/chaos_bench.py --kill_mode)."""
        from paddle_tpu.observability import registry

        if mode not in ("close", "sigkill"):
            raise ValueError(f"unknown kill mode {mode!r} "
                             f"(choose 'close' or 'sigkill')")
        rep = self._replicas[i]
        if rep.engine is None or rep.engine.closed:
            raise ValueError(f"replica {i} is already gone")
        if mode == "sigkill" and not hasattr(rep.engine, "kill"):
            raise ValueError("kill_replica(mode='sigkill') needs a "
                             "cross-process tier (Router(processes="
                             "True)) — an in-process engine has no pid")
        self.router_stats["replica_kills"] += 1
        registry().counter("serving.router.replica_kills").inc()
        self.flight.mark("replica_killed", replica=i, mode=mode)
        if mode == "sigkill":
            rep.engine.kill(mid_step=True)
        else:
            rep.engine.close()  # drops everything, stats included

    # ------------------------------------------------- bench duck-typing
    _UNSET = object()

    def set_overload_controls(self, *, max_queue=_UNSET,
                              shed_infeasible=_UNSET):
        """Flip the PR 8 overload knobs on every live replica AND on
        the template config future replicas (failover rebuilds,
        :meth:`add_replica`) are built from — the benches calibrate
        unshedded (a saturated closed-loop warmup would shed itself)
        and arm shedding for the measured pass."""
        for rep in self._replicas:
            if rep.engine is None or rep.engine.closed:
                continue
            if max_queue is not self._UNSET:
                rep.engine.max_queue = max_queue
            if shed_infeasible is not self._UNSET:
                rep.engine.shed_infeasible = bool(shed_infeasible)
        if max_queue is not self._UNSET:
            self._engine_kwargs["max_queue"] = max_queue
        if shed_infeasible is not self._UNSET:
            self._engine_kwargs["shed_infeasible"] = bool(shed_infeasible)

    @property
    def stats(self) -> Dict:
        """Tier-cumulative stats: the sum of every engine's counters
        (incl. engines retired by failover/drain — their last readable
        stats are absorbed) plus the ``router_*`` tier counters."""
        out = dict(self._stats_base)
        for rep in self._replicas:
            if rep.engine is None \
                    or not isinstance(rep.engine.stats, dict):
                continue
            for k, v in rep.engine.stats.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
        for k, v in self.router_stats.items():
            out[f"router_{k}"] = v
        return out

    def metrics_snapshot(self) -> "MetricsRegistry":
        """The tier metrics plane: one merged :class:`MetricsRegistry`
        folding every replica's ``replica="<i>"``-labeled series into
        tier totals — counters summed, histograms bucket-summed,
        quantile sketches :meth:`~QuantileSketch.merge`-d, gauges kept
        per-replica-labeled (a summed occupancy gauge is meaningless;
        a per-replica one is a dashboard row). The merged registry is
        a detached point-in-time copy with the full export surface
        (``export_jsonl`` / ``prometheus_text``); mutating it does not
        touch the live series (docs/OBSERVABILITY.md §Tier metrics).
        The tier-merged prefix gauges (``serving.router.
        prefix_hit_rate`` / ``tier_prefix_hit_rate``) are refreshed
        first so the snapshot carries them even between ticks."""
        from paddle_tpu.observability import registry
        self._update_gauges()
        return registry().merged_across("replica")

    def reset_stats(self):
        self._stats_base = {}
        self._prefix_base = [0, 0]
        if self._tier_prefix is not None:
            self._tier_prefix.hit_blocks = 0
            self._tier_prefix.lookup_blocks = 0
        for rep in self._replicas:
            if rep.engine is not None and not rep.engine.closed:
                rep.engine.reset_stats()
        for k in self.router_stats:
            self.router_stats[k] = 0

    @property
    def pool_blocks_total(self) -> int:
        """Usable KV blocks across live replicas (scratch excluded)."""
        return sum(r.engine.pool.num_blocks - 1 for r in self._replicas
                   if r.engine is not None and not r.engine.closed)

    @property
    def prefix_hit_rate(self) -> float:
        """Block-weighted prefix hit rate over the WHOLE tier: live
        replicas plus the absorbed counters of engines retired by
        failover/drain — a router-mode bench that killed a replica
        mid-run must not lose that replica's reuse accounting (the
        per-replica-only rate this replaces under-reported exactly
        when the tier was doing its job)."""
        hits, lookups = self._prefix_base
        for r in self._replicas:
            if r.engine is None or r.engine.closed \
                    or r.engine.prefix_cache is None:
                continue
            hits += r.engine.prefix_cache.hit_blocks
            lookups += r.engine.prefix_cache.lookup_blocks
        return hits / lookups if lookups else 0.0

    @property
    def tier_prefix_hit_rate(self) -> float:
        """Fraction of placement-probed prefix blocks served by a
        CROSS-REPLICA block copy through the tier store — the reuse
        the per-replica caches cannot see (0.0 with the store off)."""
        return (self._tier_prefix.hit_rate
                if self._tier_prefix is not None else 0.0)

    @property
    def tier_prefix_store(self) -> Optional[TierPrefixStore]:
        return self._tier_prefix

    def clear_prefix_caches(self):
        for r in self._replicas:
            if r.engine is not None and not r.engine.closed \
                    and r.engine.prefix_cache is not None:
                r.engine.prefix_cache.clear()
        if self._tier_prefix is not None:
            self._tier_prefix.clear()

    @property
    def active_slots(self) -> int:
        return sum(r.engine.active_slots for r in self._replicas
                   if r.engine is not None and not r.engine.closed)

    @property
    def queued(self) -> int:
        return (sum(r.engine.queued for r in self._replicas
                    if r.engine is not None and not r.engine.closed)
                + len(self._pending_replace))

    @property
    def idle(self) -> bool:
        """The tier is idle only when NOTHING can still make progress:
        no orphan awaiting re-placement, no accepted request
        unfinished, and no replica that is dead — or killed but not
        yet discovered (a closed engine in a live state means the next
        tick's heartbeat will declare it dead and fail over; treating
        that as idle would let a drive loop exit between the kill and
        the failover, silently losing its requests)."""
        if self._pending_replace or self._open:
            return False
        for r in self._replicas:
            if r.state == "dead":
                return False
            if r.state in ("healthy", "suspect", "draining"):
                if r.engine is None or r.engine.closed:
                    return False
                if not r.engine.idle:
                    return False
        return True

    def pop_result(self, request_id: int) -> RequestResult:
        return self.results.pop(request_id)

    def _stuck_replica(self):
        """(index, queue depth) of the live replica holding the most
        work — the best available name for WHO is stuck when a drain
        times out — or ``(None, pending_replace depth)`` when nothing
        live holds anything (the work is orphaned, not held)."""
        best, best_depth = None, -1
        for i, r in enumerate(self._replicas):
            if r.engine is None or r.engine.closed:
                continue
            try:
                depth = int(r.engine.active_slots) + int(r.engine.queued)
            except Exception:   # noqa: BLE001 — unreachable counts as 0
                continue
            if depth > best_depth:
                best, best_depth = i, depth
        if best is None or best_depth <= 0:
            return None, len(self._pending_replace)
        return best, best_depth

    def drain(self, max_steps: Optional[int] = None,
              timeout_s: Optional[float] = None
              ) -> Dict[int, RequestResult]:
        """Step until every accepted request has finished (or
        ``max_steps``). A tier that makes no progress for several
        consecutive all-idle ticks raises ``RuntimeError`` instead of
        spinning (the router self-heals orphans each tick, so a real
        stall means something structural). With ``timeout_s`` a drain
        that outlives the wall-clock budget raises
        :class:`DrainTimeout` naming the stuck replica and its queue
        depth — the caller gets WHO, not just "too slow"."""
        steps = idle_spins = 0
        t0 = time.perf_counter()
        while not self.idle:
            if timeout_s is not None \
                    and time.perf_counter() - t0 > timeout_s:
                idx, depth = self._stuck_replica()
                who = (f"replica {idx}" if idx is not None
                       else "no live replica (orphaned work)")
                raise DrainTimeout(
                    f"drain exceeded {timeout_s}s: {who} still holds "
                    f"{depth} request(s)", replica=idx, queue_depth=depth)
            out = self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
            # orphans with NO placeable replica count as stalled too —
            # they sit in pending_replace (reported under "queued") and
            # can never progress, so waiting on them would spin forever
            stuck_orphans = bool(self._pending_replace) \
                and not self._placeable()
            if out["active"] == 0 and not out["finished"] \
                    and (out["queued"] == 0 or stuck_orphans):
                idle_spins += 1
                if idle_spins > 8:
                    raise RuntimeError(
                        "router drain stalled: no replica can make "
                        "progress but tracked requests are unfinished"
                        + (f" ({len(self._pending_replace)} orphans "
                           f"with no placeable replica)"
                           if stuck_orphans else ""))
            else:
                idle_spins = 0
        return self.results

    def generate(self, prompts: Sequence, **req_kwargs) -> List:
        """Batch convenience mirroring ``ServingEngine.generate``."""
        # tpu-lint: allow(host-sync): API boundary — prompts are host ids
        ids = [self.submit(Request(np.asarray(p).reshape(-1),
                                   **req_kwargs)) for p in prompts]
        self.drain()
        return [self.results[i].ids for i in ids]

    def close(self):
        if self._closed:
            return
        self._closed = True
        for rep in self._replicas:
            if rep.engine is not None:
                try:
                    rep.engine.close()
                except Exception:   # noqa: BLE001 — best-effort
                    pass
                rep.engine = None
            rep.state = "removed"
        if self._tier_prefix is not None:
            self._tier_prefix.clear()
        if self.journal is not None:
            self.journal.append("close")

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # --------------------------------------------------- router recovery
    @classmethod
    def recover(cls, model, root: str, *, state=None,
                **router_kwargs) -> "Router":
        """Rebuild a whole tier after a ROUTER-process crash: replay
        the journal (corrupt lines skipped and counted), restore every
        replica whose snapshot verifies, rebuild the rest empty, and
        re-place every journaled accepted-but-unfinished request — with
        its last journaled token progress — through the resume path.
        Finished results are reconstructed from their journal records.
        ``router_kwargs`` mirror the constructor (engine knobs
        included) and must match the crashed router's config, exactly
        like ``ServingEngine.restore`` overrides."""
        events, corrupt = RouterJournal.replay(
            os.path.join(root, "journal.jsonl"))
        if corrupt:
            logger.warning("router recovery: skipped %d corrupt journal "
                           "lines", corrupt)
        header = next((e for e in events if e.get("kind") == "header"),
                      None)
        n_replicas = router_kwargs.pop(
            "replicas", header.get("replicas", 2) if header else 2)
        if header is not None and "seed" not in router_kwargs:
            router_kwargs["seed"] = header.get("seed", 0)
        rt = cls(model, replicas=n_replicas, state=state, root=root,
                 **router_kwargs)
        # journal fold: accept -> place -> progress -> finish, in order
        accepted: Dict[int, Dict] = {}
        for e in events:
            k = e.get("kind")
            if k == "accept":
                accepted[e["rid"]] = dict(e, tokens=[])
            elif k == "place" and e.get("rid") in accepted:
                accepted[e["rid"]]["replica"] = e.get("replica")
            elif k == "progress":
                for rid_s, toks in e.get("tokens", {}).items():
                    rid = int(rid_s)
                    if rid in accepted:
                        accepted[rid]["tokens"] = toks
            elif k == "finish" and e.get("rid") in accepted:
                accepted[e["rid"]]["finish"] = e
        # re-anchor the seed source past every router-assigned seed in
        # the journal: a recovered router that reset _seeds_issued to 0
        # would mint the SAME seed for its next fresh submit as the
        # first pre-crash request drew — two requests sharing one RNG
        # stream (the snapshot-coverage audit's find; engine restore
        # already carries seeds_issued in its snapshot for the same
        # reason)
        rt._seeds_issued = max(
            [rt._seeds_issued]
            + [e["seed"] - rt.seed + 1 for e in accepted.values()
               if isinstance(e.get("seed"), int)
               and e["seed"] >= rt.seed])
        # replicas were built fresh by the constructor; swap in restored
        # engines where a committed snapshot survives
        covered = set()
        for i, rep in enumerate(rt._replicas):
            if rep.root is None:
                continue
            if rt.processes:
                from paddle_tpu.resilience import integrity as _integ
                if not _integ.manifest_steps(rep.root):
                    continue    # never committed a snapshot — keep fresh
                try:
                    rep.engine.close()
                except Exception:   # noqa: BLE001 — being replaced
                    pass
                eng, cov, mode = rt._restore_engine(i, rep)
                if eng is None:
                    rep.engine = None
                    rep.state = "removed"
                    continue
                rep.engine = eng
                if mode == "restore":
                    covered |= cov
                continue
            try:
                snap = ServingEngine.load_snapshot(rep.root)
            except FileNotFoundError:
                continue
            # free the constructor-built engine BEFORE the restore
            # allocates its pool — restoring a fully-snapshotted tier
            # must not transiently double per-replica device memory
            rep.engine.close()
            try:
                eng = ServingEngine.restore(
                    model, snap, state=rt._state,
                    **rt._restore_overrides(i))
            except (RestoreError, ValueError, KeyError):
                logger.warning("router recovery: replica %d snapshot "
                               "unusable", i, exc_info=True)
                rep.engine = rt._new_engine(i)
                continue
            rep.engine = eng
            covered |= {rs["request_id"]
                        for rs in snap["slots"] + snap["queue"]}
        for rid, rec in accepted.items():
            fin = rec.get("finish")
            # tpu-lint: allow(host-sync): journal JSON is host data
            prompt = np.asarray(rec["prompt"], np.int32)
            if fin is not None:
                rt.results[rid] = RequestResult(
                    rid, prompt, fin.get("tokens", []),
                    fin.get("gen_len", len(fin.get("tokens", []))),
                    fin.get("finish", "length"), fin.get("ttft_s"),
                    fin.get("tpot_s"), 0,
                    trace_id=rec.get("trace_id"))
                t = _Tracked(rid, prompt, rec["max_new_tokens"],
                             rec["seed"], rec.get("priority", "normal"),
                             None, None, trace_id=rec.get("trace_id"))
                t.finished = True
                t.tokens = list(fin.get("tokens", []))
                rt._requests[rid] = t
                continue
            t = _Tracked(rid, prompt, rec["max_new_tokens"], rec["seed"],
                         rec.get("priority", "normal"),
                         rec.get("deadline_s"), rec.get("replica"),
                         trace_id=rec.get("trace_id"))
            t.tokens = list(rec.get("tokens", []))
            rt._requests[rid] = t
            rt._open.add(rid)
            if rid not in covered:
                rt._queue_replace(t)
        rt._drain_pending_replacements()
        rt.flight.mark("recover", requests=len(accepted),
                       covered=len(covered),
                       corrupt_journal_lines=corrupt)
        if rt.journal is not None:
            rt.journal.append("recover", requests=len(accepted),
                              corrupt=corrupt)
        return rt
