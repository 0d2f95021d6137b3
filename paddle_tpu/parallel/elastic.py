"""Elastic training: failure detection + restart-from-checkpoint.

Reference (SURVEY.md §5-failure): fleet/elastic/manager.py — ElasticManager
registers ranks in etcd, heartbeats, and on membership change the launcher
kills and relaunches workers; recovery is restart-from-latest-checkpoint,
not in-flight repair. Failure detection otherwise = the launcher watch loop
reaping dead children + NCCL timeouts.

TPU-native: multi-host membership/rendezvous belongs to
`jax.distributed.initialize` (DCN); what the framework owns is the
restart-from-checkpoint semantics. `ElasticTrainLoop` supervises a train
loop in-process: periodic (async) checkpoints via CheckpointManager, crash →
restore latest → resume, bounded restarts — the same recovery contract,
testable single-host by injecting faults (SURVEY.md §5: tests kill procs)."""

import json
import logging
import os
import threading
import time
from typing import Callable, Dict, Optional, Set

from paddle_tpu.resilience import (RetryPolicy, is_not_found, kv_op,
                                   record_event)
from paddle_tpu.resilience import faults as _faults

logger = logging.getLogger("paddle_tpu.elastic")


# ---- membership / heartbeat (reference: fleet/elastic/manager.py) ----------
#
# The reference registers each rank in etcd and heartbeats; a missed TTL
# triggers relaunch. TPU pods have no etcd; the equivalent substrate is any
# shared KV the hosts can all reach. `HeartbeatStore` is that interface;
# `FileHeartbeatStore` implements it over a shared directory (NFS/GCS-fuse
# on real pods, tmpdir in tests). `ElasticManager` owns register/heartbeat/
# watch semantics on top.

class HeartbeatStore:
    """KV with per-member freshness — the etcd-analog interface."""

    def put(self, member: str, payload: dict):
        raise NotImplementedError

    def members(self) -> Dict[str, dict]:
        """All registered members → their last payload (incl. 'ts')."""
        raise NotImplementedError

    def remove(self, member: str):
        raise NotImplementedError


class FileHeartbeatStore(HeartbeatStore):
    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, member):
        return os.path.join(self.root, f"{member}.hb")

    def put(self, member, payload):
        tmp = self._path(member) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self._path(member))  # atomic on POSIX

    def members(self):
        out = {}
        for fn in os.listdir(self.root):
            if not fn.endswith(".hb"):
                continue
            try:
                with open(os.path.join(self.root, fn)) as f:
                    out[fn[:-3]] = json.load(f)
            except (OSError, ValueError):
                continue  # torn write / concurrent removal
        return out

    def remove(self, member):
        try:
            os.unlink(self._path(member))
        except FileNotFoundError:
            pass


class CoordinationServiceStore(HeartbeatStore):
    """Heartbeats over a coordination-service KV (the TCPStore/etcd
    analog) — no shared filesystem required (VERDICT r3 #8: clusters
    without a shared dir).

    Two modes:
    * ``CoordinationServiceStore.connect(address, rank, world)`` — the
      launcher-side mode: rank 0 HOSTS the service on `address`, every
      launcher connects a client. Mirrors the reference's etcd being
      infra-level, outside the trainers.
    * ``CoordinationServiceStore(client=...)`` / ``.from_jax()`` — reuse
      an existing client (inside a training process after
      `jax.distributed.initialize`, the job's own coordination service).

    Every KV op runs under the shared bounded-retry policy
    (paddle_tpu.resilience.retry) — a transient coordination-service
    hiccup (RPC reset, leader re-election blip) must not read as a dead
    peer or kill the heartbeat loop. Pass ``retry=None`` to disable.
    """

    def __init__(self, client, prefix: str = "pt_elastic", service=None,
                 retry: Optional[RetryPolicy] = RetryPolicy()):
        self._client = client
        self._prefix = prefix
        self._service = service        # kept alive on the hosting rank
        self._retry = retry

    def _kv_call(self, describe: str, fn, retry_if=None):
        # shared resilience.kv_op wrapper: retry + the injectable kv.op
        # fault site (policy=None → fault site only, no retry)
        return kv_op(describe, fn, policy=self._retry, retry_if=retry_if)

    @classmethod
    def connect(cls, address: str, rank: int, world_size: int,
                prefix: str = "pt_elastic", timeout_s: float = 60.0):
        from jax._src.lib import _jax
        service = None
        if rank == 0:
            service = _jax.get_distributed_runtime_service(
                address, world_size)
        # a peer launcher dying is the NORMAL event elastic mode exists
        # for — the default client callbacks would terminate THIS process
        # on a peer's missed heartbeat / service error, defeating the
        # whole recovery loop. Log instead; the ElasticManager TTL watch
        # owns the reaction.
        client = _jax.get_distributed_runtime_client(
            address, rank, init_timeout=int(timeout_s),
            shutdown_on_destruction=False,
            missed_heartbeat_callback=lambda *a:
                logger.warning("elastic KV heartbeat event: %s", a))
        client.connect()
        return cls(client, prefix=prefix, service=service)

    @classmethod
    def from_jax(cls, prefix: str = "pt_elastic"):
        from jax._src import distributed
        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "CoordinationServiceStore.from_jax needs "
                "jax.distributed.initialize (init_parallel_env) first")
        return cls(client, prefix=prefix)

    def put(self, member, payload):
        self._kv_call("elastic.kv_set",
                      lambda: self._client.key_value_set(
                          f"{self._prefix}/{member}", json.dumps(payload),
                          allow_overwrite=True))

    def members(self):
        out = {}
        try:
            # empty prefix reads as NOT_FOUND on some versions — that is
            # genuinely "no members", never worth a retry. Anything else
            # (RPC hiccup, service error) is retried, and past the retry
            # budget must NOT read as an empty world: the watcher would
            # declare every peer dead and kill a healthy job.
            items = self._kv_call(
                "elastic.kv_dir_get",
                lambda: self._client.key_value_dir_get(self._prefix),
                retry_if=lambda e: not is_not_found(e))
        except Exception as e:
            if is_not_found(e):
                return out
            raise
        for key, val in items:
            try:
                out[key.rsplit("/", 1)[-1]] = json.loads(val)
            except ValueError:
                continue
        return out

    def remove(self, member):
        try:
            self._kv_call("elastic.kv_delete",
                          lambda: self._client.key_value_delete(
                              f"{self._prefix}/{member}"))
        except Exception:
            pass

    def close(self):
        try:
            self._client.shutdown()
        finally:
            self._service = None


class ElasticManager:
    """Register + heartbeat this host; watch for lost/joined peers.

    Reference semantics (fleet/elastic/manager.py): every worker heartbeats
    a TTL'd key; the manager watches membership and signals the launcher to
    relaunch on change. `watch()` here invokes `on_change(alive, dead)` from
    a daemon thread; the launcher reacts by restarting the training script,
    whose recovery is restore-from-checkpoint (ElasticTrainLoop)."""

    def __init__(self, store: HeartbeatStore, rank: int, world_size: int,
                 heartbeat_interval: float = 2.0,
                 timeout: Optional[float] = None):
        self.store = store
        self.rank = rank
        self.world_size = world_size
        self.interval = heartbeat_interval
        self.timeout = timeout if timeout is not None else 3 * heartbeat_interval
        self._stop = threading.Event()
        self._threads = []

    # -- registration / heartbeat --

    def register(self):
        # cooperative fault site: kind='drop_heartbeat' swallows this
        # put — from the peers' view this host just went silent, the
        # exact signal a hung/partitioned host produces
        fault = _faults.maybe_fire("elastic.heartbeat")
        if fault is not None and fault.kind == "drop_heartbeat":
            record_event("heartbeat_dropped")
            return
        self.store.put(str(self.rank), {"rank": self.rank, "ts": time.time()})

    def _heartbeat_loop(self):
        while not self._stop.wait(self.interval):
            self.register()

    def start(self):
        self.register()
        t = threading.Thread(target=self._heartbeat_loop, daemon=True)
        t.start()
        self._threads.append(t)
        return self

    def stop(self, deregister: bool = True):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=self.interval + 1)
        self._threads.clear()
        if deregister:
            self.store.remove(str(self.rank))

    # -- membership --

    def alive(self, now: Optional[float] = None,
              members: Optional[Dict[str, dict]] = None) -> Set[int]:
        """Ranks with a fresh heartbeat. `members` lets a caller reuse ONE
        store snapshot for several derived views (see watch) instead of
        re-polling per view."""
        now = now if now is not None else time.time()
        members = members if members is not None else self.store.members()
        out = set()
        for m, payload in members.items():
            if now - payload.get("ts", 0) <= self.timeout:
                out.add(int(m))
        return out

    def dead(self) -> Set[int]:
        return set(range(self.world_size)) - self.alive()

    def all_alive(self) -> bool:
        return len(self.alive()) == self.world_size

    def wait_for_world(self, timeout: float = 60.0) -> bool:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.all_alive():
                return True
            time.sleep(self.interval / 4)
        return False

    def watch(self, on_change: Callable[[Set[int], Set[int]], None],
              poll_interval: Optional[float] = None):
        """Daemon thread: calls on_change(alive, dead) whenever membership
        differs from the last poll (a lost heartbeat past TTL or a join)."""
        poll = poll_interval if poll_interval is not None else self.interval

        def loop():
            last = self.alive()
            while not self._stop.wait(poll):
                # ONE store snapshot per poll: alive and dead must be two
                # views of the same instant — a second poll (the old
                # self.dead() call) could disagree with `cur` mid-change
                cur = self.alive(members=self.store.members())
                if cur != last:
                    dead = set(range(self.world_size)) - cur
                    logger.warning("membership change: alive=%s dead=%s",
                                   sorted(cur), sorted(dead))
                    on_change(cur, dead)
                    last = cur

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        self._threads.append(t)
        return t


def _nan_poison(tree):
    """NaN-fill every floating leaf (the nan_grads fault injector)."""
    import jax
    import jax.numpy as jnp

    def one(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            return jnp.full_like(leaf, jnp.nan)
        return leaf

    return jax.tree_util.tree_map(one, tree)


class ElasticTrainLoop:
    """Supervised training with checkpoint/resume recovery.

    train_step(state, step) -> state : one (or k) optimizer steps; `state`
    is any orbax-serializable pytree (e.g. {"model":…, "opt":…}).

    Recovery semantics (paddle_tpu.resilience):

    * Resume restores from ``CheckpointManager.verified_latest_step()``
      when the manager provides it — a corrupt/uncommitted latest step is
      walked past instead of crash-looping forever.
    * ``nonfinite_policy``: None (off — the step takes the exact code
      path the seed took), ``"skip"`` (a step whose outputs hold NaN/Inf
      is dropped: previous state kept, counter bumped, training moves
      on) or ``"rewind"`` (skip, and after ``nonfinite_limit``
      CONSECUTIVE bad steps rewind to the last verified checkpoint —
      charged against the restart budget so a deterministic NaN can't
      rewind forever). Built on utils.nan_inf's fused device reduction.
    * The restart budget RESETS after ``restart_reset_steps`` consecutive
      clean steps (default ``save_every``; 0 disables) — a flaky step at
      hour 40 is no longer charged against failures from hour 1.
    """

    def __init__(self, checkpoint_manager, train_step: Callable,
                 init_state: Callable, max_restarts: int = 3,
                 save_every: int = 100,
                 restore_target: Optional[Callable] = None,
                 nonfinite_policy: Optional[str] = None,
                 nonfinite_limit: int = 3,
                 restart_reset_steps: Optional[int] = None):
        if nonfinite_policy not in (None, "skip", "rewind"):
            raise ValueError(
                f"nonfinite_policy must be None, 'skip' or 'rewind'; got "
                f"{nonfinite_policy!r}")
        self.mngr = checkpoint_manager
        self.train_step = train_step
        self.init_state = init_state
        self.max_restarts = max_restarts
        self.save_every = save_every
        self.restore_target = restore_target
        self.nonfinite_policy = nonfinite_policy
        self.nonfinite_limit = int(nonfinite_limit)
        self.restart_reset_steps = (save_every if restart_reset_steps is None
                                    else int(restart_reset_steps))
        self.restarts = 0
        self.nonfinite_skipped = 0

    def _resume(self):
        verified = getattr(self.mngr, "verified_latest_step", None)
        step = verified() if callable(verified) else self.mngr.latest_step()
        if step is None:
            return self.init_state(), 0
        target = self.restore_target() if self.restore_target else None
        state = self.mngr.restore(step, target=target)
        logger.info("resumed from checkpoint step %d", step)
        return state, step + 1

    def run(self, total_steps: int):
        from paddle_tpu.utils.nan_inf import tree_nonfinite_count

        state, start = self._resume()
        step = start
        clean = 0      # consecutive completed steps since last recovery
        streak = 0     # consecutive non-finite steps
        while step < total_steps:
            try:
                # raising fault kinds crash here exactly like a real step
                # failure; kind='nan_grads' poisons the step's outputs so
                # the non-finite policy (or a downstream guard) reacts
                fault = _faults.maybe_fire("train.step", index=step)
                new_state = self.train_step(state, step)
                if fault is not None and fault.kind == "nan_grads":
                    new_state = _nan_poison(new_state)
                if self.nonfinite_policy is not None \
                        and int(tree_nonfinite_count(new_state)):
                    streak += 1
                    self.nonfinite_skipped += 1
                    record_event("nonfinite_step_skipped")
                    logger.warning(
                        "step %d produced non-finite values; skipping "
                        "(%d consecutive, policy=%s)", step, streak,
                        self.nonfinite_policy)
                    if self.nonfinite_policy == "rewind" \
                            and streak >= self.nonfinite_limit:
                        record_event("nonfinite_rewind")
                        # unify with the restart path below: rewind is a
                        # restore-from-checkpoint charged to the budget
                        raise FloatingPointError(
                            f"{streak} consecutive non-finite steps "
                            f"(limit {self.nonfinite_limit})")
                    # a skipped step still honors the save cadence with
                    # the RETAINED (valid) state — otherwise one NaN on a
                    # boundary step stretches the progress-loss window to
                    # 2x save_every
                    if (step + 1) % self.save_every == 0 \
                            or step + 1 == total_steps:
                        self.mngr.save(step, state)
                    clean = 0
                    step += 1        # skip-step: old state, batch consumed
                    continue
                streak = 0
                state = new_state
                if (step + 1) % self.save_every == 0 or step + 1 == total_steps:
                    self.mngr.save(step, state)
                step += 1
                clean += 1
                if (self.restarts and self.restart_reset_steps
                        and clean >= self.restart_reset_steps):
                    logger.info("restart budget reset after %d clean steps",
                                clean)
                    record_event("restart_budget_reset")
                    self.restarts = 0
            except KeyboardInterrupt:
                raise
            except Exception as e:   # noqa: BLE001 — supervisor boundary
                self.restarts += 1
                record_event("train_restart")
                logger.warning("train step %d failed (%s); restart %d/%d",
                               step, e, self.restarts, self.max_restarts)
                if self.restarts > self.max_restarts:
                    raise
                self.mngr.wait_until_finished()
                state, step = self._resume()
                clean = 0
                streak = 0
        self.mngr.wait_until_finished()
        return state
