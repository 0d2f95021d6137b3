"""Cross-process replica tier: one OS process per replica
(docs/SERVING.md §Cross-process tier).

`worker_main` is the spawn entry of a replica child process: it builds
(or snapshot-restores) its own ``ServingEngine`` from a picklable
``model_factory`` and serves the engine's surface over a
`serving.transport.Channel` — one RPC in flight, reply piggybacking a
small status dict, which the single-client discipline (only the Router
talks to a worker) makes an EXACT cache, not an approximation.

`ReplicaProxy` is the router-side half: it duck-types the engine
surface the `Router` actually touches (submit / admit_resumable /
step / drain / save_snapshot / inflight_tokens / estimated_ttft_s /
stats / overload knobs / pool + prefix-cache occupancy views), so
placement, failover, journaling and the trace_id chains above the seam
are byte-for-byte the in-process code paths.  Robustness is layered:

* every call carries a wall-clock deadline (`TransportTimeout`
  distinguishes a HUNG worker from a dead one);
* idempotent calls retry under the shared `RetryPolicy`, seeded
  per-replica so N proxies retrying a dead peer de-correlate;
* `TransportClosed` (EOF — the process is gone) and retry exhaustion
  mark the proxy broken, SIGKILL-reap the child so it can never leak,
  and surface through the engine surface the router already handles:
  ``closed`` for the heartbeat, a raised error for the step path,
  ``Rejected("replica_unreachable")`` for placement — the
  healthy→suspect→dead machine and zero-loss failover take it from
  there, unchanged.

The spawn context (never fork — jax's thread pools do not survive
forking) matches `parallel/launch.py`; the worker process re-imports
paddle_tpu and jax from scratch, which is exactly the isolation being
bought: a replica segfault, OOM-kill or SIGKILL takes ONE engine, not
the router's heap and journal writer.
"""

import logging
import multiprocessing as mp
import os
import signal
import time
from dataclasses import replace as _dc_replace
from typing import Any, Dict, List, Optional

from paddle_tpu.serving.engine import Rejected
from paddle_tpu.serving.transport import (
    Channel, PROTOCOL_VERSION, TransportClosed, TransportCorruption,
    TransportError, TransportTimeout, decode_block_entries,
    decode_request, decode_result, encode_block_entries, encode_error,
    encode_request, encode_result, raise_remote)

logger = logging.getLogger("paddle_tpu.serving")

__all__ = ["ReplicaProxy", "worker_main"]

#: ops safe to re-send after a torn frame or timeout: pure queries plus
#: writes that converge (re-arming the same faults, re-saving the same
#: step's snapshot).  submit/step/drain are NOT here — a lost reply
#: leaves the worker's state unknown, so those mark the proxy broken
#: and let the router's failover machinery decide.  block_fetch /
#: block_put (tier prefix store) are NOT here either: a fetch gathers
#: live device blocks and a put adopts pool references — a replayed
#: half-delivered transfer would double-commit pool state, so the
#: router's best-effort share just drops the copy instead.
_IDEMPOTENT_OPS = frozenset({
    "ping", "status", "stats", "inflight", "estimated_ttft",
    "faults_fired", "save_snapshot", "snapshot_roundtrip",
    "set_overload", "clear_prefix", "reset_stats", "arm_faults",
    "disarm_faults",
})


# ---------------------------------------------------------- worker side
def _engine_status(eng) -> Dict[str, Any]:
    """The piggybacked status every reply carries — the proxy's exact
    cache of the worker's scheduler occupancy."""
    pc = eng.prefix_cache
    return {
        "active": eng.active_slots, "queued": eng.queued,
        "idle": eng.idle, "closed": eng.closed,
        "pool_used": eng.pool.used_blocks,
        "prefix_hits": 0 if pc is None else pc.hit_blocks,
        "prefix_lookups": 0 if pc is None else pc.lookup_blocks,
    }


def _build_engine(spec: Dict[str, Any]):
    """Build (or restore) the worker's engine. Returns
    ``(engine, restored, covered_rids)``."""
    from paddle_tpu.serving.engine import ServingEngine

    model = spec["model_factory"]()
    kwargs = dict(spec.get("engine_kwargs") or {})
    labels = {"replica": str(spec.get("replica", 0))}
    restore_root = spec.get("restore_root")
    if restore_root is not None:
        try:
            snap = ServingEngine.load_snapshot(restore_root)
            overrides = {"metrics_labels": labels}
            if kwargs.get("flight_dump_path") is not None:
                overrides["flight_dump_path"] = kwargs["flight_dump_path"]
            eng = ServingEngine.restore(model, snap, **overrides)
            covered = sorted({int(rs["request_id"]) for rs in
                              snap["slots"] + snap["queue"]})
            return eng, True, covered
        except FileNotFoundError:
            # never snapshotted (or wiped to force the redistribute
            # path) — a fresh build IS the contract, not a failure
            pass
        except Exception:   # noqa: BLE001 — fall back to a fresh build
            logger.warning("replica worker %s: snapshot restore failed; "
                           "building fresh", spec.get("replica"),
                           exc_info=True)
    eng = ServingEngine(model, seed=spec.get("seed", 0),
                        metrics_labels=labels, **kwargs)
    return eng, False, []


def _arm_worker_faults(specs: List[Dict[str, Any]]) -> int:
    """Rebuild a fault plan from JSON specs and arm it in THIS process
    — chaos drives engine-level sites (decode.dispatch,
    serving.snapshot, worker.tick) inside the worker that owns them."""
    from paddle_tpu.resilience import faults as _faults

    plan = _faults.FaultPlan()
    for s in specs:
        exc = None
        if s.get("kind", "raise") == "raise" and s.get("message"):
            exc = RuntimeError(s["message"])
        payload = {k: v for k, v in s.items()
                   if k not in ("site", "kind", "at", "count", "message")}
        plan.add(_faults.Fault(s["site"], kind=s.get("kind", "raise"),
                               at=s.get("at", 0), count=s.get("count", 1),
                               exc=exc, **payload))
    _faults.arm(plan)
    return len(plan.faults)


def _dispatch(eng, op: str, args: Dict[str, Any]):
    """Execute one RPC op against the worker's engine."""
    if op == "ping" or op == "status":
        return True
    if op == "submit":
        return int(eng.submit(decode_request(args["request"])))
    if op == "admit_resumable":
        return int(eng.admit_resumable(decode_request(args["request"]),
                                       tokens=args.get("tokens")))
    if op == "release_request":
        toks = eng.release_request(int(args["rid"]))
        return None if toks is None else [int(t) for t in toks]
    if op == "step":
        out = eng.step()
        results = [encode_result(eng.results.pop(rid))
                   for rid in out.get("finished", ())
                   if rid in eng.results]
        return {"active": out.get("active", 0),
                "queued": out.get("queued", 0),
                "finished": [int(r) for r in out.get("finished", ())],
                "results": results}
    if op == "drain":
        eng.drain(max_steps=args.get("max_steps"))
        results = [encode_result(r) for r in eng.results.values()]
        eng.results.clear()
        return {"results": results}
    if op == "inflight":
        return {str(rid): [int(t) for t in toks]
                for rid, toks in eng.inflight_tokens().items()}
    if op == "estimated_ttft":
        return eng.estimated_ttft_s(decode_request(args["request"]),
                                    default=args.get("default", 0.0))
    if op == "save_snapshot":
        return eng.save_snapshot(args["root"])
    if op == "snapshot_roundtrip":
        from paddle_tpu.analysis import runtime as _sanitizer
        _sanitizer.snapshot_roundtrip(eng)
        return True
    if op == "stats":
        return {k: v for k, v in eng.stats.items()
                if isinstance(v, (int, float))}
    if op == "reset_stats":
        eng.reset_stats()
        return True
    if op == "set_overload":
        if "max_queue" in args:
            eng.max_queue = args["max_queue"]
        if "shed_infeasible" in args:
            eng.shed_infeasible = bool(args["shed_infeasible"])
        return True
    if op == "clear_prefix":
        if eng.prefix_cache is not None:
            eng.prefix_cache.clear()
        return True
    if op == "block_fetch":
        return encode_block_entries(
            eng.export_prefix_blocks(args.get("keys") or []))
    if op == "block_put":
        return int(eng.import_prefix_blocks(
            decode_block_entries(args.get("entries") or {})))
    if op == "arm_faults":
        return _arm_worker_faults(args.get("faults") or [])
    if op == "disarm_faults":
        from paddle_tpu.resilience import faults as _faults
        _faults.disarm()
        return True
    if op == "faults_fired":
        from paddle_tpu.resilience import faults as _faults
        plan = _faults.armed()
        return 0 if plan is None else sum(f.fired for f in plan.faults)
    if op == "shutdown":
        return True
    raise ValueError(f"unknown worker op {op!r}")


def worker_main(conn, spec: Dict[str, Any]):
    """Child-process entry: build the engine, handshake, serve RPCs
    until shutdown or parent EOF. Runs under mp's spawn context — a
    fresh interpreter, fresh jax, fresh metrics registry."""
    from paddle_tpu.resilience import faults as _faults

    # the parent's ctrl-C must not tear workers mid-protocol; the
    # router shuts us down explicitly (or dies, which EOFs the pipe)
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    chan = Channel(conn)
    try:
        from paddle_tpu.core import compile_cache
        compile_cache.enable()
        eng, restored, covered = _build_engine(spec)
    except BaseException as e:  # noqa: BLE001 — report, then die
        try:
            chan.send({"ok": False, "error": encode_error(e)})
        except TransportError:
            pass
        return
    chan.send({
        "ok": True, "pid": os.getpid(), "protocol": PROTOCOL_VERSION,
        "restored": restored, "covered": covered,
        "block_tokens": eng.block_tokens, "max_seq_len": eng.max_seq_len,
        "max_queue": eng.max_queue,
        "pool_blocks": eng.pool.num_blocks,
        "has_prefix_cache": eng.prefix_cache is not None,
        "status": _engine_status(eng),
    })
    while True:
        try:
            msg = chan.recv()
        except TransportClosed:
            break               # parent gone: nothing left to serve
        except TransportCorruption:
            continue            # torn inbound frame: drop, stay alive
        seq, op = msg.get("seq"), msg.get("op", "")
        try:
            # the per-message fault site: a 'hang' here holds the reply
            # open (a live-but-hung worker, for the wall-clock
            # heartbeat to catch); raising kinds surface as RPC errors
            f = _faults.maybe_fire("worker.tick")
            if f is not None and f.kind == "hang":
                time.sleep(float(f.payload.get("seconds", 3600.0)))
            out = _dispatch(eng, op, msg.get("args") or {})
            reply = {"seq": seq, "ok": True, "out": out,
                     "status": _engine_status(eng)}
        except Exception as e:  # noqa: BLE001 — every app error rides back
            reply = {"seq": seq, "ok": False, "error": encode_error(e),
                     "status": _engine_status(eng)}
        try:
            chan.send(reply)
        except TransportClosed:
            break
        if op == "shutdown":
            break
    try:
        eng.close()
    except Exception:   # noqa: BLE001 — exiting anyway
        pass


# ----------------------------------------------------------- proxy side
class _PoolView:
    """Router-visible occupancy of the worker's block pool:
    ``num_blocks`` is static (handshake), ``used_blocks`` reads the
    piggybacked status — exact under the single-client discipline."""

    __slots__ = ("_proxy", "num_blocks")

    def __init__(self, proxy, num_blocks: int):
        self._proxy = proxy
        self.num_blocks = int(num_blocks)

    @property
    def used_blocks(self) -> int:
        return int(self._proxy._status.get("pool_used", 0))


class _PrefixCacheView:
    """Hit/lookup counters of the worker's prefix cache (status
    piggyback) + the clear() control surface the benches use."""

    __slots__ = ("_proxy",)

    def __init__(self, proxy):
        self._proxy = proxy

    @property
    def hit_blocks(self) -> int:
        return int(self._proxy._status.get("prefix_hits", 0))

    @property
    def lookup_blocks(self) -> int:
        return int(self._proxy._status.get("prefix_lookups", 0))

    def clear(self):
        self._proxy._rpc("clear_prefix")


class ReplicaProxy:
    """The router-side handle of one worker process, duck-typing the
    engine surface the Router drives (class docstring up top has the
    failure semantics). Not thread-safe — one client, one call in
    flight, exactly like the in-process engine it stands in for."""

    def __init__(self, proc, chan, hello: Dict[str, Any], *, replica: int,
                 rpc_timeout_s: float, retry_policy):
        self._proc = proc
        self._chan = chan
        self.replica = int(replica)
        self.pid = int(hello["pid"])
        self.restored = bool(hello.get("restored"))
        self.covered = [int(r) for r in hello.get("covered", [])]
        self.block_tokens = int(hello["block_tokens"])
        self.max_seq_len = int(hello["max_seq_len"])
        self._max_queue = hello.get("max_queue")
        self._shed_infeasible = False
        self.pool = _PoolView(self, hello["pool_blocks"])
        self.prefix_cache = (_PrefixCacheView(self)
                             if hello.get("has_prefix_cache") else None)
        self.mesh = None        # processes mode is single-device per worker
        self.results: Dict[int, Any] = {}
        self._status: Dict[str, Any] = dict(hello.get("status") or {})
        self._stats_cache: Dict[str, float] = {}
        self._rpc_timeout_s = float(rpc_timeout_s)
        # per-replica seed: N proxies retrying the same dead peer must
        # not synchronize into a retry storm (seeded jitter, PR 4)
        self._retry = _dc_replace(retry_policy,
                                  seed=retry_policy.seed + self.replica)
        self._seq = 0
        self._closed = False
        self._kill_next_step = False
        from paddle_tpu.observability import registry
        self._reg = registry()

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def start(cls, model_factory, *, engine_kwargs: Dict[str, Any],
              replica: int, seed: int = 0,
              restore_root: Optional[str] = None,
              rpc_timeout_s: float = 180.0,
              start_timeout_s: float = 300.0,
              retry_policy=None) -> "ReplicaProxy":
        """Spawn one replica worker and handshake it. Raises
        ``RuntimeError`` when the worker fails to build its engine or
        does not answer inside ``start_timeout_s`` (the child is
        SIGKILL-reaped first — a failed start can never leak)."""
        from paddle_tpu.resilience.retry import RetryPolicy

        ctx = mp.get_context("spawn")
        parent_conn, child_conn = ctx.Pipe()
        spec = {"model_factory": model_factory,
                "engine_kwargs": dict(engine_kwargs),
                "replica": int(replica), "seed": int(seed),
                "restore_root": restore_root}
        proc = ctx.Process(target=worker_main, args=(child_conn, spec),
                           name=f"paddle-replica-{replica}", daemon=True)
        proc.start()
        child_conn.close()
        chan = Channel(parent_conn)
        try:
            hello = chan.recv(timeout_s=start_timeout_s)
        except TransportError as e:
            cls._reap_pid(proc)
            raise RuntimeError(
                f"replica {replica} worker failed to start: {e}") from e
        if not hello.get("ok"):
            cls._reap_pid(proc)
            err = hello.get("error") or {}
            raise RuntimeError(
                f"replica {replica} worker engine build failed: "
                f"{err.get('type')}: {err.get('msg')}")
        return cls(proc, chan, hello, replica=replica,
                   rpc_timeout_s=rpc_timeout_s,
                   retry_policy=retry_policy or RetryPolicy())

    @staticmethod
    def _reap_pid(proc):
        """Unconditional child reaping: SIGKILL + join — the one exit
        every failure path funnels through, so a wedged worker can
        never outlive its proxy."""
        try:
            if proc.is_alive():
                os.kill(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, OSError):
            pass
        proc.join(timeout=5.0)

    def _mark_broken(self, why: str):
        if self._closed:
            return
        self._closed = True
        logger.warning("replica %d worker marked broken (%s); reaping "
                       "pid %d", self.replica, why, self.pid)
        self._chan.close()
        self._reap_pid(self._proc)

    def close(self):
        """Graceful shutdown: best-effort shutdown RPC, then the same
        unconditional reap every path ends in. Idempotent."""
        if self._closed:
            return
        self._closed = True
        try:
            self._seq += 1
            self._chan.send({"seq": self._seq, "op": "shutdown",
                             "args": {}})
            self._chan.recv(timeout_s=5.0)
        except TransportError:
            pass
        self._chan.close()
        self._proc.join(timeout=5.0)
        self._reap_pid(self._proc)

    def kill(self, mid_step: bool = False):
        """Real process death (chaos): SIGKILL now, or — ``mid_step``
        — armed to land while the worker is computing its NEXT step
        RPC. Either way the proxy does NOT mark itself closed: the
        router must DISCOVER the death (EOF at the next heartbeat ping
        or step call), exactly like a production crash."""
        if mid_step and not self._status.get("idle", True):
            self._kill_next_step = True
            return
        try:
            os.kill(self.pid, signal.SIGKILL)
        except (ProcessLookupError, OSError):
            pass

    @property
    def closed(self) -> bool:
        return self._closed or self._chan.closed

    # ------------------------------------------------------------------ rpc
    def _rpc(self, op: str, args: Optional[Dict[str, Any]] = None, *,
             timeout_s: Optional[float] = None,
             after_send=None):
        """One framed call. Idempotent ops retry under the seeded
        policy; a lost reply on a non-idempotent op (or retry
        exhaustion, or EOF) marks the proxy broken and reaps the
        worker before re-raising — the router's health machinery sees
        a closed engine, never a half-alive one."""
        from paddle_tpu.resilience.retry import call_with_retry

        if self.closed:
            raise TransportClosed(
                f"replica {self.replica} worker is closed")
        deadline_total = (timeout_s if timeout_s is not None
                          else self._rpc_timeout_s)
        t_wall = time.time()
        t0 = time.perf_counter()

        def attempt():
            self._seq += 1
            seq = self._seq
            self._chan.send({"seq": seq, "op": op, "args": args or {}})
            if after_send is not None:
                after_send()
            deadline = time.perf_counter() + deadline_total
            while True:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    raise TransportTimeout(
                        f"{op} to replica {self.replica} timed out "
                        f"after {deadline_total:.3f}s")
                reply = self._chan.recv(timeout_s=remaining)
                if reply.get("seq") == seq:
                    break
                # stale reply of an earlier timed-out call: drop it
            self._status = reply.get("status") or self._status
            if not reply.get("ok"):
                raise_remote(reply.get("error") or {})
            return reply.get("out")

        self._reg.counter("serving.transport.rpcs", op=op).inc()
        try:
            if op in _IDEMPOTENT_OPS:
                out = call_with_retry(
                    attempt, policy=self._retry,
                    retry_on=(TransportTimeout, TransportCorruption),
                    describe=f"transport.{op}")
            else:
                out = attempt()
        except TransportClosed as e:
            self._reg.counter("serving.transport.rpc_errors",
                              kind="closed").inc()
            self._mark_broken(f"{op}: {e}")
            raise
        except TransportTimeout as e:
            self._reg.counter("serving.transport.rpc_errors",
                              kind="timeout").inc()
            if op not in ("ping", "save_snapshot", "snapshot_roundtrip"):
                # a lost reply leaves non-idempotent state unknown; a
                # ping/snapshot timeout is a liveness datum the health
                # machine (not the transport) adjudicates
                self._mark_broken(f"{op}: {e}")
            raise
        except TransportCorruption as e:
            self._reg.counter("serving.transport.rpc_errors",
                              kind="corrupt").inc()
            self._mark_broken(f"{op}: {e}")
            raise
        dt = time.perf_counter() - t0
        self._reg.sketch("serving.transport.rpc_s").observe(dt)
        from paddle_tpu import observability as obs
        tr = obs.active_tracer()
        if tr is not None:
            tr.record("serving.transport.rpc", ts=t_wall, dur_s=dt,
                      op=op, replica=self.replica)
        return out

    # ----------------------------------------------------- engine surface
    def ping(self, timeout_s: Optional[float] = None) -> bool:
        """Wall-clock liveness probe: False on timeout (hung worker)
        or death — the router's heartbeat counts either as a miss."""
        if self.closed:
            return False
        try:
            self._rpc("ping", timeout_s=timeout_s)
            return True
        except TransportError:
            return False

    def submit(self, request) -> int:
        try:
            return int(self._rpc("submit",
                                 {"request": encode_request(request)}))
        except TransportError as e:
            raise Rejected("replica_unreachable",
                           f"replica {self.replica} worker gone during "
                           f"submit: {e}") from e

    def admit_resumable(self, request, tokens=None) -> int:
        args = {"request": encode_request(request)}
        if tokens is not None:
            args["tokens"] = [int(t) for t in tokens]
        try:
            return int(self._rpc("admit_resumable", args))
        except TransportError as e:
            raise Rejected("replica_unreachable",
                           f"replica {self.replica} worker gone during "
                           f"admit_resumable: {e}") from e

    def release_request(self, request_id: int) -> Optional[List[int]]:
        try:
            toks = self._rpc("release_request",
                             {"rid": int(request_id)})
        except TransportError:
            return None     # worker gone: failover re-places, not us
        return None if toks is None else [int(t) for t in toks]

    def step(self) -> Dict:
        after = None
        if self._kill_next_step:
            self._kill_next_step = False

            def after():
                # land the SIGKILL while the worker computes this tick:
                # the frame is on the wire, the worker is (after a
                # scheduling beat) inside engine.step()
                time.sleep(0.01)
                try:
                    os.kill(self.pid, signal.SIGKILL)
                except (ProcessLookupError, OSError):
                    pass
        out = self._rpc("step", after_send=after)
        for enc in out.get("results", ()):
            res = decode_result(enc)
            # tpu-lint: allow(journal-coverage): mirror of a finish that
            # happened worker-side — the ROUTER journals it when it
            # collects from self.results (the engine-tier finish site)
            self.results[res.request_id] = res
        return {"active": out.get("active", 0),
                "queued": out.get("queued", 0),
                "finished": [int(r) for r in out.get("finished", ())]}

    def drain(self, max_steps: Optional[int] = None) -> Dict[int, Any]:
        out = self._rpc("drain", {"max_steps": max_steps})
        for enc in out.get("results", ()):
            res = decode_result(enc)
            # tpu-lint: allow(journal-coverage): mirror of a worker-side
            # finish — journaled by the router at collection
            self.results[res.request_id] = res
        return self.results

    def inflight_tokens(self) -> Dict[int, List[int]]:
        try:
            out = self._rpc("inflight")
        except TransportError:
            # broken mid-query: report nothing held — the router's
            # orphan healer re-places from its own mirror (any prefix
            # is token-exact) and the reaped worker cannot double-run
            return {}
        return {int(rid): [int(t) for t in toks]
                for rid, toks in out.items()}

    def estimated_ttft_s(self, request, default: float = 0.0) -> float:
        try:
            out = self._rpc("estimated_ttft",
                            {"request": encode_request(request),
                             "default": default})
        except TransportError:
            return default
        return default if out is None else float(out)

    def save_snapshot(self, root: str,
                      timeout_s: Optional[float] = None) -> str:
        return self._rpc("save_snapshot", {"root": root},
                         timeout_s=timeout_s)

    def snapshot_roundtrip(self):
        """Run the PR 13 snapshot/restore sanitizer INSIDE the worker
        (the twin engine must live beside the real one); drift raises
        through the typed-error envelope."""
        return self._rpc("snapshot_roundtrip")

    def export_prefix_blocks(self, keys) -> Dict[str, Any]:
        """Fetch exact prefix-block payloads out of the worker's cache
        (tier store's ``block_fetch`` RPC — NOT idempotent, see
        ``_IDEMPOTENT_OPS``). Best-effort: a broken transport returns
        an empty dict and the router's share just shortens."""
        try:
            out = self._rpc("block_fetch", {"keys": list(keys)})
        except TransportError:
            return {}
        return decode_block_entries(out or {})

    def import_prefix_blocks(self, entries) -> int:
        """Deliver prefix-block payloads into the worker's cache (the
        ``block_put`` RPC — NOT idempotent). Best-effort: a broken
        transport imports nothing (returns 0)."""
        try:
            return int(self._rpc(
                "block_put",
                {"entries": encode_block_entries(entries)}))
        except TransportError:
            return 0

    def arm_faults(self, fault_specs: List[Dict[str, Any]]) -> int:
        """Arm a fault plan inside the worker process — chaos drives
        engine-level sites where the engine actually lives."""
        return int(self._rpc("arm_faults", {"faults": fault_specs}))

    def disarm_faults(self):
        return self._rpc("disarm_faults")

    def faults_fired(self) -> int:
        try:
            return int(self._rpc("faults_fired"))
        except TransportError:
            return 0

    @property
    def stats(self) -> Dict[str, float]:
        if not self.closed:
            try:
                self._stats_cache = dict(self._rpc("stats"))
            except Exception:   # noqa: BLE001 — telemetry, last cache wins
                pass
        return dict(self._stats_cache)

    def reset_stats(self):
        try:
            self._rpc("reset_stats")
        except TransportError:
            pass

    # overload knobs: setters mirror to the worker, getters serve the
    # router's template bookkeeping from the local mirror
    @property
    def max_queue(self):
        return self._max_queue

    @max_queue.setter
    def max_queue(self, v):
        self._max_queue = v
        try:
            self._rpc("set_overload", {"max_queue": v})
        except TransportError:
            pass

    @property
    def shed_infeasible(self):
        return self._shed_infeasible

    @shed_infeasible.setter
    def shed_infeasible(self, v):
        self._shed_infeasible = bool(v)
        try:
            self._rpc("set_overload", {"shed_infeasible": bool(v)})
        except TransportError:
            pass

    # status-cache views (exact: the worker only mutates on our RPCs)
    @property
    def active_slots(self) -> int:
        return int(self._status.get("active", 0))

    @property
    def queued(self) -> int:
        return int(self._status.get("queued", 0))

    @property
    def idle(self) -> bool:
        return bool(self._status.get("idle", True))
