"""Length-prefixed, CRC-framed, versioned RPC transport for the
cross-process serving tier (docs/SERVING.md §Cross-process tier).

The journal's framing discipline (`serving/journal.py`: every line
carries ``crc32(payload)`` and a reader that REJECTS rather than
guesses) reused on the wire.  A frame is::

    +-------+---------+-------+----------+---------+----------------+
    | magic | version | flags | length   | crc32   | payload (JSON) |
    | 4s    | u16     | u16   | u32      | u32     | `length` bytes |
    +-------+---------+-------+----------+---------+----------------+

big-endian, over a ``multiprocessing`` spawn-context pipe (one frame
per ``send_bytes`` message, so the length field is a CONSISTENCY check
against the kernel's own message framing, not a stream delimiter — a
bit-flipped payload fails the CRC without desynchronizing the
connection).  Everything in the payload is compact sorted-key JSON:
the protocol stays greppable in a pipe dump and versionable without a
schema compiler.

Failure classes — typed so the proxy's retry policy can distinguish
"ask again" from "the worker is gone":

* :class:`TransportCorruption` — bad magic / unsupported version /
  length mismatch / CRC mismatch.  The frame is dropped; the caller
  may retry (idempotent ops) because the fault sites fire BEFORE any
  state mutates.
* :class:`TransportTimeout` — the peer did not answer inside the
  wall-clock deadline.  Distinguishes a HUNG worker from a dead one;
  the message contains "timed out" so `retry.is_timeout` matches.
* :class:`TransportClosed` — EOF / broken pipe: the peer process is
  gone.  Never retried; feeds the router's healthy→suspect→dead
  machine.

Fault sites ``transport.send`` / ``transport.recv`` (registered in
`resilience.faults.KNOWN_SITES`) fire BEFORE the write / read, so a
raising fault never leaves a half-written frame and never consumes the
queued one — the retry observes the same world a real transient would
leave behind.
"""

import base64
import json
import struct
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from paddle_tpu.resilience import faults as _faults

__all__ = [
    "MAGIC", "PROTOCOL_VERSION", "Channel", "RemoteError",
    "TransportClosed", "TransportCorruption", "TransportError",
    "TransportTimeout", "decode_block_entries", "decode_frame",
    "decode_request", "decode_result", "encode_block_entries",
    "encode_error", "encode_frame", "encode_request", "encode_result",
    "raise_remote",
]

MAGIC = b"PTRW"                 # Paddle_Tpu Replica Worker
PROTOCOL_VERSION = 1
_HEADER = struct.Struct(">4sHHII")   # magic, version, flags, length, crc32


class TransportError(RuntimeError):
    """Base of every transport-layer failure (never a remote app error)."""


class TransportClosed(TransportError):
    """The peer is gone — EOF, broken pipe, or an already-closed channel."""


class TransportTimeout(TransportError):
    """No reply inside the wall-clock deadline (a hung — not dead — peer)."""


class TransportCorruption(TransportError):
    """A frame failed the magic/version/length/CRC checks and was dropped."""


class RemoteError(RuntimeError):
    """A worker-side exception of a type the parent cannot reconstruct;
    the original type name and message ride in ``str(exc)``."""


def encode_frame(obj: Dict[str, Any]) -> bytes:
    payload = json.dumps(obj, separators=(",", ":"),
                         sort_keys=True).encode("utf-8")
    return _HEADER.pack(MAGIC, PROTOCOL_VERSION, 0, len(payload),
                        zlib.crc32(payload)) + payload


def decode_frame(data: bytes) -> Dict[str, Any]:
    """Parse one frame, REJECTING (never guessing) on any mismatch."""
    if len(data) < _HEADER.size:
        raise TransportCorruption(
            f"short frame: {len(data)} bytes < {_HEADER.size}-byte header")
    magic, version, _flags, length, crc = _HEADER.unpack_from(data)
    if magic != MAGIC:
        raise TransportCorruption(f"bad magic {magic!r} (want {MAGIC!r})")
    if version != PROTOCOL_VERSION:
        raise TransportCorruption(
            f"protocol version {version} (this side speaks "
            f"{PROTOCOL_VERSION})")
    payload = data[_HEADER.size:]
    if len(payload) != length:
        raise TransportCorruption(
            f"length mismatch: header says {length}, got {len(payload)}")
    if zlib.crc32(payload) != crc:
        raise TransportCorruption("payload CRC mismatch (torn frame)")
    try:
        return json.loads(payload.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise TransportCorruption(
            f"CRC-valid frame holds non-JSON payload: {e}") from e


class Channel:
    """One framed endpoint over a ``multiprocessing.connection``
    Connection.  Symmetric — both the router-side proxy and the worker
    loop speak through it.  NOT thread-safe: the tier's single-client
    discipline (only the Router talks to a worker, one RPC in flight)
    is what makes reply matching and the piggybacked status exact."""

    def __init__(self, conn):
        self._conn = conn
        self._closed = False
        from paddle_tpu.observability import registry
        r = registry()
        self._c_sent = r.counter("serving.transport.frames", dir="send")
        self._c_recv = r.counter("serving.transport.frames", dir="recv")
        self._b_sent = r.counter("serving.transport.bytes", dir="send")
        self._b_recv = r.counter("serving.transport.bytes", dir="recv")
        self._c_corrupt = r.counter("serving.transport.corrupt_frames")

    @property
    def closed(self) -> bool:
        return self._closed

    def send(self, obj: Dict[str, Any]):
        _faults.maybe_fire("transport.send")
        if self._closed:
            raise TransportClosed("channel is closed")
        frame = encode_frame(obj)
        try:
            self._conn.send_bytes(frame)
        except (OSError, EOFError, BrokenPipeError) as e:
            self._closed = True
            raise TransportClosed(f"peer gone on send: {e}") from e
        self._c_sent.inc()
        self._b_sent.inc(len(frame))

    def recv(self, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        _faults.maybe_fire("transport.recv")
        if self._closed:
            raise TransportClosed("channel is closed")
        if timeout_s is not None:
            try:
                ready = self._conn.poll(timeout_s)
            except (OSError, EOFError, BrokenPipeError) as e:
                self._closed = True
                raise TransportClosed(f"peer gone on poll: {e}") from e
            if not ready:
                raise TransportTimeout(
                    f"recv timed out after {timeout_s:.3f}s")
        try:
            data = self._conn.recv_bytes()
        except EOFError as e:
            self._closed = True
            raise TransportClosed("peer closed the connection (EOF)") from e
        except (OSError, BrokenPipeError) as e:
            self._closed = True
            raise TransportClosed(f"peer gone on recv: {e}") from e
        self._c_recv.inc()
        self._b_recv.inc(len(data))
        try:
            return decode_frame(data)
        except TransportCorruption:
            self._c_corrupt.inc()
            raise

    def poll(self, timeout_s: float = 0.0) -> bool:
        if self._closed:
            return False
        try:
            return self._conn.poll(timeout_s)
        except (OSError, EOFError, BrokenPipeError):
            self._closed = True
            return False

    def close(self):
        if not self._closed:
            self._closed = True
            try:
                self._conn.close()
            except OSError:
                pass


# ---- payload codecs ---------------------------------------------------------
#
# Requests / results cross the boundary as plain JSON dicts.  Request
# ids are minted PARENT-side (`Request.__init__` numbers them through
# the process-global `_next_req_id`), so decode passes `request_id=`
# through — the worker's `_note_req_id` keeps its own mint ahead of
# everything it has seen, and uniqueness across workers is the parent's
# problem (it is the only minter).

def encode_request(req, tokens=None) -> Dict[str, Any]:
    # tpu-lint: allow(host-sync): API boundary — prompts are host ids
    d = {
        "rid": int(req.request_id),
        "prompt": np.asarray(req.prompt).astype(int).tolist(),
        "max_new_tokens": int(req.max_new_tokens),
        "seed": None if req.seed is None else int(req.seed),
        "deadline_s": req.deadline_s,
        "priority": req.priority,
        "trace_id": req.trace_id,
    }
    if tokens is not None:
        d["tokens"] = [int(t) for t in tokens]
    return d


def decode_request(d: Dict[str, Any]):
    from paddle_tpu.serving.engine import Request
    return Request(d["prompt"], max_new_tokens=d["max_new_tokens"],
                   seed=d.get("seed"), deadline_s=d.get("deadline_s"),
                   priority=d.get("priority", "normal"),
                   request_id=d["rid"], trace_id=d.get("trace_id"))


def encode_result(res) -> Dict[str, Any]:
    # tpu-lint: allow(host-sync): results are host token lists
    return {
        "rid": int(res.request_id),
        "prompt": np.asarray(res.prompt).astype(int).tolist(),
        "tokens": np.asarray(res.tokens).astype(int).tolist(),
        "gen_len": int(res.gen_len),
        "finish": res.finish,
        "ttft_s": res.ttft_s,
        "tpot_s": res.tpot_s,
        "prefix_hit_blocks": int(res.prefix_hit_blocks or 0),
        "trace_id": res.trace_id,
    }


def decode_result(d: Dict[str, Any]):
    from paddle_tpu.serving.engine import RequestResult
    # tpu-lint: allow(journal-coverage, host-sync): pure codec — the
    # finish HAPPENED worker-side (journaled by the router when it
    # collects the result); wire token lists are host ints
    return RequestResult(
        int(d["rid"]), np.asarray(d["prompt"], np.int32),
        np.asarray(d["tokens"], np.int32), int(d["gen_len"]), d["finish"],
        d.get("ttft_s"), d.get("tpot_s"), int(d.get("prefix_hit_blocks", 0)),
        trace_id=d.get("trace_id"))


# ---- prefix-block payloads (tier store, docs/SERVING.md §Hierarchical KV) --
#
# KV block payloads ride the same JSON frames as every other RPC:
# base64 bytes + dtype/shape, so the CRC framing, fault sites and
# greppability are inherited unchanged.  The codec round-trips bf16
# exactly (raw bytes, never a float cast) — a copied prefix block must
# be BITWISE the producing replica's block or the parity contract of
# the hierarchical KV tier breaks.

def encode_block_entries(entries: Dict[str, Tuple[int, Any]]
                         ) -> Dict[str, Dict[str, Any]]:
    """``{chain_key_hex: (depth, kv_array)}`` -> JSON-safe wire dict
    (the ``block_fetch`` reply / ``block_put`` request payload)."""
    out = {}
    for k, (depth, kv) in entries.items():
        # tpu-lint: allow(host-sync): wire payloads are host arrays
        kv = np.ascontiguousarray(kv)
        out[k] = {"d": int(depth), "dtype": str(kv.dtype),
                  "shape": [int(s) for s in kv.shape],
                  "b": base64.b64encode(kv.tobytes()).decode("ascii")}
    return out


def decode_block_entries(d: Dict[str, Dict[str, Any]]
                         ) -> Dict[str, Tuple[int, np.ndarray]]:
    """Inverse of :func:`encode_block_entries`. ``bfloat16`` resolves
    through ``ml_dtypes`` (jax's numpy dtype extensions) — imported
    lazily so the transport stays importable without an accelerator
    stack."""
    entries = {}
    for k, v in d.items():
        try:
            dt = np.dtype(v["dtype"])
        except TypeError:
            import ml_dtypes  # noqa: F401 — registers bf16 et al.
            dt = np.dtype(v["dtype"])
        kv = np.frombuffer(base64.b64decode(v["b"]),
                           dtype=dt).reshape(v["shape"])
        entries[k] = (int(v["d"]), kv)
    return entries


# ---- remote error envelope --------------------------------------------------
#
# Worker-side exceptions round-trip by TYPE NAME: the handful the router
# dispatches on (admission control, restore) reconstruct as their real
# classes; everything else degrades to `RemoteError` with the original
# type in the message — a worker bug must surface as a crash report,
# never as a silently-wrong RPC result.

def _error_types() -> Dict[str, type]:
    from paddle_tpu.analysis.runtime import SnapshotDriftError
    from paddle_tpu.serving.engine import Rejected, RestoreError
    from paddle_tpu.serving.pool import PoolExhausted
    return {
        "Rejected": Rejected, "RestoreError": RestoreError,
        "PoolExhausted": PoolExhausted, "ValueError": ValueError,
        "KeyError": KeyError, "FileNotFoundError": FileNotFoundError,
        "OSError": OSError, "RuntimeError": RuntimeError,
        "TimeoutError": TimeoutError,
        # the mid-soak sanitizer's verdict must keep its type across
        # the process boundary (chaos_bench exits 3 on it by class)
        "SnapshotDriftError": SnapshotDriftError,
    }


def encode_error(exc: BaseException) -> Dict[str, str]:
    d = {"type": type(exc).__name__, "msg": str(exc)}
    reason = getattr(exc, "reason", None)
    if isinstance(reason, str):    # Rejected carries a machine code
        d["reason"] = reason
    return d


def raise_remote(err: Dict[str, str]):
    cls = _error_types().get(err.get("type", ""))
    if cls is None:
        raise RemoteError(
            f"{err.get('type', 'Exception')}: {err.get('msg', '')}")
    if err.get("type") in ("Rejected", "RestoreError"):
        # two-arg ctor: (machine-readable reason, human message)
        raise cls(err.get("reason", "remote"), err.get("msg", ""))
    raise cls(err.get("msg", ""))
