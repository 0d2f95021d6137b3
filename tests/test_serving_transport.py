"""The CRC-framed RPC transport (docs/SERVING.md §Cross-process tier).

Pins the frame discipline (reject, never guess: magic / version /
length / CRC / JSON all checked), the typed failure classes
(corruption vs timeout vs EOF), the fault sites firing BEFORE I/O (a
raising fault never consumes the queued frame), and the payload codecs
round-tripping requests / results / typed errors — including the
two-arg ``Rejected(reason, msg)`` reconstruction the router's placement
loop dispatches on.
"""

import multiprocessing as mp
import struct
import zlib

import numpy as np
import pytest

from paddle_tpu.resilience import Fault, FaultPlan, faults
from paddle_tpu.serving import transport as tp
from paddle_tpu.serving.engine import Rejected, Request, RequestResult


@pytest.fixture
def pipe_pair():
    ctx = mp.get_context("spawn")
    a, b = ctx.Pipe()
    ca, cb = tp.Channel(a), tp.Channel(b)
    yield ca, cb, a, b
    ca.close()
    cb.close()


# ------------------------------------------------------------- framing

def test_frame_roundtrip():
    obj = {"op": "step", "seq": 7, "args": {"xs": [1, 2, 3]}}
    assert tp.decode_frame(tp.encode_frame(obj)) == obj


def test_frame_header_layout_is_versioned():
    raw = tp.encode_frame({"a": 1})
    magic, version, flags, length, crc = struct.Struct(
        ">4sHHII").unpack_from(raw)
    assert magic == tp.MAGIC and version == tp.PROTOCOL_VERSION
    payload = raw[16:]
    assert len(payload) == length and zlib.crc32(payload) == crc


@pytest.mark.parametrize("mutate, what", [
    (lambda r: r[:10], "short frame"),
    (lambda r: b"XXXX" + r[4:], "bad magic"),
    (lambda r: r[:4] + struct.pack(">H", 99) + r[6:], "version"),
    (lambda r: r + b"extra", "length mismatch"),
    (lambda r: r[:-1] + bytes([r[-1] ^ 0x5A]), "CRC mismatch"),
])
def test_decode_rejects_corruption(mutate, what):
    raw = tp.encode_frame({"op": "ping", "seq": 1})
    with pytest.raises(tp.TransportCorruption, match=what):
        tp.decode_frame(mutate(raw))


def test_crc_valid_non_json_rejected():
    payload = b"\xff\xfe not json"
    raw = struct.Struct(">4sHHII").pack(
        tp.MAGIC, tp.PROTOCOL_VERSION, 0, len(payload),
        zlib.crc32(payload)) + payload
    with pytest.raises(tp.TransportCorruption, match="non-JSON"):
        tp.decode_frame(raw)


# ------------------------------------------------------------- channel

def test_channel_roundtrip_and_timeout(pipe_pair):
    ca, cb, _, _ = pipe_pair
    ca.send({"op": "ping", "seq": 1})
    assert cb.recv(timeout_s=5.0) == {"op": "ping", "seq": 1}
    with pytest.raises(tp.TransportTimeout, match="timed out"):
        cb.recv(timeout_s=0.05)


def test_channel_rejects_torn_frame_and_counts(pipe_pair):
    from paddle_tpu.observability import registry
    ca, cb, a_conn, _ = pipe_pair
    before = registry().counter_total("serving.transport.corrupt_frames")
    raw = bytearray(tp.encode_frame({"op": "ping", "seq": 1}))
    raw[-1] ^= 0x5A     # flip one payload bit: CRC must catch it
    a_conn.send_bytes(bytes(raw))
    with pytest.raises(tp.TransportCorruption):
        cb.recv(timeout_s=5.0)
    after = registry().counter_total("serving.transport.corrupt_frames")
    assert after == before + 1
    # the connection did NOT desynchronize: the next good frame arrives
    ca.send({"op": "ping", "seq": 2})
    assert cb.recv(timeout_s=5.0)["seq"] == 2


def test_channel_eof_is_closed(pipe_pair):
    ca, cb, _, _ = pipe_pair
    ca.close()
    with pytest.raises(tp.TransportClosed):
        cb.recv(timeout_s=5.0)
    assert cb.closed
    with pytest.raises(tp.TransportClosed):
        cb.send({"op": "ping"})


# ---------------------------------------------------------- fault sites

def test_transport_fault_sites_fire_before_io(pipe_pair):
    """transport.send / transport.recv raise BEFORE the write/read: the
    frame is never half-written, and the queued inbound frame survives
    the injected recv failure for the retry to consume."""
    ca, cb, _, _ = pipe_pair
    ca.send({"op": "ping", "seq": 1})    # queued before arming
    plan = FaultPlan(
        Fault("transport.recv", kind="raise",
              exc=tp.TransportCorruption("injected: torn frame")),
        Fault("transport.send", kind="raise", at=0,
              exc=tp.TransportCorruption("injected: torn frame")))
    faults.arm(plan)
    try:
        with pytest.raises(tp.TransportCorruption):
            cb.recv(timeout_s=5.0)
        with pytest.raises(tp.TransportCorruption):
            ca.send({"op": "ping", "seq": 2})
    finally:
        faults.disarm()
    # the retry observes the same world a real transient would leave:
    # the first frame is still queued, the channel still works
    assert cb.recv(timeout_s=5.0)["seq"] == 1
    ca.send({"op": "ping", "seq": 3})
    assert cb.recv(timeout_s=5.0)["seq"] == 3
    assert not ca.closed and not cb.closed


def test_transport_sites_registered():
    for site in ("transport.send", "transport.recv", "worker.tick"):
        assert site in faults.KNOWN_SITES


# ------------------------------------------------------------- codecs

def test_request_codec_roundtrip():
    req = Request(np.array([5, 6, 7], np.int32), max_new_tokens=4,
                  seed=11, deadline_s=2.5, priority="high")
    d = tp.encode_request(req, tokens=[9, 10])
    import json
    d = json.loads(json.dumps(d))   # must survive the wire encoding
    back = tp.decode_request(d)
    assert back.request_id == req.request_id
    assert back.trace_id == req.trace_id
    assert list(back.prompt) == [5, 6, 7]
    assert (back.max_new_tokens, back.seed, back.deadline_s,
            back.priority) == (4, 11, 2.5, "high")
    assert d["tokens"] == [9, 10]


def test_result_codec_roundtrip():
    res = RequestResult(3, np.array([1, 2], np.int32),
                        np.array([8, 9], np.int32), 2, "length",
                        0.5, 0.1, 1, trace_id="abcd" * 4)
    back = tp.decode_result(tp.encode_result(res))
    assert back.request_id == 3 and back.finish == "length"
    assert list(back.tokens) == [8, 9] and back.trace_id == "abcd" * 4
    assert back.prefix_hit_blocks == 1


def test_error_envelope_reconstructs_typed_errors():
    err = tp.encode_error(Rejected("queue_full", "no room"))
    with pytest.raises(Rejected) as ei:
        tp.raise_remote(err)
    assert ei.value.reason == "queue_full"  # the machine code survives
    with pytest.raises(tp.RemoteError, match="SomethingWeird"):
        tp.raise_remote({"type": "SomethingWeird", "msg": "?"})
    from paddle_tpu.analysis.runtime import SnapshotDriftError
    with pytest.raises(SnapshotDriftError):
        tp.raise_remote(tp.encode_error(SnapshotDriftError("drift")))
