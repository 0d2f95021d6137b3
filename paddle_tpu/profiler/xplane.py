"""XPlane (.xplane.pb) parsing without a tensorflow dependency.

jax.profiler writes XSpace protobufs (tsl/profiler/protobuf/xplane.proto)
under ``<log_dir>/plugins/profile/<run>/*.xplane.pb``. The reference's
profiler (SURVEY.md §5: python/paddle/profiler, CUPTI tracer) exposes
per-op summaries and chrome-trace export from its own event records; the
TPU-native equivalents come from these traces. This module decodes the
protobuf wire format directly (generic tag/varint/length-delimited
reader + the xplane field numbers) so summaries work on the bare image.

Wire schema (field numbers from xplane.proto):
  XSpace:   planes=1
  XPlane:   id=1 name=2 lines=3 event_metadata=4(map) stat_metadata=5(map)
  XLine:    id=1 name=2 timestamp_ns=3 events=4 display_name=11
  XEvent:   metadata_id=1 offset_ps=2 duration_ps=3 num_occurrences=5
  XEventMetadata: id=1 name=2 display_name=4 stats=5
  XStatMetadata:  id=1 name=2
  XStat:    metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
  map entry: key=1 value=2

Where a device op's FRAMEWORK name is (TPU v5e, jax 0.9.0, seen on the
chip in PR 36): not on the event and not among the event's own stats
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale
Multiplier``: all that ``jax.profiler.ProfileData`` shows), but among
the stats of the event's METADATA entry in the plane's ``event_metadata``
map: ``tf_op`` (``jit(serving_step)/part.attn_in/dot_general:``),
beside ``hlo_category``, ``program_id``, ``flops``, ``bytes_accessed``,
``shape_with_layout``, ``source``. ``XEvent.meta.stats`` holds them;
:func:`part_seconds` splits a device plane's time by program and model
part (``profiler.parts``) from them.
"""

import glob
import json
import os
import re
import struct
from typing import Dict, List, Optional, Tuple

from paddle_tpu.profiler.parts import part_of


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = 0
    val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over a message buffer.

    wire 0 → varint int; wire 1 → 8 raw bytes; wire 2 → bytes;
    wire 5 → 4 raw bytes. Groups (3/4) don't occur in xplane.
    """
    i, n = 0, len(buf)
    while i < n:
        key, i = _read_varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _read_varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _read_varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, v


class XEventMeta:
    """An entry of a plane's ``event_metadata``: what the events that
    name it share. ``stats``: {stat name: value}."""
    __slots__ = ("name", "display", "stats")

    def __init__(self, name, display="", stats=None):
        self.name = name
        self.display = display
        self.stats = stats or {}


class XEvent:
    __slots__ = ("name", "offset_ps", "duration_ps", "occurrences", "meta")

    def __init__(self, name, offset_ps, duration_ps, occurrences, meta):
        self.name = name
        self.offset_ps = offset_ps
        self.duration_ps = duration_ps
        self.occurrences = occurrences
        self.meta = meta


class XLine:
    __slots__ = ("name", "timestamp_ns", "events")

    def __init__(self, name, timestamp_ns, events):
        self.name = name
        self.timestamp_ns = timestamp_ns
        self.events = events


class XPlane:
    __slots__ = ("name", "lines")

    def __init__(self, name, lines):
        self.name = name
        self.lines = lines


def _parse_stat(buf: bytes, stat_names: Dict[int, str]):
    """An XStat -> (stat name, value); a ``ref`` value is the NAME of
    the stat metadata it points at."""
    sid, val = 0, None
    for f, w, v in _fields(buf):
        if f == 1 and w == 0:
            sid = v
        elif f == 2 and w == 1:
            val = struct.unpack("<d", v)[0]
        elif f == 3 and w == 0:
            val = v
        elif f == 4 and w == 0:
            val = v - (1 << 64) if v >= 1 << 63 else v
        elif f == 5 and w == 2:
            val = v.decode("utf-8", "replace")
        elif f == 6 and w == 2:
            val = v
        elif f == 7 and w == 0:
            val = stat_names.get(v, v)
    return stat_names.get(sid, f"stat#{sid}"), val


def _parse_event_metadata(buf: bytes, stat_names: Dict[int, str]):
    mid, name, display, stats = 0, "", "", {}
    for f, w, v in _fields(buf):
        if f == 1 and w == 0:
            mid = v
        elif f == 2 and w == 2:
            name = v.decode("utf-8", "replace")
        elif f == 4 and w == 2:
            display = v.decode("utf-8", "replace")
        elif f == 5 and w == 2:
            key, val = _parse_stat(v, stat_names)
            stats[key] = val
    return mid, XEventMeta(name, display, stats)


def _parse_event(v: bytes):
    """(metadata_id, offset_ps, duration_ps, occurrences) of an XEvent;
    its own stats are skipped by their length. The one loop that runs
    once an event (a served cell's 3 s hold 400 k), so it reads its
    varints in line."""
    mid = off = dur = 0
    occ = 1
    i, n = 0, len(v)
    while i < n:
        key = v[i]              # XEvent's field numbers fit one byte
        i += 1
        wire = key & 7
        if wire == 1:
            i += 8
            continue
        if wire == 5:
            i += 4
            continue
        b = v[i]
        i += 1
        val = b & 0x7F
        shift = 7
        while b & 0x80:
            b = v[i]
            i += 1
            val |= (b & 0x7F) << shift
            shift += 7
        if wire == 2:
            i += val            # a length: skip the payload
        elif key == 8:          # field 1, varint
            mid = val
        elif key == 16:
            off = val
        elif key == 24:
            dur = val
        elif key == 40:
            occ = val
    return mid, off, dur, occ


def _parse_plane(buf: bytes, lines_named=None) -> XPlane:
    """``lines_named``: decode the events of these lines only (the
    others stay, empty)."""
    name = ""
    raw_lines: List[bytes] = []
    raw_meta: List[bytes] = []
    stat_names: Dict[int, str] = {}
    for f, w, v in _fields(buf):
        if f == 2 and w == 2:
            name = v.decode("utf-8", "replace")
        elif f == 3 and w == 2:
            raw_lines.append(v)
        elif f == 4 and w == 2:  # map<int64, XEventMetadata>
            raw_meta += [mv for mf, mw, mv in _fields(v)
                         if mf == 2 and mw == 2]
        elif f == 5 and w == 2:  # map<int64, XStatMetadata>
            for mf, mw, mv in _fields(v):
                if mf == 2 and mw == 2:
                    d = {ef: ev for ef, ew, ev in _fields(mv)}
                    stat_names[d.get(1, 0)] = d.get(2, b"").decode(
                        "utf-8", "replace")
    # the stat names may follow the event metadata in the bytes
    meta: Dict[int, XEventMeta] = dict(
        _parse_event_metadata(mv, stat_names) for mv in raw_meta)
    lines = []
    for lb in raw_lines:
        lname, ts_ns = "", 0
        raw_events = []
        for f, w, v in _fields(lb):
            if f == 2 and w == 2:
                lname = v.decode("utf-8", "replace")
            elif f == 11 and w == 2:
                lname = v.decode("utf-8", "replace") or lname
            elif f == 3 and w == 0:
                ts_ns = v
            elif f == 4 and w == 2:
                raw_events.append(v)
        events = []
        if lines_named is None or lname in lines_named:
            for v in raw_events:
                mid, off, dur, occ = _parse_event(v)
                m = meta.get(mid)
                if m is None:
                    m = meta[mid] = XEventMeta(f"op#{mid}")
                events.append(XEvent(m.display or m.name, off, dur, occ, m))
        lines.append(XLine(lname, ts_ns, events))
    return XPlane(name, lines)


def parse_xspace(path: str, planes_named=None,
                 lines_named=None) -> List[XPlane]:
    """The planes of one ``.xplane.pb``. ``planes_named`` (a predicate on
    the plane's name) and ``lines_named`` (a set of line names) leave
    the rest undecoded: a served cell's host planes hold most of a
    trace's events."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f_, w, v in _fields(buf):
        if f_ != 1 or w != 2:
            continue
        if planes_named is not None:
            pname = next((pv.decode("utf-8", "replace")
                          for pf, pw, pv in _fields(v)
                          if pf == 2 and pw == 2), "")
            if not planes_named(pname):
                continue
        planes.append(_parse_plane(v, lines_named))
    return planes


def find_xplane_files(log_dir: str) -> List[str]:
    return sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))


def load_latest(log_dir: str) -> List[XPlane]:
    files = find_xplane_files(log_dir)
    if not files:
        return []
    planes: List[XPlane] = []
    run_dir = os.path.dirname(files[-1])
    for p in files:
        if os.path.dirname(p) == run_dir:
            planes.extend(parse_xspace(p))
    return planes


# ---- aggregation ----------------------------------------------------------

def op_summary(planes: List[XPlane],
               device_only: bool = True,
               exclude_lines: Tuple = ()) -> List[dict]:
    """Aggregate per-op (event name) totals across device planes.

    `exclude_lines`: line names to skip (e.g. "XLA Modules", whose
    per-module rollup events double-count every op underneath them).
    Returns rows sorted by total time: {name, calls, total_ms, avg_ms, pct}.
    """
    rows: Dict[str, List[float]] = {}
    for plane in planes:
        if device_only and not any(
                k in plane.name for k in ("TPU", "GPU", "/device:")):
            continue
        for line in plane.lines:
            if line.name in exclude_lines:
                continue
            for ev in line.events:
                r = rows.setdefault(ev.name, [0, 0.0])
                r[0] += max(ev.occurrences, 1)
                r[1] += ev.duration_ps / 1e9  # ps → ms
    total = sum(r[1] for r in rows.values()) or 1.0
    out = [{"name": k, "calls": int(v[0]), "total_ms": v[1],
            "avg_ms": v[1] / max(v[0], 1), "pct": 100.0 * v[1] / total}
           for k, v in rows.items()]
    out.sort(key=lambda r: -r["total_ms"])
    return out


def format_summary(rows: List[dict], time_unit: str = "ms",
                   limit: int = 30) -> str:
    unit_div = {"s": 1e3, "ms": 1.0, "us": 1e-3}[time_unit]
    hdr = (f"{'Name':<52} {'Calls':>7} {'Total(' + time_unit + ')':>12} "
           f"{'Avg(' + time_unit + ')':>12} {'Ratio(%)':>9}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows[:limit]:
        nm = r["name"] if len(r["name"]) <= 52 else r["name"][:49] + "..."
        lines.append(f"{nm:<52} {r['calls']:>7} "
                     f"{r['total_ms'] / unit_div:>12.3f} "
                     f"{r['avg_ms'] / unit_div:>12.3f} {r['pct']:>9.2f}")
    if len(rows) > limit:
        lines.append(f"... ({len(rows) - limit} more ops)")
    return "\n".join(lines)


# ---- device time by program and model part ---------------------------------

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
NO_PROGRAM = "(no program)"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:")


def program_kind(module_event_name: str) -> str:
    """``jit_serving_step(6300310404528360631)`` -> ``jit_serving_step``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def _op_facts(meta: XEventMeta):
    """(part or None, backward?, Mosaic kernel?, label) of an op's
    metadata. The part is the first ``part.<name>`` of the op's framework
    name, the ``tf_op`` stat; the backward pass reads
    ``transpose(jvp(part.ffn))``. The label, for ops without a part: the
    instruction's name and its result type without layouts."""
    tf_op = str(meta.stats.get("tf_op", ""))
    inst = meta.display or meta.name.partition(" = ")[0].lstrip("%")
    shape = re.sub(r"\{[^}]*\}", "",
                   str(meta.stats.get("shape_with_layout", "")))
    return (part_of(tf_op), "transpose(" in tf_op,
            "tpu_custom_call" in meta.name, f"{inst} {shape[:48]}".strip())


def part_seconds(plane: XPlane, lo_ns: Optional[float] = None,
                 hi_ns: Optional[float] = None) -> Dict[str, dict]:
    """SELF seconds of one device plane by program kind and model part.

    A run of a program is one event of the ``XLA Modules`` line; an op
    (an event of ``XLA Ops``) is given to the run it starts in, a run
    to its kind (:func:`program_kind`). A ``while``, ``conditional`` or
    ``call`` event holds its body's events in time, so an event counts
    only the time that the events starting inside it do not cover: a
    loop is split among its children's parts and nothing counts twice.
    With ``lo_ns`` / ``hi_ns`` only the runs that lie whole inside count
    (``cut``: the others), ops outside every run only where they lie
    inside.

    -> {kind: {"runs", "cut", "span_s" (the runs' own durations),
    "device_s" (all self seconds: the program's busy time), "kernel_s"
    (Mosaic kernels), "parts": {part: [forward s, backward s]},
    "kernels": {part: s}, "unscoped": {label: s}}}; ops that carry no
    part are ``unscoped``, by label."""
    runs, ops = [], []
    for line in plane.lines:
        base = line.timestamp_ns * 1000
        if line.name == MODULES_LINE:
            runs += [(base + e.offset_ps, base + e.offset_ps + e.duration_ps,
                      program_kind(e.meta.name)) for e in line.events]
        elif line.name == OPS_LINE:
            # the running number keeps the sort off the metadata
            ops += [(base + e.offset_ps, -e.duration_ps, len(ops) + i, e.meta)
                    for i, e in enumerate(line.events)]
    runs.sort()
    ops.sort()                                  # a parent before its child
    lo = -float("inf") if lo_ns is None else lo_ns * 1000
    hi = float("inf") if hi_ns is None else hi_ns * 1000

    out: Dict[str, dict] = {}

    def entry(kind):
        return out.setdefault(kind, dict(
            runs=0, cut=0, span_s=0.0, device_s=0.0, kernel_s=0.0, parts={},
            kernels={}, unscoped={}))

    whole = []
    for s, e, kind in runs:
        inside = lo <= s and e <= hi
        whole.append(inside)
        row = entry(kind)
        row["runs" if inside else "cut"] += 1
        if inside:
            row["span_s"] += (e - s) * 1e-12

    facts: Dict[int, tuple] = {}
    stack: List[list] = []      # [end_ps, self_ps, kind or None, meta]
    r = 0

    def close(item):
        end, self_ps, kind, meta = item
        if kind is None or self_ps <= 0:
            return
        f = facts.get(id(meta))
        if f is None:
            f = facts[id(meta)] = _op_facts(meta)
        part, bwd, kernel, label = f
        row, sec = entry(kind), self_ps * 1e-12
        row["device_s"] += sec
        if kernel:
            row["kernel_s"] += sec
            if part is not None:
                row["kernels"][part] = row["kernels"].get(part, 0.0) + sec
        if part is None:
            row["unscoped"][label] = row["unscoped"].get(label, 0.0) + sec
        else:
            row["parts"].setdefault(part, [0.0, 0.0])[bwd] += sec

    for start, neg_dur, _, meta in ops:
        end = start - neg_dur
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        while r + 1 < len(runs) and runs[r + 1][0] <= start:
            r += 1
        if runs and runs[r][0] <= start < runs[r][1]:
            kind = runs[r][2] if whole[r] else None
        else:
            kind = NO_PROGRAM if lo <= start and end <= hi else None
        if stack:               # the parent does not count what this covers
            stack[-1][1] -= min(end, stack[-1][0]) - start
        stack.append([end, -neg_dur, kind, meta])
    while stack:
        close(stack.pop())
    return {k: v for k, v in out.items() if v["runs"] or v["device_s"]}


def format_parts(table: Dict[str, dict], limit: int = 6) -> str:
    """:func:`part_seconds` as text: a block a program kind, a row a
    part in ms a run, then the largest ops without a part."""
    blocks = []
    for kind, row in sorted(table.items(), key=lambda kv: -kv[1]["device_s"]):
        n = max(row["runs"], 1)
        dev = row["device_s"] or 1.0
        head = (f"{kind}: {row['runs']} runs, {1e3 * row['device_s'] / n:.3f}"
                f" device ms a run ({1e3 * row['span_s'] / n:.3f} from start "
                f"to end), Mosaic kernels "
                f"{100 * row['kernel_s'] / dev:.1f} %")
        lines = [head, f"  {'part':<12} {'ms a run':>10} {'%':>6} "
                       f"{'backward ms':>12} {'kernel ms':>10}"]
        for part, (fwd, bwd) in sorted(row["parts"].items(),
                                       key=lambda kv: -sum(kv[1])):
            lines.append(
                f"  {part:<12} {1e3 * (fwd + bwd) / n:>10.3f} "
                f"{100 * (fwd + bwd) / dev:>6.1f} {1e3 * bwd / n:>12.3f} "
                f"{1e3 * row['kernels'].get(part, 0.0) / n:>10.3f}")
        un = sum(row["unscoped"].values())
        lines.append(f"  {UNSCOPED:<12} {1e3 * un / n:>10.3f} "
                     f"{100 * un / dev:>6.1f}")
        for label, sec in sorted(row["unscoped"].items(),
                                 key=lambda kv: -kv[1])[:limit]:
            lines.append(f"    {1e3 * sec / n:>10.3f}  {label}")
        blocks.append("\n".join(lines))
    return "\n\n".join(blocks)


def parts_report(path: str, lo_ns=None, hi_ns=None) -> Dict[str, dict]:
    """:func:`part_seconds` of the first device plane of one
    ``.xplane.pb`` that ran an op (only that plane's two lines are
    decoded), or {}."""
    for plane in parse_xspace(path, is_device_plane,
                              {OPS_LINE, MODULES_LINE}):
        if any(line.name == OPS_LINE and line.events
               for line in plane.lines):
            return part_seconds(plane, lo_ns, hi_ns)
    return {}


# Residual-attribution buckets for the MoE training step (the r5 profile
# attributed the 22.9 ms dispatch residual to slice/gather fusions).
# First-match wins, so attention fusions don't land in "dispatch" via
# their transposes; anything unmatched stays visible as "other".
MOE_RESIDUAL_BUCKETS: Tuple = (
    ("attention", ("flash", "attention", "softmax")),
    ("optimizer", ("adam", "lamb", "momentum", "weight_decay")),
    # NOTE 'convolution' not 'conv' (would swallow 'convert' dtype casts)
    # and no 'rsqrt' in optimizer (would swallow RMSNorm fusions) — casts
    # and norms land in "other" rather than corrupting the attribution
    ("expert_matmul", ("dot", "einsum", "convolution", "ragged",
                      "matmul")),
    ("dispatch", ("gather", "scatter", "sort", "slice", "dynamic-update",
                  "dynamic_update", "iota", "cumsum", "one-hot", "one_hot",
                  "top-k", "top_k", "select", "transpose", "concatenate",
                  "broadcast", "pad", "reshape", "copy")),
)


def bucket_summary(rows: List[dict],
                   buckets=MOE_RESIDUAL_BUCKETS) -> Dict[str, float]:
    """Attribute `op_summary` rows to named buckets by FIRST substring
    match on the lowercased op/fusion name. Returns {bucket: total_ms}
    including an "other" catch-all — the per-op residual attribution the
    benches dump so a future round can verify a residual actually
    shrank (fusion names don't reveal contents; substring attribution is
    best-effort, which is why the raw top rows ride alongside)."""
    totals = {name: 0.0 for name, _ in buckets}
    totals["other"] = 0.0
    for r in rows:
        nm = r["name"].lower()
        for bname, subs in buckets:
            if any(s in nm for s in subs):
                totals[bname] += r["total_ms"]
                break
        else:
            totals["other"] += r["total_ms"]
    return totals


def roofline_report(log_dir: str, plan: Dict) -> Dict:
    """Join the latest xplane capture against an analytic roofline plan.

    `plan` (see observability.schema.validate_roofline_plan):
      hbm_gbps: float        — HBM bandwidth the DMA floor divides by (GB/s)
      peak_tflops: float     — optional matmul peak (TFLOP/s)
      steps: int             — timed steps the capture covers (divisor)
      phases: [{name, match: [substrings], bytes_per_step,
                flops_per_step}]

    Per phase: measured ms/step comes from `bucket_summary` over the
    capture's op rows (FIRST substring match wins, unmatched ops land in
    "other"); the roofline floor is max(bytes/BW, flops/peak); the
    residual is measured − floor, with the binding bound named ("dma"
    vs "matmul") — the per-phase "% of roofline, named residual" table
    the SCALE.md re-measure rows ask for. Substring attribution is
    best-effort (fusion names don't reveal contents), which is why the
    "other" row and the raw measured numbers ride along.

    Returns {"rows": [...], "other_ms_per_step": float, "table": str}.
    """
    from paddle_tpu.observability.schema import validate_roofline_plan

    validate_roofline_plan(plan)
    planes = load_latest(log_dir)
    # "XLA Modules" rollup events contain every op underneath them —
    # keeping them would double-count the whole capture into "other"
    op_rows = op_summary(planes, exclude_lines=("XLA Modules",))
    if not op_rows:                 # CPU sim: no device plane
        op_rows = op_summary(planes, device_only=False,
                             exclude_lines=("XLA Modules",))
    buckets = tuple((p["name"], tuple(s.lower() for s in p["match"]))
                    for p in plan["phases"])
    totals = bucket_summary(op_rows, buckets)
    steps = max(int(plan.get("steps", 1)), 1)
    bw = float(plan["hbm_gbps"]) * 1e9
    peak = float(plan.get("peak_tflops", 0.0)) * 1e12
    rows = []
    for p in plan["phases"]:
        measured_ms = totals.get(p["name"], 0.0) / steps
        t_dma = float(p.get("bytes_per_step", 0.0)) / bw
        flops = float(p.get("flops_per_step", 0.0))
        t_mxu = flops / peak if peak and flops else 0.0
        roof_ms = max(t_dma, t_mxu) * 1e3
        rows.append({
            "phase": p["name"],
            "measured_ms_per_step": measured_ms,
            "roofline_ms_per_step": roof_ms,
            "frac_of_roofline": (roof_ms / measured_ms
                                 if measured_ms > 0 and roof_ms > 0
                                 else None),
            "bound": ("matmul" if t_mxu > t_dma else "dma") if roof_ms
                     else None,
            "residual_ms_per_step": measured_ms - roof_ms,
        })
    other_ms = totals.get("other", 0.0) / steps
    return {"rows": rows, "other_ms_per_step": other_ms,
            "table": format_roofline(rows, other_ms)}


def format_roofline(rows: List[dict], other_ms: float = 0.0) -> str:
    hdr = (f"{'Phase':<20} {'Measured(ms)':>13} {'Roofline(ms)':>13} "
           f"{'%roof':>7} {'Bound':>7} {'Residual(ms)':>13}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        pct = (f"{100.0 * r['frac_of_roofline']:.1f}"
               if r["frac_of_roofline"] is not None else "-")
        lines.append(
            f"{r['phase']:<20} {r['measured_ms_per_step']:>13.3f} "
            f"{r['roofline_ms_per_step']:>13.3f} {pct:>7} "
            f"{r['bound'] or '-':>7} {r['residual_ms_per_step']:>13.3f}")
    lines.append(f"{'other':<20} {other_ms:>13.3f} {'-':>13} {'-':>7} "
                 f"{'-':>7} {'-':>13}")
    return "\n".join(lines)


# ---- synthetic xspace encoding (test fixtures) -----------------------------

def _enc_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _enc_tag(field: int, wire: int) -> bytes:
    return _enc_varint((field << 3) | wire)


def _enc_bytes(field: int, payload: bytes) -> bytes:
    return _enc_tag(field, 2) + _enc_varint(len(payload)) + payload


def _enc_int(field: int, v: int) -> bytes:
    return _enc_tag(field, 0) + _enc_varint(v)


def build_xspace(planes) -> bytes:
    """Encode a synthetic XSpace protobuf this module can parse back —
    the CPU-only fixture generator for roofline/summary tests (no TPU,
    no tensorflow). `planes` is
    [(plane_name, [(line_name, timestamp_ns,
                    [(event_name, offset_ps, duration_ps, occurrences),
                     ...]), ...]), ...].
    """
    space = b""
    for plane_name, lines in planes:
        # stable metadata ids per event name within the plane
        meta_ids: Dict[str, int] = {}
        for _, _, events in lines:
            for name, *_ in events:
                meta_ids.setdefault(name, len(meta_ids) + 1)
        plane = _enc_bytes(2, plane_name.encode())
        for name, mid in meta_ids.items():
            entry = _enc_int(1, mid) + _enc_bytes(
                2, _enc_int(1, mid) + _enc_bytes(2, name.encode()))
            plane += _enc_bytes(4, entry)   # event_metadata map entry
        for line_name, ts_ns, events in lines:
            line = _enc_bytes(2, line_name.encode()) + _enc_int(3, ts_ns)
            for name, off_ps, dur_ps, occ in events:
                ev = (_enc_int(1, meta_ids[name]) + _enc_int(2, off_ps)
                      + _enc_int(3, dur_ps) + _enc_int(5, occ))
                line += _enc_bytes(4, ev)
            plane += _enc_bytes(3, line)
        space += _enc_bytes(1, plane)
    return space


def write_xspace(planes, log_dir: str, run: str = "run0",
                 host: str = "host0") -> str:
    """Write `build_xspace(planes)` where `load_latest(log_dir)` finds it."""
    d = os.path.join(log_dir, "plugins", "profile", run)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{host}.xplane.pb")
    with open(path, "wb") as f:
        f.write(build_xspace(planes))
    return path


def to_chrome_trace(planes: List[XPlane]) -> dict:
    """Chrome trace-event JSON (catapult format) from xplane events."""
    events = []
    pid = 0
    for plane in planes:
        pid += 1
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": plane.name}})
        tid = 0
        for line in plane.lines:
            tid += 1
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": line.name}})
            base_us = line.timestamp_ns / 1e3
            for ev in line.events:
                events.append({
                    "ph": "X", "pid": pid, "tid": tid, "name": ev.name,
                    "ts": base_us + ev.offset_ps / 1e6,
                    "dur": ev.duration_ps / 1e6,
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(log_dir: str, out_path: Optional[str] = None) -> str:
    planes = load_latest(log_dir)
    out_path = out_path or os.path.join(log_dir, "trace.json")
    with open(out_path, "w") as f:
        json.dump(to_chrome_trace(planes), f)
    return out_path


def device_total_seconds(log_dir: str, name_substr: str) -> Optional[float]:
    """Total device execution seconds of modules whose name contains
    `name_substr`, from the latest trace in log_dir ('XLA Modules' line).
    Returns None when no matching events exist. Shared by the benches —
    device-clock timing leaves host dispatch time out."""
    total = 0
    for plane in load_latest(log_dir):
        for line in plane.lines:
            if line.name == "XLA Modules":
                total += sum(e.duration_ps for e in line.events
                             if name_substr in e.name)
    return total / 1e12 if total else None
