"""From a profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. The
device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation, named by its whole HLO text
(``%fused_paged_decode_step.1 = (...) custom-call(...)``): a Mosaic
kernel is one event whose instruction name is its ``kernel_name`` (seen
on the v5e, PR 23; asynchronous copies sit on a line of their own and
are not counted as busy). Host planes hold the benchmark's own
``bench.*`` spans (``jax.profiler.TraceAnnotation``), on the same clock.

    busy      union of the op intervals of one device, cut to the window
    idle      the window minus busy; each gap is given to the bench span
              the host was in at the gap's middle
    by label  seconds and calls of every op on the device (op_label)

The window is the ``bench.window`` span when the trace has one, else
the span from the first device op to the last.
"""

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
HOST_GAP_S = 1e-4       # a gap this long is the device waiting for the host

Reduced = collections.namedtuple(
    "Reduced", "window_s busy_s by_name gaps n_devices")


def op_label(hlo: str) -> str:
    """A short, stable label for an op event. A kernel (custom-call) is
    labelled by its instruction name without the numeric suffix, so all
    its instances add up under the kernel's name; any other op by its
    instruction name and result type, layouts stripped. The operands are
    dropped: a fusion that consumes a kernel's output must not be
    counted as that kernel."""
    inst, sep, rest = hlo.partition(" = ")
    inst = inst.lstrip("%")
    if not sep:
        return inst
    if " custom-call(" in rest:
        return re.sub(r"\.\d+$", "", inst)
    result = re.sub(r"\{[^}]*\}", "", re.split(r" [\w-]+\(", rest, 1)[0])
    return f"{inst} {result[:48]}"


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_ops(profile) -> dict:
    """{plane name: [(start_ns, end_ns, op name), ...]} sorted by start,
    for every device plane that ran an operation."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for line in plane.lines if line.name == OPS_LINE
               for e in line.events]
        if ops:
            out[plane.name] = sorted(ops)
    return out


def host_spans(profile, prefix: str = SPAN_PREFIX) -> list:
    """[(start_ns, end_ns, name), ...] of the benchmark's own spans."""
    spans = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events if e.name.startswith(prefix)]
    return sorted(spans)


def union(intervals, lo=None, hi=None) -> list:
    """Merged, sorted [(start, end), ...], cut to [lo, hi] when given."""
    out = []
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo, hi) -> list:
    """The idle intervals of [lo, hi] around merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans, t) -> str:
    """Name of the innermost bench span that covers instant ``t``
    (``bench.window`` itself is not an answer), or ``"(no span)"``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and name != WINDOW_SPAN and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "(no span)"


def reduce(profile) -> Reduced:
    """Busy seconds (mean over the devices that ran anything), window
    seconds, seconds and calls by op name (summed over devices), and the
    idle gaps of the first device with the span the host was in."""
    ops = device_ops(profile)
    if not ops:
        raise ValueError("the trace holds no device operation")
    spans = host_spans(profile)
    window = [s for s in spans if s[2] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][0], window[-1][1]
    else:
        lo = min(v[0][0] for v in ops.values())
        hi = max(e for v in ops.values() for _, e, _ in v)
    by_name = collections.defaultdict(lambda: [0.0, 0])
    busy_ns, first_busy = 0.0, None
    for plane in sorted(ops):
        merged = union(ops[plane], lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        if first_busy is None:
            first_busy = merged
        for s, e, name in ops[plane]:
            cut = min(e, hi) - max(s, lo)
            if cut > 0:
                label = op_label(name)
                by_name[label][0] += cut * 1e-9
                by_name[label][1] += 1
    idle = [((e - s) * 1e-9, span_at(spans, (s + e) / 2))
            for s, e in gaps(first_busy, lo, hi)]
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_ns * 1e-9 / len(ops),
                   by_name={k: tuple(v) for k, v in by_name.items()},
                   gaps=idle, n_devices=len(ops))


def name_seconds(reduced: Reduced, needle: str):
    """(seconds, calls) summed over the op labels that contain ``needle``."""
    hit = [v for k, v in reduced.by_name.items() if needle in k]
    return sum(v[0] for v in hit), sum(v[1] for v in hit)


def breakdown(reduced: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a traced line: the device operations that
    took most time, and where the idle time went. Per host span: the sum
    of the gaps of HOST_GAP_S and longer (the device waits for the host)
    and the sum of the shorter ones (between the ops of one program);
    then the longest single gaps."""
    device = sorted(((k, v[0]) for k, v in reduced.by_name.items()),
                    key=lambda kv: -kv[1])[:top]
    sums = collections.defaultdict(lambda: [0.0, 0])
    for sec, name in reduced.gaps:
        kind = "0.1 ms and longer" if sec >= HOST_GAP_S else "under 0.1 ms"
        sums[(name, kind)][0] += sec
        sums[(name, kind)][1] += 1
    totals = sorted(([f"{n}: sum of the {v[1]} gaps {kind}", v[0]]
                     for (n, kind), v in sums.items()), key=lambda kv: -kv[1])
    longest = sorted(([f"{name}: one gap", sec]
                      for sec, name in reduced.gaps), key=lambda kv: -kv[1])
    idle = (totals[:top - 3] + longest[:3])[:top]
    return {"device_ops": [[k, v] for k, v in device], "idle_gaps": idle}


def _escape(name: str) -> str:
    return name.replace("\\", "\\\\").replace('"', '\\"')


def excerpt_text_proto(profile, lo_ns: float, hi_ns: float) -> str:
    """A small trace of the same shape (device ``XLA Ops`` lines and the
    host's bench spans) holding only the events that start in
    [lo_ns, hi_ns): what ``tests/sample.xplane.pb`` was cut with.
    ``ProfileData.text_proto_to_serialized_xspace`` turns it into bytes."""
    planes = []
    groups = [(p, [(s, e, n) for s, e, n in v if lo_ns <= s < hi_ns])
              for p, v in device_ops(profile).items()]
    groups.append(("/host:CPU", [(s, e, n) for s, e, n in host_spans(profile)
                                 if lo_ns <= s < hi_ns or n == WINDOW_SPAN]))
    for plane, events in groups:
        ids = {}
        for _, _, n in events:
            ids.setdefault(n, len(ids) + 1)
        line = OPS_LINE if DEVICE_PLANE.match(plane) else "bench"
        body = "".join(
            f"    events {{ metadata_id: {ids[n]} "
            f"offset_ps: {int(round((s - lo_ns) * 1000))} "
            f"duration_ps: {int(round((e - s) * 1000))} }}\n"
            for s, e, n in (
                (max(s, lo_ns), min(e, hi_ns), n) for s, e, n in events))
        meta = "".join(
            f'  event_metadata {{ key: {i} value {{ id: {i} '
            f'name: "{_escape(n)}" }} }}\n' for n, i in ids.items())
        planes.append(f'planes {{ name: "{plane}"\n  lines {{ name: "{line}" '
                      f'timestamp_ns: 0\n{body}  }}\n{meta}}}\n')
    return "".join(planes)
