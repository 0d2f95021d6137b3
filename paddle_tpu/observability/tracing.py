"""Request-level span tracing for the decode path (and anything else).

A `Tracer` collects `Span` records (name, start, duration, parent,
attrs). `Tracer.span(...)` nests a `jax.profiler.TraceAnnotation` so
host-side spans land in xplane captures alongside the device planes —
the RecordEvent analog (SURVEY.md §5), but attached to a *request*, not
a training step.

Zero-overhead contract: nothing in this module runs on the hot path
unless a tracer is attached (`active_tracer()` is one global read).
`inference.generate` keeps its single-dispatch program when no tracer
is attached; with a tracer it switches to a prefill program + chunked
decode programs so TTFT and per-chunk TPOT are real measurements, not
estimates (the chunked scan applies the identical step function, so
tokens are unchanged — pinned by tests/test_observability.py).
"""

import contextlib
import json
import threading
import time
from typing import Callable, List, Optional

from paddle_tpu.observability.registry import (MetricsRegistry,
                                               append_jsonl_lines,
                                               registry as default_registry)

__all__ = ["Span", "Tracer", "attach", "detach", "active_tracer", "trace",
           "run_traced_decode"]


class Span:
    __slots__ = ("name", "ts", "dur_s", "parent", "attrs")

    def __init__(self, name, ts, parent=None, attrs=None):
        self.name = name
        self.ts = ts
        self.dur_s = 0.0
        self.parent = parent
        self.attrs = attrs if attrs is not None else {}

    def to_dict(self) -> dict:
        return {"name": self.name, "ts": self.ts, "dur_s": self.dur_s,
                "parent": self.parent, "attrs": self.attrs}


class Tracer:
    """Collects spans; mirrors request metrics into a registry.

    decode_chunk: tokens per decode dispatch in traced generate() —
    each chunk is one span (and one device dispatch), so smaller chunks
    trade dispatch overhead for span resolution.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 decode_chunk: int = 32, max_spans: int = 100_000):
        self.registry = registry or default_registry()
        self.decode_chunk = int(decode_chunk)
        self.max_spans = max_spans
        self.spans: List[Span] = []
        # per-THREAD open-span stack: concurrent requests against one
        # attached tracer must not cross-parent each other's spans; the
        # completed-spans list is shared, appended under a lock
        self._tls = threading.local()
        self._lock = threading.Lock()

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        import jax

        stack = self._stack()
        s = Span(name, time.time(),
                 parent=stack[-1].name if stack else None,
                 attrs=attrs)
        stack.append(s)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield s
        finally:
            s.dur_s = time.perf_counter() - t0
            stack.pop()
            with self._lock:
                if len(self.spans) < self.max_spans:
                    self.spans.append(s)

    def record(self, name: str, ts: float, dur_s: float,
               parent: Optional[str] = None, **attrs) -> Span:
        """Append an already-measured span (no open/close nesting) — for
        producers whose spans interleave across many dispatches, like
        the serving engine's per-request TTFT/TPOT spans: a request's
        lifetime brackets other requests' steps, so a stack-scoped
        context manager can't represent it."""
        s = Span(name, ts, parent=parent, attrs=attrs)
        s.dur_s = float(dur_s)
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(s)
        return s

    def span_dicts(self) -> List[dict]:
        return [s.to_dict() for s in self.spans]

    def export_jsonl(self, path: str) -> int:
        """Append one JSON line per span (single O_APPEND write)."""
        return append_jsonl_lines(
            path, (json.dumps(d) for d in self.span_dicts()))


_active: Optional[Tracer] = None


def attach(tracer: Tracer) -> Tracer:
    """Make `tracer` the process-wide active tracer."""
    global _active
    _active = tracer
    return tracer


def detach() -> Optional[Tracer]:
    global _active
    t, _active = _active, None
    return t


def active_tracer() -> Optional[Tracer]:
    return _active


@contextlib.contextmanager
def trace(**tracer_kwargs):
    """`with observability.trace() as t:` — attach a fresh Tracer for the
    block; spans/metrics collected on `t`. Reentrant: a nested trace()
    restores the ENCLOSING tracer on exit (it does not end it)."""
    global _active
    prev = _active
    t = Tracer(**tracer_kwargs)
    attach(t)
    try:
        yield t
    finally:
        if _active is t:
            _active = prev


# ---- the traced decode driver ---------------------------------------------

def run_traced_decode(tracer: Tracer, prefill_call: Callable,
                      decode_call: Callable, *, batch: int,
                      max_new_tokens: int, attrs: dict,
                      deadline_s: Optional[float] = None):
    """Drive a split decode under spans; returns the list of token pieces
    (each (b, n)) to concatenate along axis 1.

    prefill_call() -> (carry, aux); carry[0] is the first sampled token
    (b,). decode_call(carry, aux, i0, nsteps) -> (carry, toks) with toks
    (nsteps, b). Records TTFT (request start → first token *on the
    host*), TPOT (decode span / (new-1)), tokens/s into the tracer's
    registry and onto the request span's attrs.

    deadline_s: per-request wall-clock budget (graceful degradation,
    paddle_tpu.resilience): measured from request start; once a chunk
    boundary finds it spent, the request STOPS and returns the tokens
    produced so far (never fewer than the prefill's first token —
    already-dispatched work is not abandoned), bumping
    ``resilience.deadline_exceeded`` and tagging the request span
    ``deadline_exceeded=True``.

    Sync discipline: each phase is fenced by PULLING token values to the
    host (np.asarray of the tiny token arrays) — the host needs them for
    the result anyway, and a dependent host transfer is a fence on every
    backend.
    """
    import numpy as np

    reg = tracer.registry
    t0 = time.perf_counter()
    with tracer.span("decode.request", batch=batch,
                     max_new_tokens=max_new_tokens, **attrs) as req:
        with tracer.span("decode.prefill",
                         tokens=attrs.get("prompt_len")):
            carry, aux = prefill_call()
            # tpu-lint: allow(host-sync): TTFT fence — tiny token array
            np.asarray(carry[0])
        ttft = time.perf_counter() - t0
        pieces = [carry[0][:, None]]
        i, chunk = 1, max(tracer.decode_chunk, 1)
        cut = False
        while i < max_new_tokens:
            if deadline_s is not None \
                    and time.perf_counter() - t0 >= deadline_s:
                cut = True
                break
            c = min(chunk, max_new_tokens - i)
            with tracer.span("decode.chunk", start=i, tokens=c) as cs:
                carry, toks = decode_call(carry, aux, i, c)
                # tpu-lint: allow(host-sync): chunk fence — tiny array
                np.asarray(toks[-1])
            cs.attrs["tokens_per_sec"] = round(batch * c / cs.dur_s, 1) \
                if cs.dur_s else None
            pieces.append(toks.T)
            i += c
        produced = sum(int(p.shape[1]) for p in pieces)
        dur = time.perf_counter() - t0
        tok_s = batch * produced / dur if dur else 0.0
        tpot = (dur - ttft) / (produced - 1) if produced > 1 else None
        req.attrs.update(ttft_s=round(ttft, 6),
                         tpot_s=round(tpot, 6) if tpot is not None else None,
                         tokens_per_sec=round(tok_s, 1))
        if cut:
            req.attrs.update(deadline_exceeded=True, tokens_produced=produced)
            reg.counter("resilience.deadline_exceeded").inc()
        reg.histogram("decode.ttft_seconds").observe(ttft)
        if tpot is not None:
            reg.histogram("decode.tpot_seconds").observe(tpot)
        reg.counter("decode.requests").inc()
        reg.counter("decode.tokens").inc(batch * produced)
        reg.gauge("decode.tokens_per_sec").set(round(tok_s, 1))
    return pieces
