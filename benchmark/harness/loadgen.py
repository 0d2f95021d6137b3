"""Seeded traffic: request lists and arrival schedules from a traffic file.

The generator idea (Poisson arrivals, an open loop that sleeps only when
idle) is copied from ``examples/load_bench.py``
(``gen_arrivals``, ``drive_open_loop``) and corrected: lengths come from
the mix's own distributions instead of toy constants, every draw comes
from a seeded ``numpy.random.Generator``, and waits are counted from the
instant a request was *due*, so a late generator or a queue in front of
the engine shows in the latency.

A traffic mix is a data file; this module is the one general reader of
it. A new mix must need no new code here.
"""

import collections
import math
import statistics

import numpy as np

Req = collections.namedtuple("Req", "index due_s prompt max_new")
_NORMAL = statistics.NormalDist()
# Arrival instants and the order of the lengths are ONE fixed trace per mix,
# replayed in every run as a recorded trace would be; ``--seed`` draws the
# token ids (and, elsewhere, the weights). A tail percentile at four fifths
# of the knee is set by a handful of episodes in which every slot is taken;
# when each seed drew its own arrivals it moved by tens of percent from seed
# to seed, and the median gap by 2 % (PERF.md, PR 23).
TRACE_SEED = 0


def _quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    """The length at probability ``u`` of a distribution entry."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.asarray([_NORMAL.inv_cdf(float(x)) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(
            np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` integer lengths from one distribution entry of a mix, as a
    FIXED multiset in a seeded order: the lengths are the distribution's
    quantiles at (i + 0.5) / n, shuffled. Every seed then offers the
    same total work and differs only in order and timing, which is what
    keeps a tail percentile steady between runs.

    ``lognormal``: median and sigma of the underlying normal, clipped to
    [min, max]. ``uniform``: integers in [min, max].
    """
    return rng.permutation(_quantile(spec, (np.arange(n) + 0.5) / max(n, 1)))


def length_range(spec: dict):
    """(min, max) a distribution entry can produce."""
    return int(spec["min"]), int(spec["max"])


def prompt_buckets(spec: dict, block_tokens: int):
    """Every padded prompt length (a multiple of ``block_tokens``) the
    mix can produce: the wave-prefill programs warm-up has to run."""
    lo, hi = length_range(spec)
    first, last = -(-lo // block_tokens), -(-hi // block_tokens)
    return [b * block_tokens for b in range(first, last + 1)]


def arrivals(spec: dict, n: int, start_s: float, end_s: float, rng):
    """``n`` due times in [start_s, end_s), sorted. ``poisson``: a
    Poisson process given its count, which is ``n`` independent uniform
    instants (a fixed amount of work per run, random spacing)."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return np.sort(rng.uniform(start_s, end_s, n))


def make_requests(traffic: dict, n: int, vocab: int, trace_rng, token_rng,
                  due=None):
    """``n`` requests of the mix: lengths from the mix's distributions in
    the order ``trace_rng`` gives (draw_lengths), uniform random token
    ids from ``token_rng`` (so no prefix is shared)."""
    plen = draw_lengths(traffic["prompt_len"], n, trace_rng)
    olen = draw_lengths(traffic["output_len"], n, trace_rng)
    reqs = []
    for i in range(n):
        prompt = token_rng.integers(3, vocab, int(plen[i]), dtype=np.int32)
        reqs.append(Req(i, None if due is None else float(due[i]), prompt,
                        int(olen[i])))
    return reqs


def _rngs(seed: int, stream: int):
    """(trace, tokens): the fixed trace's generator and ``--seed``'s."""
    return (np.random.default_rng([TRACE_SEED, stream]),
            np.random.default_rng([int(seed), stream]))


def open_schedule(traffic: dict, rate_rps: float, seconds: float,
                  vocab: int, seed: int):
    """Requests of an open-loop mix: round(rate * seconds) of them due in
    [0, seconds), the measured window, and before them
    round(rate * warm_s) due in [-warm_s, 0), which fill the batch and
    are not measured."""
    trace, tokens = _rngs(seed, 1)
    warm_s = float(traffic["warm_s"])
    n_warm, n = round(rate_rps * warm_s), round(rate_rps * seconds)
    warm = make_requests(traffic, n_warm, vocab, trace, tokens, due=arrivals(
        traffic["arrivals"], n_warm, -warm_s, 0.0, trace))
    main = make_requests(traffic, n, vocab, trace, tokens, due=arrivals(
        traffic["arrivals"], n, 0.0, seconds, trace))
    return warm + [r._replace(index=n_warm + r.index) for r in main]


def backlog_stream(traffic: dict, vocab: int, seed: int, chunk: int = 64):
    """An endless stream of requests for a closed backlog; every
    ``chunk`` of them holds the same multiset of lengths."""
    trace, tokens = _rngs(seed, 2)
    base = 0
    while True:
        for r in make_requests(traffic, chunk, vocab, trace, tokens):
            yield r._replace(index=base + r.index)
        base += chunk


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sequence."""
    return float(np.percentile(np.asarray(values, float), q))


class Feeder:
    """The generator's own queue: requests that are due and not yet
    handed to the engine. ``poll(now)`` stamps each request with the
    instant the loop first saw it as due, which is what lateness
    (``seen - due``) is counted from."""

    def __init__(self, reqs):
        self._future = collections.deque(sorted(reqs, key=lambda r: r.due_s))
        self.waiting = collections.deque()
        self.seen_s = {}

    def poll(self, now: float):
        while self._future and self._future[0].due_s <= now:
            r = self._future.popleft()
            self.seen_s[r.index] = now
            self.waiting.append(r)

    def next_due(self):
        return self._future[0].due_s if self._future else None

    def close(self):
        """The window is over; a schedule has nothing to stop."""

    @property
    def exhausted(self) -> bool:
        return not self._future and not self.waiting


class BacklogFeeder:
    """The same interface over a closed backlog: one request always
    waits, until ``close()``. Its requests have no due time (``due_s`` is
    None): each is due the instant it is handed over."""

    def __init__(self, stream):
        self._stream, self._open = stream, True
        self.waiting = collections.deque([next(stream)])
        self.seen_s = {}

    def poll(self, now: float):
        if self._open and not self.waiting:
            self.waiting.append(next(self._stream))

    def next_due(self):
        return None

    def close(self):
        self._open = False
        self.waiting.clear()

    @property
    def exhausted(self) -> bool:
        return not self._open and not self.waiting
