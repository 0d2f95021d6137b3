"""Continuous-batching serving engine over the fused paged-decode kernel.

``inference.generate`` runs one fixed batch to ``max_new_tokens`` in a
single dispatch: a request that finishes early burns full decode steps
emitting eos padding, and a request that arrives late waits for the
whole batch to drain. This engine (the Orca continuous-batching /
vLLM paged-KV design, PAPERS lineage) instead schedules at *slot*
granularity over a shared paged KV pool:

* **join** — a queued request is admitted when a batch slot and enough
  pool blocks are free; prefill runs apart from the decode dispatch
  (reusing any content-hashed cached prefix blocks, and admissions that
  land on the same tick share one batched prefill program per prompt
  shape), then the slot joins the running decode batch mid-flight;
* **leave** — a slot that hits eos, its token budget, or its deadline
  retires immediately: its blocks return to the pool the same step, no
  eos-padding decode steps are spent on it;
* every decode step is ONE dispatch of the fused paged kernel for all
  active slots, whatever their lengths — per-row positions mask the
  online-softmax walk, so mixed-length slots share the program.

Parity contract (tests/test_serving.py): with greedy sampling a
request's tokens from a merged continuously-batched run are identical to
an isolated ``generate`` call — per-request RNG streams
(``fold_in(PRNGKey(request_seed), t)``) make that hold for sampled
tokens too, because a row's stream never depends on its batch
neighbours.

Overload robustness (docs/SERVING.md §Overload behavior,
tests/test_serving_robustness.py):

* **bounded admission + typed shedding** — ``max_queue`` caps the
  queue; ``shed_infeasible`` rejects requests whose deadline cannot
  even reach a first token under the EWMA capacity estimate. Both shed
  paths raise :class:`Rejected` with a machine-readable ``reason``
  (counted under ``serving.rejected{reason}``) instead of queuing work
  that can only expire;
* **priority preemption with token-exact resume** — per-request
  ``priority`` classes order the queue; when a higher-priority request
  cannot be admitted, the scheduler retires the
  lowest-priority/loosest-deadline slot, frees its blocks and requeues
  it with its generated-so-far tokens. Resume re-prefills the PROMPT
  through the normal wave-prefill program (bitwise the original
  admission's program), REPLAYS the generated tokens through the real
  decode step program (recomputing them via the prefill forward
  rounds one bf16 ulp differently and can flip a near-tie argmax),
  and continues sampling at ``fold_in(seed, count)`` — the same RNG
  stream position an uninterrupted run would use. Together that keeps
  preempt/resume token-identical (greedy and sampled, bf16 and int8);
* **crash-recoverable state** — :meth:`ServingEngine.snapshot` /
  :meth:`save_snapshot` serialize the queue, per-slot generated tokens
  and finished results through the PR 4 integrity-manifest commit
  protocol; :meth:`ServingEngine.restore` re-admits every request via
  the resume path, so a mid-step fault loses nothing.

Chunked prefill (``chunk_tokens=``; docs/SERVING.md §Chunked prefill):
the wave prefill is one blocking program per prompt shape, so a single
long prompt stalls every active decode slot for its whole prefill — a
``serving.step_prefill_s`` outlier and a TPOT p99 spike under a
long-prompt mix. With ``chunk_tokens`` set, an admitted prompt is
processed ``chunk_tokens`` tokens at a time (Sarathi-style), and each
chunk tick is ONE fused program — true coscheduling: the front
group's next chunk AND every decode-ready slot's next token (or
speculative verify tail) dispatch together, with the chunk's block
scatter folded into the decode step's pool pass
(``ops.fused_decode.fused_paged_tick_step``). The per-chunk KV
staging round trip is gone: bf16 mid chunks gather their processed
prefix straight from pool blocks (no carry buffer at all), and int8
prefills thread ONE fixed-shape resident bf16 carry, donated and
RMW'd in place across ticks. Decode TPOT is bounded
by one fused tick instead of one whole prompt, and the pool crosses
one program boundary per tick instead of two (one future ``shard_map``
seam). Same-bucket same-tick admissions form batched chunk ROWS — n
slots advance one chunk each in the same program (wave batching,
recovered). ``decode_per_chunk`` is the interleave budget — at least
that many decode dispatches separate consecutive chunk programs, and
the fused tick's own decode half (which advances every active slot)
counts as the first, so ``decode_per_chunk - 1`` chunkless ticks run
in between (the two-program tick's pacing, preserved).
``chunk_autotune=True`` (with ``slo_tpot_s``) picks the largest chunk
bucket whose predicted fused-tick time fits under the TPOT SLO,
re-evaluated per admission so the compile set stays finite. Chunked
prefill is a *scheduling* change only: tokens are pinned identical to
the monolithic wave (greedy+sampled × bf16+int8, prefix-hit and
preempt-resume cases — tests/test_serving_chunked.py).

Speculative decoding (``speculate=SpecConfig(...)``; docs/SERVING.md
§Speculative decoding): after batched heads, int8 KV, paging and
chunked prefill, decode's remaining cost is its *serial step count* —
every token pays one full weight stream. With speculation armed, each
tick verifies k proposed tokens per active slot in ONE
``fused_paged_verify_step`` dispatch (the paged decode kernel itself,
given a tail of k+1 tokens a slot: the same walk of each row's own
blocks, then a causal append window) and commits the longest proposal
prefix that matches the engine's OWN samples — token-exact acceptance
off each slot's ``fold_in(seed, count)`` stream, so committed tokens
are bit-identical to the non-speculative engine (and to isolated
``generate``; tests/test_serving_spec.py pins greedy+sampled ×
bf16+int8, through preempt/resume and snapshot/restore). Proposals come
from a device-side per-slot n-gram matcher (no extra model, zero
steady-state H2D) or a draft model riding its own block tables over
the same paged machinery.
"""

import collections
import contextlib
import functools
import heapq
import json
import logging
import numbers
import os
import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.profiler.parts import part
from paddle_tpu.ops.fused_decode import (mp_gather_kv_lastdim,
                                         mp_local_kv_lastdim)
from paddle_tpu.serving.pool import (SCRATCH_BLOCK, BlockPool,
                                     HostBlockStore, PoolExhausted,
                                     PrefixCache)
from paddle_tpu.serving.spec import SpecConfig

logger = logging.getLogger("paddle_tpu.serving")

__all__ = ["PRIORITIES", "Rejected", "Request", "RequestResult",
           "RestoreError", "ServingEngine", "SpecConfig",
           "ENGINE_SNAPSHOT_SCHEMA"]

ENGINE_SNAPSHOT_SCHEMA = "paddle_tpu.engine_snapshot/v1"

# token-count buckets for the serving.chunk_tokens histogram (chunk
# sizes are powers-of-two-ish token counts, not latencies)
_CHUNK_SIZE_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)

# rows-per-chunk-dispatch buckets for serving.chunk_rows (small integer
# counts — n same-bucket prefilling slots advancing in one fused tick)
_CHUNK_ROWS_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16)

# chunk-autotune probing cadence: every this many tuned admissions with
# an unmeasured next-larger bucket, pick it once so its tick-time EWMA
# gets a real observation (see _autotune_chunk)
_CHUNK_PROBE_EVERY = 8
# per-bucket probe budget: a probe's own ticks are COLD (fresh
# programs), and cold ticks never feed the EWMAs — only a repeat of
# the same shape dispatches warm and records. Two tries buys that
# repeat; a bucket whose shapes never recur stops costing compile
# chains after the budget instead of re-probing forever
_CHUNK_PROBE_TRIES = 2

# accepted-proposal-length buckets for serving.spec_accepted_len (small
# integer counts, not latencies — k rarely exceeds 8)
_SPEC_LEN_BUCKETS = (0, 1, 2, 3, 4, 6, 8, 12, 16)

#: admission classes, lowest to highest. The queue orders by (priority,
#: submit order); preemption only ever evicts a STRICTLY lower class, so
#: two requests of the same class can never ping-pong each other.
PRIORITIES = ("low", "normal", "high")
_PRIORITY_RANK = {p: i for i, p in enumerate(PRIORITIES)}

# module-wide request-id source. Locked (concurrent submitter threads
# must never mint the same id — results are keyed by it) and bumpable:
# restore() pushes it past every id a snapshot carries so a restored
# engine's NEW submissions cannot collide with re-admitted ones.
_req_id_state = {"next": 0}
_req_id_lock = threading.Lock()


def _abstract_operand(x):
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type,
                                sharding=getattr(x, "sharding", None))


def _program_handle(jitted, bound):
    """Wrap a jitted program with its bound leading arguments and
    attach the ``.jitted``/``.bound`` audit handle
    ``analysis.runtime.donation_report`` lowers the REAL program
    through (docs/ANALYSIS.md §Donation report). ``bound`` is a
    thunk so the handle tracks state swaps (restore/recover). The
    first call keeps its operands' shape, dtype and sharding in
    ``.ran`` (None until then): what
    :meth:`ServingEngine.lowered_programs` lowers the program from."""
    def fn(*a):
        args = (*bound(), *a)
        if fn.ran is None:
            fn.ran = jax.tree.map(_abstract_operand, args)
        return jitted(*args)
    fn.jitted, fn.bound, fn.ran = jitted, bound, None
    return fn


def _next_req_id() -> int:
    with _req_id_lock:
        v = _req_id_state["next"]
        _req_id_state["next"] = v + 1
        return v


def _note_req_id(rid: int):
    """Keep the auto-id source ahead of every explicitly assigned id."""
    with _req_id_lock:
        if rid >= _req_id_state["next"]:
            _req_id_state["next"] = rid + 1


class Rejected(RuntimeError):
    """Typed load-shed signal raised by :meth:`ServingEngine.submit`.

    ``reason`` is machine-readable: ``queue_full`` (bounded queue at
    capacity, no lower-priority victim to displace) or
    ``deadline_infeasible`` (the EWMA capacity estimate says the
    request's deadline expires before its first token). Each rejection
    also increments ``serving.rejected{reason=...}``."""

    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


class DrainTimeout(RuntimeError):
    """Typed drain-deadline failure: ``Router.drain(timeout_s=...)`` /
    ``drain_replica(..., timeout_s=...)`` raise this instead of
    spinning when a replica stops answering inside the wall-clock
    budget. ``replica`` names the stuck replica slot (None when the
    stall is tier-wide) and ``queue_depth`` is the work still parked
    behind it — the two facts an operator needs to decide between
    waiting longer and killing the worker."""

    def __init__(self, msg: str, *, replica=None, queue_depth: int = 0):
        super().__init__(msg)
        self.replica = replica
        self.queue_depth = int(queue_depth)


class RestoreError(ValueError):
    """Typed :meth:`ServingEngine.restore` failure.

    ``reason`` is machine-readable: ``schema`` (the payload is not an
    engine snapshot), ``model_fingerprint`` (the snapshot was taken on
    a different architecture/layer-count/KV-width than the model being
    restored onto — resuming would decode garbage KV), or
    ``draft_model_missing`` (the snapshot armed the draft-model
    proposer, whose model does not serialize — pass
    ``speculate=SpecConfig(..., draft_model=...)`` as a restore
    override). Subclasses ``ValueError`` so pre-existing callers that
    caught that keep working; new callers (the serving router's
    failover path) branch on ``reason`` instead of parsing messages."""

    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


class Request:
    """One generation request.

    Sampling *shape* knobs (temperature/top_k/top_p/eos) live on the
    engine — they are baked into the one shared decode program. Per
    request: the prompt, the token budget, the RNG ``seed`` (defaults to
    a fresh engine-assigned seed; pass the seed an isolated
    ``generate(..., request_seeds=[seed])`` call would use to reproduce
    it exactly), an optional wall-clock ``deadline_s`` measured from
    submit (queue wait included) — on expiry the request retires with
    the tokens it has, mirroring ``generate(deadline_s=...)`` — and a
    ``priority`` class (one of :data:`PRIORITIES`) that orders
    admission and decides who sheds/preempts whom under overload.

    Every argument is validated HERE with a plain ``ValueError`` — a
    bad budget or unknown priority must not surface as an opaque
    failure deep inside the scheduler's ``_admit``.
    """

    __slots__ = ("request_id", "prompt", "max_new_tokens", "seed",
                 "deadline_s", "priority", "trace_id", "_t_submit",
                 "_t_first", "_resume_tokens", "_seq")

    def __init__(self, prompt, max_new_tokens: int = 32,
                 seed: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 priority: str = "normal",
                 request_id: Optional[int] = None,
                 trace_id: Optional[str] = None):
        # tpu-lint: allow(host-sync): API boundary — prompts are host ids
        prompt = np.asarray(prompt)
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"prompt must hold integer token ids, got dtype "
                f"{prompt.dtype}")
        self.prompt = prompt.astype(np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if isinstance(max_new_tokens, bool) \
                or not isinstance(max_new_tokens, numbers.Integral):
            raise ValueError(
                f"max_new_tokens must be an int, got "
                f"{type(max_new_tokens).__name__}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        self.max_new_tokens = int(max_new_tokens)
        if seed is not None and (isinstance(seed, bool)
                                 or not isinstance(seed, numbers.Integral)):
            raise ValueError(f"seed must be an int or None, got "
                             f"{type(seed).__name__}")
        self.seed = None if seed is None else int(seed)
        if deadline_s is not None:
            if isinstance(deadline_s, bool) \
                    or not isinstance(deadline_s, numbers.Real):
                raise ValueError(f"deadline_s must be a number or None, "
                                 f"got {type(deadline_s).__name__}")
            if not deadline_s > 0:
                raise ValueError(
                    f"deadline_s must be > 0 (it is a wall-clock budget "
                    f"from submit), got {deadline_s}")
            deadline_s = float(deadline_s)
        self.deadline_s = deadline_s
        if priority not in _PRIORITY_RANK:
            raise ValueError(f"unknown priority {priority!r}; one of "
                             f"{PRIORITIES}")
        self.priority = priority
        if request_id is None:
            self.request_id = _next_req_id()
        else:
            self.request_id = int(request_id)
            _note_req_id(self.request_id)
        # causal trace id: minted ONCE at first construction, carried
        # verbatim through preempt/resume, snapshot/restore, and router
        # failover migration — every journal event / span / timeline
        # fragment a request produces anywhere in the tier keys on it
        # (docs/OBSERVABILITY.md §Request traces)
        if trace_id is None:
            self.trace_id = uuid.uuid4().hex[:16]
        else:
            self.trace_id = str(trace_id)
        self._t_submit: Optional[float] = None
        # preempt/resume state: the generated-so-far tokens a requeued
        # request re-prefills from (None = fresh), and the original
        # first-token timestamp so TTFT survives a preemption
        self._resume_tokens: Optional[List[int]] = None
        self._t_first: Optional[float] = None
        self._seq: int = 0          # engine submit ordinal (FIFO tiebreak)

    @property
    def rank(self) -> int:
        return _PRIORITY_RANK[self.priority]


class RequestResult:
    """Terminal state of a request. ``tokens`` are the generated ids
    (eos included when hit); ``gen_len`` counts tokens before the first
    eos — the same accounting ``generate(return_lengths=True)`` reports.
    ``finish`` is one of ``eos`` / ``length`` / ``deadline`` / ``shed``
    (a queued request displaced by a higher-priority submit under a
    full bounded queue — ``tokens`` is empty, ``ttft_s`` None)."""

    __slots__ = ("request_id", "prompt", "tokens", "gen_len", "finish",
                 "ttft_s", "tpot_s", "prefix_hit_blocks", "trace_id")

    def __init__(self, request_id, prompt, tokens, gen_len, finish,
                 ttft_s, tpot_s, prefix_hit_blocks, trace_id=None):
        self.request_id = request_id
        self.trace_id = trace_id
        self.prompt = prompt
        # tpu-lint: allow(host-sync): generated tokens are a host list
        self.tokens = np.asarray(tokens, np.int32)
        self.gen_len = int(gen_len)
        self.finish = finish
        self.ttft_s = ttft_s
        self.tpot_s = tpot_s
        self.prefix_hit_blocks = prefix_hit_blocks

    @property
    def ids(self) -> np.ndarray:
        """prompt + generated tokens, the ``generate`` output row."""
        return np.concatenate([self.prompt, self.tokens])


class _Slot:
    __slots__ = ("req", "tok", "pos", "count", "tokens", "blocks", "ntab",
                 "worst_blocks", "t_first", "deadline_at",
                 "prefix_hit_blocks", "feed", "resume",
                 "prefilling", "filled", "R", "hits", "dblocks")

    def __init__(self, req: Request, worst_blocks: int,
                 prefix_hit_blocks: int, feed: np.ndarray,
                 resume: Optional[List[int]]):
        # snapshot-coverage (docs/SERVING.md §Snapshot contract): a
        # slot's tokens/seed ARE its complete resumable state — the
        # cursor and KV fields below are volatile by design, rebuilt
        # when restore() re-admits the request through the resume path
        self.req = req
        # tpu-lint: volatile(reconstructed from tokens by resume replay)
        self.tok = 0            # last sampled, kv not yet appended
        # tpu-lint: volatile(reconstructed from tokens by resume replay)
        self.pos = 0            # append position of the next decode step
        # tpu-lint: volatile(count == len(tokens); resume re-derives it)
        self.count = 0          # tokens generated so far
        self.tokens: List[int] = []
        # tpu-lint: volatile(pool KV never survives a crash by design)
        self.blocks: List[int] = []     # owned pool refs (shared + private)
        # tpu-lint: volatile(block-table depth; re-derived at re-admission)
        self.ntab = 0                   # blocks allocated for this slot
        self.worst_blocks = worst_blocks
        # tpu-lint: volatile(wall-clock; TTFT survives via req._t_first)
        self.t_first: Optional[float] = None
        # tpu-lint: volatile(re-anchored from deadline_remaining_s)
        self.deadline_at: Optional[float] = None
        self.prefix_hit_blocks = prefix_hit_blocks
        # what the prefill program runs over: the PROMPT (for fresh and
        # resumed admissions alike — a resume's generated tokens replay
        # through the decode step program afterwards, _replay_resume;
        # the final generated token is never appended — it becomes the
        # next decode step's input, exactly where an uninterrupted run
        # left off)
        self.feed = feed
        self.resume = resume            # generated-so-far tokens, or None
        # chunked-prefill cursor state (chunk_tokens engines): while
        # `prefilling`, `filled` counts the feed tokens whose KV is
        # already written (starts at the prefix depth R), and `hits`
        # keeps the prefix-cache entries chunk 0 adopts (the int8
        # resident KV carry lives on the slot's _ChunkGroup, not
        # here). A prefilling slot stays OUT of the decode batch (its
        # mirror table row points at scratch) until its last chunk
        # samples the first token.
        # tpu-lint: volatile(restore re-prefills from tokens; the
        # serialized chunk cursor is informational)
        self.prefilling = False
        # tpu-lint: volatile(chunk cursor; re-prefill restarts it)
        self.filled = 0
        # tpu-lint: volatile(prefix depth; re-probed at re-admission)
        self.R = 0                      # prefix-hit depth in tokens
        # tpu-lint: volatile(prefix-cache refs; re-probed at re-admission)
        self.hits = None
        # draft-proposer block table rows (speculative engines with a
        # draft model: the draft's KV pages for this slot)
        # tpu-lint: volatile(draft pages rebuilt at resume adoption)
        self.dblocks: List[int] = []


class _Parked:
    """One swapped-out request's host-tier KV (docs/SERVING.md
    §Hierarchical KV): the gathered device buffer until the background
    drain lands it in the ``HostBlockStore`` (``dev`` → ``host_ids``),
    plus the cursor state a swap-in rebuilds the slot from WITHOUT a
    prefill program or a replay dispatch — the generated-position KV
    comes back bitwise. Parked KV is a resume accelerator, not durable
    state: the queue's serialized resume tokens remain the crash story
    (restore re-prefills where a live engine would swap in)."""

    __slots__ = ("rid", "dev", "host_ids", "n", "scales", "pos", "tok",
                 "count", "tokens", "worst_blocks", "prefix_hit_blocks",
                 "t_swap")

    def __init__(self, rid, dev, n, scales, pos, tok, count, tokens,
                 worst_blocks, prefix_hit_blocks):
        self.rid = rid
        self.dev = dev          # gathered (L, n_pad, BT, 2dkv) device buf
        self.host_ids: Optional[List[int]] = None
        self.n = int(n)         # real block count (rest of dev is pad)
        self.scales = scales    # int8 per-slot scale row copy, or None
        self.pos = int(pos)
        self.tok = int(tok)
        self.count = int(count)
        self.tokens = tokens    # generated-so-far (owned copy)
        self.worst_blocks = int(worst_blocks)
        self.prefix_hit_blocks = int(prefix_hit_blocks)
        self.t_swap = time.perf_counter()   # for the prefetch EWMA


class _ChunkGroup:
    """A batch of same-bucket prefilling slots advancing ONE chunk per
    fused tick (the batched-chunk-rows half of the one-program tick):
    every row shares the prefix depth ``R``, the chunk size ``chunk``
    (the autotuner's per-admission pick) and the padded feed bucket
    ``C_pad = R + ceil((P-R)/chunk)*chunk``, so the whole group's
    cursors advance in lockstep and one fused-tick program serves all
    ``n`` rows — same-tick same-shape admissions recover the wave
    batching the n=1 chunk FIFO serialized.

    The group's inputs are DEVICE-RESIDENT from creation (feed ids,
    block-id table, last-token indices, seeds, int8 valid lengths and
    prefix copies), so steady mid-prefill fused ticks re-dispatch with
    zero H2D uploads. On int8 pools ``carry`` is the resident bf16 KV
    buffer (L, n, C_pad, 2dkv) the chunk programs RMW in place
    (donated — ``analysis.runtime.donation_report`` pins the
    aliasing); bf16 pools need NO carry at all — every completed
    chunk's blocks are already in the pool, so the next chunk gathers
    its processed prefix straight from pool blocks."""

    __slots__ = ("rows", "R", "chunk", "C_pad", "int8", "carry",
                 "dev_ids", "dev_bids", "dev_last", "dev_seeds",
                 "dev_valid", "dev_prefix")

    def __init__(self, rows, R, chunk, C_pad, int8):
        self.rows = rows            # [(slot_idx, slot)]
        self.R = int(R)
        self.chunk = int(chunk)
        self.C_pad = int(C_pad)
        self.int8 = int8
        # tpu-lint: volatile(device KV carry; restore re-prefills)
        self.carry = None
        self.dev_ids = self.dev_bids = None
        self.dev_last = self.dev_seeds = None
        self.dev_valid = self.dev_prefix = None

    @property
    def n(self) -> int:
        return len(self.rows)

    @property
    def start(self) -> int:
        """The group's chunk cursor (rows advance in lockstep)."""
        return self.rows[0][1].filled

    @property
    def kind(self) -> str:
        return "last" if self.start + self.chunk >= self.C_pad else "mid"

    def args(self):
        """The chunk half's traced arguments at the current cursor —
        every one device-resident (the steady-tick 0-H2D invariant)."""
        start, last = self.start, self.kind == "last"
        a = []
        if self.int8 and start > self.R:
            a.append(self.carry)
        a += [self.dev_ids, self.dev_bids]
        if self.dev_prefix is not None and start == self.R:
            a.append(self.dev_prefix)
        if last:
            a += [self.dev_last, self.dev_seeds]
            if self.int8:
                a.append(self.dev_valid)
        return a


class _PriorityQueue:
    """Priority-then-FIFO request queue: a heap ordered by
    (-priority_rank, submit_seq) with lazy deletion. push/pop are
    O(log n); the displacement-victim scan and the estimator walk are
    O(n) over the raw heap (``items()``, no sort — neither cares about
    order); only ``__iter__`` (snapshots) pays a sort."""

    def __init__(self):
        self._heap: List = []           # (neg_rank, seq, req)
        self._removed = set()           # request_ids shed before pop
        self._live = 0

    def __len__(self) -> int:
        return self._live

    def __bool__(self) -> bool:
        return self._live > 0

    def push(self, req: Request):
        heapq.heappush(self._heap, (-req.rank, req._seq, req))
        self._live += 1

    def _prune(self):
        while self._heap and self._heap[0][2].request_id in self._removed:
            self._removed.discard(heapq.heappop(self._heap)[2].request_id)

    def peek(self) -> Optional[Request]:
        self._prune()
        return self._heap[0][2] if self._heap else None

    def pop(self) -> Request:
        self._prune()
        self._live -= 1
        return heapq.heappop(self._heap)[2]

    def remove(self, req: Request):
        self._removed.add(req.request_id)
        self._live -= 1

    def items(self):
        """Live requests in arbitrary (heap) order — the O(n) walk for
        order-insensitive consumers (victim scan, TTFT estimator)."""
        return (r for _, _, r in self._heap
                if r.request_id not in self._removed)

    def __iter__(self):
        """Live requests in pop order (snapshots). seq is unique per
        engine, so sorting never compares requests."""
        return (r for _, _, r in sorted(self._heap, key=lambda e: e[:2])
                if r.request_id not in self._removed)

    def lowest_below(self, rank: int) -> Optional[Request]:
        """The displacement victim: lowest-priority, most-recently
        queued request STRICTLY below ``rank``; None when every queued
        request is at least ``rank``."""
        best = None
        for r in self.items():
            if r.rank >= rank:
                continue
            if best is None or (r.rank, -r._seq) < (best.rank, -best._seq):
                best = r
        return best


class _Ewma:
    """One exponentially-weighted moving average (the engine's capacity
    estimator state — fed the SAME per-segment wall times the PR 7
    ``serving.step_*_s`` histograms observe)."""

    __slots__ = ("alpha", "value")

    def __init__(self, alpha: float = 0.25):
        self.alpha = float(alpha)
        self.value: Optional[float] = None

    def update(self, x: float):
        self.value = (float(x) if self.value is None
                      else (1.0 - self.alpha) * self.value
                      + self.alpha * float(x))


# the tick's phases: span name -> (the ``stats`` key its seconds add
# to, whether they come off the phase that encloses it). Six self times
# partition step(): admit + prefill + dispatch + sync + commit + tail.
# prefill runs inside admit; on the ticks that land the program in
# flight before an upload (:meth:`ServingEngine._land`) its sync and
# commit run inside admit, and a wave's joint pull is timed as the
# prefill it belongs to with the commit inside it; otherwise sync and
# commit are top-level. upload
# is a PART of admit, told apart beside it, never a seventh segment
# (docs/OBSERVABILITY.md §Span names).
_PHASES = {
    "serving.step.admit": ("step_admit_s", True),
    "serving.step.prefill": ("step_prefill_s", True),
    "serving.step.upload": ("step_upload_s", False),
    "serving.step.dispatch": ("step_dispatch_s", True),
    "serving.step.sync": ("step_sync_s", True),
    "serving.step.commit": ("step_commit_s", True),
    "serving.step.tail": ("step_tail_s", True),
}


def _round6(seconds: Optional[float]) -> Optional[float]:
    """A flight event's segment field: microseconds kept, ``None`` for
    a phase the tick never reached."""
    return None if seconds is None else round(seconds, 6)


class _Phase:
    """One named phase of a tick, as a context manager — the ONE place
    a step segment is timed (:meth:`ServingEngine._phase`). It opens a
    ``jax.profiler.TraceAnnotation``, so the phase is an event on the
    device trace's clock whenever anyone is taking a profile (with none
    running that is one object and one C++ flag test), and on exit adds
    the phase's seconds to the engine's cumulative ``stats`` and to
    ``_tick_s``, this tick's segments, which the ``serving.step_*_s``
    histograms and the flight event are read from. Attributes go to the
    annotation as keyword arguments, never into the name. Clock and
    annotation both run from construction to ``__exit__``, so the
    helper's own cost lies inside the phase it times and two phases in
    a row leave next to nothing between them. A phase opened inside
    another (the wave prefill in admit; the sync and commit of a
    program landed before an upload) comes off the one around it, so what is
    summed is every phase's self time."""

    __slots__ = ("_eng", "_name", "_ann", "_t0", "_outer", "dur_s")

    def __init__(self, eng, name, attrs):
        self._t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)
        self._eng = eng
        self._name = name

    def __enter__(self):
        self._ann.__enter__()
        self._outer = self._eng._open_phase
        self._eng._open_phase = self
        return self

    def set(self, **attrs):
        """Attributes known only once the phase has run."""
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc):
        key, carved = _PHASES[self._name]
        stats, tick = self._eng.stats, self._eng._tick_s
        self.dur_s = dt = time.perf_counter() - self._t0
        stats[key] += dt
        tick[key] = tick.get(key, 0.0) + dt
        outer = self._eng._open_phase = self._outer
        if carved and outer is not None:
            parent = _PHASES[outer._name][0]
            stats[parent] -= dt
            tick[parent] = tick.get(parent, 0.0) - dt
        self._ann.__exit__(*exc)


class _InFlight:
    """One decode program that has been dispatched and whose tokens the
    host has not pulled yet (``ServingEngine._flight_q``). ``rows`` are
    the (slot index, slot) pairs it computes a token for — at the pull a
    row whose slot has gone or changed hands is thrown away. ``head``
    and ``chunk_outs`` are the device arrays :meth:`ServingEngine.
    _fence_chunk_pulls` pulls, ``spec`` says which host commit takes
    them; ``t_launch`` is on the engine's clock."""

    __slots__ = ("rows", "head", "chunk_outs", "spec", "grp", "g_start",
                 "g_kind", "tick_warm", "t_launch")

    def __init__(self, rows, head, chunk_outs, spec, grp, g_start,
                 g_kind, tick_warm, t_launch):
        self.rows = rows
        self.head = head
        self.chunk_outs = chunk_outs
        self.spec = spec
        self.grp = grp
        self.g_start = g_start
        self.g_kind = g_kind
        self.tick_warm = tick_warm
        self.t_launch = t_launch


def _kv_from_lanes(cache, pk, *, nkv: int, hd: int):
    """The llama/gpt cache adapter, pool to cache: rows ``pk`` (L, n, R,
    [k | v] lanes) into the first R positions of a ``{"k", "v"}``
    cache."""
    n, R = pk.shape[1:3]
    dkv = nkv * hd
    cache = list(cache)
    for l in range(len(cache)):
        kl = pk[l, :, :, :dkv].reshape(n, R, nkv, hd)
        vl = pk[l, :, :, dkv:].reshape(n, R, nkv, hd)
        cache[l] = {
            "k": cache[l]["k"].at[:, :R].set(kl.astype(cache[l]["k"].dtype)),
            "v": cache[l]["v"].at[:, :R].set(vl.astype(cache[l]["v"].dtype))}
    return cache


def _kv_to_lanes(cache):
    """Cache to pool: (L, n, cache_len, [k | v] lanes)."""
    n, cache_len = cache[0]["k"].shape[:2]
    return jnp.stack([jnp.concatenate(
        [c["k"].reshape(n, cache_len, -1),
         c["v"].reshape(n, cache_len, -1)], axis=-1) for c in cache])


def _swap_bucket(n: int) -> int:
    """Power-of-two bucket for whole-block gather/scatter widths —
    bounds the swap-path compile set to O(log max_blocks_per_slot)
    programs (pad entries target the scratch block)."""
    m = 1
    while m < n:
        m *= 2
    return m


class ServingEngine:
    """Continuous-batching decode over a paged KV pool.

    ``max_slots`` is the decode batch width (one fused dispatch serves
    all active slots). The pool holds ``num_blocks`` blocks of
    ``block_tokens`` tokens each — sized directly (``num_blocks``), by
    byte budget (``pool_bytes`` / the per-block byte cost at the cache
    element size: 1 for int8, 2 for bf16), or defaulted to worst case
    (every slot filled to ``max_seq_len``). Admission reserves each
    request's worst-case blocks (prompt + max_new) so lazy per-step
    block allocation can never fail mid-flight; physical blocks are
    still allocated lazily, so pool-usage gauges track real occupancy.

    ``cache_dtype=jnp.int8`` enables the int8 KV pool: each request's
    prefill is its own calibration pass (per-SLOT scales — an isolated
    b=1 ``generate`` computes the same scales, which is what keeps int8
    parity token-exact).

    The plain steady tick keeps ONE step program in flight: ``step()``
    dispatches the next decode step before it pulls the tokens of the
    one dispatched a call earlier, so the device does not wait for the
    way back from the chip (docs/SERVING.md §The tick's order). Which
    ticks do is decided by what the tick is, never by an option.

    Observability: every ``step()`` is wall-timed in six phases that
    partition it (:class:`_Phase`: ``serving.step.*`` profiler spans,
    ``stats["step_*_s"]``, ``serving.step_*_s`` histograms, flight
    fields, all from one clock reading), per-request TTFT/TPOT land in
    the ``serving.ttft_s``/``serving.tpot_s`` quantile sketches, and a
    flight-recorder ring (last ``flight_capacity`` step events,
    auto-dumped to ``flight_dump_path`` on a fired fault /
    ``PoolExhausted`` / deadline retirement / preemption / shed) keeps
    the postmortem trail — docs/OBSERVABILITY.md has the event format.

    Overload control (all off by default — the unbounded engine is the
    PR 5 behavior): ``max_queue`` bounds the queue (a submit against a
    full queue displaces a strictly lower-priority queued victim, else
    raises :class:`Rejected`); ``shed_infeasible=True`` rejects
    deadline-carrying requests whose deadline the EWMA capacity
    estimate says cannot reach a first token. Priority preemption is
    always armed but only ever fires across *different* priority
    classes, so all-default-priority workloads never preempt.

    ``chunk_tokens`` (None = monolithic wave prefill, the PR 5
    behavior) arms chunked prefill: prompts are prefilled
    ``chunk_tokens`` tokens per program (must be a multiple of
    ``block_tokens``), at most one chunk per tick. A chunk tick is ONE
    fused program — the chunk AND the decode step for every
    decode-ready slot coscheduled, bf16 mid chunks gathering their
    processed prefix from the pool and int8 prefills threading a
    resident bf16 carry (donated, aliased in-place) — so a long
    prompt never stalls active decode slots for more than one fused
    tick, and same-bucket
    same-tick admissions advance as batched chunk rows in the same
    program. ``decode_per_chunk`` decode dispatches are guaranteed
    between consecutive chunk programs while decode-ready slots exist
    — the fused tick's own decode half counts as the first, so
    ``decode_per_chunk - 1`` chunkless ticks separate chunk ticks. Fused-tick programs are keyed by the chunk bucket
    (kind, cursor, rows, feed bucket, chunk size) — fixed buckets, so
    the compile set stays small and exactly pinned
    (tests/test_analysis.py). ``chunk_autotune=True`` (requires
    ``slo_tpot_s``) picks each admission's chunk size: the largest
    power-of-two bucket (anchored at ``chunk_tokens``) whose predicted
    fused-tick time fits under the TPOT SLO.

    ``speculate=SpecConfig(...)`` (None = plain per-token decode) arms
    speculative decoding: every decode tick verifies k proposed tokens
    per active slot in ONE ``fused_paged_verify_step`` dispatch and
    commits the longest proposal prefix matching the engine's own
    samples — 1..k+1 tokens per dispatch, bit-identical to the
    non-speculative engine (docs/SERVING.md §Speculative decoding).
    Proposals come from a device-side n-gram matcher
    (``proposer="ngram"``, no extra model) or a draft model
    (``proposer="draft"``) riding its own block tables over the same
    paged machinery.

    ``sanitize=True`` (debug; docs/ANALYSIS.md) arms the dispatch
    sanitizer: every steady-state decode dispatch runs under
    ``analysis.runtime.sanitize()`` — zero H2D transfers, zero
    recompiles, or it RAISES at the offending step.
    ``stats["sanitized_steps"]`` counts the guarded dispatches.

    ``mesh=``/``layout=`` (docs/SERVING.md §Tensor-parallel replicas)
    shard THIS replica over the ``{mp, fsdp}`` mesh axes: attention
    heads and FFN lanes column-parallel over ``mp`` with the paged KV
    pool split on the head dim (``serving.layout.ServingLayout``),
    stacked weights layer-sharded over ``fsdp`` and gathered at use.
    Every program runs under full-manual ``jax.shard_map`` through one
    seam (:meth:`_wrap_program`); sampling and scheduling stay
    replicated, so tokens are BIT-IDENTICAL to the mp=1 engine and
    snapshots stay mesh-free. ``mesh=None`` (default) is exactly the
    single-chip engine.
    """

    def __init__(self, model, *, max_slots: int = 4,
                 block_tokens: int = 128, num_blocks: Optional[int] = None,
                 pool_bytes: Optional[int] = None, max_seq_len: int = 1024,
                 cache_dtype=jnp.bfloat16, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 prefix_caching: bool = True,
                 prefix_cache_blocks: int = 256,
                 flight_capacity: int = 256,
                 flight_dump_path: Optional[str] = None,
                 metrics_labels: Optional[Dict] = None,
                 max_queue: Optional[int] = None,
                 shed_infeasible: bool = False,
                 chunk_tokens: Optional[int] = None,
                 decode_per_chunk: int = 1,
                 chunk_autotune: bool = False,
                 slo_tpot_s: Optional[float] = None,
                 speculate: Optional[SpecConfig] = None,
                 offload: bool = False,
                 host_pool_blocks: Optional[int] = None,
                 offload_prefetch: int = 2,
                 sanitize: bool = False,
                 mesh=None, layout=None,
                 state: Optional[Dict] = None):
        from paddle_tpu.inference import _inference_state
        from paddle_tpu.observability.flight import FlightRecorder
        from paddle_tpu.observability.registry import registry

        self.model = model
        self._state = state if state is not None else _inference_state(model)
        meta = (model.fused_decode_plan(self._state, probe=True)
                if hasattr(model, "fused_decode_plan") else None)
        if meta is None:
            raise ValueError(
                "ServingEngine needs a fused_decode_plan-eligible model "
                "(llama/gpt); this model/config cannot ride the paged "
                "kernel")
        self.arch = meta.get("arch", "llama")
        # a plan that names its pool rows (``cache_lanes``) brings its
        # own step body and cache adapter (docs/SERVING.md
        # §Architectures the engine takes); any other plan rides the
        # fused llama/gpt kernel
        self._own_step = "cache_lanes" in meta
        if not self._own_step and self.arch not in ("llama", "gpt"):
            raise ValueError(
                f"paged serving supports arch llama/gpt, got {self.arch!r}")
        if self._own_step:
            refused = dict(
                cache_dtype=jnp.dtype(cache_dtype) == jnp.int8,
                speculate=speculate is not None,
                chunk_tokens=chunk_tokens is not None,
                mesh=mesh is not None and getattr(mesh, "size", 1) > 1,
                layout=layout is not None,
                offload=bool(offload),
                # a plan that cannot write a shared prefix's rows back
                # into its prefill cache (no ``from_lanes``: a state a
                # slot or a pooled leaf is kept at no block's edge)
                # cannot enter one
                prefix_caching=bool(prefix_caching)
                and "from_lanes" not in meta)
            for option, given in refused.items():
                if given:
                    raise ValueError(
                        f"ServingEngine option {option!r} is not carried "
                        f"to arch {self.arch!r} yet (docs/SERVING.md "
                        f"§Architectures the engine takes)")
        # int32 counters the plan's step returns behind the hidden
        # state; they ride the tick's one pull behind the tokens
        self._step_counters = tuple(meta.get("step_counters", ()))
        # a plan whose prefill routes experts says through what
        # (``prefill_moe``: expert layers, k, path, and where a layer
        # runs in chunks ``calls_a_layer(s_pad)``), so that a wave's
        # kernel calls and routed rows are counted from its shape alone;
        # where only the program knows the rows (``rows`` ``"counted"``:
        # a share of an expert-parallel layer), its prefill returns them
        self._prefill_moe = meta.get("prefill_moe")
        self._prefill_counted = (self._prefill_moe or {}).get(
            "rows") == "counted"
        # a plan whose prefill attention may take a kernel says for
        # which waves: (R, s_pad) -> the layers whose attention does
        self._prefill_attn = meta.get("prefill_attn_calls")
        # ... or sends other kernels: (R, s_pad) -> {counter: calls}
        self._prefill_calls = meta.get("prefill_calls")
        # a plan whose step walks the pool through a list of its own
        # counts it: (positions, tables, block_tokens) -> (items walked,
        # slots x the longest row's items, rows walked)
        self._decode_walk = meta.get("decode_walk")
        # a hybrid plan (docs/SERVING.md §Architectures the engine
        # takes): pool rows for ``pool_layers`` of the layers only, a
        # second paged leaf on the same block tables (``pool_aux``:
        # one row every ``stride`` tokens, ``lanes`` wide, in ``dtype``
        # or the cache's), and leaves of
        # fixed size a slot (``slot_state``: name -> ((layers, ...),
        # dtype), held as (layers, max_slots, ...)) that the step carries
        self._pool_aux = meta.get("pool_aux")
        self._slot_state = meta.get("slot_state")
        # tpu-lint: volatile(a property of the backend)
        self._host_aliased = jax.default_backend() == "cpu"
        blocks_plan = meta.get("blocks")
        if blocks_plan is not None and blocks_plan.get("q_split", 1) != 1:
            raise ValueError(
                "paged serving does not support the q-split (big-model) "
                "weight-streaming regime yet")
        self.meta = meta
        self.kv_int8 = jnp.dtype(cache_dtype) == jnp.int8
        if not self.kv_int8 and jnp.dtype(cache_dtype).itemsize != 2:
            raise ValueError(
                f"cache_dtype must be bf16-width or int8, got "
                f"{jnp.dtype(cache_dtype).name}")
        self.cache_dtype = jnp.int8 if self.kv_int8 else cache_dtype
        if max_seq_len % block_tokens:
            raise ValueError(
                f"max_seq_len {max_seq_len} must be a multiple of "
                f"block_tokens {block_tokens}")
        self.block_tokens = int(block_tokens)
        self.max_seq_len = int(max_seq_len)
        self.max_slots = int(max_slots)
        self.max_blocks_per_slot = max_seq_len // block_tokens

        L = self._num_layers = int(meta.get("pool_layers",
                                            self._count_layers()))
        # one pool row: a plan's own ``cache_lanes``, else [k | v]
        nkv, hd = meta.get("num_kv_heads"), meta.get("head_dim")
        self._cache_lanes = int(meta["cache_lanes"] if self._own_step
                                else 2 * nkv * hd)

        # ---- tensor-parallel replica (docs/SERVING.md §Tensor-parallel
        # replicas): mesh + ServingLayout shard THIS replica over
        # {mp, fsdp}. mesh None (or size 1) is the exact pre-mp path:
        # every program compiles byte-identical to the single-chip
        # engine (tests/test_serving_mp.py pins the program set).
        if layout is not None and mesh is None:
            mesh = layout.mesh
        if mesh is not None and getattr(mesh, "size", 1) == 1:
            mesh = None
            layout = None
        if mesh is not None:
            from paddle_tpu.serving.layout import ServingLayout
            if layout is None:
                layout = ServingLayout(mesh)
            elif layout.mesh is not mesh:
                raise ValueError(
                    "layout was built for a different mesh; pass "
                    "matching mesh/layout (or just the layout)")
            layout.validate(num_heads=meta["num_heads"],
                            num_kv_heads=nkv, num_layers=L)
        self.mesh = mesh
        self.layout = layout
        self._mp = layout.mp if layout is not None else 1
        self._mp_axis = layout.mp_axis if layout is not None else None
        self._fsdp_axis = (layout.fsdp_axis if layout is not None
                           else None)
        if layout is not None:
            # commit the full state replicated so every program input
            # already lives on the mesh (no implicit transfer at
            # dispatch — the 0-H2D steady-tick pin holds under mp too)
            self._state = layout.place_replicated(self._state)
        aux = self._pool_aux
        if aux is not None and block_tokens % aux["stride"]:
            raise ValueError(
                f"block_tokens {block_tokens} must be a multiple of the "
                f"{aux['stride']} tokens a row of arch {self.arch!r}'s "
                f"second pool leaf covers")
        aux_dtype = (None if aux is None
                     else jnp.dtype(aux.get("dtype", self.cache_dtype)))
        bpb = self.block_bytes = (
            L * block_tokens * self._cache_lanes
            * (1 if self.kv_int8 else 2)
            + (0 if aux is None else
               L * (block_tokens // aux["stride"]) * aux["lanes"]
               * aux_dtype.itemsize))
        if num_blocks is None:
            if pool_bytes is not None:
                num_blocks = max(2, int(pool_bytes) // bpb)
            else:   # worst case: every slot filled to max_seq_len
                num_blocks = max_slots * self.max_blocks_per_slot + 1
        # tpu-lint: volatile(occupancy re-derives as restored requests
        # re-admit; num_blocks rides the snapshot config)
        self.pool = BlockPool(num_blocks, block_tokens)
        # tpu-lint: volatile(device KV never survives a crash by design
        # — restore re-prefills prompts and replays generated tokens)
        self.kv_pool = jnp.zeros(
            (L, num_blocks, block_tokens, self._cache_lanes),
            self.cache_dtype)
        if aux is not None or self._slot_state is not None:
            # one donated pytree through every program: the paged leaves
            # (rows, or (rows, aux)) and the per-slot leaves
            pool = self.kv_pool if aux is None else (
                self.kv_pool, jnp.zeros(
                    (L, num_blocks, block_tokens // aux["stride"],
                     aux["lanes"]), aux_dtype))
            self.kv_pool = {"pool": pool, "state": {
                name: jnp.zeros((shape[0], max_slots, *shape[1:]), dtype)
                for name, (shape, dtype) in
                (self._slot_state or {}).items()}}
        if layout is not None:
            # head-dim sharded: each shard's block-table walk reads only
            # its own heads' [k_s|v_s] lanes (zeros are permutation-
            # symmetric, so placing the canonical zeros is exact)
            self.kv_pool = layout.place(self.kv_pool, layout.pool_spec())
        # tpu-lint: volatile(rebuilds from traffic; snapshot keys are
        # postmortem info only)
        self.prefix_cache = (PrefixCache(self.pool, prefix_cache_blocks)
                             if prefix_caching else None)

        # ---- hierarchical KV: host-RAM block tier (docs/SERVING.md
        # §Hierarchical KV). offload=True arms the swap paths: a
        # preemption GATHERS the victim's blocks to host RAM instead of
        # freeing them (background D2H drain overlapped with serving
        # ticks), and resume SCATTERS them back — the generated-position
        # KV is restored bitwise, so the token-exact resume path runs
        # zero replay dispatches when the blocks survived.
        self.offload = bool(offload)
        if host_pool_blocks is not None and host_pool_blocks < 1:
            raise ValueError(f"host_pool_blocks must be >= 1 or None, "
                             f"got {host_pool_blocks}")
        self.offload_prefetch = int(offload_prefetch)
        if self.offload_prefetch < 0:
            raise ValueError(f"offload_prefetch must be >= 0, got "
                             f"{offload_prefetch}")
        # tpu-lint: volatile(host KV never survives a crash by design —
        # a restored engine's parked requests re-admit down the
        # token-exact re-prefill+replay path, exactly like slot KV;
        # host_pool_blocks rides the snapshot config)
        self.host_store = (HostBlockStore(
            host_pool_blocks if host_pool_blocks is not None
            else 4 * num_blocks) if self.offload else None)
        # in-flight and host-resident parked swap records, keyed by
        # request_id: _Parked carries the gathered device buffer until
        # the background drain lands it in host_store, then the host ids
        # tpu-lint: volatile(parked KV is a resume ACCELERATOR — the
        # queue's serialized resume tokens are the durable state, so
        # restore simply re-prefills where a live engine would swap in)
        self._parked: Dict[int, "_Parked"] = {}
        # tpu-lint: volatile(compiled-program cache)
        self._swap_fns: Dict = {}
        # device-staged swap-in payloads keyed by request_id (prefetch
        # landed ahead of admission) — see _offload_prefetch
        # tpu-lint: volatile(prefetch staging re-warms from host tier)
        self._staged: Dict[int, object] = {}
        # EWMA of observed swap-in staging wall seconds: the prefetch
        # policy's probe-and-observe estimate (chunk_autotune pattern)
        # of how far ahead of admission staging must start
        # tpu-lint: volatile(prefetch estimator re-learns)
        self._ewma_swap_s = _Ewma()

        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token_id = eos_token_id
        self.seed = int(seed)
        self._seeds_issued = 0
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 or None, got "
                             f"{max_queue}")
        self.max_queue = None if max_queue is None else int(max_queue)
        self.shed_infeasible = bool(shed_infeasible)
        if chunk_tokens is not None:
            chunk_tokens = int(chunk_tokens)
            if chunk_tokens < block_tokens or chunk_tokens % block_tokens:
                raise ValueError(
                    f"chunk_tokens {chunk_tokens} must be a positive "
                    f"multiple of block_tokens {block_tokens} (chunks "
                    f"append block-aligned KV)")
        self.chunk_tokens = chunk_tokens
        if decode_per_chunk < 1:
            raise ValueError(f"decode_per_chunk must be >= 1, got "
                             f"{decode_per_chunk}")
        self.decode_per_chunk = int(decode_per_chunk)
        if slo_tpot_s is not None and not slo_tpot_s > 0:
            raise ValueError(f"slo_tpot_s must be > 0 or None, got "
                             f"{slo_tpot_s}")
        self.slo_tpot_s = None if slo_tpot_s is None else float(slo_tpot_s)
        self.chunk_autotune = bool(chunk_autotune)
        if self.chunk_autotune and (chunk_tokens is None
                                    or self.slo_tpot_s is None):
            raise ValueError(
                "chunk_autotune needs both chunk_tokens (the cold "
                "default / ladder anchor) and slo_tpot_s (the TPOT-SLO "
                "headroom the tuner fits chunks under)")
        # the autotuner's current pick (== chunk_tokens until a warm
        # EWMA moves it); what estimated_ttft_s prices chunks at
        # tpu-lint: volatile(autotuner re-learns; config carries knobs)
        self._chunk_choice = chunk_tokens
        # per-bucket fused-tick wall-time EWMAs (the measured refinement
        # over the per-token linear prediction)
        # tpu-lint: volatile(capacity estimator re-learns)
        self._chunk_time_ewma: Dict[int, _Ewma] = {}
        # tpu-lint: volatile(probe cadence counter)
        self._chunk_probe_wait = 0
        # tpu-lint: volatile(probe budget re-learns after restore)
        self._chunk_probe_tries: Dict[int, int] = {}
        self._closed = False

        from paddle_tpu.ops import rope as rope_ops
        # tpu-lint: volatile(tables of constants)
        self._cos_tab = self._sin_tab = None    # an own step has its own
        if not self._own_step:
            self._cos_tab, self._sin_tab = rope_ops.rope_cos_sin(
                max_seq_len, hd, base=meta["rope_base"])
        if layout is not None:
            # closed-over rope tables must be mesh-committed too, or
            # every program would mix mesh and single-device operands
            self._cos_tab, self._sin_tab = layout.place_replicated(
                (self._cos_tab, self._sin_tab))

        # host mirrors of the per-slot device state — all volatile:
        # resume admission rebuilds every row from the serialized
        # (tokens, seed) resumable requests
        ms = self.max_slots
        # tpu-lint: volatile(rebuilt by resume admission)
        self._tables = np.full((ms, self.max_blocks_per_slot),
                               SCRATCH_BLOCK, np.int32)
        # tpu-lint: volatile(rebuilt by resume admission)
        self._positions = np.zeros(ms, np.int32)
        # tpu-lint: volatile(rebuilt by resume admission)
        self._toks = np.zeros(ms + len(self._step_counters), np.int32)
        # tpu-lint: volatile(rebuilt by resume admission)
        self._seeds = np.zeros(ms, np.uint32)
        # tpu-lint: volatile(rebuilt by resume admission)
        self._counts = np.zeros(ms, np.int32)
        # tpu-lint: volatile(int8 calibration reproduces scales exactly)
        self._kv_scales = (None if self._own_step else np.ones(
            (L, ms, self._cache_lanes), np.float32))

        # ---- speculative decoding (docs/SERVING.md §Speculative) ----
        self.speculate = speculate
        self._spec_k = 0
        # tpu-lint: volatile(compiled-program cache)
        self._verify_fns: Dict[int, object] = {}   # keyed by tail k
        # tpu-lint: volatile(compiled-program cache)
        self._draft_fns: Dict[int, object] = {}
        # tpu-lint: volatile(device constants, rebuilt per tail width)
        self._prop_zeros: Dict = {}     # ngram: per-k proposal reset
        # tpu-lint: volatile(device constants, rebuilt per tail width)
        self._nprop_fulls: Dict = {}    # draft: per-k full-proposal consts
        # per-slot adaptive k state (SpecConfig(adaptive=True)): the
        # device-side proposal cap, its host mirror, the per-slot k and
        # acceptance EWMAs, and the tick's effective tail width (max k
        # over active slots — one batched program serves every slot)
        # tpu-lint: volatile(adaptive k restarts at the configured k —
        # acceptance re-learns after restore, documented in SERVING.md)
        self._spec_cap = None
        # tpu-lint: volatile(device twin; re-uploads on dirty ticks)
        self._dev_cap = None
        # tpu-lint: volatile(adaptive k restarts at the configured k)
        self._spec_k_slot = None
        # tpu-lint: volatile(acceptance EWMA re-learns after restore)
        self._spec_acc_ewma = None
        # tpu-lint: volatile(adapt cadence counter)
        self._spec_adapt_tick = 0
        # tpu-lint: volatile(tail-width change detector)
        self._last_spec_k = None
        # tpu-lint: volatile(per-tick effective tail width)
        self._spec_k_eff = 0
        # tpu-lint: volatile(re-primed from committed tokens at adoption)
        self._history = None            # ngram: host mirror (ms, S)
        # tpu-lint: volatile(device twin; re-uploads on dirty ticks)
        self._dev_hist = None           # ngram: device history twin
        # tpu-lint: volatile(re-primed by the next verify dispatch)
        self._dev_prop = None           # ngram: carried device proposals
        # tpu-lint: volatile(device twin; re-uploads on dirty ticks)
        self._draft_dev = None          # draft: device block-table twin
        # tpu-lint: volatile(rebuilt by resume adoption)
        self._draft_tables = None
        # tpu-lint: volatile(draft pages rebuilt at resume adoption)
        self._draft_pool_blocks = None
        # tpu-lint: volatile(draft KV re-prefills at resume adoption)
        self.draft_kv_pool = None
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_spec = None          # (proposed, accepted) this tick
        # k=0 recovery probing (SpecConfig(adaptive=True, k_min=0);
        # docs/SERVING.md §Speculative decoding): a slot parked at k=0
        # proposes nothing, so its acceptance EWMA can never observe
        # again — every `adapt_every` parked ticks the engine raises
        # the slot's cap to ONE proposal for a two-tick window so the
        # EWMA re-observes and the slot can climb back
        # tpu-lint: volatile(probe cadence counter)
        self._spec_probe_wait = 0
        # tpu-lint: volatile(in-flight probe window; restore re-probes)
        self._probe_window = 0
        # tpu-lint: volatile(in-flight probe window; restore re-probes)
        self._probe_slots: List[int] = []
        # committed tokens per active slot per decode dispatch — what
        # the TTFT estimator divides decode work by so shed_infeasible
        # doesn't over-shed when speculation multiplies tokens/tick
        # tpu-lint: volatile(capacity estimator re-learns; cold
        # convention documented on estimated_ttft_s)
        self._ewma_spec_tokens = _Ewma()
        if speculate is not None:
            if not isinstance(speculate, SpecConfig):
                raise ValueError(
                    f"speculate must be a serving.SpecConfig, got "
                    f"{type(speculate).__name__}")
            if speculate.k >= max_seq_len:
                raise ValueError(
                    f"speculate k {speculate.k} must be < max_seq_len "
                    f"{max_seq_len}")
            self._spec_k = speculate.k
            self._spec_cap = np.full(ms, speculate.k, np.int32)
            self._spec_k_slot = np.full(ms, speculate.k, np.int32)
            self._spec_acc_ewma = [_Ewma() for _ in range(ms)]
            if speculate.proposer == "ngram":
                # the device-side suffix matcher runs over this carried
                # committed-token buffer — uploaded only on dirty ticks
                self._history = np.zeros((ms, max_seq_len), np.int32)
                # the dirty-tick proposal reset, built ONCE per tail
                # width: immutable device constants, so a join/leave
                # tick re-arms the proposer without compiling a zeros
                # program mid-drain (the compile-set pin in
                # tests/test_analysis.py)
                self._prop_zero(speculate.k)
            else:
                from paddle_tpu.inference import _inference_state as _ist
                dm = speculate.draft_model
                self._draft_state = (speculate.draft_state
                                     if speculate.draft_state is not None
                                     else _ist(dm))
                if speculate.share_embeddings:
                    # the draft rides the target's embedding table when
                    # the shapes line up (same vocab × hidden) — one
                    # buffer instead of two, and via tied_unembed the
                    # shared table is the draft's unembedding too
                    # (docs/SERVING.md §Speculative decoding)
                    shared = self._share_draft_embeddings(
                        self._draft_state)
                    if shared is not None:
                        self._draft_state = shared
                dmeta = (dm.fused_decode_plan(self._draft_state,
                                              probe=True)
                         if hasattr(dm, "fused_decode_plan") else None)
                if dmeta is None:
                    raise ValueError(
                        "draft_model needs a fused_decode_plan-eligible "
                        "config (llama/gpt) to ride the paged kernel")
                darch = dmeta.get("arch", "llama")
                if darch not in ("llama", "gpt"):
                    raise ValueError(
                        f"draft proposer supports arch llama/gpt, got "
                        f"{darch!r}")
                dbp = dmeta.get("blocks")
                if dbp is not None and dbp.get("q_split", 1) != 1:
                    raise ValueError(
                        "draft proposer does not support the q-split "
                        "(big-model) draft regime")
                self._draft_meta = dmeta
                self._draft_arch = darch
                self._draft_layers = int(getattr(dm.cfg, "num_layers"))
                self._draft_dkv = (dmeta["num_kv_heads"]
                                   * dmeta["head_dim"])
                # the draft shares the paged-pool DESIGN with its own
                # block tables; its pool is sized worst-case (a tiny
                # model's pages are cheap) so a prefix-cache-assisted
                # target admission can never strand the draft mid-flight
                dnb = ms * self.max_blocks_per_slot + 1
                self._draft_pool_blocks = BlockPool(dnb, block_tokens)
                self.draft_kv_pool = jnp.zeros(
                    (self._draft_layers, dnb, block_tokens,
                     2 * self._draft_dkv), jnp.bfloat16)
                if layout is not None:
                    # draft compute stays fully REPLICATED under mp (a
                    # tiny model — sharding it would trade parity risk
                    # for nothing); its arrays still commit to the mesh
                    # so the draft programs' shard_map wrap is uniform
                    self._draft_state = layout.place_replicated(
                        self._draft_state)
                    self.draft_kv_pool = layout.place_replicated(
                        self.draft_kv_pool)
                self._draft_stacked = jax.jit(
                    lambda st: dm.fused_decode_plan(st)["params"])(
                        self._draft_state)
                self._draft_cos, self._draft_sin = rope_ops.rope_cos_sin(
                    max_seq_len, dmeta["head_dim"],
                    base=dmeta["rope_base"])
                if layout is not None:
                    (self._draft_stacked, self._draft_cos,
                     self._draft_sin) = layout.place_replicated(
                        (self._draft_stacked, self._draft_cos,
                         self._draft_sin))
                self._draft_tables = np.full(
                    (ms, self.max_blocks_per_slot), SCRATCH_BLOCK,
                    np.int32)
                # draft proposals always fill all k slots (per-slot
                # adaptive caps are applied inside the verify program)
                self._nprop_full(speculate.k)

        self._slots: List[Optional[_Slot]] = [None] * ms
        self._queue = _PriorityQueue()
        self._submit_seq = 0
        self.results: Dict[int, RequestResult] = {}
        # tpu-lint: volatile(re-derived as restored requests re-admit)
        self._reserved = 0      # blocks promised to in-flight slots
        # tpu-lint: volatile(compiled program)
        self._step_fn = None
        # the stacked per-layer weight copy is built ONCE here and fed to
        # the step program as a traced argument: a per-token dispatch has
        # no scan to amortize the in-trace rebuild over (generate()'s
        # decode program runs build_fused_params once per max_new_tokens
        # steps; a serving step would run it once per token)
        # (an own step reads the state's own leaves: the weights are
        # held once)
        self._stacked = None if self._own_step else jax.jit(
            lambda st: model.fused_decode_plan(st)["params"])(self._state)
        # tpu-lint: volatile(per-leaf PartitionSpecs, derived from layout)
        self._stacked_specs = None
        if layout is not None:
            ffn_w = self._stacked.get("wg")
            layout.validate(num_heads=meta["num_heads"],
                            num_kv_heads=nkv, num_layers=L,
                            ffn=(int(ffn_w.shape[-1])
                                 if ffn_w is not None else None))
            self._stacked_specs = layout.stacked_specs(self._stacked)
            self._stacked = layout.shard_stacked(
                self._stacked, num_heads=meta["num_heads"],
                num_kv_heads=nkv, head_dim=hd)
        # device twins of the host mirrors above: positions/toks/counts
        # advance ON DEVICE inside the step program (no per-step H2D
        # uploads); a join/leave/table event marks them dirty and the
        # next step re-uploads from the host mirrors
        # tpu-lint: volatile(device twins re-upload from host mirrors)
        self._dev = None
        # tpu-lint: volatile(upload flag; restore starts dirty)
        self._dirty = True
        # tpu-lint: volatile(compiled-program cache)
        self._jit_cache: Dict = {}
        # tpu-lint: volatile(per-incarnation telemetry; registry
        # counters are the cross-restore accounting)
        self.stats = self._fresh_stats()
        # tpu-lint: volatile(per-tick report; results dict carries the
        # outcomes across a restore)
        self._finished_tick: List[int] = []
        # flight recorder: one compact event per step() into a fixed
        # ring; auto-dumped at the resilience seams when a dump path is
        # configured (fired fault / PoolExhausted / deadline retirement)
        self.flight = FlightRecorder(capacity=flight_capacity,
                                     auto_dump_path=flight_dump_path,
                                     name="serving-engine")
        # metrics facade: the process-global registry, optionally
        # wrapped in a label-stamping view (Router-built replicas pass
        # metrics_labels={"replica": "<i>"} so one process's series
        # stay distinguishable and merged_across("replica") can fold
        # them back into the tier export). Storage stays in the global
        # registry either way — counter_total / exporters see one pool.
        # tpu-lint: volatile(telemetry facade; the Router re-stamps it
        # via engine kwargs on restore/rebuild)
        self._metrics = (registry().view(**metrics_labels)
                         if metrics_labels else registry())
        self._step_seq = 0              # flight event ordinal
        # tpu-lint: volatile(flight-dump latch, per tick)
        self._dump_pending: Optional[str] = None
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_admitted: List[int] = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_retired: List = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_prefills: List = []
        # tpu-lint: volatile(per-tick segment timing)
        self._tick_s: Dict[str, float] = {}     # this tick's _Phase times
        # tpu-lint: volatile(per-tick segment timing)
        self._open_phase: Optional[_Phase] = None   # innermost open one
        # decode programs dispatched and not yet pulled, oldest first:
        # one between two plain steady ticks, two for an instant inside
        # one, none after any other kind of tick (docs/SERVING.md §The
        # tick's order)
        # tpu-lint: volatile(snapshot() lands them first; a crash loses
        # at most the one uncommitted token a row, which restore
        # recomputes)
        self._flight_q: collections.deque = collections.deque()
        # tpu-lint: volatile(per-tick marker)
        self._tick_landed = False       # this tick has committed a step
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_lookahead = False
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_counters: Dict[str, int] = {}    # plan's step counters
        # tpu-lint: volatile(per-program span attributes)
        self._landed_counters: Dict[str, int] = {}
        # the engine's clock: perf_counter less the time spent outside
        # step(), so that a program's seconds (from its launch, or from
        # the pull before it, to its own pull) leave the caller out
        # tpu-lint: volatile(estimator timing, per incarnation)
        self._away_s = 0.0
        # tpu-lint: volatile(estimator timing, per incarnation)
        self._t_out: Optional[float] = None
        # tpu-lint: volatile(estimator timing, per incarnation)
        self._t_landed = 0.0
        # tpu-lint: volatile(per-tick segment timing)
        self._tick_program_s: Optional[float] = None
        # overload-control tick markers + capacity estimator state
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_preempted: List[int] = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_resumed: List[int] = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_swapped_out: List[int] = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_swapped_in: List[int] = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_shed: List = []      # (request_id, reason) pairs
        # tpu-lint: volatile(shed results land in results, which the
        # snapshot serializes; the tick report is per-incarnation)
        self._pending_finished: List[int] = []  # shed between ticks
        # tpu-lint: volatile(capacity estimator re-learns; cold = no
        # estimate, the documented estimated_ttft_s convention)
        self._ewma_step = _Ewma()       # decode dispatch+sync per step
        # prefill cost PER TOKEN (wall seconds / new tokens prefilled):
        # the estimator must price a 2048-token prompt ~64x a 32-token
        # one, not one flat wave term — deadline-infeasibility shedding
        # would otherwise over-shed short prompts queued behind long
        # ones (tests/test_serving_chunked.py pins the bimodal case)
        # tpu-lint: volatile(capacity estimator re-learns)
        self._ewma_prefill_tok = _Ewma()
        # tpu-lint: volatile(capacity estimator re-learns)
        self._ewma_chunk = _Ewma()      # per chunk-program wall time
        # chunked-prefill scheduler state: FIFO of _ChunkGroup batches
        # still mid-prefill (dead rows lazily compacted by identity
        # check), chunk events this tick, and decode dispatches since
        # the last chunk (the decode_per_chunk interleave budget;
        # initialized satisfied so the first chunk runs immediately)
        # tpu-lint: volatile(mid-prefill slots snapshot as resumable
        # requests; restore re-admits them through the queue)
        self._prefill_fifo: List[_ChunkGroup] = []
        # tpu-lint: volatile(per-tick flight marker)
        self._tick_chunks: List = []    # (request_id, start, ntok)
        # tpu-lint: volatile(interleave budget restarts satisfied)
        self._decode_since_chunk = self.decode_per_chunk
        # tpu-lint: volatile(a restored engine re-pays the compile)
        self._step_fn_warm = False      # first dispatch pays the compile
        # tpu-lint: volatile(a restored engine re-pays the compile)
        # the PLAIN decode program's own first-dispatch guard: in a
        # chunked engine the first dispatch is a fused chunk tick, so
        # _step_fn_warm flips long before the chunkless step program
        # first compiles — gating the _ewma_step feed on _step_fn_warm
        # alone would ingest that compile spike and over-shed
        # deadline-carrying submits for dozens of ticks
        self._ewma_step_warm = False
        # sanitizer tiers (paddle_tpu.analysis.runtime,
        # docs/ANALYSIS.md): "dispatch" (== True, the PR 9 behavior)
        # wraps every STEADY-STATE fused dispatch — warm step program,
        # no join/leave/table event since the last upload — in
        # no_transfer(h2d) + no_recompile, so a stray host upload or
        # shape-churn recompile raises at the offending step;
        # "roundtrip" runs the snapshot->restore->snapshot byte-
        # identity check inside every save_snapshot; "all" arms both.
        if sanitize in (False, None):
            mode = None
        elif sanitize is True or sanitize == "dispatch":
            mode = "dispatch"
        elif sanitize in ("roundtrip", "all"):
            mode = sanitize
        else:
            raise ValueError(
                f"sanitize must be a bool or one of "
                f"'dispatch'/'roundtrip'/'all', got {sanitize!r}")
        self._sanitize = mode in ("dispatch", "all")
        self._sanitize_roundtrip = mode in ("roundtrip", "all")
        # the constructor-shaped value, so snapshots round-trip the
        # configured tier (not the normalized booleans)
        self._sanitize_mode = (sanitize if isinstance(sanitize, str)
                               else bool(sanitize))
        self._gauges_init()

    # ------------------------------------------------------------- helpers
    def _count_layers(self) -> int:
        cfg = self.model.cfg
        return int(getattr(cfg, "num_layers"))

    # -------------------------------------------- tensor-parallel plumbing
    _EMBED_KEYS = ("model.embed_tokens.weight", "gpt.wte.weight")

    def _share_draft_embeddings(self, draft_state):
        """Rebind the draft's embedding table to the TARGET's array when
        shape+dtype match (SpecConfig(share_embeddings=True)). Returns
        the rebound dict, or None when no key lines up — a smaller-
        hidden draft keeps its own table, silently."""
        for key in self._EMBED_KEYS:
            tw = self._state.get(key)
            dw = draft_state.get(key)
            if (tw is not None and dw is not None
                    and getattr(tw, "shape", None) == dw.shape
                    and getattr(tw, "dtype", None) == dw.dtype):
                out = dict(draft_state)
                out[key] = tw
                return out
        return None

    def _up(self, x, spec=None):
        """Host→device upload for program inputs. Single-device engines
        take the plain ``jnp.asarray`` path (byte-identical pre-mp
        behavior); a mesh-sharded engine commits the upload under an
        explicit NamedSharding (replicated unless ``spec`` says
        otherwise) so dispatch inputs never mix mesh and single-device
        placements."""
        if self._host_aliased:
            # the CPU backend may alias a host array instead of copying
            # it, and the mirrors are written again (admission, commit)
            # while a program launched with them can still be waiting
            # to run: it must see what it was launched with. A device
            # backend copies on the way up.
            # tpu-lint: allow(host-sync): inputs are host-canonical mirrors
            x = np.array(x)
        if self.layout is None:
            return jnp.asarray(x)
        from jax.sharding import PartitionSpec
        # tpu-lint: allow(host-sync): inputs are host-canonical mirrors
        return self.layout.place(
            np.asarray(x), spec if spec is not None else PartitionSpec())

    def _up_scales(self):
        """The int8 per-slot scale device twin: canonical on the host,
        shard-major permuted + head-dim sharded on the mesh (lockstep
        with the pool's last dim)."""
        if self._kv_scales is None:     # a plan's own step keeps none
            return None
        if self.layout is None:
            return self._up(self._kv_scales)
        return self.layout.shard_kv_scales(
            self._kv_scales, num_kv_heads=self.meta["num_kv_heads"],
            head_dim=self.meta["head_dim"])

    def _wrap_program(self, kind, impl, in_specs, out_specs,
                      donate_argnums=()):
        """The ONE shard seam (ISSUE 17): every engine program routes
        through here and takes its NAME here: ``serving_<kind>``, the
        kind its ``lowered_programs`` key starts with, so a run on a
        trace's ``XLA Modules`` line reads ``jit_serving_step``,
        ``jit_serving_prefill``, ... (one name a kind, not a bucket:
        the shapes are on the events). mesh=None → plain ``jax.jit`` —
        the exact pre-mp program. With a mesh, the impl runs under
        full-manual
        ``jax.shard_map``: per-head math is local, the o-proj/logits
        boundary gathers (inside fused_decode), and sampling runs
        replicated on every device so per-slot ``fold_in`` RNG streams
        survive verbatim. check_vma=False is REQUIRED: the replication
        checker cannot infer that all_gather outputs under replicated
        out_specs are in fact replicated."""
        if self.mesh is not None:
            impl = jax.shard_map(impl, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)
        impl.__name__ = impl.__qualname__ = "serving_" + kind
        return jax.jit(impl, donate_argnums=donate_argnums)

    def _gather_stacked(self, stacked):
        """fsdp gather-at-use: stacked leaves arrive sharded on the
        layer dim; one tiled all_gather per leaf at body entry
        reassembles the exact bytes (bitwise inert). mp-only meshes
        (and mesh=None) pass through untouched."""
        if self._fsdp_axis is None:
            return stacked
        ax = self._fsdp_axis
        return {k: jax.lax.all_gather(w, ax, axis=0, tiled=True)
                for k, w in stacked.items()}

    def _replicated_specs(self, tree):
        """A matching pytree of replicated PartitionSpecs."""
        from jax.sharding import PartitionSpec
        return jax.tree.map(lambda _: PartitionSpec(), tree)

    def _gauges_init(self):
        r = self._metrics
        r.gauge("serving.pool_blocks_total").set(self.pool.num_blocks - 1)
        r.gauge("serving.mp_degree").set(self._mp)
        r.gauge("serving.fsdp_degree").set(
            self.layout.fsdp if self.layout is not None else 1)
        if self.host_store is not None:
            r.gauge("serving.offload.host_blocks_total").set(
                self.host_store.capacity)
        self._update_gauges()

    def _phase(self, name: str, **attrs) -> _Phase:
        """``with self._phase("serving.step.<phase>", ...):`` — profiler
        span, ``stats`` seconds and this tick's segment in one
        (:class:`_Phase`)."""
        return _Phase(self, name, attrs)

    def _update_gauges(self):
        r = self._metrics
        active = sum(s is not None for s in self._slots)
        r.gauge("serving.batch_occupancy").set(active / self.max_slots)
        r.gauge("serving.queue_depth").set(len(self._queue))
        r.gauge("serving.pool_blocks_used").set(self.pool.used_blocks)
        if self.prefix_cache is not None:
            r.gauge("serving.prefix_hit_rate").set(
                self.prefix_cache.hit_rate)
        if self.host_store is not None:
            r.gauge("serving.offload.host_blocks_used").set(
                self.host_store.used_blocks)
            probes = (self.stats["prefetch_hits"]
                      + self.stats["prefetch_misses"])
            if probes:
                r.gauge("serving.offload.prefetch_hit_rate").set(
                    self.stats["prefetch_hits"] / probes)

    def _fresh_stats(self) -> Dict:
        """The ONE definition of the cumulative stats dict — __init__
        and reset_stats both take it from here, so a new field (the
        step-segment times, admission count) cannot drift between the
        two copies. ``step_*_s`` are cumulative wall seconds per step
        phase (:class:`_Phase`): admit, prefill, dispatch, sync, commit
        and tail partition ``step()``; ``step_upload_s`` is the part of
        ``step_admit_s`` spent re-uploading the dirty mirrors, on
        ``upload_ticks`` ticks. ``lookahead_ticks`` counts the ticks
        that dispatched a step program before the tokens of the one
        before it were pulled, ``lookahead_discarded_tokens`` the tokens
        such a program computed for rows that had left by its pull.
        A plan's ``step_counters`` (``mla_moe``: ``moe_layer_steps``,
        ``moe_experts_touched``, ``moe_rows_max``, ``moe_rows``, and for
        a share of an expert-parallel layer ``moe_picks``) are
        summed over the step programs whose tokens were pulled, as the
        program counted them. ``kv_blocks_walked`` and ``kv_blocks_dense``
        (the fused paged kernel's engines, and a plan with
        ``decode_walk``) sum, over the plain and fused-chunk steps landed,
        the items of the decode kernel's walk and what a walk of every
        slot to the longest row's length would cover; ``kv_rows_walked``
        (a plan with ``decode_walk``) the rows that walk, against
        ``max_slots`` a step (:meth:`_record_walk`). ``prefill_moe_calls``
        and ``prefill_moe_rows`` (a plan with ``prefill_moe``) sum, over
        the waves landed, the expert layers that went through the grouped
        prefill kernel and their routed rows, pad positions included
        (:meth:`_wave_kernels`; where the plan's ``rows`` are ``"counted"``,
        the picks that fell on held experts, as the prefill program
        counted them); both stay 0 where the path is ``ragged_dot``.
        ``prefill_attn_calls`` (a plan with the key) sums, over the
        waves landed, the layers whose attention took the flash prefill
        kernel: the plan's own predicate on the wave's shape.
        Per-step distributions live in the ``serving.step_*_s`` registry
        histograms."""
        return dict(steps=0, decode_tokens=0, idle_slot_steps=0,
                    prefill_tokens=0, prefill_tokens_reused=0,
                    prefill_chunks=0, replay_tokens=0,
                    requests_finished=0, requests_admitted=0,
                    preemptions=0, requests_resumed=0,
                    requests_shed=0, requests_rejected=0,
                    sanitized_steps=0, decode_slot_dispatches=0,
                    spec_ticks=0, spec_proposed=0, spec_accepted=0,
                    spec_k_probes=0, roundtrip_checks=0,
                    swap_outs=0, swap_ins=0,
                    swap_out_bytes=0, swap_in_bytes=0,
                    prefetch_hits=0, prefetch_misses=0,
                    step_admit_s=0.0, step_prefill_s=0.0,
                    step_dispatch_s=0.0, step_sync_s=0.0,
                    step_commit_s=0.0, step_tail_s=0.0,
                    step_upload_s=0.0, upload_ticks=0,
                    lookahead_ticks=0, lookahead_discarded_tokens=0,
                    **({} if self._own_step and self._decode_walk is None
                       else dict(kv_blocks_walked=0, kv_blocks_dense=0)),
                    **({} if self._decode_walk is None else
                       dict(kv_rows_walked=0)),
                    **({} if self._prefill_moe is None else
                       dict(prefill_moe_calls=0, prefill_moe_rows=0)),
                    **({} if self._prefill_attn is None else
                       dict(prefill_attn_calls=0)),
                    **({} if self._prefill_calls is None else
                       {k: 0 for k in self._prefill_calls(0, 0)}),
                    **{name: 0 for name in self._step_counters})

    def reset_stats(self):
        """Zero the cumulative throughput counters and step-segment
        times (and the prefix cache's hit accounting) — bench warmup ->
        measured pass."""
        self.stats = self._fresh_stats()
        if self.prefix_cache is not None:
            self.prefix_cache.hit_blocks = 0
            self.prefix_cache.lookup_blocks = 0

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def queued(self) -> int:
        return len(self._queue)

    @property
    def idle(self) -> bool:
        return self.active_slots == 0 and not self._queue

    # ---------------------------------------------------------- submission
    def _count_rejected(self, request: Request, reason: str):
        self._metrics.counter("serving.rejected", reason=reason).inc()
        self.stats["requests_rejected"] += 1
        # tpu-lint: allow(journal-coverage): submit-time rejection —
        # the request was never ACCEPTED, so the zero-loss journal owes
        # it nothing (the router counts tier-level rejects separately)
        self._tick_shed.append((request.request_id, reason))
        # at most one overload dump per tick, at the next step boundary
        # (a per-rejection dump would flood the sink under sustained
        # overload — the ring already carries the lead-up)
        if self._dump_pending is None:
            self._dump_pending = f"rejected:{reason}"

    def _shed_queued(self, victim: Request, reason: str):
        """Drop a queued request (displacement under a full bounded
        queue): it finishes with ``finish='shed'`` — reported, never
        silently lost. A previously-preempted victim keeps the tokens
        it already generated (like a deadline cut), not an empty
        result."""
        self._queue.remove(victim)
        self._drop_parked(victim.request_id)
        toks = victim._resume_tokens or []
        ttft = (victim._t_first - victim._t_submit
                if victim._t_first is not None
                and victim._t_submit is not None else None)
        # tpu-lint: allow(journal-coverage): engine-level displacement;
        # the Router rescues the victim onto a sibling replica or
        # journals "finish" when it collects this shed result —
        # single-engine durability is the snapshot, which serializes
        # results
        res = RequestResult(victim.request_id, victim.prompt, toks,
                            len(toks), "shed", ttft, None, 0,
                            trace_id=victim.trace_id)
        self.results[victim.request_id] = res
        self._pending_finished.append(victim.request_id)
        r = self._metrics
        r.counter("serving.rejected", reason=reason).inc()
        r.counter("serving.requests", finish="shed").inc()
        self.stats["requests_shed"] += 1
        self._tick_shed.append((victim.request_id, reason))
        if self._dump_pending is None:
            self._dump_pending = "shed"

    def estimated_ttft_s(self, request: Request,
                         default: Optional[float] = None
                         ) -> Optional[float]:
        """EWMA-capacity estimate of ``request``'s queue-wait + prefill
        time (the earliest its first token could land): decode work
        ahead of it (active slots' remaining budgets + queued requests
        at >= its priority) spread over ``max_slots`` at the EWMA
        decode step time, plus prefill work priced PER TOKEN — its own
        prompt AND the >=rank prompts queued/prefilling ahead of it, so
        a 2048-token prompt costs ~64x a 32-token one instead of one
        flat wave term (long-prompt bias would over-shed short prompts
        queued behind long ones). On a chunked engine the request's own
        prefill is priced as ceil(prompt/chunk_tokens) full chunks plus
        the ``decode_per_chunk`` decode dispatches interleaved between
        them. Fed by the same segment wall times the
        ``serving.step_*_s`` histograms observe.

        **Cold convention** (the defined contract, not an accident): an
        engine that has not completed one warm decode dispatch has NO
        capacity estimate and returns ``default`` (``None`` unless
        overridden) — never a guess. The two caller conventions:

        * *admission* (``shed_infeasible``) treats cold as
          never-shed — a request must not be rejected on zero
          evidence (``default=None``, the engine's own use);
        * *placement* (the serving :class:`~paddle_tpu.serving.Router`)
          treats cold as maximally available — an idle just-added
          replica should attract load so its estimate warms up
          (``default=0.0``).

        Callers that cannot special-case ``None`` pass the convention
        they want as ``default`` instead of re-implementing it."""
        if self._ewma_step.value is None:
            return default
        step_s = self._ewma_step.value
        tok_s = self._ewma_prefill_tok.value or 0.0
        # only work at >= this request's priority counts as "ahead":
        # strictly lower-priority slots are exactly what admission
        # would preempt for it, and lower-priority queue entries sort
        # behind it — counting either would shed feasible high-priority
        # deadlines
        ahead = sum(s.req.max_new_tokens - s.count
                    for s in self._slots
                    if s is not None and s.req.rank >= request.rank)
        ahead += sum(r.max_new_tokens - len(r._resume_tokens or [])
                     for r in self._queue.items()
                     if r.rank >= request.rank)
        # prefill tokens ahead: queued >=rank feeds (prompt + resume
        # tokens they re-prefill) and the unprefilled remainder of
        # slots still mid-chunk
        ahead_pf = sum(len(r.prompt) + len(r._resume_tokens or [])
                       for r in self._queue.items()
                       if r.rank >= request.rank)
        ahead_pf += sum(len(s.feed) - s.filled
                        for s in self._slots
                        if s is not None and s.prefilling
                        and s.req.rank >= request.rank)
        P = len(request.prompt)
        if self.chunk_tokens is not None:
            # priced at the autotuner's CURRENT bucket (== chunk_tokens
            # until a warm EWMA moves it)
            CT = self._chunk_choice or self.chunk_tokens
            n_chunks = -(-P // CT)
            own = (n_chunks * CT * tok_s
                   + (n_chunks - 1) * self.decode_per_chunk * step_s)
        else:
            own = P * tok_s
        # with speculation on, one dispatch commits an accepted-length
        # EWMA of tokens per slot (>= 1), so the decode work ahead
        # drains that much faster — pricing it at one token per step
        # would over-shed feasible deadlines exactly when speculation
        # is winning (tests/test_serving_spec.py pins the regression)
        tpt = max(self._ewma_spec_tokens.value or 1.0, 1.0)
        return (own + ahead_pf * tok_s
                + (ahead / (self.max_slots * tpt)) * step_s)

    def _check_fits(self, request: Request, count: bool):
        """The structural admissibility checks shared by
        :meth:`submit` and :meth:`admit_resumable` — ``count`` controls
        whether a refusal lands on the ``serving.rejected`` telemetry
        (submit's shed accounting; the force-admit path raises bare)."""
        P = len(request.prompt)
        worst = -(-(P + request.max_new_tokens - 1) // self.block_tokens)
        if worst > self.max_blocks_per_slot:
            if count:
                self._count_rejected(request, "too_long")
            raise ValueError(
                f"request needs {worst} blocks "
                f"({P}+{request.max_new_tokens} tokens) but max_seq_len "
                f"{self.max_seq_len} caps a slot at "
                f"{self.max_blocks_per_slot}")
        # never-fits check: optimistic bound only — with prefix caching
        # up to (P-1)//BT prompt blocks may be shared, so don't reject a
        # request the cache could make admissible. The dtype-accurate
        # reservation (int8 hits share NO physical blocks) lives in
        # _admit, where an over-sized request queues instead of raising.
        lookup = ((P - 1) // self.block_tokens
                  if self.prefix_cache is not None else 0)
        if worst - lookup > self.pool.num_blocks - 1:
            if count:
                self._count_rejected(request, "never_fits")
                self.flight.auto_dump("pool_exhausted:submit")
            raise PoolExhausted(
                f"request needs at least {worst - lookup} blocks; the "
                f"whole pool has {self.pool.num_blocks - 1}")

    def _enqueue(self, request: Request) -> int:
        """Seed assignment + submit stamping + queue push — the one
        admission tail behind :meth:`submit` and
        :meth:`admit_resumable`."""
        if request.seed is None:
            request.seed = self.seed + self._seeds_issued
            self._seeds_issued += 1
        request._t_submit = time.perf_counter()
        request._seq = self._submit_seq
        self._submit_seq += 1
        self._queue.push(request)
        self._update_gauges()
        return request.request_id

    def submit(self, request) -> int:
        """Queue a request (accepts a :class:`Request` or a 1-D prompt).
        Returns the request id; the result lands in ``self.results``.

        May raise: ``ValueError`` (request cannot fit a slot at all),
        :class:`PoolExhausted` (needs more blocks than the whole pool),
        :class:`Rejected` (load shedding — bounded queue full with no
        lower-priority victim, or deadline infeasible under the current
        capacity estimate). Every shed path is counted under
        ``serving.rejected{reason}``."""
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        if not isinstance(request, Request):
            request = Request(request)
        with jax.profiler.TraceAnnotation(
                "serving.submit", request_id=request.request_id):
            self._check_fits(request, count=True)
            if self.shed_infeasible and request.deadline_s is not None:
                est = self.estimated_ttft_s(request)
                if est is not None and est > request.deadline_s:
                    self._count_rejected(request, "deadline_infeasible")
                    raise Rejected(
                        "deadline_infeasible",
                        f"request {request.request_id} deadline "
                        f"{request.deadline_s:.3f}s < estimated "
                        f"queue-wait+prefill {est:.3f}s — it would expire "
                        f"before its first token")
            if self.max_queue is not None \
                    and len(self._queue) >= self.max_queue:
                victim = self._queue.lowest_below(request.rank)
                if victim is None:
                    self._count_rejected(request, "queue_full")
                    raise Rejected(
                        "queue_full",
                        f"queue at capacity ({self.max_queue}) with no "
                        f"lower-priority request to displace")
                self._shed_queued(victim, "displaced")
            return self._enqueue(request)

    def admit_resumable(self, request,
                        tokens: Optional[Sequence[int]] = None) -> int:
        """Force-admit a request BYPASSING the overload controls
        (bounded queue, displacement, deadline-infeasibility shedding)
        — the re-admission primitive behind :meth:`restore` and the
        router's failover / drain migration. A request on this path was
        already *accepted* once; shedding it now would turn a recovery
        action into data loss, exactly what the zero-loss contract
        forbids. ``tokens`` (generated so far) arms the token-exact
        resume: the engine re-prefills the prompt, replays the tokens
        through the decode step program and continues the request's
        own ``fold_in(seed, count)`` stream, so the final tokens are
        bit-identical to an uninterrupted run. The
        *structural* checks still apply — a request that cannot fit a
        slot (``ValueError``) or the whole pool (``PoolExhausted``)
        raises exactly like :meth:`submit`; config-identical replicas
        would have rejected it at the original accept too."""
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        if not isinstance(request, Request):
            request = Request(request)
        self._check_fits(request, count=False)
        if tokens is not None:
            request._resume_tokens = list(tokens) or None
        return self._enqueue(request)

    def release_request(self, request_id: int) -> Optional[List[int]]:
        """Remove one UNFINISHED request from this engine entirely and
        return its generated-so-far tokens (the token-exact resume
        state another engine re-admits through
        :meth:`admit_resumable`) — the role-migration primitive: a
        prefill-role replica releases a request at first token and the
        router re-places it on a decode-role replica. An active slot
        goes through the preemption path first (blocks freed, full
        bf16 blocks donated to the prefix cache, resume tokens
        captured), then the requeued request is popped back out.
        Returns None when this engine does not hold the request
        unfinished (already retired, or never here) — the caller must
        NOT re-place it elsewhere in that case."""
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        rid = int(request_id)
        self._settle()
        slot_idx = next((i for i, s in enumerate(self._slots)
                         if s is not None and s.req.request_id == rid),
                        None)
        if slot_idx is not None:
            self._preempt(slot_idx)
        for req in list(self._queue.items()):
            if req.request_id == rid:
                self._queue.remove(req)
                # the KV is leaving this engine — any host-tier parked
                # copy (including one the _preempt above just made) is
                # dead weight here; the migration target re-prefills or
                # receives the blocks through the tier prefix store
                self._drop_parked(rid)
                self._update_gauges()
                return list(req._resume_tokens or [])
        return None

    def inflight_tokens(self) -> Dict[int, List[int]]:
        """``{request_id: generated-so-far tokens}`` for every
        UNFINISHED request this engine holds — active decode slots, mid
        prefill slots (which report the resume tokens they were
        admitted with) and queued requests (their resume tokens, empty
        for fresh ones). The router's per-tick progress mirror: by the
        resume contract, re-placing a dead replica's request with any
        *prefix* of its true token stream (whatever this method last
        reported) stays token-exact."""
        out: Dict[int, List[int]] = {}
        for s in self._slots:
            if s is None:
                continue
            out[s.req.request_id] = (list(s.resume or []) if s.prefilling
                                     else list(s.tokens))
        for r in self._queue.items():
            out[r.request_id] = list(r._resume_tokens or [])
        return out

    # ----------------------------------- tier-wide prefix store surface
    def export_prefix_blocks(self, keys: Sequence[str]
                             ) -> Dict[str, Tuple[int, np.ndarray]]:
        """Exact bf16 KV payloads for the requested prefix-chain keys
        (hex) this replica's cache still holds — the tier-wide prefix
        store's fetch path. bf16 pools gather the physical blocks out
        of the pool in ONE bucketed dispatch; int8 pools return the
        cache's exact bf16 host copies. Missing keys are silently
        absent: the tier index is a hint, and a partial fetch just
        shortens the copied run."""
        out: Dict[str, Tuple[int, np.ndarray]] = {}
        if self.prefix_cache is None or self._closed:
            return out
        want = []
        for k in keys:
            e = self.prefix_cache.entry(k)
            if e is None:
                continue
            if e.kv_host is not None:
                # tpu-lint: allow(host-sync): kv_host is a host copy
                out[k] = (e.depth, np.asarray(e.kv_host))
            elif e.block_id is not None:
                want.append((k, e))
        if want:
            m = _swap_bucket(len(want))
            bids = np.full(m, SCRATCH_BLOCK, np.int32)
            bids[:len(want)] = [e.block_id for _, e in want]
            buf = self._swap_fn("gather")(self.kv_pool, self._up(bids))
            # tpu-lint: allow(host-sync): once-per-fetch D2H — prefix
            # blocks ship across the tier as host arrays
            buf = np.asarray(buf)
            for c, (k, e) in enumerate(want):
                # tpu-lint: allow(host-sync): host slice copy
                out[k] = (e.depth, np.ascontiguousarray(buf[:, c]))
        return out

    def import_prefix_blocks(self, entries: Dict[str, Tuple]) -> int:
        """Adopt another replica's prefix blocks into THIS replica's
        cache — the tier-wide prefix store's delivery path. bf16 pools
        allocate physical blocks and scatter the payloads in (one
        bucketed dispatch; the cache owns the refs, so a later
        admission shares them exactly like locally prefilled blocks);
        int8 pools keep the exact bf16 host copies and requantize at
        adoption — the cache's native int8 representation. Entries
        already cached, or that the pool has no spare room for, are
        skipped (a miss, not an error). Returns blocks added."""
        cache = self.prefix_cache
        if cache is None or self._closed or not entries:
            return 0
        added = 0
        todo = []
        for k, (depth, kv) in entries.items():
            if self.kv_int8:
                # tpu-lint: allow(host-sync): wire payloads are host
                if cache.adopt_entry(k, depth,
                                     kv_host=np.asarray(kv)):
                    added += 1
            elif cache.entry(k) is None:
                todo.append((k, int(depth), kv))
        if todo:
            # never squeeze live work: only free-and-unreserved blocks
            # (plus idle cache blocks) host imported prefixes
            free = self.pool.free_blocks - self._reserved
            if len(todo) > free:
                cache.evict_free(len(todo) - free)
                free = self.pool.free_blocks - self._reserved
            todo = todo[:max(free, 0)]
        if todo:
            bids = self.pool.alloc(len(todo))
            m = _swap_bucket(len(todo))
            dbids = np.full(m, SCRATCH_BLOCK, np.int32)
            dbids[:len(todo)] = bids
            buf = np.zeros((self._num_layers, m, self.block_tokens,
                            self._cache_lanes), jnp.dtype(self.cache_dtype))
            for c, (_, _, kv) in enumerate(todo):
                buf[:, c] = kv
            dev = (self.layout.place(buf, self.layout.pool_spec())
                   if self.layout is not None else jax.device_put(buf))
            self.kv_pool = self._swap_fn("scatter")(
                self.kv_pool, self._up(dbids), dev)
            for bid, (k, depth, _) in zip(bids, todo):
                if cache.adopt_entry(k, depth, block_id=bid):
                    added += 1
                else:       # raced into the cache meanwhile: give back
                    self.pool.free(bid)
        if added:
            self._metrics.counter(
                "serving.offload.prefix_import_blocks").inc(added)
        return added

    # ------------------------------------------------------------- prefill
    def _prefill_wave_fn(self, R, s_pad, n):
        """Batched prefill program for a WAVE of ``n`` same-shape
        admissions (shared prefix depth ``R``, padded prompt tail
        ``s_pad``): the prefix gather (bf16: straight from the pool),
        the forward pass, the pool adopt scatter, the int8 calibration,
        and the first-token sample are ONE dispatch. A b=1 prefill of a
        short prompt streams every weight once — the same traffic as a
        whole decode step — so admissions that land on the same tick
        share one weight pass and one pool write instead of paying both
        per request.

        Returns ``(fn, cached)`` — ``cached=False`` means this call
        will pay the trace+compile, which the EWMA capacity estimator
        must not ingest (a multi-second compile spike would make
        ``shed_infeasible`` reject feasible deadlines for dozens of
        steps; the ``serving.step_prefill_s`` histogram still sees it).
        """
        from paddle_tpu.inference import (_fold_rows, _row_keys,
                                          _sample_logits)
        from paddle_tpu.nn.layer import functional_call

        key = ("prefill", self.kv_int8, R, s_pad, n)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn, True
        # the cache adapter and the head's rows: a plan's own, else the
        # [k | v] rows of a llama/gpt cache and every position's logits
        own = self._own_step
        counted = self._prefill_counted
        lanes_w = self._cache_lanes
        dkv = lanes_w // 2
        hybrid = isinstance(self.kv_pool, dict)
        aux = self._pool_aux
        if own:
            to_lanes = self.meta["to_lanes"]
            from_lanes = self.meta.get("from_lanes")
            to_state = self.meta.get("to_state")
        else:
            nkv, hd = self.meta["num_kv_heads"], self.meta["head_dim"]
            to_lanes, from_lanes = _kv_to_lanes, functools.partial(
                _kv_from_lanes, nkv=nkv, hd=hd)
        BT = self.block_tokens
        cache_len = R + s_pad
        hb = R // BT                 # shared prefix blocks per row
        nb_new = s_pad // BT         # freshly prefilled blocks per row
        n0 = hb + nb_new             # blocks covering the whole prompt
        model = self.model
        int8 = self.kv_int8
        mp_axis = self._mp_axis

        def impl(state, pool, prefix, ids, last_idx, seeds, new_bids,
                 valid_len, slots=None):
            # prefix: bf16 pools pass the (n, hb) shared block ids and
            # gather the prefix KV HERE (no separate dispatch); int8
            # pools pass the host-kept bf16 copies (L, n, R, 2dkv) —
            # quantized blocks are per-slot-scaled, never shareable.
            # Under mp the pool's last dim is the LOCAL [k_s|v_s] lanes
            # (pool.shape[-1] == 2dkv/mp inside the shard): prefix
            # gathers reassemble the canonical width, adopt scatters
            # keep only the shard's own lanes.
            cache = model.init_cache(n, cache_len, dtype=jnp.bfloat16)
            if R:
                with part("attn"):      # the shared prefix, read back
                    if int8:
                        pk = prefix
                    else:
                        pk = pool[:, prefix].reshape(
                            len(cache), n, R, pool.shape[-1])
                        if mp_axis is not None:
                            pk = mp_gather_kv_lastdim(pk, mp_axis)
                    cache = from_lanes(cache, pk)
            # an own plan's model computes the head at each row's last
            # position only, and counts its routed rows if the plan
            # says so; the model's forward names its own parts
            out, cache, *moe_rows = functional_call(
                model, state, ids, cache=cache, start_pos=R,
                **({"positions": last_idx} if own else {}),
                **({"moe_rows": True} if counted else {}))
            with part("head"):
                logits = out if own else jnp.take_along_axis(
                    out, last_idx[:, None, None], axis=1)[:, 0]  # (n, vocab)
            with part("sample"):
                tok = _sample_logits(logits, _fold_rows(_row_keys(seeds), 0),
                                     self.temperature, self.top_k,
                                     self.top_p)
                if counted:
                    # the count rides the wave's one pull behind its
                    # tokens
                    tok = jnp.concatenate([tok, moe_rows[0][None].astype(
                        tok.dtype)])
            with part("attn"):          # the cache write
                kv_flat = to_lanes(cache)        # (L, n, cache_len, lanes)
                if hybrid:
                    # a hybrid plan: the second paged leaf lands in the
                    # same fresh blocks, the rows' fixed-size state in
                    # their slots
                    slot_state = {
                        name: pool["state"][name].at[:, slots].set(
                            leaf.astype(pool["state"][name].dtype))
                        for name, leaf in to_state(cache).items()}
                    pool = pool["pool"]
                    if aux is not None:
                        (kv_flat, aux_flat), (pool, aux_pool) = kv_flat, pool
                        aux_pool = aux_pool.at[:, new_bids].set(
                            aux_flat.reshape(
                                -1, n, nb_new, BT // aux["stride"],
                                aux["lanes"]).astype(aux_pool.dtype))
                if int8:
                    # per-request calibration: amax over each row's
                    # VALID prompt positions only — the padded tail
                    # holds pad-token kv, which must not leak into the
                    # scales (matches quantize_kv_cache over a
                    # contiguous cache)
                    mask = (jnp.arange(cache_len)[None]
                            < valid_len[:, None])[None, :, :, None]
                    a = jnp.where(mask,
                                  jnp.abs(kv_flat.astype(jnp.float32)),
                                  0.0).max(axis=2)          # (L, n, 2dkv)
                    a = a.reshape(-1, n, 2 * nkv, hd).max(axis=-1)
                    lanes = jnp.repeat(jnp.maximum(a / 127.0, 1e-8), hd,
                                       axis=-1)             # (L, n, 2dkv)
                    q = jnp.clip(jnp.round(
                        kv_flat.astype(jnp.float32)
                        / lanes[:, :, None, :]), -127, 127).astype(jnp.int8)
                    blkq = q.reshape(-1, n, n0, BT, 2 * dkv)
                    if mp_axis is not None:
                        blkq = mp_local_kv_lastdim(blkq, mp_axis)
                    pool = pool.at[:, new_bids].set(blkq)
                    return tok, pool, lanes, kv_flat
                blk = kv_flat[:, :, R:cache_len].reshape(
                    -1, n, nb_new, BT, lanes_w)
                if mp_axis is not None:
                    blk = mp_local_kv_lastdim(blk, mp_axis)
                pool = pool.at[:, new_bids].set(blk.astype(pool.dtype))
            if hybrid:
                pool = {"pool": pool if aux is None else (pool, aux_pool),
                        "state": slot_state}
            return tok, pool

        # `state` flows as a traced argument (matching generate) so the
        # weights are not baked into the program as constants
        from jax.sharding import PartitionSpec as P
        lay = self.layout
        pspec = lay.pool_spec() if lay is not None else None
        in_specs = (P(), pspec) + (P(),) * (7 if hybrid else 6)
        out_specs = ((P(), pspec, P(), P()) if int8 else (P(), pspec))
        jitted = self._wrap_program("prefill", impl, in_specs, out_specs,
                                    donate_argnums=(1,))
        fn = _program_handle(jitted, lambda: (self._state,))
        self._jit_cache[key] = fn
        return fn, False

    def _autotune_chunk(self, s_pad: int) -> int:
        """The chunk size for a freshly admitted prefill: with
        ``chunk_autotune`` off, the configured ``chunk_tokens``; with
        it on, the LARGEST bucket on the power-of-two ladder anchored
        at ``chunk_tokens`` whose predicted fused-tick time fits under
        the ``slo_tpot_s`` headroom — on a fused engine a chunk tick IS
        a decode latency for every active slot, so the chunk budget is
        the TPOT SLO minus nothing (the decode half rides inside the
        same program). Predictions use the per-bucket tick-time EWMA
        where one exists, else the per-token prefill EWMA times the
        bucket (plus the decode-step EWMA the fused tick carries).
        Re-evaluated at bucket boundaries only — once per admission
        group, never mid-prefill — so the cursor lattice (and with it
        the compile set) stays finite and pinnable: a bucket transition
        compiles exactly its new (start, chunk, C_pad) programs and
        nothing twice (tests/test_analysis.py). Returns the
        PER-ADMISSION pick — clamped at the first bucket covering
        ``s_pad``, possibly probe-overridden; the un-clamped SLO pick
        is what persists in ``_chunk_choice`` for
        :meth:`estimated_ttft_s` pricing (a short admission's clamp,
        or a probe's unmeasured bucket, must not re-price every other
        queued prompt)."""
        base = self.chunk_tokens
        if not self.chunk_autotune:
            return base
        tok = self._ewma_prefill_tok.value
        if tok is None:
            pick = pricing = base   # cold: no evidence, no tuning
        else:
            step = self._ewma_step.value or 0.0

            def largest_fit(cs):
                best = None
                for c in cs:        # ascending: keep the largest
                    ew = self._chunk_time_ewma.get(c)
                    pred = (ew.value if ew is not None
                            and ew.value is not None
                            else tok * c + step)
                    if pred <= self.slo_tpot_s:
                        best = c
                return cs[0] if best is None else best

            cands = [base]
            c = base // 2           # ladder: power-of-two multiples of
            while c >= self.block_tokens and c % self.block_tokens == 0:
                cands.insert(0, c)  # the configured anchor, down to
                c //= 2             # one block and up to the slot cap
            c = base * 2
            while c <= self.max_seq_len:
                cands.append(c)
                c *= 2
            # the PRICING pick is evaluated on the FULL ladder — it is
            # what estimated_ttft_s charges every queued prompt, so the
            # per-admission clamp/probe below must not leak into it (a
            # 16-token admission's clamped bucket would over-price a
            # long deadline-carrying submit severalfold and over-shed)
            pricing = largest_fit(cands)
            # clamp at the FIRST bucket covering this admission's feed
            # bucket, in both directions — a chunk wider than s_pad is
            # pure padding (it forwards, and compiles programs for,
            # positions the prompt doesn't have), including when the
            # covering bucket sits below the configured anchor
            cover = next((i for i, cc in enumerate(cands)
                          if cc >= s_pad), len(cands) - 1)
            del cands[cover + 1:]
            pick = largest_fit(cands)
            # one-step-up probing (the spec k=0 recovery-probe
            # pattern): the linear per-token prediction is badly
            # pessimistic on weight-stream-dominated backends — a 4x
            # chunk costs nowhere near 4x a tick — so an UNMEASURED
            # next bucket would never be chosen on prediction alone
            # and its per-bucket EWMA could never observe. Every
            # _CHUNK_PROBE_EVERY tuned admissions, pick the next
            # bucket up ONCE so it gets measured; evidence (not the
            # prediction) then decides whether the pick climbs.
            # the wait counter advances ONLY on probe-eligible
            # admissions and is frozen (not reset) by ineligible ones
            # — a short prompt whose clamped ladder tops out at the
            # current pick must not starve the long prompts' probe
            # under an interleaved length mix
            nxt = next((c for c in cands if c > pick), None)
            if (nxt is not None and nxt not in self._chunk_time_ewma
                    and self._chunk_probe_tries.get(nxt, 0)
                    < _CHUNK_PROBE_TRIES):
                self._chunk_probe_wait += 1
                if self._chunk_probe_wait >= _CHUNK_PROBE_EVERY:
                    self._chunk_probe_wait = 0
                    self._chunk_probe_tries[nxt] = (
                        self._chunk_probe_tries.get(nxt, 0) + 1)
                    pick = nxt
        self._chunk_choice = pricing
        self._metrics.gauge("serving.chunk_autotune").set(pricing)
        return pick

    def _make_chunk_groups(self, wave):
        """Group this tick's chunked admissions by prefill bucket
        ``(R, s_pad)`` and push one :class:`_ChunkGroup` per bucket —
        n same-shape rows advance one chunk each per fused tick (the
        wave batching the n=1 chunk FIFO lost). Every group input is
        uploaded to the device HERE, once per admission (the tick is a
        join event anyway), so subsequent mid-prefill fused ticks
        re-dispatch with zero H2D."""
        BT = self.block_tokens
        L = self._num_layers
        buckets: Dict = {}
        for slot_idx, slot, hits, R, s_pad in wave:
            buckets.setdefault((R, s_pad), []).append((slot_idx, slot))
        for (R, s_pad), rows in buckets.items():
            CT = self._autotune_chunk(s_pad)
            C_pad = R + -(-s_pad // CT) * CT
            g = _ChunkGroup(rows, R, CT, C_pad, self.kv_int8)
            n = len(rows)
            NB = C_pad // BT
            ids = np.zeros((n, C_pad), np.int32)
            bids = np.full((n, NB), SCRATCH_BLOCK, np.int32)
            last_idx = np.zeros(n, np.int32)
            seeds = np.zeros(n, np.uint32)
            valid = np.zeros(n, np.int32)
            last_start = C_pad - CT
            for r, (slot_idx, s) in enumerate(rows):
                P = len(s.feed)
                ids[r, :P] = s.feed
                bids[r, :s.ntab] = s.blocks
                last_idx[r] = P - 1 - last_start
                seeds[r] = np.uint32(s.req.seed)
                valid[r] = len(s.req.prompt)
            g.dev_ids = self._up(ids)
            g.dev_bids = self._up(bids)
            g.dev_last = self._up(last_idx)
            g.dev_seeds = self._up(seeds)
            if self.kv_int8:
                g.dev_valid = self._up(valid)
                if R:
                    # int8 chunk 0 over prefix hits rides the cache's
                    # exact bf16 host copies (quantized blocks are
                    # per-slot-scaled, never shareable) — uploaded once
                    hit_rows = [s.hits for _, s in rows]
                    g.dev_prefix = self._up(np.stack(
                        [np.concatenate([e.kv_host for e in hs], axis=1)
                         for hs in hit_rows], axis=1))   # (L, n, R, 2dkv)
                    assert g.dev_prefix.shape == (L, n, R, self._cache_lanes)
            for _, s in rows:
                s.hits = None       # consumed; drop the cache refs
            self._prefill_fifo.append(g)
        if buckets:
            self._dirty = True      # join event: mirrors re-upload

    def _compact_group(self, g: "_ChunkGroup"):
        """Drop rows whose slot retired/preempted/unwound mid-prefill
        (identity check — the index may since hold a different slot)
        and slice the group's device inputs (and resident carry) down
        to the survivors. A shrink is an EVENT tick: the n in the
        program key changes, so the next chunk recompiles — preemption
        and deadline sweeps are rare paths, never the steady state."""
        keep = [r for r, (i, s) in enumerate(g.rows)
                if self._slots[i] is s and s.prefilling]
        if len(keep) == len(g.rows):
            return
        g.rows = [g.rows[r] for r in keep]
        if not g.rows:
            return
        # tpu-lint: allow(host-sync): host row-index list, not a device
        # value — the gather below runs on device
        sel = np.asarray(keep, np.int32)
        g.dev_ids = g.dev_ids[sel]
        g.dev_bids = g.dev_bids[sel]
        g.dev_last = g.dev_last[sel]
        g.dev_seeds = g.dev_seeds[sel]
        if g.dev_valid is not None:
            g.dev_valid = g.dev_valid[sel]
        if g.dev_prefix is not None:
            g.dev_prefix = g.dev_prefix[:, sel]
        if g.carry is not None:
            g.carry = g.carry[:, sel]
        self._dirty = True

    def _front_prefill(self) -> Optional["_ChunkGroup"]:
        """The group at the head of the prefill FIFO (compacted to its
        live rows), or None."""
        while self._prefill_fifo:
            g = self._prefill_fifo[0]
            self._compact_group(g)
            if g.rows:
                return g
            self._prefill_fifo.pop(0)
        return None

    def _chunk_body(self, kind, start, n, C_pad, CT, R):
        """Trace-time CHUNK half of the fused tick: forward ``CT``
        prompt tokens for ``n`` same-bucket rows over the KV of the
        ``start`` tokens already processed, advance the RESIDENT carry
        in place, and hand the block-aligned pool payload to the decode
        half (ONE combined scatter inside the same program —
        ``ops.fused_decode.paged_chunk_scatter``).

        ``kind='mid'``: bf16 pools GATHER the processed prefix
        [0, start) straight from pool blocks (every completed chunk
        already scattered; no carry buffer exists at all — the
        O(prompt²/chunk) staging round trip BENCH_r06 caveated is
        simply gone); int8 pools thread the resident bf16 carry
        (L, n, C_pad, 2dkv), RMW'd via a static
        ``dynamic_update_slice`` — the caller donates it, so the
        buffer aliases in place (donation_report pins it). Chunk 0 of
        a multi-chunk int8 prefill CREATES the carry in-program
        (zeros + prefix + chunk — no eager zeros program, no upload).
        ``kind='last'``: samples each row's first token; int8 pools
        calibrate per-slot scales over the ORIGINAL prompt positions
        and quantize+scatter every prompt block in one go (the scale
        deferral that keeps chunked int8 bit-identical to monolithic).

        Returns ``(chunk_bids, chunk_kv, carry2, tok, lanes, kvfull)``
        — any of which may be None depending on kind/dtype."""
        from paddle_tpu.inference import (_fold_rows, _row_keys,
                                          _sample_logits)
        from paddle_tpu.nn.layer import functional_call

        nkv, hd = self.meta["num_kv_heads"], self.meta["head_dim"]
        dkv = nkv * hd
        BT = self.block_tokens
        cache_len = start + CT
        model = self.model
        int8 = self.kv_int8
        last = kind == "last"
        keep_kv = self.prefix_cache is not None
        temperature, top_k, top_p = (self.temperature, self.top_k,
                                     self.top_p)
        mp_axis = self._mp_axis

        def body(state, pool, carry, ids, bids, prefix, last_idx,
                 cseeds, valid):
            cache = model.init_cache(n, cache_len, dtype=jnp.bfloat16)
            pk = None
            if start:
                cache, pk = read_prefix(cache, pool, carry, bids, prefix)
            out, cache = functional_call(
                model, state, jax.lax.slice_in_dim(
                    ids, start, cache_len, axis=1),
                cache=cache, start_pos=start)
            tok = None
            if last:
                with part("head"):
                    logits = jnp.take_along_axis(
                        out, last_idx[:, None, None], axis=1)[:, 0]
                with part("sample"):
                    tok = _sample_logits(logits,
                                         _fold_rows(_row_keys(cseeds), 0),
                                         temperature, top_k, top_p)
            with part("attn"):          # the cache write
                chunk_bids, chunk_kv, carry2, lanes, kvfull = write_chunk(
                    cache, bids, valid, carry, pk)
            return chunk_bids, chunk_kv, carry2, tok, lanes, kvfull

        def read_prefix(cache, pool, carry, bids, prefix):
            """The processed prefix back into the forward's cache."""
            with part("attn"):
                if not int8:
                    # bf16: every completed chunk already scattered its
                    # blocks into the pool, so the processed prefix
                    # GATHERS straight from pool blocks — no carry
                    # buffer at all (the chunk-0 CoW gather generalized
                    # to every cursor; bit-exact, the pool stores the
                    # same bf16 the carry would). Only int8 pools need
                    # the resident bf16 carry (quantized blocks cannot
                    # re-feed the forward).
                    # under mp the pool gather yields the LOCAL lanes;
                    # one tiled all_gather reassembles the canonical
                    # width (the exact bf16 bytes every shard scattered)
                    pk = pool[:, bids[:, :start // BT]].reshape(
                        len(cache), n, start, pool.shape[-1])
                    if mp_axis is not None:
                        pk = mp_gather_kv_lastdim(pk, mp_axis)
                elif start == R:    # int8 chunk 0 over a prefix hit
                    pk = prefix
                else:               # int8 mid/last: the resident carry
                    pk = jax.lax.slice_in_dim(carry, 0, start, axis=2)
                for l in range(len(cache)):
                    kl = pk[l, :, :, :dkv].reshape(n, start, nkv, hd)
                    vl = pk[l, :, :, dkv:].reshape(n, start, nkv, hd)
                    cache[l] = {
                        "k": cache[l]["k"].at[:, :start].set(
                            kl.astype(cache[l]["k"].dtype)),
                        "v": cache[l]["v"].at[:, :start].set(
                            vl.astype(cache[l]["v"].dtype))}
            return cache, pk

        def write_chunk(cache, bids, valid, carry, pk):
            """The chunk's rows on their way to the pool -> (chunk_bids,
            chunk_kv, carry2, lanes, kvfull)."""
            kv_flat = jnp.stack([jnp.concatenate(
                [c["k"].reshape(n, cache_len, dkv),
                 c["v"].reshape(n, cache_len, dkv)], axis=-1)
                for c in cache])             # (L, n, cache_len, 2dkv)
            lanes = kvfull = carry2 = None
            chunk_bids = chunk_kv = None
            if int8 and last:
                # calibration over the original prompt positions only
                # (resume appends beyond the prompt were quantized with
                # prompt-only scales in the uninterrupted run too);
                # padded-tail kv must not leak into the scales either
                mask = (jnp.arange(cache_len)[None]
                        < valid[:, None])[None, :, :, None]
                a = jnp.where(mask, jnp.abs(kv_flat.astype(jnp.float32)),
                              0.0).max(axis=2)          # (L, n, 2dkv)
                a = a.reshape(-1, n, 2 * nkv, hd).max(axis=-1)
                lanes = jnp.repeat(jnp.maximum(a / 127.0, 1e-8), hd,
                                   axis=-1)
                q = jnp.clip(jnp.round(
                    kv_flat.astype(jnp.float32) / lanes[:, :, None, :]),
                    -127, 127).astype(jnp.int8)
                # bids covers every C_pad//BT block; entries past the
                # feed's last allocated block are SCRATCH, so padded-
                # tail garbage lands in the masked scratch block
                chunk_bids = bids
                chunk_kv = q.reshape(-1, n, cache_len // BT, BT, 2 * dkv)
                if keep_kv:
                    kvfull = kv_flat    # host bf16 prefix-cache copies
            elif not int8:
                # bf16: this chunk's blocks scatter as they complete
                chunk_bids = jax.lax.slice_in_dim(
                    bids, start // BT, cache_len // BT, axis=1)
                chunk_kv = kv_flat[:, :, start:].reshape(
                    -1, n, CT // BT, BT, 2 * dkv)
            if int8 and not last:
                new_kv = kv_flat[:, :, start:].astype(jnp.bfloat16)
                if start == R:      # first chunk builds the carry
                    carry2 = jnp.zeros((len(cache), n, C_pad, 2 * dkv),
                                       jnp.bfloat16)
                    if R:
                        carry2 = carry2.at[:, :, :R].set(
                            pk.astype(jnp.bfloat16))
                    carry2 = carry2.at[:, :, R:cache_len].set(new_kv)
                else:               # RMW in place: donated + aliased
                    carry2 = jax.lax.dynamic_update_slice_in_dim(
                        carry, new_kv, start, axis=2)
            return chunk_bids, chunk_kv, carry2, lanes, kvfull

        return body

    def _tick_fn(self, kind, start, n, C_pad, CT, R, K):
        """ONE program per tick: the fused Sarathi coscheduled tick —
        the front group's next prefill chunk AND every decode-ready
        slot's next token (K=0) or k-token verify tail (K>0) dispatch
        together, the pool and resident carry donated and aliased
        in-place. Keyed by the chunk bucket (kind, start, n, C_pad,
        CT, R) × the decode tail K, so the compile set is one program
        per chunk bucket — exactly as pinnable as the two-program
        tick's chunk set was (tests/test_analysis.py).

        Returns ``(fn, cached)`` — ``cached=False`` means this call
        pays the trace+compile, which the EWMA estimators must not
        ingest."""
        from paddle_tpu.inference import resident_carry_donate_argnums

        key = ("tick", kind, self.kv_int8, start, n, C_pad, CT, R, K)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn, True
        chunk_body = self._chunk_body(kind, start, n, C_pad, CT, R)
        spec = K > 0
        ngram = spec and self.speculate.proposer == "ngram"
        dec_body = self._verify_body(K) if spec else self._decode_body()
        int8 = self.kv_int8
        last = kind == "last"
        # only int8 pools thread the resident bf16 carry — bf16 mid
        # chunks gather their processed prefix from the pool itself
        has_carry = int8 and start > R
        has_prefix = int8 and R > 0 and start == R
        keep_kv = self.prefix_cache is not None

        def impl(state, stacked, pool, *rest):
            rest = list(rest)
            carry = rest.pop(0) if has_carry else None
            ids = rest.pop(0)
            bids = rest.pop(0)
            prefix = rest.pop(0) if has_prefix else None
            last_idx = rest.pop(0) if last else None
            cseeds = rest.pop(0) if last else None
            valid = rest.pop(0) if (last and int8) else None
            (tables, positions, toks, seeds, counts,
             kv_scales) = rest[:6]
            srest = rest[6:]
            chunk_bids, chunk_kv, carry2, ctok, lanes, kvfull = \
                chunk_body(state, pool, carry, ids, bids, prefix,
                           last_idx, cseeds, valid)
            if spec:
                proposals, nprop, cap = srest[0], srest[1], srest[2]
                hist = srest[3] if ngram else None
                dec = dec_body(state, stacked, pool, tables, positions,
                               toks, seeds, counts, kv_scales,
                               proposals, nprop, cap, hist,
                               chunk_bids, chunk_kv)
            else:
                dec = dec_body(state, stacked, pool, tables, positions,
                               toks, seeds, counts, kv_scales,
                               chunk_bids, chunk_kv)
            outs = tuple(o for o in (carry2, ctok, lanes,
                                     kvfull if keep_kv else None)
                         if o is not None)
            return (*dec, *outs)

        donate = [2]                # the pool, as every decode program
        if has_carry and not last:
            # the resident carry: RMW'd in place on MID chunks (input
            # shape == output shape — the donation_report pin). A LAST
            # chunk consumes the carry with no matching output, so
            # donating it is declared-but-unusable (jax warns per
            # program and frees the buffer mid-execution on some
            # backends) — the buffer dies with the group right after
            # the tick anyway
            donate.append(3)
        if ngram:
            # the carried ngram history (the _build_verify_fn donation,
            # at its shifted position behind the chunk args)
            donate.append(3 + int(has_carry) + 2 + int(has_prefix)
                          + (2 if last else 0)
                          + (1 if (last and int8) else 0) + 6 + 3)
        from jax.sharding import PartitionSpec as P
        lay = self.layout
        pspec = lay.pool_spec() if lay is not None else None
        sspec = lay.kv_scales_spec() if lay is not None else None
        rest_specs = []
        if has_carry:
            rest_specs.append(P())
        rest_specs += [P(), P()]                    # ids, bids
        if has_prefix:
            rest_specs.append(P())
        if last:
            rest_specs += [P(), P()]                # last_idx, cseeds
        if last and int8:
            rest_specs.append(P())                  # valid
        rest_specs += [P()] * 5 + [sspec]           # tables..kv_scales
        if spec:
            rest_specs += [P(), P(), P()]           # props, nprop, cap
        if ngram:
            rest_specs.append(P())                  # history
        in_specs = (P(), self._stacked_specs or P(), pspec, *rest_specs)
        if spec:
            dec_specs = [P()] * (9 if ngram else 6)
            dec_specs[2] = pspec
        else:
            dec_specs = [P(), pspec, P(), P()]
        n_outs = ((1 if (int8 and not last) else 0)
                  + (1 if last else 0)
                  + ((1 + (1 if keep_kv else 0))
                     if (int8 and last) else 0))
        out_specs = (*dec_specs, *([P()] * n_outs))
        jitted = self._wrap_program(
            "tick", impl, in_specs, out_specs,
            donate_argnums=resident_carry_donate_argnums(*donate))
        fn = _program_handle(jitted,
                             lambda: (self._state, self._stacked))
        self._jit_cache[key] = fn
        return fn, False

    def _commit_chunk(self, g: "_ChunkGroup", start, kind, ctok_np,
                      lanes_np, kvfull_np, warm):
        """Host-side tail of a fused tick's chunk half: advance every
        row's cursor (mid) or adopt it into the decode batch (last —
        :meth:`_adopt_slot`, the one join path), then the chunk
        telemetry: ``serving.prefill_chunks`` / chunk-size and
        chunk-rows histograms / the prefill-chunk span, the
        warm-tick EWMA feeds (global + per-bucket for the autotuner,
        per COMPUTED token for the estimator), and the chunk-stall
        auto-dump trigger."""
        from paddle_tpu import observability as obs

        CT = g.chunk
        n = g.n
        last = kind == "last"
        t_wall = self._tick_decode_s()
        for r, (slot_idx, s) in enumerate(g.rows):
            ntok = min(CT, len(s.feed) - start)
            self._tick_chunks.append((s.req.request_id, start, ntok))
            self._metrics.histogram(
                "serving.chunk_tokens",
                buckets=_CHUNK_SIZE_BUCKETS).observe(ntok)
            s.filled = start + CT
            if last:
                self._adopt_slot(
                    slot_idx, s, int(ctok_np[r]),
                    None if lanes_np is None else lanes_np[:, r],
                    None if kvfull_np is None else kvfull_np[:, r])
        if not last and g.dev_prefix is not None and start == g.R:
            # the int8 prefix-hit bf16 copy is consumed by chunk 0
            # only (args() appends it at the R cursor alone) — drop it
            # now rather than hold an (L, n, R, 2dkv) buffer alongside
            # the carry for the rest of a long prefill
            g.dev_prefix = None
        self.stats["prefill_chunks"] += 1
        r = self._metrics
        r.counter("serving.prefill_chunks").inc()
        r.histogram("serving.chunk_rows",
                    buckets=_CHUNK_ROWS_BUCKETS).observe(n)
        tr = obs.active_tracer()
        if tr is not None and g.rows:
            s0 = g.rows[0][1]
            tr.record("serving.prefill_chunk", ts=time.time() - t_wall,
                      dur_s=t_wall, request_id=s0.req.request_id,
                      trace_id=s0.req.trace_id,
                      start=int(start),
                      tokens=int(min(CT, len(s0.feed) - start)),
                      rows=int(n), last=bool(last))
        if warm:    # compile spikes must not poison estimator/stall EWMAs
            ew = self._ewma_chunk.value
            if ew is not None and t_wall > 4.0 * ew \
                    and self._dump_pending is None:
                # a warm fused tick overrunning 4x its EWMA is the
                # chunked-prefill analog of a step_prefill_s outlier —
                # snapshot the ring for the postmortem
                self._dump_pending = "chunk_stall"
            self._ewma_chunk.update(t_wall)
            self._chunk_time_ewma.setdefault(CT, _Ewma()).update(t_wall)
            # per COMPUTED token, not per valid token: the program
            # always forwards the full CT-wide chunk (tails are
            # padded), and estimated_ttft_s prices a prompt as
            # ceil(P/CT) * CT * tok_s — dividing a short last chunk's
            # wall time by its few valid tokens would inflate the EWMA
            # up to CT-fold and over-shed feasible deadlines. NOT
            # amortized by the row count either: weight streaming
            # dominates a chunk tick, so an n-row tick costs ~one
            # n=1 tick — dividing by n would teach the autotuner a
            # per-token cost it cannot reproduce on n=1 groups and
            # blow the TPOT SLO exactly when load thins out
            self._ewma_prefill_tok.update(t_wall / CT)

    def _release_slot(self, slot_idx: int):
        """Free a slot's blocks and reservation and zero its block
        table + host mirrors — the ONE teardown behind retire, preempt
        and wave-unwind (a new per-slot mirror array must be reset
        here, nowhere else)."""
        s = self._slots[slot_idx]
        for bid in s.blocks:
            self.pool.free(bid)
        s.hits = None           # slot objects linger on the prefill
                                # FIFO; drop the cache refs now
        if s.dblocks:           # draft proposer pages
            for bid in s.dblocks:
                self._draft_pool_blocks.free(bid)
            s.dblocks = []
        if self._draft_tables is not None:
            self._draft_tables[slot_idx][:] = SCRATCH_BLOCK
        if self._history is not None:
            self._history[slot_idx][:] = 0
        if self._spec_cap is not None:
            # a fresh occupant starts at the configured k, optimistic
            self._spec_cap[slot_idx] = self._spec_k
            self._spec_k_slot[slot_idx] = self._spec_k
            self._spec_acc_ewma[slot_idx] = _Ewma()
        self._reserved -= s.worst_blocks - s.ntab
        self._slots[slot_idx] = None
        self._tables[slot_idx][:] = SCRATCH_BLOCK
        self._positions[slot_idx] = 0
        self._toks[slot_idx] = 0
        self._counts[slot_idx] = 0
        self._dirty = True

    def _preempt_victim(self, rank: int, exclude) -> Optional[int]:
        """Slot index of the lowest-priority, loosest-deadline active
        slot with priority STRICTLY below ``rank`` (preemption never
        crosses within a class, so a preempted-then-requeued request
        can never preempt its preemptor back — no ping-pong). ``exclude``
        holds this tick's freshly admitted slots (their prefill has not
        run; there is nothing to resume from)."""
        best = best_key = None
        for i, s in enumerate(self._slots):
            if s is None or i in exclude or s.req.rank >= rank:
                continue
            slack = (float("inf") if s.deadline_at is None
                     else s.deadline_at)
            key = (s.req.rank, -slack)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _preempt(self, slot_idx: int):
        """Retire a slot back to the queue with its generated-so-far
        tokens: frees its blocks (bf16: after donating its full
        immutable blocks to the prefix cache, so resume re-prefill
        adopts instead of recomputing), releases its reservation, and
        requeues the request for a token-exact resume.

        ``offload=True`` (docs/SERVING.md §Hierarchical KV): the
        victim's blocks are GATHERED to a host-bound buffer before the
        slot tears down, so preemption becomes a block-table remap plus
        a background drain — resume scatters the bytes back instead of
        re-prefilling and replaying. The resume tokens are STILL
        captured: the parked KV is an accelerator, and any failure on
        the swap path falls back to the token-exact replay resume."""
        s = self._slots[slot_idx]
        req = s.req
        if s.prefilling:
            # mid-chunk victim: no tokens sampled yet — requeue with
            # whatever resume state it was admitted with (None for a
            # fresh request); its partial KV (and carry) are dropped
            # with the slot, and the chunked re-prefill recomputes them
            req._resume_tokens = s.resume
        else:
            req._resume_tokens = list(s.tokens)
            req._t_first = s.t_first
        swapped = (self.offload and not s.prefilling
                   and self._swap_out(slot_idx, s))
        if self.prefix_cache is not None and not self.kv_int8 \
                and not s.prefilling and not swapped:
            # feed = prompt + generated[:-1]: exactly the s.pos written
            # positions; its full blocks are append-proof and already
            # physically populated — cache them (the cache takes its own
            # refs) so the resume prefill mostly gathers instead of
            # recomputing
            full = s.pos // self.block_tokens
            if full:
                # tpu-lint: allow(host-sync): host token-list concat
                self.prefix_cache.insert(
                    np.concatenate([req.prompt, np.asarray(
                        s.tokens[:-1], np.int32)]),
                    0, block_ids=s.blocks[:full])
        self._release_slot(slot_idx)
        self._queue.push(req)
        self.stats["preemptions"] += 1
        self._metrics.counter("serving.preemptions").inc()
        # tpu-lint: allow(journal-coverage): preemption is NOT terminal
        # — the request requeues in-engine with its tokens, which the
        # router's periodic "progress" events keep mirroring
        self._tick_preempted.append(req.request_id)
        if self._dump_pending is None:
            self._dump_pending = "preemption"

    # --------------------------- hierarchical KV: host-tier swap paths
    def _swap_fn(self, kind: str):
        """Jitted whole-block gather/scatter (the ONE seam the host
        tier touches device KV through — ``ops.fused_decode.
        paged_block_gather/scatter``; the fused tick program itself is
        untouched, so every compile-set and donation pin holds)."""
        fn = self._swap_fns.get(kind)
        if fn is None:
            from paddle_tpu.ops.fused_decode import (paged_block_gather,
                                                     paged_block_scatter)
            fn = (jax.jit(paged_block_gather) if kind == "gather"
                  else jax.jit(paged_block_scatter, donate_argnums=(0,)))
            self._swap_fns[kind] = fn
        return fn

    def _swap_out(self, slot_idx: int, s: "_Slot") -> bool:
        """Gather the preemption victim's blocks into one device buffer
        bound for the host tier. Returns False — the caller keeps the
        legacy free(+donate)+recompute path — when the tier has no
        room, a fault fires, or the engine runs a draft proposer (the
        draft's own KV pages cannot be restored; recompute-on-resume
        is the correct fallback there).

        The gather output is an independent buffer, so the source
        blocks are free to reuse the moment the gather is DISPATCHED:
        single-stream ordering guarantees any later program's writes
        into re-issued blocks execute after this read. The D2H leg
        (``copy_to_host_async``) overlaps the following serving ticks;
        :meth:`_drain_swaps` lands the bytes next tick."""
        from paddle_tpu.resilience import faults as _faults
        n = len(s.blocks)
        if n == 0 or self._draft_tables is not None \
                or not self.host_store.reserve(n):
            return False
        try:
            fault = _faults.maybe_fire("offload.swap")
        except BaseException:
            # a raising fault downgrades to the legacy path — zero
            # loss: the resume tokens were captured before the attempt
            self.host_store.unreserve(n)
            return False
        m = _swap_bucket(n)
        bids = np.full(m, SCRATCH_BLOCK, np.int32)
        bids[:n] = s.blocks
        buf = self._swap_fn("gather")(self.kv_pool, self._up(bids))
        try:
            buf.copy_to_host_async()
        except Exception:   # noqa: BLE001 — overlap is best-effort
            pass
        if fault is not None and fault.kind == "hang":
            # inside the swap window: chaos SIGKILLs land mid-swap here
            time.sleep(float(fault.payload.get("seconds", 0.05)))
        # tpu-lint: allow(host-sync): _kv_scales is a host mirror
        pk = _Parked(s.req.request_id, buf, n,
                     (np.array(self._kv_scales[:, slot_idx, :])
                      if self.kv_int8 else None),
                     s.pos, s.tok, s.count, list(s.tokens),
                     s.worst_blocks, s.prefix_hit_blocks)
        self._parked[s.req.request_id] = pk
        self._tick_swapped_out.append(s.req.request_id)
        self.stats["swap_outs"] += 1
        self._metrics.counter("serving.offload.swap_outs").inc()
        return True

    def _drain_swaps(self):
        """Land completed swap-out gathers in the host tier — called at
        tick start, at least one dispatch after each gather, so the D2H
        already overlapped with the tick that preempted (lazy drain:
        the sync below observes a transfer that is effectively done)."""
        for pk in self._parked.values():
            if pk.dev is not None:
                self._drain_one(pk)

    def _drain_one(self, pk: "_Parked"):
        # tpu-lint: allow(host-sync): the host tier's classified D2H
        # seam — draining an async gather a previous tick dispatched
        buf = np.asarray(pk.dev)
        pk.dev = None
        # tpu-lint: allow(host-sync): host slice copy of the drained buf
        pk.host_ids = self.host_store.put(
            [np.ascontiguousarray(buf[:, c]) for c in range(pk.n)])
        nbytes = pk.n * self.block_bytes
        self.stats["swap_out_bytes"] += nbytes
        self._metrics.counter("serving.offload.swap_out_bytes").inc(
            nbytes)

    def _stage_parked(self, pk: "_Parked"):
        """Assemble a parked request's host blocks into one bucketed
        device upload (async ``device_put`` H2D — the scatter that
        consumes it synchronizes). Every stage is timed into the swap
        EWMA: the prefetch policy's probe-and-observe estimate."""
        t0 = time.perf_counter()
        m = _swap_bucket(pk.n)
        buf = np.zeros((self._num_layers, m, self.block_tokens,
                        self._cache_lanes), jnp.dtype(self.cache_dtype))
        for c, p in enumerate(self.host_store.get(pk.host_ids)):
            buf[:, c] = p
        dev = (self.layout.place(buf, self.layout.pool_spec())
               if self.layout is not None else jax.device_put(buf))
        nbytes = pk.n * self.block_bytes
        self.stats["swap_in_bytes"] += nbytes
        self._metrics.counter("serving.offload.swap_in_bytes").inc(
            nbytes)
        self._ewma_swap_s.update(time.perf_counter() - t0)
        return dev

    def _offload_prefetch(self):
        """Stage host-resident parked requests back to device AHEAD of
        admission (EWMA prediction, the ``chunk_autotune``
        probe-and-observe pattern): the base lookahead is
        ``offload_prefetch`` queue positions, widened by the predicted
        number of serving ticks one stage costs (swap EWMA / decode
        step EWMA) — when staging is slow relative to a tick, it must
        start earlier for the admit path to never block on a cold
        copy."""
        lead = self.offload_prefetch
        if self._ewma_swap_s.value is not None and self._ewma_step.value:
            lead += max(0, -(-int(self._ewma_swap_s.value * 1e6)
                             // max(int(self._ewma_step.value * 1e6), 1))
                        - 1)
        lead = min(lead, self.max_slots + self.offload_prefetch)
        for pos, req in enumerate(self._queue):
            if pos >= lead:
                break
            pk = self._parked.get(req.request_id)
            if pk is None or pk.host_ids is None \
                    or req.request_id in self._staged:
                continue
            self._staged[req.request_id] = self._stage_parked(pk)

    def _drop_parked(self, request_id: int):
        """Invalidate a request's host-tier state (consumed / shed /
        released / fault fallback): free its host blocks and staging.
        Safe to call for requests that were never parked."""
        pk = self._parked.pop(request_id, None)
        self._staged.pop(request_id, None)
        if pk is None:
            return
        if pk.dev is not None:
            pk.dev = None       # un-drained gather: just drop the buf
            self.host_store.unreserve(pk.n)
        elif pk.host_ids is not None:
            self.host_store.free(pk.host_ids)

    def _swap_in_admit(self, req: Request, pk: "_Parked",
                       wave_idx) -> str:
        """Admit a parked request by scattering its host-tier blocks
        into freshly allocated pool blocks and rebuilding the slot row
        DIRECTLY — no prefill program, no replay dispatches: the
        generated-position KV comes back bitwise (the parity matrix in
        tests/test_serving_offload.py pins it against uninterrupted
        generation). Returns ``"admitted"``, ``"blocked"``
        (head-of-line: no slot/blocks this tick) or ``"fallback"``
        (parked KV unusable — the caller runs the legacy token-exact
        re-prefill + replay resume)."""
        from paddle_tpu.resilience import faults as _faults
        if pk.dev is not None:
            # preempted and re-admitted inside one tick: the background
            # drain has not seen this gather yet — land it now
            self._drain_one(pk)
        try:
            fault = _faults.maybe_fire("offload.swap")
        except BaseException:
            self._drop_parked(req.request_id)
            return "fallback"
        worst = max(pk.worst_blocks, pk.n)
        n = pk.n
        while True:
            short = worst - (self.pool.free_blocks - self._reserved)
            if short <= 0:
                break
            # same reclaim ladder as the legacy admission path:
            # cached-but-idle prefix blocks first, then strictly
            # lower-priority victims
            if self.prefix_cache is not None \
                    and self.prefix_cache.evict_free(short):
                continue
            victim = self._preempt_victim(req.rank, wave_idx)
            if victim is None:
                return "blocked"
            self._preempt(victim)
        try:
            slot_idx = self._slots.index(None)
        except ValueError:
            victim = self._preempt_victim(req.rank, wave_idx)
            if victim is None:
                return "blocked"
            self._preempt(victim)
            slot_idx = victim
        self._queue.pop()
        req._resume_tokens = None       # consumed; _preempt re-sets
        staged = self._staged.pop(req.request_id, None)
        if staged is not None:
            buf = staged
            self.stats["prefetch_hits"] += 1
            self._metrics.counter("serving.offload.prefetch",
                                  outcome="hit").inc()
        else:
            buf = self._stage_parked(pk)
            self.stats["prefetch_misses"] += 1
            self._metrics.counter("serving.offload.prefetch",
                                  outcome="miss").inc()
        if fault is not None and fault.kind == "hang":
            # inside the swap window: chaos SIGKILLs land mid-swap here
            time.sleep(float(fault.payload.get("seconds", 0.05)))
        bids = self.pool.alloc(n)
        dbids = np.full(buf.shape[1], SCRATCH_BLOCK, np.int32)
        dbids[:n] = bids
        self.kv_pool = self._swap_fn("scatter")(
            self.kv_pool, self._up(dbids), buf)
        s = _Slot(req, worst, pk.prefix_hit_blocks, req.prompt, None)
        s.blocks = bids
        s.ntab = n
        s.pos = pk.pos
        s.tok = pk.tok
        s.count = pk.count
        s.tokens = list(pk.tokens)
        s.t_first = req._t_first
        row = self._tables[slot_idx]
        row[:] = SCRATCH_BLOCK
        row[:n] = bids
        self._positions[slot_idx] = s.pos
        self._toks[slot_idx] = s.tok
        self._seeds[slot_idx] = np.uint32(req.seed)
        self._counts[slot_idx] = s.count
        if self.kv_int8 and pk.scales is not None:
            self._kv_scales[:, slot_idx, :] = pk.scales
        if req.deadline_s is not None:
            s.deadline_at = req._t_submit + req.deadline_s
        if self._history is not None:
            # ngram proposer: same priming as _adopt_slot's resume
            # branch — prompt + generated[:-1], current last token
            # tpu-lint: allow(host-sync): host token-list concat
            hist = np.concatenate(
                [req.prompt, np.asarray(pk.tokens[:-1], np.int32)])
            self._history[slot_idx][:] = 0
            self._history[slot_idx, :len(hist)] = hist
            self._history[slot_idx,
                          min(len(hist), self.max_seq_len - 1)] = s.tok
        self._reserved += worst - n
        self._slots[slot_idx] = s
        self._dirty = True
        wave_idx.add(slot_idx)
        self._drop_parked(req.request_id)
        self._tick_admitted.append(req.request_id)
        self._tick_swapped_in.append(req.request_id)
        self.stats["requests_admitted"] += 1
        self.stats["requests_resumed"] += 1
        self.stats["swap_ins"] += 1
        # tpu-lint: allow(journal-coverage): swap-in resume is not
        # terminal; the router already journaled the re-placement
        # ("place") that queued this resume
        self._tick_resumed.append(req.request_id)
        r = self._metrics
        r.counter("serving.resumed").inc()
        r.counter("serving.offload.swap_ins").inc()
        return "admitted"

    def _admit(self):
        """Priority admission: while a slot and the head request's
        worst-case block reservation both fit, pop it into the current
        wave; the wave is grouped by prefill shape ``(R, s_pad)`` and
        each group runs as ONE batched prefill program. The queue is
        ordered (priority, submit order) and stays head-of-line WITHIN
        that order; when the head cannot be placed, strictly
        lower-priority slots are preempted (requeued resumable, never
        dropped) to make room — first for a slot, then for blocks.

        Chunked mode (``chunk_tokens``): admission only places slots —
        blocks reserved/allocated, cursor at the prefix depth — and
        queues them on the prefill FIFO; the chunk programs run one per
        tick from :meth:`_step_inner`, so admission cost stays bounded
        and no prefill program blocks the tick that admitted it."""
        if self.chunk_tokens is not None:
            wave = []
            wave_idx = set()
            try:
                self._collect_wave(wave, wave_idx)
                # same-bucket admissions form one _ChunkGroup — n rows
                # advance one chunk each per fused tick (wave batching)
                self._make_chunk_groups(wave)
            except BaseException:
                self._unwind_wave(wave)
                raise
            return
        while self._queue:
            wave = []           # (slot_idx, slot, hits, R, s_pad)
            wave_idx = set()    # slots admitted this wave: not preemptable
            try:
                self._collect_wave(wave, wave_idx)
            except BaseException:
                # a raising fault at a MID-wave admission pop (or any
                # error before the wave's prefill ran) must not leave
                # earlier same-wave slots active with unwritten KV — a
                # retried step() would decode them from position 0 over
                # garbage. Unwind every un-prefilled slot back to the
                # queue (resumable, like a preemption) and re-raise.
                self._unwind_wave(wave)
                raise
            if not wave:
                return
            self._dirty = True
            groups: Dict = {}
            for item in wave:
                groups.setdefault((item[3], item[4]), []).append(item)
            try:
                for (R, s_pad), grp in groups.items():
                    self._run_prefill_group(R, s_pad, grp)
            except BaseException:
                self._unwind_wave(wave)     # only count==0 slots unwind
                raise
            # an instantly-finished admission (eos/1-token budget on the
            # prefill sample) frees its slot — loop for the next wave

    def _unwind_wave(self, wave):
        """Return every slot in ``wave`` whose prefill never ran
        (``count == 0`` — no KV written, no tokens) to the queue,
        releasing its blocks and reservation; prefilled slots are fully
        valid actives and stay."""
        for slot_idx, slot, _hits, _R, _s_pad in wave:
            if slot.count != 0 or self._slots[slot_idx] is not slot:
                continue
            req = slot.req
            self._release_slot(slot_idx)
            req._resume_tokens = slot.resume
            self._queue.push(req)
            if req.request_id in self._tick_admitted:
                self._tick_admitted.remove(req.request_id)
                self.stats["requests_admitted"] -= 1
            if slot.resume and req.request_id in self._tick_resumed:
                self._tick_resumed.remove(req.request_id)
                self.stats["requests_resumed"] -= 1

    def _collect_wave(self, wave, wave_idx):
        """Pop admissible requests into ``wave`` (see :meth:`_admit`
        for the policy; :meth:`_unwind_wave` for the fault contract)."""
        from paddle_tpu.resilience import faults as _faults

        BT = self.block_tokens
        while self._queue:
            req = self._queue.peek()
            if self._parked:
                pk = self._parked.get(req.request_id)
                if pk is not None:
                    st = self._swap_in_admit(req, pk, wave_idx)
                    if st == "blocked":
                        break
                    if st == "admitted":
                        continue
                    # "fallback": the parked KV is gone — the legacy
                    # token-exact re-prefill + replay resume below
            rank = req.rank
            resume = req._resume_tokens
            # a resume prefills the PROMPT only — the same program and
            # inputs as its original admission, so the prompt KV is
            # bitwise the original's. Its generated tokens REPLAY
            # through the real decode step program afterwards
            # (_replay_resume): recomputing them through the batched
            # prefill forward rounds differently in the last bf16 ulp
            # than the per-token decode path that first produced them,
            # and one ulp is enough to flip a near-tie argmax — the
            # token-exact contract must not hinge on ties being rare
            feed = req.prompt
            P = len(feed)
            n_lookup = (P - 1) // BT
            hits = (self.prefix_cache.lookup(feed, n_lookup,
                                             record=False)
                    if self.prefix_cache is not None else [])
            # worst case covers the FINAL sequence (original prompt
            # + full budget) — identical for fresh and resumed
            # admissions, so a resume can always re-reserve what its
            # first admission could
            worst = -(-(len(req.prompt) + req.max_new_tokens - 1)
                      // BT)
            # bf16 hits ride the cached PHYSICAL blocks (refcount++,
            # no fresh allocation); int8 hits only skip prefill
            # FLOPs — the slot still allocates every prompt block,
            # so they don't reduce the worst-case reservation
            spare = 0 if self.kv_int8 else len(hits)
            short = worst - spare - (self.pool.free_blocks
                                     - self._reserved)
            if short > 0:
                # feasibility BEFORE destroying live work: preempting a
                # victim gains at most its full reservation (physical
                # blocks freed + blocks shifted to cache-only + the
                # unreserved tail = worst_blocks), and eviction at most
                # the cache-only blocks. If even that optimistic total
                # cannot cover the shortfall, the head cannot be placed
                # this tick — break with zero preemptions instead of
                # evicting every lower-priority slot for nothing.
                potential = sum(
                    s.worst_blocks for i, s in enumerate(self._slots)
                    if s is not None and i not in wave_idx
                    and s.req.rank < rank)
                if self.prefix_cache is not None:
                    potential += self.prefix_cache.evictable_count(
                        keep=hits)
                if short > potential:
                    break
            try:
                slot_idx = self._slots.index(None)
            except ValueError:
                victim = self._preempt_victim(rank, wave_idx)
                if victim is None:
                    break
                if self._flight_q:
                    # a victim is requeued with every token computed
                    # for it; the landed step may free a slot by itself
                    self._land_all()
                    continue
                self._preempt(victim)
                slot_idx = victim
                if self.prefix_cache is not None:
                    # the preempt's cache insert may have LRU-evicted
                    # stale `hits` entries (their blocks are gone) and
                    # donated new shareable ones — re-probe before the
                    # hits are adopted
                    hits = self.prefix_cache.lookup(feed, n_lookup,
                                                    record=False)
                    spare = 0 if self.kv_int8 else len(hits)
            while True:
                short = (worst - spare
                         - (self.pool.free_blocks - self._reserved))
                if short <= 0:
                    break
                if self.prefix_cache is not None:
                    # cached-but-idle prefix blocks are reclaimable
                    # pool capacity — evict LRU entries (never this
                    # request's own hits) before preempting live work
                    if self.prefix_cache.evict_free(short, keep=hits):
                        continue
                victim = self._preempt_victim(rank, wave_idx)
                if victim is None:
                    break
                if self._flight_q:
                    self._land_all()    # as above; then look again
                    continue
                self._preempt(victim)
                if self.prefix_cache is not None:
                    # the victim donated its blocks to the cache —
                    # re-probe: the head may now share them
                    hits = self.prefix_cache.lookup(feed, n_lookup,
                                                    record=False)
                    spare = 0 if self.kv_int8 else len(hits)
            if short > 0:
                break       # head-of-line within priority order
            # fault site BEFORE the pop: a raising fault (the PR 4
            # injection contract for decode.dispatch) leaves the
            # request queued — a retried step() re-admits it; firing
            # after the pop would lose it (no queue, slot or result)
            _faults.maybe_fire("decode.dispatch")
            self._queue.pop()
            req._resume_tokens = None   # consumed; _preempt re-sets
            if self.prefix_cache is not None:
                self.prefix_cache.commit(hits, n_lookup)

            R = len(hits) * BT
            n0 = -(-P // BT)        # blocks covering the feed
            s_pad = -(-(P - R) // BT) * BT
            slot = _Slot(req, worst, len(hits), feed, resume)
            slot.R = R
            row = self._tables[slot_idx]
            row[:] = SCRATCH_BLOCK
            if self.kv_int8:
                slot.blocks = self.pool.alloc(n0)
            else:
                for e in hits:  # slot's own ref on shared blocks
                    self.pool.ref(e.block_id)
                slot.blocks = ([e.block_id for e in hits]
                               + self.pool.alloc(n0 - len(hits)))
            slot.ntab = n0
            if self.chunk_tokens is not None:
                # chunked: the mirror table row STAYS at scratch until
                # the last chunk lands — a decode append into a
                # half-written prompt block would corrupt it. Blocks
                # ride the group's device block-id table; _adopt_slot
                # publishes the row when the slot joins decode.
                # (_make_chunk_groups batches this wave into groups.)
                slot.prefilling = True
                slot.filled = R
                slot.hits = hits
                if req.deadline_s is not None:
                    # mid-prefill expiry must sweep chunked slots (a
                    # monolithic slot prefills the tick it is admitted)
                    slot.deadline_at = req._t_submit + req.deadline_s
            else:
                row[:n0] = slot.blocks
            self._reserved += worst - n0
            self._slots[slot_idx] = slot
            self._tick_admitted.append(req.request_id)
            self.stats["requests_admitted"] += 1
            if resume:
                self.stats["requests_resumed"] += 1
                # tpu-lint: allow(journal-coverage): resume admission is
                # not terminal; the router already journaled the
                # re-placement ("place") that queued this resume
                self._tick_resumed.append(req.request_id)
            wave.append((slot_idx, slot, hits, R, s_pad))
            wave_idx.add(slot_idx)

    def _wave_kernels(self, R: int, s_pad: int, n: int) -> Dict:
        """What a wave of ``n`` rows of ``s_pad`` positions behind ``R``
        cached ones sends through the plan's prefill kernels, known from
        the wave's shape. ``prefill_attn_calls``: the layers whose
        attention takes the flash kernel (the plan's predicate).
        ``prefill_moe_calls`` and ``prefill_moe_rows``: one call of the
        grouped kernel an expert layer, k routed rows a position a layer
        (pad positions route too); where the plan's rows are
        ``"counted"`` only the calls: the rows are the program's own
        count, known once the wave is pulled. Nothing for a plan without
        the keys."""
        out = {}
        if self._prefill_attn is not None:
            out["prefill_attn_calls"] = self._prefill_attn(R, s_pad)
        if self._prefill_calls is not None:
            out.update(self._prefill_calls(R, s_pad))
        pm = self._prefill_moe
        if pm is not None:
            per = pm.get("calls_a_layer", lambda s: 1)
            calls = pm["layers"] * per(s_pad) if pm["path"] == "kernel" else 0
            out["prefill_moe_calls"] = calls
            if not self._prefill_counted:
                out["prefill_moe_rows"] = calls * pm["k"] * s_pad * n
        return out

    def _run_prefill_group(self, R, s_pad, grp):
        """Run one batched prefill program and adopt each row's slot
        into the running decode batch. The whole group (program + host
        pulls + slot adoption) is timed as the step's wave-prefill
        segment."""
        n = len(grp)
        sent = self._wave_kernels(R, s_pad, n)
        # a plan that names its prefill kernels also gets the rows' true
        # lengths: what its kernels' work is counted from
        true_len = ({} if self._prefill_calls is None else
                    {"true_len": sum(len(g[1].feed) for g in grp)})
        with self._phase("serving.step.prefill", rows=n, s_pad=s_pad,
                         R=R, **true_len, **sent) as ph:
            BT = self.block_tokens
            L = self._num_layers
            hb = R // BT
            ids = np.zeros((n, s_pad), np.int32)
            last_idx = np.zeros(n, np.int32)
            seeds = np.zeros(n, np.uint32)
            valid = np.zeros(n, np.int32)
            for r, (slot_idx, slot, hits, _, _) in enumerate(grp):
                P = len(slot.feed)
                ids[r, :P - R] = slot.feed[R:]
                last_idx[r] = P - 1 - R
                seeds[r] = np.uint32(slot.req.seed)
                # int8 calibration runs over the ORIGINAL prompt positions
                # only — for a fresh request that is the whole feed; for a
                # resume it reproduces the scales the uninterrupted run
                # calibrated at ITS prefill (appends beyond the prompt were
                # quantized with prompt-only scales there too, so resume
                # stays token-exact)
                valid[r] = len(slot.req.prompt)
            fn, warm = self._prefill_wave_fn(R, s_pad, n)
            if self.kv_int8:
                new_bids = np.asarray([s.blocks for _, s, _, _, _ in grp],
                                      np.int32)                    # (n, n0)
                prefix = (self._up(np.stack(
                    [np.concatenate([e.kv_host for e in hits], axis=1)
                     for _, _, hits, _, _ in grp], axis=1)) if hb
                    else self._up(np.zeros((L, n, 0, self._cache_lanes),
                                           np.float32).astype(jnp.bfloat16)))
                tok, self.kv_pool, lanes, kv_flat = fn(
                    self.kv_pool, prefix, self._up(ids),
                    self._up(last_idx), self._up(seeds),
                    self._up(new_bids), self._up(valid))
                # tpu-lint: allow(host-sync): once-per-wave D2H — int8 scales
                lanes_np = np.asarray(lanes)
                # tpu-lint: allow(host-sync): once-per-wave D2H — the prefix
                # cache keeps exact bf16 host copies of int8 blocks
                kv_np = (np.asarray(kv_flat)
                         if self.prefix_cache is not None else None)
            else:
                new_bids = np.asarray(
                    [s.blocks[hb:] for _, s, _, _, _ in grp], np.int32)
                prefix = (np.asarray([[e.block_id for e in hits]
                                      for _, _, hits, _, _ in grp], np.int32)
                          if hb else np.zeros((n, 0), np.int32))
                tok, self.kv_pool = fn(
                    self.kv_pool, self._up(prefix), self._up(ids),
                    self._up(last_idx), self._up(seeds),
                    self._up(new_bids), self._up(valid),
                    *((self._up(np.asarray([g[0] for g in grp], np.int32)),)
                      if isinstance(self.kv_pool, dict) else ()))
                lanes_np = kv_np = None
            if self._flight_q:
                # the wave went out BEHIND the step program in flight
                # (it needs the pool and its own uploads, nothing of the
                # host's): its first tokens and that step's come back in
                # one device_get, and the step commits before the rows
                # join (the upload that follows needs its tokens)
                (tok_np,) = self._land(extra=(tok,))
            else:
                # tpu-lint: allow(host-sync): once-per-wave D2H — first
                # tokens
                tok_np = np.asarray(tok)
            if self._prefill_counted:
                # the picks that fell on held experts, as the program
                # counted them (0 where no kernel ran)
                sent["prefill_moe_rows"] = (
                    int(tok_np[n]) if sent["prefill_moe_calls"] else 0)
                ph.set(prefill_moe_rows=sent["prefill_moe_rows"])
            for r, (slot_idx, slot, hits, _, _) in enumerate(grp):
                self._adopt_slot(
                    slot_idx, slot, int(tok_np[r]),
                    None if lanes_np is None else lanes_np[:, r],
                    None if kv_np is None else kv_np[:, r])
            self._tick_prefills.append((R, s_pad, n))
            for key, v in sent.items():
                self.stats[key] += v
        if warm:        # compile spikes must not poison the estimator
            new_toks = sum(len(s.feed) - s.R for _, s, _, _, _ in grp)
            self._ewma_prefill_tok.update(ph.dur_s / max(new_toks, 1))

    def _replay_resume(self, slot_idx: int, s: "_Slot"):
        """Replay a resumed request's generated-so-far tokens through
        the REAL decode step program, one forced token per dispatch,
        every other batch row masked against scratch. Recomputing those
        positions through the prefill forward would be cheaper (one
        program) but rounds differently in the last bf16 ulp than the
        per-token decode path that first produced them — and one ulp
        flips a near-tie argmax, a token-parity break the zero-loss
        contract cannot afford. Replaying the same program at the same
        positions with the same inputs reproduces the uninterrupted
        engine's KV bitwise (decode rows are batch-composition-
        invariant — the PR 5 join/leave parity property). Cost:
        ``len(resume) - 1`` dispatches per resume; resumes are
        preemption/failover events, not the hot path."""
        if len(s.resume) <= 1:
            return
        if self._step_fn is None:
            self._step_fn = self._build_step_fn()
        ms = self.max_slots
        for j, tok in enumerate(s.resume[:-1]):
            self._ensure_blocks(slot_idx)   # append position = s.pos
            # FRESH host arrays per dispatch — never mutate a numpy
            # buffer a previous jnp.asarray may still be transferring
            # (PJRT CPU uploads are ImmutableUntilTransferCompletes;
            # reusing-and-mutating one raced with the fused tick still
            # executing and fed a later iteration's token/position into
            # an earlier dispatch — a once-in-a-few-runs parity flip)
            tables = np.full((ms, self.max_blocks_per_slot),
                             SCRATCH_BLOCK, np.int32)
            positions = np.zeros(ms, np.int32)
            toks = np.zeros(len(self._toks), np.int32)
            seeds = np.zeros(ms, np.uint32)
            counts = np.zeros(ms, np.int32)
            seeds[slot_idx] = np.uint32(s.req.seed)
            tables[slot_idx, :s.ntab] = s.blocks
            positions[slot_idx] = s.pos
            toks[slot_idx] = int(tok)
            counts[slot_idx] = j + 1
            _nxt, self.kv_pool, _pos, _cnt = self._step_fn(
                self.kv_pool, self._up(tables),
                self._up(positions), self._up(toks),
                self._up(seeds), self._up(counts),
                self._up_scales())
            s.pos += 1
        n = len(s.resume) - 1
        self.stats["replay_tokens"] += n
        self._metrics.counter("serving.replay_tokens").inc(n)

    def _adopt_slot(self, slot_idx: int, s: "_Slot", tok: int,
                    lanes_row, kv_row):
        """Join a fully-prefilled slot to the running decode batch: the
        mirror table row and per-slot device-mirror state, resume/TTFT
        bookkeeping, int8 scales, the prefix-cache insert and instant
        finishes. The ONE adoption path behind both the monolithic wave
        (one call per wave row) and the chunked path (after a slot's
        last chunk) — parity between the two modes lives here.

        The prefill sample ``tok`` is a FRESH request's first GENERATED
        token (``stats["decode_tokens"]`` counts only decode-step
        tokens); a resumed slot's sample is discarded — its next token
        comes from the next decode step at ``fold_in(seed, count)``,
        exactly where the uninterrupted run's stream stood."""
        req = s.req
        P = len(s.feed)
        BT = self.block_tokens
        s.prefilling = False
        s.hits = None
        # publish the block-table row (the chunked path deferred it so
        # decode appends could not touch half-written prompt blocks)
        self._tables[slot_idx][:s.ntab] = s.blocks
        self._dirty = True
        if lanes_row is not None:
            self._kv_scales[:, slot_idx, :] = lanes_row
        s.pos = P
        r = self._metrics
        if s.resume:
            s.count = len(s.resume)
            s.tok = int(s.resume[-1])
            s.tokens = list(s.resume)
            # TTFT is measured once, at the ORIGINAL first token —
            # a preemption must not reset it (crash restore has no
            # surviving monotonic base; it restarts the clock)
            s.t_first = (req._t_first if req._t_first is not None
                         else time.perf_counter())
            # the prefill above covered the PROMPT only (bitwise the
            # original admission's program); the generated-so-far
            # tokens replay through the real decode step program so
            # the resumed KV is bitwise what the uninterrupted run
            # held — advances s.pos to P + count - 1
            self._replay_resume(slot_idx, s)
            r.counter("serving.resumed").inc()
        else:
            s.count = 1
            s.tok = int(tok)
            s.tokens = [s.tok]
            s.t_first = time.perf_counter()
            r.counter("serving.tokens_generated").inc()
        if req.deadline_s is not None and s.deadline_at is None:
            s.deadline_at = req._t_submit + req.deadline_s
        self._positions[slot_idx] = s.pos
        self._toks[slot_idx] = s.tok
        self._seeds[slot_idx] = np.uint32(req.seed)
        self._counts[slot_idx] = s.count
        if self._history is not None:
            # ngram proposer: the committed tokens are the prompt, the
            # replayed resume prefix, and the slot's current last token
            # — the suffix the device matcher extends
            # tpu-lint: allow(host-sync): host token-list concat
            hist = (s.feed if not s.resume else np.concatenate(
                [s.feed, np.asarray(s.resume[:-1], np.int32)]))
            self._history[slot_idx][:] = 0
            self._history[slot_idx, :len(hist)] = hist
            self._history[slot_idx,
                          min(len(hist), self.max_seq_len - 1)] = s.tok
        if self._draft_tables is not None:
            self._run_draft_prefill(slot_idx, s)
        self.stats["prefill_tokens"] += P - s.R
        self.stats["prefill_tokens_reused"] += s.R
        if self.prefix_cache is not None:
            # full feed blocks are append-proof (appends land at
            # pos >= P) — bf16 shares them as-is, copy-on-write by
            # construction; int8 keeps exact bf16 copies host-side.
            # Inserts land AFTER the prefill program so a same-wave
            # sibling can never hit blocks not yet written (it just
            # misses; the next wave sees the entries).
            nh = s.prefix_hit_blocks
            if self.kv_int8:
                if kv_row is not None:
                    # copy the slices: a view would pin the whole
                    # (L, cache_len, 2dkv) buffer per cached block
                    # tpu-lint: allow(host-sync): host slice copy
                    self.prefix_cache.insert(
                        s.feed, nh,
                        kv_host=[np.ascontiguousarray(
                            kv_row[:, c * BT:(c + 1) * BT])
                                 for c in range(nh, P // BT)])
            else:
                self.prefix_cache.insert(
                    s.feed, nh, block_ids=s.blocks[nh:P // BT])
        eos = self.eos_token_id
        if (eos is not None and s.tok == int(eos)) \
                or s.count >= req.max_new_tokens:
            self._retire(slot_idx,
                         "eos" if eos is not None
                         and s.tok == int(eos) else "length")

    # -------------------------------------------------------------- decode
    def _decode_body(self):
        """Trace-time DECODE half shared by the plain step program and
        the fused tick: one paged decode step for every slot, with an
        optional coscheduled prefill-chunk scatter folded into the same
        pool pass (``ops.fused_decode.fused_paged_tick_step``)."""
        from paddle_tpu.inference import _row_keys, _sample_logits
        from paddle_tpu.ops.fused_decode import fused_paged_tick_step

        meta, arch, int8 = self.meta, self.arch, self.kv_int8
        model, cos_tab, sin_tab = self.model, self._cos_tab, self._sin_tab
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p
        pos_cap = self.max_seq_len - 1
        if self._own_step:
            ms = self.max_slots

            def own_body(state, _stacked, pool, tables, positions, toks,
                         seeds, counts, _kv_scales):
                # the plan's step over the state's own leaves (no
                # stacked copy, no scales: both arrive as None); `toks`
                # carries the last program's counters behind its tokens
                # embed, step and head name their own parts
                plan_t = model.fused_decode_plan(state)
                x = plan_t["embed"](toks[:ms], positions)
                if isinstance(pool, dict):      # a hybrid plan's leaves
                    x, paged, slot_state, tallies = plan_t["step"](
                        x, pool["pool"], tables, positions, pool["state"])
                    pool = {"pool": paged, "state": slot_state}
                else:
                    x, pool, tallies = plan_t["step"](x, pool, tables,
                                                      positions)
                logits = plan_t["head"](x)
                with part("sample"):
                    keys = _row_keys(seeds)
                    ki = jax.vmap(jax.random.fold_in)(keys, counts)
                    nxt = _sample_logits(logits, ki, temperature, top_k,
                                         top_p)
                    pos2 = jnp.minimum(positions + 1, pos_cap)
                    return (jnp.concatenate([nxt,
                                             tallies.astype(nxt.dtype)]),
                            pool, pos2, counts + 1)

            return own_body
        # under mp the body runs INSIDE shard_map: each shard walks its
        # own heads over its own pool lanes (local counts), and the
        # fused op gathers at the o-proj boundary; mp=1 passes the full
        # counts and mp_axis=None — the byte-identical pre-mp trace
        mp_axis = self._mp_axis
        nh_loc = meta["num_heads"] // self._mp
        nkv_loc = meta["num_kv_heads"] // self._mp
        gather_stacked = self._gather_stacked

        def body(state, stacked, pool, tables, positions, toks, seeds,
                 counts, kv_scales, chunk_bids=None, chunk_kv=None):
            # embed/head come from the traced state (cheap gathers); the
            # stacked layer weights arrive prebuilt via `stacked`, so the
            # plan's own build_fused_params output is unused and XLA
            # dead-codes the per-step restacking away
            stacked = gather_stacked(stacked)
            plan_t = model.fused_decode_plan(state)
            blocks = plan_t.get("blocks")
            if int8 and blocks is not None:
                blocks = dict(blocks, cache_wbytes=1)
            # embed, the fused step and head name their own parts
            x = plan_t["embed"](toks, positions)
            with part("attn_in"):
                cos = jnp.take(cos_tab, positions, axis=0)
                sin = jnp.take(sin_tab, positions, axis=0)
            x, pool = fused_paged_tick_step(
                x, stacked, pool, tables, positions, cos, sin,
                num_heads=nh_loc,
                num_kv_heads=nkv_loc, eps=meta["eps"],
                rope_base=meta["rope_base"], arch=arch, blocks=blocks,
                kv_scales=kv_scales if int8 else None,
                chunk_bids=chunk_bids, chunk_kv=chunk_kv,
                mp_axis=mp_axis)
            logits = plan_t["head"](x)
            with part("sample"):
                keys = _row_keys(seeds)
                ki = jax.vmap(jax.random.fold_in)(keys, counts)
                nxt = _sample_logits(logits, ki, temperature, top_k, top_p)
                # advance the per-slot state in-program so event-free
                # steps re-dispatch with NO host->device uploads. A row
                # whose table row is scratch (idle: released, or still
                # prefilling) stays where the upload put it, at 0, so
                # the kernel's walk (`ops.fused_decode.paged_walk`) has
                # no pair for it; the clamp binds on no active row (its
                # position is bounded by its admission-checked worst
                # case)
                pos2 = jnp.where(tables[:, 0] == SCRATCH_BLOCK, positions,
                                 jnp.minimum(positions + 1, pos_cap))
                return nxt, pool, pos2, counts + 1

        return body

    def _build_step_fn(self):
        body = self._decode_body()

        def impl(state, stacked, pool, tables, positions, toks, seeds,
                 counts, kv_scales):
            return body(state, stacked, pool, tables, positions, toks,
                        seeds, counts, kv_scales)

        # donate the pool: the reference path batches every layer's
        # append into ONE scatter; on TPU the Pallas kernel aliases the
        # pool and donation skips the defensive copy (per SHARD under
        # mp — the donation_report pin covers the sharded tick too)
        from jax.sharding import PartitionSpec as P
        lay = self.layout
        pspec = lay.pool_spec() if lay is not None else None
        in_specs = (P(), self._stacked_specs or P(), pspec,
                    P(), P(), P(), P(), P(),
                    lay.kv_scales_spec() if lay is not None else None)
        out_specs = (P(), pspec, P(), P())
        jitted = self._wrap_program("step", impl, in_specs, out_specs,
                                    donate_argnums=(2,))
        return _program_handle(jitted,
                               lambda: (self._state, self._stacked))

    # ------------------------------------------------- speculative decode
    def _prop_zero(self, K: int):
        """The (proposals, nprop) reset pair for tail width ``K`` —
        immutable device constants built once per width, so a dirty
        tick re-arms the proposer without compiling a zeros program."""
        z = self._prop_zeros.get(K)
        if z is None:
            z = (jnp.zeros((self.max_slots, K), jnp.int32),
                 jnp.zeros((self.max_slots,), jnp.int32))
            if self.layout is not None:
                z = self.layout.place_replicated(z)
            self._prop_zeros[K] = z
        return z

    def _nprop_full(self, K: int):
        """The draft proposer's constant all-``K`` proposal count."""
        a = self._nprop_fulls.get(K)
        if a is None:
            a = jnp.full((self.max_slots,), K, jnp.int32)
            if self.layout is not None:
                a = self.layout.place_replicated(a)
            self._nprop_fulls[K] = a
        return a

    def _current_spec_k(self, active) -> int:
        """This tick's verify-tail width: the configured k, or with
        adaptive speculation the MAX per-slot k over the active slots
        (one batched verify program serves every slot; slots below the
        max are capped through the device-side ``cap`` vector). 0 means
        the tick runs the plain per-token decode dispatch — the whole
        point of adapting down on a low-acceptance mix. A k=0 recovery
        probe temporarily raises a parked slot's CAP above its k, so
        the width is the max over both."""
        if not self.speculate.adaptive:
            return self._spec_k
        return int(max(max(int(self._spec_k_slot[i]),
                           int(self._spec_cap[i])) for i in active))

    def _maybe_probe(self, active):
        """k=0 recovery probing (runs at the top of every decode tick
        of an adaptive engine): a slot parked at ``k_min=0`` proposes
        nothing, so its acceptance EWMA would never observe again and
        the slot could never climb back when the mix turns favorable.
        Every ``adapt_every`` consecutive parked ticks, raise each
        parked active slot's proposal cap to ONE for a two-tick probe
        window — the first (dirty) tick re-zeroes the carried ngram
        proposals and primes the device matcher, the second verifies a
        real one-token proposal and feeds the EWMA (the draft proposer
        observes on both). ``serving.spec_k_probes`` counts probed
        slots; the cap drops back when the window closes unless
        ``_adapt_spec_k`` climbed the slot's k in between."""
        if self._probe_window > 0:
            # window survives only while a probed slot is still active
            # (a retirement mid-window resets its cap via
            # _release_slot; without this the window could never close
            # once every probed slot is gone and ticks turn plain)
            if any(self._slots[i] is not None
                   for i in self._probe_slots):
                return
            self._probe_window = 0
            self._probe_slots = []
            return
        parked = [i for i in active if self._spec_k_slot[i] == 0]
        if not parked:
            self._spec_probe_wait = 0
            return
        self._spec_probe_wait += 1
        if self._spec_probe_wait < self.speculate.adapt_every:
            return
        self._spec_probe_wait = 0
        self._probe_window = 2
        self._probe_slots = list(parked)
        for i in parked:
            self._spec_cap[i] = 1
        self._dirty = True
        self.stats["spec_k_probes"] += len(parked)
        self._metrics.counter("serving.spec_k_probes").inc(len(parked))

    def _close_probe_window(self):
        """End-of-spec-tick bookkeeping for an open probe window: when
        it closes, parked slots drop back to cap 0 — unless the adapt
        step just climbed their k (the probe's success case)."""
        if self._probe_window <= 0:
            return
        self._probe_window -= 1
        if self._probe_window:
            return
        changed = False
        for i in self._probe_slots:
            if self._slots[i] is not None \
                    and int(self._spec_cap[i]) \
                    != int(self._spec_k_slot[i]):
                self._spec_cap[i] = int(self._spec_k_slot[i])
                changed = True
        self._probe_slots = []
        if changed:
            self._dirty = True

    def _adapt_spec_k(self, active, acc_np, nprop_np):
        """Per-slot adaptive-k update off the acceptance EWMA (runs at
        the end of each speculative tick). A k change is an EVENT: the
        cap vector re-uploads and the carried proposals re-zero at the
        (possibly) new tail width on the next tick — steady ticks with
        a stable k stay 0-H2D."""
        sc = self.speculate
        K_eff = self._spec_k_eff
        for i in active:
            if self._slots[i] is None:      # retired in this tick's commit
                continue
            neff = min(int(nprop_np[i]), int(self._spec_cap[i]), K_eff)
            if neff > 0:
                self._spec_acc_ewma[i].update(int(acc_np[i]) / neff)
        self._spec_adapt_tick += 1
        if self._spec_adapt_tick % sc.adapt_every:
            return
        changed = False
        for i in active:
            if self._slots[i] is None:
                continue
            ew = self._spec_acc_ewma[i].value
            if ew is None:
                continue
            k_i = int(self._spec_k_slot[i])
            if ew < sc.acceptance_floor and k_i > sc.k_min:
                k_i -= 1
            elif ew > sc.acceptance_ceiling and k_i < sc.k:
                k_i += 1
            else:
                continue
            self._spec_k_slot[i] = k_i
            self._spec_cap[i] = k_i
            changed = True
        if changed:
            self._dirty = True

    def _verify_body(self, K: int):
        """Trace-time VERIFY half shared by the speculative step
        program and the fused tick (see :meth:`_build_verify_fn` for
        the acceptance contract): an optional coscheduled prefill-chunk
        scatter folds into the same pool pass before the verify walk."""
        from paddle_tpu.inference import _row_keys, _sample_logits
        from paddle_tpu.ops.fused_decode import (fused_paged_verify_step,
                                                 paged_chunk_scatter)
        from paddle_tpu.serving.spec import ngram_propose

        meta, arch, int8 = self.meta, self.arch, self.kv_int8
        model, cos_tab, sin_tab = self.model, self._cos_tab, self._sin_tab
        temperature, top_k, top_p = (self.temperature, self.top_k,
                                     self.top_p)
        pos_cap = self.max_seq_len - 1
        K1 = K + 1
        ngram = self.speculate.proposer == "ngram"
        nmax = self.speculate.ngram_max
        nmin = self.speculate.ngram_min
        mp_axis = self._mp_axis
        nh_loc = meta["num_heads"] // self._mp
        nkv_loc = meta["num_kv_heads"] // self._mp
        gather_stacked = self._gather_stacked

        def body(state, stacked, pool, tables, positions, toks, seeds,
                 counts, kv_scales, proposals, nprop, cap, history=None,
                 chunk_bids=None, chunk_kv=None):
            stacked = gather_stacked(stacked)
            if chunk_bids is not None:
                if mp_axis is not None \
                        and chunk_kv.shape[-1] != pool.shape[-1]:
                    # the chunk half hands over CANONICAL-width payload
                    # (the replicated full-model forward); keep this
                    # shard's own [k_s|v_s] lanes before the scatter
                    chunk_kv = mp_local_kv_lastdim(chunk_kv, mp_axis)
                with part("attn"):
                    pool = paged_chunk_scatter(pool, chunk_bids, chunk_kv)
            plan_t = model.fused_decode_plan(state)
            blocks = plan_t.get("blocks")
            if int8 and blocks is not None:
                blocks = dict(blocks, cache_wbytes=1)
            tail = jnp.concatenate([toks[:, None], proposals], axis=1)
            xs, coss, sins = [], [], []
            for j in range(K1):
                # per-token embed/rope rows, shaped exactly like the
                # plain step's (the clamp binds only on over-speculation
                # past a retiring slot's cap — garbage rows)
                pj = jnp.minimum(positions + j, pos_cap)
                xs.append(plan_t["embed"](tail[:, j], pj))
                with part("attn_in"):
                    coss.append(jnp.take(cos_tab, pj, axis=0))
                    sins.append(jnp.take(sin_tab, pj, axis=0))
            with part("embed"):
                x = jnp.stack(xs, axis=1)                 # (b, K1, h)
            with part("attn_in"):
                cos, sin = jnp.stack(coss, axis=1), jnp.stack(sins, axis=1)
            x, pool = fused_paged_verify_step(
                x, stacked, pool, tables, positions, cos, sin,
                num_heads=nh_loc,
                num_kv_heads=nkv_loc, eps=meta["eps"],
                rope_base=meta["rope_base"], arch=arch, blocks=blocks,
                kv_scales=kv_scales if int8 else None, mp_axis=mp_axis)
            keys = _row_keys(seeds)
            gs = []
            for j in range(K1):
                logits = plan_t["head"](x[:, j])
                with part("sample"):
                    # the exact key the non-speculative engine folds for
                    # token count+j — sample-and-match acceptance is
                    # what makes speculation bit-invisible
                    ki = jax.vmap(jax.random.fold_in)(keys, counts + j)
                    gs.append(_sample_logits(logits, ki, temperature, top_k,
                                             top_p))
            with part("sample"):
                g = jnp.stack(gs, axis=1)                 # (b, K1)
                # per-slot proposal cap: the adaptive-k vector (full k when
                # adaptivity is off — the clamp is then a no-op)
                nprop_eff = jnp.minimum(jnp.minimum(nprop, cap), K)
                match = (proposals == g[:, :K]) \
                    & (jnp.arange(K)[None] < nprop_eff[:, None])
                acc = jnp.cumprod(match.astype(jnp.int32),
                                  axis=1).sum(axis=1)         # (b,)
                tok2 = jnp.take_along_axis(g, acc[:, None], axis=1)[:, 0]
                pos2 = jnp.minimum(positions + acc + 1, pos_cap)
                counts2 = counts + acc + 1
                if not ngram:
                    return g, acc, pool, pos2, tok2, counts2
                # committed-token history: the tail lands at its absolute
                # indices, then the corrected/bonus token at pos2 — writes
                # past the accepted prefix are stale and sit beyond the
                # committed length, exactly like rejected KV
                rows = jnp.arange(tail.shape[0])
                idxm = jnp.minimum(
                    positions[:, None] + jnp.arange(K1)[None], pos_cap)
                hist2 = history.at[rows[:, None], idxm].set(tail)
                hist2 = hist2.at[rows, pos2].set(tok2)
                prop2, nprop2 = ngram_propose(hist2, pos2 + 1, K, nmax, nmin)
                return (g, acc, pool, pos2, tok2, counts2, hist2, prop2,
                        jnp.minimum(nprop2, cap))

        return body

    def _build_verify_fn(self, K: int):
        """ONE program per speculative tick: embed the K+1-token tail
        (last sampled token + K proposals) per slot, score it through
        ``fused_paged_verify_step`` (KV appended through the multi-token
        path), sample each position's TARGET token off the slot's own
        ``fold_in(seed, count + j)`` stream, and accept the longest
        proposal prefix that matches — token-exact, so committed tokens
        are bitwise the non-speculative engine's. Per-slot state
        (positions/counts/last token) advances on device, and for the
        n-gram proposer the committed-token history and the NEXT tick's
        proposals are produced in the same program — a steady
        speculative tick re-dispatches with zero H2D uploads."""
        body = self._verify_body(K)
        ngram = self.speculate.proposer == "ngram"

        def impl(state, stacked, pool, tables, positions, toks, seeds,
                 counts, kv_scales, proposals, nprop, cap, *hist):
            return body(state, stacked, pool, tables, positions, toks,
                        seeds, counts, kv_scales, proposals, nprop, cap,
                        hist[0] if ngram else None)

        # donate the history buffer alongside the pool: the ngram path
        # RMWs it every verify tick (hist2 = history.at[...].set) and
        # the caller rebinds self._dev_hist from the output, so the old
        # buffer is dead at dispatch — undonated it cost one full
        # (max_slots, max_seq_len) copy per speculative tick (the
        # donation lint rule's first catch; donation_report pins it)
        from jax.sharding import PartitionSpec as P
        lay = self.layout
        pspec = lay.pool_spec() if lay is not None else None
        in_specs = ((P(), self._stacked_specs or P(), pspec)
                    + (P(),) * 5
                    + (lay.kv_scales_spec() if lay is not None else None,)
                    + (P(),) * 3 + ((P(),) if ngram else ()))
        out_specs = [P()] * (9 if ngram else 6)
        out_specs[2] = pspec
        jitted = self._wrap_program(
            "verify", impl, in_specs, tuple(out_specs),
            donate_argnums=(2,) + ((12,) if ngram else ()))
        return _program_handle(jitted,
                               lambda: (self._state, self._stacked))

    def _build_draft_fn(self, K: int):
        """Draft-proposer round: ONE scanned program runs k+1 greedy
        draft decode steps over the draft's own paged pool (positions
        shared with the target — draft and target appends advance in
        lockstep). k+1 appends, not k: the step that appends the k-th
        proposal's KV is what keeps the draft gap-free when the whole
        proposal is accepted (the bonus token's predecessor must be in
        the draft cache before the next round). Returns the k proposals
        and the updated draft pool; proposals stay on device — the
        verify program reads them directly, the host pulls them with
        the accepted counts after verify."""
        from paddle_tpu.inference import _sample_logits
        from paddle_tpu.ops.fused_decode import fused_paged_decode_step

        dm = self.speculate.draft_model
        dmeta = self._draft_meta
        darch = self._draft_arch
        pos_cap = self.max_seq_len - 1
        cos_tab, sin_tab = self._draft_cos, self._draft_sin

        def impl(dstate, dstacked, dpool, dtables, positions, toks):
            plan_t = dm.fused_decode_plan(dstate)
            blocks = plan_t.get("blocks")

            # NOT named `step`: the tpu-lint callgraph resolves bare
            # names module-wide, and a lax.scan body called `step`
            # would mark ServingEngine.step as jit-reachable
            def draft_step(carry, _):
                tok, pool, pos = carry
                x = plan_t["embed"](tok, pos)
                with part("attn_in"):
                    cos = jnp.take(cos_tab, pos, axis=0)
                    sin = jnp.take(sin_tab, pos, axis=0)
                x, pool = fused_paged_decode_step(
                    x, dstacked, pool, dtables, pos, cos, sin,
                    num_heads=dmeta["num_heads"],
                    num_kv_heads=dmeta["num_kv_heads"],
                    eps=dmeta["eps"], rope_base=dmeta["rope_base"],
                    arch=darch, blocks=blocks, kv_scales=None)
                logits = plan_t["head"](x)
                with part("sample"):
                    # greedy proposals: acceptance is exact-match
                    # against the target's sample, so the draft's best
                    # guess is its own argmax — no draft RNG stream
                    nxt = _sample_logits(logits, None, 0.0, 0, 1.0)
                    return (nxt, pool, jnp.minimum(pos + 1, pos_cap)), nxt

            (_, pool, _), props = jax.lax.scan(
                draft_step, (toks, dpool, positions), None, length=K + 1)
            with part("sample"):
                return props[:K].T.astype(jnp.int32), pool

        # the draft runs fully REPLICATED under mp (every spec is P());
        # the shard_map wrap still matters — it pins the draft's inputs
        # and outputs to the mesh so a speculative tick never mixes
        # mesh-committed and single-device buffers
        from jax.sharding import PartitionSpec as P
        jitted = self._wrap_program("draft", impl, (P(),) * 6, (P(), P()),
                                    donate_argnums=(2,))
        return _program_handle(
            jitted, lambda: (self._draft_state, self._draft_stacked))

    def _draft_prefill_fn(self, s_pad):
        """Draft prefill program (keyed by padded feed length, like the
        target's prefill buckets): forward the feed through the draft
        model and scatter its KV into the slot's draft pages. No
        sampling, no calibration (the draft pool is always bf16) — the
        draft is a proposer, its logits only matter during rounds.
        Returns ``(fn, cached)``."""
        from paddle_tpu.nn.layer import functional_call

        key = ("draft_prefill", s_pad)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn, True
        dm = self.speculate.draft_model
        BT = self.block_tokens
        nb = s_pad // BT
        Ld = self._draft_layers
        dkv = self._draft_dkv

        def impl(dstate, pool, ids, new_bids):
            cache = dm.init_cache(1, s_pad, dtype=jnp.bfloat16)
            _, cache = functional_call(dm, dstate, ids, cache=cache,
                                       start_pos=0)
            with part("attn"):          # the cache write
                kv_flat = jnp.stack([jnp.concatenate(
                    [c["k"].reshape(1, s_pad, dkv),
                     c["v"].reshape(1, s_pad, dkv)], axis=-1)
                    for c in cache])         # (Ld, 1, s_pad, 2dkv)
                blk = kv_flat.reshape(Ld, 1, nb, BT, 2 * dkv)
                return pool.at[:, new_bids].set(blk.astype(pool.dtype))

        from jax.sharding import PartitionSpec as P
        jitted = self._wrap_program("draft_prefill", impl, (P(),) * 4,
                                    P(), donate_argnums=(1,))
        fn = _program_handle(jitted, lambda: (self._draft_state,))
        self._jit_cache[key] = fn
        return fn, False

    def _run_draft_prefill(self, slot_idx: int, s: "_Slot"):
        """Fill the draft's KV pages for a freshly adopted slot (called
        from :meth:`_adopt_slot` — the one join path, so chunked and
        monolithic admissions both land here). The draft prefill is
        monolithic even on chunked engines: the draft is small by
        contract, so one program over the whole feed doesn't move the
        chunked TPOT bound the way a target prefill would."""
        # the draft rides the FULL committed context (prompt + replayed
        # resume tokens): its KV is advisory — proposals only, the
        # target's sample-match acceptance decides tokens — so the
        # batched prefill recompute is fine here in a way it is not
        # for the target's resumed KV (see _replay_resume)
        # tpu-lint: allow(host-sync): host token-list concat
        feed = (s.feed if not s.resume else np.concatenate(
            [s.feed, np.asarray(s.resume[:-1], np.int32)]))
        P = len(feed)
        BT = self.block_tokens
        dn0 = -(-P // BT)
        fresh = self._draft_pool_blocks.alloc(dn0 - len(s.dblocks))
        self._draft_tables[slot_idx, len(s.dblocks):dn0] = fresh
        s.dblocks.extend(fresh)
        ids = np.zeros((1, dn0 * BT), np.int32)
        ids[0, :P] = feed
        fn, _cached = self._draft_prefill_fn(dn0 * BT)
        self.draft_kv_pool = fn(
            self.draft_kv_pool, self._up(ids),
            self._up(np.asarray([s.dblocks[:dn0]], np.int32)))

    def _ensure_blocks(self, slot_idx: int, horizon: int = 0):
        """Append positions [pos, pos+horizon] must resolve to allocated
        blocks; allocate lazily as a slot's sequence crosses block
        boundaries (admission already reserved the worst case, so this
        cannot exhaust the pool). ``horizon`` is the speculative append
        depth (k tail tokens beyond the base append); allocation never
        exceeds the slot's reservation — over-speculation past it lands
        in the scratch block by table construction."""
        s = self._slots[slot_idx]
        c = min((s.pos + horizon) // self.block_tokens,
                s.worst_blocks - 1)
        while s.ntab <= c:
            bid = self.pool.alloc(1)[0]
            s.blocks.append(bid)
            self._tables[slot_idx][s.ntab] = bid
            s.ntab += 1
            self._reserved -= 1
            self._dirty = True

    def _ensure_draft_blocks(self, slot_idx: int):
        """Draft-proposer twin of :meth:`_ensure_blocks`: the draft
        appends k+1 tokens per tick at the target's positions, against
        its own worst-case-sized pool (allocation cannot fail)."""
        s = self._slots[slot_idx]
        c = min((s.pos + self._spec_k) // self.block_tokens,
                self.max_blocks_per_slot - 1)
        while len(s.dblocks) <= c:
            bid = self._draft_pool_blocks.alloc(1)[0]
            self._draft_tables[slot_idx][len(s.dblocks)] = bid
            s.dblocks.append(bid)
            self._dirty = True

    def _retire(self, slot_idx: int, finish: str):
        from paddle_tpu import observability as obs

        s = self._slots[slot_idx]
        now = time.perf_counter()
        self._release_slot(slot_idx)

        # a slot swept mid-prefill (chunked engines: deadline expiry
        # before its last chunk) has no sampled tokens yet — it retires
        # with what a preemption would have preserved (the resume
        # tokens, for a request cut while re-prefilling)
        raw = s.tokens if not s.prefilling else (s.resume or [])
        # tpu-lint: allow(host-sync): generated tokens are a host list
        toks = np.asarray(raw, np.int32)
        eos = self.eos_token_id
        if eos is not None and (toks == int(eos)).any():
            gen_len = int((toks == int(eos)).argmax())
        else:
            gen_len = len(toks)
        if s.t_first is not None:
            ttft = s.t_first - s.req._t_submit
        elif s.req._t_first is not None and s.req._t_submit is not None:
            # preempted-then-resumed, cut mid-re-prefill: TTFT is still
            # the ORIGINAL first token (same rule as _shed_queued)
            ttft = s.req._t_first - s.req._t_submit
        else:
            ttft = None
        tpot = ((now - s.t_first) / (s.count - 1) if s.count > 1 else None)
        # tpu-lint: allow(journal-coverage): THE engine finish site —
        # the Router journals "finish" when it collects this result
        # from step(); single-engine durability is the snapshot, which
        # serializes results
        res = RequestResult(s.req.request_id, s.req.prompt, toks, gen_len,
                            finish, ttft, tpot, s.prefix_hit_blocks,
                            trace_id=s.req.trace_id)
        self.results[s.req.request_id] = res
        self._finished_tick.append(s.req.request_id)
        self._tick_retired.append((s.req.request_id, finish))
        self.stats["requests_finished"] += 1
        r = self._metrics
        r.counter("serving.requests", finish=finish).inc()
        # the SLO percentile layer: per-request TTFT/TPOT land in
        # bounded-relative-error sketches (docs/OBSERVABILITY.md)
        if ttft is not None:
            r.sketch("serving.ttft_s").observe(ttft)
        if tpot is not None:
            r.sketch("serving.tpot_s").observe(tpot)
        if finish == "deadline":
            # postmortem seam: snapshot the flight ring once this tick's
            # event (the one recording this retirement) has been written
            self._dump_pending = "deadline_retirement"
        tr = obs.active_tracer()
        if tr is not None:
            # _t_submit is monotonic (perf_counter); span ts must share
            # the wall-clock base every other span uses, so map the
            # monotonic age onto time.time() at retirement
            tr.record("serving.request",
                      ts=time.time() - (now - s.req._t_submit),
                      dur_s=now - s.req._t_submit,
                      request_id=s.req.request_id,
                      trace_id=s.req.trace_id, finish=finish,
                      prompt_len=int(len(s.req.prompt)),
                      tokens=int(s.count), ttft_s=ttft, tpot_s=tpot,
                      prefix_hit_blocks=s.prefix_hit_blocks)
        return res

    def step(self) -> Dict:
        """One scheduler tick: admit what fits, retire expired deadlines,
        commit ONE fused paged decode step for every active slot, retire
        slots that finished. Returns a small status dict.

        The plain steady tick keeps one step program in flight
        (:meth:`_decode`; docs/SERVING.md §The tick's order): it
        dispatches the NEXT step program first and only then pulls and
        commits the tokens of the one dispatched a call earlier, so the
        device never waits for the way back from the chip. A tick after
        an event (a join, a leave, a lazy block) lands the program in
        flight before its mirror upload, dispatches the next one and
        returns with it in flight; every other kind of tick
        (speculative, fused chunk, anything parked, a deadline sweep, a
        preemption) lands it first and then runs as it always did. A
        row that left at the commit before is thrown away at the next
        (``stats["lookahead_discarded_tokens"]``). Either way a call
        that decodes commits exactly one plain step, and ``finished``
        is complete for result collection.

        Each tick is six phases (:class:`_Phase`), from its first line
        to its return — admit (scheduling + deadline sweep + block-table
        bookkeeping + the dirty-mirror upload, which ``step_upload_s``
        tells apart), wave-prefill, fused decode dispatch (program call;
        on async backends this is enqueue time), host sync (the
        sampled-token D2H pull of the oldest program in flight, where
        device wait surfaces), commit (the per-slot host loop and
        retirements behind the pull) and tail (this telemetry itself);
        a sync and a commit that land a program inside admit come off
        it, and a wave's joint pull is part of its prefill. Each is a
        ``serving.step.*`` span in
        any profile being taken, a cumulative ``stats["step_*_s"]``
        field, a ``serving.step_*_s`` histogram and (the tail apart) a
        field of this tick's flight-recorder event, so a TPOT spike or
        an idle device is attributable to a phase. A tick that dies
        mid-flight (injected fault, ``PoolExhausted``) closes its open
        spans, still records a partial event carrying the error,
        auto-dumps the ring, and re-raises.
        """
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        if self._t_out is not None:
            self._away_s += time.perf_counter() - self._t_out
        with jax.profiler.StepTraceAnnotation("serving.step",
                                              step_num=self._step_seq):
            self._tick_s = {}
            try:
                with self._phase("serving.step.admit"):
                    todo = self._schedule_tick()
                if todo is not None:
                    self._decode(*todo)
                with self._phase("serving.step.tail") as tail:
                    self._record_segments()
                    self._record_walk()
                    self._record_flight()
                    self._after_flight()
                    status = dict(active=self.active_slots,
                                  queued=len(self._queue),
                                  finished=self._finished_tick)
                # the one segment that cannot observe itself from inside
                self._metrics.histogram("serving.step_tail_s").observe(
                    tail.dur_s)
                return status
            except Exception as e:
                self._record_flight(err=f"{type(e).__name__}: {e}")
                self.flight.auto_dump(f"error:{type(e).__name__}")
                # the error dump supersedes any dump this tick queued
                # (e.g. a deadline retirement swept just before the
                # dispatch died) — without this, the NEXT successful
                # tick would emit a spurious "deadline_retirement" dump
                self._dump_pending = None
                raise
            finally:
                self._t_out = time.perf_counter()

    def _schedule_tick(self):
        """The admit phase: everything up to the dispatch call (the
        prefill programs inside it are their own phase and come off
        it). Returns :meth:`_decode`'s arguments, or ``None`` when the
        tick has nothing to dispatch."""
        from paddle_tpu.resilience import faults as _faults
        from paddle_tpu.resilience import record_event

        # shed events between ticks (submit-time displacement) surface
        # in THIS tick's finished list — step()['finished'] stays the
        # complete result-collection contract
        self._finished_tick = list(self._pending_finished)
        self._pending_finished = []
        self._tick_admitted = []
        self._tick_retired = []
        self._tick_prefills = []
        self._tick_chunks = []
        self._tick_preempted = []
        self._tick_resumed = []
        self._tick_swapped_out = []
        self._tick_swapped_in = []
        self._tick_spec = None
        self._tick_landed = False
        self._tick_lookahead = False
        self._tick_counters = {}
        # _tick_shed keeps accumulating across submit() calls between
        # ticks; _record_flight drains it into this tick's event

        # host-tier housekeeping BEFORE admission: land last tick's
        # swap-out gathers and stage predicted swap-ins (both gated on
        # parked work existing, so an offload-enabled engine with
        # nothing parked runs the exact steady tick — the 0-H2D pin in
        # tests/test_analysis.py covers offload=True idle ticks)
        if self._parked:
            self._land_all()    # the swap paths see the state they saw
            self._drain_swaps()
            self._offload_prefetch()
        # every _retire this tick (deadline sweep, instant finish on the
        # prefill sample inside _admit, decode finish) lands in
        # _finished_tick, so the returned `finished` list is complete
        # for result collection
        self._admit()
        now = time.perf_counter()
        expired = [(i, s) for i, s in enumerate(self._slots)
                   if s is not None and s.deadline_at is not None
                   and now > s.deadline_at]
        if expired:
            # a swept row retires with every token computed for it
            self._land_all()
            for i, s in expired:
                if self._slots[i] is s:
                    record_event("deadline_exceeded")
                    self._retire(i, "deadline")
        # chunked-prefill interleave (the ONE-PROGRAM tick): when a
        # chunk is due — every `decode_per_chunk` decode dispatches
        # while decode-ready slots exist, unconditionally otherwise —
        # the tick dispatches ONE fused program computing the front
        # group's next chunk AND every decode-ready slot's next token
        # (or verify tail), so the decode TPOT bound is one fused tick
        # and the pool/carry cross exactly one program boundary.
        grp = None
        if self.chunk_tokens is not None:
            front = self._front_prefill()
            if front is not None:
                decode_ready = any(s is not None and not s.prefilling
                                   for s in self._slots)
                if (not decode_ready
                        or self._decode_since_chunk
                        >= self.decode_per_chunk):
                    grp = front
        if grp is not None:
            self._land_all()    # a fused chunk tick keeps today's order
        spec = self.speculate is not None
        spec_tick = False
        K_eff = 0
        active = self._decode_ready()
        if active or grp is not None:
            if spec and active:
                if self.speculate.adaptive:
                    self._maybe_probe(active)
                self._spec_k_eff = K_eff = self._current_spec_k(active)
                spec_tick = K_eff > 0
                if K_eff != self._last_spec_k:
                    # a changed verify-tail width is an EVENT tick: the
                    # carried proposals re-zero at the new width and the
                    # mirrors (incl. the per-slot cap) re-upload
                    self._dirty = True
                    self._last_spec_k = K_eff
            if spec_tick:
                if K_eff not in self._verify_fns:
                    self._verify_fns[K_eff] = self._build_verify_fn(K_eff)
                    if self.speculate.proposer == "draft":
                        self._draft_fns[K_eff] = self._build_draft_fn(
                            K_eff)
            elif self._step_fn is None and grp is None:
                # non-speculative engines AND adaptive ticks whose every
                # active slot sits at k=0 ride the plain per-token
                # dispatch — the "stops paying the verify tail" case
                self._step_fn = self._build_step_fn()
            ahead = None
            if self._flight_q:
                # a plain tick that finds a program in flight: if nothing
                # has happened, the next one goes out before its pull
                ahead = self._rows_ahead()
            else:
                for i in active:
                    self._ensure_blocks(i, self._spec_k if spec else 0)
                    if self._draft_tables is not None:
                        self._ensure_draft_blocks(i)
            _faults.maybe_fire("decode.dispatch")
            if self._flight_q and self._dirty:
                # an event (a join, a leave at the last commit, a lazy
                # block): the full-mirror upload needs the host's newest
                # tokens, so the program in flight lands first
                self._land_all()
                ahead = None
                active = self._decode_ready()
                if not active:
                    return None
                for i in active:
                    self._ensure_blocks(i)
            # the fused tick program for this chunk bucket (cursor +
            # tail width); built before the steady/dirty decision so a
            # compile never counts as a steady dispatch
            tick_fn = None
            tick_warm = True
            g_start = g_kind = None
            if grp is not None:
                g_start, g_kind = grp.start, grp.kind
                tick_fn, tick_warm = self._tick_fn(
                    g_kind, g_start, grp.n, grp.C_pad, grp.chunk, grp.R,
                    K_eff if spec_tick else 0)
            # steady state = the warm program re-dispatches with NO
            # host->device upload: no join/leave/lazy-block event made
            # the mirrors dirty. This is the tick the "no steady-state
            # H2D" claim is about — and what sanitize mode guards.
            # Steady FUSED ticks (mid-prefill chunks of a covered
            # bucket) hold the same invariant: every chunk input is
            # device-resident from admission.
            steady = self._step_fn_warm and not self._dirty and tick_warm
            if self._dirty:
                with self._phase("serving.step.upload"):
                    self._upload_mirrors(spec, spec_tick)
            return (spec_tick, active, steady, grp, tick_fn, tick_warm,
                    g_start, g_kind, ahead)
        return None

    def _decode_ready(self) -> List[int]:
        """The slots of the decode batch. Prefilling slots stay OUT of
        it: their mirror rows idle against scratch until the last chunk
        adopts them."""
        return [i for i, s in enumerate(self._slots)
                if s is not None and not s.prefilling]

    def _rows_ahead(self):
        """The (slot index, slot) rows of the step program AFTER the
        newest one in flight, when that program may go out before the
        newest one's tokens are pulled; else ``None``. It may when the
        next tick is known to be the plain one and needs nothing the
        host has: no speculation (an adaptive engine's next tick may be
        a probe), nothing parked, no chunk group waiting, clean mirrors,
        a step program that has run. Positions and counts advance by one
        a step whatever the token is, so the host can tell before the
        pull which rows leave at their length (they are left out) and
        which append position needs a fresh block: a lazy block is an
        event like any other, and the program waits for the upload."""
        if (self.speculate is not None or self._dirty or self._parked
                or not self._ewma_step_warm
                or self._front_prefill() is not None):
            return None
        rows = [(i, s) for i, s in self._flight_q[-1].rows
                if s.count + 1 < s.req.max_new_tokens]
        for i, _ in rows:
            self._ensure_blocks(i, 1)
        return rows if rows and not self._dirty else None

    def _upload_mirrors(self, spec: bool, spec_tick: bool):
        """Re-upload the host mirrors a join or a leave made dirty."""
        self._dev = (self._up(self._tables),
                     self._up(self._positions),
                     self._up(self._toks),
                     self._up(self._seeds),
                     self._up(self._counts),
                     self._up_scales())
        if self._history is not None:
            self._dev_hist = self._up(self._history)
            # a join/leave tick drops the carried proposals — the
            # device matcher re-primes them at the end of this tick's
            # verify (one plain-decode tick per event, never a wrong
            # speculation)
            self._dev_prop = (self._prop_zero(self._spec_k_eff)
                              if spec_tick else None)
        if spec:
            self._dev_cap = self._up(self._spec_cap)
        if self._draft_tables is not None:
            self._draft_dev = self._up(self._draft_tables)
        self._dirty = False
        self.stats["upload_ticks"] += 1

    def _select_chunk_outs(self, grp, g_kind, chunk_outs):
        """Split the chunk half's outputs off a fused-tick result —
        the ONE place that re-implements ``_tick_fn``'s output
        ordering (carry2 | ctok [, lanes [, kvfull]], each present
        only when the bucket produces it). Mid int8 ticks rebind the
        group's resident carry in place; returns ``(ctok, lanes,
        kvfull)`` as device arrays still to fence/pull (``None`` where
        absent)."""
        ctok = lanes = kvfull = None
        if grp is not None:
            if g_kind == "last":
                ctok = chunk_outs[0]
                if self.kv_int8:
                    lanes = chunk_outs[1]
                    if self.prefix_cache is not None:
                        kvfull = chunk_outs[2]
            elif self.kv_int8:
                grp.carry = chunk_outs[0]   # the resident carry, RMW'd
        return ctok, lanes, kvfull

    def _fence_chunk_pulls(self, grp, g_kind, chunk_outs, head):
        """THE tick's one per-step D2H completion fence plus the chunk
        half's host-pull choreography, shared by the plain and
        speculative paths: select the chunk outputs off the fused
        result (:meth:`_select_chunk_outs`), pull ``head`` (the decode
        half's host-needed arrays; ``None`` entries skipped) and any
        chunk outputs in ONE batched ``device_get`` — not N round
        trips on the sync segment the TPOT bound measures — and reset
        the interleave budget (the fused tick IS this window's chunk;
        its own decode half counts toward the budget through the
        caller's increment — reset-then-increment, the two-program
        tick's order). A chunk-only mid tick has no host-needed
        output: fence on the carry (int8) or the pool (bf16 — the
        chunk scattered into it) instead, so the wall time the caller
        reads still measures completion, not dispatch (the chunk
        EWMAs/stall trigger would otherwise go blind). Returns
        ``(head_np, ctok_np, lanes_np, kvfull_np)``, ``head_np``
        mirroring ``head`` entry for entry."""
        ctok, lanes, kvfull = self._select_chunk_outs(grp, g_kind,
                                                      chunk_outs)
        pulls = [x for x in (*head, ctok, lanes, kvfull)
                 if x is not None]
        if pulls:
            # tpu-lint: allow(host-sync): the per-step D2H completion
            # fence (one batched device_get, not N round trips)
            pulled = list(jax.device_get(tuple(pulls)))
        else:
            fence = (grp.carry if grp is not None
                     and grp.carry is not None else self.kv_pool)
            # tpu-lint: allow(host-sync): the mid-chunk completion
            # fence
            fence.block_until_ready()
            pulled = []
        head_np = [pulled.pop(0) if h is not None else None
                   for h in head]
        ctok_np = pulled.pop(0) if ctok is not None else None
        lanes_np = pulled.pop(0) if lanes is not None else None
        kvfull_np = pulled.pop(0) if kvfull is not None else None
        if grp is not None:
            self._decode_since_chunk = 0
        return head_np, ctok_np, lanes_np, kvfull_np

    def _decode(self, spec_tick, active, steady, grp, tick_fn, tick_warm,
                g_start, g_kind, ahead):
        """The dispatch, sync and commit phases of a tick — the plain,
        the speculative and the fused chunk tick all pass through here,
        so all three carry the same phases, and :meth:`_launch` and
        :meth:`_land` are the one place each is written.

        A speculative or fused tick launches its program and lands it.
        The PLAIN tick keeps one step program in flight: it launches
        the step after the one in flight (``ahead``, its rows; or, with
        nothing in flight, this step and then the one after it) and only
        then lands the older one, so the device has a program queued
        while the pull travels back and the host commits. A tick that
        has landed a step already (an event tick: :meth:`_schedule_tick`
        or the wave prefill landed it before the upload) launches the
        next program and returns with it in flight. Every call lands at
        most one step of the plain kind."""
        plain = not spec_tick and grp is None
        held = plain and self._tick_landed
        with self._phase("serving.step.dispatch"):
            if not self._flight_q:
                self._launch(spec_tick, active, steady, grp, tick_fn,
                             tick_warm, g_start, g_kind)
                if plain and not held:
                    ahead = self._rows_ahead()
            if ahead:
                self._launch(False, [i for i, _ in ahead], True)
                left = len(self._flight_q[0].rows) - len(ahead)
                self._tick_lookahead = True
                self.stats["lookahead_ticks"] += 1
                self.stats["lookahead_discarded_tokens"] += left
                self._metrics.counter("serving.lookahead_ticks").inc()
        if not held:
            self._land()

    def _launch(self, spec_tick, active, steady, grp=None, tick_fn=None,
                tick_warm=True, g_start=None, g_kind=None):
        """Dispatch one decode program (plain step, verify, or fused
        chunk tick) for the slots ``active`` and queue it as in flight:
        THE one dispatch of a tick's decode."""
        dispatch = self._dispatch_spec if spec_tick else self._dispatch_plain
        t_launch = self._clock()
        head, chunk_outs = dispatch(active, steady, grp, tick_fn)
        self._flight_q.append(_InFlight(
            [(i, self._slots[i]) for i in active], head, chunk_outs,
            spec_tick, grp, g_start, g_kind, tick_warm, t_launch))

    def _land(self, extra=()):
        """Pull the tokens of the OLDEST program in flight and commit
        them: THE one sync and commit of a decode program, whichever
        phase of the tick it runs in (:class:`_Phase` takes its seconds
        off the phase around it). ``sync`` ends when the pull returns;
        everything the host does with the pulled tokens (per-slot
        commit, retirements, :meth:`_commit_chunk`) is ``commit``.
        ``extra`` device arrays ride the same ``device_get`` (the wave
        prefill's first tokens) and are returned as host arrays; that
        pull is timed as the prefill phase it runs in.

        A row whose slot has gone or changed hands since the launch (it
        left at the commit before this one, one token after the program
        went out) is thrown away: never appended, never counted in
        ``decode_tokens``. Its stray KV append went to the retired
        row's own last block, or to scratch, at a position past the
        row's last valid one, and in device order before anything a
        later owner of that block writes. The prefix cache shares only
        whole blocks of positions below the prompt's (or, on a
        preemption, the written) length, so no shared block holds it."""
        rec = self._flight_q[0]
        # a wave's joint pull stays a part of its prefill phase, which
        # has always held the wait for the wave's program; and what it
        # waited for says nothing of a step program's own seconds
        with (contextlib.nullcontext() if extra
              else self._phase("serving.step.sync")):
            head_np, ctok_np, lanes_np, kvfull_np = self._fence_chunk_pulls(
                rec.grp, rec.g_kind, rec.chunk_outs, [*rec.head, *extra])
            self._flight_q.popleft()
            now = self._clock()
            self._tick_program_s = (None if extra else
                                    now - max(rec.t_launch, self._t_landed))
            self._t_landed = now
            self._tick_landed = True
        with self._phase("serving.step.commit") as ph:
            n0 = len(self._tick_retired)
            active = [i for i, s in rec.rows if self._slots[i] is s]
            gone = len(rec.rows) - len(active)
            commit = self._commit_spec if rec.spec else self._commit_plain
            self._landed_counters = {}
            commit(active, head_np[:len(rec.head)])
            if rec.grp is not None:
                self._commit_chunk(rec.grp, rec.g_start, rec.g_kind,
                                   ctok_np, lanes_np, kvfull_np,
                                   rec.tick_warm)
            nxt = self._flight_q[0] if self._flight_q else None
            if nxt is not None and not any(self._slots[i] is s
                                           for i, s in nxt.rows):
                # every row of the program behind it has left: nothing
                # of it is worth a pull (the mirrors are dirty, so the
                # next program starts from an upload)
                gone += len(nxt.rows)
                self._flight_q.clear()
            self.stats["lookahead_discarded_tokens"] += gone
            # the landed program's own counters ride its commit span, so
            # that a trace can be read without the engine's stats
            ph.set(retired=len(self._tick_retired) - n0,
                   **self._landed_counters)
            pulled = head_np[len(rec.head):]
            # what was pulled and the record's device arrays are
            # released inside the phase, not between two of them
            rec = nxt = head_np = ctok_np = lanes_np = kvfull_np = None
        return pulled

    def _land_all(self):
        """Land whatever is in flight (between ticks: at most one
        program) before anything that reads or moves slot state."""
        while self._flight_q:
            self._land()

    def _settle(self):
        """:meth:`_land_all` from OUTSIDE a tick (``snapshot``,
        ``release_request``): what retires surfaces in the next
        ``step()``'s ``finished`` list, like a shed between ticks."""
        if self._flight_q:
            self._finished_tick = []
            self._land_all()
            self._pending_finished.extend(self._finished_tick)
            self._finished_tick = []

    def _clock(self) -> float:
        """The engine's clock: ``perf_counter`` less the seconds spent
        outside ``step()``."""
        return time.perf_counter() - self._away_s

    def _tick_decode_s(self) -> Optional[float]:
        """The seconds the program landed last took alone, as the EWMAs
        are fed it: from its launch, or from the pull of the program
        before it when it was launched ahead of that pull, to its own
        pull, on the engine's clock (the caller's time between two
        ``step()`` calls left out). For a tick that dispatches and
        pulls one program that is dispatch + sync; on a lookahead tick
        it is the tick's period; ``None`` when it came back with a
        wave's first tokens, behind the wave's program."""
        return self._tick_program_s

    def _dispatch_plain(self, active, steady, grp, tick_fn):
        """One plain (non-speculative) tick's dispatch: the fused tick
        program when a chunk is due (``grp``), else the per-token step
        program. Returns the arrays the host needs and the chunk
        half's outputs, for :meth:`_fence_chunk_pulls`."""
        if grp is not None:
            fn = tick_fn
            args = (self.kv_pool, *grp.args(), *self._dev)
        else:
            fn = self._step_fn
            args = (self.kv_pool, *self._dev)
        if self._sanitize and steady:
            from paddle_tpu.analysis import runtime as _sanitizer
            with _sanitizer.sanitize(
                    what="steady-state ServingEngine.step dispatch"):
                out = fn(*args)
            self.stats["sanitized_steps"] += 1
        else:
            out = fn(*args)
        d_nxt, self.kv_pool, d_pos, d_cnt = out[:4]
        # toks <- sampled ids; tables/seeds/scales are event-driven
        self._dev = (self._dev[0], d_pos, d_nxt, self._dev[3], d_cnt,
                     self._dev[5])
        return [d_nxt if active else None], out[4:]

    def _commit_plain(self, active, head_np):
        """A plain tick's host commit: every active slot's sampled
        token into its slot and mirrors, retiring what finished."""
        nxt = head_np[0]
        if active:
            for name, v in zip(self._step_counters, nxt[self.max_slots:]):
                v = int(v)
                self.stats[name] += v
                self._metrics.counter(f"serving.{name}").inc(v)
                self._landed_counters[name] = v
                self._tick_counters[name] = (
                    self._tick_counters.get(name, 0) + v)
            self._decode_since_chunk += 1
            self.stats["steps"] += 1
            self.stats["decode_tokens"] += len(active)
            # per-slot dispatch accounting: dispatches_per_token =
            # decode_slot_dispatches / decode_tokens, 1.0 without
            # speculation — the speculative perf gate's denominator
            self.stats["decode_slot_dispatches"] += len(active)
            self.stats["idle_slot_steps"] += self.max_slots - len(active)
            r = self._metrics
            r.counter("serving.steps").inc()
            r.counter("serving.tokens_generated").inc(len(active))
            r.counter("serving.idle_slot_steps").inc(
                self.max_slots - len(active))
            if self.speculate is not None:
                # adaptive tick with every active slot at k=0: surface
                # the degraded tail width (the verify path never runs
                # here, so _spec_decode's gauge set cannot)
                r.gauge("serving.spec_k_effective").set(0)
            for i in active:
                s = self._slots[i]
                tok = int(nxt[i])
                s.tokens.append(tok)
                s.tok = tok
                s.pos += 1
                s.count += 1
                if self._history is not None:
                    # an adaptive spec engine on a plain (k=0) tick
                    # keeps the HOST history current; the device twin
                    # refreshes on the next event tick's dirty upload
                    self._history[i, min(s.pos,
                                         self.max_seq_len - 1)] = tok
                self._positions[i] = s.pos
                self._toks[i] = tok
                self._counts[i] = s.count
                eos = self.eos_token_id
                if eos is not None and tok == int(eos):
                    self._retire(i, "eos")
                elif s.count >= s.req.max_new_tokens:
                    self._retire(i, "length")

    def _dispatch_spec(self, active, steady, grp, tick_fn):
        """One speculative tick's dispatch: the (optional) draft round
        plus ONE batched verify dispatch — the fused tick program when
        a chunk is due (``grp``), carrying the front group's chunk in
        the same program. Returns what :meth:`_dispatch_plain` does."""
        ngram = self._history is not None
        K_eff = self._spec_k_eff
        verify_fn = tick_fn if grp is not None else self._verify_fns[K_eff]
        draft_fn = self._draft_fns.get(K_eff)

        def dispatch():
            if draft_fn is not None:
                props, self.draft_kv_pool = draft_fn(
                    self.draft_kv_pool, self._draft_dev, self._dev[1],
                    self._dev[2])
                nprop = self._nprop_full(K_eff)
            else:
                props, nprop = self._dev_prop
            args = (self.kv_pool,
                    *(grp.args() if grp is not None else ()),
                    *self._dev, props, nprop, self._dev_cap)
            if ngram:
                args += (self._dev_hist,)
            return props, nprop, verify_fn(*args)

        if self._sanitize and steady:
            from paddle_tpu.analysis import runtime as _sanitizer
            with _sanitizer.sanitize(
                    what="steady-state speculative ServingEngine.step "
                         "dispatch"):
                props_dev, nprop_dev, out = dispatch()
            self.stats["sanitized_steps"] += 1
        else:
            props_dev, nprop_dev, out = dispatch()
        if ngram:
            (g, acc, self.kv_pool, d_pos, d_tok, d_cnt, hist2, prop2,
             nprop2) = out[:9]
            chunk_outs = out[9:]
            self._dev_hist = hist2
            self._dev_prop = (prop2, nprop2)
        else:
            g, acc, self.kv_pool, d_pos, d_tok, d_cnt = out[:6]
            chunk_outs = out[6:]
        self._dev = (self._dev[0], d_pos, d_tok, self._dev[3], d_cnt,
                     self._dev[5])
        return [g, acc, props_dev, nprop_dev], chunk_outs

    def _commit_spec(self, active, head_np):
        """A speculative tick's host commit: each slot's accepted
        prefix + corrected/bonus token. Mirrors stay in lockstep with
        the device state for surviving slots; a retirement inside the
        commit loop marks the mirrors dirty like any other leave
        event."""
        from paddle_tpu import observability as obs

        ngram = self._history is not None
        K_eff = self._spec_k_eff
        g_np, acc_np, prop_np, nprop_np = head_np
        self._decode_since_chunk += 1
        self.stats["steps"] += 1
        self.stats["spec_ticks"] += 1
        self.stats["decode_slot_dispatches"] += len(active)
        self.stats["idle_slot_steps"] += self.max_slots - len(active)
        r = self._metrics
        r.counter("serving.steps").inc()
        r.counter("serving.idle_slot_steps").inc(
            self.max_slots - len(active))
        pos_cap = self.max_seq_len - 1
        eos = self.eos_token_id
        committed_total = proposed_total = accepted_total = 0
        for i in active:
            s = self._slots[i]
            a = int(acc_np[i])
            # the EFFECTIVE proposal count — what the verify program
            # actually considered: min(raw nprop, per-slot adaptive
            # cap, tail width). Counting the raw draft nprop would
            # inflate spec_proposed/spec_rejected for capped slots and
            # bias the acceptance-rate telemetry low.
            proposed_total += min(int(nprop_np[i]),
                                  int(self._spec_cap[i]), K_eff)
            accepted_total += a
            r.histogram("serving.spec_accepted_len",
                        buckets=_SPEC_LEN_BUCKETS).observe(a)
            committed = ([int(t) for t in prop_np[i, :a]]
                         + [int(g_np[i, a])])
            for tok in committed:
                s.tokens.append(tok)
                s.tok = tok
                s.pos += 1
                s.count += 1
                committed_total += 1
                if ngram:
                    self._history[i, min(s.pos, pos_cap)] = tok
                self._positions[i] = s.pos
                self._toks[i] = tok
                self._counts[i] = s.count
                if eos is not None and tok == int(eos):
                    self._retire(i, "eos")
                    break
                if s.count >= s.req.max_new_tokens:
                    self._retire(i, "length")
                    break
        self.stats["decode_tokens"] += committed_total
        self.stats["spec_proposed"] += proposed_total
        self.stats["spec_accepted"] += accepted_total
        r.counter("serving.tokens_generated").inc(committed_total)
        r.counter("serving.spec_proposed").inc(proposed_total)
        r.counter("serving.spec_accepted").inc(accepted_total)
        r.counter("serving.spec_rejected").inc(
            proposed_total - accepted_total)
        if self.stats["spec_proposed"]:
            r.gauge("serving.spec_acceptance_rate").set(
                self.stats["spec_accepted"]
                / self.stats["spec_proposed"])
        r.gauge("serving.spec_k_effective").set(K_eff)
        self._ewma_spec_tokens.update(committed_total / len(active))
        self._tick_spec = (proposed_total, accepted_total)
        if self.speculate.adaptive:
            self._adapt_spec_k(active, acc_np, nprop_np)
            self._close_probe_window()
        tr = obs.active_tracer()
        if tr is not None:
            dur = self._tick_decode_s()
            tr.record("serving.spec_verify", ts=time.time() - dur,
                      dur_s=dur, slots=len(active),
                      trace_ids=[self._slots[i].req.trace_id
                                 for i in active
                                 if self._slots[i] is not None],
                      proposed=proposed_total, accepted=accepted_total,
                      committed=committed_total)

    def _after_flight(self):
        """Post-event tail of a tick: flush any queued flight dump and
        refresh the gauges."""
        if self._dump_pending is not None:
            self.flight.auto_dump(self._dump_pending)
            self._dump_pending = None
        self._update_gauges()

    def _record_segments(self):
        """Step-segment telemetry: this tick's :class:`_Phase` times
        into the registry histograms (``stats`` has them already).
        admit is observed every tick; prefill only on ticks that ran a
        wave, dispatch/sync/commit only on ticks that ran the phase (an
        event tick that lands a step before its upload and leaves the
        next program in flight has a sync and a commit inside admit and
        a dispatch after it) — so each histogram is the distribution of
        the segment when it actually happened, not diluted by
        structural zeros."""
        t = self._tick_s
        r = self._metrics
        r.histogram("serving.step_admit_s").observe(t["step_admit_s"])
        if self._tick_prefills:
            r.histogram("serving.step_prefill_s").observe(
                t["step_prefill_s"])
        for key in ("step_dispatch_s", "step_sync_s", "step_commit_s"):
            if key in t:
                r.histogram(f"serving.{key}").observe(t[key])
        if "step_dispatch_s" in t:
            self._step_fn_warm = True
        if self._tick_landed:
            # capacity-estimator feed: the same decode-step cost the
            # histograms just observed (shed_infeasible prices deadlines
            # against this EWMA) — except fused CHUNK ticks, whose wall
            # is chunk-dominated (the chunk EWMAs in _commit_chunk own
            # those; feeding them here would inflate the decode-step
            # estimate and over-shed), and except the plain program's
            # FIRST dispatch, whose trace+compile would poison the
            # estimate for dozens of steps and shed feasible deadlines
            # right after startup. The two warm flags are distinct on
            # purpose: _step_fn_warm (the steady/sanitize gate) flips
            # on ANY first dispatch, including a fused chunk tick —
            # the plain step program may not have compiled yet. A tick
            # that launched a program and left it in flight feeds
            # nothing: its seconds are fed by the tick that lands it.
            if not self._tick_chunks and self._tick_program_s is not None:
                if self._ewma_step_warm:
                    self._ewma_step.update(self._tick_decode_s())
                else:
                    self._ewma_step_warm = True

    def _record_walk(self):
        """The paged decode kernel's walk, counted on a tick that landed
        a plain or fused-chunk step: the length of the list that
        ``ops.fused_decode.paged_walk`` builds (``paged_walk_blocks``) from
        the host's positions as the tick leaves them, which are what the
        next step program reads (one a step, whichever of two
        neighbouring steps it is). A verify step runs the same kernel and
        the same walk, but its program advances EVERY row's position
        (``pos2 = min(positions + acc + 1, cap)``, idle rows included;
        only `_decode_body` leaves a scratch row where it is), so between
        uploads the device walks idle rows that the host's mirror holds
        at 0: the mirror is not what the next verify step reads, and a
        speculative tick is not counted. An engine with its own step
        counts where its plan declares the walk (``decode_walk``, the
        ``mla_moe`` plan): from the host's positions and tables, with the
        plan's own count of the list its kernel walks. That walk takes
        only rows whose table is not scratch, so an idle row, which the
        own step advances, counts nothing; other own steps do not
        count."""
        if not self._tick_landed or self._tick_spec is not None:
            return
        if self._own_step:
            if self._decode_walk is not None:
                walked, dense, rows = self._decode_walk(
                    self._positions, self._tables, self.block_tokens)
                self.stats["kv_blocks_walked"] += walked
                self.stats["kv_blocks_dense"] += dense
                self.stats["kv_rows_walked"] += rows
            return
        from paddle_tpu.ops.fused_decode import paged_walk_blocks
        _, walked, dense = paged_walk_blocks(self._positions,
                                             self.block_tokens)
        self.stats["kv_blocks_walked"] += int(walked)
        self.stats["kv_blocks_dense"] += int(dense)

    def _record_flight(self, err=None):
        """One compact JSON-ready event per tick into the flight ring.
        The segment fields are this tick's :class:`_Phase` times;
        ``None`` for a phase the tick never reached. The tail writes
        this event, so it cannot be in it."""
        t = self._tick_s.get
        evt = {"step": self._step_seq, "ts": round(time.time(), 6),
               "ts_mono": round(time.perf_counter(), 6),
               "active": self.active_slots, "queued": len(self._queue),
               "blocks_used": self.pool.used_blocks,
               "blocks_reserved": self._reserved,
               "admitted": list(self._tick_admitted),
               "retired": [[rid, fin] for rid, fin in self._tick_retired],
               "preempted": list(self._tick_preempted),
               "resumed": list(self._tick_resumed),
               "swapped_out": list(self._tick_swapped_out),
               "swapped_in": list(self._tick_swapped_in),
               "host_blocks_used": (self.host_store.used_blocks
                                    if self.host_store is not None
                                    else None),
               "shed": [[rid, reason] for rid, reason in self._tick_shed],
               "prefills": [[R, s_pad, n]
                            for R, s_pad, n in self._tick_prefills],
               "chunk_tokens": self.chunk_tokens,
               "prefill_chunks": min(len(self._tick_chunks), 1),
               "chunk_rows": len(self._tick_chunks),
               "chunks": [[rid, st, nt]
                          for rid, st, nt in self._tick_chunks],
               "spec_k": (self._spec_k if self.speculate is not None
                          else None),
               "spec_proposed": (None if self._tick_spec is None
                                 else self._tick_spec[0]),
               "spec_accepted": (None if self._tick_spec is None
                                 else self._tick_spec[1]),
               "t_admit_s": _round6(t("step_admit_s")),
               "t_prefill_s": _round6(t("step_prefill_s", 0.0)),
               "t_dispatch_s": _round6(t("step_dispatch_s")),
               "t_sync_s": _round6(t("step_sync_s")),
               "t_commit_s": _round6(t("step_commit_s")),
               "lookahead": self._tick_lookahead,
               **self._tick_counters}
        if err is not None:
            evt["err"] = err
        self.flight.record(evt)
        self._tick_shed = []    # drained into this tick's event
        self._step_seq += 1

    def pop_result(self, request_id: int) -> RequestResult:
        """Remove and return a finished request's result. ``results``
        retains every finished request until collected — a long-running
        server must pop (or periodically clear) results or host memory
        grows with every request ever served."""
        return self.results.pop(request_id)

    def drain(self, max_steps: Optional[int] = None) -> Dict[int,
                                                             RequestResult]:
        """Step until every submitted request has finished (or
        ``max_steps`` elapsed). Returns ``self.results``."""
        steps = 0
        while not self.idle:
            # stall probe: a step that BEGINS with every slot free runs
            # _admit with the whole pool reclaimable (prefix cache
            # already squeezed via evict_free) and nothing in flight to
            # retire — if it still admits nothing, no future step can,
            # and looping would spin forever (e.g. an int8-pool request
            # whose worst case exceeds the whole pool — submit's
            # never-fits check is deliberately optimistic about prefix
            # sharing). A step that merely ENDS idle is not a stall: its
            # retirements feed the next step's admission.
            q0 = len(self._queue) if self.active_slots == 0 else -1
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
            if q0 > 0 and self.active_slots == 0 and len(self._queue) == q0:
                head = self._queue.peek()
                self.flight.auto_dump("pool_exhausted:drain_stall")
                raise PoolExhausted(
                    f"drain stalled: request {head.request_id} "
                    f"({len(head.prompt)}+{head.max_new_tokens} tokens) "
                    f"cannot be admitted even with an idle engine")
        return self.results

    def generate(self, prompts: Sequence, **req_kwargs) -> List[np.ndarray]:
        """Batch convenience: submit every prompt, drain, return the
        ``prompt+tokens`` id rows in submission order."""
        # tpu-lint: allow(host-sync): API boundary — prompts are host ids
        ids = [self.submit(Request(np.asarray(p).reshape(-1), **req_kwargs))
               for p in prompts]
        self.drain()
        return [self.results[i].ids for i in ids]

    def lowered_programs(self, *kinds) -> Dict[tuple, "jax.stages.Lowered"]:
        """The programs this engine has dispatched, as
        ``jax.stages.Lowered``, keyed by cache key — ``("step",)``,
        ``("verify", K)``, ``("draft", K)``, ``("prefill", ...)``,
        ``("tick", "mid" | "last", ...)``, ``("draft_prefill", s_pad)`` —
        and narrowed to the keys that start with one of ``kinds`` when
        any is given. Each is the engine's own jitted object lowered
        from the shapes, dtypes and shardings of its first call.
        Read-only: ``.as_text()`` shows which kernels a program holds,
        ``.compile()`` what it costs."""
        handles = {("step",): self._step_fn, **self._jit_cache}
        handles.update({("verify", k): f
                        for k, f in self._verify_fns.items()})
        handles.update({("draft", k): f for k, f in self._draft_fns.items()})
        return {key: f.jitted.lower(*f.ran) for key, f in handles.items()
                if f is not None and f.ran is not None
                and (not kinds or key[0] in kinds)}

    # ------------------------------------------------- lifecycle: close
    def close(self):
        """Release the engine's device and host memory: the KV pool and
        stacked-weight arrays, the device mirrors, the jitted programs,
        and the prefix cache's host copies. In-flight and queued
        requests are DROPPED — :meth:`save_snapshot` first if they must
        survive. Idempotent; a closed engine rejects ``submit``/``step``
        with ``RuntimeError``. Long-running benches and tests should
        close (or use the engine as a context manager) so back-to-back
        engines don't stack live KV pools."""
        if self._closed:
            return
        self._closed = True
        if self._flight_q:
            # its tokens are dropped with the requests; let it finish
            # before the buffers it writes are deleted
            try:
                # tpu-lint: allow(host-sync): close() is off the tick
                jax.block_until_ready(self.kv_pool)
            except Exception:   # noqa: BLE001 — best-effort release
                pass
            self._flight_q.clear()
        for a in (self.kv_pool, self._stacked, self.draft_kv_pool,
                  getattr(self, "_draft_stacked", None)):
            try:
                if a is not None:
                    jax.tree_util.tree_map(
                        lambda x: x.delete() if hasattr(x, "delete")
                        else None, a)
            except Exception:   # noqa: BLE001 — best-effort release
                pass
        self.kv_pool = None
        self._stacked = None
        self._dev = None
        self._step_fn = None
        self.draft_kv_pool = None
        self._draft_stacked = None
        self._draft_dev = None
        self._verify_fns = {}
        self._draft_fns = {}
        self._prop_zeros = {}
        self._nprop_fulls = {}
        self._dev_hist = None
        self._dev_prop = None
        self._dev_cap = None
        self._jit_cache.clear()
        self._swap_fns = {}
        self._parked = {}
        self._staged = {}
        if self.host_store is not None:
            self.host_store.clear()
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self._slots = [None] * self.max_slots
        self._queue = _PriorityQueue()
        self._prefill_fifo = []
        self._tables = self._positions = self._toks = None
        self._seeds = self._counts = self._kv_scales = None

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -------------------------------------- crash-recoverable snapshot
    def snapshot(self) -> Dict:
        """Serializable engine state (``paddle_tpu.engine_snapshot/v1``):
        the queue and every active slot as resumable requests (id,
        prompt, generated-so-far tokens, seed, priority, remaining
        deadline), finished results, the prefix-cache keys, and the
        constructor config + a model fingerprint. Token-exact by
        construction: a request's tokens and RNG seed are the COMPLETE
        decode state — :meth:`restore` re-prefills the prompt, replays
        the generated tokens through the decode program and continues
        the same ``fold_in(seed, count)`` stream, so KV never needs to
        survive the crash.

        Call between ``step()`` calls, or after a ``step()`` that died
        on a fault — the host-side scheduler state stays consistent
        across an aborted tick (the fault sites fire *before* queue
        pops / token appends). A step program in flight is landed
        first, so the snapshot holds every token the device has been
        asked for."""
        self._settle()
        now = time.perf_counter()

        def _req(req: Request, tokens, deadline_at=None):
            if deadline_at is not None:
                rem = max(deadline_at - now, 1e-9)
            elif req.deadline_s is not None and req._t_submit is not None:
                rem = max(req._t_submit + req.deadline_s - now, 1e-9)
            else:
                rem = req.deadline_s
            return {"request_id": req.request_id,
                    "prompt": [int(t) for t in req.prompt],
                    "max_new_tokens": req.max_new_tokens,
                    "seed": int(req.seed) if req.seed is not None else None,
                    "priority": req.priority, "seq": req._seq,
                    "trace_id": req.trace_id,
                    "deadline_remaining_s": rem,
                    "tokens": [int(t) for t in tokens]}

        # a slot still mid-prefill (chunked engines) has sampled no
        # tokens: serialize the resume state it was ADMITTED with (a
        # preempted request's generated-so-far tokens must survive a
        # crash that lands mid-re-prefill), plus the chunk cursor so a
        # postmortem can see how far its prefill got — restore
        # re-prefills from the tokens, so the cursor itself is
        # informational (KV never survives a crash by design)
        slots = []
        for s in self._slots:
            if s is None:
                continue
            d = _req(s.req,
                     (s.resume or []) if s.prefilling else s.tokens,
                     s.deadline_at)
            d["chunk_filled"] = int(s.filled) if s.prefilling else None
            slots.append(d)
        queue = [_req(r, r._resume_tokens or []) for r in self._queue]
        results = [{"request_id": res.request_id,
                    "prompt": [int(t) for t in res.prompt],
                    "tokens": [int(t) for t in res.tokens],
                    "gen_len": res.gen_len, "finish": res.finish,
                    "ttft_s": res.ttft_s, "tpot_s": res.tpot_s,
                    "trace_id": res.trace_id,
                    "prefix_hit_blocks": res.prefix_hit_blocks}
                   for res in self.results.values()]
        config = {"max_slots": self.max_slots,
                  "block_tokens": self.block_tokens,
                  "num_blocks": self.pool.num_blocks,
                  "max_seq_len": self.max_seq_len,
                  "cache_dtype": jnp.dtype(self.cache_dtype).name,
                  "temperature": self.temperature, "top_k": self.top_k,
                  "top_p": self.top_p,
                  "eos_token_id": self.eos_token_id, "seed": self.seed,
                  "prefix_caching": self.prefix_cache is not None,
                  "prefix_cache_blocks": (
                      self.prefix_cache.capacity
                      if self.prefix_cache is not None else 256),
                  "flight_capacity": self.flight.capacity,
                  "flight_dump_path": self.flight.auto_dump_path,
                  "max_queue": self.max_queue,
                  "shed_infeasible": self.shed_infeasible,
                  "chunk_tokens": self.chunk_tokens,
                  "decode_per_chunk": self.decode_per_chunk,
                  "chunk_autotune": self.chunk_autotune,
                  "slo_tpot_s": self.slo_tpot_s,
                  "speculate": (self.speculate.to_config()
                                if self.speculate is not None else None),
                  "offload": self.offload,
                  "host_pool_blocks": (self.host_store.capacity
                                       if self.host_store is not None
                                       else None),
                  "offload_prefetch": self.offload_prefetch,
                  "sanitize": self._sanitize_mode}
        fingerprint = {"arch": self.arch, "num_layers": self._num_layers,
                       "dkv": self._cache_lanes // 2}
        return {"schema": ENGINE_SNAPSHOT_SCHEMA, "ts": time.time(),
                "step_seq": self._step_seq, "config": config,
                "model": fingerprint, "slots": slots, "queue": queue,
                "results": results,
                "prefix_keys": (self.prefix_cache.keys()
                                if self.prefix_cache is not None else []),
                "seeds_issued": self._seeds_issued,
                "submit_seq": self._submit_seq}

    def save_snapshot(self, root: str) -> str:
        """Commit :meth:`snapshot` to disk through the PR 4 integrity
        path: ``<root>/step_<seq>/engine.json`` (atomic tmp+rename),
        then the ``<root>/integrity/step_<seq>.json`` manifest whose
        existence IS the commit marker — :meth:`restore` walks back
        past uncommitted or corrupt snapshots exactly like checkpoint
        resume does. Returns the step directory."""
        from paddle_tpu.resilience import faults as _faults
        from paddle_tpu.resilience import integrity as _integ

        fault = _faults.maybe_fire("serving.snapshot")
        snap = self.snapshot()
        if self._sanitize_roundtrip:
            # sanitize="roundtrip"/"all": verify the snapshot being
            # committed restores byte-identically (canonical form)
            # BEFORE trusting it — SnapshotDriftError beats silently
            # persisting a snapshot that loses state. The check builds
            # a full twin engine (second KV pool!); if it CANNOT run —
            # e.g. no allocator headroom on a crash path — commit
            # unverified with a warning rather than abort the very
            # snapshot meant to preserve state: only genuine drift is
            # worth refusing to persist.
            from paddle_tpu.analysis import runtime as _sanitizer
            try:
                _sanitizer.snapshot_roundtrip(self, snap=snap)
            except _sanitizer.SnapshotDriftError:
                raise
            except Exception:   # noqa: BLE001 — check unavailable
                logger.warning(
                    "snapshot roundtrip check could not run; "
                    "committing the snapshot UNVERIFIED", exc_info=True)
        step = snap["step_seq"]
        step_dir = os.path.join(root, f"step_{step}")
        os.makedirs(step_dir, exist_ok=True)
        path = os.path.join(step_dir, "engine.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        if fault is not None and fault.kind == "hang":
            # the TORN window, held open on demand: engine.json is
            # committed but the manifest (the commit marker) is not.
            # A SIGKILL landing here leaves exactly the half-commit
            # that load_snapshot's walk-back must skip — the
            # cross-process torn-snapshot test kills the worker inside
            # this sleep and pins the walk-back.
            time.sleep(float(fault.payload.get("seconds", 3600.0)))
        _integ.write_manifest(root, step, _integ.file_checksums(step_dir))
        self._metrics.counter("serving.snapshots").inc()
        return step_dir

    @staticmethod
    def load_snapshot(root: str) -> Dict:
        """Newest committed-and-intact snapshot under ``root``: walk the
        manifest steps newest-first, skip any whose files fail the
        size/crc check (``resilience.snapshot_corrupt_skipped``) — one
        torn snapshot write must not strand the restore."""
        from paddle_tpu.resilience import integrity as _integ
        from paddle_tpu.resilience import record_event

        for step in _integ.manifest_steps(root):
            manifest = _integ.read_manifest(root, step)
            if manifest is None:
                continue
            step_dir = os.path.join(root, f"step_{step}")
            ok, reason = _integ.verify_files(manifest, step_dir)
            if not ok:
                record_event("snapshot_corrupt_skipped")
                logger.warning("engine snapshot step %d failed "
                               "verification (%s); walking back",
                               step, reason)
                continue
            with open(os.path.join(step_dir, "engine.json")) as f:
                return json.load(f)
        raise FileNotFoundError(
            f"no committed intact engine snapshot under {root}")

    @classmethod
    def restore(cls, model, source, *, state: Optional[Dict] = None,
                **overrides) -> "ServingEngine":
        """Rebuild an engine from a snapshot (dict, or a
        :meth:`save_snapshot` root directory) and re-admit EVERY
        request — in-flight slots and queued work alike — through the
        token-exact resume path: zero loss across a crash. Finished
        results carry over. ``overrides`` replace constructor config
        (e.g. a new ``flight_dump_path``). Snapshots are MESH-FREE
        (host-canonical: KV never serializes, scales/tokens are
        host-side canonical forms), so ``mesh=``/``layout=`` overrides
        restore the same snapshot onto any mesh shape — including a
        single chip — byte-identically (tests/test_serving_mp.py)."""
        from paddle_tpu.resilience import record_event

        snap = (cls.load_snapshot(source) if isinstance(source, str)
                else source)
        if snap.get("schema") != ENGINE_SNAPSHOT_SCHEMA:
            raise RestoreError(
                "schema",
                f"not an engine snapshot: schema "
                f"{snap.get('schema')!r} != {ENGINE_SNAPSHOT_SCHEMA!r}")
        cfg = dict(snap["config"])
        cfg["cache_dtype"] = jnp.dtype(cfg["cache_dtype"])
        spec_cfg = cfg.get("speculate")
        if isinstance(spec_cfg, dict) and "speculate" not in overrides:
            if spec_cfg.get("proposer") == "draft":
                raise RestoreError(
                    "draft_model_missing",
                    "snapshot used the draft-model proposer; models "
                    "don't serialize — pass speculate=SpecConfig(..., "
                    "draft_model=...) as a restore override (or "
                    "speculate=None to restore without speculation)")
            cfg["speculate"] = SpecConfig(**spec_cfg)
        cfg.update(overrides)
        eng = cls(model, state=state, **cfg)
        fp = snap.get("model", {})
        if fp and (fp.get("arch") != eng.arch
                   or fp.get("num_layers") != eng._num_layers
                   or fp.get("dkv") != eng._cache_lanes // 2):
            eng.close()     # the mismatched engine must not leak its pool
            raise RestoreError(
                "model_fingerprint",
                f"model mismatch: snapshot was taken on "
                f"{fp}, restoring onto arch={eng.arch} "
                f"L={eng._num_layers} dkv={eng._cache_lanes // 2}")
        eng._seeds_issued = int(snap.get("seeds_issued", 0))
        eng._submit_seq = int(snap.get("submit_seq", 0))
        now = time.perf_counter()
        # in-flight slots first, then the queue — both were serialized
        # in scheduling order and keep their original seq, so the
        # restored queue pops in the order the crashed engine would have
        restored = []
        for rs in snap["slots"] + snap["queue"]:
            # tpu-lint: allow(host-sync): snapshot JSON is host data
            req = Request(np.asarray(rs["prompt"], np.int32),
                          rs["max_new_tokens"], seed=rs["seed"],
                          deadline_s=rs["deadline_remaining_s"],
                          priority=rs.get("priority", "normal"),
                          request_id=rs["request_id"],
                          trace_id=rs.get("trace_id"))
            req._seq = int(rs.get("seq", 0))
            eng._submit_seq = max(eng._submit_seq, req._seq + 1)
            req._t_submit = now     # remaining deadline re-anchors here
            req._resume_tokens = list(rs["tokens"]) or None
            eng._queue.push(req)
            restored.append(req.request_id)
        for rr in snap.get("results", []):
            # tpu-lint: allow(journal-coverage): reconstructs results a
            # terminal transition already produced (and, router-side,
            # already journaled) — not a new transition
            # tpu-lint: allow(host-sync): snapshot JSON is host data
            eng.results[rr["request_id"]] = RequestResult(
                rr["request_id"], np.asarray(rr["prompt"], np.int32),
                rr["tokens"], rr["gen_len"], rr["finish"], rr["ttft_s"],
                rr["tpot_s"], rr["prefix_hit_blocks"],
                trace_id=rr.get("trace_id"))
        eng._step_seq = int(snap.get("step_seq", 0)) + 1
        eng._metrics.counter("serving.restores").inc()
        record_event("engine_restored")
        eng.flight.mark("restore", restored=restored,
                        results_carried=len(snap.get("results", [])),
                        from_step_seq=snap.get("step_seq"))
        eng.flight.auto_dump("restore")
        eng._update_gauges()
        return eng
