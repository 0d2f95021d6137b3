"""Speculative-decoding proposers for the serving engine.

Decode is memory-bandwidth-bound: every serial decode step streams the
whole model once to produce ONE token per slot. Speculation trades k
cheap *proposed* tokens per slot for one batched *verify* pass through
the fused paged kernel (`ops.fused_decode.fused_paged_verify_step`: the
decode kernel, given a tail of k+1 tokens a slot instead of one),
committing however many proposals the engine's own sampling stream
agrees with — fewer serial dispatches per generated token, bit-identical
tokens (docs/SERVING.md §Speculative decoding).

Two proposers:

* **n-gram** (self-speculative, no extra model): per-slot suffix match
  over the committed tokens (prompt + generated) — the prompt-lookup /
  "assisted generation" trick. The matcher runs ON DEVICE inside the
  verify program over a carried token-history buffer, so a steady
  speculative tick performs zero host->device transfers (the PR 9
  sanitizer invariant). Best on repetitive mixes: extraction, code,
  chat with quoting.
* **draft model** (llama-tiny drafting for llama-medium): a small model
  rides the SAME paged serving machinery — its own block tables over
  its own bf16 pool, positions shared with the target (draft and target
  appends advance in lockstep) — and proposes greedily k tokens per
  tick in one scanned program.

Acceptance is TOKEN-EXACT, not distribution-level rejection sampling: a
proposal survives only if it equals the token the engine's own
per-request RNG stream (``fold_in(seed, count)``, PR 5) would have
sampled at that position from the verify logits. Greedy collapses to
longest exact-match-of-argmax prefix; sampled draws each position's
sample from its own leave-one-out fold of the request stream. Either
way the committed tokens are bitwise the ones the non-speculative
engine emits — the parity contract tests/test_serving_spec.py pins.

Telemetry: each speculative tick records a ``serving.spec_verify``
span (proposed/accepted/committed counts) carrying the ``trace_ids``
of every active slot — a verify tick is a shared event on N causal
request chains, and the timeline export fans it out to each
(docs/OBSERVABILITY.md §Request traces).
"""

import numbers
from typing import Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["SpecConfig", "PROPOSERS", "ngram_propose",
           "ngram_propose_host"]

#: supported proposer kinds
PROPOSERS = ("ngram", "draft")


class SpecConfig:
    """Speculative-decoding config for ``ServingEngine(speculate=...)``.

    ``k`` proposals are verified per slot per tick (one fused verify
    dispatch scores k+1 tail tokens). ``proposer="ngram"`` needs no
    extra model; ``proposer="draft"`` requires ``draft_model`` — a
    fused-decode-eligible small model (llama/gpt) sharing the target's
    tokenizer/vocab. ``ngram_max``/``ngram_min`` bound the suffix
    lengths the n-gram matcher tries (longest first).

    ``adaptive=True`` arms per-slot adaptive k (docs/SERVING.md
    §Speculative decoding): each slot carries an acceptance EWMA
    (accepted/proposed per verify tick); every ``adapt_every`` spec
    ticks a slot whose EWMA sits below ``acceptance_floor`` steps its
    k down one (toward ``k_min``) and one above ``acceptance_ceiling``
    steps it back up (toward ``k``). The tick's verify tail is sized
    by the MAX k over active slots, so a replica whose whole mix has
    low acceptance stops paying the k-token verify tail — with
    ``k_min=0`` it degrades all the way to the plain per-token decode
    dispatch. Committed tokens stay bit-identical at every k
    (acceptance is exact sample-match; shorter proposals just commit
    fewer per tick). A slot parked at ``k_min=0`` proposes nothing,
    so by itself its EWMA could never observe acceptance again; the
    engine therefore PROBES parked slots — every ``adapt_every``
    parked ticks their cap is raised to one proposal for a two-tick
    window (``serving.spec_k_probes``), letting the EWMA re-observe
    and the slot climb back when the mix turns favorable.

    Everything is validated HERE with plain ``ValueError``s — a bad k
    must not surface deep inside the scheduler.
    """

    __slots__ = ("k", "proposer", "ngram_max", "ngram_min",
                 "draft_model", "draft_state", "adaptive", "k_min",
                 "acceptance_floor", "acceptance_ceiling", "adapt_every",
                 "share_embeddings")

    def __init__(self, k: int = 4, proposer: str = "ngram",
                 ngram_max: int = 3, ngram_min: int = 1,
                 draft_model=None, draft_state: Optional[dict] = None,
                 adaptive: bool = False, k_min: int = 1,
                 acceptance_floor: float = 0.35,
                 acceptance_ceiling: float = 0.65,
                 adapt_every: int = 4,
                 share_embeddings: bool = True):
        if isinstance(k, bool) or not isinstance(k, numbers.Integral) \
                or k < 1:
            raise ValueError(f"speculate k must be an int >= 1, got {k!r}")
        self.k = int(k)
        self.adaptive = bool(adaptive)
        if isinstance(k_min, bool) or not isinstance(k_min, numbers.Integral) \
                or not 0 <= k_min <= k:
            raise ValueError(
                f"k_min must be an int in [0, k={k}], got {k_min!r}")
        self.k_min = int(k_min)
        for name, v in (("acceptance_floor", acceptance_floor),
                        ("acceptance_ceiling", acceptance_ceiling)):
            if not isinstance(v, numbers.Real) or isinstance(v, bool) \
                    or not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {v!r}")
        if acceptance_floor > acceptance_ceiling:
            raise ValueError(
                f"acceptance_floor {acceptance_floor} > "
                f"acceptance_ceiling {acceptance_ceiling} (the hysteresis "
                f"band would thrash k every tick)")
        self.acceptance_floor = float(acceptance_floor)
        self.acceptance_ceiling = float(acceptance_ceiling)
        if isinstance(adapt_every, bool) \
                or not isinstance(adapt_every, numbers.Integral) \
                or adapt_every < 1:
            raise ValueError(
                f"adapt_every must be an int >= 1, got {adapt_every!r}")
        self.adapt_every = int(adapt_every)
        if proposer not in PROPOSERS:
            raise ValueError(f"unknown proposer {proposer!r}; one of "
                             f"{PROPOSERS}")
        self.proposer = proposer
        for name, v in (("ngram_max", ngram_max), ("ngram_min", ngram_min)):
            if isinstance(v, bool) or not isinstance(v, numbers.Integral) \
                    or v < 1:
                raise ValueError(f"{name} must be an int >= 1, got {v!r}")
        if ngram_min > ngram_max:
            raise ValueError(f"ngram_min {ngram_min} > ngram_max "
                             f"{ngram_max}")
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)
        if proposer == "draft" and draft_model is None:
            raise ValueError(
                "proposer='draft' requires draft_model (a fused-decode-"
                "eligible small model)")
        self.draft_model = draft_model
        self.draft_state = draft_state
        # draft proposer: rebind the draft's embedding table to the
        # TARGET's array when the shapes/dtypes line up (same
        # vocab×hidden — and through tied_unembed the shared table is
        # the draft's unembedding too). One device buffer instead of
        # two; a draft with a different hidden keeps its own table,
        # silently. Bit-inert either way: equal arrays, shared or
        # copied, produce identical draft logits.
        self.share_embeddings = bool(share_embeddings)

    def to_config(self) -> dict:
        """JSON-serializable form for engine snapshots. The draft MODEL
        is not serializable — ``ServingEngine.restore`` demands it back
        as an override when the snapshot used the draft proposer."""
        return {"k": self.k, "proposer": self.proposer,
                "ngram_max": self.ngram_max, "ngram_min": self.ngram_min,
                "adaptive": self.adaptive, "k_min": self.k_min,
                "acceptance_floor": self.acceptance_floor,
                "acceptance_ceiling": self.acceptance_ceiling,
                "adapt_every": self.adapt_every,
                "share_embeddings": self.share_embeddings}


def ngram_propose(history, lengths, k: int, nmax: int, nmin: int):
    """Device-side n-gram proposal (prompt-lookup decoding), vectorized
    over slots — runs INSIDE the engine's verify program so a steady
    speculative tick stays 0-H2D.

    history (b, S) int32 — each row's committed tokens (prompt +
    generated) at indices ``[0, lengths[r])``; entries beyond are
    stale/garbage and never read. For the longest n in [nmin, nmax]
    whose length-n suffix of the committed sequence re-occurs ending
    strictly before the suffix itself, the MOST RECENT occurrence wins
    and the committed tokens that followed it become the proposal.

    Returns (proposals (b, k) int32, nprop (b,) int32) — rows with no
    match (or too-short histories) propose nothing (nprop 0, proposals
    zero-padded), which the verify pass treats as a plain decode step.
    """
    b, S = history.shape
    lengths = lengths.astype(jnp.int32)
    pos_i = jnp.arange(S, dtype=jnp.int32)[None]      # match END index i
    Lm1 = lengths[:, None] - 1                        # suffix end index
    best_idx = jnp.full((b,), -1, jnp.int32)
    best_n = jnp.zeros((b,), jnp.int32)
    for n in range(nmax, nmin - 1, -1):               # longest wins
        eq = jnp.ones((b, S), bool)
        for d in range(n):
            # history[i - d] == history[L-1 - d] — the rolled copy wraps
            # at the left edge; the pos_i >= d mask kills the wrap
            shifted = jnp.roll(history, d, axis=1)
            suf_d = jnp.take_along_axis(
                history, jnp.maximum(Lm1 - d, 0), axis=1)     # (b, 1)
            eq = eq & (shifted == suf_d) & (pos_i >= d)
        valid = eq & (pos_i >= n - 1) & (pos_i < Lm1) \
            & (Lm1 >= n)                              # suffix must exist
        idx = jnp.where(valid, pos_i, -1).max(axis=1).astype(jnp.int32)
        take = (idx >= 0) & (best_n == 0)
        best_idx = jnp.where(take, idx, best_idx)
        best_n = jnp.where(take, n, best_n)
    start = best_idx + 1
    gidx = jnp.clip(start[:, None] + jnp.arange(k, dtype=jnp.int32)[None],
                    0, S - 1)
    props = jnp.take_along_axis(history, gidx, axis=1)
    nprop = jnp.where(best_idx >= 0,
                      jnp.clip(lengths - start, 0, k), 0).astype(jnp.int32)
    props = jnp.where(jnp.arange(k)[None] < nprop[:, None], props, 0)
    return props.astype(jnp.int32), nprop


def ngram_propose_host(tokens, k: int, nmax: int, nmin: int):
    """Plain-python twin of :func:`ngram_propose` for one sequence —
    the readable specification the device matcher is tested against."""
    toks = [int(t) for t in tokens]
    L = len(toks)
    for n in range(nmax, nmin - 1, -1):
        if L - 1 < n:
            continue
        suffix = toks[L - n:]
        best = -1
        for i in range(n - 1, L - 1):                 # match END index
            if toks[i - n + 1:i + 1] == suffix:
                best = i                              # most recent wins
        if best >= 0:
            props = toks[best + 1:best + 1 + k]
            # tpu-lint: allow(host-sync): host python-list test twin
            return (np.asarray(props + [0] * (k - len(props)), np.int32),
                    len(props))
    return np.zeros(k, np.int32), 0
