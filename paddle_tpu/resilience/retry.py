"""Bounded retry with exponential backoff — the shared transient-error
policy.

Reference (SURVEY.md §5): the reference survives coordination-service
hiccups with NCCL timeouts + launcher-level relaunch; a single flaky
etcd RPC does not kill a 1000-host job. The TPU-native analog: every
control-plane call (coordination-service KV puts/gets, heartbeat store
ops) goes through `call_with_retry` with a small bounded budget, and
every retry lands on the `resilience.retries` counter so fleet health
is visible in the metrics exporters.

Deterministic by design: the backoff schedule is a pure function of the
policy — including the OPTIONAL jitter, which is seeded rather than
drawn from a PRNG stream. Jitter exists because N workers that all lose
the same peer at the same instant would otherwise retry in lockstep (a
retry storm, re-synchronized every backoff rung); folding the policy
``seed`` and the attempt index through a hash de-correlates the
schedules while keeping every schedule reproducible — tests can still
assert the exact sleep sequence for a fixed seed, and the injected
`sleep` argument makes the tests instant.
"""

import logging
import time
import zlib
from dataclasses import dataclass
from typing import Callable, Iterable, Optional, Tuple, Type

logger = logging.getLogger("paddle_tpu.resilience")

__all__ = [
    "RetryPolicy", "backoff_delays", "call_with_retry", "kv_op",
    "is_resource_exhausted", "is_timeout", "is_not_found",
    "remaining_deadline",
]


@dataclass(frozen=True)
class RetryPolicy:
    """max_attempts counts the FIRST try too: max_attempts=3 means one
    call plus at most two retries. Delay before retry k (1-based) is
    min(base_delay_s * backoff**(k-1), max_delay_s), then scaled by the
    deterministic jitter factor for (seed, k) when ``jitter > 0``:
    a value in [1 - jitter, 1 + jitter] derived from crc32(seed:k) —
    no PRNG state, so two policies with the same seed produce the SAME
    schedule and two workers with different seeds de-correlate."""

    max_attempts: int = 3
    base_delay_s: float = 0.05
    backoff: float = 2.0
    max_delay_s: float = 2.0
    jitter: float = 0.0
    seed: int = 0


def _jitter_factor(seed: int, attempt: int, jitter: float) -> float:
    """Deterministic scale in [1 - jitter, 1 + jitter] for retry
    `attempt` (1-based) under `seed` — the attempt index is folded into
    the hash so consecutive rungs of ONE schedule de-correlate too."""
    u = zlib.crc32(f"{int(seed)}:{int(attempt)}".encode()) / 0xFFFFFFFF
    return 1.0 + jitter * (2.0 * u - 1.0)


def backoff_delays(policy: RetryPolicy) -> Iterable[float]:
    """The (max_attempts - 1) sleep durations, in order (jittered when
    the policy asks — the cap applies BEFORE the jitter scale, so the
    spread survives saturation at max_delay_s)."""
    d = policy.base_delay_s
    for k in range(1, max(policy.max_attempts, 1)):
        delay = min(d, policy.max_delay_s)
        if policy.jitter:
            delay *= _jitter_factor(policy.seed, k, policy.jitter)
        yield max(delay, 0.0)
        d *= policy.backoff


def call_with_retry(fn: Callable, *, policy: Optional[RetryPolicy] = None,
                    retry_on: Tuple[Type[BaseException], ...] = (Exception,),
                    retry_if: Optional[Callable[[BaseException], bool]] = None,
                    describe: str = "op",
                    sleep: Callable[[float], None] = time.sleep):
    """Run `fn()`; on an exception matching `retry_on` (and `retry_if`,
    when given) sleep the next backoff delay and try again, up to
    `policy.max_attempts` total attempts. The final failure re-raises.

    Each retry increments ``resilience.retries{op=describe}`` in the
    default metrics registry and logs a warning — recovery events are
    telemetry, not silence."""
    policy = policy or RetryPolicy()
    delays = list(backoff_delays(policy))
    attempt = 0
    while True:
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 — retry loop by definition
            if retry_if is not None and not retry_if(e):
                raise
            if attempt >= len(delays):
                raise
            delay = delays[attempt]
            attempt += 1
            _count_retry(describe)
            logger.warning(
                "%s failed (%s: %s); retry %d/%d in %.3fs", describe,
                type(e).__name__, e, attempt, len(delays), delay)
            sleep(delay)


def _count_retry(describe: str):
    from paddle_tpu.observability import registry
    registry().counter("resilience.retries", op=describe).inc()


_DEFAULT_POLICY = RetryPolicy()


def kv_op(describe: str, fn: Callable, *,
          policy: Optional[RetryPolicy] = _DEFAULT_POLICY,
          retry_if: Optional[Callable[[BaseException], bool]] = None):
    """THE wrapper for coordination-service control-plane calls
    (heartbeat stores, collective kv exchange): the injectable ``kv.op``
    fault site fires inside every retried attempt, so an injected
    transient error exercises the same recovery a real one hits.
    ``policy=None`` disables the retry (the fault site still fires)."""
    from paddle_tpu.resilience import faults as _faults

    def attempt():
        _faults.maybe_fire("kv.op")
        return fn()

    if policy is None:
        return attempt()
    return call_with_retry(attempt, policy=policy, describe=describe,
                           retry_if=retry_if)


def remaining_deadline(deadline_s: Optional[float],
                       t_start: float) -> Optional[float]:
    """What is left of a per-request wall-clock budget started at
    `t_start` (time.perf_counter()); None passes through. The one
    remaining-budget rule for every decode degradation rung — retries
    inherit the REMAINING budget, never a fresh allowance."""
    if deadline_s is None:
        return None
    return max(deadline_s - (time.perf_counter() - t_start), 0.0)


# ---- error-class predicates (shared across the degradation ladders) --------
#
# jax surfaces device/runtime failures as XlaRuntimeError with the gRPC
# status-code NAME in the message; matching on the string lets the
# simulated faults match too.

def is_resource_exhausted(e: BaseException) -> bool:
    """Accelerator OOM (or the injected stand-in)."""
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "Resource exhausted" in s


def is_timeout(e: BaseException) -> bool:
    s = str(e).lower()
    return "deadline_exceeded" in s or "timed out" in s or "timeout" in s


def is_not_found(e: BaseException) -> bool:
    s = str(e)
    return "NOT_FOUND" in s or "not found" in s.lower()
