"""Layer ``engine`` (``serving/engine.py``): the host tick."""

import numpy as np

from harness.serve import SEGMENTS


def tick_ms_p50(obs):
    """Median of the benchmark's clock around ``eng.step()``, over the
    ticks that dispatched a decode step."""
    lo, hi = obs["host_span"]
    ms = [d * 1e3 for t, d, decoded in obs["ticks"] if decoded and lo <= t < hi]
    return float(np.median(ms)) if ms else None


def ttft_ms_p90(obs):
    """p90 of (first token on the host) - (instant the request was due),
    over the requests due in the host part of the window that finished:
    the generator's own queue, the engine's admission and the prefill."""
    lo, hi = obs["host_span"]
    ms = [(r["submit"] - r["due"] + r["result"].ttft_s) * 1e3
          for r in obs["requests"]
          if lo <= r["due"] < hi and r["result"] is not None
          and r["result"].ttft_s is not None]
    return float(np.percentile(ms, 90)) if ms else None


def host_share(obs):
    """Admit and dispatch (host work before the device is waited for)
    over the four segments ``engine.stats`` times."""
    s = obs["stats"]
    total = sum(s[k] for k in SEGMENTS)
    if total <= 0:
        return None
    return 100.0 * (s["step_admit_s"] + s["step_dispatch_s"]) / total


def batch_occupancy(obs):
    """Tokens decoded over (decode steps x slots)."""
    s = obs["stats"]
    if not s["steps"]:
        return None
    return 100.0 * s["decode_tokens"] / (s["steps"] * obs["max_slots"])


def pool_live_share(obs):
    """Pool blocks that hold a running request's keys and values, as the
    mean over the decode steps of the host part of the window
    (``serve.live_blocks``, from the benchmark's request records), over
    the blocks of the pool."""
    if obs["live_blocks_mean"] is None:
        return None
    return dict(value=100.0 * obs["live_blocks_mean"] / obs["pool_blocks"],
                live_blocks_mean=obs["live_blocks_mean"],
                pool_blocks=obs["pool_blocks"])
