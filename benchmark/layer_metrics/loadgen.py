"""Layer ``loadgen``: is the generator itself on time?"""

import numpy as np


def late_ms_p99(obs):
    """99th percentile of (instant the generator's loop first saw the
    request as due) - (due), over the measured requests of the host
    part of the window. A starved generator is not a fast server."""
    lo, hi = obs["host_span"]
    late = [(r["seen"] - r["due"]) * 1e3 for r in obs["requests"]
            if lo <= r["due"] < hi]
    return float(np.percentile(late, 99)) if late else None
