"""Layer ``programs``: what the engine compiled and dispatched."""

from harness.serve import SEGMENTS


def prefill_share(obs):
    """Wave-prefill seconds over the four segments ``engine.stats``
    times."""
    total = sum(obs["stats"][k] for k in SEGMENTS)
    if total <= 0:
        return None
    return 100.0 * obs["stats"]["step_prefill_s"] / total


def compiles_in_window(obs):
    """Backend compiles between the window's start and its end (the
    benchmark's CompileClock). Must be 0: a compile here is set-up that
    warm-up missed."""
    return float(obs["compiles_in_window"])
