"""Layer ``device``: what the chip itself reports."""


def idle_share(obs):
    """1 - (union of the device's op intervals) / (traced window)."""
    red = obs.get("trace")
    if red is None:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
