"""Layer ``data`` (``io/native.py`` behind ``PackedTokenDataset``)."""

import numpy as np


def wait_ms_p50(obs):
    """Median of the benchmark's clock around ``next(batches)``."""
    w = obs["data_waits"]
    return float(np.median(w)) * 1e3 if w else None
