"""The benchmark's yardstick: traffic, references, trace reduction, peaks.

Nothing here is imported by ``paddle_tpu``; the harness reaches the
program only through the entry points a user calls.
"""

import json
import sys


def log(**fields):
    """One ``bench: {...}`` diagnostic line on standard error."""
    print("bench: " + json.dumps(fields), file=sys.stderr, flush=True)
