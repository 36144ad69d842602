"""Mean per step of the bytes the step copied from the device to the host,
each device array once (MB).
"""

import numpy as np


def read(view):
    # a program without the step counters has no ``counts``: left out
    per_step = [s.stats.counts["d2h_bytes"] for s in view.steps
                if "d2h_bytes" in getattr(s.stats, "counts", {})]
    if not per_step:
        return None
    return float(np.mean(per_step)) / 1e6
