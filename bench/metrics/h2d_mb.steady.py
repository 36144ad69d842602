"""Mean per step of the bytes the step uploaded from the host to the device
(MB).
"""

import numpy as np


def read(view):
    # a program without the step counters has no ``counts``: left out
    per_step = [s.stats.counts["h2d_bytes"] for s in view.steps
                if "h2d_bytes" in getattr(s.stats, "counts", {})]
    if not per_step:
        return None
    return float(np.mean(per_step)) / 1e6
