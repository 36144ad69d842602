"""Mean per step of the engine/apply/ell_refresh span: the ELL mirror
refresh inside apply (ms).
"""

from bench.measures import stage_ms


def read(view):
    # a program without the stage never sets the key: leave the metric out
    if not any("ell_refresh" in s.stats.stage_s for s in view.steps):
        return None
    return stage_ms(view, "ell_refresh")
