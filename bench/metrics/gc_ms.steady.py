"""Mean per step of the engine/gc spans: generation-2 Python garbage
collections (ms).
"""

from bench.measures import stage_ms


def read(view):
    # a program without the stage never sets the key: leave the metric out
    if not any("gc" in s.stats.stage_s for s in view.steps):
        return None
    return stage_ms(view, "gc")
