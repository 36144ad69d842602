"""Mean per step of the engine/extract/pull span: the device→host copies of
the graph arrays induced extraction reads (ms).
"""

from bench.measures import stage_ms


def read(view):
    # a program without the stage never sets the key: leave the metric out
    if not any("extract_pull" in s.stats.stage_s for s in view.steps):
        return None
    return stage_ms(view, "extract_pull")
