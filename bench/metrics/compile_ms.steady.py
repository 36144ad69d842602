"""Mean per step of the engine/compile spans: tracing, lowering, and
compiling or loading programs from the cache (ms).
"""

from bench.measures import stage_ms


def read(view):
    # a program without the stage never sets the key: leave the metric out
    if not any("compile" in s.stats.stage_s for s in view.steps):
        return None
    return stage_ms(view, "compile")
