"""Mean per step of the engine/apply span: COO update plus ELL refresh (ms)."""

from bench.measures import stage_ms


def read(view):
    return stage_ms(view, "apply")
