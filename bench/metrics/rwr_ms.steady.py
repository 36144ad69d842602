"""Mean per step of the engine/rwr span: label-conditioned RWR (ms)."""

from bench.measures import stage_ms


def read(view):
    return stage_ms(view, "rwr")
