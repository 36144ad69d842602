"""Mean per step of the engine/merge span: host fold into the stores (ms)."""

from bench.measures import stage_ms


def read(view):
    return stage_ms(view, "merge")
