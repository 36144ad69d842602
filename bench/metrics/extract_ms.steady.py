"""Mean per step of the engine/extract span: induced subgraph and its ELL
tile, built on the host (ms)."""

from bench.measures import stage_ms


def read(view):
    return stage_ms(view, "extract")
