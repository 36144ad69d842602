"""Mean per step of the engine/pem span: recompute set from the Louvain
cut (ms)."""

from bench.measures import stage_ms


def read(view):
    return stage_ms(view, "pem")
