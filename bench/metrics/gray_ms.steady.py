"""Mean per step of bank G-Ray: the engine/gray dispatch spans plus the
device waits after them (gray_wait, device_wait) (ms)."""

from bench.measures import stage_ms


def read(view):
    return stage_ms(view, "gray", "device_wait")
