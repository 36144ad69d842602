"""95th percentile of the event-to-delta latency population (ms)."""

from bench.measures import percentile_ms


def read(view):
    return percentile_ms(view.e2e_s, 95)
