"""Median event-to-delta latency: nominal arrival to the end of the step
that delivered the event's deltas, over every event of the window (ms)."""

from bench.measures import percentile_ms


def read(view):
    return percentile_ms(view.e2e_s, 50)
