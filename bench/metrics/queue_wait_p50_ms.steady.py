"""Median wait of an event from its nominal arrival until it is packed
into a micro-batch (ingress + queue), every event of the window (ms)."""

from bench.measures import percentile_ms


def read(view):
    return percentile_ms(view.queue_wait_s, 50)
