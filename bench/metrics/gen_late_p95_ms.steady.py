"""95th percentile of how late the load generator offered a tick (ms):
a starved generator must not read as a fast server."""

from bench.measures import percentile_ms


def read(view):
    return percentile_ms(view.late_s, 95)
