"""Device time of both Pallas ELL kernels per step, from the trace (ms)."""

from bench.measures import kernel_ms


def read(view):
    return kernel_ms(view)
