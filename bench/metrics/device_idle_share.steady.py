"""Share of the traced window in which no operation ran on the device (%)."""

from bench.measures import idle_percent


def read(view):
    return idle_percent(view)
