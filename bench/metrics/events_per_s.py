"""Events applied and delivered per second over the window's whole steps:
from the window's start, a step boundary, to the end of the last step that
ended inside it."""

from bench.measures import rate


def read(view):
    return rate(view)
