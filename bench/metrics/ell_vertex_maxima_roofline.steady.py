"""ell_vertex_maxima: least time for its live work over its device time (%)."""

from bench.measures import roofline_percent


def read(view):
    return roofline_percent(view, "ell_vertex_maxima")
