"""ell_vertex_sums: least time for its live work over its device time (%)."""

from bench.measures import roofline_percent


def read(view):
    return roofline_percent(view, "ell_vertex_sums")
