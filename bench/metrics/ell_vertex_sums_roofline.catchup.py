"""The reading of ``ell_vertex_sums_roofline.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("ell_vertex_sums_roofline.steady")
