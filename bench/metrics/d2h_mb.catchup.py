"""The reading of ``d2h_mb.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("d2h_mb.steady")
