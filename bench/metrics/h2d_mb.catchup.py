"""The reading of ``h2d_mb.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("h2d_mb.steady")
