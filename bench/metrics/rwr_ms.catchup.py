"""The reading of ``rwr_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("rwr_ms.steady")
