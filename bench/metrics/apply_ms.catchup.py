"""The reading of ``apply_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("apply_ms.steady")
