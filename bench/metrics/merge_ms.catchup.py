"""The reading of ``merge_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("merge_ms.steady")
