"""The reading of ``gray_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("gray_ms.steady")
