"""The reading of ``compile_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("compile_ms.steady")
