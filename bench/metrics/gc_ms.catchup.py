"""The reading of ``gc_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("gc_ms.steady")
