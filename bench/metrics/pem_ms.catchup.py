"""The reading of ``pem_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("pem_ms.steady")
