"""The reading of ``ell_refresh_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("ell_refresh_ms.steady")
