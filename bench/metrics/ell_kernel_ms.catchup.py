"""The reading of ``ell_kernel_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("ell_kernel_ms.steady")
