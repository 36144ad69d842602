"""The reading of ``compiles_in_window.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("compiles_in_window.steady")
