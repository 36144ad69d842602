"""The reading of ``device_idle_share.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("device_idle_share.steady")
