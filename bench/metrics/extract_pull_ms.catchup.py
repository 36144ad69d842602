"""The reading of ``extract_pull_ms.steady`` over a backlog cell's window."""

from bench.measures import load_reader

read = load_reader("extract_pull_ms.steady")
