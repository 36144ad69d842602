#!/usr/bin/env python3
"""Read a rate sweep (``sweep.py`` output) and name the knee.

    python3 bench/tools/knee.py <sweep.jsonl> [--share 0.8]

The knee is the highest rate at which nothing was shed or lost and the
backlog did not grow: the median queue wait of the window's last third of
events is at most that of its first third plus 200 ms (a rate above
capacity can start the window on a backlog of seconds and still grow it,
so the test is on growth alone, not on a share of the backlog). Prints the
knee and ``share`` of it rounded to 10 events/s, the rate a cell below
capacity runs at.
"""

from __future__ import annotations

import argparse
import json


def sustained(row: dict) -> bool:
    return (row["shed"] == 0 and row["failed"] == 0
            and row["wait_last_third_ms"]
            <= row["wait_first_third_ms"] + 200.0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("sweep")
    ap.add_argument("--share", type=float, default=0.8)
    args = ap.parse_args()
    rows = []
    with open(args.sweep) as f:
        for line in f:
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue
    ok = [r["rate"] for r in rows if sustained(r)]
    if not ok:
        raise SystemExit("no rate of the sweep was sustained")
    knee = max(ok)
    print(json.dumps({"knee": knee,
                      "rate": 10 * round(args.share * knee / 10)}))


if __name__ == "__main__":
    main()
