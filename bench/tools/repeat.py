#!/usr/bin/env python3
"""Run one cell several times, one process per run, and summarize.

    python3 bench/tools/repeat.py --workload <cell> --seeds 1,2,3 \
        --seconds 30 --trace 0 [--out <file>.jsonl]

Each run is ``bench/run.py`` in a child process (the parent never touches
JAX, so each child gets the chip). Every result line goes to ``--out``;
the summary prints each metric's values, median and quartile spread
(``statistics.quantiles(values, n=4)``, as a share of the median).
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--timeout", type=float, default=1300)
    args = ap.parse_args()
    lines = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        t0 = time.monotonic()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=args.timeout)
        wall = time.monotonic() - t0
        err_tail = "\n".join(p.stderr.strip().splitlines()[-14:])
        print(f"== seed {seed}: rc {p.returncode}, {wall:.1f}s\n{err_tail}",
              flush=True)
        out = p.stdout.strip().splitlines()
        if p.returncode != 0 or not out:
            print(p.stderr[-6000:], flush=True)
            continue
        line = json.loads(out[-1])
        line["seed"] = seed
        line["wall_s"] = wall
        lines.append(line)
        print(json.dumps({k: line[k] for k in ("correct", "attempted",
                                                 "failed", "metrics")}),
              flush=True)
        if args.out:
            with open(ROOT / args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    names = sorted({k for ln in lines for k in ln["metrics"]})
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        s = spread(vals)
        print(f"{name}: median {statistics.median(vals):.6g} spread "
              f"{'n/a' if s is None else f'{100 * s:.3f}%'} values "
              f"{[round(v, 4) for v in vals]}")
    print(f"correct: {[ln['correct'] for ln in lines]}")


if __name__ == "__main__":
    main()
