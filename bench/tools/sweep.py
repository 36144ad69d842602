#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve it at several fixed rates.

    python3 bench/tools/sweep.py --workload <cell> --rates 200,300,400 \
        --seconds 20 --seed 5

One child process per rate (the parent never touches JAX). For each rate
it prints the offered and shed events, the latency median and 95th
percentile, and whether the backlog grew: the median queue wait of the
window's last third of events against its first third. The knee is the
highest rate at which nothing is shed and the backlog does not grow; the
cell then runs at about four fifths of it, written into its traffic file.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(workload: str, rate: float, seconds: float, seed: int) -> dict:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import generator as gen
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    harness.persistent_cache(use_compile_cache())
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in b["workloads"] if w["name"] == workload)
    cfg_file = next(c["file"] for c in b["configs"]
                    if c["name"] == cell["config"])
    cfg = json.load(open(os.path.join(ROOT, cfg_file)))
    tr = dataclasses.replace(gen.traffic_from_file(cell["traffic"]),
                             rate_eps=rate)
    res = harness.run_cell(cell, cfg, tr, seed, seconds, False,
                           time.monotonic(), sample_steps=1)
    v = res["view"]
    qw = v.queue_wait_s
    third = max(len(qw) // 3, 1)
    buckets = collections.Counter(
        f"{harness.pow2(s.stats.subgraph_nodes, 64)}x"
        f"{harness.pow2(s.stats.subgraph_edges, 256)}" for s in v.steps)
    return {"rate": rate, "offered": v.attempted, "failed": v.failed,
            "shed": res["shed"], "steps": len(v.steps),
            "p50_ms": 1e3 * float(np.percentile(v.e2e_s, 50)),
            "p95_ms": 1e3 * float(np.percentile(v.e2e_s, 95)),
            "wait_first_third_ms": 1e3 * float(np.median(qw[:third])),
            "wait_last_third_ms": 1e3 * float(np.median(qw[-third:])),
            "mean_batch": float(np.mean([s.n_events for s in v.steps])),
            "mean_step_ms": 1e3 * float(np.mean(
                [s.stats.total_s for s in v.steps])),
            "served_s": res["served_s"], "correct": res["correct"],
            "compiles": v.compiles, "compiled": res["compiled_in_window"],
            "buckets": dict(buckets),
            "batches": [s.n_events for s in v.steps]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--one", type=float, default=0.0)
    args = ap.parse_args()
    if args.one:
        print(json.dumps(one(args.workload, args.one, args.seconds,
                             args.seed)), flush=True)
        return
    for rate in args.rates.split(","):
        p = subprocess.run([sys.executable, __file__, "--workload",
                            args.workload, "--one", rate, "--seconds",
                            str(args.seconds), "--seed", str(args.seed)],
                           capture_output=True, text=True, timeout=900)
        out = p.stdout.strip().splitlines()
        print(out[-1] if p.returncode == 0 and out
              else f"rate {rate}: rc {p.returncode} {p.stderr[-3000:]}",
              flush=True)


if __name__ == "__main__":
    main()
