#!/usr/bin/env python3
"""Run a cell with the timed path broken, and print what was compared.

    python3 bench/tools/control.py --workload <cell> --variant bf16_rwr \
        --seeds 1,2,3 --seconds 10

``--variant`` is ``bf16_rwr`` (the control: RWR sweeps in bfloat16) or one
of ``bench.faults.FAULTS``. Runs in this one process, seed after seed,
through the same ``run_cell`` the benchmark uses, at the cell's own size
and load, and prints one JSON line per seed with every compared number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import faults
    from bench import generator as gen
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    harness.persistent_cache(use_compile_cache())
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in b["workloads"] if w["name"] == args.workload)
    cfg_file = next(c["file"] for c in b["configs"]
                    if c["name"] == cell["config"])
    cfg = json.load(open(os.path.join(ROOT, cfg_file)))
    tr = gen.traffic_from_file(cell["traffic"])
    breaker = None
    if args.variant == "bf16_rwr":
        faults.bf16_rwr()
    else:
        breaker = faults.FAULTS[args.variant]
    for seed in args.seeds.split(","):
        t0 = time.monotonic()
        try:
            res = harness.run_cell(cell, cfg, tr, int(seed), args.seconds,
                                   False, t0, breaker=breaker)
        except Exception as e:  # a crashing variant has failed the check
            print(json.dumps({"seed": seed, "variant": args.variant,
                              "crashed": f"{type(e).__name__}: {e}"}),
                  flush=True)
            continue
        print(json.dumps({"seed": seed, "variant": args.variant,
                          "correct": res["correct"],
                          "compared": res["compared"],
                          "n_results": res["n_results"]}), flush=True)


if __name__ == "__main__":
    main()
