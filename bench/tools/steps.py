#!/usr/bin/env python3
"""Run one cell once and keep every window step, to see what moves a tail.

    python3 bench/tools/steps.py --workload <cell> --seed <n> --seconds 30 \
        --out <file>.jsonl

Runs the cell as ``bench/run.py --trace 0`` does, in this process (so it
needs the chip to itself), and appends one JSON line: the seed, the
end-to-end metrics, and for each window step its start (s after the
first), duration (ms), events, PEM threshold ``c``, recompute-set size
and induced subgraph (vertices, arcs).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as entry  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    b = entry.spec()
    cell = entry.find(b["workloads"], args.workload, "workload")
    entry.require_chips(int(cell["chips"]))

    from bench import generator as gen
    from bench import harness, measures
    from repro.launch.compile_cache import use_compile_cache

    harness.persistent_cache(use_compile_cache())
    cfg_entry = entry.find(b["configs"], cell["config"], "config")
    with open(ROOT / cfg_entry["file"]) as f:
        cfg = json.load(f)
    tr = gen.traffic_from_file(cell["traffic"])
    res = harness.run_cell(cell, cfg, tr, args.seed, args.seconds, False,
                           T_START)
    view = res["view"]
    names = [m["name"] for m in entry.metric_names(b, cell["name"], False)]
    t0 = view.steps[0].t_start if view.steps else 0.0
    steps = [[round(s.t_start - t0, 4), round(1e3 * (s.t_done - s.t_start), 2),
              s.n_events, s.c,
              0 if s.recompute is None else int(len(s.recompute)),
              int(s.stats.subgraph_nodes), int(s.stats.subgraph_edges)]
             for s in view.steps]
    line = {"seed": args.seed, "correct": bool(res["correct"]),
            "metrics": measures.read_all(names, view),
            "steps": steps}
    with open(ROOT / args.out, "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps({k: line[k] for k in ("seed", "correct", "metrics")}),
          flush=True)


if __name__ == "__main__":
    main()
