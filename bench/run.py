#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and metrics are found by name under ``bench/``
(``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``). With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window and from the engine's stage spans.

The run needs the chips the cell asks for: without a TPU, or with too few
chips, it exits nonzero before printing any result. The last lines on
standard error are the numbers compared with the plain reference, each
beside its limit; the last line of standard output is the result as JSON.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    fail(f"no {what} named {name!r} in BENCHMARK.json")


def require_chips(n: int) -> dict:
    """The device the run measures, or exit: no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devs[0].platform} "
             f"({devs[0].device_kind})", 3)
    if len(devs) < n:
        fail(f"the cell needs {n} chips; JAX found {len(devs)}", 3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def metric_names(b: dict, cell: str, trace: bool):
    """The cell's end-to-end metrics, or its per-layer ones."""
    group = b["per_layer"] if trace else b["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    b = spec()
    cell = find(b["workloads"], args.workload, "workload")
    device = require_chips(int(cell["chips"]))

    from bench import generator as gen
    from bench import harness
    from bench import measures
    from repro.launch.compile_cache import use_compile_cache

    harness.persistent_cache(use_compile_cache())
    cfg_entry = find(b["configs"], cell["config"], "config")
    with open(ROOT / cfg_entry["file"]) as f:
        cfg = json.load(f)
    tr = gen.traffic_from_file(cell["traffic"])
    res = harness.run_cell(cell, cfg, tr, args.seed, args.seconds,
                           bool(args.trace), T_START)
    view = res["view"]
    metrics = {}
    values = measures.read_all(
        [m["name"] for m in metric_names(b, cell["name"], bool(args.trace))],
        view)
    units = {m["name"]: m["unit"] for m in b["end_to_end"] + b["per_layer"]}
    for name, v in values.items():
        metrics[name] = {"value": v, "unit": units[name]}
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    line = {"correct": bool(res["correct"]), "attempted": view.attempted,
            "failed": view.failed, "metrics": metrics, "device": device}
    if args.trace:
        t = view.trace
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    print(f"bench: {cell['name']} seed {args.seed}: {len(view.steps)} "
          f"window steps, {view.attempted} events offered, {res['shed']} "
          f"shed, {view.compiles} programs compiled or loaded in the "
          f"window {res['compiled_in_window'][:8]}, capacity buckets "
          f"not reached in set-up {res['buckets_not_reached']}, reference "
          f"{res['reference_s']:.1f}s over {res['n_compared_steps']} "
          f"steps / {res['n_results']} results", file=sys.stderr)
    compared = res["compared"]
    for name, (value, limit) in compared.items():
        print(f"compared {name} = {value!r} (limit {limit!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line["compared"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in compared.items()}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
