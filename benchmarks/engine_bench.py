"""Engine bench — device-sharded execution across 1/2/4 forced devices.

Two sweeps, each forcing the device count per measurement with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in a fresh
subprocess (the device count is fixed at jax init, so a sweep cannot run
in one process):

  * **query axis** (``--query-only``): a 16-query bank (the zoo: 4 shapes
    × 4 label rotations, bucketed by the engine into per-shape dynamic
    banks) serves the same churn stream on 1/2/4 logical devices with the
    bank rows ``shard_map``-ed over ``("q",)``.
  * **graph axis** (``--graph-only``): the ``n_max``-scaling sweep — a
    4-query bank serves a storm-forced stream (every step takes the
    full-graph path, so every step pays the sharded label-RWR + bank
    sweeps) at growing ``n_max`` with the vertices partitioned over
    ``("g",)`` (``ServingConfig(shard="off", graph_shard="auto")``).
    Multi-device points run twice: replicated edge storage, then the
    co-partitioned layout (``edge_partition="on"`` → ``gdev{N}/part``
    rows), and every graph-axis row carries ``edge_dev_bytes``/
    ``edge_repl_bytes``/``edge_frac`` so the ~1/g per-device memory drop
    is a gated number, not a claim (DESIGN.md §10).

Reported per row: median full serving-step latency, p50/p99, and the
shard counts actually used.

On this CPU container the sharded paths add partition overhead rather
than speedup — the measured quantity is the *scaling harness* (sharded
results are pinned bit-identical in tests/test_engine_sharding.py and
tests/test_graph_sharding.py; real speedups need real devices). The JSON
artifact keeps CI honest about both paths existing and running
end-to-end.

  PYTHONPATH=src:. python benchmarks/engine_bench.py [--smoke] \
      [--query-only | --graph-only]

Writes ``benchmarks/out/engine_bench.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List

DEVICE_COUNTS = (1, 2, 4)
BANK = 16
NMAX_FULL = (1024, 2048)
NMAX_SMOKE = (256,)


def _worker(n_devices: int, smoke: bool) -> None:
    """Query-axis worker (forced-device subprocess); prints one JSON line."""
    import numpy as np

    import jax

    from benchmarks.serving_bench import _cfg, _spec
    from repro.config.base import ServingConfig
    from repro.core.query import query_zoo
    from repro.data.temporal import generate_stream
    from repro.serving import MatchServer

    assert len(jax.devices()) == n_devices, (
        f"expected {n_devices} forced devices, found {len(jax.devices())}")
    spec = _spec(smoke, 1.0)
    cfg = _cfg(spec, smoke)
    n_steps = 3 if smoke else 8
    server = MatchServer(cfg, query_zoo(BANK),
                         ServingConfig(microbatch_window=256, shard="auto"),
                         seed=0)
    shards = sorted(
        (f"{b.q_max}x{b.qe_max}x{b.b_pad}", b.n_shards)
        for b in server.engine.buckets.values())

    def pass_once():
        stream = generate_stream(spec, n_measured_steps=n_steps, u_max=256)
        g = stream.graph
        totals = []
        for upd in stream.updates:
            server.submit_update(upd)
            g, st = server.step(g)
            totals.append(st.total_s)
        return totals

    pass_once()        # warm/compile pass on an identical stream
    server.reset()
    totals = pass_once()
    snap = server.telemetry.snapshot()
    print(json.dumps({
        "devices": n_devices,
        "median_step_us": 1e6 * float(np.median(totals)),
        "p50_ms": snap["p50_step_ms"],
        "p99_ms": snap["p99_step_ms"],
        "updates_per_s": snap["updates_per_s"],
        "bucket_shards": shards,
    }))


def _edge_bytes(engine) -> dict:
    """Per-device edge-storage bytes for the engine's active layout, next
    to what a fully replicated COO copy would cost — the memory number
    the partitioned-storage rows gate on (DESIGN.md §10)."""
    import jax
    import numpy as np

    from repro.core.graph import EdgePartition

    repl = EdgePartition.replicated_nbytes(engine.cfg.e_max)
    if engine.part_cache is not None:
        dev = engine.part_cache.slice_nbytes()
    elif engine.ell_cache is not None:
        # block-sharded mirror: each device holds 1/g of the stacked rows
        tot = sum(np.asarray(x).nbytes
                  for x in jax.tree.leaves(engine.ell_cache.ell))
        dev = tot // max(engine.g_shards, 1)
    else:
        dev = repl  # replicated COO: every device carries the full arrays
    return {"edge_dev_bytes": int(dev), "edge_repl_bytes": int(repl),
            "edge_frac": round(dev / repl, 4)}


def _graph_worker(n_devices: int, n_max: int, smoke: bool,
                  partition: bool = False) -> None:
    """Graph-axis worker: storm-forced serving at ``n_max`` with the
    vertices sharded over ``("g",)`` — and, under ``--partition``, the
    edge storage co-partitioned with the receiver slices; prints one JSON
    line."""
    import numpy as np

    import jax

    from repro.config.base import IGPMConfig, ServingConfig
    from repro.core.query import query_zoo
    from repro.data.temporal import TemporalGraphSpec, generate_stream
    from repro.serving import MatchServer

    assert len(jax.devices()) == n_devices, (
        f"expected {n_devices} forced devices, found {len(jax.devices())}")
    spec = TemporalGraphSpec("nscale", "sparse_dense", n_vertices=n_max,
                             n_edges=8 * n_max, n_steps=64, seed=11,
                             churn=0.25)
    cfg = IGPMConfig(
        n_max=n_max, e_max=int(2.4 * 8 * n_max) + 4096,
        ell_width=8 if smoke else 16,
        rwr_iters=8 if smoke else 15, rwr_iters_incremental=3,
        top_k_patterns=6 if smoke else 10, init_community_size=32)
    n_steps = 2 if smoke else 6
    # storms forced: every step runs the full-graph sweeps the graph axis
    # partitions; the query axis stays off so the split is pure
    server = MatchServer(cfg, query_zoo(4),
                         ServingConfig(microbatch_window=256, shard="off",
                                       graph_shard="auto",
                                       edge_partition=("on" if partition
                                                       else "off"),
                                       full_graph_frac=-1.0),
                         seed=0)

    def pass_once():
        stream = generate_stream(spec, n_measured_steps=n_steps, u_max=256)
        g = stream.graph
        totals = []
        for upd in stream.updates:
            server.submit_update(upd)
            g, st = server.step(g)
            totals.append(st.total_s)
        return totals

    pass_once()
    server.reset()
    totals = pass_once()
    snap = server.telemetry.snapshot()
    print(json.dumps({
        "devices": n_devices,
        "n_max": n_max,
        "g_shards": server.engine.g_shards,
        "partitioned": server.engine.partitioned,
        "median_step_us": 1e6 * float(np.median(totals)),
        "p50_ms": snap["p50_step_ms"],
        "p99_ms": snap["p99_step_ms"],
        "updates_per_s": snap["updates_per_s"],
        **_edge_bytes(server.engine),
    }))


def _run_forced(n_devices: int, extra_args: List[str]) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices never open a chip
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = "src:." + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--devices", str(n_devices)] + extra_args
    out = subprocess.run(
        cmd, env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if out.returncode != 0:
        raise SystemExit(
            f"engine_bench worker (devices={n_devices}, {extra_args}) "
            f"failed:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(smoke: bool = False, query_axis: bool = True,
        graph_axis: bool = True) -> List["BenchRow"]:
    from benchmarks.common import BenchRow, write_json

    rows = []
    if query_axis:
        for nd in DEVICE_COUNTS:
            r = _run_forced(nd, ["--worker"] + (["--smoke"] if smoke else []))
            shards = ";".join(f"{k}:{v}" for k, v in r["bucket_shards"])
            rows.append(BenchRow(
                f"engine/bank{BANK}/dev{r['devices']}", r["median_step_us"],
                f"p50_ms={r['p50_ms']:.1f};p99_ms={r['p99_ms']:.1f};"
                f"updates_per_s={r['updates_per_s']:.0f};shards={shards}"))
    if graph_axis:
        for n_max in (NMAX_SMOKE if smoke else NMAX_FULL):
            for nd in DEVICE_COUNTS:
                # replicated edge storage, then (multi-device only) the
                # co-partitioned layout — same stream, so the edge_frac
                # columns are the ~1/g memory drop the partition buys
                variants = [([], "")]
                if nd > 1:
                    variants.append((["--partition"], "/part"))
                for extra, tag in variants:
                    r = _run_forced(
                        nd, ["--graph-worker", "--nmax", str(n_max)]
                        + extra + (["--smoke"] if smoke else []))
                    rows.append(BenchRow(
                        f"engine/nmax{n_max}/gdev{r['devices']}{tag}",
                        r["median_step_us"],
                        f"g_shards={r['g_shards']};"
                        f"p50_ms={r['p50_ms']:.1f};"
                        f"p99_ms={r['p99_ms']:.1f};"
                        f"updates_per_s={r['updates_per_s']:.0f};"
                        f"edge_dev_bytes={r['edge_dev_bytes']};"
                        f"edge_repl_bytes={r['edge_repl_bytes']};"
                        f"edge_frac={r['edge_frac']}"))
    # partial runs (one axis only) get their own artifact name so the CI
    # engine-smoke/sweep-smoke pair cannot clobber each other's rows; only
    # a both-axes run refreshes the canonical (smoke) artifact
    name = "engine_bench" + ("_smoke" if smoke else "")
    if not (query_axis and graph_axis):
        name += "_qaxis" if query_axis else "_gaxis"
    write_json(rows, name)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny stream for CI (same code path)")
    ap.add_argument("--query-only", action="store_true",
                    help="only the query-axis bank sweep")
    ap.add_argument("--graph-only", action="store_true",
                    help="only the graph-axis n_max sweep")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--graph-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--partition", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--nmax", type=int, default=1024, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker or args.graph_worker:
        # only the forced-device children touch JAX: the sweep parent
        # stays off every backend so it never holds a chip
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
    if args.worker:
        _worker(args.devices, args.smoke)
        return
    if args.graph_worker:
        _graph_worker(args.devices, args.nmax, args.smoke,
                      partition=args.partition)
        return
    for row in run(smoke=args.smoke, query_axis=not args.graph_only,
                   graph_axis=not args.query_only):
        print(row.csv())


if __name__ == "__main__":
    main()
