#!/usr/bin/env python3
"""Chip smoke: the served IGPM-PEM path at published size on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded layouts on a 4-chip host

One chip: the full ``igpm-pem`` config (``n_max`` 262,144, ``e_max``
8,388,608, ELL width 64, ``backend="ell"``) serves the ``transactions``
dataset twin at its published size (112,130 vertices, 538,597 edges) to a
16-query bank (the paper's four shapes × four label rotations), in phases:

  1. both Pallas ELL kernels, compiled by Mosaic, against the jnp oracle
     (``kernels/spmv_ell/ref.py``) on the stream's own ELL mirror at
     widths 4 and 32;
  2. a warm step and a few served steps through ``MatchServer`` on the
     ELL backend;
  3. the same stream on the COO backend — every query must end with the
     same patterns (vertex sets and exact flags);
  4. the same stream replayed through the threaded ``ServingRuntime``
     (lockstep ingress) — the stores must equal phase 2's bit for bit;
  5. one more update batch as a forced full-graph (storm) step, the path
     a flash crowd takes: every pattern flagged exact must then embed its
     query in the live graph (labels kept, every query edge a data edge).

``--chips 4`` runs only the multi-device phase: one forced storm step of
the same twin and bank on one device, on the query axis over four devices,
and on the 2-D mesh with the graph axis and edge-partitioned storage; each
multi-device run's stores must equal the one-device run's.

Everything runs in this one process. Any failed phase raises, so the
script exits nonzero without the final line; on success the last line is
``{"ok": true, "device": {...}}`` as JAX reports the device. A host
without a TPU exits nonzero before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TWIN = "transactions"
BANK = 16
SERVED_STEPS = 3          # after the warm step (then one storm step)
KERNEL_WIDTHS = (4, 32)   # label-RWR width; widest RHS block at n 262,144
REF_ROWS = 65536          # oracle row chunk (the full gather is 3 GiB at d 32)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def tpu_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX "
                         f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    import jax

    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", 0))


def twin_stream(cfg, n_steps: int):
    from repro.data.temporal import DATASET_TWINS, generate_stream

    t0 = time.perf_counter()
    stream = generate_stream(DATASET_TWINS[TWIN], n_max=cfg.n_max,
                             e_max=cfg.e_max, n_measured_steps=n_steps,
                             u_max=512)
    log(f"stream {TWIN}: {stream.spec.n_vertices} vertices, "
        f"{stream.spec.n_edges} edges, {stream.warmup_edges} warm-up edges, "
        f"{n_steps} update batches, n_max={cfg.n_max} e_max={cfg.e_max} "
        f"K={cfg.ell_width} ({time.perf_counter() - t0:.1f}s)")
    return stream


def check_kernels(cfg, graph, seed: int = 0) -> None:
    """Both ELL kernels on the graph's mirror vs the oracle, whose rows
    are taken in chunks and folded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.graph import ell_from_graph
    from repro.kernels.spmv_ell.ref import ell_reach_ref, ell_spmm_ref
    from repro.kernels.spmv_ell.spmv_ell import (ell_vertex_maxima,
                                                 ell_vertex_sums)

    n = cfg.n_max
    ell = ell_from_graph(graph, cfg.ell_width)
    r = ell.cols.shape[0]
    log(f"ELL mirror: {r} rows x {ell.k} slots, "
        f"{int(np.asarray(ell.mask).sum())} live entries")
    ref_s = jax.jit(ell_spmm_ref, static_argnames="n")
    ref_m = jax.jit(ell_reach_ref, static_argnames="n")
    rng = np.random.default_rng(seed)
    for d in KERNEL_WIDTHS:
        x = jnp.asarray(rng.random((n, d), dtype=np.float32))
        for name, kern, args, ref, fold in (
                ("ell_vertex_sums", ell_vertex_sums,
                 (ell.cols, ell.vals, ell.mask, ell.row_ids), ref_s, jnp.add),
                ("ell_vertex_maxima", ell_vertex_maxima,
                 (ell.cols, ell.mask, ell.row_ids), ref_m, jnp.maximum)):
            t0 = time.perf_counter()
            got = jax.block_until_ready(kern(*args, x, n))
            t1 = time.perf_counter()
            got = jax.block_until_ready(kern(*args, x, n))
            t2 = time.perf_counter()
            want = None
            for lo in range(0, r, REF_ROWS):
                part = ref(*(a[lo:lo + REF_ROWS] for a in args), x, n=n)
                want = part if want is None else fold(want, part)
            got, want = np.asarray(got), np.asarray(want)
            if kern is ell_vertex_maxima:
                np.testing.assert_array_equal(got, want, err_msg=name)
            else:
                np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                           err_msg=name)
            log(f"{name} n={n} K={ell.k} d={d}: matches ref; first call "
                f"(compile) {t1 - t0:.2f}s, steady {1e3 * (t2 - t1):.2f} ms")


def log_step(label: str, kind: str, t: int, st, extra: str = "") -> None:
    log(f"{label} {kind} {t}: {1e3 * st.total_s:.1f} ms "
        f"events={st.n_events} recompute={st.n_recompute} "
        f"subgraph={st.subgraph_nodes}v/{st.subgraph_edges}e "
        f"new={st.n_new_patterns}{extra}")


def log_stores(label: str, server) -> None:
    totals = [s.total for s in server.stores]
    log(f"{label}: {sum(totals)} patterns over {len(totals)} queries "
        f"(exact {sum(s.exact for s in server.stores)}); "
        f"peak device bytes {peak_bytes()}")


def new_server(cfg, **serving):
    """A MatchServer for the bank, stage tracing on (so storm steps can
    print where their time went)."""
    from repro.config.base import ObsConfig, ServingConfig
    from repro.core.query import query_zoo
    from repro.serving import MatchServer

    return MatchServer(cfg, query_zoo(BANK),
                       ServingConfig(adaptive=False,
                                     obs=ObsConfig(enabled=True), **serving),
                       seed=0)


def serve(cfg, graph, updates, label: str, **serving):
    """Warm step + served steps through MatchServer; returns the server."""
    server = new_server(cfg, **serving)
    g = graph
    for t, upd in enumerate(updates):
        server.submit_update(upd)
        g, st = server.step(g)
        log_step(label, "warm step" if t == 0 else "served step", t, st,
                 f" (dendrogram {server.pem.clustering_time:.2f}s)"
                 if t == 0 and server.pem is not None else "")
    log_stores(label, server)
    return server


def storm_step(server, graph, upd, label: str) -> None:
    """Serve ``upd`` on ``graph`` as a full-graph storm step: the PEM
    threshold drops below zero, so the step sweeps all ``n_max`` vertices
    — the fallback a flash crowd triggers."""
    import numpy as np

    eng = server.engine
    eng.ecfg = dataclasses.replace(eng.ecfg, full_graph_frac=-1.0)
    server.submit_update(upd)
    g, st = server.step(graph)
    log_step(label, "storm step", st.step, st,
             "; stages s " + " ".join(f"{k}={v:.2f}"
                                      for k, v in st.stage_s.items()))
    n_live = int(np.asarray(g.node_mask).sum())
    if st.subgraph_nodes != n_live:
        raise SystemExit(f"{label}: storm step swept {st.subgraph_nodes} "
                         f"vertices, not all {n_live} live ones")
    log_stores(label, server)


def check_exact(server) -> None:
    """Every pattern flagged exact must embed its query in the live graph:
    some assignment of its vertex set keeps every query label and maps
    every query edge onto a live data edge."""
    import itertools

    import numpy as np

    g = server.graph
    em = np.asarray(g.edge_mask)
    edges = set(zip(np.asarray(g.senders)[em].tolist(),
                    np.asarray(g.receivers)[em].tolist()))
    labels = np.asarray(g.labels)
    eng = server.engine
    queries = {qid: b.query(slot) for b in eng.buckets.values()
               for slot, qid in b.rows()}
    n_checked = 0
    for qid, store in eng.stores.items():
        q = queries[qid]
        qlab = np.asarray(q.labels)[np.asarray(q.mask)]
        qedges = [(int(a), int(b)) for a, b, m in
                  zip(np.asarray(q.order_src), np.asarray(q.order_dst),
                      np.asarray(q.order_mask)) if m]
        arr = store.to_arrays()
        for key in arr["keys"][arr["exact"]].tolist():
            if not any(
                    (labels[list(p)] == qlab).all()
                    and all((p[a], p[b]) in edges or (p[b], p[a]) in edges
                            for a, b in qedges)
                    for p in itertools.permutations(key)):
                raise SystemExit(f"{qid}: exact pattern {key} does not "
                                 f"embed {q.name} in the graph")
            n_checked += 1
    log(f"exact patterns embed their queries: {n_checked} checked")


def patterns(server, with_goodness: bool = True) -> dict:
    """qid → {vertex set: (goodness, exact)} — or → {vertex set: exact}."""
    out = {}
    for qid, store in server.engine.stores.items():
        arr = store.to_arrays()
        keys = [tuple(k) for k in arr["keys"].tolist()]
        vals = (list(zip(arr["goodness"].tolist(), arr["exact"].tolist()))
                if with_goodness else arr["exact"].tolist())
        out[qid] = dict(zip(keys, vals))
    return out


def require_same(a: dict, b: dict, what: str) -> None:
    differ = sorted(q for q in set(a) | set(b) if a.get(q) != b.get(q))
    if differ:
        for q in differ:
            left, right = a.get(q, {}), b.get(q, {})
            log(f"{what}: {q} differs — {len(left)} vs {len(right)} "
                f"patterns, {len(set(left) ^ set(right))} vertex sets in "
                f"only one")
        raise SystemExit(f"{what}: stores differ for {differ}")
    log(f"{what}: stores identical for {len(a)} queries")


def runtime_replay(server, stream, updates) -> None:
    """Replay ``updates`` through the threaded runtime (one tick per update
    batch, lockstep ingress) on a reset server."""
    from repro.config.base import RuntimeConfig
    from repro.runtime import ServingRuntime, WallClock
    from repro.runtime.scenarios import ScenarioConfig, Tick, Workload
    from repro.serving.queue import batch_to_events

    ticks = [Tick(t=0.05 * i, events=batch_to_events(upd))
             for i, upd in enumerate(updates)]
    sc = ScenarioConfig(name=f"replay@{TWIN}", kind="poisson",
                        tick_s=0.05, n_ticks=len(ticks))
    wl = Workload(sc, stream.spec, stream, ticks,
                  sum(len(t.events) for t in ticks))
    server.reset()
    rt = ServingRuntime(server, RuntimeConfig(ingress="lockstep",
                                              drain_timeout_s=900.0),
                        clock=WallClock())
    sub = rt.subscribe()
    t0 = time.perf_counter()
    stats = rt.serve(wl)
    deltas = sub.drain()
    log(f"runtime: {len(stats)} steps, {wl.n_events} events in "
        f"{time.perf_counter() - t0:.1f}s; step ms "
        + " ".join(f"{1e3 * s.total_s:.1f}" for s in stats)
        + f"; subscriber saw {len(deltas)} deltas")
    if len(stats) != len(ticks):
        raise SystemExit(f"runtime ran {len(stats)} steps for "
                         f"{len(ticks)} ticks")


def one_chip() -> None:
    from repro.config.registry import get_arch

    cfg = get_arch("igpm-pem").model
    log(f"config igpm-pem: n_max={cfg.n_max} e_max={cfg.e_max} "
        f"K={cfg.ell_width} backend={cfg.backend} bank={BANK}")
    stream = twin_stream(cfg, 1 + SERVED_STEPS + 1)
    updates, storm = stream.updates[:-1], stream.updates[-1]
    check_kernels(cfg, stream.graph)
    ell = serve(cfg, stream.graph, updates, "ell")
    served = patterns(ell)
    coo = serve(dataclasses.replace(cfg, backend="coo"), stream.graph,
                updates, "coo")
    require_same(patterns(ell, with_goodness=False),
                 patterns(coo, with_goodness=False), "ell vs coo")
    del coo
    runtime_replay(ell, stream, updates)
    require_same(served, patterns(ell), "runtime vs sync")
    storm_step(ell, ell.graph, storm, "ell")
    check_exact(ell)


def four_chips() -> None:
    from repro.config.registry import get_arch

    cfg = get_arch("igpm-pem").model
    stream = twin_stream(cfg, 1)

    def run(label: str, **serving):
        # the first step is a storm sweeping all n_max vertices
        server = new_server(cfg, **serving)
        storm_step(server, stream.graph, stream.updates[0], label)
        return server

    single = patterns(run("1 device", shard="off"))
    q = run("query axis", shard="auto")
    if not any(b.n_shards > 1 for b in q.engine.buckets.values()):
        raise SystemExit("query axis did not shard any bucket")
    require_same(single, patterns(q), "query axis vs 1 device")
    del q
    g = run("graph axis + partition", graph_shard="auto",
            edge_partition="on")
    if not (g.engine.g_shards > 1 and g.engine.partitioned):
        raise SystemExit("graph axis / edge partition not active")
    log(f"mesh: g_shards={g.engine.g_shards} q_budget={g.engine.q_budget}")
    require_same(single, patterns(g), "graph axis + partition vs 1 device")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the multi-device phase, on four chips")
    args = ap.parse_args()
    device = tpu_device(args.chips)
    from repro.launch.compile_cache import use_compile_cache

    log(f"device {device}; compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
