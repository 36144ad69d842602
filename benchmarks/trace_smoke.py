"""Trace smoke — the observability acceptance gate (DESIGN.md §8).

Runs ONE warm serving stream twice on the same server — untraced, then
traced — and enforces the tracing overhead budget: the traced median
step must stay within 5% of the untraced median (plus a small absolute
floor so a sub-millisecond smoke step can't fail on scheduler noise).
Then validates everything tracing promises to produce:

- the exported JSONL span stream passes the ``trace_event`` schema check
  (``validate_jsonl``) and covers every engine pipeline stage;
- the Chrome twin document is well-formed (``traceEvents`` list) so
  Perfetto/chrome://tracing load it;
- the Prometheus artifact (exported file AND a live ``/metrics`` fetch
  through :class:`~repro.obs.OpsServer`) passes the text-exposition
  checks (``validate_exposition``: HELP/TYPE framing, name validity,
  finite parseable samples);
- a triggered flight-recorder dump is itself a valid JSONL trace;
- a traced flash-crowd run through the threaded ``ServingRuntime``
  produces a cross-thread trace (ingress + executor tids) — committed
  under ``benchmarks/out/traces/`` as the Perfetto-loadable artifact.

  PYTHONPATH=src:. python benchmarks/trace_smoke.py

Exit status is the gate (``make trace-smoke`` / CI observability job).
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import OUT_DIR, BenchRow, write_json
from repro.config.base import (IGPMConfig, ObsConfig, RuntimeConfig,
                               ServingConfig)
from repro.core.query import query_zoo
from repro.data.temporal import TemporalGraphSpec, generate_stream
from repro.launch.compile_cache import use_compile_cache
from repro.obs import Obs, read_jsonl, validate_exposition, validate_jsonl
from repro.serving import MatchServer

TRACE_DIR = os.path.join(OUT_DIR, "traces")
# overhead gate: traced median ≤ untraced median × (1 + 5%) + floor.
# The absolute floor keeps a ~10 ms smoke step from failing on one
# scheduler hiccup; at production step times (100 ms+) it is negligible
# next to the 5% relative budget, so the relative gate stays the binding
# one where it matters.
OVERHEAD_FRAC = 0.05
OVERHEAD_FLOOR_S = 1e-3
N_STEPS = 24

# every stage span the engine promises per traced step (module docstring
# of repro/engine/core.py; storm steps swap extract for seeds/gray)
EXPECTED_STAGES = {"apply", "prune", "pem", "rwr", "merge"}


def _serve(server: MatchServer, stream) -> float:
    """Median full-step latency over one replay of ``stream``."""
    g = stream.graph
    totals = []
    for upd in stream.updates:
        server.submit_update(upd)
        g, st = server.step(g)
        totals.append(st.total_s)
    return float(np.median(totals))


def run() -> list:
    spec = TemporalGraphSpec("trace_smoke", "sparse_dense", n_vertices=256,
                             n_edges=2048, n_steps=64, seed=7, churn=0.25)
    cfg = IGPMConfig(n_max=spec.n_vertices, e_max=4 * spec.n_edges,
                     ell_width=8, rwr_iters=8, rwr_iters_incremental=3,
                     top_k_patterns=6, init_community_size=32)
    server = MatchServer(cfg, query_zoo(4),
                         ServingConfig(microbatch_window=256), seed=0)
    stream = generate_stream(spec, n_measured_steps=N_STEPS, u_max=256)

    # warm/compile pass, then the untraced reference measurement
    _serve(server, stream)
    server.reset()
    t_off = _serve(server, stream)
    assert server.engine.obs.tracer.n_spans == 0, \
        "untraced run emitted spans"

    # traced replay on the same warm server
    prefix = os.path.join(TRACE_DIR, "trace_smoke")
    server.reset()
    server.engine.obs = Obs(ObsConfig(
        enabled=True, trace_path=prefix, flight_n=8,
        flight_path=prefix + ".flight",
        prometheus_path=prefix + ".prom"))
    t_on = _serve(server, stream)
    paths = server.engine.obs.export(server.telemetry.snapshot())
    server.engine.obs.close()

    budget = t_off * (1.0 + OVERHEAD_FRAC) + OVERHEAD_FLOOR_S
    overhead = t_on / max(t_off, 1e-12) - 1.0
    print(f"# untraced p50 {1e3 * t_off:.2f} ms, traced p50 "
          f"{1e3 * t_on:.2f} ms ({overhead:+.1%}; gate: "
          f"<= {OVERHEAD_FRAC:.0%} + {1e3 * OVERHEAD_FLOOR_S:.0f} ms floor)")
    if t_on > budget:
        raise SystemExit(
            f"tracing overhead regressed: traced median {1e3 * t_on:.2f} ms"
            f" vs untraced {1e3 * t_off:.2f} ms (budget {1e3 * budget:.2f})")

    # exported JSONL must pass the span schema and cover the pipeline
    errors = validate_jsonl(paths["trace_jsonl"])
    if errors:
        raise SystemExit(f"trace schema violations: {errors[:5]}")
    events = read_jsonl(paths["trace_jsonl"])
    span_names = {ev["name"] for ev in events if ev["ph"] == "X"}
    stages = {n.split("/", 1)[1] for n in span_names
              if n.startswith("engine/")}
    missing = EXPECTED_STAGES - stages
    if missing:
        raise SystemExit(f"trace is missing engine stages: {sorted(missing)}"
                         f" (saw {sorted(stages)})")
    with open(paths["trace_chrome"]) as f:
        doc = json.load(f)
    if not isinstance(doc.get("traceEvents"), list) or not doc["traceEvents"]:
        raise SystemExit("chrome trace twin has no traceEvents list")

    # the Prometheus artifact must pass the text-exposition checks —
    # both the exported file and what a live ``/metrics`` endpoint
    # actually serves over HTTP (same renderer, but the round trip pins
    # content-type and byte-level framing too)
    with open(paths["prometheus"]) as f:
        expo_errors = validate_exposition(f.read())
    if expo_errors:
        raise SystemExit(f"prometheus exposition violations: "
                         f"{expo_errors[:5]}")
    from urllib.request import urlopen

    from repro.obs import OpsServer
    ops = OpsServer(snapshot=server.telemetry.snapshot).start()
    try:
        with urlopen(f"{ops.url}/metrics", timeout=10) as resp:
            ctype = resp.headers.get("Content-Type", "")
            served = resp.read().decode("utf-8")
    finally:
        ops.close()
    if not ctype.startswith("text/plain"):
        raise SystemExit(f"/metrics content-type not text/plain: {ctype}")
    expo_errors = validate_exposition(served)
    if expo_errors:
        raise SystemExit(f"served /metrics exposition violations: "
                         f"{expo_errors[:5]}")
    n_samples = sum(1 for ln in served.splitlines()
                    if ln and not ln.startswith("#"))
    print(f"# prometheus exposition ok: file + /metrics "
          f"({n_samples} samples served)")

    # a triggered flight dump is itself a valid trace
    dump = server.engine.obs.flight_dump(reason="trace_smoke")
    if dump is None or validate_jsonl(dump):
        raise SystemExit(f"flight dump invalid: {dump}")
    steps_kept = len(server.engine.obs.flight.steps())
    print(f"# trace: {len(events)} events, {len(span_names)} span names, "
          f"flight ring kept {steps_kept} steps -> {dump}")

    # traced flash-crowd through the threaded runtime: the committed
    # Perfetto artifact; must show BOTH runtime threads in the stream
    rows = [BenchRow("trace/overhead_frac", 1e6 * (t_on - t_off),
                     f"untraced_ms={1e3 * t_off:.2f};"
                     f"traced_ms={1e3 * t_on:.2f};"
                     f"overhead={overhead:.3f};gate=0.05;"
                     f"events={len(events)}")]
    rows.append(_flash_crowd_artifact())
    write_json(rows, "trace_smoke")
    return rows


def _flash_crowd_artifact() -> BenchRow:
    from repro.runtime import ServingRuntime, VirtualClock, build_workload, \
        flash_crowd

    wl = build_workload(flash_crowd(rate=2500.0, tick_s=0.01, n_ticks=10,
                                    n_vertices=128, seed=3), u_max=256)
    cfg = IGPMConfig(n_max=wl.graph.n_max, e_max=wl.graph.e_max, ell_width=8,
                     rwr_iters=6, rwr_iters_incremental=2, top_k_patterns=4,
                     init_community_size=32)
    server = MatchServer(cfg, query_zoo(2),
                         ServingConfig(microbatch_window=64), seed=0)
    prefix = os.path.join(TRACE_DIR, "flash_crowd")
    rt = ServingRuntime(
        server,
        RuntimeConfig(ingress="lockstep",
                      obs=ObsConfig(enabled=True, trace_path=prefix,
                                    flight_n=16,
                                    flight_path=prefix + ".flight")),
        clock=VirtualClock())
    stats = rt.serve(wl)
    paths = server.obs.export(server.telemetry.snapshot())
    server.obs.close()
    errors = validate_jsonl(paths["trace_jsonl"])
    if errors:
        raise SystemExit(f"flash-crowd trace violations: {errors[:5]}")
    events = read_jsonl(paths["trace_jsonl"])
    cats = {ev.get("cat") for ev in events}
    if not {"ingress", "executor"} <= cats:
        raise SystemExit(f"runtime trace is missing a thread's spans "
                         f"(categories: {sorted(c for c in cats if c)})")
    tids = {ev["tid"] for ev in events if ev.get("cat") == "engine"} | \
        {ev["tid"] for ev in events if ev.get("cat") == "ingress"}
    print(f"# flash_crowd artifact: {len(events)} events over "
          f"{len({ev['tid'] for ev in events})} threads, "
          f"{len(stats)} steps -> {paths['trace_chrome']}")
    assert len(tids) >= 2, "ingress and engine spans share one tid"
    snap = server.telemetry.snapshot()
    return BenchRow(
        "trace/flash_crowd_artifact", 1e3 * snap.get("p50_stage_rwr_ms", 0.0),
        f"events={len(events)};threads={len({e['tid'] for e in events})};"
        f"steps={len(stats)};"
        f"stage_channels="
        f"{sum(1 for k in snap if k.startswith('p50_stage_'))}")


if __name__ == "__main__":
    use_compile_cache()
    for r in run():
        print(r.csv())
