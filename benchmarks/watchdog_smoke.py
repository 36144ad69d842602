"""Watchdog smoke — the stall-injection gate for the health monitor
(DESIGN.md §11).

Runs a short wall-clock flash-crowd workload through the threaded
``ServingRuntime`` with the full observability stack live (flight
recorder, freshness ledger, watchdog thread, ops HTTP server), wedges
the executor mid-run — the 3rd device batch sleeps far past
``stall_after_s`` — and asserts, over real HTTP against the ephemeral
ops port, the incident contract:

- ``/health`` flips to ``stalled`` (HTTP 503) while the executor is
  wedged, with a ``stall`` alarm naming the executor heartbeat;
- the watchdog triggers a flight-recorder dump the moment the stall
  alarm rises (the post-mortem exists before any human asks);
- ``/freshness`` serves per-query rows and ``/metrics`` parses while
  the runtime is unhealthy — the ops surface must outlive the incident;
- once the wedge releases, the run drains cleanly and the event ring
  holds exactly one ``stall`` transition (edge-triggered, not
  one-per-check).

  PYTHONPATH=src:. python benchmarks/watchdog_smoke.py

Exit status is the gate (``make obs-watchdog-smoke`` / CI).
"""

from __future__ import annotations

import glob
import json
import os
import time
from urllib.error import HTTPError
from urllib.request import urlopen

from benchmarks.common import OUT_DIR
from repro.config.base import (IGPMConfig, ObsConfig, RuntimeConfig,
                               ServingConfig)
from repro.core.query import query_zoo
from repro.launch.compile_cache import use_compile_cache
from repro.runtime import (ServingRuntime, VirtualClock, build_workload,
                           flash_crowd, run_workload_sync)
from repro.serving import MatchServer

STALL_S = 2.0          # how long the injected wedge holds the executor
STALL_AFTER_S = 0.4    # watchdog stall threshold (≪ STALL_S)
PERIOD_S = 0.05        # watchdog check cadence
POLL_DEADLINE_S = 15.0


def _get(url: str):
    """(status, parsed JSON body) — 503 bodies included."""
    try:
        with urlopen(url, timeout=5) as resp:
            return resp.status, json.loads(resp.read().decode("utf-8"))
    except HTTPError as e:
        return e.code, json.loads(e.read().decode("utf-8"))


def run() -> None:
    sc = flash_crowd(rate=300.0, tick_s=0.1, n_ticks=40, n_vertices=128,
                     seed=5)
    wl = build_workload(sc, u_max=256)
    cfg = IGPMConfig(n_max=wl.graph.n_max, e_max=wl.graph.e_max, ell_width=8,
                     rwr_iters=6, rwr_iters_incremental=2, top_k_patterns=4,
                     init_community_size=32)
    server = MatchServer(cfg, query_zoo(4),
                         ServingConfig(microbatch_window=64), seed=0)
    run_workload_sync(server, wl, clock=VirtualClock())  # warm/compile
    server.reset()

    flight_prefix = os.path.join(OUT_DIR, "traces", "watchdog_smoke.flight")
    for stale in glob.glob(flight_prefix + ".*.jsonl"):
        os.remove(stale)
    ocfg = ObsConfig(enabled=True, flight_n=8, flight_path=flight_prefix,
                     freshness=True, watchdog=True,
                     watchdog_period_s=PERIOD_S, stall_after_s=STALL_AFTER_S,
                     metrics_port=0)
    rt = ServingRuntime(server, RuntimeConfig(ingress="shed", obs=ocfg))

    # inject the wedge AFTER the warm pass: the 3rd executor batch sleeps
    # through many watchdog periods (a hung device step, as the monitor
    # sees it — the heartbeat at the loop top goes stale)
    orig = server.step_packed
    calls = {"n": 0}

    def wedged_step(g, upd, n_events):
        calls["n"] += 1
        if calls["n"] == 3:
            time.sleep(STALL_S)
        return orig(g, upd, n_events)

    server.step_packed = wedged_step

    rt.start(wl)
    url = rt.ops.url
    print(f"# ops surface live at {url}")

    saw_stalled = None
    deadline = time.monotonic() + POLL_DEADLINE_S
    while time.monotonic() < deadline:
        status, doc = _get(url + "/health")
        if doc["state"] == "stalled":
            saw_stalled = (status, doc)
            break
        time.sleep(PERIOD_S)
    if saw_stalled is None:
        rt.stop(drain=False)
        raise SystemExit(
            f"watchdog never reported the injected stall within "
            f"{POLL_DEADLINE_S}s (executor wedged {STALL_S}s, "
            f"stall_after_s={STALL_AFTER_S})")
    status, doc = saw_stalled
    if status != 503:
        raise SystemExit(f"/health served HTTP {status} while stalled "
                         f"(want 503): {doc}")
    stall = doc["alarms"].get("stall")
    if not stall or stall.get("thread") != "executor":
        raise SystemExit(f"stall alarm missing or misattributed: "
                         f"{doc['alarms']}")
    print(f"# /health -> 503 stalled: executor heartbeat age "
          f"{stall['age_s']:.2f}s (threshold {STALL_AFTER_S}s)")

    # the ops surface must keep serving during the incident
    status, fr = _get(url + "/freshness")
    if status != 200 or not fr["queries"]:
        raise SystemExit(f"/freshness unusable mid-incident: "
                         f"{status} {fr}")
    from repro.obs import validate_exposition
    with urlopen(url + "/metrics", timeout=5) as resp:
        errors = validate_exposition(resp.read().decode("utf-8"))
    if errors:
        raise SystemExit(f"/metrics exposition broke mid-incident: "
                         f"{errors[:3]}")
    print(f"# /freshness ({len(fr['queries'])} queries) and /metrics "
          f"stayed up through the stall")

    # drain; the wedge releases well before the workload ends
    if not rt.join(timeout=sc.duration_s + STALL_S
                   + rt.rcfg.drain_timeout_s):
        rt.stop(drain=False)
        raise SystemExit("runtime failed to drain after the wedge lifted")

    dumps = sorted(glob.glob(flight_prefix + ".*.jsonl"))
    if rt.health.n_dumps_triggered < 1 or not dumps:
        raise SystemExit(
            f"stall did not trigger a flight dump "
            f"(n_dumps_triggered={rt.health.n_dumps_triggered}, "
            f"files={dumps})")
    stall_events = [e for e in rt.health.events if e.kind == "stall"]
    if len(stall_events) != 1:
        raise SystemExit(
            f"expected exactly one edge-triggered stall event, got "
            f"{len(stall_events)} (the event ring must record "
            f"transitions, not state)")
    print(f"# flight dump on stall: {dumps[-1]} "
          f"(n_dumps_triggered={rt.health.n_dumps_triggered}); "
          f"{len(stall_events)} stall transition in the event ring; "
          f"{len(rt.stats)} steps served")


if __name__ == "__main__":
    use_compile_cache()
    run()
