"""Serving bench — bank-size sweep for the shared-sweep amortization claim,
plus the sync-vs-async runtime tail-latency table.

One MatchServer serves banks of 1/4/16 standing queries against the same
churn-capable update stream, then bank64/256/1024 rows pin the
thousand-query claim: exact-duplicate dedup plus the shared sub-pattern
DAG (DESIGN.md §7) keep device work at the distinct-signature count, so
per-query cost at bank1024 lands ≥3x below the bank64 linear
extrapolation. The measured quantity is the full serving-
step latency (queue drain → update apply + ELL refresh → PEM → sweeps →
bank match → store merge; median over measured steps, after a warm compile
pass) — the p50/p99 latency a serving deployment quotes. The claim pinned
by the acceptance criterion (and tests/test_serving.py): a 16-query bank
completes a step in well under 16× — target < 6× — the single-query step
time, because everything except the per-query expansion sweeps (update
application, mirror refresh, batch packing, PEM cut, induced extraction,
label RWR, DQN feedback) is paid once per step regardless of bank size,
and the expansion sweeps themselves run as shared (n, P·k) dense blocks.

The ``runtime/{sync,async}/flash_crowd`` rows replay ONE seeded
flash-crowd workload (hotspot bursts, wall-clock paced, queue bound tight
enough that back-pressure engages) through the single-threaded reference
driver and through the threaded ``ServingRuntime`` (DESIGN.md §6), and
report open-loop end-to-end latency percentiles (nominal arrival → delta
fan-out) plus the shed-traffic counters. The gate pinned by the PR-5
acceptance criterion: async p99 e2e ≤ sync p99 e2e with drops observed.

  PYTHONPATH=src:. python benchmarks/serving_bench.py [--smoke]

Writes ``benchmarks/out/serving_bench.json`` and refreshes the top-level
``BENCH_SUMMARY.json`` (default-scale runs only).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional

import numpy as np

from benchmarks.common import BenchRow, write_json
from repro.config.base import IGPMConfig, ServingConfig
from repro.launch.compile_cache import use_compile_cache
from repro.core.query import query_zoo
from repro.data.temporal import TemporalGraphSpec, generate_stream
from repro.serving import MatchServer

BANK_SIZES = (1, 4, 16)
# thousand-query scaling rows (PR-6): the zoo cycles 16 distinct query
# signatures, so exact-duplicate dedup + the shared sub-pattern DAG keep
# the device bank at ≤16 rows no matter how many standing queries alias
# them — step cost tracks DISTINCT sub-patterns, not bank size
BANK_SCALE = (64, 256, 1024)


def _spec(smoke: bool, scale: float) -> TemporalGraphSpec:
    n = max(64, int((256 if smoke else 1024) * scale))
    return TemporalGraphSpec("serving", "sparse_dense", n_vertices=n,
                             n_edges=max(256, 8 * n), n_steps=64, seed=11,
                             churn=0.25)


def _cfg(spec: TemporalGraphSpec, smoke: bool) -> IGPMConfig:
    return IGPMConfig(
        n_max=spec.n_vertices, e_max=int(2.4 * spec.n_edges) + 4096,
        ell_width=8 if smoke else 16,
        rwr_iters=8 if smoke else 15, rwr_iters_incremental=3,
        top_k_patterns=6 if smoke else 10, init_community_size=32)


def _median_step_s(server: MatchServer, stream, warm: bool) -> float:
    """Median full serving-step latency (drain → merge; median is robust
    to GC/scheduler stragglers on the shared CI container)."""
    if warm:  # compile pass over an identical stream, SAME server instance
        g = stream.graph
        for upd in stream.updates:
            server.submit_update(upd)
            g, _ = server.step(g)
        server.reset()
    g = stream.graph
    totals = []
    for upd in stream.updates:
        server.submit_update(upd)
        g, st = server.step(g)
        totals.append(st.total_s)
    return float(np.median(totals))


def _stage_breakdown(server: MatchServer, stream) -> tuple:
    """Traced replay on the warm server: per-stage p50 wall times.

    The timing rows above measure UNTRACED steps (tracing's extra
    ``block_until_ready`` fences are real overhead, DESIGN.md §8); this
    extra pass swaps a tracing :class:`~repro.obs.Obs` onto the warm
    engine and replays the same stream, so ``stage_*`` telemetry channels
    fill and the row can name where the step time goes — in particular
    the host-side ``_merge`` alias fan-out share at bank1024 (ROADMAP).
    Returns ``(traced p50 step ms, {stage: p50 ms})``.
    """
    from repro.config.base import ObsConfig
    from repro.obs import Obs

    server.reset()
    server.engine.obs = Obs(ObsConfig(enabled=True))
    g = stream.graph
    for upd in stream.updates:
        server.submit_update(upd)
        g, _ = server.step(g)
    server.engine.obs.close()
    snap = server.telemetry.snapshot()
    stages = {k[len("p50_stage_"):-len("_ms")]: v
              for k, v in snap.items()
              if k.startswith("p50_stage_") and k.endswith("_ms")}
    return snap["p50_step_ms"], stages


def _stage_fields(t_step_ms: float, stages: dict) -> str:
    """Derived-column cells for a stage breakdown (``|``-joined inside one
    ``;``-separated field so row parsing stays ``k=v;k=v``)."""
    cells = "|".join(f"{k}:{v:.2f}" for k, v in sorted(stages.items()))
    merge_share = stages.get("merge", 0.0) / max(t_step_ms, 1e-9)
    return (f"traced_p50_ms={t_step_ms:.1f};stage_p50_ms={cells};"
            f"merge_share={merge_share:.3f}")


def _runtime_rows(smoke: bool) -> List[BenchRow]:
    """Sync vs async tail latency under the flash-crowd hotspot scenario,
    back-pressure engaged (module docstring)."""
    from repro.config.base import RuntimeConfig
    from repro.runtime import (ServingRuntime, VirtualClock, WallClock,
                               build_workload, flash_crowd,
                               run_workload_sync)

    # a sustained flash crowd well past the container's service rate: the
    # closed-loop sync baseline (the pre-runtime MatchServer loop, which
    # only sees arrivals between the backlogs it chose to process) piles
    # up pacing lag the queue bound cannot shed, while the bounded
    # drivers — the async runtime, and the open-loop single-thread
    # reference `sync_shed` — shed at the 512-event queue so served
    # events stay fresh. Back-pressure (drops) engages for all three.
    sc = flash_crowd(
        rate=1_500.0 if smoke else 800.0, tick_s=0.05,
        n_ticks=24 if smoke else 40, n_vertices=256 if smoke else 1024,
        burst_amplitude=8.0, burst_period=10, burst_len=3, seed=11)
    wl = build_workload(sc, u_max=512)
    cfg = IGPMConfig(
        n_max=wl.graph.n_max, e_max=wl.graph.e_max,
        ell_width=8 if smoke else 16,
        rwr_iters=8 if smoke else 15, rwr_iters_incremental=3,
        top_k_patterns=6 if smoke else 10, init_community_size=32)
    # full_graph_frac < 0 forces the storm (full-graph) pipeline on every
    # step: the hotspot bursts would trip it most steps anyway, and one
    # compiled shape keeps mid-run induced-bucket compilations (10+ s
    # stalls the warm pass cannot cover, since merged-batch composition
    # is timing-dependent) out of the latency measurement. The 256-event
    # queue bound (one micro-batch) is what the burst ticks overflow.
    serving = ServingConfig(microbatch_window=256, queue_depth=256,
                            telemetry_window=4096, full_graph_frac=-1.0)

    rows: List[BenchRow] = []
    for label in ("sync", "sync_shed", "async"):
        server = MatchServer(cfg, query_zoo(4), serving, seed=0)
        # warm/compile pass: identical workload, virtual time (no pacing)
        run_workload_sync(server, wl, clock=VirtualClock())
        server.reset()
        if label == "sync":
            _, stats = run_workload_sync(server, wl, clock=WallClock(),
                                         ingest="closed")
        elif label == "sync_shed":
            _, stats = run_workload_sync(server, wl, clock=WallClock(),
                                         ingest="open")
        else:
            rt = ServingRuntime(server, RuntimeConfig(ingress="shed"),
                                clock=WallClock())
            stats = rt.serve(wl)
        snap = server.telemetry.snapshot()
        rows.append(BenchRow(
            f"runtime/{label}/flash_crowd",
            1e3 * snap.get("p99_e2e_ms", 0.0),  # row value: p99 e2e in µs
            f"p50_e2e_ms={snap.get('p50_e2e_ms', 0):.1f};"
            f"p99_e2e_ms={snap.get('p99_e2e_ms', 0):.1f};"
            f"p999_e2e_ms={snap.get('p999_e2e_ms', 0):.1f};"
            f"p99_queue_wait_ms={snap.get('p99_queue_wait_ms', 0):.1f};"
            f"p99_assembly_ms={snap.get('p99_assembly_ms', 0):.2f};"
            f"p50_step_ms={snap['p50_step_ms']:.1f};"
            f"p99_step_ms={snap['p99_step_ms']:.1f};"
            f"steps={snap['steps']};"
            f"events={sum(s.n_events for s in stats)};"
            f"dropped={snap['dropped_events']};"
            f"evicted={snap['evicted_events']};"
            f"rejected={snap['rejected_events']}"))
    return rows


def _control_rows(smoke: bool) -> List[BenchRow]:
    """Learned controller vs the static knob grid on closed-loop goodput
    (DESIGN.md §9).

    Each scenario (flash crowd, diurnal ramp) runs closed-loop —
    arrivals throttle on delivered lag, goodput counts events acked
    within the SLO — under a ``VirtualClock`` driven by the calibrated
    deterministic service-time model (``sim_service_model``: fixed
    per-step cost + per-event cost, constants fitted from wall-clock
    measurements of THIS bench config on the committed container).
    Wall-clock closed loops were tried first and rejected for the gate:
    on a 2-core CI container the service capacity wobbles enough
    between runs that corner scores moved by hundreds of events/s,
    swamping the adaptivity margin — under the model, every score below
    is a pure function of the seeds, reproducible across runs and
    machines. (Real wall-clock serving speed is still measured, by the
    ``runtime/*`` rows above.)

    The static grid covers the corners of the controller's own knob
    ladders (micro-batch window × shed threshold), so ``static_best``
    is the best fixed corner RuntimeConfig with hindsight; the
    controller's interior ladder rungs and its per-phase switching are
    exactly what a fixed config cannot do. ``learned`` trains the DQN
    controller on episodes of the same workload (ε decaying over
    training), snapshots the policy every few episodes, and reports the
    best snapshot under frozen pure-greedy inference — early stopping
    against the deterministic evaluation. The score (the row value) is
    demand-accounted goodput per second: ``(good − w·viol − dropped −
    throttled) / duration``. Throttled demand — arrivals clients held
    back because delivered lag was high — counts as lost goodput
    alongside sheds; without that term a config that lags so badly
    clients stop sending would "win" by never being offered anything
    to drop. Loads are calibrated so the mean offered rate sits below
    the window=256 modeled capacity (calm phases are feasible) while
    bursts/peaks overload it severalfold: shallow shed thresholds
    forfeit calm lumps, deep ones queue bursts into SLO violations, and
    the profitable operating point moves with the phase. The gate in
    main(): learned > static_best on BOTH scenarios at default scale —
    adaptivity must beat every fixed corner configuration, not tie the
    best one. The engine baseline runs residual-adaptive sweeps
    (``rwr_tol=1e-4``, a ``tol_ladder`` rung): at ``rwr_tol=0`` the
    ControllerEnv self-disables the tol knob (exact fixed-iteration
    sweeps would silently change semantics mid-run), which used to
    leave this bench's controller a 5-action space — the full 7-action
    space needs a non-zero baseline tol.
    """
    from repro.config.base import ControlConfig
    from repro.control import ServingController
    from repro.runtime import (VirtualClock, build_workload, diurnal,
                               flash_crowd, run_closed_loop,
                               run_workload_sync, sim_service_model)
    from repro.runtime.runtime import AckLedger, RuntimeKnobs

    # Loads calibrated on the committed container: window=256 service
    # capacity at n=512 is ~800 events/s (window=32 only ~185 — the
    # per-batch overhead corner), so calm rates keep mean load ~0.8×
    # capacity with lumps (rate·tick_s) ABOVE the shallow depth corner,
    # and bursts/peaks overload 2–5×.
    n = 256 if smoke else 512
    ticks = 10 if smoke else 20
    episodes = 2 if smoke else 24
    scs = {
        "flash_crowd": flash_crowd(
            rate=350.0, tick_s=0.3, n_ticks=ticks, n_vertices=n,
            burst_amplitude=5.0, burst_period=10, burst_len=2,
            seed=11, closed_loop=True, lag_ref_s=0.5, ack_slo_s=0.5),
        "diurnal": diurnal(
            rate=1_100.0, tick_s=0.3, n_ticks=ticks, n_vertices=n,
            seed=11, closed_loop=True, lag_ref_s=0.5, ack_slo_s=0.5),
    }
    viol_w = ControlConfig().viol_weight
    model = sim_service_model()  # calibrated constants; see its docstring

    def score(server, ledger, duration_s):
        tel = server.telemetry
        return (ledger.n_good - viol_w * ledger.n_viol - tel.n_dropped
                - ledger.closed_src.n_throttled) / max(duration_s, 1e-9)

    rows: List[BenchRow] = []
    for name, sc in scs.items():
        wl = build_workload(sc, u_max=512)
        cfg = IGPMConfig(
            n_max=wl.graph.n_max, e_max=wl.graph.e_max,
            ell_width=8 if smoke else 16,
            rwr_iters=8 if smoke else 15, rwr_iters_incremental=3,
            # non-zero baseline tol (a tol_ladder rung) keeps the
            # controller's rwr_tol actions live — see the docstring
            rwr_tol=1e-4,
            top_k_patterns=6 if smoke else 10, init_community_size=32)
        serving = ServingConfig(microbatch_window=256, queue_depth=512,
                                telemetry_window=4096, full_graph_frac=-1.0)

        def fresh():
            server = MatchServer(cfg, query_zoo(4), serving, seed=0)
            run_workload_sync(server, wl, clock=VirtualClock())  # warm
            server.reset()
            return server

        # static grid: the corners of the controller's knob ladders
        best = None
        for window in (32, 256):
            for depth in (64, 512):
                server = fresh()
                knobs = RuntimeKnobs(server)
                knobs.set_window(window)
                knobs.set_queue_depth(depth)
                _, _, led = run_closed_loop(server, wl,
                                            clock=VirtualClock(),
                                            knobs=knobs,
                                            service_model=model)
                s = score(server, led, sc.duration_s)
                if best is None or s > best[0]:
                    best = (s, window, depth, led.summary(sc.duration_s),
                            server.telemetry.n_dropped,
                            led.closed_src.n_throttled)
        s_best, b_win, b_depth, b_sum, b_drop, b_thr = best
        rows.append(BenchRow(
            f"control/static_best/{name}", s_best,
            f"window={b_win};depth={b_depth};"
            f"goodput_eps={b_sum['goodput_eps']:.0f};"
            f"viol_eps={b_sum['viol_eps']:.0f};"
            f"viol_rate={b_sum['viol_rate']:.3f};"
            f"dropped={b_drop};throttled={b_thr};"
            f"grid=window(32|256)xdepth(64|512)"))

        # learned: train on simulated closed-loop episodes with ε decay
        # (decide every batch — ≈ one tick at these loads), snapshotting
        # the policy every few episodes; each snapshot is evaluated
        # FROZEN on the same deterministic sim and the best one is the
        # reported controller (standard early stopping — late-training
        # policies are not always the best ones, and every evaluation
        # here is exactly reproducible)
        server = fresh()
        knobs = RuntimeKnobs(server)
        ledger = AckLedger(slo_s=sc.ack_slo_s)
        ccfg = ControlConfig(mode="train", decide_every=1)
        ccfg = dataclasses.replace(
            ccfg, dqn=dataclasses.replace(
                ccfg.dqn, epsilon=0.3, epsilon_final=0.05,
                epsilon_decay_steps=300, gamma=0.9))
        ctl = ServingController(server, knobs, ledger, ccfg)
        frozen_cfg = dataclasses.replace(ccfg, mode="frozen")
        best = None
        for ep in range(episodes):
            run_closed_loop(server, wl, clock=VirtualClock(),
                            controller=ctl, knobs=knobs, ledger=ledger,
                            service_model=model)
            server.reset()
            if (ep + 1) % 4 and ep != episodes - 1:
                continue
            # frozen evaluation of this snapshot (deterministic)
            sd = ctl.state_dict()
            ev = ServingController(server, knobs, ledger, frozen_cfg)
            ev.load_state_dict(sd)
            ledger.reset()
            _, _, led = run_closed_loop(server, wl, clock=VirtualClock(),
                                        controller=ev, knobs=knobs,
                                        ledger=ledger, service_model=model)
            s = score(server, led, sc.duration_s)
            if best is None or s > best[0]:
                best = (s, led.summary(sc.duration_s),
                        server.telemetry.n_dropped,
                        led.closed_src.n_throttled,
                        knobs.window, knobs.queue_depth, ep + 1)
            server.reset()
            ledger.reset()
        s_learned, l_sum, l_drop, l_thr, l_win, l_depth, l_ep = best
        rows.append(BenchRow(
            f"control/learned/{name}", s_learned,
            f"episodes={episodes};best_snapshot_ep={l_ep};"
            f"decisions={ctl.n_decisions};"
            f"goodput_eps={l_sum['goodput_eps']:.0f};"
            f"viol_eps={l_sum['viol_eps']:.0f};"
            f"viol_rate={l_sum['viol_rate']:.3f};"
            f"dropped={l_drop};throttled={l_thr};"
            f"final_window={l_win};final_depth={l_depth}"))
    return rows


def _freshness_rows(smoke: bool) -> List[BenchRow]:
    """Per-query staleness under the deterministic closed loop
    (DESIGN.md §11).

    One seeded flash-crowd workload replays through ``run_closed_loop``
    under a ``VirtualClock`` + the calibrated ``sim_service_model`` with
    a :class:`~repro.obs.freshness.FreshnessLedger` fed from the batch
    fan-out, at bank64 and bank256. Every staleness sample is a pure
    function of the seeds and the model — reproducible bit-for-bit
    across machines — so these rows are the deterministic anchor the
    regression sentinel (``benchmarks/regress.py``) gates hardest on.
    Row value: p99 per-completion worst-query staleness in µs.
    """
    from repro.obs.freshness import FreshnessLedger
    from repro.runtime import (VirtualClock, build_workload, flash_crowd,
                               run_closed_loop, run_workload_sync,
                               sim_service_model)

    n = 256 if smoke else 512
    ticks = 10 if smoke else 20
    sc = flash_crowd(
        rate=350.0, tick_s=0.3, n_ticks=ticks, n_vertices=n,
        burst_amplitude=5.0, burst_period=10, burst_len=2,
        seed=11, closed_loop=True, lag_ref_s=0.5, ack_slo_s=0.5)
    wl = build_workload(sc, u_max=512)
    model = sim_service_model()
    rows: List[BenchRow] = []
    for bank in (64, 256):
        cfg = IGPMConfig(
            n_max=wl.graph.n_max, e_max=wl.graph.e_max,
            ell_width=8 if smoke else 16,
            rwr_iters=8 if smoke else 15, rwr_iters_incremental=3,
            top_k_patterns=6 if smoke else 10, init_community_size=32)
        serving = ServingConfig(microbatch_window=256, queue_depth=512,
                                telemetry_window=4096, full_graph_frac=-1.0)
        server = MatchServer(cfg, query_zoo(bank), serving, seed=0)
        run_workload_sync(server, wl, clock=VirtualClock())  # warm/compile
        server.reset()
        fresh = FreshnessLedger.from_engine(server.engine,
                                            slo_s=sc.ack_slo_s)
        clock = VirtualClock()
        run_closed_loop(server, wl, clock=clock, service_model=model,
                        freshness=fresh)
        end = clock.now()
        tel = server.telemetry
        p50 = tel.latency_percentile(50, "freshness_staleness")
        p99 = tel.latency_percentile(99, "freshness_staleness")
        per_q = fresh.snapshot(end)
        counters = fresh.counters()
        rows.append(BenchRow(
            f"freshness/bank{bank}/flash_crowd", 1e6 * p99,
            f"p50_stal_ms={1e3 * p50:.1f};p99_stal_ms={1e3 * p99:.1f};"
            f"queries={counters['freshness_queries']};"
            f"groups={counters['freshness_groups']};"
            f"breaches={counters['freshness_breaches']};"
            f"completions={tel.channel_count('freshness_staleness')};"
            f"worst_burn_slow={max((r.burn_slow for r in per_q), default=0.0):.3f};"
            f"slo_ms={1e3 * sc.ack_slo_s:.0f}"))
    return rows


def run(smoke: bool = False, scale: float = 1.0,
        steps: Optional[int] = None) -> List[BenchRow]:
    spec = _spec(smoke, scale)
    cfg = _cfg(spec, smoke)
    n_steps = steps or (3 if smoke else 8)
    serving = ServingConfig(microbatch_window=256)

    rows: List[BenchRow] = []

    # bank size 1 = separate single-query serving. The query population is
    # the zoo (4 shapes × label variants); per-query cost is shape-
    # determined, so serve each distinct shape alone and report the mean —
    # that mean × B is what B separate matchers would cost per step.
    singles = []
    for q in query_zoo(4):
        server = MatchServer(cfg, [q], serving, seed=0)
        stream = generate_stream(spec, n_measured_steps=n_steps, u_max=256)
        t = _median_step_s(server, stream, warm=True)
        singles.append(t)
        rows.append(BenchRow(f"serving/single/{q.name}", 1e6 * t,
                             "single-query server"))
    t_single = float(np.mean(singles))
    rows.append(BenchRow("serving/bank1", 1e6 * t_single,
                         "per_query_ms={:.2f};ratio_vs_bank1=1.00;"
                         "mean over the 4 query shapes served alone".format(
                             1e3 * t_single)))

    for bank in BANK_SIZES[1:]:
        server = MatchServer(cfg, query_zoo(bank), serving, seed=0)
        stream = generate_stream(spec, n_measured_steps=n_steps, u_max=256)
        t = _median_step_s(server, stream, warm=True)
        ratio = t / t_single
        snap = server.telemetry.snapshot()
        rows.append(BenchRow(
            f"serving/bank{bank}", 1e6 * t,
            f"per_query_ms={1e3 * t / bank:.2f};ratio_vs_bank1={ratio:.2f};"
            f"p99_ms={snap['p99_step_ms']:.1f};"
            f"updates_per_s={snap['updates_per_s']:.0f};"
            f"recompute_frac={snap['recompute_frac']:.2f}"))

    # bank-scale sweep (PR-6 acceptance): thousand-query serving under
    # exact-duplicate dedup and the shared sub-pattern DAG. All of these
    # banks collapse to the same 16 distinct device rows (the zoo's
    # signature period), so the absolute step time barely moves while the
    # per-query cost falls ~linearly in the alias count. The gate in
    # main(): per-query cost at bank1024 must sit ≥3x below the linear
    # extrapolation from bank64.
    for bank in BANK_SCALE:
        server = MatchServer(cfg, query_zoo(bank), serving, seed=0)
        stream = generate_stream(spec, n_measured_steps=n_steps, u_max=256)
        t = _median_step_s(server, stream, warm=True)
        snap = server.telemetry.snapshot()
        t_traced, stages = _stage_breakdown(server, stream)
        rows.append(BenchRow(
            f"serving/bank{bank}", 1e6 * t,
            f"per_query_ms={1e3 * t / bank:.4f};"
            f"bank_rows={snap.get('bank_rows', 0)};"
            f"dag_nodes={snap.get('dag_nodes', 0)};"
            f"n_dedup={snap.get('n_dedup', 0)};"
            f"standing_queries={snap.get('standing_queries', 0)};"
            f"p99_ms={snap['p99_step_ms']:.1f};"
            + _stage_fields(t_traced, stages)))

    # prefix-sharing population (ROADMAP): heavy BFS-prefix overlap with
    # ZERO exact duplication — one 7-vertex anchor-label family whose
    # variants diverge in tail attachment and closure edges, so the
    # shared sub-pattern DAG (not the exact-dup alias path) carries the
    # whole collapse. dag_nodes vs unshared_nodes is the measured ratio.
    from repro.core.query import decompose, prefix_zoo
    for bank in ((16, 64) if smoke else (64, 256)):
        qs = prefix_zoo(bank)
        server = MatchServer(cfg, qs, serving, seed=0)
        stream = generate_stream(spec, n_measured_steps=n_steps, u_max=256)
        t = _median_step_s(server, stream, warm=True)
        snap = server.telemetry.snapshot()
        unshared = sum(len(decompose(q)) for q in qs)
        dag_nodes = snap.get("dag_nodes", 0)
        t_traced, stages = _stage_breakdown(server, stream)
        rows.append(BenchRow(
            f"serving/prefix{bank}", 1e6 * t,
            f"per_query_ms={1e3 * t / bank:.4f};"
            f"bank_rows={snap.get('bank_rows', 0)};"
            f"dag_nodes={dag_nodes};unshared_nodes={unshared};"
            f"dag_sharing={unshared / max(dag_nodes, 1):.1f};"
            f"n_dedup={snap.get('n_dedup', 0)};"
            f"p99_ms={snap['p99_step_ms']:.1f};"
            + _stage_fields(t_traced, stages)))

    # storm scenario: a hotspot stream (every step bursts into one hot
    # region) with the full-graph fallback forced (full_graph_frac < 0);
    # the staleness-keyed seed cache skips the per-storm-step (n, L)
    # label-RWR refresh and — because consecutive bursts touch the same
    # communities — the per-bucket seed top-k. This pair of rows pins its
    # p50/p99 effect (DESIGN.md §4)
    storm_spec = TemporalGraphSpec(
        "storm", "sparse_dense", n_vertices=spec.n_vertices,
        n_edges=spec.n_edges, n_steps=64, seed=11, hotspot=True,
        hotspot_period=1)
    for label, staleness in (("seedcache_off", 0), ("seedcache_on", 10 ** 6)):
        server = MatchServer(
            cfg, query_zoo(4),
            ServingConfig(microbatch_window=256, full_graph_frac=-1.0,
                          seed_cache_staleness=staleness), seed=0)
        stream = generate_stream(storm_spec, n_measured_steps=n_steps,
                                 u_max=256)
        t = _median_step_s(server, stream, warm=True)
        snap = server.telemetry.snapshot()
        rows.append(BenchRow(
            f"serving/storm/{label}", 1e6 * t,
            f"p50_ms={snap['p50_step_ms']:.1f};"
            f"p99_ms={snap['p99_step_ms']:.1f};"
            f"rlab_hits={snap.get('rlab_cache_hits', 0)};"
            f"rlab_misses={snap.get('rlab_cache_misses', 0)};"
            f"seed_hits={snap.get('seed_cache_hits', 0)}"))

    # residual-adaptive RWR vs the fixed sweep count: every storm step
    # refreshes r_lab from a warm start. 'fixed' pays the full rwr_iters
    # every refresh (the paper's fixed-30 semantics — the incremental
    # shortcut is disabled so the sweep count is honest, not assumed);
    # 'adaptive' runs lax.while_loop sweeps to ∞-norm tol 1e-4 under the
    # same cap. The rwr_sweeps telemetry records the sweeps actually run —
    # this pair pins the biggest per-step latency lever (label-RWR sweeps)
    for label, tol in (("fixed", 0.0), ("adaptive", 1e-4)):
        cfg_t = dataclasses.replace(cfg, rwr_tol=tol,
                                    rwr_iters_incremental=cfg.rwr_iters)
        server = MatchServer(
            cfg_t, query_zoo(4),
            ServingConfig(microbatch_window=256, full_graph_frac=-1.0),
            seed=0)
        stream = generate_stream(storm_spec, n_measured_steps=n_steps,
                                 u_max=256)
        t = _median_step_s(server, stream, warm=True)
        snap = server.telemetry.snapshot()
        rows.append(BenchRow(
            f"serving/adaptive_rwr/{label}", 1e6 * t,
            f"p50_ms={snap['p50_step_ms']:.1f};"
            f"p99_ms={snap['p99_step_ms']:.1f};"
            f"rwr_sweeps={snap.get('rwr_sweeps', 0)};"
            f"steps={snap['steps']}"))
    # any shrunk run (smoke, scaled, or step-capped) gets the smoke-sized
    # runtime comparison — the full-scale wall-clock section only belongs
    # in the default artifact run
    shrunk = smoke or scale != 1.0 or steps is not None
    rows.extend(_runtime_rows(shrunk))
    rows.extend(_control_rows(shrunk))
    rows.extend(_freshness_rows(shrunk))

    # smoke/scaled runs must not clobber the committed default-scale artifact
    default_run = not smoke and scale == 1.0 and steps is None
    write_json(rows, "serving_bench" if default_run else "serving_bench_smoke")
    if default_run:
        from benchmarks.collect import collect
        collect()
    return rows


def _check_control(rows: List[BenchRow], gate: bool) -> None:
    """Print the learned-vs-static closed-loop comparison; when ``gate``,
    fail unless the learned controller beats the best static config on
    every scenario (the PR-8 acceptance criterion)."""
    by_name = {r.name: r.us_per_call for r in rows}
    scenarios = sorted({n.rsplit("/", 1)[1] for n in by_name
                        if n.startswith("control/")})
    for sc in scenarios:
        learned = by_name[f"control/learned/{sc}"]
        static = by_name[f"control/static_best/{sc}"]
        print(f"# control/{sc}: learned score {learned:.0f}/s vs best "
              f"static {static:.0f}/s "
              f"({'beats' if learned > static else 'LOSES TO'} the grid)")
        if gate and learned <= static:
            raise SystemExit(
                f"learned controller lost to a static config on {sc}: "
                f"{learned:.0f}/s vs {static:.0f}/s (gate: learned > "
                f"every static knob-corner config)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny stream for CI (same code path)")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--control-only", action="store_true",
                    help="run ONLY the closed-loop controller rows "
                         "(make control-smoke; no summary artifact)")
    args = ap.parse_args()
    use_compile_cache()
    if args.control_only:
        rows = _control_rows(smoke=args.smoke)
        for r in rows:
            print(r.csv())
        _check_control(rows, gate=not args.smoke)
        return
    rows = run(smoke=args.smoke, scale=args.scale, steps=args.steps)
    for r in rows:
        print(r.csv())
    # the amortization claim the acceptance criterion pins — enforced, so
    # the CI serve-smoke run fails if shared-sweep amortization regresses
    by_name = {r.name: r.us_per_call for r in rows}
    ratio = by_name["serving/bank16"] / by_name["serving/bank1"]
    print(f"# bank16/bank1 step-time ratio: {ratio:.2f}x "
          f"(shared sweeps; 16 separate matchers would be ~16x)")
    if ratio >= 6.0:
        raise SystemExit(
            f"serving amortization regressed: bank16 costs {ratio:.2f}x a "
            f"single-query step (gate: < 6x)")
    # the PR-6 acceptance gate: per-query cost at bank1024 must beat the
    # linear extrapolation from bank64 by ≥3x — i.e. a thousand-query
    # bank must NOT cost 16x a 64-query bank, because dedup + the shared
    # sub-pattern DAG pin device work to the distinct-signature count.
    pq64 = by_name["serving/bank64"] / 64
    pq1024 = by_name["serving/bank1024"] / 1024
    scale_ratio = pq64 / max(pq1024, 1e-12)
    print(f"# bank64→bank1024 per-query amortization: {scale_ratio:.1f}x "
          f"below linear extrapolation (gate: >= 3x)")
    if scale_ratio < 3.0:
        raise SystemExit(
            f"bank-scale amortization regressed: per-query cost at "
            f"bank1024 is only {scale_ratio:.2f}x below the bank64 linear "
            f"extrapolation (gate: >= 3x)")
    # the observability deliverable (DESIGN.md §8): say out loud where
    # the thousand-query step time goes — the host-side `_merge` alias
    # fan-out is the ROADMAP suspect for the bank1024 step-time growth
    b1024 = next(r for r in rows if r.name == "serving/bank1024")
    kv = dict(p.split("=") for p in b1024.derived.split(";") if "=" in p)
    stages = dict(c.split(":") for c in kv["stage_p50_ms"].split("|"))
    print(f"# bank1024 traced stage p50 breakdown (ms): "
          + " ".join(f"{k}={v}" for k, v in sorted(stages.items())))
    print(f"# bank1024 _merge/alias-fan-out share of traced step: "
          f"{float(kv['merge_share']):.1%} "
          f"({stages.get('merge', '?')} ms of {kv['traced_p50_ms']} ms)")
    ad_ratio = (by_name["serving/adaptive_rwr/adaptive"]
                / by_name["serving/adaptive_rwr/fixed"])
    print(f"# adaptive/fixed warm-storm step-time ratio: {ad_ratio:.2f}x "
          f"(residual-adaptive label-RWR vs the full fixed sweep count)")
    # the latency gate binds only at full scale: smoke graphs are too
    # small for the label-RWR sweeps to dominate the step, so the saved
    # sweeps (still visible in the rwr_sweeps column) drown in noise
    if not args.smoke and ad_ratio >= 1.0:
        raise SystemExit(
            f"residual-adaptive RWR regressed: adaptive warm-storm steps "
            f"cost {ad_ratio:.2f}x the fixed-count steps (gate: < 1.0x)")
    # the PR-5 acceptance gate: under the flash-crowd hotspot scenario
    # the async runtime's p99 end-to-end latency must not exceed the sync
    # MatchServer path's (the closed-loop serving loop the repo had
    # before the runtime), and back-pressure must actually have engaged
    # in both (otherwise the comparison measured an idle queue, not
    # serving). The open-loop single-thread `sync_shed` row is the
    # honesty reference: how much of the win is bounded-staleness
    # shedding vs ingress/execution overlap (EXPERIMENTS.md discusses the
    # 2-core-container split). Smoke graphs are too small/noisy for a
    # latency gate — smoke runs still exercise all three paths.
    sync_p99 = by_name["runtime/sync/flash_crowd"]
    async_p99 = by_name["runtime/async/flash_crowd"]
    rt_ratio = async_p99 / max(sync_p99, 1e-9)
    print(f"# async/sync flash-crowd p99 e2e ratio: {rt_ratio:.2f}x "
          f"(threaded runtime vs the closed-loop sync serving loop; "
          f"sync_shed p99 {by_name['runtime/sync_shed/flash_crowd']/1e3:.0f}"
          f" ms is the open-loop single-thread reference)")
    if not args.smoke:
        dropped = {
            r.name: int(dict(kv.split("=") for kv in r.derived.split(";"))
                        ["dropped"])
            for r in rows if r.name.startswith("runtime/")}
        gated = {k: v for k, v in dropped.items() if "sync_shed" not in k}
        if not all(d > 0 for d in gated.values()):
            raise SystemExit(
                f"runtime bench back-pressure never engaged "
                f"(dropped={dropped}); raise the arrival rate")
        if async_p99 > sync_p99:
            raise SystemExit(
                f"async runtime tail latency regressed: p99 e2e "
                f"{async_p99/1e3:.1f} ms vs sync {sync_p99/1e3:.1f} ms "
                f"(gate: async <= sync)")
    # the PR-8 acceptance gate (full scale only; smoke still runs the
    # closed-loop code path but tiny graphs make the scores noise)
    _check_control(rows, gate=not args.smoke)


if __name__ == "__main__":
    main()
