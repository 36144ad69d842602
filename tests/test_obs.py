"""Observability tests (DESIGN.md §8).

Pins the acceptance contract of the tracing/flight/export stack:
  * zero-cost disabled — with ``ObsConfig()`` (the default) the engine
    emits NO spans, takes no extra device fences, registers no compile
    listener or GC callback, compiles exactly as many traces, and
    produces BITWISE the results of a build that never heard of tracing;
  * host-path spans and counters — a traced induced step nests its
    apply and extract children inside their parents, counts each device
    array it pulls once, and names compiles and collections as
    ``engine/compile`` / ``engine/gc`` spans, also in a profiler capture;
  * traced ≡ untraced — enabling tracing changes what is *recorded*,
    never what is computed: stores and deltas stay bitwise identical;
  * telemetry credibility — percentile keys are omitted (and strict
    queries return NaN) until a channel holds ``1/(1-q/100)`` samples;
    ring wraparound keeps percentiles over the last ``window`` only;
  * counter hygiene — free-form counters may not shadow snapshot
    built-ins or percentile-shaped keys (the old silent clobber);
  * flight recorder — bounded ring of the last N traced steps; dumps
    are schema-valid JSONL; triggered (SLO/crash) dumps de-duplicate;
  * cross-thread traces — in the lockstep async runtime one stream sees
    ingress spans and engine spans on different tids, with engine spans
    carrying BOTH the step id and the ingress-stamped batch id;
  * exporters — JSONL round-trips; the Chrome twin wraps the same
    events; Prometheus text skips non-numeric keys.
"""

import gc
import json
import math
import os

import numpy as np
import pytest

from repro.config.base import (IGPMConfig, ObsConfig, RuntimeConfig,
                               ServingConfig)
from repro.core.query import (decompose, prefix_zoo, query_signature,
                              query_zoo)
from repro.obs import (NULL_SPAN, NULL_TRACER, FlightRecorder, Obs,
                       StepScope, hooks_registered, read_jsonl, to_host,
                       validate_events, validate_exposition,
                       validate_jsonl, write_chrome, write_jsonl,
                       write_prometheus)
from repro.serving import MatchServer
from repro.serving.telemetry import (Telemetry, _Ring, percentile_min_count)


def _cfg(**kw):
    base = dict(n_max=128, e_max=8192, ell_width=8, rwr_iters=6,
                rwr_iters_incremental=2, top_k_patterns=4,
                init_community_size=32)
    base.update(kw)
    return IGPMConfig(**base)


def _server(obs=None, bank=2, **serving_kw):
    serving_kw.setdefault("microbatch_window", 64)
    serving = ServingConfig(obs=obs or ObsConfig(), **serving_kw)
    return MatchServer(_cfg(), query_zoo(bank), serving, seed=0)


def _stream(n_steps=4, seed=5):
    from repro.data.temporal import TemporalGraphSpec, generate_stream
    spec = TemporalGraphSpec("obs", "sparse_dense", n_vertices=128,
                             n_edges=512, n_steps=16, seed=seed, churn=0.25)
    return generate_stream(spec, n_measured_steps=n_steps, u_max=128)


def _run(server, stream):
    g = stream.graph
    outs = []
    for upd in stream.updates:
        server.submit_update(upd)
        g, st = server.step(g)
        outs.append(st)
    return outs


# -- telemetry: rings, credibility, collisions --------------------------------

def test_ring_wraparound_windows_percentiles():
    ring = _Ring(8)
    ring.extend(float(i) for i in range(20))
    # only the last 8 samples (12..19) are resident
    assert ring.count == 20
    assert ring.percentile(50) == pytest.approx(15.5)
    assert ring.percentile(0) == 12.0
    assert ring.percentile(100) == 19.0


def test_percentile_min_count():
    assert percentile_min_count(50) == 2
    assert percentile_min_count(99) == 100
    assert percentile_min_count(99.9) == 1000
    assert percentile_min_count(100) == 1


def test_percentile_credibility_strict_nan():
    ring = _Ring(2048)
    ring.extend([1.0] * 999)
    assert ring.credible(50) and ring.credible(99)
    assert not ring.credible(99.9)
    assert math.isnan(ring.percentile(99.9, strict=True))
    ring.add(1.0)
    assert ring.credible(99.9)
    assert ring.percentile(99.9, strict=True) == 1.0


def test_snapshot_omits_uncredible_percentiles():
    t = Telemetry(window=64)
    t.record_latency("e2e", *[0.001] * 99)
    snap = t.snapshot()
    assert "p50_e2e_ms" in snap           # 2 samples suffice
    assert "p99_e2e_ms" not in snap       # needs 100
    assert "p999_e2e_ms" not in snap      # needs 1000
    t.record_latency("e2e", 0.001)
    assert "p99_e2e_ms" in t.snapshot()
    # the step channel stays schema-stable even with zero samples
    fresh = Telemetry().snapshot()
    assert fresh["p50_step_ms"] == 0.0 and fresh["p99_step_ms"] == 0.0


def test_channel_windows_configurable():
    t = Telemetry(window=4, channel_windows={"assembly": 16})
    assert t.channel_window("assembly") == 16
    assert t.channel_window("e2e") == 4096      # wide default for tails
    assert t.channel_window("anything_else") == 4
    t.record_latency("assembly", *[float(i) for i in range(16)])
    # all 16 resident: window came from channel_windows, not the default
    assert t.latency_percentile(0, "assembly") == 0.0
    t.record_latency("narrow", *[float(i) for i in range(16)])
    assert t.latency_percentile(0, "narrow") == 12.0  # window 4 wrapped


def test_counter_collision_rejected():
    t = Telemetry()
    with pytest.raises(ValueError, match="reserved"):
        t.record_counters({"steps": 7})
    with pytest.raises(ValueError, match="reserved"):
        t.record_counters({"p99_e2e_ms": 1})
    t.record_counters({"seed_cache_hits": 3})
    assert t.snapshot()["seed_cache_hits"] == 3


# -- zero-cost disabled + traced-equals-untraced ------------------------------

def _outs_equal(a, b):
    assert len(a) == len(b)
    for sa, sb in zip(a, b):
        assert sa.deltas == sb.deltas
        assert sa.n_recompute == sb.n_recompute
        assert sa.n_events == sb.n_events
        assert sa.n_pruned == sb.n_pruned


def _hooks():
    from jax._src import monitoring
    return (hooks_registered(),
            list(monitoring.get_event_duration_listeners()),
            list(gc.callbacks))


@pytest.mark.slow
def test_tracing_disabled_is_noop_and_enabled_is_bitwise_equal():
    stream = _stream()
    hooks0 = _hooks()
    off = _server()
    outs_off = _run(off, stream)
    # disabled = the null tracer: zero spans, shared no-op span object,
    # no compile listener or GC callback, no stage or count records
    assert off.obs.tracer is NULL_TRACER
    assert off.obs.tracer.n_spans == 0
    assert off.obs.span("anything") is NULL_SPAN
    assert _hooks() == hooks0
    assert all(st.stage_s == {} and st.counts == {} for st in outs_off)

    on = _server(obs=ObsConfig(enabled=True))
    outs_on = _run(on, stream)
    assert on.obs.tracer.n_spans > 0
    # tracing changes what is recorded, never what is computed
    _outs_equal(outs_off, outs_on)
    for i in range(len(off.stores)):
        assert off.stores[i]._patterns == on.stores[i]._patterns
    # ...and never what is compiled: the extra fences sit outside jit
    assert off.engine.trace_count() == on.engine.trace_count()
    assert all(st.counts["d2h_bytes"] > 0 for st in outs_on)


@pytest.mark.slow
def test_stage_breakdown_populates_only_when_traced():
    stream = _stream(n_steps=2)
    off = _server()
    for st_off in _run(off, stream):
        pass
    assert all(not k.startswith("p50_stage_")
               for k in off.telemetry.snapshot())

    on = _server(obs=ObsConfig(enabled=True))
    _run(on, stream)
    snap = on.telemetry.snapshot()
    stages = {k[len("p50_stage_"):-len("_ms")] for k in snap
              if k.startswith("p50_stage_")}
    # every non-optional pipeline stage reports a wall-time channel
    # (prune only fires on its interval, so a 2-step run may skip it)
    assert {"apply", "pem", "rwr", "merge"} <= stages


# -- flight recorder ----------------------------------------------------------

def _fake_step_events(step):
    return [{"name": "engine/apply", "cat": "engine", "ph": "X",
             "ts": 1000.0 * step, "dur": 5.0, "pid": 1, "tid": 1,
             "args": {"step": step}}]


def test_flight_ring_keeps_last_n(tmp_path):
    fr = FlightRecorder(3, str(tmp_path / "fl"))
    for s in range(5):
        fr.push(s, _fake_step_events(s))
    assert fr.steps() == [2, 3, 4]
    path = fr.dump(reason="unit")
    assert validate_jsonl(path) == []
    events = read_jsonl(path)
    marker = events[0]
    assert marker["name"] == "flight_dump"
    assert marker["args"]["reason"] == "unit"
    assert marker["args"]["steps"] == [2, 3, 4]


def test_flight_triggered_dumps_deduplicate(tmp_path):
    fr = FlightRecorder(4, str(tmp_path / "fl"))
    fr.push(0, _fake_step_events(0))
    first = fr.dump(reason="slo", triggered=True)
    assert first is not None
    # same evidence, second trigger: skipped
    assert fr.dump(reason="slo", triggered=True) is None
    fr.push(1, _fake_step_events(1))
    second = fr.dump(reason="slo", triggered=True)
    assert second is not None and second != first
    # manual dumps always write
    assert fr.dump(reason="manual") is not None


def test_flight_concurrent_triggered_dumps_deduplicate(tmp_path):
    # two triggers race over the SAME evidence (e.g. the watchdog and an
    # SLO breach in the same instant): exactly one file may be written.
    # The dump body is serialized under a lock, so the snapshot/dedup/
    # write sequence cannot interleave.
    import threading
    fr = FlightRecorder(4, str(tmp_path / "fl"))
    fr.push(0, _fake_step_events(0))
    barrier = threading.Barrier(2)
    results = []

    def trigger():
        barrier.wait()
        results.append(fr.dump(reason="race", triggered=True))

    threads = [threading.Thread(target=trigger) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    paths = [r for r in results if r is not None]
    assert len(paths) == 1, f"racing triggers wrote {results}"
    assert fr.n_dumps == 1
    files = [f for f in os.listdir(tmp_path) if f.startswith("fl.")]
    assert files == [os.path.basename(paths[0])]


def test_slo_trigger_dumps_flight(tmp_path):
    obs = Obs(ObsConfig(enabled=True, flight_n=4, slo_e2e_ms=100.0,
                        flight_path=str(tmp_path / "slo")))
    obs.begin_step(0)
    with obs.span("engine/apply"):
        pass
    obs.end_step(0)
    assert obs.observe_e2e(50.0) is None          # under the SLO
    path = obs.observe_e2e(250.0)                 # breach -> post-mortem
    assert path is not None and validate_jsonl(path) == []
    assert "slo:e2e" in read_jsonl(path)[0]["args"]["reason"]
    assert obs.observe_e2e(300.0) is None         # no new steps: de-duped


@pytest.mark.slow
def test_executor_crash_dumps_flight(tmp_path):
    from repro.runtime import (ServingRuntime, VirtualClock, build_workload,
                               churn_heavy)
    wl = build_workload(churn_heavy(rate=2500.0, tick_s=0.01, n_ticks=6,
                                    n_vertices=128, seed=3), u_max=256)
    srv = _server()
    boom = {"calls": 0}
    orig = srv.step_packed

    def failing(*a, **kw):
        boom["calls"] += 1
        if boom["calls"] > 1:
            raise RuntimeError("injected executor fault")
        return orig(*a, **kw)

    srv.step_packed = failing
    prefix = str(tmp_path / "crash")
    rt = ServingRuntime(
        srv, RuntimeConfig(ingress="lockstep",
                           obs=ObsConfig(enabled=True, flight_n=8,
                                         flight_path=prefix)),
        clock=VirtualClock())
    with pytest.raises(RuntimeError, match="injected executor fault"):
        rt.serve(wl)
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("crash.")]
    assert dumps, "executor crash produced no flight dump"
    events = read_jsonl(str(tmp_path / sorted(dumps)[0]))
    assert events[0]["args"]["reason"].startswith("crash:RuntimeError")
    assert validate_events(events) == []


# -- cross-thread tracing through the async runtime ---------------------------

@pytest.mark.slow
def test_lockstep_runtime_trace_spans_threads(tmp_path):
    from repro.runtime import (ServingRuntime, VirtualClock, build_workload,
                               churn_heavy)
    wl = build_workload(churn_heavy(rate=2500.0, tick_s=0.01, n_ticks=8,
                                    n_vertices=128, seed=3), u_max=256)
    srv = _server()
    rt = ServingRuntime(
        srv, RuntimeConfig(ingress="lockstep",
                           obs=ObsConfig(enabled=True, flight_n=64)),
        clock=VirtualClock())
    stats = rt.serve(wl)
    assert stats
    events = srv.obs.tracer.events()
    by_cat = {}
    for ev in events:
        by_cat.setdefault(ev.get("cat"), []).append(ev)
    # ingress spans and engine spans run on different threads (compile
    # and GC spans land on whichever thread compiled or collected)
    ingress_tids = {ev["tid"] for ev in by_cat["ingress"]}
    engine_tids = {ev["tid"] for ev in by_cat["engine"]
                   if ev["name"] not in ("engine/compile", "engine/gc")}
    assert ingress_tids and engine_tids
    assert ingress_tids.isdisjoint(engine_tids)
    # thread_name metadata names both runtime threads
    names = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
    assert any("ingress" in n for n in names)
    assert any("executor" in n for n in names)
    # every executed batch id was stamped by the ingress thread...
    packed = {ev["args"]["batch"] for ev in events
              if ev["name"] == "ingress/packed"}
    stepped = {ev["args"]["batch"] for ev in by_cat["executor"]
               if ev["name"] == "executor/step"}
    assert stepped and stepped <= packed
    # ...and engine spans inherit BOTH ids from the thread-local context,
    # which is what lets a post-mortem follow one batch offer -> merge
    merges = [ev for ev in by_cat["engine"]
              if ev["name"] == "engine/merge"]
    assert merges
    assert all("step" in ev["args"] and "batch" in ev["args"]
               for ev in merges)
    # the flight ring grouped those spans under their step ids
    assert srv.obs.flight.steps() == [s.step for s in stats]


# -- host-path sub-spans, counters, compile and GC spans ----------------------

APPLY_CHILDREN = ("engine/apply/update", "engine/apply/ell_refresh",
                  "engine/apply/updated", "engine/apply/wait")
EXTRACT_CHILDREN = ("engine/extract/pull", "engine/extract/select",
                    "engine/extract/ell", "engine/extract/upload")


def _induced_server(obs, **cfg_kw):
    """The ELL backend with every step on the induced path (a recompute
    set can never exceed twice the live vertices)."""
    serving = ServingConfig(obs=obs, microbatch_window=64,
                            full_graph_frac=2.0)
    return MatchServer(_cfg(backend="ell", **cfg_kw), query_zoo(2), serving,
                       seed=0)


def _spans(server, name, step=None):
    return [ev for ev in server.obs.tracer.events()
            if ev["name"] == name and ev.get("ph") == "X"
            and (step is None or ev["args"].get("step") == step)]


@pytest.mark.slow
def test_traced_induced_step_nests_sub_spans():
    srv = _induced_server(ObsConfig(enabled=True))
    outs = _run(srv, _stream(n_steps=2))
    for st in outs:
        assert st.subgraph_nodes < 128          # induced, not a storm
        assert {"apply", "ell_refresh", "extract", "extract_pull",
                "compile", "gc"} <= set(st.stage_s)
        assert st.stage_s["extract_pull"] <= st.stage_s["extract"]
        assert set(st.counts) == {"d2h_bytes", "h2d_bytes", "compiles"}
        for parent, children in (("engine/apply", APPLY_CHILDREN),
                                 ("engine/extract", EXTRACT_CHILDREN)):
            (p,) = _spans(srv, parent, st.step)
            covered = 0.0
            for name in children:
                (c,) = _spans(srv, name, st.step)
                assert c["tid"] == p["tid"]
                assert p["ts"] <= c["ts"]
                assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3
                covered += c["dur"]
            # the children cover the parent's body but for the glue
            assert covered >= 0.5 * p["dur"]


@pytest.mark.slow
def test_d2h_bytes_count_each_pulled_array_once(monkeypatch):
    import jax
    srv = _induced_server(ObsConfig(enabled=True))
    stream = _stream(n_steps=2)
    g = stream.graph
    srv.submit_update(stream.updates[0])
    g, _ = srv.step(g)
    pulled = []
    orig = StepScope.pulled

    def spy(self, x):
        pulled.append(x)
        orig(self, x)

    monkeypatch.setattr(StepScope, "pulled", spy)
    srv.submit_update(stream.updates[1])
    g, st = srv.step(g)
    device = [x for x in pulled if isinstance(x, jax.Array)]
    unique = {id(x): x for x in device}
    assert len(unique) < len(device)        # some arrays were read twice
    assert st.counts["d2h_bytes"] == sum(x.nbytes for x in unique.values())
    # the graph arrays the step reads on the host (prune's liveness mask,
    # PEM, the live count) crossed exactly once each; the extraction
    # selects on the device and reads none of them
    read = ("senders", "receivers", "edge_mask", "node_mask")
    for name in read:
        arr = getattr(g, name)
        assert sum(1 for x in unique.values() if x is arr) == 1
    assert not any(x is g.labels for x in unique.values())
    graph = sum(getattr(g, f).nbytes for f in read)
    assert graph <= st.counts["d2h_bytes"] < graph + 64 * 1024
    assert st.counts["h2d_bytes"] > 0


def test_to_host_counts_a_device_array_once_per_step():
    import jax.numpy as jnp
    obs = Obs(ObsConfig(enabled=True))
    a = jnp.arange(100, dtype=jnp.int32)
    b = jnp.ones((7, 3), jnp.float32)
    first = obs.begin_step(0)
    np.testing.assert_array_equal(to_host(a), np.arange(100))
    to_host(a)                              # read again: counted once
    to_host(b)
    to_host(np.zeros(50))                   # host data crosses nothing
    obs.end_step(0)
    assert first.counts == {"d2h_bytes": a.nbytes + b.nbytes,
                            "h2d_bytes": 0, "compiles": 0}
    second = obs.begin_step(1)              # a new step starts afresh
    to_host(a)
    obs.end_step(1)
    to_host(b)                              # outside a step: not counted
    assert second.counts["d2h_bytes"] == a.nbytes
    obs.close()


def test_last_closed_hub_removes_the_hooks():
    gc.collect()                            # drop hubs of earlier tests
    base = hooks_registered()
    a = Obs(ObsConfig(enabled=True))
    b = Obs(ObsConfig(enabled=True))
    assert hooks_registered()
    a.close()
    assert hooks_registered()
    b.close()
    assert hooks_registered() == base
    assert not base


@pytest.mark.slow
def test_new_capacity_bucket_step_holds_compile_span():
    # an rwr_iters no other test uses: the bucket programs are new here
    srv = _induced_server(ObsConfig(enabled=True), rwr_iters=5)
    outs = _run(srv, _stream(n_steps=3))
    seen = set()
    checked = 0
    for st in outs:
        bucket = (max(64, 1 << (st.subgraph_nodes - 1).bit_length()),
                  max(256, 1 << (st.subgraph_edges - 1).bit_length()))
        if bucket in seen:
            continue
        seen.add(bucket)
        checked += 1
        assert st.counts["compiles"] >= 1
        assert st.stage_s["compile"] > 0.0
        spans = _spans(srv, "engine/compile", st.step)
        assert any(ev["args"]["phase"] == "backend" for ev in spans)
        assert all(ev["args"]["fun_name"] is not None for ev in spans)
    assert checked >= 1


@pytest.mark.slow
def test_gc_inside_a_traced_step_yields_gc_span():
    srv = _server(obs=ObsConfig(enabled=True))
    pem = srv.engine.pem
    orig = pem.recompute_mask

    def collecting(g, updated):
        gc.collect()
        return orig(g, updated)

    pem.recompute_mask = collecting
    outs = _run(srv, _stream(n_steps=1))
    (st,) = outs
    assert st.stage_s["gc"] > 0.0
    (ev,) = [e for e in _spans(srv, "engine/gc", st.step)]
    (p,) = _spans(srv, "engine/pem", st.step)
    assert p["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= p["ts"] + p["dur"]


@pytest.mark.slow
def test_profiler_capture_holds_program_spans(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    srv = _server(obs=ObsConfig(enabled=True))
    stream = _stream(n_steps=2)
    _run(srv, stream)                        # compile outside the capture
    srv.reset()
    with jax.profiler.trace(str(tmp_path)):
        _run(srv, stream)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    names[ev.name] = names.get(ev.name, 0) + 1
    assert names.get("engine/apply") == 2
    assert names.get("engine/apply/update") == 2
    assert "engine/merge" in names


# -- exporters ----------------------------------------------------------------

def test_jsonl_roundtrip_and_validation(tmp_path):
    events = [
        {"name": "engine/apply", "cat": "engine", "ph": "X", "ts": 1.5,
         "dur": 2.25, "pid": 1, "tid": 1, "args": {"step": 0}},
        {"name": "ingress/packed", "cat": "ingress", "ph": "i", "ts": 4.0,
         "pid": 1, "tid": 2, "args": {"batch": 3}},
        {"name": "thread_name", "ph": "M", "ts": 0.0, "pid": 1, "tid": 2,
         "args": {"name": "rt-ingress"}},
    ]
    path = str(tmp_path / "t.jsonl")
    write_jsonl(events, path)
    assert read_jsonl(path) == events
    assert validate_jsonl(path) == []
    chrome = str(tmp_path / "t.json")
    write_chrome(events, chrome)
    with open(chrome) as f:
        doc = json.load(f)
    assert doc["traceEvents"] == events


def test_validation_catches_schema_violations(tmp_path):
    assert validate_events([{"name": "x", "ph": "X", "ts": 0.0,
                             "pid": 1, "tid": 1}])  # X without dur
    assert validate_events([{"name": "x", "ph": "Q", "ts": 0.0, "dur": 1,
                             "pid": 1, "tid": 1}])  # unknown phase
    assert validate_events([{"ph": "i", "ts": 0.0, "pid": 1, "tid": 1}])
    assert validate_jsonl(str(tmp_path / "missing.jsonl"))
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert validate_jsonl(empty) == ["no events"]


def test_prometheus_export(tmp_path):
    path = str(tmp_path / "m.prom")
    write_prometheus({"p50_step_ms": 1.25, "steps": 4, "note": "text",
                      "bad": float("nan"), "weird key!": 2.0}, path)
    text = open(path).read()
    assert "repro_p50_step_ms 1.25" in text
    assert "repro_steps 4" in text
    assert "note" not in text and "nan" not in text
    assert "repro_weird_key_ 2" in text
    # exposition framing: every sample is announced by HELP + TYPE, and
    # the whole document passes the format checks
    assert "# HELP repro_p50_step_ms" in text
    assert "# TYPE repro_p50_step_ms gauge" in text
    assert validate_exposition(text) == []


def test_metric_name_folding():
    from repro.obs.export import metric_name
    assert metric_name("p50_step_ms") == "repro_p50_step_ms"
    assert metric_name("weird key!", prefix="x") == "x_weird_key_"
    assert metric_name("9starts_numeric", prefix="") == "_9starts_numeric"
    assert metric_name("a:b", prefix="ns") == "ns_a:b"  # colons are legal


def test_validate_exposition_catches_violations():
    ok = ("# HELP m_a help\n# TYPE m_a gauge\nm_a 1.5\n")
    assert validate_exposition(ok) == []
    assert validate_exposition("") == ["no samples"]
    # sample with no HELP/TYPE announcement
    assert validate_exposition("m_a 1.5\n")
    # malformed value
    assert validate_exposition(
        "# HELP m_a h\n# TYPE m_a gauge\nm_a banana\n")
    # non-finite value
    assert validate_exposition(
        "# HELP m_a h\n# TYPE m_a gauge\nm_a nan\n")
    # duplicate sample for one name
    assert validate_exposition(
        "# HELP m_a h\n# TYPE m_a gauge\nm_a 1\nm_a 2\n")
    # invalid metric name
    assert validate_exposition(
        "# HELP 9bad h\n# TYPE 9bad gauge\n9bad 1\n")


# -- prefix-sharing population (satellite) ------------------------------------

def test_prefix_zoo_shares_prefixes_without_duplication():
    qs = prefix_zoo(32)
    sigs = {query_signature(q) for q in qs}
    assert len(sigs) == len(qs)                    # zero exact duplication
    assert all(int(q.anchor) == 0 for q in qs)     # one anchor family
    unshared = sum(len(decompose(q)) for q in qs)
    shared = len({k for q in qs for k in decompose(q)})
    # the family collapses heavily in the sub-pattern DAG (>=4x)
    assert shared * 4 <= unshared


def test_prefix_zoo_engine_dag_collapse():
    from repro.engine import Engine
    qs = prefix_zoo(12)
    eng = Engine(_cfg(), seed=0)
    for q in qs:
        eng.register(q)
    c = eng.counters()
    assert c["standing_queries"] == 12
    assert c["n_dedup"] == 0                        # no alias fast-path hits
    assert c["bank_rows"] == 12                     # every row distinct
    unshared = sum(len(decompose(q)) for q in qs)
    assert c["dag_nodes"] < unshared                # DAG carries the collapse
