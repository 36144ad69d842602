"""One benchmark run of one cell: set up, warm up, measure, check.

``run_cell`` is the whole run behind ``bench/run.py``. It builds the
published ``igpm-pem`` server for the cell's configuration, serves the
cell's traffic through ``ServingRuntime`` (ingress thread → double-buffered
executor → ``MatchServer.step_packed`` → ``engine_step``) first as warm-up
and then for the measured window, drains, compares what the window served
with the plain reference (``bench/reference.py``) and reduces the numbers.
Open-loop traffic is served as one stream on one clock (warm-up, window,
tail); a backlog drains its warm-up and starts the window on a fresh
pipeline, which fills at once.

The harness observes the program at its own object boundaries and changes
nothing it computes: it wraps ``step_packed`` (to note each micro-batch
and its host times), the PEM's ``recompute_mask`` (to keep the set it
returned, the threshold ``c`` it cut at and the split tree it cut) and
each bucket's ``match`` (to keep what it returned), the runtime's per-batch latency
record (to keep every event's arrival and delivery), and gives the
runtime a clock that notes how late the load generator ran.
"""

from __future__ import annotations

import contextlib
import dataclasses
import tempfile
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from bench import generator as gen
from bench import reference as ref


# -- what a run measured ------------------------------------------------------


class Step(NamedTuple):
    """One served step as the harness saw it (host monotonic seconds)."""

    upd: object              # the packed UpdateBatch (device arrays)
    n_events: int
    t_start: float
    t_done: float            # when its deltas were out
    stats: object            # ServingStepStats
    recompute: Optional[np.ndarray]
    results: list            # [(bucket, rows, GRayResult)]
    full_graph: bool         # a storm step (matched on the whole graph)
    c: int                   # the PEM threshold the step cut at
    tree: Optional[tuple]    # the split tree (path_ids, path_sizes) it cut


class Delivery(NamedTuple):
    """The runtime's record of one executed batch, on the host monotonic
    clock: each event's nominal arrival, when the batch was packed, and
    when its deltas were out."""

    arrivals: np.ndarray
    t_packed: float
    t_done: float


@dataclasses.dataclass
class RunView:
    """What the metric readers (``bench/metrics/*.py``) read."""

    cell: dict
    seconds: float
    setup_s: float                       # process start → window start
    attempted: int                       # events offered in the window
    failed: int                          # ... shed or never delivered
    steps: List[Step]                    # the window's steps
    e2e_s: np.ndarray                    # per window event delivered
    queue_wait_s: np.ndarray
    late_s: np.ndarray                   # generator lateness per tick
    compiles: int
    rate_events: int = 0                 # backlog: events of whole steps
    rate_s: float = 0.0                  # ... and the time they took
    trace: Optional[dict] = None         # bench.trace.reduce output
    roofline: Optional[dict] = None      # kernel → bench.roofline.Share


def late_clock():
    """The runtime's wall clock, noting (t, lateness) for each scheduled
    wait: how late the load generator itself ran."""
    from repro.runtime.clock import WallClock

    class LateClock(WallClock):
        def __init__(self):
            super().__init__()
            self.late: List[Tuple[float, float]] = []

        def wait_until(self, t: float, interrupt: threading.Event) -> None:
            super().wait_until(t, interrupt)
            if not interrupt.is_set():
                self.late.append((t, max(self.now() - t, 0.0)))

    return LateClock()


class CompileCounter:
    """Counts programs compiled or loaded from the cache while armed
    (JAX's backend-compile event fires for both)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "")))


class Recorder:
    """Notes every served step and every delivered batch (see the module
    docstring). ``clock_t0`` is the monotonic zero of the runtime clock
    that stamps the current runtime's arrivals."""

    def __init__(self, server, n_max: int):
        self.server = server
        self.steps: List[Step] = []
        self.deliveries: List[Delivery] = []
        self.clock_t0 = 0.0
        self._cur: dict = {}
        self._n_max = n_max
        self._step = server.step_packed
        server.step_packed = self._step_packed
        pem = server.engine.pem
        self._recompute = pem.recompute_mask
        pem.recompute_mask = self._recompute_mask
        for bucket in server.engine.buckets.values():
            bucket.match = self._match_of(bucket, bucket.match)

    def _step_packed(self, g, upd, n_events, t_start=None):
        self._cur = {"recompute": None, "results": [], "full": False,
                     "c": 0, "tree": None}
        t0 = time.monotonic()
        out = self._step(g, upd, n_events, t_start)
        cur, self._cur = self._cur, {}
        self.steps.append(Step(upd, n_events, t0, time.monotonic(), out[1],
                               cur["recompute"], cur["results"],
                               cur["full"], cur["c"], cur["tree"]))
        return out

    def _recompute_mask(self, g, updated):
        pem = self.server.engine.pem
        c = int(pem.c)
        mask, frac = self._recompute(g, updated)
        self._cur["recompute"] = np.flatnonzero(mask)
        self._cur["c"] = c
        self._cur["tree"] = (pem._dendro.path_ids, pem._dendro.path_sizes)
        return mask, frac

    def _match_of(self, bucket, match: Callable):
        def wrapped(g, *args, **kw):
            res = match(g, *args, **kw)
            if self._cur:   # inside a served step (not a set-up call)
                self._cur["results"].append((bucket, bucket.rows(), res))
                if g.n_max == self._n_max:
                    self._cur["full"] = True
            return res
        return wrapped

    def delivered(self, item, t_done: float) -> None:
        t0 = self.clock_t0
        self.deliveries.append(Delivery(
            t0 + np.asarray(item.arrivals, np.float64), t0 + item.t_packed,
            t0 + t_done))


@contextlib.contextmanager
def recording_deliveries(rec: Recorder):
    """Have the runtime's per-batch latency hook also hand each executed
    batch to ``rec`` (it runs on the executor thread right after the
    batch's step, so deliveries line up with ``rec.steps``)."""
    from repro.runtime import runtime as rt_mod

    orig = rt_mod._record_batch_latencies

    def hook(tel, item, t_done):
        orig(tel, item, t_done)
        rec.delivered(item, t_done)

    rt_mod._record_batch_latencies = hook
    try:
        yield
    finally:
        rt_mod._record_batch_latencies = orig


# -- set-up -------------------------------------------------------------------


def persistent_cache(cache_dir: str) -> str:
    """Keep every program in JAX's persistent cache (the defaults skip
    programs that compile in under a second, which a run would then
    compile again), so only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def engine_config(cfg: dict):
    from repro.config.base import IGPMConfig

    return IGPMConfig(**cfg["engine"])


def build_server(cfg: dict, tr: gen.Traffic, seed: int, trace: bool):
    from repro.config.base import ObsConfig, ServingConfig
    from repro.core.query import query_zoo
    from repro.serving import MatchServer

    bank = cfg["bank"]
    if bank["kind"] != "query_zoo":
        raise ValueError(f"unknown bank {bank['kind']!r}")
    serving = ServingConfig(
        adaptive=bool(cfg["serving"]["adaptive"]),
        queue_depth=tr.queue_depth, microbatch_window=tr.window,
        obs=ObsConfig(enabled=trace, event_cap=1 << 18))
    return MatchServer(engine_config(cfg), query_zoo(int(bank["count"])),
                       serving, seed=seed % (2 ** 31 - 1))


def start_graph(cfg: dict, stream: gen.Stream):
    from repro.core.graph import new_graph

    e = cfg["engine"]
    return new_graph(e["n_max"], e["e_max"], labels=stream.labels,
                     senders=np.concatenate([stream.start_src,
                                             stream.start_dst]),
                     receivers=np.concatenate([stream.start_dst,
                                               stream.start_src]))


def workload(graph, pairs, arrivals: gen.Arrivals, tick_s: float,
             name: str):
    from repro.runtime.scenarios import ScenarioConfig, Tick, Workload
    from repro.serving.queue import ADD, UpdateEvent

    events = [UpdateEvent(ADD, u, v) for u, v in pairs]
    ticks, cur = [], 0
    for t, k in zip(arrivals.times.tolist(), arrivals.counts.tolist()):
        ticks.append(Tick(t=t, events=events[cur:cur + k]))
        cur += k
    sc = ScenarioConfig(name=name, kind="poisson", tick_s=tick_s,
                        n_ticks=int(round(float(arrivals.times[-1]) / tick_s))
                        + 1 if len(ticks) else 0)

    class _Start(NamedTuple):
        graph: object

    return Workload(sc, None, _Start(graph), ticks, cur)


def runtime(server, tr: gen.Traffic, rec: Recorder, clock=None):
    from repro.config.base import RuntimeConfig
    from repro.runtime import ServingRuntime
    from repro.runtime.clock import WallClock

    clock = clock or WallClock()
    rec.clock_t0 = clock._t0
    return ServingRuntime(server, RuntimeConfig(ingress=tr.ingress,
                                                drain_timeout_s=300.0),
                          clock=clock)


def serve(rt, wl) -> None:
    rt.start(wl)
    join(rt, wl)


def join(rt, wl) -> None:
    if not rt.join(timeout=rt.rcfg.drain_timeout_s
                   + wl.scenario.duration_s):
        rt.stop(drain=False)
        raise TimeoutError("the serving runtime did not finish its workload")


def sleep_until(t: float) -> None:
    time.sleep(max(t - time.monotonic(), 0.0))


def bucket_mask(src: np.ndarray, dst: np.ndarray, live: np.ndarray,
                n_max: int, n_cap: int, e_cap: int,
                rng: np.random.Generator) -> Optional[np.ndarray]:
    """A vertex set of the live graph whose induced subgraph falls in the
    (n_cap, e_cap) capacity bucket, or None: the shortest prefix of the
    live vertices (by id) holding about 3/4 of e_cap arcs, topped up with
    vertices drawn at random (which bring few arcs) to about 3/4 of n_cap."""
    def mask_of(ids):
        m = np.zeros(n_max, bool)
        m[ids] = True
        return m

    def arcs(m):
        return int(np.count_nonzero(m[src] & m[dst]))

    lo, hi = 0, len(live)
    while lo < hi:
        mid = (lo + hi) // 2
        if arcs(mask_of(live[:mid])) >= 3 * e_cap // 4:
            hi = mid
        else:
            lo = mid + 1
    rest = rng.permutation(live[lo:])
    extra = max(0, 3 * n_cap // 4 - lo)
    m = mask_of(np.concatenate([live[:lo], rest[:extra]]))
    n, e = int(m.sum()), arcs(m)
    return m if (pow2(n, 64), pow2(e, 256)) == (n_cap, e_cap) else None


def warm_buckets(server, graph, buckets, seed: int) -> List[tuple]:
    """Run the induced path's programs (extraction, label RWR, every bank
    bucket's seeds and match) once on a real subgraph of each capacity
    bucket, as a step of that bucket would. Returns the buckets no vertex
    set of this graph reaches."""
    import jax

    from repro.core.subgraph import extract_induced

    eng = server.engine
    em = np.asarray(graph.edge_mask)
    src = np.asarray(graph.senders)[em]
    dst = np.asarray(graph.receivers)[em]
    live = np.flatnonzero(np.asarray(graph.node_mask))
    rng = gen.seed_rng(seed, 11)
    missed = []
    for n_cap, e_cap in buckets:
        mask = bucket_mask(src, dst, live, graph.n_max, n_cap, e_cap, rng)
        if mask is None:
            missed.append((n_cap, e_cap))
            continue
        sub = extract_induced(graph, mask, ell_k=(
            eng.cfg.ell_width if eng.ell_cache is not None else None))
        r_sub = eng._label_table(sub.graph, ell=sub.ell)
        jax.block_until_ready([b.match(sub.graph, r_sub, ell=sub.ell)
                               for b in eng.buckets.values()])
    return missed


# -- the run --------------------------------------------------------------------

# the profiler starts this long before an open-loop window opens (its start
# is not free, and the reduction clips the trace to the window)
TRACE_LEAD_S = 1.0


def run_cell(cell: dict, cfg: dict, tr: gen.Traffic, seed: int,
             seconds: float, trace: bool, t_start: float,
             breaker: Optional[Callable] = None,
             sample_steps: int = 6) -> dict:
    """Run one cell once; returns the run's view, the compared numbers and
    the device readings. ``breaker`` (tests and controls only) is called
    with the server before the harness wraps it, to break the timed path
    underneath."""
    import jax

    from bench import roofline
    from bench import trace as tracing

    twin = gen.twin_from_config(cfg)
    stream, warm, win, tail = gen.build_inputs(twin, tr, seconds, seed)
    warm_pairs, win_pairs, tail_pairs = gen.split_events(stream, warm, win,
                                                         tail)
    graph = start_graph(cfg, stream)
    server = build_server(cfg, tr, seed, trace)
    if breaker is not None:
        breaker(server)
    rec = Recorder(server, cfg["engine"]["n_max"])
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    anchor = None

    with recording_deliveries(rec):
        # prime: one lockstep batch (clustering, mirror, first compiles)
        prime = min(tr.window, warm.n_events)
        lock = dataclasses.replace(tr, ingress="lockstep")
        rt = runtime(server, lock, rec)
        serve(rt, workload(graph, warm_pairs[:prime], gen.Arrivals(
            np.zeros(1), np.asarray([prime])), tr.tick_s, "prime"))
        graph = rt.graph
        missed = warm_buckets(server, graph, tr.warm_buckets, seed)
        rest = gen.Arrivals(warm.times, warm.counts.copy())
        _take_first(rest.counts, prime)

        if tr.kind == "open_poisson":
            # warm-up, window and tail on one clock, without a break: the
            # window's first events meet the queue as steady state left it
            arr = gen.concat([rest, win, tail])
            clock = late_clock()
            rt = runtime(server, tr, rec, clock)
            wl = workload(graph, warm_pairs[prime:] + win_pairs + tail_pairs,
                          arr, tr.tick_s, "open-loop")
            w0 = rec.clock_t0 + gen.warmup_seconds(tr)
            w1 = w0 + seconds
            rt.start(wl)
            if trace:
                sleep_until(w0 - TRACE_LEAD_S)
                anchor = tracing.start(trace_dir, server.obs)
            sleep_until(w0)
            counter.armed = True
            join(rt, wl)
            t_end = time.monotonic()
            counter.armed = False
            n_warm_steps = 0
        else:
            # backlog: the rest of the warm-up drained in lockstep, then
            # the window from a fresh pipeline (which fills at once)
            if rest.n_events:
                rt = runtime(server, tr, rec)
                serve(rt, workload(graph, warm_pairs[prime:], rest,
                                   tr.tick_s, "warm-up"))
                graph = rt.graph
            n_warm_steps = len(rec.steps)
            if trace:
                anchor = tracing.start(trace_dir, server.obs)
            offered0 = server.queue.n_offered
            counter.armed = True
            rt = runtime(server, tr, rec)
            wl = workload(graph, win_pairs, win, tr.tick_s, "window")
            w0 = time.monotonic()
            w1 = w0 + seconds
            rt.start(wl)
            sleep_until(w1)
            offered_at_close = server.queue.n_offered - offered0
            rt.stop(drain=True)
            t_end = time.monotonic()
            counter.armed = False
            if offered_at_close >= wl.n_events:
                raise RuntimeError(
                    "the backlog ran out inside the window: raise the "
                    "traffic file's budget_eps")
        if trace:
            tracing.stop()

    mem = jax.devices()[0].memory_stats() or {}
    view = window_view(cell, tr, seconds, rec, n_warm_steps, w0, w1,
                       win.n_events, t_start, counter)
    if tr.kind == "open_poisson":
        view.late_s = np.asarray([late for t, late in clock.late
                                  if w0 <= rec.clock_t0 + t < w1],
                                 np.float64)
    shed = int(server.queue.n_dropped)
    out = {"view": view,
           "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
           "shed": shed, "served_s": t_end - w0,
           "compiled_in_window": counter.names,
           "buckets_not_reached": missed}
    if trace:
        view.trace = tracing.reduce_dir(trace_dir, anchor, server.obs, w0,
                                        t_end)
        tracing.remove(trace_dir)
        view.trace["steps"] = sum(1 for s in rec.steps
                                  if s.t_done > w0 and s.t_start < t_end)
        launches = view.trace.pop("launches")
        if any(launches.values()):
            view.roofline = roofline.kernel_shares(
                launches, step_table(rec.steps, cfg, view.trace["clock"]),
                jax.devices()[0].device_kind)

    # answers: copy what the comparison needs to the host, free the server
    picked = {id(s) for s in sample(view.steps, seed, sample_steps)}
    records = [to_record(i, s, id(s) in picked)
               for i, s in enumerate(rec.steps)]
    sampled = [r for r, s in zip(records, rec.steps) if id(s) in picked]
    queries = query_specs(server)
    final = final_graph(server)
    stores = {qid: store_dict(st)
              for qid, st in server.engine.stores.items()}
    n_offered = int(server.queue.n_offered)
    offered = np.asarray((warm_pairs + win_pairs + tail_pairs)[:n_offered],
                         np.int64)
    del server, rt, rec, graph, wl
    t_ref = time.monotonic()
    readings = compare(cfg, stream, offered, shed, records, sampled,
                       queries, final, stores)
    out["reference_s"] = time.monotonic() - t_ref
    out["correct"], out["compared"] = ref.verdict(readings)
    out["n_results"] = readings.n_results
    out["n_compared_steps"] = readings.n_steps
    return out


def window_view(cell, tr, seconds, rec, n_warm_steps, w0, w1, attempted,
                t_start, counter) -> RunView:
    """The window's population. Open loop: every event whose nominal
    arrival lies in [w0, w1), however late it was delivered, and the steps
    that delivered them. Backlog: the steps that ended inside the window,
    and their events over the time from the window's start to the last of
    them (both ends on a step boundary)."""
    if tr.kind == "open_poisson":
        e2e, wait, steps = [], [], []
        for st, d in zip(rec.steps, rec.deliveries):
            inside = (d.arrivals >= w0) & (d.arrivals < w1)
            if inside.any():
                e2e.append(d.t_done - d.arrivals[inside])
                wait.append(d.t_packed - d.arrivals[inside])
                steps.append(st)
        e2e_s = np.concatenate(e2e) if e2e else np.zeros(0)
        return RunView(cell=cell, seconds=seconds, setup_s=w0 - t_start,
                       attempted=int(attempted),
                       failed=int(attempted - len(e2e_s)), steps=steps,
                       e2e_s=e2e_s, queue_wait_s=(np.concatenate(wait)
                                                  if wait else np.zeros(0)),
                       late_s=np.zeros(0), compiles=counter.count)
    after = rec.steps[n_warm_steps:]
    steps = [s for s in after if s.t_done <= w1]
    n_in = sum(s.n_events for s in after)
    return RunView(cell=cell, seconds=seconds, setup_s=w0 - t_start,
                   attempted=int(n_in), failed=0, steps=steps,
                   e2e_s=np.zeros(0), queue_wait_s=np.zeros(0),
                   late_s=np.zeros(0), compiles=counter.count,
                   rate_events=sum(s.n_events for s in steps),
                   rate_s=(steps[-1].t_done - w0) if steps else 0.0)


def pow2(x: int, floor: int) -> int:
    """The program's induced-subgraph capacity rule (core/subgraph.py)."""
    return max(floor, 1 << int(np.ceil(np.log2(max(x, 1)))))


def step_table(steps: List[Step], cfg: dict, clock: Tuple[float, float]):
    """Each step's interval on the profiler clock and the graph its
    kernels swept: (start ns, end ns, tile vertices, live vertices, live
    arcs). The induced tile's vertex capacity follows the program's
    power-of-two bucketing (64 at least); a storm step sweeps the whole
    graph."""
    anchor_ns, anchor_mono = clock
    n_max = cfg["engine"]["n_max"]
    out = []
    for s in steps:
        nodes, arcs = s.stats.subgraph_nodes, s.stats.subgraph_edges
        n_tile = n_max if s.full_graph else pow2(nodes, 64)
        out.append((anchor_ns + (s.t_start - anchor_mono) * 1e9,
                    anchor_ns + (s.t_done - anchor_mono) * 1e9,
                    n_tile, nodes, arcs))
    return out


def _take_first(counts: np.ndarray, n: int) -> None:
    """Remove the first ``n`` events from a tick count vector in place."""
    i = 0
    while n > 0 and i < len(counts):
        k = min(int(counts[i]), n)
        counts[i] -= k
        n -= k
        i += 1


def sample(steps: List[Step], seed: int, n: int) -> List[Step]:
    """Window steps to compare: ``n`` drawn from the seed among the
    induced-subgraph steps, plus the one with the largest subgraph."""
    pool = [s for s in steps if not s.full_graph and s.recompute is not None
            and s.results]
    if not pool:
        return []
    rng = gen.seed_rng(seed, 7)
    pick = set(rng.choice(len(pool), size=min(n, len(pool)),
                          replace=False).tolist())
    pick.add(int(np.argmax([s.stats.subgraph_edges for s in pool])))
    return [pool[i] for i in sorted(pick)]


def batch_events(upd) -> np.ndarray:
    """Undirected edges a packed batch adds, in batch order (pack puts the
    forward arcs first, then the mirrored ones)."""
    m = np.asarray(upd.add_mask)
    s = np.asarray(upd.add_src)[m]
    d = np.asarray(upd.add_dst)[m]
    k = len(s) // 2
    return np.stack([s[:k], d[:k]], axis=1).astype(np.int64)


def to_record(i: int, s: Step, with_results: bool) -> ref.StepRecord:
    """Host copy of one step: its batch, and for a sampled step the PEM
    set, threshold and split tree and what G-Ray returned for every row."""
    if not with_results:
        return ref.StepRecord(i, batch_events(s.upd))
    rows = []
    for bucket, slots, res in s.results:
        matched = np.asarray(res.matched)
        good = np.asarray(res.goodness)
        exact = np.asarray(res.exact)
        valid = np.asarray(res.valid)
        for slot, qid in slots:
            nq = int(np.asarray(bucket.query(slot).mask).sum())
            rows.append(ref.RowResult(qid, matched[slot][:, :nq],
                                      good[slot], exact[slot], valid[slot]))
    return ref.StepRecord(i, batch_events(s.upd), s.recompute, rows, s.c,
                          s.tree)


def query_specs(server) -> Dict[str, ref.QuerySpec]:
    out = {}
    for bucket in server.engine.buckets.values():
        for slot, qid in bucket.rows():
            q = bucket.query(slot)
            nq = int(np.asarray(q.mask).sum())
            om = np.asarray(q.order_mask)
            sched = [(int(a), int(b), bool(t)) for a, b, t in zip(
                np.asarray(q.order_src)[om], np.asarray(q.order_dst)[om],
                np.asarray(q.order_tree)[om])]
            out[qid] = ref.QuerySpec(qid, np.asarray(q.labels)[:nq],
                                     int(q.anchor), sched)
    return out


def final_graph(server) -> Dict[str, np.ndarray]:
    g = server.graph
    em = np.asarray(g.edge_mask)
    return {"src": np.asarray(g.senders)[em], "dst": np.asarray(g.receivers)[em],
            "degree": np.asarray(g.degree)}


def store_dict(store) -> Dict[tuple, float]:
    arr = store.to_arrays()
    return {tuple(k): float(gd) for k, gd in
            zip(arr["keys"].tolist(), arr["goodness"].tolist())}


def compare(cfg, stream, offered, n_shed, records, sampled, queries, final,
            stores) -> ref.Readings:
    """The reference's comparison (``bench/reference.py``)."""
    e = cfg["engine"]
    p = ref.Params(n_labels=e["n_labels"], restart=e.get("restart_prob", 0.15),
                   rwr_iters=e["rwr_iters"], top_k=e["top_k_patterns"],
                   bridge_hops=e.get("bridge_hops", 4),
                   n_live=len(stream.labels),
                   c_min=e["min_community_size"],
                   c_max=e["max_community_size"])
    out = ref.Readings()
    applied = np.concatenate([r.events for r in records]
                             or [np.zeros((0, 2), np.int64)])
    out.order_faults = ref.check_order(offered, applied, n_shed)
    n_max = e["n_max"]
    src = np.concatenate([stream.start_src, stream.start_dst,
                          applied[:, 0], applied[:, 1]])
    dst = np.concatenate([stream.start_dst, stream.start_src,
                          applied[:, 1], applied[:, 0]])
    out.edge_diff = ref.multiset_diff(ref.arc_keys(src, dst, n_max),
                                      ref.arc_keys(final["src"],
                                                   final["dst"], n_max))
    deg = np.bincount(src, minlength=n_max)
    out.degree_diff = int(np.count_nonzero(deg != final["degree"]))
    # the live graph after each sampled step: start + batches up to it
    ends = np.cumsum([len(r.events) for r in records])
    found = []
    for r in sampled:
        k = int(ends[r.index])
        gs = np.concatenate([stream.start_src, stream.start_dst,
                             applied[:k, 0], applied[:k, 1]])
        gd = np.concatenate([stream.start_dst, stream.start_src,
                             applied[:k, 1], applied[:k, 0]])
        found += ref.check_step(r, gs, gd, stream.labels, queries, p, out)
    ref.check_trees(sampled, out)
    ref.check_stores(found, stores, out)
    return out


