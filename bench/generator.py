"""Stream and arrival generation for the benchmark, driven by data files.

A configuration file (``bench/configs/<name>.json``) names a dataset twin:
the published vertex, edge and timestep counts of one of Kanezashi et al.
2018's Table III streams and the graph type the paper assigns it. A traffic
file (``bench/traffic/<name>.json``) says how that stream reaches the
server: the share of edges already in the graph when serving starts, and
either open-loop Poisson arrivals at a fixed rate or a backlog replayed as
fast as the server takes it. Everything is drawn from one ``seed``.

The edge generators follow ``repro.data.temporal`` (paper §III-D-1 graph
types) and the arrival process follows ``repro.runtime.scenarios``; they
are copied here so that the benchmark's inputs cannot move when the
program's own generators change. The twins are synthetic: they carry the
published vertex, edge and timestep counts, but their structure is the
generator's own (for instance ``dense`` puts every edge among √(8n)
vertices, ``sparse_dense`` plants communities of ~64 consecutive ids), not
that of the published edge lists, which the repository does not hold.
The events that follow the start graph are regrouped by the vertex-id
region of their larger endpoint, as ``repro.data.temporal`` does, so that
each batch's activity clusters as PEM assumes; no published source
describes this order, and each configuration states it as assumed.
Two departures from the program's generators, both deliberate:

* the serving tail is sized from the twin's own edges per timestep; the
  program's ``build_workload`` sized it as if every step carried 256
  edges, so twins with fewer (``sx-mathoverflow``: 215) ran short;
* an open-loop run offers a fixed number of events, ``rate`` times the
  duration of each phase, at times drawn as a Poisson process conditioned
  on that count, so every seed offers the same work in a different order.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List, NamedTuple, Tuple

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent

GRAPH_KINDS = ("scale_free", "random", "sparse_isolated", "sparse_dense",
               "dense")
TRAFFIC_KINDS = ("open_poisson", "backlog")


@dataclass(frozen=True)
class Twin:
    """A Table III stream at its published size."""

    name: str
    kind: str
    n_vertices: int
    n_edges: int
    n_steps: int
    n_labels: int = 4
    locality_regions: int = 64

    @property
    def edges_per_step(self) -> int:
        return max(1, self.n_edges // self.n_steps)


@dataclass(frozen=True)
class Traffic:
    """One traffic mix (see the module docstring); times in seconds."""

    name: str
    kind: str
    warmup_frac: float       # share of the twin's edges in the start graph
    tick_s: float            # arrival quantum
    ingress: str             # ServingRuntime ingress policy
    window: int              # events per micro-batch at most
    queue_depth: int         # pending-event bound (shed policy)
    rate_eps: float = 0.0    # open_poisson: offered events per second
    # open_poisson: warm-up phases served before the window without a
    # break, each (share of rate_eps, seconds), then the window, then
    # ``tail_s`` more seconds at rate_eps while the window's events finish
    warmup: Tuple[Tuple[float, float], ...] = ()
    tail_s: float = 0.0
    # induced-subgraph capacity buckets (vertices, arcs) the traffic reaches,
    # each served once in set-up so none loads its programs in the window
    warm_buckets: Tuple[Tuple[int, int], ...] = ()
    warmup_ticks: int = 0    # backlog: warm-up ticks served in lockstep
    events_per_tick: int = 0  # backlog: events each tick carries
    budget_eps: float = 0.0  # backlog: events per second the stream covers


class Stream(NamedTuple):
    """Start graph and the ordered events that follow it."""

    labels: np.ndarray       # int32[n_vertices]
    start_src: np.ndarray    # undirected edges already in the graph
    start_dst: np.ndarray
    tail_src: np.ndarray     # events, in stream order
    tail_dst: np.ndarray


class Arrivals(NamedTuple):
    """Tick times and how many events each carries, for one phase."""

    times: np.ndarray        # float64[n_ticks]
    counts: np.ndarray       # int64[n_ticks]

    @property
    def n_events(self) -> int:
        return int(self.counts.sum())


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def twin_from_config(cfg: dict) -> Twin:
    d = cfg["dataset"]
    twin = Twin(d["name"], d["kind"], int(d["n_vertices"]),
                int(d["n_edges"]), int(d["n_steps"]),
                n_labels=int(cfg["engine"]["n_labels"]))
    if twin.kind not in GRAPH_KINDS:
        raise ValueError(f"unknown graph kind {twin.kind!r}")
    return twin


def traffic_from_file(name: str,
                      directory: Path = BENCH_DIR / "traffic") -> Traffic:
    """The traffic mix ``<directory>/<name>.json``."""
    raw = load_json(directory / f"{name}.json")
    fields = {k: v for k, v in raw.items() if k in Traffic.__annotations__}
    if "warmup" in fields:
        fields["warmup"] = tuple((float(a), float(b))
                                 for a, b in fields["warmup"])
    if "warm_buckets" in fields:
        fields["warm_buckets"] = tuple((int(a), int(b))
                                       for a, b in fields["warm_buckets"])
    tr = Traffic(name=name, **{k: v for k, v in fields.items()
                               if k != "name"})
    if tr.kind not in TRAFFIC_KINDS:
        raise ValueError(f"unknown traffic kind {tr.kind!r}")
    return tr


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generators per purpose from one (possibly > 32-bit)
    seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, stream])


def gen_edges(twin: Twin, rng: np.random.Generator
              ) -> Tuple[np.ndarray, np.ndarray]:
    """The twin's whole edge stream (self-loops dropped), paper §III-D-1."""
    n, m = twin.n_vertices, twin.n_edges
    if twin.kind == "scale_free":
        # preferential attachment: endpoint ∝ degree+1, in chunks
        src = np.zeros(m, np.int64)
        dst = np.zeros(m, np.int64)
        deg = np.ones(n, np.float64)
        chunk = max(256, m // 64)
        done = 0
        while done < m:
            k = min(chunk, m - done)
            p = deg / deg.sum()
            s = rng.choice(n, size=k, p=p)
            d = rng.choice(n, size=k, p=p)
            src[done:done + k] = s
            dst[done:done + k] = d
            np.add.at(deg, s, 1.0)
            np.add.at(deg, d, 1.0)
            done += k
    elif twin.kind == "random":
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
    elif twin.kind == "sparse_isolated":
        cell = rng.integers(0, n // 4, m) * 4
        src = cell + rng.integers(0, 4, m)
        dst = cell + rng.integers(0, 4, m)
    elif twin.kind == "sparse_dense":
        # sparse globally, dense planted communities of ~64 vertices
        n_comm = max(8, n // 64)
        comm = rng.integers(0, n_comm, m)
        within = rng.random(m) < 0.9
        lo = (comm * (n // n_comm)).astype(np.int64)
        width = max(2, n // n_comm)
        src = lo + rng.integers(0, width, m)
        dst = np.where(within, lo + rng.integers(0, width, m),
                       rng.integers(0, n, m))
    else:  # dense: confined to a core of sqrt(8n) vertices
        core = max(16, int(np.sqrt(n * 8)))
        src = rng.integers(0, core, m)
        dst = rng.integers(0, core, m)
    keep = src != dst
    return src[keep], dst[keep]


def make_stream(twin: Twin, warmup_frac: float, n_tail: int,
                seed: int) -> Stream:
    """Start graph = the first ``warmup_frac`` of the stream; the next
    ``n_tail`` edges are the events, grouped by the region of their larger
    endpoint (stable within a region). Raises when the stream is too
    short for ``n_tail`` events."""
    rng = seed_rng(seed, 0)
    src, dst = gen_edges(twin, rng)
    labels = rng.integers(0, twin.n_labels, twin.n_vertices).astype(np.int32)
    m = len(src)
    warm = int(m * warmup_frac)
    if n_tail > m - warm:
        raise ValueError(
            f"{twin.name}: {n_tail} events asked after a start graph of "
            f"{warm} edges, but the stream holds only {m - warm} more")
    tail_s = src[warm:warm + n_tail].copy()
    tail_d = dst[warm:warm + n_tail].copy()
    region = np.maximum(tail_s, tail_d) // max(
        1, twin.n_vertices // twin.locality_regions)
    order = np.argsort(region, kind="stable")
    return Stream(labels, src[:warm], dst[:warm], tail_s[order],
                  tail_d[order])


def poisson_arrivals(rate_eps: float, duration_s: float, tick_s: float,
                     rng: np.random.Generator, t0: float = 0.0) -> Arrivals:
    """``round(rate·duration)`` events at uniform random times (a Poisson
    process conditioned on its count), quantized to ``tick_s`` ticks that
    start at ``t0``."""
    n_ticks = max(1, int(round(duration_s / tick_s)))
    n_events = int(round(rate_eps * duration_s))
    t = rng.uniform(0.0, n_ticks * tick_s, n_events)
    counts = np.bincount(np.minimum((t / tick_s).astype(np.int64),
                                    n_ticks - 1), minlength=n_ticks)
    return Arrivals(t0 + np.arange(n_ticks) * tick_s,
                    counts.astype(np.int64))


def backlog_arrivals(n_ticks: int, events_per_tick: int,
                     tick_s: float) -> Arrivals:
    return Arrivals(np.arange(n_ticks) * tick_s,
                    np.full(n_ticks, events_per_tick, np.int64))


def concat(parts: List[Arrivals]) -> Arrivals:
    return Arrivals(np.concatenate([p.times for p in parts]),
                    np.concatenate([p.counts for p in parts]))


def warmup_seconds(tr: Traffic) -> float:
    return float(sum(s for _, s in tr.warmup))


def phase_arrivals(tr: Traffic, seconds: float, seed: int
                   ) -> Tuple[Arrivals, Arrivals, Arrivals]:
    """(warm-up, window, tail) arrivals. Open-loop phases follow one
    another on one clock: the window opens at ``warmup_seconds(tr)``. A
    backlog window carries enough ticks for ``budget_eps`` events a
    second (running out fails the run) and has no tail."""
    if tr.kind == "open_poisson":
        rng = seed_rng(seed, 1)
        warm, t = [], 0.0
        for share, secs in tr.warmup:
            warm.append(poisson_arrivals(share * tr.rate_eps, secs,
                                         tr.tick_s, rng, t))
            t += secs
        win = poisson_arrivals(tr.rate_eps, seconds, tr.tick_s, rng, t)
        tail = poisson_arrivals(tr.rate_eps, tr.tail_s, tr.tick_s, rng,
                                t + seconds)
        return concat(warm) if warm else Arrivals(np.zeros(0), np.zeros(
            0, np.int64)), win, tail
    n_win = int(math.ceil(tr.budget_eps * seconds / tr.events_per_tick))
    return (backlog_arrivals(tr.warmup_ticks, tr.events_per_tick, tr.tick_s),
            backlog_arrivals(n_win, tr.events_per_tick, tr.tick_s),
            Arrivals(np.zeros(0), np.zeros(0, np.int64)))


def build_inputs(twin: Twin, tr: Traffic, seconds: float, seed: int
                 ) -> Tuple[Stream, Arrivals, Arrivals, Arrivals]:
    """Stream plus (warm-up, window, tail) arrivals; the stream's events
    are exactly those the three phases offer, in order."""
    warm, win, tail = phase_arrivals(tr, seconds, seed)
    stream = make_stream(twin, tr.warmup_frac,
                         warm.n_events + win.n_events + tail.n_events, seed)
    return stream, warm, win, tail


def split_events(stream: Stream, *phases: Arrivals
                 ) -> List[List[Tuple[int, int]]]:
    """The stream's events as (u, v) pairs, cut into the given phases."""
    pairs = list(zip(stream.tail_src.tolist(), stream.tail_dst.tolist()))
    out, cur = [], 0
    for ph in phases:
        out.append(pairs[cur:cur + ph.n_events])
        cur += ph.n_events
    return out
