"""Plain reference of the served IGPM-PEM semantics, in numpy float64.

It imports nothing of the program and reads nothing the program computed
from the graph: no RWR table, no ELL tile, no dendrogram. Its inputs are
the generated stream (start graph, labels, offered events), the standing
queries as registered (labels, anchor, expansion schedule: the query's
own definition in G-Ray, Tong et al. KDD'07), and three things the run
decided by timing and records as it serves:

* which events each micro-batch carried (shed ingress batches by arrival
  timing);
* the PEM community threshold ``c`` of each step (the DQN moves it by
  elapsed time) and the Louvain split tree the step cut at ``c``: the tree
  is data the program made, so the reference only checks that it is a
  consistent tree (each recorded size is its community's vertex count,
  each community has one parent), then cuts it at ``c`` itself and
  requires the recompute set to be exactly the live vertices of every
  community that holds a vertex the batch touched (paper §III-C-1);
* what G-Ray returned for each bank row at the sampled steps, which is the
  answer under test.

For each sampled step the reference rebuilds the live graph from the
events, extracts the induced subgraph of its own recompute set, runs the
label-conditioned RWR and the single-source RWRs (restart 0.15, the
configured sweep count) and ranks seeds. Every registered query must have
a row, and every seed the reference ranks clearly inside the top k a
result. It walks every result's expansion schedule as the program picked
it: labels, the greedy choice of each tree expansion (its proximity
against the best unused label-compatible candidate), the bridge hop
counts (exact means every query edge is a data edge), and the goodness
(Σ log proximity). A result the program marks invalid must fail that
walk (a tree expansion with no candidate, or a query edge beyond the
bridge's hops); one that completes it was dropped. Finally it checks the
whole live edge set and the merged pattern stores.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import scipy.sparse as sp

EPS = 1e-12  # G-Ray's log guard, part of the goodness definition


@dataclass
class QuerySpec:
    """One standing query's definition (live part only)."""

    qid: str
    labels: np.ndarray       # int[nq]
    anchor: int
    sched: List[Tuple[int, int, bool]]  # (src, dst, is_tree)


@dataclass
class RowResult:
    """What G-Ray returned for one bank row at one step (local ids)."""

    qid: str
    matched: np.ndarray      # int[k, nq]
    goodness: np.ndarray     # float[k]
    exact: np.ndarray        # bool[k]
    valid: np.ndarray        # bool[k]


@dataclass
class StepRecord:
    """One served step as the run recorded it (host arrays)."""

    index: int                       # step index since serving began
    events: np.ndarray               # int[n, 2] undirected edges added
    recompute: Optional[np.ndarray] = None   # sorted global vertex ids
    rows: List[RowResult] = field(default_factory=list)
    c: int = 0                       # the PEM threshold the step cut at
    # the split tree it cut: (path_ids, path_sizes), int[n_max, depth+1],
    # column d the depth-d ancestor of each vertex and its size
    tree: Optional[Tuple[np.ndarray, np.ndarray]] = None


@dataclass
class Params:
    n_labels: int
    restart: float
    rwr_iters: int
    top_k: int
    bridge_hops: int
    n_live: int = 0          # vertices 0 .. n_live-1 exist
    c_min: int = 2           # the configured range of the PEM threshold
    c_max: int = 1 << 30


@dataclass
class Readings:
    """Every number the comparison reads; see :data:`LIMITS`."""

    edge_diff: int = 0        # live arcs in one edge multiset only
    degree_diff: int = 0      # vertices whose out-degree differs
    order_faults: int = 0     # events not applied once and in order
    partition_faults: int = 0  # split-tree inconsistencies, c out of range
    recompute_diff: int = 0   # vertices in one PEM set only (program, ref)
    pattern_faults: int = 0   # wrong labels / candidate / exact / valid
    missing_results: int = 0  # rows, seeds or valid results left out
    store_faults: int = 0     # results missing from the merged stores
    goodness_gap: float = 0.0  # max |Δgoodness| / max(|goodness|, 1)
    # max nats a greedy pick (a seed against the k-th best seed, a tree
    # expansion against the best unused candidate) scores below the best
    rank_gap: float = 0.0
    n_steps: int = 0          # sampled steps compared
    n_results: int = 0        # valid results compared


def check_order(offered: np.ndarray, applied: np.ndarray,
                n_shed: int) -> int:
    """Events applied once and in order: ``applied`` must be ``offered``
    with exactly ``n_shed`` events left out and nothing added, repeated
    or reordered. Returns the count of events out of place."""
    i = j = 0
    miss = 0
    while j < len(applied) and i < len(offered):
        if offered[i, 0] == applied[j, 0] and offered[i, 1] == applied[j, 1]:
            j += 1
        else:
            miss += 1
        i += 1
    extra = len(applied) - j
    skipped = miss + (len(offered) - i)
    return extra + abs(skipped - n_shed)


def arc_keys(src: np.ndarray, dst: np.ndarray, n_max: int) -> np.ndarray:
    return np.sort(np.asarray(src, np.int64) * n_max
                   + np.asarray(dst, np.int64))


def multiset_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the symmetric difference of two sorted multisets."""
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    keys = np.union1d(ua, ub)
    na = np.zeros(len(keys), np.int64)
    nb = np.zeros(len(keys), np.int64)
    na[np.searchsorted(keys, ua)] = ca
    nb[np.searchsorted(keys, ub)] = cb
    return int(np.abs(na - nb).sum())


class Induced:
    """The subgraph induced by a vertex set, with the RWR operators."""

    def __init__(self, ids: np.ndarray, src: np.ndarray, dst: np.ndarray,
                 labels: np.ndarray, p: Params):
        self.ids = ids
        self.p = p
        n = len(ids)
        self.n = n
        g2l = {int(v): i for i, v in enumerate(ids)}
        inside = np.isin(src, ids) & np.isin(dst, ids)
        ls = np.searchsorted(ids, src[inside])
        lr = np.searchsorted(ids, dst[inside])
        del g2l
        self.labels = labels[ids]
        self.deg = np.bincount(ls, minlength=n).astype(np.float64)
        self.n_arcs = len(ls)
        # agg[v] = Σ_{u→v} r[u] / deg(u)
        w = 1.0 / np.maximum(self.deg[ls], 1.0)
        m = sp.csr_matrix((w, (lr, ls)), shape=(n, n))
        a = sp.csr_matrix((np.ones(len(ls)), (lr, ls)), shape=(n, n))
        if n <= 4096 and self.n_arcs > n * n // 16:
            self.m = m.toarray()
            self.a = (a.toarray() > 0).astype(np.float64)
        else:
            self.m = m
            self.a = a

    def rwr(self, e: np.ndarray) -> np.ndarray:
        """r = c·e + (1−c)·Pᵀr from r = e, ``rwr_iters`` sweeps."""
        c = self.p.restart
        r = e
        for _ in range(self.p.rwr_iters):
            r = c * e + (1.0 - c) * (self.m @ r)
        return r

    def label_table(self) -> np.ndarray:
        onehot = (self.labels[:, None]
                  == np.arange(self.p.n_labels)[None, :]).astype(np.float64)
        e = onehot / np.maximum(onehot.sum(axis=0, keepdims=True), 1.0)
        return self.rwr(e)

    def source_tables(self, sources: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Single-source RWR columns and BFS hop counts (≤ bridge_hops,
        else bridge_hops+1), one column per source."""
        e = np.zeros((self.n, len(sources)))
        e[sources, np.arange(len(sources))] = 1.0
        r = self.rwr(e)
        h_max = self.p.bridge_hops
        hops = np.full((self.n, len(sources)), h_max + 1, np.int64)
        hops[sources, np.arange(len(sources))] = 0
        reached = e > 0
        for h in range(1, h_max + 1):
            nxt = (self.a @ reached.astype(np.float64)) > 0
            nxt |= reached
            hops[nxt & ~reached] = h
            reached = nxt
        return r, hops


def cut(path_ids: np.ndarray, path_sizes: np.ndarray, c: int) -> np.ndarray:
    """Each vertex's community at threshold ``c``: its shallowest
    ancestor of at most ``c`` vertices, else its leaf."""
    ok = path_sizes <= c
    depth = np.where(ok.any(axis=1), ok.argmax(axis=1), ok.shape[1] - 1)
    return path_ids[np.arange(len(depth)), depth]


def tree_faults(path_ids: np.ndarray, path_sizes: np.ndarray) -> int:
    """Inconsistencies of a split tree: the root is not one community, a
    recorded size is not its community's vertex count, or a community has
    more than one parent."""
    bad = int(len(np.unique(path_ids[:, 0])) != 1)
    for d in range(path_ids.shape[1]):
        col = path_ids[:, d]
        if (col < 0).any():
            bad += int(np.count_nonzero(col < 0))
            continue
        bad += int(np.count_nonzero(np.bincount(col)[col] != path_sizes[:, d]))
        if d:
            pairs = np.unique(col * (int(path_ids.max()) + 1)
                              + path_ids[:, d - 1])
            bad += len(pairs) - len(np.unique(col))
    return bad


def recompute_set(rec: StepRecord, p: Params) -> np.ndarray:
    """The PEM recompute set: every live vertex of each community, at the
    step's threshold, that holds a vertex the batch touched."""
    comm = cut(*rec.tree, rec.c)
    touched = np.unique(rec.events.ravel())
    live = np.arange(len(comm)) < p.n_live
    return np.flatnonzero(np.isin(comm, comm[touched]) & live)


class Walk(NamedTuple):
    faulty: bool     # a pick the schedule cannot make
    complete: bool   # every expansion found, every edge within the hops
    exact: bool      # every query edge is a data edge
    goodness: float
    gap: float       # nats the worst tree pick lies below the best


def walk(m: np.ndarray, q: QuerySpec, sub: "Induced", score: np.ndarray,
         r_src: np.ndarray, hops: np.ndarray, col: Dict[int, int],
         p: Params) -> Walk:
    """Follow one result's expansion schedule as the program picked it."""
    seed = int(m[q.anchor])
    good, used, exact, complete, gap = float(score[seed]), {seed}, True, \
        True, 0.0
    for a, b, tree in q.sched:
        u, w = int(m[a]), int(m[b])
        r_u = r_src[:, col[u]]
        if tree:
            cand = sub.labels == q.labels[b]
            cand[list(used)] = False
            if w < 0:   # the program found no candidate: nor may we
                return Walk(bool(cand.any()), False, False, good, gap)
            if w >= sub.n or not cand[w]:
                return Walk(True, False, False, good, gap)
            gap = max(gap, float(np.log(r_u[cand].max() + EPS)
                                 - np.log(r_u[w] + EPS)))
            used.add(w)
        elif not 0 <= w < sub.n:
            return Walk(True, False, False, good, gap)
        good += float(np.log(r_u[w] + EPS))
        h = int(hops[w, col[u]])
        complete &= h <= p.bridge_hops
        exact &= h == 1
    return Walk(False, complete, exact and complete, good, gap)


def check_step(rec: StepRecord, graph_src: np.ndarray,
               graph_dst: np.ndarray, labels: np.ndarray,
               queries: Dict[str, QuerySpec], p: Params,
               out: Readings) -> List[Tuple[str, Tuple[int, ...], float]]:
    """Compare one sampled step; returns (qid, vertex set, goodness) of
    every valid result, for the store check."""
    ids = recompute_set(rec, p)
    out.recompute_diff += len(np.setxor1d(ids, rec.recompute))
    if not p.c_min <= rec.c <= p.c_max:
        out.partition_faults += 1
    sub = Induced(ids, graph_src, graph_dst, labels, p)
    logp = np.log(sub.label_table() + EPS)
    alive = sub.deg > 0
    tie = LIMITS["rank_gap"]

    def seed_scores(q: QuerySpec) -> np.ndarray:
        s = logp[:, q.labels].sum(axis=1)
        ok = (sub.labels == q.labels[q.anchor]) & alive
        return np.where(ok, s, -np.inf)

    def is_seed(v: int, score: np.ndarray) -> bool:
        return 0 <= v < sub.n and bool(np.isfinite(score[v]))

    rows = [r for r in rec.rows if r.qid in queries]
    out.missing_results += len(set(queries) - {r.qid for r in rows})
    scores = {r.qid: seed_scores(queries[r.qid]) for r in rows}

    # every vertex some result expands from, along its schedule
    srcs = set()
    for row in rows:
        q = queries[row.qid]
        for m in row.matched:
            if is_seed(int(m[q.anchor]), scores[row.qid]):
                srcs.update(int(m[a]) for a, _, _ in q.sched)
    src_list = np.asarray(sorted(v for v in srcs if 0 <= v < sub.n),
                          np.int64)
    col = {int(v): j for j, v in enumerate(src_list)}
    r_src, hops = (sub.source_tables(src_list) if len(src_list)
                   else (np.zeros((sub.n, 0)), np.zeros((sub.n, 0), int)))

    found = []
    for row in rows:
        q = queries[row.qid]
        score = scores[row.qid]
        k = min(p.top_k, int(np.isfinite(score).sum()))
        kth = np.sort(score)[::-1][k - 1] if k > 0 else np.inf
        anchors = row.matched[:, q.anchor]
        real = [i for i in range(len(anchors))
                if is_seed(int(anchors[i]), score)]
        # seeds clearly inside the reference's top k must all be there
        sure = set(np.flatnonzero(score > kth + tie).tolist())
        out.missing_results += max(len(sure - set(anchors[real].tolist())),
                                   k - len(real))
        for i in range(len(anchors)):
            m = row.matched[i]
            if i not in real:
                out.pattern_faults += int(bool(row.valid[i]))
                continue
            w = walk(m, q, sub, score, r_src, hops, col, p)
            out.rank_gap = max(out.rank_gap, float(kth - score[m[q.anchor]]),
                               w.gap)
            if not row.valid[i]:
                out.pattern_faults += int(w.faulty)
                out.missing_results += int(w.complete and not w.faulty)
                continue
            out.n_results += 1
            if (w.faulty or not w.complete
                    or len(set(m.tolist())) != len(m)
                    or (sub.labels[m] != q.labels).any()
                    or w.exact != bool(row.exact[i])):
                out.pattern_faults += 1
                continue
            gap = abs(float(row.goodness[i]) - w.goodness) / max(
                abs(w.goodness), 1.0)
            out.goodness_gap = max(out.goodness_gap, gap)
            found.append((row.qid, tuple(sorted(ids[m].tolist())),
                          float(row.goodness[i])))
    out.n_steps += 1
    return found


def check_trees(records: Sequence[StepRecord], out: Readings) -> None:
    """Each distinct split tree the sampled steps cut, checked once."""
    seen = set()
    for r in records:
        if r.tree is not None and id(r.tree[0]) not in seen:
            seen.add(id(r.tree[0]))
            out.partition_faults += tree_faults(*r.tree)


def check_stores(found: Sequence[Tuple[str, Tuple[int, ...], float]],
                 stores: Dict[str, Dict[Tuple[int, ...], float]],
                 out: Readings) -> None:
    """A store keeps each vertex set at its best goodness so far, so every
    valid result's set is there at no less than the result's goodness."""
    for qid, key, good in found:
        have = stores.get(qid, {}).get(key)
        if have is None or have < good - 1e-6 * max(abs(good), 1.0):
            out.store_faults += 1


# Each compared number and its limit; a run is correct when every reading
# is at or below its limit (PERF.md gives the readings each was set from).
LIMITS: Dict[str, float] = {
    "edge_diff": 0,
    "degree_diff": 0,
    "order_faults": 0,
    "partition_faults": 0,
    "recompute_diff": 0,
    "pattern_faults": 0,
    "missing_results": 0,
    "store_faults": 0,
    "goodness_gap": 1e-4,
    "rank_gap": 1e-3,
}


def verdict(r: Readings) -> Tuple[bool, Dict[str, Tuple[float, float]]]:
    compared = {name: (float(getattr(r, name)), float(limit))
                for name, limit in LIMITS.items()}
    ok = all(v <= lim for v, lim in compared.values()) and r.n_results > 0
    return ok, compared
