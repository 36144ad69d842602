"""Louvain community detection (Blondel et al. 2008) — PEM's clustering
sub-component (paper §III-C-2).

Control-plane code: runs host-side in numpy (cluster membership is shipped to
the device as one int array per step). The paper's usage is "repeat the
Louvain method until clusters cannot be divided further or are smaller than
the size threshold from the RL component" — that recursive subdivision is
:func:`louvain_constrained`.

``networkx.community.louvain_communities`` is used as a *test oracle only*
(tests compare modularity quality, not exact partitions — Louvain is order
dependent).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def _csr(senders: np.ndarray, receivers: np.ndarray, weights: np.ndarray,
         n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    order = np.argsort(senders, kind="stable")
    nbr = receivers[order]
    w = weights[order]
    deg = np.bincount(senders, minlength=n)
    offs = np.concatenate([[0], np.cumsum(deg)])
    return offs, nbr, w


def _one_level(offs: np.ndarray, nbr: np.ndarray, w: np.ndarray, n: int,
               resolution: float, rng: np.random.Generator,
               max_sweeps: int = 10) -> np.ndarray:
    """Phase 1: greedy local moves maximizing modularity gain.

    The per-vertex move runs on Python scalars (lists, a dict per
    neighborhood): neighborhoods are a handful of arcs, where numpy's
    per-call overhead dominated. Float operations and their order are
    those of the array formulation — per-community weights summed in arc
    order, gains evaluated and the first maximum taken in ascending
    community order — so the partition is unchanged."""
    k = np.zeros(n)  # weighted degree
    np.add.at(k, np.repeat(np.arange(n), np.diff(offs)), w)
    two_m = float(max(w.sum(), 1e-12))  # directed sum == 2m for symmetric input
    comm = list(range(n))
    kl = k.tolist()
    sigma_tot = k.tolist()  # per-community total degree
    offs_l, nbr_l, w_l = offs.tolist(), nbr.tolist(), w.tolist()

    for _ in range(max_sweeps):
        moved = 0
        for v in rng.permutation(n).tolist():
            lo, hi = offs_l[v], offs_l[v + 1]
            if lo == hi:
                continue
            cv = comm[v]
            # weight from v to each neighboring community
            w_to: dict = {}
            for j in range(lo, hi):
                c = comm[nbr_l[j]]
                w_to[c] = w_to.get(c, 0.0) + w_l[j]
            kv = kl[v]
            sigma_tot[cv] -= kv
            # ΔQ ∝ w_to(c) − γ·k_v·Σ_tot(c)/2m  (v removed from cv first)
            rk = resolution * kv
            best, best_gain, base = cv, None, None
            for c in sorted(w_to):
                gain = w_to[c] - rk * sigma_tot[c] / two_m
                if best_gain is None or gain > best_gain:
                    best, best_gain = c, gain
                if c == cv:
                    base = gain
            # gain of staying put: w_to(cv) may be 0 if no neighbor shares cv
            if base is None:
                base = -resolution * kv * sigma_tot[cv] / two_m
            if best_gain > base + 1e-12 and best != cv:
                comm[v] = best
                sigma_tot[best] += kv
                moved += 1
            else:
                sigma_tot[cv] += kv
        if moved == 0:
            break
    # relabel densely
    _, comm = np.unique(np.asarray(comm, np.int64), return_inverse=True)
    return comm


def _aggregate(senders: np.ndarray, receivers: np.ndarray,
               weights: np.ndarray, comm: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Phase 2: collapse communities into super-vertices."""
    cs, cr = comm[senders], comm[receivers]
    nc = int(comm.max()) + 1 if len(comm) else 0
    key = cs.astype(np.int64) * nc + cr
    uniq, inv = np.unique(key, return_inverse=True)
    w = np.bincount(inv, weights=weights)
    return (uniq // nc).astype(np.int64), (uniq % nc).astype(np.int64), w, nc


def louvain(senders: np.ndarray, receivers: np.ndarray, n: int,
            weights: np.ndarray | None = None, resolution: float = 1.0,
            seed: int = 0, max_levels: int = 10) -> np.ndarray:
    """Full multi-level Louvain. Input must contain both arcs of each
    undirected edge. Returns dense community ids per vertex."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    if weights is None:
        weights = np.ones(len(senders))
    rng = np.random.default_rng(seed)
    mapping = np.arange(n)
    s, r, w, nn = senders, receivers, weights.astype(np.float64), n
    for _ in range(max_levels):
        offs, nbr, wc = _csr(s, r, w, nn)
        comm = _one_level(offs, nbr, wc, nn, resolution, rng)
        nc = int(comm.max()) + 1 if len(comm) else 0
        mapping = comm[mapping]
        if nc == nn:  # no coarsening possible — converged
            break
        s, r, w, _ = _aggregate(s, r, w, comm)
        # drop self loops' effect on moves? keep (standard louvain keeps them)
        nn = nc
    return mapping


def modularity(senders: np.ndarray, receivers: np.ndarray, n: int,
               comm: np.ndarray, weights: np.ndarray | None = None,
               resolution: float = 1.0) -> float:
    if weights is None:
        weights = np.ones(len(senders), np.float64)
    two_m = max(weights.sum(), 1e-12)
    k = np.zeros(n)
    np.add.at(k, senders, weights)
    internal = weights[comm[senders] == comm[receivers]].sum()
    sig = np.bincount(comm, weights=k, minlength=int(comm.max()) + 1)
    return float(internal / two_m - resolution * np.sum((sig / two_m) ** 2))


def _split_oversized(senders: np.ndarray, receivers: np.ndarray,
                     comm: np.ndarray, max_size: int, n: int,
                     seed: int, depth: int = 0) -> np.ndarray:
    """Recursively re-run Louvain (with a resolution bump) inside oversized
    communities; fall back to balanced chunking when indivisible."""
    comm = comm.copy()
    next_id = int(comm.max()) + 1
    sizes = np.bincount(comm)
    for c in np.where(sizes > max_size)[0]:
        members = np.where(comm == c)[0]
        local = np.full(n, -1, np.int64)
        local[members] = np.arange(len(members))
        emask = (comm[senders] == c) & (comm[receivers] == c)
        ls, lr = local[senders[emask]], local[receivers[emask]]
        sub = None
        if len(ls) and depth < 8:
            sub = louvain(ls, lr, len(members),
                          resolution=1.0 + 0.5 * (depth + 1), seed=seed + depth)
            if sub.max() == 0:
                sub = None
        if sub is None:  # indivisible — balanced chunks (paper: "cannot be
            # divided further"); chunking preserves the ≤max_size contract
            sub = np.arange(len(members)) // max_size
        sub_sizes = np.bincount(sub)
        if (sub_sizes > max_size).any():
            # recurse into sub-communities
            sub = _split_oversized(ls, lr, sub, max_size, len(members),
                                   seed + 1, depth + 1)
        comm[members] = next_id + sub
        next_id += int(sub.max()) + 1
    _, dense = np.unique(comm, return_inverse=True)
    return dense


def louvain_constrained(senders: np.ndarray, receivers: np.ndarray, n: int,
                        max_size: int, weights: np.ndarray | None = None,
                        seed: int = 0) -> np.ndarray:
    """Paper §III-C: repeat Louvain until every community ≤ ``max_size``."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    comm = louvain(senders, receivers, n, weights=weights, seed=seed)
    return _split_oversized(senders, receivers, comm, max(1, max_size), n, seed)


class Dendrogram:
    """Recursive-Louvain split tree, cuttable at ANY size threshold in
    O(n·depth) — PEM's ±1 community-size actions then cost a table lookup
    instead of a full recluster (beyond-paper optimization; EXPERIMENTS.md
    §Perf logs the win).

    ``path_ids[v, d]`` / ``path_sizes[v, d]``: the community id / size of v's
    ancestor at depth d (root = whole graph at d=0); rows are padded by
    repeating the leaf entry, so sizes are non-increasing along each row.
    """

    def __init__(self, path_ids: np.ndarray, path_sizes: np.ndarray,
                 n_edges_at_build: int):
        self.path_ids = path_ids
        self.path_sizes = path_sizes
        self.n_edges_at_build = n_edges_at_build

    def cut(self, max_size: int) -> np.ndarray:
        """Membership whose every community has size ≤ max_size (or is a
        leaf). Picks the shallowest ancestor satisfying the bound."""
        ok = self.path_sizes <= max_size
        # argmax returns the FIRST True along the row; rows with no True
        # (c < leaf size) fall back to the leaf (last column)
        first = np.argmax(ok, axis=1)
        none = ~ok.any(axis=1)
        first[none] = self.path_ids.shape[1] - 1
        comm = self.path_ids[np.arange(len(first)), first]
        _, dense = np.unique(comm, return_inverse=True)
        return dense


def build_dendrogram(senders: np.ndarray, receivers: np.ndarray, n: int,
                     min_size: int = 2, seed: int = 0,
                     max_depth: int = 32) -> Dendrogram:
    """Recursively split the graph with Louvain (resolution bump per level,
    balanced chunking for indivisible communities) down to ``min_size``.

    Tree nodes are numbered in depth-first preorder. A split buckets the
    parent's members and intra-child edges by child community with one
    stable sort each, so it costs about the parent's size once — not once
    per child, which at serving capacities (hundreds of thousands of
    singleton communities) was a setup stall of minutes."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    # column d = the depth-d ancestor (−1 below a vertex's leaf, padded
    # with the leaf entry once the tree is built)
    path_ids = np.full((n, max_depth + 1), -1, np.int64)
    path_sizes = np.zeros((n, max_depth + 1), np.int64)
    counter = 0
    deepest = 0

    def rec(ls: np.ndarray, lr: np.ndarray, members: np.ndarray,
            depth: int) -> None:
        nonlocal counter, deepest
        path_ids[members, depth] = counter
        path_sizes[members, depth] = len(members)
        counter += 1
        if len(members):
            deepest = max(deepest, depth)
        if len(members) <= min_size or depth >= max_depth:
            return
        sub = None
        if len(ls):
            sub = louvain(ls, lr, len(members),
                          resolution=1.0 + 0.4 * depth, seed=seed + depth)
            if int(sub.max()) == 0:
                sub = None
        if sub is None:
            sub = np.arange(len(members)) // max(min_size, len(members) // 2)
        n_sub = int(sub.max()) + 1
        # members grouped by child, index order kept inside each child
        order = np.argsort(sub, kind="stable")
        m_lo = np.searchsorted(sub[order], np.arange(n_sub + 1))
        local = np.empty(len(members), np.int64)
        local[order] = np.arange(len(members)) - m_lo[sub[order]]
        # edges with both ends in one child, grouped by child, edge order
        # kept inside each child
        es = sub[ls]
        inside = np.flatnonzero(es == sub[lr])
        inside = inside[np.argsort(es[inside], kind="stable")]
        e_lo = np.searchsorted(es[inside], np.arange(n_sub + 1))
        cs, cr = local[ls[inside]], local[lr[inside]]
        for c in range(n_sub):
            rec(cs[e_lo[c]:e_lo[c + 1]], cr[e_lo[c]:e_lo[c + 1]],
                members[order[m_lo[c]:m_lo[c + 1]]], depth + 1)

    rec(senders, receivers, np.arange(n), 0)
    path_ids = path_ids[:, :deepest + 1]
    path_sizes = path_sizes[:, :deepest + 1]
    for d in range(1, deepest + 1):
        leaf = path_ids[:, d] < 0
        path_ids[leaf, d] = path_ids[leaf, d - 1]
        path_sizes[leaf, d] = path_sizes[leaf, d - 1]
    return Dendrogram(path_ids, path_sizes, len(senders))
