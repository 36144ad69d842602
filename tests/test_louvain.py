"""Louvain vs networkx oracle; constrained splitting; dendrogram cuts."""

import importlib

import numpy as np
import pytest

from repro.core.louvain import (Dendrogram, build_dendrogram, louvain,
                                louvain_constrained, modularity)

louvain_mod = importlib.import_module("repro.core.louvain")


def _planted(n_comm=4, size=32, p_in=0.3, p_out=0.01, seed=0):
    rng = np.random.default_rng(seed)
    n = n_comm * size
    src, dst = [], []
    for i in range(n):
        for j in range(i + 1, n):
            p = p_in if i // size == j // size else p_out
            if rng.random() < p:
                src += [i, j]
                dst += [j, i]
    return np.array(src), np.array(dst), n


def test_louvain_recovers_planted_communities():
    s, d, n = _planted()
    comm = louvain(s, d, n, seed=1)
    # communities should align with the planted blocks (allow minor noise)
    purity = 0
    for b in range(4):
        block = comm[b * 32:(b + 1) * 32]
        purity += np.bincount(block).max()
    assert purity / n > 0.9


def test_modularity_beats_random_partition():
    s, d, n = _planted()
    comm = louvain(s, d, n, seed=1)
    q_louvain = modularity(s, d, n, comm)
    rng = np.random.default_rng(0)
    q_rand = modularity(s, d, n, rng.integers(0, 4, n))
    assert q_louvain > q_rand + 0.2


def test_matches_networkx_quality():
    nx = pytest.importorskip("networkx")
    s, d, n = _planted(seed=3)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(s.tolist(), d.tolist()))
    nx_comms = nx.community.louvain_communities(g, seed=0)
    nx_q = nx.community.modularity(g, nx_comms)
    ours = louvain(s, d, n, seed=1)
    our_q = modularity(s, d, n, ours)
    assert our_q > nx_q - 0.05  # within 0.05 modularity of the oracle


def test_constrained_respects_max_size():
    s, d, n = _planted()
    for c in (8, 16, 50):
        comm = louvain_constrained(s, d, n, max_size=c)
        sizes = np.bincount(comm)
        assert sizes.max() <= c


def test_dendrogram_cut_sizes_and_monotonicity():
    s, d, n = _planted()
    dg = build_dendrogram(s, d, n, min_size=2)
    prev_n = None
    for c in (4, 8, 16, 64, 128):
        comm = dg.cut(c)
        sizes = np.bincount(comm)
        assert sizes.max() <= max(c, 2)
        n_comm = comm.max() + 1
        if prev_n is not None:
            assert n_comm <= prev_n  # coarser threshold → fewer communities
        prev_n = n_comm


def test_dendrogram_cut_is_partition():
    s, d, n = _planted(seed=5)
    dg = build_dendrogram(s, d, n)
    comm = dg.cut(16)
    assert comm.shape == (n,)
    assert (comm >= 0).all()


def _one_level_array_oracle(offs, nbr, w, n, resolution, rng, max_sweeps=10):
    """The numpy-per-vertex Louvain move phase, frozen as the oracle the
    scalar formulation must reproduce exactly."""
    comm = np.arange(n)
    k = np.zeros(n)
    np.add.at(k, np.repeat(np.arange(n), np.diff(offs)), w)
    two_m = max(w.sum(), 1e-12)
    sigma_tot = k.copy()
    for _ in range(max_sweeps):
        moved = 0
        for v in rng.permutation(n):
            lo, hi = offs[v], offs[v + 1]
            if lo == hi:
                continue
            ncomm = comm[nbr[lo:hi]]
            cv = comm[v]
            uniq, inv = np.unique(ncomm, return_inverse=True)
            w_to = np.bincount(inv, weights=w[lo:hi])
            sigma_tot[cv] -= k[v]
            gain = w_to - resolution * k[v] * sigma_tot[uniq] / two_m
            best = uniq[int(np.argmax(gain))]
            where_cv = np.where(uniq == cv)[0]
            if len(where_cv):
                base = gain[int(where_cv[0])]
            else:
                base = -resolution * k[v] * sigma_tot[cv] / two_m
            if gain.max() > base + 1e-12 and best != cv:
                comm[v] = best
                sigma_tot[best] += k[v]
                moved += 1
            else:
                sigma_tot[cv] += k[v]
        if moved == 0:
            break
    _, comm = np.unique(comm, return_inverse=True)
    return comm


@pytest.mark.parametrize("seed,resolution", [(0, 1.0), (3, 1.4), (7, 2.2)])
def test_louvain_matches_array_oracle(seed, resolution, monkeypatch):
    s, d, n = _planted(seed=seed)
    rng = np.random.default_rng(seed)
    w = rng.random(len(s))  # non-unit weights exercise the summation order
    got = louvain(s, d, n, weights=w, resolution=resolution, seed=seed)
    monkeypatch.setattr(louvain_mod, "_one_level", _one_level_array_oracle)
    want = louvain(s, d, n, weights=w, resolution=resolution, seed=seed)
    np.testing.assert_array_equal(got, want)


def _dendrogram_per_child_oracle(senders, receivers, n, min_size=2, seed=0,
                                 max_depth=32):
    """The per-child-mask ``build_dendrogram`` loop, frozen as the oracle
    the bucketed split must reproduce exactly (callers swap in the array
    Louvain oracle too)."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    paths = [[] for _ in range(n)]
    counter = [0]

    def record(members):
        nid = counter[0]
        counter[0] += 1
        for v in members:
            paths[v].append((nid, len(members)))

    def rec(ls, lr, members, depth):
        record(members)
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
        for c in range(int(sub.max()) + 1):
            sel = sub == c
            local = np.full(len(members), -1, np.int64)
            local[sel] = np.arange(int(sel.sum()))
            emask = sel[ls] & sel[lr]
            rec(local[ls[emask]], local[lr[emask]], members[sel], depth + 1)

    rec(senders, receivers, np.arange(n), 0)
    depth = max(len(p) for p in paths) if paths else 1
    ids = np.zeros((n, depth), np.int64)
    sizes = np.zeros((n, depth), np.int64)
    for v, chain in enumerate(paths):
        for d in range(depth):
            ids[v, d], sizes[v, d] = chain[min(d, len(chain) - 1)]
    return ids, sizes


@pytest.mark.parametrize("graph,min_size,seed,max_depth", [
    ("planted", 2, 0, 32),
    ("planted", 5, 3, 32),
    ("isolated", 2, 1, 32),      # many singleton communities (n_max padding)
    ("sparse", 2, 2, 3),         # depth cap hit before min_size
    ("empty", 2, 0, 32),         # no edges: balanced chunking only
])
def test_dendrogram_matches_per_child_oracle(graph, min_size, seed,
                                             max_depth, monkeypatch):
    rng = np.random.default_rng(seed)
    if graph == "planted":
        s, d, n = _planted(seed=seed)
    elif graph == "isolated":
        s0, d0, n0 = _planted(n_comm=3, size=20, seed=seed)
        s, d, n = s0, d0, n0 + 300   # 300 vertices with no edges at all
    elif graph == "sparse":
        n = 200
        a, b = rng.integers(0, n, 150), rng.integers(0, n, 150)
        keep = a != b
        s = np.concatenate([a[keep], b[keep]])
        d = np.concatenate([b[keep], a[keep]])
    else:
        s, d, n = np.zeros(0, np.int64), np.zeros(0, np.int64), 37
    got = build_dendrogram(s, d, n, min_size=min_size, seed=seed,
                           max_depth=max_depth)
    monkeypatch.setattr(louvain_mod, "_one_level", _one_level_array_oracle)
    ids, sizes = _dendrogram_per_child_oracle(s, d, n, min_size=min_size,
                                              seed=seed, max_depth=max_depth)
    np.testing.assert_array_equal(got.path_ids, ids)
    np.testing.assert_array_equal(got.path_sizes, sizes)
    assert got.n_edges_at_build == len(s)
