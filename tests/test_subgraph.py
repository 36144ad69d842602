"""Induced-subgraph extraction on the device against a numpy oracle.

The device selects and compacts the kept arcs (``pull_induced``); the
result must be exactly what selecting them on the host from the whole
graph gives, down to slot order, local ids, capacities, the local graph
and its ELL tile.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import IGPMConfig, ObsConfig, ServingConfig
from repro.core.graph import (DynamicGraph, UpdateBatch, apply_update,
                              new_graph)
from repro.core.query import query_zoo
from repro.core.subgraph import (_pow2, extract_induced, induced_tile,
                                 pull_induced, select_induced)
from repro.serving import MatchServer
from repro.sparse.ell import build_ell_host, ell_row_capacity


class OracleSelection(NamedTuple):
    senders: np.ndarray
    receivers: np.ndarray
    labels: np.ndarray
    local_to_global: np.ndarray
    n_cap: int
    e_cap: int


def oracle_select(g: DynamicGraph, mask: np.ndarray, n_floor: int = 64,
                  e_floor: int = 256) -> OracleSelection:
    """Test oracle: the selection on the host over the whole graph's
    arrays, as the extraction did it before the device took it over."""
    senders, receivers = np.asarray(g.senders), np.asarray(g.receivers)
    edge_mask, labels = np.asarray(g.edge_mask), np.asarray(g.labels)
    mask = np.asarray(mask, bool)
    ids = np.where(mask)[0]
    n_sub = len(ids)
    g2l = np.full(len(labels), -1, np.int64)
    g2l[ids] = np.arange(n_sub)
    keep = edge_mask & mask[senders] & mask[receivers]
    ls = g2l[senders[keep]]
    lr = g2l[receivers[keep]]
    n_cap = _pow2(n_sub, n_floor)
    e_cap = _pow2(len(ls), e_floor)
    l2g = np.full(n_cap, -1, np.int64)
    l2g[:n_sub] = ids
    return OracleSelection(ls, lr, labels[ids].astype(np.int32), l2g, n_cap,
                           e_cap)


def _graph(seed: int, n: int = 300, e_max: int = 5000, n_arcs: int = 1500,
           n_removed: int = 200) -> DynamicGraph:
    """A graph with live arcs, removed arcs (dead slots inside the cursor)
    and untouched slots past the cursor; vertex ids past ``n - 20`` never
    appear, so some vertices have no arcs."""
    rng = np.random.default_rng(seed)
    g = new_graph(n, e_max, labels=rng.integers(0, 5, n).astype(np.int32),
                  senders=rng.integers(0, n - 20, n_arcs),
                  receivers=rng.integers(0, n - 20, n_arcs))
    pick = rng.choice(n_arcs, n_removed, replace=False)
    s, r = np.asarray(g.senders)[pick], np.asarray(g.receivers)[pick]
    g = apply_update(g, UpdateBatch.removals(s, r, u_max=n_removed,
                                             undirected=False))
    assert int(np.asarray(g.edge_mask).sum()) < n_arcs
    return g


def _mask(g: DynamicGraph, kind: str, seed: int) -> np.ndarray:
    n = g.n_max
    rng = np.random.default_rng(seed)
    if kind == "empty":
        return np.zeros(n, bool)
    if kind == "full":
        return np.ones(n, bool)
    if kind == "random":
        return rng.random(n) < 0.3
    if kind == "pow2_nodes":            # exactly 128 vertices
        m = np.zeros(n, bool)
        m[rng.choice(n, 128, replace=False)] = True
        return m
    # "pow2_arcs": grow a vertex set until its induced arcs number exactly
    # 512, then keep it
    s, r = np.asarray(g.senders), np.asarray(g.receivers)
    em = np.asarray(g.edge_mask)
    for _ in range(200):
        m = np.zeros(n, bool)
        for v in rng.permutation(n):
            m[v] = True
            k = int(np.count_nonzero(em & m[s] & m[r]))
            if k >= 512:
                break
        if k == 512:
            return m
    raise AssertionError("no vertex set with exactly 512 induced arcs")


# more than one selection chunk (65,536 slots), the cursor inside the last
# one, which overlaps the one before it
CHUNKED = dict(n=3000, e_max=3 * 65536 + 1000, n_arcs=3 * 65536 + 500)
CASES = ([(seed, kind, ell_k, "small") for seed in (0, 1)
          for kind in ("random", "empty", "full", "pow2_nodes", "pow2_arcs")
          for ell_k in (8, None)]
         + [(2, kind, 8, "chunked") for kind in ("random", "full")])


@pytest.mark.parametrize("seed,kind,ell_k,size", CASES)
def test_device_selection_matches_numpy_oracle(seed, kind, ell_k, size):
    g = _graph(seed, **(CHUNKED if size == "chunked" else {}))
    mask = _mask(g, kind, seed)
    want = oracle_select(g, mask)
    if kind == "pow2_nodes":
        assert want.n_cap == 128 == int(mask.sum())
    if kind == "pow2_arcs":
        assert want.e_cap == 512 == len(want.senders)
    sel = select_induced(pull_induced(g, mask))
    for field in OracleSelection._fields:
        np.testing.assert_array_equal(getattr(sel, field),
                                      getattr(want, field), err_msg=field)
    # the local graph built on the device is the one the host built and
    # uploaded from the oracle's arrays, bitwise
    n_sub = len(want.labels)
    ref = new_graph(want.n_cap, want.e_cap,
                    labels=want.labels if n_sub else None,
                    senders=want.senders, receivers=want.receivers)
    for field in DynamicGraph._fields:
        a, b = np.asarray(getattr(sel.graph, field)), \
            np.asarray(getattr(ref, field))
        assert a.dtype == b.dtype, field
        np.testing.assert_array_equal(a, b, err_msg=field)
    # and the composed extraction hands the matcher the same arrays
    sub = extract_induced(g, mask, ell_k=ell_k)
    assert (sub.n_nodes, sub.n_edges) == (n_sub, len(want.senders))
    np.testing.assert_array_equal(sub.local_to_global, want.local_to_global)
    if ell_k is None:
        assert sub.ell is None
    else:
        tile = build_ell_host(want.receivers, want.senders, want.n_cap,
                              k=ell_k, r_cap=ell_row_capacity(
                                  want.n_cap, want.e_cap, ell_k))
        got = induced_tile(sel, ell_k)
        for field in ("cols", "vals", "row_ids", "mask"):
            np.testing.assert_array_equal(getattr(got, field),
                                          getattr(tile, field))
            np.testing.assert_array_equal(np.asarray(getattr(sub.ell, field)),
                                          getattr(tile, field))


# -- the whole graph no longer crosses to the host ----------------------------

def _server(obs: ObsConfig, e_max: int) -> MatchServer:
    cfg = IGPMConfig(n_max=128, e_max=e_max, ell_width=8, rwr_iters=6,
                     rwr_iters_incremental=2, top_k_patterns=4,
                     init_community_size=32, backend="ell")
    # a recompute set never exceeds twice the live vertices: every step
    # takes the induced path
    serving = ServingConfig(obs=obs, microbatch_window=64,
                            full_graph_frac=2.0)
    return MatchServer(cfg, query_zoo(2), serving, seed=0)


def _stream(n_steps: int, e_max: int):
    from repro.data.temporal import TemporalGraphSpec, generate_stream
    # additions only: no removal, so no store pruning (which reads the
    # graph's arcs for its liveness mask)
    spec = TemporalGraphSpec("s1", "sparse_dense", n_vertices=128,
                             n_edges=512, n_steps=16, seed=5)
    return generate_stream(spec, e_max=e_max, n_measured_steps=n_steps,
                           u_max=128)


def _e_cap(n_edges: int) -> int:
    return max(256, 1 << (n_edges - 1).bit_length())


@pytest.mark.slow
def test_traced_induced_step_pulls_kept_arcs_not_the_graph():
    e_max = 65536                           # senders + receivers: 512 KiB
    srv = _server(ObsConfig(enabled=True), e_max)
    pem = srv.engine.pem
    stream = _stream(4, e_max)
    g = stream.graph
    checked = 0
    for upd in stream.updates:
        srv.submit_update(upd)
        builds = pem.recluster_count
        g, st = srv.step(g)
        assert "extract" in st.stage_s      # the induced path ran
        if pem.recluster_count != builds:
            continue                        # PEM read the arcs to cluster
        checked += 1
        # edge_mask + node_mask + labels + the kept arcs + small reads;
        # the whole graph's senders and receivers alone exceed it
        bound = (g.edge_mask.nbytes + g.node_mask.nbytes + g.labels.nbytes
                 + 8 * _e_cap(st.subgraph_edges) + 64 * 1024)
        assert st.counts["d2h_bytes"] < bound
        assert g.senders.nbytes + g.receivers.nbytes > bound
    assert checked >= 2
    # a second extraction at the same capacities compiles nothing
    first, second = np.zeros((2, g.n_max), bool)
    first[:40] = second[1:41] = True
    assert (oracle_select(g, first)[4:] == oracle_select(g, second)[4:])
    extract_induced(g, first, ell_k=8)
    scope = srv.obs.begin_step(99)
    extract_induced(g, second, ell_k=8)
    srv.obs.end_step(99)
    assert scope.counts["compiles"] == 0
    assert scope.counts["d2h_bytes"] > 0
