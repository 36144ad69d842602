"""Induced-subgraph extraction — the 'partial' in Partial Execution Manager.

IGPM's speedup (paper §IV-D) comes from running G-Ray only on the subgraph
induced by the update-touched communities, not the full graph. We gather that
subgraph into compact buffers whose capacities are rounded up to powers of
two ("static-shape bucketing"): every bucket is a distinct jit signature, so
a handful of compilations cover the whole stream while sweep cost tracks the
*live* subgraph size. With ``ell_k`` set, the extraction also emits the
bucket's incoming-adjacency ELL tile directly from the kept-edge arrays —
no COO round trip — sized to the bucket's static row capacity so the ELL
matcher path compiles once per bucket too (DESIGN.md §2). Patterns that
cross community boundaries are missed — the exact limitation the paper
concedes for cycle/dense queries (§III-D).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from repro.core.graph import DynamicGraph, new_graph
from repro.obs.trace import to_host, uploaded
from repro.sparse.ell import (EllGraph, build_ell_host, ell_row_capacity,
                              ell_to_device)


class Subgraph(NamedTuple):
    graph: DynamicGraph     # local-id graph (bucketed capacity)
    local_to_global: np.ndarray  # int64[n_cap] (−1 pad)
    n_nodes: int
    n_edges: int
    ell: Optional[EllGraph] = None  # incoming-adjacency ELL tile (bucketed)


class HostGraph(NamedTuple):
    """The arrays of a graph extraction reads, copied to the host."""

    senders: np.ndarray
    receivers: np.ndarray
    edge_mask: np.ndarray
    labels: np.ndarray


class Selection(NamedTuple):
    """The induced subgraph in local ids, on the host, at bucketed
    capacity (``n_cap`` vertices, ``e_cap`` arcs)."""

    senders: np.ndarray     # local ids of the kept arcs
    receivers: np.ndarray
    labels: np.ndarray      # int32[n_sub]
    local_to_global: np.ndarray  # int64[n_cap] (−1 pad)
    n_cap: int
    e_cap: int


def _pow2(x: int, floor: int) -> int:
    return max(floor, 1 << int(np.ceil(np.log2(max(x, 1)))))


# The extraction's phases, in order. The engine calls them one by one
# under the child spans of ``engine/extract`` (DESIGN.md §8);
# ``extract_induced`` runs them back to back.

def pull_graph(g: DynamicGraph) -> HostGraph:
    """Device→host copies of the arrays the extraction reads."""
    return HostGraph(to_host(g.senders), to_host(g.receivers),
                     to_host(g.edge_mask), to_host(g.labels))


def select_induced(h: HostGraph, mask: np.ndarray, n_floor: int = 64,
                   e_floor: int = 256) -> Selection:
    """Keep the arcs with both ends in ``mask``, remapped to local ids."""
    mask = np.asarray(mask, bool)
    ids = np.where(mask)[0]
    n_sub = len(ids)
    g2l = np.full(len(h.labels), -1, np.int64)
    g2l[ids] = np.arange(n_sub)

    keep = h.edge_mask & mask[h.senders] & mask[h.receivers]
    ls = g2l[h.senders[keep]]
    lr = g2l[h.receivers[keep]]
    n_cap = _pow2(n_sub, n_floor)
    e_cap = _pow2(len(ls), e_floor)
    l2g = np.full(n_cap, -1, np.int64)
    l2g[:n_sub] = ids
    return Selection(ls, lr, h.labels[ids].astype(np.int32), l2g, n_cap,
                     e_cap)


def induced_tile(sel: Selection, ell_k: int) -> EllGraph:
    """The bucket's incoming-adjacency ELL tile, built on the host."""
    # row owner = receiver: the gather direction of the RWR/BFS sweeps
    return build_ell_host(sel.receivers, sel.senders, sel.n_cap, k=ell_k,
                          r_cap=ell_row_capacity(sel.n_cap, sel.e_cap, ell_k))


def upload_induced(sel: Selection,
                   tile: Optional[EllGraph] = None) -> Subgraph:
    """Put the subgraph (and its host-built tile) on the device."""
    n_sub = len(sel.labels)
    # new_graph marks node_mask from the labels' length; the rest of the
    # capacity stays padding
    sub = new_graph(sel.n_cap, sel.e_cap,
                    labels=sel.labels if n_sub else None,
                    senders=sel.senders, receivers=sel.receivers)
    ell = None if tile is None else ell_to_device(tile)
    uploaded((sub, ell))
    return Subgraph(sub, sel.local_to_global, n_sub, len(sel.senders), ell)


def extract_induced(g: DynamicGraph, mask: np.ndarray,
                    n_floor: int = 64, e_floor: int = 256,
                    ell_k: Optional[int] = None) -> Subgraph:
    """Induced subgraph over ``mask`` with bucketed capacities (host-side)."""
    sel = select_induced(pull_graph(g), mask, n_floor, e_floor)
    return upload_induced(sel, None if ell_k is None
                          else induced_tile(sel, ell_k))


def remap_matched(matched: np.ndarray, local_to_global: np.ndarray) -> np.ndarray:
    """Map local matched-vertex ids back to global ids (−1 stays −1)."""
    out = np.where(matched >= 0,
                   local_to_global[np.clip(matched, 0, None)], -1)
    return out.astype(np.int64)
