"""Induced-subgraph extraction — the 'partial' in Partial Execution Manager.

IGPM's speedup (paper §IV-D) comes from running G-Ray only on the subgraph
induced by the update-touched communities, not the full graph. The device
selects that subgraph's arcs from the resident graph and compacts them, in
slot order and local ids, into buffers whose capacities are rounded up to
powers of two ("static-shape bucketing"); only those arcs cross to the
host. Every bucket is a distinct jit signature, so a handful of
compilations cover the whole stream while sweep cost tracks the *live*
subgraph size. With ``ell_k`` set, the extraction also emits the
bucket's incoming-adjacency ELL tile directly from the kept-edge arrays —
no COO round trip — sized to the bucket's static row capacity so the ELL
matcher path compiles once per bucket too (DESIGN.md §2). Patterns that
cross community boundaries are missed — the exact limitation the paper
concedes for cycle/dense queries (§III-D).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.graph import DynamicGraph
from repro.obs.trace import to_host, uploaded
from repro.sparse.ell import (EllGraph, build_ell_host, ell_row_capacity,
                              ell_to_device)


class Subgraph(NamedTuple):
    graph: DynamicGraph     # local-id graph (bucketed capacity)
    local_to_global: np.ndarray  # int64[n_cap] (−1 pad)
    n_nodes: int
    n_edges: int
    ell: Optional[EllGraph] = None  # incoming-adjacency ELL tile (bucketed)


class Selection(NamedTuple):
    """The induced subgraph in local ids at bucketed capacity (``n_cap``
    vertices, ``e_cap`` arcs): its arcs and labels on the host, and the
    same subgraph built on the device."""

    senders: np.ndarray     # local ids of the kept arcs
    receivers: np.ndarray
    labels: np.ndarray      # int32[n_sub]
    local_to_global: np.ndarray  # int64[n_cap] (−1 pad)
    n_cap: int
    e_cap: int
    graph: DynamicGraph     # local-id graph on the device


class Pulled(NamedTuple):
    """What the device selection sends back: the local-id graph it built,
    and that graph's arcs and labels copied to the host at capacity."""

    graph: DynamicGraph
    senders: np.ndarray     # int32[e_cap]; the first ``n_edges`` are kept arcs
    receivers: np.ndarray
    labels: np.ndarray      # int32[n_cap]; one per member, then padding
    members: np.ndarray     # global ids of the mask's vertices, ascending
    n_edges: int


def _pow2(x: int, floor: int) -> int:
    return max(floor, 1 << int(np.ceil(np.log2(max(x, 1)))))


# arcs per iteration of the selection loop: the loop runs to the edge
# cursor, so its cost follows the live arcs, not ``e_max``
_CHUNK = 65536


def _running_count(flags: jnp.ndarray, width: int = 1024) -> jnp.ndarray:
    """Inclusive running count of ``flags`` (int32): counted within rows
    of ``width``, then across the rows' totals. One ``cumsum`` over a
    long axis takes the v5e compiler seconds (~6.6 s at 262,144); rows
    of 1,024 take it a fraction of one."""
    n = flags.shape[0]
    rows = -(-n // width)
    x = jnp.pad(flags.astype(jnp.int32), (0, rows * width - n))
    inner = jnp.cumsum(x.reshape(rows, width), axis=1)
    before = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    return (inner + before[:, None]).reshape(-1)[:n]


@jax.jit
def _select(senders: jnp.ndarray, receivers: jnp.ndarray,
            edge_mask: jnp.ndarray, n_edges: jnp.ndarray, m: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The arcs with both ends in ``m``, in slot order and local ids,
    packed at the front of two buffers (zeros behind), and their count.
    Slots at or past the cursor ``n_edges`` are never written
    (``add_edges`` fills slots at the cursor, nothing else adds arcs), so
    the scan stops there, a chunk at a time: each chunk's kept arcs are
    packed by a scatter into a chunk-sized buffer, which lands at the
    running count (a scatter into the whole buffer takes the v5e
    compiler ~10 s)."""
    e_max = senders.shape[0]
    chunk = min(_CHUNK, e_max)
    local = jnp.where(m, _running_count(m) - 1, -1)

    def body(i, carry):
        ls, lr, count = carry
        lo = i * chunk
        start = jnp.minimum(lo, e_max - chunk)   # the last chunk may overlap
        s = lax.dynamic_slice(senders, (start,), (chunk,))
        r = lax.dynamic_slice(receivers, (start,), (chunk,))
        em = lax.dynamic_slice(edge_mask, (start,), (chunk,))
        ls_c, lr_c = local[s], local[r]
        fresh = start + jnp.arange(chunk) >= lo
        keep = em & fresh & (ls_c >= 0) & (lr_c >= 0)
        at = jnp.where(keep, _running_count(keep) - 1, chunk)  # chunk: dropped
        packed = jnp.zeros(chunk, jnp.int32)
        # what lands past this chunk's kept arcs is zeros, and the next
        # chunk's arcs overwrite it
        return (lax.dynamic_update_slice(
                    ls, packed.at[at].set(ls_c, mode="drop"), (count,)),
                lax.dynamic_update_slice(
                    lr, packed.at[at].set(lr_c, mode="drop"), (count,)),
                count + keep.sum(dtype=jnp.int32))

    n_chunks = (jnp.minimum(n_edges, e_max) + chunk - 1) // chunk
    # a chunk's room past e_max: an update never has to be moved back
    zero = jnp.zeros(e_max + chunk, jnp.int32)
    return lax.fori_loop(0, n_chunks, body,
                         (zero, zero, jnp.zeros((), jnp.int32)))


@functools.partial(jax.jit, static_argnames=("e_cap",))
def _compact(ls: jnp.ndarray, lr: jnp.ndarray, count: jnp.ndarray,
             labels: jnp.ndarray, ids: jnp.ndarray, *,
             e_cap: int) -> DynamicGraph:
    """The ``(len(ids), e_cap)`` local graph ``new_graph`` would build from
    the packed arcs and the member ids (``ids``: −1 pad)."""
    n_cap = ids.shape[0]
    if e_cap > ls.shape[0]:
        ls, lr = (jnp.pad(a, (0, e_cap - a.shape[0])) for a in (ls, lr))
    ls, lr = ls[:e_cap], lr[:e_cap]
    live = jnp.arange(e_cap) < count
    node = ids >= 0
    lab = jnp.where(node, labels[jnp.maximum(ids, 0)], 0)
    deg = jnp.zeros(n_cap, jnp.float32).at[jnp.where(live, ls, n_cap)].add(
        1.0, mode="drop")
    return DynamicGraph(ls, lr, live, lab, node, deg, count)


# The extraction's phases, in order. The engine calls them one by one
# under the child spans of ``engine/extract`` (DESIGN.md §8);
# ``extract_induced`` runs them back to back.

def pull_induced(g: DynamicGraph, mask: np.ndarray, n_floor: int = 64,
                 e_floor: int = 256) -> Pulled:
    """Select and pack the induced arcs on the device, then copy back
    only those arcs (not the graph)."""
    mask = np.asarray(mask, bool)
    members = np.flatnonzero(mask)
    ids = np.full(_pow2(len(members), n_floor), -1, np.int32)
    ids[:len(members)] = members
    m, ids = uploaded((jnp.asarray(mask), jnp.asarray(ids)))
    ls, lr, count = _select(g.senders, g.receivers, g.edge_mask, g.n_edges,
                            m)
    n_edges = int(to_host(count))
    sub = _compact(ls, lr, count, g.labels, ids,
                   e_cap=_pow2(n_edges, e_floor))
    for a in (sub.senders, sub.receivers, sub.labels):
        a.copy_to_host_async()
    return Pulled(sub, to_host(sub.senders), to_host(sub.receivers),
                  to_host(sub.labels), members, n_edges)


def select_induced(p: Pulled) -> Selection:
    """The host's part: trim the pulled arrays and map local ids back."""
    n_sub = len(p.members)
    l2g = np.full(p.graph.n_max, -1, np.int64)
    l2g[:n_sub] = p.members
    return Selection(p.senders[:p.n_edges], p.receivers[:p.n_edges],
                     p.labels[:n_sub], l2g, p.graph.n_max, p.graph.e_max,
                     p.graph)


def induced_tile(sel: Selection, ell_k: int) -> EllGraph:
    """The bucket's incoming-adjacency ELL tile, built on the host."""
    # row owner = receiver: the gather direction of the RWR/BFS sweeps
    return build_ell_host(sel.receivers, sel.senders, sel.n_cap, k=ell_k,
                          r_cap=ell_row_capacity(sel.n_cap, sel.e_cap, ell_k))


def upload_induced(sel: Selection,
                   tile: Optional[EllGraph] = None) -> Subgraph:
    """Put the host-built tile on the device beside the subgraph."""
    ell = None if tile is None else uploaded(ell_to_device(tile))
    return Subgraph(sel.graph, sel.local_to_global, len(sel.labels),
                    len(sel.senders), ell)


def extract_induced(g: DynamicGraph, mask: np.ndarray,
                    n_floor: int = 64, e_floor: int = 256,
                    ell_k: Optional[int] = None) -> Subgraph:
    """Induced subgraph over ``mask`` with bucketed capacities."""
    sel = select_induced(pull_induced(g, mask, n_floor, e_floor))
    return upload_induced(sel, None if ell_k is None
                          else induced_tile(sel, ell_k))


def remap_matched(matched: np.ndarray, local_to_global: np.ndarray) -> np.ndarray:
    """Map local matched-vertex ids back to global ids (−1 stays −1)."""
    out = np.where(matched >= 0,
                   local_to_global[np.clip(matched, 0, None)], -1)
    return out.astype(np.int64)
