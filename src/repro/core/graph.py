"""Dynamic attributed graph with static (jit-able) shapes.

The paper's input is a stream of timestamped updates over an attributed
graph: edge additions, edge removals, vertex label changes (§III-B). We keep
preallocated COO buffers (capacity ``e_max``) + masks so every update and
every RWR sweep is a fixed-shape jitted program; the edge cursor and the
degree vector are maintained incrementally.

Graphs are stored *directed*; undirected inputs insert both arcs. All arrays
live on device; builders accept numpy.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import to_host, uploaded
from repro.sparse.ell import (EllGraph, build_ell, build_ell_sharded,
                              ell_block_capacity, ell_row_capacity)


class PartitionOverflowError(RuntimeError):
    """A receiver slice's static edge capacity was exceeded.

    Raised by the partitioned-storage router (:class:`EdgePartition`) and
    the partitioned ELL mirror when a slice's LIVE arcs outgrow its static
    per-slice capacity — the deterministic compaction spill already ran,
    so this is a real capacity breach, not cursor fragmentation. The
    message names the slice and the overage; the fix is more headroom
    (``partition_slice_capacity``) or a coarser partition."""


class DynamicGraph(NamedTuple):
    senders: jnp.ndarray    # int32[e_max]
    receivers: jnp.ndarray  # int32[e_max]
    edge_mask: jnp.ndarray  # bool[e_max]
    labels: jnp.ndarray     # int32[n_max]
    node_mask: jnp.ndarray  # bool[n_max]
    degree: jnp.ndarray     # f32[n_max]  (out-degree over live edges)
    n_edges: jnp.ndarray    # int32 scalar — edge cursor (monotone)

    @property
    def n_max(self) -> int:
        return self.labels.shape[0]

    @property
    def e_max(self) -> int:
        return self.senders.shape[0]


class UpdateBatch(NamedTuple):
    """One timestep of graph updates, padded to static widths.

    add_*:   endpoints of added arcs (u_max wide, masked)
    rem_*:   endpoints of removed arcs
    lab_ids/lab_vals: vertex label changes
    """

    add_src: jnp.ndarray
    add_dst: jnp.ndarray
    add_mask: jnp.ndarray
    rem_src: jnp.ndarray
    rem_dst: jnp.ndarray
    rem_mask: jnp.ndarray
    lab_ids: jnp.ndarray
    lab_vals: jnp.ndarray
    lab_mask: jnp.ndarray

    @staticmethod
    def empty(u_max: int) -> "UpdateBatch":
        z = jnp.zeros((u_max,), jnp.int32)
        f = jnp.zeros((u_max,), bool)
        return UpdateBatch(z, z, f, z, z, f, z, z, f)

    @staticmethod
    def additions(src: np.ndarray, dst: np.ndarray, u_max: int,
                  undirected: bool = True) -> "UpdateBatch":
        """Host helper: pack an edge-addition batch (optionally both arcs)."""
        return UpdateBatch.mixed(add_src=src, add_dst=dst, u_max=u_max,
                                 undirected=undirected)

    @staticmethod
    def removals(src: np.ndarray, dst: np.ndarray, u_max: int,
                 undirected: bool = True) -> "UpdateBatch":
        """Host helper: pack an edge-removal batch (optionally both arcs)."""
        return UpdateBatch.mixed(rem_src=src, rem_dst=dst, u_max=u_max,
                                 undirected=undirected)

    @staticmethod
    def mixed(add_src: Optional[np.ndarray] = None,
              add_dst: Optional[np.ndarray] = None,
              rem_src: Optional[np.ndarray] = None,
              rem_dst: Optional[np.ndarray] = None,
              lab_ids: Optional[np.ndarray] = None,
              lab_vals: Optional[np.ndarray] = None,
              u_max: int = 512, undirected: bool = True) -> "UpdateBatch":
        """Host helper: one timestep mixing additions, removals, and label
        changes — the churn-capable constructor deletion-heavy streams use.

        ``undirected`` inserts/removes both arcs of every edge. Each lane
        (add/remove/label) is padded to ``u_max`` independently, mirroring
        the field layout :func:`apply_update` consumes.
        """
        def _arcs(s, d):
            if s is None:
                return np.zeros(0, np.int32), np.zeros(0, np.int32)
            s = np.asarray(s, np.int32)
            d = np.asarray(d, np.int32)
            if undirected:
                s, d = np.concatenate([s, d]), np.concatenate([d, s])
            return s, d

        def _pack(a: np.ndarray) -> jnp.ndarray:
            if len(a) > u_max:
                raise ValueError(
                    f"update batch {len(a)} exceeds u_max {u_max}")
            return jnp.asarray(np.pad(a, (0, u_max - len(a))))

        a_s, a_d = _arcs(add_src, add_dst)
        r_s, r_d = _arcs(rem_src, rem_dst)
        l_i = (np.zeros(0, np.int32) if lab_ids is None
               else np.asarray(lab_ids, np.int32))
        l_v = (np.zeros(0, np.int32) if lab_vals is None
               else np.asarray(lab_vals, np.int32))
        lanes = jnp.arange(u_max)
        return UpdateBatch(
            add_src=_pack(a_s), add_dst=_pack(a_d),
            add_mask=lanes < len(a_s),
            rem_src=_pack(r_s), rem_dst=_pack(r_d),
            rem_mask=lanes < len(r_s),
            lab_ids=_pack(l_i), lab_vals=_pack(l_v),
            lab_mask=lanes < len(l_i),
        )


def new_graph(n_max: int, e_max: int, labels: Optional[np.ndarray] = None,
              senders: Optional[np.ndarray] = None,
              receivers: Optional[np.ndarray] = None,
              n_nodes: Optional[int] = None) -> DynamicGraph:
    """Allocate a graph with capacity (n_max, e_max), optionally pre-filled."""
    lab = np.zeros(n_max, np.int32)
    nm = np.zeros(n_max, bool)
    if labels is not None:
        lab[: len(labels)] = labels
        nm[: len(labels)] = True
    elif n_nodes is not None:
        nm[:n_nodes] = True
    s = np.zeros(e_max, np.int32)
    r = np.zeros(e_max, np.int32)
    em = np.zeros(e_max, bool)
    ne = 0
    if senders is not None:
        assert receivers is not None
        ne = len(senders)
        if ne > e_max:
            raise ValueError(f"{ne} initial edges exceed e_max {e_max}")
        s[:ne] = senders
        r[:ne] = receivers
        em[:ne] = True
    deg = np.zeros(n_max, np.float32)
    if ne:
        np.add.at(deg, s[:ne], 1.0)
    return DynamicGraph(jnp.asarray(s), jnp.asarray(r), jnp.asarray(em),
                        jnp.asarray(lab), jnp.asarray(nm), jnp.asarray(deg),
                        jnp.asarray(ne, jnp.int32))


def add_edges(g: DynamicGraph, src: jnp.ndarray, dst: jnp.ndarray,
              mask: jnp.ndarray) -> DynamicGraph:
    """Append masked arc batch at the cursor (jit-able, fixed batch width)."""
    u = src.shape[0]
    k = mask.astype(jnp.int32)
    # pack live entries contiguously so the cursor advances by popcount(mask)
    pos = jnp.cumsum(k) - k  # slot offset of each live entry
    slots = jnp.where(mask, g.n_edges + pos, g.e_max)  # dead → OOB (dropped)
    senders = g.senders.at[slots].set(src, mode="drop")
    receivers = g.receivers.at[slots].set(dst, mode="drop")
    edge_mask = g.edge_mask.at[slots].set(mask, mode="drop")
    deg = g.degree.at[jnp.where(mask, src, g.n_max)].add(
        mask.astype(g.degree.dtype), mode="drop")
    node_mask = g.node_mask.at[jnp.where(mask, src, g.n_max)].set(True, mode="drop")
    node_mask = node_mask.at[jnp.where(mask, dst, g.n_max)].set(True, mode="drop")
    return g._replace(senders=senders, receivers=receivers,
                      edge_mask=edge_mask, degree=deg, node_mask=node_mask,
                      n_edges=g.n_edges + k.sum())


# largest n_max whose (sender·n_max + receiver) arc key fits int32
# (jax x64 is off, so int64 keys would silently truncate)
_KEYED_REMOVE_N_MAX = 46_000


def remove_edges(g: DynamicGraph, src: jnp.ndarray, dst: jnp.ndarray,
                 mask: jnp.ndarray) -> DynamicGraph:
    """Remove arcs by endpoint match — each masked request kills one live
    copy, earliest slots first; duplicate requests consume duplicate
    copies; requests with no live match are no-ops.

    Vectorized as sort + searchsorted (the seed implementation was a
    sequential ``fori_loop`` scanning all of ``e_max`` per request — at
    serving batch widths that dominated the whole step): count the
    requests per arc key, rank each live arc among live arcs with its key
    (stable → slot order), and kill arcs with rank < request count. This
    removes, per key, the first ``count`` live copies — exactly what the
    sequential first-match loop produced. Graphs too large for an int32
    arc key keep the sequential path.
    """
    if g.n_max > _KEYED_REMOVE_N_MAX:
        return _remove_edges_seq(g, src, dst, mask)
    key_e = g.senders * g.n_max + g.receivers
    key_u = src * g.n_max + dst
    sent = jnp.iinfo(key_e.dtype).max
    ku = jnp.sort(jnp.where(mask, key_u, sent))
    cnt = (jnp.searchsorted(ku, key_e, side="right")
           - jnp.searchsorted(ku, key_e, side="left"))
    ke = jnp.where(g.edge_mask, key_e, sent)
    order = jnp.argsort(ke, stable=True)
    ke_sorted = ke[order]
    rank_sorted = (jnp.arange(g.e_max)
                   - jnp.searchsorted(ke_sorted, ke_sorted, side="left"))
    rank = jnp.zeros(g.e_max, rank_sorted.dtype).at[order].set(rank_sorted)
    kill = g.edge_mask & (rank < cnt)
    deg = g.degree.at[g.senders].add(-kill.astype(g.degree.dtype))
    return g._replace(edge_mask=g.edge_mask & ~kill, degree=deg)


def _remove_edges_seq(g: DynamicGraph, src: jnp.ndarray, dst: jnp.ndarray,
                      mask: jnp.ndarray) -> DynamicGraph:
    """Sequential first-match removal (huge-graph fallback)."""
    def body(i, carry):
        em, deg = carry
        hit = (g.senders == src[i]) & (g.receivers == dst[i]) & em & mask[i]
        first = jnp.argmax(hit)  # 0 if no hit — guarded by any_hit
        any_hit = hit.any()
        em = em.at[first].set(jnp.where(any_hit, False, em[first]))
        deg = deg.at[src[i]].add(jnp.where(any_hit, -1.0, 0.0))
        return em, deg

    em, deg = jax.lax.fori_loop(0, src.shape[0], body,
                                (g.edge_mask, g.degree))
    return g._replace(edge_mask=em, degree=deg)


def set_labels(g: DynamicGraph, ids: jnp.ndarray, vals: jnp.ndarray,
               mask: jnp.ndarray) -> DynamicGraph:
    idx = jnp.where(mask, ids, g.n_max)
    return g._replace(labels=g.labels.at[idx].set(vals, mode="drop"))


def apply_update(g: DynamicGraph, upd: UpdateBatch) -> DynamicGraph:
    g = add_edges(g, upd.add_src, upd.add_dst, upd.add_mask)
    g = remove_edges(g, upd.rem_src, upd.rem_dst, upd.rem_mask)
    g = set_labels(g, upd.lab_ids, upd.lab_vals, upd.lab_mask)
    return g


def updated_vertices(g: DynamicGraph, upd: UpdateBatch,
                     v_max: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """V_l of the paper: endpoints of updated arcs + relabelled vertices.

    Returns (ids int32[v_max], mask bool[v_max]) — duplicates permitted
    (consumers operate on the implied boolean vertex mask).
    """
    ids = jnp.concatenate([upd.add_src, upd.add_dst, upd.rem_src,
                           upd.rem_dst, upd.lab_ids])
    mk = jnp.concatenate([upd.add_mask, upd.add_mask, upd.rem_mask,
                          upd.rem_mask, upd.lab_mask])
    if ids.shape[0] > v_max:
        raise ValueError(f"v_max {v_max} < update width {ids.shape[0]}")
    pad = v_max - ids.shape[0]
    return (jnp.pad(ids, (0, pad)), jnp.pad(mk, (0, pad)))


def vertex_mask(ids: jnp.ndarray, mask: jnp.ndarray, n_max: int) -> jnp.ndarray:
    """Boolean vertex mask from a padded id list."""
    out = jnp.zeros((n_max + 1,), bool)
    return out.at[jnp.where(mask, ids, n_max)].set(True)[:n_max]


def transition_weights(g: DynamicGraph) -> jnp.ndarray:
    """Per-arc random-walk weight 1/deg(sender), 0 for dead arcs."""
    safe = jnp.maximum(g.degree, 1.0)
    w = 1.0 / safe[g.senders]
    return jnp.where(g.edge_mask, w, 0.0)


# ---------------------------------------------------------------------------
# Edge-partitioned COO storage (receiver-sliced) + host update router
# ---------------------------------------------------------------------------

def partition_slice_capacity(e_max: int, n_shards: int,
                             headroom: float = 1.25) -> int:
    """Static per-slice arc capacity of the partitioned layout.

    ``headroom > 1`` absorbs receiver skew: a perfectly balanced stream
    needs ``e_max / n_shards`` slots per slice, real streams concentrate
    some receivers. At the default 1.25x the per-device edge footprint is
    0.3125x the replicated arrays for 4 slices.
    """
    return int(np.ceil(headroom * e_max / n_shards))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PartitionedEdges:
    """Receiver-sliced COO edge arrays — device view of :class:`EdgePartition`.

    Row ``d`` holds only the arcs whose receiver lives in vertex slice
    ``[d*n_loc, (d+1)*n_loc)``, in global insertion order, with receivers
    stored slice-LOCAL (``v - d*n_loc``). Under the graph mesh axis each
    device sees its ``(1, e_cap_slice)`` block, so the RWR/reach sweeps
    segment-reduce straight into local segments — no receiver masking —
    and all_gather the slices back (DESIGN.md §10).
    """

    senders: jnp.ndarray        # int32[n_shards, e_cap_slice] — global ids
    receivers_loc: jnp.ndarray  # int32[n_shards, e_cap_slice] — slice-local
    mask: jnp.ndarray           # bool[n_shards, e_cap_slice]
    n_loc: int                  # static vertex-slice width

    @property
    def n_shards(self) -> int:
        return self.senders.shape[0]

    @property
    def e_cap_slice(self) -> int:
        return self.senders.shape[1]

    def tree_flatten(self):
        return (self.senders, self.receivers_loc, self.mask), self.n_loc

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux)


class EdgePartition:
    """Host-maintained receiver-partitioned edge store for a
    :class:`DynamicGraph`, plus the update router that keeps it fresh
    (DESIGN.md §10).

    ``rebuild`` splits the live COO arcs by receiver slice, preserving
    global slot order inside each slice. ``refresh`` routes each
    :class:`UpdateBatch` by destination slice on the host in O(|update|):

    - additions mirror ``add_edges`` arc-for-arc — a global cursor tracks
      ``g.n_edges`` so arcs the replicated path drops past ``e_max`` are
      dropped here too — and append at the owning slice's fill cursor;
    - removals kill the first live copy of (u, v) in slice slot order,
      which IS global slot order because every copy of an arc lands in the
      receiver owner's slice (matching ``remove_edges``/``EllCache``);
    - when a slice's fill cursor hits ``e_cap_slice`` with dead slots
      below it, the deterministic spill policy compacts that slice in
      place (live arcs keep their relative order, so reduction orders are
      unchanged); if the LIVE count itself would exceed the capacity the
      router raises :class:`PartitionOverflowError` naming the slice and
      the overage.

    Because per-vertex slot multisets and their relative order match the
    replicated arrays exactly, partitioned sweeps are bit-identical to
    replicated ones: dead slots contribute exact zeros (+0.0 into
    non-negative partial sums / 0.0 or -inf into the reach max, identical
    in both layouts) and the all_gather concatenation does no arithmetic.
    """

    def __init__(self, n_max: int, e_max: int, n_shards: int,
                 e_cap_slice: Optional[int] = None,
                 headroom: float = 1.25):
        if n_max % n_shards:
            raise ValueError(
                f"n_max {n_max} not divisible by n_shards {n_shards}")
        self.n_max = n_max
        self.e_max = e_max
        self.n_shards = n_shards
        self.n_loc = n_max // n_shards
        self.e_cap_slice = (partition_slice_capacity(e_max, n_shards,
                                                     headroom)
                            if e_cap_slice is None else e_cap_slice)
        self._last: Optional[DynamicGraph] = None
        self.n_rebuilds = 0
        self.n_compactions = 0

    # -- capacity / introspection -------------------------------------------

    def slice_nbytes(self) -> int:
        """Per-device bytes of one slice's edge arrays (int32 senders +
        int32 local receivers + bool mask)."""
        return self.e_cap_slice * (4 + 4 + 1)

    @staticmethod
    def replicated_nbytes(e_max: int) -> int:
        """Per-device bytes of the replicated COO edge arrays."""
        return e_max * (4 + 4 + 1)

    def occupancy(self) -> float:
        """Worst live-arc fill fraction across slices ∈ [0, 1] — the
        overflow-proximity signal the health watchdog degrades on before
        :class:`PartitionOverflowError` fires (0.0 before any rebuild)."""
        live = getattr(self, "_live", None)
        if not live:
            return 0.0
        return max(live) / self.e_cap_slice

    def _overflow(self, d: int, live: int) -> None:
        raise PartitionOverflowError(
            f"edge slice {d} (receivers [{d * self.n_loc}, "
            f"{(d + 1) * self.n_loc})): {live} live arcs exceed the static "
            f"slice capacity {self.e_cap_slice} by "
            f"{live - self.e_cap_slice} — raise the partition headroom, "
            f"e_max, or the slice count")

    # -- full (re)build ------------------------------------------------------

    def rebuild(self, g: DynamicGraph) -> None:
        """Compact host+device slices from the live edge set of ``g``."""
        em = to_host(g.edge_mask)
        s = to_host(g.senders)
        r = to_host(g.receivers)
        cap = self.e_cap_slice
        send = np.zeros((self.n_shards, cap), np.int32)
        recv = np.zeros((self.n_shards, cap), np.int32)
        mask = np.zeros((self.n_shards, cap), bool)
        self._fill: List[int] = []
        self._live: List[int] = []
        owner = r // self.n_loc
        for d in range(self.n_shards):
            idx = np.nonzero(em & (owner == d))[0]  # ascending = slot order
            if len(idx) > cap:
                self._overflow(d, len(idx))
            send[d, : len(idx)] = s[idx]
            recv[d, : len(idx)] = r[idx] - d * self.n_loc
            mask[d, : len(idx)] = True
            self._fill.append(len(idx))
            self._live.append(len(idx))
        self._send_h, self._recv_h, self._mask_h = send, recv, mask
        self._send_d, self._recv_d, self._mask_d = uploaded(
            (jnp.asarray(send), jnp.asarray(recv), jnp.asarray(mask)))
        self._cursor = int(to_host(g.n_edges))
        self._last = g
        self.n_rebuilds += 1

    # -- incremental refresh -------------------------------------------------

    def _compact(self, d: int) -> None:
        """Deterministic spill policy: drop the dead slots of slice ``d``,
        keeping live arcs in their existing (global-slot) order."""
        fill = self._fill[d]
        keep = np.nonzero(self._mask_h[d, :fill])[0]
        nl = len(keep)
        self._send_h[d, :nl] = self._send_h[d, keep]
        self._recv_h[d, :nl] = self._recv_h[d, keep]
        self._mask_h[d, :] = False
        self._mask_h[d, :nl] = True
        self._fill[d] = nl
        self.n_compactions += 1

    def refresh(self, g: DynamicGraph, g2: DynamicGraph,
                upd: UpdateBatch) -> None:
        """Route ``upd`` (which turned ``g`` into ``g2``) into the slices."""
        if self._last is not g:
            self.rebuild(g)
        touched: Set[Tuple[int, int]] = set()
        dirty: Set[int] = set()  # compacted slices → full-row upload
        add_src = to_host(upd.add_src)
        add_dst = to_host(upd.add_dst)
        add_mask = to_host(upd.add_mask)
        slot = self._cursor
        for u, v, m in zip(add_src, add_dst, add_mask):
            if not m:
                continue
            if slot < self.e_max and 0 <= v < self.n_max:
                d = int(v) // self.n_loc
                j = self._fill[d]
                if j >= self.e_cap_slice:
                    if self._live[d] >= self.e_cap_slice:
                        self._overflow(d, self._live[d] + 1)
                    self._compact(d)
                    dirty.add(d)
                    j = self._fill[d]
                self._send_h[d, j] = u
                self._recv_h[d, j] = int(v) - d * self.n_loc
                self._mask_h[d, j] = True
                self._fill[d] = j + 1
                self._live[d] += 1
                touched.add((d, j))
            slot += 1
        self._cursor += int(add_mask.sum())

        rem_src = to_host(upd.rem_src)
        rem_dst = to_host(upd.rem_dst)
        rem_mask = to_host(upd.rem_mask)
        for u, v, m in zip(rem_src, rem_dst, rem_mask):
            if not (m and 0 <= v < self.n_max):
                continue
            d = int(v) // self.n_loc
            vl = int(v) - d * self.n_loc
            fill = self._fill[d]
            hit = np.nonzero(self._mask_h[d, :fill]
                             & (self._send_h[d, :fill] == u)
                             & (self._recv_h[d, :fill] == vl))[0]
            if len(hit):
                j = int(hit[0])
                self._mask_h[d, j] = False
                self._live[d] -= 1
                touched.add((d, j))
        self._push(touched, dirty)
        self._last = g2

    def _push(self, touched: Set[Tuple[int, int]], dirty: Set[int]) -> None:
        """Scatter the final host values of touched slots to device (pow-2
        padded index vectors, as the ELL mirror does); compacted slices
        upload as full rows."""
        for d in sorted(dirty):
            self._send_d = self._send_d.at[d].set(
                uploaded(jnp.asarray(self._send_h[d])))
            self._recv_d = self._recv_d.at[d].set(
                uploaded(jnp.asarray(self._recv_h[d])))
            self._mask_d = self._mask_d.at[d].set(
                uploaded(jnp.asarray(self._mask_h[d])))
        touched = {(d, j) for d, j in touched if d not in dirty}
        if not touched:
            return

        def _pad(a: np.ndarray, fill) -> jnp.ndarray:
            width = max(1, 1 << int(np.ceil(np.log2(max(len(a), 1)))))
            return uploaded(jnp.asarray(np.concatenate(
                [a, np.full(width - len(a), fill, a.dtype)])))

        dj = np.asarray(sorted(touched), np.int32)
        dd = _pad(dj[:, 0], self.n_shards)  # pad rows → OOB, dropped
        jj = _pad(dj[:, 1], 0)
        sv = _pad(self._send_h[dj[:, 0], dj[:, 1]], 0)
        rv = _pad(self._recv_h[dj[:, 0], dj[:, 1]], 0)
        mv = _pad(self._mask_h[dj[:, 0], dj[:, 1]], False)
        self._send_d = self._send_d.at[dd, jj].set(sv, mode="drop")
        self._recv_d = self._recv_d.at[dd, jj].set(rv, mode="drop")
        self._mask_d = self._mask_d.at[dd, jj].set(mv, mode="drop")

    def update(self, g: DynamicGraph, upd: UpdateBatch) -> DynamicGraph:
        """``apply_update`` + partition refresh; returns the updated graph."""
        if self._last is not g:
            self.rebuild(g)
        g2 = apply_update(g, upd)
        self.refresh(g, g2, upd)
        return g2

    # -- views ---------------------------------------------------------------

    @property
    def part(self) -> PartitionedEdges:
        """The store as a :class:`PartitionedEdges` device pytree."""
        return PartitionedEdges(self._send_d, self._recv_d, self._mask_d,
                                self.n_loc)


# ---------------------------------------------------------------------------
# ELL mirror of the live edge set (the matching hot path's layout)
# ---------------------------------------------------------------------------

def ell_from_graph(g: DynamicGraph, k: int,
                   r_cap: Optional[int] = None,
                   n_shards: int = 1) -> EllGraph:
    """Fresh *incoming*-adjacency ELL of the live arcs (host-side build).

    Row owner = receiver, columns = senders, unit weights: exactly the
    gather direction of the RWR sweep (``agg[v] = Σ_{u→v} …``) and the
    bounded-BFS frontier sweep. ``r_cap`` defaults to the graph's static
    worst case so every graph with the same (n_max, e_max, k) shares one
    jit signature. ``n_shards > 1`` emits the shard-local row-block layout
    of the graph mesh axis instead (``build_ell_sharded`` — per-slice row
    blocks, local ``row_ids``, ``r_cap`` then caps one block).
    """
    em = to_host(g.edge_mask)
    s = to_host(g.senders)[em]
    r = to_host(g.receivers)[em]
    if n_shards > 1:
        if r_cap is None:
            r_cap = ell_block_capacity(g.n_max, g.e_max, k, n_shards)
        return build_ell_sharded(r, s, g.n_max, n_shards, k=k,
                                 r_cap_block=r_cap)
    if r_cap is None:
        r_cap = ell_row_capacity(g.n_max, g.e_max, k)
    return build_ell(r, s, g.n_max, k=k, r_cap=r_cap)


class EllCache:
    """Incrementally-maintained ELL mirror of a :class:`DynamicGraph`.

    Converts the live COO edge set to the ELL layout once, then refreshes it
    per :class:`UpdateBatch` in O(|update|) host work + an O(|update|)
    device scatter — instead of an O(E) rebuild per step. Each vertex's
    entries stay compact (removal swaps the last live entry into the hole),
    and vertices whose in-degree outgrows their padded rows allocate spill
    rows from a shared cursor; when the cursor hits the static row capacity
    the cache compacts itself with a full rebuild (DESIGN.md §2).

    The device arrays always have the static bucket shape
    ``(ell_row_capacity(n_max, e_max, k), k)``, so the jitted matcher
    compiles once per graph bucket, not per step.

    ``n_shards > 1`` maintains the shard-local row-block layout of the
    graph mesh axis (DESIGN.md §5): the row axis splits into ``n_shards``
    equal blocks, block ``d`` holds the rows of vertex slice
    ``[d·n_loc, (d+1)·n_loc)`` with slice-local ``row_ids`` and its own
    spill cursor, and ``ell.n`` is the slice width ``n_loc`` — splitting
    the row axis into ``n_shards`` parts hands each device exactly its
    block. The per-vertex entry layout (and therefore every reduction
    order) is identical to the unsharded mirror.

    ``partitioned=True`` (with ``n_shards > 1``) sizes each row block for
    ``partition_slice_capacity(e_max, n_shards)`` arcs instead of the full
    ``e_max`` — the ELL expression of the edge-partitioned layout
    (DESIGN.md §10): the per-device block shrinks ~1/g, and a slice whose
    live in-degree outgrows its block raises
    :class:`PartitionOverflowError` at rebuild instead of growing.
    """

    def __init__(self, n_max: int, e_max: int, k: int, n_shards: int = 1,
                 partitioned: bool = False, headroom: float = 1.25):
        if n_max % n_shards:
            raise ValueError(
                f"n_max {n_max} not divisible by n_shards {n_shards}")
        self.n_max = n_max
        self.e_max = e_max
        self.k = k
        self.n_shards = n_shards
        self.n_loc = n_max // n_shards
        self.partitioned = partitioned and n_shards > 1
        e_cap_block = (partition_slice_capacity(e_max, n_shards, headroom)
                       if self.partitioned else e_max)
        self.r_cap_block = ell_block_capacity(n_max, e_cap_block, k, n_shards)
        self.r_cap = n_shards * self.r_cap_block
        self._vals = jnp.ones((self.r_cap, k), jnp.float32)
        self._last: Optional[DynamicGraph] = None
        self.n_rebuilds = 0

    def occupancy(self) -> float:
        """Worst spill-cursor fill fraction across row blocks ∈ [0, 1] —
        overflow proximity in partitioned mode, where a block that fills
        raises :class:`PartitionOverflowError` at the next rebuild
        instead of growing (0.0 before any rebuild)."""
        next_row = getattr(self, "_next_row", None)
        if not next_row:
            return 0.0
        return max((next_row[d] - d * self.r_cap_block) / self.r_cap_block
                   for d in range(self.n_shards))

    # -- full (re)build ------------------------------------------------------

    def rebuild(self, g: DynamicGraph) -> None:
        """Compact host+device state from the live edge set of ``g``."""
        em = to_host(g.edge_mask)
        s = to_host(g.senders)[em]
        r = to_host(g.receivers)[em]
        n, k = self.n_max, self.k
        deg_in = np.bincount(r, minlength=n)
        rows_per_v = np.maximum(1, -(-deg_in // k))
        # physical start row of every vertex: per-shard compact packing,
        # each shard based at its block offset, with its own spill cursor
        start_v = np.zeros(n, np.int64)
        self._next_row: List[int] = []
        for d in range(self.n_shards):
            lo, hi = d * self.n_loc, (d + 1) * self.n_loc
            cs = (d * self.r_cap_block
                  + np.concatenate([[0], np.cumsum(rows_per_v[lo:hi])]))
            need = int(cs[-1]) - d * self.r_cap_block
            if need > self.r_cap_block:
                # only reachable in partitioned mode (the replicated block
                # capacity covers any in-degree distribution) — a slice's
                # live arcs outgrew its shrunken block
                raise PartitionOverflowError(
                    f"ELL slice {d} (receivers [{lo}, {hi})): "
                    f"{int(deg_in[lo:hi].sum())} live arcs need {need} rows"
                    f" > block capacity {self.r_cap_block} (over by "
                    f"{need - self.r_cap_block} rows) — raise the partition"
                    f" headroom, e_max, or the slice count")
            start_v[lo:hi] = cs[:-1]
            self._next_row.append(int(cs[-1]))
        self._rows: List[List[int]] = [
            list(range(start_v[v], start_v[v] + rows_per_v[v]))
            for v in range(n)]
        self._fill = deg_in.astype(np.int64)
        self._cursor = int(to_host(g.n_edges))

        cols = np.zeros((self.r_cap, k), np.int32)
        mask = np.zeros((self.r_cap, k), bool)
        row_ids = np.zeros(self.r_cap, np.int32)
        for v in range(n):
            row_ids[start_v[v]:start_v[v] + rows_per_v[v]] = v % self.n_loc
        order = np.argsort(r, kind="stable")
        rs, ss = r[order], s[order]
        pos = np.arange(len(rs)) - np.concatenate([[0], np.cumsum(deg_in)])[rs]
        cols[start_v[rs] + pos // k, pos % k] = ss
        mask[start_v[rs] + pos // k, pos % k] = True
        self._cols_h, self._mask_h, self._row_ids_h = cols, mask, row_ids
        self._cols_d, self._mask_d, self._row_ids_d = uploaded(
            (jnp.asarray(cols), jnp.asarray(mask), jnp.asarray(row_ids)))
        self._last = g
        self.n_rebuilds += 1

    # -- incremental refresh -------------------------------------------------

    def _add(self, u: int, v: int, touched: set, new_rows: set) -> bool:
        """Append arc u→v; False if a spill row is unavailable (overflow)."""
        p = int(self._fill[v])
        ri = p // self.k
        if ri == len(self._rows[v]):
            shard = v // self.n_loc
            if self._next_row[shard] >= (shard + 1) * self.r_cap_block:
                return False
            row = self._next_row[shard]
            self._next_row[shard] += 1
            self._rows[v].append(row)
            self._row_ids_h[row] = v % self.n_loc
            new_rows.add(row)
        row = self._rows[v][ri]
        slot = p % self.k
        self._cols_h[row, slot] = u
        self._mask_h[row, slot] = True
        self._fill[v] = p + 1
        touched.add((row, slot))
        return True

    def _remove(self, u: int, v: int, touched: set) -> None:
        """Remove one live copy of arc u→v (no-op when absent) by swapping
        the block's last live entry into the hole."""
        hit = None
        for ri in range((int(self._fill[v]) + self.k - 1) // self.k):
            row = self._rows[v][ri]
            live = self._mask_h[row] & (self._cols_h[row] == u)
            nz = np.nonzero(live)[0]
            if len(nz):
                hit = (row, int(nz[0]))
                break
        if hit is None:
            return
        last_p = int(self._fill[v]) - 1
        last = (self._rows[v][last_p // self.k], last_p % self.k)
        if hit != last:
            self._cols_h[hit] = self._cols_h[last]
            touched.add(hit)
        self._mask_h[last] = False
        touched.add(last)
        self._fill[v] = last_p

    def update(self, g: DynamicGraph, upd: UpdateBatch) -> DynamicGraph:
        """``apply_update`` + ELL refresh; returns the updated graph."""
        if self._last is not g:
            # caller swapped graphs under us (fresh stream / reset) — resync
            self.rebuild(g)
        g2 = apply_update(g, upd)
        self.refresh(g, g2, upd)
        return g2

    def refresh(self, g: DynamicGraph, g2: DynamicGraph,
                upd: UpdateBatch) -> None:
        """Mirror ``upd`` (which turned ``g`` into ``g2``) into the ELL state.

        Mirrors the COO semantics arc-for-arc: additions past the e_max
        cursor are dropped (as ``add_edges`` drops them) and each masked
        removal kills at most one live copy.
        """
        if self._last is not g:
            self.rebuild(g)

        touched: set = set()
        new_rows: set = set()
        overflow = False
        add_src = to_host(upd.add_src)
        add_dst = to_host(upd.add_dst)
        add_mask = to_host(upd.add_mask)
        slot = self._cursor
        for u, v, m in zip(add_src, add_dst, add_mask):
            if not m:
                continue
            if slot < self.e_max and 0 <= v < self.n_max:
                if not self._add(int(u), int(v), touched, new_rows):
                    overflow = True
                    break
            slot += 1
        self._cursor += int(add_mask.sum())
        if not overflow:
            rem_src = to_host(upd.rem_src)
            rem_dst = to_host(upd.rem_dst)
            rem_mask = to_host(upd.rem_mask)
            for u, v, m in zip(rem_src, rem_dst, rem_mask):
                if m and 0 <= v < self.n_max:
                    self._remove(int(u), int(v), touched)

        if overflow:
            self.rebuild(g2)
        else:
            if touched or new_rows:
                self._push(touched, new_rows)
            self._last = g2

    def _push(self, touched: set, new_rows: set) -> None:
        """Scatter the final host values of touched slots to device.

        Index vectors are padded to the next power of two (pad rows point
        past r_cap and are dropped) so the number of scatter jit signatures
        stays logarithmic in the update width.
        """
        def _pad(a: np.ndarray, fill: int) -> jnp.ndarray:
            width = max(1, 1 << int(np.ceil(np.log2(max(len(a), 1)))))
            return uploaded(jnp.asarray(np.concatenate(
                [a, np.full(width - len(a), fill, a.dtype)])))

        if touched:
            rc = np.asarray(sorted(touched), np.int32)
            rr, cc = _pad(rc[:, 0], self.r_cap), _pad(rc[:, 1], 0)
            cv = _pad(self._cols_h[rc[:, 0], rc[:, 1]], 0)
            mv = _pad(self._mask_h[rc[:, 0], rc[:, 1]], False)
            self._cols_d = self._cols_d.at[rr, cc].set(cv, mode="drop")
            self._mask_d = self._mask_d.at[rr, cc].set(mv, mode="drop")
        if new_rows:
            nr = np.asarray(sorted(new_rows), np.int32)
            rr = _pad(nr, self.r_cap)
            rv = _pad(self._row_ids_h[nr], 0)
            self._row_ids_d = self._row_ids_d.at[rr].set(rv, mode="drop")

    # -- views ---------------------------------------------------------------

    @property
    def ell(self) -> EllGraph:
        """The mirror as an :class:`EllGraph`. ``n`` is the per-shard
        segment count: the global vertex count when unsharded, the vertex
        slice width under the graph mesh axis (row blocks + local ids)."""
        return EllGraph(self._cols_d, self._vals, self._row_ids_d,
                        self._mask_d, self.n_loc)
