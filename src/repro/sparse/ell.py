"""ELL-padded adjacency — the TPU-native sparse layout for RWR / SpMM.

Each vertex's neighbor list is padded to a fixed width ``K`` so every sparse
matrix-vector / matrix-matrix product is a *dense* gather + masked reduce:
fully regular access that tiles into VMEM and feeds the VPU/MXU. This is the
hardware adaptation of the paper's CSR/NetworkX loops (DESIGN.md §2).

Rows whose degree exceeds ``K`` spill into duplicate rows via ``row_ids``
(ELL + row-splitting), so no neighbor is ever dropped.

``EllGraph`` is registered as a pytree whose vertex count ``n`` is static
aux data, so an ELL graph can be passed straight through ``jax.jit``
boundaries (the matcher hot path) while ``num_segments=g.n`` stays a Python
int. Builders accept an explicit row capacity ``r_cap`` so that every graph
sharing one ``(n, e_cap, K)`` bucket lowers to one jit signature — the
static-shape convention the dynamic-graph cache relies on (DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EllGraph:
    """Padded neighbor-list graph (static shapes, jit-friendly).

    cols:    int32[R, K]   neighbor ids (arbitrary value where ~mask)
    vals:    f32[R, K]     edge weights (0 where ~mask)
    row_ids: int32[R]      owning vertex of each padded row (row-splitting)
    mask:    bool[R, K]    entry validity
    n:       int           number of vertices (static — pytree aux data)
    """

    cols: jnp.ndarray
    vals: jnp.ndarray
    row_ids: jnp.ndarray
    mask: jnp.ndarray
    n: int

    @property
    def k(self) -> int:
        return self.cols.shape[1]

    @property
    def r(self) -> int:
        return self.cols.shape[0]

    def tree_flatten(self):
        return (self.cols, self.vals, self.row_ids, self.mask), self.n

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux)


def ell_row_capacity(n: int, e_cap: int, k: int) -> int:
    """Worst-case padded-row count for ``e_cap`` live arcs over ``n`` vertices.

    Every vertex owns at least one row and each row beyond the first of a
    vertex accounts for ``k`` arcs, so Σ max(1, ceil(deg/k)) ≤ n + ceil(E/k).
    """
    return n + -(-e_cap // k)


def ell_block_capacity(n: int, e_cap: int, k: int, n_shards: int = 1) -> int:
    """Static row capacity of ONE vertex-slice block of a sharded ELL.

    The slice owns ``n/n_shards`` vertices but, in the worst case, every
    live arc: ``n/n_shards + ceil(E/k)`` rows (the :func:`ell_row_capacity`
    bound applied to the slice).
    """
    return n // n_shards + -(-e_cap // k)


def build_ell(senders: np.ndarray, receivers: np.ndarray, n: int,
              weights: Optional[np.ndarray] = None, k: int = 64,
              r_cap: Optional[int] = None) -> EllGraph:
    """Host-side ELL builder from a COO edge list (numpy).

    Produces rows in vertex order; vertices with degree > k get
    ``ceil(deg/k)`` rows. Isolated vertices still get one (all-masked) row so
    ``row_ids`` always covers ``0..n-1`` at least once. When ``r_cap`` is
    given the row axis is padded (all-masked, row_ids=0) to that fixed
    capacity so same-bucket graphs share a jit signature.
    """
    return ell_to_device(build_ell_host(senders, receivers, n, weights, k,
                                        r_cap))


def ell_to_device(ell: EllGraph) -> EllGraph:
    """Upload an :class:`EllGraph` with numpy leaves to the device."""
    return EllGraph(jnp.asarray(ell.cols), jnp.asarray(ell.vals),
                    jnp.asarray(ell.row_ids), jnp.asarray(ell.mask), ell.n)


def build_ell_host(senders: np.ndarray, receivers: np.ndarray, n: int,
                   weights: Optional[np.ndarray] = None, k: int = 64,
                   r_cap: Optional[int] = None) -> EllGraph:
    """:func:`build_ell` with numpy leaves: nothing is on the device yet."""
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    if weights is None:
        weights = np.ones(senders.shape[0], np.float32)
    order = np.argsort(senders, kind="stable")
    s, r, w = senders[order], receivers[order], weights[order]
    deg = np.bincount(s, minlength=n)
    rows_per_v = np.maximum(1, -(-deg // k))  # ceil, min 1
    row_start = np.concatenate([[0], np.cumsum(rows_per_v)])
    n_rows = int(row_start[-1])
    n_alloc = n_rows if r_cap is None else int(r_cap)
    if n_rows > n_alloc:
        raise ValueError(f"ELL needs {n_rows} rows > capacity {n_alloc}")

    cols = np.zeros((n_alloc, k), np.int32)
    vals = np.zeros((n_alloc, k), np.float32)
    mask = np.zeros((n_alloc, k), bool)
    row_ids = np.zeros(n_alloc, np.int32)
    for v in range(n):
        row_ids[row_start[v]:row_start[v + 1]] = v
    # position of each edge within its vertex block
    edge_pos = np.arange(len(s)) - np.concatenate([[0], np.cumsum(deg)])[s]
    rr = row_start[s] + edge_pos // k
    cc = edge_pos % k
    cols[rr, cc] = r
    vals[rr, cc] = w
    mask[rr, cc] = True
    return EllGraph(cols, vals, row_ids, mask, n)


def build_ell_sharded(senders: np.ndarray, receivers: np.ndarray, n: int,
                      n_shards: int, weights: Optional[np.ndarray] = None,
                      k: int = 64,
                      r_cap_block: Optional[int] = None) -> EllGraph:
    """Shard-local row-block ELL over ``n_shards`` equal vertex slices.

    The row-owner axis (``senders`` here, matching :func:`build_ell`'s
    convention) partitions into contiguous slices of ``n // n_shards``
    vertices; slice ``d`` occupies the row block
    ``[d·r_cap_block, (d+1)·r_cap_block)`` with ``row_ids`` LOCAL to the
    slice and column ids still global. ``EllGraph.n`` becomes the slice
    width — each shard's segment reduction produces its vertex slice and
    the slices concatenate back (``all_gather``) with no arithmetic, which
    is what keeps graph-sharded sweeps bit-identical (DESIGN.md §5).
    Splitting the row axis into ``n_shards`` equal parts (e.g. shard_map
    ``P("g")``) hands every device exactly its block.

    Within a slice the layout algorithm is :func:`build_ell` verbatim, so
    a vertex's entries land in the same relative (row, slot) positions as
    in the unsharded layout — the per-vertex reduction order is preserved.
    """
    if n % n_shards:
        raise ValueError(f"n {n} not divisible by n_shards {n_shards}")
    n_loc = n // n_shards
    if r_cap_block is None:
        r_cap_block = ell_block_capacity(n, len(np.asarray(senders)) or 1,
                                         k, n_shards)
    senders = np.asarray(senders, np.int64)
    receivers = np.asarray(receivers, np.int64)
    if weights is None:
        weights = np.ones(senders.shape[0], np.float32)
    blocks = []
    for d in range(n_shards):
        sel = (senders >= d * n_loc) & (senders < (d + 1) * n_loc)
        blocks.append(build_ell(senders[sel] - d * n_loc, receivers[sel],
                                n_loc, weights=weights[sel], k=k,
                                r_cap=r_cap_block))
    return EllGraph(
        jnp.concatenate([b.cols for b in blocks]),
        jnp.concatenate([b.vals for b in blocks]),
        jnp.concatenate([b.row_ids for b in blocks]),
        jnp.concatenate([b.mask for b in blocks]), n_loc)


def ell_spmm(g: EllGraph, x: jnp.ndarray) -> jnp.ndarray:
    """y[v] = sum_{u in N(v)} w(v,u) * x[u]  for dense x: (n, d) → (n, d)."""
    gathered = x[g.cols]                       # (R, K, d)
    w = jnp.where(g.mask, g.vals, 0.0)
    partial = jnp.einsum("rk,rkd->rd", w.astype(x.dtype), gathered)
    return jax.ops.segment_sum(partial, g.row_ids, num_segments=g.n)


def ell_spmv(g: EllGraph, x: jnp.ndarray) -> jnp.ndarray:
    """y = A @ x for a vector x: (n,) → (n,)."""
    return ell_spmm(g, x[:, None])[:, 0]


def ell_degree(g: EllGraph) -> jnp.ndarray:
    """Weighted out-degree per vertex."""
    w = jnp.where(g.mask, g.vals, 0.0)
    return jax.ops.segment_sum(w.sum(axis=1), g.row_ids, num_segments=g.n)


def dense_adj(g: EllGraph) -> jnp.ndarray:
    """Materialize the dense adjacency (tests only — O(n^2))."""
    a = jnp.zeros((g.n, g.n), g.vals.dtype)
    rows = jnp.repeat(g.row_ids[:, None], g.k, axis=1)
    w = jnp.where(g.mask, g.vals, 0.0)
    return a.at[rows, g.cols].add(w)
