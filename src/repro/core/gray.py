"""G-Ray: best-effort approximate subgraph isomorphism (Tong et al. KDD'07),
vectorized for TPU — the base matcher the paper extends (§III-A).

The three core functions map onto dense array ops:

  seed-finder        → masked top-k over the label-conditioned RWR goodness
  neighbor-expander  → argmax of single-source RWR among label-compatible,
                       unused candidates (k seeds expand in one (n,k) batch)
  bridge             → bounded-hop BFS reachability sweep (hop count of the
                       best connecting path; direct edge ⇒ hop 1 ⇒ exact)

Queries are *data*, not code: every matcher entry point takes the query
tensors (labels/mask/anchor/expansion schedule) as jit arguments, so a
whole bank of standing queries stacked into a :class:`~repro.core.query.
QueryBank` runs through ONE compiled program — :class:`BankGRayMatcher`
vmaps the expansion over the query axis while the expensive sparse sweeps
(single-source RWR and the BFS bridge) run as ONE ``(n, B·k)`` dense block
shared across all queries (DESIGN.md §3). :class:`GRayMatcher` is the
single-query view: a bank of size one with the leading axis squeezed.

The expansion schedule is host-static (``Query.order_*``), so we unroll it
and memoize the per-step source tables by their *source-vertex signature*:
a star-5 query runs ONE RWR for all four expansions instead of four (a
beyond-paper optimization recorded in EXPERIMENTS.md §Perf; the paper
recomputes per function call). In bank mode the signature is the vector of
per-query source vertices, so steps that line up across the bank share one
batched sweep.

Both sparse sweeps run on either the COO gather/segment path or the Pallas
ELL kernels — ``backend="ell"`` routes them through
``repro.kernels.spmv_ell`` given an ELL mirror of the graph (DESIGN.md §2;
see ``repro.core.graph.EllCache``).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import resolve_backend
from repro.core.graph import DynamicGraph, PartitionedEdges, ell_from_graph
from repro.core.rwr import (_owned_mask, label_rwr, label_rwr_adaptive, rwr,
                            rwr_adaptive)
from repro.core.query import Query, QueryBank, stack_queries
from repro.kernels.spmv_ell.ops import ell_reach_kernel
from repro.sparse.ell import EllGraph

_EPS = 1e-12


class GRayResult(NamedTuple):
    """Single-query: leading axis k (seeds). Bank: leading axes (B, k)."""

    matched: jnp.ndarray   # int32[..., q_max] — data vertex per query vertex
    goodness: jnp.ndarray  # f32[...] — Σ log proximity over schedule edges
    hops: jnp.ndarray      # int32[..., qe_max] — best-path hops per edge
    exact: jnp.ndarray     # bool[...] — every query edge is a data edge
    valid: jnp.ndarray     # bool[...] — seed live and all expansions found


def find_seeds(g: DynamicGraph, query: Query, r_lab: jnp.ndarray, k: int,
               seed_filter: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Seed-finder: top-k anchor candidates by label-goodness.

    score(v) = Σ_q log r_lab[v, label(q)]  over live query vertices,
    restricted to v with the anchor's label (and the PEM recompute mask,
    when given — that's the paper's partial execution hook).
    """
    return _find_seeds_arrays(g, r_lab, k, seed_filter, query.labels,
                              query.mask, query.anchor)


def _find_seeds_arrays(g: DynamicGraph, r_lab: jnp.ndarray, k: int,
                       seed_filter: Optional[jnp.ndarray],
                       q_labels: jnp.ndarray, q_mask: jnp.ndarray,
                       anchor: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    logp = jnp.log(r_lab + _EPS)                      # (n, L)
    score = (logp[:, q_labels] * q_mask[None, :]).sum(axis=1)  # (n,)
    anchor_lab = q_labels[anchor]
    ok = (g.labels == anchor_lab) & g.node_mask & (g.degree > 0)
    if seed_filter is not None:
        ok = ok & seed_filter
    score = jnp.where(ok, score, -jnp.inf)
    vals, ids = jax.lax.top_k(score, k)
    return ids.astype(jnp.int32), jnp.isfinite(vals)


def _bfs_reach_hops(g: DynamicGraph, sources: jnp.ndarray, max_hops: int,
                    ell: Optional[EllGraph] = None,
                    axis: Optional[str] = None,
                    part: Optional[PartitionedEdges] = None) -> jnp.ndarray:
    """hops[k_idx, v] = min #edges from sources[k_idx] to v (≤ max_hops),
    else max_hops+1. Batched bounded BFS — the bridge function's path-length
    oracle. The frontier sweep is either an edge-gather/segment-max (COO) or
    the masked-gather max kernel on the ELL layout; both propagate exact 0/1
    indicators, so the backends are bit-identical.

    ``axis`` shards the frontier sweep over the graph mesh axis (the
    receiver-slice partition of DESIGN.md §5): COO masks messages to the
    shard's slice and combines with a ``pmax``, ELL runs the kernel on the
    shard-local row block and ``all_gather``-s the slices. Max is
    idempotent over the indicator range [0, 1] and the non-owner shards
    contribute exact zeros absorbed by the ``maximum`` against the current
    frontier, so the sharded sweep stays bit-identical too.

    ``part`` (partitioned storage, needs ``axis``) sweeps this shard's
    receiver-sliced edge block instead of the replicated arrays: the
    segment-max lands straight in local segments (no receiver masking)
    and the slices ``all_gather`` back. Per-vertex slot sets match the
    replicated arrays, and a vertex with no slots yields the segment-max
    identity (absorbed by the ``maximum``) in both layouts, so this path
    is bit-identical as well."""
    k = sources.shape[0]
    reached = jax.nn.one_hot(sources, g.n_max, dtype=jnp.float32).T  # (n,k)
    hops = jnp.where(reached.T > 0, 0, max_hops + 1).astype(jnp.int32)

    if part is not None:
        assert axis is not None, "partitioned sweeps need a graph mesh axis"
        p_s = part.senders[0]
        p_r = part.receivers_loc[0]
        p_live = part.mask[0].astype(jnp.float32)[:, None]

        def sweep(reached):
            msg = reached[p_s] * p_live                      # (E_slice, k)
            agg = jax.ops.segment_max(msg, p_r, num_segments=part.n_loc)
            return jax.lax.all_gather(agg, axis, axis=0, tiled=True)
    elif ell is None:
        live = g.edge_mask.astype(jnp.float32)[:, None]

        def sweep(reached):
            msg = reached[g.senders] * live                  # (E, k)
            if axis is not None:
                msg = jnp.where(
                    _owned_mask(g.receivers, g.n_max, axis)[:, None],
                    msg, 0.0)
            agg = jax.ops.segment_max(msg, g.receivers,
                                      num_segments=g.n_max)
            if axis is not None:
                agg = jax.lax.pmax(agg, axis)
            return agg
    else:
        def sweep(reached):
            agg = ell_reach_kernel(ell.cols, ell.mask, ell.row_ids,
                                   reached, ell.n)
            if axis is not None:
                agg = jax.lax.all_gather(agg, axis, axis=0, tiled=True)
            return agg

    def body(carry, h):
        reached, hops = carry
        nxt = jnp.maximum(sweep(reached), reached)
        newly = (nxt > 0) & (reached <= 0)
        hops = jnp.where(newly.T, h, hops)
        return (nxt, hops), None

    (_, hops), _ = jax.lax.scan(body, (reached, hops),
                                jnp.arange(1, max_hops + 1))
    return hops  # (k, n)


class BankGRayMatcher:
    """Jitted G-Ray over a stacked bank of standing queries.

    One compiled program serves the whole bank: the expansion is vmapped
    over the query axis and every per-step single-source sweep (RWR +
    bounded BFS) runs as one ``(n, B·k)`` dense block — the shared-sweep
    amortization that makes a 16-query bank far cheaper than 16 single
    matchers (DESIGN.md §3, benchmarks/serving_bench.py).

    ``backend="ell"`` runs both sparse sweeps through the Pallas ELL
    kernels; callers pass the graph's ELL mirror via ``ell=`` (one is built
    on the fly when omitted — prefer a cached mirror in loops).

    ``memo=False`` selects the *content-independent* schedule: the unroll
    depth and sweep structure depend only on the bank's padded shape
    ``(B, q_max, qe_max)``, never on which queries occupy the rows. The
    per-(row, source-vertex) memo survives as DATA — table slots per query
    vertex plus a traced "filled" mask, with each unrolled step's shared
    ``(n, B·k)`` sweep guarded by a ``lax.cond`` on "any row sees a new
    source" — so repeated sources and padded tail steps still skip their
    sweeps at runtime, but swapping a row's query tensors can never
    invalidate a trace. Values are identical to the memoized mode
    (``matched`` is write-once). The engine's dynamic buckets (DESIGN.md
    §4) require this mode: register/retire inside a bucket is a row
    write, not a recompile.

    ``node_cap`` (``memo=False`` only) sizes the shared sub-pattern table:
    callers passing a ``row_node`` plan (the bucket's
    :class:`~repro.core.query.PlanDAG` mirror) get ONE table slot per
    distinct sub-pattern node instead of per (row, query vertex), and the
    per-step sweep width drops to ``min(B, node_cap)`` — the
    O(distinct sub-patterns) step cost of DESIGN.md §7. Without a
    ``row_node`` the identity plan (node ≡ (row, source vertex)) keeps the
    legacy layout bit-for-bit.
    """

    def __init__(self, bank: QueryBank, n_labels: int, k: int,
                 rwr_iters: int = 25, restart: float = 0.15,
                 bridge_hops: int = 4, backend: str = "coo",
                 ell_width: int = 64, memo: bool = True,
                 rwr_tol: float = 0.0, node_cap: Optional[int] = None):
        backend = resolve_backend(backend)
        if backend not in ("coo", "ell"):
            raise ValueError(f"unknown backend {backend!r}")
        self.bank = bank
        self.n_labels = n_labels
        self.k = k
        self.rwr_iters = rwr_iters
        self.restart = restart
        self.bridge_hops = bridge_hops
        self.backend = backend
        self.ell_width = ell_width
        self.memo = memo
        # tol > 0: the per-step expansion sweeps run residual-adaptive
        # (rwr_iters stays the hard cap) — see IGPMConfig.rwr_tol
        self.rwr_tol = rwr_tol
        B = bank.n_queries
        if memo:
            # host-static schedule structure: unroll to the longest schedule
            # in the bank; shorter queries no-op their padded tail steps
            src_np = np.asarray(bank.order_src)
            mask_np = np.asarray(bank.order_mask)
            self.n_steps = int(mask_np.sum(axis=1).max()) if mask_np.size else 0
            # per-(query, source-vertex) table memo: each query computes one
            # RWR/reach table per DISTINCT schedule source, exactly like the
            # single-query memo — but all tables first used at one unrolled
            # step batch into one shared (n, P·k) sweep. Sound because
            # matched[qa] is write-once and BFS order matches a source before
            # its first use; padded tail steps of shorter queries read slot 0
            # and mask the result out.
            pair_of: Tuple[Dict[int, int], ...] = tuple({} for _ in range(B))
            self._new_pairs: Tuple[Tuple[Tuple[int, int, int], ...], ...]
            new_pairs = []
            self._read_slot = np.zeros((self.n_steps, B), np.int32)
            for ei in range(self.n_steps):
                fresh = []
                for b in range(B):
                    if not mask_np[b, ei]:
                        continue
                    sv = int(src_np[b, ei])
                    if sv not in pair_of[b]:
                        pair_of[b][sv] = len(pair_of[b])
                        fresh.append((b, pair_of[b][sv], sv))
                    self._read_slot[ei, b] = pair_of[b][sv]
                new_pairs.append(tuple(fresh))
            self._new_pairs = tuple(new_pairs)
            self.t_max = max([1] + [len(p) for p in pair_of])
            self.n_tables = sum(len(p) for p in pair_of)
        else:
            # content-independent: full unroll; table slots per sub-
            # pattern DAG node (node_cap of them + 1 trash slot), filled
            # lazily at runtime. Without a node plan the identity layout
            # is one slot per (row, query vertex).
            self.n_steps = bank.qe_max
            self.t_max = bank.q_max
            self.node_cap = node_cap
            self.n_tables = (B * bank.q_max if node_cap is None
                             else node_cap)
        self._match = jax.jit(self._match_impl,
                              static_argnames=("graph_axis",))
        self._seeds = jax.jit(self._seeds_impl)

    # -- public API ---------------------------------------------------------

    def _ell_for(self, g: DynamicGraph,
                 ell: Optional[EllGraph]) -> Optional[EllGraph]:
        if self.backend != "ell":
            return None
        if ell is None:
            ell = ell_from_graph(g, self.ell_width)
        return ell

    def label_table(self, g: DynamicGraph,
                    r0: Optional[jnp.ndarray] = None,
                    iters: Optional[int] = None,
                    ell: Optional[EllGraph] = None) -> jnp.ndarray:
        """Label-conditioned RWR table — query-independent, computed ONCE
        per graph state and shared by every query in the bank. Honors
        ``rwr_tol`` like the expansion sweeps (an explicit ``iters``
        overrides the cap either way; ``iters=0`` stays the warm-start
        pass-through)."""
        iters = iters if iters is not None else self.rwr_iters
        ell = self._ell_for(g, ell)
        if self.rwr_tol > 0:
            r, _, _ = label_rwr_adaptive(g, self.n_labels, max_iters=iters,
                                         tol=self.rwr_tol, c=self.restart,
                                         r0=r0, ell=ell)
            return r
        return label_rwr(g, self.n_labels, iters=iters, c=self.restart,
                         r0=r0, ell=ell)

    def seeds(self, g: DynamicGraph, r_lab: jnp.ndarray,
              seed_filter: Optional[jnp.ndarray] = None,
              bank: Optional[QueryBank] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Per-query top-k anchor candidates (ids (B, k), mask (B, k))."""
        b = bank or self.bank
        return self._seeds(g, r_lab, seed_filter, b.labels, b.mask, b.anchor)

    def match(self, g: DynamicGraph, r_lab: jnp.ndarray,
              seed_filter: Optional[jnp.ndarray] = None,
              ell: Optional[EllGraph] = None,
              bank: Optional[QueryBank] = None) -> GRayResult:
        ell = self._ell_for(g, ell)
        seed_ids, seed_mask = self.seeds(g, r_lab, seed_filter, bank=bank)
        return self.match_from_seeds(g, r_lab, seed_ids, seed_mask, ell=ell,
                                     bank=bank)

    def match_from_seeds(self, g: DynamicGraph, r_lab: jnp.ndarray,
                         seed_ids: jnp.ndarray, seed_mask: jnp.ndarray,
                         ell: Optional[EllGraph] = None,
                         bank: Optional[QueryBank] = None,
                         row_node: Optional[jnp.ndarray] = None
                         ) -> GRayResult:
        b = bank or self.bank
        return self._match(g, r_lab, seed_ids, seed_mask,
                           self._ell_for(g, ell), b.labels, b.mask, b.anchor,
                           b.order_src, b.order_dst, b.order_tree,
                           b.order_mask, row_node)

    # -- implementation ------------------------------------------------------

    def _seeds_impl(self, g: DynamicGraph, r_lab: jnp.ndarray,
                    seed_filter: Optional[jnp.ndarray],
                    q_labels: jnp.ndarray, q_mask: jnp.ndarray,
                    anchor: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return jax.vmap(
            lambda lq, mq, aq: _find_seeds_arrays(g, r_lab, self.k,
                                                  seed_filter, lq, mq, aq)
        )(q_labels, q_mask, anchor)

    def _rwr(self, g: DynamicGraph, e: jnp.ndarray,
             ell: Optional[EllGraph],
             graph_axis: Optional[str],
             part: Optional[PartitionedEdges] = None) -> jnp.ndarray:
        """One shared expansion sweep block — fixed-count or residual-
        adaptive per ``rwr_tol`` (the hard cap is ``rwr_iters`` either
        way)."""
        if self.rwr_tol > 0:
            r, _, _ = rwr_adaptive(g, e, max_iters=self.rwr_iters,
                                   tol=self.rwr_tol, c=self.restart,
                                   ell=ell, axis=graph_axis, part=part)
            return r
        return rwr(g, e, iters=self.rwr_iters, c=self.restart, ell=ell,
                   axis=graph_axis, part=part)

    def _match_impl(self, g: DynamicGraph, r_lab: jnp.ndarray,
                    seed_ids: jnp.ndarray, seed_mask: jnp.ndarray,
                    ell: Optional[EllGraph], q_labels: jnp.ndarray,
                    q_mask: jnp.ndarray, anchor: jnp.ndarray,
                    order_src: jnp.ndarray, order_dst: jnp.ndarray,
                    order_tree: jnp.ndarray, order_mask: jnp.ndarray,
                    row_node: Optional[jnp.ndarray] = None,
                    part: Optional[PartitionedEdges] = None,
                    graph_axis: Optional[str] = None) -> GRayResult:
        B, k = seed_ids.shape
        n = g.n_max
        q_max = q_labels.shape[1]
        qe_max = order_src.shape[1]
        logp = jnp.log(r_lab + _EPS)

        def init_one(lq, mq, aq, sq, _):
            matched = jnp.full((k, q_max), -1, jnp.int32).at[:, aq].set(sq)
            used = jnp.zeros((k, n), bool).at[jnp.arange(k), sq].set(True)
            # seed goodness (same quantity the seed-finder ranked by)
            goodness = (logp[sq][:, lq] * mq[None, :]).sum(axis=1)
            return matched, used, goodness

        matched, used, goodness = jax.vmap(init_one)(
            q_labels, q_mask, anchor, seed_ids, seed_mask)
        hops = jnp.zeros((B, k, qe_max), jnp.int32)
        valid = seed_mask

        def step_one(ei, lq, matched_q, used_q, goodness_q, hops_q, valid_q,
                     qb, tr, on, r_q, reach_q):
            # neighbor-expander: best label-compatible unused candidate
            cand_ok = ((g.labels == lq[qb])[None, :]
                       & g.node_mask[None, :] & ~used_q)           # (k, n)
            score = jnp.where(cand_ok, r_q.T, -jnp.inf)
            best = jnp.argmax(score, axis=1).astype(jnp.int32)
            found = jnp.isfinite(jnp.max(score, axis=1))
            m_tree = jnp.where(found, best, -1)
            m_non = matched_q[:, qb]   # non-tree: both ends matched
            write = tr & on
            matched_q = matched_q.at[:, qb].set(
                jnp.where(write, m_tree, m_non))
            used_q = used_q.at[jnp.arange(k), best].set(
                used_q[jnp.arange(k), best] | (found & write))
            prox_tree = r_q[best, jnp.arange(k)]
            prox_non = r_q[jnp.clip(m_non, 0, n - 1), jnp.arange(k)]
            delta = jnp.where(tr,
                              jnp.where(found,
                                        jnp.log(prox_tree + _EPS), 0.0),
                              jnp.log(prox_non + _EPS))
            goodness_q = goodness_q + jnp.where(on, delta, 0.0)
            valid_q = valid_q & jnp.where(write, found, True)
            # bridge: hop count of best path (1 ⇒ exact edge)
            m_b = jnp.where(tr, m_tree, m_non)
            h = reach_q[jnp.arange(k), jnp.clip(m_b, 0, n - 1)]
            hops_q = hops_q.at[:, ei].set(
                jnp.where(on, h, hops_q[:, ei]))
            return matched_q, used_q, goodness_q, hops_q, valid_q

        def expand(ei, state, r_t, reach_t):
            """One schedule step for every row: (matched, used, goodness,
            hops, valid) → the same, reading the step's tables."""
            return jax.vmap(lambda *a: step_one(ei, *a))(
                q_labels, *state, order_dst[:, ei], order_tree[:, ei],
                order_mask[:, ei], r_t, reach_t)

        def sweep_tables(flat):
            """One shared RWR + reach sweep block for ``flat`` sources."""
            e = jax.nn.one_hot(flat, n, dtype=jnp.float32).T   # (n, S)
            r_new = self._rwr(g, e, ell, graph_axis, part)
            h_new = _bfs_reach_hops(g, flat, self.bridge_hops, ell=ell,
                                    axis=graph_axis, part=part)
            return r_new, h_new

        state = (matched, used, goodness, hops, valid)
        if self.memo:
            # per-(query, source) tables, all first-uses of one unrolled
            # step batched into ONE shared (n, P·k) RWR + reach sweep
            tables_r = jnp.zeros((B, self.t_max, n, k), jnp.float32)
            tables_h = jnp.zeros((B, self.t_max, k, n), jnp.int32)
            for ei in range(self.n_steps):
                pairs = self._new_pairs[ei]
                if pairs:
                    srcs = jnp.stack([state[0][b, :, sv]
                                      for b, _, sv in pairs])    # (P, k)
                    p = len(pairs)
                    r_new, h_new = sweep_tables(srcs.reshape(p * k))
                    r_new = jnp.transpose(r_new.reshape(n, p, k), (1, 0, 2))
                    b_idx = jnp.asarray([b for b, _, _ in pairs])
                    t_idx = jnp.asarray([t for _, t, _ in pairs])
                    tables_r = tables_r.at[b_idx, t_idx].set(r_new)
                    tables_h = tables_h.at[b_idx, t_idx].set(
                        h_new.reshape(p, k, n))
                slot = jnp.asarray(self._read_slot[ei])
                state = expand(ei, state,
                               tables_r[jnp.arange(B), slot],    # (B, n, k)
                               tables_h[jnp.arange(B), slot])    # (B, k, n)
        else:
            # content-independent memo over sub-pattern DAG nodes: ONE
            # table slot per node plus a trash slot (n_slots) swallowing
            # masked reads and packing fill, "node computed" tracked as
            # traced data. row_node maps (row, step) → the node whose
            # tables the step reads; the identity plan (node ≡ row·q_max +
            # source vertex) reproduces the legacy per-(row, vertex)
            # layout exactly.
            if row_node is None:
                n_slots = B * q_max
                row_node = (jnp.arange(B, dtype=jnp.int32)[:, None] * q_max
                            + order_src.astype(jnp.int32))
            else:
                assert self.node_cap is not None, \
                    "row_node plans need a node_cap-sized matcher"
                n_slots = int(self.node_cap)
            n_sweep = min(B, n_slots)

            def step(ei, carry):
                # each step's shared (n, n_sweep·k) sweep is guarded by a
                # lax.cond on "any row reads a node not computed yet" —
                # all derived from the order/row_node tensors, which are
                # jit arguments. Sweep count matches the host-static memo
                # (padded tail steps and repeated sources skip at runtime)
                # while the compiled structure depends only on the bucket
                # shape, so membership swaps never retrace; the steps run
                # as one rolled loop, so a program holds ONE sweep block
                # whatever its schedule length. Every row holding a node
                # expands through bitwise-identical partials (DESIGN.md
                # §7), so one representative row per fresh node computes
                # its tables for the whole bank.
                state, tables_r, tables_h, node_seen = carry
                on = order_mask[:, ei]                           # (B,)
                nd = jnp.where(on, row_node[:, ei],
                               n_slots).astype(jnp.int32)        # (B,)
                fresh = on & (node_seen[nd] == 0)
                # representative row per fresh node (scatter-min — any
                # holder agrees bitwise, min is a deterministic pick)
                rep = jnp.full((n_slots + 1,), B, jnp.int32).at[
                    jnp.where(fresh, nd, n_slots)].min(
                    jnp.arange(B, dtype=jnp.int32))
                idx = jnp.nonzero(rep[:n_slots] < B, size=n_sweep,
                                  fill_value=n_slots)[0]         # (n_sweep,)

                def compute(tabs):
                    t_r, t_h = tabs
                    rows = jnp.clip(rep[idx], 0, B - 1)          # (n_sweep,)
                    srcv = order_src[rows, ei]                   # (n_sweep,)
                    srcs = jnp.take_along_axis(
                        state[0][rows], srcv[:, None, None],
                        axis=2)[:, :, 0]                         # (n_sweep, k)
                    r_new, h_new = sweep_tables(srcs.reshape(n_sweep * k))
                    r_new = jnp.transpose(r_new.reshape(n, n_sweep, k),
                                          (1, 0, 2))
                    # packing fill (idx == n_slots) lands in the trash
                    # slot, which only masked reads ever see
                    return (t_r.at[idx].set(r_new),
                            t_h.at[idx].set(h_new.reshape(n_sweep, k, n)))

                tables_r, tables_h = jax.lax.cond(
                    fresh.any(), compute, lambda t: t, (tables_r, tables_h))
                node_seen = node_seen.at[nd].max(on.astype(jnp.int32))
                state = expand(ei, state, tables_r[nd],         # (B, n, k)
                               tables_h[nd])                     # (B, k, n)
                return state, tables_r, tables_h, node_seen

            state, _, _, _ = jax.lax.fori_loop(
                0, self.n_steps, step,
                (state, jnp.zeros((n_slots + 1, n, k), jnp.float32),
                 jnp.zeros((n_slots + 1, k, n), jnp.int32),
                 jnp.zeros(n_slots + 1, jnp.int32)))
        matched, used, goodness, hops, valid = state

        em = order_mask[:, None, :]                             # (B, 1, qe)
        exact = jnp.where(em, hops == 1, True).all(axis=2)
        reachable = jnp.where(em, hops <= self.bridge_hops, True).all(axis=2)
        valid = valid & reachable
        return GRayResult(matched, goodness, hops, exact & valid, valid)


class GRayMatcher:
    """Jitted G-Ray for one query shape — a bank of size one.

    Kept as the single-query API the incremental matchers drive; all the
    matching machinery lives in :class:`BankGRayMatcher` (the query tensors
    are jit arguments, not closure state), so single-query and bank-mode
    results are equal by construction.
    """

    def __init__(self, query: Query, n_labels: int, k: int,
                 rwr_iters: int = 25, restart: float = 0.15,
                 bridge_hops: int = 4, backend: str = "coo",
                 ell_width: int = 64, rwr_tol: float = 0.0):
        self.query = query
        self.n_labels = n_labels
        self.k = k
        self.rwr_iters = rwr_iters
        self.restart = restart
        self.bridge_hops = bridge_hops
        self.backend = resolve_backend(backend)
        self.ell_width = ell_width
        # host-static expansion schedule (introspection + tests)
        om = np.asarray(query.order_mask)
        self.schedule: Tuple[Tuple[int, int, bool], ...] = tuple(
            (int(a), int(b), bool(t))
            for a, b, t, m in zip(np.asarray(query.order_src),
                                  np.asarray(query.order_dst),
                                  np.asarray(query.order_tree), om) if m)
        self._bank = BankGRayMatcher(
            stack_queries([query], q_max=query.q_max,
                          qe_max=int(query.order_src.shape[0])),
            n_labels, k, rwr_iters=rwr_iters, restart=restart,
            bridge_hops=bridge_hops, backend=self.backend,
            ell_width=ell_width, rwr_tol=rwr_tol)

    # -- public API ---------------------------------------------------------

    def _ell_for(self, g: DynamicGraph,
                 ell: Optional[EllGraph]) -> Optional[EllGraph]:
        return self._bank._ell_for(g, ell)

    def label_table(self, g: DynamicGraph,
                    r0: Optional[jnp.ndarray] = None,
                    iters: Optional[int] = None,
                    ell: Optional[EllGraph] = None) -> jnp.ndarray:
        return self._bank.label_table(g, r0=r0, iters=iters, ell=ell)

    def match(self, g: DynamicGraph, r_lab: jnp.ndarray,
              seed_filter: Optional[jnp.ndarray] = None,
              ell: Optional[EllGraph] = None) -> GRayResult:
        return GRayResult(
            *(x[0] for x in self._bank.match(g, r_lab,
                                             seed_filter=seed_filter,
                                             ell=ell)))

    def match_from_seeds(self, g: DynamicGraph, r_lab: jnp.ndarray,
                         seed_ids: jnp.ndarray, seed_mask: jnp.ndarray,
                         ell: Optional[EllGraph] = None) -> GRayResult:
        return GRayResult(
            *(x[0] for x in self._bank.match_from_seeds(
                g, r_lab, seed_ids[None], seed_mask[None], ell=ell)))


def gray_match(g: DynamicGraph, query: Query, n_labels: int, k: int = 20,
               rwr_iters: int = 25, restart: float = 0.15,
               bridge_hops: int = 4,
               seed_filter: Optional[jnp.ndarray] = None,
               r_lab: Optional[jnp.ndarray] = None,
               backend: str = "coo",
               ell: Optional[EllGraph] = None) -> GRayResult:
    """One-shot batch G-Ray (builds a matcher; prefer GRayMatcher in loops)."""
    m = GRayMatcher(query, n_labels, k, rwr_iters, restart, bridge_hops,
                    backend=backend)
    if m.backend == "ell" and ell is None:
        ell = ell_from_graph(g, m.ell_width)
    if r_lab is None:
        r_lab = m.label_table(g, ell=ell)
    return m.match(g, r_lab, seed_filter=seed_filter, ell=ell)
