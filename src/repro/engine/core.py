"""The match engine — ONE step pipeline behind every matcher facade.

``engine_step(engine, state, upd)`` is the only place in the repo that
sequences the paper's serving step:

  1. ``apply_update`` + incremental ELL-mirror refresh (one graph state)
  2. pattern-store pruning when removals could have killed a matched vertex
  3. PEM recompute mask (one Louvain cut, one DQN-controlled threshold)
  4. induced-subgraph extraction — or the full-graph *storm* fallback with
     warm-started label RWR and the staleness-keyed seed cache
  5. the label-conditioned RWR table (query-independent, shared by all
     buckets)
  6. one bank G-Ray match per bucket (vmap or shard_map over the row axis)
  7. host-side merge into per-query :class:`~repro.engine.store.PatternStore`

``BatchMatcher`` / ``NaiveIncrementalMatcher`` / ``AdaptiveMatcher`` /
``MatchServer`` are thin facades projecting :class:`StepOutput` into their
historical stats types; none of them owns a pipeline anymore (DESIGN.md §4).
The functional core is explicit: all evolving data rides in
:class:`~repro.engine.state.EngineState`; the Engine object holds the
registry (buckets, stores), jit caches, and host-side caches that are pure
functions of the state (ELL mirror, Louvain dendrogram, storm seed memo).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.config.base import EngineConfig, IGPMConfig, resolve_backend
from repro.core.graph import (DynamicGraph, EdgePartition, EllCache,
                              UpdateBatch, apply_update, updated_vertices)
from repro.core.pem import PartialExecutionManager
from repro.core.query import DagFull, Query, query_signature
from repro.core.rwr import label_rwr, label_rwr_adaptive
from repro.core.subgraph import (induced_tile, pull_induced, remap_matched,
                                  select_induced, upload_induced)
from repro.engine.buckets import (QueryBucket, _pow2, bucket_shape,
                                  decode_strings, encode_strings)
from repro.engine.sharding import ShardedSweep, device_split
from repro.engine.state import EngineState, QueryDelta, StepOutput
from repro.engine.store import PatternStore, live_vertex_mask
from repro.obs import Obs, StepScope, to_host, uploaded


class Engine:
    """Functional-core match engine with bucketed dynamic query banks."""

    def __init__(self, cfg: IGPMConfig, ecfg: Optional[EngineConfig] = None,
                 seed: int = 0):
        ecfg = ecfg or EngineConfig()
        if ecfg.mode not in ("incremental", "batch"):
            raise ValueError(f"unknown engine mode {ecfg.mode!r}")
        if cfg.backend == "auto":
            cfg = dataclasses.replace(cfg,
                                      backend=resolve_backend(cfg.backend))
        self.cfg = cfg
        self.ecfg = ecfg
        self.seed = seed
        self.pem: Optional[PartialExecutionManager] = (
            None if ecfg.mode == "batch"
            else PartialExecutionManager(cfg, adaptive=ecfg.adaptive,
                                         seed=seed))
        # graph mesh axis: how the visible devices split between the query
        # and graph axes (DESIGN.md §5); 1/1 on a single device
        self.q_budget, self.g_shards = device_split(
            ecfg.shard, ecfg.graph_shard, cfg.n_max)
        self._sweeps = (ShardedSweep(self.g_shards)
                        if self.g_shards > 1 else None)
        # edge-partitioned storage (DESIGN.md §10): co-partition the edge
        # arrays with the receiver slices so each device holds ~1/g of the
        # store. Host router (EdgePartition / partitioned EllCache) keeps
        # the slices fresh; the mesh then never sees replicated edges.
        if ecfg.edge_partition not in ("off", "on"):
            raise ValueError(
                f"unknown edge_partition policy {ecfg.edge_partition!r}")
        self.partitioned = (ecfg.edge_partition == "on"
                            and self.g_shards > 1)
        self.ell_cache = (EllCache(cfg.n_max, cfg.e_max, cfg.ell_width,
                                   n_shards=self.g_shards,
                                   partitioned=self.partitioned,
                                   headroom=ecfg.partition_headroom)
                          if cfg.backend == "ell" else None)
        self.part_cache = (EdgePartition(cfg.n_max, cfg.e_max, self.g_shards,
                                         headroom=ecfg.partition_headroom)
                           if self.partitioned and cfg.backend == "coo"
                           else None)
        # per-bucket match fan-out pool (installed by the serving runtime
        # when RuntimeConfig.n_executors > 1; None = serial dispatch)
        self._exec_pool = None
        # XLA collectives carry no cross-launch ordering: two threads
        # launching shard_map programs over the same device set interleave
        # their all_gather rendezvous and deadlock. When the graph mesh has
        # collectives (g_shards > 1) pooled workers serialize device
        # dispatch through this lock; single-device meshes skip it.
        self._dispatch_lock = threading.Lock()
        self.buckets: Dict[Tuple[int, int], QueryBucket] = {}
        self.stores: Dict[str, PatternStore] = {}
        self._where: Dict[str, Tuple[int, int]] = {}  # qid → bucket (q, qe)
        self._order: List[str] = []                   # registration order
        # exact-duplicate groups: content signature → [primary, *aliases].
        # The primary owns the bank row; aliases ride it for free (zero
        # device work at register; results fan out to every store).
        self._dups: Dict[Tuple, List[str]] = {}
        self._sig_of: Dict[str, Tuple] = {}
        self._alias_query: Dict[str, Query] = {}      # alias qid → its Query
        self.n_dedup = 0
        # storm seed cache (satellite: consecutive storm steps stop paying
        # the full-graph seed recompute) — see EngineConfig. Entries are
        # (version key, recompute mask, seeds): a step reuses the seeds
        # when the versions match and its mask is within
        # ``seed_cache_hamming`` flips of the cached one (0 = exact).
        self._seed_memo: Dict[Tuple[int, int],
                              Tuple[tuple, np.ndarray, tuple]] = {}
        self.rlab_hits = 0
        self.rlab_misses = 0
        self.seed_hits = 0
        self.seed_hits_exact = 0
        self.seed_hits_bounded = 0
        self.seed_misses = 0
        self.rwr_sweeps = 0  # label-RWR sweeps actually run (adaptive)
        self.rwr_cols_skipped = 0  # converged-column sweeps retired
        self._last_sweeps = 0
        self._last_cols_skipped = 0
        # observability hub (DESIGN.md §8): the serving/runtime layers
        # reuse this engine's hub so one event stream spans all threads
        self.obs = Obs(ecfg.obs)
        # last _merge fan-out shape (bank rows folded / alias stores
        # written) — the host-cost suspect ROADMAP tracks
        self.last_merge_rows = 0
        self.last_merge_stores = 0
        self.last_merge_folds = 0
        # optional serving-controller attachment (repro.control): when a
        # runtime binds one here, its state rides the engine checkpoint so
        # save/load round-trips the learned scheduling policy too
        self.control = None

    # -- standing-query registry ----------------------------------------------

    def register(self, query: Query, qid: Optional[str] = None) -> str:
        """Add a standing query; returns its id. Inside an existing bucket
        this is a device row write (zero recompilations); a new padded
        shape — or outgrowing ``B_pad`` — builds a new bucket."""
        if qid is None:
            qid = query.name
            i = 1
            while qid in self.stores:
                qid = f"{query.name}#{i}"
                i += 1
        elif qid in self.stores:
            raise ValueError(f"qid {qid!r} already registered")
        shape = bucket_shape(query, self.ecfg)
        # with dedup disabled every registration is its own singleton group
        # (duplicates occupy real rows and must not share result fan-out)
        sig = query_signature(query) if self.ecfg.dedup else (qid,)
        if self.ecfg.dedup and self._dups.get(sig):
            # exact-duplicate fast path: the query's tensors are bitwise a
            # live row already — alias that row. ZERO device work (no bank
            # write, no DAG change, no seed-memo invalidation); the row's
            # match results fan out to this store too (DESIGN.md §7).
            self._dups[sig].append(qid)
            self._sig_of[qid] = sig
            self._alias_query[qid] = query
            self.n_dedup += 1
            store = PatternStore()
            primary = self.stores[self._dups[sig][0]]
            if primary.total == 0:
                # alias stores are bitwise clones of the primary from here
                # on (identical merge inputs per group), so share the dict:
                # _merge then folds each row ONCE per group, not per alias
                store.share_from(primary)
            self.stores[qid] = store
            self._where[qid] = shape
            self._order.append(qid)
            self.obs.instant("bank/register_alias", qid=qid,
                             primary=self._dups[sig][0])
            return qid
        bucket = self.buckets.get(shape)
        if bucket is None:
            bucket = QueryBucket(self.cfg, *shape, b_pad=1,
                                 shard=self.ecfg.shard,
                                 g_shards=self.g_shards,
                                 q_budget=self.q_budget,
                                 node_cap=shape[0])
            self.buckets[shape] = bucket
        elif bucket.full:
            bucket = self._grow(bucket)
        with self.obs.span("bank/register", qid=qid,
                           bucket=f"{shape[0]}x{shape[1]}"):
            while True:
                try:
                    bucket.register(qid, query)
                    break
                except DagFull:
                    # sub-pattern capacity outgrown: double it (a rebuild,
                    # the same amortized cost as the B_pad doubling)
                    bucket = self._rebuild(bucket, bucket.b_pad,
                                           node_cap=2 * bucket.node_cap)
        self._dups.setdefault(sig, []).append(qid)
        self._sig_of[qid] = sig
        self._seed_memo.pop(shape, None)
        self.stores[qid] = PatternStore()
        self._where[qid] = shape
        self._order.append(qid)
        return qid

    def retire(self, qid: str) -> None:
        """Drop a standing query (device row clear — zero recompilations).
        Its pattern store goes with it. Retiring an ALIAS (or a primary
        with live aliases, which hands its row to the next one) is pure
        host bookkeeping. A bucket left EMPTY is dropped outright (no
        reason to keep sweeping a dead bank); one left at ≤ quarter
        occupancy compacts to half its row capacity (the shrink mirror of
        the growth doubling, so churn-heavy servers stop sweeping dead
        rows; amortized exactly like the doubling)."""
        if qid not in self._where:
            raise KeyError(f"unknown qid {qid!r}; live: {self._order}")
        with self.obs.span("bank/retire", qid=qid):
            shape = self._where.pop(qid)
            sig = self._sig_of.pop(qid)
            group = self._dups[sig]
            del self.stores[qid]
            self._order.remove(qid)
            bucket = self.buckets[shape]
            if qid != group[0]:
                # alias — the primary keeps the row
                group.remove(qid)
                del self._alias_query[qid]
                return
            group.pop(0)
            if group:
                # primary with aliases: promote the next one onto the row
                # (bitwise the same tensors, so the device bank — and the
                # seed memo — stay untouched)
                promoted = group[0]
                bucket.rename_row(qid, promoted,
                                  self._alias_query.pop(promoted))
                return
            del self._dups[sig]
            bucket.retire(qid)
            self._seed_memo.pop(shape, None)
            if bucket.n_live == 0:
                del self.buckets[shape]
            elif bucket.b_pad > 1 and bucket.n_live <= bucket.b_pad // 4:
                self._rebuild(bucket, bucket.b_pad // 2)

    def _reshare_alias_stores(self) -> None:
        """Re-establish pattern-dict sharing across exact-duplicate groups
        whose stores hold equal content (fresh after ``reset``, or loaded
        per-qid by ``load`` — ``PatternStore.load_arrays`` rebinds each
        store's dict, silently un-sharing it). Group members that diverged
        (late aliases registered after the primary accumulated patterns)
        stay private, which preserves their per-store semantics."""
        for group in self._dups.values():
            primary = self.stores.get(group[0])
            if primary is None:
                continue
            for alias in group[1:]:
                store = self.stores.get(alias)
                if (store is not None and not store.shares_with(primary)
                        and store._patterns == primary._patterns):
                    store.share_from(primary)

    def _rebuild(self, bucket: QueryBucket, b_pad: int,
                 node_cap: Optional[int] = None) -> QueryBucket:
        """Repack a bucket's live rows into a ``b_pad``-row bank — the one
        membership change that recompiles, by design. ``_grow`` doubles a
        full bucket; ``retire`` halves one at ≤ quarter occupancy (the
        ≤1/4 ↔ ×2 hysteresis keeps both amortized O(1) per change). The
        DAG capacity re-fits to the live distinct nodes unless an explicit
        ``node_cap`` is forced (the DagFull doubling)."""
        if node_cap is None:
            node_cap = _pow2(bucket.dag.n_nodes, bucket.q_max)
        with self.obs.span("bank/rebuild", b_pad=b_pad, node_cap=node_cap,
                           rows=bucket.n_live):
            fresh = QueryBucket(self.cfg, bucket.q_max, bucket.qe_max,
                                b_pad=b_pad, shard=self.ecfg.shard,
                                g_shards=self.g_shards,
                                q_budget=self.q_budget, node_cap=node_cap)
            for slot, qid in bucket.rows():
                fresh.register(qid, bucket.query(slot))
            self.buckets[(bucket.q_max, bucket.qe_max)] = fresh
        return fresh

    def _grow(self, bucket: QueryBucket) -> QueryBucket:
        # headroom for the incoming row (≤ q_max fresh nodes), so a grow
        # is ONE rebuild, not a rebuild plus a DagFull retry
        return self._rebuild(
            bucket, 2 * bucket.b_pad,
            node_cap=_pow2(bucket.dag.n_nodes + bucket.q_max, bucket.q_max))

    def query(self, qid: str) -> Query:
        q = self._alias_query.get(qid)
        if q is not None:
            return q
        shape = self._where[qid]
        bucket = self.buckets[shape]
        return bucket.query(bucket.qids.index(qid))

    @property
    def qids(self) -> Tuple[str, ...]:
        return tuple(self._order)

    def alias_groups(self) -> Dict[str, str]:
        """qid → its frontier-group primary (itself unless an alias) for
        every live standing query. Exact-duplicate group members share
        one device row and receive identical result fan-out each step,
        so any per-query delivery frontier is shared across the group —
        the FreshnessLedger (DESIGN.md §11) consumes this map."""
        return {qid: group[0]
                for group in self._dups.values() for qid in group}

    def partition_occupancy(self) -> Optional[float]:
        """Worst live-slice fill fraction of the edge-partitioned storage
        (DESIGN.md §10), or None when storage is not partitioned. This is
        overflow *proximity*: 1.0 means the next uneven batch can raise
        ``PartitionOverflowError`` — the health watchdog degrades before
        that."""
        if self.part_cache is not None:
            return self.part_cache.occupancy()
        if self.ell_cache is not None and self.partitioned:
            return self.ell_cache.occupancy()
        return None

    def occupancy(self) -> Dict[Tuple[int, int, int], Tuple[int, int]]:
        """bucket key (q_max, qe_max, B_pad) → (live rows, padded rows)."""
        return {b.key: (b.n_live, b.b_pad) for b in self.buckets.values()}

    def dag_occupancy(self) -> Dict[Tuple[int, int, int, int],
                                    Tuple[int, int]]:
        """DAG bucket key (q_max, qe_max, B_pad, node_cap) → (live
        sub-pattern nodes, node capacity) — the shared-table view of
        :meth:`occupancy` (DESIGN.md §7)."""
        return {b.dag_key: (b.dag.n_nodes, b.node_cap)
                for b in self.buckets.values()}

    def trace_count(self) -> int:
        """Total compiled traces across bucket programs — the membership
        tests pin this flat across register/retire inside a bucket."""
        return sum(b.trace_count() for b in self.buckets.values())

    def counters(self) -> Dict[str, int]:
        return {"rlab_cache_hits": self.rlab_hits,
                "rlab_cache_misses": self.rlab_misses,
                "seed_cache_hits": self.seed_hits,
                "seed_cache_hits_exact": self.seed_hits_exact,
                "seed_cache_hits_bounded": self.seed_hits_bounded,
                "seed_cache_misses": self.seed_misses,
                "rwr_sweeps": self.rwr_sweeps,
                "rwr_cols_skipped": self.rwr_cols_skipped,
                # shared sub-pattern occupancy (DESIGN.md §7): how many
                # standing queries the bank serves vs the device rows and
                # distinct DAG nodes actually paying for them
                "n_dedup": self.n_dedup,
                "standing_queries": len(self._order),
                "bank_rows": sum(b.n_live for b in self.buckets.values()),
                "dag_nodes": sum(b.dag.n_nodes
                                 for b in self.buckets.values()),
                "dag_node_cap": sum(b.node_cap
                                    for b in self.buckets.values())}

    # -- state lifecycle -------------------------------------------------------

    def init_state(self, graph: DynamicGraph) -> EngineState:
        return EngineState(graph=graph)

    def reset(self) -> None:
        """Clear accumulated match state but KEEP jit caches (and the PEM's
        learned threshold/policy) — benchmark warm/measure passes replay
        identical streams on one engine."""
        self.stores = {qid: PatternStore() for qid in self._order}
        self._reshare_alias_stores()
        self._seed_memo.clear()
        self.rlab_hits = self.rlab_misses = 0
        self.seed_hits = self.seed_misses = 0
        self.seed_hits_exact = self.seed_hits_bounded = 0
        self.rwr_sweeps = 0
        self.rwr_cols_skipped = 0
        if self.ell_cache is not None:
            self.ell_cache = EllCache(self.cfg.n_max, self.cfg.e_max,
                                      self.cfg.ell_width,
                                      n_shards=self.g_shards,
                                      partitioned=self.partitioned,
                                      headroom=self.ecfg.partition_headroom)
        if self.part_cache is not None:
            self.part_cache = EdgePartition(self.cfg.n_max, self.cfg.e_max,
                                            self.g_shards,
                                            headroom=self.ecfg
                                            .partition_headroom)

    # -- the ONE step pipeline -------------------------------------------------

    def step(self, state: EngineState,
             upd: UpdateBatch) -> Tuple[EngineState, StepOutput]:
        return engine_step(self, state, upd)

    def _apply(self, g: DynamicGraph,
               upd: UpdateBatch) -> Tuple[DynamicGraph, float]:
        """Apply the update, refreshing whichever mirror is carried (the
        ELL cache and/or the edge-partition router — DESIGN.md §10).

        The returned refresh time covers only the mirror maintenance — the
        COO ``apply_update`` is paid identically by all backends. Child
        spans of ``engine/apply``: ``ell_rebuild`` (a mirror resync after
        a graph swap), ``update`` (the COO dispatch, removal path
        included) and ``ell_refresh`` (the refresh time returned)."""
        obs = self.obs
        mirrors = [m for m in (self.ell_cache, self.part_cache)
                   if m is not None]
        if not mirrors:
            with obs.span("engine/apply/update"):
                return apply_update(g, upd), 0.0
        for m in mirrors:
            if m._last is not g:
                with obs.span("engine/apply/ell_rebuild"):
                    m.rebuild(g)
        with obs.span("engine/apply/update"):
            g2 = apply_update(g, upd)
        with obs.span("engine/apply/ell_refresh"):
            t0 = time.perf_counter()
            for m in mirrors:
                m.refresh(g, g2, upd)
            jax.block_until_ready(self.ell_cache._cols_d
                                  if self.ell_cache is not None
                                  else self.part_cache._send_d)
            refresh_s = time.perf_counter() - t0
        return g2, refresh_s

    @property
    def _full_ell(self):
        return None if self.ell_cache is None else self.ell_cache.ell

    @property
    def _full_part(self):
        """The receiver-sliced edge partition to hand the graph mesh, or
        None when edge partitioning is off / the ELL backend carries the
        slices itself (its mirror is already built per receiver block)."""
        return None if self.part_cache is None else self.part_cache.part

    def _node_view(self, g: DynamicGraph) -> DynamicGraph:
        """``g`` with the replicated COO edge arrays stubbed to width-1
        placeholders. Partitioned mesh programs read only the node-level
        fields (labels/node_mask/degree) plus the PartitionedEdges slices,
        so shipping this view keeps replicated edge storage off the mesh
        — the whole point of the partitioned layout."""
        z = jnp.zeros((1,), jnp.int32)
        return g._replace(senders=z, receivers=z,
                          edge_mask=jnp.zeros((1,), bool))

    def set_executor_pool(self, n_executors: int) -> None:
        """Install (``n > 1``) or tear down (``n <= 1``) the per-bucket
        match fan-out pool (DESIGN.md §10). Pool workers only launch the
        independent per-bucket jit dispatches — on inputs identical to the
        serial path — and the fan-in join happens in bucket order before
        any merge, so pooled results are bit-identical to serial ones.
        Host-side step decisions (seed memo, PEM, merge) never leave the
        calling thread."""
        if self._exec_pool is not None:
            self._exec_pool.shutdown(wait=True)
            self._exec_pool = None
        if n_executors > 1:
            self._exec_pool = ThreadPoolExecutor(
                max_workers=n_executors,
                thread_name_prefix="rt-bucket-exec")

    def _label_table(self, g: DynamicGraph,
                     r0: Optional[jnp.ndarray] = None,
                     iters: Optional[int] = None, ell=None,
                     part=None, sharded: bool = False) -> jnp.ndarray:
        """The per-step label-RWR table — the single biggest sweep cost.

        ``sharded`` marks a FULL-graph call (storm/batch), which runs over
        the graph mesh axis when one is configured (``ell`` then being the
        shard-local mirror); induced-subgraph tables stay replicated.
        ``cfg.rwr_tol > 0`` swaps the fixed-count scan for the residual-
        adaptive loop (hard cap = the fixed count), and the sweeps
        actually run are accounted in ``self.rwr_sweeps``.
        """
        cfg = self.cfg
        iters = iters if iters is not None else cfg.rwr_iters
        if sharded and self._sweeps is not None:
            r, n, skipped = self._sweeps.label_table(
                g, cfg.n_labels, iters, cfg.restart_prob, r0, ell,
                tol=cfg.rwr_tol, part=part)
            self._account_sweeps(int(to_host(n)), int(to_host(skipped)))
            # decommit from the sweep mesh: bucket meshes may span a
            # different device set, and multi-device-committed inputs do
            # not transfer implicitly. The (n, L) table is tiny next to
            # the sweeps it took to produce.
            return uploaded(jnp.asarray(to_host(r)))
        if cfg.rwr_tol > 0:
            r, n, skipped = label_rwr_adaptive(
                g, cfg.n_labels, max_iters=iters, tol=cfg.rwr_tol,
                c=cfg.restart_prob, r0=r0, ell=ell)
            self._account_sweeps(int(to_host(n)), int(to_host(skipped)))
            return r
        self._account_sweeps(iters, 0)
        return label_rwr(g, cfg.n_labels, iters=iters,
                         c=cfg.restart_prob, r0=r0, ell=ell)

    def _account_sweeps(self, n: int, skipped: int) -> None:
        self.rwr_sweeps += n
        self.rwr_cols_skipped += skipped
        self._last_sweeps = n
        self._last_cols_skipped = skipped

    def _merge(self, results, remap=None,
               rebuild: bool = False) -> Tuple[QueryDelta, ...]:
        """Fold per-bucket results into the per-query stores (the only
        per-query host work of a step). Traced per bucket and per row —
        the per-alias store fan-out here was the host cost that grew the
        bank1024 step while device work stayed flat (ROADMAP). Alias
        stores created while the primary was empty SHARE the primary's
        pattern dict (see ``PatternStore.share_from``), so each row folds
        its arrays once per *distinct dict* in the group — O(1) for fully
        shared groups — and the remaining per-alias work is a dict lookup
        to emit the QueryDelta. ``last_merge_rows``/``last_merge_stores``
        keep the fan-out accounting; ``last_merge_folds`` counts the
        actual merge_arrays calls (== rows when every group is shared)."""
        obs = self.obs
        by_qid: Dict[str, QueryDelta] = {}
        n_rows = n_stores = n_folds = 0
        for shape, res in results.items():
            bucket = self.buckets[shape]
            with obs.span("engine/merge/bucket",
                          bucket=f"{shape[0]}x{shape[1]}",
                          rows=bucket.n_live):
                matched = to_host(res.matched)
                if remap is not None:
                    matched = remap_matched(
                        matched.reshape(-1, matched.shape[-1]),
                        remap).reshape(matched.shape)
                goodness = to_host(res.goodness)
                exact = to_host(res.exact)
                valid = to_host(res.valid)
                for slot, qid in bucket.rows():
                    # one device row serves its whole duplicate group: the
                    # primary (owning the row) plus every alias store
                    group = self._dups.get(self._sig_of[qid], [qid])
                    n_rows += 1
                    n_stores += len(group)
                    folded: Dict[int, int] = {}  # id(pattern dict) → n_new
                    with obs.span("engine/merge/row", qid=qid,
                                  aliases=len(group)):
                        for alias in group:
                            store = self.stores[alias]
                            pid = id(store._patterns)
                            if pid not in folded:
                                if rebuild:
                                    store._patterns.clear()
                                folded[pid] = store.merge_arrays(
                                    matched[slot], goodness[slot],
                                    exact[slot], valid[slot],
                                    bucket.row_mask(slot))
                                n_folds += 1
                            name = (bucket.query(slot).name if alias == qid
                                    else self._alias_query[alias].name)
                            by_qid[alias] = QueryDelta(alias, name,
                                                       folded[pid],
                                                       store.total,
                                                       store.exact)
        self.last_merge_rows = n_rows
        self.last_merge_stores = n_stores
        self.last_merge_folds = n_folds
        return tuple(by_qid[q] for q in self._order if q in by_qid)

    # -- whole-engine checkpointing (DESIGN.md §4) ------------------------------

    def state_dict(self, state: EngineState) -> Dict:
        """The engine's EngineState pytree as host arrays: graph, the
        warm-start r_lab table, per-bucket bank tables, PEM/DQN state, and
        the pattern-store arrays. The ELL mirror and Louvain dendrogram are
        deliberately absent — they are caches rebuilt from the graph."""
        n, L = self.cfg.n_max, self.cfg.n_labels
        d: Dict = {
            "graph": {f: np.asarray(getattr(state.graph, f))
                      for f in state.graph._fields},
            "r_lab": (np.zeros((n, L), np.float32) if state.r_lab is None
                      else np.asarray(state.r_lab)),
            "has_rlab": np.asarray(state.r_lab is not None),
            "rlab_events": np.asarray(state.rlab_events, np.int64),
            "step_idx": np.asarray(state.step_idx, np.int64),
            "buckets": {f"{k[0]}x{k[1]}": b.bank_arrays()
                        for k, b in self.buckets.items()},
            "stores": {qid: self.stores[qid].to_arrays()
                       for qid in self._order},
            # qid → primary-row aliases of the exact-duplicate groups
            # (uint8-encoded "alias\tprimary" lines; round-trip guard —
            # a load against the same registry must reproduce them)
            "aliases": encode_strings(
                f"{a}\t{self._dups[self._sig_of[a]][0]}"
                for a in self._order if a in self._alias_query),
        }
        if self.pem is not None:
            d["pem"] = {"community_size": np.asarray(self.pem.c, np.int64)}
            if self.pem.agent is not None:
                d["pem"]["agent"] = self.pem.agent.state_dict()
        if self.control is not None:
            d["control"] = self.control.state_dict()
        return d

    def save(self, state: EngineState, directory: str,
             step: Optional[int] = None) -> None:
        ckpt = Checkpointer(directory, async_save=False)
        ckpt.save(state.step_idx if step is None else step,
                  self.state_dict(state))

    def load(self, state: EngineState, directory: str,
             step: Optional[int] = None) -> Tuple[EngineState, int]:
        """Restore a checkpoint saved by :meth:`save`. The same queries
        must be registered (the registry is code+configuration; the
        checkpoint carries data). Returns (state, step)."""
        ckpt = Checkpointer(directory, async_save=False)
        tree, step = ckpt.restore(self.state_dict(state), step=step)
        graph = DynamicGraph(**{f: jnp.asarray(tree["graph"][f])
                                for f in DynamicGraph._fields})
        for key_s, arrays in tree["buckets"].items():
            q, qe = (int(x) for x in key_s.split("x"))
            self.buckets[(q, qe)].load_bank_arrays(arrays)
        if "aliases" in tree:
            live = tuple(f"{a}\t{self._dups[self._sig_of[a]][0]}"
                         for a in self._order if a in self._alias_query)
            if decode_strings(np.asarray(tree["aliases"])) != live:
                raise ValueError(
                    "checkpointed duplicate-alias groups do not match the "
                    "live registry — register the same queries before "
                    "load()")
        for qid, arrays in tree["stores"].items():
            self.stores[qid].load_arrays(arrays)
        self._reshare_alias_stores()
        if self.pem is not None:
            self.pem.c = int(tree["pem"]["community_size"])
            if self.pem.agent is not None:
                self.pem.agent.load_state_dict(tree["pem"]["agent"])
        if self.control is not None and "control" in tree:
            self.control.load_state_dict(tree["control"])
        self._seed_memo.clear()
        if self.pem is not None:
            # the Louvain dendrogram is stale-tolerant (results-affecting)
            # state, not a pure cache: drop it so an in-process load behaves
            # exactly like a fresh process restoring the same checkpoint
            self.pem.reset_clustering()
        # the ELL mirror resyncs on the next _apply (graph identity changed)
        return EngineState(
            graph=graph,
            r_lab=(jnp.asarray(tree["r_lab"]) if bool(tree["has_rlab"])
                   else None),
            rlab_events=int(tree["rlab_events"]),
            rlab_version=0,
            step_idx=int(tree["step_idx"])), step


def _n_events(upd: UpdateBatch) -> int:
    """Masked update entries in a batch (host-side; staleness accounting)."""
    return int(to_host(upd.add_mask).sum()
               + to_host(upd.rem_mask).sum()
               + to_host(upd.lab_mask).sum())


def engine_step(eng: Engine, state: EngineState,
                upd: UpdateBatch) -> Tuple[EngineState, StepOutput]:
    """THE shared step pipeline (module docstring). Pure in the functional-
    core sense: evolving data is read from ``state`` and returned in the
    new state; Engine-held host caches are rebuilt-on-demand views.

    With tracing off this delegates straight to the pipeline — no span
    objects, no stage dict, no extra device fences (the no-op path the
    bitwise/trace-count tests pin). With tracing on, the step runs inside
    a step-scoped trace context (every span carries ``step``), the flight
    recorder captures the step's span group, and per-stage wall times
    come back in ``StepOutput.stage_s``, and the bytes it moved across
    the host link and the programs it compiled in ``StepOutput.counts``
    (the step's :class:`~repro.obs.StepScope`)."""
    obs = eng.obs
    if not obs.enabled:
        return _engine_step(eng, state, upd, obs, None)
    step_idx = int(state.step_idx)
    with obs.context(step=step_idx):
        scope = obs.begin_step(step_idx)
        try:
            return _engine_step(eng, state, upd, obs, scope)
        finally:
            obs.end_step(step_idx)


def _run_matches(eng: Engine, jobs, obs: Obs, tracing: bool):
    """Dispatch the per-bucket bank matches: serially without an executor
    pool, fanned across the pool otherwise, with a fan-in join in bucket
    submission order (the merge barrier) before returning. Each job is
    ``(shape, bucket_key, thunk)``; buckets are independent jit dispatches
    on identical inputs either way, so pooled results are bit-identical
    to serial ones and ``results`` keeps bucket-insertion order. Pooled
    ``t_gray`` sums per-worker seconds (may exceed wall time)."""
    results = {}
    t_gray = t_gwait = 0.0
    pool = eng._exec_pool
    if pool is None or len(jobs) <= 1:
        for shape, bkey, thunk in jobs:
            with obs.span("engine/gray", bucket=bkey) as sp:
                results[shape] = thunk()
            t_gray += sp.dur_s
            if tracing:
                with obs.span("engine/gray_wait", bucket=bkey) as spw:
                    jax.block_until_ready(results[shape])
                t_gwait += spw.dur_s
        return results, t_gray, t_gwait

    # collective-bearing programs (graph mesh sharded over >1 device) must
    # not be launched concurrently: XLA orders collectives only within a
    # launch, so two in-flight all_gathers over the same device set reach
    # different rendezvous and deadlock. Serialize dispatch AND completion
    # through the engine lock; a 1-device mesh has no collectives, and
    # concurrent jit launches on one device are safe, so it runs lock-free.
    lock = eng._dispatch_lock if eng.g_shards > 1 else None

    def run(bkey, thunk):
        with obs.span("engine/gray", bucket=bkey, pooled=True) as sp:
            if lock is not None:
                with lock:
                    out = thunk()
                    jax.block_until_ready(out)
            else:
                out = thunk()
                if tracing:
                    jax.block_until_ready(out)
        return out, sp.dur_s

    futs = [(shape, pool.submit(run, bkey, thunk))
            for shape, bkey, thunk in jobs]
    for shape, fut in futs:
        out, dur = fut.result()
        results[shape] = out
        t_gray += dur
    return results, t_gray, t_gwait


def _engine_step(eng: Engine, state: EngineState, upd: UpdateBatch,
                 obs: Obs, scope: Optional[StepScope]
                 ) -> Tuple[EngineState, StepOutput]:
    """Pipeline body. ``scope`` is None when tracing is disabled (all
    span calls then hit the shared no-op span); when tracing, its
    ``stage`` accumulates per-stage seconds for ``StepOutput.stage_s``.
    Stage taxonomy (DESIGN.md §8): apply → prune → pem → [storm: rwr →
    seeds → gray | induced: extract → rwr → gray] → device_wait → merge →
    feedback. Extra ``block_until_ready`` fences that split host
    dispatch from device wait run ONLY under ``obs.enabled``."""
    cfg, ecfg = eng.cfg, eng.ecfg
    stage = None if scope is None else scope.stage
    tracing = stage is not None
    with obs.span("engine/apply") as sp:
        g, refresh_s = eng._apply(state.graph, upd)
        with obs.span("engine/apply/updated"):
            n_events = _n_events(upd)
            rlab_events = state.rlab_events + n_events
            rlab_version = state.rlab_version
            upd_ids = None
            if ecfg.mode != "batch":
                ids, mask = updated_vertices(g, upd, ecfg.v_max)
                upd_ids = to_host(jnp.where(mask, ids, -1))
        with obs.span("engine/apply/wait"):
            jax.block_until_ready(g)
    if tracing:
        stage["apply"] = sp.dur_s
        stage["ell_refresh"] = refresh_s

    # -- store pruning (deletion-heavy streams; DESIGN.md §3) -----------------
    n_pruned = 0
    if (ecfg.mode != "batch"
            and any(s.total for s in eng.stores.values())
            and bool(to_host(upd.rem_mask).any())):
        with obs.span("engine/prune") as sp:
            live = live_vertex_mask(g)
            # prune each DISTINCT pattern dict once (alias stores share
            # the primary's dict); every sharer still counts the removals,
            # preserving the per-store n_pruned arithmetic
            removed: Dict[int, int] = {}
            for s in eng.stores.values():
                pid = id(s._patterns)
                if pid not in removed:
                    removed[pid] = s.prune(live)
                n_pruned += removed[pid]
        if tracing:
            stage["prune"] = sp.dur_s

    t0 = time.perf_counter()
    n_live = max(int(to_host(g.node_mask).sum()), 1)
    rlab_hit = seed_hit = False
    community = 0
    rl_loss = 0.0
    t_seeds = t_gray = t_gwait = 0.0

    eng._last_sweeps = 0
    eng._last_cols_skipped = 0
    if ecfg.mode == "batch":
        # the paper's Batch oracle: full fresh pass, stores rebuilt
        frac = 0.0
        n_rec = n_live
        storm = True
        ell = eng._full_ell
        part = eng._full_part
        # partitioned storage: the mesh programs read edges from the
        # PartitionedEdges slices, so ship a node-only view of g and keep
        # the replicated COO arrays off the devices entirely
        g_mesh = eng._node_view(g) if part is not None else g
        with obs.span("engine/rwr", mode="batch") as sp:
            r_lab = eng._label_table(g_mesh, ell=ell, part=part,
                                     sharded=True)
            if tracing:
                jax.block_until_ready(r_lab)
        if tracing:
            stage["rwr"] = sp.dur_s
        jobs = [(shape, f"{shape[0]}x{shape[1]}",
                 (lambda b=bucket: b.match(g_mesh, r_lab, ell=ell,
                                           graph_sharded=True, part=part)))
                for shape, bucket in eng.buckets.items()]
        results, t_gray, t_gwait = _run_matches(eng, jobs, obs, tracing)
        with obs.span("engine/device_wait") as sp:
            jax.block_until_ready(list(results.values()))
        elapsed = time.perf_counter() - t0
        if tracing:
            stage["gray"] = t_gray
            stage["device_wait"] = t_gwait + sp.dur_s
        with obs.span("engine/merge") as sp:
            deltas = eng._merge(results, rebuild=True)
        if tracing:
            stage["merge"] = sp.dur_s
            obs.instant("engine/merge/fanout", rows=eng.last_merge_rows,
                        stores=eng.last_merge_stores,
                        folds=eng.last_merge_folds)
        sub_n = sub_e = 0
        r_lab = None  # batch mode keeps no warm-start state
        rlab_events = 0
    else:
        with obs.span("engine/pem") as sp:
            rec_mask, frac = eng.pem.recompute_mask(g, upd_ids)
            n_rec = int(rec_mask.sum())
        if tracing:
            stage["pem"] = sp.dur_s
        storm = n_rec > ecfg.full_graph_frac * n_live

        if storm:
            # update storm — full pass, warm-started label RWR (paper: "too
            # many vertices updated to be re-computed" case), gated by the
            # staleness-keyed seed cache
            ell = eng._full_ell
            part = eng._full_part
            g_mesh = eng._node_view(g) if part is not None else g
            if (ecfg.seed_cache_staleness > 0 and state.r_lab is not None
                    and rlab_events <= ecfg.seed_cache_staleness):
                r_lab = state.r_lab
                rlab_hit = True
                eng.rlab_hits += 1
                if tracing:
                    stage["rwr"] = 0.0
                    obs.instant("engine/rwr_cache_hit")
            else:
                # warm starts under the residual-adaptive loop keep the
                # full hard cap — convergence is measured, not assumed
                with obs.span("engine/rwr", mode="storm",
                              warm=state.r_lab is not None) as sp:
                    r_lab = eng._label_table(
                        g_mesh, r0=state.r_lab,
                        iters=(None if (state.r_lab is None
                                        or cfg.rwr_tol > 0)
                               else cfg.rwr_iters_incremental),
                        ell=ell, part=part, sharded=True)
                    if tracing:
                        jax.block_until_ready(r_lab)
                if tracing:
                    stage["rwr"] = sp.dur_s
                rlab_events = 0
                rlab_version += 1
                eng.rlab_misses += 1
            sf = uploaded(jnp.asarray(rec_mask))
            mask_arr = np.asarray(rec_mask, bool)
            jobs = []
            bucket_hits = []
            for shape, bucket in eng.buckets.items():
                bkey = f"{shape[0]}x{shape[1]}"
                ver_key = (rlab_version, bucket.version)
                hit = eng._seed_memo.get(shape)
                # bounded-divergence reuse: same table/bank versions and a
                # recompute mask within seed_cache_hamming flips of the
                # one the cached seeds were ranked under (0 = exact match)
                ham = (int(np.count_nonzero(hit[1] != mask_arr))
                       if hit is not None and hit[0] == ver_key else None)
                if ham is not None and ham <= ecfg.seed_cache_hamming:
                    seeds = hit[2]
                    bucket_hits.append(True)
                    eng.seed_hits += 1
                    if ham == 0:
                        eng.seed_hits_exact += 1
                    else:
                        eng.seed_hits_bounded += 1
                else:
                    with obs.span("engine/seeds", bucket=bkey) as sp:
                        seeds = bucket.seeds(g, r_lab, sf)
                        if tracing:
                            jax.block_until_ready(seeds)
                    t_seeds += sp.dur_s
                    eng._seed_memo[shape] = (ver_key, mask_arr, seeds)
                    bucket_hits.append(False)
                    eng.seed_misses += 1
                jobs.append((shape, bkey,
                             (lambda b=bucket, s=seeds:
                              b.match(g_mesh, r_lab, seed_filter=sf,
                                      ell=ell, seeds=s,
                                      graph_sharded=True, part=part))))
            seed_hit = bool(bucket_hits) and all(bucket_hits)
            results, t_gray, t_gwait = _run_matches(eng, jobs, obs, tracing)
            with obs.span("engine/device_wait") as sp:
                jax.block_until_ready(list(results.values()))
            elapsed = time.perf_counter() - t0
            if tracing:
                stage["seeds"] = t_seeds
                stage["gray"] = t_gray
                stage["device_wait"] = t_gwait + sp.dur_s
            with obs.span("engine/merge") as sp:
                deltas = eng._merge(results)
            if tracing:
                stage["merge"] = sp.dur_s
                obs.instant("engine/merge/fanout",
                            rows=eng.last_merge_rows,
                            stores=eng.last_merge_stores,
                            folds=eng.last_merge_folds)
            sub_n, sub_e = n_live, int(to_host(g.edge_mask).sum())
        else:
            with obs.span("engine/extract") as sp:
                with obs.span("engine/extract/pull") as sp_pull:
                    pulled = pull_induced(g, rec_mask)
                with obs.span("engine/extract/select"):
                    sel = select_induced(pulled)
                tile = None
                if eng.ell_cache is not None:
                    with obs.span("engine/extract/ell"):
                        tile = induced_tile(sel, cfg.ell_width)
                with obs.span("engine/extract/upload"):
                    sub = upload_induced(sel, tile)
            if tracing:
                stage["extract"] = sp.dur_s
                stage["extract_pull"] = sp_pull.dur_s
            with obs.span("engine/rwr", mode="induced") as sp:
                r_sub = eng._label_table(sub.graph, ell=sub.ell)
                if tracing:
                    jax.block_until_ready(r_sub)
            if tracing:
                stage["rwr"] = sp.dur_s
            jobs = [(shape, f"{shape[0]}x{shape[1]}",
                     (lambda b=bucket: b.match(sub.graph, r_sub,
                                               ell=sub.ell)))
                    for shape, bucket in eng.buckets.items()]
            results, t_gray, t_gwait = _run_matches(eng, jobs, obs, tracing)
            with obs.span("engine/device_wait") as sp:
                jax.block_until_ready(list(results.values()))
            elapsed = time.perf_counter() - t0
            if tracing:
                stage["gray"] = t_gray
                stage["device_wait"] = t_gwait + sp.dur_s
            with obs.span("engine/merge") as sp:
                deltas = eng._merge(results, remap=sub.local_to_global)
            if tracing:
                stage["merge"] = sp.dur_s
                obs.instant("engine/merge/fanout",
                            rows=eng.last_merge_rows,
                            stores=eng.last_merge_stores,
                            folds=eng.last_merge_folds)
            sub_n, sub_e = sub.n_nodes, sub.n_edges
            r_lab = state.r_lab  # full-graph warm start unchanged

        with obs.span("engine/pem_feedback") as sp:
            community, rl_loss = eng.pem.feedback(g, frac, elapsed)
        if tracing:
            stage["feedback"] = sp.dur_s

    new_state = state.evolve(graph=g, r_lab=r_lab, rlab_events=rlab_events,
                             rlab_version=rlab_version,
                             step_idx=state.step_idx + 1)
    out = StepOutput(
        step=state.step_idx, elapsed=elapsed, n_recompute=n_rec,
        frac_affected=frac, community_size=community, rl_loss=rl_loss,
        storm=storm, subgraph_nodes=sub_n, subgraph_edges=sub_e,
        ell_refresh_s=refresh_s, n_pruned=n_pruned, n_events=n_events,
        rlab_cache_hit=rlab_hit, seed_cache_hit=seed_hit,
        rwr_sweeps=eng._last_sweeps,
        rwr_cols_skipped=eng._last_cols_skipped, deltas=deltas,
        stage_s=stage, counts=None if scope is None else scope.counts)
    return new_state, out
