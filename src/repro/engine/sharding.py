"""Device-sharded bucket execution — the (query × graph) mesh.

Two independent mesh axes (DESIGN.md §4/§5):

``"q"`` — rows of a bucket bank are independent programs in the
content-independent (``memo=False``) schedule, so the bank match
parallelizes over the query axis with ZERO collectives: ``shard_map``
splits the bank tensors and the per-row seeds, every device runs the same
expansion on its row slice, and the results concatenate back.

``"g"`` — vertices of the data graph partition into contiguous receiver
slices, which is what lets ``n_max`` scale past one device: the COO sweep
masks messages to the shard's slice and combines partial segment-sums
with a ``psum``, and the ELL mirror carries a per-shard row-block layout
(``EllCache(n_shards=…)`` — slice-local ``row_ids``, one spill cursor per
block) so each device's Pallas launch touches only its vertex slice and
the slices ``all_gather`` back. Non-owner shards contribute exact zeros
and concatenation does no arithmetic, so BOTH axes are pure
distributions: sharded results are bit-identical to the replicated path
on both backends (pinned in ``tests/test_engine_sharding.py`` and
``tests/test_graph_sharding.py`` under 4 forced host devices).

Falls back to the plain jit path when one device is visible; shard counts
are capped at the largest power of two dividing both the device count and
the sharded dimension, so every shard carries the same static slice. When
both axes are ``"auto"`` the device pool splits between them (graph axis
≤ √devices); an ``"off"`` query axis frees every device for the graph
axis.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.graph import DynamicGraph, PartitionedEdges
from repro.core.gray import GRayResult, _bfs_reach_hops
from repro.core.query import QueryBank
from repro.core.rwr import label_rwr, label_rwr_adaptive, rwr, rwr_adaptive
from repro.sparse.ell import EllGraph

_REP = P()


def _pow2_cap(cap: int) -> int:
    n = 1
    while n * 2 <= cap:
        n *= 2
    return n


def query_shard_count(b_pad: int, shard: str = "auto",
                      max_devices: Optional[int] = None) -> int:
    """Shards for a ``b_pad``-row bucket: the largest pow-2 ≤ min(devices,
    rows). 1 disables the shard_map path (plain jit + vmap).
    ``max_devices`` caps the device budget (the rest belong to the graph
    axis)."""
    if shard == "off":
        return 1
    if shard != "auto":
        raise ValueError(f"unknown shard policy {shard!r}")
    nd = len(jax.devices()) if max_devices is None else max_devices
    return _pow2_cap(min(nd, b_pad))


def graph_shard_count(n_max: int, shard: str = "off",
                      max_devices: Optional[int] = None) -> int:
    """Shards of the graph mesh axis: the largest pow-2 ≤ devices that
    divides ``n_max`` (equal static vertex slices). ``"off"`` pins the
    replicated graph."""
    if shard == "off":
        return 1
    if shard != "auto":
        raise ValueError(f"unknown graph shard policy {shard!r}")
    nd = len(jax.devices()) if max_devices is None else max_devices
    n = 1
    while n * 2 <= min(nd, n_max) and n_max % (n * 2) == 0:
        n *= 2
    return n


def device_split(shard: str, graph_shard: str,
                 n_max: int) -> Tuple[int, int]:
    """How the visible devices split between the two mesh axes.

    Returns ``(query_budget, g_shards)``: the graph axis takes every
    device when the query axis is off, at most √devices when both are
    auto (a balanced 2-D mesh), and the query axis gets the rest.
    """
    nd = len(jax.devices())
    if graph_shard == "off":
        return nd, 1
    cap = nd if shard == "off" else _pow2_cap(int(np.sqrt(nd)))
    g = graph_shard_count(n_max, graph_shard, max_devices=max(cap, 1))
    return max(nd // g, 1), g


class ShardedBankMatch:
    """``shard_map`` wrapper around one bucket matcher's ``_match_impl``.

    ``n_shards`` splits the bank's row axis over ``"q"``; ``g_shards > 1``
    adds the ``"g"`` graph axis. A call with ``graph_sharded=True`` (the
    engine's storm/batch full-graph path) expects the shard-local ELL
    row-block mirror and runs the matcher's sweeps with ``axis="g"``;
    ``graph_sharded=False`` (the induced-subgraph path, whose compact
    extraction is already the speedup) keeps the graph replicated over
    ``"g"`` and the sweeps collective-free.
    """

    def __init__(self, matcher, n_shards: int, g_shards: int = 1):
        assert not matcher.memo, "sharded buckets require memo=False"
        self.matcher = matcher
        self.n_shards = n_shards
        self.g_shards = g_shards
        devs = np.asarray(jax.devices()[:n_shards * g_shards])
        self.mesh = Mesh(devs.reshape(n_shards, g_shards), ("q", "g"))
        # keyed (ell pytree, graph sharded, plan, part pytree): the specs
        # are built from the edge carriers' treedefs, whose static vertex
        # count changes with every induced-subgraph bucket
        self._fns = {}

    def _build(self, g: DynamicGraph, ell: Optional[EllGraph],
               part: Optional[PartitionedEdges], graph_sharded: bool,
               has_plan: bool):
        rep, q = _REP, P("q")
        axis = "g" if (graph_sharded and self.g_shards > 1) else None
        g_spec = jax.tree.map(lambda _: rep, g)
        bank_specs = (q,) * 7  # labels, mask, anchor, order_* — all row-major
        # the row_node plan splits with the rows: each shard resolves the
        # DAG nodes its local rows read and computes them independently
        # (node tables are replicated work, rows stay collective-free)
        plan_specs = (q,) if has_plan else ()
        out_specs = GRayResult(q, q, q, q, q)
        # edge carriers (mutually exclusive): the ELL mirror replicates
        # without a graph axis, the partitioned COO slices only exist ON
        # the graph axis (each device receives its receiver slice)
        extra_specs = ()
        if ell is not None:
            extra_specs += (jax.tree.map(
                lambda _: P("g") if axis is not None else rep, ell),)
        if part is not None:
            assert axis is not None and ell is None
            extra_specs += (jax.tree.map(lambda _: P("g"), part),)
        n_extra = len(extra_specs)

        def f(g_, r_lab, seed_ids, seed_mask, *rest):
            ell_ = rest[0] if ell is not None else None
            part_ = rest[n_extra - 1] if part is not None else None
            labels, mask, anchor, osrc, odst, otree, omask = \
                rest[n_extra:n_extra + 7]
            plan = rest[n_extra + 7:]
            return self.matcher._match_impl(
                g_, r_lab, seed_ids, seed_mask, ell_, labels, mask,
                anchor, osrc, odst, otree, omask,
                plan[0] if plan else None, part_, graph_axis=axis)

        in_specs = (g_spec, rep, q, q) + extra_specs + bank_specs + plan_specs
        return jax.jit(shard_map(f, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    def __call__(self, g: DynamicGraph, r_lab: jnp.ndarray,
                 seed_ids: jnp.ndarray, seed_mask: jnp.ndarray,
                 ell: Optional[EllGraph], bank: QueryBank,
                 graph_sharded: bool = False,
                 row_node: Optional[jnp.ndarray] = None,
                 part: Optional[PartitionedEdges] = None) -> GRayResult:
        # without a graph axis, graph_sharded compiles the identical
        # program — normalize so storm and induced calls share one trace
        graph_sharded = graph_sharded and self.g_shards > 1
        if not graph_sharded:
            part = None  # partitioned slices only exist on the graph axis
        key = (jax.tree.structure(ell), graph_sharded, row_node is not None,
               jax.tree.structure(part))
        if key not in self._fns:
            self._fns[key] = self._build(g, ell, part, graph_sharded,
                                         row_node is not None)
        args = (g, r_lab, seed_ids, seed_mask)
        if ell is not None:
            args = args + (ell,)
        if part is not None:
            args = args + (part,)
        args = args + (bank.labels, bank.mask, bank.anchor,
                       bank.order_src, bank.order_dst,
                       bank.order_tree, bank.order_mask)
        if row_node is not None:
            args = args + (row_node,)
        return self._fns[key](*args)

    def trace_count(self) -> int:
        n = 0
        for fn in self._fns.values():
            size = getattr(fn, "_cache_size", None)
            n += size() if size is not None else 0
        return n


class ShardedSweep:
    """Graph-axis ``shard_map`` programs for the full-graph sweeps.

    The engine drives :meth:`label_table` (the per-step label-RWR hot
    path); :meth:`run_rwr` / :meth:`reach` expose the raw sweeps so the
    bitwise-equivalence tests exercise exactly the production programs.
    ELL mirrors must be the shard-local row-block layout
    (``EllCache(n_shards=g_shards)``); COO graphs stay replicated and the
    partial scatter combines with a ``psum``.
    """

    def __init__(self, g_shards: int):
        self.g_shards = g_shards
        self.mesh = Mesh(np.asarray(jax.devices()[:g_shards]), ("g",))
        self._fns = {}

    def _specs(self, has_r0: bool, ell: Optional[EllGraph],
               g: DynamicGraph, *extra,
               part: Optional[PartitionedEdges] = None):
        g_spec = jax.tree.map(lambda _: _REP, g)
        specs = (g_spec,) + tuple(_REP for _ in extra)
        if has_r0:
            specs = specs + (_REP,)
        # edge carriers are mutually exclusive and always shard over "g"
        # (the partitioned slices only exist on the graph axis)
        if ell is not None:
            specs = specs + (jax.tree.map(lambda _: P("g"), ell),)
        if part is not None:
            assert ell is None
            specs = specs + (jax.tree.map(lambda _: P("g"), part),)
        return specs

    def _call(self, key, build, *args):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn(*args)

    def label_table(self, g: DynamicGraph, n_labels: int, iters: int,
                    c: float, r0: Optional[jnp.ndarray],
                    ell: Optional[EllGraph], tol: float = 0.0,
                    part: Optional[PartitionedEdges] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Sharded :func:`label_rwr` → ``(r_lab, n_sweeps, n_col_skipped)``
        (the sweep count is ``iters`` on the fixed path, measured when
        ``tol > 0``; the converged-column skip count is 0 on the fixed
        path)."""
        has_r0, has_ell = r0 is not None, ell is not None
        has_part = part is not None
        key = ("lab", has_ell, has_part, has_r0, n_labels, iters, c, tol)

        def build():
            def f(g_, *rest):
                r0_ = rest[0] if has_r0 else None
                # edge carriers are mutually exclusive, both appended last
                ell_ = rest[-1] if has_ell else None
                part_ = rest[-1] if has_part else None
                if tol > 0:
                    return label_rwr_adaptive(
                        g_, n_labels, max_iters=iters, tol=tol, c=c,
                        r0=r0_, ell=ell_, axis="g", part=part_)
                return (label_rwr(g_, n_labels, iters=iters, c=c, r0=r0_,
                                  ell=ell_, axis="g", part=part_),
                        jnp.int32(iters), jnp.int32(0))

            return jax.jit(shard_map(
                f, mesh=self.mesh,
                in_specs=self._specs(has_r0, ell, g, part=part),
                out_specs=(_REP, _REP, _REP), check_vma=False))

        args = ((g,) + ((r0,) if has_r0 else ())
                + ((ell,) if has_ell else ())
                + ((part,) if has_part else ()))
        return self._call(key, build, *args)

    def run_rwr(self, g: DynamicGraph, e: jnp.ndarray, iters: int,
                c: float = 0.15, r0: Optional[jnp.ndarray] = None,
                ell: Optional[EllGraph] = None, tol: float = 0.0,
                part: Optional[PartitionedEdges] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Sharded :func:`rwr` / :func:`rwr_adaptive` →
        ``(r, n_sweeps, n_col_skipped)``."""
        has_r0, has_ell = r0 is not None, ell is not None
        has_part = part is not None
        key = ("rwr", has_ell, has_part, has_r0, iters, c, tol)

        def build():
            def f(g_, e_, *rest):
                r0_ = rest[0] if has_r0 else None
                ell_ = rest[-1] if has_ell else None
                part_ = rest[-1] if has_part else None
                if tol > 0:
                    return rwr_adaptive(g_, e_, max_iters=iters, tol=tol,
                                        c=c, r0=r0_, ell=ell_, axis="g",
                                        part=part_)
                return (rwr(g_, e_, iters=iters, c=c, r0=r0_, ell=ell_,
                            axis="g", part=part_), jnp.int32(iters),
                        jnp.int32(0))

            return jax.jit(shard_map(
                f, mesh=self.mesh,
                in_specs=self._specs(has_r0, ell, g, e, part=part),
                out_specs=(_REP, _REP, _REP), check_vma=False))

        args = ((g, e) + ((r0,) if has_r0 else ())
                + ((ell,) if has_ell else ())
                + ((part,) if has_part else ()))
        return self._call(key, build, *args)

    def reach(self, g: DynamicGraph, sources: jnp.ndarray, max_hops: int,
              ell: Optional[EllGraph] = None,
              part: Optional[PartitionedEdges] = None) -> jnp.ndarray:
        """Sharded :func:`~repro.core.gray._bfs_reach_hops`."""
        has_ell, has_part = ell is not None, part is not None
        key = ("reach", has_ell, has_part, max_hops)

        def build():
            def f(g_, src_, *rest):
                ell_ = rest[0] if has_ell else None
                part_ = rest[-1] if has_part else None
                return _bfs_reach_hops(g_, src_, max_hops, ell=ell_,
                                       axis="g", part=part_)

            return jax.jit(shard_map(
                f, mesh=self.mesh,
                in_specs=self._specs(False, ell, g, sources, part=part),
                out_specs=_REP, check_vma=False))

        args = ((g, sources) + ((ell,) if has_ell else ())
                + ((part,) if has_part else ()))
        return self._call(key, build, *args)
