"""Graph-axis sharded sweep equivalence (DESIGN.md §5).

Runs ONLY under a forced multi-device host platform:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest tests/test_graph_sharding.py -q

(`make engine-smoke` / the CI multi-device job do exactly that.) On the
default single-device container every test here skips — the tier-1 suite
stays single-device as conftest.py requires.

The contract: partitioning vertices over the ``"g"`` mesh axis is a pure
distribution of the replicated sweeps — ``rwr`` / ``label_rwr`` / the
bounded-BFS reach, the residual-adaptive variants, the 2-D ``(q, g)``
bucket match, and whole served streams produce BIT-IDENTICAL results on
both backends. The COO path masks messages to each shard's receiver slice
(non-owners contribute exact zeros) and combines with psum/pmax; the ELL
path runs the kernels on shard-local row blocks and concatenates slices —
no cross-shard arithmetic exists to reorder.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config.base import IGPMConfig, RuntimeConfig, ServingConfig
from repro.core.graph import (EdgePartition, EllCache, PartitionOverflowError,
                              UpdateBatch, ell_from_graph, new_graph,
                              partition_slice_capacity)
from repro.core.gray import _bfs_reach_hops
from repro.core.query import query_zoo
from repro.core.rwr import label_rwr, restart_onehot, rwr, rwr_adaptive
from repro.data.temporal import TemporalGraphSpec, generate_stream
from repro.engine import ShardedSweep, device_split, graph_shard_count
from repro.engine.buckets import QueryBucket
from repro.serving import MatchServer

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")

G = len(jax.devices())
N, K = 256, 8


def _graph(seed=0, ne=1500):
    rng = np.random.default_rng(seed)
    g = new_graph(N, 4096, labels=rng.integers(0, 4, N).astype(np.int32),
                  senders=rng.integers(0, N, ne),
                  receivers=rng.integers(0, N, ne))
    return g, rng


def _mirrors(g, backend):
    """(replicated ell, shard-local ell) — None/None on the COO backend."""
    if backend == "coo":
        return None, None
    return ell_from_graph(g, K), ell_from_graph(g, K, n_shards=G)


def _cfg(backend):
    return IGPMConfig(n_max=N, e_max=8192, ell_width=K, rwr_iters=8,
                      rwr_iters_incremental=3, top_k_patterns=6,
                      init_community_size=32, backend=backend)


def test_graph_shard_count_divides_n():
    assert graph_shard_count(N, "off") == 1
    gc = graph_shard_count(N, "auto")
    # largest pow-2 ≤ devices that divides N (N is a pow-2 here, so = the
    # pow-2 floor of the device count)
    assert gc == 1 << (G.bit_length() - 1)
    assert N % gc == 0
    assert graph_shard_count(6, "auto") == 2  # pow-2 divisor only
    with pytest.raises(ValueError):
        graph_shard_count(N, "bogus")


def test_device_split_budgets():
    nd = len(jax.devices())
    assert device_split("auto", "off", N) == (nd, 1)
    q_budget, g = device_split("off", "auto", N)
    assert g == graph_shard_count(N, "auto") and q_budget * g <= nd
    q_budget, g = device_split("auto", "auto", N)
    assert q_budget * g <= nd and g * g <= nd  # balanced split


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_rwr_sharded_bitwise(backend):
    g, _ = _graph()
    ell, ell_sh = _mirrors(g, backend)
    e = restart_onehot(jnp.asarray([3, 77, 130]), N)
    sweeps = ShardedSweep(G)

    ref = rwr(g, e, iters=12, ell=ell)
    got, n, _ = sweeps.run_rwr(g, e, iters=12, ell=ell_sh)
    assert int(n) == 12
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    # warm-started sweeps distribute identically
    ref_w = rwr(g, e, iters=4, r0=ref, ell=ell)
    got_w, _, _ = sweeps.run_rwr(g, e, iters=4, r0=ref, ell=ell_sh)
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(ref_w))


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_adaptive_rwr_sharded_bitwise_and_same_trip_count(backend):
    g, _ = _graph()
    ell, ell_sh = _mirrors(g, backend)
    e = restart_onehot(jnp.asarray([0, 9]), N)
    ref, n_ref, sk_ref = rwr_adaptive(g, e, max_iters=40, tol=1e-5, ell=ell)
    got, n_got, sk_got = ShardedSweep(G).run_rwr(g, e, iters=40, tol=1e-5,
                                                 ell=ell_sh)
    # sweep results replicate exactly across the axis, so every shard sees
    # the identical residuals and converged-column masks and the
    # while_loop exits on the same sweep
    assert int(n_got) == int(n_ref)
    assert int(sk_got) == int(sk_ref)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_label_rwr_sharded_bitwise(backend):
    g, _ = _graph(seed=2)
    ell, ell_sh = _mirrors(g, backend)
    ref = label_rwr(g, 4, iters=10, ell=ell)
    got, n, _ = ShardedSweep(G).label_table(g, 4, 10, 0.15, None, ell_sh)
    assert int(n) == 10
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_reach_sharded_bitwise(backend):
    g, rng = _graph(seed=3)
    ell, ell_sh = _mirrors(g, backend)
    src = jnp.asarray(rng.integers(0, N, 6).astype(np.int32))
    ref = _bfs_reach_hops(g, src, 4, ell=ell)
    got = ShardedSweep(G).reach(g, src, 4, ell=ell_sh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _dense_from_blocks(ell, n_shards):
    """Densify a shard-local row-block ELL into the global (n, n) matrix."""
    n_loc = ell.n
    r_cap_b = ell.cols.shape[0] // n_shards
    a = np.zeros((n_loc * n_shards, n_loc * n_shards), np.float32)
    cols = np.asarray(ell.cols)
    vals = np.where(np.asarray(ell.mask), np.asarray(ell.vals), 0.0)
    rows = np.asarray(ell.row_ids)
    for r_ in range(ell.cols.shape[0]):
        v = (r_ // r_cap_b) * n_loc + rows[r_]
        np.add.at(a[v], cols[r_], vals[r_])
    return a


def test_sharded_ell_cache_incremental_matches_fresh_build():
    rng = np.random.default_rng(7)
    g = new_graph(N, 4096, n_nodes=N)
    cache = EllCache(N, 4096, K, n_shards=G)
    for _ in range(4):
        upd = UpdateBatch.additions(rng.integers(0, N, 40),
                                    rng.integers(0, N, 40), u_max=128)
        em = np.asarray(g.edge_mask)
        ls = np.asarray(g.senders)[em]
        lr = np.asarray(g.receivers)[em]
        if len(ls):
            idx = rng.choice(len(ls), size=min(10, len(ls)), replace=False)
            pad = 128 - len(idx)
            upd = upd._replace(
                rem_src=jnp.asarray(
                    np.pad(ls[idx], (0, pad)).astype(np.int32)),
                rem_dst=jnp.asarray(
                    np.pad(lr[idx], (0, pad)).astype(np.int32)),
                rem_mask=jnp.asarray(np.arange(128) < len(idx)))
        g = cache.update(g, upd)
        fresh = ell_from_graph(g, K, n_shards=G)
        np.testing.assert_array_equal(
            _dense_from_blocks(cache.ell, G),
            _dense_from_blocks(fresh, G))


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_bucket_2d_mesh_match_equals_plain(backend):
    g, _ = _graph(seed=1, ne=500)
    cfg = _cfg(backend)
    g_shards = min(2, G)
    ell = ell_from_graph(g, K) if backend == "ell" else None
    ell_sh = (ell_from_graph(g, K, n_shards=g_shards)
              if backend == "ell" else None)
    two_d = QueryBucket(cfg, 8, 8, 4, shard="auto", g_shards=g_shards,
                        q_budget=len(jax.devices()) // g_shards)
    plain = QueryBucket(cfg, 8, 8, 4, shard="off")
    assert two_d.g_shards > 1
    for i, q in enumerate(query_zoo(4)):
        two_d.register(f"q{i}", q)
        plain.register(f"q{i}", q)
    r_lab = label_rwr(g, cfg.n_labels, iters=cfg.rwr_iters, ell=ell)
    ra = two_d.match(g, r_lab, ell=ell_sh, graph_sharded=True)
    rb = plain.match(g, r_lab, ell=ell)
    for f in ra._fields:
        np.testing.assert_array_equal(np.asarray(getattr(ra, f)),
                                      np.asarray(getattr(rb, f)), err_msg=f)


# -- edge-partitioned storage (DESIGN.md §10) ---------------------------------

def _part_arcs(ep):
    """Live (sender, global receiver) multiset per slice, host-side."""
    out = []
    for d in range(ep.n_shards):
        m = ep._mask_h[d][:ep._fill[d]]
        s = ep._send_h[d][:ep._fill[d]][m]
        r = ep._recv_h[d][:ep._fill[d]][m] + d * ep.n_loc
        out.append(sorted(zip(s.tolist(), r.tolist())))
    return out


def _coo_arcs(g):
    em = np.asarray(g.edge_mask)
    return sorted(zip(np.asarray(g.senders)[em].tolist(),
                      np.asarray(g.receivers)[em].tolist()))


def _churn(g, rng, n_add=40, n_rem=10, u_max=128):
    """One mixed add/remove batch drawn against the live arcs of ``g``."""
    upd = UpdateBatch.additions(rng.integers(0, N, n_add),
                                rng.integers(0, N, n_add), u_max=u_max)
    em = np.asarray(g.edge_mask)
    ls = np.asarray(g.senders)[em]
    lr = np.asarray(g.receivers)[em]
    if len(ls) and n_rem:
        idx = rng.choice(len(ls), size=min(n_rem, len(ls)), replace=False)
        pad = u_max - len(idx)
        upd = upd._replace(
            rem_src=jnp.asarray(np.pad(ls[idx], (0, pad)).astype(np.int32)),
            rem_dst=jnp.asarray(np.pad(lr[idx], (0, pad)).astype(np.int32)),
            rem_mask=jnp.asarray(np.arange(u_max) < len(idx)))
    return upd


def test_partition_slice_capacity_and_bytes_gate():
    assert partition_slice_capacity(4096, 4) == 1280  # ceil(1.25 · e/g)
    ep = EdgePartition(N, 4096, G)
    assert ep.slice_nbytes() == ep.e_cap_slice * 9
    if G >= 4:
        # the ISSUE acceptance gate: per-device edge bytes at g=4 must be
        # ≤ 0.35× the replicated arrays (1.25/4 = 0.3125)
        assert (ep.slice_nbytes()
                <= 0.35 * EdgePartition.replicated_nbytes(4096))


def test_partitioned_coo_sweeps_bitwise():
    g, rng = _graph()
    ep = EdgePartition(N, 4096, G)
    ep.rebuild(g)
    part = ep.part
    sweeps = ShardedSweep(G)
    e = restart_onehot(jnp.asarray([3, 77, 130]), N)

    ref = rwr(g, e, iters=12)
    got, n, _ = sweeps.run_rwr(g, e, iters=12, part=part)
    assert int(n) == 12
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    ref = label_rwr(g, 4, iters=10)
    got, _, _ = sweeps.label_table(g, 4, 10, 0.15, None, None, part=part)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    ref, n_ref, sk_ref = rwr_adaptive(g, e, max_iters=40, tol=1e-5)
    got, n_got, sk_got = sweeps.run_rwr(g, e, iters=40, tol=1e-5, part=part)
    assert (int(n_got), int(sk_got)) == (int(n_ref), int(sk_ref))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    src = jnp.asarray(rng.integers(0, N, 6).astype(np.int32))
    ref = _bfs_reach_hops(g, src, 4)
    got = sweeps.reach(g, src, 4, part=part)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_partitioned_ell_mirror_bitwise_with_smaller_blocks():
    g, _ = _graph(seed=2)
    partd = EllCache(N, 4096, K, n_shards=G, partitioned=True)
    full = EllCache(N, 4096, K, n_shards=G)
    assert partd.r_cap_block < full.r_cap_block  # the memory win
    partd.rebuild(g)
    full.rebuild(g)
    e = restart_onehot(jnp.asarray([0, 9]), N)
    sweeps = ShardedSweep(G)
    ref, _, _ = sweeps.run_rwr(g, e, iters=8, ell=full.ell)
    got, _, _ = sweeps.run_rwr(g, e, iters=8, ell=partd.ell)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_edge_partition_router_matches_rebuild_and_coo():
    """Incremental routing (adds, removals, drops) keeps every slice's
    live-arc multiset equal to a fresh rebuild's AND to the replicated
    COO arrays' — and the partitioned sweep stays bitwise."""
    rng = np.random.default_rng(7)
    g = new_graph(N, 4096, n_nodes=N)
    ep = EdgePartition(N, 4096, G)
    sweeps = ShardedSweep(G)
    e = restart_onehot(jnp.asarray([1, 2, 250]), N)
    for _ in range(5):
        g = ep.update(g, _churn(g, rng))
        fresh = EdgePartition(N, 4096, G)
        fresh.rebuild(g)
        assert _part_arcs(ep) == _part_arcs(fresh)
        assert sorted(sum(_part_arcs(ep), [])) == _coo_arcs(g)
        ref = rwr(g, e, iters=6)
        got, _, _ = sweeps.run_rwr(g, e, iters=6, part=ep.part)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_edge_partition_mirrors_coo_drop_and_duplicate_semantics():
    """Arcs the replicated path drops past ``e_max`` never enter a slice,
    and removals kill the FIRST live copy of a duplicated arc — exactly
    ``add_edges``/``remove_edges`` semantics."""
    e_max = 64
    g = new_graph(N, e_max, n_nodes=N)
    # slice capacity large enough that only the GLOBAL e_max drops arcs
    ep = EdgePartition(N, e_max, G, e_cap_slice=128)
    # duplicate (3, 5) three times, then overflow the global cursor
    src = np.full(80, 3, np.int32)
    dst = np.full(80, 5, np.int32)
    g = ep.update(g, UpdateBatch.additions(src[:3], dst[:3], u_max=128,
                                           undirected=False))
    g = ep.update(g, UpdateBatch.additions(src, dst, u_max=128,
                                           undirected=False))
    assert int(np.asarray(g.n_edges)) > e_max  # cursor ran past capacity
    assert sorted(sum(_part_arcs(ep), [])) == _coo_arcs(g)
    # one removal kills exactly one live copy, in both layouts
    upd = UpdateBatch.empty(128)
    upd = upd._replace(rem_src=jnp.full(128, 3, jnp.int32),
                       rem_dst=jnp.full(128, 5, jnp.int32),
                       rem_mask=jnp.asarray(np.arange(128) < 1))
    n_before = len(sum(_part_arcs(ep), []))
    g = ep.update(g, upd)
    assert len(sum(_part_arcs(ep), [])) == n_before - 1
    assert sorted(sum(_part_arcs(ep), [])) == _coo_arcs(g)


def test_edge_partition_compaction_reclaims_dead_slots():
    """A full slice with dead slots compacts (order-preserving) instead
    of overflowing, and the routed result still matches a rebuild."""
    rng = np.random.default_rng(3)
    cap = 32
    ep = EdgePartition(N, 4096, G, e_cap_slice=cap)
    g = new_graph(N, 4096, n_nodes=N)
    for _ in range(6):
        # all receivers in slice 0; heavy removal keeps live count low
        # while the append cursor keeps hitting the tiny slice capacity
        upd = _churn(g, rng, n_add=6, n_rem=10)
        upd = upd._replace(add_dst=upd.add_dst % ep.n_loc)
        g = ep.update(g, upd)
    assert ep.n_compactions > 0
    fresh = EdgePartition(N, 4096, G, e_cap_slice=cap)
    fresh.rebuild(g)
    assert _part_arcs(ep) == _part_arcs(fresh)


def test_edge_partition_overflow_is_loud():
    ep = EdgePartition(N, 4096, G, e_cap_slice=8)
    g = new_graph(N, 4096, n_nodes=N)
    src = np.arange(16, dtype=np.int32)
    dst = np.zeros(16, np.int32)  # all into slice 0
    with pytest.raises(PartitionOverflowError) as ei:
        ep.update(g, UpdateBatch.additions(src, dst, u_max=128))
    msg = str(ei.value)
    assert "slice 0" in msg and "exceed" in msg and "by 1" in msg


def test_partitioned_ell_rebuild_overflow_is_loud():
    cache = EllCache(N, 4096, K, n_shards=G, partitioned=True)
    # every arc lands on vertex 0: rows needed in slice 0 far exceed the
    # partitioned block capacity (the replicated block provably cannot
    # overflow, so the loud error is partitioned-only)
    g = new_graph(N, 4096, n_nodes=N,
                  senders=np.arange(1500) % N, receivers=np.zeros(1500))
    with pytest.raises(PartitionOverflowError) as ei:
        cache.rebuild(g)
    assert "ELL slice 0" in str(ei.value)


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_server_stores_identical_partitioned_vs_replicated(backend):
    """End-to-end acceptance pin (ISSUE): a storm-forced served stream
    ends with identical per-query stores whether the edge storage is
    co-partitioned with the receiver slices or replicated."""
    spec = TemporalGraphSpec("toy", "sparse_dense", n_vertices=N,
                             n_edges=2048, n_steps=24, seed=5, churn=0.2)
    cfg = _cfg(backend)
    stores = {}
    for part in ("on", "off"):
        srv = MatchServer(cfg, query_zoo(4),
                          ServingConfig(microbatch_window=256,
                                        adaptive=False, shard="off",
                                        graph_shard="auto",
                                        edge_partition=part,
                                        full_graph_frac=-1.0),
                          seed=0)
        assert srv.engine.g_shards > 1
        if part == "on":
            assert srv.engine.partitioned
            if backend == "coo":
                assert srv.engine.part_cache is not None
            else:
                assert srv.engine.ell_cache.partitioned
        stream = generate_stream(spec, n_measured_steps=3, u_max=128)
        srv.run(stream.graph, stream.updates)
        stores[part] = [dict(s._patterns) for s in srv.stores]
    for a, b in zip(stores["on"], stores["off"]):
        assert a == b


def test_multi_executor_drain_store_identical_partitioned():
    """ISSUE acceptance: a 2-executor runtime drains a flash-crowd
    workload on the partitioned path with stores identical to the
    single-executor lockstep run."""
    from repro.runtime import (ServingRuntime, VirtualClock, build_workload,
                               flash_crowd)
    wl = build_workload(flash_crowd(rate=2500.0, tick_s=0.01, n_ticks=10,
                                    n_vertices=N, seed=3), u_max=256)
    stores = {}
    for n in (1, 2):
        # a flash crowd piles receivers onto a few hot slices, so the
        # balanced-split headroom would overflow loudly; headroom = g lets
        # any one slice absorb every live arc (memory traded for safety)
        srv = MatchServer(_cfg("coo"), query_zoo(4),
                          ServingConfig(microbatch_window=64, shard="off",
                                        graph_shard="auto",
                                        edge_partition="on",
                                        partition_headroom=float(G),
                                        full_graph_frac=-1.0),
                          seed=0)
        rt = ServingRuntime(srv, RuntimeConfig(ingress="lockstep",
                                               n_executors=n),
                            clock=VirtualClock())
        rt.serve(wl)
        assert srv.engine._exec_pool is None  # torn down after drain
        stores[n] = [dict(s._patterns) for s in srv.stores]
    assert stores[1] == stores[2]


def test_partitioned_checkpoint_roundtrip_across_device_counts(tmp_path):
    """ISSUE satellite: save a partitioned engine (g_shards > 1), load
    the checkpoint under a DIFFERENT device count (subprocess with 2
    forced devices), replay the identical remaining stream, and pin
    store equality — the partition/ELL mirrors are caches rebuilt from
    the restored graph, so the layout is free to change across restarts."""
    import os
    import pickle
    import subprocess
    import sys
    import textwrap

    spec = TemporalGraphSpec("toy", "sparse_dense", n_vertices=N,
                             n_edges=2048, n_steps=24, seed=5, churn=0.2)
    stream = generate_stream(spec, n_measured_steps=6, u_max=128)
    half = 3
    srv = MatchServer(_cfg("coo"), query_zoo(4),
                      ServingConfig(microbatch_window=256, adaptive=False,
                                    shard="off", graph_shard="auto",
                                    edge_partition="on",
                                    full_graph_frac=-1.0),
                      seed=0)
    assert srv.engine.partitioned
    srv.run(stream.graph, stream.updates[:half])
    ckpt = tmp_path / "ckpt"
    srv.save(str(ckpt))
    # restart-equivalent reference: reload the checkpoint in-process (load
    # drops the seed memo AND the stale-tolerant Louvain dendrogram, so
    # this run is bitwise what any fresh process restoring it computes)
    srv.load(stream.graph, str(ckpt))
    srv.run(srv.graph, stream.updates[half:])
    ref = [dict(s._patterns) for s in srv.stores]

    out_pkl = tmp_path / "child_stores.pkl"
    child = textwrap.dedent(f"""
        import pickle
        import jax
        assert len(jax.devices()) == 2, jax.devices()
        from repro.config.base import IGPMConfig, ServingConfig
        from repro.core.query import query_zoo
        from repro.data.temporal import TemporalGraphSpec, generate_stream
        from repro.serving import MatchServer
        spec = TemporalGraphSpec("toy", "sparse_dense", n_vertices={N},
                                 n_edges=2048, n_steps=24, seed=5, churn=0.2)
        stream = generate_stream(spec, n_measured_steps=6, u_max=128)
        cfg = IGPMConfig(n_max={N}, e_max=8192, ell_width={K}, rwr_iters=8,
                         rwr_iters_incremental=3, top_k_patterns=6,
                         init_community_size=32, backend="coo")
        srv = MatchServer(cfg, query_zoo(4),
                          ServingConfig(microbatch_window=256,
                                        adaptive=False, shard="off",
                                        graph_shard="auto",
                                        edge_partition="on",
                                        full_graph_frac=-1.0),
                          seed=0)
        assert srv.engine.g_shards == 2 and srv.engine.partitioned
        srv.load(stream.graph, {str(ckpt)!r})
        srv.run(srv.graph, stream.updates[{half}:])
        with open({str(out_pkl)!r}, "wb") as f:
            pickle.dump([dict(s._patterns) for s in srv.stores], f)
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = "src" + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr
    with open(out_pkl, "rb") as f:
        got = pickle.load(f)
    assert got == ref


@pytest.mark.parametrize("backend", ["coo", "ell"])
@pytest.mark.parametrize("shard", ["off", "auto"])
def test_server_stores_identical_graph_sharded_vs_off(backend, shard):
    """End-to-end acceptance pin: a served stream (storms forced, so every
    step exercises the graph axis) ends with identical per-query stores
    whether the graph is sharded or replicated — including the mixed 2-D
    mesh when the query axis shards too."""
    spec = TemporalGraphSpec("toy", "sparse_dense", n_vertices=N,
                             n_edges=2048, n_steps=24, seed=5, churn=0.2)
    cfg = _cfg(backend)
    stores = {}
    for graph_shard in ("auto", "off"):
        srv = MatchServer(cfg, query_zoo(4),
                          ServingConfig(microbatch_window=256,
                                        adaptive=False, shard=shard,
                                        graph_shard=graph_shard,
                                        full_graph_frac=-1.0),
                          seed=0)
        if graph_shard == "auto":
            assert srv.engine.g_shards > 1
        stream = generate_stream(spec, n_measured_steps=3, u_max=128)
        srv.run(stream.graph, stream.updates)
        stores[graph_shard] = [dict(s._patterns) for s in srv.stores]
    for a, b in zip(stores["auto"], stores["off"]):
        assert a == b
