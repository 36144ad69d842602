"""Multi-device guard inside the single-device tier-1 suite.

The sharding suites (``test_engine_sharding.py``, ``test_graph_sharding.py``)
skip on the one device tier-1 sees, so an API break in the ``shard_map``
paths stays invisible there. This test runs a small served stream in a
child process with four forced host devices (and ``JAX_PLATFORMS=cpu``, so
the child never opens an accelerator) and pins that the query-sharded and
the graph-sharded, edge-partitioned layouts end with the same pattern
stores as the single-device path.
"""

import os
import subprocess
import sys
import textwrap

_CHILD = textwrap.dedent("""
    import jax
    import numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    from repro.config.base import IGPMConfig, ServingConfig
    from repro.core.graph import ell_from_graph, new_graph
    from repro.core.query import query_zoo
    from repro.core.rwr import label_rwr
    from repro.data.temporal import TemporalGraphSpec, generate_stream
    from repro.engine.buckets import QueryBucket
    from repro.serving import MatchServer

    spec = TemporalGraphSpec("toy", "sparse_dense", n_vertices=256,
                             n_edges=2048, n_steps=24, seed=5, churn=0.2)

    def stores(backend, **layout):
        cfg = IGPMConfig(n_max=256, e_max=8192, ell_width=8, rwr_iters=8,
                         rwr_iters_incremental=3, top_k_patterns=6,
                         init_community_size=32, backend=backend)
        srv = MatchServer(cfg, query_zoo(8),
                          ServingConfig(microbatch_window=256,
                                        adaptive=False, **layout), seed=0)
        stream = generate_stream(spec, n_measured_steps=3, u_max=128)
        srv.run(stream.graph, stream.updates)
        return srv, [dict(s._patterns) for s in srv.stores]

    # one query-sharded bucket across two ELL tile sizes (consecutive
    # induced subgraphs land in different capacity buckets)
    cfg = IGPMConfig(n_max=256, e_max=8192, ell_width=8, rwr_iters=4,
                     top_k_patterns=4, backend="ell")
    sharded = QueryBucket(cfg, 8, 8, 4, shard="auto")
    plain = QueryBucket(cfg, 8, 8, 4, shard="off")
    assert sharded.n_shards == 4
    for i, q in enumerate(query_zoo(4)):
        sharded.register(f"q{i}", q)
        plain.register(f"q{i}", q)
    rng = np.random.default_rng(0)
    for n in (64, 128):
        g = new_graph(n, 1024, labels=rng.integers(0, 4, n),
                      senders=rng.integers(0, n, 300),
                      receivers=rng.integers(0, n, 300))
        ell = ell_from_graph(g, 8)
        r_lab = label_rwr(g, 4, iters=4, ell=ell)
        a, b = sharded.match(g, r_lab, ell=ell), plain.match(g, r_lab, ell=ell)
        for f in a._fields:
            assert np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f))), (f, n)

    srv, q_sharded = stores("ell", shard="auto")
    assert any(b.n_shards > 1 for b in srv.engine.buckets.values())
    _, plain = stores("ell", shard="off")
    assert q_sharded == plain, "query-sharded stores differ"

    storm = dict(shard="off", full_graph_frac=-1.0)
    srv, g_sharded = stores("coo", graph_shard="auto", edge_partition="on",
                            **storm)
    assert srv.engine.g_shards > 1 and srv.engine.partitioned
    _, replicated = stores("coo", **storm)
    assert g_sharded == replicated, "graph-sharded stores differ"
    print("stores identical")
""")


def test_sharded_stores_match_single_device_in_forced_4_device_child():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src" + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=root,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "stores identical" in res.stdout
