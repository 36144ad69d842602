"""Device-sharded bucket execution equality (DESIGN.md §4).

Runs ONLY under a forced multi-device host platform:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest tests/test_engine_sharding.py -q

(`make engine-smoke` / the CI multi-device job do exactly that). On the
default single-device container every test here skips — the tier-1 suite
stays single-device as conftest.py requires.

The contract: the shard_map-over-query-axis path is a pure distribution of
the vmap path — per-bucket match results and final per-query stores are
IDENTICAL, on both sweep backends.
"""

import numpy as np
import pytest

import jax

from repro.config.base import IGPMConfig, ServingConfig
from repro.core.graph import new_graph
from repro.core.query import query_zoo
from repro.core.rwr import label_rwr
from repro.data.temporal import TemporalGraphSpec, generate_stream
from repro.engine.buckets import QueryBucket
from repro.engine.sharding import query_shard_count
from repro.serving import MatchServer

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")


def _cfg(backend="ell"):
    return IGPMConfig(n_max=256, e_max=8192, ell_width=8, rwr_iters=8,
                      rwr_iters_incremental=3, top_k_patterns=6,
                      init_community_size=32, backend=backend)


def test_shard_count_pow2_and_capped():
    nd = len(jax.devices())
    assert query_shard_count(1) == 1
    assert query_shard_count(2) == 2
    assert query_shard_count(16) == (4 if nd >= 4 else 2)
    assert query_shard_count(16, shard="off") == 1


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_bucket_sharded_match_equals_vmap(backend):
    """One bucket matches graphs of two sizes, as consecutive induced
    subgraphs of different capacity buckets do (the ELL mirror's static
    vertex count changes between the calls)."""
    rng = np.random.default_rng(1)
    cfg = _cfg(backend)
    from repro.core.graph import ell_from_graph
    sharded = QueryBucket(cfg, 8, 8, 4, shard="auto")
    plain = QueryBucket(cfg, 8, 8, 4, shard="off")
    assert sharded.n_shards > 1
    for i, q in enumerate(query_zoo(4)):
        sharded.register(f"q{i}", q)
        plain.register(f"q{i}", q)
    for n in (128, 256):
        g = new_graph(n, 2048,
                      labels=rng.integers(0, 4, n).astype(np.int32),
                      senders=rng.integers(0, n, 500),
                      receivers=rng.integers(0, n, 500))
        ell = ell_from_graph(g, cfg.ell_width) if backend == "ell" else None
        r_lab = label_rwr(g, cfg.n_labels, iters=cfg.rwr_iters, ell=ell)
        ra = sharded.match(g, r_lab, ell=ell)
        rb = plain.match(g, r_lab, ell=ell)
        for f in ra._fields:
            np.testing.assert_array_equal(np.asarray(getattr(ra, f)),
                                          np.asarray(getattr(rb, f)),
                                          err_msg=f"{f} at n={n}")


@pytest.mark.parametrize("backend", ["coo", "ell"])
def test_server_stores_identical_sharded_vs_vmap(backend):
    """End-to-end acceptance pin: a served stream ends with identical
    per-query pattern stores whether buckets run sharded or vmapped."""
    spec = TemporalGraphSpec("toy", "sparse_dense", n_vertices=256,
                             n_edges=2048, n_steps=24, seed=5, churn=0.2)
    cfg = _cfg(backend)
    stores = {}
    for shard in ("auto", "off"):
        srv = MatchServer(cfg, query_zoo(8),
                          ServingConfig(microbatch_window=256,
                                        adaptive=False, shard=shard),
                          seed=0)
        if shard == "auto":
            assert any(b.n_shards > 1 for b in srv.engine.buckets.values())
        stream = generate_stream(spec, n_measured_steps=3, u_max=128)
        srv.run(stream.graph, stream.updates)
        stores[shard] = [dict(s._patterns) for s in srv.stores]
    for a, b in zip(stores["auto"], stores["off"]):
        assert a == b
