"""The plain reference's pieces on hand-made inputs."""

import dataclasses

import numpy as np
import pytest

from bench import reference as ref


def test_order_check_counts_reorder_repeat_and_loss():
    offered = np.array([[1, 2], [3, 4], [5, 6], [7, 8]])
    assert ref.check_order(offered, offered, 0) == 0
    # one shed event, counted by the queue: in order
    assert ref.check_order(offered, offered[[0, 2, 3]], 1) == 0
    # one lost event the queue never counted
    assert ref.check_order(offered, offered[[0, 2, 3]], 0) == 1
    # a repeat
    assert ref.check_order(offered, offered[[0, 1, 1, 2, 3]], 0) >= 1
    # a swap
    assert ref.check_order(offered, offered[[1, 0, 2, 3]], 0) >= 1


def test_multiset_diff():
    a = ref.arc_keys(np.array([1, 1, 2]), np.array([2, 2, 3]), 10)
    b = ref.arc_keys(np.array([1, 2]), np.array([2, 3]), 10)
    assert ref.multiset_diff(a, a) == 0
    assert ref.multiset_diff(a, b) == 1


def _triangle_graph():
    # undirected triangle 0-1-2 plus a pendant 2-3, labels 0,1,2,0
    e = [(0, 1), (1, 2), (2, 0), (2, 3)]
    src = np.array([u for u, v in e] + [v for u, v in e])
    dst = np.array([v for u, v in e] + [u for u, v in e])
    return src, dst, np.array([0, 1, 2, 0])


def test_induced_rwr_is_the_fixed_point_iteration():
    src, dst, labels = _triangle_graph()
    p = ref.Params(n_labels=3, restart=0.15, rwr_iters=200, top_k=2,
                   bridge_hops=4)
    sub = ref.Induced(np.arange(4), src, dst, labels, p)
    r = sub.label_table()
    # at the fixed point r = c e + (1-c) P^T r
    a = np.zeros((4, 4))
    a[dst, src] = 1.0
    pt = a / a.sum(axis=0, keepdims=True)
    e = np.eye(3)[labels]
    e = e / e.sum(axis=0, keepdims=True)
    np.testing.assert_allclose(r, 0.15 * e + 0.85 * pt @ r, atol=1e-12)
    _, hops = sub.source_tables(np.array([3]))
    assert hops[:, 0].tolist() == [2, 2, 1, 0]


def _one_community(n):
    """A split tree of one level: the root holds every vertex."""
    return np.zeros((n, 1), np.int64), np.full((n, 1), n, np.int64)


def _triangle_case():
    src, dst, labels = _triangle_graph()
    p = ref.Params(n_labels=3, restart=0.15, rwr_iters=25, top_k=2,
                   bridge_hops=4, n_live=4)
    q = ref.QuerySpec("tri", np.array([0, 1, 2]), 0,
                      [(0, 1, True), (0, 2, True), (1, 2, False)])
    sub = ref.Induced(np.arange(4), src, dst, labels, p)
    logp = np.log(sub.label_table() + ref.EPS)
    r, _ = sub.source_tables(np.array([0]))
    r1, _ = sub.source_tables(np.array([1]))
    good = (logp[0, [0, 1, 2]].sum() + np.log(r[1, 0] + ref.EPS)
            + np.log(r[2, 0] + ref.EPS) + np.log(r1[2, 0] + ref.EPS))
    return src, dst, labels, p, q, good


def _record(events, recompute, rows, n=4, c=4):
    return ref.StepRecord(0, np.array(events), np.asarray(recompute), rows,
                          c, _one_community(n))


def test_check_step_accepts_the_true_match_and_flags_a_wrong_one():
    src, dst, labels, p, q, good = _triangle_case()
    p = dataclasses.replace(p, top_k=1)   # seed 0 outranks seed 3
    row = ref.RowResult("tri", np.array([[0, 1, 2]]), np.array([good]),
                        np.array([True]), np.array([True]))
    rec = _record([[0, 1]], np.arange(4), [row])
    out = ref.Readings()
    found = ref.check_step(rec, src, dst, labels, {"tri": q}, p, out)
    assert out.pattern_faults == 0 and out.goodness_gap < 1e-12
    assert out.rank_gap == 0.0 and out.missing_results == 0
    assert out.recompute_diff == 0
    assert found == [("tri", (0, 1, 2), pytest.approx(good))]
    ref.check_stores(found, {"tri": {(0, 1, 2): good}}, out)
    assert out.store_faults == 0
    ref.check_stores(found, {"tri": {}}, out)
    assert out.store_faults == 1
    # the same vertices flagged not exact, and a wrong goodness
    bad = ref.RowResult("tri", np.array([[0, 1, 2], [0, 1, 2]]),
                        np.array([good, good + 1.0]),
                        np.array([False, True]), np.array([True, True]))
    out = ref.Readings()
    ref.check_step(_record([[0, 1]], np.arange(4), [bad]), src, dst, labels,
                   {"tri": q}, p, out)
    assert out.pattern_faults == 1 and out.goodness_gap > 1e-3


def test_check_step_flags_left_out_results():
    src, dst, labels, p, q, good = _triangle_case()
    other = ref.QuerySpec("tri2", np.array([0, 1, 2]), 0,
                          [(0, 1, True), (0, 2, True), (1, 2, False)])
    queries = {"tri": q, "tri2": other}
    # seeds of label 0 are vertices 0 and 3: the top 2 are both required
    full = ref.RowResult("tri", np.array([[0, 1, 2], [3, 1, 2]]),
                         np.array([good, 0.0]), np.array([True, False]),
                         np.array([True, False]))
    # vertex 3's match (3, 1, 2) is within the bridge's hops: valid
    out = ref.Readings()
    ref.check_step(_record([[0, 1]], np.arange(4), [full]), src, dst,
                   labels, queries, p, out)
    # tri2 has no row; seed 3's complete result is marked invalid
    assert out.missing_results == 2
    # a row with only one of its two seeds
    one = ref.RowResult("tri", np.array([[0, 1, 2]]), np.array([good]),
                        np.array([True]), np.array([True]))
    out = ref.Readings()
    ref.check_step(_record([[0, 1]], np.arange(4), [one]), src, dst, labels,
                   {"tri": q}, p, out)
    assert out.missing_results == 1


def test_recompute_set_is_every_touched_community_at_c():
    # two levels: root {0..5}, children {0,1,2} and {3,4,5}
    ids = np.array([[0, 1], [0, 1], [0, 1], [0, 2], [0, 2], [0, 2]])
    sizes = np.array([[6, 3]] * 6)
    p = ref.Params(n_labels=1, restart=0.15, rwr_iters=1, top_k=1,
                   bridge_hops=1, n_live=5)
    rec = ref.StepRecord(0, np.array([[0, 1]]), None, [], 3, (ids, sizes))
    assert ref.recompute_set(rec, p).tolist() == [0, 1, 2]
    # at c 6 the root is the community; vertex 5 is not live
    rec = ref.StepRecord(0, np.array([[4, 3]]), None, [], 6, (ids, sizes))
    assert ref.recompute_set(rec, p).tolist() == [0, 1, 2, 3, 4]
    # below every size: the leaf
    assert ref.cut(ids, sizes, 2).tolist() == [1, 1, 1, 2, 2, 2]
    assert ref.tree_faults(ids, sizes) == 0
    wrong = sizes.copy()
    wrong[0, 1] = 2
    assert ref.tree_faults(ids, wrong) == 1
    two_parents = ids.copy()
    two_parents[3, 0] = 7
    assert ref.tree_faults(two_parents, sizes) > 0
    # the program's set must match exactly, and c lie in its range
    src, dst, labels, p4, q, good = _triangle_case()
    out = ref.Readings()
    ref.check_step(_record([[0, 3]], np.arange(3), []), src, dst, labels,
                   {}, p4, out)
    assert out.recompute_diff == 1 and out.partition_faults == 0
    out = ref.Readings()
    ref.check_step(_record([[0, 3]], np.arange(4), [], c=1), src, dst,
                   labels, {}, p4, out)
    assert out.recompute_diff == 0 and out.partition_faults == 1


def test_verdict_needs_results_and_every_limit():
    ok, compared = ref.verdict(ref.Readings(n_results=3))
    assert ok and set(compared) == set(ref.LIMITS)
    assert not ref.verdict(ref.Readings())[0]
    assert not ref.verdict(ref.Readings(n_results=3, edge_diff=1))[0]
