"""Both references against answers worked out by hand on a tiny graph.

Undirected tuples: 0-1, 0-2, 1-2, 2-3; vertex 4 is isolated.
Degrees: 2, 2, 3, 1, 0.  Nothing reaches 4.
"""
import numpy as np
import pytest

from benchmark import graph500
from benchmark.apps import bfs, pagerank

SRC = np.array([0, 0, 1, 2], np.int32)
DST = np.array([1, 2, 2, 3], np.int32)


@pytest.fixture
def graph():
    return graph500.build_graph(SRC, DST, 5)


def test_the_graph_is_symmetric(graph):
    assert graph.ne == 8
    assert graph.out_degree().tolist() == [2, 2, 3, 1, 0]
    ptr, nbr = graph.out
    assert sorted(nbr[ptr[2]:ptr[3]].tolist()) == [0, 1, 3]


def test_pagerank_one_iteration_by_hand(graph):
    conf = {"semantics": {"alpha": 0.15, "iterations": 1}}
    # start: x = (1/5)/deg = [1/10, 1/10, 1/15, 1/5, 1/5] (deg 0: undivided)
    # sums: v0 <- x1+x2; v1 <- x0+x2; v2 <- x0+x1+x3 = 2/5; v3 <- x2; v4 none
    base = 0.85 / 5
    want = np.array([(base + 0.15 / 6) / 2, (base + 0.15 / 6) / 2,
                     (base + 0.15 * 2 / 5) / 3, base + 0.15 / 15, base])
    np.testing.assert_allclose(pagerank.reference(conf, graph, None), want,
                               rtol=1e-15)


def test_pagerank_reference_matches_the_recurrence_twice(graph):
    conf = {"semantics": {"alpha": 0.15, "iterations": 2}}
    one = pagerank.reference({"semantics": {"alpha": 0.15,
                                            "iterations": 1}}, graph, None)
    deg = np.array([2.0, 2.0, 3.0, 1.0, 1.0])
    acc = np.zeros(5)
    np.add.at(acc, np.concatenate([DST, SRC]), one[np.concatenate([SRC, DST])])
    np.testing.assert_allclose(pagerank.reference(conf, graph, None),
                               (0.85 / 5 + 0.15 * acc) / deg, rtol=1e-15)


def test_bfs_levels_by_hand(graph):
    assert bfs.levels(*graph.out, graph.nv, 0).tolist() == [0, 1, 1, 2, 5]
    assert bfs.levels(*graph.out, graph.nv, 3).tolist() == [2, 2, 1, 0, 5]
    # the control: one level short
    assert bfs.levels(*graph.out, graph.nv, 3, max_depth=1).tolist() == [
        5, 5, 1, 0, 5]


def test_bfs_roots_and_work(graph):
    prep = bfs.prepare({}, graph)
    # degree 1 or more, highest first, ties by id
    assert prep["eligible"].tolist() == [2, 0, 1, 3]
    edges, nbytes = bfs.work({}, graph, prep, 2)
    # 4 tuples inside the component; 4 vertices reached, 8 arcs
    assert edges == 4
    assert nbytes == 4 * 4 + 8 * 8 + 5 * 4
    assert bfs.check({}, graph, prep, 2, [1, 1, 0, 1, 5]) == {
        "level_mismatches": 0}


def test_bfs_work_counts_self_loops_and_duplicates_as_tuples():
    g = graph500.build_graph(np.array([0, 1, 0], np.int32),
                             np.array([1, 1, 1], np.int32), 3)
    edges, _ = bfs.work({}, g, bfs.prepare({}, g), 0)
    assert edges == 3


def test_pagerank_work_counts_sizes_only(graph):
    conf = {"semantics": {"iterations": 10}}
    assert pagerank.work(conf, graph, {}, None) == (
        10 * 8, 10 * (8 * 8 + 5 * 16))
