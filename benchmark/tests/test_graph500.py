"""The Kronecker generator: deterministic per seed, Graph500's
parameters, and a `.lux` file the program reads as the benchmark does."""
import json
import os

import numpy as np
import pytest

from benchmark import graph500

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def test_same_seed_same_graph_other_seed_other_graph():
    a = graph500.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 2**31 + 7)
    b = graph500.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 2**31 + 7)
    c = graph500.kronecker_edges(10, 16, 0.57, 0.19, 0.19, 2**31 + 8)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.int32 and len(a[0]) == 16 << 10


@pytest.mark.parametrize("name", ["pr-graph500-22", "bfs-graph500-22"])
def test_configs_state_graph500_parameters(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        ds = json.load(f)["dataset"]
    assert (ds["scale"], ds["edge_factor"]) == (22, 16)
    assert (ds["a"], ds["b"], ds["c"]) == (0.57, 0.19, 0.19)
    assert (ds["nv"], ds["ne"]) == (1 << 22, 2 * 16 << 22)
    assert ds["undirected"] is True


def test_quadrant_probabilities():
    """At scale 1 every edge is one quadrant draw: the diagonal pairs
    (A and D) and the most common pair (A) show the initiator, whatever
    the label permutation does."""
    src, dst = graph500.kronecker_edges(1, 1 << 17, 0.57, 0.19, 0.19, 5)
    n = len(src)
    assert np.mean(src == dst) == pytest.approx(0.62, abs=0.01)
    pairs = np.bincount(2 * src + dst, minlength=4) / n
    assert pairs.max() == pytest.approx(0.57, abs=0.01)


def test_graph_is_undirected_with_every_tuple_kept():
    src, dst = graph500.kronecker_edges(9, 16, 0.57, 0.19, 0.19, 3)
    g = graph500.kronecker(9, 16, 0.57, 0.19, 0.19, 3)
    assert (g.nv, g.ne) == (1 << 9, 2 * len(src))
    arcs = np.sort(np.repeat(np.arange(g.nv), np.diff(g.in_ptr))
                   * g.nv + g.in_src)
    both = np.sort(np.concatenate([dst * g.nv + src, src * g.nv + dst]))
    assert np.array_equal(arcs, both)


def test_lux_round_trip_and_program_reader(tmp_path):
    from lux_tpu.graph.format import read_lux as program_read

    g = graph500.kronecker(9, 16, 0.57, 0.19, 0.19, 3)
    path = str(tmp_path / "g.lux")
    graph500.write_lux(path, g)
    back = graph500.read_lux(path)
    assert np.array_equal(back.in_ptr, g.in_ptr)
    assert np.array_equal(back.in_src, g.in_src)
    assert all(np.array_equal(x, y) for x, y in zip(back.out, g.out))
    theirs = program_read(path)
    assert np.array_equal(theirs.row_ptr, g.in_ptr)
    assert np.array_equal(np.asarray(theirs.col_idx), g.in_src)
    assert np.array_equal(theirs.out_degrees(), g.out_degree())


def test_dataset_cache_keeps_one_seed(tmp_path):
    params = {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
    _, p1, made1 = graph500.dataset(str(tmp_path), "d", params, 1)
    _, p1b, made1b = graph500.dataset(str(tmp_path), "d", params, 1)
    _, p2, _ = graph500.dataset(str(tmp_path), "d", params, 2)
    assert (made1, made1b, p1) == (True, False, p1b)
    assert os.listdir(tmp_path) == [os.path.basename(p2)]
