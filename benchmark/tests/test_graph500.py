"""The Kronecker generators: deterministic per seed, Graph500's
parameters, and a `.lux` file the program reads as the benchmark does.
The chunked generator's graph does not depend on its worker count, and
its grouping is ``build_graph``'s."""
import hashlib
import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from benchmark import graph500

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
OLD, CHUNKED = "graph500 kronecker", "graph500 kronecker chunked"
PARAMS = {"edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
#: sha256 of the `.lux` bytes that the configurations' dataset, cut to
#: scale 10, gives for seed 2**31 + 12345, taken before the chunked
#: generator existed: the old generator may not move
OLD_LUX_SHA256 = (
    "36b5225e1dc03148f8c77b5396a65e6f886d4c877bd06579277822bbaf6ec684")


@pytest.fixture
def small_chunks(monkeypatch):
    """Streams of 2**10 tuples, so small scales draw from many."""
    monkeypatch.setattr(graph500, "DRAW_CHUNK", 1 << 10)


def _tuples(generator, *params):
    """(src, dst) of either generator, as host arrays."""
    if generator == OLD:
        return graph500.kronecker_edges(*params)
    with ThreadPoolExecutor(2) as pool:
        return graph500.chunked_tuples(pool, *params)


def _lux_bytes(tmp_path, g):
    path = str(tmp_path / "g.lux")
    graph500.write_lux(path, g)
    with open(path, "rb") as f:
        return f.read()


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
    assert ds["generator"] == OLD
    assert (ds["scale"], ds["edge_factor"]) == (22, 16)
    assert (ds["a"], ds["b"], ds["c"]) == (0.57, 0.19, 0.19)
    assert (ds["nv"], ds["ne"]) == (1 << 22, 2 * 16 << 22)
    assert ds["undirected"] is True


@pytest.mark.parametrize("name", ["pr-graph500-22", "bfs-graph500-22"])
def test_old_generator_bytes_are_pinned(tmp_path, name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        params = {**json.load(f)["dataset"], "scale": 10}
    _, path, made = graph500.dataset(str(tmp_path), "d", params,
                                     2**31 + 12345)
    with open(path, "rb") as f:
        assert made and hashlib.sha256(f.read()).hexdigest() == \
            OLD_LUX_SHA256


def test_chunked_bytes_do_not_depend_on_workers(tmp_path, small_chunks):
    seed = 2**33 + 5
    got = [_lux_bytes(tmp_path, graph500.kronecker_chunked(
        10, 16, 0.57, 0.19, 0.19, seed, workers=n)) for n in (1, 3, 8)]
    assert got[0] == got[1] == got[2]
    other = _lux_bytes(tmp_path, graph500.kronecker_chunked(
        10, 16, 0.57, 0.19, 0.19, seed + 1, workers=3))
    assert len(other) == len(got[0]) and other != got[0]


@pytest.mark.parametrize("tuples,scale", [("kronecker", 11), ("hubs", 8),
                                          ("hubs", 12)])
def test_chunked_grouping_is_build_graphs(small_chunks, tuples, scale):
    """Byte for byte, on the generator's tuples and on tuples that pile
    into a few vertices (one label a bucket at scale 8, 16 at 12)."""
    nv = 1 << scale
    with ThreadPoolExecutor(3) as pool:
        if tuples == "kronecker":
            src, dst = graph500.chunked_tuples(pool, scale, 16, 0.57, 0.19,
                                               0.19, 9)
        else:
            rng = np.random.default_rng(scale)
            src, dst = (np.where(rng.random(20000) < 0.5,
                                 rng.choice(hubs, 20000),
                                 rng.integers(0, nv, 20000)).astype(np.int32)
                        for hubs in ([0, nv - 1], [nv // 2]))
        g = graph500.group(pool, src, dst, nv)
    want = graph500.build_graph(src, dst, nv)
    assert (g.nv, g.ne) == (want.nv, want.ne)
    assert g.in_ptr.dtype == want.in_ptr.dtype
    assert g.in_src.dtype == want.in_src.dtype
    assert np.array_equal(g.in_ptr, want.in_ptr)
    assert np.array_equal(g.in_src, want.in_src)
    if tuples == "kronecker":
        whole = graph500.kronecker_chunked(scale, 16, 0.57, 0.19, 0.19, 9,
                                           workers=2)
        assert np.array_equal(whole.in_src, want.in_src)


@pytest.mark.parametrize("generator", [OLD, CHUNKED])
def test_quadrant_probabilities(generator):
    """At scale 1 every edge is one quadrant draw: the diagonal pairs
    (A and D) and the most common pair (A) show the initiator, whatever
    the label permutation does."""
    src, dst = _tuples(generator, 1, 1 << 17, 0.57, 0.19, 0.19, 5)
    n = len(src)
    assert n == 1 << 18
    assert np.mean(src == dst) == pytest.approx(0.62, abs=0.01)
    pairs = np.bincount(2 * src + dst, minlength=4) / n
    assert pairs.max() == pytest.approx(0.57, abs=0.01)


@pytest.mark.parametrize("generator", [OLD, CHUNKED])
def test_graph_is_undirected_with_every_tuple_kept(small_chunks, generator):
    src, dst = _tuples(generator, 9, 16, 0.57, 0.19, 0.19, 3)
    g = graph500.GENERATORS[generator](9, 16, 0.57, 0.19, 0.19, 3)
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


@pytest.mark.parametrize("generator", [OLD, CHUNKED])
def test_dataset_cache_keeps_one_seed(tmp_path, generator):
    params = {"scale": 8, **PARAMS, "generator": generator}
    _, p1, made1 = graph500.dataset(str(tmp_path), "d", params, 1)
    _, p1b, made1b = graph500.dataset(str(tmp_path), "d", params, 1)
    _, p2, _ = graph500.dataset(str(tmp_path), "d", params, 2)
    assert (made1, made1b, p1) == (True, False, p1b)
    assert os.listdir(tmp_path) == [os.path.basename(p2)]


def test_unknown_generator_is_refused(tmp_path):
    params = {"scale": 8, **PARAMS, "generator": "graph500 rmat"}
    with pytest.raises(ValueError, match="unknown dataset generator"):
        graph500.dataset(str(tmp_path), "d", params, 1)
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("generator", [OLD, CHUNKED])
def test_timing_entry_point(tmp_path, capsys, generator):
    rc = graph500.main(["--scale", "8", "--seed", "4", "--generator",
                        generator, "--out", str(tmp_path), "--workers", "2"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and os.listdir(tmp_path) == []
    assert (line["nv"], line["ne"]) == (256, 2 * 16 * 256)
    assert line["workers"] == (1 if generator == OLD else 2)
    assert all(line[k] > 0 for k in ("generation_s", "grouping_s",
                                     "write_s", "maxrss_gb"))
