"""Push shards placed part by part on their chips, and the normal push
entry point driving them.

``shard_stacked`` puts each device's k = P / D parts straight onto that
device and hands already-placed arrays back as they are;
``push_init_dist`` builds the first carry on the mesh; the apps place
the shards for ``-ng N --distributed``, and ``run_push`` runs placed
multi-device shards through ``run_push_dist``.  Each entry point agrees bit
for bit with the others and with a NumPy BFS on an undirected Kronecker
graph (Graph500 kernel 1: each tuple stored both ways).  Runs on the
suite's virtual CPU devices.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu import obs
from lux_tpu.engine import push
from lux_tpu.graph import generate
from lux_tpu.graph.csc import from_edge_list
from lux_tpu.graph.push_shards import build_push_shards
from lux_tpu.models.sssp import SSSPProgram, bfs_reference
from lux_tpu.obs.recorder import Recorder
from lux_tpu.parallel.mesh import make_mesh, parts_mesh, shard_stacked


@pytest.fixture()
def rec(tmp_path):
    r = Recorder(run_id="placed", root=str(tmp_path), enabled=True)
    old = obs.install(r)
    yield r
    r.close()
    obs.install(old)


@pytest.fixture(scope="module")
def kron():
    """Undirected Kronecker graph, scale 9, edge factor 16: every tuple
    both ways, self loops and duplicates kept."""
    d = generate.rmat(9, 16, seed=27)
    src, dst = np.asarray(d.col_idx), d.dst_of_edges()
    return from_edge_list(np.concatenate([src, dst]),
                          np.concatenate([dst, src]), d.nv)


@pytest.fixture(scope="module")
def host4(kron):
    return build_push_shards(kron, 4)


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4)


def _roots(g):
    deg = g.out_degrees()
    top = int(np.argmax(deg))
    other = int(np.flatnonzero(deg > 0)[len(np.flatnonzero(deg > 0)) // 2])
    return [top, other]


@pytest.mark.parametrize("num_parts,num_dev", [(4, 4), (8, 4), (8, 8)])
def test_shard_stacked_puts_each_device_its_parts(num_parts, num_dev):
    mesh = make_mesh(num_dev)
    k = num_parts // num_dev
    host = np.arange(num_parts * 6, dtype=np.int32).reshape(num_parts, 6)
    placed = shard_stacked(mesh, host)
    shards = sorted(placed.addressable_shards, key=lambda s: s.device.id)
    assert [s.device for s in shards] == list(mesh.devices)
    for d, s in enumerate(shards):
        assert s.data.shape == (k, 6)
        np.testing.assert_array_equal(np.asarray(s.data),
                                      host[d * k:(d + 1) * k])


def test_shard_stacked_returns_placed_arrays_as_they_are(mesh4):
    tree = {"a": np.ones((4, 5), np.float32), "b": np.zeros((8,), bool)}
    placed = shard_stacked(mesh4, tree)
    again = shard_stacked(mesh4, placed)
    for name in tree:
        assert again[name] is placed[name]
        assert ([s.data.unsafe_buffer_pointer()
                 for s in again[name].addressable_shards]
                == [s.data.unsafe_buffer_pointer()
                    for s in placed[name].addressable_shards])
    # the apps' jnp.asarray of placed arrays moves nothing either
    assert jnp.asarray(placed["a"]) is placed["a"]


def test_parts_mesh_reads_the_layout(mesh4):
    host = np.zeros((4, 3), np.int32)
    assert parts_mesh(host) is None
    assert parts_mesh(jnp.asarray(host)) is None
    assert parts_mesh(shard_stacked(make_mesh(1), host)) is None
    assert parts_mesh(shard_stacked(mesh4, host)) == mesh4


@pytest.mark.parametrize("placed", [True, False])
def test_push_init_dist_holds_no_part_off_its_device(kron, host4, mesh4,
                                                     placed):
    shards = (push.place_push_shards(host4, mesh4) if placed else host4)
    prog = SSSPProgram(nv=kron.nv, start=_roots(kron)[0])
    before = {id(a) for a in jax.live_arrays()}
    out = push.push_init_dist(prog, shards, mesh4)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert new, "push_init_dist created no array"
    for a in new:
        for s in a.addressable_shards:
            # one part's rows a device: nothing stacked over all 4 parts
            assert a.ndim == 0 or s.data.shape[0] != 4, (a.shape, s.device)
    arrays, parrays, carry = out
    for a in jax.tree.leaves((arrays, parrays, carry.state, carry.q_vid,
                              carry.q_val, carry.count, carry.sp_work)):
        assert {s.data.shape[0] for s in a.addressable_shards} == {1}
        assert len(a.addressable_shards) == 4


def test_place_push_shards_keeps_the_placed_buffers(kron, host4, mesh4):
    placed = push.place_push_shards(host4, mesh4)
    arrays, parrays, _ = push.push_init_dist(
        SSSPProgram(nv=kron.nv, start=0), placed, mesh4)
    for a, b in zip(jax.tree.leaves((arrays, parrays)),
                    jax.tree.leaves((placed.arrays, placed.parrays))):
        assert a is b


def test_build_push_app_shards_places_for_distributed(tmp_path, kron):
    from lux_tpu.apps.sssp import build_push_app_shards
    from lux_tpu.graph.format import write_lux
    from lux_tpu.utils.config import parse_args

    path = str(tmp_path / "g.lux")
    write_lux(path, kron)
    args = ["-file", path, "-ng", "4"]
    host = build_push_app_shards(kron, parse_args(args, sssp=True,
                                                  push=True))
    assert isinstance(host.arrays.src_pos, np.ndarray)
    sh = build_push_app_shards(
        kron, parse_args(args + ["--distributed"], sssp=True, push=True))
    for a in jax.tree.leaves((sh.arrays, sh.parrays)):
        assert isinstance(a, jax.Array)
        assert len(a.addressable_shards) == 4
        assert {s.data.shape[0] for s in a.addressable_shards} == {1}
    assert parts_mesh(sh.arrays).devices.size == 4
    for a, b in zip(jax.tree.leaves((sh.arrays, sh.parrays)),
                    jax.tree.leaves((host.arrays, host.parrays))):
        np.testing.assert_array_equal(np.asarray(a), b)


@pytest.mark.parametrize("root_at", [0, 1])
def test_run_push_on_placed_shards_is_bitwise(rec, kron, host4, mesh4,
                                              root_at):
    root = _roots(kron)[root_at]
    prog = SSSPProgram(nv=kron.nv, start=root)
    placed = push.place_push_shards(host4, mesh4)

    one = push.run_push(prog, host4)
    dist = push.run_push_dist(prog, host4, mesh4)
    before = obs.counters("push.")
    got = push.run_push(prog, placed)
    after = obs.counters("push.")
    assert len(got[0].addressable_shards) == 4  # ran on the mesh
    for a, b in ((one, got), (dist, got)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    levels = host4.scatter_to_global(np.asarray(got[0]))
    np.testing.assert_array_equal(levels, bfs_reference(kron, root))
    dense = after["push.dense_rounds"][1] - before["push.dense_rounds"][1]
    if root_at == 0:
        assert dense >= 1  # the top-degree root's frontier goes dense


def test_run_push_refuses_single_device_options_on_placed(kron, host4,
                                                          mesh4):
    placed = push.place_push_shards(host4, mesh4)
    prog = SSSPProgram(nv=kron.nv, start=0)
    for kw in ({"donate": True}, {"telemetry": object()},
               {"route": object()}):
        with pytest.raises(ValueError, match="distributed loop"):
            push.run_push(prog, placed, **kw)


@pytest.mark.parametrize("num_parts", [4, 8])
def test_exchange_bytes_counts_the_rounds_taken(rec, kron, mesh4,
                                                num_parts):
    shards = build_push_shards(kron, num_parts)
    spec, pspec = shards.spec, shards.pspec
    prog = SSSPProgram(nv=kron.nv, start=_roots(kron)[0])
    push.run_push_dist(prog, shards, mesh4)
    got = obs.counters("push.")
    it, dense = got["push.rounds"][1], got["push.dense_rounds"][1]
    k = num_parts // 4
    # every round: the other chips' (vid, level) queues; a dense round:
    # their levels too (int32 each)
    want = ((num_parts - k) * pspec.f_cap * 8 * it
            + (num_parts - k) * spec.nv_pad * 4 * dense)
    assert dense >= 1 and it > dense
    assert got["push.exchange_bytes"] == (1, want)
    levels = np.zeros((num_parts, spec.nv_pad), np.int32)
    assert push.exchange_bytes(pspec, spec, 4, levels) == (
        (num_parts - k) * pspec.f_cap * 8, (num_parts - k) * spec.nv_pad * 4)
    wide = np.zeros((num_parts, spec.nv_pad, 3), np.float32)
    assert push.exchange_bytes(pspec, spec, 4, wide) == (
        (num_parts - k) * pspec.f_cap * 16,
        (num_parts - k) * spec.nv_pad * 12)
    assert push.exchange_bytes(pspec, spec, 1, levels) == (0, 0)


def test_exchange_bytes_is_zero_on_one_device(rec, kron, host4):
    prog = SSSPProgram(nv=kron.nv, start=_roots(kron)[0])
    push.run_push(prog, host4)
    push.run_push_dist(prog, host4, make_mesh(1))
    assert obs.counters("push.")["push.exchange_bytes"] == (2, 0)


def test_place_push_shards_span(rec, host4, mesh4):
    push.place_push_shards(host4, mesh4)
    assert rec.total_count("push.place") == 1


def test_exchange_scope_in_distributed_op_names(kron, host4, mesh4):
    prog = SSSPProgram(nv=kron.nv, start=0)
    arrays, parrays, carry0 = push.push_init_dist(prog, host4, mesh4)
    run = push._compile_push_dist(prog, mesh4, host4.pspec, host4.spec,
                                  "scatter")
    text = run.lower(arrays, parrays, carry0,
                     jnp.int32(100)).compile().as_text()
    assert re.search(
        r'all-gather[^\n]*op_name="[^"]*push\.exchange', text)


def test_distributed_push_runs_the_scan_kernel(kron, host4, mesh4):
    """The TPU's auto method for the dense rounds is the blocked scan
    kernel (a Pallas call, interpreted here): it runs under the
    distributed loop's shard_map and agrees with scatter bit for bit."""
    prog = SSSPProgram(nv=kron.nv, start=_roots(kron)[0])
    placed = push.place_push_shards(host4, mesh4)
    want = push.run_push(prog, host4, method="scatter")
    got = push.run_push(prog, placed, method="mxscan")
    for x, y in zip(want, got):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_distributed_pull_runs_the_scan_kernel(kron, mesh4):
    from lux_tpu.engine import pull
    from lux_tpu.graph.shards import build_pull_shards
    from lux_tpu.models import components
    from lux_tpu.models.pagerank import PageRankProgram
    from lux_tpu.parallel import dist

    sh = build_pull_shards(kron, 4)
    prog = PageRankProgram(nv=kron.nv)
    state0 = pull.init_state(prog, sh.arrays)
    want = dist.run_pull_fixed_dist(prog, sh.spec, sh.arrays, state0, 3,
                                    mesh4, method="scatter")
    got = dist.run_pull_fixed_dist(prog, sh.spec, sh.arrays, state0, 3,
                                   mesh4, method="mxscan")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)
    cc = components.MaxLabelProgram()
    s0 = pull.init_state(cc, sh.arrays)
    want, _ = dist.run_pull_until_dist(cc, sh.spec, sh.arrays, s0, 100,
                                       components.active_count, mesh4,
                                       method="scatter")
    got, _ = dist.run_pull_until_dist(cc, sh.spec, sh.arrays, s0, 100,
                                      components.active_count, mesh4,
                                      method="mxscan")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
