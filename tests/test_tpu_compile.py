"""The Pallas kernels of the main and routed paths compile for a TPU v5e.

Nothing runs: each test compiles for a described (not attached) v5e with
``interpret=False``, so what Mosaic or the TPU compiler refuses fails here
instead of on the chip (the on-chip-measurement guide, section 2).  The
topology is described inside a module fixture: only the worker given
this file loads the TPU library.  The persistent compile cache is off
around these compiles, since a described device cannot read them back.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

LANE = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _on_chip(one_chip, tree):
    """Host arrays -> shape structs on the described chip."""
    return jax.tree.map(
        lambda a: _sds(one_chip, np.shape(a), np.asarray(a).dtype), tree)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("rows,data,index", [
    (1 << 17, jnp.float32, jnp.uint8),
    (1 << 17, jnp.float32, jnp.int32),
    (1 << 12, jnp.bfloat16, jnp.uint8),   # widened to 32 bits in-kernel
    (1, jnp.float32, jnp.uint8),          # ff base level: padded to 8 rows
    (2, jnp.bfloat16, jnp.uint8),
])
def test_lane_gather_compiles(one_chip, rows, data, index):
    from lux_tpu.ops import pallas_shuffle as shuf

    x = _sds(one_chip, (rows, LANE), data)
    i = _sds(one_chip, (rows, LANE), index)
    c = jax.jit(lambda x, i: shuf.lane_gather(x, i, rb=min(rows, 1024))
                ).lower(x, i).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("data", [jnp.float32, jnp.bfloat16])
def test_sublane_gather_compiles(one_chip, data):
    from lux_tpu.ops import pallas_shuffle as shuf

    x = _sds(one_chip, (8, 1 << 17), data)
    i = _sds(one_chip, (8, 1 << 17), jnp.uint8)
    c = jax.jit(shuf.sublane_gather).lower(x, i).compile()
    assert _kernels(c) == 1


def test_mxscan_segmented_compiles(one_chip):
    from lux_tpu.ops import pallas_scan

    n = 1 << 20
    c = jax.jit(lambda v, h, inv: pallas_scan.mxscan_segmented(
        v, h, inv, interpret=False)).lower(
            _sds(one_chip, (n,), jnp.float32),
            _sds(one_chip, (n,), jnp.bool_),
            _sds(one_chip, (n,), jnp.bool_)).compile()
    assert _kernels(c) >= 1


def test_spmv_blockcsr_compiles(one_chip):
    from lux_tpu.graph import generate
    from lux_tpu.ops import pallas_spmv as ps

    bc = ps.build_blockcsr(generate.rmat(16, 16, seed=0))
    c = jax.jit(lambda v, d, b, f: ps.spmv_blockcsr(
        v, d, b, f, v_blk=bc.v_blk, num_vblocks=bc.num_vblocks)).lower(
            _sds(one_chip, bc.e_dst_rel.shape, jnp.float32),
            *_on_chip(one_chip, (bc.e_dst_rel, bc.chunk_block,
                                 bc.chunk_first))).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("n,kinds", [
    (1 << 12, {"swap"}),          # 32-row tiles: address-bit swaps
    (1 << 14, {"T"}),
    (1 << 18, {"P", "T"}),
    (1 << 20, {"P", "T"}),
])
def test_pf_relayout_kernels_compile(one_chip, n, kinds):
    """The fused pass-group kernels of a pass-fused route chain across
    cross-row relayouts: their in-tile (128, 128) transposes, row-bit
    permutations and bit swaps lower in Mosaic at the plans' own tile
    sizes."""
    from lux_tpu.ops import pallas_shuffle as shuf
    from lux_tpu.ops import route

    rt = route.build_route(np.random.default_rng(0).permutation(n))
    static, arrays = shuf.plan_route_pf(rt)
    assert kinds == {op[0] for g in static.groups for st in g.steps
                     for op in st.relayout}
    i = 0
    for g in static.groups:
        idx = arrays[i:i + len(g.steps)]
        i += len(g.steps)
        c = jax.jit(lambda x, a, g=g: shuf.fused_pass_gather(x, a, g)).lower(
            _sds(one_chip, g.kshape, jnp.float32),
            _on_chip(one_chip, tuple(idx))).compile()
        assert _kernels(c) == 1


@pytest.mark.parametrize("route", ["expand-pf", "fused-pf", "fused-mx"])
def test_routed_pf_pagerank_loop_compiles(one_chip, route):
    """The routed pass-fused PageRank loops (--route-gather expand-pf,
    fused-pf, fused-mx) at rmat12, their plans built on the host, with
    the TPU's auto method."""
    from lux_tpu.engine import methods, pull
    from lux_tpu.graph import generate
    from lux_tpu.graph.shards import build_pull_shards
    from lux_tpu.models.pagerank import PageRankProgram
    from lux_tpu.ops import expand

    g = generate.rmat(12, 16, seed=0)
    shards = build_pull_shards(g, 1)
    if route == "expand-pf":
        rs, ra = expand.plan_expand_shards(shards, pf=True)
    else:
        rs, ra = expand.plan_fused_shards(shards, "sum", pf=True,
                                          mx=route == "fused-mx")
    prog = PageRankProgram(nv=g.nv)
    state = _sds(one_chip, (1, shards.spec.nv_pad), jnp.float32)
    method = methods.resolve_sum("auto", "sum", "tpu")
    c = pull._pull_fixed_jit.lower(
        prog, shards.spec, 10, method, _on_chip(one_chip, shards.arrays),
        state, None, route_static=rs, route_arrays=_on_chip(one_chip, ra),
        interpret=False, ostatic=None, oarrays=None).compile()
    assert _kernels(c) >= 1


#: the benchmark cell's shape: Graph500 scale 22, 2^27 arcs on one part
NV, NE = 1 << 22, 1 << 27


def _cell_arrays(one_chip):
    """ShardArrays shapes of one part holding the whole cell's graph."""
    from lux_tpu.graph.shards import ShardArrays

    return ShardArrays(
        row_ptr=_sds(one_chip, (1, NV + 1), jnp.int32),
        src_pos=_sds(one_chip, (1, NE), jnp.int32),
        dst_local=_sds(one_chip, (1, NE), jnp.int32),
        head_flag=_sds(one_chip, (1, NE), jnp.bool_),
        edge_mask=_sds(one_chip, (1, NE), jnp.bool_),
        vtx_mask=_sds(one_chip, (1, NV), jnp.bool_),
        degree=_sds(one_chip, (1, NV), jnp.int32),
        global_vid=_sds(one_chip, (1, NV), jnp.int32),
        weights=_sds(one_chip, (1, NE), jnp.float32),
        mirror_pos=_sds(one_chip, (1, 0), jnp.int32),
        mirror_rel=_sds(one_chip, (1, NE), jnp.int32))


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Resolve methods and pick Pallas's compiled (not interpreted) path
    as the engines do on a TPU host."""
    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _no_scatter(text: str) -> bool:
    return re.search(r"\bscatter\(", text) is None


def test_pagerank_cell_loop_reduces_with_the_kernel(one_chip, as_on_tpu):
    """The PageRank cell's 10-iteration loop with the TPU's auto method:
    the segment reduce is the one scan kernel, and no scatter is left."""
    from lux_tpu.engine import methods, pull
    from lux_tpu.graph.shards import ShardSpec
    from lux_tpu.models.pagerank import PageRankProgram

    method = methods.resolve_sum("auto", "sum", "tpu")
    assert method == "mxscan"
    c = pull._pull_fixed_jit.lower(
        PageRankProgram(nv=NV), ShardSpec(1, NV, NE, NV, NE, False), 10,
        method, _cell_arrays(one_chip),
        _sds(one_chip, (1, NV), jnp.float32), None, route_static=None,
        route_arrays=None, interpret=False, ostatic=None,
        oarrays=None).compile()
    text = c.as_text()
    assert _kernels(c) == 1
    assert _no_scatter(text)


def test_bfs_dense_step_reduces_with_the_kernel(one_chip, as_on_tpu):
    """A push dense round (int32 min, the BFS cell's shapes) with the
    TPU's auto method: one scan kernel, no scatter."""
    from lux_tpu.engine import methods, push
    from lux_tpu.models.sssp import SSSPProgram

    prog = SSSPProgram(nv=NV, start=0)
    method = methods.resolve_sum("auto", prog.reduce, "tpu")
    assert method == "mxscan"
    c = jax.jit(lambda arrays, full, local: jax.vmap(
        lambda arr, loc: push.dense_part_step(prog, arr, full, loc, method)
    )(arrays, local)).lower(
        _cell_arrays(one_chip), _sds(one_chip, (NV,), jnp.int32),
        _sds(one_chip, (1, NV), jnp.int32)).compile()
    assert _kernels(c) == 1
    assert _no_scatter(c.as_text())


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """A parts mesh over the described v5e 2x2 (compile cache off, as
    ``one_chip`` leaves it)."""
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.asarray(topo.devices[:4]), ("parts",))


def test_distributed_bfs_loop_compiles_for_four_chips(four_chips,
                                                      as_on_tpu):
    """The four-chip BFS cell's loop (-ng 4 --distributed) with the TPU's
    auto method: the dense rounds' scan kernel runs under the shard_map
    (one kernel), and the exchange's collectives are in the program."""
    from jax.sharding import NamedSharding, PartitionSpec

    from lux_tpu.engine import methods, push
    from lux_tpu.graph.push_shards import PushArrays, PushSpec
    from lux_tpu.graph.shards import ShardArrays, ShardSpec
    from lux_tpu.models.sssp import SSSPProgram

    nv, v, e, u, parts = 1 << 16, 1 << 14, 1 << 19, 1 << 14, 4
    sh = NamedSharding(four_chips, PartitionSpec("parts"))
    rep = NamedSharding(four_chips, PartitionSpec())

    def sds(shape, dtype, s=sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    arrays = ShardArrays(
        row_ptr=sds((parts, v + 1), jnp.int32),
        src_pos=sds((parts, e), jnp.int32),
        dst_local=sds((parts, e), jnp.int32),
        head_flag=sds((parts, e), jnp.bool_),
        edge_mask=sds((parts, e), jnp.bool_),
        vtx_mask=sds((parts, v), jnp.bool_),
        degree=sds((parts, v), jnp.int32),
        global_vid=sds((parts, v), jnp.int32),
        weights=sds((parts, e), jnp.float32),
        mirror_pos=sds((parts, 0), jnp.int32),
        mirror_rel=sds((parts, 0), jnp.int32))
    parrays = PushArrays(sds((parts, u), jnp.int32),
                         sds((parts, u + 1), jnp.int32),
                         sds((parts, e), jnp.int32),
                         sds((parts, e), jnp.float32))
    pspec = PushSpec(u_pad=u, f_cap=1152, e_sp=e // 4 + 128,
                     e_sp_small=e // 64)
    carry = push.PushCarry(
        sds((parts, v), jnp.int32), sds((parts, 1152), jnp.int32),
        sds((parts, 1152), jnp.int32), sds((parts,), jnp.int32),
        sds((), jnp.int32, rep), sds((), jnp.int32, rep),
        sds((2,), jnp.uint32, rep), sds((parts,), jnp.uint32),
        sds((), jnp.int32, rep))
    prog = SSSPProgram(nv=nv, start=0)
    method = methods.resolve_sum("auto", prog.reduce, "tpu")
    assert method == "mxscan"
    run = push._compile_push_dist(prog, four_chips, pspec,
                                  ShardSpec(parts, nv, parts * e, v, e,
                                            False), method)
    c = run.lower(arrays, parrays, carry,
                  sds((), jnp.int32, rep)).compile()
    text = c.as_text()
    assert _kernels(c) == 1
    assert "all-gather" in text and "all-reduce" in text
