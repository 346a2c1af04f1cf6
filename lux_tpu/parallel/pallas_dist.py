"""Distributed pull engine with the Pallas one-hot MXU reduce.

The single-chip Pallas path (models.pagerank.make_pallas_runner) covers
the bench; this module makes ``method=pallas`` a first-class DISTRIBUTED
strategy: the same per-iteration contract as parallel.dist (all_gather
the state over ICI, reduce locally, write only the own slice — the
reference's whole-region read at core/pull_model.inl:454-461) but the
per-destination reduction is the block-CSR one-hot contraction
(ops.pallas_spmv) instead of an XLA segmented reduce.  On TPU the XLA
scatter serializes (measured 264 ms/iter at rmat20/ef16 — docs/PERF.md),
so the MXU kernel is the scalable dense-round reduce.

Scope: sum-reduce programs whose ``edge_value`` is elementwise in
(src_state, weight) — PageRank and weighted-sum programs.  CF needs the
destination state per edge (error term) and keeps its dedicated 2-D
kernel path.

Host layout: each part's padded vertex range (nv_pad, the stacked-shard
row) is tiled into v_blk-wide blocks; every part gets the same
num_vblocks and chunk count (padded with no-op chunks: dst_rel == v_blk
matches no one-hot row), so the per-part arrays stack into (P, C, T)
and shard over the mesh like every other engine's.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu.graph.csc import HostGraph
from lux_tpu.graph.shards import build_pull_shards, ShardSpec, stacked_to_global
from lux_tpu.ops import pallas_spmv as ps
from lux_tpu.parallel.mesh import PARTS_AXIS, flatten_gather, shard_stacked


class PallasArrays(NamedTuple):
    """Stacked (P, ...) device arrays for the distributed Pallas pull."""

    e_src_pos: Any  # (P, C, T) int32 — gathered-coordinate sources
    e_dst_rel: Any  # (P, C, T) int32 — dst - block_base; v_blk == padding
    e_weight: Any  # (P, C, T) float32 (zeros when unweighted)
    chunk_block: Any  # (P, C) int32
    chunk_first: Any  # (P, C) int32
    global_vid: Any  # (P, V) int32   — vertex view for init/apply
    degree: Any  # (P, V) int32
    vtx_mask: Any  # (P, V) bool


@dataclasses.dataclass
class PallasParts:
    spec: ShardSpec
    cuts: np.ndarray
    num_vblocks: int
    v_blk: int
    t_chunk: int
    arrays: PallasArrays

    def scatter_to_global(self, stacked: np.ndarray) -> np.ndarray:
        return stacked_to_global(self.cuts, stacked)


class _LocalView:
    """The HostGraph surface build_blockcsr reads, for ONE part's padded
    row: local row_ptr over the full nv_pad domain (empty tail rows) and
    gathered-coordinate sources."""

    def __init__(self, row_ptr, nv, weights):
        self.row_ptr = row_ptr
        self.nv = nv
        self.weights = weights

    def dst_of_edges(self) -> np.ndarray:
        return np.repeat(
            np.arange(self.nv, dtype=np.int64), np.diff(self.row_ptr)
        )


def build_pallas_parts(
    g: HostGraph,
    num_parts: int,
    v_blk: Optional[int] = None,
    t_chunk: Optional[int] = None,
    base=None,
) -> PallasParts:
    """Partition + block-CSR re-layout for the distributed Pallas pull.

    Reuses the edge-balanced shard geometry (same cuts/padding as
    build_pull_shards, so states are interchangeable across engines).
    ``base`` optionally supplies already-built pull shards (the push
    variant shares them with its CSR layout instead of re-partitioning).
    """
    if base is None:
        base = build_pull_shards(g, num_parts)
    spec, cuts, arr = base.spec, base.cuts, base.arrays
    kw = {}
    if v_blk:
        kw["v_blk"] = v_blk
    if t_chunk:
        kw["t_chunk"] = t_chunk

    parts = []
    for p in range(num_parts):
        rp = arr.row_ptr[p].astype(np.int64)
        m = int(rp[-1])
        w = arr.weights[p][:m] if spec.weighted else None
        view = _LocalView(rp, spec.nv_pad, w)
        parts.append(
            ps.build_blockcsr(view, src_pos=arr.src_pos[p][:m], **kw)
        )

    nb = parts[0].num_vblocks
    vb, tc = parts[0].v_blk, parts[0].t_chunk
    c_max = max(bc.num_chunks for bc in parts)
    P_ = num_parts
    e_src = np.zeros((P_, c_max, tc), np.int32)
    e_dst = np.full((P_, c_max, tc), vb, np.int32)
    # unweighted graphs carry a broadcastable (P,1,1) zero placeholder —
    # PageRank-style edge_values ignore it and HBM never holds an O(E)
    # zero array (preflight counts the weight term only when weighted)
    e_w = (
        np.zeros((P_, c_max, tc), np.float32)
        if spec.weighted
        else np.zeros((P_, 1, 1), np.float32)
    )
    cb = np.zeros((P_, c_max), np.int32)
    cf = np.zeros((P_, c_max), np.int32)
    for p, bc in enumerate(parts):
        c = bc.num_chunks
        e_src[p, :c] = bc.e_src_pos
        e_dst[p, :c] = bc.e_dst_rel
        if bc.e_weight is not None:
            e_w[p, :c] = bc.e_weight
        cb[p, :c] = bc.chunk_block
        cf[p, :c] = bc.chunk_first
        # padding chunks: keep routing to the last real block with no
        # first-flag — the kernel accumulates nothing (dst == v_blk)
        cb[p, c:] = bc.chunk_block[-1] if c else 0

    arrays = PallasArrays(
        e_src_pos=e_src,
        e_dst_rel=e_dst,
        e_weight=e_w,
        chunk_block=cb,
        chunk_first=cf,
        global_vid=arr.global_vid,
        degree=arr.degree,
        vtx_mask=arr.vtx_mask,
    )
    return PallasParts(
        spec=spec, cuts=cuts, num_vblocks=nb, v_blk=vb, t_chunk=tc,
        arrays=arrays,
    )


def init_state_pallas(prog, pp: PallasParts) -> jnp.ndarray:
    """Stacked (P, V) initial state (same contract as pull.init_state)."""
    return jax.vmap(prog.init_state)(
        jnp.asarray(pp.arrays.global_vid),
        jnp.asarray(pp.arrays.degree),
        jnp.asarray(pp.arrays.vtx_mask),
    )


def _guard(prog):
    if prog.reduce != "sum" or getattr(prog, "needs_dst_state", False):
        raise ValueError(
            "pallas distributed pull: sum-reduce programs without "
            "destination-state edge terms only"
        )


@lru_cache(maxsize=64)
def _compile_fixed_pallas(prog, mesh, num_iters: int, num_vblocks: int,
                          v_blk: int, nv_pad: int, interpret: bool,
                          compute_dtype: str):
    arr_specs = PallasArrays(*([P(PARTS_AXIS)] * len(PallasArrays._fields)))

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(arr_specs, P(PARTS_AXIS)),
        out_specs=P(PARTS_AXIS),
        # pallas_call's out_shape carries no varying-mesh-axes annotation;
        # shard_map's vma check has no way to infer it (jax 0.9 requires
        # an explicit vma or check_vma=False for pallas under shard_map)
        check_vma=False,
    )
    def run(arr_blk, state_blk):
        arr = jax.tree.map(lambda a: a[0], arr_blk)

        def body(_, local):
            full = jax.lax.all_gather(local, PARTS_AXIS, tiled=True)
            # (C, T) gather in XLA; the kernel does the reduce on the MXU
            vals = prog.edge_value(full[arr.e_src_pos], arr.e_weight)
            acc = ps.spmv_blockcsr(
                vals, arr.e_dst_rel, arr.chunk_block, arr.chunk_first,
                op="sum", v_blk=v_blk, num_vblocks=num_vblocks,
                interpret=interpret, compute_dtype=compute_dtype,
            )[:nv_pad]
            return prog.apply(local, acc, arr)

        out = jax.lax.fori_loop(0, num_iters, body, state_blk[0])
        return out[None]

    return run


def run_pull_fixed_pallas_dist(
    prog,
    pp: PallasParts,
    state0: jnp.ndarray,
    num_iters: int,
    mesh: Mesh,
    interpret: bool = False,
):
    """Fixed-iteration distributed pull on the Pallas reduce.  ``state0``
    stacked (P, V); returns the final stacked (sharded) state."""
    _guard(prog)
    assert pp.spec.num_parts == mesh.devices.size
    arrays = shard_stacked(mesh, pp.arrays)
    state0 = shard_stacked(mesh, state0)
    # bf16 state programs also feed the MXU at the bf16 rate (f32
    # accumulation either way) — match the single-chip runner's contract
    compute_dtype = getattr(prog, "dtype", "float32")
    return _compile_fixed_pallas(
        prog, mesh, num_iters, pp.num_vblocks, pp.v_blk, pp.spec.nv_pad,
        interpret, compute_dtype,
    )(arrays, state0)


@lru_cache(maxsize=64)
def _compile_fixed_pallas_2d(prog, mesh, num_iters: int, num_vblocks: int,
                             v_blk: int, nv_pad: int, interpret: bool):
    arr_specs = PallasArrays(*([P(PARTS_AXIS)] * len(PallasArrays._fields)))

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(arr_specs, P(PARTS_AXIS)),
        out_specs=P(PARTS_AXIS),
        check_vma=False,  # pallas out_shape carries no vma (see above)
    )
    def run(arr_blk, state_blk):
        arr = jax.tree.map(lambda a: a[0], arr_blk)
        # per-edge destination within THIS part's padded row: dsts are
        # always local in the pull layout, so the error term's dst vector
        # gathers from the resident slice, never the exchanged buffer
        dst_local = jnp.clip(
            arr.chunk_block[:, None] * v_blk + arr.e_dst_rel, 0, nv_pad - 1
        )

        def body(_, local):
            full = jax.lax.all_gather(local, PARTS_AXIS, tiled=True)
            src_vec = full[arr.e_src_pos]  # (C, T, K)
            dst_vec = local[dst_local]
            vals = prog.edge_value(src_vec, arr.e_weight, dst_vec)
            acc = ps.spmv_blockcsr_2d(
                vals, arr.e_dst_rel, arr.chunk_block, arr.chunk_first,
                v_blk=v_blk, num_vblocks=num_vblocks, interpret=interpret,
            )[:nv_pad]
            return prog.apply(local, acc, arr)

        out = jax.lax.fori_loop(0, num_iters, body, state_blk[0])
        return out[None]

    return run


@dataclasses.dataclass
class PushPallasShards:
    """Push-engine layout whose DENSE rounds reduce on the Pallas kernel:
    the sparse-round CSR/queues come from build_push_shards, the dense
    rounds use the block-CSR chunk arrays (gathered-coordinate sources)
    instead of the pull layout's O(E) stacked arrays — the per-part hot
    loop the reference tunes in components_gpu.cu:85-130, on the VPU/MXU.
    """

    push: Any  # PushShards (pspec, spec, cuts, parrays, arrays)
    pl: PallasArrays
    num_vblocks: int
    v_blk: int
    t_chunk: int

    @property
    def spec(self):
        return self.push.spec

    @property
    def pspec(self):
        return self.push.pspec

    @property
    def cuts(self):
        return self.push.cuts

    @property
    def pull(self):
        return self.push.pull

    def scatter_to_global(self, stacked: np.ndarray) -> np.ndarray:
        return self.push.scatter_to_global(stacked)


def build_push_pallas_shards(
    g: HostGraph,
    num_parts: int,
    v_blk: Optional[int] = None,
    t_chunk: Optional[int] = None,
    cuts=None,
) -> PushPallasShards:
    """Push shards + the block-CSR dense-round layout, sharing one
    edge-balanced partitioning (states interchangeable with every other
    push engine)."""
    from lux_tpu.graph.push_shards import build_push_shards

    push_sh = build_push_shards(g, num_parts, cuts=cuts)
    pp = build_pallas_parts(
        g, num_parts, v_blk=v_blk, t_chunk=t_chunk, base=push_sh.pull
    )
    return PushPallasShards(
        push=push_sh, pl=pp.arrays, num_vblocks=pp.num_vblocks,
        v_blk=pp.v_blk, t_chunk=pp.t_chunk,
    )


@lru_cache(maxsize=64)
def _compile_push_pallas(prog, mesh, pspec, spec, num_vblocks: int,
                         v_blk: int, interpret: bool):
    """Direction-optimizing push whose dense rounds run the Pallas min/max
    reduce: same sparse-round queue exchange + global mode predicate as
    push._compile_push_dist (the shared _spmd_push_iter body); the dense
    branch all_gathers the state and reduces each part's in-edges with the
    masked-VPU one-hot kernel instead of an XLA segmented reduce."""
    from lux_tpu.engine import push as pe
    from lux_tpu.graph.push_shards import PushArrays

    pl_specs = PallasArrays(*([P(PARTS_AXIS)] * len(PallasArrays._fields)))
    parr_specs = PushArrays(*([P(PARTS_AXIS)] * len(PushArrays._fields)))
    view_specs = pe.VertexView(*([P(PARTS_AXIS)] * len(pe.VertexView._fields)))
    carry_specs = pe._carry_specs()

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pl_specs, parr_specs, view_specs, carry_specs, P()),
        out_specs=carry_specs,
        check_vma=False,  # pallas out_shape carries no vma (see above)
    )
    def run(pl_blk, parr_blk, view_blk, carry_blk, it_stop):
        # the pallas push engine keeps one part per device (driver asserts
        # P == mesh size): blocks carry a unit lane axis
        pl = jax.tree.map(lambda a: a[0], pl_blk)
        op = jnp.minimum if prog.reduce == "min" else jnp.maximum

        def dense_fn(block):  # (1, V)
            local = block[0]
            full = flatten_gather(block)
            # (C, T) gather + relax in XLA; dtype-preserving kernel reduce
            vals = prog.relax(full[pl.e_src_pos], pl.e_weight)
            acc = ps.spmv_blockcsr(
                vals, pl.e_dst_rel, pl.chunk_block, pl.chunk_first,
                op=prog.reduce, v_blk=v_blk, num_vblocks=num_vblocks,
                interpret=interpret,
            )[: spec.nv_pad]
            mask = view_blk.vtx_mask[0]
            return jnp.where(mask, op(local, acc), local)[None]

        def cond(c):
            return (c.active > 0) & (c.it < it_stop)

        def body(c):
            return pe._spmd_push_iter(
                prog, pspec, spec, parr_blk, view_blk, dense_fn, c
            )

        return jax.lax.while_loop(cond, body, carry_blk)

    return run


def run_push_pallas_dist(
    prog,
    shards: PushPallasShards,
    mesh: Mesh,
    max_iters: int = 10_000,
    interpret: bool = False,
):
    """Distributed push driver with Pallas dense rounds (min/max frontier
    programs: SSSP/CC).  Only the block-CSR chunks, the sparse CSR, and
    the O(V) vertex view touch the devices — never the pull layout's O(E)
    stacked arrays.  Returns (stacked state, iters, edge counter)."""
    from lux_tpu.engine import push as pe

    if prog.reduce not in ("min", "max"):
        raise ValueError(
            "pallas push drives min/max frontier programs; sum programs "
            "use the pull engines"
        )
    spec, pspec = shards.spec, shards.pspec
    assert spec.num_parts == mesh.devices.size
    pl, parrays, view = shard_stacked(
        mesh, (shards.pl, shards.push.parrays,
               pe.vertex_view(shards.push.arrays))
    )
    carry0 = pe.init_carry_dist(prog, pspec, view, mesh)
    run = _compile_push_pallas(
        prog, mesh, pspec, spec, shards.num_vblocks, shards.v_blk, interpret
    )
    out = run(pl, parrays, view, carry0, jnp.int32(max_iters))
    return out.state, out.it, out.edges


def run_cf_pallas_dist(
    prog,
    pp: PallasParts,
    state0: jnp.ndarray,
    num_iters: int,
    mesh: Mesh,
    interpret: bool = False,
):
    """Distributed CF on the 2-D Pallas kernel: the err·srcVec
    accumulation is a (V_BLK, T) x (T, K) MXU matmul per chunk
    (colfilter_gpu.cu:85-101's role), with the (V, K) latent state
    sharded over the mesh and all-gathered per iteration."""
    if prog.reduce != "sum" or not getattr(prog, "needs_dst_state", False):
        raise ValueError(
            "pallas 2-D distributed pull is the CF shape: sum-reduce with "
            "a destination-state edge term"
        )
    if not pp.spec.weighted:
        raise ValueError("CF requires a weighted graph")
    assert pp.spec.num_parts == mesh.devices.size
    arrays = shard_stacked(mesh, pp.arrays)
    state0 = shard_stacked(mesh, state0)
    return _compile_fixed_pallas_2d(
        prog, mesh, num_iters, pp.num_vblocks, pp.v_blk, pp.spec.nv_pad,
        interpret,
    )(arrays, state0)
