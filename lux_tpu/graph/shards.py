"""Device-ready graph shards: static-shape padded CSC partitions.

This is the TPU-native replacement for the reference's Legion logical
regions/partitions + per-GPU `GraphPiece` (core/graph.h:53-98).  Where the
reference carves one region into disjoint 1-D subregions and lets Legion
materialize per-GPU instances, we build *stacked* `(P, ...)` NumPy arrays with
every part padded to identical static shapes, ready to be:

  * consumed whole on one chip (vmap over the leading axis), or
  * dropped onto a 1-D `jax.sharding.Mesh` with the leading axis sharded and
    used inside `shard_map` (lux_tpu.parallel).

Key encodings:
  * Vertices are split into contiguous edge-balanced ranges (partition.py).
  * Per part, vertex count is padded to ``nv_pad`` and edge count to
    ``e_pad`` (multiples of 128 — TPU lane width).
  * ``src_pos`` pre-encodes each edge's source position in the *padded
    all-gathered* state vector of shape (P * nv_pad,): for source s owned by
    part q, ``src_pos = q * nv_pad + (s - cuts[q])``.  This makes the
    per-iteration whole-state exchange (the analog of the reference's
    whole-region zero-copy read, core/pull_model.inl:454-461) a plain
    `all_gather` + vectorized gather with no runtime id remapping.
  * CSC edges arrive sorted by destination, so per-part destination segment
    boundaries are encoded once as ``row_ptr``/``head_flag`` and all
    per-destination reductions run as segmented scans (lux_tpu.ops.segment)
    instead of the reference's atomicAdd/Min/Max
    (pagerank_gpu.cu:90, sssp_gpu.cu:59-77).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import numpy as np

from lux_tpu import obs
from lux_tpu.graph.csc import HostGraph
from lux_tpu.graph.partition import edge_balanced_cuts

LANE = 128  # TPU vector lane width; pad 1-D extents to multiples of this.


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Static (hashable, jit-safe) shard geometry."""

    num_parts: int
    nv: int
    ne: int
    nv_pad: int  # per-part padded vertex count
    e_pad: int  # per-part padded edge count
    weighted: bool

    @property
    def gathered_size(self) -> int:
        """Length of the padded all-gathered state vector."""
        return self.num_parts * self.nv_pad


class ShardArrays(NamedTuple):
    """Stacked per-part arrays (leading axis = part).  A jax pytree.

    Shapes (P = num_parts, V = nv_pad, E = e_pad):
      row_ptr:   (P, V+1) int32  local CSC offsets into the part's edge slice;
                 padded vertices get empty ranges.
      src_pos:   (P, E)   int32  source position in the (P*V,) gathered state.
      dst_local: (P, E)   int32  local destination index in [0, V); padding
                 slots hold the out-of-range sentinel V (keeps the array
                 sorted and makes XLA segment_* drop padding contributions).
      head_flag: (P, E)   bool   True at the first edge of each destination's
                 block (segment starts for segmented scans).
      edge_mask: (P, E)   bool   True for real (non-padding) edges.
      vtx_mask:  (P, V)   bool   True for real (non-padding) vertices.
      degree:    (P, V)   int32  out-degree of each local vertex (equivalent
                 of pull_scan_task_impl, core/pull_model.inl:322-345).
      global_vid:(P, V)   int32  global vertex id of each local slot (clamped
                 to nv-1 on padding slots; check vtx_mask).
      weights:   (P, E)   float32 edge weights (zeros when unweighted).
      mirror_pos:(P, U)   int32  compact-gather mirror: the part's UNIQUE
                 in-source positions in the gathered state, sorted
                 ascending (U = 0 when the layout is disabled — the
                 zero width is static, so jitted engines pick the gather
                 path at trace time with no extra plumbing).
      mirror_rel:(P, E)   int32  per-edge index into the part's mirror
                 (mirror_pos[mirror_rel] == src_pos on real edges).

    The mirror pair is the TPU answer to the reference's per-GPU unique
    in-vertex list + load_kernel FB staging (pagerank_gpu.cu:229-240,
    34-47): the per-edge gather's working set drops from O(P*V) to
    O(unique in-sources), and the O(U) mirror fill reads ASCENDING
    positions — sequential-friendly HBM traffic where src_pos gathers
    are random.
    """

    row_ptr: np.ndarray
    src_pos: np.ndarray
    dst_local: np.ndarray
    head_flag: np.ndarray
    edge_mask: np.ndarray
    vtx_mask: np.ndarray
    degree: np.ndarray
    global_vid: np.ndarray
    weights: np.ndarray
    mirror_pos: np.ndarray
    mirror_rel: np.ndarray


@dataclasses.dataclass
class PullShards:
    """Host bundle: spec + arrays + partition bookkeeping."""

    spec: ShardSpec
    arrays: ShardArrays
    cuts: np.ndarray  # (P+1,) vertex cut points

    def scatter_to_global(self, stacked: np.ndarray) -> np.ndarray:
        """Collapse a (P, nv_pad, ...) stacked state back to (nv, ...) global
        order, dropping padding."""
        return stacked_to_global(self.cuts, stacked)

    def global_to_stacked(self, full: np.ndarray) -> np.ndarray:
        """Split a (nv, ...) global state into (P, nv_pad, ...) padded stacks.
        Padding slots are filled with zeros."""
        return global_to_stacked(self.cuts, self.spec.nv_pad, full)


def global_to_stacked(cuts: np.ndarray, nv_pad: int,
                      full: np.ndarray) -> np.ndarray:
    """Split a (nv, ...) global state into (P, nv_pad, ...) zero-padded
    stacks under ``cuts`` — the inverse of ``stacked_to_global``; any
    shard bundle (pull/push/ring/scatter/edge2d) restacks an elastic
    checkpoint with its own cuts through this."""
    P = cuts.shape[0] - 1
    out = np.zeros((P, nv_pad) + full.shape[1:], dtype=full.dtype)
    for p in range(P):
        lo, hi = int(cuts[p]), int(cuts[p + 1])
        out[p, : hi - lo] = full[lo:hi]
    return out


def stacked_to_global(cuts: np.ndarray, stacked: np.ndarray) -> np.ndarray:
    """De-pad a (P, nv_pad, ...) stacked state into (nv, ...) global order
    under ``cuts`` (shared by every engine's shard bundle)."""
    out = []
    for p in range(cuts.shape[0] - 1):
        n = int(cuts[p + 1] - cuts[p])
        out.append(np.asarray(stacked[p])[:n])
    return np.concatenate(out, axis=0)


def shard_geometry(row_ptr_global: np.ndarray, num_parts: int, nv: int,
                   cuts: Optional[np.ndarray] = None):
    """(cuts, nv_pad, e_pad) for edge-balanced padded shards, with the
    int32-range guards (global E_ID stays int64 on host, like the
    reference's uint64 E_ID / uint32 V_ID split, pagerank/app.h:21-22).

    ``cuts`` overrides the static edge-balanced sweep with caller-chosen
    contiguous bounds (the dynamic-repartitioning path feeds
    partition.weighted_cuts here)."""
    if cuts is None:
        cuts = edge_balanced_cuts(row_ptr_global, num_parts)
    nv_counts = np.diff(cuts)
    e_counts = row_ptr_global[cuts[1:]] - row_ptr_global[cuts[:-1]]
    nv_pad = max(LANE, _round_up(int(nv_counts.max()), LANE))
    e_pad = max(LANE, _round_up(int(e_counts.max()) or 1, LANE))
    if int(e_counts.max()) >= 2**31:
        raise ValueError(
            f"a part holds {int(e_counts.max())} edges >= 2^31; "
            f"increase num_parts (currently {num_parts})"
        )
    if num_parts * nv_pad >= 2**31:
        raise ValueError("num_parts * nv_pad exceeds int32 gather range")
    del nv
    return cuts, nv_pad, e_pad


def edge2d_chunk_pad(max_part_edges: int, num_edge_shards: int) -> int:
    """Padded per-chunk edge capacity E2 of the 2-D (parts x edge)
    layout — ONE formula shared by the builder
    (parallel/edge2d.build_edge2d_shards) and the preflight hint
    (utils/preflight.suggest_edge_shards) so they can never diverge."""
    chunk_max = -(-max(1, int(max_part_edges)) // max(1, num_edge_shards))
    return _round_up(max(1, chunk_max), LANE)


def alloc_arrays(num_rows: int, nv_pad: int, e_pad: int) -> ShardArrays:
    """Zeroed stacked arrays for ``num_rows`` parts."""
    return ShardArrays(
        row_ptr=np.zeros((num_rows, nv_pad + 1), np.int32),
        src_pos=np.zeros((num_rows, e_pad), np.int32),
        dst_local=np.full((num_rows, e_pad), nv_pad, np.int32),
        head_flag=np.zeros((num_rows, e_pad), bool),
        edge_mask=np.zeros((num_rows, e_pad), bool),
        vtx_mask=np.zeros((num_rows, nv_pad), bool),
        degree=np.zeros((num_rows, nv_pad), np.int32),
        global_vid=np.zeros((num_rows, nv_pad), np.int32),
        weights=np.zeros((num_rows, e_pad), np.float32),
        mirror_pos=np.zeros((num_rows, 0), np.int32),
        mirror_rel=np.zeros((num_rows, 0), np.int32),
    )


def fill_part(
    arrays: ShardArrays,
    i: int,
    vlo: int,
    vhi: int,
    rp_local: np.ndarray,
    srcs: np.ndarray,
    w: Optional[np.ndarray],
    cuts: np.ndarray,
    nv_pad: int,
    nv: int,
    degrees_slice: np.ndarray,
) -> None:
    """Fill stacked-row ``i`` with one part's data.

    rp_local: (n+1,) local offsets with leading 0; srcs: (m,) global source
    ids; degrees_slice: (n,) out-degrees of [vlo, vhi).  Shared by the
    in-memory builder and the streaming file loader so the encodings can
    never diverge.
    """
    n, m = vhi - vlo, len(srcs)
    rp = np.asarray(rp_local, np.int32)
    arrays.row_ptr[i, : n + 1] = rp
    arrays.row_ptr[i, n + 1 :] = m  # padded vertices: empty tail ranges
    from lux_tpu import native

    if native.fill_src_pos(srcs, cuts, nv_pad, arrays.src_pos[i, :m]) is None:
        srcs64 = np.asarray(srcs, np.int64)
        own = (np.searchsorted(cuts, srcs64, side="right") - 1).astype(np.int64)
        arrays.src_pos[i, :m] = (
            own * nv_pad + (srcs64 - cuts[own])
        ).astype(np.int32)
    arrays.dst_local[i, :m] = np.repeat(
        np.arange(n, dtype=np.int32), np.diff(rp[: n + 1])
    )
    starts = rp[:n][rp[:n] < rp[1 : n + 1]]
    arrays.head_flag[i, starts] = True
    arrays.edge_mask[i, :m] = True
    arrays.vtx_mask[i, :n] = True
    arrays.degree[i, :n] = degrees_slice
    arrays.global_vid[i, :n] = np.arange(vlo, vhi, dtype=np.int32)
    arrays.global_vid[i, n:] = nv - 1
    if w is not None:
        arrays.weights[i, :m] = np.asarray(w, np.float32)


def sort_segments_inplace(arrays: ShardArrays) -> None:
    """Reorder edges WITHIN each destination segment by ascending gather
    index (src_pos) — the gather-locality relayout.

    Every shipped combiner is commutative, so per-segment order is
    semantically free (float sums round differently than the unsorted
    layout, but the relayout is a fixed deterministic choice — reruns
    stay bitwise identical; tests/test_determinism.py).  The payoff is
    on TPU: `state[src_pos]` is the roofline's dominant unknown
    (docs/PERF.md gather-amplification band), and ascending in-segment
    gather indices cluster hub sources so consecutive reads hit the
    same HBM tiles.  The reference cannot reorder this way — its
    atomicAdd scatter order is already arbitrary (pr_kernel,
    pagerank_gpu.cu:86-95); here the relayout is explicit and testable.

    Only src_pos and weights move: the lexsort's primary key is
    dst_local, so the dst sequence (and with it row_ptr, head_flag,
    edge_mask, and the padding tail at dst_local == V) is unchanged.
    """
    for r in range(arrays.src_pos.shape[0]):
        order = np.lexsort((arrays.src_pos[r], arrays.dst_local[r]))
        arrays.src_pos[r] = arrays.src_pos[r][order]
        arrays.weights[r] = arrays.weights[r][order]


def build_compact_mirror(arrays: ShardArrays,
                         u_pad: Optional[int] = None) -> ShardArrays:
    """Attach the compact-gather mirror to filled pull-layout arrays.

    Per part: ``mirror_pos`` = sorted unique src_pos of the real edges
    (padded to a shared lane-aligned width U by repeating position 0 —
    harmless extra gathers of a valid slot), and ``mirror_rel`` remaps
    every edge's src_pos to its mirror index via binary search (padding
    edges map to 0; their contributions are already dropped by the
    dst_local sentinel).  The remap is exact, so engine results are
    bitwise identical to the direct layout — only the gather traffic
    shape changes.  Host-side one-time cost, like the reference's
    init-time in-vertex sort (pagerank_gpu.cu:229-240).

    Composes with sort_segments_inplace (call it first: the mirror is
    order-insensitive per segment, and src_pos->mirror_rel is a monotone
    remap, so the relayout's in-segment ascending order survives).

    ``u_pad`` overrides the width (multi-host subset loads pass the
    GLOBAL width from sharded_load.compact_width_from_file so every
    host's blocks keep identical shapes)."""
    P = arrays.src_pos.shape[0]
    uniqs = []
    for p in range(P):
        uniqs.append(np.unique(arrays.src_pos[p][arrays.edge_mask[p]]))
    need = max((len(u) for u in uniqs), default=1) or 1
    if u_pad is None:
        u_pad = max(LANE, _round_up(need, LANE))
    elif u_pad < need:
        raise ValueError(f"compact u_pad {u_pad} < required width {need}")
    mirror_pos = np.zeros((P, u_pad), np.int32)
    mirror_rel = np.zeros_like(arrays.src_pos)
    for p in range(P):
        u = uniqs[p]
        mirror_pos[p, : len(u)] = u
        rel = np.searchsorted(u, arrays.src_pos[p])
        # padding edges hold src_pos 0; searchsorted keeps them in range
        # unless the part is empty, where clip pins them to slot 0
        mirror_rel[p] = np.clip(rel, 0, u_pad - 1).astype(np.int32)
    return arrays._replace(mirror_pos=mirror_pos, mirror_rel=mirror_rel)


def build_pull_shards(
    g: HostGraph,
    num_parts: int,
    degrees: Optional[np.ndarray] = None,
    cuts: Optional[np.ndarray] = None,
    sort_segments: bool = False,
    compact_gather: bool = False,
) -> PullShards:
    """Partition + pad a HostGraph into device-ready pull-model shards.

    ``cuts`` (optional (P+1,) bounds) selects a custom contiguous
    partition — used by dynamic repartitioning to rebalance on measured
    work instead of static in-degree.  ``sort_segments`` applies the
    gather-locality relayout (sort_segments_inplace); ``compact_gather``
    attaches the unique-in-source mirror (build_compact_mirror)."""
    with obs.span("graph.shards", kind="pull", parts=num_parts):
        cuts, nv_pad, e_pad = shard_geometry(g.row_ptr, num_parts, g.nv, cuts)
        if degrees is None:
            degrees = g.out_degrees()
        arrays = alloc_arrays(num_parts, nv_pad, e_pad)
        for p in range(num_parts):
            vlo, vhi = int(cuts[p]), int(cuts[p + 1])
            elo, ehi = int(g.row_ptr[vlo]), int(g.row_ptr[vhi])
            fill_part(
                arrays, p, vlo, vhi,
                g.row_ptr[vlo : vhi + 1] - elo,
                g.col_idx[elo:ehi],
                None if g.weights is None else g.weights[elo:ehi],
                cuts, nv_pad, g.nv, degrees[vlo:vhi],
            )
        if sort_segments:
            sort_segments_inplace(arrays)
        if compact_gather:
            arrays = build_compact_mirror(arrays)
        spec = ShardSpec(
            num_parts=num_parts,
            nv=g.nv,
            ne=g.ne,
            nv_pad=nv_pad,
            e_pad=e_pad,
            weighted=g.weights is not None,
        )
        return PullShards(spec=spec, arrays=arrays, cuts=cuts)
