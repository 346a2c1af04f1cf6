"""The push (frontier/scatter) execution engine with direction optimization.

TPU-native re-design of the reference push model (core/push_model.inl +
sssp_gpu.cu/components_gpu.cu):

  * State per part: the vertex values (dist/labels) + a sparse frontier
    QUEUE of (vertex id, value) pairs with static capacity ``f_cap``.
    Carrying the value in the queue means sparse iterations exchange only
    queues — NOT the whole state — so ICI traffic per sparse round is
    O(P * f_cap), the analog of the reference's sparse-queue frontier
    (FrontierHeader::SPARSE_QUEUE, core/graph.h:100-106).
  * Direction switch per iteration (sssp_gpu.cu:414): global frontier
    count > nv/16  =>  DENSE/pull mode (segmented reduce over all in-edges
    of the all-gathered state); otherwise SPARSE/push mode (compact the
    frontier's out-edges into a fixed ``e_sp`` buffer, scatter-min/max into
    the local slice).  Overflow of any queue or edge buffer forces dense —
    the graceful sparse->dense degradation of sssp_gpu.cu:485-490.
  * The mode predicate is made GLOBAL (psum'd) so collectives (the dense
    branch's all_gather) sit inside `lax.cond` without divergence.
  * Cross-part merge (sparse rounds): bulk concatenate-and-scatter, or
    the static asynchronous reduction TREE of ops/merge_tree.py
    (``merge="tree"`` / the banked ``tpu:merge_mode`` winner) — per-part
    partial frontiers combine pairwise, bitwise-identical for the
    min/max programs at any arity.
  * Convergence: psum'd changed-vertex count reaches zero — on-device,
    zero-lag (vs the 4-iteration SLIDING_WINDOW host pipeline,
    sssp/sssp.cc:115-129).

Determinism note: the reference's sparse queues tolerate duplicate entries
via atomicMin races (sssp_gpu.cu:74-81); here queue construction is an
exact compaction (`nonzero`) and scatters are XLA scatter-min/max —
deterministic, duplicates impossible.
"""
from __future__ import annotations

import math
from dataclasses import replace
from functools import lru_cache, partial
from typing import Any, NamedTuple, Protocol

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu import obs
from lux_tpu.engine import methods
from lux_tpu.graph.push_shards import PushArrays, PushShards, PushSpec, SRC_SENTINEL
from lux_tpu.graph.shards import ShardArrays, ShardSpec
from lux_tpu.ops import merge_tree, segment
from lux_tpu.parallel.mesh import (
    PARTS_AXIS,
    flatten_gather,
    parts_mesh,
    shard_stacked,
)


class PushProgram(Protocol):
    """Frontier vertex program (SSSP/CC app contract)."""

    #: "min" | "max" — combiner AND monotone direction of the state.
    reduce: str

    def init_state(self, global_vid, degree, vtx_mask) -> jnp.ndarray: ...

    def init_frontier(self, global_vid, state, vtx_mask) -> jnp.ndarray:
        """Initial active mask (e.g. the single source, or everyone)."""
        ...

    def relax(self, src_val, weight) -> jnp.ndarray:
        """Candidate value pushed along an edge from a source with value
        ``src_val`` (e.g. src_val + 1 for BFS-SSSP, sssp_gpu.cu:122)."""
        ...


def _op(prog):
    return jnp.minimum if prog.reduce == "min" else jnp.maximum


def _seg_reduce(prog):
    return segment.segment_min_csc if prog.reduce == "min" else segment.segment_max_csc


def dense_part_step(prog, arr: ShardArrays, full_state, local, method="scan",
                    route=None, interpret=False, del_val=None):
    """Pull-mode relaxation over ALL in-edges (sssp_pull_kernel semantics:
    new[v] = op(old[v], op over in-edges relax(state[src])).

    ``route`` = (ExpandStatic, this part's arrays): the routed-shuffle
    expand replaces the flat gather (ops/expand.py) — relax is
    elementwise on (src, weight), so results stay bitwise identical.
    A pass-fused plan (expand.to_pf / pf=True planners) replays through
    the fused kernel family transparently — apply_expand dispatches on
    the static's type, same bits, ~half the HBM sweeps per dense
    round.  ``del_val`` (lux_tpu.mutate.overlay tombstone mask)
    neutralizes deleted base edges' relax values — exactly absorbed by
    the min/max combiner, so a dense round equals the merged graph's
    bitwise; insert folding happens once per iteration in _push_relax."""
    if route is not None:
        from lux_tpu.ops import expand

        src = expand.apply_expand(full_state, route[0], route[1],
                                  interpret=interpret)
    elif arr.mirror_pos.shape[-1] > 0:
        # compact-gather mirror (engine/pull.pull_gather_part semantics)
        src = full_state[arr.mirror_pos][arr.mirror_rel]
    else:
        src = full_state[arr.src_pos]
    vals = prog.relax(src, arr.weights)
    if del_val is not None:
        from lux_tpu.mutate import overlay as _ovl

        vals = _ovl.mask_deleted(vals, del_val, prog.reduce)
    acc = _seg_reduce(prog)(
        vals, arr.row_ptr, arr.head_flag, arr.dst_local, method=method
    )
    new = _op(prog)(local, acc)
    return jnp.where(arr.vtx_mask, new, local)


def sparse_prep(parr: PushArrays, q_vids):
    """Per-part frontier -> (row index, out-edge count) via binary search
    over the part's unique sources.  Returns (rows, counts, total)."""
    u = parr.uniq_src.shape[0]
    idx = jnp.searchsorted(parr.uniq_src, q_vids)
    idx_c = jnp.clip(idx, 0, u - 1)
    found = parr.uniq_src[idx_c] == q_vids
    starts = parr.csr_row_ptr[idx_c]
    ends = parr.csr_row_ptr[jnp.clip(idx + 1, 0, u)]
    counts = jnp.where(found, ends - starts, 0)
    incl = jnp.cumsum(counts)
    total = incl[-1] if counts.shape[0] else jnp.int32(0)
    return idx_c, counts, incl, total


def _sparse_walk(prog, pspec: PushSpec, parr: PushArrays, nv_pad,
                 q_vids, q_vals, rows, incl, cap: int | None):
    """The compacted out-edge walk both merge modes share: map each slot
    of a ``cap``-sized buffer to a (queue entry, within-entry edge) pair
    and gather (dst, candidate).  Returns (dst, cand, entry_c); invalid
    slots carry ``dst == nv_pad`` (the drop sentinel)."""
    j = jnp.arange(cap or pspec.e_sp, dtype=jnp.int32)
    entry = jnp.searchsorted(incl, j, side="right")
    entry_c = jnp.clip(entry, 0, q_vids.shape[0] - 1)
    prev = jnp.where(entry_c > 0, incl[entry_c - 1], 0)
    within = j - prev
    e_max = parr.csr_dst_local.shape[0] - 1
    edge = jnp.clip(parr.csr_row_ptr[rows[entry_c]] + within, 0, e_max)
    total = incl[-1]
    valid = j < total
    dst = jnp.where(valid, parr.csr_dst_local[edge], nv_pad)
    cand = prog.relax(q_vals[entry_c], parr.csr_weight[edge])
    return dst, cand, entry_c


def sparse_part_step(prog, pspec: PushSpec, parr: PushArrays, nv_pad,
                     q_vids, q_vals, rows, counts, incl, local,
                     cap: int | None = None):
    """Push-mode BULK merge: compact the frontier's out-edges (restricted
    to this part's dsts) into a ``cap``-sized buffer (default the full
    e_sp tier), then scatter-combine the whole concatenated frontier
    into the local slice in one pass."""
    del counts
    dst, cand, _ = _sparse_walk(
        prog, pspec, parr, nv_pad, q_vids, q_vals, rows, incl, cap
    )
    if prog.reduce == "min":
        return local.at[dst].min(cand, mode="drop")
    return local.at[dst].max(cand, mode="drop")


def sparse_part_step_tree(prog, pspec: PushSpec, parr: PushArrays, nv_pad,
                          q_vids, q_vals, rows, counts, incl, local,
                          cap: int | None = None):
    """Push-mode TREE merge (Tascade-style, ops/merge_tree.py): the same
    compacted walk, but each SOURCE part's candidates scatter into their
    own neutral-initialized partial accumulator; the per-part partials
    then combine pairwise up the static reduction tree and the root
    combines with the local slice.  min/max scatters are
    order-independent and the tree reassociates only min/max, so the
    result is bitwise-identical to the bulk scatter at any arity —
    while giving the compiler P independent partial frontiers with no
    serializing all-to-one scatter dependence (the asynchronous-merge
    shape; ISSUE 17 / PERF.md "Asynchronous merge")."""
    del counts
    dst, cand, entry_c = _sparse_walk(
        prog, pspec, parr, nv_pad, q_vids, q_vals, rows, incl, cap
    )
    # queue layout is P consecutive f_cap runs (one per source part; the
    # dist exchange may rotate WHICH part owns a run, never run layout)
    blk = entry_c // pspec.f_cap
    num_blocks = q_vids.shape[0] // pspec.f_cap
    neu = merge_tree.neutral(prog.reduce, local.dtype)
    partials = jnp.full((num_blocks,) + local.shape, neu, local.dtype)
    if prog.reduce == "min":
        partials = partials.at[blk, dst].min(cand, mode="drop")
    else:
        partials = partials.at[blk, dst].max(cand, mode="drop")
    op = _op(prog)
    return op(local, merge_tree.tree_combine(partials, op))


def _resolve_merge(merge: str | None) -> str:
    """auto-resolution shim for the cross-part merge mode (OUTSIDE the
    compile caches, like methods.resolve_sum): None reads the banked
    ``tpu:merge_mode`` winner / LUX_MERGE_MODE override."""
    m = methods.merge_mode() if merge is None else merge
    if m not in methods.MERGE_MODES:
        raise ValueError(
            f"merge must be one of {methods.MERGE_MODES}, got {m!r}")
    return m


def build_queue(pspec: PushSpec, arr: ShardArrays, changed, values):
    """Exact compaction of changed vertices into a (vid, value) queue.
    Returns (q_vid, q_val, count); count may exceed f_cap (overflow — the
    queue is then truncated and the next iteration must go dense)."""
    count = jnp.sum(changed.astype(jnp.int32))
    loc = jnp.nonzero(changed, size=pspec.f_cap, fill_value=0)[0]
    slot = jnp.arange(pspec.f_cap, dtype=jnp.int32)
    in_q = slot < count
    q_vid = jnp.where(in_q, arr.global_vid[loc], SRC_SENTINEL)
    q_val = jnp.where(in_q, values[loc], jnp.zeros((), values.dtype))
    return q_vid, q_val, count


class VertexView(NamedTuple):
    """Slim (P, V) per-vertex arrays — everything the queue/carry logic
    reads from ShardArrays, without the O(E) edge arrays (the push-ring
    driver must never device-place those)."""

    global_vid: Any
    degree: Any
    vtx_mask: Any


def vertex_view(arrays) -> VertexView:
    return VertexView(arrays.global_vid, arrays.degree, arrays.vtx_mask)


class PushCarry(NamedTuple):
    state: Any
    q_vid: Any
    q_val: Any
    count: Any
    it: Any
    active: Any
    #: edges actually traversed so far, EXACT: a (2,) uint32 [hi, lo] pair
    #: (x64 is disabled under jit and float32 absorbs increments past 2^24;
    #: the reference's per-iteration traversal accounting, SURVEY.md §6)
    edges: Any
    #: per-part sparse-round walked out-edge totals since the last driver
    #: checkpoint, SATURATING uint32 (P,) — a load signal for the
    #: repartition policy (engine/repartition.py): exact to 2^32 edges
    #: per part per window, pinned at UINT32_MAX beyond (a saturated hot
    #: part still reads hot; the policy needs the imbalance ratio, not
    #: absolute totals — tests/test_repartition.py pins the saturation
    #: behavior).
    #: Dense-round work is `dense_rounds * static part edge count`, kept
    #: out of the carry (the host derives it from the cuts).
    sp_work: Any
    #: dense rounds since the last driver checkpoint, int32 scalar.
    dense_rounds: Any


def _acc_edges(edges, dense_ne: int, sparse_total, use_dense):
    """64-bit add into the [hi, lo] uint32 pair.  ``dense_ne`` is the static
    whole-graph edge count (may exceed 2^32: split at trace time);
    ``sparse_total`` is this round's traversed count, < 2^32 by construction
    (any part whose frontier out-edges exceed e_sp forces the dense mode)."""
    d_hi = jnp.where(use_dense, jnp.uint32(dense_ne >> 32), jnp.uint32(0))
    d_lo = jnp.where(
        use_dense,
        jnp.uint32(dense_ne & 0xFFFFFFFF),
        sparse_total.astype(jnp.uint32),
    )
    lo = edges[1] + d_lo  # wraps mod 2^32
    hi = edges[0] + d_hi + (lo < edges[1]).astype(jnp.uint32)
    return jnp.stack([hi, lo])


def _zero_edges():
    return jnp.zeros((2,), jnp.uint32)


def _acc_load(c: "PushCarry", total, use_dense):
    """Window load stats for the repartition policy: sparse rounds add the
    walked out-edge totals (per part, or this part's scalar in the SPMD
    bodies) into a SATURATING uint32; dense rounds bump the shared round
    counter.  Saturation (not wrap) on overflow: a wrapped counter would
    make the window's hottest part read cold and invert the recut."""
    inc = jnp.where(use_dense, 0, jnp.asarray(total)).astype(jnp.uint32)
    added = c.sp_work + inc  # wraps mod 2^32 ...
    sp_work = jnp.where(added < c.sp_work, jnp.uint32(0xFFFFFFFF), added)
    return sp_work, c.dense_rounds + use_dense.astype(jnp.int32)




def edges_total(edges) -> int:
    """Exact Python int from the device-side [hi, lo] accumulator."""
    import numpy as np

    hi, lo = np.asarray(edges).astype(np.uint64)
    return int((hi << np.uint64(32)) | lo)


def exchange_bytes(pspec: PushSpec, spec: ShardSpec, num_dev: int,
                   state) -> tuple[int, int]:
    """(bytes a round, further bytes a dense round) that one chip of a
    ``num_dev``-device mesh receives over ICI: every round gathers the
    other chips' (P - k) frontier queues of ``f_cap`` (int32 vid, value)
    pairs; a dense round gathers their (P - k) ``nv_pad`` states too
    (all-gathered or streamed round the ring).  A value is one vertex's
    row of the stacked (P, nv_pad, ...) ``state``.  The scalar psums are
    left out.  (0, 0) on one device."""
    value = state.dtype.itemsize * math.prod(state.shape[2:])
    other = spec.num_parts - spec.num_parts // num_dev
    return (other * pspec.f_cap * (4 + value),
            other * spec.nv_pad * value)


def _count_rounds(out: PushCarry, method: str,
                  exchange: tuple[int, int] = (0, 0)) -> None:
    """One call's rounds, dense rounds, dense rounds reduced by the
    blocked scan kernel, walked arcs (``ne`` per dense round plus the
    sparse rounds' frontier out-edges) and the bytes one chip received
    (``exchange``: ``exchange_bytes`` of the mesh), from the final
    carry, as recorder counters that do not sync."""
    obs.count("push.rounds", out.it)
    obs.count("push.dense_rounds", out.dense_rounds)
    kernel = segment.csc_method(method, out.state.ndim - 1) == "mxscan"
    obs.count("push.mxscan_rounds", out.dense_rounds if kernel else 0)
    obs.count("push.arcs_walked", out.edges, edges_total)
    per_round, per_dense = exchange
    obs.count("push.exchange_bytes", (out.it, out.dense_rounds),
              lambda v: per_round * int(v[0]) + per_dense * int(v[1]))


def _init_carry(prog, pspec, arrays):
    """Initial state + frontier queues (stacked (P, ...) layout)."""
    state0 = jax.vmap(prog.init_state)(
        arrays.global_vid, arrays.degree, arrays.vtx_mask
    )
    mask0 = jax.vmap(prog.init_frontier)(
        arrays.global_vid, state0, arrays.vtx_mask
    ) & arrays.vtx_mask
    q_vid, q_val, cnt = jax.vmap(partial(build_queue, pspec))(
        arrays, mask0, state0
    )
    num_parts = arrays.global_vid.shape[0]
    return PushCarry(
        state0, q_vid, q_val, cnt, jnp.int32(0), jnp.int32(1),
        _zero_edges(), jnp.zeros((num_parts,), jnp.uint32), jnp.int32(0),
    )


def _push_prep(pspec: PushSpec, spec: ShardSpec, parrays, c: PushCarry):
    """LOAD phase: flatten the frontier queues and plan each part's sparse
    out-edge walk (vmap over parts); decide the global direction.  Returns
    (q_vids_all, q_vals_all, (rows, counts, incl, totals) stacked (P, ...),
    use_dense)."""
    with jax.named_scope("push.plan"):
        P_ = spec.num_parts
        q_vids_all = c.q_vid.reshape(P_ * pspec.f_cap)
        q_vals_all = c.q_val.reshape(P_ * pspec.f_cap)
        preps = jax.vmap(lambda parr: sparse_prep(parr, q_vids_all))(parrays)
        totals = preps[3]
        overflow = jnp.any(c.count > pspec.f_cap)
        edge_overflow = totals.max() > pspec.e_sp
        use_dense = (
            (jnp.sum(c.count) > spec.nv // pspec.pull_threshold_den)
            | overflow
            | edge_overflow
        )
        return q_vids_all, q_vals_all, preps, use_dense


def _push_relax(prog, pspec: PushSpec, spec: ShardSpec, method, arrays,
                parrays, c: PushCarry, q_vids_all, q_vals_all, preps,
                use_dense, route_static=None, route_arrays=None,
                interpret=False, ostatic=None, oarrays=None,
                merge: str = "bulk"):
    """COMP phase: dense (pull over all in-edges) or sparse (scatter the
    frontier's out-edges) relaxation -> new stacked state.

    ``use_dense`` is GLOBAL (identical for every part), so the direction
    switch is ONE `lax.cond` whose branches vmap over parts — a genuine
    branch (only the taken mode executes) with compile size O(1) in P,
    not the P-fold Python unroll of round 1.

    ``ostatic``/``oarrays`` (lux_tpu.mutate.overlay): dense rounds
    neutralize tombstoned base edges in-place; sparse rounds already
    skip them (the patched CSR pads their destinations to the
    drop-sentinel, build_push_overlay).  The fixed-capacity INSERT
    buffer is folded in once per round AFTER the direction branch —
    always relaxing every delta edge from the round's input state is
    monotone-safe (min/max relaxation is idempotent) and keeps both
    branches' traces identical in shape."""
    V = spec.nv_pad
    full = c.state.reshape((spec.gathered_size,) + c.state.shape[2:])
    rows, counts, incl, _ = preps
    if (ostatic is None) != (oarrays is None):
        # a loop compiled without overlay_static would otherwise
        # silently IGNORE a passed oarrays (base-graph results under a
        # caller who believes churn applied); the reverse dies on None
        raise ValueError(
            "overlay_static and oarrays must be passed together: "
            "compile_push_chunk(..., overlay_static=ostatic) and "
            "loop(..., oarrays=oarr)")
    dv = oarrays.del_val if ostatic is not None else None

    @jax.named_scope("push.dense")
    def dense_all():
        if route_static is not None:
            return jax.vmap(
                lambda arr, loc, ra, *o: dense_part_step(
                    prog, arr, full, loc, method,
                    route=(route_static, ra), interpret=interpret,
                    del_val=o[0] if o else None)
            )(arrays, c.state, route_arrays,
              *((dv,) if dv is not None else ()))
        return jax.vmap(
            lambda arr, loc, *o: dense_part_step(
                prog, arr, full, loc, method,
                del_val=o[0] if o else None)
        )(arrays, c.state, *((dv,) if dv is not None else ()))

    @jax.named_scope("push.sparse")
    def sparse_all():
        step = sparse_part_step if merge == "bulk" else sparse_part_step_tree

        def run(cap):
            def f(arr, parr, r, cn, inc, loc):
                return jnp.where(
                    arr.vtx_mask,
                    step(
                        prog, pspec, parr, V, q_vids_all, q_vals_all,
                        r, cn, inc, loc, cap,
                    ),
                    loc,
                )

            return jax.vmap(f)(arrays, parrays, rows, counts, incl, c.state)

        small = pspec.e_sp_small
        if not small:
            return run(pspec.e_sp)
        # two-tier walk: a round whose largest per-part out-edge total fits
        # the small buffer pays O(e_sp_small), not O(e_sp) — the SSSP/CC
        # late-round tail is many tiny frontiers
        fits = preps[3].max() <= small
        return jax.lax.cond(
            fits, lambda: run(small), lambda: run(pspec.e_sp)
        )

    new = jax.lax.cond(use_dense, dense_all, sparse_all)
    if ostatic is None:
        return new
    from lux_tpu.mutate import overlay as _ovl

    # insert fold: O(cap) gather + drop-scatter per round, relaxing
    # every live delta edge from the round's INPUT state (c.state, the
    # same state both branches read) — exact for the monotone min/max
    # programs, and the empty-slot sentinel drops everything else
    return jax.vmap(
        lambda oa, loc: _ovl.delta_scatter(loc, full, oa, prog.relax,
                                           prog.reduce)
    )(oarrays, new)


def _push_requeue(prog, pspec: PushSpec, spec: ShardSpec, arrays,
                  c: PushCarry, new, preps, use_dense) -> PushCarry:
    """UPDATE phase: rebuild the frontier queues from changed vertices and
    account traversed edges."""
    with jax.named_scope("push.requeue"):
        changed = (new != c.state) & arrays.vtx_mask
        q_vid, q_val, cnt = jax.vmap(partial(build_queue, pspec))(
            arrays, changed, new
        )
        active = jnp.sum(cnt)
        # traversal accounting (SURVEY.md §6): dense walks every real
        # edge, sparse walks the frontier's out-edges (the preps totals)
        edges = _acc_edges(c.edges, spec.ne, preps[3].sum(), use_dense)
        sp_work, dense_rounds = _acc_load(c, preps[3], use_dense)
        return PushCarry(
            new, q_vid, q_val, cnt, c.it + 1, active, edges, sp_work,
            dense_rounds,
        )


def _push_iteration(prog, pspec: PushSpec, spec: ShardSpec, method,
                    arrays, parrays, c: PushCarry, route_static=None,
                    route_arrays=None, interpret=False, ostatic=None,
                    oarrays=None, merge: str = "bulk") -> PushCarry:
    """One direction-optimized iteration over all parts (single device)."""
    q_vids_all, q_vals_all, preps, use_dense = _push_prep(pspec, spec, parrays, c)
    new = _push_relax(
        prog, pspec, spec, method, arrays, parrays, c,
        q_vids_all, q_vals_all, preps, use_dense,
        route_static, route_arrays, interpret, ostatic, oarrays, merge,
    )
    return _push_requeue(prog, pspec, spec, arrays, c, new, preps, use_dense)


def compile_push_chunk(prog, pspec: PushSpec, spec: ShardSpec,
                       method: str = "auto", donate: bool = False,
                       telemetry: bool = False, overlay_static=None,
                       merge: str | None = None):
    """Single-device push loop with a DYNAMIC iteration stop (one compile
    serves every run length and every adaptive-repartition window; the
    driver inspects the carry's load stats between windows).

    ``donate=True`` selects the donating twin (the carry — state + both
    queue buffers — is consumed, argnum 2), matching the pull engine's
    run_pull_fixed/run_pull_until ``donate=`` API: the loop's ping-pong
    reuses the input carry's HBM instead of holding a second full copy.
    The caller must not reuse the carry it passed in.  luxaudit LUX-J2
    asserts the aliases actually land in the lowered module.

    ``telemetry=True`` selects the flight-recorder twin:
    ``loop(arrays, parrays, carry, it_stop, ring)`` with an
    ``obs.ring.new_ring("push")`` riding the while carry, recording
    (iteration, frontier size, traversed edges, direction) per round —
    every column derived from the carry the engine already maintains, so
    the state math (and its bytes) is untouched.  Returns (carry, ring);
    ``donate`` consumes the ring with the carry.

    ``overlay_static`` (lux_tpu.mutate.overlay.OverlayStatic) compiles
    the mutation-overlay twin: the loop then takes the stacked
    OverlayArrays as a trailing ``oarrays`` argument — occupancy is
    data, so churn re-calls never recompile (LUX-J1).

    ``merge`` ("bulk" | "tree" | None) selects the cross-part merge of
    the sparse rounds (ops/merge_tree.py); None resolves the banked
    ``tpu:merge_mode`` winner.  Bitwise-identical either way for the
    min/max push programs.

    Resolution happens OUTSIDE the compile cache: caching on "auto" would
    pin the first platform resolution for the process and split the cache
    between "auto" and its concrete equivalent."""
    return _compile_push_chunk_cached(
        prog, pspec, spec, methods.resolve_sum(method, prog.reduce),
        donate=donate, telemetry=telemetry, ostatic=overlay_static,
        merge=_resolve_merge(merge),
    )


def compile_push_chunk_routed(prog, pspec: PushSpec, spec: ShardSpec,
                              route_static, method: str = "auto",
                              donate: bool = False,
                              telemetry: bool = False,
                              overlay_static=None,
                              merge: str | None = None):
    """compile_push_chunk with the dense rounds' gather routed
    (interpret mode resolved here, off-chip = CPU tests)."""
    from lux_tpu.engine.pull import _route_interpret

    return _compile_push_chunk_cached(
        prog, pspec, spec, methods.resolve_sum(method, prog.reduce),
        route_static=route_static, interpret=_route_interpret(),
        donate=donate, telemetry=telemetry, ostatic=overlay_static,
        merge=_resolve_merge(merge),
    )


@lru_cache(maxsize=64)
def _compile_push_chunk_cached(prog, pspec: PushSpec, spec: ShardSpec,
                               method: str, route_static=None,
                               interpret=False, donate=False,
                               telemetry=False, ostatic=None,
                               merge: str = "bulk"):
    if telemetry:
        return _compile_push_chunk_telemetry(
            prog, pspec, spec, method, route_static, interpret, donate,
            ostatic, merge)

    @partial(jax.jit, donate_argnums=(2,) if donate else ())
    def loop(arrays, parrays, carry: PushCarry, it_stop, route_arrays=None,
             oarrays=None):
        def cond(c):
            return (c.active > 0) & (c.it < it_stop)

        def body(c):
            return _push_iteration(prog, pspec, spec, method, arrays,
                                   parrays, c, route_static, route_arrays,
                                   interpret, ostatic, oarrays, merge)

        return jax.lax.while_loop(cond, body, carry)

    return loop


def _compile_push_chunk_telemetry(prog, pspec: PushSpec, spec: ShardSpec,
                                  method: str, route_static, interpret,
                                  donate, ostatic=None, merge: str = "bulk"):
    """The flight-recorder twin of the push chunk loop (see
    compile_push_chunk).  A separate compile, cached under the same
    lru key family: the ring rides the while carry, every recorded
    column is a pure DERIVATION of consecutive carries (frontier =
    queued count, traversed = edge-counter delta, direction =
    dense-round delta), so the engine body is byte-for-byte the
    non-telemetry one."""
    from lux_tpu.obs import ring as obs_ring

    @partial(jax.jit, donate_argnums=(2, 4) if donate else ())
    def loop(arrays, parrays, carry: PushCarry, it_stop, ring,
             route_arrays=None, oarrays=None):
        def cond(cr):
            c, _ = cr
            return (c.active > 0) & (c.it < it_stop)

        def body(cr):
            c, rg = cr
            c2 = _push_iteration(prog, pspec, spec, method, arrays,
                                 parrays, c, route_static, route_arrays,
                                 interpret, ostatic, oarrays, merge)
            # uint32 wrap-around subtraction gives the exact per-round
            # traversed count (< 2^32 per round by construction)
            rg = obs_ring.ring_push(
                rg, c.it, c.active, c2.edges[1] - c.edges[1],
                c2.dense_rounds - c.dense_rounds)
            return c2, rg

        return jax.lax.while_loop(cond, body, (carry, ring))

    return loop


def compile_push_phases(prog, pspec: PushSpec, spec: ShardSpec,
                        method: str = "auto", merge: str | None = None):
    """Uncached resolution shim — see compile_push_chunk."""
    return _compile_push_phases_cached(
        prog, pspec, spec, methods.resolve_sum(method, prog.reduce),
        _resolve_merge(merge),
    )


@lru_cache(maxsize=64)
def _compile_push_phases_cached(prog, pspec: PushSpec, spec: ShardSpec,
                                method: str, merge: str = "bulk"):
    """One push iteration as THREE separately-jitted sub-steps for the
    -verbose phase breakdown (the reference's per-iteration
    loadTime/compTime/updateTime, sssp_gpu.cu:513-518):

      load(parrays, carry)                 -> (qv, qw, preps, use_dense)
      comp(arrays, parrays, carry, plan)   -> new stacked state
      update(arrays, carry, new, plan)     -> next PushCarry

    Observability path (fences between phases); run_push is the perf path.
    """

    @jax.jit
    def load(parrays, carry: PushCarry):
        return _push_prep(pspec, spec, parrays, carry)

    @jax.jit
    def comp(arrays, parrays, carry: PushCarry, plan):
        q_vids_all, q_vals_all, preps, use_dense = plan
        return _push_relax(
            prog, pspec, spec, method, arrays, parrays, carry,
            q_vids_all, q_vals_all, preps, use_dense, merge=merge,
        )

    @jax.jit
    def update(arrays, carry: PushCarry, new, plan):
        _, _, preps, use_dense = plan
        return _push_requeue(prog, pspec, spec, arrays, carry, new, preps, use_dense)

    return load, comp, update


def compile_push_step(prog, pspec: PushSpec, spec: ShardSpec,
                      method: str = "auto", merge: str | None = None):
    """Jitted SINGLE iteration (verbose mode / step-wise drivers — the
    per-iteration observability the reference gets from -verbose kernel
    timers, sssp_gpu.cu:513-518).  The carry is donated (state/queue
    double buffers reuse HBM)."""
    return _compile_push_step_cached(
        prog, pspec, spec, methods.resolve_sum(method, prog.reduce),
        _resolve_merge(merge),
    )


@lru_cache(maxsize=64)
def _compile_push_step_cached(prog, pspec: PushSpec, spec: ShardSpec,
                              method: str, merge: str = "bulk"):

    @partial(jax.jit, donate_argnums=2)
    def step(arrays, parrays, carry: PushCarry):
        return _push_iteration(prog, pspec, spec, method, arrays, parrays,
                               carry, merge=merge)

    return step


def push_init(prog, shards: PushShards):
    """(arrays, parrays, carry0) device tuple for step-wise driving."""
    arrays = jax.tree.map(jnp.asarray, shards.arrays)
    parrays = jax.tree.map(jnp.asarray, shards.parrays)
    return arrays, parrays, _init_carry(prog, shards.pspec, arrays)


def run_push(
    prog: PushProgram,
    shards: PushShards,
    max_iters: int = 10_000,
    method: str = "auto",
    route=None,
    donate: bool = False,
    telemetry=None,
    merge: str | None = None,
):
    """Single-device driver.  The direction switch is one global `lax.cond`
    over vmapped per-part branches — a genuine branch (only the taken mode
    executes; the global predicate makes this legal) with compile size O(1)
    in the part count.  ``route`` (ops.expand.plan_expand_shards on the
    PULL layout, unfused or pass-fused — both bitwise-identical) runs
    the dense rounds' gather through the routed expand.  ``donate=True``
    runs the donating loop twin: the freshly-built initial carry is
    consumed, so the hot loop holds ONE state + queue copy in HBM
    instead of two (the pull engine's ``donate=`` contract on the push
    side; opt-in because benchmark drivers re-run from one carry).
    ``telemetry`` (``obs.ring.new_ring("push")``) records the
    per-iteration frontier/traversed/direction curve in the loop carry
    (bitwise no-op on the results; the return gains the fetched ring).
    ``merge`` ("bulk" | "tree", None = the banked ``tpu:merge_mode``)
    selects the sparse rounds' cross-part merge — bitwise-identical for
    the min/max push programs (ops/merge_tree.py).
    Spans ``push.init`` (placement, initial carry) and ``push.dispatch``
    (loop lookup and call); counters ``push.rounds``,
    ``push.dense_rounds``, ``push.mxscan_rounds``, ``push.arcs_walked``
    and ``push.exchange_bytes`` (0 here; ``_count_rounds``, never a
    sync).
    Returns (final stacked state, iters, edge counter[, ring]).

    Shards that ``place_push_shards`` laid over a mesh of more than one
    device (``parts_mesh``, read from the arrays' sharding on the host)
    run ``run_push_dist`` on that mesh; ``donate``, ``telemetry`` and
    ``route`` belong to the single-device loop and are refused there.
    Host (NumPy) shards and shards on one device take the single-device
    loop as before.
    """
    mesh = parts_mesh(shards.arrays)
    if mesh is not None:
        if donate or telemetry is not None or route is not None:
            raise ValueError(
                "run_push: shards placed over a multi-device mesh run the "
                "distributed loop, which has no donate=, telemetry= or "
                "route= (the single-device loop's options)")
        return run_push_dist(prog, shards, mesh, max_iters, method,
                             merge=merge)
    method = methods.resolve_sum(method, prog.reduce)
    spec, pspec = shards.spec, shards.pspec
    with obs.span("push.init"):
        arrays = jax.tree.map(jnp.asarray, shards.arrays)
        parrays = jax.tree.map(jnp.asarray, shards.parrays)
        carry0 = _init_carry(prog, pspec, arrays)
        tel = telemetry
        if tel is not None:
            tel = jax.tree.map(jnp.asarray, tel)
        extra = () if tel is None else (tel,)
    with obs.span("push.dispatch"):
        if route is None:
            loop = compile_push_chunk(prog, pspec, spec, method,
                                      donate=donate,
                                      telemetry=tel is not None, merge=merge)
            out = loop(arrays, parrays, carry0, jnp.int32(max_iters),
                       *extra)
        else:
            rs, ra = route
            ra = jax.tree.map(jnp.asarray, ra)
            loop = compile_push_chunk_routed(prog, pspec, spec, rs, method,
                                             donate=donate,
                                             telemetry=tel is not None,
                                             merge=merge)
            out = loop(arrays, parrays, carry0, jnp.int32(max_iters),
                       *extra, route_arrays=ra)
    ring = None
    if tel is not None:
        out, ring = out
    _count_rounds(out, method)
    if ring is not None:
        return out.state, out.it, out.edges, ring
    return out.state, out.it, out.edges


def _carry_specs():
    """shard_map PartitionSpecs for the stacked PushCarry: state/queues/
    count/sp_work live on the parts axis; it/active/edges/dense_rounds are
    replicated scalars (psum'd or identical on every device)."""
    return PushCarry(
        *([P(PARTS_AXIS)] * 4), P(), P(), P(), P(PARTS_AXIS), P()
    )


def _spmd_push_prep(pspec: PushSpec, spec: ShardSpec, parr_blk,
                    c: PushCarry, merge: str = "bulk", num_dev: int = 1):
    """LOAD phase from a device's perspective inside shard_map: exchange
    the frontier (vid, value) queues (they are small: O(P * f_cap)), plan
    each resident part's sparse out-edge walk, and psum the GLOBAL
    direction/tier votes.  Returns the plan
    (q_vids_all, q_vals_all, (rows, counts, incl, totals), use_dense,
    flags) — use_dense/flags are psum results (replicated); the preps are
    per-resident-lane.

    ``merge == "tree"`` swaps the bulk all_gather barrier for the staged
    ppermute concatenation (merge_tree.staged_concat_gather) — each
    device then holds the full queue in a per-device ROTATED part order,
    which every downstream consumer absorbs (walk totals are sums, the
    destination scatter is min/max: order-independent, bitwise)."""
    with jax.named_scope("push.plan"):
        with jax.named_scope("push.exchange"):
            if merge == "tree" and num_dev > 1:
                # unconditional straight-line collectives with static
                # offsets — the LUX-J3 deadlock-freedom argument
                # (ops/merge_tree.py)
                q_vids_all = merge_tree.staged_concat_gather(
                    c.q_vid, PARTS_AXIS, num_dev
                ).reshape(-1)
                q_vals_all = merge_tree.staged_concat_gather(
                    c.q_val, PARTS_AXIS, num_dev
                ).reshape(-1)
            else:
                # device order x resident order == global part order
                # (shard_stacked gives device d parts [d*k, (d+1)*k)), so
                # the tiled gather flattens straight into the (P * f_cap,)
                # global queue view
                q_vids_all = jax.lax.all_gather(
                    c.q_vid, PARTS_AXIS, tiled=True
                ).reshape(-1)
                q_vals_all = jax.lax.all_gather(
                    c.q_val, PARTS_AXIS, tiled=True
                ).reshape(-1)
        rows, counts, incl, totals = jax.vmap(
            lambda parr: sparse_prep(parr, q_vids_all)
        )(parr_blk)
        votes = jnp.stack(
            [
                jnp.sum((c.count > pspec.f_cap).astype(jnp.int32)),
                jnp.sum((totals > pspec.e_sp).astype(jnp.int32)),
                # tier vote: any part too big for the small buffer?
                jnp.sum((totals > pspec.e_sp_small).astype(jnp.int32)),
            ]
        )
        with jax.named_scope("push.exchange"):
            g_cnt = jax.lax.psum(jnp.sum(c.count), PARTS_AXIS)
            flags = jax.lax.psum(votes, PARTS_AXIS)
        use_dense = (
            (g_cnt > spec.nv // pspec.pull_threshold_den)
            | (flags[:2].max() > 0)
        )
        return (q_vids_all, q_vals_all, (rows, counts, incl, totals),
                use_dense, flags)


def _spmd_push_relax(prog, pspec: PushSpec, spec: ShardSpec, parr_blk,
                     qarr_blk, dense_fn, c: PushCarry, plan,
                     merge: str = "bulk"):
    """COMP phase from a device's perspective: one GLOBAL `lax.cond`
    between the engine-specific dense relaxation and the sparse frontier
    scatter over the resident lanes."""
    q_vids_all, q_vals_all, (rows, counts, incl, _), use_dense, flags = plan
    local = c.state  # (k, V)
    V = spec.nv_pad

    @jax.named_scope("push.dense")
    def dense_branch():
        return dense_fn(local)

    @jax.named_scope("push.sparse")
    def sparse_branch():
        step = sparse_part_step if merge == "bulk" else sparse_part_step_tree

        def run(cap):
            def f(qarr, parr, r, cn, inc, loc):
                return jnp.where(
                    qarr.vtx_mask,
                    step(
                        prog, pspec, parr, V, q_vids_all, q_vals_all,
                        r, cn, inc, loc, cap,
                    ),
                    loc,
                )

            return jax.vmap(f)(qarr_blk, parr_blk, rows, counts, incl, local)

        if not pspec.e_sp_small:
            return run(pspec.e_sp)
        # globally-agreed tier (flags[2] is a psum) — identical branch on
        # every device, collective-free branches
        return jax.lax.cond(
            flags[2] == 0, lambda: run(pspec.e_sp_small),
            lambda: run(pspec.e_sp),
        )

    return jax.lax.cond(use_dense, dense_branch, sparse_branch)


def _spmd_push_requeue(prog, pspec: PushSpec, spec: ShardSpec, qarr_blk,
                       c: PushCarry, new, plan) -> PushCarry:
    """UPDATE phase from a device's perspective: rebuild the frontier
    queues from changed vertices, psum the global active count, and
    account traversed edges."""
    (_, _, (_, _, _, totals), use_dense, _) = plan
    with jax.named_scope("push.requeue"):
        changed = (new != c.state) & qarr_blk.vtx_mask
        q_vid, q_val, cnt = jax.vmap(partial(build_queue, pspec))(
            qarr_blk, changed, new
        )
        with jax.named_scope("push.exchange"):
            active = jax.lax.psum(jnp.sum(cnt), PARTS_AXIS)
            # uint32 psum is exact: a sparse round's global total is
            # bounded by sum_p e_sp_p ≈ ne/4 < 2^32 (bigger frontiers
            # force dense)
            g_total = jax.lax.psum(jnp.sum(totals.astype(jnp.uint32)),
                                   PARTS_AXIS)
        edges = _acc_edges(c.edges, spec.ne, g_total, use_dense)
        sp_work, dense_rounds = _acc_load(c, totals, use_dense)
        return PushCarry(
            new, q_vid, q_val, cnt, c.it + 1, active, edges, sp_work,
            dense_rounds,
        )


def _spmd_push_iter(prog, pspec: PushSpec, spec: ShardSpec, parr_blk,
                    qarr_blk, dense_fn, c: PushCarry,
                    merge: str = "bulk", num_dev: int = 1) -> PushCarry:
    """ONE direction-optimized iteration from a device's perspective
    inside shard_map — the single source of truth for the dist, step-dist,
    ring, and pallas engines (their only difference is ``dense_fn``), and
    for the -verbose phase split (compile_push_phases_dist jits the three
    sub-phases separately).

    Each device holds k = P / mesh_size resident parts as the leading axis
    of every blocked field (k == 1 when parts == devices); per-part work
    vmaps over the resident lanes — the mapper-slicing analog
    (core/lux_mapper.cc:102-122).

    * frontier (vid, value) queues are exchanged unconditionally (bulk
      all_gather, or ``merge == "tree"``'s staged ppermute concatenation
      — same straight-line legality, see _spmd_push_prep);
    * the mode decision is GLOBAL (psum'd count + overflow/tier flags) so
      the dense branch's collectives sit inside `lax.cond` without
      divergence;
    * ``qarr_blk`` carries the per-vertex arrays (vtx_mask/global_vid) for
      the sparse mask and queue rebuild — ShardArrays on the all-gather
      engines, the slim VertexView on the ring engine;
    * ``dense_fn(block)`` is the engine-specific dense relaxation over the
      (k, V, ...) resident block: the all-gathered segmented reduce, or
      the ppermute ring fold.
    """
    plan = _spmd_push_prep(pspec, spec, parr_blk, c, merge, num_dev)
    new = _spmd_push_relax(
        prog, pspec, spec, parr_blk, qarr_blk, dense_fn, c, plan, merge
    )
    return _spmd_push_requeue(prog, pspec, spec, qarr_blk, c, new, plan)


def _allgather_dense_fn(prog, arr_blk, method, route_static=None,
                        route_blk=None, interpret=False):
    """Dense relaxation for the all-gather engines: whole state over ICI,
    then the segmented reduce over each resident part's in-edges
    (optionally through the routed-shuffle expand — bitwise)."""

    def dense_fn(block):
        with jax.named_scope("push.exchange"):
            full = flatten_gather(block)
        if route_static is not None:
            return jax.vmap(
                lambda arr, loc, ra: dense_part_step(
                    prog, arr, full, loc, method,
                    route=(route_static, ra), interpret=interpret)
            )(arr_blk, block, route_blk)
        return jax.vmap(
            lambda arr, loc: dense_part_step(prog, arr, full, loc, method)
        )(arr_blk, block)

    return dense_fn


@lru_cache(maxsize=64)
def _compile_push_dist(prog, mesh, pspec: PushSpec, spec: ShardSpec,
                       method: str, route_static=None,
                       interpret: bool = False, merge: str = "bulk"):
    arr_specs = ShardArrays(*([P(PARTS_AXIS)] * len(ShardArrays._fields)))
    parr_specs = PushArrays(*([P(PARTS_AXIS)] * len(PushArrays._fields)))
    carry_specs = _carry_specs()
    routed = route_static is not None
    in_specs = (arr_specs, parr_specs, carry_specs, P())
    if routed:
        in_specs = in_specs + (P(PARTS_AXIS),)

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=carry_specs,
        **methods.spmd_kw(method, routed),
    )
    def run(arr_blk, parr_blk, carry_blk, it_stop, *route_blk):

        def cond(c):
            return (c.active > 0) & (c.it < it_stop)

        def body(c):
            return _spmd_push_iter(
                prog, pspec, spec, parr_blk, arr_blk,
                _allgather_dense_fn(
                    prog, arr_blk, method, route_static,
                    route_blk[0] if routed else None, interpret),
                c, merge, mesh.devices.size,
            )

        return jax.lax.while_loop(cond, body, carry_blk)

    return run


def compile_push_phases_dist(prog, mesh, pspec: PushSpec, spec: ShardSpec,
                             method: str = "auto"):
    """One DISTRIBUTED push iteration as THREE separately-jitted,
    fence-able shard_map sub-steps — the multi-GPU `-verbose` breakdown
    of the reference (per-GPU loadTime/compTime/updateTime printed on
    multi-GPU runs, sssp_gpu.cu:513-518):

      load(parrays, carry)                -> plan (queue all_gather + walk
                                             planning + psum'd direction)
      comp(arrays, parrays, carry, plan)  -> new stacked state (the dense
                                             branch's state all_gather
                                             happens here, as it does in
                                             the single-device split)
      update(arrays, carry, new, plan)    -> next PushCarry (queue rebuild
                                             + active/edges psums)

    The phase bodies are the SAME _spmd_push_* the fused engines use.
    Observability path; _compile_push_dist is the perf path.  Always
    bulk-merge: the phase split's plan_specs model the gathered queue
    views as value-replicated lanes, which the tree exchange's rotated
    per-device order would not be (the perf loops take merge=)."""
    return _compile_push_phases_dist_cached(
        prog, mesh, pspec, spec, methods.resolve_sum(method, prog.reduce)
    )


@lru_cache(maxsize=64)
def _compile_push_phases_dist_cached(prog, mesh, pspec: PushSpec,
                                     spec: ShardSpec, method: str):
    arr_specs = ShardArrays(*([P(PARTS_AXIS)] * len(ShardArrays._fields)))
    parr_specs = PushArrays(*([P(PARTS_AXIS)] * len(PushArrays._fields)))
    carry_specs = _carry_specs()
    Pp = P(PARTS_AXIS)
    # The gathered queue views are value-replicated but shard_map cannot
    # statically infer all_gather outputs as such, so each device carries
    # its copy as a (1, P*f_cap) lane under the parts spec (global shape
    # (D, P*f_cap) — exactly the per-device replicated queue view the
    # fused engines hold internally); psum'd votes ARE inferred
    # replicated; walk plans are per-resident-lane.
    plan_specs = (Pp, Pp, (Pp, Pp, Pp, Pp), P(), P())

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(parr_specs, carry_specs),
        out_specs=plan_specs,
    )
    def load(parr_blk, c):
        qv, qw, preps, use_dense, flags = _spmd_push_prep(
            pspec, spec, parr_blk, c
        )
        return qv[None], qw[None], preps, use_dense, flags

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(arr_specs, parr_specs, carry_specs, plan_specs),
        out_specs=Pp,
        **methods.spmd_kw(method),
    )
    def comp(arr_blk, parr_blk, c, plan):
        qv, qw, preps, use_dense, flags = plan
        return _spmd_push_relax(
            prog, pspec, spec, parr_blk, arr_blk,
            _allgather_dense_fn(prog, arr_blk, method), c,
            (qv[0], qw[0], preps, use_dense, flags),
        )

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(arr_specs, carry_specs, Pp, plan_specs),
        out_specs=carry_specs,
    )
    def update(arr_blk, c, new, plan):
        qv, qw, preps, use_dense, flags = plan
        return _spmd_push_requeue(
            prog, pspec, spec, arr_blk, c, new,
            (qv[0], qw[0], preps, use_dense, flags),
        )

    return load, comp, update


def place_push_shards(shards: PushShards, mesh: Mesh) -> PushShards:
    """The shards with their stacked arrays laid over ``mesh``'s parts
    axis, each device holding its own k = P / D parts and no more
    (``shard_stacked``): a deployment places the graph once and then
    runs every traversal on it (``run_push`` routes placed shards to
    ``run_push_dist``).  Program span ``push.place``."""
    with obs.span("push.place", parts=shards.spec.num_parts,
                  devices=mesh.devices.size):
        arrays, parrays = shard_stacked(mesh, (shards.arrays,
                                               shards.parrays))
        jax.block_until_ready((arrays, parrays))
    return PushShards(pull=replace(shards.pull, arrays=arrays),
                      pspec=shards.pspec, parrays=parrays)


@lru_cache(maxsize=64)
def _compile_init_dist(prog, mesh, pspec: PushSpec):
    view_specs = VertexView(*([P(PARTS_AXIS)] * len(VertexView._fields)))
    return jax.jit(jax.shard_map(
        partial(_init_carry, prog, pspec), mesh=mesh,
        in_specs=(view_specs,), out_specs=_carry_specs(),
    ))


def init_carry_dist(prog, pspec: PushSpec, view: VertexView, mesh: Mesh):
    """The first carry built on the mesh, each device from its own parts'
    rows of the placed vertex ``view``: no device holds another's."""
    return _compile_init_dist(prog, mesh, pspec)(shard_stacked(mesh, view))


def push_init_dist(prog, shards: PushShards, mesh: Mesh):
    """(arrays, parrays, carry0) sharded over the mesh for step-wise
    distributed driving.  Placed arrays are used as they are; host ones
    go straight to their devices."""
    arrays, parrays = shard_stacked(mesh, (shards.arrays, shards.parrays))
    carry0 = init_carry_dist(prog, shards.pspec, vertex_view(arrays), mesh)
    return arrays, parrays, carry0


def shard_carry(mesh: Mesh, c: PushCarry) -> PushCarry:
    """Place a host/stacked PushCarry onto the mesh (parts-axis fields
    sharded, scalars replicated)."""
    sharded = shard_stacked(
        mesh, (c.state, c.q_vid, c.q_val, c.count, c.sp_work)
    )
    return PushCarry(
        *sharded[:4], c.it, c.active, c.edges, sharded[4], c.dense_rounds
    )


def assemble_carry(c_local: PushCarry, assemble) -> PushCarry:
    """Multihost analog of shard_carry: stitch a per-host LOCAL-parts
    carry into the globally-sharded one.  ``assemble(host_stacked) ->
    global jax.Array`` (e.g. multihost.assemble_global bound to the mesh).
    Keeps the sharded-vs-replicated field split in ONE place with
    shard_carry/_carry_specs; the scalar fields are process-identical by
    construction of _init_carry."""
    import numpy as np

    def sh(a):
        return assemble(np.asarray(a))

    return PushCarry(
        sh(c_local.state), sh(c_local.q_vid), sh(c_local.q_val),
        sh(c_local.count), c_local.it, c_local.active, c_local.edges,
        sh(c_local.sp_work), c_local.dense_rounds,
    )


@lru_cache(maxsize=64)
def _compile_push_ring(prog, mesh, pspec: PushSpec, spec: ShardSpec,
                       e_bucket_pad: int, method: str,
                       route_static=None, interpret: bool = False,
                       merge: str = "bulk"):
    """Direction-optimizing push with the RING dense exchange: sparse
    rounds exchange (vid, value) queues exactly like _compile_push_dist;
    dense rounds fold ppermute-streamed state blocks through the ring
    buckets (min/max end-reductions) instead of all-gathering the whole
    state — per-chip resident state stays O(nv/P), so CC/SSSP scale past
    the replicated-state ceiling (SURVEY.md §7.3)."""
    from lux_tpu.parallel.ring import RingArrays, neutral_like, ring_sweep

    num_parts = spec.num_parts
    D = mesh.devices.size
    k = num_parts // D
    rarr_specs = RingArrays(*([P(PARTS_AXIS)] * len(RingArrays._fields)))
    parr_specs = PushArrays(*([P(PARTS_AXIS)] * len(PushArrays._fields)))
    view_specs = VertexView(*([P(PARTS_AXIS)] * len(VertexView._fields)))
    carry_specs = _carry_specs()
    routed = route_static is not None
    in_specs = (rarr_specs, parr_specs, view_specs, carry_specs, P())
    kw = {}
    if routed:
        in_specs = in_specs + (P(PARTS_AXIS),)
        kw["check_vma"] = False  # pallas under shard_map (see dist.py)

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=carry_specs,
        **kw,
    )
    def run(rarr_blk, parr_blk, view_blk, carry_blk, it_stop, *route_blk):
        V = spec.nv_pad
        my = jax.lax.axis_index(PARTS_AXIS)
        op = _op(prog)

        def cond(c):
            return (c.active > 0) & (c.it < it_stop)

        def ring_dense_fn(block):  # (k, V) resident parts
            def fold(s, acc, stream):
                # the in-flight stream holds the k parts resident on device
                # (my + s) % D; fold each streamed lane's bucket into every
                # resident lane (j is a static unroll: k is a compile-time
                # geometry constant, typically small)
                dev = (my + s) % D
                for j in range(k):
                    q = dev * k + j  # global part id of streamed lane j

                    def one(rarr_i, acc_i, ra_i=None, q=q):
                        if ra_i is not None:
                            from lux_tpu.ops import expand as _expand

                            src_vals = _expand.apply_expand(
                                stream[j], route_static,
                                jax.tree.map(lambda a: a[q], ra_i),
                                interpret=interpret)
                        else:
                            src_vals = stream[j][rarr_i.src_local[q]]
                        vals = prog.relax(src_vals, rarr_i.weights[q])
                        part = segment.segment_reduce_by_ends(
                            vals, rarr_i.head_flag[q], rarr_i.dst_local[q],
                            V, reduce=prog.reduce, method=method,
                        )
                        return op(acc_i, part)

                    if routed:
                        acc = jax.vmap(one)(rarr_blk, acc, route_blk[0])
                    else:
                        acc = jax.vmap(one)(rarr_blk, acc)
                return acc

            acc = ring_sweep(block, neutral_like(block, prog.reduce), fold, D)
            return jnp.where(view_blk.vtx_mask, op(block, acc), block)

        def body(c):
            return _spmd_push_iter(
                prog, pspec, spec, parr_blk, view_blk, ring_dense_fn, c,
                merge, D,
            )

        return jax.lax.while_loop(cond, body, carry_blk)

    return run


def place_ring_statics(shards, mesh: Mesh):
    """Device-place the ring push engine's static arrays: only O(part
    edges) buckets/CSR and the O(V) vertex view — never the pull layout's
    O(E) stacked arrays.  Returns (rarrays, parrays, view)."""
    return shard_stacked(
        mesh, (shards.rarrays, shards.parrays, vertex_view(shards.arrays))
    )


def ring_init_dist(prog, shards, mesh: Mesh):
    """(rarrays, parrays, view, carry0) sharded tuple for driving the
    ring push engine."""
    rarrays, parrays, view = place_ring_statics(shards, mesh)
    carry0 = init_carry_dist(prog, shards.pspec, view, mesh)
    return rarrays, parrays, view, carry0


def run_push_ring(
    prog: PushProgram,
    shards,  # parallel.ring.PushRingShards
    mesh: Mesh,
    max_iters: int = 10_000,
    method: str = "auto",
    route=None,
    merge: str | None = None,
):
    """Distributed push driver with the ring dense exchange.  Only the
    O(part edges) CSR/bucket arrays and O(V) vertex arrays touch the
    devices — never the pull layout's O(E) stacked arrays.  ``route``
    (ops.expand.plan_ring_route_shards on the ring buckets) replays the
    dense rounds' streamed-block gathers as routed lane shuffles —
    bitwise-identical (note its plan-footprint SCALE NOTE: the routed
    mode trades the O(nv/P) memory story for hot-loop speed).  ``auto``
    takes the blanket ``resolve``: the buckets carry no row_ptr."""
    method = methods.resolve(method, prog.reduce)
    merge = _resolve_merge(merge)
    spec, pspec = shards.spec, shards.pspec
    assert spec.num_parts % mesh.devices.size == 0
    assert method in ("scan", "scatter"), (
        segment.BUCKETED_METHODS_NOTE
    )
    with obs.span("push.init"):
        rarrays, parrays, view, carry0 = ring_init_dist(prog, shards, mesh)
    with obs.span("push.dispatch"):
        if route is None:
            run = _compile_push_ring(
                prog, mesh, pspec, spec, shards.e_bucket_pad, method,
                merge=merge,
            )
            out = run(rarrays, parrays, view, carry0, jnp.int32(max_iters))
        else:
            from lux_tpu.parallel.mesh import routed_run_args

            rs, ra, interp = routed_run_args(mesh, route)
            run = _compile_push_ring(
                prog, mesh, pspec, spec, shards.e_bucket_pad, method,
                route_static=rs, interpret=interp, merge=merge,
            )
            out = run(rarrays, parrays, view, carry0, jnp.int32(max_iters),
                      ra)
    _count_rounds(out, method, exchange_bytes(
        pspec, spec, mesh.devices.size, out.state))
    return out.state, out.it, out.edges


def run_push_dist(
    prog: PushProgram,
    shards: PushShards,
    mesh: Mesh,
    max_iters: int = 10_000,
    method: str = "auto",
    route=None,
    merge: str | None = None,
):
    """Distributed driver: queues (sparse rounds) or whole state (dense
    rounds) exchanged over ICI inside the on-device loop.  ``route``
    (an expand plan on the pull layout) replays the dense rounds'
    gather as routed shuffles — bitwise-identical.  ``merge`` ("bulk" |
    "tree", None = banked winner): tree mode exchanges the queues via
    staged ppermutes and merges through the static reduction tree —
    also bitwise (ops/merge_tree.py)."""
    method = methods.resolve_sum(method, prog.reduce)
    merge = _resolve_merge(merge)
    spec, pspec = shards.spec, shards.pspec
    assert spec.num_parts % mesh.devices.size == 0
    with obs.span("push.init"):
        arrays, parrays, carry0 = push_init_dist(prog, shards, mesh)
    with obs.span("push.dispatch"):
        if route is None:
            run = _compile_push_dist(prog, mesh, pspec, spec, method,
                                     merge=merge)
            out = run(arrays, parrays, carry0, jnp.int32(max_iters))
        else:
            from lux_tpu.parallel.mesh import routed_run_args

            rs, ra, interp = routed_run_args(mesh, route)
            run = _compile_push_dist(prog, mesh, pspec, spec, method,
                                     route_static=rs, interpret=interp,
                                     merge=merge)
            out = run(arrays, parrays, carry0, jnp.int32(max_iters), ra)
    _count_rounds(out, method, exchange_bytes(
        pspec, spec, mesh.devices.size, out.state))
    return out.state, out.it, out.edges
