"""Delta-stepping weighted SSSP: bucketed priority frontiers.

The chaotic-relaxation weighted SSSP (models/sssp.WeightedSSSPProgram on
the plain push engine) expands every improved vertex immediately, so a
vertex whose tentative distance later improves is expanded AGAIN — on
weighted graphs the wasted cascades dominate (Bellman-Ford behavior).
Delta-stepping (Meyer & Sanders 2003) processes vertices in distance
buckets of width Δ: only pending vertices with ``dist < thr`` (the
current bucket) may expand; improved vertices park in ``pending`` until
their bucket opens, so most expand exactly once, with their final
distance — Dijkstra-like edge counts with frontier-level parallelism.

BASELINE.json's config list names a "frontier delta-stepping kernel" as
the SSSP framing; the reference has no weighted SSSP at all (its app is
BFS, sssp/sssp_gpu.cu:122), so this is target parity, not code parity.

TPU-first shape: ONE extra (P, V) bool mask + ONE int32 threshold on
top of the push carry; every round expands via the push engine's OWN
prep/relax bodies (queue build, two-tier sparse walk, global direction
switch — through a synthesized PushCarry), with the threshold advance
FUSED in front (a masked min + round-up — when the current bucket is
empty the threshold jumps past the smallest pending distance in the
same round, so there are no advance-only rounds to dispatch).  The
whole loop stays on device in `lax.while_loop`.
A dense expansion round relaxes every edge (all sources, not just the
bucket), which is still exact — min-relaxation is monotone — and clears
ALL pending work for the round; the accounting (edges walked) uses the
push engine's exact [hi, lo] uint32 counter either way.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from lux_tpu.engine import methods, push
from lux_tpu.graph.push_shards import PushShards, PushSpec
from lux_tpu.graph.shards import ShardSpec


class DeltaCarry(NamedTuple):
    state: Any    # (P, V) tentative distances
    pending: Any  # (P, V) bool: improved but not yet expanded
    thr: Any      # int32 scalar: current bucket's EXCLUSIVE upper bound
    it: Any       # int32 expansion rounds run (advances are fused)
    active: Any   # int32 total pending count (0 = converged)
    edges: Any    # exact traversed-edge counter ([hi, lo] uint32 pair)


def _init_carry(prog, pspec: PushSpec, arrays, delta: int) -> DeltaCarry:
    state0 = jax.vmap(prog.init_state)(
        arrays.global_vid, arrays.degree, arrays.vtx_mask
    )
    pending0 = jax.vmap(prog.init_frontier)(
        arrays.global_vid, state0, arrays.vtx_mask
    ) & arrays.vtx_mask
    return DeltaCarry(
        state0, pending0, jnp.int32(delta), jnp.int32(0),
        jnp.sum(pending0.astype(jnp.int32)), push._zero_edges(),
    )


def _advanced_thr(prog, delta: int, c: DeltaCarry, n_in,
                  min_pend=None):
    """The bucket threshold for THIS round: unchanged while the current
    bucket still has pending work; otherwise jump past the smallest
    pending distance (skipping empty buckets in one hop).  Fused into
    the expansion round — a separate advance-only round would pay a
    whole dispatch to move one scalar, and at small Δ advance rounds
    are ~half of all rounds.  ``min_pend`` overrides the local masked
    min (the SPMD path passes its pmin) so the jump arithmetic lives in
    exactly one place."""
    if min_pend is None:
        inf = jnp.int32(prog.inf)
        min_pend = jnp.min(jnp.where(c.pending, c.state, inf))
    jumped = (min_pend // jnp.int32(delta) + 1) * jnp.int32(delta)
    return jnp.where(n_in > 0, c.thr, jumped)


def _delta_iteration(prog, pspec: PushSpec, spec: ShardSpec, method,
                     delta: int, arrays, parrays, c: DeltaCarry,
                     route_static=None, route_arrays=None, interpret=False
                     ) -> DeltaCarry:
    in_bucket = c.pending & (c.state < c.thr)
    n_in = jnp.sum(in_bucket.astype(jnp.int32))
    thr = _advanced_thr(prog, delta, c, n_in)
    # recompute under the (possibly advanced) threshold: non-empty
    # whenever any work is pending, so every round expands
    in_bucket = c.pending & (c.state < thr)
    q_vid, q_val, cnt = jax.vmap(partial(push.build_queue, pspec))(
        arrays, in_bucket, c.state
    )
    num_parts = arrays.global_vid.shape[0]
    tmp = push.PushCarry(
        c.state, q_vid, q_val, cnt, jnp.int32(0), jnp.int32(1),
        push._zero_edges(), jnp.zeros((num_parts,), jnp.uint32),
        jnp.int32(0),
    )
    q_vids_all, q_vals_all, preps, use_dense = push._push_prep(
        pspec, spec, parrays, tmp
    )
    new = push._push_relax(
        prog, pspec, spec, method, arrays, parrays, tmp,
        q_vids_all, q_vals_all, preps, use_dense,
        route_static, route_arrays, interpret,
    )
    changed = (new != c.state) & arrays.vtx_mask
    # sparse rounds expand exactly the bucket; a dense round relaxes
    # every source, so EVERYTHING pending counts as expanded
    kept = jnp.where(use_dense, False, c.pending & ~in_bucket)
    pending = kept | changed
    edges = push._acc_edges(c.edges, spec.ne, preps[3].sum(), use_dense)
    return DeltaCarry(
        new, pending, thr, c.it + 1,
        jnp.sum(pending.astype(jnp.int32)), edges,
    )


@lru_cache(maxsize=64)
def _compile_delta_loop(prog, pspec: PushSpec, spec: ShardSpec,
                        method: str, delta: int, route_static=None,
                        interpret=False):
    @jax.jit
    def loop(arrays, parrays, c0, max_iters, route_arrays=None):
        def cond(c):
            return (c.active > 0) & (c.it < max_iters)

        def body(c):
            return _delta_iteration(
                prog, pspec, spec, method, delta, arrays, parrays, c,
                route_static, route_arrays, interpret
            )

        return jax.lax.while_loop(cond, body, c0)

    return loop


def _validate(prog, delta: int) -> None:
    """Shared driver-entry guards (single-device AND distributed)."""
    if delta <= 0:
        raise ValueError(f"delta must be positive, got {delta}")
    if prog.reduce != "min":
        raise ValueError("delta-stepping is a min-relaxation driver")


def _spmd_delta_iteration(prog, pspec: PushSpec, spec: ShardSpec, method,
                          delta: int, arr_blk, parr_blk, c: DeltaCarry
                          ) -> DeltaCarry:
    """One delta round from a device's perspective inside shard_map
    (k resident parts as the leading axis).  The bucket-occupancy vote
    (one psum) and the fused threshold advance (one pmin) are GLOBAL —
    every device takes the identical single path, so the collectives
    inside never diverge; expansion reuses the push engine's OWN SPMD
    prep/relax bodies via a synthesized PushCarry."""
    lax = jax.lax

    in_bucket = c.pending & (c.state < c.thr)
    n_in = lax.psum(jnp.sum(in_bucket.astype(jnp.int32)), push.PARTS_AXIS)
    # fused threshold advance — same _advanced_thr arithmetic, with the
    # masked min pmin'd over the parts axis
    inf = jnp.int32(prog.inf)
    local_min = jnp.min(jnp.where(c.pending, c.state, inf))
    thr = _advanced_thr(prog, delta, c, n_in,
                        min_pend=lax.pmin(local_min, push.PARTS_AXIS))
    in_bucket = c.pending & (c.state < thr)
    q_vid, q_val, cnt = jax.vmap(partial(push.build_queue, pspec))(
        arr_blk, in_bucket, c.state
    )
    k = arr_blk.global_vid.shape[0]
    tmp = push.PushCarry(
        c.state, q_vid, q_val, cnt, jnp.int32(0), jnp.int32(1),
        push._zero_edges(), jnp.zeros((k,), jnp.uint32), jnp.int32(0),
    )
    plan = push._spmd_push_prep(pspec, spec, parr_blk, tmp)
    new = push._spmd_push_relax(
        prog, pspec, spec, parr_blk, arr_blk,
        push._allgather_dense_fn(prog, arr_blk, method), tmp, plan,
    )
    use_dense = plan[3]
    changed = (new != c.state) & arr_blk.vtx_mask
    kept = jnp.where(use_dense, False, c.pending & ~in_bucket)
    pending = kept | changed
    active = lax.psum(
        jnp.sum(pending.astype(jnp.int32)), push.PARTS_AXIS
    )
    totals = plan[2][3]
    g_total = lax.psum(
        jnp.sum(totals.astype(jnp.uint32)), push.PARTS_AXIS
    )
    edges = push._acc_edges(c.edges, spec.ne, g_total, use_dense)
    return DeltaCarry(new, pending, thr, c.it + 1, active, edges)


@lru_cache(maxsize=64)
def _compile_delta_dist(prog, mesh, pspec: PushSpec, spec: ShardSpec,
                        method: str, delta: int):
    from jax.sharding import PartitionSpec as P

    from lux_tpu.graph.shards import ShardArrays
    from lux_tpu.graph.push_shards import PushArrays

    Pp = P(push.PARTS_AXIS)
    arr_specs = ShardArrays(*([Pp] * len(ShardArrays._fields)))
    parr_specs = PushArrays(*([Pp] * len(PushArrays._fields)))
    carry_specs = DeltaCarry(Pp, Pp, P(), P(), P(), P())

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(arr_specs, parr_specs, carry_specs, P()),
        out_specs=carry_specs,
        **methods.spmd_kw(method),
    )
    def run(arr_blk, parr_blk, c_blk, max_iters):
        def cond(c):
            return (c.active > 0) & (c.it < max_iters)

        def body(c):
            return _spmd_delta_iteration(
                prog, pspec, spec, method, delta, arr_blk, parr_blk, c
            )

        return jax.lax.while_loop(cond, body, c_blk)

    return run


def run_push_delta_dist(
    prog,
    shards: PushShards,
    delta: int,
    mesh,
    max_iters: int = 100_000,
    method: str = "auto",
):
    """Distributed delta-stepping over a parts mesh (k resident parts
    per device supported): the same bucket discipline with ONE psum for
    the bucket-occupancy vote and ONE pmin for the threshold advance —
    both ride ICI, the loop stays on device end to end."""
    from lux_tpu.parallel.mesh import shard_stacked

    _validate(prog, delta)
    method = methods.resolve(method, prog.reduce)
    spec, pspec = shards.spec, shards.pspec
    assert spec.num_parts % mesh.devices.size == 0
    arrays_h = jax.tree.map(jnp.asarray, shards.arrays)
    c0 = _init_carry(prog, pspec, arrays_h, delta)
    arrays = shard_stacked(mesh, arrays_h)
    parrays = shard_stacked(mesh, jax.tree.map(jnp.asarray, shards.parrays))
    c0 = DeltaCarry(
        *shard_stacked(mesh, (c0.state, c0.pending)),
        c0.thr, c0.it, c0.active, c0.edges,
    )
    out = _compile_delta_dist(prog, mesh, pspec, spec, method, delta)(
        arrays, parrays, c0, jnp.int32(max_iters)
    )
    return out.state, out.it, out.edges


def run_push_delta(
    prog,
    shards: PushShards,
    delta: int,
    max_iters: int = 100_000,
    method: str = "auto",
    route=None,
):
    """Single-device delta-stepping driver (min-reduce programs).
    Returns (final stacked state, rounds run, edges [hi, lo]).  ``delta``
    is the bucket width in distance units; small Δ approaches Dijkstra
    (fewest edge relaxations, most rounds), large Δ approaches the
    chaotic engine (fewest rounds, most edges).  ``route`` (an expand
    plan on the pull layout) routes the dense rounds' gather —
    bitwise-identical."""
    _validate(prog, delta)
    method = methods.resolve(method, prog.reduce)
    spec, pspec = shards.spec, shards.pspec
    arrays = jax.tree.map(jnp.asarray, shards.arrays)
    parrays = jax.tree.map(jnp.asarray, shards.parrays)
    c0 = _init_carry(prog, pspec, arrays, delta)
    if route is None:
        loop = _compile_delta_loop(prog, pspec, spec, method, delta)
        out = loop(arrays, parrays, c0, jnp.int32(max_iters))
    else:
        from lux_tpu.engine.pull import _route_interpret

        rs, ra = route
        ra = jax.tree.map(jnp.asarray, ra)
        loop = _compile_delta_loop(prog, pspec, spec, method, delta,
                                   route_static=rs,
                                   interpret=_route_interpret())
        out = loop(arrays, parrays, c0, jnp.int32(max_iters),
                   route_arrays=ra)
    return out.state, out.it, out.edges
