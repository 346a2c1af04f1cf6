"""Distributed (multi-chip) pull-engine drivers via shard_map.

This is the communication backend of the framework — the role Legion +
GASNet play in the reference, where declaring a whole-region read
(core/pull_model.inl:454-461) makes the runtime all-gather every part's
vertex state into each node's zero-copy memory per iteration
(SURVEY.md §2.5, §5).  Here the exchange is explicit and rides ICI:

    full_state = lax.all_gather(local_state, "parts", tiled=True)

inside `shard_map` over a 1-D mesh, with the iteration loop staying
on-device (`lax.fori_loop` / `lax.while_loop`) and convergence decided by a
`lax.psum` of per-part active counts — the analog of the FutureMap
reduction at sssp/sssp.cc:116-129, minus the 4-iteration host lag.

The per-part compute is byte-identical to the single-device path
(lux_tpu.engine.pull.local_pull_step): only the state exchange differs.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from lux_tpu.engine.pull import (
    PullProgram, local_pull_step, pull_gather_part, pull_reduce_part,
)
from lux_tpu.graph.shards import ShardArrays, ShardSpec
from lux_tpu.parallel.mesh import PARTS_AXIS, shard_stacked
from lux_tpu.parallel.placement import halo_all_gather


def _arrays_specs():
    return ShardArrays(*([P(PARTS_AXIS)] * len(ShardArrays._fields)))


@lru_cache(maxsize=64)
def _compile_fixed(prog, mesh, num_iters: int, method: str,
                   route_static=None, interpret: bool = False):
    """Build (once per config) the jitted shard_map program.  Cached so
    repeated calls don't retrace; all keys are hashable statics.

    ``route_static``: ExpandStatic to run each resident part's LOAD
    phase through the routed-shuffle expand (parts share ONE static by
    construction, so the vmapped lanes stay uniform; the per-part index
    arrays ride in as a sharded pytree operand)."""
    from lux_tpu.engine import methods

    routed = route_static is not None
    in_specs = (_arrays_specs(), P(PARTS_AXIS))
    if routed:
        in_specs = in_specs + (P(PARTS_AXIS),)

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(PARTS_AXIS),
        **methods.spmd_kw(method, routed),
    )
    def run(arr_blk, state_blk, *route_blk):
        # each device holds k = P/D resident parts (k == 1 when P == D);
        # the per-part step vmaps over the resident lanes — the mapper-
        # slicing analog (core/lux_mapper.cc:102-122)
        def body(_, block):
            full = halo_all_gather(block)
            if routed:
                return jax.vmap(
                    lambda arr, loc, ra: local_pull_step(
                        prog, arr, full, loc, method,
                        route=(route_static, ra), interpret=interpret)
                )(arr_blk, block, route_blk[0])
            return jax.vmap(
                lambda arr, loc: local_pull_step(prog, arr, full, loc, method)
            )(arr_blk, block)

        return jax.lax.fori_loop(0, num_iters, body, state_blk)

    return run


def run_pull_fixed_dist(
    prog: PullProgram,
    spec: ShardSpec,
    arrays: ShardArrays,
    state0: jnp.ndarray,
    num_iters: int,
    mesh: Mesh,
    method: str = "auto",
    route=None,
):
    """Fixed-iteration distributed pull (PageRank/CF).  ``arrays`` and
    ``state0`` are stacked (P, ...) with P == mesh size; returns the final
    stacked state (sharded).  P may be any multiple of the mesh size
    (k parts resident per device).  ``route`` runs each part's hot loop
    through the routed pipelines (ops/expand.py: ExpandStatic = routed
    LOAD, bitwise; CFRouteStatic = wide src+dst routed LOAD, bitwise;
    FusedStatic = routed load AND reduce); the all_gather exchange is
    unchanged."""
    from lux_tpu.engine import methods
    from lux_tpu.engine.pull import _route_interpret

    method = methods.resolve_sum(method, prog.reduce)
    assert spec.num_parts % mesh.devices.size == 0, (spec.num_parts, mesh.shape)
    arrays = shard_stacked(mesh, arrays)
    state0 = shard_stacked(mesh, state0)
    if route is None:
        return _compile_fixed(prog, mesh, num_iters, method)(arrays, state0)
    rs, ra = route
    ra = shard_stacked(mesh, ra)
    fn = _compile_fixed(prog, mesh, num_iters, method, route_static=rs,
                        interpret=_route_interpret())
    return fn(arrays, state0, ra)


def compile_pull_phases_dist(prog, mesh, method: str = "auto"):
    """One DISTRIBUTED pull iteration as THREE separately-jitted,
    fence-able shard_map sub-steps — the multi-GPU `-verbose` phase
    breakdown of the reference (per-GPU loadTime/compTime/updateTime,
    sssp_gpu.cu:513-518, printed on multi-GPU runs too):

      load(arrays, state)        -> per-edge gathered (src, dst) states;
                                    carries THE exchange (all_gather of
                                    every part's state over ICI — the
                                    Legion/GASNet whole-region read,
                                    core/pull_model.inl:454-461)
      comp(arrays, gathered)     -> per-destination reduced accumulators
      update(arrays, state, acc) -> new state (apply; state donated)

    The per-part bodies are the SAME pull_gather_part/pull_reduce_part
    the fused engines use.  Observability path: fencing between phases
    costs dispatch latency; run_pull_fixed_dist is the perf path."""
    from lux_tpu.engine import methods

    return _compile_phases_dist_cached(
        prog, mesh, methods.resolve_sum(method, prog.reduce)
    )


@lru_cache(maxsize=64)
def _compile_phases_dist_cached(prog, mesh, method: str):
    from lux_tpu.engine import methods

    Pp = P(PARTS_AXIS)

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(_arrays_specs(), Pp),
        out_specs=(Pp, Pp),
    )
    def load(arr_blk, state_blk):
        full = halo_all_gather(state_blk)  # the ICI exchange
        return jax.vmap(
            lambda arr, loc: pull_gather_part(arr, full, loc)
        )(arr_blk, state_blk)

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(_arrays_specs(), (Pp, Pp)),
        out_specs=Pp,
        **methods.spmd_kw(method),
    )
    def comp(arr_blk, gath_blk):
        return jax.vmap(
            lambda arr, gath: pull_reduce_part(prog, arr, gath, method)
        )(arr_blk, gath_blk)

    @partial(jax.jit, donate_argnums=1)
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(_arrays_specs(), Pp, Pp),
        out_specs=Pp,
    )
    def update(arr_blk, state_blk, acc_blk):
        return jax.vmap(lambda arr, loc, a: prog.apply(loc, a, arr))(
            arr_blk, state_blk, acc_blk
        )

    return load, comp, update


@lru_cache(maxsize=64)
def _compile_until(prog, mesh, max_iters: int, active_fn, method: str):
    from lux_tpu.engine import methods

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(_arrays_specs(), P(PARTS_AXIS)),
        out_specs=(P(PARTS_AXIS), P()),
        **methods.spmd_kw(method),
    )
    def run(arr_blk, state_blk):

        def cond(carry):
            _, it, active = carry
            return (active > 0) & (it < max_iters)

        def body(carry):
            block, it, _ = carry
            full = halo_all_gather(block)
            new = jax.vmap(
                lambda arr, loc: local_pull_step(prog, arr, full, loc, method)
            )(arr_blk, block)
            # per-lane counts summed locally, then one psum over devices
            counts = jax.vmap(active_fn)(block, new)
            active = jax.lax.psum(
                jnp.sum(counts.astype(jnp.int32)), PARTS_AXIS
            )
            return new, it + 1, active

        block, iters, _ = jax.lax.while_loop(
            cond, body, (state_blk, jnp.int32(0), jnp.int32(1))
        )
        return block, iters

    return run


def run_pull_until_dist(
    prog: PullProgram,
    spec: ShardSpec,
    arrays: ShardArrays,
    state0: jnp.ndarray,
    max_iters: int,
    active_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    method: str = "auto",
):
    """Convergence-driven distributed pull (CC/SSSP): iterate until the
    global active count (psum over parts) reaches zero.

    active_fn(old_local, new_local) -> scalar active count for this part
    (must be a hashable top-level function, not a per-call lambda, so the
    compiled program can be cached).
    Returns (final stacked state, iterations run).
    """
    from lux_tpu.engine import methods

    method = methods.resolve_sum(method, prog.reduce)
    assert spec.num_parts % mesh.devices.size == 0, (spec.num_parts, mesh.shape)
    arrays = shard_stacked(mesh, arrays)
    state0 = shard_stacked(mesh, state0)
    return _compile_until(prog, mesh, max_iters, active_fn, method)(
        arrays, state0
    )
