"""The pull (gather) execution engine.

TPU-native equivalent of the reference pull model (core/pull_model.inl +
per-app `pull_app_task_impl` kernels): every iteration, each part reads the
WHOLE previous vertex state and writes only its own contiguous slice
(region contract at core/pull_model.inl:454-469).  Here that contract is:

    full_state  = all parts' padded states, concatenated -> (P*V, ...)
    local_state = this part's padded slice                -> (V, ...)

and one iteration per part is

    gather src states -> per-edge values -> segmented reduce by dst -> apply.

Apps plug in as `PullProgram`s (a gather-apply vertex program).  The engine
provides single-device execution (vmap over parts); the multi-chip driver
(lux_tpu.parallel.dist) reuses the same per-part step inside shard_map with
`all_gather` supplying full_state over ICI.

Iteration pipelining: the reference keeps 4 speculative iterations in flight
through Legion futures (SLIDING_WINDOW, sssp/app.h:20) to hide host latency.
On TPU the entire loop lives on-device in `lax.fori_loop` /
`lax.while_loop` (convergence via summed active counts), so there is no host
round-trip to hide at all.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Protocol

import jax
import jax.numpy as jnp

from lux_tpu import obs
from lux_tpu.engine import methods
from lux_tpu.graph.shards import ShardArrays, ShardSpec
from lux_tpu.obs import ring as obs_ring
from lux_tpu.ops import segment


class PullProgram(Protocol):
    """A gather-apply vertex program (the app contract, analog of the
    compile-time app.h + kernel pair in the reference)."""

    #: "sum" | "min" | "max" — the per-destination combiner.
    reduce: str

    def init_state(self, global_vid: jnp.ndarray, degree: jnp.ndarray,
                   vtx_mask: jnp.ndarray) -> Any:
        """Per-vertex initial state for one part (padded slots included)."""
        ...

    def edge_value(self, src_state: jnp.ndarray, weight: jnp.ndarray,
                   dst_state: jnp.ndarray = None) -> jnp.ndarray:
        """Per-edge value from the gathered source state (and weight).
        ``dst_state`` is the destination's CURRENT state gathered per edge
        (needed by CF's error term; unused gathers are DCE'd by XLA)."""
        ...

    def apply(self, old_local: jnp.ndarray, acc: jnp.ndarray,
              arrays: ShardArrays) -> jnp.ndarray:
        """New local state from the old state and the reduced per-dst acc."""
        ...


_REDUCERS: dict[str, Callable] = segment.reducers()


def _route_interpret() -> bool:
    """Pallas interpret mode off-chip (CPU tests / virtual meshes)."""
    return jax.default_backend() == "cpu"


def _dst_gather(arrays: ShardArrays, local_state: jnp.ndarray):
    """Per-edge destination-state read (sentinel-clipped), shared by the
    direct and routed LOAD paths so the padding convention can't drift."""
    return local_state[jnp.clip(arrays.dst_local, 0, local_state.shape[0] - 1)]


def pull_gather_part_routed(arrays: ShardArrays, full_state: jnp.ndarray,
                            local_state: jnp.ndarray, route_static,
                            route_arrays, interpret: bool):
    """LOAD phase via the routed expand (ops/expand.py): the per-edge
    state read as Benes lane shuffles instead of a flat XLA gather —
    bitwise equal on real edge slots (padding junk is only ever read
    through row_ptr / the dst_local sentinel, same as the direct
    layout's state[0] reads there)."""
    from lux_tpu.ops import expand

    src_state = expand.apply_expand(full_state, route_static, route_arrays,
                                    interpret=interpret)
    return src_state, _dst_gather(arrays, local_state)


def pull_gather_part(arrays: ShardArrays, full_state: jnp.ndarray,
                     local_state: jnp.ndarray):
    """LOAD phase for ONE part: the per-edge (src, dst) state gather —
    the replicated-state read the reference's load_kernel does ZC→FB
    (pagerank_gpu.cu:34-47).  Shared by the fused step and the -verbose
    phase split (single-device AND distributed) so the phase boundary
    can never drift from the fused math.

    With the compact-gather layout (nonzero mirror width — a STATIC
    shape, so this branch resolves at trace time), the per-edge read is
    two-stage like the reference's load_kernel: one O(U) ascending
    gather fills the part's unique-in-source mirror, then the O(E)
    per-edge gather indexes the U-sized mirror instead of the (P*V,)
    state.  mirror_pos[mirror_rel] == src_pos exactly, so results are
    bitwise identical to the direct layout."""
    if arrays.mirror_pos.shape[-1] > 0:
        mirror = full_state[arrays.mirror_pos]  # (U, ...) compact stage
        src_state = mirror[arrays.mirror_rel]   # (E, ...) from U, not P*V
    else:
        src_state = full_state[arrays.src_pos]  # (E, ...) direct gather
    return src_state, _dst_gather(arrays, local_state)


def pull_reduce_part(prog: PullProgram, arrays: ShardArrays, gath,
                     method: str, del_val=None):
    """COMP phase for ONE part: per-edge values + segmented reduce by
    destination (the pr_kernel hot loop, pagerank_gpu.cu:49-102).
    ``del_val`` (the mutation overlay's tombstone mask,
    lux_tpu.mutate.overlay) neutralizes deleted base edges' VALUES —
    the base arrays and the reduce itself run unchanged, so the overlay
    never retraces (LUX-J1)."""
    src_state, dst_state = gath
    # the edge value fuses into the gather's kernel: its time is the
    # gather's
    with jax.named_scope("pull.gather"):
        vals = prog.edge_value(src_state, arrays.weights, dst_state)
        if del_val is not None:
            from lux_tpu.mutate import overlay as _ovl

            vals = _ovl.mask_deleted(vals, del_val, prog.reduce)
    with jax.named_scope("pull.reduce"):
        return _REDUCERS[prog.reduce](
            vals, arrays.row_ptr, arrays.head_flag, arrays.dst_local,
            method=method)


def local_pull_step(
    prog: PullProgram,
    arrays: ShardArrays,
    full_state: jnp.ndarray,
    local_state: jnp.ndarray,
    method: str = "scan",
    route=None,
    interpret: bool = False,
    overlay=None,
) -> jnp.ndarray:
    """One pull iteration for ONE part.  ``full_state`` is the (P*V, ...)
    concatenated padded state of all parts; ``local_state`` is (V, ...).
    ``route`` = (ExpandStatic, per-part arrays) switches the LOAD phase
    to the routed-shuffle expand; (FusedStatic, arrays) replaces BOTH
    the load and the segmented reduce with the fused routed pipeline
    (ops/expand.apply_fused — dst-state-independent programs only).
    ``overlay`` = (OverlayStatic, this part's OverlayArrays): the
    dynamic-graph mutation overlay (lux_tpu.mutate) — tombstoned base
    edges neutralize, then the fixed-capacity insert buffer gathers D
    extra source states and scatter-combines them into the accumulator
    BEFORE apply.  Static shapes throughout: churn never retraces.
    Overlays compose with the expand AND fused-pf/fused-mx routes (the
    fused families tombstone in group space through the plan's gslot
    route, apply_fused ``del_val=``); only the CF route remains
    overlay-free (mutate.overlay.FUSED_OVERLAY_NOTE)."""
    from lux_tpu.ops import expand

    if overlay is not None and route is not None and isinstance(
            route[0], expand.CFRouteStatic):
        from lux_tpu.mutate.overlay import FUSED_OVERLAY_NOTE

        raise ValueError(FUSED_OVERLAY_NOTE)
    if route is not None and isinstance(route[0], expand.FusedStatic):
        assert route[0].reduce == prog.reduce, (
            f"fused plan was built for reduce={route[0].reduce!r} but the "
            f"program reduces with {prog.reduce!r}")
        # one kernel gathers and reduces: its time is the reduce's
        with jax.named_scope("pull.reduce"):
            acc = expand.apply_fused(
                full_state, route[0], route[1],
                edge_value=lambda s, w: prog.edge_value(s, w, None),
                interpret=interpret,
                del_val=overlay[1].del_val if overlay is not None else None)
    else:
        with jax.named_scope("pull.gather"):
            if route is not None and isinstance(route[0],
                                                expand.CFRouteStatic):
                gath = expand.apply_cf_route(full_state, local_state,
                                             route[0], route[1],
                                             interpret=interpret)
            elif route is not None:
                gath = pull_gather_part_routed(arrays, full_state,
                                               local_state, route[0],
                                               route[1], interpret)
            else:
                gath = pull_gather_part(arrays, full_state, local_state)
        acc = pull_reduce_part(
            prog, arrays, gath, method,
            del_val=overlay[1].del_val if overlay is not None else None)
    with jax.named_scope("pull.apply"):
        if overlay is not None:
            from lux_tpu.mutate import overlay as _ovl

            acc = _ovl.delta_scatter(
                acc, full_state, overlay[1],
                lambda s, w: prog.edge_value(s, w, None), prog.reduce)
        return prog.apply(local_state, acc, arrays)


def init_state(prog: PullProgram, arrays: ShardArrays) -> jnp.ndarray:
    """Stacked (P, V, ...) initial state via vmap over parts."""
    return jax.vmap(prog.init_state)(
        jnp.asarray(arrays.global_vid),
        jnp.asarray(arrays.degree),
        jnp.asarray(arrays.vtx_mask),
    )


def _pull_iteration(prog, spec: ShardSpec, method, arrays, state,
                    route_static=None, route_arrays=None,
                    interpret: bool = False, ostatic=None, oarrays=None):
    """One pull iteration over the whole (P, V, ...) shard stack.
    ``ostatic``/``oarrays`` carry the mutation overlay (static half as
    a jit static, arrays vmapped with the shards)."""
    full = state.reshape((spec.gathered_size,) + state.shape[2:])

    def step(arr, loc, ra=None, oa=None):
        return local_pull_step(
            prog, arr, full, loc, method,
            route=(route_static, ra) if route_static is not None else None,
            interpret=interpret,
            overlay=(ostatic, oa) if ostatic is not None else None)

    if route_static is None and ostatic is None:
        return jax.vmap(lambda arr, loc: step(arr, loc))(arrays, state)
    if route_static is None:
        return jax.vmap(
            lambda arr, loc, oa: step(arr, loc, oa=oa)
        )(arrays, state, oarrays)
    if ostatic is None:
        return jax.vmap(
            lambda arr, loc, ra: step(arr, loc, ra=ra)
        )(arrays, state, route_arrays)
    return jax.vmap(
        lambda arr, loc, ra, oa: step(arr, loc, ra=ra, oa=oa)
    )(arrays, state, route_arrays, oarrays)


def compile_pull_step(prog: PullProgram, spec: ShardSpec, method: str = "auto",
                      route=None):
    """Jitted SINGLE pull iteration over the whole shard stack (verbose
    mode / step-wise drivers).  The state buffer is donated — the ping-pong
    double buffer of the reference (dist_lr[2], core/graph.h:83) without
    holding both copies.  ``route``: a routed-pull plan; its device-
    placed arrays are bound as ordinary jit arguments (already-on-device
    operands cost nothing per call — baking them in as closure constants
    would bloat the lowered module instead)."""
    method = methods.resolve_sum(method, prog.reduce)
    rs, ra = route if route is not None else (None, None)
    interpret = _route_interpret()
    if ra is None:

        @partial(jax.jit, donate_argnums=1)
        def step(arrays, state):
            return _pull_iteration(prog, spec, method, arrays, state)

        return step
    ra = jax.tree.map(jnp.asarray, ra)

    @partial(jax.jit, donate_argnums=1)
    def routed_step(arrays, state, route_arrays):
        return _pull_iteration(prog, spec, method, arrays, state,
                               rs, route_arrays, interpret)

    return lambda arrays, state: routed_step(arrays, state, ra)


def compile_pull_phases(prog: PullProgram, spec: ShardSpec, method: str = "auto"):
    """One pull iteration as THREE separately-jitted, fence-able sub-steps
    — the per-phase observability of the reference's -verbose kernel timers
    (loadTime/compTime/updateTime, sssp_gpu.cu:513-518):

      load(arrays, state)          -> per-edge gathered (src, dst) states
                                      (the replicated-state HBM read phase)
      comp(arrays, gath)           -> per-destination reduced accumulators
                                      (edge_value + segmented reduction)
      update(arrays, state, acc)   -> new state (apply)

    Fencing between phases costs dispatch latency and blocks cross-phase
    fusion — this is the observability path; run_pull_fixed is the perf
    path.  Returns (load, comp, update).
    """
    method = methods.resolve_sum(method, prog.reduce)

    @jax.jit
    def load(arrays, state):
        full = state.reshape((spec.gathered_size,) + state.shape[2:])
        return jax.vmap(
            lambda arr, loc: pull_gather_part(arr, full, loc)
        )(arrays, state)

    @jax.jit
    def comp(arrays, gathered):
        return jax.vmap(
            lambda arr, gath: pull_reduce_part(prog, arr, gath, method)
        )(arrays, gathered)

    @partial(jax.jit, donate_argnums=1)
    def update(arrays, state, acc):
        return jax.vmap(lambda arr, local, a: prog.apply(local, a, arr))(
            arrays, state, acc
        )

    return load, comp, update


def _pull_fixed_fn(prog, spec, num_iters, method, arrays, state0,
                   ring=None, route_static=None, route_arrays=None,
                   interpret=False, ostatic=None, oarrays=None):
    def body(_, state):
        return _pull_iteration(prog, spec, method, arrays, state,
                               route_static, route_arrays, interpret,
                               ostatic, oarrays)

    if ring is None:
        return jax.lax.fori_loop(0, num_iters, body, state0)

    # telemetry twin: the ring rides the SAME fori carry (static shapes,
    # LUX-J1) and the state math is untouched — bitwise-identical
    # results, one extra O(P*V) residual reduction per iteration against
    # the O(E) gather work (obs/ring.py; the l1 residual is the
    # convergence curve for the fixed-iteration apps)
    def body_t(i, carry):
        state, rg = carry
        new = body(i, state)
        resid = jnp.sum(jnp.abs(new.astype(jnp.float32)
                                - state.astype(jnp.float32)))
        return new, obs_ring.ring_push(rg, i, resid)

    return jax.lax.fori_loop(0, num_iters, body_t, (state0, ring))


_PULL_FIXED_STATICS = ("prog", "spec", "num_iters", "method",
                       "route_static", "interpret", "ostatic")
_pull_fixed_jit = jax.jit(_pull_fixed_fn,
                          static_argnames=_PULL_FIXED_STATICS)
#: donating twin: state0 (positional 5) is consumed, so the loop's
#: ping-pong can reuse its HBM buffer instead of holding TWO full state
#: copies for the whole run (the reference's dist_lr[2] double buffer,
#: core/graph.h:83, without the second copy).  Opt-in via ``donate=``:
#: benchmark timing loops re-run from one s0 and must keep it alive.
#: The telemetry ring (positional 6) is donated WITH the state: it is
#: pure loop carry, so its input buffer is dead the moment the loop
#: starts (None when telemetry is off — an empty pytree donates
#: nothing; luxaudit LUX-J2 audits both aliases).
_pull_fixed_jit_donate = jax.jit(_pull_fixed_fn,
                                 static_argnames=_PULL_FIXED_STATICS,
                                 donate_argnums=(5, 6))


def run_pull_fixed(
    prog: PullProgram,
    spec: ShardSpec,
    arrays: ShardArrays,
    state0: jnp.ndarray,
    num_iters: int,
    method: str = "auto",
    route=None,
    donate: bool = False,
    telemetry=None,
    overlay=None,
):
    """Single-device driver: fixed iteration count (PageRank/CF style,
    pagerank/pagerank.cc:109-114).  Whole loop stays on device; the
    compiled program is cached on (prog, spec, num_iters, method).
    ``method="auto"`` resolves to the platform's measured winner
    (engine.methods).  ``route`` (from ops.expand.plan_expand_shards)
    switches the LOAD phase to the routed-shuffle expand — bitwise-equal
    results, measured ~15 HBM-bandwidth passes instead of an E-sized
    scalar-issue-bound flat gather (a pass-fused ``pf=True`` plan cuts
    that to ~7, same bits).  ``donate=True`` donates ``state0`` to the
    loop (jit donate_argnums) so the hot loop holds ONE full state copy
    in HBM instead of two — the caller must not reuse ``state0`` after.
    ``telemetry`` (an ``obs.ring.new_ring("pull_fixed")``) carries the
    per-iteration residual curve in the loop carry — results stay
    bitwise-identical, the return becomes (state, ring), and a donating
    run consumes the ring with the state.
    ``overlay`` ((OverlayStatic, OverlayArrays) from
    lux_tpu.mutate.overlay) runs the step against the mutating graph:
    base gathers unchanged, tombstones neutralized, the fixed-capacity
    insert buffer folded in per iteration — occupancy is data, so
    churn never recompiles (luxaudit LUX-J1 pins it).
    Span ``pull.dispatch`` around the call; counters ``pull.iterations``
    and ``pull.mxscan_iterations`` (iterations whose reduce runs the
    blocked scan kernel).
    Returns the final stacked (P, V, ...) state.
    """
    method = methods.resolve_sum(method, prog.reduce)
    arrays = jax.tree.map(jnp.asarray, arrays)
    rs, ra = route if route is not None else (None, None)
    if ra is not None:
        ra = jax.tree.map(jnp.asarray, ra)
    os_, oa = overlay if overlay is not None else (None, None)
    if oa is not None:
        oa = jax.tree.map(jnp.asarray, oa)
    tel = telemetry
    if tel is not None:
        tel = jax.tree.map(jnp.asarray, tel)
    fn = _pull_fixed_jit_donate if donate else _pull_fixed_jit
    obs.count("pull.iterations", num_iters)
    from lux_tpu.ops import expand

    kernel = (not isinstance(rs, expand.FusedStatic)
              and segment.csc_method(method, state0.ndim - 1) == "mxscan")
    obs.count("pull.mxscan_iterations", num_iters if kernel else 0)
    with obs.span("pull.dispatch"):
        return fn(prog, spec, num_iters, method, arrays, state0, tel,
                  route_static=rs, route_arrays=ra,
                  interpret=_route_interpret(), ostatic=os_, oarrays=oa)


def run_pull_fixed_overlapped(
    prog: PullProgram,
    spec: ShardSpec,
    arrays: ShardArrays,
    state0: jnp.ndarray,
    num_iters: int,
    method: str = "auto",
    route_future=None,
    chunk: int = 1,
):
    """run_pull_fixed that PIPELINES routed-plan construction with the
    first iterations: while ``route_future`` (an ops.expand.PlanFuture)
    is still building on the host, iterations run in ``chunk``-sized
    direct-gather windows; the moment the plan resolves, the remaining
    iterations run routed.  The routed expand (and the CF route) is
    bitwise-equal to the direct gather, so the handover point cannot
    change the result.  Fused plans change the reduce ASSOCIATION, so a
    mid-run handover would mix two deterministic orders: a fused future
    that is already resolved at entry runs fused from iteration 0 (the
    normal fused semantics); one that resolves mid-run finishes the
    remaining iterations DIRECT instead — completed device work is
    never discarded, and the result is exactly the direct engine's.

    This is the time-to-first-iteration fix for cold plan caches
    (VERDICT r5 #6): an engine no longer stalls ~90 s/part at 2^24
    before its first dense round.  Returns (final_state, routed_iters)
    — routed_iters counts how many iterations actually ran routed, so
    drivers can report the overlap honestly.  Compile note: each
    distinct handover residual (num_iters - done) is a separate jit
    static; a driver calls this once per run, and repeat processes hit
    the persistent XLA compile cache, so the program-cache growth is
    bounded in practice.
    """
    from lux_tpu.ops import expand

    if route_future is None:
        return run_pull_fixed(prog, spec, arrays, state0, num_iters,
                              method), 0
    if route_future.ready():
        route = route_future.result()
        return run_pull_fixed(prog, spec, arrays, state0, num_iters,
                              method, route=route), num_iters
    state = state0
    done = 0
    while done < num_iters and not route_future.ready():
        k = min(chunk, num_iters - done)
        # per-chunk flight-recorder span (host-side, OUTSIDE the
        # compiled loop — the block_until_ready below is the handover
        # race's own fence, not a telemetry one)
        with obs.span("pull.chunk", k=k, done=done, routed=False):
            # chunks after the first own their input state (the previous
            # chunk's output) — donate it so the handover loop never
            # holds two full state copies; the caller's state0 itself
            # stays alive
            state = run_pull_fixed(prog, spec, arrays, state, k, method,
                                   donate=done > 0)
            # materialize before re-polling: dispatch is async, so
            # without a sync the loop would queue every chunk before the
            # future could ever win the race
            jax.block_until_ready(state)
        done += k
    if done >= num_iters:
        return state, 0
    route = route_future.result()
    if isinstance(route[0], expand.FusedStatic):
        # mixing associations mid-run is invalid; the direct result IS a
        # valid deterministic answer, so finish direct rather than throw
        # away the iterations already computed
        with obs.span("pull.chunk", k=num_iters - done, done=done,
                      routed=False, fused_skip=True):
            state = run_pull_fixed(prog, spec, arrays, state,
                                   num_iters - done, method,
                                   donate=done > 0)
        return state, 0
    with obs.span("pull.chunk", k=num_iters - done, done=done,
                  routed=True):
        state = run_pull_fixed(prog, spec, arrays, state, num_iters - done,
                               method, route=route, donate=done > 0)
    return state, num_iters - done


def run_pull_until(
    prog: PullProgram,
    spec: ShardSpec,
    arrays: ShardArrays,
    state0: jnp.ndarray,
    max_iters: int,
    active_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    method: str = "auto",
    route=None,
    donate: bool = False,
    telemetry=None,
    overlay=None,
):
    """Single-device driver: iterate until no vertex is active (the push-app
    convergence contract — total active count == 0, sssp/sssp.cc:115-129 —
    but with the test on-device instead of 4 iterations behind on the host).

    active_fn(old_stacked, new_stacked) -> per-part active counts (P,);
    pass a top-level function (hashable) so the compiled loop caches.
    ``donate=True`` consumes ``state0`` (see run_pull_fixed).
    ``telemetry`` (``obs.ring.new_ring("pull_until")``) records the
    per-iteration active count in the loop carry (bitwise no-op on the
    state; the return becomes (state, iters, ring)).  ``overlay`` runs
    against the mutating graph (see run_pull_fixed) — this is the
    incremental-refresh entry point (lux_tpu.mutate.refresh): warm
    state in, iterate the overlay step until quiescent.
    Returns (final_state, num_iters_run).
    """
    method = methods.resolve_sum(method, prog.reduce)
    arrays = jax.tree.map(jnp.asarray, arrays)
    rs, ra = route if route is not None else (None, None)
    if ra is not None:
        ra = jax.tree.map(jnp.asarray, ra)
    os_, oa = overlay if overlay is not None else (None, None)
    if oa is not None:
        oa = jax.tree.map(jnp.asarray, oa)
    tel = telemetry
    if tel is not None:
        tel = jax.tree.map(jnp.asarray, tel)
    fn = _pull_until_jit_donate if donate else _pull_until_jit
    return fn(prog, spec, max_iters, active_fn, method, arrays,
              state0, tel, route_static=rs, route_arrays=ra,
              interpret=_route_interpret(), ostatic=os_, oarrays=oa)


def _pull_until_fn(prog, spec, max_iters, active_fn, method, arrays, state0,
                   ring=None, route_static=None, route_arrays=None,
                   interpret=False, ostatic=None, oarrays=None):
    def cond(carry):
        return (carry[2] > 0) & (carry[1] < max_iters)

    def body(carry):
        state, it = carry[0], carry[1]
        new = _pull_iteration(prog, spec, method, arrays, state,
                              route_static, route_arrays, interpret,
                              ostatic, oarrays)
        active = jnp.sum(active_fn(state, new))
        if ring is None:
            return new, it + 1, active
        # telemetry rides the while carry (static shapes; the recorded
        # active count is the one the convergence test already computes)
        return new, it + 1, active, obs_ring.ring_push(carry[3], it, active)

    init = (state0, jnp.int32(0), jnp.int32(1))
    if ring is not None:
        init = init + (ring,)
    out = jax.lax.while_loop(cond, body, init)
    if ring is None:
        return out[0], out[1]
    return out[0], out[1], out[3]


_PULL_UNTIL_STATICS = ("prog", "spec", "max_iters", "active_fn", "method",
                       "route_static", "interpret", "ostatic")
_pull_until_jit = jax.jit(_pull_until_fn,
                          static_argnames=_PULL_UNTIL_STATICS)
#: donating twin of the convergence loop (state0 = positional 6); the
#: old state is folded into the while carry immediately, so donation
#: frees the input buffer for the loop's ping-pong.  The telemetry ring
#: (positional 7) is carry too and donates alongside (None = no-op).
_pull_until_jit_donate = jax.jit(_pull_until_fn,
                                 static_argnames=_PULL_UNTIL_STATICS,
                                 donate_argnums=(6, 7))
