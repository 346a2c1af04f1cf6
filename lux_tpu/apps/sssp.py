"""SSSP CLI app (`python -m lux_tpu.apps.sssp`).

Driver parity with sssp/sssp.cc: -start source, convergence-driven loop,
-check triangle-inequality validation, -verbose per-iteration active
counts (the activeNodes/compTime breakdown of sssp_gpu.cu:516-518).
"""
from __future__ import annotations

import sys

import numpy as np

from lux_tpu.apps import common
from lux_tpu.engine import push
from lux_tpu.graph.push_shards import build_push_shards
from lux_tpu.models import sssp as sssp_model
from lux_tpu.utils import preflight
from lux_tpu.utils.config import parse_args
from lux_tpu.utils.timing import IterStats, Timer, report_elapsed


def build_push_app_shards(g, cfg):
    """Push shards for the selected dense-round --exchange strategy (or
    the block-CSR layout when the dense rounds run the Pallas kernel).
    With --distributed and the allgather exchange the shards come back
    placed on the run's mesh, each device holding its own parts
    (``push.place_push_shards``), and the host copies are dropped:
    ``push.run_push`` then runs them on that mesh."""
    if cfg.sort_segments and (
        cfg.exchange != "allgather" or cfg.method == "pallas"
    ):
        raise SystemExit(
            "--sort-segments relays out the allgather dense-round pull "
            "layout; the ring-bucket and block-CSR (pallas) layouts have "
            "their own edge orders"
        )
    if cfg.compact_gather and (
        cfg.exchange != "allgather" or cfg.method == "pallas"
    ):
        raise SystemExit(
            "--compact-gather mirrors the allgather dense-round pull "
            "layout's src_pos; ring and pallas have their own layouts"
        )
    if cfg.method == "pallas":
        if cfg.exchange != "allgather":
            raise SystemExit(
                "--method pallas has its own dense path; it cannot combine "
                "with --exchange ring"
            )
        if not cfg.distributed:
            raise SystemExit(
                "--method pallas (push) runs on a device mesh: add "
                "--distributed (single chip = -ng 1 --distributed)"
            )
        common.require_parts_fit_devices(cfg, "--method pallas")
        from lux_tpu.parallel.pallas_dist import build_push_pallas_shards

        return build_push_pallas_shards(g, cfg.num_parts)
    if cfg.exchange == "ring":
        if not cfg.distributed:
            raise SystemExit("--exchange ring requires --distributed")
        from lux_tpu.parallel.ring import build_push_ring_shards

        return build_push_ring_shards(g, cfg.num_parts)
    shards = build_push_shards(
        g, cfg.num_parts, sort_segments=cfg.sort_segments,
        compact_gather=cfg.compact_gather,
    )
    if not cfg.distributed or cfg.edge_shards > 1:
        return shards
    from lux_tpu.engine import push

    return push.place_push_shards(shards, common.make_mesh_if(cfg))


def _save_frontier_ckpt(cfg, name, shards, carry):
    """One elastic frontier checkpoint from the in-flight carry: global
    state + changed-vertex mask + exact edge counter."""
    from lux_tpu.engine import repartition
    from lux_tpu.utils import checkpoint as ckpt

    state_g = shards.scatter_to_global(np.asarray(carry.state))
    counts = np.asarray(carry.count)
    f_cap = shards.pspec.f_cap
    if counts.max() > f_cap:
        # overflowed queues are truncated; the exact frontier is not
        # recoverable — save the dense superset (min/max relaxation is
        # confluent: extra active vertices cost work, never correctness)
        changed_g = np.ones(shards.spec.nv, bool)
    else:
        changed_g = repartition._changed_mask_from_queues(
            np.asarray(carry.q_vid), counts, f_cap, shards.spec.nv
        )
    ckpt.save_frontier(
        cfg.ckpt_dir, int(carry.it), state_g, changed_g,
        np.asarray(carry.edges), name,
    )


def run_push_checkpointed(prog, shards, cfg, mesh, name: str):
    """Windowed push run with an elastic frontier checkpoint between
    windows (--ckpt-every iterations), resuming from cfg.ckpt_dir when a
    checkpoint exists — any part count / exchange / mesh can resume any
    other's checkpoint (the queues rebuild from the saved changed mask,
    engine.repartition._rebuild_carry).  Returns (stacked_state, iters,
    edges, compute_seconds); compute EXCLUDES the host-side checkpoint
    I/O so reported GTEPS stays an engine number (same contract as
    common.run_fixed_dist_chunked)."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.engine import push, repartition
    from lux_tpu.utils import checkpoint as ckpt
    from lux_tpu.utils.timing import Timer

    nv = shards.spec.nv
    statics, loop = repartition._place_statics(
        prog, shards, mesh, cfg.method, cfg.exchange
    )
    s_g, c_g, e_acc, it0, prev = ckpt.load_resume_frontier(
        cfg.ckpt_dir, name, nv
    )
    if s_g is not None:
        carry = repartition._rebuild_carry(prog, shards, s_g, c_g, it0, e_acc)
        print(f"resumed from {prev} at iteration {it0}")
    else:
        carry = push._init_carry(
            prog, shards.pspec,
            jax.tree.map(jnp.asarray, push.vertex_view(shards.arrays)),
        )
    if mesh is not None:
        carry = push.shard_carry(mesh, carry)
    compute = 0.0
    while int(carry.active) > 0 and int(carry.it) < cfg.max_iters:
        it_stop = min(int(carry.it) + cfg.ckpt_every, cfg.max_iters)
        t = Timer()
        carry = loop(*statics, carry, jnp.int32(it_stop))
        compute += t.stop(carry.state)
        _save_frontier_ckpt(cfg, name, shards, carry)
    return carry.state, int(carry.it), carry.edges, compute


def run_delta_checkpointed(prog, shards, cfg, mesh, name: str):
    """Windowed delta-stepping with elastic checkpoints between windows:
    GLOBAL state + pending mask + exact edge counter + the bucket
    threshold (utils/checkpoint.save_delta).  A resume restacks onto ANY
    part count, single-device or distributed — same contract as the
    frontier checkpoints."""
    import jax
    import jax.numpy as jnp

    from lux_tpu.engine import delta as delta_mod
    from lux_tpu.utils import checkpoint as ckpt
    from lux_tpu.utils.timing import Timer

    # same driver-entry contract as run_push_delta: validate AND resolve
    # the method, so direct callers fail fast instead of deep in the
    # segment kernel with method='auto'
    delta_mod._validate(prog, cfg.delta)
    from lux_tpu.engine import methods

    cfg.method = methods.resolve_sum(cfg.method, prog.reduce)
    spec, pspec = shards.spec, shards.pspec
    arrays = jax.tree.map(jnp.asarray, shards.arrays)
    parrays = jax.tree.map(jnp.asarray, shards.parrays)
    s_g, p_g, e_acc, thr, it0, prev = ckpt.load_resume_delta(
        cfg.ckpt_dir, name, spec.nv
    )
    if s_g is None:
        carry = delta_mod._init_carry(prog, pspec, arrays, cfg.delta)
    else:
        st = jnp.asarray(shards.pull.global_to_stacked(s_g))
        pend = jnp.asarray(shards.pull.global_to_stacked(p_g))
        carry = delta_mod.DeltaCarry(
            st, pend, jnp.int32(thr), jnp.int32(it0),
            jnp.sum(pend.astype(jnp.int32)), jnp.asarray(e_acc),
        )
        print(f"resumed from {prev} at iteration {it0}")
    if mesh is not None:
        from lux_tpu.parallel.mesh import shard_stacked

        arrays = shard_stacked(mesh, arrays)
        parrays = shard_stacked(mesh, parrays)
        carry = delta_mod.DeltaCarry(
            *shard_stacked(mesh, (carry.state, carry.pending)),
            carry.thr, carry.it, carry.active, carry.edges,
        )
        loop = delta_mod._compile_delta_dist(
            prog, mesh, pspec, spec, cfg.method, cfg.delta
        )
    else:
        loop = delta_mod._compile_delta_loop(
            prog, pspec, spec, cfg.method, cfg.delta
        )
    compute = 0.0
    while int(carry.active) > 0 and int(carry.it) < cfg.max_iters:
        it_stop = min(int(carry.it) + cfg.ckpt_every, cfg.max_iters)
        t = Timer()
        carry = loop(arrays, parrays, carry, jnp.int32(it_stop))
        compute += t.stop(carry.state)
        ckpt.save_delta(
            cfg.ckpt_dir, int(carry.it),
            shards.scatter_to_global(np.asarray(carry.state)),
            shards.scatter_to_global(np.asarray(carry.pending)),
            np.asarray(carry.edges), int(carry.thr), name,
        )
    return carry.state, int(carry.it), carry.edges, compute


def run_convergence_app(prog, shards, cfg, name: str, g=None):
    """Shared driver for frontier apps (SSSP + CC).  Returns
    (global_state, stacked_device_state, effective_shards, elapsed_s) —
    the shard layout can change mid-run under --repartition-every, so
    validation must use the returned layout, not the one passed in."""
    from lux_tpu.engine import methods

    was_auto = cfg.method == "auto"
    cfg.method = methods.resolve_sum(cfg.method, prog.reduce)
    if cfg.exchange == "ring":
        common._downgrade_scan_family(cfg, was_auto, prog, "--exchange ring")
    common.resolve_route_auto(cfg)
    if (getattr(cfg, "route_gather", "") == "expand-pf"
            and cfg.exchange == "ring"):
        common.downgrade_pf(cfg, "the ring exchange")
    if getattr(cfg, "route_gather", "") and (
            cfg.ckpt_every or cfg.repartition_every
            or cfg.verbose or cfg.method == "pallas"
            or (cfg.exchange == "ring" and not cfg.distributed)
            or cfg.exchange not in ("allgather", "ring")
            or cfg.compact_gather
            or (cfg.distributed and getattr(cfg, "delta", 0))):
        raise SystemExit(
            "--route-gather on push apps routes the allgather dense "
            "rounds (single-device or --distributed; composes with "
            "single-device --delta) and the distributed ring dense "
            "rounds; it cannot combine with checkpointing/"
            "--repartition-every/-verbose/--method pallas/"
            "--compact-gather"
        )
    if cfg.method in ("cumsum", "mxsum"):
        raise SystemExit(
            f"--method {cfg.method} is a prefix-diff strategy: sum-reduce "
            f"programs only (this app reduces with {prog.reduce})"
        )
    if cfg.method == "pallas":
        if cfg.verbose or cfg.repartition_every:
            raise SystemExit(
                "--method pallas: -verbose/--repartition-every are not "
                "wired to the kernel path; use --method scan/scatter"
            )
    if cfg.ckpt_every or cfg.ckpt_dir:
        if not (cfg.ckpt_every and cfg.ckpt_dir):
            raise SystemExit(
                "frontier-app checkpointing runs in windows: pass BOTH "
                "--ckpt-dir and --ckpt-every"
            )
        if cfg.verbose or cfg.repartition_every or cfg.method == "pallas":
            raise SystemExit(
                "--ckpt-every (frontier apps) is a windowed driver; it "
                "does not combine with -verbose, --repartition-every, or "
                "--method pallas"
            )
    if cfg.repartition_every:
        if cfg.repartition_every < 0:
            raise SystemExit("--repartition-every must be positive")
        if cfg.verbose:
            raise SystemExit(
                "--repartition-every runs the engine in windows; the "
                "per-iteration -verbose fence is not available"
            )
    if getattr(cfg, "delta", 0):
        if cfg.delta < 0:
            raise SystemExit("--delta must be positive")
        if not getattr(cfg, "weighted", False):
            raise SystemExit(
                "--delta orders WEIGHTED distances into buckets; "
                "unweighted BFS already expands one hop-bucket per "
                "iteration — add --weighted"
            )
        if (cfg.exchange != "allgather" or cfg.method == "pallas"
                or cfg.verbose or cfg.repartition_every):
            raise SystemExit(
                "--delta is the allgather bucketed driver (single-device "
                "or --distributed; --ckpt-every composes): it does not "
                "combine with --exchange ring/--method pallas/-verbose/"
                "--repartition-every"
            )
    if cfg.method == "pallas":
        est = preflight.estimate_push_pallas(
            shards.spec, shards.pspec, shards.pl.e_src_pos.shape[1],
            shards.t_chunk,
        )
    elif cfg.exchange == "ring":
        est = preflight.estimate_push_ring(
            shards.spec, shards.pspec, shards.e_bucket_pad
        )
    else:
        est = preflight.estimate_push(shards.spec, shards.pspec)
    est = preflight.scale_residency(est, common._residency(cfg))
    if getattr(cfg, "route_gather", ""):
        # the dense rounds' routed plan is a real per-part HBM slice
        est = preflight.add_routed_bytes(
            est,
            preflight.routed_plan_bytes_analytic(shards.spec, "expand")
            * common._residency(cfg),
        )
    print(est)
    preflight.check_fits(est)
    mesh = common.make_mesh_if(cfg)

    from lux_tpu.utils import profiling

    ckpt_compute = None
    with profiling.trace(cfg.profile_dir):
        # ONE plan computation for every routed branch — built outside
        # the timed region.  The ring exchange plans per-bucket; every
        # other branch plans on the pull layout (common.build_push_route).
        route = common.build_push_route(cfg, shards)

        timer = Timer()
        if cfg.ckpt_every and getattr(cfg, "delta", 0):
            state, iters, edges, ckpt_compute = run_delta_checkpointed(
                prog, shards, cfg, mesh, name
            )
        elif cfg.ckpt_every:
            state, iters, edges, ckpt_compute = run_push_checkpointed(
                prog, shards, cfg, mesh, name
            )
        elif cfg.repartition_every:
            from lux_tpu.engine import repartition

            def note(it, old_cuts, new_cuts, work):
                moved = int(np.abs(new_cuts - old_cuts).max())
                print(
                    f"iter {it}: repartition (imbalance "
                    f"{repartition.imbalance(work):.2f}, max boundary "
                    f"move {moved} vertices)"
                )

            res = repartition.run_push_adaptive(
                prog, g, cfg.num_parts, chunk=cfg.repartition_every,
                threshold=cfg.repartition_threshold,
                max_iters=cfg.max_iters, method=cfg.method, mesh=mesh,
                on_repartition=note, shards=shards, exchange=cfg.exchange,
                sort_segments=cfg.sort_segments,
                compact_gather=cfg.compact_gather,
            )
            state, iters, edges = res.stacked, res.iters, res.edges
            shards = res.shards
            print(f"{res.reparts} repartition(s)")
        elif cfg.verbose and mesh is None:
            arrays, parrays, carry = push.push_init(prog, shards)
            load, comp, update = push.compile_push_phases(
                prog, shards.pspec, shards.spec, cfg.method
            )
            stats = IterStats(verbose=True)
            it = 0
            while int(carry.active) > 0 and it < cfg.max_iters:
                t = Timer()
                plan = load(parrays, carry)
                lt = t.stop(plan)
                t = Timer()
                new = comp(arrays, parrays, carry, plan)
                ct = t.stop(new)
                t = Timer()
                carry = update(arrays, carry, new, plan)
                ut = t.stop(carry)
                stats.record_phases(it, int(carry.active), lt, ct, ut)
                it += 1
            state, iters, edges = carry.state, it, carry.edges
        elif cfg.verbose and cfg.exchange == "allgather":
            # step-wise DISTRIBUTED observability with the SAME 3-phase
            # load/comp/update fence as the single-device split — the
            # reference prints per-GPU loadTime/compTime/updateTime on
            # multi-GPU runs too (sssp_gpu.cu:513-518)
            arrays, parrays, carry = push.push_init_dist(prog, shards, mesh)
            load, comp, update = push.compile_push_phases_dist(
                prog, mesh, shards.pspec, shards.spec, cfg.method
            )
            stats = IterStats(verbose=True)
            it = 0
            while int(carry.active) > 0 and it < cfg.max_iters:
                t = Timer()
                plan = load(parrays, carry)
                lt = t.stop(plan)
                t = Timer()
                new = comp(arrays, parrays, carry, plan)
                ct = t.stop(new)
                t = Timer()
                carry = update(arrays, carry, new, plan)
                ut = t.stop(carry)
                stats.record_phases(it, int(carry.active), lt, ct, ut)
                it += 1
            state, iters, edges = carry.state, it, carry.edges
        elif cfg.method == "pallas":
            import jax

            from lux_tpu.parallel import pallas_dist as pd

            # interpret mode off-TPU so CPU smoke runs work; Mosaic on chip
            interp = jax.devices()[0].platform == "cpu"
            state, iters, edges = pd.run_push_pallas_dist(
                prog, shards, mesh, cfg.max_iters, interpret=interp
            )
        elif getattr(cfg, "delta", 0):
            from lux_tpu.engine import delta as delta_mod

            if mesh is None:
                state, iters, edges = delta_mod.run_push_delta(
                    prog, shards, cfg.delta, cfg.max_iters, cfg.method,
                    route=route
                )
            else:
                state, iters, edges = delta_mod.run_push_delta_dist(
                    prog, shards, cfg.delta, mesh, cfg.max_iters,
                    cfg.method
                )
        elif mesh is None:
            state, iters, edges = push.run_push(
                prog, shards, cfg.max_iters, cfg.method, route=route
            )
        elif cfg.exchange == "ring":
            if cfg.verbose:
                print(
                    "note: -verbose per-iteration stepping is an "
                    "allgather-exchange mode; ring runs fused on device"
                )
            state, iters, edges = push.run_push_ring(
                prog, shards, mesh, cfg.max_iters, cfg.method, route=route
            )
        else:
            state, iters, edges = push.run_push_dist(
                prog, shards, mesh, cfg.max_iters, cfg.method, route=route
            )
        elapsed = timer.stop(state)
    if ckpt_compute is not None:
        # checkpoint I/O (device_get + disk) is not engine time
        elapsed = ckpt_compute
    iters = int(iters)
    print(f"{name} converged in {iters} iterations")
    # GTEPS on edges ACTUALLY traversed (dense rounds walk every edge,
    # sparse rounds only the frontier's) — the reference's per-iteration
    # traversal accounting, SURVEY.md §6.
    report_elapsed(elapsed, shards.spec.ne, iters, traversed=push.edges_total(edges))
    # return the stacked device state too: distributed -check validates it
    # on device (CHECK_TASK_ID analog) without a host gather
    return (shards.scatter_to_global(np.asarray(state)), state, shards,
            elapsed)


def main(argv=None):
    cfg = parse_args(argv, description=__doc__, sssp=True, push=True,
                     serve=True)
    g = common.load_graph(cfg, weighted=cfg.weighted)
    if cfg.serve:
        # batched multi-source query service (lux_tpu.serve): warm
        # Q-bucket engines + micro-batching scheduler; one JSON metrics
        # line instead of the one-shot GTEPS report
        from lux_tpu.serve.driver import run_serve_cli

        return run_serve_cli(cfg, g, "sssp")
    if cfg.weighted and not np.issubdtype(g.weights.dtype, np.integer):
        # same contract the sssp() library entry enforces: int costs
        # (reference WeightType=int); silent truncation would corrupt
        # distances AND the -check oracle consistently
        raise SystemExit(
            "weighted SSSP uses integer edge costs; got dtype "
            + str(g.weights.dtype)
        )
    if cfg.delta and cfg.weighted and int(g.weights.min()) < 0:
        raise SystemExit("--delta needs non-negative edge weights "
                         "(bucket order breaks under negative costs)")
    shards = build_push_app_shards(g, cfg)
    cls = (
        sssp_model.WeightedSSSPProgram if cfg.weighted
        else sssp_model.SSSPProgram
    )
    prog = cls(nv=shards.spec.nv, start=cfg.start)
    dist_result, state, shards, elapsed = run_convergence_app(
        prog, shards, cfg, "sssp", g=g
    )
    reached = int(np.sum(dist_result < prog.inf))
    print(f"reached {reached}/{g.nv} vertices from {cfg.start}")
    ok = True
    if cfg.check:
        if cfg.distributed:
            # on-device edge walk over the sharded state — validates graphs
            # too large for a host gather (the reference's CHECK_TASK_ID
            # GPU task, core/graph.h:46 + sssp_gpu.cu:773-798)
            from lux_tpu.engine import validate

            violations = validate.count_violations(
                shards.pull, state,
                validate.sssp_violation(prog.inf, weighted=cfg.weighted),
            )
        else:
            violations = sssp_model.check_distances(
                g, dist_result, weighted=cfg.weighted
            )
        ok = common.print_check("sssp", violations)
    return common.AppResult(0 if ok else 1, dist_result, elapsed, state)


if __name__ == "__main__":
    sys.exit(main())
