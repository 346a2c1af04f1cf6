"""Shared app-driver scaffolding (the role of each reference app's
top_level_task: load graph -> preflight -> init -> iterate -> report,
e.g. pagerank/pagerank.cc:32-118)."""
from __future__ import annotations

import logging

import numpy as np

from lux_tpu.graph import generate
from lux_tpu.graph.csc import HostGraph
from lux_tpu.graph.format import read_lux
from lux_tpu.utils.config import RunConfig

log = logging.getLogger("lux_tpu")


class AppResult(int):
    """An app's exit status that also carries its answer.  ``main()``
    returns one, so ``sys.exit(main())`` and ``main() == 0`` keep their
    meaning while an in-process caller reads ``.answer`` (the global
    per-vertex result), ``.elapsed`` (the timed region's seconds, ended
    by block_until_ready) and ``.state`` (the device array the answer
    was gathered from, None where the path keeps none)."""

    answer: np.ndarray
    elapsed: float
    state: object

    def __new__(cls, rc: int, answer: np.ndarray, elapsed: float,
                state=None):
        self = super().__new__(cls, rc)
        self.answer = answer
        self.elapsed = elapsed
        self.state = state
        return self


def load_graph(cfg: RunConfig, weighted: bool = False,
               bipartite: bool = False) -> HostGraph:
    """``weighted`` requires/generates edge weights; ``bipartite`` shapes
    the synthetic graph as a rating graph (CF).  Every app's entry loads
    its graph first, so the compile cache is placed here, before anything
    compiles."""
    from lux_tpu.utils import compile_cache

    compile_cache.enable()
    if cfg.file:
        try:
            g = read_lux(cfg.file)
        except (OSError, ValueError) as e:
            raise SystemExit(f"cannot read {cfg.file}: {e}")
        if weighted and not g.weighted:
            raise SystemExit(f"{cfg.file} has no edge weights")
        log.info("loaded %s: nv=%d ne=%d", cfg.file, g.nv, g.ne)
        return g
    if bipartite:
        n_half = (1 << cfg.rmat_scale) // 2
        g = generate.bipartite_ratings(
            n_half, n_half, (1 << cfg.rmat_scale) * cfg.rmat_ef // 2,
            seed=cfg.seed,
        )
    else:
        g = generate.rmat(
            cfg.rmat_scale, cfg.rmat_ef, seed=cfg.seed, weighted=weighted
        )
    log.info("synthetic graph: nv=%d ne=%d", g.nv, g.ne)
    return g


def make_mesh_if(cfg: RunConfig):
    if not cfg.distributed:
        return None
    if cfg.edge_shards > 1:
        from lux_tpu.parallel.edge2d import make_mesh2d

        return make_mesh2d(cfg.num_parts, cfg.edge_shards)
    from lux_tpu.parallel.mesh import make_mesh_for_parts

    # -ng may exceed the device count: k = parts/mesh-size parts stay
    # resident per device (the reference mapper's slicing analog)
    return make_mesh_for_parts(cfg.num_parts)


def require_parts_fit_devices(cfg: RunConfig, what: str) -> None:
    """One part per device: the pallas engines (pull and push) don't
    support k resident parts (allgather/ring/scatter do)."""
    import jax

    if cfg.num_parts > len(jax.devices()):
        raise SystemExit(
            f"{what} keeps one part per device; -ng must not exceed the "
            f"device count ({len(jax.devices())} available; "
            "allgather/ring/scatter support multiple resident parts per "
            "device)"
        )


_ROUTE_VERBOSE_ERR = (
    "-verbose 3-phase fencing is a direct-gather observability mode; "
    "drop --route-gather or -verbose")


def route_base(rg: str) -> str:
    """Layout family of a --route-gather mode: 'expand-pf'/'fused-pf'/
    'fused-mx' bind the same shard layouts as their base — pass fusion
    (and the mxreduce in-kernel reduction) only changes the device
    kernel grouping (ops/expand.to_pf / plan_fused mx=True), never the
    plan's layout contract."""
    return rg[:-3] if rg.endswith(("-pf", "-mx")) else rg


def route_is_pf(rg: str) -> bool:
    # fused-mx is inherently pass-fused (its prefix groups + the
    # in-kernel reduce group all run the pf kernels)
    return rg.endswith(("-pf", "-mx"))


def route_mx(rg: str):
    """The ``mx`` argument of the fused planners for a --route-gather
    mode: 'fused-mx' plans the MXREDUCE form explicitly; 'fused-pf'
    follows the chip-measured ``tpu:reduce_mode`` winner (None —
    ops/expand.resolve_fused_mx), so a banked mxreduce measurement
    upgrades the pass-fused flag without a code edit; plain 'fused'
    stays the unfused family (False)."""
    if rg == "fused-mx":
        return True
    return None if rg == "fused-pf" else False


def resolve_route_auto(cfg) -> None:
    """Bare ``--route-gather`` (const 'auto') follows the chip-measured
    routed-vs-routed-pf winner (engine/methods.route_mode: overlay
    entry ``tpu:route_mode``, recorded by the default TPU bench race;
    LUX_ROUTE_MODE env override) — an unattended window's measurement
    changes what the bare flag runs without a code edit.  Both modes
    are bitwise-identical, so this is a perf decision only."""
    if getattr(cfg, "route_gather", "") != "auto":
        return
    from lux_tpu.engine import methods

    cfg.route_gather = ("expand-pf" if methods.route_mode() == "routed-pf"
                        else "expand")


def downgrade_pf(cfg, layout: str) -> None:
    """expand-pf -> expand with a stderr note.  Pass-fused plans bind
    the allgather pull layout; pf is a bitwise-identical kernel-grouping
    detail, so layouts that plan per-bucket run the unfused family
    rather than erroring — ONE policy shared by the pull validation and
    the push apps' ring path."""
    import sys

    print(f"# --route-gather expand-pf: {layout} plans per-bucket; "
          "running the unfused 'expand' family (bitwise-identical)",
          file=sys.stderr)
    cfg.route_gather = "expand"


def _downgrade_scan_family(cfg: RunConfig, was_auto: bool, prog,
                           where: str) -> None:
    """An AUTO-resolved csc winner (mxscan, the TPU default of every
    monoid, or a banked ``tpu:sum`` mxsum/mxscan; engine/methods
    .resolve_sum) downgrades to the blanket winner on the bucketed
    exchanges — their drivers run scan/scatter only, exactly like
    segment_reduce_by_ends' own downgrade.  An EXPLICIT --method
    choice is left for the branch's own SystemExit (loud CLI failure,
    never a silent swap)."""
    if was_auto and cfg.method in ("mxsum", "mxscan"):
        import sys

        from lux_tpu.engine import methods

        blanket = methods.resolve("auto", prog.reduce)
        print(f"# --method auto: csc winner {cfg.method} downgraded "
              f"to {blanket} on {where} (bucketed reductions run "
              "scan/scatter)", file=sys.stderr)
        cfg.method = blanket


def validate_exchange(cfg: RunConfig, prog) -> None:
    """Reject incompatible --exchange combinations BEFORE the O(ne) shard
    build, with a CLI-level message (not a deep driver assert).  Resolves
    ``--method auto`` to the platform's measured winner first — through
    ``resolve_sum``, so the csc engines' winner (mxscan on TPU) reaches
    them from the CLI — and every later check (and the run itself) sees
    a concrete strategy."""
    from lux_tpu.engine import methods

    was_auto = cfg.method == "auto"
    cfg.method = methods.resolve_sum(cfg.method, prog.reduce)
    if cfg.method in ("cumsum", "mxsum") and prog.reduce != "sum":
        raise SystemExit(
            f"--method {cfg.method} is a prefix-diff strategy: sum-reduce "
            f"programs only (this app reduces with {prog.reduce})"
        )
    if cfg.method == "pallas":
        if prog.reduce != "sum":
            raise SystemExit(
                "--method pallas: sum-reduce programs only; min/max apps "
                "use scan/scatter"
            )
        if cfg.exchange != "allgather" or cfg.edge_shards > 1:
            raise SystemExit(
                "--method pallas runs on the allgather exchange, 1-D mesh"
            )
        if cfg.distributed:
            require_parts_fit_devices(cfg, "--method pallas")
    # layout relayouts bind to the allgather pull layout's src_pos; check
    # BEFORE the allgather early-return so pallas combos are caught too
    if cfg.sort_segments and (
        cfg.exchange != "allgather" or cfg.edge_shards > 1
        or cfg.feat_shards > 1 or cfg.method == "pallas"
    ):
        raise SystemExit(
            "--sort-segments relays out the allgather pull layout; the "
            "bucket (ring/scatter/edge2d), feat-sharded, and block-CSR "
            "(pallas) layouts have their own edge orders"
        )
    if cfg.compact_gather and (
        cfg.exchange != "allgather" or cfg.edge_shards > 1
        or cfg.feat_shards > 1 or cfg.method == "pallas"
    ):
        raise SystemExit(
            "--compact-gather mirrors the allgather pull layout's "
            "src_pos; the bucket (ring/scatter/edge2d) and feat-sharded "
            "layouts ship their own slices and pallas has its own "
            "block-CSR gather"
        )
    if getattr(cfg, "route_gather", ""):
        resolve_route_auto(cfg)
        if (cfg.route_gather == "expand-pf"
                and (cfg.exchange != "allgather" or cfg.edge_shards > 1
                     or cfg.feat_shards > 1)):
            downgrade_pf(cfg, "this exchange/layout")
        if getattr(prog, "k", 1) > 1 and route_base(cfg.route_gather) == "fused":
            raise SystemExit(
                "--route-gather fused supports scalar vertex state; "
                "colfilter's wide dst-dependent load routes with "
                "--route-gather expand (per-column src + dst plans)"
            )
        # the bucketed / sharded exchanges plan per-bucket and are
        # served by the UNFUSED family only ('expand'); the pass-fused
        # variants bind the allgather pull layout
        bucket_ok = (cfg.exchange in ("ring", "scatter")
                     and cfg.route_gather == "expand"
                     and getattr(prog, "k", 1) == 1)
        feat_ok = (cfg.feat_shards > 1 and cfg.route_gather == "expand"
                   and cfg.exchange == "allgather")
        e2d_ok = (cfg.edge_shards > 1 and cfg.route_gather == "expand"
                  and cfg.exchange == "allgather"
                  and getattr(prog, "k", 1) == 1)
        if ((cfg.exchange != "allgather" and not bucket_ok)
                or (cfg.edge_shards > 1 and not e2d_ok)
                or (cfg.feat_shards > 1 and not feat_ok)
                or cfg.method == "pallas" or cfg.compact_gather
                or cfg.stream_hbm_gib):
            raise SystemExit(
                "--route-gather expand covers every pull layout "
                "(allgather, ring/scatter buckets, edge-sharded chunks, "
                "feat-sharded columns); 'fused' and the pass-fused "
                "'-pf' variants are allgather-only, and no mode "
                "combines with --method pallas/--compact-gather/"
                "--stream-hbm-gib"
            )
        if cfg.verbose:
            raise SystemExit(_ROUTE_VERBOSE_ERR)
        if cfg.ckpt_every and cfg.distributed:
            raise SystemExit(
                "--route-gather with checkpointing is a single-device "
                "stepping mode; the distributed chunked driver runs the "
                "direct gather — drop one of the flags"
            )
    if cfg.feat_shards > 1:
        if getattr(prog, "k", 1) <= 1:
            raise SystemExit(
                "--feat-shards shards a wide (V, K) latent state; this "
                "app's state has no feature dim (colfilter only)"
            )
        if not cfg.distributed:
            raise SystemExit("--feat-shards requires --distributed")
        if cfg.exchange not in ("allgather", "ring") or cfg.edge_shards > 1:
            raise SystemExit(
                "--feat-shards (2-D parts x feat mesh) runs on the "
                "allgather or ring exchange; it cannot combine with "
                "--exchange scatter or --edge-shards"
            )
        if cfg.exchange == "ring":
            _downgrade_scan_family(cfg, was_auto, prog,
                                   "--feat-shards --exchange ring")
        if cfg.exchange == "ring" and cfg.method not in ("scan", "scatter"):
            raise SystemExit(
                "--feat-shards --exchange ring supports --method "
                "scan/scatter (bucketed reductions carry no row_ptr)"
            )
        if cfg.method == "pallas":
            raise SystemExit(
                "--feat-shards supports --method scan/scatter/cumsum/"
                "mxsum (the kernel path has its own distribution)"
            )
        if prog.k % cfg.feat_shards:
            raise SystemExit(
                f"--feat-shards {cfg.feat_shards} must divide the latent "
                f"dim K={prog.k}"
            )
        import jax

        if len(jax.devices()) < cfg.feat_shards:
            # the parts axis shrinks to k-resident layouts, but each feat
            # shard needs its own chip column
            raise SystemExit(
                f"--feat-shards {cfg.feat_shards}: needs at least that "
                f"many devices, {len(jax.devices())} available"
            )
        return
    if cfg.edge_shards > 1:
        if not cfg.distributed:
            raise SystemExit("--edge-shards requires --distributed")
        import jax

        need = cfg.num_parts * cfg.edge_shards
        if len(jax.devices()) < need:
            raise SystemExit(
                f"--edge-shards: {cfg.num_parts} x {cfg.edge_shards} = "
                f"{need} devices needed, {len(jax.devices())} available"
            )
        if cfg.exchange != "allgather":
            raise SystemExit(
                "--edge-shards (2-D mesh) has its own exchange; it cannot "
                "combine with --exchange ring/scatter"
            )
        _downgrade_scan_family(cfg, was_auto, prog, "--edge-shards")
        if cfg.method in ("cumsum", "mxsum", "mxscan"):
            raise SystemExit(
                "--edge-shards supports --method scan or scatter "
                "(edge chunks carry no row_ptr for prefix-diff reduces; "
                "the mxscan kernel is confined to the csc engines here)"
            )
        return
    if cfg.exchange == "allgather":
        return
    if not cfg.distributed:
        raise SystemExit(f"--exchange {cfg.exchange} requires --distributed")
    _downgrade_scan_family(cfg, was_auto, prog,
                           f"--exchange {cfg.exchange}")
    if cfg.method in ("cumsum", "mxsum", "mxscan"):
        raise SystemExit(
            "--exchange ring/scatter supports --method scan or scatter "
            "(bucketed reductions carry no row_ptr for prefix-diff "
            "reduces; the mxscan kernel is confined to the csc engines "
            "here)"
        )
    if cfg.exchange == "scatter":
        if prog.reduce != "sum" or getattr(prog, "needs_dst_state", False):
            raise SystemExit(
                "--exchange scatter needs a sum-reducible program without "
                "per-edge destination reads; use --exchange ring or allgather"
            )


def build_pull_route(cfg: RunConfig, shards, prog):
    """ONE --route-gather plan construction for a pull-layout run
    (host-side — call it OUTSIDE the timed region): fused plans for the
    'fused*' modes, the CF per-column src+dst plan for wide
    dst-dependent programs, the expand plan otherwise; '' = None.
    Shared by the pagerank/colfilter mains, the generic program driver
    (apps/run.py), and run_fixed_dist, so the mode->planner dispatch
    cannot drift per driver."""
    rg = getattr(cfg, "route_gather", "")
    if not rg:
        return None
    from lux_tpu.ops import expand

    pf = route_is_pf(rg)
    if route_base(rg) == "fused":
        if getattr(prog, "k", 1) > 1:
            # defense-in-depth twin of validate_exchange's CLI guard:
            # a library caller skipping validation must get the clear
            # error here, not a mid-iteration fused-shape crash
            raise SystemExit(
                "--route-gather fused supports scalar vertex state; "
                "wide dst-dependent programs route with "
                "--route-gather expand (per-column src + dst plans)")
        return expand.plan_fused_shards_cached(shards, prog.reduce, pf=pf,
                                               mx=route_mx(rg))
    if getattr(prog, "k", 1) > 1:
        # wide states route through the CF per-column src+dst plans (a
        # program that ignores dst still reads it exactly; XLA DCEs it)
        return expand.plan_cf_route_shards_cached(shards, pf=pf)
    return expand.plan_expand_shards_cached(shards, pf=pf)


def build_push_route(cfg: RunConfig, shards):
    """The push apps' --route-gather twin of build_pull_route: ring
    exchanges plan per-bucket, every other push branch routes the dense
    rounds on the pull layout.  Shared by the sssp/components
    convergence driver and the generic program driver's frontier
    workloads."""
    if not getattr(cfg, "route_gather", ""):
        return None
    from lux_tpu.ops import expand

    if cfg.exchange == "ring":
        return expand.plan_ring_route_shards_cached(shards)
    return expand.plan_expand_shards_cached(
        shards, pf=route_is_pf(cfg.route_gather))


def build_exchange_shards(g: HostGraph, cfg: RunConfig):
    """Shard builder for the selected --exchange strategy (SURVEY.md §2.5).
    ring/scatter bucket the graph for their collectives; allgather uses the
    plain pull layout."""
    from lux_tpu.graph.shards import build_pull_shards

    if cfg.edge_shards > 1:
        from lux_tpu.parallel.edge2d import build_edge2d_shards

        return build_edge2d_shards(g, cfg.num_parts, cfg.edge_shards)
    if cfg.exchange == "allgather":
        return build_pull_shards(
            g, cfg.num_parts, sort_segments=cfg.sort_segments,
            compact_gather=cfg.compact_gather,
        )
    if not cfg.distributed:
        raise SystemExit(f"--exchange {cfg.exchange} requires --distributed")
    if cfg.exchange == "ring":
        from lux_tpu.parallel.ring import build_ring_shards

        return build_ring_shards(g, cfg.num_parts)
    from lux_tpu.parallel.scatter import build_scatter_shards

    return build_scatter_shards(g, cfg.num_parts)


def _residency(cfg: RunConfig) -> int:
    """k = parts RESIDENT per device for this config (1 when every part
    gets its own chip).  Mirrors make_mesh_for_parts /
    make_mesh_feat_for_parts slot arithmetic."""
    if cfg.edge_shards > 1:
        return 1  # edge2d estimate already counts the whole footprint
    if not cfg.distributed:
        # single-device drivers place ALL parts on the one device: the
        # stacked (P, ...) shard arrays and per-part state are all
        # resident at once, so the per-part estimate scales by P.
        return cfg.num_parts
    import jax

    slots = len(jax.devices())
    if cfg.feat_shards > 1:
        slots //= cfg.feat_shards
    d = min(slots, cfg.num_parts)
    while cfg.num_parts % d:
        d -= 1
    return cfg.num_parts // d


def estimate_exchange(shards, cfg: RunConfig, state_width: int = 1):
    """Preflight estimate matching the selected exchange strategy.
    Per-part estimates are scaled by the residency factor k (k parts
    resident per chip when num_parts exceeds the parts slots) — the
    gathered/exchange buffer is global-sized and does not scale."""
    from lux_tpu.utils import preflight

    sbytes = 2 if cfg.dtype == "bfloat16" else 4
    if cfg.edge_shards > 1:
        est = preflight.estimate_edge2d(
            shards.spec, shards.e2_pad, state_width, sbytes
        )
        if getattr(cfg, "route_gather", ""):
            # one chunk plan per device: n from the chunk pad + the
            # parts-gathered state
            est = preflight.add_routed_bytes(
                est,
                preflight.routed_bucket_plan_bytes_analytic(
                    1, shards.e2_pad,
                    shards.spec.num_parts * shards.spec.nv_pad),
            )
        return est
    if cfg.exchange == "ring":
        est = preflight.estimate_ring(
            shards.spec, shards.e_bucket_pad, state_width, sbytes
        )
    elif cfg.exchange == "scatter":
        est = preflight.estimate_scatter(
            shards.spec, shards.e_bucket_pad, state_width, sbytes
        )
    else:
        est = preflight.estimate_pull(shards.spec, state_width, sbytes)
    est = preflight.scale_residency(est, _residency(cfg))
    if getattr(cfg, "route_gather", ""):
        # routed plans are static per-graph device arrays — a real HBM
        # slice (~270 MB expand / ~630 MB fused at rmat20).  Bucketed
        # exchanges carry P per-peer plans per resident part, a
        # different (usually larger) geometry than the allgather plan.
        if cfg.exchange in ("ring", "scatter"):
            extra = preflight.routed_bucket_plan_bytes_analytic(
                shards.spec.num_parts, shards.e_bucket_pad,
                shards.spec.nv_pad)
        else:
            extra = preflight.routed_plan_bytes_analytic(
                shards.spec, cfg.route_gather, wide=state_width > 1)
        est = preflight.add_routed_bytes(est, extra * _residency(cfg))
    return est


def report_preflight(est, cfg: RunConfig, shards, state_width: int = 1,
                     stream_hint: bool = False):
    """Print the estimate and warn if it exceeds device HBM — with the
    --edge-shards hint when (and only when) a 2-D run could actually
    execute here: 1-D allgather pull layout, non-pallas, and enough
    devices for num_parts * EP part-columns (edge2d has no
    k-residency).  One implementation for every pull app, so the hint
    can't drift per driver."""
    from lux_tpu.utils import preflight

    print(est)
    spec = None
    max_ep = 0
    if (cfg.exchange == "allgather" and cfg.edge_shards == 1
            and cfg.feat_shards == 1 and cfg.method != "pallas"):
        import jax

        spec = shards.spec
        max_ep = len(jax.devices()) // max(cfg.num_parts, 1)
    return preflight.check_fits(
        est, spec=spec, state_width=state_width,
        state_dtype_bytes=2 if cfg.dtype == "bfloat16" else 4,
        max_edge_shards=max_ep,
        # only apps that EXPOSE --stream-hbm-gib may advertise it
        stream_hint=stream_hint,
    )


def run_streamed(cfg: RunConfig, g: HostGraph, prog, state_width: int = 1,
                 active_fn=None):
    """Shared --stream-hbm-gib runner for pull apps (the -ll:zsize
    zero-copy analog, core/lux_mapper.cc:146-165): host-resident edges
    streamed through a device-byte budget (engine/stream.py).  Validates
    the combination, builds + prints the streamed geometry, runs, and
    returns (global_state, elapsed_s, iters).  ``active_fn`` selects the
    convergence driver (components) instead of the fixed-iteration one.
    Each app owns its report tail."""
    if (cfg.distributed or cfg.exchange != "allgather"
            or cfg.method == "pallas" or cfg.compact_gather
            or cfg.edge_shards > 1 or cfg.feat_shards > 1 or cfg.verbose
            or cfg.ckpt_every or cfg.ckpt_dir or cfg.repartition_every):
        raise SystemExit(
            "--stream-hbm-gib is the single-process host-offload mode; "
            "it does not combine with --distributed/--exchange/"
            "--edge-shards/--feat-shards/--method pallas/"
            "--compact-gather/-verbose/checkpointing/"
            "--repartition-every"
        )
    import jax

    from lux_tpu.engine import pull, stream as stream_eng
    from lux_tpu.graph.shards import build_pull_shards
    from lux_tpu.utils.timing import Timer

    sbytes = 2 if cfg.dtype == "bfloat16" else 4
    shards = build_pull_shards(
        g, cfg.num_parts, sort_segments=cfg.sort_segments
    )
    budget = int(cfg.stream_hbm_gib * (1 << 30))
    chunk_e = stream_eng.chunk_edges_for_budget(
        shards.spec, budget, sbytes, state_width
    )
    resident = stream_eng.streamed_hbm_bytes(
        shards.spec, chunk_e, sbytes, state_width
    )
    total = stream_eng.edge_bytes_total(shards.spec)
    ssh = stream_eng.build_streamed_pull(shards, chunk_e)
    print(
        f"streamed: {len(ssh.chunks[0])} chunk(s) of {chunk_e} edges/part; "
        f"resident {resident/(1<<30):.3f} GiB <= budget "
        f"{budget/(1<<30):.3f} GiB (monolithic edge arrays "
        f"{total/(1<<30):.3f} GiB)"
    )
    state0 = pull.init_state(prog, ssh.varrays)
    from lux_tpu.utils import profiling

    with profiling.trace(cfg.profile_dir):
        timer = Timer()
        if active_fn is not None:
            out, iters = stream_eng.run_pull_until_streamed(
                prog, ssh, state0, cfg.max_iters, active_fn,
                method=cfg.method,
            )
        else:
            out = stream_eng.run_pull_fixed_streamed(
                prog, ssh, state0, cfg.num_iters, method=cfg.method
            )
            iters = cfg.num_iters
        elapsed = timer.stop(out)
    return ssh.scatter_to_global(jax.device_get(out)), elapsed, iters


def resume_or_init(cfg: RunConfig, app: str, shards, state, nv):
    """Elastic resume: restack the latest global checkpoint (any previous
    -ng/--exchange) onto THIS run's layout; returns (state, start_it)."""
    if not cfg.ckpt_dir:
        return state, 0
    import jax.numpy as jnp

    from lux_tpu.graph.shards import global_to_stacked
    from lux_tpu.utils import checkpoint

    saved, start_it, prev = checkpoint.load_resume(cfg.ckpt_dir, app, nv)
    if saved is None:
        return state, 0
    stacked = global_to_stacked(shards.cuts, shards.spec.nv_pad, saved)
    print(f"resumed from {prev} at iteration {start_it}")
    # cast to THIS run's state dtype (a resume may change --dtype)
    return jnp.asarray(stacked).astype(state.dtype), start_it


def save_global(cfg: RunConfig, app: str, shards, iteration: int, state):
    """Checkpoint the stacked device state as the layout-independent
    global vector (elastic: any later -ng/--exchange can resume it)."""
    import jax

    from lux_tpu.utils import checkpoint

    checkpoint.save_iteration(
        cfg.ckpt_dir, iteration, shards.scatter_to_global(jax.device_get(state)),
        app,
    )


def run_pull_stepwise_dist(prog, shards, state, start_it, num_iters, mesh,
                           cfg: RunConfig, nv, on_iter=None):
    """Step-wise DISTRIBUTED pull loop (-verbose --distributed only):
    each shard_map iteration fences into load/comp/update sub-steps —
    the reference prints the per-GPU phase timers on multi-GPU runs too
    (sssp_gpu.cu:513-518).  Same on_iter hook as run_pull_stepwise so
    checkpointing composes with verbose.  (Non-verbose distributed runs
    use the fused run_fixed_dist/run_fixed_dist_chunked paths.)"""
    import jax

    from lux_tpu.parallel import dist
    from lux_tpu.parallel.mesh import shard_stacked
    from lux_tpu.utils.timing import IterStats, Timer

    arrays = shard_stacked(mesh, jax.tree.map(jax.numpy.asarray, shards.arrays))
    state = shard_stacked(mesh, state)
    stats = IterStats(verbose=cfg.verbose)
    load, comp, update = dist.compile_pull_phases_dist(
        prog, mesh, cfg.method
    )
    for it in range(start_it, num_iters):
        t = Timer()
        gath = load(arrays, state)
        lt = t.stop(gath)
        t = Timer()
        acc = comp(arrays, gath)
        ct = t.stop(acc)
        t = Timer()
        state = update(arrays, state, acc)
        ut = t.stop(state)
        stats.record_phases(it, nv, lt, ct, ut)
        if on_iter is not None:
            on_iter(it, state)
    return state, stats


def run_fixed_dist_chunked(prog, shards, state, start_it, num_iters, mesh,
                           cfg: RunConfig, app: str):
    """Distributed fixed-iteration run in --ckpt-every-sized on-device
    chunks with a checkpoint between chunks.  Returns (state,
    compute_seconds) where compute_seconds EXCLUDES the host-side
    checkpoint I/O (device_get + disk) so reported GTEPS stays an engine
    number."""
    from lux_tpu.utils.timing import Timer

    compute = 0.0
    it = start_it
    while it < num_iters:
        n = min(cfg.ckpt_every, num_iters - it)
        t = Timer()
        state = run_fixed_dist(prog, shards, state, n, mesh, cfg)
        compute += t.stop(state)
        it += n
        if it < num_iters or num_iters % cfg.ckpt_every == 0:
            save_global(cfg, app, shards, it, state)
    return state, compute


def run_fixed_dist(prog, shards, state, num_iters, mesh, cfg: RunConfig):
    """Distributed fixed-iteration driver for the selected exchange."""
    if cfg.edge_shards > 1:
        from lux_tpu.parallel import edge2d

        e2_route = None
        if getattr(cfg, "route_gather", "") == "expand":
            from lux_tpu.ops import expand

            e2_route = expand.plan_edge2d_route_shards_cached(shards)
        return edge2d.run_pull_fixed_2d(
            prog, shards, state, num_iters, mesh, cfg.method,
            route=e2_route,
        )
    if cfg.exchange == "ring":
        from lux_tpu.parallel import ring

        ring_route = None
        if getattr(cfg, "route_gather", "") == "expand":
            from lux_tpu.ops import expand

            ring_route = expand.plan_ring_route_shards_cached(shards)
        return ring.run_pull_fixed_ring(
            prog, shards, state, num_iters, mesh, cfg.method,
            route=ring_route,
        )
    if cfg.exchange == "scatter":
        from lux_tpu.parallel import scatter

        sc_route = None
        if getattr(cfg, "route_gather", "") == "expand":
            from lux_tpu.ops import expand

            sc_route = expand.plan_scatter_route_shards_cached(shards)
        return scatter.run_pull_fixed_scatter(
            prog, shards, state, num_iters, mesh, cfg.method,
            route=sc_route,
        )
    from lux_tpu.parallel import dist

    route = build_pull_route(cfg, shards, prog)
    return dist.run_pull_fixed_dist(
        prog, shards.spec, shards.arrays, state, num_iters, mesh, cfg.method,
        route=route,
    )


def run_pull_stepwise(prog, spec, arrays, state, start_it, num_iters, cfg,
                      nv, on_iter=None, route=None):
    """Step-wise pull loop for -verbose / -ckpt-every runs.  Verbose mode
    fences each iteration into load/comp/update sub-steps (the reference's
    per-phase kernel timers, sssp_gpu.cu:513-518); otherwise the iteration
    runs as one jitted step.  ``route`` applies to the fused-step path
    only (the 3-phase verbose fence keeps the direct gather — its LOAD
    boundary is the observability contract).  Returns
    (final_state, IterStats)."""
    from lux_tpu.engine import pull
    from lux_tpu.utils.timing import IterStats, Timer

    stats = IterStats(verbose=cfg.verbose)
    if cfg.verbose:
        if route is not None:
            raise SystemExit(_ROUTE_VERBOSE_ERR)
        load, comp, update = pull.compile_pull_phases(prog, spec, cfg.method)
    else:
        step = pull.compile_pull_step(prog, spec, cfg.method, route=route)
    for it in range(start_it, num_iters):
        if cfg.verbose:
            t = Timer()
            gath = load(arrays, state)
            lt = t.stop(gath)
            t = Timer()
            acc = comp(arrays, gath)
            ct = t.stop(acc)
            t = Timer()
            state = update(arrays, state, acc)
            ut = t.stop(state)
            stats.record_phases(it, nv, lt, ct, ut)
        else:
            t = Timer()
            state = step(arrays, state)
            stats.record(it, nv, t.stop(state))
        if on_iter is not None:
            on_iter(it, state)
    return state, stats


def print_check(name: str, violations: int):
    """Reference-parity [PASS]/[FAIL] verdict (sssp_gpu.cu:837-842)."""
    verdict = "[PASS]" if violations == 0 else "[FAIL]"
    print(f"{verdict} {name} check: {violations} violations")
    return violations == 0


def top_k(label: str, values: np.ndarray, k: int = 5):
    idx = np.argsort(values)[::-1][:k]
    # float() so non-native dtypes (bfloat16) format cleanly
    print(
        f"top-{k} {label}: "
        + ", ".join(f"v{int(i)}={float(values[i]):.3e}" for i in idx)
    )
