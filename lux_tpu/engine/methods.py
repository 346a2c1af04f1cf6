"""Measured per-platform segment-reduction method defaults.

The reference hard-codes its reduction strategy (CUB block-scan + atomics,
pagerank_gpu.cu:59-95) because it targets exactly one architecture.  Here
four interchangeable strategies exist (lux_tpu.ops.segment) and the right
one depends on where the program runs, so the engine-wide default is
``"auto"``: resolved once at driver entry from the runtime platform and
the measured winners below.  Defaults follow measurements, not tradition:

  * **1-core CPU host** (round-2 CPU phase table, pre-PR 21): ``scatter``
    beats ``scan`` ~2x on the dominating comp phase — XLA:CPU lowers the
    sorted segment-sum to a tight sequential accumulation, while the
    log-depth associative scan makes multiple passes over the edge array.
  * **TPU**: ``scatter`` as the blanket winner, because ``scan`` does
    not compile at the sizes users run.  The 1-D
    ``lax.associative_scan`` ladder's compile time grows with the edge
    count: for a described v5e the PageRank loop took 38 s at rmat16,
    156 s at rmat18 and 540 s at rmat20 (compiled for a described
    v5e), while ``scatter`` compiles the rmat22 loop in 1.3 s.
  * **TPU, csc engines**: ``mxscan`` (``CSC_WINNERS``, through
    ``resolve_sum``), the blocked segmented-scan kernel: on one v5e
    it reduces 2^27 arcs into 2^22 rows in 161 ms against
    ``scatter``'s 1,173 ms (ops/pallas_scan).  It cannot be the blanket
    winner: the bucketed exchanges run scan/scatter only.

``resolve`` is pure/host-side: it runs before any trace, so the concrete
string participates in jit static arguments and compile caches as usual.
"""
from __future__ import annotations

import os
import threading

#: Concrete strategies a resolution may produce.  ("cumsum"/"mxsum" are
#: sum-only prefix-diff strategies and "pallas" needs the block-CSR
#: layout — none is safe as a blanket default, so winners stay within
#: the universally-valid {scan, scatter} set.  "mxscan" is the blocked
#: segmented-scan kernel: valid on every csc path and on 1-D bucketed
#: paths, the csc engines' TPU winner (CSC_WINNERS, resolve_sum).)
CONCRETE = ("scan", "cumsum", "mxsum", "mxscan", "scatter")

#: (platform, reduce) -> winner; a tpu row changes only with a chip
#: measurement (or, as now, a program that cannot compile there).
WINNERS = {
    ("cpu", "sum"): "scatter",
    ("cpu", "min"): "scatter",
    ("cpu", "max"): "scatter",
    ("tpu", "sum"): "scatter",
    ("tpu", "min"): "scatter",
    ("tpu", "max"): "scatter",
}

#: Unknown platform (gpu via XLA, interpreters): the portable choice.
FALLBACK = "scan"

#: Measured-winners overlay file: written by an (unattended) TPU bench
#: race (bench.py) so a chip window updates defaults WITHOUT a code
#: edit.  Format: {"tpu:sum": "mxsum", ...}; entries must be in
#: CONCRETE.  Overridable via LUX_METHOD_WINNERS; missing file = no-op.
WINNERS_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".lux_winners.json",
)

_overlay_raw_cache: dict | None = None
_file_winners_cache: dict | None = None
_platform_cache: str | None = None
#: one lock for every lazy-init cache above (+ _tiles_cache): method
#: resolution runs inside engine setup, which PR 2's planner fan-out
#: calls from worker threads — an unlocked check-then-act would load the
#: overlay file N times and, worse, let a reset in record_overlay_entry
#: interleave with a half-done init (luxcheck LUX-C001).  RLock because
#: _file_winners/pallas_tiles re-enter _overlay_raw under the same lock.
_CACHE_LOCK = threading.RLock()


def overlay_path() -> str:
    """The measured-winners overlay path (LUX_METHOD_WINNERS override) —
    shared by every reader AND writer so a recorded measurement always
    lands where the readers look."""
    return os.environ.get("LUX_METHOD_WINNERS", WINNERS_FILE)


def _overlay_raw() -> dict:
    """The overlay file as a raw dict, loaded once per process; malformed
    or missing files read as empty (a half-written file must never break
    every driver)."""
    global _overlay_raw_cache
    with _CACHE_LOCK:
        if _overlay_raw_cache is None:
            raw: dict = {}
            try:
                import json

                with open(overlay_path()) as f:
                    loaded = json.load(f)
                if isinstance(loaded, dict):
                    raw = loaded
            except (OSError, ValueError):
                pass
            _overlay_raw_cache = raw
        return _overlay_raw_cache


def _deep_merge(dst: dict, src: dict) -> dict:
    """Recursively merge ``src`` into a copy of ``dst``: dict-valued
    sub-keys merge, everything else overwrites.  This is what keeps one
    recorder from clobbering a SIBLING measurement — the round-5
    regression where a later ``tpu:micro_sum`` write dropped the banked
    mxsum/gather micro rows (VERDICT r5 weak #2)."""
    out = dict(dst)
    for k, v in src.items():
        if isinstance(out.get(k), dict) and isinstance(v, dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def record_overlay_entry(key: str, value) -> None:
    """Atomic read-modify-write of ONE overlay entry — the single writer
    for unattended chip measurements (bench.py's method winner, the
    Pallas sweep's tile winner).  A corrupt existing file is replaced,
    not fatal: readers already treat it as empty, and losing a chip
    window's measurement to a bad old file would be strictly worse.

    Dict values MERGE with the existing entry (recursively) instead of
    replacing it: recording one method's micro row must never erase a
    previously-banked row for a different method — chip-window data is
    too scarce to lose.  Scalar values still overwrite (a winner string
    is a decision, not a table).

    The read-modify-write holds an ``fcntl`` lock on a sidecar lockfile:
    two recorders can overlap (micro race + bench race), and an unlocked
    RMW would lose one entry.  On success the module's read caches reset so
    the recording process itself sees what it just wrote."""
    import json

    path = overlay_path()
    try:
        lock = open(path + ".lock", "a+")
        try:
            import fcntl

            fcntl.flock(lock, fcntl.LOCK_EX)
        except (ImportError, OSError):
            pass  # no flock (non-POSIX): degraded to the old racy RMW
        try:
            prev = {}
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        prev = json.load(f)
                except ValueError:
                    prev = {}  # corrupt: start fresh, don't drop the win
            if not isinstance(prev, dict):
                prev = {}
            if isinstance(prev.get(key), dict) and isinstance(value, dict):
                prev[key] = _deep_merge(prev[key], value)
            else:
                prev[key] = value
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(prev, f, indent=1)
            os.replace(tmp, path)
        finally:
            lock.close()  # releases the flock
        global _overlay_raw_cache, _file_winners_cache, _tiles_cache
        with _CACHE_LOCK:
            _overlay_raw_cache = None
            _file_winners_cache = None
            _tiles_cache = None
        print(f"# recorded {key} -> {value!r} ({path})", flush=True)
    except OSError as e:
        print(f"# winners file not written: {e}", flush=True)


def _file_winners() -> dict:
    """The method-winner view of the overlay.  Non-CONCRETE entries are
    ignored; blanket defaults must hold on EVERY engine path (the
    bucketed row_ptr-free ring/edge2d layouts only run scan/scatter, and
    cumsum/mxsum are sum-only anyway), so the overlay is restricted
    exactly like WINNERS."""
    global _file_winners_cache
    with _CACHE_LOCK:
        if _file_winners_cache is None:
            winners = {}
            for key, val in _overlay_raw().items():
                plat, _, red = str(key).partition(":")
                if plat and red and val in ("scan", "scatter"):
                    winners[(plat, red)] = val
            _file_winners_cache = winners
        return _file_winners_cache


#: LOAD-phase route modes the bench A/Bs and the overlay may record:
#: "routed" = the unfused Benes expand (one kernel per pass), "routed-pf"
#: = the pass-fused replay (2-3 passes per kernel, VMEM-resident
#: intermediates, ops/expand.to_pf).  Both are bitwise-identical to the
#: direct gather, so either is always safe to follow.
ROUTE_MODES = ("routed", "routed-pf")

#: overlay key the TPU bench race records its routed-vs-routed-pf
#: winner under (bench.py _record_route_mode) — like "tpu:sum", an
#: unattended chip window updates the default without a code edit.
ROUTE_MODE_KEY = "tpu:route_mode"


def route_mode() -> str:
    """The preferred routed-plan flavor: LUX_ROUTE_MODE env override,
    else the chip-measured overlay entry, else "routed-pf" (the
    analytic winner — ~40% fewer HBM sweeps per iteration — until a
    window banks the A/B; both modes are bitwise-identical so the
    default is a perf bet, never a correctness one)."""
    env = os.environ.get("LUX_ROUTE_MODE")
    if env:
        if env not in ROUTE_MODES:
            raise ValueError(
                f"LUX_ROUTE_MODE must be one of {ROUTE_MODES}, got {env!r}")
        return env
    rec = _overlay_raw().get(ROUTE_MODE_KEY)
    return rec if rec in ROUTE_MODES else "routed-pf"


#: REDUCE-phase modes of the fused routed hot loop the bench/micro
#: races may record: "group" = the plain masked group reshape-reduce
#: (VPU sweep over the group space, PR 4's fused form), "mxreduce" =
#: the segmented reduction fused INTO the final routed Pallas kernel
#: as a one-hot x state contraction on the MXU (ops/expand plan_fused
#: mx=True; arXiv:1811.09736's construction).  Sum rides the MXU (bf16
#: operands where exact, f32 accumulate — ops/pallas_shuffle
#: StaticMXGroup documents the precision contract); min/max and
#: integer sums use the same in-kernel layout with a masked VPU reduce
#: (min has no matmul identity), dtype-preserving bitwise.  Unlike the
#: route modes the two flavors are NOT bitwise-identical for float
#: sums (each has its own deterministic association, like mxsum vs
#: scan), so the default stays "group" until a chip window BANKS the
#: measured winner — the three-way tpu:sum story (mxsum vs scan vs
#: mxreduce) is a race, not an assumption.
REDUCE_MODES = ("group", "mxreduce")

#: overlay key the mxu-vs-vpu micro race (tools/tpu_micro_race.py) and the bench micro row record their winner under.
REDUCE_MODE_KEY = "tpu:reduce_mode"


def reduce_mode() -> str:
    """The preferred fused-reduce flavor: LUX_REDUCE_MODE env override,
    else the chip-measured overlay entry, else "group" (the shipped
    PR-4 behavior — mxreduce changes float-sum association, so it is
    followed only once measured).  Consumed by the fused planners'
    ``mx=None`` default (ops/expand.resolve_fused_mx) and the apps'
    ``--route-gather fused-pf`` path."""
    env = os.environ.get("LUX_REDUCE_MODE")
    if env:
        if env not in REDUCE_MODES:
            raise ValueError(
                f"LUX_REDUCE_MODE must be one of {REDUCE_MODES}, "
                f"got {env!r}")
        return env
    rec = _overlay_raw().get(REDUCE_MODE_KEY)
    return rec if rec in REDUCE_MODES else "group"


#: CF error-dot flavors (models/colfilter): "vpu" = the elementwise
#: multiply + lane-axis jnp.sum (the shipped form), "mxu" = the K-dim
#: contraction as a true (rows, K) @ (K, 1) dot_general matmul tile
#: (f32 operands, f32 accumulate — MXU association, so float results
#: may differ from "vpu" in the last ulps; the race is exactness-gated
#: against the NumPy oracle with the documented tolerance).
CF_DOT_MODES = ("vpu", "mxu")

#: overlay key the CF error-dot micro race (tools/tpu_micro_race.py
#: ``cfdot`` worker) banks its measured winner under.
CF_DOT_KEY = "tpu:cf_err_dot"


def cf_err_dot_mode() -> str:
    """The preferred CF error-dot flavor: LUX_CF_ERR_DOT env override,
    else the chip-measured overlay entry, else "vpu" (the shipped
    behavior — the MXU tile changes f32 association, so it is followed
    only once measured).  Resolved at driver entry (models/colfilter
    ``colfilter``/``make_pallas_runner`` with err_dot=None), never
    inside a trace."""
    env = os.environ.get("LUX_CF_ERR_DOT")
    if env:
        if env not in CF_DOT_MODES:
            raise ValueError(
                f"LUX_CF_ERR_DOT must be one of {CF_DOT_MODES}, "
                f"got {env!r}")
        return env
    rec = _overlay_raw().get(CF_DOT_KEY)
    return rec if rec in CF_DOT_MODES else "vpu"


#: SCAN-FAMILY float-sum strategies the three-way ``tpu:sum`` race
#: (tools/tpu_micro_race.py mxsum/mxscan/scan workers, bench.py's
#: standing ``scan_micro_mx_vs_vpu`` row) may bank: "scan" = the VPU
#: ``lax.associative_scan`` ladder (the shipped default), "mxsum" = the
#: prefix-diff blocked triangular matmul (arXiv:1811.09736; global-
#: prefix f32 caveat), "mxscan" = the segmented scan ITSELF as masked
#: triangular MXU contractions (ops/pallas_scan, arXiv:2505.15112's
#: blocked systolic scan; accumulation stays within a segment).  The
#: three differ only in float-sum association (min/max/integer paths
#: are bitwise), so like ``tpu:reduce_mode`` the VPU default is retired
#: only through a banked on-chip measurement — never assumed.
SUM_MODES = ("scan", "mxsum", "mxscan")


def spmd_kw(method: str, routed: bool = False) -> dict:
    """``jax.shard_map`` keywords for a distributed loop whose reduce
    runs ``method``: a Pallas kernel's output (the ``mxscan`` scan, a
    routed gather) declares no varying mesh axes, so the vma check is
    off wherever one runs under the map."""
    return {"check_vma": False} if routed or method == "mxscan" else {}


#: overlay key the scan-family race banks its winner under.  The SAME
#: key also carries the app-level bench race's blanket winner (which
#: may be "scatter"): the two readers consume disjoint value domains —
#: ``_file_winners`` follows {scan, scatter} as the blanket default,
#: ``sum_mode`` follows SUM_MODES as the csc-path refinement — so one
#: key stays coherent whichever race wrote last.
SUM_MODE_KEY = "tpu:sum"


def sum_mode(platform: str | None = None) -> str:
    """The preferred scan-family float-sum strategy: LUX_SUM_MODE env
    override (explicit choice, any platform), else the chip-measured
    ``tpu:sum`` overlay entry ON TPU ONLY, else "scan" — the shipped
    VPU default stays until a window measures, and CPU runs are
    bitwise-unchanged by a banked TPU winner (the acceptance contract
    of ISSUE 11)."""
    env = os.environ.get("LUX_SUM_MODE")
    if env:
        if env not in SUM_MODES:
            raise ValueError(
                f"LUX_SUM_MODE must be one of {SUM_MODES}, got {env!r}")
        return env
    plat = platform if platform is not None else default_platform()
    rec = _overlay_raw().get(SUM_MODE_KEY)
    if plat == "tpu" and rec in SUM_MODES:
        return rec
    return "scan"


def record_sum_family_winner(winner: str) -> bool:
    """Bank a scan-family race winner under ``tpu:sum`` — UNLESS the
    key currently holds a measured "scatter" blanket winner.  The
    scan-family races (micro race, bench's scan micro row) never time
    scatter, so overwriting a full-race scatter measurement with a
    family-internal winner would destroy the better datapoint (the
    same chip-data-is-scarce rule behind record_overlay_entry's
    deep-merge).  The full bench race's _record_winner times BOTH
    domains and may overwrite freely.  Returns True when recorded."""
    assert winner in SUM_MODES, winner
    prev = _overlay_raw().get(SUM_MODE_KEY)
    if prev == "scatter":
        print(f"# tpu:sum holds a measured blanket 'scatter' winner; "
              f"scan-family winner {winner!r} NOT banked over it "
              "(raw times live in the micro rows)", flush=True)
        return False
    record_overlay_entry(SUM_MODE_KEY, winner)
    return True


#: (platform, reduce) -> winner on the csc gather-apply engines (pull
#: single-device and dist, the push dense round), whose shards carry
#: row_ptr and head flags: there the blocked segmented-scan kernel
#: (ops/pallas_scan) applies to every monoid.  The bucketed exchanges
#: keep the blanket ``resolve``, and (E, K) values leave the kernel in
#: ops/segment.csc_method.  A row changes only with a chip measurement.
CSC_WINNERS = {
    ("tpu", "sum"): "mxscan",
    ("tpu", "min"): "mxscan",
    ("tpu", "max"): "mxscan",
}


def resolve_sum(method: str, reduce: str = "sum",
                platform: str | None = None) -> str:
    """``resolve`` for the csc gather-apply engines (pull single-device
    and dist, the push dense round, the app CLIs).  ``"auto"`` resolves,
    in order, to: a set LUX_SUM_MODE (an explicit choice, float sums,
    any platform); the banked ``tpu:sum`` scan-family winner (float
    sums, on TPU); the ``CSC_WINNERS`` row for (platform, reduce);
    else the blanket ``resolve``.  Explicit concrete methods pass
    through untouched.

    The bucketed ring/edge2d/feat/scatter drivers keep plain
    ``resolve``: their scan/scatter asserts never see a csc winner
    (apps/common downgrades one before those exchanges, and
    ops/segment.segment_reduce_by_ends downgrades for library callers
    who pass one explicitly)."""
    if method != "auto":
        return method
    if reduce == "sum" and os.environ.get("LUX_SUM_MODE"):
        return sum_mode(platform)  # validates + returns the env choice
    plat = platform if platform is not None else default_platform()
    if (reduce == "sum" and plat == "tpu"
            and _overlay_raw().get(SUM_MODE_KEY) in SUM_MODES):
        return sum_mode(plat)
    return CSC_WINNERS.get((plat, reduce)) or resolve(method, reduce, plat)


#: CROSS-PART MERGE modes of the push engine's frontier aggregation
#: (ISSUE 17, luxmerge): "bulk" = the bulk-synchronous flatten — every
#: part's queue concatenated (single all_gather on the dist engines)
#: and scattered into one destination pass per superstep (the shipped
#: PR-3 behavior); "tree" = the asynchronous reduction tree
#: (ops/merge_tree, Tascade arXiv:2311.15810's atomic-free construction)
#: — per-source-block partial frontiers combine pairwise up a STATIC
#: schedule, and the dist queue exchange runs as staged
#: recursive-doubling ppermute rounds instead of one barrier
#: all_gather.  Both modes are bitwise-identical for min/max/integer
#: monoids (scatter-reduce into disjoint destination slots is
#: order-independent there — every shipped push program reduces with
#: min/max), but a float-SUM push program would see the tree's
#: association, so like ``tpu:reduce_mode`` the bulk default is retired
#: only through a banked on-chip measurement, never assumed.
MERGE_MODES = ("bulk", "tree")

#: overlay key the merge micro race (bench.py's standing
#: ``merge_micro_tree_vs_bulk`` row) banks its measured winner under.
MERGE_MODE_KEY = "tpu:merge_mode"


def merge_mode(platform: str | None = None) -> str:
    """The preferred cross-part merge flavor: LUX_MERGE_MODE env
    override (explicit choice, any platform), else the chip-measured
    ``tpu:merge_mode`` overlay entry ON TPU ONLY, else "bulk" — the
    shipped bulk-synchronous merge stays until a window measures, and
    CPU runs are bitwise-unchanged by a banked TPU winner (the same
    acceptance contract as ``tpu:sum``)."""
    env = os.environ.get("LUX_MERGE_MODE")
    if env:
        if env not in MERGE_MODES:
            raise ValueError(
                f"LUX_MERGE_MODE must be one of {MERGE_MODES}, got {env!r}")
        return env
    plat = platform if platform is not None else default_platform()
    rec = _overlay_raw().get(MERGE_MODE_KEY)
    if plat == "tpu" and rec in MERGE_MODES:
        return rec
    return "bulk"


_tiles_cache: tuple | None = None


def pallas_tiles() -> tuple | None:
    """Measured (v_blk, t_chunk) Pallas tile winner from the overlay
    (key ``"tpu:pallas_tiles"``, written by an unattended
    `tools/tpu_pallas_check --sweep`); None while unmeasured — the
    kernels then use their compiled-in defaults (ops/pallas_spmv
    V_BLK/T_CHUNK).  Malformed entries are ignored, and v_blk must keep
    the 128-lane alignment the kernel grid assumes."""
    global _tiles_cache
    with _CACHE_LOCK:
        if _tiles_cache is not None:
            return _tiles_cache or None
        tiles: tuple = ()
        t = _overlay_raw().get("tpu:pallas_tiles")
        if (
            isinstance(t, dict)
            and isinstance(t.get("v_blk"), int)
            and 0 < t["v_blk"] <= 4096 and t["v_blk"] % 128 == 0
            and isinstance(t.get("t_chunk"), int)
            # sublane-aligned: the 2-D CF kernel's (1, t, k) BlockSpec
            # requires t a multiple of 8 (ops/pallas_spmv.py)
            and 0 < t["t_chunk"] <= 8192 and t["t_chunk"] % 8 == 0
        ):
            tiles = (t["v_blk"], t["t_chunk"])
        _tiles_cache = tiles
    return _tiles_cache or None


def default_platform() -> str:
    """The jax default backend, overridable via LUX_METHOD_PLATFORM (so
    resolution can pick a strategy string without touching a device)."""
    global _platform_cache
    env = os.environ.get("LUX_METHOD_PLATFORM")
    if env:
        return env
    with _CACHE_LOCK:
        if _platform_cache is None:
            import jax

            _platform_cache = jax.default_backend()
        return _platform_cache


def resolve(method: str, reduce: str = "sum",
            platform: str | None = None) -> str:
    """``"auto"`` -> the measured winner for (platform, reduce); concrete
    methods pass through unchanged (explicit user choice always wins)."""
    if method != "auto":
        return method
    plat = platform if platform is not None else default_platform()
    chosen = _file_winners().get(
        (plat, reduce), WINNERS.get((plat, reduce), FALLBACK)
    )
    assert chosen in CONCRETE, (chosen, plat, reduce)
    return chosen
