"""mxscan: the MXU-resident blocked segmented scan (ISSUE 11).

Pins, all in interpret mode on CPU (correctness never waits on a chip
window):

1. the kernel (ops/pallas_scan.mxscan_segmented) matches a pure-Python
   segmented-scan reference — carry across rows AND tiles, padding
   neutralized in-kernel, vmapped over parts;
2. the ``segment_*_csc`` method="mxscan" path is BITWISE equal to the
   VPU ladder for int32 sums and min/max (f32 and bf16 included) across
   segment geometries — empty segments, single-element segments, one
   all-covering segment, hubs, ragged tails vs the tile size — and
   within the documented tolerance for f32/bf16 float sums (the MXU
   contraction owns its deterministic association, like mxsum vs scan);
3. (E, K) values fall back to the VPU scan bitwise; the bucketed
   row_ptr-free path (segment_reduce_by_ends) runs mxscan for 1-D
   values, downgrades prefix-diff strategies to 'scan', and its
   validator names the accepted set and env knob;
4. the mxsum 1-D-only restriction is LIFTED: matmul_cumsum handles
   (E, K) values (the former silent degrade to a plain cumsum is gone);
5. engine-vs-direct parity through pull (pagerank, tolerance) and push
   (sssp, bitwise — min never touches the MXU), plus the zero-retrace
   contract: segment geometry is data, one compile serves every census;
6. ``sum_mode()``/``resolve_sum()`` resolution: env override, the
   banked ``tpu:sum`` overlay winner followed on TPU only (CPU runs
   bitwise-unchanged), explicit methods passing through untouched;
7. roofline + audit: mxscan is accounted (REDUCE_HBM_PASSES/byte/flop
   models), the LUX-J4 residency ledger and LUX-J501 one-kernel
   accounting run clean, and a seeded over-budget geometry is a
   finding.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lux_tpu.engine import methods
from lux_tpu.ops import pallas_scan as PS
from lux_tpu.ops import segment


def _ref_scan(vals, heads, op):
    """Pure-Python inclusive segmented scan (the oracle)."""
    out = np.empty_like(vals)
    fn = {"sum": np.add, "min": np.minimum, "max": np.maximum}[op]
    acc = None
    for i in range(len(vals)):
        acc = vals[i] if (heads[i] or acc is None) else fn(acc, vals[i])
        out[i] = acc
    return out


def _csc(widths, pad=0, pad_value=0):
    """(row_ptr, head_flag, dst_local, e_pad) for explicit segment
    widths — the geometry knob every bitwise test turns."""
    widths = np.asarray(widths, np.int64)
    rp = np.concatenate([[0], np.cumsum(widths)]).astype(np.int32)
    ne = int(rp[-1])
    e_pad = ne + pad
    head = np.zeros(e_pad, bool)
    starts = rp[:-1][rp[1:] > rp[:-1]]
    head[starts] = True
    dst = np.full(e_pad, len(widths), np.int32)
    dst[:ne] = np.repeat(np.arange(len(widths), dtype=np.int32), widths)
    return rp, head, dst, e_pad


def _seg_oracle(widths, vals, op, dtype):
    neutral = {"sum": 0,
               "min": (np.inf if np.issubdtype(dtype, np.floating)
                       else np.iinfo(dtype).max),
               "max": (-np.inf if np.issubdtype(dtype, np.floating)
                       else np.iinfo(dtype).min)}[op]
    out = np.full(len(widths), neutral,
                  np.float64 if np.issubdtype(dtype, np.floating)
                  else np.int64)
    fn = {"sum": np.add, "min": np.minimum, "max": np.maximum}[op]
    e = 0
    for i, w in enumerate(widths):
        for _ in range(int(w)):
            out[i] = fn(out[i], vals[e])
            e += 1
    return out


#: the geometry matrix of the ISSUE: empty segments, single-element
#: segments, one all-covering segment, a hub, ragged tails vs the
#: (8, 128) default tile, and widths spanning row/tile boundaries
GEOMETRIES = [
    ("empties", [0, 3, 0, 0, 5, 0, 2, 0]),
    ("singles", [1] * 70),
    ("one_segment", [517]),
    ("hub", [600, 1, 0, 7, 1]),
    ("ragged_tail", [100, 100, 100, 29]),  # 329: not a lane multiple
    ("tile_spanning", [90, 300, 700, 41]),  # crosses rows AND tiles
]


# ---------------------------------------------------------------------------
# the kernel, against the pure-Python oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("n", [5, 128, 1024, 5000])
def test_kernel_matches_reference(op, n, rng):
    heads = rng.random(n) < 0.1
    heads[0] = True
    vals = rng.standard_normal(n).astype(np.float32)
    inv = np.zeros(n, bool)
    got = np.asarray(PS.mxscan_segmented(
        jnp.asarray(vals), jnp.asarray(heads), jnp.asarray(inv), op=op))
    want = _ref_scan(vals, heads, op)
    if op == "sum":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


def test_kernel_int32_bitwise(rng):
    n = 3000
    heads = rng.random(n) < 0.05
    heads[0] = True
    vals = rng.integers(-10_000, 10_000, n).astype(np.int32)
    got = np.asarray(PS.mxscan_segmented(
        jnp.asarray(vals), jnp.asarray(heads),
        jnp.asarray(np.zeros(n, bool)), op="sum"))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _ref_scan(vals, heads, "sum"))


def test_kernel_carry_spans_tiles(rng):
    """ONE segment covering many (8, 128) tiles: the scratch carry must
    thread every row and tile boundary."""
    n = 5 * 8 * 128 + 77
    heads = np.zeros(n, bool)
    heads[0] = True
    vals = np.ones(n, np.float32)
    got = np.asarray(PS.mxscan_segmented(
        jnp.asarray(vals), jnp.asarray(heads),
        jnp.asarray(np.zeros(n, bool)), op="sum"))
    # integer-valued f32: exact under any association
    np.testing.assert_array_equal(got, np.arange(1, n + 1, dtype=np.float32))


def test_kernel_masks_nonfinite_padding(rng):
    """NaN/Inf junk in PADDING slots must not poison real outputs (the
    0 * NaN = NaN matmul hazard, docs/PERF.md precision caveat)."""
    n = 400
    heads = rng.random(n) < 0.1
    heads[0] = True
    vals = rng.standard_normal(n).astype(np.float32)
    vals[-20:] = np.nan
    vals[-21] = np.inf
    inv = np.zeros(n, bool)
    inv[-21:] = True
    got = np.asarray(PS.mxscan_segmented(
        jnp.asarray(vals), jnp.asarray(heads), jnp.asarray(inv),
        op="sum"))
    want = _ref_scan(np.where(inv, 0, vals), heads, "sum")
    np.testing.assert_allclose(got[:-21], want[:-21], rtol=1e-5,
                               atol=1e-5)
    assert np.isfinite(got[:-21]).all()


def test_kernel_vmapped_parts_isolated(rng):
    """vmap over parts: the sequential carry resets at tile 0 of every
    batch element (the engine's multi-part dispatch)."""
    P, n = 3, 700
    vals = rng.standard_normal((P, n)).astype(np.float32)
    heads = rng.random((P, n)) < 0.08
    heads[:, 0] = True
    inv = np.zeros((P, n), bool)
    got = np.asarray(jax.vmap(
        lambda v, h, i: PS.mxscan_segmented(v, h, i, op="sum"))(
            jnp.asarray(vals), jnp.asarray(heads), jnp.asarray(inv)))
    want = np.stack([_ref_scan(vals[p], heads[p], "sum")
                     for p in range(P)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_kernel_validators():
    v = jnp.ones((4, 2), jnp.float32)
    with pytest.raises(ValueError, match="1-D"):
        PS.mxscan_segmented(v, jnp.ones((4, 2), bool),
                            jnp.zeros((4, 2), bool))
    with pytest.raises(ValueError, match="sum"):
        PS.mxscan_segmented(jnp.ones(4), jnp.ones(4, bool),
                            jnp.zeros(4, bool), op="prod")
    with pytest.raises(ValueError, match="LUX_MXSCAN_TILE_ROWS"):
        PS._mxscan_defaults(3)


# ---------------------------------------------------------------------------
# segment_*_csc: the bitwise matrix across geometries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,widths", GEOMETRIES)
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_csc_int32_bitwise_across_geometries(name, widths, op, rng):
    """int32 across the geometry matrix: mxscan == scan BITWISE (and
    both == the oracle) — integer combines are order-insensitive."""
    rp, head, dst, e_pad = _csc(widths, pad=rng.integers(0, 40))
    vals = np.full(e_pad, 123456, np.int32)  # junk pad, masked in-kernel
    ne = int(rp[-1])
    vals[:ne] = rng.integers(-50_000, 50_000, ne)
    fn = {"sum": segment.segment_sum_csc, "min": segment.segment_min_csc,
          "max": segment.segment_max_csc}[op]
    args = (jnp.asarray(vals), jnp.asarray(rp), jnp.asarray(head),
            jnp.asarray(dst))
    ref = np.asarray(fn(*args, method="scan"))
    got = np.asarray(fn(*args, method="mxscan"))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(ref, got, err_msg=name)
    oracle = _seg_oracle(widths, vals[:ne], op, np.int32)
    np.testing.assert_array_equal(got, oracle.astype(np.int32))


@pytest.mark.parametrize("op", ["min", "max"])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_csc_float_minmax_bitwise(op, dtype, rng):
    """min/max never touch the MXU: dtype-preserving, bitwise vs the
    ladder — Inf sentinels included (the sssp shape)."""
    rp, head, dst, e_pad = _csc([5, 0, 900, 1, 33, 0, 7], pad=13)
    vals_np = rng.standard_normal(e_pad).astype(np.float32)
    vals_np[3] = np.inf
    vals = jnp.asarray(vals_np)
    if dtype == "bfloat16":
        vals = vals.astype(jnp.bfloat16)
    fn = (segment.segment_min_csc if op == "min"
          else segment.segment_max_csc)
    args = (vals, jnp.asarray(rp), jnp.asarray(head), jnp.asarray(dst))
    ref = fn(*args, method="scan")
    got = fn(*args, method="mxscan")
    assert got.dtype == vals.dtype
    np.testing.assert_array_equal(
        np.asarray(ref.astype(jnp.float32)),
        np.asarray(got.astype(jnp.float32)))


@pytest.mark.parametrize("name,widths", GEOMETRIES)
def test_csc_f32_sum_tolerance(name, widths, rng):
    """General f32 sums: mxscan's own deterministic association, equal
    to the f64 oracle within the documented tolerance (rtol 1e-5 —
    accumulation stays WITHIN a segment, in f32, so there is no
    global-prefix caveat) and run-to-run deterministic."""
    rp, head, dst, e_pad = _csc(widths, pad=7)
    vals = np.zeros(e_pad, np.float32)
    ne = int(rp[-1])
    vals[:ne] = rng.standard_normal(ne)
    args = (jnp.asarray(vals), jnp.asarray(rp), jnp.asarray(head),
            jnp.asarray(dst))
    got = np.asarray(segment.segment_sum_csc(*args, method="mxscan"))
    oracle = _seg_oracle(widths, vals[:ne], "sum", np.float32)
    np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5,
                               err_msg=name)
    np.testing.assert_array_equal(
        got, np.asarray(segment.segment_sum_csc(*args, method="mxscan")))


def test_csc_bf16_sum_tolerance(rng):
    """bf16 sums: bf16 operands (already the storage precision), f32
    accumulation in-kernel, ONE rounding back to bf16 per tile row —
    strictly tighter than the ladder's per-element bf16 rounding, so
    the pin is against the f32 oracle at bf16 input resolution."""
    rp, head, dst, e_pad = _csc([40, 0, 300, 9, 1], pad=5)
    ne = int(rp[-1])
    vals_np = rng.standard_normal(e_pad).astype(np.float32)
    vals = jnp.asarray(vals_np).astype(jnp.bfloat16)
    got = segment.segment_sum_csc(
        vals, jnp.asarray(rp), jnp.asarray(head), jnp.asarray(dst),
        method="mxscan")
    assert got.dtype == jnp.bfloat16
    oracle = _seg_oracle(
        [40, 0, 300, 9, 1],
        np.asarray(vals.astype(jnp.float32))[:ne], "sum", np.float32)
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)), oracle, rtol=2e-2,
        atol=2e-2)


def test_csc_f32_exact_case_bitwise(rng):
    """Integer-valued f32 sums are exact under ANY association: mxscan
    must equal the ladder bit for bit."""
    rp, head, dst, e_pad = _csc([3, 200, 0, 57, 1000, 1], pad=11)
    vals = np.zeros(e_pad, np.float32)
    ne = int(rp[-1])
    vals[:ne] = rng.integers(-1000, 1000, ne).astype(np.float32)
    args = (jnp.asarray(vals), jnp.asarray(rp), jnp.asarray(head),
            jnp.asarray(dst))
    ref = np.asarray(segment.segment_sum_csc(*args, method="scan"))
    got = np.asarray(segment.segment_sum_csc(*args, method="mxscan"))
    np.testing.assert_array_equal(ref, got)


def test_csc_2d_falls_back_to_scan_bitwise(rng):
    """(E, K) values off TPU: the blocked kernel is 1-D, so
    method='mxscan' must produce EXACTLY the ladder scan's bits (the
    engine-safety contract — a csc winner can never crash the CF/feat
    shapes)."""
    rp, head, dst, e_pad = _csc([10, 0, 25, 3], pad=4)
    vals = rng.standard_normal((e_pad, 5)).astype(np.float32)
    args = (jnp.asarray(vals), jnp.asarray(rp), jnp.asarray(head),
            jnp.asarray(dst))
    for fn in (segment.segment_sum_csc, segment.segment_min_csc):
        ref = np.asarray(fn(*args, method="scan"))
        got = np.asarray(fn(*args, method="mxscan"))
        np.testing.assert_array_equal(ref, got)


def test_csc_2d_on_tpu_takes_scatter(monkeypatch, rng):
    """(E, K) values on TPU: method='mxscan' runs XLA's scatter (the
    ladder scan does not compile at scale there), bit for bit, with no
    kernel and no scan in the program."""
    from lux_tpu.analysis.ir import aot

    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    assert segment.csc_method("mxscan", 2) == "scatter"
    assert segment.csc_method("mxscan", 1) == "mxscan"
    assert segment.csc_method("scan", 2) == "scan"
    rp, head, dst, e_pad = _csc([10, 0, 25, 3], pad=4)
    vals = rng.standard_normal((e_pad, 5)).astype(np.float32)
    args = (jnp.asarray(vals), jnp.asarray(rp), jnp.asarray(head),
            jnp.asarray(dst))
    for fn in (segment.segment_sum_csc, segment.segment_min_csc,
               segment.segment_max_csc):
        ref = np.asarray(fn(*args, method="scatter"))
        np.testing.assert_array_equal(
            ref, np.asarray(fn(*args, method="mxscan")))
        jaxpr = aot.traced_jaxpr(jax.jit(
            lambda v, fn=fn: fn(v, *args[1:], method="mxscan")).trace(
                args[0]))
        assert aot.count_primitive(jaxpr, "pallas_call") == 0
        assert aot.count_primitive(jaxpr, "cumsum") == 0
        assert sum(aot.count_primitive(jaxpr, p) for p in (
            "scatter-add", "scatter_add", "scatter-min", "scatter_min",
            "scatter-max", "scatter_max")) == 1


def test_colfilter_auto_on_tpu_is_scatter(monkeypatch, tmp_path):
    """The CF engine on TPU with method='auto': the csc resolution says
    mxscan, the (E, K) values run scatter — bitwise the explicit
    scatter run — and no iteration counts as a kernel iteration."""
    from lux_tpu import obs
    from lux_tpu.graph import generate
    from lux_tpu.models import colfilter as cf
    from lux_tpu.obs.recorder import Recorder

    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    g = generate.rmat(7, 6, seed=11, weighted=True)
    want = cf.colfilter(g, num_iters=3, gamma=1e-3, method="scatter")
    rec = Recorder(run_id="cf", root=str(tmp_path), enabled=True)
    old = obs.install(rec)
    try:
        got = cf.colfilter(g, num_iters=3, gamma=1e-3, method="auto")
        counts = obs.counters("pull.")
    finally:
        rec.close()
        obs.install(old)
    np.testing.assert_array_equal(got, want)
    assert counts["pull.iterations"] == (1, 3)
    assert counts["pull.mxscan_iterations"] == (1, 0)


@pytest.fixture(scope="module")
def graph500_csc():
    """A Graph500-shaped segment geometry at scale 12: the Kronecker
    graph's in-degrees (empty rows included), one hub widened past two
    default kernel tiles, and padding past row_ptr[-1]."""
    from lux_tpu.graph import generate

    g = generate.rmat(12, 16, seed=24)
    widths = np.bincount(g.dst_of_edges(), minlength=g.nv)
    hub = int(np.argmax(widths))
    widths[hub] = 2 * PS._mxscan_defaults() * 128 + 7000
    assert (widths == 0).sum() > 100
    return (widths,) + _csc(widths, pad=300)


@pytest.mark.parametrize("op", ["sum", "min"])
def test_graph500_shaped_at_default_tile(graph500_csc, op, rng):
    """At the default tile geometry (128 rows): f32 sums equal the
    scatter's within the existing tolerance, int32 mins bitwise."""
    widths, rp, head, dst, e_pad = graph500_csc
    assert PS._mxscan_defaults() == 128
    assert max(widths) > 2 * 128 * 128 and e_pad > rp[-1]
    if op == "sum":
        vals = rng.random(e_pad).astype(np.float32)
        fn = segment.segment_sum_csc
    else:
        vals = rng.integers(0, 1 << 30, e_pad).astype(np.int32)
        fn = segment.segment_min_csc
    vals[rp[-1]:] = vals[0] if op == "min" else np.float32(np.nan)
    args = (jnp.asarray(vals), jnp.asarray(rp), jnp.asarray(head),
            jnp.asarray(dst))
    ref = np.asarray(fn(*args, method="scatter"))
    got = np.asarray(fn(*args, method="mxscan"))
    assert got.dtype == vals.dtype
    if op == "sum":
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            got, _seg_oracle(widths, vals[:rp[-1]], "sum", np.float32),
            rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("tile_rows", [1, 2, 32])
def test_tile_rows_knob_geometries_bitwise(tile_rows, rng):
    """Every legal tile geometry lands identical bits for the exact
    cases — the knob shapes the kernel, never the math."""
    n = 1000
    heads = rng.random(n) < 0.07
    heads[0] = True
    vals = rng.integers(-500, 500, n).astype(np.int32)
    inv = np.zeros(n, bool)
    base = np.asarray(PS.mxscan_segmented(
        jnp.asarray(vals), jnp.asarray(heads), jnp.asarray(inv),
        op="sum"))
    got = np.asarray(PS.mxscan_segmented(
        jnp.asarray(vals), jnp.asarray(heads), jnp.asarray(inv),
        op="sum", tile_rows=tile_rows))
    np.testing.assert_array_equal(base, got)


# ---------------------------------------------------------------------------
# the bucketed (row_ptr-free) path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reduce", ["sum", "min", "max"])
def test_by_ends_mxscan(reduce, rng):
    from lux_tpu.parallel.ring import mark_bucket_heads

    V, m, B = 37, 60, 128
    dl = np.sort(rng.integers(0, V, size=m)).astype(np.int32)
    dst = np.full(B, V, np.int32)
    dst[:m] = dl
    head = np.zeros(B, bool)
    mark_bucket_heads(head, dl)
    vals = np.full(B, np.nan, np.float32)  # junk pads, sentinel-masked
    vals[:m] = rng.random(m).astype(np.float32) + 0.5
    args = (jnp.asarray(vals), jnp.asarray(head), jnp.asarray(dst), V)
    ref = np.asarray(segment.segment_reduce_by_ends(
        *args, reduce=reduce, method="scan"))
    got = np.asarray(segment.segment_reduce_by_ends(
        *args, reduce=reduce, method="mxscan"))
    if reduce == "sum":
        np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_array_equal(ref, got)


def test_by_ends_mxscan_full_bucket():
    """m == B: the appended end flag must close the final segment (the
    ladder contract, now through the kernel)."""
    V, B = 5, 8
    dl = np.array([0, 0, 1, 1, 1, 3, 4, 4], np.int32)
    from lux_tpu.parallel.ring import mark_bucket_heads

    head = np.zeros(B, bool)
    mark_bucket_heads(head, dl)
    vals = np.arange(1, 9, dtype=np.float32)
    got = segment.segment_reduce_by_ends(
        jnp.asarray(vals), jnp.asarray(head), jnp.asarray(dl), V,
        reduce="sum", method="mxscan")
    np.testing.assert_allclose(np.asarray(got), [3, 12, 0, 6, 15])


def test_by_ends_downgrades_and_validator(rng):
    """cumsum/mxsum (and mxscan on (E, K)) downgrade to the shipped
    'scan' BITWISE; an unknown method's error names the accepted set
    and the env knob (the ISSUE's validator satellite)."""
    from lux_tpu.parallel.ring import mark_bucket_heads

    V, m, B = 11, 30, 64
    dl = np.sort(rng.integers(0, V, size=m)).astype(np.int32)
    dst = np.full(B, V, np.int32)
    dst[:m] = dl
    head = np.zeros(B, bool)
    mark_bucket_heads(head, dl)
    vals = np.zeros(B, np.float32)
    vals[:m] = rng.random(m).astype(np.float32)
    args = (jnp.asarray(vals), jnp.asarray(head), jnp.asarray(dst), V)
    ref = np.asarray(segment.segment_reduce_by_ends(
        *args, reduce="sum", method="scan"))
    for m_ in ("cumsum", "mxsum"):
        got = np.asarray(segment.segment_reduce_by_ends(
            *args, reduce="sum", method=m_))
        np.testing.assert_array_equal(ref, got)
    vk = jnp.asarray(rng.random((B, 3)).astype(np.float32))
    ref_k = np.asarray(segment.segment_reduce_by_ends(
        vk, jnp.asarray(head), jnp.asarray(dst), V, reduce="sum",
        method="scan"))
    got_k = np.asarray(segment.segment_reduce_by_ends(
        vk, jnp.asarray(head), jnp.asarray(dst), V, reduce="sum",
        method="mxscan"))
    np.testing.assert_array_equal(ref_k, got_k)
    with pytest.raises(ValueError, match="LUX_SUM_MODE"):
        segment.segment_reduce_by_ends(*args, reduce="sum",
                                       method="bogus")


def test_csc_validators_name_set_and_knob():
    v = jnp.ones(8, jnp.float32)
    rp = jnp.asarray(np.array([0, 8], np.int32))
    hf = jnp.asarray(np.array([True] + [False] * 7))
    with pytest.raises(ValueError, match="LUX_SUM_MODE"):
        segment.segment_sum_csc(v, rp, hf, method="bogus")
    with pytest.raises(ValueError, match="LUX_SUM_MODE"):
        segment.segment_min_csc(v, rp, hf, method="mxsum")


# ---------------------------------------------------------------------------
# the lifted mxsum restriction
# ---------------------------------------------------------------------------


def test_matmul_cumsum_2d_lifted(rng):
    """matmul_cumsum now handles (E, K) values (the former silent
    degrade to a plain cumsum is gone — ISSUE 11 satellite)."""
    for shape in ((7, 3), (513, 4), (5000, 2)):
        x = rng.random(shape).astype(np.float32)
        got = np.asarray(segment.matmul_cumsum(jnp.asarray(x)))
        want = np.cumsum(x.astype(np.float64), axis=0)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)


def test_segment_sum_2d_mxsum_rides_matmul(rng):
    """(E, K) mxsum goes through the triangular-matmul cumsum and still
    matches the oracle within the documented global-prefix tolerance."""
    from lux_tpu.graph import generate
    from lux_tpu.graph.shards import build_pull_shards

    g = generate.uniform_random(60, 400, seed=5)
    sh = build_pull_shards(g, 1)
    arr = sh.arrays
    K = 8
    vals = np.zeros((sh.spec.e_pad, K), np.float32)
    vals[: g.ne] = rng.random((g.ne, K))
    out = segment.segment_sum_csc(
        jnp.asarray(vals), jnp.asarray(arr.row_ptr[0]),
        jnp.asarray(arr.head_flag[0]), method="mxsum")
    dst = g.dst_of_edges()
    expect = np.zeros((g.nv, K), np.float32)
    np.add.at(expect, dst, vals[: g.ne])
    np.testing.assert_allclose(np.asarray(out)[: g.nv], expect,
                               rtol=5e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# engine parity + zero-retrace
# ---------------------------------------------------------------------------


def test_pull_engine_mxscan_matches_scan():
    from lux_tpu.graph import generate
    from lux_tpu.models import pagerank as pr

    g = generate.rmat(8, 8, seed=15)
    for parts in (1, 3):
        base = pr.pagerank(g, num_iters=5, method="scan",
                           num_parts=parts)
        got = pr.pagerank(g, num_iters=5, method="mxscan",
                          num_parts=parts)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(base, np.float64),
            rtol=1e-4, atol=1e-7)


def test_push_engine_mxscan_bitwise():
    """Push (sssp, reduce=min): mxscan's min path never touches the
    MXU, so the whole frontier run is BITWISE the scan engine's."""
    from lux_tpu.graph import generate
    from lux_tpu.models import sssp as ss

    g = generate.rmat(8, 8, seed=5)
    a = ss.sssp(g, 0, method="scan")
    b = ss.sssp(g, 0, method="mxscan")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_dispatch_through_sum_mode(monkeypatch):
    """The end-to-end wiring: with a forced scan-family winner,
    method='auto' on a (virtual) TPU platform must produce EXACTLY the
    explicit method='mxscan' run — the resolver the engines consult."""
    from lux_tpu.graph import generate
    from lux_tpu.models import pagerank as pr

    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    monkeypatch.setenv("LUX_SUM_MODE", "mxscan")
    g = generate.rmat(8, 4, seed=3)
    auto = pr.pagerank(g, num_iters=4, method="auto")
    explicit = pr.pagerank(g, num_iters=4, method="mxscan")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


def test_zero_retrace_across_geometries(rng):
    """Segment geometry is DATA: one compile serves every census (the
    LUX-J1 contract the audit unit also pins)."""
    n = 800

    @jax.jit
    def run(v, rp, hf, dl):
        return segment.segment_sum_csc(v, rp, hf, dl, method="mxscan")

    for widths in ([100, 300, 390], [1] * 79, [779]):
        rp, head, dst, e_pad = _csc(widths, pad=n - sum(widths) - 1)
        # pad out to ONE shared shape so only the geometry values vary
        vals = np.zeros(n, np.float32)
        rp_fix = np.zeros(80 + 1, np.int32)
        rp_fix[1:len(rp)] = rp[1:]
        rp_fix[len(rp):] = rp[-1]
        head_fix = np.zeros(n, bool)
        head_fix[:len(head)] = head
        dst_fix = np.full(n, 80, np.int32)
        dst_fix[:len(dst)] = dst
        run(jnp.asarray(vals), jnp.asarray(rp_fix),
            jnp.asarray(head_fix), jnp.asarray(dst_fix))
    assert run._cache_size() == 1


# ---------------------------------------------------------------------------
# sum_mode / resolve_sum resolution
# ---------------------------------------------------------------------------


def _reset_overlay_caches(monkeypatch):
    monkeypatch.setattr(methods, "_overlay_raw_cache", None)
    monkeypatch.setattr(methods, "_file_winners_cache", None)
    monkeypatch.setattr(methods, "_tiles_cache", None)


def test_sum_mode_default_and_env(monkeypatch):
    monkeypatch.delenv("LUX_SUM_MODE", raising=False)
    assert methods.sum_mode("tpu") == "scan"
    assert methods.sum_mode("cpu") == "scan"
    monkeypatch.setenv("LUX_SUM_MODE", "mxscan")
    assert methods.sum_mode("cpu") == "mxscan"  # env = explicit choice
    # the env choice wins under auto EVERYWHERE — including platforms
    # whose blanket winner is not "scan" (the review fix: 'LUX_SUM_MODE
    # forces a flavor anywhere' must hold on the CPU scatter default)
    assert methods.resolve_sum("auto", "sum", "cpu") == "mxscan"
    assert methods.resolve_sum("auto", "sum", "tpu") == "mxscan"
    assert methods.resolve_sum("auto", "min", "cpu") == "scatter"
    assert methods.resolve_sum("scatter", "sum", "cpu") == "scatter"
    monkeypatch.setenv("LUX_SUM_MODE", "bogus")
    with pytest.raises(ValueError, match="LUX_SUM_MODE"):
        methods.sum_mode("tpu")


def test_sum_mode_follows_banked_winner_tpu_only(monkeypatch, tmp_path, scan_tpu_rows):
    """The acceptance contract: a banked tpu:sum winner retires the VPU
    default ON TPU ONLY — CPU resolution is bitwise-unchanged."""
    import json

    f = tmp_path / "w.json"
    f.write_text(json.dumps({"tpu:sum": "mxscan"}))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    _reset_overlay_caches(monkeypatch)
    assert methods.sum_mode("tpu") == "mxscan"
    assert methods.sum_mode("cpu") == "scan"
    assert methods.resolve_sum("auto", "sum", "tpu") == "mxscan"
    # min/max take the csc table's TPU row (the banked entry is
    # sum-only), CPU rows are untouched, an explicit choice wins
    assert methods.resolve_sum("auto", "min", "tpu") == "mxscan"
    assert methods.resolve_sum("auto", "sum", "cpu") == "scatter"
    assert methods.resolve_sum("scan", "sum", "tpu") == "scan"
    assert methods.resolve_sum("mxsum", "sum", "tpu") == "mxsum"
    # blanket resolve() is UNCHANGED by a scan-family entry (the
    # bucketed layouts' contract): mxscan is not a blanket winner
    assert methods.resolve("auto", "sum", "tpu") == "scan"
    _reset_overlay_caches(monkeypatch)


def test_sum_mode_ignores_non_family_entries(monkeypatch, tmp_path, scan_tpu_rows):
    """tpu:sum may also hold the app-race's blanket winner ('scatter'):
    sum_mode ignores it (resolve() follows it, the csc engines keep
    their table's row) and a garbage entry reads as the default."""
    import json

    f = tmp_path / "w.json"
    f.write_text(json.dumps({"tpu:sum": "scatter"}))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    _reset_overlay_caches(monkeypatch)
    assert methods.sum_mode("tpu") == "scan"
    assert methods.resolve("auto", "sum", "tpu") == "scatter"
    assert methods.resolve_sum("auto", "sum", "tpu") == "mxscan"
    f.write_text(json.dumps({"tpu:sum": "pallas"}))
    _reset_overlay_caches(monkeypatch)
    assert methods.sum_mode("tpu") == "scan"
    assert methods.resolve("auto", "sum", "tpu") == "scan"
    assert methods.resolve_sum("auto", "sum", "tpu") == "mxscan"
    _reset_overlay_caches(monkeypatch)


def test_mxsum_banked_follows_on_csc_paths(monkeypatch, tmp_path, scan_tpu_rows):
    """mxsum banked under tpu:sum (possible: it is in the three-way
    race) flows to the csc engines through the SAME refinement."""
    import json

    f = tmp_path / "w.json"
    f.write_text(json.dumps({"tpu:sum": "mxsum"}))
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    _reset_overlay_caches(monkeypatch)
    assert methods.resolve_sum("auto", "sum", "tpu") == "mxsum"
    assert methods.resolve("auto", "sum", "tpu") == "scan"
    _reset_overlay_caches(monkeypatch)


def test_cli_auto_reaches_banked_winner(monkeypatch, capsys, scan_tpu_rows):
    """The review fix: the app CLIs pre-resolve --method auto through
    resolve_sum, so a banked/forced scan-family winner actually reaches
    the engines from `python -m lux_tpu.apps.*` — and downgrades (with
    a note) before the bucketed exchanges, where an EXPLICIT choice
    still fails loudly."""
    from lux_tpu.apps import common
    from lux_tpu.models.pagerank import PageRankProgram
    from lux_tpu.utils.config import parse_args

    monkeypatch.setenv("LUX_METHOD_PLATFORM", "tpu")
    monkeypatch.setenv("LUX_SUM_MODE", "mxscan")
    prog = PageRankProgram(nv=16)
    cfg = parse_args([], pull=True)
    common.validate_exchange(cfg, prog)
    assert cfg.method == "mxscan"
    cfg = parse_args(["--distributed", "--exchange", "ring"], pull=True)
    common.validate_exchange(cfg, prog)
    assert cfg.method == "scan"  # blanket winner, with a stderr note
    assert "downgraded" in capsys.readouterr().err
    cfg = parse_args(["--distributed", "--exchange", "ring",
                      "--method", "mxscan"], pull=True)
    with pytest.raises(SystemExit, match="scan or scatter"):
        common.validate_exchange(cfg, prog)


def test_record_sum_family_winner_preserves_scatter(monkeypatch,
                                                    tmp_path):
    """The review fix: a scan-family race (which never times scatter)
    must not clobber a measured blanket 'scatter' tpu:sum winner; any
    other prior value may be overwritten (last full measurement
    wins)."""
    import json

    f = tmp_path / "w.json"
    monkeypatch.setenv("LUX_METHOD_WINNERS", str(f))
    _reset_overlay_caches(monkeypatch)
    assert methods.record_sum_family_winner("mxscan") is True
    assert json.loads(f.read_text())["tpu:sum"] == "mxscan"
    methods.record_overlay_entry("tpu:sum", "scatter")
    assert methods.record_sum_family_winner("mxsum") is False
    assert json.loads(f.read_text())["tpu:sum"] == "scatter"
    methods.record_overlay_entry("tpu:sum", "scan")
    assert methods.record_sum_family_winner("mxsum") is True
    assert json.loads(f.read_text())["tpu:sum"] == "mxsum"
    _reset_overlay_caches(monkeypatch)


def test_concrete_set_includes_mxscan():
    assert "mxscan" in methods.CONCRETE
    assert methods.SUM_MODES == ("scan", "mxsum", "mxscan")
    assert methods.SUM_MODE_KEY == "tpu:sum"


# ---------------------------------------------------------------------------
# accounting + audit
# ---------------------------------------------------------------------------


def test_roofline_accounts_mxscan():
    from lux_tpu.utils import roofline

    assert roofline.REDUCE_HBM_PASSES["mxscan"] == 2
    passes = roofline.pull_hbm_passes("mxscan")
    assert passes["total"] == roofline.pull_hbm_passes("scan")["total"]
    # bytes: the packed head/pad byte costs +2 B/edge over the ladder's
    # optimistic floor; flops: 2 contractions x T MACs per value
    b_scan = roofline._reduce_bytes_per_edge("scan", 4, 1)
    b_mx = roofline._reduce_bytes_per_edge("mxscan", 4, 1)
    assert b_mx == b_scan + 2
    assert (roofline._reduce_device_flops_per_edge("mxscan", 1)
            == 4 * roofline.MXSCAN_T)
    m = roofline.pull_iter_model(1000, 100, "mxscan")
    assert m.bytes_moved > 0 and m.device_flops > m.flops


def test_audit_units_clean_and_seeded():
    from lux_tpu.analysis.ir import targets, vmem

    assert targets._retrace_pull_fixed_mxscan() == []
    assert targets._vmem_mxscan() == []
    assert targets._hbm_mxscan() == []
    assert targets._hbm_mxscan_ring_neutral() == []
    findings = vmem.check_vmem_mxscan("p", "t", budget_bytes=1)
    assert len(findings) == 1 and findings[0].code == "LUX-J401"
    assert findings[0].text == "t:mxscan"
    labels = {u.label for u in targets.audit_units()}
    assert {"pull-fixed/mxscan", "mxscan", "segment/mxscan",
            "pull-fixed/mxscan/ring-neutral"} <= labels


def test_mxscan_kernel_count_is_one(rng):
    """The LUX-J501 claim behind the exact '2 sweeps' accounting: one
    csc segment sum on method='mxscan' launches exactly ONE kernel."""
    from lux_tpu.analysis.ir import aot

    rp, head, dst, e_pad = _csc([30, 100, 5], pad=9)
    vals = jnp.zeros(e_pad, jnp.float32)

    traced = jax.jit(
        lambda v: segment.segment_sum_csc(
            v, jnp.asarray(rp), jnp.asarray(head), jnp.asarray(dst),
            method="mxscan")).trace(vals)
    assert aot.count_primitive(aot.traced_jaxpr(traced),
                               "pallas_call") == 1


def test_residency_model_positive():
    assert PS.mxscan_residency_bytes(8) > 0
    assert (PS.mxscan_residency_bytes(16)
            > PS.mxscan_residency_bytes(1))
