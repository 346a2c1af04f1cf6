"""Per-destination segment reductions over CSC edge blocks.

TPU-native replacement for the reference's per-block CUB
``BlockScan::ExclusiveSum`` + edge-sweep + atomics pattern
(pagerank_gpu.cu:59-95, sssp_gpu.cu:94-130): CSC edges are already grouped by
destination, so each reduction is a *sorted* segmented reduction.  Three
interchangeable strategies, all deterministic (unlike the reference's
atomics):

  * ``scan``    — segmented inclusive scan via `lax.associative_scan` over
                  (value, head_flag) pairs, then gather each segment's last
                  element.  Log-depth, fully vectorized, numerically safe
                  (accumulation stays within a segment).
  * ``cumsum``  — plain cumsum + gather-diff at row boundaries (sum only).
                  Cheapest, but the global prefix magnitude costs float32
                  precision on large graphs.
  * ``mxsum``   — cumsum computed as blocked lower-triangular MATMULS
                  (the tensor-core scan construction of arXiv:1811.09736)
                  + the same gather-diff (sum only).  Rides the MXU: one
                  (B,T)x(T,T) contraction + a recursive block-offset scan
                  instead of a log-depth elementwise ladder.  Same global-
                  prefix precision caveat as ``cumsum``.
  * ``mxscan``  — the SEGMENTED scan itself as blocked triangular MXU
                  contractions (lux_tpu.ops.pallas_scan, ISSUE 11;
                  arXiv:2505.15112's blocked systolic scan): one Pallas
                  kernel, head flags masking the triangular operand so
                  restarts fold into the contraction, a carried
                  inter-tile offset through the sequential grid.  Float
                  sums accumulate in f32 (own deterministic association,
                  like mxsum vs scan — tolerance-equal to ``scan``);
                  min/max and integer sums use the same masked layout on
                  the VPU, BITWISE equal to ``scan``.  1-D values only:
                  (E, K) shapes take ``scatter`` on TPU and ``scan``
                  elsewhere (``csc_method``).  The csc engines' TPU
                  default (engine/methods.CSC_WINNERS).
  * ``scatter`` — `segment_sum/min/max` with sorted ids (XLA scatter).
                  The CPU default; on TPU, the default of the bucketed
                  exchanges and of (E, K) values.

All take static-shape padded inputs from lux_tpu.graph.shards.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def _segmented_scan(vals: jnp.ndarray, head_flag: jnp.ndarray, op: Callable):
    """Inclusive segmented scan: restarts accumulation at head_flag slots."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf

    out, _ = jax.lax.associative_scan(combine, (vals, head_flag))
    return out


def _ends_gather(scanned, row_ptr, neutral):
    """Pick each segment's final accumulated value; neutral for empty rows."""
    ends = row_ptr[1:] - 1
    nonempty = row_ptr[1:] > row_ptr[:-1]
    safe = jnp.clip(ends, 0, scanned.shape[0] - 1)
    nonempty = nonempty.reshape(nonempty.shape + (1,) * (scanned.ndim - 1))
    return jnp.where(nonempty, scanned[safe], neutral)


#: the ONE copy of the bucketed exchange drivers' method-assert text
#: (push-ring / pull-ring / scatter / feat share the invariant, so
#: they must share the words — a drifting copy would state false
#: guidance about where refined winners go)
BUCKETED_METHODS_NOTE = (
    "bucketed (row_ptr-free) exchange drivers accept method='scan' "
    "or 'scatter' only (--method / LUX_BENCH_METHOD); auto-resolved "
    "csc winners (mxscan on TPU, LUX_SUM_MODE's mxsum/mxscan) never "
    "reach this driver — they resolve through resolve_sum on the csc "
    "engines, and apps/common downgrades them before these "
    "exchanges — so pass 'scan' or 'scatter' explicitly")


def csc_method(method: str, ndim: int) -> str:
    """The strategy a csc reducer runs for ``method`` on values of rank
    ``ndim``.  The blocked kernel is 1-D, so (E, K) values asking for
    ``mxscan`` take XLA's scatter on TPU, where the ladder scan does not
    compile at scale (engine/methods), and the ladder scan elsewhere."""
    if method != "mxscan" or ndim == 1:
        return method
    from lux_tpu.engine import methods

    return "scatter" if methods.default_platform() == "tpu" else "scan"


def _mxscan_csc(vals, row_ptr, head_flag, op):
    """The mxscan scanned array for a csc-encoded segment reduction:
    slots at or past row_ptr[-1] are padding and neutralize in-kernel
    (lux_tpu.ops.pallas_scan precision caveat)."""
    from lux_tpu.ops import pallas_scan

    invalid = (jnp.arange(vals.shape[0], dtype=row_ptr.dtype)
               >= row_ptr[-1])
    return pallas_scan.mxscan_segmented(vals, head_flag, invalid, op=op)


MX_BLOCK = 512  # triangular-matmul tile for the mxsum cumsum


def matmul_cumsum(x: jnp.ndarray, block: int = MX_BLOCK) -> jnp.ndarray:
    """Inclusive cumsum along axis 0 as blocked triangular matmuls
    (MXU-friendly; arXiv:1811.09736 construction): per-block prefix =
    x2 @ L^T with L lower-triangular ones, block offsets by recursing on
    the block sums.  f32 accumulation throughout.  (E, K) values ride
    the same contraction with K batched along the free axis — this lifts
    the former 1-D-only restriction that silently degraded ``mxsum`` to
    a plain cumsum for CF/feat-shaped values (ISSUE 11)."""
    n = x.shape[0]
    if n == 0:
        return x
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    nb = xp.shape[0] // block
    tri = jnp.tril(jnp.ones((block, block), jnp.float32))
    if x.ndim == 1:
        x2 = xp.reshape(nb, block)
        intra = jax.lax.dot_general(
            x2.astype(jnp.float32), tri,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (nb, block): intra[b, i] = sum_{j<=i} x2[b, j]
        tots = intra[:, -1]
    else:
        x2 = xp.reshape((nb, block) + x.shape[1:])
        x2 = x2.reshape(nb, block, -1)  # (nb, block, K)
        intra = jax.lax.dot_general(
            x2.astype(jnp.float32), tri,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (nb, K, block): intra[b, k, i] = sum_{j<=i} x2[b, j, k]
        intra = jnp.swapaxes(intra, 1, 2)  # (nb, block, K)
        tots = intra[:, -1, :]
    if nb > block:
        incl = matmul_cumsum(tots, block)
    else:
        incl = jnp.cumsum(tots, axis=0)
    offs = incl - tots  # exclusive block offsets
    out = intra + offs[:, None]
    return out.reshape((-1,) + x.shape[1:])[:n].astype(x.dtype)


def segment_sum_csc(
    vals: jnp.ndarray,
    row_ptr: jnp.ndarray,
    head_flag: jnp.ndarray,
    dst_local: jnp.ndarray | None = None,
    method: str = "scan",
) -> jnp.ndarray:
    """Sum ``vals`` (edge-aligned, (E,) or (E, K)) per destination -> (V, ...)."""
    if method == "mxsum" and jnp.issubdtype(vals.dtype, jnp.integer):
        # matmul_cumsum accumulates in float32 UNCONDITIONALLY — exact
        # for the float sums the strategy was built for, but integer
        # sums (ISSUE 13's uint32 bitset unions / int32 alive counts)
        # must stay exact past 2^24: downgrade to the bitwise scan,
        # same family-downgrade contract as segment_reduce_by_ends's
        # (a banked tpu:sum=mxsum winner stays safe on every program)
        method = "scan"
    method = csc_method(method, vals.ndim)
    if method == "mxscan":
        scanned = _mxscan_csc(vals, row_ptr, head_flag, "sum")
        return _ends_gather(scanned, row_ptr, jnp.zeros((), vals.dtype))
    if method == "scan":
        flag = head_flag
        if vals.ndim > 1:
            flag = head_flag[:, None]
        scanned = _segmented_scan(vals, jnp.broadcast_to(flag, vals.shape), jnp.add)
        return _ends_gather(scanned, row_ptr, jnp.zeros((), vals.dtype))
    if method in ("cumsum", "mxsum"):
        if method == "mxsum":
            c = matmul_cumsum(vals)
        else:
            c = jnp.cumsum(vals, axis=0)
        zero = jnp.zeros((1,) + vals.shape[1:], vals.dtype)
        c = jnp.concatenate([zero, c], axis=0)
        return c[row_ptr[1:]] - c[row_ptr[:-1]]
    if method == "scatter":
        assert dst_local is not None
        return jax.ops.segment_sum(
            _scatter_dtype(vals), dst_local, num_segments=row_ptr.shape[0] - 1,
            indices_are_sorted=True,
        ).astype(vals.dtype)
    raise ValueError(
        f"segment_sum_csc: unknown method {method!r}; accepted: 'scan', "
        "'mxscan', 'cumsum', 'mxsum', 'scatter' (--method / "
        "LUX_BENCH_METHOD; the scan-family refinement is LUX_SUM_MODE, "
        "engine/methods.sum_mode)")


def _scatter_dtype(vals: jnp.ndarray) -> jnp.ndarray:
    """TPU XLA scatter has no native sub-f32 float update path — a bf16
    scatter-add lowers to a serialized emulation (measured ~1e4x slower than
    the f32 scatter on a v5-class chip).  Widen low-precision floats to f32
    for the scatter and round once on the way out; accumulation in f32 is
    also strictly better numerically."""
    if vals.dtype in (jnp.bfloat16, jnp.float16):
        return vals.astype(jnp.float32)
    return vals


def _segment_minmax(vals, row_ptr, head_flag, dst_local, op, neutral, method):
    method = csc_method(method, vals.ndim)
    if method == "mxscan":
        scanned = _mxscan_csc(vals, row_ptr, head_flag,
                              "min" if op is jnp.minimum else "max")
        return _ends_gather(scanned, row_ptr, neutral)
    if method == "scan":
        flag = head_flag
        if vals.ndim > 1:
            flag = head_flag.reshape(head_flag.shape + (1,) * (vals.ndim - 1))
        scanned = _segmented_scan(vals, jnp.broadcast_to(flag, vals.shape), op)
        return _ends_gather(scanned, row_ptr, neutral)
    if method == "scatter":
        assert dst_local is not None
        seg = jax.ops.segment_min if op is jnp.minimum else jax.ops.segment_max
        return seg(
            _scatter_dtype(vals), dst_local, num_segments=row_ptr.shape[0] - 1,
            indices_are_sorted=True,
        ).astype(vals.dtype)
    raise ValueError(
        f"segment min/max: unknown method {method!r}; accepted: 'scan', "
        "'mxscan' (bitwise — min/max stay on the masked VPU path), "
        "'scatter' (cumsum/mxsum are sum-only prefix-diff strategies); "
        "set via --method / LUX_BENCH_METHOD or LUX_SUM_MODE")


def segment_reduce_by_ends(
    vals: jnp.ndarray,
    head_flag: jnp.ndarray,
    dst_local: jnp.ndarray,
    num_segments: int,
    reduce: str = "sum",
    method: str = "scan",
) -> jnp.ndarray:
    """Per-destination reduction WITHOUT a (V+1) row_ptr: segment ends are
    the positions where ``dst_local`` changes, and each end's scanned value
    is scattered into the (num_segments, ...) output.

    This is the compressed encoding for the O(P^2)-bucket exchange layouts
    (ring/reduce_scatter): a dense per-bucket row_ptr would cost
    O(P^2 * V) host+device memory (~35 GB at the RMAT27/P=64 target,
    SURVEY.md §7.3) while head_flag/dst_local are already edge-aligned —
    so per-bucket cost stays O(bucket edges).  Padding slots must carry
    ``dst_local == num_segments`` (dropped by the scatter).  Empty
    destinations get the reduce's neutral element, matching the
    *_csc reducers.

    Accepted methods: ``scan``, ``scatter``, and ``mxscan`` (ISSUE 11 —
    the blocked MXU scan replaces the VPU ladder for 1-D values, using
    the dst_local sentinel as its padding mask).  ``cumsum``/``mxsum``
    DOWNGRADE to ``scan``: the prefix-diff strategies need a row_ptr the
    bucketed encoding deliberately doesn't have — so a blanket
    scan-family winner (engine/methods.sum_mode) stays safe on every
    layout, with the bucketed paths running exactly the shipped VPU
    scan.  ``mxscan`` on (E, K) values downgrades the same way (1-D
    kernel).
    """
    if reduce == "sum":
        op, neutral = jnp.add, jnp.zeros((), vals.dtype)
    elif reduce == "min":
        op = jnp.minimum
        neutral = jnp.asarray(
            jnp.iinfo(vals.dtype).max
            if jnp.issubdtype(vals.dtype, jnp.integer)
            else jnp.inf,
            vals.dtype,
        )
    elif reduce == "max":
        op = jnp.maximum
        neutral = jnp.asarray(
            jnp.iinfo(vals.dtype).min
            if jnp.issubdtype(vals.dtype, jnp.integer)
            else -jnp.inf,
            vals.dtype,
        )
    else:
        raise ValueError(reduce)

    if method == "scatter":
        seg = {
            "sum": jax.ops.segment_sum,
            "min": jax.ops.segment_min,
            "max": jax.ops.segment_max,
        }[reduce]
        # ids are sorted within a bucket (CSC order); padding ids ==
        # num_segments fall outside and are dropped
        return seg(
            _scatter_dtype(vals), dst_local, num_segments=num_segments,
            indices_are_sorted=True,
        ).astype(vals.dtype)
    if method in ("cumsum", "mxsum") or (method == "mxscan"
                                         and vals.ndim > 1):
        method = "scan"  # see docstring: prefix-diff needs a row_ptr
    if method == "mxscan":
        from lux_tpu.ops import pallas_scan

        scanned = pallas_scan.mxscan_segmented(
            vals, head_flag, dst_local >= num_segments, op=reduce)
    elif method == "scan":
        flag = head_flag.reshape(head_flag.shape + (1,) * (vals.ndim - 1))
        scanned = _segmented_scan(vals, jnp.broadcast_to(flag, vals.shape),
                                  op)
    else:
        raise ValueError(
            f"segment_reduce_by_ends: unknown method {method!r}; "
            "bucketed (row_ptr-free) reductions accept 'scan', 'scatter' "
            "and 'mxscan' ('cumsum'/'mxsum' downgrade to 'scan' — "
            "prefix-diff needs a row_ptr); set via --method / "
            "LUX_BENCH_METHOD or LUX_SUM_MODE (engine/methods.sum_mode)")
    # an edge is its segment's end iff the next slot starts a new segment
    # (head_flag is True at position 0 of every segment, including the
    # first padding slot after the real edges)
    is_end = jnp.concatenate(
        [head_flag[1:], jnp.ones((1,), head_flag.dtype)]
    )
    # non-end slots are redirected to num_segments and dropped, so only one
    # value per segment lands in the output (sum stays exact).  The end
    # scatter is widened like every other scatter path: a bf16 .at[].op
    # hits the same serialized TPU emulation (_scatter_dtype).
    idx = jnp.where(is_end, dst_local, num_segments)
    scanned_w = _scatter_dtype(scanned)
    out = jnp.full((num_segments,) + vals.shape[1:], neutral, scanned_w.dtype)
    if reduce == "sum":
        return out.at[idx].add(scanned_w, mode="drop").astype(vals.dtype)
    if reduce == "min":
        return out.at[idx].min(scanned_w, mode="drop").astype(vals.dtype)
    return out.at[idx].max(scanned_w, mode="drop").astype(vals.dtype)


def reducers():
    """Public reduce-name -> segment-function table (shared by the pull
    engine and the ring driver; keep in one place)."""
    return {
        "sum": segment_sum_csc,
        "min": segment_min_csc,
        "max": segment_max_csc,
    }


def segment_min_csc(vals, row_ptr, head_flag, dst_local=None, method="scan"):
    """Min of ``vals`` per destination; empty rows get the dtype max."""
    neutral = jnp.asarray(jnp.iinfo(vals.dtype).max if jnp.issubdtype(vals.dtype, jnp.integer) else jnp.inf, vals.dtype)
    return _segment_minmax(vals, row_ptr, head_flag, dst_local, jnp.minimum, neutral, method)


def segment_max_csc(vals, row_ptr, head_flag, dst_local=None, method="scan"):
    """Max of ``vals`` per destination; empty rows get the dtype min."""
    neutral = jnp.asarray(jnp.iinfo(vals.dtype).min if jnp.issubdtype(vals.dtype, jnp.integer) else -jnp.inf, vals.dtype)
    return _segment_minmax(vals, row_ptr, head_flag, dst_local, jnp.maximum, neutral, method)
