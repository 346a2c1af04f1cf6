"""mxscan: blocked segmented inclusive scan in one Pallas kernel.

The segmented reductions of both engines need each segment's running
value.  XLA's sorted scatter serializes on the TPU (1.17 s for 2^27
arcs into 2^22 rows on a v5e), and the ``lax.associative_scan`` ladder
(ops/segment.py "scan") re-materializes the edge array at every level
and does not compile at scale.  This kernel makes one streaming pass:
tile the array into 128-lane rows, scan each row under a segment mask,
and carry one running value through the sequential grid.  The segment
restarts fold into the mask, so ``segment_*_csc`` read the scanned
array through the unchanged ends-gather.

Mechanics, per (tile_rows, 128) VMEM tile of the flattened edge array
(one HBM read of the values and the packed flag byte, one write of the
scanned array):

  * the packed head/pad byte tile is split into head flags ``h`` and the
    padding mask, and the tile-wide inclusive head COUNT is one
    ``(B, 128) x (128, 128)`` triangular matmul (0/1 operands, counts
    <= 128: exact in f32);
  * per 128-lane row, ``Mt[j, i] = (j <= i) & (c[j] == c[i])`` marks the
    slots of i's segment at or before i (equal inclusive head counts ==
    same segment; ``c`` is monotone, so the mask is two broadcast
    compares), and a masked reduce over j gives every slot's scanned
    value on the VPU;
  * float sums accumulate in f32 (bf16 values widened) and round once to
    the value dtype at the row write; each segment's association is
    fixed by the geometry, so results are deterministic and within the
    f32 tolerance of the ladder.  min/max and integer sums are
    dtype-preserving and BITWISE equal to the ladder scan
    (order-insensitive combiners);
  * the carried value (the running value of the segment left open at a
    row or tile boundary) joins the slots before a row's first head and
    lives in a (1, 1) VMEM scratch across the sequential grid — reset at
    ``program_id(0) == 0``, so the kernel vmaps over parts unchanged
    (the batch grid dim is prepended and the tile axis keeps its
    program_id).

Padding slots are neutralized in-kernel via the packed pad bit, so junk
there (Inf, NaN) never reaches a real output; a real value combines by
select and add only, with the ladder's Inf/NaN semantics.

Tile height, measured on one TPU v5e (2^27 f32 arcs into 2^22 rows,
ends gather included, ms an iteration): 8 rows 226, 32 rows 176, 64 rows
166, 128 rows 161, 256 rows 159 (compile 2.0 s at 128 rows against 4.9
s at 256); XLA's scatter 1173.  The float sums' MXU contraction of the
same mask read 240/182/173/168/150 and was dropped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lux_tpu.ops.pallas_shuffle import LANE, _mx_neutral

#: packed head/pad byte: bit 0 = segment head, bit 1 = padding slot
HEAD_BIT = 1
PAD_BIT = 2


def _mxscan_defaults(tile_rows=None) -> int:
    """Scan-tile rows (LUX_MXSCAN_TILE_ROWS, default 128, the chip's
    choice in the module docstring): rows per kernel tile — the kernel
    unrolls one masked reduce per row, exactly like mxreduce's
    LUX_MX_TILE_ROWS.  Read at TRACE time and baked into the compiled
    program, never at replay."""
    from lux_tpu.utils.config import env_int

    if tile_rows is None:
        tile_rows = env_int("LUX_MXSCAN_TILE_ROWS", 128, minimum=1,
                            maximum=256)
    if tile_rows & (tile_rows - 1):
        raise ValueError(
            f"LUX_MXSCAN_TILE_ROWS must be a power of two (tile and pad "
            f"geometry divide each other), got {tile_rows}")
    return tile_rows


def _scan_kernel(op: str, tb: int, x_ref, hv_ref, o_ref, carry_ref):
    """One (tb, 128) tile: masked intra-row scan + the carried value.
    ``carry_ref`` is (1, 1) VMEM scratch in the accumulation dtype,
    holding the scanned value at the end of the previous tile."""
    import jax.experimental.pallas as pl

    acc_dtype = carry_ref.dtype
    neutral = _mx_neutral(op, acc_dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        carry_ref[:, :] = jnp.full_like(carry_ref, neutral)

    hv = hv_ref[:].astype(jnp.int32)
    h = (hv & HEAD_BIT).astype(jnp.float32)
    # neutralize padding BEFORE the reduce: junk pad values may be
    # Inf/NaN
    xm = jnp.where((hv & PAD_BIT) != 0, neutral, x_ref[:].astype(acc_dtype))
    # inclusive head count per row: C[r, i] = sum_{j<=i} h[r, j] — ONE
    # (tb, 128) x (128, 128) triangular matmul, exact in f32 (<= 128)
    io0 = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
    io1 = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
    c = jax.lax.dot_general(
        h, (io0 <= io1).astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (tb, 128)
    ct = jnp.transpose(c)    # (128, tb): c[j] addressable per row
    xt = jnp.transpose(xm)   # (128, tb): values as columns
    red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    comb = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[op]
    carry = carry_ref[:, :]
    for r in range(tb):
        c_row = c[r:r + 1, :]          # (1, 128): c[i] along lanes
        c_col = ct[:, r:r + 1]         # (128, 1): c[j] along sublanes
        # Mt[j, i] = (j <= i) & (c[j] >= c[i]) (c monotone: >= is ==)
        mt = (io0 <= io1) & (c_col >= c_row)
        y = red(jnp.where(mt, xt[:, r:r + 1], neutral), axis=0,
                keepdims=True)         # (1, 128)
        # the carried value joins the slots before the row's first head
        y = jnp.where(c_row == 0.0, comb(y, carry), y)
        carry = y[:, LANE - 1:LANE]
        o_ref[r:r + 1, :] = y.astype(o_ref.dtype)
    carry_ref[:, :] = carry


@functools.partial(jax.jit,
                   static_argnames=("op", "tile_rows", "interpret"))
def mxscan_segmented(vals, head_flag, invalid, op: str = "sum",
                     tile_rows: int | None = None,
                     interpret: bool | None = None):
    """Segmented inclusive scan of ``vals`` (E,) with restarts at
    ``head_flag`` slots — the drop-in for ``ops.segment._segmented_scan``
    on 1-D values.  ``invalid`` (E,) bool marks padding slots whose
    values must be neutralized in-kernel (csc callers: slot index >=
    row_ptr[-1]; bucketed callers: dst_local == num_segments); invalid
    slots' OUTPUTS are unspecified, exactly like the ladder scan's
    padding outputs, and are never read by the ends gathers.

    Returns the scanned array in ``vals.dtype`` (float sums accumulate
    in f32 and round once per row on the way out).
    """
    if op not in ("sum", "min", "max"):
        raise ValueError(f"mxscan op must be sum|min|max, got {op!r}")
    if vals.ndim != 1:
        raise ValueError(
            "mxscan_segmented is a 1-D kernel; (E, K)-valued reductions "
            "take another method (ops/segment.csc_method)")
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = vals.shape[0]
    if n == 0:
        return vals
    # a short array takes the fewest rows that hold it, not a full tile
    tb = min(_mxscan_defaults(tile_rows),
             1 << (pl.cdiv(n, LANE) - 1).bit_length())
    hv = (head_flag.astype(jnp.uint8) * jnp.uint8(HEAD_BIT)  # luxcheck: disable=LUX-P003 -- flag BYTE (values 0-3), a mask operand never used as a gather index
          | invalid.astype(jnp.uint8) * jnp.uint8(PAD_BIT))  # luxcheck: disable=LUX-P003 -- same packed flag byte, second bit
    unit = tb * LANE
    padn = (-n) % unit
    if padn:
        vals = jnp.pad(vals, (0, padn))
        hv = jnp.pad(hv, (0, padn), constant_values=PAD_BIT)
    rows = vals.shape[0] // LANE
    x2 = vals.reshape(rows, LANE)
    hv2 = hv.reshape(rows, LANE)
    float_sum = op == "sum" and jnp.issubdtype(vals.dtype, jnp.floating)
    carry_dtype = jnp.float32 if float_sum else vals.dtype
    spec = pl.BlockSpec((tb, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_scan_kernel, op, tb),
        grid=(rows // tb,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, vals.dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), carry_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, hv2)
    out = out.reshape(-1)
    return out[:n] if padn else out


def mxscan_residency_bytes(tile_rows: int, val_bytes: int = 4) -> int:
    """VMEM residency of one mxscan kernel instance — the LUX-J4 ledger
    entry (analysis/ir/vmem.check_vmem_mxscan).  Streamed operands
    double-buffer through the Pallas pipeline: the value tile (in + out)
    and the packed head/pad byte tile; on top live the tile-wide head
    count and its transpose (f32), the transposed value tile (widened
    to f32 for float sums), the per-row (128, 128) segment mask plus
    its select twin, and the (1, 1) carry."""
    tile = 2 * tile_rows * LANE * (2 * val_bytes + 1)
    counts = 2 * tile_rows * LANE * 4          # C + C^T, f32
    xt = tile_rows * LANE * max(val_bytes, 4)  # transposed values
    masks = 2 * LANE * LANE * 4                # iota/compare + masked op
    return tile + counts + xt + masks + 8
