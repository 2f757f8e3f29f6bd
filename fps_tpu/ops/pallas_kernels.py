"""Pallas TPU kernels for the sparse parameter-server hot paths.

SURVEY.md §7 flags the sparse gather / scatter-add paths as the rebuild's
throughput hard part (the reference's per-message ``onPullRecv`` /
``onPushRecv`` handling, expected upstream
``src/main/scala/hu/sztaki/ilab/ps/server/SimplePSLogic.scala``, becomes a
bulk row gather + duplicate-combining scatter-add here). XLA's own gather
and scatter serve every table but one kind: a SCALAR table (PA's and
logreg's weight vectors), where XLA pays a row transaction per scalar
moved. The two kernels here, :func:`gather_rows_dim1_pallas` and
:func:`scatter_add_dim1_pallas`, pack 128 rows per lane row and turn the
data-dependent indexing into dense **indicator (one-hot) matmuls on the
MXU**: duplicates accumulate in the f32 accumulator, drop sentinels (ids
outside ``[0, R)``) never match a row and vanish, and there is zero update
serialization.

Precision contract of both: f32 values ride as hi+lo bf16 halves
(:func:`_split_hi_lo`, ~16 of 24 mantissa bits) with exact f32 MXU
accumulation — ~8x cheaper than a ``Precision.HIGHEST`` f32 contraction
and far more update-mass accuracy than single-pass bf16 on hot rows;
gathered rows and duplicate sums can differ from XLA in the low mantissa
bits.

Both run in interpreter mode off-TPU so the CPU-mesh test suite exercises
them bit-for-bit. Tile sizes respect Mosaic's block constraints: the id
row is laid out ``(1, batch_tile)`` with ``batch_tile`` a multiple of 128
(lane dim), and row/batch tiles are multiples of 8 (sublane dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tiles(R: int, B: int, row_tile: int, batch_tile: int) -> tuple[int, int]:
    """Clamp requested tiles to the (padded) problem and Mosaic constraints:
    row tiles are multiples of 8, batch tiles multiples of 128."""
    row_tile = max(8, min(_round_up(row_tile, 8), _round_up(R, 8)))
    batch_tile = max(128, min(_round_up(batch_tile, 128), _round_up(B, 128)))
    return row_tile, batch_tile


# ---------------------------------------------------------------------------
# Dim-1 lane-packed kernels: scalar tables (PA / logreg weight vectors).
#
# Placing the lanes OUTSIDE the kernel (a (B, 128) delta matrix built by
# XLA) materializes ~0.5 GB per step in HBM at the PA workload shape
# (B = 2^20 ids into a 47k-row scalar table) and measured as much as the
# XLA scatter it replaced (~12 vs ~13.5 ms/step). These kernels build BOTH
# the packed-row one-hot and the lane placement inside the kernel: HBM
# traffic is just ids + deltas (8 MB), and the MXU pays (R/128) x B x 128
# MACs per precision pass. The round-4 v2 formulation is TRANSPOSE-FREE
# (see the kernel docstrings): measured on-chip at the PA shape, dedup-safe
# T=256 scan timing (tools/bench_scatter.py dim1): scatter 7.6 -> 1.5 ms,
# gather 8.1 -> 1.6 ms per 2^20-id call (the v1 kernels with in-kernel
# lane placement via minor-dim reshapes measured 2.8 ms each).
# ---------------------------------------------------------------------------

def _split_hi_lo(x: Array) -> tuple[Array, Array]:
    """f32 -> (hi, lo) bf16 with x == hi + lo to ~2^-16 relative.

    Explicit mantissa-truncation split: hi = x's top 16 bits (exactly a
    bf16 value), lo = the remainder (exact in f32, fits bf16 to ~2^-16
    relative). A plain ``x.astype(bf16)`` round-trip is NOT safe here:
    under ``--xla_allow_excess_precision`` XLA may keep the f32 value
    through the downcast-upcast pair, making lo == 0 and silently
    degrading the contraction to single-pass bf16."""
    hi_f32 = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536),
        jnp.float32,
    )
    return hi_f32.astype(jnp.bfloat16), (x - hi_f32).astype(jnp.bfloat16)


def _scatter_dim1_kernel(ids_ref, deltas_ref, out_ref, *, row_tile):
    """out[(id // 128), (id % 128)] += delta, packed rows x 128 lanes.

    TRANSPOSE-FREE formulation (round-4 v2): the delta multiplies into
    the packed-row one-hot (a native (1, bt)-over-(row_tile, bt)
    broadcast), and the lane one-hot is built TRANSPOSED (128, bt) and
    contracted via dot_general over the shared bt dim — no (bt, 1)
    minor-dim reshapes anywhere. The v1 kernel's in-kernel lane
    placement paid ~4 us/cell in relayouts plus a per-cell floor;
    measured at the PA shape (tools/bench_scatter.py dim1, Zipf(0.9)
    ids) this form is 2.8 -> 1.5 ms/call — uniform ids measure ~1.9 —
    and 0.83 -> ~0.4 ms at the 2048-row head shape.

    Exactness: deltas arrive as f32 containers of exactly-bf16 values
    (the caller's hi/lo split), and one-hot entries are exactly 0/1, so
    ``A = where(match, d, 0)`` downcasts to bf16 losslessly.
    """
    i = pl.program_id(0)  # packed-row tile (slow)
    j = pl.program_id(1)  # batch tile (fast: out block stays resident)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bt = ids_ref.shape[1]
    ids = ids_ref[:]  # (1, bt) int32; negative = drop
    # Arithmetic shift keeps negatives negative (never match a row tile).
    prow = jax.lax.shift_right_arithmetic(ids, 7)
    lane = jnp.bitwise_and(ids, 127)
    rows = i * row_tile + jax.lax.broadcasted_iota(
        jnp.int32, (row_tile, bt), dimension=0
    )
    A = jnp.where(prow == rows, deltas_ref[:], 0.0).astype(jnp.bfloat16)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (128, bt), dimension=0)
    Lt = (lane == lanes).astype(jnp.bfloat16)  # (128, bt)
    out_ref[:] += jax.lax.dot_general(
        A, Lt, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("row_tile", "batch_tile", "interpret")
)
def scatter_add_dim1_pallas(
    table: Array,
    ids: Array,
    deltas: Array,
    *,
    row_tile: int = 256,
    batch_tile: int = 8192,
    interpret: bool = False,
):
    """``table.at[ids].add(deltas)`` for a scalar table ``(R, 1)``.

    ``ids (B,)`` int32 (negative/out-of-range dropped), ``deltas (B, 1)``
    f32. hi+lo bf16 precision contract (module docstring).
    """
    R, D = table.shape
    assert D == 1, "scatter_add_dim1_pallas requires a (R, 1) table"
    B = ids.shape[0]
    rp = -(-R // 128)  # packed rows

    hi, lo = _split_hi_lo(deltas.astype(jnp.float32).reshape(B))
    # Mask ALL out-of-range ids to the -1 drop sentinel (mirrors the gather
    # kernel): ids in [R, rp*128) would otherwise be dropped only by the
    # [:R] truncation and ids >= rp*128 only by Mosaic discarding
    # out-of-bounds block stores — the drop contract must not depend on
    # OOB-store semantics that interpret mode can't exercise.
    ids = jnp.where((ids >= 0) & (ids < R), ids.astype(jnp.int32), -1)
    ids_cat = jnp.concatenate([ids] * 2)
    d_cat = jnp.concatenate([hi, lo]).astype(jnp.float32)

    B2 = 2 * B
    row_tile, batch_tile = _tiles(rp, B2, row_tile, batch_tile)
    pad_b = _round_up(B2, batch_tile) - B2
    ids2 = jnp.pad(ids_cat, (0, pad_b), constant_values=-1).reshape(1, -1)
    d2 = jnp.pad(d_cat, ((0, pad_b),)).reshape(1, -1)

    grid = (pl.cdiv(rp, row_tile), ids2.shape[1] // batch_tile)
    acc = pl.pallas_call(
        functools.partial(_scatter_dim1_kernel, row_tile=row_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, batch_tile), lambda i, j: (0, j)),
            pl.BlockSpec((1, batch_tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((row_tile, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, 128), jnp.float32),
        name="scatter_add_dim1",
        interpret=interpret,
    )(ids2, d2)
    upd = acc.reshape(rp * 128, 1)[:R]
    return table + upd.astype(table.dtype)


def _gather_dim1_kernel(ids_ref, hi_ref, lo_ref, out_ref, *, row_tile):
    """out[b] = table[(id // 128), (id % 128)]; accumulate over row tiles
    (each id matches exactly one packed row).

    TRANSPOSE-FREE formulation (round-4 v2, cf. _scatter_dim1_kernel):
    ``P = W_tile @ laneOneHot^T`` gives ``P[p, b] = W[p, lane_b]``; the
    packed-row match then selects and a column-sum lands the values in
    the native ``(1, bt)`` output layout — no minor-dim reshapes.
    Measured 2.8 -> 1.6 ms per 2^20-id call at the PA shape
    (tools/bench_scatter.py dim1). Garbage in
    the final row tile's block padding stays in its own P rows (the dot
    never mixes rows) and the row mask drops it, so no explicit
    padding-zeroing is needed.
    """
    i = pl.program_id(0)  # batch tile (slow)
    j = pl.program_id(1)  # packed-row tile (fast: out block stays resident)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bt = ids_ref.shape[1]
    ids = ids_ref[:]
    prow = jax.lax.shift_right_arithmetic(ids, 7)
    lane = jnp.bitwise_and(ids, 127)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (128, bt), dimension=0)
    Lt = (lane == lanes).astype(jnp.bfloat16)  # (128, bt)
    P = jnp.dot(hi_ref[:], Lt, preferred_element_type=jnp.float32)
    P += jnp.dot(lo_ref[:], Lt, preferred_element_type=jnp.float32)
    rows = j * row_tile + jax.lax.broadcasted_iota(
        jnp.int32, (row_tile, bt), dimension=0
    )
    sel = jnp.where(prow == rows, P, 0.0)  # (row_tile, bt)
    out_ref[:] += jnp.sum(sel, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("row_tile", "batch_tile", "interpret")
)
def gather_rows_dim1_pallas(
    table: Array,
    ids: Array,
    *,
    row_tile: int = 128,
    batch_tile: int = 8192,
    interpret: bool = False,
):
    """``table[ids]`` for a scalar table ``(R, 1)``; ids outside ``[0, R)``
    produce zero rows. Values carry the hi+lo bf16 precision contract
    (~16 mantissa bits) — callers needing bit-exact reads use the XLA
    gather."""
    R, D = table.shape
    assert D == 1, "gather_rows_dim1_pallas requires a (R, 1) table"
    B = ids.shape[0]
    rp = -(-R // 128)

    packed = jnp.pad(
        table.astype(jnp.float32).reshape(-1), (0, rp * 128 - R)
    ).reshape(rp, 128)
    hi, lo = _split_hi_lo(packed)

    # Mask ALL out-of-range ids to the -1 drop sentinel: ids in [R, rp*128)
    # would lane-select table padding, and larger ids can land a packed row
    # inside the final row tile's BLOCK padding, whose contents are
    # undefined — the zero-row contract must not depend on either.
    ids = jnp.where((ids >= 0) & (ids < R), ids.astype(jnp.int32), -1)
    row_tile, batch_tile = _tiles(rp, B, row_tile, batch_tile)
    pad_b = _round_up(B, batch_tile) - B
    ids2 = jnp.pad(ids, (0, pad_b), constant_values=-1).reshape(1, -1)

    grid = (ids2.shape[1] // batch_tile, pl.cdiv(rp, row_tile))
    out = pl.pallas_call(
        functools.partial(_gather_dim1_kernel, row_tile=row_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, batch_tile), lambda i, j: (0, i)),
            pl.BlockSpec((row_tile, 128), lambda i, j: (j, 0)),
            pl.BlockSpec((row_tile, 128), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, batch_tile), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, ids2.shape[1]), jnp.float32),
        name="gather_dim1",
        interpret=interpret,
    )(ids2, hi, lo)
    return out.reshape(-1)[:B, None].astype(table.dtype)
