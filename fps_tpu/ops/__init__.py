"""Sparse hot-path ops: the store's two row ops and the routes they take.

The store's pull/push collectives bottom out in two local ops per shard:
row **gather** (pull answers) and duplicate-combining **scatter-add** (push
folds). :func:`gather_rows` and :func:`scatter_add` are one chain each, the
same steps in the same order (the scatter-add has one more), every step a
predicate over what the call can observe (platform, backend, rows, width,
ids, dtype, and the caller's STATIC guarantees: the ingest layer's
``head_prefix``, the mean push's ``ids_sorted``):

1. ``dim1_head`` — a scalar table whose leading ids the ingest layer
   certified inside the head ``[0, hot_rows)``: the prefix rides a dim-1
   Pallas kernel over the head slice alone, the tail goes down the chain
   again (:func:`_route_head_prefix`);
2. ``dim1`` — a scalar table of at most :data:`DIM1_MAX_ROWS` rows moving
   at least :data:`DIM1_MIN_BATCH` ids: the dim-1 Pallas kernels over the
   whole table (:func:`_route_dim1`);
3. ``xla_packed`` — a narrow-row table too large for XLA's VMEM regime:
   the same XLA op on the table's lane-packed form
   (:func:`_route_xla_packed`, :data:`XLA_VMEM_TABLE_BYTES`);
4. ``xla_sorted`` (scatter-add alone) — a wide-row table too large for
   XLA's VMEM regime whose caller guarantees non-decreasing ids, the
   dropped ones last: the plain XLA scatter-add over blocks of ids in a
   loop that stops where the dropped begin, so its time follows the live
   ids (:func:`_route_xla_sorted`, :data:`XLA_SORTED_BLOCK_IDS`);
5. ``xla`` — the plain XLA op, with the reason the others were passed over.

Backend selection:

* ``set_backend("auto" | "xla" | "pallas")`` or env ``FPS_TPU_OPS`` at
  import time. Default ``"auto"``.
* ``"auto"`` — on TPU, the chain above; off TPU, pure XLA.
* ``"xla"`` — the PLAIN XLA ops everywhere (debugging / bit-exact baseline;
  the lane-packed XLA route is off too, so this is its A/B).
* ``"pallas"`` — the chain above on any platform: off TPU the dim-1 kernels
  run in interpreter mode, which is how the CPU-mesh test suite exercises
  them.

Names: every branch runs the kernel or XLA call it ends in under
``jax.named_scope("fps.ops")`` and, inside it, a scope naming the route
(``gather.dim1_head``, ``scatter_add.xla``, ...: :data:`ROUTES`), so a
device trace lays each op's time to the route that chose it; and the
choice itself is appended to a route log at trace time
(:func:`routes_traced`), with the reason a Pallas route, or the lane-packed
XLA route, was passed over (``"vmem_fit"``: the plain op already runs with
its table in VMEM).
"""

from __future__ import annotations

import contextlib
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array

_BACKEND = os.environ.get("FPS_TPU_OPS", "auto").lower()

# Every route the two ops can take, in the order the chain tries them; the
# scope under ``fps.ops`` and the ``route`` of a log entry are
# ``<op>.<route>``.
ROUTES = {
    "gather": ("dim1_head", "dim1", "xla_packed", "xla"),
    "scatter_add": ("dim1_head", "dim1", "xla_packed", "xla_sorted", "xla"),
    # :func:`scatter_set`, a row's NEW value written to distinct ids (the
    # state of a table's own stateful fold, ``push.fold_rows``).
    "scatter_set": ("xla_sorted", "xla"),
}
PALLAS_ROUTES = frozenset(
    f"{op}.{r}" for op, rs in ROUTES.items() for r in rs
    if not r.startswith("xla"))


class Route(NamedTuple):
    """One routing decision, logged where the chosen call is made — at
    TRACE time, never in the compiled program."""
    op: str          # "gather" | "scatter_add" | "push" (the store's choice
                     # of a combine's branch: fps_tpu.core.store.push) |
                     # "pull" (the driver's read of the SSP snapshot, or
                     # of the two-tier storage's replica; the store's
                     # pull of each distinct row once) | "reconcile"
                     # (the hot tier's window-end exchange)
    route: str       # "gather.dim1_head", "scatter_add.xla", ...;
                     # "push.mean_rows" / "push.mean_dense" / "push.fold"
                     # / "push.acc_runs"; "pull.snapshot";
                     # "pull.distinct_rows"; "pull.hot" /
                     # "push.hot" (rows: the head H; dim: the replica's,
                     # the pending buffer's with its count column; ids: a
                     # step's, hot and cold) / "reconcile.hot" (ids 0)
    rows: int        # rows of the table (slice) the call sees
    dim: int
    ids: int         # ids the call moves
    interpret: bool  # the Pallas kernel runs interpreted (off TPU)
    reason: str      # why a Pallas route, or the lane-packed XLA one, was
                     # passed over: "" (taken, or exact read asked for),
                     # "f64", "backend", "shape",
                     # "vmem_fit" (the plain XLA op already runs in VMEM);
                     # of "push.mean_dense": "fold", "dtype", "small_table";
                     # of "push.fold": "apply_fn"; of "push.acc_runs"
                     # what brought the push to the accumulator: "fold",
                     # "mean_dense", "callable"; of the three ".hot"
                     # entries "table=<name>", the reconcile's also
                     # "every=<E> combine=<c> shards=<S> bytes=<a device's
                     # pending buffer, reduced once a window>"


_ROUTES_TRACED: list[Route] = []


def routes_traced() -> list[Route]:
    """The route log: one entry per kernel or XLA call this module made
    while programs were traced since :func:`clear_routes` (a head-prefix
    composite logs its head and its tail). What a program holds is what
    was logged while IT was traced: clear, trace, read."""
    return list(_ROUTES_TRACED)


def clear_routes() -> None:
    _ROUTES_TRACED.clear()


def log_route(op: str, route: str, rows: int, dim: int, ids: int,
              reason: str = "") -> str:
    """Append one decision to the route log; returns its ``<op>.<route>``."""
    name = f"{op}.{route}"
    _ROUTES_TRACED.append(Route(
        op, name, int(rows), int(dim), int(ids),
        name in PALLAS_ROUTES and _use_pallas()[1], reason))
    return name


@contextlib.contextmanager
def _routed(op: str, route: str, rows: int, dim: int, ids: int,
            reason: str = ""):
    """Log the decision and open ``fps.ops/<op>.<route>``, the route's
    scope, round its own call."""
    name = log_route(op, route, rows, dim, ids, reason)
    with jax.named_scope("fps.ops"), jax.named_scope(name):
        yield


def set_backend(name: str) -> None:
    """Select the hot-path backend for subsequently *traced* programs.

    The choice is read at trace time: programs already compiled (e.g. a
    ``Trainer`` that has run a chunk) keep the backend they were traced
    with. ``Trainer`` keys its compile cache on this setting, so new
    trainers — or the same trainer's next fresh trace — pick up the change.
    """
    global _BACKEND
    if name not in ("xla", "pallas", "auto"):
        raise ValueError(f"unknown ops backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


def _use_pallas() -> tuple[bool, bool]:
    """(use_pallas, interpret) for programs traced now — the ONE place
    both are decided; every kernel call site below takes its
    ``interpret`` from here.

    On TPU a kernel is never interpreted. Off TPU only the forced
    ``"pallas"`` backend runs kernels, interpreted (the CPU suite's path);
    ``"auto"`` stays on XLA there. A backend that fails to initialise
    raises out of ``jax.default_backend()`` — it is never read as "not a
    TPU", which would quietly swap a compiled kernel for an interpreted
    one or an XLA route."""
    if _BACKEND == "xla":
        return False, False
    if jax.default_backend() == "tpu":
        return True, False
    return _BACKEND == "pallas", True


# Scalar-table (D == 1) lane-packed routing. XLA's TPU gather AND scatter
# are per-row-transaction bound (~8 ns/row at B = 2^20, dedup-safe T=256
# measurement), so a dim-1 table pays ~8 ns per SCALAR moved. The dim-1
# kernels pack 128 rows per lane row and build the one-hot + lane
# placement in-kernel (v2: transpose-free, see the kernel docstrings):
# at the PA workload shape (47k rows, 2^20 ids, 95% duplication)
# measured 1.5 (scatter) / 1.6 (gather) ms vs XLA's 7.6 / 8.1 ms per
# call. Kernel cost scales with ceil(R/128) once MAC-bound, so the win
# inverts above the cap below — MEASURED with the v2 kernels at the
# logreg stream shape (B = 426k Zipf(0.9) ids, round 5's runtime, an
# EARLIER one than the current v5e installation's,
# tools/bench_logreg_routes.py stage b on a v5 lite chip):
#
#   R        dim1 scatter/gather   XLA scatter/gather
#   131k     1.78 / 1.78 ms        3.46 / 3.77 ms   (dim1 ~2x win)
#   262k     2.93 / 3.11 ms        3.67 / 3.89 ms   (dim1 still wins)
#   524k     5.53 / 5.78 ms        3.89 / 4.35 ms   (XLA wins)
#   1M      12.09 / 11.44 ms       6.15 / 5.29 ms   (XLA wins 2x+)
#
# The XLA column does not carry to the current runtime at every width: the
# same stream on [1000000, 2] (cell lr-criteo.epochs, 425,997 ids a step
# through gather.xla / scatter_add.xla, chip run, PR 32) reads 2.25 ms for
# the gather (5.3 ns an id) and 19.8 ms for the scatter-add into the
# AdaGrad fold's [1000000, 3] accumulator (46.5 ns an id: PR 25's sweep
# read 44 at [1048576, 8]), the accumulator kept transposed and in VMEM.
#
# The cap sits at the last measured win (262144) — a THIN (~20%) margin
# verified only at the single-chip logreg stream shape above (B = 426k
# Zipf(0.9) ids, one v5 lite chip); the (131k, 262k] band is unmeasured
# at gathered multi-worker batch sizes, where per-shard R and the W*B
# batch both shift with the mesh. Treat the 131k row band as the
# robust-win region and re-run tools/bench_logreg_routes.py stage b
# before leaning on the upper band at a new shape. The shipped 1M-row
# logreg table stays correctly excluded — its full-table contraction is
# MAC-bound at ~2x XLA's transaction cost. Reads
# and duplicate sums carry the hi+lo bf16 contract (~16 mantissa bits) —
# see fps_tpu.ops.pallas_kernels — hence bit-exactness is not promised for
# routed shapes, neither across backends (CPU "auto" stays on XLA) nor
# across SHARD COUNTS on TPU: the route predicate sees per-shard R and
# the gathered W*B batch, both of which change with the mesh, so the
# same scalar table can route at one shard count and not another.
# Scope note: the framework's TESTED bit-identity invariants are table
# init across shard counts, checkpoint save/restore across shard and
# worker counts, and same-mesh runs across OS-process layouts — all
# unaffected by this route on CPU and preserved on TPU within a fixed
# mesh + backend. TRAINING bits across different mesh shapes were never
# invariant on any route (fold order follows the gathered batch layout;
# the dense-collective route reassociates differently again). What this
# route adds is same-shape backend sensitivity on TPU, in exchange for
# a ~5x measured win on both sides of every scalar-table transaction;
# force ``set_backend("xla")`` / FPS_TPU_OPS=xla for bit-exact audits
# within one mesh shape.
DIM1_MAX_ROWS = 262_144
DIM1_MIN_BATCH = 8_192

# Small-table threshold for the store's DENSE collective route (replicate
# on read, dense-reduce on write — fps_tpu.core.store.pull/push). The
# gathered route's per-shard work grows with the number of workers (every
# shard processes every worker's ids: O(W * B_local) row transactions per
# step per shard), while the dense route pays O(B_local) transactions plus
# table-sized collectives (all_gather on pull; all_to_all + fixed-order
# sums on push — order-deterministic by design) that ride ICI at line
# rate. At 8 ns/row, a worker pushing 2^20 ids on an
# 8-way mesh saves ~7 * 8.4 ms of serialized scatter per step; a 4 MB
# table costs ~tens of us per collective hop — the trade is lopsided for
# every shipped small table (PA 190 KB, MF items 1.2 MB, logreg 4 MB) and
# wrong for embedding-scale ones (w2v 20 MB+), hence the cap.
DENSE_TABLE_BYTES = 4 << 20

# When the store's per-id MEAN push (fps_tpu.core.store.push,
# combine="mean", additive fold) leaves its (rows, dim + 1) accumulator for
# the row branch ("push.mean_rows": count the pushes of each id, scale the
# B pushed rows, sum them by id in a (B, dim) buffer, scatter-add that into
# the table once). The accumulator branch makes about five passes over the
# accumulator whatever the batch; the row branch pays two sorts of B ids
# (three since PR 30; the table below is PR 28's, before the third and
# before ``scatter_add.xla_sorted``, which only widens the rows' lead at
# its lower right) and a second, payload-sized, row scatter instead. The ratio
# (``store._mean_push_ratio``) is the accumulator's tiled bytes, in the
# smaller of its row-major and transposed forms, over the payload's. In
# time (``tools/bench_scatter.py mean``, one v5 lite chip, f32, Zipf(1.0)
# ids, the table a loop carry; us a push, accumulator / rows; under each
# pair the ratio):
#
#   R          D=10, B=8192  B=32768      D=64, B=8192  B=32768      D=300, B=8192  B=32768
#   17,770     111 / 185     303 / 771    123 / 182     343 / 762    366 / 443      1054 / 1612
#              0.27          0.07         1.2           0.31         1.7            0.43
#   32,768     161 / 210     439 / 851    171 / 209     474 / 839    397 / 461      1148 / 1694
#              0.50          0.13         2.3           0.56         3.2            0.79
#   65,536     469 / 468     482 / 847    476 / 462     514 / 840    933 / 710      1364 / 1873
#              1.0           0.25         4.5           1.1          6.3            1.6
#   131,072    689 / 469     694 / 858    697 / 465     730 / 854    2259 / 1076    2301 / 2239
#              2.0           0.50         9.0           2.3          12.7           3.2
#   262,144    145 / 226     347 / 800    1609 / 673    3419 / 2662  3561 / 1094    6077 / 4109
#              4.0           1.0          18            4.5          25             6.3
#   1,115,011  727 / 511     2060 / 2011  4807 / 741    6644 / 2867  11985 / 1219   14869 / 4503
#              17            4.3          77            19           108            27
#
# and w2v-1bw's two pushes, [1115011, 300] under 8,197 and 49,182 ids
# (108 and 18): 12019 / 1275 and 17205 / 7277. From 6.3 up the rows win
# every measured point (13, by 31 % to 9.4x); up to 4.5 the accumulator
# wins or is level within 3 % at 23 of 25, and the rows take two (D=10 at
# 2.0 by 47 %, D=64 at 4.5 under 32,768 ids by 28 %). Keeping the
# accumulator is what every push did before, so the constant sits at the
# rows' clear-win edge; (4.5, 6.3) is unmeasured. By the accumulator's
# ROW-MAJOR bytes alone no constant serves: [262144, 10] under 8,192 ids
# (row-major ratio 32, accumulator 145 / rows 226: the transposed
# accumulator is 17 MB and rides VMEM) lies above w2v-1bw's out table (23,
# rows 2.4x faster).
MEAN_ROWS_TABLE_RATIO = 6.0

# XLA's own fast regime for row ops on a narrow-row table, and the
# lane-packed XLA route that puts a table back inside it. XLA's TPU gather
# and scatter run on a copy of the table in VMEM (memory space 1: ``S(1)``
# in the compiled layout, indices sorted first) while the table's
# ROW-MAJOR TILED form fits there. That form pads a row to 128 lanes
# (512 B in f32) whatever its width, so a rank-10 table of 480,189 rows
# (19 MB of numbers) counts as 246 MB, falls out, and is left transposed
# in HBM where every update is a strided read-modify-write. Compile-only,
# v5e (``tests/test_v5e_compile.py`` guards it), ``t.at[i].add(d)``,
# f32[R,10], 32,768 ids; the edge lies at 117.2-117.5 MB of tiled bytes
# for f32 and bf16, D = 10 and 32, 32,768 and 262,144 ids alike:
#
#   R                          scatter operand                 ids sorted
#   120,048; 160,063; 200,000  {1,0:T(8,128)S(1)}: VMEM        yes
#   240,095                    {1,0:T(8,128)}: row-major, HBM  yes
#   320,126; 480,189           {0,1:T(8,128)}: transposed, HBM no
#
# In time (``tools/bench_scatter.py rows``, one v5 lite chip, B = 32768
# uniform ids, f32, us a call with the table a loop carry of its plain
# shape, so the packed route's relayout both ways is in its number;
# plain / packed; MB = tiled bytes of the plain and of the packed form):
#
#   R (plain MB)       D=10 (packed MB) scatter    gather     gather+scatter
#   17,770 (9.1)       (0.8)            323 / 335  139 / 152  394 / 416
#   120,048 (61.5)     (5.2)            399 / 345  143 / 162  462 / 440
#   200,000 (102.4)    (8.6)            675 / 361  256 / 170  672 / 450
#   240,095 (122.9)    (10.3)           744 / 366  366 / 168  1018 / 458
#   320,126 (163.9)    (13.7)           1999 / 384 228 / 179  2150 / 468
#   480,189 (245.9)    (20.5)           2020 / 458 221 / 195  2148 / 556
#   1,048,576 (536.9)  (44.8)           1997 / 735 226 / 492  2153 / 818
#
#   R = 480,189    D=8 (15.4) D=11 (22.4) D=16 (30.7) D=20 (41.0) D=32 (61.5)
#   scatter        1437 / 371 2000 / 472  2003 / 470  2503 / 572  2313 / 1271
#   gather         184 / 176  229 / 201   236 / 212   310 / 355   392 / 545
#   R = 1,048,576  D=8 (33.6) D=11 (48.8) D=16 (67.1) D=20 (89.5) D=32 (134.2)
#   scatter        1436 / 526 2005 / 743  2004 / 1533 2509 / 1918 4138 / 5324
#   gather         182 / 217  223 / 502   236 / 1113  314 / 1004  1279 / 1858
#
# The plain op pays for the VMEM copy of its tiled table both ways (the
# rise from 61.5 to 102.4 MB) before it falls out altogether; at 61.5 MB
# the two routes are level, at 102.4 MB the packed one wins every width
# (scatter by 31-49 %, gather by 3-37 %). XLA_VMEM_TABLE_BYTES sits at
# that clear-win edge; (61.5, 102.4) MB is unmeasured. The packed form
# pays its relayout (a pad, a reshape, one 2-D transpose each way: source
# and result in VMEM together, so it falls out past ~58 MB, see 61.5 and
# up) and wins both ops only while small: XLA_PACKED_TABLE_BYTES is the
# largest packed form whose gather ALONE stays within a fifth of the
# plain one's (33.6 MB; at 41-49 MB the scatter still wins by 63-77 %
# but a lone gather runs from 9 % faster to 125 % slower). Fewer ids
# than XLA_PACKED_MIN_IDS do not pay the relayout (gather+scatter at
# Netflix's block, plain / packed: 1,024 ids 114 / 169, 4,096 312 / 324,
# 8,192 572 / 244, 16,384 1102 / 337). Widths outside XLA_PACKED_DIMS
# were not swept.
#
# Thirty times past the grid (``tools/bench_scatter.py rows dlrm``, one v5
# lite chip, builder's chip run, PR 48): ``dlrm-criteo``'s table,
# f32[33762577,16], under a step's 425,984 ids (26 fields, Zipf(1.05)
# within a field: 78,500 distinct), the table a donated loop carry. XLA
# keeps it TRANSPOSED, ``{0,1:T(8,128)}``: 16 sublanes by 33.8 M lanes,
# 2.16 GB, no padding (its row-major tiles would be 17.3 GB), as entry
# parameter, loop carry and scatter operand alike, and the plain ops read
# and write it in place: nothing is relaid out and nothing table-sized is
# made (``tests/test_v5e_compile.py`` guards it). Every route above stays
# out (the packed form would be 2.16 GB, past XLA_PACKED_TABLE_BYTES) and
# the calls log ``gather.xla`` / ``scatter_add.xla``, reason ``shape``.
# us a call, the plain ops / a RESIDENT lane-packed form of the same rows
# (``[4220323, 128]``, eight consecutive rows a packed row, never relaid
# out: whole packed rows gathered and the id's lanes picked by a one-hot;
# each update widened to its packed row):
#
#   gather 9587 / 7461    scatter-add 43254 / 33127    pair 52516 / 38331
#
# 22.5 ns a gathered id and 101.5 ns a scattered one, where [1048576, 16]
# read 7.2 and 61: the transposed regime does NOT pay the same for every
# id at every size (per id the scatter is 1.66x and the gather 3.1x dearer
# at 32 times the rows; which of rows, skew and the 13x more ids is not
# separated). The resident packed form wins all three (22-27 %) and is
# what a store that owned the table's layout would carry (ROADMAP M3 (i));
# nothing routes to it yet.
XLA_VMEM_TABLE_BYTES = 96 << 20
XLA_PACKED_TABLE_BYTES = 32 << 20
XLA_PACKED_DIMS = (8, 32)
XLA_PACKED_MIN_IDS = 8_192

# The sorted route of the scatter-add (``scatter_add.xla_sorted``): what a
# caller's ``ids_sorted`` guarantee is worth past XLA's VMEM regime. There
# the plain scatter-add pays about 100 ns for every id it is handed,
# dropped by the sentinel or not (99-104 ns a row at [1115011, 300], chip
# runs, PR 28 and PR 30; ``.at[].set``, ``unique_indices`` and a gather,
# add and set cost the same). With the dropped ids LAST the op can stop at
# the last live one: the same XLA scatter-add, a block of
# XLA_SORTED_BLOCK_IDS ids at a time, in a loop of ``ceil(live / block)``
# trips. In time (``tools/bench_scatter.py wide``, one v5 lite chip, f32,
# the table a loop carry, Zipf(1.0) ids handed over as ``push.mean_rows``
# does: the distinct ones sorted at the front, the sentinel in the place of
# every duplicate; us a call, plain / sorted; MB = tiled bytes; under B the
# live ids of the R = 1,115,011 row, fewer above it):
#
#   R (MB)             D=64, B=8,192  B=32,768     B=49,182     D=128, B=8,192  B=32,768     B=49,182
#   live ids           4,526          14,974       21,100       4,524           14,947       21,119
#   65,536 (33.6)      376 / 206      394 / 491    625 / 695    376 / 203       396 / 496    625 / 691
#   131,072 (67.1)     377 / 207      409 / 578    645 / 783    379 / 206       411 / 584    639 / 780
#   262,144 (134.2)    573 / 337      2178 / 998   1027 / 1394  600 / 333       2298 / 1005  1021 / 1393
#   1,115,011 (570.9)  646 / 435      2390 / 1187  3630 / 1679  637 / 422       2395 / 1179  3615 / 1664
#
#   R (MB)               D=300, B=8,192  B=32,768     B=49,182
#   65,536 (100.7)       565 / 362       1108 / 1150  1697 / 1732
#   131,072 (201.3)      926 / 552       1475 / 1366  2080 / 1977
#   262,144 (402.7)      946 / 581       3305 / 1530  2834 / 2264
#   1,115,011 (1,712.7)  1063 / 783      3671 / 2096  5793 / 3084
#
# and w2v-1bw's two pushes, [1115011, 300] under 8,197 ids (4,515 live) and
# 49,182 (21,093 live): 1096 / 791 and 5800 / 3102; the larger under blocks
# of 512 / 1,024 / 2,048 / 4,096 / 8,192 ids: 3107 / 3107 / 3185 / 3373 /
# 3390 (a block less is about 100 us; a trip more costs under 3). Inside the
# VMEM regime (the first two rows; [65536, 300] is 96 MiB to the byte) the
# plain op pays about 13 ns an id and the loop loses from 32,768 ids up: the
# route stays out. Past it the plain op is NOT linear in the ids at every
# size: at 262,144 rows it is cheaper under 49,182 ids than under 32,768
# (XLA turns to an emitter that walks the table once the ids are many
# against the rows; told ``indices_are_sorted`` it always does, 7.2 ms at
# [1115011, 300] whatever the ids, chip run, PR 30), and there the loop
# loses by 36 % at D = 64 and 128 and wins by 20 % at 300. With at least
# XLA_SORTED_ROWS_PER_ID rows an id the sorted route wins every measured
# point (16, by 26-56 %); with fewer it wins three by 5-20 % and loses two;
# the constant sits at the clear-win edge, (5.3, 8) rows an id is
# unmeasured, and so are widths under 64 (a table the lane-packed route
# could take is left to it). The gain is the dropped share of the ids: a
# batch with none pays the loop's trips and wins nothing.
XLA_SORTED_BLOCK_IDS = 1_024
XLA_SORTED_ROWS_PER_ID = 8

# A NARROW table past XLA's VMEM regime, and when the store's accumulator
# body (fps_tpu.core.store.push: a stateful fold, a callable combine, a
# mean push that kept its accumulator) sums the pushed rows by id run
# before the accumulator's scatter ("push.acc_runs": two sorts of the B
# ids carrying the rows' columns, a segmented scan between them, and the
# scatter handed each distinct id once, sorted, the sentinel after: the
# sorted route above then stops at the last of them). A table of rows
# narrower than the lane-packed route's whose row-major tiles are a little
# past XLA_VMEM_TABLE_BYTES is left ROW-MAJOR IN HBM; further out XLA keeps
# it TRANSPOSED, and in VMEM while that form fits. Compile-only, v5e
# (``tests/test_v5e_compile.py`` guards the cell's), a zero f32[R,3]
# accumulator scattered into inside a loop:
#
#   R (row-major MB)                      the accumulator, plain and by blocks
#   196,000 (100.4)                       {1,0:T(8,128)S(1)}: row-major, VMEM
#   250,000 (128.0); 262,144 (134.2)      {1,0:T(8,128)}: row-major, HBM
#   300,000 (153.6) ... 1,000,000 (512)   {0,1:T(4,128)S(1)}: transposed, VMEM
#
# In the transposed regime the plain scatter-add pays 41-46 ns for EVERY id
# it is handed, a repeat or dropped by the sentinel alike, linear in the ids
# (425,997 ids into [1000000,3]: 19.7 ms sorted or not, told
# ``indices_are_sorted`` / ``unique_indices`` or not, the last 82 % of them
# the sentinel or not); the block loop keeps its carry in VMEM across trips
# and pays the same for every LIVE id (75,553 distinct: 3.32 ms in blocks of
# 1,024, 3.38 / 3.55 in blocks of 4,096 / 16,384; a static slice of as
# many 3.27). A ``lax.switch`` over 4 / 16 static prefixes loses VMEM
# (9.5 / 7.3 ms). In the row-major-HBM band a block's id costs 71-78 ns and
# the plain op turns to a faster emitter once the ids are many against the
# rows (13-16 ns an id from 0.5 ids a row). In time (chip runs of PR 33's
# builder, the same code, quoted by PR 34 and not run again;
# ``tools/bench_scatter.py fold``, one v5 lite chip, f32, width 2 + the
# count column, the table a loop carry; us a push through the AdaGrad fold,
# plain accumulator / summed runs; under each pair the live (distinct) ids;
# ids UNIFORM over the rows, then Zipf(1.05)):
#
#   R (MB)             B=32,768       B=131,072       B=425,997        B=1,703,988
#   250,000 (128.0)                                                    28130 / 35864 (a)
#   uniform                                                            249,720
#   262,144 (134.2)    4014 / 2915    2919 / 8379     7690 / 17960
#   uniform            30,796         103,176         210,554
#   320,126 (163.9)                   5452 / 4767     20173 / 11179
#   uniform                           107,561         235,528
#   500,000 (256.0)                                   20204 / 13281
#   uniform                                           286,712
#   1,000,000 (512.0)  1528 / 1563    5549 / 5469     20276 / 15810    80581 / 40779
#   uniform            32,240         122,827         346,889          818,063
#   4,194,304 (2,147)  1780 / 1785    5772 / 5917     20697 / 18211
#   uniform            32,641         129,051         405,064
#
#   262,144 (134.2)    3969 / 1490    3121 / 3169     8332 / 7258
#   Zipf(1.05)         10,620         30,736          69,863
#   320,126 (163.9)                   5420 / 1698     19972 / 4614
#   Zipf(1.05)                        31,852          73,675
#   500,000 (256.0)                                   20001 / 4977
#   Zipf(1.05)                                        82,000
#   1,000,000 (512.0)  1530 / 739     5519 / 2020     20087 / 5550     79836 / 17803
#   Zipf(1.05)         12,206         37,792          94,158           251,502
#   4,194,304 (2,147)  1773 / 1037    5744 / 2470     20491 / 6644
#   Zipf(1.05)         13,609         43,924          115,859
#
# and lr-criteo.epochs' own push ([1000000, 2], 425,997 ids of its 26
# hashed columns, 75,551 live): 20276 / 4799, of it the sums by run 1.56 ms
# (``fold probes``). The scatter-add ALONE on such ids (the distinct sorted,
# the sentinel after) into an [R, 3] loop carry, plain / sorted route: in
# the transposed regime the sorted route is level within 1.3 % where nothing
# is dropped (1366 / 1372, 5275 / 5285) and wins everywhere else, by the
# dropped share and a little more (a sorted distinct id costs 38-41 ns);
# in the row-major band it loses wherever the plain op has its fast emitter
# (262,144 rows: 1670 / 7514 under 131,072 uniform ids, 6744 / 15923 under
# 425,997), so ``_route_xla_sorted`` takes a narrow table from
# XLA_TRANSPOSED_TABLE_BYTES up, a byte count between the last row-major
# and the first transposed layout compiled; in TIME (134.2, 163.9) MB is
# unmeasured, and so are tables whose transposed form is past 64 MiB
# (R over 4,194,304). The width swept is 3 ({0,1:T(4,128)}: four sublanes
# a row); 4 compiles to the same tiles and is taken with it
# (XLA_TRANSPOSED_DIMS); 1, 2 (T(1,128), T(2,128)) and 5 to 7 (T(8,128))
# compile transposed and in VMEM too but were not swept and stay out. The
# summed runs: in the transposed regime under UNIFORM ids level within
# 2.5 % up to 0.131 ids a row (5 points) and a win at every point from
# 0.409 (6: +14 % at 0.409, +22 % at 0.426, x1.5-2.0 from 0.85; also +12 %
# at 0.102 under 425,997 ids), and under Zipf(1.05) a win at every point
# (x1.7-4.3). ACC_RUNS_MIN_IDS_PER_ROW sits at that clear-win edge without
# skew; (0.131, 0.409) is unmeasured.
# In the row-major band (262,144 rows: read with the sorted route taking
# the scatter) they lose without skew by 2.3-2.9x from 0.5 ids a row (and
# win 27 % at 0.125): the band stays out. (a) One shard of four
# of the cell's job, [250000, 2] under the four workers' 1,703,988 ids
# (49,081 live under the cell's columns), with this predicate, so the
# scatter stays plain and pays for all the ids: a loss; its scatter alone
# by the sorted route reads 27670 / 19377 uniform and 28064 / 3916 under the
# cell's columns, so with both predicates open there the push would read
# about 27.6 (level) and 12.1 ms (x2.4): one point, not enough for a rule.
# In XLA's VMEM regime (mf-netflix.x4's [4443, 11] under 131,072 ids, route
# forced) 1041 / 2190: eleven sort operands cost more than the scatter.
XLA_TRANSPOSED_TABLE_BYTES = 144 << 20
XLA_TRANSPOSED_DIMS = (3, 4)
ACC_RUNS_MIN_IDS_PER_ROW = 0.4

# A table of the lane-packed route's WIDTHS with far more rows than that
# route reaches, and when the store's ADDITIVE push (fps_tpu.core.store.push,
# combine="sum", no apply_fn) sums a step's rows by id before its scatter
# into the table itself ("push.sum_runs": one sort of (id, position), a
# gather of the B rows into that order, and, where the batch is seen to
# repeat itself, a segmented scan over the transposed rows, a second
# 2-operand sort and the distinct ids' totals fetched a block at a time; the
# scatter handed each distinct id once, sorted, the sentinel after: the
# sorted route above then stops at the last of them). Compile-only, v5e
# (``tests/test_v5e_compile.py`` guards the cell's): f32[R,16] as a donated
# loop carry is ``{0,1:T(8,128)}``, TRANSPOSED IN HBM (no ``S(1)``), at
# 1,048,576, 2,097,152, 4,194,304, 8,440,645 and 33,762,577 rows alike, by
# the plain scatter and by the block loop; the ``[425984,16]`` payload and
# every array of the sums are kept transposed too, and in VMEM
# (``{0,1:T(8,128)S(1)}``), so the scan's 19 doubling passes are lane-dense
# as they stand. In time (``tools/bench_scatter.py rows dlrm sums`` and
# ``rows dlrm edge``, one v5 lite chip, builder's chip run, PR 49, call 207;
# f32, width 16, 425,984 ids a step, the table a donated loop carry; us a
# call). At ``dlrm-criteo.epochs``' own shape, [33762577,16], under its own
# ids (26 fields, Zipf(1.05) within a field: 78,500 distinct, 18.4 %),
# under ids UNIFORM over the rows (423,306 distinct, 99.4 %) and under a
# half-and-half batch (thirteen fields the cell's, thirteen uniform:
# 244,767 distinct, 57.5 %):
#
#                                  the cell's   half-and-half   uniform
#   scatter-add alone, plain       42451        42956           42663
#   push, plain                    42510        42968           42701
#   push, push.sum_runs, every run a tree (the first form, NOT shipped)
#                                  11798        29084           44428
#   ... the sums ALWAYS formed                                  47791
#   ... the sums NEVER formed      47134
#   push, push.sum_runs as SHIPPED (long runs chained, below; call 224,
#   where the plain push read 42975 / 43374 / 43038)
#                                  17699        36460           44610
#   ... the sums ALWAYS formed                                  56810
#   ... the sums NEVER formed      49281
#   the block loop alone on the distinct ids sorted, sentinel after
#                                  8120                         41766
#   the block loop alone on ALL the ids sorted, repeats adjacent
#                                  44633                        41960
#
# The block loop on an HBM-resident transposed table costs 103 ns a LIVE id
# (8120 / 78,500; 98.7 under uniform ids) and as much for an adjacent repeat
# (104.8): what the plain op pays for every id handed (99.7), so the gain
# is the share of the ids that the sums take away, 81.6 % on the cell. The
# sums, alone, on the cell's ids (``sums_*``): every run a tree 3672 (one
# sort of (id, position), the gather of 425,984 rows, the scan over [16, B],
# the second sort, the totals fetched 77 blocks of 1,024 at a time), as
# shipped 9468 (the long runs' chain, its cumulative maximum and sum and the
# third sort: 5.8 ms, of it the VMEM scatter of 425,984 handed rows most); with
# ALL B totals gathered by the second sort 5050; the rows transposed before
# the first gather and gathered along the lanes both times 5048 (XLA makes
# the same program of it); ``_sum_id_runs`` as the accumulator has it (two
# sorts carrying all 16 columns and a count) compiles in 426 s for a
# described v5e where the shipped form takes 17, and was not run. A batch
# WITHOUT repeats cannot gain: with the sums always formed the push loses
# 11.9 % to the plain one under uniform ids (32 % as shipped), so the batch
# is LOOKED AT (the first sort gives the distinct count) and the sums are
# formed only where at most SUM_RUNS_MAX_DISTINCT_SHARE of the pushed ids
# are distinct; past it the sorted batch is handed on as it is and the push
# loses 4.0 % (3.7 % as shipped: the sort and the gather, which the look
# cannot avoid). The constant sits at the measured win nearest the loss
# (57.5 % distinct: x1.48, x1.19 as shipped); (0.575, 0.994) is unmeasured.
# Over the ROWS (the FIRST form, every run a tree; both predicates answering
# yes whatever the rows; us a push, plain / push.sum_runs; Zipf(1.05) over
# the rows, then uniform; under each pair the distinct ids a step):
#
#   R            Zipf(1.05)             uniform
#   1,048,576    41898 / 9756  (x4.3)   42502 / 22119 (x1.9)
#                94,935                 350,008 (82 %: not summed)
#   4,194,304    43260 / 16526 (x2.6)   43325 / 48661 (-12 %)
#                115,959                405,036
#   8,440,645    43942 / 17339 (x2.5)   43277 / 46620 (-7.7 %)
#                125,137                415,410
#   33,762,577   (the cell, above: x3.6; shipped x2.4) (-4.0 %; -3.7 %)
#
# (8,440,645 rows: a shard of ``dlrm-criteo``'s table on four.) AS SHIPPED
# (call 225, the same sweep, plain / push.sum_runs):
#
#   1,048,576    41919 / 19129 (x2.19)  42518 / 46461 (-9.3 %)
#   4,194,304    43302 / 22774 (x1.90)  43327 / 48752 (-12.5 %)
#   8,440,645    43969 / 23957 (x1.84)  43296 / 46713 (-7.9 %)
#   33,762,577   (the cell: x2.43)                    (-3.7 %)
#
# Under skew the route wins every measured point, x1.84 - x2.43 as shipped
# (x2.5 - x4.3 with every run a tree); without repeats it loses 3.7 -
# 12.5 %, the sort, the gather and a block loop that is up to 4 % dearer
# on a sorted batch than the plain op on an unsorted one (1.7 % cheaper at
# 33.8 M rows; at 1,048,576 rows the first form read a WIN without
# repeats, 22119, which the shipped form does not repeat: not explained).
# XLA_TRANSPOSED_HBM_ROWS is the fewest rows measured; between
# the lane-packed route's reach (about 0.5 M rows at width 16) and it
# nothing is measured and the plain routes stay. The width swept is 16;
# 8 and 32 (``_xla_packable``'s ends) compile to the same layout at
# 4,194,304 rows and past 16 M, one to four groups of eight sublanes a row,
# and the widths between are taken with it unswept, as width 4 was with 3
# above. float32 alone: the sums are formed in the table's dtype.
#
# HOW a run's rows meet is part of the answer. The plain scatter adds an
# id's rows to the table's row ONE BY ONE, each addend rounded at the ROW's
# magnitude, and so does any plain float32 reference; a tree over the
# addends alone and one add to the row is nearer the exact sum, and differs
# from the chain by the chain's own rounding, which on a row that takes
# thousands of small addends a step is large against the row's net change:
# with every run summed in a tree (the readings above are that form's)
# ``dlrm-criteo.epochs`` read 548,300 examples/s and its comparison refused
# 2 runs of 8 (``update_gap`` of the 3-row field 1.17e-2 and 3.3e-3, of the
# 4-row field 3.1e-3, where 2.5e-3 is the limit and the plain push reads
# 5e-5; by field the gap fell with the rows: 1.8e-3 at 18 rows, 4.5e-4 at
# 105, level with the large fields from 305; builder's chip run, PR 49,
# call 208). So a LONG run, of more than SUM_RUNS_TREE_MAX_RUN rows, is
# CHAINED as the plain scatter chains it: its rows are scatter-added, in
# the batch's order (the first sort's second key is the position), into a
# copy of the table's rows in a ``[B / 33, dim]`` buffer that XLA keeps in
# VMEM (its small-table scatter, a few ns a row handed: the emitter a
# reference's own small tables take), and what the buffer's row changed by
# is the run's one row to add; on the CPU the chained rows equal the plain
# scatter's bit for bit (``tests/test_store.py``). On the cell 1,173 rows a
# step are long and take 274,249 of its 425,984 pushes; a run of up to 32
# rows stays a tree (about one ulp of the row a step apart from its chain).
# The constant is the one value run; others were not.
#
# The PULL of the same regime (fps_tpu.core.store.pull, "pull.distinct_rows",
# PR 54). The plain gather out of the HBM-resident transposed table pays for
# every id it is handed as the scatter does, a repeat or not (21.5 - 22.9 ns
# an id at 425,984 ids a step, skewed or uniform; 38 at 12,909: not flat in
# the ids). So the pull sorts (id or sentinel, position), LOOKS at the batch
# by the push's own test (SUM_RUNS_MAX_DISTINCT_SHARE) and, where it repeats
# itself, sorts the distinct ids to the front, reads their rows a block of
# XLA_SORTED_BLOCK_IDS at a time and only the live blocks (gather_rows:
# "gather.xla" with that many ids) into a buffer of the BATCH'S shape,
# brings each entry's run number back to the batch's order by a third sort
# and takes each entry's row out of the buffer; a batch that hardly repeats
# itself is gathered id by id as before. Any float dtype of the regime's
# predicate: a gather copies. In time (``tools/bench_scatter.py rows dlrm
# pull``, one v5 lite chip, builder's chip runs, PR 54, call 259; f32,
# [33762577,16], 425,984 ids a step, the table a donated loop carry; us a
# call; the cell's ids: 78,500 distinct, uniform ids: 423,306):
#
#                                          the cell's   uniform
#   gather_rows, plain                     9322         9119
#   store.pull, plain                      9282         9158
#   store.pull, pull.distinct_rows         6136         9654  (+5.4 %)
#   ... each distinct row once WHATEVER the batch       13075 (+43 %)
#   the parts, each from host-made inputs, the cell's ids:
#   the first sort of (id, position)       599
#   the firsts, their count, the sort of the ids alone  508
#   the block loop over the live ids       2353   (uniform: 9272)
#   (position, run) back by a sort         565    (by a scalar scatter: 2207)
#   the expand, 425,984 rows of the buffer 2260
#
# 30.0 ns a LIVE id at 78,500 sorted distinct ids (25.8 inside the cell's
# step) and 21.9 at 423,306: a sorted distinct id is no cheaper than an
# unsorted one, the gain is the repeats not read, 81.6 % on the cell, less
# the three sorts and the expand: 9.28 -> 6.14 ms. A batch WITHOUT repeats
# pays the first sort and the look, 5.4 %; with the look dropped it would
# pay 43 %. THE BUFFER'S SHAPE IS PART OF THE ANSWER: with the batch's own
# shape XLA keeps the buffer and the rows the expand hands back in one
# layout, transposed and in VMEM, and the expand is the gather the push
# makes of its own rows (2.26 ms; 1.93 in the step); a buffer of
# SUM_RUNS_MAX_DISTINCT_SHARE of the ids ([256000,16], all a batch that
# repeats itself can fill: the first form, call 258) is copied ROW-MAJOR
# into HBM for the expand, whose rows come back row-major and are relaid
# out twice: 6477 us in isolation, 4.48 ms in the step, the pull no faster
# than plain in isolation (9558) and the cell at x1.063 where the batch's
# shape gives x1.163. ``jnp.take`` in its default mode or along the lanes
# of the transposed buffer compiles to the same copies. Compile-only, v5e
# (``tests/test_v5e_compile.py`` guards it): the table is an operand of the
# look's conditional, ``{0,1:T(8,128)}`` everywhere and never copied (the
# cell's temporaries FALL, 0.904 -> 0.770 GB), and the three sorts carry one
# or two s32 operands (the step compiles about as fast as its parent).
# Unmeasured: every other row count, width and id count of the regime
# (``dlrm-criteo.x4``'s shard of 8,440,645 rows under its lanes' ids among
# them), distinct shares between 18.4 % and 99.4 %, bfloat16.
XLA_TRANSPOSED_HBM_ROWS = 1_048_576
SUM_RUNS_MAX_DISTINCT_SHARE = 0.6
SUM_RUNS_TREE_MAX_RUN = 32


def _bf16_pair_ok(dtype) -> bool:
    """The dim-1 kernels carry values as bf16 hi+lo: f64 would silently
    lose 8 mantissa bits and integer tables their exact-add semantics."""
    dt = jnp.dtype(dtype)
    return dt.itemsize <= 4 and jnp.issubdtype(dt, jnp.floating)


def _route_dim1(R: int, D: int, B: int, dtype=jnp.float32) -> bool:
    if D != 1 or not _bf16_pair_ok(dtype) or not _use_pallas()[0]:
        return False
    return R <= DIM1_MAX_ROWS and B >= DIM1_MIN_BATCH


def _tiled_table_bytes(rows: int, dim: int, dtype) -> int:
    """Bytes of ``rows`` rows of ``dim`` numbers in XLA's row-major tiled
    form: a row takes whole 128-lane tiles, one for any width up to 128
    (512 B in f32 at rank 10), three at 300."""
    return rows * -(-dim // 128) * 128 * jnp.dtype(dtype).itemsize


def _xla_packed_rows(R: int, D: int) -> int:
    """Rows of the lane-packed form of an ``[R, D]`` table: ``128 // D``
    table rows a packed row, rounded up to whole 128-row tiles (the packed
    form is taken from the transposed one, where rows run along lanes)."""
    return -(-R // (128 // D * 128)) * 128


def _xla_packable(D: int, dtype) -> bool:
    """A swept width, and a float of at most 4 bytes (a row is 128 lanes)."""
    return (XLA_PACKED_DIMS[0] <= D <= XLA_PACKED_DIMS[1]
            and _bf16_pair_ok(dtype))


def _route_xla_packed(R: int, D: int, B: int, dtype) -> bool:
    """Take the lane-packed XLA route? From shapes alone: on the TPU
    (backend not ``"xla"``), a float table of a swept width whose plain
    tiled form is past XLA's VMEM regime (:data:`XLA_VMEM_TABLE_BYTES`)
    while its packed form is well inside
    (:data:`XLA_PACKED_TABLE_BYTES`), moving enough ids to pay for the
    relayout."""
    use, interpret = _use_pallas()
    if not use or interpret or not _xla_packable(D, dtype):
        return False
    return (B >= XLA_PACKED_MIN_IDS
            and _tiled_table_bytes(R, D, dtype) > XLA_VMEM_TABLE_BYTES
            and _tiled_table_bytes(_xla_packed_rows(R, D), 128 // D * D, dtype)
            <= XLA_PACKED_TABLE_BYTES)


def _xla_transposed(R: int, D: int, dtype) -> bool:
    """A narrow table so far past XLA's VMEM regime that XLA keeps it
    TRANSPOSED (in VMEM while that form fits), where its scatter-add pays
    the same for every id it is handed: a swept width
    (:data:`XLA_TRANSPOSED_DIMS`) of at most 4 bytes a number whose
    row-major tiles are past :data:`XLA_TRANSPOSED_TABLE_BYTES`."""
    return (D in XLA_TRANSPOSED_DIMS
            and jnp.dtype(dtype).itemsize <= 4
            and _tiled_table_bytes(R, D, dtype) > XLA_TRANSPOSED_TABLE_BYTES)


def _xla_transposed_hbm(R: int, D: int, dtype) -> bool:
    """A table of the lane-packed route's widths (:func:`_xla_packable`)
    with so many rows that XLA keeps it TRANSPOSED IN HBM, far past that
    route's reach and past XLA's VMEM regime
    (:data:`XLA_TRANSPOSED_HBM_ROWS`, the fewest rows measured): there the
    plain scatter-add pays about 100 ns for every id it is handed, a
    repeat or dropped by the sentinel alike."""
    return _xla_packable(D, dtype) and R >= XLA_TRANSPOSED_HBM_ROWS


def _route_xla_sorted(R: int, D: int, B: int, dtype,
                      ids_sorted: bool) -> bool:
    """Scatter by blocks and stop where the dropped ids begin? Only on the
    caller's guarantee, on the TPU (backend not ``"xla"``), for more ids
    than one block, where the plain op pays for every id it is handed,
    dropped or not. Three such regimes are measured: rows WIDER than the
    lane-packed route's (:data:`XLA_PACKED_DIMS`) in a table whose tiled
    form is past XLA's VMEM regime (:data:`XLA_VMEM_TABLE_BYTES`), under
    few enough ids against the rows (:data:`XLA_SORTED_ROWS_PER_ID`);
    rows NARROWER than them in a table XLA keeps transposed
    (:func:`_xla_transposed`), whatever the ids; and rows of that route's
    own widths in a table it cannot reach, which XLA keeps transposed in
    HBM (:func:`_xla_transposed_hbm`), whatever the ids."""
    use, interpret = _use_pallas()
    if not (ids_sorted and use and not interpret
            and jnp.dtype(dtype).itemsize <= 4
            and B > XLA_SORTED_BLOCK_IDS):
        return False
    return _xla_transposed(R, D, dtype) or _xla_transposed_hbm(
        R, D, dtype) or (
        D > XLA_PACKED_DIMS[1]
        and _tiled_table_bytes(R, D, dtype) > XLA_VMEM_TABLE_BYTES
        and B <= R // XLA_SORTED_ROWS_PER_ID)


def _xla_reason(R: int, D: int, dtype) -> str:
    """Why a call that reached the plain XLA route took no other: the
    dtype cannot ride the kernels' f32 / bf16-pair
    arithmetic, the backend keeps every other route out, the table is
    already inside XLA's VMEM regime (so the lane-packed route has nothing
    to add), or no route serves the shape under this backend (a
    scatter-add of wide rows past the VMEM regime whose caller gave no
    ``ids_sorted`` guarantee among them)."""
    if jnp.dtype(dtype).itemsize > 4:
        return "f64"
    use, interpret = _use_pallas()
    if not use:
        return "backend"
    if (not interpret and _xla_packable(D, dtype)
            and _tiled_table_bytes(R, D, dtype) <= XLA_VMEM_TABLE_BYTES):
        return "vmem_fit"
    return "shape"


def _xla_pack(table: Array) -> Array:
    """``[R, D]`` -> lane-packed ``[Rp, D * pack]``: table row ``i`` lies in
    packed row ``i % Rp``, lanes ``d * pack + i // Rp``. Taken from the
    TRANSPOSED table, which is how XLA keeps a narrow table of this size
    in HBM (``{0,1:T(8,128)}``, entry parameter and loop carry alike): a
    lane pad, a reshape that splits lanes at a multiple of 128 and one 2-D
    transpose of the packed bytes, all of which XLA runs in VMEM. Packing
    consecutive rows instead (``table.reshape(R // pack, pack * D)``) makes
    XLA carry the table row-major, 128 lanes a row, and relayout that each
    way each step (246 MB at Netflix's user block, compile-only)."""
    R, D = table.shape
    pack, Rp = 128 // D, _xla_packed_rows(R, D)
    tT = jnp.pad(table.T, ((0, 0), (0, pack * Rp - R)))
    return tT.reshape(D * pack, Rp).T


def _xla_unpack(packed: Array, R: int, D: int) -> Array:
    return packed.T.reshape(D, -1)[:, :R].T


def _xla_packed_slots(R: int, D: int, ids: Array):
    """Where each id lies in the packed form: ``in_range [B]``, its packed
    ``row [B]`` (0 where out of range) and the one-hot ``sel [B, pack]`` of
    its lane group (all False where out of range)."""
    pack, Rp = 128 // D, _xla_packed_rows(R, D)
    in_range = (ids >= 0) & (ids < R)
    safe = jnp.where(in_range, ids, 0)
    sel = ((jnp.arange(pack, dtype=safe.dtype)[None, :]
            == (safe // Rp)[:, None]) & in_range[:, None])
    return in_range, safe % Rp, sel


def _xla_packed_gather(table: Array, ids: Array) -> Array:
    """``gather.xla_packed``: one XLA gather of whole packed rows, then the
    id's own lanes picked by its one-hot. Exact: a row's ``D`` numbers and
    zeros are summed (a ``-0.0`` reads ``+0.0``)."""
    R, D = table.shape
    with _routed("gather", "xla_packed", R, D, ids.shape[0]):
        _, row, sel = _xla_packed_slots(R, D, ids)
        rows = jnp.take(_xla_pack(table), row, axis=0)
        rows = rows.reshape(ids.shape[0], D, 128 // D)
        return jnp.sum(jnp.where(sel[:, None, :], rows, 0), axis=2)


def _xla_packed_scatter_add(table: Array, ids: Array,
                            deltas: Array) -> Array:
    """``scatter_add.xla_packed``: each update widened to its packed row
    (zero outside the id's own lanes: adding ``+0.0`` to the neighbours is
    exact), one XLA scatter-add into the packed table, unpacked. XLA sorts
    ``(packed row, position)``, so one id's duplicates add in the batch's
    order, as on the plain route."""
    R, D = table.shape
    with _routed("scatter_add", "xla_packed", R, D, ids.shape[0]):
        in_range, row, sel = _xla_packed_slots(R, D, ids)
        Rp = _xla_packed_rows(R, D)
        upd = jnp.where(sel[:, None, :],
                        deltas.astype(table.dtype)[:, :, None], 0)
        packed = _xla_pack(table).at[jnp.where(in_range, row, Rp)].add(
            upd.reshape(ids.shape[0], -1), mode="drop")
        return _xla_unpack(packed, R, D)


def _xla_sorted_scatter_add(table: Array, ids: Array, deltas: Array,
                            op: str = "scatter_add") -> Array:
    """``scatter_add.xla_sorted``: the plain XLA scatter-add, a block of
    :data:`XLA_SORTED_BLOCK_IDS` ids at a time, in place on the table, for
    as many blocks as hold a live id. The guarantee puts the ids to drop
    last, so the blocks past ``ceil(live / block)`` hold nothing but them.
    The last block starts early enough to end with the batch; the ids it
    shares with the block before are dropped from it. Duplicates add in
    the batch's order, as on the plain route. With ``op="scatter_set"``
    the same loop round the plain XLA scatter that WRITES the rows
    (``scatter_set.xla_sorted``)."""
    R, D = table.shape
    B, C = ids.shape[0], XLA_SORTED_BLOCK_IDS
    with _routed(op, "xla_sorted", R, D, B):
        safe = jnp.where((ids >= 0) & (ids < R), ids, R)
        deltas = deltas.astype(table.dtype)
        live = jnp.sum((safe < R).astype(jnp.int32))

        def block(c, t):
            start = jnp.minimum(c * C, B - C)
            i = jax.lax.dynamic_slice(safe, (start,), (C,))
            i = jnp.where(start + jnp.arange(C) >= c * C, i, R)
            d = jax.lax.dynamic_slice(deltas, (start, 0), (C, D))
            if op == "scatter_set":
                return t.at[i].set(d, mode="drop")
            return t.at[i].add(d, mode="drop")

        return jax.lax.fori_loop(0, (live + C - 1) // C, block, table)


def _route_head_prefix(R: int, D: int, head_prefix: int, hot_rows: int,
                       dtype) -> bool:
    """Route the guaranteed-head prefix through a head-only dim-1 kernel?

    The dim-1 kernels are STREAM-bound at small row counts (cost ~
    ``rp x B`` — measured round 5, tools/bench_logreg_routes.py), so the
    head-only form saves the row-tile factor on the prefix slice: at the
    PA shape the composite is worth ~15% of the END-TO-END headline
    (measured with the machinery off: 4.53M vs 5.36M examples/s). The
    caller guarantees ``ids[:head_prefix]`` are in ``[0, hot_rows) ∪
    {-1}`` (ingest-side frequency sort — see
    ``fps_tpu.utils.datasets.head_sort_slots``)."""
    if head_prefix < 2048 or hot_rows <= 0 or D != 1:
        return False
    if not _bf16_pair_ok(dtype) or not _use_pallas()[0]:
        return False
    # Head kernel must be meaningfully cheaper than running the prefix
    # through the full-table route it would otherwise take.
    return hot_rows * 4 <= R


def gather_rows(table: Array, ids: Array, *, hot_rows: int = 0,
                head_prefix: int = 0, exact: bool = False) -> Array:
    """``table[ids]``; ids outside ``[0, rows)`` yield **zero rows** on every
    backend (the pull path's ``-1`` padding slots read as zeros; real pulls
    are always in range).

    ``exact=True`` forces the bit-exact XLA gather regardless of backend
    and shape: the dim-1 route reads scalar tables through a hi+lo bf16
    pair (~16 mantissa bits) whenever ``B >= DIM1_MIN_BATCH``, which is a
    deliberate TRAINING concession. This is the per-call escape hatch for
    read-only consumers (an eval pass or audit pulling through the device
    path); the store's :func:`pull` forwards it. The shipped host-side
    read paths (``lookup_host``/``dump_model``) read the table arrays
    directly and are always exact.

    ``head_prefix > 0`` (with ``hot_rows = H``) asserts the STATIC
    guarantee that ``ids[:head_prefix]`` lie in ``[0, H) ∪ {-1}`` — the
    frequency-ranked head a sorted-slot batch layout puts first. The
    prefix then reads through a head-only kernel whose cost scales with
    ``ceil(H/128)`` row tiles instead of ``ceil(R/128)``. Violating the
    guarantee silently reads zeros for the out-of-head ids (the drop
    contract), so callers must only pass prefixes the ingest layer
    actually certified. ``hot_rows`` is the ``H`` of that guarantee and
    nothing else: without ``head_prefix`` it changes nothing.
    """
    R, D = table.shape
    B = ids.shape[0]
    interpret = _use_pallas()[1]
    if not exact and _route_head_prefix(R, D, head_prefix, hot_rows,
                                        table.dtype):
        from fps_tpu.ops.pallas_kernels import gather_rows_dim1_pallas

        with _routed("gather", "dim1_head", hot_rows, D, head_prefix):
            head = gather_rows_dim1_pallas(
                table[:hot_rows], ids[:head_prefix], interpret=interpret
            )
        # The tail opens its own scope BESIDE the head's, not under it.
        tail = gather_rows(table, ids[head_prefix:])
        return jnp.concatenate([head, tail], axis=0)
    if not exact and _route_dim1(R, D, B, table.dtype):
        from fps_tpu.ops.pallas_kernels import gather_rows_dim1_pallas

        with _routed("gather", "dim1", R, D, B):
            return gather_rows_dim1_pallas(table, ids, interpret=interpret)
    if _route_xla_packed(R, D, B, table.dtype):
        return _xla_packed_gather(table, ids)
    reason = "" if exact else _xla_reason(R, D, table.dtype)
    with _routed("gather", "xla", R, D, B, reason):
        in_range = (ids >= 0) & (ids < R)
        vals = jnp.take(table, jnp.where(in_range, ids, 0), axis=0)
        return jnp.where(in_range[:, None], vals, jnp.zeros_like(vals))


def scatter_add(
    table: Array, ids: Array, deltas: Array, *, hot_rows: int = 0,
    head_prefix: int = 0, ids_sorted: bool = False
) -> Array:
    """``table.at[ids].add(deltas)``; ids outside ``[0, rows)`` are dropped,
    duplicate ids accumulate (the server's additive ``paramUpdate`` fold).

    ``head_prefix`` and ``hot_rows`` are :func:`gather_rows`'s: the
    certified prefix is accumulated into the head slice by a head-only
    kernel, the tail goes down the chain again. The dim-1 routes carry f32
    deltas as a hi+lo bf16 pair (~16 of 24 mantissa bits — see
    :mod:`fps_tpu.ops.pallas_kernels`), so their sums can differ from the
    XLA scatter's in the low mantissa bits; a table wider than f32 takes
    the XLA scatter, which adds in the table's own dtype (every other
    predicate rejects it: :func:`_bf16_pair_ok`).

    ``ids_sorted=True`` asserts the STATIC guarantee that ``ids`` are
    non-decreasing and none is negative, so the ids to drop (``>= rows``)
    come last; duplicates are then adjacent and still accumulate. A wide
    table past XLA's VMEM regime, or a narrow one, then takes
    ``scatter_add.xla_sorted`` (:func:`_route_xla_sorted`), which never
    looks at the ids past the last live one; everywhere else the
    guarantee is accepted and changes nothing. The answer is the plain
    route's on every input that keeps the promise; one that breaks it is
    silently wrong on the TPU, so only a caller that made the order itself
    gives it (:func:`fps_tpu.core.store.push`'s ``push.mean_rows`` and
    ``push.acc_runs``).
    """
    R, D = table.shape
    B = ids.shape[0]
    interpret = _use_pallas()[1]
    if _route_head_prefix(R, D, head_prefix, hot_rows, table.dtype):
        from fps_tpu.ops.pallas_kernels import scatter_add_dim1_pallas

        with _routed("scatter_add", "dim1_head", hot_rows, D, head_prefix):
            head_new = scatter_add_dim1_pallas(
                table[:hot_rows], ids[:head_prefix], deltas[:head_prefix],
                interpret=interpret,
            )
            table = jax.lax.dynamic_update_slice_in_dim(table, head_new, 0,
                                                        axis=0)
        # The tail opens its own scope BESIDE the head's, not under it.
        return scatter_add(table, ids[head_prefix:], deltas[head_prefix:])
    if _route_dim1(R, D, B, table.dtype):
        from fps_tpu.ops.pallas_kernels import scatter_add_dim1_pallas

        with _routed("scatter_add", "dim1", R, D, B):
            return scatter_add_dim1_pallas(table, ids, deltas,
                                           row_tile=512, batch_tile=8192,
                                           interpret=interpret)
    if _route_xla_packed(R, D, B, table.dtype):
        return _xla_packed_scatter_add(table, ids, deltas)
    if _route_xla_sorted(R, D, B, table.dtype, ids_sorted):
        return _xla_sorted_scatter_add(table, ids, deltas)
    with _routed("scatter_add", "xla", R, D, B,
                 _xla_reason(R, D, table.dtype)):
        # Dropped by the sentinel row ALONE. Masking the deltas as well is
        # redundant, and a select on a worker's local deltas made XLA lay
        # their producer out row-major, which put the push's all-gather of
        # the SIBLING deltas into 128-lane rows in HBM (mf-netflix.x4:
        # 77.5 M -> 50.4 M examples/s, chip run, PR 25;
        # tests/test_v5e_compile.py guards the layout).
        keep = (ids >= 0) & (ids < R)
        safe = jnp.where(keep, ids, R)
        return table.at[safe].add(deltas.astype(table.dtype), mode="drop")


def scatter_set(table: Array, ids: Array, rows: Array, *,
                ids_sorted: bool = False) -> Array:
    """``table.at[ids].set(rows)`` for DISTINCT ids; ids outside ``[0,
    rows)`` are dropped (any number of them: they write nothing), every
    row not named keeps its bits. What a stateful fold writes back: a
    row's new value, not a sum into it. ``ids_sorted`` is
    :func:`scatter_add`'s guarantee and opens the same block loop
    (``scatter_set.xla_sorted``: :func:`_route_xla_sorted`), which never
    looks past the last live id; everywhere else the plain XLA scatter
    (``scatter_set.xla``). Two in-range ids that are equal leave either
    of their rows."""
    R, D = table.shape
    B = ids.shape[0]
    if _route_xla_sorted(R, D, B, table.dtype, ids_sorted):
        return _xla_sorted_scatter_add(table, ids, rows, op="scatter_set")
    with _routed("scatter_set", "xla", R, D, B,
                 _xla_reason(R, D, table.dtype)):
        safe = jnp.where((ids >= 0) & (ids < R), ids, R)
        return table.at[safe].set(rows.astype(table.dtype), mode="drop")
