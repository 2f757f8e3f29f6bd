"""Implicit-feedback iALS (Hu, Koren, Volinsky 2008) — sharded ALS driver.

BASELINE.json names "Implicit-feedback iALS (MovieLens-20M)" as a required
workload; SURVEY.md §6/§7 flag it as an *extension* (likely absent from the
reference) needing a different driver from the streaming PS loop: per-epoch
sharded normal-equation solves instead of per-record SGD.

Model: observed interaction (u, i, r) has confidence ``c = 1 + alpha*r`` and
preference 1; unobserved pairs have confidence 1 and preference 0. Each
half-epoch fixes one side and solves, per id on the other side,

    (G + alpha * sum_i r_ui * y_i y_i^T + reg*I) x_u = sum_i (1+alpha*r_ui) y_i

with ``G = Y^T Y`` the Gramian over *all* items (the classic trick that makes
the "all unobserved pairs" term tractable).

TPU-native decomposition (everything static-shape, jit-compiled once):

1. **Gramian** — each shard computes ``local_block^T @ local_block`` on its
   ``(rps, k)`` rows (MXU matmul) and ``psum``s over the shard axis.
   Padding rows are zeroed first via an on-device validity mask.
2. **Accumulate** — one program a chunk of interactions, two parts. A
   narrow scan in the plan's step order: collective
   :func:`fps_tpu.core.store.pull` of both sides' rows for the step's
   ``n`` and ``loss``, nothing pushed. Then the chunk GROUPED by the
   solved side's id on the device: the worker's live ratings sorted by
   that id (a stable ``lax.sort``: an id's ratings stay in the plan's
   order) and, a block of :data:`RUN_BLOCK` ratings at a time, the fixed
   side's rows pulled in sorted order, every rating's addend formed
   (``(alpha r w y) y^T``, k*k, and ``(1 + alpha r) w y``, k) and a
   kernel (:func:`_run_sums`) adding an id's addends ONE AT A TIME in
   float32 to a running sum it keeps on the chip, which it writes out as
   ONE row where the id's run ends. The finished rows go through
   collective :func:`~fps_tpu.core.store.push` into sharded accumulator
   tables keyed by the solved side's id, so an id that recurs in the next
   chunk or is rated on another worker is summed by the store's own
   rules. iALS thus *reuses the PS fabric*: the normal equations are just
   another sharded table being pushed to, one 16 KB row an ID a chunk at
   rank 64 where it was one a rating (PR 41). The sums are chains in the
   plan's order because that is the sum the benchmark's reference forms
   and float32 makes the order part of the answer (the chain of a movie's
   28,000 addends stands 8e-6 of its largest entry from the exact sum;
   any other grouping, a matrix product a segment among them, stands
   nearer the exact sum and as far from the chain:
   ``tools/ials_sum_order.py``).
3. **Solve** — each shard solves its own ``(rps, k, k)`` batched SPD systems
   locally by a Cholesky factorisation and two triangular solves (the
   left-hand side is the Gramian plus a non-negative combination of outer
   products plus ``reg*I``: SPD by construction), a block of ids at a
   time (:data:`SOLVE_BLOCK_IDS`), no communication.

Float32 means float32: the Gramian's contraction carries
``precision=HIGHEST`` (the TPU's default would run it in bfloat16
passes: 7e-5 on the solved tables where this reads 1e-6; chip runs, PR 35),
the sums are float32 additions on the vector unit, and XLA's Cholesky and triangular-solve kernels are float32 throughout (5e-7
against a float64 solve). A half-epoch only QUEUES device work and returns
what it did: per
accumulate step ``n`` (the live interactions) and ``loss``, the observed
term ``sum c (1 - x_u . y_i)^2`` under the tables the sweep READ (both
sides as the sweep found them), as device arrays nothing on the host waits
for.

Names (``docs/observability.md``): the accumulate program's scan body
opens ``fps.pull`` / ``fps.compute`` / ``fps.metrics`` as a ``Trainer``'s
step does; everything that forms the pushed sums (the sort, the block
loop's pull, addends, kernel and pushes) runs under ``fps.push``; what runs once a sweep is ``als.gram``, ``als.zeros`` and
``als.solve`` (no ``fps.`` prefix: a reader counts steps by the ops under
it); host spans ``als.half_epoch`` and inside it ``als.gram``,
``als.accumulate`` (one a chunk queued), ``als.solve``; the route log gets
one ``als.grouped`` and one ``als.solve`` a traced program.

The user and item factor tables share the owner-major-cyclic layout of
:mod:`fps_tpu.core.store`, so accumulators align row-for-row with the factor
table being solved and the solve phase is purely local.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from fps_tpu import ops
from fps_tpu.core.store import (
    ParamStore,
    TableSpec,
    phys_to_id,
    pull,
    push,
    ranged_uniform_init,
    rows_per_shard,
)
from fps_tpu.obs.timing import host_span, watch_device, watch_program
from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS

Array = jax.Array

USER_TABLE = "user_factors"
ITEM_TABLE = "item_factors"

# Ids a shard factorises at a time in the solve. XLA lays a batch of
# 64 x 64 float32 systems out with its 64 lanes padded to 128, 32 KB a
# system and three such arrays live (the left-hand sides, the factors, a
# transposed copy): at MovieLens-20M's 138,493 users and rank 64 the whole
# batch at once needs 13.6 GB beside the 2.27 GB accumulator and does not
# fit a 16 GB v5e (the LU of ``jnp.linalg.solve`` needs 9.2 GB and takes
# 2.78 s); in blocks of 8,192 it needs 0.8 GB and takes 1.15 s (16,384:
# 1.22 s; 32,768: 1.36 s; chip runs, PR 35).
SOLVE_BLOCK_IDS = 8192

# The accumulate program sums an id's ratings ONE AT A TIME, in the plan's
# order, in float32: the order the benchmark's reference adds them in (a
# float32 chain of 28,000 such addends stands 8e-6 of its largest entry
# from the exact sum and as far from any other grouping of the same
# addends, and the solve passes that on: ``tools/ials_sum_order.py``), in
# a kernel that keeps the running sum on the chip. It takes the sorted
# chunk a block of ratings at a time (their addends are formed by XLA and
# kept for the block: no more than RUN_BLOCK ratings, whose ids and bits
# sit in SMEM, nor than RUN_BLOCK_BYTES of addends), a tile of
# RUN_TILE_BYTES a grid step in VMEM (twice, for the pipeline), and
# pushes the finished sums RUN_PUSH rows at a time. At rank 64 an addend
# is 20 KB: 2,048 ratings a block, 256 a tile. Read on a v5e at
# MovieLens-20M's shape, seconds a user sweep + an item sweep of 8.4 M
# ratings each, the solves' 1.15 + 0.22 inside (probe runs, PR 41):
# blocks of 2,048 pushing 32 rows at a time 1.763 + 0.906; 64 rows
# 1.776 + 0.920; 128 rows 1.884 + 0.949; blocks of 4,096 by 32 / 64 /
# 128 rows 1.737 + 0.971 / 1.754 + 0.982 / 1.768 + 1.017; of 8,192 by
# 128 1.908 + 0.973; of 1,024 by 32 1.752 + 1.224. A block's addends
# that fit VMEM are kept there (42 MB at 2,048: 0.67 s an epoch to form
# and read them against 1.01 through HBM at 8,192); smaller blocks push
# more often, each push a whole RUN_PUSH rows however few sums the block
# finished.
RUN_BLOCK = 2048
RUN_BLOCK_BYTES = 160 << 20
RUN_TILE_BYTES = 5 << 20
RUN_PUSH = 32


def _sum_layout(k: int) -> tuple[int, int, int]:
    """How one id's sums lie in rows of 128 lanes: ``(kp, g, rows)``. The
    rank is padded to ``kp``, a power of two up to 128 or a multiple of
    128 beyond; the ``kp x kp`` left side fills the first ``g`` rows
    row-major; the right side's ``kp`` start the row after; ``rows`` is
    the whole, in ``(8, 128)`` float32 tiles (64, 32 and 40 at rank 64)."""
    kp = (1 << (k - 1).bit_length()) if k <= 128 else -(-k // 128) * 128
    g = -(-kp * kp // 128)
    return kp, g, -(-(g + -(-kp // 128)) // 8) * 8


def _addends(ya: Array, y: Array, by: Array) -> Array:
    """``(n, rows, 128)``: every rating's addend to its id's sums in
    :func:`_sum_layout`: ``ya y^T`` and ``by``, from ``(n, k)`` each.
    Written as broadcasts along lanes and lane rows (nothing reshapes
    the lanes), so that XLA forms it in one pass in the layout the kernel
    reads."""
    n, k = y.shape
    kp, g, rows = _sum_layout(k)
    ya, y, by = (jnp.pad(x, ((0, 0), (0, kp - k))) for x in (ya, y, by))
    if kp <= 128:
        m = 128 // kp  # rows of the left side a lane row holds
        ya = jnp.pad(ya, ((0, 0), (0, rows * m - kp)))
        group = lax.broadcasted_iota(jnp.int32, (1, 1, 128), 2) // kp
        col = ya[:, 0::m, None]
        for h in range(1, m):  # a select a group: no reshape of lanes
            col = jnp.where(group == h, ya[:, h::m, None], col)
        row = jnp.tile(y, (1, m))[:, None, :]
        by = jnp.pad(by, ((0, 0), (0, 128 - kp)))
    else:
        q = kp // 128  # lane rows a row of the left side fills
        col = jnp.pad(jnp.repeat(ya, q, axis=1),
                      ((0, 0), (0, rows - g)))[:, :, None]
        row = jnp.tile(y.reshape(n, q, 128), (1, rows // q, 1))
    by = by.reshape(n, -1, 128)
    # The left side's rows past ``g`` are zero; the right side lies there.
    return col * row + jnp.pad(
        by, ((0, 0), (g, rows - g - by.shape[1]), (0, 0)))


def _run_sums_kernel(code_ref, key_ref, add_ref, carry_ref, rows_ref, ids_ref,
                     carry_out_ref, acc, stage, sem, count, *, tile):
    """One grid step: ``tile`` ratings of the sorted order added to the
    running sum one after another. ``code`` bit 0: the rating is its id's
    first here (the sum restarts at zero); bit 1: its last (the sum is
    copied out as the next row of ``rows_ref``, in HBM, and its id as
    the next of ``ids_ref``)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc[...] = carry_ref[...]
        count[0] = 0

    def add_one(t, a):
        c = code_ref[i * tile + t]
        a = jnp.where((c & 1) > 0, 0.0, a) + add_ref[t]

        @pl.when((c & 2) > 0)
        def _():
            stage[...] = a
            copy = pltpu.make_async_copy(stage, rows_ref.at[count[0]], sem)
            copy.start()
            copy.wait()
            ids_ref[count[0]] = key_ref[i * tile + t]
            count[0] = count[0] + 1

        return a

    acc[...] = lax.fori_loop(0, tile, add_one, acc[...])

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        carry_out_ref[...] = acc[...]


def _run_sums(code: Array, key: Array, add: Array, carry: Array, tile: int):
    """The sums of the id runs that END inside a block of sorted ratings.

    ``add (n, R, 128)``: a rating's addend; ``key (n,)``: its id;
    ``code (n,)``: its first / last bits; ``carry (R, 128)``: the sum of
    the run the block opens inside, from the block before. Returns
    ``(rows, ids, carry)``: ``rows (n + RUN_PUSH, R, 128)`` and ``ids``
    as long, whose
    first entries are the finished runs' sums and ids in order (as many
    as ``code`` has last bits; the rest is never written), and the open
    run's sum. Every sum is a chain of float32 additions in the
    order given, from zero. The kernel is compiled where ``fps_tpu.ops``
    compiles its own (on the TPU) and interpreted elsewhere."""
    n, rows, _ = add.shape
    out = n + RUN_PUSH  # whole pushes can be cut from it
    tile_spec = pl.BlockSpec((tile, rows, 128), lambda i, *_: (i, 0, 0))
    sum_spec = pl.BlockSpec((rows, 128), lambda i, *_: (0, 0))
    return pl.pallas_call(
        functools.partial(_run_sums_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tile,),
            in_specs=[tile_spec, sum_spec],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM), sum_spec],
            scratch_shapes=[pltpu.VMEM((rows, 128), add.dtype),
                            pltpu.VMEM((rows, 128), add.dtype),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((out, rows, 128), add.dtype),
                   jax.ShapeDtypeStruct((out,), jnp.int32),
                   jax.ShapeDtypeStruct(carry.shape, add.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        name="als_run_sums",
        interpret=ops._use_pallas() != (True, False),
    )(code, key, add, carry)


@dataclasses.dataclass
class IALSConfig:
    num_users: int
    num_items: int
    rank: int = 16
    alpha: float = 40.0
    reg: float = 0.1
    init_scale: float = 0.01
    dtype: object = jnp.float32


class IALSSolver:
    """Alternating sharded normal-equation solver for implicit feedback.

    Usage::

        solver = IALSSolver(mesh, IALSConfig(nu, ni, rank=16))
        solver.init(jax.random.key(0))
        for _ in range(epochs):
            solver.epoch(lambda: interaction_chunks(...))
        users, items = solver.factors()
    """

    def __init__(self, mesh, cfg: IALSConfig):
        self.mesh = mesh
        self.cfg = cfg
        self.num_shards = mesh.shape[SHARD_AXIS]
        self.num_data = mesh.shape.get(DATA_AXIS, 1)
        # Workers = ALL devices: the interaction stream splits over both
        # mesh axes; pushes gather across the data axis (like the Trainer's)
        # so the replicated accumulators fold every worker's contributions
        # exactly once.
        self.num_workers = self.num_data * self.num_shards
        init = ranged_uniform_init(-cfg.init_scale, cfg.init_scale, cfg.rank,
                                   cfg.dtype)
        self.store = ParamStore(
            mesh,
            [
                TableSpec(USER_TABLE, cfg.num_users, cfg.rank, init, cfg.dtype),
                TableSpec(ITEM_TABLE, cfg.num_items, cfg.rank, init, cfg.dtype),
            ],
        )
        self._sharding = self.store.sharding
        self._replicated = NamedSharding(mesh, P())
        self._compiled_gram = {}
        self._compiled_acc = {}
        self._compiled_solve = {}
        self._compiled_zeros = {}
        # Overlapped host pipeline depth for half_epoch's chunk stream
        # (fps_tpu.core.prefetch): chunk assembly + placement run this
        # many chunks ahead on a worker thread. 0 = synchronous; the
        # accumulate order (and so the solve) is identical either way.
        self.prefetch = 0

    # -- state --------------------------------------------------------------

    def init(self, key: Array) -> dict[str, Array]:
        return self.store.init(key)

    def factors(self) -> tuple[np.ndarray, np.ndarray]:
        return (
            self.store.dump_model(USER_TABLE)[1],
            self.store.dump_model(ITEM_TABLE)[1],
        )

    # -- device-side pieces ---------------------------------------------------

    def _valid_mask(self, num_ids: int, rps: int):
        """(rps,) bool per shard: physical row is a real id (not padding)."""

        def local(_):
            me = lax.axis_index(SHARD_AXIS)
            phys = me * rps + jnp.arange(rps, dtype=jnp.int32)
            ids = phys_to_id(phys, self.num_shards, rps)
            return ids < num_ids

        return local

    def _gram_fn(self, num_ids: int, rps: int):
        """jit: sharded table -> replicated (k, k) Gramian (padding excluded)."""

        @jax.named_scope("als.gram")
        def device_fn(table):
            valid = self._valid_mask(num_ids, rps)(None)
            rows = jnp.where(valid[:, None], table, 0.0)
            # HIGHEST: the TPU's default runs an f32 contraction in bf16
            # passes.
            g = jnp.matmul(rows.T, rows, precision=lax.Precision.HIGHEST)
            return lax.psum(g, SHARD_AXIS)

        def run(table):
            return jax.shard_map(
                device_fn,
                mesh=self.mesh,
                in_specs=(P(SHARD_AXIS, None),),
                out_specs=P(),
                check_vma=False,
            )(table)

        return jax.jit(run)

    def _accumulate_fn(self, solve: str):
        """jit: stream one chunk of interactions into (A, b) accumulators.

        Chunk leaves are (T, B) with B split over ALL devices (the data AND
        shard axes): ``solve_ids``, ``fixed_ids``, ``rating``, ``weight``.
        Each worker sorts its own slice of the chunk by the solved side's
        id and pushes ONE pre-summed row for every id in it; with
        a data axis the pushes gather across it so the replicated
        accumulators fold every worker's contributions exactly once.
        Returns ``(A, b, metrics)``:
        per step ``n`` and ``loss`` summed over all workers, ``(T,)``
        each, under the two tables as they came in (``solve_table`` is
        read, never written, by this program).
        """
        cfg = self.cfg
        k = cfg.rank
        num_ids = cfg.num_users if solve == "user" else cfg.num_items
        data_axis = DATA_AXIS if self.num_data > 1 else None

        def device_fn(fixed_table, solve_table, A, b, chunk):
            T, B = chunk["weight"].shape
            kp, g, R = _sum_layout(k)
            # The worker's ratings, padded to whole blocks of whole tiles.
            tile = max(8, RUN_TILE_BYTES // (R * 512) // 8 * 8)
            blk = max(1, min(RUN_BLOCK, RUN_BLOCK_BYTES // (R * 512),
                             -(-T * B // tile) * tile) // tile) * tile
            blocks = -(-T * B // blk)
            N = blocks * blk
            # A run is an id's ratings in the sorted chunk: no more runs
            # than ids, nor than ratings.
            runs = min(num_ids, T * B)
            ops.log_route("als", "grouped", A.shape[0], k * k,
                          runs * self.num_workers, solve)

            def body(_, xs):
                solve_ids = xs["solve_ids"].astype(jnp.int32)
                fixed_ids = xs["fixed_ids"].astype(jnp.int32)
                r = xs["rating"].astype(cfg.dtype)
                w = xs["weight"].astype(cfg.dtype)

                with jax.named_scope("fps.pull"):
                    y = pull(fixed_table, fixed_ids,
                             num_shards=self.num_shards,
                             data_axis=data_axis)
                    x = pull(solve_table, solve_ids,
                             num_shards=self.num_shards,
                             data_axis=data_axis)
                with jax.named_scope("fps.compute"):
                    c = 1.0 + cfg.alpha * r  # confidence
                    miss = 1.0 - jnp.sum(x * y, axis=-1)
                    out = {"n": jnp.sum(w.astype(jnp.float32)),
                           "loss": jnp.sum((w * c * miss * miss)
                                           .astype(jnp.float32))}
                with jax.named_scope("fps.metrics"):
                    out = jax.tree.map(
                        lambda v: lax.psum(lax.psum(v, SHARD_AXIS),
                                           DATA_AXIS), out)
                return None, out

            _, metrics = lax.scan(body, None, chunk)

            with jax.named_scope("fps.push"):
                def column(x, dtype, fill):
                    return jnp.concatenate([
                        x.astype(dtype).reshape(T * B),
                        jnp.full((N - T * B,), fill, dtype)])

                r = column(chunk["rating"], cfg.dtype, 0)
                w = column(chunk["weight"], cfg.dtype, 0)
                # Group: the live ratings sorted by the solved side's id
                # (weight-0 slots last, under the key ``num_ids``), an
                # id's own in the plan's order (the sort is stable), each
                # carrying the fixed side's id, its confidence minus 1 and
                # its confidence, both masked.
                key, fixed, cr, cw = lax.sort(
                    (jnp.where(w > 0, column(chunk["solve_ids"], jnp.int32,
                                             num_ids), num_ids),
                     column(chunk["fixed_ids"], jnp.int32, 0),
                     cfg.alpha * r * w, (1.0 + cfg.alpha * r) * w),
                    num_keys=1, is_stable=True)
                # A run's first and last rating (the kernel's bits) and
                # how many runs end inside each block.
                edge = jnp.full((1,), -1, jnp.int32)
                first = key != jnp.concatenate([edge, key[:-1]])
                last = (key != jnp.concatenate([key[1:], edge])) & (
                    key < num_ids)
                code = first.astype(jnp.int32) + 2 * last.astype(jnp.int32)
                ended = jnp.sum(last.reshape(blocks, blk), axis=1,
                                dtype=jnp.int32)
                # Every worker pushes as often as the one with most to
                # push (the pulls and pushes are collectives).
                pushes = -(-lax.pmax(lax.pmax(ended, SHARD_AXIS),
                                     DATA_AXIS) // RUN_PUSH)

                def block(i, carry):
                    A, b, open_sum = carry
                    at = i * blk
                    y = pull(fixed_table,
                             lax.dynamic_slice(fixed, (at,), (blk,)),
                             num_shards=self.num_shards,
                             data_axis=data_axis)
                    # A rating's addend: ``(alpha r w y) y^T`` and
                    # ``(1 + alpha r) w y``.
                    add = _addends(
                        lax.dynamic_slice(cr, (at,), (blk,))[:, None] * y,
                        y, lax.dynamic_slice(cw, (at,), (blk,))[:, None] * y)
                    rows, run_ids, open_sum = _run_sums(
                        lax.dynamic_slice(code, (at,), (blk,)),
                        lax.dynamic_slice(key, (at,), (blk,)), add,
                        open_sum, tile)

                    def push_rows(j, Ab):
                        A, b = Ab
                        mine = (j * RUN_PUSH + jnp.arange(RUN_PUSH)
                                < ended[i])
                        # Rows past the block's runs were never written.
                        ids = jnp.where(mine, lax.dynamic_slice(
                            run_ids, (j * RUN_PUSH,), (RUN_PUSH,)), -1)
                        flat = jnp.where(
                            mine[:, None],
                            lax.dynamic_slice(
                                rows, (j * RUN_PUSH, 0, 0),
                                (RUN_PUSH, R, 128)).reshape(RUN_PUSH, -1),
                            0.0)
                        A = push(A, ids,
                                 flat[:, :kp * kp].reshape(-1, kp, kp)
                                 [:, :k, :k].reshape(-1, k * k),
                                 num_shards=self.num_shards,
                                 data_axis=data_axis)
                        b = push(b, ids, flat[:, g * 128:g * 128 + k],
                                 num_shards=self.num_shards,
                                 data_axis=data_axis)
                        return A, b

                    A, b = lax.fori_loop(0, pushes[i], push_rows, (A, b))
                    return A, b, open_sum

                A, b, _ = lax.fori_loop(
                    0, blocks, block,
                    (A, b, jnp.zeros((R, 128), cfg.dtype)))
            return A, b, metrics

        def run(fixed_table, solve_table, A, b, chunk):
            table = P(SHARD_AXIS, None)
            return jax.shard_map(
                device_fn,
                mesh=self.mesh,
                in_specs=(
                    table, table, table, table,
                    jax.tree.map(
                        lambda _: P(None, (DATA_AXIS, SHARD_AXIS)), chunk
                    ),
                ),
                out_specs=(table, table, P()),
                check_vma=False,
            )(fixed_table, solve_table, A, b, chunk)

        return jax.jit(run, donate_argnums=(2, 3))

    def _solve_fn(self, num_ids: int, rps: int):
        """jit: (gram, A, b) -> solved factor table: each shard's own
        ``(rps, k, k)`` SPD systems by a batched Cholesky factorisation
        and two triangular solves, float32 throughout,
        :data:`SOLVE_BLOCK_IDS` ids at a time. Padding rows come out
        zero."""
        cfg = self.cfg
        k = cfg.rank
        blk = min(SOLVE_BLOCK_IDS, rps)

        @jax.named_scope("als.solve")
        def device_fn(gram, A, b):
            ops.log_route("als", "solve", rps, k, blk, "cholesky")
            base = gram + cfg.reg * jnp.eye(k, dtype=cfg.dtype)

            def block(j, x):
                # The last block starts early: it solves some ids again,
                # to the same rows.
                lo = jnp.minimum(j * blk, rps - blk)
                lhs = base[None] + lax.dynamic_slice(
                    A, (lo, 0), (blk, k * k)).reshape(blk, k, k)
                rhs = lax.dynamic_slice(b, (lo, 0), (blk, k))[:, :, None]
                chol = lax.linalg.cholesky(lhs)
                z = lax.linalg.triangular_solve(
                    chol, rhs, left_side=True, lower=True)
                sol = lax.linalg.triangular_solve(
                    chol, z, left_side=True, lower=True, transpose_a=True)
                return lax.dynamic_update_slice(x, sol[:, :, 0], (lo, 0))

            x = lax.fori_loop(0, -(-rps // blk), block,
                              jnp.zeros((rps, k), cfg.dtype))
            valid = self._valid_mask(num_ids, rps)(None)
            return jnp.where(valid[:, None], x, 0.0).astype(cfg.dtype)

        def run(gram, A, b):
            return jax.shard_map(
                device_fn,
                mesh=self.mesh,
                in_specs=(P(), P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
                out_specs=P(SHARD_AXIS, None),
                check_vma=False,
            )(gram, A, b)

        return jax.jit(run)

    # -- half-epoch ----------------------------------------------------------

    def _zeros_acc(self, rows: int, dim: int) -> Array:
        fn = self._compiled_zeros.get((rows, dim))
        if fn is None:
            fn = self._compiled_zeros[(rows, dim)] = jax.jit(
                jax.named_scope("als.zeros")(
                    lambda: jnp.zeros((rows, dim), self.cfg.dtype)),
                out_shardings=self._sharding,
            )
        return fn()

    def half_epoch(self, solve: str, chunks: Iterable[dict]) -> dict:
        """One ALS half-step: solve ``"user"`` or ``"item"`` factors.

        ``chunks`` yield dicts with (T, B) arrays ``user``, ``item``,
        ``rating``, ``weight`` (as produced by
        :func:`fps_tpu.core.ingest.epoch_chunks`; B must be divisible by
        ``num_workers`` = data * shard, the full device count).

        Only queues device work (nothing here reads a device value, so
        the next sweep can be queued behind this one) and returns the
        sweep's per-step device metrics: ``{"n", "loss"}``, each
        ``(steps,)`` over every chunk's steps in order, padding steps
        (weight 0) reading 0.
        """
        cfg = self.cfg
        if solve == "user":
            solve_name, fixed_name = USER_TABLE, ITEM_TABLE
            solve_col, fixed_col = "user", "item"
            solve_n, fixed_n = cfg.num_users, cfg.num_items
        elif solve == "item":
            solve_name, fixed_name = ITEM_TABLE, USER_TABLE
            solve_col, fixed_col = "item", "user"
            solve_n, fixed_n = cfg.num_items, cfg.num_users
        else:
            raise ValueError(f"solve must be 'user' or 'item', got {solve!r}")

        solve_rps = rows_per_shard(solve_n, self.num_shards)
        fixed_rps = rows_per_shard(fixed_n, self.num_shards)
        k = cfg.rank
        sharding = NamedSharding(self.mesh, P(None, (DATA_AXIS, SHARD_AXIS)))

        def to_dev(x):
            # Device-resident chunks (fps_tpu.core.device_ingest) reshard
            # on device; host chunks upload. Either way no host round trip
            # for data already on the mesh.
            if not isinstance(x, jax.Array):
                x = jnp.asarray(np.asarray(x))
            return jax.device_put(x, sharding)

        def place(chunk):
            return {
                "solve_ids": to_dev(chunk[solve_col]),
                "fixed_ids": to_dev(chunk[fixed_col]),
                "rating": to_dev(chunk["rating"]),
                "weight": to_dev(chunk["weight"]),
            }

        # The spans time the host's QUEUEING, recorder or none, as the
        # Trainer's ``enqueue`` does: nothing here waits for the device. (A
        # span that closed on the device's completion under a recorder made
        # a traced call return finished; the benchmark's traced loop stops
        # its profiler from inside its wait for a call and never did.)
        with host_span("als.half_epoch", call=True, solve=solve):
            fixed, solved = (self.store.tables[fixed_name],
                             self.store.tables[solve_name])
            with host_span("als.gram"):
                if fixed_name not in self._compiled_gram:
                    self._compiled_gram[fixed_name] = watch_program(
                        self._gram_fn(fixed_n, fixed_rps),
                        f"als.gram/{solve}")
                gram = self._compiled_gram[fixed_name](fixed)

            A = self._zeros_acc(solve_rps * self.num_shards, k * k)
            b = self._zeros_acc(solve_rps * self.num_shards, k)
            acc = self._compiled_acc.get(solve)
            if acc is None:
                acc = self._compiled_acc[solve] = watch_program(
                    self._accumulate_fn(solve), f"als.accumulate/{solve}")

            it, pf = chunks, None
            if self.prefetch:
                from fps_tpu.core.prefetch import ChunkPrefetcher

                it = pf = ChunkPrefetcher(chunks, place, depth=self.prefetch)
            metrics = []
            try:
                for item in it:
                    with host_span("als.accumulate"):
                        # Prefetched items arrive pre-placed (PlacedChunk).
                        dev_chunk = (item.batches if pf is not None
                                     else place(item))
                        A, b, m = acc(fixed, solved, A, b, dev_chunk)
                    metrics.append(m)
            finally:
                if pf is not None:
                    pf.close()

            with host_span("als.solve"):
                if solve_name not in self._compiled_solve:
                    self._compiled_solve[solve_name] = watch_program(
                        self._solve_fn(solve_n, solve_rps),
                        f"als.solve/{solve}")
                self.store.tables[solve_name] = self._compiled_solve[
                    solve_name](gram, A, b)
            # The sweep is queued: its completion (the solved table, which
            # nothing donates, and the last chunk's metrics) is the
            # watcher's to stamp, off this thread (a None test with no
            # recorder): ``device.als.half_epoch`` is how long the sweep
            # kept the device.
            watch_device("device.als.half_epoch",
                         (self.store.tables[solve_name], metrics[-1:]),
                         solve=solve,
                         steps=sum(m["n"].shape[0] for m in metrics))
            if len(metrics) < 2:
                return metrics[0] if metrics else {}
            return jax.tree.map(lambda *xs: jnp.concatenate(xs), *metrics)

    def epoch(self, make_chunks) -> tuple[dict, dict]:
        """One full ALS epoch. ``make_chunks()`` returns a fresh chunk
        iterator (it is consumed twice: once per half-epoch). Returns the
        two sweeps' metrics, the user sweep's first."""
        return (self.half_epoch("user", make_chunks()),
                self.half_epoch("item", make_chunks()))

    # -- evaluation ----------------------------------------------------------

    def weighted_loss(self, users: np.ndarray, items: np.ndarray,
                      ratings: np.ndarray, sample_unobserved: int = 0,
                      seed: int = 0) -> float:
        """Host-side iALS objective estimate: the observed confidence-weighted
        term ``sum c*(1 - x·y)^2`` plus the exact regularizer
        ``reg*(sum ||x_u||^2 + sum ||y_i||^2)`` (+ optionally a Monte-Carlo
        estimate of the unobserved ``(0 - x·y)^2`` term: the sampled mean
        scaled by ``num_users * num_items``; pairs are drawn uniformly with
        replacement, so observed pairs can be sampled too, biasing the
        estimate up by O(nnz / (U·I)) — negligible for sparse data)."""
        cfg = self.cfg
        U, V = self.factors()
        xy = np.sum(U[users] * V[items], axis=-1)
        c = 1.0 + cfg.alpha * ratings
        loss = float(np.sum(c * (1.0 - xy) ** 2))
        loss += cfg.reg * float(np.sum(U * U) + np.sum(V * V))
        if sample_unobserved:
            rng = np.random.default_rng(seed)
            su = rng.integers(0, cfg.num_users, sample_unobserved)
            si = rng.integers(0, cfg.num_items, sample_unobserved)
            mean_sq = float(np.mean(np.sum(U[su] * V[si], axis=-1) ** 2))
            loss += mean_sq * cfg.num_users * cfg.num_items
        return loss


def interaction_chunks(
    data: dict,
    *,
    num_workers: int,
    local_batch: int,
    steps_per_chunk: int,
    seed: int | None = 0,
) -> Iterator[dict]:
    """Fixed-shape (T, B) interaction chunks for the accumulate pass.

    Thin wrapper over :func:`fps_tpu.core.ingest.epoch_chunks` with
    round-robin placement (iALS has no worker-local state to route for).
    ``num_workers`` is ALL mesh devices (``IALSSolver.num_workers``) — the
    stream splits over the data AND shard axes.
    """
    from fps_tpu.core.ingest import epoch_chunks

    return epoch_chunks(
        data,
        num_workers=num_workers,
        local_batch=local_batch,
        steps_per_chunk=steps_per_chunk,
        seed=seed,
    )


def recall_at_k(
    solver: IALSSolver,
    heldout_user: np.ndarray,
    heldout_item: np.ndarray,
    *,
    k: int = 10,
    exclude: tuple[np.ndarray, np.ndarray] | None = None,
) -> float:
    """Fraction of held-out (user, item) pairs ranked in the user's top-k.

    ``exclude`` = (train_users, train_items) pairs masked out of the ranking
    (standard leave-out evaluation).
    """
    U, V = solver.factors()
    scores = U[heldout_user] @ V.T  # (H, num_items)
    if exclude is not None:
        tu, ti = exclude
        # One groupby of train items per user, then mask each evaluated
        # user's train items — but never the held-out item itself (it may
        # also occur in train when interactions repeat).
        order = np.argsort(tu, kind="stable")
        tu_s, ti_s = np.asarray(tu)[order], np.asarray(ti)[order]
        starts = np.searchsorted(tu_s, np.arange(solver.cfg.num_users))
        ends = np.searchsorted(tu_s, np.arange(solver.cfg.num_users), "right")
        for row, u in enumerate(heldout_user):
            held = scores[row, heldout_item[row]]
            scores[row, ti_s[starts[u]:ends[u]]] = -np.inf
            scores[row, heldout_item[row]] = held
    ranks = np.argsort(-scores, axis=1)[:, :k]
    return float(np.mean(np.any(ranks == heldout_item[:, None], axis=1)))
