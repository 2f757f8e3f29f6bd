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
2. **Accumulate** — stream interaction chunks through a scan: collective
   :func:`fps_tpu.core.store.pull` of the fixed side's rows, form per-example
   ``alpha*r * y y^T`` (k*k) and ``(1+alpha*r) * y`` (k) blocks, collective
   :func:`~fps_tpu.core.store.push` into sharded accumulator tables keyed by
   the solved side's id. iALS thus *reuses the PS fabric*: the normal
   equations are just another sharded table being pushed to.
3. **Solve** — each shard solves its own ``(rps, k, k)`` batched SPD systems
   locally by a Cholesky factorisation and two triangular solves (the
   left-hand side is the Gramian plus a non-negative combination of outer
   products plus ``reg*I``: SPD by construction), a block of ids at a
   time (:data:`SOLVE_BLOCK_IDS`), no communication.

Float32 means float32: the Gramian's contraction carries
``precision=HIGHEST`` (the TPU's default would run it in bfloat16 passes:
7e-5 on the solved tables where this reads 1e-6; chip runs, PR 35), and
XLA's Cholesky and triangular-solve kernels are float32 throughout (5e-7
against a float64 solve). A half-epoch only QUEUES device work and returns
what it did: per
accumulate step ``n`` (the live interactions) and ``loss``, the observed
term ``sum c (1 - x_u . y_i)^2`` under the tables the sweep READ (both
sides as the sweep found them), as device arrays nothing on the host waits
for.

Names (``docs/observability.md``): the accumulate body opens the step
scopes a ``Trainer`` opens (``fps.pull`` / ``fps.compute`` / ``fps.push`` /
``fps.metrics``); what runs once a sweep is ``als.gram``, ``als.zeros`` and
``als.solve`` (no ``fps.`` prefix: a reader counts steps by the ops under
it); host spans ``als.half_epoch`` and inside it ``als.gram``,
``als.accumulate`` (one a chunk queued), ``als.solve``; the route log gets
one ``als.accumulate`` and one ``als.solve`` a traced program.

The user and item factor tables share the owner-major-cyclic layout of
:mod:`fps_tpu.core.store`, so accumulators align row-for-row with the factor
table being solved and the solve phase is purely local.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
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
from fps_tpu.obs.timing import host_span, watch_device
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
        With a data axis, pushes gather across it so the replicated
        accumulators fold every worker's contributions exactly once.
        Returns ``(A, b, metrics)``: per step ``n`` and ``loss`` summed
        over all workers, ``(T,)`` each, under the two tables as they came
        in (``solve_table`` is read, never written, by this program).
        """
        cfg = self.cfg
        k = cfg.rank

        def device_fn(fixed_table, solve_table, A, b, chunk):
            ops.log_route("als", "accumulate", A.shape[0], k * k,
                          chunk["weight"].shape[1] * self.num_workers, solve)

            def body(carry, xs):
                A, b = carry
                solve_ids = xs["solve_ids"].astype(jnp.int32)
                fixed_ids = xs["fixed_ids"].astype(jnp.int32)
                r = xs["rating"].astype(cfg.dtype)
                w = xs["weight"].astype(cfg.dtype)

                with jax.named_scope("fps.pull"):
                    y = pull(fixed_table, fixed_ids,
                             num_shards=self.num_shards)
                    x = pull(solve_table, solve_ids,
                             num_shards=self.num_shards)
                with jax.named_scope("fps.compute"):
                    c = 1.0 + cfg.alpha * r  # confidence
                    cr = cfg.alpha * r * w   # confidence minus 1, masked
                    outer = (cr[:, None, None] * y[:, :, None]
                             * y[:, None, :])
                    vec = (c * w)[:, None] * y
                    miss = 1.0 - jnp.sum(x * y, axis=-1)
                    out = {"n": jnp.sum(w.astype(jnp.float32)),
                           "loss": jnp.sum((w * c * miss * miss)
                                           .astype(jnp.float32))}
                    ids = jnp.where(w > 0, solve_ids, -1)
                data_axis = DATA_AXIS if self.num_data > 1 else None
                with jax.named_scope("fps.push"):
                    A = push(A, ids, outer.reshape(-1, k * k),
                             num_shards=self.num_shards, data_axis=data_axis)
                    b = push(b, ids, vec,
                             num_shards=self.num_shards, data_axis=data_axis)
                with jax.named_scope("fps.metrics"):
                    out = jax.tree.map(
                        lambda v: lax.psum(lax.psum(v, SHARD_AXIS),
                                           DATA_AXIS), out)
                return (A, b), out

            (A, b), metrics = lax.scan(body, (A, b), chunk)
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
                    self._compiled_gram[fixed_name] = self._gram_fn(
                        fixed_n, fixed_rps)
                gram = self._compiled_gram[fixed_name](fixed)

            A = self._zeros_acc(solve_rps * self.num_shards, k * k)
            b = self._zeros_acc(solve_rps * self.num_shards, k)
            acc = self._compiled_acc.get(solve)
            if acc is None:
                acc = self._compiled_acc[solve] = self._accumulate_fn(solve)

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
                    self._compiled_solve[solve_name] = self._solve_fn(
                        solve_n, solve_rps)
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
