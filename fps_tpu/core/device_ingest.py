"""Device-resident ingest: the zero-host-traffic fast path for epoch training.

The host ingest iterator (:mod:`fps_tpu.core.ingest`) regenerates and
re-uploads every chunk — the right shape for genuinely unbounded streams
(the reference's ``DataStream`` model), but wasteful for multi-epoch
benchmark training: on a TPU VM the host→device link is orders of magnitude
slower than HBM, and shuffling 20M ratings in numpy costs seconds per epoch.

Here the columnar dataset is uploaded **once** and batches are built by
on-device gathers:

* **routing** — the reference partitions the stream so worker-local state
  stays local (e.g. MF keyed by user; SURVEY.md §3.3). The per-worker
  queues (example indices with ``route_key % num_workers == w``) are
  computed on host *once* at construction and uploaded as a padded
  ``(num_workers, max_queue)`` matrix. Without a route key the matrix
  is a closed form (worker ``w`` owns rows ``w, w + W, ...``:
  :func:`unkeyed_queue_rows`) and the step computes its rows: the
  compiled call is handed no queue. Where the columns do not pack,
  :meth:`DeviceEpochPlan.local_batch_at` says in the route log
  (``fps_tpu.ops.routes_traced``) where a step's rows come from:
  ``ingest.rows_sliced``, ``ingest.rows_computed`` or, for a keyed plan,
  ``ingest.rows_queued``;
* **shuffle** — per epoch, each worker's queue is traversed under a
  permutation of ``[0, count)``: ``shuffle="sort"`` draws a true uniform
  permutation (on-device argsort of random keys), ``shuffle="interleave"``
  (default) walks a per-epoch randomized block transpose — view positions
  as an ``(r, c)`` grid and emit transposed with a cyclic offset,
  ``pos -> ((pos % r) * c + pos // r + off) mod r*c`` — an exact bijection
  in pure int32 arithmetic (no sort, no host traffic; consecutive batch
  entries sit ``c`` apart in stream order, a fresh stride every epoch).
  The reference itself never shuffles (it trains in stream arrival
  order), so any epoch permutation is already an upgrade; ``shuffle=None``
  preserves stream order exactly like the reference;
* **padding** — workers with short queues (skewed routing) read zero-weight
  padding rows, identical semantics to the host path;
* **the transposed epoch** — under ``interleave`` and in stream order the
  bijection is "roll, view as a grid, transpose", so a once-a-call
  regular relayout (``ingest.tbuf``) puts a step's rows side by side and
  the step reads ONE contiguous slice where it would gather
  ``local_batch`` rows. Two forms: the packed rows of a data set whose
  columns are all 1-D (bit-packed into one int32 matrix, keyed or not),
  and, for an unkeyed plan over columns that do not pack (one of them
  2-D), a buffer a column in its own dtype and tail
  (``DeviceEpochPlan.sliced``), where :func:`columns_take_slices` says
  from the columns' shapes that the copies pay and fit. Everything else
  (``shuffle="sort"``, a keyed plan over 2-D columns, columns 64 slots
  wide) gathers row by row.

Two consumption styles, one geometry (:class:`DeviceEpochPlan`):

* :func:`device_epoch_chunks` materializes ``(T, B)`` chunks on device for
  the generic chunked driver (``Trainer.fit_stream``);
* ``Trainer.run_indexed`` traces :meth:`DeviceEpochPlan.local_batch_at`
  *inside* its compiled scan, fusing ingest into the training program —
  one dispatch per epoch, zero per-epoch host↔device traffic.

Names in a trace: the per-step batch gather runs under the driver's
``fps.ingest`` scope; the device programs here that run once a call or
once a chunk carry names WITHOUT the ``fps.`` prefix (``ingest.pack``,
``ingest.tbuf``, ``ingest.perm``, ``ingest.chunk``), because a reader may
count steps by the ops under ``fps.*`` (docs/observability.md).

All grid geometry is baked into the trace as constants: integer div/mod by
*traced* divisors makes XLA:TPU compiles pathologically slow (40s+ observed
for this very function), and the grid row count is a power of two so the
remaining div/mod lower to shifts/masks.
"""

from __future__ import annotations

from typing import Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from fps_tpu import ops
from fps_tpu.obs.timing import (device_bytes, host_span, settle,
                                watch_program)
from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS, host_to_replicated

Array = jax.Array

WORKER_AXES = (DATA_AXIS, SHARD_AXIS)

# Cap on interleave grid rows: consecutive emitted examples sit ~count/r
# apart in stream order, and r*c must stay int32-safe.
_GRID_ROWS_MAX = 1 << 12


# Which unkeyed data sets read a step as SLICES of a once-a-call transposed
# copy of each column (``DeviceEpochPlan.sliced``, PR 50) and which keep
# the row gather. Either way an epoch touches every row once, so the
# question is a row's cost by each, and whether the copies fit. All
# readings: ``tools/bench_ingest.py`` on one v5e chip, a step of 16,384
# rows, 4-byte columns (builder's chip runs: PR 46, call 138; PR 50,
# call 1).
#
# Slots a row from which the copy stops paying. The chip keeps a narrower
# column column-major (rows minor: ``[N, 39]`` is ``{0,1:T(8,128)}``) and
# the row gather reads a row one strided word at a time: 12.0 ns a row of
# 1 slot (a 1-D column), 20.4 of 13, 34.4 of 26, 43.7 of 39, where the
# copy moves it for 0.7 / 1.2 / 2.4 / 3.0 ns and the slice reads it for
# 0.2 - 0.5. At 64 slots a 128-lane tile row no more than doubles the
# column and the step program re-tiles it row-major once a call (12 ms
# for ``[9652968, 64]``): the gather then reads a row for 8.1 ns, the
# copy moves it for 10.5 (+1.4 % at best on ``pa-rcv1.epochs``). Nothing
# between 39 and 64 was measured.
_SLICED_SLOTS_MAX = 64          # exclusive
# Copies alive at once: a caller that keeps a call queued behind the one
# running (``run_indexed(..., as_numpy=False)`` in a loop) holds both
# calls' copies: ``lr-criteo.epochs`` peaks at 8.33 GB = 2.78 resident +
# 2 x 2.76 held a queued call + 0.03 (``device.*_gb``, builder's traced
# chip run, PR 53): two, never three, two deep. The third is headroom
# for the copy a deeper queue's ``epoch_args`` makes before one is freed.
_SLICED_CALLS_ALIVE = 3
# Share of the device's memory limit that the resident columns and those
# copies may take. The rest is the tables and the programs' temporaries,
# which ``memory_stats`` never shows and ``program.memory`` states (PR
# 53): the builder's (one more copy of the widest column: 1.34 GB on
# ``lr-criteo``), the step program's (PA's two ``[9652968, 64]`` columns
# re-tiled, 4.94 GB each and BOTH AT ONCE: 9.89 GB beside 5.02 resident).
# ``lr-criteo``'s columns: 2.72 + 3 x 2.76 = 11.0 of 16.9 GB, 65 %, in;
# ``pa-rcv1``'s: 4.98 + 3 x 4.99 = 19.9 GB, 118 %, out (two alive: 88 %).
_SLICED_HBM_SHARE = 0.75


def _tiled_bytes(rows: int, tail, itemsize: int) -> int:
    """Bytes of a ``(rows, *tail)`` array as the chip tiles a column it
    keeps column-major: the rows in lanes of 128, the slots in sublanes
    of 8 words (a 1-D array: tiles of 1,024)."""
    if not tail:
        return -(-rows // 1024) * 1024 * itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    slots = -(-int(np.prod(tail)) // sublanes) * sublanes
    return slots * -(-rows // 128) * 128 * itemsize


def _slices_pay(columns) -> bool:
    """Every column narrow enough that the copy beats the row gather."""
    return all(int(np.prod(c.shape[1:])) < _SLICED_SLOTS_MAX
               for c in columns.values())


def _slices_fit(columns, num_workers: int, buffer_rows: int,
                hbm_bytes: int | None) -> bool:
    """The resident columns and the copies alive at once within the share
    of ``hbm_bytes`` (``None``: the backend reports no limit; they fit)."""
    if hbm_bytes is None:
        return True

    def tiled(rows=None):
        return sum(_tiled_bytes(rows or c.shape[0], c.shape[1:],
                                np.dtype(c.dtype).itemsize)
                   for c in columns.values())

    return (tiled() + _SLICED_CALLS_ALIVE * tiled(num_workers * buffer_rows)
            <= _SLICED_HBM_SHARE * hbm_bytes)


def columns_take_slices(columns, num_workers: int, buffer_rows: int,
                        hbm_bytes: int | None) -> bool:
    """Should an unkeyed plan over ``columns`` (name -> anything with a
    ``shape`` and a ``dtype``) read its steps as slices of transposed
    copies of ``buffer_rows`` rows a worker? From the shapes alone: the
    copies pay and, under ``hbm_bytes`` (the device's memory limit), fit."""
    return _slices_pay(columns) and _slices_fit(
        columns, num_workers, buffer_rows, hbm_bytes)


def _hbm_bytes(mesh) -> int | None:
    """The smallest memory limit of the mesh's local devices, where the
    backend reports one (the CPU's does not)."""
    stats = device_bytes(mesh)
    return None if stats is None else stats.limit


def unkeyed_queue_rows(w, qpos, count, num_workers: int):
    """Entries ``[w, qpos]`` of the queue matrix an unkeyed data set has
    (:meth:`DeviceDataset.queues` with ``route_key=None``), padding
    included: worker ``w`` owns rows ``w, w + W, ...`` in stream order,
    and zeros stand behind its ``count``. ``qpos`` lies in ``[0, maxq)``.
    """
    return jnp.where(qpos < count, w + num_workers * qpos, 0)


class DeviceDataset:
    """A columnar dataset resident on every device of the mesh.

    Columns are equal-length arrays, replicated across the mesh (``P()``)
    so any worker can gather any row. Per-(route_key, num_workers) queue
    partitions are computed once on host and cached on device.
    """

    def __init__(self, mesh, data: Mapping[str, np.ndarray]):
        self.mesh = mesh
        lengths = {k: len(v) for k, v in data.items()}
        if len(set(lengths.values())) != 1:
            raise ValueError(f"column lengths differ: {lengths}")
        self.n = next(iter(lengths.values()))
        self._host_data = {k: np.asarray(v) for k, v in data.items()}
        with host_span("dataset.place", memory=True):
            self.columns = settle({
                k: host_to_replicated(v, mesh)
                for k, v in self._host_data.items()
            })
        self._queues: dict[tuple[str | None, int], tuple[Array, np.ndarray]] = {}

    def queues(self, route_key: str | None, num_workers: int):
        """(device queue matrix, host per-worker counts).

        The queue matrix is ``(num_workers, max_queue)`` int32 — worker
        ``w``'s first ``counts[w]`` entries are the example indices it owns,
        in stream order; the rest is padding (clamped reads, weight 0).
        With ``route_key=None`` its content is :func:`unkeyed_queue_rows`,
        which a step computes in place of reading it
        (``ingest.rows_computed``); set-up still builds it, for
        :meth:`packed` and the counts. A keyed plan's steps read the
        matrix (``ingest.rows_queued``).
        """
        ck = (route_key, num_workers)
        if ck not in self._queues:
            self._queues[ck] = self._build_queues(route_key, num_workers)
        return self._queues[ck]

    @host_span("dataset.queues", memory=True)
    def _build_queues(self, route_key: str | None, num_workers: int):
        """The host sort into per-worker queues, and its upload."""
        if route_key is None:
            counts = np.full(num_workers, self.n // num_workers, np.int64)
            counts[: self.n % num_workers] += 1
            maxq = max(int(counts.max()), 1)
            q = np.zeros((num_workers, maxq), np.int32)
            for w in range(num_workers):
                q[w, : counts[w]] = np.arange(w, self.n, num_workers)
        else:
            keys = self._host_data[route_key].astype(np.int64) % num_workers
            order = np.argsort(keys, kind="stable").astype(np.int32)
            counts = np.bincount(keys, minlength=num_workers)
            maxq = max(int(counts.max()), 1)
            q = np.zeros((num_workers, maxq), np.int32)
            start = 0
            for w in range(num_workers):
                q[w, : counts[w]] = order[start : start + counts[w]]
                start += counts[w]
        return settle(host_to_replicated(q, self.mesh)), counts.astype(
            np.int64)

    def packed(self, route_key: str | None, num_workers: int):
        """Queue-ordered packed row matrix, or ``None`` when not packable.

        When every column is 1-D with a 4-byte dtype, batch building can be
        ONE gather instead of one-per-column-plus-queue-indirection: rows
        are pre-gathered in queue order and bit-packed channel-wise into a
        ``(num_workers * max_queue, C)`` int32 matrix (built on device from
        the resident columns — no host traffic). Returns
        ``(matrix, names, dtypes)`` for :meth:`DeviceEpochPlan` to unpack.
        """
        ck = (route_key, num_workers)
        cache = getattr(self, "_packed", None)
        if cache is None:
            cache = self._packed = {}
        if ck not in cache:
            items = list(self.columns.items())
            _, host_counts = self.queues(route_key, num_workers)
            # Skewed routing pads every queue to the longest one; cap the
            # HBM blowup of the packed matrix at ~2x the raw columns.
            blowup = num_workers * int(host_counts.max()) / max(self.n, 1)
            if blowup <= 2.0 and all(
                v.ndim == 1 and v.dtype.itemsize == 4 for _, v in items
            ):
                queues, _ = self.queues(route_key, num_workers)
                names = [k for k, _ in items]
                dtypes = [v.dtype for _, v in items]

                @jax.named_scope("ingest.pack")
                def build(queues, columns):
                    flat = queues.reshape(-1)
                    chans = [
                        jax.lax.bitcast_convert_type(
                            jnp.take(columns[k], flat), jnp.int32
                        )
                        for k in names
                    ]
                    return jnp.stack(chans, axis=-1)

                with host_span("dataset.pack", memory=True):
                    arr = settle(jax.jit(
                        build,
                        out_shardings=NamedSharding(self.mesh, P()),
                    )(queues, self.columns))
                cache[ck] = (arr, names, dtypes)
            else:
                cache[ck] = None
        return cache[ck]

    def column_names(self):
        return list(self.columns)

    def host_column(self, name: str) -> np.ndarray:
        """The host copy of a column (what the constructor was given)."""
        return self._host_data[name]


class DeviceEpochPlan:
    """Epoch traversal geometry over a :class:`DeviceDataset`.

    Owns the per-worker queues, the shuffle parameters, and the pure traced
    function :meth:`local_batch_at` that conjures worker ``w``'s step-``t``
    batch from the resident columns. Consumed either step-at-a-time inside
    the driver's compiled loop (``Trainer.run_indexed`` — ingest fused into
    the jit, one dispatch per epoch) or materialized chunkwise by
    :func:`device_epoch_chunks`.

    Coverage contract (all shuffle modes): every example exactly once per
    epoch; positions past a worker's queue produce weight-0 padding rows.
    """

    @host_span("plan.build", memory=True)
    def __init__(self, dataset: DeviceDataset, *, num_workers: int,
                 local_batch: int, route_key: str | None = None,
                 shuffle: str | None = "interleave", seed: int = 0,
                 sync_every: int | None = None, pack: bool = True):
        if shuffle not in (None, "interleave", "sort"):
            raise ValueError(f"unknown shuffle mode {shuffle!r}")
        self.dataset = dataset
        self.num_workers = num_workers
        self.local_batch = local_batch
        self.route_key = route_key
        self.shuffle = shuffle
        self.seed = seed
        self.sync_every = sync_every
        self.pack = pack

        queues, host_counts = dataset.queues(route_key, num_workers)
        self._queues = queues
        self._host_counts = host_counts
        self.maxq = queues.shape[1]
        max_count = int(host_counts.max())
        self._mesh = dataset.mesh
        # ~sqrt(count) rows, power of two (shift/mask div), capped.
        self.grid_r = 1 << max(0, min(_GRID_ROWS_MAX.bit_length() - 1,
                                      int(max(max_count, 1)).bit_length() // 2))
        self.grid_c = np.maximum(
            -(-host_counts // self.grid_r), 1
        ).astype(np.int32)
        self.grid_m = (self.grid_r * self.grid_c).astype(np.int32)
        self.counts = host_counts.astype(np.int32)

        # Each worker scans [0, r*ceil(count/r)) — at most count + grid_r.
        scan_len = max_count + (self.grid_r if shuffle == "interleave" else 0)
        steps = max(1, -(-scan_len // local_batch))
        if sync_every:
            steps = -(-steps // sync_every) * sync_every
        self.steps_per_epoch = steps

        # Transposed-epoch fast path: for interleave (and stream-order) the
        # per-step batch gather is replaced by a once-per-epoch REGULAR
        # relayout. The bijection qpos = (pos%r)*c + pos//r + off (mod m) is
        # exactly "roll rows by -off, view as (r, c), transpose": batches
        # then read CONTIGUOUS slices of the transposed buffer. The per-step
        # random gather of B rows is per-row-transaction bound on TPU
        # (~11ns/row measured on a 20M-row matrix = ~360us/step at B=32k);
        # the transpose is bandwidth bound (~1ms/epoch for 240MB) and the
        # contiguous dynamic_slice is ~free.
        #
        # Two forms of the buffer: the PACKED rows of a data set whose
        # columns are all 1-D (one int32 matrix, any plan), and, for an
        # unkeyed plan over columns that do not pack, one buffer a COLUMN
        # in its own dtype and tail, where :func:`columns_take_slices`
        # says the copies pay and fit (``sliced``).
        self._tbuf_jit = None
        self.sliced = False
        if pack and shuffle in (None, "interleave"):
            packed = dataset.packed(route_key, num_workers)
            if packed is not None:
                self._tbuf_jit = self._make_tbuf_jit()
            elif route_key is None and columns_take_slices(
                    dataset.columns, num_workers, steps * local_batch,
                    _hbm_bytes(dataset.mesh)):
                self.sliced = True
                self._tbuf_jit = self._make_column_tbuf_jit()

        if shuffle == "sort":
            maxq, counts, W = self.maxq, jnp.asarray(self.counts), num_workers
            # Key-data shape of the active prng impl (eval_shape: traced,
            # never executed — no device work at plan init either).
            self._key_data_shape = jax.eval_shape(
                lambda: jax.random.key_data(jax.random.key(0))
            ).shape

            @jax.named_scope("ingest.perm")
            def mk_perm(key_data):
                key = jax.random.wrap_key_data(key_data)
                keys = jax.random.split(key, W)
                u = jax.vmap(lambda k: jax.random.uniform(k, (maxq,)))(keys)
                u = jnp.where(jnp.arange(maxq)[None, :] < counts[:, None],
                              u, jnp.inf)
                return jnp.argsort(u, axis=1).astype(jnp.int32)

            # jitted ONCE per plan — a fresh jit per epoch would recompile
            # the (W, maxq) argsort program every epoch. Takes raw key data
            # (a plain numpy array, implicitly replicated) so the path works
            # under multi-controller JAX too.
            self._perm_jit = watch_program(jax.jit(
                mk_perm,
                out_shardings=NamedSharding(dataset.mesh, P()),
            ), "ingest.perm")

    def _transposed_rows(self, rows, off_w, w: int):
        """Worker ``w``'s queue-ordered ``rows`` (``(any, *tail)``, zeros
        behind its count) in STEP order, ``(steps * B, *tail)``: padded or
        cut to the grid's ``m_w``, rolled by the epoch's offset, viewed as
        ``(r, c_w)`` and transposed (stream order: as they are), then
        padded or cut to the epoch's steps. Regular ops only (slice, roll,
        reshape, transpose, pad): no gathers."""
        r, c_w, m_w = self.grid_r, int(self.grid_c[w]), int(self.grid_m[w])
        out_rows = self.steps_per_epoch * self.local_batch
        tail = rows.shape[1:]

        def padded(x, n):
            if x.shape[0] >= n:
                return x[:n]
            return jnp.concatenate(
                [x, jnp.zeros((n - x.shape[0],) + tail, x.dtype)])

        tb = padded(rows, m_w)
        if self.shuffle == "interleave":
            rolled = jnp.roll(tb, -off_w[w], axis=0)
            tb = jnp.swapaxes(
                rolled.reshape((r, c_w) + tail), 0, 1).reshape((m_w,) + tail)
        return padded(tb, out_rows)

    def _make_tbuf_jit(self):
        """Jitted per-epoch builder of the transposed row buffer.

        ``(packed rows, per-worker offsets) -> (W, steps*B, C)`` where entry
        ``[w, pos]`` holds worker ``w``'s step-order example at position
        ``pos`` — i.e. ``packed[w*maxq + (bij(pos) + off_w) mod m_w]`` — so
        :meth:`local_batch_at` reads plain contiguous slices
        (:meth:`_transposed_rows`).
        """
        W, maxq = self.num_workers, self.maxq

        @jax.named_scope("ingest.tbuf")
        def build(packed_mat, off_w):
            return jnp.stack([
                self._transposed_rows(
                    packed_mat[w * maxq : (w + 1) * maxq], off_w, w)
                for w in range(W)])

        return watch_program(jax.jit(
            build, out_shardings=NamedSharding(self._mesh, P())
        ), "ingest.tbuf")

    def _make_column_tbuf_jit(self):
        """Jitted per-epoch builder of the transposed buffers of an unkeyed
        plan's own columns: ``(columns, per-worker offsets) -> {name:
        (W * steps*B, *tail)}``, each in its column's dtype, nothing
        bit-packed. Entry ``[w * steps*B + pos]`` is the row worker ``w``
        reads at position ``pos``, or zeros where the position holds
        none. A worker's queue is the column's rows ``w, w + W, ...``
        (:func:`unkeyed_queue_rows`): on one worker the column itself, so
        there is no packed copy and no queue. The workers' segments lie
        end to end along the ROWS, not along a leading axis: the buffer
        is then an array of the column's own rank, which the TPU lays
        out as it does the column (a leading ``[1, ...]`` axis gives it
        another tiling, and the builder one more pass of temporaries the
        size of the column: compiled for a described v5e, PR 50).
        """
        W = self.num_workers

        @jax.named_scope("ingest.tbuf")
        def build(columns, off_w):
            return {
                k: jnp.concatenate([
                    self._transposed_rows(col[w::W], off_w, w)
                    for w in range(W)])
                for k, col in columns.items()
            }

        return watch_program(jax.jit(
            build, out_shardings=NamedSharding(self._mesh, P())
        ), "ingest.tbuf")

    def calls_per_epoch(self, steps_per_call: int) -> int:
        """Compiled calls covering one epoch at ``steps_per_call`` steps
        each (the final call's trailing steps are weight-0 padding).
        One definition shared by the per-chunk driver
        (``Trainer.run_indexed``) and the K-chunk megastep
        (``fps_tpu.core.megastep``), so their chunk grids — and with
        them the per-(epoch, chunk) PRNG derivation — cannot drift."""
        if steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}")
        return -(-self.steps_per_epoch // steps_per_call)

    def _epoch_rng(self, tag: int, epoch: int) -> np.random.Generator:
        """Deterministic host rng for (tag, seed, epoch) — accepts negative
        seeds (SeedSequence rejects negative entropy, so mask to 64 bits)."""
        return np.random.default_rng(
            (tag, self.seed & ((1 << 64) - 1), epoch)
        )

    @host_span("epoch_args", memory=True)
    def epoch_args(self, epoch: int):
        """Device operands for one epoch (replicated pytree)."""
        mesh = self.dataset.mesh
        off_w = np.zeros(self.num_workers, np.int32)
        perm = None
        if self.shuffle == "interleave":
            # Host-side draw: deterministic in (seed, epoch) and identical
            # on every controller. A jax.random draw here would cost a
            # device dispatch PLUS a blocking int() transfer per epoch,
            # serialized between epochs for a one-integer result (cost on
            # the v5e: not measured).
            off = int(self._epoch_rng(0x0FF5E7, epoch).integers(
                0, max(int(self._host_counts.max()), 1)
            ))
            off_w = (off % self.grid_m.astype(np.int64)).astype(np.int32)
        elif self.shuffle == "sort":
            # Same host-side-determinism reasoning: raw key data built in
            # numpy, sized for the ACTIVE prng impl (threefry (2,),
            # rbg/unsafe_rbg (4,) — probed via eval_shape at plan init, no
            # device round trip anywhere on this path).
            kd = self._epoch_rng(0x5037, epoch).integers(
                0, 1 << 32, self._key_data_shape, dtype=np.uint32
            )
            perm = self._perm_jit(kd)
        if perm is None:
            perm = host_to_replicated(np.zeros((1, 1), np.int32), mesh)
        packed = (self.dataset.packed(self.route_key, self.num_workers)
                  if self.pack else None)
        args = {
            "columns": self.dataset.columns,
            "off_w": host_to_replicated(off_w, mesh),
            "perm": perm,
        }
        if self.route_key is not None:
            # An unkeyed plan's steps compute their rows (local_batch_at):
            # no compiled call has a parameter of the queue's shape.
            args["queues"] = self._queues
        if self.sliced:
            args["tbuf"] = self._tbuf_jit(self.dataset.columns, off_w)
        elif self._tbuf_jit is not None:
            args["tbuf"] = self._tbuf_jit(packed[0], off_w)
        elif packed is not None:
            args["packed"] = packed[0]
        return args

    # -- traced: called inside jit (driver scan or chunk builder) ----------

    def local_batch_at(self, args, w, t):
        """Worker ``w``'s step-``t`` batch: dict of ``(local_batch,)`` leaves
        plus the ``weight`` mask. Pure/traceable; ``w`` and ``t`` are traced
        int32 scalars."""
        pos = t * self.local_batch + jnp.arange(self.local_batch,
                                                dtype=jnp.int32)
        cnt = jnp.asarray(self.counts)[w]
        if self.shuffle == "interleave":
            c = jnp.asarray(self.grid_c)[w]
            m = jnp.asarray(self.grid_m)[w]
            x = (pos % self.grid_r) * c + pos // self.grid_r  # bijection on [0, m)
            qpos = x + args["off_w"][w]
            qpos = jnp.where(qpos >= m, qpos - m, qpos)
            valid = (pos < m) & (qpos < cnt)
        elif self.shuffle == "sort":
            qpos = jnp.take(args["perm"].reshape(-1),
                            w * self.maxq + jnp.clip(pos, 0, self.maxq - 1))
            valid = pos < cnt
        else:
            qpos = pos
            valid = pos < cnt
        if self.sliced:
            # The columns' own transposed buffers: ONE contiguous slice a
            # column, where the unpacked branch below gathers row by row.
            # The buffers encode the bijection and the offset; ``valid``
            # comes from the same (qpos, cnt) math above.
            ops.log_route("ingest", "rows_sliced", int(self.counts.sum()),
                          len(args["tbuf"]), self.local_batch)
            start = (w * self.steps_per_epoch + t) * self.local_batch
            # Materialised here, as the gathered batch is: left alone XLA
            # fuses each slice into its consumers and the read leaves
            # ``fps.ingest`` for their scopes. Free end to end
            # (``lr-criteo.epochs`` 2,249,597 examples/s with, 2,248,931
            # without, one seed: builder's chip run, PR 50).
            batch = jax.lax.optimization_barrier({
                k: jax.lax.dynamic_slice_in_dim(buf, start, self.local_batch)
                for k, buf in args["tbuf"].items()
            })
            batch["weight"] = valid.astype(jnp.float32)
            return batch
        if "tbuf" in args:
            # Transposed fast path: batch = one contiguous slice. The buffer
            # already encodes the shuffle bijection + offset; ``valid`` was
            # computed from the same (qpos, cnt) math above.
            _, names, dtypes = self.dataset.packed(
                self.route_key, self.num_workers
            )
            C = len(names)
            rows = jax.lax.dynamic_slice(
                args["tbuf"],
                (w, t * self.local_batch, 0),
                (1, self.local_batch, C),
            ).reshape(self.local_batch, C)
            batch = {
                k: jax.lax.bitcast_convert_type(rows[:, i], dt)
                for i, (k, dt) in enumerate(zip(names, dtypes))
            }
            batch["weight"] = valid.astype(jnp.float32)
            return batch
        qc = jnp.clip(qpos, 0, self.maxq - 1)
        slot = w * self.maxq + qc
        if "packed" in args:
            # One gather of queue-ordered packed rows, then per-channel
            # bitcasts — replaces the queue indirection + one gather per
            # column (measured ~3x faster batch construction).
            _, names, dtypes = self.dataset.packed(
                self.route_key, self.num_workers
            )
            rows = jnp.take(args["packed"], slot, axis=0)  # (B, C) int32
            batch = {
                k: jax.lax.bitcast_convert_type(rows[:, i], dt)
                for i, (k, dt) in enumerate(zip(names, dtypes))
            }
        else:
            cols = args["columns"]
            # An unkeyed plan's queue matrix is a closed form: nothing is
            # read to learn it, and epoch_args hands the call no queue.
            computed = self.route_key is None
            ops.log_route(
                "ingest", "rows_computed" if computed else "rows_queued",
                len(next(iter(cols.values()))), len(cols), self.local_batch)
            row = (unkeyed_queue_rows(w, qc, cnt, self.num_workers)
                   if computed
                   else jnp.take(args["queues"].reshape(-1), slot))
            batch = {k: jnp.take(col, row, axis=0)
                     for k, col in cols.items()}
        batch["weight"] = valid.astype(jnp.float32)
        return batch

    def _chunk_builder(self, steps_per_chunk: int):
        """Jitted (epoch_args, start_step) -> (T, B) chunk, cached per plan."""
        cache = getattr(self, "_builders", None)
        if cache is None:
            cache = self._builders = {}
        if steps_per_chunk not in cache:
            out_sharding = NamedSharding(
                self.dataset.mesh,
                P(None, None, WORKER_AXES) if self.sync_every
                else P(None, WORKER_AXES),
            )
            W, B, s = self.num_workers, self.local_batch, self.sync_every

            @jax.named_scope("ingest.chunk")
            def build(args, start_step):
                ts = start_step + jnp.arange(steps_per_chunk, dtype=jnp.int32)
                ws = jnp.arange(W, dtype=jnp.int32)
                chunk = jax.vmap(
                    lambda t: jax.vmap(
                        lambda w: self.local_batch_at(args, w, t)
                    )(ws)
                )(ts)  # leaves: (T, W, B, ...)
                chunk = {
                    k: v.reshape((steps_per_chunk, W * B) + v.shape[3:])
                    for k, v in chunk.items()
                }
                if s:
                    chunk = {
                        k: v.reshape((steps_per_chunk // s, s) + v.shape[1:])
                        for k, v in chunk.items()
                    }
                return chunk

            cache[steps_per_chunk] = jax.jit(
                build,
                out_shardings={
                    k: out_sharding
                    for k in list(self.dataset.columns) + ["weight"]
                },
            )
        return cache[steps_per_chunk]


_UNSET = object()  # distinguishes omitted kwargs from explicit defaults


def device_epoch_chunks(
    dataset: DeviceDataset,
    *,
    num_workers: int,
    local_batch: int,
    steps_per_chunk: int,
    route_key=_UNSET,
    sync_every=_UNSET,
    seed=_UNSET,
    epochs: int = 1,
    start_epoch: int = 0,
    shuffle=_UNSET,
    plan: DeviceEpochPlan | None = None,
) -> Iterator[dict]:
    """Yield device-resident chunks for ``epochs`` passes over the data.

    Chunk contract matches :func:`fps_tpu.core.ingest.epoch_chunks`: leaves
    shaped ``(T, B)`` (or ``(R, s, B)`` when ``sync_every`` is set) with a
    ``weight`` mask column, batch dim worker-major and sharded over the
    worker axes — but every leaf is already a committed jax array on the
    mesh, so the driver moves no bytes. Pass an existing ``plan`` to reuse
    its compiled chunk builder across calls, with ``start_epoch`` selecting
    which epoch's shuffle the pass replays (epoch identity is a host-side
    deterministic draw keyed on ``(plan.seed, epoch)`` —
    ``DeviceEpochPlan._epoch_rng`` — so restarts are reproducible).
    """
    if plan is None:
        plan = DeviceEpochPlan(
            dataset, num_workers=num_workers, local_batch=local_batch,
            route_key=None if route_key is _UNSET else route_key,
            shuffle="interleave" if shuffle is _UNSET else shuffle,
            seed=0 if seed is _UNSET else seed,
            sync_every=None if sync_every is _UNSET else sync_every,
        )
    else:
        # An explicit plan carries its own geometry; silently ignoring
        # disagreeing kwargs would hand the caller the plan's geometry with
        # no warning (mirrors run_indexed's sync_every consistency check).
        # Only kwargs the caller actually passed are compared (_UNSET marks
        # omissions), and sync_every is truthiness-normalized like the
        # driver does (0 and None both mean fully synchronous).
        mismatches = {
            k: (got, want)
            for k, got, want in (
                ("num_workers", num_workers, plan.num_workers),
                ("local_batch", local_batch, plan.local_batch),
                ("route_key", route_key, plan.route_key),
                ("shuffle", shuffle, plan.shuffle),
                ("seed", seed, plan.seed),
                (
                    "sync_every",
                    _UNSET if sync_every is _UNSET else (sync_every or None),
                    plan.sync_every or None,
                ),
            )
            if got is not _UNSET and got != want
        }
        if mismatches:
            raise ValueError(
                "explicit plan disagrees with kwargs: "
                + ", ".join(
                    f"{k}={got!r} but plan.{k}={want!r}"
                    for k, (got, want) in mismatches.items()
                )
            )
    if plan.sync_every and steps_per_chunk % plan.sync_every:
        raise ValueError("steps_per_chunk must be a multiple of sync_every")

    def _chunks():
        build = plan._chunk_builder(steps_per_chunk)
        steps_total = (
            -(-plan.steps_per_epoch // steps_per_chunk) * steps_per_chunk
        )
        for epoch in range(start_epoch, start_epoch + epochs):
            args = plan.epoch_args(epoch)
            for start in range(0, steps_total, steps_per_chunk):
                yield build(args, np.int32(start))

    return _chunks()
