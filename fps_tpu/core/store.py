"""Sharded parameter store — the TPU-native replacement for the PS server side.

Reference semantics being rebuilt (from SURVEY.md; expected upstream paths
``src/main/scala/hu/sztaki/ilab/ps/server/SimplePSLogic.scala`` and
``.../ps/entities/``):

* the parameter space is a map ``id -> P`` hash-partitioned across
  ``psParallelism`` server instances (``hash(paramId) % psParallelism``),
* ``Pull(id)`` routes to the owning shard, which answers with the value
  (initializing it on first touch via a deterministic ``paramInit(id)``),
* ``Push(id, delta)`` routes to the owning shard, which folds the delta in
  via ``paramUpdate`` (``_ + _`` for every shipped algorithm).

TPU-native design
-----------------
A table is one jax array of shape ``(rows, dim)`` laid out **owner-major
cyclic**: parameter id ``i`` lives at physical row ``(i % S) * rps + i // S``
where ``S`` is the shard count and ``rps`` rows-per-shard. Under a
``NamedSharding(P('shard', None))`` this puts id ``i`` on device ``i % S`` —
exactly the reference's hash partitioning, and it balances Zipfian id
frequencies the way block partitioning would not.

Inside ``shard_map``:

* :func:`pull`  = ``all_gather(ids)`` → local gather of owned rows →
  ``psum_scatter`` so each worker receives exactly its requested values.
  This is the collective-gather collapse of the reference's
  pull/partitionCustom/answerPull round trip.
* :func:`push`  = ``all_gather(ids, deltas)`` (over the data axis too, so
  every replica applies every delta) → masked local ``scatter-add``.
  Duplicate ids within a batch accumulate, matching the reference's
  per-message ``paramUpdate`` fold.

Everything is static-shape and jit-compatible; XLA lowers the collectives
onto ICI when the mesh spans a pod slice.

That is the GATHERED exchange: every shard is handed every worker's ids
(and a push's rows), ``O(W * B)`` a shard. Across more than one shard, with
no data axis and a batch of at least :data:`ROUTED_MIN_IDS_PER_SHARD` ids
a shard, :func:`pull` and :func:`push` take the OWNER-ROUTED exchange
instead: a worker's ids are placed into one lane an owner shard
(:func:`_owner_lanes`, ``LANE_MARGIN * B / S`` wide), the lanes are traded
by ``all_to_all``, and a shard is handed ``LANE_MARGIN * B`` ids, all of
them its own: the reference's own routing, a push goes to the one
partition that owns the key. Whether a step's ids fit their lanes is
certified in the graph each call (one ``pmax`` over the shard axis), and a
step that does not fit runs the gathered exchange: nothing is ever
dropped. Small tables keep the DENSE exchange (``dense=True``).

Two-tier hot storage (``TableSpec.hot_tier``)
---------------------------------------------
Real id streams are Zipf-skewed (ML20M users, text8 vocab, Criteo
features), and NuPS (arxiv.org/pdf/2104.00501) shows the winning PS
design manages hot and cold keys differently: **replicate the hot head,
shard the tail**. A table with ``hot_tier = H > 0`` additionally keeps
its leading ``H`` global ids as an ``(H, dim)`` array **replicated**
across every device (stored beside the sharded table under
``hot_key(name)``), plus a per-device pending-delta buffer inside the
compiled loop:

* :func:`pull_hot` serves ``id < H`` reads from the local replica —
  **zero collectives**; cold ids ride the existing gathered/dense routes
  with the hot slots masked to ``-1`` (the documented zero-row /
  dropped-push contract).
* :func:`accumulate_hot` folds ``id < H`` pushes into the local delta
  buffer; :func:`reconcile_hot` ``psum``-reduces the buffers every
  ``TrainerConfig.hot_sync_every`` steps and applies the combined delta
  to the replica AND to the owner shard's head rows of the canonical
  sharded table — the paper's SSP bound applied to the parameter plane.

The sharded table stays the single source of truth: every compiled call
ends with a flush reconcile, so at chunk/epoch boundaries the replica is
a pure projection of the canonical table's head rows (checkpoints save
one canonical table; restore re-splits — ``Trainer._attach_hot``).
``hot_sync_every = 1`` is the exact mode: the driver lowers the
IDENTICAL untiered program (a per-step psum reconcile could not be
bit-identical to the gathered scatter's summation order — same
reasoning as the dense push path's fixed-order NOTE below — so the
exact mode is implemented as the untiered path itself, making its
zero-cost claim provable by lowered-HLO comparison).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fps_tpu import ops
from fps_tpu.parallel.mesh import DATA_AXIS, SHARD_AXIS, replicate_to_mesh

Array = jax.Array


# ---------------------------------------------------------------------------
# Physical layout helpers (owner-major cyclic).
# ---------------------------------------------------------------------------

def rows_per_shard(num_ids: int, num_shards: int) -> int:
    return -(-num_ids // num_shards)  # ceil


def padded_rows(num_ids: int, num_shards: int) -> int:
    return rows_per_shard(num_ids, num_shards) * num_shards


def id_to_phys(ids: Array, num_shards: int, rps: int) -> Array:
    """Global physical row index of each parameter id."""
    return (ids % num_shards) * rps + ids // num_shards


def phys_to_id(phys: Array, num_shards: int, rps: int) -> Array:
    """Inverse of :func:`id_to_phys` (may exceed num_ids for padding rows)."""
    return (phys % rps) * num_shards + phys // rps


# ---------------------------------------------------------------------------
# Two-tier hot storage (replicated head + sharded tail; see module docstring).
# ---------------------------------------------------------------------------

# Replica entries ride the same tables dict as the sharded tables they
# mirror, under a reserved key — checkpoint/export iterate ``store.specs``
# and therefore never serialize them (the sharded table is canonical).
HOT_KEY_SUFFIX = "::hot"
# Adaptive (mapped) tier aux entries (fps_tpu.tiering): the replica's
# membership is an arbitrary hot id SET carried as replicated DATA
# arrays — a slot map (global id -> replica slot, -1 = cold) and its
# inverse (replica slot -> global id) — so a re-rank swaps arrays
# without changing the traced program. ``::sketch`` is the device-side
# frequency window (count-min) the online tracker accumulates inside
# the compiled step. All ride the tables dict under reserved suffixes;
# like ``::hot`` they are never serialized (specs stay canonical).
MAP_KEY_SUFFIX = "::hotmap"
IDS_KEY_SUFFIX = "::hotids"
SKETCH_KEY_SUFFIX = "::sketch"
# Stateful hot-fold optimizer state (``ServerLogic.hot_fold``): per-row
# Adagrad/Adam state for the hot head, SHARDED over the shard axis in
# reduce-scatter slice order (never replicated). Persisted in snapshots
# as separate ``fold::`` arrays — never part of the canonical table
# bytes (``checkpoint._table_arrays`` iterates specs only).
FOLD_KEY_SUFFIX = "::fold"
AUX_KEY_SUFFIXES = (HOT_KEY_SUFFIX, MAP_KEY_SUFFIX, IDS_KEY_SUFFIX,
                    SKETCH_KEY_SUFFIX, FOLD_KEY_SUFFIX)


# Dense parameters of a worker logic (``api.DenseLogic``): replicated
# arrays that ride the tables dict beside the tables, as STATE of their
# own (made by ``Trainer.init_state``, carried and folded by the compiled
# call, saved as ``dense::`` arrays), not a projection of a table: not an
# aux entry, so nothing that re-derives the tiering drops them.
DENSE_KEY_SUFFIX = "::dense"


def dense_key(name: str) -> str:
    """Tables-dict key of the dense parameter ``name``."""
    return name + DENSE_KEY_SUFFIX


def split_dense(tables: Mapping[str, Any]) -> tuple[dict, dict]:
    """``(everything else, dense parameters by their own names)``."""
    rest, dense = {}, {}
    for k, v in tables.items():
        if k.endswith(DENSE_KEY_SUFFIX):
            dense[k[: -len(DENSE_KEY_SUFFIX)]] = v
        else:
            rest[k] = v
    return rest, dense


def hot_key(name: str) -> str:
    """Tables-dict key of ``name``'s replicated hot-head array."""
    return name + HOT_KEY_SUFFIX


def map_key(name: str) -> str:
    """Tables-dict key of ``name``'s replicated id->slot map."""
    return name + MAP_KEY_SUFFIX


def ids_key(name: str) -> str:
    """Tables-dict key of ``name``'s replicated slot->global-id array."""
    return name + IDS_KEY_SUFFIX


def sketch_key(name: str) -> str:
    """Tables-dict key of ``name``'s device-side frequency sketch."""
    return name + SKETCH_KEY_SUFFIX


def fold_key(name: str) -> str:
    """Tables-dict key of ``name``'s sharded hot-fold optimizer state."""
    return name + FOLD_KEY_SUFFIX


def is_hot_key(key: str) -> bool:
    return key.endswith(HOT_KEY_SUFFIX)


def is_aux_key(key: str) -> bool:
    """True for ANY reserved tiering entry (replica, maps, sketch)."""
    return any(key.endswith(s) for s in AUX_KEY_SUFFIXES)


def hot_base(key: str) -> str:
    """Inverse of :func:`hot_key`."""
    return key[: -len(HOT_KEY_SUFFIX)]


def split_tiering(
    tables: Mapping[str, Any]
) -> tuple[dict, dict, dict, dict, dict, dict]:
    """Split a tables dict into ``(canonical, hot, maps, gids, sketches,
    folds)`` — each aux dict keyed by base table name. (The old two-way
    ``split_hot`` was retired when this superseded it: a narrower split
    would misclassify the adaptive tier's aux entries as canonical
    tables.)"""
    canonical, hot, maps, gids, sketches, folds = {}, {}, {}, {}, {}, {}
    for k, v in tables.items():
        if k.endswith(HOT_KEY_SUFFIX):
            hot[k[: -len(HOT_KEY_SUFFIX)]] = v
        elif k.endswith(MAP_KEY_SUFFIX):
            maps[k[: -len(MAP_KEY_SUFFIX)]] = v
        elif k.endswith(IDS_KEY_SUFFIX):
            gids[k[: -len(IDS_KEY_SUFFIX)]] = v
        elif k.endswith(SKETCH_KEY_SUFFIX):
            sketches[k[: -len(SKETCH_KEY_SUFFIX)]] = v
        elif k.endswith(FOLD_KEY_SUFFIX):
            folds[k[: -len(FOLD_KEY_SUFFIX)]] = v
        else:
            canonical[k] = v
    return canonical, hot, maps, gids, sketches, folds


def hot_slot_map(num_ids: int, hot_gids: np.ndarray) -> np.ndarray:
    """``(num_ids + 1,)`` int32 id->slot map for an arbitrary hot id set.

    Entry ``i`` is the replica slot of global id ``i`` (``-1`` = cold);
    the trailing sentinel row stays ``-1`` so device code can index with
    ``where(ids >= 0, ids, num_ids)`` and padding ids resolve to cold
    without a second mask."""
    gids = np.asarray(hot_gids, np.int64)
    if gids.size and (gids.min() < 0 or gids.max() >= num_ids):
        raise ValueError(
            f"hot ids outside [0, {num_ids}): "
            f"[{gids.min()}, {gids.max()}]")
    if len(np.unique(gids)) != len(gids):
        raise ValueError("hot id set contains duplicates")
    m = np.full(num_ids + 1, -1, np.int32)
    m[gids] = np.arange(len(gids), dtype=np.int32)
    return m


def lookup_hot_slots(slot_map: Array, ids: Array) -> Array:
    """Device-side ``(B,)`` replica slots for ``ids`` (-1 = cold or
    padding). ``slot_map`` is :func:`hot_slot_map`'s array."""
    sentinel = slot_map.shape[0] - 1
    return jnp.take(slot_map, jnp.where(ids >= 0, ids, sentinel), axis=0)


def device_slot_map(num_ids: int, hot_gids: Array) -> Array:
    """Traced analog of :func:`hot_slot_map` for the in-graph tier tick:
    rebuild the ``(num_ids + 1,)`` id->slot map from a replicated hot
    gid array (all entries in ``[0, num_ids)`` — the tick selects from
    ``arange``, so no validation is traced). Deterministic function of
    the gid ORDER, so rebuilding for an unchanged set reproduces the
    incoming map bit-for-bit."""
    m = jnp.full((num_ids + 1,), -1, jnp.int32)
    return m.at[hot_gids].set(
        jnp.arange(hot_gids.shape[0], dtype=jnp.int32))


def replica_from_shard(local_shard: Array, hot_gids: Array, *,
                       num_shards: int,
                       shard_axis: str = SHARD_AXIS) -> Array:
    """In-graph re-split: gather arbitrary global ids' canonical rows
    into a replicated ``(H, dim)`` replica from inside ``shard_map`` —
    the traced analog of :meth:`ParamStore.rows_replica` for the
    megastep's tier tick. Each shard contributes the rows it owns under
    the owner-major cyclic layout (zero rows elsewhere); one psum makes
    the result replicated. Bit-exact: every replica row is one owned
    row plus zeros, and the boundary invariant (replica row ==
    canonical row after a reconcile) makes the re-derivation of an
    UNCHANGED hot set the identity."""
    me = lax.axis_index(shard_axis)
    owned = (hot_gids % num_shards) == me
    lidx = jnp.where(owned, hot_gids // num_shards,
                     jnp.asarray(-1, hot_gids.dtype))
    vals = ops.gather_rows(local_shard, lidx)  # -1 slots read zero rows
    return lax.psum(vals, shard_axis)


def split_hot_push_slots(
    ids: Array, deltas: Array, slots: Array
) -> tuple[tuple[Array, Array], tuple[Array, Array]]:
    """Mapped-tier analog of :func:`split_hot_push`: partition one push
    stream on ``slots >= 0`` (slot-map membership instead of ``id < H``).

    Returns ``((cold_ids, cold_deltas), (hot_slots, hot_deltas))`` with
    the other tier's entries masked to ``-1``/zero — the hot half is
    already in SLOT space, ready for :func:`accumulate_hot`."""
    hot = slots >= 0
    cold = (
        jnp.where(hot, jnp.asarray(-1, ids.dtype), ids),
        jnp.where(hot[:, None], 0, deltas).astype(deltas.dtype),
    )
    hots = (
        jnp.where(hot, slots, jnp.asarray(-1, slots.dtype)),
        jnp.where(hot[:, None], deltas, 0).astype(deltas.dtype),
    )
    return cold, hots


def reconcile_hot_mapped(
    cold_shard: Array,
    replica: Array,
    delta_buf: Array,
    hot_gids: Array,
    *,
    num_shards: int,
    shard_axis: str = SHARD_AXIS,
    data_axis: str | None = None,
    combine: str = "sum",
    fold=None,
    fold_state: Array | None = None,
) -> tuple[Array, Array, Array, Array | None]:
    """Window-end reconcile for an arbitrary hot id set (mapped tier).

    Identical contract to :func:`reconcile_hot` except the replica's slot
    ``j`` holds global id ``hot_gids[j]`` instead of id ``j``: the
    combined window delta is applied to the replica (bitwise-identical on
    every device — it comes out of the reconcile's all-gather) AND
    scattered into this shard's OWNED rows of the canonical table — under
    the owner-major cyclic layout id ``g`` lives on shard ``g % S`` at
    local row ``g // S``. ``hot_gids`` is replicated DATA, so a re-rank
    changes which rows reconcile without recompiling.

    Returns ``(new_cold_shard, new_replica, reset_delta_buf,
    new_fold_state)``.
    """
    combined, new_replica, new_state = _reconcile_combine(
        replica, delta_buf, num_shards=num_shards, shard_axis=shard_axis,
        data_axis=data_axis, combine=combine, fold=fold,
        fold_state=fold_state)
    me = lax.axis_index(shard_axis)
    owned = (hot_gids >= 0) & ((hot_gids % num_shards) == me)
    lidx = jnp.where(owned, hot_gids // num_shards,
                     jnp.asarray(-1, hot_gids.dtype))
    new_cold = ops.scatter_add(cold_shard, lidx,
                               combined.astype(cold_shard.dtype))
    return new_cold, new_replica, _reset_delta(delta_buf, combine), new_state


def pull_hot(replica: Array, ids: Array, *, hot_ids: int) -> tuple[Array, Array]:
    """Serve ``id < hot_ids`` reads from the local replica — no collectives.

    Returns ``(values, hot_mask)``: ``values`` holds the replica rows for
    hot ids and ZERO rows elsewhere (ids outside the head are gathered as
    ``-1``, the zero-row contract), so the caller can ``where`` it against
    the cold route's rows (which are zero exactly on the hot slots).
    """
    hot = (ids >= 0) & (ids < hot_ids)
    return ops.gather_rows(replica, jnp.where(hot, ids, -1)), hot


def split_hot_push(
    ids: Array, deltas: Array, *, hot_ids: int
) -> tuple[tuple[Array, Array], tuple[Array, Array]]:
    """Partition one push stream on ``id < hot_ids``.

    Returns ``((cold_ids, cold_deltas), (hot_ids_arr, hot_deltas))`` with
    the other tier's slots masked to ``-1``/zero — both the collective
    push and :func:`fps_tpu.ops.scatter_add` drop ``-1`` rows, and the
    deltas are zeroed too so the lane-packed routes never multiply a live
    indicator into a masked row's payload (same hazard the guard's mask
    path documents).
    """
    hot = (ids >= 0) & (ids < hot_ids)
    cold = (
        jnp.where(hot, jnp.asarray(-1, ids.dtype), ids),
        jnp.where(hot[:, None], 0, deltas).astype(deltas.dtype),
    )
    hots = (
        jnp.where(hot, ids, jnp.asarray(-1, ids.dtype)),
        jnp.where(hot[:, None], deltas, 0).astype(deltas.dtype),
    )
    return cold, hots


def delta_counted(combine: str, fold) -> bool:
    """Whether a table's pending-delta buffer carries the appended
    push-count column: the ``"mean"`` combine needs it to normalize, and
    every stateful fold needs it to apply lazily (touched rows only)."""
    return combine == "mean" or fold is not None


def compact_cold(
    ids: Array, deltas: Array | None, *, budget: int
) -> tuple[Array, Array | None, Array, Array]:
    """Pack a masked cold-id stream into a fixed ``budget``-wide lane.

    ``ids`` is a ``(B,)`` stream whose hot/padding slots are already
    masked to ``-1`` (the :func:`split_hot_push` / :func:`pull_hot`
    convention); the live entries are packed ORDER-PRESERVING (stable
    cumsum positions) into a ``(budget,)`` lane with ``-1`` padding, so
    the collective routes carry ``O(cold traffic)`` payload instead of
    ``O(batch)``. Live entries beyond the budget are DROPPED (their lane
    position is out of range, their pulls read zero rows) — callers must
    only dispatch the compacted program for batches the host certifier
    proved fit the budget (``Trainer._certify_cold``; the overflow count
    is returned for the device-side observability net).

    Returns ``(lane_ids, lane_deltas, pos, overflowed)``: ``pos`` maps
    each original slot to its lane position (``-1`` = masked or dropped)
    for scattering pulled lane rows back to batch positions;
    ``overflowed`` is the scalar count of dropped live entries.
    """
    live = ids >= 0
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    pos = jnp.where(live & (pos < budget), pos, -1)
    # Negative .at[] indices WRAP (numpy semantics) — map masked slots to
    # ``budget`` so mode="drop" actually drops them (ops.scatter_add's
    # xla step).
    safe = jnp.where(pos >= 0, pos, budget)
    lane_ids = jnp.full((budget,), -1, ids.dtype).at[safe].set(
        ids, mode="drop")
    lane_deltas = None
    if deltas is not None:
        lane_deltas = jnp.zeros(
            (budget,) + deltas.shape[1:], deltas.dtype
        ).at[safe].set(deltas, mode="drop")
    overflowed = jnp.maximum(
        jnp.sum(live.astype(jnp.int32)) - budget, 0)
    return lane_ids, lane_deltas, pos, overflowed


def hot_delta_init(hot_rows: int, dim: int, dtype, *, combine: str = "sum",
                   fold=None) -> Array:
    """Fresh per-device pending-delta buffer for one tiered table.

    Accumulates in at least f32 (never below the table's own precision —
    same promotion rule as the non-"sum" combine folds in :func:`push`).
    The ``mean`` combine (and any stateful fold) carries a push-count
    column appended to the payload so the reconcile can normalize / fold
    lazily per touched row per window. The ``max``/``min`` combines keep
    an elementwise-extremum buffer instead: filled with the extremal
    sentinel, plus a touched-indicator column (the same one-scatter trick
    as :func:`push`'s extremum path).
    """
    acc_dt = jnp.promote_types(dtype, jnp.float32)
    if combine in ("max", "min"):
        lim = jnp.finfo(acc_dt).max
        fill = -lim if combine == "max" else lim
        return jnp.full((hot_rows, dim + 1), fill, acc_dt)
    cols = dim + (1 if delta_counted(combine, fold) else 0)
    return jnp.zeros((hot_rows, cols), acc_dt)


def _reset_delta(delta_buf: Array, combine: str) -> Array:
    """Window-end buffer reset: zeros for the additive combines, the
    extremal sentinel fill for ``max``/``min``."""
    if combine in ("max", "min"):
        lim = jnp.finfo(delta_buf.dtype).max
        fill = -lim if combine == "max" else lim
        return jnp.full_like(delta_buf, fill)
    return jnp.zeros_like(delta_buf)


def accumulate_hot(
    delta_buf: Array, hot_ids_arr: Array, hot_deltas: Array, *,
    combine: str = "sum", fold=None
) -> Array:
    """Fold one step's hot-tier pushes into the local pending buffer.

    ``hot_ids_arr``/``hot_deltas`` come from :func:`split_hot_push` (cold
    slots already ``-1``/zero, dropped by the scatter). Purely local —
    the collective happens once per window, in :func:`reconcile_hot`.
    ``max``/``min`` combine via a native scatter-max/min with the
    touched indicator riding as an appended ones column.
    """
    vals = hot_deltas.astype(delta_buf.dtype)
    if combine in ("max", "min"):
        ones = jnp.ones(hot_ids_arr.shape, delta_buf.dtype)[:, None]
        filled = jnp.concatenate([vals, ones], axis=1)
        # Negative .at[] indices wrap — map the masked -1 slots out of
        # range so mode="drop" drops them (ops.scatter_add's xla step).
        safe = jnp.where(hot_ids_arr >= 0, hot_ids_arr,
                         delta_buf.shape[0])
        if combine == "max":
            return delta_buf.at[safe].max(filled, mode="drop")
        return delta_buf.at[safe].min(filled, mode="drop")
    if delta_counted(combine, fold):
        # One scatter carries values AND counts (appended ones column) —
        # the same one-scatter trick as push()'s non-"sum" folds.
        cnt = (hot_ids_arr >= 0).astype(delta_buf.dtype)[:, None]
        vals = jnp.concatenate([vals, cnt], axis=1)
    return ops.scatter_add(delta_buf, hot_ids_arr, vals)


def hot_fold_state_shape(fold, H: int, dim: int,
                         num_shards: int) -> tuple[int, int]:
    """GLOBAL shape of one table's hot-fold state: ``ceil(H/S)`` rows per
    shard in reduce-scatter slice order (slice ``s`` holds head rows
    ``[s*Hs, (s+1)*Hs)``), padded to a multiple of ``S``; columns per
    :meth:`fps_tpu.core.api.HotFold.state_cols`."""
    Hs = rows_per_shard(H, num_shards)
    return (Hs * num_shards, fold.state_cols(dim))


def apply_hot_fold(fold, state: Array, g: Array,
                   counts: Array) -> tuple[Array, Array]:
    """Apply a stateful fold to the owned reconcile slice.

    ``g`` is the window's combined delta for this device's contiguous
    head slice (post ``combine`` normalization), ``counts`` the per-row
    push counts, ``state`` the device's slice of the sharded optimizer
    state. LAZY semantics: rows with no pushes this window keep their
    state (and receive a zero step) — the sparse-table convention, so a
    zero-traffic row can never drift. Returns ``(step, new_state)``.
    """
    dt = g.dtype
    touched = counts > 0
    t1 = touched[:, None]
    if fold.kind == "adagrad":
        G = state + jnp.where(t1, g * g, 0.0).astype(state.dtype)
        step = jnp.where(
            t1, fold.lr * g / (jnp.sqrt(G).astype(dt) + fold.eps), 0.0)
        return step, G
    dim = g.shape[1]
    m, v = state[:, :dim], state[:, dim:2 * dim]
    t = state[:, 2 * dim]
    t_new = t + touched.astype(state.dtype)
    m_new = jnp.where(t1, fold.beta1 * m + (1.0 - fold.beta1) * g, m)
    v_new = jnp.where(t1, fold.beta2 * v + (1.0 - fold.beta2) * g * g, v)
    tc = jnp.maximum(t_new, 1.0)
    mhat = m_new / (1.0 - fold.beta1 ** tc)[:, None]
    vhat = v_new / (1.0 - fold.beta2 ** tc)[:, None]
    step = jnp.where(
        t1, fold.lr * mhat.astype(dt) / (jnp.sqrt(vhat).astype(dt)
                                         + fold.eps), 0.0)
    new_state = jnp.concatenate([m_new, v_new, t_new[:, None]], axis=1)
    return step, new_state


def _reconcile_combine(
    replica: Array,
    delta_buf: Array,
    *,
    num_shards: int,
    shard_axis: str,
    data_axis: str | None,
    combine: str,
    fold=None,
    fold_state: Array | None = None,
) -> tuple[Array, Array, Array | None]:
    """Shared half of the window-end reconcile, SHARDED over the replica
    axis (arXiv:2004.13336's cross-replica weight-update sharding applied
    to the hot tier): instead of one psum that hands every device the
    full ``(H, dim')`` window delta, the pending buffers are

    1. **reduce-scattered** over the shard axis — device ``s`` receives
       the summed slice for head rows ``[s*Hs, (s+1)*Hs)`` only;
    2. psum'd over the (replicated) data axis — now ``1/S`` the payload
       the old full-head data psum moved;
    3. normalized ("mean" count column) and, for a stateful
       :class:`~fps_tpu.core.api.HotFold`, folded against the device's
       DISJOINT slice of the sharded optimizer state — the property the
       sharding buys: per-row Adagrad/Adam state exists exactly once
       across the mesh, and each device does ``1/S`` of the fold work;
    4. **all-gathered** back so every replica applies the identical
       combined step.

    The ``max``/``min`` combines keep a full-head pmax/pmin instead
    (extremum does not reduce-scatter); they carry no fold state.

    Returns ``(combined_step, new_replica, new_fold_state_slice)`` — the
    static and mapped reconciles differ only in how the combined step
    addresses the canonical shard, so the summation / normalization /
    fold semantics live in exactly one place and cannot drift between
    them."""
    H, dim = replica.shape
    if combine in ("max", "min"):
        red = lax.pmax if combine == "max" else lax.pmin
        g = red(delta_buf, shard_axis)
        if data_axis is not None:
            g = red(g, data_axis)
        # Touched rows carry indicator 1.0; untouched still the sentinel.
        touched = jnp.abs(g[:, dim]) <= 1.0
        combined = jnp.where(touched[:, None], g[:, :dim],
                             0.0).astype(replica.dtype)
        return combined, replica + combined, fold_state
    dimp = delta_buf.shape[1]
    Hs = rows_per_shard(H, num_shards)
    pad = Hs * num_shards - H
    buf = delta_buf
    if pad:
        buf = jnp.concatenate(
            [buf, jnp.zeros((pad, dimp), buf.dtype)], axis=0)
    sl = lax.psum_scatter(buf, shard_axis, scatter_dimension=0, tiled=True)
    if data_axis is not None:
        sl = lax.psum(sl, data_axis)
    counts = sl[:, dim] if dimp > dim else None
    g = sl[:, :dim]
    if combine == "mean":
        g = g * (1.0 / jnp.maximum(counts, 1.0))[:, None]
    new_state = fold_state
    if fold is not None:
        g, new_state = apply_hot_fold(fold, fold_state, g, counts)
    g = g.astype(replica.dtype)
    full = lax.all_gather(g, shard_axis, tiled=True)
    combined = full[:H] if pad else full
    return combined, replica + combined, new_state


def reconcile_hot(
    cold_shard: Array,
    replica: Array,
    delta_buf: Array,
    *,
    num_shards: int,
    shard_axis: str = SHARD_AXIS,
    data_axis: str | None = None,
    combine: str = "sum",
    fold=None,
    fold_state: Array | None = None,
) -> tuple[Array, Array, Array, Array | None]:
    """Window-end reconcile: reduce-scatter the pending buffers, apply
    the owned 1/S slice, all-gather the combined step everywhere.

    One reduce-scatter + all-gather pair over the shard axis (see
    :func:`_reconcile_combine` — the cross-replica sharded form of the
    old full-head psum) replaces ``hot_sync_every`` steps' worth of
    per-step push collectives for the head rows. The combined step is
    applied to the replica (identically on every device — all-gather
    results are bitwise-identical across participants, so the replica
    stays replicated by construction) AND to this shard's OWNED head
    rows of the canonical table: under the owner-major cyclic layout,
    global id ``h`` lives on shard ``h % S`` at local row ``h // S``, so
    the shard's head ids occupy exactly local rows ``[0, ceil(H/S))``.

    ``combine="mean"``: the buffer's appended count column turns the
    window's sum into one count-normalized step per touched row (the
    windowed analog of the "mean" combine's one-averaged-step-per-push;
    untouched rows have count 0 and receive exactly zero).
    ``combine="max"/"min"``: one pmax/pmin of the extremum buffer — the
    windowed analog of the extremum combine (one extremal step per
    touched row per window). ``fold``: stateful Adagrad/Adam on the
    owned slice (:func:`apply_hot_fold`), state sharded over the shard
    axis in slice order.

    Returns ``(new_cold_shard, new_replica, reset_delta_buf,
    new_fold_state)``.
    """
    H, _ = replica.shape
    combined, new_replica, new_state = _reconcile_combine(
        replica, delta_buf, num_shards=num_shards, shard_axis=shard_axis,
        data_axis=data_axis, combine=combine, fold=fold,
        fold_state=fold_state)
    hl = -(-H // num_shards)  # local head rows on every shard
    me = lax.axis_index(shard_axis)
    # Global id of local head row j is j*S + me; rows past H (when S does
    # not divide H) gather id -1 -> a zero row, i.e. no update.
    gids = jnp.arange(hl, dtype=jnp.int32) * num_shards + me
    mine = ops.gather_rows(combined, jnp.where(gids < H, gids, -1))
    new_cold = cold_shard.at[:hl].add(mine.astype(cold_shard.dtype))
    return new_cold, new_replica, _reset_delta(delta_buf, combine), new_state


# ---------------------------------------------------------------------------
# Collective pull / push (call inside shard_map).
# ---------------------------------------------------------------------------

# The owner-routed exchange's lane: a worker's ``B`` ids fall ``B / S`` to
# an owner shard on average, and a lane is LANE_MARGIN times that, rounded
# up to 8 (a sublane tile). The owner is ``id % S``, which no skew of
# FREQUENCY unbalances but by the few most frequent ids themselves (the
# cyclic layout's reason for being): under word2vec's Zipf law over 1.1 M
# words, untiered, shard 0 owns 1.09 x its share of a batch (it has rank 0);
# with the head replicated the cold ids are near uniform, and at
# ``w2v-1bw-hot.x4``'s smaller batch (8,197 ids, 4 shards: 2,049 +- 39 an
# owner if every id were live) the lane of 2,568 stands 13 standard
# deviations over the mean. A batch that does not fit runs the gathered
# exchange that step. Under ROUTED_MIN_IDS_PER_SHARD ids a shard a lane
# is mostly its rounding up to 8 (at 2 ids a shard the lanes hold as many
# slots as the gathered exchange hands ids), and the exchange stays
# gathered.
LANE_MARGIN = 1.25
ROUTED_MIN_IDS_PER_SHARD = 8


def _lane_width(num_ids: int, num_shards: int) -> int:
    return -(-math.ceil(LANE_MARGIN * num_ids / num_shards) // 8) * 8


def _routes_to_owner(num_ids: int, num_shards: int,
                     data_axis: str | None) -> bool:
    """Whether a non-dense exchange of ``num_ids`` ids a worker is the
    owner-routed one, from what :func:`pull` and :func:`push` see."""
    return (num_shards > 1 and data_axis is None
            and num_ids >= ROUTED_MIN_IDS_PER_SHARD * num_shards)


class _Lanes(NamedTuple):
    """A worker's ids routed by owner (:func:`_owner_lanes`)."""
    fits: Array  # scalar bool, the same on every shard: no lane overflows
    ids: Array   # [S, L] the ids of lane ``d`` are shard ``d``'s; -1 pads
    src: Array   # [S * L] the batch position a lane slot holds, -1 pads
    slot: Array  # [B] the flat lane slot of each id, S * L for one in none


def _owner_lanes(ids: Array, *, num_shards: int, shard_axis: str) -> _Lanes:
    """Place a worker's ``(B,)`` ids into one lane an owner shard, in the
    batch's order (:func:`compact_cold`'s method, once an owner: a stable
    position ``cumsum(live & owner == d) - 1``); negative ids (padding, the
    tier's hot ids, the worker's dropped rows) take no lane. The lanes are
    filled from ONE sort of the ids by their slot and a slice a lane, not
    by a scatter. ``fits``: the fullest lane of any worker holds its ids,
    by a ``pmax`` over the shard axis, so every shard takes the same
    branch; an id past its lane's end is left out of the lane, which only
    a caller that ignores ``fits`` would see."""
    S, (B,) = num_shards, ids.shape
    L = _lane_width(B, S)
    live, owner = ids >= 0, ids % S
    slot = jnp.full((B,), S * L, jnp.int32)
    counts = []
    for d in range(S):
        mine = live & (owner == d)
        pos = jnp.cumsum(mine.astype(jnp.int32)) - 1
        slot = jnp.where(mine & (pos < L), d * L + pos, slot)
        counts.append(pos[-1] + 1)
    counts = jnp.stack(counts)
    fits = lax.pmax(jnp.max(counts), shard_axis) <= L
    # Sorted by slot an owner's ids are contiguous, in the batch's order,
    # the ids of no lane last: lane d is L of them from where owner d's
    # begin, cut at its count (the keys of the laned are distinct).
    _, by_slot, at = lax.sort(
        (slot, ids, jnp.arange(B, dtype=jnp.int32)), num_keys=1,
        is_stable=False)
    held = jnp.minimum(counts, L)
    begin = jnp.cumsum(held) - held
    lane = jnp.arange(L, dtype=jnp.int32)

    def lanes_of(x):
        x = jnp.concatenate([x, jnp.full((L,), -1, x.dtype)])
        return jnp.stack([
            jnp.where(lane < held[d],
                      lax.dynamic_slice(x, (begin[d],), (L,)), -1)
            for d in range(S)])

    return _Lanes(fits, lanes_of(by_slot), lanes_of(at).reshape(S * L), slot)


def _rows_at(x: Array, idx: Array) -> Array:
    """``x[idx]`` with ZERO rows where ``idx`` is negative: rows moved
    between a batch and its lanes (a buffer's rows, not a table's: no
    route of :mod:`fps_tpu.ops` is asked)."""
    live = idx >= 0
    rows = jnp.take(x, jnp.where(live, idx, 0), axis=0)
    return jnp.where(live[:, None], rows, jnp.zeros_like(rows))


def _lanes_of_call(op: str, local_shard: Array, ids: Array, *,
                   num_shards: int, shard_axis: str,
                   data_axis: str | None, table: str) -> _Lanes | None:
    """What a non-dense :func:`pull` / :func:`push` (``op``) opens with:
    the lanes of its ids where the exchange is the owner-routed one
    (logged as ``<op>.routed`` with the shard's shape, the worker's ids
    and ``lanes=SxL``), ``None`` where it stays gathered; either way the
    step's ``routed`` flag is noted over more than one shard."""
    B = ids.shape[0]
    if not _routes_to_owner(B, num_shards, data_axis):
        if num_shards > 1:
            _note_routed(table, 0)
        return None
    lanes = _owner_lanes(ids, num_shards=num_shards, shard_axis=shard_axis)
    S, L = lanes.ids.shape
    ops.log_route(op, "routed", *local_shard.shape, B,
                  f"table={table} lanes={S}x{L}" if table
                  else f"lanes={S}x{L}")
    _note_routed(table, lanes.fits)
    return lanes


def _trade(lanes: Array, shard_axis: str) -> Array:
    """Lane ``d`` of every worker to shard ``d``: ``[S, L, ...]`` by
    destination in, ``[S, L, ...]`` by source out (position-indexed data:
    no reduction order is delegated to the backend)."""
    return lax.all_to_all(lanes, shard_axis, split_axis=0, concat_axis=0,
                          tiled=False)


# The certificates of the exchanges traced while a watch is open, by the
# table the caller named: how a step's ``routed`` flag leaves the step
# (:meth:`fps_tpu.core.driver.Trainer._mount_hot_channel`).
_ROUTED_WATCHES: list[dict] = []


@contextlib.contextmanager
def _watch(watches: list[dict]):
    """Open a dict on ``watches`` for what is noted while a step is
    traced, and hand it over."""
    seen: dict = {}
    watches.append(seen)
    try:
        yield seen
    finally:
        watches.pop()


def watch_routed():
    """Collect ``{table: flag}`` while a step is traced: for each table
    whose :func:`pull` / :func:`push` (called with ``table=``) went over
    more than one shard by the non-dense exchange, an int32 scalar, the
    same on every shard: 1 where every such exchange of the step ran
    owner-routed, 0 where one ran gathered (its ids did not fit their
    lanes, or the shapes keep the exchange gathered)."""
    return _watch(_ROUTED_WATCHES)


def _note_routed(table: str, fits) -> None:
    if _ROUTED_WATCHES and table:
        seen = _ROUTED_WATCHES[-1]
        seen[table] = seen.get(table, 1) * jnp.asarray(fits, jnp.int32)


# What the additive pushes that summed their rows by id first
# (``push.sum_runs``) counted while a watch is open, by the table the
# caller named: how a step's counts leave the step
# (:meth:`fps_tpu.core.driver.Trainer._mount_counts`).
_SUM_RUNS_WATCHES: list[dict] = []


def watch_sum_runs():
    """Collect ``{table: {"pushed_ids", "live_ids"}}`` while a step is
    traced: for each table whose :func:`push` (called with ``table=``)
    took ``push.sum_runs``, two int32 scalars of THIS shard: the pushes it
    was handed and kept, and the distinct ids among them, which are what
    its scatter then pays for. Empty where no push took the route."""
    return _watch(_SUM_RUNS_WATCHES)


def _note_counts(watches: list[dict], table: str, **counts) -> None:
    if watches and table:
        seen = watches[-1].setdefault(table, dict.fromkeys(counts, 0))
        for k, v in counts.items():
            seen[k] += v


# What the pushes that folded the touched rows alone (``push.fold_rows``)
# counted while a watch is open, as ``push.sum_runs``' counts are kept:
# how a step's counts leave the step
# (:meth:`fps_tpu.core.driver.Trainer._mount_counts`).
_FOLD_ROWS_WATCHES: list[dict] = []


def watch_fold_rows():
    """Collect ``{table: {"handed_ids", "folded_ids"}}`` while a step is
    traced: for each table whose :func:`push` (called with ``table=``)
    took ``push.fold_rows``, two int32 scalars of THIS shard: the pushes
    it was handed and kept, and the distinct ids among them, each folded
    once. Empty where no push took the route."""
    return _watch(_FOLD_ROWS_WATCHES)


# What the pulls that read each distinct row of a step once
# (``pull.distinct_rows``) counted while a watch is open, as
# ``push.sum_runs``' counts are kept.
_DISTINCT_PULL_WATCHES: list[dict] = []


def watch_distinct_pulls():
    """Collect ``{table: {"pulled_ids", "live_ids"}}`` while a step is
    traced: for each table whose :func:`pull` (called with ``table=``)
    took ``pull.distinct_rows``, two int32 scalars of THIS shard: the ids
    it was handed and kept (its own, none negative), and the distinct ids
    among them, which are the rows its gather from the table then pays
    for. Empty where no pull took the route."""
    return _watch(_DISTINCT_PULL_WATCHES)


def _distinct_pull_route(rps: int, dim: int, num_ids: int, dt) -> bool:
    """Read each DISTINCT row of a pull from the shard once
    (``pull.distinct_rows``)? From :func:`pull`'s own shapes, by the
    predicate ``push.sum_runs`` stands on for the same reason: a table of
    narrow float rows so large that XLA keeps it transposed in HBM
    (:func:`fps_tpu.ops._xla_transposed_hbm`), where the plain gather
    pays some 23 ns for every id it is handed, a repeat or not; on the
    TPU, backend not ``"xla"``, for more ids than one block of the loop
    that reads the distinct rows. Any float dtype of that predicate: a
    gather copies, nothing is rounded. Whether the batch HAS repeats is
    not in the shapes: :func:`_distinct_rows` reads it from the ids."""
    use, interpret = ops._use_pallas()
    return (use and not interpret and num_ids > ops.XLA_SORTED_BLOCK_IDS
            and ops._xla_transposed_hbm(rps, dim, dt))


def _distinct_rows(local_shard: Array, idx: Array, keep: Array | None,
                   plain: Callable[[], Array], *,
                   exact: bool) -> tuple[Array, Array, Array]:
    """``pull.distinct_rows``: ``(rows [N, dim], pulled, live)``, the
    shard's rows at ``idx [N]`` with ZERO rows where an index is outside
    the shard or ``keep`` (a mask, where given) says to leave it out;
    ``pulled`` the entries kept and ``live`` the distinct indices among
    them. In ``push.sum_runs``' vocabulary: one sort of ``(index or the
    drop sentinel, position)``, never of rows; the batch LOOKED AT
    (:func:`_repeats`); where it repeats itself, the distinct indices
    compacted to the front by a sort of the indices alone, their rows read
    from the shard ONCE each, a block of
    :data:`fps_tpu.ops.XLA_SORTED_BLOCK_IDS` at a time
    (:func:`fps_tpu.ops.gather_rows`, so under ``fps.ops/gather.xla``) and
    only the blocks that hold a live index, into a buffer of the BATCH'S
    shape (``[N, dim]``: XLA then keeps buffer and rows in one layout,
    transposed and in VMEM, and the expand is the 2 ms gather the push
    makes of its own rows; a buffer of
    :data:`fps_tpu.ops.SUM_RUNS_MAX_DISTINCT_SHARE` of the ids, all a
    batch that repeats itself can fill, is copied row-major into HBM
    first and the expand reads 4.5 ms: chip runs, PR 54); each entry's
    run number brought back to the batch's order by a third sort, of
    ``(position, run)``, and its row taken out of the buffer. Where the
    batch hardly repeats itself the sorts would cost more than the repeats,
    and ``plain()`` reads every index as before. Either way the rows are
    the plain gather's bit for bit: a gather copies."""
    rps, dim = local_shard.shape
    (N,) = idx.shape
    C = min(ops.XLA_SORTED_BLOCK_IDS, N)
    ops.log_route("pull", "distinct_rows", rps, dim, N, "xla_transposed_hbm")
    kept = (idx >= 0) & (idx < rps)
    if keep is not None:
        kept &= keep
    s, order = lax.sort(
        (jnp.where(kept, idx, rps), jnp.arange(N, dtype=jnp.int32)),
        num_keys=2, is_stable=False)  # the keys are distinct
    first, _ = _run_ends(s)
    pulled = jnp.sum(kept, dtype=jnp.int32)
    live = jnp.sum(first & (s < rps), dtype=jnp.int32)

    def distinct():
        (ids,) = lax.sort((jnp.where(first, s, rps),), is_stable=False)

        def fetch(c, buf):
            start = jnp.minimum(c * C, N - C)
            block = ops.gather_rows(
                local_shard, lax.dynamic_slice(ids, (start,), (C,)),
                exact=exact)
            return lax.dynamic_update_slice(buf, block, (start, 0))

        buf = lax.fori_loop(0, (live + C - 1) // C, fetch,
                            jnp.zeros((N, dim), local_shard.dtype))
        # The entries left out are the LAST run, number ``live``, and the
        # buffer's row ``live`` is a zero row: with an entry left out
        # ``live <= pulled < N``, and that row was either never fetched
        # or fetched at the sentinel. No mask, no pass more.
        _, run = lax.sort((order, jnp.cumsum(first.astype(jnp.int32)) - 1),
                          num_keys=1, is_stable=False)
        return jnp.take(buf, run, axis=0, mode="clip")

    return lax.cond(_repeats(pulled, live), distinct, plain), pulled, live


def pull(
    local_shard: Array,
    ids: Array,
    *,
    num_shards: int,
    shard_axis: str = SHARD_AXIS,
    dense: bool = False,
    hot_rows: int = 0,
    head_prefix: int = 0,
    exact: bool = False,
    data_axis: str | None = None,
    table: str = "",
) -> Array:
    """Gather parameter rows for ``ids`` from the sharded table.

    Args:
      local_shard: this device's ``(rps, dim)`` block of the table.
      ids: ``(B,)`` int32 parameter ids requested by this worker.
      num_shards: size of the shard axis (static).
      dense: replicate-on-read route for SMALL tables: all_gather the
        whole table (one table-sized collective riding ICI) and gather
        locally — ``O(B)`` row transactions per worker instead of the
        gathered route's ``O(W * B)`` per shard (every shard processes
        every worker's ids). Policy: ``TableSpec.dense_collectives``,
        resolved against :data:`fps_tpu.ops.DENSE_TABLE_BYTES`.
      exact: bit-exact reads — forward to :func:`fps_tpu.ops.gather_rows`
        so read-only pulls (eval, export) skip the lossy dim-1 route
        instead of inheriting training's precision contract.
      data_axis: the mesh's replicated data axis where it is larger than
        one (:func:`push`'s argument): the exchange then stays gathered.
      table: the table's name, for the route log, the step's ``routed``
        flag (:func:`watch_routed`) and its counts
        (:func:`watch_distinct_pulls`); nothing else reads it.

    Not ``dense``, where the shard is one XLA keeps transposed in HBM
    (:func:`_distinct_pull_route`: ``pull.distinct_rows`` in the route
    log) and the batch is seen to repeat its ids, each DISTINCT row is
    read from the shard once and every requested position is handed its
    row out of a payload-sized buffer (:func:`_distinct_rows`): the plain
    gather's rows bit for bit (a gather copies), negative ids and the ids
    of other shards still zero rows, at the cost of the distinct ids and
    not of the requests. Both exchanges below read the shard that way.

    Not ``dense``, over more than one shard with no data axis, the
    exchange is the OWNER-ROUTED one (:func:`_routes_to_owner`;
    ``pull.routed`` in the route log): the ids go to their owners in lanes
    (:func:`_owner_lanes`), an owner gathers the ``S x L`` rows it is
    asked for, sends them back by a second ``all_to_all``, and the worker
    reads its ``B`` rows from its lanes' slots. Each row comes from one
    shard and nothing is summed, so the rows are the gathered exchange's
    bit for bit; a step whose ids do not fit their lanes runs the gathered
    exchange (``lax.cond`` on the lanes' certificate).

    Returns:
      ``(B, dim)`` values, one row per requested id.

    Replaces the reference's ``ParameterServerClient.pull`` →
    ``ParameterServerLogic.onPullRecv`` → ``answerPull`` round trip
    (expected upstream ``.../ps/FlinkParameterServer.scala``).
    """
    if dense:
        # (S*rps, dim) in PHYSICAL (owner-major) layout: tiled all_gather
        # concatenates shard s's block at rows [s*rps, (s+1)*rps).
        full = lax.all_gather(local_shard, shard_axis, tiled=True)
        rps = local_shard.shape[0]
        # Negative ids read as zero rows on every route (id_to_phys would
        # wrap them into range via the Python-semantics modulo).
        phys = jnp.where(ids >= 0, id_to_phys(ids, num_shards, rps), -1)
        return ops.gather_rows(full, phys, exact=exact)

    rps, dim = local_shard.shape
    lanes = _lanes_of_call("pull", local_shard, ids, num_shards=num_shards,
                           shard_axis=shard_axis, data_axis=data_axis,
                           table=table)
    # The pull's own regime, asked about the ids the exchange hands this
    # shard (the lanes' S x L where it is the owner-routed one, as the
    # push's is).
    distinct = _distinct_pull_route(
        rps, dim, num_shards * ids.shape[0] if lanes is None
        else lanes.src.shape[0], local_shard.dtype)

    def rows_of(idx, keep=None, **head):
        """The shard's rows at ``idx`` (zero rows outside it), the one
        place this pull reads the table: each distinct row once where the
        regime is the one whose gather pays for every id it is handed,
        with the step's counts beside the rows; ``keep``: the entries
        whose rows the caller uses."""
        def plain():
            return ops.gather_rows(local_shard, idx, exact=exact, **head)

        if not distinct:
            return (plain(),)
        return _distinct_rows(local_shard, idx, keep, plain, exact=exact)

    def gathered():
        me = lax.axis_index(shard_axis)
        # Every shard sees every worker's request ids: (S*B,).
        all_ids = lax.all_gather(ids, shard_axis, tiled=True)
        owned = (all_ids % num_shards) == me
        local_idx = jnp.where(owned, all_ids // num_shards, 0)
        # The head-prefix guarantee only survives when the gathered stream
        # IS the caller's stream (single shard; local_idx == ids there).
        vals, *counts = rows_of(
            local_idx, owned, hot_rows=hot_rows,
            head_prefix=head_prefix if num_shards == 1 else 0)
        vals = jnp.where(owned[:, None], vals, jnp.zeros_like(vals))
        # Each worker ends up with its own (B, dim) slice, summed over
        # shards (exactly one shard contributed each row).
        return (lax.psum_scatter(vals, shard_axis, scatter_dimension=0,
                                 tiled=True), *counts)

    def routed():
        # The ids this shard owns, by the worker that asks (-1 pads: floor
        # division keeps it negative, and it reads a zero row).
        asked = _trade(lanes.ids, shard_axis).reshape(-1)
        rows, *counts = rows_of(asked // num_shards)
        back = _trade(rows.reshape(num_shards, -1, dim), shard_axis)
        return (_rows_at(back.reshape(-1, dim),
                         jnp.where(lanes.slot < asked.shape[0], lanes.slot,
                                   -1)), *counts)

    # The counts leave the exchange's conditional with the rows, and are
    # noted for the step outside it.
    vals, *counts = (gathered() if lanes is None
                     else lax.cond(lanes.fits, routed, gathered))
    if counts:
        _note_counts(_DISTINCT_PULL_WATCHES, table, pulled_ids=counts[0],
                     live_ids=counts[1])
    return vals


# Device scope of a non-"sum" combine's own work in :func:`push`, whatever
# the routed scatter-add does not do. On the accumulator branch
# (``push.mean_dense``, callable, max / min) that is TABLE-SIZED: the making
# of the (rows, dim + 1) accumulator, the normalisation and the add to the
# shard (max / min: their raw scatter too). On the row branch
# (``push.mean_rows``) it is PAYLOAD-SIZED: the per-id counts and the
# distinct ids compacted to the front (three sorts of the B ids), the
# multiply of the B pushed rows and their sum by id in a (B, dim) buffer.
# And where the accumulator branch sums the pushed rows by id run before
# its scatter (``push.acc_runs``): two sorts of the B ids carrying the
# rows' columns and the doubling passes between them, payload-sized too;
# and where it is filled by the dense exchange (``push.dense_acc``): the
# all_to_all of the buffer's windows and the sum over the source shards,
# table-sized. The routed scatter-add keeps its own ``fps.ops/...``
# scope BESIDE this one, so no op counts under both
# (docs/observability.md).
COMBINE_SCOPE = "fps.combine"


def _id_runs(idx: Array, drop: int) -> tuple[Array, Array, Array]:
    """The duplicates of a batch of row indices ``idx [B]``, in
    ``[B]``-sized arrays alone: ``n[i]``, how many entries equal
    ``idx[i]`` (at least 1); ``slot[i]`` in ``[0, B)``, one slot an index,
    the same for all its entries, the slots numbered in the order of their
    indices; and ``slot_idx[j]``, the index whose slot ``j`` is, ``drop``
    past the last slot: the distinct indices SORTED, compacted to the
    front. By sorting the indices with their positions, taking each run's
    first and last position (a running max, a reversed running min) and
    its number (a running count of the runs' firsts), sorting back, and
    one sort more of the runs' firsts alone. No
    ``[rows]`` count vector: a scalar scatter-add and gather of the same
    ids cost 3.6x as much beside the push's row scatter at 49,182 ids
    into 1,115,011 rows (0.70 against 0.19 ms; 0.12 against 0.03 at
    8,197: ``tools/bench_scatter.py mean counts``, chip run, PR 28)."""
    B = idx.shape[0]
    pos = jnp.arange(B, dtype=jnp.int32)
    s, order = lax.sort_key_val(idx, pos)
    edge = s[1:] != s[:-1]
    one = jnp.ones((1,), bool)
    first = jnp.concatenate([one, edge])
    lo = lax.cummax(jnp.where(first, pos, 0))
    hi = lax.cummin(jnp.where(jnp.concatenate([edge, one]), pos, B - 1),
                    reverse=True)
    run = jnp.cumsum(first.astype(jnp.int32)) - 1
    _, n, slot = lax.sort((order, hi - lo + 1, run), num_keys=1)
    return n, slot, jnp.sort(jnp.where(first, s, drop))


def _run_sums(first: Array, cols: tuple[Array, ...]) -> tuple[Array, ...]:
    """Inclusive sums of each of ``cols`` (``[B]`` arrays, or ``[W, B]``:
    the batch along the LAST axis, the lane-dense form of ``W`` columns)
    WITHIN the runs whose first elements ``first [B]`` flags: a run's last
    element holds its total. A segmented scan of log depth by doubling: at
    distance ``s`` an element adds the one ``s`` before it unless a run
    began in between, ``ceil(log2(B))`` elementwise passes over ``[B]``
    arrays. A run's addends meet in a balanced tree, so its total carries
    the rounding of ``log2(run length)`` additions of its OWN addends; the
    difference of two running sums over the batch would carry that of a
    prefix of the whole batch."""
    B = first.shape[0]
    began, s = first, 1
    while s < B:
        cols = tuple(
            jnp.where(began, c, c + jnp.pad(
                c[..., :-s], [(0, 0)] * (c.ndim - 1) + [(s, 0)]))
            for c in cols)
        began = began | jnp.pad(began[:-s], (s, 0), constant_values=True)
        s *= 2
    return cols


def _sum_id_runs(idx: Array, rows: Array, drop: int) -> tuple[Array, Array]:
    """The rows of a batch summed by index, and counted: ``(ids [B], sums
    [B, W + 1])`` from ``idx [B]`` (``drop`` for a row to leave out) and
    ``rows [B, W]``: the distinct indices SORTED at the front of ``ids``,
    beside each the sum of its rows and, last column, their number, and
    ``drop`` past the last of them (the rows beside those are to be
    dropped, not zeros). One sort of the indices that carries the rows'
    columns, the sums within each run of equal indices (:func:`_run_sums`:
    a tree within the run, no scatter and no running sum over the batch;
    a ones column rides along and sums to the count, exactly), the total
    kept on the run's last element and every other element given
    ``drop``, and one sort more that brings the totals to the front.
    Neither sort is stable: the order of one index's addends is the
    tree's whatever the sort, its second keys are distinct but for
    ``drop``, and a stable TPU sort takes twice as long to compile (69 s
    against 36 with three operands, compile-only, PR 33's builder). On
    ``lr-criteo.epochs``' 425,997 ids of which 75,553 are distinct, 1.56
    ms (``tools/bench_scatter.py fold probes``, that builder's chip run:
    the first sort 0.91, the second 0.73, the 19 doubling passes under
    0.1; ``lax.associative_scan`` in their place 4.25 in all, positions
    and two gathers of the rows 5.75)."""
    W = rows.shape[1]
    s, *cols = lax.sort((idx, *(rows[:, j] for j in range(W))), num_keys=1,
                        is_stable=False)
    edge = s[1:] != s[:-1]
    one = jnp.ones((1,), bool)
    cols = _run_sums(jnp.concatenate([one, edge]),
                     (*cols, (s != drop).astype(rows.dtype)))
    last = jnp.concatenate([edge, one])
    ids, *cols = lax.sort((jnp.where(last, s, drop), *cols), num_keys=1,
                          is_stable=False)
    return ids, jnp.stack(cols, axis=1)


def _acc_runs_route(rps: int, dim: int, num_ids: int, acc_dt) -> bool:
    """Sum the pushed rows by id run before the ``(rps, dim + 1)``
    accumulator's scatter (``push.acc_runs``)? From :func:`push`'s own
    shapes, whatever the platform: the accumulator is one XLA keeps
    transposed (:func:`fps_tpu.ops._xla_transposed`: there the scatter
    pays some 45 ns for every id it is handed, a repeat or not, and the
    sorted route stops at the last distinct one; inside XLA's VMEM regime
    an id costs a few ns and the sorts would not pay), and the ids are
    many enough against the rows that a batch without skew does not lose
    (:data:`fps_tpu.ops.ACC_RUNS_MIN_IDS_PER_ROW`)."""
    return (ops._xla_transposed(rps, dim + 1, acc_dt)
            and num_ids >= ops.ACC_RUNS_MIN_IDS_PER_ROW * rps)


def _sum_runs_route(rps: int, dim: int, num_ids: int, dt) -> bool:
    """Sum the pushed rows by id before the ADDITIVE push's scatter into
    the shard itself (``push.sum_runs``)? From :func:`push`'s own shapes,
    and only where the scatter that follows stops at the last distinct id
    (:func:`fps_tpu.ops._route_xla_sorted`: on the TPU, backend not
    ``"xla"``, more ids than one of its blocks; handed to any other
    scatter the sums buy nothing): a float32 table of narrow rows so
    large that XLA keeps it transposed in HBM
    (:func:`fps_tpu.ops._xla_transposed_hbm`), where the plain scatter
    pays some 100 ns for every id it is handed, a repeat or not. A table
    narrower than float32 stays out (its scatter adds an id's pushes in
    the table's own dtype one by one; sums formed first would round
    elsewhere), and so does a wider one. Whether the batch HAS repeats is
    not in the shapes: :func:`_sorted_runs` reads it from the ids."""
    return (jnp.dtype(dt) == jnp.float32
            and ops._xla_transposed_hbm(rps, dim, dt)
            and ops._route_xla_sorted(rps, dim, num_ids, dt, True))


def _run_ends(s: Array) -> tuple[Array, Array]:
    """Of sorted ``s [B]``: where a run of equal values begins, and where
    it ends."""
    edge = s[1:] != s[:-1]
    one = jnp.ones((1,), bool)
    return jnp.concatenate([one, edge]), jnp.concatenate([edge, one])


def _sorted_runs(idx: Array, rows: Array, drop: int, pad_to: int = 0):
    """The first half of ``push.sum_runs``, from what the exchange hands a
    shard and with nothing of the table in it: the batch sorted by index
    and LOOKED AT. ``(s, in_order, begun, in_long, long_ids, pushed,
    live)`` from ``idx [B]`` (``drop`` or more for a row to leave out) and
    ``rows [B, W]``: the indices sorted (``drop`` last) and the rows
    beside them, an index's rows in the BATCH'S order (the sort's second
    key is the position); ``pushed`` the rows not left out and ``live``
    the distinct indices among them. One sort of ``(index, position)``,
    never of the rows' ``W`` columns (a TPU sort's time, and above all its
    compile time, grows with its operands: :func:`_sum_id_runs` carries
    3 - 4, this would carry 17: 426 s of compile), and ONE gather of the
    rows into that order.

    Where the batch repeats itself (:func:`_repeats`), also its LONG runs,
    those of more than :data:`fps_tpu.ops.SUM_RUNS_TREE_MAX_RUN` rows:
    ``long_ids [H]`` their indices in order (``drop`` after the last; ``H``
    is the most a batch of ``max(B, pad_to)`` can hold), ``begun [B]`` how many of them have
    begun at or before an element and ``in_long [B]`` whether it lies in
    one: what :func:`_summed_runs` chains. From a comparison with the
    element that many places on, one cumulative maximum, one cumulative
    sum and a third sort, of the indices alone. With ``pad_to`` the
    per-element arrays are lengthened to that many by elements to drop."""
    B, W = rows.shape
    T = ops.SUM_RUNS_TREE_MAX_RUN
    H = max(B, pad_to) // (T + 1) + 1
    pos = jnp.arange(B, dtype=jnp.int32)
    s, order = lax.sort((jnp.minimum(idx, drop), pos), num_keys=2,
                        is_stable=False)  # the keys are distinct
    in_order = jnp.take(rows, order, axis=0)
    first, _ = _run_ends(s)
    kept = s < drop
    pushed = jnp.sum(kept.astype(jnp.int32))
    live = jnp.sum((first & kept).astype(jnp.int32))

    def long_runs():
        # A run is long where its first element finds its own index T
        # places on; the flag rides the run's start through a cumulative
        # maximum (starts increase) to every element of the run.
        long_first = first & kept & (s == jnp.concatenate(
            [s[T:], jnp.full((min(T, B),), -1, s.dtype)])[:B])
        in_long = lax.cummax(jnp.where(
            first, 2 * pos + long_first.astype(jnp.int32), 0)) % 2 == 1
        (ids,) = lax.sort((jnp.where(long_first, s, drop),),
                          is_stable=False)
        return jnp.cumsum(long_first.astype(jnp.int32)), in_long, ids[:H]

    begun, in_long, long_ids = lax.cond(
        _repeats(pushed, live), long_runs,
        lambda: (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
                 jnp.full((H,), drop, s.dtype)))
    if pad_to > B:
        n = pad_to - B
        s = jnp.concatenate([s, jnp.full((n,), drop, s.dtype)])
        in_order = jnp.concatenate([in_order, jnp.zeros((n, W), rows.dtype)])
        begun = jnp.concatenate([begun, jnp.full((n,), H, jnp.int32)])
        in_long = jnp.concatenate([in_long, jnp.zeros((n,), bool)])
    return s, in_order, begun, in_long, long_ids, pushed, live


def _repeats(pushed: Array, live: Array) -> Array:
    """Does a batch of ``pushed`` kept rows on ``live`` distinct indices
    repeat itself enough for its sums to pay
    (:data:`fps_tpu.ops.SUM_RUNS_MAX_DISTINCT_SHARE`)?"""
    return live <= ops.SUM_RUNS_MAX_DISTINCT_SHARE * pushed


def _summed_runs(s: Array, in_order: Array, begun: Array, in_long: Array,
                 long_rows: Array, pushed: Array, live: Array,
                 drop: int) -> tuple[Array, Array]:
    """The second half of ``push.sum_runs``: ``(ids [B], sums [B, W])``
    for the scatter into the shard, ``ids`` non-decreasing with everything
    to drop last (what :func:`fps_tpu.ops.scatter_add` calls
    ``ids_sorted``), from :func:`_sorted_runs`' result and ``long_rows [H,
    W]``, the shard's rows at ``long_ids`` as they stand. The TABLE is in
    neither branch: both return arrays of the payload's size.

    Where the batch repeats itself, each distinct index once beside ONE
    row to add. A run of up to :data:`fps_tpu.ops.SUM_RUNS_TREE_MAX_RUN`
    rows gives their sum (:func:`_run_sums` over the transposed rows, ``[W,
    B]``: lane-dense passes, the addends in a tree among themselves). A
    LONG run gives what adding its rows ONE BY ONE to the shard's row, in
    the batch's order, changes that row by: the rows are scatter-added
    into ``long_rows`` (a small buffer in XLA's VMEM regime, a few ns a
    row handed; every other element rides along as a zero row on the next
    long run's slot, so the slots are sorted) and ``long_rows`` is taken
    off again. That is the plain scatter's own arithmetic, a rounding an
    addend at the ROW's magnitude: a tree is nearer the exact sum, but
    what a plain float32 reference computes is the chain, and on a row
    that takes thousands of small addends a step the two differ by the
    chain's own rounding (``dlrm-criteo``'s 3-row field: 1.2e-2 of its
    change in 65 steps, chip run, PR 49). A second sort of ``(index on a
    run's last element or drop, position)`` brings the distinct indices to
    the front, and their rows are fetched from where that sort says, a
    block of the sorted route's at a time and only the blocks that hold a
    live index; the rows past them are zeros beside ``drop``. Where the
    batch hardly repeats itself the sums would cost more than the scatter
    saves, and the sorted batch is handed on as it is, repeats adjacent
    and in the batch's order."""
    B, W = in_order.shape
    H = long_rows.shape[0]
    C = min(ops.XLA_SORTED_BLOCK_IDS, B)
    pos = jnp.arange(B, dtype=jnp.int32)

    def summed():
        first, last = _run_ends(s)
        chained = long_rows.at[begun - in_long.astype(jnp.int32)].add(
            jnp.where(in_long[:, None], in_order, 0), mode="drop") - long_rows
        (totals,) = _run_sums(first, (in_order.T,))
        totals = totals.T
        slot = jnp.where(in_long, begun - 1, H)
        ids, at = lax.sort((jnp.where(last & (s < drop), s, drop), pos),
                           num_keys=1, is_stable=False)

        def fetch(c, sums):
            start = jnp.minimum(c * C, B - C)
            p = lax.dynamic_slice(at, (start,), (C,))
            h = jnp.take(slot, p)
            block = jnp.where(
                (h < H)[:, None],
                jnp.take(chained, jnp.minimum(h, H - 1), axis=0),
                jnp.take(totals, p, axis=0))
            return lax.dynamic_update_slice(sums, block, (start, 0))

        return ids, lax.fori_loop(0, (live + C - 1) // C, fetch,
                                  jnp.zeros((B, W), in_order.dtype))

    return lax.cond(_repeats(pushed, live), summed, lambda: (s, in_order))


def _mean_push_ratio(rps: int, dim: int, num_ids: int, dtype) -> float:
    """Bytes of a mean push's ``(rps, dim + 1)`` accumulator over bytes of
    its payload (``num_ids`` rows of ``dim``), both as XLA tiles them: the
    payload row-major, the accumulator in the SMALLER of its two forms (a
    temporary of narrow rows past XLA's VMEM regime is kept transposed, 64
    B a row at ``dim`` 10 where row-major takes 512). What
    :data:`fps_tpu.ops.MEAN_ROWS_TABLE_RATIO` is compared with."""
    acc = min(ops._tiled_table_bytes(rps, dim + 1, dtype),
              ops._tiled_table_bytes(-(-(dim + 1) // 8) * 8, rps, dtype))
    return acc / ops._tiled_table_bytes(num_ids, dim, dtype)


def _mean_push_route(rps: int, dim: int, dt, num_ids: int,
                     apply_fn) -> tuple[str, str]:
    """Which branch a ``"mean"`` push takes, from the shapes its exchange
    scatters (``num_ids`` rows of ``dim`` into ``rps`` rows of the table's
    dtype ``dt``): ``("mean_rows", "")`` (normalise the pushed rows,
    scatter them into the table once) or ``("mean_dense", reason)`` (the
    ``(rows, dim + 1)`` accumulator). ``reason``: ``"fold"`` (a
    non-additive ``apply_fn`` must see the combined delta once per id),
    ``"dtype"`` (the table is narrower than the accumulate dtype: a bf16
    table sums its duplicates in f32 and rounds once, a scatter into it
    would sum in bf16) or ``"small_table"`` (the accumulator's passes cost
    less than counting and summing the pushes of every id apart from the
    scatter: :data:`fps_tpu.ops.MEAN_ROWS_TABLE_RATIO`)."""
    if apply_fn is not None:
        return "mean_dense", "fold"
    if jnp.promote_types(dt, jnp.float32) != dt:
        return "mean_dense", "dtype"
    if _mean_push_ratio(rps, dim, num_ids, dt) < ops.MEAN_ROWS_TABLE_RATIO:
        return "mean_dense", "small_table"
    return "mean_rows", ""


def _fold_push_route(rps: int, dim: int, dt, num_ids: int) -> tuple[str, str]:
    """Which body a push with the table's own stateful fold
    (``ServerLogic.fold``) takes, from the shapes its exchange scatters, as
    :func:`_mean_push_route` chooses for a mean: ``("fold_rows", "")``,
    the SPARSE body (the pushed rows summed by id in a payload-sized
    buffer, the distinct ids' state gathered, the fold applied on those
    rows, the table's rows added to and the state's rows written, nothing
    table-sized anywhere), or ``("fold", "small_table")``, the ``(rows,
    dim + 1)`` accumulator and the fold over the whole shard, whose
    passes cost less than the sparse body's sorts and row operations
    while the table is small against the payload
    (:data:`fps_tpu.ops.MEAN_ROWS_TABLE_RATIO`, the mean's edge: the
    fold's own is not measured)."""
    if _mean_push_ratio(rps, dim, num_ids, dt) < ops.MEAN_ROWS_TABLE_RATIO:
        return "fold", "small_table"
    return "fold_rows", ""


# Device scope of what the sparse body of a table's own stateful fold
# (``push.fold_rows``) does AFTER the pushed rows are summed by id (that
# is ``fps.combine``'s, as on ``push.mean_rows``): the gather of the
# distinct ids' state, the fold on those rows, the add to the table's rows
# and the write of the state's, all payload-sized; the routed row
# operations keep their ``fps.ops/<route>`` names INSIDE it.
FOLD_ROWS_SCOPE = "fps.fold_rows"


def _gathered_exchange(ids: Array, deltas: Array, *, rps: int,
                       num_shards: int, shard_axis: str,
                       data_axis: str | None) -> tuple[Array, Array, Array]:
    """The gathered exchange of a push: every shard sees EVERY worker's
    ids and deltas (all-gathered over the data axis, then the shard axis)
    and keeps the rows it owns. Returns ``(local_idx, gathered_deltas,
    owned)``: the shard-local row of each gathered push, ``rps`` (out of
    range: dropped by the scatter) for one this shard does not own or the
    worker dropped; the deltas as gathered; the mask of the owned."""
    if data_axis is not None:
        ids = lax.all_gather(ids, data_axis, tiled=True)
        deltas = lax.all_gather(deltas, data_axis, tiled=True)
    ids = lax.all_gather(ids, shard_axis, tiled=True)
    deltas = lax.all_gather(deltas, shard_axis, tiled=True)
    me = lax.axis_index(shard_axis)
    owned = ((ids % num_shards) == me) & (ids >= 0)
    return jnp.where(owned, ids // num_shards, rps), deltas, owned


def _routed_exchange(lanes: _Lanes, deltas: Array, *, rps: int,
                     num_shards: int,
                     shard_axis: str) -> tuple[Array, Array, Array]:
    """The owner-routed exchange of a push: a worker's rows follow its ids
    into the lanes (one gather by the lanes' batch positions, padding
    slots zero rows), lane ``d`` goes to shard ``d``, and a shard is handed
    ``S x L`` pushes, all of them its own, by worker and then by the
    batch's order: :func:`_gathered_exchange`'s ``(local_idx, deltas,
    owned)`` without the rows of other shards (and with padding where a
    lane was not full: ``rps``, dropped by the scatter)."""
    dim = deltas.shape[1]
    ids = _trade(lanes.ids, shard_axis).reshape(-1)
    rows = _trade(_rows_at(deltas, lanes.src).reshape(num_shards, -1, dim),
                  shard_axis).reshape(-1, dim)
    owned = ids >= 0
    return jnp.where(owned, ids // num_shards, rps), rows, owned


def _dense_exchange(buf: Array, *, num_shards: int, shard_axis: str,
                    data_axis: str | None) -> Array:
    """The dense exchange of a push: from ``buf [num_shards * rps, W]``,
    this worker's OWN pushes scatter-added into a zeroed buffer of all the
    table's rows in physical (owner-major) layout, to ``[rps, W]``, this
    shard's rows summed over every worker. ``O(B)`` row transactions a
    worker where the gathered exchange pays ``O(W * B)`` a shard, at the
    price of table-sized collectives: for small tables.

    NOTE deliberate collective choice: all_to_all / all_gather move
    position-indexed data (order-insensitive), and the cross-worker sums
    run as FIXED-ORDER in-program reductions — a psum / psum_scatter here
    would delegate the float reduction order to the backend topology and
    break the tested bit-identity of 1-process vs multi-process runs on
    the same mesh (tests/test_multiprocess.py)."""
    if num_shards > 1:
        # Route each shard's window of my contributions to its owner:
        # every shard receives (S, rps, W) — all workers' sums for ITS
        # rows — and folds them in shard-index order.
        buf = jnp.sum(lax.all_to_all(
            buf.reshape(num_shards, -1, buf.shape[1]), shard_axis,
            split_axis=0, concat_axis=0, tiled=False), axis=0)
    if data_axis is not None:
        buf = jnp.sum(lax.all_gather(buf, data_axis), axis=0)
    return buf


def pull_local(
    local_shard: Array,
    ids: Array,
    *,
    num_shards: int,
) -> Array:
    """Gather rows the calling device already owns (no communication).

    For worker-local tables (e.g. MF user factors, reference
    ``.../matrix/factorization/`` keeps user vectors in worker state): the
    ingest layer routes examples so that ``ids % num_shards`` equals the
    worker index, making every lookup local.
    """
    return ops.gather_rows(local_shard, ids // num_shards)


def push_local(
    local_shard: Array,
    ids: Array,
    deltas: Array,
    *,
    num_shards: int,
) -> Array:
    """Scatter-add into rows the calling device already owns (no
    communication): the twin of :func:`pull_local`, for the worker-local
    table's own updates. Duplicate ids accumulate; ids outside the shard
    (negative ones too: floor division keeps them negative) are dropped."""
    return ops.scatter_add(local_shard, ids // num_shards, deltas)


def push(
    local_shard: Array,
    ids: Array,
    deltas: Array,
    *,
    num_shards: int,
    shard_axis: str = SHARD_AXIS,
    data_axis: str | None = DATA_AXIS,
    apply_fn: Callable[[Array, Array], Array] | None = None,
    combine: str | Callable[[Array, Array], Array] = "sum",
    hot_rows: int = 0,
    dense: bool = False,
    head_prefix: int = 0,
    table: str = "",
    fold=None,
    fold_state: Array | None = None,
) -> Array | tuple[Array, Array]:
    """Scatter-add ``deltas`` for ``ids`` into the sharded table.

    Args:
      local_shard: this device's ``(rps, dim)`` block.
      ids: ``(B,)`` ids this worker is pushing to. **Negative ids are
        dropped entirely** — use ``-1`` for padding rows so that even
        non-additive ``apply_fn`` folds never see them.
      deltas: ``(B, dim)`` deltas.
      data_axis: if the mesh has a replicated data axis, deltas are gathered
        across it too so all replicas stay bit-identical.
      apply_fn: fold function ``(current_rows, summed_delta) -> new_rows``;
        defaults to addition (the reference's ``paramUpdate = _ + _``,
        ``SimplePSLogic``). Non-additive folds see the batch-combined delta
        once per id and are applied only to rows with at least one
        non-dropped push: an id's pushes and their count are summed in a
        zeroed ``(rps, dim + 1)`` accumulator by ONE scatter-add (a ones
        column rides it), ``apply_fn`` runs over the whole shard and a
        ``where`` by ``count > 0`` keeps every other row bit for bit. Where
        that accumulator is one XLA keeps transposed, under enough ids a
        row (:func:`_acc_runs_route`: ``push.acc_runs`` in the route log), the
        pushed rows are first summed by id run from sorts of the batch
        (:func:`_sum_id_runs`), and the scatter is handed each distinct id
        once, sorted, and nothing after the last: its cost follows the
        distinct ids, not the pushes. The same for a callable ``combine``
        and a mean push that keeps its accumulator.
      combine: how duplicate ids within one push combine — the analog of
        the reference's pluggable combining senders (user-supplied
        ``CombinationLogic``, expected upstream ``.../ps/client/sender/``):
        * ``"sum"`` — every message folds in (reference semantics). With
          the additive fold the pushes are scatter-added into the shard
          itself; where the shard is one XLA keeps transposed in HBM
          (:func:`_sum_runs_route`: ``push.sum_runs`` in the route log)
          and the batch is seen to repeat its ids, the rows of one id are
          first summed (:func:`_summed_runs`) and the
          scatter is handed each distinct id once, sorted, and nothing
          after the last: every push still folds in, the same float32
          addends in another order, and the cost follows the distinct
          ids;
        * ``"mean"`` — per-id average: one averaged step per touched row
          per push, stable for Zipfian-hot ids under large batches. Two
          branches, chosen from the arguments' shapes and logged in the
          route log (:func:`_mean_push_route`): with the additive fold,
          on a table of the accumulate dtype that is large against the
          payload (:data:`fps_tpu.ops.MEAN_ROWS_TABLE_RATIO`), the pushed
          rows are scaled by ``1 / n`` (``n`` the id's count over the
          gathered, owned, non-negative pushes), summed by id from zero,
          ``sum_i(d_i * (1/n))``, and scatter-added into the table once,
          one add a touched row: the cost follows the payload
          (``push.mean_rows``). Otherwise sums and counts ride one
          scatter into a ``(rps, dim + 1)`` accumulator that is
          normalised and added to the shard, ``sum_i(d_i) * (1/n)``:
          table-sized passes whatever the batch (``push.mean_dense``);
        * ``"max"`` / ``"min"`` — elementwise extremum of the id's deltas
          (a native scatter-max/min, no serial fold);
        * a callable ``(summed, counts) -> combined`` mapping each
          shard-local row's per-id delta SUM ``(rps, dim)`` and push
          COUNT ``(rps,)`` to the combined delta — the general
          user-extensible strategy (count-normalized steps, clipping,
          learning-rate-by-frequency, ...). Untouched rows (count 0) are
          masked out after the callable, so it need not special-case
          them.
      hot_rows, head_prefix: the ingest layer's guarantee that
        ``ids[:head_prefix]`` lie in the LOCAL leading ``hot_rows`` rows
        (see :func:`fps_tpu.ops.scatter_add`); under the owner-major
        cyclic layout, global head ids ``[0, H)`` land exactly in local
        rows ``[0, ceil(H / num_shards))`` on every shard. ``hot_rows``
        without ``head_prefix`` changes nothing.
      dense: the DENSE EXCHANGE for SMALL tables (the driver's decision
        from the table's bytes, ``TableSpec.dense_collectives`` against
        :data:`fps_tpu.ops.DENSE_TABLE_BYTES`): each worker scatters its
        OWN ``B`` rows into a zeroed buffer of all the table's rows
        (physical layout); an ``all_to_all`` of per-shard windows plus
        fixed-order in-program sums (:func:`_dense_exchange` — deliberately
        NOT psum/psum_scatter) deliver every shard its summed slice —
        ``O(B)`` row transactions per worker instead of the gathered
        exchange's ``O(W * B)`` per shard (every shard scatters every
        worker's rows and drops the unowned by index), at the price of
        table-sized collectives. The additive fold exchanges the deltas'
        sums (``(rps, dim)``); every push that keeps the ``(rps, dim + 1)``
        accumulator (a ``"mean"`` on ``push.mean_dense``, a callable
        ``combine``, any ``apply_fn``) exchanges the accumulator itself,
        the ones column riding the rows (``push.dense_acc`` in the route
        log): the same sums and the same exact counts, the float additions
        per worker first and then over the shards in index order. Two
        pushes keep the gathered exchange under ``dense=True``:
        ``"max"`` / ``"min"`` (no sum to exchange) and a mean push whose
        table is large against the worker's own payload
        (``push.mean_rows``, asked about the ``B`` ids and
        ``num_shards * rps`` rows the dense exchange would scatter). On
        one device nothing is exchanged and ``dense`` changes nothing.
      table: the table's name, for the route log and the step's
        ``routed`` flag (:func:`watch_routed`); nothing else reads it.
      fold, fold_state: the table's own stateful fold
        (:class:`fps_tpu.core.api.HotFold`, ``ServerLogic.fold``) and this
        shard's ``(rps, fold.state_cols(dim))`` block of its state; the
        push then returns ``(new block, new state)``. Under ``"sum"``
        with no ``apply_fn``: every id pushed takes ONE
        :func:`apply_hot_fold` step on the sum of its pushes over every
        worker, a row nobody pushed keeps its value and its state bit for
        bit. Two bodies, chosen from the shapes (:func:`_fold_push_route`)
        and logged: ``push.fold_rows``, the sparse one: the handed rows
        summed by id from zero in a ``(B, dim)`` buffer (:func:`_id_runs`,
        under ``fps.combine`` as on ``push.mean_rows``), then under
        ``fps.fold_rows`` the distinct ids' state rows gathered, the fold
        on those ``B`` rows, the steps scatter-added into the table and
        the new state rows written (:func:`fps_tpu.ops.scatter_set`),
        both told the ids are sorted and the dropped last; or
        ``push.fold`` (reason ``small_table``), the accumulator body
        with :func:`apply_hot_fold` over the whole shard, which the dense
        exchange may fill. No conditional takes the table or the state.

    Every push that is not ``dense``, over more than one shard with no
    data axis, takes the OWNER-ROUTED exchange (:func:`_routes_to_owner`;
    ``push.routed`` in the route log): ids and rows go to their owners in
    lanes (:func:`_routed_exchange`), and everything after the exchange
    (the additive scatter, a mean's branch asked about the rows it is
    handed, ``push.acc_runs``, a fold, max / min) runs on ``S x L`` handed
    pushes where the gathered exchange hands ``S x B``: a row's pushes in
    the same order, worker by worker and then as the batch has them. A
    step whose ids do not fit their lanes runs the gathered exchange
    (``lax.cond`` on the lanes' certificate), so no push is ever dropped.

    Returns:
      Updated ``(rps, dim)`` local block.
    """
    if not callable(combine) and combine not in ("sum", "mean", "max", "min"):
        raise ValueError(f"unknown combine mode {combine!r}")
    if fold is not None and (apply_fn is not None or combine != "sum"):
        raise ValueError("a table's own fold sums an id's pushes "
                         "(combine='sum') and takes no apply_fn")
    rps, dim = local_shard.shape
    B = ids.shape[0]
    additive = apply_fn is None and combine == "sum" and fold is None
    # Accumulate in at least f32, but never BELOW the table's own precision:
    # a float64 table must fold its duplicates in float64 (hard-coding f32
    # here would silently shave 29 mantissa bits off every non-"sum" push).
    acc_dt = jnp.promote_types(local_shard.dtype, jnp.float32)
    # Which exchange fills the buffer the scatter writes: the dense one
    # (the worker's OWN pushes into all the table's rows, then the shards'
    # windows to their owners) where the driver says the table is small
    # and there is somebody to exchange with; max / min (no sum to
    # exchange) and the mean's row branch (asked about what the dense
    # exchange would scatter: B ids into all the rows) keep the gathered
    # one, which on one device is no collective at all.
    workers = num_shards * (
        1 if data_axis is None else lax.axis_size(data_axis))
    dense = (dense and workers > 1 and combine not in ("max", "min")
             and (combine != "mean" or _mean_push_route(
                 rps * num_shards, dim, local_shard.dtype, B, apply_fn)[0]
                 == "mean_dense")
             and (fold is None or _fold_push_route(
                 rps * num_shards, dim, local_shard.dtype, B)[0] == "fold"))

    def summed(local_idx, rows, live, acc_rows, asked, *, raw=None,
               owned=None, pad_to=0):
        """The first half of a non-additive push, from what its exchange
        handed this shard: row indices ``local_idx`` into ``acc_rows``
        rows (that many for a push to drop), the pushes ``rows`` in the
        accumulate dtype with the dropped ones zeroed, ``live`` (1.0 a
        push that counts), ``raw`` and ``owned`` (the pushes as handed and
        the mask of the kept) for max / min. Returns what :func:`folded`
        applies to the shard, which does not depend on how many pushes
        were handed: the ``(rows, dim + 1)`` accumulator (sums and counts,
        or extrema and the touched indicator), or on the mean's row branch
        the distinct ids sorted and their summed rows, with ``pad_to``
        lengthened by dropped ids over zero rows to that many. ``asked``:
        the handed pushes the mean's branch is chosen for."""
        B = local_idx.shape[0]
        if combine == "mean" or fold is not None:
            route, reason = (
                _fold_push_route(acc_rows, dim, local_shard.dtype, asked)
                if fold is not None else _mean_push_route(
                    acc_rows, dim, local_shard.dtype, asked, apply_fn))
            ops.log_route("push", route, acc_rows, dim, B, reason)
            if route in ("mean_rows", "fold_rows"):
                # The cost follows the payload: every pushed row is scaled
                # by 1 / (pushes of its id) and the rows of one id are
                # summed FROM ZERO in a (B, dim) buffer,
                # sum_i(d_i * (1/n)); then ONE scatter-add of the buffer's
                # rows into the table itself, one add a touched row, as
                # "sum" does. Scattering the scaled rows straight into the
                # table rounds each of a hot id's hundreds of small
                # addends at the TABLE value's magnitude: 12-20x the
                # accumulator's gap to a float64 mean (chip run, PR 28).
                # The fold's sparse body sums the same way, unscaled, and
                # hands on how many pushes it kept (its counter's base).
                with jax.named_scope(COMBINE_SCOPE):
                    n, slot, slot_idx = _id_runs(local_idx, rps)
                    scaled = rows if fold is not None else rows * (
                        1.0 / n.astype(acc_dt))[:, None]
                    combined = jnp.zeros((B, dim), acc_dt).at[slot].add(
                        scaled)
                    if pad_to > B:
                        slot_idx = jnp.concatenate([slot_idx, jnp.full(
                            (pad_to - B,), rps, slot_idx.dtype)])
                        combined = jnp.concatenate([combined, jnp.zeros(
                            (pad_to - B, dim), acc_dt)])
                if fold is not None:
                    return slot_idx, combined, jnp.sum(live > 0,
                                                       dtype=jnp.int32)
                return slot_idx, combined
        if combine in ("max", "min"):
            # Extremum fold: ONE scatter-max/min of the raw deltas
            # (duplicates combine natively, no serialized pairwise fold)
            # with the touched indicator riding as an appended column
            # (owned rows contribute 1.0 vs the fill sentinel — same
            # one-scatter trick as the sum path's count column; the
            # scatter is per-row-transaction bound). Sentinel beyond any
            # representable delta IN THE ACCUMULATOR dtype — a hard-coded
            # f32-range constant would silently clamp f64 deltas of
            # magnitude > 3e38 to the sentinel.
            with jax.named_scope(COMBINE_SCOPE):  # its raw scatter too
                lim = jnp.finfo(acc_dt).max
                fill = jnp.asarray(-lim if combine == "max" else lim, acc_dt)
                ind = jnp.where(owned, 1.0, fill)[:, None]
                filled = jnp.where(
                    owned[:, None],
                    jnp.concatenate([raw.astype(acc_dt), ind], axis=1),
                    fill,
                )
                target = jnp.full((rps, dim + 1), fill, acc_dt)
                if combine == "max":
                    return (target.at[local_idx].max(filled, mode="drop"),)
                return (target.at[local_idx].min(filled, mode="drop"),)
        # Combine duplicate ids first, then apply once per touched row.
        # The per-id sums and counts ride ONE scatter (counts as an
        # appended ones column) — the scatter is per-row-transaction bound
        # on TPU, so a second scatter for counts would double its cost.
        if apply_fn is not None and combine != "mean":
            # A stateful fold under "sum" (or a callable combine): the
            # (rows, dim + 1) accumulator, apply_fn over the whole shard
            # and a table-sized where (a mean push logged its own branch,
            # and so did a table's own fold).
            ops.log_route("push", "fold", acc_rows, dim, B, "apply_fn")
        why = ("mean_dense" if combine == "mean" else "fold"
               if apply_fn is not None or fold is not None else "callable")
        if dense:
            # The accumulator is filled by the dense exchange: this
            # worker's own B rows into all the table's rows, then the
            # shards' windows to their owners (``rps`` rows of it stay).
            ops.log_route("push", "dense_acc", rps, dim, B, why)
        runs = _acc_runs_route(acc_rows, dim, B, acc_dt)
        if runs:
            # Into an accumulator XLA keeps transposed the scatter pays
            # for every id it is handed, and most of a large batch's ids
            # are repeats: the rows of one id are summed first (a ones
            # column to its count), and the scatter sees each distinct id
            # once, sorted, and the drop sentinel after the last of them.
            ops.log_route("push", "acc_runs", acc_rows, dim, B, why)
            with jax.named_scope(COMBINE_SCOPE):
                local_idx, withcnt = _sum_id_runs(local_idx, rows, acc_rows)
        else:
            withcnt = jnp.concatenate([rows, live[:, None]], axis=1)
        with jax.named_scope(COMBINE_SCOPE):
            # A zero the compiler cannot see through. As a broadcast of a
            # literal, XLA's TPU pipeline re-made the fill under the loop
            # body's name and it lost this scope: 1.7 GB a table a step
            # written under no name at 1.1 M x 301 (chip run, PR 27;
            # tests/test_v5e_compile.py reads the compiled text).
            zeros = jnp.broadcast_to(
                lax.optimization_barrier(jnp.zeros((), acc_dt)),
                (acc_rows, dim + 1))
        return (ops.scatter_add(zeros, local_idx, withcnt, ids_sorted=runs),)

    def folded(summed, exchange=None):
        """The second half: :func:`summed`'s result applied to the shard
        (``exchange`` where the dense one still has the accumulator to
        trade)."""
        if len(summed) == 3:
            # ``push.fold_rows``: the distinct ids sorted, their pushes'
            # sums beside them, the drop sentinel over zero rows after the
            # last; state and table are read and written at those ids.
            slot_idx, combined, kept = summed
            with jax.named_scope(FOLD_ROWS_SCOPE):
                touched = slot_idx < rps
                _note_counts(_FOLD_ROWS_WATCHES, table, handed_ids=kept,
                             folded_ids=jnp.sum(touched, dtype=jnp.int32))
                step, rows = apply_hot_fold(
                    fold, ops.gather_rows(fold_state, slot_idx), combined,
                    touched.astype(acc_dt))
                return (ops.scatter_add(local_shard, slot_idx, step,
                                        ids_sorted=True),
                        ops.scatter_set(fold_state, slot_idx, rows,
                                        ids_sorted=True))
        if len(summed) == 2:
            # ``slot_idx`` is the distinct ids in their sorted order, the
            # rows of ``combined`` beside them, and past the last of them
            # nothing but the drop sentinel (the unowned run's slot, which
            # is the last, included) over rows of exact zeros: the order
            # the sort made is handed on, and a scatter-add that is told
            # so stops where the dropped begin.
            slot_idx, combined = summed
            return ops.scatter_add(local_shard, slot_idx, combined,
                                   ids_sorted=True)
        (acc,) = summed
        with jax.named_scope(COMBINE_SCOPE):
            if combine in ("max", "min"):
                counts = (jnp.abs(acc[:, dim]) <= 1.0).astype(acc_dt)
                combined = jnp.where((counts > 0)[:, None], acc[:, :dim],
                                     0.0)
            else:
                if exchange is not None:
                    acc = exchange(acc)
                combined, counts = acc[:, :dim], acc[:, dim]
                if combine == "mean":
                    combined = combined * (
                        1.0 / jnp.maximum(counts, 1.0))[:, None]
                elif callable(combine):
                    combined = jnp.where(
                        (counts > 0)[:, None], combine(combined, counts), 0.0
                    )
        with jax.named_scope(COMBINE_SCOPE):
            if fold is not None:
                # The table's own fold over the whole shard: an untouched
                # row takes a zero step and keeps its state.
                step, state = apply_hot_fold(fold, fold_state, combined,
                                             counts)
                return local_shard + step.astype(local_shard.dtype), state
            if apply_fn is None:
                # Additive fold: untouched rows receive exactly zero, so
                # no mask is needed (a full-table where() is a measurable
                # per-step cost).
                return local_shard + combined.astype(local_shard.dtype)
            new_rows = apply_fn(local_shard,
                                combined.astype(local_shard.dtype))
            return jnp.where((counts > 0)[:, None], new_rows, local_shard)

    if dense:
        exchange = partial(_dense_exchange, num_shards=num_shards,
                           shard_axis=shard_axis, data_axis=data_axis)
        # Physical (owner-major) rows; a dropped id past the last of them
        # (the end a sort puts it at: ``push.acc_runs``).
        acc_rows = rps * num_shards
        local_idx = jnp.where(
            ids >= 0, id_to_phys(ids, num_shards, rps), acc_rows)
        if additive:
            return local_shard + exchange(ops.scatter_add(
                jnp.zeros((acc_rows, dim), local_shard.dtype), local_idx,
                deltas))
        return folded(summed(local_idx, deltas.astype(acc_dt),
                             jnp.ones((B,), acc_dt), acc_rows, B), exchange)

    def handed(local_idx, handed_deltas, owned, asked=0, pad_to=0):
        """The push, or its first half, from what the gathered or the
        owner-routed exchange hands this shard: the additive scatter into
        the shard itself (where the rows are summed by id first,
        ``push.sum_runs``, what :func:`finished` scatters), or
        :func:`summed`'s result."""
        masked = jnp.where(owned[:, None], handed_deltas,
                           jnp.zeros_like(handed_deltas))
        if sum_runs:
            # Into a table XLA keeps transposed in HBM the scatter pays
            # for every id it is handed, and most of a skewed batch's ids
            # are repeats: the rows of one id are summed first, the same
            # float32 addends in a tree among themselves, and reach the
            # table in ONE add, beside the distinct ids sorted and the
            # drop sentinel after the last.
            ops.log_route("push", "sum_runs", rps, dim,
                          local_idx.shape[0], "xla_transposed_hbm")
            with jax.named_scope(COMBINE_SCOPE):
                return _sorted_runs(local_idx,
                                    masked.astype(local_shard.dtype), rps,
                                    pad_to)
        if additive:
            # Head-prefix guarantee survives only when the gathered stream
            # is the caller's own (single shard, no data axis — the driver
            # also gates it to single-device meshes).
            keep_prefix = (num_shards == 1 and data_axis is None)
            return ops.scatter_add(
                local_shard, local_idx, masked, hot_rows=hot_rows,
                head_prefix=head_prefix if keep_prefix else 0)
        return summed(local_idx, masked.astype(acc_dt), owned.astype(acc_dt),
                      rps, asked or local_idx.shape[0], raw=handed_deltas,
                      owned=owned, pad_to=pad_to)

    def finished(out):
        """The push from :func:`handed`'s result: the additive scatter's
        table as it is; the sorted runs summed and scattered into the
        shard, outside every conditional, its counts noted for the step;
        :func:`summed`'s result folded."""
        if sum_runs:
            *runs, long_ids, pushed, live = out
            _note_counts(_SUM_RUNS_WATCHES, table, pushed_ids=pushed,
                         live_ids=live)
            with jax.named_scope(COMBINE_SCOPE):
                # The long runs' rows as they stand (a buffer's worth; ids
                # past the last are clipped and their rows unused).
                long_rows = jnp.take(
                    local_shard, jnp.minimum(long_ids, rps - 1), axis=0)
                slot_idx, sums = _summed_runs(*runs, long_rows, pushed,
                                              live, rps)
            return ops.scatter_add(local_shard, slot_idx, sums,
                                   ids_sorted=True)
        return out if additive else folded(out)

    gathered = partial(_gathered_exchange, ids, deltas, rps=rps,
                       num_shards=num_shards, shard_axis=shard_axis,
                       data_axis=data_axis)
    lanes = _lanes_of_call("push", local_shard, ids, num_shards=num_shards,
                           shard_axis=shard_axis, data_axis=data_axis,
                           table=table)
    # The additive push's own regime, asked about the pushes the exchange
    # hands this shard (the lanes' S x L where it is the owner-routed one,
    # as the mean's branch is).
    sum_runs = additive and _sum_runs_route(
        rps, dim, workers * B if lanes is None else lanes.src.shape[0],
        local_shard.dtype)
    if lanes is None:
        return finished(handed(*gathered()))
    # Both branches hand the second half the same shapes, so the shard
    # itself stays out of the conditional (but for the additive scatter
    # where the rows are not summed first): the accumulator is the shard's
    # size whatever was handed; the row branch's sorted ids (the mean's,
    # and ``push.sum_runs``') are lengthened to the gathered exchange's,
    # which a scatter that stops at the first dropped id does not pay for;
    # and a step that falls back takes the branch the lanes' S x L pushes
    # chose.
    span = lanes.src.shape[0]
    out = lax.cond(
        lanes.fits,
        lambda: handed(*_routed_exchange(
            lanes, deltas, rps=rps, num_shards=num_shards,
            shard_axis=shard_axis), span, num_shards * B),
        lambda: handed(*gathered(), span))
    return finished(out)


# ---------------------------------------------------------------------------
# Table spec + host-side store container.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TableSpec:
    """Declaration of one parameter table (one sharded ``id -> vector`` map).

    ``init_fn(key, ids) -> (len(ids), dim) values`` must be deterministic in
    ``ids`` — the reference seeds its factor initializers with the parameter
    id so that initialization is reproducible regardless of which shard
    first touches an id (expected upstream
    ``.../matrix/factorization/factors/``); we keep that contract.
    """

    name: str
    num_ids: int
    dim: int
    init_fn: Callable[[Array, Array], Array] = None  # (key, ids) -> values
    dtype: Any = jnp.float32
    # The table's certified frequency head: an int H > 0 declares that
    # the leading H GLOBAL ids are the hottest (the shipped loaders and
    # synthetic generators lay ids out that way), which is what a worker's
    # ``head_prefix`` guarantee refers to (``WorkerLogic.head_prefix``:
    # the batch's leading ids lie in ``[0, H) ∪ {-1}``, and ride a
    # head-only kernel — :func:`fps_tpu.ops.scatter_add`). Without such a
    # guarantee H selects nothing. Default 0: no head declared.
    hot_ids: int = 0
    # Dense collective route (replicate-on-read / dense-reduce-on-write,
    # :func:`pull`/:func:`push` ``dense=``): per-worker row transactions
    # drop from the gathered route's O(W * B) per shard to O(B), at the
    # price of table-sized collectives per step — the right trade exactly
    # when the table is small (PA/logreg weight vectors, MF item factors).
    #   * "auto" — on multi-device meshes, dense whenever the padded table
    #     is at most :data:`fps_tpu.ops.DENSE_TABLE_BYTES`; single-device
    #     meshes always take the (collective-free) gathered route.
    #   * True / False — force. Forcing True on an embedding-scale table
    #     turns every step into a full-table broadcast; measure first.
    # The additive fold and every push that keeps the (rows, dim + 1)
    # accumulator (a small table's mean, a callable combine, an apply_fn)
    # take the dense write path; "max" / "min" and a mean push on the row
    # branch keep gathered writes (reads may still go dense).
    dense_collectives: bool | str = "auto"
    # Two-tier hot storage (module docstring; docs/performance.md): an
    # int H > 0 replicates the leading H GLOBAL ids across the shard axis
    # beside the sharded table. Hot reads become local gathers with zero
    # collectives; hot pushes accumulate into a per-device delta buffer
    # reconciled by one psum every ``TrainerConfig.hot_sync_every`` steps
    # (bounded parameter-plane staleness). Meaningful when ids are
    # frequency-ranked (hottest first — the same head convention as
    # ``hot_ids``); H >= num_ids replicates the whole table (the NuPS
    # small-hot-table regime) and statically elides the collective
    # pull/push routes entirely. Engages only when the trainer resolves
    # it on: multi-device mesh, ``hot_sync_every > 1``, and an additive
    # ("sum") or "mean" server fold — otherwise (incl. the
    # ``hot_sync_every = 1`` exact mode) the untiered program is lowered
    # unchanged. Default 0: off.
    hot_tier: int = 0
    # Payload-proportional cold routing (docs/performance.md
    # "Payload-proportional routing"): with a PARTIAL hot head
    # (0 < H < num_ids), the cold routes otherwise keep the full-batch
    # static collective payload even at a 0.99 hit rate. A positive
    # ``cold_budget`` bounds the per-worker-per-step cold-id lane: each
    # batch's cold ids/deltas are compacted on device into a
    # ``(cold_budget,)`` stream before the collective pull/push, so the
    # gathered routes carry O(cold traffic) bytes instead of O(batch).
    # Ingest-certified like ``head_prefix``: the compacted program only
    # dispatches for chunks the host proved fit the budget
    # (``WorkerLogic.pulled_ids_host``); overflowing chunks fall back to
    # the static route bit-identically, with a
    # ``cold_route.overflow_chunks`` obs counter. Engages only when the
    # tier resolves on with a partial head on a non-dense route; 0 (the
    # default) keeps the static cold routes.
    cold_budget: int = 0

    def zeros_init(self) -> "TableSpec":
        return dataclasses.replace(
            self, init_fn=lambda key, ids: jnp.zeros((ids.shape[0], self.dim), self.dtype)
        )


def ranged_uniform_init(min_val: float, max_val: float, dim: int, dtype=jnp.float32):
    """Per-id seeded uniform init in ``[min_val, max_val)`` — mirrors the
    reference's ranged-random factor initializer (seeded by parameter id so
    initialization is reproducible across any shard count; expected upstream
    ``.../matrix/factorization/factors/``)."""

    def init(key: Array, ids: Array) -> Array:
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
        return jax.vmap(
            lambda k: jax.random.uniform(
                k, (dim,), jnp.float32, minval=min_val, maxval=max_val
            )
        )(keys).astype(dtype)

    return init


def _default_init(key: Array, ids: Array, dim: int, dtype) -> Array:
    return ranged_uniform_init(-0.01, 0.01, dim, dtype)(key, ids)


def make_table_values(
    key: Array,
    num_ids: int,
    dim: int,
    num_shards: int,
    init_fn: Callable[[Array, Array], Array] | None = None,
    dtype=jnp.float32,
) -> Array:
    """Build a full ``(rps*num_shards, dim)`` table in owner-major layout.

    Usable both for PS tables (sharded over the shard axis) and for
    worker-local tables (sharded over all worker devices, e.g. MF user
    factors). Initialization is per-id deterministic: padding rows and real
    rows alike get ``init_fn(fold_in(key, id))``-style values, so the result
    is identical regardless of shard count (matching the reference's
    id-seeded reproducible factor initializers).
    """
    rps = rows_per_shard(num_ids, num_shards)
    phys = jnp.arange(rps * num_shards, dtype=jnp.int32)
    ids = phys_to_id(phys, num_shards, rps)
    fn = init_fn or partial(_default_init, dim=dim, dtype=dtype)
    return fn(key, ids).astype(dtype)


class ParamStore:
    """Host-side container creating and tracking sharded parameter tables.

    The device-side compute never touches this class — it works on the pytree
    of arrays (``store.tables``) passed through the jitted step functions.
    """

    def __init__(self, mesh: Mesh, specs: Mapping[str, TableSpec] | list[TableSpec]):
        if not isinstance(specs, Mapping):
            specs = {s.name: s for s in specs}
        self.mesh = mesh
        self.specs = dict(specs)
        self.num_shards = mesh.shape[SHARD_AXIS]
        self.sharding = NamedSharding(mesh, P(SHARD_AXIS, None))
        self.tables: dict[str, Array] = {}
        # {table: state columns} of the tables whose ``::fold`` entry is
        # their OWN optimizer's state, laid out like the table (set by the
        # Trainer from ``ServerLogic.fold``; a snapshot saves those in
        # logical id order).
        self.row_folds: dict[str, int] = {}
        self._head_replica_fns: dict = {}  # (name, hot_rows) -> jitted gather
        self._rows_replica_fns: dict = {}  # (name, nrows) -> jitted gather

    def init(self, key: Array) -> dict[str, Array]:
        """Materialize all tables directly in their sharded layout."""
        for name, spec in self.specs.items():
            tkey = jax.random.fold_in(key, _stable_hash(name))
            make = partial(
                make_table_values,
                tkey,
                spec.num_ids,
                spec.dim,
                self.num_shards,
                spec.init_fn,
                spec.dtype,
            )
            self.tables[name] = jax.jit(make, out_shardings=self.sharding)()
        return self.tables

    def head_replica(self, name: str, hot_rows: int, table: Array | None = None) -> Array:
        """Replicated ``(hot_rows, dim)`` array of ``name``'s leading ids.

        The re-split half of the two-tier contract: derives the hot
        replica from the CANONICAL sharded table (valid at any compiled-
        call boundary — pending deltas are always reconciled before a
        call returns). Multi-controller: the replicating jit is a
        COLLECTIVE; every process reaches the run entry together, same
        as the checkpoint dump.
        """
        spec = self.specs[name]
        if not 0 < hot_rows <= spec.num_ids:
            raise ValueError(
                f"table {name!r}: hot_rows={hot_rows} outside "
                f"(0, {spec.num_ids}]"
            )
        table = self.tables[name] if table is None else table
        fn = self._head_replica_fns.get((name, hot_rows))
        if fn is None:
            # Cache the jitted gather per (table, head size): repeat
            # derivations (every restore / restart / warm-start) hit the
            # jit cache instead of re-tracing the same trivial program.
            rps = rows_per_shard(spec.num_ids, self.num_shards)
            phys = np.asarray(
                id_to_phys(np.arange(hot_rows, dtype=np.int64),
                           self.num_shards, rps)
            )
            fn = jax.jit(
                lambda t: t[phys],
                out_shardings=NamedSharding(self.mesh, P()),
            )
            self._head_replica_fns[(name, hot_rows)] = fn
        return fn(table)

    def rows_replica(self, name: str, ids: np.ndarray,
                     table: Array | None = None) -> Array:
        """Replicated ``(len(ids), dim)`` array of arbitrary global ids of
        ``name`` — the re-split half of the ADAPTIVE tier (the mapped
        analog of :meth:`head_replica`, whose head is always ``[0, H)``).

        The physical row indices ride as a jit ARGUMENT (not a baked
        constant), so every re-rank at the same head size H reuses one
        compiled gather — the no-recompile contract. Valid at any
        compiled-call boundary (pending deltas are always reconciled
        before a call returns). Multi-controller: collective, like
        :meth:`head_replica`.
        """
        spec = self.specs[name]
        ids = np.asarray(ids, np.int64)
        if ids.size == 0 or ids.min() < 0 or ids.max() >= spec.num_ids:
            raise ValueError(
                f"table {name!r}: replica ids must be a non-empty subset "
                f"of [0, {spec.num_ids})")
        table = self.tables[name] if table is None else table
        rps = rows_per_shard(spec.num_ids, self.num_shards)
        phys = np.asarray(id_to_phys(ids, self.num_shards, rps),
                          dtype=np.int32)
        fn = self._rows_replica_fns.get((name, len(ids)))
        if fn is None:
            fn = jax.jit(
                lambda t, p: t[p],
                out_shardings=NamedSharding(self.mesh, P()),
            )
            self._rows_replica_fns[(name, len(ids))] = fn
        return fn(table, phys)

    def table_specs_static(self) -> dict[str, tuple[int, int]]:
        """(num_shards, rows_per_shard) per table, for device-side code."""
        return {
            name: (self.num_shards, rows_per_shard(spec.num_ids, self.num_shards))
            for name, spec in self.specs.items()
        }

    def _host_table(self, name: str) -> np.ndarray:
        """Full table as numpy.

        Multi-controller: cross-host tables are first replicated through a
        jitted identity — a COLLECTIVE, so every process must make this
        call (via ``lookup_host``/``dump_model``/checkpoint save) together;
        a subset of processes calling alone blocks on the others' shards.
        """
        table = self.tables[name]
        if not table.sharding.is_fully_addressable:
            table = replicate_to_mesh(table, self.mesh)
        return np.asarray(table)

    def lookup_host(self, name: str, ids: np.ndarray) -> np.ndarray:
        """Host-side (numpy) read of current values — for eval / model dump.

        Replaces the reference's end-of-job model emission
        (``ParameterServerLogic.close`` → ``output((id, param))``).
        """
        spec = self.specs[name]
        rps = rows_per_shard(spec.num_ids, self.num_shards)
        table = self._host_table(name)
        phys = np.asarray(id_to_phys(np.asarray(ids), self.num_shards, rps))
        return table[phys]

    def dump_model(self, name: str) -> tuple[np.ndarray, np.ndarray]:
        """Return ``(ids, values)`` for the whole table (padding rows excluded)."""
        spec = self.specs[name]
        ids = np.arange(spec.num_ids)
        return ids, self.lookup_host(name, ids)


def _stable_hash(s: str) -> int:
    h = 0
    for c in s.encode():
        h = (h * 131 + c) % (2**31 - 1)
    return h
