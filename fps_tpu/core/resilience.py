"""Resilience layer: step-health guards, snapshot integrity, rollback.

A production PS serving live traffic must absorb two failure classes the
reference (and our own seed) could not:

* **poison updates** — one bad batch (corrupt ingest row, overflowed
  feature, adversarial input) pushes NaN/Inf or norm-exploded deltas;
  under the additive server fold a single such push irreversibly destroys
  every row it touches, and the damage then spreads through every pull.
* **torn snapshots** — a crash or disk fault mid-write (or bit rot at
  rest) leaves the newest ``.npz`` unreadable; a restore that can only
  try the latest file turns one bad snapshot into an unrecoverable job.

This module holds the policy objects and pure helpers; the wiring lives in
:mod:`fps_tpu.core.driver` (on-device guard + host-loop rollback) and
:mod:`fps_tpu.core.checkpoint` (per-array checksums + fallback restore).
Everything here is dependency-light (jax/numpy only) so both layers can
import it without cycles. Failure injection for tests lives in
:mod:`fps_tpu.testing.chaos`; the failure model is documented in
``docs/resilience.md``.

Design constraint: ``TrainerConfig.guard is None`` (the default) must
compile to the *identical* program as a guard-free build — every branch
below is resolved at trace time, so the health machinery costs nothing
when it is off (tested via compiled-HLO comparison in
``tests/test_resilience.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
Pytree = Any

HEALTH_KEY = "health"

# Health-channel entry name for the worker-LOCAL state plane (the guard's
# second coverage surface — GuardConfig.local). Lives next to the
# per-table entries; the driver rejects a store table with this name when
# the local guard is on, so the two planes can never collide.
LOCAL_STATE_KEY = "local_state"

# Out-channel entry name for the two-tier hot-storage telemetry
# (per-table hot/pulled row counts + pending-delta magnitude — the
# parameter-plane staleness gauge riding the health channel's transport).
# Mounted by the driver with the same dict-out-channel + collision
# contract as HEALTH_KEY; rollback snapshots taken under a hot tier
# carry the replica entries too (``tree_copy`` over the whole tables
# dict), so a quarantine restores replica, canonical table, and — by the
# flush-reconcile boundary invariant — an implicitly empty delta buffer
# as one consistent unit.
HOT_TIER_KEY = "hot_tier"

# Reserved prefix of the worker out channel's keys under which the additive
# pushes that summed their rows by id first (``push.sum_runs``,
# :func:`fps_tpu.core.store.push`) carry their per-step counts, as plain
# leaves ``sum_runs.<table>.pushed_ids`` / ``.live_ids``, summed over the
# workers. Mounted by the driver only where a push took the route.
SUM_RUNS_KEY = "sum_runs"
# Likewise the pushes that folded the touched rows alone
# (``push.fold_rows``, a table's own stateful fold): leaves
# ``fold_rows.<table>.handed_ids`` / ``.folded_ids``.
FOLD_ROWS_KEY = "fold_rows"
# Likewise the pulls that read each distinct row of a step once
# (``pull.distinct_rows``, :func:`fps_tpu.core.store.pull`): leaves
# ``distinct_pulls.<table>.pulled_ids`` / ``.live_ids``. A pull's ids are
# a data replica's OWN (a push's are every replica's), so every replica's
# counts are summed.
DISTINCT_PULLS_KEY = "distinct_pulls"
# The step-count channels, in the order the driver mounts and records them.
COUNT_KEYS = (SUM_RUNS_KEY, FOLD_ROWS_KEY, DISTINCT_PULLS_KEY)

GUARD_MODES = ("observe", "mask")


class SnapshotCorruptionError(RuntimeError):
    """A snapshot failed its integrity check (truncated, bit-flipped, or
    otherwise unreadable). Raised by the checkpoint layer when the caller
    pinned an explicit step; auto-resolved restores fall back to the
    previous surviving snapshot instead."""


class PoisonedStreamError(RuntimeError):
    """The host-loop rollback policy exhausted its quarantine budget —
    the input stream keeps producing poisoned chunks."""


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """On-device push-delta health guard (``TrainerConfig.guard``).

    Inside the compiled scan, every table's push deltas are screened per
    row *before* they reach the server fold:

    * rows with any non-finite element count as ``nonfinite``;
    * rows whose L2 norm exceeds ``norm_limit`` (when set) count as
      ``norm`` — the early-warning tier for divergence that is still
      finite;
    * in ``mode="mask"``, offending rows are dropped (id → ``-1``, delta
      → 0) so a poison batch degrades to a lost update instead of table
      death; ``mode="observe"`` only counts, leaving the stream
      byte-identical (pair it with a host-loop
      :class:`RollbackPolicy` to quarantine instead).

    The per-step, per-table counts ride the worker ``out`` channel as a
    ``"health"`` entry (psum'd across workers like every other metric), so
    surfacing them costs one int32 reduction per table per step — noise
    next to the pull/push collectives.

    Frozen (hashable): the guard is part of the trainer's compile-cache
    key, like ``push_delay`` and the ops backend.
    """

    mode: str = "mask"
    # Per-row L2 norm ceiling for push deltas; None disables the norm
    # tier (non-finite screening is always on while a guard is set).
    norm_limit: float | None = None
    # Restrict guarding to these tables (None = all). Tables outside the
    # set pass through untouched and report no health entry.
    tables: tuple[str, ...] | None = None
    # Extend screening to worker-LOCAL state updates (MF user factors,
    # any float leaf of the local_state pytree): after each step, rows
    # whose NEW value is non-finite — or whose update delta exceeds
    # ``norm_limit`` — are counted onto a ``"local_state"`` health entry
    # and, in mask mode, reverted to their pre-step values. Closes the
    # MF-style gap where mask mode screens PS pushes but the local
    # scatter still absorbs NaN. Off by default: ``local=False`` traces
    # the exact same program as before (the ``tables`` filter does not
    # apply — local state has no table name).
    local: bool = False

    def __post_init__(self):
        if self.mode not in GUARD_MODES:
            raise ValueError(
                f"guard mode {self.mode!r} — expected one of {GUARD_MODES}"
            )
        if self.norm_limit is not None and not self.norm_limit > 0:
            raise ValueError(f"norm_limit must be > 0, got {self.norm_limit}")
        if self.tables is not None:
            # Coerce here so a list fails at construction time, not as an
            # unhashable-type error deep in the trainer's compile cache.
            object.__setattr__(self, "tables", tuple(self.tables))


def as_guard(guard) -> GuardConfig | None:
    """Coerce ``TrainerConfig.guard`` (None | str | GuardConfig)."""
    if guard is None or isinstance(guard, GuardConfig):
        return guard
    if isinstance(guard, str):
        return GuardConfig(mode=guard)
    raise TypeError(
        f"guard must be None, 'observe'/'mask', or a GuardConfig; "
        f"got {type(guard).__name__}"
    )


def guard_pushes(
    pushes: Mapping[str, tuple[Array, Array]], guard: GuardConfig
) -> tuple[dict[str, tuple[Array, Array]], dict[str, dict[str, Array]]]:
    """Screen per-table ``(ids, deltas)`` pushes; trace-time static policy.

    Returns ``(guarded_pushes, health)`` where ``health[table]`` holds
    scalar int32 counts ``{"nonfinite", "norm", "masked"}`` for THIS
    worker's batch (the driver psums them into global per-step counts).
    Padding rows (id ``-1``) never count — they were already dropped.

    In mask mode both the id (→ ``-1``) and the delta (→ 0) of a bad row
    are cleared — and non-finite deltas are zeroed even on rows that were
    ALREADY padding (a poisoned batch value can propagate NaN into a
    weight-0 row's delta): the gathered/XLA routes drop dead rows by
    select, but the lane-packed MXU routes multiply every delta by its
    0/1 indicator, and ``0 * NaN`` would poison whole row tiles. Only
    live rows count toward health (the padding row's poison always has a
    live sibling in the same batch).
    """
    out_pushes: dict[str, tuple[Array, Array]] = {}
    health: dict[str, dict[str, Array]] = {}
    for name, (ids, deltas) in pushes.items():
        if guard.tables is not None and name not in guard.tables:
            out_pushes[name] = (ids, deltas)
            continue
        live = ids >= 0
        finite = jnp.all(jnp.isfinite(deltas), axis=-1)
        nonfinite = live & ~finite
        if guard.norm_limit is not None:
            # Compute the norm over zero-substituted rows so a NaN row
            # never double-counts (NaN comparisons are False anyway, but
            # keeping the operands finite is cheaper to reason about).
            sq = jnp.sum(
                jnp.where(finite[:, None], deltas, 0.0).astype(jnp.float32)
                ** 2,
                axis=-1,
            )
            exploded = live & finite & (sq > guard.norm_limit**2)
        else:
            exploded = jnp.zeros_like(nonfinite)
        bad = nonfinite | exploded
        counts = {
            "nonfinite": jnp.sum(nonfinite, dtype=jnp.int32),
            "norm": jnp.sum(exploded, dtype=jnp.int32),
        }
        if guard.mode == "mask":
            ids = jnp.where(bad, jnp.asarray(-1, ids.dtype), ids)
            scrub = bad | ~finite  # non-finite padding rows too (see above)
            deltas = jnp.where(
                scrub[:, None], 0.0, deltas
            ).astype(deltas.dtype)
            counts["masked"] = jnp.sum(bad, dtype=jnp.int32)
        else:
            counts["masked"] = jnp.zeros((), jnp.int32)
        out_pushes[name] = (ids, deltas)
        health[name] = counts
    return out_pushes, health


def guard_local_state(
    old: Pytree, new: Pytree, guard: GuardConfig, touched=None
) -> tuple[Pytree, dict[str, Array] | None]:
    """Screen a step's worker-LOCAL state update; trace-time static policy.

    The local plane has no ``(ids, deltas)`` stream to intercept — worker
    logics scatter into their local arrays directly inside ``step`` — so
    the guard screens the *effect*: for every inexact (float) leaf, a
    "row" is one index along axis 0 (the whole array for 0-d leaves), and

    * rows of ``new`` containing any non-finite element count as
      ``nonfinite``;
    * rows whose update delta ``new - old`` has L2 norm over
      ``guard.norm_limit`` (when set) count as ``norm``;
    * in ``mode="mask"`` offending rows REVERT to their pre-step values
      (the scatter update degrades to a lost update, mirroring the push
      guard's dropped rows); ``"observe"`` only counts.

    ``touched`` (from ``WorkerLogic.touched_local_rows``): one entry per
    flattened leaf — an int id array (``-1`` ignored) restricting that
    leaf's ROW screening (nonfinite + norm tiers, and mask-mode reverts)
    to the rows this step can actually write, or ``None`` for the
    full-leaf screen. Untouched rows are still covered by a LEAF-tier
    non-finite net: any non-finite row outside the touched set counts as
    ``nonfinite`` (it cannot be masked — its pre-step value IS its
    post-step value, so there is nothing to revert to), so a poisoned
    row can never hide outside the ids. Duplicate touched ids count per
    occurrence (the push guard's per-batch-row convention) and revert
    deterministically — every occurrence writes the same row value.

    Returns ``(guarded_new, counts)`` with the same scalar int32
    ``{"nonfinite", "norm", "masked"}`` schema as :func:`guard_pushes`
    (the driver mounts it under :data:`LOCAL_STATE_KEY`), or
    ``(new, None)`` when the pytree has no inexact leaves — an empty
    local state costs nothing and adds no health entry.

    Caveat: the delta-norm tier is computed against ``old``; if an
    earlier *observe*-mode step already let non-finite values into a row,
    that row's delta is non-finite and lands in the ``nonfinite`` tier
    (reverting cannot resurrect a row that was never finite).
    """
    old_leaves, treedef = jax.tree.flatten(old)
    new_leaves, new_treedef = jax.tree.flatten(new)
    if treedef != new_treedef:
        raise ValueError(
            "guard.local requires the worker step to preserve the "
            f"local_state pytree structure (got {treedef} -> {new_treedef})"
        )
    if touched is not None:
        touched = list(touched)
        if len(touched) != len(new_leaves):
            raise ValueError(
                "touched_local_rows must return one entry per flattened "
                f"local-state leaf ({len(new_leaves)}), got {len(touched)}"
            )
    zero = jnp.zeros((), jnp.int32)
    counts = {"nonfinite": zero, "norm": zero, "masked": zero}
    guarded = False
    out_leaves = []
    for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
        if not (hasattr(n, "dtype") and jnp.issubdtype(n.dtype, jnp.inexact)):
            out_leaves.append(n)
            continue
        guarded = True
        t = touched[i] if touched is not None else None
        if t is not None and jnp.ndim(n) >= 1:
            n, leaf_counts = _guard_rows_touched(o, n, guard, t)
        else:
            n, leaf_counts = _guard_rows_full(o, n, guard)
        for k, v in leaf_counts.items():
            counts[k] = counts[k] + v
        out_leaves.append(n)
    if not guarded:
        return new, None
    return jax.tree.unflatten(treedef, out_leaves), counts


def _guard_rows_full(o, n, guard: GuardConfig):
    """Whole-leaf row screen (every row; the ``touched=None`` path)."""
    axes = tuple(range(1, jnp.ndim(n)))
    finite = jnp.all(jnp.isfinite(n), axis=axes)
    nonfinite = ~finite
    if guard.norm_limit is not None:
        # Delta norm over zero-substituted rows, like guard_pushes:
        # a non-finite row must not double-count through the norm tier.
        delta = jnp.where(
            finite if not axes else jnp.expand_dims(
                finite, tuple(range(1, jnp.ndim(n)))),
            (n - o).astype(jnp.float32), 0.0,
        )
        sq = jnp.sum(delta * delta, axis=axes)
        exploded = finite & (sq > guard.norm_limit**2)
    else:
        exploded = jnp.zeros_like(nonfinite)
    bad = nonfinite | exploded
    counts = {
        "nonfinite": jnp.sum(nonfinite, dtype=jnp.int32),
        "norm": jnp.sum(exploded, dtype=jnp.int32),
        "masked": jnp.zeros((), jnp.int32),
    }
    if guard.mode == "mask":
        revert = bad if not axes else jnp.expand_dims(
            bad, tuple(range(1, jnp.ndim(n))))
        n = jnp.where(revert, o, n).astype(n.dtype)
        counts["masked"] = jnp.sum(bad, dtype=jnp.int32)
    return n, counts


def _guard_rows_touched(o, n, guard: GuardConfig, t):
    """Ids-aware row screen: gather the touched rows, screen THEM
    (nonfinite + norm + mask-mode revert via a drop-mode scatter), then
    run the leaf-tier net — a non-finite row outside the touched set
    still counts as ``nonfinite`` (but cannot be reverted; see
    :func:`guard_local_state`)."""
    rows = n.shape[0]
    t = jnp.asarray(t).reshape(-1).astype(jnp.int32)
    # Out-of-range ids are inert like -1: the clamped gather would
    # otherwise screen (and count reverts against) the LAST row once
    # per stray id while the drop-scatter discards the revert anyway.
    valid = (t >= 0) & (t < rows)
    safe = jnp.where(valid, t, 0)  # in-bounds gather index for -1 slots
    idx = jnp.where(valid, t, rows)  # out-of-bounds -> dropped by scatter
    n_t = jnp.take(n, safe, axis=0)
    o_t = jnp.take(o, safe, axis=0)
    axes = tuple(range(1, jnp.ndim(n_t)))
    finite_t = jnp.all(jnp.isfinite(n_t), axis=axes)
    nonfinite_t = valid & ~finite_t
    if guard.norm_limit is not None:
        delta = jnp.where(
            jnp.expand_dims(finite_t, axes) if axes else finite_t,
            (n_t - o_t).astype(jnp.float32), 0.0,
        )
        sq = jnp.sum(delta * delta, axis=axes)
        exploded_t = valid & finite_t & (sq > guard.norm_limit**2)
    else:
        exploded_t = jnp.zeros_like(nonfinite_t)
    bad_t = nonfinite_t | exploded_t
    counts = {
        "nonfinite": jnp.sum(nonfinite_t, dtype=jnp.int32),
        "norm": jnp.sum(exploded_t, dtype=jnp.int32),
        "masked": jnp.zeros((), jnp.int32),
    }
    if guard.mode == "mask":
        revert = jnp.expand_dims(bad_t, axes) if axes else bad_t
        repl = jnp.where(revert, o_t, n_t).astype(n.dtype)
        n = n.at[idx].set(repl, mode="drop")
        counts["masked"] = jnp.sum(bad_t, dtype=jnp.int32)
    # Leaf-tier net: non-finite rows OUTSIDE the touched set (stale
    # poison from an observe-mode step, a poisoned restore, bit rot in
    # host staging) are counted — detection must not depend on the ids.
    touched_mask = jnp.zeros((rows,), bool).at[idx].set(True, mode="drop")
    finite_rows = jnp.all(jnp.isfinite(n), axis=tuple(range(1, jnp.ndim(n))))
    counts["nonfinite"] = counts["nonfinite"] + jnp.sum(
        ~finite_rows & ~touched_mask, dtype=jnp.int32)
    return n, counts


def health_total(metrics: Pytree) -> int:
    """Total poison events in a chunk/epoch's HOST metrics pytree.

    Sums the ``nonfinite`` and ``norm`` counters of every table over every
    step (``masked`` is derived from those two, so it is excluded — it
    would double-count). Returns 0 when no health channel is present
    (guard off).
    """
    h = metrics.get(HEALTH_KEY) if isinstance(metrics, Mapping) else None
    if not h:
        return 0
    total = 0
    for counters in h.values():
        for kind in ("nonfinite", "norm"):
            if kind in counters:
                total += int(np.sum(np.asarray(counters[kind])))
    return total


def health_by_segment(metrics: Pytree, segments: int,
                      steps_per_segment: int) -> list[int]:
    """Per-segment poison totals of one megastep's HOST metrics pytree.

    The megastep driver (``fps_tpu.core.megastep``) dispatches
    ``segments`` in-graph chunk segments of ``steps_per_segment`` steps
    in one call; adjudication happens at megastep granularity, but the
    quarantine record should still name WHICH in-graph chunk reported
    poison. Splits the stacked per-step counters on the segment grid
    (the final, trimmed megastep may cover fewer rows — trailing
    segments then report 0) and sums ``nonfinite`` + ``norm`` per
    segment, mirroring :func:`health_total`'s counting rule.
    """
    h = metrics.get(HEALTH_KEY) if isinstance(metrics, Mapping) else None
    totals = [0] * segments
    if not h:
        return totals
    for counters in h.values():
        for kind in ("nonfinite", "norm"):
            if kind not in counters:
                continue
            v = np.asarray(counters[kind])
            if not v.ndim:
                totals[0] += int(v)
                continue
            for i in range(segments):
                sl = v[i * steps_per_segment:(i + 1) * steps_per_segment]
                totals[i] += int(np.sum(sl))
    return totals


@dataclasses.dataclass
class RollbackPolicy:
    """Host-loop degradation policy for ``fit_stream`` / ``run_indexed``.

    When a chunk/epoch's health channel reports poison (any nonzero
    ``nonfinite``/``norm`` count), the driver restores the state captured
    just before that chunk ran, records the chunk index in
    :attr:`quarantined`, and continues with the next chunk — the PRNG and
    shuffle streams are untouched because both key off the chunk/epoch
    index, not off how many chunks actually applied.

    Requires ``TrainerConfig.guard`` (either mode: ``"observe"`` gives
    pure quarantine semantics; ``"mask"`` would normally make rollback
    unnecessary, but combining them quarantines any chunk that needed
    masking at all). Each guarded chunk pays one on-device state copy
    (the pre-chunk snapshot must survive buffer donation) and one
    metrics host-sync — this is a degradation mode, not a fast path.

    ``preset`` indices are skipped OUTRIGHT — the chunk/epoch is consumed
    from the stream but never dispatched (no state copy, no metrics
    entry); PRNG/shuffle streams key off the index, so later work is
    unaffected. This is how quarantine decisions survive a process
    restart: the run supervisor (``fps_tpu.supervise``) persists the
    poisoned indices next to the checkpoint dir and the restarted child
    preloads them here, so a *deterministic* poison batch cannot crash-
    loop the run. A preset-only policy (no guard) is legal — it skips
    without needing the health channel.
    """

    # Quarantine budget: exceeding it raises PoisonedStreamError (a stream
    # that is ALL poison is an ingest bug, not a transient).
    max_rollbacks: int = 8
    # Chunk/epoch indices rolled back so far (mutated by the driver).
    quarantined: list = dataclasses.field(default_factory=list)
    # Indices quarantined by a PREVIOUS attempt (carried across restarts
    # by the supervisor): skipped without dispatch.
    preset: frozenset = frozenset()
    # Preset indices actually skipped this run (mutated by the driver).
    skipped: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        # Coerce lists/tuples (the supervisor state file round-trips
        # through JSON) so membership tests are O(1) and hashable-safe.
        self.preset = frozenset(int(i) for i in self.preset)

    def skip(self, index: int) -> None:
        """Record one preset-quarantined index skipped without dispatch
        (journal-trailed like :meth:`record`, but no budget: these chunks
        were already adjudicated by a previous attempt)."""
        self.skipped.append(index)
        from fps_tpu.obs import events as _obs_events

        _obs_events.emit("preset_skip", index=int(index),
                         total=len(self.skipped))

    def record(self, index: int) -> None:
        """Record a quarantined index; raises once the budget is exceeded.
        The index is appended BEFORE the raise so the quarantine log is
        complete for a caller that catches PoisonedStreamError. Callers
        (the driver) restore last-good state before calling this, so the
        raise never strands donated buffers."""
        self.quarantined.append(index)
        # Journal trail (fps_tpu.obs.events — stdlib-only, no cycle; no-op
        # when no process-default recorder is installed).
        from fps_tpu.obs import events as _obs_events

        _obs_events.emit("rollback", index=int(index),
                         total=len(self.quarantined),
                         budget=self.max_rollbacks)
        if len(self.quarantined) > self.max_rollbacks:
            _obs_events.emit("poisoned_stream_abort",
                             quarantined=list(self.quarantined),
                             budget=self.max_rollbacks)
            raise PoisonedStreamError(
                f"rollback budget exhausted ({self.max_rollbacks}); "
                f"quarantined chunks: {self.quarantined}"
            )


def tree_copy(tree: Pytree) -> Pytree:
    """Fresh on-device buffers for every array leaf — a pre-chunk snapshot
    that survives the training call's donation of the originals."""
    return jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree
    )


# ---------------------------------------------------------------------------
# Snapshot integrity primitives (shared by checkpoint.py and the tests).
#
# One implementation, owned by the jax-FREE on-disk-contract module so
# the write path (checkpoint.py, via this re-export) and the serving
# plane's verifier can never drift — a fork here would make every fresh
# snapshot fail read-side verification.
# ---------------------------------------------------------------------------

from fps_tpu.core.snapshot_format import array_crc32  # noqa: E402,F401
