"""Step drivers: the TPU-native replacement for Flink's iteration loop.

The reference wires worker and server operators into a cyclic dataflow
(``ConnectedIterativeStreams`` + ``closeWith`` feedback edge, expected
upstream ``src/main/scala/hu/sztaki/ilab/ps/FlinkParameterServer.scala``) and
lets records circulate asynchronously until an ``iterationWaitTime`` timeout.

Here the loop is compiled: one ``jax.lax.scan`` over a chunk of microbatches,
inside one ``shard_map`` over the ``(data, shard)`` mesh, jitted once and fed
by a host-side ingest loop. Two execution modes:

* **sync** — every step pulls fresh values through the sharded store
  (collective gather) and pushes immediately (collective scatter-add). This
  is the ``staleness = 0`` point the reference cannot even express.
* **ssp**  — bounded staleness: workers read from a device-local replicated
  *snapshot* of the tables, refreshed by an ``all_gather`` every
  ``sync_every`` steps; pushes still land in the authoritative sharded
  tables every step, so no update is ever lost. A worker therefore reads
  values at most ``sync_every`` steps stale — a *stronger* guarantee than
  the reference's free-running asynchrony, whose only flow control is the
  worker pull limiter (``WorkerLogic.addPullLimiter``, expected upstream
  ``.../ps/WorkerLogic.scala``).
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import logging
import warnings
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fps_tpu import ops
from fps_tpu.core import resilience
from fps_tpu.core.api import ServerLogic, WorkerLogic, as_hot_fold
from fps_tpu.core.prefetch import ChunkPrefetcher, PlacedChunk
from fps_tpu.core.resilience import GuardConfig, RollbackPolicy
from fps_tpu import sketch as _sketch
from fps_tpu.core.store import (
    FOLD_KEY_SUFFIX,
    IDS_KEY_SUFFIX,
    MAP_KEY_SUFFIX,
    SKETCH_KEY_SUFFIX,
    ParamStore,
    accumulate_hot,
    compact_cold,
    delta_counted,
    dense_key,
    fold_key,
    hot_base,
    hot_delta_init,
    hot_fold_state_shape,
    hot_key,
    hot_slot_map,
    id_to_phys,
    ids_key,
    is_aux_key,
    is_hot_key,
    lookup_hot_slots,
    map_key,
    padded_rows,
    pull,
    pull_hot,
    push,
    reconcile_hot,
    reconcile_hot_mapped,
    sketch_key,
    split_hot_push,
    split_dense,
    split_hot_push_slots,
    split_tiering,
    watch_distinct_pulls,
    watch_fold_rows,
    watch_routed,
    watch_sum_runs,
)
from fps_tpu.obs.health import (
    HEALTH_ABORT,
    HEALTH_ESCALATE,
    HealthMonitor,
    StepWatchdog,
)
from fps_tpu.obs.timing import (PhaseTimer, host_span, settle, watch_device,
                                watch_program)
from fps_tpu.parallel.mesh import (
    DATA_AXIS,
    SHARD_AXIS,
    host_to_sharded,
    key_to_replicated,
)

Array = jax.Array
Pytree = Any

_log = logging.getLogger("fps_tpu.driver")

WORKER_AXES = (DATA_AXIS, SHARD_AXIS)

# End-of-iterator sentinel for the timed ingest pull in fit_stream.
_STREAM_END = object()


def calls_per_epoch_of(plan, steps_per_call: int) -> int:
    """One chunk-grid definition for every indexed-plan consumer
    (run_indexed, the megastep driver): the plan's own
    ``calls_per_epoch`` when it has one (``DeviceEpochPlan``), else the
    ceil-divide fallback for duck-typed plans (e.g. the w2v device
    plan) that only expose ``steps_per_epoch``."""
    if hasattr(plan, "calls_per_epoch"):
        return plan.calls_per_epoch(steps_per_call)
    return -(-plan.steps_per_epoch // steps_per_call)


def _phase(timer: PhaseTimer | None, name: str):
    """One host phase of a call: :func:`fps_tpu.obs.timing.host_span`,
    feeding ``timer`` when telemetry is on (two flag tests when off)."""
    return host_span(name, timer)


def _watch(watchdog: StepWatchdog | None, what: str, index: int):
    return (watchdog.watch(what, index) if watchdog is not None
            else contextlib.nullcontext())


def _find_heartbeat(rec):
    """The supervised-run progress beacon riding ``rec``'s sinks, if any.

    Duck-typed on the sink's ``heartbeat`` attribute (the
    ``fps_tpu.supervise.child.HeartbeatSink`` shape) so the driver never
    imports the supervise package. With a beacon in hand the drivers beat
    at SUB-chunk boundaries (prefetch wait / dispatch) with a ``phase``
    field, so a death between chunk boundaries attributes to the right
    sub-phase in the supervisor's quarantine evidence."""
    for s in getattr(rec, "sinks", ()) if rec is not None else ():
        hb = getattr(s, "heartbeat", None)
        if hb is not None and hasattr(hb, "beat"):
            return hb
    return None


def _beat(hb, index: int, phase: str) -> None:
    """Sub-phase liveness beat (no-op without a beacon). Carries the
    index being worked on — the beat-before-work convention the
    supervisor's quarantine keys on — plus the sub-phase name."""
    if hb is not None:
        hb.beat(index=int(index), phase=phase)


def worker_index() -> Array:
    """Linear worker index of the calling device (inside shard_map)."""
    return lax.axis_index(DATA_AXIS) * lax.axis_size(SHARD_AXIS) + lax.axis_index(
        SHARD_AXIS
    )


def num_workers_of(mesh) -> int:
    return mesh.shape[DATA_AXIS] * mesh.shape[SHARD_AXIS]


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Execution-mode knobs (the reference exposes workerParallelism /
    psParallelism / iterationWaitTime on ``transform``; parallelism here
    comes from the mesh, and the timeout has no analog in a compiled loop).
    """

    sync_every: int | None = None  # None => fully synchronous mode
    # Emulates the reference's in-flight pushes: a worker's pushes reach the
    # authoritative tables ``push_delay`` steps after they were computed
    # (0 = immediately, the sync/SSP default). Worker-LOCAL state updates
    # stay immediate — in the reference, too, only PS traffic rides the
    # network while worker operator state is updated in place. Combined
    # with ``sync_every`` this brackets the reference's free-running
    # asynchrony from both sides: stale reads AND delayed writes. Delayed
    # pushes ride a ring buffer in the compiled loop's carry; whatever is
    # still in flight when a compiled call ends is flushed then (a chunk /
    # dispatch boundary acts as a quiesce point).
    push_delay: int = 0
    # Optional per-step tap with TABLE access, traced into the compiled
    # loop: ``tap(tables, batch, local_state, t) -> pytree``. Unlike the
    # worker's ``out`` channel (global sums), tap outputs are all-gathered
    # across the worker axes — metrics gain a ``"tap"`` entry whose leaves
    # carry a leading per-worker axis ``(T, W, ...)``. This is how
    # per-worker emissions that need the live tables ride the output
    # stream — e.g. online top-K recommendations interleaved with training
    # (the reference's ``...AndTopK`` jobs emit exactly such records on
    # WOut; see fps_tpu.models.recommendation.make_online_topk_tap).
    # ``batch`` is the raw (pre-``prepare``) batch. ``tables`` and
    # ``local_state`` are the step's own PRE-update view: the live tables
    # and worker state as step ``t - 1`` left them (with ``push_delay > 0``
    # less the pushes still in flight), never a model that has trained on
    # step ``t``'s batch — what the reference's ``...AndTopK`` jobs rank
    # with ("rank, then learn"; prequential evaluation stands on it). Under
    # SSP that is the live table, fresher than the round's snapshot the
    # step's pulls read. The tap's device ops sit under a scope of their
    # own, ``fps.tap`` (docs/observability.md). A tap may carry a
    # ``journal`` attribute, ``journal(host tap output) -> {counter: n}``:
    # wherever an epoch's or chunk's metrics are folded on the host its
    # counts land on that journal event and under ``tap.<counter>``.
    step_tap: Callable[..., Any] | None = None
    # On-device push-delta health guard (fps_tpu.core.resilience): None
    # (default) traces the exact guard-free program of old — zero cost
    # when off; "observe"/"mask" (or a full GuardConfig) screens every
    # table's push deltas per step inside the compiled scan, counts
    # non-finite / norm-exploded rows into a "health" entry on the
    # metrics stream, and in mask mode drops the offending rows
    # (id → -1, delta → 0) before they reach the server fold — a poison
    # batch degrades to a lost update instead of table death. Requires
    # the worker's out channel to be a dict (same constraint as
    # step_tap). Part of the compile-cache key.
    guard: GuardConfig | str | None = None
    donate: bool = True
    # --- host-pipeline knobs (fps_tpu.core.prefetch; docs/performance.md).
    # None of these touch the traced program or the compile cache: the
    # compiled HLO is identical whatever their values (tested).
    #
    # Depth of the background prefetch+place pipeline feeding fit_stream:
    # chunk assembly and host->device placement run up to this many chunks
    # ahead on a worker thread, so the device never idles waiting on host
    # ingest. 0 (default) keeps the fully synchronous host loop; numerics
    # and chunk order are bit-identical either way.
    prefetch: int = 0
    # Adaptive prefetch depth bound: > 0 lets the pipeline raise its
    # depth from `prefetch` up to this many chunks when the consumer
    # keeps draining the buffer empty (measured queue-empty stalls),
    # vetoed by available host memory — see ChunkPrefetcher.max_depth.
    # 0 (default) keeps the depth fixed at `prefetch`.
    prefetch_max: int = 0
    # Staleness (in chunks) of the forced host metrics sync that health /
    # watchdog / rollback consumers require: 0 (default) inspects chunk
    # i's metrics before dispatching i+1 (today's serial behavior); 1
    # inspects chunk i-1's metrics WHILE chunk i computes (bounded-
    # staleness health, the paper's SSP semantics applied to the control
    # plane). Quarantine under lag restores the pre-(i-1) snapshot and
    # deterministically recomputes chunk i from it, so lag on/off produce
    # identical tables and metrics (tested).
    health_lag: int = 0
    # Deferred-metrics drain cadence for fit_stream without a per-chunk
    # syncing consumer: every N chunks the buffered device metrics are
    # pulled to host so an unbounded stream cannot accumulate device
    # buffers (was a hardcoded 8). 0 = never drain mid-stream (bounded
    # streams whose caller wants zero mid-stream syncs).
    metrics_drain_every: int = 8
    # Reconcile cadence, in steps, of the two-tier hot storage
    # (TableSpec.hot_tier; docs/performance.md "Two-tier storage"): hot
    # pushes accumulate into per-device delta buffers that one psum folds
    # into the replica + the canonical table every hot_sync_every steps —
    # the SSP staleness bound applied to the parameter plane. 1 (default)
    # is the EXACT mode: the tier disengages and the driver lowers the
    # identical untiered program (bit-identical tables/metrics/
    # checkpoints by construction — a per-step psum reconcile could not
    # reproduce the gathered scatter's summation order; see the store
    # module docstring). In SSP mode the reconcile rides the sync_every
    # round boundary (the snapshot gather must see reconciled head rows),
    # so the effective parameter-plane bound there is sync_every; this
    # knob still gates the tier on/off. Every compiled call ends with a
    # flush reconcile, so chunk/epoch boundaries always hold one
    # canonical table (checkpoints/rollback need no special casing).
    # Part of the compile-cache key.
    hot_sync_every: int = 1
    # Adaptive tiering (fps_tpu.tiering; docs/performance.md "Adaptive
    # tiering"): True auto-attaches a Retierer at run entry — online
    # pulled-id frequency tracking (device-side count-min windows,
    # psum-merged), an auto-tiering plan derived from the sketched
    # densities after a warmup (per-table hot_tier / hot_sync_every /
    # dense route — replaces hand-tuning those three knobs), and
    # churn-triggered hot-set re-ranks that swap the replica + slot-map
    # DATA without recompiling. Attach ``trainer.retierer`` directly for
    # non-default cadences/thresholds/persistence. Host-only flag: the
    # compile key derives from the retierer's resolution, not this bool.
    auto_tier: bool = False
    # Upper bound on scan steps per compiled call in run_indexed: epochs
    # longer than this are split into several dispatches of one compiled
    # program (trailing steps past the epoch are weight-0 no-ops, so every
    # call has identical static shape). It was added against a
    # per-dispatch execution deadline of an earlier runtime; whether the
    # v5e's runtime has one is not measured here, and the knob's fate is
    # ROADMAP D5's.
    max_steps_per_call: int | None = None


class Trainer:
    """Compiles and runs the PS training loop for one WorkerLogic.

    Equivalent of ``FlinkParameterServer.transform(trainingData, workerLogic,
    psLogic, workerParallelism, psParallelism, iterationWaitTime)`` — but the
    "transform" output stream is returned as a per-chunk metrics pytree (the
    reference's ``WOut`` channel) plus the live sharded tables (the
    reference's end-of-job model stream).
    """

    def __init__(
        self,
        mesh,
        param_store: ParamStore,
        worker_logic: WorkerLogic,
        server_logic: Mapping[str, ServerLogic] | ServerLogic = ServerLogic(),
        config: TrainerConfig | None = None,
        recorder=None,
        audit=None,
    ):
        # Telemetry (fps_tpu.obs.Recorder) — host-side only, never part of
        # the traced program or the compile cache key; None (default) means
        # the drivers skip every obs call. Assignable after construction
        # (``trainer.recorder = rec``) and overridable per fit_stream /
        # run_indexed call.
        self.recorder = recorder
        # Opt-in compile-time program certification (fps_tpu.analysis):
        # a ProgramAuditor / ProgramContract / True / "strict". Every
        # program this trainer compiles is lowered once more on its first
        # call, run through the static-analysis pass suite against the
        # contract (default: contract_for_trainer — donation, host
        # transfers, dtype drift, and the hot-tier reconcile psum when
        # tiering resolves on), and reported through the recorder as
        # analysis.certified_programs / analysis.contract_violations
        # metrics plus an analysis.contract_violation event per finding
        # ("strict" raises ContractViolationError instead). Host-side
        # only — the executed program is untouched. Set it BEFORE the
        # first compiled call: like the guard, certification attaches at
        # program build time (already-cached programs are not re-audited).
        if audit is not None:
            from fps_tpu import analysis

            # Fail fast on typos here, not on the first dispatch;
            # False normalizes to None (disabled), so boolean flags
            # wire straight through.
            audit = analysis.as_auditor(audit)
        self.audit = audit
        self.mesh = mesh
        self.store = param_store
        self.logic = worker_logic
        if isinstance(server_logic, ServerLogic):
            server_logic = {name: server_logic for name in param_store.specs}
        self.server_logic = dict(server_logic)
        self.config = config or TrainerConfig()
        guard = resilience.as_guard(self.config.guard)  # fail fast on typos
        if guard is not None and guard.tables is not None:
            unknown = set(guard.tables) - set(param_store.specs)
            if unknown:
                raise ValueError(
                    f"guard.tables names unknown tables {sorted(unknown)} — "
                    f"store has {sorted(param_store.specs)}; a typo here "
                    "would silently disable the guard"
                )
        if (guard is not None and guard.local
                and resilience.LOCAL_STATE_KEY in param_store.specs):
            raise ValueError(
                f"guard.local reserves the {resilience.LOCAL_STATE_KEY!r} "
                "health-channel entry, but the store has a table of that "
                "name — rename the table or disable the local guard"
            )
        self.num_shards = mesh.shape[SHARD_AXIS]
        self.num_workers = num_workers_of(mesh)
        # Adaptive tiering (fps_tpu.tiering.Retierer) — host-side hot-set
        # manager. Assignable after construction, BEFORE the first
        # compiled call (mapped-tier/tracking resolution is part of the
        # compile key, like the guard); TrainerConfig.auto_tier attaches
        # a default one at run entry.
        self.retierer = None
        self._tier_warned: set[str] = set()

        self._table_sharding = NamedSharding(mesh, P(SHARD_AXIS, None))
        self._worker_sharding = NamedSharding(mesh, P(WORKER_AXES))
        self._replicated = NamedSharding(mesh, P())
        self._compiled = {}
        self._check_dense()
        self._check_fold()

    # -- dense parameters (api.DenseLogic) --------------------------------

    def _check_dense(self) -> None:
        """Note the logic's dense parameters (``_dense_like``: ``{name:
        ShapeDtypeStruct}``, empty where it declares none) and refuse, at
        construction, every mode the dense route does not run under: each
        would have to say when a dense gradient lands against when a push
        does, and none says it yet."""
        self._dense_like = {}
        if self.logic.dense is None:
            return
        shapes = jax.eval_shape(self.logic.dense.init_fn, jax.random.key(0))
        bad = [k for k in shapes if "::" in k]
        if bad:
            raise ValueError(f"dense parameter names hold '::': {bad}")
        self._dense_like = dict(shapes)
        names = sorted(shapes)
        cfg = self.config
        unsupported = {
            "sync_every (SSP rounds: a round's snapshot holds tables only,"
            " so dense reads would be fresh beside stale pulls)":
                cfg.sync_every,
            "push_delay (pushes would land later than the dense gradients"
            " of the same step)": cfg.push_delay,
            "step_tap (a tap is handed tables and local state, not the"
            " dense parameters)": cfg.step_tap,
            "guard (it screens pushes only; a poisoned step's dense"
            " gradients would still be folded)": cfg.guard,
            "hot_sync_every > 1 / TableSpec.hot_tier (the tier's windows"
            " reconcile tables only)": (
                cfg.hot_sync_every > 1
                or any(spec.hot_tier for spec in self.store.specs.values())),
            "auto_tier": cfg.auto_tier,
        }
        bad = [what for what, on in unsupported.items() if on]
        if bad:
            raise ValueError(
                f"the worker logic declares dense parameters {names}; the "
                "dense route (fps.dense) is not supported with: "
                + "; ".join(bad))

    def _dense_specs(self) -> dict:
        """Partition specs of the dense entries of the tables dict: every
        worker holds all of every one."""
        return {dense_key(name): P() for name in self._dense_like}

    def _dense_fields(self) -> dict:
        """What a queued unit's span says of the dense route: how many
        parameters a step reduces and folds, and their bytes."""
        if not self._dense_like:
            return {}
        shapes = self._dense_like.values()
        return {"dense_params": sum(int(np.prod(s.shape)) for s in shapes),
                "dense_bytes": sum(int(np.prod(s.shape)) * s.dtype.itemsize
                                   for s in shapes)}

    def _fold_dense(self, dense, grads):
        """The dense route of one step: every worker's gradients summed
        over the worker axes (ONE all-reduce, of all of them together; on
        one device none) and ``theta -= lr * sum`` applied whole, no
        gather and no scatter."""
        if grads is None or set(grads) != set(dense):
            raise ValueError(
                "a logic with dense parameters returns StepOutput."
                f"dense_grads for each of {sorted(dense)}; got "
                f"{None if grads is None else sorted(grads)}")
        lr = self.logic.dense.learning_rate
        names = sorted(dense)
        with jax.named_scope("fps.dense"):
            ops.log_route("dense", "psum_sgd",
                          sum(int(np.prod(dense[k].shape)) for k in names),
                          1, 0, f"workers={self.num_workers}")
            summed = [grads[k] for k in names]
            if self.num_workers > 1:
                # One vector, so ONE all-reduce in the graph as traced (a
                # psum of the list lowers to an all-reduce an array).
                flat = lax.psum(jnp.concatenate(
                    [g.reshape(-1) for g in summed]), WORKER_AXES)
                edges = np.cumsum([g.size for g in summed])[:-1]
                summed = [f.reshape(g.shape).astype(g.dtype) for f, g in
                          zip(jnp.split(flat, edges), summed)]
            return {k: dense[k] - (lr * g).astype(dense[k].dtype)
                    for k, g in zip(names, summed)}

    # -- a table's own stateful fold (ServerLogic.fold) -------------------

    def _check_fold(self) -> None:
        """Refuse, at construction, every mode that has no rule yet for
        when a step folded by a table's own optimizer
        (``ServerLogic.fold``) lands, and tell the store which tables'
        ``::fold`` entries are laid out like the table (a snapshot saves
        those in logical id order)."""
        folds = self._row_fold_map()
        self.store.row_folds = {
            name: f.state_cols(self.store.specs[name].dim)
            for name, f in folds.items()}
        cfg = self.config
        for name in sorted(folds):
            sl, spec = self.server_logic[name], self.store.specs[name]
            unsupported = {
                "apply_fn (the fold is the table's apply)":
                    sl.apply_fn is not None,
                f"combine={sl.combine!r} (the fold takes the SUM of an "
                "id's pushes)": sl.combine != "sum",
                "hot_fold / TableSpec.hot_tier on the same table (the "
                "tier's window would fold once where the table folds "
                "every step)": sl.hot_fold is not None or spec.hot_tier,
                "auto_tier (it may tier the table)": cfg.auto_tier,
                "sync_every (SSP rounds: a round's snapshot holds no "
                "state, and says nothing of a fold against stale rows)":
                    cfg.sync_every,
                "push_delay (a delayed push would fold against state "
                "later pushes have already moved)": cfg.push_delay,
            }
            bad = [what for what, on in unsupported.items() if on]
            if bad:
                raise ValueError(
                    f"table {name!r}: ServerLogic.fold="
                    f"{folds[name].kind!r} is not supported with: "
                    + "; ".join(bad))

    def _row_fold_map(self) -> dict:
        """{table: HotFold} for the tables that declare an optimizer of
        their own (``ServerLogic.fold``). Part of the compile-cache key
        via :meth:`_server_logic_key`."""
        return {name: as_hot_fold(sl.fold)
                for name, sl in sorted(self.server_logic.items())
                if sl.fold is not None and name in self.store.specs}

    def _fold_state_shape(self, name: str) -> tuple[int, int]:
        """GLOBAL shape of a table's ``::fold`` entry: the hot fold's in
        slice order, the table's own fold's the table's rows."""
        spec = self.store.specs[name]
        if name in self.store.row_folds:
            return (padded_rows(spec.num_ids, self.num_shards),
                    self.store.row_folds[name])
        return tuple(hot_fold_state_shape(
            self._hot_fold_map()[name], self._hot_tier_map()[name],
            spec.dim, self.num_shards))

    # -- state ------------------------------------------------------------

    def init_state(self, key: Array) -> tuple[dict[str, Array], Pytree]:
        with host_span("init_state", memory=True):
            tables = self.store.init(jax.random.fold_in(key, 0))
            if self.logic.dense is not None:
                # Into the store's own dict, as the call that ends a run
                # leaves them there (``store.tables = dict(tables)``).
                dense = jax.jit(
                    self.logic.dense.init_fn,
                    out_shardings=jax.tree.map(
                        lambda _: self._replicated, self._dense_like),
                )(jax.random.fold_in(key, 2))
                tables.update({dense_key(k): v for k, v in dense.items()})
            ls_key = jax.random.fold_in(key, 1)

            def make_local_state():
                return self.logic.init_local_state(ls_key, self.num_workers)

            local_state = jax.jit(
                make_local_state,
                out_shardings=jax.tree.map(lambda _: self._worker_sharding,
                                           jax.eval_shape(make_local_state)),
            )()
            settle((tables, local_state))
        return tables, local_state

    # -- checkpoint plumbing ----------------------------------------------

    def _host_local_state(self, local_state):
        """Local state as host numpy — multi-controller safe (cross-host
        leaves replicate through a jitted identity, a COLLECTIVE: every
        process must reach the checkpoint boundary together, same as the
        table dump)."""
        from fps_tpu.parallel.mesh import replicate_to_mesh

        def to_host(leaf):
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                leaf = replicate_to_mesh(leaf, self.mesh)
            return np.asarray(leaf)

        return jax.tree.map(to_host, local_state)

    def _save_checkpoint(self, checkpointer, step: int, local_state, *,
                         tables=None, touched=None, final=False) -> None:
        """Snapshot tables + local state, with the local state in the
        logic's worker-count-independent export form (default: the raw
        layout, tagged either way so a mismatched restore fails loudly).

        ``tables``: optional on-device boundary copies to snapshot from
        instead of the live store — the overlapped pipeline takes them at
        the chunk boundary and runs the save after the NEXT dispatch, by
        which time the live tables already hold a later chunk's state.
        With an :class:`~fps_tpu.core.checkpoint.AsyncCheckpointer` and
        fully-addressable state, the device→host capture itself defers
        onto the WRITER thread (``save_deferred``) — the training thread
        pays one enqueue and the capture overlaps device compute; the
        multi-controller dump replicates through a COLLECTIVE and must
        stay inline, so non-addressable state falls back to the
        store-swap path below. Otherwise the store's table view is
        swapped in for the duration of the dump (single-threaded: only
        the driver thread touches the store).

        ``touched``: delta-chain sourcing — an ``(ids_by_table, marker,
        tracker)`` capture from a :class:`~fps_tpu.core.checkpoint.
        TouchedRowsTracker`, taken at the SAME boundary as the state
        being saved (the overlapped paths capture alongside their
        on-device boundary copies). The tracker prefix is committed only
        after the checkpointer ACCEPTED the save, so a failed/raced save
        never loses touched ids for the next publication.

        ``final``: the end-of-run save — forced to LAND (``when_full=
        "block"``) even on a checkpointer configured to skip saves while
        its writer is busy; the run's terminal state must be durable."""
        kwargs = {}
        if touched is not None:
            kwargs["touched_rows"] = touched[0]
        if final and hasattr(checkpointer, "when_full"):
            kwargs["when_full"] = "block"
        try:
            if (tables is not None
                    and hasattr(checkpointer, "save_deferred")
                    and self._fully_addressable(tables, local_state)):
                # Writer-side capture: hand the writer a private store
                # view over the boundary copies (shallow copy — specs /
                # mesh / shard layout are stable; only ``tables`` is
                # swapped) plus the on-device local-state copy. The
                # closure runs on the writer thread; everything it
                # touches is either frozen (the copies) or immutable.
                view = copy.copy(self.store)
                view.tables = dict(tables)
                ckpt, logic = checkpointer, self.logic
                ls_dev = local_state

                def collect():
                    return ckpt._collect(
                        view,
                        logic.export_local_state(
                            self._host_local_state(ls_dev)),
                        "exported",
                    )

                checkpointer.save_deferred(step, collect, **kwargs)
            else:
                prev = None
                if tables is not None:
                    prev = self.store.tables
                    self.store.tables = dict(tables)
                try:
                    checkpointer.save(
                        step, self.store,
                        self.logic.export_local_state(
                            self._host_local_state(local_state)
                        ),
                        local_state_format="exported",
                        **kwargs,
                    )
                finally:
                    if prev is not None:
                        self.store.tables = prev
            if touched is not None:
                touched[2].commit(touched[1])
        except Exception as e:
            # A pod fence refusal (StaleEpochError, possibly re-raised
            # from the async writer wrapped in RuntimeError) means this
            # whole PROCESS belongs to an aborted pod attempt: name that
            # plainly at the driver altitude before propagating — the
            # training loop is over either way, and the pod scenarios
            # grep for this line as the zombie's epitaph.
            from fps_tpu.supervise.child import StaleEpochError

            cause = e
            while cause is not None:
                if isinstance(cause, StaleEpochError):
                    _log.error(
                        "run fenced off by the pod at step %d: %s",
                        step, cause)
                    break
                cause = cause.__cause__
            raise

    @staticmethod
    def _fully_addressable(tables, local_state) -> bool:
        """True when every array leaf is fully addressable — the gate
        for writer-thread capture (a non-addressable leaf's dump path
        runs ``replicate_to_mesh``, a COLLECTIVE every process must
        reach together on the training thread)."""
        for leaf in (list(tables.values())
                     + jax.tree.leaves(local_state)):
            if (isinstance(leaf, jax.Array)
                    and not leaf.is_fully_addressable):
                return False
        return True

    def restore_checkpoint(self, checkpointer, local_state_like, *,
                           step: int | None = None):
        """Restore a snapshot onto THIS trainer's mesh — elastic across
        shard counts (tables always) and worker counts (for "exported"
        snapshots whose logic implements ``import_local_state``; raw
        leaves must match ``local_state_like``'s shapes, i.e. same worker
        count).

        ``local_state_like`` supplies structure/shardings — pass the
        local state from :meth:`init_state`. Returns
        ``(tables, local_state, step)``.
        """
        step, values, leaves, fmt = checkpointer.read_snapshot(step)
        tables = checkpointer.load_tables(self.store, step, values)
        imported = NotImplemented
        if fmt == "exported":
            imported = self.logic.import_local_state(
                leaves, self.num_workers
            )
        if imported is NotImplemented:
            # Raw device layout (or an identity-export logic): shapes must
            # match the current worker count's local state exactly.
            like_leaves, treedef = jax.tree.flatten(local_state_like)
            if len(like_leaves) != len(leaves):
                raise ValueError(
                    f"checkpoint step {step} has {len(leaves)} local-state "
                    f"leaves, local_state_like has {len(like_leaves)}"
                )
            for saved, like in zip(leaves, like_leaves):
                if hasattr(like, "shape") and saved.shape != like.shape:
                    raise ValueError(
                        f"checkpoint local-state leaf shape {saved.shape} "
                        f"!= expected {like.shape} — was the snapshot taken "
                        "at a different worker count with a logic that has "
                        "no import_local_state?"
                    )
            imported = jax.tree.unflatten(treedef, leaves)
        placed = jax.tree.map(
            lambda leaf, like: host_to_sharded(
                np.asarray(leaf, getattr(like, "dtype", None)), like.sharding
            ) if isinstance(like, jax.Array) else leaf,
            imported,
            local_state_like,
        )
        return tables, placed, step

    # -- device-side bodies ----------------------------------------------

    @property
    def _data_axis(self) -> str | None:
        """The data axis where it replicates (what ``store.pull`` / ``push``
        / ``reconcile_hot`` take as ``data_axis``), else ``None``."""
        return DATA_AXIS if self.mesh.shape[DATA_AXIS] > 1 else None

    def _resolve_hot_rows(self, spec) -> int:
        """LOCAL rows of a table's certified head: global head ids
        ``[0, H)`` (``spec.hot_ids``) sit in local rows ``[0, ceil(H/S))``
        under the owner-major cyclic layout."""
        if not isinstance(spec.hot_ids, int):
            # Fail at the right altitude — inside the jitted push this
            # would surface as a cryptic TypeError on a unary minus.
            raise ValueError(
                f"table {spec.name!r}: hot_ids={spec.hot_ids!r} — "
                "expected an int"
            )
        return -(-spec.hot_ids // self.num_shards)

    def _resolve_dense(self, spec) -> bool:
        """Dense-collective route for this table on this mesh (see
        ``TableSpec.dense_collectives``). Static per trainer — part of the
        traced program, keyed into the compile cache via the mesh+spec."""
        from fps_tpu.core.store import rows_per_shard

        if self.num_shards * self.mesh.shape[DATA_AXIS] == 1:
            return False  # no collectives to save; gathered route is free
        if spec.dense_collectives == "auto":
            rps = rows_per_shard(spec.num_ids, self.num_shards)
            table_bytes = (
                rps * self.num_shards * spec.dim
                * jnp.dtype(spec.dtype).itemsize
            )
            return table_bytes <= ops.DENSE_TABLE_BYTES
        if isinstance(spec.dense_collectives, str):
            raise ValueError(
                f"table {spec.name!r}: dense_collectives="
                f"{spec.dense_collectives!r} — expected a bool or 'auto'"
            )
        return bool(spec.dense_collectives)

    def _resolve_hot_tier(self, spec) -> int:
        """GLOBAL replicated-head row count for this table on this mesh
        under the current config (0 = untiered). Static per compiled
        program — keyed into the compile cache alongside hot_sync_every.

        The tier engages only where it can win AND stay correct:

        * multi-device meshes (a single device already pulls/pushes with
          zero collectives);
        * ``hot_sync_every > 1`` — 1 is the exact mode, implemented as
          the untiered program itself (see TrainerConfig);
        * "sum" / "mean" / "max" / "min" server folds: the windowed
          pending buffer carries delta sums (+ counts) or elementwise
          extrema, which commute with those combines; ``apply_fn`` and
          callable combines need per-push combine-then-apply over the
          gathered union, so those tables keep the gathered route
          untouched (the one demotion left — PR 10 moved max/min onto
          the tier via the extremum pending buffer).
        """
        H = spec.hot_tier
        if isinstance(H, str):
            # Fail at the right altitude, like hot_ids/dense_collectives.
            raise ValueError(
                f"table {spec.name!r}: hot_tier={H!r} — expected an int"
            )
        if H < 0:
            raise ValueError(
                f"table {spec.name!r}: hot_tier={H} must be >= 0"
            )
        if not H:
            self._check_hot_fold(spec, 0)
            return 0
        if self.num_shards * self.mesh.shape[DATA_AXIS] == 1:
            self._check_hot_fold(spec, 0)
            return 0
        if self.config.hot_sync_every <= 1:
            self._check_hot_fold(spec, 0)
            return 0
        sl = self.server_logic[spec.name]
        if sl.apply_fn is not None or not (
                isinstance(sl.combine, str)
                and sl.combine in ("sum", "mean", "max", "min")):
            # The one SURPRISING disengagement: single-device meshes and
            # hot_sync_every=1 are documented expected states, but a
            # requested tier silently falling back because of the server
            # fold hides a real semantic limit (windowed accumulation
            # cannot reproduce per-push apply_fn / callable-combine
            # semantics over the gathered union) — say so once,
            # explicitly.
            if spec.name not in self._tier_warned:
                self._tier_warned.add(spec.name)
                fold = ("apply_fn" if sl.apply_fn is not None
                        else "a callable combine")
                msg = (
                    f"table {spec.name!r}: hot_tier={H} requested but the "
                    f"per-push server fold ({fold}) keeps the gathered "
                    "route — windowed hot-delta accumulation commutes "
                    "with the 'sum'/'mean'/'max'/'min' combines only, so "
                    "the tier is disabled for this table (the program "
                    "lowers untiered)"
                )
                warnings.warn(msg, stacklevel=2)
                _log.warning("%s", msg)
            self._check_hot_fold(spec, 0)
            return 0
        H = min(int(H), spec.num_ids)
        self._check_hot_fold(spec, H)
        return H

    def _check_hot_fold(self, spec, resolved_H: int) -> None:
        """Fail loudly when a stateful hot fold cannot engage: silently
        downgrading Adagrad/Adam to plain addition would change the
        optimizer, not just the data plane. Requires the tier to resolve
        ON with FULL replication (a partial head would give head rows the
        adaptive step and cold-tail rows the raw delta — a semantic
        fork), and a sum/mean combine (the fold consumes window delta
        sums)."""
        fold = as_hot_fold(self.server_logic[spec.name].hot_fold)
        if fold is None:
            return
        sl = self.server_logic[spec.name]
        if not resolved_H:
            raise ValueError(
                f"table {spec.name!r}: hot_fold={fold.kind!r} requires the "
                "hot tier to resolve ON (multi-device mesh, hot_tier > 0, "
                "hot_sync_every > 1, no apply_fn) — a silently-ignored "
                "server optimizer would change training semantics"
            )
        if resolved_H < spec.num_ids:
            raise ValueError(
                f"table {spec.name!r}: hot_fold={fold.kind!r} with a "
                f"PARTIAL head (H={resolved_H} < {spec.num_ids}): head "
                "rows would take the adaptive step while cold-tail pushes "
                "fold additively — set hot_tier >= num_ids (the fold's "
                "state shards over the replica axis, so full replication "
                "does not replicate it)"
            )
        if sl.combine not in ("sum", "mean"):
            raise ValueError(
                f"table {spec.name!r}: hot_fold={fold.kind!r} needs a "
                f"'sum'/'mean' combine (got {sl.combine!r}) — the fold "
                "consumes the window's combined delta sum"
            )

    def _hot_tier_map(self) -> dict[str, int]:
        """{table: replicated head rows} for every table the tier resolves
        ON for. Empty dict = the untiered program of old, byte-identical."""
        tier = {}
        for name, spec in self.store.specs.items():
            H = self._resolve_hot_tier(spec)
            if H:
                tier[name] = H
        if tier and self.config.push_delay:
            raise ValueError(
                "hot_tier and push_delay cannot combine: delayed delivery "
                "would re-order the windowed reconcile against the ring "
                "buffer. Disable one (hot tier tables: "
                f"{sorted(tier)})"
            )
        return tier

    def _hot_fold_map(self) -> dict:
        """{table: HotFold} for tables whose resolved tier carries a
        stateful server fold (validated by :meth:`_check_hot_fold`).
        Part of the compile-cache key via :meth:`_server_logic_key`."""
        out = {}
        for name in self._hot_tier_map():
            fold = as_hot_fold(self.server_logic[name].hot_fold)
            if fold is not None:
                out[name] = fold
        return out

    def _cold_compact_map(self) -> dict[str, int]:
        """{table: per-worker cold-lane width} for tables on the
        COMPACTED cold routes (``TableSpec.cold_budget``; docs/
        performance.md "Payload-proportional routing"): a partial hot
        head (0 < H < num_ids) on a non-dense route with a positive
        budget. The compacted program is a distinct compile-cache entry;
        whether a given chunk may dispatch it is the host certifier's
        per-chunk call (:meth:`_certify_cold`)."""
        out = {}
        for name, H in sorted(self._hot_tier_map().items()):
            spec = self.store.specs[name]
            C = int(getattr(spec, "cold_budget", 0) or 0)
            if C <= 0 or H >= spec.num_ids:
                continue
            if self._resolve_dense(spec):
                continue  # dense routes move table-sized payloads anyway
            out[name] = C
        return out

    def _certify_cold(self, host_ids) -> tuple[bool, list[str]]:
        """Host-side per-chunk certification for the compacted cold
        routes: every (step, worker) slice's cold-id count must fit the
        lane. ``host_ids`` is ``WorkerLogic.pulled_ids_host``'s dict (or
        None = uncertifiable). Counts are conservative — padding
        positions count like real ids, exactly as the device-side
        compaction sees them. Returns ``(fits, overflowed_tables)``;
        an uncertifiable chunk reports every compacted table."""
        from fps_tpu.core.ingest import per_worker_cold_counts

        compact = self._cold_compact_map()
        overflowed = []
        for name, C in compact.items():
            arr = None if host_ids is None else host_ids.get(name)
            if arr is None:
                overflowed.append(name)
                continue
            H = self._hot_tier_map()[name]
            member = None
            if name in self._mapped_tables() and self.retierer is not None:
                num_ids = self.store.specs[name].num_ids
                member = np.zeros(num_ids + 1, bool)
                member[self.retierer.hot_ids_for(name, H)] = True
            counts = per_worker_cold_counts(
                arr, self.num_workers, hot_head=H, hot_member=member)
            if int(counts.max(initial=0)) > C:
                overflowed.append(name)
        return not overflowed, overflowed

    def _host_cert_ids(self, chunk):
        """The logic's host certification stream for a raw host chunk
        (None when the logic cannot certify, or nothing is compacted)."""
        if not self._cold_compact_map():
            return None
        return self.logic.pulled_ids_host(chunk)

    def _mapped_tables(self) -> dict[str, int]:
        """{table: H} for tables on the ADAPTIVE (mapped) tier: the
        replica's membership is an arbitrary hot id set carried as
        replicated slot-map/gid DATA arrays, so the attached Retierer
        can re-rank without recompiling. Engages for tiered tables with
        a partial head (0 < H < num_ids) under a Retierer; full
        replication keeps the static elision (every id is hot — there
        is nothing to re-rank), and without a Retierer the static
        frequency-ranked head of old is lowered unchanged. Part of the
        compile-cache key."""
        if self.retierer is None:
            return {}
        out = {}
        for name, H in sorted(self._hot_tier_map().items()):
            if (H < self.store.specs[name].num_ids
                    and self.retierer.manages(name)):
                out[name] = H
        return out

    def _track_specs(self) -> dict:
        """{table: CountMinSpec} for tables whose pulled ids the
        compiled step sketches device-side (fps_tpu.sketch count-min
        windows, psum-merged across the mesh at the end of each call).
        Empty without a Retierer — the tracked program differs from the
        untiered one, so this is part of the compile-cache key.

        Sketching is paid only where a decision can consume it: during
        an auto-plan warmup every managed table (the planner needs
        densities for all of them); afterwards — or when the knobs were
        set by hand — only tables the RESOLVED tier actually maps
        (0 < H < num_ids, the re-rankable regime). Gating on the
        resolution, not the raw spec, keeps the documented
        disengagement states honest: hot_sync_every=1 / single-device /
        non-additive folds still lower the exact untiered program even
        with a Retierer attached (tested)."""
        if self.retierer is None:
            return {}
        track = self.retierer.track_specs(self.store.specs)
        if self.retierer.auto_plan and not self.retierer.planned:
            return track
        mapped = self._mapped_tables()
        return {n: cm for n, cm in sorted(track.items()) if n in mapped}

    def _attach_hot(self, tables, timer=None):
        """Entry-point re-split: make ``tables`` carry exactly the
        tiering aux entries the current resolution calls for — hot
        replicas (static AND mapped), the adaptive tier's slot-map/gid
        arrays, and the tracker's device sketch windows.

        Replicas are derived from the canonical sharded table — valid at
        any call boundary because every compiled call ends with a flush
        reconcile. Covers every way state reaches a run: ``init_state``,
        ``restore_checkpoint`` (a checkpoint is one canonical table;
        this is the re-split — mapped membership and sketch windows come
        from the Retierer, sidecar-restored under supervision), warm
        starts, and config changes between runs (stale/resized entries
        are dropped and re-derived; a tier turned off strips its entries
        so the lowered program is the untiered one again). Idempotent
        and O(specs) when nothing changed, so the per-chunk call from
        ``run_chunk`` costs dict lookups only.
        """
        tier = self._hot_tier_map()
        mapped = self._mapped_tables()
        track = self._track_specs()
        # Both kinds of ``::fold`` entry: the hot tier's and the table's own.
        folds = {**self._hot_fold_map(), **self._row_fold_map()}
        if not (tier or track or folds) and not any(
                is_aux_key(k) for k in tables):
            return tables
        out = {}
        for k, v in tables.items():
            if not is_aux_key(k):
                out[k] = v
            elif is_hot_key(k):
                name = hot_base(k)
                if name in tier and v.shape[0] == tier[name]:
                    out[k] = v  # live, correctly-sized replica: keep
            elif k.endswith(MAP_KEY_SUFFIX):
                name = k[: -len(MAP_KEY_SUFFIX)]
                if (name in mapped and v.shape[0]
                        == self.store.specs[name].num_ids + 1):
                    out[k] = v
            elif k.endswith(IDS_KEY_SUFFIX):
                name = k[: -len(IDS_KEY_SUFFIX)]
                if name in mapped and v.shape[0] == mapped[name]:
                    out[k] = v
            elif k.endswith(SKETCH_KEY_SUFFIX):
                name = k[: -len(SKETCH_KEY_SUFFIX)]
                cm = track.get(name)
                if cm is not None and v.shape == (cm.depth, cm.width):
                    out[k] = v
            elif k.endswith(FOLD_KEY_SUFFIX):
                name = k[: -len(FOLD_KEY_SUFFIX)]
                if name in folds and tuple(
                        v.shape) == self._fold_state_shape(name):
                    out[k] = v  # live/restored state: keep (not derivable)
        missing_hot = [n for n in sorted(tier) if hot_key(n) not in out]
        missing_map = [n for n in sorted(mapped)
                       if map_key(n) not in out or ids_key(n) not in out]
        missing_sk = [n for n in sorted(track)
                      if sketch_key(n) not in out]
        missing_fold = [n for n in sorted(folds)
                        if fold_key(n) not in out]
        if not (missing_hot or missing_map or missing_sk or missing_fold):
            return out
        # Only an actual derivation pays (and records) the reconcile
        # phase — the steady-state per-chunk call is pure dict checks.
        with _phase(timer, "reconcile"):
            for name in missing_hot:
                if name in mapped:
                    gids = self.retierer.hot_ids_for(name, mapped[name])
                    out[hot_key(name)] = self.store.rows_replica(
                        name, gids, out[name])
                else:
                    out[hot_key(name)] = self.store.head_replica(
                        name, tier[name], out[name])
            for name in missing_map:
                gids = self.retierer.hot_ids_for(name, mapped[name])
                out[ids_key(name)] = jax.device_put(
                    np.asarray(gids, np.int32), self._replicated)
                out[map_key(name)] = jax.device_put(
                    hot_slot_map(self.store.specs[name].num_ids, gids),
                    self._replicated)
            for name in missing_sk:
                cm = track[name]
                win = (self.retierer.device_window(name)
                       if self.retierer is not None else None)
                if win is None or win.shape != (cm.depth, cm.width):
                    win = np.zeros((cm.depth, cm.width), np.float32)
                out[sketch_key(name)] = jax.device_put(
                    np.asarray(win, np.float32), self._replicated)
            for name in missing_fold:
                # Fresh optimizer state (zero, or Adagrad's declared
                # ``initial_accumulator``), SHARDED over the shard
                # axis in reduce-scatter slice order; restored states
                # arrive already in ``tables`` (checkpoint ``fold::``
                # arrays) and were kept above. Made on the device: a
                # table's own state is the table's size.
                shape = self._fold_state_shape(name)
                start = folds[name].initial_accumulator
                out[fold_key(name)] = jax.jit(
                    lambda shape=shape, start=start: jnp.full(
                        shape, start, jnp.float32),
                    out_shardings=self._table_sharding)()
        return out

    def _enter_tiering(self) -> None:
        """Run-entry adaptive-tiering hook (both drivers): auto-attach
        the default Retierer when ``TrainerConfig.auto_tier`` asks for
        one, and re-apply a (sidecar-)restored plan so the tier
        resolution — and with it the compile key — matches the
        interrupted run before the first compiled call."""
        if self.config.auto_tier and self.retierer is None:
            from fps_tpu.tiering import Retierer

            self.retierer = Retierer.auto_for(self)
        if self.retierer is not None:
            if self.retierer.auto_plan and self.config.push_delay:
                # Same contract as the explicit hot_tier+push_delay
                # rejection, enforced at run entry instead of blowing up
                # at the first check boundary when the planner's tier
                # lands mid-run.
                raise ValueError(
                    "auto_tier and push_delay cannot combine: the "
                    "planner would enable a hot tier whose windowed "
                    "reconcile re-orders against the delayed-push ring "
                    "buffer. Disable one."
                )
            self.retierer.on_run_entry(self)

    def _head_prefix(self, batch) -> dict:
        """Resolve the worker's head-prefix guarantee for this batch.

        Honored only on single-device meshes: the collective pull/push
        routes reorder the id streams (all_gather across workers, physical
        re-indexing in the dense route), voiding the leading-ids
        guarantee. Requires the table to declare its frequency head via
        ``spec.hot_ids`` (an int H — the prefix ids must lie in
        ``[0, H) ∪ {-1}``)."""
        if self.num_shards * self.mesh.shape[DATA_AXIS] != 1:
            return {}
        out = {}
        for name, n in (self.logic.head_prefix(batch) or {}).items():
            spec = self.store.specs.get(name)
            if (spec is not None and isinstance(spec.hot_ids, int)
                    and spec.hot_ids > 0 and n):
                out[name] = int(n)
        return out

    def _apply_pushes(self, tables, pushes, head_prefix=None):
        head_prefix = head_prefix or {}
        new_tables = dict(tables)
        # named_scope: pure HLO metadata — the device-profile analog of the
        # host PhaseTimer (pull/compute/push are fused into one dispatch,
        # so their split is only visible on the traced timeline).
        with jax.named_scope("fps.push"):
            new_tables.update(self._apply_pushes_inner(tables, pushes,
                                                       head_prefix))
        return new_tables

    def _apply_pushes_inner(self, tables, pushes, head_prefix):
        new_tables = {}
        folds = self._row_fold_map()
        for name, (pids, pdeltas) in pushes.items():
            spec = self.store.specs[name]
            hot_local = self._resolve_hot_rows(spec)
            pushed = push(
                tables[name],
                pids,
                pdeltas,
                num_shards=self.num_shards,
                shard_axis=SHARD_AXIS,
                data_axis=self._data_axis,
                apply_fn=self.server_logic[name].apply_fn,
                combine=self.server_logic[name].combine,
                hot_rows=hot_local,
                dense=self._resolve_dense(spec),
                head_prefix=head_prefix.get(name, 0),
                table=name,
                fold=folds.get(name),
                fold_state=tables.get(fold_key(name)),
            )
            if name in folds:
                # The table's own optimizer state rides the step's tables
                # under its ``::fold`` key (:meth:`_with_row_folds`).
                new_tables[name], new_tables[fold_key(name)] = pushed
            else:
                new_tables[name] = pushed
        return new_tables

    def _with_row_folds(self, tables, fstates):
        """The state of the tables' own folds moved from ``fstates`` (what
        ``split_tiering`` set apart) back beside their tables under the
        ``::fold`` key, where a step's push reads and writes it; the hot
        tier's states stay in ``fstates``."""
        mine = self._row_fold_map()
        return ({**tables, **{fold_key(n): v for n, v in fstates.items()
                              if n in mine}},
                {n: v for n, v in fstates.items() if n not in mine})

    def _compute_step(self, tables, snapshot, local_state, batch, key,
                      hot=None, tier=None, maps=None, track=None,
                      sk=None, compact=None, dense=None):
        """Pull (from live tables, or the SSP ``snapshot`` when given), run
        the worker step, and return its pushes WITHOUT applying them,
        plus the (static) head-prefix guarantee for those pushes, the
        hot-tier pull accounting ({} when the tier is off — nothing extra
        is traced then), the updated sketch accumulators, and ``dense``
        after the step's fold (:meth:`_fold_dense`; handed back as it
        came, ``None`` or empty, by a logic that declares none).

        ``hot``/``tier``: the replicated hot-head arrays and the resolved
        {table: H} map. Sync-mode pulls partition on hot membership: hot
        rows are a LOCAL replica gather (zero collectives — when H covers
        the whole table the collective route is statically elided
        outright); cold rows ride the existing routes with hot slots
        masked to -1 (the zero-row contract). Membership is ``id < H``
        on the static tier, or a replicated slot-map lookup on the
        ADAPTIVE tier (``maps`` — arbitrary hot id set as DATA, so
        re-ranks never recompile). SSP pulls already read a local
        snapshot whose head rows match the replica (reconcile precedes
        each round's gather), so they stay untouched.

        ``track``/``sk``: online frequency tracking — every tracked
        table's pulled ids fold into its count-min window accumulator
        (a local scatter-add; the psum merge happens once per call).
        """
        tier = tier or {}
        maps = maps or {}
        track = track or {}
        compact = compact or {}
        key, prep_key = jax.random.split(key)
        # fps.prepare: the worker's own sampling before the pull (SGNS
        # draws its negatives here), beside fps.pull, not under it.
        with jax.named_scope("fps.prepare"):
            batch = self.logic.prepare(batch, prep_key)
        ids = self.logic.pull_ids(batch)
        hp = self._head_prefix(batch)
        if track:
            sk = dict(sk)
            with jax.named_scope("fps.sketch"):
                for name in sorted(track):
                    if name in ids:
                        sk[name] = _sketch.cm_update(
                            track[name], sk[name], ids[name])
        hot_counts = {}
        data_axis = self._data_axis
        # fps.pull / fps.compute named scopes: device-timeline attribution
        # for the phases the host PhaseTimer cannot split (pull, worker
        # compute, and push fuse into one dispatch) — pure op metadata,
        # visible under obs.trace() / --profile, free otherwise.
        with jax.named_scope("fps.pull"):
            if snapshot is None:
                pulled = {}
                for name, tids in ids.items():
                    H = tier.get(name, 0)
                    spec = self.store.specs[name]
                    # Hit-rate accounting only where the replica actually
                    # serves the reads: SSP pulls come from the per-round
                    # snapshot, so counting them would misattribute
                    # snapshot gathers as collective-free tier hits.
                    if H:
                        live = jnp.sum(tids >= 0, dtype=jnp.int32)
                        # Logged before the gather.* entry of the replica
                        # read it ends in (as pull.snapshot is).
                        ops.log_route("pull", "hot", *hot[name].shape,
                                      tids.shape[0], f"table={name}")
                    if H >= spec.num_ids:
                        # Fully-replicated table: the collective route is
                        # statically gone — a plain local gather.
                        pulled[name] = ops.gather_rows(hot[name], tids)
                        hot_counts[name] = {"hot_rows": live,
                                            "pulled_rows": live}
                        continue
                    if H and name in maps:
                        # Adaptive tier: membership by slot-map lookup.
                        slot = lookup_hot_slots(maps[name], tids)
                        hmask = slot >= 0
                        hot_vals = ops.gather_rows(
                            hot[name],
                            jnp.where(hmask, slot,
                                      jnp.asarray(-1, slot.dtype)))
                        tids = jnp.where(hmask,
                                         jnp.asarray(-1, tids.dtype), tids)
                    elif H:
                        hot_vals, hmask = pull_hot(hot[name], tids,
                                                   hot_ids=H)
                        tids = jnp.where(hmask,
                                         jnp.asarray(-1, tids.dtype), tids)
                    if H:
                        hot_counts[name] = {
                            "hot_rows": jnp.sum(hmask, dtype=jnp.int32),
                            "pulled_rows": live,
                        }
                    if H and name in compact:
                        # Payload-proportional cold pull: pack the cold
                        # residue into the certified lane, pull O(lane)
                        # through the collective route, scatter the lane
                        # rows back to their batch positions (masked /
                        # dropped slots read zero rows — the -1
                        # contract).
                        lane_ids, _, pos, over = compact_cold(
                            tids, None, budget=compact[name])
                        lane_vals = pull(
                            tables[name], lane_ids,
                            num_shards=self.num_shards,
                            dense=False,
                            hot_rows=self._resolve_hot_rows(spec),
                            data_axis=data_axis, table=name,
                        )
                        vals = ops.gather_rows(lane_vals, pos)
                        hot_counts[name]["cold_dropped"] = over
                    else:
                        vals = pull(
                            tables[name], tids, num_shards=self.num_shards,
                            dense=self._resolve_dense(spec),
                            hot_rows=self._resolve_hot_rows(spec),
                            head_prefix=hp.get(name, 0),
                            data_axis=data_axis, table=name,
                        )
                    if H:
                        vals = jnp.where(hmask[:, None], hot_vals, vals)
                    pulled[name] = vals
            else:
                pulled = {}
                for name, tids in ids.items():
                    rps = tables[name].shape[0]
                    # -1 padding ids must stay -1 (the zero-row pull
                    # contract): id_to_phys's floor-mod would wrap them onto
                    # the live row (S-1)*rps-1 when num_shards > 1 — the
                    # same hazard the dense pull in store.py guards.
                    phys = jnp.where(
                        tids >= 0, id_to_phys(tids, self.num_shards, rps), -1)
                    # ops.gather_rows (not a bare take): dim-1 snapshot reads
                    # ride the same lane-packed kernel as live pulls on TPU.
                    # phys == ids on the single-device meshes where hp is
                    # nonempty, so the head guarantee survives the mapping.
                    # Logged before the gather.* entry of the call it ends in.
                    ops.log_route("pull", "snapshot", *snapshot[name].shape,
                                  phys.shape[0])
                    pulled[name] = ops.gather_rows(
                        snapshot[name], phys,
                        hot_rows=self._resolve_hot_rows(
                            self.store.specs[name]),
                        head_prefix=hp.get(name, 0),
                    )
        with jax.named_scope("fps.compute"):
            if dense:
                out = self.logic.step(batch, pulled, local_state, key,
                                      dense=dense)
            else:
                out = self.logic.step(batch, pulled, local_state, key)
        if dense:
            dense = self._fold_dense(dense, out.dense_grads)
        pushes, outch, new_local = out.pushes, out.out, out.local_state
        guard = resilience.as_guard(self.config.guard)
        if guard is not None:
            # Trace-time static: guard=None compiles byte-identically to a
            # guard-free build (tested via lowered-HLO comparison).
            pushes, health = resilience.guard_pushes(pushes, guard)
            if guard.local:
                # Same screening for the worker-LOCAL plane: revert (mask)
                # or count (observe) poisoned local-state rows, mounted on
                # the health channel under the reserved "local_state" key
                # (collision with a table name rejected at construction).
                # Logics that expose which rows a batch touches
                # (touched_local_rows) get ids-aware screening: row
                # masking restricted to the touched set, untouched rows
                # still netted by the leaf-tier non-finite count.
                new_local, local_health = resilience.guard_local_state(
                    local_state, new_local, guard,
                    touched=self.logic.touched_local_rows(batch),
                )
                if local_health is not None:
                    health[resilience.LOCAL_STATE_KEY] = local_health
            if health:
                if not isinstance(outch, dict):
                    raise TypeError(
                        "TrainerConfig.guard requires the worker's out "
                        "channel to be a dict so the health counters can "
                        f"ride it (got {type(outch).__name__})"
                    )
                if resilience.HEALTH_KEY in outch:
                    raise ValueError(
                        "the worker's out channel already has a 'health' "
                        "key — it would collide with the guard's counters"
                    )
                outch = dict(outch, **{resilience.HEALTH_KEY: health})
        return pushes, new_local, outch, hp, hot_counts, sk, dense

    # -- delayed pushes (async in-flight emulation) ------------------------

    def _init_push_bufs(self, tables, local_state, batch_like, key):
        """Ring buffers of the last ``push_delay`` steps' pushes per table.

        Shapes come from a collective-free ``eval_shape`` probe of the
        worker logic. Slots start as dropped pushes (ids ``-1``), so the
        first ``push_delay`` steps deliver nothing — a cold asynchronous
        start, like the reference's empty network queues.
        """
        d = self.config.push_delay

        def probe(batch, local_state, key):
            key, prep_key = jax.random.split(key)
            with jax.named_scope("fps.prepare"):
                b = self.logic.prepare(batch, prep_key)
            ids = self.logic.pull_ids(b)
            pulled = {
                name: jnp.zeros(
                    tids.shape + (tables[name].shape[-1],),
                    tables[name].dtype,
                )
                for name, tids in ids.items()
            }
            return self.logic.step(b, pulled, local_state, key).pushes

        shapes = jax.eval_shape(probe, batch_like, local_state, key)
        return {
            name: (
                jnp.full((d,) + ids_s.shape, -1, ids_s.dtype),
                jnp.zeros((d,) + del_s.shape, del_s.dtype),
            )
            for name, (ids_s, del_s) in shapes.items()
        }

    def _gather_workers(self, x):
        """Stack a per-worker leaf into (W, ...) in worker_index order."""
        x = lax.all_gather(x, SHARD_AXIS)  # (S, ...)
        x = lax.all_gather(x, DATA_AXIS)  # (D, S, ...)
        return x.reshape((self.num_workers,) + x.shape[2:])

    def _tap_step(self, tables, batch, local_state, t):
        """The step tap's output on the step's PRE-update view, gathered
        over the workers, or None without a tap (nothing is traced then).
        Every step builder calls it BEFORE ``_compute_step``, with the
        tables and worker state step ``t - 1`` left, and mounts the result
        on the step's metrics at its end (:meth:`_mount_tap`)."""
        tap = self.config.step_tap
        if tap is None:
            return None
        with jax.named_scope("fps.tap"):
            return jax.tree.map(self._gather_workers,
                                tap(tables, batch, local_state, t))

    def _mount_tap(self, out, tapped):
        if tapped is None:
            return out
        if not isinstance(out, dict):
            raise TypeError(
                "step_tap requires the worker's out channel to be a dict "
                f"(got {type(out).__name__})"
            )
        if "tap" in out:
            raise ValueError(
                "the worker's out channel already has a 'tap' key — it "
                "would be silently clobbered by the step_tap output"
            )
        return dict(out, tap=tapped)

    def _apply_or_buffer(self, tables, bufs, t, pushes, head_prefix=None):
        """Apply ``pushes`` now (push_delay 0) or deliver the pushes from
        ``push_delay`` steps ago and enqueue the new ones in their slot.
        Ring slots preserve the push layout, so the head-prefix guarantee
        carries over to delayed deliveries unchanged."""
        d = self.config.push_delay
        if not d:
            return self._apply_pushes(tables, pushes, head_prefix), bufs
        slot = t % d
        new_bufs = {}
        delayed = {}
        for name, (ids, deltas) in pushes.items():
            bids, bdel = bufs[name]
            delayed[name] = (
                lax.dynamic_index_in_dim(bids, slot, 0, keepdims=False),
                lax.dynamic_index_in_dim(bdel, slot, 0, keepdims=False),
            )
            new_bufs[name] = (
                lax.dynamic_update_index_in_dim(bids, ids, slot, 0),
                lax.dynamic_update_index_in_dim(bdel, deltas, slot, 0),
            )
        return self._apply_pushes(tables, delayed, head_prefix), new_bufs

    def _flush_push_bufs(self, tables, bufs, t, head_prefix=None):
        """Deliver everything still in flight, oldest first (end of call).

        Cold ring slots hold all ``-1`` ids with zero deltas — inside the
        head-prefix contract, so the guarantee applies to them too."""
        d = self.config.push_delay
        if not d:
            return tables

        def body(k, tables):
            slot = (t + k) % d
            pending = {
                name: (
                    lax.dynamic_index_in_dim(bids, slot, 0, keepdims=False),
                    lax.dynamic_index_in_dim(bdel, slot, 0, keepdims=False),
                )
                for name, (bids, bdel) in sorted(bufs.items())
            }
            return self._apply_pushes(tables, pending, head_prefix)

        return lax.fori_loop(0, d, body, tables)

    # -- two-tier hot storage (device-side step/window plumbing) ----------

    def _hot_combine(self, name: str) -> str:
        return self.server_logic[name].combine

    def _hot_fold(self, name: str):
        return self._hot_fold_map().get(name)

    def _init_hot_deltas(self, tables, tier):
        """Fresh per-device pending-delta buffers ({} when untiered).
        Created inside the traced call and flushed before it returns, so
        they never exist at a host-visible boundary."""
        return {
            name: hot_delta_init(
                H, tables[name].shape[1], tables[name].dtype,
                combine=self._hot_combine(name),
                fold=self._hot_fold(name),
            )
            for name, H in tier.items()
        }

    def _apply_hot_split(self, tables, delta, pushes, tier, hp,
                         maps=None, compact=None):
        """Partition each table's pushes on hot membership (``id < H``
        statically, or the adaptive tier's slot-map lookup), apply the
        cold part through the existing routes (statically elided when H
        covers the table, COMPACTED to the ``cold_budget`` lane when the
        table rides the payload-proportional route) and fold the hot
        part into the pending buffers. Returns the per-table count of
        budget-overflow drops alongside (always zero for host-certified
        chunks — the device-side observability net)."""
        if not tier:
            return self._apply_pushes(tables, pushes, hp), delta, {}
        maps = maps or {}
        compact = compact or {}
        cold_pushes = {}
        dropped = {}
        new_delta = dict(delta)
        # Push work, once a step: INSIDE fps.push (path
        # fps.push/fps.hot_accumulate), so what divides by the push's
        # time keeps all of it when the tier takes rows off the cold route.
        with jax.named_scope("fps.push"), \
                jax.named_scope("fps.hot_accumulate"):
            for name, (pids, pdeltas) in pushes.items():
                H = tier.get(name, 0)
                if not H:
                    cold_pushes[name] = (pids, pdeltas)
                    continue
                spec = self.store.specs[name]
                ops.log_route("push", "hot", H, delta[name].shape[1],
                              pids.shape[0], f"table={name}")
                if H >= spec.num_ids:
                    hots = (pids, pdeltas)  # no cold residue to push
                elif name in maps:
                    # Adaptive tier: the hot half lands in SLOT space —
                    # the delta buffer is slot-indexed like the replica.
                    slot = lookup_hot_slots(maps[name], pids)
                    cold_pushes[name], hots = split_hot_push_slots(
                        pids, pdeltas, slot
                    )
                else:
                    cold_pushes[name], hots = split_hot_push(
                        pids, pdeltas, hot_ids=H
                    )
                if name in cold_pushes and name in compact:
                    # Payload-proportional cold push: pack the residue
                    # into the certified lane before the collective.
                    cids, cdeltas = cold_pushes[name]
                    lane_ids, lane_deltas, _, over = compact_cold(
                        cids, cdeltas, budget=compact[name])
                    cold_pushes[name] = (lane_ids, lane_deltas)
                    dropped[name] = dropped.get(name, 0) + over
                new_delta[name] = accumulate_hot(
                    delta[name], *hots,
                    combine=self._hot_combine(name),
                    fold=self._hot_fold(name),
                )
        return self._apply_pushes(tables, cold_pushes, hp), new_delta, dropped

    def _reconcile_carry(self, carry, tier, gids=None):
        """Window-boundary reconcile over every tiered table (identity
        when untiered): one reduce-scatter → owned-slice apply →
        all-gather per table (pmax/pmin for the extremum combines) folds
        the pending buffers into replica + canonical table, advances any
        sharded fold state, and resets the buffers. ``gids`` maps
        adaptive-tier tables to their replicated slot->global-id arrays
        (DATA — the mapped reconcile scatters into whichever canonical
        rows the current ranking names, without recompiling)."""
        if not tier:
            return carry
        gids = gids or {}
        tables, hot, delta, folds = (carry[0], carry[1], carry[2],
                                     carry[3])
        tables, hot, delta = dict(tables), dict(hot), dict(delta)
        folds = dict(folds)
        data_axis = self._data_axis
        # Once a WINDOW of hot_sync_every steps: named without the fps.
        # prefix (obs.timing.ROUND_SCOPES), in sync mode and inside
        # ssp.snapshot alike.
        with jax.named_scope("hot.reconcile"):
            for name, H in sorted(tier.items()):
                fold = self._hot_fold(name)
                fstate = folds.get(name)
                # (a tiered table's combine is one of the four strings)
                ops.log_route(
                    "reconcile", "hot", H, delta[name].shape[1], 0,
                    f"table={name} every={self.config.hot_sync_every} "
                    f"combine={self._hot_combine(name)} "
                    f"shards={self.num_shards} "
                    f"bytes={delta[name].size * delta[name].dtype.itemsize}")
                if name in gids:
                    (tables[name], hot[name], delta[name],
                     fstate) = reconcile_hot_mapped(
                        tables[name], hot[name], delta[name],
                        gids[name],
                        num_shards=self.num_shards,
                        data_axis=data_axis,
                        combine=self._hot_combine(name),
                        fold=fold, fold_state=fstate,
                    )
                else:
                    (tables[name], hot[name], delta[name],
                     fstate) = reconcile_hot(
                        tables[name], hot[name], delta[name],
                        num_shards=self.num_shards,
                        data_axis=data_axis,
                        combine=self._hot_combine(name),
                        fold=fold, fold_state=fstate,
                    )
                if fstate is not None:
                    folds[name] = fstate
        return (tables, hot, delta, folds) + tuple(carry[4:])

    def _ssp_round(self, step, carry, xs, tier, gids=None):
        """One SSP round, shared by the three step builders: the snapshot
        of every table (an ``all_gather`` over the shard axis) at its
        head, ``step(carry, x, snapshot)`` scanned over the round's ``xs``
        (pulls read the snapshot, pushes land in the live tables), the hot
        reconcile at its foot (identity when untiered), so the next
        round's gather sees reconciled head rows. Gather and reconcile run
        once a round under ``ssp.snapshot`` (the reconcile as
        ``ssp.snapshot/hot.reconcile``): no ``fps.`` prefix, since a
        reader counts steps by the ops under ``fps.*``
        (``obs.timing.ROUND_SCOPES``). On one shard XLA drops the gather
        and copies the live table itself, under no name: the scope then
        holds no device op, and the round's cost is that copy's."""
        with jax.named_scope("ssp.snapshot"):
            snapshot = {name: lax.all_gather(tb, SHARD_AXIS, tiled=True)
                        for name, tb in sorted(carry[0].items())}
        carry, outs = lax.scan(lambda c, x: step(c, x, snapshot), carry, xs)
        with jax.named_scope("ssp.snapshot"):
            carry = self._reconcile_carry(carry, tier, gids)
        return carry, outs

    def _windowed_scan(self, step, carry0, tier, *, head, tail,
                       gids=None):
        """Scan in reconcile windows: ``head`` is the stacked xs of the
        full windows (leading dims ``(R, E)``, or None when R == 0),
        ``tail`` the ragged remainder's xs (or None). Each window — and
        the tail — ends in a reconcile, so the final carry always holds
        one canonical table. Shared by the chunked and indexed sync
        builders so the window/flush semantics cannot drift between the
        two drivers."""

        def window_body(c, xs_w):
            c, o = lax.scan(step, c, xs_w)
            return self._reconcile_carry(c, tier, gids), o

        parts, carry = [], carry0
        if head is not None:
            carry, outs_h = lax.scan(window_body, carry, head)
            parts.append(jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), outs_h))
        if tail is not None:
            carry, outs_t = lax.scan(step, carry, tail)
            carry = self._reconcile_carry(carry, tier, gids)
            parts.append(outs_t)
        outs = parts[0] if len(parts) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *parts)
        return carry, outs

    def _mount_hot_channel(self, out, hot_counts, delta, tier,
                           dropped=None, routed=None):
        """Attach the hot-tier telemetry to the worker out channel (the
        health channel's transport): per-table hit counts plus the
        pending-buffer magnitude — the parameter-plane staleness gauge —
        and, on the compacted cold routes, the budget-overflow drop
        count (zero for every host-certified chunk); and, where the
        table's cold rows cross shards by the non-dense exchange, the
        step's ``routed`` flag (``store.watch_routed``: 1 where its pull
        and push ran owner-routed, 0 where one ran gathered; worker 0
        alone carries it into the channel's sum). Traced only when
        the tier is on; same dict/collision contract as the guard's
        health entry."""
        if not tier:
            return out
        if not isinstance(out, dict):
            raise TypeError(
                "TableSpec.hot_tier requires the worker's out channel to "
                "be a dict so the hot-tier counters can ride it (got "
                f"{type(out).__name__})"
            )
        if resilience.HOT_TIER_KEY in out:
            raise ValueError(
                "the worker's out channel already has a 'hot_tier' key — "
                "it would collide with the tier's counters"
            )
        dropped = dropped or {}
        routed = routed or {}
        chan = {}
        for name, H in sorted(tier.items()):
            counts = dict(hot_counts.get(name, {}))
            if name in dropped:
                counts["cold_dropped"] = (
                    counts.get("cold_dropped", 0) + dropped[name])
            if name in routed:
                counts["routed"] = jnp.where(worker_index() == 0,
                                             routed[name], 0)
            buf = delta[name]
            combine = self._hot_combine(name)
            dim = buf.shape[1] - (
                1 if (combine in ("max", "min")
                      or delta_counted(combine, self._hot_fold(name)))
                else 0)
            vals = buf[:, :dim].astype(jnp.float32)
            if combine in ("max", "min"):
                # The extremum buffer is sentinel-filled; only touched
                # rows (indicator column == 1) carry real magnitudes.
                touched = jnp.abs(buf[:, dim]) <= 1.0
                vals = jnp.where(touched[:, None], vals, 0.0)
            # Per-device sum of squared pending deltas (psum'd with the
            # rest of the out channel into the global magnitude).
            counts["delta_sq"] = jnp.sum(vals ** 2)
            chan[name] = counts
        return dict(out, **{resilience.HOT_TIER_KEY: chan})

    @staticmethod
    @contextlib.contextmanager
    def _watch_counts():
        """Open the store's count watches round a traced step: what
        :meth:`_mount_counts` is handed, ``{channel: {table: {count:
        scalar}}}`` by the channels of ``resilience.COUNT_KEYS``."""
        with watch_sum_runs() as summed, watch_fold_rows() as folded, \
                watch_distinct_pulls() as pulled:
            yield {resilience.SUM_RUNS_KEY: summed,
                   resilience.FOLD_ROWS_KEY: folded,
                   resilience.DISTINCT_PULLS_KEY: pulled}

    @staticmethod
    def _mount_counts(out, noted):
        """Attach what the step's pushes counted on the device
        (``noted``: ``{channel: {table: {count: scalar}}}``, the channels
        of ``resilience.COUNT_KEYS``: ``store.watch_sum_runs``, a table's
        pushes handed and kept and the distinct ids among them, a shard,
        on ``push.sum_runs``; ``store.watch_fold_rows``, the same on
        ``push.fold_rows``; ``store.watch_distinct_pulls``, a table's
        pulled ids kept and the distinct ones among them on
        ``pull.distinct_rows``) to the worker out channel, whose sum over
        the workers makes them the step's: plain leaves
        ``<channel>.<table>.<count>`` beside the worker's own (a consumer
        of per-step metrics sees arrays, no nested channel), a push's from
        the first data replica alone (a replica's shards are handed every
        replica's pushes; a pull's ids are the replica's own, and every
        replica's count). Nothing noted, nothing mounted: a program
        without the routes grows no leaf."""
        leaves = {
            f"{channel}.{name}.{k}": (channel, v)
            for channel, tables in noted.items()
            for name, counts in sorted(tables.items())
            for k, v in counts.items()}
        if not leaves:
            return out
        if not isinstance(out, dict) or set(leaves) & set(out):
            raise TypeError(
                "the step's counts ride the worker's out channel: it "
                f"must be a dict without the keys {sorted(leaves)}")
        first = lax.axis_index(DATA_AXIS) == 0
        return dict(out, **{
            k: (v if channel == resilience.DISTINCT_PULLS_KEY
                else jnp.where(first, v, 0)).astype(jnp.float32)
            for k, (channel, v) in leaves.items()})

    @staticmethod
    def _counts_channels(metrics) -> dict:
        """``{channel: {table: {count: per-step values}}}`` from the
        leaves :meth:`_mount_counts` put into a unit's metrics; ``{}``
        where there are none."""
        chans: dict = {}
        for key in metrics if isinstance(metrics, Mapping) else ():
            channel, _, rest = (key if isinstance(key, str)
                                else "").partition(".")
            if channel in resilience.COUNT_KEYS:
                table, _, count = rest.rpartition(".")
                chans.setdefault(channel, {}).setdefault(
                    table, {})[count] = metrics[key]
        return chans

    def _merge_sketches(self, sketches, sk):
        """End-of-call sketch merge: psum each tracked table's LOCAL
        window accumulator over the worker axes and fold it into the
        (replicated) incoming window — exactly the sketch module's
        additive psum-merge contract, once per compiled call (the
        per-step updates are local scatter-adds). Returns the
        ``::sketch``-keyed output entries; {} when tracking is off, so
        untracked programs trace nothing extra."""
        if not sk:
            return {}
        with jax.named_scope("fps.sketch_merge"):
            return {
                sketch_key(name): sketches[name] + lax.psum(
                    lax.psum(sk[name], SHARD_AXIS), DATA_AXIS)
                for name in sorted(sk)
            }

    # -- compiled chunk runners ------------------------------------------

    def _build_chunk_fn(self, mode: str, compact=None):
        nbatch_dims = 1 if mode == "sync" else 2
        tier = self._hot_tier_map()
        mapped = self._mapped_tables()
        track = self._track_specs()
        folds_on = {**self._hot_fold_map(), **self._row_fold_map()}
        compact = dict(compact or {})
        E = self.config.hot_sync_every

        def chunk_device(tables, local_state, batches, key):
            # Per-device key stream, decorrelated across workers.
            key = jax.random.fold_in(key, worker_index())
            tables, dense = split_dense(tables)
            (tables, hot, maps, gids, sketches,
             fstates) = split_tiering(tables)
            tables, fstates = self._with_row_folds(tables, fstates)
            delta = self._init_hot_deltas(tables, tier)
            # Sketch accumulators start at ZERO: each device folds only
            # its own ids, and the end-of-call psum merges exactly the
            # call's traffic into the (replicated) incoming window.
            sk0 = {name: jnp.zeros_like(sketches[name])
                   for name in sorted(track)}
            bufs = None
            if self.config.push_delay:
                batch0 = jax.tree.map(
                    lambda x: x[(0,) * nbatch_dims], batches
                )
                bufs = self._init_push_bufs(tables, local_state, batch0, key)

            hp_seen = {}

            def step_fn(carry, batch_t, snapshot=None):
                (tables, hot, delta, fstates, sk, bufs, local_state,
                 key, t, dense) = carry
                key, sub = jax.random.split(key)
                tapped = self._tap_step(tables, batch_t, local_state, t)
                with watch_routed() as routed, \
                        self._watch_counts() as counted:
                    (pushes, local_state, out, hp, hcounts,
                     sk, dense) = self._compute_step(
                        tables, snapshot, local_state, batch_t, sub,
                        hot=hot, tier=tier, maps=maps, track=track, sk=sk,
                        compact=compact, dense=dense,
                    )
                    hp_seen.update(hp)  # static, the same every traced step
                    dropped = {}
                    if tier:
                        tables, delta, dropped = self._apply_hot_split(
                            tables, delta, pushes, tier, hp, maps, compact)
                    else:
                        tables, bufs = self._apply_or_buffer(
                            tables, bufs, t, pushes, hp)
                out = self._mount_hot_channel(out, hcounts, delta, tier,
                                              dropped, routed)
                out = self._mount_counts(out, counted)
                with jax.named_scope("fps.metrics"):
                    out = jax.tree.map(
                        lambda x: lax.psum(lax.psum(x, SHARD_AXIS),
                                           DATA_AXIS), out
                    )
                out = self._mount_tap(out, tapped)
                return (tables, hot, delta, fstates, sk, bufs,
                        local_state, key, t + 1, dense), out

            carry0 = (tables, hot, delta, fstates, sk0, bufs,
                      local_state, key, jnp.int32(0), dense)
            if mode == "sync":
                if not tier:
                    carry, outs = lax.scan(step_fn, carry0, batches)
                else:
                    # Windows of E steps, a flush reconcile on the ragged
                    # tail: the call always returns one canonical table.
                    T = jax.tree.leaves(batches)[0].shape[0]
                    R, rem = divmod(T, E)
                    carry, outs = self._windowed_scan(
                        step_fn, carry0, tier,
                        head=jax.tree.map(
                            lambda x: x[:R * E].reshape(
                                (R, E) + x.shape[1:]),
                            batches) if R else None,
                        tail=jax.tree.map(lambda x: x[R * E:], batches)
                        if rem else None,
                        gids=gids,
                    )
                (tables, hot, delta, fstates, sk, bufs, local_state, _,
                 t, dense) = carry
            else:
                # SSP: batches leaves are (R, s, B_local, ...).
                (tables, hot, delta, fstates, sk, bufs, local_state, _,
                 t, dense), outs = lax.scan(
                    lambda c, batches_r: self._ssp_round(
                        step_fn, c, batches_r, tier, gids),
                    carry0, batches)
                outs = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), outs
                )
            tables = self._flush_push_bufs(tables, bufs, t, hp_seen)
            tables = {**tables,
                      **{dense_key(n): v for n, v in sorted(dense.items())},
                      **{hot_key(n): v for n, v in sorted(hot.items())},
                      **{map_key(n): v for n, v in sorted(maps.items())},
                      **{ids_key(n): v for n, v in sorted(gids.items())},
                      **{fold_key(n): v
                         for n, v in sorted(fstates.items())},
                      **self._merge_sketches(sketches, sk)}
            return tables, local_state, outs

        table_specs = {name: P(SHARD_AXIS, None) for name in self.store.specs}
        table_specs.update({hot_key(name): P() for name in tier})
        table_specs.update({map_key(name): P() for name in sorted(mapped)})
        table_specs.update({ids_key(name): P() for name in sorted(mapped)})
        table_specs.update({sketch_key(name): P()
                            for name in sorted(track)})
        # Fold state: SHARDED over the shard axis (reduce-scatter slice
        # order), replicated over data — never a full copy per device.
        table_specs.update({fold_key(name): P(SHARD_AXIS, None)
                            for name in sorted(folds_on)})
        table_specs.update(self._dense_specs())
        ls_spec = P(WORKER_AXES)

        def specs_for_batches(batches):
            return jax.tree.map(
                lambda _: P(*([None] * nbatch_dims), WORKER_AXES), batches
            )

        def run(tables, local_state, batches, key):
            shmapped = jax.shard_map(
                chunk_device,
                mesh=self.mesh,
                in_specs=(
                    table_specs,
                    jax.tree.map(lambda _: ls_spec, local_state),
                    specs_for_batches(batches),
                    P(),
                ),
                out_specs=(
                    table_specs,
                    jax.tree.map(lambda _: ls_spec, local_state),
                    P(),  # metrics: psum'd, identical on all devices
                ),
                check_vma=False,
            )
            return shmapped(tables, local_state, batches, key)

        donate = (0, 1) if self.config.donate else ()
        return jax.jit(run, donate_argnums=donate)

    def _server_logic_key(self):
        """Identity key over the per-table server logics: combine modes and
        apply_fns are baked into the compiled program as constants, so
        swapping ``trainer.server_logic['t']`` after a compile must miss
        the cache (same reason the ops backend is in the key). Callables go
        into the key AS OBJECTS (identity hash + a live reference) — a bare
        ``id()`` could be reused by a later callable after the original is
        garbage-collected, silently hitting a stale compiled program."""
        return tuple(
            (name, sl.combine, sl.apply_fn, as_hot_fold(sl.hot_fold),
             as_hot_fold(sl.fold))
            for name, sl in sorted(self.server_logic.items())
        )

    def _get_compiled(self, mode: str, compact_ok: bool = True,
                      timer=None):
        # Keyed on the ops backend, push_delay, and server logic too:
        # set_backend() or a config/logic change after a compile must take
        # effect on the next chunk, not be shadowed by the jit cache.
        # ``compact_ok``: the host certifier's per-chunk verdict — False
        # selects the static (full-payload) cold-route program, so a
        # budget-overflowing chunk dispatches exactly the cold_budget=0
        # program (bit-identical fallback by construction).
        compact = self._cold_compact_map() if compact_ok else {}
        key = (mode, ops.get_backend(), self.config.push_delay,
               self.config.step_tap, resilience.as_guard(self.config.guard),
               self._server_logic_key(), self.config.hot_sync_every,
               tuple(sorted(self._hot_tier_map().items())),
               # Adaptive tiering: the MAPPED set and the tracked sketch
               # specs shape the traced program; the hot id membership
               # itself is DATA, so re-ranks hit this same cache entry —
               # the no-recompile contract tests/test_tiering.py pins.
               tuple(sorted(self._mapped_tables().items())),
               tuple(sorted(self._track_specs().items())),
               tuple(sorted(compact.items())))
        with host_span("program_lookup", timer) as span:
            span["built"] = key not in self._compiled
            if span["built"]:
                label = "chunk/" + mode + ("+compact" if compact else "")
                self._compiled[key] = self._wrap_audit(
                    self._build_chunk_fn(mode, compact), label)
            return self._compiled[key]

    # -- compile-time program certification (fps_tpu.analysis) ------------

    def _wrap_audit(self, fn, label: str):
        """``fn`` behind its first-call seam
        (:func:`fps_tpu.obs.timing.watch_program`): ``self.audit``, where
        set at build time, certifies the lowered program before the first
        call, and under a process-default recorder a ``program.memory``
        span states what the compiled program needs; with neither ``fn``
        itself comes back.

        The audit lowers once more — trace cost only, paid once per
        compiled program — and hands the StableHLO text to the auditor;
        the actual dispatch path is the unmodified jitted callable, so
        donation/caching behavior is untouched. ``.lower`` passes
        through for callers that inspect programs directly.
        """
        if self.audit is None:
            return watch_program(fn, label)
        watched = watch_program(
            fn, label, lambda args: self._audit_program(label, fn, args))
        watched._fps_audited = True
        return watched

    def _audit_program(self, label: str, fn, args) -> None:
        from fps_tpu import analysis

        auditor = analysis.as_auditor(self.audit)
        if auditor is None:  # disabled after the wrapper was installed
            return
        self.audit = auditor  # keep one auditor (and its certificates)
        try:
            text = fn.lower(*args).as_text()
        except Exception:
            # Lowering for audit must never take down a run the real
            # dispatch would have survived (strict contract FAILURES, by
            # contrast, raise from certify below — that is the point).
            _log.exception("program audit: lowering %r failed; skipping "
                           "certification", label)
            return
        contract = auditor.contract
        if contract is None:
            contract = analysis.contract_for_trainer(
                self, label.split("/", 1)[-1])
        auditor.certify(label, text, contract=contract,
                        recorder=self.recorder)

    def lowered_chunk_text(self, chunk, mode: str = "sync") -> str:
        """StableHLO text of the exact per-chunk program ``fit_stream``
        dispatches for ``chunk``: fresh state with hot replicas
        attached, the chunk placed, the ``mode`` program lowered.

        The one entry point for the static-analysis tools
        (``tools/audit_programs.py``, ``tools/chaos_sweep.py``'s digest
        certificate) — keeping the
        init/attach/place/lower choreography in one place so a tiered
        trainer can't be lowered without its hot replicas. Read-only on
        the trainer: ``store.init`` writes fresh tables into
        ``store.tables`` in place, so they are restored afterwards —
        certifying after a run must not clobber the trained weights."""
        saved = dict(self.store.tables)
        try:
            tables, ls = self.init_state(jax.random.key(0))
            tables = self._attach_hot(tables)
            placed = self._place_chunk(chunk, mode)
            key = key_to_replicated(jax.random.key(1), self.mesh)
            # Same program selection as run_chunk: a compacted-route
            # trainer lowers the program THIS chunk would dispatch
            # (compacted when it certifies, static otherwise).
            compact_ok = True
            if self._cold_compact_map():
                compact_ok, _ = self._certify_cold(
                    self._host_cert_ids(chunk))
            return self._get_compiled(mode, compact_ok).lower(
                tables, ls, placed, key).as_text()
        finally:
            self.store.tables = saved

    # -- index-fed epochs (ingest fused into the compiled loop) -----------

    def _indexed_call_steps(self, plan) -> int:
        """Steps per compiled call: the whole epoch, capped by
        ``max_steps_per_call`` (rounded to a sync_every multiple)."""
        T = plan.steps_per_epoch
        cap = self.config.max_steps_per_call
        if cap is None or cap >= T:
            return T
        s = self.config.sync_every
        if s:
            if s > cap:
                import warnings

                warnings.warn(
                    f"sync_every={s} exceeds max_steps_per_call={cap}; "
                    "dispatches must contain whole SSP rounds, so each call "
                    f"runs {s} steps — lower sync_every if this risks the "
                    "per-dispatch execution deadline",
                    stacklevel=3,
                )
            cap = max(s, (cap // s) * s)
        return cap

    def _build_indexed_fn(self, plan, mode: str):
        """One jitted program running (a slice of) an epoch: per-step
        batches are gathered from the device-resident dataset inside the
        scan, so an epoch costs a handful of dispatches and zero host↔device
        traffic (:class:`fps_tpu.core.device_ingest.DeviceEpochPlan`)."""
        T = self._indexed_call_steps(plan)
        s = self.config.sync_every
        tier = self._hot_tier_map()
        mapped = self._mapped_tables()
        track = self._track_specs()
        folds_on = {**self._hot_fold_map(), **self._row_fold_map()}
        E = self.config.hot_sync_every

        def epoch_device(tables, local_state, iargs, start, key):
            widx = worker_index()
            key = jax.random.fold_in(key, widx)
            tables, dense = split_dense(tables)
            (tables, hot, maps, gids, sketches,
             fstates) = split_tiering(tables)
            tables, fstates = self._with_row_folds(tables, fstates)
            delta = self._init_hot_deltas(tables, tier)
            sk0 = {name: jnp.zeros_like(sketches[name])
                   for name in sorted(track)}
            bufs = None
            if self.config.push_delay:
                # Probe batch for push shapes (unused value, DCE'd by XLA).
                with jax.named_scope("fps.ingest"):
                    batch0 = plan.local_batch_at(iargs, widx, start)
                bufs = self._init_push_bufs(tables, local_state, batch0, key)

            hp_seen = {}

            def step_t(carry, t, snapshot=None):
                (tables, hot, delta, fstates, sk, bufs, local_state,
                 key, dense) = carry
                key, sub = jax.random.split(key)
                # Device ingest: the step's batch, gathered from the
                # resident dataset inside the compiled loop.
                with jax.named_scope("fps.ingest"):
                    batch = plan.local_batch_at(iargs, widx, t)
                tapped = self._tap_step(tables, batch, local_state, t)
                with watch_routed() as routed, \
                        self._watch_counts() as counted:
                    (pushes, local_state, out, hp, hcounts,
                     sk, dense) = self._compute_step(
                        tables, snapshot, local_state, batch, sub,
                        hot=hot, tier=tier, maps=maps, track=track, sk=sk,
                        dense=dense,
                    )
                    hp_seen.update(hp)  # static, the same every traced step
                    dropped = {}
                    if tier:
                        tables, delta, dropped = self._apply_hot_split(
                            tables, delta, pushes, tier, hp, maps)
                    else:
                        tables, bufs = self._apply_or_buffer(
                            tables, bufs, t, pushes, hp)
                out = self._mount_hot_channel(out, hcounts, delta, tier,
                                              dropped, routed)
                out = self._mount_counts(out, counted)
                with jax.named_scope("fps.metrics"):
                    out = jax.tree.map(
                        lambda x: lax.psum(lax.psum(x, SHARD_AXIS),
                                           DATA_AXIS), out
                    )
                out = self._mount_tap(out, tapped)
                return (tables, hot, delta, fstates, sk, bufs,
                        local_state, key, dense), out

            def finish(carry, outs):
                (tables, hot, delta, fstates, sk, bufs, local_state,
                 _, dense) = carry
                tables = self._flush_push_bufs(tables, bufs, start + T,
                                               hp_seen)
                tables = {**tables,
                          **{dense_key(n): v
                             for n, v in sorted(dense.items())},
                          **{hot_key(n): v for n, v in sorted(hot.items())},
                          **{map_key(n): v for n, v in sorted(maps.items())},
                          **{ids_key(n): v for n, v in sorted(gids.items())},
                          **{fold_key(n): v
                             for n, v in sorted(fstates.items())},
                          **self._merge_sketches(sketches, sk)}
                return tables, local_state, outs

            carry0 = (tables, hot, delta, fstates, sk0, bufs,
                      local_state, key, dense)
            if mode == "sync":
                if not tier:
                    carry, outs = lax.scan(
                        step_t, carry0,
                        start + jnp.arange(T, dtype=jnp.int32),
                    )
                    return finish(carry, outs)
                # Windows of E steps + a flush reconcile on the ragged
                # tail — every call returns one canonical table. The
                # scanned xs are the step indices themselves, stacked
                # (R, E) for the full windows.
                R, rem = divmod(T, E)
                carry, outs = self._windowed_scan(
                    step_t, carry0, tier,
                    head=(start + jnp.arange(R * E, dtype=jnp.int32)
                          .reshape(R, E)) if R else None,
                    tail=(start + R * E
                          + jnp.arange(rem, dtype=jnp.int32))
                    if rem else None,
                    gids=gids,
                )
                return finish(carry, outs)

            carry, outs = lax.scan(
                lambda c, r: self._ssp_round(
                    step_t, c,
                    start + r * s + jnp.arange(s, dtype=jnp.int32),
                    tier, gids),
                carry0, jnp.arange(T // s, dtype=jnp.int32),
            )
            outs = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), outs)
            return finish(carry, outs)

        table_specs = {name: P(SHARD_AXIS, None) for name in self.store.specs}
        table_specs.update({hot_key(name): P() for name in tier})
        table_specs.update({map_key(name): P() for name in sorted(mapped)})
        table_specs.update({ids_key(name): P() for name in sorted(mapped)})
        table_specs.update({sketch_key(name): P()
                            for name in sorted(track)})
        table_specs.update({fold_key(name): P(SHARD_AXIS, None)
                            for name in sorted(folds_on)})
        table_specs.update(self._dense_specs())
        ls_spec = P(WORKER_AXES)

        def run(tables, local_state, iargs, start, key):
            shmapped = jax.shard_map(
                epoch_device,
                mesh=self.mesh,
                in_specs=(
                    table_specs,
                    jax.tree.map(lambda _: ls_spec, local_state),
                    jax.tree.map(lambda _: P(), iargs),
                    P(),
                    P(),
                ),
                out_specs=(
                    table_specs,
                    jax.tree.map(lambda _: ls_spec, local_state),
                    P(),
                ),
                check_vma=False,
            )
            return shmapped(tables, local_state, iargs, start, key)

        donate = (0, 1) if self.config.donate else ()
        return jax.jit(run, donate_argnums=donate)

    def _check_rollback(self, rollback) -> None:
        if rollback is None:
            return
        if not isinstance(rollback, RollbackPolicy):
            raise TypeError(
                f"rollback must be a RollbackPolicy, got "
                f"{type(rollback).__name__}"
            )
        if resilience.as_guard(self.config.guard) is None and not rollback.preset:
            # Preset-only policies are legal without a guard: skipping
            # already-adjudicated indices needs no health channel. Health-
            # based quarantine does.
            raise ValueError(
                "a rollback policy needs the health channel: set "
                "TrainerConfig.guard ('observe' for pure quarantine "
                "semantics, 'mask' to also drop poison rows in-step)"
            )

    def _check_health(self, health) -> None:
        if health is None:
            return
        if not isinstance(health, HealthMonitor):
            raise TypeError(
                f"health must be a fps_tpu.obs.HealthMonitor, got "
                f"{type(health).__name__}"
            )
        if resilience.as_guard(self.config.guard) is None:
            raise ValueError(
                "a HealthMonitor needs the health channel: set "
                "TrainerConfig.guard ('observe' to run cheap until the "
                "monitor escalates to mask, or 'mask' outright)"
            )

    def _record_health(self, rec, metrics) -> int:
        """Fold one HOST metrics pytree's health channel into the recorder
        (per-table counters) and return the total poisoned-row count the
        HealthMonitor thresholds (nonfinite + norm tiers)."""
        h = (metrics.get(resilience.HEALTH_KEY)
             if isinstance(metrics, Mapping) else None)
        if not h:
            return 0
        poison = 0
        for table, counters in h.items():
            nf = int(np.sum(np.asarray(counters.get("nonfinite", 0))))
            nm = int(np.sum(np.asarray(counters.get("norm", 0))))
            mk = int(np.sum(np.asarray(counters.get("masked", 0))))
            if rec is not None:
                # Zero increments too: a clean guarded run's digest should
                # show the table at 0, not pretend the guard was off.
                rec.inc("health.nonfinite_rows", nf, table=table)
                rec.inc("health.norm_rows", nm, table=table)
                rec.inc("health.masked_rows", mk, table=table)
            poison += nf + nm
        return poison

    @staticmethod
    def _record_hot_tier(rec, ht) -> dict:
        """Fold one unit's HOST hot-tier channel (``{table: per-step
        counters}``) into the recorder and return the unit's own sums a
        table, the journal's fields: ``hot_rows``, ``pulled_rows``,
        ``cold_dropped`` (0 where the cold route is not compacted: nothing
        can be dropped there) and ``pending_delta``, the peak over the
        unit's steps."""
        sums = {}
        for table, counters in ht.items():
            # .get: a tiered table the worker pushes to but never
            # pulls (or an SSP run, where reads come from the round
            # snapshot, not the replica) carries no pull counters.
            hot = float(np.sum(np.asarray(counters.get("hot_rows", 0)),
                               dtype=np.float64))
            pulled = float(np.sum(np.asarray(
                counters.get("pulled_rows", 0)), dtype=np.float64))
            rec.inc("hot_tier.hot_rows", hot, table=table)
            rec.inc("hot_tier.pulled_rows", pulled, table=table)
            dropped = 0.0
            if "cold_dropped" in counters:
                # Compacted-route overflow drops — ALWAYS zero for
                # host-certified chunks; nonzero means a certifier
                # bug, surfaced rather than silently losing updates.
                dropped = float(np.sum(np.asarray(
                    counters["cold_dropped"]), dtype=np.float64))
                rec.inc("hot_tier.cold_dropped", dropped, table=table)
            # Peak pending-delta magnitude across the call's steps —
            # the parameter-plane staleness gauge (always 0 at the
            # boundary itself: the flush reconcile drained it).
            ds = np.asarray(counters.get("delta_sq", 0.0))
            peak = float(np.sqrt(np.max(ds))) if ds.size else 0.0
            rec.set("hot_tier.pending_delta", peak, table=table)
            sums[table] = {"hot_rows": hot, "pulled_rows": pulled,
                           "cold_dropped": dropped, "pending_delta": peak}
        return sums

    @staticmethod
    def _record_exchange(rec, ht) -> dict:
        """Fold the ``routed`` flags of one unit's HOST hot-tier channel
        into the recorder (``exchange.routed_steps`` / ``exchange.steps``)
        and return the unit's own sums a table, the journal's ``exchange``
        field: of the ``steps`` in which the table's cold rows crossed
        shards by the non-dense exchange, ``routed_steps`` ran it
        owner-routed (the others gathered: a step whose ids did not fit
        their lanes, or shapes that keep the exchange gathered). ``{}``
        where no table carries the flag."""
        sums = {}
        for table, counters in ht.items():
            if "routed" not in counters:
                continue
            flags = np.asarray(counters["routed"])
            sums[table] = {"routed_steps": float(np.count_nonzero(flags)),
                           "steps": float(flags.size)}
            for k, v in sums[table].items():
                rec.inc(f"exchange.{k}", v, table=table)
        return sums

    @staticmethod
    def _record_counts(rec, chans) -> dict:
        """Fold one unit's HOST count channels (:meth:`_counts_channels`)
        into the recorder (``sum_runs.pushed_ids`` / ``sum_runs.live_ids``,
        ``fold_rows.handed_ids`` / ``fold_rows.folded_ids``,
        ``distinct_pulls.pulled_ids`` / ``distinct_pulls.live_ids``) and
        return the unit's own sums a table, the journal's ``sum_runs`` /
        ``fold_rows`` / ``distinct_pulls`` fields: of the ids the pushes
        (the pulls) were handed and kept, the distinct ones a step, which
        are what they then paid for."""
        fields = {}
        for channel, tables in chans.items():
            sums = fields[channel] = {}
            for table, counters in tables.items():
                sums[table] = {
                    k: float(np.sum(np.asarray(v), dtype=np.float64))
                    for k, v in counters.items()}
                for k, v in sums[table].items():
                    rec.inc(f"{channel}.{k}", v, table=table)
        return fields

    def _record_tier_channel(self, rec, ht) -> dict:
        """Both folds of a unit's hot-tier channel, as the fields they
        set on the journal's event (``exchange`` only where it holds
        something)."""
        fields = {"hot_tier": self._record_hot_tier(rec, ht)}
        exchange = self._record_exchange(rec, ht)
        if exchange:
            fields["exchange"] = exchange
        return fields

    def _hot_tier_later(self, metrics):
        """What ``watch_device`` runs once a unit whose metrics stay on
        the device has completed (``run_indexed(as_numpy=False)``): the
        hot tier's counters (and its tables' ``routed`` flags) counted
        then, from a copy that waits for nothing, and the unit's sums
        handed back for its ``device.*`` span, the journal's record of
        the epoch's completion (its ``epoch`` event is written at
        dispatch, before the numbers exist); likewise the counts of the
        pushes on ``push.sum_runs`` and ``push.fold_rows`` and of the pulls
        on ``pull.distinct_rows`` (the span's ``sum_runs``, ``fold_rows``
        and ``distinct_pulls`` fields). ``None`` when the unit
        carries none of them."""
        ht = (metrics.get(resilience.HOT_TIER_KEY)
              if isinstance(metrics, Mapping) else None)
        chans = self._counts_channels(metrics)
        if not ht and not chans:
            return None

        def later(rec):
            fields = {}
            if ht:
                fields.update(self._record_tier_channel(
                    rec, jax.tree.map(np.asarray, ht)))
            fields.update(self._record_counts(
                rec, jax.tree.map(np.asarray, chans)))
            return fields

        return later

    def _fold_metrics_accounting(self, rec, metrics, ev=None) -> int:
        """The one per-chunk/epoch telemetry fold for a HOST metrics tree:
        per-table health counters (+ health.poisoned_chunks), example/step
        counters from the ``"n"`` leaf, and — when a journal event dict is
        given — its ``examples``/``poison_rows`` fields. One helper so the
        sync / callback / deferred paths of both drivers cannot drift.
        Returns the poisoned-row total (what HealthMonitor thresholds)."""
        poison = self._record_health(rec, metrics)
        ht = (metrics.get(resilience.HOT_TIER_KEY)
              if isinstance(metrics, Mapping) else None)
        if ht and rec is not None:
            fields = self._record_tier_channel(rec, ht)
            if ev is not None:
                ev.update(fields)
        chans = self._counts_channels(metrics)
        if chans and rec is not None:
            fields = self._record_counts(rec, chans)
            if ev is not None:
                ev.update(fields)
        if rec is not None:
            if poison:
                rec.inc("health.poisoned_chunks")
                if ev is not None:
                    ev["poison_rows"] = poison
            if isinstance(metrics, Mapping) and "n" in metrics:
                n = float(np.sum(metrics["n"]))
                rec.inc("driver.examples", n)
                rec.inc("driver.steps", int(np.shape(metrics["n"])[0]))
                if ev is not None:
                    ev["examples"] = n
            journal = getattr(self.config.step_tap, "journal", None)
            if (journal is not None and isinstance(metrics, Mapping)
                    and "tap" in metrics):
                # The tap's own counts (a top-K tap: lists answered,
                # padding queries) beside the examples they rode with.
                for name, count in journal(metrics["tap"]).items():
                    rec.inc(f"tap.{name}", count)
                    if ev is not None:
                        ev[name] = count
        return poison

    def _apply_health_decision(self, health, rec, index, poison, what):
        """Threshold step: feed the monitor and apply its decision —
        escalate swaps this trainer's guard observe→mask (the next
        chunk/epoch recompiles through the guard-keyed cache), abort
        raises PoisonedStreamError after flushing telemetry."""
        if health is None:
            return
        decision = health.update(index, poison)
        if decision == HEALTH_ESCALATE:
            guard = resilience.as_guard(self.config.guard)
            if guard is not None and guard.mode == "observe":
                self.config = dataclasses.replace(
                    self.config,
                    guard=dataclasses.replace(guard, mode="mask"),
                )
                _log.warning(
                    "health monitor: escalating guard observe->mask at "
                    "%s %d (%d poisoned rows >= %d)", what, index,
                    health.poison_rows, health.escalate_after_rows,
                )
                if rec is not None:
                    rec.event("guard_escalated", index=int(index), what=what,
                              poison_rows=health.poison_rows)
        elif decision == HEALTH_ABORT:
            if rec is not None:
                rec.event("health_abort", index=int(index), what=what,
                          poisoned_chunks=health.poisoned_chunks,
                          poison_rows=health.poison_rows)
                rec.flush()
            raise resilience.PoisonedStreamError(
                f"health monitor abort at {what} {index}: "
                f"{health.poisoned_chunks} poisoned {what}s (threshold "
                f"{health.abort_after_chunks}), {health.poison_rows} "
                "poisoned rows total"
            )

    def _maybe_quarantine(self, rollback, last_good, metrics, index, what):
        """Shared rollback step for fit_stream (chunks) and run_indexed
        (epochs): host-sync the metrics and, when the health channel
        reports poison, restore the pre-call state and record the
        quarantine. Returns ``(host_metrics, restored_state_or_None)``.

        Ordering matters: the state (and the store's host-side view) is
        restored BEFORE ``record()``, whose budget check may raise — a
        caller catching PoisonedStreamError must find last-good state, not
        donated or poisoned buffers."""
        metrics = jax.tree.map(np.asarray, metrics)
        poison = resilience.health_total(metrics)
        if not poison:
            return metrics, None
        tables, local_state = last_good
        self.store.tables = dict(tables)
        _log.warning(
            "%s %d poisoned (%d bad push rows): rolled back and "
            "quarantined", what, index, poison,
        )
        rollback.record(index)
        return metrics, (tables, local_state)

    def _get_indexed_fn(self, plan, mode: str, timer=None):
        """Compiled epoch program for the CURRENT config (looked up per
        epoch, not per run: a HealthMonitor escalation swaps the guard
        mid-run and the next epoch must recompile, keyed on the plan
        object itself — its geometry is baked into the program as
        constants, so identity is the correct key)."""
        ck = ("indexed", mode, plan, ops.get_backend(),
              self.config.push_delay, self.config.step_tap,
              resilience.as_guard(self.config.guard),
              self._server_logic_key(), self.config.hot_sync_every,
              tuple(sorted(self._hot_tier_map().items())),
              tuple(sorted(self._mapped_tables().items())),
              tuple(sorted(self._track_specs().items())))
        with host_span("program_lookup", timer) as span:
            span["built"] = ck not in self._compiled
            if span["built"]:
                self._compiled[ck] = self._wrap_audit(
                    self._build_indexed_fn(plan, mode), f"indexed/{mode}")
            return self._compiled[ck]

    def _get_megastep_fn(self, plan, mode: str, K: int, tick=None,
                         timer=None):
        """Compiled K-chunk megastep program (fps_tpu.core.megastep) for
        the CURRENT config — cache-keyed like the indexed program, plus
        the chunk count and the tick contract (its decayed-sketch spec,
        cadence, and threshold are trace constants; the hot membership
        and decayed state stay DATA, so in-graph re-ranks never miss
        this entry)."""
        tick_key = None
        if tick is not None:
            tick_key = (tick.spec, tick.check_every,
                        tick.churn_threshold, tick.tables)
        ck = ("megastep", mode, plan, K, ops.get_backend(),
              self.config.step_tap,
              resilience.as_guard(self.config.guard),
              self._server_logic_key(), self.config.hot_sync_every,
              tuple(sorted(self._hot_tier_map().items())),
              tuple(sorted(self._mapped_tables().items())),
              tuple(sorted(self._track_specs().items())),
              tuple(sorted(self._cold_compact_map().items())),
              tick_key)
        with host_span("program_lookup", timer) as span:
            span["built"] = ck not in self._compiled
            if span["built"]:
                from fps_tpu.core import megastep as _megastep

                self._compiled[ck] = self._wrap_audit(
                    _megastep.build_megastep_fn(self, plan, mode, K, tick),
                    f"megastep/{mode}")
            return self._compiled[ck]

    def run_megastep(self, tables, local_state, plan, key, *,
                     epochs: int = 1, chunks_per_dispatch: int = 4,
                     on_megastep=None, checkpointer=None,
                     checkpoint_every: int = 0, start_megastep: int = 0,
                     as_numpy: bool = True,
                     rollback: RollbackPolicy | None = None,
                     recorder=None,
                     health: HealthMonitor | None = None,
                     watchdog: StepWatchdog | None = None,
                     tick=None):
        """Run ``epochs`` passes in K-chunk device-resident megasteps —
        one compiled program per ``chunks_per_dispatch`` chunks, with
        reconcile / sketch / tier-tick boundaries executed in-graph and
        a device-side overflow vote selecting the compacted or static
        cold routes per chunk. Bit-identical to the same run driven by
        per-chunk ``run_indexed`` dispatches; see
        :func:`fps_tpu.core.megastep.run_megastep` for the full
        contract."""
        from fps_tpu.core import megastep as _megastep

        return _megastep.run_megastep(
            self, tables, local_state, plan, key, epochs=epochs,
            chunks_per_dispatch=chunks_per_dispatch,
            on_megastep=on_megastep, checkpointer=checkpointer,
            checkpoint_every=checkpoint_every,
            start_megastep=start_megastep, as_numpy=as_numpy,
            rollback=rollback, recorder=recorder, health=health,
            watchdog=watchdog, tick=tick)

    def lowered_megastep_text(self, plan, *, chunks_per_dispatch: int,
                              mode: str = "sync", tick=None) -> str:
        """StableHLO text of the exact megastep program ``run_megastep``
        dispatches — the static-analysis entry point (the megastep rows
        of ``tools/audit_programs.py`` pin its collective census as
        K-independent). Read-only on the trainer, like
        :meth:`lowered_chunk_text`."""
        saved = dict(self.store.tables)
        saved_rt = self.retierer
        try:
            if tick is not None and self.retierer is None:
                self.retierer = tick
            tables, ls = self.init_state(jax.random.key(0))
            tables = self._attach_hot(tables)
            iargs = plan.epoch_args(0)
            ekey = key_to_replicated(
                jax.random.fold_in(jax.random.key(1), 0), self.mesh)
            tick_ops = tick.tick_ops(self) if tick is not None else {}
            fn = self._get_megastep_fn(plan, mode, chunks_per_dispatch,
                                       tick)
            return fn.lower(tables, ls, iargs, np.int32(0), ekey,
                            tick_ops).as_text()
        finally:
            self.store.tables = saved
            self.retierer = saved_rt

    @host_span("run_indexed", call=True)
    def run_indexed(self, tables, local_state, plan, key, *, epochs: int = 1,
                    on_epoch=None, checkpointer=None,
                    checkpoint_every: int = 0, start_epoch: int = 0,
                    as_numpy: bool = True,
                    rollback: RollbackPolicy | None = None,
                    recorder=None,
                    health: HealthMonitor | None = None,
                    watchdog: StepWatchdog | None = None):
        """Run ``epochs`` full passes with ingest fused into the jit.

        ``plan.sync_every`` must match the trainer's config. Pass a
        ``Checkpointer`` (+ ``checkpoint_every=k`` epochs) to snapshot
        tables and local state every k epochs and once at the end, like
        ``fit_stream`` does per chunk. To resume, restore from the
        checkpointer and pass ``start_epoch=<restored epoch>`` — both the
        per-epoch shuffles (``plan.epoch_args(e)``) and the PRNG stream
        (``fold_in(key, e)``) continue where the interrupted run left off.
        Returns (tables, local_state, per-epoch host metrics list).

        ``as_numpy=False`` returns the metrics as DEVICE arrays without
        blocking on them (no effect when ``on_epoch`` is given — callbacks
        need host values). The call then returns as soon as the last
        epoch is dispatched, letting the caller overlap host work — e.g.
        evaluating epoch ``e``'s metrics while the device races ahead on
        ``e+1`` (speculative epoch pipelining; the per-dispatch +
        metric-sync round trip otherwise serializes between epochs).

        ``rollback`` (a :class:`~fps_tpu.core.resilience.RollbackPolicy`,
        requires ``TrainerConfig.guard``): when an epoch's health channel
        reports poison, restore the pre-epoch state, quarantine the epoch
        (recorded in ``rollback.quarantined``, no metrics entry, no
        checkpoint), and continue — later epochs' shuffles and PRNG keys
        derive from the epoch index, so the streams are unaffected by the
        skip. Forces a per-epoch host metrics sync and an on-device state
        copy per epoch (degradation mode, not a fast path).

        Telemetry (``fps_tpu.obs``): ``recorder`` (default
        ``self.recorder``) records phase timers (dispatch / host_sync /
        checkpoint / callback — ingest is fused into the jit here), epoch
        journal events, and per-table health counters; it never changes
        sync behavior, so attaching one costs only host bookkeeping.
        ``health`` (a :class:`~fps_tpu.obs.HealthMonitor`, requires a
        guard) thresholds the health channel — escalating this trainer's
        guard observe→mask or aborting with PoisonedStreamError — and
        ``watchdog`` (a :class:`~fps_tpu.obs.StepWatchdog`) deadline-flags
        each epoch's dispatch+sync region; both force a per-epoch host
        metrics sync like ``rollback`` does (they must see the values as
        they happen).
        """
        self._check_rollback(rollback)
        self._check_health(health)
        rec = recorder if recorder is not None else self.recorder
        timer = PhaseTimer(rec) if rec is not None else None
        hb = _find_heartbeat(rec)
        # Health-based quarantine needs the guard's health channel; a
        # preset-only policy (guard off) must not pay the per-epoch state
        # copy + forced sync that the health path requires.
        quarantine = (rollback if rollback is not None and
                      resilience.as_guard(self.config.guard) is not None
                      else None)
        sync_each = (quarantine is not None or health is not None
                     or watchdog is not None)
        saved_at = None  # step of the last periodic save (quarantine-aware)
        mode = "sync" if self.config.sync_every is None else "ssp"
        if (self.config.sync_every or None) != (plan.sync_every or None):
            raise ValueError("plan.sync_every must match TrainerConfig")
        T = plan.steps_per_epoch
        T_call = self._indexed_call_steps(plan)
        n_calls = calls_per_epoch_of(plan, T_call)
        all_metrics = []
        end_epoch = start_epoch + epochs
        self._enter_tiering()
        # Two-tier re-split at run entry (restore/warm-start/config
        # changes); per-epoch calls keep the attached structure.
        with _phase(timer, "attach_hot"):
            tables = self._attach_hot(tables, timer)
        try:
            for e in range(start_epoch, end_epoch):
                if rollback is not None and e in rollback.preset:
                    # Quarantined by a previous attempt (supervisor-carried):
                    # consume the index without dispatching — PRNG/shuffle key
                    # off e, so later epochs are unaffected by the skip.
                    rollback.skip(e)
                    if rec is not None:
                        rec.inc("rollback.preset_skipped")
                        rec.flush()
                    continue
                fn = self._get_indexed_fn(plan, mode, timer)
                if quarantine is not None:
                    last_good = (resilience.tree_copy(tables),
                                 resilience.tree_copy(local_state))
                iargs = plan.epoch_args(e)
                parts = []
                restored = None
                _beat(hb, e, "dispatch")
                with _watch(watchdog, "epoch", e):
                    for ci in range(n_calls):
                        # dispatch: all the host does to queue one call
                        # (key derivation and placement, then the call);
                        # enqueue: the jitted call alone.
                        with _phase(timer, "dispatch"):
                            ckey = key_to_replicated(
                                jax.random.fold_in(
                                    jax.random.fold_in(key, e), ci),
                                self.mesh,
                            )
                            start = np.int32(ci * T_call)
                            with _phase(timer, "enqueue"):
                                tables, local_state, metrics = fn(
                                    tables, local_state, iargs, start, ckey
                                )
                        parts.append(metrics)
                    metrics = parts[0] if len(parts) == 1 else jax.tree.map(
                        lambda *xs: jnp.concatenate(xs), *parts
                    )
                    # Drop phantom trailing steps from the last (padded) call so
                    # metrics always have exactly steps_per_epoch rows.
                    if n_calls * T_call > T:
                        metrics = jax.tree.map(lambda x: x[:T], metrics)
                    # The epoch is queued: its completion is the watcher's
                    # to stamp (a None test with no recorder), and with it
                    # the hot tier's counts of an epoch nobody fetches.
                    watch_device(
                        "device.run_indexed", metrics, timer,
                        on_done=(self._hot_tier_later(metrics)
                                 if on_epoch is None and not as_numpy
                                 and not sync_each else None),
                        epoch=e, steps=T, **self._dense_fields())
                    if quarantine is not None:
                        with _phase(timer, "host_sync"):
                            metrics, restored = self._maybe_quarantine(
                                quarantine, last_good, metrics, e, "epoch"
                            )
                    elif sync_each:
                        with _phase(timer, "host_sync"):
                            metrics = jax.tree.map(np.asarray, metrics)
                ev = {"index": e} if rec is not None else None
                poison = 0
                if sync_each and (rec is not None or health is not None):
                    poison = self._fold_metrics_accounting(rec, metrics, ev)
                if rec is not None:
                    rec.inc("driver.epochs")
                    if restored is not None:
                        rec.inc("rollback.quarantined")
                        ev["quarantined"] = True
                self._apply_health_decision(health, rec, e, poison, "epoch")
                if restored is not None:
                    if rec is not None:
                        rec.event("epoch", phases=timer.chunk_summary(), **ev)
                        rec.flush()
                    tables, local_state = restored
                    continue
                all_metrics.append(metrics)
                # The donated pre-call buffers are dead; repoint the store's
                # host-side view (lookup_host / predict_*_host) at the live
                # arrays BEFORE any callback runs — per-epoch validation via the
                # store is the natural on_epoch pattern, and doing it here also
                # leaves the store consistent if on_epoch raises (early stop).
                self.store.tables = dict(tables)
                if on_epoch is not None:
                    with _phase(timer, "host_sync"):
                        host = jax.tree.map(np.asarray, metrics)
                    if rec is not None and not sync_each:
                        # on_epoch already paid the host sync; fold the same
                        # accounting the forced-sync paths get.
                        self._fold_metrics_accounting(rec, host, ev)
                    all_metrics[-1] = host
                    with _phase(timer, "callback"):
                        on_epoch(e, host)
                if checkpointer is not None and checkpoint_every > 0 and (
                    (e + 1) % checkpoint_every == 0
                ):
                    with _phase(timer, "checkpoint"):
                        self._save_checkpoint(checkpointer, e + 1, local_state)
                    saved_at = e + 1
                if rec is not None:
                    # Emitted AFTER the callback/checkpoint phases so the
                    # epoch event's phase breakdown covers the whole epoch;
                    # flushed per boundary so the Prometheus exposition is
                    # live-scrapable mid-run and a kill loses at most one
                    # epoch of buffered JSONL.
                    rec.event("epoch", phases=timer.chunk_summary(), **ev)
                    rec.flush()
                if self.retierer is not None:
                    # Adaptive-tiering boundary: fold the epoch's sketch
                    # windows, maybe re-rank/re-plan (fps_tpu.tiering).
                    # Quarantined epochs never reach here — their sketch
                    # rolled back with the rest of the aux state.
                    with _phase(timer, "retier"):
                        tables = self.retierer.on_boundary(
                            self, tables, e, recorder=rec)
                    self.store.tables = dict(tables)
            self.store.tables = dict(tables)  # epochs == 0: loop never ran
            # End-of-run save whenever the last epoch's state isn't already on
            # disk — including when a quarantined final epoch skipped its
            # periodic save (the snapshot then holds the rolled-back state
            # under the final step number, so a resume skips the poison).
            if checkpointer is not None and epochs > 0 and saved_at != end_epoch:
                with _phase(timer, "checkpoint"):
                    self._save_checkpoint(checkpointer, end_epoch, local_state)
        finally:
            if checkpointer is not None:
                # Durability barrier: an AsyncCheckpointer's in-flight
                # write must be on disk before the run reports done
                # (no-op for the synchronous base class) — in a finally
                # so accepted saves survive a mid-run abort too.
                with _phase(timer, "checkpoint"):
                    checkpointer.flush()
        if on_epoch is None and as_numpy:
            with _phase(timer, "host_sync"):
                all_metrics = [jax.tree.map(np.asarray, m)
                               for m in all_metrics]
            if rec is not None and not sync_each:
                # Deferred-sync runs still get whole-run health totals and
                # example counts (per-epoch attribution needs a syncing
                # consumer: on_epoch, rollback, health, watchdog).
                for m in all_metrics:
                    self._fold_metrics_accounting(rec, m)
        if rec is not None:
            rec.flush()
        return tables, local_state, all_metrics

    # -- host API ---------------------------------------------------------

    def run_chunk(self, tables, local_state, batches, key, *, timer=None,
                  recorder=None):
        """Run one compiled chunk.

        Args:
          tables: dict of sharded tables (as returned by ``init_state`` /
            previous chunks).
          local_state: worker-local pytree.
          batches: pytree of host arrays with leading dims ``(T, B)`` (sync)
            or ``(R, s, B)`` (ssp) — ``B`` is the *global* batch size,
            divided across all workers.
          key: PRNG key (host scalar).
          timer: optional :class:`fps_tpu.obs.PhaseTimer` — attributes the
            host→device upload to ``place`` and the jitted call (enqueue +
            first-call compile) to ``dispatch``. ``fit_stream`` passes its
            own; standalone callers may too.
          recorder: optional :class:`fps_tpu.obs.Recorder` for the
            cold-route certification counters (default
            ``self.recorder``).

        Returns:
          (tables, local_state, metrics) — metrics leaves have leading dim
          equal to the number of steps in the chunk (global sums per step).
        """
        mode = "sync" if self.config.sync_every is None else "ssp"
        rec = recorder if recorder is not None else self.recorder
        # Two-tier re-split (no-op dict bookkeeping when already attached
        # or untiered): the compiled program's table structure must match
        # the current hot-tier resolution exactly.
        tables = self._attach_hot(tables, timer)
        # Payload-proportional cold routing: certify this chunk against
        # the cold_budget lanes at DISPATCH time (hot membership may have
        # re-ranked since placement) and select the compacted or static
        # program accordingly — the head_prefix pattern, per chunk.
        compact_ok = True
        if self._cold_compact_map():
            if isinstance(batches, PlacedChunk):
                host_ids = batches.host_ids
            elif all(not isinstance(x, jax.Array)
                     for x in jax.tree.leaves(batches)):
                host_ids = self._host_cert_ids(batches)
            else:
                host_ids = None  # device-resident chunk: uncertifiable
            compact_ok, overflowed = self._certify_cold(host_ids)
            if rec is not None:
                if compact_ok:
                    rec.inc("cold_route.compact_chunks")
                else:
                    for t in overflowed:
                        rec.inc("cold_route.overflow_chunks", table=t)
        with _phase(timer, "place"):
            if isinstance(batches, PlacedChunk):
                # The prefetch pipeline already ran _place_chunk on its
                # worker thread — same function, same sharded arrays.
                batches = batches.batches
            else:
                batches = self._place_chunk(batches, mode)
            key = key_to_replicated(key, self.mesh)
        fn = self._get_compiled(mode, compact_ok, timer)
        with _phase(timer, "dispatch"), _phase(timer, "enqueue"):
            tables, local_state, metrics = fn(
                tables, local_state, batches, key
            )
        # The donated input buffers are dead now; keep the store's host-side
        # view (lookup_host / dump_model — the reference's model-out stream)
        # pointed at the live arrays.
        self.store.tables = dict(tables)
        return tables, local_state, metrics

    def _batch_sharding_for(self, mode):
        nlead = 1 if mode == "sync" else 2
        spec = P(*([None] * nlead), WORKER_AXES)
        return NamedSharding(self.mesh, spec)

    def _place_chunk(self, batches, mode: str | None = None):
        """Place one chunk's batches onto the batch sharding — the
        host→device upload both the synchronous path (run_chunk) and the
        background pipeline's worker thread run, so prefetch on/off
        produces byte-identical device inputs by construction."""
        if mode is None:
            mode = "sync" if self.config.sync_every is None else "ssp"
        sharding = self._batch_sharding_for(mode)

        def place(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                # Device-ingest chunks are already global arrays on the
                # mesh (multi-controller); leave them where they are.
                return x
            return host_to_sharded(x, sharding)

        return jax.tree.map(place, batches)

    @host_span("fit_stream", call=True)
    def fit_stream(
        self,
        tables,
        local_state,
        chunks: Iterable[Pytree],
        key: Array,
        metrics_reduce=None,
        checkpointer=None,
        checkpoint_every: int = 0,
        start_step: int = 0,
        on_chunk=None,
        rollback: RollbackPolicy | None = None,
        recorder=None,
        health: HealthMonitor | None = None,
        watchdog: StepWatchdog | None = None,
    ):
        """Drive the compiled loop over a host-side stream of chunks.

        This is the ingest loop that replaces the Flink DataStream source —
        one-pass streaming (the reference's model) or multi-epoch, depending
        on what the iterator yields.

        Pass a ``fps_tpu.core.checkpoint.Checkpointer`` plus
        ``checkpoint_every=k`` to snapshot tables + local state every k
        chunks (and once more at the end of the stream). To resume, restore
        from the checkpointer and pass ``start_step=<restored step>`` with a
        chunk iterator positioned after the already-consumed chunks — both
        the per-chunk PRNG stream (``fold_in(key, step)``) and the snapshot
        numbering continue where the interrupted run left off.

        ``on_chunk(step, metrics)`` is called after every chunk with the
        host-side metrics pytree — the live tap on the reference's ``WOut``
        observability stream (per-chunk progress reporting, early stopping
        via raising, etc.). When no ``on_chunk`` is given, metrics stay on
        device until the stream ends so the host never blocks mid-stream
        and chunk dispatch pipelines (device-resident ingest then runs the
        whole epoch without a single host↔device round trip).

        ``rollback`` (a :class:`~fps_tpu.core.resilience.RollbackPolicy`,
        requires ``TrainerConfig.guard``): when a chunk's health channel
        reports poison, restore the state captured just before that chunk,
        quarantine it (recorded in ``rollback.quarantined``, no metrics
        entry, no checkpoint), and continue — the per-chunk PRNG stream
        keys off the chunk index, so later chunks are unaffected by the
        skip. Forces a per-chunk host metrics sync and an on-device state
        copy per chunk (degradation mode, not a fast path).

        Telemetry (``fps_tpu.obs``): ``recorder`` (default
        ``self.recorder``) times each chunk's phases (ingest / place /
        dispatch / host_sync / checkpoint / callback), journals chunk
        events, and folds the health channel into per-table counters. It
        never forces extra host syncs — phases cover whatever blocking the
        loop already does, so a recorder costs only host bookkeeping.
        ``health`` (a :class:`~fps_tpu.obs.HealthMonitor`, requires a
        guard) thresholds the health channel: escalate this trainer's
        guard observe→mask after N poisoned rows, abort (raising
        PoisonedStreamError) after M poisoned chunks. ``watchdog`` (a
        :class:`~fps_tpu.obs.StepWatchdog`) deadline-flags each chunk's
        dispatch+sync region — the straggler tripwire. Health and
        watchdog (like ``rollback``) force a per-chunk host metrics sync:
        they must observe values as they happen.

        Host pipeline (``TrainerConfig``, ``docs/performance.md``):
        ``prefetch=N`` moves chunk assembly + placement onto a background
        worker running N chunks ahead (:mod:`fps_tpu.core.prefetch`) —
        numerics, chunk order, and the compiled program are identical;
        every exit path joins the worker. ``health_lag=1`` makes the
        forced sync the syncing consumers above require lag-by-one:
        chunk ``i-1``'s host metrics are inspected while chunk ``i``
        computes (a quarantined ``i-1`` restores its pre-chunk snapshot
        and chunk ``i`` is deterministically recomputed from it, so
        guard/quarantine results match ``health_lag=0`` bit for bit;
        consumers — and ``on_chunk``/store readers — see state one chunk
        late). Two lag caveats: a HealthMonitor's observe→mask
        escalation lands one DISPATCH later than at lag 0 (a run where
        escalation fires mid-stream is not bit-identical across lag
        settings — one more chunk runs unmasked), and journal chunk
        events attribute concurrently-running phase segments to the
        adjudication boundary, so chunk ``i-1``'s event carries chunk
        ``i``'s dispatch time (overlap makes per-chunk attribution
        inherently fuzzy; run-level phase totals stay exact). With
        either knob on, boundary checkpoints dump from on-device copies
        taken at the boundary and run after the next dispatch, so the
        stream no longer stalls on the device→host ``jax.device_get``
        (the crash window grows by at most one chunk; the end-of-stream
        flush is unchanged).
        """
        self._check_rollback(rollback)
        self._check_health(health)
        cfg = self.config
        if cfg.prefetch < 0:
            raise ValueError(
                f"TrainerConfig.prefetch must be >= 0, got {cfg.prefetch}")
        if cfg.health_lag not in (0, 1):
            raise ValueError(
                f"TrainerConfig.health_lag must be 0 or 1, got "
                f"{cfg.health_lag}")
        if cfg.metrics_drain_every < 0:
            raise ValueError(
                f"TrainerConfig.metrics_drain_every must be >= 0, got "
                f"{cfg.metrics_drain_every}")
        rec = recorder if recorder is not None else self.recorder
        timer = PhaseTimer(rec) if rec is not None else None
        hb = _find_heartbeat(rec)
        # Health-based quarantine needs the guard's health channel; a
        # preset-only policy (guard off) must not pay the per-chunk state
        # copy + forced sync that the health path requires.
        quarantine = (rollback if rollback is not None and
                      resilience.as_guard(cfg.guard) is not None
                      else None)
        sync_each = (quarantine is not None or health is not None
                     or watchdog is not None)
        # Lag-by-one control plane: only meaningful when something forces
        # a per-chunk sync in the first place.
        lag = 1 if (cfg.health_lag and sync_each) else 0
        # Overlapped checkpoint dump: with the pipeline on, boundary saves
        # run from on-device boundary copies after the NEXT dispatch;
        # otherwise the save stays inline at the boundary (legacy timing:
        # crash window of at most one chunk).
        overlap_ckpt = (checkpointer is not None and checkpoint_every > 0
                        and (cfg.prefetch > 0 or lag > 0))
        saved_at = None  # step of the last periodic save (quarantine-aware)
        all_metrics = []
        # Delta-snapshot sourcing (DeltaPolicy on the checkpointer): the
        # tracker accumulates each dispatched chunk's pulled-id stream
        # (WorkerLogic.pulled_ids_host — the same exact host stream the
        # cold-route certifier consumes) so every save can publish a
        # row-sparse delta whose touched set is O(traffic), not
        # O(table). Uncertifiable chunks degrade that table to the
        # checkpointer's exact-diff fallback, never to corruption.
        delta_touched = None
        if (checkpointer is not None and checkpoint_every > 0
                and getattr(checkpointer, "delta_policy", None) is not None):
            from fps_tpu.core.checkpoint import TouchedRowsTracker

            delta_touched = TouchedRowsTracker(self.store.specs)

        def capture_touched():
            if delta_touched is None:
                return None
            ids, marker = delta_touched.capture()
            return (ids, marker, delta_touched)

        def chunk_touched_ids(c):
            if isinstance(c, PlacedChunk):
                return c.host_ids
            if any(isinstance(x, jax.Array)
                   for x in jax.tree.leaves(c)):
                # Device-resident chunk: pulling the id columns back to
                # host per chunk would reintroduce the dispatch-time
                # stall (and raises outright on non-addressable sharded
                # arrays) — same guard as the cold-route certifier.
                # None = the exact-diff fallback at save time.
                return None
            return self.logic.pulled_ids_host(c)

        it = iter(chunks)
        pf = None
        if cfg.prefetch:
            mode = "sync" if cfg.sync_every is None else "ssp"

            def _place_for_pf(b, _m=mode):
                # Placement on the worker thread, but retain the raw id
                # columns the cold-route certifier needs: certification
                # itself runs at dispatch (hot membership can re-rank
                # between placement and dispatch). With delta tracking
                # on, the same capture feeds the touched-rows tracker.
                ids = self._host_cert_ids(b)
                if ids is None and delta_touched is not None:
                    ids = self.logic.pulled_ids_host(b)
                return PlacedChunk(self._place_chunk(b, _m),
                                   host_ids=ids)

            pf = ChunkPrefetcher(
                it, _place_for_pf,
                depth=cfg.prefetch,
                max_depth=cfg.prefetch_max or None,
                recorder=rec, timer=timer,
                start_index=start_step,
                # Preset-quarantined chunks are consumed but never
                # dispatched — don't pay their host→device upload.
                skip_place=(rollback.preset if rollback is not None
                            else frozenset()),
            )
            it = pf
        i = start_step - 1
        pending = None       # lag-by-one: one dispatched, unadjudicated chunk
        pending_save = None  # deferred (overlapped) boundary snapshot
        self._enter_tiering()
        # Two-tier re-split at stream entry; run_chunk keeps the attached
        # structure live across the loop.
        with _phase(timer, "attach_hot"):
            tables = self._attach_hot(tables, timer)

        def retier_boundary(j):
            """Adaptive-tiering boundary for an adjudicated-clean chunk:
            fold sketch windows, maybe re-rank/re-plan (fps_tpu.tiering).
            Quarantined chunks skip it — their sketch window rolled back
            with the rest of the aux state. Under health_lag=1 this runs
            at chunk j's ADJUDICATION (one dispatch late, like every
            other lag consumer), so re-rank decisions see one extra
            chunk of traffic relative to lag 0."""
            nonlocal tables
            if self.retierer is None:
                return
            with _phase(timer, "retier"):
                tables = self.retierer.on_boundary(
                    self, tables, j, recorder=rec)
            self.store.tables = dict(tables)

        def save_due(j):
            return (checkpointer is not None and checkpoint_every > 0
                    and (j + 1) % checkpoint_every == 0)

        def boundary_copy(j):
            """Post-chunk-``j`` state as fresh on-device buffers (futures —
            no host block): the double-buffered snapshot the overlapped
            dump writes from after the next dispatch. The touched-rows
            capture rides along — it must describe the SAME boundary as
            the copied state, not whatever the tracker holds when the
            deferred write finally runs."""
            return (j + 1, resilience.tree_copy(tables),
                    resilience.tree_copy(local_state), capture_touched())

        def flush_save():
            """Write the deferred boundary snapshot (when set, always a
            clean, already-adjudicated boundary)."""
            nonlocal pending_save, saved_at
            if pending_save is None:
                return
            step, tb, lsd, tc = pending_save
            pending_save = None
            with _phase(timer, "checkpoint"):
                self._save_checkpoint(checkpointer, step, lsd, tables=tb,
                                      touched=tc)
            saved_at = step

        def dispatch():
            """Queue chunk ``i`` on the live state; its completion is the
            watcher's to stamp (a None test with no recorder)."""
            out = self.run_chunk(tables, local_state, chunk, ckey,
                                 timer=timer, recorder=rec)
            watch_device("device.fit_stream", out[2], timer, chunk=i,
                         **self._dense_fields())
            return out

        def sync_entry(entry):
            """Forced host sync for one dispatched chunk; on poison,
            _maybe_quarantine repoints the STORE at the restored state —
            the loop's tables/local_state are swapped by account_entry.
            Returns (metrics, restored_or_None)."""
            metrics = entry["metrics"]
            restored = None
            if quarantine is not None:
                with _phase(timer, "host_sync"):
                    metrics, restored = self._maybe_quarantine(
                        quarantine, entry["last_good"], metrics,
                        entry["index"], "chunk"
                    )
            elif sync_each:
                with _phase(timer, "host_sync"):
                    metrics = jax.tree.map(np.asarray, metrics)
            return metrics, restored

        def account_entry(entry, metrics, restored):
            """Accounting, callbacks, and boundary checkpoint for one
            adjudicated chunk; returns True when it was quarantined (the
            state is then already restored)."""
            nonlocal tables, local_state, pending_save, saved_at
            j = entry["index"]
            ev = {"index": j} if rec is not None else None
            poison = 0
            if sync_each and (rec is not None or health is not None):
                poison = self._fold_metrics_accounting(rec, metrics, ev)
            if rec is not None:
                rec.inc("driver.chunks")
                if restored is not None:
                    rec.inc("rollback.quarantined")
                    ev["quarantined"] = True
            self._apply_health_decision(health, rec, j, poison, "chunk")
            if restored is not None:
                if rec is not None:
                    rec.event("chunk", phases=timer.chunk_summary(), **ev)
                    rec.flush()
                if (self.retierer is not None
                        and entry.get("retier_state") is not None):
                    # The tracker rolls back WITH the tables: under
                    # health_lag=1 the restored aux entries predate the
                    # previous boundary's fold/re-rank, and a tracker
                    # that kept the newer hot_ids/tick would
                    # desynchronize from the ::hotids the program
                    # carries (the un-folded traffic still sits in the
                    # restored ::sketch window, so nothing is lost).
                    self.retierer.restore_snapshot(entry["retier_state"])
                tables, local_state = restored
                return True
            if on_chunk is not None:
                with _phase(timer, "host_sync"):
                    host_metrics = jax.tree.map(np.asarray, metrics)
                if rec is not None and not sync_each:
                    # on_chunk already paid the host sync; give the chunk
                    # event the same accounting the forced-sync paths get.
                    self._fold_metrics_accounting(rec, host_metrics, ev)
                all_metrics.append(host_metrics)
                with _phase(timer, "callback"):
                    on_chunk(j, host_metrics)
            else:
                # Deferred conversion keeps the dispatch pipeline full, but
                # an unbounded stream must not accumulate device buffers (or
                # run the host arbitrarily far ahead of the device): drain
                # to host every metrics_drain_every chunks (0 = never).
                all_metrics.append(metrics)
                de = cfg.metrics_drain_every
                if de and (j - start_step) % de == de - 1:
                    with _phase(timer, "host_sync"):
                        all_metrics[-de:] = [
                            jax.tree.map(np.asarray, m)
                            for m in all_metrics[-de:]
                        ]
            if save_due(j):
                if entry.get("save") is not None:
                    # Lag path: boundary copies were captured at dispatch
                    # time (the live tables have moved on since).
                    pending_save = entry["save"]
                    flush_save()
                elif overlap_ckpt:
                    # Immediate-adjudication path: capture now, write after
                    # the next dispatch — the dump's device_get then waits
                    # alongside device compute instead of in front of it.
                    pending_save = boundary_copy(j)
                else:
                    with _phase(timer, "checkpoint"):
                        self._save_checkpoint(checkpointer, j + 1,
                                              local_state,
                                              touched=capture_touched())
                    saved_at = j + 1
            if rec is not None:
                # Emitted AFTER the checkpoint/callback phases so the
                # chunk event's phase breakdown covers the whole chunk;
                # flushed per boundary so the Prometheus exposition is
                # live-scrapable mid-run and a kill loses at most one
                # chunk of buffered JSONL.
                rec.event("chunk", phases=timer.chunk_summary(), **ev)
                rec.flush()
            return False

        try:
            while True:
                with _phase(timer, "ingest"):
                    _beat(hb, i + 1, "prefetch" if pf is not None
                          else "ingest")
                    chunk = next(it, _STREAM_END)
                if chunk is _STREAM_END:
                    break
                i += 1
                if rollback is not None and i in rollback.preset:
                    # Quarantined by a previous attempt (supervisor-carried):
                    # the chunk is consumed but never dispatched — the per-
                    # chunk PRNG keys off i, so later chunks are unaffected.
                    rollback.skip(i)
                    if rec is not None:
                        rec.inc("rollback.preset_skipped")
                        rec.flush()
                    continue
                if delta_touched is not None:
                    # Every DISPATCHED chunk's pulled ids feed the delta
                    # tracker (a quarantined chunk's ids are a harmless
                    # superset — its rows revert to pre-chunk values).
                    delta_touched.observe(chunk_touched_ids(chunk))
                if quarantine is not None:
                    last_good = (resilience.tree_copy(tables),
                                 resilience.tree_copy(local_state))
                    rt_snap = (self.retierer.snapshot()
                               if self.retierer is not None else None)
                else:
                    last_good = None
                    rt_snap = None
                ckey = jax.random.fold_in(key, i)
                _beat(hb, i, "dispatch")
                if lag:
                    prev, pending = pending, None
                    with _watch(watchdog, "chunk", i):
                        tables, local_state, metrics = dispatch()
                        save = boundary_copy(i) if save_due(i) else None
                        # Adjudicate chunk i-1 NOW — its host sync waits
                        # while the device is already busy with chunk i.
                        pmetrics = prestored = None
                        if prev is not None:
                            pmetrics, prestored = sync_entry(prev)
                    if prev is not None:
                        if account_entry(prev, pmetrics, prestored):
                            # prev was poisoned and the pre-prev snapshot
                            # is restored — chunk i ran on poisoned
                            # state, so recompute it deterministically
                            # (same chunk, same key) from the restored
                            # state: exactly what the lag-0 path would
                            # have dispatched.
                            if quarantine is not None:
                                last_good = (
                                    resilience.tree_copy(tables),
                                    resilience.tree_copy(local_state))
                                rt_snap = (self.retierer.snapshot()
                                           if self.retierer is not None
                                           else None)
                            with _watch(watchdog, "chunk", i):
                                tables, local_state, metrics = dispatch()
                            save = boundary_copy(i) if save_due(i) else None
                        else:
                            retier_boundary(prev["index"])
                    pending = {"index": i, "metrics": metrics,
                               "last_good": last_good, "save": save,
                               "retier_state": rt_snap}
                else:
                    with _watch(watchdog, "chunk", i):
                        tables, local_state, metrics = dispatch()
                        entry = {"index": i, "metrics": metrics,
                                 "last_good": last_good, "save": None,
                                 "retier_state": rt_snap}
                        metrics, restored = sync_entry(entry)
                    flush_save()  # previous boundary's deferred dump —
                    # overlapped: the device is already past that boundary
                    if not account_entry(entry, metrics, restored):
                        retier_boundary(i)
            # Lag-by-one: the final chunk is still unadjudicated. Its
            # forced sync keeps watchdog coverage, like every other sync.
            if pending is not None:
                prev, pending = pending, None
                with _watch(watchdog, "chunk", prev["index"]):
                    pmetrics, prestored = sync_entry(prev)
                if not account_entry(prev, pmetrics, prestored):
                    retier_boundary(prev["index"])
            flush_save()
            # End-of-stream save whenever the last chunk's state isn't already
            # on disk — including when a quarantined final chunk skipped its
            # periodic save (the snapshot then holds the rolled-back state
            # under the final step number, so a resume skips the poison).
            if checkpointer is not None and i >= start_step and saved_at != i + 1:
                with _phase(timer, "checkpoint"):
                    self._save_checkpoint(checkpointer, i + 1, local_state,
                                          touched=capture_touched(),
                                          final=True)
        finally:
            if pf is not None:
                # Every exit path — normal end, raising on_chunk, health
                # abort, quarantine-budget abort — joins the prefetch
                # worker; no thread leaks (tested).
                pf.close()
            if checkpointer is not None:
                try:
                    # A clean, accepted boundary snapshot must not vanish
                    # just because the stream aborted before its deferred
                    # dump ran (the inline path would already have it on
                    # disk). Best-effort: teardown must not mask the
                    # original exception.
                    flush_save()
                except Exception:
                    _log.exception(
                        "deferred checkpoint dump failed during stream "
                        "teardown")
                # Durability barrier: an AsyncCheckpointer's in-flight
                # write must be on disk before the stream reports done
                # (no-op for the synchronous base class) — in a finally
                # so accepted (journaled checkpoint_enqueued) saves are
                # never silently dropped when the run dies mid-stream
                # (health abort, early-stop callback raise, ...).
                with _phase(timer, "checkpoint"):
                    checkpointer.flush()
        if on_chunk is None:
            with _phase(timer, "host_sync"):
                all_metrics = [jax.tree.map(np.asarray, m)
                               for m in all_metrics]
            if rec is not None and not sync_each:
                # Deferred-sync streams still get whole-run health totals
                # and example counts (per-chunk attribution needs a
                # syncing consumer: on_chunk, rollback, health, watchdog).
                for m in all_metrics:
                    self._fold_metrics_accounting(rec, m)
        if rec is not None:
            rec.flush()
        if metrics_reduce is not None and all_metrics:
            return tables, local_state, metrics_reduce(all_metrics)
        return tables, local_state, all_metrics
