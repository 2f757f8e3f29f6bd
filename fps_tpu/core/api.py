"""The user contract: WorkerLogic / ServerLogic, functionalized for SPMD.

Reference contract being preserved (SURVEY.md §2 #2–#4; expected upstream
``src/main/scala/hu/sztaki/ilab/ps/WorkerLogic.scala`` and the
``ParameterServerLogic`` / ``ParameterServerClient`` traits):

* ``WorkerLogic.onRecv(data, psClient)`` — consume a training record, issue
  ``psClient.pull(id)`` / ``psClient.push(id, delta)`` / ``psClient.output(o)``.
* ``WorkerLogic.onPullRecv(id, value, psClient)`` — continue once the pulled
  value arrives.
* ``ParameterServerLogic`` — per-shard state with ``onPullRecv`` /
  ``onPushRecv``; the shipped default ``SimplePSLogic`` is just
  ``paramInit: Int => P`` + ``paramUpdate: (P, P) => P``.

TPU functionalization
---------------------
The callback pair (onRecv → pull → onPullRecv) exists only because the
reference is asynchronous message passing. Under SPMD the round trip is a
collective with a known latency, so the two callbacks collapse into one pure
batch-step function and the client object disappears:

* ``WorkerLogic.pull_ids(batch)``  — which rows each table needs (the
  pull phase; one vectorized ``pull`` per table replaces per-record
  ``psClient.pull`` calls).
* ``WorkerLogic.step(batch, pulled, local_state, key)`` — the fused
  onRecv+onPullRecv body: compute updates, return pushes + outputs.
* ``ServerLogic`` — exactly ``SimplePSLogic``: per-table ``init_fn`` +
  fold for pushed deltas (additive by default, like every shipped
  reference algorithm).

Worker-local state (the reference keeps e.g. MF user vectors in worker
operator state) is the ``local_state`` pytree: arrays sharded over the
worker axes that only their owning device reads/writes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping

import jax

Array = jax.Array
Pytree = Any


@dataclasses.dataclass
class StepOutput:
    """What one worker step returns.

    Attributes:
      pushes: per-table ``(ids, deltas)`` — ids ``(B,)`` int32, deltas
        ``(B, dim)``. Zero-weight (padding) rows must carry id ``-1``
        (dropped by the store even for non-additive server folds); zero
        deltas alone are only a no-op for the additive default.
      local_state: updated worker-local pytree.
      out: the reference's ``WOut`` channel (``ParameterServerClient.output``)
        — a metrics/prediction pytree, summed or collected by the driver.
      dense_grads: for a logic that declares dense parameters
        (:class:`DenseLogic`), this worker's gradient of its own batch's
        loss by each of them, under the parameters' names and shapes; the
        driver sums them over the workers and folds them. ``None`` from
        every other logic.
    """

    pushes: Mapping[str, tuple[Array, Array]]
    local_state: Pytree
    out: Pytree
    dense_grads: Mapping[str, Array] | None = None


@dataclasses.dataclass(frozen=True)
class DenseLogic:
    """A worker logic's DENSE parameters: what every example touches (the
    layers of an MLP beside embedding tables), so no row of it is worth
    routing. Every worker holds all of them (replicated, ``P()`` over the
    mesh), reads them whole and returns a gradient for each; the driver
    sums the gradients over every worker axis (one all-reduce a step; on
    one device none) and applies ``theta -= learning_rate * sum`` inside
    the compiled call, in the step that made them, under the scope
    ``fps.dense`` (route ``dense.psum_sgd``). They never pass through a
    table's gather or scatter. Parallax's hybrid (arXiv:1808.02621): the
    sparse parameters on the servers, the dense ones data-parallel.

    ``init_fn(key) -> {name: array}`` makes them; names hold no ``::``.
    They ride the tables dict under ``<name>::dense``
    (:func:`fps_tpu.core.store.dense_key`) and a snapshot as ``dense::``
    arrays. A :class:`WorkerLogic` declares them by its ``dense``
    attribute; its ``step`` is then handed them as ``dense=`` and
    returns ``StepOutput.dense_grads``.
    """

    init_fn: Callable[[Array], Mapping[str, Array]]
    learning_rate: float


class WorkerLogic:
    """Base class for worker-side algorithm logic (pure functions only)."""

    # Dense (replicated) parameters beside the tables: a DenseLogic, or
    # None (every shipped logic but DLRM's). See DenseLogic.
    dense: "DenseLogic | None" = None

    def init_local_state(self, key: Array, num_workers: int) -> Pytree:
        """Per-device local state; called once under the driver's sharding."""
        return ()

    def prepare(self, batch: Pytree, key: Array) -> Pytree:
        """Augment the batch before pulling (e.g. sample negative ids
        on-device). Runs inside the compiled step; default is identity."""
        return batch

    def pull_ids(self, batch: Pytree) -> Mapping[str, Array]:
        """Map table name -> (B,) int32 ids to pull for this batch."""
        raise NotImplementedError

    def pulled_ids_host(self, chunk: Pytree) -> Mapping[str, Any] | None:
        """Optional HOST-side certification stream for the compacted cold
        routes (``TableSpec.cold_budget``; docs/performance.md
        "Payload-proportional routing").

        Return ``{table: int id array}`` per compactable table, computed
        from the RAW (un-``prepare``-d) host chunk: the LAST axis must be
        the worker-major per-step id stream (the global batch dim for
        one-id-per-example logics; multi-id columns shaped ``(T, B, k)``
        reshape to ``(T, B*k)`` — worker-major blocks survive the
        flatten) and the leading axes the chunk's step dims, and the
        stream must cover every id the compiled step pulls OR pushes for
        that table at each position. Padding positions may carry any id — the certifier
        counts them conservatively (a padding id outside the hot set
        consumes cold-lane budget, exactly as it would on device).

        ``None`` (default): chunks from this logic are not host-
        certifiable, so every chunk dispatches the static (full-payload)
        cold routes even when a ``cold_budget`` is configured. Logics
        whose ``prepare`` synthesizes ids on device (e.g. negative
        sampling) must return ``None`` unless the synthesized ids are
        provably hot."""
        return None

    def pulled_ids_traced(self, batch: Pytree) -> Mapping[str, Array] | None:
        """Optional TRACED certification stream for the compacted cold
        routes where no host id stream exists — the device-side half of
        :meth:`pulled_ids_host`, consumed by the megastep driver's
        in-graph overflow VOTE (``fps_tpu.core.megastep``).

        Called inside the compiled program with one worker's RAW
        (un-``prepare``-d) per-step batch; return ``{table: int id
        array}`` covering every id the step will pull OR push for that
        table (any shape — the vote flattens), or ``None`` when the
        logic cannot certify (ids synthesized in :meth:`prepare`).
        Whether ``None`` is returned must be decided by the logic's
        STATIC configuration, never by batch values — the megastep
        probes it once by abstract evaluation to choose between the
        voted and the always-static program. Padding positions may
        carry any id; the vote counts them conservatively, exactly like
        the host certifier."""
        return None

    def head_prefix(self, batch: Pytree) -> Mapping[str, int]:
        """Optional STATIC guarantee: table name -> count of LEADING ids
        (in both :meth:`pull_ids` order and the step's push order) that
        lie in ``[0, spec.hot_ids) ∪ {-1}`` — the frequency-ranked head a
        sorted-slot batch layout (``head_sort_slots``) puts first. The
        driver turns it into head-only kernel routing on single-device
        meshes (collective routes reorder the id streams, voiding the
        guarantee) — see ``fps_tpu.ops.gather_rows``. Counts must be
        plain ints derived from batch SHAPES (trace-time static).
        Default: no guarantee."""
        return {}

    def step(
        self,
        batch: Pytree,
        pulled: Mapping[str, Array],
        local_state: Pytree,
        key: Array,
    ) -> StepOutput:
        """Fused onRecv/onPullRecv body — must be jit-traceable."""
        raise NotImplementedError

    def touched_local_rows(self, batch: Pytree):
        """Optional: which axis-0 rows of each local-state leaf this
        batch's :meth:`step` can touch — the ids-aware refinement of the
        local guard (``GuardConfig(local=True)``). Return a sequence with
        ONE entry per flattened local-state leaf: an int id array
        (``-1`` = no row, e.g. padding examples) restricting that leaf's
        row screening to the touched rows, or ``None`` to screen every
        row of that leaf. Default ``None``: no guarantee, the guard
        screens (and in mask mode may revert) every row. Must be
        jit-traceable (called inside the compiled step). Rows OUTSIDE the
        returned set are still covered by the guard's leaf-tier
        non-finite net — they can be *counted*, never *masked* (an
        untouched row's pre-step value is its post-step value, so there
        is nothing to revert to)."""
        return None

    # -- checkpoint portability (optional overrides) -----------------------

    def export_local_state(self, local_state: Pytree) -> Pytree:
        """Host-side, worker-count-INDEPENDENT form of the local state for
        checkpointing (e.g. MF re-orders its worker-sharded user table to
        logical user order). Default: the raw pytree — restorable only at
        the same worker count."""
        return local_state

    def import_local_state(self, leaves: list, num_workers: int):
        """Inverse of :meth:`export_local_state`: rebuild the device-layout
        local-state pytree (host numpy) for ``num_workers`` workers from
        the exported leaves. Return ``NotImplemented`` (the default) to
        keep the raw same-worker-count restore path."""
        return NotImplemented


@dataclasses.dataclass(frozen=True)
class HotFold:
    """Stateful hot-tier optimizer fold (Adagrad / Adam server state).

    With the sharded reconcile (reduce-scatter → apply the owned 1/S
    slice → all-gather, docs/performance.md "Sharded reconcile"), every
    replica applies a DISJOINT slice of the hot head per window — so
    per-row optimizer state can live sharded over the replica axis
    instead of being replicated. A ``HotFold`` turns the window's
    combined delta ``g`` (after the ``combine`` normalization) into an
    adaptively-scaled step on the slice:

    * ``"adagrad"`` — ``G += g²; step = lr · g / (sqrt(G) + eps)``,
      ``G`` starting at ``initial_accumulator`` (0: a coordinate's first
      step is ``lr`` in size whatever ``|g|`` is, so two float32 sums of
      one gradient that differ in the last bit near zero step ``+lr`` and
      ``-lr``; a positive start, TensorFlow's 0.1, makes the first step
      continuous in ``g``);
    * ``"adam"`` — lazy per-row Adam: rows untouched in a window keep
      their moments and step count unchanged (sparse-table convention —
      decaying untouched rows would make zero-traffic rows drift), rows
      touched update ``m``/``v`` with bias correction by the row's own
      window count ``t``.

    The state is never replicated, never part of the canonical table
    bytes, and flush-reconciled like the pending-delta buffers: the
    canonical sharded table at any call boundary already holds the
    folded steps, so checkpoints stay byte-canonical (an untiered
    trainer restores them); the state itself rides the snapshot as
    separate ``fold::`` arrays so a supervised resume is bit-identical.

    Requires the hot tier to resolve ON for the table (multi-device,
    ``hot_sync_every > 1``, full replication — partial heads would give
    head rows an adaptive step and tail rows a raw one, a silent
    semantic fork, so they are rejected at resolution).

    The same declaration as ``ServerLogic.fold`` is the TABLE's own
    optimizer, hot tier or none: the window is then one step, the state
    one row an id, laid out and sharded like the table
    (:func:`fps_tpu.core.store.push`, ``fold=``).
    """

    kind: str  # "adagrad" | "adam"
    lr: float = 1.0
    eps: float = 1e-8
    beta1: float = 0.9
    beta2: float = 0.999
    initial_accumulator: float = 0.0  # Adagrad's G before the first push

    def __post_init__(self):
        if self.kind not in ("adagrad", "adam"):
            raise ValueError(
                f"HotFold.kind {self.kind!r} — expected 'adagrad' or 'adam'"
            )
        if self.initial_accumulator < 0 or (
                self.initial_accumulator and self.kind != "adagrad"):
            raise ValueError(
                "HotFold.initial_accumulator is Adagrad's starting G, a "
                f"value >= 0 — got {self.initial_accumulator!r} with kind "
                f"{self.kind!r}")

    def state_cols(self, dim: int) -> int:
        """Columns of per-row optimizer state: Adagrad keeps ``G``;
        Adam keeps ``(m, v, t)`` with the window count as a column."""
        return dim if self.kind == "adagrad" else 2 * dim + 1


def as_hot_fold(fold) -> HotFold | None:
    """Normalize the ``ServerLogic.hot_fold`` shorthand: a string names
    the fold kind with default hyperparameters; None passes through."""
    if fold is None or isinstance(fold, HotFold):
        return fold
    if isinstance(fold, str):
        return HotFold(kind=fold)
    raise TypeError(
        f"hot_fold must be a HotFold, a kind string, or None — got "
        f"{type(fold).__name__}"
    )


@dataclasses.dataclass(frozen=True)
class ServerLogic:
    """Per-table server fold — the reference's ``SimplePSLogic`` plus its
    pluggable combining senders.

    ``apply_fn(current_rows, combined_deltas) -> new_rows``; ``None`` means
    plain addition (``paramUpdate = _ + _``), which every algorithm shipped
    with the reference uses and which takes the fastest scatter-add path.

    ``combine`` controls how duplicate ids in one batch merge before the
    fold — the user-extensible analog of the reference's combination
    logic (expected upstream ``.../ps/client/sender/``): ``"sum"``
    (reference semantics), ``"mean"`` (per-id averaged step — stable for
    Zipfian hot ids under large batches), ``"max"`` / ``"min"``
    (elementwise extremum), or a callable ``(summed, counts) -> combined``
    over each row's per-id delta sum and push count (see
    :func:`fps_tpu.core.store.push`).

    ``hot_fold`` (a :class:`HotFold`, or its kind string) adds Adagrad /
    Adam optimizer state to the table's HOT TIER, sharded over the
    replica axis by the sharded reconcile — see :class:`HotFold` for the
    exact semantics and the resolution requirements. Ignored (with the
    tier's usual loud resolution errors) when the tier is off.

    ``fold`` (a :class:`HotFold`, or its kind string) is the TABLE's own
    optimizer, untiered: once a step the pushes of every worker are
    summed by id (``combine="sum"``, no ``apply_fn``) and each id pushed
    takes one :class:`HotFold` step on that sum, against optimizer state
    the trainer makes (zeros), carries, donates, shards by owner like the
    table and snapshots beside it (``fold::`` arrays, logical id order).
    A row nobody pushed keeps its value and its state bit for bit. On a
    table large against a step's pushes only the pushed rows and their
    state are read and written (``push.fold_rows`` in the route log), on
    a small one the ``(rows, dim + 1)`` accumulator is kept
    (``push.fold``): :func:`fps_tpu.core.store.push` chooses from the
    shapes. SSP rounds, ``push_delay``, the hot tier on the same table,
    ``auto_tier`` and the megastep are refused at construction.
    """

    apply_fn: Callable[[Array, Array], Array] | None = None
    combine: str | Callable[[Array, Array], Array] = "sum"
    hot_fold: "HotFold | str | None" = None
    fold: "HotFold | str | None" = None


ADDITIVE = ServerLogic(apply_fn=None)
MEAN_COMBINE = ServerLogic(apply_fn=None, combine="mean")
