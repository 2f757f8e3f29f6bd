"""Passive-aggressive classifier (binary + multiclass) on the PS.

Reference behavior being rebuilt (SURVEY.md §2 #9 / §3.4; expected upstream
``src/main/scala/hu/sztaki/ilab/ps/passive/aggressive/`` with
``PassiveAggressiveParameterServer.transformBinary`` / ``transformMulticlass``
and the closed-form algorithms in its ``algorithm/`` subpackage):

* model = weight vector (binary) or per-class weight vectors (multiclass),
  sharded by **feature id** across the servers;
* one sparse example fans out to one pull per nonzero feature; the reference
  buffers the example until all pull answers arrive, computes the margin and
  the PA/PA-I/PA-II closed-form step size, then pushes per-feature deltas;
* workloads: RCV1 binary classification.

TPU design: the pull-fanout-and-reassembly bookkeeping disappears — a batch
of examples pulls the *union* of its feature rows in one collective gather
(``(B*nnz,)`` flattened ids), computes all margins/taus dense on the VPU,
and pushes all per-feature deltas in one scatter-add. Within a batch,
updates are computed against the same pulled snapshot (mini-batch PA) —
the same interleaving the asynchronous reference produces when many
workers share the servers.

Closed-form step sizes (Crammer et al. 2006), hinge loss l = max(0, 1 - y·m):

* PA    : tau = l / ||x||^2
* PA-I  : tau = min(C, l / ||x||^2)
* PA-II : tau = l / (||x||^2 + 1/(2C))
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from fps_tpu.core.api import StepOutput, WorkerLogic
from fps_tpu.core.store import ParamStore, TableSpec

Array = jax.Array

WEIGHT_TABLE = "weights"


@dataclasses.dataclass
class PAConfig:
    """``batch_average=True`` (default) scales each example's PA step by
    1/local_batch — each worker applies the *average* of its batch's
    closed-form steps, and concurrent workers' pushes sum (exactly the
    reference's async semantics with W workers pushing interleaved
    single-example steps). Raw summing within a worker's batch diverges
    for the uncapped variants (PA, PA-II) once the batch is large.
    ``batch_average=False`` restores raw summing (safe for PA-I with
    small C or tiny batches)."""

    num_features: int
    num_classes: int = 2  # 2 => binary (single weight vector)
    variant: str = "PA-I"  # "PA" | "PA-I" | "PA-II"
    C: float = 1.0
    batch_average: bool = True
    # Feature ids [0, hot_features) are the table's frequency head
    # (``TableSpec.hot_ids``): what ``head_prefix_cols`` below refers to,
    # and nothing without it. Default 0: no head declared.
    hot_features: int = 0
    # Head-prefix routing (single-device meshes): set together with
    # ``hot_features = H`` after laying the dataset out with
    # ``fps_tpu.utils.datasets.head_sort_slots(data, H)`` — the returned
    # ``q`` is the number of leading slot COLUMNS guaranteed to carry ids
    # in [0, H). The worker then flattens ids nnz-major so those q*B
    # leading entries ride head-only kernels with ceil(H/128) row tiles
    # instead of ceil(num_features/128) (``fps_tpu.ops.gather_rows``
    # ``head_prefix``): the kernels' cost follows the row tiles they sweep.
    # Purely a routing hint: results are identical (to the dim-1 kernels'
    # documented hi+lo precision) with it on or off.
    head_prefix_cols: int = 0
    dtype: object = jnp.float32

    @property
    def table_dim(self) -> int:
        return 1 if self.num_classes == 2 else self.num_classes


def _tau(variant: str, C: float, loss: Array, x2: Array) -> Array:
    x2 = jnp.maximum(x2, 1e-12)
    if variant == "PA":
        return loss / x2
    if variant == "PA-I":
        return jnp.minimum(C, loss / x2)
    if variant == "PA-II":
        return loss / (x2 + 1.0 / (2.0 * C))
    raise ValueError(f"unknown PA variant {variant!r}")


class PassiveAggressiveWorker(WorkerLogic):
    """Binary PA: batch of sparse examples, one gather, one scatter-add.

    Batch columns: ``feat_ids (B, nnz)`` int32 (pad slots may hold any id as
    long as ``feat_vals`` is 0 there), ``feat_vals (B, nnz)``, ``label (B,)``
    in {-1, +1}, ``weight (B,)``.
    """

    def __init__(self, cfg: PAConfig):
        if cfg.num_classes != 2:
            raise ValueError("use MulticlassPassiveAggressiveWorker")
        self.cfg = cfg

    def _flatten(self, a: Array) -> Array:
        """(B, nnz[, ...]) -> (B*nnz[, ...]): nnz-major when head-prefix
        routing is on (so the head-sorted leading COLUMNS become the
        leading flat entries), row-major otherwise."""
        if self.cfg.head_prefix_cols:
            a = jnp.swapaxes(a, 0, 1)
        return a.reshape((-1,) + a.shape[2:])

    def pull_ids(self, batch) -> Mapping[str, Array]:
        return {WEIGHT_TABLE: self._flatten(
            batch["feat_ids"].astype(jnp.int32))}

    def head_prefix(self, batch) -> Mapping[str, int]:
        q = self.cfg.head_prefix_cols
        if not q:
            return {}
        B, nnz = batch["feat_ids"].shape
        return {WEIGHT_TABLE: min(q, nnz) * B}

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        B, nnz = batch["feat_ids"].shape
        x = batch["feat_vals"].astype(cfg.dtype)  # (B, nnz)
        y = batch["label"].astype(cfg.dtype)  # (B,)
        w = batch["weight"].astype(cfg.dtype)  # (B,)

        if cfg.head_prefix_cols:  # nnz-major pull order (see _flatten)
            wrows = pulled[WEIGHT_TABLE].reshape(nnz, B).T
        else:
            wrows = pulled[WEIGHT_TABLE].reshape(B, nnz)
        margin = jnp.sum(wrows * x, axis=-1)
        loss = jnp.maximum(0.0, 1.0 - y * margin)
        x2 = jnp.sum(x * x, axis=-1)
        tau = _tau(cfg.variant, cfg.C, loss, x2) * w  # zero for padding
        if cfg.batch_average:
            tau = tau / jnp.maximum(jnp.sum(w), 1.0)

        # Per-feature delta: tau * y * x_f; dropped slots push id -1.
        deltas = (tau * y)[:, None] * x  # (B, nnz)
        active = (x != 0.0) & (w[:, None] > 0)
        push_ids = jnp.where(active, batch["feat_ids"].astype(jnp.int32), -1)

        mistakes = jnp.sum(w * (jnp.sign(margin) != y))
        out = {
            "mistakes": mistakes.astype(jnp.float32),
            "loss": jnp.sum(loss * w).astype(jnp.float32),
            "n": jnp.sum(w).astype(jnp.float32),
        }
        pushes = {
            WEIGHT_TABLE: (self._flatten(push_ids),
                           self._flatten(deltas)[:, None])
        }
        return StepOutput(pushes=pushes, local_state=local_state, out=out)


class MulticlassPassiveAggressiveWorker(WorkerLogic):
    """Multiclass PA: per-class weight columns, max-margin violation update.

    For true class r and highest-scoring wrong class s:
    l = max(0, 1 - (score_r - score_s)), tau per variant with ||x||^2
    doubled (the update touches two class columns), push +tau·x to column r
    and -tau·x to column s. Mirrors the reference's multiclass algorithm
    shape (expected upstream ``.../passive/aggressive/algorithm/``).
    """

    def __init__(self, cfg: PAConfig):
        if cfg.num_classes < 3:
            raise ValueError("use PassiveAggressiveWorker for binary")
        if cfg.head_prefix_cols:
            # Head-prefix routing targets scalar tables (dim-1 kernels);
            # the multiclass table is (NF, num_classes). Fail loudly
            # rather than silently ignoring the knob.
            raise ValueError(
                "head_prefix_cols is binary-only (the multiclass table "
                "is not dim-1; no head-only kernel route exists for it)"
            )
        self.cfg = cfg

    def pull_ids(self, batch) -> Mapping[str, Array]:
        return {WEIGHT_TABLE: batch["feat_ids"].astype(jnp.int32).reshape(-1)}

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        B, nnz = batch["feat_ids"].shape
        C = cfg.num_classes
        x = batch["feat_vals"].astype(cfg.dtype)  # (B, nnz)
        r = batch["label"].astype(jnp.int32)  # (B,) class index
        w = batch["weight"].astype(cfg.dtype)

        wrows = pulled[WEIGHT_TABLE].reshape(B, nnz, C)
        scores = jnp.einsum("bn,bnc->bc", x, wrows)  # (B, C)
        r_onehot = jax.nn.one_hot(r, C, dtype=cfg.dtype)
        score_r = jnp.sum(scores * r_onehot, axis=-1)
        masked = jnp.where(r_onehot > 0, -jnp.inf, scores)
        s = jnp.argmax(masked, axis=-1)
        score_s = jnp.max(masked, axis=-1)

        loss = jnp.maximum(0.0, 1.0 - (score_r - score_s))
        x2 = 2.0 * jnp.sum(x * x, axis=-1)
        tau = _tau(cfg.variant, cfg.C, loss, x2) * w
        if cfg.batch_average:
            tau = tau / jnp.maximum(jnp.sum(w), 1.0)

        s_onehot = jax.nn.one_hot(s, C, dtype=cfg.dtype)
        class_dir = r_onehot - s_onehot  # (B, C)
        # delta[b, f, c] = tau_b * x_bf * class_dir_bc
        deltas = tau[:, None, None] * x[:, :, None] * class_dir[:, None, :]

        active = (x != 0.0) & (w[:, None] > 0)
        push_ids = jnp.where(active, batch["feat_ids"].astype(jnp.int32), -1)

        pred = jnp.argmax(scores, axis=-1)
        mistakes = jnp.sum(w * (pred != r))
        out = {
            "mistakes": mistakes.astype(jnp.float32),
            "loss": jnp.sum(loss * w).astype(jnp.float32),
            "n": jnp.sum(w).astype(jnp.float32),
        }
        pushes = {WEIGHT_TABLE: (push_ids.reshape(-1), deltas.reshape(-1, C))}
        return StepOutput(pushes=pushes, local_state=local_state, out=out)


def make_store(mesh, cfg: PAConfig) -> ParamStore:
    spec = TableSpec(
        name=WEIGHT_TABLE,
        num_ids=cfg.num_features,
        dim=cfg.table_dim,
        dtype=cfg.dtype,
        hot_ids=min(cfg.hot_features, cfg.num_features),
    ).zeros_init()  # reference: paramInit = 0.0 per feature
    return ParamStore(mesh, [spec])


def passive_aggressive(mesh, cfg: PAConfig, *, sync_every: int | None = None,
                       donate: bool = True,
                       max_steps_per_call: int | None = None,
                       guard=None):
    """(trainer, store) — the analog of
    ``PassiveAggressiveParameterServer.transformBinary/transformMulticlass``.

    ``guard``: push-delta health guard (``TrainerConfig.guard``) —
    ``"mask"`` drops poison updates in-step, ``"observe"`` only counts."""
    from fps_tpu.core.driver import Trainer, TrainerConfig

    store = make_store(mesh, cfg)
    worker = (
        PassiveAggressiveWorker(cfg)
        if cfg.num_classes == 2
        else MulticlassPassiveAggressiveWorker(cfg)
    )
    trainer = Trainer(
        mesh, store, worker,
        config=TrainerConfig(sync_every=sync_every, donate=donate,
                             max_steps_per_call=max_steps_per_call,
                             guard=guard),
    )
    return trainer, store


def predict_host(store: ParamStore, feat_ids: np.ndarray,
                 feat_vals: np.ndarray, num_classes: int = 2) -> np.ndarray:
    """Host-side predictions from the live table (binary: {-1,+1};
    multiclass: class index)."""
    rows = store.lookup_host(WEIGHT_TABLE, feat_ids.reshape(-1))
    B, nnz = feat_ids.shape
    rows = rows.reshape(B, nnz, -1)
    scores = np.einsum("bn,bnc->bc", feat_vals, rows)
    if num_classes == 2:
        return np.where(scores[:, 0] > 0, 1.0, -1.0)
    return np.argmax(scores, axis=-1)
