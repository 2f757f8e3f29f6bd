"""Sparse logistic regression with bounded-staleness SGD (Criteo CTR style).

This is the "async bounded-staleness SGD, multi-worker data-parallel"
workload named in BASELINE.json's configs. The reference framework runs any
such model through the same WorkerLogic/ServerLogic machinery; here it is
the canonical exerciser of the **SSP driver** (``sync_every=s``): workers
read weights from a snapshot up to ``s`` steps stale, compute sigmoid-loss
gradients over hashed sparse features, and push per-feature deltas that land
in the authoritative sharded table every step.

Batch columns: ``feat_ids (B, nnz)``, ``feat_vals (B, nnz)``,
``label (B,)`` in {0, 1}, ``weight (B,)``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from fps_tpu.core.api import ServerLogic, StepOutput, WorkerLogic
from fps_tpu.core.store import ParamStore, TableSpec

Array = jax.Array

WEIGHT_TABLE = "weights"


@dataclasses.dataclass
class LogRegConfig:
    num_features: int
    learning_rate: float = 0.1
    l2: float = 0.0
    batch_average: bool = True  # average grads over the local batch
    # "sgd": worker pushes lr-scaled deltas, server fold is additive (the
    # reference's SimplePSLogic semantics). "adagrad": worker pushes raw
    # [grad, grad^2] pairs and the server fold keeps a per-coordinate
    # accumulator IN the sharded table (column 1) — per-coordinate adaptive
    # rates tame Zipfian-hot features with no framework changes, showing
    # the ServerLogic fold is general enough to host optimizer state.
    optimizer: str = "sgd"
    adagrad_eps: float = 1e-6
    # FIXED-SLOT dense head: the first ``dense_features`` batch slots carry
    # feature id j at slot j in EVERY example (value 0 = inactive), the
    # Criteo loader's layout for the 13 numeric columns. The worker then
    # pulls those weights once per step (d rows, not B*d gathered rows)
    # and pushes ONE batch-combined delta per column — cutting the sparse
    # scatter from B*nnz to B*(nnz-d) rows. Semantically identical to
    # dense_features=0 under the additive server fold (the per-id sums are
    # just pre-combined on the worker; equal up to f32 reassociation).
    dense_features: int = 0
    dtype: object = jnp.float32

    def __post_init__(self):
        if self.optimizer not in ("sgd", "adagrad"):
            raise ValueError(f"unknown optimizer {self.optimizer!r}")
        if not 0 <= self.dense_features <= self.num_features:
            raise ValueError(
                f"dense_features={self.dense_features} out of range"
            )

    @property
    def table_width(self) -> int:
        """Columns per feature row: weight (+ AdaGrad accumulator)."""
        return 2 if self.optimizer == "adagrad" else 1


class LogisticRegressionWorker(WorkerLogic):
    def __init__(self, cfg: LogRegConfig):
        self.cfg = cfg

    def pull_ids(self, batch) -> Mapping[str, Array]:
        d = self.cfg.dense_features
        if not d:
            return {
                WEIGHT_TABLE: batch["feat_ids"].astype(jnp.int32).reshape(-1)
            }
        # Dense head: one static d-row pull (fixed-slot contract: slot j
        # carries id j for j < d) + the sparse tail per example.
        tail = batch["feat_ids"][:, d:].astype(jnp.int32).reshape(-1)
        return {
            WEIGHT_TABLE: jnp.concatenate(
                [jnp.arange(d, dtype=jnp.int32), tail]
            )
        }

    def pulled_ids_host(self, chunk):
        """Host certification/traffic stream (cold-route certifier +
        the delta-snapshot touched-rows tracker): the raw feature-id
        column covers every id the step pulls AND pushes. Multi-id
        contract shape: ``(T, B, nnz)`` flattens to ``(T, B*nnz)`` —
        worker-major blocks survive the flatten. A dense head pulls its
        ``d`` leading ids every step OUTSIDE the batch columns, which
        the per-position stream cannot express: those configs stay
        host-uncertifiable (None), like negative-sampling MF."""
        if self.cfg.dense_features:
            return None
        ids = np.asarray(chunk["feat_ids"])
        if ids.ndim >= 2:
            # (..., B, nnz) -> (..., B*nnz): worker-major blocks survive.
            ids = ids.reshape(*ids.shape[:-2], -1)
        return {WEIGHT_TABLE: ids}

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        d = cfg.dense_features
        B, nnz = batch["feat_ids"].shape
        x = batch["feat_vals"].astype(cfg.dtype)
        y = batch["label"].astype(cfg.dtype)  # {0,1}
        w = batch["weight"].astype(cfg.dtype)

        width = cfg.table_width
        if d:
            flat = pulled[WEIGHT_TABLE]
            head = jnp.broadcast_to(flat[:d, 0][None], (B, d))
            tail = flat[d:].reshape(B, nnz - d, width)[:, :, 0]
            wrows = jnp.concatenate([head, tail], axis=1)
        else:
            wrows = pulled[WEIGHT_TABLE].reshape(B, nnz, width)[:, :, 0]
        logit = jnp.sum(wrows * x, axis=-1)
        p = jax.nn.sigmoid(logit)
        g = (p - y) * w  # dL/dlogit, zeroed for padding

        n_real = jnp.maximum(jnp.sum(w), 1.0)
        norm = n_real if cfg.batch_average else 1.0
        grads = (g[:, None] * x + cfg.l2 * wrows * w[:, None]) / norm
        if cfg.optimizer == "adagrad":
            # raw gradient + its square; lr is applied by the server fold.
            deltas = jnp.stack([grads, grads * grads], axis=-1)
        else:
            deltas = (-cfg.learning_rate * grads)[:, :, None]

        active = (x != 0.0) & (w[:, None] > 0)
        if d:
            # Head: batch-combine on the worker (the per-id sum the server
            # fold would compute anyway) -> d pushed rows, not B*d.
            head_deltas = jnp.sum(
                jnp.where(active[:, :d, None], deltas[:, :d, :], 0.0),
                axis=0,
            )
            tail_ids = jnp.where(
                active[:, d:], batch["feat_ids"][:, d:].astype(jnp.int32), -1
            )
            push_ids = jnp.concatenate(
                [jnp.arange(d, dtype=jnp.int32), tail_ids.reshape(-1)]
            )
            push_deltas = jnp.concatenate(
                [head_deltas.astype(cfg.dtype),
                 deltas[:, d:, :].reshape(-1, width)]
            )
        else:
            push_ids = jnp.where(
                active, batch["feat_ids"].astype(jnp.int32), -1
            ).reshape(-1)
            push_deltas = deltas.reshape(-1, width)

        # log loss, clipped for monitoring stability.
        eps = 1e-7
        ll = -(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))
        mistakes = jnp.sum(w * ((p > 0.5) != (y > 0.5)))
        out = {
            "logloss": jnp.sum(ll * w).astype(jnp.float32),
            "mistakes": mistakes.astype(jnp.float32),
            "n": jnp.sum(w).astype(jnp.float32),
        }
        pushes = {WEIGHT_TABLE: (push_ids, push_deltas)}
        return StepOutput(pushes=pushes, local_state=local_state, out=out)


def make_store(mesh, cfg: LogRegConfig) -> ParamStore:
    spec = TableSpec(
        name=WEIGHT_TABLE, num_ids=cfg.num_features, dim=cfg.table_width,
        dtype=cfg.dtype,
    ).zeros_init()
    return ParamStore(mesh, [spec])


def adagrad_fold(lr: float, eps: float):
    """Server fold holding AdaGrad state in the table: column 0 = weight,
    column 1 = squared-gradient accumulator. The combined push delta is
    [sum g, sum g^2] per touched id."""

    def apply_fn(rows, delta):
        wcol, acc = rows[:, 0], rows[:, 1]
        gsum, g2sum = delta[:, 0], delta[:, 1]
        acc_new = acc + g2sum
        w_new = wcol - lr * gsum / (jnp.sqrt(acc_new) + eps)
        return jnp.stack([w_new, acc_new], axis=-1)

    return apply_fn


def logistic_regression(mesh, cfg: LogRegConfig, *,
                        sync_every: int | None = None, push_delay: int = 0,
                        donate: bool = True,
                        max_steps_per_call: int | None = None,
                        guard=None):
    """(trainer, store); pass ``sync_every=s`` for SSP bounded staleness.

    ``guard``: push-delta health guard (``TrainerConfig.guard``) —
    ``"mask"`` drops poison updates in-step, ``"observe"`` only counts."""
    from fps_tpu.core.driver import Trainer, TrainerConfig

    store = make_store(mesh, cfg)
    server_logic = (
        ServerLogic(apply_fn=adagrad_fold(cfg.learning_rate, cfg.adagrad_eps))
        if cfg.optimizer == "adagrad"
        else ServerLogic()
    )
    trainer = Trainer(
        mesh, store, LogisticRegressionWorker(cfg),
        server_logic=server_logic,
        config=TrainerConfig(sync_every=sync_every, push_delay=push_delay,
                             donate=donate,
                             max_steps_per_call=max_steps_per_call,
                             guard=guard),
    )
    return trainer, store


def predict_proba_host(store: ParamStore, feat_ids: np.ndarray,
                       feat_vals: np.ndarray) -> np.ndarray:
    rows = store.lookup_host(WEIGHT_TABLE, feat_ids.reshape(-1))
    B, nnz = feat_ids.shape
    weights = rows[:, 0]  # column 0 is the weight for every optimizer
    logit = np.sum(weights.reshape(B, nnz) * feat_vals, axis=-1)
    return 1.0 / (1.0 + np.exp(-logit))
