"""Online matrix factorization (SGD) — the reference's flagship algorithm.

Reference behavior being rebuilt (SURVEY.md §2 #8 / §3.3; expected upstream
``src/main/scala/hu/sztaki/ilab/ps/matrix/factorization/PSOnlineMatrixFactorization.scala``):

* rating stream ``(userId, itemId, score)`` (MovieLens-style);
* **item factor vectors are the PS parameters** — pulled/pushed by item id,
  hash-sharded across servers;
* **user factor vectors live in worker-local state** — the stream is
  partitioned by user so each worker owns its users' vectors outright;
* per rating: pull ``q_i`` → SGD step on ``(p_u, q_i)`` with learning rate
  and L2 regularization → ``p_u`` updated locally, ``Δq_i`` pushed;
* factors initialized by a per-id seeded uniform in a configured range so
  initialization is reproducible across shards;
* worker emits the prediction/error on the ``WOut`` channel.

TPU design: a batch of ratings per worker per step; one collective ``pull``
of the batch's item vectors; dense vectorized SGD on the (B, rank) blocks
(VPU work — rank is small); local scatter-add into the user block; collective
scatter-add ``push`` of item deltas. Duplicate users/items within a batch
accumulate additively into the same row — Hogwild-flavored, exactly the
update interleaving the asynchronous reference produces.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from fps_tpu.core.api import StepOutput, WorkerLogic
from fps_tpu.core.store import (
    ParamStore,
    TableSpec,
    make_table_values,
    pull_local,
    push_local,
    ranged_uniform_init,
    rows_per_shard,
)

Array = jax.Array

ITEM_TABLE = "item_factors"


@dataclasses.dataclass
class MFConfig:
    num_users: int
    num_items: int
    rank: int = 10
    learning_rate: float = 0.05
    reg: float = 0.01
    init_min: float = -0.1
    init_max: float = 0.1
    # Negative sampling of unrated items (the reference MF's optional knob,
    # SURVEY.md §2 #8): each rating additionally samples this many random
    # items, treated as pseudo-ratings of ``negative_target`` with weight
    # ``negative_weight`` in the same SGD step. Sharpens ranking on
    # implicit/positive-only feedback; 0 disables. Sampling is uniform over
    # items — with realistic catalog sizes the collision probability with
    # the user's true positives is negligible, matching the reference's
    # "sample unrated" intent without a per-user seen-set.
    negative_samples: int = 0
    negative_target: float = 0.0
    negative_weight: float = 1.0
    dtype: object = jnp.float32


class MatrixFactorizationWorker(WorkerLogic):
    """Worker logic: local user factors, pulled item factors, SGD updates."""

    def __init__(self, config: MFConfig, num_workers: int):
        self.cfg = config
        self.num_workers = num_workers

    # local_state = the worker-sharded user factor table (owner-major cyclic
    # over num_workers, like a PS table but never communicated).
    def init_local_state(self, key: Array, num_workers: int):
        return make_table_values(
            key,
            self.cfg.num_users,
            self.cfg.rank,
            num_workers,
            ranged_uniform_init(
                self.cfg.init_min, self.cfg.init_max, self.cfg.rank, self.cfg.dtype
            ),
            self.cfg.dtype,
        )

    def export_local_state(self, local_state):
        """User factors in LOGICAL user order (padding stripped) — the same
        worker-count-independent convention the store's tables use, so a
        checkpoint taken at one worker count restores at any other."""
        from fps_tpu.models.recommendation import mf_user_vectors

        return mf_user_vectors(
            np.asarray(local_state), self.num_workers,
            np.arange(self.cfg.num_users),
        )

    def import_local_state(self, leaves, num_workers):
        (logical,) = leaves
        nu, rank = self.cfg.num_users, self.cfg.rank
        if logical.shape != (nu, rank):
            raise ValueError(
                f"checkpointed user factors shape {logical.shape} != "
                f"({nu}, {rank})"
            )
        rps = -(-nu // num_workers)
        table = np.zeros((rps * num_workers, rank), logical.dtype)
        u = np.arange(nu)
        table[(u % num_workers) * rps + u // num_workers] = logical
        return table

    def prepare(self, batch, key):
        n = self.cfg.negative_samples
        if not n:
            return batch
        B = batch["item"].shape[0]
        negs = jax.random.randint(
            key, (B, n), 0, self.cfg.num_items, jnp.int32
        )
        # Single source of truth for the [positive, negatives] column
        # layout: pull_ids and step both consume this (B, 1+n) matrix, so
        # their orderings cannot drift apart.
        all_items = jnp.concatenate(
            [batch["item"].astype(jnp.int32)[:, None], negs], axis=1
        )
        return dict(batch, all_items=all_items)

    def pull_ids(self, batch) -> Mapping[str, Array]:
        if self.cfg.negative_samples:
            return {ITEM_TABLE: batch["all_items"].reshape(-1)}
        return {ITEM_TABLE: batch["item"].astype(jnp.int32)}

    def pulled_ids_host(self, chunk):
        """Cold-route certification stream (``TableSpec.cold_budget``):
        the raw item column covers every id the step pulls AND pushes —
        pushes mask padding to ``-1``, so certifying on the pull stream
        is conservative. With negative sampling the ids are synthesized
        on device in :meth:`prepare`, so chunks are not certifiable."""
        if self.cfg.negative_samples:
            return None
        return {ITEM_TABLE: chunk["item"]}

    def pulled_ids_traced(self, batch):
        """Device-side certification stream (the megastep's in-graph
        overflow vote): same contract as :meth:`pulled_ids_host`, from
        one worker's raw traced batch. Negative sampling synthesizes
        ids in :meth:`prepare`, so those configs stay uncertifiable."""
        if self.cfg.negative_samples:
            return None
        return {ITEM_TABLE: batch["item"].astype(jnp.int32)}

    def touched_local_rows(self, batch):
        """Ids-aware local-guard refinement: :meth:`step` scatters only
        into the batch's own users' LOCAL rows (``u // num_workers`` —
        ingest routes ``u % W == me``), so the guard's row screening can
        be restricted to exactly those; padding examples (weight 0) touch
        no row. One entry: the user-factor table is the only leaf."""
        u = batch["user"].astype(jnp.int32)
        live = batch["weight"].astype(jnp.float32) > 0
        return (jnp.where(live, u // self.num_workers, -1),)

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        n = cfg.negative_samples
        user_factors = local_state
        u = batch["user"].astype(jnp.int32)
        w = batch["weight"].astype(cfg.dtype)
        r = batch["rating"].astype(cfg.dtype)
        B = u.shape[0]
        if n:
            # Column 0 is the real rating; columns 1.. are sampled unrated
            # items with target negative_target and weight negative_weight
            # (layout defined once by prepare()'s all_items).
            q = pulled[ITEM_TABLE].reshape(B, 1 + n, -1)
            items = batch["all_items"]  # (B, 1+n)
            targets = jnp.concatenate(
                [r[:, None],
                 jnp.full((B, n), cfg.negative_target, cfg.dtype)], axis=1)
            wts = jnp.concatenate(
                [w[:, None],
                 w[:, None] * jnp.full((B, n), cfg.negative_weight,
                                       cfg.dtype)], axis=1)
        else:
            q = pulled[ITEM_TABLE][:, None, :]  # (B, 1, rank)
            items = batch["item"].astype(jnp.int32)[:, None]
            targets = r[:, None]
            wts = w[:, None]

        # Local row u // W (ingest routes u % W == me), both ways.
        p = pull_local(user_factors, u, num_shards=self.num_workers)

        pred = jnp.einsum("bd,bkd->bk", p, q)  # (B, 1+n)
        err = (targets - pred) * wts
        lr = cfg.learning_rate
        # Reference SGDUpdater: d_p = lr*(err*q - reg*p), d_q = lr*(err*p - reg*q).
        dp = lr * (jnp.einsum("bk,bkd->bd", err, q)
                   - cfg.reg * w[:, None] * p)
        dq = lr * (err[:, :, None] * p[:, None, :]
                   - cfg.reg * wts[:, :, None] * q)

        user_factors = push_local(user_factors, u, dp.astype(cfg.dtype),
                                  num_shards=self.num_workers)

        out = {
            # Quality metrics track the REAL ratings only (column 0), so
            # the train-RMSE line is comparable with negatives on or off.
            "se": jnp.sum(err[:, 0] * err[:, 0]).astype(jnp.float32),
            "n": jnp.sum(w).astype(jnp.float32),
        }
        # Padding rows push id -1 so the store drops them outright.
        push_ids = jnp.where(wts > 0, items, -1)
        pushes = {ITEM_TABLE: (push_ids.reshape(-1),
                               dq.reshape(B * (1 + n), -1))}
        return StepOutput(pushes=pushes, local_state=user_factors, out=out)


def make_store(mesh, cfg: MFConfig) -> ParamStore:
    spec = TableSpec(
        name=ITEM_TABLE,
        num_ids=cfg.num_items,
        dim=cfg.rank,
        init_fn=ranged_uniform_init(cfg.init_min, cfg.init_max, cfg.rank, cfg.dtype),
        dtype=cfg.dtype,
    )
    return ParamStore(mesh, [spec])


def online_mf(mesh, cfg: MFConfig, *, sync_every: int | None = None,
              push_delay: int = 0, donate: bool = True,
              max_steps_per_call: int | None = None,
              combine="sum", guard=None):
    """Construct (trainer, store) for online MF — the analog of
    ``PSOnlineMatrixFactorization.psOnlineMF(...)``.

    ``combine``: how duplicate item ids within one batch merge — ``"sum"``
    (the reference's per-message fold; faithful, but at very large batches
    Zipfian-hot items receive hundreds of summed steps per batch and SGD
    diverges) or ``"mean"`` (one averaged step per touched item per batch,
    the analog of the reference's combining senders — stable at any batch
    size).

    ``guard``: push-delta health guard (``TrainerConfig.guard``) —
    ``"mask"`` drops poison updates in-step, ``"observe"`` only counts."""
    from fps_tpu.core.api import ServerLogic
    from fps_tpu.core.driver import Trainer, TrainerConfig, num_workers_of

    store = make_store(mesh, cfg)
    worker = MatrixFactorizationWorker(cfg, num_workers_of(mesh))
    trainer = Trainer(
        mesh, store, worker,
        server_logic=ServerLogic(combine=combine),
        config=TrainerConfig(sync_every=sync_every, push_delay=push_delay,
                             donate=donate,
                             max_steps_per_call=max_steps_per_call,
                             guard=guard),
    )
    return trainer, store


def predict_host(
    store: ParamStore,
    user_factors_global: np.ndarray,
    num_workers: int,
    users: np.ndarray,
    items: np.ndarray,
) -> np.ndarray:
    """Host-side predictions from the live tables (for eval/RMSE)."""
    rps = rows_per_shard_global(user_factors_global, num_workers)
    phys = (users % num_workers) * rps + users // num_workers
    p = np.asarray(user_factors_global)[phys]
    q = store.lookup_host(ITEM_TABLE, items)
    return np.sum(p * q, axis=-1)


def rows_per_shard_global(table: np.ndarray, num_shards: int) -> int:
    return table.shape[0] // num_shards


def rmse(pred: np.ndarray, truth: np.ndarray) -> float:
    return float(np.sqrt(np.mean((pred - truth) ** 2)))
