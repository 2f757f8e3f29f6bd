"""DLRM (Naumov et al., arXiv:1906.00091): the hybrid click-through job.

The textbook parameter-server job with a dense part: the embedding
fields on the servers, two MLPs data-parallel beside them (Parallax,
arXiv:1808.02621). The ``F`` categorical fields are ONE table ``emb``
keyed ``offset[f] + token``, the parameter server's one key space (field
``f`` owns rows ``[offset[f], offset[f] + field_rows[f])``); the MLPs'
weights are the logic's DENSE parameters (``api.DenseLogic``): replicated,
handed to ``step`` whole, their gradients summed over the workers and
folded by the trainer's dense route, never gathered or scattered.

Per example, counts ``x`` (already ``log1p``), one token a field, label
``y`` in {0, 1}, weight ``q``:

* bottom MLP, ReLU after every layer: ``z0`` of the embedding width;
* ``e_f = emb[offset[f] + token_f]``;
* interaction ``dot``: ``T = [z0; e_1; ...; e_F]``, ``Z = T T^t``, ``p`` the
  entries of ``Z`` strictly below the diagonal, row by row; ``r = [z0, p]``;
* top MLP, ReLU on the hidden layers; its one output is the logit;
* ``L = sum_e q_e bce(sigmoid(logit_e), y_e) / max(sum_e q_e, 1)`` over
  the worker's batch, taken from the logit (no clip);
* plain SGD on rows and MLPs alike: pushed ``-lr dL/de_f`` a pulled row
  (summed by id, ``combine="sum"``); the dense gradients go back raw and
  the dense route applies ``theta -= lr * sum over workers``.

float32; every matrix product at ``jax.lax.Precision.HIGHEST``.

Batch columns: ``tokens (B, F)`` int32, ``counts (B, numeric)``,
``label (B,)`` in {0, 1}, ``weight (B,)``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from fps_tpu.core.api import DenseLogic, StepOutput, WorkerLogic
from fps_tpu.core.store import (
    ParamStore, TableSpec, ranged_uniform_init, split_dense,
)

Array = jax.Array

EMB_TABLE = "emb"
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class DLRMConfig:
    """Shapes as ``facebookresearch/dlrm``'s ``bench/dlrm_s_criteo_kaggle.sh``
    states them (``--arch-sparse-feature-size=16
    --arch-mlp-bot="13-512-256-64-16" --arch-mlp-top="512-256-1"``):
    ``bottom_mlp`` and ``top_mlp`` list each layer's OUTPUT width; the
    bottom's input is ``numeric``, its last output ``embed_dim``, the top's
    input what the interaction leaves."""

    field_rows: tuple[int, ...]
    embed_dim: int = 16
    numeric: int = 13
    bottom_mlp: tuple[int, ...] = (512, 256, 64, 16)
    top_mlp: tuple[int, ...] = (512, 256, 1)
    learning_rate: float = 0.1
    dtype: object = jnp.float32

    def __post_init__(self):
        self.field_rows = tuple(int(n) for n in self.field_rows)
        self.bottom_mlp = tuple(int(n) for n in self.bottom_mlp)
        self.top_mlp = tuple(int(n) for n in self.top_mlp)
        if self.bottom_mlp[-1] != self.embed_dim:
            raise ValueError(
                f"the bottom MLP ends at {self.bottom_mlp[-1]}, the "
                f"interaction needs embed_dim={self.embed_dim}")
        if self.top_mlp[-1] != 1:
            raise ValueError("the top MLP ends in one logit")
        if sum(self.field_rows) >= 2**31:
            raise ValueError("the fields' rows overflow an int32 key space")

    @property
    def field_offsets(self) -> tuple[int, ...]:
        """First row of each field in the one key space."""
        return tuple(int(o) for o in
                     np.concatenate([[0], np.cumsum(self.field_rows)[:-1]]))

    @property
    def num_rows(self) -> int:
        return sum(self.field_rows)

    @property
    def interact_width(self) -> int:
        """``embed_dim`` + the pairs of the ``F + 1`` vectors."""
        v = len(self.field_rows) + 1
        return self.embed_dim + v * (v - 1) // 2

    def layer_shapes(self) -> dict[str, tuple[int, ...]]:
        """Every dense parameter's shape by name: ``bot_w<l>``
        ``(inputs, outputs)``, ``bot_b<l>`` ``(outputs,)``, ``top_*``."""
        shapes = {}
        for stack, n_in, widths in (("bot", self.numeric, self.bottom_mlp),
                                    ("top", self.interact_width,
                                     self.top_mlp)):
            for l, n_out in enumerate(widths):
                shapes[f"{stack}_w{l}"] = (n_in, n_out)
                shapes[f"{stack}_b{l}"] = (n_out,)
                n_in = n_out
        return shapes


def init_dense(cfg: DLRMConfig, key: Array) -> dict[str, Array]:
    """The reference implementation's law: a layer of ``n`` inputs and
    ``m`` outputs draws its weights normal with variance ``2 / (m + n)``
    and its bias normal with variance ``1 / m``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(cfg.layer_shapes().items())):
        if len(shape) == 2:
            std = np.sqrt(2.0 / (shape[0] + shape[1]))
        else:
            std = np.sqrt(1.0 / shape[0])
        out[name] = (std * jax.random.normal(
            jax.random.fold_in(key, i), shape)).astype(cfg.dtype)
    return out


def emb_init(cfg: DLRMConfig):
    """``TableSpec.init_fn``: field ``f``'s rows uniform in
    ``+-sqrt(1 / field_rows[f])``, drawn per id."""
    edges = np.cumsum(cfg.field_rows)[:-1].astype(np.int32)
    half = jnp.asarray(1.0 / np.sqrt(np.asarray(cfg.field_rows, np.float64)),
                       jnp.float32)

    def init(key, ids):
        # Rows past the last field (a sharded table's padding) take its law.
        field = jnp.sum(ids[:, None] >= edges[None, :], axis=1)
        u = ranged_uniform_init(-1.0, 1.0, cfg.embed_dim)(key, ids)
        return (u * half[field][:, None]).astype(cfg.dtype)

    return init


def _mlp(dense, stack: str, depth: int, h, last_relu: bool):
    for l in range(depth):
        h = jnp.dot(h, dense[f"{stack}_w{l}"], precision=HIGHEST)
        h = h + dense[f"{stack}_b{l}"]
        if l < depth - 1 or last_relu:
            h = jax.nn.relu(h)
    return h


def _bottom(cfg: DLRMConfig, dense, x):
    return _mlp(dense, "bot", len(cfg.bottom_mlp), x, last_relu=True)


def _interact(z0, e):
    """``[z0, the strictly-lower triangle of T T^t row by row]``."""
    T = jnp.concatenate([z0[:, None, :], e], axis=1)
    Z = jnp.einsum("bid,bjd->bij", T, T, precision=HIGHEST)
    li, lj = np.tril_indices(T.shape[1], k=-1)
    pairs = jnp.take(Z.reshape(Z.shape[0], -1),
                     jnp.asarray(li * T.shape[1] + lj, jnp.int32), axis=1)
    return jnp.concatenate([z0, pairs], axis=1)


def _top(cfg: DLRMConfig, dense, r):
    return _mlp(dense, "top", len(cfg.top_mlp), r, last_relu=False)[:, 0]


def forward(cfg: DLRMConfig, dense, x, e):
    """Logits ``(B,)`` from counts ``x (B, numeric)`` and the pulled rows
    ``e (B, F, embed_dim)``."""
    return _top(cfg, dense, _interact(_bottom(cfg, dense, x), e))


def bce_with_logits(logit, y):
    """``-(y log p + (1 - y) log(1 - p))`` at ``p = sigmoid(logit)``,
    from the logit: exact where ``p`` rounds to 0 or 1, so no clip."""
    return (jnp.maximum(logit, 0.0) - logit * y
            + jnp.log1p(jnp.exp(-jnp.abs(logit))))


class DLRMWorker(WorkerLogic):
    def __init__(self, cfg: DLRMConfig):
        self.cfg = cfg
        self.dense = DenseLogic(init_fn=lambda key: init_dense(cfg, key),
                                learning_rate=cfg.learning_rate)
        self._offsets = np.asarray(cfg.field_offsets, np.int32)

    def _ids(self, batch) -> Array:
        return batch["tokens"].astype(jnp.int32) + self._offsets[None, :]

    def pull_ids(self, batch) -> Mapping[str, Array]:
        return {EMB_TABLE: self._ids(batch).reshape(-1)}

    def pulled_ids_host(self, chunk):
        ids = np.asarray(chunk["tokens"]).astype(np.int64) + self._offsets
        return {EMB_TABLE: ids.reshape(*ids.shape[:-2], -1)}

    def step(self, batch, pulled, local_state, key, *, dense) -> StepOutput:
        cfg = self.cfg
        B, F = batch["tokens"].shape
        x = batch["counts"].astype(cfg.dtype)
        y = batch["label"].astype(cfg.dtype)
        w = batch["weight"].astype(cfg.dtype)
        e = pulled[EMB_TABLE].reshape(B, F, cfg.embed_dim)
        n = jnp.maximum(jnp.sum(w), 1.0)
        # The chain rule by hand over the three parts, so that each part's
        # forward AND backward ops stand under its own scope on the
        # device's timeline (dlrm.bottom / dlrm.interact / dlrm.top).
        bot = {k: v for k, v in dense.items() if k.startswith("bot_")}
        top = {k: v for k, v in dense.items() if k.startswith("top_")}
        with jax.named_scope("dlrm.bottom"):
            z0, back_bottom = jax.vjp(lambda d: _bottom(cfg, d, x), bot)
        with jax.named_scope("dlrm.interact"):
            r, back_interact = jax.vjp(_interact, z0, e)
        with jax.named_scope("dlrm.top"):
            logit, back_top = jax.vjp(lambda d, r: _top(cfg, d, r), top, r)
        total = jnp.sum(w * bce_with_logits(logit, y))
        g_logit = w * (jax.nn.sigmoid(logit) - y) / n
        with jax.named_scope("dlrm.top"):
            g_top, g_r = back_top(g_logit)
        with jax.named_scope("dlrm.interact"):
            g_z0, g_e = back_interact(g_r)
        with jax.named_scope("dlrm.bottom"):
            (g_bot,) = back_bottom(g_z0)
        ids = jnp.where(w[:, None] > 0, self._ids(batch), -1).reshape(-1)
        deltas = (-cfg.learning_rate * g_e).reshape(-1, cfg.embed_dim)
        out = {
            "logloss": total.astype(jnp.float32),
            "mistakes": jnp.sum(w * ((logit > 0) != (y > 0.5))).astype(
                jnp.float32),
            "n": jnp.sum(w).astype(jnp.float32),
        }
        return StepOutput(pushes={EMB_TABLE: (ids, deltas)},
                          local_state=local_state, out=out,
                          dense_grads={**g_bot, **g_top})


def make_store(mesh, cfg: DLRMConfig) -> ParamStore:
    return ParamStore(mesh, [TableSpec(
        name=EMB_TABLE, num_ids=cfg.num_rows, dim=cfg.embed_dim,
        dtype=cfg.dtype, init_fn=emb_init(cfg))])


def dlrm(mesh, cfg: DLRMConfig, *, max_steps_per_call: int | None = None):
    """(trainer, store): the fields as one additive table, the MLPs on the
    trainer's dense route."""
    from fps_tpu.core.driver import Trainer, TrainerConfig

    store = make_store(mesh, cfg)
    trainer = Trainer(
        mesh, store, DLRMWorker(cfg),
        config=TrainerConfig(max_steps_per_call=max_steps_per_call))
    return trainer, store


def predict_proba_host(cfg: DLRMConfig, store: ParamStore,
                       tokens: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Click probabilities of host rows under the store's live state."""
    ids = tokens.astype(np.int64) + np.asarray(cfg.field_offsets, np.int64)
    e = store.lookup_host(EMB_TABLE, ids.reshape(-1)).reshape(
        tokens.shape + (cfg.embed_dim,))
    dense = {k: np.asarray(v)
             for k, v in split_dense(store.tables)[1].items()}
    with jax.default_device(jax.devices("cpu")[0]):
        logit = forward(cfg, dense, jnp.asarray(counts, cfg.dtype),
                        jnp.asarray(e))
        return np.asarray(jax.nn.sigmoid(logit))
