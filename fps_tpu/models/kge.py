"""ComplEx knowledge-graph embeddings (Trouillon et al., ICML 2016,
arXiv:1606.06357) with AdaGrad held at the server: the KGE task of Lapse
and NuPS (arXiv:2104.00501).

Two tables of one width: ``entity [E, 2K]`` and ``relation [R, 2K]``, a
row the complex vector ``[re | im]`` of rank ``K``. Each is folded by its
OWN optimizer (``ServerLogic.fold``, AdaGrad): the trainer keeps one
accumulator row an id beside the table (every component
``initial_accumulator`` at the start) and a step touches the rows it
pushed, no others.

Per positive triple ``(s, r, o)`` with weight ``q`` (0 = padding):

* ``prepare`` draws, from the step's key, ``negatives`` corruptions: a
  fair coin ``side`` (subject or object) and a replacement entity uniform
  over ``[0, E)``; accidental positives are not filtered;
* score ``phi(s, r, o) = sum_k Re(e_s[k] w_r[k] conj(e_o[k]))``;
* scored triples: the positive with ``y = +1`` and each corruption with
  ``y = -1``; a scored triple's loss is ``softplus(-y phi) + l2 (|e_s|^2 +
  |w_r|^2 + |e_o|^2)`` over the rows it scores;
* pushed: MINUS the gradient of the worker's ``q``-weighted summed loss by
  every pulled row (a subject's gradient summed over the positive and the
  corruptions that kept it), ``-1`` ids on padding; the fold sums a row's
  pushes over all workers and takes one AdaGrad step on the sum.

Batch columns: ``s``, ``r``, ``o`` int32, ``weight``. float32.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from fps_tpu.core.api import HotFold, ServerLogic, StepOutput, WorkerLogic
from fps_tpu.core.store import ParamStore, TableSpec

Array = jax.Array

ENTITY_TABLE = "entity"
RELATION_TABLE = "relation"


@dataclasses.dataclass
class KGEConfig:
    num_entities: int
    num_relations: int
    rank: int = 500            # complex components; a row holds 2 * rank
    negatives: int = 10
    l2: float = 1e-5
    learning_rate: float = 0.1
    eps: float = 1e-8
    # AdaGrad's G before a row's first push. From ZERO a coordinate's first
    # step is lr in size whatever its gradient: a last-bit difference in a
    # near-zero summed gradient (another order of the same float32 addends)
    # becomes +lr against -lr and spreads through every row that scores
    # with it; at rank 500 one run in twenty then differs from a plain
    # float32 replay of itself by percents in its hottest coordinates
    # (PERF.md, PR 51). 0.1 is TensorFlow's default.
    initial_accumulator: float = 0.1
    init_std: float = 0.1
    dtype: object = jnp.float32

    @property
    def dim(self) -> int:
        return 2 * self.rank


def _parts(rows: Array) -> tuple[Array, Array]:
    k = rows.shape[-1] // 2
    return rows[..., :k], rows[..., k:]


def complex_score(es: Array, wr: Array, eo: Array) -> Array:
    """``sum_k Re(e_s w_r conj(e_o))`` over the last axis, written out in
    its four real products."""
    a_s, b_s = _parts(es)
    a_r, b_r = _parts(wr)
    a_o, b_o = _parts(eo)
    return jnp.sum(a_s * a_r * a_o + a_s * b_r * b_o + b_s * a_r * b_o
                   - b_s * b_r * a_o, axis=-1)


def step_loss(cfg: KGEConfig, es, wr, eo, en, side, q) -> Array:
    """The worker's ``q``-weighted loss from its pulled rows: subjects
    ``es``, relations ``wr``, objects ``eo`` (each ``(B, 2K)``),
    replacements ``en (B, N, 2K)`` and their coins ``side (B, N)`` (True:
    the subject is replaced)."""
    sq = lambda x: jnp.sum(x * x, axis=-1)  # noqa: E731
    pos = jax.nn.softplus(-complex_score(es, wr, eo)) + cfg.l2 * (
        sq(es) + sq(wr) + sq(eo))
    subj = jnp.where(side[..., None], en, es[:, None, :])
    obj = jnp.where(side[..., None], eo[:, None, :], en)
    neg = jax.nn.softplus(complex_score(subj, wr[:, None, :], obj)) + cfg.l2 * (
        sq(subj) + sq(wr)[:, None] + sq(obj))
    return jnp.sum(q * (pos + jnp.sum(neg, axis=1)))


class KGEWorker(WorkerLogic):
    def __init__(self, cfg: KGEConfig):
        self.cfg = cfg

    def prepare(self, batch, key):
        B, N = batch["s"].shape[0], self.cfg.negatives
        k_side, k_ent = jax.random.split(key)
        return dict(
            batch,
            neg_side=jax.random.bernoulli(k_side, 0.5, (B, N)),
            neg_entity=jax.random.randint(
                k_ent, (B, N), 0, self.cfg.num_entities, jnp.int32))

    def _entity_ids(self, batch) -> Array:
        return jnp.concatenate([
            batch["s"].astype(jnp.int32), batch["o"].astype(jnp.int32),
            batch["neg_entity"].reshape(-1)])

    def pull_ids(self, batch) -> Mapping[str, Array]:
        return {ENTITY_TABLE: self._entity_ids(batch),
                RELATION_TABLE: batch["r"].astype(jnp.int32)}

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        B, N = batch["neg_entity"].shape
        q = batch["weight"].astype(cfg.dtype)
        ent = pulled[ENTITY_TABLE]
        es, eo = ent[:B], ent[B:2 * B]
        en = ent[2 * B:].reshape(B, N, cfg.dim)
        wr = pulled[RELATION_TABLE]
        # kge.score: the scoring and its backward, apart from what the
        # step does to shape its pushes.
        with jax.named_scope("kge.score"):
            loss, (g_s, g_r, g_o, g_n) = jax.value_and_grad(
                lambda *rows: step_loss(cfg, *rows, batch["neg_side"], q),
                argnums=(0, 1, 2, 3))(es, wr, eo, en)
        live = q > 0
        ent_ids = jnp.where(jnp.concatenate([live, live, jnp.repeat(live, N)]),
                            self._entity_ids(batch), -1)
        rel_ids = jnp.where(live, batch["r"].astype(jnp.int32), -1)
        pushes = {
            ENTITY_TABLE: (ent_ids, -jnp.concatenate(
                [g_s, g_o, g_n.reshape(B * N, cfg.dim)])),
            RELATION_TABLE: (rel_ids, -g_r),
        }
        out = {"loss": loss.astype(jnp.float32),
               "n": jnp.sum(q).astype(jnp.float32)}
        return StepOutput(pushes=pushes, local_state=local_state, out=out)


def normal_init(std: float, dim: int, dtype=jnp.float32):
    """``TableSpec.init_fn``: every component normal, standard deviation
    ``std``, drawn per id."""
    def init(key, ids):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
        return (std * jax.vmap(
            lambda k: jax.random.normal(k, (dim,), jnp.float32))(keys)
                ).astype(dtype)
    return init


def make_store(mesh, cfg: KGEConfig) -> ParamStore:
    init = normal_init(cfg.init_std, cfg.dim, cfg.dtype)
    return ParamStore(mesh, [
        TableSpec(name=ENTITY_TABLE, num_ids=cfg.num_entities, dim=cfg.dim,
                  dtype=cfg.dtype, init_fn=init),
        TableSpec(name=RELATION_TABLE, num_ids=cfg.num_relations,
                  dim=cfg.dim, dtype=cfg.dtype, init_fn=init),
    ])


def kge(mesh, cfg: KGEConfig, *, max_steps_per_call: int | None = None):
    """(trainer, store): both tables under AdaGrad at the server, the
    table's own fold (``ServerLogic.fold``)."""
    from fps_tpu.core.driver import Trainer, TrainerConfig

    store = make_store(mesh, cfg)
    fold = ServerLogic(fold=HotFold(
        "adagrad", lr=cfg.learning_rate, eps=cfg.eps,
        initial_accumulator=cfg.initial_accumulator))
    trainer = Trainer(
        mesh, store, KGEWorker(cfg), server_logic=fold,
        config=TrainerConfig(max_steps_per_call=max_steps_per_call))
    return trainer, store


def score_host(store: ParamStore, s, r, o) -> np.ndarray:
    """``phi`` of host triples under the store's live tables."""
    rows = [store.lookup_host(ENTITY_TABLE, np.asarray(s)),
            store.lookup_host(RELATION_TABLE, np.asarray(r)),
            store.lookup_host(ENTITY_TABLE, np.asarray(o))]
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(complex_score(*map(jnp.asarray, rows)))
