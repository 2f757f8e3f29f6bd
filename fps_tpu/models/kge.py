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

``complex_score`` and ``step_loss`` are the DEFINITION, written out;
the worker computes the same loss and pushes through the score's linear
form (``loss_and_pushes``), which ``tests/test_kge.py`` holds to
``jax.value_and_grad`` of the definition.
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


def _subject_partner(wr, eo) -> Array:
    """``u (B, 2K)`` with ``phi(x, r, o) = <x, u>`` over all ``2K``
    components of the subject's row ``x``."""
    a_r, b_r = _parts(wr)
    a_o, b_o = _parts(eo)
    return jnp.concatenate([a_r * a_o + b_r * b_o, a_r * b_o - b_r * a_o],
                           axis=-1)


def _object_partner(es, wr) -> Array:
    """``v (B, 2K)`` with ``phi(s, r, x) = <x, v>`` for the object's row
    ``x``."""
    a_s, b_s = _parts(es)
    a_r, b_r = _parts(wr)
    return jnp.concatenate([a_s * a_r - b_s * b_r, a_s * b_r + b_s * a_r],
                           axis=-1)


def loss_and_pushes(cfg: KGEConfig, ent, wr, side, q):
    """:func:`step_loss` and MINUS its gradient by every pulled row,
    through the score's linear form: ``phi`` is linear in each of its three
    rows, so a corruption scores ``<e_n, u>`` (subject replaced) or
    ``<e_n, v>`` (object replaced) against a ``(B, 2K)`` partner of the
    rows it keeps, and every pass over the replacements takes them as
    whole rows.

    The entity rows come and go in the pull's own 2-D layout,
    ``ent (B (2 + N), 2K)``: subjects, objects, then the replacements
    CORRUPTION-MAJOR, rows ``[(2 + j) B, (3 + j) B)`` the ``j``-th
    corruption of every positive, with ``side (N, B)`` laid the same way.
    Each such slab is scored against ``u`` and ``v`` as they are: a
    ``(B, N, 2K)`` view pads N to the tile's 8 sublanes, and a partner
    broadcast along a new axis is written out whole. Returns ``loss,
    entity pushes (as ent), relation pushes (B, 2K)``."""
    sq = lambda x: jnp.sum(x * x, axis=-1)  # noqa: E731
    N, B = side.shape
    es, eo, *slabs = (ent[j * B:(j + 1) * B] for j in range(2 + N))
    u, pull_u = jax.vjp(_subject_partner, wr, eo)
    v, pull_v = jax.vjp(_object_partner, es, wr)
    phi_pos = jnp.sum(es * u, axis=-1)
    phi_neg = jnp.where(
        side, jnp.stack([jnp.sum(x * u, axis=-1) for x in slabs]),
        jnp.stack([jnp.sum(x * v, axis=-1) for x in slabs]))
    sq_s, sq_r, sq_o = sq(es), sq(wr), sq(eo)
    softplus_pos, pull_pos = jax.vjp(jax.nn.softplus, -phi_pos)
    softplus_neg, pull_neg = jax.vjp(jax.nn.softplus, phi_neg)
    pos = softplus_pos + cfg.l2 * (sq_s + sq_r + sq_o)
    neg = softplus_neg + cfg.l2 * (
        jnp.stack([sq(x) for x in slabs]) + sq_r
        + jnp.where(side, sq_o, sq_s))
    loss = jnp.sum(q * (pos + jnp.sum(neg, axis=0)))
    # Backward, in the pushes' sign: c = -dL/dphi, decay = -dL/d|row|^2.
    # softplus' own derivative as autodiff has it, as the definition's
    # gradient does: the chip's logistic is another float32 function.
    (c_pos,) = pull_pos(q)
    c_neg = -pull_neg(jnp.broadcast_to(q, phi_neg.shape))[0]
    decay = (-cfg.l2 * 2 * q)[:, None]
    p_n = [c[:, None] * jnp.where(a[:, None], u, v) + decay * x
           for c, a, x in zip(c_neg, side, slabs)]
    p_u = c_pos[:, None] * es + sum(
        jnp.where(a, c, 0)[:, None] * x for c, a, x in zip(c_neg, side, slabs))
    p_v = sum(
        jnp.where(a, 0, c)[:, None] * x for c, a, x in zip(c_neg, side, slabs))
    r_u, o_u = pull_u(p_u)
    s_v, r_v = pull_v(p_v)
    # The L2 of a kept row, once a scored triple that scores it.
    replaced = jnp.sum(side, axis=0).astype(q.dtype)[:, None]  # subjects
    p_s = c_pos[:, None] * u + s_v + decay * (1 + N - replaced) * es
    p_o = o_u + decay * (1 + replaced) * eo
    p_r = r_u + r_v + decay * (1 + N) * wr
    return loss, jnp.concatenate([p_s, p_o, *p_n]), p_r


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
        # Subjects, objects, then the replacements corruption-major: the
        # j-th corruption of every positive is one contiguous (B, 2K) slab
        # of the pulled rows.
        return jnp.concatenate([
            batch["s"].astype(jnp.int32), batch["o"].astype(jnp.int32),
            batch["neg_entity"].T.reshape(-1)])

    def pull_ids(self, batch) -> Mapping[str, Array]:
        return {ENTITY_TABLE: self._entity_ids(batch),
                RELATION_TABLE: batch["r"].astype(jnp.int32)}

    def step(self, batch, pulled, local_state, key) -> StepOutput:
        cfg = self.cfg
        N = cfg.negatives
        q = batch["weight"].astype(cfg.dtype)
        # kge.score: the scoring, its backward and the pushes as they
        # leave, apart from what the step does to their ids.
        with jax.named_scope("kge.score"):
            loss, p_ent, p_rel = loss_and_pushes(
                cfg, pulled[ENTITY_TABLE], pulled[RELATION_TABLE],
                batch["neg_side"].T, q)
        live = q > 0
        ent_ids = jnp.where(jnp.tile(live, 2 + N), self._entity_ids(batch), -1)
        rel_ids = jnp.where(live, batch["r"].astype(jnp.int32), -1)
        pushes = {ENTITY_TABLE: (ent_ids, p_ent),
                  RELATION_TABLE: (rel_ids, p_rel)}
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
