"""Plain reference: ComplEx (Trouillon et al., ICML 2016) trained with
negative sampling and AdaGrad, one step per global batch, as the
configuration states it.

Straightforward ``jax.numpy``: no ``Trainer``, no store, no kernels,
nothing imported from the program. The program's DRAWS are data here: a
batch carries, beside its positive triples ``s, r, o`` and their weights,
each positive's corruptions (``neg_entity (G, N)``, ``neg_side (G, N)``:
True where the SUBJECT is replaced). Every scored triple is written out
one by one, its three rows gathered from the whole tables, its gradient
scatter-added into dense, whole-table buffers, and the whole tables
updated.

Tables, float32, a row ``[re | im]`` of ``K`` complex components:
``entity [E, 2K]`` and ``relation [R, 2K]``, each with an AdaGrad
accumulator of its shape, every component ``initial_accumulator`` at the
start. The entity table and its
accumulator are HELD IN ``export_blocks`` RANGES OF IDS (``entity_00`` is
ids ``[0, E / blocks)``, ``entity_acc_00`` its accumulator), so that what
is compared, an array at a time in float64 on the host, stays small; a
step puts them end to end, computes on the whole, and cuts the result.

One step, over every worker's rows together (``G`` positives):

* scored triples of positive ``g``: ``(s, r, o)`` with ``y = +1``; for
  each ``j``, ``(n_j, r, o)`` where ``side_j`` else ``(s, r, n_j)``, with
  ``y = -1``;
* ``phi(s, r, o) = sum_k a_s a_r a_o + a_s b_r b_o + b_s a_r b_o
  - b_s b_r a_o`` (``e = a + i b``: the real part of ``e_s w_r
  conj(e_o)``);
* loss of a scored triple: ``softplus(-y phi) + l2 (|e_s|^2 + |w_r|^2 +
  |e_o|^2)``; the step's ``loss`` is the ``q``-weighted sum over all of
  them, ``n = sum q`` counts positives;
* ``g_i`` = the gradient of ``loss`` by row ``i``, summed over every
  scored triple that holds it; then for every row of either table
  ``G_i += g_i^2``, ``theta_i -= lr g_i / (sqrt(G_i) + eps)``: a row in
  no scored triple has ``g_i = 0`` and keeps ``theta_i`` and ``G_i``.

``cfg.model.control = "drop_state"`` is a control: the accumulator is
never written and ``theta_i -= lr g_i`` (plain SGD). ``dtype`` is float32
for the reference proper; the bfloat16 control runs the same function with
bfloat16 tables and arithmetic. ``cfg.model.control = "reversed"`` is no
control but the configuration's float32 FLOOR: the same step on each
batch's positives in the opposite order, so every sum over them meets its
addends in another order and nothing else differs; what it reads against
the reference proper, a sound program may read too
(``perfbench/kge_controls.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ENTITY, RELATION = "entity", "relation"


def block_names(cfg: dict) -> tuple[list, list]:
    """The entity table's blocks and their accumulators', in id order."""
    n = int(cfg["model"]["export_blocks"])
    return ([f"{ENTITY}_{b:02d}" for b in range(n)],
            [f"{ENTITY}_acc_{b:02d}" for b in range(n)])


def init_tables(seed: int, cfg: dict) -> dict:
    """Initial tables in LOGICAL id order, float32: every component normal
    with standard deviation ``init_std``, every accumulator's components
    ``initial_accumulator``. Drawn
    from the CONFIGURATION's ``init_salt``, the same for every seed (as
    ``mf_sgd.init_tables``); the seed draws the triples and the
    corruptions. HOST arrays (made on the device a block at a time and
    fetched flat, which is a plain copy): the runner holds them through
    the window, where a third copy of 3.2 GB of rows and state on the
    device would crowd the program's own."""
    del seed
    m = cfg["model"]
    E, R, D = m["entities"], m["relations"], 2 * m["rank"]
    ents, accs = block_names(cfg)
    rows = E // len(ents)
    if rows * len(ents) != E:
        raise ValueError("export_blocks does not divide entities")
    key = jax.random.key(m["init_salt"] & 0xFFFFFFFF)

    def normal(k, n):
        flat = m["init_std"] * jax.random.normal(k, (n * D,), jnp.float32)
        return np.asarray(flat).reshape(n, D)

    start = np.float32(m["initial_accumulator"])
    out = {RELATION: normal(jax.random.fold_in(key, len(ents)), R),
           RELATION + "_acc": np.full((R, D), start, np.float32)}
    for b, (ent, acc) in enumerate(zip(ents, accs)):
        out[ent] = normal(jax.random.fold_in(key, b), rows)
        out[acc] = np.full((rows, D), start, np.float32)
    return out


def score(es, wr, eo):
    K = es.shape[-1] // 2
    a_s, b_s = es[..., :K], es[..., K:]
    a_r, b_r = wr[..., :K], wr[..., K:]
    a_o, b_o = eo[..., :K], eo[..., K:]
    return jnp.sum(a_s * a_r * a_o + a_s * b_r * b_o + b_s * a_r * b_o
                   - b_s * b_r * a_o, axis=-1)


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    del workers  # every worker's rows meet in one sum
    m = cfg["model"]
    lr, eps, l2 = m["learning_rate"], m["eps"], m["l2"]
    sgd = m.get("control") == "drop_state"
    reverse = m.get("control") == "reversed"
    ents, accs = block_names(cfg)

    def adagrad(theta, acc, g):
        if sgd:
            return theta - lr * g, acc
        acc = acc + g * g
        return theta - (lr * g) / (jnp.sqrt(acc) + eps), acc

    def step(tables, batch):
        if reverse:
            batch = {k: v[::-1] for k, v in batch.items()}
        entity = jnp.concatenate([tables[n] for n in ents])
        relation = tables[RELATION]
        s, r, o = batch["s"], batch["r"], batch["o"]
        neg, side = batch["neg_entity"], batch["neg_side"]
        q = batch["weight"].astype(dtype)
        # Every scored triple of every positive: (G, 1 + N) ids and signs.
        subj = jnp.concatenate(
            [s[:, None], jnp.where(side, neg, s[:, None])], axis=1)
        obj = jnp.concatenate(
            [o[:, None], jnp.where(side, o[:, None], neg)], axis=1)
        rel = jnp.broadcast_to(r[:, None], subj.shape)
        y = jnp.concatenate([jnp.ones((1,), dtype),
                             -jnp.ones((neg.shape[1],), dtype)])[None, :]

        def loss_of(es, wr, eo):
            sq = lambda x: jnp.sum(x * x, axis=-1)  # noqa: E731
            per = jax.nn.softplus(-y * score(es, wr, eo)) + l2 * (
                sq(es) + sq(wr) + sq(eo))
            return jnp.sum((q[:, None] * per).astype(jnp.float32))

        loss, (g_s, g_r, g_o) = jax.value_and_grad(
            loss_of, argnums=(0, 1, 2))(entity[subj], relation[rel],
                                        entity[obj])
        D = entity.shape[1]
        g_entity = jnp.zeros(entity.shape, dtype).at[subj.reshape(-1)].add(
            g_s.reshape(-1, D)).at[obj.reshape(-1)].add(g_o.reshape(-1, D))
        g_relation = jnp.zeros(relation.shape, dtype).at[
            rel.reshape(-1)].add(g_r.reshape(-1, D))

        new = {}
        new[RELATION], new[RELATION + "_acc"] = adagrad(
            relation, tables[RELATION + "_acc"], g_relation)
        rows = entity.shape[0] // len(ents)
        for b, (ent, acc) in enumerate(zip(ents, accs)):
            new[ent], new[acc] = adagrad(
                tables[ent], tables[acc], g_entity[b * rows:(b + 1) * rows])
        return new, {"loss": loss, "n": jnp.sum(q.astype(jnp.float32))}

    return step


LOSS_KEY = "loss"  # the program's per-step metric this loss mirrors
