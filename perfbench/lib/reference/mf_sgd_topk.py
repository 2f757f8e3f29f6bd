"""Plain reference: online matrix factorization that answers a top-K list
per rating event, then trains on it (the upstream's
``PSOnlineMatrixFactorizationAndTopKGenerator``).

Straightforward ``jax.numpy``; nothing imported from the program. One step
takes the GLOBAL batch (``workers`` equal worker batches, worker-major)
and does what the configuration states:

* the QUERIES are the first ``q = model.queries_per_step`` rows of
  every worker's batch; a row of weight 0 is padding and asks nothing;
* for every query the user's row times the WHOLE movie table (one matrix
  product, float32 at the highest precision), a full sort by score, the
  first ``K = model.topk``: the list, best first;
* THEN ``mf_sgd``'s step on the whole batch (``mf_sgd.make_step``: the
  training is ``mf-netflix``'s to the digit).

So the list for an event of step ``t`` is ranked by the tables as step
``t - 1`` left them: **prequential**, "rank, then learn". With
``model.topk_rank = "after_update"`` the lists are ranked by the tables
the step has just written instead, by a model that has seen the event:
the leak ``perfbench/prequential.py`` replays, which must fail a limit.

**Where the lists live.** ``check.run_reference`` scans a stateless
``step(tables, batch)`` and ``check.compare`` walks every table returned,
so a call's lists ride as tables beside the factors, one row a step (the
step's place in the call comes in as data, ``batch["step"]``):

* ``topk_scores`` ``(steps, Q, K)``, ``Q = workers x q``: the K best
  scores of each query, best first; zeros for a padding query;
* ``topk_id_scores`` ``(steps, Q, K)``: THIS reference's score, by its own
  pre-update tables, of the ids the PROGRAM answered at each rank
  (``batch["topk_ids"]``: the program's lists come in as data, for the
  reference to score; no code of the program does). The program's side
  of the comparison is its own score at that rank: an id translated
  wrongly (a shard offset, ``phys_to_id``, another query's list) scores
  something else by the reference's tables and fails, while two
  neighbours one rounding apart may swap and pass;
* ``topk_query`` ``(steps, Q)``: the user each list is for, -1 for
  padding (ids are below 2**24, exact in float32);
* ``topk_counts`` ``(steps, 3)``: live queries of the step; distinct ids
  inside ``[0, num_items)`` over their lists (``live x K``: no id twice
  in a list, none out of range); entries a padding query answered with
  anything but the sentinel (0).

The list tables hold ``model.topk_steps_per_call`` steps of ONE worker's
queries (the configuration maps to one chip and states how many steps its
plan makes of an epoch; the adapter refuses a plan that disagrees); a
step past them writes nothing.

``dtype`` is float32 for the reference proper; the control runs the same
function with bfloat16 tables and arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.lib.reference import mf_sgd

LOSS_KEY = mf_sgd.LOSS_KEY
LISTS = ("topk_scores", "topk_id_scores", "topk_query", "topk_counts")


def init_tables(seed: int, cfg: dict) -> dict:
    """``mf_sgd``'s initial factors and the call's lists, empty."""
    m = cfg["model"]
    T, q, K = (m["topk_steps_per_call"], m["queries_per_step"],
               m["topk"])
    return dict(
        mf_sgd.init_tables(seed, cfg),
        topk_scores=jnp.zeros((T, q, K), jnp.float32),
        topk_id_scores=jnp.zeros((T, q, K), jnp.float32),
        topk_query=jnp.zeros((T, q), jnp.float32),
        topk_counts=jnp.zeros((T, 3), jnp.float32))


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    m = cfg["model"]
    q, K = int(m["queries_per_step"]), int(m["topk"])
    after = {"before_update": False, "after_update": True}[m["topk_rank"]]
    sgd = mf_sgd.make_step(cfg, dtype=dtype, workers=workers)

    def rank(U, V, batch):
        def head(x):
            return x.reshape(workers, -1)[:, :q].reshape(-1)

        users = head(batch["user"])
        live = head(batch["weight"]) > 0
        p = U[users]
        scores = jnp.matmul(p, V.T, precision=jax.lax.Precision.HIGHEST)
        order = jnp.argsort(-scores, axis=-1)[:, :K]
        best = jnp.take_along_axis(scores, order, axis=-1)
        theirs = jnp.clip(batch["topk_ids"], 0, V.shape[0] - 1)
        of_theirs = jnp.sum(p[:, None, :] * V[theirs], axis=-1)
        n_live = jnp.sum(live, dtype=jnp.float32)
        return {
            "topk_scores": jnp.where(live[:, None], best, 0),
            "topk_id_scores": jnp.where(live[:, None], of_theirs, 0),
            "topk_query": jnp.where(live, users, -1),
            "topk_counts": jnp.stack([n_live, n_live * K, 0.0 * n_live]),
        }

    def step(tables, batch):
        factors = {k: tables[k] for k in ("user_factors", "item_factors")}
        new, out = sgd(factors, batch)
        by = new if after else factors
        lists = rank(by["user_factors"], by["item_factors"], batch)
        for name in LISTS:
            new[name] = tables[name].at[batch["step"]].set(
                lists[name].astype(dtype), mode="drop")
        return new, out

    return step
