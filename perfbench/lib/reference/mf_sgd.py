"""Plain reference: online matrix factorization by SGD.

The model's step in straightforward ``jax.numpy`` — no ``Trainer``, no
store, no kernels, nothing imported from the program. One step takes the
GLOBAL batch (every worker's rows of that step, in any order) and does
what the configuration states:

* ``p = U[user]``, ``q = V[item]``, ``err = (rating - <p, q>) * weight``;
* user factors are worker-local state: every rating's ``lr * (err q -
  reg p)`` is ADDED to its user's row (duplicates sum);
* movie factors are the served parameters with ``combine = "mean"``: a
  movie touched by k ratings of the step moves by the MEAN of their
  ``lr * (err p - reg q)`` — one averaged step per touched row;
* rows of weight 0 are padding and touch nothing. ``workers`` is unused:
  the step is the same however the global batch was split.

``dtype`` is float32 for the reference proper; the control runs the same
function with bfloat16 tables and arithmetic (the nearest precision below
the configuration's float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_tables(seed: int, cfg: dict) -> dict:
    """Initial factors in LOGICAL id order, uniform in the configuration's
    range; float32. The benchmark hands the same arrays to the program and
    to this reference. They are drawn from the CONFIGURATION's
    ``init_salt``, not from ``seed``: how long SGD takes to leave the small
    start depends on how the random start happens to lie to the planted
    factors, a handful of numbers that do not average out, so a start
    drawn from the seed moves the examples to the quality target by several
    percent from seed to seed (configuration file, ``assumed``). Like the
    planted structure, the start is one fixed member of the population; the
    seed draws the ratings, the noise and the shuffles."""
    del seed
    m = cfg["model"]
    ku, kv = jax.random.split(jax.random.key(m["init_salt"] & 0xFFFFFFFF))
    lo, hi = m["init_min"], m["init_max"]
    return {
        "user_factors": jax.random.uniform(
            ku, (m["num_users"], m["rank"]), jnp.float32, lo, hi),
        "item_factors": jax.random.uniform(
            kv, (m["num_items"], m["rank"]), jnp.float32, lo, hi),
    }


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    m = cfg["model"]
    lr, reg = m["learning_rate"], m["reg"]

    def step(tables, batch):
        U, V = tables["user_factors"], tables["item_factors"]
        u, i = batch["user"], batch["item"]
        w = batch["weight"].astype(dtype)
        r = batch["rating"].astype(dtype)
        p, q = U[u], V[i]
        err = (r - jnp.sum(p * q, axis=-1)) * w
        dp = lr * (err[:, None] * q - reg * w[:, None] * p)
        dq = lr * (err[:, None] * p - reg * w[:, None] * q)
        live = w > 0
        U = U.at[u].add(jnp.where(live[:, None], dp, 0).astype(dtype))
        acc = jnp.zeros(V.shape, dtype).at[i].add(
            jnp.where(live[:, None], dq, 0).astype(dtype))
        cnt = jnp.zeros(V.shape[0], dtype).at[i].add(live.astype(dtype))
        V = V + acc / jnp.maximum(cnt, 1)[:, None]
        out = {"loss": jnp.sum(err.astype(jnp.float32) ** 2),
               "n": jnp.sum(w.astype(jnp.float32))}
        return {"user_factors": U, "item_factors": V}, out

    return step


LOSS_KEY = "se"  # the program's per-step metric this reference's loss mirrors
