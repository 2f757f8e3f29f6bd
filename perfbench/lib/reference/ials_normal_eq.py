"""Plain reference: implicit-feedback ALS by its normal equations (Hu,
Koren and Volinsky, ICDM 2008).

Straightforward ``jax.numpy`` — no store, no shard_map, nothing imported
from the program. An observed interaction ``(u, i, r)`` has preference 1
and confidence ``c = 1 + alpha r``; every other pair preference 0 and
confidence 1. A SWEEP fixes one side ``Y`` and solves, for every id ``s``
of the other side,

    (Y^T Y + sum_f alpha r_sf y_f y_f^T + reg I) x_s = sum_f (1 + alpha r_sf) y_f

over the interactions ``(s, f, r)`` of that id: the user sweep solves the
users against the movies, the item sweep the movies against the users as
the user sweep left them. An id with no interaction is solved against the
Gramian and the regulariser alone and comes out zero.

The harness replays a call as a scan of stateless steps over the batches
the call consumed, so the sweep's sums are CARRIED as two tables,
``normal_lhs`` (``k x k`` a row) and ``normal_rhs``, long enough for the
longer side and shared by both sweeps. One step adds its batch's outer
products and right-hand rows; the sweep's LAST step (``batch["last"]``;
which side it solves is ``batch["solve_item"]``, both put beside the
columns by the adapter's ``fed_chunks``) then forms the Gramian, solves,
writes the solved table and zeroes the sums: after a whole sweep they are
zero again, which is what ``System.export`` answers for them. The solve is
``jnp.linalg.solve`` (LU), a block of ids at a time so that the batch
fits the chip beside the sums. Per step: ``n``, the live interactions,
and ``loss``, ``sum c (1 - x_s . y_f)^2`` under both tables as the sweep
FOUND them (the solved side moves only at the sweep's end).

``dtype`` is float32 for the reference proper. The control runs the same
function with bfloat16 tables, sums, Gramian and arithmetic; the
factorisation alone runs in float32 there (LU has no bfloat16 kernel), on
a left-hand side and for a result that are the control's bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

USERS, ITEMS = "user_factors", "item_factors"
LHS, RHS = "normal_lhs", "normal_rhs"
SOLVE_BLOCK = 16_384  # ids solved at a time


def init_tables(seed: int, cfg: dict) -> dict:
    """Initial factors in LOGICAL id order, uniform in ``+-init_scale``
    from the CONFIGURATION's ``init_salt`` (the same start for every seed;
    the seed draws the ratings and the shuffles), and the sums at zero (on
    the host: the program never sees them)."""
    del seed
    m = cfg["model"]
    k, s = m["rank"], m["init_scale"]
    ku, kv = jax.random.split(jax.random.key(m["init_salt"] & 0xFFFFFFFF))
    rows = max(m["num_users"], m["num_items"])
    return {
        USERS: jax.random.uniform(ku, (m["num_users"], k), jnp.float32,
                                  -s, s),
        ITEMS: jax.random.uniform(kv, (m["num_items"], k), jnp.float32,
                                  -s, s),
        LHS: np.zeros((rows, k * k), np.float32),
        RHS: np.zeros((rows, k), np.float32),
    }


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    m = cfg["model"]
    k, alpha, reg = m["rank"], m["alpha"], m["reg"]

    def solve_all(Y, A, b, n):
        """The ``n`` systems of a sweep, ``SOLVE_BLOCK`` ids at a time (the
        last block starts early and solves some ids twice, alike)."""
        gram = (Y.T @ Y).astype(dtype)
        ridge = (reg * jnp.eye(k)).astype(dtype)
        blk = min(SOLVE_BLOCK, n)

        def block(j, X):
            lo = jnp.minimum(j * blk, n - blk)
            lhs = (gram[None] + ridge[None]
                   + lax.dynamic_slice(A, (lo, 0), (blk, k * k))
                   .reshape(blk, k, k))
            rhs = lax.dynamic_slice(b, (lo, 0), (blk, k))
            x = jnp.linalg.solve(lhs.astype(jnp.float32),
                                 rhs.astype(jnp.float32)[:, :, None])
            return lax.dynamic_update_slice(X, x[:, :, 0].astype(dtype),
                                            (lo, 0))

        return lax.fori_loop(0, -(-n // blk), block,
                             jnp.zeros((n, k), dtype))

    def sweep_step(tables, batch, solved, fixed, solve_col, fixed_col):
        X, Y, A, b = tables[solved], tables[fixed], tables[LHS], tables[RHS]
        s, f = batch[solve_col], batch[fixed_col]
        w = batch["weight"].astype(dtype)
        r = batch["rating"].astype(dtype)
        x, y = X[s], Y[f]
        c = 1.0 + alpha * r
        where = jnp.where(w > 0, s, A.shape[0])  # padding rows: dropped
        outer = (alpha * r * w)[:, None, None] * y[:, :, None] * y[:, None, :]
        A = A.at[where].add(outer.reshape(-1, k * k).astype(dtype),
                            mode="drop")
        b = b.at[where].add(((c * w)[:, None] * y).astype(dtype),
                            mode="drop")
        miss = 1.0 - jnp.sum(x * y, axis=-1)
        out = {"loss": jnp.sum((w * c * miss * miss).astype(jnp.float32)),
               "n": jnp.sum(w.astype(jnp.float32))}
        X, A, b = lax.cond(
            batch["last"] > 0,
            lambda: (solve_all(Y, A, b, X.shape[0]), jnp.zeros_like(A),
                     jnp.zeros_like(b)),
            lambda: (X, A, b))
        return {solved: X, fixed: Y, LHS: A, RHS: b}, out

    def step(tables, batch):
        return lax.cond(
            batch["solve_item"] > 0,
            lambda: sweep_step(tables, batch, ITEMS, USERS, "item", "user"),
            lambda: sweep_step(tables, batch, USERS, ITEMS, "user", "item"))

    return step
