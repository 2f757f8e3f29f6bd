"""Plain reference: sparse logistic regression under bounded staleness,
AdaGrad held in the server's fold.

Straightforward ``jax.numpy``; nothing imported from the program. The
table holds rows ``[w_i, a_i]`` (weight, accumulator). A call is a whole
number of rounds of ``s`` steps (``model.sync_every``). At the head of
round ``r`` the snapshot ``S_r`` is the table as round ``r - 1`` left it;
step ``t`` of the round takes the global batch (``workers`` equal worker
batches) and does what the configuration states:

* READS come from the snapshot: ``z_e = sum_k S_r[i_ek].w x_ek``,
  ``p_e = sigmoid(z_e)``, ``g_e = (p_e - y_e) q_e`` for label ``y_e`` in
  {0, 1} and row weight ``q_e`` (0 = padding);
* ``grad_ek = (g_e x_ek + l2 S_r[i_ek].w q_e) / n`` with ``n =
  max(sum_e q_e, 1)`` over the row's OWN WORKER's batch;
* WRITES land in the live table, once per id touched in the step (a slot
  with ``x_ek != 0`` and ``q_e > 0`` touches its id): with ``G_i``,
  ``Q_i`` the sums of ``grad`` and ``grad^2`` over the slots of id ``i``,
  ``a_i += Q_i`` then ``w_i -= lr G_i / (sqrt(a_i) + eps)``; an untouched
  row is kept as it is;
* ``logloss = sum_e q_e (-(y log(p + 1e-7) + (1 - y) log(1 - p + 1e-7)))``.

**Where the snapshot lives.** ``check.run_reference`` scans a stateless
``step(tables, batch)`` and ``check.compare`` walks every table returned,
so the snapshot is a table beside ``weights``, refreshed AFTER the last
step of a round (``(step + 1) % s == 0``, the step's place in the call
coming in as data, ``batch["step"]``). After a whole number of rounds it
equals ``weights``: what the program's next round would gather.

Departures from a textbook SSP / AdaGrad job (the program's, restated in
the configuration file): the bound is met by a per-round snapshot, so
reads are 0 to ``s - 1`` steps stale in a fixed cycle and never
free-running; gradients are averaged over a worker's batch; ``Q_i`` is a
sum of squares, not the square of the sum; the logarithm is clipped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LOSS_KEY = "logloss"


def init_tables(seed: int, cfg: dict) -> dict:
    """Zeros, whatever the seed: weight 0, accumulator 0."""
    del seed
    m = cfg["model"]
    zeros = jnp.zeros((m["num_features"], m["table_width"]), jnp.float32)
    return {"weights": zeros, "snapshot": zeros}


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    m = cfg["model"]
    lr, l2, eps = m["learning_rate"], m["l2"], m["adagrad_eps"]
    s = int(m["sync_every"])

    def step(tables, batch):
        table, snap = tables["weights"], tables["snapshot"]
        F = table.shape[0]
        ids = batch["feat_ids"]
        x = batch["feat_vals"].astype(dtype)
        y = batch["label"].astype(dtype)
        q = batch["weight"].astype(dtype)
        sw = snap[:, 0][ids]
        p = jax.nn.sigmoid(jnp.sum(sw * x, axis=-1))
        g = (p - y) * q
        per_worker = q.reshape(workers, -1).astype(jnp.float32).sum(axis=1)
        n = jnp.repeat(jnp.maximum(per_worker, 1.0),
                       q.shape[0] // workers).astype(dtype)
        grad = (g[:, None] * x + l2 * sw * q[:, None]) / n[:, None]
        live = (x != 0) & (q[:, None] > 0)
        flat = ids.reshape(-1)
        grad = jnp.where(live, grad, 0).reshape(-1).astype(dtype)
        G = jnp.zeros((F,), dtype).at[flat].add(grad)
        Q = jnp.zeros((F,), dtype).at[flat].add(grad * grad)
        touched = jnp.zeros((F,), bool).at[flat].max(live.reshape(-1))
        a = table[:, 1] + Q
        w = table[:, 0] - lr * G / (jnp.sqrt(a) + eps)
        table = jnp.where(touched[:, None],
                          jnp.stack([w, a], axis=-1).astype(dtype), table)
        snap = jnp.where((batch["step"] + 1) % s == 0, table, snap)
        ll = -(y * jnp.log(p + 1e-7) + (1 - y) * jnp.log(1 - p + 1e-7))
        out = {"loss": jnp.sum((ll * q).astype(jnp.float32)),
               "n": jnp.sum(q.astype(jnp.float32))}
        return {"weights": table, "snapshot": snap}, out

    return step
