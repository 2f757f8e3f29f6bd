"""Plain reference: binary passive-aggressive (PA-I), mini-batch form.

Straightforward ``jax.numpy``; nothing imported from the program. One
step takes the global batch and does what the configuration states
(Crammer et al. 2006, hinge loss ``l = max(0, 1 - y m)``):

* margins from the weights as they stood before the step;
* ``tau = min(C, l / |x|^2)``, averaged over the batch (each example's
  step scaled by 1 / number of live examples of ITS WORKER's batch;
  ``workers`` says how many equal worker batches the global batch holds);
* every non-zero slot adds ``tau y x`` to its feature's weight;
  duplicates sum. Rows of weight 0 are padding.
"""

from __future__ import annotations

import jax.numpy as jnp


def init_tables(seed: int, cfg: dict) -> dict:
    return {"weights": jnp.zeros((cfg["model"]["num_features"],),
                                 jnp.float32)}


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    C = cfg["model"]["C"]

    def step(tables, batch):
        wt = tables["weights"]
        ids = batch["feat_ids"]
        x = batch["feat_vals"].astype(dtype)
        y = batch["label"].astype(dtype)
        w = batch["weight"].astype(dtype)
        margin = jnp.sum(wt[ids] * x, axis=-1)
        loss = jnp.maximum(0, 1 - y * margin)
        x2 = jnp.maximum(jnp.sum(x * x, axis=-1), 1e-12)
        tau = jnp.minimum(C, loss / x2) * w
        per_worker = w.reshape(workers, -1).astype(jnp.float32).sum(axis=1)
        tau = tau / jnp.repeat(jnp.maximum(per_worker, 1.0),
                               w.shape[0] // workers).astype(dtype)
        delta = (tau * y)[:, None] * x
        live = (x != 0) & (w[:, None] > 0)
        wt = wt.at[ids.reshape(-1)].add(
            jnp.where(live, delta, 0).reshape(-1).astype(dtype))
        out = {"loss": jnp.sum((loss * w).astype(jnp.float32)),
               "n": jnp.sum(w.astype(jnp.float32))}
        return {"weights": wt}, out

    return step


LOSS_KEY = "loss"
