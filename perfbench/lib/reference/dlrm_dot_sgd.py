"""Plain reference: DLRM with the ``dot`` interaction under plain SGD.

Straightforward ``jax.numpy``; nothing imported from the program. Naumov et
al., arXiv:1906.00091, at the settings ``facebookresearch/dlrm``'s
``bench/dlrm_s_criteo_kaggle.sh`` documents. The model is ``F`` SEPARATE
embedding tables ``emb_00 .. emb_<F-1>`` (field ``f`` has
``data.categorical_cardinalities[f]`` rows of ``model.embed_dim``) and the
two MLPs' matrices ``bot_w<l>`` ``(inputs, outputs)``, ``bot_b<l>``,
``top_w<l>``, ``top_b<l>``, every one a named logical table. A step takes
the global batch (``workers`` equal worker batches) and, per example with
counts ``x`` (already ``log1p``), tokens ``i_1 .. i_F``, label ``y`` in
{0, 1}, weight ``q`` (0 = padding):

* ``h_0 = x``; ``h_l = relu(h_{l-1} W_l + b_l)`` over the bottom MLP, ReLU
  after EVERY layer; ``z0 = h_last``;
* ``e_f = emb_f[i_f]``;
* ``T = [z0; e_1; ...; e_F]``, ``Z = T T^t``, ``p`` = the entries of ``Z``
  strictly below the diagonal, row by row; ``r = [z0, p]``;
* the top MLP over ``r``, ReLU on its hidden layers, its one output the
  logit ``z``; ``yhat = sigmoid(z)``;
* ``L_w = sum_e q_e bce(yhat_e, y_e) / max(sum_e q_e, 1)`` over worker
  ``w``'s own batch, ``bce`` taken from the logit
  (``max(z, 0) - z y + log1p(exp(-|z|))``: no clip);
* once a step, ``lr = model.learning_rate``: every MLP parameter
  ``theta -= lr * sum_w dL_w/dtheta``; every touched row
  ``emb_f[i] -= lr * sum of dL_w/de_f over the examples, of every worker,
  that read it`` (``.at[].add``); an untouched row keeps its bits.

Gradients by ``jax.grad`` of ``sum_w L_w``. float32, every matrix product
at ``model.matmul_precision``: ``highest`` (``jax.lax.Precision.HIGHEST``).

``model.control`` (never in a configuration file; ``perfbench/
dlrm_controls.py`` sets it on a copy) turns the step into one of the
controls the limits must refuse: ``drop_dense`` never moves an MLP
parameter, ``mean_fold`` gives a touched row the MEAN of its pushes in
place of their sum. ``matmul_precision: "default"`` is the third (every
product one pass over operands rounded to bfloat16, summed in float32:
the chip's default for a float32 product), and ``dtype=bfloat16``
(``control.py``) the fourth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LOSS_KEY = "logloss"


def field_names(cfg: dict) -> list:
    return [f"emb_{f:02d}"
            for f in range(len(cfg["data"]["categorical_cardinalities"]))]


def layer_shapes(cfg: dict) -> dict:
    """Every MLP parameter's shape by name."""
    m = cfg["model"]
    F = len(cfg["data"]["categorical_cardinalities"])
    interact = m["embed_dim"] + (F + 1) * F // 2
    shapes = {}
    for stack, n_in, widths in (("bot", m["numeric"], m["bottom_mlp"]),
                                ("top", interact, m["top_mlp"])):
        for l, n_out in enumerate(widths):
            shapes[f"{stack}_w{l}"] = (n_in, n_out)
            shapes[f"{stack}_b{l}"] = (n_out,)
            n_in = n_out
    return shapes


def init_tables(seed: int, cfg: dict) -> dict:
    """From ``seed``: ``W_l`` normal with variance ``2 / (m + n)`` and
    ``b_l`` normal with variance ``1 / m`` for a layer of ``m`` outputs
    and ``n`` inputs; ``emb_f`` uniform in ``+-sqrt(1 / rows_f)``."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    D = cfg["model"]["embed_dim"]
    out = {}
    for f, (name, rows) in enumerate(zip(
            field_names(cfg), cfg["data"]["categorical_cardinalities"])):
        half = float(np.sqrt(1.0 / rows))
        out[name] = jax.random.uniform(
            jax.random.fold_in(key, f), (rows, D), jnp.float32,
            minval=-half, maxval=half)
    for i, (name, shape) in enumerate(sorted(layer_shapes(cfg).items())):
        std = float(np.sqrt(2.0 / sum(shape) if len(shape) == 2
                            else 1.0 / shape[0]))
        out[name] = std * jax.random.normal(
            jax.random.fold_in(key, 1000 + i), shape, jnp.float32)
    return out


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    m = cfg["model"]
    lr = m["learning_rate"]
    control = m.get("control")
    if m["matmul_precision"] not in ("highest", "default"):
        raise ValueError(f"matmul_precision {m['matmul_precision']!r}")
    one_pass = m["matmul_precision"] == "default"
    fields = field_names(cfg)
    layers = sorted(layer_shapes(cfg))
    n_bot, n_top = len(m["bottom_mlp"]), len(m["top_mlp"])
    li, lj = np.tril_indices(len(fields) + 1, k=-1)

    def product(spec, a, b):
        """``highest``: float32 products. ``default``: what the chip does
        with a float32 product it is not told more about, one pass over
        operands rounded to bfloat16, summed in float32 (written out, so
        that the control reads the same wherever it is replayed)."""
        if one_pass:
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32
                              ).astype(a.dtype)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)

    def mlp(dense, stack, depth, h, last_relu):
        for l in range(depth):
            h = product("bi,io->bo", h,
                        dense[f"{stack}_w{l}"]) + dense[f"{stack}_b{l}"]
            if l < depth - 1 or last_relu:
                h = jnp.maximum(h, 0)
        return h

    def step(tables, batch):
        x = batch["counts"].astype(dtype)
        y = batch["label"].astype(dtype)
        q = batch["weight"].astype(dtype)
        tokens = batch["tokens"]
        per_worker = q.reshape(workers, -1).astype(jnp.float32).sum(axis=1)
        n = jnp.repeat(jnp.maximum(per_worker, 1.0),
                       q.shape[0] // workers).astype(dtype)

        def loss_fn(dense, rows):
            z0 = mlp(dense, "bot", n_bot, x, True)
            T = jnp.stack([z0] + rows, axis=1)
            Z = product("bid,bjd->bij", T, T)
            r = jnp.concatenate([z0, Z[:, li, lj]], axis=1)
            z = mlp(dense, "top", n_top, r, False)[:, 0]
            bce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
            return jnp.sum(q * bce / n), jnp.sum((q * bce).astype(jnp.float32))

        dense = {k: tables[k] for k in layers}
        rows = [tables[name][tokens[:, f]] for f, name in enumerate(fields)]
        (g_dense, g_rows), total = jax.grad(
            loss_fn, argnums=(0, 1), has_aux=True)(dense, rows)
        new = {}
        for k in layers:
            new[k] = (tables[k] if control == "drop_dense"
                      else tables[k] - (lr * g_dense[k]).astype(dtype))
        live = q > 0
        for f, name in enumerate(fields):
            table = tables[name]
            ids = jnp.where(live, tokens[:, f], table.shape[0])
            g = g_rows[f]
            if control == "mean_fold":
                count = jnp.zeros((table.shape[0],), dtype).at[ids].add(
                    1, mode="drop")
                g = g / jnp.maximum(count, 1)[tokens[:, f]][:, None]
            new[name] = table.at[ids].add((-lr * g).astype(dtype),
                                          mode="drop")
        return new, {"loss": total, "n": jnp.sum(q.astype(jnp.float32))}

    return step
