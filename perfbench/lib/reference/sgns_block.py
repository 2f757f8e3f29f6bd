"""Plain reference: word2vec skip-gram with negative sampling, one step
per block of tokens, as the configuration states it.

Straightforward ``jax.numpy`` — no ``Trainer``, no store, no kernels,
nothing imported from the program. The program's DRAWS are data here: the
step is handed every worker's block of the epoch's kept stream, its
half-windows and its negatives, and does the arithmetic itself, with the
pairs enumerated one by one and every row gathered from and scattered
into the whole tables.

One step, per worker ``w`` (all workers' rows meet in the per-id mean):
block ``b[0..L+W)`` of word ids, half-windows ``h[0..L)`` in ``1..W``,
valid length ``v``, negatives ``N[p, 0..K)`` for every position ``p``.

* instances: for ``i < L``, ``1 <= d <= h_i``, ``i + d < v``, the pairs
  (centre, context) = ``(i, i+d)`` and ``(i+d, i)``; ``inst_p`` counts
  the instances whose centre is position ``p``;
* per instance ``l = <v_c, u_x>``, ``g = sigmoid(l) - 1``:
  ``dv_c += -lr g u_x``, ``du_x += -lr g v_c`` (``v``: rows of
  ``in_embeddings``, ``u``: rows of ``out_embeddings``, by the word at
  the position);
* per position and negative ``l = <v_p, u_N[p,k]>``,
  ``s = inst_p sigmoid(l)``: ``dv_p += -lr s u_N[p,k]``,
  ``dn_[p,k] = -lr s v_p``;
* ``dv_p``, ``du_p`` and ``dn_[p,k]`` are divided by ``max(inst_p, 1)``;
* pushed, for positions with ``inst_p > 0`` only: ``(b_p, dv_p)`` to
  ``in_embeddings``; ``(b_p, du_p)`` and ``(N[p,k], dn_[p,k])`` to
  ``out_embeddings``. Each table adds, to every id touched, the MEAN of
  the rows pushed to it in the step;
* ``loss`` = sum over instances of ``-log sigmoid(l)`` plus sum over
  ``(p, k)`` of ``-inst_p log sigmoid(-l)``; ``n`` = the instances.

Departures from the published SGNS (Mikolov et al., 2013), which are the
program's and stated in the configuration file: a position's instances
share one set of K negatives (each weighted by ``inst_p``); the
per-instance mean (the division by ``inst_p``) and the per-id mean in
place of one sequential update per pair; keep probability
``min(1, sqrt(t/f))`` without word2vec.c's ``+ t/f`` term; a constant
learning rate. The subsampling, the windows and the negatives themselves
are the program's draws and are not recomputed here.

``dtype`` is float32 for the reference proper; the control runs the same
function with bfloat16 tables and arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

IN, OUT = "in_embeddings", "out_embeddings"


def init_tables(seed: int, cfg: dict) -> dict:
    """Initial tables in LOGICAL id order, float32: ``in_embeddings``
    uniform in +-0.5/dim (word2vec's start), ``out_embeddings`` zeros.
    Drawn from the CONFIGURATION's ``init_salt``, the same for every seed
    (as ``mf_sgd.init_tables``); the seed draws the tokens, the
    subsampling, the windows and the negatives. HOST arrays (made on the
    device and fetched flat, which is a plain copy): the runner holds
    them through the window, where two more copies of two 1.3 GB tables on
    the device would crowd the program's own."""
    del seed
    m = cfg["model"]
    V, D = m["vocab_size"], m["dim"]
    half = 0.5 / D
    flat = jax.random.uniform(jax.random.key(m["init_salt"] & 0xFFFFFFFF),
                              (V * D,), jnp.float32, -half, half)
    return {IN: np.asarray(flat).reshape(V, D),
            OUT: np.zeros((V, D), np.float32)}


def _mean_push(table, ids, rows, live):
    """Add to every id the mean of the live rows pushed to it."""
    dtype = table.dtype
    acc = jnp.zeros(table.shape, dtype).at[ids].add(
        jnp.where(live[:, None], rows, 0).astype(dtype))
    cnt = jnp.zeros(table.shape[0], dtype).at[ids].add(live.astype(dtype))
    return table + acc / jnp.maximum(cnt, 1)[:, None]


def make_step(cfg: dict, dtype=jnp.float32, workers: int = 1):
    del workers  # the batch carries one block a worker
    lr = cfg["model"]["learning_rate"]

    def step(tables, batch):
        Vt, Ut = tables[IN], tables[OUT]
        block = batch["block"]                      # (Wk, L+W) word ids
        half, vlen = batch["half"], batch["valid_len"]   # (Wk, L), (Wk,)
        negs = batch["negatives"]                   # (Wk, L+W, K)
        Wk, LW = block.shape
        L = half.shape[1]
        Wd, K, D = LW - L, negs.shape[2], Vt.shape[1]

        # Every candidate pair, one by one: worker, position i, offset d,
        # orientation. Positions are numbered across workers (w * LW + p).
        w = jnp.arange(Wk)[:, None, None]
        i = jnp.arange(L)[None, :, None]
        d = jnp.arange(1, Wd + 1)[None, None, :]
        ok = (d <= half[:, :, None]) & (i + d < vlen[:, None, None])
        lo = jnp.broadcast_to(w * LW + i, ok.shape).reshape(-1)
        hi = jnp.broadcast_to(w * LW + i + d, ok.shape).reshape(-1)
        centre = jnp.concatenate([lo, hi])
        context = jnp.concatenate([hi, lo])
        live = jnp.concatenate([ok.reshape(-1)] * 2)
        word = block.reshape(-1)                    # word at a position
        P = Wk * LW

        lw = live.astype(dtype)
        inst = jnp.zeros(P, dtype).at[centre].add(lw)
        vc, ux = Vt[word[centre]], Ut[word[context]]
        l = jnp.sum(vc * ux, axis=-1)
        g = (jax.nn.sigmoid(l) - 1) * lw
        dv = jnp.zeros((P, D), dtype).at[centre].add(-lr * g[:, None] * ux)
        du = jnp.zeros((P, D), dtype).at[context].add(-lr * g[:, None] * vc)
        loss = jnp.sum(-jax.nn.log_sigmoid(l).astype(jnp.float32)
                       * live.astype(jnp.float32))

        vp = Vt[word]                               # (P, D)
        un = Ut[negs.reshape(P, K)]                 # (P, K, D)
        ln = jnp.sum(vp[:, None, :] * un, axis=-1)  # (P, K)
        s = inst[:, None] * jax.nn.sigmoid(ln)
        dv = dv + jnp.sum(-lr * s[:, :, None] * un, axis=1)
        dn = -lr * s[:, :, None] * vp[:, None, :]   # (P, K, D)
        loss = loss + jnp.sum(
            -jax.nn.log_sigmoid(-ln).astype(jnp.float32)
            * inst.astype(jnp.float32)[:, None])

        per = 1 / jnp.maximum(inst, 1)
        dv, du = dv * per[:, None], du * per[:, None]
        dn = dn * per[:, None, None]
        pushed = inst > 0
        Vt = _mean_push(Vt, word, dv, pushed)
        Ut = _mean_push(
            Ut, jnp.concatenate([word, negs.reshape(-1)]),
            jnp.concatenate([du, dn.reshape(P * K, D)]),
            jnp.concatenate([pushed, jnp.repeat(pushed, K)]))
        out = {"loss": loss,
               "n": jnp.sum(live.astype(jnp.float32))}
        return {IN: Vt, OUT: Ut}, out

    return step


LOSS_KEY = "loss"  # the program's per-step metric this loss mirrors
