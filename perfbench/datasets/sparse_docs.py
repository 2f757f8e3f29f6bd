"""Data kind ``sparse_docs``: documents of a fixed number of sparse
features and a binary label."""

from __future__ import annotations

import numpy as np

from perfbench.lib import datagen


def generate(seed: int, d: dict):
    """``feat_ids``/``feat_vals`` ``(N, nnz)`` and ``label`` in {-1, +1}:
    power-law feature ids, N(0,1) values, label = sign of a planted linear
    margin, flipped with probability ``label_noise``.

    Rows come out already in ``head_sort_slots`` form: a row's slots are
    exchangeable, so drawing the number ``h`` of head features (ids below
    ``head_features``) first, then ``h`` ids from the head and the rest
    from the tail, gives the same rows as drawing all slots and stably
    partitioning them — without a sort. ``h`` is floored at
    ``head_prefix_cols`` so that the guaranteed prefix is one fixed number
    for every seed (the floor binds for about one row in a million)."""
    import jax
    import jax.numpy as jnp

    nf, n, nnz = d["num_features"], d["num_docs"], d["nnz"]
    alpha, flip_p = d["feature_zipf"], d["label_noise"]
    H, q = d["head_features"], d["head_prefix_cols"]
    c = datagen.power_law_cdf(H, nf, alpha) if H else 0.0
    rows = -(-n // datagen.CHUNKS)
    salt = int(d["planted_salt"]) & 0xFFFFFFFF

    def make(key, first_row):
        kh, ki, kv, kf = jax.random.split(key, 4)
        u = jax.random.uniform(ki, (rows, nnz))
        if H:
            h = jnp.sum(jax.random.uniform(kh, (rows, nnz)) < c, axis=1)
            h = jnp.maximum(h, q)
            head = jnp.arange(nnz)[None, :] < h[:, None]
            u = jnp.where(head, u * c, c + u * (1.0 - c))
        ids = datagen.power_law_ids(u, nf, alpha)
        if H:
            # f32 rounding at the seam must not push a head slot's id out.
            ids = jnp.where(head, jnp.minimum(ids, H - 1),
                            jnp.maximum(ids, H))
        vals = jax.random.normal(kv, (rows, nnz), jnp.float32)
        w_true = datagen.hash_uniform(ids, 0, salt, float(np.sqrt(3.0)))
        margin = jnp.sum(w_true * vals, axis=1)
        flip = jax.random.uniform(kf, (rows,)) < flip_p
        label = jnp.where((margin > 0) ^ flip, 1.0, -1.0)
        return {"feat_ids": ids, "feat_vals": vals,
                "label": label.astype(jnp.float32)}

    return datagen.make_and_fetch(make, seed, n, {
        "feat_ids": ((nnz,), np.int32), "feat_vals": ((nnz,), np.float32),
        "label": ((), np.float32)})
