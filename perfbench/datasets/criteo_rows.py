"""Data kind ``criteo_rows``: rows of a click log in the layout
``fps_tpu.utils.datasets.load_criteo`` gives a Criteo TSV: a label, 13
numeric columns at FIXED slots (column j at slot j with feature id j and
value ``log1p(count)``; value 0 = missing, inactive) and 26 categorical
columns whose tokens are hashed into the rest of a fixed feature space
(``id = 13 + hash(column, token) % (F - 13)``, value 1)."""

from __future__ import annotations

import numpy as np

from perfbench.lib import datagen


def hash_tokens(tokens, num_features: int, numeric: int):
    """Feature ids of ``tokens (..., C)`` (the token's rank in its column,
    int32), column ``c`` of the last axis being categorical column ``c``:
    a 32-bit mix of (column, token), the same for every seed, folded into
    ``[numeric, num_features)``. Two tokens that collide share a feature,
    as they would under any hashing of 33.8 M distinct tokens into a
    million."""
    import jax.numpy as jnp

    col = jnp.arange(tokens.shape[-1], dtype=jnp.uint32)
    x = (tokens.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + (col + jnp.uint32(1)) * jnp.uint32(0x7F4A7C15))
    h = datagen.fmix32(x) % jnp.uint32(num_features - numeric)
    return h.astype(jnp.int32) + numeric


def zipf_tokens(u, cardinalities, alpha: float):
    """Token ranks in ``[0, cardinality)`` from uniforms ``u (..., C)``:
    the continuous inverse CDF of Zipf(``alpha``) (``datagen.
    power_law_ids``), with a cardinality per column."""
    import jax.numpy as jnp

    n = jnp.asarray(cardinalities, jnp.float32)
    e = 1.0 - alpha
    top = jnp.power(n + 1.0, e) - 1.0
    ids = jnp.floor(jnp.power(top * u + 1.0, 1.0 / e)) - 1.0
    return jnp.clip(ids, 0.0, n - 1.0).astype(jnp.int32)


def generate(seed: int, d: dict):
    """``feat_ids`` / ``feat_vals`` ``(N, 39)``, ``label (N,)`` in {0, 1}.

    Numeric slot j: id j; value ``log1p(floor(exp(mu + sigma z)))`` for a
    standard normal ``z`` (a heavy-tailed count), 0 with probability
    ``numeric_missing``. Categorical slot: a Zipf-like token of its
    column, hashed. Label: a click with probability ``sigmoid(bias +
    sum_k w*[id_k] x_k)`` for planted weights ``w*`` hashed from the
    feature id under ``planted_salt`` (the same for every seed), then
    flipped with probability ``label_flip``."""
    import jax
    import jax.numpy as jnp

    F, n = d["num_features"], d["examples_resident"]
    D, cards = d["numeric_columns"], d["categorical_cardinalities"]
    C = len(cards)
    if C != d["categorical_columns"]:
        raise ValueError("categorical_cardinalities: one per column")
    rows = -(-n // datagen.CHUNKS)
    salt = int(d["planted_salt"]) & 0xFFFFFFFF
    num_ids = jnp.arange(D, dtype=jnp.int32)
    half = jnp.concatenate([
        jnp.full((D,), d["planted_half_width_numeric"], jnp.float32),
        jnp.full((C,), d["planted_half_width_categorical"], jnp.float32)])

    def make(key, first_row):
        del first_row
        kz, km, kt, kl, kf = jax.random.split(key, 5)
        count = jnp.floor(jnp.exp(
            d["numeric_log_mu"] + d["numeric_log_sigma"]
            * jax.random.normal(kz, (rows, D))))
        present = jax.random.uniform(km, (rows, D)) >= d["numeric_missing"]
        num_vals = jnp.where(present, jnp.log1p(count), 0.0)
        tokens = zipf_tokens(jax.random.uniform(kt, (rows, C)), cards,
                             d["token_zipf"])
        ids = jnp.concatenate(
            [jnp.broadcast_to(num_ids, (rows, D)),
             hash_tokens(tokens, F, D)], axis=1)
        vals = jnp.concatenate(
            [num_vals, jnp.ones((rows, C), jnp.float32)],
            axis=1).astype(jnp.float32)
        w_true = datagen.hash_uniform(ids, 0, salt, 1.0) * half
        p = jax.nn.sigmoid(d["planted_bias"] + jnp.sum(w_true * vals, axis=1))
        click = jax.random.uniform(kl, (rows,)) < p
        flip = jax.random.uniform(kf, (rows,)) < d["label_flip"]
        return {"feat_ids": ids, "feat_vals": vals,
                "label": (click ^ flip).astype(jnp.float32)}

    return datagen.make_and_fetch(make, seed, n, {
        "feat_ids": ((D + C,), np.int32), "feat_vals": ((D + C,), np.float32),
        "label": ((), np.float32)})
