"""Data kind ``criteo_fields``: rows of the Criteo Kaggle click log as a
model with embedding fields reads them: a label, the 13 numeric columns as
ONE dense column of ``log1p(count)`` (0 = missing) and the 26 categorical
columns as RAW tokens, one a field (the token's rank in its column, in
``[0, cardinality)``), unhashed. ``criteo_rows``' law otherwise: Zipf-like
tokens within a column, heavy-tailed counts, a planted click model."""

from __future__ import annotations

import numpy as np

from perfbench.datasets.criteo_rows import zipf_tokens
from perfbench.lib import datagen


def generate(seed: int, d: dict):
    """``tokens (N, 26)`` int32, ``counts (N, 13)`` float32, ``label (N,)``
    in {0, 1}.

    Count j: ``log1p(floor(exp(mu + sigma z)))`` for a standard normal
    ``z``, 0 with probability ``numeric_missing``. Token of field f: the
    continuous inverse CDF of Zipf(``token_zipf``) over the field's
    cardinality. Label: a click with probability ``sigmoid(bias + sum_j
    v*[j] x_j + sum_f w*[offset_f + token_f])`` for planted effects hashed
    under ``planted_salt`` (the same for every seed: ``v*`` uniform in
    ``+-planted_half_width_numeric``, ``w*`` in
    ``+-planted_half_width_categorical``), then flipped with probability
    ``label_flip``."""
    import jax
    import jax.numpy as jnp

    n = d["examples_resident"]
    D, cards = d["numeric_columns"], d["categorical_cardinalities"]
    C = len(cards)
    if C != d["categorical_columns"]:
        raise ValueError("categorical_cardinalities: one per column")
    rows = -(-n // datagen.CHUNKS)
    salt = int(d["planted_salt"]) & 0xFFFFFFFF
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(cards)[:-1]]),
                          jnp.int32)
    v_true = datagen.hash_uniform(jnp.arange(D, dtype=jnp.int32), 1, salt,
                                  d["planted_half_width_numeric"])

    def make(key, first_row):
        del first_row
        kz, km, kt, kl, kf = jax.random.split(key, 5)
        count = jnp.floor(jnp.exp(
            d["numeric_log_mu"] + d["numeric_log_sigma"]
            * jax.random.normal(kz, (rows, D))))
        present = jax.random.uniform(km, (rows, D)) >= d["numeric_missing"]
        counts = jnp.where(present, jnp.log1p(count), 0.0).astype(jnp.float32)
        tokens = zipf_tokens(jax.random.uniform(kt, (rows, C)), cards,
                             d["token_zipf"])
        w_true = datagen.hash_uniform(tokens + offsets[None, :], 0, salt,
                                      d["planted_half_width_categorical"])
        p = jax.nn.sigmoid(d["planted_bias"] + counts @ v_true
                           + jnp.sum(w_true, axis=1))
        click = jax.random.uniform(kl, (rows,)) < p
        flip = jax.random.uniform(kf, (rows,)) < d["label_flip"]
        return {"tokens": tokens, "counts": counts,
                "label": (click ^ flip).astype(jnp.float32)}

    return datagen.make_and_fetch(make, seed, n, {
        "tokens": ((C,), np.int32), "counts": ((D,), np.float32),
        "label": ((), np.float32)})
