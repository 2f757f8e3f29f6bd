"""Data kind ``token_stream``: one column of word ids, a corpus slice."""

from __future__ import annotations

import numpy as np

from perfbench.lib import datagen


def law_cdf(k, n: int, s: float):
    """P(id < k) of the smoothed Zipf(``s``) law over ``n`` frequency-ranked
    ids (``datagen.power_law_cdf``, with its limit at ``s`` = 1, where it
    reads ``log(k + 1) / log(n + 1)``). Float64 numpy."""
    k = np.asarray(k, np.float64)
    if s == 1.0:
        return np.log1p(k) / np.log1p(float(n))
    e = 1.0 - s
    return ((k + 1.0) ** e - 1.0) / ((n + 1.0) ** e - 1.0)


def unigram_counts(d: dict) -> np.ndarray:
    """The LAW's expected count of every word over the whole corpus
    (``corpus_tokens``), not the resident slice's own counts: what a
    deployment reads from its vocabulary file before it streams a slice."""
    n = d["vocab_size"]
    return d["corpus_tokens"] * np.diff(
        law_cdf(np.arange(n + 1), n, d["zipf_exponent"]))


def generate(seed: int, d: dict):
    """``token`` (int32), ``tokens_resident`` of them: independent draws
    from the law by its inverse CDF, ``id = floor((n+1)^u) - 1`` at
    exponent 1 (``datagen.power_law_ids`` otherwise)."""
    import jax
    import jax.numpy as jnp

    n, total, s = d["vocab_size"], d["tokens_resident"], d["zipf_exponent"]
    rows = -(-total // datagen.CHUNKS)

    def make(key, first_row):
        del first_row
        u = jax.random.uniform(key, (rows,))
        if s == 1.0:
            ids = jnp.floor(jnp.exp(u * float(np.log1p(float(n))))).astype(
                jnp.int32) - 1
            return {"token": jnp.clip(ids, 0, n - 1)}
        return {"token": datagen.power_law_ids(u, n, s)}

    return datagen.make_and_fetch(make, seed, total,
                                  {"token": ((), np.int32)})
