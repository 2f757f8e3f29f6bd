"""Data kind ``mf_ratings``: a stream of (user, movie, rating)."""

from __future__ import annotations

import numpy as np

from perfbench.lib import datagen


def generate(seed: int, d: dict):
    """``user``, ``item`` (int32) and ``rating`` (f32): users uniform
    within their route group, route groups of unequal stated shares
    (``route_group_shares``), power-law movies, rating = <p_u, q_i> +
    noise with hashed rank-``r``
    factors of variance 1/r (as ``utils.datasets.synthetic_ratings``)."""
    import jax
    import jax.numpy as jnp

    nu, ni, n = d["num_users"], d["num_items"], d["num_ratings"]
    rank, noise, alpha = d["planted_rank"], d["noise"], d["item_zipf"]
    rows = -(-n // datagen.CHUNKS)
    salt = int(d["planted_salt"]) & 0xFFFFFFFF
    half = float(np.sqrt(3.0 / rank))

    # A rating's route group (its user modulo the number of groups: a
    # worker's queue on a mesh of that many workers) is a function of its
    # POSITION in the stream, never of the seed: queue lengths are
    # constants of the compiled epoch program, and a fixed data set has one
    # set of them. Position i falls into a group by where i x golden ratio
    # mod 1 (Fibonacci hashing, uint32) lies among the cumulated shares, so
    # the groups arrive interleaved and hold UNEQUAL shares of the ratings.
    shares = [float(x) for x in d["route_group_shares"]]
    groups = len(shares)
    cuts = [min(int(c * 2.0 ** 32), 2 ** 32 - 1)
            for c in np.cumsum(shares)[:-1]]

    def make(key, first_row):
        ku, ki, kn = jax.random.split(key, 3)
        pos = (first_row + jnp.arange(rows, dtype=jnp.int32)).astype(
            jnp.uint32) * jnp.uint32(0x9E3779B9)
        g = sum((pos >= jnp.uint32(c)).astype(jnp.int32) for c in cuts)
        in_group = (nu - g + groups - 1) // groups
        q = jnp.floor(jax.random.uniform(ku, (rows,))
                      * in_group.astype(jnp.float32)).astype(jnp.int32)
        users = jnp.minimum(q, in_group - 1) * groups + g
        items = datagen.power_law_ids(jax.random.uniform(ki, (rows,)), ni,
                                      alpha)
        r = noise * jax.random.normal(kn, (rows,), jnp.float32)
        for k in range(rank):
            r = r + (datagen.hash_uniform(users, k, salt, half)
                     * datagen.hash_uniform(items, k + rank, salt, half))
        return {"user": users, "item": items, "rating": r}

    return datagen.make_and_fetch(make, seed, n, {
        "user": ((), np.int32), "item": ((), np.int32),
        "rating": ((), np.float32)})
