"""Data kind ``implicit_ratings``: a log of (user, movie, rating) whose
every rating is an interaction, at a star-rating set's shape: both sides
skewed, every user present, ratings on the half-star grid."""

from __future__ import annotations

import math

import numpy as np

from perfbench.lib import datagen


def shifted_power_law_ids(u, n: int, alpha: float, shift: float):
    """Ranks in ``[0, n)`` by the continuous inverse CDF of a shifted power
    law (Zipf-Mandelbrot): ``P(rank < k) = ((k + s)^e - s^e) / ((n + s)^e
    - s^e)``, ``e = 1 - alpha``. The shift flattens the head, so the
    busiest id can be set apart from the tail's weight (``alpha`` may pass
    1, where ``datagen.power_law_ids`` has no inverse)."""
    import jax.numpy as jnp

    e = 1.0 - alpha
    lo, hi = float(shift ** e), float((n + shift) ** e)
    ids = jnp.floor(jnp.power((hi - lo) * u + lo, 1.0 / e) - shift)
    return jnp.clip(ids.astype(jnp.int32), 0, n - 1)


def spread(n: int) -> int:
    """A multiplier coprime to ``n`` with ``rank * m`` inside int32: rank
    ``k`` becomes id ``k * m % n``, a bijection that scatters the busy
    ranks over the id space (ids of a rating set are not ranked by use)."""
    m = min(7919, (2 ** 31 - 1) // max(n, 1))
    while math.gcd(m, n) != 1:
        m -= 1
    return max(m, 1)


def generate(seed: int, d: dict):
    """``user``, ``item`` (int32) and ``rating`` (f32, 0.5 to 5.0 in half
    stars). Position ``i < min_per_user * num_users`` of the log belongs
    to user ``i mod num_users`` (every user present, none under the
    floor); every other rating's user, and every rating's movie, is drawn
    from a shifted power law over ranks spread over the ids. The rating is
    a planted low-rank preference (hashed from the ids under the
    CONFIGURATION's salt, the same for every seed) plus noise, rounded to
    the grid: the normal equations are of structured systems, not noise."""
    import jax
    import jax.numpy as jnp

    nu, ni, n = d["num_users"], d["num_items"], d["ratings_resident"]
    floor_rows = d["min_per_user"] * nu
    if n < floor_rows:
        raise ValueError(f"{n} ratings cannot give {nu} users "
                         f"{d['min_per_user']} each")
    rank, salt = d["planted_rank"], int(d["planted_salt"]) & 0xFFFFFFFF
    half = float(np.sqrt(3.0 / rank))
    rows = -(-n // datagen.CHUNKS)
    mu, mi = spread(nu), spread(ni)

    def make(key, first_row):
        ku, ki, kn = jax.random.split(key, 3)
        pos = first_row + jnp.arange(rows, dtype=jnp.int32)
        drawn = shifted_power_law_ids(jax.random.uniform(ku, (rows,)), nu,
                                      d["user_zipf"], d["user_shift"])
        users = jnp.where(pos < floor_rows, pos % nu, drawn * mu % nu)
        items = shifted_power_law_ids(jax.random.uniform(ki, (rows,)), ni,
                                      d["item_zipf"], d["item_shift"])
        items = items * mi % ni
        taste = jnp.zeros((rows,), jnp.float32)
        for k in range(rank):
            taste = taste + (datagen.hash_uniform(users, k, salt, half)
                             * datagen.hash_uniform(items, k + rank, salt,
                                                    half))
        stars = (d["rating_mean"] + d["taste_scale"] * np.sqrt(rank) * taste
                 + d["noise"] * jax.random.normal(kn, (rows,), jnp.float32))
        rating = jnp.clip(jnp.round(stars * 2.0) * 0.5, 0.5, 5.0)
        return {"user": users.astype(jnp.int32),
                "item": items.astype(jnp.int32), "rating": rating}

    return datagen.make_and_fetch(make, seed, n, {
        "user": ((), np.int32), "item": ((), np.int32),
        "rating": ((), np.float32)})
