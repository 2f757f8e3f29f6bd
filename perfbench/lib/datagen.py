"""Seeded data, made on the device in bulk and fetched once.

One generator per *kind* of data; a configuration file names the kind and
gives its sizes (``data.kind``). Everything is elementwise arithmetic on
counters and random bits — no table look-ups, no ``rng.choice`` — so 100
million ratings take well under a second of device time:

* popularity follows the continuous inverse CDF of a power law,
  ``id = floor(((N+1)^(1-a) - 1) u + 1)^(1/(1-a))) - 1`` for uniform ``u``,
  which is Zipf(a) with the steps smoothed (listed under ``assumed`` in the
  configuration files);
* planted structure (MF's rank-6 factors, PA's true weight vector) is a
  32-bit hash of the id, so a row's value needs no gather. It is salted
  by the CONFIGURATION (``planted_salt``), not by the seed: a seed draws
  other users, movies, noise, initial tables and shuffles from the same
  population, as resampling one fixed public data set would, so the
  learning curve — and with it the count of examples to the quality
  target — hardly moves from seed to seed.

The arrays come back as host numpy, which is what the program's public
constructor documents (``DeviceDataset(mesh, data)`` takes host arrays,
sorts route keys on the host and uploads a copy to every chip; PERF.md,
Open questions).
"""

from __future__ import annotations

import numpy as np

_CHUNKS = 8


def _fmix32(x):
    """murmur3's 32-bit finalizer, on uint32 arrays."""
    import jax.numpy as jnp

    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _hash_uniform(ids, lane: int, salt: int, half_width: float):
    """Deterministic uniform(-half_width, half_width) per (id, lane)."""
    import jax.numpy as jnp

    x = (ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + jnp.uint32((lane * 0x7F4A7C15 + salt) & 0xFFFFFFFF))
    u = (_fmix32(x) >> 8).astype(jnp.float32) * (2.0 ** -24)
    return (2.0 * u - 1.0) * half_width


def _power_law_ids(u, n: int, alpha: float):
    import jax.numpy as jnp

    e = 1.0 - alpha
    top = float((n + 1) ** e - 1.0)
    ids = jnp.floor(jnp.power(top * u + 1.0, 1.0 / e)).astype(jnp.int32) - 1
    return jnp.clip(ids, 0, n - 1)


def power_law_cdf(k: int, n: int, alpha: float) -> float:
    """P(id < k) under :func:`_power_law_ids`."""
    e = 1.0 - alpha
    return float(((k + 1) ** e - 1.0) / ((n + 1) ** e - 1.0))


def _make_and_fetch(make_chunk, seed: int, total: int, like: dict):
    """Run ``make_chunk(key, first_row) -> dict of (rows, ...) arrays``
    ``_CHUNKS`` times on the device and gather the chunks into host arrays of
    ``total`` rows. Returns ``(arrays, checksum)``: the order-free row
    checksum of ``check.row_checksum`` over all rows, taken on the device
    chunks so the data is not uploaded a second time for it.

    Chunks come over FLAT: a 2-D device array is un-tiled on the host at a
    fraction of the link's speed (4.9 GB of 64-wide rows took 17 s, my
    chip run, PR 23), a 1-D one is a plain copy. The fetch of one chunk
    overlaps the making of the next, and the copies into the output run on
    a few threads: first-touched host pages are what they wait for."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from perfbench.lib.check import row_checksum

    rows = -(-total // _CHUNKS)
    names = sorted(like)

    @jax.jit
    def make(key, first_row, live_rows):
        chunk = make_chunk(key, first_row)
        weight = (jnp.arange(rows) < live_rows).astype(jnp.float32)
        cs = row_checksum(dict(chunk, weight=weight), names)
        return {k: v.reshape(-1) for k, v in chunk.items()}, cs

    out = {k: np.empty((total,) + shape, dt)
           for k, (shape, dt) in like.items()}
    flat = {k: v.reshape(-1) for k, v in out.items()}
    width = {k: int(np.prod(shape, dtype=np.int64))
             for k, (shape, _) in like.items()}
    key = jax.random.key(seed & 0xFFFFFFFF)

    def copy_in(k, lo, live, host):
        w = width[k]
        flat[k][lo * w:(lo + live) * w] = host[:live * w]

    sums, pending, copies = [], None, []
    with ThreadPoolExecutor(max_workers=4) as pool:
        for c in range(_CHUNKS + 1):
            nxt = None
            if c * rows < total and c < _CHUNKS:
                live = min(rows, total - c * rows)
                arrays, cs = make(jax.random.fold_in(key, c), c * rows, live)
                for v in arrays.values():
                    v.copy_to_host_async()
                sums.append(cs)
                nxt = (c * rows, live, arrays)
            if pending is not None:
                lo, live, arrays = pending
                copies += [pool.submit(copy_in, k, lo, live, np.asarray(v))
                           for k, v in arrays.items()]
            pending = nxt
        for f in copies:
            f.result()
    return out, sum(int(x) for x in sums) & 0xFFFFFFFF


def mf_ratings(seed: int, d: dict) -> dict:
    """``user``, ``item`` (int32) and ``rating`` (f32): users uniform
    within their route group, route groups of unequal stated shares
    (``route_group_shares``), power-law movies, rating = <p_u, q_i> +
    noise with hashed rank-``r``
    factors of variance 1/r (as ``utils.datasets.synthetic_ratings``)."""
    import jax
    import jax.numpy as jnp

    nu, ni, n = d["num_users"], d["num_items"], d["num_ratings"]
    rank, noise, alpha = d["planted_rank"], d["noise"], d["item_zipf"]
    rows = -(-n // _CHUNKS)
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
        items = _power_law_ids(jax.random.uniform(ki, (rows,)), ni, alpha)
        r = noise * jax.random.normal(kn, (rows,), jnp.float32)
        for k in range(rank):
            r = r + (_hash_uniform(users, k, salt, half)
                     * _hash_uniform(items, k + rank, salt, half))
        return {"user": users, "item": items, "rating": r}

    return _make_and_fetch(make, seed, n, {
        "user": ((), np.int32), "item": ((), np.int32),
        "rating": ((), np.float32)})


def sparse_docs(seed: int, d: dict) -> dict:
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
    c = power_law_cdf(H, nf, alpha) if H else 0.0
    rows = -(-n // _CHUNKS)
    salt = int(d["planted_salt"]) & 0xFFFFFFFF

    def make(key, first_row):
        kh, ki, kv, kf = jax.random.split(key, 4)
        u = jax.random.uniform(ki, (rows, nnz))
        if H:
            h = jnp.sum(jax.random.uniform(kh, (rows, nnz)) < c, axis=1)
            h = jnp.maximum(h, q)
            head = jnp.arange(nnz)[None, :] < h[:, None]
            u = jnp.where(head, u * c, c + u * (1.0 - c))
        ids = _power_law_ids(u, nf, alpha)
        if H:
            # f32 rounding at the seam must not push a head slot's id out.
            ids = jnp.where(head, jnp.minimum(ids, H - 1),
                            jnp.maximum(ids, H))
        vals = jax.random.normal(kv, (rows, nnz), jnp.float32)
        w_true = _hash_uniform(ids, 0, salt, float(np.sqrt(3.0)))
        margin = jnp.sum(w_true * vals, axis=1)
        flip = jax.random.uniform(kf, (rows,)) < flip_p
        label = jnp.where((margin > 0) ^ flip, 1.0, -1.0)
        return {"feat_ids": ids, "feat_vals": vals,
                "label": label.astype(jnp.float32)}

    return _make_and_fetch(make, seed, n, {
        "feat_ids": ((nnz,), np.int32), "feat_vals": ((nnz,), np.float32),
        "label": ((), np.float32)})


KINDS = {"mf_ratings": mf_ratings, "sparse_docs": sparse_docs}
