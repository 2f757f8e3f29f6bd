"""Seeded data, made on the device in bulk and fetched once: what the
generators share. One generator per *kind* of data, each a file of its own
(``perfbench/datasets/<kind>.py``, found by ``resolve.py``); a
configuration file names the kind and gives its sizes (``data.kind``).
Everything is elementwise arithmetic on counters and random bits — no
table look-ups, no ``rng.choice`` — so 100 million ratings take well under
a second of device time:

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

CHUNKS = 8


def fmix32(x):
    """murmur3's 32-bit finalizer, on uint32 arrays."""
    import jax.numpy as jnp

    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def hash_uniform(ids, lane: int, salt: int, half_width: float):
    """Deterministic uniform(-half_width, half_width) per (id, lane)."""
    import jax.numpy as jnp

    x = (ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + jnp.uint32((lane * 0x7F4A7C15 + salt) & 0xFFFFFFFF))
    u = (fmix32(x) >> 8).astype(jnp.float32) * (2.0 ** -24)
    return (2.0 * u - 1.0) * half_width


def power_law_ids(u, n: int, alpha: float):
    import jax.numpy as jnp

    e = 1.0 - alpha
    top = float((n + 1) ** e - 1.0)
    ids = jnp.floor(jnp.power(top * u + 1.0, 1.0 / e)).astype(jnp.int32) - 1
    return jnp.clip(ids, 0, n - 1)


def power_law_cdf(k: int, n: int, alpha: float) -> float:
    """P(id < k) under :func:`power_law_ids`."""
    e = 1.0 - alpha
    return float(((k + 1) ** e - 1.0) / ((n + 1) ** e - 1.0))


def make_and_fetch(make_chunk, seed: int, total: int, like: dict):
    """Run ``make_chunk(key, first_row) -> dict of (rows, ...) arrays``
    ``CHUNKS`` times on the device and gather the chunks into host arrays of
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

    rows = -(-total // CHUNKS)
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
        for c in range(CHUNKS + 1):
            nxt = None
            if c * rows < total and c < CHUNKS:
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
