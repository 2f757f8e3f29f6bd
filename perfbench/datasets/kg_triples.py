"""Data kind ``kg_triples``: the training triples of a knowledge graph,
``(s, r, o)``: subject and object entities and the relation between them,
three int32 columns. Synthetic: subjects and objects drawn independently,
Zipf-like over the kept entities (rank = entity id, so the graph's head of
the degree ranking is the low ids), relations Zipf-like over theirs;
``lib/datagen.py``'s continuous inverse CDF."""

from __future__ import annotations

import numpy as np

from perfbench.lib import datagen


def generate(seed: int, d: dict):
    """``s (N,)``, ``r (N,)``, ``o (N,)`` int32 for ``N =
    triples_resident``."""
    import jax

    n = d["triples_resident"]
    rows = -(-n // datagen.CHUNKS)

    def make(key, first_row):
        del first_row
        ks, kr, ko = jax.random.split(key, 3)
        ent = lambda k: datagen.power_law_ids(  # noqa: E731
            jax.random.uniform(k, (rows,)), d["entities"], d["entity_zipf"])
        return {"s": ent(ks), "o": ent(ko),
                "r": datagen.power_law_ids(
                    jax.random.uniform(kr, (rows,)), d["relations"],
                    d["relation_zipf"])}

    return datagen.make_and_fetch(make, seed, n, {
        "s": ((), np.int32), "r": ((), np.int32), "o": ((), np.int32)})
