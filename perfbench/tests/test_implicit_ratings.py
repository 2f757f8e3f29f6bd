"""Data kind ``implicit_ratings`` at a small size: the checksum it returns
is the rows', the same seed gives the same rows, and both sides carry the
skew the configuration states."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.datasets import implicit_ratings
from perfbench.lib import check
from perfbench.lib.resolve import HERE

NU, NI, N = 2003, 401, 200_000


def _data_cfg(**over):
    with open(os.path.join(HERE, "configs", "ials-ml20m.json")) as f:
        d = json.load(f)["data"]
    d.update(num_users=NU, num_items=NI, num_ratings=N, ratings_resident=N,
             user_shift=6.0, item_shift=2.0)
    return dict(d, **over)


def test_checksum_is_the_rows_and_the_seed_decides_them():
    d = _data_cfg()
    data, cs = implicit_ratings.generate(2_147_484_001, d)
    again, cs2 = implicit_ratings.generate(2_147_484_001, d)
    other, cs3 = implicit_ratings.generate(7, d)
    assert cs == cs2 and cs != cs3
    for k in data:
        np.testing.assert_array_equal(data[k], again[k])
    assert {k: (v.dtype, v.shape) for k, v in data.items()} == {
        "user": (np.int32, (N,)), "item": (np.int32, (N,)),
        "rating": (np.float32, (N,))}
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    batch["weight"] = jnp.ones(N, jnp.float32)
    assert int(check.row_checksum(batch, sorted(data))) == cs


def test_both_sides_are_skewed_and_every_user_is_present():
    d = _data_cfg()
    data, _ = implicit_ratings.generate(3, d)
    users = np.bincount(data["user"], minlength=NU)
    items = np.bincount(data["item"], minlength=NI)
    assert data["user"].max() < NU and data["item"].max() < NI
    # Every user at the floor or over it; the busiest far over the mean.
    assert users.min() >= d["min_per_user"]
    assert users.max() > 8 * users.mean()
    # Movies: a head that holds most of the ratings, a tail that is rated.
    assert np.sort(items)[-NI // 20:].sum() > 0.3 * N
    assert items.max() > 20 * np.median(items) and items.min() > 0
    # The busy ranks are spread over the ids, not the low ids.
    assert users.argmax() == 0 and abs(int(np.argsort(users)[-2])
                                      - int(np.argsort(users)[-3])) > 1
    # Half stars, a mean near the configuration's, structure under it: the
    # planted preference explains most of the variance.
    r = data["rating"]
    assert set(np.unique(r)) <= set(np.arange(1, 11) * 0.5)
    assert abs(r.mean() - d["rating_mean"]) < 0.15 and 0.8 < r.std() < 1.2


def test_fewer_ratings_than_the_floor_needs_is_refused():
    with pytest.raises(ValueError, match="cannot give"):
        implicit_ratings.generate(1, _data_cfg(
            ratings_resident=NU * _data_cfg()["min_per_user"] - 1))
