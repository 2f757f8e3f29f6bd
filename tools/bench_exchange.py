"""On-chip microbench of the store's sharded exchange: ``store.pull`` and
``store.push`` by the owner-routed exchange against the gathered one, at
``w2v-1bw-hot.x4``'s two shapes (a ``[278753, 300]`` shard a chip of four;
8,197 and 49,182 ids a worker a step, drawn from the cell's Zipf law with
the replicated head's ids masked to -1 as the tier hands them over; the
per-id mean push, which takes ``push.mean_rows`` at the smaller batch and
``push.mean_dense`` at the larger).

    chiprun --chips 4 -- python tools/bench_exchange.py [in] [out] [pull] [push]

Each timed call runs a scan of T steps whose carry chains (the table for a
push, a checksum of the pulled rows for a pull), fenced by a host read.
The gathered arm is the same code with ``store._routes_to_owner`` ruled
out for the trace (:func:`traced_as`). Prints one JSON line an arm (ms a step, the share of
steps whose ids fit their lanes) and appends them to
``chiprun_out/bench_exchange.jsonl``. Needs more than one device.
"""

import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fps_tpu.core import store
from fps_tpu.parallel.mesh import SHARD_AXIS, make_ps_mesh

T = 16
V, DIM, HOT = 1_115_011, 300, 32_768
BATCH = {"in": 8_197, "out": 49_182}


def _ids(rng, S, B):
    """``[T, S * B]`` ids under the cell's law, the head's masked."""
    p = 1.0 / (np.arange(V) + 1.5)
    ids = rng.choice(V, size=(T, S * B), p=p / p.sum()).astype(np.int32)
    return np.where(ids < HOT, -1, ids)


def _timed(fn, table, *args):
    """Best of three calls after the one that compiles, in ms a step; the
    table is donated and handed on from call to call."""
    best = 1e9
    for timed in (False, True, True, True):
        t0 = time.perf_counter()
        table, _, fit = out = fn(table, *args)
        np.asarray(fit)
        if timed:
            best = min(best, time.perf_counter() - t0)
    return best / T * 1e3, out


def program(mesh, side):
    """The jitted scan of T steps of a pull or a push (``side``):
    ``(table, ids [T, S * B], deltas [S * B, DIM]) -> (table, checksum,
    steps that fit)``."""
    S = mesh.devices.size
    rows, workers = P(SHARD_AXIS, None), P(None, SHARD_AXIS)

    def steps(t, ids, d):
        def step(carry, i):
            t, acc, fit = carry
            with store.watch_routed() as seen:
                if side == "pull":
                    got = store.pull(t, i, num_shards=S, table="t")
                    acc = acc + jnp.sum(got)
                else:
                    t = store.push(t, i, d, num_shards=S, data_axis=None,
                                   combine="mean", table="t")
            return (t, acc, fit + seen["t"]), None

        (t, acc, fit), _ = lax.scan(
            step, (t, jnp.float32(0), jnp.int32(0)), ids)
        return t, jnp.reshape(acc, (1,)), jnp.reshape(fit, (1,))

    return jax.jit(jax.shard_map(
        steps, mesh=mesh, in_specs=(rows, workers, rows),
        out_specs=(rows, P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False), donate_argnums=(0,))


@contextlib.contextmanager
def traced_as(arm):
    """Trace (lower, first call) under it: the gathered arm is the same
    code with the owner-routed exchange ruled out."""
    rule = store._routes_to_owner
    if arm == "gathered":
        store._routes_to_owner = lambda *a: False
    try:
        yield
    finally:
        store._routes_to_owner = rule


def main(argv):
    S = len(jax.devices())
    if S < 2:
        raise SystemExit("the exchange needs more than one device")
    mesh = make_ps_mesh(num_shards=S)
    rps = store.rows_per_shard(V, S)
    rng = np.random.default_rng(0)
    rows, workers = P(SHARD_AXIS, None), P(None, SHARD_AXIS)
    table = jax.device_put(
        jnp.zeros((rps * S, DIM), jnp.float32) + 0.5,
        NamedSharding(mesh, rows))
    tables = [t for t in ("in", "out") if t in argv] or ["in", "out"]
    sides = [s for s in ("pull", "push") if s in argv] or ["pull", "push"]
    os.makedirs("chiprun_out", exist_ok=True)
    for name in tables:
        B = BATCH[name]
        ids = jax.device_put(_ids(rng, S, B), NamedSharding(mesh, workers))
        deltas = jax.device_put(
            jnp.asarray(rng.normal(0, 1e-3, (S * B, DIM)), jnp.float32),
            NamedSharding(mesh, rows))
        for side in sides:
            for arm in ("gathered", "routed"):
                t0 = time.perf_counter()
                with traced_as(arm):
                    ms, (table, _, fit) = _timed(program(mesh, side),
                                                 table, ids, deltas)
                line = {"table": name, "side": side, "arm": arm,
                        "shards": S, "ids": B, "ms_per_step": ms,
                        "fit_share": float(np.asarray(fit)[0]) / T,
                        "wall_s": time.perf_counter() - t0}
                print(json.dumps(line), flush=True)
                with open("chiprun_out/bench_exchange.jsonl", "a") as fh:
                    fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
