"""The comparison that decides ``correct``.

What is compared is what the timed path produced: the FIRST call of the
window's own entry (``System.call`` -> ``Trainer.run_indexed``, the same
compiled program, batch and plan the window drives), from seeded tables
the benchmark made. The reference replays that call step for step on the
batches the call consumed, and these numbers are held to limits written
in the configuration file (``limits``; PERF.md gives the readings each
was set from):

* ``examples``: the examples the call reports, against the data's size
  (exact: an update dropped or a row fed twice shows here);
* ``feed``: a checksum of the rows fed over the call against the data
  set's own (exact: the feed is a permutation of the data, checked
  without reading the program's shuffle);
* ``loss_gap``: the widest relative gap of a step's summed loss;
* ``table_gap.<table>``: max |program - reference| over max |reference|,
  per table, after the call (the worst leaf decides);
* ``update_gap.<table>``: the gap between the two norms of a table's
  change over the call, against the reference's (a step that returns its
  state unchanged reads 1 here).

``run_reference(..., dtype=bfloat16)`` is the control: the reference put
in the program's place at the nearest precision below the configured
float32. It must come out as not correct (tests/, PERF.md).
"""

from __future__ import annotations

import numpy as np

from perfbench.lib import resolve

STEPS_PER_CHUNK = 64


def row_checksum(batch: dict, names):
    """Order-free checksum of a batch's live rows: a sum over rows of a
    hash of the row's bits, in uint32 arithmetic (wraps, exact)."""
    import jax
    import jax.numpy as jnp

    h = jnp.zeros(batch["weight"].shape, jnp.uint32)
    for j, k in enumerate(names):
        v = batch[k]
        if v.dtype != jnp.int32:
            v = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
        v = v.astype(jnp.uint32)
        if v.ndim > h.ndim:
            lanes = jnp.arange(v.shape[-1], dtype=jnp.uint32) * 2 + 1
            v = jnp.sum(v * lanes * jnp.uint32(0x9E3779B1), axis=-1,
                        dtype=jnp.uint32)
        x = v * jnp.uint32(2654435761 + 2 * j) + jnp.uint32(j + 1)
        x = x ^ (x >> 15)
        h = h + x * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    return jnp.sum(jnp.where(batch["weight"] > 0, h, 0), dtype=jnp.uint32)


def run_reference(system, cfg: dict, init: dict, call_index: int = 0,
                  dtype=None):
    """Replay call ``call_index`` from ``init`` (logical tables) with the
    plain reference. Returns ``(tables, per-step loss, per-step n, feed
    checksum)``; tables stay on the device, in ``dtype``."""
    import jax
    import jax.numpy as jnp

    ref = resolve.reference(cfg)
    dtype = dtype or jnp.float32
    step = ref.make_step(cfg, dtype=dtype, workers=system.W)
    names = sorted(system.plan.dataset.column_names())

    @jax.jit
    def run_chunk(tables, chunk):
        def body(t, batch):
            t, out = step(t, batch)
            return t, (out["loss"], out["n"], row_checksum(batch, names))
        tables, (loss, n, cs) = jax.lax.scan(body, tables, chunk)
        return tables, loss, n, jnp.sum(cs, dtype=jnp.uint32)

    tables = {k: v.astype(dtype) for k, v in init.items()}
    losses, ns, feed = [], [], 0
    with jax.default_matmul_precision("highest"):
        for chunk, live in system.fed_chunks(call_index, STEPS_PER_CHUNK):
            tables, loss, n, cs = run_chunk(tables, chunk)
            losses.append(loss[:live])
            ns.append(n[:live])
            feed = (feed + int(cs)) & 0xFFFFFFFF
    return (tables, np.concatenate([np.asarray(x) for x in losses]),
            np.concatenate([np.asarray(x) for x in ns]), feed)


def compare(program: dict, reference: dict, init: dict, prog_loss, prog_n,
            ref_loss, ref_n, feed: int, want_feed: int,
            want_examples: int) -> dict:
    """The numbers compared, by name. ``program``/``reference``/``init``:
    logical tables by the reference's names (host or device arrays)."""
    out = {}
    out["examples"] = abs(float(np.sum(prog_n, dtype=np.float64))
                          - want_examples)
    out["feed"] = float(feed != want_feed
                        or float(np.sum(ref_n, dtype=np.float64))
                        != want_examples)
    pl, rl = np.asarray(prog_loss, np.float64), np.asarray(ref_loss,
                                                           np.float64)
    if pl.shape != rl.shape or not np.isfinite(pl).all():
        out["loss_gap"] = float("inf")
    else:
        live = rl > 0
        out["loss_gap"] = float(np.max(np.abs(pl - rl)[live] / rl[live],
                                       initial=0.0))
    for name in sorted(reference):
        p = np.asarray(program[name], np.float64).reshape(-1)
        r = np.asarray(reference[name], np.float64).reshape(-1)
        i0 = np.asarray(init[name], np.float64).reshape(-1)
        if p.shape != r.shape or not np.isfinite(p).all():
            out[f"table_gap.{name}"] = out[f"update_gap.{name}"] = float("inf")
            continue
        out[f"table_gap.{name}"] = float(np.max(np.abs(p - r))
                                         / max(np.max(np.abs(r)), 1e-30))
        dr = np.linalg.norm(r - i0)
        out[f"update_gap.{name}"] = float(abs(np.linalg.norm(p - i0) - dr)
                                          / max(dr, 1e-30))
    return out


def call_checksum(system, data_sum: int) -> int:
    """The row checksum one call must feed: the data set once an epoch."""
    return (data_sum * system.epochs_per_call) & 0xFFFFFFFF


def compare_call(system, cfg: dict, init: dict, program: dict, call_host,
                 data_sum: int):
    """Replay the system's FIRST call with the reference and compare.
    ``program``: the tables that call left (logical, by the reference's
    names); ``call_host``: its host metrics; ``data_sum``: the data set's
    row checksum. Returns ``(numbers, (reference tables, loss, n))``."""
    ref_tables, ref_loss, ref_n, feed = run_reference(system, cfg, init)
    numbers = compare(
        program, ref_tables, init,
        np.concatenate([m[system.loss_key] for m in call_host]),
        np.concatenate([m["n"] for m in call_host]),
        ref_loss, ref_n, feed, call_checksum(system, data_sum),
        system.examples_per_call)
    return numbers, (ref_tables, ref_loss, ref_n)


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """Every number against its limit; a number with no limit is a fault
    of the configuration file. Returns (all within, printable rows)."""
    ok, rows = True, []
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        within = bool(value <= limits[name])
        ok &= within
        rows.append({"number": name, "value": value, "limit": limits[name],
                     "within": within})
    return ok, rows
