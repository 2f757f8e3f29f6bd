"""On-chip microbench of the device ingest of a 2-D column: three ways to
read a step's 16,384 rows out of a resident column, at the three cells
whose columns do not pack (``pa-rcv1.epochs``: ``[9652968, 64]`` and its
1-D label; ``lr-criteo.epochs``: ``[8388608, 39]`` and its label;
``dlrm-criteo.epochs``: ``[1048576, 26]``, ``[1048576, 13]`` and its
label), one worker, the ``interleave`` shuffle on the plan's own grid
(4,096 rows; 1,024 over dlrm's 2^20). The readings are the ones beside
``fps_tpu.core.device_ingest._SLICED_SLOTS_MAX``.

    chiprun --chips 1 -- python tools/bench_ingest.py \
        [pa] [lr] [dlrm] [dlrm.dense] [wide] [label]

* ``rows``: today's program. A step's rows computed from its positions
  (``pos -> (pos % r) * c + pos // r + off  mod m``) and one gather of
  16,384 single rows, ``jnp.take(col, row, axis=0)``.
* ``runs``: the same rows read as RUNS. A step's 16,384 positions are
  4,096 runs of 4 consecutive queue positions (``pos // r`` takes 4
  values a step), which an unkeyed plan on one worker makes 4 consecutive
  DATA rows: one gather of 4,096 slices of 4 rows, and the transpose that
  puts the batch back in position order.
* ``tbuf``: a contiguous slice of a once-an-epoch transposed copy (roll by
  the epoch's offset, view as ``(r, c)``, transpose: what
  ``DeviceEpochPlan._transposed_rows`` does; since PR 50 the plan's own
  path for lr's and dlrm's columns), with the copy's own cost
  (``build_ms``) and bytes (``copy_bytes``) beside it.

Every arm is a scan of steps whose carry is a checksum of the batch (the
same number in all three arms: printed, and compared), fenced by a host
read; best of three calls after the one that compiles, at TWO lengths of
scan (``STEPS``): ``ms_per_step`` is the slope between them and
``ms_per_call`` what is left, the cost a call pays once whatever its
length (on the chip a ``[N, 64]`` column lives column-major,
``{0,1:T(8,128)}``, and a program that gathers rows from it first copies
it whole into row-major tiles, 12 ms a call: chip run, PR 46). Prints one
JSON line an arm and appends them to ``chiprun_out/bench_ingest.jsonl``.
Imported by nothing; ships nothing.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp
from jax import lax

STEPS, B = (64, 512), 16_384
# rows, slots, the epoch's offset: one under which no run of consecutive
# positions crosses the wrap (``m``) or the data's end inside the steps
# timed, so that the ``runs`` arm reads the ``rows`` arm's rows. The two
# ``dlrm`` shapes (PR 50) are ``dlrm-criteo.epochs``' sparse and dense
# columns: 2^20 rows are 64 steps, which the scans walk eight times.
SHAPES = {"pa": (9_652_968, 64, 123_457), "lr": (8_388_608, 39, 123_456),
          "dlrm": (1_048_576, 26, 12_352),
          "dlrm.dense": (1_048_576, 13, 12_352)}


def _grid(n):
    """``(r, c, m)`` of ``DeviceEpochPlan``'s interleave grid over ``n``
    rows on one worker: about ``sqrt(n)`` rows, a power of two, at most
    4,096."""
    r = 1 << min(12, n.bit_length() // 2)
    c = -(-n // r)
    return r, c, r * c


def _steps(steps, n):
    """The scan's steps: an epoch's, walked again where the scan is
    longer than the epoch."""
    return jnp.arange(steps, dtype=jnp.int32) % (_grid(n)[2] // B)


def _positions(t, n, off):
    """Queue positions of step ``t`` under ``interleave`` (one worker):
    ``(qpos clamped, valid)`` as ``local_batch_at`` forms them."""
    r, c, m = _grid(n)
    pos = t * B + jnp.arange(B, dtype=jnp.int32)
    qpos = (pos % r) * c + pos // r + off
    qpos = jnp.where(qpos >= m, qpos - m, qpos)
    return jnp.clip(qpos, 0, n - 1), (pos < m) & (qpos < n)


def _fold(acc, batch, valid):
    """The carry: every element of the batch read once, summed in int32
    (it wraps, so the order of the sum changes nothing). The batch is
    MATERIALISED first, as the step program's is (its consumers are other
    fusions): without the barrier XLA fuses the gather into the sum and
    the ``rows`` arm reads 21 ns a 256-byte row where the program's own
    gather reads 8 (chip run, PR 46)."""
    batch = lax.optimization_barrier(batch)
    batch = batch.reshape(B, -1).astype(jnp.int32)
    return acc + jnp.sum(jnp.where(valid[:, None], batch, 0))


def arm_rows(col, off, *, steps):
    n = col.shape[0]

    def step(acc, t):
        qc, valid = _positions(t, n, off)
        return _fold(acc, jnp.take(col, qc, axis=0), valid), None

    return lax.scan(step, jnp.int32(0), _steps(steps, n))[0]


def arm_runs(col, off, *, steps):
    n = col.shape[0]
    r = _grid(n)[0]
    k = B // r          # consecutive queue positions a run
    tail = col.shape[1:]

    def step(acc, t):
        qc, valid = _positions(t, n, off)
        # Position j + r * i (i < k) is the i-th row of grid row j's run.
        # A run that crossed m (the wrap) or n inside its k rows would
        # read other rows than the ``rows`` arm: the offsets in SHAPES
        # leave none in the steps timed, and the checksums say so.
        starts = qc[:r]
        runs = lax.gather(
            col, starts[:, None],
            lax.GatherDimensionNumbers(
                offset_dims=tuple(range(1, 2 + len(tail))),
                collapsed_slice_dims=(), start_index_map=(0,)),
            slice_sizes=(k,) + tail, mode="clip")        # (r, k, ...)
        batch = jnp.swapaxes(runs, 0, 1).reshape((B,) + tail)
        return _fold(acc, batch, valid), None

    return lax.scan(step, jnp.int32(0), _steps(steps, n))[0]


def build_tbuf(col, off):
    """The epoch's transposed copy: entry ``pos`` holds the row the
    ``rows`` arm reads at position ``pos``."""
    n = col.shape[0]
    r, c, m = _grid(n)
    tail = col.shape[1:]
    if m > n:
        col = jnp.concatenate([col, jnp.zeros((m - n,) + tail, col.dtype)])
    rolled = jnp.roll(col, -off, axis=0)
    return jnp.swapaxes(rolled.reshape((r, c) + tail), 0, 1).reshape(
        (m,) + tail)


def arm_tbuf(tbuf, off, *, steps, n):
    tail = tbuf.shape[1:]

    def step(acc, t):
        _, valid = _positions(t, n, off)
        batch = lax.dynamic_slice(
            tbuf, (t * B,) + (0,) * len(tail), (B,) + tail)
        return _fold(acc, batch, valid), None

    return lax.scan(step, jnp.int32(0), _steps(steps, n))[0]


def _best(fn, *args):
    """Best of three calls after the one that compiles, in seconds, and
    the result."""
    best = 1e9
    for timed in (False, True, True, True):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        if timed:
            best = min(best, time.perf_counter() - t0)
    return best, out


def _timed_arm(fn, col, off, **static):
    """An arm at both lengths of scan: ``(ms a step, ms a call whatever
    its length, checksum at the longer)``."""
    (t1, _), (t2, out) = (
        _best(jax.jit(functools.partial(fn, steps=steps, **static)),
              col, off)
        for steps in STEPS)
    slope = (t2 - t1) / (STEPS[1] - STEPS[0])
    return slope * 1e3, (t1 - STEPS[0] * slope) * 1e3, int(out)


def _make(n, slots):
    """A column whose rows tell themselves apart, made on the device."""
    def make():
        i = jnp.arange(n, dtype=jnp.int32)
        if slots is None:
            return (i % 1009).astype(jnp.float32)
        return (i[:, None] % 1009) * 64 + jnp.arange(slots, dtype=jnp.int32)
    return jax.block_until_ready(jax.jit(make)())


def main(argv):
    if jax.default_backend() != "tpu":
        raise SystemExit(f"a rate comes from the chip: found "
                         f"{jax.default_backend()}")
    shapes = [s for s in SHAPES if s in argv] or list(SHAPES)
    kinds = [k for k in ("wide", "label") if k in argv] or ["wide", "label"]
    os.makedirs("chiprun_out", exist_ok=True)
    dev = jax.devices()[0]
    for name in shapes:
        n, slots, off = SHAPES[name]
        off = jnp.int32(off)
        for kind in kinds:
            col = _make(n, slots if kind == "wide" else None)
            base = {"shape": name, "column": list(col.shape),
                    "layout": str(col.format.layout),
                    "rows_a_step": B, "row_bytes": col.nbytes // n,
                    "steps": list(STEPS), "device": dev.device_kind,
                    "hbm_bytes_limit": dev.memory_stats()["bytes_limit"]}
            sums = {}
            for arm, fn in (("rows", arm_rows), ("runs", arm_runs)):
                ms, fixed, sums[arm] = _timed_arm(fn, col, off)
                _emit(dict(base, arm=arm, ms_per_step=ms,
                           ns_per_row=ms / B * 1e6, ms_per_call=fixed,
                           checksum=sums[arm], peak_hbm_gb=_peak_gb(dev)))
            try:
                build_s, tbuf = _best(jax.jit(build_tbuf), col, off)
                ms, fixed, sums["tbuf"] = _timed_arm(arm_tbuf, tbuf, off,
                                                    n=n)
            except jax.errors.JaxRuntimeError as e:   # the copy did not fit
                _emit(dict(base, arm="tbuf", error=str(e)[:200]))
                continue
            _emit(dict(base, arm="tbuf", ms_per_step=ms,
                       ns_per_row=ms / B * 1e6, ms_per_call=fixed,
                       checksum=sums["tbuf"], build_ms=build_s * 1e3,
                       copy_bytes=tbuf.nbytes, peak_hbm_gb=_peak_gb(dev)))
            del tbuf
            _emit(dict(base, arms_read_the_same_rows=len(
                set(sums.values())) == 1))


def _peak_gb(dev):
    """The process's peak so far: it only grows from arm to arm."""
    return dev.memory_stats()["peak_bytes_in_use"] / 1e9


def _emit(line):
    print(json.dumps(line), flush=True)
    with open("chiprun_out/bench_ingest.jsonl", "a") as fh:
        fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
