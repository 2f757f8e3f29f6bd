"""CPU rehearsal of the cell ``w2v-1bw-hot.x4`` (word2vec under the
two-tier storage) at a tiny size on 4 virtual devices, through the
runner's own functions, and the reader its hit share is read by. Checks
control flow only: a CPU run has no rate. (``tests/test_w2v_hot_bench.py``
holds the program against the reference; this is the runner's path.)
"""

import contextlib
import copy
import time

import jax
import pytest

from perfbench.lib import readers, runner, spec

CELL = "w2v-1bw-hot.x4"
TINY = {"model": {"vocab_size": 2003, "dim": 16, "block_len": 64,
                  "hot_tier": 64, "hot_sync_every": 4},
        "data": {"vocab_size": 2003, "tokens_resident": 41_000,
                 "corpus_tokens": 2_000_000}}


def tiny_cell():
    loaded = spec.load_cell(spec.load_benchmark(), CELL)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY.items():
        cfg[part].update(over)
    loaded["config"] = cfg
    return loaded


@contextlib.contextmanager
def mesh_devices(n):
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:n]
    try:
        yield
    finally:
        jax.devices = real


def test_cell_runs_and_agrees_with_its_reference():
    events = []
    with mesh_devices(4):
        result = runner.run_cell(
            tiny_cell(), seed=2_147_484_001, seconds=0.3, trace=False,
            t_start=time.perf_counter(),
            emit=lambda event, **f: events.append(dict(event=event, **f)),
            out_dir="unused")
    compared = [e for e in events if e["event"] == "compared"]
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"setup_s", "examples_per_s"}
    assert result["compared"]["table_gap.pending_in"] == {
        "value": 0.0, "limit": 0}
    assert result["compared"]["programs_lowered_in_window"]["value"] == 0


def _ctx(*events):
    return {"program_span_events": {"device.run_indexed": {
        "setup": [], "after": [], "window": list(events)}}}


PARAMS = {"span": "device.run_indexed", "part": "window",
          "field": "hot_tier", "of": "hot_rows", "over": "pulled_rows",
          "scale": 100.0}


def test_hit_share_sums_every_table_of_every_call():
    read = readers.reader("span_field_share")
    call = {"t0": 0.0, "t1": 1.0, "hot_tier": {
        "in_embeddings": {"hot_rows": 60.0, "pulled_rows": 100.0},
        "out_embeddings": {"hot_rows": 240.0, "pulled_rows": 600.0}}}
    assert read(_ctx(call, call), PARAMS) == pytest.approx(100 * 300 / 700)


@pytest.mark.parametrize("ctx", [
    {}, {"program_span_events": {}}, _ctx(), _ctx({"t0": 0.0, "t1": 1.0}),
    _ctx({"t0": 0.0, "t1": 1.0, "hot_tier": {
        "in_embeddings": {"hot_rows": 0.0, "pulled_rows": 0.0}}})])
def test_hit_share_reads_nothing_where_the_program_sets_no_sums(ctx):
    """A parent commit's spans carry no ``hot_tier`` field, a run without
    a recorder no spans at all: ``None``, never an error."""
    assert readers.reader("span_field_share")(ctx, PARAMS) is None
