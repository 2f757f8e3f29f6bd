"""word2vec SGNS at block granularity against its plain reference, and the
benchmark cell ``w2v-1bw.epochs`` rehearsed on the CPU.

Tiny sizes (vocabulary 2,003, dim 16, blocks of 64 tokens) on 1 and on 4
virtual devices. What is checked is correctness and counts: the program
against ``perfbench/lib/reference/sgns_block.py`` over one ``run_indexed``
epoch, the reference's explicit pairs against the program's own pair
stream, the subsampling's keep rate and the alias sampler's distribution,
the files the cell is made of (``spec.validate`` from here, where the
driver's test command reaches), and the runner's whole path for the cell.
No rate is read: a CPU run has none.
"""

import contextlib
import copy
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.datasets import token_stream
from perfbench.lib import check, resolve, runner, spec, window
from perfbench.lib.reference import sgns_block
from perfbench.models import word2vec_sgns

# (the package exports a function of the module's name)
w2v = importlib.import_module("fps_tpu.models.word2vec")

CELL = "w2v-1bw.epochs"
TINY = {"model": {"vocab_size": 2003, "dim": 16, "block_len": 64},
        "data": {"vocab_size": 2003, "tokens_resident": 40_000,
                 "corpus_tokens": 2_000_000}}
# float32 on both sides; what differs is the ORDER of sums (the worker adds
# slices offset by offset, the reference scatter-adds pair by pair) and a
# multiply by 1/count where the reference divides: a few ulp a step
# (1.2e-7), carried through 10-60 steps. bfloat16 (8 bits) reads 1e-2.
F32_GAP = 5e-6


def tiny_cell():
    loaded = spec.load_cell(spec.load_benchmark(), CELL)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY.items():
        cfg[part].update(over)
    loaded["config"] = cfg
    return loaded


@contextlib.contextmanager
def mesh_devices(n):
    """``jax.devices()`` cut to ``n`` virtual devices for the body (the
    program builds its mesh from it)."""
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:n]
    try:
        yield
    finally:
        jax.devices = real


def build(n, seed=7):
    loaded = tiny_cell()
    cfg, traffic = loaded["config"], loaded["traffic"]
    with mesh_devices(n):
        data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
        system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    init = resolve.reference(cfg).init_tables(seed, cfg)
    return loaded, system, init, data, data_sum


@pytest.fixture(scope="module", params=[1, 4])
def first_call(request):
    """One ``run_indexed`` epoch of the timed entry from the benchmark's
    seeded tables, and the reference's replay of it."""
    loaded, system, init, _, data_sum = build(request.param)
    state, warm = window.queue_call(system, system.place(init))
    warm.wait()
    program = system.export(*state)
    numbers, (ref_tables, ref_loss, ref_n) = check.compare_call(
        system, loaded["config"], init, program, warm.host, data_sum)
    return dict(system=system, init=init, program=program, numbers=numbers,
                host=warm.host, ref_tables=ref_tables, ref_loss=ref_loss,
                ref_n=ref_n)


def test_spec_validates_the_committed_benchmark_files():
    """The check the runner makes as it starts, where the driver's test
    command reaches it (``perfbench/tests/`` is outside that command)."""
    bench = spec.load_benchmark()
    spec.validate(bench)
    cell = spec.load_cell(bench, CELL)
    assert cell["cell"]["chips"] == 1
    assert {"worker.prepare_ms_per_step", "store.combine_dense_ms_per_step",
            "kernel.xla_gather_ms_per_step"} <= set(cell["readers"])
    cfg = cell["config"]
    assert cfg["reduced"] == ["tokens_resident"]
    assert (cfg["model"]["vocab_size"], cfg["model"]["dim"]) == (
        1_115_011, 300)
    rows = cfg["model"]["block_len"] + cfg["model"]["window"]
    assert cfg["rowops"]["rows_per_worker_step"] == 2 * rows * (
        2 + cfg["model"]["negatives"])
    assert cfg["rowops"]["row_bytes"] == 4 * cfg["model"]["dim"]


def test_program_agrees_with_the_reference_step_for_step(first_call):
    c = first_call
    loss = np.concatenate([m["loss"] for m in c["host"]])
    n = np.concatenate([m["n"] for m in c["host"]])
    assert loss.shape == c["ref_loss"].shape
    # (the plan sizes an epoch with slack: the last steps may be empty)
    assert (loss[n > 0] > 0).all() and (n > 0).sum() > len(n) // 2
    # Exact: the instances are counted, not approximated.
    np.testing.assert_array_equal(n, c["ref_n"])
    np.testing.assert_allclose(loss, c["ref_loss"], rtol=F32_GAP)
    for name in (sgns_block.IN, sgns_block.OUT):
        ref = np.asarray(c["ref_tables"][name])
        scale = np.abs(ref).max()
        np.testing.assert_allclose(c["program"][name], ref, rtol=0,
                                   atol=F32_GAP * scale)
        # ... and the epoch moved both tables (out_embeddings from zeros).
        assert np.abs(ref - c["init"][name]).max() > 1e-4


def test_every_compared_number_is_inside_a_float32_gap(first_call):
    numbers = first_call["numbers"]
    assert numbers["examples"] == 0 and numbers["feed"] == 0
    assert set(numbers) == {
        "examples", "feed", "loss_gap", "table_gap.in_embeddings",
        "table_gap.out_embeddings", "update_gap.in_embeddings",
        "update_gap.out_embeddings"}
    assert max(v for k, v in numbers.items()
               if k not in ("examples", "feed")) < F32_GAP, numbers


def test_examples_are_the_instances_the_fed_batches_define(first_call):
    system = first_call["system"]
    n = sum(float(np.sum(m["n"], dtype=np.float64))
            for m in first_call["host"])
    assert system.examples_per_call == n > 0


def test_feed_is_the_kept_stream_plus_what_subsampling_dropped(first_call):
    """Live steps carry the kept stream (block positions under
    ``valid_len``), steps past them the dropped tokens with empty blocks;
    together every token of the corpus once, by counts."""
    system = first_call["system"]
    V = system.cfg["model"]["vocab_size"]
    T = int(system.plan.steps_per_epoch)
    fed = np.zeros(V, np.int64)
    kept = steps = 0
    for chunk, live in system.fed_chunks(0, 16):
        tok, wt = np.asarray(chunk["token"]), np.asarray(chunk["weight"])
        np.add.at(fed, tok[wt > 0], 1)
        vlen = np.asarray(chunk["valid_len"])
        assert (vlen[live:] == 0).all()      # past the live steps: empty
        kept += int(wt[:live].sum())
        steps += live
    corpus = np.asarray(system.plan.dataset.columns["token"])
    np.testing.assert_array_equal(fed, np.bincount(corpus, minlength=V))
    assert steps == T and 0 < kept < len(corpus)


@pytest.mark.parametrize("seed", [3, 2_147_484_001])
def test_reference_pairs_are_the_programs_pair_stream(seed):
    """The reference enumerates pairs one by one; ``block_pair_stream``
    (the program's id-only reconstruction of the block worker's pairs) and
    the host count must give the same instances."""
    rng = np.random.default_rng(seed % 1000)
    L, W, K, V, D, Wk = 64, 5, 5, 97, 8, 3
    batch = {
        "block": rng.integers(0, V, (Wk, L + W)).astype(np.int32),
        "half": rng.integers(1, W + 1, (Wk, L)).astype(np.int32),
        "valid_len": np.array([L + W, 17, 0], np.int32),
        "negatives": rng.integers(0, V, (Wk, L + W, K)).astype(np.int32),
    }
    cfg = {"model": {"learning_rate": 0.025}}
    tables = {sgns_block.IN: jnp.asarray(rng.normal(size=(V, D)), jnp.float32),
              sgns_block.OUT: jnp.asarray(rng.normal(size=(V, D)),
                                          jnp.float32)}
    _, out = sgns_block.make_step(cfg)(tables, batch)
    want = sum(float(jnp.sum(w2v.block_pair_stream(
        {k: v[w] for k, v in batch.items()})[2])) for w in range(Wk))
    assert float(out["n"]) == want > 0
    assert word2vec_sgns.count_instances(batch["half"],
                                         batch["valid_len"]) == want


def test_bf16_control_fails_the_comparison():
    """The reference in the program's place, in bfloat16: at least one
    number passes a float32 gap by orders of magnitude."""
    loaded, system, init, _, data_sum = build(1, seed=5)
    cfg = loaded["config"]
    ref, ref_loss, ref_n, feed = check.run_reference(system, cfg, init)
    lt, low_loss, low_n, low_feed = check.run_reference(
        system, cfg, init, dtype=jnp.bfloat16)
    low = check.compare(
        {k: np.asarray(v, np.float32) for k, v in lt.items()}, ref, init,
        low_loss, low_n, ref_loss, ref_n, low_feed,
        check.call_checksum(system, data_sum), system.examples_per_call)
    assert low["examples"] == 0 and low["feed"] == 0
    worst = max(v for k, v in low.items() if k not in ("examples", "feed"))
    assert worst > 1000 * F32_GAP, low


def test_subsampling_keeps_each_word_at_its_stated_rate(devices8):
    """``min(1, sqrt(t/f))`` per word: the epoch's kept count within five
    standard deviations of its expectation, and the most frequent word
    thinned to its own rate within 10 %."""
    _, system, _, data, _ = build(1, seed=9)
    plan = system.plan
    tokens = data["token"]
    counts = token_stream.unigram_counts(system.cfg["data"])
    keep_p = w2v._keep_probs(system.trainer.logic.cfg, counts)
    assert keep_p[0] < 0.1 and keep_p[-1] == 1.0
    want = keep_p[tokens].sum()
    sd = np.sqrt((keep_p[tokens] * (1 - keep_p[tokens])).sum())
    args = plan.epoch_args(0)
    kept = int(args["kept"])
    assert abs(kept - want) < 5 * sd, (kept, want, sd)
    stream = np.asarray(args["compacted"])[:kept]
    top = (tokens == 0).sum() * keep_p[0]
    assert abs((stream == 0).sum() - top) < 0.1 * top + 5 * np.sqrt(top)
    # Order kept: the stream is a subsequence of the corpus.
    it = iter(tokens.tolist())
    assert all(any(t == u for u in it) for t in stream[:200].tolist())


def test_alias_sampler_draws_unigram_to_the_three_quarters(devices8):
    """200,000 draws against unigram^0.75: total variation under 2 % (the
    sampling noise of 200 words at that many draws is 1 %)."""
    V = 200
    counts = token_stream.unigram_counts(
        {"vocab_size": V, "corpus_tokens": 1e6, "zipf_exponent": 1.0})
    worker = w2v.Word2VecWorker(w2v.W2VConfig(vocab_size=V), counts)
    draws = np.asarray(worker._draw_negatives(jax.random.key(4),
                                              (200_000,)))
    p = counts ** 0.75
    p /= p.sum()
    got = np.bincount(draws, minlength=V) / len(draws)
    assert 0.5 * np.abs(got - p).sum() < 0.02


def _walk_alias(p):
    """The sequential Vose walk ``_build_alias`` replaced."""
    V = len(p)
    prob, alias = np.zeros(V), np.zeros(V, np.int64)
    scaled = np.asarray(p, np.float64) * V
    small = [i for i in range(V) if scaled[i] < 1.0]
    large = [i for i in range(V) if scaled[i] >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], l
        scaled[l] -= 1.0 - scaled[s]
        (small if scaled[l] < 1.0 else large).append(l)
    for i in large + small:
        prob[i] = 1.0
    return prob, alias


def _encoded(prob, alias):
    V = len(prob)
    q = prob / V
    np.add.at(q, alias, (1.0 - prob) / V)
    return q


@pytest.mark.parametrize("V,law", [(1, "zipf"), (2, "zipf"), (7, "flat"),
                                   (1000, "zipf"), (50_000, "random")])
def test_build_alias_encodes_the_distribution_the_walk_encodes(V, law):
    """Rounds in place of the walk over Python lists: other pairings, the
    SAME sampled distribution (each table decoded back to ``p``)."""
    rng = np.random.default_rng(V)
    p = {"zipf": 1.0 / (np.arange(V) + 1.5) ** 0.75,
         "flat": np.ones(V), "random": rng.random(V) ** 4}[law]
    p = p / p.sum()
    prob, alias = w2v._build_alias(p)
    assert ((prob >= 0) & (prob <= 1)).all()
    assert ((alias >= 0) & (alias < V)).all()
    np.testing.assert_allclose(_encoded(prob, alias), p, rtol=0, atol=1e-14)
    np.testing.assert_allclose(_encoded(*_walk_alias(p)), p, rtol=0,
                               atol=1e-14)


def test_build_alias_at_the_cells_vocabulary_takes_under_a_second():
    V = 1_115_011
    p = 1.0 / (np.arange(V) + 1.5) ** 0.75
    p /= p.sum()
    t0 = time.perf_counter()
    prob, alias = w2v._build_alias(p)
    took = time.perf_counter() - t0
    np.testing.assert_allclose(_encoded(prob, alias), p, rtol=0, atol=1e-14)
    assert took < 5.0, took   # 0.5 s here; the walk took 2-3 s


def run_cell(n, seed):
    events = []
    with mesh_devices(n):
        result = runner.run_cell(
            tiny_cell(), seed=seed, seconds=0.3, trace=False,
            t_start=time.perf_counter(),
            emit=lambda event, **f: events.append(dict(event=event, **f)),
            out_dir="unused")
    return result, events


@pytest.mark.parametrize("n,seed", [(1, 11), (4, 2_147_484_123)])
def test_cell_rehearsal_runs_the_runners_whole_path(n, seed):
    """The benchmark's own path for the cell (data, system, seeded state,
    warm-up, queue-ahead window, comparison) at a tiny size; the limits
    are the committed file's."""
    result, events = run_cell(n, seed)
    compared = [e for e in events if e["event"] == "compared"]
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "examples_per_s"}
    readings = next(e for e in events if e["event"] == "readings")
    # Every call is another epoch: fresh subsampling, other counts.
    assert readings["window_examples"] > 0
    assert {e["number"] for e in compared} == set(
        tiny_cell()["config"]["limits"]) | {"programs_lowered_in_window"}


def test_block_workers_contractions_are_float32_on_every_backend():
    """The negatives' three einsums carry ``precision=HIGHEST``: the TPU's
    default would run them in bfloat16 passes under float32 tables."""
    V, D, L, W, K = 50, 16, 8, 5, 5
    worker = w2v.Word2VecBlockWorker(w2v.W2VConfig(vocab_size=V, dim=D),
                                     np.ones(V), L)
    batch = {"block": jnp.zeros(L + W, jnp.int32),
             "half": jnp.ones(L, jnp.int32),
             "valid_len": jnp.int32(L),
             "negatives": jnp.zeros((L + W, K), jnp.int32)}
    pulled = {w2v.IN_TABLE: jnp.zeros((L + W, D)),
              w2v.OUT_TABLE: jnp.zeros(((L + W) * (1 + K), D))}
    text = jax.jit(lambda b, p: worker.step(b, p, (), None).pushes).lower(
        batch, pulled).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 3
    assert all("HIGHEST" in line for line in dots), dots
