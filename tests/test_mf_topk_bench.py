"""Online MF that answers a top-K list per rating event against its plain
reference, and the benchmark cell ``mf-netflix-topk.epochs`` rehearsed on
the CPU.

Tiny sizes (211 users x 157 movies, rank 4, 64 ratings a step, 8 queries
a step, K 5) on one virtual device, as the cell's one chip. What is
checked is correctness and counts: the files the cell is made of
(``spec.validate`` from here, where the driver's test command reaches),
the runner's whole path for the cell, the program against
``perfbench/lib/reference/mf_sgd_topk.py`` step for step (the lists
beside the tables), both controls (the reference in bfloat16 in the
program's place; the reference ranking AFTER each step's update:
``perfbench/prequential.py``), and timed paths that skip the tap, answer
stale lists or translate an id wrongly. No rate is read: a CPU run has
none.
"""

import contextlib
import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fps_tpu.ops as ops
from perfbench import prequential
from perfbench.lib import check, resolve, runner, spec, window

CELL = "mf-netflix-topk.epochs"
NU, NI, RANK, B, Q, K = 211, 157, 4, 64, 8, 5
N = 64 * 37 + 21
# The plan interleaves its padding: 39 steps, the last one all padding, a
# padding row or two among the first rows of every other step.
T = 39
TINY = {"model": {"num_users": NU, "num_items": NI, "rank": RANK,
                  "local_batch": B, "topk": K, "queries_per_step": Q,
                  "topk_steps_per_call": T},
        "data": {"num_users": NU, "num_items": NI, "num_ratings": N,
                 "planted_rank": 3}}
LISTS = ("topk_scores", "topk_id_scores", "topk_query", "topk_counts")


def tiny_cell(**model):
    loaded = spec.load_cell(spec.load_benchmark(), CELL)
    cfg = copy.deepcopy(loaded["config"])
    for part, over in TINY.items():
        cfg[part].update(over)
    cfg["model"].update(model)
    loaded["config"] = cfg
    return loaded


@contextlib.contextmanager
def one_device():
    """``jax.devices()`` cut to one virtual device for the body (the
    program builds its mesh from it): the cell is a one-chip cell."""
    real = jax.devices
    jax.devices = lambda *a: real(*a)[:1]
    try:
        yield
    finally:
        jax.devices = real


def first_call(seed=7, **model):
    """The warm-up call of the cell's timed entry at the tiny size:
    ``(loaded, system, init, program tables, host metrics, data sum)``."""
    loaded = tiny_cell(**model)
    cfg, traffic = loaded["config"], loaded["traffic"]
    with one_device():
        data, data_sum = resolve.generator(cfg)(seed, cfg["data"])
        system = resolve.system_class(cfg, traffic)(cfg, traffic, data, seed)
    init = resolve.reference(cfg).init_tables(seed, cfg)
    state, warm = window.queue_call(system, system.place(init))
    host = warm.wait().host
    system.state = state
    return loaded, system, init, system.export(*state), host, data_sum


@pytest.fixture(scope="module")
def call():
    return first_call()


def judged(loaded, numbers):
    ok, rows = check.judge(numbers, loaded["config"]["limits"])
    return ok, {r["number"] for r in rows if not r["within"]}


# -- the files -------------------------------------------------------------

def test_spec_validates_the_committed_benchmark_files():
    bench = spec.load_benchmark()
    spec.validate(bench)
    cell = spec.load_cell(bench, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["config"] == "mf-netflix-topk"
    assert cell["traffic"]["epochs_per_call"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "examples_per_s"}
    # Every metric mf-netflix.epochs lists but the examples to the target,
    # and the tap's five, which list this cell alone.
    mf = set(spec.load_cell(bench, "mf-netflix.epochs")["readers"])
    tap = {"tap.topk_ms_per_step", "tap.score_ms_per_step",
           "tap.select_ms_per_step", "tap.topk_routes_in_program",
           "tap.pruned_routes_in_program"}
    assert set(cell["readers"]) == (mf - {"worker.examples_to_target"}) | tap
    for m in bench["per_layer"]:
        if m["name"] in tap:
            assert m["workloads"] == [CELL] and m["layer"] == "step tap"
    # Seven cells when this one came; PR 44 appended the eighth, which is
    # the second on four chips (tests/test_w2v_hot_bench.py counts them).
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 6 and len(names) >= 7
    assert [w["name"] for w in bench["workloads"][:7]
            if w["chips"] == 4] == ["mf-netflix.x4"]


def test_the_configuration_is_mf_netflix_with_the_tap():
    bench = spec.load_benchmark()
    cfg = spec.load_cell(bench, CELL)["config"]
    mf = spec.load_cell(bench, "mf-netflix.epochs")["config"]
    m = cfg["model"]
    # Model, data, hyper-parameters, start and routing: key for key.
    mine = ("kind", "queries_per_step")
    assert {k: v for k, v in m.items() if not k.startswith("topk")
            and k not in mine} == {k: v for k, v in mf["model"].items()
                                   if k != "kind"}
    assert cfg["data"] == mf["data"]
    assert (m["num_users"], m["num_items"], m["rank"], m["local_batch"],
            cfg["data"]["num_ratings"]) == (480_189, 17_770, 10, 32_768,
                                            100_480_507)
    assert (m["topk"], m["topk_every"], m["topk_rank"]) == (
        100, 1, "before_update")
    q = m["queries_per_step"]
    assert q in (64, 128, 256, 512)
    # The cut, written down: the published ratio, the rule, what fixed it.
    assert cfg["reduced"] == ["queries_per_step"]
    assert cfg["published"]["queries_per_step"] == m["local_batch"]
    cut = cfg["cut"]
    assert cut["queries_per_step"] == q and cut["rule"]
    assert cut["call_s_at_q"] <= 9.0 < cut["call_s_at_2q"] or q == 512
    assert cut["share_of_events_answered"] == q / m["local_batch"]
    assert {"topk", "exclusions", "queries"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == len(mf["guarantees"]) + 4
    assert len(next(c for c in bench["configs"]
                    if c["name"] == cfg["name"])["source"]) <= 200
    # mf-netflix's rows and the q user rows the queries read.
    assert cfg["rowops"]["rows_per_worker_step"] == (
        mf["rowops"]["rows_per_worker_step"] + q) == 4 * m["local_batch"] + q
    assert cfg["rowops"]["row_bytes"] == 4 * m["rank"]
    # The training is held to mf-netflix's limits; the lists to their own.
    assert {k: v for k, v in cfg["limits"].items()
            if "topk" not in k} == mf["limits"]
    assert set(cfg["limits"]) == set(mf["limits"]) | {
        f"{gap}.{t}" for gap in ("table_gap", "update_gap") for t in LISTS}
    assert all(cfg["limits"][f"{gap}.{t}"] == 0
               for gap in ("table_gap", "update_gap")
               for t in ("topk_query", "topk_counts"))
    assert all(0 < cfg["limits"][f"table_gap.{t}"] <= 1e-4
               for t in ("topk_scores", "topk_id_scores"))


# -- the runner's whole path -------------------------------------------------

@pytest.mark.parametrize("seed", [11, 2_147_484_123])
def test_cell_rehearsal_runs_the_runners_whole_path(seed):
    events = []
    ops.clear_routes()
    with one_device():
        result = runner.run_cell(
            tiny_cell(), seed=seed, seconds=0.3, trace=False,
            t_start=time.perf_counter(),
            emit=lambda event, **f: events.append(dict(event=event, **f)),
            out_dir="unused")
    compared = {e["number"]: e["value"] for e in events
                if e["event"] == "compared"}
    assert result["correct"], compared
    assert compared["examples"] == 0 and compared["feed"] == 0
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"setup_s", "examples_per_s"}
    assert set(compared) == set(tiny_cell()["config"]["limits"]) | {
        "programs_lowered_in_window"}
    assert [(r.route, r.rows, r.dim, r.ids, r.reason)
            for r in ops.routes_traced() if r.op == "tap"] == [
        ("tap.topk", NI, K, Q, "shards=1")]
    readings = next(e for e in events if e["event"] == "readings")
    assert readings["window_examples"] == N * readings["n"]


# -- the program against the reference ---------------------------------------

def test_program_agrees_with_the_reference_step_for_step(call):
    loaded, system, init, program, host, data_sum = call
    numbers, (ref, ref_loss, ref_n) = check.compare_call(
        system, loaded["config"], init, program, host, data_sum)
    ok, over = judged(loaded, numbers)
    assert ok, (over, numbers)
    assert int(system.plan.steps_per_epoch) == T
    assert host[0]["topk_ids"].shape == (T, 1, Q, K)
    # Every live query is answered with K distinct movies, best first.
    counts, scores = program["topk_counts"], program["topk_scores"]
    np.testing.assert_array_equal(counts, np.asarray(ref["topk_counts"]))
    assert (counts[:-1, 0] >= Q - 2).all() and not counts[-1].any()
    np.testing.assert_array_equal(counts[:, 1], K * counts[:, 0])
    assert (np.diff(scores, axis=-1) <= 0).all()
    # The lists are the reference's: same scores at every rank, and the
    # program's ids score the same by the reference's own tables.
    for t in ("topk_scores", "topk_id_scores"):
        np.testing.assert_allclose(program[t], np.asarray(ref[t]),
                                   atol=1e-6)
    np.testing.assert_array_equal(program["topk_query"],
                                  np.asarray(ref["topk_query"]))
    # The first step's lists are ranked by the SEEDED tables.
    u0 = np.asarray(init["user_factors"], np.float64)
    v0 = np.asarray(init["item_factors"], np.float64)
    asked = program["topk_query"][0].astype(int)
    want = np.sort(u0[asked] @ v0.T)[:, ::-1][:, :K]
    np.testing.assert_allclose(scores[0][asked >= 0], want[asked >= 0],
                               atol=1e-7)


def test_padding_queries_answer_the_sentinel_and_are_counted():
    """More queries a step than a step has live rows among its first ones
    (the last step has none at all): a padding row asks nothing, answers
    -1 and is counted, on both sides."""
    loaded, system, init, program, host, data_sum = first_call(
        queries_per_step=32)
    numbers, (ref, _, _) = check.compare_call(
        system, loaded["config"], init, program, host, data_sum)
    assert judged(loaded, numbers)[0], numbers
    h = host[0]
    padding = h["topk_query"][:, 0] == -1
    assert padding[-1].all() and padding[:-1].any() and not padding.all()
    assert (h["topk_ids"][:, 0][padding] == -1).all()
    assert (h["topk_ids"][:, 0][~padding] >= 0).all()
    np.testing.assert_array_equal(h["topk_padding"][:, 0], padding.sum(-1))
    np.testing.assert_array_equal(program["topk_counts"][-1], [0, 0, 0])
    assert not program["topk_scores"][padding].any()
    # The journal's counts for the call.
    from fps_tpu.models.recommendation import topk_journal
    assert topk_journal(h) == {"topk_answered": int((~padding).sum()),
                               "topk_padding": int(padding.sum())}


def test_bfloat16_control_fails(call):
    loaded, system, init, program, host, data_sum = call
    cfg = loaded["config"]
    ref, ref_loss, ref_n, _ = check.run_reference(system, cfg, init)
    lt, low_loss, low_n, low_feed = check.run_reference(
        system, cfg, init, dtype=jnp.bfloat16)
    low = check.compare(
        {k: np.asarray(v, np.float32) for k, v in lt.items()}, ref, init,
        low_loss, low_n, ref_loss, ref_n, low_feed,
        check.call_checksum(system, data_sum), system.examples_per_call)
    ok, over = judged(loaded, low)
    assert not ok
    assert {"table_gap.item_factors", "table_gap.topk_scores"} <= over


def test_ranking_after_the_update_fails(call):
    """The leak the tap had: lists ranked by the tables the step has just
    written. ``prequential.py``'s replay must fail a limit."""
    loaded, system, init, program, host, data_sum = call
    leak = copy.deepcopy(loaded["config"])
    leak["model"]["topk_rank"] = prequential.LEAK
    numbers, _ = check.compare_call(system, leak, init, program, host,
                                    data_sum)
    assert prequential.held(numbers, loaded["config"]["limits"])
    ok, over = judged(loaded, numbers)
    assert over == {"table_gap.topk_scores", "table_gap.topk_id_scores",
                    "update_gap.topk_scores", "update_gap.topk_id_scores"}
    # The training itself is untouched by where the lists are ranked.
    assert numbers["loss_gap"] <= loaded["config"]["limits"]["loss_gap"]


def _broken(call, **lists):
    """The comparison's numbers with the program's lists replaced."""
    loaded, system, init, program, host, data_sum = call
    saved = system._lists
    system._lists = dict(saved, **{
        k: f(saved[k].copy()) for k, f in lists.items()})
    try:
        numbers, _ = check.compare_call(
            system, loaded["config"], init, system.export(*system.state),
            host, data_sum)
    finally:
        system._lists = saved
    return judged(loaded, numbers)


def _stale(x):
    x[1:] = x[:-1].copy()
    return x


def _shifted(ids):
    return np.where(ids >= 0, (ids + 1) % NI, ids)


def _swapped(x):
    x[:, [0, 1]] = x[:, [1, 0]]
    return x


def _doubled(ids):
    ids[..., 1] = ids[..., 0]
    return ids


@pytest.mark.parametrize("lists,fails", [
    # No tap: sentinels everywhere, nothing answered.
    ({"topk_ids": lambda x: np.full_like(x, -1),
      "topk_query": lambda x: np.full_like(x, -1)},
     {"table_gap.topk_counts", "table_gap.topk_query"}),
    # Stale lists: every step answers with the step before's.
    ({"topk_ids": _stale, "topk_scores": _stale}, {"table_gap.topk_scores"}),
    # An id translated wrongly (off by one row): the scores still agree.
    ({"topk_ids": _shifted}, {"table_gap.topk_id_scores"}),
    # Two queries' lists (ids and scores) swapped, the users in place.
    ({"topk_ids": _swapped, "topk_scores": _swapped},
     {"table_gap.topk_scores"}),
    # The best id answered twice.
    ({"topk_ids": _doubled}, {"table_gap.topk_counts"}),
], ids=["no-tap", "stale", "id-shifted", "lists-swapped", "id-twice"])
def test_a_broken_timed_path_is_not_correct(call, lists, fails):
    ok, over = _broken(call, **lists)
    assert not ok and fails <= over, over


def test_neighbours_one_rounding_apart_may_swap(call):
    """Two ids whose scores differ by less than the limit, answered in the
    other order with their scores in place, pass: ties may fall either
    way."""
    loaded, system, init, program, host, data_sum = call
    scores = system.lists()["topk_scores"]
    gaps = scores[..., :-1] - scores[..., 1:]
    t, j, r = np.unravel_index(np.argmin(gaps), gaps.shape)
    limit = loaded["config"]["limits"]["table_gap.topk_id_scores"]
    if gaps[t, j, r] > 0.5 * limit * np.abs(scores).max():
        pytest.skip("no two neighbours that close at this size")

    def swap(ids):
        ids[t, j, [r, r + 1]] = ids[t, j, [r + 1, r]]
        return ids

    ok, over = _broken(call, topk_ids=swap)
    assert ok, over


def test_parent_without_the_prequential_tap_fails_as_it_is_built(
        monkeypatch):
    """The benchmark's files laid over a checkout whose program lacks the
    tap this PR brings: the new cell fails at once, before any data is
    placed, and the other cells' files still validate."""
    from fps_tpu.models import recommendation

    monkeypatch.delattr(recommendation, "topk_journal")
    spec.validate(spec.load_benchmark())
    loaded = tiny_cell()
    with pytest.raises(spec.SpecError, match="prequential top-K tap"):
        resolve.system_class(loaded["config"], loaded["traffic"])(
            loaded["config"], loaded["traffic"], {}, 1)


def test_a_trainer_whose_tap_ranks_after_the_update_is_not_correct(call):
    """The program itself put back to the parent's order (the tap run on
    the tables the step has written): the comparison says so."""
    from fps_tpu.core.driver import Trainer

    loaded, _, init, _, _, _ = call
    cfg, traffic = loaded["config"], loaded["traffic"]
    with one_device():
        data, data_sum = resolve.generator(cfg)(7, cfg["data"])
        system = resolve.system_class(cfg, traffic)(cfg, traffic, data, 7)
    tap = system.trainer.config.step_tap
    late = {}

    def remember(tables, batch, local_state, t):
        late["args"] = (batch, t)
        return tap(tables, batch, local_state, t)

    class Late(Trainer):
        def _apply_or_buffer(self, tables, bufs, t, pushes, hp=None):
            tables, bufs = super()._apply_or_buffer(tables, bufs, t,
                                                    pushes, hp)
            late["tables"] = tables
            return tables, bufs

        def _mount_tap(self, out, tapped):
            batch, t = late["args"]
            # local_state after the step is not in reach here; the movie
            # table alone, ranked after its push, is leak enough.
            return super()._mount_tap(out, jax.tree.map(
                self._gather_workers,
                tap(late["tables"], batch, late["local"], t)))

        def _compute_step(self, tables, snapshot, local_state, *a, **k):
            out = super()._compute_step(tables, snapshot, local_state,
                                        *a, **k)
            late["local"] = out[1]
            return out

    system.trainer.__class__ = Late
    system.trainer.config = dataclasses.replace(system.trainer.config,
                                                step_tap=remember)
    state, warm = window.queue_call(system, system.place(init))
    host = warm.wait().host
    numbers, _ = check.compare_call(system, cfg, init,
                                    system.export(*state), host, data_sum)
    ok, over = judged(loaded, numbers)
    assert not ok and "table_gap.topk_scores" in over
    assert numbers["loss_gap"] <= cfg["limits"]["loss_gap"]
