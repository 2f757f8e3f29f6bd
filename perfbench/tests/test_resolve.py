"""Kinds and entries are files found by name (``lib/resolve.py``).

What the tree names resolves; a name with no file says which file to add;
and the proof that a new kind of model, of data, of entry and of READER
needs no edit of a file the benchmark has: in a temporary copy of
``perfbench/`` they are ADDED with their reference, configuration, traffic
file, metric file and ``BENCHMARK.json`` entries, and the CPU rehearsal of that cell runs, is
``correct``, and its bfloat16 control is not.
"""

import copy
import filecmp
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

from perfbench.lib import readers, resolve, spec

BENCH = spec.load_benchmark()
CONFIGS = {c["name"]: spec._load(os.path.join(spec.ROOT, c["file"]))
           for c in BENCH["configs"]}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_configurations_kinds_and_reference_resolve(config):
    cfg = CONFIGS[config]
    assert callable(resolve.generator(cfg))
    ref = resolve.reference(cfg)
    assert callable(ref.init_tables) and callable(ref.make_step)
    model = resolve.load("model", cfg["model"]["kind"]).System
    for method in ("build", "place", "export", "call", "fed_chunks"):
        assert callable(getattr(model, method)), method


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cells_entry_resolves(cell):
    loaded = spec.load_cell(BENCH, cell)
    system = resolve.system_class(loaded["config"], loaded["traffic"])
    assert system.entry == loaded["traffic"].get("entry", system.entry)


@pytest.mark.parametrize("thing,name,file", [
    ("model", "no_such_model", "perfbench/models/no_such_model.py"),
    ("data", "no_such_data", "perfbench/datasets/no_such_data.py"),
    ("reference", "no_such_ref", "perfbench/lib/reference/no_such_ref.py"),
    ("entry", "online_mf/no_such_entry",
     "perfbench/entries/online_mf/no_such_entry.py"),
    ("reader", "no_such_reader", "perfbench/readers/no_such_reader.py"),
])
def test_a_missing_kind_names_the_file_to_add(thing, name, file):
    with pytest.raises(spec.SpecError, match=re.escape(f"add {file}")):
        resolve.load(thing, name)


@pytest.mark.parametrize("edit,file", [
    (lambda cfg, mix: cfg["model"].update(kind="wide_rows"),
     "perfbench/models/wide_rows.py"),
    (lambda cfg, mix: cfg["data"].update(kind="token_pairs"),
     "perfbench/datasets/token_pairs.py"),
    (lambda cfg, mix: cfg.update(reference="sgns"),
     "perfbench/lib/reference/sgns.py"),
    (lambda cfg, mix: mix.update(entry="fit_stream"),
     "perfbench/entries/online_mf/fit_stream.py"),
], ids=["model", "data", "reference", "entry"])
def test_validate_refuses_what_does_not_resolve(edit, file, monkeypatch):
    """``validate`` resolves every configuration's kinds and reference and
    every cell's entry as a run starts."""
    cfg = copy.deepcopy(CONFIGS["mf-netflix"])
    mix = spec.load_traffic("epochs")
    edit(cfg, mix)
    real_load, real_traffic = spec._load, spec.load_traffic
    monkeypatch.setattr(spec, "_load", lambda path: (
        cfg if path.endswith("configs/mf-netflix.json") else real_load(path)))
    monkeypatch.setattr(spec, "load_traffic", lambda name: (
        mix if name == "epochs" else real_traffic(name)))
    with pytest.raises(spec.SpecError, match=re.escape(f"add {file}")):
        spec.validate(BENCH)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_metrics_file_names_a_reader(metric):
    body = spec._load(os.path.join(spec.HERE, "metrics", metric + ".json"))
    assert callable(readers.reader(body["reader"]))
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert (body["name"], body["unit"], body["layer"]) == (
        entry["name"], entry["unit"], entry["layer"])
    assert entry["workloads"], "every per-layer metric lists its cells"


def test_validate_refuses_a_metric_file_with_no_reader(monkeypatch):
    real = spec._load
    monkeypatch.setattr(spec, "_load", lambda path: (
        dict(real(path), reader="no_such_reader")
        if path.endswith("device.idle_share.json") else real(path)))
    with pytest.raises(spec.SpecError, match=re.escape(
            "no reader 'no_such_reader': add perfbench/readers/"
            "no_such_reader.py holding read")):
        spec.validate(BENCH)


def test_the_harness_names_no_configuration_cell_or_kind():
    """``lib/*.py``, ``run.py`` and ``control.py`` hold no table of kinds
    and no string that names a configuration, a cell, a kind of model or of
    data, or a reference: all of those are found by name."""
    names = {c["name"] for c in BENCH["configs"]}
    names |= {w["name"] for w in BENCH["workloads"]}
    for cfg in CONFIGS.values():
        names |= {cfg["model"]["kind"], cfg["data"]["kind"],
                  cfg["reference"]}
    files = glob.glob(os.path.join(spec.HERE, "lib", "*.py")) + [
        os.path.join(spec.HERE, f) for f in ("run.py", "control.py")]
    assert len(files) > 8
    for path in files:
        with open(path) as f:
            text = f.read()
        found = sorted(n for n in names
                       if re.search(rf"(?<![\w.\-]){re.escape(n)}(?![\w\-])",
                                    text))
        assert not found, (path, found)
        assert not re.search(r"^KINDS\b", text, re.M), path


# -- a new kind of model, of data and of entry, as files only ---------------

TOY_MODEL = '''
"""Model kind ``toy_mf``: the program's online MF under another name."""
from perfbench.lib import systems
from perfbench.lib.systems import to_physical


class System(systems.System):
    loss_key = "se"

    def build(self, data, dataset):
        from fps_tpu.models.matrix_factorization import MFConfig, online_mf

        m = self.cfg["model"]
        self.trainer, self.store = online_mf(
            self.mesh, MFConfig(
                num_users=m["num_users"], num_items=m["num_items"],
                rank=m["rank"], learning_rate=m["learning_rate"],
                reg=m["reg"], init_min=m["init_min"],
                init_max=m["init_max"]), combine=m["combine"])
        self.plan = self._plan(dataset, m["local_batch"], m["route_key"])

    def place(self, init):
        tables, local_state = self._shells()
        tables = dict(tables, item_factors=to_physical(
            init["item_factors"], self.store.num_shards,
            tables["item_factors"]))
        return tables, to_physical(init["user_factors"], self.W, local_state)

    def export(self, tables, local_state):
        self.store.tables = dict(tables)
        return {"item_factors": self.store.dump_model("item_factors")[1],
                "user_factors": self.trainer.logic.export_local_state(
                    local_state)}
'''

TOY_ENTRY = '''
"""Entry ``blocking`` over ``toy_mf``: a call that returns only when its
work is done, as a streamed entry's would."""
from perfbench.models import toy_mf

CALLS = []


class System(toy_mf.System):
    entry = "blocking"

    def call(self, tables, local_state):
        import jax

        out = jax.block_until_ready(super().call(tables, local_state))
        CALLS.append(self.calls)
        return out
'''

TOY_DATA = '''
"""Data kind ``toy_ratings``: made on the host, with numpy."""
import numpy as np


def generate(seed, d):
    import jax.numpy as jnp

    from perfbench.lib.check import row_checksum

    rng = np.random.default_rng(seed)
    n = d["num_ratings"]
    data = {"user": rng.integers(0, d["num_users"], n).astype(np.int32),
            "item": rng.integers(0, d["num_items"], n).astype(np.int32),
            "rating": rng.normal(0.0, 0.5, n).astype(np.float32)}
    batch = dict({k: jnp.asarray(v) for k, v in data.items()},
                 weight=jnp.ones(n, jnp.float32))
    return data, int(row_checksum(batch, sorted(data)))
'''

TOY_READER = '''
"""Reader ``span_field_total``: the sum of one FIELD of a span's events
over one part of the run (what ``program_span_events`` is for: a span's
steps, bytes or depth, not its length)."""


def read(ctx, p):
    events = (ctx.get("program_span_events") or {}).get(
        p["span"], {}).get(p.get("part", "window"), [])
    vals = [e[p["field"]] for e in events if p["field"] in e]
    return float(sum(vals)) * p.get("scale", 1.0) if vals else None
'''

REHEARSE = '''
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from perfbench.lib import check, resolve, runner, spec

assert spec.ROOT == os.getcwd(), (spec.ROOT, os.getcwd())
bench = spec.load_benchmark()
spec.validate(bench)
loaded = spec.load_cell(bench, "toy.blocking")
cfg, traffic = loaded["config"], loaded["traffic"]
real = jax.devices
jax.devices = lambda *a: real(*a)[:1]
events = []
result = runner.run_cell(
    loaded, seed=2147483659, seconds=0.2, trace=False,
    t_start=time.perf_counter(),
    emit=lambda event, **f: events.append(dict(event=event, **f)),
    out_dir="unused")
from perfbench.entries.toy_mf import blocking
data, _ = resolve.generator(cfg)(5, cfg["data"])
system = resolve.system_class(cfg, traffic)(cfg, traffic, data, 5)
init = resolve.reference(cfg).init_tables(5, cfg)
ref, loss, n, feed = check.run_reference(system, cfg, init)
low, low_loss, low_n, _ = check.run_reference(system, cfg, init,
                                              dtype=jnp.bfloat16)
control_ok, _ = check.judge(check.compare(
    {k: np.asarray(v, np.float32) for k, v in low.items()}, ref, init,
    low_loss, low_n, loss, n, feed, feed, system.examples_per_call),
    cfg["limits"])
from perfbench.lib import readers
toy = {"toy.chunk_steps": loaded["readers"]["toy.chunk_steps"]}
events = {"chunk": {"window": [{"span": "chunk", "t0": 1.0, "t1": 2.0,
                                "steps": 513},
                               {"span": "chunk", "t0": 2.0, "t1": 3.0,
                                "steps": 512}], "setup": [], "after": []}}
print(json.dumps({
    "toy_reader": [readers.read_all(toy, {"program_span_events": events}),
                   readers.read_all(toy, {})],
    "result": result, "control_ok": control_ok,
    "system": [type(system).__module__, type(system).entry],
    "entry_calls": len(blocking.CALLS)}))
'''


def _snapshot(root):
    return {os.path.relpath(os.path.join(d, f), root)
            for d, _, fs in os.walk(root) for f in fs
            if "__pycache__" not in d}


@pytest.fixture(scope="module")
def toy_copy(tmp_path_factory):
    """A copy of the benchmark with the toy cell ADDED. Returns ``(root of
    the copy, the files that were there before)``."""
    root = str(tmp_path_factory.mktemp("copy"))
    shutil.copytree(spec.HERE, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = _snapshot(root)
    here = os.path.join(root, "perfbench")

    def put(rel, text):
        path = os.path.join(here, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        assert not os.path.exists(path), rel
        with open(path, "w") as f:
            f.write(textwrap.dedent(text).lstrip())

    put("models/toy_mf.py", TOY_MODEL)
    put("entries/toy_mf/blocking.py", TOY_ENTRY)
    put("datasets/toy_ratings.py", TOY_DATA)
    shutil.copy(os.path.join(here, "lib", "reference", "mf_sgd.py"),
                os.path.join(here, "lib", "reference", "toy_sgd.py"))
    cfg = copy.deepcopy(CONFIGS["mf-netflix"])
    cfg.update(name="toy", reference="toy_sgd", quality=None)
    cfg["model"].update(kind="toy_mf", num_users=601, num_items=53,
                        local_batch=256)
    cfg["data"] = {"kind": "toy_ratings", "num_users": 601, "num_items": 53,
                   "num_ratings": 20011}
    cfg["limits"] = {k: (v if v == 0 else 2e-3)
                     for k, v in cfg["limits"].items()}
    put("configs/toy.json", json.dumps(cfg))
    put("traffic/toy-blocking.json", json.dumps(
        {"name": "toy-blocking", "like": "epochs", "entry": "blocking"}))
    put("readers/span_field_total.py", TOY_READER)
    put("metrics/toy.chunk_steps.json", json.dumps(
        {"name": "toy.chunk_steps", "unit": "count", "layer": "step driver",
         "what": "steps the window's chunks say they held",
         "reader": "span_field_total",
         "params": {"span": "chunk", "field": "steps"}}))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": "toy", "source": "none: a test's toy", "reduced": [],
        "file": "perfbench/configs/toy.json", "why": "proves the resolver"})
    bench["workloads"].append({
        "name": "toy.blocking", "config": "toy", "traffic": "toy-blocking",
        "chips": 1, "why": "a new kind of model, of data and of entry"})
    next(m for m in bench["per_layer"]
         if m["name"] == "driver.dispatch_ms")["workloads"].append(
             "toy.blocking")
    bench["per_layer"].append({
        "name": "toy.chunk_steps", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "step driver",
        "moves": "examples_per_s", "workloads": ["toy.blocking"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


def test_a_new_kind_of_model_data_and_entry_is_added_files_only(toy_copy):
    root, before = toy_copy
    added = _snapshot(root) - before
    assert added == {
        "perfbench/models/toy_mf.py", "perfbench/entries/toy_mf/blocking.py",
        "perfbench/datasets/toy_ratings.py", "perfbench/configs/toy.json",
        "perfbench/lib/reference/toy_sgd.py",
        "perfbench/traffic/toy-blocking.json",
        "perfbench/readers/span_field_total.py",
        "perfbench/metrics/toy.chunk_steps.json"}
    # No file that was there differs, BENCHMARK.json but by additions.
    for rel in sorted(before - {"BENCHMARK.json"}):
        assert filecmp.cmp(os.path.join(root, rel),
                           os.path.join(spec.ROOT, rel), shallow=False), rel
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for key, was in BENCH.items():
        if not isinstance(was, list) or key in ("command", "paths"):
            assert bench[key] == was, key
            continue
        for old, new in zip(was, bench[key]):
            assert {k: v for k, v in new.items() if k != "workloads"} == {
                k: v for k, v in old.items() if k != "workloads"}
            assert new.get("workloads", [])[:len(old.get("workloads", []))] \
                == old.get("workloads", [])


def test_the_added_cell_rehearses_correct_and_its_control_does_not(toy_copy):
    root, _ = toy_copy
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, spec.ROOT]))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", REHEARSE], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["result"]["correct"] is True, out["result"]["compared"]
    assert out["result"]["failed"] == 0
    assert out["result"]["attempted"] >= 2
    assert out["control_ok"] is False
    assert out["system"] == ["perfbench.entries.toy_mf.blocking", "blocking"]
    # The added reader was found by its name, read a span event's FIELD,
    # and with nothing to read said nothing.
    assert out["toy_reader"] == [
        {"toy.chunk_steps": {"value": 1025.0, "unit": "count"}}, {}]
    # The window drove the entry's own call (the warm-up call and every
    # timed one), not the model kind's.
    assert out["entry_calls"] == out["result"]["attempted"]
