"""BENCHMARK.json and the data files it names, loaded and checked.

The runner finds everything by name:

* ``configs[].file``            one configuration (perfbench/configs/),
* ``perfbench/traffic/<traffic>.json``   one traffic mix,
* ``perfbench/metrics/<metric>.json``    one per-layer metric's reader,
* what a configuration or a traffic mix names in turn (its kind of model,
  of data, its reference, its entry): ``resolve.py``.

:func:`validate` is the check the runner makes as it starts: names and
units hold only the characters the contract allows, every per-layer
metric moves an end-to-end metric that each of its cells reports and
names a reader there is (in ``lib/readers.py`` or a file of its own),
every configuration has a cell, every file named exists, and every
configuration's kinds and reference and every cell's entry resolve.
"""

from __future__ import annotations

import json
import os
import re

from perfbench.lib import readers, resolve
from perfbench.lib.resolve import HERE, SpecError  # noqa: F401 (callers')

ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def load_traffic(name: str) -> dict:
    """A traffic mix by name. A file with a ``like`` key is the mix it
    names with its own keys laid over it: the contract admits a pair of
    configuration and traffic once, so the same mix on another number of
    chips stands under a name of its own without being a copy."""
    mix = _load(os.path.join(HERE, "traffic", name + ".json"))
    if "like" in mix:
        mix = dict(load_traffic(mix["like"]), **mix)
    return mix


def cells_of(metric: dict, bench: dict) -> list:
    """The cells a metric is reported in: its ``workloads`` key, or every
    cell."""
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def load_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one run needs, by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    per_layer = [m for m in bench["per_layer"]
                 if workload in cells_of(m, bench)]
    return {
        "cell": cell,
        "config": _load(os.path.join(root, config["file"])),
        "traffic": load_traffic(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in cells_of(m, bench)],
        "per_layer": per_layer,
        "readers": {m["name"]: _load(os.path.join(
            HERE, "metrics", m["name"] + ".json")) for m in per_layer},
    }


def validate(bench: dict, root: str = ROOT) -> None:
    """Raise :class:`SpecError` on the first thing that does not hold."""
    def name_ok(s, what):
        if not isinstance(s, str) or not _NAME.match(s):
            raise SpecError(f"{what} {s!r}: a name holds 1-64 letters, "
                            "digits, '_', '.', '-'")

    def unit_ok(s, what):
        if not isinstance(s, str) or not _UNIT.match(s):
            raise SpecError(f"{what} unit {s!r}: 1-16 letters, digits, "
                            "'_', '/', '%', '.', '-'")

    cells = {}
    for w in bench["workloads"]:
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"{w['name']}: chips must be 1 or 4")
        cells[w["name"]] = w
        path = os.path.join(HERE, "traffic", w["traffic"] + ".json")
        if not os.path.exists(path):
            raise SpecError(f"{w['name']}: no traffic file {path}")
        like = _load(path).get("like")
        if like is not None and not os.path.exists(
                os.path.join(HERE, "traffic", str(like) + ".json")):
            raise SpecError(f"{path}: no traffic file for like={like!r}")
    configs = {}
    for c in bench["configs"]:
        name_ok(c["name"], "config")
        for k in c["reduced"]:
            name_ok(k, f"{c['name']} reduced key")
        configs[c["name"]] = c
        path = os.path.join(root, c["file"])
        if not os.path.exists(path):
            raise SpecError(f"config {c['name']}: no file {c['file']}")
        body = _load(path)
        name_ok(body.get("name"), f"{c['file']} name")
        resolve.generator(body)
        resolve.reference(body)
        for w in cells.values():
            if w["config"] == c["name"]:
                resolve.system_class(body, load_traffic(w["traffic"]))
        if not body.get("limits"):
            raise SpecError(f"config {c['name']}: no limits for the "
                            "comparison with its reference")
        if not any(w["config"] == c["name"] for w in cells.values()):
            raise SpecError(f"config {c['name']} has no cell")
    for w in cells.values():
        if w["config"] not in configs:
            raise SpecError(f"{w['name']}: unknown config {w['config']!r}")
    e2e = {}
    for m in bench["end_to_end"]:
        name_ok(m["name"], "end-to-end metric")
        unit_ok(m["unit"], m["name"])
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"{m['name']}: an end-to-end metric is read by "
                            "the benchmark: host_clock or device_trace")
        e2e[m["name"]] = set(cells_of(m, bench))
    if "setup_s" not in e2e:
        raise SpecError("no setup_s among the end-to-end metrics")
    for m in bench["per_layer"]:
        name_ok(m["name"], "per-layer metric")
        unit_ok(m["unit"], m["name"])
        if m["source"] not in _SOURCES:
            raise SpecError(f"{m['name']}: unknown source {m['source']!r}")
        if m["moves"] not in e2e:
            raise SpecError(f"{m['name']} moves {m['moves']!r}, which is "
                            "not an end-to-end metric")
        for w in cells_of(m, bench):
            if w not in cells:
                raise SpecError(f"{m['name']}: unknown workload {w!r}")
            if w not in e2e[m["moves"]]:
                raise SpecError(f"{m['name']} is reported in {w}, which "
                                f"does not report {m['moves']}")
        path = os.path.join(HERE, "metrics", m["name"] + ".json")
        if not os.path.exists(path):
            raise SpecError(f"{m['name']}: no reader file {path}")
        reader = _load(path)
        name_ok(reader.get("name"), f"{path} name")
        if reader["name"] != m["name"] or reader.get("unit") != m["unit"]:
            raise SpecError(f"{path}: name/unit differ from BENCHMARK.json")
        try:
            readers.reader(str(reader.get("reader")))
        except SpecError as e:
            raise SpecError(f"{path}: {e}; lib/readers.py has "
                            f"{sorted(readers.READERS)}") from None
    for w in cells:
        if not any(w in e2e[m] for m in e2e if m != "setup_s"):
            raise SpecError(f"{w} reports no end-to-end metric but setup_s")
        if not any(w in cells_of(m, bench) for m in bench["per_layer"]):
            raise SpecError(f"{w} reports no per-layer metric")
