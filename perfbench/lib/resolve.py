"""Everything a configuration or a traffic file names is a file of its own,
found by that name — one mechanism for all of them:

| thing | named by | file | must hold |
|---|---|---|---|
| ``model`` | ``config.model.kind`` | ``models/<kind>.py`` | ``System`` |
| ``data`` | ``config.data.kind`` | ``datasets/<kind>.py`` | ``generate`` |
| ``reference`` | ``config.reference`` | ``lib/reference/<name>.py`` | ``init_tables``, ``make_step`` |
| ``entry`` | ``config.model.kind`` and ``traffic.entry`` | ``entries/<kind>/<entry>.py`` | ``System`` |
| ``reader`` | a metric file's ``reader``, where ``lib/readers.py`` has none of that name | ``readers/<name>.py`` | ``read`` |

A model kind's ``System`` drives the entry it names (``System.entry``);
a traffic file without an ``entry`` key means that one. Another entry
over the same model kind is a ``System`` in a file of its own under
``entries/<kind>/``. A name with no file raises :class:`SpecError` naming
the file to add, so a new kind of model, of data, of entry or of reader
(``read(ctx, params)``: ``lib/readers.py``) is new files and an entry in
``BENCHMARK.json``, never an edit here.
"""

from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# thing -> (directory under perfbench/, what its module must hold)
THINGS = {
    "model": (("models",), ("System",)),
    "data": (("datasets",), ("generate",)),
    "reference": (("lib", "reference"), ("init_tables", "make_step")),
    "entry": (("entries",), ("System",)),
    "reader": (("readers",), ("read",)),
}


class SpecError(ValueError):
    pass


def load(thing: str, name: str):
    """The module of ``thing`` called ``name`` (``a/b`` for a file in a
    directory of its own)."""
    where, holds = THINGS[thing]
    parts = where + tuple(str(name).split("/"))
    path = os.path.join("perfbench", *parts) + ".py"
    if not os.path.exists(os.path.join(os.path.dirname(HERE), path)):
        raise SpecError(f"no {thing} {name!r}: add {path} holding "
                        + ", ".join(holds))
    module = importlib.import_module("perfbench." + ".".join(parts))
    missing = [a for a in holds if not hasattr(module, a)]
    if missing:
        raise SpecError(f"{path} holds no {', '.join(missing)}")
    return module


def system_class(cfg: dict, traffic: dict):
    """The adapter that drives ``traffic``'s entry over ``cfg``'s kind of
    model."""
    kind = cfg["model"]["kind"]
    system = load("model", kind).System
    entry = traffic.get("entry", system.entry)
    if entry != system.entry:
        system = load("entry", f"{kind}/{entry}").System
    return system


def generator(cfg: dict):
    """``(seed, config.data) -> (host arrays, row checksum)``."""
    return load("data", cfg["data"]["kind"]).generate


def reference(cfg: dict):
    return load("reference", cfg["reference"])
