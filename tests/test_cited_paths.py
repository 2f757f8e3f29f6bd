"""Every repo path a document, a tool or a module of the package names is
in the tree.

A deleted script or record leaves sentences that point at nothing; a
reader follows one and loses the thread. One case per citing file: the
paths it names (``tools/<x>.py``, ``docs/<x>.md``, ``scratch/<x>.py``, a
script at the root, a root ``*.md`` / ``*.json`` record) must exist, a
glob such as ``AUDIT_r*.json`` counting as found when it matches a file.
``perfbench/`` is not in scope (only a ``benchmark`` PR may edit it).
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A path under one of the directories a citation may point into.
_IN_DIR = re.compile(r"(?<![\w/.\-])((?:tools|docs|scratch)/[\w.*\-]+\.(?:py|md))\b")
# A script at the root of the tree.
_ROOT_SCRIPT = re.compile(r"(?<![\w/.\-])((?:bench|chip_smoke|__graft_entry__)\.py)\b")
# A record at the root: upper-case name, optionally a round (``_r10``,
# ``_r*``). Documents under ``docs/`` name each other the same way.
_RECORD = re.compile(r"(?<![\w/.\-])([A-Z][A-Z_]+(?:_r[\d*]+)?\.(?:md|jsonl?))\b")
# An option's argument in a usage line (``--out CERTS.json``) or an
# argparse ``metavar`` is a placeholder, not a path.
_PLACEHOLDER = re.compile(r"--[\w\-]+[ =]\S+|metavar=\S+")
# Each pattern with the directories its matches are looked up in: a bare
# record is the root's, or a sibling under docs/.
_CITATIONS = ((_IN_DIR, ("",)), (_ROOT_SCRIPT, ("",)), (_RECORD, ("", "docs")))


def _citing_files():
    pats = ("README.md", "docs/*.md", "chip_smoke.py", "tools/*.py",
            "fps_tpu/**/*.py")
    found = []
    for pat in pats:
        found += glob.glob(os.path.join(ROOT, pat), recursive=True)
    return sorted(os.path.relpath(p, ROOT) for p in found)


@pytest.mark.parametrize("citing", _citing_files())
def test_cited_paths_exist(citing):
    with open(os.path.join(ROOT, citing), encoding="utf-8") as f:
        text = _PLACEHOLDER.sub("", f.read())
    missing = {m for pat, dirs in _CITATIONS for m in pat.findall(text)
               if not any(glob.glob(os.path.join(ROOT, d, m)) for d in dirs)}
    assert not missing, f"{citing} names paths not in the tree: {sorted(missing)}"
