"""The documents that tell a reader how to build, run and extend the tree
name only files the tree has. PERF.md, ROADMAP.md and CHANGES.md are
history (they name what was deleted, on purpose) and are not held to it."""

from __future__ import annotations

import os
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

DOCUMENTS = [
    "README.md",
    *sorted(f"docs/{p.name}" for p in (ROOT / "docs").glob("*.md")),
    ".claude/skills/verify/SKILL.md",
]

# a back-ticked token that is a plain path (no placeholder, glob or brace)
# ending in a source, data, document, script or config suffix
PATH_TOKEN = re.compile(r"`([\w./-]+\.(?:py|jsonl|json|md|sh|conf))`")

# the reference project's launcher and the user's own config file: named
# by the migration notes, never part of this tree
NOT_OURS = {"oryx-run.sh", "oryx.conf"}

# what building, testing and running leave behind is not the tree
_PRUNED = {"__pycache__", "chiprun_out"}


@pytest.fixture(scope="module")
def tree_files() -> list[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(ROOT):
        rel = pathlib.Path(dirpath).relative_to(ROOT)
        dirnames[:] = [
            d for d in dirnames
            if d not in _PRUNED and (not d.startswith(".") or d == ".claude")
        ]
        out.extend((rel / f).as_posix() for f in filenames)
    return out


@pytest.mark.parametrize("document", DOCUMENTS)
def test_paths_a_document_names_exist(document, tree_files):
    text = (ROOT / document).read_text(encoding="utf-8")
    missing = []
    for m in PATH_TOKEN.finditer(text):
        token = m.group(1)
        if token in NOT_OURS or token.startswith("/"):
            continue  # not ours, or an absolute path outside the checkout
        # by its path from the root, or as the tail of one (`ops/als.py`,
        # `reference.conf`)
        if not any(f == token or f.endswith("/" + token) for f in tree_files):
            line = text.count("\n", 0, m.start()) + 1
            missing.append(f"{document}:{line}: `{token}`")
    assert not missing, "names a file the tree does not have:\n" + "\n".join(missing)
