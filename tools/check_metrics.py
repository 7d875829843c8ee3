#!/usr/bin/env python
"""Static metric-name consistency check — thin wrapper (DEPRECATED entry
point; the logic now lives in the oryxlint ``metric-docs`` rule,
tools/oryxlint/checkers/consistency.py, and runs with the rest of the
static-analysis suite via ``python -m tools.oryxlint``).

Kept as a CLI because operators and older docs invoke it directly. The
collector functions (``code_metric_names``, ``doc_metric_names``) are
defined here and stay monkeypatchable as before — ``main`` reads them
through this module's globals. ``VALID_NAME`` and friends are read-only
re-exports of the rule's constants (rebinding them here does not change
the rule's behavior).

Contract (unchanged): every ``oryx_``-prefixed string literal under
``oryx_tpu/`` matches ``^oryx_[a-z0-9_]+$`` and has a reference-table
row in ``docs/observability.md`` (and vice versa); the label names the
docs must keep are present.

Exit status 0 = consistent; 1 = drift (each problem printed on stderr).
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PACKAGE = ROOT / "oryx_tpu"
DOC = ROOT / "docs" / "observability.md"

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.oryxlint.checkers import consistency as _rule  # noqa: E402

# re-exported for callers/tests that reach into this module
VALID_NAME = _rule.VALID_METRIC_NAME
CODE_LITERAL = _rule.METRIC_LITERAL
DOC_ROW = _rule.DOC_ROW
IGNORE = _rule.METRIC_IGNORE
REQUIRED_DOC_TOKENS = _rule.REQUIRED_DOC_TOKENS
REQUIRED_PERFATTR_FAMILIES = _rule.REQUIRED_PERFATTR_FAMILIES


def code_metric_names() -> dict[str, str]:
    """name -> first file using it, for every metric-shaped literal."""
    return {
        name: where
        for name, (where, _line) in _rule.code_metric_names(PACKAGE, ROOT).items()
    }


def doc_metric_names() -> set[str]:
    return _rule.doc_metric_names(DOC)


def vocabulary_problems() -> list[str]:
    problems = []
    doc_text = DOC.read_text(encoding="utf-8")
    for tok in REQUIRED_DOC_TOKENS:
        if tok not in doc_text:
            problems.append(
                f"{tok}: required label name missing from docs/observability.md"
            )
    return problems


def main() -> int:
    problems: list[str] = []
    if not DOC.exists():
        print(f"missing {DOC.relative_to(ROOT)}", file=sys.stderr)
        return 1
    problems.extend(
        _rule.metric_doc_problems(code_metric_names(), doc_metric_names())
    )
    problems.extend(vocabulary_problems())
    for p in problems:
        print(p, file=sys.stderr)
    if not problems:
        print("ok: metric names consistent with docs")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
