#!/bin/sh
# Pre-commit gate: git-scoped oryxlint (grouped by rule, with severity
# and fix hints from the --json schema) plus the ruff lint/format gate
# when ruff is installed.
#
# Install:  ln -s ../../tools/precommit.sh .git/hooks/pre-commit
# Run ad hoc:  tools/precommit.sh
#
# Exit status: 0 clean, 1 findings (commit blocked), 2 internal error.
set -u

root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$root" || exit 2

tmp="$(mktemp)"
errs="$(mktemp)"
trap 'rm -f "$tmp" "$errs"' EXIT
python -m tools.oryxlint --changed --json >"$tmp" 2>"$errs"
lint_rc=$?
if [ ! -s "$tmp" ] || [ "$lint_rc" -gt 1 ]; then
    echo "precommit: oryxlint internal error (rc=$lint_rc)" >&2
    cat "$errs" >&2
    exit 2
fi

ORYXLINT_JSON="$tmp" python - <<'PY'
import json
import os
import sys

try:
    with open(os.environ["ORYXLINT_JSON"], encoding="utf-8") as fh:
        doc = json.load(fh)
except (OSError, json.JSONDecodeError) as e:
    print(f"precommit: unparseable oryxlint --json output ({e})",
          file=sys.stderr)
    sys.exit(3)  # internal error, not findings
findings = doc.get("findings", [])
by_rule: dict = {}
for f in findings:
    by_rule.setdefault(f["rule"], []).append(f)
for rule in sorted(by_rule):
    fs = by_rule[rule]
    sev = fs[0].get("severity", "error")
    print(f"[{sev}] {rule} ({len(fs)} finding(s))")
    for f in fs:
        print(f"  {f['path']}:{f['line']}: {f['message']}")
    hint = fs[0].get("fix_hint")
    if hint:
        print(f"  fix: {hint}")
if findings:
    print(f"\nprecommit: {len(findings)} oryxlint finding(s); commit blocked")
    sys.exit(1)
print(f"precommit: oryxlint clean ({len(doc.get('suppressed', []))} suppressed)")
PY
group_rc=$?
if [ "$group_rc" -eq 3 ]; then
    cat "$errs" >&2
    exit 2
fi
[ "$group_rc" -ne 0 ] && exit 1

# ruff is optional in the minimal container; the gate runs wherever it
# exists (dev laptops, CI images with the full toolchain)
if python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check oryx_tpu tools chip_smoke.py || exit 1
    python -m ruff format --check oryx_tpu tools chip_smoke.py || exit 1
else
    echo "precommit: ruff not installed; skipping lint/format gate"
fi

exit 0
