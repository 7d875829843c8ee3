#!/usr/bin/env python
"""Bench ratchet: fail when a fresh bench run regresses a locked metric.

The ROADMAP's performance claims (kernel MFU, serving qps, latency) were
previously enforced by a human reading two JSON artifacts side by side.
This tool makes the claim a ratchet: ``BASELINE_RATCHET.json`` locks, per
metric, the best honestly-measured value, the direction that counts as
progress (``up`` for qps/MFU, ``down`` for latency), a noise tolerance,
and the platform the number was measured on. A run that slips past
tolerance in the wrong direction — or that silently stops emitting a
ratcheted metric at all — exits non-zero with a per-metric table.

    python tools/check_bench.py --current BENCH_rNN.json
    python bench.py | python tools/check_bench.py --current -
    python tools/check_bench.py --run          # runs bench.py itself

Metrics locked for a different platform than the current run's are
reported as skipped, not failed: a CPU fallback run must not trip the TPU
ratchet (and cannot satisfy it either — the TPU claim stays unproven
until the next TPU window re-measures it).

Ratcheting UP the baseline is a deliberate git edit of
BASELINE_RATCHET.json riding the PR that earned the number — never
automatic, so a lucky run can't quietly raise the bar for everyone.
``tools/check_metrics.py`` statically verifies every ratcheted metric
name still exists in bench.py's output vocabulary.

A row may carry ``"pending": true``: the baseline was set AHEAD of its
first banked measurement (a PR that rebuilt the thing being measured and
re-declared the bar, e.g. the gen-2 fused kernel retightening
pallas_speedup before a TPU window could run it). Pending rows render
loudly in the table but never fail the check — the committed ratchet
must keep accepting the previously banked artifacts. The PR that banks
the first artifact measuring a pending row REMOVES the flag (and
corrects the baseline to the measured number), at which point the row
enforces like any other.

A pending row also records ``"pending_since": <round>`` — the bench
round at which the bar was declared. ``stale_pending_problems`` (run by
this CLI and by the oryxlint ``bench-ratchet`` rule) fails a pending row
once a banked artifact of the row's platform from that round or later
MEASURES the metric: the flag has outlived its purpose, and keeping it
would let the bar float unenforced forever.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "BASELINE_RATCHET.json")


def load_baseline(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    metrics = doc.get("metrics")
    if not isinstance(metrics, list):
        raise SystemExit(f"{path}: expected a top-level 'metrics' list")
    for m in metrics:
        for field in ("name", "baseline", "direction"):
            if field not in m:
                raise SystemExit(f"{path}: metric entry missing '{field}': {m}")
        if m["direction"] not in ("up", "down"):
            raise SystemExit(
                f"{path}: direction must be 'up' or 'down': {m['name']}"
            )
    return metrics


def extract_current(raw: str) -> dict:
    """The run's metric dict from bench-style output: prefer the full
    `"detail": true` line, else the last parseable JSON object line (the
    compact final), else a whole-document JSON object (a saved artifact,
    possibly wrapped as {final, detail})."""
    try:
        doc = json.loads(raw)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        # a saved artifact: either the metric dict itself, or a
        # {final, detail} wrapper — detail carries the full vocabulary
        if isinstance(doc.get("detail"), dict):
            return doc["detail"]
        return doc
    detail = final = None
    for line in raw.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(row, dict):
            continue
        if row.get("detail") is True:
            detail = row
        final = row
    if detail is not None:
        return detail
    if final is not None:
        # a banked window artifact: one JSON object wrapping final/detail
        if "detail" in final and isinstance(final.get("detail"), dict):
            return final["detail"]
        return final
    raise SystemExit("no parseable JSON metrics found in the current input")


def banked_artifacts(root: str = ROOT) -> list[tuple[int, str, dict]]:
    """(round, platform, metric dict) for every banked bench artifact:
    ``BENCH_TPU_WINDOW_r{N}.json`` and ``BENCH_r{N}.json``. Unparseable
    files are skipped — a stale-pending verdict must rest on artifacts
    that actually decode."""
    import glob
    import re

    out: list[tuple[int, str, dict]] = []
    for path in sorted(
        glob.glob(os.path.join(root, "BENCH_TPU_WINDOW_r*.json"))
        + glob.glob(os.path.join(root, "BENCH_r*.json"))
    ):
        m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        # saved artifact shapes: some wrap {final, detail} (detail carries
        # the full vocabulary; final alone still counts), and the driver's
        # round artifacts nest the same dicts under "parsed"
        parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else {}
        current: dict = {}
        for part in (
            doc, parsed,
            doc.get("final"), doc.get("detail"),
            parsed.get("final"), parsed.get("detail"),
        ):
            if isinstance(part, dict):
                current.update({
                    k: v for k, v in part.items() if not isinstance(v, dict)
                })
        platform = current.get("platform")
        if isinstance(platform, str):
            out.append((int(m.group(1)), platform, current))
    return out


def stale_pending_problems(
    metrics: list[dict], root: str = ROOT
) -> list[str]:
    """Pending rows whose flag has outlived a banked artifact of the right
    platform: an artifact from the row's declaration round or later
    measures the metric, so the PR that banked it should have removed the
    flag and locked the measured number. Rows without ``pending_since``
    are held to the strict reading (any measuring artifact counts)."""
    problems: list[str] = []
    artifacts = None
    for m in metrics:
        if not m.get("pending") or not m.get("name"):
            # nameless rows are already reported by the vocabulary check;
            # crashing here would turn one malformed row into a traceback
            continue
        if artifacts is None:
            artifacts = banked_artifacts(root)
        try:
            since = int(m.get("pending_since", 0))
        except (TypeError, ValueError):
            since = 0  # unparseable: strict reading, any artifact counts
        for rnd, platform, current in artifacts:
            if rnd < since:
                continue
            if m.get("platform") and platform != m["platform"]:
                continue
            if current.get(m["name"]) is None:
                continue
            problems.append(
                f"{m['name']}: pending (since round {since or '?'}) but the "
                f"banked round-{rnd} {platform} artifact measures it "
                f"({current.get(m['name'])!r}) — remove the pending flag "
                "and lock the measured baseline"
            )
            break
    return problems


def check(
    metrics: list[dict], current: dict
) -> tuple[list[tuple], int, int]:
    """Returns (table rows, n_failed, n_checked). Row: (name, baseline,
    got, direction, tolerance, verdict)."""
    platform = current.get("platform")
    rows: list[tuple] = []
    failed = checked = 0
    for m in metrics:
        name, base, direction = m["name"], m["baseline"], m["direction"]
        tol = float(m.get("tolerance", 0.0))
        want_platform = m.get("platform")
        if want_platform and platform and want_platform != platform:
            rows.append((name, base, "-", direction, tol,
                         f"SKIP (locked for {want_platform}, run is {platform})"))
            continue
        if m.get("pending"):
            # baseline declared ahead of its first banked measurement:
            # report, never fail — the flag is removed by the PR that
            # banks an artifact measuring it
            got = current.get(name)
            rows.append((
                name, base, got if got is not None else "-", direction, tol,
                "PENDING (baseline ahead of first banked measurement; "
                "remove the flag when one lands)",
            ))
            continue
        checked += 1
        got = current.get(name)
        if got is None:
            failed += 1
            rows.append((name, base, "MISSING", direction, tol,
                         "FAIL (metric absent from the run)"))
            continue
        try:
            got_f = float(got)
        except (TypeError, ValueError):
            failed += 1
            rows.append((name, base, repr(got), direction, tol,
                         "FAIL (not numeric)"))
            continue
        if direction == "up":
            floor = base * (1.0 - tol)
            ok = got_f >= floor
            bound = f">= {floor:g}"
        else:
            ceil = base * (1.0 + tol)
            ok = got_f <= ceil
            bound = f"<= {ceil:g}"
        if not ok:
            failed += 1
        rows.append((
            name, base, got_f, direction, tol,
            "ok" if ok else f"FAIL (want {bound})",
        ))
    return rows, failed, checked


def render_table(rows: list[tuple]) -> str:
    headers = ("metric", "baseline", "current", "dir", "tol", "verdict")
    table = [headers] + [
        tuple(str(c) for c in row) for row in rows
    ]
    widths = [max(len(r[i]) for r in table) for i in range(len(headers))]
    lines = []
    for i, row in enumerate(table):
        lines.append("  ".join(c.ljust(widths[j]) for j, c in enumerate(row)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--baseline", default=DEFAULT_BASELINE,
        help="ratchet file (default: repo BASELINE_RATCHET.json)",
    )
    ap.add_argument(
        "--current", default=None,
        help="bench output to check: a JSON artifact path, or '-' for stdin",
    )
    ap.add_argument(
        "--run", action="store_true",
        help="run `python bench.py` fresh and check its output",
    )
    args = ap.parse_args(argv)

    metrics = load_baseline(args.baseline)
    if args.run:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py")],
            capture_output=True, text=True,
        )
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            print(f"bench.py exited {proc.returncode}", file=sys.stderr)
            return 2
        raw = proc.stdout
    elif args.current == "-":
        raw = sys.stdin.read()
    elif args.current:
        with open(args.current, encoding="utf-8") as f:
            raw = f.read()
    else:
        ap.error("one of --current or --run is required")
        return 2  # unreachable; argparse exits

    current = extract_current(raw)
    # banked artifacts live next to the ratchet file: a tmp-dir baseline
    # (tests, ad-hoc experiments) is judged against its own directory,
    # never against this repo's banked windows
    stale = stale_pending_problems(
        metrics, root=os.path.dirname(os.path.abspath(args.baseline)) or ROOT
    )
    rows, failed, checked = check(metrics, current)
    print(render_table(rows))
    if stale:
        for p in stale:
            print(p, file=sys.stderr)
        print(
            f"\nRATCHET FAILED: {len(stale)} pending row(s) outlived a "
            "banked artifact that measures them", file=sys.stderr,
        )
        return 1
    if checked == 0:
        print(
            "\nno ratcheted metric applies to this run's platform "
            f"({current.get('platform')!r}) — nothing enforced",
            file=sys.stderr,
        )
        return 0
    if failed:
        print(
            f"\nRATCHET FAILED: {failed} of {checked} applicable metric(s) "
            "regressed past tolerance or went missing", file=sys.stderr,
        )
        return 1
    print(f"\nratchet ok: {checked} applicable metric(s) within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
