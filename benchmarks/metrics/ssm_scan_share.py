"""Selective scan (ops/jamba.py): the device time under the `jamba.scan` scope
(the conv and the recurrence) as a share of the jamba programs' device time
in the traced window: how much of a step the distinctive part is. The other
scopes' shares go to stderr."""

import sys


def read(src):
    steps = src.get("steps")
    if not steps:
        return None
    seconds = sum(p["seconds"] for p in steps.values())
    if not seconds:
        return None
    scopes = sorted({s for p in steps.values() for s in p["scoped"]})
    for s in scopes:
        share = sum(p["scoped"].get(s, 0.0) for p in steps.values()) / seconds
        print(f"ssm_scan_share: {s}: {share * 100.0:.1f} % of the jamba programs' device time", file=sys.stderr)
    return sum(p["scoped"].get("jamba.scan", 0.0) for p in steps.values()) / seconds * 100.0
