"""Gated attention (ops/trinity.py): the device time under the `trinity.attn`
scope (the five projections, the q/k norms and the rotation, the cache's
writes and reads, scores and values, the gate, the norm after) as a share of
the trinity programs' device time in the traced window: how much of a step the
attention is. The other scopes' shares go to stderr."""

import sys


def read(src):
    steps = src.get("steps")
    if not steps:
        return None
    seconds = sum(p["seconds"] for p in steps.values())
    if not seconds:
        return None
    for s in sorted({s for p in steps.values() for s in p["scoped"]}):
        share = sum(p["scoped"].get(s, 0.0) for p in steps.values()) / seconds
        print(f"trinity_attn_share: {s}: {share * 100.0:.1f} % of the trinity programs' device time", file=sys.stderr)
    return sum(p["scoped"].get("trinity.attn", 0.0) for p in steps.values()) / seconds * 100.0
