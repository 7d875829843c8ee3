"""Latent attention (ops/joyai.py): the device time under the `joyai.attn`
scope (the projections, the cache's writes and reads, scores and values) as a
share of the joyai programs' device time in the traced window: how much of a
step the attention is. The other scopes' shares go to stderr."""

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
        print(f"mla_attn_share: {s}: {share * 100.0:.1f} % of the joyai programs' device time", file=sys.stderr)
    return sum(p["scoped"].get("joyai.attn", 0.0) for p in steps.values()) / seconds * 100.0
