"""Batched encoder step, kind ssm-serving: mean device time of one dispatch
of the jamba programs (`jit_prefill`, `jit_decode_step`), weighted by their
counts, from the traced window's `XLA Modules` events (benchmarks/seqtrace.py).
Each program's own mean goes to stderr."""

import sys


def read(src):
    steps = src.get("steps")
    if not steps:
        return None
    count = sum(p["count"] for p in steps.values())
    if not count:
        return None
    for name, p in sorted(steps.items()):
        if p["count"]:
            print(
                f"ssm_step_ms: {name}: {p['count']} dispatches, {p['seconds'] / p['count'] * 1e3:.3f} ms each",
                file=sys.stderr,
            )
    return sum(p["seconds"] for p in steps.values()) / count * 1e3
