"""Shared by the readers of kind `ssm-serving`: the stepper's counters by
kind of dispatch (deltas over the window), and the traced window's programs
joined to them."""

from benchmarks.kinds.ssm_serving import PROGRAMS
from benchmarks.metrics._seq import steps, tokens

KINDS = tuple(PROGRAMS)  # ("prefill", "decode")


def all_steps(src):
    return sum(steps(src, k) for k in KINDS)


def all_tokens(src, which):
    return sum(tokens(src, k, which) for k in KINDS)


def traced(src):
    """[(kind, traced program, real tokens a dispatch, real sequences a
    dispatch)] of the kinds the traced window ran and the counters counted.
    A session's events but the last are a prefill's tokens; a step's tokens
    are its sequences."""
    out = []
    traffic = src.get("traffic") or {}
    for kind, program in PROGRAMS.items():
        prog, n = (src.get("steps") or {}).get(program), steps(src, kind)
        if not prog or not prog["count"] or not n:
            continue
        per_step = tokens(src, kind, "real") / n
        rows = per_step if kind == "decode" else per_step / max(float(traffic.get("events_median", 1)) - 1.0, 1.0)
        out.append((kind, prog, per_step, rows))
    return out
