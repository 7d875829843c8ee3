"""Shared by the readers of kind `joyai-serving`: the stepper's counters by
kind of dispatch (deltas over the window), the expert layers' counts the
dispatches made on the device, and the traced window's programs joined to
them."""

from benchmarks.kinds.joyai_serving import PROGRAMS, _sizes
from benchmarks.metrics._seq import steps, tokens
from benchmarks.metrics._ssm import all_steps, all_tokens  # noqa: F401 - the same two kinds of dispatch


def touched_per_dispatch(src):
    """Routed experts that received a token, a dispatch, summed over its
    expert layers: the window's mean over prefills and steps alike (bytes are
    linear in it, so the mean serves a sum over dispatches of either kind)."""
    n = all_steps(src)
    return (src.get("counters") or {}).get("oryx_moe_experts_touched_total", 0.0) / n if n else 0.0


def expert_layers(src):
    return _sizes(src["config"])["moe"]


def traced(src):
    """[(kind, traced program, real tokens a dispatch, real sequences a
    dispatch, positions a token attends over)] of the kinds the traced window
    ran and the counters counted. A session's events but the last are a
    prefill's tokens, each attending over about half a median session; a
    step's tokens are its sequences, each over a whole one and half a basket."""
    out = []
    median = float((src.get("traffic") or {}).get("events_median", 1))
    basket = float((src.get("config") or {}).get("basket", 0))
    for kind, program in PROGRAMS.items():
        prog, n = (src.get("steps") or {}).get(program), steps(src, kind)
        if not prog or not prog["count"] or not n:
            continue
        per_step = tokens(src, kind, "real") / n
        if kind == "decode":
            out.append((kind, prog, per_step, per_step, median + basket / 2.0))
        else:
            out.append((kind, prog, per_step, per_step / max(median - 1.0, 1.0), median / 2.0))
    return out
