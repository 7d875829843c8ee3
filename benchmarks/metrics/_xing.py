"""Shared by the readers of kind `xing-serving`: the stepper's counters by
kind of dispatch (deltas over the window), the expert layers' counts the
dispatches made on the device, the traced window's programs joined to them,
and a scope's share of the xing programs' device time."""

from benchmarks.kinds.xing_serving import _sizes
# the same two kinds of dispatch under the same two program names: a prefill's tokens attend over
# half a median session, a step's over a whole one and half a basket
from benchmarks.metrics._joyai import traced  # noqa: F401
from benchmarks.metrics._ssm import all_steps, all_tokens  # noqa: F401


def touched_per_dispatch(src):
    """Routed experts that received a token, a dispatch, summed over its
    expert layers: the window's mean over prefills and steps alike (bytes are
    linear in it, so the mean serves a sum over dispatches of either kind)."""
    n = all_steps(src)
    return (src.get("counters") or {}).get("oryx_moe_experts_touched_total", 0.0) / n if n else 0.0


def expert_layers(src):
    return _sizes(src["config"])["moe"]


def scope_share(src, scope):
    """The device time under `scope` over the xing programs' in the traced
    window, in percent (None: no program traced, or nothing under it)."""
    steps = src.get("steps")
    if not steps:
        return None
    seconds = sum(p["seconds"] for p in steps.values())
    under = sum(p["scoped"].get(scope, 0.0) for p in steps.values())
    return under / seconds * 100.0 if seconds and under else None
