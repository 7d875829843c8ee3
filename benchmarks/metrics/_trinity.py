"""Shared by the readers of kind `trinity-serving`: the stepper's counters by
kind of dispatch (deltas over the window), the expert layers' counts the
dispatches made on the device (pairs computed here, pairs sent elsewhere,
held experts touched), and the traced window's programs joined to them."""

from benchmarks.kinds.trinity_serving import _sizes
# the same two kinds of dispatch under the same two program names: a prefill's tokens attend over
# half a median session, a step's over a whole one and half a basket
from benchmarks.metrics._joyai import traced  # noqa: F401
from benchmarks.metrics._ssm import all_steps, all_tokens  # noqa: F401


def _per_dispatch(src, series):
    n = all_steps(src)
    return (src.get("counters") or {}).get(series, 0.0) / n if n else 0.0


def touched_per_dispatch(src):
    """Held experts that received a token, a dispatch, summed over its expert
    layers: the window's mean over prefills and steps alike (bytes are linear
    in it, so the mean serves a sum over dispatches of either kind)."""
    return _per_dispatch(src, "oryx_moe_experts_touched_total")


def pairs_per_dispatch(src):
    """(token, expert) pairs computed by an expert held here, a dispatch,
    summed over its expert layers (FLOPs are linear in it)."""
    return _per_dispatch(src, "oryx_moe_routed_total")


def expert_layers(src):
    return _sizes(src["config"])["moe"]
