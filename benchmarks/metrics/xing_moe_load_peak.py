"""Expert layer, kind xing-serving: the busiest expert's tokens over the mean
tokens of a touched expert, averaged over the window's dispatches and expert
layers (1.0: even load). From the counts the dispatches make on the device:
delta `oryx_moe_expert_tokens_max_total` a dispatch's expert layer, over delta
`oryx_moe_routed_total` / `oryx_moe_experts_touched_total`."""

from benchmarks.metrics import _xing


def read(src):
    c = src.get("counters") or {}
    routed = c.get("oryx_moe_routed_total", 0.0)
    touched = c.get("oryx_moe_experts_touched_total", 0.0)
    n = _xing.all_steps(src)
    if not routed or not touched or not n:
        return None
    busiest = c.get("oryx_moe_expert_tokens_max_total", 0.0) / (n * _xing.expert_layers(src))
    return busiest / (routed / touched)
