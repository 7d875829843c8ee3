"""Expert layer, kind xing-serving: routed experts that received a token, a
dispatch's expert layer (of the layer's 64): delta
`oryx_moe_experts_touched_total` over the window's dispatches and expert
layers. What a dispatch streams of a layer's experts is this many."""

from benchmarks.metrics import _xing


def read(src):
    n = _xing.all_steps(src)
    touched = (src.get("counters") or {}).get("oryx_moe_experts_touched_total", 0.0)
    return touched / (n * _xing.expert_layers(src)) if n and touched else None
