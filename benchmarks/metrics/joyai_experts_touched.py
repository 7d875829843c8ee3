"""Expert layer, kind joyai-serving: routed experts that received a token, a
dispatch's expert layer (of the 256 a layer holds): delta
`oryx_moe_experts_touched_total` over the window's dispatches and expert
layers. What a dispatch streams of a layer's experts is this many of them."""

from benchmarks.metrics import _joyai


def read(src):
    n = _joyai.all_steps(src)
    touched = (src.get("counters") or {}).get("oryx_moe_experts_touched_total", 0.0)
    return touched / (n * _joyai.expert_layers(src)) if n and touched else None
