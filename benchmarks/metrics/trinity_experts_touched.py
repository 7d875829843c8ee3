"""Expert layer, kind trinity-serving: held experts that received a token, a
dispatch's expert layer (of the 32 this chip holds of a layer's 256): delta
`oryx_moe_experts_touched_total` over the window's dispatches and expert
layers. What a dispatch streams of a layer's held experts is this many."""

from benchmarks.metrics import _trinity


def read(src):
    n = _trinity.all_steps(src)
    touched = (src.get("counters") or {}).get("oryx_moe_experts_touched_total", 0.0)
    return touched / (n * _trinity.expert_layers(src)) if n and touched else None
