"""Batched encoder step, kind trinity-serving: share of the chip's bf16 peak
that the MODEL's FLOPs for the real tokens make of the trinity dispatches'
device time: the whole step, prefill and decode. FLOPs of a dispatch by
kinds/trinity_serving.py step_work at the window's mean real tokens a dispatch
of its kind and the mean pairs a dispatch that an expert HELD here computed
(the pairs sent elsewhere are other chips' FLOPs), a step's with the head's
logits; device time and counts from the traced window."""

import sys

from benchmarks.kinds.trinity_serving import step_work
from benchmarks.metrics import _trinity


def read(src):
    peaks, dispatches = src.get("peaks"), _trinity.traced(src)
    if not peaks or not dispatches:
        return None
    pairs = _trinity.pairs_per_dispatch(src)
    flops = seconds = 0.0
    for kind, prog, per_step, _rows, context in dispatches:
        head = per_step if kind == "decode" else 0.0
        flops += prog["count"] * step_work(per_step, context, head, pairs, src["config"])
        seconds += prog["seconds"]
    if not seconds:
        return None
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    print(
        f"trinity_step_mfu: {flops / 1e9:.1f} GFLOP of the model in {seconds * 1e3:.1f} ms of trinity "
        f"dispatches; at the peak {t_flops * 1e3:.2f} ms",
        file=sys.stderr,
    )
    return t_flops / seconds * 100.0
