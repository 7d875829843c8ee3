"""Batched encoder step, kind xing-serving: share of the chip's bf16 peak that
the MODEL's FLOPs for the real tokens make of the xing dispatches' device
time: the whole step, prefill and decode. FLOPs of a dispatch by
kinds/xing_serving.py step_work (every layer's attention, as written in a
prefill and absorbed in a step, the dense layer, the routers, routed and
shared experts, every sublayer's hyper-connection: its maps' product, the
Sinkhorn and the mixes, and a step's head) at the window's mean real tokens a
dispatch of its kind; device time and counts from the traced window."""

import sys

from benchmarks.kinds.xing_serving import step_work
from benchmarks.metrics import _xing


def read(src):
    peaks, dispatches = src.get("peaks"), _xing.traced(src)
    if not peaks or not dispatches:
        return None
    flops = seconds = 0.0
    for kind, prog, per_step, _rows, context in dispatches:
        step = kind == "decode"
        flops += prog["count"] * step_work(per_step, context, per_step if step else 0.0, step, src["config"])
        seconds += prog["seconds"]
    if not seconds:
        return None
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    print(
        f"xing_step_mfu: {flops / 1e9:.1f} GFLOP of the model in {seconds * 1e3:.1f} ms of xing "
        f"dispatches; at the peak {t_flops * 1e3:.2f} ms",
        file=sys.stderr,
    )
    return t_flops / seconds * 100.0
