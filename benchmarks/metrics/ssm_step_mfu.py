"""Batched encoder step, kind ssm-serving: share of the chip's bf16 peak that
the MODEL's FLOPs for the real tokens make of the jamba dispatches' device
time: the whole step, prefill and decode. FLOPs of a dispatch by
kinds/ssm_serving.py step_work at the window's mean real tokens a dispatch of
its kind (an attention layer's token attends over about half a median session
in a prefill, a whole one in a step, which also takes the head's logits);
device time and counts from the traced window."""

import sys

from benchmarks.kinds.ssm_serving import step_work
from benchmarks.metrics import _ssm


def read(src):
    peaks, dispatches = src.get("peaks"), _ssm.traced(src)
    if not peaks or not dispatches:
        return None
    median = float(src["traffic"]["events_median"])
    flops = seconds = 0.0
    for kind, prog, per_step, _rows in dispatches:
        if kind == "prefill":
            work = step_work(per_step, median / 2.0, 0.0, src["config"])
        else:
            work = step_work(per_step, median + src["config"]["basket"] / 2.0, per_step, src["config"])
        flops += prog["count"] * work
        seconds += prog["seconds"]
    if not seconds:
        return None
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    print(
        f"ssm_step_mfu: {flops / 1e9:.1f} GFLOP of the model in {seconds * 1e3:.1f} ms of jamba "
        f"dispatches; at the peak {t_flops * 1e3:.2f} ms",
        file=sys.stderr,
    )
    return t_flops / seconds * 100.0
