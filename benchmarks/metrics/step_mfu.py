"""Batched encoder step: share of the chip's bf16 peak that the MODEL's
FLOPs for the real tokens make of the encoder dispatches' device time: the
whole step, prefill and denoise. FLOPs of a dispatch by
kinds/seq_serving.py step_work at the window's mean real tokens a dispatch
of its kind (a token attends over about half a median session in a prefill,
a whole one and the block in a step; the scores are under 1 % of the
FLOPs); device time and counts from the traced window. Which bound the
step is under goes to stderr."""

import sys

from benchmarks.kinds.seq_serving import PROGRAMS, step_work
from benchmarks.metrics import _seq


def read(src):
    steps, peaks = src.get("steps"), src.get("peaks")
    if not steps or not peaks or not _seq.all_steps(src):
        return None
    cfg, traffic = src["config"], src["traffic"]
    median = float(traffic["events_median"])
    flops = seconds = 0.0
    for kind, program in PROGRAMS.items():
        traced, n = steps.get(program), _seq.steps(src, kind)
        if not traced or not traced["count"] or not n:
            continue
        per_step = _seq.tokens(src, kind, "real") / n
        if kind == "prefill":
            work = step_work(per_step, median / 2.0, 0.0, cfg)
        else:
            work = step_work(per_step, median + cfg["block_length"], per_step, cfg)
        flops += traced["count"] * work
        seconds += traced["seconds"]
    if not seconds:
        return None
    # the least a step streams: every layer's attention and, at these token
    # counts, most experts; against its FLOPs at the peak
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    print(
        f"step_mfu: {flops / 1e9:.1f} GFLOP of the model in {seconds * 1e3:.1f} ms of encoder "
        f"dispatches; at the peak {t_flops * 1e3:.2f} ms: "
        + ("compute" if t_flops > 0.5 * seconds else "memory or latency") + "-bound",
        file=sys.stderr,
    )
    return t_flops / seconds * 100.0
