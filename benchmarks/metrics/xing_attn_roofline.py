"""Latent attention (ops/mla.py behind ops/xing.py): share of its roofline.
The least time the chip could take for the traced dispatches' attention --
max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s) of ONE layer by
kinds/xing_serving.py attn_work (kind joyai-serving's: the five projections, a
prefill's written scores and values and its cache writes, a step's absorbed
ones and its cache reads) at the mean real tokens and sequences a dispatch of
its kind, times the layers -- over the device time of the instructions under
the `xing.attn` scope AND of the programs' unscoped instructions, as
`mla_attn_roofline` counts them: the compiler brings a dispatch's dense
weights from HBM into VMEM by asynchronous copies that carry no scope. The
other dense weights' copies are counted against the attention too: the share
errs low, never high. Which bound it is goes to stderr."""

import sys

from benchmarks.kinds.xing_serving import attn_work
from benchmarks.metrics import _xing


def read(src):
    peaks, dispatches = src.get("peaks"), _xing.traced(src)
    if not peaks or not dispatches:
        return None
    layers = src["config"]["num_hidden_layers"]
    least = seconds = t_flops_all = 0.0
    for kind, prog, per_step, rows, context in dispatches:
        flops, moved = attn_work(per_step, context, rows, kind == "decode", src["config"])
        t_flops, t_bytes = flops / peaks["flops_per_s"]["bfloat16"], moved / peaks["hbm_bytes_per_s"]
        least += prog["count"] * layers * max(t_flops, t_bytes)
        t_flops_all += prog["count"] * layers * t_flops
        seconds += prog["scoped"].get("xing.attn", 0.0) + prog.get("unscoped", 0.0)
    if not seconds:
        return None
    print(
        f"xing_attn_roofline: {'compute' if t_flops_all >= 0.5 * least else 'memory'}-bound: the least "
        f"{least * 1e3:.2f} ms ({t_flops_all * 1e3:.2f} ms of FLOPs at the bf16 peak), {seconds * 1e3:.2f} ms "
        "under xing.attn and unscoped",
        file=sys.stderr,
    )
    return least / seconds * 100.0
