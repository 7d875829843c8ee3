"""Gated attention (ops/trinity.py): share of its roofline. The least time the
chip could take for the traced dispatches' attention -- max(FLOPs / peak
FLOP/s, bytes / peak HBM bytes/s) of ONE layer by kinds/trinity_serving.py
attn_work (the five projections, scores and values over the context, the
cache's writes and a step's reads) at the mean real tokens and sequences a
dispatch of its kind, times the layers -- over the device time of the
instructions under the `trinity.attn` scope AND of the programs' unscoped
instructions, as `mla_attn_roofline` counts them: the compiler brings a
dispatch's dense weights from HBM into VMEM by asynchronous copies that carry
no scope, so the scoped instructions alone would read their weights faster
than HBM could deliver them. The other dense weights' copies (the dense
layer's, the shared experts') are counted against the attention too: the share
errs low, never high. Which bound it is goes to stderr."""

import sys

from benchmarks.kinds.trinity_serving import attn_work
from benchmarks.metrics import _trinity


def read(src):
    peaks, dispatches = src.get("peaks"), _trinity.traced(src)
    if not peaks or not dispatches:
        return None
    layers = src["config"]["num_hidden_layers"]
    least = seconds = t_flops_all = 0.0
    for kind, prog, per_step, rows, context in dispatches:
        flops, moved = attn_work(per_step, context, rows, kind == "decode", src["config"])
        t_flops, t_bytes = flops / peaks["flops_per_s"]["bfloat16"], moved / peaks["hbm_bytes_per_s"]
        least += prog["count"] * layers * max(t_flops, t_bytes)
        t_flops_all += prog["count"] * layers * t_flops
        seconds += prog["scoped"].get("trinity.attn", 0.0) + prog.get("unscoped", 0.0)
    if not seconds:
        return None
    print(
        f"trinity_attn_roofline: {'compute' if t_flops_all >= 0.5 * least else 'memory'}-bound: the least "
        f"{least * 1e3:.2f} ms ({t_flops_all * 1e3:.2f} ms of FLOPs at the bf16 peak), {seconds * 1e3:.2f} ms "
        "under trinity.attn and unscoped",
        file=sys.stderr,
    )
    return least / seconds * 100.0
