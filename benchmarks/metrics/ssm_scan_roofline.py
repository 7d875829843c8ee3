"""Selective scan (ops/jamba.py): share of its roofline. The least time the
chip could take for the conv and the recurrence of the window's Mamba layers
-- max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s) of ONE layer at the
mean real positions and sequences a dispatch of its kind
(kinds/ssm_serving.py scan_work), times the Mamba layers the traced
dispatches ran -- over the device time of the instructions under the
`jamba.scan` scope (benchmarks/seqtrace.py). Which bound it is goes to stderr."""

import sys

from benchmarks.kinds.ssm_serving import _sizes, scan_work
from benchmarks.metrics import _ssm


def read(src):
    peaks, dispatches = src.get("peaks"), _ssm.traced(src)
    if not peaks or not dispatches:
        return None
    layers = _sizes(src["config"])["mamba"]
    least = seconds = t_flops_all = 0.0
    for _kind, prog, per_step, rows in dispatches:
        flops, moved = scan_work(per_step, rows, src["config"])
        t_flops, t_bytes = flops / peaks["flops_per_s"]["bfloat16"], moved / peaks["hbm_bytes_per_s"]
        least += prog["count"] * layers * max(t_flops, t_bytes)
        t_flops_all += prog["count"] * layers * t_flops
        seconds += prog["scoped"].get("jamba.scan", 0.0)
    if not seconds:
        return None
    print(
        f"ssm_scan_roofline: {'compute' if t_flops_all >= 0.5 * least else 'memory'}-bound: the least "
        f"{least * 1e3:.2f} ms ({t_flops_all * 1e3:.2f} ms of FLOPs at the bf16 peak), {seconds * 1e3:.2f} ms "
        "under jamba.scan",
        file=sys.stderr,
    )
    return least / seconds * 100.0
