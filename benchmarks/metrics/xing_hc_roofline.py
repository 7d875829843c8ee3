"""Hyper-connections (ops/xing.py): share of their roofline. The least time
the chip could take for the traced dispatches' hyper-connections -- the
larger of FLOPs / peak FLOP/s (the bf16 peak: the maps' float32 product
runs in several bf16 passes, so the share errs low) and bytes / peak HBM
bytes/s of kinds/xing_serving.py hc_work (the maps' product, the Sinkhorn,
the mixes; phi once, the streams read and written once) at the mean real
tokens a dispatch of its kind, times the sublayers -- over the device time
under the `xing.hc` scope. Which bound it is goes to stderr."""

import sys

from benchmarks.kinds.xing_serving import hc_work
from benchmarks.metrics import _xing


def read(src):
    peaks, dispatches = src.get("peaks"), _xing.traced(src)
    if not peaks or not dispatches:
        return None
    sublayers = 2 * src["config"]["num_hidden_layers"]
    least = seconds = t_flops_all = 0.0
    for _kind, prog, per_step, _rows, _context in dispatches:
        flops, moved = hc_work(per_step, src["config"])
        t_flops, t_bytes = flops / peaks["flops_per_s"]["bfloat16"], moved / peaks["hbm_bytes_per_s"]
        least += prog["count"] * sublayers * max(t_flops, t_bytes)
        t_flops_all += prog["count"] * sublayers * t_flops
        seconds += prog["scoped"].get("xing.hc", 0.0)
    if not seconds:
        return None
    print(
        f"xing_hc_roofline: {'compute' if t_flops_all >= 0.5 * least else 'memory'}-bound: the least "
        f"{least * 1e3:.3f} ms ({t_flops_all * 1e3:.3f} ms of FLOPs at the bf16 peak), {seconds * 1e3:.2f} ms "
        "under xing.hc",
        file=sys.stderr,
    )
    return least / seconds * 100.0
