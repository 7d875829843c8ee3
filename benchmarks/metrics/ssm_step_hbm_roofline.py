"""Batched encoder step, kind ssm-serving: share of the chip's HBM bandwidth
that the bytes a dispatch HAS to move make of the jamba dispatches' device
time (kinds/ssm_serving.py step_bytes: every weight once, the real
sequences' state read and written, a step's view of the catalog), at the
window's mean real tokens and sequences a dispatch of its kind."""

import sys

from benchmarks.kinds.ssm_serving import step_bytes
from benchmarks.metrics import _ssm


def read(src):
    peaks, dispatches = src.get("peaks"), _ssm.traced(src)
    if not peaks or not dispatches:
        return None
    moved = seconds = 0.0
    for kind, prog, per_step, rows in dispatches:
        moved += prog["count"] * step_bytes(per_step, rows, kind == "decode", src["config"])
        seconds += prog["seconds"]
    if not seconds:
        return None
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    print(
        f"ssm_step_hbm_roofline: {moved / 1e9:.2f} GB to move in {seconds * 1e3:.1f} ms of jamba "
        f"dispatches; at the peak {t_bytes * 1e3:.2f} ms",
        file=sys.stderr,
    )
    return t_bytes / seconds * 100.0
