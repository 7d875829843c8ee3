"""Batched encoder step, kind xing-serving: share of the chip's HBM bandwidth
that the bytes a dispatch HAS to move make of the xing dispatches' device time
(kinds/xing_serving.py step_bytes: the attention's, the dense layer's, the
routers', the shared experts' and the maps' weights once, the routed experts
the dispatch TOUCHED and not all 64, the cache's rows, the streams read and
written once a sublayer, a step's rows of the catalog), at the window's mean
real tokens, sequences and touched experts a dispatch."""

import sys

from benchmarks.kinds.xing_serving import step_bytes
from benchmarks.metrics import _xing


def read(src):
    peaks, dispatches = src.get("peaks"), _xing.traced(src)
    if not peaks or not dispatches:
        return None
    touched = _xing.touched_per_dispatch(src)
    moved = seconds = 0.0
    for kind, prog, per_step, rows, context in dispatches:
        moved += prog["count"] * step_bytes(per_step, rows, context, touched, kind == "decode", src["config"])
        seconds += prog["seconds"]
    if not seconds:
        return None
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    print(
        f"xing_step_hbm_roofline: {moved / 1e9:.2f} GB to move in {seconds * 1e3:.1f} ms of xing "
        f"dispatches ({touched:.1f} routed experts touched a dispatch); at the peak {t_bytes * 1e3:.2f} ms",
        file=sys.stderr,
    )
    return t_bytes / seconds * 100.0
