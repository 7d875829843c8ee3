"""Batched encoder step, kind trinity-serving: share of the chip's HBM
bandwidth that the bytes a dispatch HAS to move make of the trinity
dispatches' device time (kinds/trinity_serving.py step_bytes: the attention's,
the dense layer's, the routers' and the shared experts' weights once, the held
experts the dispatch TOUCHED and not all 32 a layer, the cache's rows, a
step's rows of the catalog), at the window's mean real tokens, sequences and
touched experts a dispatch."""

import sys

from benchmarks.kinds.trinity_serving import step_bytes
from benchmarks.metrics import _trinity


def read(src):
    peaks, dispatches = src.get("peaks"), _trinity.traced(src)
    if not peaks or not dispatches:
        return None
    touched = _trinity.touched_per_dispatch(src)
    moved = seconds = 0.0
    for kind, prog, per_step, rows, context in dispatches:
        moved += prog["count"] * step_bytes(per_step, rows, context, touched, kind == "decode", src["config"])
        seconds += prog["seconds"]
    if not seconds:
        return None
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    print(
        f"trinity_step_hbm_roofline: {moved / 1e9:.2f} GB to move in {seconds * 1e3:.1f} ms of trinity "
        f"dispatches ({touched:.1f} held experts touched a dispatch); at the peak {t_bytes * 1e3:.2f} ms",
        file=sys.stderr,
    )
    return t_bytes / seconds * 100.0
