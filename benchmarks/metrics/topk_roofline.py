"""Top-k kernel: share of its roofline. The least time the chip could take
for one dispatch -- max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s) of
the ALGORITHM at the real rows per dispatch, the real item rows and the
published features (kinds/als_serving.py topk_work) -- over the kernel's
device time per dispatch. Which of the two bounds it goes to stderr."""

import sys

from benchmarks.metrics import dispatch_rows, topk_kernel_ms
from benchmarks.kinds.als_serving import topk_work


def read(src):
    kernel_ms = topk_kernel_ms.read(src)
    rows = dispatch_rows.read(src)
    peaks = src.get("peaks")
    if not kernel_ms or not rows or not peaks:
        return None
    cfg, traffic = src["config"], src["traffic"]
    flops, moved = topk_work(rows, cfg["items"], cfg["features"], traffic["k_bucket"])
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    print(
        f"topk_roofline: {bound}-bound: {t_bytes * 1e3:.3f} ms of HBM, "
        f"{t_flops * 1e3:.3f} ms of MXU, kernel {kernel_ms:.3f} ms at {rows:.1f} rows",
        file=sys.stderr,
    )
    return max(t_flops, t_bytes) * 1e3 / kernel_ms * 100.0
