"""Top-k kernel (ops/pallas_topk.py via ops/als.py topk_path): mean device
duration of the fused kernel's custom call per dispatch, from the trace."""

from benchmarks.xplane import op_seconds

KERNEL = "topk_pallas"


def read(src):
    trace = src.get("trace")
    if not trace:
        return None
    count, seconds = op_seconds(trace, KERNEL)
    return seconds / count * 1e3 if count else None
