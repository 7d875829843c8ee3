"""Expert layer (ops/moe.py): share of its roofline. The least time the chip
could take for the window's expert layers -- max(FLOPs / peak FLOP/s, bytes
/ peak HBM bytes/s) of ONE layer at the mean real tokens a dispatch and the
mean experts a dispatch's layer touched (kinds/seq_serving.py moe_work),
times the layers the traced dispatches ran -- over the device time of the
instructions under the `sdar.moe` scope (benchmarks/seqtrace.py). Which of
the two bounds it goes to stderr."""

import sys

from benchmarks.kinds.seq_serving import moe_work
from benchmarks.metrics import _seq


def read(src):
    steps, peaks = src.get("steps"), src.get("peaks")
    n = _seq.all_steps(src)
    if not steps or not peaks or not n:
        return None
    cfg = src["config"]
    c = src["counters"]
    layers = cfg["num_hidden_layers"]
    traced = sum(p["count"] for p in steps.values())
    seconds = sum(p["scoped"].get("sdar.moe", 0.0) for p in steps.values())
    if not traced or not seconds:
        return None
    tokens = _seq.all_tokens(src, "real") / n
    touched = c.get("oryx_moe_experts_touched_total", 0.0) / (n * layers)
    flops, moved = moe_work(tokens, touched, cfg)
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    per_layer_ms = seconds / (traced * layers) * 1e3
    print(
        f"moe_roofline: {bound}-bound: {t_bytes * 1e3:.3f} ms of HBM, {t_flops * 1e3:.3f} ms of MXU "
        f"a layer at {tokens:.1f} tokens and {touched:.1f} experts; {per_layer_ms:.3f} ms a layer "
        f"under sdar.moe over {traced} dispatches",
        file=sys.stderr,
    )
    return max(t_flops, t_bytes) * 1e3 / per_layer_ms * 100.0
