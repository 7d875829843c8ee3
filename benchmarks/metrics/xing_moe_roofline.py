"""Expert layer (ops/moe.py behind ops/xing.py): share of its roofline. The
least time the chip could take for the traced dispatches' expert layers --
max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s) of kinds/xing_serving.py
moe_work (the router over 64, the routed experts TOUCHED and the shared
expert) at the window's mean real tokens and touched experts a dispatch's
layer -- over the device time of the instructions under the `xing.moe` and
`xing.shared` scopes (benchmarks/seqtrace.py). Which of the two bounds it
goes to stderr."""

import sys

from benchmarks.kinds.xing_serving import moe_work
from benchmarks.metrics import _xing


def read(src):
    steps, peaks = src.get("steps"), src.get("peaks")
    n = _xing.all_steps(src) if steps and peaks else 0
    if not n:
        return None
    layers = _xing.expert_layers(src)
    traced = sum(p["count"] for p in steps.values())
    seconds = sum(p["scoped"].get("xing.moe", 0.0) + p["scoped"].get("xing.shared", 0.0) for p in steps.values())
    if not traced or not seconds or not layers:
        return None
    tokens = _xing.all_tokens(src, "real") / n
    touched = _xing.touched_per_dispatch(src) / layers
    flops, moved = moe_work(tokens, touched, src["config"])
    t_flops = flops / peaks["flops_per_s"]["bfloat16"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    per_layer_ms = seconds / (traced * layers) * 1e3
    print(
        f"xing_moe_roofline: {'memory' if t_bytes >= t_flops else 'compute'}-bound: {t_bytes * 1e3:.3f} ms of "
        f"HBM, {t_flops * 1e3:.3f} ms of MXU a layer at {tokens:.1f} tokens and {touched:.1f} experts; "
        f"{per_layer_ms:.3f} ms a layer under xing.moe + xing.shared over {traced} dispatches",
        file=sys.stderr,
    )
    return max(t_flops, t_bytes) * 1e3 / per_layer_ms * 100.0
