"""Batcher: the second part of `launch_host_ms`: wall of the
`batcher.issue.upload` regions over their count: the query block's upload,
its cast to the view's dtype and the `rows` scalar, whatever runs before the
jitted call (oryx_tpu/ops/als.py stage_topk_operands)."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.mean_ms(src, ("batcher.issue.upload",), "batcher.issue.upload")
