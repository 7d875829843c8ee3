"""Batcher: the third part of `launch_host_ms`: wall of the
`batcher.issue.call` regions over their count: the jitted top-k call alone,
on operands that are on the device already."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.mean_ms(src, ("batcher.issue.call",), "batcher.issue.call")
