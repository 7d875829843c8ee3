"""Batcher: the fourth part of `launch_host_ms`: wall of the
`batcher.issue.copy` regions over their count: the three
`copy_to_host_async` that start the results' way back."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.mean_ms(src, ("batcher.issue.copy",), "batcher.issue.copy")
