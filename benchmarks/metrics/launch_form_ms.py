"""Batcher: forming one dispatch, the first of the four parts of
`launch_host_ms`: wall of the `batcher.launch.form` regions over their count
(shape key, the `_cond` block, the ledgers' `batch_wait`, the pad fill: all
before the issue). From the regions' always-on counters, so over the WHOLE
window and not the traced part of it."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.mean_ms(src, ("batcher.launch.form",), "batcher.launch.form")
