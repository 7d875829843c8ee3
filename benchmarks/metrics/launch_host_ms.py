"""Batcher: host time to launch one dispatch, the mean duration of the
`batcher.launch` regions in the traced window (benchmarks/timeline.py):
group formation, pad fill, query upload, the jit call, the async copies.
Once no scan is queued ahead, this is the device's idle gap."""

from benchmarks import timeline


def read(src):
    return timeline.region_ms(src, "batcher.launch")
