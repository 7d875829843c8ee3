"""Batcher: host time to hand one dispatch's results out, the mean duration
of the `batcher.distribute` regions in the traced window
(benchmarks/timeline.py): the DispatchRecord, the ledger stamps and one
future set (a post-pool submit) per request, on the dispatcher thread."""

from benchmarks import timeline


def read(src):
    return timeline.region_ms(src, "batcher.distribute")
