"""Batcher: the share of `batcher.launch`'s wall time in which the
dispatcher's thread was NOT on a CPU, in percent: 1 - the thread's CPU time
over the wall time of the regions. Waiting for the interpreter lock (eight
post-pool threads, the event loops and a second dispatcher want it too), for
`_cond`, for the runtime, or descheduled."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.offcpu_share(src, "batcher.launch")
