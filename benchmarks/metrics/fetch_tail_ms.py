"""Batcher: the copy back, mean over the joined dispatches
(benchmarks/timeline.py) of the end of `batcher.fetch` minus the kernel's
end: result transfer, delinearisation and the dispatcher's wake-up."""

from benchmarks import timeline


def read(src):
    return timeline.joined_ms(src, lambda d: d["fetch"]["end"] - d["kernel"]["end"])
