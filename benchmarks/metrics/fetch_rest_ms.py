"""Batcher: what a fetch still waits for once its first array is on the
host: wall of `batcher.fetch.idx` + `batcher.fetch.chunks` over the count of
`batcher.fetch`. All three copies were started at issue, so this is the
runtime's (or the interpreter lock's) and not the device's."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.mean_ms(src, ("batcher.fetch.idx", "batcher.fetch.chunks"), "batcher.fetch")
