"""Serving model's post-processing (`post.rerank`: pad filter, exact
re-rank, trim; numpy on small arrays and Python): the share of its wall time
in which its post-pool thread was not on a CPU, in percent. Such a region can
only wait for the interpreter lock, so this is that lock's contention,
measured where it bites."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.offcpu_share(src, "post.rerank")
