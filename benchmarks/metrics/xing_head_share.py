"""The head inside the step (ops/seq.py catalog_head behind ops/xing.py): the
device time under the `xing.head` scope (the streams' sum and the final norm,
the kernel over the served view's valid blocks, the argmax) as a share of the
xing programs' device time in the traced window."""

from benchmarks.metrics import _xing


def read(src):
    return _xing.scope_share(src, "xing.head")
