"""Latent attention (ops/mla.py behind ops/xing.py): the device time under the
`xing.attn` scope (the projections, the rotation at YaRN's frequencies, the
cache's writes and reads, scores and values) as a share of the xing programs'
device time in the traced window: how much of a step the attention is."""

from benchmarks.metrics import _xing


def read(src):
    return _xing.scope_share(src, "xing.attn")
