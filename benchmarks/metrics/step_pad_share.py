"""Batched encoder step: share of the encoder dispatches' token slots that
held no real token (rows past the admitted sessions, positions past a
session's length, blocks past those in flight), in percent."""

from benchmarks.metrics import _seq


def read(src):
    padded = _seq.all_tokens(src, "padded")
    return (1.0 - _seq.all_tokens(src, "real") / padded) * 100.0 if padded else None
