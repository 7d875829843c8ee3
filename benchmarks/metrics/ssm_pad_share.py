"""Batched encoder step, kind ssm-serving: share of the jamba dispatches'
token slots that held no real token (rows past the admitted sessions,
positions past a session's length, step rows past the sequences in flight),
in percent."""

from benchmarks.metrics import _ssm


def read(src):
    padded = _ssm.all_tokens(src, "padded")
    return (1.0 - _ssm.all_tokens(src, "real") / padded) * 100.0 if padded else None
