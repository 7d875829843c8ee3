"""Batched encoder step, kind xing-serving: share of the xing dispatches'
token slots that held no real token (rows past the admitted sessions,
positions past a session's length, step rows past the sequences in flight),
in percent. The reader is `ssm_pad_share`'s: the same two kinds of dispatch."""

from benchmarks.metrics.ssm_pad_share import read  # noqa: F401
