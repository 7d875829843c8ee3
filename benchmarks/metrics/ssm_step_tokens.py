"""Batched encoder step, kind ssm-serving: real tokens per jamba dispatch over
the window (a prefill's session events, a step's live sequences), delta
`oryx_seq_step_tokens_total{tokens="real"}` over delta `oryx_seq_steps_total`."""

from benchmarks.metrics import _ssm


def read(src):
    n = _ssm.all_steps(src)
    return _ssm.all_tokens(src, "real") / n if n else None
