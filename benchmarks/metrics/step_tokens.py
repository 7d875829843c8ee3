"""Batched encoder step: real tokens per encoder dispatch over the window
(a prefill's session events, a step's live block positions), delta
`oryx_seq_step_tokens_total{tokens="real"}` over delta `oryx_seq_steps_total`."""

from benchmarks.metrics import _seq


def read(src):
    n = _seq.all_steps(src)
    return _seq.all_tokens(src, "real") / n if n else None
