"""Batched encoder step, kind xing-serving: real tokens per xing dispatch over
the window (a prefill's session events, a step's live sequences), delta
`oryx_seq_step_tokens_total{tokens="real"}` over delta `oryx_seq_steps_total`.
The reader is `ssm_step_tokens`'s: the same two kinds of dispatch."""

from benchmarks.metrics.ssm_step_tokens import read  # noqa: F401
