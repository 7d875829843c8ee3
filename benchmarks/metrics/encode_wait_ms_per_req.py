"""Batched encoder step: a request's wait for a cache slot and a cycle,
mean per request: `oryx_seq_encode_stage_seconds{stage="encode_wait"}`, the
first part of `encode` (submission to the stepper until its pick admits it)."""

from benchmarks import timeline


def read(src):
    return timeline.counter_mean_ms(src, "oryx_seq_encode_stage_seconds", 'stage="encode_wait"')
