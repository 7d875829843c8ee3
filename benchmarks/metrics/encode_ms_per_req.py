"""Batched encoder step (serving/stepper.py): PhaseLedger `encode`, mean per
request: submission to the stepper until the block's last hidden states are
on the host (waiting for a slot and a cycle, the prefill's cycle, the
remaining steps' cycles). Its parts are `oryx_seq_encode_stage_seconds`."""

from benchmarks import timeline


def read(src):
    return timeline.counter_mean_ms(src, "oryx_request_phase_seconds", 'phase="encode"')
