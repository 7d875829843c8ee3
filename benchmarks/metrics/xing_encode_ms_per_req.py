"""Batched encoder step (serving/stepper.py), kind xing-serving: PhaseLedger
`encode`, mean per request answered: submission to the stepper until the
basket's last hidden state is on the host (waiting for a slot and a cycle, the
prefill's cycle, the remaining steps' cycles). The reader is
`encode_ms_per_req`'s: the phase is the same whatever the encoder."""

from benchmarks.metrics.encode_ms_per_req import read  # noqa: F401
