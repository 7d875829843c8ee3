"""Batched encoder step: the share of the host's turn (`stepper.pick`,
`.prefill`, `.step`, `.distribute`) in which the stepper's thread was not on
a CPU, in percent: waiting for the interpreter lock, the runtime, a lock."""

from benchmarks.metrics import _regions
from benchmarks.metrics.stepper_host_ms_per_cycle import TURN


def read(src):
    return _regions.offcpu_share(src, *TURN)
