"""Batched encoder step: the jitted calls of one cycle: wall of
`stepper.prefill.call` + `stepper.step.call` over the count of `stepper.pick`
(one a cycle), in ms. Since PR 42 a call transfers the dispatch's host
operands itself (numpy arrays ride the call's own argument path), so this is
the runtime launching a program of a hundred buffers plus five small
transfers; a part of `stepper_host_ms_per_cycle`."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.mean_ms(src, ("stepper.prefill.call", "stepper.step.call"), "stepper.pick")
