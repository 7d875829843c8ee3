"""Batched encoder step: the host's turn between two cycles: wall of
`stepper.pick` + `stepper.prefill` + `stepper.step` + `stepper.distribute`
over the count of `stepper.pick` (one a cycle), in ms. The device idles
through it whenever it has nothing queued; `stepper.fetch`, the wait for the
device, is the rest of a cycle."""

from benchmarks.metrics import _regions

TURN = ("stepper.pick", "stepper.prefill", "stepper.step", "stepper.distribute")


def read(src):
    return _regions.mean_ms(src, TURN, "stepper.pick")
