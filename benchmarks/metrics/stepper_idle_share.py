"""Batched encoder step: share of the window the stepper's thread spent in
`stepper.idle`, its wait with nothing submitted, nothing in flight and no
engine with work, in percent."""

from benchmarks.metrics import _regions


def read(src):
    return _regions.window_share(src, "stepper.idle")
