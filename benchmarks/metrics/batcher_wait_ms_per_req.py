"""Batcher (serving/batcher.py): PhaseLedger queue_wait + batch_wait, mean
per request: enqueue until the request's group starts forming. In the
periodic state it is half a dispatch cycle."""

from benchmarks.metrics._phases import per_request_ms


def read(src):
    return per_request_ms(src, ("queue_wait", "batch_wait"))
