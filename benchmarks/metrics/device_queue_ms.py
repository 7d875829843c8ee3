"""Batcher: time a dispatch sits in the device's queue, mean over the
joined dispatches (benchmarks/timeline.py) of the kernel's start minus the
end of the dispatch's `batcher.issue`. It runs to the KERNEL's start, so it
holds the dispatch's own lane pad. With one scan queued ahead it is about
one kernel time; a batcher that picks late moves it and not the kernel.

Also prints, on stderr, the identity that proves the join: the means,
over the SAME joined dispatches, of `batcher.issue` + this + the kernel's
own duration + `fetch_tail_ms`, against the mean `device` phase per request
from the PhaseLedger (another clock, another recorder)."""

import sys

from benchmarks import timeline
from benchmarks.metrics import fetch_tail_ms


def read(src):
    joined = timeline.joined_of(src)
    if not joined or not joined["dispatches"]:
        return None

    def mean(span):
        return timeline.mean_ms([span(d) for d in joined["dispatches"]])

    value = mean(lambda d: d["kernel"]["start"] - d["issue"]["end"])
    issue = mean(lambda d: d["issue"]["end"] - d["issue"]["start"])
    kernel = mean(lambda d: d["kernel"]["end"] - d["kernel"]["start"])
    tail = fetch_tail_ms.read(src)
    device = timeline.counter_mean_ms(src, "oryx_request_phase_seconds", 'phase="device"')
    total = issue + value + kernel + tail
    print(
        f"device_queue_ms: {len(joined['dispatches'])} dispatches joined, "
        f"{joined['left_out']} left out; issue {issue:.3f} + queue {value:.3f} + kernel "
        f"{kernel:.3f} + fetch tail {tail:.3f} = {total:.3f} ms; device phase per request "
        + (f"{device:.3f} ms, ratio {total / device:.4f}" if device else "not read"),
        file=sys.stderr,
    )
    return value
