"""HTTP frontend (serving/aserver.py, serving/app.py): PhaseLedger parse +
auth + write, mean per /recommend answered in the window, from the deltas
of oryx_request_phase_seconds_sum over the window."""

from benchmarks.metrics._phases import per_request_ms


def read(src):
    return per_request_ms(src, ("parse", "auth", "write"))
