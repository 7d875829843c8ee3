"""ALS serving model (apps/als/serving.py): PhaseLedger `serialize`, which
is anchored at the end of the device phase and so holds result fan-out, the
pool hop, _rerank_exact, trim and JSON; mean per request."""

from benchmarks.metrics._phases import per_request_ms


def read(src):
    return per_request_ms(src, ("serialize",))
