"""Batcher: distinct dispatch shapes, (padded rows, k-bucket), among the
window's DispatchRecords (common/perfstats.py). 1 by design; 2 means a
second k-bucket or the 4096-row bucket was hit."""


def read(src):
    recs = src.get("dispatch_records")
    if not recs:
        return None
    return float(len({(r["padded_rows"], r["k_bucket"]) for r in recs}))
