"""Batcher: distinct dispatch shapes among the window's DispatchRecords
(common/perfstats.py). The record has no k-bucket field; its bytes_moved is
padded*features*4 + view bytes + padded*k_bucket*8 (batcher._dispatch_bytes),
so (padded_rows, bytes_moved) is one value per (padded rows, k-bucket).
1 by design; 2 means a second k-bucket or the 4096-row bucket was hit."""


def read(src):
    recs = src.get("dispatch_records")
    if not recs:
        return None
    return float(len({(r["padded_rows"], r["bytes_moved"]) for r in recs}))
