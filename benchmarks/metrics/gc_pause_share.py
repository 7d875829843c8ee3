"""Python runtime: share of the window in which CPython's cyclic collector
held every thread of the serving process, in percent: the summed durations
of the collections that started inside the window (timed by the kind through
`gc.callbacks`) over the window's length. A full collection walks the
model's id maps entry by entry (apps/als/state.py: 5M-entry `_ids`, `_rev`,
`expected_y`), 0.31-0.36 s each at 5M items."""


def read(src):
    c = src.get("collector")
    if not c or not c["pauses_s"]:
        return None  # no collection started inside the window: nothing to read
    return sum(c["pauses_s"]) / c["window_s"] * 100.0
