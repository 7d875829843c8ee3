"""Batcher: real rows per device dispatch, delta coalesced over delta
dispatches across the window."""


def read(src):
    c = src.get("counters") or {}
    n = c.get("oryx_topk_dispatches", 0.0)
    return c.get("oryx_topk_coalesced", 0.0) / n if n else None
