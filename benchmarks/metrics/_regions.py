"""Shared by the readers of the regions' counters (oryx_tpu/common/tracing.py
`Tracer.region`): `oryx_region_seconds_total{region}` (wall),
`oryx_region_cpu_seconds_total{region}` (the thread's own CPU time) and
`oryx_regions_total{region}`, as deltas over the window in `src["counters"]`.
A program without the family (the parent of PR 39) gives every reader None;
with it a region the window never entered reads 0.0."""

WALL = "oryx_region_seconds_total"
CPU = "oryx_region_cpu_seconds_total"
COUNT = "oryx_regions_total"


def there(src):
    """Whether the program counts its regions at all."""
    return any(s.startswith(WALL + "{") for s in src.get("counters") or {})


def total(src, family, *regions):
    c = src.get("counters") or {}
    return sum(c.get(f'{family}{{region="{r}"}}', 0.0) for r in regions)


def mean_ms(src, walls, per):
    """Wall of the regions `walls`, over the count of the region `per`, in ms."""
    if not there(src):
        return None
    n = total(src, COUNT, per)
    return total(src, WALL, *walls) / n * 1e3 if n else 0.0


def offcpu_share(src, *regions):
    """1 - CPU / wall over the regions, in percent: the share of their time
    in which the thread was not running."""
    if not there(src):
        return None
    wall = total(src, WALL, *regions)
    return max(0.0, 1.0 - total(src, CPU, *regions) / wall) * 100.0 if wall else 0.0


def window_share(src, *regions):
    """Wall of the regions over the window's length, in percent."""
    window = (src.get("collector") or {}).get("window_s")
    if not there(src) or not window:
        return None
    return total(src, WALL, *regions) / window * 100.0
