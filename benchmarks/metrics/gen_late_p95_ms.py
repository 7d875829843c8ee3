"""Load generator: 95th percentile of (actual send - due time) over the
window's requests, on the generator's own clock. A starved generator shows
here and must not be read as a fast server."""

from benchmarks.latency import percentile


def read(src):
    late = src.get("generator", {}).get("late_ms")
    return percentile(late, 95) if late else None
