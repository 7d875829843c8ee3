"""Load generator: 95th percentile of the latencies of the window's correct
responses, each from its DUE time (benchmarks/latency.py). It was an
end-to-end metric until PR 27 and is read here because no bound holds it:
it sits at the knee between the requests a collector pause delayed and the
rest, and spreads 10-37 % from run to run (PERF.md section 2)."""

from benchmarks.latency import percentile


def read(src):
    good = src.get("generator", {}).get("latency_ms")
    return percentile(good, 95) if good else None
