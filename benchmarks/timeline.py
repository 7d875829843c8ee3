"""One dispatch timeline on the profiler's clock: the batcher's regions
joined to the fused kernel's device events, and the counters that split
`serialize`.

The program opens the dispatcher thread's stages through `Tracer.region`
(oryx_tpu/common/tracing.py), which enters a `jax.profiler.TraceAnnotation`:
in a traced run they are events on a line of the `/host:CPU` plane, with
their attributes as stats, on the same clock as the device plane's
`XLA Ops`. Per dispatch number n:

    batcher.launch{dispatch=n, rows, padded, k_bucket}
        batcher.issue       (inside the launch, no stats)
    ... the kernel runs, one whole scan after the previous one ...
    batcher.fetch{dispatch=n}        blocks until n's results are on the host
    batcher.distribute{dispatch=n}

A device event carries no dispatch number. The device runs the kernels in
the order the one dispatcher thread issued them, which is the order of the
dispatch numbers, so the join goes by ORDER: the i-th kernel of the window
belongs to dispatch n0 + i. Only n0 comes from the clocks: every fetch
votes for the kernel whose END is the latest at or before its own end (the
fetch returns when that scan's results land), give or take SKEW_NS between
the two planes' clocks, and the offset most fetches agree on is taken, so
a fetch that returned late (after the NEXT scan ended too) is outvoted and
still gets its own kernel. Each pair is then held to the one order a
dispatch can have, issue end <= kernel start < kernel end <= fetch end; a
pair that breaks it (a number that never reached the device, a kernel the
trace lost) and a dispatch of which the window lacks the issue, the fetch
or the kernel (in flight at an edge) are left out and counted.

A reader is handed only `src`: the kind parses the traced run's xplane
once and hands the result over as `src["timeline"]`; an untraced run, and
`{}`, have none. A program without the regions (the parent of PR 25) gives
an empty timeline and every reader None.
"""

from __future__ import annotations

import gzip
from pathlib import Path

from benchmarks.xplane import OPS_LINE

KERNEL = "topk_pallas"
REGION_PREFIX = "batcher."
SKEW_NS = 1e6


def parse(path: str | Path, device_prefix: str = "/device:TPU:") -> dict:
    """{"regions": {name: [event, ...]}, "kernels": [event, ...] | None}.
    An event is {"start", "end"} in ns of the profiler's clock; a region's
    event also holds its stats (dispatch, rows, padded, k_bucket) and the
    index of its thread's `line`. `kernels` are the KERNEL events of the
    first device plane that has ops, or None when the trace has no device
    plane (a CPU run)."""
    from jax.profiler import ProfileData

    path = Path(path)
    raw = gzip.open(path).read() if path.suffix == ".gz" else path.read_bytes()
    regions: dict[str, list[dict]] = {}
    kernels: list[dict] | None = None
    n_line = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(device_prefix) and kernels is None:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    kernels = [
                        {"start": float(ev.start_ns), "end": float(ev.start_ns + ev.duration_ns)}
                        for ev in line.events if KERNEL in ev.name
                    ]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                n_line += 1
                for ev in line.events:
                    if ev.name.startswith(REGION_PREFIX):
                        event = {k: v for k, v in ev.stats if isinstance(v, (int, float))}
                        event.update(
                            start=float(ev.start_ns), end=float(ev.start_ns + ev.duration_ns),
                            line=n_line,
                        )
                        regions.setdefault(ev.name, []).append(event)
    for events in regions.values():
        events.sort(key=lambda e: e["start"])
    return {"regions": regions, "kernels": kernels}


def _inside(events: list[dict], outer: dict) -> dict | None:
    """The one event of the same thread that lies within `outer`."""
    found = [
        e for e in events
        if e["line"] == outer["line"] and e["start"] >= outer["start"] and e["end"] <= outer["end"]
    ]
    return found[0] if len(found) == 1 else None


def join(timeline: dict) -> dict | None:
    """{"dispatches": [{dispatch, launch, issue, fetch, kernel}, ...],
    "left_out": n} — each value an event — or None without a device plane.
    `left_out` counts the dispatch numbers seen on a launch or a fetch that
    are not joined: one of issue, fetch and kernel is missing, or the pair
    breaks issue end <= kernel start, kernel end <= fetch end."""
    kernels = timeline.get("kernels")
    if kernels is None:
        return None
    regions = timeline["regions"]
    launches = {e["dispatch"]: e for e in regions.get("batcher.launch", []) if "dispatch" in e}
    fetches = {e["dispatch"]: e for e in regions.get("batcher.fetch", []) if "dispatch" in e}
    kernels = sorted(kernels, key=lambda e: e["start"])
    # the one time match: dispatch number minus kernel index, by the fetches' vote
    votes: dict[int, int] = {}
    for n, fetch in fetches.items():
        ended = [i for i, k in enumerate(kernels) if k["end"] <= fetch["end"] + SKEW_NS]
        if ended:
            votes[n - ended[-1]] = votes.get(n - ended[-1], 0) + 1
    seen = sorted(set(launches) | set(fetches))
    joined = []
    if votes:
        # on a tie the larger offset, the earlier kernel: a fetch is late, never early
        n0 = max(votes, key=lambda offset: (votes[offset], offset))
        for n in seen:
            launch, fetch = launches.get(n), fetches.get(n)
            issue = _inside(regions.get("batcher.issue", []), launch) if launch else None
            if issue is None or fetch is None or not 0 <= n - n0 < len(kernels):
                continue
            kernel = kernels[n - n0]
            if issue["end"] <= kernel["start"] + SKEW_NS and kernel["end"] <= fetch["end"] + SKEW_NS:
                joined.append(
                    {"dispatch": n, "launch": launch, "issue": issue, "fetch": fetch, "kernel": kernel}
                )
    return {"dispatches": joined, "left_out": len(seen) - len(joined)}


def joined_of(src: dict) -> dict | None:
    """join() of the timeline a reader reads, or None without one."""
    timeline = src.get("timeline")
    return join(timeline) if timeline else None


def mean_ms(spans_ns: list[float]) -> float | None:
    return sum(spans_ns) / len(spans_ns) * 1e-6 if spans_ns else None


def region_ms(src: dict, name: str) -> float | None:
    """Mean duration of every `name` region in the traced window, in ms."""
    timeline = src.get("timeline")
    if not timeline:
        return None
    return mean_ms([e["end"] - e["start"] for e in timeline["regions"].get(name, [])])


def joined_ms(src: dict, span) -> float | None:
    """Mean over the joined dispatches of span(dispatch) ns, in ms."""
    joined = joined_of(src)
    return mean_ms([span(d) for d in joined["dispatches"]]) if joined else None


def counter_mean_ms(src: dict, family: str, label: str) -> float | None:
    """delta `<family>_sum{label}` / delta `<family>_count{label}` over the
    window, in ms: the mean of a histogram series' observations there."""
    c = src.get("counters") or {}
    n = c.get(f"{family}_count{{{label}}}", 0.0)
    return c.get(f"{family}_sum{{{label}}}", 0.0) / n * 1e3 if n else None


def post_stage_ms(src: dict, stage: str) -> float | None:
    """Mean `oryx_post_stage_seconds{stage}` per answer over the window: one
    of the three parts of `serialize` (oryx_tpu/common/perfattr.py)."""
    return counter_mean_ms(src, "oryx_post_stage_seconds", f'stage="{stage}"')
