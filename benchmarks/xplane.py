"""Reduce a JAX profiler trace (.xplane.pb) to device numbers.

Read with nothing but `jax.profiler.ProfileData`. A device plane is named
`/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
instruction (the event name is the instruction's text, so a kernel is
found by a substring of its name). Host threads are the lines of
`/host:CPU`, on the same clock.

An op that is in flight when tracing starts or stops is not recorded, so
the traced window is taken from the first recorded device op's start to
the last one's end: idle time at the very edges is not seen.
"""

from __future__ import annotations

import gzip
from pathlib import Path

OPS_LINE = "XLA Ops"
TOP_N = 10


def find_xplane(trace_dir: str | Path) -> Path | None:
    """The newest trace at or under `trace_dir` (a kind traces into a
    directory of its cell's own, `.bench_out/trace/<workload>`, so that two
    cells run side by side in one checkout never read or remove each
    other's)."""
    found = list(Path(trace_dir).glob("**/plugins/profile/*/*.xplane.pb"))
    return max(found, key=lambda f: f.stat().st_mtime) if found else None


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _label_gap(
    host_events: list[tuple[float, float, str]], gap: tuple[float, float],
    prefer: str | tuple[str, ...] = "",
) -> str:
    """Name of the host event that covers most of the gap; among equals the
    shortest (innermost) one. Where `prefer` is given, an event whose own
    name (after `<thread>:`) starts with it wins over every other: the
    program's regions say what the host was doing, the runtime's events
    only which call it was in. `prefer` may be several prefixes, in order:
    an event of an earlier one wins over any event of a later one (the
    thread that feeds the device before the thread that waits for it)."""
    prefixes = (prefer,) if isinstance(prefer, str) else tuple(prefer)
    best, best_key = "no_host_event", (0, 0.0, 0.0)
    for start, end, name in host_events:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > 0:
            own = name.partition(":")[2]
            rank = next((len(prefixes) - i for i, p in enumerate(prefixes) if p and own.startswith(p)), 0)
            key = (rank, overlap, -(end - start))
            if key > best_key:
                best, best_key = name, key
    return best


def reduce_trace(
    path: str | Path, device_prefix: str = "/device:TPU:", prefer: str | tuple[str, ...] = ""
) -> dict | None:
    """{window_s, busy_s, devices, ops: {name: [count, seconds]}, idle_gaps:
    [[label, seconds], ...]} or None when the trace holds no device op.
    busy_s is the union of device-op intervals, averaged over the devices;
    `prefer` is the prefix, or the ordered prefixes, of the program's own
    regions (see _label_gap)."""
    from jax.profiler import ProfileData

    path = Path(path)
    raw = gzip.open(path).read() if path.suffix == ".gz" else path.read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    per_device: list[list[tuple[float, float]]] = []
    ops: dict[str, list[float]] = {}
    host_events: list[tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            intervals = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start, dur = float(ev.start_ns), float(ev.duration_ns)
                    intervals.append((start, start + dur))
                    tot = ops.setdefault(ev.name, [0, 0.0])
                    tot[0] += 1
                    tot[1] += dur * 1e-9
            if intervals:
                per_device.append(_merge(intervals))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                thread = line.name.split("/")[0]
                for ev in line.events:
                    if ev.duration_ns > 0:
                        start = float(ev.start_ns)
                        host_events.append(
                            (start, start + float(ev.duration_ns), f"{thread}:{ev.name}")
                        )
    if not per_device:
        return None
    t_first = min(m[0][0] for m in per_device)
    t_last = max(m[-1][1] for m in per_device)
    busy = sum(sum(e - s for s, e in m) for m in per_device) / len(per_device)
    # idle gaps of the first device, longest first, named by the host's work
    first = per_device[0]
    gaps = sorted(
        ((first[i][1], first[i + 1][0]) for i in range(len(first) - 1)),
        key=lambda g: g[0] - g[1],
    )[:TOP_N]
    return {
        "window_s": (t_last - t_first) * 1e-9,
        "busy_s": busy * 1e-9,
        "devices": len(per_device),
        "ops": ops,
        "idle_gaps": [[_label_gap(host_events, g, prefer), (g[1] - g[0]) * 1e-9] for g in gaps],
    }


def op_seconds(trace: dict, match: str) -> tuple[int, float]:
    """(events, total seconds) of the device ops whose name contains match."""
    count, total = 0, 0.0
    for name, (n, seconds) in trace["ops"].items():
        if match in name:
            count += n
            total += seconds
    return count, total


def breakdown(trace: dict) -> dict:
    top = sorted(trace["ops"].items(), key=lambda kv: -kv[1][1])[:TOP_N]
    return {
        "device_ops": [[name[:96], seconds] for name, (_, seconds) in top],
        "idle_gaps": [[label[:96], seconds] for label, seconds in trace["idle_gaps"]],
    }
