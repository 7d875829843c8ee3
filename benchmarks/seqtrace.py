"""Split a traced window's device time by jitted program and by the
`jax.named_scope`s inside it (kind `seq-serving`).

The device plane's `XLA Modules` line holds one event per executed program
(`jit_prefill(<fingerprint>)`), its `XLA Ops` line one per executed HLO
instruction, named by the instruction's text (`%fusion.12 = ...`). An op
event carries no scope: the scope is in the COMPILED program's text, as the
`op_name` metadata of the instruction of that name
(`jit(denoise_step)/sdar.moe/...`). So an op is given to the program whose
event encloses it in time, and its scope is looked up in that program's
text. Two programs of one name (a prefill at two length buckets) differ in
their instruction names; each executed program takes the text that knows
most of its ops.
"""

from __future__ import annotations

import gzip
import re
from bisect import bisect_right
from pathlib import Path

from benchmarks.xplane import OPS_LINE

MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?op_name=\"([^\"]*)\"")


def program_name(event_name: str) -> str:
    """`jit_prefill(123)` -> `jit_prefill`."""
    return event_name.partition("(")[0]


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def scopes_of(hlo_text: str) -> dict[str, str]:
    """{instruction name: its op_name metadata} of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def parse(path: str | Path, device_prefix: str = "/device:TPU:") -> dict | None:
    """{"programs": {event name: {"count", "seconds", "ops": {op name:
    seconds}}}} of the first device plane that has both lines, or None."""
    from jax.profiler import ProfileData

    path = Path(path)
    raw = gzip.open(path).read() if path.suffix == ".gz" else path.read_bytes()
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue
        modules = sorted(
            (float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
            for ev in lines[MODULES_LINE].events
        )
        starts = [m[0] for m in modules]
        programs: dict[str, dict] = {}
        for start, end, name in modules:
            p = programs.setdefault(name, {"count": 0, "seconds": 0.0, "ops": {}})
            p["count"] += 1
            p["seconds"] += (end - start) * 1e-9
        for ev in lines[OPS_LINE].events:
            i = bisect_right(starts, float(ev.start_ns)) - 1
            if i < 0 or float(ev.start_ns) > modules[i][1]:
                continue  # an op of a program that began before the trace
            ops = programs[modules[i][2]]["ops"]
            name = op_name(ev.name)
            ops[name] = ops.get(name, 0.0) + float(ev.duration_ns) * 1e-9
        return {"programs": programs}
    return None


def split(parsed: dict | None, texts: dict[str, list[str]], scopes: tuple[str, ...]) -> dict:
    """{program name: {"count", "seconds", "scoped": {scope: seconds},
    "unscoped": seconds}} for the programs named in `texts` ({program name:
    the compiled texts of its shapes}). An op counts under the first of
    `scopes` that its op_name holds."""
    out: dict[str, dict] = {}
    if not parsed:
        return out
    tables = {name: [scopes_of(t) for t in ts] for name, ts in texts.items()}
    for event_name, prog in parsed["programs"].items():
        name = program_name(event_name)
        if name not in tables:
            continue
        table = max(tables[name], key=lambda t: sum(1 for op in prog["ops"] if op in t))
        agg = out.setdefault(
            name, {"count": 0, "seconds": 0.0, "scoped": {s: 0.0 for s in scopes}, "unscoped": 0.0}
        )
        agg["count"] += prog["count"]
        agg["seconds"] += prog["seconds"]
        for op, seconds in prog["ops"].items():
            where = table.get(op, "")
            scope = next((s for s in scopes if s in where), None)
            if scope:
                agg["scoped"][scope] += seconds
            else:
                agg["unscoped"] += seconds
    return out
