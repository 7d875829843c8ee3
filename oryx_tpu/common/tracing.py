"""Lightweight end-to-end tracing: spans, ring buffer, W3C propagation.

The reference delegates all observability to the Spark UI and rate-limited
log lines (SURVEY.md §5); PR 1's Prometheus registry added aggregate
counters, but counters cannot answer the question a lambda architecture
lives or dies by: *where did this request's latency go* — header parse vs.
route vs. batcher queue-wait vs. device dispatch. tf.data (arXiv
2101.12127) and TensorFlow (arXiv 1605.08695) both attribute pipeline time
to stages for exactly this reason. This module is the substrate:

- ``Span``: name + attrs + parent + monotonic start/end, grouped by a
  128-bit trace id. Spans form trees: an HTTP request span parents the
  auth/dispatch/respond stages and the micro-batcher's queue-wait and
  device spans, even across the worker-pool thread hop.
- A bounded per-process ring buffer of finished spans. Writers claim slots
  through an ``itertools.count`` (atomic under the GIL) — no lock on the
  record path, the oldest span is simply overwritten.
- W3C ``traceparent`` parse/format, so external callers can stitch serving
  spans into their own traces and bus publish stamps can carry the batch
  generation's context to the serving tier (common/freshness.py).
- Export as a span forest (``/debug/traces``) or Chrome trace-event JSON
  (``?format=chrome``) that opens directly in Perfetto next to the
  ``maybe_profile`` TPU traces (common/metrics.py).

Tracing is OFF by default (``oryx.monitoring.tracing.enabled``); every
instrumentation site guards on ``tracer.enabled``, so the disabled cost is
one attribute read per request.

Span-name families emitted by the serving hot path (the /fleet/traces
waterfall groups on these): ``http.request`` roots with ``http.parse`` /
``http.auth`` / ``http.dispatch`` / ``http.respond`` stages, the
batcher's ``batcher.queue_wait`` / ``batcher.device`` /
``batcher.host_score``, ``batcher.compile_stall`` (the first dispatch of
a new shape signature — XLA trace+compile blocking the dispatcher; see
common/perfattr.py), and ``phase.<name>`` children replayed from each
request's phase ledger (``phase.parse`` … ``phase.write``) so the
latency-budget phases line up under the request root even when a phase
ran on another thread. ``batcher.device`` carries ``dispatch=<n>``, the
number of the coalesced dispatch the request waited for.

The dispatcher thread's own timeline is a second family, opened through
``Tracer.region`` and so ALSO written into any running jax.profiler
session, on the device trace's clock: per dispatch
``batcher.launch{dispatch, rows, padded, k_bucket}`` holding
``batcher.issue``, and later ``batcher.fetch{dispatch}`` and
``batcher.distribute{dispatch}`` (docs/observability.md says what each
covers). The stepper's thread and the post pool's open theirs the same
way (``stepper.*``, ``post.*``).

A region also COUNTS, always on, with no profiler session and the ring
off: ``oryx_region_seconds_total{region}`` (wall),
``oryx_region_cpu_seconds_total{region}`` (the thread's own CPU time, for
the regions that ask for it; wall minus CPU is the time the thread was not
running: waiting for the interpreter lock, for a lock of the program, for
the runtime, or descheduled) and
``oryx_regions_total{region}``, in per-thread cells that only their thread
writes and a scrape sums. Each thread's OPEN region is
kept too, so the program can say at any moment what every instrumented
thread is in (``thread_table``: ``GET /debug/threads``), and the event
loops' heartbeat (serving/aserver.py) hands its lateness to
``note_beat``, the witness of the stalls in which every thread waits.
"""

from __future__ import annotations

import collections
import gc
import itertools
import logging
import os
import re
import sys
import threading
import time
from typing import NamedTuple

log = logging.getLogger(__name__)

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)

# Anchor for converting monotonic span times to wall-clock microseconds in
# exports (Chrome trace events want an absolute-ish timebase so separate
# dumps — e.g. a serving trace and a maybe_profile device trace — line up).
_WALL_ANCHOR = time.time()
_MONO_ANCHOR = time.monotonic()


def wall_time_us(monotonic_t: float) -> float:
    """Monotonic timestamp -> wall-clock microseconds since the epoch."""
    return (_WALL_ANCHOR + (monotonic_t - _MONO_ANCHOR)) * 1e6


class SpanContext(NamedTuple):
    """Just the ids — what propagation headers carry."""

    trace_id: str  # 32 lowercase hex chars
    span_id: str   # 16 lowercase hex chars


def parse_traceparent(value: str | None) -> SpanContext | None:
    """W3C trace-context ``traceparent`` -> SpanContext, or None when the
    header is absent/malformed (per spec, invalid headers are ignored and
    a new trace starts)."""
    if not value:
        return None
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    version, trace_id, span_id, _flags = m.groups()
    if version == "ff":  # forbidden by the spec
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:  # all-zero ids invalid
        return None
    return SpanContext(trace_id, span_id)


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


class Span:
    """One timed operation. Finished child spans append themselves to
    ``children`` (bounded) so a slow-request log can print the breakdown
    without scanning the ring."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "parent",
        "start", "end", "attrs", "tid", "seq", "children",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        parent_id: str | None,
        start: float,
        attrs: dict,
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.parent: "Span | None" = None
        self.start = start
        self.end: float | None = None
        self.attrs = attrs
        self.tid = threading.get_ident()
        self.seq = -1
        self.children: list["Span"] = []

    @property
    def duration(self) -> float:
        return (self.end - self.start) if self.end is not None else 0.0

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"Span({self.name!r}, {self.duration * 1000:.2f}ms, "
            f"trace={self.trace_id[:8]}..)"
        )


_MAX_CHILDREN = 128  # per-span bound: a runaway handler can't grow a tree


class Tracer:
    """Span factory + bounded ring buffer of finished spans.

    The record path is lock-free-ish: slot indices come from an
    ``itertools.count`` (its ``next`` is a single C call, atomic under the
    GIL) and list item assignment is likewise atomic, so concurrent
    writers — event loops, worker threads, the batcher dispatcher — never
    block each other; at worst two spans race for the same wrapped slot
    and one overwrites the other, which a *bounded* buffer accepts by
    design.
    """

    def __init__(self, capacity: int = 2048):
        self.enabled = False
        self.slow_threshold: float | None = None
        # the ring and its slot counter are REBOUND together (configure's
        # capacity change, clear) under _cfg_lock so a concurrent
        # reconfigure can't pair a fresh counter with the old buffer.
        # Writes-only guarding: slot writes in _record and snapshot reads
        # bind the list locally and are seq-claimed lock-free by design.
        self._cfg_lock = threading.Lock()
        self._buf: list[Span | None] = [None] * max(16, capacity)  # guarded-by: _cfg_lock (writes)
        self._seq = itertools.count()  # guarded-by: _cfg_lock (writes)

    @property
    def capacity(self) -> int:
        return len(self._buf)

    def configure(
        self,
        enabled: bool | None = None,
        capacity: int | None = None,
        slow_threshold: float | None | type(...) = ...,
    ) -> None:
        if capacity is not None and capacity != len(self._buf):
            with self._cfg_lock:
                self._buf = [None] * max(16, capacity)
                self._seq = itertools.count()
        if enabled is not None:
            self.enabled = bool(enabled)
        if slow_threshold is not ...:
            self.slow_threshold = (
                float(slow_threshold) if slow_threshold is not None else None
            )

    # -- span lifecycle ----------------------------------------------------

    def start(
        self,
        name: str,
        parent: "Span | SpanContext | None" = None,
        start: float | None = None,
        **attrs,
    ) -> Span | None:
        """New span, or None when tracing is disabled (call sites pass the
        None straight back into finish()/record_interval(), which absorb
        it — no branching needed beyond the hot-path ``enabled`` guard).
        ``start`` backdates the span to an already-captured monotonic
        time."""
        if not self.enabled:
            return None
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            trace_id, parent_id = _new_id(16), None
        s = Span(
            name, trace_id, parent_id,
            start if start is not None else time.monotonic(), attrs,
        )
        if isinstance(parent, Span):
            s.parent = parent
        return s

    def finish(self, span: Span | None, **attrs) -> None:
        if span is None:
            return
        if attrs:
            span.attrs.update(attrs)
        if span.end is None:
            span.end = time.monotonic()
        self._record(span)

    def record_interval(
        self,
        name: str,
        start: float,
        end: float | None = None,
        parent: "Span | SpanContext | None" = None,
        **attrs,
    ) -> Span | None:
        """Create-and-finish in one call, for stages whose edges were
        captured as plain monotonic floats (queue-wait, header parse)."""
        if not self.enabled:
            return None
        s = self.start(name, parent=parent, start=start, **attrs)
        if s is not None:
            s.end = end if end is not None else time.monotonic()
            self._record(s)
        return s

    def region(self, name: str, cpu: bool = False, **attrs) -> "_Region":
        """Context manager for a span bound to the calling thread, on both
        clocks: it ALWAYS enters a ``jax.profiler.TraceAnnotation`` (which
        costs well under a microsecond with no profiler session, and with
        one puts the region into the xplane beside the device's ops), and
        it records a ``Span`` into the ring only while tracing is enabled;
        and it ALWAYS adds its wall time and one to the thread's cell of
        ``name`` (the ``oryx_region_*`` counters, whose label is the name
        alone) and is the thread's open region meanwhile. ``cpu=True`` adds
        the thread's CPU time too: two ``time.thread_time`` calls, each a
        5.9 us system call on the serving host where the wall clock is
        0.09 us (my chip run, PR 39), so only the regions whose off-CPU
        share some reader reads ask for it, and only they have the series.
        The span parents to the thread's current span and is the current
        span inside the block, so nested regions form a tree. In a process
        without jax only the ring half exists."""
        return _Region(self, name, attrs, cpu)

    def _record(self, span: Span) -> None:
        span.seq = next(self._seq)
        buf = self._buf
        buf[span.seq % len(buf)] = span
        p = span.parent
        if p is not None and len(p.children) < _MAX_CHILDREN:
            p.children.append(span)

    # -- reading -----------------------------------------------------------

    def snapshot(self) -> list[Span]:
        """Finished spans currently in the ring, oldest first."""
        spans = [s for s in list(self._buf) if s is not None and s.end is not None]
        spans.sort(key=lambda s: s.seq)
        return spans

    def clear(self) -> None:
        with self._cfg_lock:
            self._buf = [None] * len(self._buf)

    # -- slow-request log --------------------------------------------------

    def log_if_slow(self, span: Span | None, logger: logging.Logger) -> None:
        """WARN with the full per-stage breakdown when a finished request
        span exceeds ``oryx.monitoring.slow-request-threshold``."""
        th = self.slow_threshold
        if th is None or span is None or span.end is None:
            return
        total = span.duration
        if total < th:
            return
        stages = ", ".join(
            f"{c.name}={c.duration * 1000.0:.1f}ms"
            for c in span.children
            if c.end is not None
        )
        logger.warning(
            "slow request %s %s: %.1f ms total (threshold %.0f ms)%s",
            span.attrs.get("method", "?"),
            span.attrs.get("target", span.name),
            total * 1000.0,
            th * 1000.0,
            f" — {stages}" if stages else "",
        )


# -- current-span propagation (thread-scoped) -------------------------------
#
# The serving dispatch path is synchronous within one thread (event loop for
# nonblocking routes, a worker-pool thread otherwise): ServingApp sets the
# request span as "current" around _dispatch, and everything the handler
# calls synchronously — notably TopKBatcher.submit_nowait — picks it up as
# the parent without every signature in between carrying a span argument.

_tls = threading.local()


def current_span() -> Span | None:
    return getattr(_tls, "span", None)


def swap_current(span: Span | None) -> Span | None:
    """Install ``span`` as the thread's current span; returns the previous
    one for restoration (always restore in a finally)."""
    prev = getattr(_tls, "span", None)
    _tls.span = span
    return prev


# -- regions: thread-bound spans, also on the profiler's clock ---------------

_annotation_cls = ...  # Ellipsis = jax not looked for yet; None = no jax


def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported once on first use (the fleet
    front imports this module and needs no jax), or None without jax."""
    global _annotation_cls
    if _annotation_cls is ...:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = None
        _annotation_cls = TraceAnnotation
    return _annotation_cls


class _ThreadRegions:
    """One thread's account of its regions. Only the owning thread writes:
    `cells` is {region: [wall s, CPU s, count]}; `open` the innermost open
    region as (name, monotonic start) or None; `long` the innermost region
    of STALL_S or more that it last left, as (name, start, end)."""

    __slots__ = ("thread", "name", "cells", "open", "long")

    def __init__(self):
        self.thread = threading.current_thread()
        self.name = self.thread.name
        self.cells: dict[str, list[float]] = {}
        self.open: tuple[str, float] | None = None
        self.long: tuple[str, float, float] | None = None


_region_lock = threading.Lock()
_region_threads: list[_ThreadRegions] = []  # guarded-by: _region_lock
# the cells of threads that have exited, so the counters stay monotonic
_region_retired: dict[str, list[float]] = {}  # guarded-by: _region_lock


def _thread_regions() -> _ThreadRegions:
    st = getattr(_tls, "regions", None)
    if st is None:
        st = _tls.regions = _ThreadRegions()
        with _region_lock:
            _region_threads.append(st)
    return st


def _new_cell(st: _ThreadRegions, name: str, cpu: bool) -> list[float]:
    """A thread's first entry of a region: the one place that takes a lock
    (the registry's), so that the region's series are on /metrics whatever
    cleared the registry since the name was last seen."""
    cell = st.cells[name] = [0.0, 0.0, 0]
    if cpu:
        _cpu_regions.add(name)
    _bind_region(name)
    return cell


class _Region:
    """What ``Tracer.region`` returns; see there."""

    __slots__ = (
        "_tracer", "_name", "_attrs", "_annotation", "_span", "_prev",
        "_st", "_cell", "_outer", "_t0", "_c0", "_cpu",
    )

    def __init__(self, tracer: Tracer, name: str, attrs: dict, cpu: bool = False):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._cpu = cpu
        self._annotation = None
        self._span = None
        self._prev = None

    def __enter__(self) -> "_Region":
        st = self._st = _thread_regions()
        self._cell = st.cells.get(self._name) or _new_cell(st, self._name, self._cpu)
        self._outer = st.open
        # the clocks are read OUTSIDE the annotation: entering or leaving one
        # may give the interpreter lock away (the dispatcher's first one
        # after it has set its requests' futures does, to the threads it
        # woke), and that wait is the region's, or the regions would not
        # tile. The wall clock is read outside the CPU clock, so a region
        # that never leaves the CPU reads CPU <= wall
        t0 = self._t0 = time.monotonic()
        self._c0 = time.thread_time() if self._cpu else None
        st.open = (self._name, t0)
        cls = _trace_annotation()
        if cls is not None:
            self._annotation = cls(self._name, **self._attrs)
            self._annotation.__enter__()
        if self._tracer.enabled:
            self._span = self._tracer.start(
                self._name, parent=current_span(), **self._attrs
            )
            self._prev = swap_current(self._span)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._span is not None:
            swap_current(self._prev)
            self._tracer.finish(self._span)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        cell, t0 = self._cell, self._t0
        if self._c0 is not None:
            cell[1] += time.thread_time() - self._c0
        t1 = time.monotonic()
        cell[0] += t1 - t0
        cell[2] += 1
        st = self._st
        st.open = self._outer
        if t1 - t0 >= STALL_S and (st.long is None or st.long[1] < t0):
            # the innermost wins: a child that was as long leaves first,
            # and its parent, which started before it, does not replace it
            st.long = (self._name, t0, t1)


def thread_region_seconds(name: str) -> float:
    """Wall seconds the CALLING thread has spent in region `name`: what a
    dispatcher reads to take a region's delta between two of its own
    events without a second stopwatch."""
    cell = _thread_regions().cells.get(name)
    return cell[0] if cell is not None else 0.0


def _live_cells() -> list[dict[str, list[float]]]:  # oryxlint: holds=_region_lock
    """The live threads' cells, after folding the exited threads' into
    `_region_retired` (so a counter never falls when a thread ends)."""
    for st in list(_region_threads):
        if not st.thread.is_alive():
            _region_threads.remove(st)
            _fold(_region_retired, st.cells)
    return [st.cells for st in _region_threads]


def _fold(into: dict[str, list[float]], cells: dict[str, list[float]]) -> None:
    for name, cell in list(cells.items()):
        total = into.setdefault(name, [0.0, 0.0, 0])
        for i in range(3):
            total[i] += cell[i]


def region_totals() -> dict[str, tuple[float, float, float]]:
    """{region: (wall s, CPU s, count)} summed over every thread that has
    entered one, the exited ones included. A thread's cell may move under
    the read: the three numbers of a region still open elsewhere are each
    right and need not be of the same instant."""
    out: dict[str, list[float]] = {}
    with _region_lock:
        live = _live_cells()
        _fold(out, _region_retired)
    for cells in live:
        _fold(out, cells)
    return {name: (c[0], c[1], c[2]) for name, c in out.items()}


def _region_value(name: str, i: int) -> float:
    """One series of one region: what a scrape's callback reads."""
    with _region_lock:
        live = _live_cells()
        total = _region_retired.get(name, (0.0, 0.0, 0))[i]
    for cells in live:
        cell = cells.get(name)
        if cell is not None:
            total += cell[i]
    return float(total)


# the regions some thread has entered with cpu=True: only they have a series
# of the CPU family (a 0 there would read "never on the CPU")
_cpu_regions: set[str] = set()

_REGION_FAMILIES = (
    ("oryx_region_seconds_total",
     "Wall seconds the program's threads spent in each region "
     "(Tracer.region), by region"),
    ("oryx_region_cpu_seconds_total",
     "CPU seconds of the thread's own (time.thread_time) inside each region "
     "that asks for them, by region; wall minus CPU is the time the thread "
     "was not running: waiting for the interpreter lock, a lock, the "
     "runtime, or descheduled"),
    ("oryx_regions_total", "Regions entered and left, by region"),
)


def _bind_region(name: str) -> None:
    from oryx_tpu.common.metrics import get_registry

    reg = get_registry()
    for i, (family, help_text) in enumerate(_REGION_FAMILIES):
        if i == 1 and name not in _cpu_regions:
            continue
        reg.counter(family, help_text, labeled=True).set_function(
            lambda i=i: _region_value(name, i), region=name
        )


def thread_table(now: float | None = None) -> list[dict]:
    """What every live thread that has entered a region is in right now:
    [{"thread", "region" (None between regions), "age_s", and "long": the
    innermost region of STALL_S or more it last left, as {"region",
    "seconds", "ended_s_ago"}}]."""
    now = time.monotonic() if now is None else now
    with _region_lock:
        threads = [st for st in _region_threads if st.thread.is_alive()]
    rows = []
    for st in threads:
        open_, long_ = st.open, st.long
        row = {
            "thread": st.name,
            "region": open_[0] if open_ else None,
            "age_s": round(now - open_[1], 6) if open_ else None,
        }
        if long_ is not None:
            row["long"] = {
                "region": long_[0],
                "seconds": round(long_[2] - long_[1], 6),
                "ended_s_ago": round(now - long_[2], 6),
            }
        rows.append(row)
    return rows


# -- thread names the profiler can show -------------------------------------

_PR_SET_NAME = 15


def name_thread(name: str) -> None:
    """Give the CALLING thread an OS name (Linux `prctl(PR_SET_NAME)`, 15
    bytes; a no-op elsewhere). Python 3.12 does not hand a thread's name
    to the OS, and the profiler's host lines are named from the OS: call
    it as the thread starts, before its first region. The thread's rows
    of `thread_table` take the name too."""
    _thread_regions().name = name
    if not sys.platform.startswith("linux"):
        return
    try:
        import ctypes

        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong, ctypes.c_ulong,
            ctypes.c_ulong,
        ]
        libc.prctl.restype = ctypes.c_int
        libc.prctl(_PR_SET_NAME, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):  # no libc to ask: the name is cosmetic
        pass


# -- the stall witness --------------------------------------------------------
#
# Runs in which every request waits about a second while the device does what
# it always does had no span over them. An event loop that re-arms a short
# timer sees such a stall as the timer firing late, and what it can read then
# tells the candidates apart: the process's CPU time over the beat (near zero:
# descheduled, or every thread blocked; near the lateness: ONE thread held the
# interpreter lock), the collections that ran in it, and what every
# instrumented thread is in or has just left.

BEAT_S = 0.05   # the event loops' heartbeat (serving/aserver.py)
STALL_S = 0.1   # a beat this late is a stall; a region this long is kept
LOOP_LAG_BUCKETS = (0.0005, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

# collections of 5 ms or more: (monotonic start, seconds, generation)
_collections: collections.deque = collections.deque(maxlen=64)
_gc_started = [0.0]
_stall_lock = threading.Lock()
_stalled_until = 0.0  # guarded-by: _stall_lock


def _on_gc(phase: str, info: dict) -> None:
    now = time.monotonic()
    if phase == "start":
        _gc_started[0] = now
    elif now - _gc_started[0] >= 0.005:
        _collections.append((_gc_started[0], now - _gc_started[0], info.get("generation")))


def _stall_metrics():
    """(lag histogram, stall seconds, stalls) on the global registry."""
    from oryx_tpu.common.metrics import get_registry

    reg = get_registry()
    return (
        reg.histogram(
            "oryx_http_loop_lag_seconds",
            "How late each event loop's 50 ms heartbeat fired, by loop: the "
            "time a ready callback waited for the loop's thread",
            buckets=LOOP_LAG_BUCKETS,
        ),
        reg.counter(
            "oryx_stall_seconds_total",
            "Seconds in which an event loop's heartbeat was 100 ms or more "
            "overdue (a stall that several loops see counts once)",
        ),
        reg.counter(
            "oryx_stalls_total",
            "Stalls: an event loop's heartbeat fired 100 ms late or more",
        ),
    )


def arm_stall_witness() -> None:
    """What the event loops' owner calls as they start: the witness's
    `gc.callbacks` entry (once a process) and its three families, so that a
    process that never stalls reads 0 and not nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    _stall_metrics()


def note_beat(loop: str, late_s: float, cpu_s: float) -> dict | None:
    """One heartbeat of event loop `loop` fired just now, `late_s` after it
    was due and `cpu_s` of process CPU time after it was armed, BEAT_S
    before it was due.
    Observes the lag; a beat STALL_S late or more is a stall: counted,
    logged as one WARNING line and recorded as a flight event `stall`.
    Returns the stall's fields, or None."""
    lag, stall_seconds, stalls = _stall_metrics()
    late_s = max(0.0, late_s)
    lag.observe(late_s, loop=loop)
    if late_s < STALL_S:
        return None
    now = time.monotonic()
    # a stall of the whole process is seen by every loop: the first to fire
    # counts it and writes the line, the others add what it had not seen yet
    global _stalled_until
    with _stall_lock:
        fresh = now - late_s >= _stalled_until
        unseen = min(late_s, now - _stalled_until)
        _stalled_until = now
    stall_seconds.inc(max(0.0, unseen))
    if not fresh:
        return None
    stalls.inc()
    stall = {
        "loop": loop,
        "late_s": round(late_s, 4),
        "process_cpu_s": round(cpu_s, 4),
        "collections": [
            {"generation": gen, "seconds": round(s, 4)}
            for t, s, gen in list(_collections) if t + s >= now - late_s - BEAT_S
        ],
        # what each thread is in, or the long region it left inside the beat
        "threads": [
            row for row in thread_table(now)
            if row["region"] or row.get("long", {}).get("ended_s_ago", late_s) < late_s
        ],
    }
    log.warning(
        "stall: loop %s heartbeat %.0f ms late, process CPU %.0f ms in the "
        "beat, collections %s, threads %s",
        loop, late_s * 1e3, cpu_s * 1e3, stall["collections"], stall["threads"],
    )
    from oryx_tpu.common.flightrec import get_flightrec

    get_flightrec().record(kind="stall", **stall)
    return stall


# -- export -----------------------------------------------------------------


def chrome_trace(spans: list[Span]) -> dict:
    """Chrome trace-event JSON (`ph: "X"` complete events) — open the dump
    directly in Perfetto/chrome://tracing, alongside maybe_profile's TPU
    traces (the shared wall-clock timebase lines the two up)."""
    pid = os.getpid()
    events = []
    for s in spans:
        events.append({
            "name": s.name,
            "cat": "oryx",
            "ph": "X",
            "ts": wall_time_us(s.start),
            "dur": max(0.0, s.duration) * 1e6,
            "pid": pid,
            "tid": s.tid,
            "args": {
                "trace_id": s.trace_id,
                "span_id": s.span_id,
                "parent_id": s.parent_id or "",
                **s.attrs,
            },
        })
    return {"displayTimeUnit": "ms", "traceEvents": events}


def span_forest(spans: list[Span]) -> list[dict]:
    """Spans -> list of nested trees (roots = spans whose parent is not in
    the snapshot, e.g. evicted from the ring or remote)."""
    nodes: dict[str, dict] = {}
    for s in spans:
        nodes[s.span_id] = {
            "name": s.name,
            "trace_id": s.trace_id,
            "span_id": s.span_id,
            "parent_id": s.parent_id,
            "start_ms": round(wall_time_us(s.start) / 1000.0, 3),
            "duration_ms": round(s.duration * 1000.0, 3),
            "attrs": dict(s.attrs),
            "children": [],
        }
    roots: list[dict] = []
    for s in spans:
        node = nodes[s.span_id]
        parent = nodes.get(s.parent_id) if s.parent_id else None
        if parent is None or parent is node:
            roots.append(node)
        else:
            parent["children"].append(node)
    return roots


# -- cross-process stitching ------------------------------------------------
#
# One process's ring answers "where did this request's latency go HERE";
# a fleet answers it only when the front's span tree and every replica's
# can be laid side by side under one trace id. The helpers below take
# span FORESTS (the /debug/traces JSON shape, which crosses process
# boundaries as plain dicts) from N processes and stitch them: grouped
# by trace id, or exported as one Chrome trace with a LANE PER PROCESS
# (Perfetto renders each pid as its own track, so front queueing vs
# replica dispatch vs device time line up on the shared wall clock).


def flatten_forest(roots: list[dict]) -> list[dict]:
    """Forest (nested ``children``) -> flat span list, children stripped.
    Tolerant of foreign dicts: nodes without a trace_id are dropped."""
    out: list[dict] = []
    stack = [r for r in roots if isinstance(r, dict)]
    while stack:
        node = stack.pop()
        kids = node.get("children") or []
        stack.extend(k for k in kids if isinstance(k, dict))
        if node.get("trace_id"):
            flat = {k: v for k, v in node.items() if k != "children"}
            out.append(flat)
    return out


def stitch_traces(
    processes: list[tuple[str, list[dict]]]
) -> list[dict]:
    """[(process label, span forest)] -> one entry per trace id, spans
    labeled with their owning process, ordered by earliest span start.
    Duplicate span ids across sources (co-resident processes sharing a
    ring in tests) keep the first occurrence only."""
    by_trace: dict[str, list[dict]] = {}
    seen: set[tuple[str, str]] = set()
    for label, forest in processes:
        for span in flatten_forest(forest):
            key = (span["trace_id"], span.get("span_id", ""))
            if key in seen:
                continue
            seen.add(key)
            by_trace.setdefault(span["trace_id"], []).append(
                {"process": label, **span}
            )
    out = []
    for trace_id, spans in by_trace.items():
        spans.sort(key=lambda s: s.get("start_ms", 0.0))
        out.append({
            "trace_id": trace_id,
            "processes": sorted({s["process"] for s in spans}),
            "spans": spans,
        })
    out.sort(key=lambda t: t["spans"][0].get("start_ms", 0.0))
    return out


def stitched_chrome(processes: list[tuple[str, list[dict]]]) -> dict:
    """[(process label, span forest)] -> Chrome trace-event JSON with one
    pid lane per process (``process_name`` metadata names the lanes), so
    the stitched artifact opens in Perfetto with the front and each
    replica as separate tracks on the shared wall-clock timebase."""
    events: list[dict] = []
    seen: set[tuple[str, str]] = set()
    for pid, (label, forest) in enumerate(processes, start=1):
        events.append({
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": label},
        })
        for span in flatten_forest(forest):
            key = (span["trace_id"], span.get("span_id", ""))
            if key in seen:
                continue
            seen.add(key)
            events.append({
                "name": span.get("name", "?"),
                "cat": "oryx-fleet",
                "ph": "X",
                "ts": float(span.get("start_ms", 0.0)) * 1000.0,
                "dur": max(0.0, float(span.get("duration_ms", 0.0))) * 1000.0,
                "pid": pid,
                "tid": 1,
                "args": {
                    "process": label,
                    "trace_id": span["trace_id"],
                    "span_id": span.get("span_id", ""),
                    "parent_id": span.get("parent_id") or "",
                    **(span.get("attrs") or {}),
                },
            })
    return {"displayTimeUnit": "ms", "traceEvents": events}


# -- process-global tracer --------------------------------------------------

_default = Tracer()


def get_tracer() -> Tracer:
    return _default


def configure_tracing(config) -> Tracer:
    """Apply the oryx.monitoring.* tracing keys to the global tracer (each
    layer runtime calls this at construction; last writer wins, which is
    what one config per process means)."""
    tr = _default
    tr.configure(
        enabled=config.get_bool("oryx.monitoring.tracing.enabled", False),
        capacity=config.get_int("oryx.monitoring.tracing.buffer-size", 2048),
        slow_threshold=config.get("oryx.monitoring.slow-request-threshold", None),
    )
    # the regions' series, again: a registry cleared since a name was first
    # entered (tests do) has lost them, and a thread's later entries bind nothing
    for name in region_totals():
        _bind_region(name)
    return tr
