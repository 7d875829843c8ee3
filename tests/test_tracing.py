"""End-to-end tracing: traceparent codec, the span ring buffer, exports,
model freshness, and the serving /debug/traces + /healthz lenses.

Covers the observability substrate (oryx_tpu/common/tracing.py +
freshness.py): stage-attributed spans are what make pipeline bottlenecks
actionable (tf.data, arXiv 2101.12127), so the smoke asserts an actual
loadtest request produces a span tree whose request span contains the
micro-batcher's queue-wait child — the exact attribution later perf PRs
report against.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from oryx_tpu.common.tracing import (
    Tracer,
    chrome_trace,
    format_traceparent,
    parse_traceparent,
    span_forest,
)


# ---- traceparent ----------------------------------------------------------

def test_traceparent_roundtrip():
    trace_id = "0af7651916cd43dd8448eb211c80319c"
    span_id = "b7ad6b7169203331"
    header = format_traceparent(trace_id, span_id)
    assert header == f"00-{trace_id}-{span_id}-01"
    ctx = parse_traceparent(header)
    assert ctx is not None
    assert ctx.trace_id == trace_id
    assert ctx.span_id == span_id


@pytest.mark.parametrize("bad", [
    None,
    "",
    "garbage",
    "00-abc-def-01",                                            # short ids
    "00-0af7651916cd43dd8448eb211c80319-b7ad6b7169203331-01",   # 31-char trace
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b716920333-01",   # 15-char span
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",  # version ff
    "00-" + "0" * 32 + "-b7ad6b7169203331-01",                  # zero trace id
    "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",  # zero span id
    "00-0af7651916cd43dd8448eb211c80319X-b7ad6b7169203331-01",  # non-hex
])
def test_traceparent_rejects_invalid(bad):
    assert parse_traceparent(bad) is None


def test_traceparent_case_and_whitespace_normalized():
    header = "  00-0AF7651916CD43DD8448EB211C80319C-B7AD6B7169203331-01  "
    ctx = parse_traceparent(header)
    assert ctx is not None and ctx.trace_id.islower()


# ---- ring buffer ----------------------------------------------------------

def test_disabled_tracer_records_nothing():
    tr = Tracer(capacity=32)
    assert tr.start("x") is None
    tr.finish(None)  # absorbing None is the contract
    assert tr.record_interval("y", time.monotonic()) is None
    assert tr.snapshot() == []


def test_span_parenting_and_attrs():
    tr = Tracer(capacity=32)
    tr.configure(enabled=True)
    root = tr.start("req", method="GET")
    child = tr.start("stage", parent=root, k=16)
    tr.finish(child)
    tr.finish(root, status=200)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child in root.children
    assert root.attrs == {"method": "GET", "status": 200}
    spans = tr.snapshot()
    assert [s.name for s in spans] == ["stage", "req"]  # finish order


def test_ring_wraparound_under_concurrent_writers():
    tr = Tracer(capacity=64)
    tr.configure(enabled=True)
    n_threads, per_thread = 8, 200

    def work(i: int) -> None:
        for j in range(per_thread):
            s = tr.start(f"w{i}", j=j)
            tr.finish(s)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.snapshot()
    # bounded: never more than capacity, and the ring holds the newest
    assert 0 < len(spans) <= 64
    # every surviving span is finished and well-formed
    assert all(s.end is not None and s.end >= s.start for s in spans)
    assert all(len(s.trace_id) == 32 and len(s.span_id) == 16 for s in spans)
    # snapshot is ordered by record sequence
    seqs = [s.seq for s in spans]
    assert seqs == sorted(seqs)
    # 1600 spans were recorded through a 64-slot ring
    assert max(seqs) >= n_threads * per_thread - 64


def test_capacity_reconfigure_resets_ring():
    tr = Tracer(capacity=16)
    tr.configure(enabled=True)
    tr.finish(tr.start("a"))
    tr.configure(capacity=32)
    assert tr.snapshot() == []
    assert tr.capacity == 32


# ---- exports --------------------------------------------------------------

def _sample_spans():
    tr = Tracer(capacity=32)
    tr.configure(enabled=True)
    root = tr.start("http.request", method="GET", target="/x")
    child = tr.start("batcher.queue_wait", parent=root)
    tr.finish(child)
    tr.finish(root, status=200)
    return tr.snapshot()


def test_chrome_trace_event_schema():
    spans = _sample_spans()
    out = chrome_trace(spans)
    assert out["displayTimeUnit"] == "ms"
    events = out["traceEvents"]
    assert len(events) == len(spans)
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["cat"] == "oryx"
        assert isinstance(ev["name"], str)
        assert isinstance(ev["ts"], float) and ev["ts"] > 0
        assert isinstance(ev["dur"], float) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert len(ev["args"]["trace_id"]) == 32
    json.dumps(out)  # must be serializable as-is


def test_span_forest_nests_children():
    spans = _sample_spans()
    forest = span_forest(spans)
    assert len(forest) == 1
    root = forest[0]
    assert root["name"] == "http.request"
    assert root["attrs"]["status"] == 200
    assert [c["name"] for c in root["children"]] == ["batcher.queue_wait"]
    assert root["children"][0]["parent_id"] == root["span_id"]
    assert root["duration_ms"] >= root["children"][0]["duration_ms"] >= 0


def test_orphan_spans_surface_as_roots():
    tr = Tracer(capacity=2)
    tr.configure(enabled=True)
    root = tr.start("req")
    child = tr.start("stage", parent=root)
    tr.finish(child)
    tr.finish(root)
    # capacity 2 keeps both; drop the parent manually to simulate eviction
    spans = [s for s in tr.snapshot() if s.name == "stage"]
    forest = span_forest(spans)
    assert len(forest) == 1 and forest[0]["name"] == "stage"


# ---- slow-request log -----------------------------------------------------

def test_slow_request_log_breakdown(caplog):
    import logging

    tr = Tracer(capacity=32)
    tr.configure(enabled=True, slow_threshold=0.0)
    root = tr.start("http.request", method="GET", target="/slow")
    tr.finish(tr.start("batcher.queue_wait", parent=root))
    tr.finish(root, status=200)
    logger = logging.getLogger("test.slow")
    with caplog.at_level(logging.WARNING, logger="test.slow"):
        tr.log_if_slow(root, logger)
    assert any("slow request" in r.message and "batcher.queue_wait" in r.message
               for r in caplog.records)
    # below threshold: silent
    caplog.clear()
    tr.configure(slow_threshold=3600.0)
    with caplog.at_level(logging.WARNING, logger="test.slow"):
        tr.log_if_slow(root, logger)
    assert not caplog.records


# ---- model freshness ------------------------------------------------------

def test_publish_stamp_to_update_to_serve_metrics():
    """MODEL + its TRACE publish stamp through the standard update
    dispatcher -> oryx_update_to_serve_seconds observed, staleness and
    generation gauges live, and /metrics exports all three."""
    from oryx_tpu.apps.example.serving import ExampleServingModelManager
    from oryx_tpu.bus.api import KeyMessage
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.freshness import model_freshness, publish_stamp
    from oryx_tpu.common.metrics import get_registry

    mf = model_freshness()
    before = mf._h_lag.count()
    mgr = ExampleServingModelManager(load_config())
    stamp = json.loads(publish_stamp(generation=1234567))
    stamp["published_ms"] -= 2000  # published 2s ago
    mgr.consume(iter([
        KeyMessage("MODEL", json.dumps({"w": 1})),
        KeyMessage("TRACE", json.dumps(stamp)),
    ]))
    assert mf._h_lag.count() == before + 1
    assert mf.generation == 1234567
    assert 1.5 <= mf._staleness() < 60.0
    text = get_registry().render_prometheus()
    assert "oryx_update_to_serve_seconds_count" in text
    assert "oryx_model_staleness_seconds" in text
    assert "oryx_model_generation 1234567" in text


def test_publish_stamp_ignored_when_model_load_failed():
    from oryx_tpu.api import AbstractServingModelManager
    from oryx_tpu.bus.api import KeyMessage
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.freshness import model_freshness, publish_stamp

    class _Boom(AbstractServingModelManager):
        def get_model(self):
            return None

        def consume_key_message(self, key, message):
            raise ValueError("bad model")

    mf = model_freshness()
    before = mf._h_lag.count()
    mgr = _Boom(load_config())
    mgr.consume(iter([
        KeyMessage("MODEL", "junk"),
        KeyMessage("TRACE", publish_stamp(generation=99)),
    ]))
    # the stamped model never loaded: no lag observation, generation kept
    assert mf._h_lag.count() == before
    assert mf.generation != 99


def test_app_handlers_never_see_trace_stamps():
    """TRACE stamps are framework-level (like MODEL-CHUNK): the standard
    dispatcher must intercept them before the app handler."""
    from oryx_tpu.api import AbstractServingModelManager
    from oryx_tpu.bus.api import KeyMessage
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.freshness import publish_stamp

    class _Recorder(AbstractServingModelManager):
        seen: list = []

        def get_model(self):
            return None

        def consume_key_message(self, key, message):
            self.seen.append(key)

    mgr = _Recorder(load_config())
    mgr.consume(iter([
        KeyMessage("UP", "x,1"),
        KeyMessage("TRACE", publish_stamp()),
        KeyMessage("UP", "y,2"),
    ]))
    assert mgr.seen == ["UP", "UP"]


def test_parked_model_stamp_claimed_by_late_load():
    """A MODEL-REF parked for a lagging artifact loads AFTER its stamp:
    the held stamp must be claimed by the late re-dispatched load (every
    chunk-lagged publish would otherwise be invisible to freshness)."""
    from oryx_tpu.common.freshness import model_freshness, publish_stamp

    mf = model_freshness()
    before = mf._h_lag.count()
    # parked (not given up): the stamp that follows is held, keyed to the
    # parked message...
    mf.note_load_failed(parked=True, message="/models/4242")
    mf.note_stamp(publish_stamp(generation=4242))
    assert mf._h_lag.count() == before  # not observed yet
    # ...a DIFFERENT model loading meanwhile must not claim it (it takes
    # the normal pending path and its own stamp pairs with it)
    mf.note_loaded("MODEL", message="some-other-model")
    mf.note_stamp(publish_stamp(generation=5000))
    assert mf._h_lag.count() == before + 1
    assert mf.generation == 5000
    # ...and the parked model's late re-dispatch claims ITS held stamp
    mf.note_loaded("MODEL-REF", message="/models/4242")
    assert mf._h_lag.count() == before + 2
    assert mf.generation == 4242
    # a given-up load still drops its stamp
    mf.note_load_failed(parked=False)
    mf.note_stamp(publish_stamp(generation=5555))
    mf.note_loaded("MODEL")  # a LATER load must not claim the dropped stamp
    assert mf._h_lag.count() == before + 2
    assert mf.generation == 4242


def test_freshness_hook_failure_never_kills_listener(monkeypatch):
    """_dispatch_update's isolation contract: a freshness tracker that
    blows up (e.g. metric-name collision at construction) must not
    propagate out of the dispatcher in either the loaded or failed path."""
    import oryx_tpu.common.freshness as freshness_mod
    from oryx_tpu.api import _dispatch_update
    from oryx_tpu.bus.api import KeyMessage

    def boom():
        raise ValueError("registry collision")

    monkeypatch.setattr(freshness_mod, "model_freshness", boom)
    seen = []
    _dispatch_update(lambda k, m: seen.append(k), KeyMessage("MODEL", "{}"))
    _dispatch_update(
        lambda k, m: (_ for _ in ()).throw(ValueError("bad")),
        KeyMessage("MODEL", "junk"),
    )
    assert seen == ["MODEL"]


# ---- serving integration: /debug/traces + /healthz smoke ------------------

def _als_serving_config(bus: str, loops: int = 2):
    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.common.config import load_config

    broker = get_broker(bus)
    for t in ("OryxInput", "OryxUpdate"):
        if not broker.topic_exists(t):
            broker.create_topic(t, 1)
    return load_config(overlay={
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.loops": loops,
        "oryx.monitoring.tracing.enabled": True,
        "oryx.monitoring.tracing.buffer-size": 8192,
        "oryx.serving.model-manager-class":
            "oryx_tpu.apps.als.serving.ALSServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.als",
        ],
    })


def _als_manager(cfg, n_users=32, n_items=64, features=8):
    import numpy as np

    from oryx_tpu.apps.als.serving import ALSServingModel, ALSServingModelManager
    from oryx_tpu.apps.als.state import ALSState
    from oryx_tpu.common.rng import RandomManager

    rng = RandomManager.get_random()
    state = ALSState(features, implicit=True)
    state.x.bulk_set(
        [f"u{i}" for i in range(n_users)],
        rng.standard_normal((n_users, features)).astype("float32"),
    )
    state.y.bulk_set(
        [f"i{i}" for i in range(n_items)],
        rng.standard_normal((n_items, features)).astype("float32"),
    )
    state.set_expected(state.x.ids(), state.y.ids())
    manager = ALSServingModelManager(cfg)
    manager.model = ALSServingModel(state)
    return manager


def test_loadtest_produces_span_tree_with_batcher_children(tmp_path):
    """Tier-1 smoke for the whole lens: a real loadtest against the async
    frontend with tracing on; /debug/traces must return a request span
    tree containing the batcher queue-wait (and device) children, the
    chrome export must be loadable, and /healthz must report liveness."""
    import io
    from contextlib import redirect_stdout

    from e2e_common import http_request

    from oryx_tpu.cli import main as cli_main
    from oryx_tpu.serving.server import ServingLayer

    cfg = _als_serving_config("mem://tracesmoke")
    manager = _als_manager(cfg)
    paths = tmp_path / "paths.txt"
    paths.write_text("/recommend/u0?howMany=4\n/recommend/u1?howMany=4\n")
    with ServingLayer(cfg, model_manager=manager) as sl:
        base = f"http://127.0.0.1:{sl.port}"
        out = io.StringIO()
        with redirect_stdout(out):
            rc = cli_main([
                "loadtest",
                "--url", base,
                "--paths", str(paths),
                "--duration", "1.5",
                "--workers", "4",
            ])
        assert rc == 0
        report = json.loads(out.getvalue().strip().splitlines()[-1])
        assert report["errors"] == 0 and report["requests"] > 10

        status, body = http_request("GET", f"{base}/debug/traces")
        assert status == 200
        payload = json.loads(body)
        assert payload["enabled"] is True
        roots = [
            t for t in payload["traces"]
            if t["name"] == "http.request"
            and "/recommend" in t["attrs"].get("target", "")
        ]
        assert roots, "no request span trees recorded"
        with_batcher = [
            t for t in roots
            if any(c["name"] == "batcher.queue_wait" for c in t["children"])
        ]
        assert with_batcher, (
            "no request span has a batcher.queue_wait child: "
            + json.dumps(roots[:2])[:800]
        )
        tree = with_batcher[-1]
        child_names = {c["name"] for c in tree["children"]}
        assert "batcher.device" in child_names or "batcher.host_score" in child_names
        assert "http.dispatch" in child_names
        assert tree["attrs"].get("status") == 200

        status, body = http_request("GET", f"{base}/debug/traces?format=chrome")
        assert status == 200
        chrome = json.loads(body)
        assert chrome["traceEvents"] and chrome["traceEvents"][0]["ph"] == "X"

        status, body = http_request("GET", f"{base}/healthz")
        assert status == 200
        health = json.loads(body)
        assert health["status"] == "up"
        assert health["uptime_seconds"] >= 0
        assert health["loops"] == 2

        # /metrics still renders with tracing on, and exposes freshness
        status, body = http_request("GET", f"{base}/metrics")
        assert status == 200
        assert "oryx_update_to_serve_seconds" in body
        assert "oryx_model_staleness_seconds" in body
    # restore the global tracer default for later tests in this process
    from oryx_tpu.common.tracing import get_tracer

    get_tracer().configure(enabled=False, capacity=2048)


def test_debug_traces_empty_when_disabled(tmp_path):
    """Default config: tracing off, /debug/traces reports enabled=false
    and records nothing for served requests."""
    from oryx_tpu.api import ServingModelManager
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.tracing import get_tracer
    from oryx_tpu.serving.app import Request, ServingApp

    class Manager(ServingModelManager):
        def __init__(self, config):
            self.config = config

        def consume(self, it):
            pass

        def get_model(self):
            return None

    get_tracer().clear()
    cfg = load_config(overlay={
        "oryx.serving.application-resources": ["oryx_tpu.serving.resources.common"],
    })
    app = ServingApp(cfg, Manager(cfg))
    status, body, _ = app.dispatch(
        Request("GET", "/debug/traces", {}, {}, b"", {"accept": "application/json"})
    )
    assert status == 200
    payload = json.loads(body)
    assert payload["enabled"] is False
    assert payload["traces"] == []


def test_healthz_via_dispatch_reports_generation():
    from oryx_tpu.api import ServingModelManager
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.app import Request, ServingApp

    class Manager(ServingModelManager):
        def __init__(self, config):
            self.config = config

        def consume(self, it):
            pass

        def get_model(self):
            return None

    cfg = load_config(overlay={
        "oryx.serving.application-resources": ["oryx_tpu.serving.resources.common"],
    })
    app = ServingApp(cfg, Manager(cfg))
    status, body, _ = app.dispatch(
        Request("GET", "/healthz", {}, {}, b"", {"accept": "application/json"})
    )
    assert status == 200
    health = json.loads(body)
    assert health["status"] == "up"
    assert health["loops"] == 1  # no async frontend attached
    assert "model_generation" in health
    # HEAD variant exists for probe tools
    status, body, _ = app.dispatch(
        Request("HEAD", "/healthz", {}, {}, b"", {})
    )
    assert status == 200


# ---- regions: always-on counters, the open-region table, the stall witness --


def _region_series(name):
    """(wall, cpu, count) of one region as /metrics renders them."""
    from oryx_tpu.common.metrics import get_registry

    out = {}
    for line in get_registry().render_prometheus().splitlines():
        for family in ("oryx_region_seconds_total", "oryx_region_cpu_seconds_total", "oryx_regions_total"):
            if line.startswith(f'{family}{{region="{name}"}} '):
                out[family] = float(line.rsplit(" ", 1)[1])
    return (
        out.get("oryx_region_seconds_total", 0.0),
        out.get("oryx_region_cpu_seconds_total", 0.0),
        out.get("oryx_regions_total", 0.0),
    )


@pytest.mark.parametrize("work", ["sleeps", "spins"])
def test_region_counts_wall_and_cpu_with_no_profiler_and_the_ring_off(work):
    """A region leaves its wall time, its thread's CPU time and one behind
    with nothing switched on; a sleep reads off-CPU, a spin on-CPU."""
    from oryx_tpu.common.tracing import get_tracer, thread_region_seconds

    tr = get_tracer()
    assert not tr.enabled
    name = f"test.region.{work}"
    before = _region_series(name)
    with tr.region(name, cpu=True, attr=1):
        if work == "sleeps":
            time.sleep(0.1)
        else:
            t_end = time.monotonic() + 0.1
            while time.monotonic() < t_end:
                pass
    wall, cpu, n = (a - b for a, b in zip(_region_series(name), before))
    assert n == 1 and 0.09 < wall < 1.0
    assert cpu <= wall + 1e-4
    if work == "sleeps":
        assert cpu < 0.1 * wall
    else:
        # one thread spinning in Python: on the CPU, but for what the test
        # machine's other work took of the core
        assert cpu > 0.25 * wall
    assert thread_region_seconds(name) >= wall - 1e-9


def test_region_cells_are_per_thread_and_outlive_their_thread():
    """Eight threads count into their own cells with no lock; the sum is
    exact, and stays after the threads are gone."""
    from oryx_tpu.common.tracing import get_tracer, region_totals

    tr = get_tracer()
    name = "test.region.cells"
    base = region_totals().get(name, (0.0, 0.0, 0))[2]

    def worker():
        for _ in range(500):
            with tr.region(name):
                pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert region_totals()[name][2] - base == 4000
    assert _region_series(name)[2] - base == 4000  # folded from the dead threads' cells
    # a region that does not ask for the CPU clock has no series of that family
    from oryx_tpu.common.metrics import get_registry

    page = get_registry().render_prometheus()
    assert f'oryx_region_seconds_total{{region="{name}"}}' in page
    assert f'oryx_region_cpu_seconds_total{{region="{name}"}}' not in page


def test_the_open_region_table_names_what_a_blocked_thread_is_in():
    from oryx_tpu.common.tracing import get_tracer, name_thread, thread_table

    tr = get_tracer()
    entered, release = threading.Event(), threading.Event()

    def parked():
        name_thread("oryx-test-park")
        with tr.region("test.outer"):
            with tr.region("test.parked", why="event"):
                entered.set()
                release.wait(timeout=30)

    t = threading.Thread(target=parked)
    t.start()
    try:
        assert entered.wait(timeout=10)
        time.sleep(0.15)
        row = next(r for r in thread_table() if r["thread"] == "oryx-test-park")
        assert row["region"] == "test.parked"  # the innermost
        assert 0.1 < row["age_s"] < 10
    finally:
        release.set()
        t.join(timeout=10)
    assert not t.is_alive()
    assert all(r["thread"] != "oryx-test-park" for r in thread_table())  # gone with its thread


def test_the_os_thread_name_is_set_on_linux():
    import sys

    from oryx_tpu.common.tracing import name_thread

    seen = []

    def worker():
        name_thread("oryx-test-name-that-is-long")
        with open(f"/proc/self/task/{threading.get_native_id()}/comm") as f:
            seen.append(f.read().strip())

    if not sys.platform.startswith("linux"):
        pytest.skip("prctl(PR_SET_NAME) is Linux's")
    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=10)
    assert seen == ["oryx-test-name-"]  # the kernel keeps 15 bytes


def test_a_blocked_event_loop_leaves_one_stall_line_and_a_quiet_second_none(tmp_path, caplog):
    """The witness: a handler that blocks its event loop for 300 ms makes the
    loop's heartbeat late, and the one `stall` event says how late, what CPU
    time the process spent, and what a thread parked in a region was in."""
    import http.client
    import logging

    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.common import flightrec
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.common.tracing import get_tracer, name_thread
    from oryx_tpu.serving.server import ServingLayer

    bus = "mem://tracing-stall"
    broker = get_broker(bus)
    for topic in ("OryxInput", "OryxUpdate"):
        if not broker.topic_exists(topic):
            broker.create_topic(topic, 1)
    cfg = load_config(overlay={
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.monitoring.flight.dir": str(tmp_path / "flight"),
        "oryx.serving.model-manager-class": "oryx_tpu.apps.example.serving.ExampleServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.example",
        ],
    })
    tr = get_tracer()
    entered, release = threading.Event(), threading.Event()

    def parked():
        name_thread("oryx-test-wait")
        with tr.region("test.waiting"):
            entered.set()
            release.wait(timeout=60)

    def stalls():
        return [e for e in flightrec.read_events(str(tmp_path / "flight")) if e["kind"] == "stall"]

    t = threading.Thread(target=parked)
    t.start()
    try:
        assert entered.wait(timeout=10)
        with ServingLayer(cfg) as sl:
            sl.app.route("GET", "/test-block", nonblocking=True)(
                lambda a, req: time.sleep(0.3) or {"blocked_s": 0.3}
            )
            conn = http.client.HTTPConnection("127.0.0.1", sl.port, timeout=10)
            conn.request("GET", "/healthz")  # the loops are up and beating
            assert conn.getresponse().read()
            time.sleep(0.5)  # start-up's own stalls (imports, the first compile) are over
            for _ in range(5):  # a quiet second (a loaded test machine may stall for real: look again)
                counted = get_registry().counter("oryx_stalls_total").value()
                seen = len(stalls())
                time.sleep(1.0)
                if get_registry().counter("oryx_stalls_total").value() == counted:
                    break
            assert get_registry().counter("oryx_stalls_total").value() == counted
            assert len(stalls()) == seen
            with caplog.at_level(logging.WARNING, logger="oryx_tpu.common.tracing"):
                conn.request("GET", "/test-block")
                resp = conn.getresponse()
                assert resp.read() and resp.status == 200
                time.sleep(0.3)  # the late beat fires right after the handler returns
            conn.request("GET", "/debug/threads")
            table = json.loads(conn.getresponse().read())["threads"]
            conn.close()
            new = stalls()[seen:]
            assert get_registry().counter("oryx_stalls_total").value() == counted + len(new)
            ours = [e for e in new if 0.25 <= e["late_s"] <= 0.6]
            assert len(ours) == 1, new  # (a loaded test machine may add a real one beside it)
            stall = ours[0]
            assert 0.0 <= stall["process_cpu_s"] <= 0.7
            assert isinstance(stall["collections"], list)
            waiting = [r for r in stall["threads"] if r["thread"] == "oryx-test-wait"]
            assert waiting and waiting[0]["region"] == "test.waiting" and waiting[0]["age_s"] > 0.3
            lines = [r.getMessage() for r in caplog.records if r.getMessage().startswith("stall: ")]
            assert len(lines) == len(new) and all("test.waiting" in line for line in lines)
            # the operator's table is the same one, live
            assert any(r["thread"] == "oryx-test-wait" and r["region"] == "test.waiting" for r in table)
            assert any(r["thread"].startswith("oryx-loop-") for r in table)
    finally:
        release.set()
        t.join(timeout=10)
