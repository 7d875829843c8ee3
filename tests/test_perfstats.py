"""Runtime performance observability: perfstats records and live MFU,
per-metric histogram buckets + exemplars, /debug/profile, and the
metric→trace exemplar path on both frontends.

Includes the tier-1 acceptance smoke: under a traced load window,
/metrics must report a non-null oryx_device_mfu and an
oryx_dispatch_batch_occupancy consistent with the batcher's valid_rows
accounting (and <= 1.0), and /debug/profile must return a
Perfetto-loadable artifact.
"""

import http.client
import json
import math
import threading
import time

import numpy as np
import pytest


# ---- histogram buckets + exemplars ----------------------------------------


def test_bucket_helpers():
    from oryx_tpu.common.metrics import exponential_buckets, linear_buckets

    assert linear_buckets(1.0, 2.0, 3) == (1.0, 3.0, 5.0)
    assert exponential_buckets(1.0, 10.0, 3) == (1.0, 10.0, 100.0)
    with pytest.raises(ValueError):
        linear_buckets(0.0, 1.0, 0)
    with pytest.raises(ValueError):
        exponential_buckets(0.0, 2.0, 3)
    with pytest.raises(ValueError):
        exponential_buckets(1.0, 1.0, 3)


def test_registry_histogram_per_metric_buckets_and_mismatch():
    from oryx_tpu.common.metrics import (
        DEFAULT_BUCKETS,
        MetricsRegistry,
        linear_buckets,
    )

    reg = MetricsRegistry()
    h = reg.histogram("t_occ", "occupancy", buckets=linear_buckets(0.25, 0.25, 4))
    assert h.buckets == (0.25, 0.5, 0.75, 1.0)
    # buckets=None accepts whatever the metric was registered with
    assert reg.histogram("t_occ") is h
    # same explicit buckets: fine
    assert reg.histogram("t_occ", buckets=(0.25, 0.5, 0.75, 1.0)) is h
    # conflicting explicit buckets: loud failure, not silent corruption
    with pytest.raises(ValueError):
        reg.histogram("t_occ", buckets=(1.0, 2.0))
    # default registration still gets DEFAULT_BUCKETS
    assert reg.histogram("t_lat").buckets == DEFAULT_BUCKETS


def test_histogram_bucket_counts_snapshot_and_exemplars():
    from oryx_tpu.common.metrics import Histogram

    h = Histogram("t_h", "help", buckets=(0.1, 1.0, 10.0))
    h.observe(0.05, method="GET")
    h.observe(0.5, trace_id="aaaa1111", method="GET")
    h.observe(100.0, trace_id="bbbb2222", method="GET")
    counts = h.bucket_counts(method="GET")
    assert counts == [(0.1, 1), (1.0, 2), (10.0, 2), (float("inf"), 3)]
    # exemplar sits on the exact bucket the value landed in
    assert h.exemplar(1, method="GET")[0] == "aaaa1111"
    assert h.exemplar(3, method="GET")[0] == "bbbb2222"  # +Inf bucket
    assert h.exemplar(0, method="GET") is None  # untraced observation
    # newest traced sample wins the bucket
    h.observe(0.7, trace_id="cccc3333", method="GET")
    assert h.exemplar(1, method="GET")[0] == "cccc3333"
    lines = h.render(openmetrics=True)
    ex_lines = [l for l in lines if " # {" in l]
    assert any('le="1"' in l and 'trace_id="cccc3333"' in l for l in ex_lines)
    assert any('le="+Inf"' in l and 'trace_id="bbbb2222"' in l for l in ex_lines)
    # OpenMetrics exemplar shape: `count # {labels} value timestamp`
    bucket_1 = next(l for l in ex_lines if 'le="1"' in l)
    tail = bucket_1.split(" # ", 1)[1]
    assert tail.startswith('{trace_id="cccc3333"} 0.7 ')
    # the CLASSIC exposition has no exemplar syntax — emitting it would
    # fail legacy scrape parsers on the whole page
    assert not any(" # {" in l for l in h.render())


def test_openmetrics_dialect_counter_suffix_and_eof():
    from oryx_tpu.common.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("t_good_total", "conformant").inc()
    reg.counter("t_legacy", "no _total suffix").inc()
    plain = reg.render_prometheus()
    om = reg.render_prometheus(openmetrics=True)
    assert "# TYPE t_legacy counter" in plain
    assert "# TYPE t_good_total counter" in plain
    # strict OpenMetrics parsers reject counter samples without _total:
    # legacy-named counters expose as `unknown` under negotiation
    assert "# TYPE t_legacy unknown" in om
    # ...and the counter FAMILY name strips _total (samples keep it)
    assert "# TYPE t_good counter" in om
    assert "# TYPE t_good_total" not in om
    assert "\nt_good_total 1" in om
    assert om.rstrip().endswith("# EOF") and "# EOF" not in plain


def test_openmetrics_exposition_accepted_by_reference_parser():
    """The negotiated dialect must parse under the strict OpenMetrics
    reference parser — the whole point of negotiating is that a strict
    scraper ingests the page (exemplars included) instead of failing it."""
    parser = pytest.importorskip("prometheus_client.openmetrics.parser")
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.common.perfstats import get_perfstats

    ps = get_perfstats()
    ps.ensure_metrics()
    ps.record_dispatch(
        "serving", flops=100.0, bytes_moved=10.0, wall_s=0.001,
        rows=1, padded_rows=1, valid_rows=1, capacity_rows=2,
        trace_id="feedbeef" * 4,
    )
    om = get_registry().render_prometheus(openmetrics=True)
    families = list(parser.text_string_to_metric_families(om))
    assert families, "reference parser ingested nothing"
    by_name = {f.name: f for f in families}
    assert by_name["oryx_device_fallback_dispatches"].type == "counter"
    hist = by_name["oryx_dispatch_batch_occupancy"]
    exemplars = [
        s.exemplar for s in hist.samples
        if s.name.endswith("_bucket") and s.exemplar
    ]
    assert any(
        e.labels.get("trace_id") == "feedbeef" * 4 for e in exemplars
    ), "exemplar did not survive the reference parser"


# ---- perfstats core --------------------------------------------------------


def _fresh_perfstats(window_s=10.0):
    from oryx_tpu.common.perfstats import PerfStats

    ps = PerfStats(capacity=256, window_s=window_s)
    ps.ensure_metrics()
    return ps


def test_record_dispatch_occupancy_and_mfu():
    ps = _fresh_perfstats()
    ps.assumed_peak_flops = 1e6
    ps.record_dispatch(
        "serving", flops=1e5, bytes_moved=4096, wall_s=0.01,
        rows=3, padded_rows=4, valid_rows=50, capacity_rows=128,
    )
    ps.record_dispatch(
        "serving", flops=1e5, bytes_moved=4096, wall_s=0.01,
        rows=3, padded_rows=4, valid_rows=50, capacity_rows=128,
    )
    recs = ps.records_since(0)
    assert len(recs) == 2
    assert recs[0].occupancy == pytest.approx(50 / 128)
    # 2e5 FLOPs over a 10s window against a 1e6 assumed peak
    assert ps.achieved_flops_per_sec("serving") == pytest.approx(2e4)
    assert ps.mfu("serving") == pytest.approx(0.02)
    # occupancy can never exceed 1.0, even on inconsistent inputs
    over = ps.record_dispatch(
        "train", flops=1.0, bytes_moved=0, wall_s=0.001,
        rows=10, padded_rows=10, valid_rows=20, capacity_rows=10,
    )
    assert over.occupancy == 1.0


def test_record_dispatch_occupancy_degenerate_inputs():
    """Regression (ISSUE 17): a zero-capacity or empty dispatch must
    never observe a >1.0 or NaN occupancy sample — degenerate inputs
    read as 0.0 (no real data streamed), not as a perfect batch."""
    ps = _fresh_perfstats()
    cases = [
        dict(valid_rows=5, capacity_rows=0),    # zero capacity
        dict(valid_rows=0, capacity_rows=128),  # empty dispatch
        dict(valid_rows=0, capacity_rows=0),    # both degenerate
        dict(valid_rows=-3, capacity_rows=64),  # nonsense negative
    ]
    for kw in cases:
        r = ps.record_dispatch(
            "serving", flops=1.0, bytes_moved=0, wall_s=0.001,
            rows=1, padded_rows=1, **kw,
        )
        assert r.occupancy == 0.0, kw
        assert not math.isnan(r.occupancy)
        assert r.occupancy <= 1.0


def test_mfu_nan_without_peak_and_zero_during_fallback():
    ps = _fresh_perfstats(window_s=0.2)
    ps.record_dispatch(
        "serving", flops=1e5, bytes_moved=0, wall_s=0.001,
        rows=1, padded_rows=1, valid_rows=1, capacity_rows=1,
    )
    # no chip peak, no assumed peak: NaN, not a confident 0
    assert math.isnan(ps.mfu("serving"))
    ps.assumed_peak_flops = 1e6
    assert ps.mfu("serving") > 0
    # a fallback zeroes the gauge for one window...
    ps.note_fallback(2)
    assert ps.mfu("serving") == 0.0
    # ...then it recovers (fresh work after the window: the old record
    # has also rolled out of the 0.2s window by now)
    time.sleep(0.25)
    ps.record_dispatch(
        "serving", flops=1e5, bytes_moved=0, wall_s=0.001,
        rows=1, padded_rows=1, valid_rows=1, capacity_rows=1,
    )
    assert ps.mfu("serving") > 0
    # real chip peak, once noted, wins over the assumed override
    ps.note_peak("serving", 1e7)
    assert ps.peak_for("serving") == 1e7


def test_capture_profile_artifact_and_concurrency_guard():
    ps = _fresh_perfstats()
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            ps.record_dispatch(
                "serving", flops=100.0, bytes_moved=10.0, wall_s=0.001,
                rows=1, padded_rows=1, valid_rows=64, capacity_rows=128,
            )
            time.sleep(0.01)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        art = ps.capture_profile(0.3)
    finally:
        stop.set()
        t.join()
    assert art["displayTimeUnit"] == "ms"
    assert art["traceEvents"], "no dispatch slices captured in the window"
    ev = art["traceEvents"][0]
    assert ev["ph"] == "X" and ev["name"] == "device.dispatch.serving"
    assert ev["args"]["occupancy"] == pytest.approx(0.5)
    summary = art["oryx"]["by_kind"]["serving"]
    assert summary["dispatches"] >= 1
    assert summary["mean_occupancy"] == pytest.approx(0.5)
    # the capture lock refuses concurrent jax-profiler windows
    assert ps._capture_lock.acquire(blocking=False)
    try:
        with pytest.raises(RuntimeError):
            ps.capture_profile(0.01)
    finally:
        ps._capture_lock.release()


def test_batcher_records_dispatch_costs():
    import jax.numpy as jnp

    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.serving.batcher import TopKBatcher

    ps = get_perfstats()
    t_mark = time.monotonic()
    host = np.random.default_rng(0).standard_normal((64, 8)).astype(np.float32)
    y = jnp.asarray(host)
    b = TopKBatcher()
    try:
        b.submit(host[0], 3, y, host_mat=host, valid_rows=50)
    finally:
        b.close()
    recs = [
        r for r in ps.records_since(t_mark) if r.kind == "serving"
    ]
    assert recs, "batcher dispatch did not record into perfstats"
    r = recs[-1]
    assert r.flops == pytest.approx(2.0 * 1 * 50 * 8)
    assert r.occupancy == pytest.approx(50 / 64)
    assert r.bytes_moved > 0 and r.wall_s > 0


def test_train_scan_records_dispatch_costs():
    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.ops.als import InteractionData, train_als

    ps = get_perfstats()
    t_mark = time.monotonic()
    rng = np.random.default_rng(0)
    n = 300
    data = InteractionData(
        [f"u{i}" for i in range(40)], [f"i{i}" for i in range(30)],
        rng.integers(0, 40, n).astype(np.int32),
        rng.integers(0, 30, n).astype(np.int32),
        (rng.random(n) + 0.1).astype(np.float32),
    )
    train_als(data, features=4, iterations=2)
    recs = [r for r in ps.records_since(t_mark) if r.kind == "train"]
    assert recs, "train scan did not record into perfstats"
    r = recs[-1]
    assert r.flops > 0 and r.bytes_moved > 0 and r.wall_s > 0
    # 70 real rows over the two 1024-unit padded tables
    assert r.occupancy == pytest.approx(70 / 2048)


# ---- serving integration ---------------------------------------------------


def _als_serving_config(bus: str, frontend: str = "async", extra=None):
    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.common.config import load_config

    broker = get_broker(bus)
    for t in ("OryxInput", "OryxUpdate"):
        if not broker.topic_exists(t):
            broker.create_topic(t, 1)
    overlay = {
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.server": frontend,
        "oryx.serving.api.loops": 2,
        "oryx.monitoring.tracing.enabled": True,
        "oryx.monitoring.tracing.buffer-size": 8192,
        "oryx.serving.model-manager-class":
            "oryx_tpu.apps.als.serving.ALSServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.als",
        ],
    }
    overlay.update(extra or {})
    return load_config(overlay=overlay)


def _als_manager(cfg, n_users=32, n_items=64, features=8):
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


def _http_get(
    port: int, path: str, accept: str | None = None
) -> tuple[int, dict, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path, headers={"Accept": accept} if accept else {})
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, {k.lower(): v for k, v in resp.getheaders()}, body
    finally:
        conn.close()


def _restore_tracer():
    from oryx_tpu.common.tracing import get_tracer

    get_tracer().configure(enabled=False, capacity=2048)


@pytest.mark.parametrize("frontend", ["async", "threaded"])
def test_exemplar_joins_traced_request_to_metrics(frontend, tmp_path):
    """Satellite contract: a traced request's trace id must appear in the
    /metrics exemplar of the latency bucket it landed in — on BOTH
    frontends — and exemplar rendering must coexist with `labeled=`
    zero-series suppression."""
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    cfg = _als_serving_config(f"mem://exemplar-{frontend}", frontend=frontend)
    manager = _als_manager(cfg)
    # a labeled metric with zero series: its suppression must survive the
    # exemplar-rendering path (HELP/TYPE render, no bogus `name 0` sample)
    get_registry().counter(
        "oryx_test_labeled_empty", "suppression canary", labeled=True
    )
    try:
        with ServingLayer(cfg, model_manager=manager) as sl:
            trace_ids = []
            for i in range(6):
                status, headers, _ = _http_get(
                    sl.port, f"/recommend/u{i % 4}?howMany=4"
                )
                assert status == 200
                # traced responses echo their trace context
                tp = headers.get("traceparent", "")
                assert tp.startswith("00-"), headers
                trace_ids.append(tp.split("-")[1])
            # exemplars ride ONLY the negotiated OpenMetrics dialect
            status, headers, body = _http_get(
                sl.port, "/metrics",
                accept="application/openmetrics-text",
            )
            assert status == 200
            assert headers["content-type"].startswith(
                "application/openmetrics-text"
            )
            text = body.decode()
            ex_lines = [
                l for l in text.splitlines()
                if l.startswith("oryx_serving_request_seconds_bucket")
                and " # {" in l
            ]
            assert ex_lines, "no exemplars on the request-latency histogram"
            assert any(
                tid in l for tid in trace_ids for l in ex_lines
            ), f"none of {trace_ids} in exemplars: {ex_lines}"
            # labeled= suppression survived: declaration, but no sample
            assert "# TYPE oryx_test_labeled_empty unknown" in text
            assert "\noryx_test_labeled_empty 0" not in text
            # a classic scrape stays exemplar-free (legacy parsers would
            # fail the whole page on exemplar syntax) and plain-typed
            status, headers, body = _http_get(sl.port, "/metrics")
            assert headers["content-type"].startswith("text/plain")
            plain = body.decode()
            assert " # {" not in plain and "# EOF" not in plain
            assert "# TYPE oryx_test_labeled_empty counter" in plain
    finally:
        _restore_tracer()


def test_perf_smoke_mfu_occupancy_profile(tmp_path):
    """Tier-1 acceptance smoke: under a traced load window, /metrics
    reports non-null oryx_device_mfu and oryx_dispatch_batch_occupancy
    consistent with the batcher's valid_rows accounting (<= 1.0), and
    /debug/profile?seconds=1 returns a Perfetto-loadable artifact."""
    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.serving.server import ServingLayer

    cfg = _als_serving_config(
        "mem://perfsmoke",
        extra={
            # CPU host: no honest chip peak — the configured assumed peak
            # makes the MFU gauge a real (non-null, non-NaN) ratio
            "oryx.monitoring.perf.assumed-peak-flops": 1.0e12,
            "oryx.monitoring.perf.window-sec": 120,
            "oryx.monitoring.profile.enabled": True,
            "oryx.monitoring.profile.max-seconds": 5,
        },
    )
    manager = _als_manager(cfg)
    ps = get_perfstats()
    # process-wide state an earlier test of this worker may have left: a
    # device->host fallback zeroes the serving MFU gauge for a whole
    # window, which is not this test's subject
    ps._fallback_until.clear()
    t_mark = time.monotonic()
    # the process-global occupancy histogram is cumulative across tests:
    # the load window's contribution is measured as a sum/count DELTA
    from oryx_tpu.common.metrics import get_registry

    h_occ = get_registry().histogram("oryx_dispatch_batch_occupancy")
    occ_count0 = h_occ.count(kind="serving")
    occ_sum0 = h_occ.sum(kind="serving")
    try:
        with ServingLayer(cfg, model_manager=manager) as sl:
            stop = threading.Event()
            errors = []

            def drive(worker: int):
                while not stop.is_set():
                    try:
                        status, _, _ = _http_get(
                            sl.port, f"/recommend/u{worker}?howMany=4"
                        )
                        if status != 200:
                            errors.append(status)
                    except Exception as e:  # noqa: BLE001
                        errors.append(repr(e))

            threads = [
                threading.Thread(target=drive, args=(i,), daemon=True)
                for i in range(4)
            ]
            for t in threads:
                t.start()
            try:
                time.sleep(1.0)
                # /debug/profile captures a window WHILE load is flowing
                status, headers, body = _http_get(
                    sl.port, "/debug/profile?seconds=1"
                )
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=10)
            assert not errors, errors[:5]
            assert status == 200
            assert "attachment" in headers.get("content-disposition", "")
            artifact = json.loads(body)
            # Perfetto-loadable: trace-event JSON with complete events
            assert artifact["displayTimeUnit"] == "ms"
            assert artifact["traceEvents"], "empty profile window"
            assert any(
                e["ph"] == "X" and e["name"] == "device.dispatch.serving"
                for e in artifact["traceEvents"]
            )
            assert artifact["oryx"]["by_kind"]["serving"]["dispatches"] >= 1

            status, _, body = _http_get(sl.port, "/metrics")
            assert status == 200
            metrics = body.decode()

            def metric_value(line_prefix: str) -> float:
                for line in metrics.splitlines():
                    if line.startswith(line_prefix):
                        return float(line.rsplit(" ", 1)[1])
                raise AssertionError(f"{line_prefix} not in /metrics")

            mfu = metric_value('oryx_device_mfu{kind="serving"}')
            assert not math.isnan(mfu) and mfu > 0.0
            assert metric_value(
                'oryx_device_flops_per_sec{kind="serving"}'
            ) > 0.0

            # occupancy: every observation <= 1.0 (the le="1" bucket holds
            # the full count) and the mean matches the batcher's
            # valid_rows / capacity accounting exactly
            occ_count = metric_value(
                'oryx_dispatch_batch_occupancy_count{kind="serving"}'
            )
            occ_sum = metric_value(
                'oryx_dispatch_batch_occupancy_sum{kind="serving"}'
            )
            occ_le_1 = metric_value(
                'oryx_dispatch_batch_occupancy_bucket{kind="serving",le="1"}'
            )
            assert occ_count >= 1
            assert occ_le_1 == occ_count  # nothing ever exceeded 1.0
            mean_occ = occ_sum / occ_count
            assert 0.0 < mean_occ <= 1.0
            recs = [
                r for r in ps.records_since(t_mark) if r.kind == "serving"
            ]
            assert recs
            expected = recs[-1].valid_rows / recs[-1].capacity_rows
            # this window's observations (the /metrics figures are
            # process-cumulative; earlier tests contributed other ratios)
            window_mean = (occ_sum - occ_sum0) / (occ_count - occ_count0)
            assert window_mean == pytest.approx(expected, rel=1e-6)
            # and the record's valid_rows is the model's real row count
            y_rows = manager.model._y_view_full()[0].shape[0]
            assert recs[-1].valid_rows == 64
            assert recs[-1].capacity_rows == y_rows

            # fallback accounting: /metrics exposes the counter family
            assert "oryx_device_fallback_dispatches_total" in metrics
    finally:
        _restore_tracer()


def test_debug_profile_gated_when_disabled(tmp_path):
    from oryx_tpu.serving.server import ServingLayer

    cfg = _als_serving_config("mem://profilegate")
    manager = _als_manager(cfg)
    try:
        with ServingLayer(cfg, model_manager=manager) as sl:
            status, _, body = _http_get(sl.port, "/debug/profile?seconds=1")
            assert status == 403, body
    finally:
        _restore_tracer()
