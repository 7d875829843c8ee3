"""Hot-path latency attribution (common/perfattr.py): phase ledgers,
idle-gap classification, compile telemetry + storm events, the latency
budget surfaces, and the `oryx perf` report.

Includes the ISSUE 17 tier-1 acceptance scenario: requests driven through
a real ServingLayer must produce phase-budget samples summing to >= 95%
of the measured request wall-clock with zero unattributed idle-gap share
in the steady-state window, and a forced latency fast-burn must leave a
harvestable profile-capture event (with the phase-budget payload) in the
on-disk flight ring.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from oryx_tpu.common.perfattr import (
    PHASES,
    PerfAttr,
    PhaseLedger,
    classify_idle_gap,
    current_ledger,
    get_perfattr,
    swap_ledger,
)


# ---- phase ledger ----------------------------------------------------------


def test_phase_ledger_add_items_total():
    led = PhaseLedger()
    led.add("parse", 0.002, start=1.0)
    led.add("device", 0.01)          # no start: still counted, no span
    led.add("write", -0.5)           # clock skew: dropped
    led.add("auth", float("nan"))    # NaN: dropped
    items = led.items()
    assert [p for p, _, _ in items] == ["parse", "device"]
    assert items[0][1] == 1.0
    assert items[1][1] == -1.0       # sentinel for "no start known"
    assert led.total() == pytest.approx(0.012)


def test_swap_ledger_is_thread_local():
    led = PhaseLedger()
    assert swap_ledger(led) is None
    assert current_ledger() is led
    seen = []

    def other():
        seen.append(current_ledger())

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen == [None]            # the mirror never leaks across threads
    assert swap_ledger(None) is led
    assert current_ledger() is None


# ---- idle-gap classification -----------------------------------------------


def test_classify_idle_gap_measured_causes():
    causes = classify_idle_gap(1.0, wait_s=0.9, serialize_s=0.1)
    assert causes == {
        "empty_queue": pytest.approx(0.9),
        "host_serialize": pytest.approx(0.1),
    }
    # cap order: wait first, then down, then serialize, each bounded by
    # what the gap can still hold
    causes = classify_idle_gap(1.0, wait_s=2.0, serialize_s=5.0, down_s=5.0)
    assert causes == {"empty_queue": pytest.approx(1.0)}
    causes = classify_idle_gap(1.0, down_s=0.7, serialize_s=0.9)
    assert causes["failover_backoff"] == pytest.approx(0.7)
    assert causes["host_serialize"] == pytest.approx(0.3)


def test_classify_idle_gap_residue_fold_and_unattributed():
    # small residue (<= max(2ms, 10%)) folds into host_serialize
    causes = classify_idle_gap(0.010, wait_s=0.0095)
    assert set(causes) == {"empty_queue", "host_serialize"}
    assert causes["host_serialize"] == pytest.approx(0.0005)
    # large residue is reported honestly
    causes = classify_idle_gap(1.0, wait_s=0.2)
    assert causes["unattributed"] == pytest.approx(0.8)
    # zero / negative gaps (pipelined dispatches) classify to nothing
    assert classify_idle_gap(0.0) == {}
    assert classify_idle_gap(-0.5) == {}


# ---- budget window + flush idempotence -------------------------------------


def _ledger(phases: dict[str, float]) -> PhaseLedger:
    led = PhaseLedger()
    t = led.t0
    for phase, s in phases.items():
        led.add(phase, s, start=t)
        t += s
    return led


def test_observe_request_is_idempotent_per_ledger():
    pa = PerfAttr(window_s=300.0)
    led = _ledger({"parse": 0.001, "device": 0.01})
    pa.observe_request(led)
    pa.observe_request(led)          # the Deferred + sync paths both flush
    b = pa.budget()
    assert b["phases"]["parse"]["count"] == 1
    assert b["phases"]["device"]["count"] == 1
    assert b["total_phase_seconds"] == pytest.approx(0.011, abs=1e-4)


def test_budget_percentiles_shares_and_gap_ranking():
    pa = PerfAttr(window_s=300.0)
    for ms in (1, 2, 3, 4, 100):
        pa.observe_request(_ledger({"device": ms / 1e3, "parse": 0.001}))
    pa.record_idle_gap("empty_queue", 0.9)
    pa.record_idle_gap("host_serialize", 0.1)
    pa.record_idle_gap("bogus", -1.0)     # non-positive: dropped
    b = pa.budget()
    dev = b["phases"]["device"]
    assert dev["count"] == 5
    assert dev["p50_ms"] == pytest.approx(3.0)
    assert dev["p99_ms"] == pytest.approx(100.0)
    total = 0.110 + 5 * 0.001
    assert dev["share"] == pytest.approx(0.110 / total, abs=1e-3)
    # phase ordering follows the catalog; shares sum to ~1
    assert list(b["phases"]) == ["parse", "device"]
    assert sum(p["share"] for p in b["phases"].values()) == pytest.approx(
        1.0, abs=0.01
    )
    gaps = b["idle_gaps"]
    assert list(gaps) == ["empty_queue", "host_serialize"]  # ranked
    assert gaps["empty_queue"]["share"] == pytest.approx(0.9)
    assert "bogus" not in gaps


def test_budget_window_expires_old_stamps():
    pa = PerfAttr(window_s=0.05)
    pa.observe_request(_ledger({"device": 0.01}))
    pa.record_idle_gap("empty_queue", 0.5)
    time.sleep(0.08)
    b = pa.budget()
    assert b["phases"] == {}
    assert b["idle_gaps"] == {}


def test_disabled_perfattr_still_feeds_histograms_not_windows():
    pa = PerfAttr(window_s=300.0)
    pa.enabled = False
    pa.observe_request(_ledger({"device": 0.01}))
    pa.record_idle_gap("empty_queue", 0.5)
    assert pa.budget()["phases"] == {}   # derived window off...
    from oryx_tpu.common.metrics import get_registry

    text = get_registry().render_prometheus()
    # ...but the raw families exist regardless (always-on contract)
    assert "oryx_request_phase_seconds" in text
    assert "oryx_device_idle_gap_seconds" in text


def test_phase_spans_replay_into_the_trace_waterfall():
    from oryx_tpu.common.tracing import get_tracer

    tr = get_tracer()
    tr.configure(enabled=True, capacity=256)
    try:
        pa = PerfAttr(window_s=300.0)
        root = tr.start("http.request")
        led = PhaseLedger(trace=root)
        t = time.monotonic() - 0.1
        led.add("parse", 0.001, start=t)
        led.add("device", 0.02, start=t + 0.001)
        led.add("drain", 0.005)          # no start: histogram only, no span
        pa.observe_request(led)
        tr.finish(root)
        spans = {s.name: s for s in tr.snapshot()}
        assert "phase.parse" in spans and "phase.device" in spans
        assert spans["phase.device"].parent_id == root.span_id
        assert "phase.drain" not in spans
        assert led.trace_id == root.trace_id
    finally:
        tr.configure(enabled=False, capacity=2048)


# ---- compile telemetry + storm ---------------------------------------------


def _flight_to(tmp_path):
    """Point the global flight recorder at tmp and disarm stale episode
    rate-limits so this test observes ITS events."""
    from oryx_tpu.common import flightrec

    rec = flightrec.get_flightrec()
    rec.dir = str(tmp_path)
    rec.enabled = True
    with rec._lock:
        rec._last_episode.pop("compile-storm", None)
    return rec


def test_compile_storm_fires_flight_event(tmp_path):
    from oryx_tpu.common import flightrec

    _flight_to(tmp_path)
    pa = PerfAttr(window_s=300.0)
    pa.storm_threshold = 3
    pa.storm_window_s = 60.0
    pa.record_compile("serving", 0.2)
    pa.record_compile("serving", 0.3)
    events = [
        e for e in flightrec.read_events(str(tmp_path))
        if e.get("kind") == "compile-storm"
    ]
    assert not events                    # below threshold: quiet
    pa.record_compile("serving", 0.4)    # third within the window: storm
    events = [
        e for e in flightrec.read_events(str(tmp_path))
        if e.get("kind") == "compile-storm"
    ]
    assert events, "threshold recompiles recorded no compile-storm"
    ev = events[-1]
    assert ev["compiles"] >= 3
    assert ev["dispatch_kind"] == "serving"
    assert ev["window_s"] == 60.0
    assert ev["last_compile_s"] == pytest.approx(0.4)


def _counter_total(name: str, **labels) -> float:
    from oryx_tpu.common.metrics import get_registry

    total = 0.0
    for key, v in get_registry().counter(name).series().items():
        if all(dict(key).get(k) == val for k, val in labels.items()):
            total += v
    return total


def test_batcher_new_k_bucket_increments_compile_telemetry(tmp_path):
    """Tier-1 (ISSUE 17): a shape-signature change (new k-bucket) must
    increment the compile counter/histogram, charge a compile_stall idle
    slice, and land a batcher.compile_stall span in the waterfall."""
    from oryx_tpu.common.tracing import get_tracer
    from oryx_tpu.serving.batcher import TopKBatcher, k_bucket

    tr = get_tracer()
    tr.configure(enabled=True, capacity=1024)
    try:
        rng = np.random.default_rng(7)
        y = jnp.asarray(rng.normal(size=(64, 8)), dtype=jnp.float32)
        rows = y.shape[0]
        kb_lo = min(k_bucket(5), rows)
        kb_hi = min(k_bucket(40), rows)
        assert kb_lo != kb_hi  # distinct shape signatures by construction
        before = _counter_total("oryx_xla_compiles_total", kind="serving")
        b = TopKBatcher()
        try:
            vec = rng.normal(size=8).astype(np.float32)
            b.submit(vec, 5, y)      # first signature (k-bucket kb_lo)
            b.submit(vec, 40, y)     # NEW signature (k-bucket kb_hi)
        finally:
            b.close()
        after = _counter_total("oryx_xla_compiles_total", kind="serving")
        assert after - before == 2.0
        stall_spans = [
            s for s in tr.snapshot() if s.name == "batcher.compile_stall"
        ]
        assert len(stall_spans) >= 2
        assert {s.attrs["k"] for s in stall_spans} >= {kb_lo, kb_hi}
        # the stall also landed in the device idle account
        gaps = get_perfattr().budget()["idle_gaps"]
        assert gaps.get("compile_stall", {}).get("seconds", 0.0) > 0.0
    finally:
        tr.configure(enabled=False, capacity=2048)


# ---- serving end-to-end: the attribution contract --------------------------


def _als_serving_config(bus: str, tmp_path, **extra):
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
        "oryx.monitoring.flight.dir": str(tmp_path / "flight"),
        "oryx.monitoring.perfattr.window-sec": 300,
        "oryx.serving.model-manager-class":
            "oryx_tpu.apps.als.serving.ALSServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.als",
        ],
    }
    overlay.update(extra)
    return load_config(overlay=overlay)


def _als_manager(cfg, n_users=32, n_items=64, features=8):
    from oryx_tpu.apps.als.serving import (
        ALSServingModel,
        ALSServingModelManager,
    )
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


def _phase_metric_sums(text: str) -> dict[str, dict[str, float]]:
    """family -> {label value -> _sum} for the perfattr histograms."""
    from oryx_tpu.cli import _parse_metric_sample

    out: dict[str, dict[str, float]] = {
        "oryx_request_phase_seconds": {},
        "oryx_device_idle_gap_seconds": {},
        "oryx_serving_request_seconds": {},
    }
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        parsed = _parse_metric_sample(line)
        if parsed is None:
            continue
        name, labels, value = parsed
        for family, acc in out.items():
            if name == family + "_sum":
                key = labels.get("phase") or labels.get("cause") or (
                    labels.get("method", "")
                )
                acc[key] = acc.get(key, 0.0) + value
    return out


def test_e2e_attribution_covers_request_wall_clock(tmp_path):
    """The acceptance contract: after warmup, phase stamps must tile the
    measured request wall-clock (>= 95% of the serving-request histogram
    delta) and every idle gap must classify without unattributed share;
    /healthz must advertise the latency budget."""
    from e2e_common import http_request

    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.serving.server import ServingLayer

    cfg = _als_serving_config("mem://perfattr-e2e", tmp_path)
    manager = _als_manager(cfg)
    with ServingLayer(cfg, model_manager=manager) as sl:
        base = f"http://127.0.0.1:{sl.port}"
        # warmup: backend init + first-shape compiles + the one-time
        # startup idle gap are NOT steady state
        for i in range(5):
            status, _ = http_request("GET", f"{base}/recommend/u{i}?howMany=4")
            assert status == 200
        time.sleep(0.3)              # drain in-flight flushes
        before = _phase_metric_sums(get_registry().render_prometheus())

        n = 40
        for i in range(n):
            status, _ = http_request(
                "GET", f"{base}/recommend/u{i % 16}?howMany=6"
            )
            assert status == 200
        time.sleep(0.3)
        after = _phase_metric_sums(get_registry().render_prometheus())

        def delta(family: str) -> dict[str, float]:
            return {
                k: after[family].get(k, 0.0) - before[family].get(k, 0.0)
                for k in after[family]
            }

        phase_d = delta("oryx_request_phase_seconds")
        serving_d = sum(delta("oryx_serving_request_seconds").values())
        attributed = sum(phase_d.values())
        assert serving_d > 0.0
        assert attributed >= 0.95 * serving_d, (
            f"phases covered {attributed:.4f}s of {serving_d:.4f}s "
            f"({attributed / serving_d:.1%}): {phase_d}"
        )
        # the hot phases all landed samples
        assert phase_d.get("queue_wait", 0.0) > 0.0
        assert phase_d.get("device", 0.0) + phase_d.get(
            "host_fallback", 0.0
        ) > 0.0
        assert phase_d.get("serialize", 0.0) > 0.0
        # unknown phases never appear in THIS window: the hot path only
        # stamps catalog names (other tests may have seeded odd labels
        # into the process-global family, so zero-delta keys are ignored)
        assert {k for k, v in phase_d.items() if v > 0.0} <= set(PHASES)

        # steady state: every idle gap classified, zero unattributed
        gap_d = delta("oryx_device_idle_gap_seconds")
        classified = sum(v for k, v in gap_d.items() if k != "unattributed")
        assert classified > 0.0
        assert gap_d.get("unattributed", 0.0) == pytest.approx(0.0, abs=1e-9)

        # /healthz advertises the budget the fleet front federates
        status, body = http_request("GET", f"{base}/healthz")
        assert status == 200
        lb = json.loads(body).get("latency_budget")
        assert lb and lb["phases"], body[:400]
        assert "device" in lb["phases"] or "host_fallback" in lb["phases"]
        for row in lb["phases"].values():
            assert set(row) == {"count", "p50_ms", "p99_ms", "share"}

        # the `oryx perf` report renders from the same exposition
        from oryx_tpu.cli import render_perf_report

        report = render_perf_report(get_registry().render_prometheus())
        assert "latency budget (oryx_request_phase_seconds)" in report
        assert "queue_wait" in report
        assert "device idle gaps (oryx_device_idle_gap_seconds)" in report


def test_e2e_forced_fast_burn_leaves_profile_capture(tmp_path):
    """A latency fast-burn must leave a harvestable profile-capture
    event (with the phase-budget payload) in the on-disk flight ring —
    the profile corpse contract."""
    from e2e_common import http_request

    from oryx_tpu.common import flightrec
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.perfattr import configure_perfattr
    from oryx_tpu.serving.server import ServingLayer

    cfg = _als_serving_config(
        "mem://perfattr-burn", tmp_path, **{
            # every request is "bad": an impossible latency objective
            "oryx.monitoring.slo.latency.threshold-sec": 1e-9,
            "oryx.monitoring.perfattr.burn-capture.burn-threshold": 1,
            "oryx.monitoring.perfattr.burn-capture.check-interval-sec": 0,
            "oryx.monitoring.perfattr.burn-capture.capture-sec": 0.05,
            "oryx.monitoring.perfattr.burn-capture.min-interval-sec": 600,
        }
    )
    manager = _als_manager(cfg)
    flight_dir = str(tmp_path / "flight")
    pa = get_perfattr()
    try:
        with ServingLayer(cfg, model_manager=manager) as sl:
            # a prior test may have armed the gates; this test owns them
            pa._next_burn_check = 0.0
            pa._burn_cooldown_until = 0.0
            base = f"http://127.0.0.1:{sl.port}"
            deadline = time.monotonic() + 15.0
            events = []
            while time.monotonic() < deadline:
                status, _ = http_request(
                    "GET", f"{base}/recommend/u0?howMany=4"
                )
                assert status == 200
                # > the SLO sampler's min gap, so the tracker's burn ring
                # accumulates a baseline then a hot sample
                time.sleep(0.06)
                events = [
                    e for e in flightrec.read_events(flight_dir)
                    if e.get("kind") == "profile-capture"
                ]
                if events:
                    break
            assert events, "fast burn left no profile-capture event"
            ev = events[-1]
            assert ev["trigger"] == "latency-fast-burn"
            assert ev["burn_rate"] >= 1.0
            assert ev["budget"]["phases"], ev
            assert "profile" in ev
    finally:
        # restore process-global perfattr defaults for later tests
        configure_perfattr(load_config())


# ---- fleet federation -------------------------------------------------------


def test_merge_latency_budgets():
    from oryx_tpu.fleet.observe import merge_latency_budgets

    b1 = {
        "window_s": 60,
        "phases": {
            "device": {"count": 10, "p50_ms": 2.0, "p99_ms": 8.0,
                       "share": 0.8},
            "parse": {"count": 10, "p50_ms": 0.5, "p99_ms": 1.0,
                      "share": 0.2},
        },
        "idle_gaps": {"empty_queue": {"seconds": 3.0, "share": 1.0}},
    }
    b2 = {
        "window_s": 60,
        "phases": {
            "device": {"count": 30, "p50_ms": 4.0, "p99_ms": 16.0,
                       "share": 1.0},
        },
        "idle_gaps": {
            "empty_queue": {"seconds": 1.0, "share": 0.5},
            "host_serialize": {"seconds": 1.0, "share": 0.5},
        },
    }
    merged = merge_latency_budgets([b1, b2, None, "junk"])
    assert merged["replicas"] == 2
    dev = merged["phases"]["device"]
    assert dev["count"] == 40
    # count-weighted mean of the replica percentiles
    assert dev["p50_ms"] == pytest.approx((10 * 2.0 + 30 * 4.0) / 40)
    assert dev["p99_ms"] == pytest.approx((10 * 8.0 + 30 * 16.0) / 40)
    assert merged["phases"]["parse"]["count"] == 10
    # shares recomputed from merged mass, ~sum to 1
    assert sum(
        p["share"] for p in merged["phases"].values()
    ) == pytest.approx(1.0, abs=0.01)
    gaps = merged["idle_gaps"]
    assert gaps["empty_queue"]["seconds"] == pytest.approx(4.0)
    assert list(gaps) == ["empty_queue", "host_serialize"]  # ranked
    assert merge_latency_budgets([]) == {
        "window_s": 0.0, "replicas": 0, "phases": {}, "idle_gaps": {},
    }


# ---- `oryx perf` renderer ---------------------------------------------------


SAMPLE_EXPOSITION = """\
# HELP oryx_request_phase_seconds per-request phase time
# TYPE oryx_request_phase_seconds histogram
oryx_request_phase_seconds_bucket{phase="device",le="0.001"} 0
oryx_request_phase_seconds_bucket{phase="device",le="0.01"} 8
oryx_request_phase_seconds_bucket{phase="device",le="+Inf"} 10
oryx_request_phase_seconds_sum{phase="device"} 0.2
oryx_request_phase_seconds_count{phase="device"} 10
oryx_request_phase_seconds_bucket{phase="parse",le="0.001"} 10
oryx_request_phase_seconds_bucket{phase="parse",le="+Inf"} 10
oryx_request_phase_seconds_sum{phase="parse"} 0.005
oryx_request_phase_seconds_count{phase="parse"} 10
# TYPE oryx_device_idle_gap_seconds histogram
oryx_device_idle_gap_seconds_sum{cause="empty_queue"} 9.0
oryx_device_idle_gap_seconds_count{cause="empty_queue"} 12
oryx_device_idle_gap_seconds_sum{cause="compile_stall"} 1.0
oryx_device_idle_gap_seconds_count{cause="compile_stall"} 2
# TYPE oryx_xla_compiles_total counter
oryx_xla_compiles_total{kind="serving"} 2
# TYPE oryx_xla_compile_seconds histogram
oryx_xla_compile_seconds_sum{kind="serving"} 1.0
oryx_xla_compile_seconds_count{kind="serving"} 2
"""


def test_render_perf_report_from_exposition():
    from oryx_tpu.cli import render_perf_report

    report = render_perf_report(SAMPLE_EXPOSITION)
    lines = report.splitlines()
    # device ranks above parse (share of summed seconds)
    dev_i = next(i for i, ln in enumerate(lines) if "device " in ln)
    parse_i = next(i for i, ln in enumerate(lines) if "parse" in ln)
    assert dev_i < parse_i
    dev_line = lines[dev_i]
    assert "10" in dev_line and "10ms" in dev_line       # p50 bucket bound
    assert "97.6%" in dev_line                           # 0.2 / 0.205
    # p99 beyond the largest finite bound renders as an honest ">"
    assert ">10ms" in dev_line
    assert "empty_queue" in report and "90.0%" in report
    assert "compile_stall" in report
    assert "xla compiles (oryx_xla_compiles_total)" in report
    assert "serving" in report
    # empty exposition renders placeholders, not a crash
    empty = render_perf_report("")
    assert "(no phase samples yet)" in empty
    assert "(no compiles recorded yet)" in empty


def test_parse_metric_sample_edges():
    from oryx_tpu.cli import _parse_metric_sample

    assert _parse_metric_sample("foo 1.5") == ("foo", {}, 1.5)
    name, labels, v = _parse_metric_sample(
        'h_bucket{a="x",le="+Inf"} 7 # {trace_id="abc"} 0.2 123'
    )
    assert name == "h_bucket" and labels == {"a": "x", "le": "+Inf"}
    assert v == 7.0
    assert _parse_metric_sample("# HELP foo bar") is None
    assert _parse_metric_sample("foo{a=") is None
    assert _parse_metric_sample("foo nan_is_fine_but_words_are_not") is None
