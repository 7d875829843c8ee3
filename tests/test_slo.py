"""SLO burn rates (ISSUE 14): config-declared objectives computed from
the existing counters/histograms. The acceptance property: the burn rate
MOVES under an induced shed storm (sheds are client-visible 503s) and
returns to ~0 after recovery, as the window slides past the incident."""

from __future__ import annotations

import time

import pytest

from oryx_tpu.common import slo
from oryx_tpu.common.config import load_config
from oryx_tpu.common.metrics import get_registry


def _cfg(fast=0.25, slow=0.8, **extra):
    return load_config(overlay={
        "oryx.monitoring.slo.fast-window-sec": fast,
        "oryx.monitoring.slo.slow-window-sec": slow,
        **extra,
    })


def _gap():
    # the tracker stores at most one sample per _MIN_SAMPLE_GAP_S; tests
    # must step past it so consecutive reads see distinct samples
    time.sleep(slo._MIN_SAMPLE_GAP_S + 0.02)


def test_burn_math_is_exact_on_an_isolated_source():
    """Exact burn-rate arithmetic on a private tracker (the serving
    trackers are process singletons whose windows legitimately contain
    other tests' traffic): bad fraction over (1 - objective), per
    window."""
    counts = {"total": 0.0, "bad": 0.0}
    t = slo.SloTracker(
        "math-test", 0.999,
        lambda: (counts["total"], counts["bad"]),
        fast_s=0.25, slow_s=0.8,
    )
    assert t.burn_rate(t.fast_s) == 0.0  # baseline sample
    _gap()
    counts["total"] += 50
    counts["bad"] += 50  # every request shed: bad fraction 1.0
    assert t.burn_rate(t.fast_s) == pytest.approx(1000.0)
    assert t.budget_remaining() == pytest.approx(1.0 - 1000.0)
    _gap()
    counts["total"] += 50  # recovery traffic: bad fraction 0.5 so far
    assert t.burn_rate(t.fast_s) == pytest.approx(500.0)
    # the fast window slides entirely past the storm
    time.sleep(t.fast_s + 0.05)
    counts["total"] += 20
    assert t.burn_rate(t.fast_s) == 0.0


def test_burn_moves_under_shed_storm_and_recovers():
    """The acceptance property on the REAL serving tracker: an induced
    shed storm (deliberate 503s) drives oryx_slo_burn_rate far past the
    page threshold, and recovery returns it to ~0 once the fast window
    slides past the storm."""
    slo.ensure_serving_slos(_cfg())
    t = slo.tracker("serving-availability")
    assert t is not None
    c = get_registry().counter("oryx_serving_requests_total")
    g = get_registry().gauge("oryx_slo_burn_rate")
    _gap()
    t.burn_rate(t.fast_s)  # baseline sample
    _gap()
    for _ in range(50):
        c.inc(method="GET", status="503")
    burn = g.value(slo="serving-availability", window="fast")
    assert burn > 100.0, "shed storm must move the burn rate"
    assert t.budget_remaining() < 0  # budget overspent during the storm
    _gap()
    time.sleep(t.fast_s)
    for _ in range(20):
        c.inc(method="GET", status="200")
    assert g.value(slo="serving-availability", window="fast") == 0.0


def test_latency_slo_counts_slow_requests():
    cfg = _cfg(**{
        "oryx.monitoring.slo.latency.objective": 0.9,
        "oryx.monitoring.slo.latency.threshold-sec": 0.25,
    })
    slo.ensure_serving_slos(cfg)
    t = slo.tracker("serving-latency")
    h = get_registry().histogram("oryx_serving_request_seconds")
    # samples the singleton took under another test's threshold count
    # another series (tests/test_perfattr.py sets 1e-9: every request slow),
    # and a window that starts at one reads 0.0 whatever happens here
    with t._lock:
        t._samples.clear()
    _gap()
    t.burn_rate(t.fast_s)  # baseline sample
    _gap()
    for _ in range(40):
        h.observe(0.01, method="GET")   # fast
    for _ in range(40):
        h.observe(1.5, method="GET")    # past threshold
    # ~half the window's requests are slow against a 0.1 budget: burn ~5
    # (loose bounds: the singleton's window may hold other tests' traffic)
    burn = t.burn_rate(t.fast_s)
    assert 2.0 < burn <= 5.01, burn


def test_front_availability_counts_unanswered_requests():
    slo.ensure_front_slos(_cfg())
    t = slo.tracker("front-availability")
    c = get_registry().counter("oryx_fleet_front_requests_total")
    _gap()
    t.burn_rate(t.fast_s)  # baseline sample
    _gap()
    for _ in range(9):
        c.inc(replica="r0")
    c.inc(replica="none")  # the front's own 503: no replica answered
    # bad fraction ~0.1 over budget 0.001 -> burn ~100 (loose: singleton)
    burn = t.burn_rate(t.fast_s)
    assert 50.0 < burn <= 100.01, burn


def test_idle_window_is_not_an_outage():
    # a fresh tracker (the process singletons may carry another test's
    # storm inside their slow window): zero traffic must read as burn 0
    # and a full budget, never as an outage
    t = slo.SloTracker(
        "idle-test", 0.999, lambda: (0.0, 0.0), fast_s=0.25, slow_s=0.8,
    )
    assert t.burn_rate(t.fast_s) == 0.0
    _gap()
    assert t.burn_rate(t.fast_s) == 0.0
    assert t.budget_remaining() == pytest.approx(1.0)


def test_gauges_render_on_the_registry():
    slo.ensure_serving_slos(_cfg())
    slo.ensure_front_slos(_cfg())
    text = get_registry().render_prometheus()
    for series in (
        'oryx_slo_burn_rate{slo="serving-availability",window="fast"}',
        'oryx_slo_burn_rate{slo="serving-availability",window="slow"}',
        'oryx_slo_burn_rate{slo="serving-latency",window="fast"}',
        'oryx_slo_burn_rate{slo="front-availability",window="fast"}',
        'oryx_slo_error_budget_remaining{slo="serving-availability"}',
    ):
        assert series in text, text[:2000]


def test_disabled_slo_block_registers_nothing():
    before = set(slo._trackers)
    slo.ensure_serving_slos(load_config(overlay={
        "oryx.monitoring.slo.enabled": False,
    }))
    assert set(slo._trackers) == before


def test_histogram_totals_below_threshold_semantics():
    from oryx_tpu.common.metrics import Histogram

    h = Histogram("t", "t", buckets=(0.1, 0.25, 1.0))
    for v in (0.05, 0.2, 0.9, 5.0):
        h.observe(v)
    assert h.totals_below(0.25) == (2, 4)   # exact bound
    assert h.totals_below(0.5) == (2, 4)    # between bounds: conservative
    assert h.totals_below(0.01) == (0, 4)   # under the first bound
    assert h.totals_below(2.0) == (3, 4)


def test_counter_series_snapshot():
    from oryx_tpu.common.metrics import Counter

    c = Counter("t_total", "t", labeled=True)
    c.inc(status="200")
    c.inc(2.0, status="503")
    series = c.series()
    assert series[(("status", "200"),)] == 1.0
    assert series[(("status", "503"),)] == 2.0
