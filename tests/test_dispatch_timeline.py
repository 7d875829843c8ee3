"""One dispatch timeline (ISSUE 25): the dispatcher's regions in a profiler
session, the dispatch number on the record and the request's span,
`Tracer.region` on both clocks, and the parts of `serialize`.

CPU only: a region's duration here is never a device number; what is
checked is which regions exist, how they nest and what they carry."""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import timeline
from benchmarks.xplane import find_xplane
from oryx_tpu.common import tracing
from oryx_tpu.common.metrics import get_registry
from oryx_tpu.common.perfattr import PHASES, POST_STAGES, PhaseLedger, get_perfattr
from oryx_tpu.common.perfstats import get_perfstats
from oryx_tpu.common.tracing import Tracer, current_span, get_tracer
from oryx_tpu.serving.batcher import TopKBatcher


@pytest.fixture
def y():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.normal(size=(200, 8)), dtype=jnp.float32)


def _burst(batcher, y, ks, rounds=3):
    """`rounds` bursts of len(ks) concurrent submits; every future read.
    The pause lets the dispatcher reach its wait for a non-empty queue."""
    rng = np.random.default_rng(7)
    for _ in range(rounds):
        futures = [
            batcher.submit_nowait(rng.normal(size=8).astype(np.float32), k, y) for k in ks
        ]
        for f in futures:
            assert len(f.result(timeout=60)[1]) > 0
        time.sleep(0.02)


# -- the regions, in a profiler session ------------------------------------------


def test_profiler_session_shows_one_region_tree_per_dispatch(y, tmp_path):
    batcher = TopKBatcher()
    try:
        _burst(batcher, y, [10, 40], rounds=1)  # compile both k-buckets first
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        before = batcher.dispatches
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            # two k-buckets a burst: coalesced groups, two dispatches a pick
            _burst(batcher, y, [10, 12, 40, 44, 10, 40])
        finally:
            # the last distribute ends after the futures are set: let it
            deadline = time.monotonic() + 10
            while batcher._inflight and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)
            jax.profiler.stop_trace()
        moved = batcher.dispatches - before
    finally:
        batcher.close()
    tl = timeline.parse(find_xplane(tmp_path))
    assert tl["kernels"] is None  # no device plane on the CPU
    regions = tl["regions"]
    launches = regions["batcher.launch"]
    assert moved >= 2 and len(launches) == moved
    numbers = [e["dispatch"] for e in launches]
    assert len(set(numbers)) == moved
    assert numbers == list(range(numbers[0], numbers[0] + moved))  # one counter
    for launch in launches:
        assert launch["k_bucket"] in (16, 128) and 1 <= launch["rows"] <= launch["padded"]
        issue = timeline._inside(regions["batcher.issue"], launch)
        assert issue is not None  # exactly one, on the launch's thread
    for name in ("batcher.fetch", "batcher.distribute"):
        assert sorted(e["dispatch"] for e in regions[name]) == numbers, name
    by_number = {e["dispatch"]: e for e in regions["batcher.distribute"]}
    launch_of = {e["dispatch"]: e for e in launches}
    for fetch in regions["batcher.fetch"]:
        n = fetch["dispatch"]
        assert launch_of[n]["end"] <= fetch["start"]
        assert fetch["end"] <= by_number[n]["start"]
    assert len(regions["batcher.issue"]) == moved
    # every region opened has a reader or a documented use: the four the
    # timeline joins, and the ones that tile the dispatcher's two threads
    # and their launch, issue and fetch for the oryx_region_* counters'
    # readers; `batcher.full` only where a queued request found two
    # dispatches unresolved
    assert set(regions) - {"batcher.full"} == {
        "batcher.launch", "batcher.issue", "batcher.fetch", "batcher.distribute",
        "batcher.idle", "batcher.pick", "batcher.retire", "batcher.launch.form",
        "batcher.issue.upload", "batcher.issue.call", "batcher.issue.copy",
        "batcher.fetch.vals", "batcher.fetch.idx", "batcher.fetch.chunks",
        "batcher.await",
    }


# -- the dispatch's number on its record -------------------------------------------


def test_dispatch_record_carries_number_and_bucket(y):
    batcher = TopKBatcher()
    t_mark = time.monotonic()
    try:
        _burst(batcher, y, [10, 40, 12])
    finally:
        batcher.close()
    records = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert len(records) == batcher.dispatches >= 2
    assert sorted(r.dispatch for r in records) == list(range(len(records)))
    assert {r.k_bucket for r in records} == {16, 128}
    for r in records:
        assert r.t_start >= t_mark and r.wall_s > 0.0
        # what dispatch_shapes reads keeps its value (batcher._dispatch_bytes)
        assert r.bytes_moved == r.padded_rows * 8 * 4 + y.nbytes + r.padded_rows * r.k_bucket * 8
        args = r.chrome_event(1)["args"]
        assert args["dispatch"] == r.dispatch and args["k_bucket"] == r.k_bucket


def test_a_record_of_another_kind_has_no_number():
    rec = get_perfstats().record_dispatch(
        "train", flops=1.0, bytes_moved=1.0, wall_s=0.1, rows=1, padded_rows=1,
        valid_rows=1, capacity_rows=1,
    )
    assert rec.dispatch is None and rec.k_bucket is None
    assert "dispatch" not in rec.chrome_event(1)["args"]


def _gauge(name):
    for line in get_registry().render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


def test_fused_dispatch_carries_the_kernels_fold_count(y, monkeypatch):
    """The kernel's own count of the chunks it folded rides with the
    results to the DispatchRecord and the two /metrics counters (ISSUE 26);
    a dispatch on any other path reports none. The stub is the fused path
    itself, run by the Pallas interpreter as `topk_path` would on a TPU."""
    from oryx_tpu.ops import als
    from oryx_tpu.ops.pallas_topk import topk_dot_batch_pallas

    batcher = TopKBatcher.shared()
    batcher.register_gauges()  # as the serving layer does at start-up
    t_mark = time.monotonic()
    before = (batcher.chunks_folded, batcher.chunks_total)
    _burst(batcher, y, [10, 12], rounds=1)  # the XLA path, as on any CPU
    records = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert records and all(r.chunks_folded is None and r.chunks_total is None for r in records)
    assert "chunks_total" not in records[0].chrome_event(1)["args"]
    assert (batcher.chunks_folded, batcher.chunks_total) == before
    assert _gauge("oryx_topk_chunks") == float(before[1])

    def fused(xs, y, *, k, recall=1.0, counted=False, rows=None):
        return topk_dot_batch_pallas(
            xs, y, k=k, block_b=8, block_i=128, interpret=True, counted=counted,
            rows=rows,
        )

    monkeypatch.setattr(als, "topk_dot_batch", fused)
    t_mark = time.monotonic()
    _burst(batcher, y, [10, 12, 9], rounds=2)
    records = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert records
    for r in records:
        # 200 items in blocks of 128: two chunks a row block, the first
        # always folded (nothing beats -inf before it)
        row_blocks = -(-r.padded_rows // 8)
        assert r.chunks_total == 2 * row_blocks
        assert row_blocks <= r.chunks_folded <= r.chunks_total
        # a row block of 8 is one sublane tile: one tile a fold, none for a
        # fired chunk placed without a sort (the kernel's fourth count)
        assert r.fold_tiles == r.chunks_folded - r.chunks_inserted
        assert 0 <= r.chunks_inserted <= r.chunks_folded - row_blocks
        args = r.chrome_event(1)["args"]
        assert (args["chunks_folded"], args["chunks_total"]) == (r.chunks_folded, r.chunks_total)
        assert args["fold_tiles"] == r.fold_tiles
        assert args["chunks_inserted"] == r.chunks_inserted
    moved = (batcher.chunks_folded - before[0], batcher.chunks_total - before[1])
    assert moved == (
        sum(r.chunks_folded for r in records), sum(r.chunks_total for r in records)
    )
    assert _gauge("oryx_topk_chunks_folded") == float(batcher.chunks_folded)
    assert _gauge("oryx_topk_chunks") == float(batcher.chunks_total)
    assert _gauge("oryx_topk_fold_tiles") == float(batcher.fold_tiles) > 0
    assert _gauge("oryx_topk_chunks_inserted") == float(batcher.chunks_inserted)


@pytest.fixture
def tracing_on():
    tr = get_tracer()
    tr.configure(enabled=True)
    tr.clear()
    yield tr
    tr.configure(enabled=False)
    tr.clear()


def test_device_span_names_its_dispatch_and_regions_nest_in_the_ring(y, tracing_on):
    batcher = TopKBatcher()
    t_mark = time.monotonic()
    root = tracing_on.start("http.request")
    prev = tracing.swap_current(root)
    try:
        futures = [
            batcher.submit_nowait(np.ones(8, dtype=np.float32), k, y) for k in (10, 11, 12)
        ]
    finally:
        tracing.swap_current(prev)
    for f in futures:
        f.result(timeout=60)
    batcher.close()
    records = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    spans = tracing_on.snapshot()
    device = [s for s in spans if s.name == "batcher.device"]
    assert len(device) == 3
    assert {s.attrs["dispatch"] for s in device} <= {r.dispatch for r in records}
    assert all(s.parent_id == root.span_id for s in device)
    by_id = {s.span_id: s for s in spans}
    inner = [s for s in spans if s.name == "batcher.issue"]
    assert inner and all(by_id[s.parent_id].name == "batcher.launch" for s in inner)
    launch = next(s for s in spans if s.name == "batcher.launch")
    assert set(launch.attrs) == {"dispatch", "rows", "padded", "k_bucket"}
    assert launch.parent_id is None  # the dispatcher thread has no current span
    for name in ("batcher.fetch", "batcher.distribute"):
        assert {s.attrs["dispatch"] for s in spans if s.name == name} == {
            r.dispatch for r in records
        }


# -- Tracer.region ---------------------------------------------------------------


def test_region_with_tracing_off_writes_no_ring_slot():
    tr = Tracer(capacity=16)
    with tr.region("batcher.launch", dispatch=1) as region:
        assert current_span() is None and region._span is None
    assert tr.snapshot() == [] and next(tr._seq) == 0


def test_region_records_a_thread_bound_span_tree_when_tracing_is_on():
    tr = Tracer(capacity=16)
    tr.configure(enabled=True)
    with tr.region("outer", dispatch=4):
        outer = current_span()
        with tr.region("inner"):
            assert current_span().parent is outer
        assert current_span() is outer
    assert current_span() is None
    inner, outer = tr.snapshot()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.parent_id == outer.span_id and inner.trace_id == outer.trace_id
    assert outer.attrs == {"dispatch": 4} and outer.start <= inner.start <= inner.end <= outer.end


def test_region_closes_its_span_when_the_body_raises():
    tr = Tracer(capacity=16)
    tr.configure(enabled=True)
    with pytest.raises(ValueError):
        with tr.region("batcher.issue"):
            raise ValueError("boom")
    assert current_span() is None
    assert [s.name for s in tr.snapshot()] == ["batcher.issue"]


@pytest.mark.parametrize("enabled", [False, True])
def test_region_without_jax_still_works(monkeypatch, enabled):
    """fleet/front.py imports common/tracing.py in a process that may have
    no jax: the region degrades to its ring half."""
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # import raises
    monkeypatch.setattr(tracing, "_annotation_cls", ...)
    tr = Tracer(capacity=16)
    tr.configure(enabled=enabled)
    with tr.region("front.route", replica=2) as region:
        assert region._annotation is None
    assert tracing._annotation_cls is None  # looked for once, not per region
    assert [s.name for s in tr.snapshot()] == (["front.route"] if enabled else [])


# -- the parts of `serialize` -----------------------------------------------------


def _fixed_render(monkeypatch, ledger, clock):
    from types import SimpleNamespace

    from oryx_tpu.serving import app

    times = iter(clock)
    # the module's name `time`, not the time module: other threads keep theirs
    monkeypatch.setattr(app, "time", SimpleNamespace(monotonic=lambda: next(times)))
    req = app.Request(
        "GET", "/recommend/u1", {}, {}, b"", {"accept": "application/json"}, ledger=ledger
    )
    return app._render([["i1", 1.0]], req)


@pytest.mark.parametrize("with_post", [False, True])
def test_serialize_on_a_fixed_request_is_what_it_was(monkeypatch, with_post):
    ledger = PhaseLedger()
    ledger.add("device", 1.0, start=10.0)  # ends at 11.0
    if with_post:
        ledger.add_stage("handoff", 0.3)
        ledger.add_stage("rerank", 0.1)
    assert ledger.last_end() == 11.0 and ledger.total() == 1.0  # stages move neither
    status, body, _ = _fixed_render(monkeypatch, ledger, [11.5, 11.7])
    assert status == 200 and body == b'[["i1", 1.0]]'
    phases = {p: s for p, _, s in ledger.items()}
    assert phases["serialize"] == pytest.approx(0.7)  # anchored at the device phase's end
    stages = dict(ledger.stages())
    if with_post:
        assert stages == pytest.approx({"handoff": 0.3, "rerank": 0.1, "render": 0.2})
        assert sum(stages.values()) <= phases["serialize"]
    else:
        assert stages == {}  # not a top-n answer: no part is stamped


def test_observe_request_flushes_the_stages_into_one_family():
    h = get_registry().histogram("oryx_post_stage_seconds")
    before = {s: (h.count(stage=s), h.sum(stage=s)) for s in POST_STAGES}
    ledger = PhaseLedger()
    ledger.add("device", 1.0, start=10.0)
    for stage, seconds in zip(POST_STAGES, (0.3, 0.1, 0.2)):
        ledger.add_stage(stage, seconds)
    ledger.add_stage("render", float("nan"))  # dropped like a phase's
    get_perfattr().observe_request(ledger)
    get_perfattr().observe_request(ledger)  # idempotent per ledger
    for stage, seconds in zip(POST_STAGES, (0.3, 0.1, 0.2)):
        assert h.count(stage=stage) - before[stage][0] == 1
        assert h.sum(stage=stage) - before[stage][1] == pytest.approx(seconds)
    assert not set(POST_STAGES) & set(PHASES)  # parts, not phases


def test_post_stages_count_one_of_each_per_deferred_request(tmp_path):
    from e2e_common import http_request

    from oryx_tpu.apps.als.serving import ALSServingModel, ALSServingModelManager
    from oryx_tpu.apps.als.state import ALSState
    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    bus = "mem://dispatch-timeline"
    for topic in ("OryxInput", "OryxUpdate"):
        if not get_broker(bus).topic_exists(topic):
            get_broker(bus).create_topic(topic, 1)
    cfg = load_config(overlay={
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.monitoring.flight.dir": str(tmp_path / "flight"),
        "oryx.serving.model-manager-class": "oryx_tpu.apps.als.serving.ALSServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common", "oryx_tpu.serving.resources.als",
        ],
    })
    rng = np.random.default_rng(11)
    state = ALSState(8, implicit=True)
    state.x.bulk_set([f"u{i}" for i in range(16)], rng.standard_normal((16, 8), dtype=np.float32))
    state.y.bulk_set([f"i{i}" for i in range(64)], rng.standard_normal((64, 8), dtype=np.float32))
    state.set_expected(state.x.ids(), state.y.ids())
    manager = ALSServingModelManager(cfg)
    manager.model = ALSServingModel(state)
    reg = get_registry()
    stage_h = reg.histogram("oryx_post_stage_seconds")
    phase_h = reg.histogram("oryx_request_phase_seconds")

    def read():
        counts = {s: stage_h.count(stage=s) for s in POST_STAGES}
        sums = {s: stage_h.sum(stage=s) for s in POST_STAGES}
        return counts, sums, phase_h.sum(phase="serialize"), phase_h.count(phase="device")

    with ServingLayer(cfg, model_manager=manager) as sl:
        base = f"http://127.0.0.1:{sl.port}"
        assert http_request("GET", f"{base}/recommend/u0?howMany=4")[0] == 200  # compile
        time.sleep(0.3)  # the flush follows the response's last byte
        counts0, sums0, serialize0, device0 = read()
        n = 12
        threads = [
            threading.Thread(
                target=lambda i=i: http_request("GET", f"{base}/recommend/u{i}?howMany=5")
            )
            for i in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # not through _post: no part is stamped for these
        assert http_request("GET", f"{base}/ready")[0] == 200
        assert http_request("GET", f"{base}/metrics")[0] == 200
        deadline = time.monotonic() + 10  # the flush follows the last byte
        while read()[3] - device0 < n and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(0.1)
        counts1, sums1, serialize1, device1 = read()
    assert device1 - device0 == n
    assert {s: counts1[s] - counts0[s] for s in POST_STAGES} == dict.fromkeys(POST_STAGES, n)
    parts = sum(sums1[s] - sums0[s] for s in POST_STAGES)
    assert 0.0 < parts <= serialize1 - serialize0
