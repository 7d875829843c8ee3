"""Serving score modes (oryx.serving.api.score-mode = exact|quantized|
approx): candidate-set parity at the kernel layer, quantized delta-sync
discipline, per-mode perfstats labeling, and the acceptance path — both
non-exact modes serving end-to-end over HTTP (batcher -> frontend ->
fleet front) with recall@10 against exact holding the quality gate's
floor."""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


def _recall(got_ids, exact_ids) -> float:
    return len(set(got_ids) & set(exact_ids)) / max(1, len(exact_ids))


# ---------------------------------------------------------------------------
# kernel layer: the three modes' candidate sets
# ---------------------------------------------------------------------------

def test_score_mode_candidate_sets_parity():
    """Exact equality where the math is exact: the exact mode against the
    XLA reference, the quantized Pallas kernel against the quantized XLA
    reference (identical quantized scores), and — on CPU, where
    approx_max_k computes exactly — the approx mode against exact."""
    from oryx_tpu.ops.als import (
        topk_dot_batch,
        topk_dot_batch_approx,
        topk_dot_batch_quant_xla,
        topk_dot_batch_xla,
    )
    from oryx_tpu.ops.pallas_topk import topk_dot_batch_pallas
    from oryx_tpu.ops.transfer import QuantizedMatrix, quantize_rows_int8

    rng = np.random.default_rng(5)
    y = rng.standard_normal((3000, 24)).astype(np.float32)
    xs = rng.standard_normal((12, 24)).astype(np.float32)
    xs_j, y_j = jnp.asarray(xs), jnp.asarray(y)

    v_e, i_e = topk_dot_batch_xla(xs_j, y_j, k=10)
    # exact mode through the dispatcher (CPU -> XLA path)
    v_d, i_d = topk_dot_batch(xs_j, y_j, k=10)
    assert np.array_equal(np.asarray(i_d), np.asarray(i_e))

    # quantized: dispatcher (QuantizedMatrix -> quant XLA) and the Pallas
    # quantized kernel agree index-for-index — same quantized scores
    q, s = quantize_rows_int8(y)
    qm = QuantizedMatrix(jnp.asarray(q), jnp.asarray(s))
    v_q, i_q = topk_dot_batch(xs_j, qm, k=10)
    v_qx, i_qx = topk_dot_batch_quant_xla(
        xs_j, jnp.asarray(q), jnp.asarray(s), k=10
    )
    assert np.array_equal(np.asarray(i_q), np.asarray(i_qx))
    v_qp, i_qp = topk_dot_batch_pallas(
        xs_j, jnp.asarray(q), scales=jnp.asarray(s), k=10,
        block_b=8, block_i=512, interpret=True,
    )
    assert np.array_equal(np.asarray(i_qp), np.asarray(i_qx))
    np.testing.assert_allclose(np.asarray(v_qp), np.asarray(v_qx), atol=1e-4)

    # quantized candidates recover the exact top-k after the serve
    # path's exact rescore contract (here: overlap is already near-total)
    rec = np.mean([
        _recall(list(map(int, a)), list(map(int, b)))
        for a, b in zip(np.asarray(i_q), np.asarray(i_e))
    ])
    assert rec >= 0.9, rec

    # approx on CPU computes exactly
    v_a, i_a = topk_dot_batch_approx(xs_j, y_j, k=10, recall=0.95)
    assert np.array_equal(np.asarray(i_a), np.asarray(i_e))


def test_quantized_scatter_requantizes_dirty_rows_only():
    """PR 3's delta contract under quantization: a scatter re-quantizes
    ONLY the dirty rows — untouched int8 rows and scales are bit-identical
    to the previous view's."""
    from oryx_tpu.ops.transfer import (
        QuantizedMatrix, quantized_device_put, scatter_rows,
    )

    rng = np.random.default_rng(7)
    y = rng.standard_normal((256, 8)).astype(np.float32)
    qm = quantized_device_put(y)
    dirty = np.array([3, 77, 200], dtype=np.int32)
    new_rows = 5.0 * rng.standard_normal((3, 8)).astype(np.float32)
    qm2 = scatter_rows(qm, dirty, new_rows)
    assert isinstance(qm2, QuantizedMatrix)
    q_old, q_new = np.asarray(qm.q), np.asarray(qm2.q)
    s_old, s_new = np.asarray(qm.scale), np.asarray(qm2.scale)
    clean = np.setdiff1d(np.arange(256), dirty)
    assert np.array_equal(q_old[clean], q_new[clean])
    assert np.array_equal(s_old[clean], s_new[clean])
    # dirty rows dequantize back to the new values within the scale step
    assert q_new.shape == (256, 128) and not q_new[:, 8:].any()  # lane pad
    deq = q_new[dirty, :8].astype(np.float32) * s_new[dirty][:, None]
    np.testing.assert_allclose(deq, new_rows, atol=np.abs(new_rows).max() / 100)


# ---------------------------------------------------------------------------
# perfstats: per-dispatch score-mode labels
# ---------------------------------------------------------------------------

def test_batcher_labels_dispatch_records_with_score_mode():
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.ops.transfer import quantized_device_put
    from oryx_tpu.serving.batcher import TopKBatcher

    rng = np.random.default_rng(9)
    y = rng.standard_normal((4096, 8)).astype(np.float32)
    qm = quantized_device_put(y)
    ps = get_perfstats()
    c = get_registry().counter("oryx_score_mode_dispatches_total")
    before = c.value(score_mode="quantized")
    t0 = time.monotonic()
    b = TopKBatcher(max_batch=8)
    try:
        vals, idx = b.submit(
            np.ones(8, dtype=np.float32), 5, qm,
            host_mat=y, score_mode="quantized",
        )
        assert len(idx) == 5
    finally:
        b.close()
    assert c.value(score_mode="quantized") == before + 1
    recs = [
        r for r in ps.records_since(t0)
        if r.kind == "serving" and r.score_mode == "quantized"
    ]
    assert recs, "dispatch record missing its score_mode label"
    # the mode also rides into /debug/profile slice args
    assert recs[0].chrome_event(1)["args"]["score_mode"] == "quantized"


# ---------------------------------------------------------------------------
# serving model: quantized views + delta resync
# ---------------------------------------------------------------------------

def test_quantized_model_serves_and_delta_resyncs():
    from oryx_tpu.apps.als.serving import ALSServingModel, SyncConfig
    from oryx_tpu.apps.als.state import ALSState
    from oryx_tpu.ops.transfer import QuantizedMatrix

    rng = np.random.default_rng(13)
    n, f = 400, 12
    state = ALSState(f, implicit=True)
    state.y.bulk_set([f"i{j}" for j in range(n)],
                     rng.standard_normal((n, f)).astype(np.float32))
    model = ALSServingModel(state, score_mode="quantized", sync=SyncConfig())
    try:
        xu = rng.standard_normal(f).astype(np.float32)
        got = [i for i, _ in model.top_n(xu, 5)]
        assert isinstance(model._device_view[0], QuantizedMatrix)
        mat, ids, _v = state.y.snapshot()
        exact = [
            ids[int(j)]
            for j in np.argsort(-(np.asarray(mat) @ xu), kind="stable")[:5]
        ]
        # int8 selection + exact f32 rescore: top-5 matches exact here
        assert _recall(got, exact) >= 0.8
        # cosine path: the quantized unit view shares the int8 rows
        got_cos = model.top_n(xu, 5, cosine=True)
        assert len(got_cos) == 5

        # delta: dirty a few rows, wait for the background resync, and
        # require the served answers to track the new factors
        for j in (1, 7, 42):
            state.y.set(f"i{j}", (10.0 + j) * np.ones(f, dtype=np.float32))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            model.top_n(xu, 5)  # queries observe drift and kick resync
            dv = model._device_view
            if dv is not None and dv[2] == state.y.get_version():
                break
            time.sleep(0.05)
        dv = model._device_view
        assert dv[2] == state.y.get_version(), "resync never caught up"
        assert isinstance(dv[0], QuantizedMatrix)
        assert model.last_resync and model.last_resync["kind"] == "delta"
        # the cosine view keeps SHARING the device view's int8 rows
        # across deltas (its half of the sync is scale-only) — two full
        # int8 matrices must never go resident
        uv = model._unit_view
        if uv is not None and uv[2] == dv[2]:
            assert uv[0].q is dv[0].q
        got2 = [i for i, _ in model.top_n(np.ones(f, dtype=np.float32), 3)]
        assert "i42" in got2  # the updated all-positive row must surface
    finally:
        model.close()


# ---------------------------------------------------------------------------
# acceptance: quantized + approx end-to-end over HTTP and the fleet front
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["quantized", "approx"])
def test_score_mode_serves_end_to_end_http_and_fleet_front(mode):
    from oryx_tpu.apps.als.serving import ALSServingModelManager
    from oryx_tpu.bus.broker import get_broker, topics
    from oryx_tpu.bus.inproc import InProcBroker
    from oryx_tpu.common.artifact import ModelArtifact
    from oryx_tpu.common.config import load_config
    from oryx_tpu.fleet.front import FleetFront
    from oryx_tpu.serving.server import ServingLayer

    InProcBroker.reset_all()
    rng = np.random.default_rng(17)
    n, f = 1500, 16
    bus = f"mem://mode-{mode}"
    cfg = load_config(overlay={
        "oryx.id": f"mode-{mode}",
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.init-topics": True,
        "oryx.serving.api.score-mode": mode,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.als",
        ],
        "oryx.als.hyperparams.features": f,
    })
    topics.maybe_create(bus, "OryxUpdate", partitions=1)
    topics.maybe_create(bus, "OryxInput", partitions=1)
    x_mat = rng.standard_normal((8, f)).astype(np.float32)
    y_mat = rng.standard_normal((n, f)).astype(np.float32)
    art = ModelArtifact(app="als", tensors={"X": x_mat, "Y": y_mat})
    art.set_extension("features", str(f))
    art.set_extension("implicit", "true")
    art.set_extension("XIDs", [f"u{j}" for j in range(8)])
    art.set_extension("YIDs", [f"i{j}" for j in range(n)])
    get_broker(bus).send("OryxUpdate", "MODEL", art.to_string())

    manager = ALSServingModelManager(cfg)
    assert manager.score_mode == mode
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    front = None
    try:
        base = f"http://127.0.0.1:{serving.port}"
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(f"{base}/ready", timeout=5) as r:
                    if r.status == 200:
                        break
            except Exception:
                pass
            time.sleep(0.1)
        assert manager.model is not None and manager.model.score_mode == mode

        def exact_top10(uj: int) -> list[str]:
            scores = y_mat @ x_mat[uj]
            return [
                f"i{int(j)}"
                for j in np.argsort(-scores, kind="stable")[:10]
            ]

        # direct HTTP (batcher -> frontend)
        recalls = []
        for uj in range(8):
            with urllib.request.urlopen(
                f"{base}/recommend/u{uj}?howMany=10", timeout=30
            ) as r:
                assert r.status == 200
                got = [p[0] for p in json.loads(r.read())]
            recalls.append(_recall(got, exact_top10(uj)))
        assert np.mean(recalls) >= 0.95, (mode, recalls)

        # through the fleet front: the same request routed by the L7 tier
        front = FleetFront(
            load_config(
                overlay={"oryx.fleet.front.probe-interval-sec": 0.2}
            ),
            backends=[("r0", "127.0.0.1", serving.port)],
            port=0,
        )
        front.start()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{front.port}/recommend/u0?howMany=10",
            timeout=30,
        ) as r:
            assert r.status == 200
            got = [p[0] for p in json.loads(r.read())]
        assert _recall(got, exact_top10(0)) >= 0.9
    finally:
        if front is not None:
            front.close()
        serving.close()
