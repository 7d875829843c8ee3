"""Honest-labeling and MFU-accounting contracts for the bench harness.

Round-2 verdict: a CPU artifact must never wear a TPU metric's name (it
reported a 100k-item cpu run as als_recommend_http_qps_1M_... with
vs_baseline computed against the 1M-item baseline), and no MFU accounting
existed anywhere. Since PR 21 the harness goes further: with no TPU it
measures nothing and exits non-zero. These pin the behavior.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402  (repo-root module, no jax at import time)
from oryx_tpu.ops import flops  # noqa: E402


def test_items_label():
    assert bench._items_label(1_000_000) == "1M"
    assert bench._items_label(25_000_000) == "25M"
    assert bench._items_label(100_000) == "100k"
    assert bench._items_label(1234) == "1234"


def test_metric_name_carries_true_scale_and_platform():
    assert (
        bench._metric_name("als_recommend_http_qps", 1_000_000, 50, "tpu")
        == "als_recommend_http_qps_1M_items_50f"
    )
    # the degraded path must be visibly degraded
    assert (
        bench._metric_name("als_recommend_http_qps", 100_000, 50, "cpu")
        == "als_recommend_http_qps_100k_items_50f_cpu"
    )


def test_vs_baseline_null_on_config_mismatch():
    # matches the 1M x 50f row the 437-qps baseline was measured at
    assert bench._vs_baseline(874.0, 1_000_000, 50) == 2.0
    # any other scale: not like-for-like -> null
    assert bench._vs_baseline(703.0, 100_000, 50) is None
    assert bench._vs_baseline(160.0, 1_000_000, 250) is None


def test_bench_imports_no_jax():
    # the orchestration process must never import jax: a parent that has
    # touched JAX holds the chip against its own stage children
    assert "jax" not in sys.modules or not hasattr(
        sys.modules.get("bench"), "jax"
    )


def test_bench_without_tpu_exits_nonzero_and_reports_nothing():
    """No TPU (this host): `python bench.py` stops at its first stage,
    exits non-zero, says why on stderr, and prints no result row — no
    CPU number can stand under a device metric's name."""
    import os
    import subprocess

    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "bench.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_stage_row_carries_device_stamp(monkeypatch):
    """Every stage row comes back stamped with the platform, device kind
    and device count the STAGE saw; a host-CPU stage's stamp stays with
    its own block and never overwrites the chip's."""
    rows = {
        "_bench_body": ("ok", {
            "metric": "als_recommend_kernel_qps_1M_items_50f", "value": 9.0,
            "unit": "qps", "platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1,
        }),
        "_bench_http_lsh_body": ("ok", {
            "value": 40.0, "vs_baseline": 0.09, "platform": "cpu",
            "device_kind": "cpu", "device_count": 1,
        }),
    }
    monkeypatch.setattr(
        bench, "_run_bench",
        lambda env, timeout, body, host_cpu, allow_partial: rows.get(
            body, ("failed", None)
        ),
    )
    monkeypatch.setattr(bench, "_harvest_stage_flight", lambda body: None)
    errors: list = []
    result = bench._run_suite({}, errors)
    assert (result["platform"], result["device_kind"], result["device_count"]) == (
        "tpu", "TPU v5 lite", 1,
    )
    assert result["host_cpu_stages"]["_bench_http_lsh_body"]["platform"] == "cpu"
    assert result["lsh_qps"] == 40.0 and result["stages_done"] == 2
    assert len(errors) == len(bench._SUITE_STAGES) - 2  # the rest "failed"
    # no TPU at the first stage: no result at all
    monkeypatch.setattr(bench, "_run_bench", lambda *a, **k: ("no-tpu", None))
    assert bench._run_suite({}, []) is None


def test_peak_flops_lookup():
    # published v5e peaks: 197 TFLOP/s bf16, 393 TOP/s int8
    assert flops.peak_flops_for_kind("TPU v5 lite") == 197e12
    assert flops.peak_flops_for_kind("TPU v5e") == 197e12
    assert flops.peak_flops_for_kind("TPU v5 lite", "int8") == 393e12
    assert flops.peak_flops_for_kind("TPU v5p") == 459e12
    assert flops.peak_flops_for_kind("TPU v4") == 275e12
    assert flops.peak_flops_for_kind("TPU v6e") == 918e12
    assert flops.peak_flops_for_kind("TPU v5 lite", "float32") == 98.5e12
    # an unknown kind stays None — a bare "v5" is not guessed to be v5p
    assert flops.peak_flops_for_kind("TPU v5") is None
    assert flops.peak_flops_for_kind("Radical New Chip") is None


def test_analytic_flop_counts():
    # serving: one [B,F]x[F,I] matmul
    assert flops.topk_score_flops(1, 1_000_000, 50) == 2 * 1_000_000 * 50
    # ALS half-sweep: 2BPK^2 + 2BPK + fixed-side gram 2MK^2
    b, p, k, m = 1024, 128, 50, 4096
    assert flops.als_halfstep_flops(b, p, k, m) == (
        2 * b * p * k * k + 2 * b * p * k + 2 * m * k * k
    )
    assert flops.mfu(98.5e12, 197e12) == 0.5
    assert flops.mfu(1.0, None) is None


def test_train_als_reports_flops():
    import numpy as np

    from oryx_tpu.ops.als import aggregate_interactions, train_als

    rng = np.random.default_rng(0)
    users = rng.integers(0, 64, 2000)
    items = rng.integers(0, 48, 2000)
    vals = np.ones(2000)
    data = aggregate_interactions(users, items, vals, implicit=True)
    timings: dict = {}
    train_als(data, features=8, iterations=2, timings=timings)
    assert timings["train_flops"] > 0
    assert timings["train_s"] > 0
    # FLOPs scale linearly with iterations
    t2: dict = {}
    train_als(data, features=8, iterations=4, timings=t2)
    assert abs(t2["train_flops"] / timings["train_flops"] - 2.0) < 1e-9


def test_batcher_accumulates_flops():
    import numpy as np

    from oryx_tpu.serving.batcher import TopKBatcher

    class FakeY:
        shape = (100, 8)

    b = TopKBatcher(device_timeout=60)
    y = np.random.default_rng(1).standard_normal((100, 8)).astype(np.float32)

    # real dispatch through the batcher against a jax array
    import jax.numpy as jnp

    yj = jnp.asarray(y)
    vals, idx = b.submit(np.ones(8, dtype=np.float32), 3, yj, host_mat=y)
    assert len(idx) == 3
    assert b.flops_scored == 2.0 * 1 * 100 * 8
    b.close()


def test_baseline_bound_attached_and_labeled():
    result: dict = {}
    bench._attach_baseline_bound(result, build_s=100.0, nnz=25_000_000)
    bound = result["spark_baseline_bound"]
    # the analytic floor: 10 it x 2 sides x nnz x (2f^2 + 2f) / 200 GF/s
    expect_floor = 10 * 2.0 * 25e6 * (2 * 50**2 + 2 * 50) / 200e9
    assert bound["analytic_floor_seconds"] == round(expect_floor, 1)
    assert bound["speedup_vs_mllib_floor"] == round(expect_floor / 100.0, 2)
    # anchor scales linearly in interactions from the 25M range
    assert bound["literature_anchor_seconds"] == [300.0, 1800.0]
    assert bound["speedup_vs_mllib_anchor_range"] == [3.0, 18.0]
    # both must say what they are
    assert "anchor, not a measurement" in bound["literature_anchor_basis"]
    assert "optimistic" in bound["analytic_floor_basis"]
    assert "spark_baseline.py" in bound["command"]


def test_baseline_bound_without_build():
    result: dict = {}
    bench._attach_baseline_bound(result, build_s=None, nnz=1_000_000)
    bound = result["spark_baseline_bound"]
    assert "speedup_vs_mllib_floor" not in bound
    assert bound["literature_anchor_seconds"] == [12.0, 72.0]


def test_compact_summary_contract():
    """The LAST stdout line must always carry the contract keys and the
    device stamp, and stay small enough to survive a bounded capture of
    the end of stdout."""
    result = {
        "metric": "als_recommend_http_qps_1M_items_50f", "value": 5000.0,
        "unit": "qps", "vs_baseline": 11.4, "platform": "tpu",
        "device_kind": "TPU v5 lite", "device_count": 1,
        "stages_done": 6, "lsh_qps": 40.0, "lsh_vs_baseline": 0.09,
        "scaling": [
            {"items": 10**6, "features": 50, "qps": 9000.0,
             "vs_lsh_baseline": 20.6, "mfu": 0.1, "compile_s": 3.0},
            {"items": 2 * 10**7, "features": 250, "qps": 100.0},
        ],
        "spark_baseline_bound": {
            "speedup_vs_mllib_floor": 2.5,
            "speedup_vs_mllib_anchor_range": [1.0, 6.0],
            "analytic_floor_basis": "long text " * 50,
        },
        "error": "w" * 1000 + " _bench_train_body timeout",
        "big_diag": ["x" * 100] * 50,  # detail-only ballast
    }
    s = bench._compact_summary(result)
    line = json.dumps(s)
    assert len(line) < 2000, len(line)
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in s
    assert (s["platform"], s["device_kind"], s["device_count"]) == (
        "tpu", "TPU v5 lite", 1,
    )
    assert s["final"] is True
    assert s["scaling_rows"] == 2
    assert s["scaling_best"]["vs_lsh_baseline"] == 20.6
    assert s["speedup_vs_mllib_anchor_range"] == [1.0, 6.0]
    # both ends of a long error survive truncation
    assert "_bench_train_body timeout" in s["error"]
    assert s["error"].startswith("w")
    assert "big_diag" not in s
    # degenerate artifact still carries the contract keys
    s2 = bench._compact_summary({"metric": "m", "value": 0.0, "unit": "qps"})
    assert s2["vs_baseline"] is None


def test_lsh_stage_registered_and_cpu_pinned():
    stages = {s[0]: s for s in bench._SUITE_STAGES}
    body, cap, allow_partial, merge, host_cpu = stages["_bench_http_lsh_body"]
    assert host_cpu is True  # host-CPU parity row, even on a chip host
    result: dict = {}
    merge(result, {
        "value": 40.0, "vs_baseline": 0.09, "lsh_sample_rate": 0.3,
        "lsh_num_hashes": 2, "host_cores": 1,
        "qps_per_core_vs_baseline": 2.9, "latency_ms_p50": 11.0,
    })
    assert result["lsh_qps"] == 40.0
    assert result["lsh_vs_baseline"] == 0.09
    assert result["qps_per_core_vs_baseline"] == 2.9
    assert result["lsh_latency_ms_p50"] == 11.0


def test_scale_body_chunked_path(monkeypatch, capsys):
    """With the chunking thresholds lowered, the CPU-scale sweep takes
    the chunked scoring path and reports chunk counts — the path the
    20M x 250 row needs on hardware (its one-shot dispatch failed in
    round 5)."""
    import json as _json

    import bench

    monkeypatch.setattr(bench, "_CHUNK_OVER_BYTES", 64 * 1024)
    monkeypatch.setattr(bench, "_CHUNK_TARGET_BYTES", 32 * 1024)
    bench._bench_scale_body()
    out = capsys.readouterr().out
    last = [ln for ln in out.splitlines() if ln.strip().startswith("{")][-1]
    rows = _json.loads(last)["rows"]
    assert rows and all("error" not in r for r in rows), rows
    chunked_rows = [r for r in rows if r.get("chunked")]
    assert chunked_rows, rows  # 100k x 50f bf16 = 10MB > 64KB: chunked
    assert all(r["qps"] > 0 for r in rows)
