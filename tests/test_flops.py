"""Analytic FLOP accounting (ops/flops.py) and the two places that feed
it at runtime: the trainer's timings and the batcher's scored-FLOPs
counter. perfstats' MFU gauges and the benchmark's roofline share both
rest on these counts."""

from oryx_tpu.ops import flops


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

    b = TopKBatcher(device_timeout=60)
    y = np.random.default_rng(1).standard_normal((100, 8)).astype(np.float32)

    # real dispatch through the batcher against a jax array
    import jax.numpy as jnp

    yj = jnp.asarray(y)
    vals, idx = b.submit(np.ones(8, dtype=np.float32), 3, yj, host_mat=y)
    assert len(idx) == 3
    assert b.flops_scored == 2.0 * 1 * 100 * 8
    b.close()
