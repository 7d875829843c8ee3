"""Nightly 25M-scale quality gate (round-2 verdict #8).

The bf16 singularity guard (ops/als.py _half_step: jitter-retry on a
non-finite Cholesky, zero what still fails) fixed a real NaN poisoning
observed only at ML-25M scale — one marginal system rounded indefinite
by bf16 einsum inputs NaN'd gram() and with it the whole next half-sweep
(reference analogue: Solver.java's ill-conditioned check). A CI-sized
run can't reach the failure regime, so this gate runs the full 25M-shape
build at reduced sweeps on CPU, env-gated:

    ORYX_NIGHTLY=1 python -m pytest tests/test_quality_gate.py -q

Floors: AUC >= 0.87 — measured 0.9019 on this host (2026-07-30, full
25M shape, 3 sweeps, bf16, CPU, 108 s end-to-end, nan_rows 0), matching
the round-2 healthy-window ~0.90 at 10 sweeps; a NaN-poisoned or
guard-shredded build lands far below (a zeroed factor row scores 0
everywhere).
nan_rows == 0 always — the guard must REPAIR (jitter-retry), and any row
it zeroes re-enters the next half-sweep, so a persistent NaN/zeroed row
in the final factors means the guard regressed.
"""

import os

import pytest

nightly = pytest.mark.skipif(
    not os.environ.get("ORYX_NIGHTLY"),
    reason="25M-shape quality gate: minutes of CPU; set ORYX_NIGHTLY=1",
)

AUC_FLOOR = 0.87
ML25M_SHAPE = dict(n_users=162_000, n_items=59_000, nnz=25_000_000)


@nightly
def test_25m_shape_bf16_quality_floor():
    from oryx_tpu.ml.quality import build_and_evaluate

    rep = build_and_evaluate(
        **ML25M_SHAPE,
        features=50,
        iterations=3,  # reduced sweeps: enough to enter the bf16 failure
        # regime the guard exists for, without the full 10-sweep cost
        compute_dtype="bfloat16",
        seed=7,
    )
    assert rep.nan_rows == 0, (
        f"{rep.nan_rows} NaN factor rows — the _half_step singularity "
        f"guard regressed"
    )
    assert rep.auc >= AUC_FLOOR, (
        f"AUC {rep.auc:.4f} < floor {AUC_FLOOR} at 25M shape "
        f"(healthy ~0.90; NaN/zeroed rows or a trainer regression)"
    )


def test_quality_harness_smoke():
    """Always-on smoke at toy scale: the gate's harness itself must keep
    working between nightly runs (import path, report fields, AUC well
    above chance on structured data)."""
    from oryx_tpu.ml.quality import build_and_evaluate

    rep = build_and_evaluate(
        n_users=1200, n_items=800, nnz=60_000, features=16, iterations=4,
        compute_dtype="bfloat16", seed=3, sample_users=300,
    )
    assert rep.nan_rows == 0
    assert rep.auc > 0.70
    assert rep.build_s > 0 and rep.timings.get("train_flops", 0) > 0


# ---- RDF + k-means gates (round-3 verdict #5) ---------------------------
# Floors calibrated on this host (2026-07-30, CPU, seeds noted inline);
# each harness is the SAME code tools/quality_nightly.py runs, so a
# trainer regression fails both the gate and the nightly artifact.

RDF_ACC_FLOOR = 0.88  # raised round 5 with the feature_subset=14 default.
# Evidence: sqrt-auto measured 0.8813 at full covertype shape (2026-07-30,
# CPU, 905 s); subset 14 measured 0.8986 vs auto 0.8943 at 100k-example
# scale (round-5 sweep, ml/quality.py docstring). Each round's full-shape
# run lands in QUALITY_r{N}.json. Ceiling with 10% label noise is
# 1 - 0.1*(1 - 1/7) = 0.914
KMEANS_SSE_RATIO_CEIL = 1.05  # measured 1.000 across 5 seeds after the
# maximin reduction fix; the pre-fix k-means|| lost blobs at 1.7 - 4.2x
KMEANS_SIL_FLOOR = 0.5  # measured 0.74 at the toy shape


@nightly
def test_rdf_covertype_shape_accuracy_floor():
    """Planted-rule forest at UCI-covertype shape (581k x 54, 7 classes,
    BASELINE.json config #3; reference eval RDFUpdate.java:179-205). The
    rule is axis-aligned-representable, so accuracy near the noise
    ceiling measures the TRAINER (histogram splits, bootstrap, feature
    subsets), not concept difficulty."""
    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import build_and_evaluate_rdf

    RandomManager.use_test_seed(1)
    rep = build_and_evaluate_rdf(num_trees=10)
    assert rep.accuracy >= RDF_ACC_FLOOR, (
        f"accuracy {rep.accuracy:.4f} < floor {RDF_ACC_FLOOR} at covertype "
        f"shape (ceiling ~0.914 at 10% label noise)"
    )


@nightly
def test_kmeans_planted_blob_floors():
    """Planted Gaussian blobs at full scale (reference eval strategies
    KMeansUpdate.java:137-173). SSE within 5% of the generating centers
    and a healthy silhouette — the k-means|| reduction bug this gate was
    built against cost 1.7-4.2x SSE by losing whole blobs."""
    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import build_and_evaluate_kmeans

    RandomManager.use_test_seed(1)
    rep = build_and_evaluate_kmeans(
        n_points=1_000_000, dims=20, k=50, iterations=10
    )
    assert rep.sse_ratio <= KMEANS_SSE_RATIO_CEIL, (
        f"SSE {rep.sse_ratio:.3f}x the planted centers "
        f"(> {KMEANS_SSE_RATIO_CEIL}): clusters lost or Lloyd regressed"
    )
    assert rep.silhouette >= KMEANS_SIL_FLOOR


def test_rdf_quality_harness_smoke():
    """Always-on toy-scale smoke of the RDF gate harness."""
    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import build_and_evaluate_rdf

    RandomManager.use_test_seed(1)
    rep = build_and_evaluate_rdf(
        n_examples=8_000, num_trees=4, max_depth=6, feature_subset="auto"
    )
    # 4 trees x mtry sqrt(54) only partially expresses the 4-feature rule
    # at toy scale (measured 0.52); chance is 1/7 = 0.143, so 0.4 still
    # catches a broken trainer while keeping the always-on smoke cheap
    assert rep.accuracy > 0.40
    assert rep.build_s > 0


def test_kmeans_quality_harness_smoke():
    """Always-on toy-scale smoke of the k-means gate harness — tight
    floors even at toy scale: blob recovery is exact when the init works."""
    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import build_and_evaluate_kmeans

    RandomManager.use_test_seed(1)
    rep = build_and_evaluate_kmeans(n_points=50_000, dims=20, k=12, iterations=8)
    assert rep.sse_ratio <= 1.05
    assert rep.silhouette >= 0.5


# ---- seq next-item gate (PR 10: the fourth packaged app) ----------------
# Planted-successor sessions (ml/quality.py synthesize_sessions): the
# walk follows a hidden permutation with p=0.85, so ~0.85 is the
# achievable ceiling and chance is k/V. Calibrated 2026-08-03 on this
# host: 0.819 at the full gate shape (2000 items, 3000 sessions, 12
# epochs, 27 s CPU), 0.885 at toy shape — a broken windowing, a
# mis-gathered embedding table, or a GRU cell regression lands near
# chance (0.005), far below the floor.

SEQ_HIT_RATE_FLOOR = 0.65


@nightly
def test_seq_next_item_hit_rate_floor():
    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import build_and_evaluate_seq

    RandomManager.use_test_seed(1)
    rep = build_and_evaluate_seq()
    assert rep.hit_rate >= SEQ_HIT_RATE_FLOOR, (
        f"hit-rate@{rep.k} {rep.hit_rate:.4f} < floor {SEQ_HIT_RATE_FLOOR} "
        f"(ceiling ~0.85 at follow_p=0.85, chance {rep.chance:.4f})"
    )


def test_seq_quality_harness_smoke():
    """Always-on toy-scale smoke of the seq gate harness (the same code
    path the nightly gate runs)."""
    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import build_and_evaluate_seq

    RandomManager.use_test_seed(1)
    rep = build_and_evaluate_seq(
        n_items=200, n_sessions=300, session_len=8, dim=16, epochs=6
    )
    assert rep.hit_rate > 0.5, (
        f"toy hit-rate@{rep.k} {rep.hit_rate:.4f} near chance "
        f"({rep.chance:.3f}) — windowing or trainer regressed"
    )
    assert rep.examples > 0 and rep.build_s > 0


# ---- serving score-mode recall gate (PR 8) ------------------------------
# The quantized (int8 + exact rescore) and approx (partial-reduce) score
# modes must hold recall@10 >= 0.95 against the exact top-k on the
# standing corpus — speed can never silently buy wrong answers. Tier-1
# (always on): the gate is CPU-cheap, and the CPU run regression-guards
# the quantized claim everywhere even where approx_max_k computes exactly.


def test_score_mode_recall_gate():
    from oryx_tpu.ml.quality import (
        MIN_SCORE_MODE_RECALL,
        evaluate_score_mode_recall,
    )

    rep = evaluate_score_mode_recall(n_items=40_000, n_queries=128)
    assert rep.min_recall == MIN_SCORE_MODE_RECALL == 0.95
    assert rep.recall_quantized >= rep.min_recall, (
        f"quantized recall@{rep.k} {rep.recall_quantized:.4f} below the "
        f"{rep.min_recall} gate — int8 selection + exact rescore regressed"
    )
    assert rep.recall_approx >= rep.min_recall, (
        f"approx recall@{rep.k} {rep.recall_approx:.4f} below the "
        f"{rep.min_recall} gate"
    )
    assert rep.green


@nightly
def test_score_mode_recall_gate_full_corpus():
    """The nightly-scale corpus (the same configuration
    tools/quality_nightly.py records in the QUALITY artifact)."""
    from oryx_tpu.ml.quality import evaluate_score_mode_recall

    rep = evaluate_score_mode_recall()
    assert rep.green, (
        f"score-mode recall gate RED: quantized {rep.recall_quantized:.4f} "
        f"approx {rep.recall_approx:.4f} (floor {rep.min_recall})"
    )
