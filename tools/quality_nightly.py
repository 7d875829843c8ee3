#!/usr/bin/env python3
"""Run the three nightly quality gates and write a committed artifact.

Round-4 verdict #7: the env-gated nightly gates only ran when someone
remembered to run them, and their calibration evidence lived in
docstrings. This runner executes the SAME harness configurations as
tests/test_quality_gate.py's ORYX_NIGHTLY gates — the 25M-shape bf16 ALS
NaN-guard gate, the covertype-shape RDF accuracy floor, and the planted-
blob k-means floors — and records the numbers with timestamps in
QUALITY_r{N}.json so quality claims carry the same provenance discipline
as perf claims.

    python tools/quality_nightly.py [round_number]

Exit 0 only if every gate is green.
"""

from __future__ import annotations

import json
import os
import sys
import time
from datetime import datetime, timezone
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _live_sampler_fields(
    n_items: int = 20_000, features: int = 50, n_queries: int = 64
) -> dict:
    """Drive the RUNTIME shadow-rescore sampler (common/qualitystats.py)
    through a real quantized ALSServingModel — the same request path
    production samples — and report its windowed recall. This is what
    makes the nightly artifact and the live oryx_live_recall_at_k gauge
    one vocabulary: both numbers come out of the identical sampler code
    on the identical serve pipeline."""
    import numpy as np

    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.qualitystats import QualityStats
    from oryx_tpu.apps.als.serving import ALSServingModel
    from oryx_tpu.apps.als.state import ALSState

    cfg = load_config(overlay={
        "oryx.monitoring.quality.sample-rate": 1.0,
        "oryx.monitoring.quality.window-sec": 600,
        "oryx.monitoring.quality.max-queue": max(256, n_queries),
    })
    rng = np.random.default_rng(29)
    state = ALSState(features, implicit=True)
    ids = [f"i{j}" for j in range(n_items)]
    state.y.bulk_set(ids, rng.standard_normal((n_items, features)).astype(np.float32))
    state.set_expected([], ids)
    model = ALSServingModel(state, score_mode="quantized")
    qs = QualityStats()
    qs.configure(cfg)
    # route this model's shadow samples into the PRIVATE tracker so the
    # nightly number never mixes with the process-global window
    import oryx_tpu.common.qualitystats as _qmod

    prev = _qmod._default
    _qmod._default = qs
    try:
        for _ in range(n_queries):
            q = rng.standard_normal(features).astype(np.float32)
            model.top_n(q, 10)
        qs.flush(60)
    finally:
        _qmod._default = prev
        model.close()
        qs.close()
    live = qs.live_recall()
    return {
        "live_recall_at_10": round(live, 4) if live == live else None,
        "live_recall_samples": qs.samples_processed(),
    }


def main() -> int:
    round_no = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    out_path = Path(__file__).resolve().parent.parent / (
        f"QUALITY_r{round_no:02d}.json" if round_no else "QUALITY.json"
    )

    from oryx_tpu.common.rng import RandomManager
    from oryx_tpu.ml.quality import MIN_SCORE_MODE_RECALL
    from tests.test_quality_gate import (
        AUC_FLOOR,
        KMEANS_SIL_FLOOR,
        KMEANS_SSE_RATIO_CEIL,
        ML25M_SHAPE,
        RDF_ACC_FLOOR,
        SEQ_HIT_RATE_FLOOR,
    )

    import jax

    doc: dict = {
        "started_at": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "platform": jax.devices()[0].platform,
        "floors": {
            "als_auc": AUC_FLOOR,
            "als_nan_rows": 0,
            "rdf_accuracy": RDF_ACC_FLOOR,
            "kmeans_sse_ratio_max": KMEANS_SSE_RATIO_CEIL,
            "kmeans_silhouette": KMEANS_SIL_FLOOR,
            "score_mode_recall_at_10": MIN_SCORE_MODE_RECALL,
            "seq_hit_rate_at_10": SEQ_HIT_RATE_FLOOR,
        },
        "gates": {},
    }
    ok = True

    def record(name: str, fields: dict, green: bool) -> None:
        nonlocal ok
        ok = ok and green
        fields["green"] = green
        fields["finished_at"] = datetime.now(timezone.utc).isoformat(
            timespec="seconds"
        )
        doc["gates"][name] = fields
        out_path.write_text(json.dumps(doc, indent=1))
        print(f"{name}: {'GREEN' if green else 'RED'} {fields}", flush=True)

    # ---- gate 1: 25M-shape bf16 ALS NaN guard + AUC floor ---------------
    from oryx_tpu.ml.quality import (
        build_and_evaluate,
        build_and_evaluate_kmeans,
        build_and_evaluate_rdf,
        evaluate_score_mode_recall,
    )

    t0 = time.perf_counter()
    rep = build_and_evaluate(
        **ML25M_SHAPE, features=50, iterations=3,
        compute_dtype="bfloat16", seed=7,
    )
    record(
        "als_25m_bf16",
        {
            "auc": round(rep.auc, 4),
            "nan_rows": rep.nan_rows,
            "interactions": rep.interactions,
            "build_s": round(rep.build_s, 1),
            "wall_s": round(time.perf_counter() - t0, 1),
        },
        rep.nan_rows == 0 and rep.auc >= AUC_FLOOR,
    )

    # ---- gate 2: covertype-shape RDF accuracy floor ---------------------
    RandomManager.use_test_seed(1)
    t0 = time.perf_counter()
    rdf = build_and_evaluate_rdf(num_trees=10)
    record(
        "rdf_covertype_shape",
        {
            "accuracy": round(rdf.accuracy, 4),
            "accuracy_ceiling": round(rdf.accuracy_ceiling, 4),
            "examples": rdf.examples,
            "trees": rdf.trees,
            "build_s": round(rdf.build_s, 1),
            "wall_s": round(time.perf_counter() - t0, 1),
        },
        rdf.accuracy >= RDF_ACC_FLOOR,
    )

    # ---- gate 3: planted-blob k-means floors ----------------------------
    RandomManager.use_test_seed(1)
    t0 = time.perf_counter()
    km = build_and_evaluate_kmeans(
        n_points=1_000_000, dims=20, k=50, iterations=10
    )
    record(
        "kmeans_planted_blobs",
        {
            "sse_ratio": round(km.sse_ratio, 4),
            "silhouette": round(km.silhouette, 3),
            "points": km.points,
            "k": km.k,
            "build_s": round(km.build_s, 1),
            "wall_s": round(time.perf_counter() - t0, 1),
        },
        km.sse_ratio <= KMEANS_SSE_RATIO_CEIL
        and km.silhouette >= KMEANS_SIL_FLOOR,
    )

    # ---- gate 4: serving score-mode recall floor ------------------------
    # speed modes can never silently buy wrong answers: quantized (int8 +
    # exact rescore) and approx (partial reduce) must hold recall@10
    # against the exact top-k on the standing corpus
    RandomManager.use_test_seed(1)
    t0 = time.perf_counter()
    rr = evaluate_score_mode_recall()
    live = _live_sampler_fields()
    record(
        "score_mode_recall",
        {
            # _rescored suffix: these measure the full serve pipeline
            # (overfetch + exact f32 re-rank), not the RAW kernel
            # selection at k
            "approx_recall_at_10_rescored": round(rr.recall_approx, 4),
            "quantized_recall_at_10_rescored": round(rr.recall_quantized, 4),
            "k": rr.k,
            "n_items": rr.n_items,
            "n_queries": rr.n_queries,
            "approx_recall_target": rr.approx_recall_target,
            # the RUNTIME sampler's numbers on the same class of corpus:
            # nightly and production share one recall vocabulary
            # (oryx_live_recall_at_k == live_recall_at_10 here), so a
            # nightly regression and a live pager fire on the same
            # definition
            **live,
            "wall_s": round(time.perf_counter() - t0, 1),
        },
        rr.green,
    )

    # ---- gate 5: seq next-item hit-rate floor ---------------------------
    # the fourth packaged app is recall-gated like the ALS score modes:
    # planted-successor sessions, hit-rate@10 on held-out final
    # transitions (ceiling ~0.85 at follow_p=0.85, chance k/V)
    RandomManager.use_test_seed(1)
    t0 = time.perf_counter()
    from oryx_tpu.ml.quality import build_and_evaluate_seq

    sq = build_and_evaluate_seq()
    record(
        "seq_next_item",
        {
            "hit_rate_at_10": round(sq.hit_rate, 4),
            "chance": round(sq.chance, 4),
            "examples": sq.examples,
            "n_items": sq.n_items,
            "n_sessions": sq.n_sessions,
            "epochs_run": sq.epochs_run,
            "build_s": round(sq.build_s, 1),
            "wall_s": round(time.perf_counter() - t0, 1),
        },
        sq.hit_rate >= SEQ_HIT_RATE_FLOOR,
    )

    doc["all_green"] = ok
    out_path.write_text(json.dumps(doc, indent=1))
    print(f"wrote {out_path} all_green={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    os.environ.setdefault("ORYX_NIGHTLY", "1")
    raise SystemExit(main())
