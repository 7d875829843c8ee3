"""Shared build-and-evaluate harnesses: the nightly quality run
(tools/quality_nightly.py) and the tier-1 quality gates
(tests/test_quality_gate.py) run the SAME code, so a silent quality
regression in any trainer fails both.

- ALS: the bf16 singularity guard (ops/als.py _half_step jitter retry)
  cannot silently regress between runs. Measures what
  BASELINE.json's north star asks for: end-to-end build wall-clock at a
  given interaction scale plus held-out mean-per-user AUC — with NaN
  factor rows surfaced as a first-class diagnostic.
- RDF: planted-rule synthetic at covertype shape with a held-out
  accuracy floor (reference eval: RDFUpdate.java:179-205).
- k-means: planted Gaussian blobs; SSE against the true generating
  centers plus silhouette (reference eval strategies:
  KMeansUpdate.java:137-173 and the four metric classes).
- Serving recall gate: the quantized (int8 + exact rescore) and approx
  (partial-reduce) score modes are measured for recall@k against the
  exact top-k on a standing synthetic corpus; either mode below
  MIN_SCORE_MODE_RECALL fails the nightly run and the gate — speed can never
  silently buy wrong answers.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

# The recall@k floor quantized/approx serving must hold against exact
# top-k (the serve path's acceptance bar; enforced by the tier-1 gate in
# tests/test_quality_gate.py and the nightly QUALITY artifact).
MIN_SCORE_MODE_RECALL = 0.95


@dataclass
class BuildReport:
    build_s: float
    agg_s: float
    auc: float
    nan_rows: int
    interactions: int
    timings: dict = field(default_factory=dict)


def build_and_evaluate(
    n_users: int,
    n_items: int,
    nnz: int,
    features: int = 50,
    iterations: int = 10,
    lam: float = 0.01,
    alpha: float = 1.0,
    compute_dtype: str = "bfloat16",
    seed: int = 7,
    holdout_p: float = 0.02,
    sample_users: int = 2000,
) -> BuildReport:
    """Synthesize (oryx_tpu/ml/synth.py), train, and evaluate one ALS
    build. compute_dtype="bfloat16" is the MXU-native default — quality-
    neutral on this generator (AUC 0.947 bf16 vs 0.939 f32 at 1M scale),
    and the held-out AUC keeps that claim measured on every run."""
    from oryx_tpu.ml.evaluate import auc_mean_per_user
    from oryx_tpu.ml.synth import synthesize_interactions
    from oryx_tpu.ops.als import aggregate_interactions, train_als

    # offset the eval stream from the data stream: same-seed generators
    # share the underlying bitstream, which would correlate the holdout
    # mask with the generator's user-activity draws
    rng = np.random.default_rng(seed + 1_000_003)
    users, items, values = synthesize_interactions(
        n_users, n_items, nnz, seed=seed
    )
    test_mask = rng.random(nnz) < holdout_p
    tr = ~test_mask

    t0 = time.perf_counter()
    data = aggregate_interactions(users[tr], items[tr], values[tr], implicit=True)
    agg_s = time.perf_counter() - t0
    timings: dict = {}
    model = train_als(
        data,
        features=features,
        lam=lam,
        alpha=alpha,
        iterations=iterations,
        implicit=True,
        compute_dtype=compute_dtype,
        timings=timings,
    )
    build_s = time.perf_counter() - t0

    x_np = np.asarray(model.x, dtype=np.float32)
    y_np = np.asarray(model.y, dtype=np.float32)
    nan_rows = int(
        np.isnan(x_np).any(axis=1).sum() + np.isnan(y_np).any(axis=1).sum()
    )

    # AUC on a user sample (a full per-user python loop would dominate
    # the wall-clock; 2000 users gives a +/-0.005 CI on the mean)
    uid_to_row = {u: j for j, u in enumerate(model.user_ids)}
    iid_to_row = {i: j for j, i in enumerate(model.item_ids)}
    tu_all, ti_all = users[test_mask], items[test_mask]
    known: dict[int, set[int]] = {}
    tu, ti = [], []
    sample = set(
        rng.choice(
            np.unique(tu_all),
            size=min(sample_users, len(np.unique(tu_all))),
            replace=False,
        ).tolist()
    )
    for u, i in zip(tu_all, ti_all):
        if u not in sample:
            continue
        ur, ir = uid_to_row.get(str(u)), iid_to_row.get(str(i))
        if ur is None or ir is None:
            continue
        tu.append(ur)
        ti.append(ir)
    # known (training) items for the sampled users, excluded as negatives
    smp = np.isin(users, np.fromiter(sample, dtype=np.int64)) & tr
    for u, i in zip(users[smp], items[smp]):
        ur, ir = uid_to_row.get(str(u)), iid_to_row.get(str(i))
        if ur is not None and ir is not None:
            known.setdefault(ur, set()).add(ir)
    auc = auc_mean_per_user(
        model.x,
        model.y,
        np.asarray(tu, dtype=np.int64),
        np.asarray(ti, dtype=np.int64),
        known,
    )
    return BuildReport(
        build_s=build_s,
        agg_s=agg_s,
        auc=float(auc),
        nan_rows=nan_rows,
        interactions=nnz,
        timings=timings,
    )


@dataclass
class RecallReport:
    """Measured recall@k of the approximate serving score modes against
    exact top-k on the standing corpus. green = both modes at/above the
    floor."""

    recall_quantized: float
    recall_approx: float
    k: int
    n_queries: int
    n_items: int
    features: int
    min_recall: float
    approx_recall_target: float
    eval_s: float

    @property
    def green(self) -> bool:
        return (
            self.recall_quantized >= self.min_recall
            and self.recall_approx >= self.min_recall
        )


def mean_recall_at_k(got_idx: np.ndarray, exact_idx: np.ndarray, k: int) -> float:
    """Mean per-query |top-k ∩ exact top-k| / k — the ONE recall
    definition the gate and the nightly run share, so the numbers they
    report can never drift in meaning."""
    return float(
        np.mean([
            len(set(map(int, g[:k])) & set(map(int, e[:k]))) / k
            for g, e in zip(got_idx, exact_idx)
        ])
    )


def evaluate_score_mode_recall(
    n_items: int = 100_000,
    features: int = 50,
    k: int = 10,
    n_queries: int = 256,
    seed: int = 23,
    approx_recall_target: float = 0.95,
    min_recall: float = MIN_SCORE_MODE_RECALL,
    overfetch: int | None = None,
) -> RecallReport:
    """Measure recall@k of the quantized and approx serving modes against
    the exact top-k on a standing synthetic corpus (deterministic seed —
    the same corpus every run, so the number is a gate, not a dice roll).

    Each mode is evaluated the way serving actually runs it
    (apps/als/serving.py): the device kernel selects an over-fetched
    candidate set, the candidates are re-ranked EXACTLY in f32, and the
    top-k of that re-rank is what a client sees. So this measures the
    mode's end answer, not the raw kernel's. The overfetch defaults to
    k + 8 — the rescore set a NO-EXCLUSION request actually gets back
    from the batcher (it slices the dispatch's k-bucket down to the
    request's own k = how_many + |exclude| + 8 before the rescore), so
    the gate is never more forgiving than production's weakest case.

    On CPU hosts jax.lax.approx_max_k computes exactly, so the approx row
    gates the plumbing there and the real recall target on TPU.
    """
    import jax.numpy as jnp

    from oryx_tpu.ops.als import (
        topk_dot_batch_approx, topk_dot_batch_quant_xla, topk_dot_batch_xla,
    )
    from oryx_tpu.ops.transfer import quantize_rows_int8

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    # factor-model-shaped corpus: low-rank structure plus noise, like a
    # trained Y — pure iid gaussians under-stress quantization (scores
    # concentrate), planted structure gives realistic near-ties
    basis = rng.standard_normal((max(8, features // 4), features))
    y = (
        rng.standard_normal((n_items, basis.shape[0])) @ basis
        + 0.5 * rng.standard_normal((n_items, features))
    ).astype(np.float32)
    xs = rng.standard_normal((n_queries, features)).astype(np.float32)

    # the serving over-fetch: exactly the candidate set a no-exclusion
    # request's exact rescore sees (serving requests k = how_many + 8;
    # the batcher returns that many rows of its k-bucket dispatch)
    if overfetch is None:
        overfetch = min(n_items, k + 8)

    xs_j, y_j = jnp.asarray(xs), jnp.asarray(y)
    _, exact_idx = topk_dot_batch_xla(xs_j, y_j, k=k)
    exact_idx = np.asarray(exact_idx)

    def rescored_topk(cand_idx: np.ndarray) -> np.ndarray:
        """Exact f32 re-rank of each query's candidate rows (the serve
        path's _rerank_exact), then top-k."""
        out = np.empty((n_queries, k), dtype=np.int64)
        for qi in range(n_queries):
            rows = cand_idx[qi]
            scores = y[rows] @ xs[qi]
            order = np.argsort(-scores, kind="stable")[:k]
            out[qi] = rows[order]
        return out

    # quantized: int8 + per-row scale selection, exact rescore
    q, scale = quantize_rows_int8(y)
    _, q_idx = topk_dot_batch_quant_xla(
        xs_j, jnp.asarray(q), jnp.asarray(scale), k=overfetch
    )
    recall_q = mean_recall_at_k(rescored_topk(np.asarray(q_idx)), exact_idx, k)

    # approx: the REAL partial-reduce serving kernel (ops/als.py) at the
    # recall target, exact rescore of whatever it returns
    _, a_idx = topk_dot_batch_approx(
        xs_j, y_j, k=min(overfetch, n_items), recall=approx_recall_target
    )
    recall_a = mean_recall_at_k(rescored_topk(np.asarray(a_idx)), exact_idx, k)

    return RecallReport(
        recall_quantized=recall_q,
        recall_approx=recall_a,
        k=k,
        n_queries=n_queries,
        n_items=n_items,
        features=features,
        min_recall=min_recall,
        approx_recall_target=approx_recall_target,
        eval_s=time.perf_counter() - t0,
    )


@dataclass
class SeqReport:
    """Planted-transition next-item gate: sessions walk a hidden
    successor structure, the GRU must recover it. green = hit-rate@k on
    held-out final transitions at/above the floor."""

    build_s: float
    window_s: float          # sessionize+window ingest wall-clock
    hit_rate: float          # hit-rate@k on held-out next items
    k: int
    examples: int            # training examples after windowing
    n_items: int
    n_sessions: int
    epochs_run: int

    @property
    def chance(self) -> float:
        return self.k / max(1, self.n_items)


def synthesize_sessions(
    n_items: int,
    n_sessions: int,
    session_len: int,
    seed: int = 11,
    follow_p: float = 0.85,
) -> list[np.ndarray]:
    """Planted-successor sessions: each item i has a hidden successor
    succ(i) = (i*7 + 3) mod V (a permutation when gcd(7, V) = 1); a
    session walks succ with probability follow_p, else jumps uniformly.
    A healthy next-item model must put succ(current) high; chance is
    k/V. Returns one int64 item-row array per session."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_sessions):
        it = int(rng.integers(0, n_items))
        seq = [it]
        for _ in range(session_len - 1):
            if rng.random() < follow_p:
                it = (it * 7 + 3) % n_items
            else:
                it = int(rng.integers(0, n_items))
            seq.append(it)
        out.append(np.asarray(seq, dtype=np.int64))
    return out


def build_and_evaluate_seq(
    n_items: int = 2000,
    n_sessions: int = 3000,
    session_len: int = 10,
    dim: int = 32,
    window: int = 8,
    epochs: int = 12,
    lr: float = 0.5,
    k: int = 10,
    holdout_sessions: float = 0.2,
    seed: int = 11,
) -> SeqReport:
    """Synthesize planted-transition sessions, window them (the SAME
    windowing the app's ingest uses, apps/seq/common.py), train the GRU
    (ops/seq.py) and measure hit-rate@k on each held-out session's FINAL
    transition — the serving question ("what comes next?") asked about
    the future, exactly the batch tier's temporal holdout shape."""
    import jax

    from oryx_tpu.apps.seq.common import windowed_examples
    from oryx_tpu.ops.seq import next_item_hit_rate, train_gru

    sessions = synthesize_sessions(n_items, n_sessions, session_len, seed=seed)
    rng = np.random.default_rng(seed + 1_000_003)
    eval_mask = rng.random(len(sessions)) < holdout_sessions
    item_ids = [str(i) for i in range(n_items)]
    item_to_row = {s: i for i, s in enumerate(item_ids)}

    t0 = time.perf_counter()
    train_sessions = {
        f"s{j}": [str(i) for i in (s[:-1] if eval_mask[j] else s)]
        for j, s in enumerate(sessions)
    }
    contexts, mask, targets = windowed_examples(
        train_sessions, item_to_row, window
    )
    window_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    model, epochs_run = train_gru(
        contexts, mask, targets,
        n_items=n_items, dim=dim, item_ids=item_ids,
        epochs=epochs, lr=lr,
        seed_key=jax.random.PRNGKey(seed),
    )
    build_s = time.perf_counter() - t1

    # held-out final transitions: context = the session minus its last
    # event, target = the last event (padded by the app's own helper)
    from oryx_tpu.apps.seq.common import pad_examples

    ev_rows = [j for j in range(len(sessions)) if eval_mask[j]]
    ctx, cmask, tgt = pad_examples(
        [sessions[j][:-1][-window:] for j in ev_rows],
        [int(sessions[j][-1]) for j in ev_rows],
        window,
    )
    hit = next_item_hit_rate(model.e, model.params, ctx, cmask, tgt, k=k)
    return SeqReport(
        build_s=build_s,
        window_s=window_s,
        hit_rate=float(hit),
        k=k,
        examples=int(targets.shape[0]),
        n_items=n_items,
        n_sessions=n_sessions,
        epochs_run=epochs_run,
    )


@dataclass
class RDFReport:
    build_s: float
    accuracy: float
    examples: int
    trees: int
    noise_rate: float
    n_classes: int

    @property
    def accuracy_ceiling(self) -> float:
        """Achievable held-out accuracy: flipped labels agree with the
        rule by chance 1/n_classes of the time. Lives here, next to the
        label-flip code it must match."""
        return 1.0 - self.noise_rate * (1.0 - 1.0 / self.n_classes)


def build_and_evaluate_rdf(
    n_examples: int = 581_012,
    n_features: int = 54,
    n_classes: int = 7,
    num_trees: int = 20,
    max_depth: int = 10,
    noise_rate: float = 0.1,
    holdout_p: float = 0.1,
    seed: int = 13,
    feature_subset: str | int = 14,
) -> RDFReport:
    """Planted-rule synthetic at UCI-covertype shape (581k x 54, 7
    classes — BASELINE.json config #3): the label is a deterministic
    rule over a handful of feature thresholds with `noise_rate` labels
    flipped, so the achievable held-out accuracy is ~(1 - noise_rate)
    and a healthy forest must land near it. Defaults mirror the
    reference's covertype example config (oryx.rdf.num-trees etc.).

    The rule mixes axis-aligned thresholds (what trees split on) across
    several features with unequal class difficulty — deep enough that a
    stump can't ace it, learnable enough that a regressed trainer
    (broken histogram splits, bad bootstrap, mis-grown depth) falls far
    below the floor.

    feature_subset defaults to 14 (~P/4), not "auto" (sqrt(54)=7): the
    planted rule spans 4 of 54 features, and sqrt-sized per-node subsets
    rarely offer a relevant feature near the root. Round-5 sweep at 100k
    examples: auto 0.894, 14 0.8986, 27 0.8985, depth 12 and 20 trees
    and 64 bins each neutral-or-worse — the subset size is the one knob
    that moved the number.
    """
    from oryx_tpu.ops.rdf import bin_dataset, grow_forest, predict_class_probs

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n_examples, n_features)).astype(np.float32)
    # planted rule over 4 axis-aligned thresholds — exactly representable
    # by depth>=4 trees, so the held-out ceiling is (1 - noise) plus the
    # chance agreement of flipped labels, and any shortfall measures the
    # TRAINER (histogram splits, bootstrap, subset sampling), not an
    # inexpressible concept
    r1 = (x[:, 0] > 0).astype(np.int64)
    r2 = (x[:, 7] > 0.5).astype(np.int64)
    r3 = (x[:, 21] > -0.5).astype(np.int64)
    r4 = (x[:, 40] > 0.3).astype(np.int64)
    y_true = (r1 * 4 + r2 * 2 + r3 + r4) % n_classes
    flip = rng.random(n_examples) < noise_rate
    y = np.where(
        flip, rng.integers(0, n_classes, n_examples), y_true
    ).astype(np.int32)

    test = rng.random(n_examples) < holdout_p
    tr = ~test

    t0 = time.perf_counter()
    binned = bin_dataset(
        x[tr],
        is_categorical=np.zeros(n_features, dtype=bool),
        category_counts=np.zeros(n_features, dtype=np.int32),
        max_split_candidates=32,
    )
    forest = grow_forest(
        binned, y[tr], num_trees=num_trees, max_depth=max_depth,
        impurity="entropy", n_classes=n_classes,
        feature_subset=feature_subset,
    )
    build_s = time.perf_counter() - t0

    # bin the held-out rows with the TRAINING edges (ops/rdf.py
    # bin_column — the same path serving uses, apps/rdf/common.py)
    from oryx_tpu.ops.rdf import bin_column

    xt = x[test]
    test_binned = np.empty_like(xt, dtype=np.int32)
    for j in range(n_features):
        test_binned[:, j] = bin_column(
            xt[:, j], binned.edges[j], int(binned.n_bins[j])
        )
    probs = predict_class_probs(forest, test_binned)
    acc = float((np.argmax(probs, axis=1) == y[test]).mean())
    return RDFReport(
        build_s=build_s,
        accuracy=acc,
        examples=n_examples,
        trees=num_trees,
        noise_rate=noise_rate,
        n_classes=n_classes,
    )


@dataclass
class KMeansReport:
    build_s: float
    sse_ratio: float  # model SSE / planted-centers SSE (1.0 = perfect)
    silhouette: float
    points: int
    k: int


def build_and_evaluate_kmeans(
    n_points: int = 1_000_000,
    dims: int = 20,
    k: int = 50,
    iterations: int = 10,
    spread: float = 5.0,
    seed: int = 19,
) -> KMeansReport:
    """Planted Gaussian blobs: k true centers at `spread` separation,
    unit-variance clusters. A healthy k-means|| + Lloyd's run recovers
    near the generating structure: SSE within a small factor of the
    planted-centers SSE, positive silhouette. A regressed init (bad
    k-means|| weighting) or broken Lloyd's update inflates SSE or
    collapses clusters and fails the floors."""
    from oryx_tpu.ops.kmeans import (
        silhouette_coefficient,
        sum_squared_error,
        train_kmeans,
    )

    rng = np.random.default_rng(seed)
    centers_true = (rng.standard_normal((k, dims)) * spread).astype(np.float32)
    pts = (
        centers_true[rng.integers(0, k, n_points)]
        + rng.standard_normal((n_points, dims))
    ).astype(np.float32)

    t0 = time.perf_counter()
    model = train_kmeans(pts, k=k, iterations=iterations)
    build_s = time.perf_counter() - t0

    sse_model = sum_squared_error(pts, model.centers)
    sse_true = sum_squared_error(pts, centers_true)
    sil = silhouette_coefficient(pts, model.centers)
    return KMeansReport(
        build_s=build_s,
        sse_ratio=float(sse_model / sse_true),
        silhouette=float(sil),
        points=n_points,
        k=k,
    )
