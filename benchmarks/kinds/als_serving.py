"""Configuration kind `als-serving`: ALS `/recommend` through ServingLayer
over HTTP, one process holding the chip, load from generator processes.

The model is synthetic (the reference's LoadTestALSModelFactory analogue):
standard-normal factors from --seed set straight into an ALSState, no
update-topic replay. The server is the program as it ships: default
reference.conf plus what a read-only server on mem:// brokers with port 0
needs. No batcher, bucket, pipeline or score-mode key is set here.

Also here, because later PRs may not change them: the plain float32
reference and the comparison that decides `correct`, and the function that
computes the top-k scan's operations and bytes.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from benchmarks import latency, loadgen, timeline, xplane

# Agreement of the HTTP top-N with the float32 reference, per sampled user
# (chip_smoke.py's written tolerance). Serving scans candidates in bf16 and
# re-ranks them in f32 on the host (_rerank_exact), so every served SCORE is
# an f32 dot of the same factors (rtol 1e-4 covers accumulation order); an
# ITEM may differ from the reference's only where the bf16 scan could not
# tell it from the last one (within 2^-6 * max|score|); and at least 9 of 10
# are the reference's. Computing in a lower precision than stated fails it.
SCORE_RTOL = 1e-4
BF16_SLACK = 2.0 ** -6
MIN_OVERLAP_SHARE = 0.9
CHECK_USERS = 16
REFERENCE_BLOCK_ROWS = 1_000_000
WARM_MIN_S = 5.0
WARM_CYCLES = 5
TRACE_MAX_S = 12.0


def topk_work(rows: float, items: int, features: int, k: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) the ALGORITHM needs for one top-k dispatch: `rows`
    real queries scored against `items` real item rows of `features`
    published features, the item view read once at `itemsize` bytes, the
    queries read and k (score, index) pairs written per row. Padding rows,
    padded lanes and re-reads are the implementation's, not the algorithm's."""
    flops = 2.0 * rows * items * features
    moved = items * features * itemsize + rows * features * itemsize + rows * k * 8.0
    return flops, float(moved)


def reference_scores(xs: np.ndarray, y_host: np.ndarray) -> np.ndarray:
    """Plain jax.numpy float32 scores [len(xs), len(y_host)] at `highest`
    precision, in row blocks (the whole matrix need not fit beside the view)."""
    import jax
    import jax.numpy as jnp

    xd = jnp.asarray(xs, dtype=jnp.float32)
    out = np.empty((len(xs), len(y_host)), dtype=np.float32)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(y_host), REFERENCE_BLOCK_ROWS):
            block = jnp.asarray(y_host[lo:lo + REFERENCE_BLOCK_ROWS], dtype=jnp.float32)
            out[:, lo:lo + block.shape[0]] = np.asarray(jnp.dot(xd, block.T))
    return out


def compare(answer: list, scores: np.ndarray, known: np.ndarray, how_many: int) -> dict:
    """One user's served [[item, score], ...] against the reference scores of
    that user: {"fault": what is wrong with its form or None, "score_rel":
    the worst served score's distance from the reference's, "overlap": how
    many of the reference's items were served, "gap_over_slack": how far the
    worst served item lies under the reference's last, in slacks}. The
    numbers are None where the form is wrong."""
    s = scores.copy()
    s[known] = -np.inf
    ref_top = np.argsort(-s, kind="stable")[:how_many]
    last = s[ref_top[-1]]
    slack = BF16_SLACK * float(np.max(np.abs(scores)))
    rows = [int(item[1:]) for item, _ in answer]
    got = np.asarray([score for _, score in answer], dtype=np.float64)
    out = {"fault": None, "score_rel": None, "overlap": None, "gap_over_slack": None}
    if len(rows) != how_many:
        out["fault"] = f"{len(rows)} items served, not {how_many}"
    elif set(rows) & set(known.tolist()):
        out["fault"] = "a known item was served"
    elif np.any(np.diff(got) > 0):
        out["fault"] = "scores not descending"
    else:
        out["score_rel"] = float(
            np.max(np.abs(got - scores[rows]) / np.maximum(np.abs(scores[rows]), 1e-6))
        )
        out["overlap"] = len(set(rows) & set(ref_top.tolist()))
        out["gap_over_slack"] = float(max(0.0, last - min(s[r] for r in rows)) / slack)
    return out


def verdict(c: dict, how_many: int) -> str | None:
    """None when the numbers of one compare() are within the tolerances;
    else what differs."""
    if c["fault"]:
        return c["fault"]
    if c["score_rel"] > SCORE_RTOL:
        return f"scores differ from the f32 reference by rel {c['score_rel']:.2e}"
    if c["overlap"] < math.ceil(MIN_OVERLAP_SHARE * how_many):
        return f"only {c['overlap']}/{how_many} of the reference's items served"
    if c["gap_over_slack"] > 1.0:
        return (
            f"an item served scores {c['gap_over_slack']:.2f} slacks under the "
            f"reference's last (slack {BF16_SLACK:g} x max|score|)"
        )
    return None


def agree(answer: list, scores: np.ndarray, known: np.ndarray, how_many: int) -> str | None:
    """None when one user's served [[item, score], ...] agrees with the
    reference scores of that user; else what differs."""
    return verdict(compare(answer, scores, known, how_many), how_many)


def draw_factors(seed: int, stream: int, rows: int, features: int) -> np.ndarray:
    """Standard-normal float32 [rows, features] from the seed, filled in
    row blocks on threads (numpy's generators release the GIL)."""
    out = np.empty((rows, features), dtype=np.float32)
    bounds = np.linspace(0, rows, min(12, rows) + 1, dtype=np.int64)

    def fill(j: int) -> None:
        rng = np.random.default_rng([int(seed), stream, j])
        rng.standard_normal(out=out[bounds[j]:bounds[j + 1]], dtype=np.float32)

    with ThreadPoolExecutor(len(bounds) - 1) as pool:
        list(pool.map(fill, range(len(bounds) - 1)))
    return out


def _get(url: str, timeout: float = 600.0) -> tuple[int, bytes]:
    req = urllib.request.Request(url, headers={"Accept": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def scrape(base: str) -> dict[str, float]:
    """GET /metrics -> {series: value}."""
    status, text = _get(f"{base}/metrics")
    if status != 200:
        raise RuntimeError(f"/metrics -> {status}")
    out = {}
    for line in text.decode().splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            out[series] = float(value)
    return out


def queued_ahead_share(records: list) -> float | None:
    """Share of the dispatches that were launched while the one before them
    was still on the device (`t_start` before the previous record's
    `t_start + wall_s`): the depth-1 pipeline's loaded state, in which a
    request waits for the scan ahead of its own. 1.0 or 0.0 is one state
    throughout; between them the pipeline flipped inside the records."""
    pairs = list(zip(records, records[1:]))
    if not pairs:
        return None
    return sum(1 for a, b in pairs if b.t_start < a.t_start + a.wall_s) / len(pairs)


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


def run(cell: dict, seed: int, seconds: float, trace: bool, t_process: float, info) -> dict:
    """One run of one cell. `cell` = {name, config, traffic, chips, scratch};
    `t_process` is time.time() at process start; `info(**kv)` prints an
    earlier output line."""
    import jax

    from oryx_tpu.apps.als.serving import ALSServingModel, ALSServingModelManager
    from oryx_tpu.apps.als.state import ALSState
    from oryx_tpu.bus.broker import topics
    from oryx_tpu.common.config import load_config
    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.serving.server import ServingLayer

    # the cyclic collector stops every thread of the server for as long as a
    # collection walks the model's id maps: time each one (gc_pause_share)
    collections: list[tuple[float, float]] = []  # (monotonic start, seconds)

    def on_gc(phase: str, _info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            collections.append((now, 0.0))
        else:
            collections[-1] = (collections[-1][0], now - collections[-1][0])

    gc.callbacks.append(on_gc)
    config, traffic = cell["config"], cell["traffic"]
    n_items, features = config["items"], config["features"]
    population = {"items": n_items, "active_users": config["active_users"]}
    how_many = int(traffic["how_many"])

    # -- model: ids and factors vectorised, known items for the active users
    t_build = time.monotonic()
    y_host = draw_factors(seed, 10, n_items, features)
    x_host = draw_factors(seed, 11, config["users"], features)
    known = loadgen.draw_known(seed, population, traffic)
    state = ALSState(features, implicit=bool(config["implicit"]))
    state.y.bulk_set([f"i{j}" for j in range(n_items)], y_host)
    state.x.bulk_set([f"u{j}" for j in range(config["users"])], x_host)
    state.set_expected(state.x.ids(), state.y.ids())
    for u, rows in enumerate(known):
        state.add_known_items(f"u{u}", [f"i{r}" for r in rows.tolist()])
    info(phase="model_built", seconds=time.monotonic() - t_build)

    broker = "mem://bench"
    overlay = {
        "oryx.id": "bench",
        "oryx.input-topic.broker": broker,
        "oryx.update-topic.broker": broker,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.read-only": True,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.als",
        ],
        "oryx.monitoring.flight.dir": str(Path(cell["scratch"]) / "flight"),
    }
    if jax.devices()[0].platform == "tpu":
        # a TPU that fails is an error, never a quiet start on the CPU
        overlay["oryx.compute.platform"] = "tpu"
    if cell["chips"] > 1:
        overlay["oryx.serving.api.sync.shard-count"] = cell["chips"]
    cfg = load_config(overlay=overlay)
    topics.maybe_create(broker, "OryxUpdate", partitions=1)
    manager = ALSServingModelManager(cfg)
    manager.model = ALSServingModel(
        state, sample_rate=manager.als.sample_rate,
        approx_recall=manager.als.approx_recall,
        num_cores=(manager.als.candidate_partitions or None),
        lsh_max_bits_differing=manager.als.lsh_max_bits_differing,
        sync=manager.sync, score_mode=manager.score_mode,
    )
    serving = ServingLayer(cfg, model_manager=manager)
    serving.start()
    base = f"http://127.0.0.1:{serving.port}"
    gen = None
    try:
        # the generator draws its population while the server warms up
        spec = {
            "port": serving.port, "seed": seed, "traffic": traffic,
            "population": population, "seconds": seconds,
        }
        gen = subprocess.Popen(
            [sys.executable, loadgen.__file__, json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            # the generator needs no chip and never imports jax
            env={k: v for k, v in os.environ.items()
                 if k not in ("PYTHONPATH", "JAX_PLATFORMS")},
        )
        # -- warm-up, part 1: one request uploads the view and compiles (or
        # loads) the cell's one shape; a second, alone, times one cycle
        t_prime = time.monotonic()
        for attempt in ("first", "cycle"):
            t_req = time.monotonic()
            status, body = _get(f"{base}{traffic['path'].format(user=0)}")
            if status != 200:
                raise RuntimeError(f"priming request -> {status}: {body[:200]!r}")
            cycle_s = time.monotonic() - t_req
            info(phase=f"prime_{attempt}", seconds=cycle_s)
        warm_s = float(math.ceil(max(WARM_MIN_S, WARM_CYCLES * cycle_s)))

        # -- warm-up, part 2: the cell's own traffic, then the window
        if gen.stdout.readline().strip() != "READY":
            raise RuntimeError("the load generator did not start")
        t0 = time.monotonic() + 0.25
        gen.stdin.write(json.dumps({"t0": t0, "warm_s": warm_s}) + "\n")
        gen.stdin.flush()
        t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
        _sleep_until(t_open)
        setup_s = time.time() - t_process
        before = scrape(base)
        trace_out = timeline_out = None
        if trace:
            trace_dir = Path(cell["scratch"]) / "trace" / cell["name"]  # the cell's own: see xplane.find_xplane
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            _sleep_until(t_open + 0.25)
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            _sleep_until(min(time.monotonic() + TRACE_MAX_S, t_close - 0.5))
            jax.profiler.stop_trace()
            found = xplane.find_xplane(trace_dir)
            if found:
                trace_out = xplane.reduce_trace(found, prefer=timeline.REGION_PREFIX)
                timeline_out = timeline.parse(found)
        _sleep_until(t_close)
        after = scrape(base)
        ring = get_perfstats().records_since(t_open - 1.0)  # the warm-up's last second too
        records = [r for r in ring if t_open <= r.t_start < t_close]
        pauses = [s for t, s in collections if t_open <= t < t_close]
        out, _ = gen.communicate(timeout=seconds + 240)
        result = json.loads(out.strip().splitlines()[-1])
        gen = None

        # -- correctness, outside the timing
        good, attempted, failed = latency.window_latencies(result)
        rng = np.random.default_rng([int(seed), 3])
        users = rng.choice(config["active_users"], size=CHECK_USERS, replace=False)
        scores = reference_scores(x_host[users], y_host)
        path = traffic["path"]
        with ThreadPoolExecutor(CHECK_USERS) as pool:  # together: one dispatch
            answers = list(pool.map(lambda u: _get(f"{base}{path.format(user=u)}"), users.tolist()))
        faults, checks = [], []
        for row, (u, (status, body)) in enumerate(zip(users.tolist(), answers)):
            if status != 200:
                faults.append(f"user u{u}: status {status}")
                continue
            checks.append(compare(json.loads(body), scores[row], known[u], how_many))
            wrong = verdict(checks[-1], how_many)
            if wrong:
                faults.append(f"user u{u}: {wrong}")
        wrong_bodies = sum(
            n for kind, n in result["errors"].items()
            if kind in ("unparsable", "wrong_count", "known_item")
        )
        if wrong_bodies:
            faults.append(f"{wrong_bodies} response(s) with a wrong body: {result['errors']}")
        delta = {s: after[s] - before.get(s, 0.0) for s in after}
        compiles = sum(v for s, v in delta.items() if s.startswith("oryx_xla_compiles_total"))
        if compiles:
            faults.append(f"{compiles:.0f} compile(s) inside the window")
        # the configuration's guarantee: the scan is the exact (bf16) one. The answers
        # alone cannot tell: the f32 re-rank makes an int8 scan's top-10 the same
        not_exact = sum(1 for r in records if r.score_mode != "exact")
        if not_exact:
            faults.append(f"{not_exact} dispatch(es) in the window not in score-mode exact")
        # nor can they tell a window the host's exact top-k served after a device error
        fallbacks = delta.get("oryx_topk_host_fallbacks", 0.0)
        if fallbacks:
            faults.append(f"{fallbacks:.0f} request(s) in the window scored on the host, not by the device scan")
        for f in faults:
            print(f"als_serving: {f}", file=sys.stderr)

        def worst(key, pick):
            read = [c[key] for c in checks if c[key] is not None]
            return pick(read) if read else None

        # every number `correct` rests on: [read, how it is held, limit]
        compared = {
            "users_compared": [len(checks), "==", CHECK_USERS],
            "worst_score_rel": [worst("score_rel", max), "<=", SCORE_RTOL],
            "least_overlap": [worst("overlap", min), ">=", math.ceil(MIN_OVERLAP_SHARE * how_many)],
            "worst_gap_over_slack": [worst("gap_over_slack", max), "<=", 1.0],
            "malformed_answers": [sum(1 for c in checks if c["fault"]), "==", 0],
            "wrong_bodies_in_window": [wrong_bodies, "==", 0],
            "compiles_in_window": [compiles, "==", 0],
            "host_fallbacks": [fallbacks, "==", 0],
            "dispatches_not_exact": [not_exact, "==", 0],
            "good_in_window": [len(good), ">=", 1],
        }
        late = [
            ms for ms, w in zip(result["late_ms"], result["in_window"]) if w and ms is not None
        ]
        info(
            generator_processes=1, connections_opened=result["connections_opened"],
            errors=result["errors"], warm_s=warm_s,
            in_flight_at_window_end=latency.in_flight_at(result, warm_s + seconds),
            prime_s=t_open - t_prime,
            gen_late_p95_ms=latency.percentile(late, 95) if late else None,
            # the tail and its cause, for a reader of untraced runs (per-layer metrics in a traced one)
            latency_p95_ms=latency.percentile(good, 95) if good else None,
            collector_pauses_s=[round(s, 4) for s in pauses if s > 0.05],
            # the window's dispatches, from the DispatchRecord ring
            dispatches=len(records),
            rows_per_dispatch=sum(r.rows for r in records) / len(records) if records else None,
            shapes=sorted({(r.padded_rows, r.k_bucket) for r in records}),
            queued_ahead_share=queued_ahead_share(records),
            queued_ahead_share_at_open=queued_ahead_share([r for r in ring if r.t_start < t_open]),
        )
    finally:
        gc.callbacks.remove(on_gc)
        if gen is not None:
            gen.kill()
            gen.wait()
        serving.close()

    return {
        "correct": not faults and bool(good),
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "end_to_end": {"p50_ms": latency.percentile(good, 50) if good else None},
        # what the per-layer readers (benchmarks/metrics/*.py) read
        "sources": {
            "config": config,
            "traffic": traffic,
            "counters": delta,
            "dispatch_records": [
                {"rows": r.rows, "padded_rows": r.padded_rows, "k_bucket": r.k_bucket}
                for r in records
            ],
            "generator": {"late_ms": late, "latency_ms": good},
            "collector": {"window_s": seconds, "pauses_s": pauses},
            "trace": trace_out,
            "timeline": timeline_out,
        },
        "compared": compared,
    }
