#!/usr/bin/env python3
"""chip_smoke.py — the ALS lambda slice on one TPU chip, through the
normal entry points, in one process. The quickest proof that the system
still starts on the chip.

    python chip_smoke.py                  # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu   # tiny sizes on the CPU: the flow,
                                          # never a chip pass

What it drives (tests/test_e2e_als.py::test_full_lambda_slice, at width):

  ingest   a seeded implicit dataset with planted group structure is
           POSTed to the serving layer's /ingest;
  batch    one BatchLayer.run_generation builds ALS on the device at 50
           features, MLUpdate's held-out AUC runs, MODEL + the factor-row
           UP flood go out on the update topic;
  load     the ServingLayer replays the update topic until /ready is 200;
           when the batch leg was cut below the served width (BUILD_ITEMS
           < SERVED_ITEMS) a 1M x 50 model is then loaded through the
           same topic as a MODEL-REF, exactly as MLUpdate.publish_model
           writes one;
  serve    concurrent GET /recommend over real HTTP (the batcher
           coalesces them), then /similarity, /estimate and
           /recommendToAnonymous;
  agree    the HTTP top-10 of sampled users against a plain jax.numpy
           float32 reference computed from the served factors;
  fold     two POST /pref for a new user, one SpeedLayer micro-batch, the
           UP visible in /recommend/<new user>.

All three layers live in THIS process: a chip belongs to one process at
a time. Brokers are mem://; files go under <checkout>/.chip_smoke/.

Stdout is two lines of JSON. First the report: device, versions, seconds
per phase with compile separated, the compile cache, the counters, which
top-k path ran, the agreement. Then, as the LAST line, the verdict and
nothing else: {"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}, the device as JAX reports it. With no TPU it prints
neither and exits 2; a failed check exits 1 with "ok": false.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import logging
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
SCRATCH = HERE / ".chip_smoke"

# ---------------------------------------------------------------------------
# sizes. FULL is the reference's published serving width (BASELINE.md: 50
# features x 1M items). The batch leg builds BUILD_ITEMS of them; when
# that is less than SERVED_ITEMS the served catalog arrives as a MODEL-REF.
# ---------------------------------------------------------------------------

FULL = dict(
    served_items=1_000_000, build_items=1_000_000, users=100_000,
    extra_events=2_000_000, features=50, iterations=6, groups=16,
    test_events=2_048, burst=48, check_users=16,
)
TINY = dict(
    served_items=2_000, build_items=2_000, users=400,
    extra_events=6_000, features=8, iterations=3, groups=4,
    test_events=256, burst=16, check_users=16,
)

# Agreement of the HTTP top-10 with the float32 reference, per sampled
# user. Serving scans candidates in bf16 (8 significand bits per factor)
# at k-bucket 32 or 128 — howMany + known items + 8 over-fetch — and then
# re-ranks the candidates in f32 on the host (apps/als/serving.py
# _rerank_exact). So:
# - every returned SCORE is an f32 dot of the same factors: rtol 1e-4
#   covers the accumulation order (host numpy vs device);
# - a returned ITEM may differ from the reference's top-10 only when the
#   bf16 scan could not tell it from the 10th: its reference score lies
#   within BF16_SLACK * max|score| of the reference's 10th score;
# - at least 9 of the 10 are the reference's.
SCORE_RTOL = 1e-4
BF16_SLACK = 2.0 ** -6
MIN_OVERLAP = 9

log = logging.getLogger("chip_smoke")


class Failed(Exception):
    """A check of the smoke did not hold."""


# ---------------------------------------------------------------------------
# phase clock: wall seconds per phase, with XLA compile time (trace +
# lowering + backend compile or cache retrieval, from jax.monitoring)
# attributed to the phase it happened in. The compile figure sums event
# durations across threads and counts a nested trace twice: an upper
# bound, so run_s is floored at 0.
# ---------------------------------------------------------------------------

class PhaseClock:
    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.phases: dict[str, dict] = {}
        self._current: str | None = None
        self._lock = threading.Lock()
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        import jax.monitoring as mon

        def on_duration(event: str, seconds: float, **_kw) -> None:
            if event in self._COMPILE_EVENTS:
                with self._lock:
                    if self._current is not None:
                        self.phases[self._current]["compile_s"] += seconds

        def on_event(event: str, **_kw) -> None:
            with self._lock:
                if event == "/jax/compilation_cache/cache_hits":
                    self.cache_hits += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    self.cache_misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @contextlib.contextmanager
    def phase(self, name: str):
        with self._lock:
            self.phases[name] = {"s": 0.0, "compile_s": 0.0}
            self._current = name
        t0 = time.monotonic()
        log.info("phase %s ...", name)
        try:
            yield
        finally:
            dt = time.monotonic() - t0
            with self._lock:
                self.phases[name]["s"] = dt
                self._current = None
            log.info("phase %s: %.1fs", name, dt)

    def report(self) -> dict:
        out = {}
        for name, p in self.phases.items():
            out[name] = {
                "s": round(p["s"], 2),
                "compile_s": round(p["compile_s"], 2),
                "run_s": round(max(0.0, p["s"] - p["compile_s"]), 2),
            }
        return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def http(method: str, url: str, body: bytes | None = None, timeout: float = 600):
    req = urllib.request.Request(
        url, method=method, data=body, headers={"Accept": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def scrape(base: str) -> dict[str, float]:
    """GET /metrics -> {series: value}. All three layers share this
    process's registry, so one scrape reads the batch and speed counters
    beside the serving ones."""
    status, text = http("GET", f"{base}/metrics")
    if status != 200:
        raise Failed(f"/metrics -> {status}")
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            out[series] = float(value)
    return out


def wait_for(what: str, predicate, timeout: float, poll: float = 0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = predicate()
        if got:
            return got
        time.sleep(poll)
    raise Failed(f"timed out after {timeout:.0f}s waiting for {what}")


def rebuild_native_bus() -> str:
    """liboryxbus.so is git-ignored and oryx_tpu/bus/native.py loads any
    copy it finds, so a stale binary copied along with the tree could
    outlive oryxbus.cpp: rebuild it from the committed sources before the
    bus is first used. Returns which bus path will run."""
    src = HERE / "native" / "oryxbus"
    if shutil.which("make") is None or shutil.which("g++") is None:
        for so in src.glob("liboryxbus.so"):
            so.unlink()  # no toolchain: never trust a binary we cannot rebuild
        return "python"
    subprocess.run(
        ["make", "-B", "-C", str(src)], check=True, capture_output=True,
        timeout=300,
    )
    return "native"


def make_events(sz: dict, seed: int = 20260926):
    """Seeded implicit-feedback events with planted structure: item i
    belongs to group i % G, user u to group u % G, and users only touch
    items of their own group. Three time-ordered blocks:

    1. cover: every one of build_items items once (so each has a factor
       row), by a random user of its group;
    2. extra: extra_events more, uniform over users;
    3. late: test_events from 128 users — the newest events, which the
       time-based split (ALSUpdate.split_train_test) holds out. A small
       late cohort bounds the AUC evaluation, whose cost is one full
       catalog scan per held-out user.

    Returns the CSV lines: "user,item,1,timestamp" with integer ids, the
    native parser's fast path."""
    import numpy as np

    rng = np.random.default_rng(seed)
    g, n_items, n_users = sz["groups"], sz["build_items"], sz["users"]
    per_group_users = n_users // g
    per_group_items = n_items // g

    def users_of(groups):
        return rng.integers(0, per_group_users, len(groups)) * g + groups

    def items_of(groups):
        return rng.integers(0, per_group_items, len(groups)) * g + groups

    cover_i = np.arange(n_items)
    cover_u = users_of(cover_i % g)
    extra_u = rng.integers(0, n_users, sz["extra_events"])
    extra_i = items_of(extra_u % g)
    late_cohort = rng.choice(n_users, 128, replace=False)
    late_u = late_cohort[rng.integers(0, 128, sz["test_events"])]
    late_i = items_of(late_u % g)
    users = np.concatenate([cover_u, extra_u, late_u])
    items = np.concatenate([cover_i, extra_i, late_i])
    ts = 1_700_000_000_000 + np.arange(len(users))
    return [f"{u},{i},1,{t}" for u, i, t in zip(users.tolist(), items.tolist(), ts.tolist())]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(sz: dict, rehearsal: bool, result: dict) -> None:
    """Drive the slice; fill `result`; raise Failed on a check."""
    import numpy as np

    import jax

    devices = jax.devices()
    n_dev = len(devices)
    platform = devices[0].platform
    clock = PhaseClock()
    clock.install()
    failures: list[str] = result["failures"]

    def check(ok: bool, message: str) -> None:
        if not ok:
            log.error("CHECK FAILED: %s", message)
            failures.append(message)

    with clock.phase("setup"):
        from oryx_tpu.parallel.distributed import configure_compilation_cache

        cache_dir = configure_compilation_cache()
        result["bus"] = rebuild_native_bus()

        from oryx_tpu.apps.als.batch import ALSUpdate
        from oryx_tpu.apps.als.serving import ALSServingModelManager
        from oryx_tpu.apps.als.speed import ALSSpeedModelManager
        from oryx_tpu.bus.broker import topics
        from oryx_tpu.common.config import load_config
        from oryx_tpu.common.rng import RandomManager
        from oryx_tpu.layers import BatchLayer, SpeedLayer
        from oryx_tpu.serving.batcher import k_bucket
        from oryx_tpu.serving.server import ServingLayer

        if result["bus"] == "native":
            from oryx_tpu.bus.native import NativeAppender

            NativeAppender.load()  # a rebuilt library that cannot load is an error
        RandomManager.use_test_seed(20260926)
        shutil.rmtree(SCRATCH, ignore_errors=True)
        SCRATCH.mkdir(parents=True)
        broker = "mem://chip-smoke"
        overlay = {
            "oryx.id": "chip-smoke",
            "oryx.input-topic.broker": broker,
            "oryx.update-topic.broker": broker,
            "oryx.batch.storage.data-dir": str(SCRATCH / "data"),
            "oryx.batch.storage.model-dir": str(SCRATCH / "model"),
            "oryx.monitoring.quarantine.dir": str(SCRATCH / "quarantine"),
            "oryx.serving.api.port": 0,
            "oryx.serving.application-resources": [
                "oryx_tpu.serving.resources.common",
                "oryx_tpu.serving.resources.als",
            ],
            "oryx.als.hyperparams.features": sz["features"],
            "oryx.als.hyperparams.iterations": sz["iterations"],
            "oryx.als.hyperparams.alpha": 10.0,
            "oryx.als.hyperparams.lambda": 0.01,
            # the held-out set is the late cohort's events (make_events)
            "oryx.ml.eval.test-fraction": sz["test_events"]
            / (sz["build_items"] + sz["extra_events"] + sz["test_events"]),
            "oryx.speed.streaming.generation-interval-sec": 1,
            # answer only from a complete model: which rows a partial load
            # is missing is thread timing, not a property of the system
            "oryx.serving.min-model-load-fraction": 1.0,
            "oryx.speed.min-model-load-fraction": 1.0,
        }
        if not rehearsal:
            # a TPU that fails to initialise is an error, never a CPU start
            overlay["oryx.compute.platform"] = "tpu"
        if n_dev > 1:
            overlay["oryx.serving.api.sync.shard-count"] = n_dev
            overlay["oryx.batch.train.shards"] = n_dev
        cfg = load_config(overlay=overlay)
        topics.maybe_create(broker, "OryxInput", partitions=2)
        topics.maybe_create(broker, "OryxUpdate", partitions=1)

        serving = ServingLayer(cfg, model_manager=ALSServingModelManager(cfg))
        serving.start()
        base = f"http://127.0.0.1:{serving.port}"
        status, _ = http("GET", f"{base}/ready")
        check(status == 503, f"/ready before any model: {status}, want 503")
        # the batch consumer opens at the log end: open it before ingest
        batch = BatchLayer(cfg, update=ALSUpdate(cfg))
        batch.ensure_streams()
    closers = [serving.close, batch.close]

    try:
        with clock.phase("ingest"):
            lines = make_events(sz)
            step = 250_000
            for lo in range(0, len(lines), step):
                body = "\n".join(lines[lo:lo + step]).encode()
                status, resp = http("POST", f"{base}/ingest", body=body)
                if status != 200:
                    raise Failed(f"/ingest -> {status}: {resp[:300]}")
            result["events"] = len(lines)

        with clock.phase("batch"):
            n = batch.run_generation(timestamp_ms=1_700_000_000_000)
            check(n == len(lines), f"batch consumed {n} of {len(lines)} events")
            del lines
            build_failures = scrape(base).get("oryx_batch_build_failures_total", 0.0)
            check(build_failures == 0, f"oryx_batch_build_failures_total = {build_failures}")

        with clock.phase("load"):
            wait_for(
                "serving /ready", lambda: http("GET", f"{base}/ready")[0] == 200,
                timeout=900,
            )
            # the generation's scorecard rides the publish stamp into the
            # serving tier: MLUpdate's held-out evaluation, as served
            auc = scrape(base).get('oryx_generation_quality{metric="auc"}')
            result["auc"] = None if auc is None else round(auc, 4)
            check(auc is not None and auc > 0.75,
                  f"held-out AUC {auc}: planted group structure not recovered")
            model = serving.model_manager.model
            n_built = len(model.state.y)
            check(n_built == sz["build_items"],
                  f"serving holds {n_built} item rows, batch built {sz['build_items']}")
            result["catalog"] = {
                "features": sz["features"], "batch_built_items": n_built,
                "served_items": n_built, "how": "built by the batch layer",
            }
            if sz["served_items"] > sz["build_items"]:
                publish_model_ref(cfg, sz, batch.update, SCRATCH / "model-ref")
                wait_for(
                    "the MODEL-REF catalog",
                    lambda: serving.model_manager.model is not None
                    and len(serving.model_manager.model.state.y) == sz["served_items"]
                    and http("GET", f"{base}/ready")[0] == 200,
                    timeout=900,
                )
                model = serving.model_manager.model
                result["catalog"].update(
                    served_items=len(model.state.y),
                    how="batch build at batch_built_items, then a "
                    "served_items x features model loaded through the "
                    "update topic as MODEL-REF",
                )
            state = model.state
            y_host, y_ids, _ = state.y.snapshot()
            nan_rows = int((~np.isfinite(y_host).all(axis=1)).sum())
            x_host, x_ids, _ = state.x.snapshot()
            nan_rows += int((~np.isfinite(x_host).all(axis=1)).sum())
            result["nan_factor_rows"] = nan_rows
            check(nan_rows == 0, f"{nan_rows} factor rows hold NaN/inf")
            check(y_host.shape == (sz["served_items"], sz["features"]),
                  f"served item matrix is {y_host.shape}")

        # users to ask about: seen in training, so they have vectors
        rng = np.random.default_rng(7)
        x_index = {u: j for j, u in enumerate(x_ids)}
        ask = [x_ids[int(j)] for j in rng.choice(len(x_ids), sz["burst"], replace=False)]

        def recommend(user: str):
            status, resp = http("GET", f"{base}/recommend/{user}?howMany=10")
            if status != 200:
                raise Failed(f"/recommend/{user} -> {status}: {resp[:300]}")
            return json.loads(resp)

        with clock.phase("serve_first"):
            # first request: staged upload of the device view + the first
            # top-k compile
            first = recommend(ask[0])
            check(len(first) == 10, f"first /recommend returned {len(first)} rows")

        with clock.phase("serve_burst"):
            m0 = scrape(base)
            with concurrent.futures.ThreadPoolExecutor(len(ask)) as pool:
                answers = dict(zip(ask, pool.map(recommend, ask)))
            m1 = scrape(base)
            burst_dispatches, burst_requests = (
                int(m1[name] - m0[name])
                for name in ("oryx_topk_dispatches", "oryx_topk_coalesced")
            )
            result["burst"] = {
                "requests": burst_requests, "dispatches": burst_dispatches,
            }
            check(burst_requests == len(ask),
                  f"batcher saw {burst_requests} of {len(ask)} burst requests")
            check(burst_dispatches < burst_requests,
                  f"no coalescing: {burst_dispatches} dispatches for "
                  f"{burst_requests} requests")

        with clock.phase("endpoints"):
            some_item = answers[ask[0]][0][0]
            other_item = answers[ask[0]][1][0]
            for path, want in (
                (f"/similarity/{some_item}?howMany=5", 5),
                (f"/estimate/{ask[0]}/{some_item}/{other_item}", 2),
                (f"/recommendToAnonymous/{some_item}=2/{other_item}?howMany=5", 5),
            ):
                status, resp = http("GET", base + path)
                rows = json.loads(resp) if status == 200 else None
                check(status == 200 and len(rows) == want,
                      f"{path} -> {status}, {resp[:200]}")
                if rows:
                    check(all(np.isfinite(r[1]) for r in rows), f"{path}: non-finite score")

        with clock.phase("agree"):
            view = model._y_view_full()[0]
            view_devices = sorted(view.devices(), key=lambda d: d.id)
            check(all(d.platform == platform for d in view_devices),
                  f"device view sits on {view_devices}, expected {platform}")
            from oryx_tpu.ops.als import topk_path
            from oryx_tpu.ops.transfer import ShardedMatrix

            shards = view.shards if isinstance(view, ShardedMatrix) else [view]
            buckets = sorted({
                min(k_bucket(10 + len(state.get_known_items(u)) + 8), view.shape[0])
                for u in ask
            })
            paths = sorted({topk_path(s, kb) for s in shards for kb in buckets})
            result["topk"] = {
                "path": "+".join(paths), "dtype": str(view.dtype),
                "k_buckets": buckets, "view_rows": int(view.shape[0]),
                "view_devices": [str(d) for d in view_devices],
            }
            if not rehearsal:
                check(all(p in ("pallas", "xla") for p in paths),
                      f"unexpected exact top-k path {paths}")
            check_users = ask[: sz["check_users"]]
            result["agreement"] = agree_with_reference(
                check_users, answers, state, x_host, x_index, y_host, y_ids, check,
            )

        with clock.phase("fold"):
            speed = SpeedLayer(cfg, manager=ALSSpeedModelManager(cfg))
            closers.append(speed.close)
            speed.start()
            wait_for(
                "the speed layer's model",
                lambda: speed.manager.state is not None
                and speed.manager.state.fraction_loaded() >= 1.0,
                timeout=900,
            )
            new_user = "900000001"
            g0 = int(y_ids[0]) % sz["groups"]
            liked = [i for i in y_ids if int(i) % sz["groups"] == g0][:2]
            before = speed.batch_count
            t_pref = time.monotonic()
            for item in liked:
                status, resp = http("POST", f"{base}/pref/{new_user}/{item}", body=b"3.0")
                check(status == 200, f"POST /pref/{new_user}/{item} -> {status}")
            wait_for("a speed micro-batch", lambda: speed.batch_count > before, 120)

            def new_user_rows():
                status, resp = http("GET", f"{base}/recommend/{new_user}?howMany=10")
                return json.loads(resp) if status == 200 else None

            got = wait_for(f"/recommend/{new_user}", new_user_rows, timeout=180)
            result["update_to_serve_s"] = round(time.monotonic() - t_pref, 2)
            same_group = sum(1 for r in got if int(r[0]) % sz["groups"] == g0)
            result["fold"] = {"new_user_rows": len(got), "same_group": same_group}
            check(len(got) == 10, f"/recommend/{new_user} returned {len(got)} rows")
            check(same_group >= 8,
                  f"fold-in: {same_group}/10 recommendations in the liked group")

        if n_dev > 1:
            with clock.phase("multichip"):
                result["multichip"] = multichip_checks(
                    model, devices, check, batch.update
                )

        metrics = scrape(base)
        counters = {
            name: int(metrics.get(name, 0.0))
            for name in (
                "oryx_topk_dispatches", "oryx_topk_coalesced",
                "oryx_topk_host_fallbacks", "oryx_topk_device_failovers",
                "oryx_topk_device_down", "oryx_batch_build_failures_total",
                "oryx_speed_failures_total",
            )
        }
        result["counters"] = counters
        check(counters["oryx_topk_dispatches"] > 0, "no device dispatch happened")
        for name, value in counters.items():
            if name not in ("oryx_topk_dispatches", "oryx_topk_coalesced"):
                check(value == 0, f"{name} = {value}: the device path was left")
    finally:
        for close in reversed(closers):
            try:
                close()
            except Exception:  # noqa: BLE001 - shutdown must not mask the result
                log.exception("close failed")
        result["phases"] = clock.report()
        cache_path = Path(cache_dir) if "://" not in cache_dir else None
        result["compile_cache"] = {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "non_empty": bool(cache_path and cache_path.is_dir() and any(cache_path.iterdir())),
            "hits": clock.cache_hits, "misses": clock.cache_misses,
        }
        result["compile_s_total"] = round(
            sum(p["compile_s"] for p in result["phases"].values()), 2
        )


def publish_model_ref(cfg, sz: dict, update, out_dir: Path) -> None:
    """A served_items x features ALS model, seeded, written as a model
    artifact and announced on the update topic by MLUpdate.publish_model
    itself — the base-class publish, tensors included, not ALSUpdate's
    skeleton: too large for one message, so it goes as MODEL-REF (with
    its bytes chunked onto the topic). Factors keep the planted
    structure: a group direction plus noise."""
    import numpy as np

    from oryx_tpu.bus.api import TopicProducer
    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.common.artifact import ModelArtifact
    from oryx_tpu.ml.update import MLUpdate

    rng = np.random.default_rng(11)
    g, f = sz["groups"], sz["features"]
    centers = rng.standard_normal((g, f)).astype(np.float32)

    def factors(n):
        noise = 0.3 * rng.standard_normal((n, f), dtype=np.float32)
        return (centers[np.arange(n) % g] + noise) / np.sqrt(f)

    art = ModelArtifact(
        "als",
        extensions={
            "features": str(f), "lambda": "0.01", "alpha": "10.0",
            "implicit": "true", "logStrength": "false",
        },
        tensors={"X": factors(sz["users"]), "Y": factors(sz["served_items"])},
    )
    art.set_extension("XIDs", [str(u) for u in range(sz["users"])])
    art.set_extension("YIDs", [str(i) for i in range(sz["served_items"])])
    path = art.write(out_dir / "1700000001000")
    producer = TopicProducer(
        get_broker(cfg.get_string("oryx.update-topic.broker")),
        cfg.get_string("oryx.update-topic.message.topic"),
    )
    update.note_eval(None)  # this model was not evaluated: no scorecard
    MLUpdate.publish_model(update, ModelArtifact.read(path), str(path), producer)


def agree_with_reference(users, answers, state, x_host, x_index, y_host, y_ids, check) -> dict:
    """HTTP top-10 vs a plain jax.numpy float32 reference from the same
    factors, known items excluded. Tolerances: see SCORE_RTOL."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    id_row = {ident: j for j, ident in enumerate(y_ids)}
    xs = jnp.asarray(np.stack([x_host[x_index[u]] for u in users]))
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jnp.dot(xs, jnp.asarray(y_host).T))
    exact = 0
    worst_rel = 0.0
    min_overlap = 10
    for row, user in enumerate(users):
        s = scores[row].copy()
        known = [id_row[i] for i in state.get_known_items(user) if i in id_row]
        s[known] = -np.inf
        ref_top = np.argsort(-s, kind="stable")[:10]
        tenth = s[ref_top[-1]]
        slack = BF16_SLACK * float(np.max(np.abs(scores[row])))
        got = answers[user]
        got_rows = [id_row[r[0]] for r in got]
        got_scores = np.asarray([r[1] for r in got], dtype=np.float64)
        check(not set(got_rows) & set(known), f"user {user}: a known item was recommended")
        check(bool(np.all(np.diff(got_scores) <= 0)), f"user {user}: scores not descending")
        rel = np.max(np.abs(got_scores - scores[row][got_rows])
                     / np.maximum(np.abs(scores[row][got_rows]), 1e-6))
        worst_rel = max(worst_rel, float(rel))
        check(rel <= SCORE_RTOL,
              f"user {user}: served scores differ from the f32 reference by rel {rel:.2e}")
        overlap = len(set(got_rows) & set(ref_top.tolist()))
        min_overlap = min(min_overlap, overlap)
        exact += got_rows == ref_top.tolist()
        check(overlap >= MIN_OVERLAP,
              f"user {user}: only {overlap}/10 of the reference top-10 served")
        for r in got_rows:
            if r not in ref_top:
                check(s[r] >= tenth - slack,
                      f"user {user}: item row {r} scores {s[r]:.5f}, the reference's "
                      f"10th {tenth:.5f} (bf16 slack {slack:.5f})")
    return {
        "users": len(users), "identical_top10": int(exact),
        "min_overlap": int(min_overlap), "worst_score_rel_err": float(f"{worst_rel:.3g}"),
        "tolerance": (
            f"scores rtol {SCORE_RTOL} (f32 host re-rank in _rerank_exact vs f32 "
            f"device reference: accumulation order); >= {MIN_OVERLAP}/10 items, a "
            f"differing item within 2^-6 * max|score| of the reference's 10th "
            "(bf16 candidate scan at the k-buckets reported under topk)"
        ),
    }


def multichip_checks(model, devices, check, update) -> dict:
    """More than one device visible: the shards sit on distinct devices,
    every device holds bytes, the sharded top-k agrees with an unsharded
    one (indices exact, values to the tolerance tests/test_shard_topk.py
    states), and the trainer's mesh spans every device."""
    import numpy as np

    import jax.numpy as jnp

    from oryx_tpu.ops.als import topk_dot_batch
    from oryx_tpu.ops.transfer import ShardedMatrix, staged_device_put

    view, _ids, _version, host = model._y_view_full()
    out: dict = {"devices": len(devices)}
    if not isinstance(view, ShardedMatrix):
        check(False, f"device view is {type(view).__name__}, not a ShardedMatrix")
        return out
    shard_devs = [next(iter(s.devices())) for s in view.shards]
    out["shard_devices"] = [str(d) for d in shard_devs]
    check(len(set(shard_devs)) == len(devices),
          f"{len(view.shards)} shards sit on {len(set(shard_devs))} distinct devices")
    in_use = {str(d): int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices}
    out["bytes_in_use"] = in_use
    if devices[0].platform != "cpu":  # the CPU backend reports no memory stats
        check(all(v > 0 for v in in_use.values()), f"a device holds no bytes: {in_use}")
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.standard_normal((16, host.shape[1])).astype(np.float32))
    whole = staged_device_put(host, dtype=jnp.bfloat16)
    v0, i0 = topk_dot_batch(xs, whole, k=32)
    v1, i1 = topk_dot_batch(xs, view, k=32)
    same = bool(np.array_equal(np.asarray(i0), np.asarray(i1)))
    out["sharded_indices_identical"] = same
    check(same, "sharded top-k indices differ from the unsharded dispatch")
    close = bool(np.allclose(np.asarray(v0), np.asarray(v1), rtol=1e-5))
    out["sharded_values_close"] = close
    check(close, "sharded top-k values differ from the unsharded dispatch by > rtol 1e-5")
    mesh = update._shard_mesh()
    out["train_mesh_devices"] = 0 if mesh is None else int(mesh.devices.size)
    check(mesh is not None and mesh.devices.size == len(devices),
          f"trainer mesh spans {out['train_mesh_devices']} of {len(devices)} devices")
    return out


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the flow at tiny sizes on the CPU; its output says "
        "platform cpu and \"ok\" stays false — never a chip pass",
    )
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    sys.path.insert(0, str(HERE))
    try:
        import jax
        import jaxlib

        import oryx_tpu  # noqa: F401 - a bare chip_smoke.py is not the system
    except ImportError as e:
        print(f"chip_smoke: cannot import the system: {e}", file=sys.stderr)
        return 2
    # pin the platform BEFORE first use: a TPU that fails to initialise is
    # an error here, never a quiet start on the CPU
    jax.config.update("jax_platforms", "cpu" if args.rehearse_cpu else "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no TPU: {e}", file=sys.stderr)
        return 2
    if not args.rehearse_cpu and devices[0].platform != "tpu":
        print(f"chip_smoke: JAX started on {devices[0].platform}, not a TPU",
              file=sys.stderr)
        return 2
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - reported, not required
        libtpu = None
    result: dict = {
        "ok": False,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "mode": "cpu-rehearsal" if args.rehearse_cpu else "chip",
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu},
        "failures": [],
    }
    t0 = time.monotonic()
    try:
        run(TINY if args.rehearse_cpu else FULL, args.rehearse_cpu, result)
    except Failed as e:
        result["failures"].append(str(e))
    except Exception as e:  # noqa: BLE001 - the boundary: report, then fail
        log.exception("smoke crashed")
        result["failures"].append(f"{type(e).__name__}: {e}")
    result["seconds"] = round(time.monotonic() - t0, 1)
    passed = not result["failures"]
    if args.rehearse_cpu:
        # a rehearsal can pass; it can never be a chip pass
        result["rehearsal_passed"] = passed
    else:
        result["ok"] = passed
    print(json.dumps(result))
    # the verdict: exactly these two keys, the last line of stdout
    print(json.dumps({"ok": result["ok"], "device": result["device"]}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    # os._exit: daemon threads of the layers (HTTP loops, listeners) must
    # not be able to hold the process — and the chip — after the verdict
    sys.stdout.flush()
    rc = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
