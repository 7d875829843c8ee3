"""End-to-end ALS lambda slice (SURVEY.md §7's minimum slice): ingest ->
batch model build -> update topic -> serving layer answers REST queries ->
speed layer folds new interactions -> serving applies them.

The analogue of the reference's ALSUpdateIT + serving ITs, run over the
in-process broker with a real HTTP server on a free port.
"""

import json
import time

import numpy as np
import pytest

from oryx_tpu.apps.als.batch import ALSUpdate
from oryx_tpu.apps.als.serving import ALSServingModelManager
from oryx_tpu.apps.als.speed import ALSSpeedModelManager
from oryx_tpu.bus.broker import get_broker, topics
from oryx_tpu.bus.inproc import InProcBroker
from oryx_tpu.common.config import load_config
from oryx_tpu.common.ioutil import choose_free_port
from oryx_tpu.common.rng import RandomManager
from oryx_tpu.layers import BatchLayer, SpeedLayer
from oryx_tpu.serving.server import ServingLayer


@pytest.fixture(autouse=True)
def _fresh_registry():
    InProcBroker.reset_all()
    yield
    InProcBroker.reset_all()


from e2e_common import http_request as _http  # noqa: E402


def _make_config(tmp_path, port):
    return load_config(overlay={
        "oryx.id": "e2e",
        "oryx.input-topic.broker": "mem://e2e",
        "oryx.update-topic.broker": "mem://e2e",
        "oryx.batch.storage.data-dir": str(tmp_path / "data"),
        "oryx.batch.storage.model-dir": str(tmp_path / "model"),
        "oryx.serving.api.port": port,
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.als",
        ],
        "oryx.als.hyperparams.features": 8,
        "oryx.als.hyperparams.iterations": 6,
        "oryx.als.hyperparams.alpha": 10.0,
        "oryx.als.hyperparams.lambda": 0.01,
        "oryx.ml.eval.test-fraction": 0.1,
        "oryx.speed.min-model-load-fraction": 0.8,
        # 1.0: the genre-ranking assertions below query top-5 content; at
        # the default 0.8 the gate opens while the UP flood is still
        # replaying and WHICH 20% of rows are missing is thread timing —
        # a latent flake, not a model-quality signal
        "oryx.serving.min-model-load-fraction": 1.0,
    })


def _genre_events(n_users=40, n_items=32, per_user=6, groups=4, seed=3):
    rng = np.random.default_rng(seed)
    lines = []
    for u in range(n_users):
        g = u % groups
        items = rng.choice(np.arange(g, n_items, groups), per_user, replace=False)
        for ts, i in enumerate(items):
            # timestamps unique per event: the time-based train/test split
            # breaks timestamp ties by arrival order, and arrival order
            # through the partitioned input topic depends on the line-hash
            # partitioner (PYTHONHASHSEED) — tied stamps made the split,
            # and hence the model, vary run to run
            lines.append(f"u{u},i{i},{1 + int(rng.poisson(1))},{1000 + ts * 1000 + u}")
    return lines


def test_full_lambda_slice(tmp_path):
    RandomManager.use_test_seed(99)
    port = choose_free_port()
    cfg = _make_config(tmp_path, port)
    topics.maybe_create("mem://e2e", "OryxInput", partitions=2)
    topics.maybe_create("mem://e2e", "OryxUpdate", partitions=1)
    broker = get_broker("mem://e2e")

    # ---- serving first: /ready must 503 before any model ----
    serving = ServingLayer(cfg, model_manager=ALSServingModelManager(cfg))
    serving.start()
    base = f"http://127.0.0.1:{serving.port}"
    status, _ = _http("GET", f"{base}/ready")
    assert status == 503

    # ---- ingest through the serving layer ----
    lines = _genre_events()
    body = "\n".join(lines).encode()
    status, resp = _http("POST", f"{base}/ingest", body=body)
    assert status == 200, resp
    assert json.loads(resp)["ingested"] == len(lines)

    # ---- batch generation trains + publishes ----
    batch = BatchLayer(cfg, update=ALSUpdate(cfg))
    batch.ensure_streams()
    # input was sent before the batch consumer existed: replay from earliest
    # for this test by pointing the consumer at offset 0
    batch._consumer._fetch_pos = {p: 0 for p in batch._consumer._fetch_pos}
    n = batch.run_generation(timestamp_ms=1_700_000_000_000)
    assert n == len(lines)
    batch.close()

    # update topic now has MODEL + factor-row UP flood
    recs = broker.read("OryxUpdate", 0, 0, 10)
    assert recs[0][1] == "MODEL"

    # ---- serving becomes ready by replaying the update topic ----
    deadline = time.time() + 30
    while time.time() < deadline:
        status, _ = _http("GET", f"{base}/ready")
        if status == 200:
            break
        time.sleep(0.1)
    assert status == 200, "serving never became ready"

    # per-app console section (the reference's als/Console.java analogue)
    status, resp = _http("GET", f"{base}/console")
    assert status == 200 and "ALS model" in resp and "features" in resp

    # ---- query the REST surface ----
    status, resp = _http("GET", f"{base}/recommend/u5?howMany=5")
    assert status == 200, resp
    recs5 = json.loads(resp)
    assert len(recs5) == 5
    # genre structure: u5 is group 1; with most group-1 items excluded as
    # known, the few remaining group-1 items must still rank at the top
    genres = [int(r[0][1:]) % 4 for r in recs5]
    assert genres[0] == 1 and genres[1] == 1, recs5

    # known items excluded from recommendations by default
    status, resp = _http("GET", f"{base}/knownItems/u5")
    known = set(json.loads(resp))
    assert status == 200 and known
    assert not (known & {r[0] for r in recs5})

    # estimate + similarity + anonymous
    some_known = sorted(known)[0]
    status, resp = _http("GET", f"{base}/estimate/u5/{some_known}")
    assert status == 200 and json.loads(resp)[0][1] > 0
    status, resp = _http("GET", f"{base}/similarity/{some_known}?howMany=3")
    assert status == 200 and len(json.loads(resp)) == 3
    status, resp = _http("GET", f"{base}/recommendToAnonymous/{some_known}=2?howMany=4")
    assert status == 200 and len(json.loads(resp)) == 4

    # CSV negotiation
    status, resp = _http("GET", f"{base}/recommend/u5?howMany=2", accept="text/csv")
    assert status == 200 and len(resp.strip().splitlines()) == 2 and "," in resp

    # 404s
    status, _ = _http("GET", f"{base}/recommend/nobody")
    assert status == 404
    status, _ = _http("GET", f"{base}/nothere")
    assert status == 404

    # ---- speed layer folds a new interaction ----
    speed = SpeedLayer(cfg, manager=ALSSpeedModelManager(cfg))
    speed.start()
    # wait until the speed model is loaded from the update topic
    deadline = time.time() + 30
    while time.time() < deadline:
        st = speed.manager.state
        if st is not None and st.fraction_loaded() >= 0.8:
            break
        time.sleep(0.1)
    assert speed.manager.state is not None

    # new user interacts with two group-2 items via /pref
    status, _ = _http("POST", f"{base}/pref/newuser/i2", body=b"3.0")
    assert status == 200
    status, _ = _http("POST", f"{base}/pref/newuser/i6", body=b"3.0")
    assert status == 200

    # run a micro-batch now
    deadline = time.time() + 30
    before = speed.batch_count
    while speed.batch_count == before and time.time() < deadline:
        time.sleep(0.1)

    # serving eventually applies the UP for newuser
    deadline = time.time() + 30
    got = None
    while time.time() < deadline:
        status, resp = _http("GET", f"{base}/recommend/newuser?howMany=4")
        if status == 200:
            got = json.loads(resp)
            break
        time.sleep(0.2)
    assert got is not None, "speed fold-in never reached serving"
    genres = [int(r[0][1:]) % 4 for r in got]
    assert sum(g == 2 for g in genres) >= 2, got

    speed.close()
    serving.close()


def test_serving_read_only_mode(tmp_path):
    RandomManager.use_test_seed(7)
    port = choose_free_port()
    cfg = _make_config(tmp_path, port).overlay({"oryx.serving.api.read-only": True})
    topics.maybe_create("mem://e2e", "OryxInput", partitions=1)
    topics.maybe_create("mem://e2e", "OryxUpdate", partitions=1)
    serving = ServingLayer(cfg, model_manager=ALSServingModelManager(cfg))
    serving.start()
    base = f"http://127.0.0.1:{serving.port}"
    status, resp = _http("POST", f"{base}/ingest", body=b"u1,i1,1")
    assert status == 405
    serving.close()


def test_full_lambda_slice_explicit(tmp_path):
    """The EXPLICIT-feedback mode through the full stack: ratings train an
    ALS-WR model (last-wins aggregation, -RMSE eval), serving answers
    /estimate with rating-scale predictions and /recommend ranks unseen
    items by predicted rating."""
    RandomManager.use_test_seed(21)
    port = choose_free_port()
    cfg = _make_config(tmp_path, port).overlay({
        "oryx.als.implicit": False,
        "oryx.als.hyperparams.lambda": 0.02,
    })
    topics.maybe_create("mem://e2e", "OryxInput", partitions=1)
    topics.maybe_create("mem://e2e", "OryxUpdate", partitions=1)

    serving = ServingLayer(cfg, model_manager=ALSServingModelManager(cfg))
    serving.start()
    base = f"http://127.0.0.1:{serving.port}"

    # structured ratings: users love in-group items (5) and pan the rest (1)
    rng = np.random.default_rng(4)
    lines = []
    ts = 0
    for u in range(24):
        g = u % 3
        for i in range(18):
            if rng.random() < 0.7:
                r = 5.0 if i % 3 == g else 1.0
                ts += 1
                lines.append(f"u{u},i{i},{r},{1000 + ts}")
    status, resp = _http("POST", f"{base}/ingest", body="\n".join(lines).encode())
    assert status == 200, resp

    batch = BatchLayer(cfg, update=ALSUpdate(cfg))
    batch.ensure_streams()
    batch._consumer._fetch_pos = {p: 0 for p in batch._consumer._fetch_pos}
    assert batch.run_generation(timestamp_ms=1_700_000_000_000) == len(lines)
    batch.close()

    deadline = time.time() + 30
    while time.time() < deadline:
        status, _ = _http("GET", f"{base}/ready")
        if status == 200:
            break
        time.sleep(0.1)
    assert status == 200, "serving never became ready"

    # estimates discriminate loved vs panned items for u4 (group 1)
    status, resp = _http("GET", f"{base}/estimate/u4/i1/i0")
    assert status == 200, resp
    est = dict(json.loads(resp))
    assert est["i1"] > est["i0"] + 1.0, est  # in-group ~5 vs out-group ~1

    # recommendations rank unseen in-group items first
    status, resp = _http("GET", f"{base}/recommend/u4?howMany=3")
    assert status == 200
    recs = json.loads(resp)
    assert int(recs[0][0][1:]) % 3 == 1, recs

    serving.close()


def test_evaluate_with_integer_ids(tmp_path):
    """Canonical-integer ids take the native parser's fast path, which
    returns int64 ids; the held-out evaluation must still match them to
    the artifact's string ids (it silently scored NaN — no test user
    'found' — until chip_smoke.py, whose ids are integers, asked for the
    AUC)."""
    from oryx_tpu.bus.api import KeyMessage

    cfg = _make_config(tmp_path, 0)
    update = ALSUpdate(cfg)
    rng = np.random.default_rng(5)
    events = [
        KeyMessage(None, f"{u},{4 * int(rng.integers(0, 8)) + u % 4},1,{1000 + j}")
        for j, u in enumerate(rng.integers(0, 40, 600).tolist())
    ]
    train, test = events[:540], events[540:]
    model = update.build_model(
        train, {"features": 8, "lambda": 0.01, "alpha": 10.0}
    )
    auc = update.evaluate(model, train, test)
    assert np.isfinite(auc) and auc > 0.6, auc
