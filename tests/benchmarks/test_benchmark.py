"""The benchmark's own arithmetic, its files, and a CPU rehearsal of
benchmarks/run.py end to end (ISSUE 24). No chip: nothing here is a device
number."""

import json
import operator
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import listed
from benchmarks import latency, loadgen, xplane
from benchmarks.kinds import als_serving
from benchmarks.run import find, load_module, metrics_of

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
# a traffic file states the kind whose generator reads it (absent: als-serving);
# another kind's files are for that kind's own tests/benchmarks/test_<kind>.py
TRAFFIC_FILES = [
    f for f in sorted((REPO / "benchmarks" / "traffic").glob("*.json"))
    if json.loads(f.read_text()).get("kind", "als-serving") == "als-serving"
]
RECORDED = HERE / "data" / "steady128-5s.xplane.pb.gz"
POPULATION = {"items": 5_000_000, "active_users": 20_000}


# -- latency arithmetic ------------------------------------------------------

@pytest.mark.parametrize("q", [0, 50, 95, 100])
def test_percentile_is_numpys_linear_method(q):
    values = np.random.default_rng(q).random(1001).tolist()
    assert latency.percentile(values, q) == pytest.approx(np.percentile(values, q))


def test_latency_is_taken_over_the_requests_due_in_the_window():
    result = {
        "due": [0.5, 1.0, 1.5, 2.0],
        "in_window": [False, True, True, True],
        "latency_ms": [10.0, 20.0, None, 40.0],  # None: failed, refused or wrong
        "done_at": [0.51, 1.02, None, 2.04],
    }
    good, attempted, failed = latency.window_latencies(result)
    assert (good, attempted, failed) == ([20.0, 40.0], 3, 1)
    assert latency.in_flight_at(result, 2.01) == 2  # the failed one and the last


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        latency.percentile([], 50)


# -- the schedule is a pure function of the seed -------------------------------

@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_schedule_is_a_pure_function_of_the_seed(traffic_file):
    traffic = json.loads(traffic_file.read_text())
    seed = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits
    a = loadgen.draw_schedule(seed, POPULATION, traffic, 6.0, 40.0)
    b = loadgen.draw_schedule(seed, POPULATION, traffic, 6.0, 40.0)
    c = loadgen.draw_schedule(seed + 1, POPULATION, traffic, 6.0, 40.0)
    for key in ("due", "user", "in_window"):
        assert np.array_equal(a[key], b[key])
    assert not np.array_equal(a["due"], c["due"])
    # every seed sends the same number of requests, in the window and before
    rate = traffic["rate_per_s"]
    assert int(a["in_window"].sum()) == int(c["in_window"].sum()) == round(rate * 40.0)
    assert len(a["due"]) == round(rate * 6.0) + round(rate * 40.0)
    assert np.all(np.diff(a["due"][a["in_window"]]) >= 0)
    assert a["due"][a["in_window"]].min() >= 6.0 and a["due"].max() < 46.0
    assert a["user"].min() >= 0 and a["user"].max() < POPULATION["active_users"]
    # Zipf(1.0): the most popular user gets ~1/H(20000) = 9.6 % of the picks
    top_share = np.bincount(a["user"]).max() / len(a["user"])
    assert 0.07 < top_share < 0.13


@pytest.mark.parametrize("traffic_file", TRAFFIC_FILES, ids=lambda p: p.stem)
def test_every_traffic_file_yields_exactly_one_k_bucket(traffic_file):
    from oryx_tpu.serving.batcher import k_bucket

    traffic = json.loads(traffic_file.read_text())
    small = {"items": 50_000, "active_users": 2_000}
    known = loadgen.draw_known(99, small, traffic)
    again = loadgen.draw_known(99, small, traffic)
    assert all(np.array_equal(x, y) for x, y in zip(known, again))
    lo, hi = traffic["known_items"]
    counts = {len(rows) for rows in known}
    assert min(counts) == lo and max(counts) == hi
    assert all(len(set(rows.tolist())) == len(rows) for rows in known)  # distinct
    # k = howMany + known + 8 (apps/als/serving.py _top_n_plan)
    buckets = {k_bucket(traffic["how_many"] + n + 8) for n in range(lo, hi + 1)}
    assert buckets == {traffic["k_bucket"]}


def test_check_body_counts_items_and_refuses_known_ones():
    body = json.dumps([[f"i{j}", 1.0] for j in range(10)]).encode()
    assert loadgen.check_body(body, 10, {11, 12}) is None
    assert loadgen.check_body(body, 10, {3}) == "known_item"
    assert loadgen.check_body(body, 9, set()) == "wrong_count"
    assert loadgen.check_body(b"<html>", 10, set()) == "unparsable"


# -- the comparison that decides `correct` --------------------------------------

def _served(scores, known, how_many=10):
    s = scores.copy()
    s[known] = -np.inf
    top = np.argsort(-s, kind="stable")[:how_many]
    return [[f"i{r}", float(scores[r])] for r in top]


def test_agreement_with_the_float32_reference():
    rng = np.random.default_rng(5)
    y = rng.standard_normal((5000, 16), dtype=np.float32)
    x = rng.standard_normal((2, 16), dtype=np.float32)
    scores = als_serving.reference_scores(x, y)
    assert scores.shape == (2, 5000)
    assert np.allclose(scores, x @ y.T, rtol=1e-5, atol=1e-5)
    known = np.asarray([int(np.argmax(scores[0]))])
    good = _served(scores[0], known)
    assert als_serving.agree(good, scores[0], known, 10) is None
    # a known item served, scores computed in bf16, or a far-off item: refused
    assert "known" in als_serving.agree(_served(scores[0], np.asarray([], int)), scores[0], known, 10)
    import jax.numpy as jnp

    low = [[i, float(jnp.asarray(s, dtype=jnp.bfloat16))] for i, s in good]
    low.sort(key=lambda p: -p[1])
    assert "rel" in als_serving.agree(low, scores[0], known, 10)
    worst = int(np.argmin(scores[0]))
    far = good[:9] + [[f"i{worst}", float(scores[0][worst])]]
    assert "reference's last" in als_serving.agree(far, scores[0], known, 10)
    assert "items served" in als_serving.agree(good[:9], scores[0], known, 10)


# -- roofline arithmetic and the peaks table ------------------------------------

@pytest.mark.parametrize(
    "rows,items,features,k,ms_hbm,ms_mxu",
    [
        (113, 5_000_000, 250, 128, 3.05, 1.43),  # als-5m-250f at 100 req/s
        (48, 1_000_000, 50, 32, 0.1221, 0.02437),   # the reference's headline point
    ],
)
def test_topk_work_at_the_configurations_shapes(rows, items, features, k, ms_hbm, ms_mxu):
    peaks = json.loads((REPO / "benchmarks" / "peaks.json").read_text())["TPU v5 lite"]
    flops, moved = als_serving.topk_work(rows, items, features, k)
    assert flops == 2.0 * rows * items * features
    assert moved / peaks["hbm_bytes_per_s"] * 1e3 == pytest.approx(ms_hbm, rel=0.01)
    assert flops / peaks["flops_per_s"]["bfloat16"] * 1e3 == pytest.approx(ms_mxu, rel=0.01)


# -- the per-layer readers on synthetic sources ----------------------------------

def _reader_reads_its_case(paths, name):
    read = load_module(find(paths, f"metrics/{name}.py")).read
    case = json.loads(find(paths, f"cases/{name}.json").read_text())
    src = case["src"]
    if isinstance(src.get("peaks"), str):
        src["peaks"] = json.loads(find(paths, "peaks.json").read_text())[src["peaks"]]
    assert read(src) == pytest.approx(case["expect"], rel=0.01)
    assert read({}) is None


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_listed_reader_reads_its_case_and_returns_nothing_from_nothing(name):
    """cases/<name>.json is {"src": what the reader is handed, "expect": what
    it has to read from it}; a "peaks" string in src names a device kind of
    peaks.json. Listing a metric is adding its reader, its case and its
    entry: no table here names them."""
    _reader_reads_its_case(PATHS, name)


# -- the trace reduction on a recorded trace --------------------------------------

def test_trace_reduction_on_a_recorded_v5e_trace():
    """A 5 s traced run of als-5m-250f.steady128 on one v5e (PR 24)."""
    trace = xplane.reduce_trace(RECORDED)
    assert trace["devices"] == 1
    count, seconds = xplane.op_seconds(trace, "topk_pallas")
    assert count >= 2
    assert 1.0 < seconds / count < 1.3  # the 512-row scan of 6.29M rows: ~1.13 s
    assert 0 < trace["busy_s"] <= trace["window_s"]
    assert trace["busy_s"] / trace["window_s"] > 0.99  # back-to-back dispatches
    bd = xplane.breakdown(trace)
    assert "topk_pallas" in bd["device_ops"][0][0]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(isinstance(label, str) and s >= 0 for label, s in bd["idle_gaps"])


def test_interval_union_and_gap_labels():
    assert xplane._merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    host = [(0.0, 10.0, "main:outer"), (3.0, 5.0, "batcher:fetch")]
    assert xplane._label_gap(host, (3.5, 4.5)) == "batcher:fetch"  # innermost of equals
    assert xplane._label_gap(host, (20.0, 21.0)) == "no_host_event"
    # the program's own region wins over a runtime event that covers more of the gap
    host = [(0.0, 4.0, "python3:batcher.issue"), (0.0, 10.0, "python3:PjitFunction(f)")]
    assert xplane._label_gap(host, (1.0, 9.0)) == "python3:PjitFunction(f)"
    assert xplane._label_gap(host, (1.0, 9.0), prefer="batcher.") == "python3:batcher.issue"
    assert xplane._label_gap(host, (5.0, 9.0), prefer="batcher.") == "python3:PjitFunction(f)"
    # several prefixes, in order: the thread that feeds the device before the one that waits for it
    host = [
        (0.0, 10.0, "oryx-topk:batcher.fetch"), (2.0, 3.0, "oryx-seq:stepper.step"),
        (2.2, 2.6, "oryx-seq:stepper.step.call"), (3.0, 6.0, "oryx-seq:stepper.idle"), (0.0, 10.0, "main:outer"),
    ]
    both = ("stepper.", "batcher.")
    assert xplane._label_gap(host, (1.0, 9.0), prefer=both) == "oryx-seq:stepper.idle"   # most of the gap
    assert xplane._label_gap(host, (2.0, 3.5), prefer=both) == "oryx-seq:stepper.step"   # the host's turn
    assert xplane._label_gap(host, (2.3, 2.5), prefer=both) == "oryx-seq:stepper.step.call"  # innermost of equals
    assert xplane._label_gap(host, (7.0, 9.0), prefer=both) == "oryx-topk:batcher.fetch"  # no stepper region there
    assert xplane._label_gap(host, (1.0, 9.0), prefer=("batcher.", "stepper.")) == "oryx-topk:batcher.fetch"
    assert xplane._label_gap(host, (1.0, 9.0), prefer=("batcher.",)) == xplane._label_gap(host, (1.0, 9.0), prefer="batcher.")
    assert xplane._label_gap(host, (1.0, 9.0), prefer=()) == xplane._label_gap(host, (1.0, 9.0)) == "oryx-topk:batcher.fetch"


# -- BENCHMARK.json against the contract and the files ------------------------------

def test_benchmark_json_resolves_to_files_and_metrics_move_what_cells_report():
    listed.structure_holds(BENCH)


def test_names_and_units_use_only_the_allowed_characters():
    listed.names_hold(BENCH)
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def _appended(tmp_path):
    """BENCHMARK.json as the next `model_config` PR will leave it: a
    configuration, a cell and a per-layer metric APPENDED, the cell appended
    to the five stepper metrics' lists, and their files (configuration,
    traffic, reader, case) in a directory appended to `paths`."""
    bench = json.loads(json.dumps(BENCH))
    config, traffic, metric = "synthetic-enc-2l", "synthetic-mix", "synthetic_step_ms"
    cell = f"{config}.{traffic}"
    for sub in ("configs", "traffic", "metrics", "cases"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / f"{config}.json").write_text(json.dumps({"kind": "joyai-serving", "num_hidden_layers": 2}))
    (tmp_path / "traffic" / f"{traffic}.json").write_text(json.dumps({"kind": "joyai-serving", "rate_per_s": 30}))
    (tmp_path / "metrics" / f"{metric}.py").write_text(
        "def read(src):\n    steps = src.get('steps')\n    return steps['ms'] if steps else None\n"
    )
    (tmp_path / "cases" / f"{metric}.json").write_text(json.dumps({"src": {"steps": {"ms": 2.5}}, "expect": 2.5}))
    bench["paths"].append(str(tmp_path))
    bench["configs"].append({
        "name": config, "source": "https://example.org/config.json", "file": f"{tmp_path}/configs/{config}.json",
        "reduced": ["num_hidden_layers"], "why": "a synthetic configuration, appended",
    })
    bench["workloads"].append({"name": cell, "config": config, "traffic": traffic, "chips": 1, "why": "appended"})
    bench["per_layer"].append({
        "name": metric, "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "batched encoder step", "moves": "p50_ms", "workloads": [cell],
    })
    for m in bench["per_layer"]:
        if m["name"] in listed.STEPPER:
            m["workloads"].append(cell)
    return bench, cell, metric


def test_appended_entries_break_no_assertion_over_the_lists(tmp_path):
    """Every assertion of tests/benchmarks/ that reads BENCHMARK.json's lists,
    over the file with the next PR's entries appended at their ends: the ones
    `test_benchmark.py` and the three encoder kinds' test files call."""
    bench, cell, metric = _appended(tmp_path)
    listed.structure_holds(bench)
    listed.names_hold(bench)
    _reader_reads_its_case(bench["paths"], metric)
    # the appended cell reads the shared layers' metrics, the stepper's five and its own
    mine = {m["name"] for m in metrics_of(bench["per_layer"], cell)}
    shared = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert len(shared) == 29 and mine == shared | listed.STEPPER | {metric}
    # each earlier PR's entries are where they were: present, once, in order, contiguous
    for args in listed.ADDED:
        listed.entries_of(bench, *args)
        assert listed.entries_of(BENCH, *args) == listed.entries_of(bench, *args)
    # and what a traced CPU run of an old cell has to print did not move
    for w in BENCH["workloads"]:
        assert listed.cpu_names(bench, w["name"]) == listed.cpu_names(BENCH, w["name"])
    assert listed.cpu_names(bench, "als-tiny.tiny") == listed.cpu_names(BENCH, "als-tiny.tiny")


# -- the CPU rehearsal: run.py end to end, the generator a real subprocess -----------

def _run(workload, trace, tmp_path, seconds=2):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "run.py"), "--workload", workload,
         "--seed", str(2**31 + 7), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal_of_a_test_only_cell_added_as_files(trace, tmp_path):
    """als-tiny.tiny is not in BENCHMARK.json: its configuration and traffic
    files under tests/benchmarks/ are found by name alone."""
    proc = _run("als-tiny.tiny", trace, tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "device", "metrics", "compared"]
    # each number `correct` rests on, beside its limit, and again as stderr's last lines
    for name, (value, holds, limit) in last["compared"].items():
        assert f"run.py: compared {name} = {value} (has to be {holds} {limit})" in proc.stderr
    assert proc.stderr.strip().splitlines()[-1].startswith("run.py: compared ")
    assert last["failed"] == 0 and last["attempted"] == 40
    # on the CPU the batcher pads rows to powers of two, so a burst may meet a
    # row count the warm-up never saw: the run then says so and is not correct
    assert last["correct"] is True or "inside the window" in proc.stderr
    assert last["device"]["platform"] == "cpu"  # never a chip result
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        # host spans and counters are read; a CPU trace has no device plane,
        # so the device metrics are left out and not written as zeros: the one
        # rule of listed.cpu_names, over BENCHMARK.json as it stands
        listed.printed_on_the_cpu_holds(BENCH, "als-tiny.tiny", last["metrics"])
        parts = sum(
            m["value"] for n, m in last["metrics"].items()
            if n in ("post_handoff_ms_per_req", "post_rerank_ms_per_req", "post_render_ms_per_req")
        )
        assert parts <= last["metrics"]["post_ms_per_req"]["value"]
        assert "residue" in proc.stderr
    else:
        assert set(last["metrics"]) == {"p50_ms", "setup_s"}
        for m in last["metrics"].values():
            assert set(m) == {"value", "unit"} and m["value"] > 0
    notes = [json.loads(ln)["info"] for ln in lines[:-1]]
    assert any("peak_bytes_in_use" in n and "compile_cache" in n for n in notes)
    assert any("in_flight_at_window_end" in n and n["generator_processes"] == 1 for n in notes)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_real_cells_refuse_to_run_without_a_tpu(workload, tmp_path):
    proc = _run(workload, 0, tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_alone_with_the_benchmark_files_it_prints_no_result(tmp_path):
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in PATHS:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- `correct` comes out false when the timed path is broken underneath --------------

def _scan_of_the_wrong_rows(monkeypatch):
    """The device scan answers for the negated query: the candidates it hands
    on are each user's WORST items, honestly re-ranked and served."""
    from oryx_tpu.ops import als as ops_als

    sound = ops_als.topk_dot_batch
    monkeypatch.setattr(ops_als, "topk_dot_batch", lambda xs, y, **kw: sound(-xs, y, **kw))


def _scan_in_int8(monkeypatch):
    """The control for the scan: the program's own lower-precision path
    (score-mode quantized: int8 rows and per-row scales) switched on. Its
    answers are the exact ones (the host re-ranks in float32), so it is the
    dispatch records' score mode that fails it."""
    from oryx_tpu.common import config as program_config

    sound = program_config.load_config

    def quantized(*args, overlay=None, **kw):
        return sound(*args, overlay={**(overlay or {}), "oryx.serving.api.score-mode": "quantized"}, **kw)

    monkeypatch.setattr(program_config, "load_config", quantized)


def _scores_in_bfloat16(monkeypatch):
    """The control for the re-rank: the host's exact scores, float32 by the
    configuration's guarantees, computed in the nearest precision below it."""
    import jax.numpy as jnp

    from oryx_tpu.apps.als import serving as als_app

    sound = als_app._rerank_exact

    def low(user_vector, vals, idx, host_mat, cosine):
        vals, idx = sound(user_vector, vals, idx, host_mat, cosine)
        return np.asarray(jnp.asarray(vals, dtype=jnp.bfloat16), dtype=np.float32), idx

    monkeypatch.setattr(als_app, "_rerank_exact", low)


def _scan_fails_over_to_the_host(monkeypatch):
    """Every device dispatch raises: the batcher serves its group exactly on
    the host (`host_topk`), so the answers are the reference's own and only
    the count of requests that never saw the device scan can fail the run."""
    from oryx_tpu.ops import als as ops_als

    def broken(*_args, **_kw):
        raise RuntimeError("the device scan is broken underneath")

    monkeypatch.setattr(ops_als, "topk_dot_batch", broken)


@pytest.mark.parametrize(
    "fault,failing",
    [
        (None, set()),
        (_scan_of_the_wrong_rows, {"least_overlap", "worst_gap_over_slack"}),
        (_scan_in_int8, {"dispatches_not_exact"}),
        (_scores_in_bfloat16, {"worst_score_rel"}),
        (_scan_fails_over_to_the_host, {"host_fallbacks"}),
    ],
    ids=["sound", "scan_of_the_wrong_rows", "scan_in_int8", "scores_in_bfloat16", "scan_fails_over_to_the_host"],
)
def test_a_fault_under_the_timed_path_reads_not_correct(fault, failing, tmp_path, monkeypatch):
    """The kind's whole run in this process (run.py's look for a chip is
    skipped), the program broken underneath: `correct` is false exactly when
    a compared number breaks its limit, and it is the number the fault moves."""
    import time

    if fault:
        fault(monkeypatch)
    cell = {
        "config": json.loads(find(PATHS, "configs/als-tiny.json").read_text()),
        "traffic": json.loads(find(PATHS, "traffic/tiny.json").read_text()),
        "chips": 1, "scratch": str(tmp_path),
    }
    out = als_serving.run(cell, 2**31 + 11, 1.0, False, time.time(), lambda **kv: None)
    holds = {"<=": operator.le, ">=": operator.ge, "==": operator.eq}
    broken = {
        name for name, (value, how, limit) in out["compared"].items()
        if name != "compiles_in_window" and not holds[how](value, limit)
    }
    assert broken == failing
    assert out["failed"] == 0 and out["attempted"] == 20
    # (a compile inside the window, which the CPU's row padding can cause, also reads false)
    assert out["correct"] is (not failing and out["compared"]["compiles_in_window"][0] == 0)
