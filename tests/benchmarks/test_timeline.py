"""benchmarks/timeline.py and its seven readers (ISSUE 25): the join on a
hand-built timeline, the identities the readers print, and the reduction on
recorded annotated v5e traces. Each reader's own value is held by its case
file (tests/benchmarks/cases/, test_benchmark.py), and the CPU rehearsal
there prints the five a CPU trace yields. No chip: nothing here is a device
number."""

import json
from pathlib import Path

import pytest

from benchmarks import timeline
from benchmarks.run import find, load_module

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
PATHS = BENCH["paths"]
RECORDED = HERE / "data" / "steady128-annotated-5s.xplane.pb.gz"
TWO_STATES = HERE / "data" / "steady128-two-states-5s.xplane.pb.gz"
DEVICE_SIDE = {"device_queue_ms", "fetch_tail_ms"}
HOST_SIDE = {
    "launch_host_ms", "distribute_ms", "post_handoff_ms_per_req",
    "post_rerank_ms_per_req", "post_render_ms_per_req",
}
MS = 1e6  # the timeline is in ns


def _ev(start_ms, end_ms, **stats):
    return dict(stats, start=start_ms * MS, end=end_ms * MS, line=1)


def _built(late_ms=1000.0):
    """Five dispatches around a 990 ms kernel. 0: its kernel and fetch are
    in the window, its launch is not (a kernel with no issue). 1: whole.
    2: whole, and its fetch returns `late_ms` after its results landed: by
    default after the NEXT scan ended too, so two kernels end between two
    fetches' ends. 3: whole, fetched at once after 2. 4: launched, still
    in flight when the window closes (an issue with no kernel)."""
    late = 3003 + late_ms
    then = max(late + 6, 4003)  # 3's fetch ends: after 2's, and after 3's own scan
    return {
        "regions": {
            "batcher.launch": [
                _ev(100, 112, dispatch=1, rows=112, padded=512, k_bucket=128),
                _ev(1020, 1030, dispatch=2, rows=110, padded=512, k_bucket=128),
                _ev(2020, 2032, dispatch=3, rows=113, padded=512, k_bucket=128),
                _ev(then + 11, then + 21, dispatch=4, rows=111, padded=512, k_bucket=128),
            ],
            "batcher.issue": [
                _ev(104, 110), _ev(1024, 1029), _ev(2024, 2031), _ev(then + 15, then + 20),
            ],
            "batcher.fetch": [
                _ev(20, 1002, dispatch=0), _ev(1006, 2003, dispatch=1),
                _ev(2033, late, dispatch=2), _ev(late + 3, then, dispatch=3),
            ],
            "batcher.distribute": [
                _ev(1002, 1005, dispatch=0), _ev(2003, 2007, dispatch=1),
                _ev(late, late + 3, dispatch=2), _ev(then, then + 3, dispatch=3),
            ],
        },
        "kernels": [
            {"start": 10 * MS, "end": 1000 * MS}, {"start": 1010 * MS, "end": 2000 * MS},
            {"start": 2010 * MS, "end": 3000 * MS}, {"start": 3010 * MS, "end": 4000 * MS},
        ],
    }


def _reader(name):
    return load_module(find(PATHS, f"metrics/{name}.py")).read


# -- the join -------------------------------------------------------------------

def test_join_pairs_by_order_so_a_late_fetch_keeps_its_own_kernel():
    joined = timeline.join(_built())
    assert [d["dispatch"] for d in joined["dispatches"]] == [1, 2, 3]
    assert joined["left_out"] == 2  # 0 has no issue, 4 no fetch and no kernel
    one, two, three = joined["dispatches"]
    assert (one["kernel"]["start"], one["kernel"]["end"]) == (1010 * MS, 2000 * MS)
    assert (one["issue"]["start"], one["issue"]["end"]) == (104 * MS, 110 * MS)
    assert one["launch"]["rows"] == 112 and one["fetch"]["end"] == 2003 * MS
    # two kernels ended before 2's fetch did: it keeps the earlier one, its
    # own, and 3 is not counted as left out for it
    assert two["fetch"]["end"] == 4003 * MS
    assert (two["kernel"]["start"], two["kernel"]["end"]) == (2010 * MS, 3000 * MS)
    assert (three["kernel"]["start"], three["kernel"]["end"]) == (3010 * MS, 4000 * MS)
    for d in joined["dispatches"]:
        assert d["issue"]["end"] <= d["kernel"]["start"] < d["kernel"]["end"] <= d["fetch"]["end"]


def test_on_a_tie_the_time_match_is_the_earlier_kernel():
    tl = _built()
    for name in ("batcher.fetch", "batcher.distribute"):
        del tl["regions"][name][:2]  # one fetch in time, one late: one vote each
    joined = timeline.join(tl)
    assert [d["dispatch"] for d in joined["dispatches"]] == [2, 3]
    assert joined["dispatches"][0]["kernel"]["end"] == 3000 * MS


def test_a_pair_out_of_order_is_left_out_and_counted():
    tl = _built(late_ms=0.0)
    del tl["kernels"][1]  # dispatch 1's scan was not recorded
    joined = timeline.join(tl)
    # by order 1 would take 0's kernel, which started before 1 was issued
    assert [d["dispatch"] for d in joined["dispatches"]] == [2, 3]
    assert joined["left_out"] == 3
    tl = _built(late_ms=0.0)
    tl["regions"]["batcher.fetch"][2]["end"] = 2990 * MS  # ends before its kernel does
    assert [d["dispatch"] for d in timeline.join(tl)["dispatches"]] == [1, 3]
    assert timeline.join(dict(_built(), kernels=[]))["dispatches"] == []


@pytest.mark.parametrize("late_ms,joins", [(0.5, True), (2.0, False)])
def test_the_join_allows_the_two_clocks_a_small_skew(late_ms, joins):
    tl = _built()
    tl["kernels"][1]["end"] = (2003 + late_ms) * MS  # after the fetch's end, by the planes' clocks
    numbers = [d["dispatch"] for d in timeline.join(tl)["dispatches"]]
    assert (1 in numbers) is joins and {2, 3} <= set(numbers)
    assert timeline.SKEW_NS == 1e6


def test_without_a_device_plane_there_is_no_join():
    tl = dict(_built(), kernels=None)
    assert timeline.join(tl) is None
    assert timeline.joined_ms({"timeline": tl}, lambda d: 1.0) is None


def test_a_region_inside_a_launch_is_found_on_the_same_thread_only():
    launch = _ev(100, 112)
    other_thread = dict(_ev(104, 110), line=2)
    assert timeline._inside([_ev(104, 110), other_thread], launch) == _ev(104, 110)
    assert timeline._inside([other_thread], launch) is None
    assert timeline._inside([_ev(104, 106), _ev(107, 110)], launch) is None  # two: not one


# -- the identities the readers print, and a CPU run ----------------------------------

def _case(name):
    return json.loads(find(PATHS, f"cases/{name}.json").read_text())


def test_the_readers_print_the_identities_and_a_cpu_trace_yields_the_host_side(capsys):
    """The cases of the seven hold `_built()` and the counters beside it."""
    src = _case("device_queue_ms")["src"]
    assert src["timeline"] == _built()
    for name in DEVICE_SIDE | HOST_SIDE:
        assert _reader(name)(src) == pytest.approx(_case(name)["expect"], rel=1e-9), name
    err = capsys.readouterr().err
    # issue 6 + queue 953.333 + kernel 990 (the joined dispatches' own) + tail 338.333
    # against the device phase's 2,000 ms; and 80 + 5 + 2 of serialize's 90
    assert "3 dispatches joined, 2 left out" in err
    assert "= 2287.667 ms; device phase per request 2000.000 ms, ratio 1.1438" in err
    assert "= 87.000 ms of post_ms_per_req 90.000; residue 3.000 ms" in err
    # a CPU run: host annotations and counters, no device plane
    cpu = dict(src, timeline=dict(_built(), kernels=None))
    for name in DEVICE_SIDE:
        assert _reader(name)(cpu) is None, name
    for name in HOST_SIDE:
        assert _reader(name)(cpu) == pytest.approx(_case(name)["expect"]), name


def test_a_program_without_the_regions_and_the_family_gives_nothing_and_does_not_raise():
    """The parent of PR 25 under these files: kernels, no batcher.* event,
    no oryx_post_stage_seconds."""
    parent = {
        "counters": {'oryx_request_phase_seconds_count{phase="device"}': 100.0},
        "trace": {"ops": {}, "window_s": 1.0, "busy_s": 1.0, "idle_gaps": []},
        "timeline": {"regions": {}, "kernels": _built()["kernels"]},
    }
    for name in DEVICE_SIDE | HOST_SIDE | {"topk_fold_share"}:
        assert _reader(name)(parent) is None, name


# -- the timeline a reader is handed ------------------------------------------------------

def test_the_readers_take_the_timeline_from_src_and_never_from_the_disk():
    for src in ({}, {"trace": None, "timeline": None}):  # untraced; traced, no xplane found
        assert timeline.region_ms(src, "batcher.launch") is None
        assert timeline.joined_of(src) is None
    src = {"trace": None, "timeline": _built()}
    assert timeline.region_ms(src, "batcher.launch") == pytest.approx(11.0)
    assert len(timeline.joined_of(src)["dispatches"]) == 3
    assert not hasattr(timeline, "TRACE_DIR")


# -- the reduction on a recorded annotated trace --------------------------------------------

def test_timeline_of_a_recorded_annotated_v5e_trace():
    """A 5 s traced run of als-5m-250f.steady128 on one v5e with the regions
    (my chip run, PR 25; recorded before the review took `batcher.form` out,
    so it holds that region too, which nothing reads)."""
    tl = timeline.parse(RECORDED)
    assert len(tl["kernels"]) >= 3
    assert {"batcher.launch", "batcher.issue", "batcher.fetch", "batcher.distribute"} <= set(
        tl["regions"]
    )
    joined = timeline.join(tl)
    assert len(joined["dispatches"]) >= 2 and joined["left_out"] <= 3
    src = {"timeline": tl}
    kernel_ms = timeline.joined_ms(src, lambda d: d["kernel"]["end"] - d["kernel"]["start"])
    assert 1000.0 < kernel_ms < 1300.0  # the 512-row scan of 6.29M rows
    # one scan is queued ahead: a dispatch waits about one kernel time for the device
    assert 0.8 < _reader("device_queue_ms")(src) / kernel_ms < 1.1
    assert 0.0 < _reader("fetch_tail_ms")(src) < 20.0
    assert 0.0 < _reader("launch_host_ms")(src) < 200.0
    assert 0.0 < _reader("distribute_ms")(src) < 200.0
    for d in joined["dispatches"]:
        assert d["launch"]["padded"] == 512 and d["launch"]["k_bucket"] == 128
        assert d["issue"]["end"] <= d["kernel"]["start"] < d["kernel"]["end"]
        assert d["kernel"]["end"] <= d["fetch"]["end"] + timeline.SKEW_NS


def test_a_recorded_trace_of_the_pipeline_leaving_its_idle_state():
    """5 s of the same cell (my chip run, PR 25, review round) caught with no
    scan queued ahead: dispatches 8 and 9 meet an idle device, 10 is the one
    request that arrived during 9's launch and is queued behind it, and from
    there every dispatch waits one kernel time. `device_queue_ms` is what
    tells the two states apart; the kernel's time is the same in both."""
    joined = timeline.join(timeline.parse(TWO_STATES))
    assert [d["dispatch"] for d in joined["dispatches"]] == [8, 9, 10, 11]
    assert joined["left_out"] == 2
    queue = [(d["kernel"]["start"] - d["issue"]["end"]) / MS for d in joined["dispatches"]]
    kernel = [(d["kernel"]["end"] - d["kernel"]["start"]) / MS for d in joined["dispatches"]]
    assert all(1114.0 < ms < 1116.0 for ms in kernel)
    assert all(0.0 < ms < 20.0 for ms in queue[:2])  # the upload and the lane pad, no scan ahead
    assert [d["launch"]["rows"] for d in joined["dispatches"]][2] == 1
    assert all(0.95 < ms / 1114.8 < 1.05 for ms in queue[2:])
    src = {"timeline": timeline.parse(TWO_STATES)}
    assert _reader("device_queue_ms")(src) == pytest.approx(sum(queue) / 4)
    assert 0.0 < _reader("fetch_tail_ms")(src) < 5.0
