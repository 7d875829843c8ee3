"""What the tests of tests/benchmarks/ hold `BENCHMARK.json`'s lists to, each
as a function of the parsed file, so that the tests over the file as it is
and the test over the file as the NEXT PR will leave it (a configuration, a
cell and a metric appended at the ends of their lists:
`test_benchmark.py::test_appended_entries_break_no_assertion_over_the_lists`)
call the same code. An entry is held to be PRESENT, ONCE, and a PR's entries
IN ORDER and CONTIGUOUS; never last, never counted from the end.

A path in `paths` may be absolute (a test's tmp_path laid beside the repo's
own two): `ROOT / "/abs"` is `/abs`, so `benchmarks.run.find` looks there too.
"""

import json
import re
from pathlib import Path

from benchmarks.run import find, metrics_of

REPO = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# Which per-layer metrics a TRACED run on the CPU prints, as ONE rule over
# BENCHMARK.json (the four CPU rehearsals call `cpu_names`): those that
# `metrics_of` gives the cell and whose source is not the device trace (a CPU
# trace has no device plane; its host spans and the counters are read), less
# - NOT_ON_THE_CPU: the CPU serves the scan by XLA, never by the fused kernel,
#   so `oryx_topk_chunks` stays 0 and the reader has nothing to read;
# - MAY_BE_ABSENT: a two-second window of 20-40 requests may see no
#   collection start, and the reader then returns nothing.
NOT_ON_THE_CPU = {"topk_fold_share"}
MAY_BE_ABSENT = {"gc_pause_share"}
# printed values are above 0 but these, by name: 0.0 is what a window without
# a heartbeat 100 ms overdue reads
MAY_READ_ZERO = {"stall_share"}


# The metrics of the stepper's thread: each lists the encoder cells, and a new
# encoder cell appends its name to these lists.
STEPPER = {
    "stepper_idle_share", "stepper_host_ms_per_cycle", "stepper_offcpu_share", "stepper_call_ms", "encode_wait_ms_per_req",
}

# What each `model_config` PR added, as `entries_of` takes it: (configuration,
# cell, which per-layer names are its own, how many).
ADDED = [
    ("sdar-30b-a3b-6l", "sdar-30b-a3b-6l.basket4", {
        "encode_ms_per_req", "seq_step_ms", "step_mfu", "moe_roofline", "step_tokens", "step_pad_share", "moe_load_peak",
    }.__contains__, 7),
    ("jamba2-3b", "jamba2-3b.next4", lambda name: name.startswith("ssm_"), 8),
    ("joyai-flash-5l", "joyai-flash-5l.next4moe", lambda name: name.startswith(("joyai_", "mla_")), 11),
]


def cpu_names(bench: dict, workload: str) -> tuple[set, set]:
    """(the names a traced CPU run of `workload` has to print, those it may
    print besides)."""
    listed = {
        m["name"] for m in metrics_of(bench["per_layer"], workload) if m["source"] != "device_trace"
    } - NOT_ON_THE_CPU
    return listed - MAY_BE_ABSENT, listed & MAY_BE_ABSENT


def printed_on_the_cpu_holds(bench: dict, workload: str, metrics: dict) -> None:
    """`metrics` is the `metrics` of a traced CPU run's result line."""
    must, may = cpu_names(bench, workload)
    assert must <= set(metrics) <= must | may, (sorted(must - set(metrics)), sorted(set(metrics) - must - may))
    for name, m in metrics.items():
        assert set(m) == {"value", "unit"}
        assert m["value"] >= 0 if name in MAY_READ_ZERO else m["value"] > 0, (name, m)


def structure_holds(bench: dict) -> None:
    """BENCHMARK.json resolves to files, and every metric moves what the
    cells that report it report."""
    paths = bench["paths"]
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    configs = {c["name"]: c for c in bench["configs"]}
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        on_disk = json.loads((REPO / c["file"]).read_text())
        assert find(paths, f"kinds/{on_disk['kind'].replace('-', '_')}.py").is_file()
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        assert not [k for k in c["reduced"] if k.endswith(("_dim", "_rank")) or k == "features"]
    assert len(bench["workloads"]) == len({(w["config"], w["traffic"]) for w in bench["workloads"]})
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}" and w["config"] in configs
        assert find(paths, f"configs/{w['config']}.json") == REPO / configs[w["config"]]["file"]
        assert find(paths, f"traffic/{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        reported = {m["name"] for m in metrics_of(bench["end_to_end"], w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layer = metrics_of(bench["per_layer"], w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in reported, (m["name"], w["name"])
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert find(paths, f"metrics/{m['name']}.py").is_file()
        assert find(paths, f"cases/{m['name']}.json").is_file()
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert set(m.get("workloads", [])) <= cells, m
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1


def names_hold(bench: dict) -> None:
    """Names, units and file names use only the allowed characters, and no
    two entries of a list share a name."""
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer") for x in bench[key]]
    names += [w["traffic"] for w in bench["workloads"]]
    for key in ("configs", "workloads"):
        group = [x["name"] for x in bench[key]]
        assert len(group) == len(set(group))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for p in bench["paths"]:
        base = REPO / p
        for f in base.rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(f.relative_to(base))), f
    assert len(json.dumps(bench, indent=1)) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51


def entries_of(bench: dict, config: str, cell: str, own, count: int) -> tuple[dict, dict, list]:
    """(the configuration's entry, the cell's, the per-layer entries `own`
    picks by name) of one PR's additions: each there once; the `count`
    metrics list the cell alone, move `p50_ms` and stand together in the
    order they were added, wherever in `per_layer` that is."""
    entries = [c for c in bench["configs"] if c["name"] == config]
    cells = [w for w in bench["workloads"] if w["name"] == cell]
    assert len(entries) == 1 and len(cells) == 1
    assert cells[0]["config"] == config and cells[0]["chips"] == 1 and len(cells[0]["why"]) <= 200
    at = [i for i, m in enumerate(bench["per_layer"]) if own(m["name"])]
    mine = [bench["per_layer"][i] for i in at]
    assert len(mine) == count and at == list(range(at[0], at[0] + count)), at
    assert all(m["workloads"] == [cell] and m["moves"] == "p50_ms" for m in mine)
    return entries[0], cells[0], mine
