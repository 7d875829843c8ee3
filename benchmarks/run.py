"""The benchmark's one command:

    python3 benchmarks/run.py --workload <config>.<traffic> --seed N --seconds S --trace 0|1

A cell is a configuration file and a traffic file, found by name as
`configs/<config>.json` and `traffic/<traffic>.json` under the directories
BENCHMARK.json lists in `paths`; the configuration's `kind` names the
module `kinds/<kind>.py` that runs it, and each per-layer metric is read by
`metrics/<name>.py`. Adding any of them is adding a file.

The last line of stdout is the result; earlier lines are notes for a
reader. Without the chips the cell asks for it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
SCRATCH = ROOT / ".bench_out"


def find(paths: list[str], relative: str) -> Path:
    for p in paths:
        candidate = ROOT / p / relative
        if candidate.is_file():
            return candidate
    raise FileNotFoundError(f"{relative} under none of {paths}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(entries: list[dict], workload: str) -> list[dict]:
    return [m for m in entries if workload in m.get("workloads", [workload])]


def info(**kv) -> None:
    print(json.dumps({"info": kv}), flush=True)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    paths = bench["paths"]
    config_name, _, traffic_name = args.workload.rpartition(".")
    config = json.loads(find(paths, f"configs/{config_name}.json").read_text())
    traffic = json.loads(find(paths, f"traffic/{traffic_name}.json").read_text())
    listed = [w for w in bench["workloads"] if w["name"] == args.workload]
    chips = listed[0]["chips"] if listed else 1

    sys.path.insert(0, str(ROOT))
    try:
        import jax

        import oryx_tpu  # noqa: F401 - the benchmark alone is not the system
    except ImportError as e:
        print(f"run.py: cannot import the system: {e}", file=sys.stderr)
        return 2
    # a configuration is for the TPU unless its file says otherwise (only a
    # test-only configuration does); pinned BEFORE first use, so a TPU that
    # fails to initialise is an error and never a quiet start on the CPU
    platform = config.get("platform", "tpu")
    jax.config.update("jax_platforms", platform)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"run.py: JAX found no {platform}: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != platform or len(devices) < chips:
        print(
            f"run.py: {args.workload} needs {chips} {platform} device(s), JAX has "
            f"{len(devices)} of {devices[0].platform}", file=sys.stderr,
        )
        return 2

    from oryx_tpu.parallel.distributed import configure_compilation_cache

    cache_dir = configure_compilation_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    SCRATCH.mkdir(exist_ok=True)
    kind = load_module(find(paths, f"kinds/{config['kind'].replace('-', '_')}.py"))
    cell = {"name": args.workload, "config": config, "traffic": traffic, "chips": chips, "scratch": str(SCRATCH)}
    out = kind.run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS, info)

    stats = [d.memory_stats() or {} for d in devices[:chips]]
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
    }
    info(peak_bytes_in_use=device["memory_peak_bytes"], compile_cache=cache,
         compile_cache_dir=cache_dir)

    values = dict(out["end_to_end"], setup_s=out["setup_s"])
    line = {
        "correct": bool(out["correct"]),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "device": device,
    }
    if args.trace:
        src = out["sources"]
        peaks = json.loads(find(paths, "peaks.json").read_text())
        if platform == "tpu" and device["kind"] not in peaks:
            print(f"run.py: no peaks for device kind {device['kind']!r}", file=sys.stderr)
            return 2
        src["peaks"] = peaks.get(device["kind"])
        metrics = {}
        for m in metrics_of(bench["per_layer"], args.workload):
            value = load_module(find(paths, f"metrics/{m['name']}.py")).read(src)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if src.get("trace"):
            from benchmarks import xplane

            device["busy_s"] = src["trace"]["busy_s"]
            device["window_s"] = src["trace"]["window_s"]
            line["breakdown"] = xplane.breakdown(src["trace"])
    else:
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(bench["end_to_end"], args.workload)
            if values.get(m["name"]) is not None
        }
    line["metrics"] = metrics
    # what `correct` compared, each number beside its limit: the last key of
    # the line and the last lines of stderr
    line["compared"] = out.get("compared", {})
    for name, (value, holds, limit) in line["compared"].items():
        print(f"run.py: compared {name} = {value} (has to be {holds} {limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: daemon threads of the serving layer must not hold the
    # process, and the chip, after the result
    rc = 1
    try:
        rc = main(sys.argv[1:])
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 2
    except BaseException:  # noqa: BLE001 - the boundary: report, then fail
        import traceback

        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
