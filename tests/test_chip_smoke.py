"""chip_smoke.py's contract, as far as a host without a chip can check it:
no TPU means a quick non-zero exit and no result line; the flow itself
passes at tiny size in the EXPLICIT CPU rehearsal, whose output can never
be read as a chip pass. The chip pass itself is `python chip_smoke.py` on
a TPU machine (README "Running")."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env or dict(os.environ),
        capture_output=True, text=True, timeout=timeout,
    )


def test_no_tpu_exits_nonzero_quickly_and_prints_no_result():
    # conftest pins JAX_PLATFORMS=cpu for children; the smoke pins tpu
    # over it, so the missing chip is an error and not a CPU start
    proc = _run([str(SMOKE)], cwd=REPO, timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(["chip_smoke.py"], cwd=tmp_path, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_cpu_rehearsal_passes_and_is_not_a_chip_pass(tmp_path):
    # the whole slice at tiny size: ingest -> batch build + AUC ->
    # update topic -> serving (coalesced burst, agreement with the f32
    # reference) -> speed fold-in; on the conftest's virtual devices the
    # sharded view and trainer run too. The cache goes where the
    # environment says.
    cache = tmp_path / "jax-cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    proc = _run([str(SMOKE), "--rehearse-cpu"], cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the last line is the verdict and nothing else: never a chip pass
    assert verdict == {"ok": False, "device": report["device"]}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    out = report
    assert out["rehearsal_passed"] is True and out["failures"] == []
    # never a chip pass
    assert out["ok"] is False
    assert out["mode"] == "cpu-rehearsal"
    assert out["device"]["platform"] == "cpu"
    # what ran is named, the counters the chip gate asserts on are there
    assert out["topk"]["path"] == "xla" and out["topk"]["dtype"] == "bfloat16"
    c = out["counters"]
    assert c["oryx_topk_dispatches"] > 0
    assert c["oryx_topk_host_fallbacks"] == c["oryx_topk_device_failovers"] == 0
    assert c["oryx_batch_build_failures_total"] == c["oryx_speed_failures_total"] == 0
    assert out["burst"]["dispatches"] < out["burst"]["requests"]  # coalesced
    assert out["agreement"]["users"] >= 16
    assert out["auc"] > 0.75 and out["nan_factor_rows"] == 0
    assert out["compile_cache"] == {
        "dir": str(cache), "from_env": True, "non_empty": True,
        "hits": out["compile_cache"]["hits"],
        "misses": out["compile_cache"]["misses"],
    }
    for phase in ("ingest", "batch", "load", "serve_burst", "agree", "fold"):
        p = out["phases"][phase]
        assert abs(p["compile_s"] + p["run_s"] - p["s"]) < 0.02 or p["run_s"] == 0
    if out["device"]["count"] > 1:
        m = out["multichip"]
        assert len(set(m["shard_devices"])) == out["device"]["count"]
        assert m["sharded_indices_identical"] and m["train_mesh_devices"] > 1
