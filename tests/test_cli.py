"""CLI tier tests (the oryx-run.sh surface): topic setup, stdin input
pump, config overlays via --set, and a real `python -m oryx_tpu.cli
serving` subprocess answering HTTP on a file:// broker."""

import io
import json
import pathlib
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from oryx_tpu import cli
from oryx_tpu.bus.broker import get_broker, topics
from oryx_tpu.bus.inproc import InProcBroker
from oryx_tpu.common.ioutil import choose_free_port


@pytest.fixture(autouse=True)
def _fresh():
    InProcBroker.reset_all()
    yield
    InProcBroker.reset_all()


def test_setup_creates_topics(capsys):
    rc = cli.main(
        [
            "setup",
            "--set", "oryx.input-topic.broker=mem://cli1",
            "--set", "oryx.update-topic.broker=mem://cli1",
        ]
    )
    assert rc == 0
    assert topics.exists("mem://cli1", "OryxInput")
    assert topics.exists("mem://cli1", "OryxUpdate")
    out = capsys.readouterr().out
    assert "OryxInput" in out and "OryxUpdate" in out


def test_set_overlay_parses_json_types():
    args = cli._parse_args(
        ["setup", "--set", "oryx.serving.api.port=123", "--set", "a.b=text"]
    )
    cfg = cli._build_config(args)
    assert cfg.get_int("oryx.serving.api.port") == 123
    assert cfg.get_string("a.b") == "text"
    with pytest.raises(SystemExit):
        cli._build_config(cli._parse_args(["setup", "--set", "novalue"]))


def test_input_pumps_stdin(monkeypatch):
    cli.main(
        ["setup", "--set", "oryx.input-topic.broker=mem://cli2",
         "--set", "oryx.update-topic.broker=mem://cli2"]
    )
    monkeypatch.setattr(sys, "stdin", io.StringIO("line one\nline two\n\n"))
    rc = cli.main(
        ["input", "--set", "oryx.input-topic.broker=mem://cli2",
         "--set", "oryx.update-topic.broker=mem://cli2"]
    )
    assert rc == 0
    broker = get_broker("mem://cli2")
    msgs: set[str] = set()
    for p in range(broker.num_partitions("OryxInput")):
        msgs |= {m for _, _, m in broker.read("OryxInput", p, 0, 10)}
    assert {"line one", "line two"} <= msgs


def _http(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_serving_subprocess_round_trip(tmp_path):
    port = choose_free_port()
    bus = f"file://{tmp_path}/bus"
    sets = [
        f"oryx.input-topic.broker={bus}",
        f"oryx.update-topic.broker={bus}",
        f"oryx.serving.api.port={port}",
        "oryx.serving.model-manager-class="
        "oryx_tpu.apps.example.serving.ExampleServingModelManager",
        'oryx.serving.application-resources='
        '["oryx_tpu.serving.resources.common","oryx_tpu.serving.resources.example"]',
    ]
    flags = [x for s in sets for x in ("--set", s)]
    assert cli.main(["setup", *flags]) == 0
    get_broker(bus).send("OryxUpdate", "MODEL", json.dumps({"cat": 2}))
    proc = subprocess.Popen(
        [sys.executable, "-m", "oryx_tpu.cli", "serving", *flags],
        cwd=str(pathlib.Path(__file__).resolve().parent.parent),
        stdout=subprocess.DEVNULL,
        stderr=subprocess.PIPE,
    )
    try:
        base = f"http://127.0.0.1:{port}"
        deadline = time.time() + 30
        status = None
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(proc.stderr.read().decode()[-2000:])
            try:
                status, body = _http(f"{base}/distinct/cat")
                if status == 200:
                    break
            except Exception:
                pass
            time.sleep(0.2)
        assert status == 200 and json.loads(body) == 2
    finally:
        proc.terminate()
        try:
            # the round trip is the subject, not how fast a loaded host
            # lets the child unwind: 10 s timed out under six workers
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
            raise


def test_sigterm_during_start_unwinds_before_close():
    """SIGTERM that finds the main thread inside start() must not run
    close() there: the serving frontend answers from its first event loop
    while start() is still bringing up the others, and a close() in the
    middle left those running, so the process never exited (one run of
    the round trip above in twenty under load)."""
    import os
    import signal
    import threading

    if threading.current_thread() is not threading.main_thread():
        pytest.skip("signal handlers belong to the main thread")

    class Layer:
        def __init__(self):
            self.in_start = False
            self.closed_inside_start = []

        def start(self):
            self.in_start = True
            try:
                os.kill(os.getpid(), signal.SIGTERM)
                time.sleep(0.2)  # the handler runs here, on this thread
            finally:
                self.in_start = False

        def await_termination(self):
            raise AssertionError("start() was not unwound")

        def close(self):
            self.closed_inside_start.append(self.in_start)

    before = signal.getsignal(signal.SIGTERM)
    layer = Layer()
    assert cli._run_until_interrupt(layer) == 0
    assert layer.closed_inside_start == [False]
    assert signal.getsignal(signal.SIGTERM) is before


def test_loadtest_command(tmp_path):
    """The loadtest subcommand replays paths against a live serving layer
    and reports qps + latency percentiles as one JSON line."""
    import io
    import json as _json
    from contextlib import redirect_stdout

    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.cli import main as cli_main
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    bus = "mem://clilt"
    broker = get_broker(bus)
    for t in ("OryxInput", "OryxUpdate"):
        if not broker.topic_exists(t):
            broker.create_topic(t, 1)
    broker.send("OryxUpdate", "MODEL", _json.dumps({"word": 7}))
    cfg = load_config(overlay={
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.serving.model-manager-class": "oryx_tpu.apps.example.serving.ExampleServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.example",
        ],
    })
    paths = tmp_path / "paths.txt"
    paths.write_text("/distinct/word\n/ready\n")
    with ServingLayer(cfg) as sl:
        time.sleep(0.3)
        out = io.StringIO()
        with redirect_stdout(out):
            rc = cli_main([
                "loadtest",
                "--url", f"http://127.0.0.1:{sl.port}",
                "--paths", str(paths),
                "--rate", "200",
                "--duration", "2",
                "--workers", "4",
            ])
    assert rc == 0
    report = _json.loads(out.getvalue().strip().splitlines()[-1])
    assert report["errors"] == 0
    assert report["requests"] > 100  # ~400 scheduled at 200 rps x 2s
    assert report["latency_ms"]["p50"] > 0
    # pacing must not EXCEED the target (a loaded host may undershoot)
    assert report["qps"] <= 260


def test_loadtest_http2(tmp_path):
    """loadtest --http2 drives the serving layer over HTTP/2 prior
    knowledge using the in-repo HPACK/frame client."""
    import io
    import json as _json
    from contextlib import redirect_stdout

    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.cli import main as cli_main
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    bus = "mem://clilt2"
    broker = get_broker(bus)
    for t in ("OryxInput", "OryxUpdate"):
        if not broker.topic_exists(t):
            broker.create_topic(t, 1)
    broker.send("OryxUpdate", "MODEL", _json.dumps({"word": 7}))
    cfg = load_config(overlay={
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.serving.model-manager-class": "oryx_tpu.apps.example.serving.ExampleServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.example",
        ],
    })
    paths = tmp_path / "paths.txt"
    paths.write_text("/distinct\n/ready\n")
    with ServingLayer(cfg) as sl:
        time.sleep(0.3)
        out = io.StringIO()
        with redirect_stdout(out):
            rc = cli_main([
                "loadtest", "--http2",
                "--url", f"http://127.0.0.1:{sl.port}",
                "--paths", str(paths),
                "--duration", "2",
                "--workers", "4",
            ])
    assert rc == 0
    report = _json.loads(out.getvalue().strip().splitlines()[-1])
    assert report["errors"] == 0
    assert report["requests"] > 20
    assert report["latency_ms"]["p50"] > 0


def test_loadtest_multiloop_smoke(tmp_path):
    """Tier-1 frontend-throughput smoke: an unpaced ~2s loadtest against
    an in-process MULTI-LOOP server must push real traffic with zero
    errors, and the report's post-run /metrics scrape must show more than
    one event loop carrying it — a cheap canary so a frontend that
    stopped fanning out fails here, without the chip."""
    import io
    import json as _json
    from contextlib import redirect_stdout

    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.cli import main as cli_main
    from oryx_tpu.common.config import load_config
    from oryx_tpu.serving.server import ServingLayer

    bus = "mem://cliltml"
    broker = get_broker(bus)
    for t in ("OryxInput", "OryxUpdate"):
        if not broker.topic_exists(t):
            broker.create_topic(t, 1)
    broker.send("OryxUpdate", "MODEL", _json.dumps({"word": 7}))
    cfg = load_config(overlay={
        "oryx.input-topic.broker": bus,
        "oryx.update-topic.broker": bus,
        "oryx.serving.api.port": 0,
        "oryx.serving.api.loops": 4,
        "oryx.serving.model-manager-class": "oryx_tpu.apps.example.serving.ExampleServingModelManager",
        "oryx.serving.application-resources": [
            "oryx_tpu.serving.resources.common",
            "oryx_tpu.serving.resources.example",
        ],
    })
    paths = tmp_path / "paths.txt"
    paths.write_text("/distinct/word\n/ready\n")
    with ServingLayer(cfg) as sl:
        time.sleep(0.3)
        out = io.StringIO()
        with redirect_stdout(out):
            rc = cli_main([
                "loadtest",
                "--url", f"http://127.0.0.1:{sl.port}",
                "--paths", str(paths),
                "--duration", "2",
                "--workers", "8",
            ])
    assert rc == 0
    report = _json.loads(out.getvalue().strip().splitlines()[-1])
    assert report["errors"] == 0
    # unpaced on loopback: anything below this floor is a real frontend
    # regression, not CI noise (the in-process client shares the GIL with
    # the server, so the floor is far below the external-client ceiling)
    assert report["requests"] > 150, report
    srv = report.get("server")
    assert srv is not None, "loadtest never scraped the server's /metrics"
    assert srv["loops"] == 4
    assert srv["loops_serving"] >= 2, srv


def test_serving_replicas_share_port(tmp_path):
    """oryx.serving.api.processes=2: the CLI supervises two full serving
    replicas on ONE port via SO_REUSEPORT over a file:// broker; requests
    succeed under concurrency, and a killed replica is restarted."""
    import json as _json
    import os
    import signal as _signal
    import subprocess
    import urllib.request

    import pytest as _pytest

    if not hasattr(__import__("socket"), "SO_REUSEPORT"):
        _pytest.skip("no SO_REUSEPORT on this platform")

    from oryx_tpu.bus.broker import get_broker
    from oryx_tpu.common.ioutil import choose_free_port

    bus = f"file://{tmp_path}/bus"
    b = get_broker(bus)
    b.create_topic("OryxInput", 1)
    b.create_topic("OryxUpdate", 1)
    b.send("OryxUpdate", "MODEL", _json.dumps({"replica": 7}))
    port = choose_free_port()
    conf = tmp_path / "oryx.conf"
    conf.write_text(f'''
oryx.id = replicas
oryx.input-topic.broker = "{bus}"
oryx.update-topic.broker = "{bus}"
oryx.serving.api.port = {port}
oryx.serving.api.processes = 2
oryx.serving.model-manager-class = "oryx_tpu.apps.example.serving.ExampleServingModelManager"
oryx.serving.application-resources = ["oryx_tpu.serving.resources.common", "oryx_tpu.serving.resources.example"]
''')
    root = pathlib.Path(__file__).resolve().parent.parent
    from oryx_tpu.common.executil import cpu_subprocess_env

    env = cpu_subprocess_env(PYTHONPATH=str(root))
    sup = subprocess.Popen(
        [sys.executable, "-m", "oryx_tpu.cli", "serving", "--conf", str(conf)],
        cwd=str(root),
        env=env,
        # DEVNULL, not PIPE: three chatty processes share this fd and an
        # undrained pipe buffer would block them mid-test
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        deadline = time.time() + 60
        ok = False
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/distinct/replica", timeout=2
                ) as r:
                    if r.status == 200 and _json.loads(r.read()) == 7:
                        ok = True
                        break
            except Exception:
                pass
            time.sleep(0.3)
        assert ok, "replicas never became ready"

        def children():
            out = subprocess.run(
                ["pgrep", "-P", str(sup.pid)], capture_output=True, text=True
            ).stdout.split()
            return [int(x) for x in out]

        kids = children()
        assert len(kids) == 2, kids

        # kill one replica; requests keep succeeding and it is restarted.
        # The deadline must DOMINATE the supervisor's worst-case restart
        # backoff (30s cap) plus single-core starvation under full-suite
        # load — a 30s fixed window raced it and flaked (round-3 verdict)
        os.kill(kids[0], _signal.SIGKILL)
        deadline = time.time() + 120
        kids_now: list[int] = []
        while time.time() < deadline:
            kids_now = children()  # single snapshot per iteration: two
            # separate calls can straddle a respawn and disagree
            if len(kids_now) == 2 and kids[0] not in kids_now:
                break
            time.sleep(0.3)
        assert len(kids_now) == 2 and kids[0] not in kids_now, (
            f"dead replica was not restarted: {kids_now}"
        )
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/distinct/replica", timeout=5
        ) as r:
            assert r.status == 200
    finally:
        sup.terminate()
        try:
            sup.wait(timeout=30)
        except subprocess.TimeoutExpired:
            sup.kill()


def test_config_subcommand_flattens_effective_config(capsys):
    """`cli config` prints sorted key=value lines of the EFFECTIVE config
    (the reference's ConfigToProperties shell surface)."""
    from oryx_tpu.cli import cmd_config
    from oryx_tpu.common.config import load_config

    rc = cmd_config(load_config(overlay={"oryx.id": "cfgtest",
                                         "oryx.serving.api.port": 1234}))
    assert rc == 0
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    assert "oryx.id=cfgtest" in lines
    assert "oryx.serving.api.port=1234" in lines
    assert "oryx.monitoring.metrics=true" in lines  # booleans lowercase
    assert lines == sorted(lines)


def test_apply_platform_env_prefers_env_over_config(monkeypatch):
    """oryx.compute.platform steers jax when set (not "auto"); an explicit
    JAX_PLATFORMS env var wins as the operator override."""
    import jax

    from oryx_tpu.cli import _apply_platform_env
    from oryx_tpu.common.config import load_config

    before = jax.config.jax_platforms
    try:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        _apply_platform_env(load_config(overlay={"oryx.compute.platform": "cpu"}))
        assert jax.config.jax_platforms == "cpu"
        # "auto" leaves whatever is configured alone
        jax.config.update("jax_platforms", "cpu")
        _apply_platform_env(load_config(overlay={"oryx.compute.platform": "auto"}))
        assert jax.config.jax_platforms == "cpu"
        # env var beats config
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        _apply_platform_env(load_config(overlay={"oryx.compute.platform": "tpu"}))
        assert jax.config.jax_platforms == "cpu"
    finally:
        jax.config.update("jax_platforms", before)


def test_app_flag_wires_registry_classes(capsys):
    """`--app seq` overlays the app registry's class/resource wiring
    (apps/spi.py) under the effective config — visible through the
    `config` subcommand like any other override."""
    rc = cli.main(["config", "--app", "seq"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "oryx.batch.update-class=oryx_tpu.apps.seq.batch.SeqUpdate" in out
    assert (
        "oryx.speed.model-manager-class="
        "oryx_tpu.apps.seq.speed.SeqSpeedModelManager" in out
    )
    assert (
        "oryx.serving.model-manager-class="
        "oryx_tpu.apps.seq.serving.SeqServingModelManager" in out
    )
    assert "oryx_tpu.serving.resources.seq" in out


def test_app_flag_explicit_set_still_wins(capsys):
    """An explicit --set outranks the app overlay (sugar must never
    shadow an operator's deliberate override)."""
    rc = cli.main([
        "config", "--app", "als",
        "--set", "oryx.batch.update-class=custom.Update",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "oryx.batch.update-class=custom.Update" in out
    # the rest of the app wiring still applies
    assert (
        "oryx.serving.model-manager-class="
        "oryx_tpu.apps.als.serving.ALSServingModelManager" in out
    )


def test_app_flag_unknown_app_fails_fast():
    with pytest.raises(SystemExit):
        cli.main(["config", "--app", "nosuchapp"])


def test_app_flag_survives_child_argv_rebuild():
    """fleet/pod child rebuilds keep --app (it is a value opt, so the
    subcommand detection must not eat its value either)."""
    raw = ["fleet", "--app", "seq", "--replicas", "2", "--conf", "x.conf"]
    child = cli._fleet_child_flags(raw)
    assert "--app" in child and child[child.index("--app") + 1] == "seq"
    assert "--replicas" not in child
    raw2 = ["--app", "seq", "pod", "--compute", "2"]
    child2 = cli._pod_child_flags(raw2)
    assert "--app" in child2 and child2[child2.index("--app") + 1] == "seq"
    assert "pod" not in child2
