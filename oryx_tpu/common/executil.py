"""Parallel task fan-out helpers.

Mirrors the reference's ExecUtils (framework/oryx-common
.../lang/ExecUtils.java:32-75): run N tasks at parallelism P, optionally on a
private pool, collecting results. Used by the ML harness to build and
evaluate hyperparameter candidates concurrently (MLUpdate.java:253-258).
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Sequence, TypeVar

log = logging.getLogger(__name__)

T = TypeVar("T")


def do_in_parallel(
    num_tasks: int,
    task: Callable[[int], None],
    parallelism: int | None = None,
) -> None:
    collect_in_parallel(num_tasks, task, parallelism)


def collect_in_parallel(
    num_tasks: int,
    task: Callable[[int], T],
    parallelism: int | None = None,
) -> list[T]:
    """Run task(0..num_tasks-1), at most `parallelism` at a time, returning
    results in index order. parallelism<=1 runs inline (no pool), which
    matters on TPU where concurrent jitted builds would contend for the
    device — the harness defaults to sequential candidate builds."""
    if num_tasks <= 0:
        return []
    parallelism = min(parallelism or 1, num_tasks)
    if parallelism <= 1:
        return [task(i) for i in range(num_tasks)]
    with ThreadPoolExecutor(max_workers=parallelism) as pool:
        return list(pool.map(task, range(num_tasks)))


def map_in_parallel(items: Sequence[T], fn: Callable[[T], "T"], parallelism: int = 4) -> list:
    return collect_in_parallel(len(items), lambda i: fn(items[i]), parallelism)


class LoggingRunnable:
    """Wrap a callable so exceptions are logged, not swallowed by executor
    futures (reference LoggingCallable)."""

    def __init__(self, fn: Callable[[], None], name: str = "task"):
        self.fn = fn
        self.name = name

    def __call__(self) -> None:
        try:
            self.fn()
        except Exception:  # noqa: BLE001 - must log whatever escapes a thread
            log.exception("unexpected error in %s", self.name)
            raise


def free_port_run(n: int, host: str = "127.0.0.1", attempts: int = 50) -> int:
    """Base of a run of ``n`` consecutive free TCP ports on ``host`` —
    the shape a fleet supervisor's ``base-port + i`` layout needs. All
    ``n`` ports are held bound while probing so the run is free at the
    moment of return (the usual bind race remains: the caller must bind
    soon after)."""
    import socket

    for _ in range(attempts):
        socks: list[socket.socket] = []
        try:
            s = socket.socket()
            s.bind((host, 0))
            base = s.getsockname()[1]
            socks.append(s)
            for i in range(1, n):
                si = socket.socket()
                si.bind((host, base + i))
                socks.append(si)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError(f"no run of {n} free ports found on {host}")


def config_overlay_from_sets(pairs) -> dict:
    """``key=value`` strings (the CLI's ``--set`` grammar) as a config
    overlay dict: values parse as JSON where possible (numbers, bools,
    lists) and fall back to raw strings — exactly how cli.py applies
    ``--set``, shared here so harnesses building a Config AND a child
    argv from one list of sets cannot drift from the CLI's coercion."""
    import json

    out: dict = {}
    for s in pairs:
        k, v = s.split("=", 1)
        try:
            out[k] = json.loads(v)
        except json.JSONDecodeError:
            out[k] = v
    return out


def _count_tpu_chips(env: dict) -> int:
    """TPU chips on this host, counted by a short-lived child: the caller
    is a launcher that must stay off JAX itself (a parent that has
    touched JAX holds the chip against its own children). The child
    exits — and lets go of the chips — before any real child starts."""
    import subprocess
    import sys

    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import jax; print(sum(d.platform == 'tpu' "
            "for d in jax.local_devices()))",
        ],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            "could not count this host's accelerator chips (JAX failed to "
            f"start in a probe process):\n{proc.stderr[-2000:]}"
        )
    return int(proc.stdout.strip().splitlines()[-1])


class NotEnoughChips(RuntimeError):
    """A launcher was asked for more chip-holding processes than this
    host has TPU chips."""

    def __init__(self, n: int, chips: int):
        super().__init__(
            f"{n} processes would each open a TPU chip and this host has "
            f"{chips}: a chip belongs to one process at a time. Hold the "
            "layers in one process (chip_smoke.py does), run fewer "
            "processes, or keep them off the chip with JAX_PLATFORMS=cpu."
        )


def host_tpu_chips(base: dict | None = None, platform: str | None = None) -> int:
    """TPU chips the children of a launcher have to share out: 0 when
    the children are kept off the TPU (JAX_PLATFORMS in the environment,
    else `platform`, i.e. oryx.compute.platform, names platforms and tpu
    is not among them) or the host has none. Call it BEFORE spawning:
    the count is taken by a probe child, which cannot open chips that
    running children already hold."""
    import os

    env = dict(os.environ if base is None else base)
    platforms = env.get("JAX_PLATFORMS") or (
        platform if platform and platform != "auto" else ""
    )
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return _count_tpu_chips(env)


def one_chip_env(env: dict, i: int, chips: int) -> dict:
    """`env` with child i pinned to chip i alone, as an independent
    one-chip process (each runs its own runtime controller, hence the
    per-child port). Refuses a child the host has no chip for."""
    if i >= chips:
        raise NotEnoughChips(i + 1, chips)
    return dict(
        env,
        TPU_VISIBLE_CHIPS=str(i),
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_BOUNDS="1,1,1",
        TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{8476 + i}",
        TPU_MESH_CONTROLLER_PORT=str(8476 + i),
    )


def chip_process_envs(
    n: int, base: dict | None = None, platform: str | None = None
) -> list[dict]:
    """One environment per child process, for launchers whose children
    each train or score on an accelerator (serving replicas, the pod's
    layers). A TPU chip belongs to one process at a time — a second
    process that opens it fails or hangs — so on a TPU host child i is
    pinned to chip i, and a host with fewer chips than children is
    refused (NotEnoughChips) before anything is spawned. A single child
    is never pinned: it may drive every chip of the host."""
    import os

    env = dict(os.environ if base is None else base)
    chips = host_tpu_chips(env, platform) if n > 1 else 0
    if chips == 0:
        return [dict(env) for _ in range(n)]
    if chips < n:
        raise NotEnoughChips(n, chips)
    return [one_chip_env(env, i, chips) for i in range(n)]


def cpu_subprocess_env(base: dict | None = None, **overrides: str) -> dict:
    """Environment for a CPU-only child python process: forces
    JAX_PLATFORMS=cpu. A chip belongs to one process at a time, so a
    child that needs no chip must not try to open the one its parent (or
    a sibling) holds."""
    import os

    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(overrides)
    return env
