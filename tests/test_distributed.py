"""Multi-host distributed backend: config parsing, hybrid mesh shape math,
global mesh on the virtual 8-device CPU mesh, and single-process no-ops.
True multi-process joins can't run in one test process; the shape logic
that decides the pod layout is pure and covered directly."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from oryx_tpu.common.config import load_config
from oryx_tpu.parallel.distributed import (
    DistributedConfig,
    barrier,
    global_mesh,
    host_allgather,
    hybrid_shape,
    init_distributed,
    mesh_from_config,
)
from oryx_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, MeshSpec


def test_distributed_config_defaults_disabled():
    cfg = load_config()
    dc = DistributedConfig.from_config(cfg)
    assert dc.num_processes == 1 and dc.coordinator_address is None
    assert not dc.enabled


def test_distributed_config_enabled():
    cfg = load_config(overlay={
        "oryx.compute.distributed.coordinator-address": "10.0.0.1:8476",
        "oryx.compute.distributed.num-processes": 4,
        "oryx.compute.distributed.process-id": 2,
    })
    dc = DistributedConfig.from_config(cfg)
    assert dc.enabled and dc.num_processes == 4 and dc.process_id == 2


def test_init_noop_single_process():
    assert init_distributed(load_config()) is False


def test_init_requires_coordinator():
    cfg = load_config(overlay={"oryx.compute.distributed.num-processes": 2})
    with pytest.raises(ValueError):
        init_distributed(cfg)


def test_hybrid_shape_model_within_host():
    # 4 hosts x 8 local devices, model=4: model stays inside a host
    assert hybrid_shape(4, 8, MeshSpec(data=-1, model=4)) == (2, 4, 4)
    # pure data parallel
    assert hybrid_shape(2, 8, MeshSpec()) == (8, 1, 2)


def test_hybrid_shape_rejects_cross_host_model_axis():
    with pytest.raises(ValueError):
        hybrid_shape(2, 4, MeshSpec(data=1, model=8))


def test_hybrid_shape_rejects_nondividing():
    with pytest.raises(ValueError):
        hybrid_shape(3, 8, MeshSpec(data=4, model=2))


def test_global_mesh_single_process_spans_devices():
    mesh = global_mesh(MeshSpec(data=4, model=2))
    assert mesh.shape[DATA_AXIS] == 4 and mesh.shape[MODEL_AXIS] == 2


def test_mesh_from_config_uses_all_devices():
    mesh = mesh_from_config(load_config())
    assert mesh is not None  # conftest forces 8 virtual CPU devices
    assert mesh.shape[DATA_AXIS] * mesh.shape[MODEL_AXIS] == len(jax.devices())


def test_barrier_and_allgather_single_process():
    barrier("test")  # no-op, must not raise
    out = host_allgather(np.asarray([1, 2, 3]))
    assert out.shape == (1, 3)
    assert list(out[0]) == [1, 2, 3]


def test_trainer_picks_up_mesh_automatically():
    from oryx_tpu.apps.als.batch import ALSUpdate

    upd = ALSUpdate(load_config())
    assert upd.mesh is not None
    assert upd.mesh.shape[DATA_AXIS] * upd.mesh.shape[MODEL_AXIS] == len(jax.devices())


@pytest.fixture
def _restore_cache_config():
    """configure_compilation_cache mutates process-global jax config:
    put it back, or later tests see order-dependent caching."""
    import jax

    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def test_configure_compilation_cache(tmp_path, monkeypatch, _restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR unset,
    oryx.compute.compilation-cache-dir places JAX's persistent compile
    cache (created if absent, no sub-directory appended); remote URIs
    pass through verbatim."""
    import jax

    from oryx_tpu.common.config import load_config
    from oryx_tpu.parallel.distributed import configure_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = tmp_path / "xla-cache"
    cfg = load_config(
        overlay={"oryx.compute.compilation-cache-dir": str(d)}
    )
    assert configure_compilation_cache(cfg) == str(d)
    assert jax.config.jax_compilation_cache_dir == str(d)
    assert d.is_dir()
    import jax.numpy as jnp

    # unique shape so this compile isn't served from an in-memory cache
    x = jnp.ones((173, 61))
    jax.block_until_ready(jax.jit(lambda a: (a @ a.T).sum())(x))
    assert any(d.iterdir()), "no cache entry written"
    # remote URIs pass through verbatim (no local 'gs:/...' dir)
    assert configure_compilation_cache(
        load_config(
            overlay={"oryx.compute.compilation-cache-dir": "gs://b/c"}
        )
    ) == "gs://b/c"
    assert jax.config.jax_compilation_cache_dir == "gs://b/c"
    import os

    assert not os.path.exists("gs:")


def test_compilation_cache_env_var_owns_the_location(
    tmp_path, monkeypatch, _restore_cache_config
):
    """JAX_COMPILATION_CACHE_DIR set: our code never assigns
    jax_compilation_cache_dir — not from the config key, not from the
    in-checkout default — and still applies the thresholds."""
    import jax

    from oryx_tpu.common.config import load_config
    from oryx_tpu.parallel import distributed

    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assigned = []
    real_update = jax.config.update

    def spy(name, value):
        assigned.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    cfg = load_config(
        overlay={"oryx.compute.compilation-cache-dir": str(tmp_path / "cfg")}
    )
    assert distributed.configure_compilation_cache(cfg) == env_dir
    assert distributed.configure_compilation_cache() == env_dir
    assert "jax_compilation_cache_dir" not in assigned
    assert "jax_persistent_cache_min_compile_time_secs" in assigned
    assert not (tmp_path / "cfg").exists()


def test_compilation_cache_default_is_fixed_in_checkout_path():
    """Env and config both unset: <checkout>/.jax_cache, the same string
    in two separate processes (the directory is part of the cache key —
    a name built from a pid, a time or a temp dir never hits), reached
    without initialising a backend."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    code = (
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from oryx_tpu.parallel.distributed import configure_compilation_cache\n"
        "d = configure_compilation_cache()\n"
        "assert d == jax.config.jax_compilation_cache_dir\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print(d)\n"
    )
    env = {
        k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["PYTHONPATH"] = str(repo)
    outs = [
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd, timeout=120,
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        for cwd in (str(repo), "/")
    ]
    assert outs[0] == outs[1] == str(repo / ".jax_cache")


def test_host_broadcast_bytes_single_process():
    """Single-process degenerate forms: payload passes through, None and
    empty become b"" (the multi-process paths run in test_multihost.py
    via the pod winner shipping)."""
    from oryx_tpu.parallel.distributed import host_broadcast_bytes

    assert host_broadcast_bytes(b"abc", 0) == b"abc"
    assert host_broadcast_bytes(None, 0) == b""
    assert host_broadcast_bytes(b"", 0) == b""
