"""Multi-host distributed runtime: process group init + global mesh.

The reference's distributed backend is three planes (SURVEY.md §5): Kafka
between processes, ZooKeeper for offsets/metadata, and Spark's internal
shuffle/broadcast inside a job. The first two stay (the bus tier); this
module replaces the third for multi-HOST scale-out the TPU way: one JAX
process per host joins a coordinator (jax.distributed), jax.devices() then
spans the pod, and a single global Mesh is laid out so the "model" axis
stays inside each host (collectives ride ICI) while the "data" axis spans
hosts (gradient/Gram psums cross DCN once per step, the cheap direction).
Training code is unchanged — the same pjit/shard_map programs scale from
one chip to a pod, which is the whole point of the design.

Config (oryx.compute.distributed.*): coordinator-address (host:port of
process 0), num-processes, process-id; all optional — absent means
single-process, and init is a no-op.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np
from jax.sharding import Mesh

from oryx_tpu.common.config import Config
from oryx_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, MeshSpec, make_mesh

log = logging.getLogger(__name__)

_initialized = False


@dataclass(frozen=True)
class DistributedConfig:
    coordinator_address: str | None = None
    num_processes: int = 1
    process_id: int = 0

    @classmethod
    def from_config(cls, config: Config) -> "DistributedConfig":
        g = lambda k, d: config.get(f"oryx.compute.distributed.{k}", d)  # noqa: E731
        return cls(
            coordinator_address=g("coordinator-address", None),
            num_processes=int(g("num-processes", 1) or 1),
            process_id=int(g("process-id", 0) or 0),
        )

    @property
    def enabled(self) -> bool:
        return self.num_processes > 1 or self.coordinator_address is not None


# <checkout>/.jax_cache: where the persistent compilation cache lives when
# neither the environment nor the config places it. A fixed path on
# purpose — the directory is part of the cache key, so one built from a
# temp name, a pid or a time never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compilation_cache(config: Config | None = None) -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Called by `cli batch|speed|serving`,
    chip_smoke.py and benchmarks/run.py before first JAX use, so
    every start after the first skips its cold XLA compiles — the moral
    equivalent of the reference reusing a warm Spark context across
    generations.

    Placement, first match wins:
    - JAX_COMPILATION_CACHE_DIR is set: JAX reads it itself and this code
      never assigns jax_compilation_cache_dir (an operator, or the
      machine the program was sent to, owns the location);
    - oryx.compute.compilation-cache-dir, when configured;
    - REPO_CACHE_DIR.
    No sub-directory is appended in any case and no backend is
    initialised to name one: JAX's cache key already carries the
    platform and device kind."""
    target = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not target:
        d = None
        if config is not None:
            d = config.get_string("oryx.compute.compilation-cache-dir", None)
        d = str(d) if d else str(REPO_CACHE_DIR)
        if "://" in d and not d.startswith("file://"):
            # remote cache URI (e.g. gs://bucket/path): hand it to JAX
            # verbatim — Path() would mangle the double slash into a bogus
            # local directory and silently break cross-host cache sharing
            target = d
        else:
            from oryx_tpu.common.ioutil import strip_scheme

            p = Path(strip_scheme(d))
            p.mkdir(parents=True, exist_ok=True)
            target = str(p)
        jax.config.update("jax_compilation_cache_dir", target)
    # default thresholds skip small/fast programs; serving's bucketed
    # top-k shapes are exactly those, and they are what recompiles on
    # every process start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log.info("persistent compilation cache at %s", target)
    return target


def init_distributed(config: Config) -> bool:
    """Join the JAX process group when configured; no-op (False) for
    single-process deployments and on repeat calls. Call once per process
    before any other JAX use — the batch/speed runtimes and the CLI do."""
    global _initialized
    dc = DistributedConfig.from_config(config)
    if not dc.enabled or _initialized:
        return False
    if dc.coordinator_address is None:
        raise ValueError(
            "oryx.compute.distributed.num-processes > 1 requires "
            "oryx.compute.distributed.coordinator-address"
        )
    jax.distributed.initialize(
        coordinator_address=dc.coordinator_address,
        num_processes=dc.num_processes,
        process_id=dc.process_id,
    )
    _initialized = True
    log.info(
        "joined JAX process group: process %d/%d, %d local + %d global devices",
        dc.process_id,
        dc.num_processes,
        jax.local_device_count(),
        jax.device_count(),
    )
    return True


def hybrid_shape(n_processes: int, local_devices: int, spec: MeshSpec) -> tuple[int, int, int]:
    """(per-host data, model, hosts-on-data): resolve a (data, model) mesh
    spec against a multi-host topology. The model axis must fit inside one
    host so its collectives never cross DCN; the data axis is host-major."""
    data, model = spec.resolve(n_processes * local_devices)
    if model > local_devices:
        raise ValueError(
            f"model axis {model} exceeds {local_devices} local devices; "
            "tensor-parallel groups must not span hosts (ICI only)"
        )
    if local_devices % model != 0:
        raise ValueError(f"model axis {model} must divide local devices {local_devices}")
    if data % n_processes != 0:
        raise ValueError(f"data axis {data} must be a multiple of {n_processes} hosts")
    per_host_data = data // n_processes
    if per_host_data * model != local_devices:
        raise ValueError(
            f"mesh {data}x{model} does not tile {n_processes} hosts "
            f"x {local_devices} devices"
        )
    return per_host_data, model, n_processes


def global_mesh(spec: MeshSpec | None = None) -> Mesh:
    """The pod-wide mesh. Single-process: same as make_mesh. Multi-process:
    hybrid layout — ICI inside a host, DCN only along the data axis."""
    spec = spec or MeshSpec()
    if jax.process_count() == 1:
        return make_mesh(spec)
    from jax.experimental import mesh_utils

    per_host_data, model, hosts = hybrid_shape(
        jax.process_count(), jax.local_device_count(), spec
    )
    try:
        dev = mesh_utils.create_hybrid_device_mesh(
            (per_host_data, model), dcn_mesh_shape=(hosts, 1)
        )
    except ValueError:
        # slice_index metadata is TPU-only; jax's documented fallback for
        # platforms without it groups devices by process instead, keeping
        # the topology-aware ordering inside each host
        dev = mesh_utils.create_hybrid_device_mesh(
            (per_host_data, model),
            dcn_mesh_shape=(hosts, 1),
            process_is_granule=True,
        )
    return Mesh(dev, (DATA_AXIS, MODEL_AXIS))


def mesh_from_config(config: Config) -> Mesh | None:
    """The deployment's training mesh per oryx.compute.mesh.*, or None on a
    single device (trainers then skip sharding entirely). This is how the
    app updates scale to every chip — and every host once init_distributed
    has joined the process group — without code changes."""
    if jax.device_count() == 1:
        # read nothing on single-device hosts: the mesh keys only have
        # meaning once there is something to shard over (and the early
        # return must not silently drop values already read)
        return None
    data = config.get_int("oryx.compute.mesh.data", -1)
    model = config.get_int("oryx.compute.mesh.model", 1)
    return global_mesh(MeshSpec(data=data, model=model))


def barrier(name: str = "oryx") -> None:
    """Block until every process reaches this point (e.g. before an atomic
    model publish). No-op single-process."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def host_broadcast_bytes(payload: bytes | None, src_process: int) -> bytes:
    """Ship an arbitrary byte string from one process to every process in
    the pod (e.g. the winning candidate artifact after a partitioned
    hyperparam search — only the winner's group has it on local disk).
    Two true one-to-all broadcasts (length, then the buffer): peak memory
    is one len(payload) buffer per process — fine for model artifacts in
    the tens of MB; anything larger should ride the bus-chunked
    ArtifactRelay instead. All processes must call this collectively."""
    if jax.process_count() == 1:
        return payload or b""
    from jax.experimental import multihost_utils

    is_src = jax.process_index() == src_process
    n = len(payload) if (is_src and payload is not None) else 0
    total = int(
        multihost_utils.broadcast_one_to_all(
            np.asarray(n, dtype=np.int64), is_source=is_src
        )
    )
    buf = np.zeros(total, dtype=np.uint8)
    if is_src and total:
        buf[:] = np.frombuffer(payload, dtype=np.uint8)
    return np.asarray(
        multihost_utils.broadcast_one_to_all(buf, is_source=is_src)
    ).tobytes()


def host_allgather(x) -> np.ndarray:
    """Gather a small host-side value from every process (e.g. per-host
    record counts for metrics). Returns [num_processes, ...]."""
    if jax.process_count() == 1:
        return np.asarray(x)[None]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(np.asarray(x)))
