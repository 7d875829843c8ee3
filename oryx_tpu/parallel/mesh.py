"""Mesh construction and sharding utilities.

Design: one logical mesh with axes ("data", "model"). The training kernels
shard their leading entity dimension (users / points / trees) over "data"
and keep factor/centroid tables replicated or sharded over "model"; XLA
inserts the collectives (psum for Gram matrices, all_gather for factor
reads) that the reference implemented as Spark shuffles and partition-sum
fan-ins (e.g. the parallel VTV sum in PartitionedFeatureVectors.java:209-213
is literally the psum XLA derives from a sharded X^T.X einsum).

Multi-host: when jax.distributed is initialized, jax.devices() spans all
hosts and the same mesh-building code scales out over DCN; nothing here is
single-host-specific.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; -1 means 'all remaining devices'."""

    data: int = -1
    model: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int]:
        model = self.model if self.model > 0 else 1
        data = self.data if self.data > 0 else max(1, n_devices // model)
        if data * model > n_devices:
            raise ValueError(
                f"mesh {data}x{model} needs {data * model} devices, have {n_devices}"
            )
        return data, model


def make_mesh(spec: MeshSpec | None = None, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    spec = spec or MeshSpec()
    data, model = spec.resolve(len(devices))
    dev_array = np.asarray(devices[: data * model]).reshape(data, model)
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))


def host_mesh(n: int | None = None) -> Mesh:
    """Flat data-parallel mesh over the first n (default all) devices."""
    devices = jax.devices()
    n = n or len(devices)
    return make_mesh(MeshSpec(data=n, model=1), devices[:n])


def model_mesh(n: int | None = None) -> Mesh:
    """Mesh whose MODEL axis spans the first n (default all) devices —
    the layout the sharded factor tables (ops/shard_topk.py, the pjit
    bucketed trainer) shard their row dimension over. On CPU test hosts
    the conftest's virtual 8-device mesh makes model_mesh(n) a faithful
    n-shard simulation."""
    devices = jax.devices()
    n = n or len(devices)
    return make_mesh(MeshSpec(data=1, model=n), devices[:n])


def data_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading dim over "data", replicate the rest."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def model_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading dim over "model", replicate the rest — the
    factor-table layout of the sharded trainer (rows split across the
    model axis, every other operand replicated)."""
    return NamedSharding(mesh, P(MODEL_AXIS, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_array(x, mesh: Mesh, leading: bool = True):
    """Place an array on the mesh, sharding the leading dim over "data"
    (padding it to a multiple of the axis size) or fully replicated."""
    import jax.numpy as jnp

    x = jnp.asarray(x)
    if not leading or x.ndim == 0:
        return jax.device_put(x, replicated(mesh))
    n = mesh.shape[DATA_AXIS]
    rem = x.shape[0] % n
    if rem:
        pad = [(0, n - rem)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    return jax.device_put(x, data_sharding(mesh, x.ndim))


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m
