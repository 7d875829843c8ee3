"""Edge-shape properties of the sharding plumbing (PR 11 satellites).

The uneven tail shard is where an off-by-one silently drops catalog
rows: every property here sweeps row counts NOT divisible by the shard
count, 1-device meshes, and empty deltas, and asserts the row set is
preserved exactly — nothing dropped, nothing fabricated.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from oryx_tpu.parallel.mesh import (
    DATA_AXIS, MeshSpec, host_mesh, make_mesh, pad_to_multiple, shard_array,
)
from oryx_tpu.parallel.shardspec import RowShards, shard_devices
from oryx_tpu.parallel.submesh import process_groups


def test_pad_to_multiple_props():
    for n in (0, 1, 2, 3, 5, 7, 8, 63, 64, 65, 1000):
        for m in (1, 2, 3, 4, 7, 8, 64):
            p = pad_to_multiple(n, m)
            assert p % m == 0
            assert p >= n
            assert p - n < m  # minimal: never a whole extra unit


@pytest.mark.parametrize("mesh_n", [1, 2, 3, 4, 8])
def test_shard_array_uneven_rows_keep_every_row(mesh_n):
    mesh = host_mesh(mesh_n)
    for n_rows in (1, 2, 3, 5, 7, 9, 17):
        a = np.arange(n_rows * 3, dtype=np.float32).reshape(n_rows, 3)
        out = shard_array(a, mesh)
        # rows pad to a multiple of the data axis; the real prefix is
        # bit-identical and the tail is zero padding — no row dropped
        assert out.shape[0] == pad_to_multiple(n_rows, mesh_n)
        host = np.asarray(out)
        np.testing.assert_array_equal(host[:n_rows], a)
        assert not host[n_rows:].any()


def test_shard_array_one_device_mesh_is_identity_shape():
    mesh = host_mesh(1)
    a = np.arange(15, dtype=np.float32).reshape(5, 3)
    out = shard_array(a, mesh)
    assert out.shape == a.shape
    np.testing.assert_array_equal(np.asarray(out), a)
    # scalars and replicated placement still work on the 1-device mesh
    s = shard_array(np.float32(3.0), mesh)
    assert np.asarray(s) == np.float32(3.0)


def test_rowshards_plan_matches_process_groups_contract():
    for n in (0, 1, 2, 3, 5, 7, 64, 65, 100):
        for s in (1, 2, 3, 4, 7, 8, 12):
            plan = RowShards.plan(n, s)
            sizes = [plan.size(j) for j in range(plan.n_shards)]
            assert sum(sizes) == n
            if n == 0:
                # empty stores keep the requested shard count (a
                # shard-count-S view is S-sharded from its first build)
                assert plan.n_shards == s
                continue
            # the process_groups contract, verbatim
            groups = process_groups(list(range(n)), s)
            assert sizes == [len(g) for g in groups]
            assert plan.n_shards == min(s, n)
            # larger shards first, sizes within 1 of each other
            assert sizes == sorted(sizes, reverse=True)
            assert max(sizes) - min(sizes) <= 1


def test_rowshards_slices_partition_exactly():
    for n in (1, 5, 7, 64, 65):
        for s in (1, 2, 3, 4, 8):
            plan = RowShards.plan(n, s)
            a = np.arange(n * 2).reshape(n, 2)
            parts = plan.slices(a)
            np.testing.assert_array_equal(np.concatenate(parts), a)
            # ownership agrees with the slice boundaries everywhere,
            # including the uneven tail shard
            for row in range(n):
                j = plan.owner(row)
                assert plan.bounds[j] <= row < plan.bounds[j + 1]


def test_rowshards_split_edge_deltas():
    plan = RowShards.plan(10, 4)  # sizes [3, 3, 2, 2]
    # empty delta: nothing to scatter, no shard touched
    assert plan.split(np.array([], dtype=np.int64)) == []
    # a delta entirely inside one shard yields exactly one entry with
    # local indices (the owning-shard-only sync contract)
    rows = np.arange(20, dtype=np.float32).reshape(10, 2)
    out = plan.split(np.array([3, 4]), rows[[3, 4]])
    assert len(out) == 1
    s, local, payload = out[0]
    assert s == 1
    np.testing.assert_array_equal(local, [0, 1])
    np.testing.assert_array_equal(payload, rows[[3, 4]])
    # a cross-shard delta splits by owner, preserving payload pairing
    out = plan.split(np.array([9, 0, 6]), rows[[9, 0, 6]])
    got = {s: (local.tolist(), payload.tolist()) for s, local, payload in out}
    assert set(got) == {0, 2, 3}
    assert got[0] == ([0], [rows[0].tolist()])
    assert got[2] == ([0], [rows[6].tolist()])
    assert got[3] == ([1], [rows[9].tolist()])
    # out-of-range rows are loud, never silently dropped
    with pytest.raises(IndexError):
        plan.split(np.array([10]), rows[:1])
    with pytest.raises(ValueError):
        RowShards.plan(5, 0)


def test_shard_devices_distinct_when_available():
    devs = shard_devices(4)
    assert len(devs) == 4
    # the conftest forces 8 virtual CPU devices: 4 shards get 4 distinct
    # chips; asking for more than exist cycles deterministically
    assert len(set(devs)) == 4
    n_local = len(jax.local_devices())
    devs12 = shard_devices(12)
    assert len(devs12) == 12
    # more shards than CPU devices: deterministic cycling (the
    # correctness simulation)
    assert devs12[n_local % 12] == devs12[0] or n_local >= 12


def test_shard_devices_refuses_fewer_chips_than_shards():
    # on an accelerator a 4-shard view must never sit on fewer than 4
    # chips while every layer above believes in four
    class Chip:
        platform = "tpu"

    one = [Chip()]
    with pytest.raises(ValueError, match="4 shards need 4 tpu devices"):
        shard_devices(4, one)
    four = [Chip() for _ in range(4)]
    assert shard_devices(4, four) == four
    assert shard_devices(2, four) == four[:2]


def test_make_mesh_model_axis():
    from oryx_tpu.parallel.mesh import MODEL_AXIS, model_mesh

    m = model_mesh(2)
    assert m.shape[MODEL_AXIS] == 2
    assert m.shape[DATA_AXIS] == 1
    # never more devices than asked for
    one = make_mesh(MeshSpec(data=1, model=1), jax.devices()[:1])
    assert one.devices.size == 1
