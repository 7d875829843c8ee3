"""Row-shard planning for pod-scale factor matrices.

One logical factor matrix (the ALS item-factor table, the seq
item-embedding table) sharded by ROW across a device mesh: each shard
owns a contiguous row range, serves its own slice of the fused top-k
scan, and receives ONLY its own dirty rows on delta sync. The plan here
is the single source of truth for "which shard owns row r" — the
serving view build, the dirty-row scatter split, the per-shard sync
accounting, and the cross-shard merge (ops/shard_topk.py) all read the
same bounds, so they can never disagree about ownership.

The partitioning contract is `parallel/submesh.process_groups`'s
(contiguous groups in input order, sizes as equal as possible with the
LARGER groups first, k clamped to [1, n]) — the same contract the pod
candidate search partitions processes and mesh rows with, unified by
this PR so every layer that splits an ordered axis computes the
identical partition from (n, k).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from oryx_tpu.parallel.submesh import process_groups


@dataclass(frozen=True)
class RowShards:
    """A contiguous row partition of an [n, ...] matrix: shard s owns
    rows [bounds[s], bounds[s+1]). Immutable; plan() is the only
    constructor callers should use."""

    bounds: tuple[int, ...]  # len n_shards + 1, monotone, bounds[0] == 0

    @staticmethod
    def plan(n_rows: int, n_shards: int) -> "RowShards":
        """Partition n_rows rows into min(n_shards, max(n_rows, 1))
        contiguous shards on the process_groups contract (larger shards
        first, sizes differing by at most one). n_rows == 0 keeps the
        requested shard count with all-empty shards so a shard-count-S
        serving view is S-sharded from its first (possibly empty)
        build."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if n_rows < 0:
            raise ValueError(f"n_rows must be >= 0, got {n_rows}")
        if n_rows == 0:
            return RowShards(bounds=(0,) * (n_shards + 1))
        groups = process_groups(list(range(n_rows)), n_shards)
        bounds = [0]
        for g in groups:
            bounds.append(bounds[-1] + len(g))
        return RowShards(bounds=tuple(bounds))

    @property
    def n_shards(self) -> int:
        return len(self.bounds) - 1

    @property
    def total(self) -> int:
        return self.bounds[-1]

    def size(self, shard: int) -> int:
        return self.bounds[shard + 1] - self.bounds[shard]

    def lo(self, shard: int) -> int:
        return self.bounds[shard]

    def owner(self, row: int) -> int:
        """The shard owning global row index `row`."""
        if not 0 <= row < self.total:
            raise IndexError(f"row {row} outside [0, {self.total})")
        # bounds is sorted; the owner is the last shard whose lo <= row.
        # Empty shards share a boundary value — side="right" - 1 lands on
        # the one that actually CONTAINS the row.
        return int(np.searchsorted(np.asarray(self.bounds), row, side="right") - 1)

    def split(
        self, idx: np.ndarray, rows: np.ndarray | None = None
    ) -> list[tuple[int, np.ndarray, np.ndarray | None]]:
        """Split a dirty-row delta (global indices + row payloads) by
        owning shard: [(shard, local_idx, rows_slice)] for every shard
        that owns at least one dirty row — an empty delta splits to an
        empty list, and a delta touching one shard yields exactly one
        entry (the owning-shard-only sync contract). Order within a
        shard preserves the caller's delta order."""
        idx = np.asarray(idx)
        if idx.size == 0:
            return []
        owners = np.searchsorted(
            np.asarray(self.bounds), idx, side="right"
        ) - 1
        if (idx < 0).any() or (idx >= self.total).any():
            bad = idx[(idx < 0) | (idx >= self.total)]
            raise IndexError(
                f"delta rows {bad[:4].tolist()} outside [0, {self.total})"
            )
        out: list[tuple[int, np.ndarray, np.ndarray | None]] = []
        for s in range(self.n_shards):
            sel = owners == s
            if not sel.any():
                continue
            local = idx[sel] - self.bounds[s]
            out.append((s, local, None if rows is None else np.asarray(rows)[sel]))
        return out

    def slices(self, mat):
        """The per-shard row slices of a host matrix (views, not
        copies)."""
        return [mat[self.bounds[s]:self.bounds[s + 1]] for s in range(self.n_shards)]


def shard_devices(n_shards: int, devices=None) -> list:
    """One placement device per shard: the first n_shards local devices
    (each shard's scan then runs on its own chip). On an accelerator
    fewer devices than shards is an error — a 4-shard view that sits
    entirely on chip 0 has the memory and speed of one chip while every
    layer above believes in four. On the CPU the devices are cycled: a
    1-device host shares the device between the shards and the sharded
    path is a correctness simulation, which is what the tests use."""
    import jax

    if devices is None:
        devices = jax.local_devices()
    devices = list(devices)
    if not devices:
        raise ValueError("no devices to place shards on")
    if n_shards > len(devices) and devices[0].platform != "cpu":
        raise ValueError(
            f"{n_shards} shards need {n_shards} {devices[0].platform} "
            f"devices and this process sees {len(devices)}: lower "
            "oryx.serving.api.sync.shard-count or give the process more "
            "chips"
        )
    return [devices[s % len(devices)] for s in range(n_shards)]
