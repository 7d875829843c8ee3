"""Staged host->device transfers.

One giant ``jnp.asarray`` of a multi-hundred-MB host matrix is a single
opaque transfer: it needs the whole matrix twice in host memory while the
runtime stages it, shows no progress, and a failure mid-way loses all of
it. Staging the upload in bounded chunks keeps each transfer small, makes
progress observable, and bounds what a mid-transfer failure can corrupt.

The reference never faces this — its serving tier IS host memory
(ALSServingModel.java keeps factors in JVM maps); moving the hot matrix
to device HBM is the TPU design's job, so the transfer path is ours to
harden.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

DEFAULT_CHUNK_BYTES = 64 * 1024 * 1024


@partial(jax.jit, donate_argnums=(0,))
def _write(buf, chunk, start):
    idx = (start,) + (0,) * (buf.ndim - 1)
    return jax.lax.dynamic_update_slice(buf, chunk, idx)


def staged_device_put(
    a: np.ndarray,
    dtype=None,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    shape: tuple[int, ...] | None = None,
):
    """Upload ``a`` to the default device in row-chunks of at most
    ``chunk_bytes``, concatenating on device. Returns a committed device
    array (equivalent to ``jnp.asarray(a, dtype)`` for 1-2D inputs).

    ``shape`` (at least ``a.shape`` on every axis) is the device array's
    shape when it is larger: ``a`` lands at the origin and the rest is
    zero. The padding happens inside the upload — each chunk is written
    as it is into the zeroed device buffer — so no second array the size
    of the matrix ever exists, on the host or on the device.

    Small arrays take the direct path — staging only pays off when the
    transfer itself is the risk.
    """
    a = np.asarray(a)  # NOT ascontiguousarray: it promotes 0-d to 1-d
    shape = a.shape if shape is None else tuple(int(n) for n in shape)
    if dtype is not None and a.ndim:
        target_bytes = a.shape[0] * int(np.prod(a.shape[1:], dtype=np.int64)) * jnp.dtype(dtype).itemsize
    else:
        target_bytes = a.nbytes
    if a.ndim == 0 or target_bytes <= chunk_bytes or a.shape[0] <= 1:
        if shape != a.shape:
            padded = np.zeros(shape, dtype=a.dtype)
            padded[tuple(slice(0, n) for n in a.shape)] = a
            a = padded
        out = jnp.asarray(a, dtype=dtype)
        return jax.block_until_ready(out)

    row_bytes = max(1, a.nbytes // a.shape[0])
    rows_per = max(1, chunk_bytes // row_bytes)

    # write chunks into a DONATED device buffer (module-level _write, one
    # compile per chunk shape): peak HBM stays at one matrix + one chunk —
    # collecting all chunks then concatenating would transiently double
    # device memory, enough to turn a fitting model swap into an OOM
    out_dtype = jnp.dtype(dtype) if dtype is not None else a.dtype
    buf = jnp.zeros(shape, dtype=out_dtype)
    for start in range(0, a.shape[0], rows_per):
        dev = jnp.asarray(
            np.ascontiguousarray(a[start : start + rows_per]), dtype=out_dtype
        )
        # serialize chunk transfers: queueing them all at once recreates
        # the giant-buffered-write profile staging exists to avoid
        buf = _write(buf, jax.block_until_ready(dev), jnp.int32(start))
    return jax.block_until_ready(buf)


def kernel_view_put(a: np.ndarray, dtype=None, *, align_rows: bool = True):
    """Staged upload of a host [N, F] item matrix (or one chunk or shard
    of one) in the shape the fused top-k kernel DMAs
    (ops/pallas_topk.py view_shape): features zero-padded to the lane
    tile and, with align_rows, zero rows up to a multiple of the item
    block, so no dispatch ever copies the resident matrix to pad it. On
    a TPU a bf16[N, 250] array occupies N x 256 lanes in its tiled HBM
    layout anyway: the stored pad costs no memory, it only moves the
    copy out of every dispatch. Zero rows score 0.0 like the capacity
    rows of a serving view, and callers drop indices past their real
    rows the same way."""
    from oryx_tpu.ops.pallas_topk import view_shape

    a = np.asarray(a)
    rows, width = view_shape(
        a.shape[0], a.shape[1], a.dtype if dtype is None else dtype
    )
    if not align_rows:
        rows = a.shape[0]
    return staged_device_put(a, dtype=dtype, shape=(rows, width))


# ---------------------------------------------------------------------------
# chunked device matrices: models too large to score as ONE array (a
# (20M, 250) bf16 operand is 12 GB of a 16 GB chip, and a non-donated
# delta scatter of it needs a second one). The matrix lives as bounded
# row chunks, each in the kernel's shape (kernel_view_put); every
# compiled program sees only a chunk shape, and all equal chunks share
# one program.
# ---------------------------------------------------------------------------

# auto-chunk threshold + per-chunk target for serving device views
CHUNKED_OVER_BYTES = 4 << 30
CHUNK_TARGET_BYTES = 2 << 30


class ChunkedMatrix:
    """Row-chunked committed device matrix. Quacks like an array exactly
    where the serving batcher needs it (shape / dtype / devices); scoring
    dispatches through ops.als.topk_dot_batch_chunked, which merges the
    per-chunk top-ks with globally rebased indices."""

    __slots__ = ("chunks",)

    def __init__(self, chunks):
        self.chunks = list(chunks)
        if not self.chunks:
            raise ValueError("ChunkedMatrix needs at least one chunk")

    @property
    def shape(self):
        return (sum(int(c.shape[0]) for c in self.chunks),) + tuple(
            self.chunks[0].shape[1:]
        )

    @property
    def dtype(self):
        return self.chunks[0].dtype

    def devices(self):
        return self.chunks[0].devices()

    def map(self, fn):
        """Per-chunk transform (e.g. row normalization for the cosine
        view) — row-local operations only; anything cross-chunk belongs
        in the merge step of the chunked kernel."""
        return ChunkedMatrix([fn(c) for c in self.chunks])


# ---------------------------------------------------------------------------
# sharded device matrices: one logical row-partitioned matrix whose shards
# live on (up to) as many devices as there are shards — the pod-scale form
# of the serving item matrix (ops/shard_topk.py scores it per shard and
# merges the partials; parallel/shardspec.py owns the row partition). On a
# 1-device host every shard shares the device: a faithful CPU simulation
# of the multi-chip layout, which is how the host_mesh(n) tests prove the
# sharded path bit-identical to single-device.
# ---------------------------------------------------------------------------


class ShardedMatrix:
    """Row-sharded committed device matrix: shards[s] (a device array, or
    a QuantizedMatrix for score-mode=quantized) holds the rows
    [plan.bounds[s], plan.bounds[s+1]) of the logical matrix. Quacks like
    an array exactly where the serving batcher needs it (shape / dtype /
    devices / nbytes); scoring dispatches through
    ops.shard_topk.topk_dot_batch_sharded, which merges the per-shard
    top-k partials with globally rebased indices; scatter_rows routes a
    dirty-row delta into the OWNING shards only."""

    __slots__ = ("shards", "plan")

    def __init__(self, shards, plan):
        self.shards = list(shards)
        self.plan = plan
        if len(self.shards) != plan.n_shards:
            raise ValueError(
                f"{len(self.shards)} shards for a {plan.n_shards}-shard plan"
            )
        for s, shard in enumerate(self.shards):
            if int(shard.shape[0]) != plan.size(s):
                raise ValueError(
                    f"shard {s} has {shard.shape[0]} rows, plan owns "
                    f"{plan.size(s)}"
                )

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shape(self):
        return (self.plan.total,) + tuple(self.shards[0].shape[1:])

    @property
    def dtype(self):
        return self.shards[0].dtype

    @property
    def nbytes(self):
        return int(sum(getattr(s, "nbytes", 0) for s in self.shards))

    def devices(self):
        out = set()
        for s in self.shards:
            out |= set(s.devices())
        return out

    def map(self, fn) -> "ShardedMatrix":
        """Per-shard row-local transform (e.g. row normalization for the
        cosine view); anything cross-shard belongs in the merge step of
        the sharded kernel."""
        return ShardedMatrix([fn(s) for s in self.shards], self.plan)


def sharded_device_put(
    a: np.ndarray,
    n_shards: int,
    dtype=None,
    quantize: bool = False,
    devices=None,
) -> ShardedMatrix:
    """Upload a host matrix as a ShardedMatrix: rows partitioned by
    RowShards.plan, shard s staged onto its own placement device
    (parallel/shardspec.shard_devices — distinct chips when the host has
    them, the default device cycled otherwise). quantize=True builds
    per-shard QuantizedMatrix views; per-row scales are row-local, so a
    shard-local quantization is bit-identical to quantizing the whole
    matrix and slicing."""
    from oryx_tpu.parallel.shardspec import RowShards, shard_devices

    a = np.asarray(a)
    plan = RowShards.plan(a.shape[0], n_shards)
    devs = shard_devices(plan.n_shards, devices)
    shards = []
    for s in range(plan.n_shards):
        block = np.ascontiguousarray(a[plan.bounds[s]:plan.bounds[s + 1]])
        # stage onto the shard's device, then COMMIT the buffers there
        # (device_put with an explicit device — oryxlint's
        # device-placement rule flags uncommitted puts that reach
        # long-lived stores). The default_device
        # context alone leaves the arrays uncommitted, and the first
        # scatter/normalize would silently migrate the whole shard back
        # to the default device — exactly the multi-chip OOM the sharded
        # layout exists to prevent. Committed shards pin every
        # descendant computation (delta scatters, the unit-view
        # normalize) to their own device.
        # Every shard keeps exactly the rows its plan owns (a shard's
        # local index IS its global index minus plan.lo), lane-padded in
        # features; the serving tier sizes the capacity so that each
        # shard's rows are a multiple of its item block (view_rows).
        with jax.default_device(devs[s]):
            if quantize:
                qm = quantized_device_put(block, align_rows=False)
                shards.append(QuantizedMatrix(
                    jax.device_put(qm.q, devs[s]),
                    jax.device_put(qm.scale, devs[s]),
                ))
            else:
                shards.append(jax.device_put(
                    kernel_view_put(block, dtype=dtype, align_rows=False),
                    devs[s],
                ))
    return ShardedMatrix(shards, plan)


# ---------------------------------------------------------------------------
# quantized device matrices: int8 rows + per-row f32 scales. The serving
# top-k scan is HBM-bandwidth-bound in Y; int8 halves the bf16 stream (a
# quarter of f32) and the serving tier's exact f32 re-rank of surviving
# candidates (apps/als/serving.py _rerank_exact) corrects any ordering
# error quantization introduced inside the candidate set.
# ---------------------------------------------------------------------------


def quantize_rows_int8(mat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization: (q int8 [N,F], scale f32 [N])
    with row = q * scale to within scale/2 per element. All-zero rows get
    scale 1.0 so dequantization stays exact zeros (capacity padding rows
    ride through unharmed)."""
    a = np.asarray(mat, dtype=np.float32)
    m = np.max(np.abs(a), axis=1) if a.size else np.zeros(a.shape[0])
    scale = np.where(m > 0, m / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(a / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale


class QuantizedMatrix:
    """Committed device item matrix in int8 with per-row f32 scales.
    Quacks like an array exactly where the serving batcher needs it
    (shape / dtype / devices / nbytes); scoring dispatches through
    ops.als's quantized kernels, which dequantize blocks in VMEM and
    multiply the row scales back in after the matmul."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        if q.shape[0] != scale.shape[0]:
            raise ValueError(
                f"quantized rows/scales mismatch: {q.shape[0]} vs {scale.shape[0]}"
            )
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self):
        return int(
            getattr(self.q, "nbytes", 0) + getattr(self.scale, "nbytes", 0)
        )

    def devices(self):
        return self.q.devices()

    def unit_scaled(self) -> "QuantizedMatrix":
        """The cosine (row-normalized) view of this matrix, SHARING the
        int8 rows: unit(q·s) = q/||q||, so normalization is purely a new
        scale vector (1/||q_row||, zero rows stay zero) — the quantized
        unit view costs no second item matrix in HBM, where the bf16 path
        materializes a full normalized copy."""
        return QuantizedMatrix(self.q, _int8_unit_scales(self.q))


@jax.jit
def _int8_unit_scales(q):
    """1/||q_row|| per row (0 for zero rows), jitted so XLA fuses the
    int8->f32 convert into the norm reduction — an eager astype would
    materialize a full f32 copy of the matrix in HBM, defeating the
    memory point of quantization on exactly the large catalogs it
    targets."""
    qf = q.astype(jnp.float32)
    norms = jnp.sqrt((qf * qf).sum(axis=1))
    return jnp.where(norms > 0, 1.0 / jnp.maximum(norms, 1e-12), 0.0)


def quantized_device_put(
    a: np.ndarray, *, align_rows: bool = True
) -> QuantizedMatrix:
    """Quantize a host f32 matrix per-row and upload (staged) as a
    QuantizedMatrix device view in the kernel's shape (kernel_view_put).
    The scales are per row: padded with the rows, never in features."""
    q, scale = quantize_rows_int8(a)
    q_dev = kernel_view_put(q, align_rows=align_rows)
    return QuantizedMatrix(
        q_dev, staged_device_put(scale, shape=(q_dev.shape[0],))
    )


# ---------------------------------------------------------------------------
# incremental row sync: scatter dirty rows into an existing device matrix
# instead of re-uploading it. The TensorFlow pattern of device-resident
# mutable state updated by sparse scatters (PAPERS: TensorFlow, 2016):
# host->device traffic is sized by the DELTA, not the matrix.
# ---------------------------------------------------------------------------

# delta row counts pad up this ladder so the jit cache holds a handful of
# scatter programs, not one per distinct dirty-row count; padding entries
# carry row index == buf rows and are dropped on device (mode="drop")
SCATTER_PAD_BUCKETS = (64, 512, 4096, 32768)


def _scatter_bucket(d: int) -> int:
    for b in SCATTER_PAD_BUCKETS:
        if d <= b:
            return b
    return 1 << max(0, (d - 1).bit_length())


@jax.jit
def _scatter(buf, rows, idx):
    return buf.at[idx].set(rows, mode="drop")


@partial(jax.jit, donate_argnums=(0,))
def _scatter_donated(buf, rows, idx):
    return buf.at[idx].set(rows, mode="drop")


def scatter_rows(buf, idx: np.ndarray, rows: np.ndarray, *, donate: bool = False):  # oryxlint: donates=0 when donate
    """Write ``rows`` into device matrix ``buf`` at row indices ``idx``,
    returning the updated committed device array. Only the (bucket-padded)
    delta rows cross the host->device link; out-of-range pad indices drop
    on device.

    donate=True updates in place (no transient second buffer in HBM) and
    INVALIDATES ``buf`` — legal only when the caller holds the sole
    reference. A serving view must NOT donate: in-flight coalesced
    dispatches (serving/batcher.py _Pending.y) still read the old buffer,
    and donating it under them turns every parked request into a
    deleted-array error. The non-donated form is the double-buffer: old
    view stays valid until the swap, at a transient cost of one extra
    matrix in HBM.

    A ChunkedMatrix scatters per chunk (only chunks owning dirty rows are
    touched; untouched chunks are shared with the old view).
    """
    idx = np.asarray(idx, dtype=np.int32)
    if idx.shape[0] == 0:
        return buf
    if isinstance(buf, QuantizedMatrix):
        # PR 3's delta sync contract carried over: only the DIRTY rows
        # requantize (each row's scale is independent by construction), so
        # an update storm never triggers a full-matrix requantization.
        # rows arrive as f32 factor rows; the bucket-padded int8 rows +
        # their f32 scales are all that crosses the host->device link.
        q_rows, s_rows = quantize_rows_int8(np.asarray(rows, dtype=np.float32))
        return QuantizedMatrix(
            scatter_rows(buf.q, idx, q_rows, donate=donate),
            scatter_rows(buf.scale, idx, s_rows, donate=donate),
        )
    if isinstance(buf, ShardedMatrix):
        # dirty rows scatter into their OWNING shard only (the pod-scale
        # delta-sync contract): untouched shards are shared with the old
        # view, and a quantized shard re-quantizes its own dirty rows
        # per-row via the QuantizedMatrix branch below — shard-local by
        # construction, never a cross-shard (let alone full-matrix)
        # requantization.
        new_shards = list(buf.shards)
        for s, local, r in buf.plan.split(idx, np.asarray(rows)):
            new_shards[s] = scatter_rows(
                buf.shards[s], local, r, donate=donate
            )
        return ShardedMatrix(new_shards, buf.plan)
    if isinstance(buf, ChunkedMatrix):
        order = np.argsort(idx, kind="stable")
        idx_s, rows_s = idx[order], np.asarray(rows)[order]
        out, base = [], 0
        for c in buf.chunks:
            n_c = int(c.shape[0])
            lo = np.searchsorted(idx_s, base)
            hi = np.searchsorted(idx_s, base + n_c)
            if lo == hi:
                out.append(c)  # untouched chunk: shared, not copied
            else:
                out.append(
                    scatter_rows(c, idx_s[lo:hi] - base, rows_s[lo:hi], donate=donate)
                )
            base += n_c
        return ChunkedMatrix(out)
    d = idx.shape[0]
    b = _scatter_bucket(d)
    idx_p = np.full(b, buf.shape[0], dtype=np.int32)  # pads drop on device
    idx_p[:d] = idx
    # rows arrive at the published width; the buffer may be lane-padded
    # (kernel_view_put): written at the buffer's width, the pad lanes of
    # a dirty row stay zero
    rows = np.asarray(rows, dtype=buf.dtype)
    rows_p = np.zeros((b,) + tuple(buf.shape[1:]), dtype=buf.dtype)
    rows_p[(slice(0, d),) + tuple(slice(0, n) for n in rows.shape[1:])] = rows
    fn = _scatter_donated if donate else _scatter
    return jax.block_until_ready(
        fn(buf, jnp.asarray(rows_p), jnp.asarray(idx_p))
    )


def scatter_transfer_bytes(d: int, row_itemsize: int, features: int) -> int:
    """Host->device bytes one scatter_rows call moves for ``d`` dirty rows
    (bucket padding included — the honest wire figure the
    oryx_device_sync_bytes metric reports). For a QuantizedMatrix pass
    row_itemsize=1 and add 8 for the two f32 side scatters (scale row +
    its index) via quantized_scatter_bytes."""
    if d == 0:
        return 0
    b = _scatter_bucket(d)
    return b * (features * row_itemsize + np.dtype(np.int32).itemsize)


def quantized_scatter_bytes(d: int, features: int) -> int:
    """scatter_transfer_bytes for a QuantizedMatrix delta: the int8 row
    scatter plus the per-row f32 scale scatter (each bucket-padded with
    its own int32 index vector)."""
    if d == 0:
        return 0
    b = _scatter_bucket(d)
    return b * (features * 1 + 4) + b * (4 + 4)


def row_capacity(n: int, headroom: float) -> int:
    """Device-view row capacity for an ``n``-row store: ``n`` grown by
    ``headroom`` then rounded up a ~N/8-granular bucket ladder, so
    speed-layer growth neither reallocates the device matrix nor changes
    the batcher's compiled dispatch shapes until a bucket boundary.
    Monotone in ``n``; pure-pow2 rounding would waste up to 2x HBM at
    20M-row scale, so buckets step geometrically instead."""
    target = max(64, math.ceil(n * (1.0 + max(0.0, headroom))))
    unit = 1 << max(6, target.bit_length() - 3)
    return -(-target // unit) * unit


def view_rows(n: int, features: int, dtype, shards: int = 1) -> int:
    """Rows of a device view holding ``n`` rows of ``dtype`` in the
    kernel's shape (ops/pallas_topk.py view_shape): a multiple of the
    item block, and split into ``shards`` equal row shards each of them
    one (RowShards.plan splits an evenly divisible count evenly)."""
    from oryx_tpu.ops.pallas_topk import view_shape

    return shards * view_shape(-(-n // shards), features, dtype)[0]


def device_put_maybe_chunked(
    a: np.ndarray,
    dtype=None,
    over_bytes: int | None = None,
    chunk_bytes: int | None = None,
):
    """kernel_view_put for matrices that fit one program; ChunkedMatrix
    above `over_bytes` (in TARGET dtype), with ~`chunk_bytes` chunks,
    each in the kernel's shape (only the last holds padding rows).
    Thresholds resolve at call time so tests can lower the module
    constants and exercise the chunked path at toy scale."""
    from oryx_tpu.ops.pallas_topk import view_shape

    if over_bytes is None:
        over_bytes = CHUNKED_OVER_BYTES
    if chunk_bytes is None:
        chunk_bytes = CHUNK_TARGET_BYTES
    a = np.asarray(a)
    if a.ndim != 2:
        return staged_device_put(a, dtype=dtype)
    out_dtype = a.dtype if dtype is None else dtype
    itemsize = jnp.dtype(out_dtype).itemsize
    target_bytes = int(np.prod(a.shape, dtype=np.int64)) * itemsize
    if target_bytes <= over_bytes:
        return kernel_view_put(a, dtype=dtype)
    rows_per = view_shape(
        max(1, chunk_bytes // max(1, a.shape[1] * itemsize)),
        a.shape[1], out_dtype,
    )[0]
    return ChunkedMatrix(
        kernel_view_put(a[at : at + rows_per], dtype=dtype)
        for at in range(0, a.shape[0], rows_per)
    )
