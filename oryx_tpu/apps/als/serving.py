"""ALS serving tier: in-device factor store + query methods + manager.

Mirrors ALSServingModel/ALSServingModelManager (app/oryx-app-serving
.../als/model/ALSServingModel.java:96-409, ALSServingModelManager.java:
69-182). The reference partitions Y by LSH bucket and fans requests over a
thread pool with bounded heaps; here the whole Y store is one device matrix
and top-N is a single matmul + lax.top_k (so LSH becomes an optional
approximation, not a necessity — sample-rate < 1 subsamples rows instead).
knownItems ingestion rides the X update flood like the reference.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from oryx_tpu.api import AbstractServingModelManager, ServingModel
from oryx_tpu.common.config import Config
from oryx_tpu.common.perfattr import current_ledger
from oryx_tpu.common.tracing import current_span, get_tracer
from oryx_tpu.ops.als import compute_updated_xu
from oryx_tpu.apps.als.common import ALSConfig
from oryx_tpu.serving.app import chain_future, configure_post_pool, post_pool
from oryx_tpu.serving.batcher import TopKBatcher, cosine_scale, host_topk, select_topk
from oryx_tpu.serving.viewsync import extend_view_ids, view_sync_metrics
from oryx_tpu.apps.als.state import ALSState, apply_update_message

log = logging.getLogger(__name__)

# Max LSH partition-rebuild frequency under live update ingestion.
_LSH_REFRESH_SEC = 1.0

# Background resync poll interval: the thread also wakes immediately on
# _request_resync, so this only bounds how long a pure speed-layer write
# storm (no queries observing the drift) can stay un-synced.
_RESYNC_POLL_S = 0.05

# Serving score modes (oryx.serving.api.score-mode): how the device view
# scores the catalog. "exact" = bf16 scan + f32 candidate re-rank;
# "quantized" = int8 rows + per-row scales (half the HBM stream) with the
# same exact f32 re-rank of survivors; "approx" = on-device partial
# reduce (jax.lax.approx_max_k) at a recall target. The quality gate
# (ml/quality.py) holds quantized/approx recall@k >= 0.95 against exact.
SCORE_MODES = ("exact", "quantized", "approx")

# Recall target score-mode=approx uses when oryx.als.approx-recall is
# left at its exact default.
DEFAULT_APPROX_RECALL = 0.95


@dataclass
class SyncConfig:
    """How the serving model keeps its device/host scoring views in step
    with the live factor store (oryx.serving.api.sync.*).

    mode:
      - "delta" (default): dirty rows since the served view's version are
        scattered into the device matrix in place and the host mirror /
        norms / unit view / LSH partitions update the same rows; a
        background thread does all of it off the query path and swaps
        consistent view tuples atomically.
      - "full": every resync rebuilds from a snapshot (still in the
        background) — the debugging/bisection mode when delta application
        is suspected.
      - "blocking": the pre-incremental behavior — the next query after a
        version bump rebuilds the whole view synchronously under the sync
        lock. Kept for comparison benchmarks; it re-creates the
        first-query latency cliff on purpose.
    capacity_headroom: device matrix rows are allocated for the CURRENT
      store size grown by this fraction (then bucket-laddered,
      ops/transfer.py row_capacity), so speed-layer growth neither
      reallocates the device buffer nor changes the batcher's compiled
      dispatch shapes until a bucket boundary.
    max_delta_fraction: a dirty set larger than this fraction of the store
      full-resyncs instead — past that point the delta costs more than the
      snapshot it replaces.
    shard_count: > 1 row-shards the device scoring view across that many
      shards (ops/transfer.ShardedMatrix — one device per shard when the
      host has them): each shard scores its own row slice and the
      partials merge exactly (ops/shard_topk.py, bit-identical to the
      unsharded dispatch), dirty-row deltas scatter into their OWNING
      shard only, and int8 shards re-quantize per-row locally. The
      pod-scale layout for catalogs larger than one chip's HBM; on a
      1-device host every shard shares the device (the CPU correctness
      simulation the tests pin).
    """

    mode: str = "delta"
    capacity_headroom: float = 0.125
    max_delta_fraction: float = 0.2
    shard_count: int = 1

    @staticmethod
    def from_config(config: Config) -> "SyncConfig":
        g = lambda k, d: config.get(f"oryx.serving.api.sync.{k}", d)
        mode = str(g("mode", "delta"))
        if mode not in ("delta", "full", "blocking"):
            raise ValueError(
                "oryx.serving.api.sync.mode must be delta, full or "
                f"blocking, got {mode!r}"
            )
        headroom = float(g("capacity-headroom", 0.125))
        if headroom < 0.0:
            raise ValueError(
                "oryx.serving.api.sync.capacity-headroom must be >= 0"
            )
        frac = float(g("max-delta-fraction", 0.2))
        if not (0.0 < frac <= 1.0):
            raise ValueError(
                "oryx.serving.api.sync.max-delta-fraction must be in (0, 1]"
            )
        shards = int(g("shard-count", 1))
        if shards < 1:
            raise ValueError(
                "oryx.serving.api.sync.shard-count must be >= 1, got "
                f"{shards}"
            )
        return SyncConfig(mode, headroom, frac, shards)


class _LshPartitions:
    """Per-partition contiguous scoring blocks for the LSH host path:
    rows[p] maps block rows back to store rows, mats[p] is the contiguous
    factor block, norms[p] its row norms (for cosine queries). One matched
    snapshot, rebuilt with the partition view."""

    __slots__ = ("rows", "mats", "norms")

    def __init__(self, rows, mats, norms):
        self.rows = rows
        self.mats = mats
        self.norms = norms


class ALSServingModel(ServingModel):
    def __init__(
        self,
        state: ALSState,
        sample_rate: float = 1.0,
        num_cores: int | None = None,
        approx_recall: float = 1.0,
        lsh_max_bits_differing: int | None = None,
        sync: SyncConfig | None = None,
        score_mode: str = "exact",
    ):
        self.state = state
        # < 1.0: serve via the on-device approximate top-k (the TPU
        # replacement for the reference's LSH sampling); the exact f32
        # re-rank still runs over the returned candidates
        self.approx_recall = approx_recall
        if score_mode not in SCORE_MODES:
            raise ValueError(
                f"score_mode must be one of {SCORE_MODES}, got {score_mode!r}"
            )
        if score_mode == "exact" and approx_recall < 1.0:
            # the legacy knob: oryx.als.approx-recall < 1 meant
            # approximate device selection before score-mode existed, and
            # must keep meaning it for configs that never set score-mode
            score_mode = "approx"
        self.score_mode = score_mode
        # the mode the device view ACTUALLY serves: _build_views_full may
        # downgrade quantized -> exact past the chunking threshold, and
        # dispatch labels/metrics must report what ran, not what the
        # config asked for
        self._effective_mode = score_mode
        # the next full view build ends by freezing the loaded model out
        # of the collector's sight (serving/viewsync.py
        # freeze_loaded_model): the first build, and the first after each
        # generation the manager announces
        self.freeze_due = True
        self.sync = sync or SyncConfig()
        # (device matrix [capacity,K], ids [n], version, host f32 mirror
        # [capacity,K]) swapped as ONE tuple: readers always see a matched
        # set, no lock on the read path. capacity >= n rows the device
        # buffer at headroom (row_capacity) so store growth scatters into
        # existing rows instead of re-uploading Y
        self._sync_lock = threading.Lock()
        # writes-guarded: mutation is serialized under _sync_lock; readers
        # take the whole snapshot tuple lock-free by design (atomic swap)
        self._device_view: tuple | None = None  # guarded-by: _sync_lock (writes)
        self._unit_view: tuple | None = None  # row-normalized Y, same keying  # guarded-by: _sync_lock (writes)
        # background resync: queries observing version drift set the event
        # and keep serving the previous consistent snapshot; the thread
        # applies deltas / rebuilds and swaps the view tuples atomically
        self._resync_thread: threading.Thread | None = None  # guarded-by: _sync_lock (writes)
        self._resync_evt = threading.Event()
        self._stop = threading.Event()
        # last completed resync, for test/debug introspection:
        # {kind, rows, bytes, seconds, version}
        self.last_resync: dict | None = None  # guarded-by: _sync_lock (writes)
        # LSH candidate subsampling (CPU-parity approximation; the TPU path
        # scores everything exactly): built lazily at first query
        self.sample_rate = sample_rate
        self._num_cores = num_cores
        self._lsh_max_bits = lsh_max_bits_differing
        self._lsh = None  # guarded-by: _sync_lock (writes)
        # (ids, parts, version, _LshPartitions) — no flat matrix copy: the
        # partition blocks inside _LshPartitions are the snapshot
        self._partition_view: tuple | None = None  # guarded-by: _sync_lock (writes)
        self._partition_built_at = 0.0  # guarded-by: _sync_lock (writes)
        # Host LSH scoring gates on a core-sized semaphore: each request
        # gathers an O(sample_rate·N·F) candidate matrix, and unbounded
        # dispatch-pool concurrency multiplies that working set by the
        # thread count — measured as a 14x collapse (64 threads on one
        # core thrashing ~3GB of concurrent gathers). Cores-many scorers
        # keep the CPUs busy with bounded memory; the rest queue.
        self._host_score_sem = threading.Semaphore(
            max(1, num_cores if num_cores else (os.cpu_count() or 1))
        )

    def close(self) -> None:
        """Stop the background resync thread (the manager calls this when
        a MODEL update replaces the serving model)."""
        self._stop.set()
        self._resync_evt.set()

    def effective_recall(self) -> float:
        """The recall target this model's device dispatches carry: 1.0
        (exact selection) outside approx mode; in approx mode the
        configured oryx.als.approx-recall, or DEFAULT_APPROX_RECALL when
        that knob was left at its exact 1.0 default."""
        if self.score_mode != "approx":
            return 1.0
        return (
            self.approx_recall
            if self.approx_recall < 1.0
            else DEFAULT_APPROX_RECALL
        )

    def served_version(self) -> int | None:
        """Store version of the currently SERVED device view (None before
        the first build) — `served_version() == state.y.get_version()`
        means every published update is visible to queries."""
        view = self._device_view
        return None if view is None else view[2]

    def _ensure_lsh(self):
        from oryx_tpu.apps.als.lsh import LocalitySensitiveHash

        if self._lsh is None:
            with self._sync_lock:
                if self._lsh is None:
                    self._lsh = LocalitySensitiveHash(
                        self.sample_rate, self.state.features, self._num_cores,
                        max_bits_differing=self._lsh_max_bits,
                    )
        return self._lsh

    def _build_partition_view(self) -> tuple:  # oryxlint: holds=_sync_lock
        """Full LSH re-partition from a store snapshot — O(N.H.F) plus the
        O(N.F) snapshot copy, so its cost is recorded (lsh.rebuild span +
        oryx_lsh_rebuild_seconds): with resyncs in the background this
        work no longer sits on a request, but it still burns a core and
        delays view freshness. Call under _sync_lock."""
        t0 = time.monotonic()
        mat, ids, version = self.state.y.snapshot()
        mat = np.asarray(mat, dtype=np.float32)
        parts = self._lsh.indices_for(mat)
        # partition -> (row indices, contiguous block, norms), grouped
        # once per snapshot: the query path touches only candidate
        # partitions — no O(N) isin scan and no per-request gather
        order = np.argsort(parts, kind="stable")
        sorted_parts = parts[order]
        bounds = np.searchsorted(
            sorted_parts, np.arange(self._lsh.num_partitions + 1)
        )
        rows_by_part = [
            order[bounds[p]:bounds[p + 1]]
            for p in range(self._lsh.num_partitions)
        ]
        mats = [np.ascontiguousarray(mat[r]) for r in rows_by_part]
        pindex = _LshPartitions(
            rows=rows_by_part,
            mats=mats,
            norms=[np.linalg.norm(m, axis=1) for m in mats],
        )
        # the flat arena copy is NOT kept in the view — the partition
        # blocks are a complete copy already, and retaining both would
        # double the LSH host footprint
        view = (ids, parts, version, pindex)
        self._partition_view = view
        self._partition_built_at = time.monotonic()
        dur = time.monotonic() - t0
        view_sync_metrics()[3].observe(dur)
        tr = get_tracer()
        if tr.enabled:
            tr.record_interval(
                "lsh.rebuild", t0, rows=len(ids), version=version,
            )
        return view

    def _lsh_index(self):
        """(lsh, ids, partitions-per-row, partition index) — ONE matched
        snapshot: id list, partition assignment and partition blocks all
        from the same store version (concurrent UP ingestion bumps the
        version; rows from a fresher partitioning must never index an
        older matrix). The partition index stores each partition's rows as
        a CONTIGUOUS matrix block (the reference's partitioned-store
        layout, ALSServingModel.java candidate partitions): per-query
        scoring dots the candidate blocks directly instead of gathering an
        O(sample_rate·N·F) candidate copy per request — the gather was
        ~40% of per-request cost at 1M x 50f.

        Freshness: in the background sync modes a stale view is served
        as-is and the resync thread reassigns only DIRTY rows between
        partitions (full re-partitions only on drift overflow, at most
        once per refresh window). Blocking mode keeps the old inline
        rebuild, rate-limited to once per refresh window — every single
        UP write bumps the store version, and rebuilding the O(N.F)
        snapshot + O(N.H.F) partitioning per write would dwarf the
        subsampled scoring LSH exists for."""
        self._ensure_lsh()
        view = self._partition_view
        version = self.state.y.get_version()
        if view is not None and view[2] == version:
            return self._lsh, view[0], view[1], view[3]
        if view is not None and self.sync.mode != "blocking":
            # serve the previous consistent snapshot; catch up off-path
            self._request_resync()
            return self._lsh, view[0], view[1], view[3]
        now = time.monotonic()
        if view is None or now - self._partition_built_at >= _LSH_REFRESH_SEC:
            with self._sync_lock:
                view = self._partition_view
                if view is None or (
                    view[2] != self.state.y.get_version()
                    and time.monotonic() - self._partition_built_at
                    >= _LSH_REFRESH_SEC
                ):
                    view = self._build_partition_view()
        return self._lsh, view[0], view[1], view[3]

    def fraction_loaded(self) -> float:
        return self.state.fraction_loaded()

    # -- device scoring view ----------------------------------------------

    def _y_view_full(self) -> tuple:
        """(device Y matrix [capacity,K], row ids [n], version, host Y
        matrix [capacity,K]) — an atomic tuple swap instead of the
        reference's fine-grained read locks on the hot path. Staleness
        probe is a cheap version read. On drift the background sync modes
        serve the PREVIOUS consistent snapshot and hand the catch-up to
        the resync thread (delta scatter or full rebuild, swap when
        ready); only the first build — and every drift in blocking mode —
        runs inline."""
        view = self._device_view
        if view is not None:
            if view[2] == self.state.y.get_version():
                return view
            if self.sync.mode != "blocking":
                self._request_resync()
                return view
        with self._sync_lock:
            view = self._device_view
            if view is not None and (
                self.sync.mode != "blocking"
                or view[2] == self.state.y.get_version()
            ):
                return view
            return self._build_views_full()

    def _y_view(self):
        view = self._y_view_full()
        return view[0], view[1]

    def _y_unit_view(self):
        """Row-normalized Y for cosine queries, cached per store version so
        the O(N.K) normalization runs once per model drift, not per
        request. unit/ids/host matrix/norms come from ONE view tuple — in
        the background sync modes a stale unit view is served as-is (the
        resync thread updates its dirty rows in step with the device
        view); only the FIRST cosine query pays the inline build."""
        view = self._unit_view
        if view is not None:
            if view[2] != self.state.y.get_version():
                if self.sync.mode != "blocking":
                    self._request_resync()
                    return view[0], view[1], view[3], view[4]
            else:
                return view[0], view[1], view[3], view[4]
        y, ids, version, host_mat = self._y_view_full()
        with self._sync_lock:
            view = self._unit_view
            if view is not None and (
                view[2] == version or self.sync.mode != "blocking"
            ):
                return view[0], view[1], view[3], view[4]
            # re-read the CURRENT device view under the lock: a background
            # swap may have advanced it since the unlocked read above, and
            # the unit view must mirror exactly one device snapshot
            dv = self._device_view
            if dv is not None:
                y, ids, version, host_mat = dv
            view = self._build_unit_view(y, ids, version, host_mat)
        return view[0], view[1], view[3], view[4]

    def _build_unit_view(self, y, ids, version, host_mat) -> tuple:  # oryxlint: holds=_sync_lock
        """Normalize the device view into the cosine-scoring unit view +
        cached host norms. Call under _sync_lock."""
        from oryx_tpu.ops.transfer import (
            ChunkedMatrix, QuantizedMatrix, ShardedMatrix,
        )

        def normalize(a):
            af = a.astype(jnp.float32)
            n = jnp.maximum(jnp.linalg.norm(af, axis=1, keepdims=True), 1e-12)
            return (af / n).astype(a.dtype)

        # row normalization is row-local, so a chunked view normalizes
        # per chunk and stays chunked; capacity padding rows are zero and
        # normalize to zero (they never reach callers: _post drops
        # out-of-range indices). A quantized view normalizes by SCALE
        # alone (unit(q·s) = q/||q||) and shares the int8 rows — the
        # cosine view costs no second item matrix in HBM. A sharded view
        # normalizes per shard (quantized shards stay scale-only and keep
        # sharing their int8 rows) and stays sharded.
        if isinstance(y, ShardedMatrix):
            unit = y.map(
                lambda s: s.unit_scaled()
                if isinstance(s, QuantizedMatrix)
                else normalize(s)
            )
        elif isinstance(y, QuantizedMatrix):
            unit = y.unit_scaled()
        elif isinstance(y, ChunkedMatrix):
            unit = y.map(normalize)
        else:
            unit = normalize(y)
        # host row norms cached per version too: the wedged-device cosine
        # fallback must not pay an O(N.K) norm pass per request
        host_norms = np.linalg.norm(host_mat, axis=1)
        view = (unit, ids, version, host_mat, host_norms)
        self._unit_view = view
        return view

    def _build_views_full(self) -> tuple:  # oryxlint: holds=_sync_lock
        """Full snapshot rebuild of the device + host scoring views (and
        the unit view, when materialized): the initial load, and the
        fallback when a delta can't serve (drift overflow, capacity
        exhausted, arena compaction). Call under _sync_lock."""
        from oryx_tpu.ops.transfer import (
            CHUNKED_OVER_BYTES, device_put_maybe_chunked,
            quantized_device_put, row_capacity, sharded_device_put, view_rows,
        )

        t0 = time.monotonic()
        mat, ids, version = self.state.y.snapshot()
        mat = np.asarray(mat, dtype=np.float32)
        n = len(ids)
        sharded = self.sync.shard_count > 1
        # int8 quantized views stream 1 byte/element; exact bf16 views 2
        quantize = self.score_mode == "quantized"
        if quantize and not sharded and n * self.state.features > CHUNKED_OVER_BYTES:
            # no chunked quantized form: a model this size serves exact
            # bf16 chunks instead of silently quantizing half the catalog
            log.warning(
                "score-mode=quantized needs a single-program view; %d x %d "
                "exceeds the chunking threshold — serving exact instead",
                n, self.state.features,
            )
            quantize = False
        if self.score_mode == "quantized":
            # label dispatches with the mode actually served (see __init__)
            self._effective_mode = "quantized" if quantize else "exact"
        itemsize = 1 if quantize else 2
        # capacity-padded rows: store growth within the headroom scatters
        # into existing rows — no realloc, no new batcher dispatch shape.
        # Oversized (chunked) models skip the headroom: their chunks are
        # bounded already and growth full-resyncs (blocking mode also
        # skips it — it rebuilds per drift anyway)
        cap = n
        if self.sync.mode != "blocking":
            cap = row_capacity(n, self.sync.capacity_headroom)
            if (
                not sharded
                and cap * self.state.features * itemsize > CHUNKED_OVER_BYTES
            ):
                cap = n
        # ... in the shape the top-k kernel DMAs (ops/pallas_topk.py
        # view_shape): rows a multiple of the item block, per shard when
        # sharded, so no dispatch copies the view to pad it. The
        # capacity ladder's counts already are at serving scale
        # (6,291,456 = 3 x 2^21 for 5M items); small stores round up to
        # their one block. The feature axis is lane-padded inside the
        # upload; the host mirror keeps the published width.
        cap = view_rows(
            cap, self.state.features, jnp.int8 if quantize else jnp.bfloat16,
            self.sync.shard_count,
        )
        if cap > n:
            host = np.zeros((cap, self.state.features), dtype=np.float32)
            host[:n] = mat
        else:
            host = mat
        # Device scoring view by score mode. exact: bf16 — halves the HBM
        # traffic of the memory-bound top-k scan vs f32; at 1M x 50f the
        # bf16 ranking matched f32 index-for-index (pallas_topk.py).
        # quantized: int8 rows + per-row f32 scales — halves bf16's
        # stream again; selection error is bounded by the per-row scale
        # step. Either way the f32 host matrix rides along for the exact
        # candidate re-rank — row-aligned with the device view by
        # construction, read lock-free on the request path. Oversized
        # models come back as a ChunkedMatrix (ops/transfer.py
        # CHUNKED_OVER_BYTES); the batcher scores it chunk-and-merge.
        by_shard = None
        if sharded:
            # pod-scale row shards over the CAPACITY rows: growth within
            # the headroom scatters into its owning shard without
            # re-planning, and each shard's per-program shape is bounded
            # by construction (no chunking on top). Sharding replaces
            # chunking here, never composes with it.
            y_dev = sharded_device_put(
                host, self.sync.shard_count,
                dtype=None if quantize else jnp.bfloat16, quantize=quantize,
            )
            from oryx_tpu.serving.viewsync import set_shard_rows

            set_shard_rows(view_sync_metrics()[4], y_dev.plan, n)
            per_row = self.state.features * itemsize + (4 if quantize else 0)
            by_shard = {
                s: y_dev.plan.size(s) * per_row
                for s in range(y_dev.plan.n_shards)
            }
        elif quantize:
            y_dev = quantized_device_put(host)
        else:
            y_dev = device_put_maybe_chunked(host, dtype=jnp.bfloat16)
        view = (y_dev, ids, version, host)
        self._device_view = view
        if self._unit_view is not None:
            self._build_unit_view(y_dev, ids, version, host)
        dur = time.monotonic() - t0
        # the unit view normalizes ON device from the fresh upload (the
        # quantized unit view is scale-only and shares the int8 rows), so
        # a full resync moves exactly one scoring matrix across the host
        # link — plus the per-row scales when quantized
        sync_bytes = cap * self.state.features * itemsize + (
            cap * 4 if quantize else 0
        )
        self._note_resync("full", n, sync_bytes, dur, version, by_shard)
        if self.freeze_due:
            from oryx_tpu.serving.viewsync import freeze_loaded_model

            self.freeze_due = False
            freeze_loaded_model()
        return view

    # -- background resync --------------------------------------------------

    def _note_resync(self, kind: str, rows: int, n_bytes: int,  # oryxlint: holds=_sync_lock
                     seconds: float, version: int,
                     by_shard: dict[int, int] | None = None) -> None:
        from oryx_tpu.serving.viewsync import note_sync_bytes

        m_bytes, m_secs, m_total = view_sync_metrics()[:3]
        note_sync_bytes(m_bytes, n_bytes, by_shard)
        m_secs.observe(seconds)
        m_total.inc(kind=kind)
        self.last_resync = {
            "kind": kind, "rows": rows, "bytes": n_bytes,
            "seconds": seconds, "version": version,
        }
        if by_shard is not None:
            self.last_resync["shard_bytes"] = dict(by_shard)
        tr = get_tracer()
        if tr.enabled:
            tr.record_interval(
                "view.resync", time.monotonic() - seconds,
                kind=kind, rows=rows, bytes=n_bytes, version=version,
            )

    def _request_resync(self) -> None:
        """Wake (starting if needed) the background resync thread. Queries
        call this on observing version drift and keep serving the old
        snapshot — the post-update latency cliff moves off the request
        path entirely."""
        t = self._resync_thread
        if t is None or not t.is_alive():
            with self._sync_lock:
                t = self._resync_thread
                if (t is None or not t.is_alive()) and not self._stop.is_set():
                    t = threading.Thread(
                        target=self._resync_loop, name="oryx-als-resync",
                        daemon=True,
                    )
                    self._resync_thread = t
                    t.start()
        self._resync_evt.set()

    def _views_stale(self) -> bool:
        v = self.state.y.get_version()
        dv = self._device_view
        if dv is not None and dv[2] != v:
            return True
        uv = self._unit_view
        if dv is not None and uv is not None and uv[2] != dv[2]:
            # a failed unit scatter after the device swap (partial delta
            # apply) leaves the cosine view behind: it must be rebuilt,
            # not silently served forever
            return True
        pv = self._partition_view
        return pv is not None and pv[2] != v

    def _resync_loop(self) -> None:  # oryxlint: offloop (background resync thread)
        while not self._stop.is_set():
            self._resync_evt.wait(_RESYNC_POLL_S)
            self._resync_evt.clear()
            if self._stop.is_set():
                return
            try:
                while not self._stop.is_set() and self._views_stale():
                    if not self._resync_once():
                        break  # rate-limited: retry on the next poll tick
            except Exception:
                log.exception("background view resync failed")
                # don't spin on a persistent failure (e.g. device OOM);
                # queries keep serving the last consistent snapshot
                time.sleep(0.5)

    def _resync_once(self) -> bool:
        """Bring every materialized view up to the current store version:
        dirty-row deltas when the drift is small (mode delta), snapshot
        rebuilds otherwise. Runs on the resync thread; swaps are atomic
        tuple stores under _sync_lock, so queries never see a mismatched
        matrix/ids/version set. Returns False when the only remaining
        work is a rate-limited LSH re-partition (the caller backs off
        instead of spinning on the limiter)."""
        progress = False
        with self._sync_lock:
            dv = self._device_view
            if dv is not None and dv[2] != self.state.y.get_version():
                if not (self.sync.mode == "delta" and self._try_apply_delta(dv)):
                    self._build_views_full()
                progress = True
            dv, uv = self._device_view, self._unit_view
            if dv is not None and uv is not None and uv[2] != dv[2]:
                # unit view diverged from the device view (a unit scatter
                # failed after the device swap): rebuild it from the
                # consistent device snapshot — normalization runs on
                # device, no host re-upload
                self._build_unit_view(dv[0], dv[1], dv[2], dv[3])
                progress = True
            pv = self._partition_view
            if pv is not None and pv[2] != self.state.y.get_version():
                if self.sync.mode == "delta" and self._try_partition_delta(pv):
                    progress = True
                # full re-partition is O(N.H.F): rate-limit like the old
                # inline path so a delta-overflow storm can't spin it
                # back-to-back
                elif (time.monotonic() - self._partition_built_at
                        >= _LSH_REFRESH_SEC):
                    self._build_partition_view()
                    progress = True
        return progress

    def _try_apply_delta(self, dv: tuple) -> bool:  # oryxlint: holds=_sync_lock
        """Apply a dirty-row delta to the device/host/unit views. Returns
        False when only a full rebuild can serve (drift overflow, growth
        past capacity, arena compaction). Call under _sync_lock. A
        quantized view re-quantizes ONLY the dirty rows inside
        scatter_rows (per-row scales are independent) — an update storm
        never triggers a full-matrix requantization."""
        from oryx_tpu.ops.transfer import (
            QuantizedMatrix, ShardedMatrix, quantize_rows_int8,
            quantized_scatter_bytes, scatter_rows, scatter_transfer_bytes,
        )
        from oryx_tpu.serving.viewsync import set_shard_rows, sharded_delta_bytes

        t0 = time.monotonic()
        y_dev, ids, _version, host_mat = dv
        n_old = len(ids)
        capacity = int(host_mat.shape[0])
        delta = self.state.y.delta_since(
            dv[2],
            max_rows=max(1, int(self.sync.max_delta_fraction * max(n_old, 1))),
        )
        if delta is None or delta.n > capacity:
            return False
        if delta.rows.size == 0:
            return True  # raced an already-applied version: nothing to do
        rows, mat_rows = delta.rows, delta.mat
        ids = extend_view_ids(ids, delta)
        if ids is None:
            return False
        # The host f32 mirror and cached norms update the SAME dirty rows
        # in place — the deliberate snapshot relaxation of this design: a
        # reader racing the assignment can see a dirty row one version
        # newer (or, within the numpy row-write itself, a transiently
        # mixed row) in the advisory f32 re-rank, never a torn
        # matrix/ids pairing. Norms are written back-to-back with their
        # vectors, BEFORE the slow device scatters below, so the window
        # where a cosine host fallback could pair a new vector with its
        # old cached norm is microseconds, not a device round-trip.
        uv = self._unit_view
        if uv is not None and uv[2] != dv[2]:
            # the unit view diverged from the device view (a prior unit
            # scatter failed mid-apply): this delta is relative to dv[2],
            # and applying it to the older uv would skip the rows dirtied
            # in between — leave it; _resync_once rebuilds it whole from
            # the fresh device snapshot
            uv = None
        host_mat[rows] = mat_rows
        if uv is not None:
            norms = np.linalg.norm(mat_rows, axis=1)
            uv[4][rows] = norms
        # the scatter is NOT donated: in-flight coalesced dispatches
        # (batcher _Pending.y) still score the old buffer, and donating
        # it under them would turn every parked request into a
        # deleted-array error. The functional form IS the double buffer —
        # the old view tuple stays fully consistent until the swap below,
        # at a transient cost of one extra matrix in HBM. Host->device
        # traffic is the bucket-padded delta rows either way.
        sharded = isinstance(y_dev, ShardedMatrix)
        quantized = isinstance(y_dev, QuantizedMatrix) or (
            sharded and isinstance(y_dev.shards[0], QuantizedMatrix)
        )
        by_shard: dict[int, int] | None = None
        if sharded:
            # dirty rows scatter into their OWNING shard only (untouched
            # shards stay shared with the old view). Quantized shards:
            # quantize the dirty rows ONCE here (per-row scales are
            # row-local, so the host-side quantization is bit-identical
            # to what each shard's scatter would do internally) and hand
            # every touched shard its pre-quantized slice — the unit
            # branch below reuses the same q_rows for its scales instead
            # of quantizing a second time.
            if quantized:
                q_rows, s_rows = quantize_rows_int8(mat_rows)
                new_shards = list(y_dev.shards)
                for s, local, sel in y_dev.plan.split(
                    rows, np.arange(rows.size, dtype=np.int64)
                ):
                    new_shards[s] = QuantizedMatrix(
                        scatter_rows(y_dev.shards[s].q, local, q_rows[sel]),
                        scatter_rows(
                            y_dev.shards[s].scale, local, s_rows[sel]
                        ),
                    )
                y_new = ShardedMatrix(new_shards, y_dev.plan)
            else:
                y_new = scatter_rows(y_dev, rows, mat_rows)
            if delta.n > n_old:
                set_shard_rows(view_sync_metrics()[4], y_dev.plan, delta.n)
        elif quantized:
            # quantize the dirty rows ONCE here (per-row scales are
            # independent — never a full requantization) so the unit view
            # below can keep SHARING the device view's int8 rows
            q_rows, s_rows = quantize_rows_int8(mat_rows)
            y_new = QuantizedMatrix(
                scatter_rows(y_dev.q, rows, q_rows),
                scatter_rows(y_dev.scale, rows, s_rows),
            )
        else:
            y_new = scatter_rows(y_dev, rows, mat_rows)
        self._device_view = (y_new, ids, delta.version, host_mat)

        def _bytes_of_d(d: int) -> int:
            if quantized:
                return quantized_scatter_bytes(d, self.state.features)
            return scatter_transfer_bytes(d, 2, self.state.features)

        def _delta_bytes() -> int:
            return _bytes_of_d(rows.size)

        if sharded:
            # per-shard accounting: each touched shard's scatter is its
            # own bucket-padded transfer to that shard's device
            n_bytes, by_shard = sharded_delta_bytes(
                y_dev.plan, rows, _bytes_of_d
            )
        else:
            n_bytes = _delta_bytes()
        if uv is not None:
            if sharded and quantized:
                # per-shard quantized unit view: adopt each touched
                # shard's freshly scattered int8 rows (the two views keep
                # sharing ONE int8 matrix per shard) and scatter only the
                # dirty rows' unit scales into that shard — derived from
                # the SAME q_rows the device scatter above used, so the
                # whole delta quantizes each dirty row exactly once
                qn = np.linalg.norm(q_rows.astype(np.float32), axis=1)
                unit_scales = np.where(
                    qn > 0, 1.0 / np.maximum(qn, 1e-12), 0.0
                ).astype(np.float32)
                unit_shards = list(uv[0].shards)
                for s, local, sc in y_dev.plan.split(rows, unit_scales):
                    unit_shards[s] = QuantizedMatrix(
                        y_new.shards[s].q,
                        scatter_rows(uv[0].shards[s].scale, local, sc),
                    )
                    by_shard[s] = by_shard.get(s, 0) + scatter_transfer_bytes(
                        len(local), 4, 1
                    )
                unit_new = ShardedMatrix(unit_shards, uv[0].plan)
                n_bytes = sum(by_shard.values())
            elif sharded:
                # sharded bf16 unit view: the ShardedMatrix scatter
                # routes the dirty unit rows into their owning shards —
                # the same per-shard bucket-padded transfers the device
                # scatter just priced, so each touched shard's bytes
                # simply double (no second plan.split pass)
                unit_rows = mat_rows / np.maximum(norms, 1e-12)[:, None]
                unit_new = scatter_rows(uv[0], rows, unit_rows)
                for s in list(by_shard):
                    by_shard[s] *= 2
                n_bytes = sum(by_shard.values())
            elif quantized and isinstance(uv[0], QuantizedMatrix):
                # the quantized unit view is (shared int8 rows, scale =
                # 1/||q_row||): adopt the device view's freshly scattered
                # q and scatter ONLY the dirty rows' unit scales — the
                # two views keep sharing one int8 matrix in HBM across
                # every delta, and the unit half of the sync moves 8
                # bytes/row instead of a second row scatter
                qn = np.linalg.norm(q_rows.astype(np.float32), axis=1)
                unit_scales = np.where(
                    qn > 0, 1.0 / np.maximum(qn, 1e-12), 0.0
                ).astype(np.float32)
                unit_new = QuantizedMatrix(
                    y_new.q, scatter_rows(uv[0].scale, rows, unit_scales)
                )
                n_bytes += scatter_transfer_bytes(rows.size, 4, 1)
            else:
                unit_rows = mat_rows / np.maximum(norms, 1e-12)[:, None]
                unit_new = scatter_rows(uv[0], rows, unit_rows)
                n_bytes += _delta_bytes()
            self._unit_view = (unit_new, ids, delta.version, host_mat, uv[4])
        self._note_resync(
            "delta", int(rows.size), n_bytes,
            time.monotonic() - t0, delta.version, by_shard,
        )
        return True

    def _try_partition_delta(self, pv: tuple) -> bool:  # oryxlint: holds=_sync_lock
        """Reassign only dirty rows between LSH partitions instead of
        re-partitioning the whole store. Touched partitions get rebuilt
        contiguous blocks; untouched partitions share their arrays with
        the previous view. Call under _sync_lock."""
        ids, parts, _version, pindex = pv
        n_old = len(ids)
        delta = self.state.y.delta_since(
            pv[2],
            max_rows=max(1, int(self.sync.max_delta_fraction * max(n_old, 1))),
        )
        if delta is None:
            return False
        if delta.rows.size == 0:
            return True
        t0 = time.monotonic()
        rows, mat_rows = delta.rows, delta.mat
        ids = extend_view_ids(ids, delta)
        if ids is None:
            return False
        new_parts_of_dirty = self._lsh.indices_for(
            np.ascontiguousarray(mat_rows, dtype=np.float32)
        )
        parts = np.concatenate([parts, np.zeros(delta.n - n_old, dtype=parts.dtype)]) \
            if delta.n > n_old else parts.copy()
        old_parts_of_dirty = parts[rows]
        parts[rows] = new_parts_of_dirty
        touched = set(int(p) for p in old_parts_of_dirty[rows < n_old]) | set(
            int(p) for p in new_parts_of_dirty
        )
        new_rows = list(pindex.rows)
        new_mats = list(pindex.mats)
        new_norms = list(pindex.norms)
        vec_of = {int(r): mat_rows[j] for j, r in enumerate(rows)}
        dirty_set = set(int(r) for r in rows)
        for p in touched:
            old_block_rows = pindex.rows[p]
            keep = ~np.isin(old_block_rows, rows)
            kept_rows = old_block_rows[keep]
            kept_mat = pindex.mats[p][keep]
            add = np.asarray(
                sorted(r for r in dirty_set if parts[r] == p), dtype=np.int64
            )
            if add.size:
                add_mat = np.stack([vec_of[int(r)] for r in add])
                block_rows = np.concatenate([kept_rows, add])
                block_mat = np.ascontiguousarray(
                    np.concatenate([kept_mat, add_mat.astype(np.float32)])
                )
            else:
                block_rows, block_mat = kept_rows, np.ascontiguousarray(kept_mat)
            new_rows[p] = block_rows
            new_mats[p] = block_mat
            new_norms[p] = np.linalg.norm(block_mat, axis=1)
        self._partition_view = (
            ids, parts, delta.version,
            _LshPartitions(rows=new_rows, mats=new_mats, norms=new_norms),
        )
        # no device traffic: pure host reindex — recorded as a delta
        # resync with zero sync bytes so view freshness is still visible
        self._note_resync(
            "delta", int(rows.size), 0, time.monotonic() - t0, delta.version,
        )
        return True

    # -- queries -----------------------------------------------------------

    def _shadow_sample(
        self, vec, pairs, how_many, exclude, cosine, mode, trace_id,
        snapshot_fn,
    ) -> None:
        """Offer this served response to the live quality sampler
        (common/qualitystats.py): a config-gated fraction is re-scored
        exactly on the sampler's drain thread. Called AFTER the response
        is final, on the post pool / host-path caller thread — never the
        batcher dispatcher — and rescorer-filtered responses are skipped
        (their exact reference would need the rescorer replayed)."""
        from oryx_tpu.common.qualitystats import get_qualitystats

        get_qualitystats().maybe_sample(
            vec, pairs, how_many=how_many, exclude=exclude, cosine=cosine,
            score_mode=mode, trace_id=trace_id, snapshot_fn=snapshot_fn,
        )

    def _top_n_plan(self, user_vector, how_many, exclude, rescorer, cosine):
        """Shared front half of top_n/top_n_async: either ("done", pairs)
        for paths resolved synchronously on the host, or
        ("fut", batcher_future, post_fn) for the device path."""
        span = current_span()
        trace_id = span.trace_id if span is not None else None
        if self.sample_rate < 1.0:
            # LSH candidate subsampling: score only items whose partition is
            # within the Hamming ball of the query's (the reference's
            # candidate-partition fan-out, ALSServingModel.java:264-279).
            # Matrix/ids/partitions are one matched snapshot from _lsh_index.
            # Pure host work — completes on this thread, gated by the
            # core-sized scoring semaphore (bounded memory under load).
            lsh, ids, _parts, pindex = self._lsh_index()
            if not ids:
                return "done", []
            k = min(len(ids), how_many + len(exclude) + 8)
            cand_parts = [
                int(p) for p in lsh.candidate_indices(user_vector)
                if pindex.rows[int(p)].size
            ]
            if not cand_parts:
                return "done", []
            q = np.asarray(user_vector, dtype=np.float32)
            with self._host_score_sem:
                # dot each candidate partition's contiguous block; the
                # per-partition scores and row maps concatenate into one
                # ranking problem
                score_parts = [pindex.mats[p] @ q for p in cand_parts]
                scores = (
                    score_parts[0] if len(score_parts) == 1
                    else np.concatenate(score_parts)
                )
                rows = (
                    pindex.rows[cand_parts[0]] if len(cand_parts) == 1
                    else np.concatenate([pindex.rows[p] for p in cand_parts])
                )
                if cosine:
                    norms = (
                        pindex.norms[cand_parts[0]] if len(cand_parts) == 1
                        else np.concatenate([pindex.norms[p] for p in cand_parts])
                    )
                    scores = cosine_scale(scores, norms)
                vals, top = select_topk(scores, min(k, rows.size))
                idx = rows[top]
            pairs = _trim_pairs(vals, idx, ids, how_many, exclude, rescorer)
            if rescorer is None and pairs:
                # LSH live recall: the exact reference is a fresh full-
                # store snapshot, taken on the sampler's drain thread
                store = self.state.y

                def lsh_snapshot():
                    mat, snap_ids, _v = store.snapshot()
                    return np.asarray(mat, dtype=np.float32), snap_ids, len(snap_ids)

                self._shadow_sample(
                    user_vector, pairs, how_many, exclude, cosine, "lsh",
                    trace_id, lsh_snapshot,
                )
            return "done", pairs

        host_norms = None
        if cosine:
            y, ids, host_mat, host_norms = self._y_unit_view()
        else:
            y, ids, _v, host_mat = self._y_view_full()
        n = len(ids)
        if n == 0:
            return "done", []
        # over-fetch to survive exclusions/filters, then trim.
        # Concurrent requests coalesce into one bucketed-shape device
        # dispatch (serving/batcher.py) — B=1 matmuls waste the MXU and
        # a data-dependent k would recompile per exclusion-set size.
        k = min(n, how_many + len(exclude) + 8)
        # host_mat doubles as the wedged-device fallback: the batcher
        # scores on the host if the accelerator transport hangs.
        # valid_rows: the device matrix is capacity-padded past n (zero
        # rows scatter-reserved for speed-layer growth); the batcher
        # hands the fused kernel the count, which then neither streams
        # nor scores the padding, and its FLOP accounting does not count
        # the padding as scored work on any path.
        fut = TopKBatcher.shared().submit_nowait(
            user_vector, k, y, host_mat=host_mat, cosine=cosine,
            host_norms=host_norms, recall=self.effective_recall(),
            valid_rows=n, score_mode=self._effective_mode,
        )

        # captured here, on the request's thread: _post runs on a post-pool
        # thread, which has no thread-current ledger
        ledger = current_ledger()

        def _post(result):
            t_post = time.monotonic()
            # the region around exactly what the `rerank` stage times
            with get_tracer().region("post.rerank", cpu=True):
                pairs = _post_pairs(result)
                if rescorer is None and pairs:
                    # device-path live recall: the exact reference is the
                    # row-aligned host mirror the response was re-ranked
                    # against (no copy; the drain reads it by reference)
                    self._shadow_sample(
                        user_vector, pairs, how_many, exclude, cosine,
                        self._effective_mode, trace_id,
                        lambda: (host_mat, ids, n),
                    )
            if ledger is not None:
                # the first two parts of `serialize` (perfattr.POST_STAGES):
                # the wait between the device phase's end and this call,
                # then this call; _render adds the third
                tail = ledger.last_end()
                if tail is not None:
                    ledger.add_stage("handoff", max(0.0, t_post - tail))
                    ledger.add_stage("rerank", time.monotonic() - t_post)
            return pairs

        def _post_pairs(result):
            vals, idx = result
            vals, idx = np.asarray(vals), np.asarray(idx)
            if int(y.shape[0]) > n:
                # the view is stored with room to grow, and this request
                # scores the rows its own id list names. The fused kernel
                # is handed the count and selects nothing at or past it
                # (ops/pallas_topk.py n_valid), so there this filter
                # finds nothing to drop unless the dispatch's group held
                # a request with a longer list. Every OTHER path (XLA,
                # approximate, chunked, sharded) scores the whole
                # capacity: its zero rows score 0.0 and enter the
                # candidate set when fewer than k real scores beat 0.
                # Dropping them keeps an EXACT prefix: every real row a
                # pad displaced scored <= the pad's 0.0, so the kept rows
                # are the true top-|kept| — the host rescore is needed
                # only when the kept set can't fill the request after
                # exclusions (pads ate into the non-slack candidates),
                # not on every pad sighting (a per-request O(N.F) host
                # matmul on mostly-negative queries would cliff exactly
                # the traffic the device path exists for)
                keep = idx < n
                if not keep.all():
                    vals, idx = vals[keep], idx[keep]
                    # a rescorer may filter arbitrary candidates, which is
                    # what the +8 over-fetch slack exists to absorb — with
                    # one present, dropped pads must not eat that slack
                    needed = k if rescorer is not None else how_many + len(exclude)
                    if len(idx) < min(n, needed):
                        vals, idx = host_topk(
                            user_vector, k, host_mat[:n], cosine,
                            host_norms[:n] if host_norms is not None else None,
                        )
                        return _trim_pairs(
                            vals, idx, ids, how_many, exclude, rescorer
                        )
            # The device scan selects candidates in bf16 (half the HBM
            # traffic of the memory-bound sweep); near-ties inside the
            # candidate set are then re-ranked EXACTLY by one vectorized
            # f32 gather against the row-aligned host matrix — k*features
            # flops, noise next to the scan it corrects.
            vals, idx = _rerank_exact(user_vector, vals, idx, host_mat, cosine)
            return _trim_pairs(vals, idx, ids, how_many, exclude, rescorer)

        return "fut", fut, _post

    def top_n(
        self,
        user_vector: np.ndarray,
        how_many: int,
        exclude: set[str] = frozenset(),
        rescorer=None,
        cosine: bool = False,
    ) -> list[tuple[str, float]]:
        """Blocking top-N. Post-processing runs on the CALLER's thread —
        never the post pool — so rescorers issuing nested blocking queries
        cannot exhaust the pool into a deadlock."""
        plan = self._top_n_plan(user_vector, how_many, exclude, rescorer, cosine)
        if plan[0] == "done":
            return plan[1]
        _, fut, post = plan
        return post(fut.result())

    def top_n_async(
        self,
        user_vector: np.ndarray,
        how_many: int,
        exclude: set[str] = frozenset(),
        rescorer=None,
        cosine: bool = False,
    ) -> Future:
        """top_n as a Future: the device path chains its host-side
        post-processing (exact re-rank, exclusion/rescorer trim) onto the
        batcher future, so a deferred endpoint holds no thread while the
        coalesced dispatch is in flight."""
        out: Future = Future()
        try:
            plan = self._top_n_plan(
                user_vector, how_many, exclude, rescorer, cosine
            )
        except BaseException as e:  # noqa: BLE001 - carried to caller
            out.set_exception(e)
            return out
        if plan[0] == "done":
            out.set_result(plan[1])
            return out
        _, fut, post = plan
        # post-processing (and everything chained after it: pagination,
        # render, metrics) bounces onto a pool — run inline it would
        # serialize on the batcher dispatcher thread inside the watchdog
        # window, stalling the device pipeline and deadlocking any
        # rescorer that submits its own query
        return chain_future(fut, post, executor=post_pool())

    def get_user_vector(self, user: str) -> np.ndarray | None:
        return self.state.x.get(user)

    def get_item_vector(self, item: str) -> np.ndarray | None:
        return self.state.y.get(item)

    def dot(self, user: str, item: str) -> float | None:
        xu = self.state.x.get(user)
        yi = self.state.y.get(item)
        if xu is None or yi is None:
            return None
        return float(xu @ yi)

    def fold_in_user_vector(
        self, item_strengths: list[tuple[str, float]], implicit: bool | None = None
    ) -> np.ndarray | None:
        """Anonymous-user vector from (item, strength) prefs: iterated
        fold-in against the cached Y solver (EstimateForAnonymous.java:
        47-85 / RecommendToAnonymous pattern)."""
        chol = self.state.yty.get()
        if chol is None:
            return None
        implicit = self.state.implicit if implicit is None else implicit
        xu = np.zeros(self.state.features, dtype=np.float32)
        folded = False
        for item, strength in item_strengths:
            yi = self.state.y.get(item)
            if yi is None:
                continue
            xu = np.asarray(
                compute_updated_xu(
                    jnp.asarray(chol), jnp.float32(strength),
                    jnp.asarray(xu), jnp.asarray(yi), implicit=implicit,
                )
            )
            folded = True
        return xu if folded else None

    def cosine_to_items(self, items: list[str]) -> np.ndarray | None:
        """Mean unit-vector of the given items (similarity queries)."""
        vecs = [self.state.y.get(i) for i in items]
        vecs = [v for v in vecs if v is not None]
        if not vecs:
            return None
        m = np.stack(vecs)
        norms = np.linalg.norm(m, axis=1, keepdims=True)
        norms[norms == 0] = 1
        return (m / norms).mean(axis=0)

    def most_popular_items(self, how_many: int, rescorer=None) -> list[tuple[str, int]]:
        counts: dict[str, int] = {}
        for items in self.state.known_items_snapshot().values():
            for i in items:
                counts[i] = counts.get(i, 0) + 1
        out = [
            (i, c) for i, c in counts.items()
            if rescorer is None or not rescorer.is_filtered(i)
        ]
        out.sort(key=lambda t: (-t[1], t[0]))
        return out[:how_many]

    def representative_items(self, how_many: int) -> list[str]:
        """A spread of items across the factor space. With LSH enabled this
        is the reference's one-item-per-partition sample
        (PopularRepresentativeItems); otherwise an even stride over the
        store serves the same diverse-sample purpose. The LSH branch stays
        entirely on host — no device view is materialized for it."""
        if self.sample_rate < 1.0:
            lsh, ids, parts, _pindex = self._lsh_index()
            if not ids:
                return []
            _, first_rows = np.unique(parts, return_index=True)
            return [ids[int(r)] for r in first_rows[:how_many]]
        _, ids = self._y_view()
        if not ids:
            return []
        stride = max(1, len(ids) // how_many)
        return list(ids[::stride][:how_many])

    def most_active_users(self, how_many: int) -> list[tuple[str, int]]:
        out = [(u, len(s)) for u, s in self.state.known_items_snapshot().items()]
        out.sort(key=lambda t: (-t[1], t[0]))
        return out[:how_many]


def _trim_pairs(
    vals, idx, ids, how_many: int, exclude: set[str], rescorer
) -> list[tuple[str, float]]:
    """Ranked (id, score) pairs after exclusion filtering and optional
    rescoring (the reference's per-request filter/rescore pass)."""
    out: list[tuple[str, float]] = []
    for v, j in zip(np.asarray(vals), np.asarray(idx)):
        ident = ids[int(j)]
        if ident in exclude:
            continue
        score = float(v)
        if rescorer is not None:
            if rescorer.is_filtered(ident):
                continue
            score = rescorer.rescore(ident, score)
            if score is None or np.isnan(score):
                continue
        out.append((ident, score))
        if len(out) == how_many and rescorer is None:
            break
    if rescorer is not None:
        out.sort(key=lambda t: -t[1])
        out = out[:how_many]
    return out


def _rerank_exact(user_vector, vals, idx, host_mat: np.ndarray, cosine: bool):
    """Recompute candidate scores with one vectorized f32 gather against
    the host matrix row-aligned with the device view, and re-sort. Lock-free
    and O(k*features) — no per-row store reads on the request path."""
    idx = np.asarray(idx)
    uv = np.asarray(user_vector, dtype=np.float32)
    rows = host_mat[idx]
    vals = rows @ uv
    if cosine:
        vals = vals / np.maximum(np.linalg.norm(rows, axis=1), 1e-12)
    order = np.argsort(-vals, kind="stable")
    return vals[order], idx[order]


class ALSServingModelManager(AbstractServingModelManager):
    def __init__(self, config: Config):
        super().__init__(config)
        self.als = ALSConfig.from_config(config)
        self.sync = SyncConfig.from_config(config)
        # first-class serving score mode (exact | quantized | approx).
        # Validated here so a typo fails at startup, not on the first
        # /recommend; the model itself still promotes exact -> approx
        # when the legacy oryx.als.approx-recall knob is < 1.
        self.score_mode = str(
            config.get("oryx.serving.api.score-mode", "exact")
        )
        if self.score_mode not in SCORE_MODES:
            raise ValueError(
                "oryx.serving.api.score-mode must be one of "
                f"{SCORE_MODES}, got {self.score_mode!r}"
            )
        self.model: ALSServingModel | None = None
        self._rescorer_provider = _load_rescorer_provider(config)
        configure_post_pool(
            config.get_int("oryx.serving.api.post-workers", 8)
        )

    def get_model(self) -> ALSServingModel | None:
        return self.model

    def rescorer_provider(self):
        return self._rescorer_provider

    def consume_key_message(self, key: str | None, message: str) -> None:
        prev = self.model.state if self.model is not None else None
        state = apply_update_message(prev, key, message, with_known_items=True)
        if state is not None and state is not prev:
            old = self.model
            self.model = ALSServingModel(
                state, sample_rate=self.als.sample_rate,
                approx_recall=self.als.approx_recall,
                num_cores=(self.als.candidate_partitions or None),
                lsh_max_bits_differing=self.als.lsh_max_bits_differing,
                sync=self.sync,
                score_mode=self.score_mode,
            )
            if old is not None:
                old.close()  # stop the replaced model's resync thread
        if key in ("MODEL", "MODEL-REF") and self.model is not None:
            # a generation swap: new id maps and expected-id sets, in a
            # new model or in the one kept (same rank)
            self.model.freeze_due = True

    def close(self) -> None:
        if self.model is not None:
            self.model.close()


def _load_rescorer_provider(config: Config):
    """Optional result-rescoring plugin, config-named like the reference's
    oryx.als.rescorer-provider-class (ALSServingModelManager.java:147-180)."""
    name = config.get_string("oryx.als.rescorer-provider-class", None)
    if not name:
        return None
    from oryx_tpu.common.classutil import load_instance_of

    return load_instance_of(name)
