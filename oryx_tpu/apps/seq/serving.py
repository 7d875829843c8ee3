"""Seq serving tier: GRU session encoder + top-k over item embeddings.

The request path is the ALS shape on purpose: encode the session's item
history into a hidden state (the "user vector"), then score the whole
catalog with ONE matmul + top-k through the shared micro-batcher
(serving/batcher.py) — so coalesced dispatch, shedding, host fallback,
and perfstats MFU all apply unchanged. The device view is a
capacity-padded bf16 matrix kept in step with the live FactorStore by
dirty-row deltas (PR 3's delta_since + scatter_rows): a speed-layer UP
storm re-uploads only the touched rows, and growth within the headroom
scatters into reserved padding rows without changing the batcher's
compiled dispatch shape.
"""

from __future__ import annotations

import logging
import threading
from concurrent.futures import Future

import numpy as np

import jax.numpy as jnp

from oryx_tpu.api import AbstractServingModelManager, ServingModel
from oryx_tpu.common.config import Config
from oryx_tpu.serving.app import chain_future, configure_post_pool, post_pool
from oryx_tpu.serving.batcher import TopKBatcher
from oryx_tpu.apps.seq.common import SeqConfig
from oryx_tpu.apps.seq.state import SeqState, apply_seq_update
from oryx_tpu.ops.seq import encode_sessions

log = logging.getLogger(__name__)


class SeqServingModel(ServingModel):
    def __init__(self, state: SeqState, sync=None):
        from oryx_tpu.apps.als.serving import SyncConfig

        self.state = state
        self.sync = sync or SyncConfig()
        self._sync_lock = threading.Lock()
        # (device E [capacity,d] bf16, ids [n], version, host f32 mirror)
        # swapped as ONE tuple — readers take the snapshot lock-free
        self._device_view: tuple | None = None
        # the next full view build ends by freezing the loaded model out
        # of the collector's sight (serving/viewsync.py): the first
        # build, and the first after each generation
        self.freeze_due = True

    def fraction_loaded(self) -> float:
        return self.state.fraction_loaded()

    def served_version(self) -> int | None:
        view = self._device_view
        return None if view is None else view[2]

    # -- device view (FactorStore delta sync) ------------------------------

    def _view(self) -> tuple:
        view = self._device_view
        if view is not None and view[2] == self.state.items.get_version():
            return view
        with self._sync_lock:
            view = self._device_view
            if view is not None and view[2] == self.state.items.get_version():
                return view
            if view is not None and self._try_apply_delta(view):
                return self._device_view
            return self._build_view_full()

    def _try_apply_delta(self, view: tuple) -> bool:
        """Catch the device view up by dirty-row scatter. Call under
        _sync_lock. Returns False when only a full rebuild can serve
        (drift overflow, growth past capacity, arena compaction after a
        model swap). NOT donated: in-flight coalesced dispatches still
        score the old buffer — the functional scatter IS the double
        buffer (ops/transfer.py scatter_rows contract)."""
        from oryx_tpu.ops.transfer import (
            ShardedMatrix, scatter_rows, scatter_transfer_bytes,
        )
        from oryx_tpu.serving.viewsync import (
            extend_view_ids, note_sync_bytes, set_shard_rows,
            sharded_delta_bytes, view_sync_metrics,
        )
        import time as _time

        t0 = _time.monotonic()
        y_dev, ids, _version, host_mat = view
        n_old = len(ids)
        capacity = int(host_mat.shape[0])
        delta = self.state.items.delta_since(
            view[2],
            max_rows=max(1, int(self.sync.max_delta_fraction * max(n_old, 1))),
        )
        if delta is None or delta.n > capacity:
            return False
        if delta.rows.size == 0:
            return True
        ids = extend_view_ids(ids, delta)
        if ids is None:
            return False
        host_mat[delta.rows] = delta.mat
        # a ShardedMatrix view routes each dirty row into its OWNING
        # shard only (ops/transfer.py scatter_rows)
        y_new = scatter_rows(y_dev, delta.rows, delta.mat)
        self._device_view = (y_new, ids, delta.version, host_mat)

        metrics = view_sync_metrics()
        bytes_of_d = lambda d: scatter_transfer_bytes(d, 2, self.state.dim)
        if isinstance(y_dev, ShardedMatrix):
            n_bytes, by_shard = sharded_delta_bytes(
                y_dev.plan, delta.rows, bytes_of_d
            )
            if delta.n > n_old:
                set_shard_rows(metrics[4], y_dev.plan, delta.n)
        else:
            n_bytes, by_shard = bytes_of_d(delta.rows.size), None
        note_sync_bytes(metrics[0], n_bytes, by_shard)
        metrics[1].observe(_time.monotonic() - t0)
        metrics[2].inc(kind="delta")
        return True

    def _build_view_full(self) -> tuple:
        """Initial load / delta-overflow fallback: one capacity-padded
        bf16 upload. Call under _sync_lock."""
        from oryx_tpu.ops.transfer import (
            device_put_maybe_chunked, row_capacity, sharded_device_put,
            view_rows,
        )
        from oryx_tpu.serving.viewsync import (
            freeze_loaded_model, note_sync_bytes, set_shard_rows,
            view_sync_metrics,
        )
        import time as _time

        t0 = _time.monotonic()
        mat, ids, version = self.state.items.snapshot()
        mat = np.asarray(mat, dtype=np.float32)
        n = len(ids)
        # capacity rows in the shape the top-k kernel DMAs (per shard when
        # sharded; ops/transfer.py view_rows): no dispatch pads the view
        cap = view_rows(
            row_capacity(n, self.sync.capacity_headroom), self.state.dim,
            jnp.bfloat16, self.sync.shard_count,
        )
        if cap > n:
            host = np.zeros((cap, self.state.dim), dtype=np.float32)
            host[:n] = mat
        else:
            host = mat
        by_shard = None
        if self.sync.shard_count > 1:
            # the seq item-embedding matrix shards exactly like the ALS
            # item factors: same plan, same owning-shard delta routing,
            # same cross-shard merge on the serve path
            y_dev = sharded_device_put(
                host, self.sync.shard_count, dtype=jnp.bfloat16
            )
            set_shard_rows(view_sync_metrics()[4], y_dev.plan, n)
            by_shard = {
                s: y_dev.plan.size(s) * self.state.dim * 2
                for s in range(y_dev.plan.n_shards)
            }
        else:
            y_dev = device_put_maybe_chunked(host, dtype=jnp.bfloat16)
        view = (y_dev, ids, version, host)
        self._device_view = view
        metrics = view_sync_metrics()
        note_sync_bytes(metrics[0], cap * self.state.dim * 2, by_shard)
        metrics[1].observe(_time.monotonic() - t0)
        metrics[2].inc(kind="full")
        if self.freeze_due:
            self.freeze_due = False
            freeze_loaded_model()
        return view

    # -- queries -----------------------------------------------------------

    def encode(self, context_items: list[str]) -> np.ndarray | None:
        """Session item history (oldest -> newest) -> hidden state, or
        None when no context item is known to the model."""
        if not context_items or self.state.params is None:
            return None
        ctx = context_items[-self.state.window:]
        vecs, have = self.state.items.get_many(ctx)
        if not have.any():
            return None
        # left-pad to the fixed window so the jitted encoder compiles ONE
        # (1, window, d) program for every context length (an unpadded
        # call would compile per distinct session length on the hot path)
        w = self.state.window
        mat = np.zeros((1, w, self.state.dim), dtype=np.float32)
        mask = np.zeros((1, w), dtype=np.float32)
        mat[0, w - len(ctx):] = vecs
        mask[0, w - len(ctx):] = have.astype(np.float32)
        return encode_sessions(self.state.params, mat, mask)[0]

    def next_items_async(
        self,
        context_items: list[str],
        how_many: int,
        exclude: set[str] = frozenset(),
    ) -> Future:
        """Top next items for a session context, excluding the session's
        own history — a Future so the deferred endpoint holds no worker
        thread while the coalesced device dispatch is in flight."""
        out: Future = Future()
        try:
            h = self.encode(context_items)
        except BaseException as e:  # noqa: BLE001 - carried to caller
            out.set_exception(e)
            return out
        if h is None:
            out.set_result(None)  # no known context item: 404 at the route
            return out
        y_dev, ids, _version, host_mat = self._view()
        n = len(ids)
        if n == 0:
            out.set_result([])
            return out
        from oryx_tpu.common.tracing import current_span

        span = current_span()
        trace_id = span.trace_id if span is not None else None
        k = min(n, how_many + len(exclude) + 8)
        fut = TopKBatcher.shared().submit_nowait(
            h, k, y_dev, host_mat=host_mat, valid_rows=n,
        )

        def _post(result):
            from oryx_tpu.serving.batcher import host_topk

            vals, idx = np.asarray(result[0]), np.asarray(result[1])
            keep = idx < n  # capacity-padding rows never reach callers
            if not keep.all():
                vals, idx = vals[keep], idx[keep]
                # pads score 0.0 and displace real NEGATIVE-scoring rows:
                # when the kept set can no longer fill the request after
                # exclusions, rescore exactly on the host (the ALS pad
                # backstop, apps/als/serving.py _post)
                if len(idx) < min(n, how_many + len(exclude)):
                    vals, idx = host_topk(
                        np.asarray(h, dtype=np.float32), k, host_mat[:n], False, None
                    )
                    vals, idx = np.asarray(vals), np.asarray(idx)
            # exact f32 re-rank against the row-aligned host mirror (the
            # device scan selects in bf16)
            rows = host_mat[idx]
            vals = rows @ np.asarray(h, dtype=np.float32)
            order = np.argsort(-vals, kind="stable")
            pairs = []
            for j in order:
                ident = ids[int(idx[j])]
                if ident in exclude:
                    continue
                pairs.append([ident, float(vals[j])])
                if len(pairs) == how_many:
                    break
            if pairs:
                # live recall: offer the served page to the shadow
                # rescore sampler (post-pool thread, never the batcher
                # dispatcher; the exact reference is the row-aligned
                # host mirror, read by reference on the drain thread)
                from oryx_tpu.common.qualitystats import get_qualitystats

                get_qualitystats().maybe_sample(
                    np.asarray(h, dtype=np.float32), pairs,
                    how_many=how_many, exclude=exclude,
                    score_mode="exact", trace_id=trace_id,
                    snapshot_fn=lambda: (host_mat, ids, n),
                )
            return pairs

        return chain_future(fut, _post, executor=post_pool())

    def next_items(
        self,
        context_items: list[str],
        how_many: int,
        exclude: set[str] = frozenset(),
    ):
        return self.next_items_async(context_items, how_many, exclude).result()


class SeqServingModelManager(AbstractServingModelManager):
    def __init__(self, config: Config):
        super().__init__(config)
        from oryx_tpu.apps.als.serving import SyncConfig

        self.seq = SeqConfig.from_config(config)
        self.sync = SyncConfig.from_config(config)
        self.model: SeqServingModel | None = None
        configure_post_pool(
            config.get_int("oryx.serving.api.post-workers", 8)
        )

    def get_model(self) -> SeqServingModel | None:
        return self.model

    def consume_key_message(self, key: str | None, message: str) -> None:
        prev = self.model.state if self.model is not None else None
        state = apply_seq_update(prev, key, message)
        if state is not None and state is not prev:
            self.model = SeqServingModel(state, sync=self.sync)
        if key in ("MODEL", "MODEL-REF") and self.model is not None:
            self.model.freeze_due = True  # a generation swap: new id maps
