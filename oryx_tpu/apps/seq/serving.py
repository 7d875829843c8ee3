"""Seq serving tier: a session encoder + top-k over item embeddings.

The request path is the ALS shape on purpose: encode the session's item
history into hidden states (the "user vectors": one from the GRU, one a
block position from an encoder that generates, ops/sdar.py) through the
batched encoder step (serving/stepper.py), then score the whole
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
import time
from concurrent.futures import Future

import numpy as np

import jax.numpy as jnp

from oryx_tpu.api import AbstractServingModelManager, ServingModel
from oryx_tpu.common.config import Config
from oryx_tpu.serving.app import configure_post_pool, post_pool
from oryx_tpu.serving.batcher import TopKBatcher
from oryx_tpu.serving.futureutil import try_set_exception, try_set_result
from oryx_tpu.apps.seq.common import SeqConfig
from oryx_tpu.apps.seq.state import SeqState, apply_seq_update

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
        # (parameters, their Engine) and (view ids, view row -> E_in row)
        self._engine_of: tuple | None = None
        self._row_token: tuple | None = None

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

    def _engine(self):
        """This generation's encoder on the device (serving/stepper.py):
        made with the model's first request, again when a MODEL message
        swapped the parameters in."""
        params = self.state.params
        eng = self._engine_of
        if eng is None or eng[0] is not params:
            from oryx_tpu.serving.stepper import Engine

            with self._sync_lock:
                eng = self._engine_of
                if eng is None or eng[0] is not params:
                    eng = (params, Engine(self.state.encoder, params, head=self._head))
                    self._engine_of = eng
        return eng[1]

    def _head(self) -> tuple:
        """What a generating encoder's step takes its logits over: the
        served view, its real rows, and for each view row its row of the
        encoder's input embedding (the encoder's `unknown_token`, [MASK],
        where the item came by UP after the model and has none yet)."""
        y_dev, ids, _version, _host = self._view()
        mask_id = self.state.encoder.unknown_token
        if mask_id is None:  # the step feeds back the view's own row (a tied embedding)
            return y_dev, len(ids), None
        cached = self._row_token
        if cached is None or cached[0] is not ids:
            token_of = self.state.token_of
            rows = np.full((int(y_dev.shape[0]),), mask_id, dtype=np.int32)
            rows[: len(ids)] = [token_of.get(i, mask_id) for i in ids]
            cached = (ids, jnp.asarray(rows))
            self._row_token = cached
        return y_dev, len(ids), cached[1]

    def _encode_async(self, context_items: list[str]) -> Future | None:
        """Future of the stepper's `Encoded` for a session, or None when
        no context item is known to the model. Every request of every
        encoder is encoded this way: admitted by the batched encoder step,
        never by a device call of its own in the request's thread."""
        if not context_items or self.state.params is None:
            return None
        prepared = self.state.encoder.prepare(self.state, context_items)
        if prepared is None:
            return None
        from oryx_tpu.serving.stepper import SeqStepper

        return SeqStepper.shared().submit(self._engine(), prepared)

    def encode(self, context_items: list[str]) -> np.ndarray | None:
        """Session item history (oldest -> newest) -> hidden state ([d];
        [block, d] from an encoder that generates a block), or None when
        no context item is known to the model. A synchronous wrapper:
        submits to the stepper and waits."""
        fut = self._encode_async(context_items)
        if fut is None:
            return None
        hidden = fut.result().hidden
        return hidden[0] if hidden.shape[0] == 1 else hidden

    def next_items_async(
        self,
        context_items: list[str],
        how_many: int,
        exclude: set[str] = frozenset(),
    ) -> Future:
        """Top next items for a session context, excluding the session's
        own history — a Future so the deferred endpoint holds no worker
        thread while the encoder's steps and the coalesced scan are in
        flight. An encoder that generates a block answers one entry a
        position: {"item": the item fixed there, "step": the step that
        fixed it, "next": the `how_many` best [id, score] by that step's
        logits}; the GRU answers its [id, score] pairs as before."""
        out: Future = Future()
        try:
            enc_fut = self._encode_async(context_items)
        except BaseException as e:  # noqa: BLE001 - carried to caller
            out.set_exception(e)
            return out
        if enc_fut is None:
            out.set_result(None)  # no known context item: 404 at the route
            return out
        y_dev, ids, _version, host_mat = self._view()
        n = len(ids)
        if n == 0:
            out.set_result([])
            return out
        from oryx_tpu.common.perfattr import current_ledger, swap_ledger
        from oryx_tpu.common.tracing import current_span, get_tracer

        span = current_span()
        trace_id = span.trace_id if span is not None else None
        ledger = current_ledger()
        k = min(n, how_many + len(exclude) + 8)
        generates = self.state.encoder.steps > 0

        def _scan(f):
            # the stepper's thread: enqueue only. One row a position goes
            # to the shared batcher, all in one enqueue so that they ride
            # one dispatch; the first carries the request's ledger (its
            # phases are the request's)
            try:
                encoded = f.result()
                prev = swap_ledger(ledger)
                try:
                    futs = TopKBatcher.shared().submit_many_nowait(
                        encoded.hidden, k, y_dev, host_mat=host_mat,
                        valid_rows=n,
                    )
                finally:
                    swap_ledger(prev)
            except BaseException as e:  # noqa: BLE001 - carried to caller
                try_set_exception(out, e)
                return
            results: list = [None] * len(futs)
            left = [len(futs)]
            lock = threading.Lock()

            def _one(i, g):
                try:
                    results[i] = g.result()
                except BaseException as e:  # noqa: BLE001 - carried to caller
                    try_set_exception(out, e)
                    return
                with lock:
                    left[0] -= 1
                    last = left[0] == 0
                if last:
                    try:
                        post_pool().submit(_finish, encoded, results)
                    except Exception:  # pool shut down: fail, never run inline
                        try_set_exception(
                            out, RuntimeError("post-processing pool is shut down")
                        )

            for i, g in enumerate(futs):
                g.add_done_callback(lambda g, i=i: _one(i, g))

        def _finish(encoded, results):
            try:
                t_post = time.monotonic()
                # the region around exactly what the `rerank` stage times
                with get_tracer().region("post.rerank", cpu=True):
                    pages = [
                        _post(h, r) for h, r in zip(encoded.hidden, results)
                    ]
                    if generates:
                        answer = [
                            {"item": ids[int(row)], "step": int(step), "next": page}
                            for row, step, page in zip(encoded.rows, encoded.steps, pages)
                        ]
                    else:
                        answer = pages[0]
                if ledger is not None:
                    # the first two parts of `serialize` (perfattr.POST_STAGES),
                    # as apps/als/serving.py _post stamps them
                    tail = ledger.last_end()
                    if tail is not None:
                        ledger.add_stage("handoff", max(0.0, t_post - tail))
                        ledger.add_stage("rerank", time.monotonic() - t_post)
            except BaseException as e:  # noqa: BLE001 - carried to caller
                try_set_exception(out, e)
                return
            try_set_result(out, answer)

        def _post(h, result):
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

        enc_fut.add_done_callback(_scan)
        return out

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
