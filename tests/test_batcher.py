"""Serving micro-batcher: coalesced device dispatch correctness.

The batched path must be indistinguishable from per-request topk_dot calls
(the reference's per-request partition fan-out, ALSServingModel.java:
264-279), under concurrency, mixed k, and mid-window model swaps.
"""

import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from oryx_tpu.ops.als import topk_dot
from oryx_tpu.serving.batcher import TopKBatcher, k_bucket, _Pending
from concurrent.futures import Future


@pytest.fixture
def y():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.normal(size=(200, 8)), dtype=jnp.float32)


def _direct(vec, k, y):
    vals, idx = topk_dot(jnp.asarray(vec, dtype=jnp.float32), y, k=k)
    return np.asarray(vals), np.asarray(idx)


def test_k_bucket():
    assert k_bucket(1) == 16
    assert k_bucket(16) == 16
    # 17..32 stay on the fused-kernel-eligible 32 bucket (a default
    # howMany=10 overfetches to 18)
    assert k_bucket(17) == 32
    assert k_bucket(33) == 128
    assert k_bucket(128) == 128
    assert k_bucket(129) == 1024
    assert k_bucket(5000) == 8192


def test_single_submit_matches_direct(y):
    b = TopKBatcher()
    vec = np.random.default_rng(0).normal(size=8).astype(np.float32)
    vals, idx = b.submit(vec, 10, y)
    dvals, didx = _direct(vec, 10, y)
    assert list(idx) == list(didx)
    np.testing.assert_allclose(vals, dvals, rtol=1e-5)
    b.close()


def test_concurrent_submits_all_correct(y):
    b = TopKBatcher()
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(32, 8)).astype(np.float32)
    results = [None] * 32
    ks = [5 + (i % 7) for i in range(32)]

    def go(i):
        results[i] = b.submit(vecs[i], ks[i], y)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(32):
        vals, idx = results[i]
        assert len(idx) == ks[i]
        dvals, didx = _direct(vecs[i], ks[i], y)
        assert list(idx) == list(didx)
        np.testing.assert_allclose(vals, dvals, rtol=1e-5)
    b.close()


def test_dispatch_groups_by_matrix_and_bucket(y):
    """One window containing two target matrices and two k buckets must
    produce correct per-request results (a MODEL swap mid-window splits the
    dispatch, it doesn't corrupt it)."""
    rng = np.random.default_rng(2)
    y2 = jnp.asarray(rng.normal(size=(50, 8)), dtype=jnp.float32)
    b = TopKBatcher()
    reqs = []
    for i in range(6):
        tgt = y if i % 2 == 0 else y2
        k = 3 if i < 3 else 20
        vec = rng.normal(size=8).astype(np.float32)
        reqs.append(_Pending(vec, k, tgt, Future()))
    for item in b._launch(reqs):
        b._resolve(item)
    assert b.dispatches == 4  # 2 matrices x 2 k-buckets
    assert b.coalesced == 6
    for p in reqs:
        vals, idx = p.future.result(timeout=5)
        k_eff = min(p.k, p.y.shape[0])
        assert len(idx) == k_eff
        dvals, didx = _direct(p.vec, k_eff, p.y)
        assert list(idx) == list(didx)
        np.testing.assert_allclose(vals, dvals, rtol=1e-5)


def test_launch_tells_the_kernel_how_many_rows_are_real(y, monkeypatch):
    """Each group's dispatch carries rows = len(group) beside the padded
    query block, whatever the block is padded to."""
    from oryx_tpu.ops import als

    seen = []
    real_topk_dot_batch = als.topk_dot_batch

    def recorder(xs, y, **kw):
        seen.append((xs.shape[0], kw.get("rows"), kw.get("counted")))
        return real_topk_dot_batch(xs, y, **kw)

    monkeypatch.setattr(als, "topk_dot_batch", recorder)
    rng = np.random.default_rng(6)
    b = TopKBatcher()
    reqs = [
        _Pending(rng.normal(size=8).astype(np.float32), k, y, Future())
        for k in (3, 4, 5, 40, 41)
    ]
    for item in b._launch(reqs):
        b._resolve(item)
    assert sorted(seen) == [(2, 2, True), (4, 3, True)]  # k-buckets 128 and 16
    for p in reqs:
        assert list(p.future.result(timeout=5)[1]) == list(_direct(p.vec, p.k, y)[1])


@pytest.mark.parametrize("requests, live", [(3, 1), (129, 2), (512, 4)])
def test_fused_dispatch_counts_the_row_blocks_the_kernel_skipped(
    y, monkeypatch, requests, live
):
    """A dispatch of the accelerator's 512-row bucket through the fused
    kernel (the Pallas interpreter standing in for the chip): the record and
    the two counters read the kernel's own count of the row blocks it walked,
    one of four for three requests; and the record and `oryx_topk_fold_tiles`
    carry the sublane tiles its folds sorted, one of a block's sixteen for
    three requests (ISSUE 32)."""
    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.ops import als
    from oryx_tpu.ops.pallas_topk import topk_dot_batch_pallas

    def fused(xs, y, *, k, recall=1.0, counted=False, rows=None):
        return topk_dot_batch_pallas(
            xs, y, k=k, interpret=True, counted=counted, rows=rows
        )

    monkeypatch.setattr(als, "topk_dot_batch", fused)
    b = TopKBatcher()
    b.register_gauges()
    b._peak_flops = None  # _note_device has run: the platform below stays
    b._on_accel = True
    rng = np.random.default_rng(requests)
    reqs = [
        _Pending(rng.normal(size=8).astype(np.float32), 10, y, Future())
        for _ in range(requests)
    ]
    t_mark = time.monotonic()
    for item in b._launch(reqs):
        b._resolve(item)
    (rec,) = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert (rec.rows, rec.padded_rows) == (requests, 512)
    assert (rec.row_blocks, rec.row_blocks_skipped) == (4, 4 - live)
    # 200 items in one item block of 256: two chunks a row block walked
    assert rec.chunks_total == 2 * live
    args = rec.chrome_event(1)["args"]
    assert (args["row_blocks"], args["row_blocks_skipped"]) == (4, 4 - live)
    assert (b.row_blocks, b.row_blocks_skipped) == (4, 4 - live)
    # 3 requests: every fold sorts the one live tile; 512: whole blocks of
    # sixteen; 129: block 0 whole and block 1's one live tile
    # the kernel's fourth count rides along: the fired chunks it placed
    # without a sort, which sort no tile (ISSUE 35)
    assert rec.chunks_folded >= live
    sorts = rec.chunks_folded - rec.chunks_inserted
    assert 0 <= rec.chunks_inserted <= rec.chunks_folded - live  # chunk 0 folds
    lo, hi = {3: (1, 1), 129: (2, 15), 512: (16, 16)}[requests]
    assert lo * sorts <= rec.fold_tiles <= hi * sorts
    assert args["fold_tiles"] == b.fold_tiles == rec.fold_tiles
    assert args["chunks_inserted"] == b.chunks_inserted == rec.chunks_inserted
    gauges = dict(
        line.split() for line in get_registry().render_prometheus().splitlines()
        if line.startswith(
            ("oryx_topk_row_blocks", "oryx_topk_fold_tiles", "oryx_topk_chunks_inserted")
        )
    )
    assert float(gauges["oryx_topk_row_blocks"]) == 4.0
    assert float(gauges["oryx_topk_row_blocks_skipped"]) == float(4 - live)
    assert float(gauges["oryx_topk_fold_tiles"]) == float(rec.fold_tiles)
    assert float(gauges["oryx_topk_chunks_inserted"]) == float(rec.chunks_inserted)
    for p in reqs[:: max(1, requests // 7)]:
        assert list(p.future.result(timeout=5)[1]) == list(_direct(p.vec, 10, y)[1])


@pytest.mark.parametrize(
    "valid_rows, largest, live_blocks",
    [
        ((18_000, 20_000, 19_000), 20_000, 3),  # the group's largest count
        ((8_192,), 8_192, 1),                   # an item block's edge
        ((20_000, None), 32_768, 4),            # no count: the whole view
    ],
    ids=["largest-of-three", "block-edge", "none-is-the-view"],
)
def test_fused_dispatch_walks_the_views_valid_item_blocks_alone(
    monkeypatch, valid_rows, largest, live_blocks
):
    """A view stored with room to grow (32,768 rows in four item blocks of
    8,192, the rows past its items scoring far ABOVE them): the group is
    dispatched with its largest `valid_rows`, the count reaches the kernel
    beside `rows` as ONE staged int32[2] array, the kernel walks the valid
    item blocks alone, and the record, the counters and the gauges say so
    (ISSUE 40). What the launch hands `topk_dot_batch` is on the HOST, the
    block in the view's dtype and the counts an `np.int32[2]`, and nothing
    is uploaded or computed on the device before that call: the operands
    ride the jitted call (ISSUE 45)."""
    import jax

    from oryx_tpu.common.metrics import get_registry
    from oryx_tpu.common.perfstats import get_perfstats
    from oryx_tpu.ops import als, pallas_topk

    capacity, feats = 32_768, 8
    rng = np.random.default_rng(40)
    host = rng.integers(-9, 10, size=(capacity, 128)).astype(np.float32)
    host[:, 0], host[:, feats:] = 0.0, 0.0  # lane-padded, as a view is stored
    host[largest:, 0] = 256.0  # behind the items: 2,048 a row against 7 x 81
    view = jnp.asarray(host, dtype=jnp.bfloat16)
    assert pallas_topk.view_shape(capacity, feats, view.dtype) == view.shape

    staged, calls, eager = [], [], []
    before_the_call = [False]
    real_stage = pallas_topk.stage_counts

    def stage_counts(rows, n_valid, n_queries, n_items):
        staged.append((rows, n_valid, n_queries, n_items))
        return real_stage(rows, n_valid, n_queries, n_items)

    def fused(xs, y, *, k, recall=1.0, counted=False, rows=None, **kw):
        before_the_call[0] = False
        calls.append((xs, rows, kw))
        return pallas_topk.topk_dot_batch_pallas(
            xs, y, k=k, interpret=True, counted=counted, rows=rows
        )

    def spy(name, real):
        def eager_op(*args, **kw):
            if before_the_call[0]:
                eager.append(name)
            return real(*args, **kw)
        return eager_op

    monkeypatch.setattr(als, "_on_tpu", lambda a: True)  # topk_path: "pallas"
    monkeypatch.setattr(pallas_topk, "stage_counts", stage_counts)
    monkeypatch.setattr(als, "topk_dot_batch", fused)
    for mod, name in [(jnp, "asarray"), (jnp, "pad"), (jnp, "stack"), (jax, "device_put")]:
        monkeypatch.setattr(mod, name, spy(name, getattr(mod, name)))
    b = TopKBatcher()
    b.register_gauges()
    b._peak_flops = None  # _note_device has run: the platform below stays
    b._on_accel = True
    reqs = []
    for n in valid_rows:
        vec = rng.integers(-9, 10, size=feats).astype(np.float32)
        vec[0] = 8.0
        reqs.append(_Pending(vec, 10, view, Future(), valid_rows=n))
    t_mark = time.monotonic()
    before_the_call[0] = True
    for item in b._launch(reqs):
        b._resolve(item)
    # one array for both counts: staged once from two host numbers, the call
    # is handed that very array and no count beside it, and the kernel's
    # wrapper passes it through
    ((xs, rows_d, extra),) = calls
    assert isinstance(rows_d, np.ndarray) and not extra
    assert rows_d.dtype == np.int32 and rows_d.tolist() == [len(reqs), largest]
    # the block: formed on the host at the view's width and in its dtype,
    # the real rows the requests' vectors rounded to it, the rest zeros
    assert isinstance(xs, np.ndarray) and not eager
    assert (xs.shape, xs.dtype) == ((512, 128), view.dtype)
    want = np.zeros((512, 128), dtype=np.float32)
    want[: len(reqs), :feats] = [p.vec for p in reqs]
    assert np.array_equal(xs.astype(np.float32), want)  # small integers: exact
    assert staged[0] == (len(reqs), largest, 512, capacity)
    assert [(s[0] is rows_d, s[1]) for s in staged[1:]] == [(True, None)]
    (rec,) = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert (rec.valid_rows, rec.capacity_rows) == (largest, capacity)
    # one of four row blocks walked, over 64 chunks a live item block; the
    # skipped row blocks are read against what a row block REALLY walks
    assert rec.chunks_total == live_blocks * 64
    assert (rec.row_blocks, rec.row_blocks_skipped) == (4, 3)
    assert rec.item_chunks_skipped == (4 - live_blocks) * 64
    assert rec.chrome_event(1)["args"]["item_chunks_skipped"] == rec.item_chunks_skipped
    assert (b.row_blocks_skipped, b.item_chunks_skipped) == (3, rec.item_chunks_skipped)
    gauges = dict(
        line.split() for line in get_registry().render_prometheus().splitlines()
        if line.startswith(("oryx_topk_item_chunks_skipped", "oryx_topk_chunks "))
    )
    assert float(gauges["oryx_topk_item_chunks_skipped"]) == float(rec.item_chunks_skipped)
    assert float(gauges["oryx_topk_chunks"]) == float(rec.chunks_total)
    # every request: the exact top-10 of the items the dispatch counted, none
    # of the rows behind them
    for p in reqs:
        vals, idx = p.future.result(timeout=5)
        d_vals, d_idx = _direct(p.vec, 10, jnp.asarray(host[:largest, :feats]))
        assert list(idx) == list(d_idx) and list(vals) == list(d_vals)


@pytest.mark.parametrize(
    "view_dtype, recall, block_dtype",
    [
        (jnp.float32, 1.0, np.float32),
        (jnp.bfloat16, 1.0, jnp.bfloat16),
        (jnp.bfloat16, 0.95, jnp.bfloat16),
        ("int8", 1.0, np.float32),  # the call quantises a float32 block itself
    ],
    ids=["xla-f32", "xla-bf16", "approx-bf16", "xla-int8"],
)
def test_a_launch_hands_the_call_a_host_block_in_the_dtype_the_path_scores_in(
    monkeypatch, view_dtype, recall, block_dtype
):
    """Off the fused kernel too: the block reaches `topk_dot_batch` as a
    numpy array in the dtype its jitted call takes, `rows` as the host
    number it was (no kernel, no counts' array), and the answers are those
    of the parent's staging of the same queries (a float32 upload, then the
    cast on the device), bit for bit (ISSUE 45)."""
    from oryx_tpu.ops import als
    from oryx_tpu.ops.transfer import QuantizedMatrix

    rng = np.random.default_rng(45)
    host = rng.normal(size=(200, 8)).astype(np.float32)
    if view_dtype == "int8":
        view = QuantizedMatrix(
            jnp.asarray(np.round(host * 40), dtype=jnp.int8),
            jnp.full((200,), 1 / 40, dtype=jnp.float32),
        )
    else:
        view = jnp.asarray(host, dtype=view_dtype)
    calls = []
    real = als.topk_dot_batch

    def recorder(xs, y, **kw):
        calls.append((xs, kw))
        return real(xs, y, **kw)

    monkeypatch.setattr(als, "topk_dot_batch", recorder)
    b = TopKBatcher()
    reqs = [
        _Pending(rng.normal(size=8).astype(np.float32), 10, view, Future(), recall=recall)
        for _ in range(3)
    ]
    for item in b._launch(reqs):
        b._resolve(item)
    ((xs, kw),) = calls
    assert isinstance(xs, np.ndarray) and xs.dtype == block_dtype
    assert xs.shape[0] >= 3 and kw["rows"] == 3 and isinstance(kw["rows"], int)
    parent = jnp.zeros(xs.shape, dtype=jnp.float32).at[:3].set(
        jnp.asarray(np.stack([p.vec for p in reqs]))
    )
    if view_dtype != "int8":
        parent = jnp.asarray(parent, dtype=view_dtype)
    want_vals, want_idx = real(parent, view, k=kw["k"], recall=recall)
    for i, p in enumerate(reqs):
        vals, idx = p.future.result(timeout=5)
        assert list(idx) == list(np.asarray(want_idx)[i][: len(idx)])
        assert list(vals) == list(np.asarray(want_vals)[i][: len(vals)])


def test_a_dispatch_off_the_fused_path_counts_no_row_blocks(y):
    from oryx_tpu.common.perfstats import get_perfstats

    b = TopKBatcher()
    t_mark = time.monotonic()
    b.submit(np.ones(8, dtype=np.float32), 10, y)
    b.close()
    (rec,) = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert rec.row_blocks is None and rec.row_blocks_skipped is None
    assert rec.fold_tiles is None and rec.chunks_inserted is None
    assert rec.item_chunks_skipped is None and b.item_chunks_skipped == 0
    assert not {
        "row_blocks", "fold_tiles", "chunks_inserted", "item_chunks_skipped"
    } & set(rec.chrome_event(1)["args"])
    assert (b.row_blocks, b.row_blocks_skipped, b.fold_tiles, b.chunks_inserted) == (0, 0, 0, 0)


def test_k_larger_than_items():
    rng = np.random.default_rng(4)
    small = jnp.asarray(rng.normal(size=(7, 4)), dtype=jnp.float32)
    b = TopKBatcher()
    vals, idx = b.submit(rng.normal(size=4).astype(np.float32), 50, small)
    assert len(idx) == 7  # capped at item count
    b.close()


def test_shared_is_singleton():
    assert TopKBatcher.shared() is TopKBatcher.shared()

# ---------------------------------------------------------------------------
# hung-device failover (a device call can hang forever instead of
# raising; the serving tier must degrade, not die)
# ---------------------------------------------------------------------------


from oryx_tpu.ops.als import topk_dot_batch as _real_topk_dot_batch
from e2e_common import WedgeHook


def _WedgeHook():
    return WedgeHook(_real_topk_dot_batch, block_first_only=True)


def _host_mat(y):
    return np.asarray(y, dtype=np.float32)


def test_wedged_dispatch_fails_over_to_host(y, monkeypatch):
    hook = _WedgeHook()
    monkeypatch.setattr(
        "oryx_tpu.ops.als.topk_dot_batch", hook, raising=True
    )
    b = TopKBatcher(device_timeout=0.5, probe_interval=0.2, compile_timeout=0.5)
    vec = np.random.default_rng(0).normal(size=8).astype(np.float32)
    # the dispatch wedges; the watchdog must host-resolve within ~timeout
    vals, idx = b.submit(vec, 10, y, host_mat=_host_mat(y))
    assert b.device_failovers == 1
    dvals, didx = _direct(vec, 10, y)
    assert list(idx) == list(didx)
    np.testing.assert_allclose(vals, dvals, rtol=1e-5)
    # while down, new submits take the host path immediately
    vals2, idx2 = b.submit(vec, 10, y, host_mat=_host_mat(y))
    assert list(idx2) == list(didx)
    assert b.host_fallbacks >= 2
    hook.release.set()
    b.close()


def test_wedged_dispatch_without_host_mat_errors(y, monkeypatch):
    hook = _WedgeHook()
    monkeypatch.setattr(
        "oryx_tpu.ops.als.topk_dot_batch", hook, raising=True
    )
    b = TopKBatcher(device_timeout=0.5, probe_interval=0.2, compile_timeout=0.5)
    vec = np.random.default_rng(0).normal(size=8).astype(np.float32)
    with pytest.raises(RuntimeError):
        b.submit(vec, 10, y)
    hook.release.set()
    b.close()


def test_device_recovery_resumes_device_path(y, monkeypatch):
    hook = _WedgeHook()
    monkeypatch.setattr(
        "oryx_tpu.ops.als.topk_dot_batch", hook, raising=True
    )
    b = TopKBatcher(device_timeout=0.4, probe_interval=0.1, compile_timeout=0.4)
    vec = np.random.default_rng(0).normal(size=8).astype(np.float32)
    b.submit(vec, 10, y, host_mat=_host_mat(y))  # wedge + failover
    assert b._device_down.is_set()
    hook.release.set()  # transport recovers
    # submits keep working throughout; eventually a probe flips the path
    deadline = __import__("time").time() + 10
    while b._device_down.is_set() and __import__("time").time() < deadline:
        b.submit(vec, 10, y, host_mat=_host_mat(y))
        __import__("time").sleep(0.05)
    assert not b._device_down.is_set(), "probe never recovered the device"
    # device path again: a fresh dispatcher thread serves the queue
    vals, idx = b.submit(vec, 10, y, host_mat=_host_mat(y))
    dvals, didx = _direct(vec, 10, y)
    assert list(idx) == list(didx)
    b.close()


def test_first_dispatch_compile_grace_defers_watchdog(y, monkeypatch):
    """A first dispatch of a shape that runs past device_timeout but within
    compile_timeout is a cold XLA compile, not a wedge: the watchdog must
    not fail it over to host scoring (a cold compile can take minutes
    per shape, and a misread here degrades the device path)."""
    import threading
    import time as _time

    hook = _WedgeHook()
    monkeypatch.setattr("oryx_tpu.ops.als.topk_dot_batch", hook, raising=True)
    b = TopKBatcher(device_timeout=0.3, probe_interval=0.1, compile_timeout=15.0)
    vec = np.random.default_rng(0).normal(size=8).astype(np.float32)
    threading.Thread(
        target=lambda: (_time.sleep(1.2), hook.release.set()), daemon=True
    ).start()
    vals, idx = b.submit(vec, 10, y, host_mat=_host_mat(y))
    assert b.device_failovers == 0
    assert b.host_fallbacks == 0
    dvals, didx = _direct(vec, 10, y)
    assert list(idx) == list(didx)
    np.testing.assert_allclose(vals, dvals, rtol=1e-5)
    b.close()


def test_accel_batch_padding_two_buckets():
    """On an accelerator the batch dimension pads to only two buckets
    (each extra shape is a cold compile); on CPU it stays fine-grained
    pow2."""
    from oryx_tpu.serving.batcher import MAX_BATCH, _pad_rows

    assert _pad_rows(1, True) == 512
    assert _pad_rows(512, True) == 512
    assert _pad_rows(513, True) == MAX_BATCH
    # beyond the ladder (custom max_batch): unpadded, never shrunk
    assert _pad_rows(MAX_BATCH + 1, True) == MAX_BATCH + 1
    assert _pad_rows(1, False) == 1
    assert _pad_rows(22, False) == 32
    assert _pad_rows(513, False) == 1024


def test_host_topk_cosine_matches_numpy(y):
    from oryx_tpu.serving.batcher import host_topk

    hm = _host_mat(y)
    vec = np.random.default_rng(5).normal(size=8).astype(np.float32)
    vals, idx = host_topk(vec, 5, hm, cosine=True)
    ref = (hm @ vec) / np.maximum(np.linalg.norm(hm, axis=1), 1e-12)
    order = np.argsort(-ref)[:5]
    assert list(idx) == list(order)
    np.testing.assert_allclose(vals, ref[order], rtol=1e-5)


def test_recall_groups_and_approx_path(y):
    """Requests with different recall targets dispatch in separate groups,
    and the approx path (exact on CPU) returns correct top-k."""
    b = TopKBatcher()
    vec = np.random.default_rng(6).normal(size=8).astype(np.float32)
    reqs = [
        _Pending(vec, 5, y, Future(), recall=1.0),
        _Pending(vec, 5, y, Future(), recall=0.95),
    ]
    for item in b._launch(reqs):
        b._resolve(item)
    assert b.dispatches == 2  # split by recall
    dvals, didx = _direct(vec, 5, y)
    for p in reqs:
        vals, idx = p.future.result(timeout=5)
        assert list(idx) == list(didx)  # CPU approx_max_k is exact
    b.close()


def test_serving_model_approx_recall_wired():
    """oryx.als.approx-recall reaches the model and the batcher dispatch."""
    from oryx_tpu.apps.als.serving import ALSServingModel, ALSServingModelManager
    from oryx_tpu.apps.als.state import ALSState
    from oryx_tpu.common.config import load_config

    import json

    rng = np.random.default_rng(1)
    cfg = load_config(overlay={"oryx.als.approx-recall": 0.9})
    mgr = ALSServingModelManager(cfg)
    # MODEL header then UP rows, as the update topic would deliver them:
    # the MANAGER must construct its model with the configured recall
    mgr.consume_key_message(
        "MODEL",
        json.dumps({"app": "als", "extensions": {"features": "4"}, "content": {}}),
    )
    mgr.consume_key_message("UP", json.dumps(["Y", "i0", [0.1, 0.2, 0.3, 0.4]]))
    mgr.consume_key_message("UP", json.dumps(["Y", "i1", [0.4, 0.3, 0.2, 0.1]]))
    mgr.consume_key_message("UP", json.dumps(["X", "u0", [1, 0, 0, 0]]))
    assert mgr.model is not None
    assert mgr.model.approx_recall == 0.9
    out = mgr.model.top_n(np.ones(4, dtype=np.float32), 2)
    assert len(out) == 2
    # bad config fails when the app config view is built, not at serve time
    from oryx_tpu.apps.als.common import ALSConfig

    with pytest.raises(ValueError, match="approx-recall"):
        ALSConfig.from_config(load_config(overlay={"oryx.als.approx-recall": 0.0}))


class _Late:
    """A device result that takes 1 ms to reach the host."""

    def __init__(self, value):
        self._value = value

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.001)
        return self._value


def test_the_dispatchers_regions_tile_its_life(y, monkeypatch):
    """50 dispatches of a stub that sleeps 2 ms: the top-level regions of
    each of the dispatcher's two threads cover its time to within 5 % (idle,
    full, pick and launch the launching thread's; await, fetch, distribute
    and retire the fetch thread's), and each parent's children cover the
    parent."""
    from e2e_common import region_tiling

    from oryx_tpu.common.tracing import get_tracer, region_totals
    from oryx_tpu.ops import als

    def stub(xs, y, *, k, **kw):
        time.sleep(0.002)
        n = xs.shape[0]
        return (
            _Late(np.zeros((n, k), np.float32)),
            _Late(np.tile(np.arange(k, dtype=np.int32), (n, 1))),
            None,
        )

    monkeypatch.setattr(als, "topk_dot_batch", stub)
    tr = get_tracer()
    tr.configure(enabled=True, capacity=8192)
    tr.clear()
    before = region_totals()
    b = TopKBatcher()
    try:
        vec = np.ones(8, dtype=np.float32)
        for i in range(50):
            vals, idx = b.submit(vec, 5, y)
            assert list(idx) == [0, 1, 2, 3, 4]
            if i % 10 == 9:
                time.sleep(0.01)  # let it reach its idle wait now and then
        tids = (b._thread.ident, b._fetcher.ident)
    finally:
        b.close()
        spans = tr.snapshot()
        tr.configure(enabled=False, capacity=2048)
    tops = (
        ({"batcher.idle", "batcher.full", "batcher.pick", "batcher.launch"},
         {"batcher.launch", "batcher.issue"}),
        ({"batcher.await", "batcher.fetch", "batcher.distribute", "batcher.retire"},
         {"batcher.fetch"}),
    )
    for tid, (top, parents) in zip(tids, tops):
        covered, by_parent = region_tiling(spans, tid, top)
        assert 0.95 <= covered <= 1.0001, (top, covered)
        assert set(by_parent) == parents
        for parent, share in by_parent.items():
            assert 0.95 <= share <= 1.0001, (parent, share)
    # the counters saw the same fifty, whatever the ring holds
    after = region_totals()
    for name in ("batcher.launch", "batcher.issue.call", "batcher.fetch.vals", "batcher.distribute"):
        assert after[name][2] - before.get(name, (0, 0, 0))[2] == 50, name
    call = after["batcher.issue.call"][0] - before.get("batcher.issue.call", (0.0,))[0]
    assert 0.1 <= call < 1.0  # 50 x 2 ms of the stub, inside the call region alone


# ---------------------------------------------------------------------------
# two dispatches in flight: the dispatcher launches while the fetch thread
# waits for the results of the dispatch before
# ---------------------------------------------------------------------------


class _Gated:
    """A device result that reaches the host only once its gate opens."""

    def __init__(self, value, gate):
        self._value = value
        self._gate = gate

    def __array__(self, dtype=None, copy=None):
        assert self._gate.wait(timeout=30), "gate never opened"
        return np.asarray(self._value)


class _FetchHold:
    """Stand-in for `topk_dot_batch` whose results reach the host when the
    test lets them (in the style of `e2e_common.WedgeHook`, which holds the
    call itself): the real function computes, and the fetch of call i
    blocks in `np.asarray` until `gates[i]` is set. `rows[i]` is the real
    row count call i was handed, `blocks[i]` its query block."""

    def __init__(self, hold=True):
        self.hold = hold
        self.rows, self.blocks, self.gates = [], [], []

    def __call__(self, xs, y, *, k, **kw):
        gate = threading.Event()
        if not self.hold:
            gate.set()
        vals, idx = _real_topk_dot_batch(xs, y, k=k, recall=kw.get("recall", 1.0))
        self.blocks.append(np.array(xs))
        self.gates.append(gate)
        self.rows.append(kw.get("rows"))  # last: wait_calls reads it
        return _Gated(vals, gate), _Gated(idx, gate), None

    def wait_calls(self, n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while len(self.rows) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        return len(self.rows)


def _until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


def _held_batcher(monkeypatch, **kw):
    from oryx_tpu.ops import als

    hook = _FetchHold()
    monkeypatch.setattr(als, "topk_dot_batch", hook)
    return hook, TopKBatcher(**kw)


def _vecs(n, seed):
    return np.random.default_rng(seed).normal(size=(n, 8)).astype(np.float32)


def _assert_direct(fut, vec, k, y):
    vals, idx = fut.result(timeout=10)
    dvals, didx = _direct(vec, k, y)
    assert list(idx) == list(didx)
    np.testing.assert_allclose(vals, dvals, rtol=1e-5)


def test_a_request_is_launched_while_the_previous_dispatchs_fetch_is_held(y, monkeypatch):
    hook, b = _held_batcher(monkeypatch)
    vecs = _vecs(2, 51)
    try:
        first = b.submit_nowait(vecs[0], 10, y)
        assert hook.wait_calls(1) == 1
        second = b.submit_nowait(vecs[1], 10, y)
        # launched though the first dispatch's results have not landed
        assert hook.wait_calls(2) == 2
        assert not first.done() and not second.done()
        assert hook.rows == [1, 1]
        for gate in hook.gates:
            gate.set()
        _assert_direct(first, vecs[0], 10, y)
        _assert_direct(second, vecs[1], 10, y)
    finally:
        for gate in hook.gates:
            gate.set()
        b.close()


def test_with_two_dispatches_unresolved_the_next_waits_and_coalesces(y, monkeypatch):
    hook, b = _held_batcher(monkeypatch)
    vecs = _vecs(4, 52)
    try:
        futs = [b.submit_nowait(vecs[0], 10, y)]
        assert hook.wait_calls(1) == 1
        futs.append(b.submit_nowait(vecs[1], 10, y))
        assert hook.wait_calls(2) == 2
        futs += [b.submit_nowait(v, 10, y) for v in vecs[2:]]
        time.sleep(0.2)
        assert len(hook.rows) == 2  # nothing launched behind two unresolved
        hook.gates[0].set()
        assert hook.wait_calls(3) == 3
        assert hook.rows == [1, 1, 2]  # what queued meanwhile: one dispatch
        np.testing.assert_array_equal(hook.blocks[2][:2, :8], vecs[2:])
        for gate in hook.gates:
            gate.set()
        for f, v in zip(futs, vecs):
            _assert_direct(f, v, 10, y)
    finally:
        for gate in hook.gates:
            gate.set()
        b.close()


def test_results_are_distributed_in_dispatch_order(y, monkeypatch):
    from oryx_tpu.common.perfstats import get_perfstats

    hook, b = _held_batcher(monkeypatch)
    vecs = _vecs(2, 53)
    order = []
    t_mark = time.monotonic()
    try:
        futs = []
        for i, v in enumerate(vecs):
            futs.append(b.submit_nowait(v, 10, y))
            futs[-1].add_done_callback(lambda f, i=i: order.append(i))
            assert hook.wait_calls(i + 1) == i + 1
        hook.gates[1].set()  # the second's results land first ...
        time.sleep(0.2)
        assert order == []  # ... and wait for the first's
        hook.gates[0].set()
        for f, v in zip(futs, vecs):
            _assert_direct(f, v, 10, y)
        assert order == [0, 1]
    finally:
        for gate in hook.gates:
            gate.set()
        b.close()
    records = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert [r.dispatch for r in records] == [0, 1]


def test_launched_behind_counts_the_launches_behind_an_unresolved_dispatch(y, monkeypatch):
    from oryx_tpu.common.metrics import get_registry

    hook, b = _held_batcher(monkeypatch)
    b.register_gauges()
    vecs = _vecs(5, 54)

    def gauge():
        for line in get_registry().render_prometheus().splitlines():
            if line.startswith("oryx_topk_launched_behind_total "):
                return float(line.split()[1])

    try:
        futs = [b.submit_nowait(vecs[0], 10, y)]  # nothing unresolved
        assert hook.wait_calls(1) == 1
        futs.append(b.submit_nowait(vecs[1], 10, y))  # behind the first
        assert hook.wait_calls(2) == 2
        assert _until(lambda: len(b._unresolved) == 2)  # handed over
        assert b.launched_behind == 1
        # two groups (k-buckets 16 and 128) picked behind the second
        futs.append(b.submit_nowait(vecs[2], 10, y))
        futs.append(b.submit_nowait(vecs[3], 40, y))
        hook.gates[0].set()
        assert hook.wait_calls(4) == 4
        assert _until(lambda: len(b._unresolved) == 3)  # the second and both groups
        assert b.launched_behind == 3
        for gate in hook.gates:
            gate.set()
        for f, v, k in zip(futs, vecs, (10, 10, 10, 40)):
            _assert_direct(f, v, k, y)
        assert _until(lambda: not b._inflight)  # the last retire follows its distribute
        futs.append(b.submit_nowait(vecs[4], 10, y))  # nothing unresolved
        assert hook.wait_calls(5) == 5
        hook.gates[4].set()
        _assert_direct(futs[-1], vecs[4], 10, y)
        assert _until(lambda: not b._inflight)
        assert (b.dispatches, b.launched_behind) == (5, 3)
        assert gauge() == 3.0
    finally:
        for gate in hook.gates:
            gate.set()
        b.close()


def test_the_rows_of_one_submit_many_call_ride_one_dispatch(y, monkeypatch):
    """More callers than cores at once, the interpreter switching threads
    every 10 us, ten calls of four rows each: every call's rows are in one
    dispatch, all four, each row is counted once; only the first row
    carries the caller's ledger."""
    import os
    import sys

    from oryx_tpu.common.perfattr import PhaseLedger, swap_ledger
    from oryx_tpu.ops import als

    hook = _FetchHold(hold=False)
    monkeypatch.setattr(als, "topk_dot_batch", hook)
    b = TopKBatcher()
    ledgers, errors = [], []
    callers, calls = (os.cpu_count() or 8) + 2, 10

    def caller(c):
        rng = np.random.default_rng(c)
        for n in range(calls):
            rows = rng.normal(size=(4, 8)).astype(np.float32)
            rows[:, 0] = 1000 * c + n  # the call's mark, on each of its rows
            ledger = PhaseLedger()
            ledgers.append(ledger)
            prev = swap_ledger(ledger)
            try:
                futs = b.submit_many_nowait(rows, 10, y)
            finally:
                swap_ledger(prev)
            try:
                assert len(futs) == 4
                for f, v in zip(futs, rows):
                    _assert_direct(f, v, 10, y)
            except AssertionError as e:
                errors.append(e)

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(callers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        b.close()
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    dispatches_of = {}
    for i, (block, n) in enumerate(zip(hook.blocks, hook.rows)):
        for mark in block[:n, 0]:
            dispatches_of.setdefault(float(mark), []).append(i)
    assert len(dispatches_of) == callers * calls
    assert all(len(d) == 4 and len(set(d)) == 1 for d in dispatches_of.values())
    assert b.coalesced == 4 * callers * calls and b.dispatches == len(hook.rows)
    assert b.launched_behind < b.dispatches
    for ledger in ledgers:
        assert [p for p, _, _ in ledger.items()].count("queue_wait") == 1


def test_a_wedged_fetch_fails_over_to_host(y, monkeypatch):
    """The fetch thread blocks on a transport that never answers: the
    watchdog fails the request over to the host within `device_timeout`,
    as `test_wedged_dispatch_fails_over_to_host` asserts for a wedged
    launch, and the superseded fetch thread ends once the fetch returns."""
    hook, b = _held_batcher(
        monkeypatch, device_timeout=0.5, probe_interval=0.2, compile_timeout=0.5
    )
    vec = _vecs(1, 55)[0]
    try:
        t0 = time.monotonic()
        fut = b.submit_nowait(vec, 10, y, host_mat=_host_mat(y))
        assert hook.wait_calls(1) == 1  # launched: the wedge is in the fetch
        fetcher = b._fetcher
        vals, idx = fut.result(timeout=10)
        assert time.monotonic() - t0 < 5.0 and not hook.gates[0].is_set()
        assert b.device_failovers == 1 and b._device_down.is_set()
        dvals, didx = _direct(vec, 10, y)
        assert list(idx) == list(didx)
        np.testing.assert_allclose(vals, dvals, rtol=1e-5)
        assert b._fetcher is None and not b._unresolved
        vals2, idx2 = b.submit(vec, 10, y, host_mat=_host_mat(y))  # host path
        assert list(idx2) == list(didx) and b.host_fallbacks >= 2
        assert fetcher.is_alive()  # still in the wedged fetch
    finally:
        for gate in hook.gates:
            gate.set()
        b.close()
    fetcher.join(timeout=5)
    assert not fetcher.is_alive()


@pytest.mark.parametrize("released", [True, False], ids=["released", "wedged"])
def test_close_with_dispatches_in_flight_leaves_no_future_pending(y, monkeypatch, released):
    """Two dispatches held in their fetch and two requests queued behind
    them: close() returns with every Future done, with its result where the
    fetches come back during the close, with an error where they never
    do."""
    hook, b = _held_batcher(monkeypatch)
    vecs = _vecs(4, 56)
    futs = []
    try:
        for i in range(2):
            futs.append(b.submit_nowait(vecs[i], 10, y))
            assert hook.wait_calls(i + 1) == i + 1
        futs += [b.submit_nowait(v, 10, y) for v in vecs[2:]]
        if released:
            def let_go():
                time.sleep(0.3)
                hook.hold = False
                for gate in list(hook.gates):
                    gate.set()
            threading.Thread(target=let_go, daemon=True).start()
        b.close()
        assert all(f.done() for f in futs)
        if released:
            for f, v in zip(futs, vecs):
                _assert_direct(f, v, 10, y)
        else:
            for f in futs:
                with pytest.raises(RuntimeError, match="closed"):
                    f.result(timeout=0)
    finally:
        hook.hold = False
        for gate in hook.gates:
            gate.set()
