"""The resident serving view is stored in the shape the top-k kernel DMAs
(ISSUE 29): features lane-padded, rows a multiple of the item block, so
`_topk_pallas_jit` pads no catalog. Everything here runs on the CPU; the
kernel runs in the Pallas interpreter.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.apps.als.serving import (
    ALSServingModel, SyncConfig, _rerank_exact, _trim_pairs,
)
from oryx_tpu.apps.als.state import ALSState
from oryx_tpu.common.perfstats import get_perfstats
from oryx_tpu.ops import als as ops_als
from oryx_tpu.ops import pallas_topk as pt
from oryx_tpu.ops import transfer
from oryx_tpu.ops.transfer import (
    ChunkedMatrix, QuantizedMatrix, ShardedMatrix, kernel_view_put,
    quantize_rows_int8, quantized_device_put, scatter_rows,
)
from oryx_tpu.serving.batcher import TopKBatcher, k_bucket

LANES = {250: 256, 50: 128}


def _state(n, features, seed=29):
    rng = np.random.default_rng(seed)
    st = ALSState(features, implicit=True)
    st.y.bulk_set(
        [f"i{j}" for j in range(n)],
        rng.standard_normal((n, features)).astype(np.float32),
    )
    st.set_expected([], [f"i{j}" for j in range(n)])
    return st


def _pieces(y):
    """The device arrays a view is made of, whatever its form."""
    if isinstance(y, ShardedMatrix):
        return [p for s in y.shards for p in _pieces(s)]
    if isinstance(y, ChunkedMatrix):
        return list(y.chunks)
    if isinstance(y, QuantizedMatrix):
        return [y.q]
    return [y]


def _in_kernel_shape(piece, features):
    rows, width = piece.shape
    itemsize = np.dtype(piece.dtype).itemsize
    block = pt.item_block(rows, width, itemsize)
    return width == pt.lane_pad(features) and rows > 0 and rows % block == 0


# -- (a) every form of the view is in the kernel's shape -----------------------

@pytest.mark.parametrize("form", ["plain", "sharded", "chunked", "quantized"])
@pytest.mark.parametrize("features", [250, 50])
def test_resident_view_is_stored_in_the_kernels_shape(form, features, monkeypatch):
    n = 300
    kw = {}
    if form == "sharded":
        kw["sync"] = SyncConfig(shard_count=4)
    if form == "quantized":
        kw["score_mode"] = "quantized"
    if form == "chunked":
        monkeypatch.setattr(transfer, "CHUNKED_OVER_BYTES", 1024)
        monkeypatch.setattr(transfer, "CHUNK_TARGET_BYTES", 128 * features * 2)
    model = ALSServingModel(_state(n, features), **kw)
    y, ids, _version, host = model._y_view_full()
    kind = {"sharded": ShardedMatrix, "chunked": ChunkedMatrix,
            "quantized": QuantizedMatrix}.get(form, jax.Array)
    assert isinstance(y, kind)
    assert len(ids) == n
    assert y.shape[1] == LANES[features]
    # the host float32 mirror keeps the published width, row for row
    assert host.shape == (y.shape[0], features)
    pieces = _pieces(y)
    assert len(pieces) == {"sharded": 4, "chunked": 4}.get(form, 1)
    for piece in pieces:
        assert _in_kernel_shape(piece, features), piece.shape
        assert not np.asarray(piece)[:, features:].any()  # the lane pad is zero
    got = np.concatenate([np.asarray(p, dtype=np.float32) for p in pieces])
    if form == "quantized":
        q, scale = quantize_rows_int8(host)
        np.testing.assert_array_equal(got[:, :features], q)
        # scales are per row: padded with the rows, never in features
        assert y.scale.shape == (y.shape[0],)
        np.testing.assert_array_equal(np.asarray(y.scale), scale)
    else:
        np.testing.assert_array_equal(
            got[:, :features],
            np.asarray(host.astype(jnp.bfloat16), dtype=np.float32),
        )
    assert not got[n:].any()  # capacity rows
    model.close()


def test_seq_item_view_is_stored_in_the_kernels_shape():
    from oryx_tpu.apps.seq.serving import SeqServingModel
    from oryx_tpu.apps.seq.state import SeqState

    st = SeqState(32, 4)
    rng = np.random.default_rng(3)
    st.items.bulk_set(
        [f"i{j}" for j in range(70)],
        rng.standard_normal((70, 32)).astype(np.float32),
    )
    for shards in (1, 2):
        model = SeqServingModel(st, sync=SyncConfig(shard_count=shards))
        y, ids, _v, host = model._view()
        assert y.shape == (128 * shards, 128) and host.shape == (128 * shards, 32)
        assert all(_in_kernel_shape(p, 32) for p in _pieces(y))
        assert len(ids) == 70


def test_view_shape_is_idempotent_and_the_capacity_ladder_is_aligned():
    # the reference's grid points: the ladder's counts are block multiples
    # already, so the resident view costs no extra row
    assert pt.view_shape(transfer.row_capacity(5_000_000, 0.125), 250, jnp.bfloat16) == (6_291_456, 256)
    assert pt.view_shape(transfer.row_capacity(20_000_000, 0.125), 250, jnp.bfloat16) == (25_165_824, 256)
    assert transfer.view_rows(25_165_824, 250, jnp.bfloat16, shards=4) == 25_165_824
    assert pt.view_shape(transfer.row_capacity(1_000_000, 0.125), 50, jnp.int8) == (1_310_720, 128)
    for rows in (1, 64, 300, 1280, 5000, 40_000, 49_152, 1_000_001):
        for feats, dtype in ((250, jnp.bfloat16), (50, jnp.int8), (128, jnp.float32)):
            shape = pt.view_shape(rows, feats, dtype)
            assert shape[0] >= rows and pt.view_shape(shape[0], shape[1], dtype) == shape


# -- (b) the structural guard: no catalog-sized pad inside the jitted call -----

def _primitives_with_output_rows(jaxpr, rows):
    found = []
    for eqn in jaxpr.eqns:
        if any(getattr(v.aval, "shape", ())[:1] == (rows,) for v in eqn.outvars):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives_with_output_rows(sub, rows)
    return found


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_jitted_wrapper_pads_no_catalog(quantized):
    rows, width = pt.view_shape(5000, 250, jnp.int8 if quantized else jnp.bfloat16)
    block_i = pt.item_block(rows, width, 1 if quantized else 2)
    y = jax.ShapeDtypeStruct((rows, width), jnp.int8 if quantized else jnp.bfloat16)
    xs = jax.ShapeDtypeStruct((16, 250), jnp.float32 if quantized else jnp.bfloat16)
    scales = jax.ShapeDtypeStruct((rows,), jnp.float32) if quantized else None
    fn = functools.partial(
        pt._topk_pallas_jit, k=32, block_b=8, block_i=block_i,
        quantized=quantized, interpret=True,
    )
    # the counts of real query rows and of valid item rows, one operand
    real = jax.ShapeDtypeStruct((2,), jnp.int32)
    closed = jax.make_jaxpr(fn)(xs, y, scales, real)
    sized = _primitives_with_output_rows(closed.jaxpr, rows)
    sized += _primitives_with_output_rows(closed.jaxpr, rows // 128)  # the scales' tile
    assert "pad" not in sized and "concatenate" not in sized, sized
    assert "pad" in str(closed)  # the query block alone is padded
    # and an operand that is not in the kernel's shape is refused, not padded
    for bad in ((rows, 250), (rows - 128, width)):
        with pytest.raises(ValueError, match="kernel's shape"):
            jax.make_jaxpr(fn)(xs, jax.ShapeDtypeStruct(bad, y.dtype), scales, real)


def test_unaligned_callers_are_padded_outside_the_jitted_call():
    # tests, tools and the trainer's evaluation hand the wrapper any array:
    # same answers as over the stored view, rows past n_items never selected
    rng = np.random.default_rng(5)
    y = rng.integers(-9, 10, size=(700, 50)).astype(np.float32)
    xs = rng.integers(-9, 10, size=(6, 50)).astype(np.float32)
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(xs @ y.T), 40)
    v, i = pt.topk_dot_batch_pallas(jnp.asarray(xs), jnp.asarray(y), k=40, interpret=True)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    assert np.array_equal(np.asarray(v), np.asarray(v_ref))
    # the same rows stored in the kernel's shape: its zero rows score 0.0 like
    # a serving view's capacity rows, under the 40 best of these 700
    view = kernel_view_put(y)
    assert view.shape == (1024, 128)
    v2, i2 = pt.topk_dot_batch_pallas(jnp.asarray(xs), view, k=40, interpret=True)
    assert np.array_equal(np.asarray(i2), np.asarray(i_ref))
    assert np.array_equal(np.asarray(v2), np.asarray(v_ref))


# -- (c) parity with the parent's path, through the serving path ---------------

@pytest.fixture
def fused_on_cpu(monkeypatch):
    """Route the dispatcher to the fused kernel, in the interpreter."""
    real = pt.topk_dot_batch_pallas
    monkeypatch.setattr(ops_als, "_on_tpu", lambda a: True)
    monkeypatch.setattr(ops_als, "PALLAS_TOPK_MIN_ITEMS", 1)
    monkeypatch.setattr(
        pt, "topk_dot_batch_pallas", functools.partial(real, interpret=True)
    )


def _parent_path(xs, y, kb, scales=None):
    """The parent's `_topk_pallas_jit` (PR 26): the resident view at the
    published width, lane- and block-padded INSIDE the call on every
    dispatch, then the same kernel."""
    n_items, n_feat = y.shape
    feat_pad = max(128, -(-n_feat // 128) * 128)
    itemsize = np.dtype(y.dtype).itemsize
    block_b, block_i = pt.tuned_blocks(feat_pad, itemsize)
    block_b = min(block_b, max(8, xs.shape[0]))
    block_i = max(128, min(pt._pow2_floor(block_i), pt._pow2_ceil(n_items)))
    rows = -(-n_items // block_i) * block_i
    y_p = jnp.pad(y, ((0, rows - n_items), (0, feat_pad - n_feat)))
    if scales is not None:
        scales = jnp.pad(jnp.asarray(scales, jnp.float32), (0, rows - n_items))
    vals, idx, _chunks = pt._topk_pallas_jit(
        xs, y_p, scales, pt.stage_counts(None, None, xs.shape[0], n_items),
        k=kb, block_b=block_b,
        block_i=block_i, quantized=scales is not None, interpret=True,
    )
    return np.asarray(vals), np.asarray(idx)


@pytest.mark.parametrize("n_exclude", [0, 60], ids=["k-bucket-32", "k-bucket-128"])
@pytest.mark.parametrize("mode", ["exact", "quantized"])
@pytest.mark.parametrize("features", [250, 50])
def test_serving_path_answers_are_the_parents(features, mode, n_exclude, fused_on_cpu):
    n, how_many = 600, 10
    st = _state(n, features, seed=features + n_exclude)
    model = ALSServingModel(st, score_mode=mode)
    rng = np.random.default_rng(7)
    vec = rng.standard_normal(features).astype(np.float32)
    y, ids, _v, host = model._y_view_full()
    assert ops_als.topk_path(y, 32) == ("pallas" if mode == "exact" else "pallas-int8")
    exclude = {f"i{j}" for j in rng.choice(n, size=n_exclude, replace=False)}
    k = how_many + len(exclude) + 8
    kb = k_bucket(k)
    assert kb == (32 if not n_exclude else 128)

    # the raw dispatch: ALSServingModel's view -> TopKBatcher -> the kernel
    vals, idx = TopKBatcher.shared().submit(
        vec, k, y, host_mat=host, valid_rows=n, score_mode=mode,
    )
    if mode == "exact":
        want_v, want_i = _parent_path(
            jnp.asarray(vec[None], dtype=jnp.bfloat16),
            jnp.asarray(host, dtype=jnp.bfloat16), kb,
        )
    else:
        q, scale = quantize_rows_int8(host)
        want_v, want_i = _parent_path(
            jnp.asarray(vec[None]), jnp.asarray(q), kb, scales=scale
        )
    np.testing.assert_array_equal(idx, want_i[0, :k])  # index for index
    np.testing.assert_array_equal(vals, want_v[0, :k])  # value for value

    # and the answer a caller gets: the same candidates, re-ranked in float32
    keep = want_i[0, :k] < n
    want = _trim_pairs(
        *_rerank_exact(vec, want_v[0, :k][keep], want_i[0, :k][keep], host, False),
        ids, how_many, exclude, None,
    )
    got = model.top_n(vec, how_many, exclude=exclude)
    assert got == want and len(got) == how_many
    model.close()


# -- (d) delta sync keeps the view in the kernel's shape ------------------------

@pytest.mark.parametrize("donate", [False, True], ids=["copied", "donated"])
@pytest.mark.parametrize("form", ["plain", "quantized", "sharded", "chunked"])
def test_scatter_rows_leaves_the_pad_lanes_zero(form, donate):
    rng = np.random.default_rng(11)
    features, n = 250, 512
    host = rng.standard_normal((n, features)).astype(np.float32)
    if form == "quantized":
        view = quantized_device_put(host)
    elif form == "sharded":
        view = transfer.sharded_device_put(host, 2, dtype=jnp.bfloat16)
    elif form == "chunked":
        view = transfer.device_put_maybe_chunked(
            host, dtype=jnp.bfloat16, over_bytes=1024, chunk_bytes=128 * features * 2
        )
        assert len(view.chunks) == 4
    else:
        view = kernel_view_put(host, dtype=jnp.bfloat16)
    dirty = np.array([0, 3, 130, 255, 256, 511], dtype=np.int64)
    fresh = 3.0 * rng.standard_normal((dirty.size, features)).astype(np.float32)
    host[dirty] = fresh  # the host mirror takes the same rows
    out = scatter_rows(view, dirty, fresh, donate=donate)
    assert type(out) is type(view) and out.shape == (n, 256)
    got = np.concatenate([np.asarray(p, dtype=np.float32) for p in _pieces(out)])
    assert not got[:, features:].any()  # dirty rows land with zero pad lanes
    if form == "quantized":
        q, scale = quantize_rows_int8(host)
        np.testing.assert_array_equal(got[:, :features], q)
        np.testing.assert_array_equal(np.asarray(out.scale), scale)
    else:
        np.testing.assert_array_equal(
            got[:, :features], np.asarray(host.astype(jnp.bfloat16), dtype=np.float32)
        )


def test_delta_sync_of_a_served_model_keeps_unit_and_int8_views_padded():
    st = _state(100, 50)
    model = ALSServingModel(st, score_mode="quantized", sync=SyncConfig(max_delta_fraction=0.5))
    q = np.ones(50, dtype=np.float32)
    model.top_n(q, 5)
    model.top_n(q, 5, cosine=True)  # the unit view shares the int8 rows
    before = model._device_view[0]
    for j in range(5):
        st.y.set(f"i{j}", np.full(50, j + 1.0, dtype=np.float32))
    deadline = time.monotonic() + 10
    while model.served_version() != st.y.get_version() and time.monotonic() < deadline:
        model.top_n(q, 3)
        time.sleep(0.01)
    y, _ids, _v, host = model._device_view
    assert y is not before and y.shape == before.shape == (128, 128)
    got = np.asarray(y.q)
    assert not got[:, 50:].any()
    np.testing.assert_array_equal(got[:, :50], quantize_rows_int8(host)[0])
    assert model._unit_view[0].q is y.q  # still one int8 matrix for both views
    model.close()


# -- (e) the accounting counts the published features ---------------------------

@pytest.mark.parametrize("form", ["plain", "quantized", "sharded"])
def test_dispatch_record_counts_the_published_features(form):
    features, n, k = 250, 300, 18
    kw = {}
    if form == "quantized":
        kw["score_mode"] = "quantized"
    if form == "sharded":
        kw["sync"] = SyncConfig(shard_count=2)
    model = ALSServingModel(_state(n, features), **kw)
    y, _ids, _v, host = model._y_view_full()
    cap = y.shape[0]
    assert y.shape[1] == 256
    t_mark = time.monotonic()
    TopKBatcher.shared().submit(
        np.ones(features, dtype=np.float32), k, y, host_mat=host, valid_rows=n,
        score_mode=model._effective_mode,
    )
    recs = [r for r in get_perfstats().records_since(t_mark) if r.kind == "serving"]
    assert len(recs) == 1
    rec = recs[0]
    kb = k_bucket(k)
    # the parent's formulas, over a view of `cap` rows at the published width
    view_bytes = cap * features * (1 if form == "quantized" else 2) + (
        cap * 4 if form == "quantized" else 0
    )
    assert rec.flops == 2.0 * rec.rows * n * features
    assert rec.bytes_moved == rec.padded_rows * features * 4 + view_bytes + rec.padded_rows * kb * 8
    assert (rec.rows, rec.valid_rows, rec.capacity_rows, rec.k_bucket) == (1, n, cap, kb)
    model.close()
