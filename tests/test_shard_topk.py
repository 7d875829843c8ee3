"""Sharded serving top-k: CPU multi-device proof of index identity.

The acceptance bar of PR 11's tentpole: a host_mesh(n)-style CPU
simulation (the conftest forces 8 virtual devices) must prove the
sharded top-k returns exactly the indices of the single-device exact
kernel for n in {1, 2, 4} — including int8-quantized shards and the
duplicate-score tie-break — and that a dirty-row delta scatters into
its owning shard only. Values are compared to a tolerance on the bf16
path: a shard's matmul has a different shape from the whole matrix's,
XLA accumulates it in a different order, and the f32 sums differ in the
last ulp (the int8 path accumulates exactly, so its values stay equal).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu.ops.als import topk_dot_batch
from oryx_tpu.ops.shard_topk import merge_topk_partials, topk_dot_batch_sharded
from oryx_tpu.ops.transfer import (
    QuantizedMatrix,
    ShardedMatrix,
    scatter_rows,
    sharded_device_put,
    staged_device_put,
    quantize_rows_int8,
)


def _corpus(n_items=203, features=17, batch=5, seed=3):
    rng = np.random.default_rng(seed)
    y = rng.normal(size=(n_items, features)).astype(np.float32)
    xs = rng.normal(size=(batch, features)).astype(np.float32)
    return xs, y


# f32 accumulation of a 17-term dot in a different order: a few ulps
# (eps 1.2e-7) of the result. chip_smoke.py asserts the same bound
# between the sharded and the unsharded dispatch on the chip.
SHARD_VALUE_RTOL = 1e-5


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_sharded_topk_bit_identical_bf16(n_shards):
    xs, y = _corpus()
    y_full = staged_device_put(y, dtype=jnp.bfloat16)
    y_sharded = sharded_device_put(y, n_shards, dtype=jnp.bfloat16)
    # the sharded view is stored lane-padded (the kernel's shape)
    assert y_sharded.shape == (y.shape[0], 128)
    v0, i0 = topk_dot_batch(jnp.asarray(xs), y_full, k=10)
    v1, i1 = topk_dot_batch(jnp.asarray(xs), y_sharded, k=10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(
        np.asarray(v0), np.asarray(v1), rtol=SHARD_VALUE_RTOL
    )


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_sharded_topk_bit_identical_quantized(n_shards):
    xs, y = _corpus(seed=11)
    q, s = quantize_rows_int8(y)
    full = QuantizedMatrix(jnp.asarray(q), jnp.asarray(s))
    sharded = sharded_device_put(y, n_shards, quantize=True)
    # per-row scales are row-local: shard-local quantization must be
    # bit-identical to quantize-then-slice
    q_sharded = np.concatenate([np.asarray(sh.q) for sh in sharded.shards])
    np.testing.assert_array_equal(q_sharded[:, :q.shape[1]], q)
    assert not q_sharded[:, q.shape[1]:].any()  # the lane pad
    v0, i0 = topk_dot_batch(jnp.asarray(xs), full, k=10)
    v1, i1 = topk_dot_batch(jnp.asarray(xs), sharded, k=10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_topk_duplicate_score_tie_break(n_shards):
    # duplicate rows STRADDLING shard boundaries: every duplicate pair
    # scores identically, and the winner must be the LOWER global index
    # (lax.top_k's stable order), exactly as the single dispatch picks
    rng = np.random.default_rng(7)
    base = rng.normal(size=(40, 8)).astype(np.float32)
    y = np.concatenate([base, base, base])  # 120 rows, every score x3
    xs = rng.normal(size=(4, 8)).astype(np.float32)
    y_full = staged_device_put(y, dtype=jnp.bfloat16)
    y_sharded = sharded_device_put(y, n_shards, dtype=jnp.bfloat16)
    v0, i0 = topk_dot_batch(jnp.asarray(xs), y_full, k=12)
    v1, i1 = topk_dot_batch(jnp.asarray(xs), y_sharded, k=12)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


def test_sharded_topk_uneven_rows_and_wide_k():
    # 7 rows over 4 shards (sizes 2,2,2,1) with k wider than any shard:
    # per-shard partials are narrower than k and the merge must still
    # produce the exact global ordering over every row
    xs, y = _corpus(n_items=7, features=5, batch=3, seed=23)
    y_full = staged_device_put(y, dtype=jnp.bfloat16)
    y_sharded = sharded_device_put(y, 4, dtype=jnp.bfloat16)
    v0, i0 = topk_dot_batch(jnp.asarray(xs), y_full, k=7)
    v1, i1 = topk_dot_batch(jnp.asarray(xs), y_sharded, k=7)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    with pytest.raises(ValueError):
        topk_dot_batch_sharded(jnp.asarray(xs), y_sharded, k=8)


def test_sharded_placement_uses_distinct_devices():
    _, y = _corpus(n_items=64)
    sm = sharded_device_put(y, 4, dtype=jnp.bfloat16)
    devs = [next(iter(sh.devices())) for sh in sm.shards]
    assert len(set(devs)) == 4  # conftest forces 8 virtual CPU devices
    # placement must SURVIVE computation: shards are committed, so a
    # dirty-row scatter and the unit-view normalize both stay on the
    # owning shard's device (an uncommitted shard would silently migrate
    # to the default device on first touch — the multi-chip OOM)
    assert all(getattr(sh, "committed", True) for sh in sm.shards)
    after = scatter_rows(
        sm, np.array([17], dtype=np.int64),
        np.ones((1, y.shape[1]), dtype=np.float32),
    )
    assert [next(iter(sh.devices())) for sh in after.shards] == devs
    unit = sm.map(lambda s: (s.astype(jnp.float32) / 2).astype(s.dtype))
    assert [next(iter(sh.devices())) for sh in unit.shards] == devs
    smq = sharded_device_put(y, 4, quantize=True)
    qdevs = [next(iter(sh.devices())) for sh in smq.shards]
    assert len(set(qdevs)) == 4
    afterq = scatter_rows(
        smq, np.array([33], dtype=np.int64),
        np.ones((1, y.shape[1]), dtype=np.float32),
    )
    assert [next(iter(sh.devices())) for sh in afterq.shards] == qdevs
    # full view reassembles exactly across devices
    np.testing.assert_array_equal(
        np.concatenate(
            [np.asarray(sh, dtype=np.float32) for sh in sm.shards]
        )[:, :y.shape[1]],
        np.asarray(
            staged_device_put(y, dtype=jnp.bfloat16), dtype=np.float32
        ),
    )


def test_merge_topk_partials_direct():
    # hand-built partials with ties across shards and uneven widths
    v_a = np.array([[3.0, 1.0]], dtype=np.float32)
    i_a = np.array([[4, 9]], dtype=np.int32)
    v_b = np.array([[3.0, 2.0, 0.5]], dtype=np.float32)
    i_b = np.array([[2, 11, 20]], dtype=np.int32)
    v, i = merge_topk_partials([(v_a, i_a), (v_b, i_b)], k=4)
    np.testing.assert_array_equal(np.asarray(i), [[2, 4, 11, 9]])
    np.testing.assert_array_equal(np.asarray(v), [[3.0, 3.0, 2.0, 1.0]])
    with pytest.raises(ValueError):
        merge_topk_partials([], k=2)


def test_sharded_scatter_touches_owning_shard_only():
    _, y = _corpus(n_items=20, features=6)
    sm = sharded_device_put(y, 4, dtype=jnp.bfloat16)  # sizes [5,5,5,5]
    old_shards = list(sm.shards)
    rows = np.array([6, 8], dtype=np.int64)  # both owned by shard 1
    new_rows = np.full((2, 6), 2.5, dtype=np.float32)
    out = scatter_rows(sm, rows, new_rows)
    assert isinstance(out, ShardedMatrix)
    # untouched shards are the SAME buffers, not copies
    assert out.shards[0] is old_shards[0]
    assert out.shards[2] is old_shards[2]
    assert out.shards[3] is old_shards[3]
    assert out.shards[1] is not old_shards[1]
    got = np.asarray(out.shards[1], dtype=np.float32)
    np.testing.assert_allclose(got[[1, 3], :6], new_rows, rtol=0.01)
    assert not got[:, 6:].any()  # dirty rows land lane-padded with zeros
    # empty delta: the view object rides through unchanged
    same = scatter_rows(out, np.array([], dtype=np.int64), np.zeros((0, 6)))
    assert same is out


def test_sharded_scatter_quantized_requantizes_locally():
    _, y = _corpus(n_items=12, features=4)
    sm = sharded_device_put(y, 3, quantize=True)  # sizes [4,4,4]
    old = list(sm.shards)
    rows = np.array([5], dtype=np.int64)  # shard 1, local row 1
    fresh = np.array([[9.0, -3.0, 0.5, 1.0]], dtype=np.float32)
    out = scatter_rows(sm, rows, fresh)
    assert out.shards[0] is old[0] and out.shards[2] is old[2]
    q_exp, s_exp = quantize_rows_int8(fresh)
    np.testing.assert_array_equal(np.asarray(out.shards[1].q)[1, :4], q_exp[0])
    assert not np.asarray(out.shards[1].q)[:, 4:].any()
    np.testing.assert_allclose(
        np.asarray(out.shards[1].scale)[1], s_exp[0], rtol=1e-6
    )
    # the other rows of the touched shard kept their int8 bits exactly
    np.testing.assert_array_equal(
        np.asarray(out.shards[1].q)[[0, 2, 3]], np.asarray(old[1].q)[[0, 2, 3]]
    )


def test_bucketed_train_under_pjit_sharded_factors():
    """The bucketed (donated-carry) ALS scan runs under pjit with the
    item-factor table row-sharded over a model-axis mesh — and lands on
    the same model as the single-device scan (same seeded init; only
    collective summation order differs)."""
    from oryx_tpu.ops.als import aggregate_interactions, train_als, train_als_warm
    from oryx_tpu.parallel.mesh import model_mesh

    rng = np.random.default_rng(13)
    data = aggregate_interactions(
        rng.integers(0, 50, 800).astype(str),
        rng.integers(0, 30, 800).astype(str),
        (rng.random(800) * 2 + 0.2).astype(np.float32),
        implicit=True,
    )
    key = jax.random.PRNGKey(4)
    ref = train_als(data, features=6, iterations=4, seed_key=key)
    for n in (2, 4):
        sharded = train_als(
            data, features=6, iterations=4, seed_key=key,
            shard_mesh=model_mesh(n),
        )
        np.testing.assert_allclose(sharded.x, ref.x, rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(sharded.y, ref.y, rtol=2e-3, atol=2e-4)
    # the warm-start early-stop loop threads the shard mesh through its
    # donated re-entries unchanged
    warm, sweeps = train_als_warm(
        data, features=6, iterations=8, seed_key=key, resume_y=ref.y,
        tol=0.05, min_iterations=2, check_every=2,
        shard_mesh=model_mesh(2),
    )
    assert warm.y.shape == ref.y.shape
    assert 2 <= sweeps <= 8
    # combining an explicit mesh with shard_mesh is a loud error, never a
    # silently dropped shard layout
    from oryx_tpu.parallel.mesh import host_mesh

    with pytest.raises(ValueError):
        train_als(
            data, features=6, iterations=1, seed_key=key,
            mesh=host_mesh(2), shard_mesh=model_mesh(2),
        )


def test_checkpointed_train_threads_shard_mesh(tmp_path):
    """Review regression (PR 11): the checkpointed build path must keep
    the shard layout — dropping it silently trained single-device AND
    unsharded once ALSUpdate replaced the auto mesh with None."""
    from oryx_tpu.ops.als import (
        aggregate_interactions, train_als, train_als_checkpointed,
    )
    from oryx_tpu.parallel.mesh import model_mesh

    rng = np.random.default_rng(21)
    data = aggregate_interactions(
        rng.integers(0, 30, 400).astype(str),
        rng.integers(0, 20, 400).astype(str),
        (rng.random(400) + 0.2).astype(np.float32),
        implicit=True,
    )
    key = jax.random.PRNGKey(9)
    ref = train_als(data, features=4, iterations=4, seed_key=key)
    ck = train_als_checkpointed(
        data, tmp_path / "ck", 2, features=4, iterations=4, seed_key=key,
        shard_mesh=model_mesh(2),
    )
    np.testing.assert_allclose(ck.x, ref.x, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(ck.y, ref.y, rtol=2e-3, atol=2e-4)


def test_sharded_matrix_through_the_batcher():
    """The shared TopKBatcher scores a ShardedMatrix view exactly like a
    plain device matrix — the serving integration point."""
    from oryx_tpu.serving.batcher import TopKBatcher

    xs, y = _corpus(n_items=96, features=8, batch=1)
    sm = sharded_device_put(y, 2, dtype=jnp.bfloat16)
    b = TopKBatcher()
    try:
        vals, idx = b.submit(xs[0], 5, sm, host_mat=y)
        v0, i0 = topk_dot_batch(
            jnp.asarray(xs[:1]), staged_device_put(y, dtype=jnp.bfloat16), k=5
        )
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(i0)[0])
    finally:
        b.close()


@pytest.mark.parametrize("form", ["sharded", "sharded-int8", "chunked"])
def test_every_shard_and_chunk_is_given_the_same_real_row_count(form, monkeypatch):
    """`rows` (the real leading rows of the query block) reaches the fused
    kernel of every shard and every chunk unchanged (ISSUE 30): each pays
    for its live row blocks alone, and the merged real rows are what the
    whole matrix gives. The Pallas interpreter stands in for the chip."""
    from oryx_tpu.ops import als, pallas_topk
    from oryx_tpu.ops.transfer import ChunkedMatrix

    seen = []
    real_kernel = pallas_topk.topk_dot_batch_pallas

    def kernel(xs, y, **kw):
        seen.append((int(y.shape[0]), kw.get("rows")))
        return real_kernel(xs, y, interpret=True, **kw)

    monkeypatch.setattr(pallas_topk, "topk_dot_batch_pallas", kernel)
    monkeypatch.setattr(als, "_on_tpu", lambda a: True)
    monkeypatch.setattr(als, "PALLAS_TOPK_MIN_ITEMS", 16)
    rng = np.random.default_rng(12)
    y = rng.integers(-9, 10, size=(300, 8)).astype(np.float32)
    xs = np.zeros((24, 8), dtype=np.float32)
    xs[:3] = rng.integers(-9, 10, size=(3, 8))
    if form == "chunked":
        view = ChunkedMatrix(
            [staged_device_put(y[lo:lo + 100], dtype=jnp.bfloat16) for lo in (0, 100, 200)]
        )
    else:
        view = sharded_device_put(
            y, 4, **({"quantize": True} if form == "sharded-int8" else {"dtype": jnp.bfloat16})
        )
    parts = 3 if form == "chunked" else 4
    v, i = topk_dot_batch(jnp.asarray(xs), view, k=10, rows=3)
    assert len(seen) == parts and {rows for _, rows in seen} == {3}
    v_all, i_all = topk_dot_batch(jnp.asarray(xs), view, k=10)
    assert [rows for _, rows in seen[parts:]] == [None] * parts
    np.testing.assert_array_equal(np.asarray(i)[:3], np.asarray(i_all)[:3])
    np.testing.assert_array_equal(np.asarray(v)[:3], np.asarray(v_all)[:3])
    if form != "sharded-int8":  # integer factors: the plain scores are exact
        ref = jax.lax.top_k(jnp.asarray(xs[:3] @ y.T), 10)[1]
        np.testing.assert_array_equal(np.asarray(i)[:3], np.asarray(ref))
