"""Fused streaming dot+top-k Pallas kernel vs the XLA reference, run in
the Pallas interpreter on CPU, plus a TPU lowering of the serving shapes
(jax.export needs no chip). Whether Mosaic compiles and the chip agrees
is chip_smoke.py's job."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.ops.als import topk_dot_batch, topk_dot_batch_xla
from oryx_tpu.ops.pallas_topk import topk_dot_batch_pallas


@pytest.fixture(autouse=True)
def _one_tests_programs_at_a_time(request):
    """An interpreted kernel is a large CPU program, and every program a
    process has compiled keeps its code mapped: this file's few hundred of
    them ran one xdist worker into the kernel's limit of 65,530 mappings a
    process ("LLVM compilation error: Cannot allocate memory", then an
    abort). The cases of one parametrised test share their programs; the
    next test's are others, so the caches are dropped between tests."""
    yield
    if request.node.originalname != _one_tests_programs_at_a_time.last:
        _one_tests_programs_at_a_time.last = request.node.originalname
        jax.clear_caches()


_one_tests_programs_at_a_time.last = None


def _check(b, n_items, feats, k, block_b=8, block_i=256, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(b, feats)), dtype=jnp.float32)
    y = jnp.asarray(rng.normal(size=(n_items, feats)), dtype=jnp.float32)
    v_ref, i_ref = topk_dot_batch_xla(xs, y, k=k)
    v, i = topk_dot_batch_pallas(
        xs, y, k=k, block_b=block_b, block_i=block_i, interpret=True
    )
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=1e-4)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))


def test_matches_xla_basic():
    _check(b=16, n_items=1000, feats=50, k=10)


def test_uneven_batch_and_items():
    # B not a multiple of block_b, I not a multiple of block_i: padding rows
    # must never appear in results
    _check(b=13, n_items=777, feats=33, k=5)


def test_k_equals_one_and_larger_k():
    _check(b=4, n_items=300, feats=8, k=1)
    _check(b=4, n_items=300, feats=8, k=16)
    # 32 is the serving micro-batcher's bucket for default /recommend
    # overfetch (k=18 -> 32) — the fused-kernel dispatch bound
    _check(b=4, n_items=300, feats=8, k=32)


def test_single_item_block():
    # items fit in one block: the running top-k is init + one merge
    _check(b=8, n_items=100, feats=16, k=10, block_i=256)


def test_fewer_items_than_k_padding_is_neg_inf():
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.normal(size=(4, 16)), dtype=jnp.float32)
    y = jnp.asarray(rng.normal(size=(6, 16)), dtype=jnp.float32)
    # XLA's top_k rejects k > n_items outright; the kernel degrades
    # gracefully: real items first, then -inf slots
    v, i = topk_dot_batch_pallas(xs, y, k=10, block_b=8, block_i=256, interpret=True)
    scores = np.asarray(xs, dtype=np.float64) @ np.asarray(y, dtype=np.float64).T
    order = np.argsort(-scores, axis=1)
    np.testing.assert_allclose(
        np.asarray(v)[:, :6],
        np.take_along_axis(scores, order, axis=1)[:, :6],
        atol=1e-4,
    )
    assert np.array_equal(np.asarray(i)[:, :6], order[:, :6])
    assert np.all(np.isneginf(np.asarray(v)[:, 6:]))


def test_bfloat16_inputs():
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.normal(size=(8, 50)), dtype=jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=(512, 50)), dtype=jnp.bfloat16)
    v, i = topk_dot_batch_pallas(xs, y, k=4, block_b=8, block_i=256, interpret=True)
    v_ref, i_ref = topk_dot_batch_xla(xs, y, k=4)
    # bf16 rounding differs between the two matmuls; compare scores loosely
    # and require the top-1 to agree
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=0.05, rtol=0.05)
    assert np.array_equal(np.asarray(i)[:, 0], np.asarray(i_ref)[:, 0])


def test_k_over_lane_limit_rejected():
    xs = jnp.zeros((4, 8), dtype=jnp.float32)
    y = jnp.zeros((300, 8), dtype=jnp.float32)
    with pytest.raises(ValueError):
        topk_dot_batch_pallas(xs, y, k=200, interpret=True)


def test_dispatcher_uses_xla_off_tpu():
    # On CPU the dispatcher must route to XLA (pallas requires TPU unless
    # interpret=True) and produce the standard result
    rng = np.random.default_rng(11)
    xs = jnp.asarray(rng.normal(size=(4, 8)), dtype=jnp.float32)
    y = jnp.asarray(rng.normal(size=(100, 8)), dtype=jnp.float32)
    v, i = topk_dot_batch(xs, y, k=3)
    v_ref, i_ref = topk_dot_batch_xla(xs, y, k=3)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))


def test_b1_single_request():
    # B=1: the un-coalesced dispatch shape (an idle server's immediate
    # dispatch) — batch padding must not leak into the one real row
    _check(b=1, n_items=900, feats=50, k=10)


def test_k_not_divisor_of_lane_width():
    # k that divides neither the 128-lane tile nor any bucket boundary:
    # the kernel keeps a full sorted 128-slot state and the wrapper slices
    _check(b=6, n_items=700, feats=20, k=18)
    _check(b=6, n_items=700, feats=20, k=97)


def test_duplicate_scores_stable_tie_break():
    # duplicated rows produce exactly equal scores; the bitonic network's
    # (value desc, index asc) total order must match lax.top_k's stable
    # lowest-index-first tie-break bit-for-bit
    rng = np.random.default_rng(21)
    base = rng.normal(size=(60, 16)).astype(np.float32)
    y = jnp.asarray(np.repeat(base, 5, axis=0))  # every score appears 5x
    xs = jnp.asarray(rng.normal(size=(7, 16)), dtype=jnp.float32)
    v_ref, i_ref = topk_dot_batch_xla(xs, y, k=25)
    v, i = topk_dot_batch_pallas(xs, y, k=25, block_b=8, block_i=128, interpret=True)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=1e-4)


def test_property_random_shapes_match_xla():
    # randomized sweep over awkward shapes: non-multiple-of-128 item
    # tails, batches off the block grid, k off every boundary — exact
    # index agreement with the XLA reference in interpret mode
    rng = np.random.default_rng(33)
    for trial in range(5):
        b = int(rng.integers(1, 20))
        n_items = int(rng.integers(150, 2500))
        feats = int(rng.integers(4, 70))
        k = int(rng.integers(1, min(128, n_items) + 1))
        block_i = int(rng.choice([128, 256, 512]))
        _check(
            b=b, n_items=n_items, feats=feats, k=k,
            block_b=8, block_i=block_i, seed=100 + trial,
        )


def test_quantized_kernel_parity_interpret():
    # the quantized (int8 + per-row scale) kernel against the quantized
    # XLA reference: identical quantized scores -> identical indices
    from oryx_tpu.ops.als import topk_dot_batch_quant_xla
    from oryx_tpu.ops.transfer import quantize_rows_int8

    rng = np.random.default_rng(44)
    y = rng.normal(size=(1111, 30)).astype(np.float32)
    xs = jnp.asarray(rng.normal(size=(9, 30)), dtype=jnp.float32)
    q, s = quantize_rows_int8(y)
    v, i = topk_dot_batch_pallas(
        xs, jnp.asarray(q), scales=jnp.asarray(s), k=12,
        block_b=8, block_i=256, interpret=True,
    )
    v_ref, i_ref = topk_dot_batch_quant_xla(
        xs, jnp.asarray(q), jnp.asarray(s), k=12
    )
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=1e-4)


def test_int8_block_is_at_least_bf16s():
    from oryx_tpu.ops import pallas_topk as pt

    # int8 streams twice the rows per byte: its tuned block_i must be at
    # least bf16's at the same feature pad
    bb_bf16, bi_bf16 = pt.tuned_blocks(128, 2)
    bb_i8, bi_i8 = pt.tuned_blocks(128, 1)
    assert bb_i8 == bb_bf16 == 128
    assert bi_i8 >= bi_bf16 >= 256


# every (feature pad, itemsize) the tree dispatches, with the blocks the
# VMEM budget gives it: the resident view's shape in HBM hangs on these
# (view_shape), so a change here is a change of every stored catalog
_BLOCK_RULE = {
    (128, 2): (128, 8192),
    (256, 2): (128, 8192),
    (128, 1): (128, 8192),
    (256, 1): (128, 8192),
    (128, 4): (128, 8192),
    (256, 4): (128, 4096),
}


@pytest.mark.parametrize(
    "feat_pad,itemsize", list(_BLOCK_RULE),
    ids=[f"f{f}-b{b}" for f, b in _BLOCK_RULE],
)
def test_block_rule_is_a_pure_function(monkeypatch, feat_pad, itemsize):
    from oryx_tpu.ops import pallas_topk as pt

    # nothing in the process or its environment moves it: the variable
    # that once seeded a table of overrides is set before the first call
    # (its name in two halves, so a grep for it finds the tree clean)
    monkeypatch.setenv("ORYX_PALLAS" + "_BLOCKS", "64,1024")
    block_b, block_i = pt.tuned_blocks(feat_pad, itemsize)
    assert (block_b, block_i) == _BLOCK_RULE[(feat_pad, itemsize)]
    assert pt.tuned_blocks(feat_pad, itemsize) == (block_b, block_i)
    # and it cannot: every module global the function names is a
    # function or an int, none a container a caller could rewrite
    read = [
        getattr(pt, n) for n in pt.tuned_blocks.__code__.co_names
        if hasattr(pt, n)
    ]
    assert read and all(callable(g) or isinstance(g, int) for g in read)
    assert pt._working_set_bytes(
        block_b, block_i, feat_pad, itemsize
    ) <= pt._VMEM_BUDGET_BYTES
    assert block_i >= 1024 and block_i & (block_i - 1) == 0
    # the largest such block: the next one up is over the budget or the cap
    assert block_i == 8192 or pt._working_set_bytes(
        block_b, 2 * block_i, feat_pad, itemsize
    ) > pt._VMEM_BUDGET_BYTES
    assert pt.item_block(5_000_000, feat_pad, itemsize) == block_i
    assert pt.row_block(512, feat_pad, itemsize) == block_b


def test_kernel_error_propagates_instead_of_falling_back(monkeypatch):
    # a kernel that fails to compile or run must raise out of
    # topk_dot_batch — every time, not once and then XLA results under
    # the same name (the pre-PR-21 `except Exception: fall back to XLA`
    # hid that the kernel had never compiled on the chip)
    from oryx_tpu.ops import als, pallas_topk

    def refuse(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(als, "_on_tpu", lambda a: True)
    monkeypatch.setattr(pallas_topk, "topk_dot_batch_pallas", refuse)
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(4, 8)), dtype=jnp.float32)
    y = jnp.asarray(
        rng.normal(size=(als.PALLAS_TOPK_MIN_ITEMS, 8)), dtype=jnp.float32
    )
    assert als.topk_path(y, 10) == "pallas"
    for _ in range(2):
        with pytest.raises(RuntimeError, match="Mosaic"):
            topk_dot_batch(xs, y, k=10)
    # shapes the kernel does not serve are XLA by selection, not by rescue
    assert als.topk_path(y, als.PALLAS_TOPK_MAX_K + 1) == "xla"
    assert als.topk_path(y[:1000], 10) == "xla"
    v, i = topk_dot_batch(xs, y[:1000], k=10)
    assert np.array_equal(
        np.asarray(i), np.asarray(topk_dot_batch_xla(xs, y[:1000], k=10)[1])
    )


def _kernel_call(quantized, k, rows, items, feats, sds=jax.ShapeDtypeStruct):
    """(fn, argument shapes) of one fused dispatch as the batcher makes it:
    the counts of real query rows and of valid item rows are ONE operand of
    the program (int32[2], prefetched into SMEM), never compiled in."""
    real = sds((2,), jnp.int32)
    if quantized:
        fn = lambda xs, q, sc, real: topk_dot_batch_pallas(  # noqa: E731
            xs, q, scales=sc, k=k, rows=real, counted=True
        )
        return fn, (
            sds((rows, feats), jnp.float32), sds((items, feats), jnp.int8),
            sds((items,), jnp.float32), real,
        )
    fn = lambda xs, y, real: topk_dot_batch_pallas(  # noqa: E731
        xs, y, k=k, rows=real, counted=True
    )
    return fn, (
        sds((rows, feats), jnp.bfloat16), sds((items, feats), jnp.bfloat16), real,
    )


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_kernel_lowers_for_tpu_at_serving_shapes(quantized):
    # every (row bucket, k bucket) the batcher dispatches to the fused
    # kernel against the 1M x 50 serving view (capacity-padded rows),
    # lowered for TPU on this CPU host: a primitive Pallas cannot lower
    # (PR 8's jnp.flip -> `rev`) fails here in seconds instead of being
    # discovered — or hidden — on the chip
    from jax import export

    from oryx_tpu.ops.als import PALLAS_TOPK_MAX_K
    from oryx_tpu.ops.transfer import row_capacity
    from oryx_tpu.serving.batcher import BATCH_BUCKETS_ACCEL, K_BUCKETS

    items, feats = row_capacity(1_000_000, 0.125), 50
    for rows in BATCH_BUCKETS_ACCEL:
        for k in (kb for kb in K_BUCKETS if kb <= PALLAS_TOPK_MAX_K):
            fn, args = _kernel_call(quantized, k, rows, items, feats)
            exported = export.export(jax.jit(fn), platforms=["tpu"])(*args)
            assert "tpu_custom_call" in exported.mlir_module()


# -- compiled for a described v5e (no chip): what lowering alone cannot show ----
#
# libtpu compiles for a chip that is described and not attached. Only this
# file describes one, inside a fixture, so every xdist worker collects the
# same tests and one worker loads the library.

_TOPOLOGY_ENV = {
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_WORKER_HOSTNAMES": "localhost",
    "TPU_SKIP_MDS_QUERY": "1",
    "TPU_LOG_DIR": "disabled",
}


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    before = {name: os.environ.get(name) for name in _TOPOLOGY_ENV}
    os.environ.update({n: v for n, v in _TOPOLOGY_ENV.items() if before[n] is None})
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe one is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        for name, value in before.items():
            if value is None:
                os.environ.pop(name, None)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("form", ["bf16-k128", "bf16-k32", "int8-k128"])
def test_kernel_compiles_for_the_v5e_at_the_benchmarks_shape(one_chip, form):
    # the 512-row dispatch over the 5M x 250 catalog in its resident view, the
    # count of real rows an operand: Mosaic must take every fold width (the
    # sublane-aligned slices of the score block and of the running lists) and
    # do it in seconds, not the minutes an unrolled merge tree took (PR 21)
    import time

    from jax.experimental.compilation_cache import compilation_cache

    dtype, k = form.split("-k")
    fn, args = _kernel_call(
        dtype == "int8", int(k), 512, 6_291_456, 256,
        sds=lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip),
    )
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t0 = time.monotonic()
        compiled = jax.jit(fn).lower(*args).compile()
        took = time.monotonic() - t0
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    assert took < 60.0, f"the kernel took {took:.0f} s to compile"
    # nothing the size of the catalog is copied inside the program
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


# -- the threshold gate (ISSUE 26) ---------------------------------------------
#
# Integer-valued factors: every dot product is exact in float32 whatever the
# order of the sum, so the kernel's values are compared bit for bit and the
# scores are full of exact ties.

def _int_factors(rng, n, feats, lo=-9, hi=9):
    return rng.integers(lo, hi + 1, size=(n, feats)).astype(np.float32)


def _along_a_line(scale_of_item, rows=5):
    """One feature: row r (a positive multiple) scores item i at
    r * scale_of_item[i], so every row sees the items in the same order."""
    y = np.asarray(scale_of_item, dtype=np.float32)[:, None]
    xs = np.arange(1, rows + 1, dtype=np.float32)[:, None]
    return xs, y


def _from_scores(scores):
    """(xs, y) whose plain scores are `scores` [rows, items]: row r asks for
    feature r alone, so small integers and halves come out exact in bf16 too."""
    return np.eye(scores.shape[0], dtype=np.float32), np.ascontiguousarray(scores.T)


def _held_lists(rows, n=1500):
    """Scores [rows, n] whose chunk 0 gives every row the list 4, 3, 2, 1 (items
    0-3) and whose every other score is a filler far below: what a later chunk
    brings is written into it by the case."""
    scores = np.full((rows, n), -50.0, dtype=np.float32)
    scores[:, :4] = [4.0, 3.0, 2.0, 1.0]
    return scores


def _gate_case(name):
    """(xs, y, k, block_i) of one exactness case."""
    rng = np.random.default_rng(GATE_CASES.index(name))
    n = 1500  # 12 chunks of 128 over 3 blocks of 512, the last chunk short
    if name == "single-entrants-tied":
        # one entrant a row a chunk, so each is placed without a sort: equal to
        # a held value (it loses the tie to nothing held and goes after it),
        # above the best (lane 0), just above the k-th (lane k - 1); a score
        # EQUAL to the k-th does not enter and does not fire
        s = _held_lists(2, n)
        s[0, :4] = [9.0, 7.0, 5.0, 3.0]
        s[0, 300], s[0, 400], s[0, 700] = 7.0, 5.0, 9.0  # 400: the k-th by then
        s[1, 410], s[1, 701], s[1, 900] = 10.0, 2.0, 2.5  # 701: the k-th by then
        return (*_from_scores(s), 4, 512)
    if name == "two-and-one":  # two entrants in one row, one in another: a fold
        s = _held_lists(2, n)
        s[0, 300], s[0, 301], s[1, 310] = 8.0, 6.0, 10.0
        return (*_from_scores(s), 4, 512)
    if name == "one-each":  # one entrant in each of two rows: an insert
        s = _held_lists(2, n)
        s[0, 300], s[1, 310] = 8.0, 10.0
        return (*_from_scores(s), 4, 512)
    if name == "ascending":  # every chunk holds a new best: every chunk fires
        return (*_along_a_line(np.arange(n)), 16, 512)
    if name == "descending":  # nothing after the first chunk can enter
        return (*_along_a_line(-np.arange(n)), 16, 512)
    if name == "all-equal":  # strict >: nothing ever beats an equal score
        return (*_along_a_line(np.full(n, 3.0)), 100, 512)
    if name == "tie-runs-ascending":
        # runs of 100 equal scores straddle the chunk (128) and block (512)
        # edges; within a run the lowest index wins
        return (*_along_a_line(np.arange(n) // 100), 128, 512)
    if name == "tie-runs-descending":
        return (*_along_a_line(-(np.arange(n) // 100)), 128, 512)
    if name == "zero-rows-after-real":  # as the batcher pads a dispatch
        xs = _int_factors(rng, 24, 12)
        xs[5:] = 0.0
        return xs, _int_factors(rng, n, 12), 32, 256
    if name == "short-tail":  # n_items off the block: the -inf tail never fires
        return _int_factors(rng, 9, 12), _int_factors(rng, 1111, 12), 32, 512
    if name.startswith("k="):  # lane k - 1 is the threshold; lanes past it go stale
        return (
            _int_factors(rng, 11, 20, -30, 30), _int_factors(rng, 2500, 20, -30, 30),
            int(name[2:]), 256,
        )
    raise AssertionError(name)


GATE_CASES = [
    "ascending", "descending", "all-equal", "tie-runs-ascending",
    "tie-runs-descending", "zero-rows-after-real", "short-tail",
    "k=1", "k=10", "k=16", "k=32", "k=100", "k=128",
    "single-entrants-tied", "two-and-one", "one-each",
]

# (chunks fired, sublane tiles sorted, chunks inserted) of the built cases:
# chunk 0 folds (128 scores above -inf), every later fired chunk is as named
_BUILT_COUNTS = {
    "single-entrants-tied": (5, 1, 4), "two-and-one": (2, 2, 0), "one-each": (2, 1, 1),
}


def _model_counts(scores, k, block_b, rows=None):
    """What the kernel should count, walked in numpy: (chunks fired, sublane
    tiles sorted, chunks inserted). Per row block, a chunk fires if it holds
    a score above some REAL row's running k-th (the first `rows` rows of
    `scores` are real, None for all; a padding row never fires). A fired
    chunk that brings no row more than one such score is inserted and sorts
    nothing; any other is folded, and a fold sorts the narrowest of its
    widths that holds the block's live (8-row) tiles."""
    from oryx_tpu.ops.pallas_topk import _FOLD_TILES

    n_rows, n = scores.shape
    rows = n_rows if rows is None else min(rows, n_rows)
    fired = tile_count = inserted = 0
    for b in range(0, rows, block_b):
        blk = scores[b:min(b + block_b, rows)]  # the block's real rows
        live_tiles, n_tiles = -(-blk.shape[0] // 8), block_b // 8
        width = min(w for w in (*_FOLD_TILES, n_tiles) if live_tiles <= w <= n_tiles)
        top = np.full((blk.shape[0], k), -np.inf, dtype=np.float32)  # descending
        for c in range(0, n, 128):
            chunk = blk[:, c:c + 128]
            most = int((chunk > top[:, -1:]).sum(axis=1).max())
            if most:
                fired += 1
                inserted += most == 1
                tile_count += width * (most > 1)
                top = -np.sort(-np.concatenate([top, chunk], axis=1), axis=1)[:, :k]
    return fired, tile_count, inserted


@pytest.mark.parametrize("name", GATE_CASES)
def test_gated_kernel_is_bit_identical_to_top_k_of_the_plain_scores(name):
    xs, y, k, block_i = _gate_case(name)
    scores = xs @ y.T  # exact: integers far below 2**24
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores), k)
    v, i, chunks = topk_dot_batch_pallas(
        jnp.asarray(xs), jnp.asarray(y), k=k, block_b=8, block_i=block_i,
        interpret=True, counted=True,
    )
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    assert np.array_equal(np.asarray(v), np.asarray(v_ref))
    # the kernel's own counts against the model's: a fired chunk of an 8-row
    # block is inserted, or folded and sorts its one sublane tile
    folded, total, tiles, inserted = (int(c) for c in np.asarray(chunks))
    row_blocks = -(-xs.shape[0] // 8)
    item_chunks = -(-y.shape[0] // block_i) * (block_i // 128)
    assert total == row_blocks * item_chunks
    assert (folded, tiles, inserted) == _model_counts(scores, k, 8)
    assert tiles + inserted == folded <= total
    if name in _BUILT_COUNTS:
        assert (folded, tiles, inserted) == _BUILT_COUNTS[name]
    real_chunks = -(-y.shape[0] // 128)
    if name == "ascending":
        assert folded == row_blocks * real_chunks  # the worst case: all of them
    if name in ("descending", "all-equal"):
        assert folded == row_blocks * -(-k // 128)  # the first chunk alone


def test_gated_kernel_with_fewer_items_than_k_keeps_neg_inf_slots():
    rng = np.random.default_rng(8)
    xs, y = _int_factors(rng, 4, 6), _int_factors(rng, 40, 6)
    v, i, chunks = topk_dot_batch_pallas(
        jnp.asarray(xs), jnp.asarray(y), k=100, block_b=8, block_i=128,
        interpret=True, counted=True,
    )
    scores = xs @ y.T
    order = np.argsort(-scores, axis=1, kind="stable")
    assert np.array_equal(np.asarray(i)[:, :40], order)
    assert np.array_equal(np.asarray(v)[:, :40], np.take_along_axis(scores, order, axis=1))
    assert np.all(np.isneginf(np.asarray(v)[:, 40:]))
    assert [int(c) for c in np.asarray(chunks)] == [1, 1, 1, 0]


@pytest.mark.parametrize("k", [10, 128])
def test_gated_int8_kernel_is_bit_identical_to_top_k_of_its_scores(k):
    # integer item rows quantize to themselves times one scale per row, so
    # the int8 kernel's scores (int32 dot x item scale) are exact too; the
    # per-row scales reorder items across rows and enter before the gate
    from oryx_tpu.ops.pallas_topk import quantize_queries

    rng = np.random.default_rng(40 + k)
    q = rng.integers(-127, 128, size=(1700, 16)).astype(np.int8)
    scale = rng.choice([0.25, 0.5, 1.0, 2.0], size=1700).astype(np.float32)
    xs = _int_factors(rng, 10, 16, -127, 127)
    xs[:, 0] = 127.0  # every row's scale is exactly 1: quantized to itself
    xs[6:] = 0.0
    qx, sx = quantize_queries(jnp.asarray(xs))
    assert np.array_equal(np.asarray(qx), xs) and np.all(np.asarray(sx) == 1.0)
    scores = (xs.astype(np.int64) @ q.T.astype(np.int64)).astype(np.float32) * scale
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores), k)
    v, i, chunks = topk_dot_batch_pallas(
        jnp.asarray(xs), jnp.asarray(q), scales=jnp.asarray(scale), k=k,
        block_b=8, block_i=1024, interpret=True, counted=True,
    )
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    assert np.array_equal(np.asarray(v), np.asarray(v_ref))
    folded, total, tiles, inserted = (int(c) for c in np.asarray(chunks))
    # the last of the two 8-row blocks holds the batch pad's rows 10-15
    assert (folded, tiles, inserted) == _model_counts(scores, k, 8)
    assert inserted <= folded < total


# -- row blocks past the real rows are not walked (ISSUE 30), and a fold works
# -- on the live sublane tiles of its row block alone (ISSUE 32) ---------------
#
# A 512-row query block as the batcher pads it: `rows` real rows, then zeros,
# in four row blocks of 128 (sixteen 8-row tiles each) over 1,500 items (12
# chunks in 3 item blocks). The contract: the real rows bit for bit
# `lax.top_k`'s, every row at or past `rows` (-inf, index 0).

_REAL_ROWS = [1, 5, 7, 8, 9, 64, 127, 128, 129, 300, 511, 512]


@functools.lru_cache(maxsize=None)
def _padded_dispatch(dtype, items=1500, taper=1.0):
    """(xs of 512 real rows, item operands, plain scores of them): integer
    factors, so both forms score exactly and are compared bit for bit. The
    item factors shrink along the catalog to `taper` of their size (still
    integers), so late chunks bring a row fewer entrants."""
    rng = np.random.default_rng(30)
    shrink = (1.0 - (1.0 - taper) * np.arange(items) / items)[:, None]
    if dtype == "int8":
        q = rng.integers(-127, 128, size=(items, 16))
        q = np.trunc(q * shrink).astype(np.int8)
        scale = rng.choice([0.25, 0.5, 1.0, 2.0], size=items).astype(np.float32)
        xs = _int_factors(rng, 512, 16, -127, 127)
        xs[:, 0] = 127.0  # every real row quantizes to itself (scale 1)
        scores = (xs.astype(np.int64) @ q.T.astype(np.int64)).astype(np.float32) * scale
        return xs, dict(y=jnp.asarray(q), scales=jnp.asarray(scale)), scores
    xs, y = _int_factors(rng, 512, 12), _int_factors(rng, items, 12)
    y = np.trunc(y * shrink).astype(np.float32)
    return xs, dict(y=jnp.asarray(y, dtype=jnp.bfloat16)), xs @ y.T


def _run_padded(xs, operands, rows, real=None, k=32, block_i=512):
    x = xs.copy()
    x[rows:] = 0.0
    dtype = jnp.float32 if "scales" in operands else jnp.bfloat16
    v, i, c = topk_dot_batch_pallas(
        jnp.asarray(x, dtype=dtype), operands["y"], scales=operands.get("scales"),
        k=k, block_i=block_i, interpret=True, counted=True, rows=real,
    )
    return np.asarray(v), np.asarray(i), [int(n) for n in np.asarray(c)]


def _is_filler(v, i):
    return bool(np.all(np.isneginf(v)) and not i.any())


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("rows", _REAL_ROWS)
def test_row_blocks_past_the_real_rows_are_not_walked(rows, dtype):
    xs, operands, scores = _padded_dispatch(dtype)
    v, i, (folded, walked, tiles, inserted) = _run_padded(xs, operands, rows, real=rows)
    v_all, i_all, (folded_all, walked_all, tiles_all, inserted_all) = _run_padded(
        xs, operands, rows
    )
    # the real rows: what calling every row real gives, and lax.top_k's answer
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores[:rows]), 32)
    assert np.array_equal(v[:rows], v_all[:rows]) and np.array_equal(i[:rows], i_all[:rows])
    assert np.array_equal(v[:rows], np.asarray(v_ref)) and np.array_equal(i[:rows], np.asarray(i_ref))
    # every row at or past `rows` holds the one filler, in the block the last
    # real row falls in as in the blocks past it
    assert _is_filler(v[rows:], i[rows:])
    # the kernel's own counts: live blocks x 12 item chunks walked; no fold for
    # a padding row (a zero row CALLED real folds its first chunk alone); and
    # the tiles its folds sorted, against the plain model of them
    live = -(-rows // 128)
    assert (walked, walked_all) == (live * 12, 4 * 12)
    assert (folded, tiles, inserted) == _model_counts(scores, 32, 128, rows=rows)
    padded = np.where(np.arange(512)[:, None] < rows, scores, 0.0)
    assert (folded_all, tiles_all, inserted_all) == _model_counts(padded, 32, 128)
    # every row real: the folds sort whole blocks alone
    assert tiles_all == 16 * (folded_all - inserted_all)
    # a block of zero rows folds once (128 scores of 0.0 above -inf), and the
    # zero rows of a live block enter with the first chunk, which folds anyway
    assert (folded_all, inserted_all) == (folded + (4 - live), inserted)
    sorts = folded - inserted  # the fired chunks that were folded
    if rows < 128:  # the narrowest width over the live tiles of block 0
        assert tiles == sorts * {1: 1, 5: 1, 7: 1, 8: 1, 9: 2, 64: 8, 127: 16}[rows]
    if rows == 129:
        assert sorts < tiles < 16 * sorts  # block 0 whole, block 1 one tile


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_every_row_real_is_what_no_count_gives(dtype):
    xs, operands, _ = _padded_dispatch(dtype)
    counted = _run_padded(xs, operands, 512, real=512)
    plain = _run_padded(xs, operands, 512)
    assert all(np.array_equal(a, b) for a, b in zip(counted, plain))
    # a count past the block, or none at all, walks every block too
    assert _run_padded(xs, operands, 512, real=10_000)[2] == plain[2]
    # no real row at all: nothing is walked or folded, every row is filler
    v, i, counts = _run_padded(xs, operands, 512, real=0)
    assert counts == [0, 0, 0, 0] and _is_filler(v, i)


@pytest.mark.parametrize("rows", [1, 8, 9, 40, 128, 136])
def test_live_tiles_in_the_worst_case_every_chunk_folds(rows):
    # ascending scores along one feature: every chunk of real items holds a
    # new best for every real row, so every chunk folds, in every live block,
    # and the result is still lax.top_k's bit for bit
    xs, y = _along_a_line(np.arange(1500), rows=rows)
    xs = np.concatenate([xs, np.zeros((512 - rows, 1), np.float32)])
    scores = xs[:rows] @ y.T
    v, i, (folded, walked, tiles, inserted) = (np.asarray(a) for a in topk_dot_batch_pallas(
        jnp.asarray(xs), jnp.asarray(y), k=128, block_i=512, interpret=True,
        counted=True, rows=rows,
    ))
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores), 128)
    assert np.array_equal(v[:rows], np.asarray(v_ref)) and np.array_equal(i[:rows], np.asarray(i_ref))
    assert _is_filler(v[rows:], i[rows:])
    live = -(-rows // 128)
    assert (int(folded), int(walked)) == (live * 12, live * 12)
    # 128 new bests a row a chunk: nothing is a single entrant
    assert (int(folded), int(tiles), int(inserted)) == _model_counts(scores, 128, 128)
    assert int(inserted) == 0
    # 1-8 rows: one tile a fold; 9: two; 40: five live, so the width of
    # eight; a full block: all sixteen; 136: sixteen for block 0, one for 1
    assert int(tiles) == 12 * {1: 1, 8: 1, 9: 2, 40: 8, 128: 16, 136: 17}[rows]


@pytest.mark.parametrize("rows", [7, 8, 9, 10, 16, 17])
def test_duplicate_scores_straddling_a_tile_boundary(rows):
    # the same query in every row on both sides of the 8-row tile edges, over
    # items whose every score appears five times: each tile sorts on its own,
    # and every real row must still come back as lax.top_k's stable answer
    rng = np.random.default_rng(32)
    y = np.repeat(_int_factors(rng, 300, 12), 5, axis=0)
    xs = np.zeros((512, 12), np.float32)
    xs[:rows] = _int_factors(rng, 1, 12)
    scores = xs[:rows] @ y.T
    v, i, (folded, _, tiles, inserted) = (np.asarray(a) for a in topk_dot_batch_pallas(
        jnp.asarray(xs), jnp.asarray(y), k=25, block_i=512, interpret=True,
        counted=True, rows=rows,
    ))
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores), 25)
    assert np.array_equal(v[:rows], np.asarray(v_ref)) and np.array_equal(i[:rows], np.asarray(i_ref))
    assert all(np.array_equal(i[r], i[0]) for r in range(rows))
    assert _is_filler(v[rows:], i[rows:])
    assert int(tiles) == int(folded - inserted) * {1: 1, 2: 2, 3: 4}[-(-rows // 8)]


# -- a fired chunk's single entrants are placed without the sort (ISSUE 35) ----
#
# A chunk that brings no row more than one score above the row's running k-th
# is inserted: lanes [:k] must come out bit for bit as a fold's, whatever the
# width it is placed at, and the kernel's fourth count says how often.

@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("rows", [1, 5, 9, 40, 129])
@pytest.mark.parametrize("k", [10, 32, 128])
def test_single_entrants_are_placed_bit_for_bit(k, rows, dtype):
    # 16,384 items in 128 chunks, their factors shrinking to four ninths: the
    # late chunks that fire bring a row one entrant more often than two, so
    # inserts and folds alternate at every width (1, 2 and 8 tiles, the whole
    # block, a whole block and a tile)
    xs, operands, scores = _padded_dispatch(dtype, items=16384, taper=4 / 9)
    v, i, (folded, walked, tiles, inserted) = _run_padded(
        xs, operands, rows, real=rows, k=k, block_i=2048
    )
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores[:rows]), k)
    assert np.array_equal(v[:rows], np.asarray(v_ref)) and np.array_equal(i[:rows], np.asarray(i_ref))
    assert _is_filler(v[rows:], i[rows:])
    assert (folded, tiles, inserted) == _model_counts(scores, k, 128, rows=rows)
    assert 0 < inserted < folded < walked == -(-rows // 128) * 128


@pytest.mark.parametrize(
    "row_a,row_b,counts",
    [
        (0, 1, (2, 1, 1)),      # one tile
        (0, 8, (2, 2, 1)),      # two tiles: placed at the width of two
        (3, 20, (2, 4, 1)),     # three live tiles: the width of four
        (2, 127, (2, 16, 1)),   # the whole block
        (5, 130, (4, 17, 2)),   # a whole block and one tile of the next
    ],
    ids=["one-tile", "two-tiles", "four-tiles", "whole-block", "two-blocks"],
)
def test_single_entrants_in_rows_of_different_tiles(row_a, row_b, counts):
    # chunk 0 folds into every real row (4, 3, 2, 1); chunk 2 brings one entrant
    # to row_a (a new best) and one to row_b (just above its k-th) and nothing to
    # the rows between: placed, at the width that holds the block's live tiles
    rows = row_b + 1
    scores = _held_lists(rows)
    scores[row_a, 300], scores[row_b, 310] = 10.0, 1.5
    # 136 features in every case (the rows' own, then zeros): one program
    xs, y = np.zeros((512, 136), np.float32), np.zeros((1500, 136), np.float32)
    xs[:rows, :rows], y[:, :rows] = _from_scores(scores)
    v, i, chunks = (np.asarray(a) for a in topk_dot_batch_pallas(
        jnp.asarray(xs), jnp.asarray(y), k=4, block_i=512, interpret=True,
        counted=True, rows=rows,
    ))
    v_ref, i_ref = jax.lax.top_k(jnp.asarray(scores), 4)
    assert np.array_equal(v[:rows], np.asarray(v_ref)) and np.array_equal(i[:rows], np.asarray(i_ref))
    assert list(i[row_a]) == [300, 0, 1, 2] and list(i[row_b]) == [0, 1, 2, 310]
    assert _is_filler(v[rows:], i[rows:])
    folded, _, tiles, inserted = (int(c) for c in chunks)
    assert (folded, tiles, inserted) == counts == _model_counts(scores, 4, 128)


def test_counts_of_a_standard_normal_catalog():
    # (fired, walked, tiles sorted, inserted): an insert is a fired chunk and
    # sorts nothing; over 20,000 standard-normal items most late chunks that
    # fire bring one entrant
    rng = np.random.default_rng(35)
    xs = jnp.asarray(rng.normal(size=(3, 16)), dtype=jnp.float32)
    y = jnp.asarray(rng.normal(size=(20_000, 16)), dtype=jnp.float32)
    v, i, chunks = topk_dot_batch_pallas(
        xs, y, k=10, block_b=8, block_i=1024, interpret=True, counted=True
    )
    v_ref, i_ref = topk_dot_batch_xla(xs, y, k=10)
    assert np.array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), atol=1e-4)
    fired, walked, tiles, inserted = (int(c) for c in np.asarray(chunks))
    assert walked == 160 and tiles == fired - inserted
    assert 0 < inserted <= fired < walked and inserted > fired // 2


def test_the_count_of_real_rows_is_traced_not_compiled_in():
    from oryx_tpu.ops.pallas_topk import _topk_pallas_jit

    xs, operands, _ = _padded_dispatch("bf16")
    # counts on the host ride the call as a numpy operand, counts on the
    # device are stacked there: two signatures of the one compiled program
    _run_padded(xs, operands, 3, real=3)
    _run_padded(xs, operands, 3, real=jnp.asarray(3))
    entries = _topk_pallas_jit._cache_size()
    # one live tile, several, a whole block, two blocks, none: one program
    for real in (4, 9, 128, 200, 0, np.int32(7), jnp.asarray(9), None):
        _run_padded(xs, operands, 3, real=real)
    assert _topk_pallas_jit._cache_size() == entries


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_host_operands_ride_the_one_program(dtype):
    """The query block and the two counts handed over as numpy arrays (what
    the batcher forms since ISSUE 45) run the program that device arrays
    run: the same operand types, no second entry, the same bits back."""
    import ml_dtypes

    from oryx_tpu.ops.pallas_topk import _topk_pallas_jit, stage_counts

    xs, operands, _ = _padded_dispatch(dtype)
    host_dtype = np.float32 if dtype == "int8" else ml_dtypes.bfloat16
    block = np.zeros(xs.shape, dtype=host_dtype)
    block[:5] = xs[:5]  # integers: the cast is exact
    counts = stage_counts(5, None, 512, 1500)
    assert isinstance(counts, np.ndarray) and counts.tolist() == [5, 1500]

    def run(x, c):
        out = topk_dot_batch_pallas(
            x, operands["y"], scales=operands.get("scales"), k=32,
            block_i=512, interpret=True, counted=True, rows=c,
        )
        return [np.asarray(o) for o in out]

    compiled = []

    def on_duration(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(seconds)

    on_device = run(jnp.asarray(block), jnp.asarray(counts))
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        on_host = run(block, counts)  # a second signature, no second program
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert not compiled
    assert all(np.array_equal(a, b) for a, b in zip(on_host, on_device))
    assert np.array_equal(on_host[1], _run_padded(xs, operands, 5, real=5)[1])

    def program(x, c):
        y = jnp.pad(operands["y"], ((0, 36), (0, 128 - operands["y"].shape[1])))
        scales = operands.get("scales")
        return _topk_pallas_jit.lower(
            x, y, None if scales is None else jnp.pad(scales, (0, 36)), c,
            k=32, block_b=128, block_i=512, quantized=scales is not None,
            interpret=True,
        )

    lowered = program(block, counts)
    assert [str(a) for a in lowered.in_avals[0][:1] + lowered.in_avals[0][3:]] == [
        ("float32" if dtype == "int8" else "bfloat16") + f"[512,{xs.shape[1]}]",
        "int32[2]",
    ]
    assert lowered.as_text() == program(jnp.asarray(block), jnp.asarray(counts)).as_text()


# -- item blocks past the valid rows are neither streamed nor scored (ISSUE 40) -
#
# A view stored with room to grow: 1,536 rows in three item blocks of 512
# (twelve chunks), of which the first `n_valid` are items. The rows behind them
# score far above every item for every query row, so one that was selected, or
# that raised a row's threshold, would show in the result. The contract: bit
# for bit the kernel's answer over `y[:n_valid]`.

_VIEW_ROWS, _VIEW_BLOCK = 1536, 512
# 1, a chunk's edge and either side of it, an item block's edge and either
# side, mid-view, the whole view
_N_VALID = [1, 127, 128, 129, 511, 512, 513, 750, _VIEW_ROWS]


@functools.lru_cache(maxsize=None)
def _view_with_headroom(dtype, n_valid):
    """(xs of 256 real rows, item operands) of such a view: integer factors,
    every query row with the same positive first feature, which is zero in
    every item and huge in every row past `n_valid`."""
    rng = np.random.default_rng(40)
    past = np.arange(_VIEW_ROWS) >= n_valid
    if dtype == "int8":
        q = rng.integers(-127, 128, size=(_VIEW_ROWS, 16))
        scale = rng.choice([0.25, 0.5, 1.0, 2.0], size=_VIEW_ROWS).astype(np.float32)
        q[:, 0] = 0
        q[past] = 0
        q[past, 0], scale[past] = 127, 64.0  # 127 x 127 x 64: above any item's score
        xs = _int_factors(rng, 256, 16, -127, 127)
        xs[:, 0] = 127.0  # and every real row quantizes to itself (scale 1)
        return xs, dict(y=q.astype(np.int8), scales=scale)
    xs, y = _int_factors(rng, 256, 12), _int_factors(rng, _VIEW_ROWS, 12)
    xs[:, 0], y[:, 0] = 8.0, 0.0
    y[past] = 0.0
    y[past, 0] = 256.0  # 2,048 against at most 11 x 81
    return xs, dict(y=y)


def _run_view(xs, operands, rows, n_valid, upto=None, k=32, staged=None):
    """The kernel over the view's first `upto` rows (None: all of it) with
    `rows` real query rows of 256 and the count `n_valid` (None: not given);
    `staged` stands in for `rows` as the operand the kernel is handed."""
    x = xs.copy()
    x[rows:] = 0.0
    quantized = "scales" in operands
    v, i, c = topk_dot_batch_pallas(
        jnp.asarray(x, dtype=jnp.float32 if quantized else jnp.bfloat16),
        jnp.asarray(operands["y"][:upto], dtype=jnp.int8 if quantized else jnp.bfloat16),
        scales=jnp.asarray(operands["scales"][:upto]) if quantized else None,
        k=k, block_i=_VIEW_BLOCK, interpret=True, counted=True,
        rows=rows if staged is None else staged, n_valid=n_valid,
    )
    return np.asarray(v), np.asarray(i), [int(n) for n in np.asarray(c)]


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("rows", [1, 5, 128])
@pytest.mark.parametrize("n_valid", _N_VALID)
def test_item_blocks_past_the_valid_rows_are_not_walked(n_valid, rows, dtype):
    xs, operands = _view_with_headroom(dtype, n_valid)
    v, i, (fired, walked, tiles, inserted) = _run_view(xs, operands, rows, n_valid)
    v_cut, i_cut, (fired_cut, _, tiles_cut, inserted_cut) = _run_view(
        xs, operands, rows, None, upto=n_valid
    )
    # values and indices bit for bit those of the kernel over y[:n_valid], and
    # the same chunks fired, folded and placed on the way: no row past the
    # count was selected, and none moved a threshold
    assert np.array_equal(v, v_cut) and np.array_equal(i, i_cut)
    assert (fired, tiles, inserted) == (fired_cut, tiles_cut, inserted_cut)
    assert i[:rows].max() < n_valid and _is_filler(v[rows:], i[rows:])
    assert np.all(np.isneginf(v[:rows, n_valid:])) and np.all(np.isfinite(v[:rows, :n_valid]))
    # one live row block of the two, over the item blocks that hold an item
    assert walked == 1 * -(-n_valid // _VIEW_BLOCK) * (_VIEW_BLOCK // 128)
    if n_valid < _VIEW_ROWS:
        # the rows behind ARE there to be found: without the count they win
        assert _run_view(xs, operands, rows, None)[1][:rows].min() >= n_valid


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_the_count_of_valid_rows_is_traced_not_compiled_in(dtype):
    from oryx_tpu.ops.pallas_topk import _topk_pallas_jit

    xs, operands = _view_with_headroom(dtype, _VIEW_ROWS)
    whole = _run_view(xs, operands, 5, None)
    _run_view(xs, operands, 5, jnp.asarray(_VIEW_ROWS))  # a count on the device:
    entries = _topk_pallas_jit._cache_size()  # the program's second signature
    # no count is the whole view; so is one past it (the kernel reads no
    # further than the operand it was given)
    for n_valid in (_VIEW_ROWS, np.int32(_VIEW_ROWS), jnp.asarray(_VIEW_ROWS), 10**6):
        again = _run_view(xs, operands, 5, n_valid)
        assert all(np.array_equal(a, b) for a, b in zip(again, whole))
    # every other count, and the two counts staged as one array: one program
    for n_valid in (700, 1, jnp.asarray(129)):
        _run_view(xs, operands, 5, n_valid)
    staged = jnp.asarray(np.array([5, 700], dtype=np.int32))
    assert all(
        np.array_equal(a, b) for a, b in
        zip(_run_view(xs, operands, 5, None, staged=staged), _run_view(xs, operands, 5, 700))
    )
    assert _topk_pallas_jit._cache_size() == entries
    # no item at all: nothing is walked, every row is what a dead block returns
    v, i, counts = _run_view(xs, operands, 5, 0)
    assert counts == [0, 0, 0, 0] and _is_filler(v, i)
    assert _topk_pallas_jit._cache_size() == entries
