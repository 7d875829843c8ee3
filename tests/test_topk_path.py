"""ops.als.topk_path: which scoring path a dispatch takes is read off what
the matrix shows (its form, its rows, the device it lies on) and off the
request's k and recall, before any call. One table holds the whole
selection, so a change to it is a change to a row here."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.ops import als
from oryx_tpu.ops.transfer import ChunkedMatrix, QuantizedMatrix, ShardedMatrix
from oryx_tpu.parallel.shardspec import RowShards

BIG = als.PALLAS_TOPK_MIN_ITEMS  # 32,768: the fewest rows worth streaming
SMALL = BIG - 1
K_FITS = als.PALLAS_TOPK_MAX_K  # 128: the kernel's running top-k tile
K_OVER = K_FITS + 1


def _dense(n):
    return jnp.zeros((n, 8), dtype=jnp.bfloat16)


def _quantized(n):
    return QuantizedMatrix(
        jnp.zeros((n, 8), dtype=jnp.int8), jnp.ones((n,), dtype=jnp.float32)
    )


def _chunked(n):
    return ChunkedMatrix([_dense(n // 2), _dense(n - n // 2)])


def _sharded(n, of=_dense):
    plan = RowShards.plan(n, 2)
    return ShardedMatrix([of(plan.size(s)) for s in range(2)], plan)


def _sharded_int8(n):
    return _sharded(n, of=_quantized)


# (matrix form, rows, k, recall, on a TPU) -> path
_TABLE = [
    (_dense, BIG, 10, 1.0, True, "pallas"),
    (_dense, BIG, K_FITS, 1.0, True, "pallas"),
    (_dense, BIG, K_OVER, 1.0, True, "xla"),
    (_dense, SMALL, 10, 1.0, True, "xla"),
    (_dense, BIG, 10, 1.0, False, "xla"),
    (_dense, BIG, 10, 0.95, True, "approx"),
    (_dense, BIG, 10, 0.95, False, "approx"),
    (_dense, SMALL, K_OVER, 0.9, False, "approx"),
    (_quantized, BIG, 10, 1.0, True, "pallas-int8"),
    (_quantized, BIG, K_FITS, 1.0, True, "pallas-int8"),
    (_quantized, BIG, K_OVER, 1.0, True, "xla-int8"),
    (_quantized, SMALL, 10, 1.0, True, "xla-int8"),
    (_quantized, BIG, 10, 1.0, False, "xla-int8"),
    # an int8 view has no approximate form: recall < 1 only leaves the kernel
    (_quantized, BIG, 10, 0.95, True, "xla-int8"),
    # a chunked or sharded view is named by its form alone; each chunk and
    # shard re-enters the selection with its own rows and dtype
    (_chunked, BIG, 10, 1.0, True, "chunked"),
    (_chunked, SMALL, K_OVER, 0.9, False, "chunked"),
    (_sharded, BIG, 10, 1.0, True, "sharded"),
    (_sharded, SMALL, K_OVER, 0.9, False, "sharded"),
    (_sharded_int8, BIG, 10, 1.0, True, "sharded"),
]


def _case_id(case):
    form, n, k, recall, tpu, _ = case
    return "-".join([
        form.__name__.lstrip("_"),
        "big" if n >= BIG else "small",
        f"k{k}",
        "exact" if recall >= 1.0 else f"r{recall}",
        "tpu" if tpu else "cpu",
    ])


@pytest.mark.parametrize("case", _TABLE, ids=_case_id)
def test_path_is_chosen_from_what_the_matrix_shows(monkeypatch, case):
    form, n, k, recall, tpu, want = case
    asked = []

    def on_tpu(a):
        asked.append(a)
        return tpu

    monkeypatch.setattr(als, "_on_tpu", on_tpu)
    assert als.topk_path(form(n), k, recall) == want
    # the platform is read off a device array (an int8 view's `q`), never
    # off a wrapper, and not at all where the form decides alone
    assert all(isinstance(a, jax.Array) for a in asked)
    if want in ("chunked", "sharded"):
        assert not asked


# -- the count of valid item rows is the fused kernel's alone (ISSUE 40) --------


def _filled(form, n, seed=40):
    """`form` over n rows of integer factors (a score is exact in every dtype)."""
    rng = np.random.default_rng(seed)
    dense = jnp.asarray(rng.integers(-9, 10, size=(n, 8)), dtype=jnp.bfloat16)
    scale = jnp.asarray(rng.choice([0.5, 1.0, 2.0], size=n), dtype=jnp.float32)

    def part(lo, hi):
        if form in (_quantized, _sharded_int8):
            return QuantizedMatrix(dense[lo:hi].astype(jnp.int8), scale[lo:hi])
        return dense[lo:hi]

    if form in (_dense, _quantized):
        return part(0, n)
    if form is _chunked:
        return ChunkedMatrix([part(0, n // 2), part(n // 2, n)])
    plan = RowShards.plan(n, 2)
    return ShardedMatrix(
        [part(plan.bounds[s], plan.bounds[s + 1]) for s in range(2)], plan
    )


@pytest.mark.parametrize(
    "form, recall, want",
    [
        (_dense, 1.0, "xla"),
        (_dense, 0.95, "approx"),
        (_quantized, 1.0, "xla-int8"),
        (_chunked, 1.0, "chunked"),
        (_sharded, 1.0, "sharded"),
        (_sharded_int8, 1.0, "sharded"),
    ],
    ids=["xla", "approx", "xla-int8", "chunked", "sharded", "sharded-int8"],
)
def test_a_path_that_is_not_fused_ignores_the_count_of_valid_rows(form, recall, want):
    # off the fused kernel the whole capacity is scored, with `n_valid` as
    # without it, and the counts staged for such a path stay what they were:
    # nothing is uploaded for a kernel that is not there
    y = _filled(form, 300)
    xs = jnp.asarray(
        np.random.default_rng(41).integers(-9, 10, size=(5, 8)), dtype=jnp.float32
    )
    assert als.topk_path(y, 10, recall) == want
    plain = als.topk_dot_batch(xs, y, k=10, recall=recall, counted=True, rows=3)
    for n_valid in (200, 0, 300, 10**6):
        counted = als.topk_dot_batch(
            xs, y, k=10, recall=recall, counted=True, rows=3, n_valid=n_valid
        )
        assert counted[2] is None and plain[2] is None
        assert np.array_equal(np.asarray(counted[0]), np.asarray(plain[0]))
        assert np.array_equal(np.asarray(counted[1]), np.asarray(plain[1]))
    assert int(np.asarray(plain[1]).max()) >= 200  # rows past the count are scored
    _, rows = als.stage_topk_operands(xs, y, k=10, recall=recall, rows=3, n_valid=200)
    assert rows == 3


@pytest.mark.parametrize("form", [_dense, _quantized], ids=["pallas", "pallas-int8"])
def test_the_fused_paths_counts_are_staged_as_one_array(monkeypatch, form):
    # ONE int32[2] array for both counts, and a HOST one where both are host
    # numbers: it rides the jitted call, nothing is uploaded for it (ISSUE 45)
    monkeypatch.setattr(als, "_on_tpu", lambda a: True)
    y, xs = form(BIG), np.zeros((4, 8), dtype=np.float32)
    assert als.topk_path(y, 10) in ("pallas", "pallas-int8")
    for rows, n_valid, want in [
        (3, 20_000, [3, 20_000]), (None, 20_000, [4, 20_000]), (3, None, [3, BIG]),
    ]:
        _, staged = als.stage_topk_operands(xs, y, k=10, rows=rows, n_valid=n_valid)
        assert isinstance(staged, np.ndarray) and staged.dtype == np.int32
        assert staged.tolist() == want
    # a count that is on the device already is stacked there, as before
    _, staged = als.stage_topk_operands(xs, y, k=10, rows=jnp.int32(3), n_valid=20_000)
    assert isinstance(staged, jax.Array) and staged.dtype == jnp.int32
    assert [int(c) for c in staged] == [3, 20_000]
    # neither count given: nothing to stage, the kernel's wrapper takes all of both
    assert als.stage_topk_operands(xs, y, k=10)[1] is None


# -- a dispatch's host operands ride the jitted call (ISSUE 45) ------------------

_F32_TINY = float(np.finfo(np.float32).tiny)  # the smallest normal, bf16's too


def _from_bits(bits):
    return np.asarray(bits, dtype=np.uint32).view(np.float32)


def _edge_queries(kind, shape, rng):
    """float32 queries whose cast to bfloat16 is the hard case of `kind`."""
    n = int(np.prod(shape))
    if kind == "ties":
        # the 16 bits that the cast drops are exactly half (ties to even, on
        # an even and on an odd kept mantissa), one under and one over it
        kept = rng.integers(0x3C00, 0x4200, size=n).astype(np.uint32) << 16
        sign = rng.integers(0, 2, size=n).astype(np.uint32) << 31
        low = rng.choice(np.array([0x8000, 0x7FFF, 0x8001], dtype=np.uint32), size=n)
        vals = _from_bits(kept | sign | low)
    elif kind == "subnormal":
        # float32 subnormals, bfloat16 subnormals (the same exponent range),
        # the smallest normals, and a rounding up out of the subnormals
        vals = rng.choice(
            np.concatenate([
                _from_bits([0x00000001, 0x00008000, 0x00018000, 0x007F8000, 0x007FFFFF]),
                np.float32([_F32_TINY, -_F32_TINY, 3 * _F32_TINY, 0.0, -0.0]),
            ]),
            size=n,
        ) * rng.choice(np.float32([1.0, -1.0]), size=n)
    else:
        # up to bfloat16's largest finite value, a rounding up to it, and the
        # float32 values beyond it that round to infinity
        vals = rng.choice(
            np.concatenate([
                _from_bits([0x7F7F0000, 0x7F7E8000, 0x7F7F7FFF, 0x7F7F8000, 0x7F7FFFFF]),
                np.float32([1e30, -3e38, 6.5e4, 1.0]),
            ]),
            size=n,
        ) * rng.choice(np.float32([1.0, -1.0]), size=n)
    return vals.astype(np.float32).reshape(shape)


# path -> (matrix form, rows, recall) that takes it where `_on_tpu` says so
_PATH_VIEWS = {
    "pallas": (_dense, BIG, 1.0),
    "pallas-int8": (_quantized, BIG, 1.0),
    "xla": (_dense, 300, 1.0),
    "approx": (_dense, 300, 0.95),
}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("kind", ["ties", "subnormal", "large"])
@pytest.mark.parametrize("path", list(_PATH_VIEWS))
def test_a_host_formed_block_answers_bit_for_bit_as_the_parents_staging(
    monkeypatch, path, kind
):
    """The batcher's block, zero-filled on the host in the dtype the path
    scores in with the real rows cast as they are copied in, against the
    parent's staging of the same queries (a float32 upload, then a cast on
    the device): the same bits reach the same jitted call, so values and
    indices agree to the bit on every path."""
    from functools import partial

    from oryx_tpu.ops import pallas_topk

    rng = np.random.default_rng(45)
    monkeypatch.setattr(als, "_on_tpu", lambda a: path.startswith("pallas"))
    monkeypatch.setattr(
        pallas_topk, "topk_dot_batch_pallas",
        partial(pallas_topk.topk_dot_batch_pallas, interpret=True),
    )
    form, n, recall = _PATH_VIEWS[path]
    y = _filled(form, n)
    assert als.topk_path(y, 10, recall) == path
    real, padded = 5, 16
    queries = _edge_queries(kind, (real, 8), rng)

    dtype = als.query_dtype(y, 10, recall)
    assert dtype == (np.float32 if path.endswith("int8") else y.dtype)
    block = np.zeros((padded, y.shape[1]), dtype=dtype)
    for i, q in enumerate(queries):
        block[i, :8] = q
    staged, counts = als.stage_topk_operands(block, y, k=10, recall=recall, rows=real)
    assert staged is block  # nothing left to do to it, and nothing uploaded

    parent = jnp.zeros((padded, y.shape[1]), dtype=jnp.float32).at[:real, :8].set(queries)
    if not path.endswith("int8"):
        parent = jnp.asarray(parent, dtype=y.dtype)  # the eager cast that went
    assert np.array_equal(
        np.asarray(parent).view(np.uint8), block.view(np.uint8)
    )
    got = als.topk_dot_batch(staged, y, k=10, recall=recall, rows=counts)
    want = als.topk_dot_batch(parent, y, k=10, recall=recall, rows=real)
    assert np.array_equal(_bits(got[0]), _bits(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("form", [_chunked, _sharded], ids=["chunked", "sharded"])
def test_a_fan_out_uploads_a_host_block_once_and_re_enters_with_device_arrays(
    monkeypatch, form
):
    """A chunked or sharded matrix: the host block is uploaded ONCE, before
    the fan-out, every chunk and shard re-enters topk_dot_batch with a
    device array and finds nothing left to stage on it; an operand that is
    on the device in the matrix's dtype goes through as the very object."""
    y = _filled(form, 300)
    block = np.zeros((4, 8), dtype=als.query_dtype(y, 10))
    assert block.dtype == np.float32  # each part re-enters with its own dtype
    block[:3] = np.random.default_rng(45).integers(-9, 10, size=(3, 8))

    uploads, entries = [], []
    real_asarray, real_entry = jnp.asarray, als.topk_dot_batch

    def asarray(a, *args, **kw):
        if isinstance(a, np.ndarray) and a.shape == block.shape:
            uploads.append(a)
        return real_asarray(a, *args, **kw)

    def entry(xs, part, **kw):
        entries.append((part, xs))
        return real_entry(xs, part, **kw)

    monkeypatch.setattr(jnp, "asarray", asarray)
    monkeypatch.setattr(als, "topk_dot_batch", entry)
    vals, idx = als.topk_dot_batch(block, y, k=10, rows=3)
    assert len(uploads) == 1 and uploads[0] is block
    assert entries[0] == (y, block) and len(entries) == 3  # then its two parts
    assert all(isinstance(xs, jax.Array) for _, xs in entries[1:])
    want = real_entry(jnp.asarray(block), _filled(_dense, 300), k=10)
    assert np.array_equal(np.asarray(idx)[:3], np.asarray(want[1])[:3])

    part = (y.chunks if form is _chunked else y.shards)[0]
    on_device = jnp.asarray(block, dtype=part.dtype)
    assert als.stage_topk_operands(on_device, part, k=10)[0] is on_device
