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
    monkeypatch.setattr(als, "_on_tpu", lambda a: True)
    y, xs = form(BIG), np.zeros((4, 8), dtype=np.float32)
    assert als.topk_path(y, 10) in ("pallas", "pallas-int8")
    for rows, n_valid, want in [
        (3, 20_000, [3, 20_000]), (None, 20_000, [4, 20_000]), (3, None, [3, BIG]),
    ]:
        _, staged = als.stage_topk_operands(xs, y, k=10, rows=rows, n_valid=n_valid)
        assert isinstance(staged, jax.Array) and staged.dtype == jnp.int32
        assert [int(c) for c in staged] == want
    # neither count given: nothing to stage, the kernel's wrapper takes all of both
    assert als.stage_topk_operands(xs, y, k=10)[1] is None
