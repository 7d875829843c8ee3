"""ops.als.topk_path: which scoring path a dispatch takes is read off what
the matrix shows (its form, its rows, the device it lies on) and off the
request's k and recall, before any call. One table holds the whole
selection, so a change to it is a change to a row here."""

from __future__ import annotations

import jax
import jax.numpy as jnp
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
