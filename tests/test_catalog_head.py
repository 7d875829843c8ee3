"""The catalog head (ops/seq.py catalog_head, the kernel of ops/pallas_head.py)
against the parent's masked dense expression: the same largest logit, the same
row (the first on ties) and the same confidence over the view's first
`n_valid` rows, whatever the rows behind them hold; no block past them is
read. Interpreted on the CPU at small views; the last test compiles the kernel
for a described v5e at the four generating encoders' shapes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from oryx_tpu.ops.pallas_head import HEAD_BLOCK_ROWS, head_rows
from oryx_tpu.ops.seq import catalog_head

BLOCK = HEAD_BLOCK_ROWS  # a view of four blocks


def _dense(z, view, n_valid):
    """The parent's head, as it was before the kernel."""
    logits = jnp.dot(z, view.T, preferred_element_type=jnp.float32)
    logits = jnp.where(jnp.arange(view.shape[0])[None, :] < n_valid, logits, -jnp.inf)
    top = jnp.max(logits, axis=-1)
    arg = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return top, arg, jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))


def _operands(rows, r, dtype, seed=0, feat=128):
    rng = np.random.default_rng(seed)
    view = jnp.asarray(rng.standard_normal((rows, feat)), dtype)
    z = jnp.asarray(rng.standard_normal((r, feat)), dtype)
    return z, view


def _same(got, want):
    top, arg, conf = got
    np.testing.assert_array_equal(np.asarray(arg), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(top), np.asarray(want[0]), rtol=1e-5)
    assert arg.dtype == jnp.int32 and top.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(conf), np.asarray(want[2]), rtol=1e-4)


N_VALID = {"block_edge": 2 * BLOCK, "past_an_edge": 2 * BLOCK + 1, "mid_block": 2 * BLOCK + 57, "whole_view": 4 * BLOCK}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("r", [32, 128])
@pytest.mark.parametrize("behind", [64.0, np.nan], ids=["loud", "nan"])
@pytest.mark.parametrize("where", list(N_VALID))
def test_the_bounded_head_is_the_dense_head(where, behind, r, dtype):
    """Rows past `n_valid` hold values far above any real logit, or NaN:
    reading or selecting one would change the top, the row and the
    confidence."""
    n_valid = N_VALID[where]
    z, view = _operands(4 * BLOCK, r, dtype, seed=n_valid + r)
    want = _dense(z, view, n_valid)
    _same(catalog_head(z, view.at[n_valid:].set(behind), n_valid), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("r", [32, 128])
def test_an_equal_maximum_in_two_blocks_goes_to_the_first(r, dtype):
    """The same largest row in block 0 (lane 5), in the same lane of block 2,
    in block 3 (lane 9) and in block 1 (lane 100): jnp.argmax's first row."""
    z, view = _operands(4 * BLOCK, r, dtype, seed=3)
    best = (z[0] * 8).astype(dtype)  # scores far above any other row for query 0
    view = view.at[5].set(best).at[2 * BLOCK + 5].set(best).at[3 * BLOCK + 9].set(best)
    view = view.at[BLOCK + 100].set(best)
    got = catalog_head(z, view, 4 * BLOCK)
    _same(got, _dense(z, view, 4 * BLOCK))
    assert int(got[1][0]) == 5
    # past the first, the next one is the first
    got = catalog_head(z, view.at[5].set(0), 4 * BLOCK)
    assert int(got[1][0]) == BLOCK + 100


@pytest.mark.parametrize("rows", [64, 200, 2 * BLOCK + 300])
def test_a_view_that_is_not_whole_blocks(rows):
    """A view under one block, one under a lane tile's multiple, and one that
    ends in a partial block whose last rows hold the maximum."""
    z, view = _operands(rows, 8, jnp.float32, seed=rows)
    view = view.at[rows - 3].set(z[0] * 8)
    for n_valid in (1, rows - 2, rows):
        _same(catalog_head(z, view, n_valid), _dense(z, view, n_valid))


@pytest.mark.parametrize(
    "rows,n_valid,walked",
    [
        (196_608, 151_935, 152_576),   # sdar-30b-a3b-6l
        (229_376, 200_192, 200_704),   # trinity-large-5l
        (163_840, 129_280, 130_048),   # joyai-flash-5l
        (81_920, 65_536, 65_536),      # jamba2-3b
        (512, 40, 512),                # a view under a block: one block
        (3 * BLOCK + 40, 3 * BLOCK + 1, 3 * BLOCK + 40),  # the last block cut at the view's end
        (3 * BLOCK + 40, 10, BLOCK),
    ],
)
def test_rows_walked_and_skipped(rows, n_valid, walked):
    assert head_rows(rows, n_valid) == (walked, rows - walked)


# ---- the kernel at the published widths, compiled for a described v5e ------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "r,rows,feat",
    [(128, 196_608, 2_048), (32, 229_376, 3_072), (32, 163_840, 2_048), (32, 81_920, 2_560)],
    ids=["sdar", "trinity", "joyai", "jamba"],
)
def test_the_head_compiles_for_a_v5e(one_chip, r, rows, feat, monkeypatch):
    """One Mosaic kernel and no [R, rows] temporary: the program's scratch is
    the kernel's outputs, not logits."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def head(z, view, n_valid):
        with jax.named_scope("sdar.head"):
            return catalog_head(z, view, n_valid)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    try:
        compiled = jax.jit(head).lower(
            shape((r, feat), jnp.bfloat16), shape((rows, feat), jnp.bfloat16), shape((), jnp.int32)
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1 and "sdar.head" in text
    assert compiled.memory_analysis().temp_size_in_bytes < r * rows * 4 // 100
